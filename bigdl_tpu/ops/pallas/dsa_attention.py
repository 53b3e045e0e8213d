"""Pallas TPU kernels of learned sparse attention (DeepSeek-V3.2's
lightning indexer) over a latent cache, and of latent attention over a
window kept in a ring. Decode only: one query row a slot, or (index
score and sparse sweep) the R rows of a speculative verify step folded
beside the heads, over ONE fetch of the slot's keys.

`dsa_index_score`: the indexer's score of every cached position,

    I[b, s] = sum_j w[b, j] * relu(q_I[b, j] . k_I[b, :, s])      (s <= pos)

over the index-key plane `[L, B, index_dim, S]` (positions in the lanes,
as the latent plane keeps them): one block of keys is read once, all
index heads' products with it are taken on the MXU, and the heads are
reduced in VMEM; `-inf` past `pos`. 256 B a cached position.

`dsa_select`: `ops/dsa.select_topk_mask` for one row a slot, in VMEM:
the row of scores is loaded once and the two bisections (31 counts on
the order-preserving integer keys of the scores, log2 S on the position
of the ties) run on it there; XLA's form is one fusion and one pass
over HBM a count.

`sparse_mla_decode`: `mla_decode_attention`'s sweep with a per-slot
mask of the SELECTED positions (`ops/dsa.select_topk_mask`). Exact; it
reads every block up to `pos` although only the selected columns count
(positions run along the lanes, so gathering 2048 of them is not free):
the roofline of the benchmark counts the selected rows, and a later
gather form has that number to move.

`window_mla_decode`: the same sweep over a RING `[L, B, C + R, ring]`
(`ops/kvcache.py`): column j holds position `pos - ((pos - j) mod
ring)`, live while that distance is under the window and the position
is not negative.

The online-softmax block is `mla_attention.sweep_block`, shared with the
dense sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas.mla_attention import (_s_block, sweep_block,
                                                sweep_finish, sweep_init)

INDEX_NAME = "dsa_index_score"
SELECT_NAME = "dsa_select"
SPARSE_NAME = "sparse_mla_decode"
WINDOW_NAME = "window_mla_decode"


def _index_block(s: int) -> int:
    """Positions an index-score block holds (a [128, 1024] bf16 block is
    256 KB; the [Hi, 1024] float32 products 256 KB at 64 heads)."""
    for sb in (1024, 512, 256, 128):
        if s % sb == 0:
            return sb
    return 0


def index_score_supported(q_i, index) -> bool:
    return (index.dtype == jnp.bfloat16 and index.shape[-2] == q_i.shape[-1]
            and q_i.shape[-1] % 16 == 0 and _index_block(index.shape[-1]) > 0)


def _index_kernel(layer_ref, pos_ref, q_ref, w_ref, k_ref, out_ref, *, sb):
    del layer_ref
    sj = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(sj * sb <= pos)
    def _():
        r = jnp.maximum(jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), 0.0)         # [Hi, sb]
        tot = jnp.sum(r * w_ref[...], axis=0, keepdims=True)  # [1, sb]
        col = jax.lax.broadcasted_iota(jnp.int32, tot.shape, 1)
        out_ref[...] = jnp.where(col <= pos - sj * sb, tot, -jnp.inf)

    @pl.when(sj * sb > pos)
    def _():
        out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, out_ref.dtype)


def _index_kernel_rows(layer_ref, pos_ref, q_ref, w_ref, k_ref, out_ref, *,
                       sb, rows, hp):
    """`_index_kernel` for `rows` query rows a slot folded beside the
    heads (`[rows * hp, Di]`): the block of keys is fetched once, row i
    is live up to `pos + i`."""
    del layer_ref
    sj = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(sj * sb <= pos + (rows - 1))
    def _():
        r = jnp.maximum(jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), 0.0)     # [rows * hp, sb]
        rw = r * w_ref[...]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, sb), 1)
        for i in range(rows):
            tot = jnp.sum(rw[i * hp:(i + 1) * hp], axis=0, keepdims=True)
            out_ref[i:i + 1, :] = jnp.where(col <= pos + i - sj * sb, tot,
                                            -jnp.inf)

    @pl.when(sj * sb > pos + (rows - 1))
    def _():
        out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dsa_index_score_pallas(
    q_i: jax.Array,        # [B, Hi, Di] index queries (roped)
    w: jax.Array,          # [B, Hi] float32 head weights (scaled)
    index: jax.Array,      # [L, B, Di, S] bf16 index-key stack
    pos: jax.Array,        # scalar or [B] int32: the query's position
    layer=0,
    interpret: bool = False,
) -> jax.Array:
    """`[B, S]` float32 index scores, `-inf` past `pos`. With `q_i` `[B,
    R, Hi, Di]` and `w` `[B, R, Hi]` (R rows a slot at positions `pos ..
    pos + R - 1`, one fetch of the slot's keys for all): `[B, R, S]`."""
    if q_i.ndim == 4:
        return _index_score_rows(q_i, w, index, pos, layer, interpret)
    b, hi, di = q_i.shape
    s = index.shape[-1]
    sb = _index_block(s)
    if not sb or index.shape[-2] != di:
        raise NotImplementedError(
            f"index score kernel: index plane {index.shape} against "
            f"Di={di} is not a geometry it handles")
    hp = -(-hi // 16) * 16
    q = q_i.astype(jnp.bfloat16)
    wf = w.astype(jnp.float32)[..., None]                     # [B, Hi, 1]
    if hp != hi:
        q = jnp.pad(q, ((0, 0), (0, hp - hi), (0, 0)))
        wf = jnp.pad(wf, ((0, 0), (0, hp - hi), (0, 0)))
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def k_index(bi, sj, lyr_ref, pos_ref):
        return (lyr_ref[0], bi, 0, jnp.minimum(sj, pos_ref[bi] // sb))

    out = pl.pallas_call(
        functools.partial(_index_kernel, sb=sb),
        name=INDEX_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, s // sb),
            in_specs=[
                pl.BlockSpec((None, hp, di), lambda bi, sj, *_: (bi, 0, 0)),
                pl.BlockSpec((None, hp, 1), lambda bi, sj, *_: (bi, 0, 0)),
                pl.BlockSpec((None, None, di, sb), k_index),
            ],
            out_specs=pl.BlockSpec((None, 1, sb),
                                   lambda bi, sj, *_: (bi, 0, sj)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lyr, posv, q, wf, index)
    return out[:, 0, :]


def _index_score_rows(q_i, w, index, pos, layer, interpret):
    b, rows, hi, di = q_i.shape
    s = index.shape[-1]
    sb = _index_block(s)
    if not sb or index.shape[-2] != di:
        raise NotImplementedError(
            f"index score kernel: index plane {index.shape} against "
            f"Di={di} is not a geometry it handles")
    hp = -(-hi // 16) * 16
    q = jnp.pad(q_i.astype(jnp.bfloat16),
                ((0, 0), (0, 0), (0, hp - hi), (0, 0)))
    wf = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, 0), (0, hp - hi)))
    q = q.reshape(b, rows * hp, di)
    wf = wf.reshape(b, rows * hp, 1)
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def k_index(bi, sj, lyr_ref, pos_ref):
        last = jnp.minimum(pos_ref[bi] + (rows - 1), s - 1) // sb
        return (lyr_ref[0], bi, 0, jnp.minimum(sj, last))

    return pl.pallas_call(
        functools.partial(_index_kernel_rows, sb=sb, rows=rows, hp=hp),
        name=INDEX_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, s // sb),
            in_specs=[
                pl.BlockSpec((None, rows * hp, di),
                             lambda bi, sj, *_: (bi, 0, 0)),
                pl.BlockSpec((None, rows * hp, 1),
                             lambda bi, sj, *_: (bi, 0, 0)),
                pl.BlockSpec((None, None, di, sb), k_index),
            ],
            out_specs=pl.BlockSpec((None, rows, sb),
                                   lambda bi, sj, *_: (bi, 0, sj)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lyr, posv, q, wf, index)


def _select_kernel(s_ref, out_ref, *, k, nbits):
    x = s_ref[...]                                    # [1, S] float32
    i = pltpu.bitcast(x, jnp.int32)
    key = i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))     # ordered as the floats

    def count(m):                                     # [1, 1], exact
        return jnp.sum(m.astype(jnp.float32), axis=1, keepdims=True)

    kf = jnp.float32(k)
    base = jnp.where(count(key >= 0) >= kf, jnp.int32(0),
                     jnp.int32(-2 ** 31))

    def kth(b, t):
        cand = t + (jnp.int32(1) << (jnp.int32(30) - b))
        return jnp.where(count(key >= cand) >= kf, cand, t)

    t = jax.lax.fori_loop(0, 31, kth, base)           # the k-th largest key
    above = key > t
    tie = key == t
    need = kf - count(above)
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

    def last(b, q):
        cand = q | (jnp.int32(1) << (jnp.int32(nbits - 1) - b))
        return jnp.where(count(tie & (idx < cand)) < need, cand, q)

    q = jax.lax.fori_loop(0, nbits, last, jnp.zeros_like(t))
    out_ref[...] = ((above | (tie & (idx <= q)))
                    & (x > -jnp.inf)).astype(jnp.int32)


def select_supported(scores) -> bool:
    return scores.dtype == jnp.float32 and scores.shape[-1] % 128 == 0


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def dsa_select_pallas(scores: jax.Array, k: int,
                      interpret: bool = False) -> jax.Array:
    """`[B, S]` float32 scores (`-inf`: no candidate) -> `[B, S]` int32,
    1 at the `k` largest of each row (ties to the lower position; every
    candidate where there are no more than `k`)."""
    b, s = scores.shape
    spec = pl.BlockSpec((None, 1, s), lambda bi: (bi, 0, 0))
    out = pl.pallas_call(
        functools.partial(_select_kernel, k=k,
                          nbits=max(1, s.bit_length())),
        name=SELECT_NAME,
        grid=(b,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, s), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(scores[:, None, :])
    return out[:, 0, :]


def _sparse_kernel(layer_ref, pos_ref, qc_ref, qpe_ref, sel_ref, lat_ref,
                   out_ref, m_ref, l_ref, acc_ref, *, scale, sb, ns, c):
    del layer_ref
    sj = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]
    sweep_init(sj, m_ref, l_ref, acc_ref)

    @pl.when(sj * sb <= pos)
    def _():
        def live(shape):
            col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            return (col <= pos - sj * sb) & (sel_ref[...] != 0)

        sweep_block(qc_ref, qpe_ref, lat_ref, m_ref, l_ref, acc_ref, live,
                    scale=scale, c=c)

    sweep_finish(sj, ns, out_ref, l_ref, acc_ref)


def _sparse_kernel_rows(layer_ref, pos_ref, qc_ref, qpe_ref, sel_ref,
                        lat_ref, out_ref, m_ref, l_ref, acc_ref, *, scale,
                        sb, ns, c, rows, hp):
    """`_sparse_kernel` for `rows` query rows a slot folded beside the
    heads: one fetch of a latent block serves every row; row i (heads
    `i * hp ..`) is live up to `pos + i` under ITS selection `sel[i]`."""
    del layer_ref
    sj = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]
    sweep_init(sj, m_ref, l_ref, acc_ref)

    @pl.when(sj * sb <= pos + (rows - 1))
    def _():
        def live(shape):
            col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            head = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            out = None
            for i in range(rows):
                mine = ((head >= i * hp) & (head < (i + 1) * hp)
                        & (col <= pos + i - sj * sb)
                        & (sel_ref[i:i + 1, :] != 0))
                out = mine if out is None else out | mine
            return out

        sweep_block(qc_ref, qpe_ref, lat_ref, m_ref, l_ref, acc_ref, live,
                    scale=scale, c=c)

    sweep_finish(sj, ns, out_ref, l_ref, acc_ref)


def _window_kernel(layer_ref, pos_ref, qc_ref, qpe_ref, lat_ref, out_ref,
                   m_ref, l_ref, acc_ref, *, scale, sb, ns, c, window):
    del layer_ref
    sj = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]
    ring = sb * ns
    sweep_init(sj, m_ref, l_ref, acc_ref)

    # an unwrapped ring (or a plane in position order, which is a ring
    # of its own length) holds nothing past pos and nothing the window
    # reaches before pos - window + 1
    @pl.when((sj * sb <= pos)
             & ((pos >= ring) | ((sj + 1) * sb > pos - window + 1)))
    def _():
        def live(shape):
            col = sj * sb + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            d = jax.lax.rem(pos, ring) - col
            d = jnp.where(d < 0, d + ring, d)     # how far behind pos
            return (d < window) & (d <= pos)

        sweep_block(qc_ref, qpe_ref, lat_ref, m_ref, l_ref, acc_ref, live,
                    scale=scale, c=c)

    sweep_finish(sj, ns, out_ref, l_ref, acc_ref)


def _sweep_call(kernel, name, q_c, q_pe, latent, pos, layer, sel, last_block,
                interpret, first_block=lambda p, sb: 0):
    """The (slot, block) sweep over `latent` `[L, B, C + R, S]`, with a
    `[B, S]` int32 mask plane as a fourth operand where `sel` is given.
    `first_block(pos, sb)` / `last_block(pos, sb)`: the blocks that hold
    anything for a slot; the others name the nearest of them again and
    are not fetched."""
    b, h, c = q_c.shape
    r = q_pe.shape[-1]
    s = latent.shape[-1]
    sb = _s_block(s)
    if not sb or latent.shape[-2] != c + r:
        raise NotImplementedError(
            f"{name} kernel: latent {latent.shape} against C={c} R={r} is "
            f"not a geometry it handles")
    ns = s // sb
    hp = -(-h // 16) * 16
    qc = q_c.astype(jnp.bfloat16)
    qpe = q_pe.astype(jnp.bfloat16)
    if hp != h:
        qc = jnp.pad(qc, ((0, 0), (0, hp - h), (0, 0)))
        qpe = jnp.pad(qpe, ((0, 0), (0, hp - h), (0, 0)))
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def blk(bi, sj, pos_ref):
        p = pos_ref[bi]
        return jnp.maximum(jnp.minimum(sj, last_block(p, sb)),
                           first_block(p, sb))

    in_specs = [
        pl.BlockSpec((None, hp, c), lambda bi, sj, *_: (bi, 0, 0)),
        pl.BlockSpec((None, hp, r), lambda bi, sj, *_: (bi, 0, 0)),
    ]
    operands = [qc, qpe]
    if sel is not None:
        in_specs.append(pl.BlockSpec(
            (None, 1, sb),
            lambda bi, sj, lyr_ref, pos_ref: (bi, 0, blk(bi, sj, pos_ref))))
        operands.append(sel.astype(jnp.int32)[:, None, :])
    in_specs.append(pl.BlockSpec(
        (None, None, c + r, sb),
        lambda bi, sj, lyr_ref, pos_ref: (lyr_ref[0], bi, 0,
                                          blk(bi, sj, pos_ref))))
    operands.append(latent)
    out = pl.pallas_call(
        functools.partial(kernel, sb=sb, ns=ns, c=c),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, ns),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, hp, c),
                                   lambda bi, sj, *_: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hp, 128), jnp.float32),
                pltpu.VMEM((hp, 128), jnp.float32),
                pltpu.VMEM((hp, c), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hp, c), q_c.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lyr, posv, *operands)
    return out[:, :h, :]


def _sparse_rows(q_c, q_pe, latent, pos, sel, scale, layer, interpret):
    """The sparse sweep for `q_c` `[B, R, H, C]`, `q_pe` `[B, R, H, R_]`
    and `sel` `[B, R, S]`: `[B, R, H, C]`."""
    b, rows, h, c = q_c.shape
    r = q_pe.shape[-1]
    s = latent.shape[-1]
    sb = _s_block(s)
    if not sb or latent.shape[-2] != c + r:
        raise NotImplementedError(
            f"{SPARSE_NAME} kernel: latent {latent.shape} against C={c} "
            f"R={r} is not a geometry it handles")
    ns = s // sb
    hp = -(-h // 16) * 16
    pad = ((0, 0), (0, 0), (0, hp - h), (0, 0))
    qc = jnp.pad(q_c.astype(jnp.bfloat16), pad).reshape(b, rows * hp, c)
    qpe = jnp.pad(q_pe.astype(jnp.bfloat16), pad).reshape(b, rows * hp, r)
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def blk(bi, sj, pos_ref):
        return jnp.minimum(
            sj, jnp.minimum(pos_ref[bi] + (rows - 1), s - 1) // sb)

    out = pl.pallas_call(
        functools.partial(_sparse_kernel_rows, scale=scale, sb=sb, ns=ns,
                          c=c, rows=rows, hp=hp),
        name=SPARSE_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, ns),
            in_specs=[
                pl.BlockSpec((None, rows * hp, c),
                             lambda bi, sj, *_: (bi, 0, 0)),
                pl.BlockSpec((None, rows * hp, r),
                             lambda bi, sj, *_: (bi, 0, 0)),
                pl.BlockSpec((None, rows, sb),
                             lambda bi, sj, lyr_ref, pos_ref: (
                                 bi, 0, blk(bi, sj, pos_ref))),
                pl.BlockSpec((None, None, c + r, sb),
                             lambda bi, sj, lyr_ref, pos_ref: (
                                 lyr_ref[0], bi, 0, blk(bi, sj, pos_ref))),
            ],
            out_specs=pl.BlockSpec((None, rows * hp, c),
                                   lambda bi, sj, *_: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows * hp, 128), jnp.float32),
                pltpu.VMEM((rows * hp, 128), jnp.float32),
                pltpu.VMEM((rows * hp, c), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows * hp, c), q_c.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lyr, posv, qc, qpe, sel.astype(jnp.int32), latent)
    return out.reshape(b, rows, hp, c)[:, :, :h, :]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def sparse_mla_decode_pallas(q_c, q_pe, latent, pos, sel, scale: float,
                             layer=0, interpret: bool = False) -> jax.Array:
    """`mla_decode_attention_pallas` over the positions `sel` `[B, S]`
    marks (nonzero) among those up to `pos`. With `q_c` `[B, R, H, C]`
    (R rows a slot at `pos .. pos + R - 1`, each with its own `sel[:,
    i]`): one fetch of a slot's latent blocks for all its rows."""
    if q_c.ndim == 4:
        return _sparse_rows(q_c, q_pe, latent, pos, sel, scale, layer,
                            interpret)
    return _sweep_call(
        functools.partial(_sparse_kernel, scale=scale), SPARSE_NAME,
        q_c, q_pe, latent, pos, layer, sel,
        lambda p, sb: p // sb, interpret)


@functools.partial(jax.jit,
                   static_argnames=("scale", "window", "interpret"))
def window_mla_decode_pallas(q_c, q_pe, ring_stack, pos, scale: float,
                             window: int, layer=0,
                             interpret: bool = False) -> jax.Array:
    """Latent decode attention over the last `window` positions (the
    query's own counted) kept in the ring stack `[L, B, C + R, ring]`."""
    ring = ring_stack.shape[-1]
    if window > ring:
        raise ValueError(f"window {window} does not fit a ring of {ring}")
    return _sweep_call(
        functools.partial(_window_kernel, scale=scale, window=window),
        WINDOW_NAME, q_c, q_pe, ring_stack, pos, layer, None,
        lambda p, sb: jnp.minimum(p, ring - 1) // sb, interpret,
        first_block=lambda p, sb: jnp.where(
            p >= ring, 0, jnp.maximum(p - window + 1, 0) // sb))
