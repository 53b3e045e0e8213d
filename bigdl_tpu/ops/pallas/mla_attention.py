"""Pallas TPU kernel: single-token (decode) attention over a LATENT cache.

Multi-head latent attention (DeepSeek-V2) caches, per position and
layer, the normed compressed KV `c_kv` (512 values) and ONE roped key
head `k_pe` (64) shared by all query heads. With the up-projection
absorbed into the query (`q_abs = q_nope W_uk^T`, a 512-vector per
head) a decode step is multi-query attention of H query heads over one
576-wide key row whose first 512 columns are also the value:

    s[h, j] = (q_abs[h] . c_kv[j] + q_pe[h] . k_pe[j]) * scale
    o[h]    = softmax_j(s[h, :]) @ c_kv            (W_uv is applied after)

No kernel for K/V planes computes that without reading every cached
byte twice (once as key, once as value). This one reads each block of
the latent plane ONCE and uses it for both products.

Shapes: `q_c` [B, H, C] and `q_pe` [B, H, R] (bf16); the latent STACK
`[L, B, C + R, S]` with the positions in the lanes (`ops/kvcache.py`:
576 rows of S positions tile without padding, a `[.., S, 576]` plane
does not) plus the layer index, a prefetched scalar that the BlockSpec
index map uses to address block `(layer, b, 0, s_block)` where it lies
(PR 26's pattern: no layer is copied out of the scan carry); `pos` [B]
int32, the query's own position (keys `j <= pos` are live).

Blocks past a slot's `pos` are skipped: their grid steps compute
nothing, and their index map names the last live block again, which the
pipeline does not fetch twice. At H = 128 the step sits at the chip's
ridge (2 x 128 x 1088 FLOP against 1,152 B a cached position: 242
FLOP/B; v5e 197 T / 819 G = 240).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
KERNEL_NAME = "mla_decode_attention"


def _s_block(s: int) -> int:
    """Positions a block holds: 512 where S allows (a [576, 512] bf16
    block is 590 KB, two in flight; scores [H, 512] f32), else the
    largest 128-multiple that divides S; 0 where none does."""
    for sb in (512, 256, 128):
        if s % sb == 0:
            return sb
    return 0


def mla_decode_supported(q_c, q_pe, latent) -> bool:
    """Geometry gate: bf16 latent, positions a multiple of 128, the
    compressed width a multiple of 128 (its rows are sliced off the
    block at a tile boundary)."""
    c, r = q_c.shape[-1], q_pe.shape[-1]
    return (latent.dtype == jnp.bfloat16 and latent.shape[-2] == c + r
            and c % 128 == 0 and r % 16 == 0
            and _s_block(latent.shape[-1]) > 0)


def sweep_init(sj, m_ref, l_ref, acc_ref):
    """Reset the online-softmax state at a slot's first block."""
    @pl.when(sj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)


def sweep_block(qc_ref, qpe_ref, lat_ref, m_ref, l_ref, acc_ref, live, *,
                scale, c):
    """One block of the latent plane through the online softmax: its
    scores against every head's query, masked by `live(shape)` (True
    where a column may be attended), and the same block again as the
    value. A block with no live column leaves unit weights behind that
    the first live block's correction (`exp(-1e30 - m)`, exactly 0)
    wipes out; every sweep of a slot that holds a position has one."""
    ckv = lat_ref[:c, :]                              # [C, sb]
    kpe = lat_ref[c:, :]                              # [R, sb]
    s_ = (jax.lax.dot_general(
        qc_ref[...], ckv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
        + jax.lax.dot_general(
            qpe_ref[...], kpe, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)) * scale
    s_ = jnp.where(live(s_.shape), s_, _NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s_, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s_ - m_new)
    l_ref[:] = jnp.broadcast_to(
        l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
        l_ref.shape)
    # the same block again, now as the value: [H, sb] x [C, sb]^T
    pv = jax.lax.dot_general(
        p.astype(jnp.bfloat16), ckv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)


def sweep_finish(sj, ns, out_ref, l_ref, acc_ref):
    @pl.when(sj == ns - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out_ref[...] = (acc_ref[:] / l).astype(out_ref.dtype)


def _kernel(layer_ref, pos_ref, qc_ref, qpe_ref, lat_ref, out_ref,
            m_ref, l_ref, acc_ref, *, scale, sb, ns, c):
    """One (slot, S-block) step of the online-softmax sweep."""
    del layer_ref                     # consumed by the index maps
    sj = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]
    sweep_init(sj, m_ref, l_ref, acc_ref)

    @pl.when(sj * sb <= pos)          # a block wholly past pos: nothing
    def _():
        def live(shape):
            col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            return col <= pos - sj * sb

        sweep_block(qc_ref, qpe_ref, lat_ref, m_ref, l_ref, acc_ref, live,
                    scale=scale, c=c)

    sweep_finish(sj, ns, out_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_decode_attention_pallas(
    q_c: jax.Array,        # [B, H, C] absorbed query (q_nope W_uk^T)
    q_pe: jax.Array,       # [B, H, R] roped query part
    latent: jax.Array,     # [L, B, C + R, S] bf16 stack
    pos: jax.Array,        # scalar or [B] int32: the query's position
    scale: float,
    layer=0,               # int32 scalar: which layer of the stack
    interpret: bool = False,
) -> jax.Array:
    """Softmax-weighted sum of the cached `c_kv` rows: [B, H, C] in
    `q_c.dtype` (still latent: the caller applies W_uv)."""
    b, h, c = q_c.shape
    r = q_pe.shape[-1]
    s = latent.shape[-1]
    sb = _s_block(s)
    if not sb or latent.shape[-2] != c + r:
        raise NotImplementedError(
            f"mla decode kernel: latent {latent.shape} against C={c} "
            f"R={r} is not a geometry it handles")
    ns = s // sb
    hp = -(-h // 16) * 16             # query heads ride the sublanes
    qc = q_c.astype(jnp.bfloat16)
    qpe = q_pe.astype(jnp.bfloat16)
    if hp != h:
        qc = jnp.pad(qc, ((0, 0), (0, hp - h), (0, 0)))
        qpe = jnp.pad(qpe, ((0, 0), (0, hp - h), (0, 0)))
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def lat_index(bi, sj, lyr_ref, pos_ref):
        # past the slot's last live block the same block is named again:
        # no new fetch
        return (lyr_ref[0], bi, 0, jnp.minimum(sj, pos_ref[bi] // sb))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, sb=sb, ns=ns, c=c),
        name=KERNEL_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, ns),
            in_specs=[
                pl.BlockSpec((None, hp, c), lambda bi, sj, *_: (bi, 0, 0)),
                pl.BlockSpec((None, hp, r), lambda bi, sj, *_: (bi, 0, 0)),
                pl.BlockSpec((None, None, c + r, sb), lat_index),
            ],
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
    )(lyr, posv, qc, qpe, latent)
    return out[:, :h, :]


def mla_decode_attention_xla(q_c, q_pe, latent_layer, pos, scale):
    """The same product in XLA ops on ONE layer `[B, C + R, S]` (a slice
    of the stack: the caller pays that copy): fallback and oracle."""
    c = q_c.shape[-1]
    s = latent_layer.shape[-1]
    ckv = latent_layer[:, :c, :].astype(jnp.bfloat16)
    kpe = latent_layer[:, c:, :].astype(jnp.bfloat16)
    scores = (jnp.einsum("bhc,bcs->bhs", q_c.astype(jnp.bfloat16), ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,brs->bhs", q_pe.astype(jnp.bfloat16), kpe,
                           preferred_element_type=jnp.float32)) * scale
    posv = jnp.asarray(pos, jnp.int32).reshape(-1, 1, 1)
    live = jnp.arange(s, dtype=jnp.int32)[None, None, :] <= posv
    probs = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhs,bcs->bhc", probs.astype(jnp.bfloat16), ckv,
                      preferred_element_type=jnp.float32).astype(q_c.dtype)


APPEND_NAME = "mla_latent_append"
_LANES = 128


def _append_kernel(layer_ref, pos_ref, new_ref, old_ref, out_ref, *, s):
    del layer_ref
    pos = pos_ref[pl.program_id(0)]
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    hit = (lane == pos % _LANES) & (pos < s)
    out_ref[...] = jnp.where(hit, new_ref[...], old_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def latent_append_pallas(stack: jax.Array,    # [L, B, C, S] bf16
                         layer, new: jax.Array,   # [B, C]
                         pos: jax.Array,          # [B] int32
                         interpret: bool = False) -> jax.Array:
    """Column `pos[b]` of slot b of layer `layer` set to `new[b]`, in
    place: each slot's 128-position block is read, one lane of it
    replaced and the block written back. XLA's scatter would do the same
    write after re-laying the WHOLE stack out with the positions out of
    the lanes, and back (two copies of the slab a layer: AOT compile for
    v5e, PERF.md PR 28). A `pos` past the end writes nothing."""
    l_, b, c, s = stack.shape
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    posv = jnp.asarray(pos, jnp.int32)
    wide = jnp.broadcast_to(new.astype(stack.dtype)[:, :, None],
                            (b, c, _LANES))

    def blk(bi, lyr_ref, pos_ref):
        return (lyr_ref[0], bi, 0,
                jnp.minimum(pos_ref[bi], s - 1) // _LANES)

    spec = pl.BlockSpec((None, None, c, _LANES), blk)
    return pl.pallas_call(
        functools.partial(_append_kernel, s=s),
        name=APPEND_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((None, c, _LANES),
                                   lambda bi, *_: (bi, 0, 0)), spec],
            out_specs=spec,
        ),
        out_shape=jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(lyr, posv, wide, stack)


_probe_cache: set = set()


def _kernel_compiles(b: int, h: int, c: int, r: int, s: int) -> bool:
    """Compile probe per geometry (contract in ops/probing.py: True, or
    `KernelProbeError` with the compiler's message)."""
    from bigdl_tpu.config import flags

    if flags().aot_target == "tpu":   # AOT lowering: the caller compiles
        return True
    from bigdl_tpu.ops.probing import probe_kernel

    def fn(qc, qpe, lat, p):
        return mla_decode_attention_pallas(qc, qpe, lat, p, (c + r) ** -0.5)

    return probe_kernel(
        KERNEL_NAME, _probe_cache, (h, c, r, s), fn,
        jax.ShapeDtypeStruct((1, h, c), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, h, r), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, 1, c + r, s), jnp.bfloat16),
        jax.ShapeDtypeStruct((1,), jnp.int32))


def mla_decode_attention(q_c, q_pe, latent, layer, pos, scale: float,
                         backend=None) -> jax.Array:
    """Decode attention over layer `layer` of the latent stack: the
    kernel on a TPU (probed once per geometry; a refusal raises and is
    counted in `bigdl_tpu_kernel_probe_total`), XLA ops where dispatch
    rules say so (sharded under GSPMD, an unsupported geometry, or no
    TPU), counted as `xla_by_rule` on a TPU."""
    from bigdl_tpu.config import flags, target_is_tpu, under_spmd

    be = backend or flags().attention_backend
    if be == "auto" and under_spmd(q_c, latent):
        be = "xla"
    on_tpu = target_is_tpu()
    if be in ("auto", "pallas") and mla_decode_supported(q_c, q_pe, latent):
        b, h, c = q_c.shape
        if be == "pallas" or on_tpu and _kernel_compiles(
                b, h, c, q_pe.shape[-1], latent.shape[-1]):
            return mla_decode_attention_pallas(
                q_c, q_pe, latent, pos, float(scale), layer=layer,
                interpret=not on_tpu)
    if (backend or flags().attention_backend) == "auto" and on_tpu:
        from bigdl_tpu.ops.probing import record_dispatch_rule

        record_dispatch_rule(KERNEL_NAME)
    one = jax.lax.dynamic_index_in_dim(latent, layer, 0, keepdims=False)
    return mla_decode_attention_xla(q_c, q_pe, one, pos, scale)
