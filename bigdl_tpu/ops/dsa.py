"""Learned sparse attention over a latent cache (DeepSeek-V3.2's
lightning indexer, as `models/dots3_note.py` runs it) and latent
attention over a ring window: the exact selection, the XLA forms, and
the dispatch to the decode kernels of `ops/pallas/dsa_attention.py` and
to the chunk kernel of `ops/pallas/mla_chunk_attention.py`.

A full-attention layer keeps, beside its latent rows, one index key of
`index_dim` values a position. A query scores every cached position

    I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])            (s <= t)

and attends only the `topk` positions of largest score (all of them
while there are no more than `topk`; ties to the lower position, which
is `lax.top_k`'s rule).

`select_topk_mask` is that selection as a MASK, exact and without a
sort: the k-th largest score is found by bisection on the bits of the
scores' order-preserving integer keys (32 counts; `kth_largest` is that
search alone, which the engine's sampler takes its top-k threshold from,
on 16-bit keys where the logits are bfloat16), the ties at that
score are taken from the lowest position up by a second bisection on
the position (log2 S counts). `jax.lax.approx_max_k` is not this
selection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.ops.pallas import dsa_attention as kernels
from bigdl_tpu.ops.pallas import mla_chunk_attention as chunk_kernel


def _kth_key(x: jax.Array, k):
    """`(key, t)`: unsigned integer keys in the order of the floats `x`
    `[..., V]` (as wide as x's own dtype), and `t` `[..., 1]` the `k`-th
    largest key of each row, counted with multiplicity. `k` an int or an
    int32 array `[...]`, held to `1 .. V`. No sort: bisection on the
    key's bits from the top, one count a bit, unrolled (16 for
    bfloat16, 32 for float32)."""
    nbits = x.dtype.itemsize * 8
    uint = {16: jnp.uint16, 32: jnp.uint32}[nbits]
    bits = lax.bitcast_convert_type(x, uint)
    sign = uint(1 << (nbits - 1))
    key = jnp.where(bits >= sign, ~bits, bits | sign)
    k = jnp.clip(jnp.asarray(k, jnp.int32), 1, x.shape[-1])[..., None]
    t = jnp.zeros(x.shape[:-1] + (1,), uint)
    for i in range(nbits - 1, -1, -1):
        cand = t | uint(1 << i)
        n = jnp.sum(key >= cand, axis=-1, keepdims=True, dtype=jnp.int32)
        t = jnp.where(n >= k, cand, t)
    return key, t


def kth_largest(x: jax.Array, k) -> jax.Array:
    """The `k`-th largest VALUE of each row of `x` `[..., V]`, in x's own
    dtype and counted with multiplicity: entry `k - 1` of the row sorted
    downwards, found with no sort (`_kth_key`). `k` an int or an int32
    array `[...]`, held to `1 .. V`."""
    _, t = _kth_key(x, k)
    sign = t.dtype.type(1 << (x.dtype.itemsize * 8 - 1))
    return lax.bitcast_convert_type(
        jnp.where(t >= sign, t ^ sign, ~t), x.dtype)[..., 0]


def select_topk_mask(scores: jax.Array, k: int) -> jax.Array:
    """`[..., S]` float32 scores (`-inf`: not a candidate) -> bool mask
    of the `k` largest of each row, ties to the lower position; every
    candidate where a row has no more than `k`."""
    s = scores.shape[-1]
    key, t = _kth_key(scores.astype(jnp.float32), k)
    lead = scores.shape[:-1] + (1,)

    def count(m):
        return jnp.sum(m, axis=-1, keepdims=True, dtype=jnp.int32)

    above = key > t
    tie = key == t
    need = k - count(above)                      # >= 1: t is the k-th
    idx = lax.broadcasted_iota(jnp.int32, scores.shape, scores.ndim - 1)
    nbits = max(1, int(s).bit_length())

    def last(i, q):
        cand = q | (jnp.int32(1) << (nbits - 1 - i))
        return jnp.where(count(tie & (idx < cand)) < need, cand, q)

    # the largest q with fewer than `need` ties below it: ties at
    # positions <= q are the `need` lowest
    q = lax.fori_loop(0, nbits, last, jnp.zeros(lead, jnp.int32))
    return (above | (tie & (idx <= q))) & (scores > -jnp.inf)


def index_scores_xla(q_i, w, k_i, pos, head_tile: int = 8):
    """The index scores in XLA ops. `q_i` `[B, T, Hi, Di]`, `w` `[B, T,
    Hi]` float32, `k_i` `[B, Di, S]`, `pos` `[B]` (row t of slot b is at
    position `pos[b] + t`): `[B, T, S]` float32, `-inf` past a row's own
    position. The per-head products are reduced over heads a tile of
    heads at a time, so `[Hi, T, S]` never exists."""
    b, t, hi, di = q_i.shape
    s = k_i.shape[-1]
    g = head_tile if hi % head_tile == 0 else hi
    qg = jnp.moveaxis(q_i.reshape(b, t, hi // g, g, di), 2, 0)
    wg = jnp.moveaxis(w.astype(jnp.float32).reshape(b, t, hi // g, g), 2, 0)
    from bigdl_tpu.config import target_is_tpu

    kk = k_i if target_is_tpu() else k_i.astype(jnp.float32)

    def tile(acc, xs):
        q, ww = xs                                 # [B, T, g, Di], [B, T, g]
        if not target_is_tpu():
            q = q.astype(jnp.float32)
        r = jnp.maximum(jnp.einsum("btgd,bds->btgs", q, kk,
                                   preferred_element_type=jnp.float32), 0.0)
        return acc + jnp.sum(r * ww[..., None], axis=2), None

    tot, _ = lax.scan(tile, jnp.zeros((b, t, s), jnp.float32), (qg, wg))
    at = (jnp.asarray(pos, jnp.int32).reshape(-1, 1)
          + jnp.arange(t, dtype=jnp.int32)[None, :])            # [B, T]
    live = jnp.arange(s, dtype=jnp.int32)[None, None, :] <= at[..., None]
    return jnp.where(live, tot, -jnp.inf)


def masked_mla_decode_xla(q_c, q_pe, latent_layer, live, scale):
    """Absorbed latent decode attention over the columns `live` `[B, S]`
    marks, on ONE layer `[B, C + R, S]`: fallback and oracle of the
    sparse and the window kernel."""
    if q_c.ndim == 4:       # R rows a slot, each with its own `live`
        return jax.vmap(
            lambda qc, qp, lv: masked_mla_decode_xla(qc, qp, latent_layer,
                                                     lv, scale),
            in_axes=1, out_axes=1)(q_c, q_pe, live)
    c = q_c.shape[-1]
    ckv = latent_layer[:, :c, :].astype(jnp.bfloat16)
    kpe = latent_layer[:, c:, :].astype(jnp.bfloat16)
    scores = (jnp.einsum("bhc,bcs->bhs", q_c.astype(jnp.bfloat16), ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,brs->bhs", q_pe.astype(jnp.bfloat16), kpe,
                           preferred_element_type=jnp.float32)) * scale
    probs = jax.nn.softmax(jnp.where(live[:, None, :], scores, -jnp.inf),
                           axis=-1)
    return jnp.einsum("bhs,bcs->bhc", probs.astype(jnp.bfloat16), ckv,
                      preferred_element_type=jnp.float32).astype(q_c.dtype)


def ring_live(pos, ring: int, window: int):
    """`[B, ring]` bool: the ring columns a query at `pos` `[B]` attends
    (column j holds the position `(pos - j) mod ring` behind it)."""
    posv = jnp.asarray(pos, jnp.int32).reshape(-1, 1)
    d = jnp.mod(posv - jnp.arange(ring, dtype=jnp.int32)[None, :], ring)
    return (d < window) & (d <= posv)


_probe_cache: set = set()


def _kernel_wanted(name: str, supported: bool, key, probe, backend, *arrays):
    """`mla_decode_attention`'s dispatch rule for kernel `name`: True
    where the kernel runs (probed once per geometry on a TPU, interpreted
    where `backend` forces it elsewhere), False where XLA ops do (counted
    as `xla_by_rule` on a TPU)."""
    from bigdl_tpu.config import flags, target_is_tpu, under_spmd

    be = backend or flags().attention_backend
    if be == "auto" and under_spmd(*arrays):
        be = "xla"
    on_tpu = target_is_tpu()
    if be in ("auto", "pallas") and supported:
        if be == "pallas":
            return True
        if on_tpu:
            if flags().aot_target == "tpu":
                return True
            from bigdl_tpu.ops.probing import probe_kernel

            fn, structs = probe()
            return probe_kernel(name, _probe_cache, (name,) + key, fn,
                                *structs)
    if (backend or flags().attention_backend) == "auto" and on_tpu:
        from bigdl_tpu.ops.probing import record_dispatch_rule

        record_dispatch_rule(name)
    return False


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def dsa_index_scores_decode(q_i, w, index, layer, pos, backend=None):
    """Index scores of one decoded row a slot over layer `layer` of the
    index-key stack `[L, B, Di, S]`: `[B, S]` float32. `q_i` `[B, R, Hi,
    Di]` and `w` `[B, R, Hi]`: the R rows of a verify step at positions
    `pos .. pos + R - 1`, `[B, R, S]`."""
    from bigdl_tpu.config import target_is_tpu

    rows = q_i.shape[1] if q_i.ndim == 4 else 0
    b, hi, di = q_i.shape[0], q_i.shape[-2], q_i.shape[-1]
    s = index.shape[-1]
    lead = (1, rows) if rows else (1,)

    def probe():
        return (lambda q, ww, ix, p: kernels.dsa_index_score_pallas(
            q, ww, ix, p),
            (_sds(lead + (hi, di)), _sds(lead + (hi,), jnp.float32),
             _sds((1, 1, di, s)), _sds((1,), jnp.int32)))

    if _kernel_wanted(kernels.INDEX_NAME,
                      kernels.index_score_supported(q_i, index),
                      (hi, di, s) + ((rows,) if rows else ()), probe,
                      backend, q_i, index):
        return kernels.dsa_index_score_pallas(
            q_i, w, index, pos, layer=layer, interpret=not target_is_tpu())
    one = lax.dynamic_index_in_dim(index, layer, 0, keepdims=False)
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    if rows:
        return index_scores_xla(q_i, w, one, posv)
    return index_scores_xla(q_i[:, None], w[:, None], one, posv)[:, 0]


def dsa_select_decode(scores, k: int, backend=None):
    """`select_topk_mask` of one row a slot (`[B, S]` scores): the
    in-VMEM kernel on a TPU, the XLA form elsewhere. Nonzero / True at
    the selected positions."""
    from bigdl_tpu.config import target_is_tpu

    if scores.ndim == 3:    # R rows a slot: each row is a selection
        flat = dsa_select_decode(scores.reshape(-1, scores.shape[-1]), k,
                                 backend)
        return flat.reshape(scores.shape)
    s = scores.shape[-1]

    def probe():
        return (lambda sc: kernels.dsa_select_pallas(sc, k),
                (_sds((1, s), jnp.float32),))

    if _kernel_wanted(kernels.SELECT_NAME, kernels.select_supported(scores),
                      (s, k), probe, backend, scores):
        return kernels.dsa_select_pallas(scores, int(k),
                                         interpret=not target_is_tpu())
    return select_topk_mask(scores, k)


def _sweep_supported(q_c, q_pe, latent) -> bool:
    from bigdl_tpu.ops.pallas.mla_attention import mla_decode_supported

    return mla_decode_supported(q_c, q_pe, latent)


def sparse_mla_decode(q_c, q_pe, latent, layer, pos, sel, scale: float,
                      backend=None):
    """Absorbed decode attention over the selected positions `sel`
    `[B, S]` of layer `layer` of the latent stack. `q_c` `[B, R, H, C]`,
    `q_pe` `[B, R, H, R_]` and `sel` `[B, R, S]`: the R rows of a verify
    step at positions `pos .. pos + R - 1`."""
    from bigdl_tpu.config import target_is_tpu

    rows = q_c.shape[1] if q_c.ndim == 4 else 0
    h, c = q_c.shape[-2:]
    r, s = q_pe.shape[-1], latent.shape[-1]
    lead = (1, rows) if rows else (1,)

    def probe():
        return (lambda qc, qp, lat, p, m: kernels.sparse_mla_decode_pallas(
            qc, qp, lat, p, m, (c + r) ** -0.5),
            (_sds(lead + (h, c)), _sds(lead + (h, r)),
             _sds((1, 1, c + r, s)), _sds((1,), jnp.int32),
             _sds(lead + (s,), jnp.int32)))

    if _kernel_wanted(kernels.SPARSE_NAME, _sweep_supported(q_c, q_pe, latent),
                      (h, c, r, s) + ((rows,) if rows else ()), probe,
                      backend, q_c, latent):
        return kernels.sparse_mla_decode_pallas(
            q_c, q_pe, latent, pos, sel, float(scale), layer=layer,
            interpret=not target_is_tpu())
    one = lax.dynamic_index_in_dim(latent, layer, 0, keepdims=False)
    return masked_mla_decode_xla(q_c, q_pe, one, sel, scale)


def window_mla_decode(q_c, q_pe, ring_stack, layer, pos, scale: float,
                      window: int, backend=None):
    """Absorbed decode attention over the last `window` positions in
    layer `layer` of the ring stack `[L, B, C + R, ring]`."""
    from bigdl_tpu.config import target_is_tpu

    b, h, c = q_c.shape
    r, ring = q_pe.shape[-1], ring_stack.shape[-1]

    def probe():
        return (lambda qc, qp, lat, p: kernels.window_mla_decode_pallas(
            qc, qp, lat, p, (c + r) ** -0.5, window),
            (_sds((1, h, c)), _sds((1, h, r)), _sds((1, 1, c + r, ring)),
             _sds((1,), jnp.int32)))

    if _kernel_wanted(kernels.WINDOW_NAME,
                      _sweep_supported(q_c, q_pe, ring_stack),
                      (h, c, r, ring, window), probe, backend, q_c,
                      ring_stack):
        return kernels.window_mla_decode_pallas(
            q_c, q_pe, ring_stack, pos, float(scale), int(window),
            layer=layer, interpret=not target_is_tpu())
    one = lax.dynamic_index_in_dim(ring_stack, layer, 0, keepdims=False)
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    return masked_mla_decode_xla(q_c, q_pe, one,
                                 ring_live(posv, ring, window), scale)


def mla_chunk_attention(q_nope, q_pe, latent, layer, pos, sel, w_uk, w_uv,
                        scale: float, xla, backend=None):
    """Expanded attention of a chunk of rows `q_nope` `[B, T, H, nope]`,
    `q_pe` `[B, T, H, R]` at positions `pos ..` `[B]` over layer `layer`
    of the latent stack, under the causal bound and the selection `sel`
    `[B, T, S]` (None: the bound alone): `[B, T, H, v]` float32. The
    kernel where the rule wants it; elsewhere `xla()`, the caller's
    sweep in XLA ops (it owns the absorbed form too)."""
    from bigdl_tpu.config import target_is_tpu

    t, h, nope = q_nope.shape[1:]
    r, s = q_pe.shape[-1], latent.shape[-1]
    c, vd = w_uv.shape[-2:]
    masked = sel is not None

    def probe():
        return (lambda *a: chunk_kernel.mla_chunk_attention_pallas(
            *a, (nope + r) ** -0.5),
            (_sds((1, t, h, nope)), _sds((1, t, h, r)),
             _sds((1, 1, c + r, s)), _sds((1,), jnp.int32),
             _sds((1, t, s), jnp.bool_) if masked else None,
             _sds((h, nope, c)), _sds((h, c, vd))))

    if _kernel_wanted(chunk_kernel.NAME,
                      chunk_kernel.mla_chunk_supported(q_nope, q_pe, latent,
                                                       w_uk, w_uv),
                      (t, h, nope, r, c, vd, s, masked), probe, backend,
                      q_nope, latent):
        return chunk_kernel.mla_chunk_attention_pallas(
            q_nope, q_pe, latent, pos, sel, w_uk, w_uv, float(scale),
            layer=layer, interpret=not target_is_tpu())
    return xla()
