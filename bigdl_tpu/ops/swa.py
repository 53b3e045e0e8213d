"""Grouped-query attention for a model whose layers are of two kinds,
full and sliding-window, with K and V of different widths and a learned
SINK in the window layers' softmax (`models/mimo_v2.py`): the XLA
forms, the chunk (prefill) path and the dispatch to the decode kernels
of `ops/pallas/swa_attention.py`.

The planes keep a position's heads side by side (`[.., S, Hkv * hd]`,
`ops/kvcache.py`). Head `n` reads KV head `n // (H / Hkv)`.

The sink: one learned scalar `b_n` a query head joins each row's logits
as a column with no value,

    p_ij = exp(s_ij - m_i) / (sum_j' exp(s_ij' - m_i) + exp(b_n - m_i)),

`m_i` the row's maximum over its keys AND `b_n`: a row may give weight
to nothing.

A window layer's position `t` attends `[t - window + 1, t]`. Decode
reads the ring (`ring_live` says which columns), a chunk of rows reads
the band: the `window - 1` positions before the chunk from the ring as
it was before the chunk (`rows_before`: those rows alone, never a
layer of the plane), and the chunk's own rows, in blocks of
`_WINDOW_ROWS` rows each against the `_WINDOW_ROWS + window - 1` keys it
can see: the work does not grow with the cache's length. A full layer's
chunk sweeps the live blocks of keys with an online softmax; `[heads,
rows, S]` never exists in float32.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.ops.dsa import _kernel_wanted, _sds, ring_live
from bigdl_tpu.ops.pallas import swa_attention as kernels

_NEG = -1e30
# keys of one block of a full layer's chunk sweep: scores [H, rows, 512]
_KEY_BLOCK = 512
_WINDOW_ROWS = 256


def rows_read(positions, window: int) -> Dict[str, int]:
    """Rows of K (as many of V) that ONE layer of each kind reads when
    the slots whose queries sit at `positions` (plain ints) decode:
    `window` a window layer's (the last `window` positions, the query's
    own counted), `full` a full layer's, which is also what any layer of
    a model without windows would read."""
    held = [int(p) + 1 for p in positions if p >= 0]
    return {"window": sum(min(d, window) for d in held), "full": sum(held)}


def _ein(eq: str, a, b):
    """`einsum` accumulated in float32; off the TPU the operands are
    widened first (the CPU's dot lacks the batched bf16 forms)."""
    from bigdl_tpu.config import target_is_tpu

    if not target_is_tpu():
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def _softmax_with_sink(s_, live, sink):
    """Masked softmax over the last axis of `s_` `[.., n, g, rows, keys]`
    with the sink `[n, g]` (or None) as one more column: the weights of
    the KEYS (bf16) only."""
    s_ = jnp.where(live, s_, _NEG)
    m = jnp.max(s_, axis=-1, keepdims=True)
    if sink is not None:
        b = sink.astype(jnp.float32)[..., None, None]
        m = jnp.maximum(m, b)
    p = jnp.where(live, jnp.exp(s_ - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        l = l + jnp.exp(b - m)
    return (p / jnp.maximum(l, 1e-30)).astype(jnp.bfloat16)


def decode_xla(q, k_layer, v_layer, live, scale: float, hkv: int, sink=None):
    """One row a slot over ONE layer's planes in XLA ops: `q` `[B, H,
    dk]`, `k_layer` `[B, S, Hkv * dk]`, `v_layer` `[B, S, Hkv * dv]`,
    `live` `[B, S]`; `[B, H, dv]` in q.dtype. Fallback and oracle of
    both kernels."""
    b, h, dk = q.shape
    s, g = k_layer.shape[1], h // hkv
    k = k_layer.reshape(b, s, hkv, dk)
    v = v_layer.reshape(b, s, hkv, -1)
    s_ = _ein("bngd,bsnd->bngs", q.reshape(b, hkv, g, dk), k) * scale
    p = _softmax_with_sink(
        s_[:, :, :, None], live[:, None, None, None, :],
        None if sink is None else sink.reshape(hkv, g))[:, :, :, 0]
    return _ein("bngs,bsno->bngo", p, v).reshape(b, h, -1).astype(q.dtype)


def full_decode(q, k_stack, v_stack, layer, pos, scale: float, hkv: int,
                backend=None):
    """Decode attention of one row a slot (`q` `[B, H, dk]` at `pos`
    `[B]`) over layer `layer` of a full layer's stacks."""
    from bigdl_tpu.config import target_is_tpu

    b, h, dk = q.shape
    s, wk, wv = k_stack.shape[2:] + v_stack.shape[3:]

    def probe():
        return (lambda q_, k_, v_, p_: kernels.decode_attention_lanes_pallas(
            q_, k_, v_, p_, dk ** -0.5, hkv),
            (_sds((1, h, dk)), _sds((1, 1, s, wk)), _sds((1, 1, s, wv)),
             _sds((1,), jnp.int32)))

    if _kernel_wanted(kernels.FULL_NAME,
                      kernels.lanes_supported(q, k_stack, v_stack, hkv),
                      (h, hkv, dk, wv, s), probe, backend, q, k_stack):
        return kernels.decode_attention_lanes_pallas(
            q, k_stack, v_stack, pos, float(scale), hkv, layer=layer,
            interpret=not target_is_tpu())
    k, v = (lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)
            for x in (k_stack, v_stack))
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    live = jnp.arange(s, dtype=jnp.int32)[None, :] <= posv[:, None]
    return decode_xla(q, k, v, live, scale, hkv)


def window_decode(q, ring_k, ring_v, layer, pos, scale: float, hkv: int,
                  window: int, sink=None, backend=None):
    """Decode attention of one row a slot over the last `window`
    positions in layer `layer` of the ring stacks (the row's own K and V
    already written at `pos % ring`), the sink `[H]` in the softmax. A
    plane in position order (`CacheSpec.unrolled`) is a ring of its own
    length."""
    from bigdl_tpu.config import target_is_tpu

    b, h, dk = q.shape
    ring, wk, wv = ring_k.shape[2:] + ring_v.shape[3:]

    def probe():
        args = (_sds((1, h, dk)), _sds((1, 1, ring, wk)),
                _sds((1, 1, ring, wv)), _sds((1,), jnp.int32))
        if sink is None:
            return (lambda q_, k_, v_, p_: kernels.swa_decode_attention_pallas(
                q_, k_, v_, p_, dk ** -0.5, hkv, window), args)
        return (lambda q_, k_, v_, p_, b_: kernels.swa_decode_attention_pallas(
            q_, k_, v_, p_, dk ** -0.5, hkv, window, sink=b_),
            args + (_sds((h,), jnp.float32),))

    if _kernel_wanted(kernels.WINDOW_NAME,
                      kernels.lanes_supported(q, ring_k, ring_v, hkv),
                      (h, hkv, dk, wv, ring, window, sink is None), probe,
                      backend, q, ring_k):
        return kernels.swa_decode_attention_pallas(
            q, ring_k, ring_v, pos, float(scale), hkv, int(window),
            sink=sink, layer=layer, interpret=not target_is_tpu())
    k, v = (lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)
            for x in (ring_k, ring_v))
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    return decode_xla(q, k, v, ring_live(posv, ring, window), scale, hkv,
                      sink)


def full_block(q, k_stack, v_stack, layer, pos, scale: float, hkv: int,
               backend=None):
    """A pass of `R` rows a slot under ONE limit (`q` `[B, R, H, dk]`,
    the rows at `pos .. pos + R - 1`, `pos` `[B]` or a scalar) over layer
    `layer` of a full layer's stacks, the rows' own K and V already
    written: every row reads the same keys, the positions `0 .. pos + R
    - 1` (a block of a model that generates by diffusion over blocks:
    what lies below the block, and the whole block). The `R` rows of a
    slot are `R x H` query heads over those keys, laid out KV head by KV
    head, so `full_decode` serves as it is at `H' = R H`. Returns `[B,
    R, H, dv]` in q.dtype."""
    b, r, h, dk = q.shape
    g = h // hkv
    qh = q.reshape(b, r, hkv, g, dk).transpose(0, 2, 1, 3, 4).reshape(
        b, r * h, dk)
    o = full_decode(qh, k_stack, v_stack, layer,
                    jnp.asarray(pos, jnp.int32) + (r - 1), scale, hkv,
                    backend)
    return o.reshape(b, hkv, r, g, -1).transpose(0, 2, 1, 3, 4).reshape(
        b, r, h, -1)


def full_chunk(q, k_layer, v_layer, p, scale: float, hkv: int,
               block: int = 1):
    """A chunk of `T` rows of ONE sequence at positions `p .. p + T - 1`
    through a full layer's planes `k_layer` `[S, Hkv * dk]`, `v_layer`
    `[S, Hkv * dv]` (the chunk's own rows already written): the live
    blocks of keys under an online softmax. Key `j` is live for the row
    at `i` while `j // block <= i // block`: causal at `block` 1, causal
    between blocks of `block` positions whose rows see their whole block
    above it (the chunk then starts and ends on a block's edge). Returns
    `[T, H, dv]` float32."""
    t, h, dk = q.shape
    s, g = k_layer.shape[0], h // hkv
    dv = v_layer.shape[1] // hkv
    kb = _KEY_BLOCK if s % _KEY_BLOCK == 0 else s
    n_live = jnp.minimum((p + t + kb - 1) // kb, s // kb)
    qg = q.reshape(t, hkv, g, dk)
    at = p + jnp.arange(t, dtype=jnp.int32)
    if block > 1:
        # the last position of each row's block
        at = at // block * block + (block - 1)

    def attend(j, carry):
        m, l, acc = carry
        k = lax.dynamic_slice_in_dim(k_layer, j * kb, kb).reshape(kb, hkv, dk)
        v = lax.dynamic_slice_in_dim(v_layer, j * kb, kb).reshape(kb, hkv, dv)
        live = (j * kb + jnp.arange(kb, dtype=jnp.int32))[None, :] \
            <= at[:, None]
        s_ = jnp.where(live, _ein("tngd,snd->ngts", qg, k) * scale, _NEG)
        m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
        corr = jnp.exp(m - m_new)
        pr = jnp.where(live, jnp.exp(s_ - m_new[..., None]), 0.0)
        l = l * corr + jnp.sum(pr, axis=-1)
        acc = (acc * jnp.moveaxis(corr, -1, 0)[..., None]
               + _ein("ngts,sno->tngo", pr.astype(jnp.bfloat16), v))
        return m_new, l, acc

    _, l, acc = lax.fori_loop(0, n_live, attend, (
        jnp.full((hkv, g, t), _NEG, jnp.float32),
        jnp.zeros((hkv, g, t), jnp.float32),
        jnp.zeros((t, hkv, g, dv), jnp.float32)))
    o = acc / jnp.maximum(jnp.moveaxis(l, -1, 0), 1e-30)[..., None]
    return o.reshape(t, h, dv)


def rows_before(stack, layer, pos, back: int):
    """The rows of the `back` positions before `pos` `[B]` in layer
    `layer` of a ring stack `[L, B, ring, W]` (column `t % ring` holds
    position `t`): `[B, back, W]`, read where they lie; a row of a
    position below 0 is whatever its column holds and must be masked."""
    b, ring = stack.shape[1], stack.shape[2]
    at = jnp.mod(pos[:, None] - back
                 + jnp.arange(back, dtype=jnp.int32)[None, :], ring)
    return stack[layer, jnp.arange(b, dtype=jnp.int32)[:, None], at]


def window_chunk(q, new_k, new_v, prev_k, prev_v, p, scale: float, hkv: int,
                 window: int, sink: Optional[jax.Array] = None):
    """A chunk of `T` rows of ONE sequence at positions `p ..` through a
    window layer: its keys are the `window - 1` positions before the
    chunk, `prev_k` / `prev_v` `[window - 1, Hkv * d]` as the ring held
    them BEFORE the chunk (`rows_before`), and the chunk's own rows
    `new_k` / `new_v` `[T, Hkv * d]`; row i attends the `window`
    positions ending at its own, and the sink `[H]`. Returns `[T, H,
    dv]` float32."""
    t, h, dk = q.shape
    g, back = h // hkv, window - 1
    ctx_k = jnp.concatenate([prev_k, new_k.astype(prev_k.dtype)]).reshape(
        back + t, hkv, dk)
    ctx_v = jnp.concatenate([prev_v, new_v.astype(prev_v.dtype)]).reshape(
        back + t, hkv, -1)
    qg = q.reshape(t, hkv, g, dk)
    sk = None if sink is None else sink.reshape(hkv, g)
    rb = _WINDOW_ROWS if t % _WINDOW_ROWS == 0 else t
    outs = []
    for r0 in range(0, t, rb):
        i = jnp.arange(rb, dtype=jnp.int32)[:, None]
        j = jnp.arange(rb + back, dtype=jnp.int32)[None, :]
        # key j of the band is position p + r0 - back + j
        live = (j >= i) & (j <= i + back) & (p + r0 - back + j >= 0)
        s_ = _ein("tngd,snd->ngts", qg[r0:r0 + rb],
                  ctx_k[r0:r0 + rb + back]) * scale
        outs.append(_ein("ngts,sno->tngo", _softmax_with_sink(s_, live, sk),
                         ctx_v[r0:r0 + rb + back]))
    o = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return o.reshape(t, h, -1)
