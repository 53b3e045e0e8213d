"""Pallas TPU kernel: fused decode attention over a PAGED KV arena.

The slab decode kernel (`decode_attention.py`) streams each sequence's
K/V rows contiguously. Under the paged layout (`ops/paged.py`) a
sequence's rows live scattered across the ``[P, page_size, Hkv, hd]``
arena wherever its block table points — materializing a dense copy first
would double the memory traffic of an already bandwidth-bound op.

This kernel keeps the gather INSIDE the launch: the block table rides in
as a scalar-prefetch operand, and each grid step's K/V BlockSpec
*index_map* dereferences it — ``(bt[b, j], 0, head)`` — so Mosaic's
pipeline DMAs page ``bt[b, j]`` straight from the arena into VMEM while
step ``j-1`` computes. One S-block == one page; the online-softmax state
machine is the blocked slab kernel's, with the position mask doing double
duty: padded table entries point at the null page (physical 0), whose
positions are all ``> pos`` and therefore contribute nothing.

Shapes: q ``[B, 1, H, hd]``; arena k/v ``[P, ps, Hkv, hd]`` (one layer);
block_tables ``[B, NP]`` int32; pos ``[B]`` int32. int8/int4 arenas ride
with their ``[P, ps, Hkv]`` scale planes and dequantize in-register, rows
scaled exactly like the slab kernels (`_head_scales`/`_dequant_rows`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.pallas.decode_attention import (
    _NEG_INF,
    _dequant_rows,
    _head_scales,
)


def _paged_kernel(pos_ref, bt_ref, q_ref, k_ref, v_ref, out_ref,
                  m_ref, l_ref, acc_ref, *, scale, ps, np_, gp):
    b = pl.program_id(0)
    sj = pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(sj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.bfloat16)              # [Gp, hd]
    k = k_ref[0].astype(jnp.bfloat16)                 # [ps, hd] (one page)
    v = v_ref[0].astype(jnp.bfloat16)

    s_ = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [Gp, ps]
    # logical position of this page's rows; null-page rows always mask
    # (their logical ids exceed pos by construction of the allocator)
    ids = sj * ps + jax.lax.broadcasted_iota(jnp.int32, (gp, ps), 1)
    s_ = jnp.where(ids <= pos, s_, _NEG_INF)

    m_prev = m_ref[:, :1]
    m_cur = jnp.max(s_, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s_ - m_new)
    l_ref[:] = jnp.broadcast_to(
        l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
        l_ref.shape)
    pv = jax.lax.dot_general(
        p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(sj == np_ - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out_ref[0, 0] = (acc_ref[:] / l).astype(out_ref.dtype)


def _paged_kernel_scaled(pos_ref, bt_ref, q_ref, k_ref, v_ref,
                         ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref,
                         *, scale, ps, np_, gp, hkv):
    b = pl.program_id(0)
    hi = pl.program_id(1)
    sj = pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(sj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.bfloat16)              # [Gp, hd]
    k = _dequant_rows(k_ref, _head_scales(ks_ref, hi, ps, hkv))  # [ps, hd]
    v = _dequant_rows(v_ref, _head_scales(vs_ref, hi, ps, hkv))

    s_ = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [Gp, ps]
    ids = sj * ps + jax.lax.broadcasted_iota(jnp.int32, (gp, ps), 1)
    s_ = jnp.where(ids <= pos, s_, _NEG_INF)

    m_prev = m_ref[:, :1]
    m_cur = jnp.max(s_, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s_ - m_new)
    l_ref[:] = jnp.broadcast_to(
        l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
        l_ref.shape)
    pv = jax.lax.dot_general(
        p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(sj == np_ - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out_ref[0, 0] = (acc_ref[:] / l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention_pallas(
    q: jax.Array,             # [B, 1, H, hd]
    arena_k: jax.Array,       # [P, ps, Hkv, hd] one layer's arena
    arena_v: jax.Array,
    block_tables: jax.Array,  # [B, NP] int32 (0 = null page)
    q_pos: jax.Array,         # [B] int32
    scale: float,
    interpret: bool = False,
    k_scale=None,             # [P, ps, Hkv] f32 for int8/int4 codes
    v_scale=None,
) -> jax.Array:
    """Fused paged decode SDP. Returns [B, 1, H, hd] in q.dtype."""
    b, sq, h, hd = q.shape
    p_, ps, hkv = arena_k.shape[0], arena_k.shape[1], arena_k.shape[2]
    np_ = block_tables.shape[1]
    if sq != 1:
        raise NotImplementedError("paged decode kernel handles Sq == 1")
    scaled = k_scale is not None
    g = h // hkv
    gp = max(16, -(-g // 8) * 8)   # pad query group to clean sublane run

    qr = q.reshape(b, hkv, g, hd)
    if gp != g:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    # heads into the lane axis so a per-head block is (1, ps, hd); free
    # reshape on the contiguous [P, ps, Hkv, hd] arena layout
    k2 = arena_k.reshape(p_, ps, hkv * hd)
    v2 = arena_v.reshape(p_, ps, hkv * hd)

    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    bt = block_tables.astype(jnp.int32)

    # the whole point: K/V index_maps dereference the prefetched block
    # table, so grid step (b, hi, sj) DMAs physical page bt[b, sj] —
    # the gather never materializes a dense copy in HBM
    q_spec = pl.BlockSpec((1, 1, gp, hd),
                          lambda bi, hi, sj, pos_ref, bt_ref: (bi, hi, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, ps, hd),
        lambda bi, hi, sj, pos_ref, bt_ref: (bt_ref[bi, sj], 0, hi))
    in_specs = [q_spec, kv_spec, kv_spec]
    if scaled:
        # scale planes ride full-Hkv in the lanes (see _head_scales)
        sc_spec = pl.BlockSpec(
            (1, ps, hkv),
            lambda bi, hi, sj, pos_ref, bt_ref: (bt_ref[bi, sj], 0, 0))
        in_specs += [sc_spec, sc_spec]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, np_),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, gp, hd),
            lambda bi, hi, sj, pos_ref, bt_ref: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp, 128), jnp.float32),
            pltpu.VMEM((gp, 128), jnp.float32),
            pltpu.VMEM((gp, hd), jnp.float32),
        ],
    )
    kernel = (functools.partial(_paged_kernel_scaled, scale=scale, ps=ps,
                                np_=np_, gp=gp, hkv=hkv)
              if scaled else
              functools.partial(_paged_kernel, scale=scale, ps=ps,
                                np_=np_, gp=gp))
    operands = (pos, bt, qr, k2, v2)
    if scaled:
        operands += (k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32))
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, hd), q.dtype),
        interpret=interpret,
    )(*operands)

    return out[:, :, :g, :].reshape(b, 1, h, hd)


def paged_attention_geometry_ok(q, arena_k, logits_soft_cap,
                                sliding_window, alibi_slopes,
                                k_scale=None) -> bool:
    """Feature/geometry gate: plain softmax attention, MXU-aligned
    shapes, page_size a lane-tile multiple (one page == one S-block)."""
    if alibi_slopes is not None:
        return False
    if logits_soft_cap is not None or sliding_window is not None:
        return False
    h, hd = q.shape[2], q.shape[3]
    ps, hkv = arena_k.shape[1], arena_k.shape[2]
    if h % hkv != 0 or hd % 64 != 0 or ps % 128 != 0:
        return False
    if arena_k.dtype in (jnp.bfloat16, jnp.float8_e5m2):
        return k_scale is None
    if arena_k.dtype in (jnp.int8, jnp.int4):
        return k_scale is not None
    return False


def paged_decode_attention_supported(q, arena_k, logits_soft_cap,
                                     sliding_window, alibi_slopes,
                                     k_scale=None) -> bool:
    """Gate for the sdp_attention_paged dispatch (bigdl_tpu.ops.attention)."""
    return q.shape[1] == 1 and paged_attention_geometry_ok(
        q, arena_k, logits_soft_cap, sliding_window, alibi_slopes, k_scale)
