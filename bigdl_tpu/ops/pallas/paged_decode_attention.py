"""Pallas TPU kernel: fused decode attention over a PAGED KV arena.

The slab decode kernel (`decode_attention.py`) streams each sequence's
K/V rows contiguously. Under the paged layout (`ops/paged.py`) a
sequence's rows live scattered across the arena wherever its block table
points — materializing a dense copy first would double the memory traffic
of an already bandwidth-bound op.

This kernel keeps the gather INSIDE the launch, on the arena as it lies:
the operands are the cache's whole stacks, codes ``[L*Hkv, P, ps, hd]``
(4-bit: ``[L*Hkv, P, ps/8, 8, hd]``) and (int8/int4) scales
``[L, P, Hkv, ps]``, the layout `ops/paged.py` keeps at rest (and why). The layer
index, the block table and the positions ride in as scalar-prefetch
operands, and each grid step's K/V BlockSpec *index_map* dereferences
them — ``(layer * Hkv + head, bt[b, j], 0, 0)`` — so Mosaic's pipeline DMAs
head ``head`` of page ``bt[b, j]`` of layer ``layer`` straight from the
arena into VMEM while step ``j-1`` computes:
no layer, page or scale plane is sliced, reshaped or copied on the way
in. One S-block == one page; the online-softmax state machine is the
blocked slab kernel's, with the position mask doing double duty: padded
table entries point at the null page (physical 0), whose positions are all
``> pos`` and therefore contribute nothing.

Shapes: q ``[B, 1, H, hd]``; block_tables ``[B, NP]`` int32; pos ``[B]``
int32; layer an int32 scalar. A page's scales arrive as one ``[Hkv, ps]``
block, positions in the lanes, which is how the scores lie: the head's
ROW of it multiplies the scores (K) and the probabilities (V), so no
scale is turned into a column and the codes meet the MXU as they are
(`_paged_kernel`; on the chip the column, a transpose of one row a
page-head, cost 0.37 us of a grid step's 0.82: PERF.md 6, PR 40).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.ops.paged import code_page_size
from bigdl_tpu.ops.pallas.decode_attention import _NEG_INF


def _page_rows(x_ref):
    """One head of one page, `[ps, hd]` bf16 rows. int8/int4 codes come
    out as they are (<= 127: exact in bf16; Mosaic has no direct
    low-bit-int -> f32 cast). A 4-bit block is `[ps/8, 8, hd]`: its
    groups of 8 merge in f32, where every (8, 128) tile stays whole (as
    `decode_attention._rows` merges heads)."""
    x = x_ref[...]
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.bfloat16)
    if x.ndim == 3:
        x = x.astype(jnp.float32).reshape(-1, x.shape[2])
    return x.astype(jnp.bfloat16)


def _paged_kernel(lyr_ref, pos_ref, bt_ref, q_ref, k_ref, v_ref, *rest,
                  scale, ps, np_, gp, scaled):
    """One (slot, kv head, page) step of the online-softmax sweep.

    int8/int4: a page-head's scales are row `hi` of the page's
    `[Hkv, ps]` block, positions in the lanes — the layout of the
    scores `[Gp, ps]`. So the K scales multiply the SCORES and the V
    scales the probabilities, in f32, and the codes meet the MXU as
    they are: `q . (c_k s_k) = (q . c_k) s_k` and
    `p . (c_v s_v) = (p s_v) . c_v`, with no scale turned into a column
    and one rounding to bf16 (of `p s_v`) where dequantized rows have
    two (of `c s` and of `p`)."""
    if scaled:
        ks_ref, vs_ref, out_ref, m_ref, l_ref, acc_ref = rest
    else:
        out_ref, m_ref, l_ref, acc_ref = rest
    del lyr_ref, bt_ref               # consumed by the index maps
    hi = pl.program_id(1)
    sj = pl.program_id(2)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(sj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.bfloat16)               # [Gp, hd]
    k = _page_rows(k_ref)                             # [ps, hd] (one page)
    v = _page_rows(v_ref)

    s_ = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [Gp, ps]
    if scaled:
        s_ = s_ * ks_ref[pl.ds(hi, 1), :]
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
    if scaled:
        p = p * vs_ref[pl.ds(hi, 1), :]
    pv = jax.lax.dot_general(
        p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(sj == np_ - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out_ref[...] = (acc_ref[:] / l).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "kv_heads", "interpret"))
def paged_decode_attention_pallas(
    q: jax.Array,             # [B, 1, H, hd]
    arena_k: jax.Array,       # [L*Hkv, P, ps, hd] the cache's whole stack
    arena_v: jax.Array,       # (4-bit codes: [L*Hkv, P, ps/8, 8, hd])
    block_tables: jax.Array,  # [B, NP] int32 (0 = null page)
    q_pos: jax.Array,         # [B] int32
    scale: float,
    kv_heads: int,
    interpret: bool = False,
    k_scale=None,             # [L, P, Hkv, ps] f32 for int8/int4 codes
    v_scale=None,
    layer=0,                  # int32 scalar: which layer of the stack
) -> jax.Array:
    """Fused paged decode SDP over layer `layer` of the arena. Returns
    [B, 1, H, hd] in q.dtype."""
    b, sq, h, hd = q.shape
    hkv, page = kv_heads, arena_k.shape[2:]
    ps = code_page_size(arena_k)
    np_ = block_tables.shape[1]
    if sq != 1:
        raise NotImplementedError("paged decode kernel handles Sq == 1")
    scaled = k_scale is not None
    g = h // hkv
    gp = max(16, -(-g // 8) * 8)   # pad query group to clean sublane run

    qr = q.reshape(b, hkv, g, hd)
    if gp != g:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, gp - g), (0, 0)))

    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    bt = block_tables.astype(jnp.int32)

    # the whole point: K/V index_maps dereference the prefetched layer
    # index and block table, so grid step (b, hi, sj) DMAs head hi of
    # physical page bt[b, sj] of that layer — neither the gather nor the
    # layer ever materializes in HBM
    def q_index(bi, hi, sj, *_):
        return bi, hi, 0, 0

    def kv_index(bi, hi, sj, lyr_ref, pos_ref, bt_ref):
        return (lyr_ref[0] * hkv + hi, bt_ref[bi, sj]) + (0,) * len(page)

    def sc_index(bi, hi, sj, lyr_ref, pos_ref, bt_ref):
        return lyr_ref[0], bt_ref[bi, sj], 0, 0

    q_spec = pl.BlockSpec((None, None, gp, hd), q_index)
    kv_spec = pl.BlockSpec((None, None) + page, kv_index)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = (lyr, pos, bt, qr, arena_k, arena_v)
    if scaled:
        sc_spec = pl.BlockSpec((None, None, hkv, ps), sc_index)
        in_specs += [sc_spec, sc_spec]
        operands += (k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, ps=ps, np_=np_,
                          gp=gp, scaled=scaled),
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hkv, np_),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((gp, 128), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
                pltpu.VMEM((gp, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, hd), q.dtype),
        interpret=interpret,
    )(*operands)

    return out[:, :, :g, :].reshape(b, 1, h, hd)


def paged_attention_geometry_ok(q, arena_k, kv_heads, logits_soft_cap,
                                sliding_window, alibi_slopes,
                                k_scale=None) -> bool:
    """Feature/geometry gate: plain softmax attention, MXU-aligned
    shapes, page_size a lane-tile multiple (one page == one S-block)."""
    if alibi_slopes is not None:
        return False
    if logits_soft_cap is not None or sliding_window is not None:
        return False
    h, hd = q.shape[2], q.shape[3]
    ps = code_page_size(arena_k)
    if h % kv_heads != 0 or hd % 64 != 0 or ps % 128 != 0:
        return False
    if arena_k.dtype in (jnp.bfloat16, jnp.float8_e5m2):
        return k_scale is None
    if arena_k.dtype in (jnp.int8, jnp.int4):
        return k_scale is not None
    return False


def paged_decode_attention_supported(q, arena_k, kv_heads, logits_soft_cap,
                                     sliding_window, alibi_slopes,
                                     k_scale=None) -> bool:
    """Gate for the sdp_attention_paged dispatch (bigdl_tpu.ops.attention)."""
    return q.shape[1] == 1 and paged_attention_geometry_ok(
        q, arena_k, kv_heads, logits_soft_cap, sliding_window, alibi_slopes,
        k_scale)
