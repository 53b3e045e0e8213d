"""Pallas TPU kernel: fused single-token (decode) attention over the cache.

TPU-native replacement for the reference's decode-attention kernels —
`linear_q4_0.sdp_fp8` (FP8-KV decode SDP, reference transformers/models/
llama.py:435) and ESIMD `sdp_forward` (low_bit_linear.py:744-745 gates at
models/utils.py:315-355).

Decode attention is memory-bound: the whole KV cache is read to produce one
token. The XLA fallback computes scores/softmax/values as separate fusions
with an [B,H,1,S] intermediate round-trip; this kernel walks each (batch,
kv-head) pair once — K and V stream HBM->VMEM exactly one time, the
scores/softmax/combine never leave VMEM, and FP8 caches upcast in-register
(the reference needs dedicated fp8 GEMM kernels for the same effect).

Shapes: q [B, 1, H, hd]; cache k/v [B, S, Hkv, hd] (bf16 or float8_e5m2);
pos int32 scalar or per-slot [B] (continuous batching). GQA queries ride
the sublane axis: each grid step computes the whole G = H/Hkv query group
against its kv head with one [G, hd] x [hd, S] MXU pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# above this cache length the whole-S tiles exceed VMEM (k+v bf16 at
# 8k x 128 is 4MB; 16MB/core) — switch to the S-blocked online-softmax
# sweep (same state machine as the prefill flash kernel, one query row)
_RESIDENT_MAX = 4096
_NEG_INF = -1e30


def _kernel_blocked(pos_ref, q_ref, k_ref, v_ref, out_ref,
                    m_ref, l_ref, acc_ref, *, scale, sb, ns, gp):
    b = pl.program_id(0)
    sj = pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(sj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.bfloat16)              # [Gp, hd]
    k = k_ref[0].astype(jnp.bfloat16)                 # [sb, hd]
    v = v_ref[0].astype(jnp.bfloat16)

    s_ = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [Gp, sb]
    ids = sj * sb + jax.lax.broadcasted_iota(jnp.int32, (gp, sb), 1)
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

    @pl.when(sj == ns - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out_ref[0, 0] = (acc_ref[:] / l).astype(out_ref.dtype)


def _kernel(pos_ref, q_ref, k_ref, v_ref, out_ref, *, scale, s, gp):
    b = pl.program_id(0)
    pos = pos_ref[b]

    q = q_ref[0, 0].astype(jnp.bfloat16)              # [Gp, hd]
    # K/V arrive as [B, S, Hkv*hd] views blocked (1, S, hd) per kv head —
    # Mosaic requires the last two BLOCK dims be (8,128)-tileable, which a
    # [.., S, 1, hd] per-head block is not (the 1 sits second-to-last)
    k = k_ref[0].astype(jnp.bfloat16)                 # [S, hd]
    v = v_ref[0].astype(jnp.bfloat16)                 # [S, hd]

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [Gp, S]
    ids = jax.lax.broadcasted_iota(jnp.int32, (gp, s), 1)
    scores = jnp.where(ids <= pos, scores, -jnp.inf)

    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jax.lax.dot_general(
        p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) / l        # [Gp, hd]
    out_ref[0, 0] = out.astype(out_ref.dtype)


def _head_scales(sc_ref, hi, n, hkv):
    """Extract one kv head's scale column [n, 1] from a [1, n, Hkv] block.

    Scale planes ride full-Hkv in the lane axis (an [.., n, 1] per-head
    block would put 1 in the lanes); the column select is a one-hot
    mask + keepdims lane reduction. The [n, 1] result broadcasts over
    the K/V rows — a rank-1 [n] vector here trips Mosaic's layout
    inference ("unsupported implicit dim change"), so keep it 2D."""
    sel = jax.lax.broadcasted_iota(jnp.int32, (n, hkv), 1) == hi
    return jnp.sum(jnp.where(sel, sc_ref[0], 0.0), axis=1, keepdims=True)


def _dequant_rows(codes_ref, sc, dt=jnp.bfloat16):
    """[S, hd] codes x [S, 1] scales -> bf16 rows, matching the XLA
    fallback's `(codes * scale).astype(bf16)` bit for bit. The int->f32
    hop goes via bf16 (codes <= 127 are exact there; Mosaic has no
    direct low-bit-int -> f32 cast)."""
    return (codes_ref[0].astype(jnp.bfloat16).astype(jnp.float32)
            * sc).astype(dt)


def _kernel_scaled(pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, out_ref,
                   *, scale, s, gp, hkv):
    """Resident kernel over int8/int4 codes: per-(token, head) scales
    fold into the K/V ROWS in-register (one [S, 1] broadcast each) before
    the two dots — codes only ever upcast in-register, the f32 scale
    planes stream once, and no dequantized copy touches HBM."""
    b = pl.program_id(0)
    hi = pl.program_id(1)
    pos = pos_ref[b]

    q = q_ref[0, 0].astype(jnp.bfloat16)              # [Gp, hd]
    k = _dequant_rows(k_ref, _head_scales(ks_ref, hi, s, hkv))  # [S, hd]
    v = _dequant_rows(v_ref, _head_scales(vs_ref, hi, s, hkv))

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [Gp, S]
    ids = jax.lax.broadcasted_iota(jnp.int32, (gp, s), 1)
    scores = jnp.where(ids <= pos, scores, -jnp.inf)

    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jax.lax.dot_general(
        p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) / l        # [Gp, hd]
    out_ref[0, 0] = out.astype(out_ref.dtype)


def _kernel_blocked_scaled(pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                           out_ref, m_ref, l_ref, acc_ref,
                           *, scale, sb, ns, gp, hkv):
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
    k = _dequant_rows(k_ref, _head_scales(ks_ref, hi, sb, hkv))  # [sb, hd]
    v = _dequant_rows(v_ref, _head_scales(vs_ref, hi, sb, hkv))

    s_ = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [Gp, sb]
    ids = sj * sb + jax.lax.broadcasted_iota(jnp.int32, (gp, sb), 1)
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

    @pl.when(sj == ns - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        out_ref[0, 0] = (acc_ref[:] / l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def decode_attention_pallas(
    q: jax.Array,          # [B, 1, H, hd]
    k: jax.Array,          # [B, S, Hkv, hd] bf16 | float8_e5m2 | int8 | int4
    v: jax.Array,
    q_pos: jax.Array,      # scalar int32 or [B] int32
    scale: float,
    interpret: bool = False,
    k_scale=None,          # [B, S, Hkv] f32 (int8/int4 codes), else None
    v_scale=None,
) -> jax.Array:
    """Fused decode SDP. Returns [B, 1, H, hd] in q.dtype."""
    b, sq, h, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    if sq != 1:
        raise NotImplementedError("decode kernel handles Sq == 1 only")
    scaled = k_scale is not None
    g = h // hkv
    gp = max(16, -(-g // 8) * 8)      # pad query group to a clean sublane run

    qr = q.reshape(b, hkv, g, hd)
    if gp != g:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    # flatten heads into the lane axis so the per-head block is
    # (1, S, hd) — see the kernel comment; the reshape is free on the
    # contiguous [B, S, Hkv, hd] cache layout
    k2 = k.reshape(b, s, hkv * hd)
    v2 = v.reshape(b, s, hkv * hd)

    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))

    q_spec = pl.BlockSpec((1, 1, gp, hd),
                          lambda bi, hi, *r: (bi, hi, 0, 0))
    if s > _RESIDENT_MAX:
        sb = 512 if s % 512 == 0 else 128
        ns = s // sb
        in_specs = [
            q_spec,
            pl.BlockSpec((1, sb, hd),
                         lambda bi, hi, sj, pos_ref: (bi, sj, hi)),
            pl.BlockSpec((1, sb, hd),
                         lambda bi, hi, sj, pos_ref: (bi, sj, hi)),
        ]
        if scaled:
            # scale planes ride full-Hkv in the lanes (see _head_scales)
            sc_spec = pl.BlockSpec((1, sb, hkv),
                                   lambda bi, hi, sj, pos_ref: (bi, sj, 0))
            in_specs += [sc_spec, sc_spec]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, ns),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, gp, hd), lambda bi, hi, sj, pos_ref: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((gp, 128), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
                pltpu.VMEM((gp, hd), jnp.float32),
            ],
        )
        kernel = (functools.partial(_kernel_blocked_scaled, scale=scale,
                                    sb=sb, ns=ns, gp=gp, hkv=hkv)
                  if scaled else
                  functools.partial(_kernel_blocked, scale=scale, sb=sb,
                                    ns=ns, gp=gp))
    else:
        in_specs = [
            q_spec,
            pl.BlockSpec((1, s, hd), lambda bi, hi, pos_ref: (bi, 0, hi)),
            pl.BlockSpec((1, s, hd), lambda bi, hi, pos_ref: (bi, 0, hi)),
        ]
        if scaled:
            sc_spec = pl.BlockSpec((1, s, hkv),
                                   lambda bi, hi, pos_ref: (bi, 0, 0))
            in_specs += [sc_spec, sc_spec]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, gp, hd),
                                   lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
        )
        kernel = (functools.partial(_kernel_scaled, scale=scale, s=s,
                                    gp=gp, hkv=hkv)
                  if scaled else
                  functools.partial(_kernel, scale=scale, s=s, gp=gp))
    operands = (pos, qr, k2, v2)
    if scaled:
        operands += (k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32))
    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, hd), q.dtype),
        interpret=interpret,
    )(*operands)

    return out[:, :, :g, :].reshape(b, 1, h, hd)


def attention_geometry_ok(q, k, logits_soft_cap, sliding_window,
                          alibi_slopes, k_scale=None) -> bool:
    """Shared feature/geometry gate for BOTH Pallas attention kernels
    (decode + blockwise prefill): plain softmax attention only, aligned
    shapes, KV dtypes the kernels upcast (or dequantize) in-register."""
    if alibi_slopes is not None:
        return False
    if logits_soft_cap is not None or sliding_window is not None:
        return False
    h, hd = q.shape[2], q.shape[3]
    s, hkv = k.shape[1], k.shape[2]
    if h % hkv != 0 or hd % 64 != 0 or s % 128 != 0:
        return False
    if k.dtype in (jnp.bfloat16, jnp.float8_e5m2):
        return k_scale is None
    if k.dtype in (jnp.int8, jnp.int4):
        # block-scaled codes need their scale planes for in-kernel dequant
        return k_scale is not None
    return False


def decode_attention_supported(q, k, v, q_pos, scale, logits_soft_cap,
                               sliding_window, alibi_slopes,
                               k_scale=None) -> bool:
    """Gate for the sdp_attention dispatch (bigdl_tpu.ops.attention)."""
    return q.shape[1] == 1 and attention_geometry_ok(
        q, k, logits_soft_cap, sliding_window, alibi_slopes, k_scale)
