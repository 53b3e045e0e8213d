"""Pallas TPU kernel: blockwise (flash) causal attention for prefill.

TPU-native replacement for the reference's prefill flash-attention path
(`use_flash_attention` gating ipex's F.scaled_dot_product_attention,
reference transformers/models/utils.py:33-120 and the native_sdp python
fallback at models/llama.py:1320-1349).

Why: prefill attention against the pre-allocated cache computes scores
[B, H, S, S_max]; at S=1024, S_max=2048 that is a quarter-gigabyte f32
intermediate per 32-head batch that XLA writes to HBM between the QK
matmul and the softmax. This kernel runs the classic online-softmax
sweep: for each query tile, stream key/value tiles through VMEM keeping
only [bq, hd] accumulators — scores never exist in HBM, and the KV cache
is read exactly once.

Grid: (B*H, S/bq, S_max/bk), kv innermost; m/l/acc live in VMEM scratch
and persist across the kv sweep (TPU grid order guarantees sequential
iteration of the last axis per outer step). Causality and the unwritten
cache tail share one mask: k_pos <= q_pos + q_idx.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref,
            *, scale, bq, bk, nk):
    kj = pl.program_id(2)
    qi = pl.program_id(1)
    b = pl.program_id(0)
    pos = pos_ref[b]

    @pl.when(kj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.bfloat16)                  # [bq, hd]
    # K/V arrive as [B, S_max, Hkv*hd] views blocked (1, bk, hd) per kv
    # head (a [.., bk, 1, hd] per-head block violates Mosaic's (8,128)
    # block-tiling rule — the 1 sits second-to-last)
    k = k_ref[0].astype(jnp.bfloat16)                  # [bk, hd]
    v = v_ref[0].astype(jnp.bfloat16)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_ids = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_ids = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s = jnp.where(k_ids <= pos + q_ids, s, _NEG_INF)

    m_prev = m_ref[:, :1]                              # [bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                             # [bq, bk]
    l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(jnp.bfloat16), v,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == nk - 1)
    def _():
        # fully masked rows (query beyond pos with an empty cache) keep
        # l == 0; guard the division — their output is garbage that the
        # caller's position masking never reads
        l = jnp.where(l_ref[:, :1] == 0.0, 1.0, l_ref[:, :1])
        out_ref[0] = (acc_ref[:] / l).astype(out_ref.dtype)


def _kernel_scaled(pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, out_ref,
                   m_ref, l_ref, acc_ref, *, scale, bq, bk, nk, h, g, hkv):
    """Flash sweep over int8/int4 codes: per-(token, head) scales fold
    into the K/V rows in-register before the dots (see decode_attention.
    _dequant_rows — a rank-1 scale vector would trip Mosaic layout
    inference, so the column select keeps dims)."""
    kj = pl.program_id(2)
    qi = pl.program_id(1)
    bh = pl.program_id(0)
    pos = pos_ref[bh]
    hi = (bh % h) // g      # kv head of this b*h grid row

    @pl.when(kj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    from bigdl_tpu.ops.pallas.decode_attention import (_dequant_rows,
                                                       _head_scales)

    q = q_ref[0].astype(jnp.bfloat16)                  # [bq, hd]
    k = _dequant_rows(k_ref, _head_scales(ks_ref, hi, bk, hkv))  # [bk, hd]
    v = _dequant_rows(v_ref, _head_scales(vs_ref, hi, bk, hkv))

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_ids = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_ids = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s = jnp.where(k_ids <= pos + q_ids, s, _NEG_INF)

    m_prev = m_ref[:, :1]                              # [bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                             # [bq, bk]
    l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(jnp.bfloat16), v,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == nk - 1)
    def _():
        l = jnp.where(l_ref[:, :1] == 0.0, 1.0, l_ref[:, :1])
        out_ref[0] = (acc_ref[:] / l).astype(out_ref.dtype)


def prefill_attention_pallas(
    q: jax.Array,          # [B, S, H, hd]
    k: jax.Array,          # [B, S_max, Hkv, hd] bf16 | e5m2 | int8 | int4
    v: jax.Array,
    q_pos: jax.Array,      # scalar int32 or [B]
    scale: float,
    interpret: bool = False,
    k_scale=None,          # [B, S_max, Hkv] f32 (int8/int4 codes)
    v_scale=None,
) -> jax.Array:
    """Blockwise causal SDP. Returns [B, S, H, hd] in q.dtype.

    Differentiable: the forward runs the Pallas sweep; the backward is
    standard XLA softmax-attention gradients (pallas_call itself has no
    VJP — without this, dispatching prefill to the kernel would break
    every training path that reaches sdp_attention with Sq > 1).
    Block-scaled codes (k_scale given) are inference-only — gradients
    through rounded int codes are meaningless, so that path skips the
    custom-vjp wrapper."""
    if k_scale is not None:
        return _pfa_impl(q, k, v, q_pos, float(scale), bool(interpret),
                         k_scale, v_scale)
    return _pfa_vjp(q, k, v, q_pos, float(scale), bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _pfa_vjp(q, k, v, q_pos, scale, interpret):
    return _pfa_impl(q, k, v, q_pos, scale, interpret)


def _pfa_fwd(q, k, v, q_pos, scale, interpret):
    return _pfa_impl(q, k, v, q_pos, scale, interpret), (q, k, v, q_pos)


def _pfa_bwd(scale, interpret, res, dy):
    """Backward via jax.vjp over the XLA reference attention — ONE source
    of truth for the mask/GQA semantics (ops/attention.sdp_attention)
    instead of a hand-derived gradient to keep in sync. Gradient
    precision therefore equals differentiating the XLA path itself
    (bf16 matmul operands, f32 softmax/accumulation) — exactly what
    non-kernel training runs get."""
    import numpy as _np

    q, k, v, q_pos = res

    def ref(q_, k_, v_):
        from bigdl_tpu.ops.attention import sdp_attention

        return sdp_attention(q_, k_, v_, q_pos, scale=scale,
                             backend="xla")

    _, vjp = jax.vjp(ref, q, k, v)
    dq, dk, dv = vjp(dy.astype(q.dtype))
    pos_ct = _np.zeros(jnp.shape(q_pos), jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            pos_ct)


_pfa_vjp.defvjp(_pfa_fwd, _pfa_bwd)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _pfa_impl(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    scale: float,
    interpret: bool = False,
    k_scale=None,
    v_scale=None,
) -> jax.Array:
    b, s, h, hd = q.shape
    smax, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scaled = k_scale is not None

    bq = 256 if s % 256 == 0 else 128
    bk = 512 if smax % 512 == 0 else 128
    nq, nk = s // bq, smax // bk

    qr = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    # flatten kv heads into the lane axis (see kernel comment)
    k2 = k.reshape(b, smax, hkv * hd)
    v2 = v.reshape(b, smax, hkv * hd)
    pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1), (b,))
    # per-(b*h) pos lookup: repeat to [B*H]
    pos_bh = jnp.repeat(pos, h)

    in_specs = [
        pl.BlockSpec((1, bq, hd),
                     lambda bh, qi, kj, pos_ref: (bh, qi, 0)),
        pl.BlockSpec((1, bk, hd),
                     lambda bh, qi, kj, pos_ref:
                     (bh // h, kj, (bh % h) // g)),
        pl.BlockSpec((1, bk, hd),
                     lambda bh, qi, kj, pos_ref:
                     (bh // h, kj, (bh % h) // g)),
    ]
    operands = (pos_bh, qr, k2, v2)
    if scaled:
        # scale planes ride full-Hkv in the lanes (decode_attention.
        # _head_scales explains the in-kernel column select)
        sc_spec = pl.BlockSpec((1, bk, hkv),
                               lambda bh, qi, kj, pos_ref:
                               (bh // h, kj, 0))
        in_specs += [sc_spec, sc_spec]
        operands += (k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32))
        kernel = functools.partial(_kernel_scaled, scale=scale, bq=bq,
                                   bk=bk, nk=nk, h=h, g=g, hkv=hkv)
    else:
        kernel = functools.partial(_kernel, scale=scale, bq=bq, bk=bk,
                                   nk=nk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, hd),
                               lambda bh, qi, kj, pos_ref: (bh, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="prefill_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
        interpret=interpret,
    )(*operands)

    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def prefill_attention_supported(q, k, v, q_pos, scale, logits_soft_cap,
                                sliding_window, alibi_slopes,
                                k_scale=None) -> bool:
    """Gate for the sdp_attention prefill dispatch (query-length
    alignment on top of the shared geometry gate)."""
    from bigdl_tpu.ops.pallas.decode_attention import attention_geometry_ok

    return (q.shape[1] >= 2 and q.shape[1] % 128 == 0
            and attention_geometry_ok(q, k, logits_soft_cap,
                                      sliding_window, alibi_slopes,
                                      k_scale))
