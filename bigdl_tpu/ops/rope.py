"""Rotary position embeddings.

TPU-native equivalent of the reference's rotary kernels
(`linear_q4_0.apply_rotary_embedding_half_q_and_k`, reference
transformers/models/utils.py:203-217, and the training-mode
`FastRopeEmbedding` at transformers/layers/rope_embedding.py:40-67).
Pure-JAX: XLA fuses the mul/add chain into surrounding ops; a custom VJP is
unnecessary since the ops are natively differentiable.

Supports the "half-rotation" (llama/mistral/qwen) and "interleaved"
(gptj/gptneox-rotary, chatglm) conventions, plus linear/NTK ("dynamic")
scaling as used by the reference's long-context model variants.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rope_freqs(
    head_dim: int,
    base: float = 10000.0,
    rotary_dim: Optional[int] = None,
    scaling_factor: float = 1.0,
) -> jax.Array:
    """Inverse frequencies [rotary_dim // 2] (f32), linear scaling only."""
    rd = rotary_dim or head_dim
    exponent = jnp.arange(0, rd, 2, dtype=jnp.float32) / rd
    inv_freq = 1.0 / (base ** exponent)
    return inv_freq / scaling_factor


def scaled_rope_freqs(
    head_dim: int,
    base: float,
    scaling: dict,
    rotary_dim: Optional[int] = None,
    max_position_embeddings: int = 4096,
):
    """(inv_freq [rd//2], attention_factor) for every HF rope_scaling type.

    Long-context rope variants the reference only reaches via per-model
    forks (chatglm2_32k etc., convert.py:862-888) are first-class here:
    linear, dynamic-NTK (static form), yarn (with the ln-scaled attention
    factor), and llama3's piecewise frequency remapping.
    """
    import math

    rd = rotary_dim or head_dim
    rtype = scaling.get("rope_type", scaling.get("type", "linear"))
    factor = float(scaling.get("factor", 1.0))
    half = jnp.arange(0, rd, 2, dtype=jnp.float32)

    if rtype in ("default", "none"):
        return rope_freqs(head_dim, base, rotary_dim), 1.0
    if rtype == "linear":
        return rope_freqs(head_dim, base, rotary_dim, factor), 1.0
    if rtype in ("dynamic", "ntk"):
        # static NTK-aware base adjustment at the scaled context length
        base = base * (factor ** (rd / (rd - 2)))
        return 1.0 / (base ** (half / rd)), 1.0
    if rtype == "llama3":
        inv = 1.0 / (base ** (half / rd))
        orig = float(scaling.get("original_max_position_embeddings", 8192))
        lo_f = float(scaling.get("low_freq_factor", 1.0))
        hi_f = float(scaling.get("high_freq_factor", 4.0))
        low_wl = orig / lo_f
        high_wl = orig / hi_f
        wavelen = 2.0 * jnp.pi / inv
        smooth = (orig / wavelen - lo_f) / (hi_f - lo_f)
        mid = (1.0 - smooth) * inv / factor + smooth * inv
        out = jnp.where(wavelen > low_wl, inv / factor, inv)
        out = jnp.where((wavelen <= low_wl) & (wavelen >= high_wl), mid, out)
        return out, 1.0
    if rtype == "yarn":
        orig = float(scaling.get("original_max_position_embeddings",
                                 max_position_embeddings))
        beta_fast = float(scaling.get("beta_fast", 32.0))
        beta_slow = float(scaling.get("beta_slow", 1.0))
        inv = 1.0 / (base ** (half / rd))

        def correction_dim(n_rot):
            return (rd * math.log(orig / (n_rot * 2 * math.pi))
                    / (2 * math.log(base)))

        low = math.floor(correction_dim(beta_fast))
        high = math.ceil(correction_dim(beta_slow))
        low, high = max(low, 0), min(high, rd - 1)
        span = max(high - low, 1e-3)
        ramp = jnp.clip((jnp.arange(rd // 2, dtype=jnp.float32) - low)
                        / span, 0.0, 1.0)
        extrap_mask = 1.0 - ramp     # 1 where NO interpolation (high freq)
        out = (inv / factor) * ramp + inv * extrap_mask
        attn = float(scaling.get(
            "attention_factor", 0.1 * math.log(factor) + 1.0))
        return out, attn
    raise NotImplementedError(f"rope_scaling type {rtype!r} not supported")


def deepseek_yarn_freqs(
    rotary_dim: int,
    base: float,
    scaling: dict,
    max_position_embeddings: int = 4096,
):
    """DeepSeek-V2's YaRN: `(inv_freq [rd//2], cos_sin_factor,
    softmax_scale_factor)`.

    The frequencies are YaRN's (the ramp between `beta_fast` and
    `beta_slow` over `original_max_position_embeddings`), what
    `scaled_rope_freqs` returns for `"yarn"`. The magnitudes are not: HF's
    `DeepseekV2YarnRotaryEmbedding` multiplies cos and sin by
    `yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)`
    (1 when the two are equal, as published) and `DeepseekV2Attention`
    multiplies the softmax scale by `yarn_mscale(factor, mscale_all_dim)`
    squared, with `yarn_mscale(f, m) = 0.1 * m * ln(f) + 1` for f > 1.
    Other families' `"yarn"` (`0.1 ln(factor) + 1` on cos and sin) stays
    as it is."""
    import math

    inv_freq, _ = scaled_rope_freqs(
        rotary_dim, base, dict(scaling, rope_type="yarn", type="yarn"),
        rotary_dim=rotary_dim,
        max_position_embeddings=max_position_embeddings)
    factor = float(scaling.get("factor", 1.0))

    def mscale(m):
        return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0

    all_dim = float(scaling.get("mscale_all_dim", 0.0))
    cos_sin = mscale(float(scaling.get("mscale", 1.0))) / mscale(all_dim)
    softmax = mscale(all_dim) ** 2 if all_dim else 1.0
    return inv_freq, cos_sin, softmax


def rope_cos_sin(
    positions: jax.Array,  # [...] int positions
    inv_freq: jax.Array,   # [rd // 2]
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables [..., rd // 2] for given positions (f32)."""
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def rope_tables(positions: jax.Array, kinds) -> dict:
    """cos/sin tables of SEVERAL rotary geometries in one model, for the
    same positions: `kinds` maps a name to `(rotary_dim, theta)` (layers
    of two kinds, each with its own base); returns `{name: (cos, sin)}`,
    each `[..., rotary_dim // 2]`."""
    return {name: rope_cos_sin(positions, rope_freqs(rd, theta))
            for name, (rd, theta) in kinds.items()}


def _rotate_half(x: jax.Array) -> jax.Array:
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


def apply_rope(
    x: jax.Array,           # [..., seq, heads, head_dim] or [..., seq, head_dim]
    cos: jax.Array,         # [..., seq, rd // 2]
    sin: jax.Array,
    interleaved: bool = False,
) -> jax.Array:
    """Apply rotary embedding over the last dim's first 2*(rd//2) channels.

    cos/sin are broadcast over the heads axis; pass tables built from the
    *same* positions used to index the KV cache.
    """
    dt = x.dtype
    rd2 = cos.shape[-1]
    rd = rd2 * 2
    xf = x.astype(jnp.float32)
    x_rot, x_pass = xf[..., :rd], xf[..., rd:]

    if x.ndim == cos.ndim + 1:
        # insert heads axis: [..., seq, 1, rd2]
        cos = cos[..., None, :]
        sin = sin[..., None, :]

    if interleaved:
        x1 = x_rot[..., 0::2]
        x2 = x_rot[..., 1::2]
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        out = jnp.stack([o1, o2], axis=-1).reshape(x_rot.shape)
    else:
        cs = jnp.concatenate([cos, cos], axis=-1)
        sn = jnp.concatenate([sin, sin], axis=-1)
        out = x_rot * cs + _rotate_half(x_rot) * sn

    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out.astype(dt)
