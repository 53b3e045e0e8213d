"""The plain reference: a decoder forward pass in straightforward
``jax.numpy`` and float32, one layer at a time, with no kernel, no cache
and no batching, on weights dequantized by plain arithmetic.

It follows the published layer equations of the two attention styles
the configurations use, told apart by the ``reference`` block of the
configuration's file and never by a model's name:

- grouped-query attention, ``hkv`` key/value heads shared by groups of
  ``h // hkv`` query heads, causal, optionally a sliding window;
- rotary embedding over the first ``rotary_dim`` channels of a head,
  either half-rotation (channel i pairs with i + rotary_dim/2) or
  interleaved (channel 2i pairs with 2i+1);
- optional bias on the q/k/v projections;
- RMSNorm before attention and before the gated-SiLU MLP; residuals in
  float32.

Departures from the published models: weights are the seeded random
block-quantized planes the program serves (that is the configuration),
dequantized here to float32 as ``(code - 8) * scale``; nothing else.

The only thing taken from the program is the storage layout of a
quantized plane (documented at ``unpack_sym_int4``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence

# plain arithmetic every reference shares; a new reference imports
# these (and ``unpack_sym_int4``) from here
from harness.common import next_token_loss, relative_l2  # noqa: F401


def unpack_sym_int4(data, scale, block: int):
    """Packed sym_int4 planes -> float32 ``[K, N]``.

    Layout (the program's ``ops/quant.py`` ``QTensor`` docstring):
    ``data`` uint8 ``[K//2, N]``; within each block of ``block`` rows
    along K, byte row j holds row j in its low nibble and row
    j + block/2 in its high nibble. ``scale`` ``[K//block, N]``. A code
    c in 0..15 stands for ``(c - 8) * scale``."""
    import jax.numpy as jnp

    k2, n = data.shape
    b2 = block // 2
    blk = data.astype(jnp.uint8).reshape(k2 // b2, b2, n)
    lo = (blk & 0x0F).astype(jnp.float32)
    hi = (blk >> 4).astype(jnp.float32)
    codes = jnp.concatenate([lo, hi], axis=1)          # [K/block, block, N]
    w = (codes - 8.0) * scale.astype(jnp.float32)[:, None, :]
    return w.reshape(k2 * 2, n)


def _dense(leaf, quant: Dict[str, Any]):
    """A linear leaf of the canonical tree -> float32 ``[K, N]``."""
    import jax.numpy as jnp

    if hasattr(leaf, "data") and hasattr(leaf, "scale"):
        if quant["qtype"] != "sym_int4":
            raise NotImplementedError(
                f"the reference dequantizes sym_int4 only, not "
                f"{quant['qtype']!r}")
        return unpack_sym_int4(leaf.data, leaf.scale, int(quant["block"]))
    return leaf.astype(jnp.float32)


def _rms_norm(x, w, eps: float):
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, positions, theta: float, rotary_dim: int, interleaved: bool):
    """x ``[S, H, hd]`` float32."""
    import jax.numpy as jnp

    half = rotary_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                           / rotary_dim))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    if interleaved:
        a, b = rot[..., 0::2], rot[..., 1::2]
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                        axis=-1).reshape(rot.shape)
    else:
        a, b = rot[..., :half], rot[..., half:]
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                              axis=-1)
    return jnp.concatenate([out, rest], axis=-1)


def _layer(x, lp, arch: Dict[str, Any], quant: Dict[str, Any]):
    """One decoder layer on ``x`` ``[S, D]`` float32; ``lp`` holds this
    layer's leaves (split q/k/v/o and gate/up/down)."""
    import jax
    import jax.numpy as jnp

    h, hkv, hd = arch["heads"], arch["kv_heads"], arch["head_dim"]
    s = x.shape[0]
    pos = jnp.arange(s)
    y = _rms_norm(x, lp["input_layernorm"], arch["norm_eps"])

    def proj(name):
        out = y @ _dense(lp[name], quant)
        b = lp.get(f"{name}_bias")
        return out + b.astype(jnp.float32) if b is not None else out

    q = proj("q_proj").reshape(s, h, hd)
    k = proj("k_proj").reshape(s, hkv, hd)
    v = proj("v_proj").reshape(s, hkv, hd)
    rope = arch["rope"]
    q = _rope(q, pos, rope["theta"], rope["rotary_dim"], rope["interleaved"])
    k = _rope(k, pos, rope["theta"], rope["rotary_dim"], rope["interleaved"])
    group = h // hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / math.sqrt(hd)
    allowed = pos[None, :] <= pos[:, None]
    window = arch.get("sliding_window")
    if window:
        allowed = allowed & (pos[None, :] > pos[:, None] - int(window))
    scores = jnp.where(allowed[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("hst,thd->shd", probs, v).reshape(s, h * hd)
    x = x + attn @ _dense(lp["o_proj"], quant)
    y = _rms_norm(x, lp["post_attention_layernorm"], arch["norm_eps"])
    gate = y @ _dense(lp["gate_proj"], quant)
    up = y @ _dense(lp["up_proj"], quant)
    return x + (jax.nn.silu(gate) * up) @ _dense(lp["down_proj"], quant)


def all_logits(params: Dict[str, Any], arch: Dict[str, Any],
               quant: Dict[str, Any], token_ids: Sequence[int],
               first: int = 0):
    """Float32 logits ``[S - first, V]`` of the positions of
    ``token_ids`` from ``first`` on, on the canonical (split-projection)
    tree ``params``. Every position attends to the whole sequence before
    it; ``first`` only spares the output head on positions nobody
    compares (a 4,800-token prompt ahead of 100 served tokens)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda x, lp: _layer(x, lp, arch, quant))
        ids = jnp.asarray(list(token_ids), jnp.int32)
        x = params["embed_tokens"][ids].astype(jnp.float32)
        layers = {k: v for k, v in params["layers"].items()
                  if "lora" not in k}
        n_layers = layers["input_layernorm"].shape[0]
        for i in range(n_layers):
            x = layer(x, jax.tree.map(lambda a, i=i: a[i], layers))
        # weights are arguments, never closed over: a closed-over array
        # is baked into the executable as a constant of its size
        head = jax.jit(lambda x, norm, lm_head: _rms_norm(
            x, norm, arch["norm_eps"]) @ _dense(lm_head, quant))
        return head(x[first:], params["norm"], params["lm_head"])


def last_logits(params: Dict[str, Any], arch: Dict[str, Any],
                quant: Dict[str, Any], token_ids: Sequence[int]):
    """Float32 logits ``[V]`` of the last position of ``token_ids``."""
    return all_logits(params, arch, quant, token_ids)[-1]


SCALED_KV_FACTOR = 1.9


def tolerance(config: Dict[str, Any], kv_cache_dtype: str) -> float:
    """Bound on the program's relative L2 distance from this reference,
    for the configuration ``config`` (its ``reference`` block gives the
    depth) with a cache of ``kv_cache_dtype``.

    The program keeps activations in bfloat16 (the configuration's
    compute type) and accumulates each matmul in float32, so every layer
    rounds its residual stream and about eight intermediate tensors to 8
    bits of mantissa: a relative error of about 2**-9 (RMS of a rounding
    to 2**-8) each, independent, walking randomly through the depth:
    2**-9 * sqrt(9 * layers), 0.033 at 32 layers. The estimate counts
    roundings and not how each is amplified on its way to the logits;
    the chip measured 0.051 at 32 layers with a bf16 cache (my chip
    run, PR 24, two seeds), so the bound is 2.4 times the estimate,
    0.080 there: half as much again as what was measured. A block-scaled
    int8 cache rounds every key and value once more (the program's
    prefill reads them back quantized): the chip measured 0.087 and
    0.097 at 28 layers (my chip run, PR 24, two seeds) against 0.048 by
    the bf16 rule, so such a cache widens the bound by
    ``SCALED_KV_FACTOR``, to 0.141 there. Computing in
    a lower precision than the configuration states breaks it: int4
    instead of int8 KV puts a 2**-4 error on every key and value, and
    bfloat16 accumulation of a 4096-long dot product adds
    2**-9 * sqrt(4096 / 8) per matmul, each several times the bound.

    Decode through the cache (PR 27: prefill of 32 tokens into a cache
    of the cell's kind, then 8 forced tokens one at a time) reads what
    prefill reads: the chip measured 0.053-0.058 through the bf16 slab
    of 2048 positions beside a prefill of 0.052-0.062 at 32 layers (20
    runs), and 0.085-0.096 through int8 pages of 128 behind a block
    table beside a prefill of 0.082-0.101 at 28 layers (18 runs; my
    chip runs, PR 27), so one bound holds both. The nearest lower
    precision of the cache reads 0.48-0.61 (fp8_e5m2 for bf16) and
    0.87-0.95 (int4 for int8), three seeds each."""
    layers = int(config["reference"]["layers"])
    bound = 2.4 * 2.0 ** -9 * math.sqrt(9.0 * layers)
    return bound * (1.0 if kv_cache_dtype == "bf16" else SCALED_KV_FACTOR)


# limit = factor * tolerance(config, kv); the readings behind each
# factor are in ``served_gap_limits``'s docstring
SERVED_GAP_FACTORS = {"prefill_gap_max": 6.0, "decode_gap_max": 6.0,
                      "decode_gap_mean": 0.5}


def served_gap_limits(config: Dict[str, Any], kv_cache_dtype: str
                      ) -> Dict[str, float]:
    """Limits on what ``served.compare`` reads: how far a served
    token's reference logit may lie below the reference's best, in
    standard deviations of the position's logits.

    The logits of a position have nearly zero mean, so a relative L2
    error of e between program and reference is an error of about e
    standard deviations on every logit. A greedy server picks the best
    of ITS logits; the reference then finds that token below its own
    best by at most the difference of two such errors (standard
    deviation e * sqrt(2)), and by nothing at all where its two best lie
    further apart than that. So the gaps go with ``tolerance``. With e
    about 0.7 of the tolerance, 6 tolerances are 6 standard deviations
    of that difference: no rounding reaches it, and a token from a wrong
    row, page or position, the best of unrelated logits, reads 2 to 5
    standard deviations of the LOGITS, four times the limit and more.
    The mean gap (most tokens read 0) goes with e squared.

    Readings (my chip runs, PR 27; 4 requests and 256-800 served tokens
    a run), as prefill's widest / decode's widest / decode's mean.
    bf16 slab at 32 layers (tolerance 0.0795, limits 0.477 / 0.477 /
    0.040): sound, 21 runs, at most 0.097 / 0.249 / 0.0114; fp8_e5m2
    in its place, 3 seeds, at least 0.81 / 2.43 / 0.64. int8 pages at
    28 layers (tolerance 0.1414, limits 0.848 / 0.848 / 0.071): sound,
    19 runs, at most 0.355 / 0.414 / 0.0229; int4 in their place, 3
    seeds, at least 3.14 / 4.26 / 1.73."""
    tol = tolerance(config, kv_cache_dtype)
    return {k: f * tol for k, f in SERVED_GAP_FACTORS.items()}
