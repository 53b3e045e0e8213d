"""Analytical roofline cost model — the single source of truth for
FLOPs / HBM-bytes math shared by the offline efficiency block
(``efficiency``), the serving engine (live ``bigdl_tpu_roofline_util{phase}`` /
``decode_ideal_ms`` gauges), compile_watch (per-jit cost annotation)
and the perf-regression sentinel.

Decode on one chip is HBM-bandwidth-bound: every token reads the whole
packed weight set plus the live KV slice, so the honest efficiency
number is bytes-moved / (latency x peak-BW). Prefill is compute-bound,
so its number is model FLOPs / (latency x peak-FLOPs) — classic MFU.
Chip peaks come from ONE table keyed by the device kind JAX reports
(``CHIP_PEAKS``); a device that is not in it has no roofline.

Import contract: **stdlib only** (``tests/test_observability.py``
enforces that importing ``bigdl_tpu.observability`` pulls in no heavy
deps). Model configs are duck-typed: anything with ``hidden_size``,
``intermediate_size``, ``vocab_size``, ``num_attention_heads``,
``num_key_value_heads``, ``hd`` and ``num_hidden_layers`` works
(LlamaConfig does).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

__all__ = [
    "CHIP_PEAKS",
    "KV_ELT_BYTES",
    "attn_flops_per_token",
    "attribution",
    "chip_peaks",
    "decode_costs",
    "efficiency",
    "jit_costs",
    "kv_bytes_per_token",
    "model_flops_per_token",
    "prefill_costs",
]

# logical storage bytes per KV element (int4 packs two codes per byte);
# scaled dtypes additionally carry fp32 scale planes, accounted in
# kv_bytes_per_token. Mirrors ops/kvcache.py KV_CACHE_DTYPES without
# importing jax.
KV_ELT_BYTES: Dict[str, float] = {
    "bf16": 2.0,
    "fp8_e5m2": 1.0,
    "int8": 1.0,
    "int4": 0.5,
}
_SCALED_KV_DTYPES = ("int8", "int4")
_SCALE_ELT_BYTES = 4.0  # fp32 scale per (token, head) plane


# device_kind (as ``jax.devices()[0].device_kind`` reports it) ->
# (peak bf16 TFLOP/s, peak HBM GB/s) of ONE chip.
CHIP_PEAKS: Dict[str, Tuple[float, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    "TPU v5 lite": (197.0, 819.0),
}


def chip_peaks(device_kind: Optional[str] = None) -> Tuple[float, float]:
    """(peak_bf16_tflops, peak_hbm_gbps) of ``device_kind``, default the
    kind of this process's first device. One definition for the
    efficiency block and the live gauges. A
    kind that is not in ``CHIP_PEAKS`` raises LookupError: a roofline
    share against another chip's peaks is not a number."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)})") from None


def model_flops_per_token(cfg) -> int:
    """Forward matmul FLOPs per token (qkvo + gated mlp + lm_head; no
    attention-over-cache term). Shared by the efficiency block and the
    live gauges so the cost model cannot drift.
    A family whose layers are not q/k/v/o plus one gated MLP (latent
    attention, routed experts) counts its own
    (`cfg.matmul_flops_per_token()`)."""
    own = getattr(cfg, "matmul_flops_per_token", None)
    if own is not None:
        return own()
    d, ff, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd
    proj = 2 * (d * h * hd + 2 * d * hkv * hd + h * hd * d)
    return cfg.num_hidden_layers * (proj + 2 * 3 * d * ff) + 2 * d * v


def attn_flops_per_token(cfg, seq_len: int) -> int:
    """Attention-over-cache FLOPs for one decoded token at cache length
    ``seq_len``: two matmuls (QK^T and PV) over ``seq_len`` keys (a
    latent cache: `cfg.attn_flops_per_cached_token()`, absorbed)."""
    own = getattr(cfg, "attn_flops_per_cached_token", None)
    if own is not None:
        return own() * seq_len
    h, hd = cfg.num_attention_heads, cfg.hd
    return cfg.num_hidden_layers * 2 * 2 * h * hd * seq_len


def kv_bytes_per_token(cfg, seq_len: int,
                       kv_cache_dtype: str = "bf16") -> float:
    """Live KV bytes read for one decoded token at cache length
    ``seq_len``: K and V planes across all layers, plus fp32 scale
    planes for block-scaled dtypes."""
    elt = KV_ELT_BYTES.get(kv_cache_dtype)
    if elt is None:
        raise ValueError(
            f"unknown kv_cache_dtype {kv_cache_dtype!r}; choose from "
            f"{sorted(KV_ELT_BYTES)}")
    values = getattr(cfg, "kv_values_per_position", None)
    if values is not None:
        # the cache counted by its planes: one latent plane of `values`
        # a position and layer, no scale planes
        return float(cfg.num_hidden_layers) * seq_len * values * elt
    l_, hkv, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                   cfg.hd)
    bytes_ = 2.0 * l_ * seq_len * hkv * hd * elt
    if kv_cache_dtype in _SCALED_KV_DTYPES:
        bytes_ += 2.0 * l_ * seq_len * hkv * _SCALE_ELT_BYTES
    return bytes_


def _decode_work(cfg, weight_bytes: int, seq_len: int,
                 kv_cache_dtype: str, batch: int) -> Tuple[float, float]:
    """(flops, hbm_bytes) of one decode step — counts from shapes, no
    chip peaks involved."""
    flops = float(batch) * (model_flops_per_token(cfg)
                            + attn_flops_per_token(cfg, seq_len))
    hbm_bytes = float(weight_bytes) + float(batch) * kv_bytes_per_token(
        cfg, seq_len, kv_cache_dtype)
    return flops, hbm_bytes


def decode_costs(cfg, weight_bytes: int, seq_len: int,
                 kv_cache_dtype: str = "bf16", batch: int = 1,
                 device_kind: Optional[str] = None) -> Dict[str, float]:
    """Analytical cost of one decode step at cache length ``seq_len``:

    - ``flops``: matmul + attention-over-cache FLOPs (per batch row)
    - ``hbm_bytes``: packed weights read once for the whole batch, plus
      the live KV slice per row
    - ``ideal_ms``: bandwidth-bound floor for the step at peak HBM BW
    """
    _, peak_gbps = chip_peaks(device_kind)
    flops, hbm_bytes = _decode_work(cfg, weight_bytes, seq_len,
                                    kv_cache_dtype, batch)
    ideal_ms = hbm_bytes / (peak_gbps * 1e9) * 1e3
    return {"flops": flops, "hbm_bytes": hbm_bytes, "ideal_ms": ideal_ms}


def prefill_costs(cfg, prompt_len: int,
                  batch: int = 1) -> Dict[str, float]:
    """Analytical cost of prefilling ``prompt_len`` tokens: per-token
    matmul FLOPs plus the causal-attention triangle (same
    ``prompt_len**2 // 2`` accounting as the bench efficiency block)."""
    l_ = cfg.num_hidden_layers
    h, hd = cfg.num_attention_heads, cfg.hd
    flops = float(batch) * (
        prompt_len * model_flops_per_token(cfg)
        + l_ * 2 * 2 * h * hd * (prompt_len * prompt_len // 2))
    return {"flops": flops}


def efficiency(cfg, weight_bytes: int, prompt_len: int, steps: int,
               first_ms: float, next_ms: float,
               device_kind: Optional[str] = None) -> dict:
    """MFU + HBM-roofline utilization of one measured (first, next)
    token latency pair (``tests/test_perf_observability.py`` pins the
    formulas on a fixture and asserts the live gauges agree).

    ``weight_bytes`` is measured from the live param pytree in the
    config subprocess and passed through. The KV term deliberately
    keeps the bench's bf16-cache accounting (the headline lane decodes
    against a bf16 cache) — kv-dtype-aware live gauges go through
    :func:`decode_costs` instead."""
    peak_tflops, peak_gbps = chip_peaks(device_kind)

    l_ = cfg.num_hidden_layers
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd
    flops_tok = model_flops_per_token(cfg)
    # attention FLOPs per token at cache length S: 2 matmuls over S keys
    s_mid = prompt_len + steps // 2
    attn_tok = l_ * 2 * 2 * h * hd * s_mid

    # bytes read per decode token: all packed weights + live KV slice
    kv_elt_bytes = 2  # bf16 cache
    kv_bytes = 2 * l_ * s_mid * hkv * hd * kv_elt_bytes
    ideal_decode_ms = (weight_bytes + kv_bytes) / (peak_gbps * 1e9) * 1e3

    # prefill MFU over the whole prompt
    prefill_flops = prompt_len * flops_tok + l_ * 2 * 2 * h * hd * (
        prompt_len * prompt_len // 2)
    prefill_mfu = prefill_flops / (first_ms / 1e3) / (peak_tflops * 1e12)

    decode_mfu = (flops_tok + attn_tok) / (next_ms / 1e3) / (
        peak_tflops * 1e12)
    return {
        "decode_hbm_roofline_util": round(ideal_decode_ms / next_ms, 4),
        "decode_ideal_ms": round(ideal_decode_ms, 6),
        "decode_mfu": round(decode_mfu, 5),
        "prefill_mfu": round(prefill_mfu, 4),
        "weight_bytes": int(weight_bytes),
        "peak_bf16_tflops": peak_tflops,
        "peak_hbm_gbps": peak_gbps,
    }


def attribution(cfg, weight_bytes: int, prompt_len: int, steps: int,
                first_ms: float, next_ms: float,
                kv_cache_dtype: str = "bf16",
                device_kind: Optional[str] = None) -> dict:
    """Per-phase roofline attribution block embedded in bench JSON:
    analytical FLOPs / HBM bytes / ideal ms next to the measured ms, so
    a bench record carries *why* a phase is slow, not just that it is."""
    peak_tflops, peak_gbps = chip_peaks(device_kind)
    s_mid = prompt_len + steps // 2
    dec = decode_costs(cfg, weight_bytes, s_mid, kv_cache_dtype,
                       device_kind=device_kind)
    pre = prefill_costs(cfg, prompt_len)
    prefill_ideal_ms = pre["flops"] / (peak_tflops * 1e12) * 1e3
    return {
        "prefill": {
            "flops": int(pre["flops"]),
            "ideal_ms": round(prefill_ideal_ms, 6),
            "measured_ms": round(first_ms, 3),
            "mfu": round(pre["flops"] / (first_ms / 1e3)
                         / (peak_tflops * 1e12), 4),
        },
        "decode": {
            "flops": int(dec["flops"]),
            "hbm_bytes": int(dec["hbm_bytes"]),
            "ideal_ms": round(dec["ideal_ms"], 6),
            "measured_ms": round(next_ms, 3),
            "hbm_roofline_util": round(dec["ideal_ms"] / next_ms, 4),
        },
        "kv_cache_dtype": kv_cache_dtype,
        "peak_bf16_tflops": peak_tflops,
        "peak_hbm_gbps": peak_gbps,
    }


def jit_costs(cfg, weight_bytes: int, max_batch: int, max_seq: int,
              prefill_bucket: int,
              kv_cache_dtype: str = "bf16") -> Dict[str, Dict[str, float]]:
    """Analytical {flops, hbm_bytes} per tracked_jit name, for
    compile_watch cost annotation (the "top offenders" view ranks jits
    by bytes moved). Worst-case shapes: decode at full cache, prefill
    at one bucket."""
    dec_flops, dec_bytes = _decode_work(cfg, weight_bytes, max_seq,
                                        kv_cache_dtype, max_batch)
    pre = prefill_costs(cfg, prefill_bucket)
    kv_full = float(max_batch) * kv_bytes_per_token(
        cfg, max_seq, kv_cache_dtype)
    costs: Dict[str, Dict[str, float]] = {
        "engine_decode": {"flops": dec_flops, "hbm_bytes": dec_bytes},
        "engine_decode_resident": {"flops": dec_flops,
                                   "hbm_bytes": dec_bytes},
        "engine_prefill": {"flops": pre["flops"],
                           "hbm_bytes": float(weight_bytes)},
        # insert touches one row's KV planes; argmax/sample/health are
        # O(vocab) epsilon next to a forward pass
        "engine_insert": {"flops": 0.0,
                          "hbm_bytes": kv_full / max(max_batch, 1)},
        "engine_argmax": {
            "flops": float(max_batch * cfg.vocab_size),
            "hbm_bytes": float(2 * max_batch * cfg.vocab_size)},
        "engine_sample_device": {
            "flops": float(max_batch * cfg.vocab_size),
            "hbm_bytes": float(2 * max_batch * cfg.vocab_size)},
        "engine_health": {
            "flops": float(max_batch * cfg.vocab_size),
            "hbm_bytes": float(2 * max_batch * cfg.vocab_size)},
    }
    return costs
