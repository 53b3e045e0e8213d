"""Operations and bytes that the algorithm needs, computed from shapes.

A copy of the arithmetic of the program's ``observability/roofline.py``
(``model_flops_per_token``, ``attn_flops_per_token``,
``kv_bytes_per_token``, ``decode_costs``, ``prefill_costs``) taken when
the benchmark was made, so that the yardstick stays put when the
program moves; a test pins the two together at one geometry, and a
later divergence is then a decision. Added here: the packed bytes of
the quantized linears counted by hand, and training operations.

Standard library only. ``Dims`` comes from the ``reference`` block of a
configuration's file, not from the program.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

KV_ELT_BYTES = {"bf16": 2.0, "fp8_e5m2": 1.0, "int8": 1.0, "int4": 0.5}
SCALED_KV = ("int8", "int4")
KV_SCALE_BYTES = 4.0          # one float32 per (token, head) and plane
QUANT_BITS = {"sym_int4": 4}
QUANT_SCALE_BYTES = 2.0       # one bfloat16 per block and column


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden_size: int
    intermediate_size: int
    vocab_size: int
    num_attention_heads: int
    num_key_value_heads: int
    hd: int
    num_hidden_layers: int

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "Dims":
        a = config["reference"]
        return cls(hidden_size=int(a["hidden"]),
                   intermediate_size=int(a["intermediate"]),
                   vocab_size=int(a["vocab"]),
                   num_attention_heads=int(a["heads"]),
                   num_key_value_heads=int(a["kv_heads"]),
                   hd=int(a["head_dim"]),
                   num_hidden_layers=int(a["layers"]))


def model_flops_per_token(cfg) -> int:
    """Forward matmul operations per token: q, k, v, o, gated MLP and
    the output head; no attention over the cache."""
    d, ff, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd
    proj = 2 * (d * h * hd + 2 * d * hkv * hd + h * hd * d)
    return cfg.num_hidden_layers * (proj + 2 * 3 * d * ff) + 2 * d * v


def attn_flops_per_token(cfg, seq_len: int) -> int:
    """QK^T and PV for one query token over ``seq_len`` keys."""
    return (cfg.num_hidden_layers * 2 * 2 * cfg.num_attention_heads
            * cfg.hd * seq_len)


def kv_bytes_per_token(cfg, seq_len: int, kv_cache_dtype: str = "bf16"
                       ) -> float:
    """Cache bytes one decoded token has to read at cache length
    ``seq_len``: K and V of every layer, plus the scale planes of a
    block-scaled cache."""
    elt = KV_ELT_BYTES[kv_cache_dtype]
    l_, hkv, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads, cfg.hd)
    b = 2.0 * l_ * seq_len * hkv * hd * elt
    if kv_cache_dtype in SCALED_KV:
        b += 2.0 * l_ * seq_len * hkv * KV_SCALE_BYTES
    return b


def decode_costs(cfg, weight_bytes: float, seq_len: int,
                 kv_cache_dtype: str = "bf16", batch: int = 1
                 ) -> Dict[str, float]:
    """One decode step: weights read once for the batch, the live cache
    once per row."""
    flops = float(batch) * (model_flops_per_token(cfg)
                            + attn_flops_per_token(cfg, seq_len))
    hbm = float(weight_bytes) + float(batch) * kv_bytes_per_token(
        cfg, seq_len, kv_cache_dtype)
    return {"flops": flops, "hbm_bytes": hbm}


def prefill_costs(cfg, prompt_len: int, batch: int = 1) -> Dict[str, float]:
    """Prefill of ``prompt_len`` tokens: matmuls per token plus the
    causal triangle of attention."""
    flops = float(batch) * (
        prompt_len * model_flops_per_token(cfg)
        + cfg.num_hidden_layers * 2 * 2 * cfg.num_attention_heads * cfg.hd
        * (prompt_len * prompt_len // 2))
    return {"flops": flops}


def quantized_linear_bytes(k: int, n: int, qtype: str, block: int) -> float:
    """Bytes of one block-quantized ``[K, N]`` linear as stored: packed
    codes plus one scale per block and column."""
    bits = QUANT_BITS[qtype]
    return k * n * bits / 8.0 + (k // block) * n * QUANT_SCALE_BYTES


def linear_weight_bytes(cfg, qtype: str, block: int) -> float:
    """Packed bytes of every linear a decode step reads: the seven
    projections of each layer and the output head."""
    d, ff, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd
    shapes = [(d, h * hd), (d, hkv * hd), (d, hkv * hd), (h * hd, d),
              (d, ff), (d, ff), (ff, d)]
    per_layer = sum(quantized_linear_bytes(k, n, qtype, block)
                    for k, n in shapes)
    return (cfg.num_hidden_layers * per_layer
            + quantized_linear_bytes(d, v, qtype, block))


def train_flops_per_token(cfg, seq_len: int, frozen_base: bool = True
                          ) -> float:
    """Operations one token of a training step needs: forward, and the
    backward's activation gradients (each once the forward's matmuls);
    with a frozen base no weight gradients of the base. Causal attention
    at mean depth ``seq_len / 2``. Recomputation is not counted."""
    fwd = model_flops_per_token(cfg) + attn_flops_per_token(
        cfg, max(1, seq_len // 2))
    return fwd * (2.0 if frozen_base else 3.0)


def decode_kv_bytes(cfg, records, kv_cache_dtype: str, a: float, b: float
                    ) -> float:
    """Cache bytes the decode steps of ``[a, b)`` had to read: for each
    token a client received then, the cache of its request at that
    token's position."""
    total = 0.0
    for r in records:
        got = 0
        for t, k in r.get("chunks", []):
            if a <= t < b:
                for j in range(k):
                    total += kv_bytes_per_token(
                        cfg, r["prompt_tokens"] + got + j, kv_cache_dtype)
            got += k
    return total


def serving_work(config: Dict[str, Any], dims: Dims, records,
                 kv_cache_dtype: str, trace_ab) -> Dict[str, float]:
    """``obs["work"]`` of a traced serving run, computed from shapes:
    what the roofline readers divide by, under the keys their files
    name. ``trace_ab`` is the traced stretch ``(a, b)`` on the records'
    clock, or None where nothing was traced."""
    work = {"linear_weight_bytes": linear_weight_bytes(
        dims, config["quant"], int(config["quant_block"]))}
    if trace_ab is not None:
        work["decode_kv_bytes"] = decode_kv_bytes(
            dims, records, kv_cache_dtype, *trace_ab)
    return work


def training_work(config: Dict[str, Any], dims: Dims,
                  traffic: Dict[str, Any], tokens_per_step: int
                  ) -> Dict[str, float]:
    """``obs["work"]`` of a traced training run."""
    return {"train_flops_per_step": tokens_per_step
            * train_flops_per_token(dims, int(traffic["seq_len"]))}
