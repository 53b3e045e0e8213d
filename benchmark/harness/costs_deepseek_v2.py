"""Operations and bytes a DeepSeek-V2 configuration needs, computed
from shapes: what the roofline readers of its cells divide by.

Standard library only. ``Dims`` comes from the ``reference`` block of
the configuration's file, not from the program. Bytes are the packed
codes and scales of a block-quantized linear as the program stores it
(``costs.quantized_linear_bytes``: 0.5625 B a parameter at sym_int4,
block 32, bf16 scales).

- ``expert_bytes``: the three linears of ONE routed expert. A decode
  step reads it once for every held expert that some token chose, and
  not at all for the others.
- ``dense_layer_bytes`` / ``expert_layer_bytes``: everything a step
  reads of a layer when every held expert is hit (376 MB at the
  published widths with 20 experts held).
- ``latent_bytes_per_position``: the cached row of one position of one
  layer (576 bf16 values, 1,152 B).
- ``absorbed_flops_per_position``: absorbed decode attention of one
  query token against one cached position of one layer: every head's
  score over the 576-wide row and its weighted sum of the 512 value
  columns, ``2 * heads * (2 * kv_lora_rank + rope)``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from harness.costs import KV_ELT_BYTES, quantized_linear_bytes


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden_size: int
    vocab_size: int
    num_attention_heads: int
    num_hidden_layers: int
    q_lora_rank: int              # 0: no query compression
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    dense_intermediate: int
    moe_intermediate: int
    n_shared_experts: int
    experts_total: int
    held: int
    experts_per_tok: int
    first_k_dense: int

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "Dims":
        a = config["reference"]
        return cls(
            hidden_size=int(a["hidden"]), vocab_size=int(a["vocab"]),
            num_attention_heads=int(a["heads"]),
            num_hidden_layers=int(a["layers"]),
            q_lora_rank=int(a.get("q_lora_rank") or 0),
            kv_lora_rank=int(a["kv_lora_rank"]),
            qk_nope_head_dim=int(a["qk_nope_head_dim"]),
            qk_rope_head_dim=int(a["qk_rope_head_dim"]),
            v_head_dim=int(a["v_head_dim"]),
            dense_intermediate=int(a["dense_intermediate"]),
            moe_intermediate=int(a["moe_intermediate"]),
            n_shared_experts=int(a["n_shared_experts"]),
            experts_total=int(a["experts_total"]), held=int(a["held"]),
            experts_per_tok=int(a["experts_per_tok"]),
            first_k_dense=int(a["first_k_dense"]))

    @property
    def dense_layers(self) -> int:
        return min(self.first_k_dense, self.num_hidden_layers)

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.dense_layers

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim


def _swiglu_bytes(d: int, f: int, qtype: str, block: int) -> float:
    return (2 * quantized_linear_bytes(d, f, qtype, block)
            + quantized_linear_bytes(f, d, qtype, block))


def attention_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of one layer's attention linears, ``W_kvb`` counted
    as the quantized linear it is published as."""
    d, h = dims.hidden_size, dims.num_attention_heads
    qk = dims.qk_nope_head_dim + dims.qk_rope_head_dim
    q = quantized_linear_bytes
    if dims.q_lora_rank:
        query = (q(d, dims.q_lora_rank, qtype, block)
                 + q(dims.q_lora_rank, h * qk, qtype, block))
    else:
        query = q(d, h * qk, qtype, block)
    return (query + q(d, dims.latent_dim, qtype, block)
            + q(dims.kv_lora_rank,
                h * (dims.qk_nope_head_dim + dims.v_head_dim), qtype, block)
            + q(h * dims.v_head_dim, d, qtype, block))


def expert_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of one routed expert."""
    return _swiglu_bytes(dims.hidden_size, dims.moe_intermediate, qtype,
                         block)


def dense_layer_bytes(dims: Dims, qtype: str, block: int) -> float:
    return attention_bytes(dims, qtype, block) + _swiglu_bytes(
        dims.hidden_size, dims.dense_intermediate, qtype, block)


def expert_layer_bytes(dims: Dims, qtype: str, block: int) -> float:
    """An expert layer with every held expert read: attention, the
    shared experts (one MLP), the held routed experts."""
    return (attention_bytes(dims, qtype, block)
            + _swiglu_bytes(dims.hidden_size,
                            dims.n_shared_experts * dims.moe_intermediate,
                            qtype, block)
            + dims.held * expert_bytes(dims, qtype, block))


def linear_weight_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of every DENSE linear a decode step reads: all of
    the model but the routed experts, with the output head."""
    routed = dims.held * expert_bytes(dims, qtype, block)
    return (dims.dense_layers * dense_layer_bytes(dims, qtype, block)
            + dims.expert_layers * (expert_layer_bytes(dims, qtype, block)
                                    - routed)
            + quantized_linear_bytes(dims.hidden_size, dims.vocab_size,
                                     qtype, block))


def latent_bytes_per_position(dims: Dims, kv_cache_dtype: str = "bf16"
                              ) -> float:
    """One layer's cached row of one position."""
    return dims.latent_dim * KV_ELT_BYTES[kv_cache_dtype]


def absorbed_flops_per_position(dims: Dims) -> float:
    """Absorbed decode attention of one token over one cached position
    of one layer."""
    return 2.0 * dims.num_attention_heads * (2 * dims.kv_lora_rank
                                             + dims.qk_rope_head_dim)


def kv_bytes_per_token(dims: Dims, seq_len: int,
                       kv_cache_dtype: str = "bf16") -> float:
    """Cache bytes one decoded token has to read at cache length
    ``seq_len``: the latent rows of every layer."""
    return (dims.num_hidden_layers * seq_len
            * latent_bytes_per_position(dims, kv_cache_dtype))


def decode_positions(records, a: float, b: float) -> float:
    """Cached positions the decode steps of ``[a, b)`` attended to: for
    each token a client received then, the length of its request's
    cache at that token."""
    total = 0.0
    for r in records:
        got = 0
        for t, k in r.get("chunks", []):
            if a <= t < b:
                total += k * (r["prompt_tokens"] + got) + k * (k - 1) / 2.0
            got += k
    return total


def serving_work(config: Dict[str, Any], dims: Dims, records,
                 kv_cache_dtype: str, trace_ab) -> Dict[str, float]:
    """``obs["work"]`` of a traced serving run."""
    qtype, block = config["quant"], int(config["quant_block"])
    work = {
        "linear_weight_bytes": linear_weight_bytes(dims, qtype, block),
        "expert_bytes": expert_bytes(dims, qtype, block),
        "expert_layers": float(dims.expert_layers),
        "held_experts": float(dims.held),
    }
    if trace_ab is not None:
        positions = decode_positions(records, *trace_ab)
        work["decode_latent_bytes"] = (
            positions * dims.num_hidden_layers
            * latent_bytes_per_position(dims, kv_cache_dtype))
        work["decode_absorbed_flops"] = (
            positions * dims.num_hidden_layers
            * absorbed_flops_per_position(dims))
    return work


def training_work(config: Dict[str, Any], dims: Dims,
                  traffic: Dict[str, Any], tokens_per_step: int
                  ) -> Dict[str, float]:
    raise NotImplementedError(
        "no training cell runs a DeepSeek-V2 configuration: QLoRA through "
        "a routed layer is not implemented (PERF.md 7)")
