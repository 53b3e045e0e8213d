"""Operations and bytes a dots3-note configuration needs, computed from
shapes: what the roofline readers of its cells divide by.

Standard library only. ``Dims`` comes from the ``reference`` block of
the configuration's file, not from the program. Bytes are the packed
codes and scales of a block-quantized linear as the program stores it
(``costs.quantized_linear_bytes``: 0.5625 B a parameter at sym_int4,
block 32, bf16 scales).

Per cached position and layer, for one decoded token:

- ``index_bytes_per_position``: the index key a full layer's indexer
  reads of EVERY live position (128 bf16 values, 256 B);
- ``sparse_bytes_per_position`` / ``sparse_flops_per_position``: the
  latent row (576 values, 1,152 B) and the absorbed products (2 x 128 x
  1088) of a SELECTED position: at most ``index_topk`` a token and full
  layer, whatever the kernel reads;
- ``window_bytes_per_position`` / ``window_flops_per_position``: the
  latent row (1,088 values, 2,176 B) and the absorbed products (2 x 64 x
  2112) of a position inside the window, at most ``window`` of them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

from harness.costs import KV_ELT_BYTES, quantized_linear_bytes

FULL = "full_attention"


@dataclasses.dataclass(frozen=True)
class Kind:
    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope: int
    rope: int
    v: int
    window: int = 0

    @classmethod
    def of(cls, a: Dict[str, Any]) -> "Kind":
        return cls(int(a["heads"]), int(a["q_lora_rank"]),
                   int(a["kv_lora_rank"]), int(a["nope"]), int(a["rope"]),
                   int(a["v"]), int(a.get("window", 0)))

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.rope

    def absorbed_flops_per_position(self) -> float:
        return 2.0 * self.heads * (2 * self.kv_lora_rank + self.rope)


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden_size: int
    vocab_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    full: Kind
    window: Kind
    index_heads: int
    index_dim: int
    index_topk: int
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
        ix = a["index"]
        return cls(
            hidden_size=int(a["hidden"]), vocab_size=int(a["vocab"]),
            num_hidden_layers=int(a["layers"]),
            layer_types=tuple(a["layer_types"]),
            full=Kind.of(a["full"]), window=Kind.of(a["window"]),
            index_heads=int(ix["heads"]), index_dim=int(ix["dim"]),
            index_topk=int(ix["topk"]),
            dense_intermediate=int(a["dense_intermediate"]),
            moe_intermediate=int(a["moe_intermediate"]),
            n_shared_experts=int(a["n_shared_experts"]),
            experts_total=int(a["experts_total"]), held=int(a["held"]),
            experts_per_tok=int(a["experts_per_tok"]),
            first_k_dense=int(a["first_k_dense"]))

    @property
    def full_layers(self) -> int:
        return sum(t == FULL for t in self.layer_types)

    @property
    def window_layers(self) -> int:
        return self.num_hidden_layers - self.full_layers

    @property
    def dense_layers(self) -> int:
        return min(self.first_k_dense, self.num_hidden_layers)

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.dense_layers


def _swiglu_bytes(d: int, f: int, qtype: str, block: int) -> float:
    return (2 * quantized_linear_bytes(d, f, qtype, block)
            + quantized_linear_bytes(f, d, qtype, block))


def attention_bytes(dims: Dims, kind: Kind, qtype: str, block: int,
                    indexer: bool) -> float:
    """Packed bytes of one layer's attention linears (``W_kvb`` counted
    as the quantized linear it is published as), the head-wise gate and,
    for a full layer, the indexer's three projections."""
    d, h, q = dims.hidden_size, kind.heads, quantized_linear_bytes
    out = (q(d, kind.q_lora_rank, qtype, block)
           + q(kind.q_lora_rank, h * (kind.nope + kind.rope), qtype, block)
           + q(d, kind.latent_dim, qtype, block)
           + q(kind.kv_lora_rank, h * (kind.nope + kind.v), qtype, block)
           + q(h * kind.v, d, qtype, block) + q(d, h, qtype, block))
    if indexer:
        out += (q(kind.q_lora_rank, dims.index_heads * dims.index_dim,
                  qtype, block)
                + q(d, dims.index_dim, qtype, block)
                + q(d, dims.index_heads, qtype, block))
    return out


def expert_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of one routed expert."""
    return _swiglu_bytes(dims.hidden_size, dims.moe_intermediate, qtype,
                         block)


def linear_weight_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of every DENSE linear a decode step reads: all of
    the model but the routed experts, with the output head."""
    attn = (dims.full_layers * attention_bytes(dims, dims.full, qtype,
                                               block, True)
            + dims.window_layers * attention_bytes(dims, dims.window, qtype,
                                                   block, False))
    return (attn
            + dims.dense_layers * _swiglu_bytes(
                dims.hidden_size, dims.dense_intermediate, qtype, block)
            + dims.expert_layers * _swiglu_bytes(
                dims.hidden_size,
                dims.n_shared_experts * dims.moe_intermediate, qtype, block)
            + quantized_linear_bytes(dims.hidden_size, dims.vocab_size,
                                     qtype, block))


def index_bytes_per_position(dims: Dims, kv_cache_dtype: str = "bf16"
                             ) -> float:
    return dims.index_dim * KV_ELT_BYTES[kv_cache_dtype]


def latent_bytes_per_position(kind: Kind, kv_cache_dtype: str = "bf16"
                              ) -> float:
    return kind.latent_dim * KV_ELT_BYTES[kv_cache_dtype]


def kv_bytes_per_token(dims: Dims, seq_len: int,
                       kv_cache_dtype: str = "bf16") -> float:
    """Cache bytes one decoded token HAS to read at cache length
    ``seq_len``: every live index key and the selected latent rows of
    the full layers, the window's rows of the window layers."""
    return (dims.full_layers * (
        seq_len * index_bytes_per_position(dims, kv_cache_dtype)
        + min(seq_len, dims.index_topk)
        * latent_bytes_per_position(dims.full, kv_cache_dtype))
        + dims.window_layers * min(seq_len, dims.window.window)
        * latent_bytes_per_position(dims.window, kv_cache_dtype))


def decode_lengths(records, a: float, b: float):
    """The cache length at each token a client received in ``[a, b)``."""
    for r in records:
        got = 0
        for t, k in r.get("chunks", []):
            if a <= t < b:
                for j in range(k):
                    yield r["prompt_tokens"] + got + j + 1
            got += k


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
        live = sel = win = 0.0
        for n in decode_lengths(records, *trace_ab):
            live += n
            sel += min(n, dims.index_topk)
            win += min(n, dims.window.window)
        work["dsa_index_bytes"] = (live * dims.full_layers
                                   * index_bytes_per_position(
                                       dims, kv_cache_dtype))
        work["sparse_latent_bytes"] = (
            sel * dims.full_layers
            * latent_bytes_per_position(dims.full, kv_cache_dtype))
        work["sparse_absorbed_flops"] = (
            sel * dims.full_layers
            * dims.full.absorbed_flops_per_position())
        work["window_latent_bytes"] = (
            win * dims.window_layers
            * latent_bytes_per_position(dims.window, kv_cache_dtype))
        work["window_absorbed_flops"] = (
            win * dims.window_layers
            * dims.window.absorbed_flops_per_position())
    return work


def training_work(config: Dict[str, Any], dims: Dims,
                  traffic: Dict[str, Any], tokens_per_step: int
                  ) -> Dict[str, float]:
    raise NotImplementedError(
        "no training cell runs a dots3-note configuration: it has no "
        "training forward (PERF.md 7)")
