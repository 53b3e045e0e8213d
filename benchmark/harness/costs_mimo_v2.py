"""Operations and bytes a MiMo-V2 configuration needs, computed from
shapes: what the roofline readers of its cells divide by.

Standard library only. ``Dims`` comes from the ``reference`` block of
the configuration's file, not from the program. Bytes are the packed
codes and scales of a block-quantized linear as the program stores it
(``costs.quantized_linear_bytes``: 0.5625 B a parameter at sym_int4,
block 32, bf16 scales).

Per cached position and layer, for one decoded token (bf16):

- ``full_bytes_per_position``: a full layer's K and V of one position,
  ``kv_heads x (head_dim + v_head_dim)`` values: 4 x (192 + 128) x 2 B =
  2,560 B at the published widths, of EVERY live position;
- ``window_bytes_per_position``: a window layer's, 8 x (192 + 128) x 2 B
  = 5,120 B, of the last ``window`` positions at most: ``min(pos + 1,
  128)`` rows whatever the cache's length.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

from harness.costs import KV_ELT_BYTES, quantized_linear_bytes
from harness.costs_dots3_note import _swiglu_bytes, decode_lengths


@dataclasses.dataclass(frozen=True)
class Kind:
    heads: int
    kv_heads: int
    head_dim: int
    v_head_dim: int
    window: int = 0

    @classmethod
    def of(cls, a: Dict[str, Any]) -> "Kind":
        return cls(int(a["heads"]), int(a["kv_heads"]), int(a["head_dim"]),
                   int(a["v_head_dim"]), int(a.get("window", 0)))

    def values_per_position(self) -> int:
        return self.kv_heads * (self.head_dim + self.v_head_dim)


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden_size: int
    vocab_size: int
    num_hidden_layers: int
    pattern: Tuple[int, ...]
    moe: Tuple[int, ...]
    full: Kind
    window: Kind
    dense_intermediate: int
    moe_intermediate: int
    experts_total: int
    held: int
    experts_per_tok: int

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "Dims":
        a = config["reference"]
        return cls(
            hidden_size=int(a["hidden"]), vocab_size=int(a["vocab"]),
            num_hidden_layers=int(a["layers"]),
            pattern=tuple(int(p) for p in a["pattern"]),
            moe=tuple(int(m) for m in a["moe"]),
            full=Kind.of(a["full"]), window=Kind.of(a["window"]),
            dense_intermediate=int(a["dense_intermediate"]),
            moe_intermediate=int(a["moe_intermediate"]),
            experts_total=int(a["experts_total"]), held=int(a["held"]),
            experts_per_tok=int(a["experts_per_tok"]))

    @property
    def window_layers(self) -> int:
        return sum(self.pattern)

    @property
    def full_layers(self) -> int:
        return self.num_hidden_layers - self.window_layers

    @property
    def expert_layers(self) -> int:
        return sum(self.moe)

    @property
    def dense_layers(self) -> int:
        return self.num_hidden_layers - self.expert_layers


def attention_bytes(dims: Dims, kind: Kind, qtype: str, block: int) -> float:
    """Packed bytes of one layer's q, k, v and o linears."""
    d, q = dims.hidden_size, quantized_linear_bytes
    return (q(d, kind.heads * kind.head_dim + kind.values_per_position(),
              qtype, block)
            + q(kind.heads * kind.v_head_dim, d, qtype, block))


def expert_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of one routed expert."""
    return _swiglu_bytes(dims.hidden_size, dims.moe_intermediate, qtype,
                         block)


def linear_weight_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of every DENSE linear a decode step reads: all of
    the model but the routed experts, with the output head."""
    return (dims.full_layers * attention_bytes(dims, dims.full, qtype, block)
            + dims.window_layers * attention_bytes(dims, dims.window, qtype,
                                                   block)
            + dims.dense_layers * _swiglu_bytes(
                dims.hidden_size, dims.dense_intermediate, qtype, block)
            + quantized_linear_bytes(dims.hidden_size, dims.vocab_size,
                                     qtype, block))


def full_bytes_per_position(dims: Dims, kv_cache_dtype: str = "bf16"
                            ) -> float:
    return dims.full.values_per_position() * KV_ELT_BYTES[kv_cache_dtype]


def window_bytes_per_position(dims: Dims, kv_cache_dtype: str = "bf16"
                              ) -> float:
    return dims.window.values_per_position() * KV_ELT_BYTES[kv_cache_dtype]


def kv_bytes_per_token(dims: Dims, seq_len: int,
                       kv_cache_dtype: str = "bf16") -> float:
    """Cache bytes one decoded token HAS to read at cache length
    ``seq_len``: every live position of the full layers, the window's
    rows of the window layers."""
    return (dims.full_layers * seq_len
            * full_bytes_per_position(dims, kv_cache_dtype)
            + dims.window_layers * min(seq_len, dims.window.window)
            * window_bytes_per_position(dims, kv_cache_dtype))


def serving_work(config: Dict[str, Any], dims: Dims, records,
                 kv_cache_dtype: str, trace_ab) -> Dict[str, float]:
    """``obs["work"]`` of a traced serving run. ``decode_kv_bytes`` is
    the full layers' part alone, what the trace group ``decode_attn``
    (``decode_attention_lanes``) has to read; ``swa_ring_bytes`` the
    window layers', the group ``swa_decode_attn``'s."""
    qtype, block = config["quant"], int(config["quant_block"])
    work = {
        "linear_weight_bytes": linear_weight_bytes(dims, qtype, block),
        "expert_bytes": expert_bytes(dims, qtype, block),
        "expert_layers": float(dims.expert_layers),
        "held_experts": float(dims.held),
    }
    if trace_ab is not None:
        live = win = 0.0
        for n in decode_lengths(records, *trace_ab):
            live += n
            win += min(n, dims.window.window)
        work["decode_kv_bytes"] = (
            live * dims.full_layers
            * full_bytes_per_position(dims, kv_cache_dtype))
        work["swa_ring_bytes"] = (
            win * dims.window_layers
            * window_bytes_per_position(dims, kv_cache_dtype))
    return work


def training_work(config: Dict[str, Any], dims: Dims,
                  traffic: Dict[str, Any], tokens_per_step: int
                  ) -> Dict[str, float]:
    raise NotImplementedError(
        "no training cell runs a MiMo-V2 configuration: it has no "
        "training forward (PERF.md 7)")
