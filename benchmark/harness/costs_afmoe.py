"""Operations and bytes an AFMoE (Trinity) configuration needs, computed
from shapes: what the roofline readers of its cells divide by.

Standard library only. ``Dims`` comes from the ``reference`` block of
the configuration's file, not from the program. Bytes are the packed
codes and scales of a block-quantized linear as the program stores it
(``costs.quantized_linear_bytes``: 0.5625 B a parameter at sym_int4,
block 32, bf16 scales).

Per cached position and layer, for one decoded token (bf16), both kinds
of layer alike: K and V of ``kv_heads x head_dim`` values each, 4 x 128
x 2 x 2 B = 2,048 B at the published widths; a full layer's of EVERY
live position, a window layer's of the LIVE columns of its ring,
``min(pos + 1, window)`` rows whatever the kernel's blocks fetch.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

from harness.costs import KV_ELT_BYTES, quantized_linear_bytes
from harness.costs_dots3_note import _swiglu_bytes, decode_lengths


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden_size: int
    vocab_size: int
    num_hidden_layers: int
    pattern: Tuple[int, ...]
    moe: Tuple[int, ...]
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    dense_intermediate: int
    moe_intermediate: int
    shared_intermediate: int
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
            heads=int(a["heads"]), kv_heads=int(a["kv_heads"]),
            head_dim=int(a["head_dim"]), window=int(a["window"]),
            dense_intermediate=int(a["dense_intermediate"]),
            moe_intermediate=int(a["moe_intermediate"]),
            shared_intermediate=int(a["shared_intermediate"]),
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


def attention_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of one layer's q, k, v, gate and o linears."""
    d, q = dims.hidden_size, quantized_linear_bytes
    qw, kw = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    return q(d, 2 * qw + 2 * kw, qtype, block) + q(qw, d, qtype, block)


def expert_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of one routed expert."""
    return _swiglu_bytes(dims.hidden_size, dims.moe_intermediate, qtype,
                         block)


def linear_weight_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of every DENSE linear a decode step reads: all of
    the model but the routed experts (the shared expert counted), with
    the output head."""
    return (dims.num_hidden_layers * attention_bytes(dims, qtype, block)
            + dims.dense_layers * _swiglu_bytes(
                dims.hidden_size, dims.dense_intermediate, qtype, block)
            + dims.expert_layers * _swiglu_bytes(
                dims.hidden_size, dims.shared_intermediate, qtype, block)
            + quantized_linear_bytes(dims.hidden_size, dims.vocab_size,
                                     qtype, block))


def bytes_per_position(dims: Dims, kv_cache_dtype: str = "bf16") -> float:
    """K and V of one position in one layer, either kind."""
    return (2 * dims.kv_heads * dims.head_dim
            * KV_ELT_BYTES[kv_cache_dtype])


def kv_bytes_per_token(dims: Dims, seq_len: int,
                       kv_cache_dtype: str = "bf16") -> float:
    """Cache bytes one decoded token HAS to read at cache length
    ``seq_len``: every live position of the full layers, the window's
    rows of the window layers."""
    return (bytes_per_position(dims, kv_cache_dtype)
            * (dims.full_layers * seq_len
               + dims.window_layers * min(seq_len, dims.window)))


def serving_work(config: Dict[str, Any], dims: Dims, records,
                 kv_cache_dtype: str, trace_ab) -> Dict[str, float]:
    """``obs["work"]`` of a traced serving run. ``decode_kv_bytes`` is
    the full layers' part alone, what the trace group ``decode_attn``
    (``decode_attention_lanes``) has to read; ``swa_ring_bytes`` the
    window layers', the group ``swa_decode_attn``'s: the LIVE columns
    ``min(pos + 1, window)`` of every ring."""
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
            win += min(n, dims.window)
        per = bytes_per_position(dims, kv_cache_dtype)
        work["decode_kv_bytes"] = live * dims.full_layers * per
        work["swa_ring_bytes"] = win * dims.window_layers * per
    return work


def training_work(config: Dict[str, Any], dims: Dims,
                  traffic: Dict[str, Any], tokens_per_step: int
                  ) -> Dict[str, float]:
    raise NotImplementedError(
        "no training cell runs an AFMoE configuration: it has no "
        "training forward (PERF.md 7)")
