"""Operations and bytes an SDAR-MoE configuration needs, computed from
shapes: what the roofline readers of its cells divide by.

Standard library only. ``Dims`` comes from the ``reference`` block of
the configuration's file, not from the program. Bytes are the packed
codes and scales of a block-quantized linear as the program stores it
(``costs.quantized_linear_bytes``: 0.5625 B a parameter at sym_int4,
block 32, bf16 scales).

Per cached position and layer (bf16): K and V of ``kv_heads x head_dim``
values each, 4 x 128 x 2 x 2 B = 2,048 B at the published widths. A
BLOCK PASS reads them ONCE for the ``B`` rows of a slot (the rows are
``B x H`` query heads of one kernel call over the same keys): every
position below the block and the block's own, all layers.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from harness.costs import KV_ELT_BYTES, quantized_linear_bytes
from harness.costs_dots3_note import _swiglu_bytes


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden_size: int
    vocab_size: int
    num_hidden_layers: int
    heads: int
    kv_heads: int
    head_dim: int
    moe_intermediate: int
    experts_total: int
    held: int
    experts_per_tok: int
    block: int
    denoising_steps: int

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "Dims":
        a = config["reference"]
        return cls(
            hidden_size=int(a["hidden"]), vocab_size=int(a["vocab"]),
            num_hidden_layers=int(a["layers"]), heads=int(a["heads"]),
            kv_heads=int(a["kv_heads"]), head_dim=int(a["head_dim"]),
            moe_intermediate=int(a["moe_intermediate"]),
            experts_total=int(a["experts_total"]), held=int(a["held"]),
            experts_per_tok=int(a["experts_per_tok"]),
            block=int(a["block"]),
            denoising_steps=int(a["denoising_steps"]))


def attention_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of one layer's q, k, v and o linears."""
    d, q = dims.hidden_size, quantized_linear_bytes
    qw, kw = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    return q(d, qw + 2 * kw, qtype, block) + q(qw, d, qtype, block)


def expert_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of one routed expert."""
    return _swiglu_bytes(dims.hidden_size, dims.moe_intermediate, qtype,
                         block)


def linear_weight_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of every DENSE linear a block pass reads: all of the
    model but the routed experts, with the output head."""
    return (dims.num_hidden_layers * attention_bytes(dims, qtype, block)
            + quantized_linear_bytes(dims.hidden_size, dims.vocab_size,
                                     qtype, block))


def bytes_per_position(dims: Dims, kv_cache_dtype: str = "bf16") -> float:
    """K and V of one position in one layer."""
    return (2 * dims.kv_heads * dims.head_dim
            * KV_ELT_BYTES[kv_cache_dtype])


def kv_bytes_per_token(dims: Dims, seq_len: int,
                       kv_cache_dtype: str = "bf16") -> float:
    """Cache bytes ONE block pass of a slot HAS to read at cache length
    ``seq_len`` (the block's own rows counted): every live position of
    every layer, once for the block's rows together."""
    return (bytes_per_position(dims, kv_cache_dtype)
            * dims.num_hidden_layers * seq_len)


def pass_lengths(records, a: float, b: float, dims: Dims):
    """The cache length each block pass of the stretch ``[a, b)`` read,
    a slot's: the positions up to its block's last row. A record's
    ``steps`` (the request's pass count at each token's commit) say how
    many passes lie between two events; a record without them is given
    ``denoising_steps + 1`` passes a block of tokens."""
    blk = dims.block
    for r in records:
        steps = r.get("steps")
        got, seen = 0, 0
        for t, k in r.get("chunks", []):
            if steps:
                hi = max(steps[got:got + k])
                passes = max(0, hi - seen)
                seen = max(seen, hi)
            else:
                passes = k * (dims.denoising_steps + 1) / blk
            if a <= t < b and passes:
                at = (r["prompt_tokens"] + got) // blk * blk + blk
                yield passes, at
            got += k


def serving_work(config: Dict[str, Any], dims: Dims, records,
                 kv_cache_dtype: str, trace_ab) -> Dict[str, float]:
    """``obs["work"]`` of a traced serving run. ``decode_kv_bytes`` is
    what the trace group ``decode_attn`` (``decode_attention_lanes`` at
    ``B x H`` heads, one call a layer and pass) has to read: for every
    block pass of the stretch the live K/V of its slot ONCE, whatever
    the rows of the block. ``block_attn_flops`` the operations of those
    calls (the scores and the weighted values of ``B x H`` heads over
    the live keys)."""
    qtype, block = config["quant"], int(config["quant_block"])
    work = {
        "linear_weight_bytes": linear_weight_bytes(dims, qtype, block),
        "expert_bytes": expert_bytes(dims, qtype, block),
        "expert_layers": float(dims.num_hidden_layers),
        "held_experts": float(dims.held),
    }
    if trace_ab is not None:
        live = sum(n * at for n, at in pass_lengths(records, *trace_ab,
                                                    dims))
        work["decode_kv_bytes"] = (live * dims.num_hidden_layers
                                   * bytes_per_position(dims,
                                                        kv_cache_dtype))
        work["block_attn_flops"] = (live * dims.num_hidden_layers * 4.0
                                    * dims.block * dims.heads
                                    * dims.head_dim)
    return work


def training_work(config: Dict[str, Any], dims: Dims,
                  traffic: Dict[str, Any], tokens_per_step: int
                  ) -> Dict[str, float]:
    raise NotImplementedError(
        "no training cell runs an SDAR-MoE configuration: it has no "
        "training forward (PERF.md 7)")
