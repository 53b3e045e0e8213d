"""Operations and bytes an EvaByte configuration needs, computed from
shapes: what the roofline readers of its cells divide by.

Standard library only. ``Dims`` comes from the ``reference`` block of
the configuration's file, not from the program. Bytes are the packed
codes and scales of a block-quantized linear as the program stores it
(``costs.quantized_linear_bytes``: 0.5625 B a parameter at sym_int4,
block 32, bf16 scales).

For one decoded token at position ``p`` (the query's own), a layer
reads ``window_rows(p) = p % window + 1`` exact rows of K and of V and
``summary_rows(p) = (p // window) * (window / chunk)`` summary rows of
each: ``row_bytes`` = 2 x heads x head_dim x 2 B a row (16,384 B at the
published widths). A chunk's summary reads ``chunk`` rows of K and of V
and writes one of each; its operations are the ``phi`` scores and the
two weighted sums, ``6 x chunk x heads x head_dim``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from harness.costs import KV_ELT_BYTES, quantized_linear_bytes


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden_size: int
    intermediate_size: int
    vocab_size: int
    pred_heads: int
    num_attention_heads: int
    hd: int
    num_hidden_layers: int
    window: int
    chunk: int

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "Dims":
        a = config["reference"]
        return cls(hidden_size=int(a["hidden"]),
                   intermediate_size=int(a["intermediate"]),
                   vocab_size=int(a["vocab"]),
                   pred_heads=int(a["pred_heads"]),
                   num_attention_heads=int(a["heads"]),
                   hd=int(a["head_dim"]),
                   num_hidden_layers=int(a["layers"]),
                   window=int(a["window"]), chunk=int(a["chunk"]))

    # what the dense cost functions read
    @property
    def num_key_value_heads(self) -> int:
        return self.num_attention_heads


def row_bytes(dims: Dims, kv_cache_dtype: str = "bf16") -> float:
    """One position's (or one summary's) K and V of one layer."""
    return (2.0 * dims.num_attention_heads * dims.hd
            * KV_ELT_BYTES[kv_cache_dtype])


def window_rows(dims: Dims, pos: int) -> int:
    return pos % dims.window + 1


def summary_rows(dims: Dims, pos: int) -> int:
    return pos // dims.window * (dims.window // dims.chunk)


def kv_bytes_per_token(dims: Dims, seq_len: int,
                       kv_cache_dtype: str = "bf16") -> float:
    """Cache bytes one decoded token HAS to read when its own position
    is ``seq_len - 1``: the live window rows and the live summary rows
    of every layer."""
    pos = max(int(seq_len) - 1, 0)
    return (dims.num_hidden_layers
            * (window_rows(dims, pos) + summary_rows(dims, pos))
            * row_bytes(dims, kv_cache_dtype))


def summarize_bytes_per_token(dims: Dims, kv_cache_dtype: str = "bf16"
                              ) -> float:
    """What one decode step's chunk summary moves for one slot, all
    layers: ``chunk`` rows read, one written."""
    return (dims.num_hidden_layers * (dims.chunk + 1)
            * row_bytes(dims, kv_cache_dtype))


def summarize_flops_per_token(dims: Dims) -> float:
    return (dims.num_hidden_layers * 6.0 * dims.chunk
            * dims.num_attention_heads * dims.hd)


def linear_weight_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of every linear a decode step reads: the seven
    projections of each layer and the whole head (every prediction
    head's columns are computed)."""
    d, f = dims.hidden_size, dims.intermediate_size
    hh = dims.num_attention_heads * dims.hd
    q = quantized_linear_bytes
    per_layer = (3 * q(d, hh, qtype, block) + q(hh, d, qtype, block)
                 + 2 * q(d, f, qtype, block) + q(f, d, qtype, block))
    return (dims.num_hidden_layers * per_layer
            + q(d, dims.pred_heads * dims.vocab_size, qtype, block))


def decode_positions(records, a: float, b: float):
    """The query's own position at each token a client received in
    ``[a, b)`` but a request's first (the prefill's): the decode steps'
    rows."""
    for r in records:
        got = 0
        for t, k in r.get("chunks", []):
            if a <= t < b:
                for j in range(k):
                    if got + j:
                        yield r["prompt_tokens"] + got + j - 1
            got += k


def serving_work(config: Dict[str, Any], dims: Dims, records,
                 kv_cache_dtype: str, trace_ab) -> Dict[str, float]:
    """``obs["work"]`` of a traced serving run."""
    work = {"linear_weight_bytes": linear_weight_bytes(
        dims, config["quant"], int(config["quant_block"]))}
    if trace_ab is not None:
        live = steps = 0.0
        for p in decode_positions(records, *trace_ab):
            live += window_rows(dims, p) + summary_rows(dims, p)
            steps += 1
        work["eva_live_bytes"] = (live * dims.num_hidden_layers
                                  * row_bytes(dims, kv_cache_dtype))
        work["eva_summarize_bytes"] = steps * summarize_bytes_per_token(
            dims, kv_cache_dtype)
        work["eva_summarize_flops"] = steps * summarize_flops_per_token(dims)
    return work


def training_work(config: Dict[str, Any], dims: Dims,
                  traffic: Dict[str, Any], tokens_per_step: int
                  ) -> Dict[str, float]:
    raise NotImplementedError(
        "no training cell runs an EvaByte configuration: it has no "
        "training forward (PERF.md 7)")
