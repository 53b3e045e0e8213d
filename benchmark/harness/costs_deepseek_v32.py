"""Operations and bytes a DeepSeek-V3.2 configuration needs, computed
from shapes: what the roofline readers of its cells divide by.

Standard library only. ``Dims`` comes from the ``reference`` block of
the configuration's file, not from the program. Bytes are the packed
codes and scales of a block-quantized linear as the program stores it
(``costs.quantized_linear_bytes``: 0.5625 B a parameter at sym_int4,
block 32, bf16 scales).

The engine of this configuration SPECULATES: a slot-step computes two
query rows (the slot's last token and its draft) in every layer body
(the main stack's layers and the MTP block) and the client receives one
token or two. So a kernel's least work is reckoned from the SLOT-STEPS
of the traced stretch and the rows they compute, not from the tokens
received (``slot_steps``):

- the index keys of every live position, ONCE for both rows (128 bf16
  values, 256 B a position and body);
- the latent rows of the SELECTED positions, once: ``min(n, index_topk)``
  x 1,152 B a body, with ``n`` the positions the later row can attend.
  The two rows' selections differ, and their union is at least one
  selection: a true lower bound;
- the absorbed products of BOTH rows (2 x 128 x 1088 a selected position
  and row).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from harness.costs import KV_ELT_BYTES, quantized_linear_bytes

# tokens of one request that arrive closer together than this came from
# ONE engine step (a verify step that kept both; a step is over 15 ms)
SAME_STEP_S = 0.005
VERIFY_ROWS = 2


@dataclasses.dataclass(frozen=True)
class Dims:
    hidden_size: int
    vocab_size: int
    num_hidden_layers: int
    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    nope: int
    rope: int
    v: int
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
    mtp: int

    @classmethod
    def from_config(cls, config: Dict[str, Any]) -> "Dims":
        a = config["reference"]
        at, ix = a["attn"], a["index"]
        return cls(
            hidden_size=int(a["hidden"]), vocab_size=int(a["vocab"]),
            num_hidden_layers=int(a["layers"]), heads=int(at["heads"]),
            q_lora_rank=int(at["q_lora_rank"]),
            kv_lora_rank=int(at["kv_lora_rank"]), nope=int(at["nope"]),
            rope=int(at["rope"]), v=int(at["v"]),
            index_heads=int(ix["heads"]), index_dim=int(ix["dim"]),
            index_topk=int(ix["topk"]),
            dense_intermediate=int(a["dense_intermediate"]),
            moe_intermediate=int(a["moe_intermediate"]),
            n_shared_experts=int(a["n_shared_experts"]),
            experts_total=int(a["experts_total"]), held=int(a["held"]),
            experts_per_tok=int(a["experts_per_tok"]),
            first_k_dense=int(a["first_k_dense"]), mtp=int(a.get("mtp", 0)))

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.rope

    @property
    def dense_layers(self) -> int:
        return min(self.first_k_dense, self.num_hidden_layers)

    @property
    def bodies(self) -> int:
        """Layer bodies a step runs: the stack and the MTP block."""
        return self.num_hidden_layers + self.mtp

    @property
    def expert_layers(self) -> int:
        """Bodies with routed experts, the MTP block's among them."""
        return self.bodies - self.dense_layers

    def absorbed_flops_per_position(self) -> float:
        return 2.0 * self.heads * (2 * self.kv_lora_rank + self.rope)


def _swiglu_bytes(d: int, f: int, qtype: str, block: int) -> float:
    return (2 * quantized_linear_bytes(d, f, qtype, block)
            + quantized_linear_bytes(f, d, qtype, block))


def attention_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of one body's attention linears (``W_kvb`` counted as
    the quantized linear it is published as) and the indexer's three
    projections."""
    d, h, q = dims.hidden_size, dims.heads, quantized_linear_bytes
    return (q(d, dims.q_lora_rank, qtype, block)
            + q(dims.q_lora_rank, h * (dims.nope + dims.rope), qtype, block)
            + q(d, dims.latent_dim, qtype, block)
            + q(dims.kv_lora_rank, h * (dims.nope + dims.v), qtype, block)
            + q(h * dims.v, d, qtype, block)
            + q(dims.q_lora_rank, dims.index_heads * dims.index_dim, qtype,
                block)
            + q(d, dims.index_dim, qtype, block)
            + q(d, dims.index_heads, qtype, block))


def expert_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of one routed expert."""
    return _swiglu_bytes(dims.hidden_size, dims.moe_intermediate, qtype,
                         block)


def linear_weight_bytes(dims: Dims, qtype: str, block: int) -> float:
    """Packed bytes of every DENSE linear a step reads: all of the model
    and the MTP module but the routed experts, with the output head
    (read once for the main rows and once for the MTP module's)."""
    return (dims.bodies * attention_bytes(dims, qtype, block)
            + dims.dense_layers * _swiglu_bytes(
                dims.hidden_size, dims.dense_intermediate, qtype, block)
            + dims.expert_layers * _swiglu_bytes(
                dims.hidden_size,
                dims.n_shared_experts * dims.moe_intermediate, qtype, block)
            + dims.mtp * quantized_linear_bytes(
                2 * dims.hidden_size, dims.hidden_size, qtype, block)
            + (1 + dims.mtp) * quantized_linear_bytes(
                dims.hidden_size, dims.vocab_size, qtype, block))


def index_bytes_per_position(dims: Dims, kv_cache_dtype: str = "bf16"
                             ) -> float:
    return dims.index_dim * KV_ELT_BYTES[kv_cache_dtype]


def latent_bytes_per_position(dims: Dims, kv_cache_dtype: str = "bf16"
                              ) -> float:
    return dims.latent_dim * KV_ELT_BYTES[kv_cache_dtype]


def kv_bytes_per_token(dims: Dims, seq_len: int,
                       kv_cache_dtype: str = "bf16") -> float:
    """Cache bytes one step of a slot HAS to read at cache length
    ``seq_len``, all bodies: every live index key and the selected
    latent rows."""
    return dims.bodies * (
        seq_len * index_bytes_per_position(dims, kv_cache_dtype)
        + min(seq_len, dims.index_topk)
        * latent_bytes_per_position(dims, kv_cache_dtype))


def slot_steps(records, a: float, b: float):
    """The cache length (positions the FIRST computed row can attend)
    at each decode slot-step of ``[a, b)``: tokens of one request that
    arrived within ``SAME_STEP_S`` of each other are one step's. A
    request's first token is its prefill's and no decode step."""
    for r in records:
        got = 0
        last = None
        for t, k in r.get("chunks", []):
            new_step = last is None or t - last > SAME_STEP_S
            if new_step and got and a <= t < b:
                yield r["prompt_tokens"] + got
            got += k
            last = t


def serving_work(config: Dict[str, Any], dims: Dims, records,
                 kv_cache_dtype: str, trace_ab) -> Dict[str, float]:
    """``obs["work"]`` of a traced serving run."""
    qtype, block = config["quant"], int(config["quant_block"])
    rows = (VERIFY_ROWS if int(config["engine"].get("speculative_tokens", 0))
            else 1)
    work = {
        "linear_weight_bytes": linear_weight_bytes(dims, qtype, block),
        "expert_bytes": expert_bytes(dims, qtype, block),
        "expert_layers": float(dims.expert_layers),
        "held_experts": float(dims.held),
    }
    if trace_ab is not None:
        live = sel_once = sel_rows = steps = 0.0
        for n in slot_steps(records, *trace_ab):
            steps += 1
            live += n + rows - 1
            sel_once += min(n + rows - 1, dims.index_topk)
            sel_rows += sum(min(n + r, dims.index_topk)
                            for r in range(rows))
        work["slot_steps"] = steps
        work["dsa_index_bytes"] = (live * dims.bodies
                                   * index_bytes_per_position(
                                       dims, kv_cache_dtype))
        work["sparse_latent_bytes"] = (
            sel_once * dims.bodies
            * latent_bytes_per_position(dims, kv_cache_dtype))
        work["sparse_absorbed_flops"] = (
            sel_rows * dims.bodies * dims.absorbed_flops_per_position())
    return work


def training_work(config: Dict[str, Any], dims: Dims,
                  traffic: Dict[str, Any], tokens_per_step: int
                  ) -> Dict[str, float]:
    raise NotImplementedError(
        "no training cell runs a DeepSeek-V3.2 configuration: it has no "
        "training forward (PERF.md 7)")
