"""SDAR-MoE (HF `model_type` "sdar_moe"; SDAR-30B-A3B-Chat): a routed
language model that GENERATES BY DIFFUSION OVER BLOCKS. Positions come
in blocks of `block_length`; attention is causal between blocks and
every row of a block sees its whole block; a step of the model denoises
one block (rows that hold the MASK id where nothing is committed yet),
and the tokens a pass commits are those its confidences choose;
functional and static-shape.

The layer, as this module reads the published config (every norm an
RMSNorm with a learned weight, eps `rms_norm_eps`; untied head; `x =
embed[token]`, unscaled; `mlp_only_layers` [] and `decoder_sparse_step`
1: every layer routed, `intermediate_size` read by nothing; no sliding
window: every layer full):

- Attention. `h = input_layernorm(x)`; `H` query heads, `G` KV heads, `d
  = head_dim`: `q = h W_q` `[S, H, d]`, `k = h W_k` `[S, G, d]`, `v = h
  W_v` `[S, G, d]`, no bias. q and k each go through an RMSNorm over the
  `d` values of every head (`q_norm` / `k_norm` `[d]`, one each a layer;
  ASSUMED unconditional, as in the autoregressive family it was trained
  from), then rotary on all `d` dims, half-rotation form, base
  `rope_theta`, no scaling. Scores `q . k / sqrt(d)`, head n reading KV
  head `n // (H / G)`. Key `j` is live for query `i` iff `j // B <= i //
  B`, positions counted from the sequence's first token. Plain softmax.
  `x += concat(o) W_o`.
- Experts. `h = post_attention_layernorm(x)`; this chip's share of the
  routed sum (`deepseek_v2.moe_block`, `ops/moe_routed.py`): scores =
  softmax over ALL `num_experts * ep_size` router logits in float32,
  the top `num_experts_per_tok`, their scores renormalised to sum 1
  (`norm_topk_prob`); no shared expert, no bias, no scaling factor. `x
  += y`.
- Head. `logits = norm(x) W_head`. The logit row at position `i`
  predicts the token AT position `i` (no shift; ASSUMED from the
  family's generation script, which writes `x0[i]` into `cur_x[i]`).

Generation (`block_spec`, and `serving/engine.py`'s block step; every
detail ASSUMED from the family's `block_diffusion_generate`, the
published config gives none): the prompt's whole blocks are prefilled
under the block-causal mask and yield no token; its tail opens the first
generated block as given tokens beside MASK ids. A denoise pass runs a
block's `B` rows against the stored K/V below the block and the block's
own rows, samples `x0` a row with its probability `x0_p`, and commits
rows by `remasking_strategy`: `n_s` rows are owed at pass `s` (`B // T`,
one more for the first `B % T` passes); `low_confidence_static` takes the
`n_s` most confident MASK rows, `low_confidence_dynamic` every MASK row
over `confidence_threshold` if they are at least `n_s` (else the `n_s`
most confident), `sequential` the first `n_s` MASK rows. A pass commits
`min(n_s, rows still MASK)` rows at the least and never writes a row
that is not MASK; the MASK id's logit is -inf before sampling. When no
MASK is left ONE more pass over the final ids stores the block's K/V,
and the next block begins, all MASK. These five are the MODEL's
configuration (`block_length`, `denoising_steps`, `mask_token_id`,
`remasking_strategy`, `confidence_threshold`: this program's keys of
`hf_config`, as `ep_size` / `ep_rank` are).

Attention, cache and experts are shared code: `models/mimo_v2.py`'s
`attention`, `cache_spec` and `new_cache` (full planes with a position's
heads side by side in the lanes; this family's kind turns on the QK norm
and sets `block`), `ops/swa.py` (`full_chunk`'s block-causal `live`,
`full_block`: the `B` rows of a slot as `B x H` query heads of
`decode_attention_lanes` under one limit), `ops/moe_routed.py`. A call of
`forward` with exactly `block_length` rows a slot is a block pass; a
chunk of a prompt starts and ends on a block's edge (`prefill_chunk` is
a multiple of `block_length`). A denoise pass writes its rows' K/V into
the planes at the block's positions; every later pass of the block
overwrites them and the storing pass's write stands.

DEPTH. One kind of layer: every leaf is ONE stack over all the layers
and `forward` is ONE `lax.scan` (`scan_plan`: no head); the quantized
linears are read where they lie (`ops/matmul.StackedQ`), the routed
kernels are addressed by the layer index, the planes are carried.

Parameter tree (linears contraction-major `[K, N]`, QTensor or dense):
{
  "embed_tokens": [V, D], "norm": [D], "lm_head": [D, V],
  "layers": stacked over ALL L layers: input_layernorm,
      post_attention_layernorm [L, D], q_norm, k_norm [L, d], qkv_proj
      [L, D, (H + 2 G) d], o_proj [L, H d, D], router [L, D, E_total],
  "experts": experts_gate / experts_up [L, held, D, Fe], experts_down
      [L, held, Fe, D],
}
Before `prepare_params` "layers" holds q_proj / k_proj / v_proj apart:
the canonical tree, which the benchmark's reference reads.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.models import mimo_v2
from bigdl_tpu.models.deepseek_v2 import moe_block
from bigdl_tpu.models.llama import embedding_lookup
from bigdl_tpu.models.mimo_v2 import (FULL, GqaKind, attention,  # noqa: F401
                                      cache_spec, new_cache)
from bigdl_tpu.ops.kvcache import KVCache
from bigdl_tpu.ops.matmul import hold_stacks, layer_params, linear
from bigdl_tpu.ops.moe_routed import STATS, Share
from bigdl_tpu.ops.norms import rms_norm
from bigdl_tpu.ops.rope import rope_tables

_LANES = 128
RULES = ("sequential", "low_confidence_static", "low_confidence_dynamic")
_ATTN_MERGED = ("q_proj", "k_proj", "v_proj")
_EXPERT_KEYS = ("experts_gate", "experts_up", "experts_down")


class BlockSpec(NamedTuple):
    """What one step of a family that generates by diffusion over blocks
    is (`block_spec`): the serving engine's block step reads nothing
    else of the family."""
    length: int          # rows of a block
    passes: int          # denoise passes a block gets at most
    mask_id: int
    rule: str            # one of RULES
    threshold: float

    def owed(self, s):
        """Rows owed at denoise pass `s` (a plain or a traced int)."""
        return self.length // self.passes + (s < self.length % self.passes)


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144       # read by nothing: no dense layer
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    num_experts: int = 128              # experts held HERE (see ep_size)
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 32768
    tie_word_embeddings: bool = False
    ep_size: int = 1
    ep_rank: int = 0
    # generation: the model's configuration (module docstring)
    block_length: int = 4
    denoising_steps: int = 4
    mask_token_id: int = 151669
    remasking_strategy: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9

    @classmethod
    def from_hf(cls, hf: Dict[str, Any]) -> "SdarMoeConfig":
        for key, only in (("hidden_act", "silu"), ("attention_bias", False),
                          ("rope_scaling", None), ("decoder_sparse_step", 1),
                          ("mlp_only_layers", []),
                          ("use_sliding_window", False),
                          ("sliding_window", None)):
            if (hf.get(key) if hf.get(key) is not None else only) != only:
                raise NotImplementedError(f"{key} {hf[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        cfg = cls(**{k: v for k, v in hf.items()
                     if k in names and v is not None})
        b, t = cfg.block_length, cfg.denoising_steps
        if cfg.remasking_strategy not in RULES:
            raise NotImplementedError(
                f"remasking_strategy {cfg.remasking_strategy!r}")
        if b < 1 or not 1 <= t <= b:
            raise ValueError(f"block_length {b}, denoising_steps {t}: a "
                             "block gets 1 to block_length denoise passes")
        if not 0 <= cfg.mask_token_id < cfg.vocab_size:
            raise ValueError(
                f"mask_token_id {cfg.mask_token_id} lies outside the "
                f"vocabulary of {cfg.vocab_size} rows held here")
        if cfg.head_dim % 2 or cfg.num_attention_heads \
                % cfg.num_key_value_heads:
            raise ValueError(f"head_dim {cfg.head_dim}, heads "
                             f"{cfg.num_attention_heads} / "
                             f"{cfg.num_key_value_heads}")
        return cfg

    @property
    def share(self) -> Share:
        return Share(self.num_experts * self.ep_size,
                     self.num_experts * self.ep_rank, self.num_experts)

    @property
    def full(self) -> GqaKind:
        return GqaKind(self.num_attention_heads, self.num_key_value_heads,
                       self.head_dim, self.head_dim, self.rope_theta, False,
                       qk_norm=True, norm_eps=self.rms_norm_eps,
                       block=self.block_length)

    @property
    def block(self) -> BlockSpec:
        return BlockSpec(self.block_length, self.denoising_steps,
                         self.mask_token_id, self.remasking_strategy,
                         float(self.confidence_threshold))

    # what `mimo_v2.cache_spec` reads off a config: every layer full
    @property
    def n_full(self) -> int:
        return self.num_hidden_layers

    n_window = 0

    @property
    def n_routed_layers(self) -> int:
        return self.num_hidden_layers

    # what `moe_block` reads off a config
    n_group = 1
    topk_group = 1
    topk_method = "greedy"
    scoring_func = "softmax"
    routed_scaling_factor = 1.0

    # what cost models and the generic engine read off a config
    @property
    def hd(self) -> int:
        return self.head_dim

    @property
    def sliding_window(self):
        return None

    @property
    def kv_values_per_position(self) -> float:
        """Cached values a row of a pass reads of one position, a
        layer: K and V."""
        return 2.0 * self.full.k_width

    def matmul_flops_per_token(self) -> int:
        """Forward matmul operations a row needs on THIS chip."""
        d, k = self.hidden_size, self.full
        attn = d * (k.q_width + 2 * k.k_width) + k.q_width * d
        moe = 3 * d * self.moe_intermediate_size \
            * self.num_experts_per_tok / self.ep_size
        return int(2 * (self.num_hidden_layers
                        * (attn + moe + d * self.share.experts_total)
                        + d * self.vocab_size))

    def attn_flops_per_cached_token(self) -> int:
        return self.num_hidden_layers * 4 * self.num_attention_heads \
            * self.head_dim


def block_spec(cfg: SdarMoeConfig) -> BlockSpec:
    """What the serving engine asks of a family whose step is a block
    (`models/registry.FamilyAdapter.block_spec`)."""
    return cfg.block


def scan_plan(cfg: SdarMoeConfig) -> Tuple[int, int, int]:
    """`(head, period, periods)` as `models/afmoe.scan_plan` says them:
    no layer unrolled, ONE scan of periods of one layer."""
    return 0, 1, cfg.num_hidden_layers


def _tables(cfg: SdarMoeConfig, pos, sq: int):
    """cos and sin `[B or 1, sq, d / 2]` of the positions `pos .. pos +
    sq - 1`."""
    return rope_tables(mimo_v2.row_positions(pos, sq),
                       {FULL: (cfg.head_dim, cfg.rope_theta)})


attention_block = functools.partial(mimo_v2.attention_block, tables=_tables)


def _layer(x, lp, experts, k_stack, v_stack, li, pos, cos, sin, tally,
           cfg: SdarMoeConfig):
    """One layer on the residual stream `x`, its index `li` traced."""
    eps = cfg.rms_norm_eps
    a, k_stack, v_stack = attention(
        rms_norm(x, lp["input_layernorm"], eps), lp, cfg.full, k_stack,
        v_stack, li, pos, cos, sin)
    x = x + a
    hid = rms_norm(x, lp["post_attention_layernorm"], eps)
    with jax.named_scope("moe.block"):
        y, st = moe_block(hid, lp, experts, li, cfg)
    return x + y, k_stack, v_stack, tally + st


def forward(
    params: Dict[str, Any],
    cfg: SdarMoeConfig,
    tokens: jax.Array,
    cache: KVCache,
    compute_dtype=jnp.bfloat16,
    last_only: bool = False,
) -> Tuple[jax.Array, KVCache]:
    """`tokens` `[B, sq]` at `cache.pos` under the block-causal mask: a
    chunk of a prompt (`sq` a multiple of `block_length`, `cache.pos` on
    a block's edge), or with `sq == block_length` one block pass a slot,
    MASK ids where nothing is committed. Logits `[B, sq, V]` float32,
    row i for the token AT position i; the cache with the rows' K/V
    written and `pos` moved by `sq` (the engine's block step moves a
    slot's `pos` only where the pass stores)."""
    b, sq = tokens.shape
    # serving marks an empty slot with -1: here it is a slot at 0
    pos = jnp.maximum(cache.pos, 0)
    x = embedding_lookup(params["embed_tokens"], tokens, compute_dtype)
    cos, sin = _tables(cfg, pos, sq)[FULL]
    stats = cache.stats
    tally = jnp.zeros((len(STATS),), jnp.int32) if stats is None else stats
    experts = params["experts"]
    # the quantized stacks stay whole in the scan: a linear's kernel
    # reads its layer where it lies (`ops/matmul.hold_stacks`)
    held, loose = hold_stacks(params["layers"])

    def one_layer(carry, xs):
        x, k, v, tally = carry
        li, lp = xs
        x, k, v, tally = _layer(x, layer_params(held, lp, li), experts, k, v,
                                li, pos, cos, sin, tally, cfg)
        return (x, k, v, tally), None

    (x, k, v, tally), _ = lax.scan(
        one_layer, (x, cache.full_k, cache.full_v, tally),
        (jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32), loose))
    if stats is not None:
        stats = tally
    if last_only:
        x = x[:, -1:, :]
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    lm_head = params.get("lm_head")
    if lm_head is None:
        logits = jnp.dot(x, params["embed_tokens"].T.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    else:
        # a served head is padded to whole lane tiles (`prepare_params`)
        logits = linear(x, lm_head)[..., :cfg.vocab_size]
    return logits.astype(jnp.float32), cache.replace(
        full_k=k, full_v=v, pos=pos + sq, stats=stats)


def forward_last_token(params, cfg, tokens, cache,
                       compute_dtype=jnp.bfloat16):
    return forward(params, cfg, tokens, cache, compute_dtype=compute_dtype,
                   last_only=True)


# ---------------------------------------------------------------------------
# canonical tree -> served tree, and HF checkpoint -> canonical tree
# ---------------------------------------------------------------------------

def prepare_layer(lp: Dict[str, Any]) -> Dict[str, Any]:
    """The layers' leaves (one layer's or the stack of all) as `forward`
    serves them: q / k / v merged `[D, (H + 2 G) d]` (block quantization
    is per column: bit-exact). Prepared leaves pass through."""
    from bigdl_tpu.ops.quant import QTensor, concat_qtensors_n

    if "qkv_proj" in lp:
        return lp
    lp = dict(lp)
    ws = [lp.pop(n) for n in _ATTN_MERGED]
    lp["qkv_proj"] = (concat_qtensors_n(ws) if isinstance(ws[0], QTensor)
                      else jnp.concatenate(ws, axis=-1))
    return lp


def pad_head(w):
    """A quantized head `[D, V]` whose columns are no whole lane tiles
    (a slice of a vocabulary: 37,984 = 296.75 x 128) with zero columns up
    to the next tile: a width no kernel plan tiles takes the XLA plan,
    which dequantizes the whole head every pass (0.31 GB in float32 at
    the published widths, AOT for v5e). `forward` cuts the logits back
    to the vocabulary. A dense or a whole-tile head passes through."""
    from bigdl_tpu.ops.quant import QTensor, concat_qtensors_n, quantize

    pad = -w.shape[-1] % _LANES
    if not pad or not isinstance(w, QTensor):
        return w
    return concat_qtensors_n([w, quantize(
        jnp.zeros((w.shape[0], pad), jnp.float32), w.qtype)])


def prepare_params(params: Dict[str, Any], cfg: SdarMoeConfig = None
                   ) -> Dict[str, Any]:
    out = {**params, "layers": prepare_layer(params["layers"])}
    if "lm_head" in out:
        out["lm_head"] = pad_head(out["lm_head"])
    return out


def layer_leaves(params: Dict[str, Any], cfg: SdarMoeConfig, i: int
                 ) -> Dict[str, Any]:
    """Layer `i`'s own leaves out of the stacks of a canonical or served
    tree (its routed experts stay in `params["experts"]`, row `i`)."""
    return jax.tree.map(lambda a: a[i], params["layers"])


_LINEARS = {"self_attn.q_proj": "q_proj", "self_attn.k_proj": "k_proj",
            "self_attn.v_proj": "v_proj", "self_attn.o_proj": "o_proj"}
_VECTORS = {"self_attn.q_norm": "q_norm", "self_attn.k_norm": "k_norm",
            "input_layernorm": "input_layernorm",
            "post_attention_layernorm": "post_attention_layernorm"}
_EXPERT_MLP = {"gate_proj": "experts_gate", "up_proj": "experts_up",
               "down_proj": "experts_down"}


def convert_hf_params(
    tensors,
    cfg: SdarMoeConfig,
    qtype: Optional[str] = "sym_int4",
    compute_dtype=jnp.bfloat16,
    modules_to_not_convert: Tuple[str, ...] = (),
    imatrix=None,
) -> Dict[str, Any]:
    """HF tensors -> the served tree. Tensor names ASSUMED (those of the
    autoregressive family the checkpoints were trained from; no
    checkpoint of this model has been read here): `self_attn.{q,k,v,o}_
    proj`, `self_attn.{q,k}_norm`, `input_layernorm`,
    `post_attention_layernorm`, `mlp.gate` `[E, D]` and
    `mlp.experts.<e>.{gate,up,down}_proj`. The router and the norms stay
    unquantized; of the routed experts only those this chip holds
    (`cfg.share`) are converted, and of an embedding or head with more
    rows than `cfg.vocab_size` the chip's slice (rows `ep_rank *
    vocab_size ..`)."""
    from bigdl_tpu.ops.quant import FLOAT_QTYPES, quantize_linear

    del imatrix
    do_quant = qtype is not None and qtype not in FLOAT_QTYPES
    share, n = cfg.share, cfg.num_hidden_layers

    def rows(w):
        w = np.asarray(w)
        if w.shape[0] > cfg.vocab_size:
            lo = cfg.ep_rank * cfg.vocab_size
            w = w[lo:lo + cfg.vocab_size]
        return w

    def lin(name, w):
        w = jnp.asarray(np.asarray(w))
        if do_quant and not any(m in name for m in modules_to_not_convert):
            return quantize_linear(w, qtype)
        return w.T.astype(compute_dtype)

    def vec(w):
        return jnp.asarray(np.asarray(w)).astype(compute_dtype)

    params: Dict[str, Any] = {}
    layers = [dict() for _ in range(n)]
    experts = {k: [[None] * share.held for _ in range(n)]
               for k in _EXPERT_KEYS}
    for name, w in tensors:
        if name == "model.embed_tokens.weight":
            params["embed_tokens"] = vec(rows(w))
        elif name == "model.norm.weight":
            params["norm"] = vec(w)
        elif name == "lm_head.weight":
            params["lm_head"] = lin(name, rows(w))
        elif name.startswith("model.layers."):
            parts = name.split(".")
            i = int(parts[2])
            if i >= n:
                continue
            sub = ".".join(parts[3:])
            stem = sub[:-len(".weight")] if sub.endswith(".weight") else sub
            if stem in _LINEARS:
                layers[i][_LINEARS[stem]] = lin(name, w)
            elif stem in _VECTORS:
                layers[i][_VECTORS[stem]] = vec(w)
            elif stem == "mlp.gate":
                layers[i]["router"] = vec(w).T
            elif sub.startswith("mlp.experts."):
                e = int(parts[5]) - share.first_held
                if 0 <= e < share.held:
                    experts[_EXPERT_MLP[parts[6]]][i][e] = lin(name, w)
    need = set(_LINEARS.values()) | set(_VECTORS.values()) | {"router"}
    for i, lp in enumerate(layers):
        missing = sorted(need - set(lp))
        if missing:
            raise ValueError(f"checkpoint missing layer {i} tensors: "
                             f"{missing}")
    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)  # noqa: E731
    params["layers"] = stack(layers)
    for k, per_layer in experts.items():
        if any(e is None for row in per_layer for e in row):
            raise ValueError(f"checkpoint missing held experts of {k}")
    params["experts"] = {k: stack([stack(row) for row in per_layer])
                         for k, per_layer in experts.items()}
    if cfg.tie_word_embeddings:
        params.pop("lm_head", None)
    elif "lm_head" not in params:
        raise ValueError("checkpoint has no lm_head.weight")
    return prepare_params(params, cfg)
