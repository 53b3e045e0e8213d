"""DeepSeek-V3.2-Exp: latent attention (MLA) with learned sparse attention
in EVERY layer, the group-limited bias-corrected router (`noaux_tc` over
8 groups), and a multi-token-prediction (MTP) module that drafts one
token ahead; functional and static-shape.

The layer, as this module reads the published config (pre-norm residual,
RMSNorm, untied head; `x` the layer's normed input):

- MLA as DeepSeek-V2's (`models/deepseek_v2.mla_project`, `_absorb`):
  `c_q = RMSNorm(x W_qa)`, `[q_n | q_r]_h = c_q W_qb`, `[c_kv | k_r] = x
  W_kva`, `c_kv <- RMSNorm(c_kv)`, rope (channels 2i, 2i+1 together) on
  `q_r` and the one shared `k_r`. YaRN as published (factor 40 over 4096,
  `mscale` = `mscale_all_dim` = 1): the softmax scale is `(nope +
  rope)^-1/2 m^2`, `m = 0.1 ln 40 + 1`.
- The indexer (`ops/dsa.py`, `models/dots3_note._index_queries`): `q_I =
  c_q W_Iq`, `k_I = LayerNorm(x W_Ik)`, `w = x W_Iw Hi^-1/2 Di^-1/2`, rope
  on the first `qk_rope_head_dim` channels of both, rotated as two
  HALVES (the published indexer's form; MLA's stay pairs); `I[t, s] =
  sum_j w[t, j] relu(q_I[t, j] . k_I[s])`; position t attends the
  `index_topk` positions `s <= t` of largest `I[t, s]`.
- Feed-forward: the first `first_k_dense_replace` layers dense SwiGLU,
  the others a shared expert plus this chip's share of the routed sum
  (`deepseek_v2.moe_block`): `sigmoid` scores in float32, choice by
  `scores + bias` within the 4 best of 8 groups (a group scores the sum
  of its two best), weights the chosen scores renormalised, times 2.5.
- The MTP module (checkpoint layer `num_hidden_layers`): with `h_i` the
  main stack's output at position i BEFORE the final norm and `t_{i+1}`
  the token that follows, `h'_i = [RMSNorm_e(Emb(t_{i+1})) ;
  RMSNorm_h(h_i)] W_eh` (the embedding half first), one whole block of
  the expert kind at position i with ITS OWN latent and index rows, then
  `Head(RMSNorm_s(.))`: the distribution of token i + 2. Embedding and
  head are the main model's.

The cache: one latent plane `[L + 1, B, C + R, S]` and one index plane
`[L + 1, B, Di, S]`, the MTP block the last layer of each.

`forward_hidden` is the forward. `sq <= 2` rows a slot is the decode /
verify path (each row with its own limit `pos + r` and its own
selection, over one fetch of the slot's rows: `ops/pallas/
dsa_attention.py`), and returns the logits and the pre-norm hidden rows.
A longer `sq` is a prefill chunk; given `carry` (the hidden row of the
position before the chunk) it also runs the MTP block over the chunk
LAGGED BY ONE: the MTP row of position i needs token i + 1, so a chunk
at positions `p .. p + T - 1` writes the MTP rows `p - 1 .. p + T - 2`
from `[carry, h_p .. h_{p+T-2}]` and the chunk's own tokens; the row of
the chunk's last position waits for the next chunk, or for the first
sampled token (`mtp_forward`, which the serving engine calls then). A
first chunk (`p = 0`) has no row -1: its inputs are rolled by one so
that rows `0 .. T - 2` are written and column `T - 1` holds a dead row
that the next chunk or `mtp_forward` overwrites.

Parameter tree (linears contraction-major `[K, N]`, QTensor or dense):
{
  "embed_tokens": [V, D], "norm": [D], "lm_head": [D, V],
  "layers": one dict a layer: input_layernorm, post_attention_layernorm,
      q_a_proj, q_a_layernorm, q_b_proj, kv_a_proj, kv_a_layernorm, w_uk,
      w_uv, o_proj, index_q_proj, index_k_proj, index_k_norm,
      index_k_norm_bias, index_w_proj; a dense layer gate_proj / up_proj
      / down_proj; an expert layer router [D, E_total], router_bias,
      shared_gate / shared_up / shared_down,
  "experts": experts_gate / experts_up [Le + 1, held, D, F], experts_down
      [Le + 1, held, F, D]: the expert layers', then the MTP block's,
  "mtp": enorm, hnorm [D], eh_proj [2 D, D], shared_head_norm [D],
      "block": one expert layer's dict,
}
Before `prepare_params` a layer holds `kv_b_proj` in the place of `w_uk`
/ `w_uv` and its narrow linears are not padded (the canonical tree).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.models.deepseek_v2 import (_pad_n, _rope, _rope_tables,
                                          mla_project, moe_block,
                                          prepare_attention, swiglu)
from bigdl_tpu.models.dots3_note import (_ein, _index_queries, _positions,
                                         _sparse_chunk)
from bigdl_tpu.models.llama import embedding_lookup
from bigdl_tpu.ops import dsa
from bigdl_tpu.ops.kvcache import (CacheSpec, KVCache, PlaneSpec,
                                   init_cache_spec, update_latent)
from bigdl_tpu.ops.matmul import linear
from bigdl_tpu.ops.moe_routed import STATS, Share
from bigdl_tpu.ops.norms import rms_norm

# rows a slot the decode / verify path takes (one token, or the token
# and its draft)
VERIFY_ROWS = 2


@dataclasses.dataclass(frozen=True)
class DeepseekV32Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256       # experts held HERE (see ep_size)
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    topk_method: str = "noaux_tc"
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    first_k_dense_replace: int = 3
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None
    max_position_embeddings: int = 163840
    tie_word_embeddings: bool = False
    ep_size: int = 1
    ep_rank: int = 0

    @classmethod
    def from_hf(cls, hf: Dict[str, Any]) -> "DeepseekV32Config":
        for key, only in (("moe_layer_freq", 1), ("hidden_act", "silu"),
                          ("attention_bias", False)):
            if hf.get(key, only) != only:
                raise NotImplementedError(f"{key} {hf[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        if kw.get("rope_scaling") is not None:
            kw["rope_scaling"] = tuple(sorted(kw["rope_scaling"].items()))
        cfg = cls(**kw)
        if cfg.num_nextn_predict_layers not in (0, 1):
            raise NotImplementedError(
                f"num_nextn_predict_layers {cfg.num_nextn_predict_layers}: "
                "one MTP module is what this family drafts with")
        if cfg.share.experts_total % max(cfg.n_group, 1):
            raise ValueError("experts do not divide into n_group groups")
        return cfg

    @property
    def share(self) -> Share:
        return Share(self.n_routed_experts * self.ep_size,
                     self.n_routed_experts * self.ep_rank,
                     self.n_routed_experts)

    @property
    def n_dense(self) -> int:
        return min(self.first_k_dense_replace, self.num_hidden_layers)

    @property
    def n_bodies(self) -> int:
        """Layer bodies that keep cache rows: the stack and the MTP
        block."""
        return self.num_hidden_layers + self.num_nextn_predict_layers

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def scale(self) -> float:
        """The softmax scale, YaRN's factor in it."""
        return self.hd ** -0.5 * _rope(self)[2]

    # what cost models and the generic engine read off a config
    @property
    def num_key_value_heads(self) -> int:
        return self.num_attention_heads

    @property
    def hd(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def sliding_window(self):
        return None

    @property
    def kv_values_per_position(self) -> int:
        return self.latent_dim + self.index_head_dim

    def matmul_flops_per_token(self) -> int:
        """Forward matmul operations a token needs on THIS chip through
        the main stack (the MTP block is one more expert layer a row)."""
        d, h = self.hidden_size, self.num_attention_heads
        c, r = self.kv_lora_rank, self.qk_rope_head_dim
        attn = (d * self.q_lora_rank + self.q_lora_rank * h * self.hd
                + d * (c + r) + c * h * (self.qk_nope_head_dim
                                         + self.v_head_dim)
                + h * self.v_head_dim * d
                + self.q_lora_rank * self.index_n_heads * self.index_head_dim
                + d * (self.index_head_dim + self.index_n_heads))
        f = self.moe_intermediate_size
        moe = 3 * d * f * (self.n_shared_experts
                           + self.num_experts_per_tok / self.ep_size)
        n_moe = self.num_hidden_layers - self.n_dense
        return int(2 * (self.num_hidden_layers * attn
                        + self.n_dense * 3 * d * self.intermediate_size
                        + n_moe * (moe + d * self.share.experts_total)
                        + d * self.vocab_size))

    def attn_flops_per_cached_token(self) -> int:
        """Decode attention per cached position: the index score of
        every position in every layer (the absorbed product runs over the
        selected ones, which stop growing at `index_topk`)."""
        return (self.num_hidden_layers * 2 * self.index_n_heads
                * self.index_head_dim)


def speculative_depth(cfg: DeepseekV32Config) -> int:
    """Tokens a step may draft ahead: what the serving engine's
    `speculative_tokens` may be set to for this family."""
    return cfg.num_nextn_predict_layers


def cache_spec(cfg: DeepseekV32Config) -> CacheSpec:
    n = cfg.n_bodies
    return CacheSpec(
        "latent", n, latent_dim=cfg.latent_dim,
        stats_len=len(STATS) if cfg.n_dense < n else 0,
        planes=(PlaneSpec("latent", n, (cfg.latent_dim,)),
                PlaneSpec("index", n, (cfg.index_head_dim,))))


def new_cache(cfg: DeepseekV32Config, batch: int, max_seq: int,
              quantized=False) -> KVCache:
    """The two planes; bf16 only (`ops/kvcache.reject_non_bf16_latent`)."""
    return init_cache_spec(cache_spec(cfg), batch, max_seq,
                           kv_cache_dtype=quantized)


def _attention(y, lp, cfg, lat, idx, li, pos, wpos, cos, sin, selected=None,
               probe=None):
    """One body's attention on the normed `y` `[B, T, D]` at positions
    `pos ..` (rows written at `wpos ..`): the output after `W_o` and the
    two stacks with this body's rows written. `T <= VERIFY_ROWS` is the
    decode path; `selected` `[B, T, S]` takes the selection's place and
    `probe` receives the index scores and the selection (checks)."""
    b, t, _ = y.shape
    h, vd = cfg.num_attention_heads, cfg.v_head_dim
    q_nope, q_pe, new, c_q = mla_project(y, lp, cfg, cos, sin)
    with jax.named_scope("dsa.index"):
        q_i, k_i, w_i = _index_queries(y, c_q, lp, cfg, cos, sin,
                                       interleaved=False)
        idx = update_latent(idx, li, k_i, wpos)
    with jax.named_scope("mla.kv_latent"):
        lat = update_latent(lat, li, new, wpos)
    w_uk, w_uv = lp["w_uk"], lp["w_uv"]
    if t <= VERIFY_ROWS:
        # one row goes in as the one-row kernels take it (what dots3
        # runs); the rows of a verify step keep their axis
        cut = (lambda a: a[:, 0]) if t == 1 else (lambda a: a)
        with jax.named_scope("dsa.index"):
            scores = dsa.dsa_index_scores_decode(cut(q_i), cut(w_i), idx, li,
                                                 pos)
        with jax.named_scope("dsa.select"):
            sel = (dsa.dsa_select_decode(scores, cfg.index_topk)
                   if selected is None else cut(selected)) != 0
        with jax.named_scope("mla.absorb"):
            q_abs = _ein("...hd,hdc->...hc", cut(q_nope), w_uk).astype(
                y.dtype)
        with jax.named_scope("mla.sparse"):
            o_lat = dsa.sparse_mla_decode(q_abs, cut(q_pe), lat, li, pos, sel,
                                          cfg.scale)
        with jax.named_scope("mla.out"):
            o = _ein("...hc,hcd->...hd", o_lat, w_uv)
        if t == 1:
            o, scores, sel = o[:, None], scores[:, None], sel[:, None]
    else:
        o, scores, sel = _sparse_chunk(cfg, cfg, q_nope, q_pe, q_i, w_i, lat,
                                       idx, li, pos, w_uk, w_uv, selected)
    if probe is not None:
        probe["index_scores"], probe["selected"] = scores, sel
    with jax.named_scope("mla.out"):
        out = linear(o.astype(y.dtype).reshape(b, t, h * vd), lp["o_proj"])
    return out, lat, idx


def _block(x, lp, cfg, lat, idx, li, pos, wpos, cos, sin, experts, ei, tally):
    """One body (attention and feed-forward, both residuals) on `x`:
    layer `li` of the planes, expert stack `ei`."""
    eps = cfg.rms_norm_eps
    a, lat, idx = _attention(rms_norm(x, lp["input_layernorm"], eps), lp, cfg,
                             lat, idx, li, pos, wpos, cos, sin)
    x = x + a
    hid = rms_norm(x, lp["post_attention_layernorm"], eps)
    if "gate_proj" in lp:
        x = x + swiglu(hid, lp["gate_proj"], lp["up_proj"], lp["down_proj"])
    else:
        y, st = moe_block(hid, lp, experts, jnp.int32(ei), cfg)
        x = x + y
        tally = tally + st
    return x, lat, idx, tally


def _tables(cfg, pos, sq: int):
    cos, sin, _ = _rope_tables(cfg, pos, sq)
    return cos, sin


def _head(params, x, norm, eps):
    x = rms_norm(x, norm, eps)
    lm_head = params.get("lm_head")
    if lm_head is None:
        logits = jnp.dot(x, params["embed_tokens"].T.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    else:
        logits = linear(x, lm_head)
    return logits.astype(jnp.float32)


def _mtp_rows(params, cfg, hidden, tokens, lat, idx, pos, wpos, tally,
              compute_dtype):
    """The MTP block over rows `(hidden[:, j], tokens[:, j])` at
    positions `pos + j`: its output rows (before `shared_head_norm`) and
    the planes with ITS rows written (the last layer of each)."""
    m = params["mtp"]
    eps = cfg.rms_norm_eps
    with jax.named_scope("mtp.combine"):
        e = rms_norm(embedding_lookup(params["embed_tokens"], tokens,
                                      compute_dtype), m["enorm"], eps)
        x = linear(jnp.concatenate(
            [e, rms_norm(hidden.astype(compute_dtype), m["hnorm"], eps)],
            axis=-1), m["eh_proj"])
    cos, sin = _tables(cfg, pos, tokens.shape[1])
    with jax.named_scope("mtp.block"):
        return _block(x, m["block"], cfg, lat, idx,
                      jnp.int32(cfg.num_hidden_layers), pos, wpos, cos, sin,
                      params.get("experts"),
                      cfg.num_hidden_layers - cfg.n_dense, tally)


def forward_hidden(
    params: Dict[str, Any],
    cfg: DeepseekV32Config,
    tokens: jax.Array,
    cache: KVCache,
    carry: Optional[jax.Array] = None,
    compute_dtype=jnp.bfloat16,
    last_only: bool = False,
) -> Tuple[jax.Array, jax.Array, KVCache]:
    """The main stack over `tokens` `[B, sq]` at `cache.pos`: logits
    `[B, sq, V]` float32, the hidden rows before the final norm `[B, sq,
    D]`, and the cache advanced by `sq`. `carry` `[B, D]` (a prefill
    chunk): the hidden row of the position before the chunk; the MTP
    block then runs over the chunk lagged by one (module docstring)."""
    b, sq = tokens.shape
    # serving marks an empty slot with -1: here it is a slot at 0
    pos = jnp.maximum(cache.pos, 0)
    x = embedding_lookup(params["embed_tokens"], tokens, compute_dtype)
    cos, sin = _tables(cfg, pos, sq)
    lat, idx, stats = cache.latent, cache.index, cache.stats
    tally = jnp.zeros((len(STATS),), jnp.int32) if stats is None else stats
    experts = params.get("experts")
    for i, lp in enumerate(params["layers"]):
        x, lat, idx, tally = _block(x, lp, cfg, lat, idx, jnp.int32(i), pos,
                                    pos, cos, sin, experts, i - cfg.n_dense,
                                    tally)
    if carry is not None and "mtp" in params:
        first = _positions(pos, b) == 0                         # [B]
        lagged = jnp.concatenate(
            [carry[:, None].astype(x.dtype), x[:, :-1]], axis=1)
        hid_in = jnp.where(first[:, None, None],
                           jnp.roll(lagged, -1, axis=1), lagged)
        tok_in = jnp.where(first[:, None], jnp.roll(tokens, -1, axis=1),
                           tokens)
        mpos = jnp.maximum(pos - 1, 0)
        _, lat, idx, tally = _mtp_rows(params, cfg, hid_in, tok_in, lat, idx,
                                       mpos, mpos, tally, compute_dtype)
    hidden = x
    if last_only:
        x = x[:, -1:, :]
    logits = _head(params, x, params["norm"], cfg.rms_norm_eps)
    return logits, hidden, cache.replace(
        latent=lat, index=idx, pos=pos + sq,
        stats=None if stats is None else tally)


def mtp_forward(params, cfg: DeepseekV32Config, hidden, tokens,
                cache: KVCache, pos, wpos=None, compute_dtype=jnp.bfloat16):
    """The MTP module over rows `(hidden[:, j], tokens[:, j])` (`[B, R,
    D]`, `[B, R]`: the main stack's hidden row of a position and the
    token that FOLLOWS it) at positions `pos + j`, written at `wpos + j`
    (`pos` where not given; a position past the cache's end writes
    nothing): the logits `[B, R, V]` of the token after next, and the
    cache with the module's rows written. `cache.pos` stays."""
    stats = cache.stats
    tally = jnp.zeros((len(STATS),), jnp.int32) if stats is None else stats
    x, lat, idx, tally = _mtp_rows(
        params, cfg, hidden, tokens, cache.latent, cache.index, pos,
        pos if wpos is None else wpos, tally, compute_dtype)
    logits = _head(params, x, params["mtp"]["shared_head_norm"],
                   cfg.rms_norm_eps)
    return logits, cache.replace(latent=lat, index=idx,
                                 stats=None if stats is None else tally)


def forward(params, cfg, tokens, cache, compute_dtype=jnp.bfloat16,
            last_only: bool = False):
    """The registry's forward: the main stack alone (`generate()` and a
    server with `speculative_tokens` 0 never run the MTP module)."""
    logits, _, cache = forward_hidden(params, cfg, tokens, cache,
                                      compute_dtype=compute_dtype,
                                      last_only=last_only)
    return logits, cache


def forward_last_token(params, cfg, tokens, cache,
                       compute_dtype=jnp.bfloat16):
    return forward(params, cfg, tokens, cache, compute_dtype=compute_dtype,
                   last_only=True)


def attention_block(y, lp, cfg: DeepseekV32Config, cache: KVCache,
                    selected=None, probe=None):
    """One body's attention alone, as `forward_hidden` runs it: the
    normed `y` `[B, sq, D]` through layer 0 of `cache` at `cache.pos`.
    For a check that holds a single layer to a reference on the same
    input."""
    sq = y.shape[1]
    cos, sin = _tables(cfg, cache.pos, sq)
    out, lat, idx = _attention(y, lp, cfg, cache.latent, cache.index,
                               jnp.int32(0), cache.pos, cache.pos, cos, sin,
                               selected, probe)
    return out, cache.replace(latent=lat, index=idx, pos=cache.pos + sq)


# ---------------------------------------------------------------------------
# canonical tree -> served tree, and HF checkpoint -> canonical tree
# ---------------------------------------------------------------------------

_NARROW = ("index_k_proj", "index_w_proj")


def prepare_layer(lp: Dict[str, Any], cfg: DeepseekV32Config,
                  compute_dtype=jnp.bfloat16) -> Dict[str, Any]:
    """One canonical layer as it is served: `kv_b_proj` as `w_uk` /
    `w_uv` in bf16 and `kv_a_proj` padded (`deepseek_v2.
    prepare_attention`), the indexer's narrow linears padded to a lane
    multiple. A prepared layer passes through."""
    if "kv_b_proj" not in lp:
        return lp
    lp = dict(lp)
    served = prepare_attention(
        {k: jax.tree.map(lambda a: a[None], lp.pop(k))
         for k in ("kv_b_proj", "kv_a_proj")}, cfg, compute_dtype)
    lp.update(jax.tree.map(lambda a: a[0], served))
    for k in _NARROW:
        lp[k] = _pad_n(lp[k])
    return lp


def prepare_params(params: Dict[str, Any], cfg: DeepseekV32Config,
                   compute_dtype=jnp.bfloat16) -> Dict[str, Any]:
    out = dict(params)
    out["layers"] = tuple(prepare_layer(lp, cfg, compute_dtype)
                          for lp in params["layers"])
    if "mtp" in params:
        out["mtp"] = dict(params["mtp"], block=prepare_layer(
            params["mtp"]["block"], cfg, compute_dtype))
    return out


_LINEARS = {"self_attn.q_a_proj": "q_a_proj",
            "self_attn.q_b_proj": "q_b_proj",
            "self_attn.kv_a_proj_with_mqa": "kv_a_proj",
            "self_attn.kv_b_proj": "kv_b_proj",
            "self_attn.o_proj": "o_proj",
            "self_attn.indexer.wq_b": "index_q_proj",
            "self_attn.indexer.wk": "index_k_proj",
            "self_attn.indexer.weights_proj": "index_w_proj",
            "mlp.gate_proj": "gate_proj", "mlp.up_proj": "up_proj",
            "mlp.down_proj": "down_proj",
            "mlp.shared_experts.gate_proj": "shared_gate",
            "mlp.shared_experts.up_proj": "shared_up",
            "mlp.shared_experts.down_proj": "shared_down"}
_VECTORS = {"input_layernorm.weight": "input_layernorm",
            "post_attention_layernorm.weight": "post_attention_layernorm",
            "self_attn.q_a_layernorm.weight": "q_a_layernorm",
            "self_attn.kv_a_layernorm.weight": "kv_a_layernorm",
            "self_attn.indexer.k_norm.weight": "index_k_norm",
            "self_attn.indexer.k_norm.bias": "index_k_norm_bias",
            "mlp.gate.e_score_correction_bias": "router_bias"}
_MTP_VECTORS = {"enorm.weight": "enorm", "hnorm.weight": "hnorm",
                "shared_head.norm.weight": "shared_head_norm"}
_EXPERT_MLP = {"gate_proj": "experts_gate", "up_proj": "experts_up",
               "down_proj": "experts_down"}
_ATTN_KEYS = {"q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj",
              "input_layernorm", "post_attention_layernorm",
              "q_a_layernorm", "kv_a_layernorm", "index_q_proj",
              "index_k_proj", "index_w_proj", "index_k_norm",
              "index_k_norm_bias"}
_MOE_KEYS = {"router", "router_bias", "shared_gate", "shared_up",
             "shared_down"}


def convert_hf_params(
    tensors,
    cfg: DeepseekV32Config,
    qtype: Optional[str] = "sym_int4",
    compute_dtype=jnp.bfloat16,
    modules_to_not_convert: Tuple[str, ...] = (),
    imatrix=None,
) -> Dict[str, Any]:
    """HF tensors -> the served tree, under the checkpoint's names
    (`model.layers.<i>.self_attn.indexer.{wq_b, wk, k_norm,
    weights_proj}`, `mlp.gate.e_score_correction_bias`; the MTP module
    is `model.layers.<num_hidden_layers>.*` with `enorm`, `hnorm`,
    `eh_proj` and `shared_head.norm`; its `embed_tokens` and
    `shared_head.head` are the main model's and are skipped): ASSUMED
    from the published inference code, no checkpoint has been read here.
    The router, its bias and the norms stay unquantized; of the routed
    experts only those this chip holds (`cfg.share`) are converted."""
    from bigdl_tpu.ops.quant import FLOAT_QTYPES, quantize_linear

    del imatrix
    do_quant = qtype is not None and qtype not in FLOAT_QTYPES
    share, n_dense, n_layers = cfg.share, cfg.n_dense, cfg.num_hidden_layers
    n_mtp = cfg.num_nextn_predict_layers
    n_stacks = n_layers - n_dense + n_mtp

    def lin(name, w):
        w = jnp.asarray(np.asarray(w))
        if do_quant and not any(m in name for m in modules_to_not_convert):
            return quantize_linear(w, qtype)
        return w.T.astype(compute_dtype)

    def vec(w):
        return jnp.asarray(np.asarray(w)).astype(compute_dtype)

    params: Dict[str, Any] = {}
    layers = [dict() for _ in range(n_layers + n_mtp)]
    mtp: Dict[str, Any] = {}
    experts = {k: [[None] * share.held for _ in range(n_stacks)]
               for k in _EXPERT_MLP.values()}
    for name, w in tensors:
        if name == "model.embed_tokens.weight":
            params["embed_tokens"] = vec(w)
        elif name == "model.norm.weight":
            params["norm"] = vec(w)
        elif name == "lm_head.weight":
            params["lm_head"] = lin(name, w)
        elif name.startswith("model.layers."):
            parts = name.split(".")
            layer = int(parts[2])
            if layer >= n_layers + n_mtp:
                continue
            sub = ".".join(parts[3:])
            stem = sub[:-len(".weight")] if sub.endswith(".weight") else sub
            if stem in _LINEARS:
                layers[layer][_LINEARS[stem]] = lin(name, w)
            elif sub in _VECTORS:
                layers[layer][_VECTORS[sub]] = vec(w)
            elif sub == "mlp.gate.weight":
                layers[layer]["router"] = vec(w).T
            elif sub.startswith("mlp.experts.") and layer >= n_dense:
                e = int(parts[5]) - share.first_held
                if 0 <= e < share.held:
                    experts[_EXPERT_MLP[parts[6]]][layer - n_dense][e] = \
                        lin(name, w)
            elif layer >= n_layers and sub in _MTP_VECTORS:
                mtp[_MTP_VECTORS[sub]] = vec(w)
            elif layer >= n_layers and sub == "eh_proj.weight":
                mtp["eh_proj"] = lin(name, w)
    for i, lp in enumerate(layers):
        need = _ATTN_KEYS | ({"gate_proj", "up_proj", "down_proj"}
                             if i < n_dense else _MOE_KEYS)
        missing = sorted(need - set(lp))
        if missing:
            raise ValueError(f"checkpoint missing layer {i} tensors: "
                             f"{missing}")
    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)  # noqa: E731
    if n_stacks:
        for k, per_layer in experts.items():
            if any(e is None for row in per_layer for e in row):
                raise ValueError(f"checkpoint missing held experts of {k}")
        params["experts"] = {k: stack([stack(row) for row in per_layer])
                             for k, per_layer in experts.items()}
    params["layers"] = tuple(layers[:n_layers])
    if n_mtp:
        missing = sorted({"enorm", "hnorm", "eh_proj", "shared_head_norm"}
                         - set(mtp))
        if missing:
            raise ValueError(f"checkpoint missing MTP tensors: {missing}")
        params["mtp"] = dict(mtp, block=layers[n_layers])
    if cfg.tie_word_embeddings:
        params.pop("lm_head", None)
    elif "lm_head" not in params:
        raise ValueError("checkpoint has no lm_head.weight")
    return prepare_params(params, cfg, compute_dtype)
