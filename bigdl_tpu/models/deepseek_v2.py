"""DeepSeek-V2: multi-head latent attention (MLA) and group-limited sparse
experts with shared experts, functional and static-shape.

The layer, as published (HF `modeling_deepseek.py`):

- Attention. `c_q = RMSNorm(x W_qa)`; `q = c_q W_qb`, per head
  `[q_nope | q_pe]`; `[c_kv | k_pe] = x W_kva`, `c_kv <- RMSNorm(c_kv)`;
  `k_pe` is ONE head shared by all query heads and is not normed; rope
  (channels 2i and 2i+1 rotate together; DeepSeek's YaRN,
  `ops/rope.deepseek_yarn_freqs`) on `q_pe` and `k_pe` only;
  `[k_nope | v]` per head `= c_kv W_kvb`; scores
  `(q_nope . k_nope + q_pe . k_pe) * (nope + rope)^-0.5 * m^2`;
  output `concat_h(softmax . v) W_o`.
- The cache holds, per position and layer, the normed `c_kv` and the
  roped `k_pe` (576 values against 32,768 for plain MHA at 128 heads):
  ONE latent plane of the slab (`ops/kvcache.py`, `CacheSpec("latent")`).
- Feed-forward. The first `first_k_dense_replace` layers are dense
  SwiGLU; the others `y = SwiGLU_shared(x) + sum_i w_i SwiGLU^(e_i)(x)`
  with the routed sum from `ops/moe_routed.py`, which routes over all
  experts and computes the share this chip holds (`ep_size` chips share
  each layer; `n_routed_experts` counts the experts held HERE, rank
  `ep_rank` holds experts `ep_rank * held ..`; with `ep_size` 1 it is
  the whole layer).

Absorbed or expanded, chosen from the shapes alone (`_absorb`): with the
up-projection absorbed into the query (`q_nope W_uk^T`), attention runs
on the latent rows themselves (multi-query, 576-wide keys whose first
512 columns are the values) and costs `sq * S * H * (2C + R)`
multiply-adds; expanding K and V per head from the cached rows costs
`S * C * H * (nope + v)` once plus `sq * S * H * (nope + R + v)`. The
first is smaller below `sq = C (nope + v) / (2C - nope - v)` (170 at the
published widths): decode absorbs (`ops/pallas/mla_attention.py`), a
prefill chunk of 256 expands.

`W_kvb` is kept dequantized in bf16 as `w_uk` `[H, nope, C]` and `w_uv`
`[H, C, v]` (`prepare_params`): the absorbed product contracts over the
axis the int4 blocks do NOT run along, so the block scales cannot be
factored out of it; both paths read the same two leaves.

Parameter tree (linears contraction-major `[K, N]`, QTensor or dense):
{
  "embed_tokens": [V, D], "norm": [D], "lm_head": [D, V],
  "dense_layers": {attention leaves, gate_proj, up_proj, down_proj}
                  stacked over the leading dense layers (absent if none),
  "moe_layers":   {attention leaves, router [D, E_total],
                   shared_gate / shared_up / shared_down,
                   experts_gate / experts_up [held, D, F],
                   experts_down [held, F, D]} stacked over the rest,
}
attention leaves: input_layernorm, post_attention_layernorm, q_a_proj,
q_a_layernorm, q_b_proj (or q_proj without a q_lora_rank), kv_a_proj
(N padded to a lane multiple when quantized), kv_a_layernorm, w_uk, w_uv,
o_proj. Before `prepare_params` the tree holds `kv_b_proj` `[C, H (nope +
v)]` in their place: the canonical tree, which the benchmark's reference
reads.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.models.llama import embedding_lookup
from bigdl_tpu.ops.kvcache import (CacheSpec, KVCache, init_cache_spec,
                                   update_latent)
from bigdl_tpu.ops.matmul import hold_stacks, layer_params, linear
from bigdl_tpu.ops.moe_routed import STATS, Share, routed_experts
from bigdl_tpu.ops.norms import rms_norm
from bigdl_tpu.ops.pallas.mla_attention import mla_decode_attention
from bigdl_tpu.ops.quant import QTensor
from bigdl_tpu.ops.rope import (apply_rope, deepseek_yarn_freqs, rope_cos_sin,
                                rope_freqs)


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 160       # experts held HERE (see ep_size)
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    topk_method: str = "group_limited_greedy"
    routed_scaling_factor: float = 16.0
    norm_topk_prob: bool = False
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None
    max_position_embeddings: int = 163840
    tie_word_embeddings: bool = False
    # chips that share each layer's routed experts, and which of them
    # this is; the router keeps ep_size * n_routed_experts outputs
    ep_size: int = 1
    ep_rank: int = 0

    @classmethod
    def from_hf(cls, hf: Dict[str, Any]) -> "DeepseekV2Config":
        if hf.get("moe_layer_freq", 1) != 1:
            raise NotImplementedError("moe_layer_freq other than 1")
        if hf.get("scoring_func", "softmax") != "softmax":
            raise NotImplementedError(
                f"scoring_func {hf['scoring_func']!r}: only the softmax "
                "router is implemented")
        if hf.get("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"hidden_act {hf['hidden_act']!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        if kw.get("rope_scaling") is not None:
            kw["rope_scaling"] = tuple(sorted(kw["rope_scaling"].items()))
        cfg = cls(**kw)
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
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

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
        """Cached values of one position of one layer (roofline.py)."""
        return self.latent_dim

    def matmul_flops_per_token(self) -> int:
        """Forward matmul operations a token needs on THIS chip: the
        attention projections, dense or shared MLPs, its chosen experts
        that are held here in expectation, the head (roofline.py)."""
        d, h = self.hidden_size, self.num_attention_heads
        c, r = self.kv_lora_rank, self.qk_rope_head_dim
        qk, v = self.hd, self.v_head_dim
        q = (d * h * qk if self.q_lora_rank is None
             else d * self.q_lora_rank + self.q_lora_rank * h * qk)
        attn = (q + d * (c + r) + c * h * (self.qk_nope_head_dim + v)
                + h * v * d)
        f = self.moe_intermediate_size
        moe = 3 * d * f * (self.n_shared_experts
                           + self.num_experts_per_tok / self.ep_size)
        n_moe = self.num_hidden_layers - self.n_dense
        return int(2 * (self.num_hidden_layers * attn
                        + self.n_dense * 3 * d * self.intermediate_size
                        + n_moe * (moe + d * self.share.experts_total)
                        + d * self.vocab_size))

    def attn_flops_per_cached_token(self) -> int:
        """Absorbed decode attention per cached position, all layers."""
        return (self.num_hidden_layers * 2 * self.num_attention_heads
                * (2 * self.kv_lora_rank + self.qk_rope_head_dim))


def cache_spec(cfg: DeepseekV2Config) -> CacheSpec:
    return CacheSpec("latent", cfg.num_hidden_layers,
                     latent_dim=cfg.latent_dim,
                     stats_len=len(STATS) if cfg.n_dense
                     < cfg.num_hidden_layers else 0)


def new_cache(cfg: DeepseekV2Config, batch: int, max_seq: int,
              quantized=False) -> KVCache:
    """The latent slab; bf16 only (`ops/kvcache.reject_non_bf16_latent`)."""
    return init_cache_spec(cache_spec(cfg), batch, max_seq,
                           kv_cache_dtype=quantized)


def _rope(cfg: DeepseekV2Config):
    """(inv_freq, factor on cos and sin, factor on the softmax scale)."""
    rd = cfg.qk_rope_head_dim
    if cfg.rope_scaling is None:
        return rope_freqs(rd, cfg.rope_theta), 1.0, 1.0
    scaling = dict(cfg.rope_scaling)
    if scaling.get("rope_type", scaling.get("type")) != "yarn":
        raise NotImplementedError(f"rope_scaling {scaling}")
    return deepseek_yarn_freqs(rd, cfg.rope_theta, scaling,
                               cfg.max_position_embeddings)


def _absorb(cfg: DeepseekV2Config, sq: int) -> bool:
    """Absorbed attention does fewer multiply-adds than expanding K and V
    below this many query positions (module docstring)."""
    c, nv = cfg.kv_lora_rank, cfg.qk_nope_head_dim + cfg.v_head_dim
    return sq * (2 * c - nv) < c * nv


def _head_einsum(eq: str, a, w):
    """`einsum` of activations with a per-head weight, accumulated in
    float32. Off the TPU the operands are widened first: the CPU's dot
    lacks this batched bf16 form (same values, same accumulation)."""
    from bigdl_tpu.config import target_is_tpu

    if not target_is_tpu():
        a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    return jnp.einsum(eq, a, w, preferred_element_type=jnp.float32)


def _mask(sq: int, s: int, pos):
    """[B or 1, 1, sq, S]: key j is live for query i while j <= pos + i."""
    q_ids = (jnp.asarray(pos, jnp.int32).reshape(-1, 1)
             + jnp.arange(sq, dtype=jnp.int32)[None, :])        # [B|1, sq]
    k_ids = jnp.arange(s, dtype=jnp.int32)
    return (k_ids[None, None, :] <= q_ids[:, :, None])[:, None]


def mla_project(x, lp, cfg, cos, sin, q_rescale=None, kv_rescale=None):
    """The projections of one MLA layer on the normed `x` `[B, sq, D]`:
    `(q_nope, q_pe, new, c_q)`, the query's two parts per head, the
    `[B, sq, C + R]` rows a latent cache keeps of these positions (the
    normed `c_kv` and the roped `k_pe`) and the normed compressed query
    (None without a `q_lora_rank`). `cfg` gives the sizes (`DeepseekV2
    Config`, or another family's view of one kind of its layers);
    `q_rescale` / `kv_rescale` multiply the two latent norms' outputs
    where a family aligns their variance."""
    b, sq, _ = x.shape
    h, c, r = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, eps = cfg.qk_nope_head_dim, cfg.rms_norm_eps
    c_q = None
    with jax.named_scope("mla.q"):
        if cfg.q_lora_rank is None:
            q = linear(x, lp["q_proj"])
        else:
            c_q = rms_norm(linear(x, lp["q_a_proj"]), lp["q_a_layernorm"],
                           eps)
            if q_rescale is not None:
                c_q = (c_q * q_rescale).astype(x.dtype)
            q = linear(c_q, lp["q_b_proj"])
        q = q.reshape(b, sq, h, nope + r)
        q_nope = q[..., :nope]
        q_pe = apply_rope(q[..., nope:], cos, sin, interleaved=True)
    with jax.named_scope("mla.kv_latent"):
        kv = linear(x, lp["kv_a_proj"])
        c_kv = rms_norm(kv[..., :c], lp["kv_a_layernorm"], eps)
        if kv_rescale is not None:
            c_kv = (c_kv * kv_rescale).astype(x.dtype)
        new = jnp.concatenate(
            [c_kv, apply_rope(kv[..., c:c + r], cos, sin, interleaved=True)],
            axis=-1)
    return q_nope, q_pe, new, c_q


def _attention(x, lp, cfg, lat, lidx, pos, cos, sin, scale):
    """One layer's MLA on `x` `[B, sq, D]`; returns the attention output
    (before the residual) and the latent stack with this layer's new
    rows written."""
    b, sq, _ = x.shape
    h, c, vd = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.v_head_dim
    q_nope, q_pe, new, _ = mla_project(x, lp, cfg, cos, sin)
    with jax.named_scope("mla.kv_latent"):
        lat = update_latent(lat, lidx, new, pos)
    w_uk, w_uv = lp["w_uk"], lp["w_uv"]       # [H, nope, C], [H, C, v]
    if _absorb(cfg, sq):
        with jax.named_scope("mla.absorb"):
            q_abs = _head_einsum("bqhd,hdc->bqhc", q_nope,
                                 w_uk).astype(x.dtype)
        with jax.named_scope("mla.attn"):
            if sq == 1:
                o_lat = mla_decode_attention(
                    q_abs[:, 0], q_pe[:, 0], lat, lidx, pos, scale)[:, None]
            else:
                one = lax.dynamic_index_in_dim(lat, lidx, 0, keepdims=False)
                ckv, kpe = one[:, :c], one[:, c:]
                s_ = (jnp.einsum("bqhc,bcs->bhqs", q_abs, ckv,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bqhr,brs->bhqs", q_pe, kpe,
                                   preferred_element_type=jnp.float32))
                p = jax.nn.softmax(jnp.where(
                    _mask(sq, ckv.shape[-1], pos), s_ * scale, -jnp.inf),
                    axis=-1)
                o_lat = jnp.einsum("bhqs,bcs->bqhc", p.astype(x.dtype), ckv,
                                   preferred_element_type=jnp.float32
                                   ).astype(x.dtype)
        with jax.named_scope("mla.out"):
            o = _head_einsum("bqhc,hcd->bqhd", o_lat, w_uv)
    else:
        with jax.named_scope("mla.attn"):
            one = lax.dynamic_index_in_dim(lat, lidx, 0, keepdims=False)
            ckv, kpe = one[:, :c], one[:, c:]
            k_nope = jnp.einsum("bcs,hdc->bshd", ckv, w_uk,
                                preferred_element_type=jnp.float32
                                ).astype(x.dtype)
            v = jnp.einsum("bcs,hcd->bshd", ckv, w_uv,
                           preferred_element_type=jnp.float32).astype(x.dtype)
            s_ = (jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,brs->bhqs", q_pe, kpe,
                               preferred_element_type=jnp.float32))
            p = jax.nn.softmax(jnp.where(
                _mask(sq, ckv.shape[-1], pos), s_ * scale, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqs,bshd->bqhd", p.astype(x.dtype), v,
                           preferred_element_type=jnp.float32)
    with jax.named_scope("mla.out"):
        out = linear(o.astype(x.dtype).reshape(b, sq, h * vd), lp["o_proj"])
    return out, lat


def _rope_tables(cfg: DeepseekV2Config, pos, sq: int):
    """cos and sin `[B or 1, sq, rd / 2]` of the positions `pos .. pos +
    sq - 1` (`pos` a scalar or one per slot) and the softmax scale."""
    inv_freq, cs_factor, softmax_factor = _rope(cfg)
    if getattr(pos, "ndim", 0) == 1:   # per-slot positions (serving)
        positions = pos[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    else:
        positions = (pos + jnp.arange(sq, dtype=jnp.int32))[None, :]
    cos, sin = rope_cos_sin(positions, inv_freq)
    if cs_factor != 1.0:
        cos, sin = cos * cs_factor, sin * cs_factor
    return cos, sin, cfg.hd ** -0.5 * softmax_factor


def mla_attention(y, lp, cfg: DeepseekV2Config, cache: KVCache, layer=0):
    """One layer's attention alone, as `forward` runs it: the normed
    `y` `[B, sq, D]` through layer `layer` of `cache` at `cache.pos`.
    Returns the attention output (before the residual) and the cache
    with the new rows written and `pos` advanced. For a check that holds
    a single layer to a reference on the same input."""
    sq = y.shape[1]
    cos, sin, scale = _rope_tables(cfg, cache.pos, sq)
    out, lat = _attention(y, lp, cfg, cache.latent, jnp.int32(layer),
                          cache.pos, cos, sin, scale)
    return out, cache.replace(latent=lat, pos=cache.pos + sq)


def swiglu(x, gate, up, down):
    return linear(jax.nn.silu(linear(x, gate)) * linear(x, up), down)


def _routing(cfg) -> Dict[str, Any]:
    """`route`'s arguments from a config; a family whose router scores
    by sigmoid says so (`scoring_func`)."""
    out = dict(top_k=cfg.num_experts_per_tok, n_group=cfg.n_group,
               topk_group=cfg.topk_group, method=cfg.topk_method,
               scaling_factor=cfg.routed_scaling_factor,
               norm_topk_prob=cfg.norm_topk_prob)
    if getattr(cfg, "scoring_func", "softmax") != "softmax":
        out["scoring"] = cfg.scoring_func
    return out


def moe_block(hidden, lp, experts, layer, cfg):
    """Shared experts (where the layer has any: `shared_gate` among its
    leaves) plus this chip's share of the routed sum; `experts` holds
    the `[L, held, ...]` stacks, read at `layer`; `cfg` any config with
    the routing keys and `share` (this family's, `models/
    dots3_note.py`'s, or `models/mimo_v2.py`'s, which shares nothing).
    Returns `[B, sq, D]` and the `STATS` increments."""
    b, sq, d = hidden.shape
    xf = hidden.reshape(-1, d)
    with jax.named_scope("moe.router"):
        # float32, as published: the choice of experts must not turn on
        # a bfloat16 rounding of near-equal scores
        logits = jnp.dot(xf.astype(jnp.float32),
                         lp["router"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
    with jax.named_scope("moe.routed"):
        routing = _routing(cfg)
        if "router_bias" in lp:     # `noaux_tc`: chooses, does not weigh
            routing["bias"] = lp["router_bias"]
        y, stats = routed_experts(xf, logits, experts, cfg.share,
                                  act=jax.nn.silu, layer=layer, **routing)
    if "shared_gate" in lp:
        with jax.named_scope("moe.shared"):
            y = y + swiglu(xf, lp["shared_gate"], lp["shared_up"],
                           lp["shared_down"])
    return y.reshape(b, sq, d), stats


_EXPERT_KEYS = ("experts_gate", "experts_up", "experts_down")


def forward(
    params: Dict[str, Any],
    cfg: DeepseekV2Config,
    tokens: jax.Array,
    cache: KVCache,
    compute_dtype=jnp.bfloat16,
    last_only: bool = False,
) -> Tuple[jax.Array, KVCache]:
    b, sq = tokens.shape
    # serving marks an empty slot with -1: here it is a slot at 0, whose
    # one block the latent kernels read and write as they always did
    pos = jnp.maximum(cache.pos, 0)
    x = embedding_lookup(params["embed_tokens"], tokens, compute_dtype)
    cos, sin, scale = _rope_tables(cfg, pos, sq)
    eps = cfg.rms_norm_eps

    def attn_part(x, lat, lp, lidx):
        a, lat = _attention(rms_norm(x, lp["input_layernorm"], eps), lp,
                            cfg, lat, lidx, pos, cos, sin, scale)
        x = x + a
        return x, lat, rms_norm(x, lp["post_attention_layernorm"], eps)

    lat = cache.latent
    n_dense = cfg.n_dense
    # the quantized stacks stay whole in both scans: a linear's kernel
    # reads its layer where it lies (`ops/matmul.hold_stacks`)
    if n_dense:
        held, scanned = hold_stacks(params["dense_layers"])

        def dense_step(carry, xs):
            x, lat = carry
            lp, lidx = xs
            lp = layer_params(held, lp, lidx)
            x, lat, hid = attn_part(x, lat, lp, lidx)
            return (x + swiglu(hid, lp["gate_proj"], lp["up_proj"],
                               lp["down_proj"]), lat), None

        (x, lat), _ = lax.scan(
            dense_step, (x, lat),
            (scanned, jnp.arange(n_dense, dtype=jnp.int32)))

    stats = cache.stats
    n_moe = cfg.num_hidden_layers - n_dense
    if n_moe:
        moe = params["moe_layers"]
        # the expert stacks stay whole: the routed kernels address a
        # layer where it lies, a per-layer slice would copy every held
        # expert every step
        experts = {k: moe[k] for k in _EXPERT_KEYS}
        held, scanned = hold_stacks(
            {k: v for k, v in moe.items() if k not in _EXPERT_KEYS})
        tally = (jnp.zeros((len(STATS),), jnp.int32) if stats is None
                 else stats)

        def moe_step(carry, xs):
            x, lat, tally = carry
            lp, i = xs
            lp = layer_params(held, lp, i)
            x, lat, hid = attn_part(x, lat, lp, i + n_dense)
            y, st = moe_block(hid, lp, experts, i, cfg)
            return (x + y, lat, tally + st), None

        (x, lat, tally), _ = lax.scan(
            moe_step, (x, lat, tally),
            (scanned, jnp.arange(n_moe, dtype=jnp.int32)))
        if stats is not None:
            stats = tally

    if last_only:
        x = x[:, -1:, :]
    x = rms_norm(x, params["norm"], eps)
    lm_head = params.get("lm_head")
    if lm_head is None:
        logits = jnp.dot(x, params["embed_tokens"].T.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    else:
        logits = linear(x, lm_head)
    return logits.astype(jnp.float32), cache.replace(
        latent=lat, pos=pos + sq, stats=stats)


def forward_last_token(params, cfg, tokens, cache,
                       compute_dtype=jnp.bfloat16):
    return forward(params, cfg, tokens, cache, compute_dtype=compute_dtype,
                   last_only=True)


# ---------------------------------------------------------------------------
# canonical tree -> served tree, and HF checkpoint -> canonical tree
# ---------------------------------------------------------------------------


def _pad_n(w, multiple: int = 128):
    """A quantized `[.., K, N]` linear with N padded up to a lane
    multiple by zero columns (code 8 = 0, scale 0): the int4 kernels
    tile N by 128, and 576 is 4.5 tiles."""
    if not isinstance(w, QTensor) or w.shape[1] % multiple == 0:
        return w
    pad = -w.shape[1] % multiple
    widths = [(0, 0)] * (w.data.ndim - 1) + [(0, pad)]
    if w.data.dtype == jnp.uint8:
        data = jnp.pad(w.data, widths, constant_values=0x88)
    else:
        data = jnp.pad(w.data, widths)
    return dataclasses.replace(
        w, data=data, scale=jnp.pad(w.scale, widths),
        shape=(w.shape[0], w.shape[1] + pad))


def prepare_attention(layers: Dict[str, Any], cfg,
                      compute_dtype=jnp.bfloat16) -> Dict[str, Any]:
    """One stack of layers' attention leaves as they are served:
    `kv_b_proj` becomes `w_uk` `[H, nope, C]` and `w_uv` `[H, C, v]` in
    bf16 (a layer at a time), `kv_a_proj` gets its N padded to a lane
    multiple. `cfg` gives the sizes (`mla_project`'s)."""
    h, c = cfg.num_attention_heads, cfg.kv_lora_rank
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim

    def split(w):
        w = (w.dequantize(jnp.float32) if isinstance(w, QTensor)
             else w.astype(jnp.float32)).reshape(c, h, nope + vd)
        return (jnp.transpose(w[..., :nope], (1, 2, 0)).astype(compute_dtype),
                jnp.transpose(w[..., nope:], (1, 0, 2)).astype(compute_dtype))

    layers = dict(layers)
    layers["w_uk"], layers["w_uv"] = lax.map(split, layers.pop("kv_b_proj"))
    layers["kv_a_proj"] = _pad_n(layers["kv_a_proj"])
    return layers


def prepare_params(params: Dict[str, Any], cfg: DeepseekV2Config,
                   compute_dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The canonical tree as `forward` serves it (`prepare_attention`
    on both stacks). A tree that is already prepared passes through."""
    out = dict(params)
    for group in ("dense_layers", "moe_layers"):
        layers = params.get(group)
        if layers is None or "kv_b_proj" not in layers:
            continue
        out[group] = prepare_attention(layers, cfg, compute_dtype)
    return out


_ATTN_LINEARS = {"self_attn.q_proj": "q_proj",
                 "self_attn.q_a_proj": "q_a_proj",
                 "self_attn.q_b_proj": "q_b_proj",
                 "self_attn.kv_a_proj_with_mqa": "kv_a_proj",
                 "self_attn.kv_b_proj": "kv_b_proj",
                 "self_attn.o_proj": "o_proj"}
_NORMS = {"input_layernorm": "input_layernorm",
          "post_attention_layernorm": "post_attention_layernorm",
          "self_attn.q_a_layernorm": "q_a_layernorm",
          "self_attn.kv_a_layernorm": "kv_a_layernorm"}
_DENSE_MLP = {"mlp.gate_proj": "gate_proj", "mlp.up_proj": "up_proj",
              "mlp.down_proj": "down_proj"}
_SHARED_MLP = {"mlp.shared_experts.gate_proj": "shared_gate",
               "mlp.shared_experts.up_proj": "shared_up",
               "mlp.shared_experts.down_proj": "shared_down"}
_EXPERT_MLP = {"gate_proj": "experts_gate", "up_proj": "experts_up",
               "down_proj": "experts_down"}


def convert_hf_params(
    tensors,
    cfg: DeepseekV2Config,
    qtype: Optional[str] = "sym_int4",
    compute_dtype=jnp.bfloat16,
    modules_to_not_convert: Tuple[str, ...] = (),
    imatrix=None,
) -> Dict[str, Any]:
    """HF `DeepseekV2ForCausalLM` tensors -> the served tree. The router
    (`mlp.gate`) and the norms stay unquantized; of the routed experts
    only those this chip holds (`cfg.share`) are converted."""
    from bigdl_tpu.ops.quant import FLOAT_QTYPES, quantize_linear

    del imatrix
    do_quant = qtype is not None and qtype not in FLOAT_QTYPES
    share, n_dense = cfg.share, cfg.n_dense
    n_moe = cfg.num_hidden_layers - n_dense

    def lin(name, w):
        w = jnp.asarray(np.asarray(w))
        if do_quant and not any(m in name for m in modules_to_not_convert):
            return quantize_linear(w, qtype)
        return w.T.astype(compute_dtype)

    def vec(w):
        return jnp.asarray(np.asarray(w)).astype(compute_dtype)

    params: Dict[str, Any] = {}
    groups = {"dense_layers": ({}, n_dense), "moe_layers": ({}, n_moe)}

    def put(group, key, idx, val, eidx=None):
        acc, n = groups[group]
        slot = acc.setdefault(key, [None] * n)
        if eidx is None:
            slot[idx] = val
        else:
            if slot[idx] is None:
                slot[idx] = [None] * share.held
            slot[idx][eidx] = val

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
            if layer >= cfg.num_hidden_layers:
                continue
            sub = ".".join(parts[3:-1])
            group, idx = (("dense_layers", layer) if layer < n_dense
                          else ("moe_layers", layer - n_dense))
            if sub in _ATTN_LINEARS:
                put(group, _ATTN_LINEARS[sub], idx, lin(name, w))
            elif sub in _NORMS:
                put(group, _NORMS[sub], idx, vec(w))
            elif sub in _DENSE_MLP and group == "dense_layers":
                put(group, _DENSE_MLP[sub], idx, lin(name, w))
            elif sub in _SHARED_MLP:
                put(group, _SHARED_MLP[sub], idx, lin(name, w))
            elif sub == "mlp.gate":
                put(group, "router", idx, vec(w).T)
            elif sub.startswith("mlp.experts."):
                e = int(parts[5]) - share.first_held
                if 0 <= e < share.held:
                    put(group, _EXPERT_MLP[parts[6]], idx, lin(name, w), e)

    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)  # noqa: E731
    for group, (acc, n) in groups.items():
        if not n:
            continue
        missing = [k for k, v in acc.items() if any(
            x is None or (isinstance(x, list) and any(e is None for e in x))
            for x in v)]
        if missing or not acc:
            raise ValueError(
                f"checkpoint missing {group} tensors for: {missing or 'all'}")
        params[group] = {
            k: stack([stack(x) if isinstance(x, list) else x for x in v])
            for k, v in acc.items()}
    if cfg.tie_word_embeddings:
        params.pop("lm_head", None)
    elif "lm_head" not in params:
        raise ValueError("checkpoint has no lm_head.weight")
    return prepare_params(params, cfg, compute_dtype)
