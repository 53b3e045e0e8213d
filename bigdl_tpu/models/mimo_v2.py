"""MiMo-V2 (HF `model_type` "mimo_v2"; MiMo-V2-Flash, MiMo-V2.5): grouped-
query attention of two kinds in one stack, full and sliding-window,
with keys wider than values, a learned sink in the window layers'
softmax, and a sigmoid bias-corrected router over experts with NO shared
expert; functional and static-shape. The language model on text: the
vision and audio towers and the multi-token-prediction layers of the
published model are not built (no key of its config sizes them).

The layer, as this module reads the published config (pre-norm
residual, RMSNorm, untied head; `y` is the layer's normed input):

- Attention, `hybrid_layer_pattern[i]` 0 = full, 1 = window. `H` query
  heads, `G` KV heads of the layer's KIND (`num_key_value_heads` /
  `swa_num_key_value_heads`), `d_qk = head_dim`, `d_v = v_head_dim`: `q
  = y W_q` `[S, H, d_qk]`, `k = y W_k` `[S, G, d_qk]`, `v =
  attention_value_scale * (y W_v)` `[S, G, d_v]`, no bias. Rotary on
  the FIRST `int(d_qk * partial_rotary_factor)` dims of q and k (64 of
  192), half-rotation form (dim i with dim i + 32), base `rope_theta` in
  a full layer and `swa_rope_theta` in a window layer; the other dims
  pass. Scores `q . k / sqrt(d_qk)`, head n reading KV head `n // (H /
  G)`; key j is live for query i while `j <= i`, and in a window layer
  while `i - j < sliding_window` (the query's own position counted). A
  kind with `add_*_attention_sink_bias` adds one learned scalar a query
  head to each row's logits as a column with no value (`ops/swa.py`).
  `x += concat(o) W_o`.
- Feed-forward, `moe_layer_freq[i]` 0 = dense SwiGLU, 1 = this chip's
  share of the routed sum (`deepseek_v2.moe_block`, `ops/moe_routed.py`):
  scores `sigmoid(y W_r)` in float32, choice = top-k of `scores +
  e_bias` (`noaux_tc`, one group), weights the chosen scores
  renormalised, times `routed_scaling_factor` (null: 1). Nothing is
  shared.

The cache (`cache_spec`) holds four planes of two lengths, a position's
heads side by side in the lanes: the full layers' `full_k` `[Lf, B, S,
G d_qk]` and `full_v` `[Lf, B, S, G d_v]`, and the window layers'
`ring_k` / `ring_v` `[Lw, B, ring, G' d]`, RINGS written at `pos % ring`
(`ops/kvcache.py`): a window layer's cache does not grow. bf16 only.

Decode (one row a slot) runs the kernels of `ops/pallas/
swa_attention.py` (`decode_attention_lanes` over a full plane,
`swa_decode_attention` over a ring); a chunk of rows runs per sequence
through `ops/swa.full_chunk` / `window_chunk`.

Speculation. `generate()`'s cache keeps the window layers' rows in
position order (`new_cache`), so it can hold pad rows and be rewound
(`CACHE_REWINDABLE` stays True). The serving engine's
`speculative_tokens` is refused for this family: it has no draft module
here (`speculative_depth`: the published MTP layers are not built), and
a slab ring exactly as long as the window could not disown a written
row (row `t + 1` overwrites position `t - 127`, which the query at `t`
still attends).

The layers are ONE traced body a KIND (`_layer`: full or window, dense
or routed), called with the layer's leaves and its indices as traced
scalars: a program traces and lowers three layers, not twelve.

Parameter tree (linears contraction-major `[K, N]`, QTensor or dense):
{
  "embed_tokens": [V, D], "norm": [D], "lm_head": [D, V],
  "layers": one dict a layer, in order (no stack: the kinds differ):
      input_layernorm, post_attention_layernorm [D], qkv_proj [D, H d_qk
      + G d_qk + G d_v], o_proj [H d_v, D]; a kind with a sink also
      sink [H] float32; a dense layer gate_proj / up_proj / down_proj;
      an expert layer router [D, E_total], router_bias [E_total],
  "experts": experts_gate / experts_up [Le, held, D, F], experts_down
      [Le, held, F, D], stacked over the expert layers (the routed
      kernels address a layer where it lies),
}
Before `prepare_params` a layer holds q_proj / k_proj / v_proj apart:
the canonical tree, which the benchmark's reference reads.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.models.deepseek_v2 import moe_block, swiglu
from bigdl_tpu.models.dots3_note import _positions
from bigdl_tpu.models.llama import embedding_lookup
from bigdl_tpu.ops import swa
from bigdl_tpu.ops.kvcache import (CacheSpec, KVCache, PlaneSpec,
                                   init_cache_spec, update_rows)
from bigdl_tpu.ops.matmul import linear
from bigdl_tpu.ops.moe_routed import STATS, Share
from bigdl_tpu.ops.norms import rms_norm
from bigdl_tpu.ops.rope import apply_rope, rope_tables

FULL, WINDOW = "full", "window"
_LANES = 128


@dataclasses.dataclass(frozen=True)
class GqaKind:
    """The sizes of one kind of attention layer."""
    heads: int
    kv_heads: int
    head_dim: int
    v_head_dim: int
    rope_theta: float
    sink: bool
    window: int = 0          # positions attended, the query's own counted
    value_scale: float = 1.0
    rotary: bool = True      # False: the kind's q and k take no rotary
    # what `models/afmoe.py`'s layers add: an RMSNorm over each head of
    # q and k before the rotary (`q_norm` / `k_norm` [head_dim], eps
    # `norm_eps`), and sigmoid(y W_g), a value a channel, on the
    # concatenated heads before `o_proj` (W_g the last columns of
    # `qkv_proj`)
    qk_norm: bool = False
    gate: bool = False
    norm_eps: float = 1e-5
    # what `models/sdar_moe.py`'s layers add: positions come in blocks
    # of `block`, causal between blocks, and every row of a block sees
    # its whole block (1: the causal mask). A call of exactly `block`
    # rows a slot is one block under one limit (`ops/swa.full_block`)
    block: int = 1

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def k_width(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def v_width(self) -> int:
        return self.kv_heads * self.v_head_dim


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    hybrid_layer_pattern: Tuple[int, ...] = ()
    moe_layer_freq: Tuple[int, ...] = ()
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    rope_theta: float = 1e7
    add_full_attention_sink_bias: bool = False
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 1e4
    add_swa_attention_sink_bias: bool = True
    sliding_window_size: int = 128
    # columns of the window layers' ring; 0: the window rounded up to a
    # lane multiple
    window_ring: int = 0
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    n_routed_experts: int = 256       # experts held HERE (see ep_size)
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    layernorm_epsilon: float = 1e-5
    max_position_embeddings: int = 1048576
    tie_word_embeddings: bool = False
    ep_size: int = 1
    ep_rank: int = 0

    @classmethod
    def from_hf(cls, hf: Dict[str, Any]) -> "MimoV2Config":
        for key, only in (("hidden_act", "silu"), ("attention_bias", False),
                          ("n_shared_experts", None), ("n_group", 1),
                          ("topk_group", 1), ("topk_method", "noaux_tc"),
                          ("scoring_func", "sigmoid")):
            if hf.get(key, only) != only:
                raise NotImplementedError(f"{key} {hf[key]!r}")
        scaling = hf.get("rope_scaling") or {}
        if scaling.get("rope_type", scaling.get("type", "default")) \
                != "default":
            raise NotImplementedError(f"rope_scaling {scaling}")
        window = hf.get("sliding_window_size", hf.get("sliding_window", 128))
        if hf.get("sliding_window", window) != window:
            raise ValueError("sliding_window and sliding_window_size differ")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names and v is not None}
        kw["sliding_window_size"] = window
        for key in ("hybrid_layer_pattern", "moe_layer_freq"):
            kw[key] = tuple(int(v) for v in hf[key])
        cfg = cls(**kw)
        n = cfg.num_hidden_layers
        if len(cfg.hybrid_layer_pattern) != n or len(cfg.moe_layer_freq) != n:
            raise ValueError("hybrid_layer_pattern and moe_layer_freq must "
                             "name every layer")
        if cfg.window_ring and cfg.window_ring < cfg.sliding_window_size:
            raise ValueError("window_ring is shorter than the window")
        if cfg.rotary_dim % 2 or cfg.rotary_dim > min(cfg.head_dim,
                                                      cfg.swa_head_dim):
            raise ValueError(f"rotary dims {cfg.rotary_dim}")
        return cfg

    @property
    def share(self) -> Share:
        return Share(self.n_routed_experts * self.ep_size,
                     self.n_routed_experts * self.ep_rank,
                     self.n_routed_experts)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def full(self) -> GqaKind:
        return GqaKind(self.num_attention_heads, self.num_key_value_heads,
                       self.head_dim, self.v_head_dim, self.rope_theta,
                       self.add_full_attention_sink_bias,
                       value_scale=self.attention_value_scale)

    @property
    def swa(self) -> GqaKind:
        return GqaKind(self.swa_num_attention_heads,
                       self.swa_num_key_value_heads, self.swa_head_dim,
                       self.swa_v_head_dim, self.swa_rope_theta,
                       self.add_swa_attention_sink_bias,
                       window=self.sliding_window_size,
                       value_scale=self.attention_value_scale)

    def kind_name(self, layer: int) -> str:
        return WINDOW if self.hybrid_layer_pattern[layer] else FULL

    def kind(self, layer: int) -> GqaKind:
        return self.swa if self.hybrid_layer_pattern[layer] else self.full

    def routed(self, layer: int) -> bool:
        return bool(self.moe_layer_freq[layer])

    @property
    def n_full(self) -> int:
        return sum(not p for p in self.hybrid_layer_pattern)

    @property
    def n_window(self) -> int:
        return self.num_hidden_layers - self.n_full

    @property
    def n_routed_layers(self) -> int:
        return sum(bool(f) for f in self.moe_layer_freq)

    @property
    def ring(self) -> int:
        return self.window_ring or -(-self.sliding_window_size
                                     // _LANES) * _LANES

    # what cost models and the generic engine read off a config
    @property
    def hd(self) -> int:
        return self.head_dim

    @property
    def rms_norm_eps(self) -> float:
        return self.layernorm_epsilon

    @property
    def sliding_window(self):
        return None

    @property
    def kv_values_per_position(self) -> float:
        """Cached values a decoded token reads of one position, as a
        mean over ALL the layers (roofline.py multiplies by their
        number): the full layers' K and V; the window layers keep a ring
        whose reads do not grow with the position."""
        return (self.n_full * (self.full.k_width + self.full.v_width)
                / self.num_hidden_layers)

    def matmul_flops_per_token(self) -> int:
        """Forward matmul operations a token needs on THIS chip."""
        d = self.hidden_size

        def attn(k: GqaKind) -> int:
            return d * (k.q_width + k.k_width + k.v_width) \
                + k.heads * k.v_head_dim * d

        moe = 3 * d * self.moe_intermediate_size \
            * self.num_experts_per_tok / self.ep_size
        n_moe = self.n_routed_layers
        return int(2 * (self.n_full * attn(self.full)
                        + self.n_window * attn(self.swa)
                        + (self.num_hidden_layers - n_moe) * 3 * d
                        * self.intermediate_size
                        + n_moe * (moe + d * self.share.experts_total)
                        + d * self.vocab_size))

    def attn_flops_per_cached_token(self) -> int:
        """Decode attention per cached position: the full layers."""
        k = self.full
        return self.n_full * 2 * k.heads * (k.head_dim + k.v_head_dim)


def cache_spec(cfg) -> CacheSpec:
    planes = []
    if cfg.n_full:
        planes += [PlaneSpec("full_k", cfg.n_full, (cfg.full.k_width,)),
                   PlaneSpec("full_v", cfg.n_full, (cfg.full.v_width,))]
    if cfg.n_window:
        planes += [PlaneSpec("ring_k", cfg.n_window, (cfg.swa.k_width,),
                             ring=cfg.ring),
                   PlaneSpec("ring_v", cfg.n_window, (cfg.swa.v_width,),
                             ring=cfg.ring)]
    return CacheSpec("kv", cfg.num_hidden_layers, cfg.full.kv_heads,
                     cfg.full.head_dim,
                     stats_len=len(STATS) if cfg.n_routed_layers else 0,
                     planes=tuple(planes))


def new_cache(cfg, batch: int, max_seq: int,
              quantized=False) -> KVCache:
    """The four planes with the window layers' rows in position order
    (`CacheSpec.unrolled`: `generate()` right-pads its prompt, and the
    padding would overwrite live columns of a ring; the serving engine's
    slab is the one that holds the rings, `cache_spec`); bf16 only
    (`ops/kvcache.reject_non_bf16_strided`). The forward reads a ring
    plane as a ring of ITS OWN length, which a plane in position order
    is."""
    return init_cache_spec(cache_spec(cfg).unrolled(), batch, max_seq,
                           kv_cache_dtype=quantized)


def attention(y, lp, kind: GqaKind, k_stack, v_stack, li, pos, cos, sin):
    """One layer's attention on the normed `y` `[B, T, D]` through layer
    `li` of its kind's stacks at `pos`: the output (before the residual)
    and the two stacks with this layer's rows written."""
    b, t, _ = y.shape
    h, g, dk, dv = kind.heads, kind.kv_heads, kind.head_dim, kind.v_head_dim
    scale = dk ** -0.5
    sink = lp.get("sink") if kind.sink else None
    v_end = kind.q_width + kind.k_width + kind.v_width
    with jax.named_scope("gqa.qkv"):
        qkv = linear(y, lp["qkv_proj"])
        q = qkv[..., :kind.q_width].reshape(b, t, h, dk)
        k = qkv[..., kind.q_width:kind.q_width + kind.k_width].reshape(
            b, t, g, dk)
        if kind.qk_norm:
            q = rms_norm(q, lp["q_norm"], kind.norm_eps)
            k = rms_norm(k, lp["k_norm"], kind.norm_eps)
        if kind.rotary:
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        k = k.reshape(b, t, kind.k_width)
        v = qkv[..., kind.q_width + kind.k_width:v_end]
        if kind.value_scale != 1.0:
            v = (v.astype(jnp.float32) * kind.value_scale).astype(y.dtype)
    if not kind.window:
        with jax.named_scope("gqa.full"):
            k_stack = update_rows(k_stack, li, k, pos)
            v_stack = update_rows(v_stack, li, v, pos)
            if t == 1:
                o = swa.full_decode(q[:, 0], k_stack, v_stack, li, pos,
                                    scale, g)[:, None]
            elif kind.block > 1 and t == kind.block:
                o = swa.full_block(q, k_stack, v_stack, li, pos, scale, g)
            else:
                kl, vl = (lax.dynamic_index_in_dim(x, li, 0, keepdims=False)
                          for x in (k_stack, v_stack))
                o = jax.vmap(lambda q_, k_, v_, p_: swa.full_chunk(
                    q_, k_, v_, p_, scale, g, kind.block))(
                    q, kl, vl, _positions(pos, b))
    else:
        with jax.named_scope("gqa.window"):
            if t == 1:
                k_stack = update_rows(k_stack, li, k, pos, ring=True)
                v_stack = update_rows(v_stack, li, v, pos, ring=True)
                o = swa.window_decode(q[:, 0], k_stack, v_stack, li, pos,
                                      scale, g, kind.window, sink)[:, None]
            else:
                posv = _positions(pos, b)
                pk, pv = (swa.rows_before(x, li, posv, kind.window - 1)
                          for x in (k_stack, v_stack))
                o = jax.vmap(lambda q_, nk, nv, k_, v_, p_: swa.window_chunk(
                    q_, nk, nv, k_, v_, p_, scale, g, kind.window, sink))(
                    q, k, v, pk, pv, posv)
                k_stack = update_rows(k_stack, li, k, pos, ring=True)
                v_stack = update_rows(v_stack, li, v, pos, ring=True)
    o = o.reshape(b, t, h * dv)
    if kind.gate:
        with jax.named_scope("gqa.gate"):
            o = o.astype(jnp.float32) * jax.nn.sigmoid(
                qkv[..., v_end:].astype(jnp.float32))
    with jax.named_scope("gqa.out"):
        out = linear(o.astype(y.dtype), lp["o_proj"])
    return out, k_stack, v_stack


@functools.partial(jax.jit, static_argnames=("cfg", "window", "routed"))
def _layer(x, lp, experts, k_stack, v_stack, li, ei, pos, cos, sin, tally, *,
           cfg: MimoV2Config, window: bool, routed: bool):
    """One layer of one kind on the residual stream `x`. Jitted with the
    layer's indices (`li` among its kind's planes, `ei` among the expert
    stacks) traced, so every layer of a kind is a call of one body."""
    eps = cfg.layernorm_epsilon
    kind = cfg.swa if window else cfg.full
    a, k_stack, v_stack = attention(
        rms_norm(x, lp["input_layernorm"], eps), lp, kind, k_stack, v_stack,
        li, pos, cos, sin)
    x = x + a
    hid = rms_norm(x, lp["post_attention_layernorm"], eps)
    if routed:
        with jax.named_scope("moe.block"):
            y, st = moe_block(hid, lp, experts, ei, cfg)
        x, tally = x + y, tally + st
    else:
        x = x + swiglu(hid, lp["gate_proj"], lp["up_proj"], lp["down_proj"])
    return x, k_stack, v_stack, tally


def row_positions(pos, sq: int):
    """`[B or 1, sq]`: the positions `pos .. pos + sq - 1` of the rows of
    a call at `pos` (a scalar, or one a slot)."""
    if getattr(pos, "ndim", 0) == 1:
        return pos[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    return (pos + jnp.arange(sq, dtype=jnp.int32))[None, :]


def _tables(cfg: MimoV2Config, pos, sq: int):
    """cos and sin `[B or 1, sq, rd / 2]` of the positions `pos .. pos +
    sq - 1` for the two kinds' rope."""
    return rope_tables(row_positions(pos, sq), {
        FULL: (cfg.rotary_dim, cfg.rope_theta),
        WINDOW: (cfg.rotary_dim, cfg.swa_rope_theta)})


def attention_block(y, lp, cfg, cache: KVCache, kind: str, tables=None):
    """One layer's attention alone, as `forward` runs it: the normed `y`
    `[B, sq, D]` through layer 0 of the planes of `kind` in `cache` at
    `cache.pos`. Returns the attention output (before the residual) and
    the cache with the new rows written and `pos` advanced. For a check
    that holds a single layer to a reference on the same input.
    `tables`: the family's `(cfg, pos, sq) -> {kind: (cos, sin)}`."""
    sq = y.shape[1]
    cos, sin = (tables or _tables)(cfg, cache.pos, sq)[kind]
    li = jnp.int32(0)
    if kind == FULL:
        out, k, v = attention(y, lp, cfg.full, cache.full_k, cache.full_v,
                              li, cache.pos, cos, sin)
        cache = cache.replace(full_k=k, full_v=v)
    else:
        out, k, v = attention(y, lp, cfg.swa, cache.ring_k, cache.ring_v,
                              li, cache.pos, cos, sin)
        cache = cache.replace(ring_k=k, ring_v=v)
    return out, cache.replace(pos=cache.pos + sq)


def forward(
    params: Dict[str, Any],
    cfg: MimoV2Config,
    tokens: jax.Array,
    cache: KVCache,
    compute_dtype=jnp.bfloat16,
    last_only: bool = False,
) -> Tuple[jax.Array, KVCache]:
    b, sq = tokens.shape
    # serving marks an empty slot with -1: here it is a slot at 0
    pos = jnp.maximum(cache.pos, 0)
    x = embedding_lookup(params["embed_tokens"], tokens, compute_dtype)
    tables = _tables(cfg, pos, sq)
    planes = {FULL: (cache.full_k, cache.full_v),
              WINDOW: (cache.ring_k, cache.ring_v)}
    stats = cache.stats
    tally = jnp.zeros((len(STATS),), jnp.int32) if stats is None else stats
    experts = params.get("experts")
    at = {FULL: 0, WINDOW: 0, "expert": 0}
    for i, lp in enumerate(params["layers"]):
        kind, routed = cfg.kind_name(i), cfg.routed(i)
        x, k, v, tally = _layer(
            x, lp, experts if routed else None, *planes[kind],
            jnp.int32(at[kind]), jnp.int32(at["expert"]), pos,
            *tables[kind], tally, cfg=cfg, window=kind == WINDOW,
            routed=routed)
        planes[kind] = (k, v)
        at[kind] += 1
        at["expert"] += routed
    if stats is not None:
        stats = tally
    if last_only:
        x = x[:, -1:, :]
    x = rms_norm(x, params["norm"], cfg.layernorm_epsilon)
    lm_head = params.get("lm_head")
    if lm_head is None:
        logits = jnp.dot(x, params["embed_tokens"].T.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    else:
        logits = linear(x, lm_head)
    return logits.astype(jnp.float32), cache.replace(
        full_k=planes[FULL][0], full_v=planes[FULL][1],
        ring_k=planes[WINDOW][0], ring_v=planes[WINDOW][1], pos=pos + sq,
        stats=stats)


def forward_last_token(params, cfg, tokens, cache,
                       compute_dtype=jnp.bfloat16):
    return forward(params, cfg, tokens, cache, compute_dtype=compute_dtype,
                   last_only=True)


# ---------------------------------------------------------------------------
# canonical tree -> served tree, and HF checkpoint -> canonical tree
# ---------------------------------------------------------------------------

def prepare_layer(lp: Dict[str, Any]) -> Dict[str, Any]:
    """One canonical layer as `forward` serves it: q / k / v merged
    (block quantization is per column: bit-exact). A prepared layer
    passes through."""
    from bigdl_tpu.ops.quant import QTensor, concat_qtensors_n

    if "qkv_proj" in lp:
        return lp
    lp = dict(lp)
    ws = [lp.pop(n) for n in ("q_proj", "k_proj", "v_proj")]
    lp["qkv_proj"] = (concat_qtensors_n(ws) if isinstance(ws[0], QTensor)
                      else jnp.concatenate(ws, axis=-1))
    return lp


def prepare_params(params: Dict[str, Any], cfg: MimoV2Config = None
                   ) -> Dict[str, Any]:
    out = dict(params)
    out["layers"] = tuple(prepare_layer(lp) for lp in params["layers"])
    return out


_LINEARS = {"self_attn.q_proj": "q_proj", "self_attn.k_proj": "k_proj",
            "self_attn.v_proj": "v_proj", "self_attn.o_proj": "o_proj",
            "mlp.gate_proj": "gate_proj", "mlp.up_proj": "up_proj",
            "mlp.down_proj": "down_proj"}
_VECTORS = {"input_layernorm.weight": "input_layernorm",
            "post_attention_layernorm.weight": "post_attention_layernorm",
            "mlp.gate.e_score_correction_bias": "router_bias"}
_EXPERT_MLP = {"gate_proj": "experts_gate", "up_proj": "experts_up",
               "down_proj": "experts_down"}
SINK_TENSOR = "self_attn.attention_sink_bias"


def convert_hf_params(
    tensors,
    cfg: MimoV2Config,
    qtype: Optional[str] = "sym_int4",
    compute_dtype=jnp.bfloat16,
    modules_to_not_convert: Tuple[str, ...] = (),
    imatrix=None,
) -> Dict[str, Any]:
    """HF tensors -> the served tree. Tensor names ASSUMED, no
    checkpoint of this model has been read here: `self_attn.{q,k,v,o}_
    proj` (or one fused `self_attn.qkv_proj`, read as the rows of q,
    then k, then v: `attention_projection_layout` "fused_qkv" does not
    say the order), `self_attn.attention_sink_bias` `[H]`, `mlp.gate`
    with `e_score_correction_bias` and `mlp.experts.<e>.*` as the
    DeepSeek-V3 family names them. The router, its bias, the sink and
    the norms stay unquantized; of the routed experts only those this
    chip holds (`cfg.share`) are converted."""
    from bigdl_tpu.ops.quant import FLOAT_QTYPES, quantize_linear

    del imatrix
    do_quant = qtype is not None and qtype not in FLOAT_QTYPES
    share, n_moe = cfg.share, cfg.n_routed_layers
    expert_at = np.cumsum(cfg.moe_layer_freq) - 1      # layer -> stack row

    def lin(name, w):
        w = jnp.asarray(np.asarray(w))
        if do_quant and not any(m in name for m in modules_to_not_convert):
            return quantize_linear(w, qtype)
        return w.T.astype(compute_dtype)

    def vec(w):
        return jnp.asarray(np.asarray(w)).astype(compute_dtype)

    params: Dict[str, Any] = {}
    layers = [dict() for _ in range(cfg.num_hidden_layers)]
    experts = {k: [[None] * share.held for _ in range(n_moe)]
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
            if layer >= cfg.num_hidden_layers:
                continue
            sub = ".".join(parts[3:])
            stem = sub[:-len(".weight")] if sub.endswith(".weight") else sub
            kind = cfg.kind(layer)
            if stem in _LINEARS:
                layers[layer][_LINEARS[stem]] = lin(name, w)
            elif stem == "self_attn.qkv_proj":
                w = np.asarray(w)
                cuts = np.cumsum([kind.q_width, kind.k_width])
                for key, rows in zip(("q_proj", "k_proj", "v_proj"),
                                     np.split(w, cuts, axis=0)):
                    layers[layer][key] = lin(name, rows)
            elif sub in _VECTORS:
                layers[layer][_VECTORS[sub]] = vec(w)
            elif sub == SINK_TENSOR:
                layers[layer]["sink"] = jnp.asarray(
                    np.asarray(w, np.float32).reshape(-1))
            elif sub == "mlp.gate.weight":
                layers[layer]["router"] = vec(w).T
            elif sub.startswith("mlp.experts.") and cfg.routed(layer):
                e = int(parts[5]) - share.first_held
                if 0 <= e < share.held:
                    experts[_EXPERT_MLP[parts[6]]][expert_at[layer]][e] = \
                        lin(name, w)
    for i, lp in enumerate(layers):
        need = {"q_proj", "k_proj", "v_proj", "o_proj", "input_layernorm",
                "post_attention_layernorm"}
        if cfg.kind(i).sink:
            need.add("sink")
        need |= ({"router", "router_bias"} if cfg.routed(i) else
                 {"gate_proj", "up_proj", "down_proj"})
        missing = sorted(need - set(lp))
        if missing:
            raise ValueError(f"checkpoint missing layer {i} tensors: "
                             f"{missing}")
    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)  # noqa: E731
    if n_moe:
        for k, per_layer in experts.items():
            if any(e is None for row in per_layer for e in row):
                raise ValueError(f"checkpoint missing held experts of {k}")
        params["experts"] = {k: stack([stack(row) for row in per_layer])
                             for k, per_layer in experts.items()}
    params["layers"] = tuple(layers)
    if cfg.tie_word_embeddings:
        params.pop("lm_head", None)
    elif "lm_head" not in params:
        raise ValueError("checkpoint has no lm_head.weight")
    return prepare_params(params, cfg)
