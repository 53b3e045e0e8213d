"""AFMoE (HF `model_type` "afmoe"; Trinity-Mini, Trinity-Nano): grouped-
query attention of two kinds in one stack, sliding-window and full, with
a per-head RMSNorm on queries and keys, rotary in the window layers ONLY,
a sigmoid gate on the attention output, two norms a sublayer, and a
sigmoid bias-corrected router over small experts beside a shared one;
functional and static-shape.

The layer, as this module reads the published config and the family's
modelling code (every norm an RMSNorm with a learned weight, eps
`rms_norm_eps`; untied head; `x = embed[token] * sqrt(hidden_size)`
under `mup_enabled`):

- Attention, `layer_types[i]` "sliding_attention" or "full_attention".
  `h = input_layernorm(x)`; `H` query heads, `G` KV heads, `d =
  head_dim` in both kinds: `q = h W_q` `[S, H, d]`, `k = h W_k` `[S, G,
  d]`, `v = h W_v` `[S, G, d]`, `g = h W_g` `[S, H d]`, no bias. q and k
  go through an RMSNorm over the `d` values of each head (`q_norm` /
  `k_norm` `[d]`, one each a layer). A WINDOW layer then applies rotary
  to all `d` dims of q and k (half-rotation form, base `rope_theta`); a
  FULL layer applies none. Scores `q . k / sqrt(d)`, head n reading KV
  head `n // (H / G)`; key j is live for query i while `j <= i`, and in
  a window layer while `i - j < sliding_window` (the query's own
  position counted). Plain softmax. `a = (concat(o) * sigmoid(g)) W_o`;
  `x += post_attention_layernorm(a)`: the second norm of the pair is on
  the BRANCH.
- Feed-forward. `h = pre_mlp_layernorm(x)`. The first `num_dense_layers`
  layers: dense SwiGLU. The others: this chip's share of the routed sum
  (`deepseek_v2.moe_block`, `ops/moe_routed.py`): scores `sigmoid(h
  W_r)` in float32, choice = top-k of `scores + expert_bias` (one
  group), weights the chosen scores renormalised (`route_norm`), times
  `route_scale`; PLUS one shared SwiGLU expert `moe_intermediate_size *
  num_shared_experts` wide on every row, unweighted. `x +=
  post_mlp_layernorm(y)`.

The cache, the decode kernels and the chunk path are `models/
mimo_v2.py`'s (`cache_spec`, `attention`: full planes beside K/V rings
with a position's heads side by side in the lanes), by import: this
family's kinds turn on the QK norm and the gate and turn the full kind's
rotary off. Its ring is 2048 positions, longer than most prompts and
swept in blocks (`ops/pallas/swa_attention.py`). Speculation, the host
prefix cache, `seeded` and paging are refused as for that family.

DEPTH. Every layer has the same attention leaves, so they are ONE stack
over all the layers; the layers after the leading ones are equal
periods of the pattern (`W W W F`, all routed), and `forward` runs them
as a `lax.scan` over the period (`scan_plan`): the quantized linears are
read where they lie in their stacks (`ops/matmul.StackedQ`), the routed
kernels are addressed by a layer index, the planes are carried. The
leading layers (the dense ones, up to the first period boundary) stay
unrolled. A program's size does not grow with the number of periods.

Parameter tree (linears contraction-major `[K, N]`, QTensor or dense):
{
  "embed_tokens": [V, D], "norm": [D], "lm_head": [D, V],
  "attn": stacked over ALL L layers: input_layernorm,
      post_attention_layernorm, pre_mlp_layernorm, post_mlp_layernorm
      [L, D], q_norm, k_norm [L, d], qkv_proj [L, D, (H + 2 G + H) d] (q,
      k, v and the gate's columns), o_proj [L, H d, D],
  "dense": gate_proj / up_proj [Ld, D, F], down_proj [Ld, F, D],
  "moe": stacked over the Le expert layers: router [Le, D, E_total],
      router_bias [Le, E_total], shared_gate / shared_up [Le, D, Fs],
      shared_down [Le, Fs, D],
  "experts": experts_gate / experts_up [Le, held, D, Fe], experts_down
      [Le, held, Fe, D],
}
Before `prepare_params` "attn" holds q_proj / k_proj / v_proj / g_proj
apart: the canonical tree, which the benchmark's reference reads.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.models import mimo_v2
from bigdl_tpu.models.deepseek_v2 import moe_block, swiglu
from bigdl_tpu.models.llama import embedding_lookup
from bigdl_tpu.models.mimo_v2 import (FULL, WINDOW, GqaKind,  # noqa: F401
                                      attention, cache_spec, new_cache)
from bigdl_tpu.ops.kvcache import KVCache
from bigdl_tpu.ops.matmul import hold_stacks, layer_params, linear
from bigdl_tpu.ops.moe_routed import STATS, Share
from bigdl_tpu.ops.norms import rms_norm
from bigdl_tpu.ops.rope import rope_tables

_LANES = 128
_KINDS = {"sliding_attention": WINDOW, "full_attention": FULL}
_NORMS = ("input_layernorm", "post_attention_layernorm",
          "pre_mlp_layernorm", "post_mlp_layernorm")
_ATTN_MERGED = ("q_proj", "k_proj", "v_proj", "g_proj")
_EXPERT_KEYS = ("experts_gate", "experts_up", "experts_down")


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    layer_types: Tuple[str, ...] = ()
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e4
    sliding_window_size: int = 2048     # the published `sliding_window`
    # columns of the window layers' ring; 0: the window rounded up to a
    # lane multiple
    window_ring: int = 0
    num_experts: int = 128              # experts held HERE (see ep_size)
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    ep_size: int = 1
    ep_rank: int = 0

    @classmethod
    def from_hf(cls, hf: Dict[str, Any]) -> "AfmoeConfig":
        for key, only in (("hidden_act", "silu"), ("attention_bias", False),
                          ("n_group", 1), ("topk_group", 1),
                          ("num_expert_groups", 1),
                          ("num_limited_groups", 1),
                          ("score_func", "sigmoid"), ("rope_scaling", None)):
            if hf.get(key, only) != only:
                raise NotImplementedError(f"{key} {hf[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names and v is not None}
        kw["sliding_window_size"] = int(hf.get("sliding_window", 2048))
        n = int(kw.get("num_hidden_layers", cls.num_hidden_layers))
        every = int(hf.get("global_attn_every_n_layers", 4))
        types = hf.get("layer_types") or [
            "full_attention" if (i + 1) % every == 0 else "sliding_attention"
            for i in range(n)]
        unknown = sorted(set(types) - set(_KINDS))
        if unknown:
            raise NotImplementedError(f"layer_types {unknown}")
        kw["layer_types"] = tuple(types)
        cfg = cls(**kw)
        if len(cfg.layer_types) != n:
            raise ValueError("layer_types must name every layer")
        if cfg.window_ring and cfg.window_ring < cfg.sliding_window_size:
            raise ValueError("window_ring is shorter than the window")
        if cfg.head_dim % 2 or not 0 <= cfg.num_dense_layers <= n:
            raise ValueError(f"head_dim {cfg.head_dim}, num_dense_layers "
                             f"{cfg.num_dense_layers}")
        return cfg

    @property
    def share(self) -> Share:
        return Share(self.num_experts * self.ep_size,
                     self.num_experts * self.ep_rank, self.num_experts)

    def _kind(self, window: int) -> GqaKind:
        return GqaKind(self.num_attention_heads, self.num_key_value_heads,
                       self.head_dim, self.head_dim, self.rope_theta, False,
                       window=window, rotary=bool(window), qk_norm=True,
                       gate=True, norm_eps=self.rms_norm_eps)

    @property
    def full(self) -> GqaKind:
        return self._kind(0)

    @property
    def swa(self) -> GqaKind:
        return self._kind(self.sliding_window_size)

    def kind_name(self, layer: int) -> str:
        return _KINDS[self.layer_types[layer]]

    def kind(self, layer: int) -> GqaKind:
        return self.swa if self.kind_name(layer) == WINDOW else self.full

    def routed(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    @property
    def n_window(self) -> int:
        return sum(t == "sliding_attention" for t in self.layer_types)

    @property
    def n_full(self) -> int:
        return self.num_hidden_layers - self.n_window

    @property
    def n_routed_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def ring(self) -> int:
        return self.window_ring or -(-self.sliding_window_size
                                     // _LANES) * _LANES

    @property
    def shared_intermediate(self) -> int:
        return self.moe_intermediate_size * self.num_shared_experts

    # what `moe_block` reads off a config
    topk_method = "noaux_tc"

    @property
    def scoring_func(self) -> str:
        return self.score_func

    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale

    @property
    def norm_topk_prob(self) -> bool:
        return self.route_norm

    # what cost models and the generic engine read off a config
    @property
    def hd(self) -> int:
        return self.head_dim

    @property
    def sliding_window(self):
        return None

    @property
    def kv_values_per_position(self) -> float:
        """Cached values a decoded token reads of one position, as a
        mean over ALL the layers: the full layers' K and V; the window
        layers keep a ring whose reads do not grow with the position."""
        return (self.n_full * 2 * self.full.k_width
                / self.num_hidden_layers)

    def matmul_flops_per_token(self) -> int:
        """Forward matmul operations a token needs on THIS chip."""
        d, k = self.hidden_size, self.full
        attn = d * (2 * k.q_width + 2 * k.k_width) + k.q_width * d
        moe = 3 * d * (self.moe_intermediate_size * self.num_experts_per_tok
                       / self.ep_size + self.shared_intermediate)
        return int(2 * (self.num_hidden_layers * attn
                        + self.num_dense_layers * 3 * d
                        * self.intermediate_size
                        + self.n_routed_layers
                        * (moe + d * self.share.experts_total)
                        + d * self.vocab_size))

    def attn_flops_per_cached_token(self) -> int:
        """Decode attention per cached position: the full layers."""
        return self.n_full * 4 * self.num_attention_heads * self.head_dim


def scan_plan(cfg: AfmoeConfig) -> Tuple[int, int, int]:
    """`(head, period, periods)`: layers `0 .. head - 1` run unrolled,
    the rest as `periods` equal runs of `period` layers under one
    `lax.scan`. The period is the pattern's own (the smallest shift that
    maps `layer_types` onto itself), the head ends at the first period
    boundary at or past the dense layers; fewer than two such periods,
    and everything is unrolled."""
    n, types = cfg.num_hidden_layers, cfg.layer_types
    period = next(p for p in range(1, n + 1)
                  if all(types[i] == types[i + p] for i in range(n - p)))
    head = -(-cfg.num_dense_layers // period) * period
    head += (n - head) % period if head < n else 0
    periods = (n - head) // period if head < n else 0
    if periods < 2:
        return n, period, 0
    return head, period, periods


def _tables(cfg: AfmoeConfig, pos, sq: int):
    """cos and sin `[B or 1, sq, d / 2]` of the positions `pos .. pos +
    sq - 1`: the window layers' rotary (the full layers take none, and
    are handed the same tables unread)."""
    table = rope_tables(mimo_v2.row_positions(pos, sq),
                        {WINDOW: (cfg.head_dim, cfg.rope_theta)})
    return {WINDOW: table[WINDOW], FULL: table[WINDOW]}


attention_block = functools.partial(mimo_v2.attention_block, tables=_tables)


@functools.partial(jax.jit, static_argnames=("cfg", "window", "routed"))
def _layer(x, lp, experts, k_stack, v_stack, li, ei, pos, cos, sin, tally, *,
           cfg: AfmoeConfig, window: bool, routed: bool):
    """One layer of one kind on the residual stream `x`. Jitted with the
    layer's indices (`li` among its kind's planes, `ei` among the expert
    stacks) traced, so every layer of a kind is a call of one body."""
    eps = cfg.rms_norm_eps
    a, k_stack, v_stack = attention(
        rms_norm(x, lp["input_layernorm"], eps), lp,
        cfg.swa if window else cfg.full, k_stack, v_stack, li, pos, cos, sin)
    x = x + rms_norm(a, lp["post_attention_layernorm"], eps)
    hid = rms_norm(x, lp["pre_mlp_layernorm"], eps)
    if routed:
        with jax.named_scope("moe.block"):
            y, st = moe_block(hid, lp, experts, ei, cfg)
        tally = tally + st
    else:
        y = swiglu(hid, lp["gate_proj"], lp["up_proj"], lp["down_proj"])
    return x + rms_norm(y, lp["post_mlp_layernorm"], eps), k_stack, \
        v_stack, tally


def _at(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def forward(
    params: Dict[str, Any],
    cfg: AfmoeConfig,
    tokens: jax.Array,
    cache: KVCache,
    compute_dtype=jnp.bfloat16,
    last_only: bool = False,
) -> Tuple[jax.Array, KVCache]:
    b, sq = tokens.shape
    # serving marks an empty slot with -1: here it is a slot at 0
    pos = jnp.maximum(cache.pos, 0)
    x = embedding_lookup(params["embed_tokens"], tokens, compute_dtype)
    if cfg.mup_enabled:
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
    tables = _tables(cfg, pos, sq)
    stats = cache.stats
    tally = jnp.zeros((len(STATS),), jnp.int32) if stats is None else stats
    experts = params.get("experts")
    n_dense = cfg.num_dense_layers
    # the quantized stacks stay whole, here and in the scan: a linear's
    # kernel reads its layer where it lies (`ops/matmul.hold_stacks`)
    held, loose = {}, {}
    for group in ("attn", "dense", "moe"):
        held[group], loose[group] = hold_stacks(params.get(group) or {})

    def leaves(i, feed, fi, loose_attn, loose_feed):
        """Layer `i`'s leaves: its attention's, and row `fi` of the
        `feed` group ("dense" or "moe")."""
        return layer_params(held[feed], layer_params(
            held["attn"], {**loose_attn, **loose_feed}, i), fi)

    def run(carry, fi, lp, at):
        """One layer on the carry, of the kind of layer `at` (a plain
        int: layer `at` itself, or the layer of a later period that
        repeats it); `fi` and the plane indices may be traced."""
        x, planes, tally, li = carry
        kind, routed = cfg.kind_name(at), cfg.routed(at)
        x, k, v, tally = _layer(
            x, lp, experts if routed else None, *planes[kind], li[kind], fi,
            pos, *tables[kind], tally, cfg=cfg, window=kind == WINDOW,
            routed=routed)
        return (x, {**planes, kind: (k, v)}, tally,
                {**li, kind: li[kind] + 1})

    carry = (x, {FULL: (cache.full_k, cache.full_v),
                 WINDOW: (cache.ring_k, cache.ring_v)}, tally,
             {FULL: jnp.int32(0), WINDOW: jnp.int32(0)})
    head, period, periods = scan_plan(cfg)
    for i in range(head):
        feed, fi = ("moe", i - n_dense) if cfg.routed(i) else ("dense", i)
        carry = run(carry, jnp.int32(fi),
                    leaves(jnp.int32(i), feed, jnp.int32(fi),
                           _at(loose["attn"], i), _at(loose[feed], fi)), i)
    if periods:
        fold = lambda a, lo: a[lo:].reshape(                    # noqa: E731
            (periods, period) + a.shape[1:])

        def one_period(carry, xs):
            p, loose_attn, loose_moe = xs
            for j in range(period):
                i = head + p * period + j
                carry = run(carry, i - n_dense,
                            leaves(i, "moe", i - n_dense,
                                   _at(loose_attn, j), _at(loose_moe, j)),
                            head + j)
            return carry, None

        carry, _ = lax.scan(one_period, carry, (
            jnp.arange(periods, dtype=jnp.int32),
            jax.tree.map(lambda a: fold(a, head), loose["attn"]),
            jax.tree.map(lambda a: fold(a, head - n_dense), loose["moe"])))
    x, planes, tally, _ = carry
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
    """Attention leaves (one layer's or the stack of all) as `forward`
    serves them: q / k / v and the gate merged (block quantization is
    per column: bit-exact). Prepared leaves pass through."""
    from bigdl_tpu.ops.quant import QTensor, concat_qtensors_n

    if "qkv_proj" in lp:
        return lp
    lp = dict(lp)
    ws = [lp.pop(n) for n in _ATTN_MERGED]
    lp["qkv_proj"] = (concat_qtensors_n(ws) if isinstance(ws[0], QTensor)
                      else jnp.concatenate(ws, axis=-1))
    return lp


def prepare_params(params: Dict[str, Any], cfg: AfmoeConfig = None
                   ) -> Dict[str, Any]:
    return {**params, "attn": prepare_layer(params["attn"])}


def layer_leaves(params: Dict[str, Any], cfg: AfmoeConfig, i: int
                 ) -> Dict[str, Any]:
    """Layer `i`'s own leaves out of the stacks of a canonical or served
    tree (its routed experts stay in `params["experts"]`, row `i -
    num_dense_layers`): for a check of one layer."""
    feed, fi = (("moe", i - cfg.num_dense_layers) if cfg.routed(i)
                else ("dense", i))
    return {**_at(params["attn"], i), **_at(params[feed], fi)}


_ATTN_LINEARS = {"self_attn.q_proj": "q_proj", "self_attn.k_proj": "k_proj",
                 "self_attn.v_proj": "v_proj", "self_attn.o_proj": "o_proj",
                 "self_attn.gate_proj": "g_proj"}
_ATTN_VECTORS = {"self_attn.q_norm": "q_norm", "self_attn.k_norm": "k_norm",
                 **{n: n for n in _NORMS}}
_DENSE_MLP = {"mlp.gate_proj": "gate_proj", "mlp.up_proj": "up_proj",
              "mlp.down_proj": "down_proj"}
_SHARED_MLP = {"mlp.shared_experts.gate_proj": "shared_gate",
               "mlp.shared_experts.up_proj": "shared_up",
               "mlp.shared_experts.down_proj": "shared_down"}
_EXPERT_MLP = {"gate_proj": "experts_gate", "up_proj": "experts_up",
               "down_proj": "experts_down"}


def convert_hf_params(
    tensors,
    cfg: AfmoeConfig,
    qtype: Optional[str] = "sym_int4",
    compute_dtype=jnp.bfloat16,
    modules_to_not_convert: Tuple[str, ...] = (),
    imatrix=None,
) -> Dict[str, Any]:
    """HF tensors -> the served tree. Tensor names ASSUMED from the
    family's modelling code, no checkpoint of this model has been read
    here: `self_attn.{q,k,v,o,gate}_proj`, `self_attn.{q,k}_norm`, the
    four layer norms, `mlp.router.gate` `[E, D]`, `mlp.expert_bias`
    `[E]`, `mlp.shared_experts.*` and `mlp.experts.<e>.*`. The router,
    its bias and the norms stay unquantized; of the routed experts only
    those this chip holds (`cfg.share`) are converted, and of an
    embedding or head with more rows than `cfg.vocab_size` the chip's
    slice (rows `ep_rank * vocab_size ..`)."""
    from bigdl_tpu.ops.quant import FLOAT_QTYPES, quantize_linear

    del imatrix
    do_quant = qtype is not None and qtype not in FLOAT_QTYPES
    share, n_dense = cfg.share, cfg.num_dense_layers
    n_moe = cfg.n_routed_layers

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
    attn = [dict() for _ in range(cfg.num_hidden_layers)]
    dense = [dict() for _ in range(n_dense)]
    moe = [dict() for _ in range(n_moe)]
    experts = {k: [[None] * share.held for _ in range(n_moe)]
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
            if i >= cfg.num_hidden_layers:
                continue
            sub = ".".join(parts[3:])
            stem = sub[:-len(".weight")] if sub.endswith(".weight") else sub
            routed = cfg.routed(i)
            if stem in _ATTN_LINEARS:
                attn[i][_ATTN_LINEARS[stem]] = lin(name, w)
            elif stem in _ATTN_VECTORS:
                attn[i][_ATTN_VECTORS[stem]] = vec(w)
            elif stem in _DENSE_MLP and not routed:
                dense[i][_DENSE_MLP[stem]] = lin(name, w)
            elif not routed:
                continue
            elif stem in _SHARED_MLP:
                moe[i - n_dense][_SHARED_MLP[stem]] = lin(name, w)
            elif stem == "mlp.router.gate":
                moe[i - n_dense]["router"] = vec(w).T
            elif stem == "mlp.expert_bias":
                moe[i - n_dense]["router_bias"] = vec(w)
            elif sub.startswith("mlp.experts."):
                e = int(parts[5]) - share.first_held
                if 0 <= e < share.held:
                    experts[_EXPERT_MLP[parts[6]]][i - n_dense][e] = \
                        lin(name, w)
    needs = (("attn", attn, set(_ATTN_LINEARS.values())
              | set(_ATTN_VECTORS.values())),
             ("dense", dense, set(_DENSE_MLP.values())),
             ("moe", moe, set(_SHARED_MLP.values())
              | {"router", "router_bias"}))
    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)  # noqa: E731
    for group, per_layer, need in needs:
        for i, lp in enumerate(per_layer):
            missing = sorted(need - set(lp))
            if missing:
                raise ValueError(f"checkpoint missing {group} layer {i} "
                                 f"tensors: {missing}")
        if per_layer:
            params[group] = stack(per_layer)
    if n_moe:
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
