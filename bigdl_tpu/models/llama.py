"""Llama-family model: functional, static-shape, scan-over-layers.

TPU-native re-design of the reference's optimized llama path
(reference transformers/models/llama.py: llama_model_forward_4_36 at :103,
llama_attention_forward_4_36 at :875, llama_mlp_forward at :150,
llama_rms_norm_forward at :134). Where the reference monkey-patches HF
nn.Modules and dispatches per-shape to SYCL kernels, this is a from-scratch
functional model over a parameter pytree:

- All linear weights are contraction-major leaves ([K, N] dense or QTensor),
  so every projection is one `linear()` call that hits the fused Pallas
  dequant-matmul on TPU.
- Per-layer parameters are STACKED along a leading L axis and the layer loop
  is `lax.scan` — one layer gets traced/compiled once, not 32 times.
- The KV cache is pre-allocated static-shape (ops/kvcache.py) and carried
  through the scan; decode never re-allocates or re-compiles.
- The same `forward()` serves prefill (Sq = prompt length) and decode
  (Sq = 1): query positions make causal + cache-tail masking uniform.

Covers the llama architecture family as the reference does (llama/llama2/
codellama/vicuna and, via configs, mistral-style GQA models).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import functools

from bigdl_tpu.ops.attention import sdp_attention, sdp_attention_paged
from bigdl_tpu.ops.kvcache import KVCache, init_cache, update_layer
from bigdl_tpu.ops.paged import (PagedKVCache, init_paged_cache,
                                 paged_update_layer)
from bigdl_tpu.ops.matmul import hold_stacks, layer_params, linear
from bigdl_tpu.ops.embedding import embedding_lookup
from bigdl_tpu.ops.norms import layer_norm, rms_norm
from bigdl_tpu.ops.rope import (apply_rope, rope_cos_sin, rope_freqs,
                                scaled_rope_freqs)


@jax.named_scope("lm_head")
def _lm_head(x, params, cfg):
    """Final projection (tied or separate), f32 logits, optional softcap."""
    from bigdl_tpu.ops.quant import QTensor

    lm_head = params.get("lm_head")
    if lm_head is None:
        emb = params["embed_tokens"]
        if isinstance(emb, QTensor):      # quantized table is [D, V]
            logits = linear(x, emb)
        else:
            logits = jnp.dot(x, emb.T.astype(x.dtype),
                             preferred_element_type=jnp.float32)
    else:
        logits = linear(x, lm_head, params.get("lm_head_bias"))
    logits = logits.astype(jnp.float32)
    if cfg.logits_soft_cap is not None:
        logits = jnp.tanh(logits / cfg.logits_soft_cap) * cfg.logits_soft_cap
    return logits


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Config for the generalized decoder module.

    The base fields describe llama; the knobs below let one scan-based code
    path serve the reference's other monkey-patched families (SURVEY.md §2:
    transformers/models/{gptneox,bloom,falcon,phi,gemma,starcoder2,...}.py)
    as config deltas instead of 400-line forks.
    """
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: Optional[int] = None
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling_factor: float = 1.0
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    sliding_window: Optional[int] = None
    # --- family knobs ---
    norm_type: str = "rmsnorm"          # "rmsnorm" | "layernorm"
    rms_weight_offset: float = 0.0      # gemma: y * (offset + w)
    hidden_act: str = "silu"            # "silu" | "gelu" | "gelu_tanh"
    mlp_gated: bool = True              # False: dense 2-proj (up/down) MLP
    rope_interleaved: bool = False      # gptj/chatglm rotation convention
    rotary_dim: Optional[int] = None    # partial rotary (gptneox/phi)
    use_rope: bool = True               # False for alibi families
    learned_positions: bool = False     # gptbigcode/gpt2: wpe table added
    parallel_residual: bool = False     # x + attn(n1(x)) + mlp(n2(x))
    shared_input_norm: bool = False     # phi/falcon-7b: mlp reuses n1(x)
    use_alibi: bool = False             # bloom/baichuan-13b
    # explicit TP (parallel/tp.py) traces the decoder with LOCAL head
    # counts; ALiBi slopes are a function of the FULL head count, so the
    # local trace slices alibi_slopes(alibi_total_heads) at
    # axis_index(tp_axis) * local_heads instead of regenerating a
    # (different) schedule for the local count
    alibi_total_heads: Optional[int] = None
    tp_axis: str = "tp"
    embed_scale: float = 1.0            # gemma: sqrt(hidden_size)
    embed_norm: bool = False            # bloom: LN right after embedding
    logits_soft_cap: Optional[float] = None   # gemma2 final logits
    attn_soft_cap: Optional[float] = None     # gemma2 attention scores
    lm_head_bias: bool = False          # phi
    # non-linear rope scaling (yarn/dynamic/llama3) as a hashable
    # sorted-items tuple; linear scaling uses rope_scaling_factor
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None
    # gemma2 block shape: norms AFTER attn/mlp outputs too, scaled queries,
    # sliding window on even layers only
    sandwich_norms: bool = False
    query_pre_attn_scalar: Optional[float] = None
    alt_sliding_window: bool = False
    # sparse-MoE MLP (phixtral-style; layer params carry "router" +
    # "experts_*" stacks instead of the dense mlp keys)
    num_local_experts: int = 0
    num_experts_per_tok: int = 2

    @property
    def hd(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @classmethod
    def from_hf(cls, hf: Dict[str, Any]) -> "LlamaConfig":
        """Build from an HF config dict (config.json of llama/mistral...)."""
        rs = hf.get("rope_scaling") or {}
        factor = 1.0
        rs_tuple = None
        if rs:
            rtype = rs.get("rope_type", rs.get("type", "linear"))
            if rtype == "linear":
                factor = float(rs.get("factor", 1.0))
            elif rtype in ("default", "none"):
                pass
            else:
                # yarn / dynamic / llama3: handled by scaled_rope_freqs;
                # stored as a hashable tuple (config is a jit static arg)
                rs_tuple = tuple(sorted(
                    (k, v) for k, v in rs.items()
                    if isinstance(v, (int, float, str))))
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_hidden_layers=hf["num_hidden_layers"],
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf.get(
                "num_key_value_heads", hf["num_attention_heads"]),
            head_dim=hf.get("head_dim"),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            rope_theta=hf.get("rope_theta", 10000.0),
            rope_scaling_factor=factor,
            rope_scaling=rs_tuple,
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            attention_bias=hf.get("attention_bias", False),
            mlp_bias=hf.get("mlp_bias", False),
            sliding_window=hf.get("sliding_window"),
        )


# Parameter pytree layout (all linear leaves contraction-major [K, N]):
# {
#   "embed_tokens": [V, D],
#   "layers": {
#     "input_layernorm":          [L, D],
#     "post_attention_layernorm": [L, D],
#     "q_proj" | "k_proj" | "v_proj" | "o_proj":       stacked QTensor/dense,
#     "gate_proj" | "up_proj" | "down_proj":           stacked QTensor/dense,
#     (+ "<name>_bias": [L, N] when attention_bias/mlp_bias)
#   },
#   "norm": [D],
#   "lm_head": QTensor/dense [D, V] (absent when tied),
# }


def merge_projections(params: Dict[str, Any], cfg: "LlamaConfig"
                      ) -> Dict[str, Any]:
    """Fuse q/k/v into one [D, (H+2Hkv)*hd] weight and gate/up into one
    [D, 2F] — the reference's `_optimize_pre` weight surgery + fused
    `forward_qkv`/`mlp_forward_xpu` kernels (reference transformers/
    convert.py:529-640, models/llama.py:362-373, 162-166), done here as
    a pure param transform: one matmul instead of three (two) per block
    raises prefill MFU and cuts decode kernel dispatches; block
    quantization is per-column so the merge is BIT-exact.

    Skips (returns inputs unchanged) whenever the merge would not be
    exact or the layout does not apply: mixed qtypes across the
    projections, partial biases, MoE layers, non-gated MLPs. The layer
    body (`_attn_block`/`_mlp`) accepts both layouts; use
    `unmerge_projections` to restore the split layout (adapters and
    explicit TP sharding need it)."""
    from bigdl_tpu.ops.quant import QTensor, concat_qtensors_n

    layers = params.get("layers")
    if not isinstance(layers, dict):
        return params

    def bundle(names):
        ws = [layers.get(nm) for nm in names]
        if any(w is None for w in ws):
            return None, None
        if all(isinstance(w, QTensor) for w in ws):
            if len({w.qtype for w in ws}) != 1 \
                    or len({w.shape[0] for w in ws}) != 1:
                return None, None
        elif any(isinstance(w, QTensor) for w in ws):
            return None, None
        elif len({w.dtype for w in ws}) != 1 \
                or len({w.shape[-2] for w in ws}) != 1:
            return None, None
        bs = [layers.get(f"{nm}_bias") for nm in names]
        if any(b is not None for b in bs) and not all(
                b is not None for b in bs):
            return None, None            # partial biases: keep split
        return ws, (bs if bs[0] is not None else None)

    def concat(ws):
        if isinstance(ws[0], QTensor):
            return concat_qtensors_n(ws)
        return jnp.concatenate(ws, axis=-1)

    new = dict(layers)
    changed = False
    qkv, qkv_b = bundle(("q_proj", "k_proj", "v_proj"))
    if qkv is not None:
        new["qkv_proj"] = concat(qkv)
        if qkv_b is not None:
            new["qkv_proj_bias"] = jnp.concatenate(qkv_b, axis=-1)
        for nm in ("q_proj", "k_proj", "v_proj"):
            new.pop(nm)
            new.pop(f"{nm}_bias", None)
        changed = True
    gu, gu_b = bundle(("gate_proj", "up_proj"))
    if gu is not None:
        new["gate_up_proj"] = concat(gu)
        if gu_b is not None:
            new["gate_up_proj_bias"] = jnp.concatenate(gu_b, axis=-1)
        for nm in ("gate_proj", "up_proj"):
            new.pop(nm)
            new.pop(f"{nm}_bias", None)
        changed = True
    if not changed:
        return params
    return {**params, "layers": new}


def unmerge_projections(params: Dict[str, Any], cfg: "LlamaConfig"
                        ) -> Dict[str, Any]:
    """Inverse of `merge_projections` (exact slicing)."""
    from bigdl_tpu.ops.quant import QTensor, split_qtensor_n

    layers = params.get("layers")
    if not isinstance(layers, dict):
        return params

    def split(w, sizes):
        if isinstance(w, QTensor):
            return split_qtensor_n(w, sizes)
        off, outs = 0, []
        for s in sizes:
            outs.append(w[..., off:off + s])
            off += s
        return outs

    new = dict(layers)
    changed = False
    if "qkv_proj" in new:
        h, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.hd)
        sizes = (h * hd, hkv * hd, hkv * hd)
        for nm, w in zip(("q_proj", "k_proj", "v_proj"),
                         split(new.pop("qkv_proj"), sizes)):
            new[nm] = w
        if "qkv_proj_bias" in new:
            for nm, b in zip(("q_proj", "k_proj", "v_proj"),
                             split(new.pop("qkv_proj_bias"), sizes)):
                new[f"{nm}_bias"] = b
        changed = True
    if "gate_up_proj" in new:
        gu = new.pop("gate_up_proj")
        f = (gu.shape[1] if isinstance(gu, QTensor)
             else gu.shape[-1]) // 2
        for nm, w in zip(("gate_proj", "up_proj"), split(gu, (f, f))):
            new[nm] = w
        if "gate_up_proj_bias" in new:
            for nm, b in zip(("gate_proj", "up_proj"),
                             split(new.pop("gate_up_proj_bias"), (f, f))):
                new[f"{nm}_bias"] = b
        changed = True
    if not changed:
        return params
    return {**params, "layers": new}


def model_rope_freqs(cfg: "LlamaConfig"):
    """(inv_freq, attention_factor) honoring cfg.rope_scaling."""
    if cfg.rope_scaling is not None:
        return scaled_rope_freqs(
            cfg.hd, cfg.rope_theta, dict(cfg.rope_scaling),
            rotary_dim=cfg.rotary_dim,
            max_position_embeddings=cfg.max_position_embeddings)
    return rope_freqs(cfg.hd, cfg.rope_theta, rotary_dim=cfg.rotary_dim,
                      scaling_factor=cfg.rope_scaling_factor), 1.0


def alibi_slopes(n_heads: int) -> np.ndarray:
    """Standard ALiBi slope schedule (bloom/baichuan-13b families)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return start * (start ** np.arange(n))

    if np.log2(n_heads).is_integer():
        return pow2_slopes(n_heads).astype(np.float32)
    closest = 2 ** int(np.floor(np.log2(n_heads)))
    base = pow2_slopes(closest)
    extra = pow2_slopes(2 * closest)[0::2][: n_heads - closest]
    return np.concatenate([base, extra]).astype(np.float32)


def _model_slopes(cfg: "LlamaConfig") -> Optional[jax.Array]:
    """Per-head ALiBi slopes for THIS trace's head count.

    Single device: the full schedule. Under explicit TP (parallel/tp.py)
    cfg carries local head counts but slopes are a function of the FULL
    count — slice the full schedule at this device's head offset."""
    if not cfg.use_alibi:
        return None
    total = cfg.alibi_total_heads or cfg.num_attention_heads
    full = jnp.asarray(alibi_slopes(total))
    if total == cfg.num_attention_heads:
        return full
    idx = lax.axis_index(cfg.tp_axis)
    return lax.dynamic_slice(full, (idx * cfg.num_attention_heads,),
                             (cfg.num_attention_heads,))


def _norm(x, w, b, cfg: LlamaConfig):
    if cfg.norm_type == "layernorm":
        return layer_norm(x, w, b, cfg.rms_norm_eps)
    if cfg.rms_weight_offset:
        w = w.astype(jnp.float32) + cfg.rms_weight_offset
    return rms_norm(x, w, cfg.rms_norm_eps)


def embed_prologue(params, cfg: LlamaConfig, tokens, positions,
                   compute_dtype):
    """Token embedding + scale + embedding norm + learned positions.

    THE one copy of the embed stage — forward/forward_train here,
    the pipeline schedule (parallel/pp.py) and imatrix calibration all
    call it, so a new config knob lands everywhere at once. `positions`
    is [Sq] (shared) or [B, Sq] (per-slot serving)."""
    x = embedding_lookup(params["embed_tokens"], tokens, compute_dtype)
    if cfg.embed_scale != 1.0:
        x = x * jnp.asarray(cfg.embed_scale, compute_dtype)
    if cfg.embed_norm:
        x = _norm(x, params["embed_norm"], params.get("embed_norm_bias"),
                  cfg)
    if cfg.learned_positions:
        pe = params["embed_positions"][positions].astype(x.dtype)
        if pe.ndim == 2:                  # positions [Sq]: add batch axis
            pe = pe[None]
        x = x + pe
    return x


_ACTS = {
    "silu": jax.nn.silu,
    "gelu": functools.partial(jax.nn.gelu, approximate=False),
    "gelu_tanh": functools.partial(jax.nn.gelu, approximate=True),
    "gelu_new": functools.partial(jax.nn.gelu, approximate=True),
    "gelu_pytorch_tanh": functools.partial(jax.nn.gelu, approximate=True),
    "relu": jax.nn.relu,
}


def _moe_mlp(hidden, lp, cfg: LlamaConfig):
    """Sparse-MoE MLP for generalized-decoder families (phixtral: phi body
    with a mixture of dense fc1/fc2 experts, reference transformers/models/
    phixtral.py:73-138 — there a Python loop with host syncs; here two
    host-sync-free strategies chosen by token count, like the reference's
    prefill/decode split in mixtral_moeblock_forward:

    - prefill (many tokens): dense one-hot einsum combine — every expert
      runs on every token; with enough tokens per expert the full-expert
      weight read amortizes and everything is big MXU matmuls.
    - decode (few tokens): per-token expert GATHER — only the top-k
      experts' weights leave HBM (dynamic-index on the stacked [E, ...]
      leaves), cutting MoE decode HBM traffic by E/k (4x for Mixtral
      8x top-2), which is the whole cost of a memory-bound decode step."""
    b, t, d = hidden.shape
    act = _ACTS[cfg.hidden_act]
    xf = hidden.reshape(-1, d)
    n = xf.shape[0]
    router_logits = jnp.dot(xf, lp["router"].astype(hidden.dtype),
                            preferred_element_type=jnp.float32)
    topv, topi = lax.top_k(router_logits, cfg.num_experts_per_tok)
    w = jax.nn.softmax(topv, axis=-1)                         # [N, k]

    gated = cfg.mlp_gated
    biased = (not gated) and ("experts_up_bias" in lp)
    # explicit TP wraps experts_down in a collective-injecting wrapper
    # (parallel/tp.AllReduceLinear); paths that consume the raw stack
    # (qtype probes, the ragged kernel) unwrap it and apply the reduce
    # to their partial output themselves
    dleaf = lp["experts_down"]
    post_reduce = getattr(dleaf, "post_reduce", None)
    dstack = dleaf.base if post_reduce is not None else dleaf

    def one_expert(x_row, gw, uw, dw, ub, db, backend=None):
        """x [1, D] through ONE expert's projections."""
        if gated:
            return linear(act(linear(x_row, gw, backend=backend))
                          * linear(x_row, uw, backend=backend), dw,
                          backend=backend)
        return linear(act(linear(x_row, uw, ub, backend=backend)), dw, db,
                      backend=backend)

    # gather path pays k weight-gathers per token; dense pays E expert
    # matmuls over all N tokens — switch where gathered bytes win
    if n * cfg.num_experts_per_tok <= cfg.num_local_experts:
        from bigdl_tpu.ops.matmul import vmapped_pallas_ok

        # fused kernels under vmap are gated by compile probes covering
        # EVERY (qtype, geometry) the gather actually runs — mixed_*
        # policies can land different qtypes per projection; a kernel
        # the compiler refuses raises (ops/probing.py). Off-TPU and
        # dense expert stacks never hit pallas
        ff = cfg.intermediate_size
        probes = []
        for leaf, kk, nn in ((lp.get("experts_gate"), d, ff),
                             (lp.get("experts_up"), d, ff),
                             (dstack, ff, d)):
            if leaf is not None and hasattr(leaf, "qtype"):
                probes.append((leaf.qtype, kk, nn))
        gather_backend = (
            None if probes and all(vmapped_pallas_ok(*p) for p in probes)
            else "xla")

        def per_token(x_row, idxs, wts):
            def per_choice(i):
                gw = (jax.tree.map(lambda a: a[i], lp["experts_gate"])
                      if gated else None)
                uw = jax.tree.map(lambda a: a[i], lp["experts_up"])
                dw = jax.tree.map(lambda a: a[i], lp["experts_down"])
                ub = lp["experts_up_bias"][i] if biased else None
                db = lp["experts_down_bias"][i] if biased else None
                return one_expert(x_row[None], gw, uw, dw, ub, db,
                                  backend=gather_backend)[0]

            outs = jnp.stack([per_choice(idxs[j])
                              for j in range(cfg.num_experts_per_tok)])
            return jnp.sum(outs * wts[:, None].astype(outs.dtype), axis=0)

        y = jax.vmap(per_token)(xf, topi, w)
        return y.reshape(b, t, d)

    # prefill: sorted ragged dispatch runs only the CHOSEN experts'
    # FLOPs (E/k cut vs the dense combine below); requires the Pallas
    # kernel, probed per geometry. Quantized-with-bias stacks (none of
    # the served families) would fall through to dense.
    from bigdl_tpu.config import flags, target_is_tpu, under_spmd

    if (not biased and flags().moe_dispatch != "dense"
            and not under_spmd(xf, *jax.tree_util.tree_leaves(
                lp["experts_up"]))
            and (target_is_tpu()
                 or flags().moe_dispatch == "ragged")):
        from bigdl_tpu.ops.pallas.moe_dispatch import (
            moe_mlp_ragged, ragged_kernel_compiles)

        interp = not target_is_tpu()
        forced = flags().moe_dispatch == "ragged"
        # forced mode bypasses the probes so compile errors SURFACE
        # (A/B runs must never silently measure the dense path); auto
        # probes every (qtype, geometry) pair the dispatch runs
        ff = cfg.intermediate_size
        pairs = []
        for leaf, kk, nn in ((lp.get("experts_gate"), d, ff),
                             (lp.get("experts_up"), d, ff),
                             (dstack, ff, d)):
            if leaf is not None:
                pairs.append((leaf.qtype if hasattr(leaf, "qtype")
                              else None, kk, nn))
        if interp or forced or all(
                ragged_kernel_compiles(*p) for p in pairs):
            y = moe_mlp_ragged(
                xf, topi, w,
                lp["experts_gate"] if gated else None,
                lp["experts_up"], dstack, act,
                cfg.num_local_experts, interpret=interp)
            if post_reduce is not None:
                # ragged ran on the local ff shard: reduce the partial
                y = post_reduce(y)
            return y.reshape(b, t, d)

    combine = jnp.sum(
        jax.nn.one_hot(topi, cfg.num_local_experts, dtype=w.dtype)
        * w[..., None], axis=1)                               # [N, E]

    if gated:
        all_out = jax.vmap(lambda gw, uw, dw: one_expert(
            xf, gw, uw, dw, None, None))(
            lp["experts_gate"], lp["experts_up"], lp["experts_down"])
    elif biased:
        all_out = jax.vmap(lambda uw, ub, dw, db: one_expert(
            xf, None, uw, dw, ub, db))(
            lp["experts_up"], lp["experts_up_bias"],
            lp["experts_down"], lp["experts_down_bias"])
    else:
        all_out = jax.vmap(lambda uw, dw: one_expert(
            xf, None, uw, dw, None, None))(
            lp["experts_up"], lp["experts_down"])
    y = jnp.einsum("ne,end->nd", combine.astype(hidden.dtype), all_out)
    return y.reshape(b, t, d)


@jax.named_scope("mlp")
def _mlp(hidden, lp, cfg: LlamaConfig, record=None):
    if "router" in lp:
        if record is not None:
            # silent no-stats would quietly degrade every expert weight
            # to unweighted quantization — the bulk of an MoE model
            raise NotImplementedError(
                "imatrix collection over MoE expert MLPs is not supported "
                "yet; quantize MoE models without an imatrix (attention "
                "projections would be the only weighted tensors)")
        return _moe_mlp(hidden, lp, cfg)
    act = _ACTS[cfg.hidden_act]
    if "gate_up_proj" in lp:
        if record is not None:
            record("gate_up_proj", hidden)
        gu = linear(hidden, lp["gate_up_proj"], lp.get("gate_up_proj_bias"))
        f = gu.shape[-1] // 2
        inner = act(gu[..., :f]) * gu[..., f:]
        if record is not None:
            record("down_proj", inner)
        return linear(inner, lp["down_proj"], lp.get("down_proj_bias"))
    if record is not None:
        record("gate_proj" if cfg.mlp_gated else "up_proj", hidden)
        if cfg.mlp_gated:
            record("up_proj", hidden)
    if cfg.mlp_gated:
        gate = linear(hidden, lp["gate_proj"], lp.get("gate_proj_bias"))
        up = linear(hidden, lp["up_proj"], lp.get("up_proj_bias"))
        inner = act(gate) * up
    else:
        inner = act(linear(hidden, lp["up_proj"], lp.get("up_proj_bias")))
    if record is not None:
        record("down_proj", inner)
    return linear(inner, lp["down_proj"], lp.get("down_proj_bias"))


def _split_qkv(qkv, b, sq, h, hkv, hd):
    """Merged-projection output [B, Sq, (H+2Hkv)*hd] -> q/k/v heads."""
    q = qkv[..., :h * hd].reshape(b, sq, h, hd)
    k = qkv[..., h * hd:(h + hkv) * hd].reshape(b, sq, hkv, hd)
    v = qkv[..., (h + hkv) * hd:].reshape(b, sq, hkv, hd)
    return q, k, v


def _attn_block(hidden, lp, cfg: LlamaConfig, cos, sin, slopes,
                cache_ctx=None, lidx=None, record=None,
                block_tables=None):
    """QKV + rope + (cached) attention + output projection.

    With ``block_tables`` the cache planes in ``cache_ctx`` are page
    ARENAS (the layout of `ops/paged.py`): appends scatter through the
    table and attention reads via `sdp_attention_paged` (fused gather on
    TPU, XLA take fallback elsewhere), both on the whole stack."""
    b, sq, _ = hidden.shape
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd
    scale = (cfg.query_pre_attn_scalar ** -0.5
             if cfg.query_pre_attn_scalar is not None else None)
    sw = cfg.sliding_window
    if cfg.alt_sliding_window and sw is not None and lidx is not None:
        # gemma2: sliding attention on even layers, global on odd
        sw = jnp.where(lidx % 2 == 0, sw, jnp.int32(1 << 30))
    with jax.named_scope("attn.qkv"):
        if "qkv_proj" in lp:
            if record is not None:
                record("qkv_proj", hidden)
            q, k, v = _split_qkv(
                linear(hidden, lp["qkv_proj"], lp.get("qkv_proj_bias")),
                b, sq, h, hkv, hd)
        else:
            if record is not None:
                record("q_proj", hidden)
                record("k_proj", hidden)
                record("v_proj", hidden)
            q = linear(hidden, lp["q_proj"],
                       lp.get("q_proj_bias")).reshape(b, sq, h, hd)
            k = linear(hidden, lp["k_proj"],
                       lp.get("k_proj_bias")).reshape(b, sq, hkv, hd)
            v = linear(hidden, lp["v_proj"],
                       lp.get("v_proj_bias")).reshape(b, sq, hkv, hd)
        if cfg.use_rope:
            q = apply_rope(q, cos, sin, interleaved=cfg.rope_interleaved)
            k = apply_rope(k, cos, sin, interleaved=cfg.rope_interleaved)

    out = None
    if cache_ctx is not None:
        ck, cv, cks, cvs, clidx, pos = cache_ctx
        # with scale planes (block-scaled storage) the append quantizes
        # and returns them too; attention then gets raw codes + scale
        # planes so the dequant fuses into the kernels
        with jax.named_scope("attn.kv_update"):
            if block_tables is not None:
                written = paged_update_layer(ck, cv, clidx, k, v, pos,
                                             block_tables, cks, cvs)
            else:
                written = update_layer(ck, cv, clidx, k, v, pos, cks, cvs)
            if cks is not None:
                ck, cv, cks, cvs = written
            else:
                ck, cv = written
        out = (ck, cv, cks, cvs)
    with jax.named_scope("attn.core"):
        attn_kw = dict(scale=scale, sliding_window=sw,
                       logits_soft_cap=cfg.attn_soft_cap,
                       alibi_slopes=slopes)
        if cache_ctx is None:
            attn = sdp_attention(q, k, v, jnp.zeros((), jnp.int32),
                                 **attn_kw)
        elif block_tables is not None:
            # the arena's stacks and the layer index, as the slab branch:
            # the block-table kernel reads the layer where it lies
            attn = sdp_attention_paged(q, ck, cv, block_tables, pos, hkv,
                                       k_scale=cks, v_scale=cvs,
                                       layer=clidx, **attn_kw)
        else:
            # the stack and the layer index, not a slice: decode attention
            # reads the layer where it lies (raw codes + scale planes for
            # block-scaled storage, so the dequant fuses into the kernel)
            attn = sdp_attention(q, ck, cv, pos, k_scale=cks, v_scale=cvs,
                                 layer=clidx, **attn_kw)
    attn = attn.reshape(b, sq, h * hd)
    if record is not None:
        record("o_proj", attn)
    with jax.named_scope("attn.out"):
        return linear(attn, lp["o_proj"], lp.get("o_proj_bias")), out


def _decoder_layer(x, lp, cfg: LlamaConfig, cos, sin, slopes,
                   cache_ctx=None, lidx=None, record=None,
                   block_tables=None):
    """One transformer block, sequential/parallel/sandwich residual.

    `record(key, activation)` (optional, trace-time) observes the input of
    every linear — the imatrix collection hook (bigdl_tpu.imatrix), kept
    here so statistics always match the real forward."""
    hidden = _norm(x, lp["input_layernorm"],
                   lp.get("input_layernorm_bias"), cfg)
    attn_out, cache_out = _attn_block(hidden, lp, cfg, cos, sin, slopes,
                                      cache_ctx, lidx=lidx, record=record,
                                      block_tables=block_tables)
    if cfg.sandwich_norms:
        # gemma2: x += postnorm(attn(prenorm(x))); same sandwich for mlp
        attn_out = _norm(attn_out, lp["post_attention_layernorm"],
                         lp.get("post_attention_layernorm_bias"), cfg)
        x = x + attn_out
        mlp_in = _norm(x, lp["pre_feedforward_layernorm"],
                       lp.get("pre_feedforward_layernorm_bias"), cfg)
        mlp_out = _mlp(mlp_in, lp, cfg, record=record)
        mlp_out = _norm(mlp_out, lp["post_feedforward_layernorm"],
                        lp.get("post_feedforward_layernorm_bias"), cfg)
        return x + mlp_out, cache_out
    if cfg.parallel_residual:
        if cfg.shared_input_norm:
            mlp_in = hidden
        else:
            mlp_in = _norm(x, lp["post_attention_layernorm"],
                           lp.get("post_attention_layernorm_bias"), cfg)
        x = x + attn_out + _mlp(mlp_in, lp, cfg, record=record)
    else:
        x = x + attn_out
        hidden2 = _norm(x, lp["post_attention_layernorm"],
                        lp.get("post_attention_layernorm_bias"), cfg)
        x = x + _mlp(hidden2, lp, cfg, record=record)
    return x, cache_out


def _layer_step(cfg: LlamaConfig, slopes, held, carry, xs,
                block_tables=None):
    """The body of `forward`'s and `forward_paged`'s layer scan. The
    quantized `[L, K, N]` stacks (`held`) are closed over and read at
    `lidx` where they lie; `xs` scans the small leaves by value."""
    x, ck, cv, cks, cvs, pos, cos, sin = carry
    lp, lidx = xs
    x, (ck, cv, cks, cvs) = _decoder_layer(
        x, layer_params(held, lp, lidx), cfg, cos, sin, slopes,
        cache_ctx=(ck, cv, cks, cvs, lidx, pos), lidx=lidx,
        block_tables=block_tables)
    return (x, ck, cv, cks, cvs, pos, cos, sin), None


def forward(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jax.Array,       # [B, Sq] int32
    cache: KVCache,
    compute_dtype=jnp.bfloat16,
    last_only: bool = False,
    visual: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, KVCache]:
    """Run the model; returns (logits [B, Sq, V], updated cache).

    `cache.pos` is the write offset: 0 for prefill, prompt_len + n for the
    n-th decode step. One function, both phases (static Sq distinguishes
    the compiled executables). last_only=True computes lm_head for the
    final position only — the reference's `optimize_lm_head` trick
    (low_bit_linear.py:251-258), which matters when V=32k+ and Sq is long.

    `visual=(vidx [B, Sq] int32, vemb [Nv, D])` splices multimodal
    embeddings over the token embeddings: rows where vidx > 0 take
    vemb[vidx-1] (Qwen-VL image spans, models/qwen_vl.py; the reference
    mutates hidden_states in place in qwen_vl's QWenModel.forward). One
    gather + select — shapes stay static, positions/RoPE unchanged.
    """
    b, sq = tokens.shape
    pos = cache.pos

    inv_freq, rope_mscale = model_rope_freqs(cfg)
    if getattr(pos, "ndim", 0) == 1:   # per-slot positions (serving)
        positions = pos[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
        cos, sin = rope_cos_sin(positions, inv_freq)       # [B, Sq, hd/2]
    else:
        positions = pos + jnp.arange(sq, dtype=jnp.int32)
        cos, sin = rope_cos_sin(positions[None, :], inv_freq)  # [1, Sq, hd/2]
    x = embed_prologue(params, cfg, tokens, positions, compute_dtype)
    if visual is not None:
        vidx, vemb = visual
        x = jnp.where((vidx > 0)[..., None],
                      vemb[jnp.clip(vidx - 1, 0)].astype(x.dtype), x)
    if rope_mscale != 1.0:             # yarn attention temperature
        cos, sin = cos * rope_mscale, sin * rope_mscale
    slopes = _model_slopes(cfg)

    lidx = jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)
    held, scanned = hold_stacks(params["layers"])
    # scale planes are None for bf16/fp8 storage — None is an empty
    # pytree, so the scan carry structure stays consistent either way
    (x, ck, cv, cks, cvs, _, _, _), _ = lax.scan(
        lambda c, xs: _layer_step(cfg, slopes, held, c, xs),
        (x, cache.k, cache.v, cache.k_scale, cache.v_scale, pos, cos, sin),
        (scanned, lidx),
    )

    if last_only:
        x = x[:, -1:, :]
    x = _norm(x, params["norm"], params.get("norm_bias"), cfg)
    logits = _lm_head(x, params, cfg)
    return logits, KVCache(ck, cv, pos + sq, cks, cvs)


def forward_last_token(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jax.Array,
    cache: KVCache,
    compute_dtype=jnp.bfloat16,
    visual: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, KVCache]:
    """Prefill variant of `forward` with lm_head on the final position only."""
    return forward(params, cfg, tokens, cache, compute_dtype=compute_dtype,
                   last_only=True, visual=visual)


def forward_paged(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jax.Array,        # [B, Sq] int32
    cache: PagedKVCache,
    block_tables: jax.Array,  # [B, NP] int32
    compute_dtype=jnp.bfloat16,
    last_only: bool = False,
) -> Tuple[jax.Array, PagedKVCache]:
    """`forward` over a paged KV arena: appends scatter through the
    block table, attention gathers through it (fused on TPU). Positions
    are always per-slot ([B] `cache.pos`) — the paged layout exists for
    continuous batching. With ``NP * page_size == max_seq`` the logits
    are byte-identical to the slab `forward` at equal positions (tests
    pin this for bf16/int8/int4 storage)."""
    b, sq = tokens.shape
    pos = cache.pos

    inv_freq, rope_mscale = model_rope_freqs(cfg)
    positions = pos[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    cos, sin = rope_cos_sin(positions, inv_freq)           # [B, Sq, hd/2]
    x = embed_prologue(params, cfg, tokens, positions, compute_dtype)
    if rope_mscale != 1.0:             # yarn attention temperature
        cos, sin = cos * rope_mscale, sin * rope_mscale
    slopes = _model_slopes(cfg)

    lidx = jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)
    held, scanned = hold_stacks(params["layers"])
    (x, ck, cv, cks, cvs, _, _, _), _ = lax.scan(
        lambda c, xs: _layer_step(cfg, slopes, held, c, xs, block_tables),
        (x, cache.k, cache.v, cache.k_scale, cache.v_scale, pos, cos, sin),
        (scanned, lidx),
    )

    if last_only:
        x = x[:, -1:, :]
    x = _norm(x, params["norm"], params.get("norm_bias"), cfg)
    logits = _lm_head(x, params, cfg)
    return logits, PagedKVCache(ck, cv, pos + sq, cks, cvs,
                                cache.kv_heads)


def ext_attn_layer(x, lp, cfg: LlamaConfig, cos, sin, attn_fn):
    """One transformer block with an EXTERNAL attention function —
    THE shared layer body of every parallel attention scheme
    (forward_train's ring-attention branch, parallel/cp.py's context-
    parallel prefill/decode). attn_fn(q, k, v) -> attention output;
    returns (x_out, (k, v)) so callers that keep a KV cache can collect
    the projections. Families outside the standard residual path are
    rejected by the callers' guards."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd
    hidden = _norm(x, lp["input_layernorm"],
                   lp.get("input_layernorm_bias"), cfg)
    if "qkv_proj" in lp:
        q, k, v = _split_qkv(
            linear(hidden, lp["qkv_proj"], lp.get("qkv_proj_bias")),
            b, s, h, hkv, hd)
    else:
        q = linear(hidden, lp["q_proj"], lp.get("q_proj_bias")).reshape(
            b, s, h, hd)
        k = linear(hidden, lp["k_proj"], lp.get("k_proj_bias")).reshape(
            b, s, hkv, hd)
        v = linear(hidden, lp["v_proj"], lp.get("v_proj_bias")).reshape(
            b, s, hkv, hd)
    if cfg.use_rope:
        q = apply_rope(q, cos, sin, interleaved=cfg.rope_interleaved)
        k = apply_rope(k, cos, sin, interleaved=cfg.rope_interleaved)
    attn_out = linear(attn_fn(q, k, v).reshape(b, s, h * hd),
                      lp["o_proj"], lp.get("o_proj_bias"))
    if cfg.parallel_residual:
        mlp_in = hidden if cfg.shared_input_norm else _norm(
            x, lp["post_attention_layernorm"],
            lp.get("post_attention_layernorm_bias"), cfg)
        return x + attn_out + _mlp(mlp_in, lp, cfg), (k, v)
    x2 = x + attn_out
    hidden2 = _norm(x2, lp["post_attention_layernorm"],
                    lp.get("post_attention_layernorm_bias"), cfg)
    return x2 + _mlp(hidden2, lp, cfg), (k, v)


def forward_train(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jax.Array,       # [B, S] int32
    compute_dtype=jnp.bfloat16,
    attn_fn=None,            # (q, k, v) -> out; default causal sdp
    pos_offset=0,            # global position of tokens[:, 0] (seq parallel)
    return_hidden: bool = False,   # post-norm hidden states instead of logits
) -> jax.Array:
    """Cacheless causal forward for training: returns logits [B, S, V]
    (or the post-final-norm hidden states [B, S, D] with
    `return_hidden=True` — the embeddings path, reference
    langchain/embeddings pooled model outputs).

    The finetuning path (QLoRA stack, reference transformers/qlora.py) runs
    through this; no KV cache is materialized, attention is causal over the
    in-flight sequence, and `jax.checkpoint` on the layer body trades FLOPs
    for HBM during backward (the scan carries only layer inputs).

    `attn_fn`/`pos_offset` let sequence parallelism swap in ring attention
    over the sp mesh axis (bigdl_tpu.parallel.sp) with per-shard RoPE
    offsets — the model body is otherwise unchanged.
    """
    b, s = tokens.shape
    inv_freq, rope_mscale = model_rope_freqs(cfg)
    positions = pos_offset + jnp.arange(s, dtype=jnp.int32)
    x = embed_prologue(params, cfg, tokens, positions, compute_dtype)
    cos, sin = rope_cos_sin(positions[None, :], inv_freq)
    if rope_mscale != 1.0:             # yarn attention temperature
        cos, sin = cos * rope_mscale, sin * rope_mscale

    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd

    slopes = _model_slopes(cfg)

    if attn_fn is not None:
        if (cfg.use_alibi or cfg.attn_soft_cap is not None
                or cfg.sandwich_norms or cfg.alt_sliding_window
                or cfg.query_pre_attn_scalar is not None):
            raise NotImplementedError(
                "external attn_fn (sequence-parallel ring attention) does "
                "not support ALiBi/soft-cap/gemma2-style families yet; "
                "train these single-device or extend ops/ring.py")
        ext_attn = attn_fn

        @jax.checkpoint
        def layer(x, lp):
            out, _ = ext_attn_layer(x, lp, cfg, cos, sin, ext_attn)
            return out
    else:
        @jax.checkpoint
        def layer(x, lp, lidx):
            out, _ = _decoder_layer(x, lp, cfg, cos, sin, slopes,
                                    cache_ctx=None, lidx=lidx)
            return out

    if attn_fn is not None:
        x, _ = lax.scan(lambda c, lp: (layer(c, lp), None), x,
                        params["layers"])
    else:
        lids = jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)
        x, _ = lax.scan(lambda c, xs: (layer(c, xs[0], xs[1]), None), x,
                        (params["layers"], lids))
    x = _norm(x, params["norm"], params.get("norm_bias"), cfg)
    if return_hidden:
        return x
    return _lm_head(x, params, cfg)


# this family threads int8/int4 scale planes through its forward scan;
# serving consults the attribute before enabling block-scaled storage
SUPPORTS_SCALED_KV = True

# this family's forward_paged threads block tables through its scan;
# serving consults the attribute before enabling the paged KV arena
SUPPORTS_PAGED_KV = True


def new_cache(cfg: LlamaConfig, batch: int, max_seq: int,
              quantized=False) -> KVCache:
    """`quantized` accepts the legacy bool (True -> fp8_e5m2, deprecated)
    or a kv_cache_dtype name ("bf16"|"fp8_e5m2"|"int8"|"int4")."""
    return init_cache(cfg.num_hidden_layers, batch, max_seq,
                      cfg.num_key_value_heads, cfg.hd,
                      quantized=quantized)


def new_paged_cache(cfg: LlamaConfig, num_pages: int, page_size: int,
                    batch: int, kv_cache_dtype=None) -> PagedKVCache:
    """Allocate this family's page arena (`ops/paged.py` layout)."""
    return init_paged_cache(cfg.num_hidden_layers, num_pages, page_size,
                            cfg.num_key_value_heads, cfg.hd, batch,
                            kv_cache_dtype=kv_cache_dtype)


# ---------------------------------------------------------------------------
# HF checkpoint -> parameter pytree (the conversion engine for this family;
# reference analog: ggml_convert_low_bit walking nn.Modules, convert.py:643)
# ---------------------------------------------------------------------------

_LAYER_LINEARS = {
    "self_attn.q_proj": "q_proj",
    "self_attn.k_proj": "k_proj",
    "self_attn.v_proj": "v_proj",
    "self_attn.o_proj": "o_proj",
    "mlp.gate_proj": "gate_proj",
    "mlp.up_proj": "up_proj",
    "mlp.down_proj": "down_proj",
}


def _llama_map(acc, name: str, w) -> None:
    """HF llama/mistral/qwen2-style tensor names -> pytree keys."""
    if name in ("model.embed_tokens.weight", "transformer.wte.weight"):
        acc.top["embed_tokens"] = acc.dense(w)
    elif name == "model.norm.weight":
        acc.top["norm"] = acc.dense(w)
    elif name == "model.norm.bias":
        acc.top["norm_bias"] = acc.dense(w)
    elif name == "lm_head.weight":
        acc.top["lm_head"] = acc.linear(name, w)
    elif name == "lm_head.bias":
        acc.top["lm_head_bias"] = acc.dense(w)
    elif name.startswith("model.layers."):
        parts = name.split(".")
        idx = int(parts[2])
        sub = ".".join(parts[3:-1])   # e.g. self_attn.q_proj
        leaf = parts[-1]              # weight | bias
        if sub in _LAYER_LINEARS:
            key = _LAYER_LINEARS[sub]
            if leaf == "weight":
                acc.put(key, idx, acc.linear(name, w))
            else:
                acc.put(f"{key}_bias", idx, acc.dense(w))
        elif sub in ("input_layernorm", "post_attention_layernorm",
                     "pre_feedforward_layernorm",
                     "post_feedforward_layernorm"):
            # biased LayerNorm families (stablelm) route .bias separately
            acc.put(sub if leaf == "weight" else f"{sub}_bias", idx,
                    acc.dense(w))
        # rotary_emb.inv_freq etc. are derived, skip


def convert_hf_params(
    tensors,                      # iterable of (name, np.ndarray)
    cfg: LlamaConfig,
    qtype: Optional[str] = "sym_int4",
    compute_dtype=jnp.bfloat16,
    modules_to_not_convert: Tuple[str, ...] = (),
    imatrix=None,                 # {hf_name: importance[K]} (bigdl_tpu.imatrix)
) -> Dict[str, Any]:
    """Build the parameter pytree from HF-named tensors, quantizing linears.

    qtype=None (or a FLOAT_QTYPE) keeps dense weights in compute_dtype —
    the reference's optimize_model(low_bit=False) / BF16Linear path.
    Weights are converted tensor-by-tensor (host holds one at a time) and
    per-layer results are stacked along a leading L axis for lax.scan.
    Shares the conversion engine in models/convert_base.py with every
    other family (models/families.py).
    """
    from bigdl_tpu.models.convert_base import make_convert

    return make_convert(_llama_map)(
        tensors, cfg, qtype=qtype, compute_dtype=compute_dtype,
        modules_to_not_convert=modules_to_not_convert, imatrix=imatrix)
