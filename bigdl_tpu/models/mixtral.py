"""Mixtral (sparse MoE) model: functional, static-shape, expert-sharded.

TPU-native re-design of the reference's Mixtral path (reference
transformers/models/mixtral.py: `mixtral_moeblock_forward` at :79-138 — a
Python loop over experts with a `.cpu().tolist()` host sync to pick the
top-k on decode, which is unacceptable on TPU). Here expert dispatch has
NO host sync and no data-dependent shapes:

- The MoE MLP is `models/llama._moe_mlp`, which picks by token count:
  few tokens in flight (decode) gather only the CHOSEN experts' weights
  per token; prefill runs the sorted ragged Pallas dispatch
  (`ops/pallas/moe_dispatch.py`) where it applies, and otherwise the
  dense formulation — all experts evaluated and combined with routing
  weights (`combine[n,e]`), which XLA maps onto batched MXU matmuls.
- Expert weights are stacked [L, E, K, N] (layer, expert leading axes on
  every QTensor leaf), so the `ep` mesh axis shards axis E and `tp` shards
  N — XLA inserts the all-to-all/psum (SURVEY.md §2.2: the reference has NO
  cross-device expert parallelism at all).

Attention/embeddings/lm_head reuse the llama module's layout exactly.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.models import llama as llama_mod
from bigdl_tpu.models.llama import LlamaConfig
from bigdl_tpu.ops.attention import sdp_attention
from bigdl_tpu.ops.kvcache import KVCache, update_layer
from bigdl_tpu.ops.matmul import linear, q_matmul
from bigdl_tpu.ops.norms import rms_norm
from bigdl_tpu.ops.quant import QTensor
from bigdl_tpu.ops.rope import apply_rope, rope_cos_sin


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_local_experts: int = 8
    num_experts_per_tok: int = 2

    @classmethod
    def from_hf(cls, hf: Dict[str, Any]) -> "MixtralConfig":
        kw = dataclasses.asdict(LlamaConfig.from_hf(hf))
        kw.pop("num_local_experts", None)   # now also LlamaConfig fields
        kw.pop("num_experts_per_tok", None)
        return cls(
            **kw,
            num_local_experts=hf.get("num_local_experts", 8),
            num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        )


# Parameter pytree layout: llama's, with the mlp keys replaced by
# {
#   "router":       [L, D, E] dense (small; kept full precision, as the
#                   reference excludes the gate from quantization),
#   "experts_gate": QTensor/dense stacked [L, E, D, F],   (HF w1)
#   "experts_up":   QTensor/dense stacked [L, E, D, F],   (HF w3)
#   "experts_down": QTensor/dense stacked [L, E, F, D],   (HF w2)
# }


def moe_block(x: jax.Array, lp: Dict[str, Any], cfg: MixtralConfig) -> jax.Array:
    """Sparse-MoE MLP: route, evaluate experts, one-hot combine. [B,T,D].

    One implementation serves every MoE family: the generalized decoder's
    `_moe_mlp` (models/llama.py) handles mixtral's gated expert layout
    (cfg.mlp_gated=True) and phixtral's dense fc1/fc2 experts."""
    return llama_mod._moe_mlp(x, lp, cfg)


def _layer_step(cfg: MixtralConfig, carry, xs):
    x, ck, cv, cks, cvs, pos, cos, sin = carry
    lp, lidx = xs
    b, sq, d = x.shape
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd

    hidden = rms_norm(x, lp["input_layernorm"], cfg.rms_norm_eps)
    q = linear(hidden, lp["q_proj"]).reshape(b, sq, h, hd)
    k = linear(hidden, lp["k_proj"]).reshape(b, sq, hkv, hd)
    v = linear(hidden, lp["v_proj"]).reshape(b, sq, hkv, hd)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if cks is not None:   # block-scaled int8/int4 storage (see llama)
        ck, cv, cks, cvs = update_layer(ck, cv, lidx, k, v, pos, cks, cvs)
    else:
        ck, cv = update_layer(ck, cv, lidx, k, v, pos)
    attn = sdp_attention(q, ck, cv, pos, sliding_window=cfg.sliding_window,
                         k_scale=cks, v_scale=cvs, layer=lidx)
    x = x + linear(attn.reshape(b, sq, h * hd), lp["o_proj"])

    hidden = rms_norm(x, lp["post_attention_layernorm"], cfg.rms_norm_eps)
    x = x + moe_block(hidden, lp, cfg)
    return (x, ck, cv, cks, cvs, pos, cos, sin), None


def forward(
    params: Dict[str, Any],
    cfg: MixtralConfig,
    tokens: jax.Array,
    cache: KVCache,
    compute_dtype=jnp.bfloat16,
    last_only: bool = False,
) -> Tuple[jax.Array, KVCache]:
    b, sq = tokens.shape
    pos = cache.pos
    x = llama_mod.embedding_lookup(params["embed_tokens"], tokens,
                                   compute_dtype)
    inv_freq, rope_mscale = llama_mod.model_rope_freqs(cfg)
    if getattr(pos, "ndim", 0) == 1:   # per-slot positions (serving)
        positions = pos[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
        cos, sin = rope_cos_sin(positions, inv_freq)
    else:
        positions = pos + jnp.arange(sq, dtype=jnp.int32)
        cos, sin = rope_cos_sin(positions[None, :], inv_freq)
    if rope_mscale != 1.0:
        cos, sin = cos * rope_mscale, sin * rope_mscale

    lidx = jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)
    (x, ck, cv, cks, cvs, _, _, _), _ = lax.scan(
        lambda c, xs: _layer_step(cfg, c, xs),
        (x, cache.k, cache.v, cache.k_scale, cache.v_scale, pos, cos, sin),
        (params["layers"], lidx),
    )

    if last_only:
        x = x[:, -1:, :]
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    lm_head = params.get("lm_head")
    if lm_head is None:
        logits = jnp.dot(x, params["embed_tokens"].T.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    else:
        logits = linear(x, lm_head)
    return logits.astype(jnp.float32), KVCache(ck, cv, pos + sq, cks, cvs)


def forward_last_token(params, cfg, tokens, cache, compute_dtype=jnp.bfloat16):
    return forward(params, cfg, tokens, cache, compute_dtype=compute_dtype,
                   last_only=True)


def forward_train(
    params: Dict[str, Any],
    cfg: MixtralConfig,
    tokens: jax.Array,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """Cacheless causal forward (QLoRA finetuning of MoE models)."""
    b, s = tokens.shape
    x = llama_mod.embedding_lookup(params["embed_tokens"], tokens,
                                   compute_dtype)
    inv_freq, rope_mscale = llama_mod.model_rope_freqs(cfg)
    cos, sin = rope_cos_sin(jnp.arange(s, dtype=jnp.int32)[None, :], inv_freq)
    if rope_mscale != 1.0:
        cos, sin = cos * rope_mscale, sin * rope_mscale
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd

    @jax.checkpoint
    def layer(x, lp):
        hidden = rms_norm(x, lp["input_layernorm"], cfg.rms_norm_eps)
        q = apply_rope(linear(hidden, lp["q_proj"]).reshape(b, s, h, hd),
                       cos, sin)
        k = apply_rope(linear(hidden, lp["k_proj"]).reshape(b, s, hkv, hd),
                       cos, sin)
        v = linear(hidden, lp["v_proj"]).reshape(b, s, hkv, hd)
        attn = sdp_attention(q, k, v, jnp.zeros((), jnp.int32),
                             sliding_window=cfg.sliding_window)
        x = x + linear(attn.reshape(b, s, h * hd), lp["o_proj"])
        hidden = rms_norm(x, lp["post_attention_layernorm"], cfg.rms_norm_eps)
        return x + moe_block(hidden, lp, cfg)

    x, _ = lax.scan(lambda c, lp: (layer(c, lp), None), x, params["layers"])
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    lm_head = params.get("lm_head")
    if lm_head is None:
        logits = jnp.dot(x, params["embed_tokens"].T.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    else:
        logits = linear(x, lm_head)
    return logits.astype(jnp.float32)


SUPPORTS_SCALED_KV = True   # scale planes threaded through _layer_step


def new_cache(cfg: MixtralConfig, batch: int, max_seq: int,
              quantized=False) -> KVCache:
    return llama_mod.new_cache(cfg, batch, max_seq, quantized)


def convert_hf_params(
    tensors,
    cfg: MixtralConfig,
    qtype: Optional[str] = "sym_int4",
    compute_dtype=jnp.bfloat16,
    modules_to_not_convert: Tuple[str, ...] = (),
    imatrix=None,     # {hf_name: importance[K]} (bigdl_tpu.imatrix)
) -> Dict[str, Any]:
    """HF MixtralForCausalLM tensors -> stacked [L, E, ...] pytree.

    HF names: model.layers.N.block_sparse_moe.gate.weight [E, D];
    experts.M.{w1,w3} [F, D] (gate/up), w2 [D, F] (down). The router stays
    dense (the reference also leaves the tiny gate unquantized in practice
    via modules_to_not_convert). Like the Acc-based families, an imatrix
    weights the quantization and ultra-low-bit loads apply the per-tensor
    protection policy (bigdl_tpu.imatrix.low_bit_policy) — MoE is the
    main consumer of those formats (the reference's "Mixtral on 16 GB"
    IQ2 claim, README.md:16).
    """
    from bigdl_tpu.imatrix import imatrix_lookup, low_bit_policy
    from bigdl_tpu.ops.quant import FLOAT_QTYPES, quantize_linear

    L, E = cfg.num_hidden_layers, cfg.num_local_experts
    do_quant = qtype is not None and qtype not in FLOAT_QTYPES

    def cvt_linear(name, w):
        w = jnp.asarray(np.asarray(w))
        if do_quant and not any(m in name for m in modules_to_not_convert):
            qw = imatrix_lookup(imatrix, name)
            if qw is not None and len(qw) != w.shape[1]:
                qw = None
            return quantize_linear(w, low_bit_policy(qtype, name), qw=qw)
        return w.T.astype(compute_dtype)

    attn_keys = {"self_attn.q_proj": "q_proj", "self_attn.k_proj": "k_proj",
                 "self_attn.v_proj": "v_proj", "self_attn.o_proj": "o_proj"}
    expert_keys = {"w1": "experts_gate", "w3": "experts_up",
                   "w2": "experts_down"}

    layer_acc: Dict[str, list] = {}
    params: Dict[str, Any] = {}

    def put(key, idx, val):
        layer_acc.setdefault(key, [None] * L)[idx] = val

    def put_expert(key, lidx, eidx, val):
        slot = layer_acc.setdefault(key, [None] * L)
        if slot[lidx] is None:
            slot[lidx] = [None] * E
        slot[lidx][eidx] = val

    for name, w in tensors:
        if name == "model.embed_tokens.weight":
            params["embed_tokens"] = jnp.asarray(np.asarray(w)).astype(
                compute_dtype)
        elif name == "model.norm.weight":
            params["norm"] = jnp.asarray(np.asarray(w)).astype(compute_dtype)
        elif name == "lm_head.weight":
            params["lm_head"] = cvt_linear(name, w)
        elif name.startswith("model.layers."):
            parts = name.split(".")
            idx = int(parts[2])
            sub = ".".join(parts[3:-1])
            if sub in attn_keys:
                put(attn_keys[sub], idx, cvt_linear(name, w))
            elif sub in ("input_layernorm", "post_attention_layernorm"):
                put(sub, idx,
                    jnp.asarray(np.asarray(w)).astype(compute_dtype))
            elif sub == "block_sparse_moe.gate":
                put("router", idx,
                    jnp.asarray(np.asarray(w)).T.astype(compute_dtype))
            elif sub.startswith("block_sparse_moe.experts."):
                eidx = int(sub.split(".")[2])
                wname = sub.split(".")[3]
                put_expert(expert_keys[wname], idx, eidx,
                           cvt_linear(name, w))

    missing = [k for k, v in layer_acc.items()
               if any(x is None for x in v)
               or (k.startswith("experts_")
                   and any(e is None for x in v for e in x))]
    if missing:
        raise ValueError(f"checkpoint missing layer tensors for: {missing}")

    layers: Dict[str, Any] = {}
    for key, per_layer in layer_acc.items():
        if key.startswith("experts_"):
            stacked_e = [jax.tree.map(lambda *xs: jnp.stack(xs), *experts)
                         for experts in per_layer]
            layers[key] = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked_e)
        else:
            layers[key] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    params["layers"] = layers

    if cfg.tie_word_embeddings:
        params.pop("lm_head", None)
    elif "lm_head" not in params:
        raise ValueError("checkpoint has no lm_head.weight")
    return params
