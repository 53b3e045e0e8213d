"""Yuan 2.0: llama-style decoder with Localized Filtering Attention (LFA).

TPU-native re-design of the reference's yuan path (reference
transformers/models/yuan.py: `yuan_localized_filtering_forward` at :56-93,
`yuan_attention_forward_origin` at :318 — Q and K are projected from a
causally-filtered view of the normed hidden states; V from the raw normed
hidden; the filter is two cross-channel 2-tap convolutions + LayerNorm with
a residual).

The reference carries the last-2 hidden states inside its KV tuple and
runs cuDNN-style Conv2d per token. Here:
- Prefill computes the filter as two shifted MATMUL pairs
  (c1_t = x_{t-1} W1a + x_t W1b; lf_t = c1_{t-1} W2a + c1_t W2b), which is
  exactly the (2,1)-kernel Conv2d unrolled — MXU-batched over [B*S, D],
  no conv primitive needed.
- Decode carries a [L, B, 2, D] history of the last two normed hiddens in
  `YuanCache` next to the static KV cache (the analog of the reference's
  `past_key_value[2]`). Like RWKV, the family is flagged recurrent: pad
  tokens would pollute the history, so prefill runs at exact prompt
  length and speculative rollback is rejected.

Yuan's MLP applies the activation to up_proj (reference yuan.py:141:
`down(act(up(x)) * gate(x))`) — the checkpoint's up/gate are SWAPPED into
our gated-MLP slots at conversion so the one decoder body serves it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.models import llama as M
from bigdl_tpu.models.llama import LlamaConfig
from bigdl_tpu.ops.attention import sdp_attention
from bigdl_tpu.ops.kvcache import KVCache, init_cache as init_kv, \
    reject_scaled_kv, update_layer
from bigdl_tpu.ops.matmul import linear
from bigdl_tpu.ops.norms import layer_norm, rms_norm
from bigdl_tpu.ops.rope import apply_rope, rope_cos_sin


def config_from_hf(hf: Dict[str, Any]) -> LlamaConfig:
    return LlamaConfig.from_hf(hf)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class YuanCache:
    """KV cache + per-layer last-2 normed-hidden history (LFA state)."""

    kv: KVCache
    hist: jax.Array            # [L, B, 2, D] f32

    def tree_flatten(self):
        return (self.kv, self.hist), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def pos(self):
        return self.kv.pos

    @property
    def max_seq(self) -> int:
        return self.kv.max_seq


def new_cache(cfg: LlamaConfig, batch: int, max_seq: int,
              quantized=False) -> YuanCache:
    reject_scaled_kv(quantized, "yuan")
    return YuanCache(
        kv=init_kv(cfg.num_hidden_layers, batch, max_seq,
                   cfg.num_key_value_heads, cfg.hd, quantized=quantized),
        hist=jnp.zeros((cfg.num_hidden_layers, batch, 2, cfg.hidden_size),
                       jnp.float32))


def _conv_tap(prev, cur, w, b):
    """One (2,1)-kernel cross-channel conv tap: prev @ Wa + cur @ Wb + b.

    w: [D_out, D_in, 2, 1] (HF Conv2d layout, f32)."""
    wa = w[:, :, 0, 0]
    wb = w[:, :, 1, 0]
    out = (jnp.dot(prev, wa.T, preferred_element_type=jnp.float32)
           + jnp.dot(cur, wb.T, preferred_element_type=jnp.float32))
    return out + b.astype(jnp.float32)


def _lfa_prefill(xn, lp, eps):
    """Localized filtering over a full sequence. xn [B, S, D] f32."""
    shift = lambda a: jnp.concatenate(
        [jnp.zeros_like(a[:, :1]), a[:, :-1]], axis=1)
    c1 = _conv_tap(shift(xn), xn, lp["lf_conv1"], lp["lf_conv1_bias"])
    out = _conv_tap(shift(c1), c1, lp["lf_conv2"], lp["lf_conv2_bias"])
    return layer_norm(out + xn, lp["lf_norm"], lp["lf_norm_bias"], eps)


def _lfa_decode(x1, hist, lp, eps, pos):
    """One-token filter from the [B, 2, D] history. x1 [B, 1, D] f32.

    `pos` = tokens already consumed. The prefill path's shifted sequence
    has an exact ZERO for c1_{-1} (no conv bias); with an empty history
    the naive conv of zeros would inject the bias, so c1_prev is masked
    out until a real t-1 exists (pos >= 1)."""
    h0, h1 = hist[:, 0], hist[:, 1]
    x = x1[:, 0]
    c1_prev = _conv_tap(h0, h1, lp["lf_conv1"], lp["lf_conv1_bias"])
    c1_prev = jnp.where(pos >= 1, c1_prev, 0.0)
    c1_cur = _conv_tap(h1, x, lp["lf_conv1"], lp["lf_conv1_bias"])
    out = _conv_tap(c1_prev, c1_cur, lp["lf_conv2"], lp["lf_conv2_bias"])
    lf = layer_norm((out + x)[:, None, :], lp["lf_norm"],
                    lp["lf_norm_bias"], eps)
    return lf


def _layer(x, lp, cfg, cos, sin, ck, cv, lidx, pos, hist):
    b, sq, d = x.shape
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd
    eps = cfg.rms_norm_eps

    hidden = rms_norm(x, lp["input_layernorm"], eps).astype(jnp.float32)
    if sq == 1:
        lf = _lfa_decode(hidden, hist, lp, eps, pos)
        new_hist = jnp.concatenate([hist[:, 1:], hidden], axis=1)
    else:
        lf = _lfa_prefill(hidden, lp, eps)
        new_hist = hidden[:, -2:, :]

    cdt = x.dtype
    q = linear(lf.astype(cdt), lp["q_proj"]).reshape(b, sq, h, hd)
    k = linear(lf.astype(cdt), lp["k_proj"]).reshape(b, sq, hkv, hd)
    v = linear(hidden.astype(cdt), lp["v_proj"]).reshape(b, sq, hkv, hd)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    ck, cv = update_layer(ck, cv, lidx, k, v, pos)
    attn = sdp_attention(q, ck, cv, pos, layer=lidx).reshape(b, sq, h * hd)
    x = x + linear(attn, lp["o_proj"])

    hidden2 = rms_norm(x, lp["post_attention_layernorm"], eps)
    x = x + M._mlp(hidden2, lp, cfg)
    return x, ck, cv, new_hist


def forward(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jax.Array,
    cache: YuanCache,
    compute_dtype=jnp.bfloat16,
    last_only: bool = False,
) -> Tuple[jax.Array, YuanCache]:
    b, sq = tokens.shape
    pos = cache.pos
    x = M.embedding_lookup(params["embed_tokens"], tokens, compute_dtype)
    inv_freq, _ = M.model_rope_freqs(cfg)
    positions = pos + jnp.arange(sq, dtype=jnp.int32)
    cos, sin = rope_cos_sin(positions[None, :], inv_freq)

    lidx = jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)

    def step(carry, xs):
        x, ck, cv = carry
        lp, li, hist = xs
        x, ck, cv, new_hist = _layer(x, lp, cfg, cos, sin, ck, cv, li, pos,
                                     hist)
        return (x, ck, cv), new_hist

    (x, ck, cv), new_hist = lax.scan(
        step, (x, cache.kv.k, cache.kv.v),
        (params["layers"], lidx, cache.hist))

    if last_only:
        x = x[:, -1:, :]
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    logits = M._lm_head(x, params, cfg)
    return logits, YuanCache(kv=KVCache(ck, cv, pos + sq), hist=new_hist)


def forward_last_token(params, cfg, tokens, cache, compute_dtype=jnp.bfloat16):
    return forward(params, cfg, tokens, cache, compute_dtype=compute_dtype,
                   last_only=True)


def forward_train(params, cfg, tokens, compute_dtype=jnp.bfloat16,
                  attn_fn=None, pos_offset=0):
    """Cacheless forward (fresh state; LFA prefill path throughout)."""
    if attn_fn is not None:
        raise NotImplementedError(
            "yuan's localized filtering is stateful along the sequence; "
            "ring-attention sequence parallelism is not supported")
    b = tokens.shape[0]
    logits, _ = forward(params, cfg, tokens,
                        new_cache(cfg, b, int(tokens.shape[1])),
                        compute_dtype=compute_dtype)
    return logits


# -- conversion ---------------------------------------------------------------


def _yuan_map(acc, name: str, w) -> None:
    from bigdl_tpu.models.convert_base import layer_idx

    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)
    if name == "model.embed_tokens.weight":
        acc.top["embed_tokens"] = acc.dense(w)
    elif name == "model.norm.weight":
        acc.top["norm"] = acc.dense(w)
    elif name == "lm_head.weight":
        acc.top["lm_head"] = acc.linear(name, w)
    else:
        hit = layer_idx(name, "model.layers.")
        if hit is None:
            return
        idx, sub = hit
        m = {
            "self_attn.q_proj.weight": ("q_proj", "linear"),
            "self_attn.k_proj.weight": ("k_proj", "linear"),
            "self_attn.v_proj.weight": ("v_proj", "linear"),
            "self_attn.o_proj.weight": ("o_proj", "linear"),
            # activation sits on yuan's up_proj -> our gate slot
            "mlp.up_proj.weight": ("gate_proj", "linear"),
            "mlp.gate_proj.weight": ("up_proj", "linear"),
            "mlp.down_proj.weight": ("down_proj", "linear"),
            "input_layernorm.weight": ("input_layernorm", "dense"),
            "post_attention_layernorm.weight":
                ("post_attention_layernorm", "dense"),
            "self_attn.lf_gate.conv1.weight": ("lf_conv1", "f32"),
            "self_attn.lf_gate.conv1.bias": ("lf_conv1_bias", "f32"),
            "self_attn.lf_gate.conv2.weight": ("lf_conv2", "f32"),
            "self_attn.lf_gate.conv2.bias": ("lf_conv2_bias", "f32"),
            "self_attn.lf_gate.output_layernorm.weight":
                ("lf_norm", "f32"),
            "self_attn.lf_gate.output_layernorm.bias":
                ("lf_norm_bias", "f32"),
        }.get(sub)
        if m:
            key, kind = m
            if kind == "linear":
                acc.put(key, idx, acc.linear(name, w))
            elif kind == "f32":
                acc.put(key, idx, f32(w))
            else:
                acc.put(key, idx, acc.dense(w))


def convert_hf_params(tensors, cfg, qtype="sym_int4",
                      compute_dtype=jnp.bfloat16,
                      modules_to_not_convert: Tuple[str, ...] = (),
                      imatrix=None):
    from bigdl_tpu.models.convert_base import make_convert

    return make_convert(_yuan_map)(
        tensors, cfg, qtype=qtype, compute_dtype=compute_dtype,
        modules_to_not_convert=modules_to_not_convert, imatrix=imatrix)
