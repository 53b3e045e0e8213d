"""BART: text encoder-decoder (summarization / translation).

Backs the reference's `AutoModelForSeq2SeqLM` facade (reference
transformers/model.py:701 — seq2seq checkpoints quantized through the same
low-bit pipeline). Same runtime shape as models/whisper.py — encode once,
precompute per-layer cross K/V, scan-decode against a static KV cache —
but with BART's text specifics:

- POST-layer-norm blocks (norm after the residual add, original
  transformer order; whisper/llama are pre-LN),
- learned positions with the +2 offset quirk of the BART checkpoint
  format, an embedding layernorm, and every attention projection biased,
- tied lm_head = shared embedding + final_logits_bias.

`BartCache` extends the whisper cache shape (self KV + static cross K/V)
with the source padding mask so batched, padded sources cross-attend only
real tokens.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.models.bert import _masked_attention
from bigdl_tpu.ops.attention import sdp_attention
from bigdl_tpu.ops.kvcache import KVCache, init_cache as init_kv, \
    reject_scaled_kv, update_layer
from bigdl_tpu.ops.matmul import linear
from bigdl_tpu.ops.norms import layer_norm

_POS_OFFSET = 2      # BartLearnedPositionalEmbedding reserves rows 0/1


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BartCache:
    """Decoder self KV cache + static cross K/V + source padding mask."""

    self_kv: KVCache
    cross_k: jax.Array            # [Ld, B, S_enc, H, hd]
    cross_v: jax.Array
    src_mask: jax.Array           # [B, S_enc] bool (True = real token)

    def tree_flatten(self):
        return (self.self_kv, self.cross_k, self.cross_v,
                self.src_mask), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def pos(self):
        return self.self_kv.pos

    @property
    def max_seq(self) -> int:
        return self.self_kv.max_seq


@dataclasses.dataclass(frozen=True)
class BartConfig:
    vocab_size: int = 50265
    d_model: int = 768
    encoder_layers: int = 6
    decoder_layers: int = 6
    encoder_attention_heads: int = 12
    decoder_attention_heads: int = 12
    encoder_ffn_dim: int = 3072
    decoder_ffn_dim: int = 3072
    max_position_embeddings: int = 1024
    activation_function: str = "gelu"
    scale_embedding: bool = False
    layer_norm_eps: float = 1e-5
    decoder_start_token_id: int = 2
    eos_token_id: int = 2
    pad_token_id: int = 1
    forced_bos_token_id: Optional[int] = None   # bart-large-cnn style

    @property
    def hd(self) -> int:
        return self.d_model // self.decoder_attention_heads

    @classmethod
    def from_hf(cls, hf: Dict[str, Any]) -> "BartConfig":
        return cls(
            vocab_size=hf["vocab_size"],
            d_model=hf["d_model"],
            encoder_layers=hf["encoder_layers"],
            decoder_layers=hf["decoder_layers"],
            encoder_attention_heads=hf["encoder_attention_heads"],
            decoder_attention_heads=hf["decoder_attention_heads"],
            encoder_ffn_dim=hf["encoder_ffn_dim"],
            decoder_ffn_dim=hf["decoder_ffn_dim"],
            max_position_embeddings=hf.get("max_position_embeddings", 1024),
            activation_function=hf.get("activation_function", "gelu"),
            scale_embedding=hf.get("scale_embedding", False),
            decoder_start_token_id=hf.get("decoder_start_token_id", 2),
            eos_token_id=hf.get("eos_token_id", 2),
            pad_token_id=hf.get("pad_token_id", 1),
            forced_bos_token_id=hf.get("forced_bos_token_id"),
        )


def _act(cfg: BartConfig):
    import functools

    return {
        "gelu": functools.partial(jax.nn.gelu, approximate=False),
        "gelu_new": functools.partial(jax.nn.gelu, approximate=True),
        "relu": jax.nn.relu,
        "silu": jax.nn.silu,
    }[cfg.activation_function]


def _enc_attn(x, lp, h, hd, key_mask):
    """Bidirectional encoder self-attention with a key-padding mask."""
    b, s, _ = x.shape
    q = linear(x, lp["q_proj"], lp.get("q_proj_bias")).reshape(b, s, h, hd)
    k = linear(x, lp["k_proj"], lp.get("k_proj_bias")).reshape(b, s, h, hd)
    v = linear(x, lp["v_proj"], lp.get("v_proj_bias")).reshape(b, s, h, hd)
    attn = _masked_attention(q, k, v, key_mask, hd ** -0.5)
    return linear(attn.reshape(b, s, h * hd), lp["o_proj"],
                  lp.get("o_proj_bias"))


def _embed(params, cfg: BartConfig, tokens, pos_start, compute_dtype):
    x = params["shared"][tokens].astype(compute_dtype)
    if cfg.scale_embedding:
        x = x * jnp.asarray(cfg.d_model ** 0.5, compute_dtype)
    s = tokens.shape[1]
    positions = pos_start + jnp.arange(s, dtype=jnp.int32) + _POS_OFFSET
    return x, positions


def encode(params: Dict[str, Any], cfg: BartConfig,
           input_ids: jax.Array,          # [B, S] int32
           attention_mask: Optional[jax.Array] = None,   # [B, S] 1=real
           compute_dtype=jnp.bfloat16) -> jax.Array:
    """Token encoder -> [B, S, D] (bidirectional, post-LN)."""
    b, s = input_ids.shape
    if s > cfg.max_position_embeddings:
        raise ValueError(
            f"source length {s} exceeds max_position_embeddings "
            f"{cfg.max_position_embeddings} (position rows would clamp "
            "silently under jit)")
    h, hd = cfg.encoder_attention_heads, cfg.d_model // \
        cfg.encoder_attention_heads
    key_mask = (jnp.ones((b, s), bool) if attention_mask is None
                else attention_mask.astype(bool))
    x, positions = _embed(params, cfg, input_ids, 0, compute_dtype)
    x = x + params["enc_pos"][positions].astype(compute_dtype)[None]
    x = layer_norm(x, params["enc_embed_norm"],
                   params["enc_embed_norm_bias"], cfg.layer_norm_eps)

    eps = cfg.layer_norm_eps
    act = _act(cfg)

    def enc_layer(x, lp):
        a = _enc_attn(x, lp, h, hd, key_mask)
        x = layer_norm(x + a, lp["ln1"], lp["ln1_bias"], eps)
        inner = act(linear(x, lp["fc1"], lp.get("fc1_bias")))
        out = linear(inner, lp["fc2"], lp.get("fc2_bias"))
        return layer_norm(x + out, lp["ln2"], lp["ln2_bias"], eps)

    x, _ = lax.scan(lambda c, lp: (enc_layer(c, lp), None), x,
                    params["enc_layers"])
    return x


def init_decoder_cache(params: Dict[str, Any], cfg: BartConfig,
                       enc_out: jax.Array, max_seq: Optional[int] = None,
                       quantized=False,
                       src_mask: Optional[jax.Array] = None) -> BartCache:
    reject_scaled_kv(quantized, "bart")
    b, s_enc, _ = enc_out.shape
    h, hd = cfg.decoder_attention_heads, cfg.hd
    max_seq = max_seq or cfg.max_position_embeddings
    if max_seq > cfg.max_position_embeddings:
        # decode_step gathers dec_pos[pos] under jit, where an
        # out-of-range row would clamp silently; refuse here, where
        # max_seq is still static (mirrors encode()'s length check)
        raise ValueError(
            f"max_seq={max_seq} exceeds max_position_embeddings="
            f"{cfg.max_position_embeddings}: decoder positions past the "
            "learned table would silently clamp under jit")

    def proj(carry, lp):
        k = linear(enc_out, lp["cross_k_proj"],
                   lp.get("cross_k_proj_bias")).reshape(b, s_enc, h, hd)
        v = linear(enc_out, lp["cross_v_proj"],
                   lp.get("cross_v_proj_bias")).reshape(b, s_enc, h, hd)
        return carry, (k, v)

    _, (ck, cv) = lax.scan(proj, 0, params["dec_layers"])
    return BartCache(
        self_kv=init_kv(cfg.decoder_layers, b, max_seq, h, hd,
                        quantized=quantized),
        cross_k=ck, cross_v=cv,
        src_mask=(jnp.ones((b, s_enc), bool) if src_mask is None
                  else src_mask.astype(bool)))


def decode_step(
    params: Dict[str, Any],
    cfg: BartConfig,
    tokens: jax.Array,
    cache: BartCache,
    compute_dtype=jnp.bfloat16,
) -> Tuple[jax.Array, BartCache]:
    b, sq = tokens.shape
    pos = cache.self_kv.pos
    h, hd = cfg.decoder_attention_heads, cfg.hd
    eps = cfg.layer_norm_eps
    act = _act(cfg)

    x, positions = _embed(params, cfg, tokens, pos, compute_dtype)
    x = x + params["dec_pos"][positions].astype(compute_dtype)[None]
    x = layer_norm(x, params["dec_embed_norm"],
                   params["dec_embed_norm_bias"], eps)

    lidx = jnp.arange(cfg.decoder_layers, dtype=jnp.int32)

    def step(carry, xs):
        x, ck, cv = carry
        lp, li, crk, crv = xs
        q = linear(x, lp["q_proj"], lp.get("q_proj_bias")).reshape(
            b, sq, h, hd)
        k = linear(x, lp["k_proj"], lp.get("k_proj_bias")).reshape(
            b, sq, h, hd)
        v = linear(x, lp["v_proj"], lp.get("v_proj_bias")).reshape(
            b, sq, h, hd)
        ck, cv = update_layer(ck, cv, li, k, v, pos)
        a = sdp_attention(q, ck, cv, pos, layer=li).reshape(b, sq, h * hd)
        a = linear(a, lp["o_proj"], lp.get("o_proj_bias"))
        x = layer_norm(x + a, lp["ln1"], lp["ln1_bias"], eps)

        q2 = linear(x, lp["cross_q_proj"],
                    lp.get("cross_q_proj_bias")).reshape(b, sq, h, hd)
        a2 = _masked_attention(q2, crk, crv, cache.src_mask,
                               hd ** -0.5).reshape(b, sq, h * hd)
        a2 = linear(a2, lp["cross_o_proj"], lp.get("cross_o_proj_bias"))
        x = layer_norm(x + a2, lp["ln_cross"], lp["ln_cross_bias"], eps)

        inner = act(linear(x, lp["fc1"], lp.get("fc1_bias")))
        out = linear(inner, lp["fc2"], lp.get("fc2_bias"))
        x = layer_norm(x + out, lp["ln2"], lp["ln2_bias"], eps)
        return (x, ck, cv), None

    (x, ck, cv), _ = lax.scan(
        step, (x, cache.self_kv.k, cache.self_kv.v),
        (params["dec_layers"], lidx, cache.cross_k, cache.cross_v))

    logits = jnp.dot(x, params["shared"].T.astype(x.dtype),
                     preferred_element_type=jnp.float32).astype(jnp.float32)
    if "final_logits_bias" in params:
        logits = logits + params["final_logits_bias"].astype(jnp.float32)
    return logits, BartCache(
        self_kv=KVCache(ck, cv, pos + sq),
        cross_k=cache.cross_k, cross_v=cache.cross_v,
        src_mask=cache.src_mask)


# -- conversion ---------------------------------------------------------------

def convert_hf_params(
    tensors,
    cfg: BartConfig,
    qtype: Optional[str] = "sym_int4",
    compute_dtype=jnp.bfloat16,
    modules_to_not_convert: Tuple[str, ...] = (),
    imatrix=None,
) -> Dict[str, Any]:
    """Two Acc accumulators (encoder / decoder stacks) share the standard
    conversion leaf helpers (models/convert_base.py: native-kernel
    quantization preference, imatrix weighting, protection policy)."""
    from bigdl_tpu.models.convert_base import (Acc,
                                               map_encdec_layer_tensor)

    accs = {
        True: Acc.for_layer_count(cfg.encoder_layers, qtype, compute_dtype,
                                  modules_to_not_convert, imatrix=imatrix),
        False: Acc.for_layer_count(cfg.decoder_layers, qtype, compute_dtype,
                                   modules_to_not_convert, imatrix=imatrix),
    }
    top: Dict[str, Any] = {}
    dense = accs[True].dense

    for name, w in tensors:
        w = np.asarray(w)
        if map_encdec_layer_tensor(accs, name, w):
            pass
        elif name in ("model.shared.weight", "shared.weight"):
            top["shared"] = dense(w)
        elif name in ("model.encoder.embed_tokens.weight",
                      "model.decoder.embed_tokens.weight", "lm_head.weight"):
            if "shared" not in top:                # tied duplicates: skip
                top["shared"] = dense(w)           # re-uploading [V, D]
        elif name == "model.encoder.embed_positions.weight":
            top["enc_pos"] = dense(w)
        elif name == "model.decoder.embed_positions.weight":
            top["dec_pos"] = dense(w)
        elif name == "model.encoder.layernorm_embedding.weight":
            top["enc_embed_norm"] = dense(w)
        elif name == "model.encoder.layernorm_embedding.bias":
            top["enc_embed_norm_bias"] = dense(w)
        elif name == "model.decoder.layernorm_embedding.weight":
            top["dec_embed_norm"] = dense(w)
        elif name == "model.decoder.layernorm_embedding.bias":
            top["dec_embed_norm_bias"] = dense(w)
        elif name == "final_logits_bias":
            top["final_logits_bias"] = jnp.asarray(w, jnp.float32).reshape(-1)

    top["enc_layers"] = accs[True].finish(
        tie=False, lm_head_required=False, what="bart encoder")["layers"]
    top["dec_layers"] = accs[False].finish(
        tie=False, lm_head_required=False, what="bart decoder")["layers"]
    return top
