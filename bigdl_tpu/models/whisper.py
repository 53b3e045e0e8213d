"""Whisper: encoder-decoder (speech-to-text) family.

The reference quantizes Whisper through its generic `optimize_model` API and
ships an `AutoModelForSpeechSeq2Seq` facade (reference optimize.py:196 —
"quantize ANY nn.Module (Whisper, LLaVA...)"; transformers/model.py:688-725
Auto classes; test/inference/test_optimize_model_api.py exercises whisper).
This is the TPU-native counterpart: a functional encoder-decoder built from
the same ops as the decoder-only families.

Design notes:
- The audio encoder (2x conv + bidirectional transformer) runs ONCE per
  utterance as a single jit; its output feeds a per-layer cross K/V cache
  computed once (`init_cache`) so the decode loop never re-projects
  encoder states — the encoder-decoder analog of prefill.
- The decoder is the same scan-over-layers + static KV cache pattern as
  models/llama.py, with a second (static) cross-attention read per layer.
  Bidirectional/cross attention reuses `sdp_attention` with q_pos = S_kv
  (every key visible), so there is exactly one attention op in the
  framework.
- Whisper uses learned absolute positions (no RoPE) and pre-LN blocks;
  k_proj carries no bias (HF WhisperAttention convention).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.ops.attention import sdp_attention
from bigdl_tpu.ops.kvcache import KVCache, init_cache as init_kv, \
    reject_scaled_kv, update_layer
from bigdl_tpu.ops.matmul import linear
from bigdl_tpu.ops.norms import layer_norm


@dataclasses.dataclass(frozen=True)
class WhisperConfig:
    vocab_size: int = 51865
    num_mel_bins: int = 80
    d_model: int = 384
    encoder_layers: int = 4
    encoder_attention_heads: int = 6
    decoder_layers: int = 4
    decoder_attention_heads: int = 6
    encoder_ffn_dim: int = 1536
    decoder_ffn_dim: int = 1536
    max_source_positions: int = 1500
    max_target_positions: int = 448
    layer_norm_eps: float = 1e-5
    decoder_start_token_id: int = 50257
    eos_token_id: int = 50256

    @property
    def hd(self) -> int:
        return self.d_model // self.decoder_attention_heads

    @classmethod
    def from_hf(cls, hf: Dict[str, Any]) -> "WhisperConfig":
        return cls(
            vocab_size=hf["vocab_size"],
            num_mel_bins=hf.get("num_mel_bins", 80),
            d_model=hf["d_model"],
            encoder_layers=hf["encoder_layers"],
            encoder_attention_heads=hf["encoder_attention_heads"],
            decoder_layers=hf["decoder_layers"],
            decoder_attention_heads=hf["decoder_attention_heads"],
            encoder_ffn_dim=hf["encoder_ffn_dim"],
            decoder_ffn_dim=hf["decoder_ffn_dim"],
            max_source_positions=hf.get("max_source_positions", 1500),
            max_target_positions=hf.get("max_target_positions", 448),
            decoder_start_token_id=hf.get("decoder_start_token_id", 50257),
            eos_token_id=hf.get("eos_token_id", 50256),
        )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class WhisperCache:
    """Decoder self-attention KV cache + per-layer cross K/V (static)."""

    self_kv: KVCache                  # [Ld, B, Tmax, H, hd]
    cross_k: jax.Array                # [Ld, B, S_enc, H, hd]
    cross_v: jax.Array

    def tree_flatten(self):
        return (self.self_kv, self.cross_k, self.cross_v), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def pos(self):
        return self.self_kv.pos

    @property
    def max_seq(self) -> int:
        return self.self_kv.max_seq


# -- encoder -----------------------------------------------------------------


def _conv1d(x: jax.Array, w: jax.Array, b: jax.Array,
            stride: int) -> jax.Array:
    """x [B, C, T], w [O, C, 3] -> [B, O, T//stride] (SAME-ish pad=1)."""
    y = lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride,), padding=((1, 1),),
        dimension_numbers=("NCH", "OIH", "NCH"))
    return y + b.astype(jnp.float32)[None, :, None]


def _enc_layer(x, lp, cfg: WhisperConfig):
    h, hd = cfg.encoder_attention_heads, cfg.d_model // \
        cfg.encoder_attention_heads
    b, s, _ = x.shape
    hidden = layer_norm(x, lp["ln1"], lp["ln1_bias"], cfg.layer_norm_eps)
    q = linear(hidden, lp["q_proj"], lp.get("q_proj_bias")).reshape(
        b, s, h, hd)
    k = linear(hidden, lp["k_proj"]).reshape(b, s, h, hd)
    v = linear(hidden, lp["v_proj"], lp.get("v_proj_bias")).reshape(
        b, s, h, hd)
    # q_pos = S -> every key visible (bidirectional)
    attn = sdp_attention(q, k, v, jnp.asarray(s, jnp.int32)).reshape(
        b, s, h * hd)
    x = x + linear(attn, lp["o_proj"], lp.get("o_proj_bias"))
    hidden = layer_norm(x, lp["ln2"], lp["ln2_bias"], cfg.layer_norm_eps)
    inner = jax.nn.gelu(linear(hidden, lp["fc1"], lp.get("fc1_bias")),
                        approximate=False)
    return x + linear(inner, lp["fc2"], lp.get("fc2_bias"))


def encode(params: Dict[str, Any], cfg: WhisperConfig,
           input_features: jax.Array,     # [B, n_mels, T]
           compute_dtype=jnp.bfloat16) -> jax.Array:
    """Audio features -> encoder states [B, T//2, D]."""
    x = jax.nn.gelu(_conv1d(input_features, params["enc_conv1_w"],
                            params["enc_conv1_b"], 1), approximate=False)
    x = jax.nn.gelu(_conv1d(x, params["enc_conv2_w"],
                            params["enc_conv2_b"], 2), approximate=False)
    x = x.transpose(0, 2, 1).astype(compute_dtype)        # [B, S, D]
    s = x.shape[1]
    x = x + params["enc_pos"][:s].astype(compute_dtype)[None]
    x, _ = lax.scan(lambda c, lp: (_enc_layer(c, lp, cfg), None), x,
                    params["enc_layers"])
    return layer_norm(x, params["enc_norm"], params["enc_norm_bias"],
                      cfg.layer_norm_eps)


# -- decoder -----------------------------------------------------------------


def init_decoder_cache(params: Dict[str, Any], cfg: WhisperConfig,
                       enc_out: jax.Array, max_seq: Optional[int] = None,
                       quantized=False) -> WhisperCache:
    """Allocate the self KV cache and precompute cross K/V per layer."""
    reject_scaled_kv(quantized, "whisper")
    b, s_enc, _ = enc_out.shape
    h, hd = cfg.decoder_attention_heads, cfg.hd
    max_seq = max_seq or cfg.max_target_positions
    if max_seq > cfg.max_target_positions:
        # decode_step gathers dec_pos[pos] under jit, where an
        # out-of-range row would clamp silently; refuse while static
        raise ValueError(
            f"max_seq={max_seq} exceeds max_target_positions="
            f"{cfg.max_target_positions}: decoder positions past the "
            "learned table would silently clamp under jit")

    def proj(carry, lp):
        k = linear(enc_out, lp["cross_k_proj"]).reshape(b, s_enc, h, hd)
        v = linear(enc_out, lp["cross_v_proj"],
                   lp.get("cross_v_proj_bias")).reshape(b, s_enc, h, hd)
        return carry, (k, v)

    _, (ck, cv) = lax.scan(proj, 0, params["dec_layers"])
    return WhisperCache(
        self_kv=init_kv(cfg.decoder_layers, b, max_seq, h, hd,
                        quantized=quantized),
        cross_k=ck, cross_v=cv)


def _dec_layer(x, lp, cfg: WhisperConfig, ck, cv, cross_k, cross_v,
               lidx, pos):
    h, hd = cfg.decoder_attention_heads, cfg.hd
    b, sq, _ = x.shape
    s_enc = cross_k.shape[1]

    hidden = layer_norm(x, lp["ln1"], lp["ln1_bias"], cfg.layer_norm_eps)
    q = linear(hidden, lp["q_proj"], lp.get("q_proj_bias")).reshape(
        b, sq, h, hd)
    k = linear(hidden, lp["k_proj"]).reshape(b, sq, h, hd)
    v = linear(hidden, lp["v_proj"], lp.get("v_proj_bias")).reshape(
        b, sq, h, hd)
    ck, cv = update_layer(ck, cv, lidx, k, v, pos)
    attn = sdp_attention(q, ck, cv, pos, layer=lidx).reshape(b, sq, h * hd)
    x = x + linear(attn, lp["o_proj"], lp.get("o_proj_bias"))

    hidden = layer_norm(x, lp["ln_cross"], lp["ln_cross_bias"],
                        cfg.layer_norm_eps)
    q = linear(hidden, lp["cross_q_proj"],
               lp.get("cross_q_proj_bias")).reshape(b, sq, h, hd)
    attn = sdp_attention(q, cross_k, cross_v,
                         jnp.asarray(s_enc, jnp.int32)).reshape(b, sq, h * hd)
    x = x + linear(attn, lp["cross_o_proj"], lp.get("cross_o_proj_bias"))

    hidden = layer_norm(x, lp["ln2"], lp["ln2_bias"], cfg.layer_norm_eps)
    inner = jax.nn.gelu(linear(hidden, lp["fc1"], lp.get("fc1_bias")),
                        approximate=False)
    return x + linear(inner, lp["fc2"], lp.get("fc2_bias")), (ck, cv)


def decode_step(
    params: Dict[str, Any],
    cfg: WhisperConfig,
    tokens: jax.Array,        # [B, Sq] int32
    cache: WhisperCache,
    compute_dtype=jnp.bfloat16,
) -> Tuple[jax.Array, WhisperCache]:
    """Decoder forward (prefill Sq = forced tokens, decode Sq = 1)."""
    b, sq = tokens.shape
    pos = cache.self_kv.pos
    emb = params["dec_embed"]
    x = emb[tokens].astype(compute_dtype)
    positions = pos + jnp.arange(sq, dtype=jnp.int32)
    x = x + params["dec_pos"][positions].astype(compute_dtype)[None]

    lidx = jnp.arange(cfg.decoder_layers, dtype=jnp.int32)

    def step(carry, xs):
        x, ck, cv = carry
        lp, li, crk, crv = xs
        x, (ck, cv) = _dec_layer(x, lp, cfg, ck, cv, crk, crv, li, pos)
        return (x, ck, cv), None

    (x, ck, cv), _ = lax.scan(
        step, (x, cache.self_kv.k, cache.self_kv.v),
        (params["dec_layers"], lidx, cache.cross_k, cache.cross_v))

    x = layer_norm(x, params["dec_norm"], params["dec_norm_bias"],
                   cfg.layer_norm_eps)
    logits = jnp.dot(x, emb.T.astype(x.dtype),
                     preferred_element_type=jnp.float32).astype(jnp.float32)
    return logits, WhisperCache(
        self_kv=KVCache(ck, cv, pos + sq),
        cross_k=cache.cross_k, cross_v=cache.cross_v)


# -- conversion ---------------------------------------------------------------


def convert_hf_params(
    tensors,
    cfg: WhisperConfig,
    qtype: Optional[str] = "sym_int4",
    compute_dtype=jnp.bfloat16,
    modules_to_not_convert: Tuple[str, ...] = (),
    imatrix=None,
) -> Dict[str, Any]:
    """HF WhisperForConditionalGeneration tensors -> pytree.

    Linears quantize (imatrix-weighted when given); convs, embeddings and
    norms stay dense. Two Acc accumulators (encoder / decoder stacks)
    share the standard conversion leaf helpers (models/convert_base.py:
    native-kernel preference, imatrix weighting, protection policy) —
    same structure as models/bart.py.
    """
    from bigdl_tpu.models.convert_base import (Acc,
                                               map_encdec_layer_tensor)

    accs = {
        True: Acc.for_layer_count(cfg.encoder_layers, qtype, compute_dtype,
                                  modules_to_not_convert, imatrix=imatrix),
        False: Acc.for_layer_count(cfg.decoder_layers, qtype, compute_dtype,
                                   modules_to_not_convert, imatrix=imatrix),
    }
    dense = accs[True].dense
    f32 = lambda w: jnp.asarray(np.asarray(w), jnp.float32)

    top: Dict[str, Any] = {}

    for name, w in tensors:
        w = np.asarray(w)
        if map_encdec_layer_tensor(accs, name, w):
            pass
        elif name == "model.encoder.conv1.weight":
            top["enc_conv1_w"] = f32(w)
        elif name == "model.encoder.conv1.bias":
            top["enc_conv1_b"] = f32(w)
        elif name == "model.encoder.conv2.weight":
            top["enc_conv2_w"] = f32(w)
        elif name == "model.encoder.conv2.bias":
            top["enc_conv2_b"] = f32(w)
        elif name == "model.encoder.embed_positions.weight":
            top["enc_pos"] = dense(w)
        elif name == "model.encoder.layer_norm.weight":
            top["enc_norm"] = dense(w)
        elif name == "model.encoder.layer_norm.bias":
            top["enc_norm_bias"] = dense(w)
        elif name in ("model.decoder.embed_tokens.weight",
                      "proj_out.weight"):
            top["dec_embed"] = dense(w)
        elif name == "model.decoder.embed_positions.weight":
            top["dec_pos"] = dense(w)
        elif name == "model.decoder.layer_norm.weight":
            top["dec_norm"] = dense(w)
        elif name == "model.decoder.layer_norm.bias":
            top["dec_norm_bias"] = dense(w)

    top["enc_layers"] = accs[True].finish(
        tie=False, lm_head_required=False,
        what="whisper encoder")["layers"]
    top["dec_layers"] = accs[False].finish(
        tie=False, lm_head_required=False,
        what="whisper decoder")["layers"]
    return top
