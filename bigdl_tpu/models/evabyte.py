"""EvaByte: a byte-level decoder with EVA chunked linearized attention
(HF `model_type` "evabyte", `attention_class` "eva"; Zheng et al., ICLR
2023) and eight prediction heads.

32 equal pre-norm layers. `RMSNorm(x) = x / rms(x) * (1 + g)`
(`norm_add_unit_offset`); the residual stream is float32
(`fp32_skip_add`). Attention: q, k, v of 32 heads of 128 (no grouping),
rope on the whole head (rotate-half), then `ops/eva.py`: every query
attends, in one softmax, the exact keys of its own window of 2048
positions up to itself and one learned summary a 16-position chunk of
every earlier window. Two learned vectors a head and layer make the
summaries: `adaptive_phi` (the chunk's softmax weights) and
`adaptive_mu_k` (added to the summary key). SwiGLU feed-forward. Head:
final norm, `[D, num_pred_heads * vocab_size]`; head `p` (columns `p *
V .. (p + 1) * V`) predicts the byte at `t + 1 + p`. `forward` computes
all of it and returns head 0's `V` logits: one byte a step (drafting from
heads 1-7 and verifying is not built); `all_heads=True` returns every
column.

The cache (`cache_spec`): `win_k` / `win_v` `[L, B, window, H, hd]` and
`sum_k` / `sum_v` `[L, B, max_seq / chunk, H, hd]`, bf16 only. A cache of
one slot at the published sizes and 8,192 positions is 1.34 GB where K
and V of every position would be 4.29 GB.

Params (one leaf a layer, no stack). The 32 layers are ONE traced body,
`_layer`, called 32 times with the layer's leaves and its index as a
traced scalar (the kernels take it as a prefetched scalar): a program
traces and lowers one layer, not 32.

{
  "embed_tokens": [V, D],
  "layers": (one dict a layer:
      input_layernorm, post_attention_layernorm [D] (the gain `g`, the
      unit offset is added in `forward`), qkv_proj [D, 3 * H * hd],
      o_proj, gate_up_proj [D, 2 * F], down_proj, adaptive_phi,
      adaptive_mu_k [H, hd]),
  "norm": [D],
  "lm_head": [D, num_pred_heads * V],
}
Before `prepare_params` a layer holds q_proj / k_proj / v_proj and
gate_proj / up_proj apart: the canonical tree, which the benchmark's
reference reads.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.models.llama import embedding_lookup
from bigdl_tpu.ops.eva import eva_attention
from bigdl_tpu.ops.kvcache import (CacheSpec, KVCache, PlaneSpec,
                                   init_cache_spec)
from bigdl_tpu.ops.matmul import linear
from bigdl_tpu.ops.norms import rms_norm
from bigdl_tpu.ops.rope import apply_rope, rope_cos_sin, rope_freqs


# a pad token written past a prompt or a position rewound would be
# absorbed into a summary (`registry.FamilyAdapter.rewindable`)
CACHE_REWINDABLE = False


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    num_pred_heads: int = 8
    window_size: int = 2048
    chunk_size: int = 16
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    max_position_embeddings: int = 32768
    tie_word_embeddings: bool = False

    @classmethod
    def from_hf(cls, hf: Dict[str, Any]) -> "EvaByteConfig":
        if hf.get("attention_class", "eva") != "eva":
            raise NotImplementedError(
                f"attention_class {hf['attention_class']!r}: only 'eva'")
        if hf.get("rope_scaling"):
            raise NotImplementedError("EvaByte with rope_scaling")
        if hf.get("num_key_value_heads", hf["num_attention_heads"]) \
                != hf["num_attention_heads"]:
            raise NotImplementedError("EvaByte with grouped key heads")
        if hf.get("tie_word_embeddings"):
            raise NotImplementedError("EvaByte with a tied head")
        names = {f.name for f in dataclasses.fields(cls)}
        cfg = cls(**{k: v for k, v in hf.items() if k in names})
        if cfg.window_size % cfg.chunk_size:
            raise ValueError("window_size must be a multiple of chunk_size")
        return cfg

    # what cost models and the generic engine read off a config
    @property
    def hd(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def sliding_window(self):
        return None

    def matmul_flops_per_token(self) -> int:
        d, f = self.hidden_size, self.intermediate_size
        return 2 * (self.num_hidden_layers * (4 * d * d + 3 * d * f)
                    + d * self.num_pred_heads * self.vocab_size)


def cache_spec(cfg: EvaByteConfig) -> CacheSpec:
    kv = (cfg.num_key_value_heads, cfg.hd)
    n = cfg.num_hidden_layers
    return CacheSpec("kv", n, *kv, planes=(
        PlaneSpec("sum_k", n, kv, stride=cfg.chunk_size),
        PlaneSpec("sum_v", n, kv, stride=cfg.chunk_size),
        PlaneSpec("win_k", n, kv, window=cfg.window_size),
        PlaneSpec("win_v", n, kv, window=cfg.window_size)))


def new_cache(cfg: EvaByteConfig, batch: int, max_seq: int,
              quantized=False) -> KVCache:
    """The four planes; bf16 only (`kvcache.reject_non_bf16_strided`)."""
    return init_cache_spec(cache_spec(cfg), batch, max_seq,
                           kv_cache_dtype=quantized)


def _norm(x, gain, eps: float):
    """`x / rms(x) * (1 + g)` in float32, bf16 out."""
    return rms_norm(x, gain.astype(jnp.float32) + 1.0, eps).astype(
        jnp.bfloat16)


def swiglu(x, lp):
    f = lp["down_proj"].shape[0]
    gu = linear(x, lp["gate_up_proj"])
    return linear(jax.nn.silu(gu[..., :f]) * gu[..., f:], lp["down_proj"])


def attention_block(y, lp, cfg: EvaByteConfig, cache: KVCache, layer,
                    cos, sin):
    """One layer's attention, before the residual: the normed `y` `[B,
    sq, D]` through layer `layer` (an int or a traced int32 scalar) of
    `cache`'s planes at `cache.pos`.
    Returns the output `[B, sq, D]` and the cache with the planes
    written (`pos` as it was: `forward` advances it once)."""
    b, sq, _ = y.shape
    h, hd = cfg.num_attention_heads, cfg.hd
    qkv = linear(y, lp["qkv_proj"]).reshape(b, sq, 3, h, hd)
    q = apply_rope(qkv[:, :, 0], cos, sin)
    k = apply_rope(qkv[:, :, 1], cos, sin)
    o, wk, wv, sk, sv = eva_attention(
        q, k, qkv[:, :, 2], cache.win_k, cache.win_v, cache.sum_k,
        cache.sum_v, layer, cache.pos, lp["adaptive_phi"],
        lp["adaptive_mu_k"], scale=hd ** -0.5, stride=cfg.chunk_size)
    out = linear(o.reshape(b, sq, h * hd), lp["o_proj"])
    return out, cache.replace(win_k=wk, win_v=wv, sum_k=sk, sum_v=sv)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _layer(x, lp, cache: KVCache, layer, cos, sin, *, cfg: EvaByteConfig):
    """One layer on the float32 residual stream `x`. Jitted with `layer`
    traced, so every layer of a program is a call of one body."""
    eps = cfg.rms_norm_eps
    a, cache = attention_block(_norm(x, lp["input_layernorm"], eps), lp,
                               cfg, cache, layer, cos, sin)
    x = x + a.astype(jnp.float32)
    x = x + swiglu(_norm(x, lp["post_attention_layernorm"], eps),
                   lp).astype(jnp.float32)
    return x, cache


def rope_table(cfg: EvaByteConfig, pos, sq: int):
    """cos and sin `[B or 1, sq, hd / 2]` of `pos .. pos + sq - 1`."""
    if getattr(pos, "ndim", 0) == 1:
        positions = pos[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    else:
        positions = (pos + jnp.arange(sq, dtype=jnp.int32))[None, :]
    return rope_cos_sin(positions, rope_freqs(cfg.hd, cfg.rope_theta))


def forward(
    params: Dict[str, Any],
    cfg: EvaByteConfig,
    tokens: jax.Array,
    cache: KVCache,
    compute_dtype=jnp.bfloat16,
    last_only: bool = False,
    all_heads: bool = False,
) -> Tuple[jax.Array, KVCache]:
    """Logits `[B, sq, V]` of head 0 (`all_heads`: `[B, sq,
    num_pred_heads * V]`) and the cache `sq` positions on. A slot whose
    `pos` is below 0 (serving: it holds no request) attends nothing and
    comes back at `pos + sq`."""
    b, sq = tokens.shape
    pos = cache.pos
    eps = cfg.rms_norm_eps
    cos, sin = rope_table(cfg, pos, sq)
    x = embedding_lookup(params["embed_tokens"], tokens,
                         compute_dtype).astype(jnp.float32)
    for i, lp in enumerate(params["layers"]):
        x, cache = _layer(x, lp, cache, jnp.int32(i), cos, sin, cfg=cfg)
    if last_only:
        x = x[:, -1:, :]
    logits = linear(_norm(x, params["norm"], eps),
                    params["lm_head"]).astype(jnp.float32)
    if not all_heads:
        logits = logits[..., :cfg.vocab_size]
    return logits, cache.replace(pos=pos + sq)


def forward_last_token(params, cfg, tokens, cache,
                       compute_dtype=jnp.bfloat16):
    return forward(params, cfg, tokens, cache, compute_dtype=compute_dtype,
                   last_only=True)


# ---------------------------------------------------------------------------
# canonical tree -> served tree, and HF checkpoint -> canonical tree
# ---------------------------------------------------------------------------

def prepare_layer(lp: Dict[str, Any]) -> Dict[str, Any]:
    """One canonical layer as `forward` serves it: q / k / v and gate /
    up merged (block quantization is per column: bit-exact). A prepared
    layer passes through."""
    from bigdl_tpu.ops.quant import QTensor, concat_qtensors_n

    if "qkv_proj" in lp:
        return lp

    def concat(ws):
        if isinstance(ws[0], QTensor):
            return concat_qtensors_n(ws)
        return jnp.concatenate(ws, axis=-1)

    lp = dict(lp)
    lp["qkv_proj"] = concat([lp.pop(n) for n in
                             ("q_proj", "k_proj", "v_proj")])
    lp["gate_up_proj"] = concat([lp.pop(n) for n in
                                 ("gate_proj", "up_proj")])
    return lp


def prepare_params(params: Dict[str, Any], cfg: EvaByteConfig = None
                   ) -> Dict[str, Any]:
    out = dict(params)
    out["layers"] = tuple(prepare_layer(lp) for lp in params["layers"])
    return out


_LINEARS = {"self_attn.q_proj": "q_proj", "self_attn.k_proj": "k_proj",
            "self_attn.v_proj": "v_proj", "self_attn.o_proj": "o_proj",
            "mlp.gate_proj": "gate_proj", "mlp.up_proj": "up_proj",
            "mlp.down_proj": "down_proj"}
_VECTORS = {"input_layernorm.weight": "input_layernorm",
            "post_attention_layernorm.weight": "post_attention_layernorm"}
_HEAD_VECTORS = {"self_attn.adaptive_phi": "adaptive_phi",
                 "self_attn.adaptive_mu_k": "adaptive_mu_k"}


def convert_hf_params(
    tensors,
    cfg: EvaByteConfig,
    qtype: Optional[str] = "sym_int4",
    compute_dtype=jnp.bfloat16,
    modules_to_not_convert: Tuple[str, ...] = (),
    imatrix=None,
) -> Dict[str, Any]:
    """HF tensors -> the served tree, under the tensor names of the
    public `modeling_evabyte.py` (`self_attn.adaptive_phi` /
    `adaptive_mu_k` of shape `[1, H, 1, hd]`): ASSUMED, no checkpoint of
    this model has been read here. The norms and the two learned
    vectors stay unquantized."""
    import numpy as np

    from bigdl_tpu.ops.quant import quantize

    del imatrix
    h, hd = cfg.num_attention_heads, cfg.hd

    def lin(name, w):
        w = jnp.asarray(np.asarray(w, np.float32).T)
        if qtype is None or any(m in name for m in modules_to_not_convert):
            return w.astype(compute_dtype)
        return quantize(w, qtype)

    def vec(w):
        return jnp.asarray(np.asarray(w, np.float32)).astype(compute_dtype)

    layers = []
    for i in range(cfg.num_hidden_layers):
        pre = f"model.layers.{i}."
        lp = {ours: lin(theirs, tensors[f"{pre}{theirs}.weight"])
              for theirs, ours in _LINEARS.items()}
        lp.update({ours: vec(tensors[pre + theirs])
                   for theirs, ours in _VECTORS.items()})
        lp.update({ours: jnp.asarray(np.asarray(
            tensors[pre + theirs], np.float32).reshape(h, hd))
            for theirs, ours in _HEAD_VECTORS.items()})
        layers.append(prepare_layer(lp))
    return {"embed_tokens": vec(tensors["model.embed_tokens.weight"]),
            "layers": tuple(layers),
            "norm": vec(tensors["model.norm.weight"]),
            "lm_head": lin("lm_head", tensors["lm_head.weight"])}
