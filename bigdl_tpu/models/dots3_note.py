"""dots3-note: latent attention (MLA) of two kinds in one model, learned
sparse attention in the full layers, a ring window in the others, a
head-wise gate and a sigmoid, bias-corrected router; functional and
static-shape.

The layer, as this module reads the published config (pre-norm
residual, RMSNorm, untied head; `x` is the layer's normed input):

- Full-attention layer (`layer_types[i] == "full_attention"`). MLA as
  DeepSeek-V2's (`models/deepseek_v2.mla_project`): `c_q = a_q RMSNorm(x
  W_qa)`, `[q_n | q_r]_h = c_q W_qb`, `[c_kv | k_r] = x W_kva`, `c_kv <-
  a_kv RMSNorm(c_kv)`, rope (channels 2i, 2i+1 together) on `q_r` and the
  one shared `k_r`, `[k_n | v]_h = c_kv W_kvb`. The INDEXER (DeepSeek-V3.2's
  lightning indexer; `ops/dsa.py`): `q_I = c_q W_Iq` (`index_n_heads` x
  `index_head_dim`), `k_I = LayerNorm(x W_Ik)`, `w = x W_Iw * Hi^-1/2 *
  Di^-1/2`, rope on the first `qk_rope_head_dim` channels of `q_I` and
  `k_I`; `I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`; position `t`
  attends the `index_topk` positions `s <= t` of largest `I[t, s]` (all
  while `t < index_topk`; ties to the lower position). Softmax over those
  of `(q_n . k_n + q_r . k_r) / sqrt(nope + rope)`, times `v`. The
  head-wise gate: `o_h <- sigmoid(x W_g)_h o_h`; then `W_o`.
- Window layer (`"sliding_attention"`): the same MLA at ITS OWN sizes
  (`swa_*`), no indexer; position `t` attends `s` in `[t -
  sliding_window_size + 1, t]`.
- `a_q = sqrt(hidden / q_lora_rank)`, `a_kv = sqrt(hidden /
  kv_lora_rank)` with `apply_mla_qkv_lora_rescale` (variance alignment of
  the two latents).
- Feed-forward: the first `first_k_dense_replace` layers dense SwiGLU,
  the others shared expert plus this chip's share of the routed sum
  (`deepseek_v2.moe_block`, `ops/moe_routed.py`): scores `sigmoid(x W_r)`
  in float32, choice = top-k of `scores + router_bias` (`noaux_tc`),
  weights the chosen scores renormalised, times
  `routed_scaling_factor`.

The cache holds three planes of two lengths (`cache_spec`): the full
layers' latent rows `[Lf, B, C + R, S]` and index keys `[Lf, B, Di, S]`,
and the window layers' latent rows in a RING `[Lw, B, Cw + Rw, ring]`
written at `pos % ring` (`ops/kvcache.py`).

Decode (one row a slot) absorbs `W_uk` into the query and runs the
kernels of `ops/pallas/dsa_attention.py`: index scores, the exact
selection as a mask, the masked sweep; the ring sweep in a window layer.
A chunk of rows runs in blocks of keys with an online softmax, absorbed
or expanded by `deepseek_v2._absorb`'s count at the kind's sizes
(absorbed below 170 rows in a full layer, below 190 in a window layer),
over the live blocks only. A full layer's expanded sweep (the cells'
1024-row chunks) is ONE kernel over the latent stack where it lies,
`ops/pallas/mla_chunk_attention.py` by `ops/dsa.mla_chunk_attention`'s
rule: K and V of a key block expanded once a head and every score tile
in VMEM. Its XLA form (`_sweep_chunk`: the CPU's path, the absorbed
form's only one) and the window layers' band (`_window_chunk`) sweep per
sequence in `jnp` ops, where `[heads, rows, S]` never exists in float32
but `[heads, rows, block]` does, in HBM. The indexer's per-head products
are reduced over heads a tile at a time.

Parameter tree (linears contraction-major `[K, N]`, QTensor or dense):
{
  "embed_tokens": [V, D], "norm": [D], "lm_head": [D, V],
  "layers": one dict a layer, in order (no stack: the kinds differ):
      input_layernorm, post_attention_layernorm, q_a_proj, q_a_layernorm,
      q_b_proj, kv_a_proj, kv_a_layernorm, w_uk, w_uv, o_proj, attn_gate
      [D, H]; a full layer also index_q_proj [q_lora, Hi Di], index_k_proj
      [D, Di], index_k_norm / index_k_norm_bias [Di], index_w_proj [D, Hi];
      a dense layer gate_proj / up_proj / down_proj; an expert layer
      router [D, E_total], router_bias [E_total], shared_gate / shared_up
      / shared_down,
  "experts": experts_gate / experts_up [Le, held, D, F], experts_down
      [Le, held, F, D], stacked over the expert layers (the routed
      kernels address a layer where it lies),
}
Before `prepare_params` a layer holds `kv_b_proj` in the place of `w_uk`
and `w_uv` and its narrow linears are not padded: the canonical tree,
which the benchmark's reference reads.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.models.deepseek_v2 import (_absorb, _pad_n, mla_project,
                                          moe_block, prepare_attention,
                                          swiglu)
from bigdl_tpu.models.llama import embedding_lookup
from bigdl_tpu.ops import dsa
from bigdl_tpu.ops.kvcache import (CacheSpec, KVCache, PlaneSpec,
                                   init_cache_spec, update_latent,
                                   update_ring)
from bigdl_tpu.ops.matmul import linear
from bigdl_tpu.ops.moe_routed import STATS, Share
from bigdl_tpu.ops.norms import layer_norm, rms_norm
from bigdl_tpu.ops.rope import apply_rope, rope_tables

FULL, WINDOW = "full_attention", "sliding_attention"
INDEX_NORM_EPS = 1e-6
_LANES = 128


@dataclasses.dataclass(frozen=True)
class MlaKind:
    """The sizes of one kind of attention layer, under the names
    `deepseek_v2.mla_project` and `_absorb` read."""
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    q_rescale: Optional[float]
    kv_rescale: Optional[float]
    window: int = 0          # positions attended, the query's own counted

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 46
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window_size: int = 513
    # columns of the window layers' ring; 0: the window rounded up to a
    # lane multiple
    window_ring: int = 0
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    attention_gate_type: str = "headwise"
    swa_attention_gate_type: str = "headwise"
    apply_mla_qkv_lora_rescale: bool = True
    n_routed_experts: int = 256       # experts held HERE (see ep_size)
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 524288
    tie_word_embeddings: bool = False
    ep_size: int = 1
    ep_rank: int = 0

    @classmethod
    def from_hf(cls, hf: Dict[str, Any]) -> "Dots3NoteConfig":
        for key, only in (("moe_layer_freq", 1), ("hidden_act", "silu"),
                          ("rope_scaling", None), ("attention_bias", False),
                          ("attention_gate_type", "headwise"),
                          ("swa_attention_gate_type", "headwise")):
            if hf.get(key, only) != only:
                raise NotImplementedError(f"{key} {hf[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        kw["layer_types"] = tuple(hf["layer_types"])
        cfg = cls(**kw)
        if len(cfg.layer_types) != cfg.num_hidden_layers or any(
                t not in (FULL, WINDOW) for t in cfg.layer_types):
            raise ValueError("layer_types must name every layer "
                             f"{FULL!r} or {WINDOW!r}")
        if cfg.window_ring and cfg.window_ring < cfg.sliding_window_size:
            raise ValueError("window_ring is shorter than the window")
        return cfg

    @property
    def share(self) -> Share:
        return Share(self.n_routed_experts * self.ep_size,
                     self.n_routed_experts * self.ep_rank,
                     self.n_routed_experts)

    @property
    def n_dense(self) -> int:
        return min(self.first_k_dense_replace, self.num_hidden_layers)

    def _rescale(self, rank: int) -> Optional[float]:
        if not self.apply_mla_qkv_lora_rescale:
            return None
        return math.sqrt(self.hidden_size / rank)

    @property
    def full(self) -> MlaKind:
        return MlaKind(self.num_attention_heads, self.q_lora_rank,
                       self.kv_lora_rank, self.qk_nope_head_dim,
                       self.qk_rope_head_dim, self.v_head_dim,
                       self.rope_theta, self.rms_norm_eps,
                       self._rescale(self.q_lora_rank),
                       self._rescale(self.kv_lora_rank))

    @property
    def swa(self) -> MlaKind:
        return MlaKind(self.swa_num_attention_heads, self.swa_q_lora_rank,
                       self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                       self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                       self.swa_rope_theta, self.rms_norm_eps,
                       self._rescale(self.swa_q_lora_rank),
                       self._rescale(self.swa_kv_lora_rank),
                       window=self.sliding_window_size)

    def kind(self, layer: int) -> MlaKind:
        return self.full if self.layer_types[layer] == FULL else self.swa

    @property
    def n_full(self) -> int:
        return sum(t == FULL for t in self.layer_types)

    @property
    def ring(self) -> int:
        return self.window_ring or -(-self.sliding_window_size
                                     // _LANES) * _LANES

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
    def kv_values_per_position(self) -> float:
        """Cached values a decoded token reads of one position, as a
        mean over ALL the layers (roofline.py multiplies by their
        number): the full layers' latent row and index key; the window
        layers keep a ring whose reads do not grow with the position."""
        return (self.n_full * (self.full.latent_dim + self.index_head_dim)
                / self.num_hidden_layers)

    def matmul_flops_per_token(self) -> int:
        """Forward matmul operations a token needs on THIS chip."""
        d = self.hidden_size

        def attn(k: MlaKind) -> int:
            h, qk = k.num_attention_heads, k.qk_nope_head_dim + \
                k.qk_rope_head_dim
            return (d * k.q_lora_rank + k.q_lora_rank * h * qk
                    + d * k.latent_dim + k.kv_lora_rank * h
                    * (k.qk_nope_head_dim + k.v_head_dim)
                    + h * k.v_head_dim * d + d * h)

        index = (self.q_lora_rank * self.index_n_heads * self.index_head_dim
                 + d * (self.index_head_dim + self.index_n_heads))
        f = self.moe_intermediate_size
        moe = 3 * d * f * (self.n_shared_experts
                           + self.num_experts_per_tok / self.ep_size)
        n_moe = self.num_hidden_layers - self.n_dense
        n_full = self.n_full
        return int(2 * (n_full * (attn(self.full) + index)
                        + (self.num_hidden_layers - n_full) * attn(self.swa)
                        + self.n_dense * 3 * d * self.intermediate_size
                        + n_moe * (moe + d * self.share.experts_total)
                        + d * self.vocab_size))

    def attn_flops_per_cached_token(self) -> int:
        """Decode attention per cached position, the full layers: the
        index score of every position (the absorbed product runs over
        the selected ones, which stop growing at `index_topk`)."""
        return self.n_full * 2 * self.index_n_heads * self.index_head_dim


def cache_spec(cfg: Dots3NoteConfig) -> CacheSpec:
    n_full = cfg.n_full
    planes = []
    if n_full:
        planes += [PlaneSpec("latent", n_full, (cfg.full.latent_dim,)),
                   PlaneSpec("index", n_full, (cfg.index_head_dim,))]
    if cfg.num_hidden_layers > n_full:
        planes.append(PlaneSpec("window", cfg.num_hidden_layers - n_full,
                                (cfg.swa.latent_dim,), ring=cfg.ring))
    return CacheSpec("latent", cfg.num_hidden_layers,
                     latent_dim=cfg.full.latent_dim,
                     stats_len=len(STATS) if cfg.n_dense
                     < cfg.num_hidden_layers else 0,
                     planes=tuple(planes))


def new_cache(cfg: Dots3NoteConfig, batch: int, max_seq: int,
              quantized=False) -> KVCache:
    """The three planes with the window layers' rows in position order
    (`CacheSpec.unrolled`: `generate()` right-pads its prompt, and the
    padding would overwrite live columns of a ring; the serving engine's
    slab is the one that holds the ring, `cache_spec`); bf16 only
    (`ops/kvcache.reject_non_bf16_latent`). The forward reads a window
    plane as a ring of ITS OWN length, which a plane in position order
    is."""
    return init_cache_spec(cache_spec(cfg).unrolled(), batch, max_seq,
                           kv_cache_dtype=quantized)


def _ein(eq: str, a, b):
    """`einsum` accumulated in float32; off the TPU the operands are
    widened first (the CPU's dot lacks the batched bf16 forms)."""
    from bigdl_tpu.config import target_is_tpu

    if not target_is_tpu():
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def _key_block(s: int) -> int:
    """Keys a block of the chunk path holds: 1024 where the plane's
    length allows, else the whole plane."""
    return 1024 if s % 1024 == 0 else s


def _live_blocks(p, t: int, s: int):
    """`(kb, n)`: the key block of a plane of `s` positions and how many
    of its blocks hold a key of a chunk of `t` rows at `p ..`."""
    kb = _key_block(s)
    return kb, jnp.minimum((p + t + kb - 1) // kb, s // kb)


def _online_softmax_step(carry, s_, live, pv_of):
    """One block of keys through the online softmax: `s_` `[H, T, n]`
    scaled scores, `live` `[T, n]`, `pv_of(p)` the block's weighted
    values `[T, H, .]` of the bf16 weights `p`."""
    m, l, acc = carry
    s_ = jnp.where(live[None], s_, -1e30)
    m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.where(live[None], jnp.exp(s_ - m_new[..., None]), 0.0)
    l = l * corr + jnp.sum(p, axis=-1)
    acc = acc * jnp.swapaxes(corr, 0, 1)[..., None] + pv_of(
        p.astype(jnp.bfloat16))
    return m_new, l, acc


def _block_attention(kind: MlaKind, absorb: bool, q_main, q_pe, w_uk, w_uv):
    """`(scores, values)` of one block of latent rows `blk` `[C + R, n]`
    for the rows' queries: absorbed (`q_main` = `q_nope W_uk^T` `[T, H,
    C]`, values the compressed rows themselves) or expanded (`q_main` =
    `q_nope`, K and V per head from the block)."""
    c = kind.kv_lora_rank

    def scores(blk):
        ckv, kpe = blk[:c], blk[c:]
        if absorb:
            s_ = _ein("qhc,cs->hqs", q_main, ckv)
        else:
            k_n = _ein("cs,hdc->hsd", ckv, w_uk).astype(q_main.dtype)
            s_ = _ein("qhd,hsd->hqs", q_main, k_n)
        return (s_ + _ein("qhr,rs->hqs", q_pe, kpe)) * kind.scale

    def values(blk):
        ckv = blk[:c]
        if absorb:
            return lambda p: _ein("hqs,cs->qhc", p, ckv)
        v = _ein("cs,hcd->hsd", ckv, w_uv).astype(q_main.dtype)
        return lambda p: _ein("hqs,hsd->qhd", p, v)

    return scores, values


def _finish(absorb: bool, carry, w_uv, dtype):
    """The sweep's state -> `[T, H, v]` float32."""
    _, l, acc = carry
    o = acc / jnp.maximum(jnp.swapaxes(l, 0, 1), 1e-30)[..., None]
    if absorb:
        with jax.named_scope("mla.out"):
            o = _ein("qhc,hcd->qhd", o.astype(dtype), w_uv)
    return o


def _sweep_state(kind: MlaKind, absorb: bool, t: int):
    h = kind.num_attention_heads
    width = kind.kv_lora_rank if absorb else kind.v_head_dim
    return (jnp.full((h, t), -1e30, jnp.float32),
            jnp.zeros((h, t), jnp.float32),
            jnp.zeros((t, h, width), jnp.float32))


def _select_chunk(cfg, q_i, w_i, idx, p, selected=None):
    """Index scores `[T, S]` float32 of a chunk of `T` rows of ONE
    sequence at positions `p .. p + T - 1` over the live blocks of its
    index keys `idx` `[Di, S]` (the chunk's own already written), and the
    exact selection `[T, S]` bool; `selected`, where given, takes the
    selection's place (a check that holds the attention apart from the
    selection)."""
    t, s = q_i.shape[0], idx.shape[-1]
    kb, n_live = _live_blocks(p, t, s)
    with jax.named_scope("dsa.index"):
        def score_block(j, acc):
            blk = lax.dynamic_slice(idx, (0, j * kb), (idx.shape[0], kb))
            got = dsa.index_scores_xla(q_i[None], w_i[None], blk[None],
                                       (p - j * kb)[None])[0]
            return lax.dynamic_update_slice(acc, got, (0, j * kb))

        scores = lax.fori_loop(0, n_live, score_block,
                               jnp.full((t, s), -jnp.inf, jnp.float32))
    with jax.named_scope("dsa.select"):
        sel = (dsa.select_topk_mask(scores, cfg.index_topk)
               if selected is None else selected)
    return scores, sel


def _sweep_chunk(kind, q_nope, q_pe, lat, sel, p, w_uk, w_uv):
    """Attention of a chunk of `T` rows of ONE sequence at positions `p
    ..` over the positions `sel` `[T, S]` marks of its latent plane `lat`
    `[C + R, S]`, in XLA ops: the live blocks of keys through an online
    softmax, absorbed or expanded by `_absorb`'s count. `[T, H, v]`
    float32. The CPU's path, the oracle of `ops/pallas/
    mla_chunk_attention.py` and the absorbed form's only one."""
    t = q_nope.shape[0]
    kb, n_live = _live_blocks(p, t, lat.shape[-1])
    absorb = _absorb(kind, t)
    if absorb:
        with jax.named_scope("mla.absorb"):
            q_main = _ein("qhd,hdc->qhc", q_nope, w_uk).astype(q_nope.dtype)
    else:
        q_main = q_nope
    score_of, value_of = _block_attention(kind, absorb, q_main, q_pe, w_uk,
                                          w_uv)

    def attend(j, carry):
        blk = lax.dynamic_slice(lat, (0, j * kb), (lat.shape[0], kb))
        live = lax.dynamic_slice(sel, (0, j * kb), (t, kb))
        return _online_softmax_step(carry, score_of(blk), live, value_of(blk))

    carry = lax.fori_loop(0, n_live, attend, _sweep_state(kind, absorb, t))
    return _finish(absorb, carry, w_uv, q_nope.dtype)


def _sparse_chunk(cfg, kind, q_nope, q_pe, q_i, w_i, lat, idx, li, pos, w_uk,
                  w_uv, selected=None):
    """A chunk of `T` rows a sequence (`q_nope` `[B, T, H, nope]`, ...)
    at positions `pos ..` through layer `li` of a full layer's stacks
    `lat` `[L, B, C + R, S]`, `idx` `[L, B, Di, S]` (the chunk's own rows
    already written): index scores and the exact selection over the live
    blocks of keys, then attention over the selected positions: the
    expanded form in `mla_chunk_attention`'s kernel on the stack where it
    lies, the absorbed one (and the CPU) in `_sweep_chunk`. Returns `[B,
    T, H, v]` float32, the index scores and the selection `[B, T, S]`;
    `selected`: `_select_chunk`'s."""
    b, t = q_nope.shape[:2]
    p = _positions(pos, b)
    idx_l = lax.dynamic_index_in_dim(idx, li, 0, keepdims=False)
    scores, sel = jax.vmap(
        lambda qi, wi, ix, pp, se: _select_chunk(cfg, qi, wi, ix, pp, se),
        in_axes=(0, 0, 0, 0, None if selected is None else 0))(
        q_i, w_i, idx_l, p, selected)

    def sweep():
        lat_l = lax.dynamic_index_in_dim(lat, li, 0, keepdims=False)
        return jax.vmap(lambda qn, qp, la, se, pp: _sweep_chunk(
            kind, qn, qp, la, se, pp, w_uk, w_uv))(q_nope, q_pe, lat_l, sel,
                                                   p)

    with jax.named_scope("mla.sparse"):
        if _absorb(kind, t):
            o = sweep()
        else:
            o = dsa.mla_chunk_attention(q_nope, q_pe, lat, li, p, sel, w_uk,
                                        w_uv, kind.scale, sweep)
    return o, scores, sel


_WINDOW_ROWS = 256


def _window_chunk(kind, q_nope, q_pe, new, ring, p, w_uk, w_uv):
    """A chunk of `T` rows of ONE sequence at positions `p ..` through a
    window layer: its keys are the `window - 1` positions before the
    chunk, read from the ring `[C + R, ring]` as it was BEFORE the chunk
    (column `q % ring` holds position `q`), and the chunk's own rows
    `new` `[T, C + R]`; row i attends the `window` positions ending at
    its own. Rows go in blocks of 256, each against the band of keys it
    can see. Returns `[T, H, v]` float32."""
    t = q_nope.shape[0]
    back = kind.window - 1
    n_ring = ring.shape[-1]
    before = p - back + jnp.arange(back, dtype=jnp.int32)       # positions
    prev = jnp.take(ring, jnp.mod(before, n_ring), axis=1)      # [C+R, back]
    ctx = jnp.concatenate([prev, jnp.swapaxes(new, 0, 1).astype(ring.dtype)],
                          axis=1)                               # [C+R, back+T]
    absorb = _absorb(kind, t)
    if absorb:
        with jax.named_scope("mla.absorb"):
            q_main = _ein("qhd,hdc->qhc", q_nope, w_uk).astype(q_nope.dtype)
    else:
        q_main = q_nope
    rb = _WINDOW_ROWS if t % _WINDOW_ROWS == 0 else t
    outs = []
    for r0 in range(0, t, rb):
        score_of, value_of = _block_attention(
            kind, absorb, q_main[r0:r0 + rb], q_pe[r0:r0 + rb], w_uk, w_uv)
        blk = ctx[:, r0:r0 + rb + back]
        i = jnp.arange(rb, dtype=jnp.int32)[:, None]
        j = jnp.arange(rb + back, dtype=jnp.int32)[None, :]
        # key j of the band is position p + r0 - back + j
        live = (j >= i) & (j <= i + back) & (p + r0 - back + j >= 0)
        carry = _online_softmax_step(_sweep_state(kind, absorb, rb),
                                     score_of(blk), live, value_of(blk))
        outs.append(_finish(absorb, carry, w_uv, q_nope.dtype))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


def _index_queries(y, c_q, lp, cfg, cos, sin, interleaved: bool = True):
    """The indexer's projections of the normed `y`: `q_I` `[B, T, Hi,
    Di]`, the new index keys `[B, T, Di]` and the head weights `[B, T,
    Hi]` float32 (scaled). `interleaved`: the rope channels rotate as
    pairs (2i, 2i + 1), this family's reading; False rotates them as two
    halves (i, i + rd / 2), as DeepSeek-V3.2's published indexer does."""
    b, t, _ = y.shape
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    q_i = apply_rope(linear(c_q, lp["index_q_proj"]).reshape(b, t, hi, di),
                     cos, sin, interleaved=interleaved)
    k_i = layer_norm(linear(y, lp["index_k_proj"])[..., :di],
                     lp["index_k_norm"], lp["index_k_norm_bias"],
                     INDEX_NORM_EPS)
    k_i = apply_rope(k_i, cos, sin, interleaved=interleaved)
    w_i = (linear(y, lp["index_w_proj"])[..., :hi].astype(jnp.float32)
           * (hi ** -0.5 * di ** -0.5))
    return q_i, k_i, w_i


def _positions(pos, b: int):
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))


def _full_attention(y, lp, cfg, lat, idx, li, pos, cos, sin, selected=None,
                    probe=None):
    """One full layer's attention on the normed `y` `[B, T, D]`: the
    output before `W_o`'s gate `[B, T, H, v]` float32 and the two stacks
    with this layer's rows written."""
    kind = cfg.full
    b, t, _ = y.shape
    q_nope, q_pe, new, c_q = mla_project(y, lp, kind, cos, sin,
                                         kind.q_rescale, kind.kv_rescale)
    with jax.named_scope("dsa.index"):
        q_i, k_i, w_i = _index_queries(y, c_q, lp, cfg, cos, sin)
        idx = update_latent(idx, li, k_i, pos)
    with jax.named_scope("mla.kv_latent"):
        lat = update_latent(lat, li, new, pos)
    w_uk, w_uv = lp["w_uk"], lp["w_uv"]
    if t == 1:
        with jax.named_scope("dsa.index"):
            scores = dsa.dsa_index_scores_decode(q_i[:, 0], w_i[:, 0], idx,
                                                 li, pos)
        with jax.named_scope("dsa.select"):
            sel = (dsa.dsa_select_decode(scores, cfg.index_topk)
                   if selected is None else selected[:, 0]) != 0
        with jax.named_scope("mla.absorb"):
            q_abs = _ein("bhd,hdc->bhc", q_nope[:, 0], w_uk).astype(y.dtype)
        with jax.named_scope("mla.sparse"):
            o_lat = dsa.sparse_mla_decode(q_abs, q_pe[:, 0], lat, li, pos,
                                          sel, kind.scale)
        with jax.named_scope("mla.out"):
            o = _ein("bhc,hcd->bhd", o_lat, w_uv)[:, None]
        scores, sel = scores[:, None], sel[:, None]
    else:
        o, scores, sel = _sparse_chunk(cfg, kind, q_nope, q_pe, q_i, w_i, lat,
                                       idx, li, pos, w_uk, w_uv, selected)
    if probe is not None:
        probe["index_scores"], probe["selected"] = scores, sel
    return o, lat, idx


def _window_attention(y, lp, cfg, win, li, pos, cos, sin):
    """One window layer's attention: `[B, T, H, v]` float32 and the ring
    stack with this layer's rows written."""
    kind = cfg.swa
    b, t, _ = y.shape
    q_nope, q_pe, new, _ = mla_project(y, lp, kind, cos, sin,
                                       kind.q_rescale, kind.kv_rescale)
    w_uk, w_uv = lp["w_uk"], lp["w_uv"]
    if t == 1:
        with jax.named_scope("mla.kv_latent"):
            win = update_ring(win, li, new, pos)
        with jax.named_scope("mla.absorb"):
            q_abs = _ein("bhd,hdc->bhc", q_nope[:, 0], w_uk).astype(y.dtype)
        with jax.named_scope("mla.window"):
            o_lat = dsa.window_mla_decode(q_abs, q_pe[:, 0], win, li, pos,
                                          kind.scale, kind.window)
        with jax.named_scope("mla.out"):
            o = _ein("bhc,hcd->bhd", o_lat, w_uv)[:, None]
    else:
        with jax.named_scope("mla.window"):
            ring = lax.dynamic_index_in_dim(win, li, 0, keepdims=False)
            o = jax.vmap(lambda qn, qp, nw, rg, p: _window_chunk(
                kind, qn, qp, nw, rg, p, w_uk, w_uv))(
                q_nope, q_pe, new.astype(win.dtype), ring,
                _positions(pos, b))
        with jax.named_scope("mla.kv_latent"):
            win = update_ring(win, li, new, pos)
    return o, win


def _gate_and_project(o, y, lp, kind: MlaKind):
    """The head-wise gate on the heads' outputs, then `W_o`."""
    b, t, _ = y.shape
    h = kind.num_attention_heads
    with jax.named_scope("mla.out"):
        g = jax.nn.sigmoid(linear(y, lp["attn_gate"])[..., :h].astype(
            jnp.float32))
        o = (o * g[..., None]).astype(y.dtype)
        return linear(o.reshape(b, t, h * kind.v_head_dim), lp["o_proj"])


def _tables(cfg: Dots3NoteConfig, pos, sq: int):
    """cos and sin `[B or 1, sq, rd / 2]` of the positions `pos .. pos +
    sq - 1` for the two kinds' rope."""
    if getattr(pos, "ndim", 0) == 1:
        positions = pos[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    else:
        positions = (pos + jnp.arange(sq, dtype=jnp.int32))[None, :]
    return rope_tables(positions, {
        FULL: (cfg.qk_rope_head_dim, cfg.rope_theta),
        WINDOW: (cfg.swa_qk_rope_head_dim, cfg.swa_rope_theta)})


def attention_block(y, lp, cfg: Dots3NoteConfig, cache: KVCache, kind: str,
                    selected=None, probe=None):
    """One layer's attention alone, as `forward` runs it: the normed `y`
    `[B, sq, D]` through layer 0 of the planes of `kind` in `cache` at
    `cache.pos`. Returns the attention output (before the residual) and
    the cache with the new rows written and `pos` advanced. For a check
    that holds a single layer to a reference on the same input;
    `selected` / `probe`: `_full_attention`'s."""
    sq = y.shape[1]
    cos, sin = _tables(cfg, cache.pos, sq)[kind]
    li = jnp.int32(0)
    if kind == FULL:
        o, lat, idx = _full_attention(y, lp, cfg, cache.latent, cache.index,
                                      li, cache.pos, cos, sin, selected, probe)
        cache = cache.replace(latent=lat, index=idx)
        out = _gate_and_project(o, y, lp, cfg.full)
    else:
        o, win = _window_attention(y, lp, cfg, cache.window, li, cache.pos,
                                   cos, sin)
        cache = cache.replace(window=win)
        out = _gate_and_project(o, y, lp, cfg.swa)
    return out, cache.replace(pos=cache.pos + sq)


def forward(
    params: Dict[str, Any],
    cfg: Dots3NoteConfig,
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
    eps = cfg.rms_norm_eps
    lat, idx, win = cache.latent, cache.index, cache.window
    stats = cache.stats
    tally = jnp.zeros((len(STATS),), jnp.int32) if stats is None else stats
    experts = params.get("experts")
    n_full = n_win = 0
    for i, lp in enumerate(params["layers"]):
        y = rms_norm(x, lp["input_layernorm"], eps)
        kind = cfg.layer_types[i]
        cos, sin = tables[kind]
        if kind == FULL:
            o, lat, idx = _full_attention(y, lp, cfg, lat, idx,
                                          jnp.int32(n_full), pos, cos, sin)
            n_full += 1
        else:
            o, win = _window_attention(y, lp, cfg, win, jnp.int32(n_win),
                                       pos, cos, sin)
            n_win += 1
        x = x + _gate_and_project(o, y, lp, cfg.kind(i))
        hid = rms_norm(x, lp["post_attention_layernorm"], eps)
        if i < cfg.n_dense:
            x = x + swiglu(hid, lp["gate_proj"], lp["up_proj"],
                           lp["down_proj"])
        else:
            y_moe, st = moe_block(hid, lp, experts,
                                  jnp.int32(i - cfg.n_dense), cfg)
            x = x + y_moe
            tally = tally + st
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
        latent=lat, index=idx, window=win, pos=pos + sq, stats=stats)


def forward_last_token(params, cfg, tokens, cache,
                       compute_dtype=jnp.bfloat16):
    return forward(params, cfg, tokens, cache, compute_dtype=compute_dtype,
                   last_only=True)


# ---------------------------------------------------------------------------
# canonical tree -> served tree, and HF checkpoint -> canonical tree
# ---------------------------------------------------------------------------

_NARROW = ("attn_gate", "index_k_proj", "index_w_proj")


def prepare_layer(lp: Dict[str, Any], kind: MlaKind,
                  compute_dtype=jnp.bfloat16) -> Dict[str, Any]:
    """One canonical layer as `forward` serves it: `kv_b_proj` as `w_uk`
    / `w_uv` in bf16 and `kv_a_proj` padded (`deepseek_v2.
    prepare_attention`), the gate's and the indexer's narrow linears
    padded to a lane multiple. A prepared layer passes through."""
    if "kv_b_proj" not in lp:
        return lp
    lp = dict(lp)
    # the shared helper takes a stack of layers: a stack of one
    served = prepare_attention(
        {k: jax.tree.map(lambda a: a[None], lp.pop(k))
         for k in ("kv_b_proj", "kv_a_proj")}, kind, compute_dtype)
    lp.update(jax.tree.map(lambda a: a[0], served))
    for k in _NARROW:
        if k in lp:
            lp[k] = _pad_n(lp[k])
    return lp


def prepare_params(params: Dict[str, Any], cfg: Dots3NoteConfig,
                   compute_dtype=jnp.bfloat16) -> Dict[str, Any]:
    out = dict(params)
    out["layers"] = tuple(prepare_layer(lp, cfg.kind(i), compute_dtype)
                          for i, lp in enumerate(params["layers"]))
    return out


_ATTN_LINEARS = {"self_attn.q_a_proj": "q_a_proj",
                 "self_attn.q_b_proj": "q_b_proj",
                 "self_attn.kv_a_proj_with_mqa": "kv_a_proj",
                 "self_attn.kv_b_proj": "kv_b_proj",
                 "self_attn.o_proj": "o_proj",
                 "self_attn.gate_proj": "attn_gate",
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
_EXPERT_MLP = {"gate_proj": "experts_gate", "up_proj": "experts_up",
               "down_proj": "experts_down"}


def convert_hf_params(
    tensors,
    cfg: Dots3NoteConfig,
    qtype: Optional[str] = "sym_int4",
    compute_dtype=jnp.bfloat16,
    modules_to_not_convert: Tuple[str, ...] = (),
    imatrix=None,
) -> Dict[str, Any]:
    """HF tensors -> the served tree, under the tensor names of the
    DeepSeek-V3 family (`self_attn.indexer.{wq_b, wk, k_norm,
    weights_proj}`, `self_attn.gate_proj`, `mlp.gate.
    e_score_correction_bias`): ASSUMED, no checkpoint of this model has
    been read here. The router (`mlp.gate`), its bias and the norms stay
    unquantized; of the routed experts only those this chip holds
    (`cfg.share`) are converted."""
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
            if stem in _ATTN_LINEARS:
                layers[layer][_ATTN_LINEARS[stem]] = lin(name, w)
            elif sub in _VECTORS:
                layers[layer][_VECTORS[sub]] = vec(w)
            elif sub == "mlp.gate.weight":
                layers[layer]["router"] = vec(w).T
            elif sub.startswith("mlp.experts.") and layer >= n_dense:
                e = int(parts[5]) - share.first_held
                if 0 <= e < share.held:
                    experts[_EXPERT_MLP[parts[6]]][layer - n_dense][e] = \
                        lin(name, w)
    want = {"q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj",
            "attn_gate", "input_layernorm", "post_attention_layernorm",
            "q_a_layernorm", "kv_a_layernorm"}
    for i, lp in enumerate(layers):
        need = set(want)
        if cfg.layer_types[i] == FULL:
            need |= {"index_q_proj", "index_k_proj", "index_w_proj",
                     "index_k_norm", "index_k_norm_bias"}
        need |= ({"gate_proj", "up_proj", "down_proj"} if i < n_dense else
                 {"router", "router_bias", "shared_gate", "shared_up",
                  "shared_down"})
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
    return prepare_params(params, cfg, compute_dtype)
