"""Context-parallel INFERENCE: ring prefill + sequence-sharded KV decode.

Long-context serving the reference cannot do at all (SURVEY.md §2.2: its
long-context story is FP8 KV + 32k model variants, single-device): here a
prompt longer than one chip's KV budget shards over the `sp` mesh axis —

- **Prefill** runs the generalized decoder once per chip on its token
  chunk with EXACT ring attention (ops/ring.py): peak activation and KV
  memory are O(S/n) per chip, K/V chunks ride the ICI ring.
- **The KV cache stays sharded for decode.** Global position g lives on
  device g mod n at local row g div n (the "cyclic" ring layout), so
  ownership stays balanced for any prompt length and every decode token
  lands on a rotating owner. Each step, every chip computes the (tiny)
  token forward, attends over ITS cache slice, and the partial softmax
  stats merge with one pmax + two psums (flash-style: m_g = pmax(m),
  l_g = psum(l*exp(m-m_g)), o_g = psum(o*exp(m-m_g))) — decode HBM
  traffic per chip is the weight read + 1/n of the KV read.

Everything runs inside ONE shard_map-per-phase jit; params are replicated
over sp (compose with tp via parallel/sharding.py for weight sharding).
Supported families: the standard residual path (same guard as
forward_train's attn_fn branch).
"""

from __future__ import annotations

import functools
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.models import llama as M
from bigdl_tpu.observability.compile_watch import tracked_jit
from bigdl_tpu.ops.matmul import linear

_WARNED_CP_SCALED = False    # one warning per process for int8/int4 CP
from bigdl_tpu.ops.ring import ring_attention
from bigdl_tpu.ops.rope import apply_rope, rope_cos_sin



def _check_cfg(cfg) -> None:
    if (cfg.use_alibi or cfg.attn_soft_cap is not None
            or cfg.sandwich_norms or cfg.alt_sliding_window
            or cfg.query_pre_attn_scalar is not None
            or cfg.sliding_window is not None):
        raise NotImplementedError(
            "context-parallel inference supports the standard residual "
            "path (same guard as forward_train's ring-attention branch); "
            "ALiBi/soft-cap/sliding-window families run single-device")


def to_cyclic(tokens: jax.Array, n: int) -> jax.Array:
    """[B, S] -> device-major cyclic order: sharding the result over the
    last axis hands device p the tokens p, p+n, p+2n, ..."""
    b, s = tokens.shape
    return tokens.reshape(b, s // n, n).transpose(0, 2, 1).reshape(b, s)


def cp_prefill(
    params: Dict[str, Any],
    cfg,
    tokens: jax.Array,        # [B, S] int32; S % n == 0
    mesh: Mesh,
    axis: str = "sp",
    max_seq: Optional[int] = None,
    compute_dtype=jnp.bfloat16,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Returns (next-token logits [B, V] replicated, (ck, cv) sharded
    caches [L, B, max_seq/n, Hkv, hd] in the cyclic layout, filled for
    the prompt)."""
    _check_cfg(cfg)
    n = mesh.shape[axis]
    b, s = tokens.shape
    if s % n:
        raise ValueError(f"prompt length {s} not divisible by sp={n}")
    max_seq = max_seq or s
    if max_seq % n or max_seq < s:
        raise ValueError(f"max_seq {max_seq} must be a multiple of sp={n} "
                         f"and >= prompt {s}")
    tok_cyc = to_cyclic(tokens, n)
    fn = _prefill_fn(cfg, mesh, axis, s, max_seq, compute_dtype)
    lg, ck, cv = fn(params, tok_cyc)
    return lg, (ck, cv)


@functools.lru_cache(maxsize=32)
def _prefill_fn(cfg, mesh, axis, s, max_seq, compute_dtype):
    n = mesh.shape[axis]
    cap = max_seq // n
    inv_freq, rope_mscale = M.model_rope_freqs(cfg)

    def local(params, tok_loc):
        p = lax.axis_index(axis)
        s_loc = tok_loc.shape[1]
        positions = p + jnp.arange(s_loc, dtype=jnp.int32) * n
        x = M.embed_prologue(params, cfg, tok_loc, positions,
                             compute_dtype)
        cos, sin = rope_cos_sin(positions[None, :], inv_freq)
        if rope_mscale != 1.0:
            cos, sin = cos * rope_mscale, sin * rope_mscale

        ring = functools.partial(ring_attention, axis_name=axis,
                                 layout="cyclic")

        def step(carry, lp):
            out, kv = M.ext_attn_layer(carry, lp, cfg, cos, sin, ring)
            return out, kv

        x, (ks, vs) = lax.scan(step, x, params["layers"])
        x = M._norm(x, params["norm"], params.get("norm_bias"), cfg)

        # logits only for the LAST global token (position s-1, owned by
        # device (s-1) % n at local row (s-1) // n)
        owner = (s - 1) % n
        row = (s - 1) // n
        lg = M._lm_head(x[:, row:row + 1], params, cfg)[:, 0]   # [B, V]
        lg = lax.psum(jnp.where(p == owner, lg, 0.0), axis)

        # grow the per-layer chunks into the capacity-sized cache slice
        pad = cap - s_loc
        ck = jnp.pad(ks.astype(compute_dtype),
                     ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(vs.astype(compute_dtype),
                     ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        return lg, ck, cv

    spec_tok = P(None, axis)
    spec_cache = P(None, None, axis)
    return tracked_jit("cp_prefill", jax.shard_map(
        local, mesh=mesh, in_specs=(P(), spec_tok),
        out_specs=(P(), spec_cache, spec_cache), check_vma=False))


def cp_decode_step(
    params: Dict[str, Any],
    cfg,
    tok: jax.Array,           # [B] int32 current token
    cache: Tuple[jax.Array, jax.Array],
    pos: jax.Array,           # scalar int32: global position of `tok`
    mesh: Mesh,
    axis: str = "sp",
    compute_dtype=jnp.bfloat16,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One decode step over the sequence-sharded cache. `pos` is a HOST
    int (the guard below needs it concrete). Returns (logits [B, V]
    replicated, updated cache)."""
    _check_cfg(cfg)
    pos = int(pos)
    capacity = cache[0].shape[2]      # global rows (n shards of cap each)
    if pos >= capacity:
        # dynamic_update_slice would silently CLAMP the write row and
        # corrupt the last stored position
        raise ValueError(
            f"decode position {pos} exceeds the sharded cache capacity "
            f"{capacity}; allocate a larger max_seq at cp_prefill")
    fn = _decode_fn(cfg, mesh, axis, compute_dtype)
    lg, ck, cv = fn(params, tok, cache[0], cache[1],
                    jnp.asarray(pos, jnp.int32))
    return lg, (ck, cv)


@functools.lru_cache(maxsize=32)
def _decode_fn(cfg, mesh, axis, compute_dtype):
    n = mesh.shape[axis]
    inv_freq, rope_mscale = M.model_rope_freqs(cfg)
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd
    g = h // hkv

    def local(params, tok, ck, cv, pos):
        p = lax.axis_index(axis)
        cap = ck.shape[2]
        positions = pos[None]                       # [1]
        x = M.embed_prologue(params, cfg, tok[:, None], positions,
                             compute_dtype)
        cos, sin = rope_cos_sin(positions[None, :], inv_freq)
        if rope_mscale != 1.0:
            cos, sin = cos * rope_mscale, sin * rope_mscale

        owner = pos % n
        row = pos // n
        gid = p + jnp.arange(cap, dtype=jnp.int32) * n      # global ids

        def step(carry, xs):
            x = carry
            lp, ck_l, cv_l = xs
            stored = {}

            def attn_fn(q, k, v):
                # the owner stores the new entry BEFORE attending, so
                # the current token attends itself through the same path
                k_new = jnp.where(p == owner,
                                  lax.dynamic_update_slice(
                                      ck_l, k.astype(ck_l.dtype),
                                      (0, row, 0, 0)), ck_l)
                v_new = jnp.where(p == owner,
                                  lax.dynamic_update_slice(
                                      cv_l, v.astype(cv_l.dtype),
                                      (0, row, 0, 0)), cv_l)
                stored["kv"] = (k_new, v_new)
                # partial attention over the local slice, flash-merged
                qf = q.reshape(-1, 1, hkv, g, hd).astype(jnp.bfloat16)
                s_ = jnp.einsum("bqhgd,bkhd->bhgqk", qf,
                                k_new.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32) \
                    * (hd ** -0.5)
                valid = gid <= pos
                s_ = jnp.where(valid[None, None, None, None, :], s_,
                               -jnp.inf)
                m_loc = jnp.max(s_, axis=-1)
                m_g = lax.pmax(m_loc, axis)
                pexp = jnp.where(jnp.isfinite(s_),
                                 jnp.exp(s_ - m_g[..., None]), 0.0)
                l_g = lax.psum(jnp.sum(pexp, axis=-1), axis)
                o = jnp.einsum("bhgqk,bkhd->bhgqd",
                               pexp.astype(jnp.bfloat16),
                               v_new.astype(jnp.bfloat16),
                               preferred_element_type=jnp.float32)
                o = lax.psum(o, axis) / jnp.maximum(l_g, 1e-30)[..., None]
                return jnp.moveaxis(o, 3, 1).reshape(
                    q.shape[0], 1, h * hd).astype(q.dtype)

            out, _ = M.ext_attn_layer(x, lp, cfg, cos, sin, attn_fn)
            return out, stored["kv"]

        x, (ck2, cv2) = lax.scan(step, x, (params["layers"], ck, cv))
        x = M._norm(x, params["norm"], params.get("norm_bias"), cfg)
        lg = M._lm_head(x, params, cfg)[:, 0]               # [B, V]
        return lg, ck2, cv2

    spec_cache = P(None, None, axis)
    return tracked_jit("cp_decode_step", jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), spec_cache, spec_cache, P()),
        out_specs=(P(), spec_cache, spec_cache), check_vma=False),
        donate_argnums=(2, 3))


def cp_empty_cache(cfg, batch: int, max_seq: int, mesh: Mesh,
                   axis: str = "sp", compute_dtype=jnp.bfloat16,
                   kv_cache_dtype: str = "bf16"):
    """Zero sequence-sharded (ck, cv) caches for incremental CP prefill
    (cp_prefill_chunk); max_seq % mesh size == 0.

    kv_cache_dtype selects the STORAGE dtype: "fp8_e5m2" stores e5m2
    (the einsum read sites already upcast to bf16); "int8"/"int4" need
    per-token scale planes the sharded (ck, cv) tuple does not carry, so
    the CP lane falls back to bf16 storage with a one-time warning."""
    n = mesh.shape[axis]
    if max_seq % n:
        raise ValueError(f"max_seq {max_seq} not divisible by {n}")
    if kv_cache_dtype == "fp8_e5m2":
        compute_dtype = jnp.float8_e5m2
    elif kv_cache_dtype in ("int8", "int4"):
        global _WARNED_CP_SCALED
        if not _WARNED_CP_SCALED:
            _WARNED_CP_SCALED = True
            warnings.warn(
                f"kv_cache_dtype={kv_cache_dtype!r} is not supported on "
                "the context-parallel overflow lane (no scale planes in "
                "the sequence-sharded cache); CP requests store bf16",
                stacklevel=2)
    return_dtype = compute_dtype
    shape = (cfg.num_hidden_layers, batch, max_seq,
             cfg.num_key_value_heads, cfg.hd)
    sh = NamedSharding(mesh, P(None, None, axis))
    ck = jax.device_put(jnp.zeros(shape, return_dtype), sh)
    return ck, jax.device_put(jnp.zeros(shape, return_dtype), sh)


def cp_prefill_chunk(
    params: Dict[str, Any],
    cfg,
    tokens: jax.Array,        # [B, C] int32 (pad tail with anything)
    cache: Tuple[jax.Array, jax.Array],
    p0: int,                  # global position of tokens[:, 0]
    sel_pos: int,             # global position whose logits to return
    mesh: Mesh,
    axis: str = "sp",
    compute_dtype=jnp.bfloat16,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Append one CONTIGUOUS chunk of prompt tokens to the sequence-
    sharded cache in a single dispatch — the incremental form of
    cp_prefill that a serving engine can interleave with decode steps
    (chunked admission; one chunk per engine step). Each device writes
    the chunk rows it owns (cyclic layout; out-of-capacity pad writes
    drop), then C queries flash-merge over every local cache slice.
    Returns (logits [B, V] replicated for `sel_pos`, updated cache)."""
    _check_cfg(cfg)
    fn = _extend_fn(cfg, mesh, axis, int(tokens.shape[1]), compute_dtype)
    lg, ck, cv = fn(params, tokens, cache[0], cache[1],
                    jnp.asarray(int(p0), jnp.int32),
                    jnp.asarray(int(sel_pos), jnp.int32))
    return lg, (ck, cv)


@functools.lru_cache(maxsize=32)
def _extend_fn(cfg, mesh, axis, c, compute_dtype):
    n = mesh.shape[axis]
    inv_freq, rope_mscale = M.model_rope_freqs(cfg)
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd
    g = h // hkv

    def local(params, tok, ck, cv, p0, sel_pos):
        p = lax.axis_index(axis)
        cap = ck.shape[2]
        positions = p0 + jnp.arange(c, dtype=jnp.int32)       # [C]
        x = M.embed_prologue(params, cfg, tok, positions, compute_dtype)
        cos, sin = rope_cos_sin(positions[None, :], inv_freq)
        if rope_mscale != 1.0:
            cos, sin = cos * rope_mscale, sin * rope_mscale

        mine = (positions % n) == p
        # out-of-range index -> scatter drops the write (pad tail rows
        # past capacity, and rows owned by other devices)
        lrow = jnp.where(mine, positions // n, cap)
        gid = p + jnp.arange(cap, dtype=jnp.int32) * n

        def step(carry, xs):
            x = carry
            lp, ck_l, cv_l = xs
            stored = {}

            def attn_fn(q, k, v):
                k_new = ck_l.at[:, lrow].set(
                    k.astype(ck_l.dtype), mode="drop")
                v_new = cv_l.at[:, lrow].set(
                    v.astype(cv_l.dtype), mode="drop")
                stored["kv"] = (k_new, v_new)
                qf = q.reshape(-1, c, hkv, g, hd).astype(jnp.bfloat16)
                s_ = jnp.einsum("bqhgd,bkhd->bhgqk", qf,
                                k_new.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32) \
                    * (hd ** -0.5)
                valid = gid[None, :] <= positions[:, None]    # [C, cap]
                s_ = jnp.where(valid[None, None, None], s_, -jnp.inf)
                m_loc = jnp.max(s_, axis=-1)
                m_g = lax.pmax(m_loc, axis)
                pexp = jnp.where(jnp.isfinite(s_),
                                 jnp.exp(s_ - m_g[..., None]), 0.0)
                l_g = lax.psum(jnp.sum(pexp, axis=-1), axis)
                o = jnp.einsum("bhgqk,bkhd->bhgqd",
                               pexp.astype(jnp.bfloat16),
                               v_new.astype(jnp.bfloat16),
                               preferred_element_type=jnp.float32)
                o = lax.psum(o, axis) / jnp.maximum(l_g, 1e-30)[..., None]
                return jnp.moveaxis(o, 3, 1).reshape(
                    q.shape[0], c, h * hd).astype(q.dtype)

            out, _ = M.ext_attn_layer(x, lp, cfg, cos, sin, attn_fn)
            return out, stored["kv"]

        x, (ck2, cv2) = lax.scan(step, x, (params["layers"], ck, cv))
        x = M._norm(x, params["norm"], params.get("norm_bias"), cfg)
        row = jnp.clip(sel_pos - p0, 0, c - 1)
        lg = M._lm_head(
            lax.dynamic_slice_in_dim(x, row, 1, axis=1), params, cfg)[:, 0]
        return lg, ck2, cv2

    spec_cache = P(None, None, axis)
    return tracked_jit("cp_prefill_chunk", jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), spec_cache, spec_cache, P(), P()),
        out_specs=(P(), spec_cache, spec_cache), check_vma=False),
        donate_argnums=(2, 3))


def cp_generate(
    params: Dict[str, Any],
    cfg,
    input_ids,                # [B, S] ints, S % n == 0
    mesh: Mesh,
    axis: str = "sp",
    max_new_tokens: int = 32,
    max_seq: Optional[int] = None,
    eos_token_id: Optional[int] = None,
) -> np.ndarray:
    """Greedy context-parallel generation -> [B, S + new]. The prompt KV
    never materializes on one chip; see module docstring."""
    ids = np.asarray(input_ids, np.int32)
    if ids.ndim == 1:
        ids = ids[None]
    b, s = ids.shape
    n = mesh.shape[axis]
    max_seq = max_seq or (-(-(s + max_new_tokens) // n) * n)
    if max_seq < s + max_new_tokens:
        raise ValueError(
            f"max_seq {max_seq} cannot hold prompt {s} + "
            f"max_new_tokens {max_new_tokens}")

    lg, cache = cp_prefill(params, cfg, jnp.asarray(ids), mesh, axis,
                           max_seq=max_seq)
    out = [np.asarray(jnp.argmax(lg, axis=-1), np.int32)]
    for t in range(max_new_tokens - 1):
        tok = jnp.asarray(out[-1])
        lg, cache = cp_decode_step(params, cfg, tok, cache, s + t, mesh,
                                   axis)
        nxt = np.asarray(jnp.argmax(lg, axis=-1), np.int32)
        out.append(nxt)
        if eos_token_id is not None and (nxt == eos_token_id).all():
            break
    return np.concatenate([ids, np.stack(out, axis=1)], axis=1)
