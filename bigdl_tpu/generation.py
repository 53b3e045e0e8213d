"""Token generation: jit prefill + jit decode step, sampling on device.

The reference rides HF `GenerationMixin.generate` (patched at
transformers/speculative.py:42-103); here generation is a first-class loop
built for XLA: one compiled prefill executable per prompt-length bucket and
ONE compiled decode executable reused for every token (static shapes, cache
carried as donated state). Sampling (temperature / top-k / top-p, greedy)
runs on device; only the emitted token returns to host each step.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.models import llama as llama_mod
from bigdl_tpu.observability.compile_watch import tracked_jit
from bigdl_tpu.ops.kvcache import KVCache


@dataclasses.dataclass
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 1.0
    top_k: int = 0            # 0 = disabled
    top_p: float = 1.0        # 1.0 = disabled
    do_sample: bool = False
    eos_token_id: Optional[int] = None
    seed: int = 0
    # llama.cpp-style repetition penalty (reference native sampler,
    # ggml/model/llama/llama.py:566-620): logits of already-seen tokens
    # divide (if >0) / multiply (if <0) by this. 1.0 = off.
    repetition_penalty: float = 1.0
    # OpenAI-style count penalties (reference vllm/sampling_params.py):
    # logit -= count * frequency_penalty + (count > 0) * presence_penalty
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # raise FloatingPointError on NaN/Inf logits instead of silently
    # sampling garbage (off by default: it forces a per-step host check;
    # the serving engine has its own always-on batched health check)
    check_logits: bool = False

    @property
    def needs_token_counts(self) -> bool:
        return (self.repetition_penalty != 1.0
                or self.presence_penalty != 0.0
                or self.frequency_penalty != 0.0)


def token_counts(tokens: jax.Array, vocab_size: int,
                 length: Optional[jax.Array] = None) -> jax.Array:
    """Per-row token occurrence counts [B, V] int32 for tokens [B, S].

    `length` ([B] or scalar) masks right padding: positions >= length do
    not count. The counts tensor is the jit-compatible stand-in for the
    reference sampler's `last_n_tokens` python list scan
    (ggml/model/llama/llama.py:566-620) — static shape, scatter-add
    updates, lives in the decode carry.
    """
    b, s = tokens.shape
    if length is None:
        add = jnp.ones((b, s), jnp.int32)
    else:
        idx = jnp.arange(s, dtype=jnp.int32)
        add = (idx[None, :] < jnp.broadcast_to(
            jnp.asarray(length, jnp.int32).reshape(-1, 1),
            (b, 1))).astype(jnp.int32)
    rows = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None], (b, s))
    return jnp.zeros((b, vocab_size), jnp.int32).at[rows, tokens].add(add)


def apply_penalties(
    logits: jax.Array,            # [B, V] f32
    rep_counts: jax.Array,        # [B, V] int32: prompt + output counts
    out_counts: jax.Array,        # [B, V] int32: OUTPUT-only counts
    repetition_penalty: float = 1.0,
    presence_penalty: float = 0.0,
    frequency_penalty: float = 0.0,
) -> jax.Array:
    """Repetition (llama.cpp form, over prompt + output) + presence/
    frequency (OpenAI/vllm form, over OUTPUT tokens only — vllm applies
    count penalties to generated tokens, not the prompt), pure
    gather-free tensor ops — safe inside jit/scan."""
    if repetition_penalty != 1.0:
        seen = rep_counts > 0
        penalized = jnp.where(logits > 0, logits / repetition_penalty,
                              logits * repetition_penalty)
        logits = jnp.where(seen, penalized, logits)
    if presence_penalty != 0.0 or frequency_penalty != 0.0:
        logits = (logits
                  - out_counts.astype(logits.dtype) * frequency_penalty
                  - (out_counts > 0).astype(logits.dtype)
                  * presence_penalty)
    return logits


def filter_logits(logits: jax.Array, top_k: int = 0,
                  top_p: float = 1.0) -> jax.Array:
    """top-k / top-p filtering over the last axis (-inf outside the set)."""
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep smallest set with cumulative prob >= top_p (always keep top-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def sample_token(
    logits: jax.Array,        # [B, V] f32
    key: jax.Array,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jax.Array:
    """Temperature / top-k / top-p sampling on device. Returns [B] int32."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = filter_logits(logits / temperature, top_k, top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class GenerationStats:
    """BenchmarkWrapper-compatible timing (reference
    dev/benchmark/benchmark_util.py:2447-2476: first_cost / rest_cost_mean)."""
    first_token_s: float = 0.0
    rest_token_s: List[float] = dataclasses.field(default_factory=list)

    @property
    def rest_cost_mean(self) -> float:
        return float(np.mean(self.rest_token_s)) if self.rest_token_s else 0.0


def generate_on_device(
    params: Dict[str, Any],
    cfg,
    forward_fn,
    input_ids: jax.Array,     # [B, S] int32 (right-padded ok if pos handled)
    cache: KVCache,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_token_id: Optional[int] = None,
    seed: int = 0,
    repetition_penalty: float = 1.0,
    presence_penalty: float = 0.0,
    frequency_penalty: float = 0.0,
) -> Tuple[jax.Array, KVCache]:
    """Whole-generation-on-device loop: prefill + `lax.scan` over decode
    steps inside ONE jittable function. No host sync per token — the
    TPU-idiomatic replacement for HF's Python generate loop: the host's
    per-token dispatch and readback never sit between two decode steps.

    Returns (generated [B, max_new_tokens], cache). After EOS, emits
    pad (0) tokens (masked continuation keeps shapes static).
    """
    b, s = input_ids.shape
    if s + max_new_tokens > cache.max_seq:
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"cache max_seq {cache.max_seq}")

    penal = (repetition_penalty != 1.0 or presence_penalty != 0.0
             or frequency_penalty != 0.0)

    logits, cache = forward_fn(params, cfg, input_ids, cache)
    last = logits[:, -1, :]
    key = jax.random.PRNGKey(seed)
    v = last.shape[-1]
    # rep counts include the prompt; out counts are generation-only
    # (vllm count-penalty semantics)
    rep0 = (token_counts(input_ids, v) if penal
            else jnp.zeros((b, 1), jnp.int32))      # dummy when off
    out0 = (jnp.zeros((b, v), jnp.int32) if penal
            else jnp.zeros((b, 1), jnp.int32))

    def pick(lg, k, rep, outc):
        if penal:
            lg = apply_penalties(lg, rep, outc, repetition_penalty,
                                 presence_penalty, frequency_penalty)
        return sample_token(lg, k, temperature=temperature, top_k=top_k,
                            top_p=top_p)

    def bump(counts, tok, done):
        if not penal:
            return counts
        rows = jnp.arange(counts.shape[0], dtype=jnp.int32)
        return counts.at[rows, tok].add((~done).astype(jnp.int32))

    key, sk = jax.random.split(key)
    tok0 = pick(last, sk, rep0, out0)
    done0 = (jnp.zeros((b,), jnp.bool_) if eos_token_id is None
             else tok0 == eos_token_id)
    never = jnp.zeros((b,), jnp.bool_)
    rep0 = bump(rep0, tok0, never)
    out0 = bump(out0, tok0, never)

    def step(carry, _):
        tok, done, cache, key, rep, outc = carry
        lg, cache = forward_fn(params, cfg, tok[:, None], cache)
        key, sk = jax.random.split(key)
        nxt = pick(lg[:, -1, :], sk, rep, outc)
        nxt = jnp.where(done, 0, nxt)
        rep = bump(rep, nxt, done)
        outc = bump(outc, nxt, done)
        if eos_token_id is not None:
            done = done | (nxt == eos_token_id)
        return (nxt, done, cache, key, rep, outc), nxt

    (_, _, cache, _, _, _), rest = lax.scan(
        step, (tok0, done0, cache, key, rep0, out0), None,
        length=max_new_tokens - 1)
    out = jnp.concatenate([tok0[:, None], rest.T], axis=1)
    return out, cache


def beam_search(
    params: Dict[str, Any],
    cfg,
    forward_fn,
    input_ids,                # [B, S] or [S] ints
    new_cache_fn,
    num_beams: int = 4,
    max_new_tokens: int = 32,
    max_seq: int = 2048,
    length_penalty: float = 1.0,
    eos_token_id: Optional[int] = None,
    prefill_fn=None,          # last-token-logits prefill variant, if any
) -> np.ndarray:
    """Greedy beam search -> best sequences [B, max_new_tokens].

    The HF-generate parity piece the reference gets for free from
    transformers (its native pipeline has no beams). Static-shape,
    TPU-first formulation: the batch expands to B*W rows sharing ONE
    compiled decode executable; each step is one jitted function that
    scores W*V continuations, selects the top W, and GATHERS the KV
    cache rows of the surviving parents (index bookkeeping — no
    reallocation). EOS beams freeze (their only continuation is pad at
    frozen score); the best beam by length-penalized score wins.
    Matches HF beam_search with early_stopping for the common cases;
    it does not keep a per-batch heap of >W finished hypotheses.
    """
    ids = np.asarray(input_ids, np.int32)
    if ids.ndim == 1:
        ids = ids[None]
    b, s = ids.shape
    w = num_beams
    if s + max_new_tokens > max_seq:
        raise ValueError("prompt + max_new_tokens exceeds max_seq")

    prefill_j, expand_j, select_j, reorder_decode_j = _beam_fns(
        cfg, forward_fn, prefill_fn, b, w, eos_token_id)

    # prefill at batch B, then REPEAT the cache rows per beam — all W
    # beams share the prompt KV, so prefilling B*W rows would waste
    # (W-1)/W of the dominant long-prompt cost; with a last-token
    # prefill_fn the [B, S, V] logits tensor is never materialized
    # either. (One executable per prompt LENGTH — warm common lengths
    # or go through Generator for bucketing.)
    cache1 = new_cache_fn(cfg, b, max_seq)
    lp_b, cache1 = prefill_j(params, jnp.asarray(ids), cache1)
    cache, gathered = _beam_expand_cache(cache1, expand_j, b, w)
    if not gathered:
        raise NotImplementedError(
            "beam search requires a cache with [.., batch, ..] leaves at "
            f"axis 1 (got {type(cache1).__name__} with none)")
    lp0 = jnp.repeat(lp_b, w, axis=0)                         # [B*W, V]

    # all beams identical after prefill: only beam 0 may seed candidates
    init_bias = jnp.full((w,), -jnp.inf).at[0].set(0.0)
    scores = jnp.tile(init_bias, (b,)).reshape(b, w)          # [B, W]
    done = jnp.zeros((b, w), jnp.bool_)
    toks = jnp.zeros((b, w, max_new_tokens), jnp.int32)
    lengths = jnp.zeros((b, w), jnp.int32)

    tok_flat, scores, done, lengths, toks, parent_flat = select_j(
        lp0, scores, done, lengths, toks, 0)
    for t in range(1, max_new_tokens):
        if bool(jnp.all(done)):
            break
        lp, cache = reorder_decode_j(params, parent_flat, cache, tok_flat)
        tok_flat, scores, done, lengths, toks, parent_flat = select_j(
            lp, scores, done, lengths, toks, t)

    final = scores / jnp.maximum(
        lengths.astype(jnp.float32), 1.0) ** length_penalty
    best = jnp.argmax(final, axis=1)                          # [B]
    out = jnp.take_along_axis(
        toks, best[:, None, None], axis=1)[:, 0]
    return np.asarray(out)


def _beam_expand_cache(cache1, expand_j, b: int, w: int):
    """Repeat batch-axis-1 cache leaves per beam. Returns (cache, n
    leaves expanded). Batch-axis CONTRACT: beam state must live on axis
    1 of >=2-D leaves (true of KVCache and every family cache built on
    it); other leaves must be beam-invariant (e.g. scalar positions,
    per-prompt anchors) — they are left untouched."""
    n_hit = 0

    def rep(x):
        nonlocal n_hit
        if getattr(x, "ndim", 0) >= 2 and x.shape[1] == b:
            n_hit += 1
            return expand_j(x)
        return x

    return jax.tree.map(rep, cache1), n_hit


@functools.lru_cache(maxsize=32)
def _beam_fns(cfg, forward_fn, prefill_fn, b: int, w: int, eos_token_id):
    """Jitted beam-search step functions, cached per geometry so repeated
    beam_search calls reuse the compiled executables (the free-function
    analog of Generator's cached prefill/decode)."""

    pre = prefill_fn or forward_fn
    prefill = tracked_jit("beam_prefill", lambda p, i, c: pre(p, cfg, i, c))

    def prefill_lp(p, i, c):
        lg, c = prefill(p, i, c)
        return jax.nn.log_softmax(
            lg[:, -1, :].astype(jnp.float32), -1), c

    expand = tracked_jit("beam_expand", lambda x: jnp.repeat(x, w, axis=1))

    @functools.partial(tracked_jit, "beam_select")
    def select(lp, scores, done, lengths, toks, t):
        """lp [B*W, V] log-probs -> (next_tok [B*W], new state)."""
        v = lp.shape[-1]
        lp = lp.reshape(b, w, v)
        # finished beams: only pad continues, at unchanged score
        pad_only = jnp.full((v,), -jnp.inf).at[0].set(0.0)
        lp = jnp.where(done[..., None], pad_only[None, None, :], lp)
        cand = scores[..., None] + lp                         # [B, W, V]
        flat = cand.reshape(b, w * v)
        top_sc, top_ix = jax.lax.top_k(flat, w)               # [B, W]
        parent = top_ix // v
        tok = (top_ix % v).astype(jnp.int32)
        # reorder per-beam state to the surviving parents
        gather = lambda x: jnp.take_along_axis(               # noqa: E731
            x, parent.reshape(b, w, *([1] * (x.ndim - 2))), axis=1)
        done_n = gather(done[..., None])[..., 0]
        lengths_n = gather(lengths[..., None])[..., 0]
        toks_n = gather(toks)
        toks_n = toks_n.at[:, :, t].set(jnp.where(done_n, 0, tok))
        lengths_n = jnp.where(done_n, lengths_n, lengths_n + 1)
        if eos_token_id is not None:
            done_n = done_n | (tok == eos_token_id)
        flat_parent = (jnp.arange(b, dtype=jnp.int32)[:, None] * w
                       + parent).reshape(-1)                  # [B*W]
        return (tok.reshape(-1), top_sc, done_n, lengths_n, toks_n,
                flat_parent)

    @functools.partial(tracked_jit, "beam_reorder_decode",
                       donate_argnums=(2,))
    def reorder_decode(params, parent_flat, cache, tok_flat):
        cache = jax.tree.map(
            lambda x: jnp.take(x, parent_flat, axis=1)
            if getattr(x, "ndim", 0) >= 2 and x.shape[1] == b * w else x,
            cache)
        lg, cache = forward_fn(params, cfg, tok_flat[:, None], cache)
        return jax.nn.log_softmax(
            lg[:, -1, :].astype(jnp.float32), -1), cache

    return prefill_lp, expand, select, reorder_decode


class Generator:
    """Compiled generate loop for a (params, config) pair.

    forward_fn(params, cfg, tokens, cache) -> (logits, cache); defaults to
    the llama forward. Prefill compiles per prompt-length bucket; decode
    compiles once. The KV cache buffer is donated between steps so XLA
    updates it in place.
    """

    def __init__(self, params: Dict[str, Any], cfg,
                 forward_fn=None, prefill_fn=None, max_seq: int = 2048,
                 kv_quantized=False, new_cache_fn=None,
                 recurrent: Optional[bool] = None,
                 kv_cache_dtype: Optional[str] = None,
                 faults=None):
        from bigdl_tpu.ops.kvcache import resolve_kv_cache_dtype
        from bigdl_tpu.robustness.faults import NULL as _no_faults

        # same fault-injection surface the serving engine exposes
        # (robustness/faults.py): chaos tests drive the offline decode
        # loop through identical step/logits hooks. Default: no-op.
        self.faults = faults if faults is not None else _no_faults
        self.params = params
        self.cfg = cfg
        self.max_seq = max_seq
        # canonical storage name; kv_quantized is the deprecated alias
        # (True -> fp8_e5m2) and also accepts a dtype name directly
        self.kv_cache_dtype = resolve_kv_cache_dtype(
            kv_cache_dtype if kv_cache_dtype is not None else kv_quantized)
        self.kv_quantized = self.kv_cache_dtype != "bf16"   # legacy mirror
        self.new_cache = new_cache_fn or llama_mod.new_cache
        self.recurrent = recurrent      # None: sniff from the cache type
        fwd = forward_fn or llama_mod.forward
        pre = prefill_fn or llama_mod.forward_last_token

        self._decode = tracked_jit(
            "generate_decode",
            lambda p, c, t, kv: fwd(p, c, t, kv), static_argnums=(1,),
            donate_argnums=(3,))
        self._prefill = tracked_jit(
            "generate_prefill",
            lambda p, c, t, kv: pre(p, c, t, kv), static_argnums=(1,),
            donate_argnums=(3,))
        # multimodal prefill (families whose prefill takes visual=):
        # built lazily so text-only models never trace it
        self._prefill_raw = pre
        self._prefill_vis = None
        self._sample = tracked_jit(
            "generate_sample", sample_token,
            static_argnames=("temperature", "top_k", "top_p"))

        def sample_pen(lg, k, rep_counts, out_counts, *, temperature,
                       top_k, top_p, rep, pres, freq):
            lg = apply_penalties(lg, rep_counts, out_counts, rep, pres,
                                 freq)
            tok = sample_token(lg, k, temperature=temperature, top_k=top_k,
                               top_p=top_p)
            rows = jnp.arange(rep_counts.shape[0], dtype=jnp.int32)
            rep_counts = rep_counts.at[rows, tok].add(1)
            out_counts = out_counts.at[rows, tok].add(1)
            return tok, rep_counts, out_counts

        self._sample_pen = tracked_jit(
            "generate_sample_pen", sample_pen,
            static_argnames=("temperature", "top_k", "top_p",
                             "rep", "pres", "freq"))

        def step_resident(p, c, tok, kv, key, finished, *, temperature,
                          top_k, top_p, eos):
            """ONE-dispatch decode step: layer-scanned forward + PRNG
            split + sampling + EOS masking fused into a single
            executable. Keeps the legacy step's exact op order (split
            THEN sample THEN mask) so greedy output is byte-identical
            and sampled output reuses the same key chain."""
            lg, kv = fwd(p, c, tok[:, None], kv)
            key, sk = jax.random.split(key)
            nxt = sample_token(lg[:, -1, :], sk, temperature=temperature,
                               top_k=top_k, top_p=top_p)
            if eos is not None:
                nxt = jnp.where(finished, 0, nxt)
                finished = finished | (nxt == eos)
            return nxt, kv, key, finished

        self._decode_resident = tracked_jit(
            "generate_decode_resident", step_resident,
            static_argnums=(1,), donate_argnums=(3,),
            static_argnames=("temperature", "top_k", "top_p", "eos"))
        self._counts = tracked_jit("generate_token_counts", token_counts,
                                   static_argnums=(1,))
        # phase timing published as bigdl_tpu_generate_{prefill,decode}
        # _seconds histograms (observability registry); .summary() gives
        # the host-side view
        from bigdl_tpu.utils.profiling import StepTimer

        self.step_timer = StepTimer(metrics_prefix="bigdl_tpu_generate")

    def _bucket(self, n: int) -> int:
        """Round prompt length up to a power-of-two bucket to bound the
        number of compiled prefill executables."""
        b = 16
        while b < n:
            b *= 2
        return min(b, self.max_seq)

    def _register_memory(self, cache, batch: int) -> None:
        """Record this generation's weights + cache footprint in the
        process memory ledger (observability/memory.py) — postmortems
        and bench memory reports read it. Best-effort."""
        try:
            from bigdl_tpu.observability.memory import (default_ledger,
                                                        tree_nbytes)

            led = default_ledger()
            led.register("weights", "generator_params",
                         tree_nbytes(self.params))
            led.register("kv_cache", "generator_cache",
                         tree_nbytes(cache),
                         dtype=self.kv_cache_dtype, batch=batch)
        except Exception:
            pass

    def generate(
        self,
        input_ids,                       # [B, S] or [S] ints
        gen: Optional[GenerationConfig] = None,
        stats: Optional[GenerationStats] = None,
        visual: Optional[Tuple[Any, Any]] = None,  # (vidx [B,S], vemb [Nv,D])
    ) -> np.ndarray:
        """Returns generated ids [B, <=max_new_tokens] (prompt excluded)."""
        return np.stack(list(self.stream(input_ids, gen, stats, visual)),
                        axis=1)

    def stream(
        self,
        input_ids,
        gen: Optional[GenerationConfig] = None,
        stats: Optional[GenerationStats] = None,
        visual: Optional[Tuple[Any, Any]] = None,
    ):
        """Token-by-token generation: yields [B] int32 per step — the
        streaming-callback surface the reference gets from FastChat's
        TextIteratorStreamer (serving/fastchat/ipex_llm_worker.py)."""
        gen = gen or GenerationConfig()
        ids = np.asarray(input_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        b, s = ids.shape
        if s + gen.max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({gen.max_new_tokens}) "
                f"exceeds max_seq {self.max_seq}")

        cache = self.new_cache(self.cfg, b, self.max_seq,
                               self.kv_cache_dtype)
        recurrent = (not isinstance(cache, KVCache)
                     if self.recurrent is None else self.recurrent)
        self._register_memory(cache, b)
        if recurrent:
            # recurrent families (RWKV): the state absorbs every token it
            # sees, so pad tokens cannot be masked retroactively — prefill
            # at the exact prompt length (one executable per length).
            bucket = s
        else:
            bucket = self._bucket(s)
        # right-pad into the bucket: positions stay correct for RoPE, the
        # garbage keys the pad writes are overwritten/masked (see below)
        pad = bucket - s
        padded = np.zeros((b, bucket), np.int32)
        padded[:, :s] = ids

        key = jax.random.PRNGKey(gen.seed)
        t0 = time.perf_counter()
        if visual is not None:
            vidx, vemb = visual
            vidx = np.asarray(vidx, np.int32)
            if pad > 0 and (vidx[:, s - 1] > 0).any():
                raise ValueError(
                    "prompt must end with at least one text token after "
                    "the final image span (the padded-prefill repair step "
                    "re-runs the last token without injection)")
            vpad = np.zeros((b, bucket), np.int32)
            vpad[:, :s] = vidx
            # bucket the embedding-row count too (power of two) so a
            # varying image count reuses one compiled prefill — padding
            # rows are never gathered (vidx only references real rows)
            vemb = np.asarray(vemb)
            rows = max(16, 1 << (int(vemb.shape[0]) - 1).bit_length())
            if rows != vemb.shape[0]:
                vemb = np.concatenate(
                    [vemb, np.zeros((rows - vemb.shape[0],) +
                                    vemb.shape[1:], vemb.dtype)])
            if self._prefill_vis is None:
                self._prefill_vis = tracked_jit(
                    "generate_prefill_vis",
                    lambda p, c, t, kv, vi, ve: self._prefill_raw(
                        p, c, t, kv, visual=(vi, ve)),
                    static_argnums=(1,), donate_argnums=(3,))
            logits, cache = self._prefill_vis(
                self.params, self.cfg, jnp.asarray(padded), cache,
                jnp.asarray(vpad), jnp.asarray(vemb))
        else:
            logits, cache = self._prefill(
                self.params, self.cfg, jnp.asarray(padded), cache)
        # logits from forward_last_token are for the LAST cache position
        # (bucket-1); when padded, recompute pointer: forward_last_token
        # returns position bucket-1 which may be padding. Use full-forward
        # logits gather instead when pad > 0.
        if pad > 0:
            # cheap fix: decode path needs logits at position s-1; rerun the
            # last real token through decode after trimming cache.pos
            # (reset_pos keeps non-KVCache cache types' extra state)
            cache = cache.reset_pos(jnp.asarray(s - 1, jnp.int32))
            logits, cache = self._decode(
                self.params, self.cfg, jnp.asarray(ids[:, -1:]), cache)
        else:
            logits = logits[:, -1:, :]

        temp = gen.temperature if gen.do_sample else 0.0

        penal = gen.needs_token_counts
        if penal:
            v = logits.shape[-1]
            counts = self._counts(jnp.asarray(padded), v,
                                  jnp.full((b,), s, jnp.int32))
            out_counts = jnp.zeros((b, v), jnp.int32)

        def sample(lg, k):
            nonlocal counts, out_counts
            if penal:
                t, counts, out_counts = self._sample_pen(
                    lg, k, counts, out_counts, temperature=temp,
                    top_k=gen.top_k, top_p=gen.top_p,
                    rep=gen.repetition_penalty,
                    pres=gen.presence_penalty, freq=gen.frequency_penalty)
                return t
            return self._sample(lg, k, temperature=temp, top_k=gen.top_k,
                                top_p=gen.top_p)

        if gen.check_logits and not np.isfinite(
                np.asarray(logits[:, -1, :])).all():
            raise FloatingPointError("non-finite logits after prefill")

        key, sk = jax.random.split(key)
        tok = sample(logits[:, -1, :], sk)
        tok_host = np.asarray(tok)
        self.step_timer.record("prefill", time.perf_counter() - t0)
        if stats is not None:
            stats.first_token_s = time.perf_counter() - t0

        yield tok_host
        finished = np.zeros((b,), bool)
        finished_dev = jnp.zeros((b,), jnp.bool_)
        if gen.eos_token_id is not None:
            finished |= tok_host == gen.eos_token_id
            finished_dev = jnp.asarray(finished)

        # resident single-dispatch decode (ISSUE 14b): forward + PRNG
        # split + sampling + EOS masking run as ONE executable per token,
        # so the host dispatch overhead is paid once per step instead
        # of once per phase. Host-side per-step work (penalty counters
        # via _sample_pen's nonlocals, fault hooks, check_logits pulls)
        # keeps the legacy multi-dispatch loop.
        from bigdl_tpu.config import decode_resident_enabled
        from bigdl_tpu.robustness.faults import NULL as _no_faults

        resident = (decode_resident_enabled() and not penal
                    and not gen.check_logits
                    and self.faults is _no_faults)

        for step_i in range(1, gen.max_new_tokens):
            if finished.all():
                break
            t1 = time.perf_counter()
            if resident:
                tok, cache, key, finished_dev = self._decode_resident(
                    self.params, self.cfg, tok, cache, key, finished_dev,
                    temperature=temp, top_k=gen.top_k, top_p=gen.top_p,
                    eos=gen.eos_token_id)
                tok_host = np.asarray(tok)
                self.step_timer.record("decode", time.perf_counter() - t1)
                if stats is not None:
                    stats.rest_token_s.append(time.perf_counter() - t1)
                yield tok_host
                if gen.eos_token_id is not None:
                    finished |= tok_host == gen.eos_token_id
                continue
            # fault hooks mirror the serving engine's step points
            self.faults.raise_point("step", step_i)
            ms = self.faults.sleep_ms("step", step_i)
            if ms > 0:
                time.sleep(ms / 1000.0)
            logits, cache = self._decode(
                self.params, self.cfg, tok[:, None], cache)
            bad = self.faults.poison_rows(step_i, list(range(b)))
            if bad:
                logits = logits.at[jnp.asarray(bad)].set(jnp.nan)
            if gen.check_logits and not np.isfinite(
                    np.asarray(logits[:, -1, :])).all():
                raise FloatingPointError(
                    f"non-finite logits at decode step {step_i}")
            key, sk = jax.random.split(key)
            tok = sample(logits[:, -1, :], sk)
            if gen.eos_token_id is not None:
                # post-EOS rows emit pad (0): parity with generate_on_device.
                # Mask and track EOS on device; nothing is uploaded per step.
                tok = jnp.where(finished_dev, 0, tok)
                finished_dev = finished_dev | (tok == gen.eos_token_id)
            tok_host = np.asarray(tok)
            self.step_timer.record("decode", time.perf_counter() - t1)
            if stats is not None:
                stats.rest_token_s.append(time.perf_counter() - t1)
            yield tok_host
            if gen.eos_token_id is not None:
                finished |= tok_host == gen.eos_token_id
