"""Self-speculative decoding: low-bit draft proposes, target verifies.

TPU-native re-design of the reference's `speculative_generate` (reference
transformers/speculative.py:443-1022: host-side draft loop with adaptive
early stop, batched verify forward, greedy prefix-match or min(1,q/p)
rejection-sampling accept, and KV-cache rollback done by slicing/copying
cache tensors per architecture, speculative.py:393-439).

Everything that made the reference's version hard on accelerators is
restructured for XLA:

- **One dispatch per round.** Draft loop (`lax.while_loop`, early-exiting
  on draft confidence), target verify (one gamma+1-token forward), accept
  computation, and the cache rollback all run inside ONE jitted function;
  the host reads back one small (tokens, n_accept) tuple per round. The
  reference pays a host round-trip per draft token.
- **Rollback is index bookkeeping, not realloc.** Our KV caches are
  pre-allocated with validity tracked by a scalar `pos` (ops/kvcache.py);
  rejected entries beyond the accepted prefix are simply left in place —
  masked by position until overwritten. The reference copies/extends cache
  tensors (`_check_and_extend_kv_cache`).
- **Bonus token on full accept.** Verify runs over [cur, d_1..d_gamma]
  (gamma+1 positions), so a fully-accepted round emits gamma+1 tokens —
  the reference's bonus token (speculative.py ~:826), kept jit-static by
  one extra draft catch-up step that writes the last proposed token's KV.
- **Adaptive draft stop, compiled.** The draft while_loop exits when the
  draft's own probability of its pick drops below `th_stop_draft`
  (reference th_stop_draft, speculative.py:63) — saving the remaining
  draft forwards; the threshold is a traced scalar, so the host can adapt
  it between rounds (auto_th_stop_draft) with NO recompilation.

The serving engine speculates too (`EngineConfig.speculative_tokens`,
for a family with its own draft module: a multi-token-prediction block
that drafts one token a slot and a two-row verify inside the resident
step). It shares `accept_and_resample` with the offline round here, so
there is one accept rule to test; the paths below stay the offline
`generate` forms for a separate draft model or a prompt lookup.

The draft is typically the same checkpoint at sym_int4 (self-speculation,
reference model.py:323-331) and the target bf16/fp8 — both share one
tokenizer, so only token ids cross model boundaries.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bigdl_tpu.observability.compile_watch import tracked_jit
from bigdl_tpu.ops.kvcache import KVCache


@dataclasses.dataclass
class SpecStats:
    """Reference telemetry equivalent (speculative.py:143-151:
    draft_time/verify_time/accept_num + draft_num for the auto
    threshold)."""
    rounds: int = 0
    accepted: List[int] = dataclasses.field(default_factory=list)
    drafted: List[int] = dataclasses.field(default_factory=list)
    round_s: List[float] = dataclasses.field(default_factory=list)
    first_token_s: float = 0.0

    @property
    def mean_accept(self) -> float:
        return float(np.mean(self.accepted)) if self.accepted else 0.0

    @property
    def accept_rate(self) -> float:
        d = float(np.sum(self.drafted))
        return float(np.sum(self.accepted)) / d if d else 0.0

    @property
    def tokens_per_round(self) -> float:
        return self.mean_accept + 1.0


def _spec_observe(mode: str, n_accept: int, n_draft: int,
                  round_s: float) -> None:
    """Publish one verify round to the observability registry
    (bigdl_tpu_spec_accept_ratio / _round_seconds / _tokens_total,
    labeled mode="draft_model"|"prompt_lookup"). Unconditional — unlike
    SpecStats, which only exists when the caller asks for it."""
    try:
        from bigdl_tpu.observability.metrics import (RATIO_BUCKETS,
                                                     default_registry)

        m = default_registry()
        if n_draft > 0:
            m.histogram("bigdl_tpu_spec_accept_ratio",
                        "Speculative decoding acceptance ratio per "
                        "verify round.", labelnames=("mode",),
                        buckets=RATIO_BUCKETS,
                        ).labels(mode).observe(n_accept / n_draft)
        m.histogram("bigdl_tpu_spec_round_seconds",
                    "Wall time of one draft+verify round.",
                    labelnames=("mode",)).labels(mode).observe(round_s)
        tok = m.counter("bigdl_tpu_spec_tokens_total",
                        "Draft tokens proposed / accepted.",
                        labelnames=("mode", "kind"))
        tok.labels(mode, "drafted").inc(n_draft)
        tok.labels(mode, "accepted").inc(n_accept)
    except Exception:
        pass  # telemetry must never break the decode loop


def accept_and_resample(p: jax.Array, q: jax.Array, drafts: jax.Array,
                        u: jax.Array, valid=None):
    """The exact speculative-sampling rule, once, for every path that
    speculates (this module's offline round and the serving engine's
    verify step, `serving/engine.py`).

    `p` `[B, G + 1, V]`: the target's distributions at the G drafted
    positions and the one after; `q` `[B, G, V]`: the distributions the
    drafts `drafts` `[B, G]` were drawn from (under the SAME temperature
    / top-k / top-p transform as `p`); `u` `[B, G]` uniforms; `valid`
    `[B or 1, G]` bool, False where no draft was made.

    Draft i is accepted with probability `min(1, p_i(d_i) / q_i(d_i))`,
    and only while every earlier one was. Returns `n_accept` `[B]` and
    `dist` `[B, V]`, the distribution of the token at position
    `n_accept`: `normalize(max(p - q, 0))` after a true rejection, the
    target's own after a full accept (the bonus token). The emitted
    stream's distribution is the target's exactly, whatever `q` is."""
    g = drafts.shape[1]
    p_tok = jnp.take_along_axis(p[:, :g], drafts[..., None], axis=-1)[..., 0]
    q_tok = jnp.take_along_axis(q, drafts[..., None], axis=-1)[..., 0]
    accepted = u < jnp.minimum(1.0, p_tok / jnp.maximum(q_tok, 1e-20))
    n_draft = g
    if valid is not None:
        accepted = accepted & valid
        n_draft = jnp.sum(valid, axis=1)
    n_accept = jnp.sum(jnp.cumprod(accepted.astype(jnp.int32), axis=1),
                       axis=1)
    p_n = jnp.take_along_axis(p, n_accept[:, None, None], axis=1)[:, 0]
    q_pad = jnp.concatenate([q, jnp.zeros_like(q[:, :1])], axis=1)
    q_n = jnp.take_along_axis(q_pad, n_accept[:, None, None], axis=1)[:, 0]
    resid = jnp.maximum(p_n - q_n, 0.0)
    resid_sum = jnp.sum(resid, axis=-1, keepdims=True)
    true_reject = n_accept < n_draft
    dist = jnp.where(
        (true_reject & (resid_sum[:, 0] > 1e-9))[:, None],
        resid / jnp.maximum(resid_sum, 1e-20), p_n)
    return n_accept, dist


def make_spec_round(
    fwd_target: Callable,
    cfg_target: Any,
    fwd_draft: Callable,
    cfg_draft: Any,
    gamma: int,
    do_sample: bool = False,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
):
    """Build the fused per-round executable.

    round(params_t, params_d, cache_t, cache_d, cur_tok, key, th_stop) ->
        (out_tokens [B, gamma+1], n_accept [B], n_draft scalar,
         cache_t, cache_d, key)

    Emits n_accept+1 valid tokens per round: the accepted drafts plus the
    target's token at the first divergence — or, on a full accept of all
    n_draft proposals, the target's BONUS token after the last draft.
    `th_stop` (f32 scalar, traced) stops drafting early when the draft's
    confidence in its own pick falls below it; 0.0 drafts all gamma.
    """

    sampling = do_sample and temperature > 0.0

    @functools.partial(tracked_jit, "spec_round", donate_argnums=(2, 3))
    def spec_round(params_t, params_d, cache_t: KVCache, cache_d: KVCache,
                   cur_tok: jax.Array, key: jax.Array, th_stop: jax.Array):
        b = cur_tok.shape[0]
        pos0 = cache_t.pos

        # --- draft: up to gamma proposals + ONE catch-up step that only
        # writes the last proposal's KV (so a full accept + bonus leaves
        # the draft cache consistent) ---
        def one_draft(tok, cache, k):
            logits, cache = fwd_draft(params_d, cfg_draft, tok[:, None],
                                      cache)
            lg = logits[:, -1, :].astype(jnp.float32)
            if sampling:
                # identical tempering for the draw and the recorded q —
                # the accept ratio must use the true draft distribution
                tempered = lg / max(temperature, 1e-6)
                k, sk = jax.random.split(k)
                nxt = jax.random.categorical(
                    sk, tempered, axis=-1).astype(jnp.int32)
                q = jax.nn.softmax(tempered, axis=-1)
            else:
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                q = jax.nn.softmax(lg, axis=-1)
            conf = jnp.take_along_axis(q, nxt[:, None], axis=-1)[:, 0]
            return nxt, q, conf, cache, k

        # probe vocab once (first step always runs; also j=0 of the loop)
        key, dk = jax.random.split(key)
        d1, q1, conf1, cache_d, dk = one_draft(cur_tok, cache_d, dk)
        vocab = q1.shape[-1]
        buf_toks = jnp.zeros((gamma, b), jnp.int32).at[0].set(d1)
        buf_q = jnp.zeros((gamma, b, vocab), jnp.float32).at[0].set(q1)

        def cond(c):
            j, _, _, _, going = c
            return going & (j < gamma)

        def body(c):
            j, cache, k, bufs, _ = c
            toks, qs = bufs
            tok_j = toks[j - 1]                       # consume d_j
            d, q, cnf, cache, k = one_draft(tok_j, cache, k)
            toks = toks.at[j].set(d)
            qs = qs.at[j].set(q)
            # gate the NEXT iteration on this fresh proposal's confidence
            going = jnp.all(cnf >= th_stop)
            return (j + 1, cache, k, (toks, qs), going)

        going0 = jnp.all(conf1 >= th_stop)
        n_draft, cache_d, dk, (buf_toks, buf_q), _ = lax.while_loop(
            cond, body,
            (jnp.asarray(1, jnp.int32), cache_d, dk, (buf_toks, buf_q),
             going0))
        # catch-up: consume the last proposal so its KV is written;
        # its output token is discarded
        _, _, _, cache_d, _ = one_draft(buf_toks[n_draft - 1], cache_d, dk)

        draft_toks = buf_toks.T                     # [B, gamma]
        draft_q = jnp.moveaxis(buf_q, 0, 1)         # [B, gamma, V]

        # --- verify: ONE target forward over [cur, d_1..d_gamma] ---
        verify_in = jnp.concatenate([cur_tok[:, None], draft_toks], axis=1)
        logits_t, cache_t = fwd_target(params_t, cfg_target, verify_in,
                                       cache_t)     # [B, gamma+1, V]

        valid = jnp.arange(gamma)[None, :] < n_draft  # [1|B, gamma]

        if sampling:
            # min(1, p/q) rejection sampling (the reference's sampling
            # accept, speculative.py ~:775: q>=p accept / rejected resample)
            from bigdl_tpu.generation import filter_logits

            p = jax.nn.softmax(filter_logits(
                logits_t.astype(jnp.float32) / temperature, top_k, top_p),
                axis=-1)                            # [B, gamma+1, V]
            key, uk, rk = jax.random.split(key, 3)
            u = jax.random.uniform(uk, draft_toks.shape)
            # token at position n: residual (p - q)+ on a true rejection
            # (n < n_draft); the target distribution itself on a full
            # accept (bonus token)
            n_accept, dist = accept_and_resample(
                p, draft_q, draft_toks, u,
                jnp.broadcast_to(valid, draft_toks.shape))
            correction = jax.random.categorical(
                rk, jnp.log(jnp.maximum(dist, 1e-20)), axis=-1
            ).astype(jnp.int32)                     # [B]
            idx = jnp.arange(gamma + 1)[None, :]
            out = jnp.where(
                idx < n_accept[:, None],
                jnp.concatenate([draft_toks, draft_toks[:, -1:]], axis=1),
                correction[:, None])
        else:
            target_pred = jnp.argmax(logits_t, axis=-1).astype(jnp.int32)
            # --- accept: greedy prefix match over the proposed prefix ---
            matches = (draft_toks == target_pred[:, :-1]) & valid
            n_accept = jnp.sum(
                jnp.cumprod(matches.astype(jnp.int32), axis=1), axis=1)
            # out[i] = d_{i+1} for i < n_accept; target's token at
            # position n_accept (divergence fix OR bonus); garbage after
            idx = jnp.arange(gamma + 1)[None, :]
            out = jnp.where(
                idx < n_accept[:, None],
                jnp.concatenate([draft_toks, draft_toks[:, -1:]], axis=1),
                jnp.take_along_axis(target_pred, n_accept[:, None], axis=1))

        # --- rollback: pure index bookkeeping (reset_pos keeps any
        # family-specific cache state, e.g. ChatGLMCache anchors) ---
        new_pos = pos0 + n_accept[0] + 1            # B=1: scalar pos
        return (out, n_accept, n_draft, cache_t.reset_pos(new_pos),
                cache_d.reset_pos(new_pos), key)

    return spec_round


def _update_threshold(th: float, accept_rate: float,
                      target: float = 0.9, step: float = 0.02,
                      lo: float = 0.0, hi: float = 0.95) -> float:
    """auto_th_stop_draft (reference speculative.py:63-64,81): nudge the
    stop threshold toward a target per-round accept rate. Low accept rate
    -> raise the bar (draft fewer, surer tokens); high -> lower it."""
    return float(np.clip(th + (step if accept_rate < target else -step),
                         lo, hi))


def speculative_generate(
    params_target: Any,
    params_draft: Any,
    cfg_target: Any,
    cfg_draft: Any,
    input_ids,                              # [S] or [1, S] ints
    *,
    family_forward: Callable,
    family_prefill: Callable,
    new_cache: Callable,                    # (cfg, batch, max_seq) -> KVCache
    max_new_tokens: int = 128,
    gamma: int = 4,
    do_sample: bool = False,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_token_id: Optional[int] = None,
    max_seq: int = 2048,
    seed: int = 0,
    kv_quantized=False,
    kv_cache_dtype: Optional[str] = None,
    th_stop_draft: float = 0.8,
    auto_th_stop_draft: bool = True,
    stats: Optional[SpecStats] = None,
) -> np.ndarray:
    """Generate with draft/verify speculation. Returns new tokens [1, <=N].

    `family_forward/prefill` serve both models (self-speculation: same
    architecture, different qtype). `th_stop_draft`/`auto_th_stop_draft`
    mirror the reference's adaptive draft control (speculative.py:63-64);
    set th_stop_draft=0.0 to always draft the full gamma.
    """
    ids = np.asarray(input_ids, np.int32)
    if ids.ndim == 1:
        ids = ids[None]
    if ids.shape[0] != 1:
        raise ValueError("speculative decoding supports batch size 1 "
                         "(as the reference does)")
    s = ids.shape[1]
    if s + max_new_tokens + gamma + 1 > max_seq:
        raise ValueError(f"prompt ({s}) + max_new_tokens ({max_new_tokens}) "
                         f"+ gamma+1 ({gamma + 1}) exceeds max_seq {max_seq}")

    from bigdl_tpu.ops.kvcache import resolve_kv_cache_dtype

    # canonical dtype string rides the legacy positional `quantized` slot
    # of the family new_cache adapters (they resolve bools and names)
    kv_dtype = resolve_kv_cache_dtype(
        kv_cache_dtype if kv_cache_dtype is not None else kv_quantized)
    cache_t = new_cache(cfg_target, 1, max_seq, kv_dtype)
    cache_d = new_cache(cfg_draft, 1, max_seq, kv_dtype)

    prefill = tracked_jit("spec_prefill", family_prefill,
                          static_argnums=1, donate_argnums=3)

    t0 = time.perf_counter()
    toks = jnp.asarray(ids)
    logits_t, cache_t = prefill(params_target, cfg_target, toks, cache_t)
    _, cache_d = prefill(params_draft, cfg_draft, toks, cache_d)
    cur = jnp.argmax(logits_t[:, -1, :], axis=-1).astype(jnp.int32)
    cur_host = int(np.asarray(cur)[0])
    if stats is not None:
        stats.first_token_s = time.perf_counter() - t0

    spec_round = make_spec_round(
        family_forward, cfg_target, family_forward, cfg_draft, gamma,
        do_sample=do_sample, temperature=temperature, top_k=top_k,
        top_p=top_p)

    out: List[int] = [cur_host]
    key = jax.random.PRNGKey(seed)
    th = float(th_stop_draft)
    while len(out) < max_new_tokens:
        if eos_token_id is not None and out and out[-1] == eos_token_id:
            break
        t1 = time.perf_counter()
        toks_r, n_acc, n_drf, cache_t, cache_d, key = spec_round(
            params_target, params_draft, cache_t, cache_d, cur, key,
            jnp.asarray(th, jnp.float32))
        toks_host = np.asarray(toks_r)[0]
        n = int(np.asarray(n_acc)[0])
        nd = int(np.asarray(n_drf))      # scalar loop counter
        round_s = time.perf_counter() - t1
        _spec_observe("draft_model", n, nd, round_s)
        if stats is not None:
            stats.rounds += 1
            stats.accepted.append(n)
            stats.drafted.append(nd)
            stats.round_s.append(round_s)
        if auto_th_stop_draft and th_stop_draft > 0.0:
            th = _update_threshold(th, n / max(nd, 1))
        emitted = list(toks_host[: n + 1])
        if eos_token_id is not None and eos_token_id in emitted:
            emitted = emitted[: emitted.index(eos_token_id) + 1]
        out.extend(int(t) for t in emitted)
        cur = toks_r[:, n]
    return np.asarray(out[:max_new_tokens], np.int32)[None]


# ---------------------------------------------------------------------------
# Prompt-lookup speculation: n-gram drafts from the token HISTORY, no
# draft model at all (beyond the reference, whose only speculation is
# self-speculation with a quantized draft model, speculative.py:443).
# Greedy decoding stays EXACT — the target verifies every proposal —
# while repetitive spans (code, quotes, retrieved context) decode up to
# gamma+1 tokens per target forward.


def make_lookup_round(fwd_target: Callable, cfg_target: Any, gamma: int,
                      ngram: int = 2):
    """Build the fused per-round executable for prompt-lookup.

    round(params_t, cache_t, hist, hist_len, cur_tok) ->
        (out_tokens [1, gamma+1], n_accept [1], found flag, cache_t)

    The driver below intentionally mirrors speculative_generate's loop
    (same validation, prefill timing, eos truncation) with lookup state
    instead of draft-model state — keep edits to either in sync.

    `hist` is the full token sequence so far (prompt + emitted), valid
    up to `hist_len`, with `cur_tok == hist[hist_len-1]`. The draft is
    the gamma tokens FOLLOWING the most recent earlier occurrence of the
    trailing `ngram` tokens; with no match the round degrades to a
    plain (verified) single-token step. All index work happens on
    device — no host sync inside the round.
    """

    @functools.partial(tracked_jit, "lookup_round", donate_argnums=(1,))
    def lookup_round(params_t, cache_t: KVCache, hist: jax.Array,
                     hist_len: jax.Array, cur_tok: jax.Array):
        pos0 = cache_t.pos
        size = hist.shape[0]
        pos_ar = jnp.arange(size, dtype=jnp.int32)

        # positions p whose trailing ngram equals the CURRENT trailing
        # ngram (hist[hist_len-ngram .. hist_len-1]); p itself must be
        # strictly before the current position so the draft is history
        match = (pos_ar >= ngram - 1) & (pos_ar < hist_len - 1)
        for j in range(ngram):
            h_at = hist[jnp.clip(pos_ar - j, 0, size - 1)]
            match &= h_at == hist[jnp.clip(hist_len - 1 - j, 0, size - 1)]
        p_best = jnp.max(jnp.where(match, pos_ar, -1))
        found = p_best >= 0

        draft = jnp.where(
            found,
            hist[jnp.clip(p_best + 1 + jnp.arange(gamma), 0, size - 1)],
            0)[None, :]                                     # [1, gamma]
        # proposals past the end of written history are stale guesses;
        # they simply fail verification

        verify_in = jnp.concatenate([cur_tok[:, None], draft], axis=1)
        logits_t, cache_t = fwd_target(params_t, cfg_target, verify_in,
                                       cache_t)             # [1, g+1, V]
        target_pred = jnp.argmax(logits_t, axis=-1).astype(jnp.int32)

        matches = (draft == target_pred[:, :-1]) & found
        n_accept = jnp.sum(
            jnp.cumprod(matches.astype(jnp.int32), axis=1), axis=1)
        idx = jnp.arange(gamma + 1)[None, :]
        out = jnp.where(
            idx < n_accept[:, None],
            jnp.concatenate([draft, draft[:, -1:]], axis=1),
            jnp.take_along_axis(target_pred, n_accept[:, None], axis=1))

        new_pos = pos0 + n_accept[0] + 1
        return out, n_accept, found, cache_t.reset_pos(new_pos)

    return lookup_round


def prompt_lookup_generate(
    params: Any,
    cfg: Any,
    input_ids,                              # [S] or [1, S] ints
    *,
    family_forward: Callable,
    family_prefill: Callable,
    new_cache: Callable,                    # (cfg, batch, max_seq) -> KVCache
    max_new_tokens: int = 128,
    gamma: int = 8,
    ngram: int = 2,
    eos_token_id: Optional[int] = None,
    max_seq: int = 2048,
    kv_quantized=False,
    kv_cache_dtype: Optional[str] = None,
    stats: Optional[SpecStats] = None,
) -> np.ndarray:
    """Greedy generation with prompt-lookup speculation. Returns new
    tokens [1, <=N], identical to plain greedy decoding."""
    ids = np.asarray(input_ids, np.int32)
    if ids.ndim == 1:
        ids = ids[None]
    if ids.shape[0] != 1:
        raise ValueError("prompt-lookup decoding supports batch size 1")
    s = ids.shape[1]
    if s + max_new_tokens + gamma + 1 > max_seq:
        raise ValueError(f"prompt ({s}) + max_new_tokens "
                         f"({max_new_tokens}) + gamma+1 ({gamma + 1}) "
                         f"exceeds max_seq {max_seq}")

    from bigdl_tpu.ops.kvcache import resolve_kv_cache_dtype

    cache = new_cache(cfg, 1, max_seq, resolve_kv_cache_dtype(
        kv_cache_dtype if kv_cache_dtype is not None else kv_quantized))
    prefill = tracked_jit("lookup_prefill", family_prefill,
                          static_argnums=1, donate_argnums=3)

    t0 = time.perf_counter()
    logits, cache = prefill(params, cfg, jnp.asarray(ids), cache)
    cur = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    cur_host = int(np.asarray(cur)[0])
    if stats is not None:
        stats.first_token_s = time.perf_counter() - t0

    lookup_round = make_lookup_round(family_forward, cfg, gamma, ngram)

    hist = np.zeros((max_seq,), np.int32)
    hist[:s] = ids[0]
    hist_len = s + 1
    hist[s] = cur_host

    out: List[int] = [cur_host]
    while len(out) < max_new_tokens:
        if eos_token_id is not None and out[-1] == eos_token_id:
            break
        t1 = time.perf_counter()
        toks_r, n_acc, found, cache = lookup_round(
            params, cache, jnp.asarray(hist),
            jnp.asarray(hist_len, jnp.int32), cur)
        toks_host = np.asarray(toks_r)[0]
        n = int(np.asarray(n_acc)[0])
        round_s = time.perf_counter() - t1
        # a no-match round proposed NOTHING — recording gamma would
        # deflate accept_rate vs draft-model speculation, whose
        # driver records the true n_draft
        nd = gamma if bool(np.asarray(found)) else 0
        _spec_observe("prompt_lookup", n, nd, round_s)
        if stats is not None:
            stats.rounds += 1
            stats.accepted.append(n)
            stats.drafted.append(nd)
            stats.round_s.append(round_s)
        emitted = list(toks_host[: n + 1])
        if eos_token_id is not None and eos_token_id in emitted:
            emitted = emitted[: emitted.index(eos_token_id) + 1]
        out.extend(int(t) for t in emitted)
        k = len(emitted)
        hist[hist_len: hist_len + k] = emitted[:max(0, max_seq - hist_len)]
        hist_len = min(hist_len + k, max_seq)
        cur = toks_r[:, min(n, gamma)]
    return np.asarray(out[:max_new_tokens], np.int32)[None]
