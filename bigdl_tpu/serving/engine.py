"""Continuous-batching serving engine, TPU-native.

Re-design of the reference's vLLM port (reference vllm/engine/llm_engine.py:
66-687 `LLMEngine.step`, vllm/core/scheduler.py:93 `FixedWindowScheduler`,
vllm/worker/worker.py:260 single in-process worker, per-sequence padded KV
dicts at vllm/model_executor/models/bigdl_model.py:88-139).

The reference re-pads and re-assembles a python dict of per-sequence KV
tensors every step — unusable under XLA. Here the design is slot-based and
fully static:

- ONE batched KV cache [L, max_batch, max_seq, H, D] with a per-slot
  position vector (ops/kvcache.py per_slot_pos). A slot is a sequence's
  home for its whole lifetime; admission = prefill into the slot,
  completion = slot freed (pos reset), nothing ever re-pads or copies KV.
- ONE compiled decode executable for the whole engine lifetime: tokens
  [max_batch] + cache -> logits. Finished/empty slots decode garbage
  that is never read — the FLOP cost of static shapes, repaid by zero
  recompiles and an always-full MXU batch. Their token is -1, which is
  how the program knows them: an empty slot's position goes into the
  forward as -1 (decode attention multiplies nothing for it) and stays 0 in
  the cache, so an idle row does not deepen.
- Prefill is compiled per prompt-length bucket and writes K/V straight
  into the batched cache at the slot index.
- Scheduling is FCFS admission (the reference's FixedWindowScheduler
  semantics) driven from `step()`. Sampling: per-slot temperature/top-k/
  top-p/seed runs batched ON DEVICE (gumbel-max; only [B] ints reach
  the host); slots needing penalty counts or logprobs fall back to the
  host sampler (the reference's BigDLSampler role, which is host-side
  for every request).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.config import (decode_resident_enabled, flags,
                              quality_enabled, resolve_kv_page_size,
                              resolve_kv_pages, resolve_prefix_sharing,
                              sentinel_enabled)
from bigdl_tpu.observability import roofline
from bigdl_tpu.observability.compile_watch import (annotate_costs,
                                                   compiles_in_progress,
                                                   declare_startup_metrics,
                                                   top_offenders,
                                                   tracked_jit)
from bigdl_tpu.observability.compile_watch import mark as startup_mark
from bigdl_tpu.observability.quality import (GOLDEN_PROBE_PROMPTS,
                                             QUALITY_METRICS,
                                             QualitySentinel,
                                             golden_nll_allowance,
                                             resolve_quality_probe_steps)
from bigdl_tpu.observability.sentinel import PerfSentinel
from bigdl_tpu.observability.disttrace import SpanRecorder, new_span_id
from bigdl_tpu.observability.flight import (FlightRecorder, build_postmortem,
                                            exception_fields)
from bigdl_tpu.observability.flight import write_postmortem as \
    _write_postmortem_file
from bigdl_tpu.observability.memory import MemoryLedger, tree_nbytes
from bigdl_tpu.observability.metrics import (RATIO_BUCKETS,
                                             STEP_WALL_BUCKETS_S,
                                             default_registry)
from bigdl_tpu.observability.slo import SLOTracker
from bigdl_tpu.observability.stats import ewma as stats_ewma
from bigdl_tpu.observability.tracing import (ADMISSION_KIND, PhaseClock,
                                             RequestTracer)
from bigdl_tpu.observability.usage import UsageLedger
from bigdl_tpu.ops.dsa import kth_largest
from bigdl_tpu.ops.eva import rows_read
from bigdl_tpu.ops.kvcache import (SNAPSHOT_REFUSAL, KVCache, cache_nbytes,
                                   cache_spec_of, init_cache_spec,
                                   kv_cache_bytes, publish_kv_cache_bytes,
                                   resolve_kv_cache_dtype)
from bigdl_tpu.ops.pallas.decode_attention import blocks_read, slab_blocks
from bigdl_tpu.ops.pallas.paged_decode_attention import pages_read
from bigdl_tpu.ops.paged import (NULL_PAGE, cow_copy_pages,
                                 gather_pages_dense, paged_cache_bytes,
                                 publish_paged_cache_bytes, splice_pages)
from bigdl_tpu.ops.pallas.swa_attention import ring_blocks
from bigdl_tpu.ops.swa import rows_read as swa_rows_read
from bigdl_tpu.robustness import (resolve_drain_timeout_sec,
                                  resolve_request_deadline_ms)
from bigdl_tpu.robustness.faults import FaultInjector
from bigdl_tpu.serving.overload import (QOS_CLASSES, SHED_REASONS,
                                        OverloadConfig, OverloadController,
                                        RequestShed)
from bigdl_tpu.serving.pagepool import PagePool, RadixCache
from bigdl_tpu.utils.profiling import annotate

# decode-step EWMA over its floor at which the brownout ladder's
# latency-inflation signal reads 1.0
_INFLATION_SATURATES = 3.0


class EngineDraining(RuntimeError):
    """Raised by ``add_request`` while the engine drains (SIGTERM /
    ``begin_drain``): the caller should retry against another replica.
    The API server maps it to 503 + ``Retry-After``."""


#: ``bigdl_tpu_migrations_total{outcome}`` label values (live sequence
#: migration, export_sequence/import_sequence). Source side: exported ->
#: committed (the target owns the sequence) or failed + local_resume
#: (the sender gave up; the sequence re-admits here); unexportable means
#: the request was not mid-decode when asked. Target side: imported (KV
#: staged into the arena / prefix cache) -> claimed (the resumed
#: request's admission picked the staged pages up).
MIGRATION_OUTCOMES = ("exported", "committed", "failed", "local_resume",
                      "unexportable", "imported", "claimed")


@dataclasses.dataclass
class SamplingParams:
    """Per-request sampling (reference vllm/sampling_params.py surface:
    temperature/top_k/top_p/penalties/n/best_of/logprobs/stop)."""
    max_tokens: int = 128
    temperature: float = 0.0       # 0 = greedy
    top_k: int = 0
    top_p: float = 1.0
    stop_token_ids: Tuple[int, ...] = ()
    ignore_eos: bool = False
    # llama.cpp-form repetition penalty + OpenAI-form count penalties
    # (see bigdl_tpu.generation.apply_penalties). 1.0 / 0.0 = off.
    repetition_penalty: float = 1.0
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # parallel sampling: generate best_of sequences, return the n best by
    # mean logprob (best_of defaults to n). n>1 streams with choice
    # indices; best_of>n buffers until all candidates finish.
    n: int = 1
    best_of: Optional[int] = None
    # per-token logprobs: 0 = chosen token only, k>0 = also top-k
    # alternatives per step. None = off.
    logprobs: Optional[int] = None
    seed: Optional[int] = None
    # per-request deadline (wall ms from arrival, enforced per step);
    # None defers to $BIGDL_TPU_REQUEST_DEADLINE_MS (unset = no
    # deadline). An expired request finishes with reason "deadline"
    # (HTTP 504 at the API server) wherever it is in its lifecycle —
    # queued, mid-prefill, or decoding.
    max_time_ms: Optional[float] = None
    # overload control (serving/overload.py): QoS class — one of
    # "interactive"/"standard"/"batch" (admission priority + who sheds
    # first under pressure); None defers to $BIGDL_TPU_QOS_DEFAULT.
    qos: Optional[str] = None
    # tenant key for fair queuing and rate limits (the API server fills
    # it from X-Tenant-Id / the API-key hash); empty = "default"
    tenant: str = "default"

    @property
    def needs_counts(self) -> bool:
        return (self.repetition_penalty != 1.0
                or self.presence_penalty != 0.0
                or self.frequency_penalty != 0.0)


@dataclasses.dataclass
class Request:
    request_id: str
    prompt_token_ids: List[int]
    params: SamplingParams
    arrival: float = dataclasses.field(default_factory=time.time)
    # preempt-resume: tokens already generated (and streamed) before this
    # (re-)admission; they are part of prompt_token_ids now and must count
    # against max_tokens without being re-emitted
    generated_offset: int = 0
    resumed_cum_logprob: float = 0.0
    # absolute deadline (time.time()), resolved at add_request from
    # max_time_ms / $BIGDL_TPU_REQUEST_DEADLINE_MS; survives
    # preempt-resume (the clock does not restart on readmission)
    deadline: Optional[float] = None
    # step/prefill failures attributed to this request (blast-radius
    # blame counter); past max_slot_crashes the request is quarantined
    crashes: int = 0
    # distributed-trace context (observability/disttrace.py):
    # (trace_id, parent_span_id) propagated from the traceparent header;
    # None for untraced requests
    trace: Optional[Tuple[str, str]] = None
    # live-migration resume (export_sequence/import_sequence): the
    # source slot's device-sampler stream carried over verbatim — an
    # unseeded request otherwise draws a fresh nonce at admission and
    # its continuation diverges from the unmigrated run
    resume_dev_seed: Optional[int] = None
    # staging key a migrated-in sequence presents at admission:
    # _paged_admit claims the imported arena pages stashed under it
    # (one-shot; None after the claim, or for ordinary requests)
    resume_id: Optional[str] = None
    # a block family's preempt-resume: passes the request was given
    # before this (re-)admission (`RequestOutput.steps` counts on)
    block_passes: int = 0


@dataclasses.dataclass
class LogprobEntry:
    """One emitted token's logprob record."""
    token_id: int
    logprob: float
    top: List[Tuple[int, float]] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class RequestOutput:
    request_id: str
    new_token_ids: List[int]
    finished: bool
    finish_reason: Optional[str] = None
    index: int = 0                    # choice index (n>1 fan-out)
    logprobs: Optional[List[LogprobEntry]] = None
    # structured failure detail for finish_reason "error" (quarantine):
    # {"reason", "request_id"[, "type", "message"]}
    error: Optional[dict] = None
    # time.perf_counter() when _push_output took it: the start of the
    # API server's bigdl_tpu_stream_delivery_seconds
    t_push: float = 0.0
    # a family whose step is a block: beside each of `new_token_ids`,
    # the count of passes the request had been given when the token was
    # committed (storing passes counted, prefill chunks not); None for a
    # family whose step is one next token
    steps: Optional[List[int]] = None


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    max_seq: int = 2048
    prefill_bucket: int = 16       # smallest prefill compile bucket
    # KV cache storage dtype: "bf16", "fp8_e5m2", "int8" or "int4"
    # (int8/int4 carry per-(token, head) scales and need a family with
    # SUPPORTS_SCALED_KV). "bf16" defers to the deprecated kv_quantized.
    kv_cache_dtype: str = "bf16"
    kv_quantized: bool = False     # deprecated: True == "fp8_e5m2"
    # chunked prefill: a step() never runs more than this many prompt
    # tokens of prefill before the batched decode, so a long admission
    # cannot stall in-flight streams for more than one chunk's latency
    # (the reference engine runs the whole prefill inline and freezes
    # every stream, llm_engine.py:543 + scheduler.py:93)
    prefill_chunk: int = 256
    # starvation guard (the reference scheduler's preemption-by-recompute,
    # vllm/core/scheduler.py:52-66): when requests have been waiting this
    # many consecutive steps with every slot busy, the LATEST-arrived
    # running sequence is evicted to the BACK of the queue — its tokens
    # so far become prompt, recomputed on readmission (the prompt-prefix
    # cache makes that cheap when enabled), while the starved requests
    # admit into the freed slot first. 0 disables.
    preempt_after_steps: int = 64
    # context-parallel overflow lane: with a mesh passed to LLMEngine,
    # prompts longer than max_seq are admitted anyway — their KV shards
    # over the mesh (parallel/cp.py ring prefill + sequence-sharded
    # decode) up to this many tokens (multiple of the mesh size). One CP
    # request runs at a time, advancing one token per engine step
    # alongside the batched slots. None disables.
    cp_max_seq: Optional[int] = None
    # prompt-prefix KV reuse (the reference gen-1 pipeline's LlamaCache/
    # LlamaState, ggml/model/llama/llama.py:63,109-121,1346-1373): after
    # each admission the prompt's KV snapshot is kept on HOST; a later
    # prompt sharing a prefix seeds its private cache from the longest
    # match and prefills only the tail. 0 (the default) disables: for a
    # 7B-class model each entry holds on the order of 100-500 MB of host
    # DRAM (2*L*prefix_cache_max_tokens*Hkv*hd values) and its device
    # slices pin HBM until the next cache touch — opt in per deployment.
    prefix_cache_entries: int = 0
    # only the first N prompt tokens are snapshotted — bounds the D2H
    # transfer and host memory per entry (system prompts live here)
    prefix_cache_max_tokens: int = 1024
    # -- paged KV cache (ops/paged.py + serving/pagepool.py) ----------
    # token positions per arena page. None defers to
    # $BIGDL_TPU_KV_PAGE_SIZE; 0 keeps the per-slot slab. Must be a
    # power of two dividing max_seq. With paging on, the per-slot slab
    # becomes one [P, page_size, H, hd] arena per layer addressed
    # through per-sequence block tables, and prompt prefixes are shared
    # copy-on-write across requests via a radix tree.
    kv_page_size: Optional[int] = None
    # total physical pages in the arena. None defers to
    # $BIGDL_TPU_KV_PAGES; 0 auto-sizes to max_batch *
    # (max_seq / page_size) + 1 — the slab's worst case plus the pinned
    # null page. Configure it below that to oversubscribe: admission
    # then depends on prefix sharing actually deduplicating pages.
    kv_pages: Optional[int] = None
    # radix-tree prefix sharing across requests (paged mode only).
    # None defers to $BIGDL_TPU_PREFIX_SHARING; "auto"/"on" share
    # full-page prompt chunks copy-on-write, "off" keeps every
    # sequence's pages private.
    prefix_sharing: Optional[str] = None
    # retention bound for prefix-cache entries seeded by remote KV
    # handoffs (disaggregated prefill). -1 defers to 2 * max_batch;
    # 0 drops staged snapshots outright. Kept SEPARATE from
    # prefix_cache_entries so a decode-role replica that disables the
    # local prefix cache (prefix_cache_entries == 0) still expresses
    # an explicit bound instead of silently re-enabling caching.
    handoff_cache_entries: int = -1
    # headroom-aware admission: an admission whose private prefill
    # cache would push bytes_in_use past this fraction of the device's
    # bytes_limit is deferred (FCFS order kept) until headroom returns.
    # None defers to $BIGDL_TPU_HBM_BUDGET_FRACTION (default 0.9).
    # Backends without memory_stats() (CPU/interpret) always admit.
    hbm_budget_fraction: Optional[float] = None
    # -- robustness (bigdl_tpu/robustness/) ---------------------------
    # default per-request deadline in ms; None defers to
    # $BIGDL_TPU_REQUEST_DEADLINE_MS (unset = no deadline).
    # SamplingParams.max_time_ms overrides per request.
    request_deadline_ms: Optional[float] = None
    # transient step failures: a failing step() is retried up to this
    # many consecutive times (exponential backoff from
    # retry_backoff_ms) before the exception propagates. Failures that
    # can be blamed on one request (mid-admission, or a slot crossing
    # max_slot_crashes) quarantine that request and refresh the budget
    # — the engine degrades per-request, never per-process.
    max_step_retries: int = 3
    retry_backoff_ms: float = 20.0
    # per-request crash budget: once this many step/prefill failures
    # are attributed to one request it is quarantined (finish reason
    # "error", bigdl_tpu_requests_quarantined_total{reason="crash_loop"})
    max_slot_crashes: int = 3
    # per-step NaN/Inf logits health check: a non-finite decode row
    # quarantines exactly that slot (reason "nan_logits") while every
    # other slot keeps decoding. Costs one tiny [B]-bool readback per
    # decode step; False disables.
    logits_health_check: bool = True
    # graceful drain: in-flight work gets this long to finish after
    # begin_drain() before being failed with reason "drain_timeout".
    # None defers to $BIGDL_TPU_DRAIN_TIMEOUT_SEC (default 30).
    drain_timeout_sec: Optional[float] = None
    # hard bound on queued requests (waiting + CP lanes), enforced at
    # add_request with a RequestShed (HTTP 503) even when every other
    # overload feature is off — an unbounded deque under a traffic
    # storm is an OOM. None defers to $BIGDL_TPU_MAX_QUEUE_DEPTH
    # (default 256). Shorthand for overload.max_queue_depth.
    max_queue_depth: Optional[int] = None
    # full overload-control policy (QoS aging, tenant rate limits,
    # queue byte caps, brownout thresholds); None resolves every knob
    # from its $BIGDL_TPU_* env variable (serving/overload.py)
    overload: Optional[OverloadConfig] = None
    # perf-regression sentinel (observability/sentinel.py): None defers
    # to config.sentinel_enabled() ($BIGDL_TPU_SENTINEL tristate);
    # True/False force it per engine (tests)
    sentinel: Optional[bool] = None
    # perf-history JSONL path the sentinel baselines against; None
    # defers to $BIGDL_TPU_PERF_HISTORY (unset = in-memory baseline)
    perf_history: Optional[str] = None
    # live quality telemetry + QualitySentinel (observability/
    # quality.py): None defers to config.quality_enabled()
    # ($BIGDL_TPU_QUALITY tristate); True/False force it per engine
    quality: Optional[bool] = None
    # quality-history JSONL the QualitySentinel baselines against; None
    # defers to $BIGDL_TPU_QUALITY_HISTORY (unset = in-memory baseline)
    quality_history: Optional[str] = None
    # teacher-forced NLL probe period in DECODE STEPS (not seconds, so
    # tests are deterministic); None defers to
    # $BIGDL_TPU_QUALITY_PROBE_STEPS (default 0 = probe off)
    quality_probe_steps: Optional[int] = None
    # speculative decoding inside the resident step: tokens a step
    # drafts ahead with the FAMILY'S OWN draft module (multi-token
    # prediction) and verifies in the same dispatch, so a step yields
    # 1..1+n tokens a slot. 0 = the one-token step (every family's
    # default); 1 only for a family that declares such a module
    # (registry `speculative_depth`), refused at start-up otherwise.
    speculative_tokens: int = 0


class _Slot:
    __slots__ = ("req", "generated", "last_token", "active", "counts",
                 "counts_out", "rng", "cum_logprob", "n_logprobs",
                 "dev_seed", "drafted", "block")

    def __init__(self):
        self.req: Optional[Request] = None
        self.generated: List[int] = []
        self.last_token: int = 0
        self.active: bool = False
        # [V] int32 penalty counts: `counts` over prompt + output
        # (repetition penalty), `counts_out` over output only
        # (presence/frequency — vllm semantics)
        self.counts: Optional[np.ndarray] = None
        self.counts_out: Optional[np.ndarray] = None
        self.rng: Optional[np.random.Generator] = None
        self.cum_logprob: float = 0.0              # over generated tokens
        self.n_logprobs: int = 0
        # 31-bit seed for the DEVICE sampler stream (SamplingParams.seed
        # folded down, or a per-admission nonce when unseeded)
        self.dev_seed: int = 0
        # a standing draft of the family's MTP module waits on the device
        self.drafted: bool = False
        # a block family's block in flight (`_BlockState`)
        self.block: Optional["_BlockState"] = None


@dataclasses.dataclass
class _BlockState:
    """The host's view of the block a slot of a block family denoises:
    what it has READ of the passes (the device's own view, the packed
    state, may be one pass further on)."""
    pos: int                 # position of the block's first row
    ids: List[int]           # a row's final token; -1 while it is MASK
    steps: List[int]         # the request's pass count at a row's commit
    sent: int                # rows streamed, or given by the prompt
    s: int = 0               # denoise passes this block has been given
    passes: int = 0          # passes the REQUEST has been given


@dataclasses.dataclass
class _CPActive:
    """The in-flight context-parallel request: a pseudo-slot carries its
    sampler state; the KV cache lives sequence-sharded on the mesh."""
    slot: _Slot
    cache: Tuple[Any, Any]
    pos: int                 # global position of the NEXT cache write
    alloc: int               # sharded cache capacity (tokens)


@dataclasses.dataclass
class _CPAdmitting:
    """A long prompt mid-chunked-CP-prefill: like _Admission, the engine
    advances it ONE chunk per step so batched decodes keep flowing."""
    req: Request
    cache: Tuple[Any, Any]
    consumed: int
    alloc: int


@dataclasses.dataclass
class _Fanout:
    """Parent bookkeeping for n/best_of parallel sampling: child requests
    `rid#i` run as independent sequences; outputs route back under the
    parent id with choice indices (the reference scheduler forks
    SequenceGroups for the same purpose)."""
    parent_id: str
    n: int
    best_of: int
    # best_of > n: buffer each child's stream until all finish, then emit
    # the n best (by mean logprob); n == best_of streams through directly
    buffered: Dict[int, List["RequestOutput"]] = dataclasses.field(
        default_factory=dict)
    scores: Dict[int, float] = dataclasses.field(default_factory=dict)
    lengths: Dict[int, int] = dataclasses.field(default_factory=dict)
    done: int = 0


@dataclasses.dataclass
class _Admission:
    """A sequence mid-(chunked)-prefill: consumed tokens so far and its
    private 1-row cache (spliced into the batched cache on completion)."""
    req: Request
    slot_idx: int
    bucket: int
    consumed: int
    cache1: KVCache
    # effective prefill chunk, FROZEN at admission start: a brownout
    # level change mid-admission must not change the chunk width the
    # private cache was sized for
    chunk: int
    # paged mode (kv_page_size > 0): radix pages seeding the prompt
    # prefix (one slot reference each, taken at admission start) and
    # the freshly allocated private pages. The slot's block-table row
    # is written only at COMPLETION — until then it stays all-null, so
    # mid-admission decode steps of other slots can never write into
    # shared data through this row.
    shared_pages: Optional[List[int]] = None
    new_pages: Optional[List[int]] = None
    # speculative_tokens: the main stack's hidden row of the position
    # before the next chunk ([1, D], on the device), which the MTP
    # block's lagged pass over that chunk starts from
    carry: Any = None
    # the last chunk is out and its first token not yet read: (the
    # last prompt position's logits, its hidden row where the engine
    # speculates). Behind a decode step in flight the admission ends at
    # the next step's start, so that the step is read and emitted first
    last: Any = None


def _transform_rows(lg, temps, top_ks, top_ps):
    """A slot's temperature / top-k / top-p transform of its logits:
    ``(t, greedy)``, ``t`` ``[B, V]`` float32 with ``-inf`` at the
    masked tokens (softmax of it is the distribution a sampled slot
    draws from), ``greedy`` ``[B]`` bool. Nothing here sorts a row
    unless a sampled row asks for a nucleus."""
    v = lg.shape[-1]
    greedy = temps <= 0.0
    scale = jnp.maximum(temps, 1e-6)[:, None]
    t = lg.astype(jnp.float32) / scale                  # [B, V]
    # top-k: per-row threshold, the k-th largest with ties kept, by
    # counts on the logits' own bits (the division is monotone, so it
    # maps the k-th logit to the k-th of `t`); k=0 -> all, and greedy
    # rows keep all, their argmax ignores masking anyway
    kth = kth_largest(lg, top_ks).astype(jnp.float32)[:, None] / scale
    keep_all = greedy | (top_ks <= 0) | (top_ks >= v)
    t = jnp.where(t < jnp.where(keep_all[:, None], -jnp.inf, kth),
                  -jnp.inf, t)

    def nucleus(t):
        # top-p on the post-top-k distribution: keep the smallest
        # sorted prefix whose mass reaches p (first always)
        p = jnp.where(greedy, 1.0, top_ps)[:, None]
        sd = -jnp.sort(-t, axis=-1)
        probs = jax.nn.softmax(sd, axis=-1)
        # p >= 1.0 keeps ALL tokens (matching _sample_host's
        # `top_p < 1.0` gate): without it, f32 cumsum rounding can
        # push the pre-token mass to 1.0 and mask real tail tokens
        # on temperature-only requests
        keep = ((jnp.cumsum(probs, axis=-1) - probs) < p) | (p >= 1.0)
        # the top token survives even top_p=0.0 (OpenAI clients send
        # it to mean greedy; all-False keep would mask every token)
        keep = keep | (jnp.arange(v)[None, :] == 0)
        cutoff = jnp.min(jnp.where(keep, sd, jnp.inf), axis=-1)
        return jnp.where(t < cutoff[:, None], -jnp.inf, t)

    # the one sort left, run only while a sampled row asks for a
    # nucleus (a slot nobody holds is packed greedy at top_p 1.0)
    return jax.lax.cond(jnp.any(~greedy & (top_ps < 1.0)), nucleus,
                        lambda t: t, t), greedy


@jax.named_scope("sampler")
def _sample_rows(lg, temps, top_ks, top_ps, seeds, poss):
    """Batched on-device sampler body: temperature / top-k / top-p via
    gumbel-max, one seeded stream per row: `(token, confidence)`, the
    confidence the probability of the token under the distribution it
    was drawn from (a greedy row: the softmax of its raw logits; else of
    the row after temperature, top-k and top-p). Shared by the
    standalone ``engine_sample_device`` jit, the fused resident decode
    step and the block step, so the paths are numerically identical
    token-for-token; a program that reads no confidence computes
    none."""
    t, greedy = _transform_rows(lg, temps, top_ks, top_ps)
    lg = lg.astype(jnp.float32)

    def row(row_t, row_lg, g, seed, pos):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
        gum = jax.random.gumbel(key, row_t.shape, row_t.dtype)
        z = jnp.where(g, row_lg, row_t + gum)
        tok = jnp.argmax(z).astype(jnp.int32)
        dist = jnp.where(g, row_lg, row_t)
        return tok, jnp.exp(dist[tok] - jax.nn.logsumexp(dist))

    return jax.vmap(row)(t, lg, greedy, seeds, poss)


def _device_sample_rows(lg, temps, top_ks, top_ps, seeds, poss):
    """`_sample_rows`' tokens."""
    return _sample_rows(lg, temps, top_ks, top_ps, seeds, poss)[0]


def _block_sample(logits, spec, temps, top_ks, top_ps, seeds, first,
                  all_greedy: bool):
    """What a denoise pass samples for every row of every slot's block
    and how sure it is: `logits` `[N, B, V]` -> `(x0, conf)` `[N, B]`,
    the token and its probability under the distribution it was drawn
    from (`_sample_rows`), the MASK id no candidate (its logit -inf
    before both). `temps`, `top_ks`, `top_ps`, `seeds` `[N]` are a
    slot's; row j of a slot draws from its seed's stream at `first + j`,
    its index among the request's generated tokens. `all_greedy`: the
    program of a pass that samples no row."""
    b = spec.length
    lg = logits.reshape(-1, logits.shape[-1]).at[:, spec.mask_id].set(
        -jnp.inf)
    if all_greedy:
        x0 = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        conf = jnp.exp(jnp.max(lg, axis=-1) - jax.nn.logsumexp(lg, axis=-1))
    else:
        rows = lambda a: jnp.repeat(a, b)                   # noqa: E731
        x0, conf = _sample_rows(
            lg, rows(temps), rows(top_ks), rows(top_ps), rows(seeds),
            jnp.maximum(first[:, None] + jnp.arange(b)[None, :],
                        0).reshape(-1))
    return x0.reshape(-1, b), conf.reshape(-1, b)


@jax.named_scope("block.transfer")
def _block_transfer(conf, masked, s, spec):
    """Which rows of each block a denoise pass commits: `conf` `[N, B]`
    float32 (a row's `x0_p`), `masked` `[N, B]` bool (rows still MASK),
    `s` `[N]` the pass's index within its block, `spec` the family's
    `BlockSpec` -> `[N, B]` bool. `min(spec.owed(s), rows still MASK)`
    rows at the least, and never a row that is not MASK: the most
    confident (a tie goes to the lower row), every MASK row over the
    threshold where those are at least as many (`low_confidence_
    dynamic`), or the first MASK rows (`sequential`)."""
    b = spec.length
    k = jnp.minimum(spec.owed(s).astype(jnp.int32),
                    masked.sum(axis=1).astype(jnp.int32))[:, None]
    if spec.rule == "sequential":
        return masked & (jnp.cumsum(masked, axis=1) <= k)
    c = jnp.where(masked, conf, -jnp.inf)
    row = jnp.arange(b)
    # [N, j, i]: row i goes before row j
    before = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None])
        & (row[None, None, :] < row[None, :, None]))
    top = masked & (before.sum(axis=-1) < k)
    if spec.rule == "low_confidence_static":
        return top
    over = masked & (conf > spec.threshold)
    return jnp.where(over.sum(axis=1, keepdims=True) >= k, over, top)


class LLMEngine:
    """Synchronous continuous-batching engine over one model.

    model: a TpuCausalLM (bigdl_tpu.transformers.model) or anything exposing
    .params/.config/.family. Drive with add_request() + step(), identical in
    spirit to the reference engine loop (llm_engine.py:543).
    """

    def __init__(self, model: Any, config: Optional[EngineConfig] = None,
                 cp_mesh: Any = None, registry=None, tracer=None,
                 flight: Optional[FlightRecorder] = None,
                 ledger: Optional[MemoryLedger] = None,
                 memory_stats_provider: Optional[Callable[[], dict]] = None,
                 faults: Optional[FaultInjector] = None):
        t_init = time.perf_counter()    # the engine_init_begin mark
        self.cfg_engine = config or EngineConfig()
        self.params = model.params
        self.cfg = model.config
        self.family = model.family
        # quality-observability inputs: the serving qtype labels every
        # bigdl_tpu_quality_* sample; the load-time attribution report
        # (transformers/model.py) backs GET /v1/quality
        self.qtype = getattr(model, "qtype", None) or "bf16"
        self.quality_report = getattr(model, "quality_report", None)
        if getattr(self.family, "is_recurrent", False):
            raise ValueError(
                f"continuous batching is KV-cache based; the "
                f"{self.family.name!r} family carries recurrent state "
                "whose slots cannot be rewound/packed — serve it through "
                "model.generate() instead")
        probe_cache = self.family.new_cache(self.cfg, 1, 8, False)
        if not isinstance(probe_cache, KVCache):
            raise ValueError(
                f"the {self.family.name!r} family uses a custom cache "
                f"({type(probe_cache).__name__}) the slot engine cannot "
                "splice — serve it through model.generate() instead")
        # what the family's layers keep per position (K and V planes, or
        # one latent plane): every cache here is made, spliced, measured
        # and exported through this description
        self._cache_spec = cache_spec_of(self.family, self.cfg)
        if (self._cache_spec.has_ring
                and self.cfg_engine.prefix_cache_entries > 0):
            raise ValueError(
                f"prefix_cache_entries="
                f"{self.cfg_engine.prefix_cache_entries}: the "
                f"{self.family.name!r} family keeps a ring plane (a window "
                "layer's last positions), and a snapshot of a ring is the "
                "state at the length it was taken, not at a shorter "
                "prefix; serve it with prefix_cache_entries=0")
        if (self._cache_spec.has_strided
                and self.cfg_engine.prefix_cache_entries > 0):
            raise ValueError(
                f"prefix_cache_entries="
                f"{self.cfg_engine.prefix_cache_entries}: the "
                f"{self.family.name!r} family: {SNAPSHOT_REFUSAL}; serve "
                "it with prefix_cache_entries=0")
        self.eos_token_id = None
        hf = getattr(model, "hf_config", None) or {}
        eos = hf.get("eos_token_id")
        self.eos_token_id = eos[0] if isinstance(eos, list) else eos

        ce = self.cfg_engine
        B = ce.max_batch
        self.kv_cache_dtype = resolve_kv_cache_dtype(
            ce.kv_cache_dtype if ce.kv_cache_dtype != "bf16"
            else ce.kv_quantized)
        if (self.kv_cache_dtype in ("int8", "int4")
                and not getattr(self.family, "SUPPORTS_SCALED_KV", False)):
            raise ValueError(
                f"kv_cache_dtype={self.kv_cache_dtype!r} needs a family "
                f"that threads scale planes through its forward; "
                f"{getattr(self.family, 'name', '?')!r} does not "
                "(SUPPORTS_SCALED_KV)")
        # -- paged KV mode: one page arena (ops/paged.py keeps its layout) +
        # host-owned block tables instead of the per-slot slab.
        # Explicit EngineConfig values validate loudly here; env-driven
        # values already passed through config.flags() (typos fall back
        # to off/auto and utils/env_check.py reports them).
        page_size = resolve_kv_page_size(
            ce.kv_page_size if ce.kv_page_size is not None
            else flags().kv_page_size)
        n_pages_spec = resolve_kv_pages(
            ce.kv_pages if ce.kv_pages is not None else flags().kv_pages)
        sharing = resolve_prefix_sharing(
            ce.prefix_sharing if ce.prefix_sharing is not None
            else flags().prefix_sharing)
        self._paged = page_size > 0
        self._page_size = page_size
        # a family that generates by diffusion over blocks: its step is a
        # BLOCK (`_block_step`), and what one is, is the model's
        # configuration (`BlockSpec`); None: one next token a step
        spec_of = getattr(self.family, "block_spec", None)
        self._block = spec_of(self.cfg) if spec_of is not None else None
        if self._block is not None:
            self._refuse_for_block_family()
        self.pool: Optional[PagePool] = None
        self.radix: Optional[RadixCache] = None
        if self._paged:
            if not getattr(self.family, "SUPPORTS_PAGED_KV", False):
                raise ValueError(
                    f"kv_page_size={page_size} needs a family with a "
                    f"paged forward (SUPPORTS_PAGED_KV); "
                    f"{getattr(self.family, 'name', '?')!r} has none")
            if ce.max_seq % page_size:
                raise ValueError(
                    f"max_seq {ce.max_seq} must be a multiple of "
                    f"kv_page_size {page_size}")
            self._pages_per_seq = ce.max_seq // page_size
            self._num_pages = n_pages_spec or B * self._pages_per_seq + 1
            self.cache = self.family.new_paged_cache(
                self.cfg, self._num_pages, page_size, B,
                kv_cache_dtype=self.kv_cache_dtype)
            self.pool = PagePool(self._num_pages, page_size)
            if sharing != "off":
                self.radix = RadixCache(self.pool)
            # host-authoritative block tables ([B, pages_per_seq] int32,
            # 0 = null page); the device mirror refreshes lazily through
            # _bt() only when a row changed, so the per-token step path
            # never indexes page state on the host
            self._bt_np = np.zeros((B, self._pages_per_seq), np.int32)
            self._bt_dev = jnp.asarray(self._bt_np)
            self._bt_dirty = False
        else:
            self._pages_per_seq = 0
            self._num_pages = 0
            self.cache = init_cache_spec(
                self._cache_spec, B, ce.max_seq,
                kv_cache_dtype=self.kv_cache_dtype, per_slot_pos=True)

        self.slots = [_Slot() for _ in range(B)]
        # deque (admission pops the front; preemption appends the back)
        self.waiting: "collections.deque[Request]" = collections.deque()
        self._outputs: Dict[str, List[RequestOutput]] = {}
        self._abort: set = set()
        self._lock = threading.Lock()
        # n/best_of fan-out: child request id -> (parent id, choice index)
        self._children: Dict[str, Tuple[str, int]] = {}
        self._fanouts: Dict[str, _Fanout] = {}
        self._stall_steps = 0       # consecutive steps with starved queue
        self._step_idx = 0          # lifetime step() counter
        self._last_step_ts = time.monotonic()   # step-loop heartbeat

        # observability backbone, created BEFORE the jit definitions so
        # tracked_jit can mirror compile metrics into the engine's
        # registry (bigdl_tpu/observability/__init__.py has the full
        # metric-name <-> engine-field map). Families are get-or-create,
        # so sharing a registry across engines or with the probe/spec
        # sites is safe.
        self.registry = registry if registry is not None \
            else default_registry()
        # the start-up account (observability/compile_watch.py): its
        # families render from scrape 1, its marks as they are reached
        declare_startup_metrics(self.registry)
        startup_mark("engine_init_begin", self.registry, t_init)
        # each set once, by a flag a request checks, none read in a step
        self._mark_first_request = self._mark_first_token = True
        self.tracer = tracer if tracer is not None else RequestTracer()
        # distributed-trace span store (observability/disttrace.py):
        # per-request queue_wait/prefill/decode spans and per-step
        # dispatch/device sub-spans for requests carrying a traceparent;
        # the API server serves it at GET /v1/internal/spans
        self.spans = SpanRecorder(service="engine")
        # flight recorder: bounded ring of structured step/scheduling
        # events; its tail is the core of every postmortem dump
        self.flight = flight if flight is not None else FlightRecorder()
        # HBM ledger: static bytes for params + batched KV registered
        # below, live device telemetry for headroom-aware admission. A
        # passed-in ledger keeps its own budget fraction; tests inject
        # memory_stats_provider for deterministic deferral.
        self.ledger = ledger if ledger is not None else MemoryLedger(
            stats_provider=memory_stats_provider,
            budget_fraction=ce.hbm_budget_fraction)
        self._deferred_admissions = 0   # lifetime deferral count
        self._deferred_streak = False   # one flight event per streak

        # -- robustness: fault injection + lifecycle hardening
        # (bigdl_tpu/robustness/). The injector's hooks sit in the real
        # step/admit/prefill/logits paths below; with no spec configured
        # each is one attribute check.
        self.faults = faults if faults is not None \
            else FaultInjector.from_env()
        self.faults.on_fire = self._on_fault_fired
        try:
            self._request_deadline_ms = (
                ce.request_deadline_ms
                if ce.request_deadline_ms is not None
                else resolve_request_deadline_ms())
        except ValueError:
            self._request_deadline_ms = None    # env_check reports it
        try:
            self._drain_timeout_sec = (
                ce.drain_timeout_sec if ce.drain_timeout_sec is not None
                else resolve_drain_timeout_sec())
        except ValueError:
            self._drain_timeout_sec = 30.0      # env_check reports it
        self._draining = False
        self._drain_deadline: Optional[float] = None
        self._any_deadline = False      # fast path: skip expiry scans
        self._consec_failures = 0       # consecutive failing step()s
        self._retry_total = 0           # lifetime retried steps

        # -- overload control (serving/overload.py): QoS priorities,
        # tenant fair queuing + rate limits, bounded queues with early
        # shedding, and the brownout ladder. Always constructed — the
        # queue-depth hard bound protects even deployments that leave
        # every policy knob at its default.
        try:
            oc = ce.overload or OverloadConfig()
            if ce.max_queue_depth is not None:
                oc = dataclasses.replace(
                    oc, max_queue_depth=ce.max_queue_depth)
            self.overload = OverloadController(oc)
        except ValueError:
            # env_check reports the bad knob; serve with pure defaults
            self.overload = OverloadController(OverloadConfig(
                qos_default="standard", qos_aging_sec=5.0,
                tenant_rps=0.0, tenant_tps=0.0, tenant_burst=4.0,
                brownout_high=0.85, brownout_low=0.6,
                max_queue_depth=ce.max_queue_depth or 256,
                max_queue_bytes=64 << 20))
        # decode-step latency: the EWMA of every step is the queue-wait
        # admission test's estimate; the EWMA and its observed floor
        # over the steps that measure the decode alone are the brownout
        # latency-inflation signal (_overload_pressure)
        self._tpot_ewma = 0.0
        self._decode_ewma = 0.0
        self._decode_floor: Optional[float] = None
        # host-dispatch share of the decode step (dispatch-return vs
        # blocked block_until_ready, measured every step): the
        # attribution denominator for
        # the decode roofline gap, surfaced as stats_snapshot()
        # dispatch_overhead_ms
        self._dispatch_ewma = 0.0
        # recent finish timestamps -> measured drain rate (Retry-After)
        self._finish_times: "collections.deque[float]" = \
            collections.deque(maxlen=64)

        # context-parallel overflow lane (long prompts)
        self._cp_mesh = cp_mesh
        self._cp_axis = cp_mesh.axis_names[0] if cp_mesh is not None \
            else None
        self._cp_waiting: "collections.deque[Request]" = collections.deque()
        self._cp_active: Optional[_CPActive] = None
        self._cp_admitting: Optional[_CPAdmitting] = None
        if cp_mesh is not None and ce.cp_max_seq:
            n_cp = cp_mesh.shape[self._cp_axis]
            if ce.cp_max_seq % n_cp:
                raise ValueError(f"cp_max_seq {ce.cp_max_seq} must be a "
                                 f"multiple of the mesh size {n_cp}")
            layer_keys = set(self.params.get("layers") or {})
            if not ({"q_proj", "qkv_proj"} & layer_keys):
                raise ValueError(
                    "context-parallel serving needs the generalized "
                    "llama-family parameter layout (layers/q_proj or "
                    "the merged layers/qkv_proj)")

        fwd = self.family.forward

        def decode_forward(params, tokens, cache):
            """One token a slot through the family's forward; `tokens`
            [B] int32, -1 in a slot that holds no request. Such a slot
            goes in at position -1, which the slab's append writes at
            row 0 and decode attention reads as "nothing cached"
            (ops/pallas/decode_attention.py: no block of it is
            multiplied), and comes back at 0 rather than one deeper
            every step."""
            live = tokens >= 0
            logits, out = fwd(
                params, self.cfg, jnp.maximum(tokens, 0)[:, None],
                cache.replace(pos=jnp.where(live, cache.pos, -1)))
            return logits[:, -1, :], out.replace(
                pos=jnp.where(live, out.pos, 0))

        self._decode = tracked_jit(
            "engine_decode", decode_forward, registry=self.registry,
            donate_argnums=(2,))
        # greedy fast path: one fused argmax, [B] ints to the host
        self._argmax = tracked_jit(
            "engine_argmax",
            lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32),
            registry=self.registry)
        # per-slot logits health: [B] bools to the host — the
        # blast-radius check that turns a NaN/Inf decode row into ONE
        # quarantined request instead of a poisoned batch
        self._health = tracked_jit(
            "engine_health",
            lambda lg: jnp.isfinite(lg).all(axis=-1),
            registry=self.registry)
        # batched DEVICE sampler: temperature / top-k / top-p via
        # gumbel-max, one seeded stream per slot. Serves every slot that
        # needs no penalty counts and no logprobs — the [B, V] logits
        # never leave the chip for such batches, extending the greedy
        # fast path to sampled traffic (host _sample_host remains the
        # full-featured path). Seeded slots derive their key from
        # (seed, absolute position), so a preempt-resume — or a change
        # in WHICH other requests share the batch — replays identically.
        @functools.partial(tracked_jit, "engine_sample_device",
                           registry=self.registry)
        def sample_device(lg, temps, top_ks, top_ps, seeds, poss):
            return _device_sample_rows(lg, temps, top_ks, top_ps,
                                       seeds, poss)

        self._sample_device = sample_device

        # resident single-dispatch decode step: layer-scanned forward +
        # per-slot health check + on-device sampling fused into ONE
        # executable, so a pure-decode engine step costs exactly one
        # host dispatch (vs decode + health + argmax/sampler = 3). The
        # greedy branch is the same fused argmax as engine_argmax (so
        # greedy serving stays byte-identical) and the sampled branch
        # is the shared _device_sample_rows body (so seeded streams
        # replay identically whichever path served them). Used by
        # _step_inner when every active slot is device-samplable and
        # no fault clauses are live (poison_rows needs the logits on
        # the host side of the dispatch).
        @functools.partial(tracked_jit, "engine_decode_resident",
                           registry=self.registry, donate_argnums=(1, 3),
                           static_argnames=("all_greedy", "with_quality"))
        def decode_resident(params, ints, floats, cache, *, all_greedy,
                            with_quality=False):
            """What the host gives and takes is PACKED, because a
            transfer of a few bytes costs the step about a millisecond
            each way: `ints` `[4, B]` (each slot's last token, -1 for
            none; top-k; seed; the absolute index of the token it
            samples next) and `floats` `[2, B]` (temperature, top-p) go
            in; out come ONE int32 block `[B, 2]` (token, health; `[B,
            5]` with the bits of the three quality values where
            `with_quality`) and the NEXT step's `ints` (the sampled
            token, the index moved on), which the host hands back
            untouched while the same requests hold the same slots
            (`_io`)."""
            tokens, top_ks, seeds, poss = ints
            temps, top_ps = floats
            lg, cache = decode_forward(params, tokens, cache)
            finite = jnp.isfinite(lg).all(axis=-1)
            if all_greedy:
                toks = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            else:
                toks = _device_sample_rows(lg, temps, top_ks, top_ps,
                                           seeds, poss)
            cols = [toks, finite.astype(jnp.int32)]
            if with_quality:
                # live decode-quality telemetry, fused into the SAME
                # executable so the single-dispatch invariant survives:
                # per-slot chosen-token logprob, full-softmax entropy,
                # and top-1 margin, three f32 columns of the one block
                # the host pulls (their bits, read back as f32)
                lp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
                chosen = jnp.take_along_axis(
                    lp, toks[:, None].astype(jnp.int32), axis=-1)[:, 0]
                entropy = -jnp.sum(jnp.exp(lp) * lp, axis=-1)
                # the two largest by two maxima (`lax.top_k` is a sort
                # of the whole row); a tie at the top gives margin 0
                first = jnp.argmax(lg, axis=-1)      # the greedy token
                second = jnp.max(jnp.where(
                    jnp.arange(lg.shape[-1])[None, :] == first[:, None],
                    -jnp.inf, lg), axis=-1)
                margin = (jnp.max(lg, axis=-1).astype(jnp.float32)
                          - second.astype(jnp.float32))
                cols += [jax.lax.bitcast_convert_type(q, jnp.int32)
                         for q in (chosen, entropy, margin)]
            live = tokens >= 0
            ints = jnp.stack([jnp.where(live, toks, -1), top_ks, seeds,
                              poss + 1])
            return jnp.stack(cols, axis=1), ints, cache

        self._decode_resident = decode_resident
        if self._block is not None:
            self._init_block_step(fwd)

        # prefill one sequence on a private 1-row cache, then splice its K/V
        # (and, for scaled dtypes, the per-token scale planes) and position
        # into the batched cache at the slot index
        @functools.partial(tracked_jit, "engine_insert",
                           registry=self.registry, donate_argnums=(0,))
        def insert(cache: KVCache, cache1: KVCache, slot, plen):
            # the private cache may be chunk-padded past max_seq; the
            # tail holds only pad garbage (plen <= max_seq is enforced
            # at add_request): every plane is cut to the slab's length
            # and written at the slot, whatever planes the cache holds
            return cache.spliced(cache1, slot, plen)

        self._insert = insert

        # a block family's prefill yields no token: its chunks run the
        # family's last-row forward, and the head meets one row
        fwd_chunk = fwd if self._block is None else self.family.prefill

        @functools.partial(tracked_jit, "engine_prefill",
                           registry=self.registry, donate_argnums=(2,))
        def prefill_chunk(params, tokens, cache1):
            # one tracked fn; XLA caches an executable per (chunk width,
            # cache bucket, kv dtype) shape tuple — the compile table's
            # per-signature rows ARE the engine's prefill executables
            return fwd_chunk(params, self.cfg, tokens, cache1)

        self._prefill = prefill_chunk

        # A resident step (`engine_decode_resident`, or the verify step
        # of a speculating engine) takes all it needs from what the step
        # before it left on the device, so it goes out BEFORE that step's
        # tokens are read (`_decode_step`, `_may_lead`): the device runs
        # step k + 1 while the host fetches, emits and observes step k.
        # The host's view of a slot stays the truth (export, preemption
        # and `_finish` go by the host's count and set the slot's `pos`);
        # what the step ahead computed for a request that step k ended
        # is never read.
        # `_io`: (who holds which slot, the next step's `ints`, its
        # `floats`): the device's own view of every slot's last token
        # and token index, good while no slot changes hands
        self._io = None
        # `_ahead`: a step that is out and not read yet, sent before the
        # last one's tokens were read or at that step's close: ((slot,
        # request) of every slot in it, its block, the `ints` after it,
        # its `floats`). The device's view may be this one step further
        # on than the host's
        self._ahead = None
        # a prefill chunk went out beside live streams and no decode
        # program has followed it yet: the next chunk waits for one
        # (no stream waits behind two chunks)
        self._chunk_unanswered = False
        # when the last decode step's tokens were timed (perf_counter)
        self._t_step_timed = 0.0

        # -- speculative_tokens: the family's MTP module drafts one token
        # a slot and the main stack verifies it in the same dispatch
        self._mtp = bool(ce.speculative_tokens)
        if ce.speculative_tokens:
            self._init_speculative()

        # -- paged-mode executables. Prefill stays on the slab path (a
        # private 1-row cache1 per admission); only the splice into the
        # batched store, the cross-request page machinery, and the
        # decode step itself change shape.
        if self._paged:
            fwd_paged = self.family.forward_paged

            # paged decode: same contract as engine_decode, but K/V
            # gathers go through the block tables INSIDE the jit — the
            # host never indexes the arena per token (graftlint's
            # paged-host-gather rule holds the line)
            @functools.partial(tracked_jit, "engine_decode_paged",
                               registry=self.registry,
                               donate_argnums=(2,))
            def decode_paged(params, tokens, cache, block_tables):
                # an empty slot's -1 is token 0 here, as it always was:
                # its block table is the null page
                logits, cache = fwd_paged(
                    params, self.cfg, jnp.maximum(tokens, 0)[:, None],
                    cache, block_tables, last_only=True)
                return logits[:, -1, :], cache

            self._decode_paged = decode_paged

            # splice a finished admission's private cache1 into the
            # arena, whole pages at a time: the page of each logical
            # page is computed on host ONCE per admission; pages inside
            # the shared prefix (and of chunk padding) are the null
            # page, the arena's write sink
            @functools.partial(tracked_jit, "engine_insert_paged",
                               registry=self.registry,
                               donate_argnums=(0,))
            def insert_paged(cache, cache1, pages, slot, plen):
                cache = splice_pages(
                    cache, cache1.seq_slices(cache1.max_seq, row=0), pages)
                return dataclasses.replace(
                    cache, pos=cache.pos.at[slot].set(plen))

            self._insert_paged = insert_paged

            # seed a fresh cache1 from shared radix pages: one dense
            # gather of n full pages into positions [0, n*page_size)
            @functools.partial(tracked_jit, "engine_seed_pages",
                               registry=self.registry,
                               donate_argnums=(0,))
            def seed_pages(cache1, cache, pages, consumed):
                planes = gather_pages_dense(cache, pages)
                k = jax.lax.dynamic_update_slice(
                    cache1.k, planes[0].astype(cache1.k.dtype),
                    (0, 0, 0, 0, 0))
                v = jax.lax.dynamic_update_slice(
                    cache1.v, planes[1].astype(cache1.v.dtype),
                    (0, 0, 0, 0, 0))
                ks = vs = None
                if cache1.k_scale is not None:
                    ks = jax.lax.dynamic_update_slice(
                        cache1.k_scale, planes[2], (0, 0, 0, 0))
                    vs = jax.lax.dynamic_update_slice(
                        cache1.v_scale, planes[3], (0, 0, 0, 0))
                pos = jnp.full_like(cache1.pos, consumed)
                return KVCache(k, v, pos, ks, vs)

            self._seed_pages = seed_pages

            # batched copy-on-write: gather every shared source page,
            # scatter into the fresh destinations. Pairs are padded to
            # max_batch with null->null self-copies so ONE executable
            # serves every CoW step regardless of how many slots hit
            # their shared tail page simultaneously.
            self._cow_pages = tracked_jit(
                "engine_cow_pages", cow_copy_pages, registry=self.registry,
                donate_argnums=(0,))

        # chunk width must divide the private cache length or the last
        # chunk's dynamic_update_slice would CLAMP its start index and
        # silently overwrite earlier positions — normalize to a power of
        # two and size the cache up to a multiple of it (_admission_step)
        self._chunk = 1 << (max(1, ce.prefill_chunk).bit_length() - 1)
        self._admitting: Optional[_Admission] = None
        # prefix cache: {prompt_tuple: (k, v[, k_scale, v_scale])} in
        # insertion (LRU) order — host DRAM, not HBM
        self._prefix_cache: Dict[Tuple[int, ...], Tuple[Any, ...]] = {}
        # lookup index over the prefix cache: length (a multiple of the
        # granularity g) -> {hash(prompt[:length]): stored key}. Admission
        # probes O(max_seq/chunk) bucketed lengths instead of scanning
        # every entry token-by-token. Usable only when every possible
        # chunk width is a multiple of g; otherwise _seed_from_prefix_cache
        # falls back to the linear scan.
        g = min(self._chunk, max(1, ce.prefill_bucket))
        self._prefix_g = g if (self._chunk % g == 0
                               and ce.prefill_bucket % g == 0) else 0
        self._prefix_index: Dict[int, Dict[int, Tuple[int, ...]]] = {}
        # KV handoff inbox: (prompt_tuple, planes) staged by HTTP
        # handler threads (stage_handoff), drained into the prefix
        # cache by the engine loop at the top of _admission_step. The
        # deque is the only cross-thread structure — append/popleft
        # are atomic, and all prefix-cache mutation stays on the
        # engine thread.
        self._handoff_in: "collections.deque" = collections.deque()
        # staged handoff keys in arrival order, engine-thread only:
        # bounds how many remote snapshots can pin host DRAM when the
        # local prefix cache is disabled (prefix_cache_entries == 0)
        self._handoff_keys: "collections.deque" = collections.deque()
        # -- live sequence migration (export_sequence/import_sequence).
        # HTTP sender threads only touch the thread-safe set/deques and
        # the _lock-guarded dicts; every slot/page/cache mutation stays
        # on the engine thread (_migration_step / _drain_migrations).
        self._migrate_req: set = set()      # rids to suspend + export
        self._migration_out: Dict[str, dict] = {}   # rid -> wire state
        self._migration_meta: Dict[str, dict] = {}  # rid -> local resume
        self._migration_done: "collections.deque" = collections.deque()
        self._migration_fail: "collections.deque" = collections.deque()
        self._migration_in: "collections.deque" = collections.deque()
        # target-side staging: resume_id -> (state, staged_at). A lost
        # commit-ack means the source resumed locally — the stale copy
        # here must expire UNCLAIMED or the sequence would run twice.
        self._migration_staged: Dict[str, Tuple[dict, float]] = {}
        # resume_id -> (imported pages, kv_len, staged_at); claimed by
        # _paged_admit, expired (pages decref'd) with the stage above
        self._migration_pages: Dict[str, Tuple[List[int], int,
                                               float]] = {}
        self._migration_ttl = 30.0
        self._mig: Dict[str, int] = {oc: 0 for oc in MIGRATION_OUTCOMES}
        self._mig["migrated_tokens_total"] = 0
        self._mig["recomputed_tokens_total"] = 0

        # -- metric families (registry/tracer/flight created above,
        # before the jit definitions)
        m = self.registry
        self._m_phase = m.histogram(
            "bigdl_tpu_request_phase_seconds",
            "Per-request phase latency: ingest (API server: request "
            "line read to add_request returned), decode (first token "
            "to finish). Queue wait and prefill are "
            "bigdl_tpu_step_phase_seconds{phase=queue_wait|prefill}.",
            labelnames=("phase",))
        for ph in ("ingest", "decode"):
            # render from scrape 1
            self._m_phase.labels(ph)
        m_step_phase = m.histogram(
            "bigdl_tpu_step_phase_seconds",
            "Engine step critical-path decomposition. Per request "
            "(kind=admission): queue_wait, prefill. Per step, kind=chunk "
            "when the step dispatched a prefill chunk, else plain. One "
            "sample per working step: sweep, admission, observe, cache "
            "(the step's cache.* spans), h2d (its host-to-device puts: "
            "the *.h2d spans), fetch (its device-to-host reads: "
            "sample.fetch, admission.wait). One per step that "
            "decoded: dispatch (host, to the decode call's return), "
            "device (blocked block_until_ready on the decode result), "
            "sample, emit, host (step wall less device).",
            labelnames=("phase", "kind"))
        # render from scrape 1
        self._m_queue_wait = m_step_phase.labels("queue_wait",
                                                 ADMISSION_KIND)
        self._m_prefill = m_step_phase.labels("prefill", ADMISSION_KIND)
        # the step's phase clock: every part of step() is a span on the
        # profiler's clock (engine.<phase>, cache.*, observe.*,
        # admission.*, dispatch.h2d, sample.fetch) and a share of one
        # histogram sample per step
        self.phases = PhaseClock(
            m_step_phase,
            m.histogram(
                "bigdl_tpu_tpot_seconds",
                "Time per output token: wall of a step that decoded, "
                "step() entry to return (every active stream advances "
                "one token per step), by kind: chunk when the step "
                "dispatched a prefill chunk, else plain.",
                labelnames=("kind",), buckets=STEP_WALL_BUCKETS_S),
            annotate)
        self._m_ttft = m.histogram(
            "bigdl_tpu_ttft_seconds",
            "Time to first token: arrival to first sampled token.")
        self._m_occupancy = m.gauge(
            "bigdl_tpu_slot_occupancy", "Active decode slots.")
        self._m_queue_depth = m.gauge(
            "bigdl_tpu_queue_depth",
            "Requests waiting for admission (slot + CP lanes).")
        self._m_admissions = m.counter(
            "bigdl_tpu_admissions_total",
            "Completed admissions (prefill finished, slot running).")
        self._m_preemptions = m.counter(
            "bigdl_tpu_preemptions_total",
            "Sequences evicted to the queue by the starvation guard.")
        self._m_stall_trips = m.counter(
            "bigdl_tpu_stall_guard_trips_total",
            "Times the stall guard reached preempt_after_steps.")
        self._m_finished = m.counter(
            "bigdl_tpu_requests_finished_total",
            "Finished sequences by reason.", labelnames=("reason",))
        self._m_steps = m.counter(
            "bigdl_tpu_engine_steps_total",
            "step() iterations that did work.")
        self._m_tokens = m.counter(
            "bigdl_tpu_tokens_generated_total",
            "Tokens emitted to clients.")
        self._m_attn_blocks = m.counter(
            "bigdl_tpu_decode_attn_blocks_total",
            "S-blocks of K the slab decode-attention kernel names in a "
            "decode step, all layers: kind=read those it fetches (up to "
            "each live slot's position, the first of an empty slot), "
            "kind=slab those the whole slab holds.",
            labelnames=("kind",))
        if self._paged:
            self._m_paged_pages = m.counter(
                "bigdl_tpu_paged_attn_pages_total",
                "Pages of K behind the block tables in a decode step, "
                "all layers: kind=read those the block-table kernel "
                "copies (a live slot's, up to the one that holds its "
                "position; none of an empty slot), kind=table every "
                "column of every table.",
                labelnames=("kind",))
        # (layers, blocks of one layer's slab, S, kv heads) of a K/V slab
        # cache; None for a paged or a latent one, whose kernels have
        # block rules of their own
        self._attn_blocks = None
        if not self._paged and self.cache.k is not None:
            _, b_, s_, hkv_ = self.cache.k.shape[:4]
            self._attn_blocks = (self.cache.num_layers,
                                 slab_blocks(b_, s_, hkv_), s_, hkv_)
        # a family whose full layers select their keys (an index plane):
        # (full layers, positions a query selects at most); else None
        self._dsa = None
        if not self._paged and self.cache.index is not None:
            self._dsa = (int(self.cache.index.shape[0]),
                         int(self.cfg.index_topk))
            self._m_dsa_positions = m.counter(
                "bigdl_tpu_dsa_positions_total",
                "Cached positions of the sparse-attention layers in a "
                "decode step, all full layers and live slots: kind=live "
                "those a query could attend (its own counted), "
                "kind=selected those its selection keeps (at most "
                "index_topk a query and layer).", labelnames=("kind",))
        # a family of chunked linearized attention (window and summary
        # planes): (window, positions a summary column reduces); else None
        self._eva = None
        if not self._paged and self.cache.sum_k is not None:
            self._eva = (int(self.cache.win_k.shape[2]),
                         int(self.cache.stride))
            self._m_eva_rows = m.counter(
                "bigdl_tpu_eva_rows_total",
                "Rows a query of chunked linearized attention reads in a "
                "decode step, one layer's, all live slots: kind=window "
                "the exact keys of its own window up to itself, "
                "kind=summary one row a chunk of every earlier window, "
                "kind=context the positions full attention would read.",
                labelnames=("kind",))
        # a family of full and window K/V layers (ring planes): (window
        # layers, full layers, the window); else None
        self._swa = None
        if not self._paged and self.cache.ring_k is not None:
            self._swa = (int(self.cache.ring_k.shape[0]),
                         int(self.cache.full_k.shape[0])
                         if self.cache.full_k is not None else 0,
                         int(self.cfg.sliding_window_size))
            self._m_swa_rows = m.counter(
                "bigdl_tpu_swa_rows_total",
                "Rows of K a decode step's queries read, all layers and "
                "live slots: kind=window those of the window layers' "
                "rings (the last sliding_window positions, the query's "
                "own counted), kind=full those of the full layers' "
                "planes, kind=context those a model of as many layers, "
                "all full, would read.", labelnames=("kind",))
            self._m_swa_blocks = m.counter(
                "bigdl_tpu_swa_ring_blocks_total",
                "Blocks of the window layers' K rings in a decode step, "
                "all window layers and live slots, by the ring kernel's "
                "own rule: state=live those it fetches (up to the one "
                "that holds the query's position while a ring is still "
                "filling, all of them once it has wrapped), state=dead "
                "those it names again and does not fetch.",
                labelnames=("state",))
            for st in ("live", "dead"):  # render from scrape 1
                self._m_swa_blocks.labels(st)
        self._m_decode_steps = m.counter(
            "bigdl_tpu_decode_steps_total",
            "Decode programs dispatched: sent=ahead before the step "
            "before it was read (the device runs it while the host reads, "
            "emits and observes that one), sent=in_step by the step that "
            "waits for it.", labelnames=("sent",))
        for st in ("ahead", "in_step"):     # render from scrape 1
            self._m_decode_steps.labels(st)
        self._m_sampler_steps = m.counter(
            "bigdl_tpu_sampler_steps_total",
            "Decode programs dispatched, by what their sampler does with "
            "a vocabulary-wide row: path=greedy every slot takes the "
            "argmax, path=topk some slot samples and none asks for a "
            "nucleus (thresholds by counts, no row is sorted), "
            "path=nucleus a sampled slot has top_p < 1 (the rows are "
            "sorted once).", labelnames=("path",))
        for pt in ("greedy", "topk", "nucleus"):    # render from scrape 1
            self._m_sampler_steps.labels(pt)
        self._m_block_passes = m.counter(
            "bigdl_tpu_block_passes_total",
            "Passes the slots of a family that generates by diffusion "
            "over blocks were given and the host read (a program holds "
            "one a live slot): kind=denoise a pass over a block that "
            "still held a MASK (it commits the rows its confidences "
            "choose), kind=store the pass over a block's final ids that "
            "leaves its K/V in the cache and commits nothing.",
            labelnames=("kind",))
        for kd in ("denoise", "store"):     # render from scrape 1
            self._m_block_passes.labels(kd)
        self._m_block_tokens = m.counter(
            "bigdl_tpu_block_tokens_committed_total",
            "Rows the denoise passes committed (MASK to a final token), "
            "streamed or not.")
        self._m_blocks = m.counter(
            "bigdl_tpu_blocks_total",
            "Blocks stored: every row final and their K/V in the cache.")
        self._m_vain_steps = m.counter(
            "bigdl_tpu_decode_steps_vain_total",
            "Decode programs sent ahead of which no slot was read: every "
            "request in them ended, or left its slot, in the step before.")
        self._m_prefill_chunks = m.counter(
            "bigdl_tpu_prefill_chunks_total",
            "Prefill chunks dispatched by admission (at most one per "
            "step).")
        self._m_prefill_tokens = m.counter(
            "bigdl_tpu_prefill_tokens_total",
            "Tokens of the dispatched prefill chunks: prompt tokens, "
            "and the padding that fills the rest of a chunk's width.",
            labelnames=("kind",))
        for kd in ("prompt", "padding"):  # render from scrape 1
            self._m_prefill_tokens.labels(kd)
        # live-migration observability: outcomes, source-side wall
        # time, and the tokens a committed migration preserved (the
        # recomputed count rides /v1/stats "migration" only)
        self._m_migrations = m.counter(
            "bigdl_tpu_migrations_total",
            "Live sequence migrations by outcome.",
            labelnames=("outcome",))
        for oc in MIGRATION_OUTCOMES:    # render from scrape 1
            self._m_migrations.labels(oc)
        self._m_migration_ms = m.histogram(
            "bigdl_tpu_migration_ms",
            "Source-side migration wall milliseconds, slot export to "
            "commit-ack.",
            buckets=(1.0, 5.0, 25.0, 100.0, 500.0, 2500.0, 10000.0))
        self._m_migrated_tokens = m.counter(
            "bigdl_tpu_migrated_tokens_total",
            "Generated-so-far tokens preserved across committed "
            "migrations (decode work NOT thrown away by a drain, "
            "rolling restart, or scale-down).")
        # pre-register the families fed by ops/probing.py and by
        # speculation (speculative.py's offline rounds, mode=draft |
        # lookup; this engine's verify step, mode=mtp) so /metrics
        # exposes them before the first probe or round in this process
        m.counter("bigdl_tpu_kernel_probe_total",
                  "Kernel compile-probe outcomes "
                  "(compiled vs XLA fallback) per kernel.",
                  labelnames=("kernel", "outcome"))
        m.histogram("bigdl_tpu_spec_accept_ratio",
                    "Speculative decoding acceptance ratio per "
                    "verify round.", labelnames=("mode",),
                    buckets=RATIO_BUCKETS)
        self._m_deferred = m.counter(
            "bigdl_tpu_admission_deferred_total",
            "Admissions deferred by the headroom guard, by reason.",
            labelnames=("reason",))
        for r in ("memory", "pages"):   # render from scrape 1
            self._m_deferred.labels(r)
        # paged-KV observability: pool pressure + radix-tree traffic.
        # PagePool/RadixCache keep plain host ints (scheduling code
        # stays metrics-free); _update_gauges mirrors them by delta-inc
        # once per working step.
        self._m_pool_exhausted = m.counter(
            "bigdl_tpu_page_pool_exhausted_total",
            "KV page-pool allocation failures (admissions deferred on "
            "pages, copy-on-write eviction fallbacks).")
        self._m_radix_lookups = m.counter(
            "bigdl_tpu_prefix_radix_lookups_total",
            "Radix prefix-tree lookups at admission, by outcome.",
            labelnames=("outcome",))
        for oc in ("hit", "miss"):       # render from scrape 1
            self._m_radix_lookups.labels(oc)
        self._m_radix_tokens = m.counter(
            "bigdl_tpu_prefix_radix_tokens_total",
            "Prompt tokens looked up vs already resident in shared "
            "radix pages.", labelnames=("kind",))
        for kd in ("looked_up", "hit"):  # render from scrape 1
            self._m_radix_tokens.labels(kd)
        self._pub_pool_exhausted = 0     # delta-inc mirror baselines
        self._pub_radix = {"lookups": 0, "hits": 0,
                           "lookup_tokens": 0, "hit_tokens": 0}
        self._m_quarantined = m.counter(
            "bigdl_tpu_requests_quarantined_total",
            "Requests failed by blast-radius isolation, by reason.",
            labelnames=("reason",))
        for r in ("nan_logits", "crash_loop"):   # render from scrape 1
            self._m_quarantined.labels(r)
        self._m_retries = m.counter(
            "bigdl_tpu_step_retries_total",
            "Engine steps retried after a transient failure.")
        self._m_faults = m.counter(
            "bigdl_tpu_faults_injected_total",
            "Faults fired by the injection harness "
            "($BIGDL_TPU_FAULT_SPEC), by kind.", labelnames=("kind",))
        self._m_draining = m.gauge(
            "bigdl_tpu_engine_draining",
            "1 while the engine refuses new requests (graceful drain).")
        self._m_shed = m.counter(
            "bigdl_tpu_requests_shed_total",
            "Requests rejected at admission by overload control, by "
            "shed reason and QoS class.", labelnames=("reason", "qos"))
        for r in SHED_REASONS:           # render from scrape 1
            for q in QOS_CLASSES:
                self._m_shed.labels(r, q)
        self._m_brownout = m.gauge(
            "bigdl_tpu_brownout_level",
            "Brownout degradation level (0 healthy ... 3 shedding "
            "batch QoS at admission).")
        self._m_brownout.set(0)
        self._m_tenant_reqs = m.counter(
            "bigdl_tpu_tenant_requests_total",
            "Per-tenant admission outcomes.",
            labelnames=("tenant", "outcome"))
        # -- service-level objectives + usage metering
        # (observability/slo.py, usage.py): the SLO tracker gets TTFT /
        # TPOT / result feeds from the hooks below and evaluates
        # burn-rate alerts on a throttle inside step(); the usage
        # ledger writes one JSONL record per finished/shed request off
        # this thread and backs GET /v1/usage
        self.slo = SLOTracker(registry=m, flight=self.flight)
        self.usage = UsageLedger()
        # request id -> (tenant, qos), set at admission (fanout
        # children individually), popped at finish — the attribution
        # map for both the SLO feeds and the usage ledger
        self._usage_meta: Dict[str, Tuple[str, str]] = {}
        # batched-cache storage footprint per component (codes vs scales);
        # shapes are static for the engine lifetime, so set once
        self._weight_bytes = tree_nbytes(self.params)
        self.ledger.register(
            "weights", "engine_params", self._weight_bytes,
            family=getattr(self.family, "name",
                           type(self.family).__name__))
        if self._paged:
            # the arena is the ONE static KV allocation: admission
            # cost stays the private cache1, and page availability —
            # not worst-case per-slot bytes — gates concurrency, so
            # max_batch can rise far past what the slab admitted in
            # the same ledger budget
            publish_paged_cache_bytes(self.cache, m)
            kvb = paged_cache_bytes(self.cache)
            self.ledger.register(
                "kv_cache", "engine_paged_arena", kvb["total"],
                dtype=self.kv_cache_dtype, codes=kvb["codes"],
                scales=kvb["scales"], pages=self._num_pages,
                page_size=self._page_size)
            self._kv_bytes_per_page = kvb["total"] // self._num_pages
            self._kv_bytes_per_slot = (
                self._kv_bytes_per_page * self._pages_per_seq)
        else:
            publish_kv_cache_bytes(self.cache, m)
            if self.cache.stats is not None:
                self._init_moe_counters(m)
            # static ledger entries: params (packed, QTensor/int4-aware)
            # and the batched KV cache; per-slot bytes drive the
            # admission cost
            kvb = kv_cache_bytes(self.cache)
            self.ledger.register(
                "kv_cache", "engine_batched", kvb["total"],
                dtype=self.kv_cache_dtype, codes=kvb["codes"],
                scales=kvb["scales"], slots=B)
            self._kv_bytes_per_slot = kvb["total"] // B
            self._kv_bytes_per_page = 0
        self.ledger.publish(m)

        # -- live roofline attribution + perf-regression sentinel
        # (observability/roofline.py + sentinel.py). The decode gauge is
        # roofline.efficiency's decode_hbm_roofline_util formula
        # evaluated each working step from the measured step wall time;
        # tests assert 4-decimal agreement with the offline math.
        # A device kind without published peaks (roofline.CHIP_PEAKS)
        # exports NO roofline gauges: a share of another chip's roof is
        # not a number.
        try:
            self._peaks: Optional[Tuple[float, float]] = \
                roofline.chip_peaks()
        except LookupError:
            self._peaks = None
        if self._peaks is not None:
            self._m_roofline = m.gauge(
                "bigdl_tpu_roofline_util",
                "Live roofline utilization per phase: decode is "
                "bandwidth-bound (ideal bytes-ms over measured ms), "
                "prefill is compute-bound (MFU).", labelnames=("phase",))
            for ph in ("decode", "prefill"):    # render from scrape 1
                self._m_roofline.labels(ph)
            self._m_decode_ideal = m.gauge(
                "bigdl_tpu_decode_ideal_ms",
                "Bandwidth-bound floor for the current decode step "
                "(weights + live KV over peak HBM GB/s).")
        self._m_perf_regress = m.counter(
            "bigdl_tpu_perf_regression_total",
            "Sentinel trips by regressed metric.",
            labelnames=("metric",))
        from bigdl_tpu.observability.sentinel import METRICS as \
            _SENTINEL_METRICS
        for mt in _SENTINEL_METRICS:        # render from scrape 1
            self._m_perf_regress.labels(mt)
        self._last_perf: Optional[dict] = None     # last decode step
        self._last_prefill_perf: Optional[dict] = None
        self._pending_perf: Optional[Tuple[int, int]] = None
        self._auto_capture_dir: Optional[str] = None
        use_sentinel = (ce.sentinel if ce.sentinel is not None
                        else sentinel_enabled())
        self.sentinel: Optional[PerfSentinel] = None
        if use_sentinel:
            self.sentinel = PerfSentinel(
                history_path=ce.perf_history,
                on_trip=self._on_perf_trip,
                on_recover=self._on_perf_recover)

        # -- live quality telemetry + QualitySentinel (observability/
        # quality.py). All histogram samples carry (qtype,
        # kv_cache_dtype, qos) so a fleet scrape can slice quality by
        # quantization format. Families exist from scrape 1 for the
        # standard QoS classes (render-before-traffic idiom above).
        self._use_quality = (ce.quality if ce.quality is not None
                             else quality_enabled())
        _qlabels = ("qtype", "kv_cache_dtype", "qos")
        self._m_q_logprob = m.histogram(
            "bigdl_tpu_quality_token_logprob",
            "Chosen-token logprob per decode step (resident path "
            "computes it inside the fused dispatch).",
            labelnames=_qlabels,
            buckets=(-16.0, -8.0, -4.0, -2.0, -1.0, -0.5, -0.25,
                     -0.1, -0.01, 0.0))
        self._m_q_entropy = m.histogram(
            "bigdl_tpu_quality_entropy",
            "Full-softmax entropy (nats) of the decode distribution.",
            labelnames=_qlabels,
            buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0))
        self._m_q_margin = m.histogram(
            "bigdl_tpu_quality_top1_margin",
            "Top-1 minus top-2 logit margin of the decode "
            "distribution.",
            labelnames=_qlabels,
            buckets=(0.1, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0))
        self._m_q_eos = m.counter(
            "bigdl_tpu_quality_eos_total",
            "EOS tokens emitted, by qtype/kv dtype/QoS.",
            labelnames=_qlabels)
        self._m_q_repeat = m.counter(
            "bigdl_tpu_quality_repeat_total",
            "Immediate token repetitions (tok == previous tok) "
            "emitted, by qtype/kv dtype/QoS.",
            labelnames=_qlabels)
        for q in QOS_CLASSES:              # render from scrape 1
            lbl = (self.qtype, self.kv_cache_dtype, q)
            self._m_q_logprob.labels(*lbl)
            self._m_q_entropy.labels(*lbl)
            self._m_q_margin.labels(*lbl)
            self._m_q_eos.labels(*lbl)
            self._m_q_repeat.labels(*lbl)
        self._m_q_probe_nll = m.gauge(
            "bigdl_tpu_quality_probe_nll",
            "Latest teacher-forced NLL over the golden probe prompts "
            "(nats/token).")
        self._m_q_regress = m.counter(
            "bigdl_tpu_quality_regression_total",
            "QualitySentinel trips by regressed metric.",
            labelnames=("metric",))
        for mt in QUALITY_METRICS:         # render from scrape 1
            self._m_q_regress.labels(mt)
        self._last_quality: Optional[dict] = None   # last observed step
        self._last_probe: Optional[dict] = None     # last probe result
        self._quality_probe_fn = None               # lazily compiled
        try:
            self._quality_probe_steps = (
                ce.quality_probe_steps
                if ce.quality_probe_steps is not None
                else resolve_quality_probe_steps())
        except ValueError:
            self._quality_probe_steps = 0   # env_check reports it
        self.qsentinel: Optional[QualitySentinel] = None
        if self._use_quality:
            self.qsentinel = QualitySentinel(
                history_path=ce.quality_history,
                on_trip=self._on_quality_trip,
                on_recover=self._on_quality_recover)
        # annotate the compile table with analytical per-jit costs so
        # compile_table()/top_offenders() rank jits by bytes moved
        try:
            for name, c in roofline.jit_costs(
                    self.cfg, self._weight_bytes, B, ce.max_seq,
                    ce.prefill_bucket, self.kv_cache_dtype).items():
                annotate_costs(name, flops=c["flops"],
                               hbm_bytes=c["hbm_bytes"])
        except AttributeError:
            pass    # a config without the dense-llama geometry fields
            #         has no cost model yet (ROADMAP D8)

        self.flight.record(
            "engine_init", max_batch=B, max_seq=ce.max_seq,
            kv_cache_dtype=self.kv_cache_dtype,
            kv_cache_total_bytes=kvb["total"],
            kv_page_size=self._page_size, kv_pages=self._num_pages,
            prefix_sharing=self.radix is not None,
            prefill_chunk=self._chunk, family=getattr(
                self.family, "name", type(self.family).__name__))
        startup_mark("engine_init_end", self.registry)

    # -- public api ---------------------------------------------------------

    def add_request(self, request_id: str, prompt_token_ids, params=None,
                    trace=None, resume=None):
        if self._mark_first_request:
            self._mark_first_request = False
            startup_mark("first_request", self.registry)
        if self._draining:
            raise EngineDraining(
                "engine is draining (admission stopped); retry against "
                "another replica")
        params = params or SamplingParams()
        ids = list(prompt_token_ids)
        long = len(ids) + 1 > self.cfg_engine.max_seq
        cp_cap = (self.cfg_engine.cp_max_seq
                  if self._cp_mesh is not None else None)
        if long and (cp_cap is None or len(ids) + 1 > cp_cap):
            raise ValueError(
                f"prompt length {len(ids)} exceeds engine max_seq "
                f"{self.cfg_engine.max_seq}"
                + ("" if cp_cap is None else
                   f" and cp_max_seq {cp_cap}"))
        if not ids:
            raise ValueError("empty prompt")
        # validate CLIENT input here (HTTP clients send raw token ids):
        # a bad id crashing inside step() would wedge the admission lane
        # for every future request
        v = self.cfg.vocab_size
        if any(not isinstance(t, (int, np.integer)) or t < 0 or t >= v
               for t in ids):
            raise ValueError(f"prompt token ids must be ints in [0, {v})")
        if params.logprobs is not None and not (
                0 <= params.logprobs < v):
            raise ValueError(f"logprobs must be in [0, {v})")
        if params.n < 1:
            raise ValueError("n must be >= 1")
        if params.max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        best_of = params.best_of or params.n
        if best_of < params.n:
            raise ValueError(f"best_of ({best_of}) < n ({params.n})")
        if self._block is not None:
            self._refuse_request_for_block_family(params, best_of)
        if resume is not None and best_of > 1:
            # migration exports only simple (non-fanout) slots; a
            # resume of a fan-out parent has no single sampler stream
            raise ValueError("migration resume requires n=1/best_of=1")
        if params.max_time_ms is not None and params.max_time_ms <= 0:
            raise ValueError("max_time_ms must be positive")
        deadline_ms = (params.max_time_ms
                       if params.max_time_ms is not None
                       else self._request_deadline_ms)
        if deadline_ms is not None:
            self._any_deadline = True
        # -- overload control: validate QoS, run every early-shedding
        # test (RequestShed -> HTTP 429/503 with Retry-After), and
        # apply the brownout max_tokens cap — all BEFORE any engine
        # state is created for the request
        qos = params.qos or self.overload.cfg.qos_default
        if qos not in QOS_CLASSES:
            raise ValueError(
                f"qos must be one of {QOS_CLASSES}, got {qos!r}")
        params = dataclasses.replace(
            params, qos=qos, tenant=params.tenant or "default")
        if resume is None:
            self._overload_admit(request_id, ids, params, deadline_ms,
                                 best_of, trace)
            cap = self.overload.max_tokens_cap()
            if cap is not None and params.max_tokens > cap:
                params = dataclasses.replace(params, max_tokens=cap)
        # a migration resume bypasses early shedding and the brownout
        # max_tokens cap: the sequence passed admission control when it
        # first entered the fleet, its staged state is already claimed
        # (a shed here would strand it mid-stream), and a cap would
        # silently truncate tokens the client was promised. The intake
        # membrane for an overloaded target is /v1/internal/migrate_in.
        with self._lock:
            self._outputs[request_id] = []
        target = self._cp_waiting if long else self.waiting
        if best_of > 1:
            # fan out into independent child sequences; ranking needs
            # per-token logprobs, so force their computation on children
            self._fanouts[request_id] = _Fanout(request_id, params.n,
                                                best_of)
            for i in range(best_of):
                cid = f"{request_id}#{i}"
                cparams = dataclasses.replace(
                    params, n=1, best_of=None,
                    seed=None if params.seed is None else params.seed + i)
                self._children[cid] = (request_id, i)
                self._usage_meta[cid] = (params.tenant, qos)
                creq = Request(cid, list(ids), cparams)
                creq.trace = trace
                if deadline_ms is not None:
                    creq.deadline = creq.arrival + deadline_ms / 1000.0
                self.tracer.start(cid, prompt_len=len(ids),
                                  t_arrival=creq.arrival,
                                  trace=self._child_trace(trace))
                target.append(creq)
            return
        self._usage_meta[request_id] = (params.tenant, qos)
        req = Request(request_id, ids, params)
        req.trace = trace
        if deadline_ms is not None:
            req.deadline = req.arrival + deadline_ms / 1000.0
        if resume is not None:
            # live-migration resume: generation continues mid-stream.
            # The generated-so-far tail already rides in the prompt;
            # the sampler stream and logprob accumulator carry over,
            # and the source's absolute deadline (if any) keeps ticking
            # — the clock does not restart on the new replica.
            req.generated_offset = int(resume.get("generated_offset", 0))
            req.resumed_cum_logprob = float(
                resume.get("cum_logprob", 0.0))
            if resume.get("dev_seed") is not None:
                req.resume_dev_seed = int(resume["dev_seed"])
            if resume.get("resume_id"):
                req.resume_id = str(resume["resume_id"])
            if resume.get("deadline") is not None:
                req.deadline = float(resume["deadline"])
                self._any_deadline = True
        self.tracer.start(request_id, prompt_len=len(ids),
                          t_arrival=req.arrival,
                          trace=self._child_trace(trace))
        target.append(req)

    @staticmethod
    def _child_trace(trace):
        # (trace_id, parent_span_id) from the wire becomes a tracer
        # 3-tuple with a fresh span id for THIS request's engine span
        if trace is None:
            return None
        return (trace[0], trace[1], new_span_id())

    def abort_request(self, request_id: str) -> None:
        """Reference api_server behavior on client disconnect
        (vllm/entrypoints/openai/api_server.py:371)."""
        fo = self._fanouts.get(request_id)
        if fo is not None:
            for i in range(fo.best_of):
                if i not in fo.scores:       # skip finished children
                    self._abort.add(f"{request_id}#{i}")
            return
        self._abort.add(request_id)

    def step_heartbeat_age(self) -> float:
        """Seconds since the last step() entered. The driving loop
        calls step() continuously (even idle), so a large age with
        unfinished work means the step loop is WEDGED — a hung device
        transfer, a replica_hang fault — while the process (and its
        HTTP threads) look alive. `/health` turns this into a 503 so a
        supervisor can kill and replace the replica."""
        return time.monotonic() - self._last_step_ts

    def has_unfinished(self) -> bool:
        # a suspended migration-out sequence is still this replica's
        # responsibility until its sender commits or resumes it —
        # draining must not declare victory while one is in flight
        return (len(self.waiting) > 0 or self._admitting is not None
                or any(s.active for s in self.slots)
                or len(self._cp_waiting) > 0 or self._cp_active is not None
                or self._cp_admitting is not None
                or bool(self._migration_meta) or bool(self._migrate_req)
                or bool(self._migration_done)
                or bool(self._migration_fail))

    def get_outputs(self, request_id: str) -> List[RequestOutput]:
        with self._lock:
            out = self._outputs.get(request_id, [])
            if any(o.finished for o in out):
                # request complete: drop the entry (unread finished entries
                # of aborted streams must not accumulate)
                self._outputs.pop(request_id, None)
            elif out:
                self._outputs[request_id] = []
        return out

    @property
    def speculative_allowed(self) -> bool:
        """False while browned out (level >= 1): speculative lookahead
        is the first work shed under pressure. Speculative drivers
        (bigdl_tpu/speculative.py harnesses) must consult this before
        each propose/verify round when serving through an engine."""
        return self.overload.speculative_allowed

    # -- overload control ----------------------------------------------------

    def _queue_bytes(self) -> int:
        """Summed prompt footprint (int32 ids) of every queued request
        — recomputed on demand so it can never drift from the queues
        themselves (admission, expiry, preemption and aborts all
        mutate them)."""
        return 4 * (sum(len(r.prompt_token_ids) for r in self.waiting)
                    + sum(len(r.prompt_token_ids)
                          for r in self._cp_waiting))

    def _drain_rate(self) -> float:
        """Measured drain rate in finished requests/sec over the
        recent finish window (0.0 until two finishes land)."""
        ft = self._finish_times
        if len(ft) >= 2 and ft[-1] > ft[0]:
            return (len(ft) - 1) / (ft[-1] - ft[0])
        return 0.0

    def _shed_retry_after(self) -> int:
        """Retry-After seconds for a capacity shed: time for the
        current backlog to drain at the measured rate (TPOT-based
        estimate before any request finished), floored higher while
        the memory ledger reports thin headroom — a memory-bound
        engine drains slower than its request rate suggests."""
        depth = len(self.waiting) + len(self._cp_waiting)
        rate = self._drain_rate()
        if rate > 0:
            est = depth / rate
        else:
            est = max(1.0, depth * max(self._tpot_ewma, 0.01))
        hr = self.ledger.headroom()
        hb, lim = hr.get("headroom_bytes"), hr.get("bytes_limit")
        if hb is not None and lim and hb < 0.1 * lim:
            est = max(est, 5.0)
        return max(1, min(60, int(math.ceil(est))))

    def _overload_admit(self, request_id: str, ids: List[int],
                        params: SamplingParams,
                        deadline_ms: Optional[float],
                        n_seqs: int, trace=None) -> None:
        """Run the controller's early-shedding tests for one incoming
        request; on shed, count + breadcrumb and re-raise."""
        depth = len(self.waiting) + len(self._cp_waiting)
        try:
            self.overload.check_admission(
                qos=params.qos, tenant=params.tenant, n_seqs=n_seqs,
                prompt_len=len(ids), queue_depth=depth,
                queue_bytes=self._queue_bytes(),
                deadline_sec=(deadline_ms / 1000.0
                              if deadline_ms is not None else None),
                tpot_sec=self._tpot_ewma,
                retry_after_sec=self._shed_retry_after(),
                now=time.monotonic())
        except RequestShed as e:
            self._m_shed.labels(e.reason, e.qos).inc()
            # tenant ids are admission-controlled (PR-7 quota map),
            # not caller-invented — audited
            self._m_tenant_reqs.labels(e.tenant, "shed").inc()  # graftlint: disable=metric-label-cardinality
            # a shed spends the availability budget and is a ledger
            # line the tenant can reconcile against their 429s
            self.slo.observe_result(e.qos, "shed")
            self.usage.record_shed(request_id, e.tenant, e.qos,
                                   e.reason)
            self.flight.record(
                "shed", step=self._step_idx, request_id=request_id,
                reason=e.reason, qos=e.qos, tenant=e.tenant,
                retry_after_sec=e.retry_after_sec, queue_depth=depth,
                brownout_level=self.overload.level,
                **({"trace_id": trace[0]} if trace else {}))
            if trace is not None:
                self.spans.annotate(trace[0], "shed", parent_id=trace[1],
                                    request_id=request_id,
                                    reason=e.reason, qos=e.qos,
                                    tenant=e.tenant)
            raise
        # tenant ids are admission-controlled (PR-7 quota map) —
        # audited
        self._m_tenant_reqs.labels(params.tenant, "admitted").inc()  # graftlint: disable=metric-label-cardinality

    def _overload_pressure(self) -> float:
        """Measured pressure in [0, 1]: worst of queue-depth ratio,
        memory-ledger headroom exhaustion, and decode-step latency
        inflation over its observed floor (3x the floor saturates;
        `_decode_step` says which steps count, and for how much)."""
        p = ((len(self.waiting) + len(self._cp_waiting))
             / max(1, self.overload.cfg.max_queue_depth))
        hr = self.ledger.headroom()
        hb, lim = hr.get("headroom_bytes"), hr.get("bytes_limit")
        if hb is not None and lim:
            p = max(p, 1.0 - hb / lim)
        if self._decode_floor and self._decode_ewma > self._decode_floor:
            p = max(p, (self._decode_ewma / self._decode_floor - 1.0)
                    / (_INFLATION_SATURATES - 1.0))
        return min(1.0, max(0.0, p))

    def _update_brownout(self) -> None:
        pressure = self._overload_pressure()
        storm = self.faults.storm_pressure(self._step_idx)
        if storm is not None:
            pressure = max(pressure, storm)
        if self.overload.update_pressure(pressure) is not None:
            self._m_brownout.set(self.overload.level)
            self.flight.record(
                "brownout", step=self._step_idx,
                level=self.overload.level, pressure=round(pressure, 4),
                speculative_allowed=self.overload.speculative_allowed)
            self.spans.annotate_recent(
                "brownout", level=self.overload.level,
                pressure=round(pressure, 4))

    # -- engine internals ---------------------------------------------------

    def _bucket(self, n: int) -> int:
        b = self.cfg_engine.prefill_bucket
        while b < n:
            b *= 2
        return min(b, self.cfg_engine.max_seq)

    def _admission_cost(self, prompt_len: int) -> int:
        """HBM bytes the admission of a prompt of this length newly
        allocates: its private 1-row prefill cache, sized exactly as
        `_admission_step` will size it (chunk-multiple >= bucket)."""
        bucket = self._bucket(prompt_len)
        chunk = min(self._chunk, bucket)
        alloc = -(-bucket // chunk) * chunk
        return cache_nbytes(self._cache_spec.unrolled(), 1, alloc,
                            self.kv_cache_dtype)["total"]

    def _init_speculative(self) -> None:
        """The programs and the device state of `speculative_tokens`: a
        slot's standing draft `d` and the distribution `q` it was drawn
        from stay on the device between steps (`_mtp_draft` `[B]`, -1
        where a slot has none; `_mtp_q` `[B, V]`) and are never fetched.

        The state a slot is in between steps, with `n` rows in the cache
        and `x` its last token (position `n`, not yet cached): the MTP
        module's own rows `0 .. n - 1` are written (row i from the main
        stack's hidden row i and token i + 1) and its row `n - 1` gave
        `q`, the distribution of token `n + 1`, and `d ~ q`. Every path
        below keeps it: the verify step, the plain step (brownout, or a
        slot that needs the host sampler) followed by `engine_mtp_row`,
        and admission (the last chunk's hidden row and the first sampled
        token through `engine_mtp_row`). A slot with no draft (-1: a
        sequence that arrived by migration) is verified as a rejection
        with `q = 0`, which is the plain step's draw from `p`.

        A verify step takes all it needs from what the step before it
        left on the device, so it goes out one step ahead as the plain
        resident step does (`_ahead`, `_may_lead`)."""
        from bigdl_tpu.speculative import accept_and_resample

        ce, fam, cfg = self.cfg_engine, self.family, self.cfg
        depth_of = getattr(fam, "speculative_depth", None)
        depth = int(depth_of(cfg)) if depth_of is not None else 0
        if ce.speculative_tokens < 0 or ce.speculative_tokens > depth:
            raise ValueError(
                f"speculative_tokens={ce.speculative_tokens}: the "
                f"{getattr(fam, 'name', '?')!r} family drafts "
                f"{depth} token(s) ahead (its own multi-token-prediction "
                "module is what the engine's step drafts with)")
        if not getattr(fam, "rewindable", True):
            raise ValueError(
                "speculative_tokens needs a cache that can disown a "
                "written row (CACHE_REWINDABLE)")
        if self._paged or ce.prefix_cache_entries > 0:
            raise ValueError(
                "speculative_tokens runs on the slab without the host "
                "prefix cache: a page table or a prefix snapshot carries "
                "no MTP rows' hidden state")
        fwd_hidden, mtp_forward = fam.forward_hidden, fam.mtp_forward
        b, s_max = ce.max_batch, ce.max_seq
        self._mtp_draft = jnp.full((b,), -1, jnp.int32)
        self._mtp_q = jnp.zeros((b, int(cfg.vocab_size)), jnp.float32)

        def transform(lg, temps, top_ks, top_ps):
            """`_transform_rows`, its counts and its nucleus branch
            skipped while no slot asks for a top-k or a top-p."""
            plain = jnp.all((top_ks <= 0) & (top_ps >= 1.0))
            return jax.lax.cond(
                plain,
                lambda: lg.astype(jnp.float32)
                / jnp.maximum(temps, 1e-6)[:, None],
                lambda: _transform_rows(lg, temps, top_ks, top_ps)[0])

        def keys_at(seeds, poss, stream):
            """One key a slot for the draw of the token at absolute
            index `poss`: stream 0 is the sampler's own gumbel
            (`_device_sample_rows`' key), 1 the accept test's uniform, 2
            the draft's gumbel."""
            def one(seed, pos):
                key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
                return key if stream == 0 else jax.random.fold_in(key,
                                                                  stream)
            return jax.vmap(one)(seeds, poss)

        def gumbel_argmax(t, keys):
            gum = jax.vmap(lambda k: jax.random.gumbel(
                k, t.shape[1:], jnp.float32))(keys)
            return jnp.argmax(t + gum, axis=-1).astype(jnp.int32)

        def next_draft(mt_lg, temps, top_ks, top_ps, seeds, poss):
            """The draft of the token at index `poss` and the
            distribution it is drawn from, off the MTP module's logits
            `[B, V]`."""
            t = transform(mt_lg, temps, top_ks, top_ps)
            drawn = gumbel_argmax(t, keys_at(seeds, poss, 2))
            d = jnp.where(temps <= 0.0,
                          jnp.argmax(mt_lg, axis=-1).astype(jnp.int32),
                          drawn)
            return d, jax.nn.softmax(t, axis=-1)

        @functools.partial(tracked_jit, "engine_decode_resident_mtp",
                           registry=self.registry,
                           donate_argnums=(1, 3, 4, 5))
        def decode_resident_mtp(params, ints, floats, drafts, q, cache):
            """One verify step: the main stack over `[x, d]` a slot,
            health of both rows, accept / resample / bonus, the MTP
            module over the rows that became final, the next drafts,
            and `pos` advanced by what each slot kept.

            What the host gives and takes is PACKED, because a transfer
            of a few bytes costs the step about a millisecond each way:
            `ints` `[4, B]` (each slot's last token, -1 for none; top-k;
            seed; the absolute index of the token it samples next) and
            `floats` `[2, B]` (temperature, top-p) go in; out come ONE
            `[B, 4]` block (the two tokens, how many were kept, health)
            and the NEXT step's `ints` (the last kept token, the index
            moved on), which the host hands back untouched while the
            same requests hold the same slots (`_io`)."""
            tokens, top_ks, seeds, poss = ints
            temps, top_ps = floats
            live = tokens >= 0
            has = live & (drafts >= 0)
            d = jnp.maximum(drafts, 0)
            both = jnp.stack([jnp.maximum(tokens, 0), d], axis=1)
            pos0 = jnp.where(live, cache.pos, 0)
            lg, hid, cache = fwd_hidden(
                params, cfg, both,
                cache.replace(pos=jnp.where(live, cache.pos, -1)))
            finite = jnp.isfinite(lg).all(axis=(-2, -1))
            greedy = temps <= 0.0
            with jax.named_scope("sampler"):
                rep = lambda a: jnp.repeat(a, 2)            # noqa: E731
                v = lg.shape[-1]
                p = jax.nn.softmax(transform(
                    lg.reshape(-1, v), rep(temps), rep(top_ks),
                    rep(top_ps)).reshape(lg.shape), axis=-1)
                u = jax.vmap(jax.random.uniform)(keys_at(seeds, poss, 1))
                n_acc, dist = accept_and_resample(
                    p, jnp.where(has[:, None], q, 0.0)[:, None],
                    d[:, None], u[:, None], has[:, None])
                acc_s = n_acc > 0
                # the resampled token is token `poss`; the bonus token
                # after an accept is token `poss + 1`
                y_s = gumbel_argmax(
                    jnp.log(jnp.maximum(dist, 1e-30)),
                    keys_at(seeds, poss + acc_s.astype(jnp.int32), 0))
                am = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # [B, 2]
                acc_g = has & (am[:, 0] == d)
                acc = jnp.where(greedy, acc_g, acc_s)
                y = jnp.where(greedy, jnp.where(acc_g, am[:, 1], am[:, 0]),
                              y_s)
            first = jnp.where(acc, d, y)
            toks = jnp.stack([first, y], axis=1)
            n_emit = jnp.where(acc, 2, 1).astype(jnp.int32)
            # the MTP module over the rows that became final: (h_n,
            # first) and, where the draft was accepted, (h_{n+1}, bonus);
            # a rejected slot's second row is dead (overwritten next step)
            mt_lg, cache = mtp_forward(params, cfg, hid, toks, cache, pos0)
            last = jnp.take_along_axis(
                mt_lg, (n_emit - 1)[:, None, None], axis=1)[:, 0]
            with jax.named_scope("sampler"):
                d_new, q_new = next_draft(last, temps, top_ks, top_ps, seeds,
                                          poss + n_emit)
            out = jnp.concatenate(
                [toks, n_emit[:, None], finite.astype(jnp.int32)[:, None]],
                axis=1)
            # the last kept token is `y` either way: the bonus token
            # after an accept, the resampled one after a reject
            ints = jnp.stack([jnp.where(live, y, -1), top_ks, seeds,
                              poss + n_emit])
            return (out, ints, jnp.where(live, d_new, -1), q_new,
                    cache.replace(pos=jnp.where(live, pos0 + n_emit, 0)))

        self._decode_resident_mtp = decode_resident_mtp

        @functools.partial(tracked_jit, "engine_mtp_row",
                           registry=self.registry, donate_argnums=(1, 2, 3))
        def mtp_row(params, cache, drafts, q, hidden, tokens, temps, top_ks,
                    top_ps, seeds, poss):
            """The MTP module's row of each slot's LAST cached position
            (`hidden` `[B, D]` its main-stack hidden row, `tokens` `[B]`
            the token that follows it, -1 for a slot that takes no
            part: nothing of it is written), and so the slot's draft of
            token `poss`. Admission and the plain step end with it."""
            take = tokens >= 0
            at = jnp.where(take, cache.pos - 1, 0)
            mt_lg, cache = mtp_forward(
                params, cfg, hidden[:, None], jnp.maximum(tokens, 0)[:, None],
                cache, at, wpos=jnp.where(take, at, s_max))
            with jax.named_scope("sampler"):
                d_new, q_new = next_draft(mt_lg[:, 0], temps, top_ks, top_ps,
                                          seeds, poss)
            return (cache, jnp.where(take, d_new, drafts),
                    jnp.where(take[:, None], q_new, q))

        self._mtp_row = mtp_row

        @functools.partial(tracked_jit, "engine_decode_hidden",
                           registry=self.registry, donate_argnums=(2,))
        def decode_hidden(params, tokens, cache):
            """`engine_decode` with the hidden rows beside the logits:
            the plain step of a speculating engine (`engine_mtp_row`
            follows it, once the tokens are sampled)."""
            live = tokens >= 0
            lg, hid, out = fwd_hidden(
                params, cfg, jnp.maximum(tokens, 0)[:, None],
                cache.replace(pos=jnp.where(live, cache.pos, -1)))
            return lg[:, -1, :], hid[:, -1, :], out.replace(
                pos=jnp.where(live, out.pos, 0))

        self._decode_hidden = decode_hidden

        @functools.partial(tracked_jit, "engine_prefill_mtp",
                           registry=self.registry, donate_argnums=(2,))
        def prefill_chunk_mtp(params, tokens, cache1, carry, row):
            """`engine_prefill` with the MTP module's lagged pass over
            the chunk; also returns the hidden row `row` of the chunk
            (the last prompt position's starts the slot's first draft)
            and the next chunk's carry."""
            lg, hid, cache1 = fwd_hidden(params, cfg, tokens, cache1,
                                         carry=carry)
            return (lg, jax.lax.dynamic_index_in_dim(hid, row, 1, False),
                    hid[:, -1], cache1)

        self._prefill_mtp = prefill_chunk_mtp
        m = self.registry
        self._m_mtp_drafts = m.counter(
            "bigdl_tpu_mtp_drafts_total",
            "Drafts of the family's multi-token-prediction module that a "
            "verify step judged, by outcome.", labelnames=("outcome",))
        self._m_mtp_slot_steps = m.counter(
            "bigdl_tpu_mtp_slot_steps_total",
            "Slot-steps of a speculating engine: kind=verify two rows a "
            "slot (one or two tokens kept), kind=plain one row and one "
            "token (brownout, or a slot that needs the host sampler).",
            labelnames=("kind",))
        for oc in ("accepted", "rejected"):     # render from scrape 1
            self._m_mtp_drafts.labels(oc)
        for kd in ("verify", "plain"):
            self._m_mtp_slot_steps.labels(kd)
        self._m_spec_accept = m.histogram(
            "bigdl_tpu_spec_accept_ratio",
            "Speculative decoding acceptance ratio per "
            "verify round.", labelnames=("mode",),
            buckets=RATIO_BUCKETS).labels("mtp")

    # -- a family whose step is a block ------------------------------------

    def _refuse_for_block_family(self) -> None:
        """What a family that generates by diffusion over blocks cannot
        be served with, each said at construction with its reason."""
        ce, blk = self.cfg_engine, self._block
        name = getattr(self.family, "name", "?")
        b = blk.length
        if ce.speculative_tokens:
            raise ValueError(
                f"speculative_tokens={ce.speculative_tokens}: the {name!r} "
                "family's step denoises a block and has no next token to "
                "draft; serve it with speculative_tokens=0")
        if ce.prefix_cache_entries > 0:
            raise ValueError(
                f"prefix_cache_entries={ce.prefix_cache_entries}: the "
                f"{name!r} family's rows see their whole block, so a "
                "snapshot is a valid prefix at a block's edge only "
                "(prefix reuse at block edges is not built); serve it "
                "with prefix_cache_entries=0")
        if self._paged:
            raise ValueError(
                f"kv_page_size={self._page_size}: the {name!r} family's "
                "block pass reads and writes the slab's planes (no paged "
                "forward writes a block's rows pass after pass); serve it "
                "with kv_page_size=0")
        if self.kv_cache_dtype != "bf16":
            raise ValueError(
                f"kv_cache_dtype={self.kv_cache_dtype!r}: the {name!r} "
                "family's planes are bf16 only (a denoise pass's rows are "
                "overwritten by every later pass: no scale plane follows "
                "them); serve it with kv_cache_dtype='bf16'")
        chunk = 1 << (max(1, ce.prefill_chunk).bit_length() - 1)
        if chunk % b or ce.prefill_bucket % b or ce.max_seq % b:
            raise ValueError(
                f"block_length {b}: prefill_chunk {chunk}, prefill_bucket "
                f"{ce.prefill_bucket} and max_seq {ce.max_seq} must be "
                "multiples of it (a chunk starts and ends on a block's "
                "edge)")

    def _refuse_request_for_block_family(self, params, best_of) -> None:
        """What a request cannot ask of a block family: its tokens are
        sampled on the device inside the block pass, which carries no
        penalty counts and returns no logits."""
        if params.logprobs is not None or best_of > params.n:
            raise ValueError(
                "logprobs (and best_of > n, which ranks by them) need a "
                "row's logits on the host; a block pass returns the "
                "tokens its confidences committed, no logits")
        if params.needs_counts:
            raise ValueError(
                "repetition / presence / frequency penalties count the "
                "tokens before a row; the rows of a block are final out "
                "of sequence order")

    def _init_block_step(self, fwd) -> None:
        """The resident program of a block family: ONE pass over the
        block of every live slot. A slot's block (its ids, which rows
        are MASK, the pass's index) stays ON the device between passes,
        packed as `_io` packs the plain step's: `state` `[slots, 2 B +
        5]` int32 (B ids, B MASK flags, the denoise passes the block has
        had, top-k, seed, the index among the request's generated tokens
        of the block's first row, live) and `floats` `[2, slots]`
        (temperature, top-p) go in; out come ONE int32 block `[slots, B
        + 2]` (a row's token where it is final after the pass, -1 where
        it is still MASK; whether the pass STORED; health) and the next
        pass's `state`.

        A pass runs the family's forward on the block's `B` rows at the
        slot's `pos` (the rows' K/V land in the planes at the block's
        positions, over those of the pass before). Where a MASK is left
        it is a DENOISE pass: every row samples `x0` with its
        probability under the distribution it was drawn from, the MASK
        id's logit at -inf, `_block_transfer` commits rows, `pos`
        stays. Where none is left it is the STORING pass: the K/V just
        written are those of the final ids and stand, `pos` moves by
        `B`, and the state is the next block, all MASK. One program
        serves a batch whose slots are at different passes."""
        blk = self._block
        b, mask_id = blk.length, blk.mask_id

        @functools.partial(tracked_jit, "engine_block_resident",
                           registry=self.registry, donate_argnums=(1, 3),
                           static_argnames=("all_greedy",))
        def block_decode_resident(params, state, floats, cache, *,
                                  all_greedy):
            # (the XLA module is `jit_block_decode_resident`: this
            # family's DECODE program, which is how a trace finds one)
            ids, masked = state[:, :b], state[:, b:2 * b] != 0
            s, top_ks, seeds, first = (state[:, 2 * b + j] for j in range(4))
            live = state[:, 2 * b + 4] != 0
            temps, top_ps = floats
            store = ~jnp.any(masked, axis=1)
            pos0 = cache.pos
            with jax.named_scope("block.denoise"):
                logits, cache = fwd(
                    params, self.cfg, jnp.where(masked, mask_id, ids),
                    cache.replace(pos=jnp.where(live, pos0, -1)))
                finite = jnp.isfinite(logits).all(axis=(1, 2))
                x0, conf = _block_sample(logits, blk, temps, top_ks, top_ps,
                                         seeds, first, all_greedy)
            commit = _block_transfer(conf, masked, s, blk)
            with jax.named_scope("block.store"):
                after = jnp.where(commit, x0, ids)
                left = masked & ~commit
                out = jnp.concatenate(
                    [jnp.where(left, -1, after),
                     store.astype(jnp.int32)[:, None],
                     finite.astype(jnp.int32)[:, None]], axis=1)
                nxt = store[:, None]
                state = jnp.concatenate(
                    [jnp.where(nxt, mask_id, after),
                     (nxt | left).astype(jnp.int32),
                     jnp.stack([jnp.where(store, 0, s + 1), top_ks, seeds,
                                jnp.where(store, first + b, first),
                                live.astype(jnp.int32)], axis=1)], axis=1)
                cache = cache.replace(pos=jnp.where(
                    live, jnp.where(store, pos0 + b, pos0), 0))
            return out, state, cache

        self._block_resident = block_decode_resident

    def _block_args(self, active):
        """`(holders, state, floats)` of a block pass over the slots
        `active` from the host's view of their blocks (`_step_args`)."""
        blk, n = self._block, self.cfg_engine.max_batch
        b = blk.length
        state = np.zeros((n, 2 * b + 5), np.int32)
        state[:, :b] = blk.mask_id
        state[:, b:2 * b] = 1
        temps, top_ks, top_ps, seeds, _ = self._sampling_arrays(active)
        for i in active:
            s = self.slots[i]
            at = s.block
            state[i, :b] = [t if t >= 0 else blk.mask_id for t in at.ids]
            state[i, b:2 * b] = [t < 0 for t in at.ids]
            # the index among the request's generated tokens of the
            # block's first row: what seeds a row's draw, the same
            # before and after a preempt-resume
            first = at.pos - (len(s.req.prompt_token_ids)
                              - s.req.generated_offset)
            state[i, 2 * b:] = (at.s, top_ks[i], seeds[i], first, 1)
        with self.phases.phase("dispatch.h2d", child=True):
            state_dev = jnp.asarray(state)
            floats = jnp.asarray(np.stack([temps, top_ps]))
        return (tuple((i, self.slots[i].req) for i in active), state_dev,
                floats)

    def _block_may_lead(self, active) -> bool:
        """`_may_lead` of a block family: the pass just sent can end no
        slot of `active` by its length (it streams `B` tokens at most),
        and the pass after it writes no row past the slab."""
        b, s_max = self._block.length, self.cfg_engine.max_seq
        for i in active:
            s = self.slots[i]
            r = s.req
            if r.params.max_tokens - (r.generated_offset
                                      + len(s.generated)) <= b:
                return False
            if s.block.pos + 3 * b > s_max:
                return False
        return True

    def _prefill_len(self, req: Request) -> int:
        """Prompt tokens an admission prefills: all of them, or of a
        block family the prompt's WHOLE blocks (its tail opens the first
        generated block as given tokens)."""
        n = len(req.prompt_token_ids)
        if self._block is None:
            return n
        return n // self._block.length * self._block.length

    def _sampling_arrays(self, rows):
        """The device sampler's per-slot arguments for the slots `rows`
        (`[max_batch]` each): temperature, top-k, top-p, seed and the
        absolute index of the token each slot samples next."""
        b = self.cfg_engine.max_batch
        temps = np.zeros((b,), np.float32)
        top_ks = np.zeros((b,), np.int32)
        top_ps = np.ones((b,), np.float32)
        seeds = np.zeros((b,), np.int32)
        poss = np.zeros((b,), np.int32)
        for i in rows:
            s = self.slots[i]
            p = s.req.params
            temps[i] = p.temperature
            top_ks[i] = p.top_k
            top_ps[i] = p.top_p
            seeds[i] = s.dev_seed
            poss[i] = s.req.generated_offset + len(s.generated)
        return temps, top_ks, top_ps, seeds, poss

    @staticmethod
    def _simple(s: _Slot) -> bool:
        # no penalty counts, no logprobs: the device sampler covers it
        # (any temperature / top-k / top-p / seed)
        return s.counts is None and s.n_logprobs < 0

    def _packed_step(self, active) -> Optional[str]:
        """The packed step that serves the slots `active`, "plain" or
        "verify", or None. Resident fast path: when every active slot
        is device-samplable and no fault clause is live (poison_rows
        edits logits on the host side), forward + health + sampling
        run as ONE dispatch, and the [B, V] logits never exist outside
        the executable. A speculating engine's is the verify step (two
        rows a slot); it falls to the plain one-row step, followed by
        the MTP module's row, under brownout and with any slot that
        needs the host sampler."""
        if self._block is not None:
            return "block"
        if not (decode_resident_enabled() and not self._paged
                and not self.faults.enabled
                and all(self._simple(self.slots[i]) for i in active)):
            return None
        if not self._mtp:
            return "plain"
        return "verify" if self.speculative_allowed else None

    def _sampler_path(self, active) -> str:
        """What the device sampler does for the slots `active`, by the
        predicate it evaluates itself (`_transform_rows`): "greedy",
        "topk" (a slot samples, no row is sorted) or "nucleus"."""
        sampled = [p for p in (self.slots[i].req.params for i in active)
                   if p.temperature > 0.0]
        if not sampled:
            return "greedy"
        return "nucleus" if any(p.top_p < 1.0 for p in sampled) else "topk"

    def _sent_decode(self, sent: str, path: str) -> None:
        """A decode program went out: `sent` "ahead" of the step that
        reads it, or "in_step"; `path` its `_sampler_path`."""
        self._m_decode_steps.labels(sent).inc()
        self._m_sampler_steps.labels(path).inc()
        self._chunk_unanswered = False

    def _step_args(self, active):
        """`(holders, ints, floats)` of a packed step over the slots
        `active`: the device's own where `_io` stands for them (nothing
        goes to the device while the same requests hold the same
        slots), else one put each from the host's view."""
        holders = tuple((i, self.slots[i].req) for i in active)
        io, self._io = self._io, None
        if io is not None and len(io[0]) == len(holders) and all(
                i == j and r is q for (i, r), (j, q) in zip(io[0], holders)):
            return io
        if self._block is not None:
            return self._block_args(active)
        temps, top_ks, top_ps, seeds, poss = self._sampling_arrays(active)
        with self.phases.phase("dispatch.h2d", child=True):
            ints = jnp.asarray(np.stack(
                [self._token_row(active), top_ks, seeds, poss]))
            floats = jnp.asarray(np.stack([temps, top_ps]))
        return holders, ints, floats

    def _token_row(self, active) -> np.ndarray:
        """Each slot's last token, `[max_batch]` int32; -1: the slot
        holds no request (decode_forward)."""
        tokens = np.full((self.cfg_engine.max_batch,), -1, np.int32)
        for i in active:
            tokens[i] = self.slots[i].last_token
        return tokens

    def _send_step(self, verify: bool, active, ints, floats,
                   ahead: bool = False):
        """Dispatch one packed step over the slots `active`: the block
        the host will fetch and the `ints` after it."""
        path = self._sampler_path(active)
        if self._block is not None:
            out, ints, self.cache = self._block_resident(
                self.params, ints, floats, self.cache,
                all_greedy=path == "greedy")
        elif verify:
            (out, ints, self._mtp_draft, self._mtp_q,
             self.cache) = self._decode_resident_mtp(
                self.params, ints, floats, self._mtp_draft, self._mtp_q,
                self.cache)
        else:
            out, ints, self.cache = self._decode_resident(
                self.params, ints, floats, self.cache,
                all_greedy=path == "greedy",
                with_quality=self._use_quality)
        self._sent_decode("ahead" if ahead else "in_step", path)
        return out, ints

    @property
    def _joining(self) -> bool:
        """An admission's last chunk is out and its first token not yet
        read: its slot joins the next step, which goes out once the
        token is known."""
        a = self._admitting
        return a is not None and a.last is not None

    def _send_ahead(self) -> None:
        """The close of a decode step that leaves no step in flight (it
        read one that went out ahead and the device's `ints` no longer
        serve: a request ended, a newcomer joined, a slot is at its
        end): the next one goes out now, on the host's view of the
        slots that go on, so that the device does not wait for the next
        step's sweep and admission, and a chunk that step dispatches
        follows a decode step and not the last chunk. Not while a
        prompt's last chunk awaits its first token (`_may_lead`)."""
        going = [i for i, s in enumerate(self.slots) if s.active]
        packed = self._packed_step(going) if going else None
        if packed is None or self._joining:
            return
        with self.phases.phase("dispatch"):
            holders, ints, floats = self._step_args(going)
            out, ints = self._send_step(packed == "verify", going, ints,
                                        floats, ahead=True)
        self._ahead = (holders, out, ints, floats)

    def _take_ahead(self, active):
        """Takes up the step that went out during the last one, if one
        did: it IS this step for the slots whose requests it held and
        that are still here (a slot admitted since waits a step; one that
        ended since is read no further). Returns that step (None where
        none serves a slot of `active`: sent in vain), the slots this
        step serves, and whether they are all of `active` and all the
        step sent ahead held, so that this step may lead in its turn."""
        ahead, self._ahead = self._ahead, None
        if ahead is None:
            return None, active, True
        held = dict(ahead[0])
        stay = [i for i in active if held.get(i) is self.slots[i].req]
        if not stay:
            self._m_vain_steps.inc()
            return None, active, True
        return ahead, stay, len(stay) == len(active) == len(held)

    def _dispatch_packed(self, verify: bool, active, ahead, lead: bool,
                         rows_a_slot: int):
        """A packed step's dispatch, inside its `dispatch` phase: this
        step's output block (of `ahead`, the step `_take_ahead` took up,
        or sent here from `_step_args`), and, where `lead` and
        `_may_lead` allow, the NEXT step sent out now on what this one
        leaves on the device, so that the device does not wait while
        the host reads and emits this step's tokens (what it holds of a
        slot that this step ends is never read: `_finish` sets the
        slot's `pos` back). Returns `(out_dev, io_next)`: `io_next` is
        the device's view of the next step where none went out, for
        `_keep_io`. The caller lets go of `ahead` with the call: the
        arguments are released inside the phase, while the program
        runs, not after the wait, where the device idles."""
        io_next = None
        if ahead is not None:
            holders, out_dev, ints_dev, floats_dev = ahead
        else:
            holders, ints_dev, floats_dev = self._step_args(active)
            out_dev, ints_dev = self._send_step(verify, active, ints_dev,
                                                floats_dev)
        if lead and self._may_lead(active, rows_a_slot):
            out_next, ints_next = self._send_step(
                verify, active, ints_dev, floats_dev, ahead=True)
            self._ahead = (holders, out_next, ints_next, floats_dev)
        elif ahead is None or lead:
            io_next = (holders, ints_dev, floats_dev)
        return out_dev, io_next

    def _keep_io(self, io_next, active) -> None:
        """At a packed step's emit: where no step went out ahead and
        every slot the step served goes on, the device's view of the
        next step (`_dispatch_packed`'s `io_next`) stands."""
        if io_next is not None and len(active) == len(io_next[0]) and all(
                self.slots[i].active for i in active):
            self._io = io_next

    def _may_lead(self, active, rows_a_slot: int) -> bool:
        """Whether the step AFTER the one just sent may go out before
        its tokens are read: no slot of `active` can end this step by
        its length, so that only a stop token, an abort or a deadline
        leaves a step computed in vain, and no step writes a row past
        the slab. A step computes `rows_a_slot` rows a slot (two of a
        verify step). An admission does not stop it: the device
        alternates chunk and step as it did, and a slot admitted
        meanwhile waits one step. But not behind a prompt's LAST chunk:
        the program that samples its first token would queue behind the
        step, and the first token wait a step longer."""
        if self._joining:
            return False
        if self._block is not None:
            return self._block_may_lead(active)
        s_max = self.cfg_engine.max_seq
        for i in active:
            s = self.slots[i]
            r = s.req
            if r.params.max_tokens - (r.generated_offset
                                      + len(s.generated)) <= rows_a_slot:
                return False
            if (len(r.prompt_token_ids) + len(s.generated)
                    + 2 * rows_a_slot >= s_max):
                return False
        return True

    def _mtp_rows_after(self, rows, hidden_dev) -> None:
        """`engine_mtp_row` for the slots `rows`, each with its last
        token: writes the MTP module's row of the position before it
        and leaves the slot's standing draft. `hidden_dev` `[B, D]`."""
        self._io = None           # a slot changed hands, or a plain step
        for i in rows:
            self.slots[i].drafted = True
        puts = [jnp.asarray(a) for a in
                (self._token_row(rows),) + self._sampling_arrays(rows)]
        self.cache, self._mtp_draft, self._mtp_q = self._mtp_row(
            self.params, self.cache, self._mtp_draft, self._mtp_q,
            hidden_dev, *puts)
        del puts

    def _admission_step(self) -> None:
        """Advance chunked admission by AT MOST one chunk (bounds the
        decode gap a long prompt can cause). Starts a new admission when
        a slot is free and the queue is non-empty."""
        self._drain_migrations()    # before handoffs: slab-mode imports
        self._drain_handoffs()      # ride the handoff staging inbox
        a = self._admitting
        # the last chunk went out beside live streams and no decode
        # program has followed it (a step that read a step sent ahead
        # and could send none): this step's goes first
        hold = self._chunk_unanswered and any(s.active for s in self.slots)
        if a is None:
            free = next((i for i, s in enumerate(self.slots)
                         if not s.active), None)
            if free is None or hold:
                return
            # overload-aware scheduling replaces pure FCFS: strict QoS
            # priority with aging promotion, then least-served tenant
            # (deficit round-robin, quantum 1), then arrival order. The
            # pick runs over a snapshot (HTTP threads append
            # concurrently) and is removed by identity.
            req = None
            while req is None:
                snapshot = list(self.waiting)
                if not snapshot:
                    return
                cand = snapshot[self.overload.select_index(
                    snapshot, time.time())]
                try:
                    self.waiting.remove(cand)
                except ValueError:
                    return               # raced with another mutation
                if cand.request_id in self._abort:
                    # aborted while still queued: the client is owed a
                    # finished output or its poll loop never ends
                    self._abort.discard(cand.request_id)
                    self._push_output(cand.request_id, RequestOutput(
                        cand.request_id, [], True, "abort"))
                    self._obs_finish(cand.request_id, "abort")
                    cand = None
                req = cand
            # headroom guard: the admission's private prefill cache is
            # the one new HBM allocation this path makes — defer (FCFS
            # order kept, request back at the FRONT) while it would
            # push bytes_in_use past the budget. would_fit() is None on
            # backends without memory_stats(): always admit there.
            cost = self._admission_cost(len(req.prompt_token_ids))
            if self.ledger.would_fit(cost) is False:
                self.waiting.appendleft(req)
                self._deferred_admissions += 1
                self._m_deferred.labels("memory").inc()
                if not self._deferred_streak:
                    self._deferred_streak = True
                    hr = self.ledger.headroom()
                    self.flight.record(
                        "admit_deferred", step=self._step_idx,
                        request_id=req.request_id, reason="memory",
                        needed_bytes=cost,
                        headroom_bytes=hr.get("headroom_bytes"),
                        bytes_limit=hr.get("bytes_limit"))
                return
            self._deferred_streak = False
            self.overload.note_scheduled(req.params.tenant or "default")
            # private cache sized to a chunk multiple (>= bucket) so no
            # chunk write can straddle the end; _insert clips the splice
            # back down to the batched cache's max_seq. Brownout level
            # >= 2 shrinks the chunk (still a power of two) so admission
            # work yields to in-flight decodes sooner under pressure.
            bucket = self._bucket(len(req.prompt_token_ids))
            chunk = min(max(1 if self._block is None
                            else self._block.length, self._chunk
                            >> self.overload.chunk_shift()), bucket)
            alloc = -(-bucket // chunk) * chunk
            shared_pages = new_pages = None
            if self._paged:
                # page-side reservation FIRST (before the cache1 HBM
                # allocation): radix longest-prefix match + worst-case
                # page grab, or a requeue-and-defer on exhaustion
                paged_adm = self._paged_admit(req, chunk)
                if paged_adm is None:
                    return
                consumed, shared_pages, new_pages = paged_adm
            # a ring of the slab is a plane in position order here: the
            # chunk's padding must not overwrite live columns
            cache1 = init_cache_spec(self._cache_spec.unrolled(), 1, alloc,
                                     kv_cache_dtype=self.kv_cache_dtype)
            if self._paged:
                if consumed:
                    cache1 = self._seed_pages(
                        cache1, self.cache,
                        jnp.asarray(np.asarray(shared_pages, np.int32)),
                        jnp.asarray(consumed, jnp.int32))
            elif self._block is not None:
                # `seeded` is refused with the prefix cache: a staged
                # snapshot (a handoff's) is no prefix of a block family
                consumed = 0
            else:
                consumed, seed_kv = self._seed_from_prefix_cache(
                    req.prompt_token_ids, chunk)
                if consumed:
                    cache1 = cache1.seeded(seed_kv, consumed)
            a = self._admitting = _Admission(req, free, bucket, consumed,
                                             cache1, chunk,
                                             shared_pages=shared_pages,
                                             new_pages=new_pages)
            self.tracer.admitted(req.request_id)
            self.flight.record(
                "admit_start", step=self._step_idx,
                request_id=req.request_id, slot=free, bucket=bucket,
                prompt_len=len(req.prompt_token_ids),
                prefix_seeded=consumed)
            # chaos: admission failures are attributable to ONE request
            # (self._admitting is set), exercising the requeue/
            # quarantine blame path in _on_step_failure
            self.faults.raise_point("admit", self._step_idx)

        if a.req.request_id in self._abort:      # aborted mid-admission
            self._abort.discard(a.req.request_id)
            self._finish_admission_abort(a)
            return
        if a.last is not None:
            # the last chunk went out a step ago, behind a decode step
            # that has been read since: this step waits for the chunk
            self.phases.mark_chunk()
            self._finish_admission(a)
            return
        if hold:
            return

        plen = self._prefill_len(a.req)
        if plen == 0:
            # a block family's prompt shorter than a block: all of it
            # opens the first block, and there is nothing to prefill
            a.last = (None, None)
            self._finish_admission(a)
            return
        chunk = a.chunk
        padded = np.zeros((1, chunk), np.int32)
        part = a.req.prompt_token_ids[a.consumed:a.consumed + chunk]
        padded[0, :len(part)] = part
        self.faults.raise_point("prefill", self._step_idx)
        with self.phases.phase("admission.h2d", child=True):
            padded_dev = jnp.asarray(padded)
        hidden = None
        if self._mtp:
            if a.carry is None:
                a.carry = jnp.zeros((1, int(self.cfg.hidden_size)),
                                    jnp.bfloat16)
            # the hidden row that comes back: the last prompt position's
            # where this chunk holds it
            logits, hidden, a.carry, a.cache1 = self._prefill_mtp(
                self.params, padded_dev, a.cache1, a.carry,
                jnp.asarray(min(max(plen - 1 - a.consumed, 0), chunk - 1),
                            jnp.int32))
        else:
            logits, a.cache1 = self._prefill(self.params, padded_dev,
                                             a.cache1)
        # a put's buffer is let go where the call's own temporary was:
        # while the program runs, not at the frame's exit after the wait
        del padded_dev
        self._chunk_unanswered = True
        self._m_prefill_chunks.inc()
        self._m_prefill_tokens.labels("prompt").inc(len(part))
        self._m_prefill_tokens.labels("padding").inc(chunk - len(part))
        start = a.consumed
        a.consumed += chunk

        if a.consumed >= plen:
            # a block family's prefill yields no token: nothing of the
            # chunk is read or waited for, and the slot joins at once
            a.last = ((None, None) if self._block is not None
                      else (logits[:, plen - 1 - start], hidden))
            if self._ahead is not None and self._block is None:
                # a decode step is on the device whose tokens are not
                # read yet, and this chunk is queued behind it: the wait
                # for the first token would hold them back by a chunk, so
                # the admission ends at the next step's start, and that
                # step is the one this chunk makes a chunk step
                return
            self._finish_admission(a)
        self.phases.mark_chunk()

    def _finish_admission(self, a: _Admission) -> None:
        """The end of an admission whose last chunk is out (`a.last`):
        its rows into the batched cache at the slot, the first token
        sampled, waited for and emitted, the slot live."""
        logits, hidden = a.last
        plen = self._prefill_len(a.req)
        if self._block is not None:
            # the prompt's whole blocks into the slab; its tail opens the
            # first block as given rows beside MASK, and the first pass
            # is the next step's
            self.cache = self._insert(self.cache, a.cache1, a.slot_idx,
                                      plen)
            s = self.slots[a.slot_idx]
            s.req = a.req
            self._setup_slot_sampler(s)
            s.generated = []
            s.active = True
            b = self._block.length
            tail = [int(t) for t in a.req.prompt_token_ids[plen:]]
            s.block = _BlockState(plen, tail + [-1] * (b - len(tail)),
                                  [0] * b, len(tail),
                                  passes=a.req.block_passes)
            self._obs_admission_complete(a.req.request_id,
                                         first_token=False)
            self._admitting = None
            return
        if self._paged:
            self.cache = self._paged_insert(a, plen)
        else:
            self._remember_prefix(a.req.prompt_token_ids, a.cache1)
            self.cache = self._insert(self.cache, a.cache1,
                                      a.slot_idx, plen)
        s = self.slots[a.slot_idx]
        s.req = a.req
        self._setup_slot_sampler(s)
        first, lp = self._sample_admission(logits, s)
        s.generated = [int(first)]
        s.last_token = int(first)
        s.active = True
        self._obs_admission_complete(a.req.request_id)
        self._emit(s, lp)
        s.drafted = False
        if not self._check_done(a.slot_idx) and self._mtp:
            # the MTP row of the last prompt position waited for
            # this token: with it the slot's first draft stands
            self._mtp_rows_after(
                [a.slot_idx], jnp.broadcast_to(
                    hidden,
                    (self.cfg_engine.max_batch, hidden.shape[-1])))
        self._admitting = None

    # -- paged KV bookkeeping (kv_page_size > 0) ----------------------------

    def _bt(self):
        """Device mirror of the host block tables, refreshed only when
        a row changed — steady-state decode reuses the resident array
        (no per-token H2D of page indices)."""
        if self._bt_dirty:
            with self.phases.phase("cache.block_table", child=True):
                self._bt_dev = jnp.asarray(self._bt_np)
            self._bt_dirty = False
        return self._bt_dev

    def _paged_admit(self, req: Request, chunk: int):
        """Page-side half of admission start: radix longest-prefix
        match, then an all-or-nothing grab of every page the sequence
        can EVER need (prompt + max_tokens, capped at max_seq) — the
        decode path never allocates, so a running sequence cannot
        deadlock against an admission for pages. Returns ``(consumed,
        shared_pages, new_pages)`` or None after requeueing the request
        (pool exhausted even after evicting idle radix leaves)."""
        ce = self.cfg_engine
        prompt = req.prompt_token_ids
        plen = len(prompt)
        ps = self._page_size
        consumed = 0
        shared: List[int] = []
        owned = False
        mig = (self._migration_pages.pop(req.resume_id, None)
               if req.resume_id is not None else None)
        if mig is not None:
            # migrated-in sequence: the imported pages arrive at
            # refcount 1 (owned by the staging stash) and that
            # reference BECOMES the slot's — no incref below. Only the
            # aligned prefix is consumable (the same chunk/page
            # alignment as a radix hit); tail pages holding the
            # re-prefilled remainder give their reference back.
            req.resume_id = None         # claim is one-shot
            pages_m, kv_imported, _ = mig
            align = max(chunk, ps)
            consumed = min(kv_imported, plen - 1)
            consumed -= consumed % align
            keep = consumed // ps
            shared = pages_m[:keep]
            for p in pages_m[keep:]:
                self.pool.decref(p)
            owned = True
            self._mig_inc("claimed")
            self.flight.record(
                "migration_claim", step=self._step_idx,
                request_id=req.request_id, consumed=consumed,
                n_pages=keep)
        elif self.radix is not None:
            with self.phases.phase("cache.radix_match", child=True):
                matched, pages = self.radix.match(prompt)
            # the seeded length must stay aligned to both the prefill
            # chunk and the page size (powers of two: lcm == max), and
            # the final prompt token must run to produce logits
            align = max(chunk, ps)
            consumed = min(matched, plen - 1)
            consumed -= consumed % align
            shared = pages[:consumed // ps]
        want = min(plen + req.params.max_tokens, ce.max_seq)
        n_new = -(-want // ps) - len(shared)
        with self.phases.phase("cache.page_alloc", child=True):
            new = self.pool.alloc(n_new)
            if new is None and self.radix is not None:
                # reclaim idle radix leaves (LRU-first; a page a live
                # slot maps is never an eviction candidate), retry once
                self.radix.evict(n_new - self.pool.num_free)
                new = self.pool.alloc(n_new)
        if new is None:
            if owned:
                # give the claimed pages back; the deferred re-admission
                # re-prefills from tokens (the claim was one-shot)
                for p in shared:
                    self.pool.decref(p)
            self.waiting.appendleft(req)
            self._deferred_admissions += 1
            self._m_deferred.labels("pages").inc()
            if not self._deferred_streak:
                self._deferred_streak = True
                self.flight.record(
                    "admit_deferred", step=self._step_idx,
                    request_id=req.request_id, reason="pages",
                    needed_pages=n_new, free_pages=self.pool.num_free)
            return None
        if not owned:
            for p in shared:
                self.pool.incref(p)      # the slot's own reference
        return consumed, shared, new

    def _paged_insert(self, a: _Admission, plen: int):
        """Completion half of a paged admission: write the slot's
        block-table row (shared prefix pages first, then the private
        pages), scatter the private cache1 rows into their pages, and
        publish the prompt's pages — including the partial tail page,
        the future copy-on-write target — to the radix tree."""
        idx = a.slot_idx
        ps = self._page_size
        shared = a.shared_pages or []
        row = list(shared) + list(a.new_pages or [])
        self._bt_np[idx, :] = 0
        self._bt_np[idx, :len(row)] = row
        self._bt_dirty = True
        # the page each logical page of cache1 is written to: pages
        # already resident in the shared prefix must NOT be rewritten (a
        # concurrent reader of those pages stays byte-identical), and
        # chunk padding past the allocated pages has nowhere to live —
        # both go to the null page
        cap = min(a.cache1.max_seq, self.cfg_engine.max_seq)
        write_row = np.zeros((self._pages_per_seq,), np.int32)
        write_row[:len(row)] = row
        write_row[:len(shared)] = NULL_PAGE
        cache = self._insert_paged(
            self.cache, a.cache1, jnp.asarray(write_row[:-(-cap // ps)]),
            jnp.asarray(idx, jnp.int32), jnp.asarray(plen, jnp.int32))
        if self.radix is not None:
            n_prompt_pages = -(-plen // ps)
            with self.phases.phase("cache.radix_insert", child=True):
                self.radix.insert(
                    a.req.prompt_token_ids,
                    [int(p) for p in self._bt_np[idx, :n_prompt_pages]])
        return cache

    def _cow_step(self, active: List[int]) -> None:
        """Copy-on-write barrier before a paged decode: any active slot
        whose write page (the page holding the position this step
        appends to) is shared gets a private copy first. All copies
        ride ONE fixed-shape jit call — pairs padded to max_batch with
        null->null self-copies — so a CoW step costs one extra
        dispatch, never one per slot."""
        if self.pool.num_shared == 0:
            return
        ps = self._page_size
        pairs: List[Tuple[int, int, int, int]] = []
        for i in active:
            s = self.slots[i]
            wpos = len(s.req.prompt_token_ids) + len(s.generated) - 1
            lp = wpos // ps
            if lp >= self._pages_per_seq:
                continue          # at capacity; the append masks out
            page = int(self._bt_np[i, lp])
            if page == NULL_PAGE or self.pool.refcount(page) <= 1:
                continue
            fresh = self.pool.alloc(1)
            if fresh is None and self.radix is not None:
                self.radix.evict(1)
                fresh = self.pool.alloc(1)
            if fresh is None:
                # pool dry: surrender the prompt's radix path instead.
                # A shared WRITE page is always the prompt's partial
                # tail — referenced by exactly this slot and its radix
                # node (match never returns partial pages) — so the
                # drop makes it private and the append proceeds in
                # place without a copy.
                if self.radix is not None:
                    self.radix.drop(s.req.prompt_token_ids)
                continue
            pairs.append((i, lp, page, fresh[0]))
        if not pairs:
            return
        srcs = np.zeros((self.cfg_engine.max_batch,), np.int32)
        dsts = np.zeros((self.cfg_engine.max_batch,), np.int32)
        for j, (_, _, src, dst) in enumerate(pairs):
            srcs[j] = src
            dsts[j] = dst
        self.cache = self._cow_pages(self.cache, jnp.asarray(srcs),
                                     jnp.asarray(dsts))
        for i, lp, src, dst in pairs:
            self._bt_np[i, lp] = dst
            self.pool.decref(src)
        self._bt_dirty = True
        self.flight.record("cow_pages", step=self._step_idx,
                           n_pages=len(pairs))

    def _release_slot_pages(self, idx: int) -> None:
        """Drop the slot's block-table references (finish, preempt,
        quarantine). Pages the radix tree still references stay
        resident for future prefix hits; the rest free immediately."""
        if not self._paged:
            return
        row = self._bt_np[idx]
        for p in row[row != NULL_PAGE]:
            self.pool.decref(int(p))
        row[:] = 0
        self._bt_dirty = True

    def _release_admission_pages(self,
                                 a: Optional[_Admission]) -> None:
        """Failed/aborted/expired mid-admission: give back the pages
        reserved at admission start (the block-table row was never
        written, so the slot path cannot double-release them)."""
        if not self._paged or a is None:
            return
        for p in (a.shared_pages or []) + (a.new_pages or []):
            self.pool.decref(p)
        a.shared_pages = None
        a.new_pages = None

    def _paged_snapshot(self) -> dict:
        """JSON-ready paged-KV state for /v1/stats and /v1/memory."""
        d = {
            "page_size": self._page_size,
            "num_pages": self._num_pages,
            "pages_used": self.pool.num_used,
            "pages_shared": self.pool.num_shared,
            "pages_free": self.pool.num_free,
            "pool_exhausted_total": self.pool.exhausted_total,
            "kv_bytes_per_page": self._kv_bytes_per_page,
        }
        if self.radix is not None:
            d["radix"] = self.radix.snapshot()
        return d

    # -- KV handoff (disaggregated prefill/decode, serving/api_server) ------

    def export_prefix_snapshot(self, prompt: List[int]):
        """Host-materialized KV planes for this exact prompt's prefix
        snapshot, or None when nothing is cached for it. Planes are
        ``(k, v)`` or ``(k, v, k_scale, v_scale)`` numpy arrays shaped
        ``[L, 1, keep, H, D]`` (scales ``[L, 1, keep, H]``) — the
        prefix-cache entry format, which is also the handoff wire
        format. Safe from HTTP handler threads: one dict get plus
        materialization of the entry's own planes; no engine-owned
        structure is mutated (the materialized copy is NOT written
        back — the engine loop re-materializes on its next touch)."""
        entry = self._prefix_cache.get(tuple(prompt))
        if entry is None:
            return None
        return self._materialize(entry)

    def stage_handoff(self, prompt: List[int], planes) -> None:
        """Queue a remote prefill's KV snapshot for injection into the
        prefix cache. Called from HTTP handler threads BEFORE the
        corresponding add_request; the engine loop drains the inbox at
        the top of _admission_step, so the planes are visible to
        _seed_from_prefix_cache before the request that shipped them
        can be selected for admission. Only the thread-safe deque
        append happens here."""
        self._handoff_in.append((tuple(prompt), tuple(planes)))

    def _drain_handoffs(self) -> None:
        """Engine-loop half of stage_handoff: move staged snapshots
        into the prefix cache (+ hash index). Staged entries are
        bounded separately from prefix_cache_entries — a decode-role
        replica typically runs with the local prefix cache disabled,
        and remote snapshots must not accumulate without bound."""
        if not self._handoff_in:
            return
        ce = self.cfg_engine
        cap = (ce.handoff_cache_entries if ce.handoff_cache_entries >= 0
               else 2 * ce.max_batch)
        if self._paged or cap == 0:
            # paged engines share KV through device pages (host-DRAM
            # snapshots have no splice path into the arena); cap 0
            # disables handoff retention outright — either way the
            # staged planes must not accumulate
            self._handoff_in.clear()
            return
        while True:
            try:
                key, entry = self._handoff_in.popleft()
            except IndexError:
                break
            if key in self._prefix_cache:
                self._prefix_cache.pop(key)      # refresh LRU position
            else:
                self._prefix_index_add(key)
            self._prefix_cache[key] = entry
            self._handoff_keys.append(key)
            seed_shape = tuple(entry[0].shape)
            self.flight.record("handoff_staged", step=self._step_idx,
                               prompt_len=len(key),
                               seed_tokens=seed_shape[self._cache_spec.seq_axis])
        # bound retention by the EXPLICIT handoff knob, never by
        # prefix_cache_entries: prefix_cache_entries == 0 means the
        # operator turned local prefix caching OFF, and the old
        # max(entries, 2B) floor silently re-enabled it here
        while len(self._handoff_keys) > cap:
            old = self._handoff_keys.popleft()
            self._drop_prefix(list(old))

    # -- live sequence migration (zero-loss drains/restarts/scale-downs) ----
    #
    # Source side (this replica is being drained/retired): an HTTP
    # sender thread calls request_migration(rid); the engine loop
    # suspends the slot mid-decode and exports its complete resumable
    # state (KV planes, tokens, sampler stream, cum-logprob, QoS/
    # deadline/trace) into take_export(rid). After the target's
    # /v1/internal/migrate_in returns 200 the sender calls
    # finish_migrated() (the request finishes here with reason
    # "migrated"); after the retry ladder fails it calls resume_local()
    # and the sequence re-admits HERE, re-seeded from the exported
    # planes, so a dead target costs a requeue — never the tokens.
    #
    # Target side: stage_migration(state) parks the state under its
    # resume_id; the engine loop imports the KV (paged: fresh pages +
    # arena scatter, slab: prefix-cache staging) and the resumed
    # request's admission claims it — the bounded tail re-prefill is
    # the same byte-identical invariant preempt-resume relies on.
    # Unclaimed state expires after _migration_ttl (a lost commit-ack
    # means the source resumed locally; the stale copy must die
    # unclaimed or the sequence would run twice).

    def request_migration(self, request_id: str) -> None:
        """Ask the engine loop to suspend + export one mid-decode
        request. Thread-safe; poll take_export for the state."""
        self._migrate_req.add(request_id)

    def take_export(self, request_id: str) -> Optional[dict]:
        """The exported state (planes are host numpy — the API layer
        wire-encodes them), ``{"unexportable": True}`` when the request
        was not mid-decode, or None while the export is pending."""
        with self._lock:
            return self._migration_out.pop(request_id, None)

    def export_sequence(self, request_id: str,
                        timeout_sec: float = 5.0) -> Optional[dict]:
        """Blocking convenience over request_migration/take_export for
        senders that can wait: returns the resumable state, or None
        when the request is not mid-decode here (or the engine loop
        never got to it) — the caller leaves the request alone then.
        On timeout the export is cancelled (resume_local), so a late
        export can never leave the sequence suspended forever."""
        self.request_migration(request_id)
        deadline = time.monotonic() + timeout_sec
        while time.monotonic() < deadline:
            st = self.take_export(request_id)
            if st is not None:
                return None if st.get("unexportable") else st
            time.sleep(0.002)
        self.resume_local(request_id)
        return None

    def finish_migrated(self, request_id: str, target: str,
                        resume_id: str) -> None:
        """Commit ack from the sender thread: the target replica owns
        the sequence now. The engine loop delivers the "migrated"
        finish (so the HTTP handler can emit the resume marker) —
        nothing is re-emitted, nothing is recomputed."""
        self._migration_done.append((request_id, target, resume_id))

    def resume_local(self, request_id: str) -> None:
        """Every transfer attempt failed (or the export timed out):
        cancel the export and re-admit the sequence locally, re-seeded
        from its own exported planes. Safe to call at any point of the
        export lifecycle, from any thread, more than once."""
        self._migrate_req.discard(request_id)
        self._migration_fail.append(request_id)

    def stage_migration(self, state: dict) -> str:
        """Target-side intake (HTTP handler threads): park a migrated
        sequence's state for the resumed request to claim, and queue
        its KV planes for the engine loop to import. Returns the
        resume_id the source's client must present (X-Resume-Id)."""
        resume_id = state.get("resume_id")
        if not resume_id:
            raise ValueError("migration state carries no resume_id")
        with self._lock:
            self._migration_staged[str(resume_id)] = (state,
                                                      time.monotonic())
        self._migration_in.append(state)
        return str(resume_id)

    # ISSUE-facing aliases: the tentpole API names
    import_sequence = stage_migration

    def claim_migration(self, resume_id: str) -> Optional[dict]:
        """One-shot claim of staged state by the resumed request's
        HTTP handler; None when nothing is staged under resume_id (the
        request then proceeds as a fresh replay — full recompute, but
        correct)."""
        with self._lock:
            ent = self._migration_staged.pop(str(resume_id), None)
        return None if ent is None else ent[0]

    def resume_migrated_request(self, request_id: str, state: dict,
                                trace=None) -> None:
        """Admit a claimed migrated sequence as a resumable request:
        prompt = source prompt + generated-so-far, generation resumes
        at the source's offset with the source's sampler stream,
        cum-logprob, QoS/tenant, and deadline. Raises like add_request
        (EngineDraining / RequestShed / ValueError)."""
        fields = {f.name for f in dataclasses.fields(SamplingParams)}
        params = SamplingParams(**{
            k: v for k, v in (state.get("params") or {}).items()
            if k in fields})
        params = dataclasses.replace(
            params,
            stop_token_ids=tuple(params.stop_token_ids or ()))
        gen = list(state.get("generated") or [])
        full = list(state.get("prompt_token_ids") or []) + gen
        self.add_request(
            request_id, full, params, trace=trace,
            resume={
                "generated_offset":
                    int(state.get("generated_offset") or 0) + len(gen),
                "cum_logprob": float(state.get("cum_logprob") or 0.0),
                "dev_seed": state.get("dev_seed"),
                "resume_id": state.get("resume_id"),
                "deadline": state.get("deadline"),
            })

    def active_request_ids(self,
                           qos: Optional[str] = None) -> List[str]:
        """Request ids currently resident in decode slots — the
        migratable set, optionally filtered to one QoS class (the
        brownout ladder migrates only batch-QoS sequences off an
        overloaded replica). A snapshot; safe from any thread."""
        out = []
        for s in self.slots:
            r = s.req
            if s.active and r is not None and (
                    qos is None or (r.params.qos or None) == qos):
                out.append(r.request_id)
        return out

    def migration_snapshot(self) -> dict:
        """The /v1/stats "migration" block: flat counters the router's
        stats poll turns into per-replica deltas, plus live staging
        depth."""
        with self._lock:
            staged = len(self._migration_staged)
        d = dict(self._mig)
        d["staged"] = staged
        d["pending_out"] = len(self._migration_meta)
        d["wants_migration"] = bool(
            getattr(self.overload, "wants_migration", False))
        if self._paged:
            d["pool"] = {
                "exported_pages_total": self.pool.exported_pages_total,
                "imported_pages_total": self.pool.imported_pages_total,
                "import_exhausted_total":
                    self.pool.import_exhausted_total,
            }
        return d

    def _mig_inc(self, outcome: str) -> None:
        self._mig[outcome] += 1
        self._m_migrations.labels(outcome).inc()

    def _export_slot(self, idx: int) -> None:
        """Engine-loop half of request_migration: gather the slot's KV
        off the device, capture every resumable field, then tear the
        slot down preempt-style (pages released, pos reset) WITHOUT
        requeueing — the sequence is in limbo until the sender commits
        (finish_migrated) or gives up (resume_local)."""
        s = self.slots[idx]
        req = s.req
        rid = req.request_id
        t0 = time.perf_counter()
        plen = len(req.prompt_token_ids)
        gen = list(s.generated)
        # the last sampled token has not been fed back yet — the cache
        # holds plen + len(gen) - 1 positions (the same invariant
        # preempt-resume's bounded tail re-prefill relies on)
        kv_len = plen + len(gen) - 1
        resume_id = f"{rid}-m{self._step_idx}"
        state = {
            "version": 1,
            "resume_id": resume_id,
            "request_id": rid,
            "prompt_token_ids": [int(t) for t in req.prompt_token_ids],
            "generated": [int(t) for t in gen],
            "generated_offset": int(req.generated_offset),
            "kv_len": int(kv_len),
            "dev_seed": int(s.dev_seed),
            "cum_logprob": float(s.cum_logprob),
            "deadline": req.deadline,
            "params": dataclasses.asdict(req.params),
            "trace": list(req.trace) if req.trace is not None else None,
            "kv_cache_dtype": self.kv_cache_dtype,
            "paged": self._paged,
        }
        try:
            if self._paged:
                ps = self._page_size
                n_pages = -(-kv_len // ps)
                pages = [int(p) for p in self._bt_np[idx, :n_pages]]
                state["page_manifest"] = self.pool.export_pages(pages)
                dev = gather_pages_dense(
                    self.cache, jnp.asarray(np.asarray(pages, np.int32)))
                # audited: a rare-path migration pulls this sequence's
                # 2-4 planes once — not a per-token sync
                planes = tuple(
                    np.ascontiguousarray(np.asarray(p)[:, :, :kv_len])  # graftlint: disable=step-host-sync
                    for p in dev)
            else:
                planes = tuple(
                    np.ascontiguousarray(  # graftlint: disable=step-host-sync
                        np.asarray(p))  # graftlint: disable=step-host-sync
                    for p in self.cache.seq_slices(kv_len, row=idx))
        except Exception as e:
            # export must never kill the step loop: leave the sequence
            # running (the sender times out; the request finishes here)
            self.flight.record("migration_export_failed",
                               step=self._step_idx, request_id=rid,
                               **exception_fields(e))
            self._mig_inc("failed")
            with self._lock:
                self._migration_out[rid] = {"unexportable": True}
            return
        state["planes"] = planes
        resumed = dataclasses.replace(
            req,
            prompt_token_ids=list(req.prompt_token_ids) + gen,
            generated_offset=req.generated_offset + len(gen),
            resumed_cum_logprob=s.cum_logprob,
            resume_dev_seed=int(s.dev_seed))
        s.req = None
        s.active = False
        s.generated = []
        s.counts = None
        s.counts_out = None
        self._release_slot_pages(idx)
        self.cache = dataclasses.replace(
            self.cache, pos=self.cache.pos.at[idx].set(0))
        self._migration_meta[rid] = {
            "resumed": resumed, "planes": planes, "kv_len": kv_len,
            "t0": t0, "n_generated": resumed.generated_offset}
        with self._lock:
            self._migration_out[rid] = state
        self._mig_inc("exported")
        self.flight.record(
            "migration_export", step=self._step_idx, request_id=rid,
            resume_id=resume_id, slot=idx, kv_len=kv_len,
            n_generated=resumed.generated_offset)

    def _migration_step(self) -> bool:
        """Engine-loop migration work: sweep export requests, deliver
        commit finishes, re-admit failed sends. Returns True when any
        migration work happened (counts as a working step)."""
        did = False
        if self._migrate_req:
            for rid in list(self._migrate_req):
                self._migrate_req.discard(rid)
                idx = next(
                    (i for i, s in enumerate(self.slots)
                     if s.active and s.req is not None
                     and s.req.request_id == rid), None)
                if idx is None or self._block is not None:
                    # not mid-decode here (queued, admitting, CP lane,
                    # already finished, unknown), or a block family's
                    # (refused: the planes hold a block in flight, which
                    # no resume takes up): nothing to move —
                    # tell the sender so it leaves the request alone
                    self._mig_inc("unexportable")
                    with self._lock:
                        self._migration_out[rid] = {
                            "unexportable": True}
                    continue
                self._export_slot(idx)
                did = True
        while self._migration_done:
            try:
                rid, target, resume_id = self._migration_done.popleft()
            except IndexError:
                break
            meta = self._migration_meta.pop(rid, None)
            with self._lock:
                self._migration_out.pop(rid, None)
            self._abort.discard(rid)
            if meta is None:
                continue             # raced with resume_local: resolved
            self._mig_inc("committed")
            self._mig["migrated_tokens_total"] += meta["n_generated"]
            self._m_migrated_tokens.inc(meta["n_generated"])
            self._m_migration_ms.observe(
                (time.perf_counter() - meta["t0"]) * 1000.0)
            self._push_output(
                rid, RequestOutput(rid, [], True, "migrated"),
                score=meta["resumed"].resumed_cum_logprob,
                length=meta["n_generated"])
            self._obs_finish(rid, "migrated",
                             n_generated=meta["n_generated"])
            self.flight.record(
                "migration_commit", step=self._step_idx,
                request_id=rid, target=target, resume_id=resume_id,
                n_generated=meta["n_generated"])
            did = True
        while self._migration_fail:
            try:
                rid = self._migration_fail.popleft()
            except IndexError:
                break
            meta = self._migration_meta.pop(rid, None)
            with self._lock:
                self._migration_out.pop(rid, None)
            if meta is None:
                continue             # never exported / already resolved
            self._mig_inc("failed")
            resumed = meta["resumed"]
            if rid in self._abort:
                # client hung up while the transfer was failing
                self._abort.discard(rid)
                self._push_output(rid, RequestOutput(rid, [], True,
                                                     "abort"))
                self._obs_finish(rid, "abort",
                                 n_generated=meta["n_generated"])
                did = True
                continue
            if not self._reseed_local(resumed, meta):
                # no staged copy: the resume's prefill recomputes the
                # generated-so-far tail from tokens
                self._mig["recomputed_tokens_total"] += \
                    meta["n_generated"]
            self.waiting.append(resumed)
            self._mig_inc("local_resume")
            self.tracer.preempted(rid)
            self.flight.record(
                "migration_local_resume", step=self._step_idx,
                request_id=rid, n_generated=meta["n_generated"])
            did = True
        return did

    def _reseed_local(self, resumed: Request, meta: dict) -> bool:
        """Failed migration: put the exported KV back (paged:
        self-import into fresh pages; slab: prefix-cache staging) so
        the local resume is a cache splice, not a recompute. False when
        nothing could be staged."""
        planes = meta.get("planes")
        kv_len = int(meta.get("kv_len") or 0)
        if planes is None or kv_len <= 0:
            return False
        if self._paged:
            resume_id = (f"{resumed.request_id}"
                         f"-local{self._step_idx}")
            if not self._import_planes(resume_id, planes, kv_len):
                return False
            resumed.resume_id = resume_id
            return True
        key = tuple(resumed.prompt_token_ids[:kv_len])
        self._handoff_in.append((key, tuple(planes)))
        return True

    def _import_planes(self, resume_id: str, planes,
                       kv_len: int) -> bool:
        """Scatter host KV planes into freshly imported arena pages and
        stash them under resume_id for _paged_admit to claim. Engine
        thread only. False when the pool cannot hold the sequence —
        the resume then re-prefills from tokens (correct, just slower)."""
        ps = self._page_size
        n = -(-kv_len // ps)
        pages = self.pool.import_pages(n)
        if pages is None and self.radix is not None:
            self.radix.evict(n - self.pool.num_free)
            pages = self.pool.import_pages(n)
        if pages is None:
            self.flight.record(
                "migration_import_exhausted", step=self._step_idx,
                resume_id=resume_id, needed_pages=n,
                free_pages=self.pool.num_free)
            return False
        rows = []
        for plane in planes:
            # audited: plane arrived as host bytes off the wire — this
            # asarray is dtype/view normalization, not a device pull
            plane = np.asarray(plane)  # graftlint: disable=step-host-sync
            rows.append(jnp.asarray(plane[:, :, :kv_len]))
        self.cache = splice_pages(
            self.cache, rows, jnp.asarray(np.asarray(pages, np.int32)))
        self._migration_pages[resume_id] = (pages, kv_len,
                                            time.monotonic())
        return True

    def _drain_migrations(self) -> None:
        """Engine-loop half of stage_migration: import staged KV, and
        expire unclaimed staging (state AND pages) past the TTL."""
        now = time.monotonic()
        with self._lock:
            dead = [r for r, (_, ts) in
                    self._migration_staged.items()
                    if now - ts > self._migration_ttl]
            for r in dead:
                self._migration_staged.pop(r, None)
        for r in dead:
            self.flight.record("migration_stage_expired",
                               step=self._step_idx, resume_id=r)
        if self._migration_pages:
            for r in [r for r, (_, _, ts) in
                      self._migration_pages.items()
                      if now - ts > self._migration_ttl]:
                pages, _, _ = self._migration_pages.pop(r)
                for p in pages:
                    self.pool.decref(p)
        while self._migration_in:
            try:
                state = self._migration_in.popleft()
            except IndexError:
                break
            planes = state.pop("planes", None)
            resume_id = state.get("resume_id")
            kv_len = int(state.get("kv_len") or 0)
            if planes is None or kv_len <= 0:
                continue
            if state.get("kv_cache_dtype") not in (None,
                                                   self.kv_cache_dtype):
                # mixed-dtype fleet: the quantized codes don't splice —
                # the resume re-prefills from tokens instead
                self.flight.record(
                    "migration_dtype_skew", step=self._step_idx,
                    resume_id=resume_id,
                    theirs=state.get("kv_cache_dtype"),
                    ours=self.kv_cache_dtype)
                continue
            ok = False
            if self._paged:
                ok = self._import_planes(str(resume_id), planes, kv_len)
            else:
                full = (list(state.get("prompt_token_ids") or [])
                        + list(state.get("generated") or []))
                if len(full) > kv_len:
                    self._handoff_in.append(
                        (tuple(full[:kv_len]), tuple(planes)))
                    ok = True
            if ok:
                self._mig_inc("imported")
                self.flight.record(
                    "migration_import", step=self._step_idx,
                    resume_id=resume_id, kv_len=kv_len,
                    request_id=state.get("request_id"))

    @staticmethod
    def _materialize(entry):
        """Pending device slices -> host numpy (cheap if the async copy
        already landed). device_get can hand back non-contiguous views on
        some backends; force contiguity before keeping them around.
        Entries are (k, v) or, for scaled dtypes, (k, v, k_scale,
        v_scale)."""
        if not isinstance(entry[0], np.ndarray):
            # audited: the "loop" is over the 2-4 planes of ONE entry
            # whose async copy already landed — one pull per plane is
            # the sanctioned pattern, not a per-token sync
            entry = tuple(np.ascontiguousarray(np.asarray(x))  # graftlint: disable=step-host-sync
                          for x in entry)
        return entry

    def _seed_from_prefix_cache(self, prompt: List[int], chunk: int):
        """(consumed, entry) for the longest usable cached prefix —
        rounded DOWN to a chunk multiple (continuation chunks must stay
        chunk-aligned) and capped at plen-1 (the final token must run to
        produce sampling logits). (0, None) on miss.

        Lookup goes through the bucketed prefix-hash index: only chunk
        multiples are usable, so probe the candidate lengths directly
        (longest first), O(max_seq/chunk) hashes independent of how many
        entries the cache holds. A hash hit is verified against the
        stored key before use — a collision degrades to a miss at that
        length, never to a wrong seed."""
        if not self._prefix_cache:
            return 0, None
        best = 0
        best_key = None
        if self._prefix_g and chunk % self._prefix_g == 0:
            pt = tuple(prompt)
            top = chunk * ((len(prompt) - 1) // chunk)
            for length in range(top, 0, -chunk):
                key = self._prefix_index.get(length, {}).get(
                    hash(pt[:length]))
                if key is not None and key[:length] == pt[:length]:
                    best, best_key = length, key
                    break
        else:
            # non-divisible bucket/chunk configuration: linear scan
            for stored in self._prefix_cache:
                n = 0
                for a, b in zip(stored, prompt):
                    if a != b:
                        break
                    n += 1
                if n > best:
                    best, best_key = n, stored
            best = min(best, len(prompt) - 1)
            best -= best % chunk
        if best <= 0:
            return 0, None
        entry = self._materialize(self._prefix_cache[best_key])
        self._prefix_cache[best_key] = entry
        # snapshots are truncated to prefix_cache_max_tokens; never seed
        # past what was actually stored
        best = min(best, entry[0].shape[self._cache_spec.seq_axis])
        best -= best % chunk
        if best <= 0:
            return 0, None
        return best, entry

    def _prefix_index_add(self, key: Tuple[int, ...]) -> None:
        g = self._prefix_g
        if not g:
            return
        for length in range(g, len(key) + 1, g):
            self._prefix_index.setdefault(length, {})[
                hash(key[:length])] = key

    def _prefix_index_drop(self, key: Tuple[int, ...]) -> None:
        g = self._prefix_g
        if not g:
            return
        for length in range(g, len(key) + 1, g):
            d = self._prefix_index.get(length)
            if d is not None and d.get(hash(key[:length])) == key:
                del d[hash(key[:length])]
                if not d:
                    del self._prefix_index[length]

    def _remember_prefix(self, prompt: List[int], cache1: KVCache) -> None:
        """Snapshot the prompt's (truncated) KV for later prefix reuse.

        The snapshot is taken as device slices with an ASYNC host copy
        started immediately — step() is not stalled by a blocking D2H of
        the whole prompt KV; materialization happens on the next cache
        touch, by when the copy has usually landed."""
        ce = self.cfg_engine
        if ce.prefix_cache_entries <= 0:
            return
        key = tuple(prompt)
        entry = self._prefix_cache.pop(key, None)
        if entry is None:
            keep = min(len(prompt), ce.prefix_cache_max_tokens)
            planes = cache1.seq_slices(keep)
            for p in planes:
                try:
                    p.copy_to_host_async()
                except Exception:
                    pass                  # backend without async copies
            entry = tuple(planes)
            self._prefix_index_add(key)
        self._prefix_cache[key] = entry          # (re-)insert most-recent
        while len(self._prefix_cache) > ce.prefix_cache_entries:
            old = next(iter(self._prefix_cache))
            self._prefix_cache.pop(old)
            self._prefix_index_drop(old)

    def reset_prefix_cache(self) -> None:
        self._prefix_cache.clear()
        self._prefix_index.clear()
        if self.radix is not None:
            self.radix.clear()

    def _drop_prefix(self, prompt: List[int]) -> None:
        """Evict one prompt's KV snapshot (cancellation/quarantine).
        In paged mode the snapshot IS the prompt's radix path — drop
        purges it bottom-up, stopping at nodes other prompts share."""
        if self.radix is not None:
            self.radix.drop(prompt)
        key = tuple(prompt)
        if self._prefix_cache.pop(key, None) is not None:
            self._prefix_index_drop(key)

    def _finish_admission_abort(self, a: _Admission) -> None:
        self._release_admission_pages(a)
        self._push_output(a.req.request_id, RequestOutput(
            a.req.request_id, [], True, "abort"))
        self._obs_finish(a.req.request_id, "abort")
        self._admitting = None

    def _setup_slot_sampler(self, s: _Slot) -> None:
        """Per-request sampler state at admission: penalty counts over the
        prompt, a seeded generator, and whether logprobs are tracked
        (explicitly requested, or needed to rank best_of candidates)."""
        p = s.req.params
        # unseeded: one persistent stream. Seeded: the stream is re-derived
        # PER TOKEN from (seed, absolute position) in _sample_host, so a
        # preempt-resume replays identically to an uninterrupted run.
        s.rng = np.random.default_rng() if p.seed is None else None
        # device-sampler stream: user seed folded to 31 bits, the
        # migrated-in stream carried over verbatim (an unseeded resume
        # must continue the SOURCE's stream or its continuation
        # diverges from the unmigrated run), or a fresh nonce per
        # admission (unseeded non-resumed requests promise no replay)
        if p.seed is not None:
            s.dev_seed = int(p.seed) & 0x7FFFFFFF
        elif s.req.resume_dev_seed is not None:
            s.dev_seed = int(s.req.resume_dev_seed) & 0x7FFFFFFF
        else:
            s.dev_seed = int(np.random.default_rng().integers(1 << 31))
        s.cum_logprob = s.req.resumed_cum_logprob
        # rank scores are only consumed when best_of oversamples (> n);
        # don't pay the per-token host log-softmax otherwise
        link = self._children.get(s.req.request_id)
        need_rank = False
        if link is not None:
            fo = self._fanouts.get(link[0])
            need_rank = fo is not None and fo.best_of > fo.n
        s.n_logprobs = (-1 if p.logprobs is None and not need_rank
                        else (p.logprobs or 0))
        if p.needs_counts:
            v = self.cfg.vocab_size
            s.counts = np.zeros((v,), np.int32)
            np.add.at(s.counts, np.asarray(s.req.prompt_token_ids,
                                           np.int64), 1)
            s.counts_out = np.zeros((v,), np.int32)
            if s.req.generated_offset:
                # preempt-resume: the prompt tail IS earlier output
                np.add.at(s.counts_out, np.asarray(
                    s.req.prompt_token_ids[-s.req.generated_offset:],
                    np.int64), 1)
        else:
            s.counts = None
            s.counts_out = None

    def _sample_admission(self, lg_dev, s: _Slot
                          ) -> Tuple[int, Optional[LogprobEntry]]:
        """First token after an (re)admission prefill (lg_dev: [1, V] on
        device). Simple slots draw from the SAME device stream as decode
        steps — without this, a seeded request's resume-recompute token
        came from the host stream and diverged from an uninterrupted
        run (caught by test_seeded_sampling_survives_preemption)."""
        p = s.req.params
        if s.counts is None and s.n_logprobs < 0:
            pos = s.req.generated_offset     # position 0 of this resume
            with self.phases.phase("admission.h2d", child=True):
                sampling = (jnp.asarray([p.temperature], jnp.float32),
                            jnp.asarray([p.top_k], jnp.int32),
                            jnp.asarray([p.top_p], jnp.float32),
                            jnp.asarray([s.dev_seed], jnp.int32),
                            jnp.asarray([pos], jnp.int32))
            tok_dev = self._sample_device(lg_dev, *sampling)
            del sampling
            # the one blocking fetch of admission: the host waits here
            # for every prefill chunk queued ahead of the sampler
            with self.phases.phase("admission.wait", child=True):
                tok = int(np.asarray(tok_dev)[0])
            return tok, None
        with self.phases.phase("admission.wait", child=True):
            lg = np.asarray(lg_dev)[0]
        return self._sample_host(lg, s)

    def _sample_host(self, logits: np.ndarray, s: _Slot
                     ) -> Tuple[int, Optional[LogprobEntry]]:
        """Sample one token for a slot: penalties -> (logprobs) ->
        temperature/top-k/top-p (the reference's BigDLSampler role plus the
        native sampler's repeat-penalty, ggml/model/llama/llama.py:566-620).
        """
        p = s.req.params
        # single D2H pull: np.asarray lands the row on the host in one
        # copy even if a caller hands us a device array, so every
        # float(ls[...]) below (cum_logprob, top-k logprobs) is pure
        # numpy indexing — not one device sync per token
        lg = np.asarray(logits, np.float64)
        if s.counts is not None:
            if p.repetition_penalty != 1.0:
                pen = np.where(lg > 0, lg / p.repetition_penalty,
                               lg * p.repetition_penalty)
                lg = np.where(s.counts > 0, pen, lg)
            if p.frequency_penalty != 0.0 or p.presence_penalty != 0.0:
                # output-token counts only (vllm count-penalty semantics)
                lg = (lg - s.counts_out * p.frequency_penalty
                      - (s.counts_out > 0) * p.presence_penalty)

        entry = None
        if s.n_logprobs >= 0:
            # distribution AFTER penalties, BEFORE temperature (the
            # model's adjusted distribution; also the best_of rank score)
            ls = lg - (np.max(lg) + np.log(
                np.sum(np.exp(lg - np.max(lg)))))
        if p.temperature <= 0.0:
            tok = int(np.argmax(lg))
        else:
            t = lg / p.temperature
            if p.top_k > 0:
                kth = np.sort(t)[-p.top_k]
                t = np.where(t < kth, -np.inf, t)
            if p.top_p < 1.0:
                order = np.argsort(t)[::-1]
                probs = np.exp(t[order] - np.max(t))
                probs /= probs.sum()
                cum = np.cumsum(probs)
                cut = int(np.searchsorted(cum, p.top_p)) + 1
                mask = np.full_like(t, -np.inf)
                mask[order[:cut]] = t[order[:cut]]
                t = mask
            probs = np.exp(t - np.max(t[np.isfinite(t)]))
            probs = np.where(np.isfinite(t), probs, 0.0)
            probs /= probs.sum()
            if s.rng is not None:
                rng = s.rng
            else:
                # stateless seeded draw keyed by absolute token position
                pos = s.req.generated_offset + len(s.generated)
                rng = np.random.default_rng((p.seed, pos))
            tok = int(rng.choice(len(probs), p=probs))

        if s.n_logprobs >= 0:
            s.cum_logprob += float(ls[tok])
            top: List[Tuple[int, float]] = []
            if s.n_logprobs > 0:
                idx = np.argpartition(ls, -s.n_logprobs)[-s.n_logprobs:]
                idx = idx[np.argsort(ls[idx])[::-1]]
                top = [(int(i), float(ls[i])) for i in idx]
            entry = LogprobEntry(tok, float(ls[tok]), top)
        if s.counts is not None:
            s.counts[tok] += 1
            s.counts_out[tok] += 1
        return tok, entry

    def _push_output(self, rid: str, out: RequestOutput,
                     score: Optional[float] = None,
                     length: int = 0) -> None:
        """Deliver an output, routing n/best_of children to their parent.

        Streaming children (best_of == n) pass through with their choice
        index; their per-choice finishes are demoted to finished=False (a
        choice ending is not the request ending) and ONE synthetic
        finished output closes the parent when the last child lands.
        Oversampled children (best_of > n) buffer until all candidates
        finish, then the n best by mean logprob are re-emitted as choices
        0..n-1."""
        out.t_push = time.perf_counter()
        link = self._children.get(rid)
        if link is None:
            with self._lock:
                self._outputs.setdefault(rid, []).append(out)
            return
        pid, idx = link
        fo = self._fanouts[pid]
        out = dataclasses.replace(out, request_id=pid, index=idx)
        stream = fo.best_of == fo.n
        if out.finished:
            fo.done += 1
            fo.scores[idx] = score if score is not None else -np.inf
            fo.lengths[idx] = length
            if stream:
                out = dataclasses.replace(out, finished=False)
        if stream:
            with self._lock:
                self._outputs.setdefault(pid, []).append(out)
        else:
            fo.buffered.setdefault(idx, []).append(out)
        if fo.done == fo.best_of:
            self._finish_fanout(fo)

    def _finish_fanout(self, fo: _Fanout) -> None:
        outs: List[RequestOutput] = []
        if fo.best_of > fo.n:
            mean = {i: fo.scores[i] / max(fo.lengths.get(i, 1), 1)
                    for i in fo.scores}
            ranked = sorted(mean, key=lambda i: mean[i], reverse=True)
            for new_idx, child_idx in enumerate(ranked[:fo.n]):
                for o in fo.buffered.get(child_idx, []):
                    # only the synthetic closer below finishes the parent
                    outs.append(dataclasses.replace(
                        o, index=new_idx, finished=False))
        # the closer carries NO finish_reason: choice-level reasons were
        # already delivered (demoted finishes), and a reason here would
        # clobber choice 0's real one in aggregating clients
        outs.append(RequestOutput(fo.parent_id, [], True, None))
        with self._lock:
            self._outputs.setdefault(fo.parent_id, []).extend(outs)
        for i in range(fo.best_of):
            self._children.pop(f"{fo.parent_id}#{i}", None)
            self._abort.discard(f"{fo.parent_id}#{i}")   # no leaks
        self._fanouts.pop(fo.parent_id, None)

    # -- observability hooks ------------------------------------------------

    def _obs_admission_complete(self, rid: str,
                                first_token: bool = True) -> None:
        """First token of an admission just sampled: close out the queue
        and prefill phases, record TTFT (first admission only — a
        preempt-resume already streamed its first token). A block
        family's prefill yields no token (`first_token` False): its
        first token is its first pass's (`_obs_first_token`)."""
        span = self.tracer.get(rid)
        now = time.time()
        just_first = span is not None and span.t_first_token is None
        if span is not None and span.t_admitted is not None:
            qw = span.queue_wait_s
            if qw is not None and qw >= 0:
                self._m_queue_wait.observe(qw)
            pf = max(now - span.t_admitted, 0.0)
            self._m_prefill.observe(pf)
            self._obs_prefill_perf(span.prompt_len, pf)
            if (span.trace_id is not None and just_first
                    and span.t_enqueued is not None):
                self.spans.record(
                    "queue_wait", span.trace_id,
                    parent_id=span.trace_span,
                    t_start=span.t_enqueued, t_end=span.t_admitted,
                    request_id=rid)
                self.spans.record(
                    "prefill", span.trace_id,
                    parent_id=span.trace_span,
                    t_start=span.t_admitted, t_end=now,
                    request_id=rid)
        if first_token:
            self._obs_first_token(rid)
        self._m_admissions.inc()
        self.flight.record("admit_complete", step=self._step_idx,
                           request_id=rid)

    def _obs_first_token(self, rid: str) -> None:
        """A request's first token: TTFT, once a request."""
        span = self.tracer.get(rid)
        just_first = span is not None and span.t_first_token is None
        self.tracer.first_token(rid)
        if self._mark_first_token:
            self._mark_first_token = False
            startup_mark("first_token", self.registry)
        if just_first and span.ttft_s is not None:
            self._m_ttft.observe(span.ttft_s)
            meta = self._usage_meta.get(rid)
            if meta is not None:
                self.slo.observe_ttft(meta[1], span.ttft_s)

    def _obs_finish(self, rid: str, reason: str,
                    n_generated: int = 0) -> None:
        span = self.tracer.finish(rid, reason, n_generated=n_generated)
        if span is not None:
            d = span.decode_s
            if d is not None and d >= 0:
                self._m_phase.labels("decode").observe(d)
            if span.trace_id is not None:
                if (span.t_first_token is not None
                        and span.t_finished is not None):
                    self.spans.record(
                        "decode", span.trace_id,
                        parent_id=span.trace_span,
                        t_start=span.t_first_token,
                        t_end=span.t_finished, request_id=rid)
                self.spans.record(
                    "engine.request", span.trace_id,
                    span_id=span.trace_span,
                    parent_id=span.trace_parent,
                    t_start=span.t_arrival,
                    t_end=span.t_finished or time.time(),
                    request_id=rid, finish_reason=reason,
                    n_generated=n_generated,
                    preemptions=span.n_preemptions)
        self._m_finished.labels(reason).inc()
        self._finish_times.append(time.time())   # drain-rate window
        meta = self._usage_meta.pop(rid, None)
        if meta is not None:
            tenant, qos = meta
            self.slo.observe_finish(qos, reason)
            self.usage.record_finish(
                rid, tenant, qos,
                prompt_tokens=span.prompt_len if span is not None else 0,
                generated_tokens=n_generated,
                finish_reason=reason,
                queue_wait_s=(span.queue_wait_s
                              if span is not None else None),
                ttft_s=span.ttft_s if span is not None else None,
                tpot_s=span.tpot_s if span is not None else None,
                preemptions=(span.n_preemptions
                             if span is not None else 0))
        self.flight.record("finish", step=self._step_idx, request_id=rid,
                           reason=reason, n_generated=n_generated)

    def _init_moe_counters(self, m) -> None:
        """Counters of a family with routed experts. Its forward adds
        to a small int32 leaf carried with the cache (`KVCache.stats`,
        order `ops/moe_routed.STATS`) inside the decode and prefill
        programs; nothing is fetched in a step. The leaf is read when
        the registry is rendered (a scrape hook) and the counters move
        by what it gained since the last read."""
        import weakref

        self._m_moe_assign = m.counter(
            "bigdl_tpu_moe_assignments_total",
            "Token-expert choices of the routed layers, by whether the "
            "chosen expert is held on this chip.", labelnames=("held",))
        self._m_moe_hit = m.counter(
            "bigdl_tpu_moe_experts_hit_total",
            "Held experts that at least one token chose, summed over "
            "routed layers and program runs.")
        self._m_moe_layer_steps = m.counter(
            "bigdl_tpu_moe_layer_steps_total",
            "Routed layers run, summed over program runs (decode steps "
            "and prefill chunks).")
        for v in ("yes", "no"):
            self._m_moe_assign.labels(v)
        self._moe_lock = threading.Lock()
        with self._moe_lock:
            self._moe_seen = np.zeros((4,), np.uint32)
        ref = weakref.ref(self)

        def hook():
            eng = ref()
            if eng is None:
                return False
            eng._scrape_moe_stats()
            return True

        m.add_scrape_hook(hook)

    def _scrape_moe_stats(self) -> None:
        """Read the device tally and move the counters. Any thread: the
        leaf may be donated to a step that is being dispatched this
        instant, whose result then replaces it; read again."""
        vals = None
        for _ in range(8):
            stats = getattr(self.cache, "stats", None)
            if stats is None:
                return
            try:
                # audited: at scrape time only, never in a step
                vals = np.asarray(stats).astype(np.uint32)  # graftlint: disable=step-host-sync
                break
            except RuntimeError:
                time.sleep(0.002)
        if vals is None:
            return
        with self._moe_lock:
            # uint32 differences: the int32 tally may wrap
            d = (vals - self._moe_seen).astype(np.uint32)
            self._moe_seen = vals
        self._m_moe_assign.labels("yes").inc(float(d[0]))
        self._m_moe_assign.labels("no").inc(float(d[1]))
        self._m_moe_hit.inc(float(d[2]))
        self._m_moe_layer_steps.inc(float(d[3]))

    def _update_gauges(self) -> None:
        self._m_occupancy.set(sum(1 for s in self.slots if s.active))
        self._m_queue_depth.set(len(self.waiting) + len(self._cp_waiting))
        # brownout ladder: one pressure sample per working step (the
        # overload_storm fault overrides the measured signal here)
        self._update_brownout()
        # hbm gauges: the ledger throttles its own device poll
        # ($BIGDL_TPU_MEMORY_POLL_SEC), so per-step publish is cheap
        self.ledger.publish(self.registry)
        if self._paged:
            # page gauges + host-int -> counter mirrors (delta-inc so
            # shared registries and engine restarts never double-count)
            self.pool.publish(self.registry)
            d = self.pool.exhausted_total - self._pub_pool_exhausted
            if d:
                self._m_pool_exhausted.inc(d)
                self._pub_pool_exhausted += d
            if self.radix is not None:
                r, pub = self.radix, self._pub_radix
                hits_d = r.hits - pub["hits"]
                miss_d = (r.lookups - pub["lookups"]) - hits_d
                if hits_d:
                    self._m_radix_lookups.labels("hit").inc(hits_d)
                if miss_d:
                    self._m_radix_lookups.labels("miss").inc(miss_d)
                lt_d = r.lookup_tokens - pub["lookup_tokens"]
                ht_d = r.hit_tokens - pub["hit_tokens"]
                if lt_d:
                    self._m_radix_tokens.labels("looked_up").inc(lt_d)
                if ht_d:
                    self._m_radix_tokens.labels("hit").inc(ht_d)
                pub.update(lookups=r.lookups, hits=r.hits,
                           lookup_tokens=r.lookup_tokens,
                           hit_tokens=r.hit_tokens)

    def memory_snapshot(self) -> dict:
        """The `GET /v1/memory` dict: ledger static report + live
        device stats + budget math, plus the engine's own admission
        accounting."""
        snap = self.ledger.snapshot()
        snap["engine"] = {
            "kv_cache_dtype": self.kv_cache_dtype,
            "kv_bytes_per_slot": self._kv_bytes_per_slot,
            "admissions_deferred": self._deferred_admissions,
            "hbm_budget_fraction": self.ledger.budget_fraction,
            "next_admission_cost_bytes": (
                self._admission_cost(
                    len(self.waiting[0].prompt_token_ids))
                if self.waiting else None),
        }
        if self._paged:
            snap["engine"]["paged"] = self._paged_snapshot()
        return snap

    def _overload_snapshot(self) -> dict:
        """The stats_snapshot "overload" block: controller state plus
        the engine-side load measurements it feeds on."""
        ov = self.overload.snapshot()
        ov["queue_bytes"] = self._queue_bytes()
        ov["tpot_ewma_ms"] = round(self._tpot_ewma * 1000.0, 3)
        ov["drain_rate_rps"] = round(self._drain_rate(), 3)
        return ov

    def stats_snapshot(self) -> dict:
        """JSON-ready engine state for `GET /v1/stats`: live occupancy,
        queue depths, metric summaries, recent request spans, and the
        jit compile table."""
        from bigdl_tpu.observability.compile_watch import (
            compile_table, startup_snapshot)

        return {
            "slots": {"total": len(self.slots),
                      "active": sum(1 for s in self.slots if s.active)},
            "queue_depth": len(self.waiting),
            "cp_queue_depth": len(self._cp_waiting),
            "admitting": self._admitting is not None,
            "stall_steps": self._stall_steps,
            "engine_steps": self._step_idx,
            "dispatch_overhead_ms": round(
                self._dispatch_ewma * 1000.0, 3),
            # compact live-perf subset for the router's poll loop; the
            # full attribution lives at GET /v1/perf
            "perf": {
                "roofline_util_decode": (
                    self._last_perf["roofline_util"]
                    if self._last_perf else None),
                "decode_ideal_ms": (
                    self._last_perf["decode_ideal_ms"]
                    if self._last_perf else None),
                "roofline_mfu_prefill": (
                    self._last_prefill_perf["mfu"]
                    if self._last_prefill_perf else None),
                "sentinel_tripped": (
                    self.sentinel.tripped
                    if self.sentinel is not None else None),
                "sentinel_trips": (
                    self.sentinel.snapshot()["trips"]
                    if self.sentinel is not None else 0),
            },
            # compact live-quality subset for the router's poll loop;
            # the full view (attribution table, probe history) lives at
            # GET /v1/quality
            "quality": {
                "qtype": self.qtype,
                "token_nll": (self._last_quality["token_nll"]
                              if self._last_quality else None),
                "entropy": (self._last_quality["entropy"]
                            if self._last_quality else None),
                "top1_margin": (self._last_quality["top1_margin"]
                                if self._last_quality else None),
                "probe_nll": (self._last_probe["nll"]
                              if self._last_probe else None),
                "sentinel_tripped": (
                    self.qsentinel.tripped
                    if self.qsentinel is not None else None),
                "sentinel_trips": (
                    self.qsentinel.snapshot()["trips"]
                    if self.qsentinel is not None else 0),
            } if self._use_quality else None,
            "paged": self._paged_snapshot() if self._paged else None,
            "migration": self.migration_snapshot(),
            "metrics": self.registry.summary(),
            "requests": self.tracer.snapshot(),
            "compile_table": compile_table(),
            # marks and first calls by stage, on one clock
            "startup": startup_snapshot(),
            "memory": self.memory_snapshot(),
            "overload": self._overload_snapshot(),
            "slo": self.slo.snapshot(),
            "usage": self.usage.snapshot(),
            "robustness": {
                "step_heartbeat_age_sec": round(
                    self.step_heartbeat_age(), 3),
                "compiles_in_progress": compiles_in_progress(),
                "draining": self._draining,
                "drain_deadline": self._drain_deadline,
                "faults_enabled": self.faults.enabled,
                "step_retries": self._retry_total,
                "consecutive_failures": self._consec_failures,
                "request_deadline_ms": self._request_deadline_ms,
                "slot_crashes": {
                    s.req.request_id: s.req.crashes
                    for s in self.slots
                    if s.active and s.req.crashes > 0},
            },
        }

    # -- live roofline + perf-regression sentinel ---------------------------

    def _perf_observe(self, wall_s: float, n_active: int,
                      seq_len: int) -> None:
        """Fold one decode step into the live roofline gauges and the
        sentinel. Called from step() with the FULL step wall time; cost
        is a handful of float ops + three gauge sets (the fastpath
        dispatch-count test asserts it adds no device dispatches)."""
        decode_ms = wall_s * 1e3
        if decode_ms <= 0:
            return
        self._last_perf = {
            "decode_ms": round(decode_ms, 3),
            "decode_ideal_ms": None,
            "roofline_util": None,
            "seq_len": seq_len,
            "batch": n_active,
            "step": self._step_idx,
        }
        util = None
        if self._peaks is not None:
            costs = roofline.decode_costs(
                self.cfg, self._weight_bytes, seq_len,
                self.kv_cache_dtype, batch=n_active)
            ideal_ms = costs["ideal_ms"]
            hbm_bytes = costs["hbm_bytes"]
            flops = costs["flops"]
            util = round(ideal_ms / decode_ms, 4)
            self._m_roofline.labels("decode").set(util)
            self._m_decode_ideal.set(round(ideal_ms, 6))
            self._last_perf.update(
                decode_ideal_ms=round(ideal_ms, 6), roofline_util=util,
                hbm_bytes=int(hbm_bytes), flops=int(flops))
        if self.sentinel is not None:
            self.sentinel.observe(
                decode_ms=decode_ms, roofline_util=util,
                dispatch_ms=self._dispatch_ewma * 1e3)

    def _obs_prefill_perf(self, prompt_len: int, prefill_s: float) -> None:
        """Prefill-side roofline gauge (MFU), fed from the admission
        observability hook."""
        if prefill_s <= 0 or prompt_len <= 0:
            return
        flops = roofline.prefill_costs(self.cfg, prompt_len)["flops"]
        mfu = None
        if self._peaks is not None:
            mfu = round(flops / prefill_s / (self._peaks[0] * 1e12), 4)
            self._m_roofline.labels("prefill").set(mfu)
        self._last_prefill_perf = {
            "prompt_len": prompt_len,
            "prefill_ms": round(prefill_s * 1e3, 3),
            "mfu": mfu,
            "flops": int(flops),
        }

    def _on_perf_trip(self, info: dict) -> None:
        """Sentinel tripped: counter + flight event + postmortem + a
        bounded profiler auto-capture into the postmortem dir, all
        best-effort (a perf regression must never become an outage)."""
        try:
            for mt in info.get("metrics", ()):
                self._m_perf_regress.labels(mt).inc()
            self.flight.record(
                "perf_regression", step=self._step_idx,
                metrics=list(info.get("metrics", ())),
                ewma=info.get("ewma"), baseline=info.get("baseline"),
                threshold=info.get("threshold"))
            self.write_postmortem("perf_regression")
            self._start_auto_capture(info)
        except Exception:
            pass

    def _on_perf_recover(self, info: dict) -> None:
        try:
            self.flight.record(
                "perf_recovered", step=self._step_idx,
                metrics=list(info.get("metrics", ())),
                ewma=info.get("ewma"), baseline=info.get("baseline"))
            self._auto_capture_dir = None
        except Exception:
            pass

    def _start_auto_capture(self, info: dict) -> None:
        """Bounded jax.profiler capture at the moment of the slowdown:
        at most BIGDL_TPU_PROFILER_MAX_SEC into a per-trip subdir of
        the postmortem dir (skipped when no dir is configured or a
        capture is already live), annotated onto any live traces."""
        from bigdl_tpu.utils.profiling import start_profiler

        base = os.environ.get("BIGDL_TPU_POSTMORTEM_DIR")
        if not base:
            return
        cap_dir = os.path.abspath(os.path.join(
            base, f"perf_capture_step{self._step_idx}"))
        try:
            out = start_profiler(cap_dir,
                                 capture_id=f"perf-{self._step_idx}")
        except Exception:
            return      # capture live elsewhere, bad env, profiler err
        self._auto_capture_dir = cap_dir
        self.flight.record(
            "perf_auto_capture", step=self._step_idx,
            log_dir=cap_dir, max_sec=out.get("max_sec"))
        # stitch the capture onto live traces: one span per distinct
        # trace id among active slots, so the fleet timeline shows
        # WHERE the profiler evidence lives
        now = time.time()
        for s in self.slots:
            if s.active and s.req is not None and s.req.trace is not None:
                self.spans.record(
                    "perf_auto_capture", s.req.trace[0],
                    t_start=now, t_end=now, step=self._step_idx,
                    request_id=s.req.request_id, log_dir=cap_dir,
                    metrics=list(info.get("metrics", ())))

    def perf_snapshot(self) -> dict:
        """JSON-ready live-performance view for ``GET /v1/perf``:
        per-phase roofline attribution, the sentinel state, and the
        compile table's top offenders by analytical bytes moved."""
        peak_tflops, peak_gbps = self._peaks or (None, None)
        return {
            "decode": dict(self._last_perf) if self._last_perf else None,
            "prefill": (dict(self._last_prefill_perf)
                        if self._last_prefill_perf else None),
            "tpot_ewma_ms": round(self._tpot_ewma * 1e3, 3),
            "dispatch_overhead_ms": round(self._dispatch_ewma * 1e3, 3),
            "weight_bytes": self._weight_bytes,
            "model_flops_per_token": roofline.model_flops_per_token(
                self.cfg),
            "kv_cache_dtype": self.kv_cache_dtype,
            "peak_bf16_tflops": peak_tflops,
            "peak_hbm_gbps": peak_gbps,
            "sentinel": (self.sentinel.snapshot()
                         if self.sentinel is not None else None),
            "top_offenders": top_offenders(8),
        }

    # -- live quality telemetry + QualitySentinel ---------------------------

    def _host_quality_rows(self, logits: np.ndarray,
                           q_meta) -> np.ndarray:
        """Host-side twin of the fused quality block: chosen-token
        logprob / entropy / top-1 margin per q_meta row, computed from
        the [B, V] logits array the complex rows already pulled. Only
        runs when that pull happened anyway — never adds a transfer."""
        out = np.zeros((logits.shape[0], 3), np.float32)
        for i, tok, _, _ in q_meta:
            row = logits[i].astype(np.float64)
            mx = float(row.max())
            ex = np.exp(row - mx)
            z = float(ex.sum())
            lp = row - mx - np.log(z)
            p = ex / z
            top2 = np.partition(row, -2)[-2:]
            out[i, 0] = lp[tok]
            out[i, 1] = -float((p * lp).sum())
            out[i, 2] = float(top2[-1] - top2[-2])
        return out

    def _quality_observe(self, qrows_np: np.ndarray, q_meta) -> None:
        """Fold one decode step's quality rows into the histograms,
        the compact snapshot, and the QualitySentinel. Pure host float
        work — the fastpath dispatch-count test asserts it adds no
        device dispatches. The ``_np`` suffix declares the host-mirror
        contract: callers pass an already-pulled numpy array, never a
        device buffer (graftlint's step-host-sync rule audits this)."""
        lps: List[float] = []
        ents: List[float] = []
        margins: List[float] = []
        for i, tok, repeat, qos in q_meta:
            lp = float(qrows_np[i, 0])
            ent = float(qrows_np[i, 1])
            margin = float(qrows_np[i, 2])
            lbl = (self.qtype, self.kv_cache_dtype, qos)
            self._m_q_logprob.labels(*lbl).observe(lp)
            self._m_q_entropy.labels(*lbl).observe(ent)
            self._m_q_margin.labels(*lbl).observe(margin)
            if (self.eos_token_id is not None
                    and tok == self.eos_token_id):
                self._m_q_eos.labels(*lbl).inc()
            if repeat:
                self._m_q_repeat.labels(*lbl).inc()
            lps.append(lp)
            ents.append(ent)
            margins.append(margin)
        n = len(lps)
        if not n:
            return
        mean_lp = sum(lps) / n
        self._last_quality = {
            # NLL (= -logprob) keeps every sentinel metric positive,
            # which the multiplicative threshold machinery requires
            "token_nll": round(-mean_lp, 4),
            "entropy": round(sum(ents) / n, 4),
            "top1_margin": round(sum(margins) / n, 4),
            "batch": n,
            "step": self._step_idx,
        }
        if self.qsentinel is not None:
            self.qsentinel.observe(
                token_nll=-mean_lp, entropy=sum(ents) / n,
                top1_margin=sum(margins) / n)

    def _on_quality_trip(self, info: dict) -> None:
        """QualitySentinel tripped: counter + flight event +
        postmortem + bounded profiler auto-capture, all best-effort
        (a quality regression must never become an outage)."""
        try:
            for mt in info.get("metrics", ()):
                self._m_q_regress.labels(mt).inc()
            self.flight.record(
                "quality_regression", step=self._step_idx,
                metrics=list(info.get("metrics", ())),
                ewma=info.get("ewma"), baseline=info.get("baseline"),
                threshold=info.get("threshold"))
            self.write_postmortem("quality_regression")
            self._start_auto_capture(info)
        except Exception:
            pass

    def _on_quality_recover(self, info: dict) -> None:
        try:
            self.flight.record(
                "quality_recovered", step=self._step_idx,
                metrics=list(info.get("metrics", ())),
                ewma=info.get("ewma"), baseline=info.get("baseline"))
            self._auto_capture_dir = None
        except Exception:
            pass

    def _maybe_quality_probe(self) -> None:
        """Run the teacher-forced NLL probe every
        ``quality_probe_steps`` decode steps (0 = off, the default, so
        the pure-decode dispatch-count invariant holds untouched)."""
        p = self._quality_probe_steps
        if not self._use_quality or not p or self._step_idx % p:
            return
        try:
            self._quality_probe()
        except Exception:
            pass        # the probe is telemetry, never load-bearing

    def _quality_probe(self) -> None:
        """Teacher-forced NLL over the golden probe prompts: one extra
        dispatch on its own fresh 4-row cache, scored against the
        SERVING weights — so silent numeric corruption (logit_drift)
        moves this number even when byte-level canaries cannot see it.
        When fault clauses are live the probe applies the same
        column-0 drift bias the decode path applies (mask + bias enter
        as traced values, so fault state never forces a recompile)."""
        v = self.cfg.vocab_size
        prompts = np.asarray(
            [[t % v for t in p] for p in GOLDEN_PROBE_PROMPTS],
            np.int32)
        n, w = prompts.shape
        if self._quality_probe_fn is None:
            fwd = self.family.forward

            @functools.partial(tracked_jit, "engine_quality_probe",
                               registry=self.registry)
            def probe(params, toks, cache, drift_mask, drift_bias):
                logits, _ = fwd(params, self.cfg, toks, cache)
                lg = logits.astype(jnp.float32)
                lg = lg.at[:, :, 0].add(
                    jnp.where(drift_mask, drift_bias, 0.0)[:, None])
                lp = jax.nn.log_softmax(lg, axis=-1)
                chosen = jnp.take_along_axis(
                    lp[:, :-1, :],
                    toks[:, 1:, None].astype(jnp.int32), axis=-1)[..., 0]
                return -jnp.mean(chosen)

            self._quality_probe_fn = probe
        mask = np.zeros((n,), bool)
        bias = 0.0
        if self.faults.enabled:
            rows, b = self.faults.drift_rows(self._step_idx,
                                             list(range(n)))
            if rows:
                mask[rows] = True
                bias = float(b)
        cache = self.family.new_cache(self.cfg, n, w, False)
        nll_dev = self._quality_probe_fn(
            self.params, jnp.asarray(prompts), cache,
            jnp.asarray(mask), jnp.asarray(bias, jnp.float32))
        nll = float(np.asarray(nll_dev))
        self._m_q_probe_nll.set(round(nll, 4))
        self._last_probe = {
            "nll": round(nll, 4),
            "step": self._step_idx,
            "prompts": int(n),
            "tokens_per_prompt": int(w),
        }
        if self.qsentinel is not None:
            self.qsentinel.observe(probe_nll=nll)

    def quality_snapshot(self) -> dict:
        """JSON-ready quality view for ``GET /v1/quality``: the
        load-time quantization-error attribution table, the live
        decode telemetry, the latest golden probe, and the
        QualitySentinel state."""
        return {
            "enabled": self._use_quality,
            "qtype": self.qtype,
            "kv_cache_dtype": self.kv_cache_dtype,
            "attribution": (self.quality_report.to_doc()
                            if self.quality_report is not None
                            else None),
            "live": (dict(self._last_quality)
                     if self._last_quality else None),
            "probe": dict(self._last_probe) if self._last_probe else None,
            "probe_period_steps": self._quality_probe_steps,
            "golden_nll_allowance": golden_nll_allowance(self.qtype),
            "sentinel": (self.qsentinel.snapshot()
                         if self.qsentinel is not None else None),
        }

    def _config_fingerprint(self) -> dict:
        out = dataclasses.asdict(self.cfg_engine)
        out["kv_cache_dtype_resolved"] = self.kv_cache_dtype
        out["family"] = getattr(self.family, "name",
                                type(self.family).__name__)
        out["eos_token_id"] = self.eos_token_id
        out["request_deadline_ms_resolved"] = self._request_deadline_ms
        out["drain_timeout_sec_resolved"] = self._drain_timeout_sec
        out["fault_spec_active"] = self.faults.enabled
        return out

    def postmortem(self, reason: str = "on_demand",
                   error: Optional[BaseException] = None) -> dict:
        """The postmortem dict (flight tail, span tail, metrics
        snapshot, compile table, config + env fingerprint) — what
        `GET /v1/debug/dump` serves and crash dumps write."""
        return build_postmortem(
            reason, flight=self.flight, tracer=self.tracer,
            registry=self.registry, config=self._config_fingerprint(),
            memory=self._memory_best_effort(), error=error)

    def _memory_best_effort(self) -> Optional[dict]:
        """memory_snapshot() for dump paths: a failing snapshot must
        not mask the failure being dumped."""
        try:
            return self.memory_snapshot()
        except Exception as e:
            return {"error": repr(e)}

    def write_postmortem(self, reason: str,
                         error: Optional[BaseException] = None,
                         directory: Optional[str] = None):
        """Write the postmortem JSON to `directory` (default
        $BIGDL_TPU_POSTMORTEM_DIR); returns the path or None. Never
        raises."""
        return _write_postmortem_file(
            reason, directory=directory, flight=self.flight,
            tracer=self.tracer, registry=self.registry,
            config=self._config_fingerprint(),
            memory=self._memory_best_effort(), error=error)

    def _finish(self, idx: int, reason: str,
                error: Optional[dict] = None) -> None:
        s = self.slots[idx]
        if s.req is None:
            return
        gen_len = s.req.generated_offset + len(s.generated)
        if reason in ("abort", "error"):
            # a cancelled client's snapshot is dead weight; a poisoned
            # request's snapshot must never seed a future admission
            self._drop_prefix(s.req.prompt_token_ids)
        self._push_output(
            s.req.request_id,
            RequestOutput(s.req.request_id, [], True, reason, error=error),
            score=s.cum_logprob, length=gen_len)
        self._obs_finish(s.req.request_id, reason, n_generated=gen_len)
        s.req = None
        s.active = False
        s.drafted = False
        s.block = None
        self._io = None
        s.generated = []
        s.counts = None
        s.counts_out = None
        # release the slot's pages (paged) and reset its position so
        # the idle row stops deepening; KVCache and PagedKVCache are
        # both dataclasses, so replace() covers either store
        self._release_slot_pages(idx)
        self.cache = dataclasses.replace(
            self.cache, pos=self.cache.pos.at[idx].set(0))

    def _emit(self, s: _Slot, lp: Optional[LogprobEntry] = None) -> None:
        want_lp = s.req.params.logprobs is not None and lp is not None
        self._push_output(
            s.req.request_id,
            RequestOutput(s.req.request_id, [s.last_token], False,
                          logprobs=[lp] if want_lp else None))
        self._m_tokens.inc()
        # post-paid tenant token-rate accounting: future admissions of
        # a tenant in debt shed with 429 until its bucket refills
        self.overload.note_generated(s.req.params.tenant or "default",
                                     1, time.monotonic())

    def _done_reason(self, idx: int) -> Optional[str]:
        """Why the slot's request ends with its last token, or None."""
        s = self.slots[idx]
        p = s.req.params
        tok = s.last_token
        if (not p.ignore_eos and self.eos_token_id is not None
                and tok == self.eos_token_id):
            return "stop"
        if tok in p.stop_token_ids:
            return "stop"
        if s.req.generated_offset + len(s.generated) >= p.max_tokens:
            return "length"
        plen = len(s.req.prompt_token_ids)
        # (a block family ends at the slab's end by blocks: `_block_step`)
        if self._block is None and (plen + len(s.generated) + 1
                                    >= self.cfg_engine.max_seq):
            return "length"
        return None

    def _check_done(self, idx: int) -> bool:
        reason = self._done_reason(idx)
        if reason is not None:
            self._finish(idx, reason)
        return reason is not None

    def _emit_run(self, idx: int, toks: List[int], steps: List[int]
                  ) -> int:
        """A block family's event: the run of final tokens `toks` that
        continues what the slot's stream was sent, each beside the pass
        count that committed it, in ONE output; cut after the first
        token that ends the request (a stop token, `max_tokens`), the
        rest of the block dropped. Returns the tokens streamed."""
        s = self.slots[idx]
        rid = s.req.request_id
        reason = None
        n = 0
        for tok in toks:
            s.last_token = tok
            s.generated.append(tok)
            n += 1
            reason = self._done_reason(idx)
            if reason is not None:
                break
        span = self.tracer.get(rid)
        if span is not None and span.t_first_token is None:
            self._obs_first_token(rid)
        self._push_output(rid, RequestOutput(
            rid, list(toks[:n]), False, steps=list(steps[:n])))
        self._m_tokens.inc(n)
        self.overload.note_generated(s.req.params.tenant or "default", n,
                                     time.monotonic())
        if reason is not None:
            self._finish(idx, reason)
        return n

    # -- context-parallel overflow lane -------------------------------------

    def _cp_finish(self, reason: str) -> None:
        a = self._cp_active
        s = a.slot
        gen_len = s.req.generated_offset + len(s.generated)
        self._push_output(
            s.req.request_id,
            RequestOutput(s.req.request_id, [], True, reason),
            score=s.cum_logprob, length=gen_len)
        self._obs_finish(s.req.request_id, reason, n_generated=gen_len)
        self._cp_active = None

    def _cp_check_done(self) -> None:
        a = self._cp_active
        s = a.slot
        p = s.req.params
        tok = s.last_token
        if (not p.ignore_eos and self.eos_token_id is not None
                and tok == self.eos_token_id):
            return self._cp_finish("stop")
        if tok in p.stop_token_ids:
            return self._cp_finish("stop")
        if s.req.generated_offset + len(s.generated) >= p.max_tokens:
            return self._cp_finish("length")
        if a.pos >= a.alloc:      # next token has no cache row left
            return self._cp_finish("length")

    def _cp_step(self) -> bool:
        """Advance the context-parallel lane by at most one unit of work
        per engine step — ONE prefill chunk (so a cp_max_seq-scale
        admission never stalls the batched streams for more than a
        chunk, the same contract as the slot lane's chunked admission)
        or ONE decode token. Returns True if any CP work was done."""
        import jax.numpy as jnp

        from bigdl_tpu.parallel.cp import (cp_decode_step, cp_empty_cache,
                                           cp_prefill_chunk)

        a = self._cp_active
        adm = self._cp_admitting
        if a is None and adm is None:
            while self._cp_waiting:
                req = self._cp_waiting.popleft()
                if req.request_id in self._abort:
                    self._abort.discard(req.request_id)
                    self._push_output(req.request_id, RequestOutput(
                        req.request_id, [], True, "abort"))
                    self._obs_finish(req.request_id, "abort")
                    continue
                break
            else:
                return False
            n = self._cp_mesh.shape[self._cp_axis]
            ids = req.prompt_token_ids
            want = len(ids) + req.params.max_tokens + 1
            alloc = min(-(-want // n) * n, self.cfg_engine.cp_max_seq)
            cache = cp_empty_cache(self.cfg, 1, alloc, self._cp_mesh,
                                   self._cp_axis,
                                   kv_cache_dtype=self.kv_cache_dtype)
            adm = self._cp_admitting = _CPAdmitting(req, cache, 0, alloc)
            self.tracer.admitted(req.request_id)

        if adm is not None:
            if adm.req.request_id in self._abort:
                self._abort.discard(adm.req.request_id)
                self._push_output(adm.req.request_id, RequestOutput(
                    adm.req.request_id, [], True, "abort"))
                self._obs_finish(adm.req.request_id, "abort")
                self._cp_admitting = None
                return True
            ids = adm.req.prompt_token_ids
            plen = len(ids)
            c = self._chunk
            part = ids[adm.consumed:adm.consumed + c]
            padded = np.zeros((1, c), np.int32)
            padded[0, :len(part)] = part
            lg, adm.cache = cp_prefill_chunk(
                self.params, self.cfg, jnp.asarray(padded), adm.cache,
                adm.consumed, min(plen - 1, adm.consumed + c - 1),
                self._cp_mesh, self._cp_axis)
            self.phases.mark_chunk()
            adm.consumed += len(part)
            if adm.consumed < plen:
                return True
            slot = _Slot()
            slot.req = adm.req
            self._setup_slot_sampler(slot)
            tok, lp = self._sample_host(np.asarray(lg)[0], slot)
            slot.generated = [int(tok)]
            slot.last_token = int(tok)
            slot.active = True
            self._cp_active = _CPActive(slot, adm.cache, plen, adm.alloc)
            self._cp_admitting = None
            self._obs_admission_complete(slot.req.request_id)
            self._emit(slot, lp)
            self._cp_check_done()
            return True

        s = a.slot
        if s.req.request_id in self._abort:
            self._abort.discard(s.req.request_id)
            self._cp_finish("abort")
            return True
        lg, a.cache = cp_decode_step(
            self.params, self.cfg,
            jnp.asarray([s.last_token], jnp.int32), a.cache, a.pos,
            self._cp_mesh, self._cp_axis)
        a.pos += 1
        tok, lp = self._sample_host(np.asarray(lg)[0], s)
        s.last_token = int(tok)
        s.generated.append(int(tok))
        self._emit(s, lp)
        self._cp_check_done()
        return True

    def _preempt(self) -> None:
        """Starvation relief: evict the LATEST-arrived running sequence by
        recompute (reference scheduler's PreemptionMode.RECOMPUTE,
        vllm/core/scheduler.py:52-66). Its tokens so far become the prompt
        of a resumed request appended at the BACK of the queue — starved
        requests admit into the freed slot first (round-robin under
        pressure), and the prompt-prefix cache (when enabled) makes the
        recompute prefill cheap. Nothing already streamed is re-emitted."""
        victim = max((i for i, s in enumerate(self.slots) if s.active),
                     key=lambda i: self.slots[i].req.arrival, default=None)
        if victim is None:
            return
        s = self.slots[victim]
        req = s.req
        resumed = dataclasses.replace(
            req,
            prompt_token_ids=list(req.prompt_token_ids) + list(s.generated),
            generated_offset=req.generated_offset + len(s.generated),
            resumed_cum_logprob=s.cum_logprob,
            # a block family: the block in flight is dropped (what it
            # streamed opens the resumed request's first block as given
            # rows, the rest is generated again), the pass count goes on
            block_passes=s.block.passes if s.block is not None else 0)
        s.req = None
        s.active = False
        s.block = None
        self._io = None
        s.generated = []
        s.counts = None
        s.counts_out = None
        self._release_slot_pages(victim)
        self.cache = dataclasses.replace(
            self.cache, pos=self.cache.pos.at[victim].set(0))
        self.waiting.append(resumed)
        self._m_preemptions.inc()
        self.tracer.preempted(resumed.request_id)
        self.flight.record(
            "preempt", step=self._step_idx,
            request_id=resumed.request_id, slot=victim,
            n_generated=resumed.generated_offset)

    # -- robustness: quarantine, retries, deadlines, drain ------------------

    def _on_fault_fired(self, kind: str, point: str, step: int) -> None:
        """FaultInjector.on_fire: count + breadcrumb every injection."""
        self._m_faults.labels(kind).inc()
        self.flight.record("fault_injected", step=step, kind=kind,
                           point=point)

    def _fail_request(self, rid: str, reason: str,
                      error: Optional[dict] = None) -> None:
        """Fail a request that is NOT resident in a slot (queued or
        mid-admission): deliver the finished output and close its
        span."""
        self._push_output(rid, RequestOutput(rid, [], True, reason,
                                             error=error))
        self._obs_finish(rid, reason)

    def _quarantine_slot(self, idx: int, reason: str,
                         error: Optional[BaseException] = None) -> None:
        """Blast-radius isolation: fail ONE resident request with a
        structured error while every other slot keeps decoding. Its
        prefix snapshot is dropped (a poisoned prompt must not seed
        future admissions), a `quarantined` flight event and counter
        fire, and a postmortem dump captures the evidence."""
        s = self.slots[idx]
        rid = s.req.request_id
        self._m_quarantined.labels(reason).inc()
        fields = exception_fields(error) if error is not None else {}
        self.flight.record("quarantined", step=self._step_idx,
                           request_id=rid, slot=idx, reason=reason,
                           crashes=s.req.crashes, **fields)
        self._finish(idx, "error", error=self._quarantine_error(
            reason, rid, error))
        self.write_postmortem("request_quarantined", error=error)

    def _quarantine_request(self, req: Request, reason: str,
                            error: Optional[BaseException] = None) -> None:
        """Quarantine a non-resident request (its admission keeps
        crashing before it ever reaches a slot)."""
        self._m_quarantined.labels(reason).inc()
        fields = exception_fields(error) if error is not None else {}
        self.flight.record("quarantined", step=self._step_idx,
                           request_id=req.request_id, slot=None,
                           reason=reason, crashes=req.crashes, **fields)
        self._drop_prefix(req.prompt_token_ids)
        self._fail_request(req.request_id, "error",
                           error=self._quarantine_error(
                               reason, req.request_id, error))
        self.write_postmortem("request_quarantined", error=error)

    @staticmethod
    def _quarantine_error(reason: str, rid: str,
                          error: Optional[BaseException]) -> dict:
        out = {"reason": reason, "request_id": rid}
        if error is not None:
            out["type"] = type(error).__name__
            out["message"] = str(error)[:200]
        return out

    def begin_drain(self, timeout_sec: Optional[float] = None) -> None:
        """Graceful drain (SIGTERM path): stop admitting NEW requests
        (add_request raises EngineDraining -> API 503 + Retry-After),
        let in-flight work finish, and fail whatever remains at the
        drain deadline with reason "drain_timeout" (-> API 504)."""
        if self._draining:
            return
        self._draining = True
        t = (timeout_sec if timeout_sec is not None
             else self._drain_timeout_sec)
        self._drain_deadline = time.time() + max(t, 0.0)
        self._m_draining.set(1)
        self.flight.record(
            "drain_start", step=self._step_idx, timeout_sec=t,
            queue_depth=len(self.waiting) + len(self._cp_waiting),
            occupancy=sum(1 for s in self.slots if s.active))

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        return self._draining and not self.has_unfinished()

    def drain_retry_after_sec(self) -> int:
        """Seconds a 503'd client should wait before retrying: the
        remaining drain window (a fresh replica should be up by then)."""
        if self._drain_deadline is None:
            return 1
        return max(1, int(self._drain_deadline - time.time()) + 1)

    def _drain_expire(self) -> None:
        """Drain deadline reached with work still in flight: fail every
        remaining request with reason "drain_timeout" so clients get a
        definitive 504 instead of a cut socket."""
        self.flight.record(
            "drain_timeout", step=self._step_idx,
            queue_depth=len(self.waiting) + len(self._cp_waiting),
            occupancy=sum(1 for s in self.slots if s.active))
        self.write_postmortem("drain_timeout")
        for q in (self.waiting, self._cp_waiting):
            for r in q:
                self._fail_request(r.request_id, "drain_timeout")
            q.clear()
        if self._admitting is not None:
            self._release_admission_pages(self._admitting)
            self._fail_request(self._admitting.req.request_id,
                               "drain_timeout")
            self._admitting = None
        if self._cp_admitting is not None:
            self._fail_request(self._cp_admitting.req.request_id,
                               "drain_timeout")
            self._cp_admitting = None
        for i, s in enumerate(self.slots):
            if s.active:
                self._finish(i, "drain_timeout")
        if self._cp_active is not None:
            self._cp_finish("drain_timeout")
        # suspended migrations whose sender never resolved them: the
        # drain window is closed — fail them too (the sender's late
        # commit/resume finds no meta and no-ops)
        self._migrate_req.clear()
        self._migration_done.clear()
        self._migration_fail.clear()
        for rid, meta in list(self._migration_meta.items()):
            self._migration_meta.pop(rid, None)
            with self._lock:
                self._migration_out.pop(rid, None)
            self._push_output(rid, RequestOutput(
                rid, [], True, "drain_timeout"))
            self._obs_finish(rid, "drain_timeout",
                             n_generated=meta["n_generated"])

    def _expire_deadlines(self) -> None:
        """Per-step deadline enforcement across every lane a request
        can live in: waiting queues, (CP) admission, resident slots,
        and the CP pseudo-slot. Reason "deadline" -> API 504."""
        now = time.time()

        def expired(req: Request) -> bool:
            return req.deadline is not None and now >= req.deadline

        for q in (self.waiting, self._cp_waiting):
            if any(expired(r) for r in q):
                keep = [r for r in q if not expired(r)]
                for r in q:
                    if expired(r):
                        self._fail_request(r.request_id, "deadline")
                q.clear()
                q.extend(keep)
        a = self._admitting
        if a is not None and expired(a.req):
            self._release_admission_pages(a)
            self._fail_request(a.req.request_id, "deadline")
            self._admitting = None
        ca = self._cp_admitting
        if ca is not None and expired(ca.req):
            self._fail_request(ca.req.request_id, "deadline")
            self._cp_admitting = None
        for i, s in enumerate(self.slots):
            if s.active and expired(s.req):
                self._finish(i, "deadline")
        if self._cp_active is not None \
                and expired(self._cp_active.slot.req):
            self._cp_finish("deadline")

    def _on_step_failure(self, e: Exception) -> bool:
        """Recovery path for a failed step(): record + dump, attribute
        blame, quarantine crash-looping requests, and retry with
        exponential backoff while the consecutive-failure budget lasts.
        Re-raises when the budget is exhausted with no one to blame (a
        systemic failure, not a poisoned request)."""
        ce = self.cfg_engine
        self._consec_failures += 1
        attempt = self._consec_failures
        self.flight.record("step_exception", step=self._step_idx,
                           error=repr(e), attempt=attempt,
                           **exception_fields(e))
        self.write_postmortem("engine_step_exception", error=e)
        blamed = False
        a = self._admitting
        if a is not None:
            # mid-admission failures are attributable to ONE request:
            # drop the (possibly corrupt) private cache and retry it
            # from scratch at the FRONT of the queue (FCFS kept) until
            # its crash budget runs out, then quarantine it
            self._release_admission_pages(a)
            self._admitting = None
            a.req.crashes += 1
            if a.req.crashes > ce.max_slot_crashes:
                self._quarantine_request(a.req, "crash_loop", error=e)
            else:
                self.waiting.appendleft(a.req)
            blamed = True
        else:
            suspects = [i for i, s in enumerate(self.slots) if s.active]
            for i in suspects:
                self.slots[i].req.crashes += 1
            over = [i for i in suspects
                    if self.slots[i].req.crashes >= ce.max_slot_crashes]
            if over:
                # a batched decode failure cannot name its culprit; peel
                # ONE suspect per round (latest arrival, mirroring the
                # preemption victim policy) — repeated failures bisect
                # the batch down to the poisoned request while every
                # cleared slot keeps decoding
                victim = max(over,
                             key=lambda i: self.slots[i].req.arrival)
                self._quarantine_slot(victim, "crash_loop", error=e)
                blamed = True
        if blamed:
            # blame assigned and state changed: fresh retry budget
            self._consec_failures = 0
        elif attempt > ce.max_step_retries:
            raise                        # the active exception (e)
        self._retry_total += 1
        self._m_retries.inc()
        backoff_s = min(ce.retry_backoff_ms * (2 ** (attempt - 1)),
                        2000.0) / 1000.0
        self.flight.record("step_retry", step=self._step_idx,
                           attempt=attempt,
                           backoff_ms=round(backoff_s * 1000.0, 3))
        if backoff_s > 0:
            time.sleep(backoff_s)
        return True

    def step(self) -> bool:
        """One engine iteration (reference LLMEngine.step): advance the
        (chunked) admission by one chunk, then run one batched decode
        step. Returns True if any work was done.

        A step that raises records the exception into the flight
        recorder (with error type + truncated message) and writes a
        postmortem dump (when $BIGDL_TPU_POSTMORTEM_DIR is set), then
        enters the bounded-retry/quarantine path (_on_step_failure) —
        transient failures back off and retry, attributable ones
        quarantine the culprit request, and only budget exhaustion
        with no one to blame propagates out of step()."""
        self._step_idx += 1
        # liveness heartbeat, stamped BEFORE the fault hooks: a step
        # that hangs (replica_hang, a wedged device) leaves this stale,
        # which is what the API server's /health wedge check reads
        self._last_step_ts = time.monotonic()
        # sentinel wall clock from step() ENTRY: everything a client
        # experiences per token — fault sleeps, scheduler work, the
        # decode itself — belongs in the regression signal, so the
        # timer brackets the whole step, not just the device call
        t_step0 = time.perf_counter()
        self._pending_perf = None
        ph = self.phases.phase
        self.phases.begin()
        try:
            self.faults.raise_point("step", self._step_idx)
            if self.has_unfinished():
                # process-granularity faults (replica_crash/_hang) only
                # fire on steps with live work: the chaos harness wants
                # a replica dying MID-REQUEST, not on an idle spin
                self.faults.process_point("step", self._step_idx)
            ms = self.faults.sleep_ms("step", self._step_idx)
            if ms > 0:
                time.sleep(ms / 1000.0)
            did = self._step_inner()
        except Exception as e:
            return self._on_step_failure(e)
        self._consec_failures = 0
        with ph("observe"):
            # burn-rate evaluation: throttled to the spec's eval_sec,
            # runs on idle steps too so alerts recover without traffic
            with ph("observe.slo", child=True):
                self.slo.maybe_evaluate()
            if self._pending_perf is not None:
                n_active, seq_len = self._pending_perf
                self._pending_perf = None
                with ph("observe.perf", child=True):
                    self._perf_observe(time.perf_counter() - t_step0,
                                       n_active, seq_len)
            # periodic teacher-forced NLL probe (off by default: probe
            # period 0 keeps the pure-decode dispatch count untouched)
            if did:
                with ph("observe.probe", child=True):
                    self._maybe_quality_probe()
        self.phases.end(worked=did)
        return did

    def _step_inner(self) -> bool:
        """The phases of one step, contiguous and in this order: sweep,
        admission, then (with an active slot) dispatch, device, sample,
        emit, observe. Each is a span and a share of one
        bigdl_tpu_step_phase_seconds sample (self.phases)."""
        ph = self.phases.phase
        with ph("sweep"):
            mig_did, cp_did = self._sweep_step()

        # admission: at most ONE prefill chunk per step — a long prompt
        # admits across several steps while decodes keep flowing
        with ph("admission"):
            self._admission_step()

        active = [i for i, s in enumerate(self.slots) if s.active]
        if not active:
            did = cp_did or mig_did or self._admitting is not None
            with ph("observe"):
                self._observe_step(
                    ("admit" if self._admitting is not None else "cp")
                    if did else None, 0)
            return did
        if self._block is not None:
            return self._block_step(active)
        return self._decode_step(active)

    def _sweep_step(self) -> Tuple[bool, bool]:
        """What runs ahead of admission on every step: aborts, the
        migration step, deadlines, drain, the stall guard and the
        context-parallel lane. Returns whether the migration step and
        the CP lane did work."""
        # aborts
        for i, s in enumerate(self.slots):
            if s.active and s.req.request_id in self._abort:
                self._abort.discard(s.req.request_id)
                self._finish(i, "abort")

        # queued aborts: sweep the waiting queues every step so an
        # abandoned client's request frees its queue slot NOW — not
        # when it finally reaches the queue front (under a storm that
        # could be minutes of a dead request occupying bounded-queue
        # capacity and inflating every wait estimate)
        if self._abort and (self.waiting or self._cp_waiting):
            for q in (self.waiting, self._cp_waiting):
                if not any(r.request_id in self._abort for r in q):
                    continue
                keep = []
                for r in q:
                    if r.request_id in self._abort:
                        self._abort.discard(r.request_id)
                        self._push_output(r.request_id, RequestOutput(
                            r.request_id, [], True, "abort"))
                        self._obs_finish(r.request_id, "abort")
                    else:
                        keep.append(r)
                q.clear()
                q.extend(keep)

        # live migration: suspend + export requested sequences, finish
        # committed ones, re-admit failed ones (serving/api_server
        # drives the other half from its sender threads)
        mig_did = self._migration_step()

        # per-request deadlines (skip the scan entirely until the first
        # deadline-carrying request arrives)
        if self._any_deadline:
            self._expire_deadlines()

        # graceful drain: past the deadline, fail whatever is left so
        # blocked clients get a definitive 504 instead of a cut socket
        if (self._draining and self._drain_deadline is not None
                and time.time() >= self._drain_deadline
                and self.has_unfinished()):
            self._drain_expire()

        # starvation guard: requests queued while every slot grinds a
        # long generation eventually preempt the newest running sequence
        ce = self.cfg_engine
        if (ce.preempt_after_steps > 0 and self.waiting
                and self._admitting is None
                and all(s.active for s in self.slots)):
            self._stall_steps += 1
            if self._stall_steps >= ce.preempt_after_steps:
                self._m_stall_trips.inc()
                self.flight.record(
                    "stall_guard_trip", step=self._step_idx,
                    stall_steps=self._stall_steps,
                    queue_depth=len(self.waiting))
                # a trip means admission starved for preempt_after_steps
                # consecutive steps — dump the evidence while it is hot
                self.write_postmortem("stall_guard_trip")
                self._preempt()
                self._stall_steps = 0
        else:
            self._stall_steps = 0

        # context-parallel lane: one token (or one admission) per step
        cp_did = False
        if self._cp_mesh is not None:
            cp_did = self._cp_step()
        return mig_did, cp_did

    def _observe_step(self, flight_phase: Optional[str],
                      n_active: int) -> None:
        """The close of every ``_step_inner`` path, inside the observe
        phase: count a working step (``flight_phase`` names what it
        did; None for an idle one), leave its flight breadcrumb,
        refresh the gauges."""
        ph = self.phases.phase
        if flight_phase is not None:
            self._m_steps.inc()
            with ph("observe.flight", child=True):
                self._flight_step(flight_phase, n_active)
        with ph("observe.gauges", child=True):
            self._update_gauges()

    def _decode_step(self, active: List[int]) -> bool:
        """One batched decode step for the ``active`` slots."""
        ce = self.cfg_engine
        ph = self.phases.phase

        simple = self._simple
        # the packed step that serves these slots, if one does: the
        # resident step, or a speculating engine's verify step
        packed = self._packed_step(active)
        verify, resident = packed == "verify", packed == "plain"
        # this step may send the next one out before it reads its own
        # tokens: only from a packed step of its own choosing, over the
        # slots that step held
        lead = resident or verify
        ahead, active, whole = self._take_ahead(active)
        if ahead is not None:
            # brownout, a fault clause or a host-sampled newcomer take
            # effect with the next step
            lead = lead and whole
            verify, resident = self._mtp, not self._mtp
        rows_a_slot = 2 if verify else 1
        read_ahead = ahead is not None
        if self._dsa is not None:
            # what the selection keeps this step, by its own rule from
            # the positions the host already knows (outside the phases):
            # every computed row counts, both rows of a verify step
            n_full, topk = self._dsa
            held = [len(self.slots[i].req.prompt_token_ids)
                    + len(self.slots[i].generated) + r for i in active
                    for r in range(rows_a_slot)]
            self._m_dsa_positions.labels("live").inc(n_full * sum(held))
            self._m_dsa_positions.labels("selected").inc(
                n_full * sum(min(d, topk) for d in held))
        if self._eva is not None:
            # what the attention kernel reads this step, by its own rule
            # from the positions the host already knows (outside the
            # phases)
            window, stride = self._eva
            rows = rows_read(
                [len(self.slots[i].req.prompt_token_ids)
                 + len(self.slots[i].generated) - 1 for i in active],
                window, stride)
            for kd, n in rows.items():
                self._m_eva_rows.labels(kd).inc(n)
        if self._swa is not None:
            # what the two decode kernels read this step, by their own
            # rule from the positions the host already knows (outside
            # the phases)
            n_win, n_full, window = self._swa
            at = [len(self.slots[i].req.prompt_token_ids)
                  + len(self.slots[i].generated) - 1 for i in active]
            rows = swa_rows_read(at, window)
            self._m_swa_rows.labels("window").inc(n_win * rows["window"])
            self._m_swa_rows.labels("full").inc(n_full * rows["full"])
            self._m_swa_rows.labels("context").inc(
                (n_win + n_full) * rows["full"])
            live, dead = ring_blocks(at, *self.cache.ring_k.shape[2:])
            self._m_swa_blocks.labels("live").inc(n_win * live)
            self._m_swa_blocks.labels("dead").inc(n_win * dead)
        toks = None
        finite_host = None
        n_emit = None       # verify step: tokens each slot kept, [B]
        hidden_dev = io_next = None
        out_dev = finite_dev = logits_dev = None
        qrows = None        # [B, 3] chosen_lp/entropy/top1_margin (f32)
        t_decode0 = time.perf_counter()
        t_wall0 = time.time()
        # dispatch vs device split: the time to the decode call's
        # return is pure host work (trace + transfer enqueue); the
        # blocked wait on the step result is device compute
        with ph("dispatch"):
            # tokens each active slot will hold after this step (its
            # query sits one below), captured while every slot's request
            # is still attached (_check_done frees finishing slots
            # before the step timing lands)
            depths = [len(self.slots[i].req.prompt_token_ids)
                      + len(self.slots[i].generated) for i in active]
            # mean live cache depth for the roofline sample
            perf_seq_len = max(1, sum(depths) // len(active))
            if self._attn_blocks is not None:
                # what the slab decode kernel fetches this step, by its
                # own rule from the positions the host already knows
                layers, slab, s_max, hkv = self._attn_blocks
                at = [-1] * ce.max_batch         # -1: an empty slot
                for i, d in zip(active, depths):
                    at[i] = d - 1
                self._m_attn_blocks.labels("read").inc(
                    layers * blocks_read(at, s_max, hkv))
                self._m_attn_blocks.labels("slab").inc(layers * slab)
            elif self._paged:
                # what the block-table kernel copies this step, by its
                # own rule
                layers = self.cache.num_layers
                self._m_paged_pages.labels("read").inc(layers * pages_read(
                    [d - 1 for d in depths], self.cache.page_size,
                    self._pages_per_seq))
                self._m_paged_pages.labels("table").inc(
                    layers * ce.max_batch * self._pages_per_seq)

            if not (verify or resident):
                # another decode program, sent and waited for here
                self._sent_decode("in_step", self._sampler_path(active))
            if verify or resident:
                out_dev, io_next = self._dispatch_packed(
                    verify, active, ahead, lead, rows_a_slot)
                ahead = None
            elif self._mtp:
                with ph("dispatch.h2d", child=True):
                    tokens_dev = jnp.asarray(self._token_row(active))
                logits_dev, hidden_dev, self.cache = self._decode_hidden(
                    self.params, tokens_dev, self.cache)
                del tokens_dev
            elif self._paged:
                # CoW barrier first (shared write pages get private
                # copies), then one block-table-driven decode dispatch
                with ph("cache.cow", child=True):
                    self._cow_step(active)
                with ph("dispatch.h2d", child=True):
                    tokens_dev = jnp.asarray(self._token_row(active))
                    bt_dev = self._bt()
                logits_dev, self.cache = self._decode_paged(
                    self.params, tokens_dev, self.cache, bt_dev)
                del tokens_dev, bt_dev
            else:
                with ph("dispatch.h2d", child=True):
                    tokens_dev = jnp.asarray(self._token_row(active))
                logits_dev, self.cache = self._decode(
                    self.params, tokens_dev, self.cache)
                del tokens_dev
        with ph("device"):
            jax.block_until_ready(  # graftlint: disable=step-host-sync
                out_dev if resident or verify else logits_dev)
        dispatch_s = self.phases.seconds("dispatch")
        device_s = self.phases.seconds("device")

        with ph("sample"):
            if verify or resident:
                with ph("sample.fetch", child=True):
                    packed = np.asarray(out_dev)       # the one fetch
            if verify:
                toks = np.asarray(packed[:, :2])
                n_emit = np.asarray(packed[:, 2])
                finite_host = packed[:, 3] != 0
            elif resident:
                toks = packed[:, 0]
                finite_host = packed[:, 1] != 0
                if packed.shape[1] > 2:
                    qrows = np.ascontiguousarray(
                        packed[:, 2:]).view(np.float32)
            else:
                # fault injection: poison selected rows with NaN AFTER
                # the decode — other rows' values are untouched, so
                # healthy neighbors stay byte-identical to a fault-free
                # run (the resident path is gated off whenever fault
                # clauses exist)
                bad = self.faults.poison_rows(self._step_idx, active)
                if bad:
                    logits_dev = logits_dev.at[jnp.asarray(bad)].set(
                        jnp.nan)
                # logit_drift: a finite bias on ONE vocab column of the
                # drifted rows — argmax changes (silent wrong tokens at
                # full speed) while the isfinite health check below
                # stays green; only a golden-canary replay can notice
                drows, dbias = self.faults.drift_rows(self._step_idx,
                                                      active)
                if drows:
                    logits_dev = logits_dev.at[
                        jnp.asarray(drows), 0].add(dbias)

            # per-slot logits health check: a NaN/Inf row fails ONE
            # request (quarantine, structured error) while the rest of
            # the batch keeps decoding — blast-radius isolation for
            # numeric blowups
            if ce.logits_health_check:
                finite = finite_host
                if finite is None:
                    finite_dev = self._health(logits_dev)
                    with ph("sample.fetch", child=True):
                        finite = np.asarray(finite_dev)
                sick = [i for i in active if not bool(finite[i])]
                if sick:
                    for i in sick:
                        self._quarantine_slot(i, "nan_logits")
                    active = [i for i in active if i not in sick]

            simple_rows = [i for i in active if simple(self.slots[i])]
            complex_rows = [i for i in active
                            if not simple(self.slots[i])]
            picked_dev = None
            if resident or verify or not active:
                pass      # tokens already sampled inside the fused step
            elif simple_rows and all(
                    self.slots[i].req.params.temperature <= 0.0
                    for i in simple_rows):
                # all-greedy fast path: one fused argmax, no
                # sampling-param transfers (the default-traffic hot
                # path)
                picked_dev = self._argmax(logits_dev)
            elif simple_rows:
                # runs for EVERY batch containing a simple slot (not
                # only all-simple ones): a seeded request must sample
                # from the same stream whether or not a
                # penalties/logprobs request happens to share the batch
                with ph("sample.h2d", child=True):
                    puts = [jnp.asarray(a)
                            for a in self._sampling_arrays(simple_rows)]
                picked_dev = self._sample_device(logits_dev, *puts)
                del puts
            logits = None
            if picked_dev is not None or complex_rows:
                with ph("sample.fetch", child=True):
                    if picked_dev is not None:
                        toks = np.asarray(picked_dev)
                    if complex_rows:
                        logits = np.asarray(logits_dev)
            # the step's device outputs end here, inside a phase: the
            # release of their buffers is host time with an owner (left
            # to the frame's exit it fell between two phases)
            out_dev = finite_dev = logits_dev = None
            picked_dev = None
        if not active:          # every row was sick
            with ph("observe"):
                self._observe_step("decode", 0)
            return True

        def picks(i):
            """The token(s) this step gives slot i, in order: one, or
            the two a verify step kept."""
            if n_emit is not None:
                for j in range(int(n_emit[i])):
                    yield int(toks[i, j]), None
            elif simple(self.slots[i]):
                yield int(toks[i]), None
            else:
                yield self._sample_host(logits[i], self.slots[i])

        # collect traced requests BEFORE _check_done: a finishing
        # request's slot is freed (req=None, tracer entry closed)
        # inside it, and its final step still belongs on the timeline
        # — so capture the parent span id now, not at record time
        traced: Dict[str, Tuple[str, Optional[str]]] = {}
        step_qos: List[str] = []    # per-slot QoS for the SLO TPOT feed
        # (slot, tok, is_repeat, qos) captured BEFORE _check_done can
        # free the slot — the quality-telemetry feed for this step
        q_meta: List[Tuple[int, int, bool, str]] = []
        # tokens each stream was given this step, beside its QoS class
        step_tokens: List[int] = []
        with ph("emit"):
            for i in active:
                s = self.slots[i]
                had_draft = s.drafted
                given = 0
                # a stop token or max_tokens on the first token of two
                # drops the second (the slot is released with it)
                for tok, lp in picks(i):
                    repeat = bool(s.generated) and s.generated[-1] == tok
                    s.last_token = tok
                    s.generated.append(tok)
                    r = s.req
                    if given == 0 and r is not None:
                        step_qos.append(r.params.qos or "standard")
                    if r is not None and self._use_quality \
                            and n_emit is None:
                        q_meta.append((i, tok, repeat,
                                       r.params.qos or "standard"))
                    if r is not None and r.trace is not None:
                        sp = self.tracer.get(r.request_id)
                        traced.setdefault(
                            r.trace[0],
                            (r.request_id,
                             sp.trace_span if sp is not None else None))
                    given += 1
                    self._emit(s, lp)
                    if self._check_done(i):
                        break
                step_tokens.append(given)
                if n_emit is not None:
                    s.drafted = True
                    if had_draft:
                        self._m_mtp_drafts.labels(
                            "accepted" if n_emit[i] == 2
                            else "rejected").inc()
            if self._mtp:
                self._m_mtp_slot_steps.labels(
                    "verify" if verify else "plain").inc(len(active))
                if verify:
                    judged = [int(n_emit[i]) - 1 for i in active]
                    if judged:
                        self._m_spec_accept.observe(
                            sum(judged) / len(judged))
                else:
                    # the plain step of a speculating engine: the MTP
                    # module's row of each slot that goes on, now that
                    # its token is known (one more dispatch a step)
                    going = [i for i in active if self.slots[i].active]
                    if going:
                        self._mtp_rows_after(going, hidden_dev)
                hidden_dev = None
            self._keep_io(io_next, active)
            io_next = None
        if self._ahead is None:
            self._send_ahead()
        with ph("observe"):
            # live quality telemetry: resident steps hand over the
            # fused [B, 3] block (zero extra dispatches); host-sampled
            # steps reuse the logits array that the complex rows
            # already pulled. Simple-row non-resident batches keep
            # their logits on-device — telemetry never adds a transfer
            # the step didn't make.
            if q_meta:
                with ph("observe.quality", child=True):
                    if qrows is None and logits is not None:
                        qrows = self._host_quality_rows(logits, q_meta)
                    if qrows is not None:
                        self._quality_observe(qrows, q_meta)
            self._observe_tokens(
                read_ahead, t_decode0, t_wall0, dispatch_s, device_s,
                step_qos, step_tokens, traced,
                len(active) * rows_a_slot, perf_seq_len)
            self._observe_step("decode", len(active))
        return True

    def _observe_tokens(self, read_ahead: bool, t_decode0: float,
                        t_wall0: float, dispatch_s: float, device_s: float,
                        step_qos: List[str], step_tokens: List[int],
                        traced, rows: int, perf_seq_len: int) -> None:
        """The close of a step that decoded (a plain or a verify step,
        or a block family's pass), inside its observe phase: the SLO's
        time per output token for each stream's tokens, the step-time
        averages, the staged roofline sample over the `rows` the step
        computed, and one `decode_step` span a traced request."""
        ph = self.phases.phase
        with ph("observe.slo", child=True):
            # one batched step advances every active stream by the
            # tokens it was given (one; one or two of a verify
            # step), so a stream's time-per-output-token is the
            # step's wall time over them. A step that went out
            # ahead ran under the last step's emit and observe: what
            # its tokens took is the time since that step's were
            # timed, not the rest of the wait this step saw
            now = time.perf_counter()
            span_s = now - t_decode0
            dt = now - self._t_step_timed if read_ahead else span_s
            self._t_step_timed = now
            # each stream's TPOT samples for its QoS class, one a
            # token
            for q, n in zip(step_qos, step_tokens):
                for _ in range(n):
                    self.slo.observe_tpot(q, dt / n)
            # the queue-wait admission test's estimate: every step
            self._tpot_ewma = stats_ewma(self._tpot_ewma or None, dt)
            # the brownout latency-inflation signal: EWMA over its
            # observed floor, of the steps that measure the decode
            # alone. A decode dispatched behind an admission chunk
            # still in flight measures the chunk too, i.e. how long
            # the prompt is (a chunk is 3 decodes at 7B, PERF.md PR
            # 26); a last chunk was waited for before the decode
            # went out (no step is sent ahead behind a last chunk:
            # `_may_lead`). A sample counts for no more than the
            # ratio at which the signal saturates: one step of many
            # floors (an executable's first load) is not inflation,
            # a run of them still fills the signal
            if self._admitting is None:
                self._decode_ewma = stats_ewma(
                    self._decode_ewma or None,
                    min(dt, _INFLATION_SATURATES
                        * (self._decode_floor or dt)))
                if (self._decode_floor is None
                        or self._decode_ewma < self._decode_floor):
                    self._decode_floor = self._decode_ewma
            self._dispatch_ewma = stats_ewma(
                self._dispatch_ewma or None, dispatch_s)
        # stage the roofline/sentinel sample for step() to finalize
        # with the FULL step wall time (fault sleeps happen before
        # this method's timing bracket)
        # rows the step computed (both of a verify step's), not
        # tokens given
        self._pending_perf = (rows, perf_seq_len)
        # one decode_step span per distinct trace among active slots
        with ph("observe.spans", child=True):
            for tid, (rid, parent_sid) in traced.items():
                self.spans.record(
                    "decode_step", tid,
                    parent_id=parent_sid,
                    t_start=t_wall0, t_end=t_wall0 + span_s,
                    step=self._step_idx, request_id=rid,
                    dispatch_ms=round(dispatch_s * 1000.0, 3),
                    device_ms=round(device_s * 1000.0, 3))

    def _block_step(self, active: List[int]) -> bool:
        """One pass over the block of each of the ``active`` slots of a
        family that generates by diffusion over blocks: this family's
        plain step on the phase clock. The pass is `engine_block_
        resident` (`_init_block_step`); like the plain resident step it
        takes all it needs from what the pass before left on the device
        and goes out one pass ahead (`_ahead`, `_may_lead`). The host
        reads which rows became final, streams the longest run of final
        tokens that continues what each stream was sent (zero to `B`
        tokens an event, in sequence order whatever order they were
        committed in), and counts a slot's block on when the pass
        stored it."""
        ce = self.cfg_engine
        ph = self.phases.phase
        b = self._block.length
        ahead, active, lead = self._take_ahead(active)
        read_ahead = ahead is not None
        t_decode0 = time.perf_counter()
        t_wall0 = time.time()
        with ph("dispatch"):
            # mean live cache depth for the roofline sample: a pass
            # reads up to its block's last row
            perf_seq_len = max(1, sum(self.slots[i].block.pos + b
                                      for i in active) // len(active))
            out_dev, io_next = self._dispatch_packed(False, active, ahead,
                                                     lead, b)
            ahead = None
        with ph("device"):
            jax.block_until_ready(  # graftlint: disable=step-host-sync
                out_dev)
        dispatch_s = self.phases.seconds("dispatch")
        device_s = self.phases.seconds("device")
        with ph("sample"):
            with ph("sample.fetch", child=True):
                packed = np.asarray(out_dev)           # the one fetch
            out_dev = None
            if ce.logits_health_check:
                sick = [i for i in active if packed[i, b + 1] == 0]
                for i in sick:
                    self._quarantine_slot(i, "nan_logits")
                active = [i for i in active if i not in sick]
        if not active:          # every row was sick
            with ph("observe"):
                self._observe_step("decode", 0)
            return True
        traced: Dict[str, Tuple[str, Optional[str]]] = {}
        step_qos: List[str] = []
        step_tokens: List[int] = []
        with ph("emit"):
            for i in active:
                s = self.slots[i]
                at, r = s.block, s.req
                at.passes += 1
                if r.trace is not None:
                    sp = self.tracer.get(r.request_id)
                    traced.setdefault(
                        r.trace[0],
                        (r.request_id,
                         sp.trace_span if sp is not None else None))
                if packed[i, b]:
                    # the storing pass: the block's K/V stand, the next
                    # block begins all MASK
                    self._m_block_passes.labels("store").inc()
                    self._m_blocks.inc()
                    s.block = _BlockState(at.pos + b, [-1] * b, [0] * b, 0,
                                          passes=at.passes)
                    if at.pos + 2 * b > ce.max_seq:
                        self._finish(i, "length")   # the slab's end
                    continue
                self._m_block_passes.labels("denoise").inc()
                at.s += 1
                new = [j for j in range(b)
                       if at.ids[j] < 0 and packed[i, j] >= 0]
                for j in new:
                    at.ids[j] = int(packed[i, j])
                    at.steps[j] = at.passes
                self._m_block_tokens.inc(len(new))
                run = at.sent
                while run < b and at.ids[run] >= 0:
                    run += 1
                if run > at.sent:
                    lo, at.sent = at.sent, run
                    n = self._emit_run(i, at.ids[lo:run], at.steps[lo:run])
                    step_qos.append(r.params.qos or "standard")
                    step_tokens.append(n)
            self._keep_io(io_next, active)
            io_next = None
        if self._ahead is None:
            self._send_ahead()
        with ph("observe"):
            # (a pass advances a stream by the tokens its event carried)
            self._observe_tokens(
                read_ahead, t_decode0, t_wall0, dispatch_s, device_s,
                step_qos, step_tokens, traced, len(active) * b,
                perf_seq_len)
            self._observe_step("decode", len(active))
        return True

    def _flight_step(self, phase: str, n_active: int) -> None:
        """One structured flight-recorder event per working step: what
        the engine was doing, with how many streams, against what
        backlog — the per-step breadcrumb trail a postmortem replays."""
        self.flight.record(
            "step", step=self._step_idx, phase=phase,
            occupancy=n_active, queue_depth=len(self.waiting),
            cp_queue_depth=len(self._cp_waiting),
            admitting=self._admitting is not None,
            stall_steps=self._stall_steps)

    # -- convenience: blocking one-shot generation --------------------------

    def generate(self, prompts: List[List[int]],
                 params: Optional[SamplingParams] = None) -> List[List[int]]:
        """Batch-generate (the reference's offline `LLM.generate` analog)."""
        ids = [f"gen-{i}" for i in range(len(prompts))]
        for rid, p in zip(ids, prompts):
            self.add_request(rid, p, params)
        done: Dict[str, List[int]] = {rid: [] for rid in ids}
        finished: set = set()
        while len(finished) < len(ids):
            if not self.step():
                time.sleep(0.001)
            for rid in ids:
                for out in self.get_outputs(rid):
                    done[rid].extend(out.new_token_ids)
                    if out.finished:
                        finished.add(rid)
        return [done[rid] for rid in ids]
