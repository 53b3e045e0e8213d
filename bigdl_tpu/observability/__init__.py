"""Dependency-free metrics, tracing, compile telemetry, memory
accounting and postmortems.

Five pieces, all stdlib-only at import time (jax is allowed elsewhere
in the package but this subpackage must import with nothing beyond the
standard library — tests/test_observability.py enforces it):

- ``metrics``: Counter / Gauge / Histogram registry with labels and
  Prometheus text exposition (``MetricsRegistry.render()``). The
  serving engine, speculative decoders, kernel probes and StepTimer all
  publish here; ``GET /metrics`` on the API server renders the
  engine's registry.
- ``tracing``: per-request lifecycle spans (queue wait, prefill, TTFT,
  decode/TPOT, preemptions) kept in a ring buffer and optionally
  appended as JSONL to ``$BIGDL_TPU_EVENT_LOG`` (size-rotated at
  ``$BIGDL_TPU_EVENT_LOG_MAX_BYTES``, keeping
  ``$BIGDL_TPU_EVENT_LOG_KEEP`` rolled files ``.1`` .. ``.N``);
  ``GET /v1/stats`` serves the snapshot. ``PhaseClock`` is the
  engine step's one instrument: each part of ``LLMEngine.step`` is a
  profiler span (``engine.<phase>``, trace-only children ``cache.*``,
  ``observe.*``, ``admission.wait``, the puts ``*.h2d`` and the reads
  ``sample.fetch``) and a share of one
  ``bigdl_tpu_step_phase_seconds{phase, kind}`` sample per step,
  ``kind`` saying whether the step dispatched a prefill chunk.
- ``disttrace``: fleet-wide distributed tracing — W3C-style
  ``traceparent`` propagation (router -> replica -> engine -> KV-handoff
  target), a thread-safe ``SpanRecorder`` of completed spans per
  process (JSONL sink at ``$BIGDL_TPU_EVENT_LOG`` + ``.spans``, same
  rotation policy), deterministic tail sampling via
  ``$BIGDL_TPU_TRACE_SAMPLE``, and ``merge_timeline`` — the
  clock-skew-adjusted stitch behind the router's
  ``GET /v1/trace/{trace_id}``.
- ``compile_watch``: ``tracked_jit(name, fn, ...)`` — jax.jit plus
  compile accounting (count, wall time, abstract-shape signature per
  executable) feeding the jit metrics below, a process-wide
  ``compile_table()``, and a recompile-storm warning past
  ``$BIGDL_TPU_RECOMPILE_WARN`` compiles per name. It also keeps the
  START-UP ACCOUNT: every first call by stage (trace, lower, compile
  or cache load, memory analysis, first run) from JAX's own monitoring
  events, booked to the program open on the calling thread or to
  ``fn="untracked"``, a ``compile.<fn>`` span, the timeline
  ``startup_snapshot()`` serves (``/v1/stats`` ``startup``, postmortem
  dumps) and the start-up marks on the process's own clock
  (``mark()``, ``process_age_s()``).
- ``memory``: ``MemoryLedger`` — exact static HBM accounting
  (packed weight / KV-cache / adapter bytes registered at build and
  allocation time) plus live ``device.memory_stats()`` telemetry
  (``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit``, a no-op
  ``{}`` on CPU/interpret), a ``headroom()`` budget view driven by
  ``$BIGDL_TPU_HBM_BUDGET_FRACTION``, and ``would_fit(nbytes)`` — the
  predicate behind the serving engine's headroom-aware admission
  (deferral shows up as ``bigdl_tpu_admission_deferred_total``, an
  ``admit_deferred`` flight event, and ``GET /v1/memory``).
  ``memory_report()`` rolls the snapshot plus the compile table's peak
  temp bytes into the bench JSON records.
- ``roofline``: the analytical FLOPs / HBM-bytes cost model (single
  source for the offline efficiency block, the engine's live
  ``bigdl_tpu_roofline_util{phase}`` / ``decode_ideal_ms`` gauges and
  compile_watch's per-jit cost annotation). Chip peaks come from
  ``roofline.CHIP_PEAKS``, keyed by the device kind JAX reports; an
  unknown kind exports no roofline gauges.
- ``sentinel``: ``PerfSentinel`` — dwell-gated perf-regression
  detection over decode ms/token, roofline util and dispatch overhead
  EWMAs vs a rolling baseline persisted at ``$BIGDL_TPU_PERF_HISTORY``
  (size-rotated like the event log); trips emit ``perf_regression``
  flight events + postmortems + a bounded profiler auto-capture, then
  recover with hysteresis.
- ``stats``: the shared percentile / median / EWMA math (single source
  for StepTimer summaries, sentinel baseline seeding and bench lane
  stats; ``percentile`` is bit-compatible with ``np.percentile``'s
  default linear method).
- ``slo``: declarative per-QoS service-level objectives (TTFT p99,
  TPOT p99, error rate, availability; defaults overridden by JSON in
  ``$BIGDL_TPU_SLO_SPEC``) evaluated against multi-window sliding
  histograms with Google-SRE fast/slow burn-rate alerting — alerts
  emit ``slo_burn`` flight events,
  ``bigdl_tpu_slo_burn_rate{qos,objective,window}`` gauges,
  ``bigdl_tpu_slo_alerts_total`` and a size-rotated JSONL sink at
  ``$BIGDL_TPU_SLO_ALERT_LOG``; ``GET /v1/slo`` serves the snapshot
  and the router aggregates it fleet-wide.
- ``usage``: per-tenant usage metering — one append-only JSONL record
  per finished/shed request (``$BIGDL_TPU_USAGE_LOG``, written off the
  engine thread) plus the live rollup behind ``GET /v1/usage``,
  reconciled exactly against the tenant counters.
- ``flight``: ``FlightRecorder`` ring buffer of per-step engine events
  plus postmortem dumps — on engine-step exception, stall-guard trip,
  or SIGTERM/SIGINT a single JSON (flight tail, span tail, metrics
  snapshot, compile table, config + env fingerprint) is written to
  ``$BIGDL_TPU_POSTMORTEM_DIR``; ``GET /v1/debug/dump`` serves the
  same dict on demand.

Metric name -> engine field map (see also serving/engine.py):

==========================================  ===============================
metric                                      source
==========================================  ===============================
bigdl_tpu_request_phase_seconds{phase=...}  RequestSpan decode;
                                            ingest: api_server handler
bigdl_tpu_step_phase_seconds{phase, kind}   tracing.PhaseClock in LLMEngine.step
                                            (kind=plain|chunk); RequestSpan
                                            queue_wait/prefill (kind=admission)
bigdl_tpu_prefill_chunks_total              LLMEngine._admission_step
bigdl_tpu_prefill_tokens_total{kind}        LLMEngine._admission_step
bigdl_tpu_decode_attn_blocks_total{kind}    LLMEngine._decode_step (slab K/V
                                            cache): decode_attention's
                                            blocks_read / slab_blocks
bigdl_tpu_paged_attn_pages_total{kind}      LLMEngine._decode_step (paged K/V
                                            cache): paged_decode_attention's
                                            pages_read / every table column
bigdl_tpu_stream_delivery_seconds           api_server stream handler
bigdl_tpu_ttft_seconds                      RequestSpan.ttft_s
bigdl_tpu_tpot_seconds{kind}                tracing.PhaseClock.end: wall of a
                                            step that decoded, step() entry
                                            to return, by kind
bigdl_tpu_engine_loop_seconds_total{state}  api_server._EngineLoop._run
bigdl_tpu_slot_occupancy                    len(LLMEngine._slots)
bigdl_tpu_queue_depth                       len(LLMEngine._queue)
bigdl_tpu_admissions_total                  LLMEngine._admission_step
bigdl_tpu_preemptions_total                 LLMEngine._preempt
bigdl_tpu_stall_guard_trips_total           LLMEngine._stall_steps trip
bigdl_tpu_requests_finished_total{reason}   LLMEngine._finish
bigdl_tpu_engine_steps_total                LLMEngine.step
bigdl_tpu_tokens_generated_total            LLMEngine._emit
bigdl_tpu_kernel_probe_total{kernel,...}    ops/probing.record_probe_result
bigdl_tpu_spec_accept_ratio{mode}           speculative._spec_observe (mode=
                                            draft | lookup, offline rounds);
                                            LLMEngine._decode_step (mode=mtp:
                                            accepted over judged drafts of a
                                            verify step)
bigdl_tpu_mtp_drafts_total{outcome}         LLMEngine._decode_step: drafts of
                                            the family's MTP module a verify
                                            step judged (n_emit 2 / 1)
bigdl_tpu_mtp_slot_steps_total{kind}        LLMEngine._decode_step of a
                                            speculating engine: verify (two
                                            rows a slot) | plain (one row)
bigdl_tpu_decode_steps_total{sent}          LLMEngine._decode_step, where a
                                            decode program is dispatched:
                                            ahead of the last step's read |
                                            in_step
bigdl_tpu_decode_steps_vain_total           LLMEngine._decode_step: a step sent
                                            ahead that no slot was read from
bigdl_tpu_sampler_steps_total{path}         LLMEngine._sent_decode, once a
                                            decode program: greedy | topk
                                            (sampled, no row sorted) |
                                            nucleus (a live top_p < 1: the
                                            sorted branch ran)
bigdl_tpu_block_passes_total{kind}          LLMEngine._block_step (a family
                                            that generates by diffusion over
                                            blocks), a slot's pass as the
                                            host reads it: denoise | store
bigdl_tpu_block_tokens_committed_total      LLMEngine._block_step: rows a
                                            denoise pass committed
bigdl_tpu_blocks_total                      LLMEngine._block_step: blocks
                                            stored
bigdl_tpu_spec_round_seconds{mode}          speculative._spec_observe
bigdl_tpu_spec_tokens_total{mode,kind}      speculative._spec_observe
bigdl_tpu_kv_cache_bytes{dtype,component}   ops/kvcache.publish_kv_cache_bytes
bigdl_tpu_kv_dequant_path_total{dtype,path} ops/attention._note_dequant_path
bigdl_tpu_jit_compiles_total{fn}            compile_watch.TrackedJit
bigdl_tpu_jit_compile_seconds{fn}           compile_watch.TrackedJit
bigdl_tpu_jit_stage_seconds_total{fn,stage} compile_watch.TrackedJit (a
                                            first call) and its listener
                                            (fn="untracked")
bigdl_tpu_compile_cache_requests_total{fn,outcome}  the same
bigdl_tpu_startup_mark_seconds{mark}        compile_watch.mark:
                                            LLMEngine.__init__ /
                                            add_request / _obs_first_token,
                                            OpenAIServer.serve, TrackedJit
bigdl_tpu_hbm_bytes{kind}                   memory.MemoryLedger.publish
bigdl_tpu_hbm_headroom_bytes                memory.MemoryLedger.publish
bigdl_tpu_admission_deferred_total{reason}  LLMEngine._admission_step
bigdl_tpu_requests_quarantined_total{reason} LLMEngine._quarantine_slot
bigdl_tpu_step_retries_total                LLMEngine._on_step_failure
bigdl_tpu_faults_injected_total{kind}       robustness.FaultInjector
bigdl_tpu_engine_draining                   LLMEngine.begin_drain
==========================================  ===============================

``bigdl_tpu_kv_cache_bytes`` reports the batched KV cache's logical
storage footprint split by component ("codes", "scales", "total" — int4
counts two codes per byte). ``bigdl_tpu_kv_dequant_path_total`` counts
how quantized attention dequantized: "fused" (inside the Pallas kernel)
vs "xla" (upcast fallback); increments happen at trace time, so read it
as "which path compiled", not a per-token rate.

``bigdl_tpu_jit_compiles_total{fn}`` counts jax.jit compiles per
tracked executable name (one per new abstract shape signature — e.g.
one per (prefill bucket, kv dtype) pair for ``engine_prefill``);
``bigdl_tpu_jit_compile_seconds{fn}`` holds the first-call wall time
of each. A steadily incrementing compile counter in steady state IS the
recompile-storm signature these exist to catch.
``bigdl_tpu_jit_stage_seconds_total{fn,stage}`` takes that wall time
apart, exclusive seconds by ``stage``: ``trace``, ``lower``,
``compile`` (a backend compile: the persistent cache missed),
``cache_load`` (the same bracket on a hit), ``memory_analysis`` (the
capture below, when asked for) and ``first_run`` (the call's wall less
the rest); ``bigdl_tpu_compile_cache_requests_total{fn,outcome=hit|
miss}`` counts what the persistent cache did. ``fn="untracked"`` holds
JAX's compile events on a thread with no tracked first call open
(eager ops, a model's own ``jax.jit``; default registry only). A warm
replica reads ``compile`` near 0 and ``hit`` for every ``engine_*``
program. ``bigdl_tpu_startup_mark_seconds{mark}`` is the process's age
(seconds since it started: ``CLOCK_BOOTTIME`` less ``/proc/self/stat``'s
start time, 10 ms; else since ``bigdl_tpu`` was imported) at
``engine_init_begin``, ``engine_init_end``, ``listening``,
``first_request``, ``first_token`` (each set once) and
``last_compile_end`` (moved by every first call); ``/health`` carries
``age_s`` and ``first_token_s`` from the same clock. With
``BIGDL_TPU_COMPILE_MEMORY=1`` each first compile also captures
``compiled.memory_analysis()`` (temp/argument/output bytes) via an AOT
lower+compile of the same signature: off by default since PR 55, for
it can lower and compile or load every program a second time on the
way to readiness (booked as ``stage="memory_analysis"``).

``bigdl_tpu_hbm_bytes{kind}`` carries both the ledger's static sums
per kind ("weights", "kv_cache", ...) and the device telemetry rows
("device_in_use", "device_peak", "device_limit" — absent without a
real accelerator). ``bigdl_tpu_hbm_headroom_bytes`` is
``budget_fraction * bytes_limit - bytes_in_use``; when an admission's
KV-cache cost exceeds it the request stays queued and
``bigdl_tpu_admission_deferred_total{reason="memory"}`` increments.

Environment knobs: ``BIGDL_TPU_EVENT_LOG`` (span JSONL sink) +
``BIGDL_TPU_EVENT_LOG_MAX_BYTES`` (rotate past this size) +
``BIGDL_TPU_EVENT_LOG_KEEP`` (rotated files retained, default 1),
``BIGDL_TPU_TRACE_SAMPLE`` (distributed-trace tail-sampling fraction,
default 1.0),
``BIGDL_TPU_POSTMORTEM_DIR`` (where crash/stall/signal dumps land),
``BIGDL_TPU_RECOMPILE_WARN`` (compiles-per-name warning threshold,
default 8), ``BIGDL_TPU_HBM_BUDGET_FRACTION`` (admission budget as a
fraction of ``bytes_limit``, float in (0, 1], default 0.9),
``BIGDL_TPU_MEMORY_POLL_SEC`` (min seconds between live
``memory_stats()`` reads, default 1.0), ``BIGDL_TPU_COMPILE_MEMORY``
(set 1 for per-compile memory analysis; default off),
``BIGDL_TPU_SLO_SPEC`` (JSON SLO spec override),
``BIGDL_TPU_SLO_ALERT_LOG`` (burn-alert JSONL sink),
``BIGDL_TPU_USAGE_LOG`` (per-request usage ledger). All are validated
by ``python -m bigdl_tpu.utils.env_check``.
"""

from bigdl_tpu.observability.compile_watch import (
    TrackedJit,
    annotate_costs,
    compile_table,
    reset_compile_table,
    resolve_recompile_threshold,
    top_offenders,
    tracked_jit,
)
from bigdl_tpu.observability.flight import (
    FlightRecorder,
    build_postmortem,
    env_fingerprint,
    install_signal_dumps,
    validate_postmortem_dir,
    write_postmortem,
)
from bigdl_tpu.observability.memory import (
    MemoryLedger,
    default_ledger,
    device_memory_stats,
    memory_report,
    reset_default_ledger,
    resolve_hbm_budget_fraction,
    resolve_memory_poll_sec,
    tree_nbytes,
)
from bigdl_tpu.observability.metrics import (
    LATENCY_BUCKETS_S,
    RATIO_BUCKETS,
    MetricFamily,
    MetricsRegistry,
    default_registry,
)
from bigdl_tpu.observability.disttrace import (
    SpanRecorder,
    make_traceparent,
    merge_timeline,
    new_span_id,
    new_trace_id,
    parse_traceparent,
    resolve_trace_sample,
    trace_sampled,
)
from bigdl_tpu.observability.tracing import (
    PhaseClock,
    RequestSpan,
    RequestTracer,
    resolve_event_log_keep,
    resolve_event_log_max_bytes,
    rotate_event_log,
    validate_event_log_path,
)
from bigdl_tpu.observability.roofline import (
    attn_flops_per_token,
    chip_peaks,
    decode_costs,
    jit_costs,
    kv_bytes_per_token,
    model_flops_per_token,
    prefill_costs,
)
from bigdl_tpu.observability.roofline import (
    attribution as roofline_attribution,
    efficiency as roofline_efficiency,
)
from bigdl_tpu.observability.slo import (
    DEFAULT_OBJECTIVES,
    OBJECTIVES,
    SLOTracker,
    SlidingHistogram,
    resolve_slo_alert_log,
    resolve_slo_spec,
    validate_slo_alert_log_path,
)
from bigdl_tpu.observability.stats import (
    EWMA_DECAY,
    ewma,
    median,
    percentile,
    summarize,
)
from bigdl_tpu.observability.usage import (
    UsageLedger,
    resolve_usage_log,
    validate_usage_log_path,
)
from bigdl_tpu.observability.sentinel import (
    PerfSentinel,
    resolve_perf_history,
    resolve_sentinel_recover_steps,
    resolve_sentinel_threshold,
    resolve_sentinel_trip_steps,
    validate_perf_history_path,
)

__all__ = [
    "LATENCY_BUCKETS_S",
    "RATIO_BUCKETS",
    "MetricFamily",
    "MetricsRegistry",
    "default_registry",
    "PhaseClock",
    "RequestSpan",
    "RequestTracer",
    "resolve_event_log_keep",
    "resolve_event_log_max_bytes",
    "rotate_event_log",
    "validate_event_log_path",
    "SpanRecorder",
    "make_traceparent",
    "merge_timeline",
    "new_span_id",
    "new_trace_id",
    "parse_traceparent",
    "resolve_trace_sample",
    "trace_sampled",
    "TrackedJit",
    "tracked_jit",
    "annotate_costs",
    "top_offenders",
    "compile_table",
    "reset_compile_table",
    "resolve_recompile_threshold",
    "MemoryLedger",
    "default_ledger",
    "device_memory_stats",
    "memory_report",
    "reset_default_ledger",
    "resolve_hbm_budget_fraction",
    "resolve_memory_poll_sec",
    "tree_nbytes",
    "FlightRecorder",
    "build_postmortem",
    "env_fingerprint",
    "install_signal_dumps",
    "validate_postmortem_dir",
    "write_postmortem",
    "attn_flops_per_token",
    "chip_peaks",
    "decode_costs",
    "jit_costs",
    "kv_bytes_per_token",
    "model_flops_per_token",
    "prefill_costs",
    "roofline_attribution",
    "roofline_efficiency",
    "DEFAULT_OBJECTIVES",
    "OBJECTIVES",
    "SLOTracker",
    "SlidingHistogram",
    "resolve_slo_alert_log",
    "resolve_slo_spec",
    "validate_slo_alert_log_path",
    "EWMA_DECAY",
    "ewma",
    "median",
    "percentile",
    "summarize",
    "UsageLedger",
    "resolve_usage_log",
    "validate_usage_log_path",
    "PerfSentinel",
    "resolve_perf_history",
    "resolve_sentinel_recover_steps",
    "resolve_sentinel_threshold",
    "resolve_sentinel_trip_steps",
    "validate_perf_history_path",
]
