"""Per-request lifecycle tracing for the serving path.

Each request the engine touches gets a ``RequestSpan`` recording the
timestamps the serving metrics are computed from:

    t_enqueued  -> t_admitted          queue wait
                   (bigdl_tpu_step_phase_seconds{phase="queue_wait"})
    t_admitted  -> t_first_token       prefill latency ({phase="prefill"})
    t_arrival   -> t_first_token       TTFT (bigdl_tpu_ttft_seconds)
    t_first_token -> t_finished        decode phase
                   (bigdl_tpu_request_phase_seconds{phase="decode"})
    decode phase / tokens              TPOT (PhaseClock observes the wall
                                       of every step that decoded into
                                       bigdl_tpu_tpot_seconds{kind})

plus discrete events (``preempt``, ``resume``, ``finish``) with their
own timestamps. Spans live in the tracer's in-memory ring buffer
(``GET /v1/stats`` serves them) and, when an event-log path is
configured — explicitly or via ``BIGDL_TPU_EVENT_LOG`` — every event is
appended to a JSONL file for offline analysis.

``PhaseClock`` is the engine step's one instrument: ``phase(name)``
opens a profiler span, reads the host clock at both ends and adds the
duration to the step's total for ``name``; ``end()`` observes each
total once into ``bigdl_tpu_step_phase_seconds{phase=<name>, kind}``,
``kind`` saying whether the step carried a prefill chunk.

Stdlib-only by design (see observability/metrics.py): the profiler's
annotation factory is handed in by the engine.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple


def resolve_event_log_max_bytes(value=None):
    """Size bound for the JSONL sink: explicit value, else
    ``$BIGDL_TPU_EVENT_LOG_MAX_BYTES``, else None (unbounded). Raises
    ValueError on a non-positive or non-integer setting
    (utils/env_check.py surfaces this for the env var)."""
    if value is None:
        value = os.environ.get("BIGDL_TPU_EVENT_LOG_MAX_BYTES")
    if value is None or value == "":
        return None
    try:
        n = int(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"event log size limit must be a positive integer, got "
            f"{value!r}")
    if n <= 0:
        raise ValueError(
            f"event log size limit must be a positive integer, got {n}")
    return n


def resolve_event_log_keep(value=None) -> int:
    """How many rotated event-log files to keep: explicit value, else
    ``$BIGDL_TPU_EVENT_LOG_KEEP``, else 1 (the pre-existing single
    ``.1`` rollover). Raises ValueError on a non-positive or
    non-integer setting (utils/env_check.py surfaces this for the env
    var; the tracer itself degrades to the default)."""
    if value is None:
        value = os.environ.get("BIGDL_TPU_EVENT_LOG_KEEP")
    if value is None or value == "":
        return 1
    try:
        n = int(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"event log keep count must be a positive integer, got "
            f"{value!r}")
    if n <= 0:
        raise ValueError(
            f"event log keep count must be a positive integer, got {n}")
    return n


def rotate_event_log(path: str, keep: int) -> None:
    """Cascade ``path.{keep-1}`` -> ``path.{keep}``, ...,
    ``path`` -> ``path.1``. With ``keep`` files retained plus the live
    one, total disk footprint stays bounded at ~``(keep + 1)`` x the
    rotation limit. Missing intermediates are skipped (a fresh deploy
    with keep=5 has no ``.3`` yet)."""
    for i in range(keep - 1, 0, -1):
        src = f"{path}.{i}"
        if os.path.exists(src):
            os.replace(src, f"{path}.{i + 1}")
    os.replace(path, path + ".1")


def validate_event_log_path(path: str) -> dict:
    """Report whether `path` is usable as a JSONL event-log sink
    (utils/env_check.py surfaces this for BIGDL_TPU_EVENT_LOG)."""
    out = {"path": path}
    d = os.path.dirname(os.path.abspath(path)) or "."
    if not os.path.isdir(d):
        out["writable"] = False
        out["error"] = f"directory {d!r} does not exist"
    elif os.path.exists(path) and not os.access(path, os.W_OK):
        out["writable"] = False
        out["error"] = f"{path!r} exists and is not writable"
    elif not os.path.exists(path) and not os.access(d, os.W_OK):
        out["writable"] = False
        out["error"] = f"directory {d!r} is not writable"
    else:
        out["writable"] = True
    return out


@dataclasses.dataclass
class RequestSpan:
    """Lifecycle timestamps for one engine-level request (n/best_of
    fan-out children are separate sequences and get separate spans)."""
    request_id: str
    prompt_len: int = 0
    t_arrival: float = 0.0
    t_enqueued: float = 0.0          # re-set on preemption (re-queue)
    t_admitted: Optional[float] = None
    t_first_token: Optional[float] = None
    t_finished: Optional[float] = None
    finish_reason: Optional[str] = None
    n_generated: int = 0
    n_preemptions: int = 0
    events: List[Tuple[float, str]] = dataclasses.field(
        default_factory=list)
    # distributed-trace context (observability/disttrace.py): the fleet
    # trace id, the upstream parent span id, and this request's own
    # engine-side span id — None for untraced/unsampled requests
    trace_id: Optional[str] = None
    trace_parent: Optional[str] = None
    trace_span: Optional[str] = None

    # -- derived durations (None until the span reaches that point) --------

    @property
    def queue_wait_s(self) -> Optional[float]:
        if self.t_admitted is None:
            return None
        return self.t_admitted - self.t_enqueued

    @property
    def prefill_s(self) -> Optional[float]:
        if self.t_admitted is None or self.t_first_token is None:
            return None
        return self.t_first_token - self.t_admitted

    @property
    def ttft_s(self) -> Optional[float]:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_arrival

    @property
    def decode_s(self) -> Optional[float]:
        if self.t_first_token is None or self.t_finished is None:
            return None
        return self.t_finished - self.t_first_token

    @property
    def tpot_s(self) -> Optional[float]:
        d = self.decode_s
        if d is None or self.n_generated <= 1:
            return None
        return d / (self.n_generated - 1)

    def to_dict(self) -> dict:
        out = {
            "request_id": self.request_id,
            "prompt_len": self.prompt_len,
            "t_arrival": self.t_arrival,
            "n_generated": self.n_generated,
            "n_preemptions": self.n_preemptions,
            "finish_reason": self.finish_reason,
        }
        if self.trace_id is not None:
            out["trace_id"] = self.trace_id
        for k in ("queue_wait_s", "prefill_s", "ttft_s", "decode_s",
                  "tpot_s"):
            v = getattr(self, k)
            if v is not None:
                out[k] = round(v, 6)
        out["events"] = [(round(t, 6), kind) for t, kind in self.events]
        return out


class RequestTracer:
    """Thread-safe span store: active spans by request id plus a ring
    buffer of finished spans; optional JSONL event sink."""

    def __init__(self, capacity: int = 256,
                 event_log_path: Optional[str] = None,
                 event_log_max_bytes: Optional[int] = None,
                 event_log_keep: Optional[int] = None):
        if event_log_path is None:
            event_log_path = os.environ.get("BIGDL_TPU_EVENT_LOG")
        self._lock = threading.Lock()
        self._active: Dict[str, RequestSpan] = {}
        self._finished: "collections.deque[RequestSpan]" = \
            collections.deque(maxlen=capacity)
        self._sink_path = event_log_path or None
        self._sink = None
        self._sink_dead = False
        # size-bounded rotation: when the sink would grow past the
        # limit the rotated files cascade (`.1` -> `.2` -> ... up to
        # $BIGDL_TPU_EVENT_LOG_KEEP files) and a fresh file is started
        # — total disk footprint is bounded at ~(keep + 1)x the limit
        if event_log_max_bytes is None:
            try:
                event_log_max_bytes = resolve_event_log_max_bytes()
            except ValueError:
                # env_check reports the bad value; the tracer itself
                # degrades to an unbounded sink rather than dying
                event_log_max_bytes = None
        if event_log_keep is None:
            try:
                event_log_keep = resolve_event_log_keep()
            except ValueError:
                event_log_keep = 1     # env_check reports the bad value
        self._sink_max_bytes = event_log_max_bytes
        self._sink_keep = event_log_keep
        self._sink_bytes = 0

    # -- JSONL sink ---------------------------------------------------------

    def _log(self, request_id: str, event: str, **data) -> None:
        if self._sink_path is None or self._sink_dead:
            return
        line = {"ts": round(time.time(), 6), "request_id": request_id,
                "event": event}
        line.update(data)
        try:
            # handler threads and the engine thread both log: the
            # open/rotate/write sequence must be atomic or a rotation
            # can race a write into a closed file
            with self._lock:
                if self._sink is None:
                    self._sink = open(self._sink_path, "a", buffering=1)
                    try:
                        self._sink_bytes = os.path.getsize(
                            self._sink_path)
                    except OSError:
                        self._sink_bytes = 0
                payload = json.dumps(line) + "\n"
                if (self._sink_max_bytes is not None and self._sink_bytes
                        and self._sink_bytes + len(payload)
                        > self._sink_max_bytes):
                    self._sink.close()
                    rotate_event_log(self._sink_path, self._sink_keep)
                    self._sink = open(self._sink_path, "a", buffering=1)
                    self._sink_bytes = 0
                self._sink.write(payload)
                self._sink_bytes += len(payload)
        except OSError as e:
            # one warning, then the sink stays off — tracing must never
            # take the serving loop down
            self._sink_dead = True
            import logging

            logging.getLogger(__name__).warning(
                "event log %s unwritable (%s); JSONL tracing disabled",
                self._sink_path, e)

    def close(self) -> None:
        with self._lock:
            if self._sink is not None:
                try:
                    self._sink.close()
                except OSError:
                    pass
                self._sink = None

    # -- lifecycle ----------------------------------------------------------

    def start(self, request_id: str, prompt_len: int = 0,
              t_arrival: Optional[float] = None,
              trace: Optional[Tuple[str, str, str]] = None) -> RequestSpan:
        """``trace`` is the distributed-trace context
        ``(trace_id, parent_span_id, own_span_id)`` threaded in by the
        engine for requests that arrived with a ``traceparent``."""
        now = time.time()
        span = RequestSpan(request_id, prompt_len,
                           t_arrival=t_arrival or now,
                           t_enqueued=t_arrival or now)
        if trace is not None:
            span.trace_id, span.trace_parent, span.trace_span = trace
        span.events.append((span.t_arrival, "enqueue"))
        with self._lock:
            self._active[request_id] = span
        self._log(request_id, "enqueue", prompt_len=prompt_len,
                  **self._trace_fields(span))
        return span

    @staticmethod
    def _trace_fields(span: Optional["RequestSpan"]) -> dict:
        if span is None or span.trace_id is None:
            return {}
        return {"trace_id": span.trace_id}

    def get(self, request_id: str) -> Optional[RequestSpan]:
        with self._lock:
            return self._active.get(request_id)

    def admitted(self, request_id: str) -> Optional[RequestSpan]:
        now = time.time()
        span = self.get(request_id)
        if span is not None:
            span.t_admitted = now
            span.events.append((now, "admit"))
            self._log(request_id, "admit",
                      queue_wait_s=round(now - span.t_enqueued, 6),
                      **self._trace_fields(span))
        return span

    def first_token(self, request_id: str) -> Optional[RequestSpan]:
        now = time.time()
        span = self.get(request_id)
        if span is not None and span.t_first_token is None:
            span.t_first_token = now
            span.events.append((now, "first_token"))
            self._log(request_id, "first_token",
                      ttft_s=round(now - span.t_arrival, 6),
                      **self._trace_fields(span))
        return span

    def preempted(self, request_id: str) -> Optional[RequestSpan]:
        """Victim evicted back to the queue: the next admit's queue wait
        counts from NOW, not from arrival."""
        now = time.time()
        span = self.get(request_id)
        if span is not None:
            span.n_preemptions += 1
            span.t_enqueued = now
            span.t_admitted = None
            span.events.append((now, "preempt"))
            self._log(request_id, "preempt",
                      **self._trace_fields(span))
        return span

    def finish(self, request_id: str, reason: str,
               n_generated: int = 0) -> Optional[RequestSpan]:
        now = time.time()
        with self._lock:
            span = self._active.pop(request_id, None)
        if span is not None:
            span.t_finished = now
            span.finish_reason = reason
            span.n_generated = n_generated
            span.events.append((now, "finish"))
            with self._lock:
                self._finished.append(span)
            self._log(request_id, "finish", reason=reason,
                      n_generated=n_generated,
                      **self._trace_fields(span))
        return span

    # -- introspection ------------------------------------------------------

    def snapshot(self, recent: int = 32) -> dict:
        with self._lock:
            active = [s.to_dict() for s in self._active.values()]
            done = [s.to_dict() for s in
                    list(self._finished)[-max(recent, 0):]]
        return {"active": active, "recent": done}


# -- the engine step's phase clock -------------------------------------------

# Labels of bigdl_tpu_step_phase_seconds that PhaseClock.end() observes,
# by population: one sample on every working step (what
# bigdl_tpu_engine_steps_total counts), or one on every step that
# decoded. ``cache``, ``h2d`` and ``fetch`` are derived: each the sum of
# the step's child spans that _CHILD_LABEL gives it; so is ``host``: the
# step's wall less ``device``.
WORKING_STEP_PHASES = ("sweep", "admission", "observe", "cache", "h2d",
                       "fetch")
DECODE_STEP_PHASES = ("dispatch", "device", "sample", "emit", "host")
# What a step carried: ``chunk`` when a prefill program was dispatched
# in it (PhaseClock.mark_chunk), else ``plain``. The samples the engine
# takes once an admission into the same histogram (queue_wait, prefill)
# are no step's: they carry ADMISSION_KIND.
STEP_KINDS = PLAIN, CHUNK = ("plain", "chunk")
ADMISSION_KIND = "admission"
# trace-only child spans that also add to a derived label: every
# ``cache.*`` by its head, a transfer in and a fetch out by name
_CHILD_LABEL = {"cache": "cache",
                "dispatch.h2d": "h2d", "admission.h2d": "h2d",
                "sample.h2d": "h2d",
                "sample.fetch": "fetch", "admission.wait": "fetch"}


class _Phase:
    __slots__ = ("_clock", "_key", "_span", "_t0")

    def __init__(self, clock: "PhaseClock", key: Optional[str], span):
        self._clock = clock
        self._key = key
        self._span = span

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        if self._key is not None:
            totals = self._clock._totals
            totals[self._key] = totals.get(self._key, 0.0) + dt
        self._span.__exit__(*exc)
        return False


class PhaseClock:
    """Spans and per-step totals of the parts of ``LLMEngine.step``.

    ``phase(name)`` is a top-level phase: a profiler span
    ``engine.<name>`` and a share of this step's total for ``name``.
    ``phase(name, child=True)`` is trace-only: a span under the dotted
    name given, with no label of its own; a child that ``_CHILD_LABEL``
    names (``cache.*``, the transfers in, the fetches out) also adds to
    that derived label's total. ``mark_chunk()`` says that this step
    dispatched a prefill program: ``end()`` then observes the step under
    ``kind="chunk"``, and the wall of a step that decoded into
    ``step_wall`` under the same kind. ``annotation(name)`` makes the
    span (``utils.profiling.annotate``: this module imports no jax).
    Used from the engine thread only.
    """

    def __init__(self, histogram, step_wall,
                 annotation: Callable[[str], object]):
        self._annotation = annotation
        self._totals: Dict[str, float] = {}
        self._t_begin = time.perf_counter()
        self._kind = PLAIN
        # made here, so that every pair of a label and a kind renders
        # from scrape 1 and a step looks nothing up
        self._samples = {
            kind: {name: histogram.labels(name, kind) for name in
                   WORKING_STEP_PHASES + DECODE_STEP_PHASES}
            for kind in STEP_KINDS}
        self._walls = {kind: step_wall.labels(kind) for kind in STEP_KINDS}

    def begin(self) -> None:
        """Start of a step: forget the last step's totals and kind."""
        self._totals.clear()
        self._kind = PLAIN
        self._t_begin = time.perf_counter()

    def mark_chunk(self) -> None:
        """This step dispatched a prefill program, or waits for one
        that went out behind a decode step in flight."""
        self._kind = CHUNK

    def phase(self, name: str, child: bool = False) -> _Phase:
        if child:
            key = _CHILD_LABEL.get(name) or _CHILD_LABEL.get(
                name.partition(".")[0])
            return _Phase(self, key, self._annotation(name))
        return _Phase(self, name, self._annotation("engine." + name))

    def seconds(self, name: str) -> float:
        """This step's total for ``name`` so far."""
        return self._totals.get(name, 0.0)

    def end(self, worked: bool) -> None:
        """End of a step: one sample per phase of the step's
        population, under the step's kind (a step that ran the
        ``device`` phase decoded, and its wall is one sample of
        ``step_wall``); an idle step observes nothing."""
        totals = self._totals
        samples = self._samples[self._kind]
        if "device" in totals:
            wall = time.perf_counter() - self._t_begin
            self._walls[self._kind].observe(wall)
            totals["host"] = max(wall - totals.get("device", 0.0), 0.0)
            for name in DECODE_STEP_PHASES:
                samples[name].observe(totals.get(name, 0.0))
        if worked:
            for name in WORKING_STEP_PHASES:
                samples[name].observe(totals.get(name, 0.0))
