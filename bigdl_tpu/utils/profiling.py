"""Profiling helpers: traces + named regions around the hot loops.

The reference has no tracer — its observability is BenchmarkWrapper's
per-token timing (reference dev/benchmark/benchmark_util.py:489-520) and
manual `torch.xpu.synchronize()` wall-clocks. On TPU the native story is
`jax.profiler` (XLA device traces viewable in TensorBoard/Perfetto); this
module makes it a one-liner around our entry points and keeps working on
CPU test runs.

    from bigdl_tpu.utils.profiling import trace, annotate

    with trace("/tmp/tb"):                     # device + host trace
        with annotate("prefill"):
            model.generate(ids, max_new_tokens=64)
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Dict, Iterator, Optional

import jax


def resolve_profiler_max_sec(value=None) -> float:
    """Hard cap on any on-demand profiler capture: explicit value, else
    ``$BIGDL_TPU_PROFILER_MAX_SEC``, else 60 seconds. Every capture —
    operator-started, router fleet fan-out, or sentinel auto-capture —
    is auto-stopped at this deadline so an abandoned capture can never
    run unbounded. ValueError on a non-positive or non-numeric setting
    (utils/env_check.py surfaces this)."""
    if value is None:
        value = os.environ.get("BIGDL_TPU_PROFILER_MAX_SEC")
    if value is None or value == "":
        return 60.0
    try:
        f = float(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"profiler max seconds must be a positive number, got "
            f"{value!r}")
    if f <= 0:
        raise ValueError(
            f"profiler max seconds must be a positive number, got {f}")
    return f


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a jax.profiler trace into `log_dir` (TensorBoard format)."""
    jax.profiler.start_trace(log_dir,
                             create_perfetto_link=False,
                             create_perfetto_trace=True)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# On-demand profiler for the API server (POST /v1/profiler/{start,stop}):
# same jax.profiler trace as `trace()` above but split into explicit
# start/stop calls so a capture can bracket live traffic. One capture at
# a time per process (jax.profiler itself is single-session). A
# watchdog timer auto-stops every capture at its deadline.
_profiler_lock = threading.Lock()
_profiler_dir: Optional[str] = None
_profiler_started_at: Optional[float] = None
_profiler_deadline: Optional[float] = None
_profiler_capture_id: Optional[str] = None
_profiler_timer: Optional[threading.Timer] = None
_last_capture: Optional[dict] = None

# a runaway capture dir (Perfetto traces of a busy chip are big) stops
# admission of NEW captures past this many bytes; env-overridable for
# tests and small disks
_CAPTURE_DIR_CAP_BYTES = 1 << 30


def _capture_dir_cap() -> int:
    raw = os.environ.get("BIGDL_TPU_PROFILER_DIR_CAP_BYTES")
    if raw:
        try:
            n = int(raw)
            if n > 0:
                return n
        except ValueError:
            pass
    return _CAPTURE_DIR_CAP_BYTES


def _dir_bytes(path: str) -> int:
    total = 0
    for root, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return total


def start_profiler(log_dir: str, max_sec: Optional[float] = None,
                   capture_id: Optional[str] = None) -> dict:
    """Start a device trace into `log_dir`; error if one is running.

    Hardening (all three bit operators in practice): non-absolute paths
    are rejected (a capture landing in whatever CWD the server happened
    to start from is a lost capture), the directory is created if
    missing, and an already-oversized capture dir refuses new captures.
    A daemon watchdog stops the capture after ``max_sec`` (clamped to
    ``resolve_profiler_max_sec()``) so it can never run unbounded."""
    global _profiler_dir, _profiler_started_at, _profiler_deadline
    global _profiler_capture_id, _profiler_timer
    if not os.path.isabs(log_dir):
        raise ValueError(
            f"profiler log_dir must be an absolute path, got {log_dir!r}")
    cap_sec = resolve_profiler_max_sec()
    if max_sec is not None:
        try:
            max_sec = float(max_sec)
        except (TypeError, ValueError):
            raise ValueError(
                f"profiler duration must be a positive number, got "
                f"{max_sec!r}")
        if max_sec <= 0:
            raise ValueError(
                f"profiler duration must be a positive number, got "
                f"{max_sec}")
        cap_sec = min(cap_sec, max_sec)
    with _profiler_lock:
        if _profiler_dir is not None:
            raise RuntimeError(
                f"profiler already capturing into {_profiler_dir}")
        os.makedirs(log_dir, exist_ok=True)
        used = _dir_bytes(log_dir)
        cap_bytes = _capture_dir_cap()
        if used >= cap_bytes:
            raise RuntimeError(
                f"capture dir {log_dir} already holds {used} bytes "
                f"(cap {cap_bytes}); clean it up before capturing")
        jax.profiler.start_trace(log_dir,
                                 create_perfetto_link=False,
                                 create_perfetto_trace=True)
        now = time.time()
        _profiler_dir = log_dir
        _profiler_started_at = now
        _profiler_deadline = now + cap_sec
        _profiler_capture_id = capture_id
        _profiler_timer = threading.Timer(
            cap_sec, _auto_stop, args=(log_dir,))
        _profiler_timer.daemon = True
        _profiler_timer.start()
        out = {"status": "started", "log_dir": log_dir,
               "max_sec": cap_sec, "deadline": _profiler_deadline}
        if capture_id is not None:
            out["capture_id"] = capture_id
        return out


def _auto_stop(expected_dir: str) -> None:
    """Watchdog body: stop the capture iff it is still the one we armed
    for (an operator stop + fresh start must not be killed by a stale
    timer)."""
    with _profiler_lock:
        if _profiler_dir != expected_dir:
            return
    try:
        stop_profiler(_reason="auto_stop")
    except RuntimeError:
        pass  # lost the race with an operator stop: fine


def stop_profiler(_reason: str = "manual") -> dict:
    """Stop the running capture; error if none is running.

    ``_profiler_dir`` is cleared BEFORE ``stop_trace()`` can raise
    (try/finally): a failed stop used to leave the module convinced a
    capture was live, wedging the profiler until process restart."""
    global _profiler_dir, _profiler_started_at, _profiler_deadline
    global _profiler_capture_id, _profiler_timer, _last_capture
    with _profiler_lock:
        if _profiler_dir is None:
            raise RuntimeError("no profiler capture in progress")
        log_dir, _profiler_dir = _profiler_dir, None
        started_at, _profiler_started_at = _profiler_started_at, None
        capture_id, _profiler_capture_id = _profiler_capture_id, None
        _profiler_deadline = None
        timer, _profiler_timer = _profiler_timer, None
        if timer is not None:
            timer.cancel()
        out = {"status": "stopped", "log_dir": log_dir,
               "stopped_by": _reason}
        if started_at is not None:
            out["duration_s"] = round(time.time() - started_at, 3)
        if capture_id is not None:
            out["capture_id"] = capture_id
        try:
            jax.profiler.stop_trace()
        finally:
            _last_capture = dict(out)
        return out


def profiler_status() -> dict:
    """Structured view of the on-demand profiler: whether a capture is
    live, its dir / start / deadline, the configured cap, and the last
    finished capture (who stopped it, how long it ran)."""
    try:
        max_sec = resolve_profiler_max_sec()
    except ValueError:
        max_sec = 60.0  # status must render even with a bad env knob
    with _profiler_lock:
        out = {"capturing": _profiler_dir is not None,
               "log_dir": _profiler_dir,
               "max_sec": max_sec}
        if _profiler_dir is not None:
            out["started_at"] = _profiler_started_at
            out["deadline"] = _profiler_deadline
            if _profiler_capture_id is not None:
                out["capture_id"] = _profiler_capture_id
        if _last_capture is not None:
            out["last_capture"] = dict(_last_capture)
        return out


def annotate(name: str) -> "jax.profiler.TraceAnnotation":
    """Named region (``with annotate("x"):``) that shows up on the trace
    timeline (TraceAnnotation) AND works as a no-op grouping label
    outside a trace: with no profiler running, entering it is a flag
    test. The engine's PhaseClock takes this as its span factory."""
    return jax.profiler.TraceAnnotation(name)


class StepTimer:
    """Blocking wall-clock timer for steps (training loops, engine steps).

    The per-phase analog of GenerationStats: `block_until_ready` on the
    step output before reading the clock, so the clock covers the
    device work and not only its asynchronous enqueue."""

    def __init__(self, metrics_prefix: Optional[str] = None,
                 registry=None):
        """With `metrics_prefix` set, every sample is also observed into a
        `{prefix}_{name}_seconds` histogram in `registry` (the
        observability default registry when None)."""
        self.times: Dict[str, list] = {}
        self._metrics_prefix = metrics_prefix
        self._registry = registry

    def record(self, name: str, seconds: float) -> None:
        """Append one sample; mirror it to the metrics registry when a
        prefix was configured."""
        self.times.setdefault(name, []).append(seconds)
        if self._metrics_prefix is None:
            return
        try:
            if self._registry is None:
                from bigdl_tpu.observability.metrics import default_registry
                self._registry = default_registry()
            self._registry.histogram(
                f"{self._metrics_prefix}_{name}_seconds",
                f"StepTimer samples for {name}.",
            ).observe(seconds)
        except Exception:
            pass  # telemetry must never break the timed code path

    @contextlib.contextmanager
    def measure(self, name: str, result=None) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        except BaseException:
            # the block failed — a sample here would mix error paths into
            # the latency distribution, so drop it
            raise
        else:
            if result is not None:
                jax.block_until_ready(result)
            self.record(name, time.perf_counter() - t0)

    def timed(self, name: str, fn, *args, **kwargs):
        """Run fn, block on its output, record the wall time, return it."""
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        self.record(name, time.perf_counter() - t0)
        return out

    def summary(self) -> Dict[str, Dict[str, float]]:
        from bigdl_tpu.observability.stats import summarize

        out = {}
        for name, ts in self.times.items():
            s = summarize(ts, scale=1e3)
            out[name] = {
                "count": s["count"],
                "mean_ms": s["mean"],
                "min_ms": s["min"],
                "max_ms": s["max"],
                "p50_ms": s["p50"],
                "p90_ms": s["p90"],
                "p99_ms": s["p99"],
                "total_s": s["total"],
            }
        return out


def _percentile(sorted_samples, q: float) -> float:
    """Linear-interpolation percentile over pre-sorted samples; the
    shared implementation lives in observability/stats.py (single
    source for StepTimer, the sentinel baseline, and bench lane
    stats). Kept as a name here for existing callers."""
    from bigdl_tpu.observability.stats import percentile

    return percentile(sorted_samples, q)
