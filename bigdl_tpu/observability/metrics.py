"""Dependency-free metrics registry with Prometheus text exposition.

The reference stack's only runtime telemetry is BenchmarkWrapper's
per-token wall clocks (reference dev/benchmark/benchmark_util.py) — no
counters, no scrape endpoint. This module is the substrate the serving
path (serving/engine.py), speculative decoding (speculative.py), the
kernel dispatch probes (ops/probing.py) and the bench harnesses report
through: Counter / Gauge / Histogram with labels, thread-safe, rendered
in the Prometheus text exposition format by ``MetricsRegistry.render()``
and as JSON by ``snapshot()`` / ``summary()``.

Deliberately stdlib-only (no prometheus_client, no numpy): it is
imported inside the engine's hot step loop and must never add a
dependency or measurable overhead. An observe/inc is a lock + a bisect
over a fixed bucket list.

Metric families are get-or-create: asking the registry for an existing
name returns the existing family (kind and labelnames must match), so
every subsystem can declare the metrics it touches without coordinating
module import order.

Canonical serving metric names (emitted by serving/engine.py; see that
module and observability/__init__ for the field mapping):

    bigdl_tpu_request_phase_seconds{phase=ingest|decode}         histogram
    bigdl_tpu_step_phase_seconds{phase=queue_wait|prefill (per request,
        kind=admission), sweep|admission|observe|cache|h2d|fetch (per
        working step), dispatch|device|sample|emit|host (per step that
        decoded); kind=plain|chunk: whether the step dispatched a
        prefill chunk, or waited for one}                        histogram
    bigdl_tpu_prefill_chunks_total                               counter
    bigdl_tpu_prefill_tokens_total{kind=prompt|padding}          counter
    bigdl_tpu_decode_attn_blocks_total{kind=read|slab}           counter
    bigdl_tpu_paged_attn_pages_total{kind=read|table}            counter
    bigdl_tpu_stream_delivery_seconds (serving/api_server.py)    histogram
    bigdl_tpu_ttft_seconds                                       histogram
    bigdl_tpu_tpot_seconds{kind=plain|chunk} (wall of a step that
        decoded, step() entry to return; STEP_WALL_BUCKETS_S)    histogram
    bigdl_tpu_engine_loop_seconds_total{state=wait|step}
        (serving/api_server.py)                                  counter
    bigdl_tpu_slot_occupancy / bigdl_tpu_queue_depth             gauge
    bigdl_tpu_admissions_total / bigdl_tpu_preemptions_total     counter
    bigdl_tpu_stall_guard_trips_total                            counter
    bigdl_tpu_requests_finished_total{reason=...}                counter
    bigdl_tpu_engine_steps_total / bigdl_tpu_tokens_generated_total
    bigdl_tpu_kernel_probe_total{kernel=...,outcome=...}         counter
    bigdl_tpu_jit_stage_seconds_total{fn,stage=trace|lower|compile|
        cache_load|memory_analysis|first_run} (compile_watch: a first
        call's exclusive seconds; fn=untracked outside one)      counter
    bigdl_tpu_compile_cache_requests_total{fn,outcome=hit|miss}  counter
    bigdl_tpu_startup_mark_seconds{mark=engine_init_begin|
        engine_init_end|listening|first_request|first_token|
        last_compile_end} (seconds since the process started)    gauge
    bigdl_tpu_spec_accept_ratio{mode=draft|lookup|mtp}           histogram
    bigdl_tpu_mtp_drafts_total{outcome=accepted|rejected}        counter
    bigdl_tpu_mtp_slot_steps_total{kind=verify|plain}            counter
    bigdl_tpu_decode_steps_total{sent=ahead|in_step}             counter
    bigdl_tpu_decode_steps_vain_total                            counter
    bigdl_tpu_sampler_steps_total{path=greedy|topk|nucleus}      counter
    bigdl_tpu_block_passes_total{kind=denoise|store}             counter
    bigdl_tpu_block_tokens_committed_total                       counter
    bigdl_tpu_blocks_total                                       counter
    bigdl_tpu_spec_round_seconds{mode=...}                       histogram
    bigdl_tpu_spec_tokens_total{mode=...,kind=drafted|accepted}  counter
    bigdl_tpu_requests_quarantined_total{reason=nan_logits|crash_loop}
    bigdl_tpu_step_retries_total                                 counter
    bigdl_tpu_faults_injected_total{kind=...}                    counter
    bigdl_tpu_engine_draining                                    gauge
"""

from __future__ import annotations

import bisect
import math
import re
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Fixed log-spaced latency buckets (seconds): third-of-a-decade steps
# from 100 us to 100 s. Latencies in this stack span host sampling
# (~100 us) to a cold-compiling 7B prefill (tens of seconds), so a fixed
# log grid keeps every phase resolvable with one bucket list.
LATENCY_BUCKETS_S: Tuple[float, ...] = tuple(
    round(10.0 ** (e / 3.0), 6) for e in range(-12, 7))

# A decode step's wall (bigdl_tpu_tpot_seconds): forty bounds a decade
# (ratio 1.0593) from 4 ms to 1 s, where the steps of every served model
# lie, so that a percentile interpolated inside its bucket is within 3 %
# of the exact one; a few bounds either side for what falls outside.
STEP_WALL_BUCKETS_S: Tuple[float, ...] = (
    (0.0001, 0.001, 0.002)
    + tuple(round(10.0 ** (e / 40.0), 6) for e in range(-96, 1))
    + (2.0, 5.0, 10.0, 30.0, 100.0))

# Acceptance-rate style ratios live in [0, 1]; linear decile buckets.
RATIO_BUCKETS: Tuple[float, ...] = tuple(
    round(i / 10.0, 1) for i in range(1, 11))

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


def _escape_label(value: str) -> str:
    return (str(value).replace("\\", r"\\").replace("\n", r"\n")
            .replace('"', r"\""))


def _fmt(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    f = float(v)
    if f.is_integer() and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _series(name: str, labelnames: Sequence[str], labelvalues: Sequence[str],
            extra: Tuple[str, str] = ()) -> str:
    pairs = list(zip(labelnames, labelvalues))
    if extra:
        pairs.append(extra)
    if not pairs:
        return name
    inner = ",".join(f'{k}="{_escape_label(v)}"' for k, v in pairs)
    return f"{name}{{{inner}}}"


class _CounterChild:
    __slots__ = ("_lock", "value")

    def __init__(self):
        self._lock = threading.Lock()
        self.value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError("counters can only increase")
        with self._lock:
            self.value += amount


class _GaugeChild:
    __slots__ = ("_lock", "value")

    def __init__(self):
        self._lock = threading.Lock()
        self.value = 0.0

    def set(self, value: float) -> None:
        with self._lock:
            self.value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self.value += amount

    def dec(self, amount: float = 1.0) -> None:
        with self._lock:
            self.value -= amount


class _HistogramChild:
    __slots__ = ("_lock", "buckets", "counts", "sum", "count")

    def __init__(self, buckets: Tuple[float, ...]):
        self._lock = threading.Lock()
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)     # +1 for +Inf
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        value = float(value)
        idx = bisect.bisect_left(self.buckets, value)
        with self._lock:
            self.counts[idx] += 1
            self.sum += value
            self.count += 1

    def quantile(self, q: float) -> float:
        """Bucket-interpolated quantile estimate (0 when empty)."""
        with self._lock:
            total = self.count
            counts = list(self.counts)
        if total == 0:
            return 0.0
        rank = q * total
        cum = 0
        for i, c in enumerate(counts):
            if cum + c >= rank and c > 0:
                lo = self.buckets[i - 1] if i > 0 else 0.0
                hi = (self.buckets[i] if i < len(self.buckets)
                      else self.buckets[-1])
                frac = (rank - cum) / c
                return lo + (hi - lo) * frac
            cum += c
        return self.buckets[-1]


_CHILD_TYPES = {"counter": _CounterChild, "gauge": _GaugeChild,
                "histogram": _HistogramChild}


class MetricFamily:
    """One named metric with zero or more label dimensions.

    Unlabeled families expose the child API (inc/set/observe) directly;
    labeled families hand out children via ``labels(...)``.
    """

    def __init__(self, name: str, help: str, kind: str,
                 labelnames: Sequence[str] = (),
                 buckets: Optional[Sequence[float]] = None):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        for ln in labelnames:
            if not _LABEL_RE.match(ln):
                raise ValueError(f"invalid label name {ln!r}")
        if kind not in _CHILD_TYPES:
            raise ValueError(f"unknown metric kind {kind!r}")
        self.name = name
        self.help = help
        self.kind = kind
        self.labelnames = tuple(labelnames)
        if kind == "histogram":
            bk = tuple(sorted(float(b) for b in (buckets or
                                                 LATENCY_BUCKETS_S)))
            if not bk:
                raise ValueError("histogram needs at least one bucket")
            self.buckets = bk
        else:
            self.buckets = None
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], object] = {}
        if not self.labelnames:
            self._children[()] = self._new_child()

    def _new_child(self):
        if self.kind == "histogram":
            return _HistogramChild(self.buckets)
        return _CHILD_TYPES[self.kind]()

    def labels(self, *values) -> object:
        vals = tuple(str(v) for v in values)
        if len(vals) != len(self.labelnames):
            raise ValueError(
                f"{self.name} takes {len(self.labelnames)} label value(s) "
                f"{self.labelnames}, got {len(vals)}")
        with self._lock:
            child = self._children.get(vals)
            if child is None:
                child = self._children[vals] = self._new_child()
        return child

    # -- unlabeled passthrough ----------------------------------------------

    def _default(self):
        if self.labelnames:
            raise ValueError(
                f"{self.name} is labeled {self.labelnames}; use .labels()")
        # safe unlocked: an unlabeled family materializes its sole ()
        # child in __init__ and labels() (the only _children writer)
        # rejects unlabeled use, so this dict never changes after
        # construction
        return self._children[()]  # graftlint: disable=lock-guarded-unlocked

    def inc(self, amount: float = 1.0) -> None:
        self._default().inc(amount)

    def dec(self, amount: float = 1.0) -> None:
        self._default().dec(amount)

    def set(self, value: float) -> None:
        self._default().set(value)

    def observe(self, value: float) -> None:
        self._default().observe(value)

    def children(self) -> List[Tuple[Tuple[str, ...], object]]:
        with self._lock:
            return sorted(self._children.items())


class MetricsRegistry:
    """Thread-safe, get-or-create registry of MetricFamily objects."""

    def __init__(self):
        self._lock = threading.Lock()
        self._families: Dict[str, MetricFamily] = {}
        self._scrape_hooks: List = []

    def add_scrape_hook(self, hook) -> None:
        """`hook()` runs at the start of every `render()`: for counters
        that live on the device and are fetched only when somebody
        reads them. A hook returning False is dropped (its owner is
        gone); one that raises is skipped this time."""
        with self._lock:
            self._scrape_hooks.append(hook)

    def _run_scrape_hooks(self) -> None:
        with self._lock:
            hooks = list(self._scrape_hooks)
        for hook in hooks:
            try:
                alive = hook()
            except Exception:
                continue
            if alive is False:
                with self._lock:
                    if hook in self._scrape_hooks:
                        self._scrape_hooks.remove(hook)

    def _get_or_create(self, name: str, help: str, kind: str,
                       labelnames: Sequence[str],
                       buckets: Optional[Sequence[float]]) -> MetricFamily:
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.kind != kind:
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{fam.kind}, not {kind}")
                if fam.labelnames != tuple(labelnames):
                    raise ValueError(
                        f"metric {name!r} already registered with labels "
                        f"{fam.labelnames}, not {tuple(labelnames)}")
                return fam
            fam = MetricFamily(name, help, kind, labelnames, buckets)
            self._families[name] = fam
            return fam

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> MetricFamily:
        return self._get_or_create(name, help, "counter", labelnames, None)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> MetricFamily:
        return self._get_or_create(name, help, "gauge", labelnames, None)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Optional[Sequence[float]] = None) -> MetricFamily:
        return self._get_or_create(name, help, "histogram", labelnames,
                                   buckets)

    def families(self) -> List[MetricFamily]:
        with self._lock:
            return [self._families[k] for k in sorted(self._families)]

    # -- exposition ---------------------------------------------------------

    def render(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        self._run_scrape_hooks()
        out: List[str] = []
        for fam in self.families():
            if fam.help:
                out.append(f"# HELP {fam.name} "
                           + fam.help.replace("\\", r"\\")
                           .replace("\n", r"\n"))
            out.append(f"# TYPE {fam.name} {fam.kind}")
            for vals, child in fam.children():
                if fam.kind == "histogram":
                    cum = 0
                    for i, ub in enumerate(fam.buckets):
                        cum += child.counts[i]
                        out.append(_series(
                            fam.name + "_bucket", fam.labelnames, vals,
                            ("le", _fmt(ub))) + f" {cum}")
                    out.append(_series(
                        fam.name + "_bucket", fam.labelnames, vals,
                        ("le", "+Inf")) + f" {child.count}")
                    out.append(_series(fam.name + "_sum", fam.labelnames,
                                       vals) + f" {_fmt(child.sum)}")
                    out.append(_series(fam.name + "_count", fam.labelnames,
                                       vals) + f" {child.count}")
                else:
                    out.append(_series(fam.name, fam.labelnames, vals)
                               + f" {_fmt(child.value)}")
        return "\n".join(out) + "\n"

    def snapshot(self) -> dict:
        """Full structured dump (the /v1/stats 'metrics' block)."""
        out: dict = {}
        for fam in self.families():
            series = []
            for vals, child in fam.children():
                labels = dict(zip(fam.labelnames, vals))
                if fam.kind == "histogram":
                    series.append({
                        "labels": labels,
                        "count": child.count,
                        "sum": round(child.sum, 9),
                        "buckets": {_fmt(ub): c for ub, c in
                                    zip(fam.buckets, child.counts)},
                    })
                else:
                    series.append({"labels": labels, "value": child.value})
            out[fam.name] = {"type": fam.kind, "help": fam.help,
                             "series": series}
        return out

    def summary(self) -> dict:
        """Flat compact dump keyed by full series name — counters and
        gauges map to their value, histograms to
        {count, sum, mean, p50, p90, p99} (quantiles bucket-estimated).
        This is what the bench harnesses embed in BENCH json."""
        out: dict = {}
        for fam in self.families():
            for vals, child in fam.children():
                key = _series(fam.name, fam.labelnames, vals)
                if fam.kind == "histogram":
                    if child.count == 0:
                        continue
                    out[key] = {
                        "count": child.count,
                        "sum": round(child.sum, 9),
                        "mean": round(child.sum / child.count, 9),
                        "p50": round(child.quantile(0.5), 9),
                        "p90": round(child.quantile(0.9), 9),
                        "p99": round(child.quantile(0.99), 9),
                    }
                else:
                    out[key] = child.value
        return out


_default_registry = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    """The process-wide registry every subsystem reports into unless
    handed an explicit one (engines accept ``registry=`` for isolation,
    e.g. per-bench-run registries)."""
    return _default_registry
