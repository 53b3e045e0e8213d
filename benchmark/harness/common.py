"""What both runners share: the compile watch, the device block, the
selection of a line's metrics, and the plain arithmetic of a comparison
with a reference."""

from __future__ import annotations

import json
import shutil
import threading
from pathlib import Path
from typing import Any, Dict, Optional, Sequence


class CompileWatch:
    """Counts what JAX compiles or fetches from its persistent cache,
    from JAX's own monitoring events, so that a compile inside the
    window shows whatever jitted it."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        self._lock = threading.Lock()
        self.backend_compiles = 0
        self.cache_hits = 0
        self.backend_seconds = 0.0

    def install(self) -> "CompileWatch":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event: str, seconds: float, **_kw) -> None:
        with self._lock:
            if event == self.BACKEND:
                self.backend_compiles += 1
                self.backend_seconds += seconds
            elif event == self.CACHE_HIT:
                self.cache_hits += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {"backend_compiles": self.backend_compiles,
                    "cache_hits": self.cache_hits,
                    "backend_seconds": self.backend_seconds}

    @staticmethod
    def programs_between(before: Dict[str, float],
                         after: Dict[str, float]) -> int:
        """Programs compiled or fetched from the cache between two
        snapshots: either way a new shape met the window."""
        return int((after["backend_compiles"] + after["cache_hits"])
                   - (before["backend_compiles"] + before["cache_hits"]))


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device; 0 where the backend
    does not say (the CPU)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def free_device() -> None:
    """Delete every array the process still holds on its devices,
    whoever refers to it: what runs next gets the whole device."""
    import gc

    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def report_compared(compared, checks: Dict[str, bool]) -> None:
    """The run's last lines on standard error: every number that was
    compared beside its limit, then every check that failed."""
    import sys

    for name, value, limit in compared:
        verdict = ("not read" if value is None
                   else "ok" if value <= limit else "OVER")
        print(f"compared {name} = {value} limit {limit}: {verdict}",
              file=sys.stderr)
    failed = sorted(k for k, ok in checks.items() if not ok)
    print(f"checks failed: {failed if failed else 'none'}",
          file=sys.stderr, flush=True)


def note(**fields: Any) -> None:
    """One earlier line of standard output (never the last)."""
    print(json.dumps(fields), flush=True)


def select_end_to_end(cell, values: Dict[str, Optional[float]]
                      ) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.end_to_end():
        v = values.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def next_token_loss(logits, token_ids: Sequence[int]) -> float:
    """Mean cross-entropy of position t's logits against token t+1."""
    import numpy as np

    lg = np.asarray(logits, np.float64)[:-1]
    tgt = np.asarray(list(token_ids))[1:]
    lg = lg - lg.max(axis=-1, keepdims=True)
    logp = lg - np.log(np.exp(lg).sum(axis=-1, keepdims=True))
    return float(-logp[np.arange(len(tgt)), tgt].mean())


def relative_l2(a, b) -> float:
    """``|a - b| / |b|`` over all entries, in float64 on the host."""
    import numpy as np

    a = np.asarray(a, np.float64).reshape(-1)
    b = np.asarray(b, np.float64).reshape(-1)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def start_trace(trace_dir: Path) -> None:
    """Start the profiler into an emptied ``trace_dir``, host spans on
    (TraceMe level 2) and the Python tracer off: it would slow the host
    that is being measured."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def traced_metrics(cell, result: Dict[str, Any], obs: Dict[str, Any],
                   trace_dir: Optional[Path], host_span: str, tiny: bool,
                   out_dir: Path, **note_fields: Any) -> None:
    """Fill a traced run's line: reduce the trace (a chip run only),
    read the cell's per-layer metrics from ``obs``, and put busy time,
    window and breakdown where the contract wants them."""
    from harness import layer_metrics, trace_reduce

    reduction = None
    if trace_dir is not None and not tiny:
        xplane = trace_reduce.find_xplane(trace_dir)
        if xplane is not None:
            reduction = trace_reduce.reduce(
                trace_reduce.load(xplane), cell.trace_groups(),
                host_spans=[host_span])
    obs = dict(obs, trace=reduction)
    result["metrics"] = layer_metrics.read_all(cell, obs, counts_only=tiny)
    if reduction is None:
        return
    result["device"]["busy_s"] = reduction["busy_s"]
    result["device"]["window_s"] = reduction["window_s"]
    result["breakdown"] = {"device_ops": reduction["device_ops"],
                           "idle_gaps": reduction["idle_gaps"]}
    with open(out_dir / "trace_summary.json", "w") as f:
        json.dump(reduction, f, indent=1)
    note(info="trace", groups=reduction["groups"],
         programs=reduction["programs"],
         unmatched_share=reduction["unmatched_share"],
         longest_gap_s=reduction["longest_gap_s"],
         host_span_calls=reduction["host_span_calls"], **note_fields)
