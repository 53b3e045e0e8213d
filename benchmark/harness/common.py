"""What both runners share: the compile watch, the device block, the
selection of a line's metrics, and the plain arithmetic of a comparison
with a reference."""

from __future__ import annotations

import json
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, Optional, Sequence

# A run's whole wall time, process start to the result line, is held to
# this: five sixths of the 360 s after which the driver stops a run. A
# run over it is still reported and still ``correct``; it is only said.
RUN_BUDGET_S = 300.0
# Where a run's wall time goes, in the order it is spent. A runner laps
# the phases it has; every name is on the note line, 0.0 where a cell
# has no such phase.
PHASES = ("devices_ready", "weights", "engine_and_server", "warmup",
          "window", "drain", "check_a_program", "canonical_tree",
          "layer_check", "check_a_reference", "check_b_served",
          "trace_reduction", "exit")


class WallClock:
    """Consecutive laps from the process's start: each lap charges the
    time since the last one to a phase, so the phases add up to the
    wall time whatever runs between them."""

    def __init__(self, t_process: float):
        self.t_process = self._last = t_process
        self.phases: Dict[str, float] = {k: 0.0 for k in PHASES}

    def lap(self, phase: str) -> None:
        now = time.monotonic()
        self.phases[phase] += now - self._last
        self._last = now

    def move(self, seconds: float, src: str, dst: str) -> None:
        """Charge ``seconds`` of ``src`` to ``dst``: a part of a lap that
        a configuration's module timed itself."""
        seconds = min(max(0.0, float(seconds)), self.phases[src])
        self.phases[src] -= seconds
        self.phases[dst] += seconds

    def close(self) -> Dict[str, Any]:
        """``{"wall_s", "phases"}`` for the note line; what is left
        since the last lap is ``exit``."""
        self.lap("exit")
        return {"wall_s": self._last - self.t_process,
                "phases": dict(self.phases)}


def report_wall(wall_s: float) -> None:
    """The run's last line on standard error: its wall time beside the
    harness's budget. Said, not judged: ``correct`` does not read it."""
    print(f"wall_s = {wall_s} budget {RUN_BUDGET_S}: "
          f"{'ok' if wall_s <= RUN_BUDGET_S else 'OVER'}",
          file=sys.stderr, flush=True)


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


def _verdict(value, limit, floor: bool) -> str:
    if value is None:
        return "not read"
    return "ok" if (value >= limit if floor else value <= limit) else "OVER"


def print_compared(compared) -> Dict[str, Dict[str, Any]]:
    """Each ``(name, value, limit)`` on a line of standard error; a
    fourth entry ``"floor"`` makes the limit a lower one. Returns the
    same numbers as the result line carries them."""
    out = {}
    for name, value, limit, *kind in compared:
        floor = bool(kind) and kind[0] == "floor"
        print(f"compared {name} = {value} {'floor' if floor else 'limit'} "
              f"{limit}: {_verdict(value, limit, floor)}", file=sys.stderr,
              flush=True)
        out[name] = {"value": value, "floor" if floor else "limit": limit}
    return out


def report_compared(compared, checks: Dict[str, bool]
                    ) -> Dict[str, Dict[str, Any]]:
    """The run's last lines on standard error: every number that was
    compared beside its limit, then every check that failed. Returns
    the numbers for the result line's ``compared`` key."""
    out = print_compared(compared)
    failed = sorted(k for k, ok in checks.items() if not ok)
    print(f"checks failed: {failed if failed else 'none'}",
          file=sys.stderr, flush=True)
    return out


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
