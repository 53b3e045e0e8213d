"""Flight recorder + postmortem dumps for the serving engine.

When a serving process dies mid-step, stalls, or is SIGTERMed during a
deploy, steady-state metrics say nothing about what it was *doing*. Two
pieces fix that:

- ``FlightRecorder``: a bounded ring buffer of structured engine events
  (per-step occupancy/queue depth, admission starts/completions,
  preemptions, stall-guard trips, finishes, exceptions). Appending is a
  lock + deque append — safe inside the hot step loop. The engine owns
  one (``LLMEngine.flight``).

- postmortems: ``build_postmortem()`` assembles one JSON-ready dict —
  flight-recorder tail, recent request spans, full metrics snapshot,
  the jit compile table (compile_watch), config + environment
  fingerprint, and the active exception when there is one.
  ``write_postmortem()`` writes it to ``$BIGDL_TPU_POSTMORTEM_DIR``
  (atomically, via tmp + rename) and NEVER raises — a failing dump must
  not mask the original failure. The engine writes one on step
  exceptions and stall-guard trips; ``install_signal_dumps()`` hooks
  SIGTERM/SIGINT for operator kills; ``GET /v1/debug/dump`` serves the
  same dict from a live server.

Stdlib-only (tests/test_observability.py enforces it for this
subpackage).
"""

from __future__ import annotations

import collections
import json
import logging
import os
import signal
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

logger = logging.getLogger(__name__)

POSTMORTEM_DIR_ENV = "BIGDL_TPU_POSTMORTEM_DIR"


class FlightRecorder:
    """Thread-safe bounded ring buffer of structured engine events.

    Each event is a flat dict ``{"ts": ..., "event": ..., **fields}``;
    the buffer holds the most recent ``capacity`` of them. Recording
    never raises and never blocks beyond a lock."""

    def __init__(self, capacity: int = 1024):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._events: "collections.deque[dict]" = \
            collections.deque(maxlen=capacity)
        self._total = 0

    def record(self, event: str, **fields) -> None:
        entry = {"ts": round(time.time(), 6), "event": event}
        entry.update(fields)
        with self._lock:
            self._events.append(entry)
            self._total += 1

    def snapshot(self, last: Optional[int] = None) -> List[dict]:
        """Most recent events, oldest first (all when ``last`` is
        None)."""
        with self._lock:
            ev = list(self._events)
        if last is not None and last >= 0:
            ev = ev[-last:]
        return ev

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    @property
    def total_recorded(self) -> int:
        """Events recorded over the recorder's lifetime (>= len when
        the ring has wrapped)."""
        with self._lock:
            return self._total

    def clear(self) -> None:
        with self._lock:
            self._events.clear()


def exception_fields(error: BaseException, max_len: int = 200) -> dict:
    """Flat ``{"error_type", "error_msg"}`` fields for a flight event:
    the exception's type name and its truncated message, so events like
    ``step_exception`` / ``quarantined`` are debuggable straight from
    the ring buffer without chasing the postmortem file (which carries
    the full traceback)."""
    msg = str(error)
    if len(msg) > max_len:
        msg = msg[: max_len - 1] + "…"
    return {"error_type": type(error).__name__, "error_msg": msg}


def env_fingerprint() -> dict:
    """Process + environment identity for a postmortem: interpreter,
    pid, argv, accelerator-relevant env flags, and library versions for
    whatever is ALREADY imported (no new imports — a dump must work
    from a dying process)."""
    out: dict = {
        "python": sys.version.split()[0],
        "pid": os.getpid(),
        "argv": list(sys.argv),
        "env": {k: v for k, v in os.environ.items()
                if k.startswith(("JAX_", "XLA_", "BIGDL_", "LIBTPU"))},
    }
    for mod in ("jax", "numpy", "bigdl_tpu"):
        m = sys.modules.get(mod)
        ver = getattr(m, "__version__", None) if m is not None else None
        if ver is not None:
            out[mod] = ver
    jax_mod = sys.modules.get("jax")
    if jax_mod is not None:
        try:
            out["backend"] = jax_mod.default_backend()
        except Exception:
            pass
    return out


def build_postmortem(reason: str, *, flight: Optional[FlightRecorder] = None,
                     tracer=None, registry=None,
                     config: Optional[dict] = None,
                     memory: Optional[dict] = None,
                     error: Optional[BaseException] = None,
                     events_tail: int = 256,
                     spans_tail: int = 32) -> dict:
    """Assemble the postmortem dict. Every section degrades to a
    partial record rather than failing the dump. ``memory`` is a
    ready-made snapshot (the engine passes its ledger view); when
    omitted, the process-default MemoryLedger's snapshot is used so
    even bare dumps answer "where was HBM when it died"."""
    out: dict = {"reason": reason, "ts": round(time.time(), 6)}
    if error is not None:
        out["error"] = {
            "type": type(error).__name__,
            "message": str(error),
            "traceback": traceback.format_exception(
                type(error), error, error.__traceback__),
        }
    try:
        out["fingerprint"] = env_fingerprint()
    except Exception as e:
        out["fingerprint"] = {"error": repr(e)}
    if config is not None:
        out["config"] = config
    if flight is not None:
        try:
            out["flight"] = flight.snapshot(last=events_tail)
            out["flight_total_events"] = flight.total_recorded
        except Exception as e:
            out["flight"] = [{"event": "snapshot_error", "error": repr(e)}]
    if tracer is not None:
        try:
            out["spans"] = tracer.snapshot(recent=spans_tail)
        except Exception as e:
            out["spans"] = {"error": repr(e)}
    if registry is not None:
        try:
            out["metrics"] = registry.snapshot()
        except Exception as e:
            out["metrics"] = {"error": repr(e)}
    try:
        from bigdl_tpu.observability.compile_watch import (
            compile_table, startup_snapshot)

        out["compile_table"] = compile_table()
        out["startup"] = startup_snapshot()
    except Exception as e:
        out["compile_table"] = {"error": repr(e)}
    if memory is not None:
        out["memory"] = memory
    else:
        try:
            from bigdl_tpu.observability.memory import default_ledger

            out["memory"] = default_ledger().snapshot()
        except Exception as e:
            out["memory"] = {"error": repr(e)}
    return out


def postmortem_dir() -> Optional[str]:
    return os.environ.get(POSTMORTEM_DIR_ENV) or None


def validate_postmortem_dir(path: str) -> dict:
    """Report whether `path` can receive postmortem dumps
    (utils/env_check.py surfaces this for BIGDL_TPU_POSTMORTEM_DIR).
    A missing directory is fine — it is created at dump time — as long
    as some existing ancestor is writable."""
    out = {"path": path, "exists": os.path.isdir(path)}
    if os.path.isdir(path):
        out["writable"] = os.access(path, os.W_OK)
        if not out["writable"]:
            out["error"] = f"directory {path!r} is not writable"
        return out
    if os.path.exists(path):
        out["writable"] = False
        out["error"] = f"{path!r} exists and is not a directory"
        return out
    parent = os.path.abspath(path)
    while parent and not os.path.isdir(parent):
        nxt = os.path.dirname(parent)
        if nxt == parent:
            break
        parent = nxt
    out["writable"] = bool(parent) and os.access(parent, os.W_OK)
    if not out["writable"]:
        out["error"] = f"no writable ancestor for {path!r}"
    return out


def write_postmortem(reason: str, *, directory: Optional[str] = None,
                     **build_kwargs) -> Optional[str]:
    """Write one postmortem JSON; returns its path, or None when no
    directory is configured (``directory=`` or
    ``$BIGDL_TPU_POSTMORTEM_DIR``). Never raises: dump failures are
    logged and swallowed so they cannot mask the original failure."""
    try:
        d = directory or postmortem_dir()
        if not d:
            return None
        dump = build_postmortem(reason, **build_kwargs)
        os.makedirs(d, exist_ok=True)
        safe = "".join(c if c.isalnum() or c in "-_" else "-"
                       for c in reason) or "dump"
        path = os.path.join(
            d, f"postmortem-{int(time.time() * 1000)}-{os.getpid()}"
               f"-{safe}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(dump, f, default=repr)
        os.replace(tmp, path)
        return path
    except Exception:
        logger.warning("postmortem dump failed", exc_info=True)
        return None


def install_signal_dumps(write_fn, signals=(signal.SIGTERM, signal.SIGINT)):
    """Install handlers that call ``write_fn(reason)`` (e.g. the
    engine's postmortem writer) on SIGTERM/SIGINT, then chain to the
    previous handler so default termination semantics are preserved.
    Main-thread only (CPython restriction); returns {signum: previous
    handler}."""
    previous: Dict[int, Any] = {}

    def handler(signum, frame):
        try:
            write_fn(f"signal_{signal.Signals(signum).name}")
        except Exception:
            logger.warning("signal postmortem failed", exc_info=True)
        prev = previous.get(signum)
        if callable(prev):
            prev(signum, frame)
        elif prev == signal.SIG_DFL:
            signal.signal(signum, signal.SIG_DFL)
            signal.raise_signal(signum)
        # SIG_IGN / None: swallow, matching the prior disposition

    for s in signals:
        previous[s] = signal.signal(s, handler)
    return previous
