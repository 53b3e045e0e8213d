"""HBM memory ledger: static byte accounting + live device telemetry.

The whole stack exists to fit models into scarce accelerator memory
(low-bit weights, block-scaled KV caches), yet nothing at runtime could
answer "where did HBM go?" — the footprint claims lived in one unit
test. The ``MemoryLedger`` closes that gap with two complementary
views:

- **static**: exact packed bytes per registered allocation, grouped by
  kind ("weights", "kv_cache", "lora", "optimizer", ...). Producers
  register at build/allocation time (the serving engine registers its
  params and batched KV cache; ``Generator`` registers
  its own) with the same byte conventions the allocators use — int4 at
  two codes per byte, scale planes counted separately — so
  ``static_report()`` matches allocated ``nbytes`` exactly.
- **live**: ``device.memory_stats()`` (``bytes_in_use``,
  ``peak_bytes_in_use``, ``bytes_limit``) polled at most once per
  ``$BIGDL_TPU_MEMORY_POLL_SEC`` (default 1.0s). CPU/interpret backends
  return no stats; every consumer degrades to "no telemetry" rather
  than failing — admission control admits, gauges stay unset.

``headroom()`` combines the two into budget math: the serving engine
defers admissions whose projected usage exceeds
``$BIGDL_TPU_HBM_BUDGET_FRACTION`` (a float in (0, 1], default 0.9) of
``bytes_limit``. Tests inject a deterministic ``stats_provider``
callable instead of a real device.

``publish()`` exports ``bigdl_tpu_hbm_bytes{kind=...}`` (static kinds
plus ``device_in_use`` / ``device_peak`` / ``device_limit``) and
``bigdl_tpu_hbm_headroom_bytes`` (budget minus in-use; negative means
overdraft) to a metrics registry.

Stdlib-only at import time (tests/test_observability.py enforces it):
jax is imported lazily inside ``device_memory_stats``/``tree_nbytes``.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Callable, Dict, Optional

logger = logging.getLogger(__name__)

HBM_BUDGET_FRACTION_ENV = "BIGDL_TPU_HBM_BUDGET_FRACTION"
MEMORY_POLL_SEC_ENV = "BIGDL_TPU_MEMORY_POLL_SEC"
DEFAULT_HBM_BUDGET_FRACTION = 0.9
DEFAULT_MEMORY_POLL_SEC = 1.0

# device.memory_stats() keys the ledger snapshots/headroom math read
_STATS_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")


def resolve_hbm_budget_fraction(value: Optional[object] = None) -> float:
    """The admission HBM budget as a fraction of ``bytes_limit``:
    explicit value, else ``$BIGDL_TPU_HBM_BUDGET_FRACTION``, else the
    default. Raises ValueError outside (0, 1] (utils/env_check.py
    surfaces this for the env var)."""
    if value is None:
        value = os.environ.get(HBM_BUDGET_FRACTION_ENV)
    if value is None or value == "":
        return DEFAULT_HBM_BUDGET_FRACTION
    try:
        f = float(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"HBM budget fraction must be a float in (0, 1], got "
            f"{value!r}")
    if not (0.0 < f <= 1.0):
        raise ValueError(
            f"HBM budget fraction must be in (0, 1], got {f}")
    return f


def resolve_memory_poll_sec(value: Optional[object] = None) -> float:
    """Minimum seconds between live ``memory_stats()`` polls: explicit
    value, else ``$BIGDL_TPU_MEMORY_POLL_SEC``, else the default.
    Raises ValueError on a negative or non-numeric setting (0 disables
    throttling — every read polls)."""
    if value is None:
        value = os.environ.get(MEMORY_POLL_SEC_ENV)
    if value is None or value == "":
        return DEFAULT_MEMORY_POLL_SEC
    try:
        f = float(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"memory poll interval must be a non-negative float, got "
            f"{value!r}")
    if f < 0.0:
        raise ValueError(
            f"memory poll interval must be a non-negative float, got {f}")
    return f


def device_memory_stats(device: Any = None) -> Dict[str, int]:
    """Best-effort ``device.memory_stats()`` as a plain dict of numeric
    fields. Returns ``{}`` whenever telemetry is unavailable — CPU and
    interpret backends return None, some plugins raise — so callers
    can treat falsy as "no live view" without try/except."""
    try:
        if device is None:
            import jax

            devs = jax.local_devices()
            if not devs:
                return {}
            device = devs[0]
        stats = device.memory_stats()
        if not stats:
            return {}
        return {k: int(v) for k, v in stats.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}
    except Exception:
        return {}


def tree_nbytes(tree: Any) -> int:
    """Packed storage bytes of an array pytree, with the allocators'
    byte conventions: jnp.int4 counts two codes per byte (QTensor
    flattens into its raw component planes, so this reproduces
    ``QTensor.nbytes`` exactly); everything else size * itemsize.
    Non-array leaves (python scalars, None) count zero."""
    import jax
    import jax.numpy as jnp

    int4 = jnp.dtype(jnp.int4)

    def leaf_bytes(a: Any) -> int:
        dt = getattr(a, "dtype", None)
        size = getattr(a, "size", None)
        if dt is None or size is None:
            return 0
        if jnp.dtype(dt) == int4:
            return -(-int(size) // 2)
        return int(size) * jnp.dtype(dt).itemsize

    return sum(leaf_bytes(a) for a in jax.tree_util.tree_leaves(tree))


class MemoryLedger:
    """Static allocation ledger + throttled live device telemetry.

    ``stats_provider`` is any zero-arg callable returning a
    ``memory_stats()``-shaped dict (or ``{}``/None for "no telemetry");
    the default polls the first local jax device. Tests inject a fake
    provider for deterministic headroom behaviour. All methods are
    thread-safe and never raise out of telemetry paths.
    """

    def __init__(self, stats_provider: Optional[Callable[[], dict]] = None,
                 budget_fraction: Optional[float] = None,
                 poll_sec: Optional[float] = None):
        self._lock = threading.Lock()
        self._static: Dict[str, Dict[str, Dict[str, Any]]] = {}
        self._stats_provider = stats_provider or device_memory_stats
        try:
            self.budget_fraction = resolve_hbm_budget_fraction(
                budget_fraction)
        except ValueError:
            logger.warning(
                "invalid %s=%r; using default %g", HBM_BUDGET_FRACTION_ENV,
                os.environ.get(HBM_BUDGET_FRACTION_ENV),
                DEFAULT_HBM_BUDGET_FRACTION)
            self.budget_fraction = DEFAULT_HBM_BUDGET_FRACTION
        try:
            self.poll_sec = resolve_memory_poll_sec(poll_sec)
        except ValueError:
            logger.warning(
                "invalid %s=%r; using default %g", MEMORY_POLL_SEC_ENV,
                os.environ.get(MEMORY_POLL_SEC_ENV),
                DEFAULT_MEMORY_POLL_SEC)
            self.poll_sec = DEFAULT_MEMORY_POLL_SEC
        self._last_poll = 0.0
        self._last_stats: Dict[str, int] = {}

    # -- static accounting ---------------------------------------------------

    def register(self, kind: str, name: str, nbytes: int,
                 **meta: Any) -> int:
        """Record (or replace) one named allocation under ``kind``.
        ``meta`` (dtype, shape, components, ...) rides along into
        ``static_report()``. Returns ``nbytes`` for chaining."""
        entry = {"bytes": int(nbytes)}
        entry.update(meta)
        with self._lock:
            self._static.setdefault(kind, {})[name] = entry
        return int(nbytes)

    def unregister(self, kind: str, name: str) -> None:
        with self._lock:
            self._static.get(kind, {}).pop(name, None)

    def static_report(self) -> dict:
        """JSON-ready static view: every registered entry, per-kind
        subtotals, and the grand total."""
        with self._lock:
            entries = {kind: {name: dict(ent)
                              for name, ent in sorted(named.items())}
                       for kind, named in sorted(self._static.items())}
        by_kind = {kind: sum(e["bytes"] for e in named.values())
                   for kind, named in entries.items()}
        return {"entries": entries, "by_kind": by_kind,
                "total_bytes": sum(by_kind.values())}

    def static_bytes(self, kind: Optional[str] = None) -> int:
        """Total registered bytes, optionally restricted to one kind."""
        with self._lock:
            if kind is not None:
                return sum(e["bytes"]
                           for e in self._static.get(kind, {}).values())
            return sum(e["bytes"] for named in self._static.values()
                       for e in named.values())

    # -- live telemetry ------------------------------------------------------

    def device_stats(self, refresh: bool = False) -> Dict[str, int]:
        """Most recent device stats dict (``{}`` when the backend has
        none). Polls the provider at most once per ``poll_sec`` unless
        ``refresh=True`` forces it. Never raises."""
        now = time.monotonic()
        with self._lock:
            fresh = (now - self._last_poll) < self.poll_sec \
                and self._last_poll > 0.0
            if fresh and not refresh:
                return dict(self._last_stats)
        try:
            stats = self._stats_provider() or {}
        except Exception:
            stats = {}
        stats = {k: int(v) for k, v in stats.items()
                 if isinstance(v, (int, float)) and not isinstance(v, bool)}
        with self._lock:
            self._last_poll = now
            self._last_stats = stats
            return dict(stats)

    def headroom(self, refresh: bool = False) -> dict:
        """Budget math from the live view: ``{}`` without telemetry,
        else bytes_limit/bytes_in_use/budget_bytes/headroom_bytes
        (budget minus in-use; negative = overdraft) + the fraction."""
        stats = self.device_stats(refresh=refresh)
        limit = stats.get("bytes_limit")
        in_use = stats.get("bytes_in_use")
        if not limit or in_use is None:
            return {}
        budget = int(limit * self.budget_fraction)
        return {
            "bytes_limit": int(limit),
            "bytes_in_use": int(in_use),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use",
                                               in_use)),
            "budget_fraction": self.budget_fraction,
            "budget_bytes": budget,
            "headroom_bytes": budget - int(in_use),
        }

    def would_fit(self, nbytes: int,
                  refresh: bool = False) -> Optional[bool]:
        """Whether an extra allocation of ``nbytes`` stays within the
        budget. ``None`` means "no telemetry" — the caller decides
        (admission control admits, matching the CPU/interpret no-op
        contract)."""
        hr = self.headroom(refresh=refresh)
        if not hr:
            return None
        return int(nbytes) <= hr["headroom_bytes"]

    # -- export --------------------------------------------------------------

    def snapshot(self, refresh: bool = False) -> dict:
        """The one-call JSON view served by ``GET /v1/memory`` and
        embedded in postmortems/bench records: static report + live
        stats + budget math."""
        return {
            "static": self.static_report(),
            "device": self.device_stats(refresh=refresh),
            "headroom": self.headroom(),
        }

    def publish(self, registry: Any = None) -> None:
        """Set the HBM gauges on ``registry`` (default process
        registry). Best-effort: metric export never gates the caller."""
        try:
            if registry is None:
                from bigdl_tpu.observability.metrics import default_registry

                registry = default_registry()
            g = registry.gauge(
                "bigdl_tpu_hbm_bytes",
                "HBM bytes by kind: statically registered allocations "
                "(weights, kv_cache, ...) plus live device_in_use / "
                "device_peak / device_limit when the backend reports "
                "memory_stats().", labelnames=("kind",))
            report = self.static_report()
            for kind, total in report["by_kind"].items():
                g.labels(kind).set(float(total))
            stats = self.device_stats()
            for key, label in (("bytes_in_use", "device_in_use"),
                               ("peak_bytes_in_use", "device_peak"),
                               ("bytes_limit", "device_limit")):
                if key in stats:
                    g.labels(label).set(float(stats[key]))
            hr = self.headroom()
            if hr:
                registry.gauge(
                    "bigdl_tpu_hbm_headroom_bytes",
                    "HBM budget (budget_fraction * bytes_limit) minus "
                    "bytes_in_use; negative means overdraft.").set(
                        float(hr["headroom_bytes"]))
        except Exception:
            pass


_default_ledger: Optional[MemoryLedger] = None
_default_lock = threading.Lock()


def default_ledger() -> MemoryLedger:
    """The process-wide ledger (bench tooling, generation, postmortem
    fallbacks). The serving engine keeps its own when handed one."""
    global _default_ledger
    with _default_lock:
        if _default_ledger is None:
            _default_ledger = MemoryLedger()
        return _default_ledger


def reset_default_ledger() -> None:
    """Drop the process-wide ledger (tests)."""
    global _default_ledger
    with _default_lock:
        _default_ledger = None


def memory_report(ledger: Optional[MemoryLedger] = None) -> dict:
    """The embeddable memory report: a ledger snapshot plus flat
    headline scalars that compare across runs —
    ``hbm_static_total_bytes`` (registered allocations),
    ``hbm_device_peak_bytes`` (live peak, absent on CPU), and
    ``jit_peak_temp_bytes`` (largest per-executable scratch from the
    compile table's memory analysis: 0 unless the process ran with
    ``BIGDL_TPU_COMPILE_MEMORY=1``)."""
    led = ledger if ledger is not None else default_ledger()
    out = led.snapshot()
    out["hbm_static_total_bytes"] = out["static"]["total_bytes"]
    dev = out.get("device", {})
    if "peak_bytes_in_use" in dev:
        out["hbm_device_peak_bytes"] = dev["peak_bytes_in_use"]
    try:
        from bigdl_tpu.observability.compile_watch import compile_table

        out["jit_peak_temp_bytes"] = max(
            (ent.get("peak_temp_bytes", 0)
             for ent in compile_table().values()), default=0)
    except Exception:
        pass
    return out
