"""Quality observability: quantization-error attribution + the live
quality sentinel for the serving fleet.

The offline quality evidence (ACCURACY.md, bench/accuracy_eval.py)
says low-bit formats are near quality-neutral; this module measures
that claim continuously, the same way the perf stack ratchets the
roofline:

* **Load-time attribution** — while a checkpoint quantizes
  (models/convert_base.Acc.linear), every converted linear records
  SNR, max-abs-error, and clip-saturation of the quantized weights vs
  the pre-quant floats into the active :class:`AttributionReport`
  (installed with :func:`collect_attribution`). The ranked worst-first
  table is served at ``GET /v1/quality`` and lands in the BENCH json
  ``quality`` block, so a Δppl regression can be blamed on specific
  layers/qtypes instead of "the model got worse".

* **QualitySentinel** — the PR 16 perf-sentinel machinery
  (EWMA + warmup/JSONL-seeded baseline + trip/recover dwell
  hysteresis) re-pointed at quality signals: the periodic
  teacher-forced NLL probe over the golden prompt set, plus the
  decode path's live chosen-token NLL / entropy / top-1 margin. A
  trip becomes a ``quality_regression`` flight event + postmortem +
  bounded auto-capture and a nonzero
  ``bigdl_tpu_quality_regression_total``.

* **Golden Δppl baselines** — the refreshed per-format deltas from
  ACCURACY.md, exposed as :data:`GOLDEN_PPL_DELTA` /
  :func:`golden_nll_allowance`: the per-qtype quality budget the
  bench ``nll_delta_vs_bf16`` ratchet and the canary NLL tolerance
  default against.

The sentinel half is stdlib-only (it inherits observability/sentinel);
the attribution math imports numpy lazily — it only ever runs inside
checkpoint conversion, which already requires numpy.
"""

from __future__ import annotations

import contextlib
import math
import os
import threading
from typing import Dict, List, Optional

from .sentinel import (
    PerfSentinel,
    _resolve_steps,
    validate_perf_history_path,
)

__all__ = [
    "QUALITY_METRICS",
    "GOLDEN_PPL_DELTA",
    "GOLDEN_PROBE_PROMPTS",
    "AttributionReport",
    "QualitySentinel",
    "collect_attribution",
    "current_attribution",
    "golden_nll_allowance",
    "resolve_quality_history",
    "resolve_quality_probe_steps",
    "resolve_quality_recover_steps",
    "resolve_quality_threshold",
    "resolve_quality_trip_steps",
    "validate_quality_history_path",
    "weight_error_stats",
]

# the watched quality signals. Directions: probe NLL (teacher-forced
# golden-prompt NLL), live chosen-token NLL, and entropy rising above
# baseline*(1+threshold) are bad (the model got less sure of the right
# thing); the top-1 margin FALLING below baseline*(1-threshold) is bad
# (the argmax is losing its lead — byte drift is next). All four are
# positive quantities so the shared multiplicative threshold applies.
QUALITY_METRICS = ("probe_nll", "token_nll", "entropy", "top1_margin")
_QUALITY_HIGHER_IS_BAD = {"probe_nll": True, "token_nll": True,
                          "entropy": True, "top1_margin": False}

#: refreshed per-format Δppl vs bf16 (fraction, ACCURACY.md after the
#: r5 imatrix-objective fix — regenerated by
#: `python -m bigdl_tpu.bench.accuracy_eval --size small`). This is
#: the quality budget each format is ALLOWED to cost: the golden
#: baseline the sentinel/canary tolerances seed from and the number
#: the bench `nll_delta_vs_bf16` ratchet shrinks.
GOLDEN_PPL_DELTA: Dict[str, float] = {
    "bf16": 0.000,
    "fp16": 0.000,
    "fp32": 0.000,
    "sym_int8": 0.000,
    "fp8_e4m3": 0.000,
    "fp8_e5m2": 0.002,     # not in the small-testbed sweep; e4m3 + pad
    "sym_int4": 0.000,     # measured -0.2% (noise); budget floors at 0
    "asym_int4": 0.003,
    "nf4": 0.000,
    "fp4": 0.003,
    "mixed_fp4": 0.000,
    "mixed_fp8": 0.000,
    "q2_k": 0.010,
    "iq2_xxs": 0.022,
    "iq2_xs": 0.014,       # worse of unweighted/imatrix
    "iq1_s": 0.123,
    "iq1_m": 0.082,        # worse of unweighted/imatrix
}

#: teacher-forced NLL probe prompts: small fixed token-id rows (taken
#: modulo the model's vocab at probe build time). Same spirit as the
#: canary's DEFAULT_PROMPTS but engine-local — the probe runs inside
#: the replica, no HTTP involved. All rows share one length so the
#: probe is a single fixed-shape forward (one compile, ever).
GOLDEN_PROBE_PROMPTS = (
    (1, 15, 29, 47, 8, 23, 60, 31, 5),
    (2, 9, 41, 17, 52, 3, 36, 58, 12),
    (1, 15, 29, 47, 8, 44, 21, 7, 50),
    (4, 33, 26, 55, 19, 11, 48, 2, 38),
)


def golden_nll_allowance(qtype: Optional[str]) -> float:
    """The per-token NLL delta (nats) the golden Δppl budget allows
    for ``qtype``: ppl = exp(mean NLL), so Δnll = ln(1 + Δppl).
    Unknown/None qtypes get the worst tracked budget (conservative:
    an unlisted format should not silently pass a tight gate)."""
    key = (qtype or "").strip().lower()
    if key.startswith("gguf_"):
        key = key[len("gguf_"):]
    if key in GOLDEN_PPL_DELTA:
        return math.log1p(max(0.0, GOLDEN_PPL_DELTA[key]))
    return math.log1p(max(GOLDEN_PPL_DELTA.values()))


# -- env knobs (every one validated by utils/env_check.py) ----------------


def resolve_quality_threshold(value=None) -> float:
    """Relative quality drift that counts as "past threshold":
    explicit value, else ``$BIGDL_TPU_QUALITY_THRESHOLD``, else 0.5.
    ValueError on a non-positive or non-numeric setting."""
    if value is None:
        value = os.environ.get("BIGDL_TPU_QUALITY_THRESHOLD")
    if value is None or value == "":
        return 0.5
    try:
        f = float(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"quality threshold must be a positive number, got "
            f"{value!r}")
    if f <= 0:
        raise ValueError(
            f"quality threshold must be a positive number, got {f}")
    return f


def resolve_quality_trip_steps(value=None) -> int:
    """Consecutive past-threshold steps before the quality sentinel
    trips: explicit value, else ``$BIGDL_TPU_QUALITY_TRIP_STEPS``,
    else 5."""
    return _resolve_steps(value, "BIGDL_TPU_QUALITY_TRIP_STEPS", 5,
                          "quality trip steps")


def resolve_quality_recover_steps(value=None) -> int:
    """Consecutive healthy steps before a tripped quality sentinel
    recovers (hysteresis dwell): explicit value, else
    ``$BIGDL_TPU_QUALITY_RECOVER_STEPS``, else 10."""
    return _resolve_steps(value, "BIGDL_TPU_QUALITY_RECOVER_STEPS", 10,
                          "quality recover steps")


def resolve_quality_probe_steps(value=None) -> int:
    """Teacher-forced NLL probe period in engine steps: explicit
    value, else ``$BIGDL_TPU_QUALITY_PROBE_STEPS``, else 0 (probe
    off). 0 is legal — the live decode signals still feed the
    sentinel; any other value must be a positive integer. The probe
    is one extra fixed-shape dispatch per period, OFF the pure-decode
    step budget, so it defaults off and deployments opt in."""
    if value is None:
        value = os.environ.get("BIGDL_TPU_QUALITY_PROBE_STEPS")
    if value is None or value == "":
        return 0
    try:
        n = int(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"quality probe steps must be a non-negative integer, got "
            f"{value!r}")
    if n < 0:
        raise ValueError(
            f"quality probe steps must be a non-negative integer, got "
            f"{n}")
    return n


def resolve_quality_history(value=None) -> Optional[str]:
    """Quality-history JSONL path: explicit value, else
    ``$BIGDL_TPU_QUALITY_HISTORY``, else None (in-memory baseline)."""
    if value is None:
        value = os.environ.get("BIGDL_TPU_QUALITY_HISTORY")
    if value is None or value == "":
        return None
    return value


def validate_quality_history_path(path: str) -> dict:
    """Report whether `path` is usable as the quality-history sink
    (utils/env_check.py surfaces this for BIGDL_TPU_QUALITY_HISTORY).
    Same contract as the perf-history check."""
    return validate_perf_history_path(path)


# -- load-time quantization-error attribution -----------------------------


def weight_error_stats(ref, deq) -> Dict[str, float]:
    """Per-tensor quantization-error stats of dequantized weights
    ``deq`` against the pre-quant floats ``ref`` (same shape):

    - ``snr_db``    — signal-to-noise ratio in dB
      (10·log10(Σref² / Σerr²)); HIGHER is better, +inf on exact.
    - ``max_abs_err`` — worst single-weight absolute error.
    - ``clip_sat``  — fraction of weights landing on the tensor's
      extreme representable magnitude (within 0.1% of max |deq|):
      a clamp-heavy encode saturates far more weights than the one
      true maximum a healthy scale maps there.
    - ``rel_err``   — RMS error over RMS signal (the linear-scale
      twin of snr_db, convenient for thresholds).
    """
    import numpy as np

    r = np.asarray(ref, np.float32).ravel()
    d = np.asarray(deq, np.float32).ravel()
    err = d - r
    sig = float(np.dot(r, r))
    noise = float(np.dot(err, err))
    if noise <= 0.0:
        snr_db = float("inf")
        rel = 0.0
    elif sig <= 0.0:
        snr_db = float("-inf")
        rel = float("inf")
    else:
        snr_db = 10.0 * math.log10(sig / noise)
        rel = math.sqrt(noise / sig)
    amax = float(np.max(np.abs(d))) if d.size else 0.0
    clip = (float(np.mean(np.abs(d) >= amax * (1.0 - 1e-3)))
            if amax > 0.0 else 0.0)
    return {
        "snr_db": round(snr_db, 3) if math.isfinite(snr_db) else snr_db,
        "max_abs_err": float(np.max(np.abs(err))) if err.size else 0.0,
        "clip_sat": round(clip, 6),
        "rel_err": round(rel, 6) if math.isfinite(rel) else rel,
    }


class AttributionReport:
    """Per-tensor quantization-error records collected during one
    checkpoint conversion, rankable worst-first.

    Thread-safe adds (conversion is single-threaded today, but the
    report outlives the load and is snapshotted by HTTP handler
    threads through the engine)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._records: List[dict] = []

    def add(self, name: str, qtype: str,
            stats: Dict[str, float]) -> None:
        rec = {"name": name, "qtype": qtype}
        rec.update(stats)
        with self._lock:
            self._records.append(rec)

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)

    def table(self, limit: Optional[int] = None) -> List[dict]:
        """Records ranked worst-first (ascending SNR — the layer most
        damaged by quantization leads), ties broken by name for a
        stable table across identical loads."""
        with self._lock:
            recs = list(self._records)
        recs.sort(key=lambda r: (r.get("snr_db", float("inf")),
                                 r.get("name", "")))
        return recs[:limit] if limit else recs

    def summary(self) -> Optional[dict]:
        """Compact headline block for stats polls and BENCH json:
        tensor count, worst/mean SNR, worst tensor name, max
        clip-saturation. None when nothing was recorded (float load,
        load_low_bit, GGUF passthrough)."""
        recs = self.table()
        finite = [r["snr_db"] for r in recs
                  if isinstance(r.get("snr_db"), (int, float))
                  and math.isfinite(r["snr_db"])]
        if not recs or not finite:
            return None
        worst = recs[0]
        return {
            "tensors": len(recs),
            "worst_name": worst["name"],
            "worst_qtype": worst["qtype"],
            "worst_snr_db": worst["snr_db"],
            "mean_snr_db": round(sum(finite) / len(finite), 3),
            "max_clip_sat": max(r.get("clip_sat", 0.0) for r in recs),
        }

    def to_doc(self, limit: int = 64) -> dict:
        """The ``GET /v1/quality`` attribution document."""
        return {"summary": self.summary(), "table": self.table(limit)}


_COLLECTOR_LOCK = threading.Lock()
_collector: Optional[AttributionReport] = None


def current_attribution() -> Optional[AttributionReport]:
    """The report installed by :func:`collect_attribution`, or None —
    convert_base.Acc.linear checks this once per tensor; no collector
    means no dequant round-trip and zero added load cost."""
    return _collector


@contextlib.contextmanager
def collect_attribution():
    """Install a fresh :class:`AttributionReport` for the duration of
    a checkpoint conversion and yield it. Nested loads serialize on
    the collector lock (conversion is process-wide state)."""
    global _collector
    report = AttributionReport()
    with _COLLECTOR_LOCK:
        prev = _collector
        _collector = report
        try:
            yield report
        finally:
            _collector = prev


# -- the quality sentinel -------------------------------------------------


class QualitySentinel(PerfSentinel):
    """The perf sentinel's EWMA/baseline/hysteresis machinery pointed
    at quality signals (:data:`QUALITY_METRICS`), with its own env
    knobs (``BIGDL_TPU_QUALITY_*``) and history sink. The engine feeds
    :meth:`observe` from the decode step's live telemetry and the
    periodic NLL probe; trip/recover callbacks get the same info dict
    shape as the perf sentinel's."""

    def __init__(self,
                 threshold: Optional[float] = None,
                 trip_steps: Optional[int] = None,
                 recover_steps: Optional[int] = None,
                 history_path: Optional[str] = None,
                 warmup_steps: int = 16,
                 on_trip=None, on_recover=None):
        hist = resolve_quality_history(history_path)
        super().__init__(
            threshold=resolve_quality_threshold(threshold),
            trip_steps=resolve_quality_trip_steps(trip_steps),
            recover_steps=resolve_quality_recover_steps(recover_steps),
            # "" (not None) so the base class never falls back to the
            # PERF history env var; restored to None below
            history_path=hist if hist is not None else "",
            warmup_steps=warmup_steps,
            on_trip=on_trip, on_recover=on_recover,
            metrics=QUALITY_METRICS,
            higher_is_bad=_QUALITY_HIGHER_IS_BAD)
        if hist is None:
            self.history_path = None

    def observe(self, probe_nll: Optional[float] = None,
                token_nll: Optional[float] = None,
                entropy: Optional[float] = None,
                top1_margin: Optional[float] = None) -> Optional[str]:
        """Fold one step's quality numbers in; returns ``"trip"`` /
        ``"recover"`` on a state transition, else None."""
        return self.observe_sample(
            {"probe_nll": probe_nll, "token_nll": token_nll,
             "entropy": entropy, "top1_margin": top1_margin})
