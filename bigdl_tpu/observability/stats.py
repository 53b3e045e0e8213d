"""Shared descriptive-statistics helpers for the observability stack.

One home for the percentile / median / EWMA math that used to be
hand-rolled in three places with three subtly different behaviors:

* ``utils/profiling.StepTimer.summary`` — linear-interpolation
  percentile (numpy's default method, without numpy),
* ``observability/sentinel`` baseline seeding — classic median
  (mean-of-two-middles on even length),
* the benchmark's latency percentiles — ``np.percentile`` with the
  default (linear) interpolation.

All three are the SAME function: ``np.percentile``'s default "linear"
method reduces to mean-of-two-middles at q=0.5, so ``median(xs)``
equals ``percentile(sorted(xs), 0.5)`` for both parities and
``percentile`` is bit-compatible with ``np.percentile(v, q * 100)``
(same ``lo + (hi - lo) * frac`` evaluation order).
tests/test_slo.py asserts value-identity against pinned r05-style lane
numbers.

Stdlib-only by design (see observability/metrics.py): bench code may
have numpy, the serving engine's observability path must not need it.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence


def percentile(sorted_samples: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile over PRE-SORTED samples, ``q``
    in [0, 1]. Matches ``np.percentile(samples, q * 100)`` (the default
    "linear" method) bit-for-bit: ``lo + (hi - lo) * frac``. Empty
    input returns NaN."""
    s = sorted_samples
    if not s:
        return float("nan")
    if len(s) == 1:
        return float(s[0])
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    frac = pos - lo
    a, b = float(s[lo]), float(s[hi])
    # numpy's lerp flips the anchor at frac >= 0.5 so the interpolant
    # stays monotone in floating point; mirror it for bit-identity
    if frac >= 0.5:
        return b - (b - a) * (1.0 - frac)
    return a + (b - a) * frac


def median(samples: Sequence[float]) -> float:
    """Median (mean of the two middles on even length) — exactly
    ``percentile(sorted(samples), 0.5)``."""
    return percentile(sorted(samples), 0.5)


def summarize(samples: Sequence[float],
              scale: float = 1.0) -> Optional[Dict[str, float]]:
    """The StepTimer summary block: count / mean / min / max /
    p50 / p90 / p99 / total over ``samples``, with min..p99 multiplied
    by ``scale`` (1e3 turns seconds into the ``_ms`` fields). None on
    empty input (callers omit the row)."""
    if not samples:
        return None
    s = sorted(samples)
    n = len(samples)
    return {
        "count": n,
        "mean": sum(samples) / n * scale,
        "min": s[0] * scale,
        "max": s[-1] * scale,
        "p50": percentile(s, 0.50) * scale,
        "p90": percentile(s, 0.90) * scale,
        "p99": percentile(s, 0.99) * scale,
        "total": sum(samples),
    }


#: the one smoothing constant the serving EWMAs share (engine TPOT /
#: dispatch overhead, sentinel metric tracks): 0.8 carry, 0.2 sample
EWMA_DECAY = 0.8


def ewma(prev: Optional[float], sample: float,
         decay: float = EWMA_DECAY) -> float:
    """One EWMA update. ``prev`` of None or 0.0 seeds with the sample
    (the engine's ``_tpot_ewma == 0.0`` idiom and the sentinel's
    ``None`` idiom are the same rule)."""
    if prev is None or prev == 0.0:
        return float(sample)
    return decay * prev + (1.0 - decay) * sample


__all__ = ["percentile", "median", "summarize", "ewma", "EWMA_DECAY"]
