"""Sample arithmetic: percentiles, spreads and the serving metrics taken
from the load generator's records. Standard library only."""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics; None of an empty sample."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def highest_supported_percentile(n: int, beyond: int = 10) -> Optional[int]:
    """The highest whole percentile with at least ``beyond`` of ``n``
    samples beyond it (choosing-metrics guide, section 1)."""
    for q in range(99, 49, -1):
        if samples_beyond(n, q) >= beyond:
            return q
    return None


def iqr_spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)``'s quartiles."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def serving_metrics(records: List[Dict[str, Any]], t0: float,
                    seconds: float) -> Dict[str, Any]:
    """End-to-end numbers of one window from the generator's records.

    A record has ``due`` (open loop) or None, ``sent``, ``first`` (time
    of the first streamed token or None), ``chunks`` (``[time, tokens]``
    pairs), ``expected`` tokens and ``ok``. All times are on one clock
    and the window is ``[t0, t0 + seconds)``. A request that failed, was
    refused or did not finish counts its time to first token as the
    window's length. A chunk of k tokens is k gaps of equal length.
    """
    t1 = t0 + seconds
    ttft, gaps = [], []
    tokens_in_window = 0
    late = []
    failed = 0
    for r in records:
        start = r["due"] if r.get("due") is not None else r["sent"]
        if r.get("due") is not None and r.get("sent") is not None:
            late.append((r["sent"] - r["due"]) * 1e3)
        if not r["ok"]:
            failed += 1
            ttft.append(seconds * 1e3)
        else:
            ttft.append((r["first"] - start) * 1e3)
        prev = None
        for t, k in r.get("chunks", []):
            if t0 <= t < t1:
                tokens_in_window += k
                if prev is not None and k > 0:
                    gaps.extend([(t - prev) * 1e3 / k] * k)
            prev = t
    out = {
        "attempted": len(records),
        "failed": failed,
        "n_ttft": len(ttft),
        "n_gaps": len(gaps),
        "tokens_in_window": tokens_in_window,
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
        "ttft_p50_ms": percentile(ttft, 50),
        "ttft_p90_ms": percentile(ttft, 90),
        "itl_p50_ms": percentile(gaps, 50),
        "itl_p95_ms": percentile(gaps, 95),
        "output_tokens_per_s": tokens_in_window / seconds,
        "late_p50_ms": percentile(late, 50),
        "late_p99_ms": percentile(late, 99),
        "ttft_ms": [round(x, 1) for x in ttft],
    }
    return out
