"""Read the Prometheus text a ``/metrics`` endpoint serves into a flat
``{(series, ((label, value), ...)): number}`` map, and take deltas,
ratios and histogram quantiles from two such maps. Standard library
only."""

from __future__ import annotations

import re
from typing import Dict, Iterable, Optional, Tuple

Key = Tuple[str, Tuple[Tuple[str, str], ...]]
_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{([^}]*)\})?\s+(\S+)")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> Dict[Key, float]:
    out: Dict[Key, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line)
        if not m:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(3) or "")))
        try:
            out[(m.group(1), labels)] = float(m.group(4))
        except ValueError:
            continue
    return out


def _match(key: Key, series: str, labels: Dict[str, str]) -> bool:
    if key[0] != series:
        return False
    have = dict(key[1])
    return all(have.get(k) == v for k, v in labels.items())


def total(snap: Dict[Key, float], series: str,
          labels: Optional[Dict[str, str]] = None) -> Optional[float]:
    """Sum of every sample of ``series`` whose labels include
    ``labels``; None when there is none."""
    vals = [v for k, v in snap.items() if _match(k, series, labels or {})]
    return sum(vals) if vals else None


def delta(start: Dict[Key, float], end: Dict[Key, float], series: str,
          labels: Optional[Dict[str, str]] = None) -> Optional[float]:
    e = total(end, series, labels)
    if e is None:
        return None
    return e - (total(start, series, labels) or 0.0)


def histogram_delta(start, end, series: str,
                    labels: Optional[Dict[str, str]] = None
                    ) -> Iterable[Tuple[float, float]]:
    """``(upper bound, cumulative count in the window)`` pairs of the
    histogram ``series``, by rising bound."""
    rows = {}
    for k, v in end.items():
        if not _match(k, series + "_bucket", labels or {}):
            continue
        le = dict(k[1]).get("le")
        if le is None:
            continue
        bound = float("inf") if le in ("+Inf", "inf") else float(le)
        rows[bound] = rows.get(bound, 0.0) + v - start.get(k, 0.0)
    return sorted(rows.items())


def histogram_quantile(start, end, series: str, q: float,
                       labels: Optional[Dict[str, str]] = None
                       ) -> Optional[float]:
    """The ``q`` quantile (0..1) of what the histogram saw in the
    window, interpolated inside its bucket as Prometheus does; an
    observation in the +Inf bucket reads as the last finite bound."""
    rows = list(histogram_delta(start, end, series, labels))
    if not rows or rows[-1][1] <= 0:
        return None
    want = q * rows[-1][1]
    prev_b, prev_c = 0.0, 0.0
    for bound, cum in rows:
        if cum >= want:
            if bound == float("inf"):
                return prev_b
            if cum == prev_c:
                return bound
            return prev_b + (bound - prev_b) * (want - prev_c) / (cum - prev_c)
        prev_b, prev_c = bound, cum
    return prev_b


def histogram_mean(start, end, series: str,
                   labels: Optional[Dict[str, str]] = None
                   ) -> Optional[float]:
    n = delta(start, end, series + "_count", labels)
    s = delta(start, end, series + "_sum", labels)
    if not n or s is None:
        return None
    return s / n
