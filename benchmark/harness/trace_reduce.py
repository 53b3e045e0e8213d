"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
time per group of device operations, time per group of programs, and
the idle gaps labelled by what the host was doing.

Only ``jax.profiler.ProfileData`` is needed to read the file. The
reduction itself (``reduce``) works on plain tuples, so the fixture
test reads a small recorded file through the same path as a chip run.

Device planes are those whose name matches ``DEVICE_PLANE``; on them the
line ``OPS_LINE`` holds one event per executed operation (control-flow
operations such as a ``while`` contain the events of their bodies) and
``MODULES_LINE`` one event per executed program. Host planes hold the
threads' spans; the benchmark's own ``TraceAnnotation`` names are given
in ``host_spans``.
"""

from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = re.compile(r"^/host:")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP_N = 10

Event = Tuple[str, float, float]          # name, start_ns, duration_ns


def find_xplane(trace_dir: Path) -> Optional[Path]:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return files[-1] if files else None


def load(path: Path) -> List[Dict[str, Any]]:
    """``[{"name", "lines": [{"name", "events": [Event, ...]}]}]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def exclusive_times(events: Sequence[Event]) -> List[float]:
    """Each event's duration less the part its nested events cover
    (an event is nested when it lies inside an earlier, longer one)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    excl = [events[i][2] for i in range(len(events))]
    stack: List[int] = []
    for i in order:
        _, s, d = events[i]
        while stack and s >= events[stack[-1]][1] + events[stack[-1]][2]:
            stack.pop()
        if stack:
            excl[stack[-1]] -= d
        stack.append(i)
    return [max(0.0, x) for x in excl]


def _innermost_at(thread: Sequence[Event], mids: Sequence[float]
                  ) -> List[Optional[Event]]:
    """For each time of the rising ``mids``, the innermost event of one
    thread (whose events nest properly) that is open then."""
    evs = sorted(thread, key=lambda ev: (ev[1], -ev[2]))
    out: List[Optional[Event]] = []
    stack: List[Event] = []
    j = 0
    for m in mids:
        while j < len(evs) and evs[j][1] <= m:
            while stack and stack[-1][1] + stack[-1][2] <= evs[j][1]:
                stack.pop()
            stack.append(evs[j])
            j += 1
        while stack and stack[-1][1] + stack[-1][2] <= m:
            stack.pop()
        out.append(stack[-1] if stack else None)
    return out


def _group_of(name: str, groups: Dict[str, Dict[str, Any]], line: str,
              inside: Sequence[str] = ()) -> Optional[str]:
    """The first group (by name) of ``line`` whose pattern matches; a
    group with ``"within": <program group>`` takes only operations that
    ran inside a program of that group (``inside``)."""
    for gname in sorted(groups):
        g = groups[gname]
        if g.get("line", OPS_LINE) != line:
            continue
        if "within" in g and g["within"] not in inside:
            continue
        if any(re.search(p, name) for p in g["patterns"]):
            return gname
    return None


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(name: str, limit: int = 120) -> str:
    """An operation's trace name without its layout annotations, cut
    to ``limit`` characters: the trace names an operation by its whole
    HLO text."""
    return _LAYOUT.sub("", name)[:limit]


def reduce(planes: List[Dict[str, Any]],
           groups: Dict[str, Dict[str, Any]],
           host_spans: Sequence[str] = ()) -> Optional[Dict[str, Any]]:
    """The reduction. ``groups`` maps a group's name to ``{"patterns":
    [regex, ...], "line": OPS_LINE | MODULES_LINE}``. Returns None when
    the trace holds no device plane (a CPU run)."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        return None
    hosts = [p for p in planes if HOST_PLANE.match(p["name"])]

    def line_events(plane, name) -> List[Event]:
        return [ev for ln in plane["lines"] if ln["name"] == name
                for ev in ln["events"]]

    # the traced window: from the first to the last event of the device
    # operations and of the benchmark's own host spans
    marks: List[float] = []
    span_events: List[Event] = []
    span_threads: List[List[Event]] = []
    for p in hosts:
        for ln in p["lines"]:
            mine = [ev for ev in ln["events"] if ev[0] in host_spans]
            if mine:
                span_events.extend(mine)
                span_threads.append(ln["events"])
    for ev in span_events:
        marks += [ev[1], ev[1] + ev[2]]
    for p in devices:
        for ev in line_events(p, OPS_LINE):
            marks += [ev[1], ev[1] + ev[2]]
    if not marks:
        return None
    w0, w1 = min(marks), max(marks)
    window_ns = w1 - w0

    busy_ns = 0.0
    group_s: Dict[str, Dict[str, float]] = {
        g: {"seconds": 0.0, "calls": 0} for g, d in groups.items()
        if d.get("line", OPS_LINE) == OPS_LINE}
    program_s: Dict[str, Dict[str, float]] = {
        g: {"seconds": 0.0, "calls": 0} for g, d in groups.items()
        if d.get("line", OPS_LINE) == MODULES_LINE}
    by_name: Dict[str, float] = {}
    by_program: Dict[str, float] = {}
    unmatched_ns = 0.0
    total_excl_ns = 0.0
    for p in devices:
        ops = line_events(p, OPS_LINE)
        busy_ns += sum(e - s for s, e in _union(
            (ev[1], ev[1] + ev[2]) for ev in ops))
        modules = sorted(line_events(p, MODULES_LINE), key=lambda ev: ev[1])
        starts = [ev[1] for ev in modules]
        module_groups = [_group_of(ev[0], groups, MODULES_LINE)
                         for ev in modules]
        for ev, ex in zip(ops, exclusive_times(ops)):
            total_excl_ns += ex
            by_name[ev[0]] = by_name.get(ev[0], 0.0) + ex
            i = bisect.bisect_right(starts, ev[1]) - 1
            inside = ()
            if i >= 0 and ev[1] < modules[i][1] + modules[i][2] \
                    and module_groups[i] is not None:
                inside = (module_groups[i],)
            g = _group_of(ev[0], groups, OPS_LINE, inside)
            if g is None:
                unmatched_ns += ex
            else:
                group_s[g]["seconds"] += ex / 1e9
                group_s[g]["calls"] += 1
        for ev in line_events(p, MODULES_LINE):
            by_program[ev[0]] = by_program.get(ev[0], 0.0) + ev[2]
            g = _group_of(ev[0], groups, MODULES_LINE)
            if g is not None:
                program_s[g]["seconds"] += ev[2] / 1e9
                program_s[g]["calls"] += 1
    n_dev = len(devices)
    for table in (group_s, program_s):
        for g in table.values():
            g["seconds"] /= n_dev

    # idle gaps of the first device, each owned by the innermost host
    # span open at its midpoint on a thread that carries the
    # benchmark's spans
    first_ops = line_events(devices[0], OPS_LINE)
    merged = _union((ev[1], ev[1] + ev[2]) for ev in first_ops)
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]

    gaps.sort()
    mids = [(s + e) / 2.0 for s, e in gaps]
    per_thread = [_innermost_at(thread, mids) for thread in span_threads]
    gap_owner: Dict[str, float] = {}
    for i, (s, e) in enumerate(gaps):
        open_now = [t[i] for t in per_thread if t[i] is not None]
        name = (min(open_now, key=lambda ev: ev[2])[0] if open_now
                else "no host span open")
        gap_owner[name] = gap_owner.get(name, 0.0) + (e - s) / 1e9

    def top(table: Dict[str, float], scale: float, n: int = TOP_N
            ) -> List[List[Any]]:
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:n]
        return [[short_name(k), v * scale] for k, v in rows]

    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9 / n_dev,
        "devices": n_dev,
        "groups": group_s,
        "programs": program_s,
        "unmatched_share": (unmatched_ns / total_excl_ns
                            if total_excl_ns else 0.0),
        "device_ops": top(by_name, 1e-9 / n_dev),
        "device_ops_long": top(by_name, 1e-9 / n_dev, 60),
        "device_programs": top(by_program, 1e-9 / n_dev, 30),
        "idle_gaps": top(gap_owner, 1.0),
        "longest_gap_s": max((e - s for s, e in gaps), default=0.0) / 1e9,
        "host_span_calls": len(span_events),
    }
