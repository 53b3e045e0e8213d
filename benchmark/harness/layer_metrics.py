"""Per-layer metric readers.

Each per-layer metric of ``BENCHMARK.json`` has a file of its own under
``benchmark/layer_metrics/``: ``<metric>.json`` names one of the
reducers below with its arguments, or ``<metric>.py`` defines
``read(obs)`` itself. A reader that finds nothing to read returns None
and the metric is left out of the line.

``obs`` (what one traced run observed):
  ``counters_start``/``counters_end``  parsed ``/metrics`` at the
                                       window's edges (serving) or None
  ``trace``        the reduction of ``trace_reduce.reduce`` or None
  ``memory_peak_bytes``  peak on the fullest chip or None
  ``device_kind``  as JAX reports it (None on the CPU: device-time
                   readers then return None, never zero)
  ``peaks``        the chip's published peaks or None
  ``client``       what ``stats.serving_metrics`` took from the load
                   generator's records (serving) or None
  ``work``         counts of the traced stretch computed from shapes:
                   ``steps``, ``linear_weight_bytes``, ``decode_kv_bytes``,
                   ``decode_steps`` ...
"""

from __future__ import annotations

from pathlib import Path
from typing import Any, Callable, Dict, Optional

from harness import promtext, spec


def _scaled(v: Optional[float], args: Dict[str, Any]) -> Optional[float]:
    return None if v is None else v * float(args.get("scale", 1.0))


def histogram_quantile(obs, args):
    if obs.get("counters_end") is None:
        return None
    return _scaled(promtext.histogram_quantile(
        obs["counters_start"], obs["counters_end"], args["series"],
        float(args["q"]), args.get("labels")), args)


def histogram_mean(obs, args):
    if obs.get("counters_end") is None:
        return None
    return _scaled(promtext.histogram_mean(
        obs["counters_start"], obs["counters_end"], args["series"],
        args.get("labels")), args)


def counter_ratio(obs, args):
    """Delta of one counter over the delta of another, in the window."""
    if obs.get("counters_end") is None:
        return None
    s, e = obs["counters_start"], obs["counters_end"]
    num = promtext.delta(s, e, args["num"]["series"],
                         args["num"].get("labels"))
    den = promtext.delta(s, e, args["den"]["series"],
                         args["den"].get("labels"))
    if num is None or not den:
        return None
    return _scaled(num / den, args)


def client_value(obs, args):
    """A number the load generator's records gave (client side, host
    clock), for a cell in which it is not an end-to-end metric."""
    if obs.get("device_kind") is None:
        return None           # a CPU rehearsal yields no time
    return _scaled((obs.get("client") or {}).get(args["key"]), args)


def memory_peak(obs, args):
    v = obs.get("memory_peak_bytes")
    return _scaled(v, args) if v else None


def trace_idle_share(obs, args):
    tr = obs.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return _scaled(1.0 - tr["busy_s"] / tr["window_s"], args)


def trace_program_share(obs, args):
    """Device time inside the programs of one trace group over the
    device's busy time."""
    tr = obs.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    g = tr["programs"].get(args["group"])
    if g is None:
        return None
    return _scaled(g["seconds"] / tr["busy_s"], args)


def _calls(tr, group: str) -> float:
    g = tr["programs"].get(group)
    return float(g["calls"]) if g else 0.0


def trace_busy_per_call(obs, args):
    """Device busy time of the traced stretch over the number of times
    the programs of one group ran in it."""
    tr = obs.get("trace")
    if not tr:
        return None
    calls = _calls(tr, args["per_call_of"])
    if not calls:
        return None
    return _scaled(tr["busy_s"] / calls, args)


def trace_group_roofline(obs, args):
    """Share of its roofline that one group of device operations
    reached in the traced stretch: the least time the chip could take
    for the group's work (the larger of operations over the peak rate
    and bytes over the peak bandwidth) over the group's device time.
    The work comes from ``obs['work']`` under the keys the metric's file
    names, computed from shapes by ``costs``; with ``per_call_of`` it is
    the work of ONE run of that group's programs and is multiplied by
    how often they ran in the stretch."""
    tr, peaks, work = obs.get("trace"), obs.get("peaks"), obs.get("work")
    if not tr or not peaks or not work:
        return None
    g = tr["groups"].get(args["group"])
    if g is None or not g["seconds"]:
        return None
    times = _calls(tr, args["per_call_of"]) if "per_call_of" in args else 1.0
    flops = float(work.get(args.get("flops_key", ""), 0.0) or 0.0) * times
    nbytes = float(work.get(args.get("bytes_key", ""), 0.0) or 0.0) * times
    least = max(flops / (peaks["bf16_tflops"] * 1e12),
                nbytes / (peaks["hbm_gbps"] * 1e9))
    if least <= 0:
        return None
    return _scaled(least / g["seconds"], args)


REDUCERS: Dict[str, Callable[[Dict[str, Any], Dict[str, Any]],
                             Optional[float]]] = {
    f.__name__: f for f in (
        histogram_quantile, histogram_mean, counter_ratio, client_value,
        memory_peak,
        trace_idle_share, trace_program_share, trace_busy_per_call,
        trace_group_roofline)}


def read_metric(path: Path, obs: Dict[str, Any]) -> Optional[float]:
    if path.suffix == ".py":
        return spec.import_file(f"layer_metrics.{path.stem}", path).read(obs)
    doc = spec.load_json(path)
    try:
        reducer = REDUCERS[doc["reducer"]]
    except KeyError:
        raise spec.SpecError(
            f"{path}: unknown reducer {doc.get('reducer')!r} (known: "
            f"{sorted(REDUCERS)})") from None
    return reducer(obs, doc.get("args", {}))


def read_all(cell, obs: Dict[str, Any], counts_only: bool = False
             ) -> Dict[str, Dict[str, Any]]:
    """``{name: {"value", "unit"}}`` for every per-layer metric of the
    cell whose reader found something. ``counts_only`` (a CPU
    rehearsal) keeps the metrics that are counts and leaves out every
    time and share of the device."""
    out = {}
    for m in cell.per_layer():
        if counts_only and m["source"] != "program_counter":
            continue
        v = read_metric(cell.layer_metric_file(m["name"]), obs)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
