"""Resolve the names in ``BENCHMARK.json`` to the benchmark's data files.

A cell names a configuration and a traffic mix; a per-layer metric names
itself. Each resolves to a file of that name under ``benchmark/``. A
name with no file is an error that says which file is missing, so that a
later PR adds a cell by adding files and entries only.

A configuration's file names the four modules that know its
architecture and how it generates, ``"harness": {"reference": <stem>,
"weights": <stem>, "costs": <stem>, "generation": <stem>}``: stems of
files under ``benchmark/harness/``, each defaulting to its role's name. The runners take them from the cell and
import none by name. ``MODULE_CONTRACT`` below is what each must define;
``harness/__init__.py`` says what each function is given and returns.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = "benchmark"
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# role -> what a module in that role defines; a dotted name is an
# attribute of a class (``Dims.from_config``)
MODULE_CONTRACT = {
    "reference": ("all_logits", "tolerance", "served_gap_limits"),
    "weights": ("build_model", "canonical_params"),
    "costs": ("Dims.from_config", "kv_bytes_per_token", "serving_work",
              "training_work"),
    "generation": ("program_rows", "reference_rows", "served_gaps"),
}


class SpecError(Exception):
    """A name in BENCHMARK.json that does not resolve, or a bad file."""


def load_json(path: Path) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file: {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: not JSON ({e})") from None


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(Path(root) / "BENCHMARK.json")


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def deep_update(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``base`` with ``over`` laid on top, nested dicts merged."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_update(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


class Cell:
    """One ``workloads`` entry with its configuration, traffic mix and
    metrics resolved to files."""

    def __init__(self, name: str, root: Path = ROOT, tiny: bool = False):
        self.root = Path(root)
        self.bench = load_benchmark(self.root)
        entries = [w for w in self.bench["workloads"] if w["name"] == name]
        if not entries:
            known = ", ".join(w["name"] for w in self.bench["workloads"])
            raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                            f"(known: {known})")
        self.entry = entries[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = [c for c in self.bench["configs"]
                     if c["name"] == self.entry["config"]]
        if not cfg_entry:
            raise SpecError(f"workload {name!r} names config "
                            f"{self.entry['config']!r}, which BENCHMARK.json "
                            "does not list under configs")
        self.config_name = self.entry["config"]
        self.config = load_json(self.root / cfg_entry[0]["file"])
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json(
            self.root / BENCH_DIR / "traffic" / f"{self.traffic_name}.json")
        if tiny:
            self.config = deep_update(self.config,
                                      self.config.get("tiny", {}))
            self.traffic = deep_update(self.traffic,
                                       self.traffic.get("tiny", {}))
        self.tiny = tiny
        self.modules = self._load_modules()

    def _load_modules(self) -> Dict[str, Any]:
        """The configuration's reference, weights, costs and generation
        modules, loaded from ``benchmark/harness/<stem>.py`` of this tree and
        held to ``MODULE_CONTRACT``."""
        named = self.config.get("harness", {})
        unknown = sorted(set(named) - set(MODULE_CONTRACT))
        if unknown:
            raise SpecError(f"config {self.config_name!r}: \"harness\" "
                            f"names unknown roles {unknown} (known: "
                            f"{sorted(MODULE_CONTRACT)})")
        out = {}
        for role, needs in MODULE_CONTRACT.items():
            stem = named.get(role, role)
            if not valid_name(stem):
                raise SpecError(f"config {self.config_name!r}: harness "
                                f"{role} module {stem!r} is no valid name")
            path = self.root / BENCH_DIR / "harness" / f"{stem}.py"
            if not path.exists():
                raise SpecError(f"missing file: {path}: config "
                                f"{self.config_name!r} names it as its "
                                f"{role} module")
            mod = import_file(f"harness.{stem}", path)
            for dotted in needs:
                obj = mod
                for part in dotted.split("."):
                    obj = getattr(obj, part, None)
                if not callable(obj):
                    raise SpecError(
                        f"{path}: the {role} module of config "
                        f"{self.config_name!r} lacks {dotted}()")
            out[role] = mod
        return out

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["end_to_end"]
                if _applies(m, self.name)]

    def per_layer(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["per_layer"] if _applies(m, self.name)]

    def layer_metric_file(self, metric: str) -> Path:
        base = self.root / BENCH_DIR / "layer_metrics"
        for suffix in (".json", ".py"):
            p = base / f"{metric}{suffix}"
            if p.exists():
                return p
        raise SpecError(f"missing file: {base / metric}.json (or .py): "
                        f"per-layer metric {metric!r} has no reader")

    def trace_groups(self) -> Dict[str, Dict[str, Any]]:
        base = self.root / BENCH_DIR / "trace_groups"
        return {p.stem: load_json(p) for p in sorted(base.glob("*.json"))}


def import_file(name: str, path: Path):
    """Import ``path`` as module ``name``, once. A file of another tree
    than this module's own (the tests point ``root`` at copies) goes
    under a name of its own and never replaces this tree's module."""
    import sys

    if path.resolve().parent != Path(__file__).resolve().parent:
        name = f"{name}@{path.resolve().parent}"
    have = sys.modules.get(name)
    if have is not None:
        return have
    sp = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(sp)
    sys.modules[name] = mod
    try:
        sp.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def peaks_for(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; a kind that the
    table lacks is an error, never a default."""
    table = load_json(Path(root) / BENCH_DIR / "harness" / "peaks.json")
    try:
        return table["chips"][device_kind]
    except KeyError:
        raise SpecError(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmark/harness/peaks.json (known: "
            f"{sorted(table['chips'])})") from None


def valid_name(s: Any) -> bool:
    return isinstance(s, str) and bool(NAME_RE.match(s))


def valid_unit(s: Any) -> bool:
    return isinstance(s, str) and bool(UNIT_RE.match(s))


def one_line(s: Any, limit: int = 200) -> bool:
    return (isinstance(s, str) and 1 <= len(s) <= limit
            and "\n" not in s and "\t" not in s)


def check_benchmark(bench: Dict[str, Any]) -> List[str]:
    """Every way ``bench`` breaks the contract's static rules; empty
    when it keeps them all. The tests assert it is empty."""
    bad: List[str] = []
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bench) != keys:
        bad.append(f"top-level keys {sorted(bench)} != {sorted(keys)}")
        return bad
    if not (isinstance(bench["run_seconds"], int)
            and 1 <= bench["run_seconds"] <= 51):
        bad.append("run_seconds out of 1..51")
    paths = bench["paths"]
    for c in bench["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config keys {sorted(c)}")
        if not valid_name(c.get("name")):
            bad.append(f"config name {c.get('name')!r}")
        for k in c.get("reduced", []):
            if not valid_name(k):
                bad.append(f"reduced key {k!r}")
        if not any(str(c.get("file", "")).startswith(p + "/")
                   for p in paths):
            bad.append(f"config file {c.get('file')!r} not under paths")
        if not one_line(c.get("why")) or not one_line(c.get("source")):
            bad.append(f"config {c.get('name')}: why/source not one line "
                       "of 1..200")
    names = [c["name"] for c in bench["configs"]]
    seen_pairs = set()
    for w in bench["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            if not valid_name(w.get(k)):
                bad.append(f"workload {k} {w.get(k)!r}")
        if w.get("config") not in names:
            bad.append(f"workload {w.get('name')}: unknown config")
        if w.get("chips") not in (1, 4):
            bad.append(f"workload {w.get('name')}: chips")
        if not one_line(w.get("why")):
            bad.append(f"workload {w.get('name')}: why")
        pair = (w.get("config"), w.get("traffic"))
        if pair in seen_pairs:
            bad.append(f"pair {pair} twice")
        seen_pairs.add(pair)
    used = {w["config"] for w in bench["workloads"]}
    for n in names:
        if n not in used:
            bad.append(f"config {n} used by no cell")
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for m in bench["end_to_end"]:
        allowed = {"name", "unit", "better", "bound", "source", "workloads"}
        if not set(m) <= allowed or not {"name", "unit", "better", "bound",
                                         "source"} <= set(m):
            bad.append(f"end_to_end keys {sorted(m)}")
        if m.get("source") not in ("host_clock", "device_trace"):
            bad.append(f"{m.get('name')}: end-to-end source")
        if not (isinstance(m.get("bound"), (int, float))
                and 0 < m["bound"] <= 0.1):
            bad.append(f"{m.get('name')}: bound")
    for m in bench["per_layer"]:
        allowed = {"name", "unit", "better", "source", "layer", "moves",
                   "workloads"}
        if not set(m) <= allowed or not {"name", "unit", "better", "source",
                                         "layer", "moves"} <= set(m):
            bad.append(f"per_layer keys {sorted(m)}")
        if m.get("source") not in SOURCES:
            bad.append(f"{m.get('name')}: source")
        if not one_line(m.get("layer")):
            bad.append(f"{m.get('name')}: layer")
        target = e2e.get(m.get("moves"))
        if target is None:
            bad.append(f"{m.get('name')}: moves {m.get('moves')!r} is no "
                       "end-to-end metric")
            continue
        for cell in m.get("workloads", cells):
            if not _applies(target, cell):
                bad.append(f"{m['name']} moves {target['name']}, which "
                           f"cell {cell} does not report")
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        if not valid_name(m.get("name")):
            bad.append(f"metric name {m.get('name')!r}")
        if not valid_unit(m.get("unit")):
            bad.append(f"{m.get('name')}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"{m.get('name')}: better")
        for cell in m.get("workloads", []):
            if cell not in cells:
                bad.append(f"{m.get('name')}: unknown cell {cell}")
    mnames = [m["name"] for m in metrics]
    for group in (mnames, cells, names):
        if len(set(group)) != len(group):
            bad.append(f"duplicate names in {group}")
    for cell in cells:
        e = [m for m in bench["end_to_end"] if _applies(m, cell)]
        if len([m for m in e if m["name"] != "setup_s"]) < 1:
            bad.append(f"cell {cell}: no end-to-end metric but setup_s")
        if not [m for m in bench["per_layer"] if _applies(m, cell)]:
            bad.append(f"cell {cell}: no per-layer metric")
    return bad
