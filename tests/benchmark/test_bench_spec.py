"""BENCHMARK.json against the contract's static rules, and the
add-by-name resolution of cells, configurations, traffic and metrics."""

import json
import re
import shutil

import pytest

import _paths
from harness import layer_metrics, spec

BENCH = spec.load_benchmark(_paths.ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
LAYER_METRICS = [m["name"] for m in BENCH["per_layer"]]


def test_benchmark_json_keeps_the_static_rules():
    assert spec.check_benchmark(BENCH) == []


def test_benchmark_json_is_small_and_paths_are_the_two_directories():
    raw = (_paths.ROOT / "BENCHMARK.json").read_bytes()
    assert len(raw) <= 64 * 1024
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert BENCH["command"] == ["python3", "benchmark/run.py"]


@pytest.mark.parametrize("bad", ["has space", "tokens per second", "a,b",
                                 "x/y", "", "é", "-lead", "n" * 65])
def test_names_outside_the_allowed_characters_are_refused(bad):
    assert not spec.valid_name(bad)


@pytest.mark.parametrize("unit", ["ms", "tokens/s", "%", "GB", "s"])
def test_units_in_use_are_allowed(unit):
    assert spec.valid_unit(unit)
    assert unit in {m["unit"] for m in
                    BENCH["end_to_end"] + BENCH["per_layer"]}


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(cell):
    c = spec.Cell(cell, _paths.ROOT)
    assert c.config["hf_config"] and c.traffic["runner"] in ("serve",
                                                             "train")
    assert (_paths.BENCH / "harness"
            / f"{c.traffic['runner']}_runner.py").exists()
    names = {m["name"] for m in c.end_to_end()}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer()
    for m in c.per_layer():
        assert c.layer_metric_file(m["name"]).exists()


@pytest.mark.parametrize("metric", LAYER_METRICS)
def test_layer_metric_file_agrees_with_benchmark_json(metric):
    entry = [m for m in BENCH["per_layer"] if m["name"] == metric][0]
    path = _paths.BENCH / "layer_metrics" / f"{metric}.json"
    doc = json.loads(path.read_text())
    for key in ("layer", "source", "unit", "moves"):
        assert doc[key] == entry[key], key
    assert doc["reducer"] in layer_metrics.REDUCERS
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    target = e2e[entry["moves"]]
    for cell in entry.get("workloads", CELLS):
        assert "workloads" not in target or cell in target["workloads"]
    group = doc.get("args", {}).get("group")
    if group:
        assert (_paths.BENCH / "trace_groups" / f"{group}.json").exists()


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_configuration_states_source_reduced_and_assumed(config):
    entry = [c for c in BENCH["configs"] if c["name"] == config][0]
    doc = json.loads((_paths.ROOT / entry["file"]).read_text())
    assert doc["source"] == entry["source"]
    assert doc["reduced"] == entry["reduced"]
    assert doc["assumed"] and doc["deployment"]
    ref, hf = doc["reference"], doc["hf_config"]
    assert ref["hidden"] == hf["hidden_size"] == 4096
    assert ref["heads"] * ref["head_dim"] == ref["hidden"]
    for key in entry["reduced"]:
        assert not re.search(r"(_dim|_rank|hidden|intermediate|head)", key)


def test_unknown_names_say_which_file_is_missing(tmp_path):
    with pytest.raises(spec.SpecError, match="no workload 'nope'"):
        spec.Cell("nope", _paths.ROOT)
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"][0]["traffic"] = "absent-mix"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(_paths.BENCH / "configs",
                    tmp_path / "benchmark" / "configs")
    with pytest.raises(spec.SpecError,
                       match=r"missing file: .*traffic/absent-mix\.json"):
        spec.Cell(bench["workloads"][0]["name"], tmp_path)
    c = spec.Cell(CELLS[0], _paths.ROOT)
    with pytest.raises(spec.SpecError,
                       match=r"layer_metrics/no_such_metric\.json"):
        c.layer_metric_file("no_such_metric")


def test_unknown_device_kind_has_no_peaks():
    assert spec.peaks_for("TPU v5 lite", _paths.ROOT)["hbm_gbps"] == 819.0
    with pytest.raises(spec.SpecError, match="no published peaks"):
        spec.peaks_for("cpu", _paths.ROOT)


def test_harness_names_no_cell_configuration_or_model():
    banned = set(CELLS) | {c["name"] for c in BENCH["configs"]} | {
        w["traffic"] for w in BENCH["workloads"]} | {
        "mistral", "chatglm", "internlm", "llama"}
    for path in sorted((_paths.BENCH / "harness").glob("*.py")) + [
            _paths.BENCH / "run.py"]:
        text = path.read_text().lower()
        # the program's own module path models/llama.py may be cited
        text = text.replace("models/llama.py", "").replace(
            "llama as llama_mod", "").replace("llama_mod", "")
        text = text.replace("random_llama_params", "")
        for name in banned:
            assert name.lower() not in text, (path.name, name)
