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
    assert c.config["hf_config"] and spec.valid_name(c.traffic["runner"])
    assert (_paths.BENCH / "harness"
            / f"{c.traffic['runner']}_runner.py").exists()
    assert set(c.modules) == set(spec.MODULE_CONTRACT)
    names = {m["name"] for m in c.end_to_end()}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer()
    for m in c.per_layer():
        assert c.layer_metric_file(m["name"]).exists()


def _reader_doc(path):
    """What a reader's file states about its metric: a ``.json``'s
    keys, or a ``.py``'s ``LAYER``, ``SOURCE``, ``UNIT``, ``MOVES`` beside
    its ``read(obs)``."""
    if path.suffix == ".json":
        return json.loads(path.read_text())
    scope = {}
    exec(compile(path.read_text(), str(path), "exec"), scope)
    assert callable(scope["read"])
    return {"layer": scope["LAYER"], "source": scope["SOURCE"],
            "unit": scope["UNIT"], "moves": scope["MOVES"],
            "reducer": None, "args": scope.get("ARGS", {})}


def test_a_py_reader_states_its_metric_as_a_json_reader_does(tmp_path):
    path = tmp_path / "some_share.py"
    path.write_text(
        'LAYER = "kernels"\nSOURCE = "device_trace"\nUNIT = "%"\n'
        'MOVES = "itl_p95_ms"\n\n\ndef read(obs):\n'
        '    return (obs.get("work") or {}).get("some_key")\n')
    doc = _reader_doc(path)
    assert (doc["layer"], doc["source"], doc["unit"], doc["moves"]) == (
        "kernels", "device_trace", "%", "itl_p95_ms")
    assert layer_metrics.read_metric(path, {"work": {"some_key": 3.0}}) \
        == 3.0
    assert layer_metrics.read_metric(path, {"work": None}) is None


@pytest.mark.parametrize("metric", LAYER_METRICS)
def test_layer_metric_file_agrees_with_benchmark_json(metric):
    entry = [m for m in BENCH["per_layer"] if m["name"] == metric][0]
    path = spec.Cell(CELLS[0], _paths.ROOT).layer_metric_file(metric)
    doc = _reader_doc(path)
    for key in ("layer", "source", "unit", "moves"):
        assert doc[key] == entry[key], key
    assert doc["reducer"] in layer_metrics.REDUCERS or path.suffix == ".py"
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
    assert ref["hidden"] == hf["hidden_size"]
    for key in entry["reduced"]:
        assert not re.search(r"(_dim|_rank|hidden|intermediate|head)", key)
    for role, stem in doc.get("harness", {}).items():
        assert role in spec.MODULE_CONTRACT and spec.valid_name(stem)


@pytest.mark.parametrize("config", ["mistral-7b-int4",
                                    "chatglm2-6b-int4-pagedkv8"])
def test_the_two_dense_configurations_keep_their_published_widths(config):
    """Properties of these two, not of a configuration as such: hidden
    4096 split evenly over the heads, and the default three modules."""
    doc = json.loads(
        (_paths.BENCH / "configs" / f"{config}.json").read_text())
    ref, hf = doc["reference"], doc["hf_config"]
    assert ref["hidden"] == hf["hidden_size"] == 4096
    assert ref["heads"] * ref["head_dim"] == ref["hidden"]
    assert "harness" not in doc


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


def _tree_with_config(tmp_path, harness_block, modules=()):
    """A copy of the benchmark's data with the first configuration's
    file naming ``harness_block`` and ``modules`` (stem -> source)
    written beside copies of the three default modules."""
    shutil.copytree(_paths.BENCH / "configs",
                    tmp_path / "benchmark" / "configs")
    shutil.copytree(_paths.BENCH / "traffic",
                    tmp_path / "benchmark" / "traffic")
    hdir = tmp_path / "benchmark" / "harness"
    hdir.mkdir()
    for stem in spec.MODULE_CONTRACT:
        shutil.copy(_paths.BENCH / "harness" / f"{stem}.py", hdir)
    for stem, text in modules:
        (hdir / f"{stem}.py").write_text(text)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BENCH))
    path = tmp_path / BENCH["configs"][0]["file"]
    doc = json.loads(path.read_text())
    doc["harness"] = harness_block
    path.write_text(json.dumps(doc))
    cell = [w["name"] for w in BENCH["workloads"]
            if w["config"] == BENCH["configs"][0]["name"]][0]
    return cell


def test_a_configuration_names_its_three_modules(tmp_path):
    cell = _tree_with_config(
        tmp_path, {"costs": "other_costs"},
        [("other_costs", "from harness.costs import *  # noqa\n"
                         "MARK = 'other'\n")])
    c = spec.Cell(cell, tmp_path)
    assert c.modules["costs"].MARK == "other"
    assert c.modules["costs"].__file__.endswith("other_costs.py")
    assert c.modules["reference"].__file__ == str(
        tmp_path / "benchmark" / "harness" / "reference.py")
    # this tree's own modules are not replaced by the copy's
    from harness import costs

    assert not hasattr(costs, "MARK")


def test_a_named_module_that_is_absent_says_which_file(tmp_path):
    cell = _tree_with_config(tmp_path, {"reference": "latent_reference"})
    with pytest.raises(
            spec.SpecError,
            match=r"missing file: .*harness/latent_reference\.py.*"
                  r"reference module"):
        spec.Cell(cell, tmp_path)


@pytest.mark.parametrize("role,lacks,source", [
    ("reference", "served_gap_limits",
     "def all_logits(): pass\ndef tolerance(): pass\n"),
    ("weights", "canonical_params", "def build_model(): pass\n"),
    ("costs", "Dims.from_config",
     "class Dims: pass\ndef kv_bytes_per_token(): pass\n"
     "def serving_work(): pass\ndef training_work(): pass\n"),
    ("costs", "training_work",
     "class Dims:\n    @classmethod\n    def from_config(cls, c): pass\n"
     "def kv_bytes_per_token(): pass\ndef serving_work(): pass\n"),
])
def test_a_module_that_lacks_a_contract_function_says_which(
        tmp_path, role, lacks, source):
    cell = _tree_with_config(tmp_path, {role: "short_module"},
                             [("short_module", source)])
    with pytest.raises(spec.SpecError,
                       match=rf"short_module\.py: the {role} module of "
                             rf"config .* lacks {re.escape(lacks)}\(\)"):
        spec.Cell(cell, tmp_path)


def test_harness_block_names_roles_and_valid_stems_only(tmp_path):
    cell = _tree_with_config(tmp_path, {"kernels": "x"})
    with pytest.raises(spec.SpecError, match=r"unknown roles \['kernels'\]"):
        spec.Cell(cell, tmp_path)
    doc_path = tmp_path / BENCH["configs"][0]["file"]
    doc = json.loads(doc_path.read_text())
    doc["harness"] = {"costs": "../elsewhere"}
    doc_path.write_text(json.dumps(doc))
    with pytest.raises(spec.SpecError, match="is no valid name"):
        spec.Cell(cell, tmp_path)


def test_the_runners_import_no_architecture_module_by_name():
    for runner in sorted((_paths.BENCH / "harness").glob("*_runner.py")):
        text = runner.read_text()
        assert "cell.modules" in text
        for line in text.splitlines():
            if line.startswith(("from harness import", "import harness")):
                for stem in spec.MODULE_CONTRACT:
                    assert not re.search(rf"\b{stem}\b", line), (
                        runner.name, line)


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
