"""The command itself, at toy widths on the CPU: the last-line schema of
each runner kind, the add-by-files property, and the refusal to measure
without a chip. Each case waits on one child process, with a timeout of
its own; no measured time is asserted."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import _paths

RUN_TIMEOUT_S = 420
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}
WALL_LINE = re.compile(r"^wall_s = (\d+\.\d+) budget 300\.0: (ok|OVER)$")


def _compared_lines(r, line=None):
    """Standard error's lines before its last, which must be the run's
    wall time beside the harness's budget; with ``line`` (the result)
    also holds its ``compared`` key, the last there, to what standard
    error printed."""
    lines = r.stderr.strip().splitlines()
    assert WALL_LINE.match(lines[-1]), lines[-1]
    if line is not None:
        assert list(line)[-1] == "compared"
        printed = [x.split()[1] for x in lines if x.startswith("compared ")]
        assert printed == list(line["compared"])
        assert all(set(v) in ({"value", "limit"}, {"value", "floor"})
                   for v in line["compared"].values())
    return lines[:-1]


def _wall(r, note):
    """The note line's ``wall_s`` and ``phases``: every name of the
    harness's list, seconds that add up to the wall time, which the
    last line of standard error repeats a moment later."""
    from harness import common

    assert list(note["phases"]) == list(common.PHASES)
    assert all(v >= 0.0 for v in note["phases"].values())
    assert sum(note["phases"].values()) == pytest.approx(note["wall_s"])
    assert note["budget_s"] == common.RUN_BUDGET_S == 300.0
    said = float(WALL_LINE.match(
        r.stderr.strip().splitlines()[-1]).group(1))
    assert note["wall_s"] <= said < note["wall_s"] + 5.0
    return note["phases"]


def _run(root, *args, timeout=RUN_TIMEOUT_S, program=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # a copy of the tree holds the benchmark only; the program comes
    # from the repository
    env["PYTHONPATH"] = str(_paths.ROOT) if program else ""
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"),
         "--root", str(root), *args],
        cwd=str(root), env=env, capture_output=True, text=True,
        timeout=timeout)


def _copy_tree(tmp_path):
    root = tmp_path / "tree"
    root.mkdir()
    shutil.copy(_paths.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(_paths.BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", ".out",
                                                  "__pycache__"))
    return root


def _digest(root):
    import hashlib

    out = {}
    for p in sorted((root / "benchmark").rglob("*")):
        if p.is_file() and ".out" not in p.parts and \
                "__pycache__" not in p.parts:
            out[str(p.relative_to(root))] = hashlib.sha256(
                p.read_bytes()).hexdigest()
    return out


def test_a_new_cell_is_added_by_files_and_entries_only(tmp_path):
    """A new configuration, traffic mix, cell and count-type per-layer
    metric dropped into a copy of the tree run with ``--tiny``; no file
    that was there is touched. This is also the tiny run of the serving
    runner: its last line is checked against the schema."""
    root = _copy_tree(tmp_path)
    before = _digest(root)
    bench_dir = root / "benchmark"
    config = json.loads(
        (bench_dir / "configs" / "mistral-7b-int4.json").read_text())
    config["why"] = "a copy under a new name, for the add-by-files test"
    config["tiny"]["engine"]["max_batch"] = 2
    (bench_dir / "configs" / "added-config.json").write_text(
        json.dumps(config))
    traffic = json.loads(
        (bench_dir / "traffic" / "chat-steady.json").read_text())
    traffic["tiny"]["arrivals"]["rate_rps"] = 4.0
    traffic["sampling"] = [{"share": 1.0, "temperature": 0.0, "top_k": 0}]
    (bench_dir / "traffic" / "added-mix.json").write_text(
        json.dumps(traffic))
    (bench_dir / "layer_metrics" / "admissions_per_step.json").write_text(
        json.dumps({
            "layer": "engine step", "source": "program_counter",
            "unit": "requests", "moves": "output_tokens_per_s",
            "reducer": "counter_ratio",
            "args": {"num": {"series": "bigdl_tpu_admissions_total"},
                     "den": {"series": "bigdl_tpu_engine_steps_total"}}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "added-config", "source": config["source"],
        "file": "benchmark/configs/added-config.json", "reduced": [],
        "why": "add-by-files test"})
    bench["workloads"].append({
        "name": "added-cell", "config": "added-config",
        "traffic": "added-mix", "chips": 1, "why": "add-by-files test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] != "train_tokens_per_s":
            m["workloads"].append("added-cell")
    bench["per_layer"].append({
        "name": "admissions_per_step", "unit": "requests",
        "better": "higher", "source": "program_counter",
        "layer": "engine step", "moves": "output_tokens_per_s",
        "workloads": ["added-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    sys.path.insert(0, str(_paths.BENCH))
    from harness import spec

    assert spec.check_benchmark(bench) == []

    r = _run(root, "--workload", "added-cell", "--seed", str(2 ** 31 + 9),
             "--seconds", "2", "--trace", "1", "--tiny")
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(line)
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["attempted"] == 8 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    # a CPU run writes counts only: no time, idle or roofline metric
    assert set(line["metrics"]) == {"admissions_per_step"}
    assert line["metrics"]["admissions_per_step"]["value"] > 0
    assert "busy_s" not in line["device"] and "breakdown" not in line
    after = _digest(root)
    added = set(after) - set(before)
    assert added == {"benchmark/configs/added-config.json",
                     "benchmark/traffic/added-mix.json",
                     "benchmark/layer_metrics/admissions_per_step.json"}
    assert all(after[k] == before[k] for k in before)
    notes = [json.loads(x) for x in r.stdout.strip().splitlines()[:-1]
             if x.startswith("{")]
    run_note = [n for n in notes if n.get("info") == "run"][0]
    assert run_note["samples"]["ttft"] == 8
    assert run_note["window_compiles"] == {"tracked": 0.0, "jax": 0}
    assert all(run_note["checks"].values())
    assert run_note["generator_late_ms"]["p99"] is not None
    phases = _wall(r, run_note)
    # the slab family: every serving phase but a layer check of its own
    assert all(phases[k] > 0 for k in phases if k != "layer_check")
    assert phases["layer_check"] == 0.0
    _compared_lines(r, line)


WIDE_MODULES = {
    "wide_reference": (
        "from harness.reference import (all_logits,  # noqa\n"
        "                               served_gap_limits, tolerance)\n"),
    "wide_weights": (
        "from harness.weights import build_model, canonical_params  # noqa\n"),
    "wide_costs": (
        "from harness.costs import (Dims, kv_bytes_per_token,  # noqa\n"
        "                           training_work)\n"
        "from harness import costs as _dense\n\n\n"
        "def serving_work(config, dims, records, kv_cache_dtype, trace_ab):\n"
        "    work = _dense.serving_work(config, dims, records,\n"
        "                               kv_cache_dtype, trace_ab)\n"
        "    work['wide_tokens_served'] = float(\n"
        "        sum(r.get('received', 0) for r in records))\n"
        "    return work\n"),
}
WIDE_READER = (
    'LAYER = "engine step"\nSOURCE = "program_counter"\nUNIT = "tokens"\n'
    'MOVES = "output_tokens_per_s"\n\n\ndef read(obs):\n'
    '    return (obs.get("work") or {}).get("wide_tokens_served")\n')


def test_a_configuration_of_another_architecture_is_files_and_entries_only(
        tmp_path):
    """A configuration that names three modules of its own (the fourth,
    ``generation``: ``test_bench_generation.py``), with a ``.py`` reader
    of a ``work`` key only its costs module returns and a hidden size
    that is not 4096 (5120 over 40 heads of 128), joins a copy of the
    tree by new files and new entries and runs ``--tiny``; no file that
    was there is touched. It rides on a traffic mix that is there. The
    guard that the next configuration's PR edits nothing
    under ``paths``."""
    root = _copy_tree(tmp_path)
    before = _digest(root)
    bench_dir = root / "benchmark"
    for stem, text in WIDE_MODULES.items():
        (bench_dir / "harness" / f"{stem}.py").write_text(text)
    (bench_dir / "layer_metrics" / "wide_tokens_served.py").write_text(
        WIDE_READER)
    config = json.loads(
        (bench_dir / "configs" / "mistral-7b-int4.json").read_text())
    config["why"] = "hidden 5120 with modules of its own, for the test"
    config["harness"] = {"reference": "wide_reference",
                         "weights": "wide_weights", "costs": "wide_costs"}
    config["hf_config"].update(hidden_size=5120, num_attention_heads=40)
    config["reference"].update(hidden=5120, heads=40)
    config["tiny"]["engine"]["max_batch"] = 2
    (bench_dir / "configs" / "wide-config.json").write_text(
        json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "wide-config", "source": config["source"],
        "file": "benchmark/configs/wide-config.json", "reduced": [],
        "why": "another-architecture test"})
    bench["workloads"].append({
        "name": "wide-cell", "config": "wide-config",
        "traffic": "chat-steady", "chips": 1,
        "why": "another-architecture test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] != "train_tokens_per_s":
            m["workloads"].append("wide-cell")
    bench["per_layer"].append({
        "name": "wide_tokens_served", "unit": "tokens",
        "better": "higher", "source": "program_counter",
        "layer": "engine step", "moves": "output_tokens_per_s",
        "workloads": ["wide-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    from harness import spec

    assert spec.check_benchmark(bench) == []
    cell = spec.Cell("wide-cell", root)
    assert cell.config["reference"]["hidden"] == 5120 != 4096
    assert cell.layer_metric_file("wide_tokens_served").suffix == ".py"
    # the fourth role, which the file leaves out, takes the default
    assert {m.__name__.split("@")[0] for m in cell.modules.values()} == {
        "harness.wide_reference", "harness.wide_weights",
        "harness.wide_costs", "harness.generation"}

    r = _run(root, "--workload", "wide-cell", "--seed", str(2 ** 31 + 27),
             "--seconds", "2", "--trace", "1", "--tiny")
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(line)
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert set(line["metrics"]) == {"wide_tokens_served"}
    assert line["metrics"]["wide_tokens_served"] == {
        "value": line["metrics"]["wide_tokens_served"]["value"],
        "unit": "tokens"}
    assert line["metrics"]["wide_tokens_served"]["value"] > 0
    after = _digest(root)
    assert set(after) - set(before) == {
        "benchmark/configs/wide-config.json",
        "benchmark/layer_metrics/wide_tokens_served.py",
        "benchmark/harness/wide_reference.py",
        "benchmark/harness/wide_weights.py",
        "benchmark/harness/wide_costs.py"}
    assert all(after[k] == before[k] for k in before)


def test_tiny_run_of_the_paged_cell_compares_each_number_with_its_limit():
    r = _run(_paths.ROOT, "--workload", "chatglm2-6b-docqa-shared",
             "--seed", str(2 ** 32 + 5), "--seconds", "2", "--trace", "0",
             "--tiny")
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(line) == LINE_KEYS
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["attempted"] == 6 and line["failed"] == 0
    assert line["metrics"] == {}          # no time from a CPU run
    note = [json.loads(x) for x in r.stdout.strip().splitlines()[:-1]
            if x.startswith("{") and '"info": "run"' in x][0]
    assert set(note["reference_rel_l2"]) == {"prefill", "decode"}
    assert 0 < max(note["reference_rel_l2"].values()) \
        <= note["reference_tolerance"]
    assert note["served"]["requests"] == 4
    assert note["served"]["longest"] >= 64      # a document and more
    # the last lines of standard error: each number beside its limit,
    # then the wall time beside its budget
    tail = _compared_lines(r, line)[-6:]
    assert tail[-1] == "checks failed: none"
    assert [x.split()[1] for x in tail[:-1]] == [
        "reference_rel_l2.prefill", "reference_rel_l2.decode",
        "prefill_gap_max", "decode_gap_max", "decode_gap_mean"]
    assert all(x.endswith(": ok") for x in tail[:-1])
    phases = _wall(r, note)
    # untraced: no trace to reduce; the paged family has no layer check
    assert phases["trace_reduction"] == 0.0 == phases["layer_check"]
    assert min(phases[k] for k in ("weights", "warmup", "window",
                                   "check_a_program", "canonical_tree",
                                   "check_a_reference",
                                   "check_b_served")) > 0


BROKEN_DRIVER = """
import sys
sys.path.insert(0, {bench!r})
sys.path.insert(0, {root!r})
import run
from bigdl_tpu.serving import engine

pushed = engine.LLMEngine._push_output


def altered(self, rid, out, *a, **kw):
    # the timed path broken where a token is produced: every token
    # that leaves the engine is the one after the token it computed
    out.new_token_ids = [t + 1 if t < 250 else t - 1
                         for t in out.new_token_ids]
    return pushed(self, rid, out, *a, **kw)


engine.LLMEngine._push_output = altered
sys.exit(run.main(sys.argv[1:]))
"""


def test_a_run_whose_engine_emits_altered_tokens_is_not_correct(tmp_path):
    """The whole of a run but the look for a chip, with the timed path
    broken underneath: every other check still passes (the counts are
    exact, the probe repeats, nothing compiles, the family's logits
    agree), and the comparison of what was served says no."""
    driver = tmp_path / "broken.py"
    driver.write_text(BROKEN_DRIVER.format(bench=str(_paths.BENCH),
                                           root=str(_paths.ROOT)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out_root = _copy_tree(tmp_path)       # the run's files go to a copy
    r = subprocess.run(
        [sys.executable, str(driver), "--root", str(out_root),
         "--workload", "mistral7b-batch-closed", "--seed", "31",
         "--seconds", "2", "--trace", "0", "--tiny"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=RUN_TIMEOUT_S)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    note = [json.loads(x) for x in r.stdout.strip().splitlines()[:-1]
            if x.startswith("{") and '"info": "run"' in x][0]
    failed = sorted(k for k, ok in note["checks"].items() if not ok)
    assert failed == ["served_tokens_within_reference_gap"]
    assert note["served"]["decode_gap_max"] > 1.0
    assert _compared_lines(r)[-1] == (
        "checks failed: ['served_tokens_within_reference_gap']")
    assert any(x.startswith("compared decode_gap_max") and
               x.endswith("OVER") for x in r.stderr.splitlines())


def test_tiny_run_of_the_training_runner_prints_the_schema():
    r = _run(_paths.ROOT, "--workload", "mistral7b-qlora-alpaca",
             "--seed", "7", "--seconds", "1", "--trace", "0", "--tiny")
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(line) == LINE_KEYS
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}          # no rate from a CPU run
    note = json.loads(r.stdout.strip().splitlines()[-2])
    assert note["checks"]["frozen_base_bit_identical"]
    assert note["checks"]["loss_fell"]
    phases = _wall(r, note)
    # the training runner has no server, drain or served comparison
    assert min(phases[k] for k in ("devices_ready", "weights",
                                   "check_a_reference", "check_a_program",
                                   "warmup", "window")) > 0
    assert phases["drain"] == phases["check_b_served"] == 0.0
    assert _compared_lines(r, line)[-1] == "checks failed: none"
    assert list(line["compared"]) == ["reference_rel_l2", "sample_loss_gap"]


def test_without_a_chip_it_refuses_to_measure():
    r = _run(_paths.ROOT, "--workload", "mistral7b-chat-steady",
             "--seed", "1", "--seconds", "1", "--trace", "0", timeout=120)
    assert r.returncode != 0
    assert "no CPU fallback" in r.stderr
    assert not [x for x in r.stdout.splitlines() if x.startswith("{")]


def test_an_unknown_workload_is_an_error_that_names_it():
    r = _run(_paths.ROOT, "--workload", "no-such-cell", "--tiny",
             timeout=120)
    assert r.returncode != 0
    assert "no workload 'no-such-cell'" in r.stderr


def test_without_the_program_beside_it_nothing_is_printed(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files
    under ``paths`` the command fails and prints no result."""
    root = _copy_tree(tmp_path)
    r = _run(root, "--workload", "mistral7b-chat-steady", "--seed", "1",
             "--seconds", "1", "--trace", "0", "--tiny", timeout=180,
             program=False)
    assert r.returncode != 0
    assert "bigdl_tpu" in r.stderr
    assert not [x for x in r.stdout.splitlines() if x.startswith("{")]
