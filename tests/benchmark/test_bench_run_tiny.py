"""The command itself, at toy widths on the CPU: the last-line schema of
each runner kind, the add-by-files property, and the refusal to measure
without a chip. Each case waits on one child process, with a timeout of
its own; no measured time is asserted."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import _paths

RUN_TIMEOUT_S = 420
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


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
