"""``prefill_chunk_device_ms`` (PR 29): device time of the prefill
programs over their runs, from the reduction of the small recorded trace
of ``benchmark/fixtures`` with and without a prefill program in it."""

import json

import pytest

import _paths
from harness import layer_metrics, trace_reduce

FIX = _paths.BENCH / "fixtures"
METRIC = _paths.BENCH / "layer_metrics" / "prefill_chunk_device_ms.py"


def _reduction(prefill_pattern):
    expected = json.loads((FIX / "two_ops.expected.json").read_text())
    groups = dict(expected["groups_given"])
    groups["prefill_programs"] = {"line": "XLA Modules",
                                  "patterns": [prefill_pattern]}
    return expected, trace_reduce.reduce(
        trace_reduce.load(FIX / "two_ops.xplane.pb"), groups,
        host_spans=expected["host_spans"])


def test_reads_device_time_per_run_of_the_prefill_programs():
    # the fixture's one program, jit_step, stands in for a prefill chunk
    expected, red = _reduction("^jit_step")
    value = layer_metrics.read_metric(METRIC, {"trace": red})
    assert value == pytest.approx(
        expected["step_programs_us"] / 1e3 / expected["step_programs_calls"])


@pytest.mark.parametrize("obs", [
    "no_prefill_program", {"trace": None}, {}],
    ids=["stretch_without_a_chunk", "untraced_run", "empty"])
def test_a_stretch_without_a_prefill_program_reads_nothing(obs):
    if obs == "no_prefill_program":
        obs = {"trace": _reduction("^jit_engine_prefill")[1]}
        assert not obs["trace"]["programs"].get(
            "prefill_programs", {}).get("calls")
    assert layer_metrics.read_metric(METRIC, obs) is None


def test_benchmark_json_lists_the_metric_for_the_cells_with_chunks():
    doc = json.loads((_paths.BENCH.parent / "BENCHMARK.json").read_text())
    (m,) = [m for m in doc["per_layer"]
            if m["name"] == "prefill_chunk_device_ms"]
    assert (m["layer"], m["source"], m["unit"], m["better"], m["moves"]) == (
        "model step", "device_trace", "ms", "lower", "itl_p95_ms")
    # not the bursty cell: its arrivals (the same at every seed) leave the
    # traced stretch, 8-11 s, inside a gap from 0.04 s to 15.99 s
    assert m["workloads"] == [
        "mistral7b-chat-steady", "chatglm2-6b-docqa-shared",
        "mistral7b-batch-closed"]
