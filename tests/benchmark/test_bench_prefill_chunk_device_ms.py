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
    # the bursty cell since PR 38: its traced stretch, 16.5-19.5 s, lies
    # inside the burst whose 19 requests are due from 15.99 s to 17.31 s at
    # every seed (the old stretch, 8-11 s, lay in the gap from 0.04 s to
    # 15.99 s and held no chunk); the sparse-latent cell's stretch holds 9
    # chunks of 1024 rows. Not the DeepSeek-V2 cell: its stretch often
    # holds none.
    assert m["workloads"] == [
        "mistral7b-chat-steady", "chatglm2-6b-docqa-shared",
        "mistral7b-batch-closed", "mistral7b-chat-bursty",
        "dots3-ep8-longdoc-closed"]


def test_the_bursty_stretch_lies_inside_a_burst_at_every_seed():
    """The traced stretch of the bursty mix is chosen from its arrivals,
    which the file's ``order_seed`` fixes: requests arrive just before
    and inside it, and their prefill chunks, one a step, outlast its
    first second whatever the run's seed."""
    from harness import traffic

    mix = json.loads((_paths.BENCH / "traffic" / "chat-bursty.json")
                     .read_text())
    start, length = mix["trace_start_s"], mix["trace_seconds"]
    plans = [traffic.window_plan(mix, seed, 50.0, 32000)
             for seed in (1, 2 ** 31 + 7)]
    due = [[r["due"] for r in p["requests"]] for p in plans]
    assert due[0] == due[1]                      # the file's, not the seed's
    burst = [r for r in plans[0]["requests"]
             if start - 1.0 <= r["due"] <= start + length]
    assert len(burst) >= 12
    assert sum(start <= r["due"] < start + length for r in burst) >= 6
    chunks = sum(-(-r["prompt_len"] // 256) for r in burst)
    assert chunks >= 30                          # seconds of chunk steps
    assert start <= 0.4 * 50.0                   # serve_runner caps it
