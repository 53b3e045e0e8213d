"""``mla_chunk_attn_device_ms`` (PR 47): device time of the latent chunk
attention kernel over the runs of the prefill programs, from the
reduction of the small recorded trace of ``benchmark/fixtures`` with and
without the kernel's group in it."""

import json
import re

import pytest

import _paths
from harness import layer_metrics, spec, trace_reduce

FIX = _paths.BENCH / "fixtures"
METRIC = _paths.BENCH / "layer_metrics" / "mla_chunk_attn_device_ms.py"
GROUP = _paths.BENCH / "trace_groups" / "mla_chunk_attn.json"


def _reduction(kernel_pattern):
    """The fixture's one program, jit_step, stands in for a prefill
    chunk and its custom call for the kernel."""
    expected = json.loads((FIX / "two_ops.expected.json").read_text())
    group = json.loads(GROUP.read_text())
    groups = {"prefill_programs": {"line": "XLA Modules",
                                   "patterns": ["^jit_step"]},
              "mla_chunk_attn": dict(group, patterns=[kernel_pattern])}
    return expected, trace_reduce.reduce(
        trace_reduce.load(FIX / "two_ops.xplane.pb"), groups,
        host_spans=expected["host_spans"])


def test_the_group_takes_the_chunk_kernel_inside_prefill_programs_only():
    group = json.loads(GROUP.read_text())
    assert group == spec.Cell("dots3-ep8-longdoc-closed",
                              _paths.ROOT).trace_groups()["mla_chunk_attn"]
    assert (group["line"], group["within"]) == ("XLA Ops",
                                                "prefill_programs")
    (pattern,) = group["patterns"]
    assert re.search(pattern, "%mla_chunk_attention.3 = f32[1,1024,16384]")
    assert re.search(pattern, "mla_chunk_attention")
    # a decode kernel's roofline never counts a chunk's kernel, nor the
    # other way round
    others = {p.stem: json.loads(p.read_text())["patterns"]
              for p in GROUP.parent.glob("*.json") if p != GROUP}
    for name, patterns in others.items():
        if name.endswith("_programs"):
            continue
        assert not any(re.search(q, "mla_chunk_attention") for q in patterns)
    for kernel in ("mla_decode_attention", "sparse_mla_decode",
                   "window_mla_decode", "decode_attention"):
        assert not re.search(pattern, kernel)


def test_reads_the_kernels_device_time_per_run_of_the_prefill_programs():
    expected, red = _reduction("^custom-call")
    value = layer_metrics.read_metric(METRIC, {"trace": red})
    assert value == pytest.approx(
        expected["kernels_us"] / 1e3 / expected["step_programs_calls"])


@pytest.mark.parametrize("obs", ["no_kernel", {"trace": None}, {}],
                         ids=["a_program_that_sweeps_in_xla_ops",
                              "untraced_run", "empty"])
def test_a_trace_without_the_kernel_reads_nothing(obs):
    if obs == "no_kernel":
        obs = {"trace": _reduction("^mla_chunk_attention")[1]}
        assert obs["trace"]["programs"]["prefill_programs"]["calls"]
        assert not obs["trace"]["groups"].get("mla_chunk_attn", {}).get(
            "seconds")
    assert layer_metrics.read_metric(METRIC, obs) is None


def test_benchmark_json_lists_the_metric_for_the_two_sparse_latent_cells():
    doc = json.loads((_paths.BENCH.parent / "BENCHMARK.json").read_text())
    (m,) = [m for m in doc["per_layer"]
            if m["name"] == "mla_chunk_attn_device_ms"]
    assert (m["layer"], m["source"], m["unit"], m["better"], m["moves"]) == (
        "kernels", "device_trace", "ms", "lower", "itl_p95_ms")
    assert m["workloads"] == ["dots3-ep8-longdoc-closed",
                              "deepseekv32-ep8-reason-closed"]
