"""``step_device_ms`` (PR 26): the mean of the phase clock's ``device``
phase, read from a tiny engine's own ``/metrics`` text at two instants
(a host-clock sum of a CPU run: the reducer is checked, no time is
asserted)."""

import json

import pytest

import _paths
from harness import layer_metrics, promtext
from test_bench_phase_metrics import window  # noqa: F401  (the fixture)

METRICS = ("step_device_ms",)


@pytest.mark.parametrize("metric", METRICS)
def test_metric_file_reads_the_device_phase(window, metric):  # noqa: F811
    path = _paths.BENCH / "layer_metrics" / f"{metric}.json"
    spec = json.loads(path.read_text())
    assert spec["args"]["labels"] == {"phase": "device"}
    value = layer_metrics.read_metric(path, window)
    assert isinstance(value, float) and value > 0.0
    s, e = window["counters_start"], window["counters_end"]
    series, labels = spec["args"]["series"], spec["args"]["labels"]
    n = promtext.delta(s, e, series + "_count", labels)
    total = promtext.delta(s, e, series + "_sum", labels)
    # one sample per step that decoded, like the dispatch phase
    assert n >= 1 and n == promtext.delta(
        s, e, series + "_count", {"phase": "dispatch"})
    assert value == pytest.approx(1000.0 * total / n)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_phase_reads_nothing(metric):
    """A program that has no such phase: the reader returns None and
    the line leaves the metric out; it does not raise."""
    path = _paths.BENCH / "layer_metrics" / f"{metric}.json"
    old = promtext.parse(
        "bigdl_tpu_engine_steps_total 40\n"
        'bigdl_tpu_step_phase_seconds_sum{phase="dispatch"} 1.6\n'
        'bigdl_tpu_step_phase_seconds_count{phase="dispatch"} 400\n')
    assert layer_metrics.read_metric(
        path, {"counters_start": old, "counters_end": old}) is None
