"""Reading the server's Prometheus text and the JSON reducers of the
per-layer metrics."""

import pytest

import _paths  # noqa: F401
from harness import layer_metrics, promtext

START = """
# HELP x
bigdl_tpu_tokens_generated_total 100
bigdl_tpu_engine_steps_total 10
bigdl_tpu_prefix_radix_tokens_total{kind="hit"} 50
bigdl_tpu_prefix_radix_tokens_total{kind="looked_up"} 100
bigdl_tpu_step_phase_seconds_bucket{phase="queue_wait",le="0.1"} 0
bigdl_tpu_step_phase_seconds_bucket{phase="queue_wait",le="0.2"} 0
bigdl_tpu_step_phase_seconds_bucket{phase="queue_wait",le="+Inf"} 0
bigdl_tpu_step_phase_seconds_sum{phase="dispatch"} 1.0
bigdl_tpu_step_phase_seconds_count{phase="dispatch"} 100
"""
END = """
bigdl_tpu_tokens_generated_total 700
bigdl_tpu_engine_steps_total 40
bigdl_tpu_prefix_radix_tokens_total{kind="hit"} 950
bigdl_tpu_prefix_radix_tokens_total{kind="looked_up"} 1100
bigdl_tpu_step_phase_seconds_bucket{phase="queue_wait",le="0.1"} 80
bigdl_tpu_step_phase_seconds_bucket{phase="queue_wait",le="0.2"} 100
bigdl_tpu_step_phase_seconds_bucket{phase="queue_wait",le="+Inf"} 100
bigdl_tpu_step_phase_seconds_sum{phase="dispatch"} 1.6
bigdl_tpu_step_phase_seconds_count{phase="dispatch"} 400
"""


@pytest.fixture()
def obs():
    return {"counters_start": promtext.parse(START),
            "counters_end": promtext.parse(END), "trace": None,
            "memory_peak_bytes": None, "peaks": None, "work": {}}


def test_counter_ratio_is_a_delta_over_a_delta(obs):
    v = layer_metrics.counter_ratio(obs, {
        "num": {"series": "bigdl_tpu_tokens_generated_total"},
        "den": {"series": "bigdl_tpu_engine_steps_total"}})
    assert v == pytest.approx(600 / 30)


def test_labelled_counter_ratio_and_scale(obs):
    v = layer_metrics.counter_ratio(obs, {
        "num": {"series": "bigdl_tpu_prefix_radix_tokens_total",
                "labels": {"kind": "hit"}},
        "den": {"series": "bigdl_tpu_prefix_radix_tokens_total",
                "labels": {"kind": "looked_up"}}, "scale": 100.0})
    assert v == pytest.approx(90.0)


def test_histogram_quantile_interpolates_inside_the_bucket(obs):
    v = layer_metrics.histogram_quantile(obs, {
        "series": "bigdl_tpu_step_phase_seconds",
        "labels": {"phase": "queue_wait"}, "q": 0.9, "scale": 1000.0})
    assert v == pytest.approx(150.0)


def test_histogram_mean_of_the_window(obs):
    v = layer_metrics.histogram_mean(obs, {
        "series": "bigdl_tpu_step_phase_seconds",
        "labels": {"phase": "dispatch"}, "scale": 1000.0})
    assert v == pytest.approx(2.0)


@pytest.mark.parametrize("reducer", ["trace_idle_share",
                                     "trace_program_share",
                                     "trace_busy_per_call",
                                     "trace_group_roofline", "memory_peak"])
def test_a_reader_with_nothing_to_read_returns_nothing(obs, reducer):
    args = {"group": "g", "per_call_of": "p", "bytes_key": "b"}
    assert layer_metrics.REDUCERS[reducer](obs, args) is None


def test_a_series_that_is_absent_reads_as_nothing(obs):
    assert layer_metrics.counter_ratio(obs, {
        "num": {"series": "no_such_total"},
        "den": {"series": "bigdl_tpu_engine_steps_total"}}) is None


def test_a_metric_may_bring_a_reader_of_its_own_as_a_python_file(obs,
                                                                 tmp_path):
    path = tmp_path / "steps_in_window.py"
    path.write_text(
        "from harness import promtext\n\n\n"
        "def read(obs):\n"
        "    return promtext.delta(obs['counters_start'],\n"
        "                          obs['counters_end'],\n"
        "                          'bigdl_tpu_engine_steps_total')\n")
    assert layer_metrics.read_metric(path, obs) == pytest.approx(30.0)
