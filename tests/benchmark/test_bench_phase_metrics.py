"""The four per-layer metrics that read the engine's phase clock, the
admission counters and the stream-delivery histogram, against a tiny
engine's own ``/metrics`` text at two instants (counts and host-clock
sums of a CPU run: the reducers are checked, no time is asserted)."""

import json
import urllib.request

import pytest

import _paths
from harness import layer_metrics, promtext

METRICS = ("step_host_ms", "cache_host_ms", "prefill_chunks_per_step",
           "stream_delivery_mean_ms")


@pytest.fixture(scope="module")
def window():
    """``/metrics`` of a tiny paged engine behind the in-process server
    before and after two streamed requests."""
    from bigdl_tpu.observability import MetricsRegistry, RequestTracer
    from bigdl_tpu.serving import EngineConfig, LLMEngine
    from bigdl_tpu.serving.api_server import OpenAIServer
    from bigdl_tpu.utils.testing import tiny_random_model

    eng = LLMEngine(
        tiny_random_model(seed=0),
        EngineConfig(max_batch=2, max_seq=64, prefill_bucket=8,
                     prefill_chunk=8, kv_page_size=16,
                     prefix_sharing="on"),
        registry=MetricsRegistry(), tracer=RequestTracer(event_log_path=""))
    server = OpenAIServer(eng)
    httpd = server.serve(port=0, background=True)
    url = f"http://127.0.0.1:{httpd.server_address[1]}/v1/completions"
    try:
        start = promtext.parse(eng.registry.render())
        for prompt in (list(range(1, 20)), list(range(1, 17)) + [90, 91, 92]):
            req = urllib.request.Request(
                url, data=json.dumps({"prompt": prompt, "max_tokens": 5,
                                      "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                assert r.read().decode().rstrip().endswith("data: [DONE]")
        end = promtext.parse(eng.registry.render())
    finally:
        server.shutdown()
    return {"counters_start": start, "counters_end": end}


@pytest.mark.parametrize("metric", METRICS)
def test_metric_file_reads_a_number_from_the_program(window, metric):
    path = _paths.BENCH / "layer_metrics" / f"{metric}.json"
    value = layer_metrics.read_metric(path, window)
    assert isinstance(value, float) and value > 0.0
    s, e = window["counters_start"], window["counters_end"]
    if metric == "prefill_chunks_per_step":
        chunks = promtext.delta(s, e, "bigdl_tpu_prefill_chunks_total")
        steps = promtext.delta(s, e, "bigdl_tpu_engine_steps_total")
        # 19 tokens are three chunks of 8; of the second prompt's 19
        # tokens 16 hit the first's page, leaving one chunk
        assert chunks == 4 and steps >= 8
        assert value == chunks / steps
    else:
        args = json.loads(path.read_text())["args"]
        n = promtext.delta(s, e, args["series"] + "_count",
                           args.get("labels"))
        total = promtext.delta(s, e, args["series"] + "_sum",
                               args.get("labels"))
        assert n >= 1 and value == pytest.approx(1000.0 * total / n)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_the_series_reads_nothing(metric):
    """What the parent commit gives: the reader returns None and the
    line leaves the metric out; it does not raise."""
    path = _paths.BENCH / "layer_metrics" / f"{metric}.json"
    old = promtext.parse(
        "bigdl_tpu_engine_steps_total 40\n"
        'bigdl_tpu_step_phase_seconds_sum{phase="dispatch"} 1.6\n'
        'bigdl_tpu_step_phase_seconds_count{phase="dispatch"} 400\n')
    assert layer_metrics.read_metric(
        path, {"counters_start": old, "counters_end": old}) is None
