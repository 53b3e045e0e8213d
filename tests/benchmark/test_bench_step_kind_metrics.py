"""The seven per-layer metrics that read what the phase clock says of a
step's kind and of the host's part of it (``bigdl_tpu_tpot_seconds{kind}``,
``bigdl_tpu_step_phase_seconds{phase, kind}``,
``bigdl_tpu_engine_loop_seconds_total{state}``), against a tiny engine's
own ``/metrics`` text at two instants; and the five accepted readers of
the relabelled histogram, which read what they read before it had a
``kind`` (counts and host-clock sums of a CPU run: the reducers are
checked, no time is asserted)."""

import json
import time
import urllib.request

import pytest

import _paths
from harness import layer_metrics, promtext

NEW = ("plain_step_ms", "plain_step_p95_ms", "chunk_step_ms",
       "plain_step_host_ms", "step_h2d_ms", "step_fetch_ms",
       "loop_wait_share")
ACCEPTED = ("step_device_ms", "step_host_ms", "step_dispatch_ms",
            "cache_host_ms", "queue_wait_p90_ms")
PHASES = "bigdl_tpu_step_phase_seconds"
WALL = "bigdl_tpu_tpot_seconds"
KINDS = ("plain", "chunk")
LOOP = "bigdl_tpu_engine_loop_seconds_total"


def _file(metric):
    return _paths.BENCH / "layer_metrics" / f"{metric}.json"


def _settled(eng, limit_s=60.0):
    """``/metrics`` once the engine's thread has gone idle. A client
    reads ``[DONE]`` from INSIDE the step that emitted its last token:
    the step observes itself into the histograms after that, so a scrape
    taken as soon as a response ends holds that step or not (a window
    between two such scrapes counted 58, 59 or 60 decoding steps,
    whichever side each edge's step fell on). With nothing unfinished,
    the loop's ``wait`` counter moves only after a step that found no
    work, which the last working step precedes."""
    def waited():
        return promtext.total(promtext.parse(eng.registry.render()), LOOP,
                              {"state": "wait"})

    deadline = time.monotonic() + limit_s
    seen = None
    while time.monotonic() < deadline:
        if eng.has_unfinished():
            seen = None
        elif seen is None:
            seen = waited()
        elif waited() > seen:
            return promtext.parse(eng.registry.render())
        time.sleep(0.005)
    raise AssertionError(f"the engine was not idle within {limit_s} s")


@pytest.fixture(scope="module")
def window():
    """``/metrics`` of a tiny paged engine behind the in-process server
    before and after three streamed requests: the second and the third
    are admitted while the first decodes, so their chunks ride decoding
    steps. Both scrapes wait for the engine to go idle (``_settled``),
    so the window holds every step of its three requests and none of
    the warm-up's."""
    from bigdl_tpu.observability import MetricsRegistry, RequestTracer
    from bigdl_tpu.serving import EngineConfig, LLMEngine
    from bigdl_tpu.serving.api_server import OpenAIServer
    from bigdl_tpu.utils.testing import tiny_random_model

    eng = LLMEngine(
        tiny_random_model(seed=0),
        EngineConfig(max_batch=4, max_seq=128, prefill_bucket=8,
                     prefill_chunk=8, kv_page_size=16,
                     prefix_sharing="on"),
        registry=MetricsRegistry(), tracer=RequestTracer(event_log_path=""))
    server = OpenAIServer(eng)
    httpd = server.serve(port=0, background=True)
    url = f"http://127.0.0.1:{httpd.server_address[1]}/v1/completions"

    def post(prompt, n):
        return urllib.request.urlopen(urllib.request.Request(
            url, data=json.dumps({"prompt": prompt, "max_tokens": n,
                                  "stream": True}).encode(),
            headers={"Content-Type": "application/json"}), timeout=120)

    try:
        first = promtext.parse(eng.registry.render())
        # every program compiled before the window, as in a cell
        with post(list(range(1, 20)), 4) as r:
            r.read()
        start = _settled(eng)
        with post(list(range(30, 49)), 60) as long:
            long.readline()             # decoding: its first token is out
            for prompt in (list(range(60, 79)), list(range(90, 100))):
                with post(prompt, 3) as r:
                    assert r.read().decode().rstrip().endswith(
                        "data: [DONE]")
            assert long.read().decode().rstrip().endswith("data: [DONE]")
        end = _settled(eng)
    finally:
        server.shutdown()
    return {"counters_start": start, "counters_end": end, "first": first}


def _delta(window, series, **labels):
    return promtext.delta(window["counters_start"], window["counters_end"],
                          series, labels)


@pytest.mark.parametrize("metric", NEW)
def test_metric_file_reads_a_float_from_the_program(window, metric):
    value = layer_metrics.read_metric(_file(metric), window)
    assert isinstance(value, float) and value >= 0.0
    doc = json.loads(_file(metric).read_text())
    args = doc["args"]
    if metric == "loop_wait_share":
        wait = _delta(window, args["num"]["series"], state="wait")
        step = _delta(window, args["num"]["series"], state="step")
        assert wait > 0.0 and step > 0.0
        assert value == pytest.approx(100.0 * wait / (wait + step))
    elif doc["reducer"] == "histogram_mean":
        n = _delta(window, args["series"] + "_count", **args["labels"])
        total = _delta(window, args["series"] + "_sum", **args["labels"])
        assert n >= 1 and value == pytest.approx(1000.0 * total / n)
        assert value > 0.0
    else:
        # a percentile lies between the least bound and the longest wall
        assert doc["reducer"] == "histogram_quantile"
        assert 0.1 <= value < 1000.0 * _delta(
            window, WALL + "_sum", kind="plain")


def test_a_chunk_that_rode_a_decoding_step_is_a_chunk_step(window):
    """A wall under ``chunk`` is a chunk that rode a decoding step, one
    a step, and the two kinds together are the steps that decoded."""
    chunks = _delta(window, "bigdl_tpu_prefill_chunks_total")
    # 19, 19 and 10 tokens in chunks of 8. The long request's first two
    # ran with no slot decoding; its last made its slot live, and the
    # step went on to decode; the five of the others rode its decode
    assert chunks == 3 + 3 + 2
    assert _delta(window, WALL + "_count", kind="chunk") == chunks - 2
    assert _delta(window, PHASES + "_count", phase="admission",
                  kind="chunk") == chunks
    decoded = _delta(window, PHASES + "_count", phase="device")
    # 60 tokens: the first is the prefill's, a decoding step each for the
    # rest, and the short requests' tokens rode those; exact, because
    # both edges of the window are settled
    assert decoded == _delta(window, WALL + "_count") == 59
    assert _delta(window, WALL + "_count", kind="plain") == decoded - 6


@pytest.mark.parametrize("metric", ACCEPTED)
def test_accepted_readers_read_the_sum_over_both_kinds(window, metric):
    """The relabelling moves none of them: each equals the by-hand sum
    over the kinds of its label, which is what the label held alone."""
    doc = json.loads(_file(metric).read_text())
    args = doc["args"]
    phase = args["labels"]["phase"]
    value = layer_metrics.read_metric(_file(metric), window)
    kinds = ("admission",) if phase == "queue_wait" else KINDS
    n = sum(_delta(window, PHASES + "_count", phase=phase, kind=k)
            for k in kinds)
    assert n >= 1 and n == _delta(window, PHASES + "_count", phase=phase)
    if doc["reducer"] == "histogram_mean":
        total = sum(_delta(window, PHASES + "_sum", phase=phase, kind=k)
                    for k in kinds)
        assert value == pytest.approx(1000.0 * total / n)
    else:
        s, e = window["counters_start"], window["counters_end"]
        assert value == pytest.approx(1000.0 * promtext.histogram_quantile(
            s, e, PHASES, args["q"], {"phase": phase, "kind": "admission"}))


def test_every_series_renders_from_the_first_scrape(window):
    first = window["first"]
    from bigdl_tpu.observability.tracing import (DECODE_STEP_PHASES,
                                                 WORKING_STEP_PHASES)

    for kind in KINDS:
        for phase in WORKING_STEP_PHASES + DECODE_STEP_PHASES:
            assert promtext.total(first, PHASES + "_count",
                                  {"phase": phase, "kind": kind}) == 0.0
        assert promtext.total(first, WALL + "_count", {"kind": kind}) == 0.0
    assert {"h2d", "fetch"} <= set(WORKING_STEP_PHASES)
    for state in ("wait", "step"):
        assert promtext.total(
            first, "bigdl_tpu_engine_loop_seconds_total",
            {"state": state}) is not None
    for phase in ("queue", "prefill"):
        assert promtext.total(
            first, "bigdl_tpu_request_phase_seconds_count",
            {"phase": phase}) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_series_reads_nothing(metric):
    """What the parent commit gives: one label a phase, no ``kind``, no
    ``h2d`` or ``fetch``, an unlabelled ``tpot_seconds``, no loop
    counter. The reader returns None and the line leaves the metric
    out; it does not raise."""
    old = promtext.parse(
        "bigdl_tpu_engine_steps_total 40\n"
        'bigdl_tpu_step_phase_seconds_sum{phase="host"} 1.6\n'
        'bigdl_tpu_step_phase_seconds_count{phase="host"} 400\n'
        'bigdl_tpu_tpot_seconds_bucket{le="0.1"} 400\n'
        'bigdl_tpu_tpot_seconds_bucket{le="+Inf"} 400\n'
        "bigdl_tpu_tpot_seconds_sum 9.5\n"
        "bigdl_tpu_tpot_seconds_count 400\n")
    assert layer_metrics.read_metric(
        _file(metric), {"counters_start": {}, "counters_end": old}) is None
