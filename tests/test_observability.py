"""Observability tests: metrics registry semantics (labels, histogram
buckets, Prometheus golden text), request-span lifecycle, StepTimer
satellites, and /metrics + /v1/stats + profiler round-trips against a
live APIServer driving real requests through the engine."""

import json
import os
import re
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from bigdl_tpu.observability import (LATENCY_BUCKETS_S, MetricsRegistry,
                                     RequestTracer,
                                     validate_event_log_path)
from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params


class FakeModel:
    def __init__(self, params, cfg):
        self.params = params
        self.config = cfg
        self.hf_config = {"eos_token_id": None}

        from bigdl_tpu.models import llama as llama_mod

        class Fam:
            forward = staticmethod(llama_mod.forward)
            prefill = staticmethod(llama_mod.forward_last_token)
            new_cache = staticmethod(llama_mod.new_cache)

        self.family = Fam()


@pytest.fixture(scope="module")
def model():
    return FakeModel(random_llama_params(TINY_LLAMA, qtype="sym_int4",
                                         seed=0), TINY_LLAMA)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    r = MetricsRegistry()
    c = r.counter("t_total", "help")
    c.inc()
    c.inc(2.5)
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("t_gauge")
    g.set(5)
    g.inc(2)
    g.dec()
    snap = r.snapshot()
    assert snap["t_total"]["series"][0]["value"] == 3.5
    assert snap["t_gauge"]["series"][0]["value"] == 6.0


def test_labels_and_get_or_create():
    r = MetricsRegistry()
    c = r.counter("reqs_total", "x", labelnames=("reason",))
    c.labels("stop").inc(3)
    c.labels("length").inc()
    # same child handed back for the same label values
    assert c.labels("stop") is c.labels("stop")
    # get-or-create: identical declaration -> same family
    assert r.counter("reqs_total", "x", labelnames=("reason",)) is c
    # kind / labelnames mismatches are programming errors
    with pytest.raises(ValueError):
        r.gauge("reqs_total")
    with pytest.raises(ValueError):
        r.counter("reqs_total", labelnames=("other",))
    # unlabeled passthrough on a labeled family is an error
    with pytest.raises(ValueError):
        c.inc()
    with pytest.raises(ValueError):
        c.labels("a", "b")          # wrong arity


def test_invalid_names_rejected():
    r = MetricsRegistry()
    with pytest.raises(ValueError):
        r.counter("bad name")
    with pytest.raises(ValueError):
        r.counter("ok_name", labelnames=("bad-label",))


def test_histogram_bucket_counts():
    r = MetricsRegistry()
    h = r.histogram("lat_seconds", "x", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 2.0, 100.0):
        h.observe(v)
    text = r.render()
    # le is INCLUSIVE: 0.1 falls in the 0.1 bucket; cumulative counts
    assert 'lat_seconds_bucket{le="0.1"} 2' in text
    assert 'lat_seconds_bucket{le="1"} 3' in text
    assert 'lat_seconds_bucket{le="10"} 4' in text
    assert 'lat_seconds_bucket{le="+Inf"} 5' in text
    assert "lat_seconds_count 5" in text
    snap = r.snapshot()["lat_seconds"]["series"][0]
    assert snap["count"] == 5 and snap["sum"] == pytest.approx(102.65)


def test_latency_buckets_log_spaced():
    assert LATENCY_BUCKETS_S[0] == pytest.approx(1e-4)
    assert LATENCY_BUCKETS_S[-1] == pytest.approx(100.0)
    ratios = [b / a for a, b in zip(LATENCY_BUCKETS_S,
                                    LATENCY_BUCKETS_S[1:])]
    # buckets are rounded to 6 decimals, so allow some slack
    assert all(r == pytest.approx(10 ** (1 / 3), rel=1e-2)
               for r in ratios)


def test_prometheus_golden_text():
    r = MetricsRegistry()
    r.counter("app_requests_total", "Requests.",
              labelnames=("code",)).labels("200").inc(7)
    r.gauge("app_depth", "Depth.").set(2)
    h = r.histogram("app_wait_seconds", "Wait.", buckets=(0.5, 5.0))
    h.observe(0.25)
    h.observe(2.0)
    assert r.render() == (
        "# HELP app_depth Depth.\n"
        "# TYPE app_depth gauge\n"
        "app_depth 2\n"
        "# HELP app_requests_total Requests.\n"
        "# TYPE app_requests_total counter\n"
        'app_requests_total{code="200"} 7\n'
        "# HELP app_wait_seconds Wait.\n"
        "# TYPE app_wait_seconds histogram\n"
        'app_wait_seconds_bucket{le="0.5"} 1\n'
        'app_wait_seconds_bucket{le="5"} 2\n'
        'app_wait_seconds_bucket{le="+Inf"} 2\n'
        "app_wait_seconds_sum 2.25\n"
        "app_wait_seconds_count 2\n")


def test_label_escaping():
    r = MetricsRegistry()
    r.counter("esc_total", labelnames=("v",)).labels('a"b\\c\nd').inc()
    assert r'esc_total{v="a\"b\\c\nd"} 1' in r.render()


def test_summary_shape():
    r = MetricsRegistry()
    r.counter("c_total").inc(4)
    h = r.histogram("h_seconds", buckets=(1.0, 2.0))
    # empty histograms are omitted from the summary
    assert "h_seconds" not in r.summary()
    for v in (0.5, 1.5, 1.5, 1.5):
        h.observe(v)
    s = r.summary()
    assert s["c_total"] == 4.0
    hs = s["h_seconds"]
    assert hs["count"] == 4
    assert 1.0 <= hs["p50"] <= 2.0
    assert hs["mean"] == pytest.approx(1.25)


_SERIES_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? "
    r"(?:[-+]?[0-9]+(?:\.[0-9]+)?(?:[eE][-+]?[0-9]+)?|[-+]?Inf|NaN)$")


def assert_valid_prometheus(text: str) -> None:
    """Structural validation: every line is a comment or a sample;
    each histogram child's le='+Inf' bucket equals its _count."""
    inf_counts = {}
    counts = {}
    for line in text.rstrip("\n").splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*",
                            line), line
            continue
        assert _SERIES_RE.match(line), f"bad sample line: {line!r}"
        name, val = line.rsplit(" ", 1)
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?$", name)
        base, labelstr = m.group(1), m.group(2) or ""
        labels = frozenset(
            l for l in re.findall(r'[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"',
                                  labelstr)
            if not l.startswith("le="))
        if base.endswith("_bucket") and 'le="+Inf"' in labelstr:
            inf_counts[(base[:-len("_bucket")], labels)] = float(val)
        elif base.endswith("_count"):
            counts[(base[:-len("_count")], labels)] = float(val)
    assert inf_counts, "no histograms rendered"
    for key, v in inf_counts.items():
        assert counts.get(key) == v, key


# ---------------------------------------------------------------------------
# StepTimer satellites
# ---------------------------------------------------------------------------

def test_steptimer_summary_fields():
    from bigdl_tpu.utils.profiling import StepTimer

    t = StepTimer()
    for v in (0.010, 0.030, 0.020):
        t.record("step", v)
    s = t.summary()["step"]
    assert s["count"] == 3
    assert s["min_ms"] == pytest.approx(10.0)
    assert s["max_ms"] == pytest.approx(30.0)
    assert s["p50_ms"] == pytest.approx(20.0)
    assert s["mean_ms"] == pytest.approx(20.0)


def test_steptimer_measure_exception_records_nothing():
    from bigdl_tpu.utils.profiling import StepTimer

    t = StepTimer()
    with pytest.raises(RuntimeError):
        with t.measure("boom"):
            raise RuntimeError("inside")
    assert "boom" not in t.times
    with t.measure("fine"):
        pass
    assert len(t.times["fine"]) == 1


def test_steptimer_publishes_to_registry():
    from bigdl_tpu.utils.profiling import StepTimer

    r = MetricsRegistry()
    t = StepTimer(metrics_prefix="unit_test", registry=r)
    t.record("phase", 0.5)
    assert "unit_test_phase_seconds_count 1" in r.render()


# ---------------------------------------------------------------------------
# request tracer
# ---------------------------------------------------------------------------

def test_span_lifecycle_ordering():
    tr = RequestTracer(event_log_path="")     # "" -> no sink
    span = tr.start("r1", prompt_len=7)
    tr.admitted("r1")
    tr.first_token("r1")
    done = tr.finish("r1", "stop", n_generated=5)
    assert done is span
    ts = [t for t, _ in span.events]
    assert ts == sorted(ts)
    assert [k for _, k in span.events] == \
        ["enqueue", "admit", "first_token", "finish"]
    for k in ("queue_wait_s", "prefill_s", "ttft_s", "decode_s"):
        assert getattr(span, k) >= 0.0, k
    assert span.tpot_s >= 0.0          # 5 tokens -> decode_s / 4
    assert span.finish_reason == "stop"
    assert tr.get("r1") is None        # moved to the ring buffer
    snap = tr.snapshot()
    assert snap["active"] == []
    assert snap["recent"][0]["request_id"] == "r1"
    assert snap["recent"][0]["n_generated"] == 5


def test_span_preemption_resets_queue_clock():
    tr = RequestTracer(event_log_path="")
    span = tr.start("r1")
    tr.admitted("r1")
    tr.first_token("r1")
    t_enq0 = span.t_enqueued
    tr.preempted("r1")
    assert span.n_preemptions == 1
    assert span.t_admitted is None
    assert span.t_enqueued >= t_enq0
    tr.admitted("r1")                  # resume
    assert span.queue_wait_s >= 0.0
    # first_token is one-shot: the resume must not move it
    t_ft = span.t_first_token
    tr.first_token("r1")
    assert span.t_first_token == t_ft


def test_tracer_ring_buffer_capacity():
    tr = RequestTracer(capacity=4, event_log_path="")
    for i in range(10):
        tr.start(f"r{i}")
        tr.finish(f"r{i}", "stop")
    snap = tr.snapshot()
    assert len(snap["recent"]) == 4
    assert snap["recent"][-1]["request_id"] == "r9"


def test_tracer_jsonl_sink(tmp_path):
    path = str(tmp_path / "events.jsonl")
    tr = RequestTracer(event_log_path=path)
    tr.start("r1", prompt_len=3)
    tr.admitted("r1")
    tr.first_token("r1")
    tr.finish("r1", "length", n_generated=2)
    tr.close()
    lines = [json.loads(ln) for ln in open(path)]
    assert [ln["event"] for ln in lines] == \
        ["enqueue", "admit", "first_token", "finish"]
    assert all(ln["request_id"] == "r1" for ln in lines)
    assert lines[0]["prompt_len"] == 3
    assert lines[-1]["reason"] == "length"


def test_tracer_env_var_sink(tmp_path, monkeypatch):
    path = str(tmp_path / "env_events.jsonl")
    monkeypatch.setenv("BIGDL_TPU_EVENT_LOG", path)
    tr = RequestTracer()
    tr.start("r1")
    tr.finish("r1", "stop")
    tr.close()
    assert len(open(path).readlines()) == 2


def test_tracer_sink_failure_disables_quietly(tmp_path):
    tr = RequestTracer(event_log_path=str(tmp_path / "no" / "dir" / "f"))
    tr.start("r1")                     # must not raise
    assert tr._sink_dead
    tr.finish("r1", "stop")            # still fine


def test_validate_event_log_path(tmp_path):
    good = validate_event_log_path(str(tmp_path / "ok.jsonl"))
    assert good["writable"] is True
    bad = validate_event_log_path("/nonexistent_dir_xyz/f.jsonl")
    assert bad["writable"] is False and "error" in bad


def test_env_check_reports_event_log(tmp_path, monkeypatch):
    from bigdl_tpu.utils import env_check

    monkeypatch.setenv("BIGDL_TPU_EVENT_LOG", str(tmp_path / "e.jsonl"))
    info = env_check.collect()
    assert info["event_log"]["writable"] is True
    assert "BIGDL_TPU_EVENT_LOG" in info["env"]


# ---------------------------------------------------------------------------
# engine instrumentation (real requests, fresh registry)
# ---------------------------------------------------------------------------

def test_engine_metrics_end_to_end(model):
    reg = MetricsRegistry()
    tr = RequestTracer(event_log_path="")
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128),
                    registry=reg, tracer=tr)
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]
    eng.generate(prompts, SamplingParams(max_tokens=5))

    s = reg.summary()
    assert s["bigdl_tpu_admissions_total"] == 3
    assert s['bigdl_tpu_requests_finished_total{reason="length"}'] == 3
    assert s["bigdl_tpu_tokens_generated_total"] == 15
    assert s["bigdl_tpu_ttft_seconds"]["count"] == 3
    # queue wait and prefill are the phase clock's per-request samples
    assert s['bigdl_tpu_step_phase_seconds{phase="queue_wait",'
             'kind="admission"}']["count"] == 3
    assert s['bigdl_tpu_step_phase_seconds{phase="prefill",'
             'kind="admission"}']["count"] == 3
    assert not [k for k in s if k.startswith(
        "bigdl_tpu_request_phase_seconds") and (
            'phase="queue"' in k or 'phase="prefill"' in k)]
    assert s['bigdl_tpu_request_phase_seconds{phase="decode"}'][
        "count"] == 3
    # 5 tokens per request -> 4 decode steps each; batching makes the
    # exact step count scheduling-dependent, but >= 4 must have run
    assert sum(s.get('bigdl_tpu_tpot_seconds{kind="%s"}' % kd,
                     {"count": 0})["count"]
               for kd in ("plain", "chunk")) >= 4
    assert s["bigdl_tpu_engine_steps_total"] >= 4
    # drained engine: gauges back to zero
    assert s["bigdl_tpu_slot_occupancy"] == 0
    assert s["bigdl_tpu_queue_depth"] == 0

    # spans landed in the tracer ring with consistent phase math
    recent = tr.snapshot()["recent"]
    assert len(recent) == 3
    assert all(r["finish_reason"] == "length" for r in recent)
    assert all(r["n_generated"] == 5 for r in recent)

    text = reg.render()
    assert_valid_prometheus(text)
    # acceptance criterion: every required family present on /metrics
    for needle in (
            "# TYPE bigdl_tpu_request_phase_seconds histogram",
            "# TYPE bigdl_tpu_ttft_seconds histogram",
            "# TYPE bigdl_tpu_tpot_seconds histogram",
            "# TYPE bigdl_tpu_slot_occupancy gauge",
            "# TYPE bigdl_tpu_queue_depth gauge",
            "# TYPE bigdl_tpu_kernel_probe_total counter",
            "# TYPE bigdl_tpu_spec_accept_ratio histogram",
            'bigdl_tpu_step_phase_seconds_bucket{phase="queue_wait",'
            'kind="admission",le=',
            'bigdl_tpu_step_phase_seconds_bucket{phase="prefill",'
            'kind="admission",le=',
            'bigdl_tpu_request_phase_seconds_bucket{phase="decode",le=',
            'bigdl_tpu_tpot_seconds_bucket{kind="plain",le=',
            'bigdl_tpu_tpot_seconds_bucket{kind="chunk",le=',
    ):
        assert needle in text, needle

    snap = eng.stats_snapshot()
    assert snap["slots"] == {"total": 2, "active": 0}
    assert snap["queue_depth"] == 0
    assert snap["metrics"]["bigdl_tpu_admissions_total"] == 3
    json.dumps(snap)                   # must be JSON-serializable


def test_engine_preemption_metrics(model):
    reg = MetricsRegistry()
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128,
                                        preempt_after_steps=2),
                    registry=reg)
    eng.add_request("a", [1, 2, 3], SamplingParams(max_tokens=30))
    eng.add_request("b", [4, 5, 6], SamplingParams(max_tokens=4))
    while eng.has_unfinished():
        eng.step()
    s = reg.summary()
    assert s["bigdl_tpu_preemptions_total"] >= 1
    assert s["bigdl_tpu_stall_guard_trips_total"] >= 1
    # the preempted request re-admits: more admissions than requests
    assert s["bigdl_tpu_admissions_total"] >= 3


def test_abort_counted(model):
    reg = MetricsRegistry()
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128),
                    registry=reg)
    eng.add_request("a", [1, 2, 3], SamplingParams(max_tokens=4))
    eng.add_request("queued", [4, 5, 6], SamplingParams(max_tokens=4))
    eng.abort_request("queued")
    while eng.has_unfinished():
        eng.step()
    s = reg.summary()
    assert s['bigdl_tpu_requests_finished_total{reason="abort"}'] == 1
    assert s['bigdl_tpu_requests_finished_total{reason="length"}'] == 1


# ---------------------------------------------------------------------------
# HTTP round-trip: /metrics, /v1/stats, profiler endpoints
# ---------------------------------------------------------------------------

def test_server_metrics_roundtrip(model, tmp_path):
    from bigdl_tpu.serving.api_server import OpenAIServer

    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128),
                    registry=MetricsRegistry(),
                    tracer=RequestTracer(event_log_path=""))
    server = OpenAIServer(eng)
    httpd = server.serve(port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        # drive a real request through the engine first
        req = urllib.request.Request(
            f"{base}/v1/completions",
            data=json.dumps({"prompt": [1, 2, 3, 4],
                             "max_tokens": 6}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert json.loads(r.read())["usage"]["completion_tokens"] == 6

        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        assert_valid_prometheus(text)
        assert "bigdl_tpu_ttft_seconds_count 1" in text
        assert "bigdl_tpu_admissions_total 1" in text
        assert "# TYPE bigdl_tpu_kernel_probe_total counter" in text
        assert "# TYPE bigdl_tpu_spec_accept_ratio histogram" in text

        with urllib.request.urlopen(f"{base}/v1/stats", timeout=30) as r:
            stats = json.loads(r.read())
        assert stats["slots"]["total"] == 2
        assert stats["metrics"]["bigdl_tpu_tokens_generated_total"] == 6
        assert stats["requests"]["recent"][0]["n_generated"] == 6

        # profiler: stop without start -> 409
        def post(path, body):
            rq = urllib.request.Request(
                f"{base}{path}", data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            return urllib.request.urlopen(rq, timeout=60)

        with pytest.raises(urllib.error.HTTPError) as ei:
            post("/v1/profiler/stop", {})
        assert ei.value.code == 409
        with pytest.raises(urllib.error.HTTPError) as ei:
            post("/v1/profiler/start", {})    # log_dir required
        assert ei.value.code == 400

        log_dir = str(tmp_path / "trace")
        with post("/v1/profiler/start", {"log_dir": log_dir}) as r:
            assert json.loads(r.read())["status"] == "started"
        # double start -> 409
        with pytest.raises(urllib.error.HTTPError) as ei:
            post("/v1/profiler/start", {"log_dir": log_dir})
        assert ei.value.code == 409
        with post("/v1/profiler/stop", {}) as r:
            assert json.loads(r.read())["status"] == "stopped"
        assert os.path.isdir(log_dir)    # jax wrote the trace dir
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# speculative + probe metric plumbing (registry-level; the drivers are
# exercised on CPU by tests/test_speculative.py)
# ---------------------------------------------------------------------------

def test_spec_observe_publishes():
    from bigdl_tpu.observability.metrics import default_registry
    from bigdl_tpu.speculative import _spec_observe

    before = default_registry().summary().get(
        'bigdl_tpu_spec_tokens_total{mode="unit",kind="accepted"}', 0)
    _spec_observe("unit", 3, 4, 0.01)
    s = default_registry().summary()
    assert s['bigdl_tpu_spec_tokens_total{mode="unit",kind="accepted"}'] \
        == before + 3
    assert s['bigdl_tpu_spec_accept_ratio{mode="unit"}']["count"] >= 1


def test_record_probe_result_publishes():
    from bigdl_tpu.observability.metrics import default_registry
    from bigdl_tpu.ops.probing import record_probe_result

    record_probe_result("unit_kernel", True)
    record_probe_result("unit_kernel", False)
    s = default_registry().summary()
    assert s['bigdl_tpu_kernel_probe_total'
             '{kernel="unit_kernel",outcome="compiled"}'] >= 1
    assert s['bigdl_tpu_kernel_probe_total'
             '{kernel="unit_kernel",outcome="fallback"}'] >= 1


# ---------------------------------------------------------------------------
# dependency check: observability must stay stdlib(+jax)-only
# ---------------------------------------------------------------------------

def test_observability_imports_no_third_party_deps():
    """Importing bigdl_tpu.observability must not pull in any heavy or
    third-party dependency beyond what bigdl_tpu itself needs (jax,
    numpy). Guards the 'dependency-free' contract."""
    code = (
        "import sys\n"
        "import bigdl_tpu.observability\n"
        "forbidden = ['flax', 'optax', 'transformers', 'torch', 'yaml',\n"
        "             'prometheus_client', 'safetensors']\n"
        "loaded = [m for m in forbidden if m in sys.modules]\n"
        "assert not loaded, f'observability pulled in {loaded}'\n"
        "print('ok')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "ok"


def test_observability_alone_is_stdlib_only():
    """The observability modules THEMSELVES import with no jax/numpy:
    loading them directly (bypassing the package __init__) must leave
    both out of sys.modules."""
    code = (
        "import importlib.util, sys, types\n"
        "pkg = types.ModuleType('obspkg')\n"
        "pkg.__path__ = ['bigdl_tpu/observability']\n"
        "sys.modules['obspkg'] = pkg\n"
        "# order matters: stats/tracing first so slo/usage's relative\n"
        "# imports resolve against the already-loaded stub package\n"
        "for name in ('metrics', 'tracing', 'stats', 'slo', 'usage'):\n"
        "    spec = importlib.util.spec_from_file_location(\n"
        "        'obspkg.' + name,\n"
        "        'bigdl_tpu/observability/' + name + '.py')\n"
        "    mod = importlib.util.module_from_spec(spec)\n"
        "    sys.modules[spec.name] = mod\n"
        "    spec.loader.exec_module(mod)\n"
        "bad = [m for m in ('jax', 'numpy') if m in sys.modules]\n"
        "assert not bad, f'stdlib-only modules imported {bad}'\n"
        "print('ok')\n"
    )
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=60,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "ok"
