"""The start-up account at the engine's level: a tiny engine behind the
API server reaches its marks in order on the process's own clock,
renders the account's series from the first scrape, serves the
timeline of its programs' first calls at ``/v1/stats`` and its age at
``/health``; a new signature met inside a step is a ``compile.<fn>``
span and a row whose interval lies inside that step."""

import json
import urllib.request

import pytest

from bigdl_tpu.observability import MetricsRegistry, RequestTracer
from bigdl_tpu.observability import compile_watch as cw
from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from bigdl_tpu.utils.testing import tiny_random_model

IN_ORDER = ("engine_init_begin", "engine_init_end", "listening",
            "first_request", "first_token")


def _engine() -> LLMEngine:
    return LLMEngine(
        tiny_random_model(seed=0),
        EngineConfig(max_batch=4, max_seq=64, prefill_bucket=8,
                     prefill_chunk=0, prefix_cache_entries=0),
        registry=MetricsRegistry(),
        tracer=RequestTracer(event_log_path=""))


def _marks(text: str) -> dict:
    out = {}
    for ln in text.splitlines():
        if ln.startswith(cw.MARK_SECONDS + "{"):
            out[ln.split('mark="')[1].split('"')[0]] = float(ln.split()[-1])
    return out


@pytest.fixture(scope="module")
def served():
    """One streamed request through the server; what its endpoints said
    before and after."""
    from bigdl_tpu.serving.api_server import OpenAIServer

    age0 = cw.process_age_s()
    eng = _engine()
    server = OpenAIServer(eng)
    httpd = server.serve(port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"

    def get(path):
        with urllib.request.urlopen(base + path, timeout=120) as r:
            return r.read().decode()

    try:
        out = {"age0": age0, "scrape1": get("/metrics"),
               "health0": json.loads(get("/health"))}
        req = urllib.request.Request(
            f"{base}/v1/completions",
            data=json.dumps({"prompt": [1, 2, 3, 4], "max_tokens": 5,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            out["stream"] = r.read().decode()
        out["metrics"] = get("/metrics")
        out["health"] = json.loads(get("/health"))
        out["stats"] = json.loads(get("/v1/stats"))
        out["age1"] = cw.process_age_s()
    finally:
        server.shutdown()
        httpd.server_close()
    return out


def test_the_marks_are_reached_in_order_on_the_process_clock(served):
    assert served["stream"].rstrip().endswith("data: [DONE]")
    marks = _marks(served["metrics"])
    assert set(marks) == set(cw.MARKS)
    ages = [marks[m] for m in IN_ORDER]
    assert ages == sorted(ages)
    assert served["age0"] <= ages[0] and ages[-1] <= served["age1"]
    # the process last stopped to compile for the decode program, after
    # the first token (the prefill's) was out
    assert marks["first_token"] <= marks["last_compile_end"] \
        <= served["age1"]


def test_before_a_request_only_the_reached_marks_render(served):
    assert set(_marks(served["scrape1"])) == {
        "engine_init_begin", "engine_init_end", "listening"}
    for fam in (cw.STAGE_SECONDS, cw.CACHE_REQUESTS, cw.MARK_SECONDS):
        assert f"# TYPE {fam} " in served["scrape1"]


@pytest.mark.parametrize("stage", cw.STAGES)
def test_every_stage_series_of_a_compiled_program_renders(served, stage):
    series = (f'{cw.STAGE_SECONDS}{{fn="engine_prefill",'
              f'stage="{stage}"}}')
    line = [ln for ln in served["metrics"].splitlines()
            if ln.startswith(series)]
    assert len(line) == 1
    value = float(line[0].split()[-1])
    if stage == "memory_analysis":
        assert value == 0       # the capture is off unless asked for
    elif stage in ("trace", "lower", "first_run"):
        assert value > 0


def test_the_scrape_passes_the_lint(served):
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    import promlint

    assert promlint.lint_text(served["metrics"]) == []


def test_stats_serves_the_timeline_of_first_calls(served):
    startup = served["stats"]["startup"]
    assert set(startup) == {"process_age_s", "clock_source", "marks",
                            "programs"}
    rows = startup["programs"]
    fns = [r["fn"] for r in rows]
    assert "engine_prefill" in fns
    assert any(f.startswith("engine_decode") for f in fns)
    for r in rows:
        assert set(r) == {"fn", "signature", "t0", "t1", "stages", "cache",
                          "thread"}
        assert r["t0"] < r["t1"] and r["cache"] in cw.CACHE_OUTCOMES
    mine = [r for r in rows
            if served["age0"] <= r["t0"] and r["fn"].startswith("engine_")]
    assert mine and all(r["t1"] <= served["age1"] for r in mine)
    # the compile table's rows carry the same stages
    sig = served["stats"]["compile_table"]["engine_prefill"]["signatures"]
    assert all("stages" in s for s in sig)


def test_health_says_how_old_the_process_is(served):
    before, after = served["health0"], served["health"]
    assert before["status"] == after["status"] == "ok"
    assert served["age0"] <= before["age_s"] <= after["age_s"] \
        <= served["age1"]
    assert after["first_token_s"] is not None
    assert 0 < after["first_token_s"] <= after["age_s"]


def test_a_new_signature_inside_a_step_is_a_span_and_a_row(monkeypatch):
    import bigdl_tpu.utils.profiling as profiling

    eng = _engine()
    eng.add_request("warm", [1, 2, 3], SamplingParams(max_tokens=2))
    while eng.has_unfinished():
        eng.step()
    opened = []
    real = profiling.annotate
    monkeypatch.setattr(
        profiling, "annotate",
        lambda name: opened.append(name) or real(name))
    # a prompt of another bucket: a prefill program the engine has not
    # met, compiled inside the step that admits it
    eng.add_request("long", list(range(1, 20)),
                    SamplingParams(max_tokens=2))
    t0 = cw.process_age_s()
    eng.step()
    t1 = cw.process_age_s()
    # by time, not by position: the timeline is bounded, and full in a
    # process that has made 256 first calls before this test
    rows = [r for r in cw.startup_snapshot()["programs"] if r["t1"] > t0]
    new = [r for r in rows if r["fn"] == "engine_prefill"]
    assert len(new) == 1 and "compile.engine_prefill" in opened
    assert t0 <= new[0]["t0"] < new[0]["t1"] <= t1
    assert all(t0 <= r["t0"] and r["t1"] <= t1 for r in rows)
    # a step of known signatures opens no compile span
    while eng.has_unfinished():
        eng.step()
    del opened[:]
    eng.add_request("again", list(range(1, 20)),
                    SamplingParams(max_tokens=2))
    before = cw.startup_snapshot()["programs"]
    while eng.has_unfinished():
        eng.step()
    assert not [n for n in opened if n.startswith("compile.")]
    assert cw.startup_snapshot()["programs"] == before
