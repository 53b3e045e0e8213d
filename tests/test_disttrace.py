"""Fleet-wide distributed tracing (observability/disttrace.py).

Two layers of coverage:

- **In-thread unit tests**: traceparent codec strictness (malformed
  headers are ignored, never errors), deterministic tail sampling,
  the SpanRecorder store + JSONL sink rotation, keep-N event-log
  rotation, ``merge_timeline`` skew/orphan math, env_check surfacing
  of the two new knobs, and the engine's per-request / per-step span
  decomposition on a tiny model.
- **Subprocess chaos e2e** (a ``["prefill", "decode"]`` fleet of real
  ``api_server --tiny-random`` replicas behind a served router): one
  traced completion produces a stitched ``GET /v1/trace/{id}``
  timeline covering the router and BOTH replicas (through the
  KV-handoff hop) with zero orphan spans; kill -9 of the replica
  holding an in-flight traced request forces a failover replay that
  lands on the same timeline.
"""

from __future__ import annotations

import json
import os
import pathlib
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from test_handoff import _wait_fleet_healthy  # noqa: E402
from test_serving import FakeModel  # noqa: E402

from bigdl_tpu.observability.disttrace import (  # noqa: E402
    SpanRecorder,
    make_traceparent,
    merge_timeline,
    new_span_id,
    new_trace_id,
    parse_traceparent,
    resolve_trace_sample,
    trace_sampled,
)
from bigdl_tpu.observability.tracing import (  # noqa: E402
    resolve_event_log_keep,
    rotate_event_log,
)
from bigdl_tpu.serving import (EngineConfig, LLMEngine,  # noqa: E402
                               SamplingParams)
from bigdl_tpu.serving.router import Router, RouterConfig  # noqa: E402
from bigdl_tpu.utils.testing import (TINY_LLAMA,  # noqa: E402
                                     random_llama_params)


# -- traceparent codec ------------------------------------------------------


def test_traceparent_roundtrip():
    tid, sid = new_trace_id(), new_span_id()
    assert len(tid) == 32 and len(sid) == 16
    hdr = make_traceparent(tid, sid)
    assert hdr == f"00-{tid}-{sid}-01"
    assert parse_traceparent(hdr) == (tid, sid)
    # surrounding whitespace is tolerated, flags value is ignored
    assert parse_traceparent(f"  {hdr}  ") == (tid, sid)
    assert parse_traceparent(make_traceparent(tid, sid, "00")) == (tid, sid)


def test_traceparent_rejects_malformed():
    tid, sid = new_trace_id(), new_span_id()
    bad = [
        None, 123, "", "00",
        f"00-{tid}-{sid}",                      # missing flags
        f"00-{tid}-{sid}-01-extra",             # trailing field
        f"00-{tid[:-1]}-{sid}-01",              # short trace id
        f"00-{tid}x-{sid}-01",                  # long trace id
        f"00-{tid}-{sid[:-1]}-01",              # short span id
        f"00-{tid.upper()}-{sid}-01",           # uppercase hex
        f"00-{'g' * 32}-{sid}-01",              # non-hex digits
        f"ff-{tid}-{sid}-01",                   # forbidden version
        f"00-{'0' * 32}-{sid}-01",              # all-zero trace id
        f"00-{tid}-{'0' * 16}-01",              # all-zero span id
    ]
    for hdr in bad:
        assert parse_traceparent(hdr) is None, hdr


def test_trace_sampled_deterministic():
    tid = new_trace_id()
    assert trace_sampled(tid, 1.0) is True
    assert trace_sampled(tid, 0.0) is False
    # the decision is a pure function of the id: every process agrees
    lo = "00000000" + "a" * 24      # hash fraction 0.0
    hi = "ffffffff" + "a" * 24      # hash fraction ~1.0
    assert trace_sampled(lo, 0.5) is True
    assert trace_sampled(hi, 0.5) is False
    for _ in range(3):
        assert trace_sampled(tid, 0.37) == trace_sampled(tid, 0.37)


def test_resolve_trace_sample(monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_TRACE_SAMPLE", raising=False)
    assert resolve_trace_sample() == 1.0
    assert resolve_trace_sample("0.25") == 0.25
    monkeypatch.setenv("BIGDL_TPU_TRACE_SAMPLE", "0.5")
    assert resolve_trace_sample() == 0.5
    for bad in ("1.5", "-0.1", "nope"):
        with pytest.raises(ValueError):
            resolve_trace_sample(bad)


# -- keep-N event-log rotation ----------------------------------------------


def test_resolve_event_log_keep(monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_EVENT_LOG_KEEP", raising=False)
    assert resolve_event_log_keep() == 1
    assert resolve_event_log_keep("3") == 3
    monkeypatch.setenv("BIGDL_TPU_EVENT_LOG_KEEP", "4")
    assert resolve_event_log_keep() == 4
    for bad in ("0", "-2", "x"):
        with pytest.raises(ValueError):
            resolve_event_log_keep(bad)


def test_rotate_event_log_cascade(tmp_path):
    p = tmp_path / "events.jsonl"
    for payload in ("a", "b", "c"):
        p.write_text(payload)
        rotate_event_log(str(p), keep=2)
        assert not p.exists()
    # newest rolled file is .1, older shifted to .2, third gen dropped
    assert (tmp_path / "events.jsonl.1").read_text() == "c"
    assert (tmp_path / "events.jsonl.2").read_text() == "b"
    assert not (tmp_path / "events.jsonl.3").exists()


# -- SpanRecorder -----------------------------------------------------------


def test_span_recorder_store_and_annotate():
    rec = SpanRecorder(service="svc", sink_path="")
    tid = new_trace_id()
    assert rec.record("s", None) is None          # no trace -> dropped
    root = rec.record("root", tid, t_start=10.0, t_end=10.5, request_id="r")
    child = rec.record("child", tid, parent_id=root["span_id"],
                       t_start=10.1, t_end=10.2)
    spans = rec.spans_for(tid)
    assert [s["name"] for s in spans] == ["root", "child"]
    assert spans[0]["service"] == "svc"
    assert spans[0]["attrs"]["request_id"] == "r"
    assert spans[0]["duration_s"] == 0.5
    assert child["parent_id"] == root["span_id"]

    # slower trace sorts first in the /v1/traces index
    tid2 = new_trace_id()
    rec.record("root2", tid2, t_start=20.0, t_end=24.0)
    idx = rec.recent_traces()
    assert [t["trace_id"] for t in idx[:2]] == [tid2, tid]
    assert idx[0]["duration_s"] == 4.0 and idx[0]["root"] == "root2"

    # annotations are zero-duration event spans stamped "now"
    note = rec.annotate(tid, "decision", parent_id=root["span_id"], why="x")
    assert note["attrs"]["event"] is True and note["duration_s"] == 0.0
    assert rec.spans_for(tid)[-1]["name"] == "decision"
    assert rec.annotate_recent("fleet_event", level=1) == 2
    assert rec.spans_for(tid)[-1]["name"] == "fleet_event"

    snap = rec.snapshot()
    assert snap["service"] == "svc" and snap["traces"] == 2


def test_span_recorder_tail_sampling_drops():
    rec = SpanRecorder(service="svc", sink_path="", sample=0.0)
    assert rec.record("s", new_trace_id()) is None
    assert rec.snapshot()["spans"] == 0


def test_span_recorder_sink_rotation(tmp_path):
    path = tmp_path / "ev.jsonl.spans"
    rec = SpanRecorder(service="svc", sink_path=str(path),
                       sink_max_bytes=400, sink_keep=2)
    tid = new_trace_id()
    for i in range(20):
        rec.record("span", tid, t_start=float(i), t_end=float(i) + 0.1,
                   idx=i, pad="x" * 40)
    rec.close()
    assert path.exists()
    assert (tmp_path / "ev.jsonl.spans.1").exists()   # rotation fired
    for line in path.read_text().splitlines():
        doc = json.loads(line)
        assert doc["trace_id"] == tid and doc["name"] == "span"


# -- merge_timeline ---------------------------------------------------------


def test_merge_timeline_skew_and_orphans():
    tid = new_trace_id()
    local = [
        {"name": "router.request", "service": "router", "trace_id": tid,
         "span_id": "r" * 16, "parent_id": None,
         "t_start": 100.0, "t_end": 101.0, "duration_s": 1.0},
    ]
    remote = [
        {"name": "engine.request", "service": "replica:1", "trace_id": tid,
         "span_id": "e" * 16, "parent_id": "r" * 16,
         "t_start": 98.2, "t_end": 98.9, "duration_s": 0.7},
        {"name": "lost_child", "service": "replica:1", "trace_id": tid,
         "span_id": "c" * 16, "parent_id": "dead" + "0" * 12,
         "t_start": 98.3, "t_end": 98.4, "duration_s": 0.1},
    ]
    doc = merge_timeline(tid, [(0.0, local), (2.0, remote)])
    assert doc["n_spans"] == 3
    assert doc["services"] == ["replica:1", "router"]
    # remote timestamps shifted into the router's clock domain
    shifted = next(s for s in doc["spans"] if s["name"] == "engine.request")
    assert shifted["t_start"] == 100.2 and shifted["skew_adjust_s"] == 2.0
    assert [s["t_start"] for s in doc["spans"]] == sorted(
        s["t_start"] for s in doc["spans"])
    # the span whose parent never reported is the orphan; the resolved
    # child is not
    assert doc["orphan_spans"] == ["c" * 16]
    assert doc["t_start"] == 100.0 and doc["duration_s"] == 1.0

    # a client-held parent id is external, not an orphan
    ext = [{"name": "router.request", "service": "router", "trace_id": tid,
            "span_id": "r" * 16, "parent_id": "f" * 16,
            "t_start": 1.0, "t_end": 2.0, "duration_s": 1.0}]
    doc2 = merge_timeline(tid, [(0.0, ext)],
                          external_parents=("f" * 16,))
    assert doc2["orphan_spans"] == []


# -- env_check surfacing ----------------------------------------------------


def test_env_check_reports_trace_knobs(monkeypatch):
    from bigdl_tpu.utils import env_check

    assert "BIGDL_TPU_EVENT_LOG_KEEP" in env_check.KNOWN_ENV
    assert "BIGDL_TPU_TRACE_SAMPLE" in env_check.KNOWN_ENV

    monkeypatch.setenv("BIGDL_TPU_EVENT_LOG_KEEP", "3")
    monkeypatch.setenv("BIGDL_TPU_TRACE_SAMPLE", "0.5")
    info = env_check.collect()
    assert info["event_log_keep"] == {"value": 3, "valid": True}
    assert info["trace_sample"] == {"value": 0.5, "valid": True}

    monkeypatch.setenv("BIGDL_TPU_EVENT_LOG_KEEP", "0")
    monkeypatch.setenv("BIGDL_TPU_TRACE_SAMPLE", "2")
    info = env_check.collect()
    assert info["event_log_keep"]["valid"] is False
    assert "error" in info["event_log_keep"]
    assert info["trace_sample"]["valid"] is False


# -- engine decomposition (tiny model, in-thread) ---------------------------


@pytest.fixture(scope="module")
def model():
    return FakeModel(random_llama_params(TINY_LLAMA, qtype="sym_int4",
                                         seed=0), TINY_LLAMA)


def test_engine_spans_and_phase_decomposition(model):
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    tid, parent = new_trace_id(), new_span_id()
    eng.add_request("tr-1", [1, 2, 3, 4], SamplingParams(max_tokens=6),
                    trace=(tid, parent))
    while eng.has_unfinished():
        eng.step()

    spans = eng.spans.spans_for(tid)
    names = {s["name"] for s in spans}
    assert {"queue_wait", "prefill", "decode", "decode_step",
            "engine.request"} <= names, names
    umbrella = next(s for s in spans if s["name"] == "engine.request")
    assert umbrella["parent_id"] == parent
    assert umbrella["attrs"]["finish_reason"] == "length"
    assert umbrella["attrs"]["n_generated"] == 6
    # every span resolves into the trace: its parent is another span
    # here or the wire parent (no in-process orphans)
    ids = {s["span_id"] for s in spans} | {parent}
    assert all(s["parent_id"] in ids for s in spans
               if s["parent_id"] is not None)
    steps = [s for s in spans if s["name"] == "decode_step"]
    assert steps
    for s in steps:
        assert s["attrs"]["dispatch_ms"] >= 0.0
        assert s["attrs"]["device_ms"] >= 0.0
        assert s["attrs"]["request_id"] == "tr-1"

    # the step-phase histograms and the dispatch EWMA populate without
    # any trace attached
    summ = eng.registry.summary()
    for ph, kind in (("queue_wait", "admission"), ("prefill", "admission"),
                     ("dispatch", "plain"), ("device", "plain")):
        key = ('bigdl_tpu_step_phase_seconds{phase="%s",kind="%s"}'
               % (ph, kind))
        assert summ[key]["count"] >= 1, (ph, sorted(summ))
    assert eng.stats_snapshot()["dispatch_overhead_ms"] > 0.0

    # an untraced request records no spans
    before = eng.spans.snapshot()["traces"]
    eng.add_request("plain", [9, 8, 7], SamplingParams(max_tokens=3))
    while eng.has_unfinished():
        eng.step()
    assert eng.spans.snapshot()["traces"] == before


# -- subprocess chaos e2e ---------------------------------------------------

_ROLES = {0: "prefill", 1: "decode"}


def _spawn_replica(idx: int, port: int):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("BIGDL_TPU_FAULT_SPEC", None)
    env["BIGDL_TPU_DRAIN_TIMEOUT_SEC"] = "30"
    env["BIGDL_TPU_REPLICA_ROLE"] = _ROLES.get(idx, "mixed")
    cmd = [sys.executable, "-m", "bigdl_tpu.serving.api_server",
           "--tiny-random", "--tiny-seed", "7",
           "--host", "127.0.0.1", "--port", str(port),
           "--max-batch", "4", "--max-seq", "96", "--wedge-sec", "3"]
    return subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL)


def _post_traced(base, path, payload, headers=None, timeout=300):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read()), resp.headers


def _get_json(base, path, timeout=30):
    with urllib.request.urlopen(base + path, timeout=timeout) as resp:
        return json.loads(resp.read())


@pytest.fixture(scope="module")
def trace_cluster():
    """prefill + decode replicas behind a served router — the handoff
    hop is what makes a single completion span BOTH replicas."""
    router = Router(spawn=_spawn_replica, config=RouterConfig(
        replicas=2, roles=["prefill", "decode"], health_sec=0.2,
        backoff_base_sec=0.2, crash_budget=20, crash_window_sec=5.0,
        unhealthy_after=4, spawn_timeout_sec=240.0,
        drain_exit_timeout_sec=90.0, no_replica_wait_sec=120.0))
    router.start(wait_healthy=True)
    httpd = router.serve(port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        _wait_fleet_healthy(router)
        yield router, base
    finally:
        httpd.shutdown()
        router.shutdown()


def _poll_timeline(base, tid, want_names, timeout=30.0):
    """GET /v1/trace/{tid} until every wanted span name appears and no
    orphans remain (spans land asynchronously: the router records its
    own span after the response is written, replicas flush on their own
    clocks)."""
    deadline = time.monotonic() + timeout
    tl = {}
    while time.monotonic() < deadline:
        tl = _get_json(base, f"/v1/trace/{tid}")
        names = {s["name"] for s in tl["spans"]}
        if want_names <= names and not tl["orphan_spans"]:
            return tl
        time.sleep(0.1)
    return tl


def test_e2e_traceparent_propagates_across_handoff(trace_cluster):
    """One traced completion through prefill -> KV-handoff -> decode:
    the stitched timeline covers the router and both replicas, carries
    the per-request and per-step decomposition, resolves every parent
    (zero orphans, zero orphan-counter increments), and the trace shows
    up in the GET /v1/traces index."""
    router, base = trace_cluster
    tid, client_span = new_trace_id(), new_span_id()
    status, doc, headers = _post_traced(
        base, "/v1/completions",
        {"prompt": [5, 6, 7, 2], "max_tokens": 8, "temperature": 0},
        headers={"traceparent": make_traceparent(tid, client_span)})
    assert status == 200 and doc["usage"]["completion_tokens"] == 8
    # the client learns its trace id even when it supplied one
    assert headers.get("X-Trace-Id") == tid

    want = {"router.request", "engine.request", "queue_wait", "prefill",
            "decode", "decode_step", "kv_handoff", "kv_handoff.decode"}
    tl = _poll_timeline(base, tid, want)
    names = {s["name"] for s in tl["spans"]}
    assert want <= names, (sorted(names), tl["orphan_spans"])
    assert tl["orphan_spans"] == []
    assert all(s["trace_id"] == tid for s in tl["spans"])

    # one request, three clock domains: the router + both replicas
    assert "router" in tl["services"]
    replica_services = [s for s in tl["services"]
                        if s.startswith("replica:")]
    assert len(replica_services) == 2, tl["services"]

    # the client's own parent id survives onto the router's root span
    root = next(s for s in tl["spans"] if s["name"] == "router.request")
    assert root["parent_id"] == client_span

    # per-step decomposition rode along: host dispatch vs device wait
    steps = [s for s in tl["spans"] if s["name"] == "decode_step"]
    assert steps
    assert all(s["attrs"]["dispatch_ms"] >= 0.0
               and s["attrs"]["device_ms"] >= 0.0 for s in steps)

    # the decode target echoed X-Trace-Span for every traced handoff
    prefill = router.replicas[0]
    stats = _get_json(f"http://127.0.0.1:{prefill.port}", "/v1/stats")
    assert stats["metrics"].get(
        "bigdl_tpu_handoff_span_orphans_total", 0) == 0

    # the timeline is ordered and the index lists the trace
    starts = [s["t_start"] for s in tl["spans"]]
    assert starts == sorted(starts)
    idx = _get_json(base, "/v1/traces")
    assert any(t["trace_id"] == tid for t in idx["traces"])


def test_e2e_kill9_traced_replay_one_timeline(trace_cluster):
    """The acceptance chaos run: kill -9 the replica holding an
    in-flight traced request. The client still gets its 200 (failover
    replay), and the trace shows ONE stitched timeline: the failover +
    replay annotations, spans from the replay replica, and no orphans.
    Retries the kill dance if the request wins the race."""
    router, base = trace_cluster
    _wait_fleet_healthy(router)
    for attempt in range(4):
        tid, client_span = new_trace_id(), new_span_id()
        payload = {"prompt": [70 + attempt, 71, 72, 73],
                   "max_tokens": 48, "temperature": 0}
        before = router.counts["failovers"]
        box = {}

        def go():
            box["resp"] = _post_traced(
                base, "/v1/completions", payload,
                headers={"traceparent": make_traceparent(tid, client_span)})

        t = threading.Thread(target=go)
        t.start()
        victim = None
        deadline = time.monotonic() + 90
        while victim is None and time.monotonic() < deadline:
            for r in router.replicas:
                if r.inflight:
                    victim = r
                    break
            time.sleep(0.002)
        assert victim is not None, "request never reached a replica"
        time.sleep(0.05)
        try:
            os.kill(victim.pid, signal.SIGKILL)
        except (ProcessLookupError, TypeError):
            pass
        t.join(timeout=300)
        status, doc, headers = box["resp"]
        assert status == 200, doc
        assert doc["usage"]["completion_tokens"] == 48
        if router.counts["failovers"] > before:
            break                        # the kill landed mid-flight
    else:
        pytest.fail("4 attempts never caught the request in flight")

    assert headers.get("X-Trace-Id") == tid
    want = {"router.request", "failover", "failover_replay",
            "engine.request", "decode_step"}
    tl = _poll_timeline(base, tid, want)
    names = {s["name"] for s in tl["spans"]}
    assert want <= names, (sorted(names), tl["orphan_spans"])
    # the whole incident — original attempt, failover decision, replay —
    # is one trace with every parent resolved
    assert all(s["trace_id"] == tid for s in tl["spans"])
    assert tl["orphan_spans"] == []
    failover = next(s for s in tl["spans"] if s["name"] == "failover")
    assert failover["service"] == "router"
    assert failover["parent_id"] == next(
        s["span_id"] for s in tl["spans"] if s["name"] == "router.request")
    _wait_fleet_healthy(router)          # supervisor respawned the victim
