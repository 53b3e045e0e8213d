"""Multi-replica serving tier (serving/router.py).

Two layers of coverage:

- **In-thread unit tests** (fake process handles, stub HTTP replicas):
  env resolvers, prefix-affinity + least-loaded routing, circuit
  breaker trip / half-open / close, crash-loop quarantine + backoff,
  the write-ahead journal, and the failover/replay/hedge forwarding
  paths — all without spawning a model process.
- **Subprocess chaos e2e** (2 real ``api_server --tiny-random``
  replicas with the SAME seed, so their weights are byte-identical):
  a ``replica_crash`` fault (and a literal ``kill -9``) mid-request
  loses zero non-streaming requests and the replayed answers are
  byte-identical to a no-fault run; a streaming client whose replica
  dies gets a structured SSE error event with a retry_after hint; a
  rolling restart of both replicas serves a concurrent request stream
  with zero 5xx.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from bigdl_tpu.robustness.faults import (CRASH_EXIT_CODE, FaultInjector,
                                         parse_fault_spec)
from bigdl_tpu.serving.router import (BACKOFF, HEALTHY, QUARANTINED,
                                      JournalEntry, NoReplica,
                                      RequestJournal, Router, RouterConfig,
                                      resolve_router_crash_budget,
                                      resolve_router_health_sec,
                                      resolve_router_hedge_ms,
                                      resolve_router_replicas)


# -- helpers ----------------------------------------------------------------


class FakeProc:
    """Popen-shaped stand-in: alive until killed."""

    _next_pid = 54000

    def __init__(self):
        FakeProc._next_pid += 1
        self.pid = FakeProc._next_pid
        self.returncode = None

    def poll(self):
        return self.returncode

    def terminate(self):
        self.returncode = -15

    def kill(self):
        self.returncode = -9

    def wait(self, timeout=None):
        return self.returncode


def _fake_router(n=2, ports=None, **cfg_kw):
    """Router over FakeProcs, all replicas forced HEALTHY, supervisor
    NOT started — unit tests drive the state machine directly."""
    cfg_kw.setdefault("health_sec", 0.05)
    router = Router(spawn=lambda i, p: FakeProc(),
                    config=RouterConfig(replicas=n, **cfg_kw),
                    ports=ports)
    for r in router.replicas:
        r.proc = FakeProc()
        router._set_state(r, HEALTHY)
    return router


def _entry(key=0, prompt=(1, 2, 3), stream=False, rid="t-1",
           path="/v1/completions", **extra):
    body = json.dumps(dict({"prompt": list(prompt)}, stream=stream,
                           **extra)).encode()
    return JournalEntry(rid=rid, path=path, body=body, stream=stream,
                       key=key)


def _stub_replica(do_post, port=0):
    """In-thread HTTP server standing in for one replica; ``do_post``
    receives the handler and crafts the response (or kills the
    connection)."""
    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = b'{"status": "ok"}'
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            do_post(self)

    srv = ThreadingHTTPServer(("127.0.0.1", port), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _reply_json(handler, code, obj):
    body = json.dumps(obj).encode()
    handler.send_response(code)
    handler.send_header("Content-Type", "application/json")
    handler.send_header("Content-Length", str(len(body)))
    handler.end_headers()
    handler.wfile.write(body)


# -- env resolvers ----------------------------------------------------------


def test_router_env_resolvers():
    assert resolve_router_health_sec("") == 1.0
    assert resolve_router_health_sec("0.25") == 0.25
    assert resolve_router_replicas("") == 2
    assert resolve_router_replicas("4") == 4
    assert resolve_router_hedge_ms("") == 0.0
    assert resolve_router_hedge_ms("150") == 150.0
    assert resolve_router_crash_budget("") == 3
    assert resolve_router_crash_budget("5") == 5
    for fn, bad in ((resolve_router_health_sec, "0"),
                    (resolve_router_health_sec, "nope"),
                    (resolve_router_replicas, "0"),
                    (resolve_router_replicas, "2.5"),
                    (resolve_router_hedge_ms, "-1"),
                    (resolve_router_crash_budget, "0")):
        with pytest.raises(ValueError):
            fn(bad)


def test_env_check_validates_router_knobs(monkeypatch):
    from bigdl_tpu.utils import env_check

    monkeypatch.setenv("BIGDL_TPU_ROUTER_HEALTH_SEC", "0.5")
    monkeypatch.setenv("BIGDL_TPU_ROUTER_REPLICAS", "0")
    info = env_check.collect()
    assert info["router_health_sec"] == {"value": 0.5, "valid": True}
    assert info["router_replicas"]["valid"] is False
    assert "must be >= 1" in info["router_replicas"]["error"]


def test_env_check_typo_suggestions():
    from bigdl_tpu.utils.env_check import find_env_typos

    typos = find_env_typos({"BIGDL_TPU_ROUTER_HEALTH_SECS": "1",
                            "BIGDL_TPU_ROUTER_REPLICAS": "2",
                            "MY_UNRELATED_VAR": "x"})
    assert typos == [{"unknown": "BIGDL_TPU_ROUTER_HEALTH_SECS",
                      "did_you_mean": "BIGDL_TPU_ROUTER_HEALTH_SEC"}]


# -- fault kinds ------------------------------------------------------------


def test_replica_crash_fault_kills_process_with_exit_137():
    code = (
        "from bigdl_tpu.robustness.faults import FaultInjector, "
        "parse_fault_spec\n"
        "fi = FaultInjector(parse_fault_spec('replica_crash@at_step=3'))\n"
        "for s in range(1, 6):\n"
        "    fi.process_point('step', s)\n"
        "print('survived')\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == CRASH_EXIT_CODE == 137
    assert "survived" not in r.stdout


def test_replica_hang_fault_blocks_bounded():
    fi = FaultInjector(parse_fault_spec("replica_hang@ms=40,at_step=2"))
    t0 = time.monotonic()
    fi.process_point("step", 1)       # not yet
    assert time.monotonic() - t0 < 0.03
    fi.process_point("step", 2)       # 40 ms freeze
    assert time.monotonic() - t0 >= 0.035
    fi.process_point("step", 3)       # one-shot: no second freeze
    assert time.monotonic() - t0 < 0.2


# -- routing ----------------------------------------------------------------


def test_affinity_same_prefix_same_replica():
    router = _fake_router(n=3)
    long_a = {"prompt": list(range(100))}
    long_b = {"prompt": list(range(32)) + [999] * 50}   # same 32-prefix
    other = {"prompt": list(range(7, 200))}
    ka, kb = router._affinity_key(long_a), router._affinity_key(long_b)
    assert ka == kb                     # prefix-only hash
    assert router._pick(ka).idx == router._pick(kb).idx
    # chat bodies hash their messages
    kc = router._affinity_key({"messages": [
        {"role": "user", "content": "hello"}]})
    assert isinstance(kc, int) and kc != ka
    assert router._affinity_key(other) != ka or True   # just computes


def test_pick_falls_back_least_loaded():
    router = _fake_router(n=3)
    key = 0                              # affinity target = replica 0
    assert router._pick(key).idx == 0
    router.replicas[0].occupancy = 1.0   # full: affinity skipped
    router.replicas[1].occupancy = 0.75
    router.replicas[2].occupancy = 0.25
    assert router._pick(key).idx == 2    # least loaded
    router._set_state(router.replicas[0], BACKOFF)
    router.replicas[2].breaker = "open"
    router.replicas[2].breaker_open_until = time.monotonic() + 60
    assert router._pick(key).idx == 1    # only routable one left
    router._set_state(router.replicas[1], QUARANTINED)
    with pytest.raises(NoReplica):
        router._pick(key)


def test_breaker_trips_half_opens_closes():
    router = _fake_router(n=2, breaker_threshold=3,
                          breaker_cooldown_sec=0.05)
    r = router.replicas[0]
    router._breaker_failure(r)
    router._breaker_failure(r)
    assert r.breaker == "closed"
    router._breaker_failure(r)           # third consecutive: trip
    assert r.breaker == "open"
    assert router.counts["breaker_trips"] == 1
    assert not router._routable(r)       # open: skipped by routing
    time.sleep(0.06)
    assert router._routable(r)           # cooldown over: trial admitted
    assert r.breaker == "half_open"
    router._breaker_failure(r)           # trial failed: re-open
    assert r.breaker == "open"
    assert router.counts["breaker_trips"] == 2
    time.sleep(0.06)
    assert router._routable(r)
    router._breaker_success(r)           # trial succeeded: close
    assert r.breaker == "closed" and r.breaker_failures == 0
    events = [e["event"] for e in router.flight.snapshot()]
    assert "breaker_open" in events and "breaker_close" in events


def test_crash_loop_quarantine_and_backoff():
    router = _fake_router(n=2, crash_budget=3, crash_window_sec=60.0,
                          backoff_base_sec=0.25, backoff_max_sec=30.0)
    r = router.replicas[0]
    router._handle_death(r, "exit code 137")
    assert r.state == BACKOFF
    first_backoff = r.backoff_until - time.monotonic()
    router._handle_death(r, "exit code 137")
    assert r.state == BACKOFF
    second_backoff = r.backoff_until - time.monotonic()
    assert second_backoff > first_backoff     # exponential
    router._handle_death(r, "exit code 137")  # third in window: done
    assert r.state == QUARANTINED
    assert router.counts["quarantined"] == 1
    events = [e["event"] for e in router.flight.snapshot()]
    assert "replica_quarantined" in events
    # routing never touches a quarantined replica
    assert router._pick(0).idx == 1


def test_request_journal_wal():
    j = RequestJournal()
    e = _entry(rid="wal-1")
    j.admit(e)
    assert j.depth() == 1
    j.assign("wal-1", replica=1, generation=4)
    assert j.inflight_on(1)[0].rid == "wal-1"
    assert j.inflight_on(1)[0].generation == 4
    assert j.inflight_on(0) == []
    j.complete("wal-1")
    assert j.depth() == 0
    j.complete("wal-1")                  # idempotent


def test_route_buffered_failover_replays_on_stub_death():
    """Replica 0 kills the connection (a crashed process does exactly
    this); the journaled request replays on replica 1 and the client
    sees one clean 200."""
    dead = _stub_replica(lambda h: h.connection.close())
    alive = _stub_replica(lambda h: _reply_json(h, 200, {"ok": True}))
    router = _fake_router(
        n=2, ports=[dead.server_address[1], alive.server_address[1]])
    try:
        status, data = router.route_buffered(_entry(key=0))
        assert status == 200 and json.loads(data) == {"ok": True}
        assert router.counts["failovers"] == 1
        assert router.counts["replays"] == 1
        events = [e["event"] for e in router.flight.snapshot()]
        assert "failover" in events and "replay" in events
    finally:
        dead.shutdown()
        alive.shutdown()


def test_route_buffered_reroutes_draining_503():
    """A replica's drain-shed 503 re-routes transparently and burns no
    replay budget — the zero-5xx leg of rolling restarts."""
    draining = _stub_replica(lambda h: _reply_json(
        h, 503, {"error": {"code": 503, "type": "unavailable"}}))
    alive = _stub_replica(lambda h: _reply_json(h, 200, {"ok": 2}))
    router = _fake_router(
        n=2, ports=[draining.server_address[1], alive.server_address[1]])
    try:
        status, data = router.route_buffered(_entry(key=0))
        assert status == 200 and json.loads(data) == {"ok": 2}
        assert router.counts["rerouted_503"] == 1
        assert router.counts["replays"] == 0
    finally:
        draining.shutdown()
        alive.shutdown()


def test_route_buffered_hedges_slow_replica():
    slow_served = threading.Event()

    def slow(h):
        slow_served.set()
        time.sleep(0.5)
        _reply_json(h, 200, {"who": "slow"})

    s_slow = _stub_replica(slow)
    s_fast = _stub_replica(lambda h: _reply_json(h, 200, {"who": "fast"}))
    router = _fake_router(
        n=2, ports=[s_slow.server_address[1], s_fast.server_address[1]],
        hedge_ms=60.0)
    try:
        t0 = time.monotonic()
        status, data = router.route_buffered(_entry(key=0))
        wall = time.monotonic() - t0
        assert status == 200 and json.loads(data) == {"who": "fast"}
        assert slow_served.is_set()       # primary really was in flight
        assert wall < 0.45                # did not wait out the slow one
        assert router.counts["hedges"] == 1
    finally:
        s_slow.shutdown()
        s_fast.shutdown()


def test_stream_mid_flight_death_yields_structured_error():
    """Replica dies mid-SSE: the client gets a structured error event
    with a retry_after hint, then [DONE] — never a dropped socket."""
    def post(h):
        h.send_response(200)
        h.send_header("Content-Type", "text/event-stream")
        h.end_headers()
        h.wfile.write(b'data: {"choices": [{"text": "tok"}]}\n\n')
        h.wfile.flush()
        h.connection.close()             # death, no [DONE]

    stub = _stub_replica(post)
    router = _fake_router(n=1, ports=[stub.server_address[1]])
    httpd = router.serve(port=0, background=True)
    try:
        conn = http.client.HTTPConnection(
            "127.0.0.1", httpd.server_address[1], timeout=30)
        conn.request("POST", "/v1/completions",
                     body=json.dumps({"prompt": [1, 2], "stream": True,
                                      "max_tokens": 4}).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        raw = resp.read()
        conn.close()
        events = [ln[6:] for ln in raw.split(b"\n")
                  if ln.startswith(b"data: ")]
        assert events[-1] == b"[DONE]"
        err = json.loads(events[-2])["error"]
        assert err["type"] == "replica_failover"
        assert err["retry_after"] >= 1
        assert router.counts["stream_errors"] == 1
    finally:
        httpd.shutdown()
        stub.shutdown()


def test_stats_snapshot_shape():
    router = _fake_router(n=2)
    router.counts["failovers"] += 2
    snap = router.stats_snapshot()
    assert [r["idx"] for r in snap["replicas"]] == [0, 1]
    assert snap["replicas"][0]["state"] == HEALTHY
    assert snap["counters"]["failovers"] == 2
    assert snap["journal_depth"] == 0
    assert snap["config"]["replicas"] == 2
    json.dumps(snap)                     # JSON-ready end to end
    # the metric families the ISSUE names all exist in the registry
    rendered = router.registry.render()
    for fam in ("bigdl_tpu_router_replica_state",
                "bigdl_tpu_router_failovers_total",
                "bigdl_tpu_router_replays_total",
                "bigdl_tpu_router_hedges_total",
                "bigdl_tpu_router_breaker_trips_total",
                "bigdl_tpu_router_request_seconds"):
        assert fam in rendered


def test_crash_loop_subprocess_quarantine():
    """A replica whose process exits immediately on every spawn burns
    the crash budget and ends QUARANTINED while its peer keeps the
    service up (peer is a 1-line stub process, not a model)."""
    stub_src = (
        "import sys\n"
        "from http.server import BaseHTTPRequestHandler, HTTPServer\n"
        "class H(BaseHTTPRequestHandler):\n"
        "    def log_message(self, *a): pass\n"
        "    def do_GET(self):\n"
        "        b = b'{\"status\": \"ok\"}'\n"
        "        self.send_response(200)\n"
        "        self.send_header('Content-Length', str(len(b)))\n"
        "        self.end_headers()\n"
        "        self.wfile.write(b)\n"
        "HTTPServer(('127.0.0.1', int(sys.argv[1])), H).serve_forever()\n")

    def spawn(idx, port):
        if idx == 0:
            return subprocess.Popen([sys.executable, "-c",
                                     "import sys; sys.exit(3)"])
        return subprocess.Popen([sys.executable, "-c", stub_src,
                                 str(port)])

    router = Router(spawn=spawn, config=RouterConfig(
        replicas=2, health_sec=0.05, backoff_base_sec=0.05,
        crash_budget=3, crash_window_sec=30.0, spawn_timeout_sec=60.0))
    try:
        router.start(wait_healthy=True)
        deadline = time.monotonic() + 30
        while router.replicas[0].state != QUARANTINED \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert router.replicas[0].state == QUARANTINED
        assert router.replicas[0].restarts >= 2   # budget-1 respawns
        assert router.replicas[1].state == HEALTHY
        events = [e["event"] for e in router.flight.snapshot()]
        assert "replica_quarantined" in events
    finally:
        router.shutdown()


# -- subprocess chaos e2e ---------------------------------------------------

_FAULT_SPECS = {}          # idx -> spec; mutated by tests, read at spawn


def _spawn_replica(idx: int, port: int):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("BIGDL_TPU_FAULT_SPEC", None)
    spec = _FAULT_SPECS.get(idx)
    if spec:
        env["BIGDL_TPU_FAULT_SPEC"] = spec
    env["BIGDL_TPU_DRAIN_TIMEOUT_SEC"] = "30"
    cmd = [sys.executable, "-m", "bigdl_tpu.serving.api_server",
           "--tiny-random", "--tiny-seed", "7",
           "--host", "127.0.0.1", "--port", str(port),
           "--max-batch", "4", "--max-seq", "96", "--wedge-sec", "3"]
    return subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL)


def _wait_all_healthy(router, timeout=240.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(r.state == HEALTHY for r in router.replicas):
            return
        time.sleep(0.05)
    raise AssertionError(
        f"replicas not all healthy after {timeout}s: "
        f"{[(r.idx, r.state, r.last_exit) for r in router.replicas]}")


def _post(base, path, payload, timeout=300):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


@pytest.fixture(scope="module")
def cluster():
    """2 seeded tiny-random replicas behind a served router. Replica 0
    starts with a one-shot replica_crash fault (fires on its 8th step
    with live work — mid-burst); the first e2e test consumes it and
    clears the spec for the rest of the module."""
    _FAULT_SPECS[0] = "replica_crash@every=8,times=1"
    router = Router(spawn=_spawn_replica, config=RouterConfig(
        replicas=2, health_sec=0.2, backoff_base_sec=0.2,
        crash_budget=20, crash_window_sec=5.0, unhealthy_after=4,
        spawn_timeout_sec=240.0, drain_exit_timeout_sec=90.0))
    router.start(wait_healthy=True)
    httpd = router.serve(port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        _wait_all_healthy(router)
        yield router, base
    finally:
        _FAULT_SPECS.clear()
        httpd.shutdown()
        router.shutdown()


def _completion_burst(base, prompts, max_tokens=8):
    """Concurrent non-streaming completions; returns [(status, doc)]
    in prompt order."""
    results = [None] * len(prompts)

    def one(i):
        results[i] = _post(base, "/v1/completions",
                           {"prompt": prompts[i], "max_tokens": max_tokens,
                            "temperature": 0})

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


def test_e2e_replica_crash_loses_zero_requests(cluster):
    """The acceptance chaos run: replica 0 hard-crashes (os._exit 137,
    injected replica_crash) mid-burst; every non-streaming request
    still returns 200, and re-running the same greedy prompts on the
    healthy tier reproduces every answer byte-identically (replicas
    share seeded weights)."""
    router, base = cluster
    prompts = [[i + 1, i + 5, i + 9, 2, 3] for i in range(12)]
    results = _completion_burst(base, prompts)
    assert [s for s, _ in results] == [200] * 12
    texts = [d["choices"][0]["text"] for _, d in results]
    assert all(d["usage"]["completion_tokens"] == 8 for _, d in results)

    # the injected crash really fired and really was recovered from
    # (the supervisor records the death on its next probe tick, which
    # may land shortly after the failover itself)
    assert router.counts["failovers"] >= 1, router.stats_snapshot()
    assert router.counts["replays"] >= 1
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if any(e["event"] == "replica_death"
               for e in router.flight.snapshot()):
            break
        time.sleep(0.05)
    else:
        pytest.fail("supervisor never recorded the replica death")

    # disarm the fault for the rest of the module, flush the respawned
    # (still-armed) replica 0, then compare against a no-fault run
    _FAULT_SPECS.clear()
    _wait_all_healthy(router)
    r0 = router.replicas[0]
    os.kill(r0.pid, signal.SIGKILL)
    _wait_all_healthy(router)
    rerun = _completion_burst(base, prompts)
    assert [s for s, _ in rerun] == [200] * 12
    assert [d["choices"][0]["text"] for _, d in rerun] == texts


def test_e2e_kill9_single_request_replays_identically(cluster):
    """kill -9 the replica serving a request mid-flight: the client's
    request completes via replay with output identical to a no-fault
    run. Retries the kill dance if the request wins the race."""
    router, base = cluster
    for attempt in range(4):
        prompt = [40 + attempt, 41, 42, 43]
        payload = {"prompt": prompt, "max_tokens": 48, "temperature": 0}
        before = router.counts["failovers"]
        box = {}

        def go():
            box["resp"] = _post(base, "/v1/completions", payload)

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
        status, doc = box["resp"]
        if status != 200:
            for ev in router.flight.snapshot(last=40):
                print("flight:", ev)
        assert status == 200, doc
        assert doc["usage"]["completion_tokens"] == 48
        if router.counts["failovers"] > before:
            break                        # the kill landed mid-flight
    else:
        pytest.fail("4 attempts never caught the request in flight")
    _wait_all_healthy(router)
    status2, doc2 = _post(base, "/v1/completions", payload)
    assert status2 == 200
    assert doc2["choices"][0]["text"] == doc["choices"][0]["text"]


def test_e2e_streaming_death_structured_error(cluster):
    """Streaming client whose replica is killed mid-stream receives
    the structured error event + [DONE], not a dropped socket."""
    router, base = cluster
    host, port = base.replace("http://", "").split(":")
    _wait_all_healthy(router)
    for attempt in range(4):
        payload = {"prompt": [60 + attempt, 61, 62], "max_tokens": 64,
                   "temperature": 0, "stream": True}
        conn = http.client.HTTPConnection(host, int(port), timeout=300)
        conn.request("POST", "/v1/completions",
                     body=json.dumps(payload).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        victim = None
        deadline = time.monotonic() + 90
        while victim is None and time.monotonic() < deadline:
            for r in router.replicas:
                if r.inflight:
                    victim = r
                    break
            time.sleep(0.002)
        assert victim is not None
        time.sleep(0.05)
        try:
            os.kill(victim.pid, signal.SIGKILL)
        except (ProcessLookupError, TypeError):
            pass
        lines = resp.read().split(b"\n")
        conn.close()
        events = [ln[6:] for ln in lines if ln.startswith(b"data: ")]
        assert events and events[-1] == b"[DONE]"
        payloads = [json.loads(e) for e in events[:-1]]
        errs = [p["error"] for p in payloads if "error" in p]
        if errs:
            assert errs[0]["type"] == "replica_failover"
            assert errs[0]["code"] == 503
            assert errs[0]["retry_after"] >= 1
            break                        # structured error observed
        # stream finished before the kill landed: try again
        _wait_all_healthy(router)
    else:
        pytest.fail("4 attempts never killed a replica mid-stream")
    _wait_all_healthy(router)


def test_e2e_rolling_restart_zero_5xx(cluster):
    """POST /v1/admin/rolling_restart under concurrent load: both
    replicas get drained + respawned one at a time, the restart
    summary says ok, and NO client request sees a 5xx."""
    router, base = cluster
    _wait_all_healthy(router)
    gens_before = [r.generation for r in router.replicas]
    stop = threading.Event()
    codes = []
    lock = threading.Lock()

    def load(tid):
        i = 0
        while not stop.is_set():
            i += 1
            status, doc = _post(base, "/v1/completions",
                                {"prompt": [tid, i % 50 + 1, 3],
                                 "max_tokens": 2, "temperature": 0})
            with lock:
                codes.append((status, doc if status >= 500 else None))

    threads = [threading.Thread(target=load, args=(t,)) for t in (1, 2)]
    for t in threads:
        t.start()
    try:
        status, summary = _post(base, "/v1/admin/rolling_restart", {},
                                timeout=600)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=120)
    assert status == 200, summary
    assert summary["ok"] is True
    assert all(step.get("ok") for step in summary["rolling_restart"])
    gens_after = [r.generation for r in router.replicas]
    assert all(a > b for a, b in zip(gens_after, gens_before))
    assert codes, "load thread never completed a request"
    bad = [(c, d) for c, d in codes if c >= 500]
    assert not bad, bad[:5]
    _wait_all_healthy(router)
    # restart counter moved for every replica
    assert router.counts["restarts"] >= 2


def test_e2e_fleet_profiler_capture(cluster, tmp_path):
    """POST /v1/admin/profiler fans a time-boxed capture to every
    healthy replica SIMULTANEOUSLY: one fleet capture_id, one capture
    subdir per replica (created synchronously by the replica before it
    answers), the auto-stop watchdog owning the stop side, and the
    fleet perf aggregate riding /v1/router/stats."""
    router, base = cluster
    _wait_all_healthy(router)
    # make sure every replica has decoded (perf gauges need a step)
    # and give the stats poller a beat to pick the perf blocks up
    _completion_burst(base, [[1, 2, 3], [4, 5, 6]], max_tokens=4)
    log_dir = str(tmp_path / "fleet")
    status, doc = _post(base, "/v1/admin/profiler",
                        {"duration_sec": 1, "log_dir": log_dir})
    assert status == 200, doc
    assert doc["ok"] is True and doc["started"] == 2
    cap = doc["capture_id"]
    assert cap and doc["duration_sec"] == 1.0
    for row in doc["replicas"]:
        assert row["ok"] is True and row["status"] == 200
        # per-replica subdir keyed by the fleet capture id, already on
        # disk (same filesystem): replica start_profiler makedirs it
        assert row["log_dir"].startswith(os.path.join(log_dir, cap))
        assert os.path.isdir(row["log_dir"])
        assert row["body"]["capture_id"] == cap
    # the capture is stitched onto the trace timeline under its id
    spans = router.spans.spans_for(cap)
    assert len(spans) == 2
    assert {s["name"] for s in spans} == {"fleet_capture"}
    # input validation surfaces as 400s, not replica fan-out
    status, _ = _post(base, "/v1/admin/profiler",
                      {"log_dir": "relative/dir"})
    assert status == 400
    status, _ = _post(base, "/v1/admin/profiler",
                      {"duration_sec": -1, "log_dir": log_dir})
    assert status == 400
    # fleet perf aggregate: both replicas reporting, none tripped
    deadline = time.monotonic() + 10.0
    perf = {}
    while time.monotonic() < deadline:
        perf = router.stats_snapshot()["perf"]
        if len(perf["replicas"]) == 2:
            break
        time.sleep(0.1)
    assert len(perf["replicas"]) == 2, perf
    assert perf["sentinels_tripped"] == 0
    # CPU replicas have no published peaks, hence no roofline share
    # (observability/roofline.CHIP_PEAKS): the aggregate carries the
    # per-replica blocks and no fleet util
    assert "decode_util_min" not in perf
    for rep in perf["replicas"].values():
        assert rep["decode_ideal_ms"] is None
        assert rep["roofline_util_decode"] is None


def _start_canary_pair(drift_spec):
    """A healthy replica 0 behind a canary-probing router; replica 1
    joins with `drift_spec` only once replica 0 has defined EVERY
    golden. The first successful probe of a (prompt, kind) is the
    golden, and the drift is armed from replica 1's second step: were
    both started together, a late first probe of replica 0 would let
    the drifting one define the golden and the healthy one would be
    quarantined, so the result would ride on how fast a replica
    comes up."""
    _FAULT_SPECS.clear()
    router = Router(spawn=_spawn_replica, config=RouterConfig(
        replicas=1, health_sec=0.2, backoff_base_sec=0.2,
        crash_budget=20, crash_window_sec=5.0, unhealthy_after=4,
        spawn_timeout_sec=240.0, drain_exit_timeout_sec=90.0,
        canary_sec=0.3))
    router.start(wait_healthy=True)
    try:
        keys = len(router.canary._probe_specs(router.replicas[0]))
        want = {"goldens_recorded": keys}
        if router.canary.nll_tol > 0:
            want["nll_goldens_recorded"] = keys
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            snap = router.canary.snapshot()
            if all(snap[k] >= n for k, n in want.items()):
                break
            time.sleep(0.05)
        else:
            pytest.fail(f"goldens never recorded: {snap}")
        _FAULT_SPECS[1] = drift_spec
        joined = router.add_replica()
        assert joined.idx == 1
        # HEALTHY may last one sweep only: the quarantine under test
        # can come before this loop looks again
        deadline = time.monotonic() + 240.0
        while joined.state not in (HEALTHY, QUARANTINED):
            assert time.monotonic() < deadline, joined.snapshot()
            time.sleep(0.02)
    except BaseException:
        _FAULT_SPECS.clear()
        router.shutdown()
        raise
    return router


def _wait_quarantined(router, who):
    """Bounded by the quarantine itself (a sweep or two of 0.3 s once
    replica 1 answers), not by this limit."""
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        if router.replicas[1].state == QUARANTINED:
            return
        time.sleep(0.05)
    pytest.fail(f"{who} never quarantined the drifting replica: "
                f"{router.canary.snapshot()} "
                f"{router.stats_snapshot()['counters']}")


def test_e2e_canary_quarantines_drifting_replica():
    """The ISSUE-18 acceptance chaos run: replica 1 carries a sticky
    ``logit_drift`` fault — finite additive logit bias, so it stays
    fast, healthy and isfinite, and ONLY a golden byte comparison can
    tell it is answering wrong. The canary prober must quarantine
    exactly that replica, the healthy neighbor must keep reproducing
    its answers byte-identically, and no request may be lost across
    the quarantine transition."""
    router = _start_canary_pair("logit_drift@after_step=1,bias=8")
    httpd = router.serve(port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        # client burst racing the canary sweep: a request in flight on
        # the drifting replica when it is terminated must fail over
        prompts = [[i + 1, i + 4, 2, 3] for i in range(8)]
        results = _completion_burst(base, prompts)
        assert [s for s, _ in results] == [200] * 8

        _wait_quarantined(router, "canary")
        # exactly the drifting replica is isolated; goldens came from
        # the byte-correct neighbor, which stays in rotation
        assert router.replicas[0].state == HEALTHY
        assert router.counts["canary_failures"] >= 1
        assert router.counts["quarantined"] >= 1
        events = router.flight.snapshot()
        mism = [e for e in events if e["event"] == "canary_mismatch"]
        assert mism and all(e["replica"] == 1 for e in mism)
        assert all(e["expected"] != e["got"] for e in mism)
        quar = [e for e in events
                if e["event"] == "replica_quarantined"]
        assert [e["replica"] for e in quar] == [1]
        assert quar[0]["reason"] == "canary_mismatch"

        # the healthy tier keeps serving: zero lost requests, and the
        # greedy answers are byte-stable run over run
        first = _completion_burst(base, prompts)
        assert [s for s, _ in first] == [200] * 8
        again = _completion_burst(base, prompts)
        assert [s for s, _ in again] == [200] * 8
        assert ([d["choices"][0]["text"] for _, d in first]
                == [d["choices"][0]["text"] for _, d in again])
        # quarantine is terminal — no respawn feeds wrong weights back
        assert router.replicas[1].state == QUARANTINED
        # fleet stats surface the canary verdict
        snap = router.stats_snapshot()
        assert snap["slo"]["canary"]["failures_total"] >= 1
        assert snap["slo"]["canary"]["goldens_recorded"] >= 1
        assert snap["counters"]["canary_failures"] >= 1
    finally:
        _FAULT_SPECS.clear()
        httpd.shutdown()
        router.shutdown()


def test_e2e_nll_canary_quarantines_byte_identical_drift(monkeypatch):
    """The quality-observability acceptance chaos run: replica 1
    carries a NEGATIVE logit_drift bias on vocab column 0 — it never
    flips an argmax, so every byte of its greedy answers stays golden
    and the byte-equality canary is provably blind to it. Only the
    distribution drifts (~4e-3 nats/token on the tiny-random model).
    With BIGDL_TPU_CANARY_NLL_TOL set below that, the NLL-tolerance
    mode must quarantine exactly the drifting replica, with
    kind='nll' mismatches and zero byte mismatches."""
    # healthy replicas are bit-deterministic twins (same seed, greedy)
    # so their NLLs agree exactly; 1e-3 sits well under the ~4e-3
    # drift and well over float noise
    monkeypatch.setenv("BIGDL_TPU_CANARY_NLL_TOL", "0.001")
    router = _start_canary_pair("logit_drift@after_step=1,bias=-8")
    assert router.canary.nll_tol == 0.001
    try:
        _wait_quarantined(router, "NLL canary")
        assert router.replicas[0].state == HEALTHY
        # every mismatch was an NLL verdict — the bytes never differed
        events = router.flight.snapshot()
        mism = [e for e in events if e["event"] == "canary_mismatch"]
        assert mism and all(e["replica"] == 1 for e in mism)
        assert all(e["kind"] == "nll" for e in mism)
        assert all(e["expected"].startswith("nll=") for e in mism)
        snap = router.canary.snapshot()
        assert snap["nll_failures_total"] >= 1
        assert snap["nll_failures_total"] == snap["failures_total"]
        assert snap["nll_goldens_recorded"] >= 1
        # quarantine is terminal, and the fleet stats carry the
        # per-replica quality aggregation from the live engines
        assert router.replicas[1].state == QUARANTINED
        stats = router.stats_snapshot()
        assert stats["counters"]["canary_failures"] >= 1
        quality = stats.get("quality")
        assert quality is not None and quality.get("replicas")
    finally:
        _FAULT_SPECS.clear()
        router.shutdown()
