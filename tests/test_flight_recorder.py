"""Flight-recorder / compile-telemetry / postmortem tests: ring-buffer
bounds, tracked_jit compile counting (cache hits vs new shapes, storm
warning), postmortem dumps on injected step exceptions and stall-guard
trips, the /v1/debug/dump and /v1/profiler/status endpoints, event-log
rotation, StepTimer interpolated percentiles."""

import glob
import json
import os
import signal
import urllib.request

import pytest

from bigdl_tpu.observability import (FlightRecorder, MetricsRegistry,
                                     RequestTracer, build_postmortem,
                                     compile_table,
                                     resolve_event_log_max_bytes,
                                     resolve_recompile_threshold,
                                     tracked_jit, validate_postmortem_dir)
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
# ring buffer
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_bounds():
    fr = FlightRecorder(capacity=8)
    for i in range(20):
        fr.record("step", step=i)
    assert len(fr) == 8
    assert fr.total_recorded == 20
    ev = fr.snapshot()
    # oldest first, only the most recent 8 survive
    assert [e["step"] for e in ev] == list(range(12, 20))
    assert all(e["event"] == "step" and "ts" in e for e in ev)
    tail = fr.snapshot(last=3)
    assert [e["step"] for e in tail] == [17, 18, 19]
    fr.clear()
    assert len(fr) == 0
    assert fr.total_recorded == 20      # lifetime count survives clear


def test_flight_recorder_capacity_validated():
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


# ---------------------------------------------------------------------------
# tracked_jit compile accounting
# ---------------------------------------------------------------------------

def test_tracked_jit_counts_compiles_not_cache_hits():
    import jax.numpy as jnp

    reg = MetricsRegistry()
    f = tracked_jit("t_flight_add", lambda a, b: a + b, registry=reg)
    x = jnp.ones((2, 3))
    f(x, x)
    f(x, x)                              # cache hit: same signature
    assert f.compiles == 1
    f(jnp.ones((4, 3)), jnp.ones((4, 3)))  # new shape: a compile
    assert f.compiles == 2

    ent = compile_table()["t_flight_add"]
    assert ent["compiles"] == 2
    assert ent["total_s"] > 0
    assert not ent["storm"]
    sigs = [s["signature"] for s in ent["signatures"]]
    assert "float32[2,3]" in sigs[0] and "float32[4,3]" in sigs[1]

    # metrics mirrored into the explicit registry AND the default one
    from bigdl_tpu.observability import default_registry

    def series(snap, name):
        return [s for s in snap[name]["series"]
                if s["labels"] == {"fn": "t_flight_add"}]

    for r in (reg, default_registry()):
        snap = r.snapshot()
        assert series(snap, "bigdl_tpu_jit_compiles_total")[0]["value"] \
            == 2
        assert series(snap, "bigdl_tpu_jit_compile_seconds")[0]["count"] \
            == 2


def test_tracked_jit_decorator_and_static_args():
    import functools

    import jax.numpy as jnp

    @functools.partial(tracked_jit, "t_flight_scale",
                       static_argnames=("k",))
    def scale(x, *, k):
        return x * k

    x = jnp.ones((3,))
    scale(x, k=2)
    scale(x, k=2)
    assert scale.compiles == 1
    scale(x, k=3)                        # new static value: a compile
    assert scale.compiles == 2
    # jit attributes still reachable through the wrapper
    assert hasattr(scale, "lower")


def test_tracked_jit_recompile_storm_warns(caplog):
    import jax.numpy as jnp

    f = tracked_jit("t_flight_storm", lambda x: x + 1, warn_threshold=3)
    with caplog.at_level("WARNING",
                         logger="bigdl_tpu.observability.compile_watch"):
        for n in range(1, 5):
            f(jnp.ones((n,)))            # every call a new shape
    assert f.compiles == 4
    assert compile_table()["t_flight_storm"]["storm"] is True
    assert any("recompile storm" in r.message for r in caplog.records)


def test_resolve_recompile_threshold(monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_RECOMPILE_WARN", raising=False)
    assert resolve_recompile_threshold() == 8
    assert resolve_recompile_threshold(3) == 3
    monkeypatch.setenv("BIGDL_TPU_RECOMPILE_WARN", "12")
    assert resolve_recompile_threshold() == 12
    monkeypatch.setenv("BIGDL_TPU_RECOMPILE_WARN", "zero")
    with pytest.raises(ValueError):
        resolve_recompile_threshold()
    with pytest.raises(ValueError):
        resolve_recompile_threshold(0)


# ---------------------------------------------------------------------------
# postmortem dumps
# ---------------------------------------------------------------------------

def _read_single_postmortem(directory, reason):
    files = glob.glob(os.path.join(directory, f"*-{reason}.json"))
    assert files, f"no {reason} postmortem in {os.listdir(directory)}"
    with open(files[-1]) as f:
        return json.load(f)


def test_step_exception_writes_postmortem(model, tmp_path, monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_POSTMORTEM_DIR", str(tmp_path))
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=64),
                    registry=MetricsRegistry())
    eng.add_request("boom", [1, 2, 3, 4], SamplingParams(max_tokens=8))

    def raiser(*a, **k):
        raise RuntimeError("injected decode failure")

    # break the decode jit on whichever path this step takes (legacy
    # multi-dispatch or the resident single-dispatch variant)
    eng._decode = raiser
    eng._decode_resident = raiser
    # the failure is blamed on the lone active request, which gets
    # quarantined after its crash budget — the engine keeps running
    # instead of propagating (blast-radius isolation; the postmortem
    # below is the forensic record)
    for _ in range(16):
        eng.step()
        if not eng.has_unfinished():
            break
    outs = eng.get_outputs("boom")
    assert outs and outs[-1].finish_reason == "error"
    assert outs[-1].error["reason"] == "crash_loop"

    dump = _read_single_postmortem(str(tmp_path),
                                   "engine_step_exception")
    assert dump["reason"] == "engine_step_exception"
    assert dump["error"]["type"] == "RuntimeError"
    assert "injected decode failure" in dump["error"]["message"]
    # the four sections the dump exists to preserve
    events = [e["event"] for e in dump["flight"]]
    assert "engine_init" in events and "step_exception" in events
    assert "admit_start" in events       # the doomed request's trail
    assert "spans" in dump and "metrics" in dump
    assert "engine_prefill" in dump["compile_table"]
    assert dump["config"]["max_batch"] == 2
    assert dump["fingerprint"]["pid"] == os.getpid()


def test_stall_guard_trip_writes_postmortem(model, tmp_path, monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_POSTMORTEM_DIR", str(tmp_path))
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128,
                                        preempt_after_steps=2),
                    registry=MetricsRegistry())
    eng.add_request("a", [1, 2, 3], SamplingParams(max_tokens=30))
    eng.add_request("b", [4, 5, 6], SamplingParams(max_tokens=4))
    while eng.has_unfinished():
        eng.step()

    dump = _read_single_postmortem(str(tmp_path), "stall_guard_trip")
    assert dump["reason"] == "stall_guard_trip"
    assert "error" not in dump           # a trip is not an exception
    trips = [e for e in dump["flight"] if e["event"] == "stall_guard_trip"]
    assert trips and trips[0]["queue_depth"] >= 1
    # both the trip and the preemption it triggered are on the tape
    all_events = [e["event"] for e in eng.flight.snapshot()]
    assert "preempt" in all_events and "finish" in all_events


def test_write_postmortem_unconfigured_is_noop(model, monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_POSTMORTEM_DIR", raising=False)
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=64),
                    registry=MetricsRegistry())
    assert eng.write_postmortem("noop") is None


def test_build_postmortem_sections_degrade():
    class BadTracer:
        def snapshot(self, recent=32):
            raise RuntimeError("tracer broken")

    dump = build_postmortem("partial", tracer=BadTracer())
    assert dump["reason"] == "partial"
    assert "error" in dump["spans"]      # degraded, not raised


def test_validate_postmortem_dir(tmp_path):
    ok = validate_postmortem_dir(str(tmp_path))
    assert ok["exists"] and ok["writable"]
    # missing-but-creatable: some writable ancestor exists
    missing = validate_postmortem_dir(str(tmp_path / "a" / "b"))
    assert not missing["exists"] and missing["writable"]
    f = tmp_path / "file.txt"
    f.write_text("x")
    bad = validate_postmortem_dir(str(f))
    assert not bad["writable"] and "not a directory" in bad["error"]


def test_install_signal_dumps_chains_previous_handler():
    from bigdl_tpu.observability import install_signal_dumps

    seen = []
    orig = signal.signal(signal.SIGUSR1, lambda s, f: seen.append("prev"))
    try:
        install_signal_dumps(lambda reason: seen.append(reason),
                             signals=(signal.SIGUSR1,))
        signal.raise_signal(signal.SIGUSR1)
        assert seen == ["signal_SIGUSR1", "prev"]
    finally:
        signal.signal(signal.SIGUSR1, orig)


# ---------------------------------------------------------------------------
# server endpoints + the /metrics acceptance loop
# ---------------------------------------------------------------------------

def test_debug_dump_and_profiler_status_endpoints(model):
    from bigdl_tpu.serving.api_server import OpenAIServer

    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128),
                    registry=MetricsRegistry(),
                    tracer=RequestTracer(event_log_path=""))
    server = OpenAIServer(eng)
    httpd = server.serve(port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        def completion():
            req = urllib.request.Request(
                f"{base}/v1/completions",
                data=json.dumps({"prompt": [1, 2, 3, 4],
                                 "max_tokens": 4}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                json.loads(r.read())

        def jit_compiles():
            with urllib.request.urlopen(f"{base}/metrics",
                                        timeout=30) as r:
                text = r.read().decode()
            return {
                line.split()[0]: float(line.split()[1])
                for line in text.splitlines()
                if line.startswith("bigdl_tpu_jit_compiles_total{")}

        completion()
        counts = jit_compiles()
        # the decode jit is "engine_decode_resident" on the resident
        # fast path, "engine_decode" on the legacy one — either counts
        assert any(
            counts.get('bigdl_tpu_jit_compiles_total{fn="%s"}' % fn, 0)
            >= 1 for fn in ("engine_decode", "engine_decode_resident"))
        assert counts['bigdl_tpu_jit_compiles_total{fn="engine_prefill"}'] \
            >= 1
        # second identical request: every signature already compiled
        completion()
        assert jit_compiles() == counts

        with urllib.request.urlopen(f"{base}/v1/debug/dump",
                                    timeout=30) as r:
            dump = json.loads(r.read())
        assert dump["reason"] == "on_demand"
        for key in ("flight", "spans", "metrics", "compile_table",
                    "config", "fingerprint"):
            assert key in dump, key
        assert any(e["event"] == "finish" for e in dump["flight"])
        assert any(
            dump["compile_table"].get(fn, {}).get("compiles", 0) >= 1
            for fn in ("engine_decode", "engine_decode_resident"))

        with urllib.request.urlopen(f"{base}/v1/profiler/status",
                                    timeout=30) as r:
            status = json.loads(r.read())
        assert status["capturing"] is False

        # stats snapshot carries the compile table too
        with urllib.request.urlopen(f"{base}/v1/stats", timeout=30) as r:
            stats = json.loads(r.read())
        assert stats["engine_steps"] >= 1
        assert any(fn.startswith("engine_decode")
                   for fn in stats["compile_table"])
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# event-log rotation
# ---------------------------------------------------------------------------

def test_event_log_rotation(tmp_path):
    log = tmp_path / "events.jsonl"
    tr = RequestTracer(event_log_path=str(log), event_log_max_bytes=400)
    for i in range(40):
        tr.start(f"r{i}", prompt_len=3)
        tr.admitted(f"r{i}")
        tr.finish(f"r{i}", "stop", n_generated=2)
    tr.close()
    rolled = tmp_path / "events.jsonl.1"
    assert rolled.exists()
    # both generations stay parseable JSONL and near the bound
    for p in (log, rolled):
        assert p.stat().st_size <= 400 + 200     # limit + one line slack
        for line in p.read_text().splitlines():
            assert json.loads(line)["event"] in ("enqueue", "admit",
                                                 "finish")


def test_resolve_event_log_max_bytes(monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_EVENT_LOG_MAX_BYTES", raising=False)
    assert resolve_event_log_max_bytes() is None
    assert resolve_event_log_max_bytes(1024) == 1024
    monkeypatch.setenv("BIGDL_TPU_EVENT_LOG_MAX_BYTES", "2048")
    assert resolve_event_log_max_bytes() == 2048
    monkeypatch.setenv("BIGDL_TPU_EVENT_LOG_MAX_BYTES", "-1")
    with pytest.raises(ValueError):
        resolve_event_log_max_bytes()


# ---------------------------------------------------------------------------
# StepTimer percentiles
# ---------------------------------------------------------------------------

def test_steptimer_interpolated_percentiles():
    from bigdl_tpu.utils.profiling import StepTimer

    t = StepTimer()
    for v in (0.010, 0.020, 0.030, 0.040):
        t.record("step", v)
    s = t.summary()["step"]
    # even-length median is the midpoint of the middle pair — the old
    # `s[len(s) // 2]` picked 30ms here
    assert s["p50_ms"] == pytest.approx(25.0)
    assert s["p90_ms"] == pytest.approx(37.0)
    assert s["p99_ms"] == pytest.approx(39.7)
    single = StepTimer()
    single.record("one", 0.005)
    assert single.summary()["one"]["p99_ms"] == pytest.approx(5.0)
