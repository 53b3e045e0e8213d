"""Fleet SLO engine: burn-rate alerting, usage metering, canaries.

Coverage map (ISSUE 18):

- ``resolve_slo_spec`` — defaults, JSON overrides, every rejection
  path ``utils/env_check.py`` relies on;
- ``SlidingHistogram`` / ``SlidingCounts`` — windowed aggregation,
  exact ``count_above`` at a spliced target bound, pruning;
- ``SLOTracker`` — Google-SRE fast/slow burn alerting with a fake
  clock: page-grade alert fires, ``min_events`` cold-start gate,
  hysteresis recovery, flight + metrics + JSONL sink emission;
- ``UsageLedger`` — rollup, JSONL records, shed accounting, and the
  EXACT reconciliation against the engine's PR-7 tenant counters;
- ``CanaryProber`` — golden record/compare against a stub router,
  mismatch quarantine, transport-error tolerance;
- ``logit_drift`` fault — parse validation + sticky ``drift_rows``;
- satellite gate — ``stats.percentile`` ≡ ``np.percentile``.
"""

from __future__ import annotations

import json
import pathlib
import sys
import threading

import numpy as np
import pytest

from bigdl_tpu.observability.metrics import MetricsRegistry
from bigdl_tpu.observability.slo import (
    SLOTracker,
    SlidingCounts,
    SlidingHistogram,
    resolve_slo_spec,
)
from bigdl_tpu.observability.stats import percentile
from bigdl_tpu.observability.usage import UsageLedger
from bigdl_tpu.robustness.faults import FaultInjector, parse_fault_spec
from bigdl_tpu.serving.canary import (
    CanaryProber,
    resolve_canary_sec,
)

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))


# ---------------------------------------------------------------------------
# resolve_slo_spec


def test_spec_defaults():
    spec = resolve_slo_spec("")
    assert set(spec["qos"]) == {"interactive", "standard", "batch"}
    assert spec["qos"]["interactive"]["tpot_p99_ms"] == 200.0
    assert spec["windows"] == {"fast_sec": 300.0, "slow_sec": 3600.0}
    assert spec["burn"] == {"fast": 14.4, "slow": 3.0}
    assert spec["eval_sec"] == 5.0
    assert spec["recover_evals"] == 3
    assert spec["min_events"] == 12


def test_spec_overrides():
    spec = resolve_slo_spec(json.dumps({
        "interactive": {"tpot_p99_ms": 50, "availability": 0.9999},
        "windows": {"fast_sec": 60, "slow_sec": 600},
        "burn": {"fast": 10},
        "eval_sec": 0.5, "min_events": 3, "recover_evals": 1}))
    assert spec["qos"]["interactive"]["tpot_p99_ms"] == 50.0
    assert spec["qos"]["interactive"]["availability"] == 0.9999
    # untouched classes keep their defaults
    assert spec["qos"]["batch"]["tpot_p99_ms"] == 1000.0
    assert spec["windows"]["fast_sec"] == 60.0
    assert spec["burn"] == {"fast": 10.0, "slow": 3.0}
    assert spec["min_events"] == 3


def test_spec_env_pickup(monkeypatch):
    monkeypatch.setenv("BIGDL_TPU_SLO_SPEC",
                       json.dumps({"eval_sec": 1.25}))
    assert resolve_slo_spec()["eval_sec"] == 1.25


@pytest.mark.parametrize("raw,msg", [
    ("{not json", "not valid JSON"),
    ("[1, 2]", "must be a JSON object"),
    ('{"widget": 1}', "unknown SLO spec key"),
    ('{"interactive": {"p50_ms": 1}}', "unknown SLO objective"),
    ('{"interactive": {"tpot_p99_ms": -3}}', "positive number"),
    ('{"interactive": {"tpot_p99_ms": true}}', "positive number"),
    ('{"interactive": {"error_rate": 1.5}}', r"in \(0, 1\)"),
    ('{"interactive": {"availability": 1}}', r"in \(0, 1\)"),
    ('{"windows": {"fast_sec": 600, "slow_sec": 60}}',
     "fast_sec must be <="),
    ('{"windows": {"mid_sec": 5}}', "unknown SLO windows key"),
    ('{"burn": {"medium": 5}}', "unknown SLO burn key"),
    ('{"recover_evals": 0}', "integer >= 1"),
    ('{"min_events": 1.5}', "integer >= 1"),
    ('{"eval_sec": 0}', "positive number"),
])
def test_spec_rejections(raw, msg):
    with pytest.raises(ValueError, match=msg):
        resolve_slo_spec(raw)


# ---------------------------------------------------------------------------
# sliding windows


def test_sliding_histogram_window_and_count_above():
    h = SlidingHistogram(bounds=(10.0, 200.0, 1000.0),
                         max_window_s=100.0, slice_s=1.0)
    t = 1000.0
    h.observe(5.0, t)        # <= 10 bucket
    h.observe(150.0, t)      # <= 200 bucket
    h.observe(500.0, t + 2)  # <= 1000 bucket
    counts, total, acc = h.window(100.0, t + 2)
    assert total == 3 and acc == 655.0
    # threshold AT a bound is exact: only strictly-above buckets count
    assert h.count_above(200.0, 100.0, t + 2) == (1, 3)
    assert h.count_above(10.0, 100.0, t + 2) == (2, 3)
    # a narrow window excludes the older slice
    assert h.count_above(200.0, 1.0, t + 2) == (1, 1)


def test_sliding_histogram_prunes_old_slices():
    h = SlidingHistogram(bounds=(10.0,), max_window_s=5.0, slice_s=1.0)
    h.observe(1.0, 100.0)
    h.observe(2.0, 110.0)    # first slice now beyond max_window
    assert len(h._slices) == 1
    _, total, _ = h.window(5.0, 110.0)
    assert total == 1


def test_sliding_histogram_quantile():
    h = SlidingHistogram(bounds=(10.0, 20.0), max_window_s=60.0,
                         slice_s=1.0)
    for v in (5.0, 5.0, 15.0, 15.0):
        h.observe(v, 50.0)
    q = h.quantile(0.5, 60.0, 50.0)
    assert q is not None and 0.0 < q <= 10.0
    assert h.quantile(0.99, 60.0, 50.0) <= 20.0
    empty = SlidingHistogram(bounds=(1.0,), max_window_s=5.0,
                             slice_s=1.0)
    assert empty.quantile(0.5, 5.0, 0.0) is None


def test_sliding_counts_window():
    c = SlidingCounts(max_window_s=10.0, slice_s=1.0)
    c.add("ok", 100.0)
    c.add("ok", 100.0)
    c.add("shed", 105.0)
    assert c.window(10.0, 105.0) == {"ok": 2, "shed": 1}
    assert c.window(1.0, 105.0) == {"shed": 1}
    c.add("ok", 130.0)       # prunes everything older
    assert c.window(10.0, 130.0) == {"ok": 1}


# ---------------------------------------------------------------------------
# SLOTracker state machine (fake clock throughout)


class _Clock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


class _Flight:
    def __init__(self):
        self.events = []

    def record(self, event, **kw):
        self.events.append((event, kw))


_TINY = json.dumps({
    "windows": {"fast_sec": 60, "slow_sec": 120},
    "eval_sec": 0.5, "min_events": 5, "recover_evals": 2})


def _tiny_tracker(**kw):
    clock = _Clock()
    tr = SLOTracker(spec=resolve_slo_spec(_TINY), time_fn=clock, **kw)
    return tr, clock


def test_burn_alert_fires_fast():
    tr, clock = _tiny_tracker()
    # every TPOT sample blows the 200ms interactive target:
    # burn = (bad/total)/0.01 = 100 >> 14.4 (page) once min_events fill
    for _ in range(10):
        tr.observe_tpot("interactive", 0.5)
    transitions = tr.evaluate(clock())
    burns = [t for t in transitions if t["event"] == "slo_burn"]
    assert len(burns) == 1
    (tr_ev,) = burns
    assert tr_ev["qos"] == "interactive"
    assert tr_ev["objective"] == "tpot_p99"
    assert tr_ev["severity"] == "fast"
    assert tr_ev["burn_fast"] == 100.0
    assert tr.alerts_active() == 1
    assert tr.burn_rate_max() == 100.0


def test_min_events_cold_start_gate():
    tr, clock = _tiny_tracker()
    for _ in range(4):       # one short of min_events=5
        tr.observe_tpot("interactive", 0.5)
    assert tr.evaluate(clock()) == []
    assert tr.alerts_active() == 0
    tr.observe_tpot("interactive", 0.5)
    assert any(t["event"] == "slo_burn" for t in tr.evaluate(clock()))


def test_availability_burn_from_shed():
    tr, clock = _tiny_tracker()
    for _ in range(5):
        tr.observe_result("interactive", "shed")
    transitions = tr.evaluate(clock())
    assert any(t["objective"] == "availability" for t in transitions)
    # finish-reason mapping: stop/length/abort/deadline are ok
    tr2, clock2 = _tiny_tracker()
    for reason in ("stop", "length", "abort", "deadline", "stop"):
        tr2.observe_finish("standard", reason)
    assert tr2.evaluate(clock2()) == []
    for _ in range(5):
        tr2.observe_finish("standard", "internal_error")
    assert any(t["objective"] == "error_rate"
               for t in tr2.evaluate(clock2()))


def test_hysteresis_recovery_needs_consecutive_good_evals():
    flight = _Flight()
    tr, clock = _tiny_tracker(flight=flight)
    for _ in range(6):
        tr.observe_tpot("interactive", 0.5)
    tr.evaluate(clock())
    assert tr.alerts_active() == 1
    # age every bad sample out of the slow window: burn drops to 0,
    # but recover_evals=2 consecutive healthy passes are required
    clock.t += 130.0
    assert tr.evaluate(clock()) == []      # good eval #1: still active
    assert tr.alerts_active() == 1
    clock.t += 1.0
    transitions = tr.evaluate(clock())     # good eval #2: recovers
    assert [t["event"] for t in transitions] == ["slo_recover"]
    assert tr.alerts_active() == 0
    names = [e for e, _ in flight.events]
    assert names == ["slo_burn", "slo_recover"]


def test_alert_interrupts_recovery_countdown():
    tr, clock = _tiny_tracker()
    for _ in range(6):
        tr.observe_tpot("interactive", 0.5)
    tr.evaluate(clock())
    clock.t += 130.0
    tr.evaluate(clock())                   # good eval #1
    for _ in range(6):                     # relapse before eval #2
        tr.observe_tpot("interactive", 0.5)
    assert tr.evaluate(clock()) == []      # same alert stays active
    assert tr.alerts_active() == 1
    clock.t += 130.0
    tr.evaluate(clock())                   # countdown restarted at 0
    assert tr.alerts_active() == 1


def test_maybe_evaluate_throttles_to_eval_sec():
    tr, clock = _tiny_tracker()
    tr.maybe_evaluate()
    first = tr._last_eval
    clock.t += 0.1                         # < eval_sec=0.5
    tr.maybe_evaluate()
    assert tr._last_eval == first
    clock.t += 1.0
    tr.maybe_evaluate()
    assert tr._last_eval > first


def test_compliance_fraction():
    tr, clock = _tiny_tracker()
    for _ in range(8):
        tr.observe_tpot("interactive", 0.01)   # inside 200ms target
    for _ in range(2):
        tr.observe_tpot("interactive", 0.5)
    assert tr.compliance("interactive", "tpot", "fast") == 0.8
    assert tr.compliance("interactive", "ttft", "fast") is None


def test_alert_metrics_render(capsys):
    reg = MetricsRegistry()
    clock = _Clock()
    tr = SLOTracker(spec=resolve_slo_spec(_TINY), registry=reg,
                    time_fn=clock)
    for _ in range(6):
        tr.observe_tpot("interactive", 0.5)
    tr.evaluate(clock())
    text = reg.render()
    assert "bigdl_tpu_slo_burn_rate" in text
    assert 'qos="interactive"' in text
    line = next(l for l in text.splitlines()
                if l.startswith("bigdl_tpu_slo_alerts_total")
                and 'severity="fast"' in l and 'qos="interactive"' in l
                and 'objective="tpot_p99"' in l)
    assert line.rsplit(" ", 1)[1] == "1"


def test_alert_jsonl_sink(tmp_path):
    log = tmp_path / "slo_alerts.jsonl"
    clock = _Clock()
    tr = SLOTracker(spec=resolve_slo_spec(_TINY),
                    alert_log_path=str(log), time_fn=clock)
    for _ in range(6):
        tr.observe_tpot("interactive", 0.5)
    tr.evaluate(clock())
    clock.t += 130.0
    tr.evaluate(clock())
    clock.t += 1.0
    tr.evaluate(clock())
    docs = [json.loads(l) for l in log.read_text().splitlines()]
    assert [d["event"] for d in docs] == ["slo_burn", "slo_recover"]
    assert docs[0]["severity"] == "fast"
    assert docs[0]["ts"] == 1000.0


def test_snapshot_shape():
    tr, clock = _tiny_tracker()
    tr.observe_ttft("interactive", 0.02)
    tr.observe_tpot("interactive", 0.5)
    tr.observe_result("interactive", "ok")
    tr.evaluate(clock())
    snap = tr.snapshot()
    assert snap["alerts_active"] == 0      # min_events gate
    assert snap["alerts_total"] == 0
    assert snap["spec"]["min_events"] == 5
    q = snap["qos"]["interactive"]
    assert q["ttft_count"] == 1
    assert q["tpot_count"] == 1
    assert q["events"] == {"ok": 1}
    assert set(q["objectives"]) == {"ttft_p99", "tpot_p99",
                                    "error_rate", "availability"}
    for o in q["objectives"].values():
        assert set(o["burn"]) == {"fast", "slow"}
        assert o["alert"] is None


# ---------------------------------------------------------------------------
# usage ledger


def test_usage_rollup_without_path():
    led = UsageLedger()
    led.record_finish("r1", "acme", "interactive", prompt_tokens=10,
                      generated_tokens=20, finish_reason="stop",
                      queue_wait_s=0.1, ttft_s=0.05, tpot_s=0.01)
    led.record_finish("r2", "acme", "batch", prompt_tokens=5,
                      generated_tokens=7, finish_reason="error")
    led.record_shed("r3", "hog", "batch", reason="quota")
    assert led.totals() == {
        "acme": {"requests": 2, "shed": 0, "generated_tokens": 27},
        "hog": {"requests": 0, "shed": 1, "generated_tokens": 0},
    }
    snap = led.snapshot()
    acme = snap["tenants"]["acme"]
    assert acme["prompt_tokens"] == 15
    assert acme["errors"] == 1
    assert acme["mean_ttft_s"] == 0.05
    assert snap["records_total"] == 3
    assert snap["ledger_path"] is None
    assert led.drain() is False            # no sink thread to drain


def test_usage_jsonl_ledger(tmp_path):
    path = tmp_path / "usage.jsonl"
    led = UsageLedger(path=str(path))
    led.record_finish("r1", "acme", "standard", prompt_tokens=3,
                      generated_tokens=8, finish_reason="length")
    led.record_shed("r2", "acme", "standard", reason="brownout")
    assert led.drain() is True
    docs = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(docs) == 2
    assert docs[0]["rid"] == "r1"
    assert docs[0]["tenant"] == "acme"
    assert docs[0]["outcome"] == "finish"
    assert docs[0]["generated_tokens"] == 8
    assert docs[1]["outcome"] == "shed"
    assert docs[1]["reason"] == "brownout"


def test_usage_ledger_thread_safety():
    led = UsageLedger()

    def work(tag):
        for i in range(200):
            led.record_finish(f"{tag}-{i}", tag, "standard",
                              prompt_tokens=1, generated_tokens=2,
                              finish_reason="stop")

    threads = [threading.Thread(target=work, args=(f"t{j}",))
               for j in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tot = led.totals()
    assert all(tot[f"t{j}"]["requests"] == 200 for j in range(4))
    assert sum(v["generated_tokens"] for v in tot.values()) == 1600


# ---------------------------------------------------------------------------
# canary prober (stub router — no processes)


class _StubReplica:
    def __init__(self, idx, state="H"):
        self.idx = idx
        self.port = 9000 + idx
        self.state = state
        self.role = "any"


class _StubRouter:
    host = "127.0.0.1"

    def __init__(self, n=2):
        self.replicas = [_StubReplica(i) for i in range(n)]
        self.probes = 0
        self.mismatches = []

    def canary_probe(self):
        self.probes += 1

    def canary_mismatch(self, r, **kw):
        self.mismatches.append((r.idx, kw))
        r.state = "Q"        # quarantine: later probes must skip it


@pytest.fixture
def stub_router(monkeypatch):
    import bigdl_tpu.serving.canary as canary_mod
    # the prober compares replica state against router.HEALTHY
    monkeypatch.setattr("bigdl_tpu.serving.router.HEALTHY", "H")
    return _StubRouter(), canary_mod


def _doc(text):
    return {"id": "cmpl-x", "choices": [
        {"text": text, "finish_reason": "length", "index": 0}]}


def test_canary_goldens_then_quarantine(stub_router, monkeypatch):
    router, _ = stub_router
    prober = CanaryProber(router, interval_sec=0.0)
    answers = {9000: "alpha", 9001: "alpha"}
    monkeypatch.setattr(
        prober, "_post_completion",
        lambda port, prompt, headers=None: _doc(answers[port]))
    out = prober.sweep()
    # 3 probes per replica (plain + 2 prefix), all agree: goldens only
    assert out == {"probes": 6, "mismatches": 0}
    assert len(prober.goldens) == 3
    assert router.probes == 6
    assert router.mismatches == []

    answers[9001] = "DRIFTED"              # replica 1 starts diverging
    out = prober.sweep()
    assert out["mismatches"] == 1          # quarantined on first hit
    assert router.replicas[1].state == "Q"
    assert router.replicas[0].state == "H"
    (idx, kw), = router.mismatches
    assert idx == 1
    assert kw["kind"] == "plain" and kw["prompt_idx"] == 0
    assert "DRIFTED" in kw["got"] and "alpha" in kw["expected"]
    # quarantined replicas are skipped on the next sweep
    assert prober.sweep()["probes"] == 3


def test_canary_transport_errors_are_not_mismatches(stub_router,
                                                    monkeypatch):
    router, _ = stub_router
    prober = CanaryProber(router, interval_sec=0.0)
    monkeypatch.setattr(prober, "_post_completion",
                        lambda port, prompt, headers=None: None)
    out = prober.sweep()
    assert out == {"probes": 6, "mismatches": 0}
    assert prober.goldens == {}            # liveness is not our job
    snap = prober.snapshot()
    assert snap["probes_total"] == 6 and snap["failures_total"] == 0


def test_canary_canonicalization_ignores_ids():
    a = _doc("same")
    b = {"id": "cmpl-OTHER", "created": 123, "choices": [
        {"finish_reason": "length", "text": "same", "index": 0,
         "logprobs": None}]}
    assert CanaryProber._canonical(a) == CanaryProber._canonical(b)
    assert CanaryProber._canonical({"error": "boom"}) is None


def test_resolve_canary_sec(monkeypatch):
    monkeypatch.delenv("BIGDL_TPU_CANARY_SEC", raising=False)
    assert resolve_canary_sec() == 0.0
    monkeypatch.setenv("BIGDL_TPU_CANARY_SEC", "2.5")
    assert resolve_canary_sec() == 2.5
    with pytest.raises(ValueError):
        resolve_canary_sec("-1")
    with pytest.raises(ValueError):
        resolve_canary_sec("soon")


# ---------------------------------------------------------------------------
# logit_drift fault


def test_logit_drift_parse_and_validation():
    (c,) = parse_fault_spec("logit_drift@after_step=5,bias=8")
    assert c.kind == "logit_drift" and c.bias == 8.0
    for bad in ("logit_drift@bias=0", "logit_drift@bias=inf",
                "logit_drift@bias=nan"):
        with pytest.raises(ValueError):
            parse_fault_spec(bad)


def test_logit_drift_rows_are_sticky():
    inj = FaultInjector(parse_fault_spec("logit_drift@after_step=5,"
                                         "bias=8"))
    assert inj.drift_rows(1, [0, 1]) == ([], 0.0)   # not armed yet
    assert inj.drift_rows(6, [0, 1]) == ([0, 1], 8.0)
    # sticky: no re-fire needed, applies to whatever rows are active
    assert inj.drift_rows(7, [2]) == ([2], 8.0)
    assert inj.drift_rows(100, []) == ([], 0.0)     # idle step


# ---------------------------------------------------------------------------
# satellite gates


def test_stats_percentile_matches_numpy():
    # percentile() takes PRE-SORTED samples and q in [0, 1]; it must
    # match np.percentile's default "linear" method bit-for-bit
    data = sorted([3.1, 0.2, 44.0, 8.8, 8.8, 17.3, 0.9, 25.0])
    for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
        assert percentile(data, q) == float(np.percentile(data, q * 100))
    assert percentile([7.0], 0.5) == 7.0


# ---------------------------------------------------------------------------
# engine integration (real jax CPU decode)


@pytest.fixture(scope="module")
def served_engine():
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
    from bigdl_tpu.utils.testing import tiny_random_model

    eng = LLMEngine(tiny_random_model(seed=0),
                    EngineConfig(max_batch=2, max_seq=96))
    reqs = [("a-1", "acme", "interactive", 6),
            ("a-2", "acme", "standard", 4),
            ("b-1", "bob", "batch", 5)]
    for rid, tenant, qos, toks in reqs:
        eng.add_request(rid, [1, 2, 3, 4],
                        SamplingParams(max_tokens=toks, qos=qos,
                                       tenant=tenant))
    while eng.has_unfinished():
        eng.step()
    return eng, reqs


def test_engine_usage_reconciles_with_tenant_counters(served_engine):
    eng, reqs = served_engine
    tot = eng.usage.totals()
    assert tot["acme"]["requests"] == 2
    assert tot["bob"]["requests"] == 1
    assert tot["acme"]["generated_tokens"] == 10
    assert tot["bob"]["generated_tokens"] == 5
    # EXACT reconciliation with the PR-7 admission counters
    admitted = {}
    for (tenant, outcome), child in eng._m_tenant_reqs._children.items():
        if outcome == "admitted":
            admitted[tenant] = admitted.get(tenant, 0) + child.value
    for tenant in ("acme", "bob"):
        assert admitted[tenant] == tot[tenant]["requests"]
    # ...and with the overload controller's per-tenant ledger
    ov = eng.stats_snapshot()["overload"]["tenants"]
    for tenant in ("acme", "bob"):
        assert ov[tenant]["admitted_total"] == tot[tenant]["requests"]
        assert (ov[tenant]["generated_total"]
                == tot[tenant]["generated_tokens"])


def test_engine_slo_sees_real_latency(served_engine):
    eng, _ = served_engine
    eng.slo.evaluate()
    snap = eng.slo.snapshot()
    # one TTFT sample per request, one TPOT sample per decode step
    assert snap["qos"]["interactive"]["ttft_count"] == 1
    assert snap["qos"]["batch"]["tpot_count"] >= 4
    assert snap["alerts_active"] == 0      # min_events guards CPU jitter
    stats = eng.stats_snapshot()
    assert stats["slo"]["spec"]["min_events"] == 12
    assert stats["usage"]["records_total"] == 3


def test_engine_fast_burn_alert_and_recovery_e2e():
    """Overload e2e on a live engine: a swapped-in tracker with a
    sub-millisecond TPOT target makes every REAL decode step a
    violation — the page fires from genuine engine feeds, then
    hysteresis recovers once the bad samples age out."""
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
    from bigdl_tpu.utils.testing import tiny_random_model

    eng = LLMEngine(tiny_random_model(seed=0),
                    EngineConfig(max_batch=2, max_seq=96))
    clock = _Clock()
    spec = resolve_slo_spec(json.dumps({
        "interactive": {"tpot_p99_ms": 0.0001},
        "windows": {"fast_sec": 60, "slow_sec": 120},
        "eval_sec": 0.01, "min_events": 4, "recover_evals": 2}))
    eng.slo = SLOTracker(spec=spec, flight=eng.flight, time_fn=clock)
    eng.add_request("hot", [1, 2, 3], SamplingParams(
        max_tokens=8, qos="interactive"))
    while eng.has_unfinished():
        eng.step()
        clock.t += 0.02      # outrun the eval_sec throttle
    assert eng.slo.alerts_active() == 1
    kinds = [e["event"] for e in eng.flight.snapshot()
             if e["event"].startswith("slo_")]
    assert kinds == ["slo_burn"]
    # recovery: idle steps keep evaluating after the window drains
    clock.t += 130.0
    for _ in range(4):
        eng.step()
        clock.t += 0.02
    assert eng.slo.alerts_active() == 0
    kinds = [e["event"] for e in eng.flight.snapshot()
             if e["event"].startswith("slo_")]
    assert kinds == ["slo_burn", "slo_recover"]
