"""Chaos tests for overload control (bigdl_tpu/serving/overload.py):
QoS priority scheduling with aging, per-tenant token buckets + DRR
fairness, bounded queues with early load shedding (429/503 +
Retry-After), the brownout degradation ladder driven by the
``overload_storm`` fault, and byte-identical greedy outputs for every
admitted request under shedding-only load."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from bigdl_tpu.models import llama as llama_mod
from bigdl_tpu.robustness.faults import FaultInjector, parse_fault_spec
from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from bigdl_tpu.serving.overload import (BROWNOUT_ENGAGE_STEPS,
                                        BROWNOUT_RECOVER_STEPS,
                                        QOS_CLASSES, OverloadConfig,
                                        OverloadController, RequestShed,
                                        TokenBucket,
                                        resolve_brownout_high,
                                        resolve_brownout_low,
                                        resolve_max_queue_bytes,
                                        resolve_max_queue_depth,
                                        resolve_qos_aging_sec,
                                        resolve_qos_default,
                                        resolve_tenant_burst,
                                        resolve_tenant_rps,
                                        resolve_tenant_tps)
from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params


# -- env resolvers (no model) -----------------------------------------------


def test_resolver_defaults(monkeypatch):
    for var in ("QOS_DEFAULT", "QOS_AGING_SEC", "TENANT_RPS",
                "TENANT_TPS", "TENANT_BURST", "BROWNOUT_HIGH",
                "BROWNOUT_LOW", "MAX_QUEUE_DEPTH", "MAX_QUEUE_BYTES"):
        monkeypatch.delenv(f"BIGDL_TPU_{var}", raising=False)
    assert resolve_qos_default() == "standard"
    assert resolve_qos_aging_sec() == 5.0
    assert resolve_tenant_rps() == 0.0          # 0 = unlimited
    assert resolve_tenant_tps() == 0.0
    assert resolve_tenant_burst() == 4.0
    assert resolve_brownout_high() == 0.85
    assert resolve_brownout_low() == 0.6
    assert resolve_max_queue_depth() == 256
    assert resolve_max_queue_bytes() == 64 << 20


def test_resolver_ranges():
    assert resolve_qos_default("batch") == "batch"
    assert resolve_qos_aging_sec("2.5") == 2.5
    assert resolve_tenant_rps("10") == 10.0
    assert resolve_max_queue_depth("8") == 8
    with pytest.raises(ValueError, match="must be one of"):
        resolve_qos_default("gold")
    with pytest.raises(ValueError):
        resolve_qos_aging_sec("0")
    with pytest.raises(ValueError):
        resolve_tenant_rps("-1")
    with pytest.raises(ValueError):
        resolve_tenant_burst("0.5")             # needs >= 1
    with pytest.raises(ValueError):
        resolve_brownout_high("1.5")
    with pytest.raises(ValueError):
        resolve_brownout_low("1.0")             # [0, 1)
    with pytest.raises(ValueError):
        resolve_max_queue_depth("0")
    with pytest.raises(ValueError):
        resolve_max_queue_bytes("nope")


def test_env_check_flags_bad_overload_knobs(monkeypatch):
    from bigdl_tpu.utils.env_check import collect

    monkeypatch.setenv("BIGDL_TPU_QOS_DEFAULT", "gold")
    monkeypatch.setenv("BIGDL_TPU_TENANT_RPS", "-2")
    monkeypatch.setenv("BIGDL_TPU_BROWNOUT_HIGH", "1.5")
    info = collect()
    assert info["qos_default"]["valid"] is False
    assert info["tenant_rps"]["valid"] is False
    assert info["brownout_high"]["valid"] is False
    monkeypatch.setenv("BIGDL_TPU_QOS_DEFAULT", "interactive")
    monkeypatch.setenv("BIGDL_TPU_TENANT_RPS", "25")
    monkeypatch.setenv("BIGDL_TPU_BROWNOUT_HIGH", "0.9")
    info = collect()
    assert info["qos_default"]["valid"] is True
    assert info["qos_default"]["value"] == "interactive"
    assert info["tenant_rps"]["value"] == 25.0
    assert info["brownout_high"]["value"] == 0.9


# -- token bucket -----------------------------------------------------------


def test_token_bucket_refill_and_cap():
    b = TokenBucket(rate=2.0, capacity=4.0)
    assert b.level == 4.0
    assert b.try_take(3, now=0.0)
    assert not b.try_take(2, now=0.0)            # only 1 left
    assert b.try_take(2, now=0.5)                # +1 refilled -> 2
    assert b.try_take(4, now=100.0)              # refill capped at 4
    assert not b.try_take(1, now=100.0)
    # rate=0 disables: always admits, never waits
    off = TokenBucket(rate=0.0, capacity=0.0)
    assert off.try_take(1000, now=0.0)
    assert off.wait_sec(1000, now=0.0) == 0.0


def test_token_bucket_postpaid_debt_and_wait():
    b = TokenBucket(rate=10.0, capacity=10.0)
    b.charge(35, now=0.0)                        # post-paid: -> -25
    assert b.level == -25.0
    assert not b.try_take(1, now=0.0)
    assert b.wait_sec(0.0, now=0.0) == pytest.approx(2.5)
    b.charge(0, now=2.5)                         # refill only
    assert b.level == pytest.approx(0.0)


# -- controller: priorities, fairness, brownout (no model) ------------------


class _FakeReq:
    def __init__(self, qos, tenant, arrival):
        self.params = SamplingParams(qos=qos, tenant=tenant)
        self.arrival = arrival


def _ctl(**kw):
    base = dict(qos_default="standard", qos_aging_sec=5.0,
                tenant_rps=0.0, tenant_tps=0.0, tenant_burst=4.0,
                brownout_high=0.85, brownout_low=0.6,
                max_queue_depth=8, max_queue_bytes=64 << 20)
    base.update(kw)
    return OverloadController(OverloadConfig(**base))


def test_controller_rejects_inverted_hysteresis():
    with pytest.raises(ValueError, match="brownout_low"):
        _ctl(brownout_low=0.9, brownout_high=0.8)


def test_select_index_priority_then_aging_then_fairness():
    c = _ctl(qos_aging_sec=5.0)
    now = 100.0
    # strict priority: interactive beats older batch/standard
    waiting = [_FakeReq("batch", "a", now - 3),
               _FakeReq("standard", "a", now - 2),
               _FakeReq("interactive", "a", now - 1)]
    assert c.select_index(waiting, now) == 2
    # aging: a batch request waiting 2 aging periods is promoted to
    # interactive priority and wins on queue order (it queued first);
    # without promotion the younger interactive request would win
    waiting = [_FakeReq("batch", "a", now - 11),
               _FakeReq("interactive", "a", now - 1)]
    assert c.select_index(waiting, now) == 0
    waiting = [_FakeReq("batch", "a", now - 4),   # not yet promoted
               _FakeReq("interactive", "a", now - 1)]
    assert c.select_index(waiting, now) == 1
    # DRR fairness: same class, the least-served tenant wins even when
    # the hot tenant's request arrived first
    c2 = _ctl()
    for _ in range(5):
        c2.note_scheduled("hot")
    waiting = [_FakeReq("standard", "hot", now - 2),
               _FakeReq("standard", "cold", now - 1)]
    assert c2.select_index(waiting, now) == 1


def test_depth_limits_per_class():
    c = _ctl(max_queue_depth=8)
    assert c.depth_limit("interactive") == 8     # the hard cap itself
    assert c.depth_limit("standard") == 6
    assert c.depth_limit("batch") == 4
    with pytest.raises(RequestShed) as ei:
        c.check_admission(qos="batch", tenant="t", n_seqs=1,
                          prompt_len=4, queue_depth=4, queue_bytes=0,
                          deadline_sec=None, tpot_sec=0.0,
                          retry_after_sec=7, now=0.0)
    e = ei.value
    assert e.reason == "queue_full" and e.http_status == 503
    assert e.retry_after_sec == 7 and e.qos == "batch"
    # interactive still admits at the same depth
    c.check_admission(qos="interactive", tenant="t", n_seqs=1,
                      prompt_len=4, queue_depth=4, queue_bytes=0,
                      deadline_sec=None, tpot_sec=0.0,
                      retry_after_sec=7, now=0.0)


def test_admission_sheds_bytes_rate_and_doomed():
    c = _ctl(tenant_rps=1.0, tenant_burst=1.0, max_queue_bytes=64)

    def admit(**kw):
        base = dict(qos="standard", tenant="t", n_seqs=1, prompt_len=4,
                    queue_depth=0, queue_bytes=0, deadline_sec=None,
                    tpot_sec=0.0, retry_after_sec=3, now=0.0)
        base.update(kw)
        c.check_admission(**base)

    with pytest.raises(RequestShed) as ei:
        admit(prompt_len=32)                     # 128B > 64B cap
    assert ei.value.reason == "queue_bytes"
    admit(now=0.0)                               # burns the rps bucket
    with pytest.raises(RequestShed) as ei:
        admit(now=0.1)
    assert ei.value.reason == "rate_limit"
    assert ei.value.http_status == 429 and ei.value.retry_after_sec >= 1
    with pytest.raises(RequestShed) as ei:
        admit(now=10.0, deadline_sec=0.5, tpot_sec=0.2, queue_depth=5)
    assert ei.value.reason == "doomed"           # 1.0s wait > 0.5s left
    snap = c.snapshot()
    assert snap["shed"] == {"queue_bytes": 1, "rate_limit": 1,
                            "doomed": 1}
    assert snap["tenants"]["t"]["shed_total"] == 3


def test_token_rate_postpaid_shed():
    c = _ctl(tenant_tps=10.0, tenant_burst=1.0)
    c.note_generated("t", 40, now=0.0)           # debt: 10 - 40 = -30
    with pytest.raises(RequestShed) as ei:
        c.check_admission(qos="standard", tenant="t", n_seqs=1,
                          prompt_len=4, queue_depth=0, queue_bytes=0,
                          deadline_sec=None, tpot_sec=0.0,
                          retry_after_sec=3, now=0.0)
    e = ei.value
    assert e.reason == "token_rate" and e.http_status == 429
    assert e.retry_after_sec == 3                # ceil(30 / 10)
    # debt drains: admitted again once the bucket is non-negative
    c.check_admission(qos="standard", tenant="t", n_seqs=1,
                      prompt_len=4, queue_depth=0, queue_bytes=0,
                      deadline_sec=None, tpot_sec=0.0,
                      retry_after_sec=3, now=4.0)


def test_brownout_ladder_hysteresis():
    c = _ctl()
    # dwell: high pressure must persist ENGAGE_STEPS samples
    for _ in range(BROWNOUT_ENGAGE_STEPS - 1):
        assert c.update_pressure(1.0) is None
    assert c.update_pressure(1.0) == 1
    assert not c.speculative_allowed
    assert c.max_tokens_cap() == 256
    # mid-band pressure resets both streaks (no flapping)
    for _ in range(BROWNOUT_RECOVER_STEPS * 2):
        assert c.update_pressure(0.7) is None
    assert c.level == 1
    # climb to the top, then batch QoS is shed outright
    for _ in range(BROWNOUT_ENGAGE_STEPS * 2):
        c.update_pressure(1.0)
    assert c.level == 3 and c.max_tokens_cap() == 16
    assert c.chunk_shift() == 2
    with pytest.raises(RequestShed) as ei:
        c.check_admission(qos="batch", tenant="t", n_seqs=1,
                          prompt_len=4, queue_depth=0, queue_bytes=0,
                          deadline_sec=None, tpot_sec=0.0,
                          retry_after_sec=5, now=0.0)
    assert ei.value.reason == "brownout"
    # recovery: RECOVER_STEPS low samples per level, back to healthy
    for lvl in (2, 1, 0):
        for _ in range(BROWNOUT_RECOVER_STEPS - 1):
            assert c.update_pressure(0.0) is None
        assert c.update_pressure(0.0) == lvl
    assert c.speculative_allowed and c.max_tokens_cap() is None


def test_parse_overload_storm_spec():
    c = parse_fault_spec("overload_storm@after_step=2,times=6,"
                         "pressure=0.9")[0]
    assert c.kind == "overload_storm" and c.pressure == 0.9
    with pytest.raises(ValueError, match="not in \\[0, 1\\]"):
        parse_fault_spec("overload_storm@at_step=1,pressure=1.5")
    # storm_pressure: max of the firing clauses, None outside
    inj = FaultInjector(parse_fault_spec(
        "overload_storm@at_step=3,pressure=0.4;"
        "overload_storm@at_step=3,pressure=0.8"))
    assert inj.storm_pressure(2) is None
    assert inj.storm_pressure(3) == 0.8
    assert inj.storm_pressure(4) is None         # pins are one-shot


# -- engine chaos -----------------------------------------------------------


class FakeModel:
    def __init__(self, params, cfg):
        self.params = params
        self.config = cfg
        self.hf_config = {"eos_token_id": None}

        class Fam:
            forward = staticmethod(llama_mod.forward)
            prefill = staticmethod(llama_mod.forward_last_token)
            new_cache = staticmethod(llama_mod.new_cache)

        self.family = Fam()


@pytest.fixture(scope="module")
def model():
    return FakeModel(random_llama_params(TINY_LLAMA, qtype="sym_int4",
                                         seed=0), TINY_LLAMA)


def _drive(eng, rids, timeout_s=120):
    """Step until every rid finishes; returns ({rid: tokens},
    {rid: reason}, [rid order of first token])."""
    outs = {rid: [] for rid in rids}
    reasons, first_order = {}, []
    deadline = time.time() + timeout_s
    while len(reasons) < len(rids):
        assert time.time() < deadline, f"engine stuck: {reasons}"
        if not eng.step():
            time.sleep(0.001)
        for rid in rids:
            if rid in reasons:
                continue
            for o in eng.get_outputs(rid):
                if o.new_token_ids and rid not in first_order:
                    first_order.append(rid)
                outs[rid].extend(o.new_token_ids)
                if o.finished:
                    reasons[rid] = o.finish_reason
    return outs, reasons, first_order


def run_to_completion(eng, reqs, params=None, timeout_s=120):
    for rid, prompt in reqs.items():
        eng.add_request(rid, prompt, params)
    return _drive(eng, list(reqs), timeout_s)


def test_no_shed_below_caps(model):
    """Acceptance (1): below the configured caps nothing is shed and
    the brownout ladder never engages."""
    eng = LLMEngine(model, EngineConfig(max_batch=4, max_seq=128,
                                        max_queue_depth=16))
    prompts = {f"r{i}": [i + 1, i + 2, i + 3] for i in range(8)}
    _, reasons, _ = run_to_completion(eng, prompts,
                                      SamplingParams(max_tokens=6))
    assert all(r == "length" for r in reasons.values())
    assert sum(eng.overload.shed_counts.values()) == 0
    assert eng.overload.level == 0
    s = eng.registry.summary()
    assert all(v == 0 for k, v in s.items()
               if k.startswith("bigdl_tpu_requests_shed_total"))
    assert s.get("bigdl_tpu_brownout_level", 0) == 0
    ov = eng.stats_snapshot()["overload"]
    assert ov["brownout_level"] == 0 and ov["shed"] == {}
    assert ov["tenants"]["default"]["admitted_total"] == 8


def test_queue_full_sheds_batch_first_keeps_interactive(model):
    """Acceptance (2): past the per-class depth caps the engine sheds
    early with 503 + Retry-After; batch hits its (smaller) cap while
    interactive still admits at the same depth."""
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128,
                                        max_queue_depth=4))
    # hold the single slot so everything else queues
    eng.add_request("hold", [1, 2, 3],
                    SamplingParams(max_tokens=40, qos="interactive"))
    eng.step()                                   # hold takes the slot
    admitted = ["hold"]
    # batch limit is 4 * 0.5 = 2 queued requests
    for i in range(2):
        eng.add_request(f"b{i}", [5 + i, 6 + i],
                        SamplingParams(max_tokens=2, qos="batch"))
        admitted.append(f"b{i}")
    with pytest.raises(RequestShed) as ei:
        eng.add_request("b2", [9, 10],
                        SamplingParams(max_tokens=2, qos="batch"))
    e = ei.value
    assert e.reason == "queue_full" and e.http_status == 503
    assert e.retry_after_sec >= 1
    # the same depth still admits interactive (its limit IS the cap)
    eng.add_request("i0", [11, 12],
                    SamplingParams(max_tokens=2, qos="interactive"))
    admitted.append("i0")
    _, reasons, first_order = _drive(eng, admitted)
    assert set(reasons) == set(admitted)
    # priority scheduling: the interactive request reaches its first
    # token before every earlier-arrived batch request (bounded TTFT)
    assert first_order.index("i0") < first_order.index("b0")
    assert first_order.index("i0") < first_order.index("b1")
    s = eng.registry.summary()
    assert s.get('bigdl_tpu_requests_shed_total'
                 '{reason="queue_full",qos="batch"}', 0) == 1
    shed = next(ev for ev in eng.flight.snapshot()
                if ev["event"] == "shed")
    assert shed["request_id"] == "b2" and shed["reason"] == "queue_full"
    assert shed["qos"] == "batch" and shed["retry_after_sec"] >= 1


def test_tenant_rate_limit_isolates_tenants(model):
    """Acceptance (3): a hot tenant hitting its request-rate bucket is
    shed with 429 while a cold tenant's traffic is untouched."""
    eng = LLMEngine(model, EngineConfig(
        max_batch=2, max_seq=128,
        overload=OverloadConfig(tenant_rps=0.5, tenant_burst=1.0)))
    p = SamplingParams(max_tokens=2, tenant="hot")
    eng.add_request("h0", [1, 2, 3], p)
    with pytest.raises(RequestShed) as ei:
        eng.add_request("h1", [4, 5, 6], p)
    e = ei.value
    assert e.reason == "rate_limit" and e.http_status == 429
    assert e.tenant == "hot" and e.retry_after_sec >= 1
    # cold tenant admits straight through
    eng.add_request("c0", [7, 8, 9],
                    SamplingParams(max_tokens=2, tenant="cold"))
    _, reasons, _ = _drive(eng, ["h0", "c0"])
    assert reasons == {"h0": "length", "c0": "length"}
    s = eng.registry.summary()
    assert s.get('bigdl_tpu_requests_shed_total'
                 '{reason="rate_limit",qos="standard"}', 0) == 1
    assert s.get('bigdl_tpu_tenant_requests_total'
                 '{tenant="hot",outcome="shed"}', 0) == 1
    assert s.get('bigdl_tpu_tenant_requests_total'
                 '{tenant="cold",outcome="admitted"}', 0) == 1
    ten = eng.stats_snapshot()["overload"]["tenants"]
    assert ten["hot"]["shed_total"] == 1
    assert ten["cold"]["shed_total"] == 0


def test_token_rate_limit_postpaid(model):
    """Generated tokens are charged post-paid: a tenant that burned its
    token budget is shed on its NEXT request."""
    eng = LLMEngine(model, EngineConfig(
        max_batch=1, max_seq=128,
        overload=OverloadConfig(tenant_tps=1.0, tenant_burst=1.0)))
    p = SamplingParams(max_tokens=8, tenant="t")
    _, reasons, _ = run_to_completion(eng, {"r0": [1, 2, 3]}, p)
    assert reasons["r0"] == "length"
    with pytest.raises(RequestShed) as ei:
        eng.add_request("r1", [4, 5, 6], p)
    assert ei.value.reason == "token_rate"
    assert ei.value.http_status == 429
    assert ei.value.retry_after_sec >= 1


def test_brownout_latency_signal_skips_steps_behind_a_chunk(model):
    """The latency-inflation signal is EWMA over floor of the decode
    steps that measure the decode alone. A decode dispatched behind an
    admission chunk still in flight is a chunk plus a decode (4.4 decodes
    at 7B on the chip: a long prompt alone would read as overload) and is
    no sample; the queue-wait EWMA takes every step."""
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128,
                                        prefill_bucket=8, prefill_chunk=8))
    eng.add_request("a", [1, 2, 3], SamplingParams(max_tokens=24))
    while not any(s.active for s in eng.slots):
        eng.step()
    eng.add_request("b", list(range(1, 41)), SamplingParams(max_tokens=2))
    behind = clean = 0
    for _ in range(12):
        before = (eng._decode_ewma, eng._decode_floor, eng._tpot_ewma)
        assert eng.step()
        after = (eng._decode_ewma, eng._decode_floor, eng._tpot_ewma)
        assert after[2] != before[2]          # every step decoded
        if eng._admitting is not None:        # "b" is mid-prompt
            behind += 1
            assert after[:2] == before[:2]
        else:
            clean += 1
            assert after[0] != before[0]
    assert behind >= 3 and clean >= 3
    assert 0 < eng._decode_floor <= eng._decode_ewma
    # steps behind a chunk ten times the floor move the queue-wait
    # estimate and not the signal; the decode itself at 3x its floor
    # saturates it
    eng._tpot_ewma = 0.40
    eng._decode_ewma, eng._decode_floor = 0.041, 0.04
    assert eng._overload_pressure() < 0.05
    eng._decode_ewma = 0.08
    assert eng._overload_pressure() == pytest.approx(0.5)
    eng._decode_ewma = 0.12
    assert eng._overload_pressure() == 1.0


def test_brownout_latency_signal_survives_one_slow_step(model, monkeypatch):
    """One decode step hundreds of floors long (an executable loaded
    from the cache in set-up, a compile) counts for 3 floors, the ratio
    at which the signal saturates: it moves the pressure by a fifth and
    not over a threshold; sustained slowness still fills the signal."""
    import bigdl_tpu.serving.engine as E

    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128))
    eng.add_request("a", [1, 2, 3], SamplingParams(max_tokens=40))
    for _ in range(12):
        eng.step()
    floor = eng._decode_floor
    assert floor and eng._overload_pressure() < 0.5
    real = E.time.perf_counter
    late = [0.0]
    monkeypatch.setattr(E.time, "perf_counter", lambda: real() + late[0])

    def slow_step(extra):
        orig = eng._decode_resident

        def held(*a, **k):              # the clock jumps inside the step
            late[0] += extra
            return orig(*a, **k)
        eng._decode_resident = held
        try:
            assert eng.step()
        finally:
            eng._decode_resident = orig

    before = eng._decode_ewma
    slow_step(500 * floor)
    assert eng._tpot_ewma > 50 * floor        # the estimate saw it whole
    assert eng._decode_ewma <= 0.8 * before + 0.2 * 3.0 * floor + 1e-9
    assert eng._overload_pressure() < 0.5
    for _ in range(20):
        slow_step(5 * floor)
    assert eng._overload_pressure() > 0.97


def test_doomed_queue_wait_shed(model):
    """A request whose deadline cannot outlast the measured backlog is
    rejected at admission instead of timing out in the queue."""
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128,
                                        max_queue_depth=16))
    # establish the decode-latency EWMA with a real run
    _, reasons, _ = run_to_completion(eng, {"w": [1, 2, 3]},
                                      SamplingParams(max_tokens=4))
    assert reasons["w"] == "length"
    assert eng.stats_snapshot()["overload"]["tpot_ewma_ms"] > 0
    # build a backlog, then offer a request with a 1 ms deadline
    rids = []
    for i in range(4):
        eng.add_request(f"q{i}", [10 + i, 11 + i],
                        SamplingParams(max_tokens=2))
        rids.append(f"q{i}")
    with pytest.raises(RequestShed) as ei:
        eng.add_request("late", [20, 21],
                        SamplingParams(max_tokens=2, max_time_ms=1))
    assert ei.value.reason == "doomed" and ei.value.http_status == 503
    _, reasons, _ = _drive(eng, rids)
    assert all(r == "length" for r in reasons.values())


def test_overload_storm_brownout_engages_and_recovers(model):
    """Acceptance (4): a deterministic overload_storm drives the
    brownout ladder up (with dwell) and pressure receding walks it back
    down — observable in flight events and the level gauge."""
    eng = LLMEngine(
        model, EngineConfig(max_batch=1, max_seq=128),
        faults=FaultInjector(parse_fault_spec(
            "overload_storm@after_step=2,times=6,pressure=1.0")))
    _, reasons, _ = run_to_completion(eng, {"r0": [1, 2, 3]},
                                      SamplingParams(max_tokens=48))
    assert reasons["r0"] == "length"
    s = eng.registry.summary()
    assert s.get('bigdl_tpu_faults_injected_total'
                 '{kind="overload_storm"}', 0) == 6
    levels = [ev["level"] for ev in eng.flight.snapshot()
              if ev["event"] == "brownout"]
    # 6 high samples = two engage dwells -> level 2, then recovery
    assert levels[:2] == [1, 2]
    assert max(levels) == 2
    assert levels[-1] < 2                        # recovery began
    assert eng.overload.level == 0               # fully recovered
    assert s.get("bigdl_tpu_brownout_level", -1) == 0
    ev1 = next(ev for ev in eng.flight.snapshot()
               if ev["event"] == "brownout" and ev["level"] == 1)
    assert ev1["speculative_allowed"] is False


def test_brownout_level3_caps_tokens_and_sheds_batch(model):
    """At the top of the ladder: batch QoS is shed outright and
    admitted work gets its max_tokens clamped."""
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128))
    eng.overload.level = 3
    assert not eng.overload.speculative_allowed
    assert eng.overload.chunk_shift() == 2
    with pytest.raises(RequestShed) as ei:
        eng.add_request("b", [1, 2], SamplingParams(max_tokens=4,
                                                    qos="batch"))
    assert ei.value.reason == "brownout" and ei.value.http_status == 503
    # a standard request is admitted but clamped to 16 tokens
    eng.add_request("s", [1, 2, 3], SamplingParams(max_tokens=64))
    outs, reasons, _ = _drive(eng, ["s"])
    assert reasons["s"] == "length" and len(outs["s"]) == 16


def test_byte_identical_outputs_for_admitted_requests(model):
    """Acceptance (5): under shedding-only overload (no brownout),
    every ADMITTED request's greedy output is byte-identical to an
    unloaded run of the same prompts."""
    prompts = {f"r{i}": [7 * i + 1, 7 * i + 2, 7 * i + 3]
               for i in range(6)}
    params = SamplingParams(max_tokens=10)
    clean = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    want, _, _ = run_to_completion(clean, prompts, params)

    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128,
                                        max_queue_depth=4))
    admitted, shed = [], []
    for rid, prompt in prompts.items():          # standard cap: 3 queued
        try:
            eng.add_request(rid, prompt, params)
            admitted.append(rid)
        except RequestShed as e:
            assert e.reason == "queue_full"
            shed.append(rid)
    assert admitted and shed                     # overload really bit
    assert eng.overload.level == 0               # shedding-only
    outs, reasons, _ = _drive(eng, admitted)
    for rid in admitted:
        assert outs[rid] == want[rid], rid
        assert reasons[rid] == "length"


def test_queued_abort_is_swept_without_a_slot(model):
    """Aborting a request that never reached a slot frees its queue
    entry and delivers the abort finish."""
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128,
                                        max_queue_depth=8))
    eng.add_request("hold", [1, 2, 3], SamplingParams(max_tokens=30))
    eng.step()                                   # hold takes the slot
    eng.add_request("q0", [4, 5], SamplingParams(max_tokens=2))
    eng.add_request("q1", [6, 7], SamplingParams(max_tokens=2))
    eng.abort_request("q0")
    _, reasons, _ = _drive(eng, ["hold", "q0", "q1"])
    assert reasons["q0"] == "abort"
    assert reasons["hold"] == "length" and reasons["q1"] == "length"


def test_hard_queue_bound_with_defaults(model):
    """EngineConfig.max_queue_depth alone bounds the queue with a 503
    even when every other overload knob is at its default."""
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128,
                                        max_queue_depth=2))
    eng.add_request("r0", [1, 2], SamplingParams(max_tokens=2))
    with pytest.raises(RequestShed) as ei:       # standard cap: 1 queued
        for i in range(1, 4):
            eng.add_request(f"r{i}", [1, 2],
                            SamplingParams(max_tokens=2))
    assert ei.value.http_status == 503
    assert ei.value.reason == "queue_full"


# -- HTTP API semantics -----------------------------------------------------


def _post(base, path, payload, headers=(), timeout=120):
    req = urllib.request.Request(
        f"{base}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **dict(headers)})
    return urllib.request.urlopen(req, timeout=timeout)


def test_api_tenant_429_with_retry_after(model):
    """Per-tenant rate limits over HTTP: 429 + Retry-After + a machine-
    readable body, keyed on X-Tenant-Id; other tenants unaffected."""
    from bigdl_tpu.serving.api_server import OpenAIServer

    eng = LLMEngine(model, EngineConfig(
        max_batch=2, max_seq=128,
        overload=OverloadConfig(tenant_rps=0.01, tenant_burst=1.0)))
    server = OpenAIServer(eng)
    httpd = server.serve(port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        with _post(base, "/v1/completions",
                   {"prompt": [1, 2, 3], "max_tokens": 2},
                   headers={"X-Tenant-Id": "alpha"}) as r:
            assert r.status == 200
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, "/v1/completions",
                  {"prompt": [4, 5, 6], "max_tokens": 2},
                  headers={"X-Tenant-Id": "alpha"})
        assert ei.value.code == 429
        assert int(ei.value.headers["Retry-After"]) >= 1
        body = json.loads(ei.value.read())["error"]
        assert body["reason"] == "rate_limit"
        assert body["type"] == "rate_limited"
        assert body["tenant"] == "alpha"
        assert body["retry_after"] >= 1
        # a different tenant's bucket is untouched
        with _post(base, "/v1/completions",
                   {"prompt": [7, 8, 9], "max_tokens": 2},
                   headers={"X-Tenant-Id": "beta"}) as r:
            assert r.status == 200
        # unknown qos is a 400, not a shed
        with pytest.raises(urllib.error.HTTPError) as qi:
            _post(base, "/v1/completions",
                  {"prompt": [1], "max_tokens": 2, "qos": "gold"})
        assert qi.value.code == 400
    finally:
        server.shutdown()


def test_api_queue_full_503_under_storm(model):
    """A burst past the queue cap sheds with 503 + Retry-After before
    the server commits stream headers; admitted requests complete."""
    from bigdl_tpu.serving.api_server import OpenAIServer

    eng = LLMEngine(
        model, EngineConfig(max_batch=1, max_seq=128,
                            max_queue_depth=2),
        faults=FaultInjector(parse_fault_spec(
            "slow_step@ms=60,every=1,times=0")))
    server = OpenAIServer(eng)
    httpd = server.serve(port=0, background=True)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    results = []
    lock = threading.Lock()

    def fire(i):
        try:
            with _post(base, "/v1/completions",
                       {"prompt": [i + 1, i + 2],
                        "max_tokens": 8}) as r:
                r.read()
                code, retry = r.status, None
        except urllib.error.HTTPError as e:
            code = e.code
            retry = e.headers.get("Retry-After")
            body = json.loads(e.read())
            assert body["error"]["reason"] == "queue_full"
        with lock:
            results.append((code, retry))

    try:
        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        codes = [c for c, _ in results]
        assert set(codes) <= {200, 503}
        assert codes.count(200) >= 1
        assert codes.count(503) >= 1             # the cap really bit
        for code, retry in results:
            if code == 503:
                assert retry is not None and int(retry) >= 1
    finally:
        server.shutdown()


# -- router overload behavior (no subprocess replicas) ----------------------


def test_router_retry_after_header_rebuild():
    from bigdl_tpu.serving.router import _retry_after_headers

    data = json.dumps({"error": {"retry_after": 7}}).encode()
    assert _retry_after_headers(data) == (("Retry-After", "7"),)
    assert _retry_after_headers(b"not json") == ()
    assert _retry_after_headers(b"{}") == ()


def test_router_tenant_derivation_matches_api_server():
    from bigdl_tpu.serving.api_server import OpenAIServer
    from bigdl_tpu.serving.router import Router

    hdrs = {"X-Tenant-Id": "acme", "Authorization": "Bearer sk-xyz"}
    assert Router._tenant_of(hdrs) == "acme"
    key_only = {"Authorization": "Bearer sk-xyz"}
    derived = Router._tenant_of(key_only)
    assert derived.startswith("key-") and "sk-xyz" not in derived
    # the router forwards the SAME identity the api_server would derive
    assert derived == OpenAIServer._tenant_of(key_only)
    assert Router._tenant_of({}) is None


def test_router_pick_routes_around_brownout():
    from bigdl_tpu.serving.router import HEALTHY, Router, RouterConfig

    router = Router(spawn=lambda idx, port: None,
                    config=RouterConfig(replicas=2),
                    ports=[18401, 18402])
    for r in router.replicas:
        r.state = HEALTHY
        r.occupancy = 0.5
    # replica 0 is the affinity target for key 0; brown it out
    router.replicas[0].brownout = 2
    assert router._pick(0).idx == 1
    router.replicas[0].brownout = 0
    assert router._pick(0).idx == 0
    assert router.replicas[0].snapshot()["brownout"] == 0
    # fleet-wide tenant aggregation sums the probed replica blocks
    router.replicas[0].tenants = {"a": {"admitted_total": 3,
                                        "shed_total": 1}}
    router.replicas[1].tenants = {"a": {"admitted_total": 2},
                                  "b": {"admitted_total": 5}}
    agg = router._tenant_aggregate()
    assert agg["a"] == {"admitted_total": 5, "shed_total": 1}
    assert agg["b"] == {"admitted_total": 5}
