"""Live roofline attribution + perf-regression sentinel + profiler
hardening (observability/roofline.py, observability/sentinel.py,
utils/profiling.py, and their engine wiring).

Four invariants from the PR that introduced them:

1. **Formula identity** — ``roofline.efficiency`` reproduces the
   exact numbers of a fixed fixture, and the live-gauge formula
   (``decode_costs``) agrees with its ``decode_hbm_roofline_util``
   to 4 decimals for a bf16 cache at batch 1.
2. **Sentinel state machine** — trips after N consecutive
   past-threshold steps, recovers with hysteresis dwell, loads its
   baseline from (and appends to) the size-rotated perf-history JSONL,
   and degrades gracefully on corrupt history.
3. **Chaos trip** — a ``slow_step`` fault run through a real engine
   emits the ``perf_regression`` flight event, a postmortem, and a
   bounded profiler auto-capture, then recovers once the fault clears.
4. **Profiler hardening** — non-absolute paths rejected, capture dir
   created, the auto-stop watchdog fires, and a failing stop_trace
   still clears the capture state so the next start works.
"""

import dataclasses
import glob
import json
import os
import time

import pytest

from bigdl_tpu import config as config_mod
from bigdl_tpu.observability import roofline
from bigdl_tpu.observability.sentinel import (
    PerfSentinel,
    resolve_sentinel_recover_steps,
    resolve_sentinel_threshold,
    resolve_sentinel_trip_steps,
    validate_perf_history_path,
)


@pytest.fixture(autouse=True)
def _restore_flags():
    snap = dataclasses.replace(config_mod.flags())
    yield
    config_mod._flags = snap


@pytest.fixture(autouse=True)
def _v5e_peaks(monkeypatch):
    """These tests pin v5e numbers: give the CPU the tests run on the
    v5e's row of the peaks table (an unknown kind has no roofline —
    tests/test_chip_smoke.py covers that side)."""
    import jax

    monkeypatch.setitem(roofline.CHIP_PEAKS,
                        jax.devices()[0].device_kind,
                        roofline.CHIP_PEAKS["TPU v5 lite"])


# ---------------------------------------------------------------------------
# analytical model vs the fixture


class _Llama7B:
    """LLaMA-2-7B dims."""

    hidden_size = 4096
    intermediate_size = 11008
    vocab_size = 32000
    num_attention_heads = 32
    num_key_value_heads = 32
    hd = 128
    num_hidden_layers = 32


# fixture inputs: a llama2-7B sym_int4 weight-byte count and a pair of
# first/next token latencies; the expected outputs below pin the formulas
_FIX_WEIGHT_BYTES = 3979157504
_FIX_PROMPT, _FIX_STEPS = 1024, 64
_FIX_FIRST_MS, _FIX_NEXT_MS = 109.301, 28.607


def test_efficiency_reproduces_fixture():
    """The exact fixture numbers pin the formulas."""
    out = roofline.efficiency(_Llama7B, _FIX_WEIGHT_BYTES, _FIX_PROMPT,
                              _FIX_STEPS, _FIX_FIRST_MS, _FIX_NEXT_MS)
    assert out["decode_hbm_roofline_util"] == 0.1935
    assert out["decode_ideal_ms"] == 5.534561
    assert out["decode_mfu"] == 0.00244
    assert out["prefill_mfu"] == 0.6412
    assert out["weight_bytes"] == _FIX_WEIGHT_BYTES


def test_decode_costs_agree_with_bench_formula():
    """The live gauge path (`decode_costs`, kv-dtype aware) and the
    bench formula (`efficiency`, bf16 cache) compute the same ideal ms
    — and hence the same util to 4 decimals — for bf16 at batch 1."""
    s_mid = _FIX_PROMPT + _FIX_STEPS // 2
    costs = roofline.decode_costs(_Llama7B, _FIX_WEIGHT_BYTES, s_mid,
                                  kv_cache_dtype="bf16", batch=1)
    eff = roofline.efficiency(_Llama7B, _FIX_WEIGHT_BYTES, _FIX_PROMPT,
                              _FIX_STEPS, _FIX_FIRST_MS, _FIX_NEXT_MS)
    assert round(costs["ideal_ms"], 6) == eff["decode_ideal_ms"]
    assert (round(costs["ideal_ms"] / _FIX_NEXT_MS, 4)
            == eff["decode_hbm_roofline_util"])


@pytest.mark.parametrize("dtype,elt", [("bf16", 2.0), ("fp8_e5m2", 1.0),
                                       ("int8", 1.0), ("int4", 0.5)])
def test_kv_bytes_per_dtype(dtype, elt):
    cfg = _Llama7B
    seq = 512
    got = roofline.kv_bytes_per_token(cfg, seq, dtype)
    base = (2 * cfg.num_hidden_layers * seq * cfg.num_key_value_heads
            * cfg.hd * elt)
    if dtype in ("int8", "int4"):
        # fp32 per-(token, head) scale planes ride along
        base += 2 * cfg.num_hidden_layers * seq \
            * cfg.num_key_value_heads * 4.0
    assert got == base


def test_decode_costs_scale_with_batch_and_kv_dtype():
    cfg = _Llama7B
    w = _FIX_WEIGHT_BYTES
    bf16 = roofline.decode_costs(cfg, w, 512, "bf16", batch=1)
    fp8 = roofline.decode_costs(cfg, w, 512, "fp8_e5m2", batch=1)
    b4 = roofline.decode_costs(cfg, w, 512, "bf16", batch=4)
    # a smaller cache dtype moves fewer bytes -> lower ideal ms
    assert fp8["hbm_bytes"] < bf16["hbm_bytes"]
    assert fp8["ideal_ms"] < bf16["ideal_ms"]
    # weights are read ONCE per step regardless of batch; only the KV
    # term scales, so batch-4 moves less than 4x the bytes
    assert bf16["hbm_bytes"] < b4["hbm_bytes"] < 4 * bf16["hbm_bytes"]
    # flops scale linearly with batch (per-token matmuls)
    assert b4["flops"] == pytest.approx(4 * bf16["flops"])


def test_chip_peaks_come_from_the_table(monkeypatch):
    assert roofline.chip_peaks("TPU v5 lite") == (197.0, 819.0)
    monkeypatch.setitem(roofline.CHIP_PEAKS, "twice a v5e",
                        (394.0, 1640.0))
    half = roofline.decode_costs(_Llama7B, _FIX_WEIGHT_BYTES, 512,
                                 device_kind="twice a v5e")
    full = roofline.decode_costs(_Llama7B, _FIX_WEIGHT_BYTES, 512,
                                 device_kind="TPU v5 lite")
    assert half["ideal_ms"] == pytest.approx(
        full["ideal_ms"] * 819.0 / 1640.0)


def test_jit_costs_cover_tracked_jits():
    costs = roofline.jit_costs(_Llama7B, _FIX_WEIGHT_BYTES,
                               max_batch=4, max_seq=1024,
                               prefill_bucket=256)
    for name in ("engine_decode", "engine_decode_resident",
                 "engine_prefill"):
        assert costs[name]["flops"] > 0
        assert costs[name]["hbm_bytes"] > 0
    # the fused resident step moves at least what the bare decode does
    assert (costs["engine_decode_resident"]["hbm_bytes"]
            >= costs["engine_decode"]["hbm_bytes"])


# ---------------------------------------------------------------------------
# sentinel: resolvers + state machine + history


def test_sentinel_resolvers_validate(monkeypatch):
    assert resolve_sentinel_threshold(None) == 0.5
    assert resolve_sentinel_trip_steps(None) == 5
    assert resolve_sentinel_recover_steps(None) == 10
    monkeypatch.setenv("BIGDL_TPU_SENTINEL_THRESHOLD", "0.25")
    monkeypatch.setenv("BIGDL_TPU_SENTINEL_TRIP_STEPS", "3")
    monkeypatch.setenv("BIGDL_TPU_SENTINEL_RECOVER_STEPS", "4")
    assert resolve_sentinel_threshold(None) == 0.25
    assert resolve_sentinel_trip_steps(None) == 3
    assert resolve_sentinel_recover_steps(None) == 4
    with pytest.raises(ValueError):
        resolve_sentinel_threshold(-1)
    with pytest.raises(ValueError):
        resolve_sentinel_threshold("nope")
    with pytest.raises(ValueError):
        resolve_sentinel_trip_steps(0)
    with pytest.raises(ValueError):
        resolve_sentinel_recover_steps("x")


def test_perf_history_path_validation(tmp_path):
    ok = validate_perf_history_path(str(tmp_path / "perf.jsonl"))
    assert ok["writable"] is True
    bad = validate_perf_history_path(str(tmp_path / "no" / "perf.jsonl"))
    assert bad["writable"] is False and "error" in bad


def test_sentinel_trips_and_recovers_with_hysteresis():
    trips, recovers = [], []
    s = PerfSentinel(threshold=0.2, trip_steps=3, recover_steps=2,
                     warmup_steps=4, on_trip=trips.append,
                     on_recover=recovers.append)
    for _ in range(4):                      # healthy baseline ~10 ms
        assert s.observe(decode_ms=10.0) is None
    assert s.snapshot()["baseline"]["decode_ms"] == pytest.approx(10.0)
    # sustained 3x slowdown: EWMA crosses 12 ms, trips after 3
    # CONSECUTIVE bad steps (not on the first excursion)
    transitions = [s.observe(decode_ms=30.0) for _ in range(8)]
    assert "trip" in transitions
    assert s.tripped
    assert len(trips) == 1 and "decode_ms" in trips[0]["metrics"]
    # a single good step must NOT recover (hysteresis dwell)
    s.observe(decode_ms=10.0)
    assert s.tripped
    # sustained recovery: EWMA decays below threshold, then 2
    # consecutive good steps close the trip
    for _ in range(40):
        if s.observe(decode_ms=10.0) == "recover":
            break
    assert not s.tripped
    assert len(recovers) == 1
    snap = s.snapshot()
    assert snap["trips"] == 1 and snap["recoveries"] == 1


def test_sentinel_lower_is_bad_for_roofline_util():
    s = PerfSentinel(threshold=0.2, trip_steps=2, recover_steps=2,
                     warmup_steps=3)
    for _ in range(3):
        s.observe(roofline_util=0.5)
    out = [s.observe(roofline_util=0.05) for _ in range(8)]
    assert "trip" in out
    assert s.snapshot()["tripped_metrics"] == ["roofline_util"]


def test_sentinel_loads_baseline_from_history(tmp_path):
    hist = tmp_path / "perf.jsonl"
    rows = [{"ts": 1.0, "decode_ms": v} for v in (9.0, 10.0, 11.0)]
    hist.write_text("".join(json.dumps(r) + "\n" for r in rows))
    s = PerfSentinel(threshold=0.2, trip_steps=2, recover_steps=2,
                     history_path=str(hist))
    # baseline = median of the tail -> no warmup needed: a regression
    # present from the very first step still trips
    assert s.snapshot()["baseline"]["decode_ms"] == pytest.approx(10.0)
    out = [s.observe(decode_ms=50.0) for _ in range(6)]
    assert "trip" in out


def test_sentinel_corrupt_history_degrades(tmp_path):
    hist = tmp_path / "perf.jsonl"
    hist.write_text("not json\n{\"decode_ms\": \"nan?\"}\n{broken\n")
    s = PerfSentinel(history_path=str(hist), warmup_steps=2)
    assert s.snapshot()["baseline"] == {}
    s.observe(decode_ms=10.0)
    s.observe(decode_ms=10.0)               # live baseline after warmup
    assert s.snapshot()["baseline"]["decode_ms"] == pytest.approx(10.0)


def test_sentinel_appends_history_when_healthy(tmp_path):
    hist = tmp_path / "perf.jsonl"
    s = PerfSentinel(threshold=0.5, trip_steps=3, recover_steps=2,
                     warmup_steps=2, history_path=str(hist))
    for _ in range(70):                     # > _HISTORY_EVERY samples
        s.observe(decode_ms=10.0, dispatch_ms=1.0)
    assert hist.is_file()
    doc = json.loads(hist.read_text().splitlines()[0])
    assert doc["decode_ms"] == pytest.approx(10.0)
    assert doc["dispatch_ms"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# profiler hardening


@pytest.fixture
def fake_jax_profiler(monkeypatch):
    """jax.profiler stub: records calls, never spins a real capture."""
    calls = {"start": [], "stop": 0}
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls["start"].append(d))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.__setitem__(
                            "stop", calls["stop"] + 1))
    from bigdl_tpu.utils import profiling

    # a previous test (or a leaked capture) must not bleed in
    try:
        profiling.stop_profiler()
    except RuntimeError:
        pass
    yield calls
    try:
        profiling.stop_profiler()
    except RuntimeError:
        pass


def test_profiler_rejects_relative_path(fake_jax_profiler):
    from bigdl_tpu.utils.profiling import start_profiler

    with pytest.raises(ValueError):
        start_profiler("relative/dir")


def test_profiler_start_creates_dir_and_stop_reports(
        tmp_path, fake_jax_profiler):
    from bigdl_tpu.utils import profiling

    d = str(tmp_path / "cap")
    out = profiling.start_profiler(d, max_sec=30.0, capture_id="c-1")
    assert os.path.isdir(d)
    assert out["status"] == "started" and out["capture_id"] == "c-1"
    assert out["max_sec"] == 30.0
    st = profiling.profiler_status()
    assert st["capturing"] is True and st["log_dir"] == d
    assert st["deadline"] is not None and st["capture_id"] == "c-1"
    # double-start refused while a capture is live
    with pytest.raises(RuntimeError):
        profiling.start_profiler(str(tmp_path / "cap2"))
    stopped = profiling.stop_profiler()
    assert stopped["stopped_by"] == "manual"
    assert stopped["capture_id"] == "c-1"
    assert stopped["duration_s"] >= 0
    st = profiling.profiler_status()
    assert st["capturing"] is False
    assert st["last_capture"]["stopped_by"] == "manual"


def test_profiler_auto_stop_watchdog(tmp_path, fake_jax_profiler):
    from bigdl_tpu.utils import profiling

    d = str(tmp_path / "cap")
    profiling.start_profiler(d, max_sec=0.2)
    deadline = time.monotonic() + 5.0
    while (profiling.profiler_status()["capturing"]
           and time.monotonic() < deadline):
        time.sleep(0.05)
    st = profiling.profiler_status()
    assert st["capturing"] is False
    assert fake_jax_profiler["stop"] == 1
    assert st["last_capture"]["stopped_by"] == "auto_stop"


def test_profiler_stop_failure_clears_state(
        tmp_path, fake_jax_profiler, monkeypatch):
    from bigdl_tpu.utils import profiling
    import jax

    profiling.start_profiler(str(tmp_path / "cap"))

    def boom():
        raise RuntimeError("profiler backend died")

    monkeypatch.setattr(jax.profiler, "stop_trace", boom)
    with pytest.raises(RuntimeError):
        profiling.stop_profiler()
    # the capture slot is FREE again: a new start must work
    assert profiling.profiler_status()["capturing"] is False
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    profiling.start_profiler(str(tmp_path / "cap2"))
    profiling.stop_profiler()


def test_profiler_max_sec_resolver(monkeypatch):
    from bigdl_tpu.utils.profiling import resolve_profiler_max_sec

    assert resolve_profiler_max_sec(None) == 60.0
    monkeypatch.setenv("BIGDL_TPU_PROFILER_MAX_SEC", "5")
    assert resolve_profiler_max_sec(None) == 5.0
    with pytest.raises(ValueError):
        resolve_profiler_max_sec(0)
    monkeypatch.setenv("BIGDL_TPU_PROFILER_MAX_SEC", "junk")
    with pytest.raises(ValueError):
        resolve_profiler_max_sec(None)


# ---------------------------------------------------------------------------
# live engine: gauges + chaos trip/recover with auto-capture


class _FakeModel:
    def __init__(self, params, cfg):
        from bigdl_tpu.models import llama as llama_mod

        self.params = params
        self.config = cfg
        self.hf_config = {"eos_token_id": None}

        class Fam:
            forward = staticmethod(llama_mod.forward)
            prefill = staticmethod(llama_mod.forward_last_token)
            new_cache = staticmethod(llama_mod.new_cache)

        self.family = Fam()


def _mk_engine(tiny_params, faults=None, **cfg_kw):
    from bigdl_tpu.serving import EngineConfig, LLMEngine
    from bigdl_tpu.utils.testing import TINY_LLAMA

    return LLMEngine(_FakeModel(tiny_params, TINY_LLAMA),
                     EngineConfig(max_batch=2, max_seq=128, **cfg_kw),
                     faults=faults)


@pytest.fixture
def tiny_params():
    from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params

    return random_llama_params(TINY_LLAMA, seed=0)


def test_live_gauge_matches_bench_formula(tiny_params):
    """Acceptance criterion: the live decode gauge agrees with bench's
    `decode_hbm_roofline_util` formula to 4 decimals — same ideal-ms
    numerator (weights + bf16 KV slice at the live cache depth) over
    the measured step time."""
    from bigdl_tpu.serving import SamplingParams
    from bigdl_tpu.utils.testing import TINY_LLAMA

    eng = _mk_engine(tiny_params, sentinel=True)
    eng.add_request("r0", [1, 2, 3, 4], SamplingParams(max_tokens=12))
    for _ in range(6):
        eng.step()
    perf = eng._last_perf
    assert perf is not None
    costs = roofline.decode_costs(
        TINY_LLAMA, eng._weight_bytes, perf["seq_len"],
        eng.kv_cache_dtype, batch=perf["batch"])
    want = round(costs["ideal_ms"] / perf["decode_ms"], 4)
    assert perf["roofline_util"] == pytest.approx(want, abs=1e-4)
    snap = eng.perf_snapshot()
    assert snap["decode"]["roofline_util"] == perf["roofline_util"]
    assert snap["sentinel"]["steps"] >= 1
    assert snap["weight_bytes"] == eng._weight_bytes


def test_stats_snapshot_carries_perf_block(tiny_params):
    from bigdl_tpu.serving import SamplingParams

    eng = _mk_engine(tiny_params, sentinel=True)
    eng.add_request("r0", [1, 2, 3], SamplingParams(max_tokens=6))
    for _ in range(4):
        eng.step()
    perf = eng.stats_snapshot()["perf"]
    assert perf["roofline_util_decode"] is not None
    assert perf["sentinel_tripped"] is False
    assert perf["sentinel_trips"] == 0


def test_slow_step_chaos_trips_sentinel_and_captures(
        tiny_params, tmp_path, monkeypatch, fake_jax_profiler):
    """The chaos acceptance run: a slow_step fault (which sleeps BEFORE
    the decode bracket — only the step()-entry wall clock sees it)
    drives the sentinel through trip -> auto-capture -> recovery."""
    from bigdl_tpu.robustness.faults import (FaultInjector,
                                             parse_fault_spec)
    from bigdl_tpu.serving import SamplingParams

    pm_dir = tmp_path / "postmortem"
    monkeypatch.setenv("BIGDL_TPU_POSTMORTEM_DIR", str(pm_dir))
    monkeypatch.setenv("BIGDL_TPU_SENTINEL_THRESHOLD", "1.0")
    monkeypatch.setenv("BIGDL_TPU_SENTINEL_TRIP_STEPS", "3")
    monkeypatch.setenv("BIGDL_TPU_SENTINEL_RECOVER_STEPS", "3")
    # a 150 ms stall on every step past 30 vs a CPU-tiny baseline:
    # unambiguously past a 2x threshold, cheap enough for CI
    faults = FaultInjector(parse_fault_spec(
        "slow_step@ms=150,after_step=30,times=10"))
    eng = _mk_engine(tiny_params, faults=faults, sentinel=True,
                     perf_history=str(tmp_path / "perf.jsonl"))
    eng.add_request("r0", list(range(1, 6)),
                    SamplingParams(max_tokens=110))

    # settle past the first-step jit-compile spike, then re-baseline
    # from the decayed EWMA — a prod engine's warmup window (and its
    # history file) covers thousands of steps, a CI run gets ~25
    for _ in range(25):
        eng.step()
    with eng.sentinel._lock:
        eng.sentinel._baseline = {}
    eng.step()                              # baseline := settled EWMA

    tripped_at = None
    for i in range(40):
        eng.step()
        if eng.sentinel.tripped:
            tripped_at = i
            break
    assert tripped_at is not None, eng.sentinel.snapshot()

    events = [e["event"] for e in eng.flight.snapshot()]
    assert "perf_regression" in events
    # postmortem landed in the configured dir
    dumps = glob.glob(str(pm_dir / "postmortem-*perf_regression*"))
    assert dumps, list(pm_dir.iterdir()) if pm_dir.is_dir() else []
    # bounded auto-capture started into a per-trip subdir
    assert "perf_auto_capture" in events
    caps = glob.glob(str(pm_dir / "perf_capture_step*"))
    assert caps and os.path.isdir(caps[0])
    assert fake_jax_profiler["start"], "profiler never started"
    # the prometheus counter actually incremented, per tripped metric
    lines = [ln for ln in eng.registry.render().splitlines()
             if ln.startswith("bigdl_tpu_perf_regression_total{")]
    assert lines and any(float(ln.split()[-1]) > 0 for ln in lines)

    # fault clauses exhaust (times=10) -> healthy steps -> EWMA decays
    # -> hysteresis recovery
    for _ in range(80):
        if not eng.has_unfinished():
            break
        eng.step()
        if not eng.sentinel.tripped:
            break
    assert not eng.sentinel.tripped, eng.sentinel.snapshot()
    events = [e["event"] for e in eng.flight.snapshot()]
    assert "perf_recovered" in events
    snap = eng.sentinel.snapshot()
    assert snap["trips"] == 1 and snap["recoveries"] == 1
