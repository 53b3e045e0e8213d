"""Quality observability: load-time quantization-error attribution,
live decode-path quality telemetry, the QualitySentinel, and the
NLL-tolerance canary mode (observability/quality.py + engine/canary
wiring).

Five invariants from the PR that introduced them:

1. **Attribution** — every converted linear lands in the
   AttributionReport with sane SNR/clip stats, ranked worst-first,
   and the table is byte-stable across prepack on/off (attribution
   runs at convert time, before any repacking).
2. **Sentinel state machine** — QualitySentinel trips after N
   consecutive past-threshold samples (rising NLL/entropy, falling
   top-1 margin), recovers with hysteresis, and validates its env
   knobs.
3. **Single dispatch** — with quality telemetry ON, a pure-decode
   resident step still issues exactly ONE host dispatch; the quality
   rows ride the existing transfer.
4. **Chaos trip** — a sticky ``logit_drift`` fault drives the probe
   NLL through trip (``quality_regression`` flight event + postmortem
   + nonzero ``bigdl_tpu_quality_regression_total``) and back through
   hysteresis recovery once the drift is healed.
5. **NLL canary** — the prober records golden NLLs, tolerates
   in-budget drift, and quarantines (kind="nll") a replica whose
   distribution drifts while its bytes stay golden.
"""

import dataclasses
import glob
import math

import numpy as np
import pytest

from bigdl_tpu import config as config_mod
from bigdl_tpu.config import set_flags
from bigdl_tpu.observability.quality import (
    GOLDEN_PPL_DELTA,
    QUALITY_METRICS,
    AttributionReport,
    QualitySentinel,
    collect_attribution,
    current_attribution,
    golden_nll_allowance,
    resolve_quality_probe_steps,
    resolve_quality_recover_steps,
    resolve_quality_threshold,
    resolve_quality_trip_steps,
    weight_error_stats,
)


@pytest.fixture(autouse=True)
def _restore_flags():
    snap = dataclasses.replace(config_mod.flags())
    yield
    config_mod._flags = snap


@pytest.fixture(autouse=True)
def _clean_quality_env(monkeypatch):
    for var in ("BIGDL_TPU_QUALITY", "BIGDL_TPU_QUALITY_THRESHOLD",
                "BIGDL_TPU_QUALITY_TRIP_STEPS",
                "BIGDL_TPU_QUALITY_RECOVER_STEPS",
                "BIGDL_TPU_QUALITY_PROBE_STEPS",
                "BIGDL_TPU_QUALITY_HISTORY",
                "BIGDL_TPU_CANARY_NLL_TOL"):
        monkeypatch.delenv(var, raising=False)


# ---------------------------------------------------------------------------
# env knobs + golden budgets


def test_quality_resolvers_defaults_and_validation(monkeypatch):
    assert resolve_quality_threshold() == 0.5
    assert resolve_quality_trip_steps() == 5
    assert resolve_quality_recover_steps() == 10
    assert resolve_quality_probe_steps() == 0

    monkeypatch.setenv("BIGDL_TPU_QUALITY_THRESHOLD", "0.25")
    monkeypatch.setenv("BIGDL_TPU_QUALITY_TRIP_STEPS", "3")
    monkeypatch.setenv("BIGDL_TPU_QUALITY_RECOVER_STEPS", "7")
    monkeypatch.setenv("BIGDL_TPU_QUALITY_PROBE_STEPS", "16")
    assert resolve_quality_threshold() == 0.25
    assert resolve_quality_trip_steps() == 3
    assert resolve_quality_recover_steps() == 7
    assert resolve_quality_probe_steps() == 16

    with pytest.raises(ValueError):
        resolve_quality_threshold("0")
    with pytest.raises(ValueError):
        resolve_quality_threshold("soon")
    with pytest.raises(ValueError):
        resolve_quality_trip_steps("0")
    with pytest.raises(ValueError):
        resolve_quality_probe_steps("-1")
    with pytest.raises(ValueError):
        resolve_quality_probe_steps("often")
    # 0 is legal for the probe (off) but not for trip/recover dwell
    assert resolve_quality_probe_steps("0") == 0


def test_golden_nll_allowance_tracks_accuracy_md():
    # ppl = exp(mean nll)  =>  allowed Δnll = ln(1 + Δppl)
    assert golden_nll_allowance("bf16") == 0.0
    assert golden_nll_allowance("sym_int4") == 0.0
    assert golden_nll_allowance("q2_k") == pytest.approx(
        math.log1p(GOLDEN_PPL_DELTA["q2_k"]))
    # GGUF spellings map onto the same budget
    assert golden_nll_allowance("gguf_iq1_s") \
        == golden_nll_allowance("iq1_s")
    # unknown/None formats get the WORST tracked budget, never a free
    # pass through a tight gate
    worst = math.log1p(max(GOLDEN_PPL_DELTA.values()))
    assert golden_nll_allowance("mystery_2bit") == pytest.approx(worst)
    assert golden_nll_allowance(None) == pytest.approx(worst)


# ---------------------------------------------------------------------------
# weight_error_stats + AttributionReport


def test_weight_error_stats_math():
    rng = np.random.default_rng(0)
    ref = rng.standard_normal(4096).astype(np.float32)
    noise = 0.01 * rng.standard_normal(4096).astype(np.float32)
    st = weight_error_stats(ref, ref + noise)
    want_snr = 10.0 * math.log10(
        float(np.dot(ref, ref)) / float(np.dot(noise, noise)))
    assert st["snr_db"] == pytest.approx(want_snr, abs=1e-3)
    assert st["max_abs_err"] == pytest.approx(
        float(np.max(np.abs(noise))), rel=1e-5)
    assert st["rel_err"] == pytest.approx(
        math.sqrt(float(np.dot(noise, noise)) / float(np.dot(ref, ref))),
        abs=1e-5)


def test_weight_error_stats_exact_and_clipped():
    ref = np.linspace(-1.0, 1.0, 64, dtype=np.float32)
    st = weight_error_stats(ref, ref)
    assert st["snr_db"] == float("inf")
    assert st["max_abs_err"] == 0.0 and st["rel_err"] == 0.0
    # a clamp-heavy encode: half the weights saturate at the extreme
    deq = np.clip(ref, -0.5, 0.5)
    st = weight_error_stats(ref, deq)
    assert st["clip_sat"] > 0.4          # ~half the range clamps
    assert st["max_abs_err"] == pytest.approx(0.5, abs=1e-6)


def test_attribution_report_ranks_worst_first():
    rep = AttributionReport()
    rep.add("layers.0.q_proj", "sym_int4",
            {"snr_db": 40.0, "clip_sat": 0.0})
    rep.add("layers.1.down_proj", "sym_int4",
            {"snr_db": 12.5, "clip_sat": 0.02})
    rep.add("lm_head", "sym_int8", {"snr_db": 55.0, "clip_sat": 0.0})
    tab = rep.table()
    assert [r["name"] for r in tab] \
        == ["layers.1.down_proj", "layers.0.q_proj", "lm_head"]
    s = rep.summary()
    assert s["tensors"] == 3
    assert s["worst_name"] == "layers.1.down_proj"
    assert s["worst_snr_db"] == 12.5
    assert s["max_clip_sat"] == 0.02
    doc = rep.to_doc(limit=2)
    assert len(doc["table"]) == 2 and doc["summary"]["tensors"] == 3


def test_collect_attribution_installs_and_restores():
    assert current_attribution() is None
    with collect_attribution() as rep:
        assert current_attribution() is rep
        rep.add("x", "nf4", {"snr_db": 30.0})
    assert current_attribution() is None
    assert len(rep) == 1


def _tiny_llama_ckpt():
    """(hf_config, [(name, tensor)]) for a 2-layer tied-head llama."""
    D, FF, V, L, H = 32, 64, 96, 2, 4
    rng = np.random.default_rng(7)

    def t(*shape):
        return (0.1 * rng.standard_normal(shape)).astype(np.float32)

    hf = {"architectures": ["LlamaForCausalLM"], "vocab_size": V,
          "hidden_size": D, "intermediate_size": FF,
          "num_hidden_layers": L, "num_attention_heads": H,
          "num_key_value_heads": H, "rms_norm_eps": 1e-5,
          "tie_word_embeddings": True}
    ts = [("model.embed_tokens.weight", t(V, D)),
          ("model.norm.weight", np.ones((D,), np.float32))]
    for i in range(L):
        p = f"model.layers.{i}."
        ts += [(p + "self_attn.q_proj.weight", t(D, D)),
               (p + "self_attn.k_proj.weight", t(D, D)),
               (p + "self_attn.v_proj.weight", t(D, D)),
               (p + "self_attn.o_proj.weight", t(D, D)),
               (p + "mlp.gate_proj.weight", t(FF, D)),
               (p + "mlp.up_proj.weight", t(FF, D)),
               (p + "mlp.down_proj.weight", t(D, FF)),
               (p + "input_layernorm.weight", np.ones((D,), np.float32)),
               (p + "post_attention_layernorm.weight",
                np.ones((D,), np.float32))]
    return hf, ts


def _convert_with_attribution(prepack_mode):
    from bigdl_tpu.models.registry import get_family
    from bigdl_tpu.ops.quant import prepack_tree

    set_flags(prepack=prepack_mode)
    hf, ts = _tiny_llama_ckpt()
    fam = get_family(hf["architectures"][0])
    cfg = fam.config_from_hf(hf)
    with collect_attribution() as rep:
        params = fam.convert_params(iter(ts), cfg, qtype="sym_int4")
    # mimic the model load tail: prepack AFTER conversion, so the
    # attribution (recorded against the pre-quant floats) cannot see it
    prepack_tree(params)
    return rep


def test_convert_attributes_every_linear():
    rep = _convert_with_attribution("off")
    tab = rep.table()
    # 2 layers x 7 projections, all quantized, all recorded
    assert len(tab) == 14
    assert all(r["qtype"] == "sym_int4" for r in tab)
    # int4 on small gaussian weights: a real but bounded SNR
    for r in tab:
        assert 5.0 < r["snr_db"] < 60.0, r
        assert r["max_abs_err"] > 0.0
        assert 0.0 <= r["clip_sat"] <= 1.0
    # worst-first ranking
    snrs = [r["snr_db"] for r in tab]
    assert snrs == sorted(snrs)


def test_attribution_table_stable_across_prepack():
    """Acceptance criterion: the attribution table is identical with
    prepack off and forced on — the error is measured at convert
    time, before any layout transform can touch the encodings."""
    t_off = _convert_with_attribution("off").table()
    t_on = _convert_with_attribution("on").table()
    assert t_off == t_on


# ---------------------------------------------------------------------------
# QualitySentinel state machine


def test_quality_sentinel_trips_on_rising_nll_and_recovers():
    events = []
    s = QualitySentinel(threshold=0.5, trip_steps=3, recover_steps=3,
                        warmup_steps=4,
                        on_trip=lambda info: events.append(("trip", info)),
                        on_recover=lambda info: events.append(
                            ("recover", info)))
    for _ in range(5):
        assert s.observe(token_nll=1.0) is None
    assert not s.tripped

    transitions = []
    for _ in range(10):
        r = s.observe(token_nll=5.0)
        if r:
            transitions.append(r)
            break
    assert transitions == ["trip"] and s.tripped
    assert events[0][0] == "trip"
    assert "token_nll" in events[0][1]["metrics"]

    for _ in range(30):
        r = s.observe(token_nll=1.0)
        if r:
            transitions.append(r)
            break
    assert transitions == ["trip", "recover"] and not s.tripped
    snap = s.snapshot()
    assert snap["trips"] == 1 and snap["recoveries"] == 1


def test_quality_sentinel_margin_direction_is_inverted():
    """top-1 margin FALLING below baseline*(1-threshold) is the bad
    direction — the argmax losing its lead, not gaining one."""
    s = QualitySentinel(threshold=0.5, trip_steps=2, recover_steps=2,
                        warmup_steps=3)
    for _ in range(4):
        s.observe(top1_margin=4.0)
    # margin DOUBLING is healthy
    for _ in range(6):
        assert s.observe(top1_margin=8.0) is None
    assert not s.tripped
    # margin collapsing is not
    tripped = None
    for _ in range(10):
        if s.observe(top1_margin=0.2) == "trip":
            tripped = True
            break
    assert tripped and s.tripped
    assert "top1_margin" in s.snapshot()["tripped_metrics"]


def test_quality_sentinel_watches_the_quality_metric_set():
    s = QualitySentinel()
    assert tuple(s.metrics) == QUALITY_METRICS
    assert s.higher_is_bad["probe_nll"] is True
    assert s.higher_is_bad["top1_margin"] is False
    # env-free defaults mirror the resolvers
    assert s.threshold == 0.5
    assert s.trip_steps == 5 and s.recover_steps == 10
    assert s.history_path is None


# ---------------------------------------------------------------------------
# live engine: single dispatch, telemetry, probe, chaos trip/recover


@pytest.fixture
def tiny_params():
    from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params

    return random_llama_params(TINY_LLAMA, seed=0)


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

    try:
        profiling.stop_profiler()
    except RuntimeError:
        pass
    yield calls
    try:
        profiling.stop_profiler()
    except RuntimeError:
        pass


def test_resident_one_dispatch_with_quality_telemetry(tiny_params):
    """The PR acceptance criterion: with quality telemetry explicitly
    ON (probe off), a pure-decode step still issues exactly ONE host
    dispatch — the quality rows come back inside the fused step's one
    existing transfer."""
    from bigdl_tpu.observability.compile_watch import (
        dispatch_table,
        reset_dispatch_table,
    )
    from bigdl_tpu.serving import SamplingParams

    set_flags(decode_resident="on")
    eng = _mk_engine(tiny_params, quality=True)
    assert eng.qsentinel is not None
    eng.add_request("r0", [1, 2, 3, 4], SamplingParams(max_tokens=50))
    eng.step()                              # admission + first decode
    reset_dispatch_table()
    for _ in range(5):
        eng.step()
    assert dispatch_table() == {"engine_decode_resident": 5}
    # the telemetry actually ran inside that budget
    q = eng._last_quality
    assert q is not None and q["batch"] == 1
    assert q["token_nll"] > 0.0 and q["entropy"] > 0.0
    assert eng.qsentinel.snapshot()["steps"] >= 5


def test_quality_histograms_render_and_lint_clean(tiny_params):
    import pathlib
    import sys

    sys.path.insert(0, str(
        pathlib.Path(__file__).resolve().parent.parent / "tools"))
    from promlint import lint_text

    from bigdl_tpu.serving import SamplingParams

    set_flags(decode_resident="on")
    eng = _mk_engine(tiny_params, quality=True)
    eng.add_request("r0", [1, 2, 3], SamplingParams(max_tokens=8))
    for _ in range(6):
        eng.step()
    text = eng.registry.render()
    for fam in ("bigdl_tpu_quality_token_logprob",
                "bigdl_tpu_quality_entropy",
                "bigdl_tpu_quality_top1_margin",
                "bigdl_tpu_quality_eos_total",
                "bigdl_tpu_quality_repeat_total",
                "bigdl_tpu_quality_probe_nll",
                "bigdl_tpu_quality_regression_total"):
        assert fam in text, fam
    assert lint_text(text) == [], "\n".join(lint_text(text))
    # the histograms are labeled by numeric config + qos and got fed
    line = [ln for ln in text.splitlines()
            if ln.startswith("bigdl_tpu_quality_token_logprob_count{")
            and 'qos="standard"' in ln]
    assert line and any(float(ln.split()[-1]) > 0 for ln in line)


def test_quality_snapshot_and_stats_block(tiny_params):
    from bigdl_tpu.serving import SamplingParams

    set_flags(decode_resident="on")
    eng = _mk_engine(tiny_params, quality=True, quality_probe_steps=2)
    eng.add_request("r0", [1, 2, 3], SamplingParams(max_tokens=10))
    for _ in range(6):
        eng.step()
    snap = eng.quality_snapshot()
    assert snap["enabled"] is True
    assert snap["qtype"] == eng.qtype
    assert snap["live"]["token_nll"] > 0.0
    assert snap["probe"] is not None and snap["probe"]["nll"] > 0.0
    assert snap["probe"]["prompts"] == 4
    assert snap["probe_period_steps"] == 2
    assert snap["sentinel"]["tripped"] is False
    assert snap["golden_nll_allowance"] >= 0.0
    # the probe is its own tracked jit, visible in the dispatch table
    from bigdl_tpu.observability.compile_watch import dispatch_table
    assert dispatch_table().get("engine_quality_probe", 0) >= 1

    q = eng.stats_snapshot()["quality"]
    assert q["token_nll"] == snap["live"]["token_nll"]
    assert q["probe_nll"] == snap["probe"]["nll"]
    assert q["sentinel_tripped"] is False and q["sentinel_trips"] == 0

    # off means off: no sentinel, no block, no probe fn
    eng2 = _mk_engine(tiny_params, quality=False)
    assert eng2.qsentinel is None
    assert eng2.stats_snapshot()["quality"] is None
    assert eng2.quality_snapshot()["enabled"] is False


def test_logit_drift_chaos_trips_quality_sentinel(
        tiny_params, tmp_path, monkeypatch, fake_jax_profiler):
    """The chaos acceptance run: a sticky logit_drift fault — fast,
    healthy, isfinite, byte-level-invisible to perf sentinels — moves
    the teacher-forced probe NLL, trips the QualitySentinel
    (flight event + postmortem + counter), and hysteresis-recovers
    once the drift is healed."""
    from bigdl_tpu.robustness.faults import (FaultInjector,
                                             parse_fault_spec)
    from bigdl_tpu.serving import SamplingParams

    pm_dir = tmp_path / "postmortem"
    monkeypatch.setenv("BIGDL_TPU_POSTMORTEM_DIR", str(pm_dir))
    monkeypatch.setenv("BIGDL_TPU_QUALITY_THRESHOLD", "0.5")
    monkeypatch.setenv("BIGDL_TPU_QUALITY_TRIP_STEPS", "3")
    monkeypatch.setenv("BIGDL_TPU_QUALITY_RECOVER_STEPS", "3")
    # +12 on vocab column 0 of every probe row: the probe's chosen
    # tokens lose ~ln(e^12/V) nats — unambiguously past 1.5x baseline
    faults = FaultInjector(parse_fault_spec(
        "logit_drift@after_step=25,times=1,bias=12"))
    eng = _mk_engine(tiny_params, faults=faults, quality=True,
                     quality_probe_steps=1,
                     quality_history=str(tmp_path / "quality.jsonl"))
    eng.add_request("r0", list(range(1, 6)),
                    SamplingParams(max_tokens=120))

    # healthy probes through the warmup window establish the baseline
    for _ in range(20):
        eng.step()
    assert not eng.qsentinel.tripped
    healthy_nll = eng._last_probe["nll"]
    assert eng.qsentinel.snapshot()["baseline"].get("probe_nll") \
        == pytest.approx(healthy_nll, rel=0.05)

    tripped_at = None
    for i in range(30):
        eng.step()
        if eng.qsentinel.tripped:
            tripped_at = i
            break
    assert tripped_at is not None, eng.qsentinel.snapshot()
    assert eng._last_probe["nll"] > healthy_nll * 1.5

    events = [e["event"] for e in eng.flight.snapshot()]
    assert "quality_regression" in events
    dumps = glob.glob(str(pm_dir / "postmortem-*quality_regression*"))
    assert dumps, list(pm_dir.iterdir()) if pm_dir.is_dir() else []
    lines = [ln for ln in eng.registry.render().splitlines()
             if ln.startswith("bigdl_tpu_quality_regression_total{")]
    assert lines and any(float(ln.split()[-1]) > 0 for ln in lines)
    assert eng.stats_snapshot()["quality"]["sentinel_tripped"] is True

    # heal the drift (the clause is sticky by design; times=1 means it
    # cannot re-arm) -> probe NLL decays -> hysteresis recovery
    for clause in eng.faults._by_kind["logit_drift"]:
        clause._drifting = False
    for _ in range(60):
        if not eng.has_unfinished():
            break
        eng.step()
        if not eng.qsentinel.tripped:
            break
    assert not eng.qsentinel.tripped, eng.qsentinel.snapshot()
    events = [e["event"] for e in eng.flight.snapshot()]
    assert "quality_recovered" in events
    snap = eng.qsentinel.snapshot()
    assert snap["trips"] == 1 and snap["recoveries"] == 1


# ---------------------------------------------------------------------------
# NLL-tolerance canary mode (stub router — no processes)


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
    # the prober compares replica state against router.HEALTHY
    monkeypatch.setattr("bigdl_tpu.serving.router.HEALTHY", "H")
    return _StubRouter()


def _doc(text, logprobs=None):
    ch = {"text": text, "finish_reason": "length", "index": 0}
    if logprobs is not None:
        ch["logprobs"] = {"token_logprobs": list(logprobs)}
    return {"id": "cmpl-x", "choices": [ch]}


def test_resolve_canary_nll_tol(monkeypatch):
    from bigdl_tpu.serving.canary import resolve_canary_nll_tol

    assert resolve_canary_nll_tol() == 0.0
    monkeypatch.setenv("BIGDL_TPU_CANARY_NLL_TOL", "0.05")
    assert resolve_canary_nll_tol() == 0.05
    with pytest.raises(ValueError):
        resolve_canary_nll_tol("-0.1")
    with pytest.raises(ValueError):
        resolve_canary_nll_tol("lots")


def test_canary_nll_goldens_and_tolerance(stub_router, monkeypatch):
    from bigdl_tpu.serving.canary import CanaryProber

    router = stub_router
    prober = CanaryProber(router, interval_sec=0.0, nll_tol=0.05)
    # replica 0 answers first (defines byte + NLL goldens); replica 1
    # matches bytes exactly and drifts NLL by only 0.01 — in budget
    lps = {9000: [-1.00, -1.20, -0.80], 9001: [-1.01, -1.21, -0.81]}
    monkeypatch.setattr(
        prober, "_post_completion",
        lambda port, prompt, headers=None: _doc("same", lps[port]))
    out = prober.sweep()
    assert out == {"probes": 6, "mismatches": 0}
    assert len(prober.goldens_nll) == 3
    assert router.mismatches == []
    snap = prober.snapshot()
    assert snap["nll_tol"] == 0.05
    assert snap["nll_goldens_recorded"] == 3
    assert snap["nll_failures_total"] == 0


def test_canary_nll_drift_quarantines_byte_identical_replica(
        stub_router, monkeypatch):
    """The blind spot this mode closes: bytes match the golden exactly
    — only the distribution drifted — and the replica is still
    quarantined, with kind='nll' so the flight event says why."""
    from bigdl_tpu.serving.canary import CanaryProber

    router = stub_router
    prober = CanaryProber(router, interval_sec=0.0, nll_tol=0.05)
    lps = {9000: [-1.00, -1.20, -0.80], 9001: [-1.50, -1.70, -1.30]}
    monkeypatch.setattr(
        prober, "_post_completion",
        lambda port, prompt, headers=None: _doc("same", lps[port]))
    out = prober.sweep()
    assert out["mismatches"] == 1
    assert router.replicas[1].state == "Q"
    assert router.replicas[0].state == "H"
    idx, kw = router.mismatches[0]
    assert idx == 1 and kw["kind"] == "nll"
    assert "nll=" in kw["expected"] and "±" in kw["expected"]
    assert prober.nll_failures_total == 1
    # byte goldens never disagreed: this was purely the NLL check
    assert prober.failures_total == 1


def test_canary_byte_mismatch_preempts_nll_check(stub_router,
                                                 monkeypatch):
    from bigdl_tpu.serving.canary import CanaryProber

    router = stub_router
    prober = CanaryProber(router, interval_sec=0.0, nll_tol=0.05)
    answers = {9000: "alpha", 9001: "beta"}
    monkeypatch.setattr(
        prober, "_post_completion",
        lambda port, prompt, headers=None: _doc(
            answers[port], [-9.0, -9.0, -9.0]))
    out = prober.sweep()
    assert out["mismatches"] == 1
    # quarantined on bytes; the NLL path never double-counted it
    assert prober.nll_failures_total == 0
    assert router.mismatches[0][1]["kind"] != "nll"


def test_canary_nll_requests_logprobs_only_when_enabled(stub_router,
                                                        monkeypatch):
    """payload hygiene: byte-only mode must not change the request
    shape (golden stability across upgrades); NLL mode adds
    logprobs=0."""
    from bigdl_tpu.serving.canary import CanaryProber

    import http.client
    import json

    router = stub_router
    seen = {}

    class FakeConn:
        def __init__(self, host, port, timeout=0.0):
            pass

        def request(self, method, path, body=None, headers=None):
            seen.clear()
            seen.update(json.loads(body.decode()))
            raise OSError("stub transport")

        def close(self):
            pass

    monkeypatch.setattr(http.client, "HTTPConnection", FakeConn)
    for tol, want in ((0.0, False), (0.05, True)):
        prober = CanaryProber(router, interval_sec=0.0, nll_tol=tol)
        assert prober._post_completion(9000, (1, 2, 3)) is None
        assert ("logprobs" in seen) is want, (tol, seen)
        if want:
            assert seen["logprobs"] == 0 and seen["temperature"] == 0.0


def test_canary_missing_logprobs_is_not_a_mismatch(stub_router,
                                                   monkeypatch):
    """A replica that answers without a logprobs block (older build
    mid-rolling-upgrade) is not drift — liveness and API shape are
    other probes' jobs."""
    from bigdl_tpu.serving.canary import CanaryProber

    router = stub_router
    prober = CanaryProber(router, interval_sec=0.0, nll_tol=0.05)
    monkeypatch.setattr(
        prober, "_post_completion",
        lambda port, prompt, headers=None: _doc("same"))
    out = prober.sweep()
    assert out == {"probes": 6, "mismatches": 0}
    assert prober.goldens_nll == {}
