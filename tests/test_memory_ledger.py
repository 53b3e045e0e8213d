"""Memory-ledger tests: static byte exactness against the allocators,
live-telemetry fallback contracts, compile-table memory capture,
headroom-aware admission (defer then resume, deterministically, via an
injected stats provider), the /v1/memory endpoint, postmortem memory
snapshots."""

import json
import threading
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import llama as llama_mod
from bigdl_tpu.observability.memory import (MemoryLedger, default_ledger,
                                            device_memory_stats,
                                            memory_report,
                                            reset_default_ledger,
                                            resolve_hbm_budget_fraction,
                                            resolve_memory_poll_sec,
                                            tree_nbytes)
from bigdl_tpu.ops.kvcache import (init_cache, kv_cache_bytes,
                                   kv_cache_nbytes)
from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params


# deliberately unaligned: odd seq, odd head count, non-power-of-2 dim
GEOMETRIES = [
    (2, 1, 17, 3, 12),     # L, B, S, Hkv, hd — odd everything
    (3, 2, 64, 2, 16),     # aligned control
    (1, 3, 33, 1, 7),      # tiny odd
]


# -- static accounting exactness ------------------------------------------


@pytest.mark.parametrize("geom", GEOMETRIES)
@pytest.mark.parametrize("dtype", ["bf16", "fp8_e5m2", "int8", "int4"])
def test_kv_nbytes_matches_allocation(geom, dtype):
    """The pure-formula footprint must equal the allocated cache's
    nbytes component-for-component — the ledger's registrations and the
    engine's admission-cost estimate both depend on this."""
    L, B, S, H, hd = geom
    want = kv_cache_bytes(init_cache(L, B, S, H, hd, kv_cache_dtype=dtype))
    got = kv_cache_nbytes(L, B, S, H, hd, dtype)
    assert got == want


@pytest.mark.parametrize("geom", GEOMETRIES)
def test_kv_dtype_byte_ratios(geom):
    """int8 codes are exactly half of bf16's; int4 packs two codes per
    byte (= quarter of bf16 on even element counts, ceil on odd)."""
    L, B, S, H, hd = geom
    n = L * B * S * H * hd
    bf16 = kv_cache_nbytes(L, B, S, H, hd, "bf16")
    i8 = kv_cache_nbytes(L, B, S, H, hd, "int8")
    i4 = kv_cache_nbytes(L, B, S, H, hd, "int4")
    assert i8["codes"] * 2 == bf16["codes"]
    assert i4["codes"] == 2 * (-(-n // 2))
    if n % 2 == 0:
        assert i4["codes"] * 4 == bf16["codes"]
    # both carry f32 scale planes; bf16 carries none
    assert bf16["scales"] == 0
    assert i8["scales"] == i4["scales"] > 0


def test_tree_nbytes_matches_quantized_params():
    """tree_nbytes over a sym_int4 param tree reproduces the packed
    QTensor byte convention (two int4 codes per byte) — spot-checked
    against a hand-built mixed tree."""
    params = random_llama_params(TINY_LLAMA, qtype="sym_int4", seed=0)
    total = tree_nbytes(params)
    assert total > 0
    # against bf16 params of the same config the packed tree must be
    # substantially smaller (4-bit codes + scales vs 16-bit weights)
    bf16_total = tree_nbytes(random_llama_params(TINY_LLAMA, qtype=None,
                                                 seed=0))
    assert total < bf16_total
    # explicit convention check on a hand-built tree
    tree = {"a": jnp.zeros((3, 5), jnp.int4),       # 15 codes -> 8 bytes
            "b": jnp.zeros((2, 2), jnp.bfloat16),   # 8 bytes
            "c": 3}                                  # non-array -> 0
    assert tree_nbytes(tree) == 8 + 8


def test_ledger_static_report_math():
    led = MemoryLedger(stats_provider=lambda: {})
    led.register("weights", "m", 100, qtype="sym_int4")
    led.register("kv_cache", "c", 40, dtype="int8")
    led.register("kv_cache", "c2", 10)
    rep = led.static_report()
    assert rep["by_kind"] == {"weights": 100, "kv_cache": 50}
    assert rep["total_bytes"] == 150
    assert rep["entries"]["weights"]["m"]["qtype"] == "sym_int4"
    assert led.static_bytes("kv_cache") == 50
    led.unregister("kv_cache", "c2")
    assert led.static_bytes() == 140
    # re-register replaces, not accumulates
    led.register("weights", "m", 70)
    assert led.static_bytes("weights") == 70


# -- resolvers ------------------------------------------------------------


def test_budget_fraction_resolver():
    assert resolve_hbm_budget_fraction(None) == 0.9
    assert resolve_hbm_budget_fraction("0.5") == 0.5
    assert resolve_hbm_budget_fraction(1.0) == 1.0
    for bad in ("0", "-0.1", "1.5", "nope"):
        with pytest.raises(ValueError):
            resolve_hbm_budget_fraction(bad)


def test_memory_poll_sec_resolver():
    assert resolve_memory_poll_sec(None) == 1.0
    assert resolve_memory_poll_sec("0") == 0.0
    assert resolve_memory_poll_sec(2.5) == 2.5
    for bad in ("-1", "soon"):
        with pytest.raises(ValueError):
            resolve_memory_poll_sec(bad)


# -- live telemetry fallback ----------------------------------------------


def test_cpu_backend_degrades_to_no_telemetry():
    """On CPU, memory_stats() is None: the ledger must answer with
    empty dicts and would_fit None (admission control then admits)."""
    assert device_memory_stats() == {}    # this suite runs on CPU
    led = MemoryLedger()
    assert led.device_stats(refresh=True) == {}
    assert led.headroom() == {}
    assert led.would_fit(10**12) is None
    snap = led.snapshot()
    assert set(snap) == {"static", "device", "headroom"}


def test_provider_exception_swallowed():
    def boom():
        raise RuntimeError("plugin exploded")

    led = MemoryLedger(stats_provider=boom, poll_sec=0.0)
    assert led.device_stats() == {}
    assert led.would_fit(1) is None


def test_headroom_math_and_poll_throttle():
    calls = {"n": 0}
    stats = {"bytes_in_use": 600, "peak_bytes_in_use": 700,
             "bytes_limit": 1000}

    def provider():
        calls["n"] += 1
        return dict(stats)

    led = MemoryLedger(stats_provider=provider, budget_fraction=0.8,
                       poll_sec=3600.0)
    hr = led.headroom()
    assert hr["budget_bytes"] == 800
    assert hr["headroom_bytes"] == 200
    assert led.would_fit(200) is True
    assert led.would_fit(201) is False
    # throttled: the two would_fit calls above reused the first poll
    assert calls["n"] == 1
    stats["bytes_in_use"] = 0
    assert led.device_stats()["bytes_in_use"] == 600   # still cached
    assert led.device_stats(refresh=True)["bytes_in_use"] == 0
    assert calls["n"] == 2


def test_publish_gauges():
    led = MemoryLedger(
        stats_provider=lambda: {"bytes_in_use": 10, "bytes_limit": 100},
        budget_fraction=0.5, poll_sec=0.0)
    led.register("weights", "w", 1234)
    from bigdl_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    led.publish(reg)
    text = reg.render()
    assert 'bigdl_tpu_hbm_bytes{kind="weights"} 1234' in text
    assert 'bigdl_tpu_hbm_bytes{kind="device_limit"} 100' in text
    assert "bigdl_tpu_hbm_headroom_bytes 40" in text   # 50 - 10


# -- compile-table memory capture -----------------------------------------


def test_tracked_jit_captures_memory_analysis(monkeypatch):
    from bigdl_tpu.observability import compile_watch as cw
    from bigdl_tpu.observability.compile_watch import (compile_table,
                                                       tracked_jit)

    monkeypatch.setenv(cw.COMPILE_MEMORY_ENV, "1")   # conftest defaults 0
    f = tracked_jit("_memtest_add", lambda a, b: a @ b + 1.0)
    x = jnp.ones((8, 16), jnp.float32)
    f(x, x.T)
    ent = compile_table()["_memtest_add"]
    assert ent["compiles"] >= 1
    assert "peak_temp_bytes" in ent
    row = ent["signatures"][-1]
    mem = row.get("memory")
    assert mem is not None, "memory analysis missing from compile row"
    for key in ("temp_bytes", "argument_bytes", "output_bytes"):
        assert key in mem and mem[key] >= 0
    # 8x16 + 16x8 f32 arguments = 1024 bytes, 8x8 f32 output = 256
    assert mem["argument_bytes"] == 1024
    assert mem["output_bytes"] == 256


def test_compile_memory_kill_switch(monkeypatch):
    from bigdl_tpu.observability import compile_watch as cw

    monkeypatch.setenv(cw.COMPILE_MEMORY_ENV, "0")
    assert cw.memory_capture_enabled() is False
    f = cw.tracked_jit("_memtest_off", lambda a: a * 2)
    f(jnp.ones((4,), jnp.float32))
    row = cw.compile_table()["_memtest_off"]["signatures"][-1]
    assert row.get("memory") is None


def test_memory_report_headlines():
    reset_default_ledger()
    try:
        default_ledger().register("weights", "r", 512)
        rep = memory_report()
        assert rep["hbm_static_total_bytes"] == 512
        assert "jit_peak_temp_bytes" in rep
        assert rep["static"]["by_kind"] == {"weights": 512}
    finally:
        reset_default_ledger()


# -- engine: headroom-aware admission -------------------------------------


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


def test_engine_registers_static_memory(model):
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    rep = eng.ledger.static_report()
    assert rep["entries"]["weights"]["engine_params"]["bytes"] \
        == tree_nbytes(model.params)
    kv = rep["entries"]["kv_cache"]["engine_batched"]
    want = kv_cache_nbytes(
        TINY_LLAMA.num_hidden_layers, 2, 128,
        TINY_LLAMA.num_key_value_heads,
        TINY_LLAMA.hidden_size // TINY_LLAMA.num_attention_heads,
        eng.kv_cache_dtype)
    assert kv["bytes"] == want["total"]
    assert eng._kv_bytes_per_slot == want["total"] // 2


def test_admission_defers_then_resumes(model):
    """Shrink the fake device's free memory below the admission cost:
    the request must stay queued (counter + flight event), then admit
    and finish once headroom returns — fully deterministic."""
    stats = {"bytes_in_use": 0, "bytes_limit": 1 << 40}
    led = MemoryLedger(stats_provider=lambda: dict(stats),
                       budget_fraction=0.9, poll_sec=0.0)
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128),
                    ledger=led)
    eng.add_request("r1", [1, 2, 3, 4], SamplingParams(max_tokens=4))

    stats["bytes_in_use"] = stats["bytes_limit"]      # no headroom
    for _ in range(3):
        eng.step()
    assert eng._deferred_admissions == 3
    assert len(eng.waiting) == 1                      # still queued, FCFS
    assert not any(s.active for s in eng.slots)
    text = eng.registry.render()
    assert 'bigdl_tpu_admission_deferred_total{reason="memory"} 3' in text
    events = [e for e in eng.flight.snapshot()
              if e.get("event") == "admit_deferred"]
    assert len(events) == 1                           # one per streak
    assert events[0]["reason"] == "memory"
    assert events[0]["needed_bytes"] > 0

    snap = eng.memory_snapshot()
    assert snap["engine"]["admissions_deferred"] == 3
    assert snap["engine"]["next_admission_cost_bytes"] > 0
    assert snap["headroom"]["headroom_bytes"] < 0

    stats["bytes_in_use"] = 0                         # memory came back
    while eng.has_unfinished():
        eng.step()
    got = []
    for o in eng.get_outputs("r1"):
        got.extend(o.new_token_ids)
    assert len(got) == 4
    assert eng._deferred_admissions == 3              # no new deferrals
    assert 'bigdl_tpu_admission_deferred_total{reason="memory"} 3' \
        in eng.registry.render()


def test_no_telemetry_always_admits(model):
    """CPU contract: a ledger without stats never defers."""
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128),
                    ledger=MemoryLedger(stats_provider=lambda: {},
                                        poll_sec=0.0))
    outs = eng.generate([[1, 2, 3]], SamplingParams(max_tokens=3))
    assert len(outs[0]) == 3
    assert eng._deferred_admissions == 0


def test_postmortem_carries_memory(model):
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128))
    dump = eng.postmortem(reason="test")
    mem = dump.get("memory")
    assert mem is not None
    assert "static" in mem and "headroom" in mem
    assert "engine_params" in mem["static"]["entries"]["weights"]


def test_v1_memory_endpoint(model):
    from bigdl_tpu.serving.api_server import OpenAIServer

    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    server = OpenAIServer(eng)
    httpd = server.serve(port=0, background=True)
    port = httpd.server_address[1]
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/memory", timeout=30) as r:
            doc = json.loads(r.read())
        assert set(doc) >= {"static", "device", "headroom", "engine"}
        assert doc["static"]["total_bytes"] > 0
        eng_block = doc["engine"]
        assert eng_block["kv_cache_dtype"] == eng.kv_cache_dtype
        assert eng_block["kv_bytes_per_slot"] == eng._kv_bytes_per_slot
        json.dumps(doc)    # fully JSON-serializable
    finally:
        server.shutdown()

