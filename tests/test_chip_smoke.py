"""chip_smoke.py and the bring-up rules it stands on (ISSUE 21): the
smoke's control flow on the CPU, a parent that stays off JAX, a compile
cache placed from outside, probes that raise instead of falling back,
no roofline for an unknown device."""

import ast
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *argv, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # the conftest's 8 virtual devices
    return subprocess.run([sys.executable, os.path.join(REPO, script),
                           *argv], cwd=REPO, env=env, timeout=timeout,
                          capture_output=True, text=True)


# ------------------------------------------------------------ the smoke

def test_chip_smoke_tiny_runs_end_to_end():
    """(i) every phase, through the real entry points, at toy widths."""
    r = _run("chip_smoke.py", "--tiny")
    lines = [json.loads(ln) for ln in r.stdout.strip().splitlines()]
    assert r.returncode == 0, r.stdout[-3000:]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    phases = {d["phase"]: d for d in lines if "phase" in d}
    assert list(phases) == ["build", "serve", "serve_paged_int8", "qlora"]
    assert all(d["ok"] and d["checks"] for d in phases.values())
    # the environment's KV dtype reached the server's engine, through a
    # family built by the registry (not a hand-made test family)
    assert phases["serve_paged_int8"]["memory"]["kv_cache_dtype"] == "int8"
    assert phases["serve_paged_int8"]["paged"]["radix"]["hits"] >= 3


def test_chip_smoke_refuses_a_cpu_at_full_size():
    r = _run("chip_smoke.py")
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"


def test_chip_smoke_parent_imports_neither_jax_nor_package():
    """(ii) a parent that touched JAX would hold the chip its children
    need. No import of either, at any depth of the module."""
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        tree = ast.parse(f.read())
    roots = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            roots |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            roots.add((node.module or "").split(".")[0])
    assert roots and not roots & {"jax", "jaxlib", "bigdl_tpu", "numpy"}


def test_registry_family_declares_serving_capabilities():
    """What LLMEngine asks of a family (int8/int4 and paged KV) is there
    on an adapter from the registry, as on the module it routes to."""
    from bigdl_tpu.models import llama as llama_mod
    from bigdl_tpu.models.registry import get_family

    fam = get_family("MistralForCausalLM", {})
    assert fam.SUPPORTS_SCALED_KV and fam.SUPPORTS_PAGED_KV
    assert fam.forward_paged is llama_mod.forward_paged
    assert fam.new_paged_cache is llama_mod.new_paged_cache
    rwkv = get_family("RwkvForCausalLM", {})
    assert not rwkv.SUPPORTS_PAGED_KV and rwkv.forward_paged is None


# ------------------------------------------------------ compile cache

def test_compilation_cache_is_placed_from_outside(monkeypatch):
    """(iii) JAX_COMPILATION_CACHE_DIR set: no directory set in code;
    unset: the fixed <checkout>/.jax_cache."""
    from bigdl_tpu.config import enable_compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)


# ------------------------------------------------------------- probes

def _quant(k, n):
    from bigdl_tpu.ops.quant import quantize

    return quantize(jnp.ones((k, n), jnp.float32), "sym_int4")


def _probe_sites():
    from bigdl_tpu.ops import attention, matmul
    from bigdl_tpu.ops.pallas import dequant_matmul, moe_dispatch

    x1 = jnp.ones((1, 256), jnp.bfloat16)
    x64 = jnp.ones((64, 256), jnp.bfloat16)
    q = jnp.ones((1, 1, 8, 64), jnp.bfloat16)
    kv = jnp.ones((1, 128, 4, 64), jnp.bfloat16)
    arena = jnp.ones((4, 3, 128, 64), jnp.bfloat16)     # ops/paged.py
    bt = jnp.zeros((1, 2), jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)
    return {
        "gemv": lambda: matmul.q_matmul(x1, _quant(256, 256)),
        "matmul_generic": lambda: matmul.q_matmul(x64, _quant(256, 256)),
        "decode_attention": lambda: attention.sdp_attention(
            q, kv, kv, jnp.zeros((), jnp.int32)),
        "paged_decode_attention": lambda: attention.sdp_attention_paged(
            q, arena, arena, bt, pos, 4),
        "vmapped_gemm": lambda: matmul.vmapped_pallas_ok(
            "sym_int4", 256, 256),
        "moe_ragged": lambda: moe_dispatch.ragged_kernel_compiles(
            None, 256, 256),
    }, (attention._probe_cache, dequant_matmul._gemv_probe_cache,
        dequant_matmul._matmul_probe_cache, matmul._VMAPPED_PALLAS,
        moe_dispatch._probe_cache)


@pytest.mark.parametrize("site", [
    "gemv", "matmul_generic", "decode_attention",
    "paged_decode_attention", "vmapped_gemm", "moe_ragged"])
def test_refused_probe_raises_on_tpu(monkeypatch, site):
    """(iv) backend reported as TPU, dispatch auto, the compiler refuses
    the kernel: an exception with the compiler's message and one
    `fallback` count — never a quiet XLA run."""
    from bigdl_tpu import config
    from bigdl_tpu.observability.metrics import default_registry
    from bigdl_tpu.ops import probing

    def refuse(fn, *structs):
        raise RuntimeError("Mosaic failed to compile TPU kernel: boom")

    monkeypatch.setattr(config, "target_is_tpu", lambda: True)
    monkeypatch.setattr(probing, "probe_compile", refuse)
    sites, caches = _probe_sites()
    sizes = [len(c) for c in caches]

    def fallbacks():
        return sum(v for k, v in default_registry().summary().items()
                   if k.startswith("bigdl_tpu_kernel_probe_total")
                   and 'outcome="fallback"' in k)

    n0 = fallbacks()
    with pytest.raises(probing.KernelProbeError, match="boom"):
        sites[site]()
    assert fallbacks() == n0 + 1
    assert [len(c) for c in caches] == sizes    # a refusal is not cached


def test_probe_compiles_from_inside_a_shard_map_body():
    """A probe reached while tracing the explicit-TP body builds its
    structs under that trace's abstract mesh; the compile must not
    inherit it (on the four-chip smoke it did, and raised)."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.ops.matmul import _q_matmul_xla
    from bigdl_tpu.ops.probing import probe_compile, quant_struct
    from bigdl_tpu.parallel import make_mesh

    mesh = make_mesh(devices=jax.devices()[:4], tp=4)
    ran = []

    def body(x):
        probe_compile(_q_matmul_xla,
                      jax.ShapeDtypeStruct((16, 256), jnp.bfloat16),
                      quant_struct(256, 256, "sym_int4", mxu=True))
        ran.append(True)
        return x * 2

    with mesh:
        y = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("tp"),
                                  out_specs=P("tp"),
                                  check_vma=False))(jnp.ones((8, 4)))
    assert ran and np.asarray(y).sum() == 64


def test_cpu_auto_takes_xla_without_probing(monkeypatch):
    """(iv, other half) off-TPU `auto` is the XLA path and no probe."""
    from bigdl_tpu.ops import probing
    from bigdl_tpu.ops.matmul import q_matmul

    def never(*a, **k):
        raise AssertionError("probe ran on a CPU backend")

    monkeypatch.setattr(probing, "probe_compile", never)
    sites, _ = _probe_sites()
    for name in ("gemv", "matmul_generic", "decode_attention",
                 "paged_decode_attention"):
        assert bool(jnp.isfinite(sites[name]()).all()), name
    assert sites["vmapped_gemm"]() is False
    w = _quant(256, 256)
    x = jnp.ones((1, 256), jnp.bfloat16)
    assert jnp.array_equal(q_matmul(x, w), q_matmul(x, w, backend="xla"))


def test_xla_by_design_is_counted_apart_from_fallbacks(monkeypatch):
    """Rows past the measured crossover (`matmul.PALLAS_MAX_ROWS`) on a TPU
    are a dispatch RULE: their own label, no probe, no `fallback`."""
    from bigdl_tpu import config
    from bigdl_tpu.observability.metrics import default_registry
    from bigdl_tpu.ops import probing
    from bigdl_tpu.ops.matmul import q_matmul

    monkeypatch.setattr(config, "target_is_tpu", lambda: True)
    monkeypatch.setattr(probing, "probe_compile", lambda *a: pytest.fail(
        "probe ran for a shape XLA serves by design"))
    key = 'bigdl_tpu_kernel_probe_total{kernel="matmul",' \
          'outcome="xla_by_rule"}'
    n0 = default_registry().summary().get(key, 0)
    y = q_matmul(jnp.ones((2048, 256), jnp.bfloat16), _quant(256, 256))
    assert y.shape == (2048, 256)
    assert default_registry().summary()[key] == n0 + 1


def test_no_op_sliding_window_keeps_the_kernels():
    """Mistral's published window (4096) at a 2048-token cache masks
    nothing: dispatch drops it, a window that can bite stays."""
    from bigdl_tpu.ops.attention import _live_window

    assert _live_window(4096, 2048) is None
    assert _live_window(2048, 2048) is None
    assert _live_window(1024, 2048) == 1024
    traced = jnp.int32(7)
    assert _live_window(traced, 2048) is traced
    assert _live_window(None, 2048) is None


# ----------------------------------------------------------- roofline

def test_unknown_device_kind_has_no_roofline():
    """(v) peaks come from one table keyed by device_kind; a kind that
    is not in it raises, and the engine exports no roofline gauges."""
    from bigdl_tpu.observability import roofline
    from bigdl_tpu.observability.metrics import MetricsRegistry
    from bigdl_tpu.serving import EngineConfig, LLMEngine
    from bigdl_tpu.utils.testing import LLAMA2_7B, tiny_random_model

    assert roofline.chip_peaks("TPU v5 lite") == (197.0, 819.0)
    with pytest.raises(LookupError, match="warp drive"):
        roofline.chip_peaks("warp drive")
    assert jax.devices()[0].device_kind not in roofline.CHIP_PEAKS
    with pytest.raises(LookupError):
        roofline.decode_costs(LLAMA2_7B, 4 << 30, 512)
    reg = MetricsRegistry()
    eng = LLMEngine(tiny_random_model(), EngineConfig(
        max_batch=2, max_seq=64), registry=reg)
    eng.generate([[1, 2, 3]])
    text = reg.render()
    assert 'bigdl_tpu_tpot_seconds_count{kind="plain"}' in text
    assert "bigdl_tpu_roofline_util" not in text
    assert "bigdl_tpu_decode_ideal_ms" not in text
    perf = eng.perf_snapshot()
    assert perf["peak_hbm_gbps"] is None
    assert perf["decode"]["roofline_util"] is None
    assert perf["decode"]["decode_ms"] > 0

