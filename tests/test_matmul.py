"""Tests for the quantized matmul (XLA fallback path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.matmul import q_linear, q_matmul
from bigdl_tpu.ops.quant import dequantize, quantize


def _rand(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


@pytest.mark.parametrize("qtype", ["sym_int4", "nf4", "sym_int8", "fp8_e4m3"])
@pytest.mark.parametrize("m", [1, 8, 64])
def test_q_matmul_matches_dequant_dot(qtype, m):
    k, n = 256, 128
    x = _rand((m, k), seed=1) * 0.1
    w = _rand((k, n), seed=2) * 0.05
    qt = quantize(w, qtype)
    got = q_matmul(x, qt, backend="xla")
    want = x.astype(jnp.bfloat16) @ dequantize(qt, jnp.bfloat16)
    assert got.shape == (m, n)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_q_matmul_quality_vs_float():
    # end-to-end quality: int4 matmul ≈ float matmul within quant noise
    k, n, m = 512, 256, 4
    x = _rand((m, k), seed=3) / np.sqrt(k)
    w = _rand((k, n), seed=4)
    qt = quantize(w, "sym_int4")
    got = np.asarray(q_matmul(x, qt), np.float32)
    want = np.asarray(x @ w, np.float32)
    rel = np.abs(got - want).mean() / np.abs(want).mean()
    assert rel < 0.12, rel


def test_q_linear_bias_and_batch_dims():
    k, n = 128, 64
    x = _rand((2, 3, k))
    w = _rand((k, n))
    b = _rand((n,), seed=9)
    qt = quantize(w, "sym_int4")
    y = q_linear(x, qt, bias=b)
    assert y.shape == (2, 3, n)
    want = x @ dequantize(qt, jnp.float32) + b
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(want), rtol=3e-2, atol=6e-2
    )


def test_q_matmul_under_jit():
    k, n = 128, 128
    x = _rand((4, k))
    qt = quantize(_rand((k, n), seed=7), "sym_int4")

    @jax.jit
    def f(x, qt):
        return q_matmul(x, qt)

    y = f(x, qt)
    assert y.shape == (4, n)


def test_auto_dispatch_m_threshold(monkeypatch):
    """Auto dispatch on a TPU target: decode rows, a prefill chunk's 256
    rows and every row count up to the crossover measured on the chip
    (`matmul.PALLAS_MAX_ROWS`) take the Pallas kernel; rows above it
    (QLoRA's 8192-row forward) take the XLA dequantize-then-dot plan and
    are counted as a rule. A forced backend ignores the row count either
    way."""
    import bigdl_tpu.ops.pallas.dequant_matmul as dq
    import bigdl_tpu.ops.probing as probing
    from bigdl_tpu.config import set_flags
    from bigdl_tpu.ops.matmul import PALLAS_MAX_ROWS, _q_matmul_xla

    w = quantize(_rand((64, 128)) * 0.05, "sym_int4")
    seen, ruled = [], []

    def fake_kernel(x, wq, gemv, tiles, **kw):
        assert gemv == (x.shape[0] <= dq.GEMV_MAX_M) and tiles == (64, 128)
        seen.append(int(x.shape[0]))
        return _q_matmul_xla(x, wq)

    monkeypatch.setattr(dq, "q_matmul_kernel", fake_kernel)
    monkeypatch.setattr(probing, "record_dispatch_rule", ruled.append)
    crossover = PALLAS_MAX_ROWS
    assert 256 <= crossover < 8192
    ones = lambda m: jnp.ones((m, 64), jnp.bfloat16)  # noqa: E731
    set_flags(aot_target="tpu")
    try:
        for m in (8, 32, 200, 256, crossover):        # the kernel's
            q_matmul(ones(m), w)
        assert seen == [8, 32, 200, 256, crossover] and not ruled
        for m in (crossover + 1, 8192):               # XLA's, by rule
            q_matmul(ones(m), w)
        assert seen == [8, 32, 200, 256, crossover]
        assert ruled == ["matmul", "matmul"]
        # forced backends ignore the row count
        q_matmul(ones(8192), w, backend="pallas")
        q_matmul(ones(8), w, backend="xla")
        assert seen[-1] == 8192 and len(seen) == 6 and len(ruled) == 2
    finally:
        set_flags(aot_target=None)


# What the PARENT of the PR that wrote `select_matmul` (07c3ea7) chose,
# read from its `_q_matmul_dispatch` under aot_target="tpu", at every
# quantized linear of the benchmark's three configurations
# (tools/qmatmul_ab.SHAPES) in the layout a TPU load gives sym_int4:
# [K, N] -> the plan at 8 rows (decode), at 256 (a prefill chunk). At
# 8192 rows (QLoRA's forward) every one was plain XLA. ChatGLM2's
# K = 13696 = 2^7 x 107 has no GEMV tiling (fused XLA) and one full-K
# GEMM tile.
_PARENT_CHOSE = {
    (4096, 6144): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (4096, 4096): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (4096, 28672): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (14336, 4096): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (4096, 4608): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (4096, 27392): (('gemv_mxu', (4096, 256)), ('gemm', (4096, 256))),
    (13696, 4096): (('xla_fused', None), ('gemm', (13696, 128))),
    (5120, 1536): (('gemv_mxu', (5120, 256)), ('gemm', (5120, 256))),
    (1536, 24576): (('gemv_mxu', (1536, 512)), ('gemm', (1536, 512))),
    (5120, 640): (('gemv_mxu', (5120, 128)), ('gemm', (5120, 128))),
    (16384, 5120): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (5120, 3072): (('gemv_mxu', (5120, 256)), ('gemm', (5120, 256))),
    (3072, 5120): (('gemv_mxu', (3072, 256)), ('gemm', (3072, 512))),
    (5120, 12288): (('gemv_mxu', (5120, 256)), ('gemm', (5120, 256))),
    (12288, 5120): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
}
# EvaByte's linears (PR 39; merged q/k/v and gate/up, the down
# projection, the eight prediction heads' columns): [K, N] -> the plan at
# 6 rows (its cell's decode step) and at 256 and 1024 (its cell's prefill
# chunk, and the largest the GEMM takes). Every one takes a kernel;
# K = 11008 = 2^8 x 43 tiles only in 256-row K blocks.
_EVABYTE_CHOSE = {
    (4096, 12288): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (4096, 22016): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (11008, 4096): (('gemv_mxu', (256, 512)), ('gemm', (256, 512))),
    (4096, 2560): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
}
# MiMo-V2.5's linears (PR 45): merged q/k/v of a full and of a window
# layer, W_o, the dense layer's gate / up and down, the head's slice
_MIMO_CHOSE = {
    (4096, 13568): (('gemv_mxu', (4096, 256)), ('gemm', (4096, 256))),
    (4096, 14848): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (8192, 4096): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (4096, 16384): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (16384, 4096): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (4096, 19072): (('gemv_mxu', (4096, 128)), ('gemm', (4096, 128))),
}
# Trinity-Mini's linears (PR 49): q / k / v and the gate merged, W_o, a
# dense layer's gate / up and down, the shared expert's, the head's slice:
# every one a kernel plan, none falls to XLA
_AFMOE_CHOSE = {
    (2048, 9216): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (4096, 2048): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (2048, 6144): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (6144, 2048): (('gemv_mxu', (2048, 512)), ('gemm', (6144, 256))),
    (2048, 1024): (('gemv_mxu', (2048, 512)), ('gemm', (2048, 512))),
    (1024, 2048): (('gemv_mxu', (1024, 512)), ('gemm', (1024, 512))),
    (2048, 50048): (('gemv_mxu', (2048, 128)), ('gemm', (2048, 128))),
}
# SDAR-30B-A3B's linears at a block pass's 64 rows (16 slots x 4) and a
# 1024-row chunk (PR 53): q / k / v merged, W_o, and the head's slice as
# SERVED, padded from 37,984 columns to 38,016 (`sdar_moe.pad_head`):
# every one the GEMM kernel; the unpadded head has no tiling and would
# take the XLA plan, which dequantizes it whole every pass
_SDAR_CHOSE = {
    (2048, 5120): (('gemm', (2048, 512)), ('gemm', (2048, 512))),
    (4096, 2048): (('gemm', (4096, 512)), ('gemm', (2048, 512))),
    (2048, 38016): (('gemm', (2048, 128)), ('gemm', (2048, 128))),
    (2048, 37984): (('xla', None), ('xla', None)),
}
_TPU = dict(int4_layout=True, spmd=False, tpu=True)
_CANON = dict(_TPU, int4_layout=False)
# the rules, one case each: (qtype, rows, K, N, what the call sees, plan)
_RULES = [
    # the GEMV's last row and the GEMM's first; the crossover
    ("sym_int4", 32, 4096, 4096, _TPU, ("gemv_mxu", (2048, 512))),
    ("sym_int4", 33, 4096, 4096, _TPU, ("gemm", (4096, 512))),
    ("sym_int4", 1024, 4096, 4096, _TPU, ("gemm", (2048, 512))),
    ("sym_int4", 1025, 4096, 4096, _TPU, ("xla", None)),
    # the body follows from the layout: canonical packing, codebooks,
    # zero points and int8 take the std body
    ("sym_int4", 8, 4096, 4096, _CANON, ("gemv_std", (2048, 512))),
    ("nf4", 8, 4096, 4096, _CANON, ("gemv_std", (2048, 512))),
    ("asym_int4", 8, 4096, 4096, _CANON, ("gemv_std", (2048, 512))),
    ("sym_int8", 8, 4096, 4096, _CANON, ("gemv_std", (2048, 512))),
    ("nf4", 256, 4096, 4096, _CANON, ("gemm", (2048, 512))),
    # a tp=4 shard of ff = 11008: legal only as ONE full-K block
    ("sym_int4", 8, 2752, 4096, _CANON, ("gemv_std", (2752, 256))),
    # no legal tiling: fused XLA at decode rows for the qtypes it
    # covers, chunked for the rest against a 7B-class weight, dense above
    ("nf4", 8, 13696, 4096, _CANON, ("xla_fused", None)),
    ("fp4", 8, 13696, 4096, _CANON, ("xla_chunked", None)),
    ("sym_int4", 256, 32224, 4096, _TPU, ("xla", None)),
    # qtypes the kernels do not cover: the heavy ones chunked, the
    # others chunked at decode rows against a 7B-class weight
    ("q2_k", 8, 4096, 11008, _CANON, ("xla_chunked", None)),
    ("q2_k", 256, 4096, 11008, _CANON, ("xla_chunked", None)),
    ("sym_int5", 8, 4096, 11008, _CANON, ("xla_chunked", None)),
    ("sym_int5", 64, 4096, 11008, _CANON, ("xla", None)),
    ("sym_int5", 8, 4096, 4096, _CANON, ("xla", None)),
    # GSPMD operands: Mosaic kernels cannot be partitioned
    ("sym_int4", 8, 4096, 4096, dict(_TPU, spmd=True), ("xla_fused", None)),
    ("sym_int4", 256, 4096, 4096, dict(_TPU, spmd=True), ("xla", None)),
    # a CPU: XLA, never fused
    ("sym_int4", 8, 4096, 4096, dict(_TPU, tpu=False), ("xla", None)),
    ("sym_int4", 256, 4096, 4096, dict(_TPU, tpu=False), ("xla", None)),
    # forced backends take their plan whatever the target and the rows
    ("sym_int4", 8192, 4096, 4096, dict(_TPU, tpu=False, backend="pallas"),
     ("gemm", (2048, 512))),
    ("sym_int4", 8, 4096, 4096, dict(_TPU, backend="xla"), ("xla", None)),
    ("sym_int4", 256, 4096, 4096, dict(_TPU, backend="xla_fused"),
     ("xla_fused", None)),
    ("q2_k", 8, 4096, 4096, dict(_CANON, backend="xla_fused"),
     ("xla_chunked", None)),
    # ... and forced "pallas" raises where the kernel has no plan
    ("sym_int4", 8, 13696, 4096, dict(_TPU, backend="pallas"), None),
    ("sym_int5", 8, 4096, 4096, dict(_CANON, backend="pallas"), None),
]


@pytest.mark.parametrize("qtype,rows,k,n,sees,want", [
    ("sym_int4", rows, k, n, _TPU, plan)
    for (k, n), at in _PARENT_CHOSE.items()
    for rows, plan in zip((8, 256, 8192), (*at, ("xla", None)))] + [
    ("sym_int4", rows, k, n, _TPU, plan)
    for (k, n), at in _EVABYTE_CHOSE.items()
    for rows, plan in zip((6, 256, 1024), (*at, at[1]))] + [
    ("sym_int4", rows, k, n, _TPU, plan)
    for (k, n), at in {**_MIMO_CHOSE, **_AFMOE_CHOSE}.items()
    for rows, plan in zip((16, 1024), at)] + [
    ("sym_int4", rows, k, n, _TPU, plan)
    for (k, n), at in _SDAR_CHOSE.items()
    for rows, plan in zip((64, 1024), at)] + _RULES)
def test_kernel_selection_table(qtype, rows, k, n, sees, want):
    """`select_matmul` is the one place a quantized linear's plan is
    chosen, from what the call can see; at every linear of the three
    benchmark configurations, at decode, chunk and training rows, it
    chooses what the parent's flags and fall-throughs chose."""
    from bigdl_tpu.ops.matmul import MatmulPlan, select_matmul

    if want is None:
        with pytest.raises(NotImplementedError, match="no Pallas plan"):
            select_matmul(qtype, rows, k, n, **sees)
        return
    assert select_matmul(qtype, rows, k, n, **sees) == MatmulPlan(*want)


def test_selection_table_covers_every_benchmark_linear(monkeypatch):
    import importlib.util
    import pathlib
    import sys

    monkeypatch.setattr(sys, "path", list(sys.path))   # the tool extends it
    spec = importlib.util.spec_from_file_location(
        "qmatmul_ab", pathlib.Path(__file__).parents[1] / "tools"
        / "qmatmul_ab.py")
    ab = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ab)
    assert set(_PARENT_CHOSE) == {s for fam in ab.SHAPES.values()
                                  for s in fam}
    assert len(_PARENT_CHOSE) == 15


@pytest.mark.parametrize("qtype", ["q2_k", "iq2_xxs", "iq1_s"])
def test_chunked_xla_matmul_matches_direct(qtype):
    """Heavy-decode formats route the XLA fallback through N-chunked
    dequant (bounded temp — unchunked, a mixtral-8x7B in iq2_xxs
    compiled to 9GB of temp and OOM'd a 16GB v5e). The chunked result
    must agree with the direct dequantize-then-dot within bf16
    rounding (different f32 reduction shapes; not bit-identical)."""
    from bigdl_tpu.ops.matmul import (_HEAVY_DECODE_QTYPES,
                                      _q_matmul_xla_chunked)
    from bigdl_tpu.ops.quant import dequantize, quantize

    assert qtype in _HEAVY_DECODE_QTYPES
    rng = np.random.default_rng(0)
    k, n = 512, 768   # small enough to encode quickly; 3 chunks at 256
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    wq = quantize(w, qtype)
    x = jnp.asarray(rng.standard_normal((4, k)).astype(np.float32)
                    ).astype(jnp.bfloat16)

    y_chunk = _q_matmul_xla_chunked(x, wq, min_elems=0,
                                    target_cols=256)
    assert y_chunk is not None

    ref = np.asarray(
        x.astype(jnp.float32) @ dequantize(wq, dtype=jnp.bfloat16
                                           ).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(y_chunk, np.float32), ref,
                               rtol=2e-2, atol=2e-2)


def test_chunked_backward_matches_direct():
    """The chunked backward (heavy-decode formats under AD) introduces
    no error beyond the shared bf16 weight rounding."""
    from bigdl_tpu.ops.matmul import _q_matmul_bwd_chunked
    from bigdl_tpu.ops.quant import dequantize, quantize

    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal((512, 768)).astype(np.float32)
                    * 0.1)
    wq = quantize(w, "q2_k")
    dy = jnp.asarray(rng.standard_normal((4, 768)).astype(np.float32))

    g_chunk = np.asarray(_q_matmul_bwd_chunked(
        dy, wq, min_elems=0, target_cols=256))
    wd = dequantize(wq, dtype=jnp.float32)
    g_exact = np.asarray(dy @ wd.T)
    g_direct = np.asarray(jnp.dot(
        dy.astype(jnp.bfloat16), dequantize(wq, dtype=jnp.bfloat16).T,
        preferred_element_type=jnp.float32))

    def rel(a):
        return np.max(np.abs(a - g_exact) / np.maximum(np.abs(g_exact), 1.0))

    # chunked error must be the same class as the direct bf16 path's
    assert rel(g_chunk) <= rel(g_direct) * 1.5 + 1e-4, \
        (rel(g_chunk), rel(g_direct))
