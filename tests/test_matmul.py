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
    (`RuntimeFlags.matmul_pallas_max_m`) take the Pallas kernel; rows
    above it (QLoRA's 8192-row forward) take the XLA
    dequantize-then-dot plan and are counted as a rule. A forced backend
    ignores the row count either way."""
    import bigdl_tpu.ops.pallas.dequant_matmul as dq
    import bigdl_tpu.ops.probing as probing
    from bigdl_tpu.config import flags, set_flags
    from bigdl_tpu.ops.matmul import _q_matmul_xla

    w = quantize(_rand((64, 128)) * 0.05, "sym_int4")
    seen, ruled = [], []

    def fake_impl(x, wq, **kw):
        seen.append(int(x.shape[0]))
        return _q_matmul_xla(x, wq)

    monkeypatch.setattr(dq, "q_matmul_pallas_impl", fake_impl)
    monkeypatch.setattr(probing, "record_dispatch_rule", ruled.append)
    crossover = flags().matmul_pallas_max_m
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
        # the flag moves the crossover
        set_flags(matmul_pallas_max_m=128)
        q_matmul(ones(256), w)
        assert len(seen) == 6 and len(ruled) == 3
    finally:
        set_flags(aot_target=None, matmul_pallas_max_m=crossover)


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
