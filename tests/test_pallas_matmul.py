"""Pallas dequant-matmul kernel vs the XLA fallback (interpret mode on CPU).

The same kernel runs compiled on TPU; interpret=True executes the identical
dataflow on CPU so CI covers kernel logic without TPU hardware (SURVEY.md §4
implication: simulatable test layer).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops.matmul import _q_matmul_xla, q_matmul_pallas
from bigdl_tpu.ops.quant import quantize


def _rand(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


@pytest.mark.parametrize(
    "qtype", ["sym_int4", "sym_int4:mxu", "asym_int4", "nf4", "nf3", "fp4",
              "sym_int8"])
@pytest.mark.parametrize("m", [1, 16, 64, 200, 256])
def test_pallas_matches_xla(qtype, m):
    """Decode rows (GEMV bodies), generic tiles, a prefill chunk's 256
    rows (one `bm` = 256 row tile) and a ragged 200 (padded to 208) —
    `sym_int4` in the canonical packing and, as `:mxu`, in the int4-dtype
    layout a TPU load gives it."""
    from bigdl_tpu.ops.quant import to_mxu_layout

    qtype, _, layout = qtype.partition(":")
    k, n = 256, 128
    x = _rand((m, k), seed=1) * 0.3
    w = _rand((k, n), seed=2) * 0.1
    qt = quantize(w, qtype)
    if layout == "mxu":
        qt = to_mxu_layout(qt)
        assert qt.data.dtype == jnp.int4
    got = q_matmul_pallas(x, qt, interpret=True)
    want = _q_matmul_xla(x, qt)
    assert got.shape == (m, n)
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_pallas_odd_batch_dims():
    k, n = 128, 128
    x = _rand((3, 5, k)) * 0.2
    qt = quantize(_rand((k, n), seed=3), "sym_int4")
    got = q_matmul_pallas(x, qt, interpret=True)
    want = _q_matmul_xla(x.reshape(15, k), qt).reshape(3, 5, n)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_pallas_large_k_tiling():
    # K large enough to need multiple K tiles
    k, n = 4096, 256
    x = _rand((8, k)) / np.sqrt(k)
    qt = quantize(_rand((k, n), seed=5), "sym_int4")
    got = q_matmul_pallas(x, qt, interpret=True)
    want = _q_matmul_xla(x, qt)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


@pytest.mark.parametrize(
    "qtype", ["sym_int4", "asym_int4", "nf4", "sym_int8"])
def test_gemv_variant_matches_generic(qtype):
    """The decode-GEMV specialization (m<=32) must match the GEMM's
    tiling (called directly: no rule sends decode rows there)
    bit-for-bit-close across qtypes and multi-tile K."""
    from bigdl_tpu.ops.pallas.dequant_matmul import (_q_matmul_generic,
                                                     gemm_tiles)
    from bigdl_tpu.ops.quant import get_qtype

    k, n = 1024, 256
    x = _rand((1, k), seed=7) * 0.3
    qt = quantize(_rand((k, n), seed=8) * 0.1, qtype)
    got = q_matmul_pallas(x, qt, interpret=True)           # gemv
    want = _q_matmul_generic(
        x.astype(jnp.bfloat16), qt, get_qtype(qtype), 1, k, n,
        gemm_tiles(get_qtype(qtype), k, n, 1), True, x.dtype)
    # different tile sweeps accumulate bf16 products in different orders
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_gemv_padded_k():
    """K not a block multiple: the padded tail must not disturb GEMV."""
    k, n = 200, 128           # pads to 224 (block 32)
    x = _rand((2, k), seed=9) * 0.2
    qt = quantize(_rand((k, n), seed=10) * 0.1, "sym_int4")
    got = q_matmul_pallas(x, qt, interpret=True)
    want = _q_matmul_xla(x, qt)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_gemv_mxu_layout_matches_reference():
    """Int4-dtype weights through the native-load GEMV body must match
    the dequant reference."""
    from bigdl_tpu.ops.quant import to_mxu_layout, from_mxu_layout

    k, n = 1024, 256
    x = _rand((1, k), seed=13) * 0.3
    qt = quantize(_rand((k, n), seed=14) * 0.1, "sym_int4")
    qm = to_mxu_layout(qt)
    assert qm.data.dtype == jnp.int4
    # round trip is bit-exact
    np.testing.assert_array_equal(
        np.asarray(from_mxu_layout(qm).data), np.asarray(qt.data))
    got = q_matmul_pallas(x, qm, interpret=True)
    want = _q_matmul_xla(x, qt)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_generic_tiles_mxu_layout_matches_reference():
    """Generic-tile (prefill-class M) path with int4-dtype weights."""
    from bigdl_tpu.ops.quant import to_mxu_layout

    k, n = 1024, 256
    x = _rand((64, k), seed=15) * 0.2
    qt = quantize(_rand((k, n), seed=16) * 0.1, "sym_int4")
    qm = to_mxu_layout(qt)
    got = q_matmul_pallas(x, qm, interpret=True)
    want = _q_matmul_xla(x, qt)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_mxu_layout_dequantize_exact():
    """dequantize(to_mxu_layout(qt)) == dequantize(qt) bit-exactly."""
    from bigdl_tpu.ops.quant import to_mxu_layout, dequantize

    qt = quantize(_rand((224, 128), seed=17) * 0.1, "sym_int4")
    np.testing.assert_array_equal(
        np.asarray(dequantize(to_mxu_layout(qt)), np.float32),
        np.asarray(dequantize(qt), np.float32))


def test_mxu_layout_layer_stacked():
    """Model params stack per-layer QTensors with a leading L axis; the
    layout transform must round-trip them (caught by verify r5)."""
    import dataclasses as dc

    from bigdl_tpu.ops.quant import to_mxu_layout, from_mxu_layout

    qt = quantize(_rand((256, 128), seed=18) * 0.1, "sym_int4")
    stacked = dc.replace(
        qt, data=jnp.stack([qt.data] * 3),
        scale=jnp.stack([qt.scale] * 3))
    qm = to_mxu_layout(stacked)
    assert qm.data.dtype == jnp.int4 and qm.data.shape == (3, 256, 128)
    back = from_mxu_layout(qm)
    np.testing.assert_array_equal(
        np.asarray(back.data), np.asarray(stacked.data))
    # [L, E, K//2, N] MoE expert stacks must pass through untouched —
    # the ragged MoE kernel reads the canonical packing
    experts = dc.replace(
        qt, data=jnp.stack([jnp.stack([qt.data] * 2)] * 3),
        scale=jnp.stack([jnp.stack([qt.scale] * 2)] * 3))
    assert to_mxu_layout(experts) is experts


@pytest.mark.parametrize("qtype,m,k", [
    ("sym_int4", 2, 256),          # GEMV, canonical body
    ("sym_int4:mxu", 2, 256),      # GEMV, int4-dtype body
    ("asym_int4", 2, 256),         # GEMV with a zero plane
    ("sym_int4:mxu", 2, 200),      # GEMV, K padded to 224
    ("sym_int4:mxu", 64, 256),     # GEMM, integer codes
    ("asym_int4", 64, 256),        # GEMM, nibbles and a zero plane
    ("nf4", 64, 200),              # GEMM, codebook, K padded to 256
])
def test_stack_read_in_place_equals_each_layers_slice(qtype, m, k):
    """A `StackedQ` inside a `lax.scan` over the layer index (what a
    scanned model hands to `linear`): the kernel reads layer 0, 1, 2 of
    the `[3, K, N]` stack where it lies, and the result equals the 2-D
    call on that layer's slice bit for bit."""
    from bigdl_tpu.ops.matmul import StackedQ, q_matmul_pallas_impl
    from bigdl_tpu.ops.quant import to_mxu_layout

    qtype, _, layout = qtype.partition(":")
    n = 128
    x = _rand((m, k), seed=21) * 0.3
    layers = [quantize(_rand((k, n), seed=30 + i) * 0.1, qtype)
              for i in range(3)]
    if layout == "mxu":
        layers = [to_mxu_layout(q) for q in layers]
    stack = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    assert stack.data.ndim == 3 and stack.shape == (k, n)

    @jax.jit
    def scanned(x, stack):
        def step(_, i):
            return None, q_matmul_pallas_impl(x, StackedQ(stack, i),
                                              interpret=True)
        return jax.lax.scan(step, None, jnp.arange(3, dtype=jnp.int32))[1]

    got = np.asarray(scanned(x, stack), np.float32)
    for i, q in enumerate(layers):
        want = np.asarray(q_matmul_pallas(x, q, interpret=True), np.float32)
        np.testing.assert_array_equal(got[i], want)
    assert not np.array_equal(got[0], got[1])


def test_stacked_xla_plan_takes_the_layer_and_is_differentiable():
    """Off a kernel plan a `StackedQ` is the layer taken out of the
    stack (what the scan did itself), forward and backward."""
    from bigdl_tpu.ops.matmul import StackedQ, q_matmul

    k, n = 64, 128
    x = _rand((4, k), seed=41) * 0.3
    layers = [quantize(_rand((k, n), seed=50 + i) * 0.1, "sym_int4")
              for i in range(2)]
    stack = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    view = StackedQ(stack, jnp.int32(1))
    np.testing.assert_array_equal(
        np.asarray(q_matmul(x, view, backend="xla")),
        np.asarray(q_matmul(x, layers[1], backend="xla")))
    grad = jax.grad(lambda x, w: jnp.sum(q_matmul(x, w, backend="xla") ** 2))
    got, want = jax.jit(lambda x: (grad(x, view), grad(x, layers[1])))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
