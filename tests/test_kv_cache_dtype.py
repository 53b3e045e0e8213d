"""Block-scaled int8/int4 KV cache: quantize-on-append round trips, fused
dequant attention kernels (resident + S-blocked decode, prefill flash) vs
the XLA reference, storage-footprint guarantees, the kv_cache_dtype knob
plumbing (deprecated boolean alias, env validation), and the serving
engine end-to-end (including prefix-cache seeding of quantized caches)."""

import dataclasses
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.config import set_flags
from bigdl_tpu.ops import kvcache as kvc
from bigdl_tpu.ops.attention import sdp_attention
from bigdl_tpu.ops.pallas import decode_attention as DA
from bigdl_tpu.ops.pallas.prefill_attention import prefill_attention_pallas

# accuracy budget vs the bf16 cache (documented in README): attention
# outputs are softmax-weighted averages of V rows, so per-element error
# stays well under the raw code granularity (scale/2 = amax/254 for int8,
# amax/14 for int4)
TOL_VS_BF16 = {"int8": 0.1, "int4": 0.35}
# kernel-vs-XLA on the SAME codes must agree tightly (both dequant the
# same integers; only accumulation order differs)
TOL_VS_XLA = 2e-2


def _mk(b, s, h, hkv, hd, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)).astype(np.float32),
                    jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, hd)).astype(np.float32),
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, hd)).astype(np.float32),
                    jnp.bfloat16)
    return q, k, v


def _xla_ref(q, k, v, pos, k_scale=None, v_scale=None):
    try:
        set_flags(attention_backend="xla")
        return sdp_attention(q, k, v, pos, k_scale=k_scale,
                             v_scale=v_scale)
    finally:
        set_flags(attention_backend="auto")


# -- dtype knob / deprecated alias ------------------------------------------

def test_resolve_kv_cache_dtype():
    r = kvc.resolve_kv_cache_dtype
    assert r("int8") == "int8"
    assert r("INT4 ") == "int4"
    assert r("bfloat16") == "bf16"
    assert r("fp8") == "fp8_e5m2"
    assert r("e5m2") == "fp8_e5m2"
    assert r(None) == "bf16"
    assert r(False) == "bf16"
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        r("int2")


def test_deprecated_boolean_warns_once():
    kvc._warned_quantized_alias = False
    with pytest.warns(DeprecationWarning, match="fp8_e5m2"):
        assert kvc.resolve_kv_cache_dtype(True) == "fp8_e5m2"
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert kvc.resolve_kv_cache_dtype(True) == "fp8_e5m2"


def test_default_kv_cache_dtype_precedence():
    from bigdl_tpu.config import default_kv_cache_dtype, flags

    old = flags()
    try:
        set_flags(kv_cache_dtype="int8", quantize_kv_cache=False)
        assert default_kv_cache_dtype() == "int8"
        # explicit dtype wins over the deprecated boolean
        set_flags(kv_cache_dtype="int4", quantize_kv_cache=True)
        assert default_kv_cache_dtype() == "int4"
        kvc._warned_quantized_alias = True   # silence the alias warning
        set_flags(kv_cache_dtype="bf16", quantize_kv_cache=True)
        assert default_kv_cache_dtype() == "fp8_e5m2"
        set_flags(kv_cache_dtype="bf16", quantize_kv_cache=False)
        assert default_kv_cache_dtype() == "bf16"
    finally:
        set_flags(kv_cache_dtype=old.kv_cache_dtype,
                  quantize_kv_cache=old.quantize_kv_cache)


def test_env_check_validates_kv_dtype(monkeypatch):
    from bigdl_tpu.utils.env_check import collect

    monkeypatch.setenv("BIGDL_TPU_KV_CACHE_DTYPE", "int8")
    info = collect()
    assert info["kv_cache_dtype"] == {"value": "int8", "valid": True}
    monkeypatch.setenv("BIGDL_TPU_KV_CACHE_DTYPE", "banana")
    info = collect()
    assert info["kv_cache_dtype"]["valid"] is False
    assert "int4" in info["kv_cache_dtype"]["choices"]


# -- quantize / append / read round trips -----------------------------------

@pytest.mark.parametrize("name", ["int8", "int4"])
def test_quantize_roundtrip_error_bound(name):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 9, 3, 64)), jnp.float32)
    codes, scale = kvc.quantize_kv(x, kvc.KV_CACHE_DTYPES[name])
    back = kvc.dequantize_kv(codes, scale, jnp.float32)
    # symmetric rounding: error per element <= scale/2 of ITS vector
    err = np.abs(np.asarray(back) - np.asarray(x))
    bound = np.asarray(scale)[..., None] * 0.5 + 1e-6
    assert (err <= bound).all()
    # zero vectors round-trip exactly
    z = jnp.zeros((1, 2, 1, 8), jnp.float32)
    zc, zs = kvc.quantize_kv(z, kvc.KV_CACHE_DTYPES[name])
    assert np.asarray(zs).max() == 0.0
    assert np.abs(np.asarray(
        kvc.dequantize_kv(zc, zs, jnp.float32))).max() == 0.0


@pytest.mark.parametrize("name", ["int8", "int4"])
def test_append_read_unaligned_positions(name):
    cache = kvc.init_cache(2, 1, 32, 3, 64, kv_cache_dtype=name)
    rng = np.random.default_rng(7)
    k1 = jnp.asarray(rng.standard_normal((1, 5, 3, 64)), jnp.bfloat16)
    v1 = jnp.asarray(rng.standard_normal((1, 5, 3, 64)), jnp.bfloat16)
    ck, cv, cks, cvs = kvc.update_layer(
        cache.k, cache.v, 0, k1, v1, jnp.asarray(0, jnp.int32),
        cache.k_scale, cache.v_scale)
    kd0, _ = kvc.read_layer(ck, cv, 0, cache_ks=cks, cache_vs=cvs)
    # append 3 more at the unaligned offset 5
    k2 = jnp.asarray(rng.standard_normal((1, 3, 3, 64)), jnp.bfloat16)
    v2 = jnp.asarray(rng.standard_normal((1, 3, 3, 64)), jnp.bfloat16)
    ck, cv, cks, cvs = kvc.update_layer(
        ck, cv, 0, k2, v2, jnp.asarray(5, jnp.int32), cks, cvs)
    kd, vd = kvc.read_layer(ck, cv, 0, cache_ks=cks, cache_vs=cvs)
    tol = TOL_VS_BF16[name]
    np.testing.assert_allclose(np.asarray(kd, np.float32)[:, :5],
                               np.asarray(k1, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(kd, np.float32)[:, 5:8],
                               np.asarray(k2, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(vd, np.float32)[:, 5:8],
                               np.asarray(v2, np.float32), atol=tol)
    # the second append must NOT requantize (so not perturb) older tokens
    np.testing.assert_array_equal(np.asarray(kd, np.float32)[:, :5],
                                  np.asarray(kd0, np.float32)[:, :5])


def test_append_read_per_slot_positions():
    cache = kvc.init_cache(1, 2, 96, 2, 64, kv_cache_dtype="int8",
                           per_slot_pos=True)
    rng = np.random.default_rng(9)
    kn = jnp.asarray(rng.standard_normal((2, 1, 2, 64)), jnp.bfloat16)
    vn = jnp.asarray(rng.standard_normal((2, 1, 2, 64)), jnp.bfloat16)
    pos = jnp.asarray([3, 77], jnp.int32)
    ck, cv, cks, cvs = kvc.update_layer(
        cache.k, cache.v, 0, kn, vn, pos, cache.k_scale, cache.v_scale)
    kd, vd = kvc.read_layer(ck, cv, 0, cache_ks=cks, cache_vs=cvs)
    kd = np.asarray(kd, np.float32)
    np.testing.assert_allclose(kd[0, 3], np.asarray(kn, np.float32)[0, 0],
                               atol=2e-2)
    np.testing.assert_allclose(kd[1, 77], np.asarray(kn, np.float32)[1, 0],
                               atol=2e-2)
    # neighbouring rows untouched
    assert np.abs(kd[0, 4]).max() == 0.0
    assert np.abs(kd[1, 76]).max() == 0.0


@pytest.mark.parametrize("s_new", [1, 4])
@pytest.mark.parametrize("name", ["bf16", "fp8_e5m2", "int8", "int4"])
def test_per_slot_update_equals_per_row_reference(name, s_new):
    """Per-slot `update_layer` (one scatter on the stack) against a plain
    per-row loop: middle layer, unequal offsets, every storage dtype; all
    other rows, layers and scales keep their bits."""
    layers, b, s, hkv, hd, layer = 3, 3, 64, 2, 64, 1
    rng = np.random.default_rng(21)
    cache = kvc.init_cache(layers, b, s, hkv, hd, kv_cache_dtype=name,
                           per_slot_pos=True)
    scaled = cache.k_scale is not None

    def noise(a):       # a cache that is NOT zero: untouched must mean equal
        return jnp.asarray(rng.integers(-3, 4, a.shape), a.dtype)

    planes = [noise(cache.k), noise(cache.v)]
    if scaled:
        planes += [noise(cache.k_scale), noise(cache.v_scale)]
    kn = jnp.asarray(rng.standard_normal((b, s_new, hkv, hd)), jnp.bfloat16)
    vn = jnp.asarray(rng.standard_normal((b, s_new, hkv, hd)), jnp.bfloat16)
    pos = jnp.asarray([7, 0, s - s_new], jnp.int32)
    # op by op, like the reference below: under one jit XLA may turn the
    # scale's division into a reciprocal multiply (one ulp of a scale)
    got = kvc.update_layer(planes[0], planes[1], jnp.int32(layer),
                           kn, vn, pos, *planes[2:])
    assert len(got) == len(planes)

    if scaled:
        (kq, ksc), (vq, vsc) = (kvc.quantize_kv(kn, cache.k.dtype),
                                kvc.quantize_kv(vn, cache.v.dtype))
        rows = [kq, vq, ksc, vsc]
    else:
        rows = [kn.astype(cache.k.dtype), vn.astype(cache.v.dtype)]
    for plane, new, out in zip(planes, rows, got):
        want = np.array(plane.astype(jnp.float32))
        for bi in range(b):
            for i in range(s_new):
                want[layer, bi, int(pos[bi]) + i] = np.asarray(
                    new[bi, i].astype(jnp.float32))
        assert out.dtype == plane.dtype
        np.testing.assert_array_equal(
            np.asarray(out.astype(jnp.float32)), want)


@pytest.mark.parametrize("name", ["bf16", "int8"])
def test_per_slot_update_drops_rows_past_the_end(name):
    """A slot whose `pos + S_new` runs past the cache: the rows that fit
    land, the rest are dropped (the scatter's out-of-bounds mode; its
    indices stay unique and sorted), and no other row of any plane moves.
    The engine never asks for it (a slot at max_seq is finished first)."""
    layers, b, s, hkv, hd, layer, s_new = 2, 3, 32, 2, 64, 1, 4
    rng = np.random.default_rng(22)
    cache = kvc.init_cache(layers, b, s, hkv, hd, kv_cache_dtype=name,
                           per_slot_pos=True)
    planes = [jnp.asarray(rng.integers(-3, 4, a.shape), a.dtype)
              for a in (cache.k, cache.v, cache.k_scale, cache.v_scale)
              if a is not None]
    kn = jnp.asarray(rng.standard_normal((b, s_new, hkv, hd)), jnp.bfloat16)
    pos = jnp.asarray([5, s - 2, s], jnp.int32)   # fits, half fits, none
    # op by op, as above (a scale's ulp under one jit)
    got = kvc.update_layer(planes[0], planes[1], jnp.int32(layer),
                           kn, kn, pos, *planes[2:])
    alone = kvc.update_layer(
        *(p_[:, :1] for p_ in planes[:2]), jnp.int32(layer), kn[:1], kn[:1],
        pos[:1], *(p_[:, :1] for p_ in planes[2:]))
    for plane, out, one in zip(planes, got, alone):
        want = np.array(plane.astype(jnp.float32))
        out = np.asarray(out.astype(jnp.float32))
        new = np.asarray(one.astype(jnp.float32))[layer, 0, 5:5 + s_new]
        want[layer, 0, 5:5 + s_new] = new     # the rows as slot 0 stored them
        want[layer, 1, s - 2:] = out[layer, 1, s - 2:]
        np.testing.assert_array_equal(out, want)
    # slot 1's two rows that fit are rows 0 and 1 of ITS new block
    k_out = np.asarray(got[0].astype(jnp.float32))
    if name == "bf16":
        np.testing.assert_array_equal(
            k_out[layer, 1, s - 2:], np.asarray(kn[1, :2], np.float32))
    else:
        codes, _ = kvc.quantize_kv(kn, cache.k.dtype)
        np.testing.assert_array_equal(
            k_out[layer, 1, s - 2:], np.asarray(codes[1, :2], np.float32))


# -- fused dequant kernels vs XLA -------------------------------------------

@pytest.mark.parametrize("name", ["int8", "int4"])
@pytest.mark.parametrize("h,hkv,hd", [(8, 2, 64), (4, 4, 128)])
def test_decode_resident_scaled(name, h, hkv, hd):
    q, k, v = _mk(2, 128, h, hkv, hd, seed=11)
    kq, ks = kvc.quantize_kv(k, kvc.KV_CACHE_DTYPES[name])
    vq, vs = kvc.quantize_kv(v, kvc.KV_CACHE_DTYPES[name])
    pos = jnp.asarray(97, jnp.int32)
    got = DA.decode_attention_pallas(q, kq[None], vq[None], pos, hd ** -0.5,
                                     interpret=True, k_scale=ks[None],
                                     v_scale=vs[None])
    ref = _xla_ref(q, kq, vq, pos, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=TOL_VS_XLA, atol=TOL_VS_XLA)
    # and within the documented budget of the unquantized bf16 cache
    full = _xla_ref(q, k, v, pos)
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(full, np.float32)).max() < TOL_VS_BF16[name]


@pytest.mark.parametrize("name", ["int8", "int4"])
def test_decode_blocked_scaled(name, monkeypatch):
    monkeypatch.setattr(DA, "_BLOCK_ROWS", 256)
    s = 768 if name == "int8" else 896   # distinct shapes: fresh traces
    q, k, v = _mk(2, s, 4, 2, 64, seed=12)
    kq, ks = kvc.quantize_kv(k, kvc.KV_CACHE_DTYPES[name])
    vq, vs = kvc.quantize_kv(v, kvc.KV_CACHE_DTYPES[name])
    for pos_v in (s - 1, 300, 0):
        pos = jnp.asarray(pos_v, jnp.int32)
        got = DA.decode_attention_pallas(q, kq[None], vq[None], pos,
                                         64 ** -0.5, interpret=True,
                                         k_scale=ks[None], v_scale=vs[None])
        ref = _xla_ref(q, kq, vq, pos, k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=TOL_VS_XLA, atol=TOL_VS_XLA,
                                   err_msg=f"pos={pos_v}")


def test_decode_blocked_scaled_per_slot(monkeypatch):
    monkeypatch.setattr(DA, "_BLOCK_ROWS", 256)
    q, k, v = _mk(3, 640, 4, 4, 64, seed=13)
    kq, ks = kvc.quantize_kv(k, jnp.int8)
    vq, vs = kvc.quantize_kv(v, jnp.int8)
    pos = jnp.asarray([5, 300, 639], jnp.int32)
    got = DA.decode_attention_pallas(q, kq[None], vq[None], pos, 64 ** -0.5,
                                     interpret=True, k_scale=ks[None],
                                     v_scale=vs[None])
    ref = _xla_ref(q, kq, vq, pos, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=TOL_VS_XLA, atol=TOL_VS_XLA)


def test_decode_resident_scaled_per_slot():
    q, k, v = _mk(2, 128, 4, 2, 64, seed=14)
    kq, ks = kvc.quantize_kv(k, jnp.int8)
    vq, vs = kvc.quantize_kv(v, jnp.int8)
    pos = jnp.asarray([9, 127], jnp.int32)
    got = DA.decode_attention_pallas(q, kq[None], vq[None], pos, 64 ** -0.5,
                                     interpret=True, k_scale=ks[None],
                                     v_scale=vs[None])
    ref = _xla_ref(q, kq, vq, pos, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=TOL_VS_XLA, atol=TOL_VS_XLA)


@pytest.mark.parametrize("name", ["int8", "int4"])
def test_prefill_flash_scaled(name):
    rng = np.random.default_rng(15)
    sq, smax, h, hkv, hd = 128, 256, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((1, sq, h, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, smax, hkv, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, smax, hkv, hd)), jnp.bfloat16)
    kq, ks = kvc.quantize_kv(k, kvc.KV_CACHE_DTYPES[name])
    vq, vs = kvc.quantize_kv(v, kvc.KV_CACHE_DTYPES[name])
    pos = jnp.asarray(sq - 1, jnp.int32)
    got = prefill_attention_pallas(q, kq, vq, pos, hd ** -0.5,
                                   interpret=True, k_scale=ks, v_scale=vs)
    ref = _xla_ref(q, kq, vq, pos, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=TOL_VS_XLA, atol=TOL_VS_XLA)


def test_geometry_gate_requires_scales():
    q, k, v = _mk(1, 128, 4, 2, 64)
    kq, ks = kvc.quantize_kv(k, jnp.int8)
    vq, _ = kvc.quantize_kv(v, jnp.int8)
    pos = jnp.asarray(0, jnp.int32)
    # int8 codes WITHOUT scales must not dispatch to the kernel
    assert not DA.decode_attention_supported(q, kq, vq, pos, 0.125,
                                             None, None, None)
    assert DA.decode_attention_supported(q, kq, vq, pos, 0.125,
                                         None, None, None, k_scale=ks)
    # and bf16 WITH scales is equally malformed
    assert not DA.decode_attention_supported(q, k, v, pos, 0.125,
                                             None, None, None, k_scale=ks)


# -- storage footprint -------------------------------------------------------

def test_cache_bytes_ratios_and_gauge():
    from bigdl_tpu.observability.metrics import MetricsRegistry

    dims = (2, 1, 64, 4, 128)   # L, B, S, Hkv, hd=128 (serving-like)
    bf16 = kvc.kv_cache_bytes(kvc.init_cache(*dims))
    assert bf16["scales"] == 0
    for name, code_cap, total_cap in (("int8", 0.5, 0.52),
                                      ("int4", 0.25, 0.27)):
        c = kvc.init_cache(*dims, kv_cache_dtype=name)
        sizes = kvc.kv_cache_bytes(c)
        assert sizes["codes"] <= code_cap * bf16["total"], (name, sizes)
        assert sizes["total"] <= total_cap * bf16["total"], (name, sizes)
        reg = MetricsRegistry()
        published = kvc.publish_kv_cache_bytes(c, reg)
        assert published == sizes
        rendered = reg.render()
        assert f'bigdl_tpu_kv_cache_bytes{{dtype="{name}",' \
               f'component="total"}} {sizes["total"]}' in rendered


def test_fp8_cache_halves_codes():
    dims = (2, 1, 64, 4, 128)
    bf16 = kvc.kv_cache_bytes(kvc.init_cache(*dims))
    fp8 = kvc.kv_cache_bytes(kvc.init_cache(*dims,
                                            kv_cache_dtype="fp8_e5m2"))
    assert fp8["total"] == bf16["total"] // 2 and fp8["scales"] == 0


# -- family / parallel guards -----------------------------------------------

def test_reject_scaled_kv_guard():
    with pytest.raises(NotImplementedError, match="yuan"):
        kvc.reject_scaled_kv("int8", "yuan")
    with pytest.raises(NotImplementedError):
        kvc.reject_scaled_kv("int4", "whisper")
    # scale-free dtypes pass
    kvc.reject_scaled_kv("bf16", "yuan")
    kvc.reject_scaled_kv("fp8_e5m2", "yuan")
    kvc.reject_scaled_kv(False, "yuan")


def test_tp_rejects_scaled():
    from jax.sharding import Mesh

    from bigdl_tpu.parallel.tp import new_cache_tp
    from bigdl_tpu.utils.testing import TINY_LLAMA

    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    with pytest.raises(NotImplementedError, match="tensor parallelism"):
        new_cache_tp(TINY_LLAMA, 1, 32, mesh, quantized="int8")


def test_engine_rejects_family_without_scaled_support():
    from bigdl_tpu.models import llama as llama_mod
    from bigdl_tpu.serving import EngineConfig, LLMEngine
    from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params

    class M:
        params = random_llama_params(TINY_LLAMA, qtype="sym_int4", seed=0)
        config = TINY_LLAMA
        hf_config = {"eos_token_id": None}

        class family:
            name = "nokv"
            forward = staticmethod(llama_mod.forward)
            prefill = staticmethod(llama_mod.forward_last_token)
            new_cache = staticmethod(llama_mod.new_cache)

    with pytest.raises(ValueError, match="SUPPORTS_SCALED_KV"):
        LLMEngine(M(), EngineConfig(max_batch=1, max_seq=64,
                                    kv_cache_dtype="int8"))


# -- model + serving end-to-end ---------------------------------------------

def _fake_model():
    from bigdl_tpu.models import llama as llama_mod
    from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params

    class FakeModel:
        params = random_llama_params(TINY_LLAMA, qtype="sym_int4", seed=0)
        config = TINY_LLAMA
        hf_config = {"eos_token_id": None}

        class family:
            forward = staticmethod(llama_mod.forward)
            prefill = staticmethod(llama_mod.forward_last_token)
            new_cache = staticmethod(llama_mod.new_cache)
            SUPPORTS_SCALED_KV = True

    return FakeModel()


def _plain(params, prompt, n, kv_dtype):
    from bigdl_tpu.generation import generate_on_device
    from bigdl_tpu.models import llama as llama_mod
    from bigdl_tpu.utils.testing import TINY_LLAMA

    cache = llama_mod.new_cache(TINY_LLAMA, 1, 128, kv_dtype)
    out, _ = generate_on_device(
        params, TINY_LLAMA, llama_mod.forward,
        jnp.asarray(np.asarray(prompt, np.int32)[None]), cache,
        max_new_tokens=n)
    return list(np.asarray(out)[0])


def test_llama_forward_int8_logits_close():
    from bigdl_tpu.models import llama as llama_mod
    from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params

    params = random_llama_params(TINY_LLAMA, qtype="sym_int4", seed=0)
    toks = jnp.asarray(np.arange(1, 17, dtype=np.int32)[None])
    outs = {}
    for d in ("bf16", "int8"):
        cache = llama_mod.new_cache(TINY_LLAMA, 1, 64, d)
        lg, cache = llama_mod.forward(params, TINY_LLAMA, toks, cache)
        assert int(np.asarray(cache.pos)) == 16
        outs[d] = np.asarray(lg, np.float32)[:, -1]
    ref, got = outs["bf16"], outs["int8"]
    rel = np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-6)
    assert rel < 0.15, rel


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_engine_e2e_matches_plain(kv_dtype):
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    model = _fake_model()
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128,
                                        kv_cache_dtype=kv_dtype))
    prompts = [list(range(1, 9)), list(range(20, 26))]
    outs = eng.generate(prompts, SamplingParams(max_tokens=8))
    for p, got in zip(prompts, outs):
        assert got == _plain(model.params, p, 8, kv_dtype), (kv_dtype, p)


def test_engine_e2e_int8_prefix_seeding():
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    model = _fake_model()
    eng = LLMEngine(model, EngineConfig(
        max_batch=2, max_seq=128, kv_cache_dtype="int8",
        prefill_bucket=16, prefill_chunk=16, prefix_cache_entries=4))
    p1 = list(range(1, 40))
    eng.generate([p1], SamplingParams(max_tokens=4))
    assert len(eng._prefix_cache) == 1 and eng._prefix_index
    # a prompt sharing the first 32 tokens seeds 32 quantized positions
    p2 = p1[:32] + [88, 77]
    consumed, entry = eng._seed_from_prefix_cache(p2, 16)
    assert consumed == 32
    assert entry is not None and len(entry) == 4   # k, v, k_scale, v_scale
    out = eng.generate([p2], SamplingParams(max_tokens=8))[0]
    assert out == _plain(model.params, p2, 8, "int8")


def test_engine_bytes_gauge_published():
    from bigdl_tpu.observability.metrics import MetricsRegistry
    from bigdl_tpu.serving import EngineConfig, LLMEngine

    reg = MetricsRegistry()
    eng = LLMEngine(_fake_model(),
                    EngineConfig(max_batch=2, max_seq=64,
                                 kv_cache_dtype="int4"),
                    registry=reg)
    assert eng.kv_cache_dtype == "int4"
    rendered = reg.render()
    assert 'bigdl_tpu_kv_cache_bytes{dtype="int4",component="codes"}' \
        in rendered


def test_prefix_index_matches_linear_scan():
    """The bucketed prefix-hash index must agree with the O(entries)
    linear scan it replaced, on hits, misses, and after LRU eviction."""
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    model = _fake_model()
    eng = LLMEngine(model, EngineConfig(
        max_batch=2, max_seq=128, prefill_bucket=16, prefill_chunk=16,
        prefix_cache_entries=2))
    assert eng._prefix_g == 16
    a = list(range(1, 40))                    # 39 tokens
    b = list(range(1, 20)) + [90] * 21        # shares 16-token prefix bucket
    c = [70] * 37                             # unrelated; evicts `a`
    for p in (a, b, c):
        eng.generate([p], SamplingParams(max_tokens=2))
    assert len(eng._prefix_cache) == 2        # LRU evicted the oldest
    # every index pointer must refer to a live entry
    live = set(eng._prefix_cache)
    for d in eng._prefix_index.values():
        for key in d.values():
            assert key in live
    probes = [a, b, c, a[:17] + [5, 5, 5], [99] * 20,
              b[:33] + [1], c + [2, 2]]
    for probe in probes:
        got = eng._seed_from_prefix_cache(probe, 16)[0]
        saved, eng._prefix_g = eng._prefix_g, 0   # force linear fallback
        try:
            want = eng._seed_from_prefix_cache(probe, 16)[0]
        finally:
            eng._prefix_g = saved
        assert got == want, (probe[:4], got, want)


def test_from_pretrained_kwarg_conflict_free(tmp_path):
    """TpuCausalLM resolves kv_cache_dtype over the deprecated boolean."""
    from bigdl_tpu.transformers.model import TpuCausalLM

    m = TpuCausalLM({}, None, object(), {}, None,
                    kv_quantized=False, kv_cache_dtype="int8")
    assert m.kv_cache_dtype == "int8" and m.kv_quantized
    kvc._warned_quantized_alias = True
    m = TpuCausalLM({}, None, object(), {}, None, kv_quantized=True)
    assert m.kv_cache_dtype == "fp8_e5m2" and m.kv_quantized
    m = TpuCausalLM({}, None, object(), {}, None)
    assert m.kv_cache_dtype == "bf16" and not m.kv_quantized
