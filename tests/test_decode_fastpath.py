"""Decode fast path: fused packed gemv parity, resident single-dispatch
step identity, and load-time prepacking.

Three invariants from the PR that introduced the resident decode step:

1. **Kernel parity** — the fused-dequant XLA decode path
   (`_q_matmul_xla_fused`) and the Pallas decode GEMV (m <= 32,
   interpret mode on CPU) must match the reference `_q_matmul_xla`
   within one bf16 ULP; the bounded-temp chunked XLA plan must match it
   bitwise (over-N splits leave each column's K-reduction untouched).
2. **Resident identity** — with the single-dispatch resident step ON
   vs OFF, Generator and LLMEngine output is byte-identical (greedy
   AND seeded device sampling), and a pure-decode engine step issues
   exactly ONE host dispatch.
3. **Prepack** — `prepack_tree` is a no-op when off, value-preserving
   when forced on, and its report says what happened.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import config as config_mod
from bigdl_tpu.config import set_flags
from bigdl_tpu.generation import GenerationConfig, Generator
from bigdl_tpu.models import llama as llama_mod
from bigdl_tpu.observability.compile_watch import (
    dispatch_table,
    reset_dispatch_table,
)
from bigdl_tpu.ops.matmul import _q_matmul_xla, _q_matmul_xla_fused, q_matmul
from bigdl_tpu.ops.quant import dequantize, prepack_tree, quantize
from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params

# one bf16 ULP: 8-bit significand -> eps = 2^-7; the fused path only
# reassociates the per-block scale multiply out of the contraction
BF16_ULP = 2.0 ** -7


@pytest.fixture(autouse=True)
def _restore_flags():
    snap = dataclasses.replace(config_mod.flags())
    yield
    config_mod._flags = snap


def _rand(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


# ---------------------------------------------------------------------------
# fused-dequant XLA decode path


@pytest.mark.parametrize("qtype", ["sym_int4", "sym_int8", "nf4",
                                   "asym_int4"])
@pytest.mark.parametrize("m", [1, 3, 16])
def test_fused_xla_matches_reference(qtype, m):
    k, n = 256, 192
    x = _rand((m, k), seed=1) * 0.3
    qt = quantize(_rand((k, n), seed=2) * 0.1, qtype)
    want = np.asarray(_q_matmul_xla(x, qt), np.float32)
    got = np.asarray(_q_matmul_xla_fused(x, qt), np.float32)
    np.testing.assert_allclose(got, want, rtol=BF16_ULP,
                               atol=BF16_ULP * np.abs(want).max())


def test_fused_xla_odd_shapes_and_batch_dims():
    # K not a multiple of the quant block (pad path) + leading batch dims
    k, n = 320, 96
    x = _rand((2, 3, k), seed=3) * 0.2
    qt = quantize(_rand((k, n), seed=4) * 0.1, "sym_int4")
    want = np.asarray(_q_matmul_xla(x.reshape(6, k), qt),
                      np.float32).reshape(2, 3, n)
    got = np.asarray(_q_matmul_xla_fused(x, qt), np.float32)
    assert got.shape == (2, 3, n)
    np.testing.assert_allclose(got, want, rtol=BF16_ULP,
                               atol=BF16_ULP * np.abs(want).max())


def test_fused_xla_public_backend():
    x = _rand((1, 256), seed=5) * 0.3
    qt = quantize(_rand((256, 128), seed=6) * 0.1, "sym_int4")
    want = np.asarray(q_matmul(x, qt, backend="xla"), np.float32)
    got = np.asarray(q_matmul(x, qt, backend="xla_fused"), np.float32)
    np.testing.assert_allclose(got, want, rtol=BF16_ULP,
                               atol=BF16_ULP * np.abs(want).max())


def test_fused_xla_rejects_unfactorable_qtype():
    # fp4's dequant doesn't factor as code * blockscale with a single LUT
    x = _rand((1, 256)) * 0.3
    qt = quantize(_rand((256, 128), seed=7), "fp4")
    with pytest.raises(NotImplementedError):
        _q_matmul_xla_fused(x, qt)


def test_chunked_xla_matches_dense():
    """Over-N chunking (the decode OOM fix) leaves every column's
    K-reduction mathematically untouched; the only wiggle left is
    XLA reassociating the f32 accumulation differently for the
    narrower dot, so the tolerance is f32-roundoff tight — orders of
    magnitude below quantization error."""
    from bigdl_tpu.ops.matmul import _q_matmul_xla_chunked

    k, n = 512, 1024
    x = _rand((2, k), seed=8) * 0.2
    qt = quantize(_rand((k, n), seed=9) * 0.1, "sym_int4")
    chunked = _q_matmul_xla_chunked(x, qt, min_elems=1, target_cols=256)
    assert chunked is not None
    dense = jnp.dot(x.astype(jnp.bfloat16),
                    dequantize(qt, dtype=jnp.bfloat16),
                    preferred_element_type=jnp.float32).astype(x.dtype)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                               rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# Pallas decode GEMV, widened to m <= 32 (interpret mode on CPU)


@pytest.mark.parametrize("qtype", ["sym_int4", "sym_int8", "nf4"])
@pytest.mark.parametrize("m", [17, 32])
def test_gemv_wide_m_matches_xla(qtype, m):
    from bigdl_tpu.ops.matmul import q_matmul_pallas
    from bigdl_tpu.ops.pallas.dequant_matmul import GEMV_MAX_M

    assert m <= GEMV_MAX_M
    k, n = 512, 256
    x = _rand((m, k), seed=10) * 0.3
    qt = quantize(_rand((k, n), seed=11) * 0.1, qtype)
    got = np.asarray(q_matmul_pallas(x, qt, interpret=True), np.float32)
    want = np.asarray(_q_matmul_xla(x, qt), np.float32)
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# load-time prepacking


def test_prepack_off_is_identity():
    qt = quantize(_rand((256, 128), seed=12), "sym_int4")
    tree = {"w": qt, "other": jnp.ones((4,))}
    out, report = prepack_tree(tree, mode="off")
    assert out is tree
    assert report["mode"] == "off" and not report["applied"]
    assert report["bytes_packed"] == 0


def test_prepack_auto_skips_off_tpu():
    qt = quantize(_rand((256, 128), seed=13), "sym_int4")
    out, report = prepack_tree({"w": qt}, mode="auto")
    assert out["w"] is qt                   # CPU target: untouched
    assert not report["applied"]


def test_prepack_on_preserves_values_and_reports():
    w = _rand((256, 128), seed=14) * 0.1
    qt = quantize(w, "sym_int4")
    out, report = prepack_tree({"w": qt}, mode="on")
    assert report["mode"] == "on"
    assert report["qtensors"] == 1
    assert report["applied"] and report["converted"] == 1
    assert report["bytes_packed"] > 0
    # the retile permutes storage, never values: dequant is exact
    np.testing.assert_array_equal(
        np.asarray(dequantize(out["w"], dtype=jnp.float32)),
        np.asarray(dequantize(qt, dtype=jnp.float32)))


def test_prepack_rejects_bad_mode():
    with pytest.raises(ValueError):
        prepack_tree({}, mode="bogus")


# ---------------------------------------------------------------------------
# resident Generator: byte-identity + dispatch count

PROMPT = [1, 5, 9, 42]


def _gen(params, **gen_kw):
    g = Generator(params, TINY_LLAMA, max_seq=64)
    return g.generate(PROMPT, GenerationConfig(max_new_tokens=10,
                                               **gen_kw))


@pytest.fixture(scope="module")
def tiny_params():
    return random_llama_params(TINY_LLAMA, qtype="sym_int4", seed=0)


@pytest.mark.parametrize("gen_kw", [
    {},                                                       # greedy
    {"do_sample": True, "temperature": 0.8, "top_k": 20, "seed": 7},
], ids=["greedy", "sampled"])
def test_generator_resident_byte_identical(tiny_params, gen_kw):
    set_flags(decode_resident="off")
    legacy = _gen(tiny_params, **gen_kw)
    set_flags(decode_resident="on")
    resident = _gen(tiny_params, **gen_kw)
    np.testing.assert_array_equal(legacy, resident)


def test_generator_resident_eos_identical(tiny_params):
    set_flags(decode_resident="off")
    ref = _gen(tiny_params)
    eos = int(ref[0][3])                    # token that WILL appear
    legacy = _gen(tiny_params, eos_token_id=eos)
    set_flags(decode_resident="on")
    resident = _gen(tiny_params, eos_token_id=eos)
    np.testing.assert_array_equal(legacy, resident)


def test_generator_resident_dispatch_shape(tiny_params):
    """A resident 10-token generation decodes through the fused step:
    at most the one padded-prefill repair call hits the legacy decode
    jit, everything after the first token is generate_decode_resident."""
    set_flags(decode_resident="on")
    g = Generator(tiny_params, TINY_LLAMA, max_seq=64)
    reset_dispatch_table()
    g.generate(PROMPT, GenerationConfig(max_new_tokens=10))
    dt = dispatch_table()
    assert dt.get("generate_decode_resident", 0) >= 9, dt
    assert dt.get("generate_decode", 0) <= 1, dt


# ---------------------------------------------------------------------------
# resident engine: byte-identity + ONE dispatch per pure-decode step


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


def _engine_generate(model, sp):
    from bigdl_tpu.serving import EngineConfig, LLMEngine

    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    return eng.generate([list(range(1, 9)), [7, 3, 99, 5]], sp)


@pytest.mark.parametrize("sp_kw", [
    {},                                                       # greedy
    {"temperature": 0.8, "top_k": 5, "seed": 42},             # sampled
], ids=["greedy", "sampled"])
def test_engine_resident_byte_identical(tiny_params, sp_kw):
    from bigdl_tpu.serving import SamplingParams

    model = FakeModel(tiny_params, TINY_LLAMA)
    sp = SamplingParams(max_tokens=10, **sp_kw)
    set_flags(decode_resident="off")
    legacy = _engine_generate(model, sp)
    set_flags(decode_resident="on")
    resident = _engine_generate(model, sp)
    assert legacy == resident


def test_engine_resident_one_dispatch_per_step(tiny_params):
    """The PR acceptance criterion: a pure-decode engine step issues
    exactly ONE host dispatch (forward + health + sampling fused)."""
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    set_flags(decode_resident="on")
    eng = LLMEngine(FakeModel(tiny_params, TINY_LLAMA),
                    EngineConfig(max_batch=2, max_seq=128))
    eng.add_request("r0", [1, 2, 3, 4], SamplingParams(max_tokens=50))
    eng.step()                              # admission + first decode
    reset_dispatch_table()
    for _ in range(5):
        eng.step()
    assert dispatch_table() == {"engine_decode_resident": 5}


def test_engine_resident_one_dispatch_with_sentinel(tiny_params):
    """The perf sentinel + live roofline gauges ride the shared step
    path as pure host-side float math: with the sentinel explicitly ON
    a pure-decode step still issues exactly ONE host dispatch."""
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    set_flags(decode_resident="on")
    eng = LLMEngine(FakeModel(tiny_params, TINY_LLAMA),
                    EngineConfig(max_batch=2, max_seq=128,
                                 sentinel=True))
    assert eng.sentinel is not None
    eng.add_request("r0", [1, 2, 3, 4], SamplingParams(max_tokens=50))
    eng.step()                              # admission + first decode
    reset_dispatch_table()
    for _ in range(5):
        eng.step()
    assert dispatch_table() == {"engine_decode_resident": 5}
    # the observability hooks actually ran: gauges fed, sentinel stepped
    assert eng._last_perf is not None
    assert eng.sentinel.snapshot()["steps"] >= 5


def test_engine_legacy_multi_dispatch_still_works(tiny_params):
    """Sanity for the fallback: with the resident step off the engine
    still decodes (multi-dispatch) — and never touches the fused jit."""
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    set_flags(decode_resident="off")
    eng = LLMEngine(FakeModel(tiny_params, TINY_LLAMA),
                    EngineConfig(max_batch=2, max_seq=128))
    eng.add_request("r0", [1, 2, 3, 4], SamplingParams(max_tokens=8))
    eng.step()
    reset_dispatch_table()
    for _ in range(3):
        eng.step()
    dt = dispatch_table()
    assert "engine_decode_resident" not in dt
    assert dt.get("engine_decode", 0) == 3


def _attn_blocks_counter(eng, kind):
    return eng._m_attn_blocks.labels(kind).value


@pytest.mark.parametrize("attention", ["xla", "pallas"])
@pytest.mark.parametrize("resident", ["on", "off"])
def test_engine_empty_slot_stays_at_zero(resident, attention, monkeypatch):
    """Both slab decode programs (`engine_decode_resident`, and
    `engine_decode` + sampler), over the XLA ops and over the decode
    kernel (interpret mode): a finished request's slot stays at position
    0 while another slot decodes on, the survivor's tokens are those of
    a run where it was alone, and every decode step adds to
    `bigdl_tpu_decode_attn_blocks_total` exactly what the kernel's block
    rule gives for the slots' positions (-1 for an empty one), times
    layers. Blocks of 128
    positions, so the survivor crosses from its first block into its
    second."""
    from bigdl_tpu.ops.pallas import decode_attention as DA
    from bigdl_tpu.ops.pallas.decode_attention import blocks_read, slab_blocks
    from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    set_flags(decode_resident=resident, attention_backend=attention)
    # head_dim 128 and two kv heads: a geometry the kernel takes, on the
    # stack in place
    cfg = dataclasses.replace(TINY_LLAMA, hidden_size=256,
                              num_hidden_layers=3, num_attention_heads=2,
                              num_key_value_heads=2)
    model = FakeModel(random_llama_params(cfg, qtype="sym_int4", seed=0),
                      cfg)
    b, s = 3, 256
    layers, hkv = cfg.num_hidden_layers, cfg.num_key_value_heads
    monkeypatch.setattr(DA, "_BLOCK_ROWS", 128 * hkv)
    long_prompt, n_long = [1 + i % 50 for i in range(120)], 16

    def engine():
        return LLMEngine(model, EngineConfig(max_batch=b, max_seq=s))

    alone = engine().generate([long_prompt],
                              SamplingParams(max_tokens=n_long))[0]

    eng = engine()
    eng.add_request("short", [7, 3, 99, 5], SamplingParams(max_tokens=3))
    eng.add_request("long", long_prompt, SamplingParams(max_tokens=n_long))
    got, short_slot, idle_steps = [], None, 0
    while len(got) < n_long:
        live = {i: len(sl.req.prompt_token_ids) + len(sl.generated) - 1
                for i, sl in enumerate(eng.slots) if sl.active}
        before = {k: _attn_blocks_counter(eng, k) for k in ("read", "slab")}
        pos_before = np.asarray(eng.cache.pos)
        # slots of a resident step that is out ahead of the host's read
        led = {i for i, _ in eng._ahead[0]} if eng._ahead else set()
        # a step that admits decodes slots this loop did not see live
        settled = not eng.waiting and eng._admitting is None
        eng.step()
        for out in eng.get_outputs("long"):
            got.extend(out.new_token_ids)
        for i, sl in enumerate(eng.slots):
            if sl.req is not None and sl.req.request_id == "short":
                short_slot = i
        if settled:
            # the host's positions are the cache's own, or one behind
            # them where the next step is out already
            for i, p in live.items():
                assert pos_before[i] == p + (i in led)
            assert (_attn_blocks_counter(eng, "read") - before["read"]
                    == layers * blocks_read(
                        [live.get(i, -1) for i in range(b)], s, hkv))
            assert (_attn_blocks_counter(eng, "slab") - before["slab"]
                    == layers * slab_blocks(b, s, hkv))
        pos = np.asarray(eng.cache.pos)
        for i, sl in enumerate(eng.slots):
            if not sl.active and settled:
                assert pos[i] == 0, (i, pos)
        if short_slot is not None and not eng.slots[short_slot].active:
            idle_steps += 1
    assert idle_steps >= 10
    assert got == alone
    assert slab_blocks(b, s, hkv) == 2 * b
    assert _attn_blocks_counter(eng, "read") < _attn_blocks_counter(
        eng, "slab")


# ---------------------------------------------------------------------------
# speculative draft path: greedy identity holds under either flag


def test_speculative_identity_under_resident_flag(tiny_params):
    """Speculation changes latency, never text — and flipping the
    resident-decode flag must not perturb either side of that
    equality (the draft loop is its own fused program)."""
    from bigdl_tpu.generation import generate_on_device
    from bigdl_tpu.speculative import speculative_generate

    prompt = (np.arange(1, 13, dtype=np.int32).reshape(1, 12)
              % TINY_LLAMA.vocab_size)

    def greedy(n):
        cache = llama_mod.new_cache(TINY_LLAMA, 1, 128)
        out, _ = generate_on_device(
            tiny_params, TINY_LLAMA, llama_mod.forward,
            jnp.asarray(prompt), cache, max_new_tokens=n)
        return np.asarray(out)

    def spec(n):
        return speculative_generate(
            tiny_params, tiny_params, TINY_LLAMA, TINY_LLAMA, prompt,
            family_forward=llama_mod.forward,
            family_prefill=llama_mod.forward_last_token,
            new_cache=llama_mod.new_cache,
            max_new_tokens=n, gamma=4, max_seq=128)

    set_flags(decode_resident="off")
    ref_off, spec_off = greedy(16), spec(16)
    set_flags(decode_resident="on")
    ref_on, spec_on = greedy(16), spec(16)
    np.testing.assert_array_equal(ref_off, ref_on)
    np.testing.assert_array_equal(np.asarray(spec_off),
                                  np.asarray(spec_on))
    np.testing.assert_array_equal(np.asarray(spec_on), ref_on)
