"""MiMo-V2 on the serving path, CPU, tiny widths at which every mechanism
BINDS (window 9 in a ring of 16, 8 query heads on 2 / 4 KV heads of 48 /
32, 4 of 16 experts held, sequences of 5-100): the plane-listing K/V
cache spec, each new kernel in interpret mode against its XLA form, the
rings across their wrap and the splice mid-ring, the chunk path at
chunk boundaries inside and outside the band, the sink, a layer without
a shared expert, an engine run against `model.generate()`, the
counters and the refusals."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import deepseek_v2, mimo_v2
from bigdl_tpu.models.registry import get_family
from bigdl_tpu.ops import kvcache, moe_routed, swa
from bigdl_tpu.ops.pallas import swa_attention as kernels

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

CONFIG = "mimo-v25-ep8-int4"


def _tiny_config():
    from harness import spec

    doc = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    return spec.deep_update(doc, doc["tiny"])


@pytest.fixture(scope="module")
def model():
    from harness import weights_mimo_v2 as weights

    return weights.build_model(_tiny_config(), 2 ** 31 + 3, merge=True)[0]


def test_registry_loads_the_family_and_its_cache_lists_four_planes(model):
    cfg = model.config
    assert get_family("MiMoV2ForCausalLM").name == "mimo_v2"
    assert (cfg.n_full, cfg.n_window, cfg.ring, cfg.share,
            cfg.rotary_dim) == (2, 4, 16, (16, 0, 4), 16)
    assert (cfg.full.sink, cfg.swa.sink, cfg.swa.window) == (False, True, 9)
    spec = kvcache.cache_spec_of(model.family, cfg)
    assert [(p.name, p.layers, p.dims, p.ring) for p in spec.planes] == [
        ("full_k", 2, (96,), 0), ("full_v", 2, (64,), 0),
        ("ring_k", 4, (192,), 16), ("ring_v", 4, (128,), 16)]
    assert spec.has_ring and not spec.has_strided
    cache = kvcache.init_cache_spec(spec, 2, 64, per_slot_pos=True)
    assert {k: v.shape for k, v in cache.planes().items()} == {
        "full_k": (2, 2, 64, 96), "full_v": (2, 2, 64, 64),
        "ring_k": (4, 2, 16, 192), "ring_v": (4, 2, 16, 128)}
    want = 2 * 2 * (2 * 64 * (96 + 64) + 4 * 16 * (192 + 128))
    assert kvcache.kv_cache_bytes(cache) == kvcache.cache_nbytes(
        spec, 2, 64) == {"codes": want, "scales": 0, "total": want}
    assert cache.max_seq == 64 and cache.stats.shape == (4,)
    # a private prefill cache keeps the rings' rows in position order
    assert {p.name: p.shape(1, 64) for p in spec.unrolled().planes}[
        "ring_k"] == (4, 1, 64, 192)
    # the published sizes: 64 rotary dims, a ring of exactly the window,
    # rows of 768 / 512 and 1,536 / 1,024 values
    pub = mimo_v2.MimoV2Config()
    assert (pub.rotary_dim, pub.ring, pub.full.k_width, pub.full.v_width,
            pub.swa.k_width, pub.swa.v_width) == (64, 128, 768, 512, 1536,
                                                  1024)
    # the pytree round trip keeps every plane where it was
    leaves, tree = jax.tree.flatten(cache)
    again = jax.tree.unflatten(tree, leaves)
    assert set(again.planes()) == set(cache.planes()) and again.k is None


def _planes(rng, layers, b, s, hkv, dk, dv):
    k = jnp.asarray(rng.standard_normal((layers, b, s, hkv * dk)),
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((layers, b, s, hkv * dv)),
                    jnp.bfloat16)
    return k, v


@pytest.mark.parametrize("pos", [[0, 3, 127], [128, 200, 255], [-1, 64, 5]])
def test_full_decode_kernel_against_its_xla_form(pos):
    """`decode_attention_lanes` in interpret mode: K of 64 and V of 32 a
    head, 8 query heads on 2 KV heads, two 128-position blocks; a slot
    below 0 holds nothing and reads zeros."""
    rng = np.random.default_rng(1)
    k, v = _planes(rng, 2, 3, 256, 2, 64, 64)
    q = jnp.asarray(rng.standard_normal((3, 8, 64)), jnp.bfloat16)
    posv = jnp.asarray(pos, jnp.int32)
    assert kernels.lanes_supported(q, k, v, 2)
    got = kernels.decode_attention_lanes_pallas(
        q, k, v, posv, 0.125, 2, layer=1, interpret=True)
    live = jnp.arange(256)[None, :] <= posv[:, None]
    want = swa.decode_xla(q, k[1], v[1], live, 0.125, 2)
    for i, p in enumerate(pos):
        if p < 0:
            assert not np.asarray(got[i], np.float32).any()
        else:
            np.testing.assert_allclose(np.asarray(got[i], np.float32),
                                       np.asarray(want[i], np.float32),
                                       atol=2e-2)
    # the dispatch takes the kernel where it is forced
    again = swa.full_decode(q, k, v, jnp.int32(1), jnp.maximum(posv, 0),
                            0.125, 2, backend="pallas")
    assert again.shape == (3, 8, 64)


@pytest.mark.parametrize("sink", [True, False])
@pytest.mark.parametrize("pos", [[0, 5, 99], [127, 128, 700], [-1, 1000, 255]])
def test_ring_decode_kernel_across_the_wrap_with_the_sink(pos, sink):
    """`swa_decode_attention` in interpret mode over a ring of 128 and a
    window of 100: positions inside the first window, at the wrap and
    several rings on, the sink in the softmax or not."""
    rng = np.random.default_rng(2)
    k, v = _planes(rng, 3, 3, 128, 4, 32, 32)
    q = jnp.asarray(rng.standard_normal((3, 8, 32)), jnp.bfloat16)
    b = (jnp.asarray(rng.standard_normal(8) + 2.0, jnp.float32)
         if sink else None)
    posv = jnp.asarray(pos, jnp.int32)
    got = kernels.swa_decode_attention_pallas(
        q, k, v, posv, 0.2, 4, 100, sink=b, layer=2, interpret=True)
    want = swa.decode_xla(q, k[2], v[2],
                          swa.ring_live(jnp.maximum(posv, 0), 128, 100), 0.2,
                          4, b)
    for i, p in enumerate(pos):
        if p < 0:
            assert not np.asarray(got[i], np.float32).any()
        else:
            np.testing.assert_allclose(np.asarray(got[i], np.float32),
                                       np.asarray(want[i], np.float32),
                                       atol=2e-2)
    if sink:     # the sink takes weight: the rows are shorter with it
        bare = swa.decode_xla(q, k[2], v[2], swa.ring_live(
            jnp.maximum(posv, 0), 128, 100), 0.2, 4)
        assert float(jnp.abs(bare[1].astype(jnp.float32)
                             - want[1].astype(jnp.float32)).max()) > 0.05


def test_the_sink_is_a_column_with_no_value():
    """One key of score 0 and a sink of 0 share the row's weight: the
    output is half the value."""
    k = jnp.zeros((1, 1, 128, 128), jnp.bfloat16)
    v = jnp.ones((1, 1, 128, 128), jnp.bfloat16)
    q = jnp.zeros((1, 2, 128), jnp.bfloat16)
    for fn in (
            lambda b: kernels.swa_decode_attention_pallas(
                q, k, v, jnp.zeros((1,), jnp.int32), 1.0, 1, 128, sink=b,
                interpret=True),
            lambda b: swa.decode_xla(q, k[0], v[0], swa.ring_live(
                jnp.zeros((1,), jnp.int32), 128, 128), 1.0, 1, b)):
        out = np.asarray(fn(jnp.asarray([0.0, 50.0], jnp.float32)),
                         np.float32)
        assert out[0, 0, 0] == pytest.approx(0.5, abs=1e-2)
        assert out[0, 1, 0] == pytest.approx(0.0, abs=1e-6)


def _dense_window(q, ks, vs, p, scale, hkv, window, sink):
    """Rows at `p ..` of one sequence against ALL of `ks` / `vs` `[S, .]`
    (positions 0 ..) under the causal window mask, float32."""
    t, h, dk = q.shape
    s, g = ks.shape[0], h // hkv
    k = np.asarray(ks, np.float32).reshape(s, hkv, dk)
    v = np.asarray(vs, np.float32).reshape(s, hkv, -1)
    qq = np.asarray(q, np.float32).reshape(t, hkv, g, dk)
    sc = np.einsum("tngd,snd->ngts", qq, k) * scale
    d = (p + np.arange(t))[:, None] - np.arange(s)[None, :]
    ok = (d >= 0) & ((d < window) if window else True)
    sc = np.where(ok, sc, -np.inf)
    m = sc.max(-1, keepdims=True)
    if sink is not None:
        b = np.asarray(sink, np.float32).reshape(hkv, g)[..., None, None]
        m = np.maximum(m, b)
    e = np.exp(sc - m)
    den = e.sum(-1, keepdims=True) + (np.exp(b - m) if sink is not None
                                      else 0.0)
    return np.einsum("ngts,sno->tngo", e / den, v).reshape(t, h, -1)


@pytest.mark.parametrize("p,t", [(0, 32), (0, 5), (4, 32), (32, 32),
                                 (45, 16), (200, 32)])
def test_window_chunk_reads_the_band_and_nothing_else(p, t):
    """A chunk at `p` through a window layer (window 9): the keys before
    it come from the ring as it was before the chunk (a ring of 16 that
    has wrapped, or a plane in position order), its own rows from the
    chunk; a chunk boundary inside the band (p = 4), outside it (p = 32,
    200), a prompt shorter than the window (5 rows at 0)."""
    rng = np.random.default_rng(p + t)
    hkv, dk, dv, window = 4, 48, 32, 9
    s = p + t
    ks = jnp.asarray(rng.standard_normal((s, hkv * dk)), jnp.bfloat16)
    vs = jnp.asarray(rng.standard_normal((s, hkv * dv)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((t, 8, dk)), jnp.bfloat16)
    sink = jnp.asarray(rng.standard_normal(8) + 1.0, jnp.float32)
    want = _dense_window(q, ks, vs, p, 0.14, hkv, window, sink)
    for ring in (16, 256):
        rk = jnp.zeros((2, 1, ring, hkv * dk), jnp.bfloat16)
        rv = jnp.zeros((2, 1, ring, hkv * dv), jnp.bfloat16)
        at = np.arange(max(0, p - ring), p)          # what the ring holds
        rk = rk.at[1, 0, at % ring].set(ks[at])
        rv = rv.at[1, 0, at % ring].set(vs[at])
        pk, pv = (swa.rows_before(x, jnp.int32(1), jnp.asarray([p]),
                                  window - 1)[0] for x in (rk, rv))
        got = swa.window_chunk(q, ks[p:], vs[p:], pk, pv, jnp.int32(p), 0.14,
                               hkv, window, sink)
        np.testing.assert_allclose(np.asarray(got), want, atol=3e-2)


@pytest.mark.parametrize("p,t", [(0, 32), (96, 32), (500, 24), (1000, 24)])
def test_full_chunk_sweeps_the_live_key_blocks(p, t):
    """A chunk at `p` through a full layer's planes of 1,024 positions
    (two key blocks of 512; rows 500-523 straddle them): all earlier
    keys and its own, nothing past its rows (stale values there)."""
    rng = np.random.default_rng(p)
    hkv, dk, dv, s = 2, 48, 32, 1024
    ks = np.full((s, hkv * dk), 100.0, np.float32)
    vs = np.full((s, hkv * dv), 100.0, np.float32)
    ks[:p + t] = rng.standard_normal((p + t, hkv * dk))
    vs[:p + t] = rng.standard_normal((p + t, hkv * dv))
    ks, vs = jnp.asarray(ks, jnp.bfloat16), jnp.asarray(vs, jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((t, 8, dk)), jnp.bfloat16)
    got = np.asarray(swa.full_chunk(q, ks, vs, jnp.int32(p), 0.14, hkv))
    want = _dense_window(q, ks[:p + t], vs[:p + t], p, 0.14, hkv, 0, None)
    np.testing.assert_allclose(got, want, atol=3e-2)


def test_update_rows_writes_a_ring_across_its_wrap_and_a_plane_in_place():
    ring = jnp.zeros((2, 2, 16, 4), jnp.bfloat16)
    # 40 rows at 0 and at 7: the ring wraps 2.5 times, the last 16 stay
    new = jnp.broadcast_to(jnp.arange(40, dtype=jnp.float32)[None, :, None],
                           (2, 40, 4))
    out = kvcache.update_rows(ring, 1, new, jnp.asarray([0, 7]), ring=True)
    for slot, p in enumerate((0, 7)):
        held = np.asarray(out[1, slot, :, 0], np.float32)
        for i in range(24, 40):
            assert held[(p + i) % 16] == i
    assert not np.asarray(out[0], np.float32).any()
    # one decoded row a slot at pos % ring
    one = kvcache.update_rows(out, 1, jnp.full((2, 1, 4), 99.0),
                              jnp.asarray([35, 16]), ring=True)
    assert float(one[1, 0, 3, 0]) == 99.0 and float(one[1, 1, 0, 0]) == 99.0
    plane = jnp.zeros((1, 2, 32, 4), jnp.bfloat16)
    a = kvcache.update_rows(plane, 0, new[:, :8], jnp.int32(5))
    b = kvcache.update_rows(plane, 0, new[:, :8], jnp.asarray([5, 5]))
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
    assert float(a[0, 1, 12, 0]) == 7.0 and float(a[0, 1, 13, 0]) == 0.0
    # a slot at a negative offset writes at 0; a row past the end is dropped
    c = kvcache.update_rows(plane, 0, new[:, :4], jnp.asarray([-1, 30]))
    assert float(c[0, 0, 3, 0]) == 3.0 and float(c[0, 1, 31, 0]) == 1.0


@pytest.mark.parametrize("plen", [5, 16, 17, 40, 75])
def test_a_private_cache_is_spliced_into_the_slabs_rings_mid_ring(plen):
    """`KVCache.spliced` of a private cache (rings in position order)
    into a slab whose rings keep 16 columns: column `t % 16` holds
    position t of the last 16 before `plen`; the full planes are copied
    as they are."""
    cfg = mimo_v2.MimoV2Config.from_hf(_tiny_config()["hf_config"])
    spec = mimo_v2.cache_spec(cfg)
    one = kvcache.init_cache_spec(spec.unrolled(), 1, 96)
    mark = jnp.arange(96, dtype=jnp.float32)[None, None, :, None]
    one = one.replace(ring_k=jnp.broadcast_to(mark, one.ring_k.shape).astype(
        jnp.bfloat16), full_k=jnp.broadcast_to(
            mark, one.full_k.shape).astype(jnp.bfloat16))
    slab = kvcache.init_cache_spec(spec, 3, 64, per_slot_pos=True)
    out = slab.spliced(one, 1, plen)
    assert int(out.pos[1]) == plen and out.ring_k.shape == (4, 3, 16, 192)
    held = np.asarray(out.ring_k[2, 1, :, 0], np.float32)
    for t in range(max(0, plen - 16), plen):
        assert held[t % 16] == t
    np.testing.assert_array_equal(
        np.asarray(out.full_k[1, 1, :, 5], np.float32), np.arange(64))
    assert not np.asarray(out.ring_k[:, 0], np.float32).any()


def test_a_layer_without_a_shared_expert_is_its_routed_sum_alone(model):
    """`deepseek_v2.moe_block` on a layer with no `shared_gate` leaf:
    the held experts' part of the routed sum and nothing else; the
    router's arguments are dots3's but for the experts (sigmoid,
    noaux_tc, one group), so `route` takes the same path bit for bit."""
    from bigdl_tpu.models.dots3_note import Dots3NoteConfig

    cfg = model.config
    assert deepseek_v2._routing(cfg) == dict(
        deepseek_v2._routing(Dots3NoteConfig()), top_k=3)
    lp = model.params["layers"][1]
    assert "shared_gate" not in lp and "router_bias" in lp
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 5, 64)),
                    jnp.bfloat16)
    y, st = mimo_v2.moe_block(x, lp, model.params["experts"], jnp.int32(0),
                              cfg)
    logits = jnp.dot(x.reshape(-1, 64).astype(jnp.float32),
                     lp["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    want, st2 = moe_routed.routed_experts(
        x.reshape(-1, 64), logits, model.params["experts"], cfg.share,
        act=jax.nn.silu, layer=jnp.int32(0), bias=lp["router_bias"],
        **deepseek_v2._routing(cfg))
    np.testing.assert_array_equal(np.asarray(y, np.float32).reshape(-1, 64),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(st), np.asarray(st2))
    # the choice is dots3's: the same logits, bias and arguments
    a = moe_routed.route(logits, 3, method="noaux_tc", scoring="sigmoid",
                         norm_topk_prob=True, bias=lp["router_bias"])
    b = moe_routed.route(logits, bias=lp["router_bias"],
                         **deepseek_v2._routing(cfg))
    for u, w in zip(a, b):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(w))


def test_the_programs_trace_one_body_a_kind_of_layer(model):
    """Six layers, three kinds (full dense, window routed, full routed):
    the jaxpr of a forward holds three distinct layer bodies."""
    cache = model.family.new_cache(model.config, 1, 64, "bf16")
    jaxpr = jax.make_jaxpr(
        lambda p, t, c: mimo_v2.forward(p, model.config, t, c))(
        model.params, jnp.zeros((1, 8), jnp.int32), cache)
    calls = [e for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit")
             and e.params.get("name") == "_layer"]
    assert len(calls) == 6
    assert len({id(e.params["jaxpr"]) for e in calls}) == 3


def _engine(model, **kw):
    from bigdl_tpu.serving.engine import EngineConfig, LLMEngine

    return LLMEngine(model, EngineConfig(
        max_batch=4, max_seq=128, prefill_chunk=32, prefix_cache_entries=0,
        **kw))


def _run(eng, want):
    got = {rid: [] for rid in want}
    done = set()
    for _ in range(400):
        eng.step()
        for rid in want:
            for o in eng.get_outputs(rid):
                got[rid] += list(o.new_token_ids)
                if o.finished:
                    done.add(rid)
        if len(done) == len(want):
            return got
    raise AssertionError(f"unfinished: {set(want) - done}")


def test_engine_serves_the_tiny_model_with_generates_greedy_tokens(model):
    """LLMEngine on the resident decode step over the four planes:
    chunked prefill (chunks of 32 over rings of 16) into a private
    cache, `engine_insert` of a slot MID-RING (prompts of 5, 21, 45 and
    70: shorter than the window, and the ring wrapped 1, 2 and 4 times),
    decode at per-slot positions; greedy tokens equal
    `model.generate()`; the counters reach `/metrics`."""
    from bigdl_tpu.serving.engine import SamplingParams

    eng = _engine(model)
    assert eng.cache.ring_k.shape == (4, 4, 16, 192)
    assert eng.cache.full_v.shape == (2, 4, 128, 64) and eng.cache.k is None
    rng = np.random.default_rng(3)
    prompts = {f"r{i}": [int(x) for x in rng.integers(1, 256, n)]
               for i, n in enumerate((5, 21, 45, 70))}
    for rid, p in prompts.items():
        eng.add_request(rid, p, SamplingParams(max_tokens=8, temperature=0.0))
    got = _run(eng, prompts)
    for rid, p in prompts.items():
        ref = np.asarray(model.generate(np.asarray([p]), max_new_tokens=8,
                                        do_sample=False))[0][len(p):]
        assert got[rid] == [int(t) for t in ref], rid
    text = eng.registry.render()
    series = {ln.split(" ")[0]: float(ln.split(" ")[1])
              for ln in text.splitlines()
              if ln.startswith(("bigdl_tpu_moe_", "bigdl_tpu_swa_",
                                "bigdl_tpu_kv_cache_bytes"))}
    rows = {k: series[f'bigdl_tpu_swa_rows_total{{kind="{k}"}}']
            for k in ("window", "full", "context")}
    # 7 decode steps a request (the first token is the prefill's); a
    # query at position n - 1 holds n rows, 9 of them in a window layer
    held = [n + j for n in (5, 21, 45, 70) for j in range(1, 8)]
    assert rows == {"window": 4 * sum(min(d, 9) for d in held),
                    "full": 2 * sum(held), "context": 6 * sum(held)}
    assert series['bigdl_tpu_moe_assignments_total{held="yes"}'] > 0
    for comp, n in (("full_kv", 2 * 128 * (96 + 64)),
                    ("ring_kv", 4 * 16 * (192 + 128))):
        assert series['bigdl_tpu_kv_cache_bytes{dtype="bf16",component="'
                      f'{comp}"}}'] == 4 * n * 2


def test_rings_are_exported_whole_and_the_refusals_say_why(model):
    from bigdl_tpu.serving.engine import (EngineConfig, LLMEngine,
                                          SamplingParams)

    eng = _engine(model)
    p = [int(x) for x in np.random.default_rng(4).integers(1, 256, 40)]
    eng.add_request("a", p, SamplingParams(max_tokens=6, temperature=0.0))
    for _ in range(4):
        eng.step()
    idx = next(i for i, s in enumerate(eng.slots) if s.active)
    kv_len = int(eng.cache.pos[idx])
    planes = eng.cache.seq_slices(kv_len, row=idx)
    assert [tuple(x.shape) for x in planes] == [
        (2, 1, kv_len, 96), (2, 1, kv_len, 64), (4, 1, 16, 192),
        (4, 1, 16, 128)]
    # a private prefill cache keeps the window layers' rows in position
    # order (64 columns, not the ring's 16), and is charged as that
    assert eng._admission_cost(40) == kvcache.cache_nbytes(
        eng._cache_spec.unrolled(), 1, 64)["total"] \
        == 2 * 64 * (2 * (96 + 64) + 4 * (192 + 128))
    one = kvcache.init_cache_spec(eng._cache_spec, 1, 64)
    with pytest.raises(NotImplementedError, match="ring"):
        one.seeded([np.asarray(x) for x in planes], 16)
    with pytest.raises(ValueError, match="ring"):
        LLMEngine(model, EngineConfig(max_batch=2, max_seq=64,
                                      prefix_cache_entries=4))
    with pytest.raises(NotImplementedError, match="K/V rings"):
        model.family.new_cache(model.config, 1, 32, "fp8_e5m2")
    with pytest.raises(ValueError, match="SUPPORTS_PAGED_KV"):
        LLMEngine(model, EngineConfig(max_batch=2, max_seq=64,
                                      kv_page_size=16))
    # no draft module here: the engine's speculation is refused
    assert model.family.speculative_depth is None and model.family.rewindable
    with pytest.raises(ValueError, match="drafts 0 token"):
        _engine(model, speculative_tokens=1)


def test_cost_models_count_what_a_decoded_token_reads(model):
    from bigdl_tpu.observability import roofline

    cfg = model.config
    assert roofline.model_flops_per_token(cfg) == cfg.matmul_flops_per_token()
    assert roofline.attn_flops_per_token(cfg, 10) == 2 * 2 * 8 * (48 + 32) * 10
    # 2 of 6 layers grow with the position: their K and V rows
    assert roofline.kv_bytes_per_token(cfg, 100, "bf16") \
        == 2 * 100 * (96 + 64) * 2
    assert swa.rows_read([-1, 0, 8, 9, 200], 9) == {
        "window": 1 + 9 + 9 + 9, "full": 1 + 9 + 10 + 201}


@pytest.mark.parametrize("fused", [False, True])
def test_checkpoint_conversion_keeps_this_chips_share(model, fused):
    """Tensors under the assumed HF names -> the served tree: the same
    leaves as the seeded model's, the held experts only; a fused
    `qkv_proj` is read as the rows of q, k, v."""
    cfg = model.config
    rng = np.random.default_rng(0)

    def w(o, i):
        return (rng.standard_normal((o, i)) * 0.05).astype(np.float32)

    d = cfg.hidden_size
    out = [("model.embed_tokens.weight", w(cfg.vocab_size, d)),
           ("model.norm.weight", np.ones(d, np.float32)),
           ("lm_head.weight", w(cfg.vocab_size, d))]
    for i in range(cfg.num_hidden_layers):
        k, p = cfg.kind(i), f"model.layers.{i}."
        out += [(p + "input_layernorm.weight", np.ones(d, np.float32)),
                (p + "post_attention_layernorm.weight",
                 np.ones(d, np.float32)),
                (p + "self_attn.o_proj.weight", w(d, k.heads * k.v_head_dim))]
        qkv = [w(k.q_width, d), w(k.k_width, d), w(k.v_width, d)]
        if fused:
            out.append((p + "self_attn.qkv_proj.weight",
                        np.concatenate(qkv)))
        else:
            out += [(p + f"self_attn.{n}_proj.weight", x)
                    for n, x in zip("qkv", qkv)]
        if k.sink:
            out.append((p + mimo_v2.SINK_TENSOR, np.arange(
                k.heads, dtype=np.float32)))
        if cfg.routed(i):
            out += [(p + "mlp.gate.weight", w(16, d)),
                    (p + "mlp.gate.e_score_correction_bias",
                     np.zeros(16, np.float32))]
            for e in range(16):
                out += [(p + f"mlp.experts.{e}.{n}_proj.weight", x)
                        for n, x in (("gate", w(32, d)), ("up", w(32, d)),
                                     ("down", w(d, 32)))]
        else:
            out += [(p + f"mlp.{n}_proj.weight", x)
                    for n, x in (("gate", w(128, d)), ("up", w(128, d)),
                                 ("down", w(d, 128)))]
    params = model.family.convert_params(out, cfg, "sym_int4")
    want = jax.tree.map(lambda a: (a.shape, a.dtype), model.params)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert got == want
    assert params["experts"]["experts_gate"].data.shape[:2] == (5, 4)
    np.testing.assert_array_equal(np.asarray(params["layers"][1]["sink"]),
                                  np.arange(8))
    short = [t for t in out if ".mlp.experts.2." not in t[0]]
    with pytest.raises(ValueError, match="held experts"):
        model.family.convert_params(short, cfg, "sym_int4")
    with pytest.raises(ValueError, match="sink"):
        model.family.convert_params(
            [t for t in out if not t[0].endswith("attention_sink_bias")],
            cfg, "sym_int4")


def test_config_refuses_what_it_does_not_implement():
    hf = _tiny_config()["hf_config"]
    for key, bad in (("n_shared_experts", 1), ("n_group", 2),
                     ("scoring_func", "softmax"), ("attention_bias", True)):
        with pytest.raises(NotImplementedError, match=key):
            mimo_v2.MimoV2Config.from_hf(dict(hf, **{key: bad}))
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        mimo_v2.MimoV2Config.from_hf(dict(hf, rope_scaling={"type": "yarn"}))
    with pytest.raises(ValueError, match="shorter than the window"):
        mimo_v2.MimoV2Config.from_hf(dict(hf, window_ring=8))
    with pytest.raises(ValueError, match="name every layer"):
        mimo_v2.MimoV2Config.from_hf(dict(hf, moe_layer_freq=[0, 1]))
    cfg = mimo_v2.MimoV2Config.from_hf(hf)
    assert cfg.routed_scaling_factor == 1.0 and cfg.sliding_window is None
    assert dataclasses.replace(cfg, window_ring=0).ring == 128
