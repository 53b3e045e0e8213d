"""dots3-note on the serving path, CPU, tiny widths at which every
mechanism BINDS (window 9 in a ring of 16, index_topk 16, 4 of 16 experts
held, sequences of 40-100): the plane-listing cache spec, the exact
selection against `lax.top_k` (ties included), each new kernel in
interpret mode against its XLA form, the ring across its wrap, the
sigmoid bias-corrected router (and the softmax branch bit for bit as
before), an engine run against `model.generate()` with slots spliced
mid-ring, and the refusals."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import dots3_note
from bigdl_tpu.models.registry import get_family
from bigdl_tpu.ops import dsa, kvcache, moe_routed
from bigdl_tpu.ops.pallas import dsa_attention as kernels

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

CONFIG = "dots3-note-ep8-int4"


def _tiny_config():
    from harness import spec

    doc = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    return spec.deep_update(doc, doc["tiny"])


@pytest.fixture(scope="module")
def model():
    from harness import weights_dots3_note as weights

    return weights.build_model(_tiny_config(), 2 ** 31 + 3, merge=True)[0]


def test_registry_loads_the_family_and_its_cache_lists_three_planes(model):
    cfg = model.config
    assert get_family("Dots3NoteForCausalLM").name == "dots3_note"
    assert (cfg.n_full, cfg.ring, cfg.share) == (3, 16, (16, 0, 4))
    assert cfg.full.q_rescale == pytest.approx((64 / 32) ** 0.5)
    assert cfg.swa.kv_rescale == pytest.approx((64 / 256) ** 0.5)
    spec = kvcache.cache_spec_of(model.family, cfg)
    assert [(p.name, p.layers, p.dims, p.ring) for p in spec.planes] == [
        ("latent", 3, (144,), 0), ("index", 3, (32,), 0),
        ("window", 3, (272,), 16)]
    cache = kvcache.init_cache_spec(spec, 2, 64, per_slot_pos=True)
    assert {k: v.shape for k, v in cache.planes().items()} == {
        "latent": (3, 2, 144, 64), "index": (3, 2, 32, 64),
        "window": (3, 2, 272, 16)}
    want = 2 * 2 * 3 * (144 * 64 + 32 * 64 + 272 * 16)
    assert kvcache.kv_cache_bytes(cache) == kvcache.cache_nbytes(
        spec, 2, 64) == {"codes": want, "scales": 0, "total": want}
    assert cache.max_seq == 64 and cache.stats.shape == (4,)
    # the published ring: 513 positions rounded up as the lanes need
    assert dots3_note.Dots3NoteConfig(
        layer_types=("sliding_attention",), num_hidden_layers=1).ring == 640


@pytest.mark.parametrize("kind", ["kv", "kv_int8", "latent"])
def test_the_geometry_forms_forward_to_the_plane_listing_forms(kind):
    """`init_cache` / `kv_cache_nbytes` are the spec-taking forms of a
    "kv" spec, byte for byte; a spec without a list derives its planes."""
    if kind == "latent":
        spec = kvcache.CacheSpec("latent", 3, latent_dim=144)
        cache = kvcache.init_cache_spec(spec, 2, 32)
        assert list(cache.planes()) == ["latent"]
        assert kvcache.cache_nbytes(spec, 2, 32)["total"] \
            == 3 * 2 * 144 * 32 * 2
        return
    dt = "int8" if kind == "kv_int8" else "bf16"
    a = kvcache.init_cache(2, 3, 16, 4, 8, kv_cache_dtype=dt,
                           per_slot_pos=True)
    b = kvcache.init_cache_spec(kvcache.CacheSpec("kv", 2, 4, 8), 3, 16,
                                kv_cache_dtype=dt, per_slot_pos=True)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), a) \
        == jax.tree.map(lambda x: (x.shape, x.dtype), b)
    assert list(a.planes()) == (["k", "v", "k_scale", "v_scale"]
                                if dt == "int8" else ["k", "v"])
    assert kvcache.kv_cache_nbytes(2, 3, 16, 4, 8, dt) \
        == kvcache.cache_nbytes(kvcache.CacheSpec("kv", 2, 4, 8), 3, 16, dt) \
        == kvcache.kv_cache_bytes(a)


def _top_k_mask(scores, k):
    s = scores.shape[-1]
    want = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        _, idx = jax.lax.top_k(jnp.asarray(scores[b]), min(k, s))
        want[b, np.asarray(idx)] = True
    return want & (scores > -np.inf)


@pytest.mark.parametrize("case", ["ties", "floats", "few_live", "all_tied",
                                  "negative"])
def test_selection_is_lax_top_k_ties_to_the_lower_position(case):
    """The bisection's mask against `lax.top_k`'s indices on seeded
    scores, in XLA ops and in the `dsa_select` kernel (interpret)."""
    rng = np.random.default_rng(7)
    s, k = 256, 16
    if case == "ties":
        sc = rng.integers(-3, 4, (6, s)).astype(np.float32)
    elif case == "floats":
        sc = rng.standard_normal((6, s)).astype(np.float32)
    elif case == "few_live":
        sc = rng.standard_normal((6, s)).astype(np.float32)
    elif case == "all_tied":
        sc = np.full((6, s), 0.5, np.float32)
    else:
        sc = -np.abs(rng.standard_normal((6, s))).astype(np.float32) - 1.0
    live = rng.integers(1, s, 6) if case != "few_live" else \
        np.asarray([1, 5, 15, 16, 17, 3])
    sc = np.where(np.arange(s)[None, :] < live[:, None], sc, -np.inf)
    want = _top_k_mask(sc, k)
    got = np.asarray(dsa.select_topk_mask(jnp.asarray(sc), k))
    np.testing.assert_array_equal(got, want)
    assert (got.sum(axis=1) == np.minimum(live, k)).all()
    in_kernel = np.asarray(kernels.dsa_select_pallas(
        jnp.asarray(sc), k, interpret=True)) != 0
    np.testing.assert_array_equal(in_kernel, want)


def _operands(rng, b, h, c, r, s, layers=2):
    bf = jnp.bfloat16
    return (jnp.asarray(rng.standard_normal((b, h, c)), bf),
            jnp.asarray(rng.standard_normal((b, h, r)), bf),
            jnp.asarray(rng.standard_normal((layers, b, c + r, s)), bf))


@pytest.mark.parametrize("pos", [[0, 3, 127], [128, 200, 255]])
def test_index_score_kernel_against_its_xla_form(pos):
    rng = np.random.default_rng(1)
    b, hi, di, s = 3, 4, 32, 256
    q = jnp.asarray(rng.standard_normal((b, hi, di)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((b, hi)), jnp.float32)
    ix = jnp.asarray(rng.standard_normal((2, b, di, s)), jnp.bfloat16)
    p = jnp.asarray(pos, jnp.int32)
    got = np.asarray(kernels.dsa_index_score_pallas(q, w, ix, p, layer=1,
                                                    interpret=True))
    want = np.asarray(dsa.index_scores_xla(q[:, None], w[:, None], ix[1],
                                           p)[:, 0])
    live = np.arange(s)[None, :] <= np.asarray(pos)[:, None]
    assert (np.isfinite(got) == live).all() and (np.isfinite(want)
                                                 == live).all()
    np.testing.assert_allclose(got[live], want[live], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("pos", [[2, 90, 255], [17, 128, 200]])
def test_sparse_decode_kernel_against_its_xla_form(pos):
    """Only the selected columns count, a block without any included."""
    rng = np.random.default_rng(2)
    qc, qp, lat = _operands(rng, 3, 4, 128, 16, 256)
    p = jnp.asarray(pos, jnp.int32)
    sel = np.zeros((3, 256), bool)
    for b, n in enumerate(pos):
        live = np.arange(n + 1)
        live = live[live >= 128] if n >= 140 else live     # block 0 empty
        sel[b, rng.choice(live, min(8, len(live)), replace=False)] = True
    sel = jnp.asarray(sel)
    got = kernels.sparse_mla_decode_pallas(qc, qp, lat, p, sel, 0.1, layer=1,
                                           interpret=True)
    want = dsa.masked_mla_decode_xla(qc, qp, lat[1], sel, 0.1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.02)


@pytest.mark.parametrize("pos", [[0, 5, 99], [100, 255, 256],
                                 [300, 1000, 4097]])
def test_window_decode_kernel_over_the_ring_across_its_wrap(pos):
    rng = np.random.default_rng(3)
    qc, qp, ring = _operands(rng, 3, 4, 128, 16, 256)
    p = jnp.asarray(pos, jnp.int32)
    got = kernels.window_mla_decode_pallas(qc, qp, ring, p, 0.1, window=100,
                                           layer=0, interpret=True)
    live = dsa.ring_live(p, 256, 100)
    assert np.asarray(live).sum(axis=1).tolist() \
        == [min(n + 1, 100) for n in pos]
    want = dsa.masked_mla_decode_xla(qc, qp, ring[0], live, 0.1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.02)


def _chunk_case(case):
    """`(B, T, S, positions, selection)` of one case of the chunk
    kernel's test; S = 384 sweeps in 128-key blocks."""
    rng = np.random.default_rng(11)
    b, t, s, pos = {
        "p0": (1, 128, 256, [0]),
        "inside_a_block": (1, 128, 384, [70]),
        "across_three_blocks": (1, 128, 384, [200]),
        "selection_drops_keys": (1, 128, 384, [256]),
        "a_row_without_a_key": (1, 128, 256, [128]),
        "no_selection": (1, 128, 256, [128]),
        "two_sequences": (2, 128, 384, [0, 250]),
        "row_blocks_of_256": (1, 512, 1024, [512]),
    }[case]
    at = np.asarray(pos)[:, None, None] + np.arange(t)[None, :, None]
    sel = np.arange(s)[None, None, :] <= at
    if case in ("selection_drops_keys", "two_sequences"):
        # a third of the keys, before the chunk and inside it
        sel = sel & (rng.random((b, t, s)) < 0.33)
        before = sel[-1, :, :pos[-1]]
        assert before.any() and not before.all()
    elif case == "a_row_without_a_key":
        sel = sel.copy()
        sel[0, 5] = False
    elif case == "no_selection":
        sel = None
    return b, t, s, pos, sel


@pytest.mark.parametrize("case", [
    "p0", "inside_a_block", "across_three_blocks", "selection_drops_keys",
    "a_row_without_a_key", "no_selection", "two_sequences",
    "row_blocks_of_256"])
def test_chunk_kernel_against_the_expanded_sweep_in_xla_ops(case,
                                                            monkeypatch):
    """`mla_chunk_attention` interpreted, on layer 1 of a stack, against
    `_sweep_chunk` held to the expanded form: the same rows whatever the
    chunk's first position, the blocks it crosses and the selection."""
    from bigdl_tpu.ops.pallas import mla_chunk_attention as chunk

    monkeypatch.setattr(dots3_note, "_absorb", lambda kind, t: False)
    h, c, r, nope, vd = 4, 128, 64, 128, 128
    kind = dots3_note.MlaKind(h, 64, c, nope, r, vd, 1e4, 1e-5, None, None)
    b, t, s, pos, sel = _chunk_case(case)
    rng = np.random.default_rng(12)

    def bf(*shape, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape), jnp.bfloat16)

    qn, qp, lat = bf(b, t, h, nope), bf(b, t, h, r), bf(2, b, c + r, s)
    w_uk, w_uv = bf(h, nope, c, scale=0.1), bf(h, c, vd, scale=0.1)
    p = jnp.asarray(pos, jnp.int32)
    got = chunk.mla_chunk_attention_pallas(
        qn, qp, lat, p, None if sel is None else jnp.asarray(sel), w_uk,
        w_uv, kind.scale, layer=1, interpret=True)
    if sel is None:
        sel = np.arange(s)[None, None, :] <= (
            np.asarray(pos)[:, None, None] + np.arange(t)[None, :, None])
    want = jax.vmap(lambda a, b_, la, se, pp: dots3_note._sweep_chunk(
        kind, a, b_, la, se, pp, w_uk, w_uv))(qn, qp, lat[1],
                                              jnp.asarray(sel), p)
    assert got.shape == want.shape == (b, t, h, vd)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.03)
    if case == "a_row_without_a_key":
        assert not np.asarray(got)[0, 5].any()
        assert np.abs(np.asarray(got)[0, 6]).max() > 0.1


@pytest.mark.parametrize("t,absorbed", [(256, False), (128, True)])
def test_a_full_layers_chunk_goes_through_the_kernel_by_its_form(
        t, absorbed, monkeypatch):
    """`_sparse_chunk` on the stacks, the selection made from the index
    scores: the expanded form (256 rows at these widths) through the
    kernel where the backend says so, the absorbed form (128 rows)
    never; either way what the XLA sweep gives, which is what the CPU
    runs when nothing is forced."""
    from bigdl_tpu.config import set_flags
    from bigdl_tpu.ops.pallas import mla_chunk_attention as chunk

    seen = []
    real = chunk.mla_chunk_attention_pallas
    monkeypatch.setattr(chunk, "mla_chunk_attention_pallas",
                        lambda *a, **kw: seen.append(1) or real(*a, **kw))
    cfg = dataclasses.replace(
        dots3_note.Dots3NoteConfig(), num_attention_heads=2, kv_lora_rank=256,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        index_n_heads=2, index_head_dim=32, index_topk=96)
    kind = cfg.full
    assert dots3_note._absorb(kind, t) == absorbed
    rng = np.random.default_rng(13)

    def bf(*shape, scale=1.0):
        return jnp.asarray(scale * rng.standard_normal(shape), jnp.bfloat16)

    b, s = 2, 512
    args = (bf(b, t, 2, 128), bf(b, t, 2, 64), bf(b, t, 2, 32),
            jnp.asarray(rng.random((b, t, 2)), jnp.float32),
            bf(2, b, kind.latent_dim, s), bf(2, b, 32, s), jnp.int32(1),
            jnp.asarray([0, 200], jnp.int32), bf(2, 128, 256, scale=0.1),
            bf(2, 256, 128, scale=0.1))

    def run(backend):
        set_flags(attention_backend=backend)
        try:
            return jax.jit(lambda *a: dots3_note._sparse_chunk(
                cfg, kind, *a))(*args)
        finally:
            set_flags(attention_backend="auto")

    o, _, sel = run("pallas")
    assert len(seen) == (0 if absorbed else 1)
    want, _, sel_x = run("auto")
    assert len(seen) == (0 if absorbed else 1)
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(sel_x))
    assert np.asarray(sel).sum(axis=-1).max() == 96
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=0.03)


@pytest.mark.parametrize("plen", [5, 16, 17, 40, 75])
def test_a_private_cache_is_spliced_into_the_slabs_ring_mid_ring(plen):
    """`engine_insert`'s splice: the private cache keeps its window rows
    in position order (with a chunk's padding after them); the slab's
    ring gets the last 16 positions before `plen`, each in its column."""
    spec = kvcache.CacheSpec("latent", 2, latent_dim=8, planes=(
        kvcache.PlaneSpec("latent", 1, (8,)),
        kvcache.PlaneSpec("window", 1, (4,), ring=16)))
    rng = np.random.default_rng(plen)
    one = kvcache.init_cache_spec(spec.unrolled(), 1, 96)
    assert one.window.shape == (1, 1, 4, 96)
    rows = jnp.asarray(rng.standard_normal((1, 1, 4, 96)), jnp.bfloat16)
    one = one.replace(window=rows, latent=jnp.ones_like(one.latent))
    slab = kvcache.init_cache_spec(spec, 3, 64, per_slot_pos=True)
    slab = jax.jit(lambda c, o, n: c.spliced(o, 1, n))(slab, one,
                                                       jnp.int32(plen))
    assert slab.pos.tolist() == [0, plen, 0]
    got = np.asarray(slab.window[0, 1], np.float32)
    for p in range(max(0, plen - 16), plen):
        np.testing.assert_array_equal(
            got[:, p % 16], np.asarray(rows[0, 0, :, p], np.float32))
    assert float(jnp.abs(slab.window[:, 0].astype(jnp.float32)).sum()) == 0
    assert float(slab.latent[0, 1].astype(jnp.float32).sum()) == 8 * 64


def test_the_ring_holds_the_last_positions_across_its_wrap():
    """Chunks longer and shorter than the ring and single rows, per-slot
    positions: column `p % ring` holds position p's row."""
    rng = np.random.default_rng(4)
    ring, c, total = 16, 8, 75
    rows = rng.standard_normal((2, total, c)).astype(np.float32)
    stack = jnp.zeros((2, 2, c, ring), jnp.bfloat16)
    at = 0
    for n in (5, 1, 40, 1, 1, 16, 11):          # 40 > ring: a long chunk
        new = jnp.asarray(rows[:, at:at + n])
        stack = kvcache.update_ring(stack, 1, new,
                                    jnp.asarray([at, at], jnp.int32))
        at += n
    assert at == total
    got = np.asarray(stack[1], np.float32)                   # [B, C, ring]
    for p in range(total - ring, total):
        np.testing.assert_allclose(
            got[:, :, p % ring],
            np.asarray(jnp.asarray(rows[:, p]).astype(jnp.bfloat16),
                       np.float32))
    assert float(jnp.abs(stack[0].astype(jnp.float32)).sum()) == 0


def _old_route(logits, top_k, n_group=1, topk_group=1, method="greedy",
               scaling_factor=1.0, norm_topk_prob=False):
    """`route` as it stood before the sigmoid branch (PR 31's tree)."""
    from jax import lax

    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    n, e = scores.shape
    choice = scores
    if method == "group_limited_greedy":
        group_best = scores.reshape(n, n_group, e // n_group).max(axis=-1)
        _, gi = lax.top_k(group_best, topk_group)
        keep = jnp.zeros((n, n_group), bool).at[
            jnp.arange(n)[:, None], gi].set(True)
        choice = jnp.where(jnp.repeat(keep, e // n_group, axis=1),
                           scores, 0.0)
    topv, topi = lax.top_k(choice, top_k)
    if norm_topk_prob and top_k > 1:
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    else:
        topv = topv * scaling_factor
    return topi.astype(jnp.int32), topv


@pytest.mark.parametrize("kw", [
    dict(method="greedy", norm_topk_prob=True),
    dict(method="greedy", scaling_factor=2.5),
    dict(method="group_limited_greedy", n_group=4, topk_group=2,
         scaling_factor=16.0)], ids=["norm", "scaled", "groups"])
def test_the_softmax_branch_of_route_is_bit_for_bit_as_before(kw):
    logits = jax.random.normal(jax.random.PRNGKey(5), (33, 16), jnp.float32)
    old_i, old_w = _old_route(logits, 3, **kw)
    new_i, new_w = moe_routed.route(logits, 3, **kw)
    np.testing.assert_array_equal(np.asarray(old_i), np.asarray(new_i))
    np.testing.assert_array_equal(np.asarray(old_w), np.asarray(new_w))


def test_the_sigmoid_router_chooses_by_the_bias_and_weighs_without_it():
    """By hand: scores sigmoid(0.0, 1.0, 2.0, -1.0) = 0.5, 0.731, 0.881,
    0.269; the bias lifts expert 3 by 1.0 over expert 1: the choice is
    {2, 3}, the weights are their OWN scores renormalised."""
    logits = jnp.asarray([[0.0, 1.0, 2.0, -1.0]], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0], jnp.float32)
    topi, topw = moe_routed.route(logits, 2, method="noaux_tc",
                                  scoring="sigmoid", bias=bias,
                                  norm_topk_prob=True, scaling_factor=1.0)
    assert sorted(np.asarray(topi)[0].tolist()) == [2, 3]
    s = 1 / (1 + np.exp(-np.asarray([2.0, -1.0])))
    np.testing.assert_allclose(np.sort(np.asarray(topw)[0])[::-1],
                               s / s.sum(), rtol=1e-6)
    plain, _ = moe_routed.route(logits, 2, method="noaux_tc",
                                scoring="sigmoid", bias=jnp.zeros(4))
    assert sorted(np.asarray(plain)[0].tolist()) == [1, 2]
    with pytest.raises(NotImplementedError):
        moe_routed.route(logits, 2, scoring="tanh")


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
    """LLMEngine on the resident decode step over the three planes:
    chunked prefill (chunks of 32 over a ring of 16) into a private
    cache, `engine_insert` of a slot MID-RING (prompts of 21, 45 and 70:
    the ring wrapped 1, 2 and 4 times), decode at per-slot positions past
    index_topk; greedy tokens equal `model.generate()`; the counters
    reach `/metrics`."""
    from bigdl_tpu.serving.engine import SamplingParams

    eng = _engine(model)
    assert eng.cache.window.shape == (3, 4, 272, 16)
    assert eng.cache.index.shape == (3, 4, 32, 128) and eng.cache.k is None
    rng = np.random.default_rng(3)
    prompts = {f"r{i}": [int(x) for x in rng.integers(1, 256, n)]
               for i, n in enumerate((21, 45, 70))}
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
              if ln.startswith(("bigdl_tpu_moe_", "bigdl_tpu_dsa_",
                                "bigdl_tpu_kv_cache_bytes"))}
    live = series['bigdl_tpu_dsa_positions_total{kind="live"}']
    kept = series['bigdl_tpu_dsa_positions_total{kind="selected"}']
    # 3 full layers; every decoded token holds more than 16 positions
    assert kept == 3 * 16 * (3 * 8 - 3) and live > 1.5 * kept
    assert series['bigdl_tpu_moe_assignments_total{held="yes"}'] > 0
    for comp, n in (("latent", 144 * 128), ("index", 32 * 128),
                    ("window", 272 * 16)):
        assert series['bigdl_tpu_kv_cache_bytes{dtype="bf16",component="'
                      f'{comp}"}}'] == 3 * 4 * n * 2


def test_a_ring_is_exported_whole_and_refuses_a_prefix_snapshot(model):
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
        (3, 1, 144, kv_len), (3, 1, 32, kv_len), (3, 1, 272, 16)]
    # a private prefill cache keeps the window layers' rows in position
    # order (64 columns, not the ring's 16), and is charged as that
    assert eng._admission_cost(40) == kvcache.cache_nbytes(
        eng._cache_spec.unrolled(), 1, 64)["total"] \
        == 2 * 3 * 64 * (144 + 32 + 272)
    assert kvcache.CacheSpec("kv", 2, 4, 8).unrolled() \
        == kvcache.CacheSpec("kv", 2, 4, 8)
    one = kvcache.init_cache_spec(eng._cache_spec, 1, 64)
    with pytest.raises(NotImplementedError, match="ring"):
        one.seeded([np.asarray(x) for x in planes], 16)
    with pytest.raises(ValueError, match="ring"):
        LLMEngine(model, EngineConfig(max_batch=2, max_seq=64,
                                      prefix_cache_entries=4))
    with pytest.raises(NotImplementedError, match="bf16 only"):
        model.family.new_cache(model.config, 1, 32, "fp8_e5m2")
    with pytest.raises(ValueError, match="SUPPORTS_PAGED_KV"):
        LLMEngine(model, EngineConfig(max_batch=2, max_seq=64,
                                      kv_page_size=16))


def test_cost_models_count_what_a_decoded_token_reads(model):
    from bigdl_tpu.observability import roofline

    cfg = model.config
    assert roofline.model_flops_per_token(cfg) == cfg.matmul_flops_per_token()
    assert roofline.attn_flops_per_token(cfg, 10) == 3 * 2 * 4 * 32 * 10
    # 3 of 6 layers grow with the position: their latent row and index key
    assert roofline.kv_bytes_per_token(cfg, 100, "bf16") \
        == 3 * 100 * (144 + 32) * 2


def test_checkpoint_conversion_keeps_this_chips_share(model):
    """Tensors under the assumed HF names -> the served tree: the same
    leaves as the seeded model's, the held experts only."""
    cfg = model.config
    hf = _tiny_config()["hf_config"]
    rng = np.random.default_rng(0)

    def w(o, i):
        return (rng.standard_normal((o, i)) * 0.05).astype(np.float32)

    d = cfg.hidden_size
    out = [("model.embed_tokens.weight", w(cfg.vocab_size, d)),
           ("model.norm.weight", np.ones(d, np.float32)),
           ("lm_head.weight", w(cfg.vocab_size, d))]
    for i in range(cfg.num_hidden_layers):
        k, p = cfg.kind(i), f"model.layers.{i}."
        h, c, r = k.num_attention_heads, k.kv_lora_rank, k.qk_rope_head_dim
        out += [(p + "input_layernorm.weight", np.ones(d, np.float32)),
                (p + "post_attention_layernorm.weight",
                 np.ones(d, np.float32)),
                (p + "self_attn.q_a_proj.weight", w(k.q_lora_rank, d)),
                (p + "self_attn.q_a_layernorm.weight",
                 np.ones(k.q_lora_rank, np.float32)),
                (p + "self_attn.q_b_proj.weight",
                 w(h * (k.qk_nope_head_dim + r), k.q_lora_rank)),
                (p + "self_attn.kv_a_proj_with_mqa.weight", w(c + r, d)),
                (p + "self_attn.kv_a_layernorm.weight",
                 np.ones(c, np.float32)),
                (p + "self_attn.kv_b_proj.weight",
                 w(h * (k.qk_nope_head_dim + k.v_head_dim), c)),
                (p + "self_attn.o_proj.weight", w(d, h * k.v_head_dim)),
                (p + "self_attn.gate_proj.weight", w(h, d))]
        if not k.window:
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            out += [(p + "self_attn.indexer.wq_b.weight",
                     w(hi * di, k.q_lora_rank)),
                    (p + "self_attn.indexer.wk.weight", w(di, d)),
                    (p + "self_attn.indexer.weights_proj.weight", w(hi, d)),
                    (p + "self_attn.indexer.k_norm.weight",
                     np.ones(di, np.float32)),
                    (p + "self_attn.indexer.k_norm.bias",
                     np.zeros(di, np.float32))]
        if i < cfg.n_dense:
            ff = cfg.intermediate_size
            out += [(p + "mlp.gate_proj.weight", w(ff, d)),
                    (p + "mlp.up_proj.weight", w(ff, d)),
                    (p + "mlp.down_proj.weight", w(d, ff))]
            continue
        f, total = cfg.moe_intermediate_size, cfg.share.experts_total
        out += [(p + "mlp.gate.weight", w(total, d)),
                (p + "mlp.gate.e_score_correction_bias",
                 np.zeros(total, np.float32)),
                (p + "mlp.shared_experts.gate_proj.weight", w(f, d)),
                (p + "mlp.shared_experts.up_proj.weight", w(f, d)),
                (p + "mlp.shared_experts.down_proj.weight", w(d, f))]
        for e in range(total):
            q = p + f"mlp.experts.{e}."
            out += [(q + "gate_proj.weight", w(f, d)),
                    (q + "up_proj.weight", w(f, d)),
                    (q + "down_proj.weight", w(d, f))]
    family = get_family(hf["architectures"][0], hf)
    params = family.convert_params(out, cfg, "sym_int4")
    shapes = lambda t: jax.tree.map(                          # noqa: E731
        lambda a: (a.shape, str(a.dtype)), t)
    assert shapes(params) == shapes(model.params)
    with pytest.raises(ValueError, match="missing layer 0"):
        family.convert_params(
            [t for t in out if "layers.0.self_attn.gate_proj" not in t[0]],
            cfg, "sym_int4")
    # a config whose layers are of no known kind is refused
    with pytest.raises(ValueError, match="layer_types"):
        dots3_note.Dots3NoteConfig.from_hf(dict(hf, layer_types=["x"] * 6))
    assert dataclasses.replace(cfg, window_ring=0).ring == 128
