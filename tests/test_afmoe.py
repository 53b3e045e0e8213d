"""AFMoE (Trinity-Mini) on the serving path, CPU, tiny widths at which
every mechanism BINDS (12 layers: a head of four and two scanned periods
`W W W F`; window 24 in a ring of 32, 8 query heads on 2 KV heads of 32,
4 of 16 experts held beside a shared one, sequences of 5-100): the ring
kernel swept in blocks (interpret mode) against its XLA form on both
sides of a ring's first fill, the dead blocks' count, the splice of a
prompt shorter than the ring, the scan against the unrolled layers, an
engine run against `model.generate()`, the counters, the refusals and
the checkpoint conversion."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import afmoe, mimo_v2
from bigdl_tpu.models.registry import get_family
from bigdl_tpu.ops import kvcache, swa
from bigdl_tpu.ops.pallas import swa_attention as kernels

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

CONFIG = "trinity-mini-ep4-int4"


def _doc():
    return json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())


def _tiny_config():
    from harness import spec

    doc = _doc()
    return spec.deep_update(doc, doc["tiny"])


@pytest.fixture(scope="module")
def model():
    from harness import weights_afmoe as weights

    return weights.build_model(_tiny_config(), 2 ** 31 + 3, merge=True)[0]


def test_registry_loads_the_family_and_its_cache_lists_four_planes(model):
    cfg = model.config
    assert get_family("AfmoeForCausalLM").name == "afmoe"
    assert (cfg.n_full, cfg.n_window, cfg.ring, cfg.share) == (
        3, 9, 32, (16, 0, 4))
    assert afmoe.scan_plan(cfg) == (4, 4, 2)
    full, win = cfg.full, cfg.swa
    assert (full.rotary, win.rotary, full.window, win.window) == (
        False, True, 0, 24)
    assert full.qk_norm and full.gate and not full.sink and not win.sink
    spec = kvcache.cache_spec_of(model.family, cfg)
    assert [(p.name, p.layers, p.dims, p.ring) for p in spec.planes] == [
        ("full_k", 3, (64,), 0), ("full_v", 3, (64,), 0),
        ("ring_k", 9, (64,), 32), ("ring_v", 9, (64,), 32)]
    assert spec.has_ring and not spec.has_strided
    # the published sizes: 24 window and 8 full layers, seven scanned
    # periods after a head of four, a ring of exactly the window, rows
    # of 512 values, q / k / v and the gate merged to 9216 columns
    pub = afmoe.AfmoeConfig.from_hf(_doc()["hf_config"])
    assert (pub.n_window, pub.n_full, pub.ring, pub.full.k_width,
            pub.num_hidden_layers, pub.share) == (24, 8, 2048, 512, 32,
                                                  (128, 0, 32))
    assert afmoe.scan_plan(pub) == (4, 4, 7)
    assert [pub.kind_name(i) for i in range(4)] == ["window"] * 3 + ["full"]
    assert [pub.routed(i) for i in range(4)] == [False, False, True, True]
    shapes = {k: v.shape for k, v in model.params["attn"].items()}
    assert shapes["qkv_proj"] == (64, 2 * 8 * 32 + 2 * 2 * 32)
    assert "q_proj" not in shapes and "g_proj" not in shapes


@pytest.mark.parametrize("plan,types,dense", [
    ((4, 4, 7), ["sliding_attention"] * 3 + ["full_attention"], 2),
    ((8, 4, 6), ["sliding_attention"] * 3 + ["full_attention"], 5),
    ((0, 1, 32), ["full_attention"], 0),
    ((32, 4, 0), ["sliding_attention"] * 3 + ["full_attention"], 28),
])
def test_scan_plan_ends_the_head_at_a_period_boundary(plan, types, dense):
    cfg = afmoe.AfmoeConfig(layer_types=tuple(types * (32 // len(types))),
                            num_dense_layers=dense)
    assert afmoe.scan_plan(cfg) == plan


def _planes(rng, layers, b, s, width):
    return tuple(jnp.asarray(rng.standard_normal((layers, b, s, width)),
                             jnp.bfloat16) for _ in range(2))


RING, BLOCK = 512, 128


@pytest.mark.parametrize("pos", [0, BLOCK - 1, BLOCK, RING - 1, RING,
                                 3 * RING + 5])
@pytest.mark.parametrize("window", [RING, 300])
def test_ring_kernel_in_blocks_against_its_xla_form(pos, window, monkeypatch):
    """`swa_decode_attention` in interpret mode over a ring of 512 swept
    in four blocks of 128 (the block's byte limit is lowered to force
    them), no sink: while the ring is filling, at the first wrap and
    three rings on; beside each slot an empty one and one a block
    further on."""
    monkeypatch.setattr(kernels, "_BLOCK_BYTES", BLOCK * 64 * 2)
    assert kernels.s_block(RING, 64) == BLOCK
    rng = np.random.default_rng(pos)
    k, v = _planes(rng, 2, 3, RING, 64)
    q = jnp.asarray(rng.standard_normal((3, 8, 32)), jnp.bfloat16)
    posv = jnp.asarray([pos, -1, pos + BLOCK], jnp.int32)
    fn = kernels.swa_decode_attention_pallas.__wrapped__
    got = fn(q, k, v, posv, 0.2, 2, window, layer=1, interpret=True)
    want = swa.decode_xla(
        q, k[1], v[1], swa.ring_live(jnp.maximum(posv, 0), RING, window),
        0.2, 2)
    assert not np.asarray(got[1], np.float32).any()
    for i in (0, 2):
        np.testing.assert_allclose(np.asarray(got[i], np.float32),
                                   np.asarray(want[i], np.float32),
                                   atol=2e-2)
    # what the kernel fetched, by the rule the engine's counter uses
    live, dead = kernels.ring_blocks([int(p) for p in posv], RING, 64)
    per = [min(p // BLOCK, 3) + 1 for p in (pos, pos + BLOCK)]
    assert (live, dead) == (sum(per), 8 - sum(per))


def test_a_dead_block_is_not_read():
    """Columns past a filling ring's `pos` hold NaN: a kernel that read
    them would return NaN."""
    rng = np.random.default_rng(7)
    k, v = _planes(rng, 1, 2, 4096, 512)
    assert kernels.s_block(4096, 512) == 1024
    posv = jnp.asarray([700, 1500], jnp.int32)
    col = jnp.arange(4096)[None, None, :, None]
    k, v = (jnp.where(col >= 2048, jnp.nan, x) for x in (k, v))
    q = jnp.asarray(rng.standard_normal((2, 32, 128)), jnp.bfloat16)
    got = kernels.swa_decode_attention_pallas(
        q, k, v, posv, 0.09, 4, 4096, interpret=True)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert kernels.ring_blocks([700, 1500, -1], 4096, 512) == (3, 5)
    # the published ring: two blocks of 1024, MiMo's one of 128
    assert kernels.ring_blocks([5, 1023, 1024, 9000], 2048, 512) == (6, 2)
    assert kernels.ring_blocks([5, 127, 128, 9000], 128, 1536) == (4, 0)
    assert kernels.ring_blocks([3, 40], 32, 64) == (2, 0)


@pytest.mark.parametrize("plen", [5, 31, 32, 33, 75])
def test_a_prompt_shorter_than_the_ring_is_spliced_where_it_lies(plen):
    """`KVCache.spliced` of a private cache (rings in position order)
    into a slab whose rings keep 32 columns: a prompt of fewer positions
    than the ring leaves the later columns alone, a longer one has
    wrapped; column `t % 32` holds position t."""
    cfg = afmoe.AfmoeConfig.from_hf(_tiny_config()["hf_config"])
    spec = afmoe.cache_spec(cfg)
    one = kvcache.init_cache_spec(spec.unrolled(), 1, 96)
    mark = jnp.arange(96, dtype=jnp.float32)[None, None, :, None]
    one = one.replace(ring_k=jnp.broadcast_to(mark, one.ring_k.shape).astype(
        jnp.bfloat16))
    slab = kvcache.init_cache_spec(spec, 3, 64, per_slot_pos=True)
    out = slab.spliced(one, 1, plen)
    assert int(out.pos[1]) == plen and out.ring_k.shape == (9, 3, 32, 64)
    held = np.asarray(out.ring_k[4, 1, :, 0], np.float32)
    for t in range(max(0, plen - 32), plen):
        assert held[t % 32] == t
    assert not np.asarray(out.ring_k[:, 0], np.float32).any()
    # `rows_before` over a ring the prompt has not filled: the rows of
    # positions below 0 are whatever the columns hold, to be masked
    rows = swa.rows_before(out.ring_k, 4, jnp.asarray([0, plen, 0]), 23)
    got = np.asarray(rows[1, :, 0], np.float32)
    for j, t in enumerate(range(plen - 23, plen)):
        if t >= max(0, plen - 32):
            assert got[j] == t


def test_a_programs_size_does_not_grow_with_the_periods(model):
    """Two periods under ONE `lax.scan`; the same model at twice the
    depth (five periods) lowers to a program of the same size."""
    cfg = model.config
    ids = jnp.asarray(np.random.default_rng(5).integers(1, 256, (2, 40)))

    def lowered(layers):
        c = dataclasses.replace(
            cfg, num_hidden_layers=layers,
            layer_types=cfg.layer_types[:4] * (layers // 4))
        rep = layers // 12
        p = dict(model.params)
        for group, rows in (("attn", layers), ("moe", layers - 2),
                            ("experts", layers - 2)):
            p[group] = jax.tree.map(
                lambda a, rows=rows: jnp.concatenate([a] * (rep + 1))[:rows],
                model.params[group])
        cache = model.family.new_cache(c, 2, 64, "bf16")
        return afmoe.scan_plan(c), jax.jit(
            lambda p_, c_: afmoe.forward(p_, c, ids, c_)).lower(
            p, cache).as_text()

    (plan, small), (plan2, large) = lowered(12), lowered(24)
    assert plan == (4, 4, 2) and plan2 == (4, 4, 5)
    assert small.count("stablehlo.while") == large.count("stablehlo.while")
    assert len(large) < len(small) * 1.02, (len(small), len(large))


def test_scanned_layers_equal_unrolled_layers(model, monkeypatch):
    cfg = model.config
    ids = jnp.asarray(np.random.default_rng(6).integers(1, 256, (2, 40)))
    cache = model.family.new_cache(cfg, 2, 64, "bf16")
    lg, c1 = afmoe.forward(model.params, cfg, ids, cache)
    monkeypatch.setattr(afmoe, "scan_plan", lambda c: (12, 4, 0))
    lg2, c2 = afmoe.forward(model.params, cfg, ids, cache)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg2), atol=1e-4)
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)
    assert c1.stats is not None and int(c1.stats[3]) == 10   # expert layers


def test_the_programs_trace_one_body_a_kind_of_layer(model):
    """Twelve layers, three kinds in the head (window dense, window
    routed, full routed) and the same two routed kinds in the scan."""
    cache = model.family.new_cache(model.config, 1, 64, "bf16")
    jaxpr = jax.make_jaxpr(
        lambda p, t, c: afmoe.forward(p, model.config, t, c))(
        model.params, jnp.zeros((1, 8), jnp.int32), cache)
    calls = [e for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit")
             and e.params.get("name") == "_layer"]
    assert len(calls) == 4
    assert len({id(e.params["jaxpr"]) for e in calls}) == 3
    scan = [e for e in jaxpr.eqns if e.primitive.name == "scan"][0]
    inner = [e for e in scan.params["jaxpr"].jaxpr.eqns
             if e.primitive.name in ("pjit", "jit")
             and e.params.get("name") == "_layer"]
    assert len(inner) == 4 and scan.params["length"] == 2
    assert len({id(e.params["jaxpr"]) for e in inner}) == 2


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
    chunked prefill (chunks of 32 over rings of 32) into a private
    cache, `engine_insert` of prompts shorter than the ring (5, 21) and
    of prompts that wrapped it (45, 70), decode at per-slot positions
    below, across and past the window of 24; greedy tokens equal
    `model.generate()` (on prompts whose eight best logits lie further
    apart than the two paths' bfloat16 rounding: at toy widths one
    request in five has a tie); the counters reach `/metrics`."""
    from bigdl_tpu.serving.engine import SamplingParams

    eng = _engine(model)
    assert eng.cache.ring_k.shape == (9, 4, 32, 64)
    assert eng.cache.full_v.shape == (3, 4, 128, 64) and eng.cache.k is None
    rng = np.random.default_rng(4)
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
              if ln.startswith(("bigdl_tpu_moe_", "bigdl_tpu_swa_"))}
    rows = {k: series[f'bigdl_tpu_swa_rows_total{{kind="{k}"}}']
            for k in ("window", "full", "context")}
    held = [n + j for n in (5, 21, 45, 70) for j in range(1, 8)]
    assert rows == {"window": 9 * sum(min(d, 24) for d in held),
                    "full": 3 * sum(held), "context": 12 * sum(held)}
    # a ring of 32 columns is one block: every block is live
    assert series['bigdl_tpu_swa_ring_blocks_total{state="live"}'] \
        == 9 * len(held)
    assert series['bigdl_tpu_swa_ring_blocks_total{state="dead"}'] == 0
    assert series['bigdl_tpu_moe_assignments_total{held="yes"}'] > 0


def test_the_refusals_say_why(model):
    from bigdl_tpu.serving.engine import EngineConfig, LLMEngine

    eng = _engine(model)
    one = kvcache.init_cache_spec(eng._cache_spec, 1, 64)
    with pytest.raises(NotImplementedError, match="ring"):
        one.seeded([np.zeros((1,))] * 4, 16)
    with pytest.raises(ValueError, match="ring"):
        LLMEngine(model, EngineConfig(max_batch=2, max_seq=64,
                                      prefix_cache_entries=4))
    with pytest.raises(NotImplementedError, match="K/V rings"):
        model.family.new_cache(model.config, 1, 32, "fp8_e5m2")
    with pytest.raises(ValueError, match="SUPPORTS_PAGED_KV"):
        LLMEngine(model, EngineConfig(max_batch=2, max_seq=64,
                                      kv_page_size=16))
    assert model.family.speculative_depth is None and model.family.rewindable
    with pytest.raises(ValueError, match="drafts 0 token"):
        _engine(model, speculative_tokens=1)
    # a private prefill cache keeps the window layers' rows in position
    # order (64 columns, not the ring's 32), and is charged as that
    assert eng._admission_cost(40) == kvcache.cache_nbytes(
        eng._cache_spec.unrolled(), 1, 64)["total"] == 2 * 64 * 12 * 128


def test_cost_models_count_what_a_decoded_token_reads(model):
    from bigdl_tpu.observability import roofline

    cfg = model.config
    assert roofline.model_flops_per_token(cfg) == cfg.matmul_flops_per_token()
    assert roofline.attn_flops_per_token(cfg, 10) == 3 * 4 * 8 * 32 * 10
    # 3 of 12 layers grow with the position: their K and V rows
    assert roofline.kv_bytes_per_token(cfg, 100, "bf16") \
        == 3 * 100 * 128 * 2
    pub = afmoe.AfmoeConfig.from_hf(_doc()["hf_config"])
    d = 2048
    attn = d * (4096 * 2 + 512 * 2) + 4096 * d
    want = 2 * (32 * attn + 2 * 3 * d * 6144
                + 30 * (3 * d * (1024 * 8 / 4 + 1024) + d * 128)
                + d * 50048)
    assert pub.matmul_flops_per_token() == want


def _checkpoint(cfg, rng, vocab_rows):
    d, hd = cfg.hidden_size, cfg.head_dim
    qw, kw = cfg.full.q_width, cfg.full.k_width

    def w(o, i):
        return rng.standard_normal((o, i)).astype(np.float32) * 0.05

    yield "model.embed_tokens.weight", w(vocab_rows, d)
    yield "model.norm.weight", np.ones(d, np.float32)
    yield "lm_head.weight", w(vocab_rows, d)
    for i in range(cfg.num_hidden_layers):
        pre = f"model.layers.{i}."
        for n, shape in (("q_proj", (qw, d)), ("k_proj", (kw, d)),
                         ("v_proj", (kw, d)), ("gate_proj", (qw, d)),
                         ("o_proj", (d, qw))):
            yield pre + f"self_attn.{n}.weight", w(*shape)
        for n in ("q_norm", "k_norm"):
            yield pre + f"self_attn.{n}.weight", 1 + rng.standard_normal(
                hd).astype(np.float32) * 0.1
        for n in ("input_layernorm", "post_attention_layernorm",
                  "pre_mlp_layernorm", "post_mlp_layernorm"):
            yield pre + n + ".weight", np.ones(d, np.float32)
        if not cfg.routed(i):
            ff = cfg.intermediate_size
            yield pre + "mlp.gate_proj.weight", w(ff, d)
            yield pre + "mlp.up_proj.weight", w(ff, d)
            yield pre + "mlp.down_proj.weight", w(d, ff)
            continue
        total, fe = cfg.share.experts_total, cfg.moe_intermediate_size
        yield pre + "mlp.router.gate.weight", w(total, d)
        yield pre + "mlp.expert_bias", rng.standard_normal(total).astype(
            np.float32) * 0.02
        for n, shape in (("gate_proj", (fe, d)), ("up_proj", (fe, d)),
                         ("down_proj", (d, fe))):
            yield pre + f"mlp.shared_experts.{n}.weight", w(*shape)
            for e in range(total):
                yield pre + f"mlp.experts.{e}.{n}.weight", w(*shape) + e


def test_checkpoint_conversion_keeps_this_chips_share(model):
    """`convert_hf_params` from the names under `assumed`: the held
    experts (4-7 of 16 at rank 1) and the vocabulary's slice, the gate
    merged after v; the converted tree serves."""
    hf = dict(_tiny_config()["hf_config"], ep_rank=1)
    cfg = afmoe.AfmoeConfig.from_hf(hf)
    rng = np.random.default_rng(11)
    tensors = list(_checkpoint(cfg, rng, 4 * cfg.vocab_size))
    params = afmoe.convert_hf_params(iter(tensors), cfg, qtype=None)
    by_name = dict(tensors)
    assert params["embed_tokens"].shape == (256, 64)
    np.testing.assert_allclose(
        np.asarray(params["embed_tokens"], np.float32),
        by_name["model.embed_tokens.weight"][256:512], atol=1e-2)
    assert params["experts"]["experts_gate"].shape == (10, 4, 64, 32)
    # expert e's weights were shifted by e: rank 1 holds experts 4-7
    got = np.asarray(params["experts"]["experts_up"][3, 2], np.float32)
    np.testing.assert_allclose(
        got, by_name["model.layers.5.mlp.experts.6.up_proj.weight"].T,
        atol=5e-2)
    qkv = np.asarray(params["attn"]["qkv_proj"][7], np.float32)
    np.testing.assert_allclose(
        qkv[:, -256:], by_name["model.layers.7.self_attn.gate_proj.weight"].T,
        atol=1e-2)
    assert params["moe"]["router"].shape == (10, 64, 16)
    assert params["dense"]["down_proj"].shape == (2, 128, 64)
    cache = afmoe.new_cache(cfg, 1, 32)
    lg, _ = afmoe.forward(params, cfg, jnp.ones((1, 6), jnp.int32), cache)
    assert np.isfinite(np.asarray(lg)).all()
    missing = [t for t in tensors if "layers.3.self_attn.q_norm" not in t[0]]
    with pytest.raises(ValueError, match="q_norm"):
        afmoe.convert_hf_params(iter(missing), cfg, qtype=None)


def test_config_refuses_what_it_does_not_implement():
    hf = _tiny_config()["hf_config"]
    for key, bad in (("score_func", "softmax"), ("n_group", 2),
                     ("rope_scaling", {"rope_type": "yarn"}),
                     ("hidden_act", "gelu")):
        with pytest.raises(NotImplementedError, match=key):
            afmoe.AfmoeConfig.from_hf({**hf, key: bad})
    with pytest.raises(NotImplementedError, match="layer_types"):
        afmoe.AfmoeConfig.from_hf({**hf, "layer_types": ["linear"] * 12})
    with pytest.raises(ValueError, match="every layer"):
        afmoe.AfmoeConfig.from_hf({**hf, "layer_types": ["full_attention"]})
    with pytest.raises(ValueError, match="window_ring"):
        afmoe.AfmoeConfig.from_hf({**hf, "window_ring": 16})
    # without `layer_types`, every fourth layer is full
    del hf["layer_types"]
    assert afmoe.AfmoeConfig.from_hf(hf).layer_types[:4] == (
        "sliding_attention",) * 3 + ("full_attention",)


def test_mimo_v2s_kinds_keep_their_options():
    """The options this family added to `GqaKind` are off in MiMo-V2's
    kinds, and its value scale rides the kind."""
    cfg = mimo_v2.MimoV2Config()
    for kind in (cfg.full, cfg.swa):
        assert kind.rotary and not kind.qk_norm and not kind.gate
        assert kind.value_scale == 0.707
    assert kernels.s_block(128, 1536) == 128       # MiMo's ring: one block
    assert kernels.s_block(16384, 768) == 1024
    assert kernels.s_block(2048, 512) == 1024 and kernels.s_block(8192, 512) \
        == 1024
