"""The plain MiMo-V2 reference against the program on the CPU at a tiny
size at which every mechanism binds (window 9 in a ring of 16, 16 of 48
dims rotated, sequences of 88): logits of a chunked prefill and of
decoded tokens through the cache, with the whole layer held and with a
quarter of the experts; the layer check with its controls (the sink
dropped, the value scale dropped, rotary on every dim, a window one
short, the router's bias dropped, the precision below bf16: each fails
a tolerance); the share test (the eight shares of one routed layer add
up to the uncut layer, nothing being shared); the reference's short
cuts against the plain forms; the costs against hand arithmetic at the
published widths; the configuration's file against the catalog row."""

import json

import numpy as np
import pytest

import _paths
from harness import (checks_mimo_v2 as checks, costs_mimo_v2,
                     reference_mimo_v2 as reference, spec,
                     weights_mimo_v2 as weights)

CONFIG = "mimo-v25-ep8-int4"
QUANT = {"qtype": "sym_int4", "block": 32}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _doc():
    return json.loads((_paths.BENCH / "configs" / f"{CONFIG}.json").read_text())


def _tiny(ep_size, ep_rank=0):
    """The file's tiny configuration with `ep_size` chips a layer: 16
    experts in all, 16 // ep_size held."""
    doc = _doc()
    config = spec.deep_update(doc, doc["tiny"])
    held = 16 // ep_size
    config["hf_config"].update(n_routed_experts=held, ep_size=ep_size,
                               ep_rank=ep_rank)
    config["reference"].update(held=held, first_held=held * ep_rank)
    return config


@pytest.fixture(scope="module", params=[1, 4], ids=["whole", "quarter"])
def built(request):
    import jax

    config = _tiny(request.param)
    box = {}
    model, _ = weights.build_model(
        config, 2 ** 31 + 5, merge=True,
        with_canonical=lambda canonical, cfg: box.update(
            canonical=jax.tree.map(lambda x: x, canonical)))
    ids = np.random.default_rng(5).integers(
        1, config["reference"]["vocab"], 88)
    fwd = jax.jit(model.family.forward, static_argnums=1)
    return config, model, fwd, ids, box["canonical"]


def _through_the_cache(model, fwd, ids, cuts=((0, 32), (32, 64), (64, 80))):
    """Chunks (each longer than the window, two longer than the ring),
    then the rest one token at a time."""
    import jax.numpy as jnp

    cache, rows = model.family.new_cache(model.config, 1, 128, "bf16"), []
    for a, b in cuts:
        lg, cache = fwd(model.params, model.config,
                        jnp.asarray(ids[None, a:b], jnp.int32), cache)
        rows.append(np.asarray(lg[0]))
    for t in ids[cuts[-1][1]:]:
        lg, cache = fwd(model.params, model.config,
                        jnp.asarray([[int(t)]], jnp.int32), cache)
        rows.append(np.asarray(lg[0]))
    return np.concatenate(rows), cache


def test_chunked_prefill_and_decode_agree_with_one_pass_of_the_reference(
        built):
    """The window, the sink, the partial rotary, the value scale, the two
    kinds' KV heads and the router are all in the logits: every position
    inside the bfloat16 walk of ONE pass of the reference."""
    config, model, fwd, ids, canonical = built
    ref = np.asarray(reference.all_logits(canonical, config["reference"],
                                          QUANT, ids.tolist()))
    got, cache = _through_the_cache(model, fwd, ids)
    tol = reference.rounding_walk(config["reference"]["layers"])
    assert reference.relative_l2(got[:80], ref[:80]) < tol
    assert reference.relative_l2(got[80:], ref[80:]) < tol
    layers = config["reference"]["layers"] - 1
    assert int(cache.stats[3]) == layers * (3 + 8)
    assert int(cache.stats[0] + cache.stats[1]) == layers * 88 * 3


@pytest.mark.parametrize("cuts", [((0, 5),), ((0, 4), (4, 36)),
                                  ((0, 32), (32, 38))])
def test_a_short_prompt_and_chunk_boundaries_inside_the_band(built, cuts):
    """A prompt shorter than the window (5 of 9), and a chunk boundary
    4 and 6 positions from the next chunk's rows: the band reaches back
    into the earlier chunk through the plane."""
    config, model, fwd, ids, canonical = built
    n = cuts[-1][1] + 6
    ref = np.asarray(reference.all_logits(canonical, config["reference"],
                                          QUANT, ids[:n].tolist()))
    got, _ = _through_the_cache(model, fwd, ids[:n], cuts)
    assert reference.relative_l2(got, ref) < reference.rounding_walk(
        config["reference"]["layers"])


@pytest.mark.parametrize("alter", [{"sink": False}, {"value_scale": False},
                                   {"window": 8}, {"router_bias": False}])
def test_a_reference_with_a_mechanism_dropped_is_another_model(built, alter):
    """End to end at toy widths: each planted fault moves the logits
    more than twice as far as bfloat16 rounding carries the program's
    (0.007 here; the routed sum of a quarter of the experts is a small
    part of the residual, so the bias dropped reads 0.02). Rotary on
    every dim moves them by 0.009, where scores are near 0: the layer
    check's control holds that one."""
    config, model, fwd, ids, canonical = built
    arch = config["reference"]
    sound = np.asarray(reference.all_logits(canonical, arch, QUANT,
                                            ids.tolist()))
    bad = np.asarray(reference.all_logits(canonical, arch, QUANT,
                                          ids.tolist(), alter=alter))
    got, _ = _through_the_cache(model, fwd, ids)
    walk = reference.relative_l2(got, sound)
    assert 0 < walk < reference.rounding_walk(arch["layers"])
    assert reference.relative_l2(bad, sound) > 2 * walk


@pytest.fixture(scope="module")
def quarter():
    config = _tiny(4)
    return config, weights.canonical_params(config, 2 ** 31 + 9, check=False)


def test_the_checks_sizes_make_every_mechanism_bind():
    """Published: 2,048 rows in two 1024-row chunks (sixteen rings, a
    chunk boundary inside a band), decoded rows in the full kernel's
    third block; layers 0 (full, dense), 1 (window, experts) and 5
    (full, experts)."""
    doc = _doc()
    eng, arch = doc["engine"], doc["reference"]
    rows = checks.prefill_rows(eng["max_seq"])
    assert rows == 2048 and rows % eng["prefill_chunk"] == 0
    assert rows // eng["prefill_chunk"] >= 2
    assert rows >= 16 * arch["window"]["window"]
    from bigdl_tpu.ops.pallas.swa_attention import s_block

    assert rows // s_block(eng["max_seq"], 4 * 192) >= 2
    assert checks.checked_layers(arch) == [0, 1, 5]
    tiny = spec.deep_update(doc, doc["tiny"])
    assert checks.checked_layers(tiny["reference"]) == [0, 1, 5]
    assert checks.prefill_rows(tiny["engine"]["max_seq"]) == 128
    ids = checks.check_ids(2 ** 33 + 1, 256, 40)
    assert ids.min() >= 1 and ids.max() < 256


def test_the_layer_check_passes_the_program_on_every_block(quarter):
    """128 rows in chunks of 32 into a private cache, the splice into a
    one-slot slab mid-ring (the ring wrapped eight times), 8 decoded
    rows through the slab: well inside the limits, and far above
    float32 noise (bfloat16 rows are what the program keeps)."""
    config, canonical = quarter
    out = checks.layer_check(config, canonical, 2 ** 31 + 9)
    assert out["within"], out["found"]
    assert set(out["found"]) == set(out["limits"])
    for k, v in out["found"].items():
        assert 1e-4 < v < 0.7 * out["limits"][k], k
    assert out["checked_layers"] == [0, 1, 5]
    assert len(out["layers"]["ffn_decode"]) == 3
    assert len(out["layers"]["full_attention_decode"]) == 2
    assert len(out["layers"]["window_attention_decode"]) == 1
    assert [c[0] for c in checks.report(out)] == [
        f"layer_rel_l2.{k}" for k in out["limits"]]


@pytest.mark.parametrize("control,over", [
    ("no_sink", {"window_attention"}),
    ("no_value_scale", {"full_attention", "window_attention"}),
    ("rotary_all", {"full_attention", "window_attention"}),
    ("window_one_short", {"window_attention"}),
    ("no_router_bias", {"ffn"}),
    ("kv_fp8_e5m2", {"full_attention", "window_attention"}),
])
def test_each_control_comes_out_not_within_the_limits(quarter, control, over):
    """The reference with a planted fault (no sink; no value scale;
    rotary on all 48 dims; a window one short; the bias left out of the
    choice) or with its K and V rows in float8_e5m2, the precision below
    the configuration's, in the program's place: refused, by the
    readings that see that part and by no other."""
    config, canonical = quarter
    out = checks.layer_check(
        config, canonical, 2 ** 31 + 9, stand_in=checks.AlteredReference(
            config["reference"], QUANT, canonical, checks.CONTROLS[control]))
    assert not out["within"]
    bad = {k.rsplit("_", 1)[0] for k, v in out["limits"].items()
           if not checks._within(out["found"], {k: v})}
    assert bad == over, (control, out["found"])


def test_canonical_params_marks_the_tree_by_the_layer_check(quarter,
                                                            monkeypatch):
    config, _ = quarter
    seed = 2 ** 31 + 9
    passed = weights.canonical_params(config, seed)
    assert passed["refused"] is False
    own = passed["layer_check"]
    assert own["within"] is True and own["seconds"] > 0
    assert [c[0].replace("layer_rel_l2.", "") for c in own["compared"]] \
        == list(reference.layer_limits(config))
    ids = [3, 5, 7, 9, 11, 13, 15, 17]
    lg = np.asarray(reference.all_logits(passed, config["reference"], QUANT,
                                         ids, first=6))
    assert lg.shape == (2, 256) and np.isfinite(lg).all()
    sound = checks.layer_check
    monkeypatch.setattr(checks, "layer_check", lambda *a, **k: dict(
        sound(*a, **k), within=False))
    refused = weights.canonical_params(config, seed)
    assert refused["refused"] is True
    assert refused["layer_check"]["within"] is False
    assert np.isnan(np.asarray(reference.all_logits(
        refused, config["reference"], QUANT, ids, first=6))).all()


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts that the eight shares of a layer
    give (2 of 16 experts each) add up to what the uncut reference gives
    for the whole layer, in the program and in the reference alike.
    Nothing is shared, so nothing is counted once."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import deepseek_v2
    from bigdl_tpu.models.registry import get_family

    whole = _tiny(1)
    canonical = weights.canonical_params(whole, 11, check=False)
    arch = whole["reference"]
    x = jax.random.normal(jax.random.PRNGKey(2), (24, arch["hidden"]),
                          jnp.float32).astype(jnp.bfloat16).astype(
                              jnp.float32)
    layer = canonical["layers"][1]
    assert not any(k.startswith("shared") for k in layer)
    stacks = jax.tree.map(lambda a: a[0], canonical["experts"])
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(reference.feed_forward(x, layer, stacks, arch,
                                                  QUANT))
        parts = []
        for rank in range(8):
            ex = jax.tree.map(lambda a: a[2 * rank:2 * rank + 2], stacks)
            parts.append(np.asarray(reference.feed_forward(
                x, layer, ex, arch, QUANT, share=(2 * rank, 2))))
    assert reference.relative_l2(sum(parts), uncut) < 1e-5
    assert min(float(np.abs(p).max()) for p in parts) > 0

    xb = x.astype(jnp.bfloat16)[None]
    got = []
    for rank in range(8):
        hf = _tiny(8, rank)["hf_config"]
        cfg = get_family(hf["architectures"][0], hf).config_from_hf(hf)
        experts = jax.tree.map(lambda a: a[None, 2 * rank:2 * rank + 2],
                               stacks)
        y, stats = deepseek_v2.moe_block(xb, layer, experts, 0, cfg)
        got.append(np.asarray(y[0], np.float32))
        assert int(stats[0] + stats[1]) == 24 * 3
    assert reference.relative_l2(sum(got), uncut) < 0.02


def test_the_references_short_cuts_leave_out_only_products_that_are_zero():
    """An expert on the rows that chose it against the expert on every
    row; a window layer's row blocks against their band and a full
    layer's causal runs against the plain masked softmax over all keys,
    at 1,536 rows (three row blocks, a band of two)."""
    import jax
    import jax.numpy as jnp

    config = _tiny(4)
    arch = config["reference"]
    canonical = weights.canonical_params(config, 13, check=False)
    x = jax.random.normal(jax.random.PRNGKey(3), (1536, arch["hidden"]),
                          jnp.float32)
    layer = canonical["layers"][1]
    stacks = jax.tree.map(lambda a: a[0], canonical["experts"])
    with jax.default_matmul_precision("highest"):
        few = np.asarray(reference.feed_forward(x, layer, stacks, arch, QUANT,
                                                capacity=512))
        every = np.asarray(reference.feed_forward(x, layer, stacks, arch,
                                                  QUANT, capacity=1536))
        assert reference.relative_l2(few, every) < 1e-5

        def plain(lp, window_layer):
            a = arch["window" if window_layer else "full"]
            h, g, dk = a["heads"], a["kv_heads"], a["head_dim"]
            s = x.shape[0]
            pos = jnp.arange(s)
            q = reference._rope((x @ reference._dense(lp["q_proj"], QUANT))
                                .reshape(s, h, dk), pos, a["theta"],
                                arch["rotary_dim"], False)
            k = reference._rope((x @ reference._dense(lp["k_proj"], QUANT))
                                .reshape(s, g, dk), pos, a["theta"],
                                arch["rotary_dim"], False)
            v = (x @ reference._dense(lp["v_proj"], QUANT)).reshape(
                s, g, -1) * arch["value_scale"]
            k, v = (jnp.repeat(t, h // g, axis=1) for t in (k, v))
            sc = jnp.einsum("shd,thd->hst", q, k) * dk ** -0.5
            d = pos[:, None] - pos[None, :]
            ok = (d >= 0) & ((d < a["window"]) if window_layer else True)
            sc = jnp.where(ok[None], sc, -jnp.inf)
            if a.get("sink"):
                sc = jnp.concatenate([sc, jnp.broadcast_to(
                    lp["sink"][:, None, None], (h, s, 1))], axis=-1)
            p = jax.nn.softmax(sc, axis=-1)[..., :s]
            return jnp.einsum("hst,thd->shd", p, v).reshape(s, -1) \
                @ reference._dense(lp["o_proj"], QUANT)

        for i, window_layer in ((0, False), (1, True)):
            lp = canonical["layers"][i]
            got = np.asarray(reference.attention(x, lp, arch, QUANT,
                                                 window_layer))
            assert reference.relative_l2(
                got, np.asarray(plain(lp, window_layer))) < 1e-5


def test_costs_pinned_to_hand_arithmetic_at_the_published_widths():
    """ISSUE 45's arithmetic: full attention 89.1 M parameters a layer
    (4096 x 13,568 + 8192 x 4096), window attention 94.4 M (4096 x
    14,848 + 8192 x 4096), an expert 25.17 M = 14.16 MB at 0.5625 B a
    parameter; 2,560 B a position and full layer, 5,120 B a position and
    window layer, 128 of them at most."""
    config = _doc()
    c = costs_mimo_v2
    dims = c.Dims.from_config(config)
    assert (dims.full_layers, dims.window_layers, dims.expert_layers,
            dims.dense_layers) == (3, 9, 11, 1)
    full = 4096 * 13568 + 8192 * 4096
    window = 4096 * 14848 + 8192 * 4096
    assert round(full / 1e6, 1) == 89.1 and round(window / 1e6, 1) == 94.4
    assert c.attention_bytes(dims, dims.full, "sym_int4", 32) == full * 0.5625
    assert c.attention_bytes(dims, dims.window, "sym_int4", 32) \
        == window * 0.5625
    assert c.expert_bytes(dims, "sym_int4", 32) == 3 * 4096 * 2048 * 0.5625
    assert c.linear_weight_bytes(dims, "sym_int4", 32) == 0.5625 * (
        3 * full + 9 * window + 3 * 4096 * 16384 + 4096 * 19072)
    assert c.full_bytes_per_position(dims) == 2560
    assert c.window_bytes_per_position(dims) == 5120
    assert c.kv_bytes_per_token(dims, 9000) \
        == 3 * 9000 * 2560 + 9 * 128 * 5120
    assert c.kv_bytes_per_token(dims, 100) == 3 * 100 * 2560 + 9 * 100 * 5120
    # 7,680 B a position here against 30,720 if all 12 layers were full
    assert 3 * 2560 == 7680 and 12 * 2560 == 30720
    records = [{"prompt_tokens": 3000, "chunks": [(1.0, 1), (2.0, 2)]},
               {"prompt_tokens": 50, "chunks": [(2.1, 1)]}]
    work = c.serving_work(config, dims, records, "bf16", (1.5, 2.5))
    # the two tokens at t=2.0 sit at cache lengths 3002, 3003; the short
    # request's at 51
    assert work["decode_kv_bytes"] == (3002 + 3003 + 51) * 3 * 2560
    assert work["swa_ring_bytes"] == (128 + 128 + 51) * 9 * 5120
    assert work["expert_layers"] == 11 and work["held_experts"] == 32
    assert "decode_kv_bytes" not in c.serving_work(config, dims, records,
                                                   "bf16", None)
    with pytest.raises(NotImplementedError, match="training"):
        c.training_work(config, dims, {}, 1)


def test_the_new_metrics_read_their_counter_and_their_group():
    from harness import layer_metrics, promtext

    base = _paths.BENCH / "layer_metrics"
    rows = "bigdl_tpu_swa_rows_total"
    text = lambda w, f: promtext.parse(                        # noqa: E731
        f'{rows}{{kind="window"}} {w}\n{rows}{{kind="full"}} {f}\n'
        f'{rows}{{kind="context"}} {4 * f}\n')
    obs = {"counters_start": text(100, 1000), "counters_end": text(400, 2700)}
    assert layer_metrics.read_metric(base / "swa_rows_read_share.py", obs) \
        == pytest.approx(100.0 * 300 / 2000)
    # the parent has no such counter; a CPU rehearsal no trace
    assert layer_metrics.read_metric(
        base / "swa_rows_read_share.py",
        {"counters_start": promtext.parse(""),
         "counters_end": promtext.parse("")}) is None
    tr = {"groups": {"swa_decode_attn": {"seconds": 0.002, "calls": 90}},
          "programs": {}, "busy_s": 1.0, "window_s": 3.0}
    obs = {"trace": tr, "peaks": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
           "work": {"swa_ring_bytes": 819e9 * 0.001}}
    assert layer_metrics.read_metric(base / "swa_decode_attn_roofline.json",
                                     obs) == pytest.approx(50.0)
    assert layer_metrics.read_metric(
        base / "swa_decode_attn_roofline.json",
        dict(obs, trace=dict(tr, groups={}))) is None
    group = json.loads((_paths.BENCH / "trace_groups"
                        / "swa_decode_attn.json").read_text())
    import re

    from bigdl_tpu.ops.pallas import swa_attention

    full = json.loads((_paths.BENCH / "trace_groups"
                       / "decode_attn.json").read_text())
    hits = lambda g, name: any(re.search(p, name)              # noqa: E731
                               for p in g["patterns"])
    assert hits(group, swa_attention.WINDOW_NAME)
    assert not hits(group, swa_attention.FULL_NAME)
    assert hits(full, swa_attention.FULL_NAME)
    assert not hits(full, swa_attention.WINDOW_NAME)


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's config stands at the file's top
    level with its value; the reduced keys differ and say so; the
    hf_config that runs differs from the row only by the cuts, the
    architecture's name and the share."""
    doc = _doc()
    row = [json.loads(x) for x in open(CATALOG) if '"MiMo-V2.5"' in x][0]
    assert doc["source"] == row["source_url"]
    assert doc["reduced"] == ["layers", "n_routed_experts", "vocab_size"]
    assert (doc["layers"], doc["n_routed_experts"], doc["vocab_size"]) \
        == (12, 32, 19072)
    assert doc["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 256,
                                "vocab_size": 152576}
    hf = doc["hf_config"]
    cut = ("num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq")
    for key, value in row["config"].items():
        if key not in ("n_routed_experts", "vocab_size"):
            assert doc[key] == value, key
        if key not in ("n_routed_experts", "vocab_size") + cut:
            assert hf[key] == value, key
    assert hf["num_hidden_layers"] == 12
    assert hf["hybrid_layer_pattern"] == row["config"][
        "hybrid_layer_pattern"][:12] == [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0]
    assert hf["moe_layer_freq"] == [0] + [1] * 11
    assert hf["n_routed_experts"] * hf["ep_size"] == 256
    assert hf["vocab_size"] * 8 == 152576
    for line in ("layers", "rotary_dims", "rotary_form", "value_scale",
                 "sink", "router_bias", "sliding_window", "unused_keys",
                 "towers", "tensor_names", "architectures", "cache_layout",
                 "prefill_chunk"):
        assert doc["assumed"][line], line
    ref = doc["reference"]
    assert ref["pattern"] == hf["hybrid_layer_pattern"]
    assert ref["moe"] == hf["moe_layer_freq"]
    assert (ref["full"]["kv_heads"], ref["window"]["kv_heads"]) == (4, 8)
    assert ref["rotary_dim"] == int(192 * 0.334) == 64
    assert ref["full"]["sink"] is False and ref["window"]["sink"] is True
    eng = doc["engine"]
    assert (eng["max_batch"], eng["max_seq"], eng["prefill_chunk"],
            eng["kv_cache_dtype"], eng["kv_page_size"]) \
        == (16, 16384, 1024, "bf16", 0)
    # the program reads the same sizes off hf_config
    from harness.weights import _family_config

    _, cfg, _ = _family_config(doc)
    assert (cfg.n_full, cfg.n_window, cfg.n_routed_layers, cfg.share,
            cfg.ring, cfg.rotary_dim) == (3, 9, 11, (256, 0, 32), 128, 64)
    traffic = json.loads((_paths.BENCH / "traffic"
                          / "mixedlen-closed.json").read_text())
    assert traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"] \
        == 13568 <= eng["max_seq"]
    assert traffic["clients"] == eng["max_batch"]
