"""The plain AFMoE (Trinity-Mini) reference against the program on the
CPU at a tiny size at which every mechanism binds (12 layers, window 24
in a ring of 32, sequences of 100): logits of a chunked prefill through
a private cache, the splice into the slab's rings and decoded tokens at
positions below, at and past the window and past a wrap of the ring,
with the whole layer held and with a quarter of the experts; chunked
prefill against one shot; the layer check with its seven controls (each
fails a limit); the share test (the four shares' routed parts plus the
shared expert counted ONCE add up to the uncut layer); the reference's
short cuts against the plain forms; the costs against hand arithmetic at
the published widths; the new metric; the configuration's file against
the catalog row."""

import json

import numpy as np
import pytest

import _paths
from harness import (checks_afmoe as checks, costs_afmoe,
                     reference_afmoe as reference, spec,
                     weights_afmoe as weights)

CONFIG = "trinity-mini-ep4-int4"
CELL = "trinity-mini-ep4-thinking-closed"
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
    config["hf_config"].update(num_experts=held, ep_size=ep_size,
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
        1, config["reference"]["vocab"], 100)
    fwd = jax.jit(model.family.forward, static_argnums=1)
    return config, model, fwd, ids, box["canonical"]


def _row_errors(got, ref):
    """The relative L2 of each position's logits. A branch's output is
    normed to unit size before it joins the stream in this family, so
    one swapped expert (the third and fourth score closer than bfloat16
    rounding: one token in four over ten expert layers, at any width,
    since the noise scales with the logits) moves a layer's whole
    contribution to that row, by 0.1-0.3, and through attention the rows
    after it; the rows the coin did not reach stay inside the walk, and
    a wrong mechanism moves every row. The tests hold the MEDIAN row to
    the walk where a quarter of the experts is held (a swap then counts
    one time in four), and the whole to a bound that a wrong model
    (1.41) cannot meet."""
    return (np.linalg.norm(got - ref, axis=-1)
            / np.linalg.norm(ref, axis=-1))


WRONG_MODEL = 0.35


def _through_the_slab(model, fwd, ids, cuts):
    """As the engine: chunks into a private cache (rings in position
    order), the splice into slot 1 of a two-slot slab (rings of 32),
    then the rest one token at a time at per-slot positions."""
    import jax.numpy as jnp

    from bigdl_tpu.ops.kvcache import cache_spec_of, init_cache_spec

    spec_ = cache_spec_of(model.family, model.config)
    cache, rows = init_cache_spec(spec_.unrolled(), 1, 128), []
    for a, b in cuts:
        lg, cache = fwd(model.params, model.config,
                        jnp.asarray(ids[None, a:b], jnp.int32), cache)
        rows.append(np.asarray(lg[0]))
    n = cuts[-1][1]
    slab = init_cache_spec(spec_, 2, 128, per_slot_pos=True).spliced(
        cache, 1, n)
    for t in ids[n:]:
        lg, slab = fwd(model.params, model.config,
                       jnp.asarray([[1], [int(t)]], jnp.int32), slab)
        rows.append(np.asarray(lg[1]))
    return np.concatenate(rows), slab


@pytest.mark.parametrize("cuts", [
    ((0, 12),),                      # decode below, at and past the window
    ((0, 32), (32, 60)),             # the ring wrapped before the splice
    ((0, 30),),                      # decode across the ring's first wrap
    ((0, 32), (32, 64), (64, 90)),
])
def test_prefill_the_splice_and_decode_agree_with_one_pass_of_the_reference(
        built, cuts):
    """The window, the QK norm, rotary in the window layers only, the
    gate, the four norms, the router with its factor and the shared
    expert are all in the logits: every position inside the bfloat16
    walk of ONE pass of the reference. A prompt of 12 decodes through
    positions 12-39 (the window of 24 fills at 23, the ring of 32 wraps
    at 32) in a ring the splice did not fill."""
    config, model, fwd, ids, canonical = built
    n = min(100, cuts[-1][1] + 28)
    ref = np.asarray(reference.all_logits(canonical, config["reference"],
                                          QUANT, ids[:n].tolist()))
    got, slab = _through_the_slab(model, fwd, ids[:n], cuts)
    tol = reference.rounding_walk(config["reference"]["layers"])
    at = cuts[-1][1]
    rows = _row_errors(got, ref)
    if config["reference"]["held"] < config["reference"]["experts_total"]:
        assert np.median(rows[:at]) < tol
        # the decoded positions in three stretches (after 12 rows:
        # before the window fills, until the ring wraps, and after),
        # each on its own
        third = -(-(n - at) // 3)
        for a in range(at, n, third):
            assert np.median(rows[a:a + third]) < tol, (a, n)
    assert reference.relative_l2(got, ref) < WRONG_MODEL
    assert int(slab.pos[1]) == n


def test_chunked_prefill_equals_one_shot(built):
    import jax.numpy as jnp

    config, model, fwd, ids, canonical = built

    def prefill(cuts):
        cache = model.family.new_cache(model.config, 1, 128, "bf16")
        rows = []
        for a, b in cuts:
            lg, cache = fwd(model.params, model.config,
                            jnp.asarray(ids[None, a:b], jnp.int32), cache)
            rows.append(np.asarray(lg[0]))
        return np.concatenate(rows)

    one = prefill(((0, 96),))
    tol = reference.rounding_walk(config["reference"]["layers"])
    for cuts in (((0, 32), (32, 64), (64, 96)), ((0, 20), (20, 96))):
        rows = _row_errors(prefill(cuts), one)
        # the first chunk IS the one shot's first rows
        assert rows[:cuts[0][1]].max() < 1e-3
        assert np.median(rows) < tol / 2
        assert reference.relative_l2(prefill(cuts), one) < WRONG_MODEL


@pytest.mark.parametrize("alter", [{"gate": False}, {"qk_norm": False},
                                   {"rotary_full": True}, {"window": 12},
                                   {"route_scale": False},
                                   {"shared": False}])
def test_a_reference_with_a_mechanism_dropped_is_another_model(built, alter):
    """End to end at toy widths: each planted fault moves the MEDIAN
    row's logits more than twice as far as bfloat16 rounding carries
    the program's."""
    config, model, fwd, ids, canonical = built
    arch = config["reference"]
    sound = np.asarray(reference.all_logits(canonical, arch, QUANT,
                                            ids.tolist()))
    bad = np.asarray(reference.all_logits(canonical, arch, QUANT,
                                          ids.tolist(), alter=alter))
    got, _ = _through_the_slab(model, fwd, ids, ((0, 32), (32, 64), (64, 90)))
    walk = np.median(_row_errors(got, sound))
    assert 0 < walk < reference.rounding_walk(arch["layers"])
    assert np.median(_row_errors(bad, sound)) > 2 * walk


@pytest.fixture(scope="module")
def quarter():
    config = _tiny(4)
    return config, weights.canonical_params(config, 2 ** 31 + 9, check=False)


def test_the_checks_sizes_make_every_mechanism_bind():
    """Published: 3,072 rows in three 1024-row chunks (the window drops
    positions inside the third chunk and for every decoded row; the
    2048-column ring has wrapped at the splice), decoded rows in the
    full kernel's fourth block; layers 0 (window, dense), 2 (window,
    experts) and 3 (full, experts)."""
    doc = _doc()
    eng, arch = doc["engine"], doc["reference"]
    rows = checks.prefill_rows(eng["max_seq"])
    assert rows == 3072 and rows % eng["prefill_chunk"] == 0
    assert rows > arch["window"] + eng["prefill_chunk"] - 1
    from bigdl_tpu.ops.pallas.swa_attention import s_block

    assert rows // s_block(eng["max_seq"], 4 * 128) == 3
    assert arch["window"] // s_block(arch["window"], 4 * 128) == 2
    assert checks.checked_layers(arch) == [0, 2, 3]
    tiny = spec.deep_update(doc, doc["tiny"])
    assert checks.checked_layers(tiny["reference"]) == [0, 2, 3]
    assert checks.prefill_rows(tiny["engine"]["max_seq"]) == 192
    ids = checks.check_ids(2 ** 33 + 1, 256, 40)
    assert ids.min() >= 1 and ids.max() < 256
    assert set(checks.CONTROLS) == {
        "no_gate", "no_qk_norm", "rotary_in_full", "window_halved",
        "no_route_scale", "no_shared_expert", "ring_fp8_e5m2"}


def test_the_layer_check_passes_the_program_on_every_block(quarter):
    """192 rows in chunks of 32 into a private cache, the splice into a
    one-slot slab (the ring wrapped six times), 8 decoded rows through
    the slab: well inside the limits, and far above float32 noise."""
    config, canonical = quarter
    out = checks.layer_check(config, canonical, 2 ** 31 + 9)
    assert out["within"], out["found"]
    assert set(out["found"]) == set(out["limits"])
    for k, v in out["found"].items():
        assert 1e-4 < v < 0.7 * out["limits"][k], k
    assert out["checked_layers"] == [0, 2, 3]
    assert len(out["layers"]["ffn_decode"]) == 3
    assert len(out["layers"]["full_attention_decode"]) == 1
    assert len(out["layers"]["window_attention_decode"]) == 2
    assert [c[0] for c in checks.report(out)] == [
        f"layer_rel_l2.{k}" for k in out["limits"]]


@pytest.mark.parametrize("control,over", [
    ("no_gate", {"full_attention", "window_attention"}),
    ("no_qk_norm", {"full_attention", "window_attention"}),
    ("rotary_in_full", {"full_attention"}),
    ("window_halved", {"window_attention"}),
    ("no_route_scale", {"ffn"}),
    ("no_shared_expert", {"ffn"}),
    ("ring_fp8_e5m2", {"window_attention"}),
])
def test_each_control_comes_out_not_within_the_limits(quarter, control, over):
    """The reference with a planted fault (no gate; no QK norm; rotary
    in a full layer; a window half as long; `route_scale` left out; the
    shared expert left out) or with the window layers' K and V in
    float8_e5m2, the precision below the configuration's, in the
    program's place: refused, by the readings that see that part and by
    no other."""
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


def _routed_layer(canonical, at=0):
    import jax

    lp = {**jax.tree.map(lambda a: a[at], canonical["moe"])}
    return lp, jax.tree.map(lambda a: a[at], canonical["experts"])


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The share test: the routed parts that the four shares of a layer
    give (4 of 16 experts each) plus the shared expert, which every chip
    computes alike, counted ONCE, add up to what the uncut reference
    gives for the whole layer, in the program and in the reference
    alike."""
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
    layer, stacks = _routed_layer(canonical)
    assert {"shared_gate", "shared_up", "shared_down"} <= set(layer)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(reference.feed_forward(x, layer, stacks, arch,
                                                  QUANT))
        shared = uncut - np.asarray(reference.feed_forward(
            x, layer, stacks, arch, QUANT, alter={"shared": False}))
        parts = []
        for rank in range(4):
            ex = jax.tree.map(lambda a: a[4 * rank:4 * rank + 4], stacks)
            parts.append(np.asarray(reference.feed_forward(
                x, layer, ex, arch, QUANT, share=(4 * rank, 4))) - shared)
    assert float(np.abs(shared).max()) > 0
    assert reference.relative_l2(sum(parts) + shared, uncut) < 1e-5
    assert min(float(np.abs(p).max()) for p in parts) > 0
    # the shares with the shared expert counted four times are NOT it
    assert reference.relative_l2(sum(parts) + 4 * shared, uncut) > 0.05

    xb = x.astype(jnp.bfloat16)[None]
    got = []
    for rank in range(4):
        hf = _tiny(4, rank)["hf_config"]
        cfg = get_family(hf["architectures"][0], hf).config_from_hf(hf)
        experts = jax.tree.map(lambda a: a[None, 4 * rank:4 * rank + 4],
                               stacks)
        y, stats = deepseek_v2.moe_block(xb, layer, experts, 0, cfg)
        got.append(np.asarray(y[0], np.float32) - shared)
        assert int(stats[0] + stats[1]) == 24 * 3
    assert reference.relative_l2(sum(got) + shared, uncut) < 0.02


def test_the_references_short_cuts_leave_out_only_products_that_are_zero():
    """A window layer's row blocks against their band and a full
    layer's causal runs against the plain masked softmax over all keys,
    at 1,536 rows (three row blocks, a band of two) and a window of
    600; the routed sum is the plain form already."""
    import jax
    import jax.numpy as jnp

    config = _tiny(4)
    arch = dict(config["reference"], window=600)
    canonical = weights.canonical_params(config, 13, check=False)
    x = jax.random.normal(jax.random.PRNGKey(3), (1536, arch["hidden"]),
                          jnp.float32)
    with jax.default_matmul_precision("highest"):

        def plain(lp, window_layer):
            h, g, d = arch["heads"], arch["kv_heads"], arch["head_dim"]
            s = x.shape[0]
            pos = jnp.arange(s)
            q = reference._rms_norm(
                (x @ reference._dense(lp["q_proj"], QUANT)).reshape(s, h, d),
                lp["q_norm"], arch["norm_eps"])
            k = reference._rms_norm(
                (x @ reference._dense(lp["k_proj"], QUANT)).reshape(s, g, d),
                lp["k_norm"], arch["norm_eps"])
            if window_layer:
                q = reference._rope(q, pos, arch["theta"], d, False)
                k = reference._rope(k, pos, arch["theta"], d, False)
            v = (x @ reference._dense(lp["v_proj"], QUANT)).reshape(s, g, d)
            k, v = (jnp.repeat(t, h // g, axis=1) for t in (k, v))
            sc = jnp.einsum("shd,thd->hst", q, k) * d ** -0.5
            dist = pos[:, None] - pos[None, :]
            ok = (dist >= 0) & ((dist < arch["window"]) if window_layer
                                else True)
            p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
            o = jnp.einsum("hst,thd->shd", p, v).reshape(s, -1)
            o = o * jax.nn.sigmoid(x @ reference._dense(lp["g_proj"], QUANT))
            return o @ reference._dense(lp["o_proj"], QUANT)

        for i, window_layer in ((2, True), (3, False)):
            lp = jax.tree.map(lambda a, i=i: a[i], canonical["attn"])
            got = np.asarray(reference.attention(x, lp, arch, QUANT,
                                                 window_layer))
            assert reference.relative_l2(
                got, np.asarray(plain(lp, window_layer))) < 1e-5


def test_costs_pinned_to_hand_arithmetic_at_the_published_widths():
    """ISSUE 49's arithmetic: attention 27.3 M parameters a layer (2048
    x 9,216 + 4096 x 2048), an expert 6.29 M = 3.54 MB at 0.5625 B a
    parameter, a dense layer's MLP 37.7 M; 2,048 B a position and layer
    of either kind, 2,048 positions of a window layer at most."""
    config = _doc()
    c = costs_afmoe
    dims = c.Dims.from_config(config)
    assert (dims.full_layers, dims.window_layers, dims.expert_layers,
            dims.dense_layers) == (8, 24, 30, 2)
    attn = 2048 * 9216 + 4096 * 2048
    assert round(attn / 1e6, 1) == 27.3
    assert c.attention_bytes(dims, "sym_int4", 32) == attn * 0.5625
    assert c.expert_bytes(dims, "sym_int4", 32) == 3 * 2048 * 1024 * 0.5625
    assert round(3 * 2048 * 1024 / 1e6, 2) == 6.29
    assert round(3 * 2048 * 6144 / 1e6, 1) == 37.7
    assert c.linear_weight_bytes(dims, "sym_int4", 32) == 0.5625 * (
        32 * attn + 2 * 3 * 2048 * 6144 + 30 * 3 * 2048 * 1024
        + 2048 * 50048)
    assert c.bytes_per_position(dims) == 2048
    assert c.kv_bytes_per_token(dims, 5000) == 2048 * (8 * 5000 + 24 * 2048)
    assert c.kv_bytes_per_token(dims, 100) == 2048 * 32 * 100
    # a slot's rings: 24 x 2048 x 2,048 B = 100.7 MB
    assert round(24 * 2048 * 2048 / 1e6, 1) == 100.7
    records = [{"prompt_tokens": 3000, "chunks": [(1.0, 1), (2.0, 2)]},
               {"prompt_tokens": 50, "chunks": [(2.1, 1)]}]
    work = c.serving_work(config, dims, records, "bf16", (1.5, 2.5))
    # the two tokens at t=2.0 sit at cache lengths 3002, 3003; the short
    # request's at 51: its ring holds 51 live columns
    assert work["decode_kv_bytes"] == (3002 + 3003 + 51) * 8 * 2048
    assert work["swa_ring_bytes"] == (2048 + 2048 + 51) * 24 * 2048
    assert work["expert_layers"] == 30 and work["held_experts"] == 32
    assert "decode_kv_bytes" not in c.serving_work(config, dims, records,
                                                   "bf16", None)
    with pytest.raises(NotImplementedError, match="training"):
        c.training_work(config, dims, {}, 1)


def test_the_ring_blocks_reader_reads_its_counter_and_the_cell_is_on_its_lists():
    from harness import layer_metrics, promtext

    path = _paths.BENCH / "layer_metrics" / "swa_ring_blocks_read_share.json"
    blocks = "bigdl_tpu_swa_ring_blocks_total"
    text = lambda live, dead: promtext.parse(                  # noqa: E731
        f'{blocks}{{state="live"}} {live}\n{blocks}{{state="dead"}} {dead}\n')
    obs = {"counters_start": text(100, 40), "counters_end": text(400, 140)}
    assert layer_metrics.read_metric(path, obs) == pytest.approx(75.0)
    # one block a ring: every block live
    assert layer_metrics.read_metric(path, {
        "counters_start": text(0, 0), "counters_end": text(90, 0)}) == 100.0
    # the parent has no such counter
    assert layer_metrics.read_metric(path, {
        "counters_start": promtext.parse(""),
        "counters_end": promtext.parse("")}) is None
    bench = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
    lists = {m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert {"decode_attn_roofline", "swa_decode_attn_roofline",
            "swa_rows_read_share", "swa_ring_blocks_read_share",
            "moe_routed_roofline",
            "moe_experts_hit_share", "moe_held_assignment_share",
            "plain_step_ms", "step_device_ms"} <= lists
    assert not lists & {"prefill_chunk_device_ms",
                        "decode_attn_blocks_read_share"}
    ends = {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", (CELL,))}
    assert {"itl_p95_ms", "setup_s"} <= ends


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's config stands at the file's top
    level with its value; the reduced keys differ and say so; the
    hf_config that runs differs from the row only by the cuts, the
    architecture's name and the share. No width differs and no layer is
    left out."""
    doc = _doc()
    row = [json.loads(x) for x in open(CATALOG) if '"Trinity-Mini"' in x][0]
    assert doc["source"] == row["source_url"]
    assert doc["reduced"] == ["num_experts", "vocab_size"]
    assert (doc["num_experts"], doc["vocab_size"]) == (32, 50048)
    assert doc["published"] == {"num_experts": 128, "vocab_size": 200192,
                                "num_hidden_layers": 32}
    hf = doc["hf_config"]
    for key, value in row["config"].items():
        if key not in ("num_experts", "vocab_size"):
            assert doc[key] == value, key
            assert hf[key] == value, key
    assert hf["num_hidden_layers"] == doc["num_hidden_layers"] == 32
    assert hf["num_experts"] * hf["ep_size"] == 128
    assert hf["vocab_size"] * 4 == 200192
    for line in ("num_experts", "vocab_size", "gate", "qk_norm", "rotary",
                 "norms", "expert_bias", "route_scale", "shared_expert",
                 "embedding", "tensor_names", "weights", "unused_keys"):
        assert doc["assumed"][line], line
    ref = doc["reference"]
    assert ref["pattern"] == [1, 1, 1, 0] * 8
    assert ref["moe"] == [0, 0] + [1] * 30
    assert (ref["heads"], ref["kv_heads"], ref["head_dim"], ref["window"]) \
        == (32, 4, 128, 2048)
    assert ref["embed_scale"] == 2048 ** 0.5
    assert ref["routed_scaling_factor"] == row["config"]["route_scale"]
    eng = doc["engine"]
    assert (eng["max_batch"], eng["max_seq"], eng["prefill_chunk"],
            eng["kv_cache_dtype"], eng["kv_page_size"]) \
        == (16, 8192, 1024, "bf16", 0)
    # the program reads the same sizes off hf_config
    from harness.weights import _family_config

    _, cfg, _ = _family_config(doc)
    assert (cfg.n_full, cfg.n_window, cfg.n_routed_layers, cfg.share,
            cfg.ring, cfg.route_scale, cfg.shared_intermediate) == (
        8, 24, 30, (128, 0, 32), 2048, 2.826, 1024)
    traffic = json.loads((_paths.BENCH / "traffic"
                          / "thinking-closed.json").read_text())
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.7, "min": 128,
        "max": 4096}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 1280, "sigma": 0.4, "min": 640,
        "max": 2560}
    assert traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"] \
        == 6656 <= eng["max_seq"]
    assert (traffic["clients"], traffic["client_stagger_s"],
            traffic["requests_per_client"], traffic["drain_seconds"],
            traffic["trace_start_s"], traffic["trace_seconds"]) == (
        eng["max_batch"], 0.05, 4, 90, 30.0, 3.0)
