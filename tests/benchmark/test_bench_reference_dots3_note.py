"""The plain dots3-note reference against the program on the CPU at a
tiny size at which every mechanism binds (window 9 in a ring of 16,
index_topk 16, sequences of 88-136): logits of a chunked prefill and of
decoded tokens through the cache, with the whole layer held and with a
quarter of the experts; the layer check with its controls; the share
test (the eight shares of one routed layer add up to the uncut layer);
the costs against hand arithmetic at the published widths; the
configuration's file against the catalog row."""

import json

import numpy as np
import pytest

import _paths
from harness import (checks_dots3_note as checks, costs_dots3_note,
                     reference_dots3_note as reference, spec,
                     weights_dots3_note as weights)

CONFIG = "dots3-note-ep8-int4"
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


def _through_the_cache(model, fwd, ids):
    """Chunks of 32, 32 and 16 (each longer than the window, two longer
    than the ring), then 8 tokens one at a time."""
    import jax.numpy as jnp

    cache, rows = model.family.new_cache(model.config, 1, 128, "bf16"), []
    for a, b in ((0, 32), (32, 64), (64, 80)):
        lg, cache = fwd(model.params, model.config,
                        jnp.asarray(ids[None, a:b], jnp.int32), cache)
        rows.append(np.asarray(lg[0]))
    for t in ids[80:88]:
        lg, cache = fwd(model.params, model.config,
                        jnp.asarray([[int(t)]], jnp.int32), cache)
        rows.append(np.asarray(lg[0]))
    return np.concatenate(rows), cache


def test_chunked_prefill_and_decode_agree_while_no_selection_binds(built):
    """With index_topk past the sequence (every position selected) the
    window, the ring's wrap, the gate, the rescale, the indexer's writes
    and the router are all in the logits, and no coin: every position
    inside the bfloat16 walk of ONE pass of the reference."""
    import dataclasses

    config, model, fwd, ids, canonical = built
    arch = dict(config["reference"],
                index=dict(config["reference"]["index"], topk=4096))
    wide = dataclasses.replace(model.config, index_topk=4096)
    ref = np.asarray(reference.all_logits(canonical, arch, QUANT,
                                          ids.tolist()))

    class Wide:
        params, config, family = model.params, wide, model.family

    got, cache = _through_the_cache(Wide, fwd, ids)
    tol = reference.rounding_walk(config["reference"]["layers"])
    assert reference.relative_l2(got[:80], ref[:80]) < tol
    assert reference.relative_l2(got[80:], ref[80:]) < tol
    layers = config["reference"]["layers"] - 1
    assert int(cache.stats[3]) == layers * (3 + 8)
    assert int(cache.stats[0] + cache.stats[1]) == layers * 88 * 3


def test_with_the_selection_binding_the_logits_stay_the_models(built):
    """index_topk 16 of up to 88 positions: one of 16 selected positions
    swapped on a near tie moves a row by a few per cent (the layer check
    holds the selection itself); far from unrelated logits (1.41)."""
    config, model, fwd, ids, canonical = built
    ref = np.asarray(reference.all_logits(canonical, config["reference"],
                                          QUANT, ids.tolist()))
    got, _ = _through_the_cache(model, fwd, ids)
    assert reference.relative_l2(got[:80], ref[:80]) < 0.2
    assert reference.relative_l2(got[80:], ref[80:]) < 0.2


@pytest.fixture(scope="module")
def quarter():
    config = _tiny(4)
    return config, weights.canonical_params(config, 2 ** 31 + 9,
                                            check=False)


def test_the_checks_sizes_make_every_mechanism_bind():
    """From the configuration's file: the rows the layer check prefills
    outnumber ``index_topk`` plus one chunk (the selection drops
    positions inside a chunk and in decode) and the ring (it wraps, and
    the splice lands mid-ring); they are whole chunks; 8 decoded rows."""
    doc = _doc()
    for config in (doc, spec.deep_update(doc, doc["tiny"])):
        eng, hf = config["engine"], config["hf_config"]
        rows = checks.prefill_rows(int(eng["max_seq"]))
        chunk = int(eng["prefill_chunk"])
        ring = int(hf.get("window_ring") or
                   -(-int(hf["sliding_window_size"]) // 128) * 128)
        assert rows > int(hf["index_topk"]) + chunk
        assert rows > ring and rows % ring != 0 or rows > 2 * ring
        assert rows % chunk == 0 and rows + checks.DECODE_ROWS <= \
            int(eng["max_seq"])
        assert int(hf["sliding_window_size"]) < chunk
    assert checks.DECODE_ROWS == 8
    assert checks.prefill_rows(int(doc["engine"]["max_seq"])) == 4096
    assert (int(doc["hf_config"]["index_topk"]),
            int(doc["engine"]["prefill_chunk"])) == (2048, 1024)


def _kinds(arch, layers):
    """What makes a layer a kind: its attention, and whether its
    feed-forward is dense or routed."""
    return {(arch["layer_types"][i],
             "dense" if i < arch["first_k_dense"] else "routed")
            for i in layers}


def test_the_checked_layers_hold_every_kind_of_the_cut():
    """One whole period after layer 1, never every layer: the published
    cut's 14 layers are checked in its first 6 (dense layer 0, layer 1,
    three window layers with experts, the full layer that closes the
    period), which hold every kind ``reference.layer_stack`` yields."""
    doc = _doc()
    arch = doc["reference"]
    covered = checks.checked_layers(arch)
    assert list(covered) == [0, 1, 2, 3, 4, 5] and arch["layers"] == 14
    stack = [(i, kind) for i, kind, _, _ in reference.layer_stack(
        {"layers": [None] * arch["layers"], "experts": {}}, arch)]
    assert _kinds(arch, covered) == _kinds(arch, [i for i, _ in stack]) == {
        ("full_attention", "dense"), ("full_attention", "routed"),
        ("sliding_attention", "routed")}
    # a full layer WITH its indexer and experts, past a window layer
    assert arch["layer_types"][covered[-1]] == "full_attention"
    assert arch["layer_types"][covered[-1] - 1] == "sliding_attention"
    # no window layer: every layer is checked
    assert list(checks.checked_layers(
        {"layers": 3, "layer_types": ["full_attention"] * 3})) == [0, 1, 2]


def test_the_stream_stops_at_the_last_layer_checked():
    """A tiny cut of 10 layers (two periods and more): the check reads
    the first six layers, every reading named as ``layer_limits`` names
    it, and the reference is carried no further."""
    config = _tiny(4)
    types = config["reference"]["layer_types"] + [
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention"]
    config["hf_config"].update(num_hidden_layers=10, layer_types=types)
    config["reference"].update(layers=10, layer_types=types)
    seed = 2 ** 31 + 9
    canonical = weights.canonical_params(config, seed, check=False)
    assert len(canonical["layers"]) == 10
    out = checks.layer_check(config, canonical, seed)
    assert out["checked_layers"] == [0, 1, 2, 3, 4, 5]
    assert out["within"], out["found"]
    assert set(out["found"]) == set(reference.layer_limits(config)) \
        == set(reference.LAYER_LIMITS)
    assert len(out["layers"]["ffn_decode"]) == 6
    assert len(out["layers"]["window_attention_prefill"]) == 3
    assert len(out["layers"]["index_overlap_min"]) == 3
    assert out["seconds"] > 0
    names = [c[0] for c in checks.report(out)]
    assert len(names) == 10 and "index_overlap_min" in names
    assert [c[3:] for c in checks.report(out)
            if c[0] == "index_overlap_min"] == [("floor",)]


def test_the_layer_check_passes_the_program_on_every_block(quarter):
    """128 rows in chunks of 32 into a private cache, the splice into a
    one-slot slab mid-ring, 8 decoded rows through the slab; index
    scores, the selection and the attention with the reference's
    selection handed in: well inside the limits."""
    config, canonical = quarter
    out = checks.layer_check(config, canonical, 2 ** 31 + 9)
    assert out["within"], out["found"]
    assert set(out["found"]) == set(out["limits"])
    found = out["found"]
    for k in ("window_attention_prefill", "window_attention_decode",
              "given_selection_prefill", "given_selection_decode",
              "ffn_prefill", "ffn_decode", "index_score_rel_l2"):
        assert found[k] < 0.6 * out["limits"][k], k
    assert found["index_overlap_min"] >= 14 / 16
    assert len(out["layers"]["ffn_decode"]) == 6
    assert len(out["layers"]["index_overlap_min"]) == 3


@pytest.mark.parametrize("control,over", [
    ("first_2048", {"full_attention", "index_overlap_min"}),
    ("window_512", {"window_attention"}),
    ("no_gate", {"full_attention", "given_selection", "window_attention"}),
    ("no_router_bias", {"ffn"}),
    ("no_rescale", {"given_selection", "window_attention",
                    "index_score_rel_l2"}),
    ("latent_fp8_e5m2", {"given_selection", "window_attention"}),
])
def test_each_control_comes_out_not_within_the_limits(quarter, control, over):
    """The reference with a planted fault (the first 16 positions and not
    the best; a window one short; no gate; the bias left out of the
    choice; no rescale) or with its latent rows in float8_e5m2, in the
    program's place: refused, by the readings that see that part."""
    config, canonical = quarter
    out = checks.layer_check(
        config, canonical, 2 ** 31 + 9, stand_in=checks.AlteredReference(
            config["reference"], QUANT, canonical, checks.CONTROLS[control]))
    assert not out["within"]
    bad = {k for k, v in out["limits"].items()
           if not checks._within(out["found"], {k: v})}
    stems = {k.rsplit("_", 1)[0] if k.endswith(("_prefill", "_decode"))
             else k for k in bad}
    assert over <= stems, (control, out["found"])


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
    give (2 of 16 experts each), with the shared expert counted once,
    add up to what the uncut reference gives for the whole layer, in the
    program and in the reference alike."""
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
    stacks = jax.tree.map(lambda a: a[0], canonical["experts"])
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(reference.feed_forward(x, layer, stacks, arch,
                                                  QUANT))
        shared = np.asarray(reference._swiglu(
            x, *(reference._dense(layer[k], QUANT) for k in
                 ("shared_gate", "shared_up", "shared_down"))))
        parts = []
        for rank in range(8):
            cut = dict(arch, held=2, first_held=2 * rank)
            ex = jax.tree.map(lambda a: a[2 * rank:2 * rank + 2], stacks)
            parts.append(np.asarray(reference.feed_forward(
                x, layer, ex, cut, QUANT)) - shared)
    assert reference.relative_l2(shared + sum(parts), uncut) < 1e-5
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
    shared_p = np.asarray(deepseek_v2.swiglu(
        xb[0], layer["shared_gate"], layer["shared_up"],
        layer["shared_down"]), np.float32)
    total = sum(g - shared_p for g in got) + shared_p
    assert reference.relative_l2(total, uncut) < 0.02


def test_the_references_short_cuts_leave_out_only_products_that_are_zero(
        monkeypatch):
    """PR 38's three savings in the reference, each against the plain
    form of the same function on the same input: an expert run on the
    rows that chose it (capacity 640 of 1,536 rows; and 8, which every
    expert outgrows, so that it takes every row) against every expert
    on every row; a window layer's row blocks against the keys of their
    window (1,024 of 1,536) against all keys; a full layer's rows, index
    scores and selection in three causal runs (512, 1,024 and 1,536
    keys) against one run over all keys."""
    import jax
    import jax.numpy as jnp

    config = _tiny(4)
    arch = config["reference"]
    canonical = weights.canonical_params(config, 7, check=False)
    x = jax.random.normal(jax.random.PRNGKey(3), (1536, arch["hidden"]),
                          jnp.float32)
    layer = canonical["layers"][2]
    assert arch["layer_types"][2] == "sliding_attention"
    ex = jax.tree.map(lambda a: a[1], canonical["experts"])
    assert reference.expert_capacity(14336, _doc()["reference"]) == 896
    assert reference.expert_capacity(40, _doc()["reference"]) == 40
    with jax.default_matmul_precision("highest"):
        plain = np.asarray(reference.feed_forward(x, layer, ex, arch, QUANT,
                                                  capacity=1536))
        chosen = jnp.sum(reference.route(
            jax.nn.sigmoid(x @ layer["router"].astype(jnp.float32)),
            layer["router_bias"], arch)[:, :4] > 0, axis=0)
        assert 8 < int(chosen.min()) and int(chosen.max()) <= 640
        for cap in (640, 8):
            got = np.asarray(reference.feed_forward(x, layer, ex, arch,
                                                    QUANT, capacity=cap))
            assert reference.relative_l2(got, plain) < 1e-6, cap
        banded = np.asarray(reference.attention(x, layer, arch, QUANT,
                                                "sliding_attention"))
        assert reference._causal_groups(29) == [(0, 7), (7, 14), (14, 22),
                                                (22, 29)]
        assert reference._causal_groups(3) == [(0, 1), (1, 2), (2, 3)]
        full, runs = canonical["layers"][1], {}
        for groups in (4, 1):
            monkeypatch.setattr(reference, "CAUSAL_GROUPS", groups)
            probe = {}
            out = reference.attention(x, full, arch, QUANT, "full_attention",
                                      probe=probe)
            runs[groups] = [np.asarray(out)] + [
                np.asarray(probe[k]) for k in ("index_scores", "selected")]
        monkeypatch.setattr(reference, "ROW_BLOCK", 1000)   # one block
        whole = np.asarray(reference.attention(x, layer, arch, QUANT,
                                               "sliding_attention"))
    assert reference.relative_l2(banded, whole) < 1e-6
    assert np.abs(whole).max() > 0
    assert reference.relative_l2(runs[4][0], runs[1][0]) < 1e-6
    live = np.isfinite(runs[1][1])
    assert (np.isfinite(runs[4][1]) == live).all()
    assert np.allclose(runs[4][1][live], runs[1][1][live], rtol=1e-6,
                       atol=1e-6)
    assert (runs[4][2] != runs[1][2]).mean() < 1e-5    # a tie at most
    assert runs[1][2].sum(axis=1).max() == arch["index"]["topk"] == 16


def test_the_references_selection_is_the_top_and_its_window_counts_itself():
    """By hand on 6 positions, topk 2: row 4 of scores (1, 5, 5, 2, 0)
    takes positions 1 and 2; row 0 its only position; "first" (the
    control) takes 0 and 1. A window of 3 lets position 4 see 2, 3, 4."""
    import jax.numpy as jnp

    sc = np.full((6, 6), -np.inf, np.float32)
    for t in range(6):
        sc[t, :t + 1] = 0.0
    sc[4, :5] = [1, 5, 5, 2, 0]
    top = np.asarray(reference.select(jnp.asarray(sc), 2))
    assert np.nonzero(top[4])[0].tolist() == [1, 2]
    assert np.nonzero(top[0])[0].tolist() == [0]
    assert np.nonzero(top[5])[0].tolist() == [0, 1]      # ties: the lower
    first = np.asarray(reference.select(jnp.asarray(sc), 2, "first"))
    assert np.nonzero(first[4])[0].tolist() == [0, 1]


def test_costs_pinned_to_hand_arithmetic_at_the_published_widths():
    """ISSUE 33's arithmetic: full attention 144.05 M parameters a layer
    (5.24 + 25.17 + 2.95 + 16.78 + 83.89 + 0.66 gate + 9.37 indexer),
    window attention 90.8 M, an expert 23.59 M = 13.27 MB at 0.5625 B a
    parameter; 256 B an index key, 1,152 B and 2 x 128 x 1088 a selected
    row, 2,176 B and 2 x 64 x 2112 a window row."""
    config = _doc()
    c = costs_dots3_note
    dims = c.Dims.from_config(config)
    assert (dims.full_layers, dims.window_layers, dims.expert_layers) \
        == (5, 9, 13)
    full = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
            + 128 * 128 * 5120 + 5120 * 128
            + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64)
    window = (5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024 * 64 * 320
              + 64 * 128 * 5120 + 5120 * 64)
    assert round(full / 1e6, 2) == 144.05 and round(window / 1e6, 1) == 90.8
    assert c.attention_bytes(dims, dims.full, "sym_int4", 32, True) \
        == full * 0.5625
    assert c.attention_bytes(dims, dims.window, "sym_int4", 32, False) \
        == window * 0.5625
    assert c.expert_bytes(dims, "sym_int4", 32) == 3 * 5120 * 1536 * 0.5625
    assert c.linear_weight_bytes(dims, "sym_int4", 32) == 0.5625 * (
        5 * full + 9 * window + 3 * 5120 * 13824 + 13 * 3 * 5120 * 1536
        + 5120 * 19008)
    assert c.kv_bytes_per_token(dims, 9000) \
        == 5 * (9000 * 256 + 2048 * 1152) + 9 * 513 * 2176
    assert c.kv_bytes_per_token(dims, 100) \
        == 5 * 100 * (256 + 1152) + 9 * 100 * 2176
    records = [{"prompt_tokens": 3000, "chunks": [(1.0, 1), (2.0, 2)]}]
    work = c.serving_work(config, dims, records, "bf16", (1.5, 2.5))
    # the two tokens of the chunk at t=2.0 sit at cache lengths 3002, 3003
    assert work["dsa_index_bytes"] == (3002 + 3003) * 5 * 256
    assert work["sparse_latent_bytes"] == 2 * 2048 * 5 * 1152
    assert work["sparse_absorbed_flops"] == 2 * 2048 * 5 * 2 * 128 * 1088
    assert work["window_latent_bytes"] == 2 * 513 * 9 * 2176
    assert work["window_absorbed_flops"] == 2 * 513 * 9 * 2 * 64 * 2112
    assert work["expert_layers"] == 13 and work["held_experts"] == 32


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's config stands at the file's top
    level with its value; the three reduced keys differ and say so; the
    hf_config that runs differs from the row only by the cuts, the
    architecture's name and the share."""
    doc = _doc()
    row = [json.loads(x) for x in open(CATALOG)
           if '"dots3-note-prev"' in x][0]
    assert doc["source"] == row["source_url"]
    assert doc["reduced"] == ["layers", "n_routed_experts", "vocab_size"]
    assert (doc["layers"], doc["n_routed_experts"], doc["vocab_size"]) \
        == (14, 32, 19008)
    assert doc["published"] == {"num_hidden_layers": 46,
                                "n_routed_experts": 256,
                                "vocab_size": 152064}
    hf = doc["hf_config"]
    for key, value in row["config"].items():
        if key not in ("n_routed_experts", "vocab_size"):
            assert doc[key] == value, key
        if key not in ("n_routed_experts", "vocab_size", "num_hidden_layers",
                       "layer_types"):
            assert hf[key] == value, key
    assert hf["num_hidden_layers"] == 14
    assert hf["layer_types"] == row["config"]["layer_types"][:14]
    assert hf["n_routed_experts"] * hf["ep_size"] == 256
    for line in ("rescale", "attn_gate", "n_group", "router_bias", "rope",
                 "index_keys", "window", "architectures", "towers",
                 "prefill_chunk", "w_kvb", "padded_n", "index_k_norm"):
        assert doc["assumed"][line], line
    ref = doc["reference"]
    assert ref["layer_types"] == hf["layer_types"]
    assert (ref["full"]["kv_lora_rank"], ref["window"]["kv_lora_rank"],
            ref["window"]["nope"], ref["index"]["topk"],
            ref["window"]["window"]) == (512, 1024, 192, 2048, 513)
