"""The plain DeepSeek-V3.2 reference against the program on the CPU at a
tiny size at which every mechanism binds (index_topk 16, 16 experts in 4
groups of 4 with 2 groups a token, 1 dense + 1 expert layer + the MTP
module, sequences of 88-136): logits of a chunked prefill and of decoded
tokens through the cache, with the whole layer held and with a quarter
of the experts; the layer check with its seven controls; the share test
(the shares of one routed layer add up to the uncut layer); the costs
against hand arithmetic at the published widths (rows, not tokens); the
configuration's file against the catalog row."""

import json

import numpy as np
import pytest

import _paths
from harness import (checks_deepseek_v32 as checks, costs_deepseek_v32,
                     reference_deepseek_v32 as reference, spec,
                     weights_deepseek_v32 as weights)

CONFIG = "deepseek-v32-ep8-int4"
QUANT = {"qtype": "sym_int4", "block": 32}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _doc():
    return json.loads((_paths.BENCH / "configs" / f"{CONFIG}.json").read_text())


def _tiny(ep_size, ep_rank=0):
    """The file's tiny configuration with `ep_size` chips a layer: 16
    experts in 4 groups, 16 // ep_size held."""
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
    """Chunks of 32, 32 and 16, then 8 tokens one at a time."""
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
    """With index_topk past the sequence (every position selected) YaRN,
    the indexer's writes and the grouped router are all in the logits,
    and no selection's coin: every position inside the bfloat16 walk of
    ONE pass of the reference."""
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
    # one expert layer (the registry's forward never runs the MTP block)
    assert int(cache.stats[3]) == 3 + 8
    assert int(cache.stats[0] + cache.stats[1]) == 88 * 3


def test_with_the_selection_binding_the_logits_stay_the_models(built):
    config, model, fwd, ids, canonical = built
    ref = np.asarray(reference.all_logits(canonical, config["reference"],
                                          QUANT, ids.tolist()))
    got, _ = _through_the_cache(model, fwd, ids)
    assert reference.relative_l2(got[:80], ref[:80]) < 0.25
    assert reference.relative_l2(got[80:], ref[80:]) < 0.25


@pytest.fixture(scope="module")
def quarter():
    config = _tiny(4)
    return config, weights.canonical_params(config, 2 ** 31 + 9,
                                            check=False)


def test_the_checks_sizes_make_every_mechanism_bind():
    """From the configuration's file: the rows the layer check prefills
    outnumber ``index_topk`` plus one chunk (the selection drops
    positions inside a chunk and in every decoded row); they are whole
    chunks; 8 decoded rows, four singly and two verify steps of two; the
    forced outcomes fit the decoded rows and hold both kinds."""
    doc = _doc()
    for config in (doc, spec.deep_update(doc, doc["tiny"])):
        eng, hf = config["engine"], config["hf_config"]
        rows = checks.prefill_rows(int(eng["max_seq"]))
        chunk = int(eng["prefill_chunk"])
        assert rows > int(hf["index_topk"]) + chunk
        assert rows % chunk == 0 and rows + checks.DECODE_ROWS <= \
            int(eng["max_seq"])
        assert checks.second_rows(rows) == (rows + 5, rows + 7)
    kept = sum(2 if k else 1 for k in checks.OUTCOMES)
    assert kept + 1 <= checks.DECODE_ROWS
    assert True in checks.OUTCOMES and False in checks.OUTCOMES
    assert checks.checked_bodies(doc["reference"]) == [0, 1, "mtp"]


def test_the_layer_check_passes_the_program_on_every_block(quarter):
    config, canonical = quarter
    out = checks.layer_check(config, canonical, 2 ** 31 + 9)
    assert out["within"], out["found"]
    assert set(out["found"]) == set(out["limits"])
    found = out["found"]
    for k in ("given_selection_prefill", "given_selection_decode",
              "verify_rel_l2", "ffn_prefill", "ffn_decode",
              "index_score_rel_l2", "mtp_rel_l2", "head_rel_l2",
              "accepted_stream"):
        assert found[k] < 0.6 * out["limits"][k], k
    assert found["index_overlap_min"] >= 14 / 16
    assert found["verify_live_mismatch"] == 0
    # the dense body, the expert body and the MTP block
    assert len(out["layers"]["ffn_decode"]) == 3
    assert len(out["layers"]["accepted_stream"]) == 3
    # the combine at prefill and decode rows, and the module's head
    assert len(out["layers"]["mtp_rel_l2"]) == 3


@pytest.mark.parametrize("control,over", [
    ("no_group_limit", {"ffn"}),
    ("group_score_max", {"ffn"}),
    ("eh_proj_swapped", {"mtp_rel_l2"}),
    ("no_hnorm", {"mtp_rel_l2"}),
    ("verify_row1_blind", {"verify_live_mismatch"}),
    ("dead_row_kept", {"accepted_stream"}),
    ("latent_fp8_e5m2", {"given_selection", "verify_rel_l2"}),
])
def test_each_control_comes_out_not_within_the_limits(quarter, control, over):
    """The reference with a planted fault (no group limit; groups scored
    by their best and not their two best; the halves of ``eh_proj``
    swapped; ``hnorm`` left out; row 1 of a verify step blind to row 0;
    the dead row of a rejected step kept) or with its latent rows in
    float8_e5m2, in the program's place: refused, by the reading that
    sees that part."""
    config, canonical = quarter
    out = checks.layer_check(
        config, canonical, 2 ** 31 + 9, stand_in=checks.AlteredReference(
            config["reference"], QUANT, canonical, checks.CONTROLS[control],
            checks.prefill_rows(config["engine"]["max_seq"])))
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
    assert np.isnan(np.asarray(reference.all_logits(
        refused, config["reference"], QUANT, ids, first=6))).all()


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts that the four shares of a layer
    give (one routing group of 4 experts each), with the shared expert
    counted once, add up to what the uncut reference gives for the whole
    layer, in the program and in the reference alike."""
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
        for rank in range(4):
            cut = dict(arch, held=4, first_held=4 * rank)
            ex = jax.tree.map(lambda a: a[4 * rank:4 * rank + 4], stacks)
            parts.append(np.asarray(reference.feed_forward(
                x, layer, ex, cut, QUANT)) - shared)
    assert reference.relative_l2(shared + sum(parts), uncut) < 1e-5
    assert min(float(np.abs(p).max()) for p in parts) > 0

    xb = x.astype(jnp.bfloat16)[None]
    got, held_share = [], []
    for rank in range(4):
        hf = _tiny(4, rank)["hf_config"]
        cfg = get_family(hf["architectures"][0], hf).config_from_hf(hf)
        experts = jax.tree.map(lambda a: a[None, 4 * rank:4 * rank + 4],
                               stacks)
        y, stats = deepseek_v2.moe_block(xb, layer, experts, 0, cfg)
        got.append(np.asarray(y[0], np.float32))
        assert int(stats[0] + stats[1]) == 24 * 3
        held_share.append(int(stats[0]))
    # every choice falls on exactly one chip's group
    assert sum(held_share) == 24 * 3
    shared_p = np.asarray(deepseek_v2.swiglu(
        xb[0], layer["shared_gate"], layer["shared_up"],
        layer["shared_down"]), np.float32)
    total = sum(g - shared_p for g in got) + shared_p
    assert reference.relative_l2(total, uncut) < 0.02


def test_the_references_router_keeps_the_best_groups_by_their_two_best():
    """By hand: 4 groups of 2, 2 groups a token, top 3. Group scores are
    sums of two: (0.9 + 0.1, 0.6 + 0.5, 0.55 + 0.5, 0.2 + 0.1): groups 1
    and 2 stay (a best-of-one rule would keep group 0), and the three
    largest among their four experts are chosen; the weights are the
    scores of the chosen over their sum, times the factor."""
    import jax.numpy as jnp

    arch = {"experts_per_tok": 3, "n_group": 4, "topk_group": 2,
            "norm_topk_prob": True, "routed_scaling_factor": 2.0}
    s = jnp.asarray([[0.9, 0.1, 0.6, 0.5, 0.55, 0.5, 0.2, 0.1]])
    w = np.asarray(reference.route(s, jnp.zeros(8), arch))[0]
    assert np.nonzero(w)[0].tolist() == [2, 3, 4]
    np.testing.assert_allclose(w[[2, 3, 4]],
                               np.array([0.6, 0.5, 0.55]) / 1.65 * 2.0,
                               rtol=1e-6)
    by_max = np.asarray(reference.route(s, jnp.zeros(8), arch,
                                        {"group_score": "max"}))[0]
    assert np.nonzero(by_max)[0].tolist() == [0, 2, 3]
    plain = np.asarray(reference.route(s, jnp.zeros(8), arch,
                                       {"group_limit": False}))[0]
    assert np.nonzero(plain)[0].tolist() == [0, 2, 4]
    # the bias chooses and does not weigh
    b = jnp.asarray([0, 0, 0, 0, 0, 0, 0.5, 0.45])
    biased = np.asarray(reference.route(s, b, arch))[0]
    assert np.nonzero(biased)[0].tolist() == [2, 6, 7]
    np.testing.assert_allclose(biased[6], 0.2 / 0.9 * 2.0, rtol=1e-6)


def test_costs_pinned_to_hand_arithmetic_at_the_published_widths():
    """ISSUE 43's arithmetic: attention 187.1 M + indexer 14.0 M = 201.1
    M parameters a body, an expert 44.04 M = 24.77 MB at 0.5625 B a
    parameter; 256 B an index key, 1,152 B and 2 x 128 x 1088 a selected
    row. A verify slot-step counts the index keys and the selected rows
    ONCE for its two rows and the products of both."""
    config = _doc()
    c = costs_deepseek_v32
    dims = c.Dims.from_config(config)
    assert (dims.bodies, dims.dense_layers, dims.expert_layers) == (8, 1, 7)
    attn = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256
            + 128 * 128 * 7168)
    index = 1536 * 64 * 128 + 7168 * 128 + 7168 * 64
    assert round(attn / 1e6, 1) == 187.1 and round(index / 1e6, 1) == 14.0
    assert c.attention_bytes(dims, "sym_int4", 32) == (attn + index) * 0.5625
    assert c.expert_bytes(dims, "sym_int4", 32) == 3 * 7168 * 2048 * 0.5625
    assert round(c.expert_bytes(dims, "sym_int4", 32) / 1e6, 2) == 24.77
    assert c.linear_weight_bytes(dims, "sym_int4", 32) == 0.5625 * (
        8 * (attn + index) + 3 * 7168 * 18432 + 7 * 3 * 7168 * 2048
        + 14336 * 7168 + 2 * 7168 * 16160)
    assert c.kv_bytes_per_token(dims, 5000) == 8 * (5000 * 256 + 2048 * 1152)
    assert c.kv_bytes_per_token(dims, 100) == 8 * 100 * (256 + 1152)
    # one request: its prefill's token at 1.0 (no decode step); a step
    # that kept ONE token at 2.0; a step that kept TWO at 2.5 (two chunks
    # 1 ms apart); all three inside the stretch
    records = [{"prompt_tokens": 3000,
                "chunks": [(1.0, 1), (2.0, 1), (2.5, 1), (2.501, 1)]}]
    assert list(c.slot_steps(records, 0.5, 3.0)) == [3001, 3002]
    work = c.serving_work(config, dims, records, "bf16", (0.5, 3.0))
    assert work["slot_steps"] == 2
    assert work["dsa_index_bytes"] == (3002 + 3003) * 8 * 256
    assert work["sparse_latent_bytes"] == 2 * 2048 * 8 * 1152
    assert work["sparse_absorbed_flops"] == 4 * 2048 * 8 * 2 * 128 * 1088
    assert work["expert_layers"] == 7 and work["held_experts"] == 32
    # under index_topk the later row's positions bound the bytes
    short = [{"prompt_tokens": 100, "chunks": [(1.0, 1), (2.0, 1)]}]
    w2 = c.serving_work(config, dims, short, "bf16", (0.5, 3.0))
    assert w2["sparse_latent_bytes"] == 102 * 8 * 1152
    assert w2["sparse_absorbed_flops"] == (101 + 102) * 8 * 2 * 128 * 1088


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's config stands at the file's top
    level with its value; the reduced keys differ and say so; the
    hf_config that runs differs from the row only by the cuts, the
    architecture's name and the share; no width differs."""
    doc = _doc()
    row = [json.loads(x) for x in open(CATALOG)
           if '"name": "DeepSeek-V3.2-Exp"' in x][0]
    assert doc["source"] == row["source_url"]
    assert doc["reduced"] == ["layers", "first_k_dense_replace",
                              "n_routed_experts", "vocab_size"]
    assert (doc["layers"], doc["first_k_dense_replace"],
            doc["n_routed_experts"], doc["vocab_size"]) == (7, 1, 32, 16160)
    assert doc["published"] == {"num_hidden_layers": 61,
                                "first_k_dense_replace": 3,
                                "n_routed_experts": 256,
                                "vocab_size": 129280}
    cut = ("n_routed_experts", "vocab_size", "first_k_dense_replace")
    hf = doc["hf_config"]
    for key, value in row["config"].items():
        if key not in cut:
            assert doc[key] == value, key
        if key not in cut + ("num_hidden_layers", "ep_size"):
            assert hf[key] == value, key
    assert hf["num_hidden_layers"] == 7 and hf["first_k_dense_replace"] == 1
    assert hf["n_routed_experts"] * hf["ep_size"] == 256
    assert hf["num_nextn_predict_layers"] == 1
    assert doc["engine"]["speculative_tokens"] == 1
    for line in ("layers", "first_k_dense_replace", "n_routed_experts",
                 "vocab_size", "mtp_module", "eh_proj_order", "index_rope",
                 "yarn", "index_keys", "router", "router_bias",
                 "speculation", "w_kvb", "padded_n", "index_k_norm"):
        assert doc["assumed"][line], line
    assert "1 of 8" in doc["assumed"]["mtp_module"]
    ref = doc["reference"]
    assert (ref["attn"]["kv_lora_rank"], ref["attn"]["q_lora_rank"],
            ref["index"]["topk"], ref["n_group"], ref["topk_group"],
            ref["experts_per_tok"], ref["mtp"]) == (512, 1536, 2048, 8, 4,
                                                    8, 1)
    assert reference.softmax_scale(ref) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
