"""The plain DeepSeek-V2 reference against the program on the CPU at
tiny widths: logits of prefill, of a chunked prefill into the latent
slab and of decoded tokens through it, with the whole layer held and
with a quarter of the experts held; the share test (all shares add up
to the uncut layer); group-limited routing against a hand-written case;
the costs against hand arithmetic at the published widths."""

import json

import numpy as np
import pytest

import _paths
from harness import (checks_deepseek_v2 as checks, costs_deepseek_v2,
                     reference_deepseek_v2 as reference, serve_runner, served,
                     spec, weights_deepseek_v2 as weights)

CONFIG = "deepseek-v2-ep8-int4"
QUANT = {"qtype": "sym_int4", "block": 32}


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
        1, config["reference"]["vocab"], 40)
    ref = np.asarray(reference.all_logits(
        box["canonical"], config["reference"], QUANT, ids.tolist()))
    fwd = jax.jit(model.family.forward, static_argnums=1)
    return config, model, fwd, ids, ref


def _cache(model, length=64):
    return model.family.new_cache(model.config, 1, length, "bf16")


def test_prefill_logits_agree_with_the_reference(built):
    import jax.numpy as jnp

    config, model, fwd, ids, ref = built
    lg, cache = fwd(model.params, model.config,
                    jnp.asarray(ids[None, :32], jnp.int32), _cache(model))
    assert int(cache.pos) == 32
    assert reference.relative_l2(np.asarray(lg[0]), ref[:32]) \
        < reference.rounding_walk(config["reference"]["layers"])


def test_chunked_prefill_and_decode_through_the_slab_agree(built):
    """Two chunks of 16 into the latent slab, then 8 tokens one at a
    time through it (the absorbed path and the decode kernel's XLA
    twin): every position against ONE pass of the reference."""
    import jax.numpy as jnp

    config, model, fwd, ids, ref = built
    cache, rows = _cache(model), []
    for a in (0, 16):
        lg, cache = fwd(model.params, model.config,
                        jnp.asarray(ids[None, a:a + 16], jnp.int32), cache)
        rows.append(np.asarray(lg[0]))
    for t in ids[32:40]:
        lg, cache = fwd(model.params, model.config,
                        jnp.asarray([[int(t)]], jnp.int32), cache)
        rows.append(np.asarray(lg[0]))
    got = np.concatenate(rows)
    tol = reference.rounding_walk(config["reference"]["layers"])
    assert reference.relative_l2(got[:32], ref[:32]) < tol
    assert reference.relative_l2(got[32:], ref[32:40]) < tol
    # the counters rode along: every routed layer of every program ran
    layers = config["reference"]["layers"] - 1
    assert int(cache.stats[3]) == layers * (2 + 8)
    assert int(cache.stats[0] + cache.stats[1]) == layers * 40 * 3


def test_a_latent_cache_one_precision_lower_reads_several_times_the_error(
        built):
    """The control of the chip's check, at toy size: the same decode
    with the latent rows rounded to float8_e5m2 lies well over the
    sound reading (on the chip, at real widths, it breaks the limit)."""
    import jax.numpy as jnp

    config, model, fwd, ids, ref = built

    def decode(lower):
        lg, cache = fwd(model.params, model.config,
                        jnp.asarray(ids[None, :32], jnp.int32),
                        _cache(model))
        rows = []
        for t in ids[32:40]:
            if lower:
                cache = cache.replace(latent=cache.latent.astype(
                    jnp.float8_e5m2).astype(jnp.bfloat16))
            lg, cache = fwd(model.params, model.config,
                            jnp.asarray([[int(t)]], jnp.int32), cache)
            rows.append(np.asarray(lg[0, -1]))
        return reference.relative_l2(np.stack(rows), ref[32:40])

    assert decode(True) > 2.0 * decode(False)


def _harness_verdict(config, canonical, seed):
    """What `serve_runner` concludes from the reference on `canonical`
    with the sound program's own logits in hand: its two checks."""
    ids = serve_runner.check_ids(seed, config["reference"]["vocab"])
    n = serve_runner.REF_PROMPT_TOKENS
    sound = dict(canonical, refused=False)
    rows = np.asarray(reference.all_logits(
        sound, config["reference"], QUANT, ids.tolist(), first=n - 1))
    rel = serve_runner.logits_errors(reference, canonical,
                                     config["reference"], QUANT, ids,
                                     rows[0], rows[1:])
    seq, tokens = ids[:n].tolist(), []
    for _ in range(3):          # greedy tokens of the reference itself
        tokens.append(int(np.asarray(reference.all_logits(
            sound, config["reference"], QUANT, seq + tokens,
            first=len(seq) + len(tokens) - 1))[0].argmax()))
    found = served.compare(reference, canonical, config["reference"], QUANT,
                           [{"prompt": seq, "tokens": tokens}])
    return (max(rel.values()) <= reference.tolerance(config, "bf16"),
            served.within(found, reference.served_gap_limits(config,
                                                             "bf16")))


@pytest.fixture(scope="module")
def quarter():
    config = _tiny(4)
    return config, weights.canonical_params(config, 2 ** 31 + 9,
                                            check=False)


def test_the_layer_check_passes_the_program_on_every_block(quarter):
    """Prefill chunk and decoded rows, attention through a new latent
    slab and the routed layer on the stacks, each layer on the
    reference's own input: well inside the limits."""
    config, canonical = quarter
    out = checks.layer_check(config, canonical, 2 ** 31 + 9)
    assert out["within"]
    assert set(out["found"]) == set(checks.NAMES) == set(out["limits"])
    assert all(len(v) == config["reference"]["layers"]
               for v in out["layers"].values())
    assert max(out["found"].values()) < 0.5 * min(out["limits"].values())


def test_a_latent_cache_one_precision_lower_comes_out_not_correct(quarter,
                                                                  capsys):
    """The control, committed: the reference with its latent rows in
    float8_e5m2 in the program's place breaks the attention limits and
    no other; as a command its last line says `correct` false."""
    import jax.numpy as jnp

    config, canonical = quarter
    out = checks.layer_check(
        config, canonical, 2 ** 31 + 9,
        stand_in=checks.LowerPrecisionBlocks(
            config["reference"], QUANT, canonical, jnp.float8_e5m2))
    assert not out["within"]
    over = {k for k, v in out["limits"].items() if out["found"][k] > v}
    assert over == {"attention_prefill", "attention_decode"}
    assert checks.main(["--config", CONFIG, "--seed", str(2 ** 31 + 9),
                        "--tiny"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is False and last["control"] == "float8_e5m2"


@pytest.mark.parametrize("fault", ["factor_16_missing", "wrong_expert_index",
                                   "routed_sum_dropped"])
def test_a_planted_fault_in_the_routed_layer_comes_out_not_correct(
        quarter, fault, monkeypatch):
    """A program whose routed layer is wrong (the scaling factor left
    out; the held experts taken for their neighbours'; the routed sum
    left out) is refused by `canonical_params`, and the harness's own
    comparisons with the reference then read not correct."""
    import dataclasses

    import jax.numpy as jnp

    from bigdl_tpu.models import deepseek_v2
    from bigdl_tpu.ops import moe_routed

    config, canonical = quarter
    if fault == "factor_16_missing":
        sound = deepseek_v2._routing
        monkeypatch.setattr(deepseek_v2, "_routing", lambda cfg: dict(
            sound(cfg), scaling_factor=1.0))
    elif fault == "wrong_expert_index":
        sound = moe_routed._combine
        monkeypatch.setattr(
            moe_routed, "_combine", lambda topi, topw, share: sound(
                topi, topw, share._replace(first_held=share.held)))
    else:
        sound = moe_routed.routed_experts

        def dropped(xf, logits, stacks, share, **kw):
            y, stats = sound(xf, logits, stacks, share, **kw)
            return jnp.zeros_like(y), stats

        monkeypatch.setattr(deepseek_v2, "routed_experts", dropped)
    seed = 2 ** 31 + 9
    out = checks.layer_check(config, canonical, seed)
    over = {k for k, v in out["limits"].items() if out["found"][k] > v}
    assert over == {"ffn_prefill", "ffn_decode"}
    refused = dict(canonical, refused=not out["within"])
    assert _harness_verdict(config, refused, seed) == (False, False)


def test_canonical_params_marks_the_tree_by_the_layer_check(quarter,
                                                            monkeypatch):
    """The harness's call runs the check and marks the tree; a tree that
    passed reads correct through the harness's comparisons."""
    config, _ = quarter
    seed = 2 ** 31 + 9
    passed = weights.canonical_params(config, seed)
    assert passed["refused"] is False
    assert _harness_verdict(config, passed, seed) == (True, True)
    sound = checks.layer_check
    monkeypatch.setattr(checks, "layer_check", lambda *a, **k: dict(
        sound(*a, **k), within=False))
    assert weights.canonical_params(config, seed)["refused"] is True


def test_all_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts that the four shares of a layer
    give, with the shared experts and the residual counted once, add up
    to what the uncut reference gives for the whole layer, in the
    program and in the reference alike."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import deepseek_v2
    from bigdl_tpu.models.registry import get_family

    whole = _tiny(1)
    canonical = weights.canonical_params(whole, 11, check=False)
    arch = whole["reference"]
    # values bfloat16 holds, so that program and reference route alike
    x = jax.random.normal(jax.random.PRNGKey(2), (24, arch["hidden"]),
                          jnp.float32).astype(jnp.bfloat16).astype(
                              jnp.float32)
    layer = jax.tree.map(lambda a: a[0], canonical["moe_layers"])
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(reference._moe(x, layer, arch, QUANT))
        shared = np.asarray(reference._swiglu(
            x, *(reference._dense(layer[k], QUANT) for k in
                 ("shared_gate", "shared_up", "shared_down"))))
        parts = []
        for rank in range(4):
            cut = dict(arch, held=4, first_held=4 * rank)
            lp = dict(layer)
            for k in ("experts_gate", "experts_up", "experts_down"):
                lp[k] = jax.tree.map(lambda a: a[4 * rank:4 * rank + 4],
                                     layer[k])
            parts.append(np.asarray(reference._moe(x, lp, cut, QUANT))
                         - shared)
    assert reference.relative_l2(shared + sum(parts), uncut) < 1e-5

    # the program's layer, share by share, against the same uncut result
    xb = x.astype(jnp.bfloat16)[None]
    got = []
    for rank in range(4):
        config = _tiny(4, rank)
        hf = config["hf_config"]
        cfg = get_family(hf["architectures"][0], hf).config_from_hf(hf)
        lp = {k: v for k, v in layer.items() if not k.startswith("experts")}
        experts = {k: jax.tree.map(lambda a: a[None, 4 * rank:4 * rank + 4],
                                   layer[k])
                   for k in ("experts_gate", "experts_up", "experts_down")}
        y, stats = deepseek_v2.moe_block(xb, lp, experts, 0, cfg)
        got.append(np.asarray(y[0], np.float32))
        assert int(stats[0] + stats[1]) == 24 * 3
    shared_p = np.asarray(deepseek_v2.swiglu(
        xb[0], layer["shared_gate"], layer["shared_up"],
        layer["shared_down"]), np.float32)
    total = sum(g - shared_p for g in got) + shared_p
    assert reference.relative_l2(total, uncut) < 0.02


def test_group_limited_routing_differs_from_plain_top_k_as_by_hand():
    """16 experts in 4 groups of 4, the best 2 groups stay, 3 experts a
    token. By hand: the groups' best scores are 0.30 (group 0), 0.05
    (1), 0.20 (2), 0.19 (3): groups 0 and 2 stay. Plain top-3 takes
    experts 0, 8 and 12 (0.30, 0.20, 0.19); group-limited cannot take
    12 (group 3) and takes 0, 8 and 9 (0.12)."""
    import jax.numpy as jnp

    from bigdl_tpu.ops.moe_routed import route

    scores = np.full(16, 0.01)
    scores[[0, 4, 8, 9, 12]] = [0.30, 0.05, 0.20, 0.12, 0.19]
    scores = scores / scores.sum()
    logits = jnp.log(jnp.asarray(scores, jnp.float32))[None]
    arch = {"experts_per_tok": 3, "n_group": 4, "topk_group": 2,
            "routed_scaling_factor": 2.0, "norm_topk_prob": False}
    limited = np.asarray(reference.route(jnp.asarray(scores[None],
                                                     jnp.float32), arch))[0]
    assert sorted(np.nonzero(limited)[0]) == [0, 8, 9]
    np.testing.assert_allclose(limited[[0, 8, 9]],
                               2.0 * scores[[0, 8, 9]], rtol=1e-5)
    topi, topw = route(logits, 3, n_group=4, topk_group=2,
                       method="group_limited_greedy", scaling_factor=2.0)
    assert sorted(np.asarray(topi)[0]) == [0, 8, 9]
    np.testing.assert_allclose(np.sort(np.asarray(topw)[0]),
                               np.sort(2.0 * scores[[0, 8, 9]]), rtol=1e-5)
    plain, _ = route(logits, 3, method="greedy")
    assert sorted(np.asarray(plain)[0]) == [0, 8, 12]


def test_yarn_of_the_reference_against_hand_computed_values():
    """theta 10000, 64 rotary channels, factor 40 over 4096 positions,
    beta 32 / 1: the ramp runs from channel pair 10 to 23 (correction
    dims 10.47 and 22.51, floor and ceiling), so pairs up to 10 keep
    their frequency, pairs from 23 are divided by 40, pair 17 lies
    7/13 of the way, and m = 0.1 * 0.707 * ln 40 + 1 = 1.2608."""
    rope = _doc()["reference"]["rope"]
    inv = np.asarray(reference.yarn_inv_freq(rope, 64))
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40.0, rtol=1e-6)
    np.testing.assert_allclose(
        inv[17], base[17] * (7 / 13 / 40 + 6 / 13), rtol=1e-5)
    assert abs(reference.yarn_mscale(40.0, 0.707) - 1.26080) < 1e-4


def test_costs_pinned_to_hand_arithmetic_at_the_published_widths():
    """Attention 149.2 M parameters a layer (7.9 + 37.7 + 2.9 + 16.8 +
    83.9), shared experts 47.2 M, a routed expert 23.6 M, at 0.5625 B a
    parameter: 376 MB a held expert layer, 13.27 MB an expert; 1,152 B
    and 2 x 128 x 1088 operations a cached position-layer."""
    config = _doc()
    dims = costs_deepseek_v2.Dims.from_config(config)
    c = costs_deepseek_v2
    attn = (5120 * 1536 + 1536 * 128 * 192 + 5120 * 576
            + 512 * 128 * 256 + 128 * 128 * 5120)
    assert attn == 149_225_472
    assert c.attention_bytes(dims, "sym_int4", 32) == attn * 0.5625
    assert c.expert_bytes(dims, "sym_int4", 32) == 3 * 5120 * 1536 * 0.5625
    layer = c.expert_layer_bytes(dims, "sym_int4", 32)
    assert layer == (attn + 3 * 5120 * 3072 + 20 * 3 * 5120 * 1536) * 0.5625
    assert round(layer / 1e6) == 376
    assert c.dense_layer_bytes(dims, "sym_int4", 32) \
        == (attn + 3 * 5120 * 12288) * 0.5625
    assert c.latent_bytes_per_position(dims) == 1152
    assert c.absorbed_flops_per_position(dims) == 2 * 128 * 1088
    assert c.kv_bytes_per_token(dims, 4096) == 20 * 4096 * 1152
    records = [{"prompt_tokens": 100, "chunks": [(1.0, 1), (2.0, 2)]}]
    work = c.serving_work(config, dims, records, "bf16", (1.5, 2.5))
    # the two tokens of the chunk at t=2.0 sit at cache lengths 101, 102
    assert work["decode_latent_bytes"] == (101 + 102) * 20 * 1152
    assert work["decode_absorbed_flops"] == (101 + 102) * 20 * 278528
    assert work["expert_bytes"] == c.expert_bytes(dims, "sym_int4", 32)
    assert work["expert_layers"] == 19 and work["held_experts"] == 20


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row stands at the file's top level
    under its own key; the three reduced keys differ and say so."""
    doc = _doc()
    pub = doc["published"]
    assert doc["reduced"] == ["layers", "n_routed_experts", "vocab_size"]
    assert (doc["layers"], doc["n_routed_experts"], doc["vocab_size"]) \
        == (20, 20, 12800)
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (60, 160, 102400)
    hf = doc["hf_config"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
                "num_experts_per_tok", "n_group", "topk_group",
                "n_shared_experts", "routed_scaling_factor", "rope_scaling"):
        assert hf[key] == doc[key], key
    assert doc["num_hidden_layers"] == 60 and hf["num_hidden_layers"] == 20
    assert hf["n_routed_experts"] * hf["ep_size"] == 160
