"""The plain SDAR-MoE reference and the configuration's `generation`
module against the program on the CPU at a tiny size at which every
mechanism binds (3 layers, blocks of 4, 2 of 8 experts held): the
configuration resolves with its four roles; check (a)'s rows through
the module, and a causal mask in the program's place failing it; the
reference's mask and routing against hand arithmetic; `replay` against
`all_logits` stream by stream; `served_gaps` on a hand-made block and on
what a tiny engine really served, with a tampered token standing out;
the layer check with its three controls (each fails a limit); the costs
against hand arithmetic at the published widths; the two new metrics'
files against `BENCHMARK.json`; the configuration's file against the
catalog row."""

import json

import numpy as np
import pytest

import _paths
from harness import (checks_sdar_moe as checks, costs_sdar_moe,
                     generation_sdar_moe as generation,
                     reference_sdar_moe as reference, spec,
                     weights_sdar_moe as weights)

CONFIG = "sdar-30b-a3b-ep4-int4"
CELL = "sdar-30b-ep4-fixedlen-closed"
QUANT = {"qtype": "sym_int4", "block": 32}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _doc():
    return json.loads((_paths.BENCH / "configs" / f"{CONFIG}.json").read_text())


def _tiny():
    doc = _doc()
    return spec.deep_update(doc, doc["tiny"])


@pytest.fixture(scope="module")
def built():
    import jax

    config = _tiny()
    box = {}
    model, _ = weights.build_model(
        config, 2 ** 31 + 5, merge=True,
        with_canonical=lambda canonical, cfg: box.update(
            canonical=jax.tree.map(lambda x: x, canonical)))
    return config, model, box["canonical"]


def test_the_cell_resolves_with_its_four_roles():
    cell = spec.Cell(CELL, _paths.ROOT, tiny=True)
    assert {k: v.__name__.split(".")[-1] for k, v in cell.modules.items()} \
        == {"reference": "reference_sdar_moe", "weights": "weights_sdar_moe",
            "costs": "costs_sdar_moe", "generation": "generation_sdar_moe"}
    assert cell.chips == 1 and cell.traffic["kind"] == "closed"
    assert generation.SEQUENCE == (32, 8)


def test_check_a_rows_agree_and_a_causal_program_fails(built):
    """`program_rows` against `reference_rows` on the runner's seeded
    sequence: a prefill row and eight block rows, all inside the
    tolerance at toy widths' rounding walk; the same comparison against
    the reference under a CAUSAL mask is far outside."""
    from harness import serve_runner

    config, model, canonical = built
    arch = config["reference"]
    ids = serve_runner.check_ids(7, arch["vocab"], 40)
    prog = generation.program_rows(model, config["engine"], ids, 7)
    ref = generation.reference_rows(reference, canonical, arch, QUANT, ids)
    assert serve_runner.rows_fault(prog, ref, 9) is None
    assert prog["block"].shape == (8, 256) and prog["prefill"].shape == (256,)
    rel = serve_runner.rows_errors(prog, ref)
    assert 0 < max(rel.values()) < reference.rounding_walk(3)
    seq = generation.noised(ids, 32, 4, 0)
    assert 2 <= sum(t == 0 for t in seq[32:]) <= 6 and seq[:32] == list(ids[:32])
    causal = np.asarray(reference.all_logits(
        canonical, arch, QUANT, seq, first=31, alter={"mask": "causal"}))
    bad = serve_runner.rows_errors(prog, {"prefill": causal[0],
                                          "block": causal[1:]})
    assert bad["block"] > 10 * reference.rounding_walk(3)
    # the CELL's tolerance parts them too: it lies between what the
    # program reads and what this control reads (PERF.md 6, PR 53: on
    # the chip at the published widths as here)
    assert max(rel.values()) < reference.tolerance(config, "bf16") \
        < min(bad.values())


def test_the_references_mask_is_block_causal(built):
    """A token changed inside a block moves every row of that block and
    of the blocks after it, and no row of a block before it."""
    config, _, canonical = built
    arch = config["reference"]
    ids = np.random.default_rng(3).integers(1, 256, 16)
    base = np.asarray(reference.all_logits(canonical, arch, QUANT, ids))
    other = ids.copy()
    other[10] = (other[10] + 1) % 255 + 1
    moved = np.abs(np.asarray(reference.all_logits(
        canonical, arch, QUANT, other)) - base).max(axis=-1)
    assert (moved[:8] == 0).all() and (moved[8:] > 0).all()
    with pytest.raises(ValueError, match="whole blocks"):
        reference.all_logits(canonical, arch, QUANT, ids[:15])


def test_the_references_routing_is_softmax_top_k_renormalised(built):
    import jax.numpy as jnp

    config, _, canonical = built
    arch = config["reference"]
    _, lp, _ = next(iter(reference.layer_stack(canonical, arch)))
    h = jnp.asarray(np.random.default_rng(4).normal(size=(5, 64)),
                    jnp.float32)
    w = np.asarray(reference.route(h, lp, arch))
    logits = np.asarray(h) @ np.asarray(lp["router"], np.float32)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    for i in range(5):
        top = np.argsort(-p[i])[:3]
        want = np.zeros(8)
        want[top] = p[i, top] / p[i, top].sum()
        np.testing.assert_allclose(w[i], want, rtol=1e-4, atol=1e-6)
    assert np.allclose(w.sum(-1), 1.0, atol=1e-5)


def test_replay_is_all_logits_stream_by_stream(built):
    """`replay`'s noised stream reads the final stream below its block
    and itself inside: its gaps are those `all_logits` gives on a
    sequence that is final before a block and noised inside it."""
    config, _, canonical = built
    arch = config["reference"]
    rng = np.random.default_rng(8)
    final = [int(t) for t in rng.integers(1, 256, 32)]
    start, g = 16, 16
    noised = list(final[start:])
    for j in (1, 2, 6, 11, 12, 13):
        noised[j] = 0
    targets = [int(t) for t in final[start:]]
    found = reference.replay(canonical, arch, QUANT, final, [noised], start,
                             targets)
    assert set(found) == {"gap", "confidence", "spread", "best"}
    got = np.asarray(found["gap"])[0]
    conf = np.asarray(found["confidence"])[0]
    best = np.asarray(found["best"])[0]
    spread = np.asarray(found["spread"])[0]
    for blk in range(g // 4):
        lo = start + 4 * blk
        seq = final[:lo] + noised[4 * blk:4 * blk + 4]
        lg = np.asarray(reference.all_logits(canonical, arch, QUANT, seq),
                        np.float64)[lo:, 1:]          # the MASK id left out
        for j in range(4):
            tok = targets[4 * blk + j]
            want = (lg[j].max() - lg[j][tok - 1]) / lg[j].std()
            assert got[4 * blk + j] == pytest.approx(want, abs=2e-3)
            # the confidence is log x0_p of the best candidate, the MASK
            # id's logit no candidate
            lse = np.log(np.exp(lg[j] - lg[j].max()).sum()) + lg[j].max()
            assert conf[4 * blk + j] == pytest.approx(lg[j].max() - lse,
                                                      abs=2e-3)
            assert best[4 * blk + j] == lg[j].argmax() + 1
            assert spread[4 * blk + j] == pytest.approx(lg[j].std(),
                                                        rel=1e-3)
    # targets may differ stream by stream
    two = reference.replay(canonical, arch, QUANT, final, [noised, noised],
                           start, [targets, [1] * g])
    assert np.asarray(two["gap"])[0] == pytest.approx(got, abs=1e-5)
    assert np.asarray(two["gap"]).shape == (2, g)


def test_served_gaps_replays_a_hand_made_block_from_steps():
    """The module's arithmetic on `steps`, with a stub reference that
    returns which copy and place it was asked for."""
    assert generation.pass_index([1, 1, 2, 2, 5, 5, 4, 4, 8, 8], 16, 4, 2) \
        == [0, 0, 1, 1, 1, 1, 0, 0, 1, 1]
    assert generation.pass_index([1, 4, 4, 3, 3, 6], 7, 4, 2) \
        == [0, 1, 1, 0, 0, 0]
    with pytest.raises(ValueError, match="denoise passes"):
        generation.pass_index([1, 1, 2, 3], 16, 4, 2)
    seen = {}

    class Stub:
        @staticmethod
        def replay(canonical, arch, quant, final, copies, start, targets):
            seen.update(final=list(final), copies=np.asarray(copies),
                        start=start, targets=np.asarray(targets))
            c, g = np.asarray(copies).shape
            return {"gap": (np.arange(c)[:, None] * 100.0
                            + np.arange(g)[None, :]),
                    "confidence": np.zeros((c, g)),
                    "spread": np.ones((c, g))}

        transfer, owed = staticmethod(reference.transfer), staticmethod(
            reference.owed)

    arch = {"block": 4, "mask_token_id": 0, "denoising_steps": 2,
            "remasking_strategy": "low_confidence_dynamic",
            "confidence_threshold": 0.9}
    sample = {"prompt": [9, 8, 7, 6, 5, 4], "tokens": [11, 12, 13, 14, 15],
              "steps": [2, 1, 4, 5, 4]}
    out = generation.served_gaps(Stub, None, arch, None, sample, 32)
    # the prompt's tail (2 rows) opens the block at 4; two served rows
    # fill it, three more open the next
    assert seen["start"] == 4 and seen["copies"].shape == (2, 16)
    assert seen["final"][:11] == [9, 8, 7, 6, 5, 4, 11, 12, 13, 14, 15]
    assert seen["final"][11:] == [0] * 21
    # pass 0 saw the tail and MASK; pass 1 also the tokens of pass 0
    assert seen["copies"][0][:8].tolist() == [5, 4, 0, 0, 0, 0, 0, 0]
    assert seen["copies"][1][:8].tolist() == [5, 4, 0, 12, 13, 0, 15, 0]
    assert seen["targets"][:8].tolist() == [0, 0, 11, 12, 13, 14, 15, 0]
    # each token's gap from the copy of its own pass, at its own place
    assert out == {"first": [3.0], "later": [102.0, 4.0, 105.0, 6.0]}
    with pytest.raises(ValueError, match="steps"):
        generation.served_gaps(Stub, None, arch, None,
                               dict(sample, steps=None), 32)


def test_served_gaps_on_what_a_tiny_engine_served(built):
    """A greedy request through the engine, then through `served_gaps`:
    every token is the reference's best at the pass that committed it
    (gap 0 or a rounding's worth); a token swapped after the fact, or
    the same tokens with their `steps` shuffled, stand out."""
    from bigdl_tpu.serving.engine import (EngineConfig, LLMEngine,
                                          SamplingParams)

    config, model, canonical = built
    arch = config["reference"]
    eng = LLMEngine(model, EngineConfig(
        max_batch=2, max_seq=256, prefill_chunk=32, prefill_bucket=16,
        sentinel=False, quality=False))
    prompt = [int(t) for t in np.random.default_rng(2).integers(1, 256, 22)]
    eng.add_request("r", prompt, SamplingParams(max_tokens=24))
    tokens, steps = [], []
    for _ in range(400):
        eng.step()
        outs = eng.get_outputs("r")
        for o in outs:
            tokens.extend(o.new_token_ids)
            steps.extend(o.steps or [])
        if any(o.finished for o in outs):
            break
    assert len(tokens) == 24 == len(steps)
    sample = {"prompt": prompt, "tokens": tokens, "steps": steps}
    g = generation.served_gaps(reference, canonical, arch, QUANT, sample, 64)
    assert len(g["first"]) + len(g["later"]) == 24
    assert len(g["first"]) == steps.count(1) >= 1
    assert max(g["first"] + g["later"]) < 0.5
    assert sum(x == 0.0 for x in g["first"] + g["later"]) >= 16
    wrong = list(tokens)
    wrong[9] = wrong[9] % 255 + 1
    gw = generation.served_gaps(reference, canonical, arch, QUANT,
                                dict(sample, tokens=wrong), 64)
    assert max(gw["first"] + gw["later"]) > 1.0
    limits = reference.served_gap_limits(config, "bf16")
    assert set(limits) == {"prefill_gap_max", "decode_gap_max",
                           "decode_gap_mean"}


def _under(model, cfg):
    """The same served model under another family config."""
    import copy

    other = copy.copy(model)
    other.config = cfg
    return other


def _serve(model, prompts, max_tokens):
    from bigdl_tpu.serving.engine import (EngineConfig, LLMEngine,
                                          SamplingParams)

    eng = LLMEngine(model, EngineConfig(
        max_batch=4, max_seq=256, prefill_chunk=32, prefill_bucket=16,
        sentinel=False, quality=False))
    for i, p in enumerate(prompts):
        eng.add_request(f"r{i}", p, SamplingParams(max_tokens=max_tokens))
    got = {f"r{i}": {"prompt": p, "tokens": [], "steps": []}
           for i, p in enumerate(prompts)}
    live = set(got)
    for _ in range(2000):
        if not live:
            break
        eng.step()
        for rid in list(live):
            for o in eng.get_outputs(rid):
                got[rid]["tokens"].extend(o.new_token_ids)
                got[rid]["steps"].extend(o.steps or [])
                if o.finished:
                    live.discard(rid)
    assert not live
    return list(got.values())


def test_a_program_that_commits_the_first_rows_fails_check_b(built):
    """The transfer rule is held by check (b): the same model served
    under `remasking_strategy` `sequential` (the first MASK rows a pass,
    whatever their confidences) gives tokens that ARE the reference's
    best at every replayed state, and is still not within the cell's
    limit on the mean gap, by the rows it chose; the configuration's own
    rule on the same prompts is."""
    import dataclasses

    from harness import served

    config, model, canonical = built
    arch = config["reference"]
    rng = np.random.default_rng(17)
    prompts = [[int(t) for t in rng.integers(1, 256, n)]
               for n in (24, 22, 33, 40)]
    limits = reference.served_gap_limits(config, "bf16")
    found = {}
    for rule in ("low_confidence_dynamic", "sequential"):
        cfg = dataclasses.replace(model.config, remasking_strategy=rule)
        samples = _serve(_under(model, cfg), prompts, 48)
        found[rule] = served.compare(reference, canonical, arch, QUANT,
                                     samples, gaps=generation.served_gaps)
    sound, seq = found["low_confidence_dynamic"], found["sequential"]
    assert served.within(sound, limits), sound
    assert not served.within(seq, limits), seq
    assert seq["decode_gap_mean"] > limits["decode_gap_mean"] \
        > 4 * sound["decode_gap_mean"]
    # the tokens alone would have passed: most are the reference's best
    assert seq["reference_best_share"] > 0.25


def test_the_references_transfer_rule_on_hand_made_confidences():
    t = reference.transfer
    assert [reference.owed(s, 4, 2) for s in range(2)] == [2, 2]
    assert [reference.owed(s, 4, 3) for s in range(3)] == [2, 1, 1]
    conf = [0.5, 0.1, 0.3, 0.2]
    assert t(conf, 2, "sequential", 0.9) == [0, 1]
    assert t(conf, 2, "low_confidence_static", 0.9) == [0, 2]
    assert t(conf, 2, "low_confidence_dynamic", 0.9) == [0, 2]
    # over the threshold: all of them, if they are at least the count
    assert t(conf, 2, "low_confidence_dynamic", 0.15) == [0, 2, 3]
    assert t(conf, 2, "low_confidence_dynamic", 0.4) == [0, 2]
    assert t(conf, 2, "low_confidence_static", 0.15) == [0, 2]
    # never more rows than are MASK (the tail block), ties by position
    assert t([0.3], 2, "low_confidence_dynamic", 0.9) == [0]
    assert t([0.2, 0.2, 0.2], 2, "low_confidence_static", 0.9) == [0, 1]
    with pytest.raises(ValueError, match="remasking_strategy"):
        t(conf, 2, "random", 0.9)


def test_transfer_gaps_reads_a_row_committed_in_anothers_place():
    arch = {"block": 4, "mask_token_id": 0, "denoising_steps": 2,
            "remasking_strategy": "low_confidence_dynamic",
            "confidence_threshold": 0.9}
    conf = np.log([[0.5, 0.1, 0.3, 0.2, 0.4, 0.3, 0.2, 0.1],
                   [0.5, 0.9, 0.9, 0.2, 0.9, 0.9, 0.2, 0.1]])
    found = {"confidence": conf, "spread": np.full((2, 8), 2.0)}
    copies = np.array([[0] * 8, [0, 7, 7, 0, 7, 7, 0, 0]])
    # block 0: pass 0 took rows 1 and 2 where the reference takes 0 and
    # 2; block 1: rows 4 and 5, the reference's own
    committed = {1: 0, 2: 0, 0: 1, 3: 1, 4: 0, 5: 0, 6: 1, 7: 1}
    got = generation.transfer_gaps(reference, found, copies, committed, 8,
                                   arch)
    assert got == {1: pytest.approx((np.log(0.3) - np.log(0.1)) / 2.0),
                   2: 0.0, 0: 0.0, 3: 0.0, 4: 0.0, 5: 0.0, 6: 0.0, 7: 0.0}
    # only whole blocks whose every row is known
    assert set(generation.transfer_gaps(reference, found, copies, committed,
                                        4, arch)) == {0, 1, 2, 3}


def test_the_end_to_end_controls_each_read_over_a_limit(built):
    """`checks_sdar_moe.generation_controls` at toy widths: the seeded
    request follows the family's schedule; a causal mask in the
    program's place is outside check (a)'s tolerance, and committing
    the first rows is outside check (b)'s mean gap. (The router in
    bfloat16 swaps no expert among 8: it fails at the published widths
    only, PERF.md 7, 36 e.)"""
    config, _, canonical = built
    arch = config["reference"]
    sample = checks.seeded_request(5, arch)
    assert (len(sample["prompt"]), len(sample["tokens"])) \
        == checks.CONTROL_REQUEST
    at = generation.pass_index(sample["steps"], len(sample["prompt"]), 4, 2)
    assert at[:2] == [0, 0] and sorted(at[2:6]) == [0, 0, 1, 1]
    found = checks.generation_controls(config, canonical, 5)
    assert set(found["controls"]) == {"causal_mask", "router_bf16",
                                      "sequential", "wrong_token"}
    assert set(found["limits"]) == {"rel_l2", "prefill_gap_max",
                                    "decode_gap_max", "decode_gap_mean"}
    causal = found["controls"]["causal_mask"]
    assert {"rel_l2.prefill", "rel_l2.block"} <= set(causal["over"])
    seq = found["controls"]["sequential"]
    assert "rel_l2" not in seq and "decode_gap_mean" in seq["over"]
    assert not found["controls"]["router_bf16"]["over"]
    assert set(found["controls"]["wrong_token"]["over"]) == {
        "prefill_gap_max", "decode_gap_max", "decode_gap_mean"}
    # the toy preset states its own mean (the rows of a toy block lie
    # far apart in confidence); the cell's limits are the module's
    assert found["limits"]["decode_gap_mean"] == 0.002
    assert reference.served_gap_limits(_doc(), "bf16") == {
        "prefill_gap_max": 0.3, "decode_gap_max": 0.3,
        "decode_gap_mean": 0.05}
    assert reference.tolerance(_doc(), "bf16") == 0.1


def test_the_layer_check_passes_the_program_and_each_control_fails(built):
    config, _, canonical = built
    arch = config["reference"]
    sound = checks.layer_check(config, canonical, 5)
    assert sound["within"], sound["found"]
    assert set(sound["found"]) == set(sound["limits"]) == {
        "attention_prefill", "attention_block", "ffn_prefill", "ffn_block",
        "transfer_apart"}
    # the engine's sampler and transfer rule choose as the reference's
    # arithmetic does on every seeded block, greedy, sampled and mixed
    assert sound["found"]["transfer_apart"] == 0.0
    # rows in three blocks after two chunks
    # rows in thirty-two blocks after four chunks: what the toy slab has
    # room for; one chunk and 64 blocks at the cell's sizes
    assert checks.prefill_rows(256, 32) == 128 and checks.BLOCKS == 64
    assert checks.block_rows(256, 32, 4) == 128
    assert checks.prefill_rows(3072, 1024) == 1024
    assert checks.block_rows(3072, 1024, 4) == 256
    for name, over in (("causal_mask", "attention_"),
                       ("no_qk_norm", "attention_")):
        found = checks.layer_check(
            config, canonical, 5, stand_in=checks.AlteredReference(
                arch, QUANT, canonical, checks.CONTROLS[name]))
        assert not found["within"], (name, found["found"])
        bad = [k for k, v in found["limits"].items()
               if found["found"][k] > v]
        assert bad and all(k.startswith(over) for k in bad), (name, bad)
    # the router in bfloat16 moves every routing weight; at toy widths
    # (8 experts, scores far apart) it swaps no expert, so only the
    # published widths can fail it: the chip's control (PERF.md 2, PR 53)
    found = checks.layer_check(
        config, canonical, 5, stand_in=checks.AlteredReference(
            arch, QUANT, canonical, checks.CONTROLS["router_bf16"]))
    assert found["found"]["attention_prefill"] == 0.0
    assert 0.0 < found["found"]["ffn_prefill"] < 0.015
    # the engine's `sequential` rule in the configured one's place
    found = checks.layer_check(
        config, canonical, 5, stand_in=checks.AlteredReference(
            arch, QUANT, canonical, checks.CONTROLS["sequential"]))
    assert not found["within"] and found["found"]["transfer_apart"] > 0.5
    assert found["found"]["attention_prefill"] == 0.0
    assert sorted(checks.CONTROLS) == ["causal_mask", "no_qk_norm",
                                       "router_bf16", "sequential"]


def test_canonical_params_marks_the_tree_by_the_layer_check(monkeypatch):
    config = _tiny()
    tree = weights.canonical_params(config, 2 ** 31 + 5)
    assert tree["layer_check"]["within"] and not tree["refused"]
    assert len(tree["layer_check"]["compared"]) == 5
    monkeypatch.setitem(config, "layer_limits", {
        "attention_prefill": 1e-9, "attention_block": 0.01,
        "ffn_prefill": 0.015, "ffn_block": 0.015})
    bad = weights.canonical_params(config, 2 ** 31 + 5)
    assert bad["refused"] and not bad["layer_check"]["within"]
    ids = list(range(1, 9))
    assert np.isnan(np.asarray(reference.all_logits(
        bad, config["reference"], QUANT, ids))).all()


def test_costs_pinned_to_hand_arithmetic_at_the_published_widths():
    """ISSUE 53's arithmetic: attention 18.9 M parameters a layer (2048
    x 5,120 + 4096 x 2048), an expert 4.72 M = 2.65 MB at 0.5625 B a
    parameter, 32 held a layer x 48 layers = 4.08 GB; 2,048 B a position
    and layer, read ONCE a pass for a block's four rows."""
    config = _doc()
    c = costs_sdar_moe
    dims = c.Dims.from_config(config)
    attn = 2048 * 5120 + 4096 * 2048
    assert round(attn / 1e6, 1) == 18.9
    assert c.attention_bytes(dims, "sym_int4", 32) == attn * 0.5625
    assert c.expert_bytes(dims, "sym_int4", 32) == 3 * 2048 * 768 * 0.5625
    assert round(3 * 2048 * 768 / 1e6, 2) == 4.72
    assert round(48 * 32 * 3 * 2048 * 768 * 0.5625 / 1e9, 2) == 4.08
    assert c.linear_weight_bytes(dims, "sym_int4", 32) == 0.5625 * (
        48 * attn + 2048 * 37984)
    assert c.bytes_per_position(dims) == 2048
    assert c.kv_bytes_per_token(dims, 1000) == 2048 * 48 * 1000
    # 16 slots x 3,072 positions x 98,304 B = 4.83 GB
    assert round(16 * 3072 * 48 * 2048 / 1e9, 2) == 4.83
    records = [
        # three events: passes 1 and 2 commit, 3 stores, 4 commits
        {"prompt_tokens": 1001, "steps": [1, 1, 2, 4, 4],
         "chunks": [(1.0, 2), (2.0, 1), (2.2, 2)]},
        # no steps on the record: T + 1 passes a block of tokens
        {"prompt_tokens": 50, "steps": None, "chunks": [(2.1, 4)]}]
    work = c.serving_work(config, dims, records, "bf16", (1.5, 2.5))
    # the event at 2.0: one pass at 1000 + 4 positions; at 2.2: two
    # passes (the store and a denoise) at 1004 + 4
    want = (1 * 1004 + 2 * 1008 + 3 * 52) * 48 * 2048
    assert work["decode_kv_bytes"] == want
    assert work["block_attn_flops"] == want / 2048 * 4 * 4 * 32 * 128
    assert work["expert_layers"] == 48 and work["held_experts"] == 32
    assert "decode_kv_bytes" not in c.serving_work(config, dims, records,
                                                   "bf16", None)
    with pytest.raises(NotImplementedError, match="training"):
        c.training_work(config, dims, {}, 1)


def test_the_new_metrics_read_their_counters_and_agree_with_benchmark_json():
    from harness import layer_metrics, promtext

    passes = "bigdl_tpu_block_passes_total"
    text = lambda d, s, t: promtext.parse(                     # noqa: E731
        f'{passes}{{kind="denoise"}} {d}\n{passes}{{kind="store"}} {s}\n'
        f"bigdl_tpu_block_tokens_committed_total {t}\n")
    obs = {"counters_start": text(100, 50, 190),
           "counters_end": text(300, 150, 580)}
    per = _paths.BENCH / "layer_metrics"
    assert layer_metrics.read_metric(
        per / "block_tokens_per_denoise_pass.json", obs) \
        == pytest.approx(1.95)
    assert layer_metrics.read_metric(
        per / "block_store_pass_share.json", obs) \
        == pytest.approx(100.0 / 3)
    # the parent has no such counters: nothing is read, nothing raises
    empty = {"counters_start": promtext.parse(""),
             "counters_end": promtext.parse("")}
    for name in ("block_tokens_per_denoise_pass", "block_store_pass_share"):
        assert layer_metrics.read_metric(per / f"{name}.json", empty) is None
    bench = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, unit, better in (
            ("block_tokens_per_denoise_pass", "tokens", "higher"),
            ("block_store_pass_share", "%", "lower")):
        entry, doc = by_name[name], json.loads((per / f"{name}.json")
                                               .read_text())
        assert (entry["unit"], entry["better"], entry["workloads"]) == (
            unit, better, [CELL])
        for key in ("layer", "source", "unit", "moves"):
            assert entry[key] == doc[key], (name, key)
        assert doc["reducer"] == "counter_ratio"
    lists = {m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert {"decode_attn_roofline", "moe_routed_roofline",
            "moe_experts_hit_share", "moe_held_assignment_share",
            "plain_step_ms", "step_device_ms", "decode_ahead_share",
            "sampler_sortfree_share"} <= lists
    assert not lists & {"swa_decode_attn_roofline", "swa_rows_read_share",
                        "decode_attn_blocks_read_share", "gemv_roofline"}
    ends = {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", (CELL,))}
    assert {"itl_p95_ms", "setup_s"} <= ends
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "fixedlen-closed", 1)
    assert bench["workloads"][-1] is cell
    assert bench["configs"][-1]["name"] == CONFIG


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's config stands at the file's top
    level with its value; the reduced keys differ and say so; the
    hf_config that runs differs from the row only by the cuts, the
    architecture's name, the share and the generation's keys. No width
    differs and no layer is left out."""
    doc = _doc()
    row = [json.loads(x) for x in open(CATALOG)
           if '"SDAR-30B-A3B-Chat"' in x][0]
    assert doc["source"] == row["source_url"]
    assert doc["reduced"] == ["num_experts", "vocab_size"]
    assert (doc["num_experts"], doc["vocab_size"]) == (32, 37984)
    assert doc["published"]["num_experts"] == 128
    assert doc["published"]["vocab_size"] == 151936
    assert doc["published"]["num_hidden_layers"] == 48
    assert "four" in doc["published"]["deployment"]
    hf = doc["hf_config"]
    for key, value in row["config"].items():
        if key not in ("num_experts", "vocab_size"):
            assert doc[key] == value, key
            assert hf[key] == value, key
    assert hf["num_hidden_layers"] == doc["num_hidden_layers"] == 48
    assert hf["num_experts"] * hf["ep_size"] == 128
    assert hf["vocab_size"] * 4 == 151936
    assert (hf["block_length"], hf["denoising_steps"], hf["mask_token_id"],
            hf["remasking_strategy"], hf["confidence_threshold"]) == (
        4, 2, 0, "low_confidence_dynamic", 0.9)
    for line in ("num_experts", "vocab_size", "qk_norm", "rotary", "layers",
                 "mask", "no_shift", "block_length", "denoising_steps",
                 "remasking_strategy", "generation",
                 "departure_commit_count", "departure_mask_logit",
                 "mask_token_id", "weights", "tensor_names",
                 "prefill_bucket"):
        assert doc["assumed"][line], line
    assert doc["harness"] == {
        "reference": "reference_sdar_moe", "weights": "weights_sdar_moe",
        "costs": "costs_sdar_moe", "generation": "generation_sdar_moe"}
    ref = doc["reference"]
    assert (ref["layers"], ref["heads"], ref["kv_heads"], ref["head_dim"],
            ref["block"], ref["denoising_steps"], ref["mask_token_id"],
            ref["experts_total"], ref["held"], ref["experts_per_tok"]) == (
        48, 32, 4, 128, 4, 2, 0, 128, 32, 8)
    eng = doc["engine"]
    assert (eng["max_batch"], eng["max_seq"], eng["prefill_chunk"],
            eng["kv_cache_dtype"], eng["kv_page_size"]) \
        == (16, 3072, 1024, "bf16", 0)
    from harness.weights import _family_config

    _, cfg, _ = _family_config(doc)
    assert (cfg.n_full, cfg.n_window, cfg.n_routed_layers, cfg.share,
            cfg.block) == (48, 0, 48, (128, 0, 32),
                           (4, 2, 0, "low_confidence_dynamic", 0.9))
    traffic = json.loads((_paths.BENCH / "traffic"
                          / "fixedlen-closed.json").read_text())
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.7, "min": 128,
        "max": 1920}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.5, "min": 256,
        "max": 1024, "multiple_of": 128}
    assert traffic["sampling"] == [
        {"share": 0.5, "temperature": 0.0, "top_k": 0},
        {"share": 0.5, "temperature": 1.0, "top_k": 0}]
    assert traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"] \
        == 2944 <= eng["max_seq"]
    assert (traffic["clients"], traffic["client_stagger_s"],
            traffic["requests_per_client"], traffic["drain_seconds"],
            traffic["trace_start_s"], traffic["trace_seconds"]) == (
        eng["max_batch"], 0.05, 16, 60, 30.0, 3.0)
    from harness import served, traffic as traffic_mod

    plan = traffic_mod.all_requests(traffic_mod.window_plan(
        traffic, 1, 50.0, 37984))
    assert {r["max_tokens"] % 128 for r in plan} == {0}
    assert 256 <= min(r["max_tokens"] for r in plan) \
        and max(r["max_tokens"] for r in plan) <= 1024
    assert any(r["prompt_len"] % 4 for r in plan)
    assert served.pad_length(max(r["prompt_len"] + r["max_tokens"]
                                 for r in plan)) == 3072
