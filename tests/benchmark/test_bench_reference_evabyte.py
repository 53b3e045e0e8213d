"""The plain EvaByte reference against its own plain masked form and
against the program on the CPU at a tiny size at which every mechanism
binds (window 32, chunk 4): the short cuts leave out only products that
are zero; the layer check passes the program and refuses each control;
the tree is marked by the check; the costs against hand arithmetic at
the published widths; the configuration's file against the catalog
row; the readers of the new per-layer metrics."""

import json

import numpy as np
import pytest

import _paths
from harness import (checks_evabyte as checks, costs_evabyte,
                     layer_metrics, reference_evabyte as reference, spec,
                     weights_evabyte as weights)

CONFIG = "evabyte-int4"
QUANT = {"qtype": "sym_int4", "block": 32}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2 ** 31 + 11


def _doc():
    return json.loads(
        (_paths.BENCH / "configs" / f"{CONFIG}.json").read_text())


@pytest.fixture(scope="module")
def tiny():
    doc = _doc()
    config = spec.deep_update(doc, doc["tiny"])
    return config, weights.canonical_params(config, SEED, check=False)


@pytest.mark.parametrize("rows", [31, 32, 33, 100, 131])
def test_the_references_short_cut_leaves_out_only_products_that_are_zero(
        tiny, rows):
    """`attention` (a window's rows against their window's keys and the
    summaries before it) against `attention_masked` (every query against
    every key and every summary under the definition's mask), at lengths
    that end inside a window, at its last position and at a window's
    first."""
    import jax
    import jax.numpy as jnp

    config, canonical = tiny
    arch, lp = config["reference"], canonical["layers"][0]
    y = jnp.asarray(np.random.default_rng(rows).normal(
        size=(rows, arch["hidden"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        lean = reference.attention(y, lp, arch, QUANT)
        plain = reference.attention_masked(y, lp, arch, QUANT)
    assert lean.shape == (rows, arch["hidden"])
    assert float(jnp.abs(lean - plain).max()) < 1e-6
    # and the summaries bind: without them the later windows move
    if rows > 32:
        with jax.default_matmul_precision("highest"):
            bare = reference.attention(y, lp, arch, QUANT,
                                       {"summaries": False})
        assert float(jnp.abs(bare[:32] - lean[:32]).max()) < 1e-6
        assert reference.relative_l2(np.asarray(bare[32:]),
                                     np.asarray(lean[32:])) > 0.1


def test_all_logits_is_head_zero_of_all_head_logits(tiny):
    config, canonical = tiny
    ids = np.random.default_rng(1).integers(0, 320, 70)
    arch = config["reference"]
    every = np.asarray(reference.all_head_logits(canonical, arch, QUANT,
                                                 ids, first=10))
    first = np.asarray(reference.all_logits(canonical, arch, QUANT, ids,
                                            first=10))
    assert every.shape == (60, 8 * 320) and first.shape == (60, 320)
    np.testing.assert_array_equal(first, every[:, :320])
    # the heads differ: head 1 is no copy of head 0
    assert reference.relative_l2(every[:, 320:640], first) > 0.5


def test_windows_of_right_padding_are_not_walked(tiny):
    """`served.compare` right-pads every request to one length with id
    0: the reference walks the windows up to the last one that holds a
    non-zero id (or a compared position) and returns zeros past them;
    the live rows read what an unpadded pass reads."""
    config, canonical = tiny
    arch = config["reference"]
    assert reference.live_rows([5] * 70 + [0] * 58, 0, 32) == 96
    assert reference.live_rows([5] * 64 + [0] * 64, 0, 32) == 64
    assert reference.live_rows([5] * 10 + [0] * 118, 64, 32) == 96
    assert reference.live_rows([0] * 128, 0, 32) == 32
    assert reference.live_rows([5] * 40, 0, 32) == 40     # no padding
    ids = [int(x) for x in
           np.random.default_rng(3).integers(1, 320, 70)]
    bare = np.asarray(reference.all_head_logits(canonical, arch, QUANT, ids))
    padded = np.asarray(reference.all_head_logits(
        canonical, arch, QUANT, ids + [0] * 58, first=32))
    assert padded.shape == (96, 8 * 320)
    np.testing.assert_allclose(padded[:38], bare[32:], atol=1e-6)
    assert not padded[64:].any() and padded[38:64].any()


def test_the_checks_sizes_make_every_mechanism_bind():
    """At the published sizes: five whole chunks of the cell's 1024 rows
    and one of 1,016 (three windows less eight rows), then 32 decoded rows that
    cross the boundary at 6,144 and complete the chunk 6,144-6,159."""
    arch = _doc()["reference"]
    p, d = checks.prefill_rows(arch), checks.decode_rows(arch)
    assert (p, d) == (6136, 32)
    chunk = _doc()["engine"]["prefill_chunk"]
    assert (chunk, p % chunk, p // chunk) == (1024, 1016, 5)
    assert p < 3 * 2048 < p + d and (p + d) // 16 == 385
    assert p + d <= _doc()["engine"]["max_seq"]
    tiny = _doc()["tiny"]["reference"]
    assert (checks.prefill_rows(tiny), checks.decode_rows(tiny)) == (94, 8)


@pytest.fixture(scope="module")
def checked(tiny):
    config, canonical = tiny
    return checks.layer_check(config, canonical, SEED)


def test_the_layer_check_passes_the_program_on_every_block(checked):
    assert checked["within"] is True, checked["found"]
    assert set(checked["found"]) == set(checked["limits"]) == {
        "eva_attention_prefill", "eva_attention_decode",
        "eva_summary_rel_l2", "ffn_prefill", "ffn_decode", "head_rel_l2"}
    assert all(0 < v < 0.01 for v in checked["found"].values())
    # summaries the prefill wrote and those a decode step finished
    assert set(checked["summaries"]) == {"k_sum_prefill", "k_sum_decode",
                                         "v_sum_prefill", "v_sum_decode"}
    assert checked["rows"] == {"prefill": 94, "decode": 8}


@pytest.mark.parametrize("control,over", [
    ("no_summaries", "eva_attention_decode"),
    ("sliding_window", "eva_attention_prefill"),
    ("no_mu", "eva_summary_rel_l2"),
    ("kv_fp8_e5m2", "eva_summary_rel_l2")])
def test_each_control_comes_out_not_within_the_limits(tiny, control, over):
    """The reference with a planted fault in the program's place, held
    to the PUBLISHED limits (the tiny preset's are wider)."""
    config, canonical = tiny
    config = dict(config, layer_limits=None)
    check = checks.layer_check(
        config, canonical, SEED, stand_in=checks.AlteredReference(
            config["reference"], QUANT, canonical, checks.CONTROLS[control]))
    assert check["limits"] == reference.LAYER_LIMITS
    assert check["within"] is False
    assert check["found"][over] > check["limits"][over]
    assert check["found"]["ffn_prefill"] == 0.0


def test_canonical_params_marks_the_tree_by_the_layer_check(tiny,
                                                            monkeypatch):
    config, _ = tiny
    tree = weights.canonical_params(config, SEED)
    assert tree["refused"] is False and tree["layer_check"]["within"]
    assert len(tree["layer_check"]["compared"]) == 6
    names = [c[0] for c in tree["layer_check"]["compared"]]
    assert "eva_summary_rel_l2" in names and "head_rel_l2" in names \
        and "layer_rel_l2.eva_attention_decode" in names
    monkeypatch.setitem(config, "layer_limits",
                        {"eva_attention_prefill": 1e-9})
    tree = weights.canonical_params(config, SEED)
    assert tree["refused"] is True
    with pytest.raises(RuntimeError, match="refused"):
        reference.all_logits(tree, config["reference"], QUANT, [1, 2, 3])


def test_costs_pinned_to_hand_arithmetic_at_the_published_widths():
    doc = _doc()
    dims = costs_evabyte.Dims.from_config(doc)
    assert (dims.num_hidden_layers, dims.vocab_size) == (32, 320)
    # a layer: 4 x 4096^2 + 3 x 4096 x 11008 = 202.4 M parameters
    per_layer = 4 * 4096 ** 2 + 3 * 4096 * 11008
    assert per_layer == 202_375_168
    w = costs_evabyte.linear_weight_bytes(dims, "sym_int4", 32)
    assert w == 0.5625 * (32 * per_layer + 4096 * 2560)
    assert 3.64e9 < w < 3.66e9
    # K and V of a position of a layer
    assert costs_evabyte.row_bytes(dims) == 2 * 32 * 128 * 2
    # the first position of the third window: one key, 256 summaries
    assert costs_evabyte.kv_bytes_per_token(dims, 4097) \
        == 32 * (1 + 256) * 16384
    # the last position of a cache of 8,192: a whole window, 384
    assert costs_evabyte.kv_bytes_per_token(dims, 8192) \
        == 32 * (2048 + 384) * 16384
    assert costs_evabyte.summarize_flops_per_token(dims) \
        == 32 * 6 * 16 * 32 * 128
    with pytest.raises(NotImplementedError, match="no training"):
        costs_evabyte.training_work(doc, dims, {}, 1)


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog row's config stands at the file's top
    level AND in the hf_config that runs, with its value: nothing is
    cut."""
    doc = _doc()
    row = [json.loads(x) for x in open(CATALOG) if '"EvaByte"' in x][0]
    assert doc["source"] == row["source_url"]
    assert doc["reduced"] == []
    for key, value in row["config"].items():
        assert doc[key] == value, key
        assert doc["hf_config"][key] == value, key
    assert doc["hf_config"]["architectures"] == ["EvaByteForCausalLM"]
    for line in ("origin", "max_seq", "norm", "residual", "rope",
                 "attention", "learned_vectors", "head", "fp32_logits",
                 "vocab", "cache"):
        assert doc["assumed"][line], line
    assert "not built" in doc["assumed"]["head"]
    ref = doc["reference"]
    assert (ref["layers"], ref["heads"], ref["head_dim"], ref["window"],
            ref["chunk"], ref["pred_heads"], ref["vocab"]) \
        == (32, 32, 128, 2048, 16, 8, 320)
    assert doc["engine"]["max_batch"] == 6
    assert doc["engine"]["max_seq"] == 8192
    assert doc["engine"]["prefill_chunk"] == 1024
    assert "ISSUE 39" in doc["assumed"]["prefill_chunk"]
    bench = spec.load_benchmark(_paths.ROOT)
    entry = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == [] and entry["source"] == doc["source"]


def test_the_cells_traffic_is_the_issues():
    t = json.loads((_paths.BENCH / "traffic"
                    / "longbytes-closed.json").read_text())
    assert (t["kind"], t["clients"], t["requests_per_client"],
            t["client_stagger_s"]) == ("closed", 6, 4, 0.05)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 4096,
                                  "sigma": 0.35, "min": 2048, "max": 6144}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 1024,
                                  "sigma": 0.35, "min": 512, "max": 1536}
    assert t["prompt_tokens"]["max"] + t["output_tokens"]["max"] == 7680
    assert (t["drain_seconds"], t["trace_start_s"], t["trace_seconds"]) \
        == (90, 30.0, 3.0)
    assert [s["share"] for s in t["sampling"]] == [0.5, 0.5]


def test_the_new_readers_read_the_counter_and_nothing_from_a_parent():
    cell = spec.Cell("evabyte-longbytes-closed", _paths.ROOT)
    path = cell.layer_metric_file("eva_summary_rows_share")
    rows = "bigdl_tpu_eva_rows_total"
    start = {(rows, (("kind", k),)): v for k, v in
             (("window", 10.0), ("summary", 0.0), ("context", 10.0))}
    end = {(rows, (("kind", k),)): v for k, v in
           (("window", 910.0), ("summary", 100.0), ("context", 4010.0))}
    obs = {"counters_start": start, "counters_end": end}
    assert layer_metrics.read_metric(path, obs) == pytest.approx(10.0)
    # a program without the counter (the parent): nothing, no raise
    assert layer_metrics.read_metric(
        path, {"counters_start": {}, "counters_end": {}}) is None
    assert layer_metrics.read_metric(path, {}) is None
    for name in ("eva_decode_attn_roofline", "eva_summarize_roofline"):
        p = cell.layer_metric_file(name)
        assert layer_metrics.read_metric(p, {}) is None
        tr = {"groups": {}, "programs": {}, "busy_s": 1.0, "window_s": 3.0}
        assert layer_metrics.read_metric(p, {
            "trace": tr, "peaks": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
            "work": {"eva_live_bytes": 1e9}}) is None
    groups = cell.trace_groups()
    assert groups["eva_decode_attn"]["within"] == "decode_programs"
    assert groups["eva_summarize"]["patterns"] == ["^%?eva_summarize"]
    got = layer_metrics.read_metric(
        cell.layer_metric_file("eva_decode_attn_roofline"),
        {"trace": {"groups": {"eva_decode_attn": {"seconds": 0.01}},
                   "programs": {}},
         "peaks": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
         "work": {"eva_live_bytes": 4.095e9}})
    assert got == pytest.approx(50.0)
