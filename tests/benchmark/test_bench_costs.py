"""The benchmark's copy of the cost arithmetic: pinned to the program's
``observability/roofline.py`` at one geometry today, and the packed
bytes checked against a hand count."""

import json

import pytest

import _paths
from harness import costs

CONFIG = json.loads(
    (_paths.BENCH / "configs" / "mistral-7b-int4.json").read_text())
DIMS = costs.Dims.from_config(CONFIG)


def test_dims_come_from_the_configuration_file():
    assert (DIMS.hidden_size, DIMS.intermediate_size, DIMS.vocab_size,
            DIMS.num_attention_heads, DIMS.num_key_value_heads, DIMS.hd,
            DIMS.num_hidden_layers) == (4096, 14336, 32000, 32, 8, 128, 32)


@pytest.mark.parametrize("seq_len", [1, 384, 2048])
@pytest.mark.parametrize("kv", ["bf16", "int8", "int4"])
def test_pinned_to_the_programs_roofline_today(seq_len, kv):
    from bigdl_tpu.observability import roofline

    assert costs.model_flops_per_token(DIMS) == \
        roofline.model_flops_per_token(DIMS)
    assert costs.attn_flops_per_token(DIMS, seq_len) == \
        roofline.attn_flops_per_token(DIMS, seq_len)
    assert costs.kv_bytes_per_token(DIMS, seq_len, kv) == \
        roofline.kv_bytes_per_token(DIMS, seq_len, kv)
    wb = 4.26e9
    mine = costs.decode_costs(DIMS, wb, seq_len, kv, batch=32)
    theirs = roofline.decode_costs(DIMS, wb, seq_len, kv, batch=32,
                                   device_kind="TPU v5 lite")
    assert mine["flops"] == theirs["flops"]
    assert mine["hbm_bytes"] == theirs["hbm_bytes"]
    assert costs.prefill_costs(DIMS, seq_len)["flops"] == \
        roofline.prefill_costs(DIMS, seq_len)["flops"]


def test_peaks_pinned_to_the_programs_table_today():
    from bigdl_tpu.observability import roofline

    table = json.loads(
        (_paths.BENCH / "harness" / "peaks.json").read_text())
    chip = table["chips"]["TPU v5 lite"]
    assert (chip["bf16_tflops"], chip["hbm_gbps"]) == \
        roofline.CHIP_PEAKS["TPU v5 lite"]
    assert table["source"]


def test_packed_int4_and_scale_bytes_against_a_hand_count():
    # one [4096, 14336] linear: 4096*14336/2 code bytes, and one
    # bfloat16 scale per block of 32 rows and column
    assert costs.quantized_linear_bytes(4096, 14336, "sym_int4", 32) == \
        29_360_128 + 128 * 14336 * 2
    # a layer: q 4096x4096, k and v 4096x1024, o 4096x4096, gate, up
    # 4096x14336, down 14336x4096 = 218,103,808 weights; the head
    # 4096x32000. 4.5 bits a weight and a sixteenth of a byte of scale
    weights = 32 * 218_103_808 + 4096 * 32000
    assert costs.linear_weight_bytes(DIMS, "sym_int4", 32) == \
        weights / 2 + weights / 32 * 2
    assert costs.linear_weight_bytes(DIMS, "sym_int4", 32) == \
        pytest.approx(3.999e9, rel=1e-3)


def test_bf16_slab_bytes_per_token_is_the_issues_131072():
    assert costs.kv_bytes_per_token(DIMS, 1, "bf16") == 131_072


def test_training_counts_forward_and_activation_gradients_only():
    fwd = costs.model_flops_per_token(DIMS) + costs.attn_flops_per_token(
        DIMS, 512)
    assert costs.train_flops_per_token(DIMS, 1024) == 2 * fwd
    assert costs.train_flops_per_token(DIMS, 1024, frozen_base=False) \
        == 3 * fwd


def test_serving_work_is_what_the_runner_computed_inline():
    """Pinned by hand at this geometry: the packed linears once, and for
    every token received in the traced stretch the bf16 cache of its
    request at that token's position, 131,072 B a position."""
    records = [
        # 100-token prompt; tokens 1-2 before the stretch, 3-5 inside
        {"prompt_tokens": 100, "chunks": [[9.0, 2], [10.5, 3], [12.5, 1]]},
        # nothing inside
        {"prompt_tokens": 7, "chunks": [[1.0, 4]]},
        # a failed request has no chunks
        {"prompt_tokens": 50},
    ]
    work = costs.serving_work(CONFIG, DIMS, records, "bf16", (10.0, 12.0))
    assert set(work) == {"linear_weight_bytes", "decode_kv_bytes"}
    assert work["linear_weight_bytes"] == costs.linear_weight_bytes(
        DIMS, "sym_int4", 32) == (32 * 218_103_808 + 4096 * 32000) * 0.5625
    assert work["decode_kv_bytes"] == 131_072 * (102 + 103 + 104)
    # an int8 cache: a byte an element and a float32 scale per head and
    # plane: 2 * 32 layers * 8 heads * (128 + 4) a position
    assert costs.serving_work(CONFIG, DIMS, records, "int8", (10.0, 12.0))[
        "decode_kv_bytes"] == 2 * 32 * 8 * 132 * (102 + 103 + 104)
    # nothing traced: no cache bytes to divide by
    assert set(costs.serving_work(CONFIG, DIMS, records, "bf16", None)) == {
        "linear_weight_bytes"}


def test_training_work_is_what_the_runner_computed_inline():
    work = costs.training_work(CONFIG, DIMS, {"seq_len": 1024}, 8 * 1024)
    assert set(work) == {"train_flops_per_step"}
    assert work["train_flops_per_step"] == 8192 * 2 * (
        costs.model_flops_per_token(DIMS)
        + costs.attn_flops_per_token(DIMS, 512))
    # by hand: 32 layers * 2 * 218,103,808 + the head's 2 * 4096 * 32000
    # matmul operations a token, 32 * 4 * 32 * 128 * 512 of attention
    assert costs.model_flops_per_token(DIMS) == 14_220_787_712
    assert costs.attn_flops_per_token(DIMS, 512) == 268_435_456
