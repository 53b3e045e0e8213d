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
