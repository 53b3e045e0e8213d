"""Explicit-shard_map tensor-parallel inference (parallel/tp.py):
the kernel-capable TP path — logits/generations must match the
single-device forward exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from bigdl_tpu.generation import generate_on_device
from bigdl_tpu.models import llama as M
from bigdl_tpu.models.llama import LlamaConfig
from bigdl_tpu.parallel.tp import (new_cache_tp, shard_params_tp,
                                   tp_forward_step, tp_generate)
from bigdl_tpu.utils.testing import random_llama_params

# sized so EVERY quantized plane splits by tp=4: row-parallel weights
# need K/32 % 4 == 0 (o_proj K = h*hd = 256, down_proj K = ff = 512)
CFG = LlamaConfig(
    vocab_size=128,
    hidden_size=256,
    intermediate_size=512,
    num_hidden_layers=2,
    num_attention_heads=8,
    num_key_value_heads=4,
    max_position_embeddings=128,
)


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    return Mesh(np.array(jax.devices()[:4]), ("tp",))


@pytest.mark.parametrize("qtype", ["sym_int4", None])
def test_tp_logits_match_single_device(mesh, qtype):
    params = random_llama_params(CFG, qtype=qtype, seed=0)
    prompt = jnp.asarray(np.arange(1, 13, dtype=np.int32)[None])

    cache1 = M.new_cache(CFG, 1, 64)
    ref_lg, ref_cache = M.forward(params, CFG, prompt, cache1)

    with mesh:
        p_s = shard_params_tp(params, mesh)
        cache = new_cache_tp(CFG, 1, 64, mesh)
        lg, cache = tp_forward_step(p_s, CFG, prompt, cache, mesh)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(ref_lg[:, -1, :]), rtol=2e-2,
        atol=2e-2)

    # decode step continues identically (cache round-trips)
    tok = jnp.argmax(ref_lg[:, -1:, :], axis=-1).astype(jnp.int32)
    ref_lg2, _ = M.forward(params, CFG, tok, ref_cache)
    with mesh:
        lg2, _ = tp_forward_step(p_s, CFG, tok, cache, mesh)
    np.testing.assert_allclose(
        np.asarray(lg2), np.asarray(ref_lg2[:, -1, :]), rtol=2e-2,
        atol=2e-2)


def test_tp_generate_matches_greedy(mesh):
    params = random_llama_params(CFG, qtype="sym_int4", seed=1)
    prompt = np.arange(1, 10, dtype=np.int32)[None]

    cache = M.new_cache(CFG, 1, 64)
    ref, _ = generate_on_device(
        params, CFG, M.forward, jnp.asarray(prompt), cache,
        max_new_tokens=10)

    with mesh:
        p_s = shard_params_tp(params, mesh)
        out = tp_generate(p_s, CFG, prompt, mesh, max_new_tokens=10,
                          max_seq=64)
    np.testing.assert_array_equal(out[:, prompt.shape[1]:],
                                  np.asarray(ref))


def test_tp_fp8_kv_cache_matches(mesh):
    """fp8-quantized KV under explicit TP: head-sharded e5m2 cache,
    logits identical to the single-device fp8 path."""
    params = random_llama_params(CFG, qtype="sym_int4", seed=3)
    prompt = jnp.asarray(np.arange(1, 13, dtype=np.int32)[None])
    c1 = M.new_cache(CFG, 1, 64, quantized=True)
    ref, _ = M.forward(params, CFG, prompt, c1)
    with mesh:
        p_s = shard_params_tp(params, mesh)
        cache = new_cache_tp(CFG, 1, 64, mesh, quantized=True)
        lg, _ = tp_forward_step(p_s, CFG, prompt, cache, mesh)
    np.testing.assert_allclose(np.asarray(lg),
                               np.asarray(ref[:, -1, :]),
                               rtol=2e-2, atol=2e-2)


def test_tp_custom_axis_name():
    """The axis= parameter must thread through specs/cache/forward."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    m2 = Mesh(np.array(jax.devices()[:2]), ("model",))
    params = random_llama_params(CFG, qtype=None, seed=2)
    prompt = np.arange(1, 9, dtype=np.int32)[None]
    cache = M.new_cache(CFG, 1, 64)
    ref, _ = generate_on_device(
        params, CFG, M.forward, jnp.asarray(prompt), cache,
        max_new_tokens=4)
    with m2:
        p_s = shard_params_tp(params, m2, axis="model")
        out = tp_generate(p_s, CFG, prompt, m2, axis="model",
                          max_new_tokens=4, max_seq=64)
    np.testing.assert_array_equal(out[:, prompt.shape[1]:],
                                  np.asarray(ref))


BLOOM_CFG = LlamaConfig(
    # bloom-style block: ALiBi (no rope), layernorm, non-gated gelu MLP
    vocab_size=128, hidden_size=256, intermediate_size=512,
    num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=8,
    max_position_embeddings=128, use_rope=False, use_alibi=True,
    norm_type="layernorm", mlp_gated=False, hidden_act="gelu")


def test_tp_alibi_family_matches(mesh):
    """Family coverage: under explicit TP each device
    must slice the FULL alibi slope schedule at its head offset (heads
    8 -> 4 shards x 2 heads with four DIFFERENT slope pairs); logits
    equal to the single-device forward."""
    cfg = BLOOM_CFG
    params = random_llama_params(cfg, qtype="sym_int4", seed=7)
    layers = dict(params["layers"])
    d = cfg.hidden_size
    zeros = jnp.zeros((cfg.num_hidden_layers, d), jnp.bfloat16)
    layers["input_layernorm_bias"] = zeros
    layers["post_attention_layernorm_bias"] = zeros + 0.01
    params = {**params, "layers": layers,
              "norm_bias": jnp.zeros((d,), jnp.bfloat16)}
    prompt = jnp.asarray(np.arange(1, 13, dtype=np.int32)[None])

    ref_lg, ref_cache = M.forward(params, cfg, prompt,
                                  M.new_cache(cfg, 1, 64))
    with mesh:
        p_s = shard_params_tp(params, mesh)
        cache = new_cache_tp(cfg, 1, 64, mesh)
        lg, cache2 = tp_forward_step(p_s, cfg, prompt, cache, mesh)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(ref_lg[:, -1, :]), rtol=2e-2,
        atol=2e-2)

    # decode continues identically (ALiBi bias depends on positions)
    tok = jnp.argmax(ref_lg[:, -1:, :], axis=-1).astype(jnp.int32)
    ref_lg2, _ = M.forward(params, cfg, tok, ref_cache)
    with mesh:
        lg2, _ = tp_forward_step(p_s, cfg, tok, cache2, mesh)
    np.testing.assert_allclose(
        np.asarray(lg2), np.asarray(ref_lg2[:, -1, :]), rtol=2e-2,
        atol=2e-2)


FALCON_CFG = LlamaConfig(
    # falcon-style block: parallel residual, SHARED input norm, GQA,
    # non-gated gelu MLP
    vocab_size=128, hidden_size=256, intermediate_size=512,
    num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
    max_position_embeddings=128, parallel_residual=True,
    shared_input_norm=True, mlp_gated=False, hidden_act="gelu")

GPTNEOX_CFG = LlamaConfig(
    # gptneox-style block: parallel residual, separate post-attn norm,
    # LAYERNORM, non-gated gelu MLP, partial rotary
    vocab_size=128, hidden_size=256, intermediate_size=512,
    num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=8,
    max_position_embeddings=128, parallel_residual=True,
    norm_type="layernorm", mlp_gated=False, hidden_act="gelu",
    rotary_dim=16)


@pytest.mark.parametrize("cfg", [FALCON_CFG, GPTNEOX_CFG],
                         ids=["falcon", "gptneox"])
def test_tp_parallel_residual_families_match(mesh, cfg):
    """Explicit TP (kernels on shards) must cover
    parallel-residual / non-gated families — logits equal to the
    single-device forward."""
    params = random_llama_params(cfg, qtype="sym_int4", seed=6)
    if cfg.norm_type == "layernorm":
        layers = dict(params["layers"])
        d = cfg.hidden_size
        zeros = jnp.zeros((cfg.num_hidden_layers, d), jnp.bfloat16)
        layers["input_layernorm_bias"] = zeros
        layers["post_attention_layernorm_bias"] = zeros + 0.01
        params = {**params, "layers": layers,
                  "norm_bias": jnp.zeros((d,), jnp.bfloat16)}
    prompt = jnp.asarray(np.arange(1, 13, dtype=np.int32)[None])

    ref_lg, ref_cache = M.forward(params, cfg, prompt,
                                  M.new_cache(cfg, 1, 64))
    with mesh:
        p_s = shard_params_tp(params, mesh)
        cache = new_cache_tp(cfg, 1, 64, mesh)
        lg, cache2 = tp_forward_step(p_s, cfg, prompt, cache, mesh)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(ref_lg[:, -1, :]), rtol=2e-2,
        atol=2e-2)

    # decode continues identically (cache round-trips through shards)
    tok = jnp.argmax(ref_lg[:, -1:, :], axis=-1).astype(jnp.int32)
    ref_lg2, _ = M.forward(params, cfg, tok, ref_cache)
    with mesh:
        lg2, _ = tp_forward_step(p_s, cfg, tok, cache2, mesh)
    np.testing.assert_allclose(
        np.asarray(lg2), np.asarray(ref_lg2[:, -1, :]), rtol=2e-2,
        atol=2e-2)


def test_tp_moe_logits_match_single_device(mesh):
    """Explicit TP must cover MoE expert stacks — each
    expert's ff dim splits across tp (gate/up column-, down row-
    parallel with an in-body psum on the partial expert outputs);
    logits equal the single-device forward, prefill AND decode (the
    decode step exercises the per-token expert-gather path under the
    collective wrapper)."""
    from bigdl_tpu.models.mixtral import MixtralConfig
    from bigdl_tpu.utils.testing import random_mixtral_params

    cfg = MixtralConfig(
        vocab_size=128, hidden_size=256, intermediate_size=512,
        num_hidden_layers=2, num_attention_heads=8,
        num_key_value_heads=4, max_position_embeddings=128,
        num_local_experts=4, num_experts_per_tok=2)
    params = random_mixtral_params(cfg, qtype="sym_int4", seed=9)
    prompt = jnp.asarray(np.arange(1, 13, dtype=np.int32)[None])

    ref_lg, ref_cache = M.forward(params, cfg, prompt,
                                  M.new_cache(cfg, 1, 64))
    with mesh:
        p_s = shard_params_tp(params, mesh)
        cache = new_cache_tp(cfg, 1, 64, mesh)
        lg, cache2 = tp_forward_step(p_s, cfg, prompt, cache, mesh)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(ref_lg[:, -1, :]), rtol=2e-2,
        atol=2e-2)

    tok = jnp.argmax(ref_lg[:, -1:, :], axis=-1).astype(jnp.int32)
    ref_lg2, _ = M.forward(params, cfg, tok, ref_cache)
    with mesh:
        lg2, _ = tp_forward_step(p_s, cfg, tok, cache2, mesh)
    np.testing.assert_allclose(
        np.asarray(lg2), np.asarray(ref_lg2[:, -1, :]), rtol=2e-2,
        atol=2e-2)


def test_tp_moe_indivisible_ff_rejected(mesh):
    """MoE ff that doesn't divide by tp must fail with a named error
    (expert stacks are not lane-padded)."""
    from bigdl_tpu.models.mixtral import MixtralConfig
    from bigdl_tpu.utils.testing import random_mixtral_params

    cfg = MixtralConfig(
        vocab_size=64, hidden_size=256, intermediate_size=2051,
        num_hidden_layers=1, num_attention_heads=8,
        num_key_value_heads=4, max_position_embeddings=64,
        num_local_experts=2, num_experts_per_tok=2)
    params = random_mixtral_params(cfg, qtype=None, seed=0)
    with pytest.raises(ValueError, match="expert ff"):
        with mesh:
            tp_generate(params, cfg, np.arange(1, 5)[None], mesh,
                        max_new_tokens=2, max_seq=32)


def test_tp_rejects_indivisible_heads(mesh):
    bad = LlamaConfig(vocab_size=64, hidden_size=48, intermediate_size=96,
                      num_hidden_layers=1, num_attention_heads=6,
                      num_key_value_heads=6)
    params = random_llama_params(bad, qtype=None, seed=0)
    with pytest.raises(ValueError,
                       match="not divisible|cannot shard"):
        with mesh:
            tp_generate(shard_params_tp(params, mesh), bad,
                        np.arange(1, 5, dtype=np.int32)[None], mesh,
                        max_new_tokens=2, max_seq=32)


def test_pad_ff_exact_zero_extension():
    """pad_ff_for_tp must be numerically invisible: padded gate/up
    columns and down rows dequantize to exactly zero, real entries
    unchanged (lane-aligning tp shards of ff=11008)."""
    from bigdl_tpu.ops.quant import dequantize
    from bigdl_tpu.parallel.tp import pad_ff_for_tp

    cfg = LlamaConfig(
        vocab_size=128, hidden_size=128, intermediate_size=2752,
        num_hidden_layers=2, num_attention_heads=8,
        num_key_value_heads=4, max_position_embeddings=128)
    params = random_llama_params(cfg, qtype="sym_int4", seed=0)
    padded = pad_ff_for_tp(params, 4)     # 2752 -> 4 x 768 = 3072

    def layer0(tree, name):
        return jax.tree.map(lambda a: a[0], tree["layers"][name])

    for name in ("gate_proj", "up_proj"):
        w0, w1 = layer0(params, name), layer0(padded, name)
        assert w1.shape == (128, 3072)
        d0 = np.asarray(dequantize(w0), np.float32)
        d1 = np.asarray(dequantize(w1), np.float32)
        np.testing.assert_array_equal(d1[:, :2752], d0)
        np.testing.assert_array_equal(d1[:, 2752:], 0.0)
    w0, w1 = layer0(params, "down_proj"), layer0(padded, "down_proj")
    assert w1.shape == (3072, 128)
    d0 = np.asarray(dequantize(w0), np.float32)
    d1 = np.asarray(dequantize(w1), np.float32)
    np.testing.assert_array_equal(d1[:2752, :], d0)
    np.testing.assert_array_equal(d1[2752:, :], 0.0)


def test_tp_ff_padding_logits_match(mesh):
    """End-to-end explicit TP over an ff whose tp=4 shard is NOT
    lane-aligned (2752/4 = 688): shard_params_tp pads to 3072 and the
    logits still match the single-device forward exactly."""
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=256, intermediate_size=2752,
        num_hidden_layers=2, num_attention_heads=8,
        num_key_value_heads=4, max_position_embeddings=128)
    params = random_llama_params(cfg, qtype="sym_int4", seed=4)
    prompt = jnp.asarray(np.arange(1, 13, dtype=np.int32)[None])

    cache1 = M.new_cache(cfg, 1, 64)
    ref_lg, _ = M.forward(params, cfg, prompt, cache1)

    with mesh:
        p_s = shard_params_tp(params, mesh)
        gate = p_s["layers"]["gate_proj"]
        assert gate.shape[1] == 3072, "ff padding did not engage"
        cache = new_cache_tp(cfg, 1, 64, mesh)
        lg, _ = tp_forward_step(p_s, cfg, prompt, cache, mesh)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(ref_lg[:, -1, :]), rtol=2e-2,
        atol=2e-2)


def test_tp_logits_match_on_mxu_layout(mesh):
    """Explicit TP over int4-dtype (MXU layout) weights — the shipped
    TPU load default — must shard (incl. host-side ff padding of int4
    planes) and match single-device logits."""
    from bigdl_tpu.ops.quant import tree_to_mxu_layout

    params = tree_to_mxu_layout(random_llama_params(CFG, qtype="sym_int4",
                                                    seed=0))
    prompt = jnp.asarray(np.arange(1, 13, dtype=np.int32)[None])
    ref_lg, _ = M.forward(params, CFG, prompt, M.new_cache(CFG, 1, 64))
    with mesh:
        p_s = shard_params_tp(params, mesh)
        cache = new_cache_tp(CFG, 1, 64, mesh)
        lg, _ = tp_forward_step(p_s, CFG, prompt, cache, mesh)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(ref_lg[:, -1, :]), rtol=2e-2,
        atol=2e-2)
