"""SDAR-MoE (generation by diffusion over blocks) on the serving path,
CPU, tiny widths at which every mechanism BINDS (3 layers in one scan,
blocks of 4 rows, 8 query heads on 2 KV heads of 32, 2 of 8 experts
held, top 3): prefill plus block passes through the cache against the
reference's ONE block-causal pass (a prompt of whole blocks and one with
a tail), `full_chunk` at `block` 1 bit for bit what it was, the `R`-rows
pass of the lanes kernel (interpret mode) against its XLA form, the four
shares of the experts against the uncut layer, the refusals of `from_hf`
and the checkpoint conversion."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import mimo_v2, sdar_moe
from bigdl_tpu.models.registry import get_family
from bigdl_tpu.ops import kvcache, swa

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

CONFIG = "sdar-30b-a3b-ep4-int4"
QUANT = {"qtype": "sym_int4", "block": 32}


def _doc():
    return json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())


def _tiny_config(ep_size=4, ep_rank=0):
    from harness import spec

    doc = _doc()
    config = spec.deep_update(doc, doc["tiny"])
    held = 8 // ep_size
    config["hf_config"].update(num_experts=held, ep_size=ep_size,
                               ep_rank=ep_rank)
    config["reference"].update(held=held, first_held=held * ep_rank)
    return config


@pytest.fixture(scope="module")
def built():
    from harness import weights_sdar_moe as weights

    config = _tiny_config()
    box = {}
    model, _ = weights.build_model(
        config, 2 ** 31 + 3, merge=True,
        with_canonical=lambda canonical, cfg: box.update(
            canonical=jax.tree.map(lambda x: x, canonical)))
    return config, model, box["canonical"]


def test_registry_loads_the_family_and_its_step_is_a_block(built):
    _, model, _ = built
    cfg = model.config
    fam = get_family("SdarMoeForCausalLM")
    assert fam.name == "sdar_moe"
    assert fam.block_spec(cfg) == sdar_moe.BlockSpec(
        4, 2, 0, "low_confidence_dynamic", 0.9)
    assert get_family("LlamaForCausalLM").block_spec is None
    assert sdar_moe.scan_plan(cfg) == (0, 1, 3)
    kind = cfg.full
    assert (kind.qk_norm, kind.rotary, kind.gate, kind.window,
            kind.block) == (True, True, False, 0, 4)
    spec = kvcache.cache_spec_of(model.family, cfg)
    assert [(p.name, p.layers, p.dims, p.ring) for p in spec.planes] == [
        ("full_k", 3, (64,), 0), ("full_v", 3, (64,), 0)]
    # the published sizes: 48 layers in ONE scan, 32 of 128 experts, rows
    # of 512 values, q / k / v merged to 5120 columns, n_s = 2, 2
    pub = sdar_moe.SdarMoeConfig.from_hf(_doc()["hf_config"])
    assert (pub.num_hidden_layers, pub.share, pub.full.k_width,
            pub.full.q_width + 2 * pub.full.k_width, pub.vocab_size) == (
        48, (128, 0, 32), 512, 5120, 37984)
    assert sdar_moe.scan_plan(pub) == (0, 1, 48)
    assert [int(pub.block.owed(s)) for s in range(2)] == [2, 2]
    three = dataclasses.replace(pub, denoising_steps=3).block
    assert [int(three.owed(s)) for s in range(3)] == [2, 1, 1]
    shapes = {k: v.shape for k, v in model.params["layers"].items()}
    assert shapes["qkv_proj"] == (64, 8 * 32 + 2 * 2 * 32)
    assert model.params["layers"]["qkv_proj"].data.shape[0] == 3
    assert "q_proj" not in shapes and shapes["router"] == (3, 64, 8)


@pytest.mark.parametrize("n_prompt", [32, 24], ids=["whole", "shorter"])
def test_prefill_and_block_passes_match_one_block_causal_pass(built,
                                                              n_prompt):
    """The program's chunks and then block passes through the slab (MASK
    ids in the blocks, each stored as it stands) against the reference's
    one pass over the same ids; a causal mask in the program's place is
    far outside."""
    from harness import reference_sdar_moe as reference

    config, model, canonical = built
    cfg, arch = model.config, config["reference"]
    ids = np.random.default_rng(n_prompt).integers(1, 256, n_prompt + 12)
    for at in (n_prompt + 1, n_prompt + 2, n_prompt + 7, n_prompt + 8):
        ids[at] = cfg.mask_token_id
    want = np.asarray(reference.all_logits(canonical, arch, QUANT, ids))
    fwd = jax.jit(model.family.forward, static_argnums=1)
    cache = model.family.new_cache(cfg, 1, 64, "bf16")
    rows = []
    for lo, hi in [(0, 16), (16, n_prompt)] + [
            (a, a + 4) for a in range(n_prompt, n_prompt + 12, 4)]:
        lg, cache = fwd(model.params, cfg, jnp.asarray(ids[lo:hi])[None],
                        cache)
        rows.append(np.asarray(lg[0]))
    got = np.concatenate(rows)
    limit = reference.rounding_walk(3)
    assert reference.relative_l2(got[:n_prompt], want[:n_prompt]) < limit
    assert reference.relative_l2(got[n_prompt:], want[n_prompt:]) < limit
    assert int(np.asarray(cache.pos).reshape(-1)[0]) == n_prompt + 12
    causal = np.asarray(reference.all_logits(
        canonical, arch, QUANT, ids, alter={"mask": "causal"}))
    assert reference.relative_l2(got, causal) > 10 * limit


def test_a_denoise_pass_is_overwritten_and_the_storing_pass_stands(built):
    """A pass at a `pos` that does not move writes its rows over those
    of the pass before: what a later block reads is the LAST pass's."""
    _, model, _ = built
    cfg = model.config
    fwd = jax.jit(model.family.forward, static_argnums=1)
    ids = np.random.default_rng(7).integers(1, 256, 16)
    cache = model.family.new_cache(cfg, 1, 32, "bf16")
    _, cache = fwd(model.params, cfg, jnp.asarray(ids[:8])[None], cache)
    noisy = jnp.asarray([[ids[8], 0, 0, ids[11]]])
    _, once = fwd(model.params, cfg, noisy, cache)
    _, twice = fwd(model.params, cfg, jnp.asarray(ids[8:12])[None],
                   once.replace(pos=cache.pos))
    _, clean = fwd(model.params, cfg, jnp.asarray(ids[8:12])[None], cache)
    for name in ("full_k", "full_v", "pos"):
        np.testing.assert_array_equal(np.asarray(getattr(twice, name)),
                                      np.asarray(getattr(clean, name)))
    assert not np.array_equal(np.asarray(once.full_k),
                              np.asarray(clean.full_k))


def test_full_chunk_at_block_one_is_the_causal_chunk_bit_for_bit():
    rng = np.random.default_rng(1)
    t, s, h, g, d = 8, 32, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(t, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(s, g * d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(s, g * d)), jnp.bfloat16)
    old = swa.full_chunk(q, k, v, jnp.int32(8), 0.25, g)
    one = swa.full_chunk(q, k, v, jnp.int32(8), 0.25, g, 1)
    np.testing.assert_array_equal(np.asarray(old), np.asarray(one))
    # and the compiled program is the same text: MiMo's and Trinity's
    low = lambda *a: jax.jit(swa.full_chunk, static_argnums=(4, 5, 6)  # noqa: E731
                             ).lower(q, k, v, jnp.int32(8), 0.25, g, *a)
    assert jax.jit(swa.full_chunk, static_argnums=(4, 5)).lower(
        q, k, v, jnp.int32(8), 0.25, g).as_text() \
        .replace("jit_full_chunk", "") == low(1).as_text() \
        .replace("jit_full_chunk", "")
    # blocks of 4: row 0 of a block sees the block's last key
    blk = np.asarray(swa.full_chunk(q, k, v, jnp.int32(8), 0.25, g, 4))
    assert not np.allclose(blk[0], np.asarray(old)[0])
    np.testing.assert_allclose(blk[3], np.asarray(old)[3], rtol=1e-6)
    np.testing.assert_allclose(blk[7], np.asarray(old)[7], rtol=1e-6)


@pytest.mark.parametrize("rows", [4, 2])
def test_the_rows_pass_of_the_lanes_kernel_matches_its_xla_form(rows):
    """`R` rows a slot under ONE limit as `R x H` heads of
    `decode_attention_lanes` (interpret mode) against `decode_xla`, and
    against `full_chunk` under the block mask row by row."""
    rng = np.random.default_rng(rows)
    b, s, h, g, d, layers = 3, 128, 8, 2, 64, 2
    q = jnp.asarray(rng.normal(size=(b, rows, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(layers, b, s, g * d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(layers, b, s, g * d)), jnp.bfloat16)
    pos = jnp.asarray([0, 40, 124 - rows + 4 - 4], jnp.int32) // rows * rows
    scale = d ** -0.5
    xla = swa.full_block(q, k, v, jnp.int32(1), pos, scale, g,
                         backend="xla")
    ker = swa.full_block(q, k, v, jnp.int32(1), pos, scale, g,
                         backend="pallas")
    assert xla.shape == (b, rows, h, d)
    np.testing.assert_allclose(np.asarray(ker, np.float32),
                               np.asarray(xla, np.float32), atol=2e-2)
    for i in range(b):
        want = swa.full_chunk(q[i], k[1, i], v[1, i], pos[i], scale, g,
                              rows)
        np.testing.assert_allclose(np.asarray(xla[i], np.float32),
                                   np.asarray(want), atol=2e-2)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each chip's routed part on the same rows, ranks 0-3, summed,
    against the reference's layer with every expert held."""
    from harness import reference_sdar_moe as reference, \
        weights_sdar_moe as weights

    whole = _tiny_config(ep_size=1)
    cfg1 = sdar_moe.SdarMoeConfig.from_hf(whole["hf_config"])
    canonical = weights.build_params(cfg1, "sym_int4", 11)
    arch = whole["reference"]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.bfloat16)
    _, lp, ex = next(iter(reference.layer_stack(canonical, arch)))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.feed_forward(
            h.astype(jnp.float32), lp, ex, arch, QUANT))
    total = 0.0
    for rank in range(4):
        cfg = dataclasses.replace(cfg1, num_experts=2, ep_size=4,
                                  ep_rank=rank)
        part = jax.tree.map(lambda a: a[:, 2 * rank:2 * rank + 2],
                            canonical["experts"])
        y, stats = sdar_moe.moe_block(
            h.reshape(6, 4, 64), sdar_moe.layer_leaves(canonical, cfg, 0),
            part, jnp.int32(0), cfg)
        total = total + np.asarray(y, np.float32).reshape(24, 64)
        assert int(stats[0]) + int(stats[1]) == 24 * 3
    assert reference.relative_l2(total, want) < 0.02
    # one share alone is a quarter of it, not the layer
    assert reference.relative_l2(np.asarray(y, np.float32).reshape(24, 64),
                                 want) > 0.3


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
    ("use_sliding_window", True), ("sliding_window", 4096),
    ("remasking_strategy", "random"), ("denoising_steps", 5),
    ("denoising_steps", 0), ("mask_token_id", 151669)])
def test_from_hf_refuses_what_it_does_not_implement(key, value):
    hf = dict(_doc()["hf_config"])
    assert sdar_moe.SdarMoeConfig.from_hf(hf).block_length == 4
    hf[key] = value
    with pytest.raises((NotImplementedError, ValueError)) as e:
        sdar_moe.SdarMoeConfig.from_hf(hf)
    assert key in str(e.value)


def test_convert_hf_params_round_trip_keeps_the_share_and_the_slice():
    """A hand-made checkpoint in the names under `assumed` (all 8
    experts, 512 vocabulary rows) -> rank 1's served tree: its two
    experts, its 256 rows, q / k / v merged, the head padded to a lane
    multiple; the forward runs on it."""
    cfg = sdar_moe.SdarMoeConfig.from_hf(dict(
        _tiny_config(ep_rank=1)["hf_config"]))
    rng = np.random.default_rng(3)
    w = lambda *s: rng.normal(size=s).astype(np.float32) * 0.05  # noqa: E731
    tensors = {"model.embed_tokens.weight": w(512, 64),
               "model.norm.weight": 1 + w(64), "lm_head.weight": w(512, 64)}
    for i in range(3):
        p = f"model.layers.{i}."
        tensors.update({
            p + "self_attn.q_proj.weight": w(256, 64),
            p + "self_attn.k_proj.weight": w(64, 64),
            p + "self_attn.v_proj.weight": w(64, 64),
            p + "self_attn.o_proj.weight": w(64, 256),
            p + "self_attn.q_norm.weight": 1 + w(32),
            p + "self_attn.k_norm.weight": 1 + w(32),
            p + "input_layernorm.weight": 1 + w(64),
            p + "post_attention_layernorm.weight": 1 + w(64),
            p + "mlp.gate.weight": w(8, 64)})
        for e in range(8):
            tensors.update({
                p + f"mlp.experts.{e}.gate_proj.weight": w(32, 64),
                p + f"mlp.experts.{e}.up_proj.weight": w(32, 64),
                p + f"mlp.experts.{e}.down_proj.weight": w(64, 32)})
    params = sdar_moe.convert_hf_params(tensors.items(), cfg, qtype=None)
    assert params["embed_tokens"].shape == (256, 64)
    np.testing.assert_allclose(
        np.asarray(params["embed_tokens"], np.float32),
        tensors["model.embed_tokens.weight"][256:], atol=1e-2)
    assert params["layers"]["qkv_proj"].shape == (3, 64, 384)
    assert params["layers"]["router"].shape == (3, 64, 8)
    assert params["experts"]["experts_gate"].shape == (3, 2, 64, 32)
    np.testing.assert_allclose(
        np.asarray(params["experts"]["experts_down"][2, 1], np.float32),
        tensors["model.layers.2.mlp.experts.3.down_proj.weight"].T,
        atol=1e-2)
    cache = sdar_moe.new_cache(cfg, 2, 16, False)
    lg, cache = sdar_moe.forward(params, cfg, jnp.ones((2, 8), jnp.int32),
                                 cache)
    assert lg.shape == (2, 8, 256) and bool(jnp.isfinite(lg).all())
    del tensors["model.layers.1.mlp.experts.2.up_proj.weight"]
    with pytest.raises(ValueError, match="held experts"):
        sdar_moe.convert_hf_params(tensors.items(), cfg, qtype=None)
    # a quantized head of 37,984 columns is served at 38,016
    from bigdl_tpu.ops.quant import quantize

    head = sdar_moe.pad_head(quantize(jnp.ones((64, 200)), "sym_int4"))
    assert head.shape == (64, 256)
    assert sdar_moe.pad_head(head) is head
    np.testing.assert_array_equal(
        np.asarray(head.dequantize(jnp.float32))[:, 200:], 0.0)
    assert mimo_v2.GqaKind(8, 2, 32, 32, 1e4, False).block == 1
