"""The plain reference against the program's forward on the CPU at tiny
widths, for both attention styles of the benchmark's configurations
(full half-rotation rope without bias; half-dim interleaved rope with
QKV bias and a KV group of 4), and against a wrong reference."""

import json

import numpy as np
import pytest

import _paths
from harness import reference, spec, weights

CONFIGS = ["mistral-7b-int4", "chatglm2-6b-int4-pagedkv8"]


def _tiny(name):
    doc = json.loads((_paths.BENCH / "configs" / f"{name}.json").read_text())
    return spec.deep_update(doc, doc["tiny"])


@pytest.fixture(scope="module", params=CONFIGS)
def built(request):
    import jax
    import jax.numpy as jnp

    config = _tiny(request.param)
    box = {}
    model, _ = weights.build_model(
        config, 2 ** 31 + 3, merge=True,
        with_canonical=lambda canonical, cfg: box.update(
            canonical=jax.tree.map(lambda x: x, canonical)))
    ids = np.random.default_rng(3).integers(
        1, config["reference"]["vocab"], 32)
    quant = {"qtype": config["quant"], "block": config["quant_block"]}
    ref = np.asarray(reference.all_logits(
        box["canonical"], config["reference"], quant, ids.tolist()))
    cfg, family = model.config, model.family
    kv = config["engine"]["kv_cache_dtype"]
    cache = family.new_cache(cfg, 1, 64, kv)
    lg, _ = jax.jit(family.prefill, static_argnums=1)(
        model.params, cfg, jnp.asarray(ids, jnp.int32)[None], cache)
    train = np.asarray(jax.jit(family.forward_train, static_argnums=1)(
        model.params, cfg, jnp.asarray(ids, jnp.int32)[None]))[0]
    return {"config": config, "ref": ref, "canonical": box["canonical"],
            "prefill": np.asarray(lg, np.float32).reshape(-1),
            "train": train, "ids": ids, "quant": quant, "kv": kv}


def test_prefill_last_logits_agree_with_the_reference(built):
    rel = reference.relative_l2(built["prefill"], built["ref"][-1])
    tol = reference.tolerance(built["config"], built["kv"])
    assert rel <= tol, (rel, tol)


def test_training_forward_agrees_at_every_position(built):
    rel = reference.relative_l2(built["train"], built["ref"])
    assert rel <= reference.tolerance(built["config"], "bf16")
    a = reference.next_token_loss(built["train"], built["ids"])
    b = reference.next_token_loss(built["ref"], built["ids"])
    assert a == pytest.approx(b, rel=0.01)


@pytest.mark.parametrize("fault", ["no_bias", "int4_as_offset_7"])
def test_the_tolerance_catches_a_wrong_model(built, fault):
    """The bound is tight enough that another architecture, or another
    reading of the quantized codes, falls outside it."""
    import copy

    arch = copy.deepcopy(built["config"]["reference"])
    params = built["canonical"]
    quant = dict(built["quant"])
    if fault == "no_bias":
        if not arch["qkv_bias"]:
            pytest.skip("this configuration has no bias to leave out")
        layers = {k: v for k, v in params["layers"].items()
                  if not k.endswith("_bias")}
        params = dict(params, layers=layers)
    elif fault == "int4_as_offset_7":
        wrong = reference.unpack_sym_int4

        def off7(data, scale, block):
            import jax.numpy as jnp

            return wrong(data, scale, block) + scale.astype(
                jnp.float32).repeat(block, axis=0)

        reference.unpack_sym_int4 = off7
    try:
        bad = np.asarray(reference.last_logits(
            params, arch, quant, built["ids"].tolist()))
    finally:
        if fault == "int4_as_offset_7":
            reference.unpack_sym_int4 = wrong
    rel = reference.relative_l2(built["prefill"], bad)
    tol = reference.tolerance(built["config"], built["kv"])
    assert rel > tol, (fault, rel, tol)


@pytest.mark.parametrize("rotary_dim,interleaved", [
    (16, False), (8, False), (16, True), (8, True)])
def test_rope_of_the_reference_is_the_programs_in_both_styles(
        rotary_dim, interleaved):
    """At toy widths random attention is nearly uniform and the logits
    hardly see the rotation, so the two rotations are compared
    directly: full and partial, half-rotation and interleaved."""
    import jax.numpy as jnp

    from bigdl_tpu.ops.rope import apply_rope, rope_cos_sin, rope_freqs

    x = np.random.default_rng(rotary_dim + interleaved).normal(
        size=(12, 4, 16)).astype(np.float32)
    pos = jnp.arange(12)
    mine = np.asarray(reference._rope(jnp.asarray(x), pos, 10000.0,
                                      rotary_dim, interleaved))
    cos, sin = rope_cos_sin(pos[None, :], rope_freqs(
        16, 10000.0, rotary_dim=rotary_dim))
    theirs = np.asarray(apply_rope(jnp.asarray(x)[None], cos, sin,
                                   interleaved=interleaved))[0]
    assert np.allclose(mine, theirs, atol=1e-5)
    assert not np.allclose(mine, x, atol=1e-2)


def test_unpack_sym_int4_against_a_hand_made_block():
    import jax.numpy as jnp

    # one block of 32 rows, one column: rows 0..15 in the low nibbles,
    # rows 16..31 in the high nibbles of the same 16 bytes
    codes = np.arange(32) % 16
    packed = (codes[:16] | (codes[16:] << 4)).astype(np.uint8)[:, None]
    w = np.asarray(reference.unpack_sym_int4(
        jnp.asarray(packed), jnp.asarray([[0.5]], jnp.bfloat16), 32))
    assert w[:, 0].tolist() == [(c - 8) * 0.5 for c in codes]
