"""Seeded random weights of an SDAR-MoE configuration, made on the
device in ONE jitted call and in the type they are served in.

The canonical tree of ``bigdl_tpu/models/sdar_moe.py`` (its module
docstring has the layout): every layer's leaves as ONE stack over all
the layers (one kind of layer), q / k / v apart, the router, and the
routed experts HELD here (the configuration's share). Each linear is
drawn N(0, 0.02) (the published config has no ``initializer_range``)
and quantized by the program's own ``quantize``; the router and the
norms stay unquantized. What trained weights would bring and a constant
would hide is SEEDED, as ``weights_afmoe`` does and for its reasons: the
per-head norms' weights ``q_norm`` / ``k_norm`` N(1, 0.25) (at 1 a
program that mixed the two up, or applied one after the rotary, would
read the same), the layer norms' N(1, 0.1).

``build_model`` then lets the program prepare the tree it serves
(``prepare_params``), as its checkpoint conversion does.
``canonical_params`` runs the layer check (``checks_sdar_moe``) on the
tree it hands the reference.
"""

from __future__ import annotations

from typing import Any, Dict

from harness.weights import _family_config

WEIGHT_STD = 0.02
QK_NORM_STD = 0.25
NORM_STD = 0.1


def build_params(cfg, qtype: str, seed: int, compute_dtype=None
                 ) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.ops.quant import quantize

    compute_dtype = compute_dtype or jnp.bfloat16
    d, v, n = cfg.hidden_size, cfg.vocab_size, cfg.num_hidden_layers
    kind, share, fe = cfg.full, cfg.share, cfg.moe_intermediate_size

    def linear(key, kdim, ndim):
        w = jax.random.normal(key, (kdim, ndim), jnp.float32) * WEIGHT_STD
        return quantize(w, qtype)

    def stack(key, layers, kdim, ndim):
        return lax.map(lambda k: linear(k, kdim, ndim),
                       jax.random.split(key, layers))

    def around_one(key, shape, std):
        return (1.0 + std * jax.random.normal(key, shape, jnp.float32)
                ).astype(compute_dtype)

    def expert_stack(key, kd, nd):
        return lax.map(lambda k: stack(k, share.held, kd, nd),
                       jax.random.split(key, n))

    def build(key):
        keys = jax.random.split(key, 16)
        layers = {
            "q_proj": stack(keys[0], n, d, kind.q_width),
            "k_proj": stack(keys[1], n, d, kind.k_width),
            "v_proj": stack(keys[2], n, d, kind.v_width),
            "o_proj": stack(keys[3], n, kind.q_width, d),
            "q_norm": around_one(keys[4], (n, cfg.head_dim), QK_NORM_STD),
            "k_norm": around_one(keys[5], (n, cfg.head_dim), QK_NORM_STD),
            "input_layernorm": around_one(keys[6], (n, d), NORM_STD),
            "post_attention_layernorm": around_one(keys[7], (n, d),
                                                   NORM_STD),
            # every chip routes over ALL the experts
            "router": (jax.random.normal(
                keys[8], (n, d, share.experts_total), jnp.float32)
                * WEIGHT_STD).astype(compute_dtype)}
        params: Dict[str, Any] = {
            "embed_tokens": (jax.random.normal(
                keys[9], (v, d), jnp.float32) * WEIGHT_STD
            ).astype(compute_dtype),
            "norm": around_one(keys[10], (d,), NORM_STD),
            "layers": layers,
            "experts": {
                "experts_gate": expert_stack(keys[11], d, fe),
                "experts_up": expert_stack(keys[12], d, fe),
                "experts_down": expert_stack(keys[13], fe, d)}}
        if not cfg.tie_word_embeddings:
            params["lm_head"] = linear(keys[14], d, v)
        return params

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(build)(key)


def canonical_params(config: Dict[str, Any], seed: int, check: bool = True
                     ) -> Dict[str, Any]:
    """The canonical tree of ``seed`` alone, as the reference reads it.
    With ``check`` (the harness's call, once the window has closed) the
    program's blocks are first held to the reference's on that tree, the
    one kind of layer once (``checks_sdar_moe``). What it found rides
    the tree under ``"layer_check"`` (``harness/__init__.py``); a tree
    on which a reading is over its limit also comes back ``refused`` and
    ``reference_sdar_moe`` vouches for nothing on it."""
    _, cfg, _ = _family_config(config)
    canonical = build_params(cfg, config["quant"], seed)
    if check:
        from harness import checks_sdar_moe as checks

        found = checks.layer_check(config, canonical, seed)
        canonical["layer_check"] = {"seconds": found["seconds"],
                                    "within": found["within"],
                                    "compared": checks.report(found)}
        canonical["refused"] = not found["within"]
    return canonical


def build_model(config: Dict[str, Any], seed: int, merge: bool,
                with_canonical=None):
    """Configuration file -> registry family -> config -> seeded params
    -> the program's ``prepare_params`` -> ``TpuCausalLM`` (prepack): the
    load path of a deployment with ``from_pretrained`` skipped. ``merge``
    has nothing more to merge here (q / k / v are served merged
    always). Returns the model and the seconds each stage took."""
    import time

    import jax

    from bigdl_tpu.models import sdar_moe
    from bigdl_tpu.transformers.model import TpuCausalLM

    del merge
    family, cfg, hf = _family_config(config)
    stages: Dict[str, float] = {}
    clock = {"t": time.monotonic()}

    def lap(name):
        now = time.monotonic()
        stages[name] = now - clock["t"]
        clock["t"] = now

    canonical = jax.block_until_ready(
        build_params(cfg, config["quant"], seed))
    lap("weights_s")
    if with_canonical is not None:
        with_canonical(canonical, cfg)
        lap("with_canonical_s")
    params = sdar_moe.prepare_params(canonical, cfg)
    del canonical
    eng = config.get("engine", {})
    model = TpuCausalLM(params, cfg, family, hf, qtype=config["quant"],
                        max_seq=int(eng.get("max_seq", 2048)),
                        kv_cache_dtype=eng.get("kv_cache_dtype", "bf16"))
    jax.block_until_ready(model.params)
    lap("merge_prepack_s")
    return model, stages
