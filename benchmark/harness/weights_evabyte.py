"""Seeded random weights of an EvaByte configuration, made on the device
in the type they are served in, one jitted call A LAYER (the layers are
equal, so one program makes all 32: a single call that unrolls them
compiled for 196 s cold, my chip run, PR 39) and one for the rest.

The canonical tree of ``bigdl_tpu/models/evabyte.py`` (its module
docstring has the layout): one dict a layer with q / k / v and gate / up
apart. Each linear is drawn N(0, ``init_std``) (the published 0.01275)
and quantized by the program's own ``quantize``; the norms' gains ``g``
(the unit offset is the model's) are drawn N(0, ``init_std``) too, so
that the offset shows; ``adaptive_phi`` and ``adaptive_mu_k`` are N(0,
1) clipped to [-1, 1] times ``head_dim ** -0.5``, float32, so that they
change the summaries' weights and keys. ``build_model`` then lets the
program prepare the tree it serves (``prepare_params``: the merged
projections), as its checkpoint conversion does. ``canonical_params``
runs the layer check (``checks_evabyte``) on the tree it hands the
reference.
"""

from __future__ import annotations

from typing import Any, Dict

from harness.weights import _family_config

INIT_STD = 0.01275


def _layer_shapes(cfg):
    d, f = cfg.hidden_size, cfg.intermediate_size
    hh = cfg.num_attention_heads * cfg.hd
    return {"q_proj": (d, hh), "k_proj": (d, hh), "v_proj": (d, hh),
            "o_proj": (hh, d), "gate_proj": (d, f), "up_proj": (d, f),
            "down_proj": (f, d)}


def build_params(cfg, qtype: str, seed: int, compute_dtype=None
                 ) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.quant import quantize

    compute_dtype = compute_dtype or jnp.bfloat16
    d, v = cfg.hidden_size, cfg.vocab_size
    h, hd = cfg.num_attention_heads, cfg.hd

    def linear(key, kdim, ndim):
        w = jax.random.normal(key, (kdim, ndim), jnp.float32) * INIT_STD
        return quantize(w, qtype)

    def gain(key):
        return (jax.random.normal(key, (d,), jnp.float32)
                * INIT_STD).astype(compute_dtype)

    def learned(key):
        return jnp.clip(jax.random.normal(key, (h, hd), jnp.float32),
                        -1.0, 1.0) * hd ** -0.5

    def layer(key):
        out = {}
        for j, (name, (kd, nd)) in enumerate(sorted(
                _layer_shapes(cfg).items())):
            out[name] = linear(jax.random.fold_in(key, j), kd, nd)
        out["input_layernorm"] = gain(jax.random.fold_in(key, 100))
        out["post_attention_layernorm"] = gain(jax.random.fold_in(key, 101))
        out["adaptive_phi"] = learned(jax.random.fold_in(key, 102))
        out["adaptive_mu_k"] = learned(jax.random.fold_in(key, 103))
        return out

    def rest(key):
        keys = jax.random.split(key, 3)
        return {
            "embed_tokens": (jax.random.normal(
                keys[0], (v, d), jnp.float32) * INIT_STD
            ).astype(compute_dtype),
            "norm": gain(keys[2]),
            "lm_head": linear(keys[1], d, cfg.num_pred_heads * v),
        }

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    one_layer = jax.jit(layer)
    params = jax.jit(rest)(jax.random.fold_in(key, 1))
    params["layers"] = tuple(
        one_layer(jax.random.fold_in(jax.random.fold_in(key, 2), i))
        for i in range(cfg.num_hidden_layers))
    return params


def canonical_params(config: Dict[str, Any], seed: int, check: bool = True
                     ) -> Dict[str, Any]:
    """The canonical tree of ``seed`` alone, as the reference reads it.
    With ``check`` (the harness's call, once the window has closed) the
    program's blocks are first held to the reference's on that tree
    (``checks_evabyte``). What it found rides the tree under
    ``"layer_check"`` (``harness/__init__.py``); a tree on which a
    reading is over also comes back ``refused`` and
    ``reference_evabyte.all_logits`` vouches for nothing on it."""
    _, cfg, _ = _family_config(config)
    canonical = build_params(cfg, config["quant"], seed)
    if check:
        from harness import checks_evabyte as checks

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
    load path of a deployment with ``from_pretrained`` skipped. The
    projections are merged whatever ``merge`` says (the family serves no
    other layout). Returns the model and the seconds each stage took."""
    import time

    import jax

    from bigdl_tpu.models import evabyte
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
    params = evabyte.prepare_params(canonical, cfg)
    del canonical
    eng = config.get("engine", {})
    model = TpuCausalLM(params, cfg, family, hf, qtype=config["quant"],
                        max_seq=int(eng.get("max_seq", 2048)),
                        kv_cache_dtype=eng.get("kv_cache_dtype", "bf16"))
    jax.block_until_ready(model.params)
    lap("merge_prepack_s")
    return model, stages
