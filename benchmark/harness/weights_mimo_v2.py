"""Seeded random weights of a MiMo-V2 configuration, made on the device
in ONE jitted call and in the type they are served in.

The canonical tree of ``bigdl_tpu/models/mimo_v2.py`` (its module
docstring has the layout): one dict a layer (the two kinds of attention
layer differ in their KV heads), q / k / v apart, the routed experts of
the expert layers one stack of the experts HELD here (the
configuration's share). Each linear is drawn N(0, 0.02) and quantized by
the program's own ``quantize``; the router, the norms and the two
learned vectors the published weights would bring stay unquantized, and
both are SEEDED so that the mechanism they feed can be observed:

- the router's correction bias ``e_bias`` N(0, 0.02), as
  ``weights_dots3_note`` draws it: beside sigmoid scores whose eighth
  and ninth lie a few thousandths apart it changes choices (left out of
  the choice the routed block reads 0.08-0.10 against a limit of 0.015);
- a window layer's sink ``b_n`` N(ln(window), 1) a query head: a row's
  keys sum to about ``window`` at toy widths and to about four times
  that at the published ones (scores of deviation 1.6), so the sink
  takes between a twentieth and a half of a row's weight, and a program
  that drops it is 5-50 % off on every row (zeros, or a draw around 0,
  would hide among 128 keys).

``build_model`` then lets the program prepare the tree it serves
(``prepare_params``), as its checkpoint conversion does.
``canonical_params`` runs the layer check (``checks_mimo_v2``) on the
tree it hands the reference.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from harness.weights import _family_config

WEIGHT_STD = 0.02
SINK_STD = 1.0


def _layer_shapes(cfg, i: int):
    """``{name: (K, N)}`` of the linears of layer ``i``."""
    d, k = cfg.hidden_size, cfg.kind(i)
    lin = {"q_proj": (d, k.q_width), "k_proj": (d, k.k_width),
           "v_proj": (d, k.v_width), "o_proj": (k.heads * k.v_head_dim, d)}
    if not cfg.routed(i):
        ff = cfg.intermediate_size
        lin.update(gate_proj=(d, ff), up_proj=(d, ff), down_proj=(ff, d))
    return lin


def build_params(cfg, qtype: str, seed: int, compute_dtype=None
                 ) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.ops.quant import quantize

    compute_dtype = compute_dtype or jnp.bfloat16
    d, v, f = cfg.hidden_size, cfg.vocab_size, cfg.moe_intermediate_size
    n_moe = cfg.n_routed_layers
    share = cfg.share

    def linear(key, kdim, ndim):
        w = jax.random.normal(key, (kdim, ndim), jnp.float32) * WEIGHT_STD
        return quantize(w, qtype)

    def layer(key, i):
        out = {name: jnp.ones((d,), compute_dtype)
               for name in ("input_layernorm", "post_attention_layernorm")}
        for j, (name, (kd, nd)) in enumerate(sorted(
                _layer_shapes(cfg, i).items())):
            out[name] = linear(jax.random.fold_in(key, j), kd, nd)
        kind = cfg.kind(i)
        if kind.sink:
            out["sink"] = (math.log(kind.window or 128.0) + SINK_STD
                           * jax.random.normal(jax.random.fold_in(key, 999),
                                               (kind.heads,), jnp.float32))
        if cfg.routed(i):
            kr, kb = jax.random.split(jax.random.fold_in(key, 1000))
            # every chip routes over ALL the experts
            out["router"] = (jax.random.normal(
                kr, (d, share.experts_total), jnp.float32)
                * WEIGHT_STD).astype(compute_dtype)
            out["router_bias"] = (jax.random.normal(
                kb, (share.experts_total,), jnp.float32)
                * WEIGHT_STD).astype(compute_dtype)
        return out

    def expert_stack(key, kd, nd):
        return lax.map(lambda k: lax.map(
            lambda kk: linear(kk, kd, nd), jax.random.split(k, share.held)),
            jax.random.split(key, n_moe))

    def build(key):
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed_tokens": (jax.random.normal(
                keys[0], (v, d), jnp.float32) * WEIGHT_STD
            ).astype(compute_dtype),
            "norm": jnp.ones((d,), compute_dtype),
            "layers": tuple(layer(jax.random.fold_in(keys[2], i), i)
                            for i in range(cfg.num_hidden_layers)),
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = linear(keys[1], d, v)
        if n_moe:
            params["experts"] = {
                "experts_gate": expert_stack(keys[3], d, f),
                "experts_up": expert_stack(keys[4], d, f),
                "experts_down": expert_stack(keys[5], f, d)}
        return params

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(build)(key)


def canonical_params(config: Dict[str, Any], seed: int, check: bool = True
                     ) -> Dict[str, Any]:
    """The canonical tree of ``seed`` alone, as the reference reads it.
    With ``check`` (the harness's call, once the window has closed) the
    program's blocks are first held to the reference's on that tree,
    one layer of each kind (``checks_mimo_v2``). What it found rides the
    tree under ``"layer_check"`` (``harness/__init__.py``: seconds,
    verdict, each reading beside its limit, which the runner prints and
    holds ``correct`` to); a tree on which one is over also comes back
    ``refused`` and ``reference_mimo_v2.all_logits`` vouches for nothing
    on it."""
    _, cfg, _ = _family_config(config)
    canonical = build_params(cfg, config["quant"], seed)
    if check:
        from harness import checks_mimo_v2 as checks

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
    has nothing more to merge here (q / k / v are served merged always).
    Returns the model and the seconds each stage took."""
    import time

    import jax

    from bigdl_tpu.models import mimo_v2
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
    params = mimo_v2.prepare_params(canonical, cfg)
    del canonical
    eng = config.get("engine", {})
    model = TpuCausalLM(params, cfg, family, hf, qtype=config["quant"],
                        max_seq=int(eng.get("max_seq", 2048)),
                        kv_cache_dtype=eng.get("kv_cache_dtype", "bf16"))
    jax.block_until_ready(model.params)
    lap("merge_prepack_s")
    return model, stages
