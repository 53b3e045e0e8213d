"""Seeded random weights of a DeepSeek-V2 configuration, made on the
device in ONE jitted call and in the type they are served in.

The canonical tree of ``bigdl_tpu/models/deepseek_v2.py`` (its module
docstring has the layout): the leading dense layers and the expert
layers are two stacks, the routed experts of a layer a stack of the
experts HELD here (the configuration's share), ``kv_b_proj`` still one
quantized linear. Each linear is drawn N(0, 0.02) one layer (and one
expert) at a time and quantized by the program's own ``quantize``; the
router and the norms stay unquantized. 0.02 is the published ``initializer_range``, for every
weight. ``build_model`` then lets the program prepare the tree it serves
(``prepare_params``: the absorbed form of ``kv_b_proj``), as its
checkpoint conversion does. ``canonical_params`` runs the layer check
(``checks_deepseek_v2``) on the tree it hands the reference.
"""

from __future__ import annotations

from typing import Any, Dict

from harness.weights import _family_config

WEIGHT_STD = 0.02


def _shapes(cfg):
    d, h = cfg.hidden_size, cfg.num_attention_heads
    c, r = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    attn = {"kv_a_proj": (d, c + r), "kv_b_proj": (c, h * (nope + vd)),
            "o_proj": (h * vd, d)}
    if cfg.q_lora_rank is None:
        attn["q_proj"] = (d, h * (nope + r))
    else:
        attn["q_a_proj"] = (d, cfg.q_lora_rank)
        attn["q_b_proj"] = (cfg.q_lora_rank, h * (nope + r))
    ff, f = cfg.intermediate_size, cfg.moe_intermediate_size
    dense = {"gate_proj": (d, ff), "up_proj": (d, ff), "down_proj": (ff, d)}
    fs = f * cfg.n_shared_experts
    shared = {"shared_gate": (d, fs), "shared_up": (d, fs),
              "shared_down": (fs, d)}
    experts = {"experts_gate": (d, f), "experts_up": (d, f),
               "experts_down": (f, d)}
    return attn, dense, shared, experts


def build_params(cfg, qtype: str, seed: int, compute_dtype=None
                 ) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.ops.quant import quantize

    compute_dtype = compute_dtype or jnp.bfloat16
    d, v = cfg.hidden_size, cfg.vocab_size
    attn, dense, shared, experts = _shapes(cfg)
    n_dense = cfg.n_dense
    n_moe = cfg.num_hidden_layers - n_dense
    share = cfg.share

    def linear(key, kdim, ndim):
        w = jax.random.normal(key, (kdim, ndim), jnp.float32) * WEIGHT_STD
        return quantize(w, qtype)

    def stack(key, shapes, n_layers, per_layer=1):
        out = {}
        for i, (name, (kd, nd)) in enumerate(sorted(shapes.items())):
            lkeys = jax.random.split(jax.random.fold_in(key, i), n_layers)
            if per_layer == 1:
                out[name] = lax.map(
                    lambda k, kd=kd, nd=nd: linear(k, kd, nd), lkeys)
            else:
                out[name] = lax.map(
                    lambda k, kd=kd, nd=nd: lax.map(
                        lambda kk: linear(kk, kd, nd),
                        jax.random.split(k, per_layer)), lkeys)
        return out

    def norms(n_layers):
        out = {"input_layernorm": jnp.ones((n_layers, d), compute_dtype),
               "post_attention_layernorm": jnp.ones((n_layers, d),
                                                    compute_dtype),
               "kv_a_layernorm": jnp.ones((n_layers, cfg.kv_lora_rank),
                                          compute_dtype)}
        if cfg.q_lora_rank is not None:
            out["q_a_layernorm"] = jnp.ones((n_layers, cfg.q_lora_rank),
                                            compute_dtype)
        return out

    def build(key):
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed_tokens": (jax.random.normal(
                keys[0], (v, d), jnp.float32) * WEIGHT_STD
            ).astype(compute_dtype),
            "norm": jnp.ones((d,), compute_dtype),
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = linear(keys[1], d, v)
        if n_dense:
            params["dense_layers"] = {
                **stack(keys[2], {**attn, **dense}, n_dense),
                **norms(n_dense)}
        if n_moe:
            params["moe_layers"] = {
                **stack(keys[3], {**attn, **shared}, n_moe),
                **stack(keys[4], experts, n_moe, per_layer=share.held),
                **norms(n_moe),
                # every chip routes over ALL the experts
                "router": (jax.random.normal(
                    keys[5], (n_moe, d, share.experts_total), jnp.float32)
                    * WEIGHT_STD).astype(compute_dtype)}
        return params

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(build)(key)


def canonical_params(config: Dict[str, Any], seed: int, check: bool = True
                     ) -> Dict[str, Any]:
    """The canonical tree of ``seed`` alone, as the reference reads it.
    With ``check`` (the harness's call, once the window has closed) the
    program's blocks are first held to the reference's on that tree,
    layer by layer (``checks_deepseek_v2``), every reading printed
    beside its limit; a tree on which one is over comes back ``refused``
    and ``reference_deepseek_v2.all_logits`` vouches for nothing on
    it."""
    _, cfg, _ = _family_config(config)
    canonical = build_params(cfg, config["quant"], seed)
    if check:
        from harness import checks_deepseek_v2 as checks

        found = checks.layer_check(config, canonical, seed)
        # rides the tree: ``harness/__init__.py``, "layer_check"
        canonical["layer_check"] = {"seconds": found["seconds"],
                                    "within": found["within"],
                                    "compared": checks.report(found)}
        canonical["refused"] = not found["within"]
    return canonical


def build_model(config: Dict[str, Any], seed: int, merge: bool,
                with_canonical=None):
    """Configuration file -> registry family -> config -> seeded params
    -> the program's ``prepare_params`` -> ``TpuCausalLM`` (prepack):
    the load path of a deployment with ``from_pretrained`` skipped.
    ``merge`` has nothing to merge here. Returns the model and the
    seconds each stage took."""
    import time

    import jax

    from bigdl_tpu.models import deepseek_v2
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
    params = deepseek_v2.prepare_params(canonical, cfg)
    del canonical
    eng = config.get("engine", {})
    model = TpuCausalLM(params, cfg, family, hf, qtype=config["quant"],
                        max_seq=int(eng.get("max_seq", 2048)),
                        kv_cache_dtype=eng.get("kv_cache_dtype", "bf16"))
    jax.block_until_ready(model.params)
    lap("merge_prepack_s")
    return model, stages
