"""Seeded random weights of a DeepSeek-V3.2 configuration, made on the
device in ONE jitted call and in the type they are served in.

The canonical tree of ``bigdl_tpu/models/deepseek_v32.py`` (its module
docstring has the layout): one dict a layer, the routed experts of the
expert layers AND of the MTP block one stack of the experts HELD here
(the configuration's share; the MTP block's is the last), ``kv_b_proj``
still one quantized linear, the MTP module under ``"mtp"`` (``enorm``,
``hnorm``, ``eh_proj``, ``shared_head_norm`` and one expert layer's
``block``). Each linear is drawn N(0, 0.02) and quantized by the
program's own ``quantize``; the router, its correction bias and the
norms stay unquantized. The bias is drawn N(0, 0.004): wide enough that
it changes choices (the scores next to the cut lie about 0.007 apart),
narrow enough that the experts stay about evenly loaded, which is what
a trained bias is there for. At 0.02 one expert's load differs by a
third from the next and a group's by 6 % a layer, so the experts a step
hits here, and with them the step's time, follow the seed (PERF.md 6,
PR 43).
``build_model`` then lets the program prepare the tree it serves
(``prepare_params``), as its checkpoint conversion does.
``canonical_params`` runs the layer check (``checks_deepseek_v32``) on
the tree it hands the reference.
"""

from __future__ import annotations

from typing import Any, Dict

from harness.weights import _family_config

WEIGHT_STD = 0.02
ROUTER_BIAS_STD = 0.004


def _layer_shapes(cfg, dense: bool):
    """``(linears {name: (K, N)}, vectors {name: (n, fill)})`` of one
    layer body."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    c, r = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    lin = {"q_a_proj": (d, cfg.q_lora_rank),
           "q_b_proj": (cfg.q_lora_rank, h * (cfg.qk_nope_head_dim + r)),
           "kv_a_proj": (d, c + r),
           "kv_b_proj": (c, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
           "o_proj": (h * cfg.v_head_dim, d),
           "index_q_proj": (cfg.q_lora_rank, hi * di),
           "index_k_proj": (d, di), "index_w_proj": (d, hi)}
    vec = {"input_layernorm": (d, 1.0), "post_attention_layernorm": (d, 1.0),
           "q_a_layernorm": (cfg.q_lora_rank, 1.0),
           "kv_a_layernorm": (c, 1.0),
           "index_k_norm": (di, 1.0), "index_k_norm_bias": (di, 0.0)}
    if dense:
        ff = cfg.intermediate_size
        lin.update(gate_proj=(d, ff), up_proj=(d, ff), down_proj=(ff, d))
    else:
        fs = cfg.moe_intermediate_size * cfg.n_shared_experts
        lin.update(shared_gate=(d, fs), shared_up=(d, fs),
                   shared_down=(fs, d))
    return lin, vec


def build_params(cfg, qtype: str, seed: int, compute_dtype=None
                 ) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.ops.quant import quantize

    compute_dtype = compute_dtype or jnp.bfloat16
    d, v, f = cfg.hidden_size, cfg.vocab_size, cfg.moe_intermediate_size
    n_mtp = cfg.num_nextn_predict_layers
    n_stacks = cfg.num_hidden_layers - cfg.n_dense + n_mtp
    share = cfg.share

    def linear(key, kdim, ndim):
        w = jax.random.normal(key, (kdim, ndim), jnp.float32) * WEIGHT_STD
        return quantize(w, qtype)

    def layer(key, dense: bool):
        lin, vec = _layer_shapes(cfg, dense)
        out = {name: jnp.full((n,), fill, compute_dtype)
               for name, (n, fill) in vec.items()}
        for j, (name, (kd, nd)) in enumerate(sorted(lin.items())):
            out[name] = linear(jax.random.fold_in(key, j), kd, nd)
        if not dense:
            kr, kb = jax.random.split(jax.random.fold_in(key, 1000))
            # every chip routes over ALL the experts
            out["router"] = (jax.random.normal(
                kr, (d, share.experts_total), jnp.float32)
                * WEIGHT_STD).astype(compute_dtype)
            out["router_bias"] = (jax.random.normal(
                kb, (share.experts_total,), jnp.float32)
                * ROUTER_BIAS_STD).astype(compute_dtype)
        return out

    def expert_stack(key, kd, nd):
        return lax.map(lambda k: lax.map(
            lambda kk: linear(kk, kd, nd), jax.random.split(k, share.held)),
            jax.random.split(key, n_stacks))

    def build(key):
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed_tokens": (jax.random.normal(
                keys[0], (v, d), jnp.float32) * WEIGHT_STD
            ).astype(compute_dtype),
            "norm": jnp.ones((d,), compute_dtype),
            "layers": tuple(layer(jax.random.fold_in(keys[2], i),
                                  i < cfg.n_dense)
                            for i in range(cfg.num_hidden_layers)),
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = linear(keys[1], d, v)
        if n_stacks:
            params["experts"] = {
                "experts_gate": expert_stack(keys[3], d, f),
                "experts_up": expert_stack(keys[4], d, f),
                "experts_down": expert_stack(keys[5], f, d)}
        if n_mtp:
            ones = jnp.ones((d,), compute_dtype)
            params["mtp"] = {
                "enorm": ones, "hnorm": ones, "shared_head_norm": ones,
                "eh_proj": linear(keys[6], 2 * d, d),
                "block": layer(keys[7], False)}
        return params

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(build)(key)


def canonical_params(config: Dict[str, Any], seed: int, check: bool = True
                     ) -> Dict[str, Any]:
    """The canonical tree of ``seed`` alone, as the reference reads it.
    With ``check`` (the harness's call, once the window has closed) the
    program's blocks are first held to the reference's on that tree
    (``checks_deepseek_v32``). What it found rides the tree under
    ``"layer_check"`` (``harness/__init__.py``); a tree on which a
    reading is over a limit also comes back ``refused`` and
    ``reference_deepseek_v32.all_logits`` vouches for nothing on it."""
    _, cfg, _ = _family_config(config)
    canonical = build_params(cfg, config["quant"], seed)
    if check:
        from harness import checks_deepseek_v32 as checks

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
    has nothing to merge here. Returns the model and the seconds each
    stage took."""
    import time

    import jax

    from bigdl_tpu.models import deepseek_v32
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
    params = deepseek_v32.prepare_params(canonical, cfg)
    del canonical
    eng = config.get("engine", {})
    model = TpuCausalLM(params, cfg, family, hf, qtype=config["quant"],
                        max_seq=int(eng.get("max_seq", 2048)),
                        kv_cache_dtype=eng.get("kv_cache_dtype", "bf16"))
    jax.block_until_ready(model.params)
    lap("merge_prepack_s")
    return model, stages
