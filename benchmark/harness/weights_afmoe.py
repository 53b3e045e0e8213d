"""Seeded random weights of an AFMoE (Trinity) configuration, made on the
device in ONE jitted call and in the type they are served in.

The canonical tree of ``bigdl_tpu/models/afmoe.py`` (its module
docstring has the layout): the attention leaves of every layer as one
stack (both kinds of layer have the same shapes), q / k / v and the
gate apart; the dense layers' MLP, the expert layers' router, bias and
shared expert, and the routed experts HELD here (the configuration's
share), each a stack. Each linear is drawn N(0, 0.02) (the published
config has no ``initializer_range``) and quantized by the program's own
``quantize``; the router, the norms and the router's bias stay
unquantized. What trained weights would bring and a constant would hide
is SEEDED, so that the mechanism it feeds can be observed:

- the router's correction bias ``expert_bias`` N(0, 0.02), as
  ``weights_dots3_note`` draws it: beside sigmoid scores whose eighth and
  ninth lie a few thousandths apart it changes choices;
- the per-head norms' weights ``q_norm`` / ``k_norm`` N(1, 0.25): at
  weights of 1 the norm is a rescaling of every head alike, and a program
  that mixed the two weights up, or applied one after the rotary, would
  read the same;
- the four layer norms' weights N(1, 0.1), for the same reason (the
  norm on a BRANCH scales what joins the stream: at 1 everywhere a
  program that normed the stream instead would differ, but one that
  swapped the two of a pair would not).

``build_model`` then lets the program prepare the tree it serves
(``prepare_params``), as its checkpoint conversion does.
``canonical_params`` runs the layer check (``checks_afmoe``) on the
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
    d, v = cfg.hidden_size, cfg.vocab_size
    kind, share = cfg.full, cfg.share
    n, n_dense, n_moe = (cfg.num_hidden_layers, cfg.num_dense_layers,
                         cfg.n_routed_layers)

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
                       jax.random.split(key, n_moe))

    def build(key):
        keys = jax.random.split(key, 32)
        attn = {"q_proj": stack(keys[0], n, d, kind.q_width),
                "k_proj": stack(keys[1], n, d, kind.k_width),
                "v_proj": stack(keys[2], n, d, kind.v_width),
                "g_proj": stack(keys[3], n, d, kind.q_width),
                "o_proj": stack(keys[4], n, kind.q_width, d),
                "q_norm": around_one(keys[5], (n, cfg.head_dim),
                                     QK_NORM_STD),
                "k_norm": around_one(keys[6], (n, cfg.head_dim),
                                     QK_NORM_STD)}
        for j, name in enumerate(("input_layernorm",
                                  "post_attention_layernorm",
                                  "pre_mlp_layernorm",
                                  "post_mlp_layernorm")):
            attn[name] = around_one(keys[7 + j], (n, d), NORM_STD)
        params: Dict[str, Any] = {
            "embed_tokens": (jax.random.normal(
                keys[11], (v, d), jnp.float32) * WEIGHT_STD
            ).astype(compute_dtype),
            "norm": around_one(keys[12], (d,), NORM_STD),
            "attn": attn,
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = linear(keys[13], d, v)
        if n_dense:
            ff = cfg.intermediate_size
            params["dense"] = {
                "gate_proj": stack(keys[14], n_dense, d, ff),
                "up_proj": stack(keys[15], n_dense, d, ff),
                "down_proj": stack(keys[16], n_dense, ff, d)}
        if n_moe:
            fs, fe = cfg.shared_intermediate, cfg.moe_intermediate_size
            # every chip routes over ALL the experts
            params["moe"] = {
                "router": (jax.random.normal(
                    keys[17], (n_moe, d, share.experts_total), jnp.float32)
                    * WEIGHT_STD).astype(compute_dtype),
                "router_bias": (jax.random.normal(
                    keys[18], (n_moe, share.experts_total), jnp.float32)
                    * WEIGHT_STD).astype(compute_dtype),
                "shared_gate": stack(keys[19], n_moe, d, fs),
                "shared_up": stack(keys[20], n_moe, d, fs),
                "shared_down": stack(keys[21], n_moe, fs, d)}
            params["experts"] = {
                "experts_gate": expert_stack(keys[22], d, fe),
                "experts_up": expert_stack(keys[23], d, fe),
                "experts_down": expert_stack(keys[24], fe, d)}
        return params

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(build)(key)


def canonical_params(config: Dict[str, Any], seed: int, check: bool = True
                     ) -> Dict[str, Any]:
    """The canonical tree of ``seed`` alone, as the reference reads it.
    With ``check`` (the harness's call, once the window has closed) the
    program's blocks are first held to the reference's on that tree,
    one layer of each kind (``checks_afmoe``). What it found rides the
    tree under ``"layer_check"`` (``harness/__init__.py``: seconds,
    verdict, each reading beside its limit, which the runner prints and
    holds ``correct`` to); a tree on which one is over also comes back
    ``refused`` and ``reference_afmoe.all_logits`` vouches for nothing
    on it."""
    _, cfg, _ = _family_config(config)
    canonical = build_params(cfg, config["quant"], seed)
    if check:
        from harness import checks_afmoe as checks

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
    has nothing more to merge here (q / k / v and the gate are served
    merged always). Returns the model and the seconds each stage took."""
    import time

    import jax

    from bigdl_tpu.models import afmoe
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
    params = afmoe.prepare_params(canonical, cfg)
    del canonical
    eng = config.get("engine", {})
    model = TpuCausalLM(params, cfg, family, hf, qtype=config["quant"],
                        max_seq=int(eng.get("max_seq", 2048)),
                        kv_cache_dtype=eng.get("kv_cache_dtype", "bf16"))
    jax.block_until_ready(model.params)
    lap("merge_prepack_s")
    return model, stages
