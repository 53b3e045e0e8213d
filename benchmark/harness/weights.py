"""Seeded random weights, made on the device in ONE jitted call and in
the type they are served in.

The tree has the layout ``models/llama.py`` documents (contraction-major
linears stacked over layers, ``<name>_bias`` planes ``[L, N]`` where the
configuration has ``attention_bias``). Each linear is drawn N(0, 0.02)
one layer at a time (``lax.map``, so only one layer's float32 weight is
ever live) and quantized by the program's own ``quantize``; the float32
detour per layer is the program's to remove. The program's
``utils.testing.random_llama_params`` makes the same tree leaf by leaf
from the host and without bias planes, which is why this copy exists.
"""

from __future__ import annotations

from typing import Any, Dict

BIAS_STD = 0.02


def build_params(cfg, qtype: str, seed: int, compute_dtype=None
                 ) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.ops.quant import quantize

    compute_dtype = compute_dtype or jnp.bfloat16
    d, ff, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd
    n_layers = cfg.num_hidden_layers
    per = {
        "q_proj": (d, h * hd), "k_proj": (d, hkv * hd),
        "v_proj": (d, hkv * hd), "o_proj": (h * hd, d),
        "gate_proj": (d, ff), "up_proj": (d, ff), "down_proj": (ff, d),
    }
    biased = [n for n in ("q_proj", "k_proj", "v_proj")
              if getattr(cfg, "attention_bias", False)]

    def linear(key, kdim, ndim):
        w = jax.random.normal(key, (kdim, ndim), jnp.float32) * 0.02
        return quantize(w, qtype)

    def build(key):
        keys = jax.random.split(key, len(per) + len(biased) + 2)
        layers: Dict[str, Any] = {}
        for i, (name, (kdim, ndim)) in enumerate(per.items()):
            lkeys = jax.random.split(keys[i], n_layers)
            layers[name] = lax.map(
                lambda k, kd=kdim, nd=ndim: linear(k, kd, nd), lkeys)
        for j, name in enumerate(biased):
            layers[f"{name}_bias"] = (jax.random.normal(
                keys[len(per) + j], (n_layers, per[name][1]), jnp.float32)
                * BIAS_STD).astype(compute_dtype)
        ones = jnp.ones((n_layers, d), compute_dtype)
        layers["input_layernorm"] = ones
        layers["post_attention_layernorm"] = ones
        params: Dict[str, Any] = {
            "embed_tokens": (jax.random.normal(
                keys[-2], (v, d), jnp.float32) * 0.02).astype(compute_dtype),
            "layers": layers,
            "norm": jnp.ones((d,), compute_dtype),
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = linear(keys[-1], d, v)
        return params

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(build)(key)


def _family_config(config: Dict[str, Any]):
    from bigdl_tpu.models.registry import get_family

    hf = config["hf_config"]
    family = get_family(hf["architectures"][0], hf)
    return family, family.config_from_hf(hf), hf


def canonical_params(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The canonical (split-projection) tree of ``seed`` alone, as the
    reference reads it: what ``build_model`` hands ``with_canonical``.
    The serving runner calls it once the window has closed and the
    program's state is freed."""
    _, cfg, _ = _family_config(config)
    return build_params(cfg, config["quant"], seed)


def build_model(config: Dict[str, Any], seed: int, merge: bool,
                with_canonical=None):
    """Configuration file -> registry family -> config -> seeded params
    (-> merged projections) -> ``TpuCausalLM`` (prepack): the load path
    of a deployment with ``from_pretrained`` skipped.
    ``with_canonical(params, cfg)`` is called on the canonical
    (split-projection, not yet prepacked) tree, which the reference
    reads; the tree is dropped afterwards so that only the served copy
    stays on the device. Returns the model and the seconds each stage
    took."""
    from bigdl_tpu.models import llama as llama_mod
    from bigdl_tpu.transformers.model import TpuCausalLM

    family, cfg, hf = _family_config(config)
    import time

    import jax

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
    params = (llama_mod.merge_projections(canonical, cfg) if merge
              else canonical)
    del canonical
    eng = config.get("engine", {})
    model = TpuCausalLM(params, cfg, family, hf, qtype=config["quant"],
                        max_seq=int(eng.get("max_seq", 2048)),
                        kv_cache_dtype=eng.get("kv_cache_dtype", "bf16"))
    jax.block_until_ready(model.params)
    lap("merge_prepack_s")
    return model, stages
