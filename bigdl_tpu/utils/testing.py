"""Synthetic model builders (benchmarks, compile checks, unit tests).

Weights are generated *on device* with JAX PRNG and quantized tensor by
tensor, so building a 7B-parameter INT4 model for latency benchmarking
never materializes the float model on host (the benchmark analog of the
reference's low_cpu_mem_usage loading).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.models.llama import LlamaConfig
from bigdl_tpu.ops.quant import FLOAT_QTYPES, quantize


TINY_LLAMA = LlamaConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_hidden_layers=2,
    num_attention_heads=8,
    num_key_value_heads=4,
    max_position_embeddings=256,
)

LLAMA2_7B = LlamaConfig()  # defaults are llama2-7b

MISTRAL_7B = LlamaConfig(
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=14336,
    num_hidden_layers=32,
    num_attention_heads=32,
    num_key_value_heads=8,
    rope_theta=10000.0,
    max_position_embeddings=8192,
)


def random_llama_params(
    cfg: LlamaConfig,
    qtype: Optional[str] = "sym_int4",
    seed: int = 0,
    compute_dtype=jnp.bfloat16,
) -> Dict[str, Any]:
    """Random llama-family parameter pytree, quantized linears, on device."""
    key = jax.random.PRNGKey(seed)
    do_quant = qtype is not None and qtype not in FLOAT_QTYPES
    d, ff, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd

    def nxt():
        nonlocal key
        key, sub = jax.random.split(key)
        return sub

    def randw(k, kdim, ndim):
        # contraction-major [K, N] directly; ~N(0, 0.02)
        return jax.random.normal(k, (kdim, ndim), jnp.float32) * 0.02

    def make_linear(kdim, ndim):
        w = randw(nxt(), kdim, ndim)
        if do_quant:
            return quantize(w, qtype)
        return w.astype(compute_dtype)

    def stack(makers):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *makers)

    layers: Dict[str, Any] = {}
    per = {
        "q_proj": (d, h * hd),
        "k_proj": (d, hkv * hd),
        "v_proj": (d, hkv * hd),
        "o_proj": (h * hd, d),
        "gate_proj": (d, ff),
        "up_proj": (d, ff),
        "down_proj": (ff, d),
    }
    for name, (kdim, ndim) in per.items():
        layers[name] = stack(
            [make_linear(kdim, ndim) for _ in range(cfg.num_hidden_layers)])
    ones = jnp.ones((cfg.num_hidden_layers, d), compute_dtype)
    layers["input_layernorm"] = ones
    layers["post_attention_layernorm"] = ones

    params: Dict[str, Any] = {
        "embed_tokens": (jax.random.normal(nxt(), (v, d), jnp.float32)
                         * 0.02).astype(compute_dtype),
        "layers": layers,
        "norm": jnp.ones((d,), compute_dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = make_linear(d, v)
    return params


class SyntheticCausalLM:
    """Duck-typed stand-in for TpuCausalLM — ``.params`` / ``.config``
    / ``.family`` / ``.hf_config`` is all ``LLMEngine`` needs. Weights
    come from ``random_llama_params`` with an explicit seed, so two
    PROCESSES built with the same seed hold byte-identical weights:
    the serving router's replica-replay guarantees (a replayed greedy
    request must reproduce the dead replica's answer exactly) are
    testable without shipping a checkpoint into every subprocess."""

    def __init__(self, params, cfg):
        from bigdl_tpu.models import llama as llama_mod

        self.params = params
        self.config = cfg
        self.hf_config = {"eos_token_id": None}

        class _Family:
            name = "llama-synthetic"
            forward = staticmethod(llama_mod.forward)
            prefill = staticmethod(llama_mod.forward_last_token)
            new_cache = staticmethod(llama_mod.new_cache)
            forward_paged = staticmethod(llama_mod.forward_paged)
            new_paged_cache = staticmethod(llama_mod.new_paged_cache)
            SUPPORTS_SCALED_KV = llama_mod.SUPPORTS_SCALED_KV
            SUPPORTS_PAGED_KV = llama_mod.SUPPORTS_PAGED_KV

        self.family = _Family()


def tiny_random_model(seed: int = 0, qtype: Optional[str] = "sym_int4",
                      cfg=None) -> SyntheticCausalLM:
    """A tiny random llama ready for ``LLMEngine`` / ``OpenAIServer``
    (the ``api_server --tiny-random`` replica mode and router tests)."""
    cfg = cfg or TINY_LLAMA
    return SyntheticCausalLM(
        random_llama_params(cfg, qtype=qtype, seed=seed), cfg)


def random_mixtral_params(
    cfg,
    qtype: Optional[str] = "sym_int4",
    seed: int = 0,
    compute_dtype=jnp.bfloat16,
) -> Dict[str, Any]:
    """Random mixtral parameter pytree: llama attention + stacked experts."""
    from bigdl_tpu.ops.quant import quantize

    key = jax.random.PRNGKey(seed)
    do_quant = qtype is not None and qtype not in FLOAT_QTYPES
    d, ff, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.hd
    L, E = cfg.num_hidden_layers, cfg.num_local_experts

    def nxt():
        nonlocal key
        key, sub = jax.random.split(key)
        return sub

    def make_linear(kdim, ndim):
        w = jax.random.normal(nxt(), (kdim, ndim), jnp.float32) * 0.02
        if do_quant:
            return quantize(w, qtype)
        return w.astype(compute_dtype)

    def stack(makers):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *makers)

    layers: Dict[str, Any] = {}
    for name, (kdim, ndim) in {
        "q_proj": (d, h * hd), "k_proj": (d, hkv * hd),
        "v_proj": (d, hkv * hd), "o_proj": (h * hd, d),
    }.items():
        layers[name] = stack([make_linear(kdim, ndim) for _ in range(L)])
    for name, (kdim, ndim) in {
        "experts_gate": (d, ff), "experts_up": (d, ff),
        "experts_down": (ff, d),
    }.items():
        layers[name] = stack(
            [stack([make_linear(kdim, ndim) for _ in range(E)])
             for _ in range(L)])
    layers["router"] = (jax.random.normal(nxt(), (L, d, E), jnp.float32)
                        * 0.02).astype(compute_dtype)
    ones = jnp.ones((L, d), compute_dtype)
    layers["input_layernorm"] = ones
    layers["post_attention_layernorm"] = ones

    params: Dict[str, Any] = {
        "embed_tokens": (jax.random.normal(nxt(), (v, d), jnp.float32)
                         * 0.02).astype(compute_dtype),
        "layers": layers,
        "norm": jnp.ones((d,), compute_dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = make_linear(d, v)
    return params
