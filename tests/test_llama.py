"""End-to-end llama tests: numerical equivalence vs HF transformers (torch).

This is the reference's strongest test pattern, ported: load the same
checkpoint through the float path and through our converted/quantized path
and compare layer outputs / logits within a bound (reference
test/inference_gpu/test_transformers_api_attention.py:45-100). Here the
float reference is HF torch itself on CPU over a tiny random llama.
"""

import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

TINY_CFG = dict(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_position_embeddings=128,
    rms_norm_eps=1e-5,
    tie_word_embeddings=False,
)


@pytest.fixture(scope="module")
def tiny_hf_model(tmp_path_factory):
    """Create a tiny random HF llama on disk (no network)."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFLlamaConfig, LlamaForCausalLM

    torch.manual_seed(0)
    hf_cfg = HFLlamaConfig(**TINY_CFG)
    model = LlamaForCausalLM(hf_cfg)
    model.eval()
    path = tmp_path_factory.mktemp("tiny_llama")
    model.save_pretrained(path)
    return str(path), model


def _load_ours(path, qtype):
    from bigdl_tpu.models.llama import LlamaConfig, convert_hf_params
    from bigdl_tpu.utils.hf import iter_hf_tensors, load_hf_config

    cfg = LlamaConfig.from_hf(load_hf_config(path))
    params = convert_hf_params(iter_hf_tensors(path), cfg, qtype=qtype,
                               compute_dtype=jnp.float32)
    return cfg, params


def test_float_logits_match_hf(tiny_hf_model):
    """Unquantized path must match HF torch logits closely."""
    torch = pytest.importorskip("torch")
    path, hf_model = tiny_hf_model
    from bigdl_tpu.models.llama import forward, new_cache

    cfg, params = _load_ours(path, qtype=None)

    ids = np.array([[1, 5, 9, 42, 7, 100, 3, 250]], np.int32)
    with torch.no_grad():
        ref = hf_model(torch.tensor(ids.astype(np.int64))).logits.numpy()

    cache = new_cache(cfg, 1, 32)
    logits, cache = forward(params, cfg, jnp.asarray(ids), cache,
                            compute_dtype=jnp.float32)
    got = np.asarray(logits)

    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
    assert int(cache.pos) == ids.shape[1]


def test_int4_logits_close_and_same_argmax(tiny_hf_model):
    torch = pytest.importorskip("torch")
    path, hf_model = tiny_hf_model
    from bigdl_tpu.models.llama import forward, new_cache

    cfg, params = _load_ours(path, qtype="sym_int4")
    ids = np.array([[1, 5, 9, 42, 7, 100, 3, 250]], np.int32)
    with torch.no_grad():
        ref = hf_model(torch.tensor(ids.astype(np.int64))).logits.numpy()

    cache = new_cache(cfg, 1, 32)
    logits, _ = forward(params, cfg, jnp.asarray(ids), cache,
                        compute_dtype=jnp.float32)
    got = np.asarray(logits)
    # int4 noise: logits close in aggregate
    rel = np.abs(got - ref).mean() / (np.abs(ref).mean() + 1e-9)
    assert rel < 0.35, rel


def test_decode_matches_prefill(tiny_hf_model):
    """Token-by-token decode must produce identical logits to one-shot
    prefill at every position (static cache correctness)."""
    path, _ = tiny_hf_model
    from bigdl_tpu.models.llama import forward, new_cache

    cfg, params = _load_ours(path, qtype=None)
    ids = np.array([[1, 17, 33, 99, 250, 8]], np.int32)

    cache = new_cache(cfg, 1, 16)
    all_logits, _ = forward(params, cfg, jnp.asarray(ids), cache,
                            compute_dtype=jnp.float32)
    all_logits = np.asarray(all_logits)

    cache = new_cache(cfg, 1, 16)
    step_logits = []
    for t in range(ids.shape[1]):
        lg, cache = forward(params, cfg, jnp.asarray(ids[:, t:t + 1]), cache,
                            compute_dtype=jnp.float32)
        step_logits.append(np.asarray(lg)[:, 0])
    step_logits = np.stack(step_logits, axis=1)

    np.testing.assert_allclose(step_logits, all_logits, rtol=1e-3, atol=1e-3)


def test_fp8_kv_cache_close(tiny_hf_model):
    path, _ = tiny_hf_model
    from bigdl_tpu.models.llama import forward, new_cache

    cfg, params = _load_ours(path, qtype=None)
    ids = np.array([[1, 17, 33, 99, 250, 8]], np.int32)

    exact, _ = forward(params, cfg, jnp.asarray(ids), new_cache(cfg, 1, 16),
                       compute_dtype=jnp.float32)
    fp8, _ = forward(params, cfg, jnp.asarray(ids),
                     new_cache(cfg, 1, 16, quantized=True),
                     compute_dtype=jnp.float32)
    exact, fp8 = np.asarray(exact), np.asarray(fp8)
    rel = np.abs(fp8 - exact).mean() / (np.abs(exact).mean() + 1e-9)
    assert rel < 0.3, rel


def test_generate_greedy_deterministic(tiny_hf_model):
    path, _ = tiny_hf_model
    from bigdl_tpu.generation import GenerationConfig, Generator

    cfg, params = _load_ours(path, qtype="sym_int4")
    g = Generator(params, cfg, max_seq=64)
    out1 = g.generate([1, 5, 9], GenerationConfig(max_new_tokens=8))
    out2 = g.generate([1, 5, 9], GenerationConfig(max_new_tokens=8))
    assert out1.shape == (1, 8)
    np.testing.assert_array_equal(out1, out2)
    assert (out1 >= 0).all() and (out1 < TINY_CFG["vocab_size"]).all()


def test_generate_matches_hf_greedy(tiny_hf_model):
    """Greedy continuation of the float path matches HF torch generate."""
    torch = pytest.importorskip("torch")
    path, hf_model = tiny_hf_model
    from bigdl_tpu.generation import GenerationConfig, Generator

    ids = [1, 5, 9, 42]
    with torch.no_grad():
        ref = hf_model.generate(
            torch.tensor([ids]), max_new_tokens=6, do_sample=False,
            num_beams=1)
    ref_new = ref[0, len(ids):].numpy()

    cfg, params = _load_ours(path, qtype=None)
    g = Generator(params, cfg, max_seq=64)
    out = g.generate(ids, GenerationConfig(max_new_tokens=6))
    np.testing.assert_array_equal(out[0], ref_new)


def test_generate_sampling_runs(tiny_hf_model):
    path, _ = tiny_hf_model
    from bigdl_tpu.generation import GenerationConfig, Generator

    cfg, params = _load_ours(path, qtype="sym_int4")
    g = Generator(params, cfg, max_seq=64)
    out = g.generate(
        [1, 5, 9],
        GenerationConfig(max_new_tokens=8, do_sample=True, temperature=0.8,
                         top_k=20, top_p=0.9, seed=7),
    )
    assert out.shape == (1, 8)


def test_generate_on_device_matches_host_loop(tiny_hf_model):
    """The fused on-device scan loop must emit the same greedy tokens as the
    per-token host loop."""
    path, _ = tiny_hf_model
    import jax
    from bigdl_tpu.generation import (GenerationConfig, Generator,
                                      generate_on_device)
    from bigdl_tpu.models.llama import forward, new_cache

    cfg, params = _load_ours(path, qtype=None)
    ids = np.array([[1, 5, 9, 42]], np.int32)

    g = Generator(params, cfg, max_seq=64)
    host_out = g.generate(ids, GenerationConfig(max_new_tokens=8))

    fwd = lambda p, c, t, kv: forward(p, c, t, kv, compute_dtype=jnp.float32)
    dev_out, _ = jax.jit(
        lambda p, t, kv: generate_on_device(p, cfg, fwd, t, kv, 8),
    )(params, jnp.asarray(ids), new_cache(cfg, 1, 64))
    np.testing.assert_array_equal(np.asarray(dev_out), host_out)


def test_rope_scaling_modes():
    """yarn/dynamic/llama3 configs load, run, and differ from unscaled."""
    from bigdl_tpu.models import llama as llama_mod
    from bigdl_tpu.models.llama import LlamaConfig, model_rope_freqs
    from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params

    base_hf = {"vocab_size": 256, "hidden_size": 64,
               "intermediate_size": 128, "num_hidden_layers": 2,
               "num_attention_heads": 8, "num_key_value_heads": 4,
               "max_position_embeddings": 256}
    params = random_llama_params(TINY_LLAMA, qtype=None, seed=0)
    toks = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    ref = np.asarray(llama_mod.forward_train(params, TINY_LLAMA, toks))

    for rs in [{"rope_type": "llama3", "factor": 8.0,
                "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                "original_max_position_embeddings": 128},
               {"type": "yarn", "factor": 4.0,
                "original_max_position_embeddings": 64},
               {"type": "dynamic", "factor": 2.0}]:
        cfg = LlamaConfig.from_hf({**base_hf, "rope_scaling": rs})
        inv, mscale = model_rope_freqs(cfg)
        assert inv.shape == (TINY_LLAMA.hd // 2,)
        out = np.asarray(llama_mod.forward_train(params, cfg, toks))
        assert np.all(np.isfinite(out))
        assert not np.allclose(out, ref), rs  # scaling changes outputs

    with pytest.raises(NotImplementedError, match="longrope"):
        cfg = LlamaConfig.from_hf(
            {**base_hf, "rope_scaling": {"type": "longrope"}})
        model_rope_freqs(cfg)


# --- the layer scan hands `linear` the stack and the layer (PR 46) ---

def _kernel_sized_llama():
    """A 3-layer model whose every linear has a Pallas tiling (N a
    multiple of 128), merged like a `from_pretrained` load."""
    from bigdl_tpu.models.llama import LlamaConfig, merge_projections
    from bigdl_tpu.utils.testing import random_llama_params

    cfg = LlamaConfig(vocab_size=256, hidden_size=128, intermediate_size=256,
                      num_hidden_layers=3, num_attention_heads=2,
                      num_key_value_heads=1, max_position_embeddings=128)
    return cfg, merge_projections(random_llama_params(cfg, "sym_int4"), cfg)


def _probe_counts():
    from bigdl_tpu.observability.metrics import default_registry

    series = default_registry().summary()
    return {o: series.get('bigdl_tpu_kernel_probe_total{kernel="matmul",'
                          f'outcome="{o}"}}', 0)
            for o in ("stack_in_place", "stack_by_value")}


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_stack_addressed_in_place_equals_by_value_scan(forced_pallas,
                                                       monkeypatch, paged):
    """`forward` / `forward_paged` with the quantized stacks closed over
    and read at the layer index give the logits of the by-value scan
    (the parent's form: every leaf sliced by `lax.scan`) bit for bit,
    over a 40-row prefill (GEMM) and a decode step (GEMV)."""
    from bigdl_tpu.models import llama as M
    from bigdl_tpu.ops.paged import init_paged_cache

    cfg, params = _kernel_sized_llama()
    toks = jnp.asarray(np.arange(40, dtype=np.int32)[None] % 256)

    def run():
        if paged:
            cache = init_paged_cache(cfg.num_hidden_layers, 5, 16,
                                     cfg.num_key_value_heads, cfg.hd, 1)
            tables = jnp.arange(1, 5, dtype=jnp.int32)[None]
            f = jax.jit(lambda p, t, c: M.forward_paged(p, cfg, t, c,
                                                        tables))
        else:
            cache = M.new_cache(cfg, 1, 64)
            f = jax.jit(lambda p, t, c: M.forward(p, cfg, t, c))
        pre, cache = f(params, toks, cache)
        dec, _ = f(params, toks[:, :1], cache)
        return np.asarray(pre, np.float32), np.asarray(dec, np.float32)

    before = _probe_counts()
    in_place = run()
    after = _probe_counts()
    # qkv, o, gate_up, down: once in the prefill's trace, once in decode's
    assert after["stack_in_place"] - before["stack_in_place"] == 8
    assert after["stack_by_value"] == before["stack_by_value"]
    monkeypatch.setattr(M, "hold_stacks", lambda layers: ({}, layers))
    by_value = run()
    assert _probe_counts() == after      # the by-value scan has no stack
    for a, b in zip(in_place, by_value):
        assert np.isfinite(a).all() and np.abs(a).max() > 0
        np.testing.assert_array_equal(a, b)


def test_decode_scan_slices_no_quantized_layer(forced_pallas):
    """The jaxpr of the scanned `forward` at decode rows: the quantized
    stacks enter the scan whole (as constants of its body, not as
    scanned operands), each kernel call's weight operand is the `[L, K,
    N]` stack, and no `dynamic_slice` in the body produces a layer of
    it."""
    from bigdl_tpu.models import llama as M
    from bigdl_tpu.ops.quant import QTensor

    cfg, params = _kernel_sized_llama()
    cache = M.new_cache(cfg, 2, 64)
    toks = jnp.zeros((2, 1), jnp.int32)
    before = _probe_counts()
    jaxpr = jax.make_jaxpr(
        lambda p, t, c: M.forward(p, cfg, t, c))(params, toks, cache)
    after = _probe_counts()
    assert after["stack_in_place"] - before["stack_in_place"] == 4
    assert after["stack_by_value"] == before["stack_by_value"]

    layers = cfg.num_hidden_layers
    planes = {tuple(a.shape) for q in params["layers"].values()
              if isinstance(q, QTensor) for a in (q.data, q.scale)}
    assert len(planes) == 8 and all(s[0] == layers for s in planes)
    (scan,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    n_consts = scan.params["num_consts"]
    n_carry = scan.params["num_carry"]
    consts = {tuple(v.aval.shape) for v in scan.invars[:n_consts]}
    scanned = {tuple(v.aval.shape) for v in scan.invars[n_consts + n_carry:]}
    assert planes <= consts and not planes & scanned

    def walk(jp):
        for e in jp.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)

    body = list(walk(scan.params["jaxpr"].jaxpr))
    kernels = [e for e in body if e.primitive.name == "pallas_call"
               and "qmatmul" in str(e.params.get("name", ""))]
    assert len(kernels) == 4
    for e in kernels:
        assert {tuple(v.aval.shape) for v in e.invars} & planes, e
    # `lm_head`, a single weight, takes the plain grid: 2-D planes
    (head,) = [e for e in walk(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call" and e not in kernels
               and "qmatmul" in str(e.params.get("name", ""))]
    assert tuple(params["lm_head"].data.shape) in {
        tuple(v.aval.shape) for v in head.invars}
    sliced = [tuple(v.aval.shape) for e in body
              if e.primitive.name in ("dynamic_slice", "gather", "slice")
              for v in e.outvars
              if tuple(v.aval.shape) in {s[1:] for s in planes}
              | {(1,) + s[1:] for s in planes}]
    assert not sliced, f"a layer of a quantized stack is sliced: {sliced}"
