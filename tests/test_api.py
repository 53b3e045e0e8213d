"""API façade tests: Auto* from_pretrained / save_low_bit / load_low_bit /
optimize_model (reference surface: transformers/model.py, optimize.py)."""

import numpy as np
import pytest

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
def tiny_hf_dir(tmp_path_factory):
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFLlamaConfig, LlamaForCausalLM

    torch.manual_seed(0)
    model = LlamaForCausalLM(HFLlamaConfig(**TINY_CFG))
    path = tmp_path_factory.mktemp("tiny_llama_api")
    model.save_pretrained(path)
    return str(path)


def test_from_pretrained_4bit_generate(tiny_hf_dir):
    from bigdl_tpu.transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(
        tiny_hf_dir, load_in_4bit=True, max_seq=64)
    assert model.qtype == "sym_int4"
    out = model.generate([1, 5, 9], max_new_tokens=6)
    assert out.shape == (1, 3 + 6)
    np.testing.assert_array_equal(out[0, :3], [1, 5, 9])


def test_low_bit_roundtrip_identical_logits(tiny_hf_dir, tmp_path):
    from bigdl_tpu.transformers import AutoModelForCausalLM

    m1 = AutoModelForCausalLM.from_pretrained(
        tiny_hf_dir, load_in_low_bit="nf4", max_seq=64)
    save_dir = str(tmp_path / "lowbit")
    m1.save_low_bit(save_dir)

    m2 = AutoModelForCausalLM.load_low_bit(save_dir)
    assert m2.qtype == "nf4"
    assert m2.max_seq == 64

    out1 = m1.generate([2, 8, 30, 4], max_new_tokens=8)
    out2 = m2.generate([2, 8, 30, 4], max_new_tokens=8)
    np.testing.assert_array_equal(out1, out2)


def test_from_pretrained_detects_low_bit_dir(tiny_hf_dir, tmp_path):
    from bigdl_tpu.transformers import AutoModelForCausalLM

    m1 = AutoModelForCausalLM.from_pretrained(
        tiny_hf_dir, load_in_4bit=True, max_seq=64)
    save_dir = str(tmp_path / "lb2")
    m1.save_low_bit(save_dir)
    # from_pretrained on a low-bit dir takes the fast load path
    m2 = AutoModelForCausalLM.from_pretrained(save_dir)
    out1 = m1.generate([7, 3], max_new_tokens=4)
    out2 = m2.generate([7, 3], max_new_tokens=4)
    np.testing.assert_array_equal(out1, out2)


def test_optimize_model_matches_direct_quantized_load(tiny_hf_dir):
    from bigdl_tpu import optimize_model
    from bigdl_tpu.transformers import AutoModelForCausalLM

    direct = AutoModelForCausalLM.from_pretrained(
        tiny_hf_dir, load_in_low_bit="sym_int4", max_seq=64)
    dense = AutoModelForCausalLM.from_pretrained(
        tiny_hf_dir, load_in_low_bit="bf16", max_seq=64)
    opt = optimize_model(dense, low_bit="sym_int4")

    from bigdl_tpu.ops.quant import QTensor
    # merged-projection layout is the from_pretrained default
    assert isinstance(opt.params["layers"]["qkv_proj"], QTensor)
    assert isinstance(opt.params["lm_head"], QTensor)
    assert not isinstance(opt.params["embed_tokens"], QTensor)

    out1 = direct.generate([1, 9, 77], max_new_tokens=6)
    out2 = opt.generate([1, 9, 77], max_new_tokens=6)
    # bf16 load then quantize vs fp32 load then quantize: tiny rounding
    # differences may flip late tokens; the first few must agree
    np.testing.assert_array_equal(out1[:, :5], out2[:, :5])


def test_unsupported_arch_raises(tmp_path):
    import json
    from bigdl_tpu.transformers import AutoModelForCausalLM

    d = tmp_path / "weird"
    d.mkdir()
    (d / "config.json").write_text(json.dumps(
        {"architectures": ["TotallyUnknownModel"], "vocab_size": 8}))
    with pytest.raises(ValueError, match="unsupported architecture"):
        AutoModelForCausalLM.from_pretrained(str(d))


def test_llm_patch_roundtrip():
    import transformers

    import bigdl_tpu.llm_patching as lp
    from bigdl_tpu.transformers.model import _BaseAutoModelClass

    orig = transformers.AutoModelForCausalLM
    lp.llm_patch()
    try:
        assert issubclass(transformers.AutoModelForCausalLM,
                          _BaseAutoModelClass)
    finally:
        lp.llm_unpatch()
    assert transformers.AutoModelForCausalLM is orig


def test_runtime_flags():
    from bigdl_tpu import config as C

    f = C.flags()
    assert f.matmul_backend in ("auto", "xla", "pallas")
    C.set_flags(default_max_seq=123)
    assert C.flags().default_max_seq == 123
    C.set_flags(default_max_seq=2048)


def test_example_packing():
    from bigdl_tpu.examples.qlora_finetune import format_alpaca, pack_batches

    text = format_alpaca({"instruction": "add", "input": "1+1",
                          "output": "2"})
    assert "### Input:" in text and text.endswith("2")
    assert format_alpaca({"text": "raw"}) == "raw"
    batches = list(pack_batches([[1, 2, 3]] * 30, batch=2, seq_len=8))
    assert len(batches) == 5
    assert batches[0]["input_ids"].shape == (2, 8)


def test_loader_util(tmp_path):
    from bigdl_tpu.transformers.loader import get_model_path

    d = tmp_path / "hub" / "meta" / "llama"
    d.mkdir(parents=True)
    assert get_model_path("meta/llama", str(tmp_path / "hub")) == str(d)
    assert get_model_path("/abs/path", None) == "/abs/path"


def test_lowbit_to_numpy_contiguous():
    """device_get can return non-C-contiguous hosts arrays; safetensors
    ignores strides, so _to_numpy must always hand back C-contiguous
    memory."""
    import numpy as np

    from bigdl_tpu.transformers.lowbit_io import _to_numpy

    strided = np.arange(24, dtype=np.float32).reshape(4, 6).T  # F-order view
    out, dt = _to_numpy(strided)
    assert out.flags["C_CONTIGUOUS"]
    np.testing.assert_array_equal(out, strided)

    import ml_dtypes

    bf = np.arange(12, dtype=np.float32).astype(ml_dtypes.bfloat16)
    bf_strided = np.broadcast_to(bf.reshape(3, 4).T, (4, 3))[:, ::-1]
    out, dt = _to_numpy(bf_strided)
    assert out.flags["C_CONTIGUOUS"] and dt == "bfloat16"


def test_profiling_helpers(tmp_path):
    """trace/annotate/StepTimer work on the CPU backend (jax.profiler
    emits a TensorBoard/Perfetto trace directory)."""
    import os

    import jax.numpy as jnp

    from bigdl_tpu.utils.profiling import StepTimer, annotate, trace

    d = str(tmp_path / "tb")
    with trace(d):
        with annotate("matmul"):
            x = jnp.ones((64, 64)) @ jnp.ones((64, 64))
            x.block_until_ready()
    # a plugins/profile/<ts> dir with trace artifacts must exist
    prof = os.path.join(d, "plugins", "profile")
    assert os.path.isdir(prof) and os.listdir(prof)

    t = StepTimer()
    out = t.timed("step", lambda a: a @ a, jnp.ones((32, 32)))
    assert out.shape == (32, 32)
    with t.measure("region", result=out):
        out2 = out + 1
    s = t.summary()
    assert s["step"]["count"] == 1 and s["step"]["mean_ms"] > 0
    assert "region" in s


def test_from_pretrained_speculative_merged(tiny_hf_dir):
    """speculative=True must work with the merged-projection default:
    target and draft share the merged layout, and self-speculative
    greedy output equals the plain greedy output (speculative decoding
    is lossless for greedy)."""
    from bigdl_tpu.transformers import AutoModelForCausalLM

    spec = AutoModelForCausalLM.from_pretrained(
        tiny_hf_dir, load_in_low_bit="bf16", speculative=True, max_seq=64)
    assert spec.draft_params is not None
    assert "qkv_proj" in spec.params["layers"]
    assert "qkv_proj" in spec.draft_params["layers"]
    plain = AutoModelForCausalLM.from_pretrained(
        tiny_hf_dir, load_in_low_bit="bf16", max_seq=64)
    out_s = spec.generate([3, 1, 4, 1, 5], max_new_tokens=8)
    out_p = plain.generate([3, 1, 4, 1, 5], max_new_tokens=8)
    np.testing.assert_array_equal(out_s, out_p)


def test_model_hub_kwarg(tmp_path):
    """model_hub validation (reference model.py:147-150): bad values
    rejected; 'modelscope' without the package errors actionably;
    local paths bypass the hub."""
    import pytest

    from bigdl_tpu.transformers.model import _resolve_hub_path

    with pytest.raises(ValueError, match="model_hub"):
        _resolve_hub_path("x", "wrong")
    assert _resolve_hub_path(str(tmp_path), "modelscope") == str(tmp_path)
    try:
        import modelscope  # noqa: F401
        has_ms = True
    except ImportError:
        has_ms = False
    if not has_ms:
        with pytest.raises(ImportError, match="modelscope"):
            _resolve_hub_path("org/nonexistent-repo", "modelscope")


def test_mxu_layout_save_roundtrip(tiny_hf_dir, tmp_path):
    """The TPU shipped default loads with the int4-dtype MXU layout;
    save_low_bit must repack to the canonical interchange format and the
    reloaded model (canonical) must generate the same tokens."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.config import set_flags
    from bigdl_tpu.ops.quant import QTensor
    from bigdl_tpu.transformers import AutoModelForCausalLM

    set_flags(prepack="on")
    try:
        m1 = AutoModelForCausalLM.from_pretrained(
            tiny_hf_dir, load_in_4bit=True, max_seq=64)
    finally:
        set_flags(prepack="auto")
    # the layout actually applied (int4-dtype planes present)
    datas = [leaf.data.dtype for leaf in jax.tree_util.tree_leaves(
        m1.params, is_leaf=lambda x: isinstance(x, QTensor))
        if isinstance(leaf, QTensor)]
    assert jnp.int4 in datas, "mxu layout did not apply"

    save_dir = str(tmp_path / "mxu_rt")
    m1.save_low_bit(save_dir)
    m2 = AutoModelForCausalLM.load_low_bit(save_dir)
    datas2 = [leaf.data.dtype for leaf in jax.tree_util.tree_leaves(
        m2.params, is_leaf=lambda x: isinstance(x, QTensor))
        if isinstance(leaf, QTensor)]
    assert jnp.int4 not in datas2, "saved checkpoint kept the MXU layout"

    out1 = m1.generate([2, 8, 30, 4], max_new_tokens=8)
    out2 = m2.generate([2, 8, 30, 4], max_new_tokens=8)
    np.testing.assert_array_equal(out1, out2)
