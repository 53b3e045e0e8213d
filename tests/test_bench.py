"""Benchmark tooling tests: BenchmarkWrapper timing, perplexity sanity,
lm-eval loglikelihood core, all-in-one runner config."""

import json

import numpy as np
import pytest

from bigdl_tpu.bench import BenchmarkWrapper, perplexity
from bigdl_tpu.bench.lm_eval_adapter import sequence_loglikelihood
from bigdl_tpu.models import llama as llama_mod
from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params


class MiniModel:
    """TpuCausalLM-shaped shim over raw params (public generate path)."""

    def __init__(self):
        from bigdl_tpu.generation import Generator

        self.params = random_llama_params(TINY_LLAMA, qtype="sym_int4")
        self.config = TINY_LLAMA

        class Fam:
            forward = staticmethod(llama_mod.forward)
            prefill = staticmethod(llama_mod.forward_last_token)
            forward_train = staticmethod(llama_mod.forward_train)
            new_cache = staticmethod(llama_mod.new_cache)

        self.family = Fam()
        self._gen = Generator(self.params, TINY_LLAMA, max_seq=256)

    def generate(self, ids, max_new_tokens=16, stats=None, **kw):
        ids = np.asarray(ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        from bigdl_tpu.generation import GenerationConfig

        new = self._gen.generate(
            ids, GenerationConfig(max_new_tokens=max_new_tokens),
            stats=stats)
        return np.concatenate([ids, new], axis=1)


@pytest.fixture(scope="module")
def model():
    return MiniModel()


def test_benchmark_wrapper(model):
    bench = BenchmarkWrapper(model)
    out = bench.generate(np.arange(1, 9, dtype=np.int32), max_new_tokens=8)
    assert out.shape[1] == 16
    res = bench.results[-1]
    assert res.first_cost > 0
    assert res.rest_cost_mean > 0
    assert res.n_tokens == 8
    # passthrough attributes
    assert bench.config is model.config


def test_perplexity_self_generated_is_low(model):
    """Greedy self-generated text must have far lower ppl than random."""
    prompt = np.arange(1, 9, dtype=np.int32)
    full = model.generate(prompt, max_new_tokens=120)[0]
    ppl_self = perplexity((model.params, model.config,
                           llama_mod.forward_train), full,
                          window=32, stride=16)
    rng = np.random.default_rng(0)
    ppl_rand = perplexity((model.params, model.config,
                           llama_mod.forward_train),
                          rng.integers(0, TINY_LLAMA.vocab_size, 128),
                          window=32, stride=16)
    assert np.isfinite(ppl_self) and np.isfinite(ppl_rand)
    # random weights are near-uniform: random-token ppl ~= vocab_size,
    # self-generated strictly lower
    assert 0.5 * TINY_LLAMA.vocab_size < ppl_rand < 2 * TINY_LLAMA.vocab_size
    assert ppl_self < ppl_rand * 0.8, (ppl_self, ppl_rand)


def test_perplexity_short_input_rejected(model):
    with pytest.raises(ValueError, match="need >"):
        perplexity((model.params, model.config, llama_mod.forward_train),
                   np.arange(10), window=32)


def test_sequence_loglikelihood_greedy(model):
    prompt = np.arange(1, 9, dtype=np.int32)
    full = model.generate(prompt, max_new_tokens=8)[0]
    ctx, cont = full[:8], full[8:]
    ll, greedy = sequence_loglikelihood(model, ctx, cont)
    assert greedy is True          # continuation WAS generated greedily
    assert ll < 0
    # a mismatched continuation must score worse and not be greedy
    bad = (cont + 7) % TINY_LLAMA.vocab_size
    ll_bad, greedy_bad = sequence_loglikelihood(model, ctx, bad)
    assert ll_bad < ll and greedy_bad is False


def test_runner_config_load(tmp_path):
    from bigdl_tpu.bench.run import load_config

    p = tmp_path / "cfg.yaml"
    p.write_text("model_paths: [/m]\nin_out_pairs: ['32-32']\n"
                 "low_bit: sym_int4\n")
    cfg = load_config(str(p))
    assert cfg["model_paths"] == ["/m"]
    pj = tmp_path / "cfg.json"
    pj.write_text(json.dumps({"model_paths": ["/m2"]}))
    assert load_config(str(pj))["model_paths"] == ["/m2"]


def test_mcq_eval(model):
    """Multiple-choice eval picks the model's own greedy continuation."""
    from bigdl_tpu.bench.mcq_eval import evaluate_mcq, format_mcq

    class TokenizerStub:
        """Token-id 'tokenizer': prompts are int lists already."""

        def __call__(self, text, add_special_tokens=True):
            # map each character to a small token id deterministically
            return {"input_ids": [ord(c) % 250 for c in text][:48]}

    tok = TokenizerStub()
    # build records whose correct answer is whatever the model scores
    # highest, then verify evaluate_mcq agrees with a manual argmax
    from bigdl_tpu.bench.lm_eval_adapter import sequence_loglikelihood

    recs = [{"question": f"Question number {i}?",
             "choices": ["alpha", "beta", "gamma", "delta"],
             "answer": 0} for i in range(3)]
    # compute the model-preferred answer per record, set it as truth
    for r in recs:
        ctx = tok(format_mcq(r["question"], r["choices"]))["input_ids"]
        scores = []
        for j in range(4):
            cont = tok(f" {'ABCD'[j]}", add_special_tokens=False)["input_ids"]
            ll, _ = sequence_loglikelihood(model, ctx, cont)
            scores.append(ll / len(cont))
        r["answer"] = int(np.argmax(scores))
    res = evaluate_mcq(model, tok, recs)
    assert res["n"] == 3
    assert res["accuracy"] == 1.0

    # letter answers parse too
    recs[0]["answer"] = "ABCD"[recs[0]["answer"]]
    res2 = evaluate_mcq(model, tok, recs[:1])
    assert res2["accuracy"] == 1.0


def test_public_exports():
    import bigdl_tpu

    assert bigdl_tpu.AutoModelForCausalLM is not None
    assert bigdl_tpu.LLMEngine is not None
    assert callable(bigdl_tpu.speculative_generate)
    assert callable(bigdl_tpu.llm_patch)
    import pytest as _pytest

    with _pytest.raises(AttributeError):
        bigdl_tpu.not_a_thing


def test_report_csv_html_and_diff(tmp_path):
    """bench/report.py: JSON-lines -> csv + html, with baseline diff
    (the reference's csv_to_html/check_results role)."""
    import json

    from bigdl_tpu.bench.report import (diff_results, load_results,
                                        write_csv, write_html)

    # rows use bench/run.py's real schema (run_one's return dict)
    cur = [{"model": "m", "low_bit": "sym_int4", "api": "transformers_int4",
            "in_out": "32-8", "first_token_ms": 10.0, "rest_token_ms": 2.0,
            "peak_memory": 0},
           {"model": "m", "low_bit": "sym_int4", "api": "transformers_int4",
            "in_out": "64-8", "first_token_ms": 20.0, "rest_token_ms": 2.5,
            "peak_memory": 0}]
    prev = [{"model": "m", "low_bit": "sym_int4", "api": "transformers_int4",
             "in_out": "32-8", "first_token_ms": 12.0, "rest_token_ms": 3.0,
             "peak_memory": 0},
            {"model": "m", "low_bit": "sym_int4", "api": "transformers_int4",
             "in_out": "64-8", "first_token_ms": 24.0, "rest_token_ms": 5.0,
             "peak_memory": 0}]
    p = tmp_path / "cur.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in cur))
    assert load_results(str(p)) == cur

    d = diff_results(cur, prev)
    # per in-out pair ratios (keys must NOT collapse across pairs)
    assert d[0]["rest_token_ms_ratio"] == 1.5
    assert d[1]["rest_token_ms_ratio"] == 2.0

    csvp = tmp_path / "r.csv"
    write_csv(d, str(csvp))
    csv_text = csvp.read_text()
    assert "sym_int4" in csv_text and "32-8" in csv_text
    assert "rest_token_ms_ratio" in csv_text     # diff columns survive

    htmlp = tmp_path / "r.html"
    write_html(d, str(htmlp))
    body = htmlp.read_text()
    assert "<table>" in body and "rest_token_ms_ratio" in body


def test_run_matrix_apis(tmp_path):
    """bench/run.py drives the widened test_api x low_bit matrix
    over one tiny checkpoint."""
    import jax

    from bigdl_tpu.bench.accuracy_eval import export_hf
    from bigdl_tpu.bench.run import TEST_APIS, run
    from bigdl_tpu.models.llama import LlamaConfig
    from bigdl_tpu.utils.testing import random_llama_params

    import jax.numpy as jnp

    cfg = LlamaConfig(
        vocab_size=128, hidden_size=256, intermediate_size=512,
        num_hidden_layers=2, num_attention_heads=8,
        num_key_value_heads=8, max_position_embeddings=128)
    params = random_llama_params(cfg, qtype=None, seed=0,
                                 compute_dtype=jnp.float32)
    ckpt = str(tmp_path / "tiny")
    export_hf(params, cfg, ckpt)

    apis = ["transformers_int4", "no_merge", "fp8_kv", "serving"]
    # mesh apis shard over ALL local devices; only valid when the head
    # count divides (e.g. a host with 16 virtual devices must skip)
    if (len(jax.devices()) >= 2
            and cfg.num_attention_heads % len(jax.devices()) == 0):
        apis += ["explicit_tp", "gspmd_tp"]
    rows = run({"model_paths": [ckpt], "in_out_pairs": ["16-8"],
                "low_bit": "sym_int4", "test_api": apis,
                "num_trials": 1, "warm_up": 1})
    assert len(rows) == len(apis)
    by_api = {r["api"]: r for r in rows}
    assert by_api["transformers_int4"]["rest_token_ms"] > 0
    assert by_api["serving"]["serving_tokens_per_s"] > 0
    if "explicit_tp" in by_api:
        assert by_api["explicit_tp"]["per_token_ms"] > 0
    for api in TEST_APIS:
        assert isinstance(api, str)


def test_run_matrix_rejects_unknown_api(tmp_path):
    from bigdl_tpu.bench.run import run_one

    with pytest.raises(ValueError, match="unknown test_api"):
        run_one("x", "sym_int4", 8, 4, "cuda_fp16", 1, 0)
