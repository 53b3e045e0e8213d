"""What decides ``correct`` in a serving cell, on the CPU at the tiny
widths of both configurations: the program's logits prefilled and then
decoded through a cache of the cell's kind against the reference's one
full forward pass, and the tokens the engine serves against the
reference's best. Each comparison is shown to pass on the sound program
and to fail on the fault it is there for: a cache of the next lower
precision, a cache whose rows are one position off, an altered token.

On the CPU the program computes in float32, so its error is a tenth of
what the chip's bfloat16 gives and ``reference.tolerance`` (the chip's
bound) is no yardstick for a precision here: a lower precision is held
to reading three times the sound error of the same seed, the rule by
which a limit may be set at all.
"""

import dataclasses

import numpy as np
import pytest

import _paths
from harness import serve_runner, served, spec

CELLS = {"mistral-7b-int4": "mistral7b-chat-steady",
         "chatglm2-6b-int4-pagedkv8": "chatglm2-6b-docqa-shared"}
# the nearest precision below the one the configuration states
CONTROL_KV = {"bf16": "fp8_e5m2", "int8": "int4"}
SEED = 2 ** 31 + 11


@pytest.fixture(scope="module", params=sorted(CELLS))
def built(request):
    cell = spec.Cell(CELLS[request.param], _paths.ROOT, tiny=True)
    config = cell.config
    reference, weights = cell.modules["reference"], cell.modules["weights"]
    model, _ = weights.build_model(config, SEED, merge=True)
    return {
        "config": config, "reference": reference, "model": model,
        "canonical": weights.canonical_params(config, SEED),
        "quant": {"qtype": config["quant"], "block": config["quant_block"]},
        "ids": serve_runner.check_ids(SEED, config["reference"]["vocab"]),
        "kv": config["engine"]["kv_cache_dtype"]}


def _errors(built, eng_cfg, between=None):
    n = serve_runner.REF_PROMPT_TOKENS
    row, (step, cache) = serve_runner.prefill_into_cache(
        built["model"], eng_cfg, built["ids"][:n], SEED)
    if between is not None:
        cache = between(cache)
    rows = serve_runner.decode_through_cache((step, cache),
                                             built["ids"][n:])
    assert rows.shape[0] == serve_runner.DECODE_POSITIONS >= 8
    return serve_runner.logits_errors(
        built["reference"], built["canonical"],
        built["config"]["reference"], built["quant"], built["ids"], row,
        rows)


@pytest.fixture(scope="module")
def sound(built):
    return _errors(built, built["config"]["engine"])


def test_prefill_then_decode_through_the_cells_cache_agree(built, sound):
    tol = built["reference"].tolerance(built["config"], built["kv"])
    assert 0 < sound["prefill"] <= tol and 0 < sound["decode"] <= tol


def test_the_cache_is_the_kind_the_cell_times(built):
    _, (_, cache) = serve_runner.prefill_into_cache(
        built["model"], built["config"]["engine"], built["ids"][:32], SEED)
    eng = built["config"]["engine"]
    if eng.get("kv_page_size"):
        assert type(cache).__name__ == "PagedKVCache"
        assert cache.k.shape[2] == eng["kv_page_size"]
        assert str(cache.k.dtype) == "int8" and cache.k_scale is not None
    else:
        assert type(cache).__name__ == "KVCache"
        assert cache.k.shape[2] == eng["max_seq"]
        assert str(cache.k.dtype) == "bfloat16"


def test_a_cache_of_the_next_lower_precision_reads_three_times_the_error(
        built, sound):
    eng = dict(built["config"]["engine"],
               kv_cache_dtype=CONTROL_KV[built["kv"]])
    control = _errors(built, eng)
    assert control["prefill"] > 3 * sound["prefill"], (control, sound)
    assert control["decode"] > 3 * sound["decode"], (control, sound)


def test_cached_rows_one_position_off_fail_the_decode_comparison(
        built, sound):
    """Prefill is sound; every decoded position then reads values that
    belong to the position before: the comparison of the decoded
    positions is what sees it."""
    import jax.numpy as jnp

    broken = _errors(
        built, built["config"]["engine"],
        between=lambda c: dataclasses.replace(
            c, v=jnp.roll(c.v, 1, axis=2)))
    tol = built["reference"].tolerance(built["config"], built["kv"])
    assert broken["prefill"] == sound["prefill"]
    assert broken["decode"] > tol and broken["decode"] > 3 * sound["decode"]


@pytest.fixture(scope="module")
def engine_served(built):
    """Greedy requests through the program's engine at the tiny
    deployment: what ``served.compare`` is given after a window."""
    from bigdl_tpu.serving.engine import (EngineConfig, LLMEngine,
                                          SamplingParams)

    eng_cfg = dict(built["config"]["engine"])
    eng_cfg.pop("overload", None)
    engine = LLMEngine(built["model"], EngineConfig(
        prefix_cache_entries=0, **eng_cfg))
    rng = np.random.default_rng(5)
    vocab = built["config"]["reference"]["vocab"]
    prompts = [[int(x) for x in rng.integers(1, vocab, int(n))]
               for n in (40, 71, 97, 120)]
    tokens = engine.generate(prompts, SamplingParams(
        max_tokens=12, temperature=0.0, ignore_eos=True))
    return [{"prompt": p, "tokens": t} for p, t in zip(prompts, tokens)]


def _compare(built, samples):
    return served.compare(built["reference"], built["canonical"],
                          built["config"]["reference"], built["quant"],
                          samples)


def test_what_the_engine_serves_lies_at_the_references_best(
        built, engine_served):
    found = _compare(built, engine_served)
    limits = built["reference"].served_gap_limits(built["config"],
                                                  built["kv"])
    assert found["requests"] == 4 and found["served_tokens"] == 48
    assert found["longest"] == 132 and found["padded"] == 512
    assert set(limits) <= set(found)
    assert served.within(found, limits), (found, limits)
    assert found["reference_best_share"] >= 0.9


@pytest.mark.parametrize("where", [0, 7])
def test_one_altered_token_fails_the_served_gap(built, engine_served,
                                                where):
    """A token that is not what the model computed (first: prefill's;
    later: a decode step's) is the best of nothing: the reference finds
    it standard deviations below its own best."""
    vocab = built["config"]["reference"]["vocab"]
    samples = [dict(s, tokens=list(s["tokens"])) for s in engine_served]
    samples[1]["tokens"][where] = (samples[1]["tokens"][where] + 1) % vocab
    found = _compare(built, samples)
    limits = built["reference"].served_gap_limits(built["config"],
                                                  built["kv"])
    assert not served.within(found, limits)
    key = "prefill_gap_max" if where == 0 else "decode_gap_max"
    assert found[key] > 1.0 > limits[key]


def test_nothing_to_compare_is_not_correct():
    assert not served.within(served.compare(None, None, {}, {}, []),
                             {"decode_gap_max": 1.0})


def _record(i, prompt, n, ok=True, greedy=True):
    return {"ok": ok, "greedy": greedy, "request": i,
            "prompt_tokens": prompt, "tokens": ["1"] * n}


def test_the_sample_is_drawn_from_the_seed_and_holds_the_longest():
    records = [_record(i, 100 + 7 * i, 10 + i) for i in range(30)]
    records[3] = _record(3, 5000, 64)                 # the longest
    records[4] = _record(4, 9000, 64, ok=False)       # failed
    records[5] = _record(5, 9000, 64, greedy=False)   # sampled
    records[6] = dict(_record(6, 9000, 64), request=None)
    a = served.pick_sample(records, 2 ** 31 + 1, 4)
    assert len(a) == 4 and a[-1]["request"] == 3
    assert not {4, 5, 6} & {r["request"] for r in a}
    assert a == served.pick_sample(records, 2 ** 31 + 1, 4)
    drawn = {tuple(r["request"] for r in served.pick_sample(records, s, 4))
             for s in range(8)}
    assert len(drawn) > 1
    assert served.pick_sample(records[4:7], 1, 4) == []
    assert len(served.pick_sample(records[:2], 1, 4)) == 2


def test_padding_the_sequence_changes_no_compared_position(
        built, engine_served, monkeypatch):
    s = engine_served[2]
    args = (built["reference"], built["canonical"],
            built["config"]["reference"], built["quant"], s["prompt"],
            s["tokens"])
    padded = served.request_gaps(*args)
    # to the one length of a sample whose longest request is longer
    assert np.allclose(served.request_gaps(*args, padded=1536), padded,
                       atol=1e-4)
    monkeypatch.setattr(served, "PAD_TO", 1)
    assert np.allclose(served.request_gaps(*args), padded, atol=1e-4)
    assert padded.shape == (12,)


def test_the_one_length_comes_from_the_traffics_longest_request(
        monkeypatch):
    """Not from which requests a run happened to finish: a sample whose
    own longest is 11,917 tokens still goes to the 16,384 of the longest
    request the traffic can ask for."""
    seen = []
    monkeypatch.setattr(
        served, "request_gaps",
        lambda *a: seen.append(a[-1]) or np.zeros(len(a[-2])))
    samples = [{"prompt": [1] * n, "tokens": [2, 3]} for n in (40, 11915)]
    assert served.compare(None, None, {}, {}, samples,
                          longest=14336 + 640)["padded"] == 16384
    assert served.compare(None, None, {}, {}, samples)["padded"] == 12288
    assert seen == [16384, 16384, 12288, 12288]


@pytest.mark.parametrize("longest,padded", [
    (1, 512), (267, 512), (1660, 2048), (2048, 2048), (4992, 6144),
    (7892, 8192), (14336 + 128, 16384), (14336 + 640, 16384)])
def test_a_sample_is_padded_to_one_coarse_length(longest, padded):
    """Every request of a sample goes to ``pad_length`` of the longest:
    a quarter of its power-of-two ceiling, so the long-context cell's
    longest request, 14,336 tokens and its answer, gives 16,384 whatever
    the answer's length, and the reference compiles once a checkout."""
    assert served.pad_length(longest) == padded
    assert longest <= padded < longest * 4 / 3 + served.PAD_TO
