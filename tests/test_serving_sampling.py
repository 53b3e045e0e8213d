"""Serving sampler breadth + scheduler preemption.

Reference parity targets: vllm/sampling_params.py (penalties, n, best_of,
logprobs, seed) and vllm/core/scheduler.py:52-66 (preemption by recompute
under pressure).
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params
from bigdl_tpu.models import llama as llama_mod


class FakeModel:
    def __init__(self, params, cfg):
        self.params = params
        self.config = cfg
        self.hf_config = {"eos_token_id": None}

        class Fam:
            forward = staticmethod(llama_mod.forward)
            prefill = staticmethod(llama_mod.forward_last_token)
            new_cache = staticmethod(llama_mod.new_cache)

        self.family = Fam()


@pytest.fixture(scope="module")
def model():
    return FakeModel(random_llama_params(TINY_LLAMA, qtype="sym_int4",
                                         seed=0), TINY_LLAMA)


def run_one(eng, rid, prompt, params):
    eng.add_request(rid, prompt, params)
    toks, lps, done = {}, {}, False
    for _ in range(500):
        eng.step()
        for o in eng.get_outputs(rid):
            toks.setdefault(o.index, []).extend(o.new_token_ids)
            if o.logprobs:
                lps.setdefault(o.index, []).extend(o.logprobs)
            done = done or o.finished
        if done:
            break
    assert done, "request never finished"
    return toks, lps


def test_repetition_penalty_changes_engine_output(model):
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    prompt = [3, 9, 3, 9, 3, 9, 3, 9]
    plain, _ = run_one(eng, "p", prompt, SamplingParams(max_tokens=16))
    pen, _ = run_one(eng, "q", prompt, SamplingParams(
        max_tokens=16, repetition_penalty=1.8))
    assert plain[0] != pen[0]
    assert max(pen[0].count(t) for t in set(pen[0])) < max(
        plain[0].count(t) for t in set(plain[0]))


def test_logprobs_returned_and_consistent(model):
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    toks, lps = run_one(eng, "lp", [1, 2, 3, 4], SamplingParams(
        max_tokens=6, logprobs=3))
    assert len(lps[0]) == len(toks[0]) == 6
    for entry, tok in zip(lps[0], toks[0]):
        assert entry.token_id == tok
        assert entry.logprob <= 0.0
        assert len(entry.top) == 3
        # top list sorted descending and contains >= chosen's logprob first
        tops = [lp for _, lp in entry.top]
        assert tops == sorted(tops, reverse=True)
        # greedy: the chosen token has the max logprob (bf16 ties can put
        # a different token id first, but never a higher value)
        assert entry.top[0][1] == pytest.approx(entry.logprob, abs=1e-9)


def test_n_parallel_sampling_streams_choice_indices(model):
    eng = LLMEngine(model, EngineConfig(max_batch=4, max_seq=128))
    toks, _ = run_one(eng, "n2", [5, 6, 7], SamplingParams(
        max_tokens=5, n=2, temperature=0.8, seed=11))
    assert set(toks) == {0, 1}
    assert len(toks[0]) == 5 and len(toks[1]) == 5
    # different seeds per child: overwhelmingly different samples
    assert toks[0] != toks[1]


def test_best_of_returns_best_candidate(model):
    eng = LLMEngine(model, EngineConfig(max_batch=4, max_seq=128))
    toks, _ = run_one(eng, "bo", [5, 6, 7], SamplingParams(
        max_tokens=5, n=1, best_of=3, temperature=1.2, seed=7))
    assert set(toks) == {0}
    assert len(toks[0]) == 5
    # greedy reference: best_of with temperature cannot beat picking the
    # greedy sequence's own mean logprob often, but the API contract here
    # is just: one choice out, request completes. Ranking correctness is
    # covered by determinism below: same request, same seed, same winner.
    toks2, _ = run_one(eng, "bo2", [5, 6, 7], SamplingParams(
        max_tokens=5, n=1, best_of=3, temperature=1.2, seed=7))
    assert toks2[0] == toks[0]


def test_seeded_sampling_deterministic(model):
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    a, _ = run_one(eng, "s1", [2, 4, 6], SamplingParams(
        max_tokens=8, temperature=0.9, seed=123))
    b, _ = run_one(eng, "s2", [2, 4, 6], SamplingParams(
        max_tokens=8, temperature=0.9, seed=123))
    assert a[0] == b[0]


def test_preemption_relieves_starvation_and_preserves_output(model):
    """One slot, a long-running request, a second queued request: without
    preemption the second starves until the first finishes. With it, the
    first is evicted by recompute, the second runs, and the first's FINAL
    token stream is identical to an uninterrupted greedy run."""
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128,
                                        preempt_after_steps=3))
    long_p = SamplingParams(max_tokens=30)
    short_p = SamplingParams(max_tokens=4)
    eng.add_request("long", [1, 2, 3, 4], long_p)
    eng.add_request("short", [9, 8, 7], short_p)

    toks = {"long": [], "short": []}
    first_short_at = None
    long_done_at = None
    for i in range(400):
        eng.step()
        for rid in ("long", "short"):
            for o in eng.get_outputs(rid):
                toks[rid].extend(o.new_token_ids)
                if rid == "short" and first_short_at is None and \
                        o.new_token_ids:
                    first_short_at = i
                if rid == "long" and o.finished:
                    long_done_at = i
        if len(toks["short"]) >= 4 and long_done_at is not None:
            break
    assert len(toks["short"]) == 4, "queued request starved"
    assert len(toks["long"]) == 30
    assert long_done_at is not None
    assert first_short_at < long_done_at, \
        "short request did not run until the long one finished: no preempt"

    # uninterrupted reference
    eng2 = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128,
                                         preempt_after_steps=0))
    ref, _ = run_one(eng2, "ref", [1, 2, 3, 4], long_p)
    assert toks["long"] == ref[0], "preempt-resume diverged from greedy"


def test_seeded_sampling_survives_preemption(model):
    """Seeded temperature sampling is keyed by (seed, absolute position),
    so a preempt-resume draws the same tokens as an uninterrupted run."""
    pr = SamplingParams(max_tokens=20, temperature=1.0, seed=77)
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128,
                                        preempt_after_steps=3))
    eng.add_request("a", [1, 2, 3], pr)
    eng.add_request("b", [4, 5, 6], SamplingParams(max_tokens=3))
    got, done = [], False
    for _ in range(400):
        eng.step()
        for o in eng.get_outputs("a"):
            got.extend(o.new_token_ids)
            done = done or o.finished
        eng.get_outputs("b")
        if done:
            break
    assert done and len(got) == 20

    eng2 = LLMEngine(model, EngineConfig(max_batch=1, max_seq=128,
                                         preempt_after_steps=0))
    ref, _ = run_one(eng2, "ref", [1, 2, 3], pr)
    assert got == ref[0], "seeded stream diverged across preemption"


def test_oversubscription_all_complete_no_starvation(model):
    """6 requests through 2 slots with aggressive preemption: everyone
    completes with exactly max_tokens tokens."""
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128,
                                        preempt_after_steps=2))
    rids = [f"r{i}" for i in range(6)]
    for i, rid in enumerate(rids):
        eng.add_request(rid, [i + 1, i + 2, i + 3],
                        SamplingParams(max_tokens=6))
    got = {rid: [] for rid in rids}
    finished = set()
    for _ in range(800):
        eng.step()
        for rid in rids:
            for o in eng.get_outputs(rid):
                got[rid].extend(o.new_token_ids)
                if o.finished:
                    finished.add(rid)
        if len(finished) == len(rids):
            break
    assert finished == set(rids)
    for rid in rids:
        assert len(got[rid]) == 6, (rid, got[rid])


def test_wide_batch_all_slots_correct(model):
    """16 slots decoding concurrently (beyond the reference-scale
    max_batch 8): every request matches its single-request output —
    the device-argmax fast path and per-slot bookkeeping scale."""
    from bigdl_tpu.generation import generate_on_device
    from bigdl_tpu.models import llama as llama_mod
    import jax.numpy as jnp

    eng = LLMEngine(model, EngineConfig(max_batch=16, max_seq=64))
    prompts = {f"w{i}": [(i * 5 + j) % TINY_LLAMA.vocab_size or 1
                         for j in range(1, 5)] for i in range(16)}
    for rid, p in prompts.items():
        eng.add_request(rid, p, SamplingParams(max_tokens=5))
    got = {r: [] for r in prompts}
    finished = set()
    for _ in range(600):
        eng.step()
        for r in prompts:
            for o in eng.get_outputs(r):
                got[r].extend(o.new_token_ids)
                if o.finished:
                    finished.add(r)
        if len(finished) == 16:
            break
    assert len(finished) == 16
    for rid, p in prompts.items():
        cache = llama_mod.new_cache(TINY_LLAMA, 1, 64)
        want, _ = generate_on_device(
            model.params, TINY_LLAMA, llama_mod.forward,
            jnp.asarray(np.asarray(p, np.int32)[None]), cache,
            max_new_tokens=5)
        assert got[rid] == list(np.asarray(want)[0]), rid


def test_malformed_requests_rejected_at_add(model):
    """Client input is validated at add_request (HTTP 400), never inside
    step() — a bad token id there would wedge the admission lane."""
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    V = TINY_LLAMA.vocab_size
    with pytest.raises(ValueError, match="token ids"):
        eng.add_request("bad1", [1, 2, V], SamplingParams(
            repetition_penalty=1.5))
    with pytest.raises(ValueError, match="token ids"):
        eng.add_request("bad2", [1, -3], SamplingParams())
    with pytest.raises(ValueError, match="logprobs"):
        eng.add_request("bad3", [1, 2], SamplingParams(logprobs=V + 5))
    with pytest.raises(ValueError, match="max_tokens"):
        eng.add_request("bad4", [1, 2], SamplingParams(max_tokens=0))
    # engine still serves fine afterwards
    toks, _ = run_one(eng, "ok", [1, 2, 3], SamplingParams(max_tokens=3))
    assert len(toks[0]) == 3


def test_openai_endpoint_penalties_n_logprobs(model):
    """HTTP surface: penalties accepted, n=2 -> two choices, logprobs
    block present (token-id keyed, no tokenizer)."""
    from bigdl_tpu.serving.api_server import OpenAIServer

    eng = LLMEngine(model, EngineConfig(max_batch=4, max_seq=128))
    server = OpenAIServer(eng)
    httpd = server.serve(port=0, background=True)
    port = httpd.server_address[1]
    try:
        def post(body):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/completions",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                return json.loads(r.read())

        out = post({"prompt": [3, 9, 3, 9, 3, 9], "max_tokens": 8,
                    "repetition_penalty": 1.8, "logprobs": 2})
        assert len(out["choices"]) == 1
        lp = out["choices"][0]["logprobs"]
        assert len(lp["token_logprobs"]) == 8
        assert all(len(d) == 2 for d in lp["top_logprobs"])

        out2 = post({"prompt": [5, 6, 7], "max_tokens": 4, "n": 2,
                     "temperature": 0.9, "seed": 3})
        assert {c["index"] for c in out2["choices"]} == {0, 1}
        assert out2["usage"]["completion_tokens"] == 8
    finally:
        server.shutdown()


def test_topk1_any_temperature_is_greedy(model):
    """top_k=1 pins the device sampler to argmax regardless of
    temperature (gumbel noise cannot reorder a single candidate)."""
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    g, _ = run_one(eng, "g", [2, 4, 6], SamplingParams(max_tokens=10))
    k1, _ = run_one(eng, "k", [2, 4, 6], SamplingParams(
        max_tokens=10, temperature=3.0, top_k=1))
    assert k1[0] == g[0]


def test_top_p_epsilon_is_greedy(model):
    """A vanishing nucleus keeps only the most-probable token."""
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    g, _ = run_one(eng, "g", [2, 4, 6], SamplingParams(max_tokens=10))
    p_, _ = run_one(eng, "p", [2, 4, 6], SamplingParams(
        max_tokens=10, temperature=2.0, top_p=1e-6))
    assert p_[0] == g[0]


def test_seeded_output_independent_of_batch_composition(model):
    """A seeded request samples from the same device stream whether it
    runs alone or co-batched with a host-sampled (penalties) request —
    the device sampler serves simple rows in mixed batches too."""
    p = SamplingParams(max_tokens=12, temperature=0.9, top_k=8, seed=7)
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    alone, _ = run_one(eng, "a", [5, 6, 7], p)

    eng2 = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    eng2.add_request("noise", [3, 9, 3, 9], SamplingParams(
        max_tokens=60, repetition_penalty=1.3))
    for _ in range(3):
        eng2.step()                    # noise decoding on the host path
    mixed, _ = run_one(eng2, "b", [5, 6, 7], p)
    assert mixed[0] == alone[0]


def test_top_p_zero_is_greedy(model):
    """OpenAI clients send top_p=0 to mean greedy; the device sampler
    must keep the top token rather than masking everything to -inf."""
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    g, _ = run_one(eng, "g", [2, 4, 6], SamplingParams(max_tokens=10))
    z, _ = run_one(eng, "z", [2, 4, 6], SamplingParams(
        max_tokens=10, temperature=1.0, top_p=0.0))
    assert z[0] == g[0]


# -- the sampler sorts no vocabulary (PR 51) -------------------------------

def _two_sort_transform(lg, temps, top_ks, top_ps):
    """`engine._transform_rows` as it stood before PR 51, the plain
    reference: the k-th entry of a sorted copy, then the nucleus prefix
    of a second sorted copy, for every row."""
    import jax
    import jax.numpy as jnp

    lg = lg.astype(jnp.float32)
    v = lg.shape[-1]
    greedy = temps <= 0.0
    t = lg / jnp.maximum(temps, 1e-6)[:, None]
    k = jnp.where(greedy | (top_ks <= 0), v, top_ks)
    sd = -jnp.sort(-t, axis=-1)
    kth = jnp.take_along_axis(
        sd, jnp.clip(k - 1, 0, v - 1)[:, None], axis=-1)
    t = jnp.where(t < kth, -jnp.inf, t)
    p = jnp.where(greedy, 1.0, top_ps)[:, None]
    sd = -jnp.sort(-t, axis=-1)
    probs = jax.nn.softmax(sd, axis=-1)
    keep = ((jnp.cumsum(probs, axis=-1) - probs) < p) | (p >= 1.0)
    keep = keep | (jnp.arange(v)[None, :] == 0)
    cutoff = jnp.min(jnp.where(keep, sd, jnp.inf), axis=-1)
    return jnp.where(t < cutoff[:, None], -jnp.inf, t), greedy


def _tied_logits(v, rows, dtype, seed):
    """`[rows, v]` logits in `dtype` whose 21st to 36th largest of every
    row are one value: ties that straddle rank 32 and lie inside the top
    V - 1."""
    import jax.numpy as jnp

    x = np.random.default_rng(seed).standard_normal(
        (rows, v)).astype(np.float32) * 3.0
    top = np.argsort(-x, axis=-1)
    for r in range(rows):
        x[r, top[r, 20:36]] = x[r, top[r, 20]]
    return jnp.asarray(x).astype(dtype)


@pytest.mark.parametrize("top_p", [1.0, 0.9, 0.0])
@pytest.mark.parametrize("v", [320, 32000, 50048])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_transform_rows_is_the_two_sort_reference_bit_for_bit(dtype, v,
                                                               top_p):
    """Greedy and sampled rows in one batch, every `top_k` of {0, 1, 2,
    32, V - 1, V, > V}: the masked set and every kept value are the
    sorted reference's, ties at the threshold kept."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.serving.engine import _transform_rows

    ks = [0, 1, 2, 32, v - 1, v, v + 7, 32, 2]
    lg = _tied_logits(v, len(ks), dtype, seed=v)
    temps = jnp.asarray([0.8, 0.05, 1.0, 0.8, 0.7, 1.3, 0.8, 0.0, 0.0],
                        jnp.float32)
    args = (lg, temps, jnp.asarray(ks, jnp.int32),
            jnp.full((len(ks),), top_p, jnp.float32))
    got, greedy = jax.jit(_transform_rows)(*args)
    want, greedy_ref = jax.jit(_two_sort_transform)(*args)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(greedy_ref))
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))
    if top_p >= 1.0:    # rank 32 falls among the ties: all 16 are kept
        assert int(np.isfinite(np.asarray(got)[3]).sum()) == 36


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kth_largest_is_the_sorted_rows_entry_with_multiplicity(dtype):
    import jax.numpy as jnp
    from bigdl_tpu.ops.dsa import kth_largest

    v = 1000
    lg = _tied_logits(v, 8, dtype, seed=5)
    lg = lg.at[0, :3].set(jnp.asarray([-jnp.inf, jnp.inf, -0.0], lg.dtype))
    ks = np.asarray([1, 2, 21, 32, 36, 37, v, v + 5], np.int32)
    got = np.asarray(kth_largest(lg, jnp.asarray(ks)).astype(jnp.float32))
    sd = -np.sort(-np.asarray(lg.astype(jnp.float32)), axis=-1)
    want = sd[np.arange(8), np.clip(ks, 1, v) - 1]
    np.testing.assert_array_equal(got, want)
    # a scalar k (the DSA caller's) is every row's
    np.testing.assert_array_equal(
        np.asarray(kth_largest(lg, 32).astype(jnp.float32)), sd[:, 31])


def test_select_topk_mask_goes_through_the_shared_search(monkeypatch):
    """`ops/dsa.select_topk_mask` takes its k-th key from `_kth_key`, the
    search `kth_largest` returns the value of, and keeps `lax.top_k`'s
    selection (ties to the lower position)."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.ops import dsa

    calls = []
    inner = dsa._kth_key
    monkeypatch.setattr(dsa, "_kth_key",
                        lambda x, k: calls.append(k) or inner(x, k))
    sc = np.array(_tied_logits(512, 4, "float32", seed=9))
    sc[1, 100:] = -np.inf                       # 100 candidates
    sc[2, 5:] = -np.inf                         # fewer than k
    k = 32
    got = np.asarray(dsa.select_topk_mask(jnp.asarray(sc), k))
    assert calls == [k]
    _, idx = jax.lax.top_k(jnp.asarray(sc), k)
    want = np.zeros_like(got)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    want &= sc > -np.inf
    np.testing.assert_array_equal(got, want)


# what the engine emitted for these requests before PR 51 (commit
# ec2ae14, the two-sort sampler): the masked sets and the gumbel draws
# are unchanged, so a seeded stream replays token for token
_PARENT_STREAMS = {
    "topk": (dict(temperature=0.8, top_k=32, seed=11),
             [116, 105, 22, 85, 36, 26, 29, 146, 196, 34, 62, 35, 241, 99,
              241, 97, 186, 213, 128, 12, 232, 217, 233, 193]),
    "topk_p": (dict(temperature=0.8, top_k=32, top_p=0.9, seed=11),
               [116, 145, 22, 85, 36, 26, 137, 236, 77, 64, 101, 39, 223,
                164, 58, 10, 161, 17, 10, 119, 57, 16, 251, 75]),
    "temp": (dict(temperature=1.3, seed=5),
             [255, 122, 80, 74, 15, 204, 183, 166, 92, 30, 189, 59, 76, 202,
              160, 115, 142, 180, 153, 196, 65, 73, 215, 60]),
    "nucleus": (dict(temperature=1.0, top_p=0.7, seed=3),
                [134, 212, 196, 42, 20, 238, 76, 44, 83, 121, 232, 169, 182,
                 81, 143, 123, 170, 9, 204, 181, 0, 26, 228, 130]),
}


@pytest.mark.parametrize("name", sorted(_PARENT_STREAMS))
def test_seeded_sampled_stream_replays_the_parents_tokens(model, name):
    kw, want = _PARENT_STREAMS[name]
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=128))
    got, _ = run_one(eng, name, [5, 6, 7, 11],
                     SamplingParams(max_tokens=24, **kw))
    assert got[0] == want
