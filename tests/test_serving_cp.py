"""Engine-level context-parallel serving: a prompt
longer than one slot's max_seq admits anyway — its KV shards over the
mesh (parallel/cp.py) while the batched slots keep serving."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from bigdl_tpu.generation import generate_on_device
from bigdl_tpu.models import llama as llama_mod
from bigdl_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from bigdl_tpu.utils.testing import TINY_LLAMA, random_llama_params

MAX_SEQ = 64          # slot budget — the long prompt will exceed this


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
def setup():
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    model = FakeModel(
        random_llama_params(TINY_LLAMA, qtype="sym_int4", seed=0),
        TINY_LLAMA)
    return model, mesh


def drain(eng, rids, max_steps=600):
    got = {r: [] for r in rids}
    finished = set()
    for _ in range(max_steps):
        eng.step()
        for r in rids:
            for o in eng.get_outputs(r):
                got[r].extend(o.new_token_ids)
                if o.finished:
                    finished.add(r)
        if finished == set(rids):
            break
    assert finished == set(rids), f"unfinished: {set(rids) - finished}"
    return got


def plain_greedy(params, prompt, n):
    cache = llama_mod.new_cache(TINY_LLAMA, 1, 256)
    out, _ = generate_on_device(
        params, TINY_LLAMA, llama_mod.forward,
        jnp.asarray(np.asarray(prompt, np.int32)[None]), cache,
        max_new_tokens=n)
    return list(np.asarray(out)[0])


def test_long_prompt_streams_through_cp(setup):
    """83-token prompt through a max_seq=64 engine: sharded-KV path,
    greedy output identical to the single-device reference."""
    model, mesh = setup
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=MAX_SEQ,
                                        cp_max_seq=128), cp_mesh=mesh)
    prompt = [(7 * i) % TINY_LLAMA.vocab_size for i in range(1, 84)]
    assert len(prompt) + 1 > MAX_SEQ
    eng.add_request("long", prompt, SamplingParams(max_tokens=10))
    got = drain(eng, ["long"])
    assert got["long"] == plain_greedy(model.params, prompt, 10)


def test_cp_and_slots_serve_concurrently(setup):
    model, mesh = setup
    eng = LLMEngine(model, EngineConfig(max_batch=2, max_seq=MAX_SEQ,
                                        cp_max_seq=128), cp_mesh=mesh)
    long_prompt = list(range(2, 90))
    short_prompt = [5, 6, 7, 8]
    eng.add_request("long", long_prompt, SamplingParams(max_tokens=6))
    eng.add_request("short", short_prompt, SamplingParams(max_tokens=6))
    got = drain(eng, ["long", "short"])
    assert got["long"] == plain_greedy(model.params, long_prompt, 6)
    assert got["short"] == plain_greedy(model.params, short_prompt, 6)


def test_second_long_prompt_queues(setup):
    model, mesh = setup
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=MAX_SEQ,
                                        cp_max_seq=128), cp_mesh=mesh)
    p1 = list(range(1, 81))
    p2 = [(3 * i) % TINY_LLAMA.vocab_size for i in range(1, 71)]
    eng.add_request("a", p1, SamplingParams(max_tokens=4))
    eng.add_request("b", p2, SamplingParams(max_tokens=4))
    got = drain(eng, ["a", "b"])
    assert got["a"] == plain_greedy(model.params, p1, 4)
    assert got["b"] == plain_greedy(model.params, p2, 4)


def test_too_long_for_cp_still_rejected(setup):
    model, mesh = setup
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=MAX_SEQ,
                                        cp_max_seq=128), cp_mesh=mesh)
    with pytest.raises(ValueError, match="cp_max_seq"):
        eng.add_request("x", list(range(130)), SamplingParams())


def test_without_mesh_long_prompt_rejected(setup):
    model, _ = setup
    eng = LLMEngine(model, EngineConfig(max_batch=1, max_seq=MAX_SEQ))
    with pytest.raises(ValueError, match="max_seq"):
        eng.add_request("x", list(range(80)), SamplingParams())
