"""The serving engine's BLOCK step (a family that generates by diffusion
over blocks, `models/sdar_moe.py`), CPU, the configuration's tiny preset
(blocks of 4 rows, 2 denoise passes, 3 layers): the transfer rule on
hand-made confidences; the engine's greedy and seeded streams, with the
pass that committed each token, against a host replay of the family's
generation loop on the program's own forward; `max_tokens`, a stop token,
an abort and a deadline inside a block; a slot used again; a pass sent
ahead; preemption at a block's edge; the counters; what the engine
refuses for the family; the server's events with `steps`, read by the
benchmark's own client."""

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models.sdar_moe import BlockSpec
from bigdl_tpu.ops.kvcache import init_cache_spec
from bigdl_tpu.serving import engine as engine_mod
from bigdl_tpu.serving.engine import (EngineConfig, LLMEngine,
                                      SamplingParams, _block_transfer)

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

CONFIG = "sdar-30b-a3b-ep4-int4"
SLOTS, MAX_SEQ, CHUNK = 4, 128, 16


def _tiny_config():
    from harness import spec

    doc = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    return spec.deep_update(doc, doc["tiny"])


@pytest.fixture(scope="module")
def model():
    from harness import weights_sdar_moe as weights

    return weights.build_model(_tiny_config(), 2 ** 31 + 7, merge=True)[0]


def _engine(model, **kw):
    kw.setdefault("max_batch", SLOTS)
    return LLMEngine(model, EngineConfig(
        max_seq=MAX_SEQ, prefill_chunk=CHUNK, prefill_bucket=CHUNK,
        sentinel=False, quality=False, **kw))


def _drain(eng, rids, steps_limit=2000):
    """Step the engine until every request of `rids` has finished:
    `{rid: (tokens, steps, events, reason)}`."""
    out = {r: ([], [], [], None) for r in rids}
    left = set(rids)
    for _ in range(steps_limit):
        if not left:
            break
        eng.step()
        for r in list(left):
            for o in eng.get_outputs(r):
                toks, steps, events, _ = out[r]
                if o.new_token_ids:
                    assert o.steps is not None \
                        and len(o.steps) == len(o.new_token_ids)
                    toks.extend(o.new_token_ids)
                    steps.extend(o.steps)
                    events.append(len(o.new_token_ids))
                if o.finished:
                    out[r] = (toks, steps, events, o.finish_reason)
                    left.discard(r)
    assert not left, left
    return out


# -- the transfer rule -------------------------------------------------------

M = True
_CASES = {
    # name: (rule, T, threshold, conf, masked, s) -> committed rows
    "threshold_met_by_three": (
        "low_confidence_dynamic", 2, 0.9,
        [0.95, 0.2, 0.99, 0.91], [M, M, M, M], 0, [0, 2, 3]),
    "threshold_met_by_none": (
        "low_confidence_dynamic", 2, 0.9,
        [0.5, 0.2, 0.7, 0.1], [M, M, M, M], 0, [0, 2]),
    "threshold_met_by_one_of_two_owed": (
        "low_confidence_dynamic", 2, 0.9,
        [0.5, 0.95, 0.7, 0.1], [M, M, M, M], 1, [1, 2]),
    "ties_go_to_the_lower_row": (
        "low_confidence_dynamic", 2, 0.9,
        [0.3, 0.3, 0.3, 0.3], [M, M, M, M], 0, [0, 1]),
    "tail_block_one_mask_left": (
        "low_confidence_dynamic", 2, 0.9,
        [0.99, 0.99, 0.99, 0.1], [False, False, False, M], 0, [3]),
    "tail_block_never_writes_a_given_row": (
        "low_confidence_static", 2, 0.9,
        [0.99, 0.2, 0.1, 0.98], [False, M, M, False], 0, [1, 2]),
    "three_passes_owe_two_one_one_first": (
        "low_confidence_static", 3, 0.9,
        [0.1, 0.4, 0.3, 0.2], [M, M, M, M], 0, [1, 2]),
    "three_passes_owe_two_one_one_second": (
        "low_confidence_static", 3, 0.9,
        [0.1, 0.4, 0.3, 0.2], [M, False, False, M], 1, [3]),
    "static_ignores_the_threshold": (
        "low_confidence_static", 2, 0.9,
        [0.95, 0.92, 0.99, 0.91], [M, M, M, M], 0, [0, 2]),
    "sequential_first_masked_rows": (
        "sequential", 2, 0.9,
        [0.1, 0.9, 0.2, 0.99], [False, M, M, M], 0, [1, 2]),
    "sequential_last_pass": (
        "sequential", 2, 0.9,
        [0.1, 0.9, 0.2, 0.99], [False, False, False, M], 1, [3]),
    "nothing_masked_commits_nothing": (
        "low_confidence_dynamic", 2, 0.9,
        [0.99, 0.99, 0.99, 0.99], [False] * 4, 0, []),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_block_transfer_on_hand_made_confidences(case):
    rule, passes, thr, conf, masked, s, want = _CASES[case]
    spec = BlockSpec(4, passes, 0, rule, thr)
    got = _block_transfer(jnp.asarray([conf], jnp.float32),
                          jnp.asarray([masked]), jnp.asarray([s], jnp.int32),
                          spec)
    assert [j for j in range(4) if bool(got[0, j])] == want
    assert not bool((got & ~jnp.asarray([masked])).any())


# -- the host replay of the family's loop -----------------------------------

def _commit(conf, masked, s, spec):
    """The transfer rule again, in plain Python (ISSUE 53, section 1)."""
    b = spec.length
    owed = b // spec.passes + (1 if s < b % spec.passes else 0)
    rows = [j for j in range(b) if masked[j]]
    k = min(owed, len(rows))
    if spec.rule == "sequential":
        return rows[:k]
    top = sorted(rows, key=lambda j: (-conf[j], j))[:k]
    if spec.rule == "low_confidence_static":
        return sorted(top)
    over = [j for j in rows if conf[j] > spec.threshold]
    return over if len(over) >= k else sorted(top)


def replay(model, prompt, max_tokens, temperature=0.0, seed=0, stop=()):
    """The family's generation loop on the program's own forward, one
    request alone in slot 0 of a slab of the engine's shapes:
    `(tokens, steps, events)`."""
    fam, cfg = model.family, model.config
    spec = fam.block_spec(cfg)
    b, mask = spec.length, spec.mask_id
    cspec = fam.cache_spec(cfg)
    fwd = jax.jit(fam.forward, static_argnums=1)
    pre = jax.jit(fam.prefill, static_argnums=1)
    whole = len(prompt) // b * b
    slab = init_cache_spec(cspec, SLOTS, MAX_SEQ, kv_cache_dtype="bf16",
                           per_slot_pos=True)
    bucket = CHUNK
    while bucket < len(prompt):
        bucket *= 2
    cache1 = init_cache_spec(cspec.unrolled(), 1, bucket,
                             kv_cache_dtype="bf16")
    for lo in range(0, whole, CHUNK):
        part = np.zeros((1, CHUNK), np.int32)
        part[0, :len(prompt[lo:lo + CHUNK])] = prompt[lo:lo + CHUNK]
        _, cache1 = pre(model.params, cfg, jnp.asarray(part), cache1)
    slab = slab.spliced(cache1, 0, whole)
    live = jnp.asarray([True] + [False] * (SLOTS - 1))
    ids = list(prompt[whole:]) + [-1] * (b - (len(prompt) - whole))
    given = len(prompt) - whole
    pos, passes, s, sent = whole, 0, 0, given
    tokens, steps, events, at = [], [], [], [0] * b
    while True:
        blk = np.full((SLOTS, b), mask, np.int32)
        blk[0] = [t if t >= 0 else mask for t in ids]
        lg, out = fwd(model.params, cfg, jnp.asarray(blk), slab.replace(
            pos=jnp.where(live, slab.pos, -1)))
        passes += 1
        if all(t >= 0 for t in ids):             # the storing pass
            slab = out.replace(pos=jnp.where(live, pos + b, 0))
            pos, ids, s, sent, given = pos + b, [-1] * b, 0, 0, 0
            continue
        slab = out.replace(pos=jnp.where(live, pos, 0))
        rows = lg[0].at[:, mask].set(-jnp.inf)
        first = pos - len(prompt)
        x0, conf = engine_mod._sample_rows(
            rows, jnp.full((b,), temperature, jnp.float32),
            jnp.zeros((b,), jnp.int32), jnp.ones((b,), jnp.float32),
            jnp.full((b,), seed, jnp.int32),
            jnp.maximum(first + jnp.arange(b), 0).astype(jnp.int32))
        conf = [float(c) for c in np.asarray(conf)]
        for j in _commit(conf, [t < 0 for t in ids], s, spec):
            ids[j] = int(x0[j])
            at[j] = passes
        s += 1
        run = sent
        while run < b and ids[run] >= 0:
            run += 1
        if run > sent:
            n = 0
            for j in range(sent, run):
                tokens.append(ids[j])
                steps.append(at[j])
                n += 1
                if ids[j] in stop or len(tokens) >= max_tokens:
                    events.append(n)
                    return tokens, steps, events
            events.append(n)
            sent = run


_REQUESTS = [
    # prompt length, max_tokens, temperature
    (16, 12, 0.0), (18, 11, 1.0), (7, 9, 0.0), (3, 14, 1.0), (33, 8, 0.0)]


def test_streams_equal_the_host_replay_greedy_and_seeded(model):
    """Five requests through four slots (whole blocks, tails of 2, 3 and
    1 rows, a prompt shorter than a block; greedy and seeded at
    temperature 1): every stream, the pass that committed each token and
    the tokens an event carried are the replay's."""
    eng = _engine(model)
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(1, 256, n)]
               for n, _, _ in _REQUESTS]
    for i, (p, (_, mt, temp)) in enumerate(zip(prompts, _REQUESTS)):
        eng.add_request(f"r{i}", p, SamplingParams(
            max_tokens=mt, temperature=temp, seed=40 + i))
    got = _drain(eng, [f"r{i}" for i in range(len(prompts))])
    seen_out_of_order = False
    for i, (p, (_, mt, temp)) in enumerate(zip(prompts, _REQUESTS)):
        toks, steps, events, reason = got[f"r{i}"]
        want = replay(model, p, mt, temp, 40 + i)
        assert (toks, steps, events) == want, i
        assert reason == "length" and len(toks) == mt
        assert 0 not in toks                  # the MASK id is never final
        seen_out_of_order |= steps != sorted(steps)
    # tokens became final out of sequence order somewhere, and an event
    # carried several of them
    assert seen_out_of_order
    assert max(max(g[2]) for g in got.values()) >= 2
    # the counters: every denoise pass committed 1-2 rows, every third
    # pass or so stored
    text = eng.registry.render()
    read = lambda name: float([x for x in text.splitlines()   # noqa: E731
                               if x.startswith(name)][0].split()[-1])
    denoise = read('bigdl_tpu_block_passes_total{kind="denoise"}')
    store = read('bigdl_tpu_block_passes_total{kind="store"}')
    committed = read("bigdl_tpu_block_tokens_committed_total")
    assert read("bigdl_tpu_blocks_total") == store > 0
    assert denoise <= committed <= 2 * denoise
    assert committed >= sum(len(g[0]) for g in got.values())
    assert 0.2 < store / (store + denoise) < 0.4
    assert read('bigdl_tpu_decode_steps_total{sent="ahead"}') > 0


def test_a_pass_sent_ahead_changes_no_stream(model):
    """The same request with passes going out one ahead and one at a
    time: the same stream; only the first engine sends ahead."""
    prompt = [int(t) for t in np.random.default_rng(5).integers(1, 256, 21)]
    streams = []

    def sent_ahead(eng):
        return float([x for x in eng.registry.render().splitlines()
                      if x.startswith('bigdl_tpu_decode_steps_total'
                                      '{sent="ahead"}')][0].split()[-1])

    for ahead in (True, False):
        eng = _engine(model)
        if not ahead:
            eng._may_lead = lambda *a: False
            eng._send_ahead = lambda: None
        before = sent_ahead(eng)        # the registry is the process's
        eng.add_request("r", prompt, SamplingParams(max_tokens=23))
        streams.append(_drain(eng, ["r"])["r"])
        assert (sent_ahead(eng) > before) == ahead
    assert streams[0] == streams[1]
    assert streams[0][:3] == replay(model, prompt, 23)


def test_max_tokens_and_a_stop_token_fall_inside_a_block(model):
    prompt = [int(t) for t in np.random.default_rng(6).integers(1, 256, 16)]
    full = replay(model, prompt, 16)[0]
    eng = _engine(model)
    for n in (1, 2, 3, 5, 6, 7):
        eng.add_request(f"n{n}", prompt, SamplingParams(max_tokens=n))
        toks, steps, _, reason = _drain(eng, [f"n{n}"])[f"n{n}"]
        assert (toks, reason) == (full[:n], "length")
    # a stop token cuts the stream at its first place in SEQUENCE order
    stop = full[5]
    cut = full.index(stop) + 1
    eng.add_request("s", prompt, SamplingParams(
        max_tokens=16, stop_token_ids=(stop,)))
    toks, _, _, reason = _drain(eng, ["s"])["s"]
    assert (toks, reason) == (full[:cut], "stop")
    assert toks == replay(model, prompt, 16, stop=(stop,))[0]
    # the slot is used again, by another request, at once
    other = [int(t) for t in np.random.default_rng(8).integers(1, 256, 10)]
    eng.add_request("o", other, SamplingParams(max_tokens=9))
    assert _drain(eng, ["o"])["o"][:3] == replay(model, other, 9)


def test_abort_and_deadline_end_a_block_in_flight(model):
    eng = _engine(model, max_batch=2)
    rng = np.random.default_rng(9)
    a, b_, c = ([int(t) for t in rng.integers(1, 256, n)]
                for n in (17, 9, 12))
    eng.add_request("a", a, SamplingParams(max_tokens=60))
    eng.add_request("b", b_, SamplingParams(max_tokens=60, max_time_ms=1e6))
    for _ in range(6):
        eng.step()
    slot_b = [s for s in eng.slots if s.req and s.req.request_id == "b"][0]
    assert slot_b.block is not None
    eng.abort_request("a")
    slot_b.req.deadline = time.time() - 1.0      # the deadline has passed
    got = _drain(eng, ["a", "b"])
    assert got["a"][3] == "abort" and got["b"][3] == "deadline"
    assert len(got["a"][0]) < 60 and len(got["b"][0]) < 60
    assert not any(s.active or s.block for s in eng.slots)
    # both slots serve again, and what they serve is sound
    eng.add_request("c", c, SamplingParams(max_tokens=10))
    assert _drain(eng, ["c"])["c"][:3] == replay(model, c, 10)


def test_preemption_acts_at_a_blocks_edge(model):
    """One slot, two requests, a stall guard of a few steps: the running
    request is preempted with a block in flight; what it had streamed
    opens its resumed block as given rows, nothing is streamed twice,
    the pass count goes on, and both requests end with their counts."""
    eng = _engine(model, max_batch=1, preempt_after_steps=4)
    rng = np.random.default_rng(10)
    p0, p1 = ([int(t) for t in rng.integers(1, 256, n)] for n in (13, 8))
    eng.add_request("x", p0, SamplingParams(max_tokens=22))
    for _ in range(3):
        eng.step()
    eng.add_request("y", p1, SamplingParams(max_tokens=6))
    got = _drain(eng, ["x", "y"])
    assert [len(got[r][0]) for r in ("x", "y")] == [22, 6]
    assert got["x"][3] == got["y"][3] == "length"
    pre = [x for x in eng.registry.render().splitlines()
           if x.startswith("bigdl_tpu_preemptions_total")]
    assert float(pre[0].split()[-1]) >= 1
    assert max(got["x"][1]) > 22 * 3 // 4     # the count went on
    # `y` was preempted in turn with a block in flight of which nothing
    # had been streamed: the block is generated again, to the same tokens
    assert got["y"][0] == replay(model, p1, 6)[0]


def test_what_the_engine_refuses_for_a_block_family(model):
    for kw, word in ((dict(speculative_tokens=1), "speculative_tokens"),
                     (dict(prefix_cache_entries=2), "prefix_cache_entries"),
                     (dict(kv_page_size=16), "kv_page_size"),
                     (dict(kv_cache_dtype="int8"), "kv_cache_dtype")):
        with pytest.raises((ValueError, NotImplementedError)) as e:
            _engine(model, **kw)
        assert word in str(e.value), (kw, str(e.value))
    with pytest.raises(ValueError, match="multiples"):
        LLMEngine(model, EngineConfig(max_batch=2, max_seq=126,
                                      prefill_chunk=16, prefill_bucket=16,
                                      sentinel=False, quality=False))
    eng = _engine(model)
    for params, word in (
            (SamplingParams(logprobs=1), "logprobs"),
            (SamplingParams(n=1, best_of=2), "logprobs"),
            (SamplingParams(repetition_penalty=1.2), "penalties")):
        with pytest.raises(ValueError, match=word):
            eng.add_request("q", [1, 2, 3], params)
    # a prompt that fills the slab but for its last block is served: the
    # one block that fits is generated, and the slab's end ends it
    eng.add_request("e", [1] * 125, SamplingParams(max_tokens=30))
    toks, _, _, reason = _drain(eng, ["e"])["e"]
    assert (len(toks), reason) == (3, "length")
    # a migration of a block in flight is refused, not attempted
    eng.add_request("m", [5, 6, 7, 8, 9], SamplingParams(max_tokens=30))
    for _ in range(3):
        eng.step()
    eng.request_migration("m")
    eng.step()
    assert eng.take_export("m") == {"unexportable": True}
    assert _drain(eng, ["m"])["m"][3] == "length"


def test_server_events_carry_steps_the_benchmarks_client_accepts(model):
    """`api_server` streams the tokens of one pass's run an event with
    `choices[0]["steps"]`, one integer a token; `loadgen.send_request`
    records them beside the tokens."""
    from harness import loadgen

    from bigdl_tpu.serving.api_server import OpenAIServer

    eng = _engine(model)
    server = OpenAIServer(eng, None)
    httpd = server.serve("127.0.0.1", 0, background=True)
    try:
        prompt = [int(t) for t in
                  np.random.default_rng(12).integers(1, 256, 18)]
        rec = loadgen.send_request(
            httpd.server_address[1],
            {"prompt": prompt, "max_tokens": 13, "temperature": 0.0,
             "top_k": 0, "index": 0}, time.monotonic() + 120.0)
        # a stop STRING holds text back from an event, whose `steps`
        # would then say tokens it does not deliver: refused with a 400
        import http.client
        import json

        conn = http.client.HTTPConnection("127.0.0.1",
                                          httpd.server_address[1], timeout=60)
        conn.request("POST", "/v1/completions", json.dumps(
            {"prompt": prompt, "max_tokens": 8, "stop": ["7 7"],
             "stream": True}), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        refused = (resp.status, json.loads(resp.read()))
        conn.close()
    finally:
        server.shutdown()
        httpd.server_close()
    assert rec["ok"], rec["error"]
    want = replay(model, prompt, 13)
    assert [int(t) for t in rec["tokens"]] == want[0]
    assert rec["steps"] == want[1]
    assert [k for _, k in rec["chunks"]] == want[2]
    assert max(k for _, k in rec["chunks"]) >= 2
    assert refused[0] == 400 and "stop strings" in refused[1]["error"]
