"""The resident decode step one step ahead (`LLMEngine._ahead`,
`_may_lead`), on the CPU at tiny widths: the plain step
(`engine_decode_resident`, a tiny llama) and the verify step of a
speculating engine (`engine_decode_resident_mtp`, the tiny DeepSeek-V3.2
of `tests/test_deepseek_v32.py`) share one bookkeeping, so every case
runs over both. The reference is the same engine held to one step at a
time (`_may_lead` never, no `_send_ahead`): the programs and their arguments are the same,
only the order of dispatch and read differs, and every stream has to be
token for token the same."""

import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bigdl_tpu.observability.metrics import MetricsRegistry
from bigdl_tpu.serving.engine import EngineConfig, LLMEngine, SamplingParams

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

KINDS = ("plain", "verify")
GREEDY = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)


def _tiny(config):
    from harness import spec

    doc = json.loads(
        (ROOT / "benchmark" / "configs" / f"{config}.json").read_text())
    return spec.deep_update(doc, doc["tiny"])


@pytest.fixture(scope="module")
def plain_model():
    from bigdl_tpu.utils.testing import tiny_random_model

    return tiny_random_model(seed=0)


@pytest.fixture(scope="module")
def verify_model():
    from harness import weights_deepseek_v32 as weights

    return weights.build_model(_tiny("deepseek-v32-ep8-int4"), 2 ** 31 + 3,
                               merge=True)[0]


@pytest.fixture(scope="module")
def afmoe_model():
    from harness import weights_afmoe as weights

    return weights.build_model(_tiny("trinity-mini-ep4-int4"), 2 ** 31 + 3,
                               merge=True)[0]


@pytest.fixture(scope="module")
def evabyte_model():
    from harness import weights_evabyte as weights

    return weights.build_model(_tiny("evabyte-int4"), 2 ** 31 + 39,
                               merge=True)[0]


@pytest.fixture
def model(request, kind):
    return request.getfixturevalue(f"{kind}_model")


def _engine(model, kind, sync=False, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq", 256)
    kw.setdefault("prefill_chunk", 32)
    kw.setdefault("prefix_cache_entries", 0)
    eng = LLMEngine(model, EngineConfig(
        speculative_tokens=int(kind == "verify"), sentinel=False,
        quality=False, **kw),
        registry=MetricsRegistry())
    if sync:
        # one step at a time: the reference every stream is held to
        eng._may_lead = lambda active, rows_a_slot: False
        eng._send_ahead = lambda: None
    return eng


def _prompts(n, seed=1, lo=33, hi=110, vocab=250):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, vocab, k)))
            for k in rng.integers(lo, hi, n)]


def _counter(eng, series):
    for line in eng.registry.render().splitlines():
        if line.startswith(series + " "):
            return float(line.split()[-1])
    raise AssertionError(f"{series} not on /metrics")


def _sent(eng):
    return {st: _counter(eng, 'bigdl_tpu_decode_steps_total{sent="%s"}' % st)
            for st in ("ahead", "in_step")}


def _collect(eng, got, outs=None):
    for rid in got:
        for o in eng.get_outputs(rid):
            got[rid].extend(o.new_token_ids)
            if outs is not None:
                outs.setdefault(rid, []).append(o)


def _drive(eng, got, before_step=None, outs=None, limit=600):
    """Step the engine dry. `before_step(n)` runs ahead of step n."""
    n = 0
    while eng.has_unfinished():
        if before_step is not None:
            before_step(n)
        eng.step()
        n += 1
        _collect(eng, got, outs)
        assert n < limit
    return n


def _spy(monkeypatch, eng, order, mark, name):
    """Append `mark` to `order` at every call of the engine's program
    `name`: the order in which programs are dispatched."""
    inner = getattr(eng, name)

    def call(*a, **k):
        order.append(mark)
        return inner(*a, **k)
    monkeypatch.setattr(eng, name, call)


def _mixed(i, max_tokens=20):
    """Even requests greedy, odd ones seeded at T 0.8 top-k 32."""
    if i % 2 == 0:
        return SamplingParams(temperature=0.0, max_tokens=max_tokens + i,
                              ignore_eos=True)
    return SamplingParams(temperature=0.8, top_k=32, seed=100 + i,
                          max_tokens=max_tokens + i, ignore_eos=True)


@pytest.mark.parametrize("kind", KINDS)
def test_streams_are_those_of_one_step_at_a_time(model, kind):
    """Greedy and seeded sampled requests in one batch, a fourth admitted
    beside the three while a step is ahead: token for token the streams
    of the engine that never leads, and most decode programs went out
    ahead."""
    prompts = _prompts(4)
    streams = {}
    for sync in (True, False):
        eng = _engine(model, kind, sync=sync)
        for i, p in enumerate(prompts[:3]):
            eng.add_request(f"r{i}", p, _mixed(i))
        got = {f"r{i}": [] for i in range(4)}

        def late(n):
            if n == 14:
                eng.add_request("r3", prompts[3], _mixed(3))
        _drive(eng, got, late)
        streams[sync] = got
        sent = _sent(eng)
        if sync:
            assert sent["ahead"] == 0 and eng._ahead is None
        else:
            assert sent["ahead"] > 2 * sent["in_step"]
    assert streams[False] == streams[True]
    if kind == "plain":
        assert [len(v) for v in streams[False].values()] == [20, 21, 22, 23]


def _late_first(stream, lo, hi):
    """The last place in `stream[lo:hi]` whose token occurs nowhere
    before it: a stop token that ends the stream there and no earlier."""
    for k in range(hi - 1, lo - 1, -1):
        if stream[k] not in stream[:k]:
            return k
    raise AssertionError(f"every token of {stream[lo:hi]} came earlier")


@pytest.mark.parametrize("how", ["stop", "abort", "deadline"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_request_that_ends_under_a_step_ahead_is_read_no_further(
        model, kind, how):
    """A request ends (a stop token, an abort, a deadline) in a step
    that had sent the next one out with its slot in it: what that step
    computed for the slot is never read, its neighbours stream on
    unchanged, and a request admitted into the slot afterwards streams
    what it streams alone."""
    prompts = _prompts(4, seed=11)
    late = prompts[3][:20]
    ref = _engine(model, kind, sync=True)
    want = ref.generate(prompts[:3] + [late], GREEDY)
    # the request whose stream holds a new token latest, so that all
    # three decode, a step ahead, when it ends
    cuts = {}
    for j, w in enumerate(want[:3]):
        try:
            cuts[j] = _late_first(w, 10, 20)
        except AssertionError:
            pass
    j = max(cuts, key=cuts.get)
    ends, cut = f"a{j}", cuts[j] + 1
    eng = _engine(model, kind)
    for i, p in enumerate(prompts[:3]):
        eng.add_request(f"a{i}", p, dataclasses.replace(
            GREEDY, stop_token_ids=[want[j][cut - 1]]
            if (i, how) == (j, "stop") else []))
    got = {f"a{i}": [] for i in range(4)}
    state = {"under_lead": False, "late": False, "ended": False}

    def slot():
        return next(i for i, s in enumerate(eng.slots)
                    if s.req is not None and s.req.request_id == ends)

    def held():
        return eng._ahead is not None and any(
            r.request_id == ends for _, r in eng._ahead[0])

    def before(n):
        if how != "stop" and not state["ended"] and held() \
                and len(got[ends]) >= 10:
            state["ended"] = state["under_lead"] = True
            if how == "abort":
                eng.abort_request(ends)
            else:
                eng.slots[slot()].req.deadline = time.time() - 1.0
                eng._any_deadline = True

    outs = {}
    n = 0
    while eng.has_unfinished():
        before(n)
        eng.step()
        n += 1
        _collect(eng, got, outs)
        if not state["late"] and any(o.finished for o in outs.get(ends, [])):
            if how == "stop":
                # the step that found the stop token had sent the next
                # one out already, the slot in it
                state["under_lead"] = held()
            eng.add_request("a3", late, GREEDY)
            state["late"] = True
        assert n < 400
    assert state["under_lead"] and state["late"]
    assert outs[ends][-1].finish_reason == how
    if how == "stop":
        assert got[ends] == want[j][:cut]
    else:
        assert 10 <= len(got[ends]) < 24
        assert got[ends] == want[j][:len(got[ends])]
    for i in set(range(3)) - {j}:
        assert got[f"a{i}"] == want[i]
    assert got["a3"] == want[3]
    # every slot's position went back to 0, behind the step in vain too
    assert np.asarray(eng.cache.pos).tolist() == [0, 0, 0, 0]


@pytest.mark.parametrize("chunks", [1, 3])
@pytest.mark.parametrize("kind", KINDS)
def test_a_prompt_admitted_beside_a_led_stream_never_puts_two_chunks_between_two_steps(
        model, kind, chunks, monkeypatch):
    """A one-chunk and a three-chunk prompt arrive while a stream is led:
    middle chunks go out under the step ahead (the device alternates
    chunk and step), the last chunk's first token is waited for only
    after the step in flight is read, and the order of the dispatched
    programs never holds two chunks between two decode steps. Both
    streams are those of one step at a time."""
    prompts = _prompts(2, seed=5)
    newcomer = _prompts(1, seed=6, lo=32 * chunks - 10,
                        hi=32 * chunks - 2)[0]
    want = {}
    ref = _engine(model, kind, sync=True)
    want["s0"], want["s1"] = ref.generate(
        [prompts[0], newcomer], dataclasses.replace(GREEDY, max_tokens=40))
    eng = _engine(model, kind)
    order = []

    _spy(monkeypatch, eng, order, "d", "_decode_resident_mtp"
         if kind == "verify" else "_decode_resident")
    _spy(monkeypatch, eng, order, "c",
         "_prefill_mtp" if kind == "verify" else "_prefill")
    sp = dataclasses.replace(GREEDY, max_tokens=40)
    eng.add_request("s0", prompts[0], sp)
    got = {"s0": [], "s1": []}
    reads = []                  # (programs dispatched, tokens s0 has) a step

    def before(n):
        if len(got["s0"]) == 8 and "s1" not in reads:
            assert eng._ahead is not None
            eng.add_request("s1", newcomer, sp)
            reads.append("s1")
            del order[:]
    _drive(eng, got, before)
    assert got == want
    text = "".join(order)
    assert text.count("c") == chunks
    # from the newcomer's first chunk on: chunk and step alternate, and
    # where the last chunk went out behind a step in flight the next
    # step is dispatched only once its first token is read
    first, last = text.index("c"), text.rindex("c")
    assert "cc" not in text
    assert set(text[first:last + 1].split("c")) <= {"", "d"}
    assert text[last + 1:last + 3] == "dd"      # in step, then ahead again
    assert _sent(eng)["ahead"] > 25


@pytest.mark.parametrize("kind,what", [
    ("plain", "preempt"), ("verify", "preempt"), ("plain", "export")])
def test_preemption_and_export_go_by_the_hosts_count(model, kind, what):
    """With a step in flight the device is one step further on than the
    host: a preempted request resumes from the host's tokens, an exported
    one carries the host's tokens and `kv_len`, and either streams on as
    it does when one step at a time is disturbed at the same token. (A
    speculating engine takes no prefix snapshot: its export resumes by
    recompute, which its preemption covers.)"""
    prompts = _prompts(2, seed=21)
    sp = dataclasses.replace(GREEDY, max_tokens=30)
    runs, at = {}, None
    for sync in (False, True):
        eng = _engine(model, kind, sync=sync)
        for rid, p in zip(("p0", "p1"), prompts):
            eng.add_request(rid, p, sp)
        got = {"p0": [], "p1": []}
        done = {}

        def before(n):
            if not done and all(s.active for s in eng.slots[:2]) and (
                    len(got["p1"]) >= at if sync else
                    eng._ahead is not None and len(got["p1"]) >= 8):
                done["at"] = len(got["p1"])
                if what == "preempt":
                    eng._preempt()          # the latest arrival: p1
                else:
                    eng.request_migration("p1")
            elif what == "export" and done and "state" not in done:
                st = eng.take_export("p1")
                if st is not None:
                    done["state"] = st
                    eng.resume_local("p1")
        _drive(eng, got, before)
        at = done["at"]
        runs[sync] = got
        assert [len(v) for v in got.values()] == [30, 30]
        if what == "export":
            st = done["state"]
            assert st["generated"] == got["p1"][:at]
            assert st["kv_len"] == len(prompts[1]) + at - 1
        else:
            assert _counter(eng, "bigdl_tpu_preemptions_total") == 1
    assert runs[False] == runs[True]


@pytest.mark.parametrize("kind", KINDS)
def test_a_newcomer_that_needs_the_host_sampler_joins_once_the_step_ahead_is_read(
        model, kind):
    """A request with logprobs arrives beside a led stream: the step in
    flight is read first, the newcomer's first token is waited for after
    it, and from then on every step is the host-sampled one until the
    newcomer is gone; then the engine leads again. Streams and logprobs
    are those of one step at a time."""
    prompts = _prompts(2, seed=31, lo=20, hi=30)
    a = dataclasses.replace(GREEDY, max_tokens=40)
    b = dataclasses.replace(GREEDY, max_tokens=6, logprobs=1)
    runs = {}
    for sync in (True, False):
        eng = _engine(model, kind, sync=sync)
        eng.add_request("h0", prompts[0], a)
        got, outs, seen = {"h0": [], "h1": []}, {}, []

        def before(n):
            if len(got["h0"]) >= 8 and not seen:
                seen.append(eng._ahead is not None)
                eng.add_request("h1", prompts[1], b)
            if eng.slots[1].active:
                # no step goes out ahead while the host samples a slot
                assert eng._ahead is None
        _drive(eng, got, before, outs)
        lps = [e for o in outs["h1"] for e in (o.logprobs or [])]
        assert len(lps) == 6
        runs[sync] = (got, [round(e.logprob, 4) for e in lps])
        assert seen == [not sync]
        if not sync:
            assert eng._ahead is not None or _sent(eng)["ahead"] > 20
    assert runs[False][0] == runs[True][0]
    assert runs[False][1] == pytest.approx(runs[True][1], abs=2e-3)


@pytest.mark.parametrize("kind", KINDS)
def test_a_fault_clause_stops_the_engine_leading(model, kind, monkeypatch):
    """A fault clause that comes live while a step is ahead, under the
    middle chunks of an admission, takes effect one step later: that
    step is read, then every step is dispatched in the step that waits
    for it, through the program that leaves the logits where the clause
    can reach them. The chunk that went out beside the step that was
    read is followed by a decode program before the next chunk goes."""
    from bigdl_tpu.robustness.faults import FaultInjector, parse_fault_spec

    prompts = _prompts(2, seed=41, lo=20, hi=30) + _prompts(
        1, seed=42, lo=120, hi=127)
    sp = dataclasses.replace(GREEDY, max_tokens=30)
    want = _engine(model, kind, sync=True).generate(prompts, sp)
    eng = _engine(model, kind)
    order = []

    for name in ("_decode", "_decode_resident") + (
            ("_decode_hidden", "_decode_resident_mtp")
            if kind == "verify" else ()):
        _spy(monkeypatch, eng, order, "d", name)
    _spy(monkeypatch, eng, order, "c",
         "_prefill_mtp" if kind == "verify" else "_prefill")
    for i, p in enumerate(prompts[:2]):
        eng.add_request(f"f{i}", p, sp)
    got = {"f0": [], "f1": [], "f2": []}
    live = {}

    def before(n):
        a = eng._admitting
        if len(got["f0"]) == 8 and not eng.waiting and a is None \
                and not eng.slots[2].active:
            eng.add_request("f2", prompts[2], sp)
            del order[:]
        if not live and eng._ahead is not None and a is not None \
                and a.req.request_id == "f2" and 32 <= a.consumed < 96:
            live.update(_sent(eng), tokens=len(got["f0"]))
            eng.faults = FaultInjector(
                parse_fault_spec("nan_logits@at_step=100000"),
                on_fire=eng._on_fault_fired)
        elif live:
            assert eng._ahead is None or len(got["f0"]) == live["tokens"]
    _drive(eng, got, before)
    assert live and [got["f0"], got["f1"], got["f2"]] == want
    sent = _sent(eng)
    assert sent["ahead"] == live["ahead"]
    assert sent["in_step"] - live["in_step"] >= 15
    assert _counter(eng, "bigdl_tpu_decode_steps_vain_total") == 0
    text = "".join(order)
    assert text.count("c") == 4 and "cc" not in text, text


@pytest.mark.parametrize("family", ["afmoe", "evabyte"])
def test_a_ring_and_a_summary_cache_reuse_a_slot_that_ended_under_a_step_ahead(
        request, family):
    """Tiny Trinity (K/V rings of 32 columns) and tiny EvaByte (a window
    and one summary a chunk of 16): a request ends by a stop token under
    a step ahead, which wrote one more ring column, or closed one more
    chunk's summary, for a slot that was over; the request admitted into
    the slot next, and the neighbour, stream what one step at a time
    streams."""
    model = request.getfixturevalue(f"{family}_model")
    geometry = dict(max_batch=2, max_seq=256 if family == "evabyte" else 128,
                    prefill_chunk=16 if family == "evabyte" else 32)
    vocab = 320 if family == "evabyte" else 256
    # lengths that wrap the ring of 32 and cross EvaByte's chunks of 16
    prompts = _prompts(3, seed=51, lo=40, hi=60, vocab=vocab)
    sp = dataclasses.replace(GREEDY, max_tokens=44)
    probe = _engine(model, "plain", sync=True, **geometry)
    stream = probe.generate([prompts[1]], sp)[0]
    cut = _late_first(stream, 12, 30) + 1
    stop = stream[cut - 1]
    runs = {}
    for sync in (True, False):
        eng = _engine(model, "plain", sync=sync, **geometry)
        eng.add_request("g0", prompts[0], sp)
        eng.add_request("g1", prompts[1], dataclasses.replace(
            sp, stop_token_ids=[stop]))
        got = {"g0": [], "g1": [], "g2": []}
        outs, seen = {}, {}

        def before(n):
            if "g1" in outs and outs["g1"][-1].finished and not seen:
                seen["under"] = eng._ahead is not None and any(
                    i == 1 for i, _ in eng._ahead[0])
                eng.add_request("g2", prompts[2], sp)
        _drive(eng, got, before, outs)
        assert seen["under"] == (not sync)
        assert got["g1"] == stream[:cut]
        assert len(got["g0"]) == len(got["g2"]) == 44
        runs[sync] = got
        if not sync:
            assert _sent(eng)["ahead"] > 40
    assert runs[False] == runs[True]


@pytest.mark.parametrize("kind", KINDS)
def test_the_counter_counts_ahead_in_step_and_vain_steps(model, kind,
                                                          monkeypatch):
    """`bigdl_tpu_decode_steps_total{sent}` counts every decode program
    where it is dispatched, `bigdl_tpu_decode_steps_vain_total` the one
    sent ahead that nobody read: both requests are aborted under it."""
    prompts = _prompts(2, seed=61, lo=20, hi=30)
    eng = _engine(model, kind)
    name = "_decode_resident_mtp" if kind == "verify" else "_decode_resident"
    calls = []
    inner = getattr(eng, name)
    monkeypatch.setattr(eng, name,
                        lambda *a, **k: calls.append(1) or inner(*a, **k))
    for i, p in enumerate(prompts):
        eng.add_request(f"v{i}", p, dataclasses.replace(
            GREEDY, max_tokens=200))
    got = {"v0": [], "v1": [], "v2": []}
    for _ in range(12):
        eng.step()
    _collect(eng, got)
    assert eng._ahead is not None and len(eng._ahead[0]) == 2
    assert _counter(eng, "bigdl_tpu_decode_steps_vain_total") == 0
    for rid in ("v0", "v1"):
        eng.abort_request(rid)
    eng.step()                  # no slot is live: the step ahead waits
    assert not eng.has_unfinished() and eng._ahead is not None
    eng.add_request("v2", prompts[0], dataclasses.replace(
        GREEDY, max_tokens=5))
    _drive(eng, got)
    assert len(got["v2"]) == 5
    assert _counter(eng, "bigdl_tpu_decode_steps_vain_total") == 1
    sent = _sent(eng)
    assert sent["ahead"] + sent["in_step"] == len(calls)
    assert sent["ahead"] >= 10 and sent["in_step"] >= 2


SAMPLER_BATCHES = {
    # path: the sampling parameters of the two requests of a batch
    "greedy": (GREEDY, GREEDY),
    "topk": (GREEDY, dataclasses.replace(GREEDY, temperature=0.8, top_k=32,
                                         seed=3)),
    "nucleus": (dataclasses.replace(GREEDY, temperature=0.8, top_k=32,
                                    seed=3),
                dataclasses.replace(GREEDY, temperature=1.0, top_p=0.9,
                                    seed=4)),
}


def _sampler_steps(eng):
    return {pt: _counter(eng,
                         'bigdl_tpu_sampler_steps_total{path="%s"}' % pt)
            for pt in ("greedy", "topk", "nucleus")}


@pytest.mark.parametrize("path", sorted(SAMPLER_BATCHES))
@pytest.mark.parametrize("kind", KINDS)
def test_the_sampler_counter_names_the_path_of_every_decode_program(
        model, kind, path):
    """`bigdl_tpu_sampler_steps_total{path}` is bumped once a decode
    program, beside `bigdl_tpu_decode_steps_total`, by the predicate the
    device evaluates: all three values render before traffic, and a
    batch counts under its own path alone while both requests run."""
    eng = _engine(model, kind, max_batch=2)
    assert _sampler_steps(eng) == {"greedy": 0, "topk": 0, "nucleus": 0}
    prompts = _prompts(2, seed=71, lo=20, hi=30)
    got = {}
    for i, (p, sp) in enumerate(zip(prompts, SAMPLER_BATCHES[path])):
        eng.add_request(f"s{i}", p, sp)
        got[f"s{i}"] = []
    _drive(eng, got)
    assert all(len(t) == GREEDY.max_tokens for t in got.values())
    steps, sent = _sampler_steps(eng), _sent(eng)
    assert sum(steps.values()) == sent["ahead"] + sent["in_step"]
    # the first request decodes alone while the second is admitted, and
    # a verify step may end the two at different steps: those programs
    # count under the path of whoever is live (greedy for s0 of the
    # greedy and topk batches, topk for s0 of the nucleus batch)
    assert steps[path] >= 10
    alone = {"greedy": "greedy", "topk": "greedy", "nucleus": "topk"}[path]
    assert {pt for pt, n in steps.items() if n} <= {path, alone}, steps


def test_sampler_sortfree_share_reduces_a_scrape_pair_to_the_share():
    """`layer_metrics/sampler_sortfree_share.json`: decode programs that
    sampled and sorted nothing over all decode programs of the window;
    nothing on a program without the counter (the parent of PR 51)."""
    from harness import layer_metrics, promtext

    path = ROOT / "benchmark" / "layer_metrics" / "sampler_sortfree_share.json"
    series = "bigdl_tpu_sampler_steps_total"
    start = "\n".join(f'{series}{{path="{p}"}} {n}' for p, n in
                      (("greedy", 10), ("topk", 5), ("nucleus", 0)))
    end = "\n".join(f'{series}{{path="{p}"}} {n}' for p, n in
                    (("greedy", 30), ("topk", 185), ("nucleus", 0)))
    obs = {"counters_start": promtext.parse(start),
           "counters_end": promtext.parse(end)}
    assert layer_metrics.read_metric(path, obs) == pytest.approx(90.0)
    assert promtext.delta(obs["counters_start"], obs["counters_end"],
                          series, {"path": "nucleus"}) == 0
    other = "bigdl_tpu_decode_steps_total"
    old = {"counters_start": promtext.parse(f'{other}{{sent="ahead"}} 10'),
           "counters_end": promtext.parse(f'{other}{{sent="ahead"}} 40')}
    assert layer_metrics.read_metric(path, old) is None
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in doc["per_layer"]
                if m["name"] == "sampler_sortfree_share"]
    file = json.loads(path.read_text())
    assert {k: entry[k] for k in ("unit", "layer", "moves", "source")} == {
        k: file[k] for k in ("unit", "layer", "moves", "source")}
    assert entry["better"] == "higher" and len(entry["workloads"]) == 9
    greedy_cells = {"mistral7b-batch-closed", "chatglm2-6b-docqa-shared",
                    "mistral7b-qlora-alpaca"}
    assert not greedy_cells & set(entry["workloads"])
    assert set(entry["workloads"]) <= {w["name"] for w in doc["workloads"]}
