"""The ``generation`` role (PR 52): both comparisons of a serving run
with the reference go through a module of the configuration, so a
family whose step is not "one next token a sequence" joins by new files
and entries; and the records may say which step committed a token.

The tiny runs are the command itself on the CPU (``--tiny``), one child
process each, in ONE copy of the tree that holds four configurations
with a generation module each: a sound one, one whose reference rows
are a position off, one whose two sides name their rows differently,
one that compares the prefill's easy row alone."""

import http.server
import json
import threading
import time
import types

import numpy as np
import pytest

import _paths
import test_bench_run_tiny as tiny
from harness import common, loadgen, serve_runner, served, spec
from harness import traffic as traffic_mod

BENCH = spec.load_benchmark(_paths.ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]

# A generation module written for the test. The forced tokens go through
# the cache TWO rows a call (a causal family gives the same logits as
# one at a time), under a name of its own that the run's note line
# shows; the sequence's lengths are the module's (``SEQUENCE``); and
# ``served_gaps`` READS ``steps``: the tokens of a request's first step
# are the prefill's, and a program that says no steps gave one a step.
GENERATION = '''
"""Feeds the forced tokens {rows} rows a call through the cache."""
import numpy as np

from harness import serve_runner, served

ROWS_A_CALL = {rows}
SHIFT = {shift}
SEQUENCE = {sequence}
PREFILL_ALONE = {prefill_alone}


def program_rows(model, eng_cfg, ids, seed):
    import jax.numpy as jnp

    n = SEQUENCE[0]
    assert len(ids) == sum(SEQUENCE)
    row, (step, cache) = serve_runner.prefill_into_cache(
        model, eng_cfg, ids[:n], seed)
    if PREFILL_ALONE:
        return {{"prefill": row}}
    rows = []
    for i in range(n, len(ids), ROWS_A_CALL):
        toks = [int(t) for t in ids[i:i + ROWS_A_CALL]]
        lg, cache = step(jnp.asarray([toks], jnp.int32), cache)
        assert lg.shape[1] == len(toks) == ROWS_A_CALL
        rows.append(np.asarray(lg[0], np.float32))
    return {{"prefill": row, "two_rows_a_call": np.concatenate(rows)}}


def reference_rows(reference, canonical, arch, quant, ids):
    n = SEQUENCE[0]
    ref = np.asarray(reference.all_logits(
        canonical, arch, quant, [int(x) for x in ids],
        first=n - 1 - SHIFT))
    if PREFILL_ALONE:
        return {{"prefill": ref[0]}}
    return {{"prefill": ref[0], "{ref_name}": ref[1:len(ids) - n + 1]}}


def served_gaps(reference, canonical, arch, quant, sample, padded):
    tokens, steps = sample["tokens"], sample["steps"]
    if steps is None:
        steps = list(range(len(tokens)))
    if len(steps) != len(tokens) or list(steps) != sorted(steps):
        raise ValueError(f"steps {{steps}} beside {{len(tokens)}} tokens")
    g = served.request_gaps(reference, canonical, arch, quant,
                            sample["prompt"], tokens, padded)
    n_first = sum(1 for s in steps if s == steps[0])
    return {{"first": [float(x) for x in g[:n_first]],
            "later": [float(x) for x in g[n_first:]]}}
'''
DEFAULT_SEQUENCE = (serve_runner.REF_PROMPT_TOKENS,
                    serve_runner.DECODE_POSITIONS)
VARIANTS = {
    "pairs": dict(rows=2, shift=0, ref_name="two_rows_a_call",
                  sequence=(40, 12), prefill_alone=False),
    "shifted": dict(rows=2, shift=1, ref_name="two_rows_a_call",
                    sequence=DEFAULT_SEQUENCE, prefill_alone=False),
    "misnamed": dict(rows=2, shift=0, ref_name="decode",
                     sequence=DEFAULT_SEQUENCE, prefill_alone=False),
    "easy": dict(rows=2, shift=0, ref_name="two_rows_a_call",
                 sequence=DEFAULT_SEQUENCE, prefill_alone=True),
}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the tree with the four configurations added by new
    files and new entries; ``before`` is what was there."""
    root = tiny._copy_tree(tmp_path_factory.mktemp("generation"))
    before = tiny._digest(root)
    bench_dir = root / "benchmark"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    base = json.loads(
        (bench_dir / "configs" / "mistral-7b-int4.json").read_text())
    for name, how in VARIANTS.items():
        (bench_dir / "harness" / f"{name}_generation.py").write_text(
            GENERATION.format(**how))
        config = json.loads(json.dumps(base))
        config["why"] = "a generation module of its own, for the test"
        config["harness"] = {"generation": f"{name}_generation"}
        config["tiny"]["engine"]["max_batch"] = 2
        (bench_dir / "configs" / f"{name}-config.json").write_text(
            json.dumps(config))
        bench["configs"].append({
            "name": f"{name}-config", "source": config["source"],
            "file": f"benchmark/configs/{name}-config.json", "reduced": [],
            "why": "generation-role test"})
        bench["workloads"].append({
            "name": f"{name}-cell", "config": f"{name}-config",
            "traffic": "chat-steady", "chips": 1,
            "why": "generation-role test"})
        for m in bench["end_to_end"]:
            if "workloads" in m and m["name"] != "train_tokens_per_s":
                m["workloads"].append(f"{name}-cell")
        for m in bench["per_layer"]:
            if m["name"] == "decode_batch_mean":
                m["workloads"].append(f"{name}-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert spec.check_benchmark(bench) == []
    return {"root": root, "before": before}


def _tiny_run(tree, name, seed):
    r = tiny._run(tree["root"], "--workload", f"{name}-cell", "--seed",
                  str(seed), "--seconds", "2", "--trace", "0", "--tiny")
    assert r.returncode == 0, r.stderr[-2000:]
    out = r.stdout.strip().splitlines()
    line = json.loads(out[-1])
    assert tiny.LINE_KEYS <= set(line)
    note = [json.loads(x) for x in out[:-1]
            if x.startswith("{") and '"info": "run"' in x][0]
    return r, line, note


def test_a_generation_module_of_its_own_is_files_and_entries_only(tree):
    """(i) The configuration names ``generation`` alone and takes the
    three other defaults; its run is ``correct`` on rows the module
    named, and nothing that was there is touched."""
    cell = spec.Cell("pairs-cell", tree["root"])
    assert {r: m.__name__.split("@")[0] for r, m in cell.modules.items()} \
        == {"reference": "harness.reference", "weights": "harness.weights",
            "costs": "harness.costs",
            "generation": "harness.pairs_generation"}
    assert cell.modules["generation"].ROWS_A_CALL == 2
    # the sequence's lengths are the module's: 40 + 12 ids, so 13 rows
    # are owed and 13 are compared (the module checks what it was given)
    assert serve_runner.sequence_of(cell.modules["generation"]) == (40, 12)
    r, line, note = _tiny_run(tree, "pairs", 2 ** 31 + 52)
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert all(note["checks"].values())
    # the key the module chose says how the rows were fed
    assert list(note["reference_rel_l2"]) == ["prefill", "two_rows_a_call"]
    assert 0 < max(note["reference_rel_l2"].values()) \
        <= note["reference_tolerance"]
    compared = list(line["compared"])
    assert compared[:2] == ["reference_rel_l2.prefill",
                            "reference_rel_l2.two_rows_a_call"]
    assert tiny._compared_lines(r, line)[-1] == "checks failed: none"
    # (half of this mix's requests are sampled, and only greedy ones
    # are compared)
    assert 1 <= note["served"]["requests"] <= 4
    assert note["served"]["served_tokens"] >= note["served"]["requests"]
    after = tiny._digest(tree["root"])
    assert set(after) - set(tree["before"]) == {
        f"benchmark/{d}/{n}{suffix}" for n in VARIANTS
        for d, suffix in (("configs", "-config.json"),
                          ("harness", "_generation.py"))}
    assert all(after[k] == tree["before"][k] for k in tree["before"])


def test_reference_rows_one_position_off_are_not_correct(tree):
    """(ii) The same module with the reference's rows taken a position
    early: every other check passes, the logits comparison says no."""
    r, line, note = _tiny_run(tree, "shifted", 2 ** 31 + 53)
    assert line["correct"] is False
    assert sorted(k for k, ok in note["checks"].items() if not ok) == [
        "reference_within_tolerance"]
    assert min(note["reference_rel_l2"].values()) \
        > 3 * note["reference_tolerance"]
    assert tiny._compared_lines(r, line)[-1] == (
        "checks failed: ['reference_within_tolerance']")
    assert any(x.startswith("compared reference_rel_l2.two_rows_a_call")
               and x.endswith("OVER") for x in r.stderr.splitlines())


def test_two_sides_that_name_their_rows_apart_are_not_correct(tree):
    """(iii) A name only one side has compares nothing; the run says
    both sets and is not ``correct``."""
    r, line, note = _tiny_run(tree, "misnamed", 2 ** 31 + 54)
    assert line["correct"] is False
    assert sorted(k for k, ok in note["checks"].items() if not ok) == [
        "reference_within_tolerance"]
    rel = note["reference_rel_l2"]
    assert list(rel) == ["prefill", "two_rows_a_call", "decode"]
    assert rel["prefill"] is not None
    assert rel["two_rows_a_call"] is None and rel["decode"] is None
    tail = tiny._compared_lines(r, line)
    assert tail[-1] == "checks failed: ['reference_within_tolerance']"
    said = ("generation rows named apart: program_rows ['prefill', "
            "'two_rows_a_call'], reference_rows ['decode', 'prefill']")
    # said once, right before the numbers compared
    assert tail.count(said) == 1
    assert tail[tail.index(said) + 1].startswith("compared ")
    for name in ("two_rows_a_call", "decode"):
        assert line["compared"][f"reference_rel_l2.{name}"]["value"] is None
        assert any(x.startswith(f"compared reference_rel_l2.{name} ")
                   and x.endswith(": not read")
                   for x in r.stderr.splitlines())


def test_the_prefills_easy_row_alone_is_not_correct(tree):
    """(iii) Both sides give the prefill's last row and nothing that
    went through the cache: the one number compared is within its
    tolerance, and the run is not ``correct`` for the rows it owes."""
    r, line, note = _tiny_run(tree, "easy", 2 ** 31 + 56)
    assert line["correct"] is False
    assert sorted(k for k, ok in note["checks"].items() if not ok) == [
        "reference_within_tolerance"]
    assert list(note["reference_rel_l2"]) == ["prefill"]
    assert 0 < note["reference_rel_l2"]["prefill"] \
        <= note["reference_tolerance"]
    tail = tiny._compared_lines(r, line)
    assert all(x.endswith(": ok") for x in tail
               if x.startswith("compared "))
    assert tail[-1] == "checks failed: ['reference_within_tolerance']"
    said = [x for x in tail if x.startswith("generation rows")]
    assert len(said) == 1
    assert said[0].startswith("generation rows compared: 1 in ")
    assert said[0].endswith("9 owed (one of the prefill, one for every "
                            "id after the prompt)")


ROW, ROWS = np.zeros(5, np.float32), np.zeros((8, 5), np.float32)


@pytest.mark.parametrize("program,reference,says", [
    ({"prefill": ROW, "decode": ROWS}, {"prefill": ROW, "decode": ROWS},
     None),
    ({"block": np.zeros((9, 5))}, {"block": np.zeros((9, 5))}, None),
    ({"prefill": ROW, "a": ROWS}, {"prefill": ROW, "b": ROWS},
     "named apart: program_rows ['a', 'prefill'], reference_rows "
     "['b', 'prefill']"),
    ({"prefill": ROW, "decode": ROWS}, {"prefill": ROW, "decode": ROWS[:7]},
     "shaped apart (program, reference): {'decode': ((8, 5), (7, 5))}"),
    ({"prefill": ROW, "decode": ROWS}, {"prefill": ROW, "decode": ROWS.T},
     "shaped apart"),
    ({"prefill": ROW}, {"prefill": ROW}, "compared: 1 in"),
    ({"prefill": ROW, "decode": ROWS[:7]},
     {"prefill": ROW, "decode": ROWS[:7]}, "compared: 8 in"),
    ({}, {}, "compared: 0 in"),
], ids=["default", "one_block", "names", "fewer_rows_one_side",
        "transposed", "easy_row_alone", "a_position_short", "nothing"])
def test_rows_that_are_not_the_comparison_owed_are_a_fault(program,
                                                           reference, says):
    fault = serve_runner.rows_fault(program, reference, owed=9)
    if says is None:
        assert fault is None
    else:
        assert fault.startswith("generation rows ") and says in fault
    # a name the two sides shape apart is not compared, and raises nothing
    rel = serve_runner.rows_errors(program, reference)
    assert set(rel) == set(program) | set(reference)
    if says is None:
        assert all(v == 0.0 for v in rel.values())
    if says and "apart" in says:
        assert None in rel.values()


def test_a_module_may_lengthen_the_seeded_sequence_and_not_shorten_it():
    ns = types.SimpleNamespace
    assert serve_runner.sequence_of(ns()) == (32, 8)
    assert serve_runner.sequence_of(ns(SEQUENCE=(64, 64))) == (64, 64)
    for short in ((16, 8), (32, 4), (32.0, 8), (32,)):
        with pytest.raises(ValueError):
            serve_runner.sequence_of(ns(SEQUENCE=short))
    # the default's 40 ids are what they were, and a longer draw of the
    # same seed begins with them
    ids = serve_runner.check_ids(7, 1000)
    assert len(ids) == 40 and 1 <= ids.min() and ids.max() < 1000
    assert serve_runner.check_ids(7, 1000, 128)[:40].tolist() \
        == ids.tolist()


# -- (iv) the records: which step committed a token ------------------------

TOKENS = [5, 9, 13, 2, 7, 11]
EVENTS = [(TOKENS[0:1], [0]), (TOKENS[1:4], [1, 1, 2]), (TOKENS[4:6], [3, 3])]


class _StubSSE(http.server.BaseHTTPRequestHandler):
    """``/v1/completions`` as a stream of three events; the request's
    ``top_k`` says which of them carry ``steps``."""

    def log_message(self, *_a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        mode = {0: "none", 1: "all", 2: "some", 3: "short"}[body["top_k"]]
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        for i, (toks, steps) in enumerate(EVENTS):
            choice = {"index": 0, "text": " ".join(str(t) for t in toks),
                      "finish_reason": None}
            if mode == "all" or (mode == "some" and i != 1):
                choice["steps"] = steps
            if mode == "short" and i == 1:
                choice["steps"] = steps[:-1]
            self.wfile.write(b"data: " + json.dumps(
                {"choices": [choice]}).encode() + b"\n\n")
        self.wfile.write(b'data: {"choices": [{"index": 0, "text": "", '
                         b'"finish_reason": "length"}]}\n\n')
        self.wfile.write(b"data: [DONE]\n\n")


@pytest.fixture(scope="module")
def stub_port():
    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _StubSSE)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield httpd.server_address[1]
    httpd.shutdown()
    httpd.server_close()
    t.join(timeout=10)
    assert not t.is_alive()


def _send(port, top_k):
    return loadgen.send_request(
        port, {"prompt": [1, 2, 3], "max_tokens": len(TOKENS),
               "temperature": 0.0, "top_k": top_k, "index": 7},
        time.monotonic() + 30.0)


def test_events_with_steps_give_a_record_parallel_to_its_tokens(stub_port):
    rec = _send(stub_port, 1)
    assert rec["ok"] and rec["error"] is None
    assert [int(t) for t in rec["tokens"]] == TOKENS
    assert rec["steps"] == [0, 1, 1, 2, 3, 3]
    assert len(rec["steps"]) == len(rec["tokens"]) == rec["received"]
    assert [n for _, n in rec["chunks"]] == [1, 3, 2]


def test_events_without_steps_give_null_and_nothing_else_changes(stub_port):
    rec, said = _send(stub_port, 0), _send(stub_port, 1)
    assert rec["ok"] and rec["steps"] is None
    assert set(rec) == set(said)
    timed = {"sent", "first", "chunks", "steps"}
    assert {k: v for k, v in rec.items() if k not in timed} == {
        k: v for k, v in said.items() if k not in timed}


@pytest.mark.parametrize("top_k,says", [(2, "on every event or on none"),
                                        (3, "one integer a token")],
                         ids=["some_events_only", "fewer_than_tokens"])
def test_steps_on_some_tokens_only_are_that_requests_error(stub_port, top_k,
                                                           says):
    rec = _send(stub_port, top_k)
    assert not rec["ok"] and says in rec["error"]
    assert rec["steps"] is None
    # that request's alone: the next one on the same server is sound
    assert _send(stub_port, 1)["ok"]


# -- (v) the samples of the served comparison carry the steps --------------

def test_steps_reach_served_gaps_through_the_runners_samples(monkeypatch):
    """``_reference_checks`` reaches both comparisons through the
    cell's ``generation`` module alone, and each sample it hands
    ``served_gaps`` holds the request's prompt, its tokens and the
    ``steps`` of its record (None where the program said none)."""
    seed, seconds = 2 ** 31 + 55, 2.0
    cell = spec.Cell("mistral7b-batch-closed", _paths.ROOT, tiny=True)
    vocab = cell.modules["costs"].Dims.from_config(cell.config).vocab_size
    plan = traffic_mod.all_requests(traffic_mod.window_plan(
        cell.traffic, seed, seconds, vocab))
    records = []
    for i, req in enumerate(plan[:5]):
        n = int(req["max_tokens"])
        records.append({
            "ok": True, "greedy": True, "request": i,
            "prompt_tokens": len(req["prompt"]),
            "tokens": [str(1 + (i + j) % 7) for j in range(n)],
            "steps": None if i % 2 == 0 else [j // 2 for j in range(n)]})
    seen, calls = [], []

    def program_rows(model, eng_cfg, ids, seed):
        calls.append(("program_rows", model, len(ids)))
        return {"block": np.ones((17, 4), np.float32)}

    def reference_rows(reference, canonical, arch, quant, ids):
        calls.append(("reference_rows", canonical["made_from"], len(ids)))
        return {"block": np.full((17, 4), 1.01, np.float32)}

    def served_gaps(reference, canonical, arch, quant, sample, padded):
        seen.append((sample, padded))
        n = len(sample["tokens"])
        first = [0.5, 0.25][:n]
        return {"first": first, "later": [0.0] * (n - len(first))}

    cell.modules = {
        "reference": types.SimpleNamespace(
            tolerance=lambda config, kv: 0.05,
            served_gap_limits=lambda config, kv: {"prefill_gap_max": 1.0}),
        "weights": types.SimpleNamespace(
            canonical_params=lambda config, s: {"made_from": s}),
        "generation": types.SimpleNamespace(
            program_rows=program_rows, reference_rows=reference_rows,
            served_gaps=served_gaps, SEQUENCE=(40, 16))}
    # the runner frees the device between the two sides: not in a test
    # process, whose other tests hold arrays
    monkeypatch.setattr(common, "free_device", lambda: None)
    ref = serve_runner._reference_checks(
        cell, "the-model", records, seed, seconds,
        types.SimpleNamespace(vocab_size=vocab),
        common.WallClock(time.monotonic()))
    n_ids = 40 + 16          # the module's SEQUENCE, not the default's 40
    assert calls == [("program_rows", "the-model", n_ids),
                     ("reference_rows", seed, n_ids)]
    assert list(ref["rel"]) == ["block"]
    assert ref["rel"]["block"] == pytest.approx(0.01 / 1.01, rel=1e-4)
    assert ref["rows_fault"] is None     # 17 rows, 16 + 1 owed
    picked = served.pick_sample(records, seed)
    assert len(seen) == len(picked) == 4
    assert {p for _, p in seen} == {ref["served"]["padded"]}
    for (sample, _), rec in zip(seen, picked):
        assert set(sample) == {"prompt", "tokens", "steps"}
        assert sample["prompt"] == plan[rec["request"]]["prompt"]
        assert sample["tokens"] == [int(t) for t in rec["tokens"]]
        assert sample["steps"] == rec["steps"]
    # four of the five: records of both kinds are among them
    assert any(s["steps"] is None for s, _ in seen)
    assert any(s["steps"] is not None for s, _ in seen)
    # a module may give several first tokens a request: all are counted
    assert ref["served"]["prefill_gap_max"] == 0.5
    assert ref["served"]["served_tokens"] == sum(
        len(s["tokens"]) for s, _ in seen)


def test_a_served_gaps_that_reads_the_steps_of_its_sample(tree):
    """The test's module (the one whose tiny run is ``correct`` above,
    on records whose ``steps`` are None) reads ``sample["steps"]``: the
    tokens of the first step are the prefill's, however many they are,
    and steps out of order are refused."""
    gen = spec.Cell("pairs-cell", tree["root"]).modules["generation"]
    table = np.random.default_rng(52).normal(size=(64, 64))
    reference = types.SimpleNamespace(
        all_logits=lambda canonical, arch, quant, toks, first=0:
            table[np.asarray(toks)][first:])
    sample = {"prompt": [3, 9, 27, 17], "tokens": [5, 11, 2, 40, 8],
              "steps": None}
    plain = served.next_token_gaps(reference, None, {}, {}, sample, 64)
    assert len(plain["first"]) == 1 and len(plain["later"]) == 4
    assert max(plain["later"]) > 0.0
    everything = plain["first"] + plain["later"]
    for steps, n_first in ((None, 1), ([0, 1, 2, 3, 4], 1),
                           ([0, 0, 1, 1, 2], 2), ([4, 4, 4, 7, 7], 3)):
        got = gen.served_gaps(reference, None, {}, {},
                              dict(sample, steps=steps), 64)
        assert got["first"] == everything[:n_first]
        assert got["later"] == everything[n_first:]
    for steps in ([0, 2, 1, 3, 4], [0, 0, 1]):
        with pytest.raises(ValueError, match="beside 5 tokens"):
            gen.served_gaps(reference, None, {}, {},
                            dict(sample, steps=steps), 64)


def test_a_module_owes_a_gap_for_every_served_token():
    samples = [{"prompt": [1, 2], "tokens": [3, 4, 5], "steps": None}]
    with pytest.raises(ValueError, match="1 \\+ 1 gaps for 3 served"):
        served.compare(None, None, {}, {}, samples,
                       gaps=lambda *a: {"first": [0.0], "later": [0.0]})


# -- (vi) every cell has the four roles -------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_the_four_roles(cell):
    assert list(spec.MODULE_CONTRACT) == ["reference", "weights", "costs",
                                          "generation"]
    assert spec.MODULE_CONTRACT["generation"] == (
        "program_rows", "reference_rows", "served_gaps")
    c = spec.Cell(cell, _paths.ROOT)
    assert set(c.modules) == set(spec.MODULE_CONTRACT)
    # a configuration that names no module of its own takes the default
    # (a later cell may name one: that is what the role is for)
    if "generation" not in c.config.get("harness", {}):
        gen = c.modules["generation"]
        assert gen.__name__ == "harness.generation"
        assert gen.served_gaps is served.next_token_gaps
        assert serve_runner.sequence_of(gen) == (32, 8)


def test_the_default_module_is_the_helpers_that_were_there(monkeypatch):
    """The bodies exist once: the default ``generation`` calls the
    helpers the tests and the builders' tools import by name."""
    from harness import generation

    ids = serve_runner.check_ids(7, 100)
    said = []
    monkeypatch.setattr(
        serve_runner, "prefill_into_cache",
        lambda model, eng_cfg, prompt, seed: (
            said.append(("prefill", len(prompt))) or "row", "state"))
    monkeypatch.setattr(
        serve_runner, "decode_through_cache",
        lambda state, forced: said.append((state, len(forced))) or "rows")
    assert generation.program_rows("m", {}, ids, 7) == {
        "prefill": "row", "decode": "rows"}
    assert said == [("prefill", 32), ("state", 8)]
    reference = types.SimpleNamespace(
        all_logits=lambda canonical, arch, quant, toks, first=0:
            np.arange(len(toks) - first, dtype=np.float32)[:, None])
    rows = generation.reference_rows(reference, None, {}, {}, ids)
    assert list(rows) == ["prefill", "decode"]
    assert rows["prefill"].tolist() == [0.0]
    assert rows["decode"][:, 0].tolist() == [float(i) for i in range(1, 9)]
    # and ``logits_errors`` is those two put together
    assert serve_runner.logits_errors(
        reference, None, {}, {}, ids, rows["prefill"], rows["decode"]) == {
        "prefill": 0.0, "decode": 0.0}
