"""The traffic generator: the same work for every seed in another
order, schedules inside the window, lengths clipped as the file says."""

import json

import numpy as np
import pytest

import _paths
from harness import traffic as T

BIG_SEED = 2 ** 31 + 12345


def _load(name):
    with open(_paths.BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def chat():
    return _load("chat-steady")


def test_the_same_seed_gives_the_same_inputs(chat):
    a = T.window_plan(chat, BIG_SEED, 10.0, 32000)["requests"]
    b = T.window_plan(chat, BIG_SEED, 10.0, 32000)["requests"]
    assert a == b


def test_the_seed_changes_token_values_and_nothing_else(chat):
    a = T.window_plan(chat, BIG_SEED, 10.0, 32000)["requests"]
    c = T.window_plan(chat, 7, 10.0, 32000)["requests"]
    for key in ("due", "prompt_len", "max_tokens", "temperature", "top_k"):
        assert [r[key] for r in a] == [r[key] for r in c]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]


def test_the_files_order_seed_reorders_the_same_work(chat):
    a = T.window_plan(chat, 7, 10.0, 32000)["requests"]
    c = T.window_plan(dict(chat, order_seed=1), 7, 10.0, 32000)["requests"]
    assert [r["due"] for r in a] != [r["due"] for r in c]
    for key in ("prompt_len", "max_tokens", "temperature"):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in c)

    def gaps(rs):
        # the due times are a running sum of the gaps less the first
        # one, which is what is left of the window after the last
        due = sorted(r["due"] for r in rs)
        return np.sort(np.append(np.diff(due), 10.0 - due[-1]))

    assert np.allclose(gaps(a), gaps(c), atol=1e-9)


def test_open_loop_count_and_due_times_fill_the_window(chat):
    reqs = T.window_plan(chat, 3, 20.0, 32000)["requests"]
    assert len(reqs) == round(chat["arrivals"]["rate_rps"] * 20.0)
    due = [r["due"] for r in reqs]
    assert min(due) == 0.0 and max(due) < 20.0
    assert due == sorted(due)


@pytest.mark.parametrize("name", ["chat-steady", "docqa-shared",
                                  "batch-closed"])
def test_lengths_are_clipped_as_the_file_says(name):
    tr = _load(name)
    plan = T.window_plan(tr, 11, 10.0, 32000)
    reqs = T.all_requests(plan)
    p, o = tr["prompt_tokens"], tr["output_tokens"]
    assert all(p["min"] <= r["prompt_len"] <= p["max"] for r in reqs)
    assert all(o["min"] <= r["max_tokens"] <= o["max"] for r in reqs)
    for r in reqs:
        doc = len(plan["documents"][r["doc"]]) if r["doc"] >= 0 else 0
        assert len(r["prompt"]) == doc + r["prompt_len"]
        assert all(1 <= t < 32000 for t in r["prompt"][:8])


def test_closed_loop_has_one_list_per_client():
    tr = _load("batch-closed")
    plan = T.window_plan(tr, 5, 10.0, 32000)
    assert len(plan["clients"]) == tr["clients"] == 32
    assert all(len(c) == tr["requests_per_client"]
               for c in plan["clients"])


def test_documents_are_whole_pages_and_zipf_favours_the_first():
    tr = _load("docqa-shared")
    lens = T.document_lengths(tr["documents"])
    assert len(lens) == 24 and all(n % 512 == 0 for n in lens)
    assert lens.min() >= 3072 and lens.max() <= 5120
    reqs = T.window_plan(tr, 5, 40.0, 65024)["requests"]
    counts = np.bincount([r["doc"] for r in reqs], minlength=24)
    assert counts[0] == counts.max() and counts[0] >= 4 * counts[-1]


def test_warmup_asks_every_document_then_every_prompt_class():
    tr = _load("docqa-shared")
    plan = T.window_plan(tr, 5, 40.0, 65024)
    waves = T.warmup_plan(tr, plan, 5, 65024)
    first = [w[0]["doc"] for w in waves[:24]]
    assert first == list(range(24))
    warmed = {(len(plan["documents"][w[0]["doc"]]),
               T._pow2_ceil(len(w[0]["prompt"]))) for w in waves[24:]}
    for r in plan["requests"]:
        dlen = len(plan["documents"][r["doc"]])
        assert (dlen, T._pow2_ceil(len(r["prompt"]))) in warmed


def test_warmup_covers_every_sampling_variant_alone_and_together(chat):
    plan = T.window_plan(chat, 5, 10.0, 32000)
    waves = T.warmup_plan(chat, plan, 5, 32000)
    assert len(waves[-1]) == len(chat["sampling"])
    temps = {w[0]["temperature"] for w in waves if len(w) == 1}
    assert temps == {s["temperature"] for s in chat["sampling"]}


def test_train_batch_is_seeded_and_fully_masked():
    tr = _load("qlora-alpaca")
    a = T.train_batch(tr, BIG_SEED, 32000)
    b = T.train_batch(tr, BIG_SEED, 32000)
    assert (a["input_ids"] == b["input_ids"]).all()
    assert a["input_ids"].shape == (tr["micro_batch"], tr["seq_len"])
    assert a["attention_mask"].all()
