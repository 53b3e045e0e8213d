"""The new cell's command at a tiny size on the CPU: a traced `--tiny`
run of `sdar-30b-ep4-fixedlen-closed` is `correct` through its own
`generation` module (every record carries `steps`, every served token
is replayed at the pass that committed it), compares each number with
its limit, and reports the counters' per-layer metrics (counts only: a
CPU run yields no time and no share of the device)."""

import json

import _paths
from test_bench_run_tiny import LINE_KEYS, _compared_lines, _run, _wall

CELL = "sdar-30b-ep4-fixedlen-closed"


def test_tiny_run_of_the_fixedlen_cell_is_correct():
    r = _run(_paths.ROOT, "--workload", CELL, "--seed", str(2 ** 31 + 9),
             "--seconds", "3", "--trace", "1", "--tiny")
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(line) == LINE_KEYS
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 4
    metrics = line["metrics"]
    assert set(metrics) >= {"prefill_chunks_per_step",
                            "moe_experts_hit_share",
                            "moe_held_assignment_share",
                            "decode_ahead_share", "sampler_sortfree_share",
                            "block_tokens_per_denoise_pass",
                            "block_store_pass_share"}
    # the tiny share: 2 of 8 experts held, 3 of 8 chosen a row
    assert 15.0 < metrics["moe_held_assignment_share"]["value"] < 35.0
    # two denoise passes commit a block of four; the prompts' tail
    # blocks open with fewer MASK rows
    assert 1.5 < metrics["block_tokens_per_denoise_pass"]["value"] <= 2.0
    # a request of 4-12 tokens ends before its last block is stored
    assert 10.0 < metrics["block_store_pass_share"]["value"] < 34.0
    note = [json.loads(x) for x in r.stdout.strip().splitlines()[:-1]
            if x.startswith("{") and '"info": "run"' in x][0]
    assert set(note["reference_rel_l2"]) == {"prefill", "block"}
    assert 0 < max(note["reference_rel_l2"].values()) \
        <= note["reference_tolerance"]
    assert note["served"]["requests"] == 4
    assert note["served"]["served_tokens"] >= 16
    assert note["compile_table"]["engine_block_resident"]["compiles"] >= 1
    records = json.loads((_paths.BENCH / ".out" / CELL
                          / "records.json").read_text())["records"]
    done = [x for x in records if x["ok"]]
    assert done and all(
        x["steps"] and len(x["steps"]) == len(x["tokens"]) for x in done)
    # several tokens an event, and tokens final out of sequence order
    assert max(k for x in done for _, k in x["chunks"]) >= 2
    assert any(x["steps"] != sorted(x["steps"]) for x in done)
    layer = [json.loads(x) for x in r.stdout.strip().splitlines()[:-1]
             if x.startswith("{") and '"info": "layer_check"' in x][0]
    assert layer["within"] is True and len(layer["found"]) == 5
    assert layer["found"]["transfer_apart"] == 0.0
    assert layer["checked_layers"] == [0]
    assert note["checks"]["configuration_layer_check"] is True
    # the layer check's five numbers and the harness's five, together at
    # the end of standard error and under the result line's last key
    tail = _compared_lines(r, line)
    assert tail[-1] == "checks failed: none"
    assert all(x.startswith("compared ") and x.endswith(": ok")
               for x in tail[-11:-1])
    assert len(line["compared"]) == 10
    phases = _wall(r, note)
    assert all(v > 0 for v in phases.values())
    assert phases["layer_check"] == layer["seconds"]
