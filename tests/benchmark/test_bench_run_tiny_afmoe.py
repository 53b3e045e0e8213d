"""The new cell's command at a tiny size on the CPU: a traced `--tiny`
run of `trinity-mini-ep4-thinking-closed` is `correct`, compares each
number with its limit, and reports the counters' per-layer metrics
(counts only: a CPU run yields no time and no share of the device)."""

import json

import _paths
from test_bench_run_tiny import LINE_KEYS, _compared_lines, _run, _wall

CELL = "trinity-mini-ep4-thinking-closed"


def test_tiny_run_of_the_thinking_cell_is_correct():
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
                            "swa_rows_read_share"}
    # the tiny share: 4 of 16 experts held, 3 of 16 chosen a token
    assert 15.0 < metrics["moe_held_assignment_share"]["value"] < 35.0
    # 9 window layers read 24 rows where 3 full layers read 40-130
    assert 35.0 < metrics["swa_rows_read_share"]["value"] < 65.0
    note = [json.loads(x) for x in r.stdout.strip().splitlines()[:-1]
            if x.startswith("{") and '"info": "run"' in x][0]
    assert 0 < max(note["reference_rel_l2"].values()) \
        <= note["reference_tolerance"]
    assert note["served"]["requests"] == 4
    layer = [json.loads(x) for x in r.stdout.strip().splitlines()[:-1]
             if x.startswith("{") and '"info": "layer_check"' in x][0]
    assert layer["within"] is True and len(layer["found"]) == 6
    assert layer["checked_layers"] == [0, 2, 3]
    assert note["checks"]["configuration_layer_check"] is True
    # the layer check's six numbers and the harness's five, together at
    # the end of standard error and under the result line's last key
    tail = _compared_lines(r, line)
    assert tail[-1] == "checks failed: none"
    assert all(x.startswith("compared ") and x.endswith(": ok")
               for x in tail[-12:-1])
    assert len(line["compared"]) == 11
    phases = _wall(r, note)
    # every phase of a traced serving run, the family's own check too
    assert all(v > 0 for v in phases.values())
    assert phases["layer_check"] == layer["seconds"]
