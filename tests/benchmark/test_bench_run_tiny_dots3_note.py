"""The new cell's command at a tiny size on the CPU: a traced `--tiny`
run of `dots3-ep8-longdoc-closed` is `correct`, compares each number
with its limit, and reports the counters' per-layer metrics (counts
only: a CPU run yields no time and no share of the device)."""

import json

import _paths
from test_bench_run_tiny import LINE_KEYS, _run

CELL = "dots3-ep8-longdoc-closed"


def test_tiny_run_of_the_sparse_latent_cell_is_correct():
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
                            "dsa_selected_share"}
    # the tiny share: 4 of 16 experts held, 3 of 16 chosen a token
    assert 15.0 < metrics["moe_held_assignment_share"]["value"] < 35.0
    # prompts of 40-120 positions, 16 of them selected
    assert 10.0 < metrics["dsa_selected_share"]["value"] < 45.0
    note = [json.loads(x) for x in r.stdout.strip().splitlines()[:-1]
            if x.startswith("{") and '"info": "run"' in x][0]
    assert 0 < max(note["reference_rel_l2"].values()) \
        <= note["reference_tolerance"]
    assert note["served"]["requests"] == 4
    layer = [json.loads(x) for x in r.stdout.strip().splitlines()[:-1]
             if x.startswith("{") and '"info": "layer_check"' in x][0]
    assert layer["within"] is True and len(layer["found"]) == 10
    assert sum(x.startswith("compared ") and x.endswith(": ok")
               for x in r.stderr.splitlines()) == 15
    assert r.stderr.strip().splitlines()[-1] == "checks failed: none"
