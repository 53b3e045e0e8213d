"""The new cell's command at a tiny size on the CPU: a traced `--tiny`
run of `deepseekv32-ep8-reason-closed` is `correct`, compares each
number with its limit, and reports the counters' per-layer metrics, the
two of the speculating step among them (counts only: a CPU run yields
no time and no share of the device)."""

import json

import _paths
from test_bench_run_tiny import LINE_KEYS, _compared_lines, _run, _wall

CELL = "deepseekv32-ep8-reason-closed"


def test_tiny_run_of_the_speculating_cell_is_correct():
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
                            "dsa_selected_share", "mtp_accept_share",
                            "mtp_tokens_per_slot_step"}
    assert "decode_batch_mean" not in metrics
    # the tiny share: group 0 of 4 held, 2 groups and 3 experts a token
    assert 8.0 < metrics["moe_held_assignment_share"]["value"] < 35.0
    # prompts of 40-120 positions, 16 of them selected
    assert 10.0 < metrics["dsa_selected_share"]["value"] < 45.0
    # half the traffic samples at temperature 1.0 over 256 ids: both
    # branches of the verify step run
    assert 2.0 < metrics["mtp_accept_share"]["value"] < 70.0
    assert 1.0 < metrics["mtp_tokens_per_slot_step"]["value"] < 2.0
    note = [json.loads(x) for x in r.stdout.strip().splitlines()[:-1]
            if x.startswith("{") and '"info": "run"' in x][0]
    assert 0 < max(note["reference_rel_l2"].values()) \
        <= note["reference_tolerance"]
    assert note["served"]["requests"] == 4
    assert {"engine_decode_resident_mtp", "engine_prefill_mtp",
            "engine_mtp_row"} <= set(note["compile_table"])
    layer = [json.loads(x) for x in r.stdout.strip().splitlines()[:-1]
             if x.startswith("{") and '"info": "layer_check"' in x][0]
    assert layer["within"] is True and len(layer["found"]) == 13
    assert layer["checked_layers"] == ["0", "1", "mtp"]
    assert note["checks"]["configuration_layer_check"] is True
    # the layer check's thirteen numbers and the harness's five, together
    # at the end of standard error and under the result line's last key
    tail = _compared_lines(r, line)
    assert tail[-1] == "checks failed: none"
    assert all(x.startswith("compared ") and x.endswith(": ok")
               for x in tail[-19:-1])
    assert len(line["compared"]) == 18
    assert line["compared"]["index_overlap_min"]["floor"] == 0.8
    phases = _wall(r, note)
    assert all(v > 0 for v in phases.values())
    assert phases["layer_check"] == layer["seconds"]
