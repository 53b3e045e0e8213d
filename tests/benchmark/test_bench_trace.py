"""The trace reduction on the small recorded trace of
``benchmark/fixtures``: busy and idle share, time per group, the share
no group matched, and the owner of each gap."""

import json

import pytest

import _paths
from harness import layer_metrics, trace_reduce

FIX = _paths.BENCH / "fixtures"


@pytest.fixture(scope="module")
def expected():
    return json.loads((FIX / "two_ops.expected.json").read_text())


@pytest.fixture(scope="module")
def reduction(expected):
    planes = trace_reduce.load(FIX / "two_ops.xplane.pb")
    return trace_reduce.reduce(planes, expected["groups_given"],
                               host_spans=expected["host_spans"])


def test_busy_and_idle_share(reduction, expected):
    assert reduction["window_s"] == pytest.approx(expected["window_us"] / 1e6)
    assert reduction["busy_s"] == pytest.approx(expected["busy_us"] / 1e6)
    idle = layer_metrics.trace_idle_share({"trace": reduction}, {})
    assert idle == pytest.approx(expected["idle_share"])


def test_time_per_group_and_per_program(reduction, expected):
    g = reduction["groups"]["kernels"]
    assert g["seconds"] == pytest.approx(expected["kernels_us"] / 1e6)
    assert g["calls"] == expected["kernels_calls"]
    p = reduction["programs"]["step_programs"]
    assert p["seconds"] == pytest.approx(expected["step_programs_us"] / 1e6)
    assert p["calls"] == expected["step_programs_calls"]


def test_unmatched_share_is_reported(reduction, expected):
    assert reduction["unmatched_share"] == pytest.approx(
        expected["unmatched_share"])
    name, secs = reduction["device_ops"][0]
    assert name == expected["top_op"][0]
    assert secs == pytest.approx(expected["top_op"][1] / 1e6)


def test_gaps_are_owned_by_the_innermost_open_host_span(reduction, expected):
    owners = {k: pytest.approx(v / 1e6)
              for k, v in expected["gap_owners_us"].items()}
    assert dict(reduction["idle_gaps"]) == owners
    assert reduction["longest_gap_s"] == pytest.approx(
        expected["longest_gap_us"] / 1e6)


def test_roofline_share_from_the_trace_and_the_cost_of_the_work(reduction):
    # the group ran 30 us; its work is 819 bytes per run of the step
    # program (1 run) on an 819 GB/s chip: 1 ns least time
    obs = {"trace": reduction, "peaks": {"bf16_tflops": 197.0,
                                         "hbm_gbps": 819.0},
           "work": {"bytes_per_step": 819.0}}
    v = layer_metrics.trace_group_roofline(obs, {
        "group": "kernels", "bytes_key": "bytes_per_step",
        "per_call_of": "step_programs", "scale": 100.0})
    assert v == pytest.approx(100.0 * 1e-9 / 30e-6)


def test_a_trace_without_a_device_plane_reduces_to_nothing():
    planes = [p for p in trace_reduce.load(FIX / "two_ops.xplane.pb")
              if not p["name"].startswith("/device")]
    assert trace_reduce.reduce(planes, {}, ["engine_step"]) is None


def test_exclusive_time_subtracts_nested_events():
    evs = [("outer", 0.0, 100.0), ("a", 10.0, 30.0), ("b", 60.0, 30.0),
           ("after", 150.0, 50.0)]
    assert trace_reduce.exclusive_times(evs) == [40.0, 30.0, 30.0, 50.0]


def test_a_group_within_a_program_group_takes_only_operations_inside_it():
    planes = trace_reduce.load(FIX / "two_ops.xplane.pb")
    groups = {
        "step_programs": {"line": "XLA Modules", "patterns": ["^jit_step"]},
        "other_programs": {"line": "XLA Modules",
                           "patterns": ["^jit_other"]},
        "fusions_in_steps": {"line": "XLA Ops", "patterns": ["^fusion"],
                             "within": "step_programs"},
    }
    r = trace_reduce.reduce(planes, groups, ["engine_step"])
    # fusion.1 (30 us) ran inside jit_step; fusion.3 and fusion.4 did not
    assert r["groups"]["fusions_in_steps"]["seconds"] == pytest.approx(30e-6)
    assert r["groups"]["fusions_in_steps"]["calls"] == 1
    assert r["programs"]["other_programs"]["calls"] == 2


def test_short_name_drops_layouts_and_cuts():
    name = ("%reshape.250 = bf16[32,2048,1024]{2,1,0:T(8,128)(2,1)} "
            "reshape(bf16[1,32,2048,8,128]{4,3,2,1,0:T(8,128)(2,1)} %x)")
    assert trace_reduce.short_name(name) == (
        "%reshape.250 = bf16[32,2048,1024] reshape(bf16[1,32,2048,8,128] %x)")
    assert len(trace_reduce.short_name("x" * 500)) == 120
