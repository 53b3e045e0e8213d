"""The run's wall clock (PR 38): consecutive laps that add up to the
wall time, a part of a lap moved to the phase a configuration's module
timed itself, the last line of standard error beside the budget, and a
compared number whose limit is a floor."""

import time

import pytest

import _paths  # noqa: F401
from harness import common


def test_laps_add_up_to_the_wall_time_and_name_every_phase():
    clock = common.WallClock(time.monotonic() - 2.0)
    clock.lap("devices_ready")
    time.sleep(0.01)
    clock.lap("weights")
    clock.lap("weights")                      # a phase may be lapped twice
    wall = clock.close()
    assert list(wall["phases"]) == list(common.PHASES)
    assert wall["phases"]["devices_ready"] >= 2.0
    assert wall["phases"]["weights"] >= 0.01
    assert wall["phases"]["window"] == 0.0    # never lapped: still named
    assert sum(wall["phases"].values()) == pytest.approx(wall["wall_s"])
    with pytest.raises(KeyError):
        clock.lap("no_such_phase")


@pytest.mark.parametrize("asked,moved", [(0.5, 0.5), (9.0, 1.0), (-1.0, 0.0)],
                         ids=["a_part", "no_more_than_the_lap", "never_back"])
def test_a_part_of_a_lap_moves_to_the_phase_that_was_timed_inside_it(
        asked, moved):
    clock = common.WallClock(time.monotonic() - 1.0)
    clock.lap("canonical_tree")
    before = clock.phases["canonical_tree"]
    clock.move(asked, "canonical_tree", "layer_check")
    assert clock.phases["layer_check"] == pytest.approx(
        min(moved, before), abs=0.01)
    assert clock.phases["canonical_tree"] + clock.phases["layer_check"] \
        == pytest.approx(before)


@pytest.mark.parametrize("wall,verdict", [(269.5, "ok"), (300.0, "ok"),
                                          (300.5, "OVER")])
def test_the_last_line_says_the_wall_time_beside_the_budget(capsys, wall,
                                                            verdict):
    assert common.RUN_BUDGET_S == 300.0       # five sixths of 360 s
    common.report_wall(wall)
    assert capsys.readouterr().err == \
        f"wall_s = {wall} budget 300.0: {verdict}\n"


def test_a_floor_is_printed_and_carried_as_a_floor(capsys):
    got = common.report_compared(
        [("index_overlap_min", 0.99, 0.985, "floor"),
         ("index_overlap_min.low", 0.5, 0.985, "floor"),
         ("rel", 0.01, 0.02), ("rel.over", 0.03, 0.02),
         ("rel.unread", None, 0.02)], {"a": True, "b": False})
    err = capsys.readouterr().err.splitlines()
    assert err == [
        "compared index_overlap_min = 0.99 floor 0.985: ok",
        "compared index_overlap_min.low = 0.5 floor 0.985: OVER",
        "compared rel = 0.01 limit 0.02: ok",
        "compared rel.over = 0.03 limit 0.02: OVER",
        "compared rel.unread = None limit 0.02: not read",
        "checks failed: ['b']"]
    assert got["index_overlap_min"] == {"value": 0.99, "floor": 0.985}
    assert got["rel.unread"] == {"value": None, "limit": 0.02}
