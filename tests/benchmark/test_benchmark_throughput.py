"""``throughput`` reads the pace that held over the window (the median
unit), and ``unit_stall_share`` keeps what that leaves out."""

import os

import pytest

from benchtools import ROOT
from benchmark import run

LOOKUP = run.Lookup([os.path.join(ROOT, "benchmark")])
THROUGHPUT = LOOKUP.module("end_to_end", "throughput")
STALL = LOOKUP.module("layer_metrics", "unit_stall_share")

#: ``resnet50.fit_cached`` on the v5e, PR 22: seed 7 (steady) and seed 8
#: (its fourth unit stalled), 5,120 samples a unit
STEADY = [1.8359, 1.8361, 1.8362, 1.8361, 1.8356, 1.8368, 1.8358, 1.8361,
          1.8361, 1.8359, 1.8356]
STALLED = [1.836, 1.8361, 1.836, 2.4065, 1.8375, 1.8361, 1.8358, 1.8359,
           1.8356, 1.8358, 1.8359]


def _record(walls, items_per_unit=5120):
    return {"unit_walls_s": walls, "items_per_unit": items_per_unit,
            "items": items_per_unit * len(walls), "window_s": sum(walls)}


@pytest.mark.parametrize("walls", [STEADY, STALLED],
                         ids=["steady", "one_stalled_unit"])
def test_one_stalled_unit_does_not_move_throughput(walls):
    record = _record(walls)
    assert THROUGHPUT.read(record) == pytest.approx(2788.8, rel=2e-4)
    mean = record["items"] / record["window_s"]
    assert (mean == pytest.approx(2788.8, rel=2e-4)) == (walls is STEADY)


def test_half_the_units_slower_moves_it():
    """A pace that did not hold is not hidden: with six units of eleven
    at 2.0 s the median is a slow one."""
    walls = [1.836] * 5 + [2.0] * 6
    assert THROUGHPUT.read(_record(walls)) == pytest.approx(5120 / 2.0)


def test_an_even_count_takes_the_middle_pair():
    assert THROUGHPUT.read(_record([1.0, 2.0, 4.0, 8.0], 6)) == \
        pytest.approx(6 / 3.0)


def test_without_units_it_is_items_over_the_windows_wall():
    assert THROUGHPUT.read({"items": 300, "window_s": 1.5}) == 200.0
    assert THROUGHPUT.read({"items": 0, "window_s": 1.5}) is None
    assert STALL.read({"items": 300, "window_s": 1.5}) is None


@pytest.mark.parametrize("walls,low,high", [
    (STEADY, 0.0, 0.02), (STALLED, 2.7, 2.8), ([2.0] * 4, 0.0, 0.0)],
    ids=["steady", "one_stalled_unit", "equal_units"])
def test_stall_share_keeps_what_the_median_leaves_out(walls, low, high):
    assert low <= STALL.read(_record(walls)) <= high
