"""``xplane.py`` on the small trace recorded on the v5e
(``record_small_trace.py``: three units of a jitted matrix chain with a
20 ms host sleep after each), and on hand-made events."""

import os

import pytest

from benchtools import HERE
from benchmark import xplane

TRACE_DIR = os.path.join(HERE, "data")


@pytest.fixture(scope="module")
def events():
    return xplane.read_events(xplane.find_trace(TRACE_DIR))


def test_recorded_trace_structure(events):
    assert list(events["devices"]) == [0]
    names = {n for n, _, _ in events["spans"]}
    assert names == {"bench/window", "bench/unit", "bench/sleep"}
    assert sum(n == "bench/unit" for n, _, _ in events["spans"]) == 3
    # names are HLO instruction names, not whole HLO lines
    assert all(len(n) < 40 and " = " not in n
               for n, _, _ in events["devices"][0])


def test_recorded_trace_idle_share_and_gaps(events):
    r = xplane.reduce_events(events)
    assert r["devices"] == 1
    # three 20 ms sleeps and three sub-millisecond units
    assert 0.060 < r["window_s"] < 0.080
    assert 0.0001 < r["busy_s"] < 0.001
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert r["idle_share"] > 0.98
    # the idle time is attributed to the span the host slept under
    name, seconds = r["idle_gaps"][0]
    assert name == "bench/sleep" and seconds > 0.058
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_recorded_trace_top_operations(events):
    r = xplane.reduce_events(events)
    assert 1 <= len(r["device_ops"]) <= 10
    assert all(name.startswith(("fusion", "copy")) for name, _ in
               r["device_ops"][:4])
    seconds = [s for _, s in r["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    assert sum(seconds) <= r["busy_s"] * (1 + 1e-9)
    assert r["collective_s"] == 0.0


def test_nothing_to_read_gives_nothing(events, tmp_path):
    assert xplane.reduce_trace(str(tmp_path)) is None
    assert xplane.reduce_events({"devices": {}, "spans": events["spans"]}) \
        is None
    assert xplane.reduce_events({"devices": events["devices"],
                                 "spans": []}) is None


def test_container_operations_do_not_count_as_busy():
    """A ``while`` spans its whole body, gaps included: only the events
    that contain no other event are device work."""
    ops = [("while.1", 0.0, 10.0), ("fusion.1", 1.0, 2.0),
           ("conditional.2", 3.0, 8.0), ("fusion.2", 4.0, 5.0),
           ("fusion.1", 6.0, 7.0), ("copy.3", 11.0, 12.0)]
    assert [n for n, _, _ in xplane.leaves(ops)] == \
        ["fusion.1", "fusion.2", "fusion.1", "copy.3"]
    r = xplane.reduce_events({
        "devices": {0: xplane.leaves(ops)},
        "spans": [("bench/window", 0.0, 12.0), ("bench/fit", 0.0, 9.0),
                  ("bench/fit/next", 2.0, 4.0), ("bench/score", 9.0, 12.0)]})
    assert r["busy_s"] == pytest.approx(4.0)
    assert r["idle_share"] == pytest.approx(8.0 / 12.0)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    gaps = dict(r["idle_gaps"])
    # 2-4 lies under both spans: the innermost one names it
    assert gaps["bench/fit/next"] == pytest.approx(2.0)
    # the gap 7-11 is split between the two spans that cover it
    assert gaps["bench/fit"] == pytest.approx(1.0 + 1.0 + 2.0)
    assert gaps["bench/score"] == pytest.approx(2.0)
    assert sum(gaps.values()) == pytest.approx(8.0)


def test_collective_time_and_its_exposed_part_per_device():
    ops0 = [("fusion.1", 0.0, 4.0), ("all-reduce-start.1", 4.0, 4.1),
            ("fusion.2", 4.1, 6.0), ("all-reduce-done.1", 8.0, 8.1)]
    flight0 = [("all-reduce-start.1", 4.0, 8.1)]
    ops1 = [("fusion.1", 0.0, 10.0)]
    r = xplane.reduce_events({
        "devices": {0: ops0, 1: ops1}, "async": {0: flight0},
        "spans": [("bench/window", 0.0, 10.0)]})
    assert r["devices"] == 2
    # device 0: in flight 4.0-8.1, of which 4.1-6.0 is hidden by fusion.2
    assert r["collective_s"] == pytest.approx(4.1 / 2)
    assert r["collective_exposed_s"] == pytest.approx((4.1 - 1.9) / 2)
    assert r["busy_s_by_device"][1] == pytest.approx(10.0)
    assert r["idle_share_worst"] >= r["idle_share"]


def test_interval_helpers():
    assert xplane.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert xplane.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert xplane.clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]
    assert xplane.op_name("%fusion.8 = bf16[8]{0} fusion(bf16[8] %p)") == \
        "fusion.8"
