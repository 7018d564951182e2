"""The load generator: its schedule is a function of the seed, latency
runs from the due time, and lateness is accounted."""

import numpy as np
import pytest

import benchtools  # noqa: F401  (puts the repo on sys.path)
from benchmark import loadgen

TRAFFIC = {"arrivals": "poisson", "rate_per_s": 500.0,
           "rows_mix": {"1": 0.70, "2": 0.15, "4": 0.10, "8": 0.05}}


def test_schedule_equal_for_equal_seeds():
    a = loadgen.schedule(TRAFFIC, 4.0, 7)
    b = loadgen.schedule(TRAFFIC, 4.0, 7)
    c = loadgen.schedule(TRAFFIC, 4.0, 8)
    for key in ("at", "rows", "offset"):
        assert np.array_equal(a[key], b[key])
    assert not np.array_equal(a["at"][:50], c["at"][:50])


@pytest.mark.parametrize("kind", ["poisson", "bursty"])
def test_arrivals_mean_rate_and_order(kind):
    at = loadgen.arrival_times(kind, 500.0, 20.0,
                               np.random.default_rng(0))
    assert np.all(np.diff(at) > 0) and at[-1] < 20.0
    assert at.size == pytest.approx(10_000, rel=0.05)


def test_bursty_concentrates_arrivals():
    at = loadgen.arrival_times("bursty", 500.0, 20.0,
                               np.random.default_rng(0))
    in_burst = ((at % 5.0) / 5.0 < 0.25).mean()
    assert in_burst == pytest.approx(0.75, abs=0.03)    # 3x in 25%


def test_rows_mix_shares():
    rows = loadgen.draw_rows(TRAFFIC["rows_mix"], 20_000,
                             np.random.default_rng(1))
    assert set(np.unique(rows)) == {1, 2, 4, 8}
    assert (rows == 1).mean() == pytest.approx(0.70, abs=0.02)
    assert rows.mean() == pytest.approx(1.8, abs=0.05)


class _Clock:
    """``time`` for ``loadgen``: sleeping is what moves it, so the test
    does not depend on how busy the machine is."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now

    def sleep(self, seconds):
        self.now += seconds


class _AnsweredAfter:
    """A future whose answer comes ``delay`` after the send, without
    holding the generator up."""

    def __init__(self, clock, delay, error=None):
        self.clock, self.delay, self.error = clock, delay, error

    def add_done_callback(self, callback):
        sent = self.clock.now
        self.clock.now = sent + self.delay
        callback(self)
        self.clock.now = sent

    def exception(self):
        return self.error

    def result(self):
        return "answer"


def test_latency_runs_from_the_due_time_and_lag_is_kept(monkeypatch):
    """The second send stalls for 50 ms: every later request was due
    while the generator was stuck, so it leaves late (lag) and its
    latency still counts from when it was due."""
    clock = _Clock()
    monkeypatch.setattr(loadgen, "time", clock)
    at = np.array([0.00, 0.01, 0.02, 0.03])

    def send(i):
        if i == 1:
            clock.sleep(0.05)
        return _AnsweredAfter(clock, 0.005)

    res = loadgen.open_loop(send, at, timeout_s=1.0)
    assert res["ok"].all()
    assert res["lag_s"] == pytest.approx([0.0, 0.0, 0.04, 0.03])
    assert res["latency_s"] == pytest.approx([0.005, 0.055, 0.045, 0.035])


def test_failures_refusals_timeouts_and_rejected_answers(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(loadgen, "time", clock)
    at = np.array([0.0, 0.005, 0.010, 0.015, 0.020])

    def send(i):
        if i == 0:
            raise RuntimeError("queue full")            # refused at once
        if i == 1:                                      # failed later
            return _AnsweredAfter(clock, 0.005, RuntimeError("refused"))
        if i == 2:
            return _AnsweredAfter(clock, 0.4)           # too late
        return _AnsweredAfter(clock, 0.005)

    res = loadgen.open_loop(send, at, timeout_s=0.2,
                            accept=lambda i, answer: i != 3)
    assert list(res["ok"]) == [False, False, False, False, True]
    assert (res["latency_s"][:4] == loadgen.FAILED).all()
    # a failure is slower than every answer: it owns the tail
    assert loadgen.percentile_with_failures(res["latency_s"], 99) == \
        loadgen.FAILED
    assert loadgen.percentile_with_failures(res["latency_s"], 20) < 0.1


def test_percentile_with_failures_matches_sorted_rank():
    lat = np.arange(1, 101) / 1000.0
    assert loadgen.percentile_with_failures(lat, 50) == 0.050
    assert loadgen.percentile_with_failures(lat, 99) == 0.099
