"""The one general traffic generator.

A traffic mix is a data file of parameters under ``benchmark/traffic/``;
this module turns those parameters and a seed into a schedule, and
sends it.  Everything is fixed before the first send: arrival times,
rows per request and which seeded example each request carries, so the
same seed offers the same load.

Open loop: requests leave on the schedule whether or not earlier ones
have come back, from ONE generator thread (asynchronous sends; the
completion callback stamps the answer's time).  Latency runs from when
a request was DUE, so a stall is charged to every request it delays,
and how late each send really left is kept (``lag``): a starved
generator must not read as a fast server.

The arrival processes are ``bench.py``'s ``_arrival_times`` (Poisson,
and the bursty Lewis-Shedler thinning at 3x the mean in a 25% duty
cycle), copied so that no later PR can change the yardstick.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, List, Sequence

import numpy as np


#: the latency written for a request that failed: an hour, finite so
#: that the result line stays JSON, and beyond any answer's
FAILED = 3600.0


def arrival_times(kind: str, rate: float, duration_s: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Scheduled send times in ``[0, duration_s)`` with mean ``rate``."""
    if rate <= 0 or duration_s <= 0:
        return np.zeros(0)
    if kind == "poisson":
        lam_max, lam = rate, None
    elif kind == "bursty":
        burst_x, duty = 3.0, 0.25
        period = max(0.5, duration_s / 4.0)
        base = (1.0 - duty * burst_x) / (1.0 - duty)
        lam_max = rate * burst_x

        def lam(t):
            return rate * np.where((t % period) / period < duty,
                                   burst_x, base)
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    # candidates at the highest rate, drawn in one block and thinned
    n = int(lam_max * duration_s * 1.2) + 64
    t = np.cumsum(rng.exponential(1.0 / lam_max, n))
    while t[-1] < duration_s:
        t = np.concatenate(
            [t, t[-1] + np.cumsum(rng.exponential(1.0 / lam_max, n))])
    t = t[t < duration_s]
    if lam is not None:
        t = t[rng.random(t.size) * lam_max < lam(t)]
    return t


def draw_rows(mix: Dict[str, float], n: int,
              rng: np.random.Generator) -> np.ndarray:
    """Rows per request drawn from ``{"rows": share}``."""
    sizes = np.array([int(k) for k in mix], np.int64)
    shares = np.array([float(v) for v in mix.values()], np.float64)
    return rng.choice(sizes, size=n, p=shares / shares.sum())


def schedule(traffic: Dict, duration_s: float, seed: int) -> Dict:
    """The whole offered load of one window, from the traffic file's
    parameters and the seed."""
    rng = np.random.default_rng([int(seed), 0x10AD])
    at = arrival_times(traffic.get("arrivals", "poisson"),
                       float(traffic["rate_per_s"]), duration_s, rng)
    return {"at": at, "rows": draw_rows(traffic["rows_mix"], at.size, rng),
            "offset": rng.integers(0, 1 << 30, at.size)}


def open_loop(send: Callable[[int], "object"], at: Sequence[float],
              timeout_s: float, annotate=None, accept=None) -> Dict:
    """Send request ``i`` at ``at[i]`` seconds after the start.
    ``send(i)`` returns a future (``add_done_callback``, ``exception``,
    ``result``); it may raise, which counts as a failure, as does an
    answer that ``accept(i, answer)`` turns down.  ``annotate(name)``
    gives a context manager for host spans (the wait for the next
    arrival, each send).  Returns per-request arrays: ``latency_s``
    (due -> whole answer), ``lag_s`` (due -> really sent) and ``ok``.
    A failed, refused, timed-out or never-answered request is not
    ``ok`` and its latency is ``FAILED``: slower than every answer, so
    it takes its place in the tail."""
    if annotate is None:
        annotate = lambda name: contextlib.nullcontext()
    n = len(at)
    done_at = np.full(n, np.nan)
    failed = np.zeros(n, bool)
    lag = np.zeros(n)
    left = threading.Semaphore(0)
    start = time.perf_counter()

    def on_done(i, fut):
        done_at[i] = time.perf_counter() - start
        if fut.exception() is not None or (
                accept is not None and not accept(i, fut.result())):
            failed[i] = True
        left.release()

    for i in range(n):
        delay = at[i] - (time.perf_counter() - start)
        if delay > 0:
            with annotate("bench/wait_arrival"):
                time.sleep(delay)
        sent = time.perf_counter() - start
        lag[i] = sent - at[i]
        try:
            with annotate("bench/send"):
                fut = send(i)
        except Exception:
            failed[i] = True
            done_at[i] = sent
            left.release()
            continue
        fut.add_done_callback(lambda f, i=i: on_done(i, f))
    # answers still on their way: each gets its own timeout, no more
    deadline = start + (at[-1] if n else 0.0) + timeout_s
    for _ in range(n):
        if not left.acquire(timeout=max(0.0, deadline - time.perf_counter())):
            break
    end = time.perf_counter() - start
    latency = done_at - np.asarray(at)
    ok = ~failed & np.isfinite(latency) & (latency <= timeout_s)
    return {"latency_s": np.where(ok, latency, FAILED), "lag_s": lag,
            "ok": ok, "wall_s": end}


def percentile_with_failures(latency_s: np.ndarray, q: float) -> float:
    """The ``q``-th percentile (nearest rank) in which a failed request
    is slower than every answered one: ``FAILED`` if the percentile
    falls on a failure."""
    if latency_s.size == 0:
        return float("nan")
    s = np.sort(latency_s)
    return float(s[min(s.size - 1, int(np.ceil(q / 100.0 * s.size)) - 1)])
