"""Scaling-efficiency harness.

BASELINE.json north star: ParallelWrapper scaling efficiency
``throughput(N) / (N * throughput(1))`` for 1..16 chips (target >=90% at
v5e-16).  The reference only ships the *mechanism* (workers x avgFreq,
``ParallelWrapper.java:44-55``); the measurement harness is ours, built on
the PerformanceListener-style samples/sec accounting.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from ..datasets.dataset import DataSet
from .parallel_wrapper import ParallelWrapper


def measure_throughput(net_factory: Callable[[], object], workers: int,
                       batch_size: int = 128, n_rounds: int = 10,
                       averaging_frequency: int = 1,
                       feature_shape=(784,), n_classes: int = 10,
                       warmup_rounds: int = 2,
                       devices: Optional[list] = None) -> float:
    """Samples/sec of data-parallel training at ``workers`` devices.

    Each worker consumes ``batch_size`` examples per local step, so one
    round moves ``workers * averaging_frequency * batch_size`` samples.
    """
    rng = np.random.RandomState(0)
    k = averaging_frequency

    def make_batches(n):
        return [DataSet(
            rng.randn(batch_size, *feature_shape).astype(np.float32),
            np.eye(n_classes, dtype=np.float32)[
                rng.randint(0, n_classes, batch_size)])
            for _ in range(n * k * workers)]

    net = net_factory()
    net.init()
    pw = ParallelWrapper(net, workers=workers, averaging_frequency=k,
                         devices=devices)
    pw.fit(make_batches(warmup_rounds))
    jax.block_until_ready(net.params)

    batches = make_batches(n_rounds)
    t0 = time.perf_counter()
    pw.fit(batches)
    jax.block_until_ready(net.params)
    elapsed = time.perf_counter() - t0
    return len(batches) * batch_size / elapsed


def scaling_report(net_factory: Callable[[], object],
                   worker_counts: List[int], **kw) -> Dict[int, dict]:
    """Throughput + efficiency per worker count (efficiency relative to the
    1-worker throughput: throughput(N) / (N * throughput(1)))."""
    out: Dict[int, dict] = {}
    base = None
    for w in worker_counts:
        tput = measure_throughput(net_factory, w, **kw)
        if base is None:
            base = tput / w  # per-chip baseline at the smallest count
        out[w] = {
            "workers": w,
            "samples_per_sec": round(tput, 1),
            "efficiency": round(tput / (w * base), 4),
        }
    return out


def collective_overhead_report(net_factory: Callable[[], object],
                               batch_size: int = 256,
                               feature_shape=(784,), n_classes: int = 10,
                               steps: int = 40, trials: int = 3,
                               pipeline: int = 4) -> dict:
    """Bound the shard_map/collective cost on ONE real chip (round-3
    verdict: with no multi-chip hardware, the honest scaling substitute
    is the measured overhead of the sharded program at workers=1 —
    pmean over a 1-slot axis plus shard_map plumbing vs the plain jitted
    step; the true N-chip cost adds only the ICI all-reduce itself).

    Returns per-path step times and the overhead ratio.  Both paths run
    ``steps`` dispatches per completion fetch (launch latency
    amortized, same as bench.py), best of ``trials``."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    f = rng.rand(batch_size, *feature_shape).astype(np.float32)
    l = np.eye(n_classes, dtype=np.float32)[
        rng.randint(0, n_classes, batch_size)]

    # --- plain jitted step ------------------------------------------------
    net = net_factory()
    net.init()
    is_graph = hasattr(net, "conf") and hasattr(net.conf, "network_inputs")
    fj = jnp.asarray(f)
    lj = jnp.asarray(l)
    if is_graph:
        fj, lj = (fj,), (lj,)   # ComputationGraph: tuple-of-inputs
    state = [net.params, net.updater_state, net.net_state, 0]

    def plain_dispatch():
        (state[0], state[1], state[2], score, _) = net._train_step(
            state[0], state[1], state[2], state[3], fj, lj, None, None,
            net._rng_key)
        state[3] += 1
        return score

    float(np.asarray(plain_dispatch()))

    def plain_timed() -> float:
        t0 = time.perf_counter()
        for _ in range(pipeline * steps):
            s = plain_dispatch()
        float(np.asarray(s))
        return time.perf_counter() - t0

    plain = min(plain_timed() for _ in range(trials)) / (pipeline * steps)

    # --- shard_map(workers=1) step ---------------------------------------
    net2 = net_factory()
    net2.init()
    pw = ParallelWrapper(net2, workers=1, averaging_frequency=1,
                         devices=jax.devices()[:1])
    fs = jnp.asarray(f[None, None])      # (k=1, w=1, B, ...)
    ls = jnp.asarray(l[None, None])
    if is_graph:
        fs, ls = (fs,), (ls,)
    wstate = [net2.params,
              jax.tree.map(lambda a: a[None], net2.updater_state),
              net2.net_state]

    def pw_dispatch():
        (wstate[0], wstate[1], wstate[2], score,
         _health) = pw._parallel_step(
            wstate[0], wstate[1], wstate[2], 0, fs, ls, None, None,
            net2._rng_key, None)
        return score

    float(np.asarray(pw_dispatch()))

    def pw_timed() -> float:
        t0 = time.perf_counter()
        for _ in range(pipeline * steps):
            s = pw_dispatch()
        float(np.asarray(s))
        return time.perf_counter() - t0

    sharded = min(pw_timed() for _ in range(trials)) / (pipeline * steps)
    return {"plain_step_ms": round(plain * 1e3, 4),
            "shard_map_step_ms": round(sharded * 1e3, 4),
            "overhead_ms": round((sharded - plain) * 1e3, 4),
            "overhead_ratio": round(sharded / plain, 4),
            "batch": batch_size, "device": str(jax.devices()[0])}
