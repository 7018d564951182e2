"""``fit(ListDataSetIterator, epochs=E)`` through the epoch cache, on
one chip: the trainer's path with ingest doing nothing.

One timed unit is one ``fit`` call (with no listeners the E epochs fuse
into one dispatch) ending in ``score()``, which blocks until the device
is done.  Units repeat until their walls add up to the window.  Traffic
parameters: ``examples``, ``batch``, ``epochs``, ``trace_after_units``,
``trace_units``.
"""

from __future__ import annotations

import time

from benchmark import nets, units


def setup(run):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    laps = units.Laps()
    net, errors = units.checked_net(run, laps)
    x, y = nets.images(run.cfg, run.traffic["examples"], run.seed, stream=1)
    iterator = ListDataSetIterator(DataSet(x, y), run.traffic["batch"])
    laps("data")
    state = {"net": net, "iterator": iterator, "errors": errors,
             "scores": [], "setup_phases": laps.phases}
    _unit(run, state)                       # compiles the fused program
    laps("warm_unit")
    return state


def _unit(run, state) -> float:
    net = state["net"]
    t0 = time.perf_counter()
    with run.annotate("bench/fit"):
        net.fit(state["iterator"], epochs=run.traffic["epochs"])
    with run.annotate("bench/score"):
        state["scores"].append(float(net.score()))
    return time.perf_counter() - t0


def measure(run, state):
    traffic = run.traffic
    steps = (traffic["examples"] // traffic["batch"]) * traffic["epochs"]
    walls = units.fill_window(run, lambda: _unit(run, state))
    return units.record(run, state, walls, steps * traffic["batch"], steps)
