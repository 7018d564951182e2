"""``ParallelWrapper(net, workers=chips).fit(iterator)`` with product
defaults: data-parallel training across the cell's chips, fed by a host
iterator.

One timed unit is one ``fit`` over ``rounds_per_unit`` averaging rounds
(``workers`` batches each), ending in a blocking fetch of the score.
The iterator cycles ``distinct_batches`` seeded batches held in host
memory, so every round is stacked and staged to the devices by the
wrapper's own prefetch thread.  Traffic parameters: ``batch_per_chip``,
``distinct_batches``, ``rounds_per_unit``, ``warm_rounds``,
``trace_after_units``, ``trace_units``.
"""

from __future__ import annotations

import time

from benchmark import nets, units


class CyclingIterator:
    """``length`` batches an epoch, cycling over ``batches``; every
    ``next()`` is a host span in a traced window."""

    def __init__(self, batches, length, annotate):
        self.batches, self.length, self.annotate = batches, length, annotate
        self.at = 0

    def reset(self):
        self.at = 0

    def __iter__(self):
        return self

    def __next__(self):
        with self.annotate("bench/fit/next"):
            if self.at >= self.length:
                raise StopIteration
            ds = self.batches[self.at % len(self.batches)]
            self.at += 1
            return ds


def setup(run):
    import jax
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.parallel.parallel_wrapper import ParallelWrapper
    laps = units.Laps()
    traffic, workers = run.traffic, len(run.devices)
    net, errors = units.checked_net(run, laps)
    per = traffic["batch_per_chip"]
    x, y = nets.images(run.cfg, per * traffic["distinct_batches"], run.seed,
                       stream=1)
    batches = [DataSet(x[i * per:(i + 1) * per], y[i * per:(i + 1) * per])
               for i in range(traffic["distinct_batches"])]
    laps("data")
    wrapper = ParallelWrapper(net, workers=workers, devices=run.devices)
    state = {"net": net, "wrapper": wrapper, "batches": batches,
             "errors": errors, "scores": [], "workers": workers,
             "setup_phases": laps.phases}
    _unit(run, state, traffic["warm_rounds"])       # compiles the round
    laps("warm_rounds")
    holders = {d for leaf in jax.tree.leaves(net.params)
               for d in leaf.devices()}
    state["replicas_ok"] = (holders == set(run.devices)
                            and len(holders) == workers)
    return state


def _unit(run, state, rounds) -> float:
    iterator = CyclingIterator(state["batches"], rounds * state["workers"],
                               run.annotate)
    t0 = time.perf_counter()
    with run.annotate("bench/fit"):
        state["wrapper"].fit(iterator)
    with run.annotate("bench/score"):
        state["scores"].append(float(state["net"].score()))
    return time.perf_counter() - t0


def measure(run, state):
    traffic = run.traffic
    rounds = traffic["rounds_per_unit"]
    walls = units.fill_window(run, lambda: _unit(run, state, rounds))
    # one step = one averaging round of every worker's local step
    return units.record(
        run, state, walls,
        rounds * state["workers"] * traffic["batch_per_chip"], rounds,
        also_correct=state["replicas_ok"],
        notes=[f"replicas on {state['workers']} distinct devices: "
               f"{state['replicas_ok']}"])
