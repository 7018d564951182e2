"""What the ``fit`` drivers share: set-up laps, the loop of timed units
that fills the window, and the record the readers get."""

from __future__ import annotations

import time

import numpy as np

from benchmark import flops, nets


class Laps:
    """Seconds of each set-up phase, for the ``bench: setup`` line."""

    def __init__(self):
        self.t, self.phases = time.perf_counter(), {}

    def __call__(self, name: str) -> None:
        now = time.perf_counter()
        self.phases[name], self.t = round(now - self.t, 3), now


def checked_net(run, laps: Laps):
    """The cell's net from its file and the seed, compared with the
    reference before anything trains it."""
    net = nets.build_net(run.cfg, run.seed)
    laps("build_net")
    cx, cy = nets.check_examples(run.cfg, run.seed)
    errors = nets.compare(run.cfg, net, cx, cy, net.output(cx), train=True)
    laps("check")
    return net, errors


def fill_window(run, unit) -> list:
    """Call ``unit()`` (which returns its wall seconds and ends in a
    blocking fetch) until the walls add up to the window; in a traced
    run, ``trace_units`` of them after the first ``trace_after_units``
    run under the profiler (starting and stopping it is outside every
    unit's wall)."""
    first = run.traffic["trace_after_units"]
    last = first + run.traffic["trace_units"]
    walls = []
    while sum(walls) < run.seconds:
        if len(walls) == first:
            run.tracer.start()
        walls.append(unit())
        if len(walls) == last:
            run.tracer.stop()
    run.tracer.stop()
    return walls


def scores_move(scores) -> bool:
    """Whether training changed the score at all.  Every unit trains on
    the same seeded examples with a deterministic program, so a step
    that applies no update reports the same score unit after unit, to
    the last bit; any update moves it (in the 38 runs PR 22 recorded on
    the v5e a run's scores range over 13% of the largest or more for
    ResNet-50, 4.8% or more for VGG-16, 33% under ``ParallelWrapper``).
    This is the least a run can say of the backward pass and the
    updater; their arithmetic is held to the reference's Nesterov steps
    in ``tests/benchmark/`` on the CPU in float32, not here (PERF.md
    section 7 says what a check on the chip needs)."""
    scores = np.asarray(scores, np.float64)
    return bool(scores.size >= 2
                and np.ptp(scores) > 1e-6 * np.abs(scores).max())


def record(run, state, walls, items_per_unit, steps_per_unit,
           also_correct=True, notes=()):
    """The ``fit`` cells' record: ``correct`` needs the comparison with
    the reference within its bounds, every score of the window finite,
    and scores that move (the warm-up unit's counts as the first).
    Every unit's wall goes in (``unit_walls_s``): ``throughput`` reads
    the median unit and ``unit_stall_share`` what lies beyond it."""
    first = run.traffic["trace_after_units"]
    traced = min(run.traffic["trace_units"], max(0, len(walls) - first))
    scores = state["scores"]
    window_scores = np.asarray(scores[len(scores) - len(walls):])
    dtype = nets.compute_dtype(state["net"])
    finite = np.asarray(scores, np.float64)
    finite = finite[np.isfinite(finite)]
    checks = {f"{k}_rel_err": [state["errors"][k], f"<={limit}"]
              for k, limit in nets.BOUNDS[dtype].items()
              if k in state["errors"]}
    checks["nonfinite_scores"] = [
        int((~np.isfinite(window_scores)).sum()), "<=0"]
    checks["score_range_rel"] = [
        float(np.ptp(finite) / np.abs(finite).max()) if finite.size
        else float("nan"), ">1e-06"]
    return {
        "checks": checks,
        "correct": bool(nets.verdict(state["errors"], dtype)
                        and np.isfinite(window_scores).all()
                        and scores_move(scores)
                        and also_correct),
        "attempted": len(walls),
        "failed": int((~np.isfinite(window_scores)).sum()),
        "window_s": float(sum(walls)),
        "unit_walls_s": [float(w) for w in walls],
        "items_per_unit": items_per_unit,
        "items": items_per_unit * len(walls),
        "steps": steps_per_unit * len(walls),
        "trace_items": items_per_unit * traced,
        "flops_per_item": flops.flops_per_item(run.cfg, training=True),
        "notes": [
            f"policy {state['net']._pol().describe()}",
            f"check errors {state['errors']} bounds {nets.BOUNDS[dtype]}",
            *notes,
            f"first scores {scores[:20]}",
            f"units {len(walls)} walls_s {[round(w, 4) for w in walls]}",
            f"items/s by the median unit "
            f"{items_per_unit / float(np.median(walls)):.2f} by the "
            f"window's mean {items_per_unit * len(walls) / sum(walls):.2f}"],
    }
