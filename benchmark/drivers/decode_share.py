"""``decode_sessions`` for a configuration that holds a share of its
routed experts: the same set-up, the same timed unit, the same
comparison (``drivers/decode_sessions.py`` says what they are), with two
things read for the share.

The reference is the configuration's own (``reference/mla_moe_plain.py``),
which takes the held ids from the configuration's file, so what the
absent experts would have added is left out on both sides.

The routed experts' kernel is counted over the HELD experts:
``decode_sessions`` counts the experts that received a token over the
router's whole width, which for a share would charge the step for
matrices that lie on other chips.  Here its count is replaced: from the
last unit's picks by expert, the held experts that received a token (at
most the layer holds) and the picks that landed on them, a step.

``correct`` is decided by this cell's own limits (``BOUNDS``), applied
to the errors ``decode_sessions`` measured.
"""

from __future__ import annotations

import importlib

import numpy as np

from benchmark.drivers import decode_sessions as base

#: limits of the comparison, as ``decode_sessions.BOUNDS`` defines them
#: (each kept row on its own), float32 as there.  bfloat16, measured on
#: the v5e at the cell's size (PERF.md, PR 35; 17 runs, 34 rows):
#: ``median`` 0.0158-0.0164 and, with the reference's matrices rounded
#: to float8 (the control, 4 runs), 0.137-0.150: the limit that tells
#: precisions apart, three times the one and a third of the other.
#: ``overall`` 0.019-0.040 and 0.152-0.157 under the control.  A share
#: keeps one sixteenth of the routed sum beside the whole shared
#: expert, so a routing choice that rounding flipped moves a position's
#: logits only where it names a held expert, and then by 0.08-0.20, not
#: by 0.3-0.8 as with every expert held: three or four such positions
#: of 64 read 0.04, fifteen would read the limit, which lies twice over
#: the largest reading and at half the control's.  With every expert
#: held that limit is 0.5, above the control: here both limits tell.
BOUNDS = {"float32": base.BOUNDS["float32"],
          "bfloat16": {"median": 0.05, "overall": 0.075}}

setup = base.setup


def held_counts(cfg, expert_tokens, steps: int):
    """``(experts touched, picks a step)`` by layer over the held ids,
    from a unit's picks by expert (``{vertex: counts over the router's
    width}``) and the unit's token steps."""
    held = np.asarray(cfg["builder_args"]["experts_held"])
    rows = [np.asarray(row)[held] for row in expert_tokens.values()]
    return ([float((row > 0).sum()) for row in rows],
            [float(row.sum()) / steps for row in rows])


def measure(run, state):
    record = base.measure(run, state)
    traffic, cfg = run.traffic, run.cfg
    family = importlib.import_module(f"benchmark.families.{cfg['family']}")
    rows, new = traffic["rows"], traffic["new_tokens"]
    touched, picks = held_counts(cfg, record["expert_tokens"], new)
    steps_traced = record["trace_items"] // rows
    record["kernels"]["moe_experts"] = {
        k: v * steps_traced for k, v in family.moe_experts_kernel(
            cfg, rows, touched, picks).items()}
    record["notes"].append(
        f"held experts touched in the last unit by layer {touched}, picks "
        f"on them a step {picks}")
    dtype = np.dtype(state["net"]._pol().compute_dtype).name
    for name, (value, _) in record["checks"].items():
        for kind, limit in BOUNDS[dtype].items():
            if name.startswith(f"logits_rel_err_{kind}_"):
                record["checks"][name] = [value, f"<={limit}"]
    record["correct"] = all(value <= float(limit[2:])
                            for value, limit in record["checks"].values())
    record["notes"].append(f"this cell's bounds {BOUNDS[dtype]}")
    return record
