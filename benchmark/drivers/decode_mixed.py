"""``decode_sessions`` for the configuration whose attention layers are
of two kinds, a window's (a ring that wraps) and full ones (a ring as
long as the context), under a parallel block with a held share of
routed experts: the same set-up, the same timed unit, the same
comparison (``drivers/decode_sessions.py`` says what they are), with
three things read for this cell.

The two attentions' counts go under ``record["kernels"]``:
``window_attention`` (the window's rows of keys and values in every
``sliding_attention`` layer) and ``full_attention`` (the context's rows
in every ``full_attention`` layer, at the unit's mean context), from
``families/gqa_window_share.py``; what ``decode_sessions`` filed under
``mla_decode`` (it asks every family for a function of that name) is
taken out, so ``mla_decode_roofline`` finds nothing here, as it should.

The routed experts' kernel is not counted, and the cell is not on
``moe_experts_roofline``'s list: at 8 tokens a step the experts take the
dense form, which reads all 16 held experts whichever of them a step's
64 picks named, so the algorithm's count follows the routing's luck from
seed to seed (PERF.md, PR 37: the same finding in the other share's
cell).

``correct`` is decided by this cell's own limits (``BOUNDS``), applied
to the errors ``decode_sessions`` measured.  ``BENCH_DECODE_CONTROL``
names a control (``CONTROLS``): ``fp8``, the reference with every matrix
rounded to ``float8_e4m3fn``; ``all_full``, the reference with no window
anywhere (a window layer sees every position up to the query's own);
``rope_all``, the reference with rotary on the full layers too.  A
control's reference is compiled after the window and held to the same
limits under ``control_...`` names in ``checks``, so a control run has
to come out ``correct: false``: where it does not, the comparison does
not see the precision, or the mechanism.
"""

from __future__ import annotations

import importlib
import os
import time

import numpy as np

from benchmark.drivers import decode_sessions as base

#: limits of the comparison, as ``decode_sessions.BOUNDS`` defines them
#: (each kept row on its own), float32 as there.  bfloat16, measured on
#: the v5e at the cell's size (PERF.md, PR 41; 21 runs, 42 rows, seeds
#: of their own): ``median`` 0.0095-0.0122; with the reference's matrices
#: rounded to float8 (the ``fp8`` control, 2 runs, 4 rows) 0.079-0.173,
#: with rotary on the full layer too (``rope_all``) 0.301-0.303, with no
#: window (``all_full``) 0.861-0.918: the limit that tells precisions
#: apart, 2.5 times the largest reading and 2.6 times under the smallest
#: control's.  ``overall`` 0.0102-0.0508; 0.112-0.205, 0.304 and
#: 0.861-0.919 under the controls: the limit lies 2.0 times over the
#: largest reading, under every control reading and 3 times under the
#: smallest of a mechanism control; it guards against a fault on a
#: minority of positions and is not what tells precisions apart (a
#: routing choice that flips moves a position's logits by 0.07-0.19
#: here: one of a token's 8 picks names a held expert on average, beside
#: four shared experts that every token meets; top-k is not continuous,
#: so a fresh seed can read higher, and the room is above the readings).
BOUNDS = {"float32": base.BOUNDS["float32"],
          "bfloat16": {"median": 0.03, "overall": 0.1}}

#: what ``BENCH_DECODE_CONTROL`` may name, and what it asks of the
#: reference
CONTROLS = {"fp8": {"fp8_weights": True}, "all_full": {"all_full": True},
            "rope_all": {"rope_all": True}}

setup = base.setup


def _control(run, state, name: str, record, bounds) -> None:
    """The control ``name``: its errors into ``record["checks"]`` under
    this cell's limits."""
    traffic = run.traffic
    new = traffic["new_tokens"]
    errors = base.compare(run, state, base.reference_for(
        run, state["net"], 1, traffic["prompt_tokens"] + new - 1, new,
        **CONTROLS[name]))
    for row in ("row_first", "row_last"):
        for kind, limit in bounds.items():
            record["checks"][f"control_{name}_logits_rel_err_{kind}_{row}"] \
                = [errors[row][kind], f"<={limit}"]
    record["notes"].append(
        f"control {name}: against the reference with {CONTROLS[name]} "
        f"{errors} (has to read over one of {bounds})")


def measure(run, state):
    # this cell's controls are judged here: ``decode_sessions`` would
    # only note its own
    control = os.environ.pop("BENCH_DECODE_CONTROL", None)
    t0 = time.perf_counter()
    try:
        record = base.measure(run, state)
    finally:
        if control is not None:
            os.environ["BENCH_DECODE_CONTROL"] = control
    traffic, cfg = run.traffic, run.cfg
    family = importlib.import_module(f"benchmark.families.{cfg['family']}")
    rows, new = traffic["rows"], traffic["new_tokens"]
    steps_traced = record["trace_items"] // rows
    kernels = record["kernels"]
    del kernels["mla_decode"]
    kernels.pop("moe_experts", None)
    # the rows a query of the unit sees on average: a generated token at
    # place j of the unit stands at position prompt - 1 + j
    context = traffic["prompt_tokens"] - 1 + (new + 1) / 2.0
    per_step = {
        "window_attention": family.window_attention_kernel(
            cfg, rows, min(context, cfg["sliding_window"])),
        "full_attention": family.full_attention_kernel(cfg, rows, context)}
    for name, counts in per_step.items():
        kernels[name] = {k: v * steps_traced for k, v in counts.items()}
    dtype = np.dtype(state["net"]._pol().compute_dtype).name
    bounds = BOUNDS[dtype]
    for name, (value, _) in record["checks"].items():
        for kind, limit in bounds.items():
            if name.startswith(f"logits_rel_err_{kind}_"):
                record["checks"][name] = [value, f"<={limit}"]
    if control in CONTROLS:
        _control(run, state, control, record, bounds)
    record["correct"] = all(value <= float(limit[2:])
                            for value, limit in record["checks"].values())
    record["notes"] += [
        f"this cell's bounds {bounds}",
        f"set-up phases (s) {state['setup_phases']}; the window "
        f"{record['window_s']:.2f} s; the comparison and what else follows "
        f"the window {time.perf_counter() - t0 - record['window_s']:.2f} s"]
    return record
