"""``decode_sessions`` for the configuration whose attention reads the
rows an indexer selects and whose experts are a held share: the same
set-up, the same timed unit, the same comparison
(``drivers/decode_sessions.py`` says what they are), with three things
read for this cell.

The new kernels' counts go under ``record["kernels"]``: ``indexer`` (the
indexer-key ring at its capacity and the indexer's matrices) and
``sparse_attention`` (the selected rows of both rings), from
``families/gqa_sparse_share.py``; what ``decode_sessions`` filed under
``mla_decode`` (it asks every family for a function of that name) is
moved to ``sparse_attention``, so ``mla_decode_roofline`` finds nothing
here, as it should.

The routed experts' kernel is not counted, and the cell is not on
``moe_experts_roofline``'s list: at 8 tokens a step the experts take the
dense form, which reads all 16 held experts whichever of them a step's
64 picks named, and part of that read is fetched under the scope's
neighbours (151 MB a layer in 0.171 ms: 883 GB/s of a peak of 819), so
the algorithm's count follows the routing's luck from seed to seed and
the form's own count passes 100% (PERF.md, PR 37).

``correct`` is decided by this cell's own limits (``BOUNDS``), applied
to the errors ``decode_sessions`` measured.  ``BENCH_DECODE_CONTROL``
names a control (``CONTROLS``): ``fp8``, the reference with every matrix
rounded to ``float8_e4m3fn``, or ``dense``, the reference with the
selection left out (every query attends over every position up to its
own).  A control's reference is compiled after the window and held to
the same limits under ``control_...`` names in ``checks``, so a control
run has to come out ``correct: false``: where it does not, the
comparison does not see the precision, or the mechanism.

A selection is not continuous in its scores: a position near the
2,048th score may be in for the program and out for the reference.  A
control run also notes how much of the selection the two share where
both see the same input, in the first layer: for a kept row's prompt
and generated ids, the first layer's selection at the generated
positions by the program's own layer and ops (its dtype) and by the
reference's (``reference.selection``, float32), and the share of the
reference's selected positions that the program selected too
(``selection shared``; nothing is judged by it).
"""

from __future__ import annotations

import importlib
import os

import numpy as np

from benchmark.drivers import decode_sessions as base

#: limits of the comparison, as ``decode_sessions.BOUNDS`` defines them
#: (each kept row on its own), float32 as there.  bfloat16, measured on
#: the v5e at the cell's size (PERF.md, PR 37; 28 runs, 56 rows, seeds
#: of their own): ``median`` 0.0150-0.0317; with the reference's
#: matrices rounded to float8 (the ``fp8`` control, 5 runs, 10 rows)
#: 0.0697-0.0850, with its selection left out (the ``dense`` control, 3
#: runs, 6 rows) 0.167-0.297: the limit that tells precisions apart,
#: 1.58 times the largest reading and 1.39 times under the smallest
#: control's.  ``overall`` 0.0184-0.0477; 0.0716-0.0908 and 0.179-0.306
#: under the controls: the limit lies 1.57 times over the largest
#: reading and 2.4 times under the smallest ``dense`` reading; it guards
#: against a fault on a minority of positions and does not tell
#: precisions apart (4 of the 10 ``fp8`` rows pass it, all 10 fail the
#: median).  A position's logits move little where a routing choice
#: flips (one of a token's 8 picks names a held expert on average, and
#: there is no shared expert to dwarf it: 0.03-0.16 at the worst
#: position of a row) or where a selection flips near the 2,048th score
#: (program and reference share 99.5-99.7% of the selected positions in
#: the first layer, never under 99.0% at any position), so both limits
#: are the other share's cell's.
BOUNDS = {"float32": base.BOUNDS["float32"],
          "bfloat16": {"median": 0.05, "overall": 0.075}}

#: what ``BENCH_DECODE_CONTROL`` may name, and what it asks of the
#: reference
CONTROLS = {"fp8": {"fp8_weights": True}, "dense": {"dense_attention": True}}

setup = base.setup


def _shared_selection(run, net):
    """``f(params, ids (1, tokens)) -> (mean, least)`` over the last
    ``new_tokens`` positions of the share of the reference's selected
    positions that the program's first layer selects too."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference.mla_moe_decoder import rmsnorm
    from deeplearning4j_tpu.ops.attention import indexer_scores, select_mask
    cfg, new = run.cfg, run.traffic["new_tokens"]
    reference = importlib.import_module(
        f"benchmark.reference.{cfg['reference']}")
    norm, layer = (net.vertices[n].layer for n in ("L0_attn_norm", "L0_attn"))
    dtype = net._pol().compute_dtype

    def program(params, ids):
        table, pn, pa = (params["embed"]["W"], params["L0_attn_norm"],
                         params["L0_attn"])
        x = jnp.take(table, ids, axis=0)
        cast = lambda tree: jax.tree.map(lambda a: a.astype(dtype), tree)
        h = norm.forward(cast(pn), {}, x.astype(dtype), train=False)[0]
        positions = jnp.arange(ids.shape[1], dtype=jnp.int32)
        q_idx, w_idx, k_idx = layer.indexer(cast(pa), h, positions)
        visible = positions[None, :] <= positions[-new:, None]
        mine = select_mask(
            indexer_scores(q_idx[:, -new:], w_idx[:, -new:], k_idx),
            visible[None], layer.topk)[0]
        with jax.default_matmul_precision("highest"):
            theirs = reference.selection(cfg, pa, rmsnorm(
                x[0].astype(jnp.float32), float(cfg["rms_norm_eps"]),
                pn["gain"].astype(jnp.float32)), new)
        shared = (jnp.sum(mine & theirs, axis=-1)
                  / jnp.sum(theirs, axis=-1))
        return jnp.mean(shared), jnp.min(shared)

    return jax.jit(program)


def _control(run, state, name: str, record, bounds) -> None:
    """The control ``name``: its errors into ``record["checks"]`` under
    this cell's limits, and the selection the program shares with the
    reference into the notes."""
    traffic = run.traffic
    rows, new = traffic["rows"], traffic["new_tokens"]
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
    program = _shared_selection(run, state["net"])
    gen, ids, shared = state["last"], state["ids"], {}
    for row_name, row in (("row_first", 0), ("row_last", rows - 1)):
        sequence = np.concatenate([ids[row], gen.ids[row, :-1]])[None]
        mean, least = program(state["net"].params,
                              sequence.astype(np.int32))
        shared[row_name] = {"mean": float(mean), "least": float(least)}
    record["notes"].append(
        f"selection shared with the reference in the first layer, over the "
        f"generated positions {shared}")


def measure(run, state):
    # this cell's controls are judged here: ``decode_sessions`` would
    # only note its own
    control = os.environ.pop("BENCH_DECODE_CONTROL", None)
    try:
        record = base.measure(run, state)
    finally:
        if control is not None:
            os.environ["BENCH_DECODE_CONTROL"] = control
    traffic, cfg = run.traffic, run.cfg
    family = importlib.import_module(f"benchmark.families.{cfg['family']}")
    rows = traffic["rows"]
    steps_traced = record["trace_items"] // rows
    kernels = record["kernels"]
    del kernels["mla_decode"]
    kernels.pop("moe_experts", None)
    per_step = {
        "sparse_attention": family.sparse_attention_kernel(
            cfg, rows, min(traffic["prompt_tokens"],
                           cfg["sa_config"]["topk"])),
        "indexer": family.indexer_kernel(cfg, rows, traffic["ring_slots"])}
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
    record["notes"].append(f"this cell's bounds {bounds}")
    return record
