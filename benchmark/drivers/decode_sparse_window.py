"""``decode_sessions`` for the configuration whose latent attention layers
are of two kinds, full ones over the latent rows an indexer selects (a
ring that grows) and a window's (a ring that wraps), each gated headwise,
with a held share of routed experts beside a shared one: the same
set-up, the same timed unit, the same comparison
(``drivers/decode_sessions.py`` says what they are), with three things
read for this cell.

The new kernels' counts go under ``record["kernels"]``: ``indexer`` (the
indexer-key ring at its capacity and the indexer's matrices),
``sparse_latent_attention`` (the selected latent rows of the full
layers) and ``window_latent_attention`` (the window's latent rows of the
window layers), from ``families/mla_sparse_window_share.py``; what
``decode_sessions`` filed under ``mla_decode`` (it asks every family for
a function of that name) is taken out, so ``mla_decode_roofline`` finds
nothing here, as it should.

The routed experts' kernel is not counted, and the cell is not on
``moe_experts_roofline``'s list: at 32 tokens a step the experts take the
dense form, which reads all 8 held experts whichever of them a step's
256 picks named (PERF.md section 6: the same finding in the other
shares' cells).

``correct`` is decided by this cell's own limits (``BOUNDS``), applied
to the errors ``decode_sessions`` measured, and by one check of this
cell's (``WINDOW_BOUNDS``): the first window layer's output at the
generated positions of the first row, computed by the program's served
layer in the forms the timed path takes (prefill chunks, then the token
step's one position a dispatch at the cell's rows, over a ring that has
wrapped) and by the reference, both from the reference's own input to
that layer.  With weights drawn at
N(0, 0.02) a window layer's attention is nearly uniform, so whether it
averages 513 rows or every row moves the logits by less than bf16 does
(PERF.md section 6): the logits alone cannot see the window, the layer's
output can.  ``BENCH_DECODE_CONTROL``
names a control (``CONTROLS``): ``fp8``, the reference with every matrix
rounded to ``float8_e4m3fn``; ``dense``, the reference with the
selection left out (a full layer's query attends over every position up
to its own); ``all_full``, the reference with no window; ``no_gate``,
the reference without the headwise gates.  A control is judged by
``decode_mixed``'s own control step, asked for this cell's controls, so
a control run has to come out ``correct: false``: where it does not, the
comparison does not see the precision, or the mechanism.
"""

from __future__ import annotations

import contextlib
import importlib
import os
import time

import numpy as np

from benchmark.drivers import decode_mixed as mixed
from benchmark.drivers import decode_sessions as base

#: limits of the comparison, as ``decode_sessions.BOUNDS`` defines them
#: (each kept row on its own), float32 as there.  bfloat16, measured on
#: the v5e at the cell's size (PERF.md section 6; 6 runs, 12 rows, seeds of
#: their own): ``median`` 0.0848-0.0907; with the reference's matrices
#: rounded to float8 (``fp8``) 0.269-0.274, without the selection
#: (``dense``) 1.11-1.12, without the gates (``no_gate``) 0.747-0.759:
#: the limit lies 1.65 times over the largest reading and 1.8 times under
#: the smallest control's.  ``overall`` 0.0858-0.0904; 0.271-0.273,
#: 1.11-1.12 and 0.749-0.759 under the same controls: the same limit.
#: Three to four times the other latent cells' median (0.016 in
#: ``ax_k1``): the selection turns bf16's rounding of the indexer's
#: scores into rows attended or not (the small decoder on the CPU, dense
#: forms in bf16 against float32: 0.057 with its selection, 0.029
#: without; PERF.md section 6).
BOUNDS = {"float32": base.BOUNDS["float32"],
          "bfloat16": {"median": 0.15, "overall": 0.15}}

#: the limit of the window layer's check, by compute dtype: the relative
#: L2 error over the generated positions of the first row.  bfloat16,
#: measured on the v5e at the cell's size (PERF.md section 6): the
#: program 0.0042; the reference with its matrices rounded to float8
#: (``fp8``) 0.0416, without the gates (``no_gate``) 0.566, with no
#: window (``all_full``) 0.759: the limit lies 3.1 times over the
#: program and 3.2 times under ``fp8``.
WINDOW_BOUNDS = {"float32": 1e-4, "bfloat16": 0.013}

#: what ``BENCH_DECODE_CONTROL`` may name, and what it asks of the
#: reference
CONTROLS = {"fp8": {"fp8_weights": True}, "dense": {"dense_attention": True},
            "all_full": {"all_full": True}, "no_gate": {"no_gate": True}}

def setup(run):
    state = base.setup(run)
    state["window_check"] = window_programs(run, state)
    return state


@contextlib.contextmanager
def _these_controls():
    """``decode_mixed``'s control step reads its own table of controls:
    for the call, the table is this cell's."""
    kept = dict(mixed.CONTROLS)
    mixed.CONTROLS.clear()
    mixed.CONTROLS.update(CONTROLS)
    try:
        yield
    finally:
        mixed.CONTROLS.clear()
        mixed.CONTROLS.update(kept)


def _theirs(run, control):
    """The reference's side of the window layer's check, jitted:
    ``(norm params, layer params, stream) -> (the normed input of the
    positions the program's side is fed, in the program's dtype; the
    reference's attention output at the generated positions)``, with
    ``control`` (``CONTROLS``' keys) asked of the reference."""
    import jax
    import jax.numpy as jnp
    cfg = run.cfg
    module = importlib.import_module(f"benchmark.reference.{cfg['reference']}")
    new = run.traffic["new_tokens"]

    def theirs(pn, pa, x, fed, dtype):
        with jax.default_matmul_precision("highest"):
            h = module.rmsnorm(x, float(cfg["rms_norm_eps"]),
                               pn["gain"].astype(jnp.float32))
            return (h[:, -fed:].astype(dtype),
                    module.attention(cfg, pa, h, sliding=True,
                                     **control)[0, -new:])
    return jax.jit(theirs, static_argnums=(3, 4))


def window_programs(run, state):
    """The window layer's check compiled ahead of time, so that nothing
    compiles after set-up: the first window layer's vertex; the
    program's served layer as the timed path runs it, at the cell's
    rows, for a prefill chunk of ``prefill_chunk`` positions and for the
    token step's one position (the form each takes, by the layer's own
    predicate); its empty ring; how many whole chunks come before the
    generated positions (enough that every slot holds a position of the
    sequence, the window of the first generated one among them, and the
    ring has wrapped); and the reference's side (:func:`_theirs`)
    without a control."""
    import jax
    import jax.numpy as jnp
    cfg, net, traffic = run.cfg, state["net"], run.traffic
    rows, chunk = traffic["rows"], traffic["prefill_chunk"]
    at = cfg["layer_types"][:cfg["num_hidden_layers"]].index(
        "sliding_attention")
    vertex = f"L{at}_attn"
    layer, dtype = net.vertices[vertex].layer, net._pol().compute_dtype
    ring, _ = layer.init_carry(rows, dtype)
    carry = (ring, jnp.zeros((), jnp.int32))
    chunks = -(-ring.shape[1] // chunk) + 1
    if chunks * chunk > traffic["prompt_tokens"] - 1:
        raise ValueError(f"{chunks} chunks of {chunk} fill the window ring "
                         f"of {ring.shape[1]} slots: longer than the prompt")
    fed = chunks * chunk + traffic["new_tokens"]
    tokens = traffic["prompt_tokens"] + traffic["new_tokens"] - 1
    stream = jax.ShapeDtypeStruct((1, tokens, cfg["hidden_size"]),
                                  jnp.float32)
    step = jax.jit(lambda p, x, c: layer.forward_seq(p, x, c, train=False))
    laid = net.served_params()[vertex]
    return {"vertex": vertex, "ring": ring, "chunks": chunks, "chunk": chunk,
            "rows": rows, "fed": fed, "stream": stream, "dtype": dtype,
            "paths": {t: layer.attention_path(t, carry) for t in (chunk, 1)},
            "steps": {t: step.lower(laid, jax.ShapeDtypeStruct(
                (rows, t, cfg["hidden_size"]), dtype), carry).compile()
                for t in (chunk, 1)},
            "theirs": _compile_theirs(run, net, vertex, stream, fed, dtype)}


def _compile_theirs(run, net, vertex, stream, fed, dtype, **control):
    return _theirs(run, control).lower(
        net.params[f"{vertex}_norm"], net.params[vertex], stream, fed,
        dtype).compile()


def window_layer_error(run, state, **control) -> float:
    """The relative L2 error of the first window layer's output at the
    last unit's generated positions of row 0, the program's side by the
    timed path's own forms: the served layer, fed the reference's own
    normed input to that layer (in the program's dtype; every one of the
    cell's rows the same), whole prefill chunks from a ring that starts
    empty at the chunk boundary the engine's prefill used (so the ring
    wraps as the cell's does), then the generated positions one a
    dispatch, as the token step takes them; against the reference's
    attention of that layer over the whole sequence (with ``control``,
    whose side is compiled here: a control run compiles after its window
    anyway)."""
    programs = state["window_check"]
    net, ref, new = state["net"], state["reference"], run.traffic["new_tokens"]
    vertex, params = programs["vertex"], net.params
    theirs = programs["theirs"] if not control else _compile_theirs(
        run, net, vertex, programs["stream"], programs["fed"],
        programs["dtype"], **control)
    gen, ids = state["last"], state["ids"]
    sequence = np.concatenate([ids[0], gen.ids[0, :-1]])[None]
    x = ref.programs["embed"](params["embed"]["W"], sequence)
    for kind, names in ref.layers():
        if names[1] == vertex:
            break
        x = ref.programs[kind](*(params[n] for n in names), x)
    fed, want = theirs(params[f"{vertex}_norm"], params[vertex], x)
    fed, chunk, steps = np.asarray(fed), programs["chunk"], programs["steps"]
    wide = lambda a: np.broadcast_to(a, (programs["rows"],) + a.shape[1:])
    carry = (programs["ring"],
             np.asarray(sequence.shape[1] - fed.shape[1], np.int32))
    laid, out = net.served_params()[vertex], []
    for i in range(programs["chunks"]):
        _, carry = steps[chunk](laid, wide(fed[:, i * chunk:(i + 1) * chunk]),
                                carry)
    for at in range(programs["chunks"] * chunk, fed.shape[1]):
        # row 0 taken on the host: indexing the device array would
        # compile after the window
        y, carry = steps[1](laid, wide(fed[:, at:at + 1]), carry)
        out.append(np.asarray(y, np.float64)[0, 0])
    return base._rel_l2(np.stack(out), want)


def _window_check(run, state, record, limit, control=None) -> None:
    """The window layer's check into ``record["checks"]``: the
    program's, or under a control the control reference's."""
    name = "window_layer_rel_err_row_first"
    error = window_layer_error(run, state, **(CONTROLS[control]
                                              if control else {}))
    record["checks"][f"control_{control}_{name}" if control else name] = [
        error, f"<={limit}"]


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
        "indexer": family.indexer_kernel(cfg, rows, traffic["ring_slots"]),
        "sparse_latent_attention": family.sparse_latent_attention_kernel(
            cfg, rows, min(context, cfg["index_topk"])),
        "window_latent_attention": family.window_latent_attention_kernel(
            cfg, rows, min(context, cfg["sliding_window_size"]))}
    for name, counts in per_step.items():
        kernels[name] = {k: v * steps_traced for k, v in counts.items()}
    dtype = np.dtype(state["net"]._pol().compute_dtype).name
    bounds = BOUNDS[dtype]
    for name, (value, _) in record["checks"].items():
        for kind, limit in bounds.items():
            if name.startswith(f"logits_rel_err_{kind}_"):
                record["checks"][name] = [value, f"<={limit}"]
    window_limit = WINDOW_BOUNDS[dtype]
    _window_check(run, state, record, window_limit)
    if control in CONTROLS:
        with _these_controls():
            mixed._control(run, state, control, record, bounds)
        _window_check(run, state, record, window_limit, control)
    record["correct"] = all(value <= float(limit[2:])
                            for value, limit in record["checks"].values())
    record["notes"] += [
        f"this cell's bounds {bounds}",
        f"the window layer's check by the forms "
        f"{state['window_check']['paths']} (positions a dispatch: form)",
        f"selections that settled ties in the last unit by layer "
        f"{state['last'].selection_tied}",
        f"set-up phases (s) {state['setup_phases']}; the window "
        f"{record['window_s']:.2f} s; the comparison and what else follows "
        f"the window {time.perf_counter() - t0 - record['window_s']:.2f} s"]
    return record
