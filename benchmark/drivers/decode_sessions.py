"""Saturated static-batch decode through ``InferenceEngine`` sessions:
conversations that share one long prefilled prompt, answered again and
again.

Set-up builds the configuration's net for serving (``init(for_inference=
True)``: the parameter dtype's bytes a parameter), draws ``rows`` prompts
of ``prompt_tokens`` ids from the seed over the whole vocabulary, puts
all but each prompt's last token into a snapshot session through the
engine's own chunked prefill (``prefill_chunk`` tokens a dispatch, rings
of ``ring_slots``), and runs one unit to compile the fork and the token
step.  A timed **unit** is: fork the snapshot (which drops the last unit's
session first), generate ``new_tokens`` a row greedily from the prompts' last
tokens (one dispatch a token, ids fed back on the device), the last ids
on the host.  Units of equal work run back to back until the window is
full: closed loop, no think time, so the rate is what the system
sustains and ``throughput`` (generated tokens a second by the median
unit) is what is judged.

``correct`` is decided after the window from what the timed path itself
produced: the float32 logits the last unit's token steps kept (rows 0
and ``rows - 1``, every generated position) against the plain
reference's full forward (``reference/<cfg.reference>.py``, float32 at
"highest" precision, no cache) over those rows' prompt plus the ids the
unit generated.  Logits, never tokens; two limits a row (``BOUNDS``).  The reference's programs are
compiled in set-up, ahead of time, so nothing compiles in the window or
after it.

``BENCH_DECODE_CONTROL=fp8`` adds the control reading (the same
comparison against the reference computed with every matrix rounded to
``float8_e4m3fn``), which has to read over a limit; it compiles the
reference a second time, after the window.
"""

from __future__ import annotations

import importlib
import os
import statistics
import time

import numpy as np

from benchmark import nets, units

#: limits of the comparison, by compute dtype, each kept row on its own:
#: ``median``: the median over the generated positions of the relative
#: L2 error of a position's logits; ``overall``: the relative L2 error
#: of all the row's kept logits together.  The median is the limit that
#: tells precisions apart; the overall error is ruled by the few
#: positions at which rounding flipped a routing choice between two
#: nearly tied experts (top-k is not continuous: such a position reads
#: 0.3-0.8 whatever the precision), and guards against a fault on a
#: minority of positions.  float32: both sides in float32, another
#: order of accumulation.  bfloat16, measured on the v5e (PERF.md, PR
#: 30; 16 runs, 32 rows): median 0.0103-0.0164, overall 0.073-0.252;
#: the fp8-weights control: median 0.313-0.367, overall 0.383-0.404.
BOUNDS = {"float32": {"median": 1e-4, "overall": 1e-4},
          "bfloat16": {"median": 0.05, "overall": 0.5}}


def _rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def build_net(run):
    cfg = run.cfg
    conf = nets._resolve(cfg["builder"])(
        cfg, cache_len=run.traffic["ring_slots"], seed=run.seed,
        **cfg.get("builder_args", {}))
    return nets._resolve(cfg["container"])(conf).init(for_inference=True)


def reference_for(run, net, batch: int, tokens: int, last: int, **kw):
    """The configuration's reference with its programs compiled ahead of
    time for ``(batch, tokens)`` ids and the net's own parameter tree."""
    import jax
    import jax.numpy as jnp
    module = importlib.import_module(
        f"benchmark.reference.{run.cfg['reference']}")
    ref = module.Forward(run.cfg, last=last, **kw)
    shape = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    params = shape(net.params)
    stream = jax.eval_shape(
        ref.programs["embed"], params["embed"]["W"],
        jax.ShapeDtypeStruct((batch, tokens), jnp.int32))
    compiled = {"embed": ref.programs["embed"].lower(
        params["embed"]["W"],
        jax.ShapeDtypeStruct((batch, tokens), jnp.int32)).compile()}
    for kind, names in ref.layers():
        if kind not in compiled:
            compiled[kind] = ref.programs[kind].lower(
                *(params[n] for n in names), stream).compile()
    compiled["head"] = ref.programs["head"].lower(
        params["final_norm"], params["head"], stream).compile()
    ref.programs = compiled
    return ref


def _peak_gb(device) -> float:
    stats = device.memory_stats() or {}
    return round(stats.get("peak_bytes_in_use", 0) / 1e9, 3)


class _Laps(units.Laps):
    """Set-up laps that also keep the device's ``peak_bytes_in_use`` at
    the end of each phase (GB): which phase sets the run's peak."""

    def __init__(self, device):
        super().__init__()
        self.device, self.peaks_gb = device, {}

    def __call__(self, name: str) -> None:
        super().__call__(name)
        self.peaks_gb[name] = _peak_gb(self.device)


def setup(run):
    from deeplearning4j_tpu.serving import InferenceEngine
    traffic = run.traffic
    laps = _Laps(run.devices[0])
    net = build_net(run)
    laps("build_net")
    rows, prompt = traffic["rows"], traffic["prompt_tokens"]
    ids = np.random.default_rng(run.seed).integers(
        0, run.cfg["vocab_size"], size=(rows, prompt), dtype=np.int32)
    engine = InferenceEngine(net, max_batch_size=rows, devices=run.devices,
                             name=run.cell.get("name", "decode"),
                             session_ttl_s=0.0, max_sessions=4).start()
    engine.prefill_session("prompt", ids[:, :-1],
                           chunk=traffic["prefill_chunk"],
                           cache_len=traffic["ring_slots"])
    import jax
    jax.block_until_ready(engine.sessions.get_carries("prompt"))
    laps("prefill")
    state = {"net": net, "engine": engine, "ids": ids, "last": None,
             "setup_phases": laps.phases, "laps": laps}
    _unit(run, state)                       # compiles fork and token step
    laps("warm_unit")
    state["reference"] = reference_for(
        run, net, 1, prompt + traffic["new_tokens"] - 1,
        traffic["new_tokens"])
    laps("reference_compile")
    return state


def _unit(run, state) -> float:
    engine = state["engine"]
    t0 = time.perf_counter()
    with run.annotate("bench/fork"):
        engine.fork_session("prompt", "unit")   # drops the last unit's
    with run.annotate("bench/generate"):
        state["last"] = engine.generate("unit", state["ids"][:, -1:],
                                        run.traffic["new_tokens"])
    return time.perf_counter() - t0


def compare(run, state, reference) -> dict:
    """``{row: {"median", "overall", "max"}}`` for row 0 and the last
    row: relative L2 errors of the last unit's kept logits against
    ``reference`` over the row's prompt + generated ids (by position:
    their median and largest; ``overall``: all positions together)."""
    gen, ids = state["last"], state["ids"]
    got = np.stack([np.asarray(k) for k in gen.kept_logits], axis=1)
    out = {"finite": bool(np.isfinite(got).all())}
    for name, row, kept in (("row_first", 0, got[0]),
                            ("row_last", ids.shape[0] - 1, got[1])):
        # one row at a time: the reference's float32 activations of a
        # whole sequence are what it needs beside the model
        sequence = np.concatenate([ids[row], gen.ids[row, :-1]])[None]
        want = np.asarray(reference(state["net"].params, sequence))[0]
        by_position = [_rel_l2(k, w) for k, w in zip(kept, want)]
        out[name] = {"median": float(np.median(by_position)),
                     "overall": _rel_l2(kept, want),
                     "max": float(np.max(by_position))}
    return out


def measure(run, state):
    from deeplearning4j_tpu import monitor
    traffic, cfg = run.traffic, run.cfg
    family = importlib.import_module(f"benchmark.families.{cfg['family']}")
    rows, new = traffic["rows"], traffic["new_tokens"]
    walls = units.fill_window(run, lambda: _unit(run, state))
    peaks_gb = dict(state["laps"].peaks_gb, window=_peak_gb(run.devices[0]))
    books = {"monitor_after": monitor.snapshot(),
             "spans": monitor.tracer().events()}
    session_bytes = state["engine"].sessions.state_bytes()
    state["engine"].sessions.clear_all()         # room for the reference
    first = traffic["trace_after_units"]
    traced = min(traffic["trace_units"], max(0, len(walls) - first))

    errors = compare(run, state, state["reference"])
    dtype = np.dtype(state["net"]._pol().compute_dtype).name
    bounds = BOUNDS[dtype]
    checks = {f"logits_rel_err_{kind}_{row}": [errors[row][kind],
                                               f"<={limit}"]
              for row in ("row_first", "row_last")
              for kind, limit in bounds.items()}
    checks["nonfinite_logits"] = [0 if errors["finite"] else 1, "<=0"]
    within = all(value <= float(limit[2:])
                 for value, limit in checks.values())
    notes = []
    if os.environ.get("BENCH_DECODE_CONTROL") == "fp8":
        control = compare(run, state, reference_for(
            run, state["net"], 1, traffic["prompt_tokens"] + new - 1, new,
            fp8_weights=True))
        notes.append(f"control: against the reference with fp8 weights "
                     f"{control} (has to read over one of {bounds})")
    state["engine"].stop()
    peaks_gb["check"] = _peak_gb(run.devices[0])

    # experts that received a token, a step, by layer: the last unit's
    # counts say which experts the unit touched at all; a step touches
    # at most rows * top_k of them
    picks = state["last"].expert_tokens
    touched = [float(min((row > 0).sum(),
                         rows * cfg["num_experts_per_tok"]))
               for row in picks.values()]
    steps_traced = traced * new
    kernels = {}
    if touched:
        per_step = family.moe_experts_kernel(cfg, rows, touched)
        kernels["moe_experts"] = {k: v * steps_traced
                                  for k, v in per_step.items()}
    per_step = family.mla_decode_kernel(cfg, rows, traffic["ring_slots"])
    kernels["mla_decode"] = {k: v * steps_traced for k, v in per_step.items()}
    mean_context = traffic["prompt_tokens"] - 1 + (new + 1) / 2.0
    return {
        "checks": checks,
        "correct": bool(within),
        "attempted": len(walls), "failed": 0,
        "window_s": float(sum(walls)),
        "unit_walls_s": [float(w) for w in walls],
        "items_per_unit": rows * new,
        "items": rows * new * len(walls),
        "steps": new * len(walls),
        "trace_items": rows * new * traced,
        "flops_per_item": 2 * sum(layer["macs"] for layer in family.layers(
            cfg, context=mean_context)),
        "kernels": kernels,
        "expert_tokens": {k: [int(x) for x in v] for k, v in picks.items()},
        "notes": [
            f"policy {state['net']._pol().describe()}",
            f"session state bytes in the window {session_bytes}",
            f"peak_bytes_in_use (GB) at the end of each phase "
            f"{peaks_gb}",
            f"check errors {errors} bounds {bounds}", *notes,
            f"experts touched in the last unit by layer {touched}",
            f"units {len(walls)} walls_s {[round(w, 4) for w in walls]}",
            f"tokens/s by the median unit "
            f"{rows * new / statistics.median(walls):.2f} by the window's "
            f"mean {rows * new * len(walls) / sum(walls):.2f}"],
        **books,
    }
