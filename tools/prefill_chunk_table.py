"""Device seconds by scope for ONE prefill chunk of a decode cell, taken
with the program's own ``monitor.device_trace``, with the routed
experts in the form the layer picks and again with the dense form
forced:

    python tools/prefill_chunk_table.py \\
        [--workload xing4_29b_a4b.decode_b64_ctx4k] [--warm-chunks 60]

Builds the cell's served net as its driver does
(``benchmark/drivers/decode_sessions.py:build_net``), advances a fresh
state tree over ``--warm-chunks`` chunks of the cell's ``prefill_chunk``
tokens a row (so that the latent attention reads about half a ring, as
the mean chunk of the cell's prefill does), then traces three
``prefill_step`` calls and prints, a chunk, the busy seconds and the
rows of the layers' parts (``.experts``, ``.latent_attention``,
``.router``, ``.shared``, ``.sinkhorn``, and of a sparse decoder
``.indexer``, ``.select``, ``.sparse_attention``) summed over the
layers, with what is left; beside the seconds (PR 39) the HBM traffic
the compiler counted for each part in GB/s, the seconds whose bytes are
estimated from shapes (the Pallas kernels), and the idle time inside
and between programs with its largest gaps.  The executable store is left off: the second pass
patches the predicate in memory, which no digest of files sees.  Needs
the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time
import types
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PARTS = ("experts", "latent_attention", "router", "shared", "sinkhorn",
         "indexer", "select", "sparse_attention")
TRACED = 3


def by_part(report: dict) -> dict:
    """Seconds a chunk: busy, each part over all layers, the ten largest
    other rows; and, from the compiler's counts the trace carries
    (``cost_by_scope``), the HBM bytes a chunk and GB/s of the whole and
    of each part, the seconds whose bytes are estimated from shapes, and
    the idle time inside and between programs with its largest gaps."""
    out = {"busy_s": report["busy_s"] / TRACED,
           "window_s": report["window_s"] / TRACED,
           "idle_share": report["idle_share"],
           "hbm_gb": report["hbm_bytes"] / TRACED / 1e9,
           "hbm_gb_per_s": report["hbm_bytes"] / report["busy_s"] / 1e9,
           "estimated_s": report["estimated_s"] / TRACED,
           "uncounted_s": report["uncounted_s"] / TRACED,
           "idle_in_program_s": report["idle_in_program_s"] / TRACED,
           "idle_between_programs_s":
               report["idle_between_programs_s"] / TRACED,
           "gaps_in_program": [[scope, before, seconds / TRACED, n]
                               for scope, before, seconds, n
                               in report["gaps_in_program"][:5]]}
    parts = dict.fromkeys(PARTS, 0.0)
    traffic = dict.fromkeys(PARTS, 0.0)
    others = []
    for scope, pass_, seconds, read, written, _, _ in \
            report["cost_by_scope"]:
        part = scope.rsplit(".", 1)[-1]
        if scope.startswith("layer.") and part in parts:
            parts[part] += seconds / TRACED
            traffic[part] += (read + written) / TRACED
        else:
            others.append([f"{scope}/{pass_}", seconds / TRACED,
                           (read + written) / seconds / 1e9])
    out.update(parts)
    out["gb_per_s"] = {part: traffic[part] / seconds / 1e9
                       for part, seconds in parts.items() if seconds}
    out["other_s"] = out["busy_s"] - sum(parts.values())
    out["other_rows"] = others[:10]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="xing4_29b_a4b.decode_b64_ctx4k")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--warm-chunks", type=int, default=60)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "prefill_chunk"))
    args = ap.parse_args()

    import jax
    import numpy as np
    from benchmark.drivers import decode_sessions
    from benchmark.run import HERE, Lookup
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.nn.layers import decoder

    lookup = Lookup([HERE])
    cell = lookup.data("workloads", args.workload)
    run = types.SimpleNamespace(
        cfg=lookup.data("configs", cell["config"]),
        traffic=lookup.data("traffic", cell["traffic"]), seed=args.seed)
    device = jax.devices()[0]
    print("device", device.platform, device.device_kind, flush=True)
    t0 = time.perf_counter()
    net = decode_sessions.build_net(run)
    print(f"net built in {time.perf_counter() - t0:.1f} s", flush=True)
    rows, chunk = run.traffic["rows"], run.traffic["prefill_chunk"]
    ids = np.random.default_rng(args.seed).integers(
        0, run.cfg["vocab_size"],
        size=(args.warm_chunks + TRACED + 1, rows, chunk), dtype=np.int32)

    def chunks(form: str):
        """``by_part`` of the three traced chunks, and a chunk's wall."""
        net.__dict__.pop("_prefill_step_fn", None)      # trace anew
        carries = net._init_carries(rows,
                                    cache_len=run.traffic["ring_slots"])
        for block in ids[:args.warm_chunks + 1]:
            carries = net.prefill_step(carries, block)
        jax.block_until_ready(carries)
        trace_dir = os.path.join(args.out, "trace", form)
        shutil.rmtree(trace_dir, ignore_errors=True)
        with monitor.device_trace(trace_dir) as trace:
            t0 = time.perf_counter()
            for block in ids[args.warm_chunks + 1:]:
                carries = net.prefill_step(carries, block)
            jax.block_until_ready(carries)
            wall = (time.perf_counter() - t0) / TRACED
        shutil.rmtree(trace_dir, ignore_errors=True)
        if trace.report is None:
            return None, wall
        return by_part(trace.report), wall

    report = {"workload": args.workload, "warm_chunks": args.warm_chunks,
              "tokens_a_chunk": rows * chunk}
    forms = {"as_picked": contextlib.nullcontext(),
             "dense_forced": mock.patch.object(decoder, "moe_experts_path",
                                               lambda *a, **k: "dense")}
    for form, patch in forms.items():
        with patch:
            parts, wall = chunks(form)
        if parts is None:
            print("no TPU operation in the trace: this needs the chip",
                  file=sys.stderr)
            return 1
        report[form] = dict(parts, wall_s=wall)
        print(form, json.dumps(report[form]), flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, args.workload + ".json"), "w") as fh:
        json.dump(report, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
