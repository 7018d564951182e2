"""Device seconds by scope for one warm unit of a benchmark cell's
traffic, taken with the program's own ``monitor.device_trace``:

    python tools/device_scope_table.py --workload resnet50.fit_cached \\
        [--seed 1] [--out chiprun_out/scopes]

Builds the cell's net and data through ``benchmark/nets.py`` (the
benchmark's builder, so the program is the cell's), runs the unit the
``fit_cached`` driver times (one fused ``fit`` call ending in a blocking
``score()``) once to compile and twice untraced, then once under
``monitor.device_trace``, prints the table of PERF.md section 5 (each
row with its HBM GB/s and TFLOP/s as the compiler counted them, the
idle time inside and between programs with its largest gaps: PR 39)
and writes the report as JSON.  Needs the chip: a CPU trace has no device
plane.  Since PR 29 the benchmark shows the same rows itself
(``--trace 1``: ``breakdown`` and ``record["trace"]["by_scope"]``, for
every cell, the serving ones too); this tool is for a ``fit`` cell's
unit outside a benchmark window.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "scopes"))
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the .xplane.pb under --out")
    args = ap.parse_args()

    from benchmark import nets
    from benchmark.run import HERE, Lookup
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.monitor.device_trace import table
    from deeplearning4j_tpu.serving import compile_cache
    compile_cache.enable()
    import jax

    lookup = Lookup([HERE])
    cell = lookup.data("workloads", args.workload)
    cfg = lookup.data("configs", cell["config"])
    traffic = lookup.data("traffic", cell["traffic"])
    print("device", jax.devices()[0].platform, jax.devices()[0].device_kind,
          flush=True)
    net = nets.build_net(cfg, args.seed)
    x, y = nets.images(cfg, traffic["examples"], args.seed, stream=1)
    iterator = ListDataSetIterator(DataSet(x, y), traffic["batch"])

    def unit() -> float:
        t0 = time.perf_counter()
        net.fit(iterator, epochs=traffic["epochs"])
        net.score()
        return time.perf_counter() - t0

    print(f"first unit (compiles) {unit():.3f} s", flush=True)
    untraced = [unit(), unit()]
    trace_dir = os.path.join(args.out, "trace", args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    with monitor.device_trace(trace_dir) as trace:
        traced = unit()
    if not args.keep_trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    after = unit()
    print(f"unit wall: untraced {untraced[0]:.4f} {untraced[1]:.4f} s, "
          f"traced {traced:.4f} s, untraced after {after:.4f} s")
    if trace.report is None:
        print("no TPU operation in the trace: this needs the chip",
              file=sys.stderr)
        return 1
    report = dict(trace.report, workload=args.workload, seed=args.seed,
                  unit_walls_s={"untraced": untraced + [after],
                                "traced": traced})
    print(table(report, args.top))
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, args.workload + ".json"), "w") as fh:
        json.dump(report, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
