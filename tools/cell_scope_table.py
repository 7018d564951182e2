"""The table of PERF.md section 5 for ANY benchmark cell, a decode cell
too: one traced run of the cell through the benchmark's own ``main``
(``--trace 1``), then the program's reduction of that run's window by
scope, every row with the HBM GB/s and TFLOP/s the compiler counted:

    python tools/cell_scope_table.py --workload <cell> [--seed 1] \\
        [--seconds 20] [--top 40] [--out chiprun_out/scopes]

The harness deletes a traced run's ``.xplane.pb`` after its last reader;
the readers of PR 39 keep the program's report of it
(``benchmark/scope_costs.py``), which this prints
(``monitor.device_trace.table``) and writes as JSON beside the run's
result line.  ``tools/device_scope_table.py`` is the same table for one
warm unit of a ``fit`` cell outside a benchmark window.  Needs the chip.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "scopes"))
    args = ap.parse_args()
    from benchmark import run, scope_costs
    # the module: ``monitor.device_trace`` is the context manager
    device_trace = importlib.import_module(
        "deeplearning4j_tpu.monitor.device_trace")
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"])
    report = next(iter(scope_costs._reduced.values()), None)
    if rc or report is None:
        print("no report of the traced window was kept", file=sys.stderr)
        return rc or 1
    print(device_trace.table(report, top=args.top))
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.workload}.json")
    with open(path, "w") as fh:
        json.dump(report, fh, default=str)
    print(f"report written to {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
