"""Shared pieces of the benchmark's CPU tests: the toy cells that exist
only under ``tests/benchmark/toy`` and a helper that runs the harness
in-process on them (a function argument lifts the TPU requirement; the
command line has no switch for it)."""

import io
import json
import os
import sys
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "toy")
for p in (ROOT, TOY):
    if p not in sys.path:
        sys.path.insert(0, p)


def run_toy(cell, trace, seconds=0.6, seed=3, out_dir=None):
    """``benchmark.run.main`` on a toy cell; returns (exit code, the
    last line parsed, all earlier lines)."""
    from benchmark import run
    if out_dir is not None:
        os.environ["BENCHMARK_OUT_DIR"] = str(out_dir)
    buf = io.StringIO()
    try:
        with redirect_stdout(buf):
            rc = run.main(
                ["--workload", cell, "--seed", str(seed), "--seconds",
                 str(seconds), "--trace", str(trace)],
                manifest_path=os.path.join(TOY, "BENCHMARK.toy.json"),
                extra_roots=[TOY], require_tpu=False)
    finally:
        os.environ.pop("BENCHMARK_OUT_DIR", None)
    lines = buf.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), lines[:-1]


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)
