"""How ``tests/benchmark/data/toy_fit_trace.xplane.pb.gz`` was made (on
the chip, once; run again only if the profiler's format, the scope
grammar or the harness's annotations change):

    python tests/benchmark/record_toy_fit_trace.py <output directory>

The toy ``fit`` cell through ``benchmark.run.main`` with ``--trace 1``,
as the harness traces any cell: one fused ``fit`` call of 8 steps ending
in ``score()`` under ``bench/window``, ``bench/fit`` and
``bench/score``, with the program's own spans (``fit/epoch``,
``fit/stage``, ``fit/dispatch``, ``fit/score_wait``) inside them and the
step program's scopes in the HLO module the trace carries.  The harness
deletes its trace once the readers ran; here its ``rmtree`` is turned
off and the file copied out, gzipped (nearly all of it is the module;
the tests unpack it into a temporary directory).
"""

import glob
import gzip
import os
import shutil
import sys
import types

import benchtools
from benchmark import run

out = os.path.abspath(sys.argv[1])
os.makedirs(out, exist_ok=True)
run.shutil = types.SimpleNamespace(rmtree=lambda *a, **k: None)
rc, result, lines = benchtools.run_toy(
    "toy_vgg.fit", trace=1, seed=5, out_dir=out,
    manifest_path=os.path.join(benchtools.TOY, "BENCHMARK.tracing.json"))
print("\n".join(l for l in lines if "trace" in l))
print(rc, result)
(found,) = glob.glob(os.path.join(out, "trace", "**", "*.xplane.pb"),
                     recursive=True)
target = os.path.join(out, "toy_fit_trace.xplane.pb.gz")
with open(found, "rb") as src, gzip.open(target, "wb", 9) as dst:
    shutil.copyfileobj(src, dst)
print(os.path.getsize(found), "bytes raw,", os.path.getsize(target),
      "gzipped")
