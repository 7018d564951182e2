"""How ``tests/data/scope_trace.xplane.pb.gz`` was made (on the chip,
once; run again only if the profiler's format or the scope grammar
changes):

    python tests/data/record_scope_trace.py <output directory>

A two-layer dense ``MultiLayerNetwork`` (1024 -> 2048 -> 16, Nesterov,
l2) fits 8192 seeded examples at batch 2048 for 10 epochs through the
epoch cache: with no listeners that is ONE dispatch of the gather step,
40 fused steps (many steps, so that the program's prologue stays a
small part, and a large batch, so that the nameless copies of the
parameters into the loop's state each step do).  The second such ``fit`` (the first compiles) runs under
``monitor.device_trace``, which profiles it, reduces the trace and
leaves the ``.xplane.pb`` behind.  So the trace must show forward,
backward, update and gather time under the scopes of
``monitor/device_trace.py``, the program's own spans (``fit/epoch``,
``fit/stage``, ``fit/dispatch``, ``fit/score_wait``) on the host plane,
and little that is unscoped.  The compile cache is left off: an
executable cached before the scopes existed would come back with its
old metadata.  The file is gzipped: nearly all of it is the HLO module
the trace carries (``reduce`` reads ``.gz``).
"""

import gzip

import os
import shutil
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.monitor.device_trace import find_trace, table
from deeplearning4j_tpu.nn.conf.neural_net_configuration import \
    NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

out = sys.argv[1]
os.makedirs(out, exist_ok=True)
conf = (NeuralNetConfiguration.builder().seed(7).updater("nesterovs")
        .learning_rate(0.05).weight_init("xavier").activation("relu")
        .l2(1e-4).list()
        .layer(DenseLayer(n_in=1024, n_out=2048))
        .layer(OutputLayer(n_in=2048, n_out=16, activation="softmax",
                           loss="mcxent")))
net = MultiLayerNetwork(conf.build()).init()
rng = np.random.default_rng(7)
x = rng.random((8192, 1024), dtype=np.float32)
y = np.eye(16, dtype=np.float32)[rng.integers(0, 16, 8192)]
iterator = ListDataSetIterator(DataSet(x, y), 2048)
net.fit(iterator, epochs=10)
print("warm score", net.score())
raw = os.path.join(out, "raw")
shutil.rmtree(raw, ignore_errors=True)
with monitor.device_trace(raw) as trace:
    net.fit(iterator, epochs=10)
    print("traced score", net.score())
found = find_trace(raw)
print(found, os.path.getsize(found), "bytes")
if trace.report is None:
    raise SystemExit("no TPU operation in the trace: record it on the chip")
print(table(trace.report, top=30))
packed = os.path.join(out, "scope_trace.xplane.pb.gz")
with open(found, "rb") as src, gzip.GzipFile(packed, "wb", 9,
                                             mtime=0) as dst:
    shutil.copyfileobj(src, dst)
print(packed, os.path.getsize(packed), "bytes")
