"""The indexer (its three projections of the new token and its scores
against the indexer-key ring) as a share of its roofline: the least time
the chip could take (the larger of bytes over the HBM peak and
operations over the bf16 peak, ``record["kernels"]["indexer"]``, counted
from shapes at the ring's capacity by ``families/<family>.py`` for the
traced units) over the device seconds of the ``layer.<vertex>.indexer``
scopes in the traced window.  Nothing to read is ``None``."""

from benchmark import kernel_roofline

LAYER = "step program"
UNIT, BETTER, SOURCE = "%", "higher", "device_trace"


def read(record):
    return kernel_roofline.share(record, "indexer", ".indexer")
