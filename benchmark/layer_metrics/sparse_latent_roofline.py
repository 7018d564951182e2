"""The full layers' attention over the latent rows their indexer selects
(the rows taken, scores, softmax, latent context) as a share of its
roofline: the least time the chip could take for the rows the algorithm
reads (the larger of bytes over the HBM peak and operations over the bf16
peak, ``record["kernels"]["sparse_latent_attention"]``, counted from
shapes by ``families/<family>.py`` for the traced units: the SELECTED
joined latent rows, whatever the program read to get them) over the
device seconds of the ``layer.<vertex>.sparse_latent_attention`` scopes
in the traced window.  Nothing to read is ``None``."""

from benchmark import kernel_roofline

LAYER = "step program"
UNIT, BETTER, SOURCE = "%", "higher", "device_trace"


def read(record):
    return kernel_roofline.share(record, "sparse_latent_attention",
                                 ".sparse_latent_attention")
