"""The attention over the selected rows (the selection's mask, scores,
softmax, context) as a share of its roofline: the least time the chip
could take for the rows the algorithm reads (the larger of bytes over
the HBM peak and operations over the bf16 peak,
``record["kernels"]["sparse_attention"]``, counted from shapes by
``families/<family>.py`` for the traced units: the SELECTED rows of both
rings, whatever the program read to get them) over the device seconds of
the ``layer.<vertex>.sparse_attention`` scopes in the traced window.
Nothing to read is ``None``."""

from benchmark import kernel_roofline

LAYER = "step program"
UNIT, BETTER, SOURCE = "%", "higher", "device_trace"


def read(record):
    return kernel_roofline.share(record, "sparse_attention",
                                 ".sparse_attention")
