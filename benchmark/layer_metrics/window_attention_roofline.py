"""The window layers' attention (scores, softmax, context over a ring that
wraps) as a share of its roofline: the least time the chip could take for
the rows the algorithm reads (the larger of bytes over the HBM peak and
operations over the bf16 peak, ``record["kernels"]["window_attention"]``,
counted from shapes by ``families/<family>.py`` for the traced units: the
window's rows of keys and values, whatever slots the program read to get
them) over the device seconds of the ``layer.<vertex>.window_attention``
scopes in the traced window.  Nothing to read is ``None``."""

from benchmark import kernel_roofline

LAYER = "step program"
UNIT, BETTER, SOURCE = "%", "higher", "device_trace"


def read(record):
    return kernel_roofline.share(record, "window_attention",
                                 ".window_attention")
