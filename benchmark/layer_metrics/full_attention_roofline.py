"""The full layers' attention (scores, softmax, context over a ring that
grows) as a share of its roofline: the least time the chip could take for
the rows the algorithm reads (the larger of bytes over the HBM peak and
operations over the bf16 peak, ``record["kernels"]["full_attention"]``,
counted from shapes by ``families/<family>.py`` for the traced units: the
context's rows of keys and values) over the device seconds of the
``layer.<vertex>.full_attention`` scopes in the traced window.  Nothing
to read is ``None``."""

from benchmark import kernel_roofline

LAYER = "step program"
UNIT, BETTER, SOURCE = "%", "higher", "device_trace"


def read(record):
    return kernel_roofline.share(record, "full_attention",
                                 ".full_attention")
