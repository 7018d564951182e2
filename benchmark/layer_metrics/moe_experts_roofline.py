"""The routed experts' matrix products as a share of their roofline:
the least time the chip could take for them (the larger of their bytes
over the HBM peak and their operations over the bf16 peak, counted from
shapes by ``families/<family>.py`` for the traced units:
``record["kernels"]["moe_experts"]``) over the device seconds of the
``layer.<vertex>.experts`` scopes in the traced window
(``record["trace"]["by_scope"]``).  Nothing to read (no such scope, no
count, no peaks) is ``None``."""

from benchmark import kernel_roofline

LAYER = "step program"
UNIT, BETTER, SOURCE = "%", "higher", "device_trace"


def read(record):
    return kernel_roofline.share(record, "moe_experts", ".experts")
