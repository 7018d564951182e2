"""Model FLOP/s utilisation of the step program while the device is
busy: operations from shapes (``benchmark/flops.py``) of the items
completed in the traced window, over device-busy seconds from the trace
(summed over the chips) times the chip's peak."""

LAYER = "step program"
UNIT, BETTER, SOURCE = "%", "higher", "device_trace"


def read(record):
    trace, peaks = record.get("trace"), record.get("peaks")
    if not trace or not peaks or not record.get("trace_items"):
        return None
    busy = trace["busy_s"] * trace["devices"]
    if busy <= 0:
        return None
    return 100.0 * record["trace_items"] * record["flops_per_item"] / (
        busy * peaks["flops_per_s_bf16"])
