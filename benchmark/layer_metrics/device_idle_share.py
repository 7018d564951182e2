"""1 minus the union of device-operation intervals over the traced
window; on several chips the mean over the devices (the worst is on an
earlier line)."""

LAYER = "device"
UNIT, BETTER, SOURCE = "%", "lower", "device_trace"


def read(record):
    trace = record.get("trace")
    if not trace:
        return None
    return 100.0 * trace["idle_share"]
