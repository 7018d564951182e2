"""Time in collective operations per device over the traced window."""

LAYER = "parallel"
UNIT, BETTER, SOURCE = "%", "lower", "device_trace"


def read(record):
    trace = record.get("trace")
    if not trace or trace["devices"] < 2:
        return None
    return 100.0 * trace["collective_s"] / trace["window_s"]
