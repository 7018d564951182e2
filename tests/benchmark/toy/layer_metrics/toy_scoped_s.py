"""A reader that arrives as a file, as a later PR's would: the device
seconds of the traced window that the program's scopes name or leave
unscoped, summed over ``record["trace"]["by_scope"]``.  Nothing to sum
(no device trace, as on the CPU; a program without scopes) is nothing
to report."""

LAYER = "step program"
UNIT, BETTER, SOURCE = "s", "lower", "device_trace"


def read(record):
    rows = (record.get("trace") or {}).get("by_scope")
    if not rows:
        return None
    return sum(seconds for _, _, seconds, _ in rows)
