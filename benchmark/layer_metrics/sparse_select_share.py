"""The selection's share of the device's busy time: the seconds of the
``layer.<vertex>.select`` rows of ``record["trace"]["by_scope"]`` (the
exact top-k of the indexer's scores, as row numbers or as a mask) over
``busy_s`` of the traced window.  A selection has no roof to be held
against (it moves few bytes and multiplies nothing), so what is tracked
is how much of the step it takes.  Nothing to read (no trace, no such
scope: a program without the mechanism) is ``None``."""

LAYER = "step program"
UNIT, BETTER, SOURCE = "%", "lower", "device_trace"


def read(record):
    trace = record.get("trace") or {}
    rows = [r for r in trace.get("by_scope") or ()
            if r[0].startswith("layer.") and r[0].endswith(".select")]
    busy = trace.get("busy_s") or 0.0
    if not rows or busy <= 0:
        return None
    return 100.0 * sum(r[2] for r in rows) / busy
