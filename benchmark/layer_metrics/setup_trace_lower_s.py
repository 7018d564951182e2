"""Seconds of set-up spent tracing Python to jaxprs and lowering them to
MLIR: the program's ``jit_trace_seconds_total`` +
``jit_lower_seconds_total`` (``monitor/jit_watch.py``, added up from
JAX's own compile events), summed over their ``fn`` labels in
``record["monitor_before"]``.  The registry is the process's, so its
value at the window's start is what set-up cost.  A program without the
counters (before PR 24) reports nothing."""

LAYER = "containers"
UNIT, BETTER, SOURCE = "s", "lower", "program_counter"
COUNTERS = ("jit_trace_seconds_total", "jit_lower_seconds_total")


def read(record):
    before = record.get("monitor_before") or {}
    found = [before[name] for name in COUNTERS if name in before]
    if not found:
        return None
    return float(sum(sum(c.get("values", {}).values()) for c in found))
