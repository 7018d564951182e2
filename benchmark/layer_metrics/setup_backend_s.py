"""Seconds of set-up spent in the backend, compiling or loading
executables from the persistent compile cache: the program's
``jit_backend_seconds_total`` (``monitor/jit_watch.py``, added up from
JAX's ``backend_compile_duration`` events), summed over its ``fn``
labels in ``record["monitor_before"]`` (the registry at the window's
start).  A program without the counter (before PR 24) reports
nothing."""

LAYER = "compile cache"
UNIT, BETTER, SOURCE = "s", "lower", "program_counter"


def read(record):
    counter = (record.get("monitor_before") or {}).get(
        "jit_backend_seconds_total")
    if counter is None:
        return None
    return float(sum(counter.get("values", {}).values()))
