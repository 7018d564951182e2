"""Compilations inside the measured window; must read 0.  The larger
of what the program's own counters saw (watched jits, serving buckets)
and the backend compilations JAX reported."""

from benchmark.reading import counter_delta

LAYER = "containers"
UNIT, BETTER, SOURCE = "count", "lower", "program_counter"


def read(record):
    watched = sum(counter_delta(record, name) for name in (
        "jit_compiles_total", "serving_bucket_compiles_total"))
    return max(watched, record["cache"]["compiles_in_window"])
