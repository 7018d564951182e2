"""Programs dispatched per step in the window: calls of the program's
watched jits (``jit_cache_hits_total`` + ``jit_compiles_total``) plus
the engine's batches (``serving_batches_total``), over the driver's
count of steps (a training step; in a serving cell, a request)."""

from benchmark.reading import counter_delta

LAYER = "containers"
UNIT, BETTER, SOURCE = "count", "lower", "program_counter"


def read(record):
    if not record.get("steps"):
        return None
    calls = sum(counter_delta(record, name) for name in (
        "jit_cache_hits_total", "jit_compiles_total",
        "serving_batches_total"))
    return calls / record["steps"]
