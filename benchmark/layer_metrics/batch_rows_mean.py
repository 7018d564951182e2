"""Rows served per coalesced batch: rows answered in the window over
``serving_batches_total``."""

from benchmark.reading import counter_delta

LAYER = "serving"
UNIT, BETTER, SOURCE = "rows", "higher", "program_counter"


def read(record):
    batches = counter_delta(record, "serving_batches_total")
    if not batches or "latency_s" not in record:
        return None
    return record["items"] / batches
