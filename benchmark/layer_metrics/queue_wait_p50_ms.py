"""Median of the engine's ``serve/queue_wait`` spans (enqueue to the
collector taking the request).  The program's span ring holds 4,096
spans, five a request, so this reads the window's last ~700 requests."""

import numpy as np

LAYER = "serving"
UNIT, BETTER, SOURCE = "ms", "lower", "program_span"


def read(record):
    waits = [s["dur_ms"] for s in record.get("spans", ())
             if s.get("name") == "serve/queue_wait"]
    if not waits:
        return None
    return float(np.median(waits))
