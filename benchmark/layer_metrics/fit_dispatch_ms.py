"""Median of the program's ``fit/dispatch`` spans in the window
(``record["spans"]``, the span ring's ``dur_ms``): the host's cost of
one launch of the fused step in the epoch-cache ``fit`` (signature
hash, jit lookup, argument handling, enqueue; not the device's time,
which ``fit/score_wait`` waits for).  A program without the span (before
PR 24) reports nothing."""

import numpy as np

LAYER = "containers"
UNIT, BETTER, SOURCE = "ms", "lower", "program_span"


def read(record):
    launches = [s["dur_ms"] for s in record.get("spans") or ()
                if s.get("name") == "fit/dispatch"]
    if not launches:
        return None
    return float(np.median(launches))
