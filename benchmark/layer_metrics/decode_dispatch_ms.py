"""Median of the program's ``serve/decode_step`` spans in the window
(``record["spans"]``, the span ring's ``dur_ms``): the host's cost of
launching one token step (argument handling, jit lookup, enqueue; not
the device's time).  It has to stay under the device's step for the
cell to stay device-bound.  A program without the span reports
nothing."""

import numpy as np

LAYER = "serving"
UNIT, BETTER, SOURCE = "ms", "lower", "program_span"


def read(record):
    launches = [s["dur_ms"] for s in record.get("spans") or ()
                if s.get("name") == "serve/decode_step"]
    if not launches:
        return None
    return float(np.median(launches))
