"""How late the load generator ran: 99th percentile of (really sent -
due).  A starved generator must not read as a fast server."""

import numpy as np

LAYER = "load generator"
UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"


def read(record):
    lag = record.get("lag_s")
    if lag is None or not len(lag):
        return None
    return float(np.percentile(lag, 99)) * 1e3
