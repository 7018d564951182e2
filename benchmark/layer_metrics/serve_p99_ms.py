"""99th percentile of the time from when a request was due to its whole
answer (a failed request slower than every answered one).  Too few
requests lie beyond it in a window for a bound, so it is the serving
layer's metric and the end-to-end tail is the 95th percentile."""

from benchmark.loadgen import percentile_with_failures

LAYER = "serving"
UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"


def read(record):
    if "latency_s" not in record:
        return None
    return percentile_with_failures(record["latency_s"], 99.0) * 1e3
