"""95th percentile of the time from when a request was due to be sent to its whole
answer.  A failed request counts as slower than every answered one."""

from benchmark.loadgen import percentile_with_failures

UNIT, BETTER, SOURCE = "ms", "lower", "host_clock"


def read(record):
    if "latency_s" not in record:
        return None
    return percentile_with_failures(record["latency_s"], 95.0) * 1e3
