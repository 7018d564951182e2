"""Process start (the first line of ``run.py``) to the first measured
unit: imports, building the net, making the data, the comparison with
the reference, warm-up and, in a run that compiles, compilation."""

UNIT, BETTER, SOURCE = "s", "lower", "host_clock"


def read(record):
    return record["setup_s"]
