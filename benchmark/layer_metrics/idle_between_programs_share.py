"""The share of the traced window in which the device was idle while NO
program ran on it: how far the host holds the chip back
(``scope_costs.py``; the program's ``idle_between_programs_s`` over its
window).  With ``idle_in_program_share`` it is ``device_idle_share`` as
the program's reduction counts it.  Nothing to read is ``None``."""

from benchmark import scope_costs

LAYER = "device"
UNIT, BETTER, SOURCE = "%", "lower", "device_trace"


def read(record):
    return scope_costs.window_share(record, "idle_between_programs_s")
