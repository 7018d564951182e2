"""The share of the traced window in which the device was idle INSIDE a
running program: gaps between leaf operations that lie within an ``XLA
Modules`` event (waits for a prefetch, the compiler's own schedule),
which no change to the host can recover (``scope_costs.py``; the
program's ``idle_in_program_s`` over its window).  With
``idle_between_programs_share`` it is ``device_idle_share`` as the
program's reduction counts it.  Nothing to read is ``None``."""

from benchmark import scope_costs

LAYER = "step program"
UNIT, BETTER, SOURCE = "%", "lower", "device_trace"


def read(record):
    return scope_costs.window_share(record, "idle_in_program_s")
