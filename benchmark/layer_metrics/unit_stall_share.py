"""The part of the window's wall that its timed units spent beyond the
median unit's wall: what ``throughput`` (items of a unit over the
median unit's wall) leaves out.  About 0.005% when every unit keeps
the pace; one stalled unit in ten reads 2.8% (PR 22).  Who stalled it,
host or device, this number does not say: the ``units ... walls_s``
line has each unit, and a traced unit has the idle gaps."""

import statistics

LAYER = "device"
UNIT, BETTER, SOURCE = "%", "lower", "host_clock"


def read(record):
    walls = record.get("unit_walls_s")
    if not walls:
        return None
    median = statistics.median(walls)
    return 100.0 * sum(max(0.0, w - median) for w in walls) / sum(walls)
