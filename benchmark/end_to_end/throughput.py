"""Work completed per second of the window.  The cell's driver says
what an item is (a training sample in the ``fit`` cells, all chips
together; an answered row in a serving cell).  The manifest says which
cells report it.

Where the driver times units of equal work one after the other (the
``fit`` cells: ``unit_walls_s``), it is the items of a unit over the
MEDIAN unit's wall: the units of a window repeat to 0.03% on the v5e,
but one of them now and then takes half a second longer (PR 22: 2.41 s
among ten of 1.836 s), which alone moves the window's mean by 2.8%,
over any bound the spread of the other runs allows.  The median reads
the pace that held over the window; what it leaves out is not dropped,
it is the per-layer metric ``unit_stall_share``.  Where requests
overlap there is no unit, and it is the items over the window's
wall."""

import statistics

UNIT, BETTER, SOURCE = "items/s", "higher", "host_clock"


def read(record):
    walls = record.get("unit_walls_s")
    if walls:
        return record["items_per_unit"] / statistics.median(walls)
    if not record.get("items"):
        return None
    return record["items"] / record["window_s"]
