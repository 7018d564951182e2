"""The share of the chip's HBM peak that the step program's counted
traffic reaches while the device is busy: 100 x the HBM bytes read and
written in the traced window, as the compiler's own cost analysis counts
them for every instruction (``scope_costs.py``; a Pallas kernel, which
the compiler cannot count, by its operands and results outside on-chip
memory, whole and once), over ``record["trace"]["busy_s"]`` x the peak
(``record["peaks"]["hbm_bytes_per_s"]``).  It is the program's traffic,
not the algorithm's need (a program that reads a matrix twice reads
higher): the ``<kernel>_roofline`` metrics keep that.  ``None`` where
there is nothing to read (no trace, no peaks, a program whose reduction
has no bytes) and where more than 2% of busy time has no count, so that
a low number is never a hole in the count."""

from benchmark import scope_costs

LAYER = "step program"
UNIT, BETTER, SOURCE = "%", "higher", "device_trace"


def read(record):
    report = scope_costs.costs(record)
    peaks = record.get("peaks")
    busy = (record.get("trace") or {}).get("busy_s") or 0.0
    if report is None or not peaks or busy <= 0:
        return None
    if report["uncounted_s"] > scope_costs.UNCOUNTED_LIMIT * busy:
        return None
    return 100.0 * report["hbm_bytes"] / (busy * peaks["hbm_bytes_per_s"])
