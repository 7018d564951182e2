"""``memory_stats()`` of the fullest chip after the window:
``peak_bytes_in_use`` (live buffers) plus ``peak_bytes_reserved`` (the
programs' scratch, which a TPU keeps out of the first number)."""

LAYER = "device"
UNIT, BETTER, SOURCE = "GiB", "lower", "program_counter"


def read(record):
    peak = record.get("memory_peak_bytes")
    if not peak:
        return None
    return peak / 2.0 ** 30
