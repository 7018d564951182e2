"""Host seconds in the program's own data phase
(``monitor.observe_phase("data")``, read through
``monitor.phase_breakdown``) over the window's wall.  In
``fit(iterator)`` the step waits for that time; in ``ParallelWrapper``
it is the prefetch thread's busy share, and near 100% it sets the
pace."""

LAYER = "ingest"
UNIT, BETTER, SOURCE = "%", "lower", "program_counter"


def read(record):
    phase = record.get("phase")
    if phase is None or not record.get("window_s"):
        return None
    return 100.0 * phase["data_ms"] / 1e3 / record["window_s"]
