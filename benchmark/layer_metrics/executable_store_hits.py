"""Programs that set-up loaded from the executable store instead of
deriving them: the program's ``executable_store_total`` counter
(``monitor/jit_watch.py``), its ``result="hit"`` values summed over
their ``fn`` labels in ``record["monitor_before"]`` (the registry at the
window's start).  A warm start of a ``fit`` cell reads 3: the two staged
``init`` programs and the gather train step; a checkout's first run
reads 0 (every lookup is ``miss_absent``, then ``written``).  A program
without the counter (before PR 28), or a process that installed no
store, reports nothing."""

LAYER = "compile cache"
UNIT, BETTER, SOURCE = "count", "higher", "program_counter"


def read(record):
    counter = (record.get("monitor_before") or {}).get(
        "executable_store_total")
    if counter is None:
        return None
    return float(sum(v for labels, v in counter.get("values", {}).items()
                     if 'result="hit"' in labels))
