"""Persistent compile-cache misses of the whole run
(``/jax/compilation_cache/cache_misses`` events).  In a run that is not
the first in its checkout it must read 0: every program is found."""

LAYER = "compile cache"
UNIT, BETTER, SOURCE = "count", "lower", "program_counter"


def read(record):
    return record["cache"]["misses"]
