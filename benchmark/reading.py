"""Helper for metric readers: the growth of one of the program's
counters over the measured window, from the two ``monitor.snapshot()``
copies the harness puts into the record."""

from __future__ import annotations

from typing import Dict


def _values(snapshot: Dict, name: str) -> Dict:
    return snapshot.get(name, {}).get("values", {})


def counter_delta(record: Dict, name: str) -> float:
    """Growth of counter ``name`` over the window, summed over labels."""
    before = _values(record["monitor_before"], name)
    return float(sum(v - before.get(k, 0.0) for k, v in
                     _values(record["monitor_after"], name).items()))
