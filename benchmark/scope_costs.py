"""What the three readers of PR 39 share: the program's reduction of the
traced window with the compiler's own counts (``hbm_traffic_share``,
``idle_in_program_share``, ``idle_between_programs_share``).

Every ``.xplane.pb`` carries, as stats of each instruction's
``XEventMetadata``, the compiler's cost analysis (operations, bytes by
memory space), and the ``XLA Modules`` line says when a program ran.
``deeplearning4j_tpu/monitor/device_trace.py:reduce`` reads both since
PR 39 (``docs/OBSERVABILITY.md`` section 8): ``hbm_bytes``,
``uncounted_s``, ``idle_in_program_s``, ``idle_between_programs_s``,
``cost_by_scope``, ``gaps_in_program``.  ``scopes.py`` keeps three keys
of that reduction; these readers need others, so they run it again over
the file the harness still holds (``record["trace"]["path"]``), once a
file however many readers ask.  From the program this takes ``reduce``
and nothing else; a program whose reduction lacks the keys (the parent
of PR 39), raises or finds nothing gives ``None``, and so does a record
without a trace.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

from benchmark import xplane

KEYS = ("hbm_bytes", "uncounted_s", "idle_in_program_s",
        "idle_between_programs_s")
#: the share of busy time that may lack a count before
#: ``hbm_traffic_share`` reports nothing
UNCOUNTED_LIMIT = 0.02

_reduced: Dict = {}


def costs(record: Dict) -> Optional[Dict]:
    """The program's ``reduce`` of the record's trace over
    ``bench/window`` (one reduction a file), or ``None``."""
    path = (record.get("trace") or {}).get("path")
    if not path or not os.path.isfile(path):
        return None
    key = (path, os.path.getmtime(path), os.path.getsize(path))
    if key not in _reduced:
        _reduced.clear()
        try:
            from deeplearning4j_tpu.monitor.device_trace import reduce
            report = reduce(path, window=xplane.WINDOW_SPAN)
        except Exception as exc:
            print(f"bench: scope costs: the program's reduction failed "
                  f"({type(exc).__name__}: {exc})", flush=True)
            report = None
        if report is not None and any(k not in report for k in KEYS):
            report = None
        if report is not None:
            print(f"bench: scope costs reduced in "
                  f"{report.get('reduce_s', float('nan')):.2f} s", flush=True)
        _reduced[key] = report
    return _reduced[key]


def window_share(record: Dict, key: str) -> Optional[float]:
    """100 x ``costs(record)[key]`` (seconds) over the traced window."""
    report = costs(record)
    if report is None or not report.get("window_s"):
        return None
    return 100.0 * report[key] / report["window_s"]
