"""The traced window by the program's names.

``xplane.py`` counts the device's seconds; this module says whose they
are, in the names the program gave its own work: the scopes of
``deeplearning4j_tpu/monitor/device_trace.py`` (``layer.<name>``,
``update.<name>``, ``loss``, ``ingest.gather``, ...; the grammar is
``docs/OBSERVABILITY.md`` section 8) and the program's spans
(``fit/score_wait``, ``fit/dispatch``, ...).  It adds three keys to what
readers get as ``record["trace"]``:

``by_scope``      ``[scope, pass, seconds, events]``, every row, largest
                  first, from the program's ``reduce`` over the
                  benchmark's window: the leaf operations of ``XLA
                  Ops`` by the scope and pass of their ``op_name``.  The
                  rows are names for seconds ``xplane.py`` already
                  counted: they sum to its ``busy_s``.
``unscoped_ops``  ``[instruction, seconds, events]`` of EVERY leaf
                  operation that took no scope, by HLO instruction name
                  (``reduce`` gives these by opcode only): a kernel that
                  carries no scope is found here by its name.  They sum
                  to ``by_scope``'s ``unscoped`` row.
``idle_by_span``  ``[span, seconds]``: the first device's idle time by
                  the innermost span that covers it, the program's and
                  the benchmark's own ``bench/*`` alike, ``no_span`` for
                  the rest.

Nothing that bounds a claim reads them: ``device_mfu``,
``device_idle_share`` and the rest keep to ``xplane.py``'s arithmetic.
From the program this takes its reduction and its grammar, nothing
else; where either is missing (a parent without them) or finds nothing
(a trace without HLO modules), ``view`` raises or returns ``None`` and
the harness goes on with ``xplane.py``'s names.
"""

from __future__ import annotations

import bisect
import time
from typing import Dict, List, Optional

from benchmark import xplane

UNSCOPED = "unscoped"


def unscoped_ops(events: Dict, modules: Dict, parse_op_name,
                 lo: float, hi: float) -> List[List]:
    """``[instruction, seconds, events]`` of the leaf operations in
    ``events`` (``xplane.read_events``) whose ``op_name`` in ``modules``
    (the program's ``hlo_modules``) names no scope, clipped to
    ``[lo, hi]``, seconds as means over the devices, largest first.  An
    operation is looked up in the module of the ``XLA Modules`` event
    that contains it; no module or no such instruction is no scope."""
    n_dev = len(events["devices"])
    scoped: Dict = {}
    rows: Dict[str, List] = {}
    for idx, ops in events["devices"].items():
        launched = sorted(events["modules"].get(idx, []))
        starts = [s for s, _, _ in launched]
        for name, s, e in ops:
            seconds = min(e, hi) - max(s, lo)
            if seconds <= 0:
                continue
            i = bisect.bisect_right(starts, s) - 1
            module = launched[i][2] if i >= 0 and s < launched[i][1] else ""
            if (module, name) not in scoped:
                op_name = modules.get(module, {}).get(
                    name, ("", "", (), False))[1]
                scoped[module, name] = parse_op_name(op_name)[0] != UNSCOPED
            if not scoped[module, name]:
                row = rows.setdefault(name, [0.0, 0])
                row[0] += seconds / n_dev
                row[1] += 1
    return sorted(([k, sec, n] for k, (sec, n) in rows.items()),
                  key=lambda r: -r[1])


def view(path: str, events: Dict) -> Optional[Dict]:
    """The three keys for the trace at ``path``, whose events
    (``xplane.read_events``) hold a ``bench/window``; ``None`` where the
    program's reduction finds no device operation."""
    from deeplearning4j_tpu.monitor.device_trace import (hlo_modules,
                                                         parse_op_name,
                                                         reduce)
    t0 = time.perf_counter()
    report = reduce(path, window=xplane.WINDOW_SPAN)
    if not report or not report.get("by_scope"):
        return None
    windows = [(s, e) for name, s, e in events["spans"]
               if name == xplane.WINDOW_SPAN]
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    return {
        "by_scope": [list(row) for row in report["by_scope"]],
        "unscoped_ops": unscoped_ops(events, hlo_modules(path),
                                     parse_op_name, lo, hi),
        "idle_by_span": [list(row) for row in report["idle_by_span"]],
        "by_scope_reduce_s": time.perf_counter() - t0,
    }


def breakdown(trace: Dict, top_n: int = 10) -> Dict:
    """``{"device_ops": [[name, seconds]], "idle_gaps": [[name,
    seconds]]}`` in the program's names: the largest ``(scope, pass)``
    rows as ``<scope>/<pass>``, the unscoped row split by instruction as
    ``unscoped/<instruction>``; the idle seconds by span."""
    ops = [[f"{scope}/{pass_}", seconds]
           for scope, pass_, seconds, _ in trace["by_scope"]
           if scope != UNSCOPED]
    ops += [[f"{UNSCOPED}/{name}", seconds]
            for name, seconds, _ in trace["unscoped_ops"]]
    ops.sort(key=lambda row: -row[1])
    return {"device_ops": ops[:top_n],
            "idle_gaps": [list(row) for row in
                          trace["idle_by_span"][:top_n]]}
