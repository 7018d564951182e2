"""From a profiler trace (``.xplane.pb``) to device numbers.

``jax.profiler`` writes one ``.xplane.pb`` per traced window;
``jax.profiler.ProfileData.from_file`` reads it with nothing but JAX.
This module is the one reduction from that file to what the benchmark
reports, kept as code so that every PR computes the same number the
same way (``tests/benchmark/`` checks it on a small recorded trace):

- per device: the seconds in which an operation ran (the union of the
  leaf-operation intervals on the device's ``XLA Ops`` line), so the
  idle share is ``1 - busy / window``.  A ``while`` or ``conditional``
  is one event that spans its whole body, gaps included, so an event
  that contains another event is a container and does not count;
- the device operations that took most time, by the names the trace
  gives them (the HLO instruction's name, without its operands);
- the idle time of the first device by what the host was doing: each
  gap is split among the benchmark's own host spans
  (``jax.profiler.TraceAnnotation`` named ``bench/...``) that cover it,
  the innermost span first, and what no span covers is ``no_span``;
- collective operations (on ``XLA Ops`` and, while in flight, on
  ``Async XLA Ops``): their time per device, and the part of it during
  which no other operation ran on that device.

The traced window is the host span ``bench/window`` that the harness
opens right after ``start_trace`` and closes right before
``stop_trace``; host and device events are on one clock in the file.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench/window"
SPAN_PREFIX = "bench/"
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)", re.I)

Interval = Tuple[float, float]


def find_trace(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of the disjoint sorted ``busy`` in ``[lo, hi]``."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(iv: Interval, others: List[Interval]) -> float:
    return sum(max(0.0, min(iv[1], b) - max(iv[0], a)) for a, b in others)


def op_name(event_name: str) -> str:
    """``%fusion.8 = bf16[...] fusion(...)`` -> ``fusion.8``: the trace
    names a device operation by its whole HLO line."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:120]


def leaves(ops: List[Tuple[str, float, float]]) -> List:
    """The events of one line that contain no other event.  Events on a
    line nest properly, so in start order (longest first on a tie) an
    event is a container exactly when the next one starts before it
    ends."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [op for i, op in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][1] >= op[2]
            or ops[i + 1][2] > op[2]]


def read_events(path: str) -> Dict:
    """``{"devices": {index: [(name, start_s, end_s)]}, "async":
    {index: [...]}, "modules": {index: [(start_s, end_s, name)]},
    "spans": [(name, start_s, end_s)]}`` from one ``.xplane.pb``: the
    leaf device operations of every TPU plane, its asynchronous
    operations while in flight, the programs it ran (``scopes.py`` looks
    an operation's name up in the one that contains it; no number of
    this file reads them), and the benchmark's host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List] = {}
    in_flight: Dict[int, List] = {}
    modules: Dict[int, List] = {}
    spans: List = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.setdefault(int(m.group(1)), []).extend(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                        for ev in line.events)
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                ops = []
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append((op_name(ev.name), s,
                                s + ev.duration_ns * 1e-9))
                if line.name == OPS_LINE:
                    devices.setdefault(int(m.group(1)), []).extend(
                        leaves(ops))
                else:
                    in_flight.setdefault(int(m.group(1)), []).extend(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s,
                                      s + ev.duration_ns * 1e-9))
    return {"devices": devices, "async": in_flight, "modules": modules,
            "spans": spans}


def _top(named_seconds: Dict[str, float], n: int) -> List[List]:
    return [[k, v] for k, v in sorted(named_seconds.items(),
                                      key=lambda kv: -kv[1])[:n]]


def reduce_events(events: Dict, top_n: int = 10) -> Optional[Dict]:
    """The reduction proper (``read_events`` output in, numbers out).
    ``None`` when the trace holds no device operation or no window
    span: a reader that finds nothing reports nothing."""
    devices = {k: v for k, v in events["devices"].items() if v}
    windows = [(s, e) for name, s, e in events["spans"]
               if name == WINDOW_SPAN]
    if not devices or not windows:
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    window_s = hi - lo
    busy_by_device, coll_by_device, exposed_by_device = {}, {}, {}
    op_seconds: Dict[str, float] = {}
    for idx, ops in devices.items():
        ivs = clip([(s, e) for _, s, e in ops], lo, hi)
        busy = union(ivs)
        busy_by_device[idx] = total(busy)
        coll = union(clip(
            [(s, e) for n, s, e in
             ops + events.get("async", {}).get(idx, [])
             if COLLECTIVE.search(n)], lo, hi))
        rest = union(clip([(s, e) for n, s, e in ops
                           if not COLLECTIVE.search(n)], lo, hi))
        coll_by_device[idx] = total(coll)
        exposed_by_device[idx] = total(coll) - sum(
            overlap(iv, rest) for iv in coll)
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_seconds[name] = op_seconds.get(name, 0.0) + d
    n_dev = len(devices)
    first = min(devices)
    # each idle gap goes to the host spans that cover it, piece by
    # piece, the innermost span (the deepest name) first
    host = sorted(((n, s, e) for n, s, e in events["spans"]
                   if n != WINDOW_SPAN), key=lambda x: -x[0].count("/"))
    gap_seconds: Dict[str, float] = {}
    first_busy = union(clip([(s, e) for _, s, e in devices[first]], lo, hi))
    for gap in gaps(first_busy, lo, hi):
        pieces = [gap]
        for name, s, e in host:
            if e <= gap[0] or s >= gap[1] or not pieces:
                continue
            covered = clip(pieces, s, e)
            if covered:
                gap_seconds[name] = gap_seconds.get(name, 0.0) \
                    + total(covered)
                pieces = [p for a, b in pieces
                          for p in ((a, min(b, s)), (max(a, e), b))
                          if p[1] > p[0]]
        if pieces:
            gap_seconds["no_span"] = gap_seconds.get("no_span", 0.0) \
                + total(pieces)
    mean = lambda d: sum(d.values()) / n_dev
    return {
        "window_s": window_s,
        "devices": n_dev,
        "busy_s": mean(busy_by_device),
        "busy_s_by_device": busy_by_device,
        "idle_share": 1.0 - mean(busy_by_device) / window_s,
        "idle_share_worst": 1.0 - min(busy_by_device.values()) / window_s,
        "collective_s": mean(coll_by_device),
        "collective_exposed_s": mean(exposed_by_device),
        "device_ops": _top({k: v / n_dev for k, v in op_seconds.items()},
                           top_n),
        "idle_gaps": _top(gap_seconds, top_n),
    }


def reduce_trace(trace_dir: str, top_n: int = 10) -> Optional[Dict]:
    path = find_trace(trace_dir)
    if path is None:
        return None
    return reduce_events(read_events(path), top_n)


def describe(path: str, per_line: int = 3) -> str:
    """Planes, lines and a few events of each: what to look at by hand
    before trusting the reduction on a new kind of trace."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:per_line]:
                out.append(f"    {ev.name[:80]!r} start_ns={ev.start_ns} "
                           f"dur_ns={ev.duration_ns}")
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys
    if sys.argv[1] == "--describe":
        print(describe(find_trace(sys.argv[2])))
    else:
        print(json.dumps(reduce_trace(sys.argv[1]), indent=1))
