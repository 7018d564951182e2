"""The window rings' share of the session state that lives on the device
while the window runs: gauge ``serving_session_state_bytes{kind=}`` in
the snapshot taken at the window's end (the prefilled snapshot session
and the last unit's fork), ``kind="window_kv"`` over all kinds.  What a
window layer's ring costs beside the rings that grow with the context: a
window layer handed a full ring would read three quarters and more here.
A program that publishes no such kind reports nothing."""

import re

LAYER = "serving"
UNIT, BETTER, SOURCE = "%", "lower", "program_counter"


def read(record):
    gauge = (record.get("monitor_after") or {}).get(
        "serving_session_state_bytes") or {}
    by_kind = {}
    for labels, value in gauge.get("values", {}).items():
        kind = re.search(r'kind="([^"]*)"', labels)
        if kind:
            by_kind[kind.group(1)] = by_kind.get(kind.group(1), 0.0) + value
    window, total = by_kind.get("window_kv"), sum(by_kind.values())
    if not window or total <= 0:
        return None
    return 100.0 * window / total
