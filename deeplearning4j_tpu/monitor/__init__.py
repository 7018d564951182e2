"""Unified runtime telemetry: tracing spans, metrics, jit compile-watch.

The reference stack's observability is listener-shaped: ``StatsListener``
samples per-iteration statistics into a ``StatsStorage`` and the UI server
charts them (``deeplearning4j-ui-parent``).  That answers "how is the model
doing"; it cannot answer the questions that dominate TPU performance work —
how many times did each jitted step recompile (and which shape triggered
it), and where host wall-clock goes between phases (ingest -> device step ->
listener overhead).  This package is the runtime-side answer, three pillars:

- :mod:`.tracing` — nested wall-clock spans in a bounded ring buffer,
  dumpable as a Chrome/Perfetto trace (``span("fit/epoch")``).
- :mod:`.metrics` — a process-global registry of counters, gauges and
  histograms (p50/p95/p99) with label support, cheap enough to update
  every iteration.
- :mod:`.jit_watch` — ``watched_jit(...)``, a ``jax.jit`` wrapper used at
  every step-cache call site; counts compiles vs cache hits, times
  compiles, and records the abstract-shape signature that triggered each
  recompile so shape churn is diagnosable.
- :mod:`.device_trace` — the scope grammar of the step programs
  (``scope("layer", name)``) and the way back: ``device_trace(log_dir)``
  profiles a region and reduces the trace to device seconds by scope and
  pass, with the device's idle time split among the spans above.

Export paths: ``ui/server.py`` serves ``GET /metrics`` (Prometheus text)
and ``GET /trace`` (Chrome-event JSONL) straight from the globals here, and
:func:`system_metrics_persistable` posts a snapshot into the existing
``StatsStorageRouter`` so the HTML overview picks it up unchanged.

All state is process-global and thread-safe; every accessor resolves
through :func:`registry`/:func:`tracer` at call time, so :func:`reset`
(tests, bench isolation) never leaves stale handles behind.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional

from . import health
from .health import (TrainingDivergedError, disable as disable_health,
                     enable as enable_health, enabled as health_enabled,
                     snapshot as health_snapshot)
from . import flight_recorder
from .flight_recorder import incident_dir, record_incident
from . import alerts
from .alerts import (AlertEngine, Rule, default_rules,
                     status as alert_status)
from . import attribution
from .attribution import StepAttributor, breakdown as wall_breakdown
from .device_trace import (DeviceTrace, device_trace, parse_op_name, scope,
                           subscope)
from .jit_watch import (WatchedJit, program_identity,
                        publish_cost_analysis, watched_jit)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, registry)
from .tracing import (TraceContext, Tracer, attach, current_context,
                      current_trace_hex, detach, new_trace_id,
                      parse_traceparent, span, tracer)

__all__ = [
    "AlertEngine", "Counter", "DeviceTrace", "Gauge", "Histogram",
    "MetricsRegistry", "Rule", "StepAttributor", "TraceContext", "Tracer",
    "TrainingDivergedError", "WatchedJit", "alert_status", "alerts",
    "attach", "attribution", "counter", "current_context",
    "current_trace_hex", "default_rules", "detach", "device_trace",
    "disable_health", "enable_health", "flight_recorder", "gauge",
    "health", "health_enabled", "health_snapshot", "histogram",
    "incident_dir", "new_trace_id", "observe_phase", "parse_op_name",
    "parse_traceparent", "phase_breakdown", "post_system_metrics",
    "program_identity", "prometheus_text", "publish_cost_analysis", "record_incident",
    "registry", "reset", "sanitize_end_warmup", "sanitize_scenario",
    "scope", "snapshot", "span", "subscope", "system_metrics_persistable",
    "trace_chrome_json", "trace_jsonl", "tracer", "wall_breakdown",
    "watched_jit",
]


def _sanitizer_mod():
    """``tools.analyze.sanitizer`` when importable AND armed, else
    ``None`` — so fit/serving call sites stay no-ops in stripped
    deployments and unarmed processes (mirrors ``locks.make_lock``)."""
    try:
        from tools.analyze import sanitizer as _san
    except Exception:
        return None
    return _san if _san.enabled() else None


def sanitize_scenario(name: str, units: int = 1, extra: int = 0):
    """Bracket one unit of dispatch-budgeted work (one fused fit epoch
    group, one serving RNN step) for the runtime sanitizer; a null
    context unless ``DL4J_TPU_SANITIZE=1``."""
    san = _sanitizer_mod()
    if san is None:
        return contextlib.nullcontext()
    return san.scenario(name, units=units, extra=extra)


def sanitize_end_warmup() -> None:
    """Tell the armed sanitizer warmup is over: from here on any
    recompile is a contract violation."""
    san = _sanitizer_mod()
    if san is not None:
        san.end_warmup()

# Canonical phase-histogram names: host wall-clock attribution of one
# training loop.  "data" = host-side batch prep + transfer staging,
# "step" = jitted-step dispatch, "listener" = host listener callbacks
# (including the device score fetch they force).
_PHASE_HELP = {
    "data": "host data prep + transfer staging per dispatch (ms)",
    "step": "jitted train-step dispatch per iteration (ms)",
    "listener": "host listener callbacks per iteration (ms)",
}


def counter(name: str, help: str = "") -> Counter:
    return registry().counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return registry().gauge(name, help)


def histogram(name: str, help: str = "") -> Histogram:
    return registry().histogram(name, help)


def observe_phase(phase: str, seconds: float, **labels) -> None:
    """Record ``seconds`` of host wall-clock against a training phase
    (``data`` / ``step`` / ``listener``) as a ``phase_<name>_ms``
    histogram observation."""
    registry().histogram(f"phase_{phase}_ms",
                         _PHASE_HELP.get(phase, "")).observe(
        seconds * 1e3, **labels)


def snapshot() -> Dict:
    """Point-in-time copy of every metric (see
    :meth:`MetricsRegistry.snapshot`); feed it back to
    :func:`phase_breakdown` to get deltas over a region."""
    return registry().snapshot()


def phase_breakdown(since: Optional[Dict] = None) -> Dict:
    """Per-phase wall-clock attribution (ms) plus compile counts,
    optionally as a delta against an earlier :func:`snapshot`.

    Returns ``{"data_ms", "step_ms", "listener_ms", "compile_ms",
    "recompiles", "steps"}`` — the breakdown bench.py emits next to its
    throughput JSON and the exporter posts into the stats storage.
    """
    snap = registry().snapshot()

    def _sums(name: str, field: str) -> float:
        total = 0.0
        for key, val in snap.get(name, {}).get("values", {}).items():
            prev = 0.0
            if since is not None:
                prev_val = since.get(name, {}).get("values", {}).get(key)
                if isinstance(prev_val, dict):
                    prev = float(prev_val.get(field, 0.0))
                elif prev_val is not None:
                    prev = float(prev_val)
            total += (float(val.get(field, 0.0))
                      if isinstance(val, dict) else float(val)) - prev
        return total

    return {
        "data_ms": round(_sums("phase_data_ms", "sum"), 3),
        "step_ms": round(_sums("phase_step_ms", "sum"), 3),
        "listener_ms": round(_sums("phase_listener_ms", "sum"), 3),
        "compile_ms": round(_sums("jit_compile_ms", "sum"), 3),
        "recompiles": int(_sums("jit_compiles_total", "sum")),
        "steps": int(_sums("phase_step_ms", "count")),
    }


def prometheus_text() -> str:
    """The ``GET /metrics`` body: Prometheus text exposition of every
    registered metric."""
    return registry().prometheus_text()


def trace_jsonl(trace_id=None, name=None, limit=None) -> str:
    """The ``GET /trace`` body: one Chrome trace event per line (wrap the
    lines in ``[...]`` to load in Perfetto / chrome://tracing).  Filters
    mirror the endpoint's ``?trace_id=``/``?name=``/``?limit=``."""
    return tracer().to_jsonl(trace_id=trace_id, name=name, limit=limit)


def trace_chrome_json(trace_id=None, name=None, limit=None) -> str:
    """The ``GET /trace?format=chrome`` body: a ready-to-load JSON array
    of Chrome trace events."""
    return tracer().to_chrome_json(trace_id=trace_id, name=name,
                                   limit=limit)


def system_metrics_persistable(model, session_id: str,
                               worker_id: str = "monitor_0"):
    """Build a stats record carrying the monitor snapshot, shaped so the
    existing UI overview renders it unchanged (same ``TYPE_ID`` and
    ``iteration``/``score``/``memory_rss_mb`` keys the ``StatsListener``
    posts), with the full registry snapshot + phase breakdown under the
    ``monitor`` key."""
    import resource

    from ..ui.stats_listener import TYPE_ID
    from ..ui.storage import Persistable

    data = {
        "report_type": "update",
        "iteration": int(getattr(model, "iteration", 0)),
        "epoch": int(getattr(model, "epoch", 0)),
        "score": float(model.score()),
        "memory_rss_mb":
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0,
        "monitor": {
            "phases": phase_breakdown(),
            "metrics": snapshot(),
        },
    }
    return Persistable(session_id, TYPE_ID, worker_id, time.time(), data)


def post_system_metrics(router, model, session_id: str,
                        worker_id: str = "monitor_0") -> None:
    """Post a :func:`system_metrics_persistable` into a
    ``StatsStorageRouter`` (the second export sink next to ``/metrics``)."""
    router.put_update(system_metrics_persistable(model, session_id,
                                                 worker_id))


def reset() -> None:
    """Clear every metric and trace event (test / bench isolation), and
    return the health layer to its env-configured default state.
    Live instrumentation keeps working: all call sites re-resolve their
    metric objects through the registry on each update."""
    registry().clear()
    tracer().clear()
    health.reset()
    flight_recorder.reset_rate_limit()
    alerts.reset()
