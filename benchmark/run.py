"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run.  Builds the cell's net and inputs from
``--seed``, warms the cell's own shapes (all of that is ``setup_s``),
measures for ``--seconds``, checks the answers against the plain
reference, and prints ONE JSON object as the last line of stdout:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
with ``--trace 1``, ``breakdown``.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics (a few seconds
of the window run under ``jax.profiler``; ``breakdown`` names the
device's seconds by the program's scopes and its idle time by the
program's spans, ``scopes.py``).  Earlier lines, prefixed ``bench:``,
carry what else is worth reading.

It finds everything by name (``BENCHMARK.json`` names the cell):
``workloads/<cell>.json`` -> ``configs/<config>.json``,
``traffic/<traffic>.json``, ``drivers/<driver>.py``, and each metric's
reader ``end_to_end/<metric>.py`` / ``layer_metrics/<metric>.py``.  A
new cell, configuration, traffic mix or metric is new files plus an
entry in ``BENCHMARK.json``; no file here is edited for it.

Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program is not in the
checkout.  Never sets ``JAX_PLATFORMS``; starts no child process.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up runs from here

import argparse
import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
from typing import Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def say(*parts) -> None:
    print("bench:", *parts, flush=True)


# ------------------------------------------------------------------ lookup
class Lookup:
    """Files by kind and name under the benchmark's roots.  The first
    root is ``benchmark/``; a test passes one more."""

    def __init__(self, roots: Sequence[str]):
        self.roots = [os.path.abspath(r) for r in roots]

    def path(self, kind: str, name: str, suffixes=DATA_SUFFIXES) -> str:
        for root in self.roots:
            for suffix in suffixes:
                p = os.path.join(root, kind, name + suffix)
                if os.path.isfile(p):
                    return p
        raise FileNotFoundError(
            f"no {kind}/{name}{{{','.join(suffixes)}}} under "
            f"{self.roots}")

    def data(self, kind: str, name: str) -> Dict:
        with open(self.path(kind, name, (".json",))) as fh:
            return json.load(fh)

    def module(self, kind: str, name: str):
        path = self.path(kind, name, (".py",))
        spec = importlib.util.spec_from_file_location(
            f"_bench_{kind}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


# ----------------------------------------------------------------- tracing
class Tracer:
    """``jax.profiler`` around part of the window.  ``start`` and
    ``stop`` run on one thread; the span ``bench/window`` between them
    is what ``xplane.py`` takes for the traced window."""

    def __init__(self, enabled: bool, directory: str):
        self.enabled = enabled
        self.directory = directory
        self.active = False
        self._window = None

    def annotate(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax.profiler
        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if not self.enabled or self.active:
            return
        import jax.profiler
        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # our spans are TraceMe's
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.active = True
        self._window = jax.profiler.TraceAnnotation("bench/window")
        self._window.__enter__()

    def stop(self) -> None:
        if not self.active:
            return
        import jax.profiler
        self._window.__exit__(None, None, None)
        self.active = False
        jax.profiler.stop_trace()


class Run:
    """What a driver gets: the cell's three files, the seed, the window
    length, the devices, and the tracer."""

    def __init__(self, cell, cfg, traffic, seed, seconds, devices, tracer,
                 out_dir):
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.seed, self.seconds = int(seed), float(seconds)
        self.devices, self.tracer, self.out_dir = devices, tracer, out_dir
        self.annotate = tracer.annotate
        self.say = say


# ------------------------------------------------------------- the harness
def _metric_entries(manifest: Dict, group: str, cell: str) -> List[Dict]:
    return [m for m in manifest.get(group, [])
            if "workloads" not in m or cell in m["workloads"]]


def main(argv: Optional[Sequence[str]] = None, *,
         manifest_path: Optional[str] = None,
         extra_roots: Sequence[str] = (),
         require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    lookup = Lookup([HERE, *extra_roots])
    with open(manifest_path or os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    cell = lookup.data("workloads", args.workload)
    cfg = lookup.data("configs", cell["config"])
    traffic = lookup.data("traffic", cell["traffic"])
    chips = int(cell["chips"])

    try:
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.serving import compile_cache
    except ImportError as exc:
        print(f"benchmark/run.py: the program is not in this checkout "
              f"({exc}); nothing was run", file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()      # before anything compiles
    import jax
    import jax.monitoring

    events = {"hits": 0, "misses": 0, "compiles": 0, "compile_s": 0.0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            events["compiles"] += 1
            events["compile_s"] += duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if (require_tpu and device["platform"] != "tpu") or len(devs) < chips:
        print(f"benchmark/run.py: cell {args.workload!r} needs {chips} TPU "
              f"chip(s); JAX found platform {device['platform']!r} "
              f"({device['kind']} x{device['count']}); nothing was run",
              file=sys.stderr)
        return 1
    say(f"cell {args.workload} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace} device {json.dumps(device)} cache {cache_dir} "
        f"({compile_cache.stats(cache_dir)['entries']} entries)")

    out_dir = os.path.abspath(os.environ.get(
        "BENCHMARK_OUT_DIR", os.path.join(ROOT, "benchmark_out")))
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(out_dir, "trace", args.workload)
    tracer = Tracer(bool(args.trace), trace_dir)
    run = Run(cell, cfg, traffic, args.seed, args.seconds, devs[:chips],
              tracer, out_dir)
    driver = lookup.module("drivers", cell["driver"])

    state = driver.setup(run)
    if "sweep_rates_per_s" in traffic:
        # not a cell: the knee sweep, a mode of the driver that writes
        # its table to a file under the output directory
        driver.sweep(run, state)
        return 0
    setup_events = dict(events)
    before = monitor.snapshot()
    monitor.tracer().clear()
    setup_s = time.perf_counter() - T_START
    record = driver.measure(run, state)
    tracer.stop()                           # a driver that raised left it
    after = monitor.snapshot()

    record.update(cell=args.workload, cfg=cfg, traffic=traffic, chips=chips,
                  setup_s=setup_s, monitor_before=before, trace=None,
                  cache={"hits": events["hits"], "misses": events["misses"],
                         "compile_s": events["compile_s"],
                         "compiles_in_window":
                             events["compiles"] - setup_events["compiles"]})
    # a driver that closed its books before the window's end (the serve
    # driver, before its traced part) brings these itself
    record.setdefault("monitor_after", after)
    record.setdefault("spans", monitor.tracer().events())
    record["phase"] = monitor.phase_breakdown(since=before)
    from benchmark import flops, scopes, xplane
    if device["platform"] == "tpu":
        record["peaks"] = flops.chip_peaks(device["kind"])
    # the peak on the fullest chip: live buffers plus what the runtime
    # reserved for the programs' scratch (XLA's temporaries are not in
    # ``peak_bytes_in_use`` on a TPU; they are ``peak_bytes_reserved``)
    peak_bytes = 0
    for d in run.devices:
        stats = d.memory_stats() or {}
        peak_bytes = max(peak_bytes,
                         int(stats.get("peak_bytes_in_use", 0))
                         + int(stats.get("peak_bytes_reserved", 0)))
    say("memory_stats of the last device", json.dumps(stats))
    device["memory_peak_bytes"] = peak_bytes
    record["memory_peak_bytes"] = peak_bytes

    breakdown = None
    if args.trace:
        t0 = time.perf_counter()
        path = xplane.find_trace(trace_dir)
        traced = xplane.read_events(path) if path else None
        reduced = xplane.reduce_events(traced) if traced else None
        if reduced is not None:
            record["trace"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            say("trace", json.dumps({
                k: reduced[k] for k in ("window_s", "devices", "busy_s",
                                        "busy_s_by_device", "idle_share",
                                        "idle_share_worst")}),
                f"reduced in {time.perf_counter() - t0:.1f}s")
            # the same seconds by the program's names: for readers and
            # for ``breakdown``, for no number above
            try:
                named = scopes.view(path, traced)
            except Exception as exc:
                named = None
                say(f"trace: the program's reduction by scope failed "
                    f"({type(exc).__name__}: {exc})")
            if named is None:
                say("trace: no rows by scope; breakdown keeps the "
                    "compiler's names")
            else:
                reduced.update(named, path=path)
                breakdown = scopes.breakdown(reduced)
                say("trace by scope", json.dumps({
                    "rows": len(named["by_scope"]),
                    "unscoped_ops": len(named["unscoped_ops"]),
                    "unscoped_s": sum(r[1] for r in named["unscoped_ops"]),
                    "reduce_s": named["by_scope_reduce_s"]}))
        else:
            say("trace: no device operation or no window span was read")

    group, kind = (("per_layer", "layer_metrics") if args.trace
                   else ("end_to_end", "end_to_end"))
    metrics = {}
    try:
        for entry in _metric_entries(manifest, group, args.workload):
            value = lookup.module(kind, entry["name"]).read(record)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
    finally:
        # a reader may open the trace itself (``record["trace"]["path"]``)
        shutil.rmtree(trace_dir, ignore_errors=True)
    say("setup", json.dumps({
        "setup_s": setup_s, "cache_hits": events["hits"],
        "cache_misses": events["misses"],
        "backend_compile_s": events["compile_s"],
        "phases": state.get("setup_phases")}))
    for line in record.get("notes", []):
        say(line)
    result = {"correct": bool(record["correct"]),
              "attempted": int(record["attempted"]),
              "failed": int(record["failed"]),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if record.get("checks"):
        # each number ``correct`` compared, beside its limit: last on
        # stderr and last in the line, for whoever reads a refusal
        # (a number that is not finite goes in as text: strict JSON)
        result["checks"] = {
            name: [value if math.isfinite(value) else str(value), limit]
            for name, (value, limit) in record["checks"].items()}
        for name, (value, limit) in record["checks"].items():
            print(f"check {name} {value} limit {limit}", file=sys.stderr)
        sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
