"""A net behind ``InferenceEngine`` under an open loop at a fixed rate.

Arrivals, rows per request and which seeded images each request carries
come from ``benchmark/loadgen.py`` and the traffic file; one generator
thread sends with ``predict_async`` and the completion callback stamps
the answer.  Traffic parameters: ``rate_per_s``, ``arrivals``,
``rows_mix``, ``timeout_s``, ``max_batch_size``, ``pool_examples``,
``trace_seconds``.  With ``sweep_rates_per_s`` in the traffic file the
driver runs its other mode, the knee sweep (``sweep``), which is not a
cell: ``sweep_seeds`` windows per rate, each with arrivals of its own,
and a table written to ``<out>/knee_sweep.<cell>.json``.
"""

from __future__ import annotations

import itertools
import json
import os
import time

import numpy as np

from benchmark import flops, loadgen, nets, units

#: the split of the check examples into requests: every bucket the
#: traffic's rows use
CHECK_SPLIT = (1, 2, 4, 1)


def setup(run):
    from deeplearning4j_tpu.serving import InferenceEngine
    lap = units.Laps()
    cfg, traffic = run.cfg, run.traffic
    net = nets.build_net(cfg, run.seed)
    lap("build_net")
    pool, _ = nets.images(cfg, traffic["pool_examples"], run.seed, stream=1)
    lap("data")
    engine = InferenceEngine(
        net, max_batch_size=traffic["max_batch_size"]).start()
    shape = (cfg["image_size"], cfg["image_size"], cfg["num_channels"])
    buckets = engine.warmup(shape)
    lap("compile_buckets")
    # warm-up proper: an executable is only loaded, and its activations
    # only allocated, when it first runs.  The ladder is the deployment's
    # (``max_batch_size``), and the traffic has to fill it: a bucket the
    # window never reaches is set-up and memory that stand for nothing
    for rows in reversed(engine.stats()["batch_buckets"]):
        engine.predict(np.broadcast_to(pool[:1], (rows,) + shape),
                       timeout=120.0)
    lap("run_buckets")
    # rows served through the engine against the reference
    cx, cy = nets.check_examples(cfg, run.seed)
    served, at = [], 0
    for rows in CHECK_SPLIT:
        served.append(np.asarray(engine.predict(cx[at:at + rows],
                                                timeout=120.0)))
        at += rows
    errors = nets.compare(cfg, net, cx, cy, np.concatenate(served),
                          train=False)
    lap("check")
    return {"net": net, "engine": engine, "pool": pool, "errors": errors,
            "buckets": buckets, "setup_phases": lap.phases}


def _window(run, state, rate, seconds, seed, trace):
    traffic = dict(run.traffic, rate_per_s=rate)
    sched = loadgen.schedule(traffic, seconds, seed)
    pool, engine = state["pool"], state["engine"]
    rows, at = sched["rows"], sched["at"]
    start_row = sched["offset"] % (pool.shape[0] - int(rows.max(initial=1)))
    classes = run.cfg["num_classes"]

    def part(lo, hi, shift):
        def send(i):
            i += lo
            return engine.predict_async(
                pool[start_row[i]:start_row[i] + int(rows[i])])

        def accept(i, answer):
            return np.shape(answer) == (int(rows[i + lo]), classes)

        return loadgen.open_loop(send, at[lo:hi] - shift,
                                 traffic["timeout_s"],
                                 annotate=run.annotate, accept=accept)

    if not trace:
        result = part(0, at.size, 0.0)
    else:
        # the profiler slows the host path (under it the engine falls
        # behind even this rate), so the schedule's last
        # ``trace_seconds`` run as a part of their own, traced, after
        # the first part has drained and its books are closed: requests,
        # latencies, counters and spans are the untraced part's, and the
        # traced part is there for the device trace alone
        from deeplearning4j_tpu import monitor
        t_split = seconds - run.traffic["trace_seconds"]
        split = int(np.searchsorted(at, t_split))
        result = part(0, split, 0.0)
        result.update(monitor_after=monitor.snapshot(),
                      spans=monitor.tracer().events())
        run.tracer.start()
        tail = part(split, at.size, t_split)
        run.tracer.stop()
        result["traced"] = {"rows": int(rows[split:][tail["ok"]].sum()),
                            "requests": int(at.size - split),
                            "failed": int((~tail["ok"]).sum())}
        rows, at = rows[:split], at[:split]
    result.update(rows=rows, at=at)
    return result


def measure(run, state):
    traffic = run.traffic
    res = _window(run, state, traffic["rate_per_s"], run.seconds, run.seed,
                  trace=run.tracer.enabled)
    state["engine"].stop()
    ok = res["ok"]
    dtype = nets.compute_dtype(state["net"])
    traced = res.get("traced", {"rows": 0})
    record = {
        "correct": nets.verdict(state["errors"], dtype),
        "attempted": int(ok.size), "failed": int((~ok).sum()),
        "window_s": float(res["wall_s"]),
        "latency_s": res["latency_s"], "lag_s": res["lag_s"],
        "items": int(res["rows"][ok].sum()),
        "steps": int(ok.sum()),             # one step = one request
        "trace_items": traced["rows"],
        "flops_per_item": flops.flops_per_item(run.cfg, training=False),
        "notes": [
            f"policy {state['net']._pol().describe()}",
            f"check errors {state['errors']} bounds {nets.BOUNDS[dtype]}",
            f"warmed buckets {state['buckets']} "
            f"(max_batch_size {traffic['max_batch_size']})",
            f"offered {ok.size} requests at {traffic['rate_per_s']}/s, "
            f"answered {int(ok.sum())}, rows {int(res['rows'].sum())}; "
            "latency ms (a failure slower than any answer) " + ", ".join(
                f"p{q} {loadgen.percentile_with_failures(res['latency_s'], q) * 1e3:.4f}"
                for q in (50, 90, 95, 99)) +
            f"; generator lag ms p99 "
            f"{np.percentile(res['lag_s'], 99) * 1e3 if ok.size else None}",
            f"traced part (not judged: the profiler slows the host) "
            f"{traced}"],
    }
    for key in ("monitor_after", "spans"):
        if key in res:
            record[key] = res[key]
    return record


def sweep(run, state):
    """The knee: the highest of the stepped rates at which, in every one
    of ``sweep_seeds`` windows of ``--seconds`` (each with arrivals of
    its own: the tail swings with them), p99 stays under
    ``knee_p99_ms``, nothing fails, and the backlog does not grow (the
    last quarter's median latency under twice the first's plus 5 ms)."""
    traffic = run.traffic
    seeds = [run.seed + k for k in range(traffic["sweep_seeds"])]
    table = []
    for rate, seed in itertools.product(traffic["sweep_rates_per_s"], seeds):
        res = _window(run, state, rate, run.seconds, seed, trace=False)
        lat, ok = res["latency_s"], res["ok"]
        n = lat.size
        q = max(1, n // 4)
        head = float(np.median(lat[:q])) * 1e3
        tail = float(np.median(lat[-q:])) * 1e3
        row = {
            "rate_per_s": rate, "seed": seed, "offered": int(n),
            "failed": int((~ok).sum()),
            "answered_per_s": float(ok.sum() / res["wall_s"]),
            "p50_ms": loadgen.percentile_with_failures(lat, 50) * 1e3,
            "p95_ms": loadgen.percentile_with_failures(lat, 95) * 1e3,
            "p99_ms": loadgen.percentile_with_failures(lat, 99) * 1e3,
            "first_quarter_p50_ms": head, "last_quarter_p50_ms": tail,
            "lag_p99_ms": float(np.percentile(res["lag_s"], 99)) * 1e3,
        }
        row["sustained"] = bool(
            row["failed"] == 0 and row["p99_ms"] < traffic["knee_p99_ms"]
            and tail < 2.0 * head + 5.0)
        table.append(row)
        run.say("sweep", json.dumps(row))
        time.sleep(1.0)                     # let the queue drain
    state["engine"].stop()
    sustained = [rate for rate in traffic["sweep_rates_per_s"]
                 if all(r["sustained"] for r in table
                        if r["rate_per_s"] == rate)]
    out = {"cell": run.cell.get("name"), "seconds_per_window": run.seconds,
           "seeds_per_rate": len(seeds),
           "knee_p99_ms": traffic["knee_p99_ms"],
           "knee_rate_per_s": max(sustained) if sustained else None,
           "table": table}
    path = os.path.join(run.out_dir,
                        f"knee_sweep.{run.cell.get('name', 'cell')}.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    run.say(f"knee {out['knee_rate_per_s']} requests/s; table in {path}")
