"""Batch-size x steps-per-dispatch autotuner (the roofline-driven MFU
campaign, tentpole part 2).

Walks the (batch, ``DL4J_TPU_MAX_STEPS_PER_DISPATCH``) ladder for a
model and scores every rung with the compiler's own cost model — the
same ``xla_cost_flops`` / ``xla_cost_bytes_accessed`` /
``xla_cost_peak_hbm_bytes`` gauges the compile-watch publishes for every
executable — plus (in measured mode) wall-clock step time.  The winner
is the best samples/sec whose peak HBM fits the cap; the decision is
cached per (model-signature, backend, precision policy) so a training
process pays the ladder walk once per model per machine.

Modes
-----
- **measured** (default): AOT-compiles the steps-deep scan program per
  rung and times ``trials`` donated dispatches; best samples/sec wins.
- **deterministic** (``--deterministic`` or
  ``DL4J_TPU_AUTOTUNE_DETERMINISTIC=1``): no wall clock at all — rungs
  are ranked by cost-model bytes/sample ascending (tie: flops/sample,
  then the larger batch/deeper dispatch).  The cost model is a pure
  function of the compiled program, so two runs on the same backend emit
  byte-identical decisions — the CI perf-smoke job asserts exactly that.
  The scan body is charged once per program by the cost model, so deeper
  dispatch stacks amortize it in the score the same way they amortize
  real dispatch overhead.

The decision is exported to the runtime by :func:`apply_decision`, which
sets ``DL4J_TPU_MAX_STEPS_PER_DISPATCH`` (read by ``nn/ingest.py`` for
every fused-scan epoch dispatch) and returns the chosen batch size.
Resolved decisions are published as ``autotune_*`` gauges alongside the
training telemetry.

Usage: python tools/autotune.py [lenet|mlp] [--deterministic] [--smoke]
           [--no-cache] [--apply]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import numpy as np

DET_ENV = "DL4J_TPU_AUTOTUNE_DETERMINISTIC"
CACHE_ENV = "DL4J_TPU_AUTOTUNE_CACHE"
CAP_ENV = "DL4J_TPU_AUTOTUNE_HBM_CAP_GB"
DISPATCH_ENV = "DL4J_TPU_MAX_STEPS_PER_DISPATCH"

# per-model (batches, steps_per_dispatch) ladders: full for a tuning
# run, tiny for --smoke / CI (rates are meaningless there; only the
# ranking and its determinism are exercised)
_LADDERS = {
    "full": ((64, 128, 256, 512), (8, 32, 128)),
    "smoke": ((16, 32), (2, 4)),
}


def _cache_path() -> str:
    p = os.environ.get(CACHE_ENV)
    if p:
        return p
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "deeplearning4j_tpu", "autotune.json")


def _load_cache() -> dict:
    try:
        with open(_cache_path()) as f:
            return json.load(f)
    except Exception:
        return {}


def _save_cache(cache: dict) -> None:
    path = _cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _backend() -> str:
    import jax
    d = jax.devices()[0]
    kind = getattr(d, "device_kind", "") or ""
    return f"{d.platform}:{kind}" if kind else d.platform


def hbm_cap_bytes() -> float:
    """Rungs whose compiler-reported peak HBM exceeds this are skipped.
    Env override in GB; else the device's own bytes_limit; else 16 GB."""
    env = os.environ.get(CAP_ENV)
    if env:
        return float(env) * 1e9
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
        if stats and stats.get("bytes_limit"):
            return float(stats["bytes_limit"])
    except Exception:
        pass
    return 16e9


def model_signature(conf, policy) -> str:
    """Stable id for (model architecture, backend, precision policy):
    the autotuner's cache key and the gauges' label."""
    try:
        conf_txt = conf.to_json(indent=None)
    except Exception:
        conf_txt = repr(conf)
    payload = "|".join((conf_txt, _backend(), policy.describe()))
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def _lenet_spec():
    from deeplearning4j_tpu.models.lenet import lenet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    def make_net():
        return MultiLayerNetwork(lenet()).init()

    def make_data(jnp, steps, batch, fdt):
        return (jnp.zeros((steps, batch, 784), fdt),
                jnp.zeros((steps, batch, 10), jnp.float32))

    return make_net, make_data


def _mlp_spec(n_in: int = 32, hidden: int = 64, n_out: int = 10):
    # tiny dense net: the determinism tests' fast signature
    from deeplearning4j_tpu.nn.conf import inputs as _inputs
    from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    def make_net():
        conf = (NeuralNetConfiguration.builder().seed(12)
                .updater("adam").learning_rate(1e-3)
                .list()
                .layer(DenseLayer(n_out=hidden))
                .layer(OutputLayer(n_out=n_out))
                .set_input_type(_inputs.feed_forward(n_in))
                .build())
        return MultiLayerNetwork(conf).init()

    def make_data(jnp, steps, batch, fdt):
        return (jnp.zeros((steps, batch, n_in), fdt),
                jnp.zeros((steps, batch, n_out), jnp.float32))

    return make_net, make_data


_MODELS = {"lenet": _lenet_spec, "mlp": _mlp_spec}


def _rung_cost(compiled) -> dict:
    out = {}
    try:
        c = compiled.cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0]
        out["flops"] = float(c.get("flops", 0.0))
        out["bytes"] = float(c.get("bytes accessed", 0.0))
    except Exception:
        pass
    try:
        m = compiled.memory_analysis()
        out["peak_hbm"] = (float(m.argument_size_in_bytes)
                           + float(m.output_size_in_bytes)
                           + float(m.temp_size_in_bytes)
                           - float(m.alias_size_in_bytes))
    except Exception:
        pass
    return out


def deterministic_mode(flag=None) -> bool:
    if flag is not None:
        return bool(flag)
    return os.environ.get(DET_ENV, "") not in ("", "0", "false")


def autotune(model: str = "lenet", batches=None, steps_ladder=None,
             deterministic=None, use_cache: bool = True,
             trials: int = 2, smoke: bool = False) -> dict:
    """Walk the ladder and return (and cache) the decision dict."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import monitor

    det = deterministic_mode(deterministic)
    lad_b, lad_s = _LADDERS["smoke" if smoke else "full"]
    batches = tuple(batches) if batches else lad_b
    steps_ladder = tuple(steps_ladder) if steps_ladder else lad_s

    make_net, make_data = _MODELS[model]()
    probe = make_net()
    pol = probe._pol()
    sig = model_signature(probe.conf, pol)

    cache = _load_cache() if use_cache else {}
    hit = cache.get(sig)
    if hit is not None and hit.get("mode") == (
            "deterministic" if det else "measured"):
        _publish(model, hit)
        return dict(hit, cached=True)

    cap = hbm_cap_bytes()
    rungs = []
    for batch in batches:
        for steps in steps_ladder:
            net = make_net()
            f, l = make_data(jnp, steps, batch,
                             jnp.dtype(net._pol().compute_dtype))
            args = (net.params, net.updater_state, net.net_state,
                    net.iteration, f, l, None, None, net._rng_key)
            rung = {"batch": int(batch), "steps": int(steps)}
            try:
                compiled = net._multi_train_step.lower(*args).compile()
            except Exception as e:
                rung["error"] = repr(e)[:200]
                rungs.append(rung)
                continue
            rung.update(_rung_cost(compiled))
            samples = steps * batch
            if rung.get("bytes"):
                rung["bytes_per_sample"] = round(rung["bytes"] / samples, 2)
            if rung.get("flops"):
                rung["flops_per_sample"] = round(rung["flops"] / samples, 2)
            peak = rung.get("peak_hbm")
            if peak and peak > cap:
                rung["skipped"] = "hbm_cap"
                rungs.append(rung)
                continue
            if not det:
                # donated state: re-feed what the program returns
                p, u, s, scores = compiled(*args)[:4]
                float(np.asarray(scores)[-1])        # warm + barrier
                t0 = time.perf_counter()
                for _ in range(trials):
                    p, u, s, scores = compiled(
                        p, u, s, net.iteration, f, l, None, None,
                        net._rng_key)[:4]
                float(np.asarray(scores)[-1])
                elapsed = time.perf_counter() - t0
                rung["samples_per_sec"] = round(
                    trials * samples / elapsed, 1)
            rungs.append(rung)

    ok = [r for r in rungs
          if "error" not in r and "skipped" not in r]
    if not ok:
        raise RuntimeError("autotune: every rung failed or exceeded the "
                           "HBM cap: %r" % rungs)
    if det:
        best = min(ok, key=lambda r: (r.get("bytes_per_sample",
                                            float("inf")),
                                      r.get("flops_per_sample",
                                            float("inf")),
                                      -r["batch"], -r["steps"]))
        score = {"bytes_per_sample": best.get("bytes_per_sample")}
    else:
        best = max(ok, key=lambda r: r.get("samples_per_sec", 0.0))
        score = {"samples_per_sec": best.get("samples_per_sec")}

    decision = {"model": model, "signature": sig, "backend": _backend(),
                "policy": pol.describe(),
                "mode": "deterministic" if det else "measured",
                "batch": best["batch"],
                "steps_per_dispatch": best["steps"],
                **score,
                "hbm_cap_bytes": cap, "rungs": rungs}
    if use_cache:
        cache[sig] = decision
        try:
            _save_cache(cache)
        except Exception:
            pass
    _publish(model, decision)
    return decision


def _publish(model: str, decision: dict) -> None:
    try:
        from deeplearning4j_tpu import monitor
        sig = decision.get("signature", "")
        monitor.gauge("autotune_batch",
                      "autotuned batch size").set(
            float(decision["batch"]), model=model, signature=sig)
        monitor.gauge("autotune_steps_per_dispatch",
                      "autotuned DL4J_TPU_MAX_STEPS_PER_DISPATCH").set(
            float(decision["steps_per_dispatch"]), model=model,
            signature=sig)
        if decision.get("bytes_per_sample"):
            monitor.gauge("autotune_bytes_per_sample",
                          "cost-model HBM bytes per sample at the chosen "
                          "rung").set(float(decision["bytes_per_sample"]),
                                      model=model, signature=sig)
        if decision.get("samples_per_sec"):
            monitor.gauge("autotune_samples_per_sec",
                          "measured samples/sec at the chosen rung").set(
                float(decision["samples_per_sec"]), model=model,
                signature=sig)
        monitor.gauge("autotune_rungs_evaluated",
                      "ladder rungs walked for the decision").set(
            float(len(decision.get("rungs", ()))), model=model,
            signature=sig)
    except Exception:
        pass


def apply_decision(decision: dict) -> int:
    """Export the decision to the runtime: the fused-scan dispatcher
    reads DL4J_TPU_MAX_STEPS_PER_DISPATCH on every epoch dispatch
    (nn/ingest.py), so setting it here retunes fit() without any API
    change.  Returns the chosen batch size for the caller's iterator."""
    os.environ[DISPATCH_ENV] = str(int(decision["steps_per_dispatch"]))
    return int(decision["batch"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("model", nargs="?", default="lenet",
                    choices=sorted(_MODELS))
    ap.add_argument("--deterministic", action="store_true",
                    help="rank by cost model only (no wall clock)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI ladder")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--apply", action="store_true",
                    help="print the env export line for the decision")
    args = ap.parse_args(argv)
    decision = autotune(args.model, deterministic=args.deterministic
                        or None, use_cache=not args.no_cache,
                        smoke=args.smoke)
    print(json.dumps(decision, sort_keys=True), flush=True)
    if args.apply:
        apply_decision(decision)
        print(f"export {DISPATCH_ENV}={decision['steps_per_dispatch']}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
