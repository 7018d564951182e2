"""Benchmark harness for the BASELINE.json configs.

Default run (the driver contract): LeNet-5 MNIST training throughput,
printed as exactly ONE JSON line
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
     "platform": ..., "device_kind": ..., "device_count": N}

``--all`` additionally benchmarks the other BASELINE configs (ResNet-50,
VGG-16, GravesLSTM char-RNN, word2vec skip-gram pairs/sec), the Pallas
flash-attention training throughput at T=8192, and — in a CPU subprocess
with a virtual 8-device mesh — the ParallelWrapper scaling harness;
those extra lines go to stderr so stdout stays one line.

Both need a TPU and exit non-zero without one; every line names the
platform, device kind and device count it ran on.  The ``--smoke`` /
proof modes run anywhere and say where they ran the same way.

Measurement notes: the harness (a) runs the training loop ON-CHIP via
the scan-based ``fit_scan`` multi-step (one dispatch = STEPS sequential
SGD steps — reference ``StochasticGradientDescent.java:50-72`` does this
loop on the host), (b) issues ``pipeline`` async dispatches per timed
window (program order keeps on-chip execution sequential, and a real
training loop is equally async), each window closed by
``block_until_ready``, and (c) reports the median of TRIALS windows
with the best/worst band.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import time
from typing import Optional

import numpy as np

from deeplearning4j_tpu import monitor

# Recorded floor for the LeNet config: the round-1 CPU-XLA floor on the
# original image (the reference publishes no numbers).
BASELINE_SAMPLES_PER_SEC = 1488.0


def _bf16_if_tpu():
    # deduplicated: the precision module owns the backend-default compute
    # dtype (and the DL4J_TPU_PRECISION override) — docs/PERFORMANCE.md
    from deeplearning4j_tpu.nn.precision import default_compute_dtype
    return default_compute_dtype()


def _measured(fn, trials: int) -> dict:
    """Run ``fn`` (returns elapsed seconds) ``trials`` times and return
    the median elapsed plus a variance band: a single best-of number
    can mistake run-to-run noise for a perf change; the median over
    timed windows plus the min/max spread makes cross-round comparisons
    falsifiable."""
    return _sorted_meas([fn() for _ in range(trials)])


def _sorted_meas(times) -> dict:
    """Median/best/worst of a list of elapsed-seconds windows."""
    times = sorted(times)
    n = len(times)
    median = (times[n // 2] if n % 2 else
              0.5 * (times[n // 2 - 1] + times[n // 2]))
    return {"median": median, "best": times[0], "worst": times[-1]}


def _band_fields(meas: dict, scale: float, trials: int) -> dict:
    """Per-window rates derived from a ``_measured`` result: best/worst
    rates and the spread as a fraction of the median-rate value."""
    val = scale / meas["median"]
    out = {"best": round(scale / meas["best"], 1),
           "worst": round(scale / meas["worst"], 1),
           "trials": trials}
    if val:
        out["spread_pct"] = round(
            100.0 * (out["best"] - out["worst"]) / val, 1)
    return out


#: ``device`` override for the proofs whose work runs in child processes
#: pinned to the CPU (``--chaos``/``--mesh``/``--scaleout``/``--fleet``):
#: the line must not carry the parent's accelerator.
_CPU_CHILDREN = {"platform": "cpu",
                 "device_kind": "cpu (child processes pinned)",
                 "device_count": None}


def _device_fields() -> dict:
    """Where this process's JAX work runs, as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _emit(line: dict, file=None, device: Optional[dict] = None) -> None:
    """Print one result line; every line says what it ran on."""
    print(json.dumps({**line, **(device or _device_fields())}),
          file=file or sys.stdout, flush=True)


def _require_tpu(what: str) -> None:
    """The measurement modes publish device metrics, so without a TPU
    they fail instead of printing a CPU rate under a chip's name."""
    dev = _device_fields()
    if dev["platform"] != "tpu":
        print(f"bench.py: {what} needs a TPU, but JAX found platform "
              f"{dev['platform']!r} ({dev['device_kind']} x"
              f"{dev['device_count']}); use --smoke for a CPU run",
              file=sys.stderr, flush=True)
        sys.exit(1)


# Chip peaks for the roofline/MFU report (bf16 matmul peak, HBM stream
# peak), keyed by device_kind substring.  v5e ("TPU v5 lite"): 197
# bf16-TFLOP/s, 819 GB/s HBM (Google Cloud documentation, "TPU v5e").
_TPU_PEAKS = {
    "v5 lite": (197e12, 819e9),
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v3": (123e12, 900e9),
    "v6": (918e12, 1640e9),
}


def _chip_peaks(device_kind: str):
    """(peak FLOP/s, peak bytes/s) of a TPU ``device_kind``.  A kind the
    table does not hold is an error, not a default: MFU against another
    chip's peak is a wrong number."""
    kind = device_kind.lower()
    for key, peaks in _TPU_PEAKS.items():
        if key in kind:
            return peaks
    raise KeyError(f"no peaks recorded for device_kind {device_kind!r}; "
                   f"add it to _TPU_PEAKS with its source")


def _compiled_cost(compiled) -> dict:
    """XLA's own cost model for an AOT-compiled executable: total flops
    and HBM bytes accessed per dispatch."""
    try:
        c = compiled.cost_analysis()
        if isinstance(c, list):
            c = c[0]
        return {"flops": float(c.get("flops", 0.0)) or None,
                "bytes": float(c.get("bytes accessed", 0.0)) or None}
    except Exception:
        return {}


def _roofline_fields(cost: dict, steps_per_sec: float) -> dict:
    """Printed roofline so 'memory-bound' is a number, not prose
    (round-3 verdict item 3): model FLOPs/step, achieved TFLOP/s, MFU
    against the chip's bf16 peak, and HBM bytes/step with the implied
    stream rate vs peak.  FLOPs/bytes come from XLA's cost model of the
    exact compiled program; the `lax.scan` loop body is counted ONCE by
    that model (verified empirically: steps=2 and steps=8 stacks report
    equal flops), so `cost` is per training step (reference metric
    surface being extended: ``PerformanceListener.java:99-102``)."""
    out = {}
    flops, bts = cost.get("flops"), cost.get("bytes")
    if flops:
        out["flops_per_step"] = round(flops, 1)
        out["tflops"] = round(flops * steps_per_sec / 1e12, 2)
    if bts:
        out["hbm_bytes_per_step"] = round(bts, 1)
        out["hbm_gb_per_sec"] = round(bts * steps_per_sec / 1e9, 1)
    # XLA's own bytes estimate next to whatever model fed "bytes": on
    # rows where a hand model overrode it (scatter kernels; the compiler
    # charges full-table traffic), "bytes_xla" preserves the compiler
    # number so both are printed — and large disagreement is FLAGGED
    # rather than silently resolved (MLPerf-style cost-model rooflines).
    xla_bts = cost.get("bytes_xla", bts)
    if xla_bts:
        out["bytes_model_xla"] = round(xla_bts, 1)
        if bts and abs(bts - xla_bts) / max(bts, xla_bts) > 0.25:
            out["hbm_model_mismatch"] = True
    dev = _device_fields()
    if dev["platform"] == "tpu":    # a CPU line has no chip peak to share
        peak_flops, peak_bw = _chip_peaks(dev["device_kind"])
        if flops:
            out["mfu"] = round(flops * steps_per_sec / peak_flops, 4)
        if bts:
            out["hbm_frac_of_peak"] = round(
                bts * steps_per_sec / peak_bw, 4)
    return out


def _phase_fields(snap: dict) -> dict:
    """Per-phase wall-clock attribution since ``snap`` (a
    ``monitor.snapshot()`` taken at bench start): data/step/listener/
    compile ms plus the recompile count, read from the telemetry
    registry the runtime now feeds — bench lines carry phase
    attribution, not just a rate."""
    return {"phases": monitor.phase_breakdown(since=snap)}


def _run_scan_bench(net, feats, labels, steps: int, pipeline: int,
                    trials: int):
    """Shared harness for the net-based configs: AOT-compile the on-chip
    multi-step scan once (cost analysis comes from the same executable),
    run `pipeline` async dispatches per completion fetch, best of
    `trials`.  Returns (samples... elapsed seconds, cost dict)."""
    import jax as _jax

    args = (net.params, net.updater_state, net.net_state, net.iteration,
            feats, labels, None, None, net._rng_key)
    compiled = net._multi_train_step.lower(*args).compile()
    # Cost comes from a 1-step twin of the same program: the cost model
    # charges a scan body ALL stacked input bytes, so the steps-deep
    # program would overcount HBM traffic by ~steps x; the 1-step stack's
    # IO is exactly one batch (flops per body are identical either way —
    # verified: steps=2 vs 8 report equal flops).
    cost_args = (net.params, net.updater_state, net.net_state,
                 net.iteration, _jax.tree.map(lambda a: a[:1], feats),
                 _jax.tree.map(lambda a: a[:1], labels), None, None,
                 net._rng_key)
    cost = _compiled_cost(
        net._multi_train_step.lower(*cost_args).compile())
    state = {"p": net.params, "u": net.updater_state, "s": net.net_state,
             "it": net.iteration}

    def dispatch():
        (state["p"], state["u"], state["s"],
         scores, _) = compiled(state["p"], state["u"], state["s"],
                            state["it"], feats, labels, None, None,
                            net._rng_key)
        state["it"] += steps
        return scores

    _jax.block_until_ready(dispatch())   # warmup
    monitor.sanitize_end_warmup()   # armed runs: recompiles now violate

    def timed() -> float:
        t0 = time.perf_counter()
        for _ in range(pipeline):
            scores = dispatch()
        _jax.block_until_ready(scores)
        elapsed = time.perf_counter() - t0
        # one observation per timed window (pipeline*steps on-chip
        # steps): zero per-step overhead, and the registry still carries
        # the step-phase total for the breakdown line
        monitor.observe_phase("step", elapsed)
        return elapsed

    meas = _measured(timed, trials)
    # per-step duration of one fully-blocked dispatch (launch through
    # block_until_ready) over the steps it retired, next to the
    # pipelined rate above
    t0 = time.perf_counter()
    _jax.block_until_ready(dispatch())
    device_ms = (time.perf_counter() - t0) / steps * 1e3
    net.params, net.updater_state = state["p"], state["u"]
    net.net_state, net.iteration = state["s"], state["it"]
    return meas, cost, device_ms


def bench_lenet(batch: int = 256, steps: int = 3200, trials: int = 3,
                pipeline: int = 3) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.mnist import mnist_arrays
    from deeplearning4j_tpu.models.lenet import lenet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = lenet(compute_dtype=_bf16_if_tpu())
    net = MultiLayerNetwork(conf).init()
    snap = monitor.snapshot()

    t_data = time.perf_counter()
    features, labels = mnist_arrays(train=True, num_examples=batch * 8)
    n = features.shape[0] // batch
    # stack the 8 distinct minibatches cyclically into (steps, B, ...) and
    # stage them on-device ONCE — the timed region measures the on-chip
    # scan, not host->device transfer
    # transfer the n distinct batches once (~6 MB), expand to the (steps,
    # B, ...) stack by an ON-DEVICE gather — shipping the redundant copies
    # from the host would cost ~400x the transfer at steps=3200
    # (round-4 depth sweep: 1600-step 1.52M / 3200-step 1.59M / 6400-step
    # 1.55M samples/s; 3200 amortizes the last dispatch overhead)
    # cast the base pool to the compute dtype BEFORE the on-device
    # gather, so the staged (steps, B, ...) stack is bf16 (~1.3 GB at
    # 3200 steps) rather than f32 (~2.6 GB) — same policy as the
    # ResNet bench's staging
    in_dtype = jnp.dtype(net._pol().compute_dtype)
    f_dev = jnp.asarray(np.stack(
        [features[i * batch:(i + 1) * batch]
         for i in range(n)])).astype(in_dtype)
    l_dev = jnp.asarray(np.stack(
        [labels[i * batch:(i + 1) * batch] for i in range(n)]))
    idx = jnp.asarray([i % n for i in range(steps)])
    _gather = jax.jit(lambda d, i: d[i])
    f_stk = _gather(f_dev, idx)
    l_stk = _gather(l_dev, idx)
    jax.block_until_ready((f_stk, l_stk))
    monitor.observe_phase("data", time.perf_counter() - t_data)

    # Dispatches are PIPELINED — `pipeline` async launches per blocking
    # wait — so launch latency amortizes over pipeline*steps on-chip
    # steps, as in a real training loop.
    meas, cost, device_ms = _run_scan_bench(net, f_stk, l_stk, steps,
                                            pipeline, trials)
    work = pipeline * steps * batch
    sps = work / meas["median"]
    result = {
        "metric": "lenet_mnist_train_samples_per_sec_per_chip",
        "value": round(sps, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(sps / BASELINE_SAMPLES_PER_SEC, 3),
        "batch": batch,
        "step_device_ms": round(device_ms, 4),
        "precision": net._pol().describe(),
    }
    result.update(_band_fields(meas, work, trials))
    result.update(_roofline_fields(cost, pipeline * steps / meas["median"]))
    result.update(_phase_fields(snap))
    return result


def bench_resnet50(batch: int = 128, steps: int = 8, trials: int = 3,
                   pipeline: int = 4) -> dict:
    """ResNet-50 synthetic-ImageNet training (BASELINE config #2) — the
    real MXU test: conv-dominated, bf16 on TPU.  Batch 128 is the measured
    single-chip optimum.  The inner loop runs ON-CHIP via the graph
    scan-based multi-step (one dispatch = ``steps`` updates), so launch
    overhead is paid once per ``steps`` and not by every step."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.resnet import resnet50
    from deeplearning4j_tpu.nn.computation_graph import ComputationGraph

    bf16 = _bf16_if_tpu()
    conf = resnet50(compute_dtype=bf16)
    net = ComputationGraph(conf).init()
    snap = monitor.snapshot()
    t_data = time.perf_counter()
    rng = np.random.RandomState(0)
    in_dtype = jnp.dtype(net._pol().compute_dtype)
    f = rng.rand(batch, 224, 224, 3).astype(np.float32)
    l = np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, batch)]
    # stage (steps, B, ...) on-device once: cast on host batch, broadcast
    # ON DEVICE — transfers one batch (not steps of them) and never holds
    # an f32 copy of the stack in HBM
    f_stk = jnp.broadcast_to(jnp.asarray(f).astype(in_dtype),
                             (steps,) + f.shape)
    l_stk = jnp.broadcast_to(jnp.asarray(l), (steps,) + l.shape)
    jax.block_until_ready((f_stk, l_stk))
    monitor.observe_phase("data", time.perf_counter() - t_data)

    meas, cost, device_ms = _run_scan_bench(net, [f_stk], [l_stk], steps,
                                            pipeline, trials)
    work = pipeline * steps * batch
    sps = work / meas["median"]
    result = {"metric": "resnet50_imagenet_train_samples_per_sec_per_chip",
              "value": round(sps, 1), "unit": "samples/sec/chip",
              "vs_baseline": None, "batch": batch,
              "step_device_ms": round(device_ms, 4),
              "precision": net._pol().describe()}
    result.update(_band_fields(meas, work, trials))
    result.update(_roofline_fields(cost, pipeline * steps / meas["median"]))
    result.update(_phase_fields(snap))
    return result


def bench_lstm(batch: int = 32, seq: int = 64, vocab: int = 84,
               hidden: int = 256, steps: int = 800, trials: int = 3,
               pipeline: int = 3) -> dict:
    """GravesLSTM char-RNN tBPTT step (BASELINE config #3): lax.scan over
    time inside the jitted train step.  800 steps/dispatch measured best
    (round 4: 200→4.75M, 400→6.09M, 800→6.35M, 1600→6.26M chars/s)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.recurrent import (GravesLSTM,
                                                        RnnOutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    builder = (NeuralNetConfiguration.builder()
               .seed(12).updater("rmsprop").learning_rate(0.1)
               .weight_init("xavier"))
    bf16 = _bf16_if_tpu()
    if bf16:
        builder = builder.compute_dtype(bf16)
    conf = (builder
            .list()
            .layer(GravesLSTM(n_in=vocab, n_out=hidden, activation="tanh"))
            .layer(GravesLSTM(n_in=hidden, n_out=hidden, activation="tanh"))
            .layer(RnnOutputLayer(n_in=hidden, n_out=vocab,
                                  activation="softmax", loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    snap = monitor.snapshot()
    t_data = time.perf_counter()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, seq))
    f = np.eye(vocab, dtype=np.float32)[ids]
    l = np.eye(vocab, dtype=np.float32)[np.roll(ids, -1, axis=1)]
    # one-batch transfer, device-side broadcast (see bench_resnet50)
    f_stk = jnp.broadcast_to(jnp.asarray(f), (steps,) + f.shape)
    l_stk = jnp.broadcast_to(jnp.asarray(l), (steps,) + l.shape)
    jax.block_until_ready((f_stk, l_stk))
    monitor.observe_phase("data", time.perf_counter() - t_data)

    meas, cost, device_ms = _run_scan_bench(net, f_stk, l_stk, steps,
                                            pipeline, trials)
    work = pipeline * steps * batch * seq
    chars = work / meas["median"]
    result = {"metric": "graves_lstm_charnn_chars_per_sec_per_chip",
              "value": round(chars, 1), "unit": "chars/sec/chip",
              "vs_baseline": None, "batch": batch, "seq": seq,
              "step_device_ms": round(device_ms, 4),
              "precision": net._pol().describe()}
    result.update(_band_fields(meas, work, trials))
    result.update(_roofline_fields(cost, pipeline * steps / meas["median"]))
    result.update(_phase_fields(snap))
    return result


def bench_vgg16(batch: int = 256, steps: int = 4, trials: int = 3,
                pipeline: int = 4) -> dict:
    """VGG-16 training step (BASELINE config #5: the Keras-import
    architecture — built through keras/trained_models.vgg16, the same
    config the importer targets), single chip; the 16-chip data-parallel
    variant needs hardware this session doesn't have.  Batch 256 is the
    measured throughput optimum (32→870, 64→857, 128→1296, 256→1355)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.keras.trained_models import vgg16
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    bf16 = _bf16_if_tpu()
    conf = vgg16(compute_dtype=bf16)
    net = MultiLayerNetwork(conf).init()
    snap = monitor.snapshot()
    t_data = time.perf_counter()
    rng = np.random.RandomState(0)
    in_dtype = jnp.dtype(net._pol().compute_dtype)
    f = rng.rand(batch, 224, 224, 3).astype(np.float32)
    l = np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, batch)]
    # on-chip scan loop + cast-then-broadcast staging; see bench_resnet50
    f_stk = jnp.broadcast_to(jnp.asarray(f).astype(in_dtype),
                             (steps,) + f.shape)
    l_stk = jnp.broadcast_to(jnp.asarray(l), (steps,) + l.shape)
    jax.block_until_ready((f_stk, l_stk))
    monitor.observe_phase("data", time.perf_counter() - t_data)

    meas, cost, device_ms = _run_scan_bench(net, f_stk, l_stk, steps,
                                            pipeline, trials)
    work = pipeline * steps * batch
    sps = work / meas["median"]
    result = {"metric": "vgg16_import_train_samples_per_sec_per_chip",
              "value": round(sps, 1), "unit": "samples/sec/chip",
              "vs_baseline": None, "batch": batch,
              "step_device_ms": round(device_ms, 4),
              "precision": net._pol().describe()}
    result.update(_band_fields(meas, work, trials))
    result.update(_roofline_fields(cost, pipeline * steps / meas["median"]))
    result.update(_phase_fields(snap))
    return result


def bench_word2vec(vocab: int = 10000, dim: int = 128, batch: int = 8192,
                   negative: int = 5, steps: int = 800,
                   trials: int = 3, pipeline: int = 2) -> dict:
    """Word2Vec skip-gram negative-sampling kernel throughput (BASELINE
    config #4), pairs/sec through the XLA scatter-add kernel (the
    ``AggregateSkipGram`` role).  The step loop runs on-chip via
    ``lax.scan`` so dispatch overhead doesn't tax it."""
    import functools

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nlp.word2vec import _ns_step

    rng = np.random.RandomState(0)
    syn0 = jnp.asarray(rng.randn(vocab, dim).astype(np.float32) * 0.01)
    syn1 = jnp.asarray(np.zeros((vocab, dim), np.float32))
    inputs = jnp.asarray(rng.randint(0, vocab, batch).astype(np.int32))
    targets = jnp.asarray(
        rng.randint(0, vocab, (batch, 1 + negative)).astype(np.int32))
    labels = jnp.asarray(np.concatenate(
        [[1.0], np.zeros(negative)]).astype(np.float32))
    tmask = jnp.ones((batch, 1 + negative), jnp.float32)
    pmask = jnp.ones((batch,), jnp.float32)
    lr = jnp.float32(0.025)

    @functools.partial(jax.jit, static_argnums=2, donate_argnums=(0, 1))
    def multi(s0, s1, n):
        def body(carry, _):
            s0, s1 = carry
            s0, s1, loss = _ns_step(s0, s1, inputs, targets, labels,
                                    tmask, pmask, lr)
            return (s0, s1), loss
        (s0, s1), losses = jax.lax.scan(body, (s0, s1), None, length=n)
        return s0, s1, losses

    def run_once(s0, s1):
        for _ in range(pipeline):
            s0, s1, losses = multi(s0, s1, steps)
        float(np.asarray(losses)[-1])   # fetch = completion barrier
        return s0, s1

    # FLOPs from XLA's 1-step twin; HBM bytes from a HAND model — the XLA
    # cost model charges every scatter/gather full-table traffic
    # (V x D x 4 bytes each), reporting ~41 GB/step for a kernel that
    # touches ~100 k rows, so its HBM fraction exceeded 1.0 and the row
    # was unfalsifiable (round-4 verdict, weak item 4).  Real traffic per
    # step: syn0 rows read+written once per pair row (2 x B x D x 4) plus
    # syn1neg rows read+written once per (positive|negative) target
    # (2 x B x (1+K) x D x 4), plus the int32 index/label operands;
    # rows hit k times in one batch still stream ~once thanks to cache
    # locality, so this is the achievable-traffic model, not a lower
    # bound artifact.
    cost = _compiled_cost(multi.lower(syn0, syn1, 1).compile())
    cost["bytes_xla"] = cost.get("bytes")
    K = negative
    hand_bytes = (2 * batch * dim * 4            # syn0 gather + scatter
                  + 2 * batch * (1 + K) * dim * 4  # syn1neg gather+scatter
                  + batch * 4                    # inputs (int32)
                  + batch * (1 + K) * (4 + 4 + 4))  # targets+tmask+labels
    cost["bytes"] = float(hand_bytes)
    syn0, syn1 = run_once(syn0, syn1)

    def timed() -> float:
        nonlocal syn0, syn1
        t0 = time.perf_counter()
        syn0, syn1 = run_once(syn0, syn1)
        return time.perf_counter() - t0

    meas = _measured(timed, trials)
    work = pipeline * steps * batch
    pairs = work / meas["median"]
    result = {"metric": "word2vec_sgns_pairs_per_sec_per_chip",
              "value": round(pairs, 1), "unit": "pairs/sec/chip",
              "vs_baseline": None, "batch": batch,
              "hbm_model": "hand (see bench_word2vec)"}
    result.update(_band_fields(meas, work, trials))
    result.update(_roofline_fields(cost, pipeline * steps / meas["median"]))
    return result


def bench_word2vec_fit(vocab: int = 10000, dim: int = 128,
                       corpus_words: int = 2_000_000, sent_len: int = 1000,
                       negative: int = 5, batch: int = 8192,
                       trials: int = 3) -> dict:
    """END-TO-END ``SequenceVectors.fit()`` pairs/s through the
    on-device pair-generation pipeline (``nlp/device_corpus.py``):
    subsampling, window draws, and unigram negative draws all on-chip,
    one scan dispatch per corpus pass.  The round-4 host feeding loop
    bounded this path orders of magnitude below the 11.8M pairs/s
    staged kernel rate (round-4 verdict item 4); the target is within
    ~2x of staged.  Vocab build (host, one-time) is excluded — the
    metric is the training loop, matching the staged bench's scope."""
    from deeplearning4j_tpu.nlp.word2vec import SequenceVectors

    rng = np.random.RandomState(0)
    n_sent = corpus_words // sent_len
    seqs = [["w%d" % w for w in rng.randint(0, vocab, sent_len)]
            for _ in range(n_sent)]
    sv = SequenceVectors(layer_size=dim, window_size=5, negative=negative,
                         use_hierarchic_softmax=False, batch_size=batch,
                         epochs=1, min_word_frequency=1,
                         pair_generation="device")
    sv.build_vocab(seqs)
    sv.fit(seqs)        # warmup: corpus upload + compile + one pass

    def timed() -> float:
        t0 = time.perf_counter()
        sv.fit(seqs)    # finish() fetches counters = completion barrier
        return time.perf_counter() - t0

    meas = _measured(timed, trials)
    pairs = sv._device_pipeline_stats["pairs_trained"]
    rate = pairs / meas["median"]
    result = {"metric": "word2vec_fit_end_to_end_pairs_per_sec",
              "value": round(rate, 1), "unit": "pairs/sec/chip",
              "vs_baseline": None, "corpus_words": corpus_words,
              "pairs_per_pass": round(pairs, 0)}
    result.update(_band_fields(meas, pairs, trials))
    return result


def bench_glove(vocab: int = 20000, dim: int = 128, batch: int = 8192,
                triples: int = 400_000, epochs_per_window: int = 2,
                trials: int = 3, naive: bool = True) -> dict:
    """GloVe AdaGrad triple-updates/s through the fused dual-buffer
    scatter path (``ops/scatter.py``): duplicate destination rows
    collapse via sort + segment-sum, then each side's weights AND
    accumulators land in ONE sorted-unique scatter — 2 scatters per
    batch where the naive kernel issued 8.  The naive eight-scatter
    reference runs in the SAME process (``naive_value``), so the
    speedup is falsifiable on any platform.  Triples are zipf-weighted
    (co-occurrence rows repeat hot words), one epoch = one scan dispatch
    over device-resident triples.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nlp.glove import (_glove_epoch,
                                              _glove_epoch_fused)

    rng = np.random.RandomState(0)
    rows = np.minimum(rng.zipf(1.5, triples) - 1, vocab - 1)
    cols = np.minimum(rng.zipf(1.5, triples) - 1, vocab - 1)
    xs = rng.rand(triples).astype(np.float32) * 50 + 1
    logx = jnp.asarray(np.log(xs))
    fx = jnp.asarray(np.minimum(1.0, (xs / 100.0) ** 0.75)
                     .astype(np.float32))
    rows_d = jnp.asarray(rows.astype(np.int32))
    cols_d = jnp.asarray(cols.astype(np.int32))
    n_chunks = -(-triples // batch)
    order = np.full(n_chunks * batch, -1, np.int32)
    order[:triples] = rng.permutation(triples)
    order_d = jnp.asarray(order.reshape(n_chunks, batch))
    lr = jnp.float32(0.05)

    def init_tables():
        r = np.random.RandomState(1)
        W = jnp.asarray((r.rand(vocab, dim).astype(np.float32) - .5) / dim)
        Wc = jnp.asarray((r.rand(vocab, dim).astype(np.float32) - .5) / dim)
        # distinct buffers: the naive epoch donates all eight args
        z = lambda: jnp.zeros((vocab,), jnp.float32)
        zh = lambda: jnp.zeros((vocab, dim), jnp.float32)
        return W, Wc, z(), z(), zh(), zh(), z(), z()

    # -- fused path ------------------------------------------------------
    W, Wc, b, bc, hW, hWc, hb, hbc = init_tables()
    Sr = jnp.concatenate([W, b[:, None], hW, hb[:, None]], axis=1)
    Sc = jnp.concatenate([Wc, bc[:, None], hWc, hbc[:, None]], axis=1)

    def run_fused(Sr, Sc):
        for _ in range(epochs_per_window):
            Sr, Sc, loss = _glove_epoch_fused(
                Sr, Sc, rows_d, cols_d, logx, fx, order_d, lr)
        float(np.asarray(loss))        # fetch = completion barrier
        return Sr, Sc

    # FLOPs from XLA's 1-chunk twin; HBM bytes from a HAND model (the
    # XLA cost model charges scatters full-table traffic — the same
    # overcount bench_word2vec documents).  Real traffic per chunk:
    # both packed (2D+2)-wide sides gathered + scattered once per
    # element row (aggregation only lowers the scatter side), plus the
    # int32/f32 triple operands.
    cost = _compiled_cost(_glove_epoch_fused.lower(
        Sr, Sc, rows_d, cols_d, logx, fx, order_d[:1], lr).compile())
    cost["bytes_xla"] = cost.get("bytes")
    hand_bytes = (2 * 2 * batch * (2 * dim + 2) * 4    # gather+scatter x2 sides
                  + batch * (4 + 4 + 4 + 4))           # rows/cols/logx/fx
    cost["bytes"] = float(hand_bytes)
    Sr, Sc = run_fused(Sr, Sc)         # warmup past compile

    def timed() -> float:
        nonlocal Sr, Sc
        t0 = time.perf_counter()
        Sr, Sc = run_fused(Sr, Sc)
        return time.perf_counter() - t0

    meas = _measured(timed, trials)
    work = epochs_per_window * triples
    result = {"metric": "glove_triple_updates_per_sec_per_chip",
              "value": round(work / meas["median"], 1),
              "unit": "triples/sec/chip", "vs_baseline": None,
              "batch": batch, "vocab": vocab, "triples": triples,
              "hbm_model": "hand (see bench_glove)"}
    result.update(_band_fields(meas, work, trials))
    result.update(_roofline_fields(
        cost, epochs_per_window * n_chunks / meas["median"]))

    # -- naive eight-scatter reference, same process ---------------------
    if naive:
        state = list(init_tables())

        def run_naive():
            nonlocal state
            for _ in range(epochs_per_window):
                *state, loss = _glove_epoch(*state, rows_d, cols_d,
                                            logx, fx, order_d, lr)
            float(np.asarray(loss))
            return state

        run_naive()                    # warmup

        def timed_naive() -> float:
            t0 = time.perf_counter()
            run_naive()
            return time.perf_counter() - t0

        meas_n = _measured(timed_naive, trials)
        result["naive_value"] = round(work / meas_n["median"], 1)
        result["vs_naive_8scatter"] = round(
            meas_n["median"] / meas["median"], 3)
    return result


def bench_deepwalk(n_vertices: int = 20000, n_edges: int = 200_000,
                   walk_length: int = 40, window: int = 2,
                   dim: int = 128, epochs_per_window: int = 2,
                   trials: int = 3) -> dict:
    """DeepWalk pairs/s INCLUDING walk generation — walks are generated
    on device (threefry uniform neighbour draws over the device-resident
    CSR) inside the same scan dispatch as the hierarchical-softmax
    updates, so the number covers the full epoch loop, not just the
    update kernel.  One dispatch per epoch; zero per-epoch host traffic
    (the host path shipped the walk matrix + pair arrays every epoch)."""
    from deeplearning4j_tpu.graph.deepwalk import DeepWalk
    from deeplearning4j_tpu.graph.graph import Graph

    rng = np.random.RandomState(0)
    g = Graph(n_vertices)
    a = rng.randint(0, n_vertices, n_edges)
    b = rng.randint(0, n_vertices, n_edges)
    for i in range(n_edges):
        if a[i] != b[i]:
            g.add_edge(int(a[i]), int(b[i]), 1.0, False)
    dw = (DeepWalk.Builder().vector_size(dim).window_size(window)
          .seed(7).build())
    dw.initialize(g)
    dw.fit(g, walk_length=walk_length, epochs=1)   # warmup: CSR + compile

    def timed() -> float:
        t0 = time.perf_counter()
        dw.fit(g, walk_length=walk_length, epochs=epochs_per_window)
        return time.perf_counter() - t0

    meas = _measured(timed, trials)
    L = walk_length + 1
    pairs_per_epoch = n_vertices * (L - 2 * window) * 2 * window
    work = epochs_per_window * pairs_per_epoch
    # hand bytes model per epoch: syn0 rows read+written once per pair,
    # syn1 rows once per (pair x Huffman path node) at the degree-tree's
    # mean code length, pair indices int32, plus the walk generator's
    # CSR probes (indptr twice + one neighbour gather per step).
    avg_len = float(np.asarray(dw._cmask_dev).sum(axis=1).mean())
    hand_bytes = (pairs_per_epoch * (2 * dim * 4
                                     + 2 * avg_len * dim * 4 + 8)
                  + n_vertices * walk_length * 3 * 4)
    result = {"metric": "deepwalk_pairs_per_sec_per_chip",
              "value": round(work / meas["median"], 1),
              "unit": "pairs/sec/chip", "vs_baseline": None,
              "n_vertices": n_vertices, "walk_length": walk_length,
              "includes_walk_generation": True,
              "hbm_model": "hand (see bench_deepwalk)",
              "hbm_bytes_per_epoch": round(hand_bytes, 1),
              "hbm_gb_per_sec": round(
                  hand_bytes * epochs_per_window / meas["median"] / 1e9,
                  1),
              "avg_code_len": round(avg_len, 2)}
    # The walk-epoch executable published its compiler cost estimate on
    # first compile (monitor.jit_watch); print it next to the hand model
    # and flag >25% disagreement like every other roofline row.
    xla_bytes = monitor.gauge("xla_cost_bytes_accessed", "").value(
        fn="deepwalk.device_walk_epoch")
    if xla_bytes:
        result["bytes_model_xla"] = round(xla_bytes, 1)
        if abs(hand_bytes - xla_bytes) / max(hand_bytes, xla_bytes) > 0.25:
            result["hbm_model_mismatch"] = True
    result.update(_band_fields(meas, work, trials))
    return result


def bench_pv(mode: str = "dbow", n_docs: int = 1200,
             doc_len: int = 500, vocab: int = 10000, dim: int = 128,
             negative: int = 5, batch: int = 8192,
             trials: int = 3) -> dict:
    """END-TO-END ``ParagraphVectors.fit()`` pairs/s through the device
    pipelines (word side: the corpus scan; label side: DBOW's label-pair
    scan or DM's always-live label column) — the PV twin of
    ``bench_word2vec_fit``.  Re-fits hit the pipeline cache (corpus
    uploads once; each pass is one scan dispatch per side segment), so
    the window times the training loop.  Pairs counted are word+label
    pairs actually trained (fetched from the device counters)."""
    from deeplearning4j_tpu.nlp.paragraph_vectors import ParagraphVectors

    rng = np.random.RandomState(0)
    docs = [(" ".join("w%d" % w
                      for w in rng.randint(0, vocab, doc_len)),
             "DOC_%d" % i) for i in range(n_docs)]
    pv = ParagraphVectors(sequence_learning_algorithm=mode,
                          layer_size=dim, negative=negative,
                          use_hierarchic_softmax=False, epochs=1,
                          batch_size=batch, min_word_frequency=1,
                          pair_generation="device")
    pv.fit(docs)        # warmup: vocab + corpus upload + compile + pass

    def timed() -> float:
        t0 = time.perf_counter()
        pv.fit(docs)    # pipeline cache: training loop only
        return time.perf_counter() - t0

    meas = _measured(timed, trials)
    stats_label = getattr(pv, "_device_%s_stats" % mode)
    word_pairs = (pv._device_pipeline_stats or {}).get("pairs_trained",
                                                       0.0)
    pairs = word_pairs + stats_label["pairs_trained"]
    result = {"metric": "pv_%s_fit_end_to_end_pairs_per_sec" % mode,
              "value": round(pairs / meas["median"], 1),
              "unit": "pairs/sec/chip", "vs_baseline": None,
              "n_docs": n_docs, "corpus_words": n_docs * doc_len,
              "word_pairs_per_pass": round(word_pairs, 0),
              "label_pairs_per_pass": round(stats_label["pairs_trained"],
                                            0)}
    result.update(_band_fields(meas, pairs, trials))
    return result


def bench_pv_dbow(**kw) -> dict:
    return bench_pv("dbow", **kw)


def bench_pv_dm(**kw) -> dict:
    return bench_pv("dm", **kw)


def bench_flash_attention(batch: int = 2, seq: int = 8192, heads: int = 4,
                          d_head: int = 64, steps: int = 8,
                          trials: int = 3) -> dict:
    """Pallas flash attention fwd+fused-bwd throughput at a sequence
    length the XLA attention path cannot compile (linear-memory
    long-context tier; see BASELINE.md).  Inputs follow the precision
    policy's compute dtype (the kernel accumulates f32 regardless)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.precision import default_compute_dtype
    from deeplearning4j_tpu.ops.attention import flash_attention

    in_dtype = (jnp.bfloat16 if default_compute_dtype() == "bfloat16"
                else jnp.float32)
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(batch, seq, heads, d_head)
                           .astype(np.float32)).astype(in_dtype)
               for _ in range(3))
    lossg = jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32)
            ** 2),
        argnums=(0, 1, 2)))
    # hand roofline for the flash step (the cost model cannot see inside
    # the Pallas custom call): with N = B*S*H*D streamed at the input
    # width, fwd reads q/k/v + writes o (4N) plus the f32 per-row
    # logsumexp; the fused 2-pass bwd reads q/k/v/do twice (8N), writes
    # dq/dk/dv (3N), and the delta pre-pass reads do/o (2N) — 17N total
    # plus 3 f32 row-stat streams.  FLOPs: 2 matmuls fwd + 5 bwd over
    # the S^2 score tiles, halved by causal masking.
    n_elems = batch * seq * heads * d_head
    isz = jnp.dtype(in_dtype).itemsize
    hand_bytes = 17 * n_elems * isz + 3 * batch * heads * seq * 4
    hand_flops = 0.5 * 14 * batch * heads * seq * seq * d_head
    cost = _compiled_cost(lossg.lower(q, k, v).compile())
    cost = {"flops": cost.get("flops") or hand_flops,
            "bytes": float(hand_bytes), "bytes_xla": cost.get("bytes")}
    jax.block_until_ready(lossg(q, k, v))   # warm before the timed region

    def timed() -> float:
        # async-pipelined dispatches closed by one blocking wait
        t0 = time.perf_counter()
        for _ in range(steps):
            out = lossg(q, k, v)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    meas = _measured(timed, trials)
    # the timed window is a blocked region (steps async dispatches closed
    # by block_until_ready), so dividing by steps gives per-step time
    device_ms = meas["median"] / steps * 1e3
    work = steps * batch * seq
    tokens = work / meas["median"]
    result = {"metric": "flash_attention_train_tokens_per_sec_per_chip",
              "value": round(tokens, 1), "unit": "tokens/sec/chip",
              "vs_baseline": None, "batch": batch, "seq": seq,
              "step_device_ms": round(device_ms, 4),
              "precision": jnp.dtype(in_dtype).name}
    result.update(_band_fields(meas, work, trials))
    result.update(_roofline_fields(cost, steps / meas["median"]))
    return result


def bench_fit_iterator_resnet(batch: int = 128, examples: int = 1280,
                              epochs_per_window: int = 4,
                              trials: int = 3) -> dict:
    """End-to-end ResNet-50 ``fit(iterator)`` through the graph epoch
    cache (the round-4 verdict item-1 'plus a ResNet end-to-end number'
    line): synthetic ImageNet-shaped data resident on device (bf16
    features — the step's first op is the same cast), listener-free."""
    import ml_dtypes

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models.resnet import resnet50
    from deeplearning4j_tpu.nn.computation_graph import ComputationGraph

    bf16 = _bf16_if_tpu()
    net = ComputationGraph(resnet50(compute_dtype=bf16)).init()
    rng = np.random.RandomState(0)
    f = rng.rand(examples, 224, 224, 3).astype(np.float32)
    if bf16:
        f = f.astype(ml_dtypes.bfloat16)
    l = np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, examples)]
    it = ListDataSetIterator(DataSet(f, l), batch)
    snap = monitor.snapshot()        # fit() feeds the phase registry itself
    net.fit(it, epochs=1)            # warmup: upload + compile

    def timed() -> float:
        t0 = time.perf_counter()
        net.fit(it, epochs=epochs_per_window)
        net.score()                  # fetch = completion barrier
        return time.perf_counter() - t0

    meas = _measured(timed, trials)
    work = epochs_per_window * examples
    sps = work / meas["median"]
    result = {"metric": "fit_iterator_resnet50_samples_per_sec",
              "value": round(sps, 1), "unit": "samples/sec/chip",
              "vs_baseline": None, "batch": batch,
              "examples_per_epoch": examples}
    result.update(_band_fields(meas, work, trials))
    result.update(_phase_fields(snap))
    return result


def bench_native_ingest(batch: int = 256, steps: int = 50,
                        trials: int = 3) -> dict:
    """End-to-end ingest: the C++ prefetch ring (``native/dataloader.cc``)
    feeding ``MultiLayerNetwork.fit_scan`` — host shuffle+gather on a
    native thread, host->device transfer, on-chip multi-step scan.  This
    is the data path a real training run pays for, unlike the
    staged-on-device configs above (round-3 verdict item 1: the native
    prefetcher must demonstrably feed fit_scan)."""
    from deeplearning4j_tpu.datasets.iterators import AsyncDataSetIterator
    from deeplearning4j_tpu.datasets.mnist import MnistDataSetIterator
    from deeplearning4j_tpu.models.lenet import lenet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net = MultiLayerNetwork(lenet(compute_dtype=_bf16_if_tpu())).init()
    it = AsyncDataSetIterator(
        MnistDataSetIterator(batch, batch * steps), queue_size=4)
    native = it.native
    snap = monitor.snapshot()        # fit_scan feeds the phase registry

    def epoch() -> None:
        batches = list(it)
        net.fit_scan(batches)

    epoch()   # warmup: compile fit_scan + fill the ring

    def timed() -> float:
        t0 = time.perf_counter()
        epoch()
        return time.perf_counter() - t0

    meas = _measured(timed, trials)
    it.close()
    work = steps * batch
    sps = work / meas["median"]
    result = {"metric": "native_ring_to_fit_scan_samples_per_sec",
              "value": round(sps, 1), "unit": "samples/sec/chip",
              "vs_baseline": None, "batch": batch,
              "native_prefetcher": bool(native)}
    result.update(_band_fields(meas, work, trials))
    result.update(_phase_fields(snap))
    return result


def bench_fit_iterator(batch: int = 256, examples: int = 60000,
                       epochs_per_window: int = 2,
                       trials: int = 3) -> list:
    """End-to-end ``MultiLayerNetwork.fit(iterator)`` through the product
    API — the path a real user pays for (round-4 verdict item 1: the
    overlapped-ingest rework must post a BENCH number vs the 1.47M
    staged ceiling).  Two lines: the device-resident epoch-cache path
    (MNIST fits HBM; per-epoch host traffic is one int32 permutation)
    and the windowed double-buffered staging path (forced, as if the
    dataset didn't fit), both on the full 60k-example MNIST epoch.
    The iterator ships the uint8 wire twin when enabled (decode fused
    on device), so ``staged_bytes`` shows what actually crossed."""
    import os

    from deeplearning4j_tpu.datasets.dataset import wire_enabled
    from deeplearning4j_tpu.datasets.mnist import MnistDataSetIterator
    from deeplearning4j_tpu.models.lenet import lenet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    results = []
    for mode in ("cache", "window"):
        net = MultiLayerNetwork(lenet(compute_dtype=_bf16_if_tpu())).init()
        it = MnistDataSetIterator(batch, examples)
        snap = monitor.snapshot()   # fit() feeds the phase registry itself
        net.fit(it, epochs=1, ingest=mode)   # warmup: compile + first epoch

        def timed() -> float:
            t0 = time.perf_counter()
            net.fit(it, epochs=epochs_per_window, ingest=mode)
            net.score()    # device->host fetch = the completion barrier
            return time.perf_counter() - t0

        meas = _measured(timed, trials)
        # blocked single-epoch window — for the cache path this is
        # dispatch + on-chip scan time
        t0 = time.perf_counter()
        net.fit(it, epochs=1, ingest=mode)
        net.score()
        epoch_device_ms = (time.perf_counter() - t0) * 1e3
        work = epochs_per_window * examples
        sps = work / meas["median"]
        result = {"metric": f"fit_iterator_{mode}_samples_per_sec",
                  "value": round(sps, 1), "unit": "samples/sec/chip",
                  "vs_baseline": None, "batch": batch,
                  "examples_per_epoch": examples,
                  "epoch_device_ms": round(epoch_device_ms, 2),
                  "wire": "uint8" if wire_enabled() else "float32",
                  "staged_bytes": monitor.gauge(
                      "ingest_staged_bytes", "").value(path=mode)}
        result.update(_band_fields(meas, work, trials))
        result.update(_phase_fields(snap))
        results.append(result)
    return results


def bench_serving(n_in: int = 64, hidden: int = 256, n_out: int = 10,
                  max_batch: int = 32, max_latency_ms: float = 2.0,
                  concurrency_sweep=(1, 4, 16, 64),
                  seq_requests: int = 300,
                  duration_s: float = 3.0) -> dict:
    """Dynamic-batching serving throughput (``serving.InferenceEngine``)
    vs the sequential single-request ``output()`` path on the same model.

    Closed-loop offered-load sweep: at each concurrency level, that many
    client threads issue back-to-back 1-row ``predict()`` calls for
    ``duration_s``; the engine coalesces them into bucket-padded batches
    behind one shape-bucketed AOT executable per bucket.  The stdout line
    reports the saturating level's request throughput with
    ``vs_baseline`` = speedup over the sequential baseline measured in
    the same process; per-level throughput + client-observed p50/p95/p99
    go to stderr.  Recompiles stay bounded by the warmed bucket count —
    read back from the monitor registry and included in the line."""
    import threading

    from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import InferenceEngine

    from deeplearning4j_tpu.nn.conf import inputs as _inputs
    conf = (NeuralNetConfiguration.builder().seed(12)
            .list()
            .layer(DenseLayer(n_out=hidden))
            .layer(DenseLayer(n_out=hidden))
            .layer(OutputLayer(n_out=n_out))
            .set_input_type(_inputs.feed_forward(n_in))
            .build())
    model = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    x1 = rng.randn(1, n_in).astype(np.float32)

    # -- sequential baseline: one dispatch per request, no coalescing ----
    np.asarray(model.output(x1))                     # warm the compile
    t0 = time.perf_counter()
    for _ in range(seq_requests):
        np.asarray(model.output(x1))
    seq_rps = seq_requests / (time.perf_counter() - t0)

    compiles_before = _serving_compile_count()
    engine = InferenceEngine(model, max_batch_size=max_batch,
                             max_latency_ms=max_latency_ms,
                             queue_capacity=4 * max_batch,
                             name="bench")
    engine.start()
    warmed = engine.warmup((n_in,))

    best = {"rps": 0.0, "clients": 0, "p50": None, "p95": None,
            "p99": None}
    try:
        for clients in concurrency_sweep:
            lat: list = []
            counts = [0] * clients
            stop_at = time.perf_counter() + duration_s

            def client(i):
                x = x1
                while time.perf_counter() < stop_at:
                    t = time.perf_counter()
                    engine.predict(x, timeout=30.0)
                    lat.append(time.perf_counter() - t)
                    counts[i] += 1

            t_start = time.perf_counter()
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t_start
            done = sum(counts)
            rps = done / elapsed
            lat.sort()

            def pct(p):
                return (round(lat[min(len(lat) - 1,
                                      int(p * len(lat)))] * 1e3, 2)
                        if lat else None)

            level = {"clients": clients, "rps": round(rps, 1),
                     "p50_ms": pct(0.50), "p95_ms": pct(0.95),
                     "p99_ms": pct(0.99)}
            _emit({"metric": "serving_sweep_level", **level},
                  file=sys.stderr)
            if rps > best["rps"]:
                best = {"rps": rps, "clients": clients,
                        "p50": level["p50_ms"], "p95": level["p95_ms"],
                        "p99": level["p99_ms"]}
    finally:
        engine.stop()
    compiles = _serving_compile_count() - compiles_before

    return {"metric": "serving_dynamic_batching_requests_per_sec",
            "value": round(best["rps"], 1), "unit": "requests/sec",
            "vs_baseline": round(best["rps"] / seq_rps, 3)
            if seq_rps else None,
            "sequential_rps": round(seq_rps, 1),
            "saturating_clients": best["clients"],
            "p50_ms": best["p50"], "p95_ms": best["p95"],
            "p99_ms": best["p99"],
            "warmed_buckets": warmed, "recompiles": compiles,
            "max_batch": max_batch, "max_latency_ms": max_latency_ms}


def bench_serving_v2(n_in: int = 32, hidden: int = 128, n_out: int = 8,
                     max_batch: int = 16, max_latency_ms: float = 2.0,
                     concurrency_sweep=(4, 16, 48),
                     duration_s: float = 3.0,
                     naive_buckets=(8, 16, 32, 64, 128)) -> dict:
    """Serving v2 offered-load sweep: 4 registered models (2 dense, 1
    GravesLSTM, 1 KV-ring causal-attention decoder) behind one
    ``ModelRegistry``, RNN and decode traffic through device-resident
    sessions (ONE timestep/token dispatch per request), and a p99 SLO
    enforced by admission control — versus the naive
    single-model/full-sequence baseline that recomputes the whole
    conversation every request.

    The SLO is calibrated from the unloaded single-step latency (CPU and
    TPU differ by orders of magnitude), then the sweep offers increasing
    closed-loop load; the engine sheds past saturation, so the admitted
    p99 must hold near the target while the naive baseline's per-request
    cost grows linearly with session length and blows through it.  The
    stdout line reports the saturating level, admitted-p99-vs-SLO, shed
    fraction, and the naive baseline's p99 for ``vs_baseline``."""
    import threading

    from deeplearning4j_tpu.nn.conf import inputs as _inputs
    from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.layers.recurrent import (GravesLSTM,
                                                        RnnOutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (InferenceEngine, ModelRegistry,
                                            ServingError)

    def dense(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .list()
                .layer(DenseLayer(n_out=hidden))
                .layer(OutputLayer(n_out=n_out))
                .set_input_type(_inputs.feed_forward(n_in))
                .build())
        return MultiLayerNetwork(conf).init()

    def rnn(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .list()
                .layer(GravesLSTM(n_out=hidden))
                .layer(RnnOutputLayer(n_out=n_out, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(_inputs.recurrent(n_in, max(naive_buckets)))
                .build())
        return MultiLayerNetwork(conf).init()

    decode_cache_len = 256

    def decode(seed):
        from deeplearning4j_tpu.nn.layers.attention import (
            CausalSelfAttention)
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .list()
                .layer(CausalSelfAttention(n_out=hidden, n_heads=8,
                                           cache_len=decode_cache_len))
                .layer(RnnOutputLayer(n_out=n_out, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(_inputs.recurrent(n_in, decode_cache_len))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.RandomState(0)
    x_dense = rng.randn(1, n_in).astype(np.float32)
    x_step = rng.randn(1, n_in).astype(np.float32)

    # ---- naive baseline: the reference stack under the SAME load ------
    # One model, one request at a time (``output()`` is not reentrant in
    # the reference stack, so a lock serializes), and every request
    # recomputes the FULL conversation history.  Generously bucketed
    # (shapes pre-warmed, history padded up the ladder) so the baseline
    # pays NO compiles in the measured loop — only the O(T) recompute
    # plus head-of-line blocking that sessions + batching eliminate.
    naive = rnn(21)
    for tb in naive_buckets:
        np.asarray(naive.output(np.zeros((1, tb, n_in), np.float32)))
    naive_clients = (concurrency_sweep[1] if len(concurrency_sweep) > 1
                     else concurrency_sweep[0])
    naive_lat: list = []
    naive_serial = threading.Lock()
    naive_record = threading.Lock()
    naive_stop = time.perf_counter() + duration_s

    def naive_client(i):
        hist = 0
        while time.perf_counter() < naive_stop:
            hist = min(hist + 1, max(naive_buckets))
            tb = next(b for b in naive_buckets if b >= hist)
            xs = np.zeros((1, tb, n_in), np.float32)
            t0 = time.perf_counter()
            with naive_serial:           # one request at a time
                np.asarray(naive.output(xs))
            dt = time.perf_counter() - t0
            with naive_record:
                naive_lat.append(dt)

    nthreads = [threading.Thread(target=naive_client, args=(i,))
                for i in range(naive_clients)]
    for t in nthreads:
        t.start()
    for t in nthreads:
        t.join()
    naive_lat.sort()

    def pct(lat, p):
        return (round(lat[min(len(lat) - 1, int(p * len(lat)))] * 1e3, 2)
                if lat else None)

    naive_p99 = pct(naive_lat, 0.99)
    naive_rps = len(naive_lat) / duration_s

    # ---- SLO calibration: unloaded single-step session latency --------
    cal = InferenceEngine(rnn(22), max_batch_size=max_batch,
                          timestep_buckets=naive_buckets,
                          max_latency_ms=max_latency_ms,
                          name="bench-cal").start()
    cal_lat = []
    for i in range(30):
        t0 = time.perf_counter()
        cal.predict_session("cal", x_step)
        cal_lat.append(time.perf_counter() - t0)
    cal.stop()
    cal_lat.sort()
    slo_p99_ms = max(25.0, 8.0 * (pct(cal_lat, 0.50) or 1.0))

    # ---- 3-model registry, RNN sessions, SLO admission ----------------
    reg = ModelRegistry()
    engines = {
        "dense-a": InferenceEngine(dense(23), max_batch_size=max_batch,
                                   max_latency_ms=max_latency_ms,
                                   queue_capacity=4 * max_batch,
                                   name="dense-a", slo_p99_ms=slo_p99_ms),
        "dense-b": InferenceEngine(dense(24), max_batch_size=max_batch,
                                   max_latency_ms=max_latency_ms,
                                   queue_capacity=4 * max_batch,
                                   name="dense-b", slo_p99_ms=slo_p99_ms),
        "rnn": InferenceEngine(rnn(25), max_batch_size=max_batch,
                               timestep_buckets=naive_buckets,
                               max_latency_ms=max_latency_ms,
                               queue_capacity=4 * max_batch,
                               name="rnn", slo_p99_ms=slo_p99_ms),
        # KV-ring decode tenant: all its traffic is sessions (one
        # dispatch per token), so batching knobs stay minimal
        "decode": InferenceEngine(decode(26), max_batch_size=1,
                                  max_latency_ms=max_latency_ms,
                                  queue_capacity=4 * max_batch,
                                  name="decode", slo_p99_ms=slo_p99_ms),
    }
    for name, eng in engines.items():
        reg.register(name, eng)
    engines["dense-a"].warmup((n_in,))
    engines["dense-b"].warmup((n_in,))
    engines["decode"].warmup_decode((n_in,))

    best = {"rps": 0.0}
    try:
        for clients in concurrency_sweep:
            lat: list = []
            lock = threading.Lock()
            counts = [0] * clients
            sheds = [0] * clients
            stop_at = time.perf_counter() + duration_s

            def client(i):
                # a quarter each: RNN sessions, KV-ring decode sessions,
                # and the two dense tenants
                names = ("rnn", "decode", "dense-a", "dense-b")
                name = names[i % 4]
                # session ids are scoped to the sweep level: the cache
                # outlives levels, and a reused decode id would resume
                # a ring already at cache_len with this level's token
                # counter back at zero
                sid = f"conv-{clients}x{i}"
                while time.perf_counter() < stop_at:
                    t0 = time.perf_counter()
                    try:
                        if name == "rnn":
                            reg.predict(name, x_step, session=sid)
                        elif name == "decode":
                            # the ring fills after cache_len tokens:
                            # rotate to a fresh conversation, like a
                            # chat frontend opening a new session
                            part = counts[i] // decode_cache_len
                            reg.predict(name, x_step,
                                        session=f"{sid}-{part}")
                        else:
                            reg.predict(name, x_dense, timeout=30.0)
                    except ServingError:
                        sheds[i] += 1
                        time.sleep(0.002)       # shed: back off briefly
                        continue
                    with lock:
                        lat.append(time.perf_counter() - t0)
                    counts[i] += 1

            t_start = time.perf_counter()
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t_start
            done = sum(counts)
            lat.sort()
            level = {"clients": clients, "rps": round(done / elapsed, 1),
                     "admitted_p99_ms": pct(lat, 0.99),
                     "shed": sum(sheds),
                     "shed_fraction": round(
                         sum(sheds) / max(1, done + sum(sheds)), 3)}
            _emit({"metric": "serving_v2_sweep_level", **level},
                  file=sys.stderr)
            if level["rps"] > best.get("rps", 0.0):
                best = level
    finally:
        reg.stop_all()

    session_steps = 0.0
    decode_steps = 0.0
    for labels, val in monitor.snapshot().get(
            "serving_session_steps_total", {}).get("values", {}).items():
        session_steps += val
        if 'model="decode"' in labels:
            decode_steps += val
    admitted_p99 = best.get("admitted_p99_ms")
    return {"metric": "serving_v2_multimodel_requests_per_sec",
            "value": best.get("rps", 0.0), "unit": "requests/sec",
            "vs_baseline": (round(best.get("rps", 0.0) / naive_rps, 3)
                            if naive_rps else None),
            "models": 4, "saturating_clients": best.get("clients"),
            "decode_session_steps": decode_steps,
            "slo_p99_ms": round(slo_p99_ms, 2),
            "admitted_p99_ms": admitted_p99,
            "held_slo": (admitted_p99 is not None
                         and admitted_p99 <= 1.5 * slo_p99_ms),
            "shed_fraction": best.get("shed_fraction"),
            "session_steps": session_steps,
            "naive_clients": naive_clients,
            "naive_fullseq_rps": round(naive_rps, 1),
            "naive_fullseq_p99_ms": naive_p99,
            "baseline_missed_slo": (naive_p99 is not None
                                    and naive_p99 > slo_p99_ms),
            "max_batch": max_batch, "max_latency_ms": max_latency_ms}


def bench_decode(n_in: int = 64, hidden: int = 128, heads: int = 8,
                 n_out: int = 32, T: int = 128, trials: int = 5,
                 smoke: bool = False) -> dict:
    """Autoregressive decode roofline (``--decode``): tokens/sec of the
    one-dispatch-per-token KV-cache ring (``decode_step`` through a
    device-resident ``SessionCache``) versus the naive baseline that
    re-runs ``output()`` over the growing prefix every token — O(T^2)
    total attention work and O(T) dispatch payload per token, against
    the ring's O(T) work and O(1) payload.

    Both sides are shape-warmed before timing (the naive side pads the
    prefix up a powers-of-two bucket ladder exactly like the serving
    tier, so it pays zero compiles in the loop — only the recompute).
    The hand bytes model prices one decoded token: stream the weights +
    read the K/V ring once.  ``vs_baseline`` is the decode/naive
    tokens/sec ratio — the acceptance gate is >= 5x at T=128 on CPU.
    """
    from deeplearning4j_tpu.nn.conf import inputs as _inputs
    from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.attention import CausalSelfAttention
    from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import SessionCache
    from deeplearning4j_tpu.serving.bucketing import batch_ladder

    if smoke:
        T, trials = 32, 2

    def decode_net(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .list()
                .layer(CausalSelfAttention(n_out=hidden, n_heads=heads,
                                           cache_len=T))
                .layer(RnnOutputLayer(n_out=n_out, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(_inputs.recurrent(n_in, T))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.RandomState(0)
    tokens = rng.randn(T, 1, n_in).astype(np.float32)
    ladder = batch_ladder(T)

    # ---- naive baseline: full-prefix recompute per token --------------
    naive = decode_net(31)
    for tb in ladder:                      # pre-warm every prefix bucket
        np.asarray(naive.output(np.zeros((1, tb, n_in), np.float32)))

    def naive_tokens() -> float:
        gc.collect()               # keep GC pauses out of the window
        t0 = time.perf_counter()
        for t in range(1, T + 1):
            tb = next(b for b in ladder if b >= t)
            xs = np.zeros((1, tb, n_in), np.float32)
            xs[:, :t] = np.swapaxes(tokens[:t], 0, 1)
            np.asarray(naive.output(xs))
        return time.perf_counter() - t0

    # ---- KV-ring decode: one dispatch per token ------------------------
    ring = decode_net(31)
    cache = SessionCache(ring, name="bench-decode")
    for t in range(T):                     # warm every (cap, grow) bucket
        cache.step("warm", tokens[t].astype(np.float32))
    cache.clear_all()
    for t in range(T):                     # untimed shakeout session
        cache.step("shakeout", tokens[t])  # (fresh-session alloc path)
    cache.clear_all()

    def ring_tokens() -> float:
        sid = f"s{time.monotonic_ns()}"
        gc.collect()               # ~20 ms windows: one pause is a 50%
        t0 = time.perf_counter()   # swing, so collect outside the timer
        for t in range(T):
            cache.step(sid, tokens[t])
        dt = time.perf_counter() - t0
        cache.clear(sid)
        return dt

    # Interleave the two sides: host throughput drifts over a run
    # (frequency scaling, neighbors), so timing all naive windows then
    # all ring windows would bill the drift to whichever side ran
    # second.  Paired windows see the same weather; ``vs_baseline`` is
    # the median of per-pair ratios, immune to monotone drift.
    pairs = [(naive_tokens(), ring_tokens()) for _ in range(trials)]
    naive_meas = _sorted_meas([n for n, _ in pairs])
    ring_meas = _sorted_meas([r for _, r in pairs])
    naive_tps = T / naive_meas["median"]
    ring_tps = T / ring_meas["median"]
    ratios = sorted(n / r for n, r in pairs)
    ratio = (ratios[trials // 2] if trials % 2 else
             0.5 * (ratios[trials // 2 - 1] + ratios[trials // 2]))

    # ---- hand bytes model: one decoded token at full ring --------------
    # stream the weights once + read the K/V ring once (f32);
    # everything else (the token's activations) is noise at B=1
    weight_bytes = 4 * (3 * n_in * hidden + hidden * hidden + hidden
                        + hidden * n_out + n_out)
    ring_bytes = 2 * heads * T * (hidden // heads) * 4
    decode_bytes_per_token = weight_bytes + ring_bytes
    # the naive side recomputes the whole prefix every token:
    # sum_t t = T(T+1)/2 attention positions for the ring's T
    naive_recompute_positions = T * (T + 1) // 2

    return {"metric": "decode_tokens_per_sec",
            "value": round(ring_tps, 1), "unit": "tokens/sec",
            "vs_baseline": round(ratio, 2),
            "naive_fullseq_tokens_per_sec": round(naive_tps, 1),
            "T": T, "hidden": hidden, "heads": heads,
            "hand_bytes_per_token": decode_bytes_per_token,
            "hand_weight_bytes": weight_bytes,
            "hand_kv_ring_bytes": ring_bytes,
            "naive_recompute_positions": naive_recompute_positions,
            "ring_positions": T,
            **_band_fields(ring_meas, T, trials)}


def bench_scaleout(smoke: bool = False) -> dict:
    """Compressed-wire async Hogwild vs synchronous data-parallel
    (``scaleout/async_trainer.py``): K=3 OS-process workers against the
    TCP parameter server.  Records the three scaleout acceptance
    numbers on one stdout line:

    - ``wire_reduction_x``: total wire bytes of a topk8 run vs an f32
      run at equal rounds, with both runs' final accuracy inside the
      sync-DP parity band (int8-quantized top-k pushes + int8 dense
      pulls vs dense f32 both ways).
    - ``value`` (the crossover): async samples/sec over sync-DP
      samples/sec, both time-boxed under the same seeded one-rank
      straggler (``DL4J_TPU_FAULT_SLOW_WORKER_MS=rank:ms``) — sync
      pays the straggler every barrier, async only loses the
      straggler's own contribution.
    - ``kill_survived``: a topk8 run with one worker SIGKILLed
      mid-run (PR-6 preemption simulator) still finishes and converges.

    Sub-run records go to stderr; stdout stays one line.
    """
    from deeplearning4j_tpu.scaleout import async_trainer as at

    k = 3
    rounds = 12 if smoke else 40
    duration = 1.5 if smoke else 4.0
    straggler = (1, 120.0 if smoke else 250.0)
    band = 0.08

    def note(tag, rec):
        slim = {kk: vv for kk, vv in rec.items() if kk != "workers"}
        _emit({"metric": f"scaleout_{tag}", **slim}, file=sys.stderr,
              device=_CPU_CHILDREN)
        return rec

    sync = note("sync_dp", at.run_sync_dp(k=k, rounds=rounds))
    topk = note("async_topk8", at.run_async(k=k, codec="topk8",
                                            rounds=rounds))
    f32 = note("async_f32", at.run_async(k=k, codec="f32",
                                         rounds=rounds))
    kill = note("async_kill", at.run_async(
        k=k, codec="topk8", rounds=rounds,
        die_at_round=(k - 1, max(2, rounds // 3))))
    a_thr = note("async_straggler", at.run_async(
        k=k, codec="topk8", rounds=rounds, duration=duration,
        straggler=straggler))
    s_thr = note("sync_straggler", at.run_sync_dp(
        k=k, rounds=rounds, duration=duration, straggler=straggler))

    crossover = (a_thr["samples_per_sec"] / s_thr["samples_per_sec"]
                 if s_thr["samples_per_sec"] else None)
    wire_reduction = (f32["wire_bytes"] / topk["wire_bytes"]
                      if topk["wire_bytes"] else None)
    lock = monitor.histogram(
        "server_lock_wait_seconds",
        "seconds waiting to acquire a parameter-server lock shard"
    ).stats()
    return {
        "metric": "scaleout_async_vs_sync_throughput_x",
        "value": round(crossover, 2) if crossover else None,
        "unit": "x", "vs_baseline": None,
        "k": k, "rounds": rounds, "smoke": smoke,
        "straggler_rank": straggler[0], "straggler_ms": straggler[1],
        "async_samples_per_sec": a_thr["samples_per_sec"],
        "sync_samples_per_sec": s_thr["samples_per_sec"],
        "crossover_ok": bool(crossover and crossover >= 2.0),
        "wire_bytes_f32": f32["wire_bytes"],
        "wire_bytes_topk8": topk["wire_bytes"],
        "wire_reduction_x": (round(wire_reduction, 2)
                             if wire_reduction else None),
        "wire_ok": bool(wire_reduction and wire_reduction >= 3.0),
        "acc_sync": sync["accuracy"], "acc_async_topk8": topk["accuracy"],
        "acc_async_f32": f32["accuracy"], "parity_band": band,
        "parity_ok": bool(
            abs(topk["accuracy"] - sync["accuracy"]) <= band
            and abs(f32["accuracy"] - sync["accuracy"]) <= band),
        "kill_survived": bool(-9 in kill["returncodes"]
                              and kill["survivors"] == k - 1
                              and abs(kill["accuracy"] - sync["accuracy"])
                              <= band),
        "staleness_max": topk["staleness_max"],
        "staleness_bound": topk["staleness_bound"],
        "staleness_gauge_on_metrics": (
            "scaleout_staleness" in monitor.prometheus_text()),
        "lock_wait": {"count": lock.get("count"),
                      "p95_s": lock.get("p95")},
    }


def _serving_compile_count() -> float:
    """Total AOT bucket compiles recorded by the monitor registry —
    proves recompiles stay bounded by the warmed bucket count."""
    total = 0.0
    snap = monitor.snapshot()
    for name in ("serving_bucket_compiles_total",):
        for _labels, val in snap.get(name, {}).get("values", {}).items():
            total += val
    return total


def bench_deploy(smoke: bool = False) -> dict:
    """Zero-downtime deployment acceptance (``deploy/``): a live
    ``fit()`` publishes weight versions into a
    :class:`~deeplearning4j_tpu.deploy.VersionedWeightStore` while the
    same model serves HTTP traffic; a sidecar
    :class:`~deeplearning4j_tpu.deploy.RolloutController` canaries and
    promotes each version.  The stdout line asserts the four
    acceptance properties:

    - >= 2 automatic promotions (``push -> probe -> promote``) land
      during/after training, and served accuracy strictly improves
      from the untrained baseline;
    - the constant client load observes ZERO 5xx across every swap;
    - ``serving_bucket_compiles_total`` never moves after warmup
      (weights are call operands — swap is pure data motion);
    - a seeded bad update (garbage weights) canaries, fails the gates,
      auto-rolls-back leaving a ``rollout_rollback`` flight bundle,
      and a corrupted snapshot is refused over HTTP with a 4xx and no
      engine change.
    """
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from deeplearning4j_tpu.deploy import (DeploymentListener,
                                           RolloutController,
                                           VersionedWeightStore)
    from deeplearning4j_tpu.nn.conf import inputs as _inputs
    from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import InferenceEngine, ModelRegistry
    from deeplearning4j_tpu.ui.server import UIServer

    n_in, n_out, hidden = 8, 3, 16
    n_train = 192 if smoke else 512
    epochs = 2 if smoke else 4
    tmp = tempfile.mkdtemp(prefix="dl4j-deploy-")
    os.environ[("DL4J_TPU_FLIGHT_DIR")] = os.path.join(tmp, "flight")
    os.environ["DL4J_TPU_FLIGHT_MIN_INTERVAL_S"] = "0"

    # seeded 3-class gaussian blobs: separable enough that even a short
    # fit() beats the untrained baseline by a wide margin
    rng = np.random.RandomState(7)
    centers = rng.randn(n_out, n_in) * 3.0
    cls = rng.randint(0, n_out, size=n_train)
    X = (centers[cls] + rng.randn(n_train, n_in)).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[cls]
    Xe, ye = X[:64], y[:64]

    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater("sgd").learning_rate(0.1)
            .list()
            .layer(DenseLayer(n_out=hidden))
            .layer(OutputLayer(n_out=n_out))
            .set_input_type(_inputs.feed_forward(n_in))
            .build())
    net = MultiLayerNetwork(conf).init()

    registry = ModelRegistry()
    registry.register(
        "deploy",
        InferenceEngine(net, max_batch_size=16, max_latency_ms=1.0,
                        queue_capacity=256, name="deploy"),
        warmup_shape=(n_in,))
    store = VersionedWeightStore(os.path.join(tmp, "store"))
    ctl = RolloutController(registry, "deploy", store,
                            canary_fraction=0.3,
                            eval_features=Xe, eval_labels=ye,
                            min_probe_rounds=2)
    ui = UIServer(port=0).attach_registry(registry).attach_deployment(ctl)
    ui.start()
    base = f"http://127.0.0.1:{ui.port}"

    def served_accuracy() -> float:
        out = np.concatenate(
            [np.asarray(registry.predict("deploy", Xe[i:i + 16]))
             for i in range(0, len(Xe), 16)])
        return float(np.mean(np.argmax(out, -1) == np.argmax(ye, -1)))

    acc_before = served_accuracy()
    compiles0 = _serving_compile_count()

    # -- constant client load over HTTP; every swap happens under it ----
    codes: dict = {}
    stop = threading.Event()
    stop_roller = threading.Event()

    def load_client():
        body = json.dumps({"model": "deploy",
                           "features": Xe[:4].tolist()}).encode()
        while not stop.is_set():
            try:
                req = urllib.request.Request(
                    base + "/predict", data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as r:
                    codes[r.status] = codes.get(r.status, 0) + 1
            except urllib.error.HTTPError as e:
                codes[e.code] = codes.get(e.code, 0) + 1
            except Exception:
                codes["io"] = codes.get("io", 0) + 1
            time.sleep(0.005)

    # -- sidecar rollout loop: promotes whatever fit() publishes --------
    actions: list = []

    def rollout_loop():
        while not stop_roller.is_set():
            try:
                act = ctl.step()
            except Exception as e:        # corrupt push etc. must not kill it
                act = f"error:{type(e).__name__}"
            if act != "noop":
                actions.append(act)
            time.sleep(0.01)

    loader = threading.Thread(target=load_client, daemon=True)
    roller = threading.Thread(target=rollout_loop, daemon=True)
    loader.start()
    roller.start()

    def drain(timeout_s: float) -> None:
        """Wait for the sidecar to consume the store head (or
        quarantine it) and return to idle."""
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            head = ctl.store.latest()
            if (ctl.state == "idle"
                    and head is not None
                    and (registry.get("deploy").active_version >= head
                         or head in ctl.quarantined)):
                return
            time.sleep(0.05)

    # two fit segments, each publishing versions the sidecar promotes —
    # the >= 2 promotions land while the load thread hammers /predict
    listener = DeploymentListener(store, every_n_iterations=0,
                                  publish_on_epoch_end=True)
    net.set_listeners(listener)
    seg_timeout = 30 if smoke else 60
    net.fit(X, y, epochs=max(1, epochs // 2))
    drain(seg_timeout)
    net.fit(X, y, epochs=max(1, epochs - epochs // 2))
    drain(seg_timeout)
    acc_after = served_accuracy()
    promotions = sum(1 for h in ctl.history if h["action"] == "promote")

    # -- seeded bad update: garbage weights must canary then roll back --
    n_params = net.get_flat_params().size
    active_before_bad = registry.get("deploy").active_version
    store.publish(rng.randn(n_params).astype(np.float32) * 100.0,
                  source="bad_update")
    deadline = time.time() + (20 if smoke else 40)
    rollbacks = 0
    while time.time() < deadline:
        rollbacks = sum(1 for h in ctl.history
                        if h["action"] == "rollback")
        if rollbacks >= 1 and ctl.state == "idle":
            break
        time.sleep(0.05)
    active_after_bad = registry.get("deploy").active_version

    # -- corrupted snapshot over HTTP: 4xx, no swap ---------------------
    # stop the sidecar first: the corruption below must land before
    # anything races to push the fresh version
    stop_roller.set()
    roller.join(timeout=5)
    vbad = store.publish(net.get_flat_params(), source="corrupt_me")
    _corrupt_store_entry(store, vbad)
    corrupt_code = None
    try:
        req = urllib.request.Request(
            base + "/deploy/deploy",
            data=json.dumps({"action": "push", "version": vbad}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=10) as r:
            corrupt_code = r.status
    except urllib.error.HTTPError as e:
        corrupt_code = e.code
    active_after_corrupt = registry.get("deploy").active_version

    stop.set()
    loader.join(timeout=5)
    ui.stop()
    compiles = _serving_compile_count() - compiles0

    n5xx = sum(v for k, v in codes.items()
               if isinstance(k, int) and 500 <= k < 600)
    ok = bool(promotions >= 2
              and acc_after > acc_before
              and n5xx == 0
              and compiles == 0
              and rollbacks >= 1
              and ctl.last_bundle
              and active_after_bad == active_before_bad
              and active_after_corrupt == active_before_bad
              and corrupt_code is not None and 400 <= corrupt_code < 500)
    return {"metric": "deploy_hot_swap_acceptance", "value": int(ok),
            "unit": "pass", "vs_baseline": None, "smoke": smoke,
            "pass": ok,
            "promotions": promotions,
            "published_versions": listener.published,
            "served_acc_before": round(acc_before, 4),
            "served_acc_after": round(acc_after, 4),
            "acc_improved": bool(acc_after > acc_before),
            "http_codes": {str(k): v for k, v in sorted(
                codes.items(), key=str)},
            "http_5xx": n5xx,
            "recompiles_after_warmup": compiles,
            "rollbacks": rollbacks,
            "rollback_bundle": ctl.last_bundle,
            "bad_update_rolled_back": bool(
                rollbacks >= 1
                and active_after_bad == active_before_bad),
            "corrupt_push_status": corrupt_code,
            "corrupt_rejected": bool(
                corrupt_code is not None and 400 <= corrupt_code < 500),
            "active_version": registry.get("deploy").active_version,
            "rollout_actions": actions[-20:]}


def _corrupt_store_entry(store, version: int) -> None:
    """Flip bytes inside a snapshot's ``flat.bin`` while keeping the
    (now stale) manifest — a guaranteed SHA-256 mismatch on load.
    Byte-flipping the zip at a random offset is NOT enough: zip readers
    go through the central directory and ignore damaged local headers."""
    import io
    import zipfile
    path = os.path.join(store.directory,
                        "weights-v%010d.zip" % int(version))
    with zipfile.ZipFile(path) as zf:
        entries = {n: zf.read(n) for n in zf.namelist()}
    flat = bytearray(entries["flat.bin"])
    flat[len(flat) // 2] ^= 0xFF
    entries["flat.bin"] = bytes(flat)
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w") as zf:
        for n, b in entries.items():
            zf.writestr(n, b)
    with open(path, "wb") as f:
        f.write(buf.getvalue())


def bench_scaling() -> dict:
    """ParallelWrapper scaling efficiency 1→8 on a virtual CPU mesh, in a
    subprocess (the TPU session only has one real chip; the CPU mesh is the
    Spark-``local[N]`` analogue, SURVEY.md §4)."""
    import os
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8'\n"
        "os.environ['JAX_PLATFORMS']='cpu'\n"
        "import jax\n"
        "jax.config.update('jax_platforms','cpu')\n"
        "import json\n"
        "from deeplearning4j_tpu.parallel.scaling import scaling_report\n"
        "from deeplearning4j_tpu.models.lenet import lenet\n"
        "from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork\n"
        "rep = scaling_report(lambda: MultiLayerNetwork(lenet()),\n"
        "                     [1, 2, 4, 8], batch_size=64, n_rounds=4)\n"
        "print(json.dumps({'efficiency_8': rep[8]['efficiency'],\n"
        "                  'report': rep}))\n"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=1200, env=env)
    if out.returncode != 0:
        return {"metric": "parallel_scaling_efficiency_1to8",
                "value": None, "unit": "ratio",
                "error": out.stderr.strip()[-500:]}
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    return {"metric": "parallel_scaling_efficiency_1to8",
            "value": rep.get("efficiency_8"), "unit": "ratio",
            "detail": rep, "vs_baseline": None}


def bench_mesh(smoke: bool = False) -> dict:
    """Pod-runtime proof (``parallel/mesh.py`` + ``parallel/main.py``):
    real K=2 OS-process pods over the gloo CPU fabric, one stdout JSON
    line with the three mesh acceptance numbers the CI mesh job asserts:

    - ``parity_dp_ok``: a 2-process data-parallel pod's per-step fp32
      scores AND final param SHA-256 are bitwise identical to the
      1-process run over the same 2-slot mesh (same shape -> same
      program -> same bits).
    - ``parity_zero_ok``: same bit-identity for the DP x ZeRO pod
      (``data=1, zero=2`` — updater state sharded over ``zero``).
    - ``updater_bytes_ratio`` / ``zero_bytes_ok``: per-process
      addressable updater-state bytes of the ZeRO pod vs the unsharded
      DP pod (the ``mesh_updater_state_bytes`` gauge); the gate is
      <= 0.6x at zero_degree=2.

    The full (non-smoke) run adds ``resume_ok``: SIGKILL one process at
    step entry mid-run, relaunch the whole pod with ``--resume auto``
    from the sharded pod checkpoint, and require the restored+resumed
    curve and final params to match the uninterrupted pod bitwise.

    Sub-run records go to stderr; stdout stays one line.
    """
    from deeplearning4j_tpu.parallel.main import run_pod

    steps = 4 if smoke else 6

    def note(tag, rec):
        slim = {kk: rec[kk] for kk in ("k", "data", "zero", "mode",
                                       "steps", "returncodes")}
        slim.update({kk: rec.get(kk) for kk in ("scores", "param_sha",
                                                "updater_state_bytes")})
        _emit({"metric": f"mesh_{tag}", **slim}, file=sys.stderr,
              device=_CPU_CHILDREN)
        return rec

    dp2 = note("dp_k2", run_pod(k=2, data=2, mode="dp", steps=steps))
    dp1 = note("dp_k1", run_pod(k=1, data=2, mode="dp", steps=steps))
    z2 = note("zero_k2", run_pod(k=2, data=1, zero=2, mode="zero",
                                 steps=steps))
    z1 = note("zero_k1", run_pod(k=1, data=1, zero=2, mode="zero",
                                 steps=steps))

    def parity(a, b):
        return (a["returncodes"] == [0] * a["k"]
                and b["returncodes"] == [0] * b["k"]
                and a.get("scores") == b.get("scores")
                and a.get("param_sha") is not None
                and a.get("param_sha") == b.get("param_sha"))

    parity_dp_ok = parity(dp2, dp1)
    parity_zero_ok = parity(z2, z1)
    ratio = (z2["updater_state_bytes"] / dp2["updater_state_bytes"]
             if dp2.get("updater_state_bytes") else None)
    zero_bytes_ok = bool(ratio is not None and ratio <= 0.6)

    resume_ok = None
    if not smoke:
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            hurt = note("dp_killed", run_pod(
                k=2, data=2, mode="dp", steps=steps,
                checkpoint_dir=d, checkpoint_every=2,
                die_at=(1, steps - 2), relaunch=True))
            resumed = note("dp_resumed", hurt["resumed"])
            resume_ok = (any(rc != 0 for rc in hurt["returncodes"])
                         and resumed["returncodes"] == [0, 0]
                         and resumed.get("scores") == dp2.get("scores")
                         and resumed.get("param_sha") == dp2.get(
                             "param_sha"))

    ok = bool(parity_dp_ok and parity_zero_ok and zero_bytes_ok
              and resume_ok is not False)
    return {"metric": "mesh_pod_runtime", "value": 1 if ok else 0,
            "unit": "ok", "smoke": smoke, "steps": steps,
            "parity_dp_ok": parity_dp_ok,
            "parity_zero_ok": parity_zero_ok,
            "updater_bytes_ratio": (round(ratio, 4)
                                    if ratio is not None else None),
            "zero_bytes_ok": zero_bytes_ok,
            "resume_ok": resume_ok,
            "updater_state_bytes": {
                "dp_k2": dp2.get("updater_state_bytes"),
                "zero_k2": z2.get("updater_state_bytes")}}


def _smoke_precision_fields(batch: int = 32) -> dict:
    """Precision-campaign fields for the CI perf-smoke line: the fp32
    twin's cost-model bytes, the chip-posture estimate under the
    resolved policy, and the deterministic autotuner decision for the
    smoke ladder.  The estimate re-costs the fp32 program's f32 traffic
    at policy widths (tools/hbm_profile.py owns the model) because
    CPU-XLA upcasts bf16 conv/dot through convert fusions and would
    OVERSTATE the bf16 program's bytes."""
    import os

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.lenet import lenet
    from deeplearning4j_tpu.nn import precision
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from tools import autotune as _autotune
    from tools import hbm_profile as _hp

    pol = MultiLayerNetwork(lenet()).init()._pol()
    prev = os.environ.get(precision._ENV)
    os.environ[precision._ENV] = precision.FP32
    try:
        net32 = MultiLayerNetwork(lenet()).init()
    finally:
        if prev is None:
            os.environ.pop(precision._ENV, None)
        else:
            os.environ[precision._ENV] = prev
    f = jnp.zeros((1, batch, 784), jnp.float32)
    l = jnp.zeros((1, batch, 10), jnp.float32)
    compiled32 = net32._multi_train_step.lower(
        net32.params, net32.updater_state, net32.net_state,
        net32.iteration, f, l, None, None, net32._rng_key).compile()
    cost32 = _compiled_cost(compiled32).get("bytes") or 0.0
    _, total32, by_dtype32 = _hp.profile_hlo(compiled32.as_text())
    moments_io = 2 * sum(int(a.size) * a.dtype.itemsize
                         for a in jax.tree.leaves(net32.updater_state))
    master_io = 2 * 4 * sum(int(a.size)
                            for a in jax.tree.leaves(net32.params))
    est = _hp.chip_posture_estimate(total32, by_dtype32.get("f32", 0),
                                    moments_io, master_io,
                                    pol.master_weights)
    est_cost = cost32 * (est / total32) if total32 else cost32
    if pol.name == precision.FP32:
        est_cost = cost32
    fields = {"precision": pol.describe(),
              "xla_cost_bytes_fp32": round(cost32, 1),
              "hbm_bytes_chip_estimate": round(est_cost, 1),
              "bytes_dropped": bool(est_cost < cost32)}
    d = _autotune.autotune("lenet", deterministic=True, use_cache=False,
                           smoke=True)
    fields["autotune"] = {"signature": d["signature"],
                          "batch": d["batch"],
                          "steps_per_dispatch": d["steps_per_dispatch"],
                          "bytes_per_sample": d["bytes_per_sample"]}
    try:
        base_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools",
            "perf_baseline.json")
        with open(base_path) as fh:
            ref = json.load(fh)["lenet_smoke"]["xla_cost_bytes_fp32"]
        fields["fp32_baseline_bytes"] = ref
        fields["vs_fp32_baseline"] = round(est_cost / ref, 4)
        fields["bytes_dropped_vs_baseline"] = bool(est_cost < ref)
    except Exception:
        pass
    return fields


def _sanitizer_smoke_fields() -> dict:
    """Armed-run fields for the CI smoke line (``DL4J_TPU_SANITIZE=1``):
    drive the device-cache fit path through its budgeted scenario —
    twice, because the sanitizer treats each scenario's first occurrence
    as warmup — then report the process-wide violation count.  The CI
    ingest job asserts ``sanitizer_violations == 0``.  Unarmed runs get
    no extra fields."""
    try:
        from tools.analyze import sanitizer
    except Exception:
        return {}
    if not sanitizer.enabled():
        return {}
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models.lenet import lenet
    from deeplearning4j_tpu.nn.conf import inputs
    from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder()
            .seed(7).updater("adam").learning_rate(0.05)
            .activation("tanh").weight_init("xavier").list()
            .layer(DenseLayer(n_out=16))
            .layer(OutputLayer(n_out=3))
            .set_input_type(inputs.feed_forward(8))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    X = rng.randn(64, 8).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 64)]
    it = ListDataSetIterator(DataSet(X, y), batch_size=16)
    # warmup fit compiles the fused 2-epoch dispatch AND counts as the
    # scenario's warmup occurrence; the second fit replays the same
    # shape, so it must be all cache hits within budget
    net.fit(it, epochs=2, ingest="cache")
    monitor.sanitize_end_warmup()
    net.fit(it, epochs=2, ingest="cache")   # enforced occurrence
    return {"sanitizer_violations": sanitizer.violation_count(),
            "sanitizer_violation_kinds": sorted(
                {v["kind"] for v in sanitizer.violations()})}


def _alert_smoke_fields() -> dict:
    """One alert-engine pass over everything the smoke run published:
    a clean run must leave every default rule in ``ok`` (the CI ingest
    job asserts ``alerts_firing == []``).  Two passes so the windowed
    rules also evaluate against a real ring sample, not just the
    burst-from-zero path."""
    from deeplearning4j_tpu.monitor import alerts
    engine = alerts.AlertEngine(interval_s=0.1)
    engine.evaluate_once()
    statuses = engine.evaluate_once()
    return {
        "alerts_evaluated": len(statuses),
        "alerts_firing": sorted(s["name"] for s in statuses
                                if s["state"] == alerts.FIRING),
    }


def _open_loop(fire, offered_qps: float, duration_s: float,
               seed: int = 0, pool_size: int = 64) -> dict:
    """Open-loop load generator: Poisson arrivals at ``offered_qps``
    for ``duration_s``, each served by calling ``fire()`` (returns an
    HTTP-ish status code; 200 = admitted, 429/503 = shed).

    Arrival times are fixed up front and every latency is measured
    from the SCHEDULED arrival, not from when a generator thread got
    around to sending — so queueing delay the service induces (or
    generator starvation it causes) is charged to the service.  That
    is the coordinated-omission fix closed-loop clients can't give:
    a closed-loop client waits for a reply before its next send and
    so quietly lowers the offered rate whenever the service slows.
    Percentiles cover admitted requests only (shed fast-fails are
    counted, not timed)."""
    import threading

    rng = np.random.RandomState(seed)
    arrivals = []
    t = rng.exponential(1.0 / offered_qps)
    while t < duration_s:
        arrivals.append(t)
        t += rng.exponential(1.0 / offered_qps)

    results: list = []
    rec = threading.Lock()
    nxt = threading.Lock()
    cursor = [0]
    start = time.perf_counter()

    def runner():
        while True:
            with nxt:
                i = cursor[0]
                if i >= len(arrivals):
                    return
                cursor[0] = i + 1
            at = arrivals[i]
            delay = at - (time.perf_counter() - start)
            if delay > 0:
                time.sleep(delay)
            try:
                code = fire()
            except Exception:
                code = -1
            lat = (time.perf_counter() - start) - at
            with rec:
                results.append((code, lat))

    pool = [threading.Thread(target=runner, daemon=True)
            for _ in range(min(pool_size, len(arrivals) or 1))]
    for th in pool:
        th.start()
    for th in pool:
        th.join(timeout=120.0)

    admitted = sorted(lat for code, lat in results if code == 200)
    shed = sum(1 for code, _ in results if code in (429, 503))
    errors = len(results) - len(admitted) - shed

    def pct(p):
        return (round(admitted[min(len(admitted) - 1,
                                   int(p * len(admitted)))] * 1e3, 2)
                if admitted else None)

    return {"offered": len(arrivals),
            "offered_qps": round(len(arrivals) / duration_s, 1),
            "admitted": len(admitted), "shed": shed, "errors": errors,
            "admitted_rps": round(len(admitted) / duration_s, 1),
            "p50_ms": pct(0.50), "p95_ms": pct(0.95),
            "p99_ms": pct(0.99)}


def bench_serving_open_loop(n_in: int = 64, hidden: int = 256,
                            n_out: int = 10, max_batch: int = 32,
                            max_latency_ms: float = 2.0,
                            offered_qps: float = None,
                            duration_s: float = 4.0) -> dict:
    """Open-loop serving benchmark (``--serve --open-loop``): Poisson
    arrivals at a fixed offered rate against the same single-model
    ``InferenceEngine`` the closed-loop sweep uses.  The offered rate
    defaults to 2x the measured sequential one-dispatch-per-request
    rate, so the dynamic batcher is genuinely oversubscribed and must
    coalesce to keep up; admission stays open (no SLO) so the admitted
    rate IS the sustained service rate.  Latencies are
    coordinated-omission-free (see ``_open_loop``)."""
    from deeplearning4j_tpu.nn.conf import inputs as _inputs
    from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import InferenceEngine, QueueFull

    conf = (NeuralNetConfiguration.builder().seed(12)
            .list()
            .layer(DenseLayer(n_out=hidden))
            .layer(DenseLayer(n_out=hidden))
            .layer(OutputLayer(n_out=n_out))
            .set_input_type(_inputs.feed_forward(n_in))
            .build())
    model = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    x1 = rng.randn(1, n_in).astype(np.float32)

    np.asarray(model.output(x1))                     # warm the compile
    t0 = time.perf_counter()
    for _ in range(200):
        np.asarray(model.output(x1))
    seq_rps = 200 / (time.perf_counter() - t0)
    if offered_qps is None:
        offered_qps = round(2.0 * seq_rps, 1)

    engine = InferenceEngine(model, max_batch_size=max_batch,
                             max_latency_ms=max_latency_ms,
                             queue_capacity=4 * max_batch,
                             name="bench-open").start()
    warmed = engine.warmup((n_in,))

    def fire():
        try:
            engine.predict(x1, timeout=10.0)
            return 200
        except QueueFull:
            return 429

    try:
        res = _open_loop(fire, offered_qps, duration_s, seed=3)
    finally:
        engine.stop()

    return {"metric": "serving_open_loop_requests_per_sec",
            "value": res["admitted_rps"], "unit": "requests/sec",
            "vs_baseline": round(res["admitted_rps"] / seq_rps, 3)
            if seq_rps else None,
            "sequential_rps": round(seq_rps, 1),
            "open_loop": True, "warmed_buckets": warmed,
            "max_batch": max_batch, "max_latency_ms": max_latency_ms,
            **{k: res[k] for k in ("offered_qps", "offered", "admitted",
                                   "shed", "errors", "p50_ms", "p95_ms",
                                   "p99_ms")}}


def _arrival_times(kind: str, rate: float, duration_s: float,
                   rng) -> list:
    """Pre-scheduled arrival times for one open-loop tenant.

    ``poisson`` is the homogeneous process ``_open_loop`` uses;
    ``bursty`` and ``diurnal`` are nonhomogeneous Poisson processes
    (Lewis-Shedler thinning): bursty concentrates 3x the mean rate into
    a 25% duty cycle (queue-filling spikes separated by quiet gaps),
    diurnal sweeps one sinusoidal "day" compressed across the run.  All
    three share mean ``rate``, so tenant mixes stay comparable across
    kinds."""
    if rate <= 0 or duration_s <= 0:
        return []
    if kind == "poisson":
        out = []
        t = rng.exponential(1.0 / rate)
        while t < duration_s:
            out.append(t)
            t += rng.exponential(1.0 / rate)
        return out

    burst_x, duty = 3.0, 0.25
    period = max(0.5, duration_s / 4.0)
    base = (1.0 - duty * burst_x) / (1.0 - duty)

    def lam(t: float) -> float:
        if kind == "bursty":
            return rate * (burst_x if (t % period) / period < duty
                           else base)
        if kind == "diurnal":
            return rate * (1.0 + 0.8 * np.sin(
                2.0 * np.pi * t / duration_s))
        raise ValueError(f"unknown arrival kind {kind!r}")

    lam_max = rate * max(burst_x, 1.8)
    out = []
    t = rng.exponential(1.0 / lam_max)
    while t < duration_s:
        if rng.rand() * lam_max < lam(t):
            out.append(t)
        t += rng.exponential(1.0 / lam_max)
    return out


def _open_loop_tagged(fire, arrivals, pool_size: int = 64,
                      join_timeout_s: float = 300.0) -> list:
    """``_open_loop`` generalized to a pre-merged multi-tenant
    schedule: ``arrivals`` is a time-sorted list of ``(t, tag)`` pairs,
    ``fire(tag)`` returns an HTTP-ish status code, and every latency is
    charged from the SCHEDULED arrival (the same coordinated-omission
    contract).  Returns ``[(tag, code, latency_s), ...]``."""
    import threading

    results: list = []
    rec = threading.Lock()
    nxt = threading.Lock()
    cursor = [0]
    start = time.perf_counter()

    def runner():
        while True:
            with nxt:
                i = cursor[0]
                if i >= len(arrivals):
                    return
                cursor[0] = i + 1
            at, tag = arrivals[i]
            delay = at - (time.perf_counter() - start)
            if delay > 0:
                time.sleep(delay)
            try:
                code = fire(tag)
            except Exception:
                code = -1
            lat = (time.perf_counter() - start) - at
            with rec:
                results.append((tag, code, lat))

    pool = [threading.Thread(target=runner, daemon=True)
            for _ in range(min(pool_size, len(arrivals) or 1))]
    for th in pool:
        th.start()
    for th in pool:
        th.join(timeout=join_timeout_s)
    return results


def bench_traffic(smoke: bool = False, n_in: int = 24, hidden: int = 96,
                  n_out: int = 8, max_batch: int = 8,
                  max_latency_ms: float = 2.0) -> dict:
    """Multi-tenant SLO isolation proof (``--traffic``): an open-loop
    generator with per-tenant arrival processes against a 3-model
    registry sharing ONE fair admission controller.  Three phases, one
    stdout JSON line (``metric: traffic_admitted_rps``).

    Tenant mix (rates relative to a closed-loop capacity probe):
    ``gold`` — the victim, Poisson at ~25% of capacity with its own SLO
    and a 2x provisioned share; ``free`` — the offender, bursty at
    ~2.2x capacity with a 1x share; ``public`` — background, diurnal at
    ~10% of capacity.  Each arrival picks a model Zipf-style (rank-1
    head gets most traffic) and RNN traffic churns session ids through
    the device-resident session cache's TTL.

    1. **Calibrate**: gold runs alone; its coordinated-omission-free
       p99 is the unloaded baseline the SLO (and the victim gate) are
       set from.
    2. **Observe-mode overload** (``enforce=False``): the full mix runs
       with shedding disabled — the offender crosses unshed, the victim
       p99 inflates, the ``serving_tenant_unfairness`` gauge rises, the
       ``tenant_unfairness`` alert fires onto ``GET /alerts`` with a
       flight bundle, and ``tenant_slo_violation`` bundles capture the
       scoreboard.
    3. **Fair enforcement** (``enforce=True``): the same mix again —
       now the offender's excess is shed first, and the gates assert
       the victim's p99 holds within 1.5x of unloaded while the
       offender's shed rate is > 0.

    The CI ``traffic-smoke`` job asserts ``victim_held``,
    ``offender_shed_rate > 0``, ``tenants_endpoint_ok`` (a real
    ``GET /tenants`` roundtrip), and ``unfairness_alert`` +
    ``unfairness_bundle``."""
    import glob as _glob
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from deeplearning4j_tpu.monitor import alerts as _alerts
    from deeplearning4j_tpu.nn.conf import inputs as _inputs
    from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.layers.recurrent import (GravesLSTM,
                                                        RnnOutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import (InferenceEngine, ModelRegistry,
                                            QueueFull, SloShed)
    from deeplearning4j_tpu.serving.admission import (
        SloAdmissionController, publish_tenant_telemetry,
        reset_tenant_labels)
    from deeplearning4j_tpu.ui.server import UIServer

    # dur1 is the baseline-estimator budget: the victim gate divides
    # two p99s, and the unloaded one is the scarcer sample
    dur1, dur2, dur3 = (3.0, 2.5, 4.0) if smoke else (6.0, 5.0, 10.0)
    ts_buckets = (4, 8)

    # the generator and the batcher threads share one GIL: at the
    # default 5 ms switch interval a runnable batcher can wait several
    # intervals behind generator threads, charging pure interpreter
    # scheduling to victim latency.  Rotate faster for the bench.
    switch0 = sys.getswitchinterval()
    sys.setswitchinterval(0.001)

    # isolated flight dir: bundle assertions must see THIS run's
    # incidents, not a previous process's
    flight_dir = tempfile.mkdtemp(prefix="dl4j_tpu_traffic_flight_")
    os.environ["DL4J_TPU_FLIGHT_DIR"] = flight_dir
    monitor.reset()
    reset_tenant_labels()
    _alerts.reset()
    alert_eng = _alerts.engine(interval_s=0.5)

    def dense(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .list()
                .layer(DenseLayer(n_out=hidden))
                .layer(OutputLayer(n_out=n_out))
                .set_input_type(_inputs.feed_forward(n_in))
                .build())
        return MultiLayerNetwork(conf).init()

    def rnn(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .list()
                .layer(GravesLSTM(n_out=hidden))
                .layer(RnnOutputLayer(n_out=n_out, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(_inputs.recurrent(n_in,
                                                  max(ts_buckets)))
                .build())
        return MultiLayerNetwork(conf).init()

    # ONE controller shared by every engine: fairness is a service
    # property, not a per-model one.  SLO is calibrated in phase 1;
    # observe-only until phase 3 flips enforcement on.
    # short window + fast refresh: the bang-bang shed rule reacts to a
    # burst within ~refresh_s and breach evidence ages out quickly, so
    # the victim's steady-state p99 stays pinned near the SLO instead
    # of riding multi-second breach transients
    # window must be SHORTER than the offender's burst period (dur/4):
    # a window that spans whole bursts averages the offender's admitted
    # fraction back under its share between bursts and the bang-bang
    # never binds inside the burst — exactly where the victim needs it
    # penalty_s outlasts the enforcement phase: the default 4x-window
    # hold-down (3 s here) expires mid-phase, and the offender's
    # full-rate re-entry gulp lands inside the measured window — a
    # production hold-down is tens of seconds for the same reason
    # (release-on-backoff makes a long penalty cheap to hold)
    adm = SloAdmissionController(
        1e4, window_s=0.75, min_samples=30, refresh_s=0.02,
        tenants={"gold": {"share": 2.0}, "free": {"share": 1.0},
                 "public": {"share": 1.0}},
        fair=True, enforce=False, penalty_s=15.0)

    reg = ModelRegistry()
    engines = {}
    # queue_capacity = HALF a batch: the victim's admitted p99 under a
    # burst is bounded by queue drain time, so a sub-batch queue caps
    # it near one service time no matter what phase the admission
    # controller's window is in — offender gulps turn into fast 429s
    # instead of queue latency charged to whoever is admitted next
    qcap = max(2, max_batch // 2)
    for name, model in (("dense-a", dense(31)), ("dense-b", dense(32))):
        engines[name] = InferenceEngine(
            model, max_batch_size=max_batch,
            max_latency_ms=max_latency_ms,
            queue_capacity=qcap, name=name, admission=adm)
    engines["rnn"] = InferenceEngine(
        rnn(33), max_batch_size=max_batch, timestep_buckets=ts_buckets,
        max_latency_ms=max_latency_ms, queue_capacity=qcap,
        name="rnn", admission=adm, session_ttl_s=2.0)
    for name, eng in engines.items():
        reg.register(name, eng)

    rng = np.random.RandomState(7)
    x_dense = rng.randn(1, n_in).astype(np.float32)
    x_step = rng.randn(1, n_in).astype(np.float32)
    engines["dense-a"].warmup((n_in,))
    engines["dense-b"].warmup((n_in,))
    engines["rnn"].warmup((max(ts_buckets), n_in))
    engines["rnn"].predict_session("_warm", x_step)

    ui = UIServer(port=0)
    ui.attach_registry(reg)
    ui.start()
    base_url = f"http://127.0.0.1:{ui.port}"

    models = ["dense-a", "dense-b", "rnn"]
    zipf_w = np.array([1.0 / (k + 1) ** 1.2
                       for k in range(len(models))])
    zipf_w = zipf_w / zipf_w.sum()

    # per-tenant workload pools: the victim is a latency-sensitive
    # dense-only API tenant (its p99 gate must measure the batched
    # path, not RNN session-creation cost); the offender and the
    # background tenant also churn sessions through the RNN cache TTL
    pools = {"gold": models[:2], "free": models, "public": models}
    pool_w = {}
    for tn, ms in pools.items():
        w = np.array([1.0 / (k + 1) ** 1.2 for k in range(len(ms))])
        pool_w[tn] = w / w.sum()

    def tag_for(tenant: str, t: float, r) -> tuple:
        ms = pools.get(tenant, models)
        m = ms[int(r.choice(len(ms), p=pool_w[tenant]))]
        sess = None
        if m == "rnn":
            # session churn: ids rotate every second against a 2 s
            # session TTL, so the device-resident cache continuously
            # expires old carries and admits fresh ones
            sess = f"{tenant}-{int(t)}-{int(r.randint(4))}"
        return (tenant, m, sess, t)

    # service time of admitted victim requests, measured inside the
    # call — the spread between this and the open-loop (charged from
    # scheduled arrival) p99 is generator lag, not service latency
    victim_svc: list = []
    svc_lock = threading.Lock()

    def fire(tag) -> int:
        tenant, model, sess = tag[:3]
        t0 = time.perf_counter()
        try:
            # block=False is the wire contract (UIServer answers 429 +
            # Retry-After on a full queue): a victim arriving during an
            # offender burst fast-fails instead of absorbing the
            # offender's queue wait, and admitted p99 measures what the
            # service actually served
            reg.predict(model, x_step if model == "rnn" else x_dense,
                        session=sess, timeout=20.0, block=False,
                        tenant=tenant)
            if tenant == "gold":
                with svc_lock:
                    victim_svc.append(time.perf_counter() - t0)
            return 200
        except SloShed:
            return 503
        except QueueFull:
            return 429

    def pct_ms(lats, p):
        return (round(lats[min(len(lats) - 1,
                               int(p * len(lats)))] * 1e3, 2)
                if lats else None)

    def schedule(specs, seed: int) -> list:
        merged = []
        for tenant, kind, rate, dur in specs:
            # stable per-tenant stream (str hash is salted per process)
            r = np.random.RandomState(
                seed + sum(ord(ch) for ch in tenant))
            for t in _arrival_times(kind, rate, dur, r):
                merged.append((t, tag_for(tenant, t, r)))
        merged.sort(key=lambda p: p[0])
        return merged

    # ---- capacity probe: closed-loop batched throughput ---------------
    # enough closed-loop clients to saturate the batcher (3 full
    # batches in flight): an under-estimated capacity makes the
    # "overload" phases fit inside the real capacity and the whole
    # fairness scenario degenerates to mild contention
    probe_stop = time.perf_counter() + 0.8
    counts = [0] * (3 * max_batch)

    def prober(i):
        while time.perf_counter() < probe_stop:
            engines["dense-a"].predict(x_dense, timeout=5.0)
            counts[i] += 1

    pthreads = [threading.Thread(target=prober, args=(i,))
                for i in range(len(counts))]
    for t in pthreads:
        t.start()
    for t in pthreads:
        t.join()
    # the ceiling keeps absolute rates inside what the generator's
    # thread pool can schedule without charging its own lag to victims
    probed_rps = min(500.0, max(50.0, sum(counts) / 0.8))

    mix = {"gold": ("poisson", 0.25 * probed_rps),
           "free": ("bursty", 1.8 * probed_rps),
           "public": ("diurnal", 0.10 * probed_rps)}

    # ---- phase 1: unloaded victim calibration -------------------------
    # the first ~0.3 s still pays one-off costs (thread-pool spin-up,
    # first session-state allocations) that would land exactly on the
    # p99 of a small sample — exclude the warm-in so the baseline is
    # the steady unloaded tail, which is what "inflation" is against
    res1 = _open_loop_tagged(
        fire, schedule([("gold",) + mix["gold"] + (dur1,)], seed=101),
        pool_size=96)
    lat1 = sorted(l for tg, c, l in res1 if c == 200 and tg[3] > 0.3)
    unloaded_p99_ms = pct_ms(lat1, 0.99) or 5.0
    unloaded_ref_ms = max(unloaded_p99_ms, 5.0)
    # SLO well inside the 1.5x victim gate: admitted latency hovers at
    # the SLO under bang-bang shedding, so the gate's headroom has to
    # absorb the controller's reaction lag, not the SLO itself
    slo_p99_ms = max(1.2 * unloaded_ref_ms, unloaded_ref_ms + 1.5)
    adm.slo_p99_ms = slo_p99_ms
    adm.configure_tenant("gold", slo_p99_ms=slo_p99_ms, share=2.0)

    # ---- phase 2: observe-mode overload (unfairness must be SEEN) -----
    # unfairness is a DURING-the-flood fact: by the time the open loop
    # returns, the sliding window holds the drained tail and the
    # evidence is gone.  A watcher thread publishes the tenant gauges,
    # evaluates the alert rules, and keeps the peak unfairness sample
    # while the overload is live; once tenant_unfairness fires it stops
    # evaluating so the FIRING state latches for the /alerts roundtrip.
    monitor.flight_recorder.reset_rate_limit()
    unfair_peak: dict = {"ratio": 0.0}
    firing_seen: set = set()
    stop_watch = threading.Event()

    def watcher():
        while not stop_watch.is_set():
            try:
                publish_tenant_telemetry(adm, "dense-a")
                u = adm.unfairness()
                if u["ratio"] > unfair_peak["ratio"]:
                    unfair_peak.clear()
                    unfair_peak.update(u)
                if "tenant_unfairness" not in firing_seen:
                    for s in alert_eng.evaluate_once():
                        if s["state"] == _alerts.FIRING:
                            firing_seen.add(s["name"])
            except Exception:
                pass
            stop_watch.wait(0.2)

    wt = threading.Thread(target=watcher, daemon=True)
    wt.start()
    specs2 = [(tn,) + mix[tn] + (dur2,) for tn in mix]
    res2 = _open_loop_tagged(fire, schedule(specs2, seed=202),
                             pool_size=96)
    stop_watch.set()
    wt.join(timeout=10)
    unfair = unfair_peak if unfair_peak["ratio"] else adm.unfairness()
    firing = sorted(firing_seen)
    unfairness_alert = "tenant_unfairness" in firing

    try:
        with urllib.request.urlopen(base_url + "/alerts",
                                    timeout=10) as r:
            alerts_doc = json.loads(r.read().decode())
        alerts_endpoint_ok = ("tenant_unfairness"
                              in alerts_doc.get("firing", []))
    except Exception:
        alerts_endpoint_ok = False

    bundles = sorted(os.path.basename(p) for p in
                     _glob.glob(os.path.join(flight_dir, "*")))
    unfairness_bundle = any("alert_tenant_unfairness" in b
                            for b in bundles)
    violation_bundle = any("tenant_slo_violation" in b for b in bundles)

    gold2 = sorted(l for tg, c, l in res2
                   if tg[0] == "gold" and c == 200)
    observe_victim_p99 = pct_ms(gold2, 0.99)

    # ---- phase 2.5: re-baseline next to the enforcement phase ---------
    # the victim gate divides phase 3's p99 by the unloaded p99; on a
    # shared box those must sample the same machine weather, so the
    # reference is re-measured seconds before enforcement (the process-
    # start measurement can be minutes of CPU drift away by now)
    time.sleep(adm.window_s + 0.3)
    res2b = _open_loop_tagged(
        fire, schedule([("gold",) + mix["gold"] + (dur1 / 2,)],
                       seed=404), pool_size=96)
    lat2b = sorted(l for tg, c, l in res2b if c == 200 and tg[3] > 0.3)
    rebase_p99_ms = pct_ms(lat2b, 0.99)
    if rebase_p99_ms:
        unloaded_ref_ms = max(rebase_p99_ms, 5.0)
        slo_p99_ms = max(1.2 * unloaded_ref_ms, unloaded_ref_ms + 1.5)
        adm.slo_p99_ms = slo_p99_ms
        adm.configure_tenant("gold", slo_p99_ms=slo_p99_ms, share=2.0)

    # ---- phase 3: fair enforcement (isolation must HOLD) --------------
    adm.enforce = True
    victim_svc.clear()
    specs3 = [(tn,) + mix[tn] + (dur3,) for tn in mix]
    res3 = _open_loop_tagged(fire, schedule(specs3, seed=303),
                             pool_size=96)
    ramp_s = dur3 / 3.0      # discard the onset transient before the
    #                          controller's window has breach evidence
    gold3 = sorted(l for tg, c, l in res3
                   if tg[0] == "gold" and c == 200 and tg[3] > ramp_s)
    victim_p99_fair = pct_ms(gold3, 0.99)
    victim_held = (victim_p99_fair is not None
                   and victim_p99_fair <= 1.5 * unloaded_ref_ms)
    free3 = [(c, l) for tg, c, l in res3 if tg[0] == "free"]
    free_shed = sum(1 for c, _ in free3 if c in (429, 503))
    offender_shed_rate = (round(free_shed / len(free3), 4)
                          if free3 else 0.0)
    admitted3 = sum(1 for _, c, _ in res3 if c == 200)
    admitted_rps = round(admitted3 / dur3, 1)

    # ---- wire + scoreboard roundtrips ---------------------------------
    # let the admission window drain past the overload so the wire
    # probes below see a quiet service, not phase 3's tail
    time.sleep(adm.window_s + 0.2)

    def post(payload: dict):
        req = urllib.request.Request(
            base_url + "/predict", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.getcode(), json.loads(r.read().decode())
        except urllib.error.HTTPError as e:
            try:
                return e.code, json.loads(e.read().decode())
            except Exception:
                return e.code, {}
        except Exception:
            return -1, {}

    wire_code, _ = post({"model": "dense-a",
                         "features": x_dense.tolist(),
                         "tenant": "gold"})
    wire_default_code, _ = post({"model": "dense-a",
                                 "features": x_dense.tolist()})
    try:
        with urllib.request.urlopen(base_url + "/tenants",
                                    timeout=10) as r:
            tenants_doc = json.loads(r.read().decode())
        rows = tenants_doc.get("tenants", {})
        tenants_endpoint_ok = ("gold" in rows and "free" in rows
                               and "public" in rows)
    except Exception:
        rows, tenants_endpoint_ok = {}, False

    scoreboard = adm.tenant_snapshot()
    sessions = engines["rnn"].stats().get("sessions")
    ui.stop()
    reg.stop_all()
    _alerts.reset()
    sys.setswitchinterval(switch0)

    return {
        "metric": "traffic_admitted_rps", "value": admitted_rps,
        "unit": "requests/sec", "open_loop": True, "smoke": smoke,
        "probed_rps": round(probed_rps, 1),
        "unloaded_p99_ms": unloaded_p99_ms,
        "rebaseline_p99_ms": rebase_p99_ms,
        "unloaded_ref_ms": round(unloaded_ref_ms, 2),
        "slo_p99_ms": round(slo_p99_ms, 2),
        "tenant_mix": {tn: {"arrivals": mix[tn][0],
                            "offered_qps": round(mix[tn][1], 1),
                            "share": (scoreboard[tn]["share"]
                                      if tn in scoreboard else None)}
                       for tn in mix},
        "zipf_popularity": {m: round(float(w), 3)
                            for m, w in zip(models, zipf_w)},
        "observe": {"offered": len(res2),
                    "victim_p99_ms": observe_victim_p99,
                    "unfairness": unfair,
                    "alerts_firing": firing},
        "fair": {"offered": len(res3), "admitted": admitted3,
                 "victim_service_p99_ms": pct_ms(sorted(victim_svc),
                                                 0.99),
                 "victim_p99_ms": victim_p99_fair,
                 "victim_inflation_x": (
                     round(victim_p99_fair / unloaded_ref_ms, 3)
                     if victim_p99_fair else None),
                 "offender_shed": free_shed,
                 "scoreboard": {tn: {k: scoreboard[tn][k] for k in
                                     ("window_p99_ms", "shed_rate",
                                      "over_share", "slo_ok")}
                                for tn in scoreboard}},
        "victim_held": bool(victim_held),
        # enforcement's own contribution: unprotected (observe-mode)
        # victim p99 over the enforced one
        "isolation_gain_x": (
            round(observe_victim_p99 / victim_p99_fair, 1)
            if observe_victim_p99 and victim_p99_fair else None),
        "offender_shed_rate": offender_shed_rate,
        "unfairness_alert": bool(unfairness_alert),
        "unfairness_bundle": bool(unfairness_bundle),
        "violation_bundle": bool(violation_bundle),
        "alerts_endpoint_ok": bool(alerts_endpoint_ok),
        "tenants_endpoint_ok": bool(tenants_endpoint_ok),
        "wire_tenant_ok": wire_code == 200,
        "wire_default_ok": wire_default_code == 200,
        "sessions": sessions,
    }


def _fleet_post(url: str, payload: dict, timeout: float = 15.0) -> int:
    """POST ``/predict`` and return the HTTP status (-1 on transport
    error) — shed responses (429/503) come back as statuses, not
    exceptions, so the open-loop generator can count them."""
    import urllib.error
    import urllib.request
    req = urllib.request.Request(
        url + "/predict", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            resp.read()
            return resp.getcode()
    except urllib.error.HTTPError as e:
        try:
            e.read()
        except Exception:
            pass
        return e.code
    except Exception:
        return -1


def bench_fleet(smoke: bool = False) -> dict:
    """Horizontal serving-fleet proof (``--fleet``): three phases, one
    stdout JSON line.

    1. **Respawn**: spawn a worker against an empty executable-cache
       namespace (cold compile ladder), kill it, spawn its replacement
       against the now-populated persistent cache.  Both ready-line
       timings print; ``respawn_speedup_x`` is cold/warm
       serve-ready time (the CI fleet job asserts >= 5x).
    2. **Cache-hit serving, sanitizer armed**: the warm worker runs
       with ``DL4J_TPU_SANITIZE=1``; session steps + both stateless
       timestep buckets after its ``sanitize_end_warmup`` must compile
       NOTHING (``sanitizer_violations`` scraped from its /metrics).
    3. **Scale-out**: K=1 vs K=3 fleets behind the consistent-hash
       front door, serving the same open-loop Poisson session load at
       an offered rate fixed at ~2.5x the measured K=1 closed-loop
       capacity.  Admitted (2xx) throughput while SLO admission holds
       p99 is the headline ``fleet_requests_per_sec``; the CI job
       asserts ``speedup_x >= 2`` on its multi-core runners (a
       single-core box prints honest numbers — ``cores`` is in the
       line so gates can tell the difference)."""
    import itertools
    import shutil
    import tempfile
    import threading
    import urllib.request

    from deeplearning4j_tpu.deploy.store import VersionedWeightStore
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving.fleet import (
        FLEET_SPECS, FleetRouter, WorkerHandle, build_fleet_conf,
        spawn_worker, wait_ready)

    model_name = "lstm"
    spec = FLEET_SPECS[model_name]
    n_in = spec["n_in"]
    work = tempfile.mkdtemp(prefix="dl4j-fleet-bench-")
    # a cache placed from outside is used as given (and may already be
    # warm, which makes the "cold" spawn below a warm one); only an
    # unplaced run gets a fresh directory for the cold/warm contrast
    cache_root = (os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
                  or os.path.join(work, "cache"))
    store_dir = os.path.join(work, "store")

    def sub(tag, rec):
        _emit({"metric": f"fleet_{tag}", **rec}, file=sys.stderr,
              device=_CPU_CHILDREN)

    # the versioned store is the single source of truth every worker
    # (and every respawn) warms from
    conf, _, _ = build_fleet_conf(model_name)
    ref = MultiLayerNetwork(conf).init()
    store_version = VersionedWeightStore(store_dir).publish_model(
        ref, source="bench")
    del ref

    rng = np.random.RandomState(0)
    step_row = [np.round(rng.randn(n_in), 4).tolist()]      # (1, n_in)
    seqs = [np.zeros((1, tb, n_in), np.float32).tolist()
            for tb in spec["timestep_buckets"][:2]]

    common = dict(model=model_name, store_dir=store_dir,
                  cache_root=cache_root, slo_p99_ms=None, seed=11)

    # ---- phase 1: cold spawn against an empty cache namespace ---------
    proc = spawn_worker(0, sanitize=False, **common)
    cold = WorkerHandle(0, proc, wait_ready(proc))
    cold.start_drains()
    cold.terminate()
    sub("respawn_cold", cold.ready)

    # ---- phase 2: warm respawn, sanitizer armed -----------------------
    proc = spawn_worker(0, sanitize=True, **common)
    warm = WorkerHandle(0, proc, wait_ready(proc))
    warm.start_drains()
    sub("respawn_warm", warm.ready)

    cal_lat: list = []
    try:
        # post-warmup traffic: with the executable cache hit, not one
        # of these requests may compile — the armed sanitizer in the
        # worker records any that do
        codes = []
        for i in range(20):
            t0 = time.perf_counter()
            codes.append(_fleet_post(warm.url, {
                "model": "fleet", "session": f"cal-{i % 4}",
                "features": step_row}))
            cal_lat.append(time.perf_counter() - t0)
        for seq in seqs:
            codes.append(_fleet_post(warm.url, {"model": "fleet",
                                                "features": seq}))
        serving_ok = all(c == 200 for c in codes)
        with urllib.request.urlopen(warm.url + "/metrics",
                                    timeout=10.0) as resp:
            exposition = resp.read().decode()
        violations = int(sum(
            float(ln.rsplit(" ", 1)[-1])
            for ln in exposition.splitlines()
            if ln.startswith("sanitizer_violations_total")))
    finally:
        warm.terminate()

    cal_lat.sort()
    unloaded_p50_ms = cal_lat[len(cal_lat) // 2] * 1e3
    slo_p99_ms = max(50.0, 10.0 * unloaded_p50_ms)

    # ---- phase 3: K=1 vs K=3 under the same open-loop session load ----
    duration_s = 5.0 if smoke else 10.0
    n_sessions = 32
    offered_qps = None
    results = {}
    for k in (1, 3):
        router = FleetRouter(k, model=model_name, store_dir=store_dir,
                             cache_root=cache_root,
                             slo_p99_ms=slo_p99_ms,
                             health_interval_s=1.0)
        router.start()
        ui = router.serve()
        url = f"http://127.0.0.1:{ui.port}"
        try:
            if offered_qps is None:
                # closed-loop capacity probe on K=1 fixes the offered
                # rate BOTH fleet sizes then face
                burst_s = 1.5 if smoke else 2.5
                counts = [0] * 4
                stop_at = time.perf_counter() + burst_s

                def probe(i):
                    j = i
                    while time.perf_counter() < stop_at:
                        if _fleet_post(url, {
                                "model": "fleet",
                                "session": f"conv-{j % n_sessions}",
                                "features": step_row}) == 200:
                            counts[i] += 1
                        j += 4

                ths = [threading.Thread(target=probe, args=(i,))
                       for i in range(4)]
                for th in ths:
                    th.start()
                for th in ths:
                    th.join()
                cap_rps = sum(counts) / burst_s
                offered_qps = max(20.0, round(2.5 * cap_rps, 1))
                sub("capacity_probe",
                    {"closed_loop_rps": round(cap_rps, 1),
                     "offered_qps": offered_qps})

            counter = itertools.count()

            def fire():
                j = next(counter) % n_sessions
                return _fleet_post(url, {
                    "model": "fleet", "session": f"conv-{j}",
                    "features": step_row})

            res = _open_loop(fire, offered_qps, duration_s, seed=k)
            res["k"] = k
            res["workers_healthy"] = router.status()["healthy"]
            sub(f"open_loop_k{k}", res)
            results[k] = res
        finally:
            try:
                ui.stop()
            except Exception:
                pass
            router.stop()

    shutil.rmtree(work, ignore_errors=True)
    speedup = (results[3]["admitted_rps"]
               / max(results[1]["admitted_rps"], 1e-9))
    # respawn-to-first-reply = executable-ladder rebuild + first served
    # request: the component the persistent cache addresses.  The full
    # boot-to-serving walls (interpreter + imports + model init, which
    # no executable cache can touch) print alongside.
    respawn_cold_s = round(cold.ready["warmup_s"]
                           + cold.ready["first_reply_s"], 3)
    respawn_warm_s = round(warm.ready["warmup_s"]
                           + warm.ready["first_reply_s"], 3)
    return {
        "metric": "fleet_requests_per_sec",
        "value": results[3]["admitted_rps"], "unit": "requests/sec",
        "k": 3, "open_loop": True, "offered_qps": offered_qps,
        "baseline_k1_rps": results[1]["admitted_rps"],
        "speedup_x": round(speedup, 2),
        "p99_ms_k1": results[1]["p99_ms"],
        "p99_ms_k3": results[3]["p99_ms"],
        "shed_k1": results[1]["shed"], "shed_k3": results[3]["shed"],
        "errors_k1": results[1]["errors"],
        "errors_k3": results[3]["errors"],
        "slo_p99_ms": round(slo_p99_ms, 1),
        "respawn_cold_s": respawn_cold_s,
        "respawn_warm_s": respawn_warm_s,
        "respawn_speedup_x": round(
            respawn_cold_s / max(respawn_warm_s, 1e-9), 2),
        "cold_warmup_s": cold.ready["warmup_s"],
        "warm_warmup_s": warm.ready["warmup_s"],
        "cold_serve_ready_s": cold.ready["serve_ready_s"],
        "warm_serve_ready_s": warm.ready["serve_ready_s"],
        "cache_entries": warm.ready["cache_entries_before"],
        "cache_hit": warm.ready["cache_entries_before"] > 0,
        "store_version": store_version,
        "sanitizer_violations": violations,
        "serving_ok": serving_ok,
        "cores": os.cpu_count(), "model": model_name, "smoke": smoke,
    }


def main() -> None:
    # first, before anything compiles: every mode shares one persistent
    # compile cache (JAX_COMPILATION_CACHE_DIR, else a fixed path in the
    # checkout), so a second call does not recompile ResNet-50 from cold
    from deeplearning4j_tpu.serving import compile_cache
    compile_cache.enable()
    run_all = "--all" in sys.argv
    if "--chaos" in sys.argv:
        # Resilience proof: train a child process, SIGKILL it mid-epoch
        # via the fault layer, resume from its last checkpoint, and
        # assert the loss curve + final params match an uninterrupted
        # run bit-for-bit.  One stdout JSON line; --smoke is accepted
        # (the workload is already CI-sized).  The CI resilience job
        # asserts value == 1.
        from deeplearning4j_tpu.resilience.chaos import run_chaos
        _emit(run_chaos(smoke="--smoke" in sys.argv),
              device=_CPU_CHILDREN)
        return
    if "--mesh" in sys.argv:
        # Pod-runtime proof: K=2 real-process pods (DP and DP x ZeRO)
        # must be bit-identical to their 1-process runs, the ZeRO pod's
        # per-process updater bytes must drop <= 0.6x vs unsharded, and
        # (non-smoke) kill one process + relaunch --resume auto must
        # match the uninterrupted curve.  One stdout JSON line; the CI
        # mesh job asserts value == 1.
        _emit(bench_mesh(smoke="--smoke" in sys.argv),
              device=_CPU_CHILDREN)
        return
    if "--scaleout" in sys.argv:
        # Scaleout proof: K=3 subprocess Hogwild workers on the
        # compressed wire vs synchronous DP, one stdout JSON line.  The
        # CI scaleout-async job asserts parity_ok, wire_ok (>=3x), and
        # staleness_gauge_on_metrics.
        _emit(bench_scaleout(smoke="--smoke" in sys.argv),
              device=_CPU_CHILDREN)
        return
    if "--deploy" in sys.argv:
        # Deployment proof: a live fit() publishes versions while the
        # model serves HTTP traffic; the rollout sidecar canaries and
        # promotes them (>= 2 promotions, accuracy improves, zero 5xx,
        # zero recompiles), a seeded bad update auto-rolls-back with a
        # flight bundle, and a corrupted snapshot answers 4xx with no
        # swap.  One stdout JSON line; the CI deploy-smoke job asserts
        # value == 1.
        _emit(bench_deploy(smoke="--smoke" in sys.argv))
        return
    if "--fleet" in sys.argv:
        # Fleet proof: cold vs cache-warm worker respawn (>= 5x),
        # sanitizer-armed cache-hit serving (zero violations), and
        # K=3 vs K=1 open-loop admitted throughput through the
        # consistent-hash front door.  One stdout JSON line; the CI
        # fleet-smoke job asserts respawn_speedup_x >= 5,
        # sanitizer_violations == 0, and speedup_x >= 2 on its
        # multi-core runners.
        _emit(bench_fleet(smoke="--smoke" in sys.argv),
              device=_CPU_CHILDREN)
        return
    if "--decode" in sys.argv:
        # Decode proof: KV-ring one-dispatch-per-token decode vs the
        # O(T^2) full-prefix recompute baseline at T=128, one stdout
        # JSON line with the hand bytes model.  The acceptance gate is
        # vs_baseline >= 5 on CPU (BASELINE.md row); ``--smoke``
        # shrinks to T=32 for the CI decode-smoke job.
        _emit(bench_decode(smoke="--smoke" in sys.argv))
        return
    if "--traffic" in sys.argv:
        # Multi-tenant SLO isolation proof: open-loop tenant mix
        # (Poisson victim / bursty offender / diurnal background, Zipf
        # model popularity, session churn) through fair per-tenant
        # admission.  One stdout JSON line; the CI traffic-smoke job
        # asserts victim_held, offender_shed_rate > 0,
        # tenants_endpoint_ok, and unfairness_alert + bundle.
        _emit(bench_traffic(smoke="--smoke" in sys.argv))
        return
    if "--smoke" in sys.argv:
        # CI smoke: tiny LeNet config, one stdout JSON line — the CI
        # ingest job asserts the step_device_ms field parses; the CI
        # perf-smoke job additionally asserts bytes_dropped_vs_baseline
        # (chip-posture estimate vs the committed fp32 baseline in
        # tools/perf_baseline.json) and that the deterministic autotune
        # sub-decision is run-to-run stable.  Rates are meaningless at
        # this size.
        result = bench_lenet(batch=32, steps=8, trials=2, pipeline=1)
        result.update(_smoke_precision_fields(batch=32))
        result.update(_sanitizer_smoke_fields())
        result.update(_alert_smoke_fields())
        _emit(result)
        return
    if "--glove-smoke" in sys.argv:
        # CI embeddings smoke: small fused-vs-naive GloVe run, one stdout
        # JSON line — the CI job asserts the fused rate clears the
        # pre-aggregation plateau and that the in-process naive
        # reference loses (platform-independent assertion).
        _emit(bench_glove(vocab=4000, dim=64, batch=4096,
                          triples=100_000, epochs_per_window=2, trials=2))
        return
    if "--serve" in sys.argv:
        if "--open-loop" in sys.argv:
            # open-loop arrival mode: Poisson at a fixed offered QPS
            # (coordinated-omission-free latencies); ONE stdout line
            _emit(bench_serving_open_loop())
            return
        # serving mode (closed-loop, the default): TWO stdout lines —
        # the single-model dynamic batching benchmark, then the v2
        # multi-model/session/SLO sweep (offered-load sweep levels go
        # to stderr)
        _emit(bench_serving())
        _emit(bench_serving_v2())
        return
    _require_tpu("--all" if run_all else "the default run")
    _emit(bench_lenet())
    if not run_all:
        return
    failed = []
    for fn in (bench_resnet50, bench_vgg16, bench_lstm, bench_word2vec,
               bench_word2vec_fit, bench_glove, bench_deepwalk,
               bench_pv_dbow, bench_pv_dm, bench_flash_attention,
               bench_fit_iterator, bench_fit_iterator_resnet,
               bench_native_ingest, bench_scaling):
        device = _CPU_CHILDREN if fn is bench_scaling else None
        try:
            out = fn()
            for line in (out if isinstance(out, list) else [out]):
                _emit(line, file=sys.stderr, device=device)
                if "error" in line:     # a child process failed
                    failed.append(fn.__name__)
        except Exception as e:  # finish the sweep, then fail the run
            failed.append(fn.__name__)
            _emit({"metric": fn.__name__, "error": repr(e)},
                  file=sys.stderr, device=device)
    if failed:
        print(f"bench.py --all: {len(failed)} config(s) raised: "
              f"{', '.join(failed)}", file=sys.stderr, flush=True)
        sys.exit(1)


if __name__ == "__main__":
    sys.exit(main())
