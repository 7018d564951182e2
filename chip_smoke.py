"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # on a machine with a TPU

drives the main path once, in ONE process, through the entry points a
user calls — ``fit(iterator)``, ``InferenceEngine.predict`` /
``predict_session``, ``flash_attention`` — at the full width of the
models the repo ships, with random weights and data made from a seed.
Phases, each a function below (``tests/test_chip_smoke.py`` runs the
same functions at toy widths on the CPU mesh):

- ``train``      ResNet-50 ``fit`` through the epoch cache, backend-default
                 precision policy (``mixed_bf16`` on a TPU), health build
- ``serve``      the same net behind ``InferenceEngine``, concurrent clients
- ``decode``     a 2-layer causal-attention decoder: ``fit`` at T=2048
                 (the flash kernel through the layer), then one served
                 session — prefill plus single-token steps
- ``kernels``    ``ops.attention.flash_attention`` forward and gradient,
                 compiled by Mosaic (never the interpreter), against the
                 dense reference; the streamed latent attention against
                 its dense form at the decode cell's shape for one layer;
                 the routed experts' grouped product against their dense
                 form for a prefill chunk's tokens at the cell's widths
                 and for a token step's under a share (12 of 192 held)
- ``four_chips`` ``ParallelWrapper`` and ``ZeroShardedParallelWrapper``
                 over four devices; says so when it finds fewer

A phase that fails raises, and the process exits non-zero.  Without a
TPU the script says which platform it found and exits 1 before any
phase.  The last stdout line of a passing run is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

A chip belongs to one process: nothing here starts a child that needs
one, and ``JAX_PLATFORMS`` is never set.  The compile cache is wherever
``JAX_COMPILATION_CACHE_DIR`` says, else ``.jax_compile_cache/`` here.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.metadata
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.serving import compile_cache

SEED = 20260926

# Parity bounds, relative to the largest |reference| value of the array
# compared (a probability row, a gradient tensor).
#
# Served vs ``net.output`` — the bound ``tests/test_serving.py`` states
# for float32: a padded bucket takes another matmul tiling, which
# reorders float32 accumulations of up to ~4.6k terms (sqrt(K)*eps32
# ~ 8e-6).  Under bf16 compute the same reordering can flip the bf16
# rounding of an activation, one bf16 ulp (2^-8) at that element; four
# ulps leave room for a few flips to line up.
SERVE_BOUND = {"float32": 1e-5, "bfloat16": 4 * 2.0 ** -8}
# Kernel vs dense reference at "highest" precision: the kernel's default
# MXU precision may round p and ds to one bf16 pass (2^-9 per term) and
# a bf16 output rounds once more (2^-8); 2e-2 leaves ~5x.
KERNEL_BOUND = 2e-2


def _resnet50_conf():
    from deeplearning4j_tpu.models.resnet import resnet50
    return resnet50()       # NO compute_dtype: the backend default resolves


def _lenet_conf():
    from deeplearning4j_tpu.models.lenet import lenet
    return lenet()


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every width the phases use.  The defaults are what the chip gets;
    the CPU test passes toy values and ``interpret=True``."""

    # train + serve + four_chips: one image classifier (a graph conf)
    graph_conf: Callable = _resnet50_conf
    image: Tuple[int, ...] = (224, 224, 3)
    classes: int = 1000
    examples: int = 512
    batch: int = 128
    serve_max_batch: int = 32
    serve_clients: int = 8
    # decode: bench_decode's model widened to a real attention shape
    hidden: int = 2048
    heads: int = 16                 # head size 128
    cache_len: int = 2048
    layers: int = 2
    vocab: int = 1024
    train_t: int = 2048
    train_batch: int = 2
    prefill: int = 64
    decode_steps: int = 16
    # kernels: the bench_flash_attention shape, reference at a T the
    # dense path can hold, and short odd lengths (block clamp)
    kernel_bthd: Tuple[int, int, int, int] = (2, 8192, 4, 64)
    kernel_ref_t: int = 1024
    kernel_short_ts: Tuple[int, ...] = (40, 8)
    # the latent attention of one layer of the decode cell: (batch,
    # heads, rank, rotary, ring slots, cursor)
    latent_shape: Tuple[int, ...] = (64, 32, 512, 64, 4096, 4000)
    # the routed experts of one layer of the decode cell under a prefill
    # chunk: (tokens, experts, width, hidden, picks a token)
    experts_shape: Tuple[int, ...] = (2048, 64, 1024, 3584, 4)
    # and of one layer of the share's cell under its token step: (tokens,
    # the router's width, experts held, width, hidden, picks a token)
    experts_share_shape: Tuple[int, ...] = (256, 192, 12, 2048, 7168, 8)
    # the indexed sparse attention of one layer of the sparse cell:
    # (batch, query heads, key/value heads, head size, indexer heads,
    # indexer head size, ring slots, selected rows, a chunk's positions)
    sparse_shape: Tuple[int, ...] = (8, 32, 4, 128, 16, 64, 8192, 2048, 256)
    # the dense grouped-query attention of the window-and-full cell:
    # (batch, query heads, key/value heads, head size, window, a full
    # ring's slots, a chunk's positions)
    gqa_shape: Tuple[int, ...] = (8, 128, 8, 128, 4096, 8192, 256)
    interpret: bool = False         # True only where there is no Mosaic
    # four_chips: ZeRO needs a MultiLayerNetwork
    mln_conf: Callable = _lenet_conf
    mln_features: int = 784
    mln_classes: int = 10


# ------------------------------------------------------------------ helpers
def _compiles() -> float:
    """Every compile the monitor saw: jitted steps and serving buckets."""
    snap = monitor.snapshot()
    return sum(sum(snap.get(name, {}).get("values", {}).values())
               for name in ("jit_compiles_total",
                            "serving_bucket_compiles_total"))


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        raise AssertionError(f"shape {got.shape} != reference {ref.shape}")
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values")
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _check(cond: bool, what: str) -> None:
    # not ``assert``: the checks must survive ``python -O``
    if not cond:
        raise AssertionError(what)


@contextlib.contextmanager
def _armed_sanitizer():
    """Arm ``tools.analyze.sanitizer`` (an environment switch read at each
    dispatch) for one phase, from a clean state."""
    from tools.analyze import sanitizer
    prev = os.environ.get(sanitizer.ENV_FLAG)
    os.environ[sanitizer.ENV_FLAG] = "1"
    sanitizer.reset()
    try:
        yield sanitizer
    finally:
        sanitizer.reset()
        if prev is None:
            del os.environ[sanitizer.ENV_FLAG]
        else:
            os.environ[sanitizer.ENV_FLAG] = prev


def _check_mosaic(lowered_text: str, what: str) -> None:
    """On a TPU the lowered program must hold the Mosaic custom call,
    whatever ``interpret`` flag got there: a kernel that quietly runs
    through the Pallas interpreter must not pass."""
    import jax
    if jax.devices()[0].platform == "tpu":
        _check("tpu_custom_call" in lowered_text,
               f"{what}: no Mosaic custom call in the lowered program — "
               f"the kernel would run interpreted")


def _image_data(sz: Sizes, n: int, seed: int):
    rng = np.random.RandomState(seed)
    f = rng.rand(n, *sz.image).astype(np.float32)
    l = np.eye(sz.classes, dtype=np.float32)[rng.randint(0, sz.classes, n)]
    return f, l


# -------------------------------------------------------------------- train
def phase_train(sz: Sizes, expect_policy: str):
    """``fit(iterator)`` twice through the product's default path."""
    import jax
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn.computation_graph import ComputationGraph

    net = ComputationGraph(sz.graph_conf()).init()
    policy = net._pol()
    _check(policy.name == expect_policy,
           f"resolved precision policy {policy.describe()}, expected "
           f"{expect_policy}")
    platform = jax.devices()[0].platform
    placed = {d.platform for leaf in jax.tree.leaves(net.params)
              for d in leaf.devices()}
    _check(placed == {platform}, f"parameters live on {placed}")

    f, l = _image_data(sz, sz.examples, SEED)
    it = ListDataSetIterator(DataSet(f, l), sz.batch)
    staged = monitor.gauge("ingest_staged_bytes", "")
    t0 = time.perf_counter()
    net.fit(it, epochs=2)
    first = float(net.score())
    t1 = time.perf_counter()
    _check(staged.value(path="cache") == f.nbytes + l.nbytes,
           "the epoch-cache ingest path did not stage the dataset")
    # the epoch cache dispatches the gather step and no other program
    _check(net._gather_train_step.compile_count == 1,
           "the gather step did not dispatch exactly one program")
    _check("_train_step" not in vars(net)
           and "_multi_train_step" not in vars(net),
           "fit over the epoch cache built another step program")
    _check(monitor.health_snapshot()["last_dispatch_timestamp"] is not None,
           "no health vector was recorded")

    before = _compiles()
    net.fit(it, epochs=2)
    second = float(net.score())
    t2 = time.perf_counter()
    _check(_compiles() == before, "the second fit() compiled something")
    _check(np.isfinite([first, second]).all() and first != second,
           f"score not finite or did not move: {first} -> {second}")
    return net, {"policy": policy.describe(), "score": [first, second],
                 "first_fit_s": round(t1 - t0, 1),
                 "second_fit_s": round(t2 - t1, 2),
                 "steps_per_fit": 2 * (sz.examples // sz.batch)}


# -------------------------------------------------------------------- serve
def phase_serve(sz: Sizes, net):
    """The trained net behind ``InferenceEngine``: concurrent one-row
    clients, answers against ``net.output``."""
    from deeplearning4j_tpu.serving import InferenceEngine

    x, _ = _image_data(sz, sz.serve_clients, SEED + 1)
    ref = np.asarray(net.output(x))
    bound = SERVE_BOUND[np.dtype(net._pol().compute_dtype).name]
    with InferenceEngine(net, max_batch_size=sz.serve_max_batch) as eng, \
            ThreadPoolExecutor(max_workers=sz.serve_clients) as clients:
        t0 = time.perf_counter()
        buckets = eng.warmup(sz.image)
        warm_s = time.perf_counter() - t0
        before = _compiles()
        futures = [clients.submit(eng.predict, x[i:i + 1], timeout=120.0)
                   for i in range(sz.serve_clients)]
        # result() re-raises whatever a client hit
        got = [np.asarray(f.result(timeout=180.0)) for f in futures]
        _check(_compiles() == before, "predict() compiled after warm-up")
        backend = eng._backend
    err = max(_rel_err(g, ref[i:i + 1]) for i, g in enumerate(got))
    _check(err <= bound,
           f"served rows differ from net.output by {err:.3g} > {bound:.3g}")
    return {"engine_backend": backend, "warmup_buckets": buckets,
            "warmup_s": round(warm_s, 1), "max_rel_err": err,
            "bound": bound}


# ------------------------------------------------------------------- decode
def phase_decode(sz: Sizes):
    """Train the decoder through ``fit`` (flash kernel through the layer),
    then serve one session: prefill, then one dispatch per token."""
    import jax
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn.conf import inputs
    from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.attention import CausalSelfAttention
    from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.serving import InferenceEngine
    from deeplearning4j_tpu.serving.bucketing import batch_ladder

    b = NeuralNetConfiguration.builder().seed(SEED).list()
    for _ in range(sz.layers):
        b = b.layer(CausalSelfAttention(n_out=sz.hidden, n_heads=sz.heads,
                                        cache_len=sz.cache_len))
    conf = (b.layer(RnnOutputLayer(n_out=sz.vocab, activation="softmax",
                                   loss="mcxent"))
            .set_input_type(inputs.recurrent(sz.hidden, sz.train_t))
            .build())
    net = MultiLayerNetwork(conf).init()

    # the kernel the layer's training tier lowers to on this backend
    attn, params = net.layers[0], net.params[0]
    xs = jax.ShapeDtypeStruct((sz.train_batch, sz.train_t, sz.hidden),
                              net._pol().compute_dtype)
    _check_mosaic(jax.jit(lambda p, x: attn.forward(
        p, None, x, train=True)[0]).lower(params, xs).as_text(),
        "the attention layer's training forward")

    rng = np.random.RandomState(SEED + 2)
    n = 2 * sz.train_batch                       # two steps per epoch
    f = rng.randn(n, sz.train_t, sz.hidden).astype(np.float32)
    l = np.eye(sz.vocab, dtype=np.float32)[
        rng.randint(0, sz.vocab, (n, sz.train_t))]
    t0 = time.perf_counter()
    net.fit(ListDataSetIterator(DataSet(f, l), sz.train_batch), epochs=1)
    score = float(net.score())
    fit_s = time.perf_counter() - t0
    _check(np.isfinite(score), f"decoder training score {score}")
    _check(net.iteration == 2, f"{net.iteration} training steps, wanted 2")

    total = sz.prefill + sz.decode_steps
    tokens = rng.randn(1, total, sz.hidden).astype(np.float32)
    full = np.asarray(net.output(tokens))        # (1, total, vocab)

    def session(eng, sanitizer, sid):
        outs = [np.asarray(eng.predict_session(sid, tokens[:, :sz.prefill]))]
        counts = []
        for t in range(sz.prefill, total):
            with sanitizer.scenario("chip_smoke.token") as scen:
                outs.append(np.asarray(
                    eng.predict_session(sid, tokens[:, t]))[:, None])
            counts.append(scen.dispatches)
        return np.concatenate(outs, axis=1), counts

    with _armed_sanitizer() as sanitizer, InferenceEngine(net) as eng:
        session(eng, sanitizer, "warm")   # compiles prefill, step, hops
        eng.sessions.clear_all()
        sanitizer.end_warmup()
        before = _compiles()
        stepped, counts = session(eng, sanitizer, "s")
        violations = sanitizer.violations()
    _check(_compiles() == before, "the warmed session compiled something")
    # one dispatch per token; each ring bucket the session outgrows on
    # the way (power-of-two ladder) adds one grow dispatch to that step
    hops = sum(1 for cap in batch_ladder(sz.cache_len)
               if sz.prefill <= cap < total)
    _check(all(c in (1, 2) for c in counts)
           and sum(c - 1 for c in counts) == hops,
           f"dispatches per decoded token {counts}, {hops} bucket hop(s)")
    _check(not violations, f"sanitizer violations: {violations}")
    bound = SERVE_BOUND[np.dtype(net._pol().compute_dtype).name]
    err = _rel_err(stepped, full)
    _check(err <= bound, f"prefill+decode differs from output() by "
                         f"{err:.3g} > {bound:.3g}")
    return {"policy": net._pol().describe(), "train_score": score,
            "fit_s": round(fit_s, 1), "dispatches_per_token": counts,
            "max_rel_err": err, "bound": bound}


# ------------------------------------------------------------------ kernels
def phase_kernels(sz: Sizes):
    """``flash_attention`` forward + gradient with ``interpret`` passed
    explicitly, so a quiet interpreter run cannot pass on the chip."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.attention import flash_attention
    from deeplearning4j_tpu.parallel.sequence import _full_attention

    def loss_of(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) ** 2)

    flash = jax.jit(jax.value_and_grad(loss_of(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=sz.interpret)),
        argnums=(0, 1, 2)))
    dense = jax.jit(jax.value_and_grad(loss_of(
        lambda q, k, v: _full_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2)))

    def qkv(t, dtype, seed):
        bsz, _, h, d = sz.kernel_bthd
        rng = np.random.RandomState(seed)
        return tuple(jnp.asarray(rng.randn(bsz, t, h, d).astype(np.float32))
                     .astype(dtype) for _ in range(3))

    report = {}
    for dtype in (jnp.bfloat16, jnp.float32):
        name = jnp.dtype(dtype).name
        # the big shape: compiles (Mosaic, not the interpreter) and runs
        q, k, v = qkv(sz.kernel_bthd[1], dtype, SEED + 3)
        _check_mosaic(flash.lower(q, k, v).as_text(),
                      f"flash_attention {name}")
        t0 = time.perf_counter()
        loss, grads = jax.block_until_ready(flash(q, k, v))
        report[f"{name}_T{sz.kernel_bthd[1]}_first_call_s"] = round(
            time.perf_counter() - t0, 2)
        _check(bool(jnp.isfinite(loss))
               and all(g.shape == q.shape and g.dtype == q.dtype
                       and bool(jnp.isfinite(g.astype(jnp.float32)).all())
                       for g in grads),
               f"{name} T={sz.kernel_bthd[1]}: non-finite or misshapen")
        # against the dense reference where it fits; the short odd
        # lengths exercise the block clamp (bf16 is the one at risk)
        ts = (sz.kernel_ref_t,) + (sz.kernel_short_ts
                                   if dtype == jnp.bfloat16 else ())
        for t in ts:
            q, k, v = qkv(t, dtype, SEED + 4 + t)
            loss, grads = flash(q, k, v)
            with jax.default_matmul_precision("highest"):
                rloss, rgrads = dense(*(x.astype(jnp.float32)
                                        for x in (q, k, v)))
            err = max([abs(float(loss) - float(rloss)) / abs(float(rloss))]
                      + [_rel_err(g, r) for g, r in zip(grads, rgrads)])
            _check(err <= KERNEL_BOUND,
                   f"{name} T={t}: flash differs from the dense reference "
                   f"by {err:.3g} > {KERNEL_BOUND}")
            report[f"{name}_T{t}_max_rel_err"] = err
    report["latent_streamed_max_rel_err"] = _latent_kernel(sz)
    tokens, n_experts, *widths = sz.experts_shape
    report["experts_grouped_max_rel_err"] = _experts_kernel(
        sz, "grouped", tokens, n_experts, n_experts, *widths)
    report["experts_share_grouped_max_rel_err"] = _experts_kernel(
        sz, "held_rows", *sz.experts_share_shape)
    report["sparse_streamed_max_rel_err"] = _sparse_kernels(sz)
    report["gqa_ring_streamed_max_rel_err"] = _gqa_ring_kernels(sz)
    return report


def _latent_kernel(sz: Sizes) -> float:
    """The streamed latent attention (one token a row, bf16 rings)
    against the dense form of the same arguments.  Both round ``p`` to
    bf16 for the context product, the dense form after the division and
    the streamed one before it: ``KERNEL_BOUND`` holds both."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import attention

    batch, heads, rank, rope, slots, cursor = sz.latent_shape
    rng = np.random.RandomState(SEED + 5)
    q_lat, q_rope, c_ring, r_ring = (
        jnp.asarray(scale * rng.randn(*shape).astype(np.float32),
                    jnp.bfloat16)
        for scale, shape in ((0.3, (batch, 1, heads, rank)),
                             (1.0, (batch, 1, heads, rope)),
                             (1.0, (batch, slots, rank)),
                             (1.0, (batch, slots, rope))))
    scale = (3 * rope) ** -0.5          # (d_nope + d_rope)^-1/2 at 128 + 64
    streamed = jax.jit(lambda *a: attention.latent_ring_attention_streamed(
        *a, sm_scale=scale, interpret=sz.interpret))
    dense = jax.jit(lambda *a: attention.latent_ring_attention_dense(
        *a, sm_scale=scale))
    args = (q_lat, q_rope, c_ring, r_ring, jnp.asarray(cursor, jnp.int32))
    _check_mosaic(streamed.lower(*args).as_text(),
                  "latent_ring_attention_streamed")
    err = _rel_err(streamed(*args), dense(*args))
    _check(err <= KERNEL_BOUND,
           f"the streamed latent attention differs from its dense form by "
           f"{err:.3g} > {KERNEL_BOUND}")
    return err


def _sparse_kernels(sz: Sizes) -> float:
    """The indexed sparse attention's kernels (bf16 rings) against the
    plain forms of the same arguments, for a token step's one position
    and a chunk's: the indexer's scores (float32 both ways), the
    selection (the same rows), then the attention over them, streamed
    through the mask and, for the token step, with the selected slots
    fetched by the kernel's own descriptors.  All attentions round ``p``
    to bf16 for the context product, the plain form after the division
    and the kernels before it."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import attention

    batch, heads, kv_heads, d, j, di, slots, topk, chunk = sz.sparse_shape
    rng = np.random.RandomState(SEED + 8)
    draw = lambda *shape: jnp.asarray(
        rng.randn(*shape).astype(np.float32), jnp.bfloat16)
    kv_ring, i_ring = draw(batch, slots, 2 * kv_heads, d), \
        draw(batch, slots, di)
    worst = 0.0
    for t in (1, chunk):
        cursor = jnp.asarray(slots - t - 3, jnp.int32)
        q, q_idx, w_idx = (draw(batch, t, heads, d), draw(batch, t, j, di),
                           draw(batch, t, j))
        visible = attention.visible_slots(cursor, t, slots)[None]
        scores = jax.jit(attention.indexer_scores)
        plain = scores(q_idx, w_idx, i_ring)
        scored = jax.jit(lambda *a: attention.indexer_scores_streamed(
            *a, cursor, interpret=sz.interpret))
        _check_mosaic(scored.lower(q_idx, w_idx, i_ring).as_text(),
                      "indexer_scores_streamed")
        worst = max(worst, _rel_err(
            jnp.where(visible, scored(q_idx, w_idx, i_ring), 0.0),
            jnp.where(visible, plain, 0.0)))
        select = jax.jit(lambda s: attention.select_mask(s, visible, topk))
        selected = select(plain)
        _check(bool(jnp.all(jnp.sum(selected, axis=-1) == topk)),
               f"the selection of {topk} rows picked another number")
        search = jax.jit(lambda s: attention.select_mask_streamed(
            s, cursor, topk, interpret=sz.interpret))
        _check_mosaic(search.lower(plain).as_text(), "select_mask_streamed")
        _check(bool(jnp.all((search(plain) != 0) == selected)),
               "the streamed selection picked other rows than the plain one")
        streamed = jax.jit(lambda *a: attention.sparse_attention_streamed(
            *a, cursor, sm_scale=d ** -0.5, interpret=sz.interpret))
        _check_mosaic(streamed.lower(q, kv_ring, selected).as_text(),
                      "sparse_attention_streamed")
        masked = jax.jit(lambda *a: attention.sparse_attention_masked(
            *a, sm_scale=d ** -0.5))
        plain = masked(q, kv_ring, selected)
        worst = max(worst, _rel_err(streamed(q, kv_ring, selected), plain))
        if t == 1:
            slots_of, count = jax.jit(lambda m: attention.selected_slots(
                m[:, 0], topk))(selected)
            gathered = jax.jit(
                lambda *a: attention.sparse_attention_gathered(
                    *a, sm_scale=d ** -0.5, interpret=sz.interpret))
            _check_mosaic(
                gathered.lower(q, kv_ring, slots_of, count).as_text(),
                "sparse_attention_gathered")
            worst = max(worst, _rel_err(
                gathered(q, kv_ring, slots_of, count), plain))
    _check(worst <= KERNEL_BOUND,
           f"the sparse attention's kernels differ from the plain form by "
           f"{worst:.3g} > {KERNEL_BOUND}")
    return worst


def _gqa_ring_kernels(sz: Sizes) -> float:
    """The dense grouped-query attention's streamed kernel (bf16 rings)
    against the masked form of the same arguments: a full ring that
    grows, and a window's ring that has wrapped (the cursor several laps
    on, the newest slot inside a block), each for a token step's one
    position and a chunk's."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import attention

    batch, heads, kv_heads, d, window, slots, chunk = sz.gqa_shape
    rng = np.random.RandomState(SEED + 9)
    draw = lambda *shape: jnp.asarray(
        rng.randn(*shape).astype(np.float32), jnp.bfloat16)
    worst = 0.0
    for win, cap in ((None, slots),
                     (window, attention.window_ring_slots(window, chunk))):
        ring = draw(batch, cap, 2 * kv_heads, d)
        for t in (1, chunk):
            # a full ring nearly full; a window's ring three laps on
            cursor = jnp.asarray(
                slots - t - 3 if win is None else 3 * cap + cap // 3,
                jnp.int32)
            q = draw(batch, t, heads, d)
            streamed = jax.jit(
                lambda *a: attention.gqa_ring_attention_streamed(
                    *a, sm_scale=d ** -0.5, window=win,
                    interpret=sz.interpret))
            _check_mosaic(streamed.lower(q, ring, cursor).as_text(),
                          "gqa_ring_attention_streamed")
            masked = jax.jit(lambda *a: attention.gqa_ring_attention_masked(
                *a, sm_scale=d ** -0.5, window=win))
            got = streamed(q, ring, cursor)
            # the masked form's scores are (heads, T, slots) float32 a
            # conversation (1 GB for a chunk against 8,192 slots): the
            # first and the last conversation, one at a time
            for row in (slice(0, 1), slice(batch - 1, batch)):
                worst = max(worst, _rel_err(
                    got[row], masked(q[row], ring[row], cursor)))
    _check(worst <= KERNEL_BOUND,
           f"the streamed grouped-query attention differs from the masked "
           f"form by {worst:.3g} > {KERNEL_BOUND}")
    return worst


def _experts_kernel(sz: Sizes, path: str, tokens: int, n_experts: int,
                    held: int, width: int, hidden: int, picks: int) -> float:
    """The routed experts' grouped form (each pair through the expert it
    chose; bf16; the first ``held`` of ``n_experts`` held, and under a
    share the held pairs alone laid in rows) against the dense form of
    the same layer and routing.  The grouped form keeps the two products
    in float32 up to the one rounding before the last product, the dense
    form rounds them first: ``KERNEL_BOUND`` holds both.  On a TPU the
    layer's own predicate has to answer ``path`` at this shape, and
    what the layer then runs has to agree with the dense form too."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.layers import decoder
    from deeplearning4j_tpu.ops import experts

    shape = (tokens, n_experts, held, width, hidden, picks)
    layer = decoder.MixtureOfExperts(
        n_in=hidden, n_out=hidden, n_experts=n_experts, top_k=picks,
        width=width, n_shared=0, routed_scaling=2.0,
        experts_held=list(range(held)), weight_init="distribution",
        dist=decoder.Distribution(kind="normal", std=hidden ** -0.5))
    params = layer.init_params(jax.random.PRNGKey(SEED + 6), jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(SEED + 7), (tokens, hidden),
                          jnp.bfloat16)
    # a function a form: jit keeps what it traced for a function it knows
    picked = jax.jit(lambda p, x: layer.forward(
        p, layer.init_state(), x, train=False)[0])(params, x)
    if jax.devices()[0].platform == "tpu":
        _check(layer.experts_path(tokens, x.dtype) == path,
               f"the experts' predicate answers "
               f"{layer.experts_path(tokens, x.dtype)!r}, not {path!r}, for "
               f"{tokens} tokens at {shape}")
    grouped = jax.jit(lambda p, x: experts.grouped_experts(
        x, *layer.route(p, x), p["Wg"], p["Wu"], p["Wd"], held=layer.held(),
        n_experts=n_experts, interpret=sz.interpret)[0])
    _check_mosaic(grouped.lower(params, x).as_text(), "grouped_experts")
    with mock.patch.object(decoder, "moe_experts_path",
                           lambda *a, **k: "dense"):
        dense = jax.jit(lambda p, x: layer.forward(
            p, layer.init_state(), x, train=False)[0])(params, x)
    err = max(_rel_err(grouped(params, x), dense), _rel_err(picked, dense))
    _check(err <= KERNEL_BOUND,
           f"the grouped experts at {shape} differ from their dense form "
           f"by {err:.3g} > {KERNEL_BOUND}")
    return err


# --------------------------------------------------------------- four chips
def phase_four_chips(sz: Sizes, devices: Sequence):
    """Data parallelism on four devices: ``ParallelWrapper`` (local steps,
    then parameter averaging) and one ``ZeroShardedParallelWrapper``
    round."""
    import jax
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.parallel_wrapper import ParallelWrapper
    from deeplearning4j_tpu.parallel.zero import ZeroShardedParallelWrapper

    devices = list(devices)[:4]
    _check(len(set(devices)) == 4, f"need four devices, got {devices}")
    w, k, per = 4, 2, sz.batch

    net = ComputationGraph(sz.graph_conf()).init()
    f, l = _image_data(sz, w * per, SEED + 5)
    distinct = [DataSet(f[i * per:(i + 1) * per], l[i * per:(i + 1) * per])
                for i in range(w)]
    batches = distinct * (2 * k)            # two rounds of k steps x w
    pw = ParallelWrapper(net, workers=w, averaging_frequency=k,
                         devices=devices)
    t0 = time.perf_counter()
    pw.fit(batches)
    score = float(net.score())
    pw_s = time.perf_counter() - t0
    _check(net.iteration == 2 * k and np.isfinite(score),
           f"ParallelWrapper: iteration {net.iteration}, score {score}")
    holders = {d for leaf in jax.tree.leaves(net.params)
               for d in leaf.devices()}
    _check(holders == set(devices),
           f"replicas live on {len(holders)} devices: {holders}")
    shards = {s.device for leaf in jax.tree.leaves(pw._worker_ustate)
              for s in leaf.addressable_shards}
    _check(shards == set(devices), "per-worker updater state is not "
                                   "spread over the four devices")
    # the text of the program fit dispatched (lowering it again is served
    # from JAX's in-memory executable cache, not compiled twice)
    staged = pw._stage_round(batches[:k * w])
    hlo = pw._parallel_step.lower(
        net.params, pw._worker_ustate, net.net_state, net.iteration,
        *staged[:4], net._rng_key, staged[4]).compile().as_text()
    _check("all-reduce" in hlo, "no all-reduce in the compiled round")

    mln = MultiLayerNetwork(sz.mln_conf()).init()
    rng = np.random.RandomState(SEED + 6)
    zbatches = [DataSet(
        rng.rand(per, sz.mln_features).astype(np.float32),
        np.eye(sz.mln_classes, dtype=np.float32)[
            rng.randint(0, sz.mln_classes, per)]) for _ in range(w)]
    zw = ZeroShardedParallelWrapper(mln, workers=w, devices=devices)
    zw.fit(zbatches)
    zscore = float(mln.score())
    _check(mln.iteration == 1 and np.isfinite(zscore),
           f"ZeRO: iteration {mln.iteration}, score {zscore}")
    zshards = {s.device for leaf in jax.tree.leaves(zw._state)
               for s in leaf.addressable_shards}
    _check(zshards == set(devices) or not zw._state,
           "ZeRO updater-state shards are not spread over the devices")
    return {"parallel_wrapper_score": score,
            "parallel_wrapper_s": round(pw_s, 1), "zero_score": zscore,
            "devices": [str(d) for d in devices]}


# --------------------------------------------------------------------- main
PHASES = ("train", "serve", "decode", "kernels", "four_chips")


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset, for debugging one phase "
                         "(serve needs train); default: all")
    args = ap.parse_args(argv)
    chosen = [p for p in args.phases.split(",") if p]
    unknown = set(chosen) - set(PHASES)
    if unknown or ("serve" in chosen and "train" not in chosen):
        ap.error(f"bad --phases {args.phases!r}")

    t_start = time.perf_counter()
    cache_dir = compile_cache.enable()      # first: before anything compiles
    entries_before = compile_cache.stats(cache_dir)["entries"]
    import jax
    import jax.monitoring

    cache_events = {"hits": 0, "misses": 0, "backend_compile_s": 0.0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            cache_events["backend_compile_s"] += duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"chip_smoke: device {json.dumps(device)}")
    print(f"chip_smoke: jax {jax.__version__}, jaxlib {_version('jaxlib')}, "
          f"libtpu {_version('libtpu')}")
    print(f"chip_smoke: compile cache {cache_dir} "
          f"({entries_before} entries)", flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX found platform {device['platform']!r}, not "
              f"'tpu'; nothing was run", file=sys.stderr)
        return 1

    from deeplearning4j_tpu.datasets import native_io
    print(f"chip_smoke: reader tier {native_io.describe()} (the phases feed "
          f"arrays made from a seed, so no file reader runs; the serve "
          f"phase reports the engine's backend)", flush=True)

    sz = Sizes()
    net = None
    for name in chosen:
        t0 = time.perf_counter()
        if name == "train":
            net, info = phase_train(sz, expect_policy="mixed_bf16")
        elif name == "serve":
            info = phase_serve(sz, net)
        elif name == "decode":
            info = phase_decode(sz)
        elif name == "kernels":
            info = phase_kernels(sz)
        elif device["count"] >= 4:
            info = phase_four_chips(sz, devs)
        else:
            info = {"ran": False,
                    "reason": f"{device['count']} device(s), needs 4"}
        print(json.dumps({"phase": name, **{"ran": True, **info},
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)
    print(json.dumps({
        "phases": chosen,
        "wall_s": round(time.perf_counter() - t_start, 1),
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": compile_cache.stats(cache_dir)["entries"],
            "hits": cache_events["hits"], "misses": cache_events["misses"],
            "backend_compile_s": round(cache_events["backend_compile_s"],
                                       1)}}))
    result = {"ok": True, "device": device}
    if chosen != list(PHASES):
        result["partial"] = chosen      # not the whole proof
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
