"""Overlapped ingest for ``fit(iterator)``: device-resident epoch cache
and windowed double-buffered staging.

The reference hides ETL behind compute with a prefetch thread
(``datasets/iterator/AsyncDataSetIterator.java`` feeding
``MultiLayerNetwork.fit:976-980``).  On a TPU behind a host<->device
link, the analogous wins are:

1. **Device-resident epoch cache** — a dataset that fits in HBM is
   uploaded ONCE and stays resident across epochs; each epoch's
   permutation is computed ON DEVICE (threefry keyed off the fit RNG)
   inside the same ``lax.scan`` dispatch that gathers and trains, so
   steady-state epochs have ZERO per-epoch host->device traffic — not
   even the index upload v1 paid.  Consecutive epochs additionally
   fuse into one dispatch (bounded by
   :func:`max_steps_per_dispatch`) when no listeners need per-epoch
   callbacks and there is no tail batch.
2. **Windowed staging** — datasets that do not fit HBM stream in
   multi-batch windows: the host stacks window k+1 and enqueues its
   transfer while window k's multi-step scan runs on-chip (JAX async
   dispatch provides the overlap; nothing blocks until scores are
   fetched).

Both paths ship the **uint8 wire** when the source carries one
(``datasets/dataset.attach_wire``): integer-pixel datasets upload 1
byte/pixel — 4x fewer bytes than float32 (47 MB instead of 188 MB for
MNIST-60k) — and the ``f32(u8)/denom*mult+add`` decode is fused into
the first ops of the compiled train step (:func:`device_decode`).  The
decode replicates the host's float32 op order exactly, so wire and
non-wire paths are BIT-EXACT for both float32 and bfloat16 compute
(parity-tested; ``DL4J_TPU_WIRE_UINT8=0`` is the escape hatch).

Both paths preserve per-iteration listener semantics by REPLAY: the
scan returns per-step scores, and listeners fire once per underlying
iteration with the exact score of that step (params seen by a replayed
listener are end-of-dispatch params — the documented divergence, same
compromise as ``fit_scan``).
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import List, Optional, Tuple

import jax
import numpy as np

from .. import monitor as _monitor
from ..datasets.dataset import wire_enabled, wire_of

#: Datasets larger than this (features + labels bytes) never device-cache.
#: Default 2 GB leaves headroom on a 16 GB-HBM chip for params, updater
#: state, activations and the scan's score stack.
DEVICE_CACHE_LIMIT_BYTES = int(os.environ.get(
    "DL4J_TPU_DEVICE_CACHE_LIMIT", 2_000_000_000))

_CACHEABLE_DTYPES = ("float32", "bfloat16")


def max_steps_per_dispatch() -> int:
    """Upper bound on scan steps folded into ONE epoch-cache dispatch
    (``DL4J_TPU_MAX_STEPS_PER_DISPATCH``, default 1024).  Bounds both
    the scanned score stack's HBM footprint and how long listeners can
    lag behind the chip when epochs fuse."""
    return int(os.environ.get("DL4J_TPU_MAX_STEPS_PER_DISPATCH", 1024))


def _scaler_wire(preprocessor, features: np.ndarray):
    """(u8, fmt) when ``preprocessor`` is an affine pixel scaler over
    uint8 features — the one preprocessor whose transform the device
    decode can reproduce bit-exactly — else None."""
    from ..datasets.normalizers import wire_format_of
    if preprocessor is None or features.dtype != np.uint8:
        return None
    fmt = wire_format_of(preprocessor)
    return None if fmt is None else (features, fmt)


def cacheable_source(iterator):
    """Return the underlying ``ListDataSetIterator`` when ``iterator``
    can be served by the device-resident epoch cache, else ``None``.

    Mirrors the eligibility posture of the native-prefetch takeover in
    ``datasets/iterators.py``: exact ``ListDataSetIterator`` iteration
    semantics only (a subclass overriding ``__next__``/``reset`` keeps
    its override by falling back), dense float features/labels, no
    masks, and total bytes under :data:`DEVICE_CACHE_LIMIT_BYTES`.
    Preprocessors disqualify — with ONE exception: an affine pixel
    scaler (``ImagePreProcessingScaler``) over uint8 features, whose
    transform IS the uint8 wire decode and therefore fuses into the
    compiled step (wire enabled only).
    """
    from ..datasets.iterators import (AsyncDataSetIterator,
                                      ListDataSetIterator)
    u = iterator
    if isinstance(u, AsyncDataSetIterator):
        if u.get_preprocessor() is not None:
            return None
        u = u._under
    if not isinstance(u, ListDataSetIterator):
        return None
    if (type(u).__next__ is not ListDataSetIterator.__next__
            or type(u).reset is not ListDataSetIterator.reset):
        return None
    ds = u._ds
    if ds.features is None or ds.labels is None:
        return None
    if ds.features_mask is not None or ds.labels_mask is not None:
        return None
    f = np.asarray(ds.features)
    l = np.asarray(ds.labels)
    if u.get_preprocessor() is not None:
        if not (wire_enabled()
                and _scaler_wire(u.get_preprocessor(), f) is not None):
            return None
    elif f.dtype.name not in _CACHEABLE_DTYPES:
        return None
    if l.dtype.name not in _CACHEABLE_DTYPES:
        return None
    if f.nbytes + l.nbytes > DEVICE_CACHE_LIMIT_BYTES:
        return None
    return u


def device_cached_arrays(model, ds, preprocessor=None) -> Tuple:
    """``(dev_features, dev_labels, wire_spec)`` device copies of ``ds``
    that stay resident ACROSS ``fit()`` calls (true epoch-cache
    residency: without this, every fit() re-pays the full dataset
    host->device transfer).

    When ``ds`` carries a uint8 wire twin (or ``preprocessor`` is an
    affine pixel scaler over uint8 features) and the wire is enabled,
    the UINT8 buffer is what gets uploaded — 4x fewer bytes than
    float32 — and ``wire_spec`` is the ``(denom, mult, add)`` float
    triple whose on-device decode (:func:`device_decode`) reproduces
    the float32 features bit-exactly.  ``wire_spec`` is None when the
    float32 arrays shipped as-is.

    The cache lives on the model and is keyed by host-array identity
    (plus the wire decision, so flipping ``DL4J_TPU_WIRE_UINT8``
    between fits re-uploads): it holds references to the exact
    feature/label ndarrays it uploaded, so re-use requires ``ds`` to
    still expose those same objects; assigning new arrays re-uploads.
    In-place mutation of the same arrays between fits is NOT detected —
    matching the reference's posture that a dataset is immutable while
    training on it."""
    import jax.numpy as jnp
    f = np.asarray(ds.features)
    l = np.asarray(ds.labels)
    wire = None
    if wire_enabled():
        w = wire_of(ds)
        if w is not None and w[0].shape == f.shape:
            wire = w
        else:
            wire = _scaler_wire(preprocessor, f)
    fmt = None if wire is None else wire[1]
    cache = getattr(model, "_ingest_device_cache", None)
    if (cache is not None and cache[0] is f and cache[1] is l
            and cache[2] == fmt):
        return cache[3], cache[4], cache[5]
    if wire is not None:
        dev_f = jnp.asarray(np.ascontiguousarray(wire[0]))
        wire_spec = fmt.as_tuple()
    else:
        dev_f = jnp.asarray(f)
        wire_spec = None
    dev_l = jnp.asarray(l)
    _monitor.gauge(
        "ingest_staged_bytes",
        "bytes uploaded to the device per staging event").set(
        dev_f.nbytes + dev_l.nbytes, path="cache")
    model._ingest_device_cache = (f, l, fmt, dev_f, dev_l, wire_spec)
    return dev_f, dev_l, wire_spec


def device_decode(f, wire):
    """Fused on-device wire decode: ``f32(u8) / denom * mult + add``.
    Applied unconditionally (all three ops) so the program shape never
    depends on the wire VALUES — ``/1.0``, ``*1.0`` and ``+0.0`` are
    exact float32 identities for the non-negative pixel range.  The op
    order replicates the host readers' numpy float32 arithmetic
    (``u8.astype(f32) / 255.0``; ``ImagePreProcessingScaler.transform``)
    operation for operation, and IEEE-754 round-to-nearest-even makes
    each op bit-identical between numpy and XLA — the root of the
    wire-vs-float32 parity guarantee.  ``wire`` is a ``(denom, mult,
    add)`` python-float triple (weak-typed scalars: values never force
    a retrace) or None for pass-through; for a graph's tuple of inputs,
    a tuple with one of those for each."""
    if wire is None:
        return f
    if isinstance(f, (list, tuple)):
        return tuple(device_decode(fi, w) for fi, w in zip(f, wire))
    import jax.numpy as jnp
    denom, mult, add = wire
    return f.astype(jnp.float32) / denom * mult + add


def consume_epoch(u) -> None:
    """Advance ``u`` through one epoch's worth of state transitions
    without materializing any batches.  The canonical ``fit(iterator)``
    path resets twice per epoch (the explicit ``it.reset()`` plus
    ``__iter__``'s reset), so the cache path performs the same two
    transitions and then marks the iterator consumed — external
    observers (and a later fall-back to the per-batch path) see the
    same iterator state.  The example ORDER itself comes from the
    on-device threefry permutation stream, not from the iterator's
    host RNG."""
    u.reset()
    u.reset()
    u._pos = u._ds.num_examples()


def epoch_index_batches(order: np.ndarray,
                        batch: int) -> List[np.ndarray]:
    """Split an epoch permutation into (S, B) full-batch indices plus an
    optional (1, tail) remainder — the same batch boundaries
    ``ListDataSetIterator.__next__`` produces."""
    n = order.shape[0]
    s, tail = divmod(n, batch)
    out = []
    if s:
        out.append(order[:s * batch].reshape(s, batch).astype(np.int32))
    if tail:
        out.append(order[s * batch:].reshape(1, tail).astype(np.int32))
    return out


def window_signature(mds) -> Tuple:
    """Shape/mask-presence signature of a MultiDataSet (a chain's
    DataSet arrives wrapped as one: ``network._as_multi``); a window
    only stacks batches with identical signatures (a change flushes the
    window)."""
    def shps(seq):
        if seq is None:
            return None
        return tuple(None if a is None else np.shape(a) for a in seq)
    return (shps(mds.features), shps(mds.labels),
            shps(mds.features_masks), shps(mds.labels_masks))


def stack_window(mbs) -> Tuple:
    """Stack a window of same-signature MultiDataSets into per-input
    lists of (W, B, ...) numpy arrays (host-side, so the work overlaps
    on-chip execution of the previous window).  Returns (features,
    labels, fmasks, lmasks); a masks entry is None where no batch has
    masks."""
    n_in = len(mbs[0].features)
    n_out = len(mbs[0].labels)
    features = [np.stack([np.asarray(m.features[i]) for m in mbs])
                for i in range(n_in)]
    labels = [np.stack([np.asarray(m.labels[i]) for m in mbs])
              for i in range(n_out)]

    def masks(get, count):
        if all(get(m) is None for m in mbs):
            return None
        out = []
        for i in range(count):
            if get(mbs[0]) is None or get(mbs[0])[i] is None:
                out.append(None)
            else:
                out.append(np.stack([np.asarray(get(m)[i]) for m in mbs]))
        return out

    fmasks = masks(lambda m: m.features_masks, n_in)
    lmasks = masks(lambda m: m.labels_masks, n_out)
    return features, labels, fmasks, lmasks


def window_wire(mbs, n_in: int):
    """Per-input wire staging for a window of MultiDataSets (wire twins
    ride on ``_wires``, attached by ``network._as_multi`` when the source
    batch carried one).  Where EVERY batch of the window carries the
    same-format uint8 twin of an input (and the wire is enabled), that
    input ships 1 byte/pixel and decodes on device.  Returns ``(stacks,
    specs)`` — per-input lists where a wired slot holds its stacked
    (W, B, ...) uint8 array / ``(denom, mult, add)`` spec and an unwired
    slot holds None — or ``(None, None)`` when no input wires and the
    window stages float32 (or host-cast bfloat16) as before."""
    if not wire_enabled():
        return None, None
    wire_lists = [getattr(m, "_wires", None) for m in mbs]
    stacks: List[Optional[np.ndarray]] = []
    specs: List[Optional[Tuple]] = []
    for i in range(n_in):
        ok = all(w is not None and len(w) > i and w[i] is not None
                 for w in wire_lists)
        if (ok and len({w[i][1] for w in wire_lists}) == 1
                and all(w[i][0].shape == np.shape(m.features[i])
                        for w, m in zip(wire_lists, mbs))):
            stacks.append(np.stack([w[i][0] for w in wire_lists]))
            specs.append(wire_lists[0][i][1].as_tuple())
        else:
            stacks.append(None)
            specs.append(None)
    if all(s is None for s in stacks):
        return None, None
    return stacks, tuple(specs)


def cast_for_transfer(features: np.ndarray, compute_dtype) -> np.ndarray:
    """Halve the windowed path's host->device bytes: when the model
    computes in bfloat16, cast float32 feature stacks on HOST before the
    transfer.  The train step's first action on floating inputs is this
    exact cast (``multilayer.py`` ``_forward``: inputs go to the compute
    dtype), both sides round-to-nearest-even, so this just moves the
    cast across the wire — identical numerics, half the bytes on the
    bandwidth-bound link.  Integer features (embedding ids) and labels
    (loss-side) are left untouched."""
    if compute_dtype != "bfloat16" or features.dtype != np.float32:
        return features
    import ml_dtypes
    return features.astype(ml_dtypes.bfloat16)


def fetch_scores(scores) -> np.ndarray:
    """Device scores on the host.  For a device array this is where the
    host waits for the device (the dispatch that produced it may still
    be running), so the wait is a ``fit/score_wait`` span; a value that
    is already on the host is just converted."""
    if not isinstance(scores, jax.Array):
        return np.asarray(scores)
    with _monitor.span("fit/score_wait"):
        return np.asarray(scores)


class ScoreReplayer:
    """Collects (start_iteration, device scores) per dispatch and
    replays listeners with per-step scores.  Fetching a dispatch's
    scores is the only blocking point, so dispatch k+1's staging always
    overlaps dispatch k's on-chip execution."""

    def __init__(self, model):
        self._model = model
        self._pending: List[Tuple[int, object]] = []

    def add(self, start_iteration: int, scores) -> None:
        self._pending.append((start_iteration, scores))

    def replay(self) -> None:
        """Fetch pending scores and fire ``iteration_done`` once per
        step (exact per-iteration score; params are end-of-dispatch)."""
        model = self._model
        for start, dev_scores in self._pending:
            scores = fetch_scores(dev_scores)
            for j, s in enumerate(scores):
                model._score = s
                for listener in model.listeners:
                    listener.iteration_done(model, start + j + 1)
        self._pending = []

    def finish(self) -> None:
        """End-of-fit bookkeeping for the no-listener case: leave
        ``_score`` as the LAZY last-step device scalar (no host
        round-trip on the hot path — ``score()`` fetches on demand)."""
        if self._pending:
            self._model._score = self._pending[-1][1][-1]
            self._pending = []


def run_device_cached_fit(model, u, epochs: int, dispatch, *,
                          start_step: int = 0, ckpt=None):
    """Shared MLN/ComputationGraph driver for the device-resident
    epoch-cache fit.  ``u`` is the vetted ``ListDataSetIterator``;
    ``dispatch(first_epoch, fused_epochs, tail, start, run)`` invokes
    the model's gather-scan train step (which derives each epoch's
    permutation on device — see ``_gather_train_step``) and returns
    per-step scores; ``start``/``run`` select a sub-range of the
    epoch's full-batch steps so a dispatch can begin mid-epoch.

    One call per epoch normally; when no listeners are attached, the
    batch divides the dataset (no tail), and no step-cadence checkpoint
    is active, up to :func:`max_steps_per_dispatch` steps' worth of
    CONSECUTIVE epochs fold into a single dispatch — multi-epoch fits
    become a handful of XLA invocations with zero host traffic between
    them.  Listeners force per-epoch dispatches so score replay and
    epoch callbacks keep their per-iteration/per-epoch semantics.  A
    tail batch runs as its own 1-step dispatch (same on-device
    permutation, last ``tail`` entries), preserving the per-batch
    path's batch boundaries.

    Resilience hooks: ``start_step`` (from a restored checkpoint's
    ``step_in_epoch``) starts the FIRST epoch at that scan offset —
    the permutation is recomputed from the same threefry key, so the
    split epoch trains the identical step sequence an uninterrupted
    run would have, then later epochs return to full fusion.  ``ckpt``
    (a ``resilience.CheckpointManager``) bounds dispatch chunks to the
    step cadence, saves when due (scores are replayed first so
    listener output is never ahead of a checkpoint), and gives the
    fault layer its preemption point *after* each save."""
    from ..resilience import faults as _faults

    replay = ScoreReplayer(model)
    iters = _monitor.counter("train_iterations_total",
                             "supervised train iterations")
    n = u._ds.num_examples()
    batch = u._batch
    steps, tail = divmod(n, batch)
    fuse_cap = max(1, max_steps_per_dispatch() // max(1, steps))
    pos = int(start_step)
    if pos < 0 or pos >= steps:
        pos = 0
    step_cadence = (getattr(ckpt, "every_steps", None)
                    if ckpt is not None else None)

    def maybe_save(step_in_epoch, epoch_boundary=False):
        if ckpt is not None and ckpt.due(epoch_boundary=epoch_boundary):
            replay.replay()  # flush scores; listeners never trail a save
            ckpt.save(model, step_in_epoch=step_in_epoch)

    # ``fit/dispatch`` is the host's cost of one launch: signature hash,
    # jit lookup, argument handling, enqueue.  Opened in this frame, not
    # in a wrapper around ``dispatch``: the first launch traces and
    # lowers the step under it, and one more Python frame there moved
    # that by over a second (PERF.md section 6, PR 24).
    done = 0
    while done < epochs:
        fuse = 1
        if (not model.listeners and tail == 0 and steps > 0 and pos == 0
                and step_cadence is None):
            fuse = min(epochs - done, fuse_cap)
        with _monitor.span("fit/epoch", epoch=model.epoch, path="cache",
                           fused=fuse, start=pos):
            if pos == 0:
                for listener in model.listeners:
                    if hasattr(listener, "on_epoch_start"):
                        listener.on_epoch_start(model)
            t0 = time.perf_counter()
            with _monitor.span("fit/stage"):
                for _ in range(fuse):
                    consume_epoch(u)
            _monitor.observe_phase("data", time.perf_counter() - t0)
            t1 = time.perf_counter()
            chunked = bool(steps and (pos or step_cadence is not None))
            # Clean fused path: the sanitizer's budgeted unit is one
            # dispatch per fused epoch plus one for the tail batch.
            # Resumed/checkpointed epochs legitimately chunk into
            # multiple dispatches, so only the clean path is bracketed.
            scen = (contextlib.nullcontext() if chunked else
                    _monitor.sanitize_scenario("fit.epoch_cache",
                                               units=fuse,
                                               extra=1 if tail else 0))
            with scen:
                if chunked:
                    # resumed and/or checkpointed epoch: chunked
                    # dispatches over [pos, steps), each chunk ending
                    # on a save point
                    while pos < steps:
                        run = steps - pos
                        if step_cadence is not None:
                            run = min(run, ckpt.steps_to_next_save())
                        with _monitor.span("fit/dispatch", steps=run,
                                           fused=1):
                            scores = dispatch(model.epoch, 1, 0, pos, run)
                        replay.add(model.iteration, scores)
                        iters.inc(run)
                        model.iteration += run
                        model.last_batch_size = batch
                        pos += run
                        if ckpt is not None:
                            ckpt.note_steps(run)
                        if pos < steps:
                            maybe_save(pos)
                            _faults.maybe_die(model.iteration)
                elif steps:
                    with _monitor.span("fit/dispatch", steps=fuse * steps,
                                       fused=fuse):
                        scores = dispatch(model.epoch, fuse, 0, 0, steps)
                    replay.add(model.iteration, scores)
                    iters.inc(fuse * steps)
                    model.iteration += fuse * steps
                    model.last_batch_size = batch
                    if ckpt is not None:
                        ckpt.note_steps(fuse * steps)
                if tail:
                    with _monitor.span("fit/dispatch", steps=1, fused=1):
                        scores = dispatch(model.epoch, 1, tail, 0, 0)
                    replay.add(model.iteration, scores)
                    iters.inc(1)
                    model.iteration += 1
                    model.last_batch_size = tail
                    if ckpt is not None:
                        ckpt.note_steps(1)
            _monitor.observe_phase("step", time.perf_counter() - t1)
            if model.listeners:
                t2 = time.perf_counter()
                replay.replay()     # blocks: exact per-step scores
                _monitor.observe_phase("listener",
                                       time.perf_counter() - t2)
            for listener in model.listeners:
                if hasattr(listener, "on_epoch_end"):
                    listener.on_epoch_end(model)
            model.epoch += fuse
            pos = 0
        done += fuse
        maybe_save(0, epoch_boundary=True)
        _faults.maybe_die(model.iteration)
    if ckpt is not None:
        replay.replay()
        ckpt.save_if_progress(model, step_in_epoch=0)
        ckpt.flush()
    replay.finish()
    return model
