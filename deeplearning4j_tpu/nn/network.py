"""The training path both containers share.

``MultiLayerNetwork`` (a chain of layers, parameters in a list) and
``ComputationGraph`` (a DAG of named vertices, parameters in a dict)
differ in how a net is laid out and run forward.  How it is trained does
not depend on that: the updater and the regularization score over the
layers in order, the supervised step and the three programs built round
it, the ``fit`` drivers that feed them, and the flat-vector view of
parameters and updater state.  All of that is written once, here, over
what a container supplies:

- ``_jit_prefix``: ``"mln"`` / ``"cg"``, the first half of every
  ``watched_jit`` name (the jit-watch counters, the sanitizer's budgets
  and the executable store read those names);
- ``_layer_items()``: ``(key, scope name, layer)`` for every layer that
  holds parameters, in the flat vector's order (a list index and
  ``"<i>_<Class>"`` for the chain, the vertex name twice for the graph,
  in topological order);
- ``_inputs_of(arrays)``: what ``_loss_fn`` takes in place of a sequence
  holding one array for each input (or each output, or their masks or
  wires): the chain has one of each and takes it bare, the graph takes
  the tuple;
- ``conf``, ``init``, ``_forward``, ``_loss_fn``, ``_updater_conf``,
  ``pretrain``, ``_fit_tbptt`` and everything that serves a trained net.
"""

from __future__ import annotations

import copy
import functools
import time
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import precision as _precision
from . import updaters as _updaters
from .. import monitor as _monitor
from ..datasets.dataset import DataSet, MultiDataSet, wire_of

Array = jax.Array


def _as_multi(data) -> MultiDataSet:
    if isinstance(data, MultiDataSet):
        return data
    if isinstance(data, DataSet):
        mds = MultiDataSet(
            features=[data.features], labels=[data.labels],
            features_masks=(None if data.features_mask is None
                            else [data.features_mask]),
            labels_masks=(None if data.labels_mask is None
                          else [data.labels_mask]))
        wire = wire_of(data)
        if wire is not None:
            # per-input wire list (ingest.window_wire): a wrapped
            # DataSet wires its single input
            mds._wires = [wire]
        return mds
    raise TypeError(f"Expected DataSet/MultiDataSet, got {type(data)}")


def _on_device(arrays):
    """A sequence of host arrays (None where a mask is absent), or None
    for no masks at all, as a tuple of device arrays."""
    if arrays is None:
        return None
    return tuple(None if a is None else jnp.asarray(a) for a in arrays)


class Network:
    """Base of ``MultiLayerNetwork`` and ``ComputationGraph``: see the
    module docstring for what a container supplies."""

    _jit_prefix: str
    _inference_only = False

    def _pol(self) -> _precision.PrecisionPolicy:
        """The precision policy, resolved once per network instance
        (docs/PERFORMANCE.md) — param storage dtype, compute dtype,
        updater-state dtype, and the fp32-master-weights flag."""
        p = self._precision
        if p is None:
            p = self._precision = _precision.resolve_policy(self.conf.conf)
        return p

    @functools.cached_property
    def _solver(self):
        """Line-search solver when ``optimization_algo`` asks for one
        (reference ``Solver.java``); None selects the jitted SGD path.
        Unknown algorithms raise instead of silently training with SGD."""
        from ..optimize.solvers import SGD, Solver
        algo = (self.conf.conf.optimization_algo or SGD).lower()
        if algo == SGD:
            return None
        if self.conf.backprop_type == "tbptt":
            raise ValueError(
                f"optimization_algo {algo!r} is incompatible with tBPTT; "
                "use stochastic_gradient_descent")
        return Solver(self, algo)

    # ------------------------------------------------------------ train step
    def _reg_score(self, params) -> Array:
        total = jnp.asarray(0.0, jnp.float32)
        with _monitor.scope("reg"):
            for key, _, layer in self._layer_items():
                total = total + _updaters.regularization_score(
                    params[key], layer.l1_by_param(), layer.l2_by_param())
        return total

    def _apply_updates(self, params, updater_state, grads, iteration):
        """DL4J-order updater application (l1/l2 into grad, grad-norm, then
        per-param update rule).  A layer without gradients keeps its
        parameters and updater state as they are."""
        new_params = copy.copy(params)
        new_updater_state = copy.copy(updater_state)
        for key, name, layer in self._layer_items():
            if grads[key]:
                with _monitor.scope("update", name):
                    new_params[key], new_updater_state[key] = \
                        _updaters.apply_layer_updates(
                            self._updater_conf(key), layer, params[key],
                            updater_state[key], grads[key], iteration)
        return new_params, new_updater_state

    def _step_fn(self, base_rng, batch_of):
        """The supervised step, as a ``lax.scan`` body over the carry
        ``(params, updater_state, net_state, iteration)``: fwd + bwd +
        updater, the score, the packed per-layer grad/param/update
        statistics (``monitor/health.py``: a few scalar reductions over
        values already in registers) and the in-jit divergence guard.
        ``batch_of(x)`` turns the scanned element into ``(features,
        labels, features_mask, labels_mask)``.  Returns ``(new carry,
        (score, health vector))``: the (S, 2+3L) f32 health stack rides
        the same dispatch as the scores, so exact per-step telemetry
        costs zero extra dispatches.  The three programs below are this
        body and nothing else, so a change to the step is made here and
        reaches every ``fit`` path of both containers."""
        from ..monitor import health as _health
        order = [key for key, _, _ in self._layer_items()]

        def body(carry, x):
            p, u, s, it = carry
            f, l, fm, lm = batch_of(x)
            rng = jax.random.fold_in(base_rng, it)
            (data_loss, (new_s, _)), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True)(p, s, f, l, fm, lm, rng, True)
            new_p, new_u = self._apply_updates(p, u, grads, it)
            score = data_loss + self._reg_score(p)
            hvec, bad = _health.layer_stats(p, new_p, grads, data_loss,
                                            order=order)
            new_p, new_u, new_s = _health.guard_select(
                bad, (new_p, new_u, new_s), (p, u, s))
            return (new_p, new_u, new_s, it + 1), (score, hvec)

        return body

    @functools.cached_property
    def _train_step(self):
        """The jitted train step: fwd + bwd + updater in one XLA program,
        ``(params, updater_state, net_state, score, health vector)`` out.
        Donation lets XLA update params/updater state in place in HBM
        (the analogue of the reference's in-place flat-buffer step)."""
        def step(params, updater_state, net_state, iteration, features,
                 labels, features_mask, labels_mask, base_rng):
            body = self._step_fn(base_rng, lambda batch: batch)
            (params, updater_state, net_state, _), (score, hvec) = body(
                (params, updater_state, net_state, iteration),
                (features, labels, features_mask, labels_mask))
            return params, updater_state, net_state, score, hvec

        return _monitor.watched_jit(
            step, name=f"{self._jit_prefix}.train_step",
            donate_argnums=(0, 1, 2))

    @functools.cached_property
    def _multi_train_step(self):
        """S sequential train steps in ONE XLA program via ``lax.scan`` over
        stacked (S, B, ...) batches.  The reference runs its inner loop on
        the host (``StochasticGradientDescent.java:50-72``, one dispatch per
        iteration); on TPU the scan keeps the whole loop on-chip, so
        throughput is set by the MXU, not by host dispatch latency.
        ``wire`` is the ``(denom, mult, add)`` spec of uint8 features (one
        for each input of a graph) or None."""
        from . import ingest

        def multi(params, updater_state, net_state, iteration, features,
                  labels, features_mask, labels_mask, base_rng, wire=None):
            def batch_of(xs):
                f, l, fm, lm = xs
                return ingest.device_decode(f, wire), l, fm, lm

            body = self._step_fn(base_rng, batch_of)
            init = (params, updater_state, net_state,
                    jnp.asarray(iteration, jnp.int32))
            (params, updater_state, net_state, _), (scores, hstack) = \
                jax.lax.scan(body, init, (features, labels, features_mask,
                                          labels_mask))
            return params, updater_state, net_state, scores, hstack

        return _monitor.watched_jit(
            multi, name=f"{self._jit_prefix}.multi_train_step",
            donate_argnums=(0, 1, 2))

    @functools.cached_property
    def _gather_train_step(self):
        """Device-cached-epoch train step, v2: the epoch PERMUTATION is
        computed on device (threefry ``fold_in(shuffle_key, epoch)``
        feeding ``jax.random.permutation``) and up to ``fused`` whole
        epochs scan in ONE XLA program, each step gathering its
        minibatch from the HBM-resident dataset arrays.  v1 uploaded a
        host-shuffled (S, B) int32 index array every epoch; v2's
        steady-state epochs move ZERO bytes host->device — the epoch
        loop never leaves the chip.  When the resident features are the
        uint8 wire, the affine decode fuses into the gathered batch
        (``ingest.device_decode``).

        Static args (``fused``/``steps``/``batch``/``shuffle``/
        ``tail``/``start``/``run``) fix the program shape;
        ``first_epoch`` stays dynamic (weak int32) so advancing epochs
        never retraces.  ``tail > 0`` selects the 1-step tail dispatch:
        the SAME epoch permutation is recomputed and its last ``tail``
        entries form the ragged final batch, keeping v1's batch
        boundaries.  ``start``/``run`` select the sub-range
        ``[start, start+run)`` of the epoch's full-batch steps — the
        preemption-resume hook: a checkpoint restored mid-epoch
        re-derives the SAME permutation and scans from the saved
        offset, so the split epoch is bit-identical to the fused one
        (the scan body compiles to the same per-step HLO regardless of
        trip count, and the carry chain crosses dispatches exactly).
        The fused multi-epoch program stays ONE dispatch per call,
        health stack included."""
        from . import ingest

        def multi(params, updater_state, net_state, iteration, data_f,
                  data_l, base_rng, shuffle_key, first_epoch, fused,
                  steps, batch, shuffle, tail, wire, start=0, run=None):
            n = jax.tree.leaves(data_f)[0].shape[0]
            span = steps if run is None else run

            def epoch_rows(e):
                if shuffle:
                    perm = jax.random.permutation(
                        jax.random.fold_in(shuffle_key, e), n)
                else:
                    perm = jnp.arange(n)
                if tail:
                    return perm[steps * batch:].reshape(1, tail)
                return perm[start * batch:(start + span) * batch] \
                    .reshape(span, batch)

            rows = jax.vmap(epoch_rows)(first_epoch + jnp.arange(fused))
            rows = rows.reshape((-1,) + rows.shape[2:])

            def batch_of(idx_row):
                def take(d):
                    return jnp.take(d, idx_row, axis=0)

                with _monitor.scope("ingest", "gather"):
                    f = ingest.device_decode(jax.tree.map(take, data_f),
                                             wire)
                    l = jax.tree.map(take, data_l)
                return f, l, None, None

            body = self._step_fn(base_rng, batch_of)
            init = (params, updater_state, net_state,
                    jnp.asarray(iteration, jnp.int32))
            (params, updater_state, net_state, _), (scores, hstack) = \
                jax.lax.scan(body, init, rows)
            return params, updater_state, net_state, scores, hstack

        return _monitor.watched_jit(
            multi, name=f"{self._jit_prefix}.gather_train_step",
            static_argnums=(9, 10, 11, 12, 13, 15, 16),
            donate_argnums=(0, 1, 2),
            identity=lambda: _monitor.program_identity(
                self, "gather_train_step"))

    # ------------------------------------------------------------ fit paths
    def _fit_device_cached(self, source, epochs: int,
                           start_step: int = 0, ckpt=None):
        """One ``fit`` over a device-resident dataset (see
        ``_gather_train_step``).  ``source`` is the underlying
        ``ListDataSetIterator`` vetted by ``ingest.cacheable_source``
        (single-input DataSets).  Batch boundaries (incl. the tail batch)
        and the per-iteration RNG/updater stream are IDENTICAL to the
        per-batch path; the example order comes from the on-device
        threefry permutation stream (keyed off the fit RNG, continuing
        across fits via ``self.epoch``) — parity-tested against a host
        replay of the same permutations.  Listeners fire per iteration
        by replaying the scanned scores.  ``start_step``/``ckpt`` are the
        resume offset and checkpoint manager threaded through to the
        shared driver (``ingest.run_device_cached_fit``)."""
        from . import ingest

        data_f, data_l, wire = (
            self._inputs_of((a,)) for a in ingest.device_cached_arrays(
                self, source._ds, source.get_preprocessor()))
        shuffle_key = jax.random.fold_in(self._rng_key, 0xFFFFFFFF)
        steps = source._ds.num_examples() // source._batch

        def dispatch(first_epoch, fused, tail, start=0, run=None):
            (self.params, self.updater_state, self.net_state,
             scores, health) = self._gather_train_step(
                self.params, self.updater_state, self.net_state,
                self.iteration, data_f, data_l, self._rng_key,
                shuffle_key, first_epoch, fused, steps, source._batch,
                bool(source._shuffle), tail, wire, start,
                steps if run is None else run)
            _monitor.health.record_dispatch(self, health, self.iteration)
            return scores

        return ingest.run_device_cached_fit(self, source, epochs, dispatch,
                                            start_step=start_step,
                                            ckpt=ckpt)

    def _fit_windowed(self, iterator, epochs: int, window: int,
                      ckpt=None):
        """Streaming ``fit(iterator)`` in multi-batch windows: the host
        stacks window k+1 (numpy) and enqueues its transfer while window
        k's multi-step scan runs on-chip — JAX async dispatch provides
        the overlap, nothing blocks until scores are fetched (the
        double-buffered-staging half of the ingest design; datasets that
        fit HBM take ``_fit_device_cached`` instead).  ``ckpt`` saves at
        epoch boundaries (windows re-stack from the host iterator, so
        mid-epoch offsets are not replayable here — the epoch-cache
        path owns exact mid-epoch resume)."""
        from . import ingest
        from ..resilience import faults as _faults

        replay = ingest.ScoreReplayer(self)

        def dispatch(buf):
            t0 = time.perf_counter()
            # straggler point inside the timed data phase, so an armed
            # DL4J_TPU_FAULT_SLOW_WORKER_MS stall lands in phase_data_ms
            # and the step attributor names "data" as the dominant
            # component (monitor/attribution.py)
            _faults.slow_worker()
            features, labels, fms, lms = ingest.stack_window(buf)
            cdt = self._pol().compute_name
            u8s, wires = ingest.window_wire(buf, len(features))
            # a wired input ships 1 byte/pixel; its decode is fused on
            # the device
            features = [
                u8s[i] if u8s is not None and u8s[i] is not None
                else ingest.cast_for_transfer(f, cdt)
                for i, f in enumerate(features)]
            features, labels, fms, lms = (
                _on_device(a) for a in (features, labels, fms, lms))
            _monitor.gauge(
                "ingest_staged_bytes",
                "bytes uploaded to the device per staging event").set(
                sum(f.nbytes for f in features)
                + sum(l.nbytes for l in labels), path="window")
            t1 = time.perf_counter()
            _monitor.observe_phase("data", t1 - t0)
            (self.params, self.updater_state, self.net_state,
             scores, health) = self._multi_train_step(
                self.params, self.updater_state, self.net_state,
                self.iteration, self._inputs_of(features),
                self._inputs_of(labels), self._inputs_of(fms),
                self._inputs_of(lms), self._rng_key,
                self._inputs_of(wires))
            _monitor.health.record_dispatch(self, health, self.iteration)
            replay.add(self.iteration, scores)
            _monitor.observe_phase("step", time.perf_counter() - t1)
            _monitor.counter("train_iterations_total",
                             "supervised train iterations").inc(len(buf))
            self.iteration += len(buf)
            self.last_batch_size = buf[0].num_examples()

        it_mark = self.iteration
        for _ in range(epochs):
            with _monitor.span("fit/epoch", epoch=self.epoch,
                               path="window"):
                for listener in self.listeners:
                    if hasattr(listener, "on_epoch_start"):
                        listener.on_epoch_start(self)
                if hasattr(iterator, "reset"):
                    iterator.reset()
                buf, sig = [], None
                for ds in iterator:
                    mds = _as_multi(ds)
                    s = ingest.window_signature(mds)
                    if buf and (s != sig or len(buf) >= window):
                        dispatch(buf)
                        buf = []
                    sig = s
                    buf.append(mds)
                if buf:
                    dispatch(buf)
                if self.listeners:
                    t2 = time.perf_counter()
                    replay.replay()
                    _monitor.observe_phase("listener",
                                           time.perf_counter() - t2)
                for listener in self.listeners:
                    if hasattr(listener, "on_epoch_end"):
                        listener.on_epoch_end(self)
                self.epoch += 1
            if ckpt is not None:
                ckpt.note_steps(self.iteration - it_mark)
                it_mark = self.iteration
                if ckpt.due(epoch_boundary=True):
                    replay.replay()
                    ckpt.save(self, step_in_epoch=0)
            _faults.maybe_die(self.iteration)
        if ckpt is not None:
            replay.replay()
            ckpt.save_if_progress(self, step_in_epoch=0)
            ckpt.flush()
        replay.finish()
        return self

    def fit_scan(self, batches: Sequence) -> np.ndarray:
        """Fit a list of same-shaped minibatches (DataSets, or
        MultiDataSets for a graph) in one device dispatch (scan-based
        inner loop).  Returns the per-step scores.  Listeners fire once
        at the end with the final iteration — per-step host callbacks
        would break the single-HLO hot loop.

        Supports the standard-backprop regime only: configs using tBPTT,
        pretraining, or ``num_iterations > 1`` must go through ``fit()``
        (raises loudly rather than silently training differently)."""
        self.init()
        if self.conf.backprop_type == "tbptt":
            raise ValueError("fit_scan does not support tBPTT; use fit()")
        if self.conf.pretrain and not self._pretrain_done:
            raise ValueError("fit_scan does not run pretraining; call "
                             "pretrain() (or fit()) first")
        if self.conf.conf.num_iterations != 1:
            raise ValueError("fit_scan runs one update per batch; "
                             "num_iterations > 1 must use fit()")
        if self._solver is not None:
            raise ValueError("fit_scan supports the SGD path only; this "
                             "config uses a line-search solver")
        mbs = [_as_multi(b) for b in batches]

        def stack(get, count):
            return tuple(jnp.stack([jnp.asarray(get(m)[i]) for m in mbs])
                         for i in range(count))

        def stack_masks(get, count):
            if all(get(m) is None for m in mbs):
                return None
            # presence must agree per input INDEX across batches: batch 0
            # is not a template (masks are Sequence[Optional[array]])
            out = []
            for i in range(count):
                present = [get(m) is not None and get(m)[i] is not None
                           for m in mbs]
                if not any(present):
                    out.append(None)
                    continue
                if not all(present):
                    raise ValueError(
                        f"Mixed mask presence across batches for input "
                        f"{i} in fit_scan; provide masks on all batches "
                        f"or none")
                out.append(jnp.stack([jnp.asarray(get(m)[i]) for m in mbs]))
            return tuple(out)

        from ..resilience import faults as _faults
        t0 = time.perf_counter()
        # straggler point inside the timed data phase (see dispatch())
        _faults.slow_worker()
        n_in, n_out = len(mbs[0].features), len(mbs[0].labels)
        features = stack(lambda m: m.features, n_in)
        labels = stack(lambda m: m.labels, n_out)
        fmasks = stack_masks(lambda m: m.features_masks, n_in)
        lmasks = stack_masks(lambda m: m.labels_masks, n_out)
        t1 = time.perf_counter()
        _monitor.observe_phase("data", t1 - t0)
        (self.params, self.updater_state, self.net_state,
         scores, health) = self._multi_train_step(
            self.params, self.updater_state, self.net_state, self.iteration,
            self._inputs_of(features), self._inputs_of(labels),
            self._inputs_of(fmasks), self._inputs_of(lmasks),
            self._rng_key)
        _monitor.health.record_dispatch(self, health, self.iteration)
        _monitor.observe_phase("step", time.perf_counter() - t1)
        _monitor.counter("train_iterations_total",
                         "supervised train iterations").inc(len(mbs))
        self.iteration += len(mbs)
        self._score = scores[-1]
        self.last_batch_size = mbs[0].num_examples()
        self._fire_listeners()
        return np.asarray(scores)

    def _resolve_resilience(self, checkpoint, resume_from, epochs):
        """(manager, start_step, remaining_epochs) for ``fit``'s
        ``checkpoint=``/``resume_from=`` hooks; the no-resilience call
        stays import-free."""
        if checkpoint is None and resume_from is None:
            return None, 0, epochs
        from ..resilience.checkpoint import resolve_fit_resilience
        return resolve_fit_resilience(self, checkpoint, resume_from,
                                      epochs)

    def _warn_partial_epoch_restart(self, start_step: int,
                                    path: str) -> None:
        """Mid-epoch resume offsets are only replayable on the
        epoch-cache path (the shuffle lives in the on-device threefry
        stream); other paths restart the interrupted epoch."""
        if start_step:
            import warnings
            warnings.warn(
                f"resume_from checkpoint was taken mid-epoch "
                f"(step_in_epoch={start_step}) but the {path} path "
                "cannot seek into an epoch; restarting the epoch from "
                "step 0 (at-least-once semantics)", RuntimeWarning)

    def fit(self, data, labels=None, epochs: int = 1,
            ingest: str = "auto",
            window: int = 16, checkpoint=None,
            resume_from=None):
        """Train (reference ``MultiLayerNetwork.fit(DataSetIterator):976``
        / ``fit(INDArray,INDArray):1406``, ``ComputationGraph.fit``
        variants ``:650-810``).

        ``data`` may be a DataSetIterator-like iterable of :class:`DataSet`
        (or, for a graph, :class:`MultiDataSet`), a single one of them,
        or a features array with ``labels``.

        With ``conf.pretrain=True`` the first call runs layer-wise
        unsupervised pretraining before supervised backprop (reference
        ``fit`` at ``:991``, ``ComputationGraph.pretrain:510``); with
        ``conf.backprop=False`` only pretraining runs.

        ``ingest`` selects the iterator data path (the reference hides
        ETL behind ``AsyncDataSetIterator`` prefetch; on TPU the wins
        are device residency and transfer/compute overlap):

        - ``"auto"`` (default): device-resident epoch cache when the
          dataset fits HBM (``nn/ingest.py`` eligibility), else
          windowed double-buffered staging, else per-batch.
        - ``"cache"`` / ``"window"`` / ``"batch"``: force one path.

        The cache/window paths run multi-step ``lax.scan`` dispatches
        and fire listeners by exact per-step score replay (params seen
        by a replayed listener are end-of-dispatch — the ``fit_scan``
        compromise).  Solver/tBPTT/num_iterations>1 configs always use
        the per-batch path.

        Resilience (``docs/RESILIENCE.md``): ``checkpoint=`` (a
        ``resilience.CheckpointManager`` or a directory) saves
        preemption-safe checkpoints at the manager's step/second
        cadence (epoch boundaries by default); ``resume_from=``
        (``"auto"``, a directory, or a checkpoint path) restores
        params/updater/RNG/progress before training.  With
        ``resume_from``, ``epochs`` is the TOTAL epoch target the
        original run aimed for — the restored epoch counter determines
        how much work remains, so callers re-issue the identical fit
        call after a preemption.  On the epoch-cache path a mid-epoch
        restore resumes at the exact fused-scan step offset
        (bit-identical to the uninterrupted run); the window/batch
        paths restart the interrupted epoch from its beginning.
        """
        if ingest not in ("auto", "cache", "window", "batch"):
            raise ValueError(
                f"unknown ingest mode {ingest!r}; expected 'auto', "
                "'cache', 'window', or 'batch'")
        self.init()
        if self._inference_only:
            raise ValueError(
                "this net was initialised with init(for_inference=True): "
                "it holds no updater state and no master weights, so it "
                "cannot be trained")
        ckpt, start_step, epochs = self._resolve_resilience(
            checkpoint, resume_from, epochs)
        if labels is not None:
            data = DataSet(np.asarray(data), np.asarray(labels))
        if isinstance(data, (DataSet, MultiDataSet)):
            batches: Optional[Sequence] = [data]
            iterator = None
        else:
            iterator = data
            batches = None

        from ..optimize.listeners.listeners import finalize_listeners
        try:
            if self.conf.pretrain and not self._pretrain_done:
                if batches is None and not hasattr(iterator, "reset"):
                    # One-shot iterable: materialize so layer-wise
                    # pretraining and the supervised phase each see the
                    # full data.
                    batches = list(iterator)
                    iterator = None
                self.pretrain(batches if batches is not None else iterator)
                self._pretrain_done = True
            if not self.conf.backprop:
                return self

            if (iterator is not None and ingest != "batch"
                    and self._solver is None
                    and self.conf.backprop_type != "tbptt"
                    and self.conf.conf.num_iterations == 1):
                from . import ingest as ingest_mod
                if ingest in ("auto", "cache"):
                    source = ingest_mod.cacheable_source(iterator)
                    if source is not None:
                        return self._fit_device_cached(
                            source, epochs, start_step=start_step,
                            ckpt=ckpt)
                    if ingest == "cache":
                        raise ValueError(
                            "ingest='cache' but the iterator is not "
                            "device-cacheable (see nn/ingest.py "
                            "eligibility)")
                self._warn_partial_epoch_restart(start_step, "window")
                return self._fit_windowed(iterator, epochs, window,
                                          ckpt=ckpt)

            self._warn_partial_epoch_restart(start_step, "batch")
            from ..resilience import faults as _faults
            it_mark = self.iteration
            for _ in range(epochs):
                with _monitor.span("fit/epoch", epoch=self.epoch,
                                   path="batch"):
                    for listener in self.listeners:
                        if hasattr(listener, "on_epoch_start"):
                            listener.on_epoch_start(self)
                    it = batches if batches is not None else iterator
                    if hasattr(it, "reset"):
                        it.reset()
                    for ds in it:
                        self._fit_batch(ds)
                    for listener in self.listeners:
                        if hasattr(listener, "on_epoch_end"):
                            listener.on_epoch_end(self)
                    self.epoch += 1
                if ckpt is not None:
                    ckpt.note_steps(self.iteration - it_mark)
                    it_mark = self.iteration
                    if ckpt.due(epoch_boundary=True):
                        ckpt.save(self, step_in_epoch=0)
                _faults.maybe_die(self.iteration)
            if ckpt is not None:
                ckpt.save_if_progress(self, step_in_epoch=0)
                ckpt.flush()
            return self
        finally:
            finalize_listeners(self.listeners)

    def _fire_listeners(self) -> None:
        """Per-iteration listener callbacks, timed as the ``listener``
        phase (they run on the host and may force a device score fetch)."""
        if not self.listeners:
            return
        t0 = time.perf_counter()
        for listener in self.listeners:
            listener.iteration_done(self, self.iteration)
        _monitor.observe_phase("listener", time.perf_counter() - t0)

    def _fit_batch(self, ds) -> None:
        """One minibatch (a DataSet, or a MultiDataSet for a graph)
        through the solver, tBPTT or the jitted train step, as the conf
        says."""
        from ..resilience import faults as _faults
        mds = _as_multi(ds)
        self.last_batch_size = mds.num_examples()
        t0 = time.perf_counter()
        # straggler point inside the timed data phase (see dispatch())
        _faults.slow_worker()
        features, labels, fmasks, lmasks = (
            self._inputs_of(_on_device(a)) for a in (
                mds.features, mds.labels, mds.features_masks,
                mds.labels_masks))
        _monitor.observe_phase("data", time.perf_counter() - t0)
        iters = _monitor.counter("train_iterations_total",
                                 "supervised train iterations")
        if self._solver is not None:
            # line-search solver family (reference Solver.optimize path)
            for _ in range(self.conf.conf.num_iterations):
                t1 = time.perf_counter()
                self._score = self._solver.optimize(features, labels,
                                                    fmasks, lmasks)
                _monitor.observe_phase("step", time.perf_counter() - t1)
                self.iteration += 1
                iters.inc()
                self._fire_listeners()
            return
        if self.conf.backprop_type == "tbptt":
            for _ in range(self.conf.conf.num_iterations):
                self._fit_tbptt(features, labels, fmasks, lmasks)
            return
        for _ in range(self.conf.conf.num_iterations):
            t1 = time.perf_counter()
            (self.params, self.updater_state, self.net_state,
             score, health) = self._train_step(
                self.params, self.updater_state, self.net_state,
                self.iteration, features, labels, fmasks, lmasks,
                self._rng_key)
            _monitor.health.record_dispatch(self, health, self.iteration)
            _monitor.observe_phase("step", time.perf_counter() - t1)
            self._score = score
            self.iteration += 1
            iters.inc()
            self._fire_listeners()

    # ------------------------------------------------ flat-param invariant
    def _flat_param_refs(self):
        """``(key, param name)`` in the flat vector's order: layer
        order, then the layer's ``param_order()``.  This order is the
        checkpoint format."""
        return [(key, name) for key, _, layer in self._layer_items()
                for name in layer.param_order()]

    def param_table(self) -> Dict[str, np.ndarray]:
        """Named params ``{"0_W": ..., "0_b": ...}`` (a graph:
        ``{"<vertex>_W": ...}``; reference ``paramTable()`` naming)."""
        from ..utils.device import fetch_all
        self.init()
        refs = self._flat_param_refs()
        # fetch_all: per-array synchronous np.asarray costs one full
        # host<->device round trip EACH (~320 arrays per StatsListener
        # post on ResNet-50).
        return dict(zip(
            (f"{key}_{name}" for key, name in refs),
            fetch_all([self.params[key][name] for key, name in refs])))

    def num_params(self) -> int:
        self.init()
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

    def get_flat_params(self) -> np.ndarray:
        """One contiguous vector over all params in deterministic layer/param
        order — the reference's single flat buffer (``init():396-470``)."""
        from ..utils.device import fetch_all
        self.init()
        chunks = [a.ravel() for a in fetch_all(
            [self.params[key][name]
             for key, name in self._flat_param_refs()])]
        if not chunks:
            return np.zeros((0,), np.float32)
        return np.concatenate(chunks)

    def set_flat_params(self, flat: np.ndarray) -> None:
        self.init()
        flat = np.asarray(flat)
        offset = 0
        for key, name in self._flat_param_refs():
            old = self.params[key][name]
            size = int(np.prod(old.shape))
            self.params[key][name] = jnp.asarray(
                flat[offset:offset + size].reshape(old.shape), old.dtype)
            offset += size
        if offset != flat.size:
            raise ValueError(
                f"Flat param size mismatch: expected {offset}, got {flat.size}")
        self._sync_masters_from_params()

    def _sync_masters_from_params(self) -> None:
        """Re-derive the fp32 masters from freshly-assigned params so the
        master/param coherence invariant holds after a direct param write
        (param averaging, solvers).  Checkpoint restore overwrites the
        masters afterwards with the exact saved fp32 values
        (set_flat_params runs before set_flat_updater_state)."""
        for key, _, _ in self._layer_items():
            tree = self.updater_state[key]
            if isinstance(tree, dict) and _updaters.MASTER_KEY in tree:
                tree[_updaters.MASTER_KEY] = {
                    k: jnp.asarray(self.params[key][k], jnp.float32)
                    for k in tree[_updaters.MASTER_KEY]}

    def get_flat_updater_state(self) -> np.ndarray:
        """Updater state as one flat vector (reference
        ``BaseUpdater.getStateViewArray`` -> ``updaterState.bin``)."""
        self.init()
        leaves = [np.asarray(leaf).ravel()
                  for key, _, _ in self._layer_items()
                  for leaf in jax.tree_util.tree_leaves(
                      self.updater_state[key])]
        if not leaves:
            return np.zeros((0,), np.float32)
        return np.concatenate(leaves)

    def set_flat_updater_state(self, flat: np.ndarray) -> None:
        self.init()
        flat = np.asarray(flat)
        offset = 0
        for key, _, _ in self._layer_items():
            leaves, treedef = jax.tree_util.tree_flatten(
                self.updater_state[key])
            new_leaves = []
            for leaf in leaves:
                size = int(np.prod(leaf.shape))
                new_leaves.append(jnp.asarray(
                    flat[offset:offset + size].reshape(leaf.shape),
                    leaf.dtype))
                offset += size
            self.updater_state[key] = jax.tree_util.tree_unflatten(
                treedef, new_leaves)

    # -------------------------------------------------------------- misc API
    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def add_listener(self, listener) -> None:
        self.listeners.append(listener)
