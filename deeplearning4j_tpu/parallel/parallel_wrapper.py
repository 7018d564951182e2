"""ParallelWrapper: single-process multi-device data-parallel training.

TPU-native equivalent of the reference's
``deeplearning4j-scaleout-parallelwrapper/.../ParallelWrapper.java`` (1862
LoC): per-device worker threads (``Trainer`` at ``:597``), round-robin batch
dispatch (``:150-151``), barrier join, and **parameter averaging** every
``averagingFrequency`` iterations via ``Nd4j.averageAndPropagate`` (``:179``)
plus updater-state averaging (``:199-224``).

TPU-first design: the whole choreography — k local steps per worker followed
by cross-device parameter (and updater-state) averaging — compiles to ONE
XLA program via ``jax.shard_map`` over the pod's shared
:class:`~deeplearning4j_tpu.parallel.mesh.MeshRuntime` mesh (the legacy
``workers=``/``devices=`` constructor builds a local ``data=w`` runtime, so
single-process call sites are unchanged; pass ``runtime=`` to span
processes).  Worker replicas live on the flattened ``data x zero`` extent
of the global ``("data", "zero", "pipe")`` mesh:

- worker replica  -> mesh ``data`` axis slot (ICI neighbor, not a thread)
- round-robin     -> batch stacked (avg_freq, workers, per_worker_batch, ...)
                     and sharded over ``data``
- local steps     -> ``lax.scan`` over the avg_freq axis inside shard_map
- averageAndPropagate -> ``lax.pmean`` over ``data`` (XLA all-reduce on ICI)

``averaging_frequency=1`` reproduces the lockstep allreduce-SGD regime; >1
is the reference's local-SGD mode with identical semantics: workers step
INDEPENDENTLY (params averaged, not gradients — for non-linear updaters like
Adam this differs from grad-averaging, matching the reference exactly).
"""

from __future__ import annotations

import functools
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import monitor as _monitor
from .mesh import MeshRuntime
from ..datasets.dataset import DataSet
from ..nn.multilayer import MultiLayerNetwork

Array = jax.Array


class ParallelWrapper:
    """Builder + fit API mirroring the reference
    (``ParallelWrapper.Builder`` flags at ``ParallelWrapperMain.java:28-70``:
    ``--workers``, ``--averagingFrequency``, ``--averageUpdaters``,
    ``--reportScore``, ``--prefetchSize``)."""

    def __init__(self, model, workers: Optional[int] = None,
                 averaging_frequency: int = 1, average_updaters: bool = True,
                 report_score: bool = False, prefetch_size: int = 2,
                 devices: Optional[list] = None,
                 runtime: Optional[MeshRuntime] = None):
        from ..nn.computation_graph import ComputationGraph
        self.model = model
        self._is_graph = isinstance(model, ComputationGraph)
        if runtime is None:
            self.devices = devices if devices is not None else jax.devices()
            self.workers = workers or len(self.devices)
            if self.workers > len(self.devices):
                raise ValueError(
                    f"{self.workers} workers > {len(self.devices)} devices")
            runtime = MeshRuntime.local(data=self.workers,
                                        devices=self.devices)
        else:
            if runtime.pipe_degree != 1:
                raise ValueError(
                    "ParallelWrapper runs on the data x zero extent; got "
                    f"a runtime with pipe={runtime.pipe_degree} (compose "
                    "pipeline via PipelineParallel)")
            self.devices = list(runtime.devices)
            # every data x zero slot is a DP worker replica
            self.workers = runtime.dp_degree
        self.runtime = runtime
        self.averaging_frequency = max(1, averaging_frequency)
        self.average_updaters = average_updaters
        self.report_score = report_score
        self.prefetch_size = prefetch_size
        self.mesh = runtime.mesh
        self._dp = ("data", "zero")  # the flattened worker extent
        self.listeners: List[Any] = []
        self._worker_ustate = None  # stacked (workers, ...) across rounds
        self.skipped_tail_batches = 0  # stragglers left unfitted (ref parity)

    # -- builder-style API (reference ParallelWrapper.Builder) -------------
    class Builder:
        def __init__(self, model):
            self._model = model
            self._kw = {}

        def workers(self, n: int) -> "ParallelWrapper.Builder":
            self._kw["workers"] = int(n)
            return self

        def averaging_frequency(self, k: int) -> "ParallelWrapper.Builder":
            self._kw["averaging_frequency"] = int(k)
            return self

        def average_updaters(self, flag: bool) -> "ParallelWrapper.Builder":
            self._kw["average_updaters"] = flag
            return self

        def report_score_after_averaging(self, flag: bool
                                         ) -> "ParallelWrapper.Builder":
            self._kw["report_score"] = flag
            return self

        def prefetch_buffer(self, n: int) -> "ParallelWrapper.Builder":
            self._kw["prefetch_size"] = int(n)
            return self

        def build(self) -> "ParallelWrapper":
            return ParallelWrapper(self._model, **self._kw)

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    # ------------------------------------------------------------ the step
    @functools.cached_property
    def _parallel_step(self):
        """One averaging round: each worker runs avg_freq local train steps
        on its own batches, then params (and updater state) are pmean-ed.
        Single XLA program; collectives ride the mesh."""
        net = self.model
        avg_updaters = self.average_updaters
        # MultiLayerNetwork tBPTT config: each worker's local step runs
        # the same windowed program as single-device _fit_tbptt (window
        # slicing, carried recurrent state, back<fwd trunk truncation)
        # instead of full-sequence BPTT — required for the n-vs-1
        # equality guarantee on recurrent nets.
        tbptt = (not self._is_graph
                 and net.conf.backprop_type == "tbptt")
        from ..monitor import health as _health
        horder = list(net._layer_names()) if self._is_graph else None
        dp = self._dp  # worker extent: flattened ("data", "zero")
        zero_n = self.runtime.zero_degree

        def local_round(params, updater_state, net_state, iteration,
                        features, labels, fmask, lmask, base_rng, wire):
            # Global shapes: batches (avg_freq, workers, batch, ...) and
            # updater state (workers, ...); this worker's view carries a
            # leading worker axis of size 1 — drop it.  features/labels are
            # single arrays for MultiLayerNetwork, tuples of arrays for
            # ComputationGraph; masks are None (empty pytree) or shaped like
            # batches — the reference trains with full DataSet masks, so
            # they thread through to _loss_fn.
            features = jax.tree.map(lambda a: a[:, 0], features)
            labels = jax.tree.map(lambda a: a[:, 0], labels)
            fmask = jax.tree.map(lambda a: a[:, 0], fmask)
            lmask = jax.tree.map(lambda a: a[:, 0], lmask)
            updater_state = jax.tree.map(lambda a: a[0], updater_state)
            # Combined worker index over the flattened data x zero extent
            # (lax.axis_index takes a single name on this JAX).  Row-major
            # over the mesh layout, so rng streams match the legacy
            # one-axis ("data",) mesh ordering for any (data, zero) split.
            widx = lax.axis_index("data") * zero_n + lax.axis_index("zero")
            # Mark replicated state as device-varying: each worker steps its
            # own copy independently.  Without this, shard_map's replication
            # tracking auto-psums gradients taken w.r.t. unvarying params
            # (allreduce-SGD), which is NOT the reference's local-step-then-
            # average semantics.
            for ax in dp:
                params, net_state = lax.pcast((params, net_state), ax,
                                              to="varying")

            def one_step(carry, batch):
                from ..nn import ingest
                params, updater_state, net_state, it = carry
                f, l, fm, lm = batch
                # uint8 wire staging: batches crossed the host->device
                # link at 1 byte/pixel; the affine decode fuses here
                # (a graph: one spec for each input)
                f = ingest.device_decode(f, wire)
                if tbptt:
                    # the single-device windowed program, per worker:
                    # slice tbptt_fwd_length windows, carry recurrent
                    # state, stop gradients at window boundaries
                    # (back<fwd trunk truncation included via
                    # _tbptt_window_loss); iteration advances per window
                    window = net.conf.tbptt_fwd_length
                    back = net.conf.tbptt_back_length or window
                    T = f.shape[1]
                    carries = net._init_carries(f.shape[0])
                    score = jnp.float32(0.0)
                    params0, ustate0, state0 = (params, updater_state,
                                                net_state)
                    for start in range(0, T, window):
                        stop = min(start + window, T)
                        adv = max(0, (stop - start) - back)
                        fm_w = None if fm is None else fm[:, start:stop]
                        lm_w = None if lm is None else lm[:, start:stop]
                        rng = jax.random.fold_in(
                            jax.random.fold_in(base_rng, it), widx)
                        wloss = net._tbptt_window_loss(adv, carries)
                        (data_loss, (net_state, carries)), grads = \
                            jax.value_and_grad(wloss, has_aux=True)(
                                params, net_state, f[:, start:stop],
                                l[:, start:stop], fm_w, lm_w, rng)
                        params, updater_state = net._apply_updates(
                            params, updater_state, grads, it)
                        score = data_loss + net._reg_score(params)
                        it = it + 1
                    # tBPTT health is coarse: one vector for the whole
                    # batch (pre-loop params vs post-loop params, last
                    # window's grads/loss), guarded at batch granularity.
                    hvec, bad = _health.layer_stats(
                        params0, params, grads, data_loss, order=horder)
                    params, updater_state, net_state = \
                        _health.guard_select(
                            bad, (params, updater_state, net_state),
                            (params0, ustate0, state0))
                    return ((params, updater_state, net_state, it),
                            (score, hvec))
                rng = jax.random.fold_in(
                    jax.random.fold_in(base_rng, it), widx)
                (data_loss, aux), grads = jax.value_and_grad(
                    net._loss_fn, has_aux=True)(
                        params, net_state, f, l, fm, lm, rng, True)
                # MLN aux is (state, carries); CG aux is the state dict
                new_state = aux[0] if isinstance(aux, tuple) else aux
                new_params, new_ustate = net._apply_updates(
                    params, updater_state, grads, it)
                score = data_loss + net._reg_score(params)
                hvec, bad = _health.layer_stats(
                    params, new_params, grads, data_loss, order=horder)
                new_params, new_ustate, new_state = _health.guard_select(
                    bad, (new_params, new_ustate, new_state),
                    (params, updater_state, net_state))
                return ((new_params, new_ustate, new_state, it + 1),
                        (score, hvec))

            ((params, updater_state, net_state, _),
             (scores, hstack)) = lax.scan(
                one_step, (params, updater_state, net_state, iteration),
                (features, labels, fmask, lmask))
            # averageAndPropagate: params always, updater state if enabled
            params = lax.pmean(params, dp)
            if avg_updaters:
                updater_state = lax.pmean(updater_state, dp)
                for ax in dp:
                    updater_state = lax.pcast(updater_state, ax,
                                              to="varying")
            net_state = lax.pmean(net_state, dp)
            score = lax.pmean(jnp.mean(scores), dp)
            # Mean across workers: a single worker's NaN poisons the
            # averaged vector and the 0/1 flag column stays > 0 iff any
            # worker flagged — the pmean'd stack still decodes.
            health = lax.pmean(hstack, dp)
            # updater state stays per-worker (stacked) across rounds
            updater_state = jax.tree.map(lambda a: a[None], updater_state)
            return params, updater_state, net_state, score, health

        mesh = self.mesh
        in_specs = (P(), P(dp), P(), P(), P(None, dp),
                    P(None, dp), P(None, dp), P(None, dp), P(),
                    P())
        out_specs = (P(), P(dp), P(), P(), P())
        fn = jax.shard_map(local_round, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs)
        return _monitor.watched_jit(fn, name="parallel.step",
                                    donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------ fit
    def fit(self, iterator, epochs: int = 1) -> "ParallelWrapper":
        """Reference ``fit(DataSetIterator):322``: round-robin dispatch of
        minibatches to workers, averaging every ``averaging_frequency``
        per-worker iterations.

        With ``prefetch_buffer(n) > 0`` the host side of each round
        (minibatch stacking + ``device_put`` staging) runs on a
        background thread, up to ``n`` rounds ahead of the round
        currently executing — round k+1 stages while round k's
        ``shard_map`` program runs (the reference's ``prefetchSize``
        MagicQueue role).  ``prefetch_buffer(0)`` restores the fully
        synchronous path.
        """
        import collections
        from concurrent.futures import ThreadPoolExecutor

        net = self.model
        net.init()
        k, w = self.averaging_frequency, self.workers
        rounds_run = 0
        self.skipped_tail_batches = 0
        prefetch = max(0, int(self.prefetch_size or 0))
        executor = (ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pw-prefetch")
            if prefetch else None)
        staged: "collections.deque" = collections.deque()
        try:
            for _ in range(epochs):
                if hasattr(iterator, "reset"):
                    iterator.reset()
                pending: List[DataSet] = []
                for ds in iterator:
                    pending.append(ds)
                    if len(pending) == k * w:
                        if executor is not None:
                            staged.append(executor.submit(
                                self._stage_round, pending))
                            _monitor.gauge(
                                "parallel_prefetch_depth",
                                "rounds staged ahead of dispatch").set(
                                len(staged))
                            if len(staged) > prefetch:
                                self._dispatch_staged(staged.popleft())
                                rounds_run += 1
                        else:
                            self._run_round(pending)
                            rounds_run += 1
                        pending = []
                # Tail: an incomplete round is left unfitted, matching the
                # reference exactly (``ParallelWrapper.java:150-165``
                # dispatches only full worker groups; stragglers never
                # reach a Trainer).  Padding the round with duplicated
                # batches would give tail examples extra gradient weight;
                # a smaller round would force an XLA recompile for one
                # step.  Stragglers are counted so callers can size
                # iterators to workers*averaging_frequency.
                self.skipped_tail_batches += len(pending)
            while staged:
                self._dispatch_staged(staged.popleft())
                rounds_run += 1
        finally:
            # on error, surface staged rounds' exceptions but never leak
            # the prefetch thread
            while staged:
                staged.popleft().cancel()
            if executor is not None:
                executor.shutdown(wait=True)
        if self.skipped_tail_batches:
            _monitor.counter(
                "parallel_skipped_tail_batches_total",
                "straggler batches dropped by incomplete averaging "
                "rounds").inc(self.skipped_tail_batches)
        if rounds_run == 0:
            import warnings
            warnings.warn(
                f"ParallelWrapper.fit trained NOTHING: the iterator yielded "
                f"fewer than workers*averaging_frequency = {w * k} batches "
                f"per epoch ({self.skipped_tail_batches} straggler batches "
                f"dropped across {epochs} epoch(s)). Use a bigger dataset, "
                f"fewer workers, or a smaller averaging_frequency.",
                stacklevel=2)
        return self

    def _run_round(self, batches: List[DataSet]) -> None:
        with _monitor.span("parallel/round", workers=self.workers,
                           steps=self.averaging_frequency):
            self._dispatch_round(self._stage_round(batches))

    def _dispatch_staged(self, future) -> None:
        """Dispatch one background-staged round (prefetch path): block on
        the staging future, then run the shard_map program."""
        with _monitor.span("parallel/round", workers=self.workers,
                           steps=self.averaging_frequency, prefetched=True):
            with _monitor.span("parallel/data_wait"):
                staged = future.result()
            self._dispatch_round(staged)

    def _stage_round(self, batches: List[DataSet]):
        """Host side of a round: stack the k*w minibatches into the
        (k, w, b, ...) layout and stage them onto the mesh with
        ``device_put``.  Runs on the prefetch thread when
        ``prefetch_size > 0`` — overlapping the previous round's device
        compute — and returns the staged pytrees for
        ``_dispatch_round``."""
        net = self.model
        k, w = self.averaging_frequency, self.workers
        t0 = time.perf_counter()
        b = min(ds.num_examples() for ds in batches)

        def stack(get):
            # (k, w, b, ...): scan axis k outside, worker axis w sharded.
            return np.stack([
                np.stack([np.asarray(get(batches[j * w + i]))[:b]
                          for i in range(w)])
                for j in range(k)])

        def stack_masks(get):
            # Masks are optional; a round must be uniform (the reference
            # trains every minibatch with its own masks — a mixed round
            # can't compile to one static-shape XLA program).
            present = [get(ds) is not None for ds in batches]
            if not any(present):
                return None
            if not all(present):
                raise ValueError(
                    "Mixed mask presence across batches within one "
                    "averaging round; provide masks on all batches or none")
            return stack(get)

        from ..datasets.dataset import wire_enabled, wire_of
        from ..nn import ingest as _ingest
        # bf16 policy: float features cross the host->device wire in the
        # compute dtype (half the staging bytes); the forward pass would
        # apply the identical cast on device anyway (nn/precision.py)
        cdt = net._pol().compute_name
        wire = None
        if self._is_graph:
            from ..nn.computation_graph import _as_multi
            batches = [_as_multi(ds) for ds in batches]
            n_in = len(batches[0].features)
            n_out = len(batches[0].labels)
            mwires = [getattr(m, "_wires", None) for m in batches]
            feats_list, specs = [], []
            for s in range(n_in):
                wired = (wire_enabled()
                         and all(mw is not None and len(mw) > s
                                 and mw[s] is not None for mw in mwires)
                         and len({mw[s][1] for mw in mwires}) == 1
                         and all(mw[s][0].shape == np.shape(m.features[s])
                                 for mw, m in zip(mwires, batches)))
                if wired:
                    feats_list.append(stack(lambda m, s=s: m._wires[s][0]))
                    specs.append(mwires[0][s][1].as_tuple())
                else:
                    feats_list.append(_ingest.cast_for_transfer(
                        stack(lambda m, s=s: m.features[s]), cdt))
                    specs.append(None)
            feats = tuple(feats_list)
            if any(x is not None for x in specs):
                wire = tuple(specs)
            labs = tuple(stack(lambda m, s=s: m.labels[s])
                         for s in range(n_out))
            fmask = tuple(stack_masks(
                lambda m, s=s: None if m.features_masks is None
                else m.features_masks[s]) for s in range(n_in))
            lmask = tuple(stack_masks(
                lambda m, s=s: None if m.labels_masks is None
                else m.labels_masks[s]) for s in range(n_out))
            if all(m is None for m in fmask):
                fmask = None
            if all(m is None for m in lmask):
                lmask = None
        else:
            ws = [wire_of(ds) for ds in batches]
            if (wire_enabled() and all(x is not None for x in ws)
                    and len({x[1] for x in ws}) == 1
                    and all(x[0].shape == np.shape(ds.features)
                            for x, ds in zip(ws, batches))):
                feats = stack(lambda ds: wire_of(ds)[0])
                wire = ws[0][1].as_tuple()
            else:
                feats = _ingest.cast_for_transfer(
                    stack(lambda ds: ds.features), cdt)
            labs = stack(lambda ds: ds.labels)
            fmask = stack_masks(lambda ds: ds.features_mask)
            lmask = stack_masks(lambda ds: ds.labels_mask)
        # shard the worker axis (axis 1) over the flattened data x zero
        # extent; runtime.put_tree stages process-spanning shardings via
        # make_array_from_callback where plain device_put cannot
        spec = P(None, self._dp)
        feats = self.runtime.put_tree(feats, spec)
        labs = self.runtime.put_tree(labs, spec)
        if fmask is not None:
            fmask = self.runtime.put_tree(fmask, spec)
        if lmask is not None:
            lmask = self.runtime.put_tree(lmask, spec)
        _monitor.gauge(
            "ingest_staged_bytes",
            "bytes uploaded to the device per staging event").set(
            sum(a.nbytes for a in jax.tree_util.tree_leaves((feats, labs))),
            path="parallel")
        _monitor.observe_phase("data", time.perf_counter() - t0)
        return feats, labs, fmask, lmask, wire

    def _dispatch_round(self, staged) -> None:
        """Device side of a round: run the fused local-steps + pmean
        shard_map program on an already-staged round and fold the results
        back into the model."""
        net = self.model
        k, w = self.averaging_frequency, self.workers
        feats, labs, fmask, lmask, wire = staged
        if self._worker_ustate is None:
            # Replicate the model's updater state to every worker (the
            # reference's per-worker model replication at Trainer start).
            self._worker_ustate = self.runtime.put_tree(
                jax.tree.map(
                    lambda a: np.broadcast_to(np.asarray(a),
                                              (w,) + np.shape(a)),
                    net.updater_state),
                P(self._dp))
        t1 = time.perf_counter()
        (net.params, self._worker_ustate, net.net_state,
         score, health) = self._parallel_step(
            net.params, self._worker_ustate, net.net_state,
            net.iteration, feats, labs, fmask, lmask, net._rng_key, wire)
        _monitor.health.record_dispatch(net, health, net.iteration)
        _monitor.observe_phase("step", time.perf_counter() - t1)
        _monitor.counter("parallel_rounds_total",
                         "parameter-averaging rounds (one pmean sync "
                         "each)").inc()
        _monitor.counter("parallel_worker_steps_total",
                         "per-replica local train steps across all "
                         "workers").inc(k * w)
        # Keep the model's own updater state in sync (worker 0's replica —
        # identical across workers when average_updaters is on).  When the
        # worker extent spans processes, row 0 may not be addressable here;
        # pod checkpoints read the sharded stack directly instead.
        if not self.runtime.is_multiprocess:
            net.updater_state = jax.tree.map(lambda a: a[0],
                                             self._worker_ustate)
        self.runtime.publish_state_bytes(self._worker_ustate, axis="data")
        net.iteration += k
        net._score = score
        self.last_score = float(score) if self.report_score else None
        t2 = time.perf_counter()
        for listener in self.listeners + net.listeners:
            listener.iteration_done(net, net.iteration)
        if self.listeners or net.listeners:
            _monitor.observe_phase("listener", time.perf_counter() - t2)

    # ------------------------------------------------------------ shutdown
    def shutdown(self) -> None:
        """Reference API parity (threads to stop there; nothing here)."""

    def __enter__(self) -> "ParallelWrapper":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
