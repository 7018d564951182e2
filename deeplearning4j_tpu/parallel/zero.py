"""Cross-replica sharding of the weight update (ZeRO-1 style).

Implements the technique of "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training" (Xu et al., arXiv:2004.13336 — see
PAPERS.md): in data-parallel training the gradient all-reduce already
gives every replica identical gradients, so having every replica ALSO
apply the full weight update (and hold the full updater state) is
redundant.  Instead each replica updates only its 1/n shard of the flat
parameter vector — holding only that shard's updater state — and the
updated shards are re-assembled with an all-gather.  Updater-state
memory and update FLOPs drop n-fold; semantics are bit-identical to
replicated data parallelism.

TPU-first shape: the whole step (forward, backward, psum, sharded
update, all-gather) is ONE ``shard_map``-ed XLA program over the shared
:class:`~deeplearning4j_tpu.parallel.mesh.MeshRuntime` mesh; the
reference (2016 DL4J) has no analogue — its ParallelWrapper replicates
updater state per worker (``ParallelWrapper.java:199-224`` averages it,
this shards it).

Axis composition (DP x ZeRO): batches shard over the FLATTENED
``data x zero`` extent (every mesh slot is a batch replica), but the
updater state — moment rows and fp32 masters — shards over ``zero``
ONLY and is replicated over ``data``.  Per-process optimizer-state
residency therefore drops ~``1/zero_degree`` even when ``zero`` spans
OS processes (the paper's memory win at pod scale).  The legacy
``workers=w`` constructor maps to ``MeshRuntime.local(zero=w)``
(data=1), which reproduces the old single-axis semantics exactly.

Scope (raise, don't silently diverge): one network-wide updater config
(per-layer updater overrides would need per-element kind vectors),
no ``direct_update_params`` layers.  Per-layer l1/l2 and gradient
normalization ARE supported — both applied tree-wise before the flat
sharded update, in the replicated path's exact order (regularize, then
normalize, then the updater transform).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as P

from ..datasets.dataset import DataSet
from ..nn import updaters as U
from .mesh import MeshRuntime

Array = jax.Array


class ZeroShardedParallelWrapper:
    """Lockstep data parallelism with the weight update sharded across
    replicas (ZeRO-1).  API mirrors :class:`ParallelWrapper` for the
    ``averaging_frequency=1`` regime it replaces."""

    def __init__(self, model, workers: Optional[int] = None,
                 devices: Optional[list] = None,
                 runtime: Optional[MeshRuntime] = None):
        from ..nn.multilayer import MultiLayerNetwork
        if not isinstance(model, MultiLayerNetwork):
            raise ValueError("ZeRO sharding currently supports "
                             "MultiLayerNetwork")
        self.model = model
        model.init()
        if runtime is None:
            self.devices = devices if devices is not None else jax.devices()
            workers = workers or len(self.devices)
            if workers > len(self.devices):
                raise ValueError(
                    f"{workers} workers > {len(self.devices)} devices")
            # legacy single-axis semantics: every worker is a zero shard
            runtime = MeshRuntime.local(zero=workers, devices=self.devices)
        else:
            if runtime.pipe_degree != 1:
                raise ValueError(
                    "ZeRO sharding runs on the data x zero extent; got a "
                    f"runtime with pipe={runtime.pipe_degree}")
            self.devices = list(runtime.devices)
        self.runtime = runtime
        self.mesh = runtime.mesh
        # batch replicas = every data x zero slot; state shards = zero only
        self.workers = runtime.dp_degree
        self.zero_n = runtime.zero_degree
        self._dp = ("data", "zero")
        self._validate()
        self._build()

    # ---- scope checks (implement-or-raise) -------------------------------
    def _validate(self) -> None:
        net = self.model
        confs = [l.updater for l in net.layers]
        first = confs[0]
        if any(c != first for c in confs):
            raise ValueError(
                "ZeRO weight-update sharding needs ONE updater config "
                "network-wide; per-layer overrides found")
        for l in net.layers:
            if l.direct_update_params():
                raise ValueError(
                    f"layer {type(l).__name__} uses direct-update params "
                    f"(unsupported under ZeRO sharding)")
        if first.updater.lower() == "lars":
            raise ValueError(
                "lars computes per-TENSOR trust ratios; flat-slice "
                "sharding would break them — use replicated DP for lars")
        self.uconf = first

    # ---- static flat metadata --------------------------------------------
    def _build(self) -> None:
        net = self.model
        pol = net._pol()
        flat, self._unravel = ravel_pytree(net.params)
        self._flat_dtype = np.dtype(flat.dtype)
        # an fp32 twin of the unravel for state keys stored above the
        # param dtype (moments and masters under the mixed policy)
        _, self._unravel_f32 = ravel_pytree(jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), net.params))
        self.total = flat.shape[0]
        n = self.zero_n
        self.shard = -(-self.total // n)          # ceil
        self.padded = self.shard * n
        # state keys from the ONE source of truth (updaters.init_state),
        # so a new updater kind there automatically works here
        state_keys = U.init_state(self.uconf,
                                  jnp.zeros((1,), jnp.float32)).keys()
        sdtype = jnp.dtype(pol.updater_dtype)
        state = {k: np.zeros((n, self.shard), sdtype) for k in state_keys}
        self._masters = bool(
            pol.master_weights and self._flat_dtype.itemsize < 4)
        if self._masters:
            # the fp32 master shard IS part of the sharded state: each
            # replica owns 1/n of the masters, exactly the setting of the
            # cross-replica weight-update sharding paper (arXiv:2004.13336)
            state[U.MASTER_KEY] = np.pad(
                np.asarray(flat, dtype=np.float32),
                (0, self.padded - self.total)).reshape(n, self.shard)
        # per-zero-shard updater state: ONE shard each (the n-fold
        # saving), replicated over the data axis and — when zero spans
        # processes — resident only 1/n per process
        self._state = self.runtime.put_tree(state, P("zero"))
        self.runtime.publish_state_bytes(self._state, axis="zero")

    # ------------------------------------------------------------ the step
    @functools.cached_property
    def _step(self):
        net = self.model
        uconf = self.uconf
        zero_n = self.zero_n
        dp = self._dp
        shard, total, padded = self.shard, self.total, self.padded
        unravel = self._unravel

        def zero_step(params, state_shard, net_state, iteration,
                      features, labels, fmask, lmask, rng):
            # this replica's batch shard (leading worker axis of size 1)
            f = features[0]
            l = labels[0]
            fm = jax.tree.map(lambda a: a[0], fmask)
            lm = jax.tree.map(lambda a: a[0], lmask)
            state_shard = jax.tree.map(lambda a: a[0], state_shard)
            # reg score on the replicated params (stays invariant for the
            # P() out spec)
            reg = net._reg_score(params)
            # everything after the gradient psum (l1/l2, the slice the
            # update applies to) reads the REPLICATED copy, so the updated
            # slice and state vary over ``zero`` only and match their
            # P("zero") out specs; the copy cast below would leave them
            # varying over ``data`` as well
            shared_params = params
            # varying params -> per-replica grads + EXPLICIT pmean below
            # (unvarying params would make shard_map auto-psum the grads,
            # i.e. SUM not MEAN — the ParallelWrapper pattern)
            for ax in dp:
                params, net_state = lax.pcast((params, net_state), ax,
                                              to="varying")
            # combined batch-replica index over the flattened data x zero
            # extent (matches the legacy single-axis ordering when data=1)
            widx = lax.axis_index("data") * zero_n + lax.axis_index("zero")
            # which 1/zero_n slice of the flat update this slot owns —
            # identical across the data axis, so each update is computed
            # once per zero shard and the all-gather reassembles it
            zidx = lax.axis_index("zero")
            rng = jax.random.fold_in(rng, widx)    # decorrelate dropout
            (data_loss, aux), grads = jax.value_and_grad(
                net._loss_fn, has_aux=True)(
                    params, net_state, f, l, fm, lm, rng, True)
            new_net_state = aux[0] if isinstance(aux, tuple) else aux
            # masked losses are means over each shard's UNMASKED steps, so
            # the cross-shard fold must weight by mask count to equal the
            # big-batch mean (uniform pmean is exact only when unmasked)
            if lm is not None:
                wgt = jnp.sum(lm).astype(jnp.float32)
            elif fm is not None:
                wgt = jnp.sum(fm).astype(jnp.float32)
            else:
                wgt = jnp.float32(1.0)
            wsum = lax.psum(wgt, dp)
            grads = jax.tree.map(
                lambda g: lax.psum(g * wgt, dp) / wsum, grads)
            new_net_state = lax.pmean(new_net_state, dp)
            score = lax.psum(data_loss * wgt, dp) / wsum + reg
            # EXACT replicated-path order (updaters.apply_layer_updates):
            # l1/l2 into the grads FIRST, then per-layer normalization,
            # then the (sharded) updater transform
            grads = [
                U.regularize(g, p, layer.l1_by_param(),
                             layer.l2_by_param())
                for layer, p, g in zip(net.layers, shared_params, grads)]
            grads = [
                U.normalize_gradients(
                    g, layer.gradient_normalization,
                    layer.gradient_normalization_threshold)
                for layer, g in zip(net.layers, grads)]
            # frozen layers (transfer-learning feature extractors) take no
            # update on this path either — zero AFTER regularization so
            # l2 decay cannot leak into them
            grads = [jax.tree.map(jnp.zeros_like, g)
                     if getattr(layer, "frozen", False) else g
                     for layer, g in zip(net.layers, grads)]
            flat_g, _ = ravel_pytree(grads)
            flat_p, _ = ravel_pytree(shared_params)
            flat_g = jnp.pad(flat_g, (0, padded - total))
            flat_p_pad = jnp.pad(flat_p, (0, padded - total))
            start = zidx * shard
            my_g = lax.dynamic_slice(flat_g, (start,), (shard,))
            my_p = lax.dynamic_slice(flat_p_pad, (start,), (shard,))
            state_shard = dict(state_shard)
            master = state_shard.pop(U.MASTER_KEY, None)
            if master is not None:
                # mixed policy: updater math against the fp32 master shard,
                # one cast back to the storage dtype (cast-on-apply)
                my_g = my_g.astype(jnp.float32)
            updates, new_state = U.compute_update(
                uconf, my_g, state_shard, iteration)
            if master is not None:
                new_master = master - updates
                new_state[U.MASTER_KEY] = new_master
                new_slice = new_master.astype(my_p.dtype)
            else:
                new_slice = my_p - updates
            # each replica emits ONLY its slice; the out spec reassembles
            # the flat vector and XLA inserts the all-gather where the
            # next consumer needs it replicated
            new_state = jax.tree.map(lambda a: a[None], new_state)
            return new_slice, new_state, new_net_state, score

        sharded = jax.shard_map(
            zero_step, mesh=self.mesh,
            in_specs=(P(), P("zero"), P(), P(), P(dp), P(dp),
                      P(dp), P(dp), P()),
            out_specs=(P("zero"), P("zero"), P(), P()))

        replicated = self.runtime.sharding(P())

        def step(params, state, net_state, iteration, feats, labs,
                 fmask, lmask, rng):
            new_flat, new_state, new_net_state, score = sharded(
                params, state, net_state, iteration, feats, labs,
                fmask, lmask, rng)
            new_params = unravel(new_flat[:total])
            # pin the reassembled params to replicated: without this the
            # compiler may leave them zero-partitioned, and a
            # process-spanning pod could never fetch them whole
            # (get_flat_params / serialization / the parity SHA)
            new_params = jax.lax.with_sharding_constraint(
                new_params, replicated)
            return new_params, new_state, new_net_state, score

        return jax.jit(step, donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------ fit
    def fit(self, iterator, epochs: int = 1) -> "ZeroShardedParallelWrapper":
        w = self.workers
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            pending: List[DataSet] = []
            for ds in iterator:
                pending.append(ds)
                if len(pending) == w:
                    self._run_step(pending)
                    pending = []
            if pending:
                n = len(pending)
                for i in range(w - n):
                    pending.append(pending[i % n])
                self._run_step(pending)
        # keep the MODEL's per-layer updater state in sync so direct
        # net.fit / serialization resume correctly after ZeRO training
        # (the ParallelWrapper does the same sync each round)
        self._sync_model_state()
        return self

    def _sync_model_state(self) -> None:
        net = self.model
        if not self._state:
            return                      # stateless updater (sgd/none)
        if self.runtime.is_multiprocess:
            # the full state is not addressable from any one process;
            # pod checkpoints persist the sharded stack directly instead
            return
        per_key = {}
        for key, sharded in self._state.items():
            flat = np.asarray(sharded).reshape(-1)[:self.total]
            unravel = (self._unravel
                       if np.dtype(sharded.dtype) == self._flat_dtype
                       else self._unravel_f32)
            per_key[key] = unravel(jnp.asarray(flat))
        net.updater_state = [
            {key: per_key[key][i] for key in per_key}
            for i in range(len(net.layers))]

    def _run_step(self, batches: List[DataSet]) -> None:
        net = self.model
        b = min(ds.num_examples() for ds in batches)
        spec = P(self._dp)

        def stack(get):
            return self.runtime.put(np.stack(
                [np.asarray(get(ds))[:b] for ds in batches]), spec)

        def stack_masks(get):
            present = [get(ds) is not None for ds in batches]
            if not any(present):
                return None
            if not all(present):
                raise ValueError(
                    "Mixed mask presence across batches within one ZeRO "
                    "step; provide masks on all batches or none")
            return stack(get)

        feats = stack(lambda ds: ds.features)
        labs = stack(lambda ds: ds.labels)
        fmask = stack_masks(lambda ds: ds.features_mask)
        lmask = stack_masks(lambda ds: ds.labels_mask)
        rng = jax.random.fold_in(net._rng_key, net.iteration)
        (net.params, self._state, net.net_state, score) = self._step(
            net.params, self._state, net.net_state, net.iteration,
            feats, labs, fmask, lmask, rng)
        net.iteration += 1
        net._score = score
        self.runtime.publish_state_bytes(self._state, axis="zero")
        for listener in net.listeners:
            listener.iteration_done(net, net.iteration)

    # ---- introspection ----------------------------------------------------
    def state_elements_per_replica(self) -> int:
        """Updater-state elements each replica holds (the n-fold saving:
        replicated DP holds ``total`` per state tensor, this holds
        ``ceil(total/n)``)."""
        return sum(int(np.prod(v.shape[1:]))
                   for v in jax.tree_util.tree_leaves(self._state))
