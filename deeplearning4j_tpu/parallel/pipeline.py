"""Pipeline parallelism (GPipe-style) over the pod mesh's ``pipe`` axis.

The 2016 reference has no pipeline parallelism (its only axis is data
parallelism); this is the TPU-native pipeline tier completing the
portfolio (dp: ``parallel_wrapper``/``zero``, tp: GSPMD shardings,
sp: ``sequence``, pp: here).

Design: the layer stack is partitioned into S contiguous stages; a
minibatch is split into M microbatches; inside ONE ``shard_map``-ed XLA
program over the shared :class:`~deeplearning4j_tpu.parallel.mesh.MeshRuntime`
mesh's ``pipe`` axis, a ``lax.scan`` runs ``M + S - 1``
ticks.  At tick t, stage s processes microbatch ``t - s`` (when in
range): stage 0 feeds fresh microbatches, every stage hands its
activation to stage s+1 via ``lax.ppermute``, and the last stage's
outputs are collected tick by tick.  Each device executes ONLY its
stage's layers per tick (``lax.switch`` on the stage index), so the S
stages compute concurrently on different microbatches — the classic
pipeline overlap.  Activations crossing stage boundaries are padded to
one common width (ppermute needs a uniform shape), sliced per stage.

Backward: ``jax.grad`` differentiates straight through the scan +
ppermute + switch — the transposed program IS the reverse pipeline
(cotangents flow stage s+1 -> s via the transposed ppermute), so the
train step needs no hand-written schedule.  Gradients for each stage's
params are produced on that stage and (auto-psum over the unvarying
params) summed across the mesh, where non-owning stages contribute
exact zeros.

Scope: feed-forward stacks with 2-D (batch, features) activations
between stages (Dense/Output families — pipeline boundaries inside
conv/rnn blocks would need per-boundary shape plumbing); raise
otherwise.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..datasets.dataset import DataSet
from .mesh import MeshRuntime

Array = jax.Array


def partition_stages(layers: Sequence, params: Sequence,
                     n_stages: int) -> List[Tuple[int, int]]:
    """Contiguous [start, end) layer ranges balanced by parameter count
    (the usual pipeline partitioner heuristic)."""
    counts = [sum(int(np.prod(v.shape)) for v in p.values()) or 1
              for p in params]
    total = sum(counts)
    bounds = [0]
    acc = 0
    for i, c in enumerate(counts):
        acc += c
        # close the current stage once it holds its fair share, keeping
        # enough layers for the remaining stages
        remaining_stages = n_stages - len(bounds)
        remaining_layers = len(counts) - (i + 1)
        if (acc >= total * len(bounds) / n_stages
                and remaining_layers >= remaining_stages):
            bounds.append(i + 1)
            if len(bounds) == n_stages:
                break
    while len(bounds) < n_stages:
        bounds.append(bounds[-1] + 1)
    bounds.append(len(counts))
    return [(bounds[i], bounds[i + 1]) for i in range(n_stages)]


class PipelineParallel:
    """GPipe-style trainer: ``PipelineParallel(net, stages=4,
    microbatches=8).fit(iterator)``.

    The model's layers are split across ``stages`` mesh devices; every
    ``fit`` minibatch is cut into ``microbatches`` and streamed through
    the pipeline in one jitted step (forward, reverse-pipeline backward,
    updater).
    """

    def __init__(self, model, stages: Optional[int] = None,
                 microbatches: int = 4, devices: Optional[list] = None,
                 runtime: Optional[MeshRuntime] = None):
        from ..nn.multilayer import MultiLayerNetwork
        if not isinstance(model, MultiLayerNetwork):
            raise ValueError("PipelineParallel supports MultiLayerNetwork")
        self.model = model
        model.init()
        if runtime is None:
            self.devices = devices if devices is not None else jax.devices()
            self.stages = stages or len(self.devices)
            if self.stages > len(self.devices):
                raise ValueError(
                    f"{self.stages} stages > {len(self.devices)} devices")
            runtime = MeshRuntime.local(pipe=self.stages,
                                        devices=self.devices)
        else:
            if runtime.data_degree != 1 or runtime.zero_degree != 1:
                raise ValueError(
                    "PipelineParallel runs on the pipe axis; got a runtime "
                    f"with data={runtime.data_degree} "
                    f"zero={runtime.zero_degree} (compose DP via "
                    "ParallelWrapper/ZeroShardedParallelWrapper)")
            self.devices = list(runtime.devices)
            self.stages = runtime.pipe_degree
        if self.stages > len(model.layers):
            raise ValueError(
                f"{self.stages} stages > {len(model.layers)} layers")
        self.runtime = runtime
        self.microbatches = microbatches
        self.mesh = runtime.mesh
        self._validate()
        self.ranges = partition_stages(model.layers, model.params,
                                       self.stages)

    def _validate(self) -> None:
        net = self.model
        from ..nn.layers.base import FeedForwardLayerConfig
        for layer in net.layers:
            if not isinstance(layer, FeedForwardLayerConfig):
                raise ValueError(
                    f"pipeline stages need 2-D feed-forward activations "
                    f"with explicit n_in/n_out; layer "
                    f"{type(layer).__name__} is not feed-forward")
            if layer.dropout:
                raise ValueError(
                    "dropout inside pipeline stages is not supported yet "
                    "(per-stage rng plumbing)")
        for state in net.net_state:
            if state:
                raise ValueError(
                    "stateful layers (batch-norm running stats) are not "
                    "supported inside pipeline stages yet")
        if net.conf.input_preprocessors:
            raise ValueError("input preprocessors inside the stack are "
                             "not supported across pipeline boundaries")
        out_layer = net.layers[-1]
        if getattr(out_layer, "NEEDS_INPUT_FOR_SCORE", False):
            raise ValueError(
                f"{type(out_layer).__name__} scores against its input "
                f"features (compute_score_with_input); not supported "
                f"inside pipeline stages")
        gconf = net.conf.conf
        if getattr(gconf, "num_iterations", 1) not in (None, 1):
            raise ValueError("num_iterations > 1 is not supported under "
                             "pipeline parallelism")
        algo = (getattr(gconf, "optimization_algo", None)
                or "stochastic_gradient_descent").lower()
        if algo != "stochastic_gradient_descent":
            raise ValueError(f"optimization_algo {algo!r} (line-search "
                             "solvers) is not supported under pipeline "
                             "parallelism")

    # ---- stage functions --------------------------------------------------
    def _boundary_widths(self) -> List[int]:
        """Activation width entering each stage (and the final output)."""
        net = self.model
        widths = []
        for start, _ in self.ranges:
            layer = net.layers[start]
            widths.append(int(layer.n_in))
        out_layer = net.layers[-1]
        widths.append(int(out_layer.n_out))
        return widths

    # ------------------------------------------------------------ the step
    @functools.cached_property
    def _step(self):
        net = self.model
        S = self.stages
        M = self.microbatches
        ranges = self.ranges
        widths = self._boundary_widths()
        W = max(widths)                     # common ppermute width
        out_width = widths[-1]

        def stage_fn(s: int):
            start, end = ranges[s]
            in_w = widths[s]
            out_w = widths[s + 1]

            def fn(params, x):
                x = x[:, :in_w]
                for i in range(start, end):
                    layer = net.layers[i]
                    if i == len(net.layers) - 1:
                        # output layer contributes its PRE-activation so
                        # the loss fuses softmax/sigmoid stably
                        x = layer.pre_output(params[i], x)
                    else:
                        x, _ = layer.forward(params[i], net.net_state[i],
                                             x, train=True, rng=None)
                pad = W - out_w
                return jnp.pad(x, ((0, 0), (0, pad))) if pad else x
            return fn

        stage_fns = [stage_fn(s) for s in range(S)]

        def pipeline_loss(params, x_mb, y_mb):
            """Inside shard_map over the pipe axis: x_mb (M, mb, W) padded
            microbatch features, y_mb (M, mb, out_width) labels."""
            s = lax.axis_index("pipe")
            mb = x_mb.shape[1]

            def tick(buf, t):
                # stage 0 picks up fresh microbatch t; others read the
                # activation handed over from the left neighbor
                fresh = x_mb[jnp.clip(t, 0, M - 1)]
                x_in = jnp.where(s == 0, fresh, buf)
                y = lax.switch(s, stage_fns, params, x_in)
                my_mb = t - s
                active = (my_mb >= 0) & (my_mb < M)
                y = jnp.where(active, y, 0.0)
                handed = lax.ppermute(y, "pipe",
                                      [(i, (i + 1) % S) for i in range(S)])
                # collect the LAST stage's finished microbatch
                out_t = jnp.where((s == S - 1) & active, y, 0.0)
                out_t = lax.psum(out_t, "pipe")
                return handed, out_t

            buf0 = jnp.zeros((mb, W), x_mb.dtype)
            _, outs = lax.scan(tick, buf0, jnp.arange(M + S - 1))
            # microbatch j finishes at tick j + S - 1
            preout = outs[S - 1:, :, :out_width]          # (M, mb, out)
            out_layer = net.layers[-1]
            average = bool(getattr(net.conf.conf, "mini_batch", True))
            losses = [
                out_layer.compute_score(y_mb[j], preout[j], None, average)
                for j in range(M)]
            # equal-size microbatches: mean of per-microbatch means ==
            # full-batch mean; sums just add (mini_batch=False)
            return sum(losses) / M if average else sum(losses)

        def train_step(params, updater_state, iteration, x_mb, y_mb):
            loss, grads = jax.value_and_grad(pipeline_loss)(
                params, x_mb, y_mb)
            # Gradient assembly under check_vma=False semantics: the
            # transpose of the out_t psum re-psums the cotangent, so each
            # device holds (S x true) grads for ITS stage's params and
            # zeros elsewhere.  psum collects the owner contributions
            # (others add zero) and the 1/S normalizes the inflation —
            # verified against serial grads for S=2 and S=4.
            grads = jax.tree.map(lambda g: lax.psum(g, "pipe") / S, grads)
            new_params, new_ustate = net._apply_updates(
                params, updater_state, grads, iteration)
            score = loss + net._reg_score(params)
            return new_params, new_ustate, score

        fn = jax.shard_map(
            train_step, mesh=self.mesh,
            in_specs=(P(),) * 5, out_specs=(P(), P(), P()),
            check_vma=False)
        return jax.jit(fn, donate_argnums=(0, 1))

    # ------------------------------------------------------------------ fit
    def fit(self, iterator, epochs: int = 1) -> "PipelineParallel":
        net = self.model
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            for ds in iterator:
                self._run_step(ds)
        return self

    def _run_step(self, ds: DataSet) -> None:
        net = self.model
        M = self.microbatches
        if ds.features_mask is not None or ds.labels_mask is not None:
            raise ValueError("masked DataSets are not supported under "
                             "pipeline parallelism (2-D activations only)")
        dtype = np.dtype(net.conf.conf.dtype)
        f = np.asarray(ds.features, dtype)
        y = np.asarray(ds.labels, dtype)
        b = f.shape[0]
        if b % M:
            raise ValueError(f"batch {b} not divisible by {M} microbatches")
        mb = b // M
        widths = self._boundary_widths()
        W = max(widths)
        x_mb = np.zeros((M, mb, W), dtype)
        x_mb[:, :, :f.shape[1]] = f.reshape(M, mb, -1)
        y_mb = y.reshape(M, mb, -1)
        (net.params, net.updater_state, score) = self._step(
            net.params, net.updater_state, net.iteration,
            jnp.asarray(x_mb), jnp.asarray(y_mb))
        net.iteration += 1
        net._score = score
        for listener in net.listeners:
            listener.iteration_done(net, net.iteration)
