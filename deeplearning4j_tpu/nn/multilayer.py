"""MultiLayerNetwork: sequential network container.

TPU-native equivalent of the reference's
``nn/multilayer/MultiLayerNetwork.java`` (2527 LoC): ``init():384-470``
(flat params + per-layer views), ``fit(DataSetIterator):976``,
``computeGradientAndScore:1805``, ``output:1519-1601``, ``score:1705``.

Architecture: the reference materializes layer objects holding views over one
flat parameter buffer, then drives per-layer ``activate``/``backpropGradient``
loops from a ``Solver``.  Here the entire inner loop — forward, loss,
backward (``jax.grad``), updater — is ONE jitted function, so XLA compiles
the whole train step into a single HLO graph executed on the TPU (the north
star in BASELINE.json).  Params/updater-state are pytrees; ``params()``
exposes the reference's flat-vector invariant via deterministic raveling.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import precision as _precision
from . import updaters as _updaters
from . import weights as _weights
from .. import monitor as _monitor
from .conf.neural_net_configuration import MultiLayerConfiguration
from .network import Network
from ..datasets.dataset import DataSet

Array = jax.Array


class MultiLayerNetwork(Network):
    """Sequential model: list of layer configs -> pure train/inference fns.
    The training path (step programs, ``fit``, the flat accessors) is
    ``network.Network``'s."""

    _jit_prefix = "mln"

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        self.params: List[Dict[str, Array]] = []
        self.net_state: List[Dict[str, Array]] = []
        self.updater_state: List[Dict[str, Any]] = []
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[Any] = []
        self._init_done = False
        self._score = float("nan")
        self._rng_key: Optional[jax.Array] = None
        self._rnn_carries = None
        self._rnn_carry_batch = -1
        self._pretrain_step_cache: Dict[int, Any] = {}
        self._pretrain_done = False
        self._tbptt_step_cache: Dict[int, Any] = {}
        self._decode_grow_cache: Dict[int, Any] = {}
        self._precision: Optional[_precision.PrecisionPolicy] = None

    # ------------------------------------------------------------------ init
    def init(self) -> "MultiLayerNetwork":
        """Initialize params/state (reference ``init():384-470``) from
        the seed's key, by the staged programs of ``_init_program``."""
        if self._init_done:
            return self
        _precision.publish(self._pol())
        key = jax.random.PRNGKey(self.conf.conf.seed)
        self._rng_key = key
        out = self._init_program(key)
        self.params, self.net_state, self.updater_state = out
        self._init_done = True
        return self

    @functools.cached_property
    def _init_program(self):
        """Per-layer keys, parameters, layer state, updater state and
        masters as jitted programs of the seed's key (two, staged so
        that the values are the leaf-by-leaf ones bit for bit:
        ``weights.init_programs``), served by the executable store where
        one is installed: a warm start loads them and derives nothing.
        ``_init_program.__wrapped__(key)`` is the leaf-by-leaf init."""
        pol = self._pol()
        dtype = jnp.dtype(pol.param_dtype)

        def init(key):
            keys = jax.random.split(key, len(self.layers) + 1)
            params = [
                layer.init_params(keys[i], dtype)
                for i, layer in enumerate(self.layers)
            ]
            net_state = [layer.init_state(dtype) for layer in self.layers]
            updater_state = [
                _updaters.init_state(
                    self._updater_conf(i),
                    _updaters.updatable_params(self.layers[i], params[i]),
                    policy=pol)
                for i in range(len(self.layers))
            ]
            return params, net_state, updater_state

        return _weights.init_programs(
            init, "mln.init",
            lambda part: _monitor.program_identity(self, part))

    def _updater_conf(self, i: int) -> _updaters.UpdaterConfig:
        return self.layers[i].updater or self.conf.conf.updater

    def _scope_name(self, i: int) -> str:
        """Layer ``i`` in the step program's scopes (``layer.<name>``,
        ``update.<name>``; ``monitor/device_trace.py``)."""
        return f"{i}_{type(self.layers[i]).__name__}"

    def _layer_items(self):
        return [(i, self._scope_name(i), layer)
                for i, layer in enumerate(self.layers)]

    @staticmethod
    def _inputs_of(arrays):
        """One input, one output: ``_loss_fn`` takes each bare."""
        if arrays is None:
            return None
        (only,) = arrays
        return only

    # --------------------------------------------------------------- forward
    def _forward(self, params, net_state, x, *, train: bool,
                 rng: Optional[jax.Array], mask=None, carries=None,
                 to_layer: Optional[int] = None, from_layer: int = 0,
                 preoutput_last: bool = False):
        """Compose preprocessors + layers (reference ``feedForwardToLayer``).

        Returns (out, new_state, new_carries).  ``mask`` is the per-timestep
        features mask (batch, time).  ``carries`` is a per-layer list of
        recurrent carries ((), for non-recurrent layers) used by tBPTT and
        ``rnn_time_step``; None runs every recurrent layer from zero state.
        With ``preoutput_last`` the final (output) layer contributes its
        pre-activation, letting the loss fuse softmax/sigmoid stably.
        ``from_layer`` starts composition mid-stack with ``x`` as that
        layer's input (the suffix path of the exact-tBPTT split).
        """
        from .layers.recurrent import BaseRecurrentLayer
        n = len(self.layers) if to_layer is None else to_layer + 1
        new_state = list(net_state)
        new_carries = list(carries) if carries is not None else [
            () for _ in self.layers]
        keys = (jax.random.split(rng, n) if rng is not None else [None] * n)
        pol = self._pol()
        compute_dtype = jnp.dtype(pol.compute_dtype)
        with _monitor.scope("precision", "cast"):
            if jnp.issubdtype(x.dtype, jnp.floating):
                # Cast inputs to the policy compute dtype (bfloat16 for
                # MXU-friendly matmuls under the TPU default); integer
                # inputs (embedding indices) pass through.
                x = x.astype(compute_dtype)
            if compute_dtype != jnp.dtype(pol.param_dtype):
                # Mixed compute: storage params stay in the param dtype;
                # compute sees a bfloat16 copy (XLA fuses the casts into
                # the matmul/conv).
                params = jax.tree.map(
                    lambda p: p.astype(compute_dtype)
                    if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        for i in range(from_layer, n):
            layer = self.layers[i]
            with _monitor.scope("layer", self._scope_name(i)):
                if i in self.conf.input_preprocessors:
                    x = self.conf.input_preprocessors[i](x)
                if preoutput_last and i == n - 1 \
                        and hasattr(layer, "pre_output"):
                    if layer.dropout and train:
                        x = layer.apply_dropout(x, train, keys[i])
                    x = layer.pre_output(params[i], x)
                elif (pol.downcasts_output and i == len(self.layers) - 1
                      and hasattr(layer, "pre_output")
                      and hasattr(layer, "_activate")):
                    # fp32 logits contract, head half: the output head's
                    # logits are cast to fp32 BEFORE the softmax/sigmoid
                    # so serving probabilities are fp32-exact, not
                    # bf16-rounded (bf16 softmax row sums wobble at the
                    # 1e-3 level).  Checked BEFORE the carries branch: a
                    # carried step (rnn_step / decode_step) must honor
                    # the same contract or N single-token calls drift
                    # from output() under mixed precision.  The only
                    # recurrent head with pre_output is RnnOutputLayer,
                    # whose carry is () — so skipping forward_seq leaves
                    # new_carries[i] correct.
                    x = layer.apply_dropout(x, train, keys[i])
                    x = layer._activate(
                        layer.pre_output(params[i], x).astype(jnp.float32))
                elif (carries is not None
                      and isinstance(layer, BaseRecurrentLayer)):
                    x, new_carries[i] = layer.forward_seq(
                        params[i], x, carries[i], train=train, rng=keys[i],
                        mask=mask)
                else:
                    x, new_state[i] = layer.forward(
                        params[i], net_state[i], x, train=train,
                        rng=keys[i], mask=mask)
        if pol.downcasts_output:
            # fp32 logits contract: every consumer (loss, softmax, metrics
            # accumulation, serving) sees fp32 even under bf16 storage so
            # Evaluation numbers never drift with the policy.
            with _monitor.scope("precision", "cast"):
                x = x.astype(jnp.float32)
        return x, new_state, new_carries

    # ----------------------------------------------------------------- loss
    def _loss_fn(self, params, net_state, features, labels, features_mask,
                 labels_mask, rng, train: bool, carries=None,
                 from_layer: int = 0, per_example: bool = False):
        """Data loss (+ new state, new carries).  Regularization is handled
        updater-side to match the reference order of operations (SURVEY.md §7
        hard part d); the reported score adds the reg term separately
        (``BaseLayer.calcL2``).  ``from_layer`` scores a mid-stack
        activation through the remaining layers (exact-tBPTT suffix).
        ``per_example`` returns the unreduced (batch,) score vector
        (reference ``computeScoreForExamples``)."""
        out_layer = self.layers[-1]
        if getattr(out_layer, "NEEDS_INPUT_FOR_SCORE", False):
            # Center-loss-style heads score against the layer *input* (the
            # penultimate features) as well as the preactivation.
            n = len(self.layers)
            x, new_state, new_carries = self._forward(
                params, net_state, features, train=train, rng=rng,
                mask=features_mask, carries=carries, to_layer=n - 2,
                from_layer=from_layer)
            if (n - 1) in self.conf.input_preprocessors:
                x = self.conf.input_preprocessors[n - 1](x)
            if out_layer.dropout and train:
                x = out_layer.apply_dropout(
                    x, train, jax.random.fold_in(rng, n - 1)
                    if rng is not None else None)
            with _monitor.scope("loss"):
                if per_example:
                    data_loss = \
                        out_layer.compute_score_examples_with_input(
                            params[n - 1], labels, x, labels_mask)
                else:
                    data_loss = out_layer.compute_score_with_input(
                        params[n - 1], labels, x, labels_mask,
                        average=self.conf.conf.mini_batch)
            return data_loss, (new_state, new_carries)
        preout, new_state, new_carries = self._forward(
            params, net_state, features, train=train, rng=rng,
            mask=features_mask, carries=carries, preoutput_last=True,
            from_layer=from_layer)
        if not hasattr(out_layer, "compute_score"):
            raise ValueError(
                "Last layer must be an output/loss layer to fit()")
        lmask = labels_mask
        if lmask is None and features_mask is not None and preout.ndim == 3:
            # Per-timestep output: the features mask doubles as the labels
            # mask (reference feedForwardMaskArray propagation).
            lmask = features_mask
        with _monitor.scope("loss"):
            if per_example:
                data_loss = out_layer.compute_score_examples(
                    labels, preout, lmask)
            else:
                data_loss = out_layer.compute_score(
                    labels, preout, lmask,
                    average=self.conf.conf.mini_batch)
        return data_loss, (new_state, new_carries)

    def _last_stateful_recurrent(self) -> int:
        """Index of the deepest layer carrying real recurrent state (-1 if
        none); the exact-tBPTT split point.  RnnOutputLayer-style
        time-distributed heads have an empty carry and sit in the suffix."""
        from .layers.recurrent import BaseRecurrentLayer
        last = -1
        for i, layer in enumerate(self.layers):
            if isinstance(layer, BaseRecurrentLayer) \
                    and layer.init_carry(1, jnp.float32) != ():
                last = i
        return last

    def _tbptt_window_loss(self, adv: int, carries):
        """Loss closure for ONE truncated-BPTT window with ``carries`` in
        (gradients stopped at the window boundary): ``loss(p, ns, f, l,
        fm, lm, r) -> (loss, (new_state, new_carries))``.  Shared by the
        single-device window step (:meth:`_tbptt_step_for`) and
        ``ParallelWrapper``'s per-worker round, so both train the exact
        same windowed program.

        ``adv`` > 0 reproduces the reference's ``tbptt_back_length <
        fwd`` semantics exactly (``LSTMHelpers`` truncated backward loop):
        the leading ``adv`` steps run through the recurrent trunk with
        stopped gradients, then score through the suffix layers normally —
        so layers above the last recurrent layer accumulate gradients from
        ALL window steps while the recurrent trunk sees only the trailing
        ``back`` steps, matching the reference's per-layer truncation.
        """
        last_rec = self._last_stateful_recurrent()
        carries = jax.lax.stop_gradient(carries)

        def loss(p, ns, f, l, fm, lm, r):
            if adv == 0:
                return self._loss_fn(p, ns, f, l, fm, lm, r, True,
                                     carries=carries)
            rA = rB = None
            if r is not None:
                rA = jax.random.fold_in(r, 0)
                rB = jax.random.fold_in(r, 1)
            fmA = None if fm is None else fm[:, :adv]
            # leading steps: recurrent trunk, gradients stopped
            trunk, _, mid = self._forward(
                p, ns, f[:, :adv], train=True, rng=rA, mask=fmA,
                carries=carries, to_layer=last_rec)
            trunk = jax.lax.stop_gradient(trunk)
            mid = jax.lax.stop_gradient(mid)
            lmA = None if lm is None else lm[:, :adv]
            lmB = None if lm is None else lm[:, adv:]
            loss_a, _ = self._loss_fn(
                p, ns, trunk, l[:, :adv], fmA, lmA, rA, True,
                from_layer=last_rec + 1)
            loss_b, aux = self._loss_fn(
                p, ns, f[:, adv:], l[:, adv:],
                None if fm is None else fm[:, adv:], lmB, rB,
                True, carries=mid)
            # Masked scores normalize by each segment's own mask
            # count; recombine so the window averages over the
            # TOTAL active steps, matching the adv == 0 path.
            eff_a = lmA if lmA is not None else fmA
            eff_b = (lmB if lmB is not None
                     else (None if fm is None else fm[:, adv:]))
            if (self.conf.conf.mini_batch and eff_a is not None
                    and eff_b is not None):
                ca = jnp.sum(eff_a)
                cb = jnp.sum(eff_b)
                total = (loss_a * ca + loss_b * cb) / \
                    jnp.maximum(ca + cb, 1.0)
                return total, aux
            return loss_a + loss_b, aux

        return loss

    def _tbptt_step_for(self, adv: int):
        """Truncated-BPTT window step (reference ``doTruncatedBPTT:1138``):
        one fwd+bwd+update over a ``tbptt_fwd_length`` window with carries
        in from the previous window, gradients stopped at the window
        boundary (window-loss semantics: :meth:`_tbptt_window_loss`).
        """
        if adv not in self._tbptt_step_cache:

            def step(params, updater_state, net_state, carries, iteration,
                     features, labels, features_mask, labels_mask,
                     base_rng):
                rng = (jax.random.fold_in(base_rng, iteration)
                       if base_rng is not None else None)
                loss = self._tbptt_window_loss(adv, carries)
                (data_loss, (new_state, new_carries)), grads = \
                    jax.value_and_grad(loss, has_aux=True)(
                        params, net_state, features, labels, features_mask,
                        labels_mask, rng)
                new_params, new_updater_state = self._apply_updates(
                    params, updater_state, grads, iteration)
                score = data_loss + self._reg_score(params)
                return (new_params, new_updater_state, new_state,
                        new_carries, score)

            self._tbptt_step_cache[adv] = _monitor.watched_jit(
                step, name=f"mln.tbptt_step_adv{adv}",
                donate_argnums=(0, 1, 2, 3))
        return self._tbptt_step_cache[adv]

    @functools.cached_property
    def _score_fn(self):
        def score(params, net_state, features, labels, features_mask,
                  labels_mask):
            data_loss, _ = self._loss_fn(params, net_state, features, labels,
                                         features_mask, labels_mask, None,
                                         False)
            return data_loss + self._reg_score(params)
        return _monitor.watched_jit(score, name="mln.score")

    @functools.cached_property
    def _output_fn(self):
        def run(params, net_state, features, features_mask):
            out, _, _ = self._forward(params, net_state, features,
                                      train=False, rng=None,
                                      mask=features_mask)
            return out
        return _monitor.watched_jit(run, name="mln.output")

    @functools.cached_property
    def _eval_argmax_fn(self):
        """Inference forward + argmax in one program: evaluation transfers
        int32 class indices, not (batch, classes) logits."""
        def run(params, net_state, features, features_mask):
            out, _, _ = self._forward(params, net_state, features,
                                      train=False, rng=None,
                                      mask=features_mask)
            return jnp.argmax(out, axis=-1).astype(jnp.int32)
        return _monitor.watched_jit(run, name="mln.eval_argmax")

    @functools.cached_property
    def _rnn_step_fn(self):
        """Streaming inference step (reference ``rnnTimeStep:2230``): forward
        with explicit carries in/out, jitted once and reused per step."""
        def run(params, net_state, carries, features):
            out, _, new_carries = self._forward(
                params, net_state, features, train=False, rng=None,
                carries=carries)
            return out, new_carries
        return _monitor.watched_jit(run, name="mln.rnn_step")

    @functools.cached_property
    def _decode_step_fn(self):
        """Autoregressive decode step: the ``rnn_step`` contract over
        generalized state trees (RNN carries AND KV-cache rings), under
        its own jit name so the serving sanitizer can budget
        ``serving.decode_step`` separately (one dispatch per token)."""
        def run(params, net_state, carries, features):
            out, _, new_carries = self._forward(
                params, net_state, features, train=False, rng=None,
                carries=carries)
            return out, new_carries
        return _monitor.watched_jit(run, name="mln.decode_step")

    def _decode_grow_fn(self, cache_len: int):
        """Jitted state-tree growth to a larger KV ring capacity — ONE
        dispatch per (shape, target) pair, cached like the tbptt steps,
        so a serving bucket hop costs exactly one extra dispatch."""
        from .layers.recurrent import BaseRecurrentLayer
        if cache_len not in self._decode_grow_cache:
            def grow(carries):
                return [
                    layer.grow_carry(carries[i], cache_len)
                    if (isinstance(layer, BaseRecurrentLayer)
                        and getattr(layer, "HAS_KV_RING", False))
                    else carries[i]
                    for i, layer in enumerate(self.layers)]
            self._decode_grow_cache[cache_len] = _monitor.watched_jit(
                grow, name="mln.decode_grow")
        return self._decode_grow_cache[cache_len]

    # -------------------------------------------------------------- pretrain
    def _pretrain_step(self, i: int):
        """Jitted one-batch unsupervised step for layer ``i``: forward the
        input through layers 0..i-1 (inference mode), stop the gradient, and
        apply the layer's ``pretrain_grads`` through the DL4J-order updater
        — all one XLA program (reference ``MultiLayerNetwork.pretrain:991``:
        per-layer fit with ``feedForwardToLayer`` input)."""
        if i not in self._pretrain_step_cache:
            layer = self.layers[i]
            uconf = self._updater_conf(i)

            def step(params, ustate_i, net_state, iteration, features,
                     base_rng):
                rng = jax.random.fold_in(base_rng, iteration)
                x, _, _ = self._forward(params, net_state, features,
                                        train=False, rng=None,
                                        to_layer=i - 1)
                if i in self.conf.input_preprocessors:
                    x = self.conf.input_preprocessors[i](x)
                x = jax.lax.stop_gradient(x)
                score, grads = layer.pretrain_grads(params[i], x, rng)
                grads = _updaters.regularize(grads, params[i],
                                             layer.l1_by_param(),
                                             layer.l2_by_param())
                grads = _updaters.normalize_gradients(
                    grads, layer.gradient_normalization,
                    layer.gradient_normalization_threshold)
                updates, new_ustate = _updaters.compute_update(
                    uconf, grads, ustate_i, iteration,
                    params={k: params[i][k] for k in grads})
                new_p = jax.tree.map(lambda p, u: p - u, params[i], updates)
                score = score + _updaters.regularization_score(
                    params[i], layer.l1_by_param(), layer.l2_by_param())
                return new_p, new_ustate, score

            self._pretrain_step_cache[i] = _monitor.watched_jit(
                step, name=f"mln.pretrain_step_layer{i}", donate_argnums=(1,))
        return self._pretrain_step_cache[i]

    def pretrain(self, data, epochs: int = 1) -> "MultiLayerNetwork":
        """Greedy layer-wise unsupervised pretraining of every pretrainable
        layer (VAE/AutoEncoder/RBM), in order (reference
        ``MultiLayerNetwork.pretrain:991``)."""
        self.init()
        if not isinstance(data, DataSet) and not hasattr(data, "reset"):
            data = list(data)  # one-shot iterable: each layer needs a pass
        for i, layer in enumerate(self.layers):
            if getattr(layer, "IS_PRETRAINABLE", False):
                self.pretrain_layer(i, data, epochs)
        # fit() must not re-run pretraining (and the flag serializes, so a
        # restored model doesn't re-pretrain over fine-tuned weights)
        self._pretrain_done = True
        return self

    def pretrain_layer(self, i: int, data,
                       epochs: int = 1) -> "MultiLayerNetwork":
        """Pretrain one layer (reference ``pretrainLayer``); non-pretrainable
        layers are skipped like the reference (no-op, not an error)."""
        self.init()
        layer = self.layers[i]
        if not getattr(layer, "IS_PRETRAINABLE", False):
            return self
        if getattr(layer, "frozen", False):
            return self          # frozen extractor: pretraining is a no-op
        step = self._pretrain_step(i)
        if isinstance(data, DataSet):
            data_iter: Sequence[DataSet] = [data]
        else:
            data_iter = data
        for _ in range(epochs):
            if hasattr(data_iter, "reset"):
                data_iter.reset()
            for ds in data_iter:
                features = jnp.asarray(ds.features)
                (self.params[i], self.updater_state[i],
                 score) = step(self.params, self.updater_state[i],
                               self.net_state, self.iteration, features,
                               self._rng_key)
                self._score = score
                self.iteration += 1
                self._fire_listeners()
        return self

    # ----------------------------------------------------------------- tBPTT
    def _fit_tbptt(self, features, labels, fmask, lmask) -> None:
        """Slice the time axis into tbptt_fwd_length windows, carrying
        recurrent state forward across windows (reference
        ``doTruncatedBPTT:1138`` + ``updateRnnStateWithTBPTTState:1187``).
        State is cleared at the start of each new minibatch."""
        self._require_carry_support("truncated BPTT")
        if labels.ndim < 3:
            raise ValueError(
                "Truncated BPTT needs per-timestep labels (batch, time, ...); "
                f"got shape {labels.shape}. Use standard backprop for "
                "sequence-level labels.")
        window = self.conf.tbptt_fwd_length
        back = self.conf.tbptt_back_length or window
        if back > window:
            raise ValueError(
                f"tbptt_back_length ({back}) > tbptt_fwd_length "
                f"({window}) is not meaningful")
        T = features.shape[1]
        carries = self._init_carries(features.shape[0])
        scores = []
        for start in range(0, T, window):
            stop = min(start + window, T)
            # back < fwd: loss covers the WHOLE window; the leading
            # fwd-back steps run the recurrent trunk gradient-stopped
            # (exact reference semantics — see _tbptt_step_for)
            adv = max(0, (stop - start) - back)
            sl = slice(start, stop)
            f = features[:, sl]
            l = labels[:, sl]
            fm = None if fmask is None else fmask[:, sl]
            lm = None if lmask is None else lmask[:, sl]
            t1 = time.perf_counter()
            (self.params, self.updater_state, self.net_state, carries,
             score) = self._tbptt_step_for(adv)(
                self.params, self.updater_state, self.net_state, carries,
                self.iteration, f, l, fm, lm, self._rng_key)
            _monitor.observe_phase("step", time.perf_counter() - t1)
            scores.append(score)
            self.iteration += 1
            _monitor.counter("train_iterations_total",
                             "supervised train iterations").inc()
            self._fire_listeners()
        self._score = scores[-1] if scores else self._score

    def _require_carry_support(self, what: str) -> None:
        """Bidirectional layers cannot carry state across time chunks
        (reference GravesBidirectionalLSTM.rnnTimeStep throws
        UnsupportedOperationException)."""
        from .layers.recurrent import BaseRecurrentLayer
        for i, layer in enumerate(self.layers):
            if (isinstance(layer, BaseRecurrentLayer)
                    and not layer.SUPPORTS_CARRY):
                raise ValueError(
                    f"Layer {i} ({type(layer).__name__}) does not support "
                    f"{what}: its backward pass needs the full sequence")

    def _init_carries(self, batch: int, cache_len: Optional[int] = None):
        """Zero recurrent carries, one entry per layer (() if stateless).
        ``cache_len`` overrides KV-ring capacities (the serving
        (batch, cache_len) bucket ladder); RNN carries ignore it."""
        from .layers.recurrent import BaseRecurrentLayer
        dtype = jnp.dtype(self._pol().compute_dtype)
        out = []
        for layer in self.layers:
            if not isinstance(layer, BaseRecurrentLayer):
                out.append(())
            elif cache_len is not None and getattr(layer, "HAS_KV_RING",
                                                   False):
                out.append(layer.init_carry(batch, dtype,
                                            cache_len=cache_len))
            else:
                out.append(layer.init_carry(batch, dtype))
        return out

    def has_kv_ring(self) -> bool:
        """Whether any layer carries a KV-cache ring (the decode-serving
        state class — chooses the ``serving.decode_step`` sanitizer
        scenario over ``serving.rnn_step``)."""
        return any(getattr(layer, "HAS_KV_RING", False)
                   for layer in self.layers)

    def max_cache_len(self) -> int:
        """Largest capacity of the KV rings that grow with a session (0
        without any) — the top of the serving cache-len bucket ladder."""
        return max((int(layer.cache_len) for layer in self.layers
                    if getattr(layer, "HAS_KV_RING", False)
                    and getattr(layer, "RING_GROWS", True)), default=0)

    # ------------------------------------------------------------- inference
    def output(self, features, train: bool = False,
               features_mask=None) -> np.ndarray:
        """Forward pass (reference ``output:1519-1601``; TEST mode: no
        dropout, BN running stats)."""
        self.init()
        fmask = None if features_mask is None else jnp.asarray(features_mask)
        out = self._output_fn(self.params, self.net_state,
                              jnp.asarray(features), fmask)
        return np.asarray(out)

    def compile_output(self, feature_shape, dtype=None, mask_shape=None,
                       mask_dtype=None, params=None, net_state=None):
        """AOT-compile the inference forward for ONE concrete input shape
        (``jit(...).lower().compile()`` through ``monitor.watched_jit``,
        so every warmed shape is counted in
        ``jit_compiles_total{fn="mln.output"}``).  This is the serving
        bucket-warmup primitive: the ``serving.InferenceEngine`` compiles
        one executable per (batch-bucket, timestep-bucket) up front and
        then dispatches with zero trace/compile work on the hot path.

        Returns the compiled executable; call it as
        ``compiled(params, net_state, features, features_mask)`` with
        arrays matching the lowered shapes exactly (pass ``None`` for the
        mask iff ``mask_shape`` was ``None``).  ``params``/``net_state``
        override the lowering operands — pass device-committed copies to
        pin the executable to a specific device (the serving worker-pool
        path).
        """
        self.init()
        if params is None:
            params = self.params
        if net_state is None:
            net_state = self.net_state
        dt = jnp.dtype(dtype if dtype is not None else self.conf.conf.dtype)
        aval = jax.ShapeDtypeStruct(tuple(int(d) for d in feature_shape),
                                    dt)
        maval = None
        if mask_shape is not None:
            mdt = jnp.dtype(mask_dtype if mask_dtype is not None else dt)
            maval = jax.ShapeDtypeStruct(
                tuple(int(d) for d in mask_shape), mdt)
        return self._output_fn.lower(params, net_state, aval,
                                     maval).compile()

    def feed_forward(self, features) -> List[np.ndarray]:
        """All layer activations (reference ``feedForward:655-747``)."""
        self.init()
        acts = []
        for i in range(len(self.layers)):
            x, _, _ = self._forward(self.params, self.net_state,
                                    jnp.asarray(features), train=False,
                                    rng=None, to_layer=i)
            acts.append(np.asarray(x))
        return acts

    # --------------------------------------------- rnn streaming state API
    def rnn_time_step(self, features) -> np.ndarray:
        """Stateful streaming inference (reference ``rnnTimeStep:2230``):
        feeds one or more timesteps, carrying hidden state between calls.
        2-D input (batch, features) is one timestep and returns
        (batch, n_out); 3-D input returns the full (batch, time, n_out)."""
        self.init()
        self._require_carry_support("rnn_time_step")
        x = jnp.asarray(features)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]
        if self._rnn_carries is None:
            self._rnn_carries = self._init_carries(x.shape[0])
            self._rnn_carry_batch = x.shape[0]
        elif self._rnn_carry_batch != x.shape[0]:
            # Reference throws DL4JInvalidInputException here — silently
            # resetting would discard state from a half-fed sequence.
            raise ValueError(
                f"rnn_time_step batch size {x.shape[0]} != stored state "
                f"batch size {self._rnn_carry_batch}; call "
                "rnn_clear_previous_state() between unrelated sequences")
        out, self._rnn_carries = self._rnn_step_fn(
            self.params, self.net_state, self._rnn_carries, x)
        out = np.asarray(out)
        return out[:, -1] if squeeze else out

    def rnn_stateless_step(self, carries, features, params=None,
                           net_state=None):
        """Explicit-carry streaming step (the re-entrant twin of
        :meth:`rnn_time_step`): advance the given carry pytree by the
        input timesteps and return ``(out, new_carries)`` WITHOUT
        touching the model's own hidden-state slot.  ``carries=None``
        starts from zero state.  This is what lets N concurrent serving
        sessions share one model instance (``serving.SessionCache``) —
        state lives with the caller, arrays stay on device, and each
        call is exactly ONE dispatch of the jitted
        ``mln.rnn_step`` program.

        3-D ``features`` only (``(batch, time, n_in)``); the session
        layer owns the 2-D squeeze convention.

        ``params``/``net_state`` override the weight operands (same
        shapes/dtypes, so the jitted step is a cache hit, never a
        recompile) — what lets a serving session stay pinned to the
        weight version its carries came from across a hot-swap
        (docs/DEPLOY.md).
        """
        self.init()
        self._require_carry_support("rnn_stateless_step")
        x = jnp.asarray(features)
        if x.ndim != 3:
            raise ValueError(
                f"rnn_stateless_step expects (batch, time, features), "
                f"got shape {x.shape}")
        if carries is None:
            carries = self._init_carries(int(x.shape[0]))
        return self._rnn_step_fn(
            self.params if params is None else params,
            self.net_state if net_state is None else net_state,
            carries, x)

    def decode_step(self, carries, features, params=None, net_state=None):
        """Autoregressive decode step: :meth:`rnn_stateless_step`
        generalized to arbitrary per-session state trees — RNN carries
        and KV-cache rings alike — under the ``mln.decode_step`` jit
        name.  Advance the state tree by the input timesteps and return
        ``(out, new_carries)``; N single-token calls BIT-match one
        full-sequence ``output()`` (fp32-logits contract included —
        ``tests/test_decode.py``).  ``carries=None`` starts a fresh
        state tree (ring capacity from the layers' ``cache_len``).
        3-D ``(batch, time, n_in)`` features only; ``params``/
        ``net_state`` override the weight operands for version-pinned
        serving sessions (same shapes → jit cache hit, no recompile).
        """
        self.init()
        self._require_carry_support("decode_step")
        # No explicit jnp.asarray: jit commits np inputs itself, and an
        # eager device_put of a single-token array costs more host time
        # than the decode dispatch it feeds (bench.py --decode).
        x = features if hasattr(features, "ndim") else np.asarray(features)
        if x.ndim != 3:
            raise ValueError(
                f"decode_step expects (batch, time, features), got "
                f"shape {x.shape}")
        if carries is None:
            carries = self._init_carries(int(x.shape[0]))
        return self._decode_step_fn(
            self.params if params is None else params,
            self.net_state if net_state is None else net_state,
            carries, x)

    def grow_decode_carries(self, carries, cache_len: int):
        """Pad every KV ring in ``carries`` up to ``cache_len`` slots
        (ONE jitted dispatch; non-ring carries pass through) — the
        serving cache-len bucket hop.  Ring slots beyond the cursor are
        exact-zero under the cursor mask, so growth never changes
        results."""
        self.init()
        return self._decode_grow_fn(int(cache_len))(carries)

    def rnn_clear_previous_state(self) -> None:
        """Reference ``rnnClearPreviousState()``."""
        self._rnn_carries = None
        self._rnn_carry_batch = -1

    def rnn_get_previous_state(self, layer: int):
        """Carry pytree for one layer (reference ``rnnGetPreviousState``)."""
        return (None if self._rnn_carries is None
                else self._rnn_carries[layer])

    def rnn_set_previous_state(self, layer: int, state) -> None:
        if self._rnn_carries is None:
            raise ValueError("No rnn state yet; call rnn_time_step first")
        self._rnn_carries[layer] = state

    def predict(self, features) -> np.ndarray:
        """Argmax class predictions (reference ``predict``)."""
        return np.argmax(self.output(features), axis=-1)

    def score(self, dataset: Optional[DataSet] = None) -> float:
        """Mean loss on a dataset (reference ``score:1705``)."""
        if dataset is None:
            from . import ingest
            # fetched once: the next call finds a host value
            self._score = float(ingest.fetch_scores(self._score))
            return self._score
        self.init()
        fmask = (None if dataset.features_mask is None
                 else jnp.asarray(dataset.features_mask))
        lmask = (None if dataset.labels_mask is None
                 else jnp.asarray(dataset.labels_mask))
        val = self._score_fn(self.params, self.net_state,
                             jnp.asarray(dataset.features),
                             jnp.asarray(dataset.labels), fmask, lmask)
        return float(val)

    @functools.cached_property
    def _score_examples_fn(self):
        @functools.partial(_monitor.watched_jit,
                           name="mln.score_examples", static_argnums=(6,))
        def run(params, net_state, features, labels, features_mask,
                labels_mask, add_reg):
            per, _ = self._loss_fn(params, net_state, features, labels,
                                   features_mask, labels_mask, None, False,
                                   per_example=True)
            if add_reg:
                per = per + self._reg_score(params)
            return per
        return run

    def score_examples(self, data,
                       add_regularization_terms: bool = True) -> np.ndarray:
        """Per-example loss vector, no batch averaging (reference
        ``scoreExamples:1740-1775``) — e.g. autoencoder anomaly scoring.
        ``data`` is a DataSet or an iterator (streamed batch by batch);
        with regularization, each entry equals ``score()`` on that single
        example."""
        self.init()
        batches = [data] if isinstance(data, DataSet) else iter(data)
        out = []
        for ds in batches:
            fmask = (None if ds.features_mask is None
                     else jnp.asarray(ds.features_mask))
            lmask = (None if ds.labels_mask is None
                     else jnp.asarray(ds.labels_mask))
            out.append(np.asarray(self._score_examples_fn(
                self.params, self.net_state, jnp.asarray(ds.features),
                jnp.asarray(ds.labels), fmask, lmask,
                bool(add_regularization_terms))))
        if not out:
            return np.zeros((0,), np.float32)
        return np.concatenate(out)

    def do_evaluation(self, iterator, *evaluators):
        """Run one forward pass per batch, feeding every evaluator
        (reference ``doEvaluation(iterator, IEvaluation...)``) —
        time-series outputs go through the masked ``evalTimeSeries``
        path.  Returns the evaluators.

        When every evaluator is a plain top-1 ``Evaluation``, the argmax
        runs on device fused into the forward program and only int32
        class indices cross the wire; label argmax and mask filtering
        stay on the host where the labels already live.  The
        ``eval_bytes_transferred`` gauge reports what the last
        evaluation actually moved device->host."""
        from ..eval.evaluation import Evaluation
        if isinstance(iterator, DataSet):
            iterator = [iterator]
        if hasattr(iterator, "reset"):
            iterator.reset()
        fast = bool(evaluators) and all(
            type(ev) is Evaluation and ev.top_n == 1 for ev in evaluators)
        bytes_moved = 0
        for ds in iterator:
            labels = np.asarray(ds.labels)
            mask = (ds.labels_mask if ds.labels_mask is not None
                    else ds.features_mask)
            mask = None if mask is None else np.asarray(mask)
            if fast:
                self.init()
                fmask = (None if ds.features_mask is None
                         else jnp.asarray(ds.features_mask))
                guess = np.asarray(self._eval_argmax_fn(
                    self.params, self.net_state, jnp.asarray(ds.features),
                    fmask))
                bytes_moved += guess.nbytes
                actual = labels.argmax(-1)
                if labels.ndim == 3:
                    actual, guess = actual.reshape(-1), guess.reshape(-1)
                    if mask is not None:
                        keep = mask.reshape(-1) > 0
                        actual, guess = actual[keep], guess[keep]
                for ev in evaluators:
                    ev.eval_class_indices(actual, guess, labels.shape[-1])
                continue
            out = self.output(ds.features, features_mask=ds.features_mask)
            bytes_moved += out.nbytes
            for ev in evaluators:
                if out.ndim == 3:
                    ev.eval_time_series(labels, out, mask)
                else:
                    ev.eval(labels, out)
        _monitor.gauge(
            "eval_bytes_transferred",
            "device->host bytes moved by the most recent do_evaluation",
        ).set(bytes_moved, path="indices" if fast else "logits")
        return evaluators

    def evaluate(self, iterator):
        """Classification evaluation over an iterator (reference
        ``MultiLayerNetwork.evaluate``)."""
        from ..eval.evaluation import Evaluation
        return self.do_evaluation(iterator, Evaluation())[0]

    def evaluate_roc(self, iterator, threshold_steps: int = 30):
        """Binary ROC over an iterator (reference ``evaluateROC``)."""
        from ..eval.roc import ROC
        return self.do_evaluation(iterator, ROC(threshold_steps))[0]

    def evaluate_roc_multi_class(self, iterator,
                                 threshold_steps: int = 30):
        """One-vs-all ROC (reference ``evaluateROCMultiClass``)."""
        from ..eval.roc import ROCMultiClass
        return self.do_evaluation(iterator,
                                  ROCMultiClass(threshold_steps))[0]

    def evaluate_regression(self, iterator):
        """Per-column regression stats (reference
        ``evaluateRegression``)."""
        from ..eval.regression import RegressionEvaluation
        return self.do_evaluation(iterator, RegressionEvaluation())[0]

    def f1_score(self, data) -> float:
        """Macro F1 on a DataSet/iterator (reference ``f1Score``)."""
        return self.evaluate(data).f1()

    # -------------------------------------------------------------- misc API
    def clone(self) -> "MultiLayerNetwork":
        """Config+params copy (reference ``clone()``)."""
        import copy
        other = MultiLayerNetwork(copy.deepcopy(self.conf))
        other.init()
        # Materialize copies: the jitted train step donates the originals, so
        # shared references would be invalidated by the next fit().
        other.params = jax.tree.map(jnp.copy, self.params)
        other.net_state = jax.tree.map(jnp.copy, self.net_state)
        other.updater_state = jax.tree.map(jnp.copy, self.updater_state)
        other.iteration = self.iteration
        other._pretrain_done = self._pretrain_done
        return other
