"""ComputationGraph: arbitrary-DAG network container.

TPU-native equivalent of the reference's ``nn/graph/ComputationGraph.java``
(2276 LoC): ``init():267``, topo-order forward loop at ``:1048-1049``,
``fit`` variants ``:650-810``, ``calcBackpropGradients:1175``,
``output:1099-1123``.

Where the reference walks materialized vertex objects per call, here one
traced pure function executes the DAG in the (build-time) topological order;
jit compiles forward + loss + backward + updater into a single XLA program.
Multi-input/multi-output batches are :class:`MultiDataSet` pytrees.
"""

from __future__ import annotations

import functools
import json
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import precision as _precision
from . import updaters as _updaters
from . import weights as _weights
from .conf import serde
from .. import monitor as _monitor
from .conf.computation_graph import (ComputationGraphConfiguration,
                                     DuplicateToTimeSeriesVertex,
                                     LastTimeStepVertex, LayerVertex)
from .network import Network, _as_multi
from ..datasets.dataset import DataSet, MultiDataSet

Array = jax.Array


def _same_layer(layer) -> str:
    """What two layers have to share to share a program: their
    configuration, as canonical JSON."""
    return json.dumps(serde.to_dict(layer), sort_keys=True, default=str)


def _layer_init_programs(layer, dtype):
    """``(init, hold, finish)`` for one layer: its parameters and state
    from a key leaf by leaf, and the same as the two staged programs of
    ``weights.staged``, jitted."""
    def init(key):
        return layer.init_params(key, dtype), layer.init_state(dtype)

    hold, finish = _weights.staged(init)
    return init, jax.jit(hold), jax.jit(finish)


def _lay_program(layer, identity):
    """``layer.lay`` (its stored parameters in the forms its step
    multiplies) as a program the executable store can serve."""
    return _monitor.watched_jit(lambda stored: layer.lay(stored),
                                name="cg.lay_weights", identity=identity)


class ComputationGraph(Network):
    """DAG network with named vertices (reference ``ComputationGraph``).
    The training path (step programs, ``fit``, the flat accessors) is
    ``network.Network``'s."""

    _jit_prefix = "cg"

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.topo = conf.topological_order()
        self.vertices = conf.vertices
        self.params: Dict[str, Dict[str, Array]] = {}
        self.net_state: Dict[str, Dict[str, Array]] = {}
        self.updater_state: Dict[str, Any] = {}
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[Any] = []
        self._init_done = False
        self._score = float("nan")
        self._rng_key: Optional[jax.Array] = None
        self._pretrain_step_cache: Dict[str, Any] = {}
        self._pretrain_done = False
        self._rnn_carries: Optional[Dict[str, Any]] = None
        self._rnn_carry_batch = -1
        self._decode_grow_cache: Dict[int, Any] = {}
        self._precision: Optional[_precision.PrecisionPolicy] = None
        self._inference_only = False
        # vertex -> (the stored leaves its forms were laid from, the forms)
        self._laid: Dict[str, Any] = {}

    # ------------------------------------------------------------------ init
    def init(self, for_inference: bool = False) -> "ComputationGraph":
        """Initialize params/state from the seed's key, by the staged
        programs of ``_init_program``.

        ``for_inference`` makes the net a served one: the same
        parameters (the staged programs and leaf by leaf give the same
        values) and nothing a ``fit`` would need beside them, so it
        holds the parameter dtype's bytes a parameter.  The staged
        programs hand every normal draw from one program to the next in
        float32, with float32 masters and the updater's moments beside
        them: some 14 bytes a parameter at the peak, which a net sized
        to fill the chip in bf16 cannot pay.  Such a net is drawn leaf
        by leaf (one leaf in float32 at a time) with an empty updater
        state; ``fit`` refuses it."""
        if self._init_done:
            return self
        _precision.publish(self._pol())
        key = jax.random.PRNGKey(self.conf.conf.seed)
        self._rng_key = key
        self._inference_only = bool(for_inference)
        out = (self._init_for_inference(key) if for_inference
               else self._init_program(key))
        # a jitted program returns its dicts sorted: back to topo order
        self.params, self.net_state, self.updater_state = (
            {n: tree[n] for n in self._layer_names()} for tree in out)
        self._init_done = True
        if for_inference:
            self.served_params()        # laid in set-up, not by a step
        return self

    def _init_for_inference(self, key):
        """Parameters and state, a layer at a time: each layer's own two
        staged programs (the values of ``_init_program``; equal layers
        share theirs), so that what is live beside the finished layers
        is one layer's draws in float32, not the net's; leaf by leaf
        where a scheme cannot be staged."""
        dtype = jnp.dtype(self._pol().param_dtype)
        names = self._layer_names()
        keys = jax.random.split(key, max(len(names), 1))
        programs, params, net_state = {}, {}, {}
        for n, k in zip(names, keys):
            layer = self.vertices[n].layer
            same = _same_layer(layer)
            if same not in programs:
                programs[same] = _layer_init_programs(layer, dtype)
            init, hold, finish = programs[same]
            try:
                params[n], net_state[n] = finish(k, hold(k))
            except (jax.errors.JAXTypeError, _weights.NotStaged):
                params[n], net_state[n] = init(k)
            # dispatch runs ahead of the device and a program's outputs
            # are allocated when it is enqueued: unwaited, every layer's
            # float32 draws are live at once (16.05 of 16 GB on the v5e
            # for a 9.6 GB net, PR 30)
            jax.block_until_ready(params[n])
        return params, net_state, {n: {} for n in names}

    @functools.cached_property
    def _init_program(self):
        """Graph twin of ``MultiLayerNetwork._init_program``: every
        layer vertex's parameters, state, updater state and masters, in
        topological order, as staged jitted programs of the seed's key
        that the executable store can serve."""
        pol = self._pol()
        dtype = jnp.dtype(pol.param_dtype)

        def init(key):
            names = self._layer_names()
            keys = jax.random.split(key, max(len(names), 1))
            params, net_state, updater_state = {}, {}, {}
            for n, k in zip(names, keys):
                layer = self.vertices[n].layer
                params[n] = layer.init_params(k, dtype)
                net_state[n] = layer.init_state(dtype)
                updater_state[n] = _updaters.init_state(
                    self._updater_conf(n),
                    _updaters.updatable_params(layer, params[n]),
                    policy=pol)
            return params, net_state, updater_state

        return _weights.init_programs(
            init, "cg.init",
            lambda part: _monitor.program_identity(self, part))

    def _updater_conf(self, name: str):
        return (self.vertices[name].layer.updater
                or self.conf.conf.updater)

    def _layer_names(self) -> List[str]:
        return [n for n in self.topo
                if isinstance(self.vertices[n], LayerVertex)]

    def _layer_items(self):
        return [(n, n, self.vertices[n].layer) for n in self._layer_names()]

    @staticmethod
    def _inputs_of(arrays):
        """``_loss_fn`` takes the sequences as they are."""
        return arrays

    def _output_layer_vertices(self) -> List[str]:
        return list(self.conf.network_outputs)

    # --------------------------------------------------------------- forward
    def _forward(self, params, net_state, inputs: Sequence[Array], *,
                 train: bool, rng: Optional[jax.Array],
                 input_masks: Optional[Dict[str, Array]] = None,
                 preoutput_outputs: bool = False, carries=None):
        """Execute the DAG (reference forward loop ``:1048``).  Returns
        (activations dict, new_state dict, new_carries dict).

        ``carries`` is a dict of per-recurrent-vertex carry pytrees; when
        given, recurrent layer vertices run ``forward_seq`` with explicit
        state in/out (the graph analogues of ``rnnTimeStep:1789`` /
        ``rnnActivateUsingStoredState``)."""
        conf = self.conf
        acts: Dict[str, Array] = {}
        pol = self._pol()
        compute_dtype = jnp.dtype(pol.compute_dtype)
        with _monitor.scope("precision", "cast"):
            for name, x in zip(conf.network_inputs, inputs):
                if jnp.issubdtype(x.dtype, jnp.floating):
                    x = x.astype(compute_dtype)
                acts[name] = x
            if compute_dtype != jnp.dtype(pol.param_dtype):
                params = jax.tree.map(
                    lambda p: p.astype(compute_dtype)
                    if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        new_state = dict(net_state)
        layer_names = self._layer_names()
        keys = (jax.random.split(rng, max(len(layer_names), 1))
                if rng is not None else [None] * max(len(layer_names), 1))
        key_of = dict(zip(layer_names, keys))
        # Per-vertex propagated time masks (feedForwardMaskArray analogue):
        # input masks flow along the DAG for per-timestep layers.
        masks: Dict[str, Optional[Array]] = dict(input_masks or {})
        new_carries = dict(carries) if carries is not None else {}

        for name in self.topo:
            v = self.vertices[name]
            with _monitor.scope("layer", name):
                xs = [acts[i] for i in v.inputs]
                in_masks = [masks.get(i) for i in v.inputs]
                mask = next((m for m in in_masks if m is not None), None)
                if isinstance(v, LayerVertex):
                    layer = v.layer
                    own = params[name]
                    tied = getattr(layer, "TIED_PARAMS", ())
                    if tied:
                        # a layer that reads another vertex's parameters
                        # (a head tied to the embedding's table): the
                        # same arrays, held once under that vertex
                        own = {**own, **{k: params[layer.tied_to][k]
                                         for k in tied}}
                    # a layer of several inputs (a hyper-connection's
                    # write) takes them all, as a tuple
                    x = (tuple(xs) if getattr(layer, "MULTI_INPUT", False)
                         else xs[0])
                    if v.preprocessor is not None:
                        x = v.preprocessor(x)
                    if preoutput_outputs and name in conf.network_outputs \
                            and hasattr(layer, "pre_output"):
                        if layer.dropout and train:
                            x = layer.apply_dropout(x, train, key_of[name])
                        out = layer.pre_output(own, x)
                    elif (pol.downcasts_output and name in conf.network_outputs
                          and hasattr(layer, "pre_output")
                          and hasattr(layer, "_activate")):
                        # fp32 logits contract, head half: output-head logits
                        # are cast fp32 BEFORE softmax/sigmoid so serving
                        # probabilities are fp32-exact, not bf16-rounded.
                        # Applies even when the vertex is in ``carries``
                        # (rnn_step / decode_step): the only recurrent head
                        # with pre_output is RnnOutputLayer, whose carry is
                        # () — forward_seq would be the same math minus the
                        # fp32 cast, and skipping it must not change the
                        # carry.  Without this, N single-token decode calls
                        # drift from output() under mixed_bf16.
                        x = layer.apply_dropout(x, train, key_of[name])
                        out = layer._activate(
                            layer.pre_output(own, x)
                            .astype(jnp.float32))
                    elif carries is not None and name in carries:
                        # a layer whose steps count what they did
                        # returns its state too
                        step = getattr(layer, "forward_seq_counted",
                                       layer.forward_seq)
                        out, new_carries[name], *counted = step(
                            own, x, carries[name], train=train,
                            rng=key_of[name], mask=mask)
                        if counted:
                            new_state[name] = counted[0]
                    else:
                        out, new_state[name] = layer.forward(
                            own, net_state[name], x, train=train,
                            rng=key_of[name], mask=mask)
                    acts[name] = out
                    masks[name] = mask
                elif isinstance(v, DuplicateToTimeSeriesVertex):
                    ref = v.reference_input
                    acts[name] = v.apply(*xs, masks=masks,
                                         timesteps=acts[ref].shape[1])
                    masks[name] = masks.get(ref)
                elif isinstance(v, LastTimeStepVertex):
                    acts[name] = v.apply(*xs, masks=masks)
                    masks[name] = None
                else:
                    acts[name] = v.apply(*xs, masks=masks)
                    masks[name] = mask
        if pol.downcasts_output:
            # fp32 logits contract: loss/softmax/metrics accumulation and
            # serving all consume fp32 even under bf16 storage.
            with _monitor.scope("precision", "cast"):
                for out in conf.network_outputs:
                    acts[out] = acts[out].astype(jnp.float32)
        return acts, new_state, new_carries

    # ------------------------------------------------------------------ loss
    def _loss_fn(self, params, net_state, features, labels, features_masks,
                 labels_masks, rng, train: bool, carries=None,
                 per_example: bool = False):
        """``per_example`` accumulates the unreduced (batch,) score vector
        across output layers (reference ``computeScoreForExamples``)
        instead of the scalar batch loss."""
        input_masks = None
        if features_masks is not None:
            input_masks = {n: m for n, m in zip(self.conf.network_inputs,
                                                features_masks)
                           if m is not None}
        acts, new_state, new_carries = self._forward(
            params, net_state, features, train=train, rng=rng,
            input_masks=input_masks, preoutput_outputs=True,
            carries=carries)
        total = (jnp.zeros((features[0].shape[0],), jnp.float32)
                 if per_example else jnp.asarray(0.0, jnp.float32))
        with _monitor.scope("loss"):
            for i, out_name in enumerate(self.conf.network_outputs):
                v = self.vertices[out_name]
                layer = v.layer
                lmask = None if labels_masks is None else labels_masks[i]
                if getattr(layer, "NEEDS_INPUT_FOR_SCORE", False):
                    # Center-loss-style heads score against their input
                    # activations; those are already in the DAG's acts.
                    x = acts[v.inputs[0]]
                    if v.preprocessor is not None:
                        x = v.preprocessor(x)
                    if layer.dropout and train and rng is not None:
                        x = layer.apply_dropout(
                            x, train, jax.random.fold_in(rng, 100_000 + i))
                    if per_example:
                        total = total + \
                            layer.compute_score_examples_with_input(
                                params[out_name], labels[i], x, lmask)
                    else:
                        total = total + layer.compute_score_with_input(
                            params[out_name], labels[i], x, lmask,
                            average=self.conf.conf.mini_batch)
                    continue
                if not hasattr(layer, "compute_score"):
                    raise ValueError(
                        f"Output vertex '{out_name}' is not an output layer")
                if per_example:
                    total = total + layer.compute_score_examples(
                        labels[i], acts[out_name], lmask)
                else:
                    total = total + layer.compute_score(
                        labels[i], acts[out_name], lmask,
                        average=self.conf.conf.mini_batch)
        return total, (new_state, new_carries)

    @functools.cached_property
    def _tbptt_step(self):
        """Truncated-BPTT window step for the graph (reference graph tBPTT
        path in ``ComputationGraph.doTruncatedBPTT:1936``): one
        fwd+bwd+update over a time window with recurrent carries in from
        the previous window, gradients stopped at the window boundary."""

        def step(params, updater_state, net_state, carries, iteration,
                 features, labels, features_masks, labels_masks, base_rng):
            rng = jax.random.fold_in(base_rng, iteration)
            carries = jax.lax.stop_gradient(carries)

            def loss(p, ns, f, l, fm, lm, r):
                return self._loss_fn(p, ns, f, l, fm, lm, r, True,
                                     carries=carries)

            (data_loss, (new_state, new_carries)), grads = \
                jax.value_and_grad(loss, has_aux=True)(
                    params, net_state, features, labels, features_masks,
                    labels_masks, rng)
            new_params, new_ustate = self._apply_updates(
                params, updater_state, grads, iteration)
            score = data_loss + self._reg_score(params)
            return (new_params, new_ustate, new_state, new_carries, score)

        return _monitor.watched_jit(step, name="cg.tbptt_step",
                                    donate_argnums=(0, 1, 2, 3))

    @functools.cached_property
    def _advance_fn(self):
        """Carry-advance without gradients or updates: used to roll state
        over the leading ``fwd - back`` steps of a window when
        ``tbptt_back_length < tbptt_fwd_length`` (the reference truncates
        the LSTM backward iteration to backLength steps from the window
        end, ``LSTMHelpers`` truncated loop), and by ``rnn_time_step``."""

        def run(params, net_state, carries, features, features_masks):
            input_masks = None
            if features_masks is not None:
                input_masks = {
                    n: m for n, m in zip(self.conf.network_inputs,
                                         features_masks) if m is not None}
            acts, _, new_carries = self._forward(
                params, net_state, features, train=False, rng=None,
                input_masks=input_masks, carries=carries)
            return [acts[o] for o in self.conf.network_outputs], new_carries

        return _monitor.watched_jit(run, name="cg.advance")

    def _build_decode_step(self, donate: bool):
        """Autoregressive decode step: the ``cg.advance`` contract over
        generalized state trees (RNN carries AND KV-cache rings), under
        its own jit name so the serving sanitizer can budget
        ``serving.decode_step`` separately (one dispatch per token).
        ``donate`` gives the carries up to the step, which then updates
        the rings in place (a session's path: it keeps the new tree and
        never looks at the old one)."""
        def run(params, net_state, carries, features):
            acts, _, new_carries = self._forward(
                params, net_state, features, train=False, rng=None,
                carries=carries)
            return ([acts[o] for o in self.conf.network_outputs],
                    new_carries)
        return _monitor.watched_jit(run, name="cg.decode_step",
                                    donate_argnums=(2,) if donate else ())

    @functools.cached_property
    def _decode_step_fn(self):
        return self._build_decode_step(donate=False)

    @functools.cached_property
    def _decode_step_donating_fn(self):
        return self._build_decode_step(donate=True)

    # ------------------------------------------------- weights laid once
    @functools.cached_property
    def _lay_programs(self) -> Dict[str, Any]:
        """``{vertex: program}`` for the layer vertices whose layer
        lays its weights (``layer.lay``: the stored parameters in the
        forms its step multiplies): one jitted program a distinct layer
        configuration, which the executable store serves like
        ``cg.token_step``.  Empty for a net of layers that lay
        nothing."""
        programs, by_vertex = {}, {}
        for n in self._layer_names():
            layer = self.vertices[n].layer
            if not hasattr(layer, "lay"):
                continue
            same = _same_layer(layer)
            if same not in programs:
                programs[same] = _lay_program(
                    layer, lambda same=same: _monitor.program_identity(
                        self, "lay_weights", same))
            by_vertex[n] = programs[same]
        return by_vertex

    def laid_vertices(self) -> List[str]:
        """The vertices whose steps multiply weights laid once: those
        of :meth:`served_params`."""
        return list(self._lay_programs) if self._inference_only else []

    def _laid_tree(self, vertex: str, stored, forms):
        """A laying vertex's parameters as its step takes them: the
        laid ``forms`` in place of the leaves they were laid from, every
        other leaf of ``stored`` as it is (the same array)."""
        sources = self.vertices[vertex].layer.LAID_FROM
        return {**{k: v for k, v in stored.items() if k not in sources},
                **forms}

    def lay_weights(self, params):
        """``params`` with every laying vertex's parameters as its step
        takes them: the pure form of :meth:`served_params` (traceable;
        what ``tools/step_copies.py`` shapes a step's arguments with)."""
        return {n: (self._laid_tree(n, p, self.vertices[n].layer.lay(p))
                    if n in self._lay_programs else p)
                for n, p in params.items()}

    def served_params(self):
        """The parameters ``token_step`` and ``prefill_step`` multiply
        when handed none.  A served net (``init(for_inference=True)``)
        whose layers lay their weights holds, beside ``params``, each
        such vertex's laid forms, derived by ``cg.lay_weights`` once:
        when the net is initialised, and again at the first step after
        a leaf they were laid from was replaced (assignment to
        ``params``, ``set_flat_params``, a serializer's load: found by
        the leaves' identity, so no way of writing them is missed).
        Every other net, and every vertex that lays nothing, is served
        ``params`` as it is.  ``params`` keeps every name and shape."""
        programs = self._lay_programs
        if not (self._inference_only and programs):
            return self.params
        served = dict(self.params)
        for n, program in programs.items():
            stored = self.params[n]
            sources = {k: stored[k]
                       for k in self.vertices[n].layer.LAID_FROM}
            held = self._laid.get(n)
            if held is None or any(held[0][k] is not a
                                   for k, a in sources.items()):
                held = self._laid[n] = (sources, program(sources))
                _monitor.gauge(
                    "serving_laid_weight_bytes",
                    "bytes of the forms a served net laid its weights in, "
                    "held beside the stored ones, by vertex").set(
                    sum(a.nbytes for a in held[1].values()), vertex=n)
            served[n] = self._laid_tree(n, stored, held[1])
        return served

    def _expert_vertices(self) -> List[str]:
        """Layer vertices whose state counts tokens by expert."""
        return [n for n in self._layer_names()
                if "expert_tokens" in self.net_state.get(n, {})]

    def _selection_vertices(self) -> List[str]:
        """Layer vertices whose state says whether a step's sparse
        selection settled ties (``selection_tied``)."""
        return [n for n in self._layer_names()
                if "selection_tied" in self.net_state.get(n, {})]

    @functools.cached_property
    def _token_step_fn(self):
        """One step of token generation, sampling included: integer ids
        (batch, time) in, through the graph over the session's state
        tree, the greedy argmax of the last position's logits out as
        (batch, 1) int32 on the device, to be the next step's input
        with no fetch between.  Also out: the float32 logits of the
        first and the last row at that position (computed anyway; what
        a comparison with a reference reads), and ``counts`` plus this
        step's tokens by expert, then the held experts whose blocks it
        read and, last in the row, whether its grouped experts spilled,
        one row a vertex of
        ``_expert_vertices``; then a row a vertex of
        ``_selection_vertices`` whose last column says whether its
        selection settled ties.  The carries and the counts are donated:
        the rings are updated in place.  Like ``cg.prefill_step`` and
        ``cg.fork_state`` it says what it closes over, so the executable
        store serves a warm start with it."""
        out_name = self.conf.network_outputs[0]

        def run(params, net_state, carries, ids, counts):
            acts, new_state, new_carries = self._forward(
                params, net_state, (ids,), train=False, rng=None,
                carries=carries)
            last = acts[out_name][:, -1]
            next_ids = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
            kept = jnp.stack([last[0], last[-1]])
            picks = [jnp.concatenate([new_state[n]["expert_tokens"],
                                      new_state[n]["experts_read"][None],
                                      new_state[n]["experts_spilled"][None]])
                     for n in self._expert_vertices()]
            # the rare branch of each step, in the last column
            picks += [jnp.zeros(counts.shape[1], jnp.int32).at[-1].set(
                new_state[n]["selection_tied"])
                for n in self._selection_vertices()]
            if picks:
                counts = counts + jnp.stack(picks)
            return next_ids, kept, counts, new_carries

        return _monitor.watched_jit(
            run, name="cg.token_step", donate_argnums=(2, 4),
            identity=lambda: _monitor.program_identity(self, "token_step"))

    @functools.cached_property
    def _prefill_step_fn(self):
        """A chunk of prompt ids through the graph for the state tree
        alone (donated): nothing reads the logits, so the compiler
        drops the head."""
        def run(params, net_state, carries, ids):
            return self._forward(params, net_state, (ids,), train=False,
                                 rng=None, carries=carries)[2]
        return _monitor.watched_jit(
            run, name="cg.prefill_step", donate_argnums=(2,),
            identity=lambda: _monitor.program_identity(self, "prefill_step"))

    @functools.cached_property
    def _fork_state_fn(self):
        """A device copy of a state tree, as one dispatch."""
        return _monitor.watched_jit(
            lambda carries: jax.tree.map(jnp.copy, carries),
            name="cg.fork_state",
            identity=lambda: _monitor.program_identity(self, "fork_state"))

    def _decode_grow_fn(self, cache_len: int):
        """Jitted state-tree growth to a larger KV ring capacity — ONE
        dispatch per (shape, target) pair (the serving bucket hop)."""
        from .layers.recurrent import BaseRecurrentLayer
        if cache_len not in self._decode_grow_cache:
            def grow(carries):
                out = {}
                for n, c in carries.items():
                    layer = self.vertices[n].layer
                    if (isinstance(layer, BaseRecurrentLayer)
                            and getattr(layer, "HAS_KV_RING", False)):
                        out[n] = layer.grow_carry(c, cache_len)
                    else:
                        out[n] = c
                return out
            self._decode_grow_cache[cache_len] = _monitor.watched_jit(
                grow, name="cg.decode_grow")
        return self._decode_grow_cache[cache_len]

    @functools.cached_property
    def _output_fn(self):
        def run(params, net_state, features, features_masks):
            input_masks = None
            if features_masks is not None:
                input_masks = {
                    n: m for n, m in zip(self.conf.network_inputs,
                                         features_masks) if m is not None}
            acts, _, _ = self._forward(params, net_state, features,
                                       train=False, rng=None,
                                       input_masks=input_masks)
            return [acts[o] for o in self.conf.network_outputs]
        return _monitor.watched_jit(run, name="cg.output")

    @functools.cached_property
    def _eval_argmax_fn(self):
        """Single-output inference forward + argmax in one program:
        evaluation transfers int32 class indices, not logits."""
        def run(params, net_state, features, features_masks):
            input_masks = None
            if features_masks is not None:
                input_masks = {
                    n: m for n, m in zip(self.conf.network_inputs,
                                         features_masks) if m is not None}
            acts, _, _ = self._forward(params, net_state, features,
                                       train=False, rng=None,
                                       input_masks=input_masks)
            out = acts[self.conf.network_outputs[0]]
            return jnp.argmax(out, axis=-1).astype(jnp.int32)
        return _monitor.watched_jit(run, name="cg.eval_argmax")

    @functools.cached_property
    def _score_fn(self):
        def score(params, net_state, features, labels, features_masks,
                  labels_masks):
            data_loss, _ = self._loss_fn(
                params, net_state, features, labels, features_masks,
                labels_masks, None, False)
            return data_loss + self._reg_score(params)
        return _monitor.watched_jit(score, name="cg.score")

    @functools.cached_property
    def _score_examples_fn(self):
        @functools.partial(_monitor.watched_jit,
                           name="cg.score_examples", static_argnums=(6,))
        def run(params, net_state, features, labels, features_masks,
                labels_masks, add_reg):
            per, _ = self._loss_fn(params, net_state, features, labels,
                                   features_masks, labels_masks, None,
                                   False, per_example=True)
            if add_reg:
                per = per + self._reg_score(params)
            return per
        return run

    def score_examples(self, data,
                       add_regularization_terms: bool = True) -> np.ndarray:
        """Per-example loss vector, summed over output layers, no batch
        averaging (reference ``ComputationGraph.scoreExamples:1486-1520``).
        ``data`` is a DataSet/MultiDataSet or an iterator of either,
        streamed batch by batch."""
        self.init()
        batches = ([data] if isinstance(data, (DataSet, MultiDataSet))
                   else iter(data))
        out = []
        for b in batches:
            mds = _as_multi(b)
            feats = tuple(jnp.asarray(f) for f in mds.features)
            labels = tuple(jnp.asarray(l) for l in mds.labels)
            fmasks = (None if mds.features_masks is None else tuple(
                None if m is None else jnp.asarray(m)
                for m in mds.features_masks))
            lmasks = (None if mds.labels_masks is None else tuple(
                None if m is None else jnp.asarray(m)
                for m in mds.labels_masks))
            out.append(np.asarray(self._score_examples_fn(
                self.params, self.net_state, feats, labels, fmasks,
                lmasks, bool(add_regularization_terms))))
        if not out:
            return np.zeros((0,), np.float32)
        return np.concatenate(out)

    # -------------------------------------------------------------- pretrain
    def _pretrain_step(self, name: str):
        """Jitted unsupervised step for one layer vertex (reference
        ``ComputationGraph.pretrain:510-555``)."""
        if name not in self._pretrain_step_cache:
            v = self.vertices[name]
            layer = v.layer
            uconf = self._updater_conf(name)

            def step(params, ustate, net_state, iteration, features,
                     base_rng):
                rng = jax.random.fold_in(base_rng, iteration)
                acts, _, _ = self._forward(params, net_state, features,
                                           train=False, rng=None)
                x = acts[v.inputs[0]]
                if v.preprocessor is not None:
                    x = v.preprocessor(x)
                x = jax.lax.stop_gradient(x)
                score, grads = layer.pretrain_grads(params[name], x, rng)
                grads = _updaters.regularize(grads, params[name],
                                             layer.l1_by_param(),
                                             layer.l2_by_param())
                grads = _updaters.normalize_gradients(
                    grads, layer.gradient_normalization,
                    layer.gradient_normalization_threshold)
                updates, new_ustate = _updaters.compute_update(
                    uconf, grads, ustate, iteration,
                    params={k: params[name][k] for k in grads})
                new_p = jax.tree.map(lambda p, u: p - u, params[name],
                                     updates)
                score = score + _updaters.regularization_score(
                    params[name], layer.l1_by_param(), layer.l2_by_param())
                return new_p, new_ustate, score

            self._pretrain_step_cache[name] = _monitor.watched_jit(
                step, name=f"cg.pretrain_step_{name}", donate_argnums=(1,))
        return self._pretrain_step_cache[name]

    def pretrain(self, data, epochs: int = 1) -> "ComputationGraph":
        """Greedy layer-wise pretraining of every pretrainable layer vertex
        in topological order (reference ``ComputationGraph.pretrain:510``)."""
        self.init()
        if not isinstance(data, (DataSet, MultiDataSet)) \
                and not hasattr(data, "reset"):
            data = list(data)  # one-shot iterable: each layer needs a pass
        for name in self._layer_names():
            if getattr(self.vertices[name].layer, "IS_PRETRAINABLE", False):
                self.pretrain_layer(name, data, epochs)
        # fit() must not re-run pretraining (and the flag serializes, so a
        # restored model doesn't re-pretrain over fine-tuned weights)
        self._pretrain_done = True
        return self

    def pretrain_layer(self, name: str, data,
                       epochs: int = 1) -> "ComputationGraph":
        self.init()
        if not getattr(self.vertices[name].layer, "IS_PRETRAINABLE", False):
            return self
        if getattr(self.vertices[name].layer, "frozen", False):
            return self          # frozen extractor: pretraining is a no-op
        step = self._pretrain_step(name)
        batches = ([data] if isinstance(data, (DataSet, MultiDataSet))
                   else data)
        for _ in range(epochs):
            if hasattr(batches, "reset"):
                batches.reset()
            for ds in batches:
                mds = _as_multi(ds)
                features = tuple(jnp.asarray(f) for f in mds.features)
                (self.params[name], self.updater_state[name],
                 score) = step(self.params, self.updater_state[name],
                               self.net_state, self.iteration, features,
                               self._rng_key)
                self._score = score
                self.iteration += 1
                self._fire_listeners()
        return self

    # ---------------------------------------------------------------- tBPTT
    def _fit_tbptt(self, features, labels, fmasks, lmasks) -> None:
        """Graph truncated BPTT (reference
        ``ComputationGraph.doTruncatedBPTT:1936`` +
        ``rnnUpdateStateWithTBPTTState``): slice every 3-D input/label along
        time into ``tbptt_fwd_length`` windows, carrying recurrent vertex
        state across windows.  When ``tbptt_back_length <
        tbptt_fwd_length``, the leading ``fwd - back`` steps of each window
        advance state without gradients (the reference instead truncates
        the LSTM backward iteration at backLength steps from the window
        end — recurrent truncation is identical; feedforward-parameter
        gradients from those leading steps are not accumulated here)."""
        self._require_carry_support("truncated BPTT")
        if any(l.ndim > 3 for l in labels):
            raise ValueError(
                "Graph tBPTT supports (batch, time, features) labels only; "
                "got a label of rank "
                f"{max(l.ndim for l in labels)} (4-D per-timestep targets "
                "are not time-sliceable here)")
        seq = [l for l in labels if l.ndim == 3]
        if not seq:
            raise ValueError(
                "Truncated BPTT needs per-timestep labels (batch, time, "
                "...); use standard backprop for sequence-level labels.")
        T = seq[0].shape[1]
        window = self.conf.tbptt_fwd_length
        back = self.conf.tbptt_back_length or window
        if back > window:
            raise ValueError(
                f"tbptt_back_length ({back}) > tbptt_fwd_length "
                f"({window}) is not meaningful")
        carries = self._init_carries(features[0].shape[0])

        def _t(arrs, sl, masks=False):
            # time axis is 1 for 3-D (batch, time, feat) arrays and for
            # 2-D (batch, time) masks; 2-D labels/static inputs and 4-D
            # image inputs pass through whole (an image whose height
            # happens to equal T must not be cropped)
            def want(a):
                return (a.ndim == 3 or (masks and a.ndim == 2)) \
                    and a.shape[1] == T
            return tuple(None if a is None
                         else (a[:, sl] if want(a) else a) for a in arrs)

        scores = []
        for start in range(0, T, window):
            stop = min(start + window, T)
            adv = max(0, (stop - start) - back)
            if adv:
                asl = slice(start, start + adv)
                _, carries = self._advance_fn(
                    self.params, self.net_state, carries,
                    _t(features, asl),
                    None if fmasks is None else _t(fmasks, asl,
                                                   masks=True))
                start = start + adv
            sl = slice(start, stop)
            t1 = time.perf_counter()
            (self.params, self.updater_state, self.net_state, carries,
             score) = self._tbptt_step(
                self.params, self.updater_state, self.net_state, carries,
                self.iteration, _t(features, sl), _t(labels, sl),
                None if fmasks is None else _t(fmasks, sl, masks=True),
                None if lmasks is None else _t(lmasks, sl, masks=True),
                self._rng_key)
            _monitor.observe_phase("step", time.perf_counter() - t1)
            scores.append(score)
            self.iteration += 1
            _monitor.counter("train_iterations_total",
                             "supervised train iterations").inc()
            self._fire_listeners()
        self._score = scores[-1] if scores else self._score

    def _recurrent_vertex_names(self) -> List[str]:
        from .layers.recurrent import BaseRecurrentLayer
        return [n for n in self._layer_names()
                if isinstance(self.vertices[n].layer, BaseRecurrentLayer)]

    def _require_carry_support(self, what: str) -> None:
        """Bidirectional layers cannot carry state across time chunks
        (reference graph rnnTimeStep throws for them too)."""
        from .layers.recurrent import BaseRecurrentLayer
        for n in self._layer_names():
            layer = self.vertices[n].layer
            if (isinstance(layer, BaseRecurrentLayer)
                    and not layer.SUPPORTS_CARRY):
                raise ValueError(
                    f"Vertex '{n}' ({type(layer).__name__}) does not "
                    f"support {what}: its backward pass needs the full "
                    "sequence")

    def _init_carries(self, batch: int,
                      cache_len: Optional[int] = None) -> Dict[str, Any]:
        """Zero carries per recurrent vertex; ``cache_len`` overrides
        KV-ring capacities (the serving (batch, cache_len) bucket
        ladder) and is ignored by RNN carries."""
        dtype = jnp.dtype(self._pol().compute_dtype)
        out = {}
        for n in self._recurrent_vertex_names():
            layer = self.vertices[n].layer
            if cache_len is not None and getattr(layer, "HAS_KV_RING",
                                                 False):
                out[n] = layer.init_carry(batch, dtype,
                                          cache_len=cache_len)
            else:
                out[n] = layer.init_carry(batch, dtype)
        return out

    def has_kv_ring(self) -> bool:
        """Whether any vertex carries a KV-cache ring (selects the
        ``serving.decode_step`` sanitizer scenario)."""
        return any(getattr(self.vertices[n].layer, "HAS_KV_RING", False)
                   for n in self._layer_names())

    def max_cache_len(self) -> int:
        """Largest capacity of the KV rings that grow with a session (0
        without any: no ring, or window rings alone, which are sized
        once and wrap)."""
        layers = (self.vertices[n].layer for n in self._layer_names())
        return max((int(layer.cache_len) for layer in layers
                    if getattr(layer, "HAS_KV_RING", False)
                    and getattr(layer, "RING_GROWS", True)), default=0)

    # --------------------------------------------- rnn streaming state API
    def rnn_time_step(self, *features):
        """Stateful streaming inference (reference
        ``ComputationGraph.rnnTimeStep:1789``): feed one or more timesteps
        per input, carrying every recurrent vertex's hidden state between
        calls.  2-D inputs (batch, features) are single timesteps and the
        matching outputs come back 2-D; 3-D inputs return full
        (batch, time, n_out) sequences."""
        self.init()
        self._require_carry_support("rnn_time_step")
        xs = [jnp.asarray(f) for f in features]
        squeeze = xs[0].ndim == 2
        xs = [x[:, None, :] if x.ndim == 2 else x for x in xs]
        batch = xs[0].shape[0]
        if self._rnn_carries is None:
            self._rnn_carries = self._init_carries(batch)
            self._rnn_carry_batch = batch
        elif self._rnn_carry_batch != batch:
            raise ValueError(
                f"rnn_time_step batch size {batch} != stored state batch "
                f"size {self._rnn_carry_batch}; call "
                "rnn_clear_previous_state() between unrelated sequences")
        outs, self._rnn_carries = self._advance_fn(
            self.params, self.net_state, self._rnn_carries, tuple(xs),
            None)
        outs = [np.asarray(o) for o in outs]
        if squeeze:
            outs = [o[:, -1] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_stateless_step(self, carries, *features, params=None,
                           net_state=None):
        """Explicit-carry streaming step (re-entrant twin of
        :meth:`rnn_time_step`): advance the given carry dict by the input
        timesteps and return ``(outs, new_carries)`` without touching the
        graph's own hidden-state slot — the primitive behind
        ``serving.SessionCache``'s N-concurrent-sessions-per-model.
        ``carries=None`` starts from zero state; inputs must be 3-D
        ``(batch, time, n_in)``; ``outs`` is always a list (one per
        graph output) and each call is ONE dispatch of the jitted
        ``cg.advance`` program.  ``params``/``net_state`` override the
        weight operands (same shapes/dtypes → jit cache hit, no
        recompile) so a serving session can stay pinned to the weight
        version its carries came from across a hot-swap
        (docs/DEPLOY.md)."""
        self.init()
        self._require_carry_support("rnn_stateless_step")
        xs = tuple(jnp.asarray(f) for f in features)
        for x in xs:
            if x.ndim != 3:
                raise ValueError(
                    f"rnn_stateless_step expects (batch, time, features) "
                    f"inputs, got shape {x.shape}")
        if carries is None:
            carries = self._init_carries(int(xs[0].shape[0]))
        return self._advance_fn(
            self.params if params is None else params,
            self.net_state if net_state is None else net_state,
            carries, xs, None)

    def decode_step(self, carries, *features, params=None,
                    net_state=None, donate: bool = False):
        """Autoregressive decode step: :meth:`rnn_stateless_step`
        generalized to arbitrary per-session state trees (RNN carries
        and KV-cache rings) under the ``cg.decode_step`` jit name.
        Returns ``(outs, new_carries)`` with ``outs`` a list (one per
        graph output); N single-token calls BIT-match one full-sequence
        ``output()`` with the fp32-logits contract intact.  Inputs must
        be 3-D; ``carries=None`` starts a fresh state tree;
        ``params``/``net_state`` pin a weight version (same shapes →
        jit cache hit); ``donate`` consumes ``carries`` (the rings are
        updated in place; a second program beside the default one)."""
        self.init()
        self._require_carry_support("decode_step")
        # jit commits np inputs itself; an eager device_put per token
        # would dominate the single-token dispatch (bench.py --decode).
        xs = tuple(f if hasattr(f, "ndim") else np.asarray(f)
                   for f in features)
        for x in xs:
            if x.ndim != 3:
                raise ValueError(
                    f"decode_step expects (batch, time, features) "
                    f"inputs, got shape {x.shape}")
        if carries is None:
            carries = self._init_carries(int(xs[0].shape[0]))
        step = (self._decode_step_donating_fn if donate
                else self._decode_step_fn)
        return step(
            self.params if params is None else params,
            self.net_state if net_state is None else net_state,
            carries, xs)

    def token_step(self, carries, ids, counts=None, params=None,
                   net_state=None):
        """One generation step of a token model (single integer input,
        logits out): ``(next ids (batch, 1) int32, kept logits (2,
        vocabulary) float32 of rows 0 and batch-1, tokens by expert,
        new carries)``, all device arrays, ONE dispatch.  ``ids`` is
        (batch, time); ``carries`` and ``counts`` are consumed (donated).
        ``counts`` is ``(len(_expert_vertices()) +
        len(_selection_vertices()), n_experts + 2)`` int32 or None to
        start from zero."""
        self.init()
        if counts is None:
            counts = self.zero_expert_counts()
        return self._token_step_fn(
            self.served_params() if params is None else params,
            self.net_state if net_state is None else net_state,
            carries, ids, counts)

    def zero_expert_counts(self):
        rows = [self.net_state[n]["expert_tokens"].shape[0] + 2
                for n in self._expert_vertices()]
        rows += [1] * len(self._selection_vertices())
        return jnp.zeros((len(rows), max(rows, default=0)), jnp.int32)

    def prefill_step(self, carries, ids, params=None, net_state=None):
        """Advance ``carries`` (consumed) over a (batch, time) chunk of
        ids; returns the new carries only.  ONE dispatch."""
        self.init()
        return self._prefill_step_fn(
            self.served_params() if params is None else params,
            self.net_state if net_state is None else net_state,
            carries, ids)

    def fork_carries(self, carries):
        """A copy of a state tree on the device (ONE dispatch): what a
        session forked from a prefilled prefix starts from, so that the
        donating steps of the fork leave the original intact."""
        return self._fork_state_fn(carries)

    def grow_decode_carries(self, carries, cache_len: int):
        """Pad every KV ring in ``carries`` up to ``cache_len`` slots
        (ONE jitted dispatch; non-ring carries pass through) — the
        serving cache-len bucket hop."""
        self.init()
        return self._decode_grow_fn(int(cache_len))(carries)

    def rnn_clear_previous_state(self) -> None:
        """Reference ``rnnClearPreviousState()``."""
        self._rnn_carries = None
        self._rnn_carry_batch = -1

    def rnn_get_previous_state(self, vertex_name: str):
        """Carry pytree for one recurrent vertex (reference
        ``rnnGetPreviousState(String)``)."""
        return (None if self._rnn_carries is None
                else self._rnn_carries.get(vertex_name))

    def rnn_set_previous_state(self, vertex_name: str, state) -> None:
        if self._rnn_carries is None:
            raise ValueError("No rnn state yet; call rnn_time_step first")
        if vertex_name not in self._rnn_carries:
            raise KeyError(f"'{vertex_name}' is not a recurrent vertex")
        self._rnn_carries[vertex_name] = state

    # ------------------------------------------------------------- inference
    def output(self, *features, features_masks=None):
        """Forward to all outputs (reference ``output:1099-1123``).  Returns
        a single array for single-output graphs, else a list."""
        self.init()
        feats = tuple(jnp.asarray(f) for f in features)
        fmasks = (None if features_masks is None else tuple(
            None if m is None else jnp.asarray(m) for m in features_masks))
        outs = [np.asarray(o) for o in self._output_fn(
            self.params, self.net_state, feats, fmasks)]
        return outs[0] if len(outs) == 1 else outs

    def compile_output(self, feature_shapes, dtype=None, mask_shapes=None,
                       mask_dtype=None, params=None, net_state=None):
        """AOT-compile the inference forward for one concrete shape per
        graph input (``.lower().compile()`` through
        ``monitor.watched_jit`` → counted in
        ``jit_compiles_total{fn="cg.output"}``); the ``ComputationGraph``
        face of the serving bucket-warmup primitive — see
        ``MultiLayerNetwork.compile_output``.

        ``feature_shapes`` is one shape tuple per network input;
        ``mask_shapes`` (optional) one shape-or-None per input.  Call the
        result as ``compiled(params, net_state, features_tuple,
        masks_tuple_or_None)``; it returns the output list.
        ``params``/``net_state`` override the lowering operands (pass
        device-committed copies to pin the executable to a device).
        """
        self.init()
        if params is None:
            params = self.params
        if net_state is None:
            net_state = self.net_state
        dt = jnp.dtype(dtype if dtype is not None else self.conf.conf.dtype)
        avals = tuple(
            jax.ShapeDtypeStruct(tuple(int(d) for d in s), dt)
            for s in feature_shapes)
        mavals = None
        if mask_shapes is not None:
            mdt = jnp.dtype(mask_dtype if mask_dtype is not None else dt)
            mavals = tuple(
                None if s is None
                else jax.ShapeDtypeStruct(tuple(int(d) for d in s), mdt)
                for s in mask_shapes)
        return self._output_fn.lower(params, net_state, avals,
                                     mavals).compile()

    def score(self, data=None) -> float:
        if data is None:
            from . import ingest
            # fetched once: the next call finds a host value
            self._score = float(ingest.fetch_scores(self._score))
            return self._score
        self.init()
        mds = _as_multi(data)
        fmasks = (None if mds.features_masks is None else tuple(
            None if m is None else jnp.asarray(m)
            for m in mds.features_masks))
        lmasks = (None if mds.labels_masks is None else tuple(
            None if m is None else jnp.asarray(m) for m in mds.labels_masks))
        return float(self._score_fn(
            self.params, self.net_state,
            tuple(jnp.asarray(f) for f in mds.features),
            tuple(jnp.asarray(l) for l in mds.labels), fmasks, lmasks))

    def do_evaluation(self, iterator, *evaluators):
        """Run one forward pass per batch, feeding every evaluator
        (reference ``doEvaluation``); single-output graphs only.  Returns
        the evaluators."""
        if len(self.conf.network_outputs) != 1:
            raise ValueError("do_evaluation() requires a single-output "
                             "graph")
        from ..eval.evaluation import Evaluation
        if isinstance(iterator, (DataSet, MultiDataSet)):
            iterator = [iterator]
        if hasattr(iterator, "reset"):
            iterator.reset()
        fast = bool(evaluators) and all(
            type(ev) is Evaluation and ev.top_n == 1 for ev in evaluators)
        bytes_moved = 0
        for ds in iterator:
            mds = _as_multi(ds)
            labels = np.asarray(mds.labels[0])
            mask = None
            if mds.labels_masks is not None:
                mask = mds.labels_masks[0]
            elif mds.features_masks is not None:
                mask = mds.features_masks[0]
            mask = None if mask is None else np.asarray(mask)
            if fast:
                self.init()
                feats = tuple(jnp.asarray(f) for f in mds.features)
                fmasks = (None if mds.features_masks is None else tuple(
                    None if m is None else jnp.asarray(m)
                    for m in mds.features_masks))
                guess = np.asarray(self._eval_argmax_fn(
                    self.params, self.net_state, feats, fmasks))
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
            out = self.output(*mds.features,
                              features_masks=mds.features_masks)
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
        """Single-output classification evaluation (reference
        ``SparkComputationGraph``-style ``evaluate``)."""
        from ..eval.evaluation import Evaluation
        return self.do_evaluation(iterator, Evaluation())[0]

    def evaluate_roc(self, iterator, threshold_steps: int = 30):
        """Binary ROC (reference ``evaluateROC``)."""
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

    def predict(self, *features) -> np.ndarray:
        out = self.output(*features)
        if isinstance(out, list):
            raise ValueError("predict() requires a single-output graph")
        return np.argmax(out, axis=-1)

    # -------------------------------------------------------------- misc API
    def clone(self) -> "ComputationGraph":
        import copy
        other = ComputationGraph(copy.deepcopy(self.conf))
        other.init()
        other.params = jax.tree.map(jnp.copy, self.params)
        other.net_state = jax.tree.map(jnp.copy, self.net_state)
        other.updater_state = jax.tree.map(jnp.copy, self.updater_state)
        other.iteration = self.iteration
        other._pretrain_done = self._pretrain_done
        return other
