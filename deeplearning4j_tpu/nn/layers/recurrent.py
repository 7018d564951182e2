"""Recurrent layers: GravesLSTM, GravesBidirectionalLSTM, RnnOutputLayer.

TPU-native equivalents of the reference's
``nn/layers/recurrent/GravesLSTM.java`` + ``LSTMHelpers.java`` (501 LoC;
``activateHelper:58`` runs an explicit per-timestep Java loop),
``GravesBidirectionalLSTM.java`` (fwd + bwd passes summed at ``:227``), and
``nn/layers/recurrent/RnnOutputLayer.java``.

Semantics preserved from ``LSTMHelpers.java``:

- fused 4H-wide preactivation ``[block-input | forget | output | input-mod]``
  (``:176-206``; DL4J calls the input gate "input modulation")
- peephole connections stored as 3 extra columns of the recurrent weight
  matrix ``RW`` of shape (H, 4H+3): column 4H = wFF (forget gate, reads
  c_{t-1}), 4H+1 = wOO (output gate, reads c_t), 4H+2 = wGG (input-mod gate,
  reads c_{t-1}) — ``LSTMHelpers.java:104-106``
- block input uses the layer activation fn; the three gates use
  ``gate_activation_fn`` (default sigmoid)
- forget-gate bias initialized to ``forget_gate_bias_init`` — bias slice
  [H, 2H) (``GravesLSTMParamInitializer.java:100``)

TPU-first design: the Java timestep loop becomes ``lax.scan``; the input
projection ``x·W + b`` for ALL timesteps is hoisted out of the scan as one
big MXU matmul, so only the (H,4H) recurrent matmul lives in the scan body.
Data layout is time-major inside the scan, (batch, time, features) at the
API (the reference uses (batch, features, time); preprocessors adapt).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import activations as _activations
from .. import lossfunctions as _losses
from ..conf import inputs as _inputs
from ..conf import serde
from ..weights import init_weights
from .base import Array, BaseLayerConfig, ParamTree, StateTree

InputType = _inputs.InputType

# An LSTM carry is (h, c), each (batch, hidden).
Carry = Tuple[Array, Array]


def _match_varying(tree, ref: Array):
    """Pcast every leaf of ``tree`` to carry the same varying manual axes
    (shard_map vma) as ``ref``.

    Fresh ``jnp.zeros`` carries are unvarying; inside ``shard_map`` (the
    ParallelWrapper step) the scanned inputs are device-varying, and
    ``lax.scan`` requires carry-in and carry-out types to match.  Outside
    shard_map ``ref`` has no vma and this is a no-op."""
    ref_vma = jax.typeof(ref).vma
    if not ref_vma:
        return tree

    def cast(leaf):
        missing = ref_vma - jax.typeof(leaf).vma
        return lax.pcast(leaf, tuple(missing), to="varying") if missing \
            else leaf

    return jax.tree.map(cast, tree)


def lstm_scan(W: Array, RW: Array, b: Array, x: Array, carry: Carry, *,
              afn, gate_fn, mask: Optional[Array] = None,
              reverse: bool = False) -> Tuple[Array, Carry]:
    """Run the peephole LSTM over a (batch, time, n_in) sequence.

    Returns (outputs (batch, time, H), final (h, c)).  With a (batch, time)
    mask, masked steps pass the previous state through unchanged and emit
    zeros (the reference zeroes masked epsilons/activations via
    ``MaskedReductionUtil``).
    """
    # One big MXU matmul for every timestep's input projection.
    xw = jnp.einsum("bti,ij->btj", x, W) + b
    return lstm_scan_preact(RW, xw, carry, afn=afn, gate_fn=gate_fn,
                            mask=mask, reverse=reverse)


def lstm_scan_preact(RW: Array, xw: Array, carry: Carry, *,
                     afn, gate_fn, mask: Optional[Array] = None,
                     reverse: bool = False) -> Tuple[Array, Carry]:
    """The recurrent chain of :func:`lstm_scan`, taking the already-
    projected (batch, time, 4H) preactivations.  Split out so callers that
    reuse the projection across invocations (the sequence-parallel ring
    scan in ``parallel/sequence.py``) don't recompute it per round."""
    H = RW.shape[0]
    RWg = RW[:, :4 * H]
    w_ff = RW[:, 4 * H]       # forget-gate peephole (reads c_prev)
    w_oo = RW[:, 4 * H + 1]   # output-gate peephole (reads c_current)
    w_gg = RW[:, 4 * H + 2]   # input-mod-gate peephole (reads c_prev)

    xw_t = jnp.swapaxes(xw, 0, 1)                       # (time, batch, 4H)
    mask_t = (None if mask is None
              else jnp.swapaxes(mask, 0, 1))            # (time, batch)
    # Scan carries must be dtype-stable; under mixed precision (bf16
    # activations, f32 weights) the step body promotes, so promote the
    # incoming carry once up front.
    res_dtype = jnp.result_type(xw.dtype, RW.dtype)
    carry = jax.tree.map(lambda a: a.astype(res_dtype), carry)

    def step(c_prev_pair: Carry, inputs):
        h_prev, c_prev = c_prev_pair
        if mask_t is None:
            ifog = inputs
        else:
            ifog, m = inputs
        ifog = ifog + h_prev @ RWg
        z = afn(ifog[:, :H])                            # block input
        f = gate_fn(ifog[:, H:2 * H] + c_prev * w_ff)
        g = gate_fn(ifog[:, 3 * H:4 * H] + c_prev * w_gg)
        c = f * c_prev + g * z
        o = gate_fn(ifog[:, 2 * H:3 * H] + c * w_oo)
        h = o * afn(c)
        if mask_t is None:
            return (h, c), h
        keep = (m > 0)[:, None]
        h_new = jnp.where(keep, h, h_prev)
        c_new = jnp.where(keep, c, c_prev)
        return (h_new, c_new), jnp.where(keep, h, 0.0)

    xs = xw_t if mask_t is None else (xw_t, mask_t)
    carry = _match_varying(carry, xw_t)
    final, ys = lax.scan(step, carry, xs, reverse=reverse)
    return jnp.swapaxes(ys, 0, 1), final


@dataclasses.dataclass
class BaseRecurrentLayer(BaseLayerConfig):
    """Layers consuming (batch, time, features) activations and optionally
    carrying hidden state across calls (tBPTT / ``rnnTimeStep``)."""

    INPUT_KIND = "rnn"
    # Whether hidden state can be meaningfully carried across time chunks.
    # False for bidirectional layers: the backward scan needs the whole
    # sequence (the reference GravesBidirectionalLSTM.rnnTimeStep throws
    # UnsupportedOperationException).
    SUPPORTS_CARRY = True

    n_in: int = 0
    n_out: int = 0

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in <= 0:
            if input_type.kind != "recurrent":
                raise ValueError(
                    f"{type(self).__name__} needs recurrent input, got "
                    f"{input_type.kind}")
            self.n_in = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        ts = input_type.timesteps if input_type.kind == "recurrent" else -1
        return _inputs.recurrent(self.n_out, ts)

    # -- stateful-sequence contract ---------------------------------------
    def init_carry(self, batch: int, dtype) -> Carry:
        raise NotImplementedError

    def forward_seq(self, params: ParamTree, x: Array, carry, *,
                    train: bool, rng=None, mask: Optional[Array] = None):
        """(out, new_carry); carry threads tBPTT/streaming state."""
        raise NotImplementedError

    def forward(self, params: ParamTree, state: StateTree, x: Array, *,
                train: bool, rng=None, mask=None):
        out, _ = self.forward_seq(
            params, x, self.init_carry(x.shape[0], x.dtype),
            train=train, rng=rng, mask=mask)
        return out, state


@serde.register("graves_lstm")
@dataclasses.dataclass
class GravesLSTM(BaseRecurrentLayer):
    """Peephole LSTM (reference ``nn/conf/layers/GravesLSTM.java`` /
    ``nn/layers/recurrent/GravesLSTM.java``)."""

    forget_gate_bias_init: float = 1.0
    gate_activation_fn: str = "sigmoid"

    def param_order(self) -> tuple[str, ...]:
        # GravesLSTMParamInitializer.java:47-49 layout: W, RW, b.
        return ("W", "RW", "b")

    def init_params(self, rng: jax.Array, dtype=jnp.float32) -> ParamTree:
        kw, kr = jax.random.split(rng)
        H = self.n_out
        b = jnp.zeros((4 * H,), dtype)
        b = b.at[H:2 * H].set(self.forget_gate_bias_init)
        return {
            "W": init_weights(kw, (self.n_in, 4 * H),
                              self.weight_init or "xavier", self.dist, dtype),
            "RW": init_weights(kr, (H, 4 * H + 3),
                               self.weight_init or "xavier", self.dist, dtype),
            "b": b,
        }

    def init_carry(self, batch: int, dtype) -> Carry:
        H = self.n_out
        return (jnp.zeros((batch, H), dtype), jnp.zeros((batch, H), dtype))

    def forward_seq(self, params: ParamTree, x: Array, carry: Carry, *,
                    train: bool, rng=None, mask: Optional[Array] = None):
        x = self.apply_dropout(x, train, rng)
        return lstm_scan(
            params["W"], params["RW"], params["b"], x, carry,
            afn=_activations.get(self.activation),
            gate_fn=_activations.get(self.gate_activation_fn),
            mask=mask)


@serde.register("graves_bidirectional_lstm")
@dataclasses.dataclass
class GravesBidirectionalLSTM(BaseRecurrentLayer):
    """Bidirectional peephole LSTM; forward and backward passes run the same
    cell and their outputs are SUMMED (reference
    ``GravesBidirectionalLSTM.java:227`` ``fwdOutput.addi(backOutput)``).
    Param keys WF/RWF/bF + WB/RWB/bB
    (``GravesBidirectionalLSTMParamInitializer.java:47-53``)."""

    SUPPORTS_CARRY = False

    forget_gate_bias_init: float = 1.0
    gate_activation_fn: str = "sigmoid"

    def param_order(self) -> tuple[str, ...]:
        return ("WF", "RWF", "bF", "WB", "RWB", "bB")

    def init_params(self, rng: jax.Array, dtype=jnp.float32) -> ParamTree:
        H = self.n_out
        keys = jax.random.split(rng, 4)
        out: Dict[str, Array] = {}
        for d, (kw, kr) in zip("FB", ((keys[0], keys[1]),
                                      (keys[2], keys[3]))):
            b = jnp.zeros((4 * H,), dtype)
            b = b.at[H:2 * H].set(self.forget_gate_bias_init)
            out["W" + d] = init_weights(
                kw, (self.n_in, 4 * H), self.weight_init or "xavier",
                self.dist, dtype)
            out["RW" + d] = init_weights(
                kr, (H, 4 * H + 3), self.weight_init or "xavier", self.dist,
                dtype)
            out["b" + d] = b
        return out

    def init_carry(self, batch: int, dtype):
        H = self.n_out
        zero = lambda: (jnp.zeros((batch, H), dtype),
                        jnp.zeros((batch, H), dtype))
        return (zero(), zero())

    def forward_seq(self, params: ParamTree, x: Array, carry, *,
                    train: bool, rng=None, mask: Optional[Array] = None):
        x = self.apply_dropout(x, train, rng)
        afn = _activations.get(self.activation)
        gate = _activations.get(self.gate_activation_fn)
        fwd_carry, bwd_carry = carry
        out_f, new_f = lstm_scan(params["WF"], params["RWF"], params["bF"],
                                 x, fwd_carry, afn=afn, gate_fn=gate,
                                 mask=mask)
        out_b, new_b = lstm_scan(params["WB"], params["RWB"], params["bB"],
                                 x, bwd_carry, afn=afn, gate_fn=gate,
                                 mask=mask, reverse=True)
        return out_f + out_b, (new_f, new_b)


@serde.register("rnn_output")
@dataclasses.dataclass
class RnnOutputLayer(BaseRecurrentLayer):
    """Time-distributed dense + loss head (reference
    ``nn/conf/layers/RnnOutputLayer.java`` /
    ``nn/layers/recurrent/RnnOutputLayer.java``): the same W/b applied at
    every timestep, scored against (batch, time, classes) labels with an
    optional (batch, time) mask."""

    activation: str = "softmax"
    loss: str = "mcxent"

    def param_order(self) -> tuple[str, ...]:
        return ("W", "b")

    def init_params(self, rng: jax.Array, dtype=jnp.float32) -> ParamTree:
        kw, _ = jax.random.split(rng)
        return {
            "W": init_weights(kw, (self.n_in, self.n_out),
                              self.weight_init or "xavier", self.dist, dtype),
            "b": jnp.full((self.n_out,), self.bias_init or 0.0, dtype),
        }

    def init_carry(self, batch: int, dtype):
        return ()

    def forward_seq(self, params: ParamTree, x: Array, carry, *,
                    train: bool, rng=None, mask=None):
        x = self.apply_dropout(x, train, rng)
        z = x @ params["W"] + params["b"]
        return self._activate(z), carry

    def pre_output(self, params: ParamTree, x: Array) -> Array:
        return x @ params["W"] + params["b"]

    def compute_score(self, labels: Array, preout: Array,
                      mask: Optional[Array] = None,
                      average: bool = True) -> Array:
        return _losses.score(self.loss, labels, preout, self.activation,
                             mask, average)

    def compute_score_examples(self, labels: Array, preout: Array,
                               mask: Optional[Array] = None) -> Array:
        """Per-example scores (reference
        ``BaseOutputLayer.computeScoreForExamples``)."""
        return _losses.score_examples(self.loss, labels, preout,
                                      self.activation, mask)
