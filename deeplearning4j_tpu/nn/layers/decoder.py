"""Decoder-block layers: token embedding, RMS norm and LayerNorm, gated
feed-forward, latent attention over a compressed ring (over every
position, over the rows a learned indexer selects, or over a window's
ring that wraps, gated headwise or not), grouped-query
attention over the cached rows a learned indexer selects, dense
grouped-query attention over a ring that grows or over a window's ring
that wraps, a routed expert layer (sigmoid or softmax scores, shared
experts summed or averaged), manifold-constrained hyper-connections,
and the language-model head, with a table of its own or the
embedding's.

Everything here is plain ``jax.numpy`` under the container's
``layer.<name>`` scopes, but for the kernels that ``ops/`` puts under
the two attentions (``ops.attention``) and under the routed experts of
a long chunk (``ops.experts``), each chosen from the call's shapes
alone; the parts a trace has to tell apart open a sub-scope
(``monitor.subscope``: ``layer.<name>.experts``, ``.latent_attention``,
``.router``, ``.shared``, ``.sinkhorn``, ``.indexer``, ``.select``,
``.sparse_attention``, ``.window_attention``, ``.full_attention``,
``.sparse_latent_attention``, ``.window_latent_attention``).

The equations are DeepSeek-V2/V3's for latent attention (MLA), routing
and the expert layer, DeepSeek-V3.2's for the indexer and its
selection, and those of "Manifold-Constrained Hyper-Connections"
(arXiv:2512.24880) for the residual path; the plain references the
benchmark compares with are ``benchmark/reference/mla_moe_decoder.py``,
``gqa_sparse_moe.py``, ``gqa_window_moe.py`` (Cohere2's window and
full layers under one norm a block) and ``mla_sparse_window_moe.py``
(dots3's latent attention over selected rows beside latent window
layers), written apart from this file.

Activations are (batch, time, features); the residual path of a
hyper-connected model is (batch, time, streams, features).
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import jax
import jax.numpy as jnp

from ... import monitor as _monitor
from ...ops.attention import (_einsum_acc, gqa_attention_path,
                              gqa_ring_attention, gqa_ring_update,
                              latent_ring_attention, latent_ring_path,
                              joined_width, latent_ring_update,
                              latent_walk_path, ring_write,
                              sparse_attention_path,
                              sparse_latent_attention, sparse_ring_attention,
                              sparse_ring_update, window_latent_attention,
                              window_ring_slots)
from ...ops.experts import (dense_experts, grouped_experts,
                            held_rows_experts, held_token_rows,
                            moe_experts_path)
from ..conf import inputs as _inputs
from ..conf import serde
from ..weights import Distribution, init_weights
from .base import (Array, BaseLayerConfig, FeedForwardLayerConfig,
                   ParamTree)
from .recurrent import BaseRecurrentLayer

_HIGHEST = jax.lax.Precision.HIGHEST


def _acc(dtype):
    """float32 for the small float32 islands (norms, routing, stream
    mixing); float64 stays float64 (the CPU parity tests)."""
    return jnp.promote_types(dtype, jnp.float32)


def rms_normalize(x: Array, eps: float, gain: Optional[Array] = None):
    """``x / sqrt(mean(x^2) + eps)`` over the last axis, in float32,
    times ``gain`` where given; the caller casts back."""
    xf = x.astype(_acc(x.dtype))
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y if gain is None else y * gain.astype(y.dtype)


def _matrix(layer: BaseLayerConfig, rng, shape, dtype) -> Array:
    return init_weights(rng, shape, layer.weight_init or "xavier",
                        layer.dist, dtype)


def _normal(rng, shape, std: float, dtype) -> Array:
    if not std:
        return jnp.zeros(shape, dtype)
    return Distribution(kind="normal", std=float(std)).sample(
        rng, shape, dtype)


def _gated(x: Array, wg: Array, wu: Array, wd: Array) -> Array:
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


# ------------------------------------------------------------- embedding
@serde.register("token_embedding")
@dataclasses.dataclass
class TokenEmbedding(FeedForwardLayerConfig):
    """Integer ids (batch, time) or (batch, time, 1) to rows of a
    (``n_in`` = vocabulary, ``n_out``) table."""

    def param_order(self) -> tuple:
        return ("W",)

    def init_params(self, rng, dtype=jnp.float32) -> ParamTree:
        return {"W": _matrix(self, rng, (self.n_in, self.n_out), dtype)}

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        ids = x[..., 0] if x.ndim == 3 else x
        return jnp.take(params["W"], ids.astype(jnp.int32), axis=0), state


@serde.register("lm_head")
@dataclasses.dataclass
class LMHead(FeedForwardLayerConfig):
    """Hidden states to float32 logits over the vocabulary through a
    (hidden, vocabulary) matrix of its own; no bias.  A model that ties
    its head to the embedding takes :class:`TiedLMHead`."""

    def param_order(self) -> tuple:
        return ("W",)

    def init_params(self, rng, dtype=jnp.float32) -> ParamTree:
        return {"W": _matrix(self, rng, (self.n_in, self.n_out), dtype)}

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        return _einsum_acc("btc,cv->btv", x, params["W"],
                           _acc(x.dtype)), state


@serde.register("tied_lm_head")
@dataclasses.dataclass
class TiedLMHead(FeedForwardLayerConfig):
    """Hidden states to float32 logits over the vocabulary through the
    embedding's own table: ``logit_scale * x E^T`` with ``E`` the
    (vocabulary, hidden) parameter ``W`` of the vertex ``tied_to``.  The
    layer has no parameter: the container hands it the other vertex's
    (``TIED_PARAMS``), so the table is held once, a new table set on the
    embedding is the head's at the next step, and a gradient reaches it
    from both uses."""

    tied_to: str = "embed"
    logit_scale: float = 1.0

    #: the parameters :meth:`forward` reads of the vertex ``tied_to``
    TIED_PARAMS = ("W",)

    def param_order(self) -> tuple:
        return ()

    def init_params(self, rng, dtype=jnp.float32) -> ParamTree:
        return {}

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        logits = _einsum_acc("btc,vc->btv", x, params["W"], _acc(x.dtype))
        if self.logit_scale != 1.0:
            logits = logits * jnp.asarray(self.logit_scale, logits.dtype)
        return logits, state


# ------------------------------------------------------------------ norm
@serde.register("rms_norm")
@dataclasses.dataclass
class RMSNorm(BaseLayerConfig):
    n_out: int = 0
    eps: float = 1e-6

    def output_type(self, input_type):
        return input_type

    def set_n_in(self, input_type) -> None:
        if self.n_out <= 0:
            self.n_out = input_type.flat_size()

    def param_order(self) -> tuple:
        return ("gain",)

    def init_params(self, rng, dtype=jnp.float32) -> ParamTree:
        return {"gain": jnp.ones((self.n_out,), dtype)}

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        return rms_normalize(x, self.eps, params["gain"]).astype(x.dtype), \
            state


@serde.register("layer_norm")
@dataclasses.dataclass
class LayerNorm(RMSNorm):
    """``(x - mean(x)) / sqrt(var(x) + eps) * gain`` over the last axis,
    float32 inside; a gain and no bias (Cohere's)."""

    eps: float = 1e-5

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        xf = x.astype(_acc(x.dtype))
        centred = xf - jnp.mean(xf, axis=-1, keepdims=True)
        return rms_normalize(centred, self.eps,
                             params["gain"]).astype(x.dtype), state


# ---------------------------------------------------------- feed-forward
@serde.register("gated_feed_forward")
@dataclasses.dataclass
class GatedFeedForward(FeedForwardLayerConfig):
    """``(silu(x Wg) * x Wu) Wd`` with ``n_in`` = ``n_out`` = hidden and
    ``width`` the inner size."""

    width: int = 0

    def param_order(self) -> tuple:
        return ("Wg", "Wu", "Wd")

    def init_params(self, rng, dtype=jnp.float32) -> ParamTree:
        kg, ku, kd = jax.random.split(rng, 3)
        return {"Wg": _matrix(self, kg, (self.n_in, self.width), dtype),
                "Wu": _matrix(self, ku, (self.n_in, self.width), dtype),
                "Wd": _matrix(self, kd, (self.width, self.n_out), dtype)}

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        return _gated(x, params["Wg"], params["Wu"], params["Wd"]), state


@serde.register("mixture_of_experts")
@dataclasses.dataclass
class MixtureOfExperts(FeedForwardLayerConfig):
    """Routed experts with a selection bias and shared experts
    (DeepSeek-V3's ``noaux_tc`` with one group): ``g = sigmoid(x Wr)``,
    the ``top_k`` largest of ``g + bias`` are chosen, their weights are
    ``g`` there, divided by their sum and times ``routed_scaling``;
    ``y = sum_i w_i E_i(x) + E_shared(x)``.  No token is dropped.
    ``scoring="softmax"`` scores with ``g = softmax(x Wr)`` over all
    ``n_experts`` instead (the Qwen3-MoE family's router: no bias drawn,
    no scaling, ``n_shared`` 0).  ``n_shared`` shared experts lie side by
    side in ``Sg``/``Su``/``Sd`` (``n_shared x width`` wide: their sum is
    one gated product); ``shared_combine="average"`` adds their mean
    instead of their sum (Cohere's), one factor on that product.

    ``experts_held`` (default: all) says which experts this layer holds:
    it routes over all ``n_experts`` and computes the held experts' part
    of the sum (the shared expert included), which is what one chip of
    an expert-parallel deployment computes before the exchange.

    The experts' matrices are stored side by side as plain matrices,
    ``Wg``/``Wu`` (hidden, held * width) and ``Wd`` (held * width,
    hidden), expert after expert, so that all held experts are three
    plain matrix products (a third axis would have the TPU's compiler
    treat the experts as a convolution's window); each token's unchosen
    experts are weighted 0.  That dense form serves while the tokens are
    few (the token step: the experts' bytes bound it); a long chunk on a
    TPU takes the grouped form, ``ops.experts.grouped_experts``, which
    puts each (token, pick) pair through its own expert only, over the
    same matrices where they lie, and so does the token step of a share
    whose tokens are many for the few picks that land on it, or so few
    that most held experts get no pick and are not read
    (``experts_path``: from the call's shapes alone).  State
    ``expert_tokens`` (``n_experts`` int32) counts the picks of the last
    call, for ``moe_expert_tokens_total``, ``experts_spilled``
    (int32) says whether its held pairs outgrew the grouped form's rows
    and took further rounds, for ``moe_experts_spilled_total``, and
    ``experts_read`` (int32) how many held experts' blocks it read (every
    held one in the dense forms, the picked ones in the grouped form),
    for ``moe_experts_read_total``.
    """

    n_experts: int = 8
    top_k: int = 2
    width: int = 0
    n_shared: int = 1
    routed_scaling: float = 1.0
    norm_topk: bool = True
    router_bias_std: float = 0.0
    experts_held: Optional[List[int]] = None
    scoring: str = "sigmoid"
    shared_combine: str = "sum"

    def held(self) -> List[int]:
        return (list(range(self.n_experts)) if self.experts_held is None
                else [int(e) for e in self.experts_held])

    def param_order(self) -> tuple:
        return ("router", "router_bias", "Wg", "Wu", "Wd") + (
            ("Sg", "Su", "Sd") if self.n_shared else ())

    def init_params(self, rng, dtype=jnp.float32) -> ParamTree:
        c, f, e = self.n_in, self.width, self.n_experts
        kr, kb, kg, ku, kd, ksg, ksu, ksd = jax.random.split(rng, 8)

        def experts(key, shape, axis):
            # each expert from a key of its own (the matrix's key folded
            # with the expert's id), all in one draw: a share is drawn
            # without the whole layer ever existing, and holds what the
            # whole layer has
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
                jnp.asarray(self.held(), jnp.uint32))
            w = _matrix(self, keys, shape, dtype)       # (held, *shape)
            return (jnp.moveaxis(w, 0, 1).reshape(c, -1) if axis
                    else w.reshape(-1, c))

        p = {"router": _matrix(self, kr, (c, e), dtype),
             "router_bias": _normal(kb, (e,), self.router_bias_std, dtype),
             "Wg": experts(kg, (c, f), 1),
             "Wu": experts(ku, (c, f), 1),
             "Wd": experts(kd, (f, c), 0)}
        if self.n_shared:
            fs = f * self.n_shared
            p.update(Sg=_matrix(self, ksg, (c, fs), dtype),
                     Su=_matrix(self, ksu, (c, fs), dtype),
                     Sd=_matrix(self, ksd, (fs, c), dtype))
        return p

    def init_state(self, dtype=jnp.float32):
        return {"expert_tokens": jnp.zeros((self.n_experts,), jnp.int32),
                "experts_spilled": jnp.zeros((), jnp.int32),
                "experts_read": jnp.zeros((), jnp.int32)}

    def route(self, params: ParamTree, x: Array):
        """(tokens, top_k) expert indices and weights, in float32."""
        acc = _acc(x.dtype)
        score = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[
            self.scoring]
        g = score(jnp.matmul(
            x.astype(acc), params["router"].astype(acc),
            precision=_HIGHEST))
        _, idx = jax.lax.top_k(g + params["router_bias"].astype(acc),
                               self.top_k)
        # g at the picks, compared and not looked up: the same numbers
        # (a gather of 256 x 8 of them costs a v5e 21 us; PERF.md, PR 36)
        w = jnp.sum(jnp.where(
            idx[..., None] == jnp.arange(self.n_experts, dtype=idx.dtype),
            g[..., None, :], 0.0), axis=-1)
        if self.norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx, w * self.routed_scaling

    def experts_path(self, tokens: int, dtype, train: bool = False) -> str:
        """``"grouped"`` or ``"dense"``: the form ``forward`` takes for
        ``tokens`` tokens stored in ``dtype``, by the op's own predicate
        (host code asks it without tracing the step)."""
        return moe_experts_path(tokens, len(self.held()), self.n_experts,
                                self.top_k, self.n_in, self.width, dtype,
                                train)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        shape = x.shape
        x = x.reshape(-1, shape[-1])
        path = self.experts_path(x.shape[0], x.dtype, train)
        with _monitor.subscope("router"):
            idx, w = self.route(params, x)
            if path != "grouped":
                hit = idx[:, :, None] == jnp.asarray(self.held(), jnp.int32)
                # (tokens, held): the weight of each held expert, 0
                # unchosen
                combine = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)
            counts = jnp.sum(
                idx[:, :, None] == jnp.arange(self.n_experts)[None, None],
                axis=(0, 1), dtype=jnp.int32)
        # the dense form reads every held expert
        spilled, read = (jnp.zeros((), jnp.int32),
                         jnp.asarray(len(self.held()), jnp.int32))
        matrices = params["Wg"], params["Wu"], params["Wd"]
        with _monitor.subscope("experts"):
            if path == "grouped":
                y, spilled, read = grouped_experts(
                    x, idx, w, *matrices, held=self.held(),
                    n_experts=self.n_experts)
            elif path == "held_rows":
                y, spilled, read = held_rows_experts(
                    x, jnp.any(hit, axis=(1, 2)), combine, *matrices,
                    rows=held_token_rows(x.shape[0], self.top_k,
                                         len(self.held()), self.n_experts))
            else:
                y = dense_experts(x, combine, *matrices)
        if self.n_shared:
            with _monitor.subscope("shared"):
                shared = _gated(x, params["Sg"], params["Su"], params["Sd"])
                if self.shared_combine == "average":
                    shared = shared * jnp.asarray(1.0 / self.n_shared,
                                                  shared.dtype)
                elif self.shared_combine != "sum":
                    raise ValueError(f"shared experts combine by 'sum' or "
                                     f"'average', not {self.shared_combine!r}")
                y = y + shared
        return y.reshape(shape), {"expert_tokens": counts,
                                  "experts_spilled": spilled,
                                  "experts_read": read}


# -------------------------------------------------------------- attention
def yarn_inv_freq(dim: int, theta: float, scaling: Optional[dict]):
    """Rotary inverse frequencies (``dim // 2`` of them) and the factor
    on cos/sin, with YaRN scaling as DeepSeek's code has it: a linear
    ramp between interpolated (``/ factor``) and extrapolated
    frequencies over the dimensions that turn ``beta_fast`` to
    ``beta_slow`` times within the original context."""
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / (theta ** exponent)
    if not scaling:
        return extra, 1.0
    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv_freq = (extra / factor) * ramp + extra * (1.0 - ramp)
    return inv_freq, (yarn_mscale(factor, scaling.get("mscale", 1))
                      / yarn_mscale(factor, scaling.get("mscale_all_dim", 0)))


def yarn_mscale(factor: float, mscale: float) -> float:
    if factor <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def rotate(x: Array, positions: Array, inv_freq: Array,
           factor: float = 1.0) -> Array:
    """Rotary embedding of the last axis of (batch, time, ..., dim) by
    (time,) positions, pairs ``(2i, 2i+1)`` turned by
    ``position * inv_freq[i]``; float32 inside."""
    acc = _acc(x.dtype)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    extra = (1,) * (x.ndim - 3)
    cos = cos.reshape((1, -1) + extra + (cos.shape[-1],)).astype(acc)
    sin = sin.reshape(cos.shape).astype(acc)
    pairs = x.astype(acc).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rotate_half(x: Array, positions: Array, inv_freq: Array) -> Array:
    """Rotary embedding of the last axis of (batch, time, ..., dim) by
    (time,) positions over the half-split pairs ``(i, i + dim / 2)``
    (the Llama/Qwen layout, where :func:`rotate` turns ``(2i, 2i+1)``);
    float32 inside."""
    acc = _acc(x.dtype)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (1, -1) + (1,) * (x.ndim - 3) + (angle.shape[-1],)
    cos = jnp.cos(angle).reshape(shape).astype(acc)
    sin = jnp.sin(angle).reshape(shape).astype(acc)
    a, b = jnp.split(x.astype(acc), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


@serde.register("latent_attention")
@dataclasses.dataclass
class LatentAttention(BaseRecurrentLayer):
    """Multi-head latent attention (DeepSeek-V2's MLA) over a ring of
    compressed rows.

    ``c_q = norm(x Wqa)``, ``q = c_q Wqb`` (heads of ``d_nope + d_rope``);
    ``[c_kv | k_r] = x Wkva``, ``c_kv = norm(c_kv)``, ``k_r`` rotated and
    shared by all heads; keys and values are ``c_kv Wkvb`` (heads of
    ``d_nope + d_v``).  The carry is ``(c_kv ring (batch, capacity,
    kv_rank), k_r ring (batch, capacity, d_rope), cursor)``:
    ``kv_rank + d_rope`` numbers a token, nothing per head.  One path
    serves prefill chunks, single steps and ``output()`` (from a zero
    ring): the key half of ``Wkvb`` is absorbed into the query, scores
    and context are taken against the latent ring, and the value half
    is applied to the latent context.  ``Wqb`` and ``Wkvb`` are
    multiplied in the forms :meth:`lay` gives them, laid once by a
    served net or inside the step from the stored parameters: the same
    products either way.

    Three options, each off by default (dots3's two kinds of layer):

    - ``window``: query ``t`` sees ``t - window < s <= t`` (the query's
      own position counted), over a ring that WRAPS: position ``p`` in
      slot ``p mod capacity``, the capacity the layer's own whatever
      ``cache_len`` it is handed (``ops.attention.window_ring_slots(
      window, chunk)``: a chunk of up to ``chunk`` positions is written
      before it is read), never grown; ``STATE_KIND`` ``"window_latent"``.
    - an indexer (``index_heads`` > 0; DeepSeek-V3.2's, its queries
      from the query latent): ``qI = c_q WqI`` (``index_heads`` of
      ``index_dim``), ``kI = LayerNorm(x WkI)`` (one head, gain and
      bias, cached), both turned over the half-split pairs of their
      first ``index_rope`` numbers, ``w = x Ww``; ``I[t, s] = sum_j
      w[t, j] relu(qI[t, j] . kI[s])`` in float32; query ``t`` attends
      over the ``topk`` visible positions of largest ``I[t, .]`` (every
      visible one while they are no more; equal scores: the lowest
      position first); ``STATE_KIND`` ``"sparse_latent"``.
    - ``gate``: each head's output times ``sigmoid(x Wg)[head]``
      (``Wg`` hidden -> heads; headwise), before ``Wo``.

    With a window or an indexer the latent ring is JOINED, ``(batch,
    capacity, joined_width(kv_rank, d_rope))``: a slot's ``c_kv`` and
    ``k_r`` one row of whole lanes, which one copy reads
    (``ops.attention``'s joined latent ring); an indexer adds its key
    ring ``(batch, capacity, index_dim)`` before the cursor.  Without
    either the layer keeps its two rings: its kernel reads them within
    a tenth of the HBM roof on a TPU v5e (the narrow ``k_r`` ring taken
    slots-minor, as XLA lays it), and a joined row's padding to whole
    lanes would add a ninth to those bytes (576 numbers to 640).
    """

    HAS_KV_RING = True

    activation: str = "identity"
    n_heads: int = 1
    q_rank: int = 0
    kv_rank: int = 0
    d_nope: int = 0
    d_rope: int = 0
    d_v: int = 0
    eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    cache_len: int = 128
    window: Optional[int] = None
    chunk: int = 256
    index_heads: int = 0
    index_dim: int = 0
    index_rope: int = 0
    topk: int = 2048
    gate: bool = False

    @property
    def STATE_KIND(self) -> str:    # serving_session_state_bytes{kind=}
        if self.window:
            return "window_latent"
        return "sparse_latent" if self.index_heads else "latent"

    @property
    def RING_GROWS(self) -> bool:
        """Whether the ring's capacity follows the session's length."""
        return not self.window

    @property
    def _joined(self) -> bool:
        return bool(self.window or self.index_heads)

    def param_order(self) -> tuple:
        return (("Wqa", "q_gain", "Wqb", "Wkva", "kv_gain", "Wkvb", "Wo")
                + (("Wg",) if self.gate else ())
                + (("WqI", "WkI", "kI_gain", "kI_bias", "Ww")
                   if self.index_heads else ()))

    def init_params(self, rng, dtype=jnp.float32) -> ParamTree:
        h, c = self.n_heads, self.n_in
        ka, kb, kc, kd, ko = jax.random.split(rng, 5)
        # the options' keys apart, so that a layer without them draws
        # what it always drew
        kg, ki = jax.random.fold_in(rng, 5), jax.random.fold_in(rng, 6)
        p = {
            "Wqa": _matrix(self, ka, (c, self.q_rank), dtype),
            "q_gain": jnp.ones((self.q_rank,), dtype),
            "Wqb": _matrix(self, kb, (self.q_rank,
                                      h * (self.d_nope + self.d_rope)), dtype),
            "Wkva": _matrix(self, kc, (c, self.kv_rank + self.d_rope), dtype),
            "kv_gain": jnp.ones((self.kv_rank,), dtype),
            "Wkvb": _matrix(self, kd, (self.kv_rank,
                                       h * (self.d_nope + self.d_v)), dtype),
            "Wo": _matrix(self, ko, (h * self.d_v, self.n_out), dtype),
        }
        if self.gate:
            p["Wg"] = _matrix(self, kg, (c, h), dtype)
        if self.index_heads:
            kq, kk, kw = jax.random.split(ki, 3)
            p.update(
                WqI=_matrix(self, kq, (self.q_rank,
                                       self.index_heads * self.index_dim),
                            dtype),
                WkI=_matrix(self, kk, (c, self.index_dim), dtype),
                kI_gain=jnp.ones((self.index_dim,), dtype),
                kI_bias=jnp.zeros((self.index_dim,), dtype),
                Ww=_matrix(self, kw, (c, self.index_heads), dtype))
        return p

    def sm_scale(self) -> float:
        scale = (self.d_nope + self.d_rope) ** -0.5
        s = self.rope_scaling
        if s and s.get("mscale_all_dim", 0):
            scale *= yarn_mscale(float(s["factor"]), s["mscale_all_dim"]) ** 2
        return scale

    # -------------------------------------------------------------- carry
    def init_carry(self, batch: int, dtype, cache_len: Optional[int] = None):
        cap = int(cache_len if cache_len is not None else self.cache_len)
        if self.window:         # sized by its window, whatever it is handed
            cap = window_ring_slots(self.window, self.chunk)
        if cap < 1:
            raise ValueError("cache_len must be >= 1")
        return self._rings(batch, dtype, cap)

    def _rings(self, batch: int, dtype, cap: int):
        cursor = jnp.zeros((), jnp.int32)
        if not self._joined:
            return (jnp.zeros((batch, cap, self.kv_rank), dtype),
                    jnp.zeros((batch, cap, self.d_rope), dtype), cursor)
        ring = jnp.zeros((batch, cap, joined_width(self.kv_rank,
                                                   self.d_rope)), dtype)
        if self.window:
            return ring, cursor
        return ring, jnp.zeros((batch, cap, self.index_dim), dtype), cursor

    def grow_carry(self, carry, cache_len: int):
        if self.window:         # sized once, at its window
            return carry
        *rings, cursor = carry
        cap = rings[0].shape[1]
        if cache_len < cap:
            raise ValueError(
                f"cannot shrink the latent ring from {cap} to {cache_len}")
        pad = [(0, 0), (0, cache_len - cap), (0, 0)]
        return tuple(jnp.pad(r, pad) for r in rings) + (cursor,)

    # ------------------------------------------------------------ forward
    @property
    def LAID_FROM(self) -> tuple:
        """The stored matrices :meth:`lay` turns into the forms the step
        multiplies; every other parameter is multiplied as it is
        stored."""
        return ("Wqb", "Wkvb") + (("WqI",) if self.index_heads else ())

    def lay(self, params: ParamTree) -> ParamTree:
        """The forms ``forward_seq`` multiplies in place of ``Wqb`` and
        ``Wkvb`` (stored (in, heads x out), as ``init_params`` draws and
        a serializer writes them): split by what reads them and turned
        heads-major with the contracted rank minor, the orientation the
        compiled step reads without a copy (``tools/step_copies.py``):

        - ``Wq_nope`` (heads, d_nope, q_rank): the query's no-position
          columns;
        - ``Wq_rope`` (d_rope / 2, 2, heads, q_rank): its rotary
          columns, the two members of a rotated pair apart;
        - ``Wk_absorbed`` (heads, d_nope, kv_rank): the key half of
          ``Wkvb``, which the query absorbs;
        - ``Wv`` (heads, kv_rank, d_v): its value half;
        - ``Wq_index`` (index_heads, index_dim, q_rank): an indexer's
          ``WqI``, turned likewise.

        Transposes and column splits: every number is kept as it is.
        A served net runs this once when its weights are set
        (``ComputationGraph.served_params``); ``forward_seq`` given
        stored parameters runs it inside the step."""
        h = self.n_heads
        wqb = params["Wqb"].reshape(self.q_rank, h, self.d_nope + self.d_rope)
        wkvb = params["Wkvb"].reshape(self.kv_rank, h,
                                      self.d_nope + self.d_v)
        laid = {
            "Wq_nope": wqb[..., :self.d_nope].transpose(1, 2, 0),
            "Wq_rope": wqb[..., self.d_nope:].reshape(
                self.q_rank, h, self.d_rope // 2, 2).transpose(2, 3, 1, 0),
            "Wk_absorbed": wkvb[..., :self.d_nope].transpose(1, 2, 0),
            "Wv": wkvb[..., self.d_nope:].transpose(1, 0, 2),
        }
        if self.index_heads:
            laid["Wq_index"] = params["WqI"].reshape(
                self.q_rank, self.index_heads, self.index_dim).transpose(
                    1, 2, 0)
        return laid

    def compress(self, params: ParamTree, x: Array, turn):
        """What the cache holds of ``x``: (normed ``c_kv``, ``k_r``
        rotated by ``turn``)."""
        kv = x @ params["Wkva"]
        c_kv = rms_normalize(kv[..., :self.kv_rank], self.eps,
                             params["kv_gain"]).astype(x.dtype)
        return c_kv, turn(kv[..., self.kv_rank:])

    def query_latent(self, laid: ParamTree, x: Array) -> Array:
        """``c_q = norm(x Wqa)``: what the queries (and an indexer's)
        are projected from."""
        return rms_normalize(x @ laid["Wqa"], self.eps,
                             laid["q_gain"]).astype(x.dtype)

    def queries(self, laid: ParamTree, c_q: Array, turn):
        """((batch, time, heads, d_nope), (.., d_rope) rotated by
        ``turn``) of the query latent ``c_q``, from the laid forms."""
        q_nope = jnp.einsum("btq,hdq->bthd", c_q, laid["Wq_nope"])
        q_rope = jnp.einsum("btq,pjhq->bthpj", c_q, laid["Wq_rope"])
        return q_nope, turn(q_rope.reshape(q_rope.shape[:3] + (self.d_rope,)))

    def indexer(self, laid: ParamTree, x: Array, c_q: Array,
                positions: Array):
        """The indexer's view of ``x`` (batch, time, hidden) at
        ``positions``, from the laid forms: ``(queries (batch, time,
        index_heads, index_dim) from the query latent ``c_q``, head
        weights (batch, time, index_heads), keys (batch, time,
        index_dim): LayerNorm(x WkI))``, queries and keys turned over the
        half-split pairs of their first ``index_rope`` numbers, the rest
        as they are."""
        r = self.index_rope
        inv_freq, _ = yarn_inv_freq(r, self.rope_theta, None)

        def turn(a):
            return jnp.concatenate(
                [rotate_half(a[..., :r], positions, inv_freq), a[..., r:]],
                axis=-1)

        q_idx = turn(jnp.einsum("btq,jdq->btjd", c_q, laid["Wq_index"]))
        k = (x @ laid["WkI"]).astype(_acc(x.dtype))
        k = rms_normalize(k - jnp.mean(k, axis=-1, keepdims=True), self.eps,
                          laid["kI_gain"])
        k = k + laid["kI_bias"].astype(k.dtype)
        return q_idx, x @ laid["Ww"], turn(k.astype(x.dtype))

    def init_state(self, dtype=jnp.float32):
        # an indexer's step says whether its selection settled equal
        # scores at the ``topk``-th place, for ``sparse_select_tied_total``
        if self.index_heads:
            return {"selection_tied": jnp.zeros((), jnp.int32)}
        return {}

    def forward_seq(self, params, x, carry, *, train, rng=None, mask=None):
        return self.forward_seq_counted(params, x, carry, train=train,
                                        rng=rng, mask=mask)[:2]

    def forward_seq_counted(self, params, x, carry, *, train, rng=None,
                            mask=None):
        """``forward_seq``, and after the carry, where the layer has an
        indexer, the step's state (``init_state``'s keys): what a served
        step counts."""
        t, cap = x.shape[1], carry[0].shape[1]
        if t > (cap - self.window + 1 if self.window else cap):
            raise ValueError(
                f"chunk of {t} timesteps exceeds what the latent ring of "
                f"{cap} slots takes at once"
                + (f" under a window of {self.window}" if self.window
                   else ""))
        return self._attend(params, x, carry, mask)

    def _attend(self, params, x, carry, mask=None):
        *rings, cursor = carry
        t = x.shape[1]
        # stored parameters (``fit``, ``output()``, a net not prepared,
        # a pinned version) are laid here, inside the step; behind a
        # barrier, so that the compiler multiplies the forms as made
        # and the two ways give the same numbers bit for bit
        laid = ({**params, **jax.lax.optimization_barrier(self.lay(params))}
                if "Wqb" in params else params)
        positions = cursor + jnp.arange(t, dtype=jnp.int32)
        inv_freq, factor = yarn_inv_freq(self.d_rope, self.rope_theta,
                                         self.rope_scaling)
        turn = lambda a: rotate(a, positions, inv_freq, factor)
        c_q = self.query_latent(laid, x)
        q_nope, q_rope = self.queries(laid, c_q, turn)
        compressed = self.compress(laid, x, turn)
        counted = ()
        if not self._joined:
            rings = latent_ring_update(*rings, cursor, *compressed)
            with _monitor.subscope("latent_attention"):
                q_lat = jnp.einsum("bthd,hdr->bthr", q_nope,
                                   laid["Wk_absorbed"])
                ctx = latent_ring_attention(q_lat, q_rope, *rings, cursor,
                                            sm_scale=self.sm_scale())
        else:
            # the query joined as the ring is: the absorbed no-position
            # half beside the rotary one and zeros to whole lanes, one
            # product a score
            fill = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(
                0, rings[0].shape[2] - self.kv_rank - self.d_rope)])
            q = fill(jnp.concatenate(
                [jnp.einsum("bthd,hdr->bthr", q_nope, laid["Wk_absorbed"]),
                 q_rope.astype(q_nope.dtype)], axis=-1))
            rings[0] = ring_write(rings[0], cursor,
                                  fill(jnp.concatenate(compressed, axis=-1)),
                                  wraps=bool(self.window))
            kw = dict(rank=self.kv_rank, sm_scale=self.sm_scale())
            if self.window:
                with _monitor.subscope("window_latent_attention"):
                    ctx = window_latent_attention(q, rings[0], cursor,
                                                  window=self.window, **kw)
            else:
                with _monitor.subscope("indexer"):
                    q_idx, w_idx, k_idx = self.indexer(laid, x, c_q,
                                                       positions)
                rings[1] = ring_write(rings[1], cursor, k_idx)
                ctx, tied = sparse_latent_attention(
                    q, q_idx, w_idx, *rings, cursor, topk=self.topk,
                    scope=_monitor.subscope, **kw)
                counted = ({"selection_tied": tied},)
        out = jnp.einsum("bthr,hrd->bthd", ctx, laid["Wv"])
        if self.gate:
            g = jax.nn.sigmoid((x @ laid["Wg"]).astype(_acc(x.dtype)))
            out = out * g[..., None].astype(out.dtype)
        out = self._activate(
            out.reshape(x.shape[:2] + (-1,)) @ laid["Wo"])
        if mask is not None:
            out = out * mask[..., None].astype(out.dtype)
        return (out, (*rings, cursor + jnp.asarray(t, jnp.int32))) + counted

    def attention_path(self, t: int, carry) -> str:
        """The form ``forward_seq`` takes for ``t`` new positions against
        ``carry``, by the op's own predicate (host code asks it without
        tracing the step): ``"streamed"`` or ``"dense"`` over the two
        rings of the plain layer, ``"gathered"``, ``"streamed"`` or
        ``"masked"`` over a joined ring."""
        if self._joined:
            ring = carry[0]
            return latent_walk_path(t, self.n_heads, ring.shape[2],
                                    self.kv_rank, ring.shape[1], ring.dtype,
                                    None if self.window else self.topk)
        c_ring, r_ring = carry[0], carry[1]
        return latent_ring_path(t, self.n_heads, c_ring.shape[2],
                                r_ring.shape[2], c_ring.shape[1],
                                c_ring.dtype)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        # the whole sequence at once: rings as long as it, which never
        # wrap, under the same rule of visibility
        return self._attend(params, x, self._rings(
            x.shape[0], x.dtype, x.shape[1]), mask)[0], state


@serde.register("sparse_grouped_query_attention")
@dataclasses.dataclass
class SparseGroupedQueryAttention(BaseRecurrentLayer):
    """Grouped-query attention over the cached rows a learned indexer
    selects (DeepSeek-V3.2's sparse attention under grouped-query heads).

    ``q = norm_head(x Wq)`` (``n_heads`` of ``head_dim``), ``k =
    norm_head(x Wk)``, ``v = x Wv`` (``n_kv_heads`` each; query head
    ``h`` reads key/value head ``h // (n_heads / n_kv_heads)``), rotary
    on ``q`` and ``k`` over the half-split pairs; ``norm_head`` an RMS
    norm over a head with one gain.  The indexer: ``qI = x WqI``
    (``index_heads`` of ``index_dim``), ``kI = LayerNorm(x WkI)`` (one
    head, cached), rotary on both, ``w = x Ww``; ``I[t, s] = sum_j
    w[t, j] relu(qI[t, j] . kI[s])`` in float32 whatever the storage;
    query ``t`` attends over the ``topk`` visible positions of largest
    ``I[t, .]`` (every visible one while they are no more; equal scores:
    the lowest position first), softmax of ``q . k / sqrt(head_dim)``
    over them.  The carry is ``(key/value ring (batch, capacity, 2 x
    n_kv_heads, head_dim), indexer-key ring (batch, capacity,
    index_dim), cursor)``: slots-major, a slot's key heads and then its
    value heads the rows of one tile, which a token step over a long
    ring fetches by one copy descriptor a selected slot.
    One path serves prefill chunks, single steps and ``output()`` (from
    a zero ring); the form the selection
    and the attention take is ``ops.attention.sparse_attention_path``'s,
    from the call's shapes alone.
    """

    HAS_KV_RING = True
    STATE_KIND = "sparse_kv"    # serving_session_state_bytes{kind=}

    activation: str = "identity"
    n_heads: int = 1
    n_kv_heads: int = 1
    head_dim: int = 0
    index_heads: int = 1
    index_dim: int = 0
    topk: int = 2048
    eps: float = 1e-6
    rope_theta: float = 10000.0
    cache_len: int = 128

    def param_order(self) -> tuple:
        return ("Wq", "q_gain", "Wk", "k_gain", "Wv", "Wo",
                "WqI", "WkI", "kI_gain", "kI_bias", "Ww")

    def init_params(self, rng, dtype=jnp.float32) -> ParamTree:
        h, g, d, c = self.n_heads, self.n_kv_heads, self.head_dim, self.n_in
        kq, kk, kv, ko, kqi, kki, kw = jax.random.split(rng, 7)
        return {
            "Wq": _matrix(self, kq, (c, h * d), dtype),
            "q_gain": jnp.ones((d,), dtype),
            "Wk": _matrix(self, kk, (c, g * d), dtype),
            "k_gain": jnp.ones((d,), dtype),
            "Wv": _matrix(self, kv, (c, g * d), dtype),
            "Wo": _matrix(self, ko, (h * d, self.n_out), dtype),
            "WqI": _matrix(self, kqi,
                           (c, self.index_heads * self.index_dim), dtype),
            "WkI": _matrix(self, kki, (c, self.index_dim), dtype),
            "kI_gain": jnp.ones((self.index_dim,), dtype),
            "kI_bias": jnp.zeros((self.index_dim,), dtype),
            "Ww": _matrix(self, kw, (c, self.index_heads), dtype),
        }

    # -------------------------------------------------------------- carry
    def init_carry(self, batch: int, dtype, cache_len: Optional[int] = None):
        cap = int(cache_len if cache_len is not None else self.cache_len)
        if cap < 1:
            raise ValueError("cache_len must be >= 1")
        return (jnp.zeros((batch, cap, 2 * self.n_kv_heads, self.head_dim),
                          dtype),
                jnp.zeros((batch, cap, self.index_dim), dtype),
                jnp.zeros((), jnp.int32))

    def grow_carry(self, carry, cache_len: int):
        *rings, cursor = carry
        cap = rings[0].shape[1]
        if cache_len < cap:
            raise ValueError(
                f"cannot shrink the key/value ring from {cap} to {cache_len}")
        pad = [(0, 0), (0, cache_len - cap)]
        return tuple(jnp.pad(r, pad + [(0, 0)] * (r.ndim - 2))
                     for r in rings) + (cursor,)

    # ------------------------------------------------------------ forward
    def _turn(self, positions: Array):
        """Rotary by ``positions`` (time,) over the whole last axis."""
        def turn(a):
            inv_freq, _ = yarn_inv_freq(a.shape[-1], self.rope_theta, None)
            return rotate_half(a, positions, inv_freq)
        return turn

    def indexer(self, params: ParamTree, x: Array, positions: Array):
        """The indexer's view of ``x`` (batch, time, hidden) at
        ``positions``: ``(queries (batch, time, index_heads, index_dim),
        head weights (batch, time, index_heads), keys (batch, time,
        index_dim): LayerNorm(x WkI))``, queries and keys rotated."""
        turn = self._turn(positions)
        q_idx = turn((x @ params["WqI"]).reshape(
            x.shape[:2] + (self.index_heads, self.index_dim)))
        k = (x @ params["WkI"]).astype(_acc(x.dtype))
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                              + self.eps)
        k = (k * params["kI_gain"].astype(k.dtype)
             + params["kI_bias"].astype(k.dtype))
        return q_idx, x @ params["Ww"], turn(k.astype(x.dtype))

    def init_state(self, dtype=jnp.float32):
        # whether the last step's selection settled equal scores at the
        # ``topk``-th place (its rare branch), for
        # ``sparse_select_tied_total``
        return {"selection_tied": jnp.zeros((), jnp.int32)}

    def forward_seq(self, params, x, carry, *, train, rng=None, mask=None):
        out, carry, _ = self.forward_seq_counted(
            params, x, carry, train=train, rng=rng, mask=mask)
        return out, carry

    def forward_seq_counted(self, params, x, carry, *, train, rng=None,
                            mask=None):
        """``forward_seq`` that also returns the step's state
        (``init_state``'s keys): what a served step counts."""
        kv_ring, i_ring, cursor = carry
        (b, t), cap = x.shape[:2], kv_ring.shape[1]
        if t > cap:
            raise ValueError(f"chunk of {t} timesteps exceeds the "
                             f"key/value ring's capacity {cap}")
        h, g, d = self.n_heads, self.n_kv_heads, self.head_dim
        positions = cursor + jnp.arange(t, dtype=jnp.int32)
        turn = self._turn(positions)

        def heads(w, gain, n):
            a = (x @ params[w]).reshape(b, t, n, d)
            return turn(rms_normalize(a, self.eps,
                                      params[gain]).astype(x.dtype))

        q = heads("Wq", "q_gain", h)
        k = heads("Wk", "k_gain", g).reshape(b, t, g * d)
        v = x @ params["Wv"]
        with _monitor.subscope("indexer"):
            q_idx, w_idx, k_idx = self.indexer(params, x, positions)
        kv_ring, i_ring = sparse_ring_update(
            kv_ring, i_ring, cursor, k, v, k_idx)
        ctx, tied = sparse_ring_attention(
            q, q_idx, w_idx, kv_ring, i_ring, cursor,
            topk=self.topk, sm_scale=d ** -0.5, scope=_monitor.subscope)
        out = self._activate(ctx.reshape(b, t, h * d) @ params["Wo"])
        if mask is not None:
            out = out * mask[..., None].astype(out.dtype)
        return (out, (kv_ring, i_ring, cursor + jnp.asarray(t, jnp.int32)),
                {"selection_tied": tied})

    def attention_path(self, t: int, carry) -> str:
        """``"gathered"``, ``"streamed"`` or ``"masked"``: the form
        ``forward_seq`` takes for ``t`` new positions against ``carry``,
        by the op's own predicate (host code asks it without tracing the
        step)."""
        kv_ring = carry[0]
        return sparse_attention_path(t, self.n_heads, self.n_kv_heads,
                                     self.head_dim, kv_ring.shape[1],
                                     kv_ring.dtype, self.topk)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        out, _ = self.forward_seq(
            params, x, self.init_carry(x.shape[0], x.dtype, x.shape[1]),
            train=train, rng=rng, mask=mask)
        return out, state


@serde.register("grouped_query_attention")
@dataclasses.dataclass
class GroupedQueryAttention(BaseRecurrentLayer):
    """Dense grouped-query attention over a key/value ring, causal, with
    a window or none and rotary or none (Cohere2's two kinds of layer).

    ``q = x Wq`` (``n_heads`` of ``head_dim``), ``k = x Wk``, ``v = x Wv``
    (``n_kv_heads`` each; query head ``h`` reads key/value head ``h //
    (n_heads / n_kv_heads)``), no bias, no norm on ``q`` or ``k``.  With
    ``rotary`` both are turned over the interleaved pairs ``(2i, 2i+1)``
    of the whole head (``rope_theta``, no scaling); without, the layer
    applies no positional embedding.  Query ``t`` sees the positions
    ``s <= t`` and, under ``window``, ``s > t - window`` (``window`` keys
    with its own); softmax of ``q . k / sqrt(head_dim)`` in float32.

    The carry is ``(key/value ring (batch, capacity, 2 x n_kv_heads,
    head_dim), cursor)``, the sparse layer's slots-major joined ring.
    Without a window the ring grows with the session (``cache_len``,
    the serving ladder, ``grow_carry``) and ``STATE_KIND`` is ``"kv"``.
    With one the ring WRAPS: position ``p`` lives in slot ``p mod
    capacity``, the capacity is the layer's own whatever ``cache_len`` it
    is handed (``window + chunk - 1`` slots in whole blocks,
    ``ops.attention.window_ring_slots``: a chunk of up to ``chunk``
    positions is written before it is read), it never grows, a session's
    position may pass it by any amount, and ``STATE_KIND`` is
    ``"window_kv"``.  One path serves prefill chunks, single steps and
    ``output()`` (from a zero ring as long as the sequence); the form the
    attention takes is ``ops.attention.gqa_attention_path``'s, from the
    call's shapes alone.
    """

    HAS_KV_RING = True

    activation: str = "identity"
    n_heads: int = 1
    n_kv_heads: int = 1
    head_dim: int = 0
    window: Optional[int] = None
    rotary: bool = True
    rope_theta: float = 10000.0
    cache_len: int = 128
    chunk: int = 256

    @property
    def STATE_KIND(self) -> str:    # serving_session_state_bytes{kind=}
        return "window_kv" if self.window else "kv"

    @property
    def RING_GROWS(self) -> bool:
        """Whether the ring's capacity follows the session's length (the
        serving ladder and its overflow checks are about such rings)."""
        return not self.window

    def param_order(self) -> tuple:
        return ("Wq", "Wk", "Wv", "Wo")

    def init_params(self, rng, dtype=jnp.float32) -> ParamTree:
        h, g, d, c = self.n_heads, self.n_kv_heads, self.head_dim, self.n_in
        kq, kk, kv, ko = jax.random.split(rng, 4)
        return {"Wq": _matrix(self, kq, (c, h * d), dtype),
                "Wk": _matrix(self, kk, (c, g * d), dtype),
                "Wv": _matrix(self, kv, (c, g * d), dtype),
                "Wo": _matrix(self, ko, (h * d, self.n_out), dtype)}

    # -------------------------------------------------------------- carry
    def _ring(self, batch: int, dtype, slots: int):
        return (jnp.zeros((batch, slots, 2 * self.n_kv_heads, self.head_dim),
                          dtype), jnp.zeros((), jnp.int32))

    def init_carry(self, batch: int, dtype, cache_len: Optional[int] = None):
        if self.window:
            return self._ring(batch, dtype,
                              window_ring_slots(self.window, self.chunk))
        cap = int(cache_len if cache_len is not None else self.cache_len)
        if cap < 1:
            raise ValueError("cache_len must be >= 1")
        return self._ring(batch, dtype, cap)

    def grow_carry(self, carry, cache_len: int):
        if self.window:         # sized once, at its window
            return carry
        ring, cursor = carry
        cap = ring.shape[1]
        if cache_len < cap:
            raise ValueError(
                f"cannot shrink the key/value ring from {cap} to {cache_len}")
        return jnp.pad(ring, [(0, 0), (0, cache_len - cap),
                              (0, 0), (0, 0)]), cursor

    # ------------------------------------------------------------ forward
    def _attend(self, params, x, carry, mask=None):
        ring, cursor = carry
        (b, t), h, d = x.shape[:2], self.n_heads, self.head_dim
        flat = x.reshape(b * t, -1)
        # behind a barrier: the products leave their results as plain
        # (tokens, features) arrays.  Without it the TPU's compiler takes
        # the layout the attention kernel wants of its queries back
        # through the product and turns Wq, Wk and Wv inside every step
        # (604 MB read and written a token step at 128 heads over
        # hidden 4,096; ``tools/step_copies.py``)
        q, k, v = jax.lax.optimization_barrier(tuple(
            flat @ params[w] for w in ("Wq", "Wk", "Wv")))
        q, k, v = q.reshape(b, t, h, d), k.reshape(b, t, -1), \
            v.reshape(b, t, -1)
        if self.rotary:
            positions = cursor + jnp.arange(t, dtype=jnp.int32)
            inv_freq, _ = yarn_inv_freq(d, self.rope_theta, None)
            q = rotate(q, positions, inv_freq)
            k = rotate(k.reshape(b, t, self.n_kv_heads, d), positions,
                       inv_freq).reshape(k.shape)
        ring = gqa_ring_update(ring, cursor, k, v, wraps=bool(self.window))
        with _monitor.subscope("window_attention" if self.window
                               else "full_attention"):
            ctx = gqa_ring_attention(q, ring, cursor, sm_scale=d ** -0.5,
                                     window=self.window or None)
        out = self._activate(ctx.reshape(b, t, h * d) @ params["Wo"])
        if mask is not None:
            out = out * mask[..., None].astype(out.dtype)
        return out, (ring, cursor + jnp.asarray(t, jnp.int32))

    def forward_seq(self, params, x, carry, *, train, rng=None, mask=None):
        t, cap = x.shape[1], carry[0].shape[1]
        if t > (cap - self.window + 1 if self.window else cap):
            raise ValueError(
                f"chunk of {t} timesteps exceeds what the key/value ring "
                f"of {cap} slots takes at once"
                + (f" under a window of {self.window}" if self.window
                   else ""))
        return self._attend(params, x, carry, mask)

    def attention_path(self, t: int, carry) -> str:
        """``"streamed"`` or ``"masked"``: the form ``forward_seq`` takes
        for ``t`` new positions against ``carry``, by the op's own
        predicate (host code asks it without tracing the step)."""
        ring = carry[0]
        return gqa_attention_path(t, self.n_heads, self.n_kv_heads,
                                  self.head_dim, ring.shape[1], ring.dtype)

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        # the whole sequence at once: a ring as long as it, which never
        # wraps, under the same rule of visibility
        out, _ = self._attend(
            params, x, self._ring(x.shape[0], x.dtype, x.shape[1]), mask)
        return out, state


# ------------------------------------------------------ hyper-connections
def sinkhorn(m: Array, iters: int, eps: float) -> Array:
    """``iters`` rounds of rows, then columns, divided by their sums
    (+ ``eps``) over the last two axes: towards doubly stochastic."""
    for _ in range(int(iters)):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


@dataclasses.dataclass
class _HyperConnection(BaseLayerConfig):
    """What the two halves of one hyper-connection share: ``n_streams``
    streams of width ``n_in``, and the per-token coefficients
    ``alpha * (norm(vec X) phi) + b`` (``norm`` an RMS norm over all
    ``n_streams * n_in`` numbers, eps ``eps``, no gain)."""

    n_in: int = 0
    n_streams: int = 4
    eps: float = 1e-6
    alpha_init: float = 0.01
    bias_std: float = 0.0

    def output_type(self, input_type):
        return _inputs.recurrent(self.n_in)

    def _coefficients(self, params, flat, which: str, shape):
        acc = flat.dtype
        h = jnp.matmul(flat, params[f"phi_{which}"].astype(acc),
                       precision=_HIGHEST)
        return (params[f"alpha_{which}"].astype(acc) * h.reshape(
            flat.shape[:-1] + shape) + params[f"b_{which}"].astype(acc))

    def _init(self, rng, dtype, shapes: dict) -> ParamTree:
        p = {}
        for (which, shape), k in zip(shapes.items(),
                                     jax.random.split(rng, len(shapes))):
            kp, kb = jax.random.split(k)
            n = math.prod(shape)
            p[f"phi_{which}"] = _matrix(
                self, kp, (self.n_streams * self.n_in, n), dtype)
            p[f"alpha_{which}"] = jnp.full((1,), self.alpha_init, dtype)
            p[f"b_{which}"] = _normal(kb, shape, self.bias_std, dtype)
        return p


@serde.register("hyper_connection_read")
@dataclasses.dataclass
class HyperConnectionRead(_HyperConnection):
    """The stream's part a sublayer reads: ``u = H_pre X`` with
    ``H_pre = sigmoid(coefficients)``; (batch, time, streams, n_in) to
    (batch, time, n_in)."""

    def param_order(self) -> tuple:
        return ("phi_pre", "alpha_pre", "b_pre")

    def init_params(self, rng, dtype=jnp.float32) -> ParamTree:
        return self._init(rng, dtype, {"pre": (self.n_streams,)})

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        flat = rms_normalize(x.reshape(x.shape[:2] + (-1,)), self.eps)
        h_pre = jax.nn.sigmoid(self._coefficients(
            params, flat, "pre", (self.n_streams,)))
        u = jnp.einsum("btn,btnc->btc", h_pre, x.astype(flat.dtype))
        return u.astype(x.dtype), state


@serde.register("hyper_connection_write")
@dataclasses.dataclass
class HyperConnectionWrite(_HyperConnection):
    """The stream after a sublayer: ``X' = H_res X + H_post^T y`` with
    ``H_post = 2 sigmoid(.)`` and ``H_res = sinkhorn(exp(clip(.)))``,
    doubly stochastic.  Takes two inputs, the stream and the sublayer's
    output."""

    MULTI_INPUT = True

    sinkhorn_iters: int = 20
    clamp_min: float = -30.0
    clamp_max: float = 30.0

    def param_order(self) -> tuple:
        return ("phi_post", "alpha_post", "b_post",
                "phi_res", "alpha_res", "b_res")

    def init_params(self, rng, dtype=jnp.float32) -> ParamTree:
        n = self.n_streams
        return self._init(rng, dtype, {"post": (n,), "res": (n, n)})

    def mixing(self, params, x: Array):
        """(H_post (batch, time, streams), H_res (.., streams, streams))
        of the stream ``x``, in float32."""
        n = self.n_streams
        flat = rms_normalize(x.reshape(x.shape[:2] + (-1,)), self.eps)
        h_post = 2.0 * jax.nn.sigmoid(self._coefficients(
            params, flat, "post", (n,)))
        with _monitor.subscope("sinkhorn"):
            h_res = sinkhorn(jnp.exp(jnp.clip(
                self._coefficients(params, flat, "res", (n, n)),
                self.clamp_min, self.clamp_max)),
                self.sinkhorn_iters, self.eps)
        return h_post, h_res

    def forward(self, params, state, xs, *, train, rng=None, mask=None):
        x, y = xs
        h_post, h_res = self.mixing(params, x)
        acc = h_res.dtype
        out = (jnp.einsum("btij,btjc->btic", h_res, x.astype(acc))
               + h_post[..., None] * y.astype(acc)[:, :, None, :])
        return out.astype(x.dtype), state
