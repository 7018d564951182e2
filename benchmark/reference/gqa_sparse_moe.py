"""Plain float32 reference for the decoder of grouped-query attention
over the rows a learned indexer selects, with softmax-routed experts of
which this chip holds a share, on a plain pre-norm residual path
(``families/gqa_sparse_share.py`` counts it, ``models/mla_moe_decoder.py``
builds the program's graph from a file with ``sa_config``).

The full-sequence forward pass in straightforward ``jax.numpy``, float32
under ``jax.default_matmul_precision("highest")``: no cache, no kernels,
the selection computed for every query from its own float32 scores with
``lax.top_k``, the experts as a loop over the held ids.  Queries go
through the attention in blocks of ``QUERY_BLOCK`` so that a sequence of
32,767 positions fits beside the model (a block's scores against every
position are one array).  It imports nothing of the package.  The
weights are the container's own parameter tree, read by the vertex names
the builder gives (``embed``, ``L<i>_attn_norm``, ``L<i>_attn``,
``L<i>_ffn_norm``, ``L<i>_moe`` | ``L<i>_ffn``, ``final_norm``,
``head``).

Per token, ``x_0`` the embedding, ``h = rmsnorm(x)``:

- attention: ``q = rmsnorm_head(h Wq)`` (``num_attention_heads`` of
  ``head_dim``), ``k = rmsnorm_head(h Wk)``, ``v = h Wv``
  (``num_key_value_heads``), rotary (``rope_theta``, pairs ``(i, i +
  dim/2)``; for text all three ``mrope_section`` parts carry the same
  position) on ``q`` and ``k``.  Indexer (``sa_config``): ``qI = h WqI``
  (``indexer_num_heads`` of ``indexer_head_dim``), ``kI = LayerNorm(h
  WkI)`` (one head), rotary on both, ``w = h Ww``; ``I[t, s] = sum_j
  w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``; ``S_t`` the
  ``min(topk, t + 1)`` positions of largest ``I[t, .]``, of equal scores
  the lowest position first.  ``o[t] = sum_{s in S_t} softmax_{S_t}(q[t]
  . k[s] / sqrt(head_dim)) v[s]``, query head ``h`` against key/value
  head ``h // group``; ``x += concat(o) Wo``.
- experts: ``p = softmax(h Wr)`` over the source's expert count
  (``published.num_experts``), the ``num_experts_per_tok`` largest,
  weights ``p_i`` over their sum (``norm_topk_prob``); ``x += sum over
  the picks whose expert is held of w_i (silu(h Wg_i) * (h Wu_i))
  Wd_i``.  No shared expert, no scaling, no selection bias.  What the
  absent experts would have added is left out, and that partial result
  goes on.

``fp8_weights=True`` is one control (every matrix first rounded to
``float8_e4m3fn``, scaled per tensor to the type's range);
``dense_attention=True`` the other (the selection left out: every query
attends over every position up to its own).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.mla_moe_decoder import F32, _load, gated, rmsnorm

#: queries a block of the attention: its scores against 32,768 positions
#: are (32 heads, 128, 32,768) float32, 0.54 GB
QUERY_BLOCK = 128


def held_experts(cfg: Dict) -> Optional[Sequence[int]]:
    """The ids the file says this chip holds, or None for all."""
    return cfg.get("builder_args", {}).get("experts_held")


def router_width(cfg: Dict) -> int:
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def rotary(x, positions, theta: float):
    """``x`` (time, [heads,] dim) turned by ``positions`` (time,): pairs
    ``(i, i + dim / 2)``, frequencies ``theta ** (-2i / dim)``."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    angle = jnp.asarray(positions, F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layernorm(x, eps, gain, bias):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * gain + bias


def index_scores(q_idx, w_idx, k_idx):
    """``I`` (queries, positions) from (queries, heads, dim) indexer
    queries, (queries, heads) weights and (positions, dim) keys; a zero
    is +0.0 whatever the weights' signs."""
    s = jnp.einsum("tjd,sd->tjs", q_idx, k_idx)
    out = jnp.sum(jnp.maximum(s, 0.0) * w_idx[:, :, None], axis=1)
    return jnp.where(out == 0.0, 0.0, out)


def select(scores, visible, k: int):
    """Bool (queries, positions): each query's ``k`` largest visible
    scores (all visible ones where they are fewer), of equal scores the
    lowest positions: ``lax.top_k`` keeps the lower index of a tie."""
    k = min(int(k), scores.shape[-1])
    _, rows = lax.top_k(jnp.where(visible, scores, -jnp.inf), k)
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], rows].set(True)
    return picked & visible


def _indexer(cfg: Dict, w: Dict, x, turn):
    """``(qI (time, heads, dim), w (time, heads), kI (time, dim))`` of
    one sequence ``x`` (time, hidden), from loaded matrices ``w``."""
    sa = cfg["sa_config"]
    j, di = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    q_idx = turn((x @ w["WqI"]).reshape(x.shape[0], j, di))
    k_idx = turn(layernorm(x @ w["WkI"], float(cfg["rms_norm_eps"]),
                           w["kI_gain"], w["kI_bias"]))
    return q_idx, x @ w["Ww"], k_idx


def selection(cfg: Dict, p: Dict, x, last: int):
    """Bool (``last``, time): the positions each of the ``last``
    positions of one sequence ``x`` (time, hidden: normed hidden states)
    selects."""
    t = x.shape[0]
    positions = jnp.arange(t)
    w = {k: _load(p[k], False) for k in p}
    q_idx, w_idx, k_idx = _indexer(
        cfg, w, x, lambda a: rotary(a, positions, float(cfg["rope_theta"])))
    visible = positions[None, :] <= positions[-last:, None]
    return select(index_scores(q_idx[-last:], w_idx[-last:], k_idx),
                  visible, int(cfg["sa_config"]["topk"]))


def attention(cfg: Dict, p: Dict, x, fp8=False, dense_attention=False,
              rotary_on=True, query_block: int = QUERY_BLOCK):
    """(batch, time, hidden) normed hidden states to the attention's
    output, one sequence after the other, queries in blocks."""
    h, g, d = (int(cfg[k]) for k in ("num_attention_heads",
                                     "num_key_value_heads", "head_dim"))
    topk = int(cfg["sa_config"]["topk"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    w = {k: _load(p[k], fp8) for k in p}

    def one(x):                                             # (time, hidden)
        t = x.shape[0]
        positions = jnp.arange(t)
        turn = (lambda a: rotary(a, positions, theta)) if rotary_on \
            else (lambda a: a)
        q = turn(rmsnorm((x @ w["Wq"]).reshape(t, h, d), eps, w["q_gain"]))
        k = turn(rmsnorm((x @ w["Wk"]).reshape(t, g, d), eps, w["k_gain"]))
        v = (x @ w["Wv"]).reshape(t, g, d)
        q_idx, w_idx, k_idx = _indexer(cfg, w, x, turn)
        block = min(int(query_block), t)
        pad = (-t) % block

        def queries(args):
            qb, qib, wib, pos = args
            visible = positions[None, :] <= pos[:, None]
            keep = visible if dense_attention else select(
                index_scores(qib, wib, k_idx), visible, topk)
            s = jnp.einsum("tgrd,sgd->grts",
                           qb.reshape(block, g, h // g, d), k) / math.sqrt(d)
            s = jnp.where(keep[None, None], s, -jnp.inf)
            o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, -1), v)
            return o.reshape(block, h * d)

        blocks = lambda a: jnp.pad(
            a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
                (-1, block) + a.shape[1:])
        # a padded query stands at position 0; its row is cut off below
        out = lax.map(queries, (blocks(q), blocks(q_idx), blocks(w_idx),
                                blocks(positions)))
        return out.reshape(-1, h * d)[:t] @ w["Wo"]

    return lax.map(one, x)


def routing(cfg: Dict, p: Dict, x, fp8=False):
    """(tokens, router width) weights: 0 for an expert the token did not
    choose."""
    probs = jax.nn.softmax(x @ _load(p["router"], fp8), axis=-1)
    _, idx = lax.top_k(probs, int(cfg["num_experts_per_tok"]))
    chosen = jnp.zeros(probs.shape, bool).at[
        jnp.arange(probs.shape[0])[:, None], idx].set(True)
    w = jnp.where(chosen, probs, 0.0)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w


def moe(cfg: Dict, p: Dict, x, experts_held: Optional[Sequence[int]] = None,
        fp8=False):
    """``p["Wg"]``/``p["Wu"]`` are (hidden, held * width) and ``p["Wd"]``
    (held * width, hidden): the held experts' matrices side by side, in
    the order of ``experts_held`` (default: every expert of the router's
    width)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    held = jnp.asarray(list(range(router_width(cfg)))
                       if experts_held is None else experts_held, jnp.int32)
    weights = routing(cfg, p, x, fp8)
    f = int(cfg["moe_intermediate_size"])

    def one(i, y):
        mats = (lax.dynamic_slice_in_dim(p["Wg"], i * f, f, axis=1),
                lax.dynamic_slice_in_dim(p["Wu"], i * f, f, axis=1),
                lax.dynamic_slice_in_dim(p["Wd"], i * f, f, axis=0))
        w = jnp.take(weights, held[i], axis=1)
        return y + w[:, None] * gated(x, *(_load(m, fp8) for m in mats))

    y = lax.fori_loop(0, held.shape[0], one, jnp.zeros_like(x))
    return y.reshape(shape)


def dense_ffn(cfg, p, x, fp8=False):
    return gated(x, *(_load(p[k], fp8) for k in ("Wg", "Wu", "Wd")))


def is_dense(cfg: Dict, i: int) -> bool:
    return (i in cfg.get("mlp_only_layers", ())
            or (i + 1) % int(cfg.get("decoder_sparse_step", 1)) != 0)


class Forward:
    """The forward pass for one configuration as jitted programs
    (``programs``: ``embed``, a sublayer of each kind with its norm
    before and the add after, ``head``): every layer of a kind has the
    same shapes, so each compiles once, and ``__call__`` runs nothing
    outside them (a caller may swap in ahead-of-time compiled ones).
    ``faults`` plants what a test wants caught: ``rotary_off``,
    ``residual_off`` (a sublayer's output goes on without the stream it
    read)."""

    def __init__(self, cfg: Dict, experts_held=None, fp8_weights=False,
                 dense_attention=False, faults: Sequence[str] = (),
                 last: Optional[int] = None,
                 query_block: int = QUERY_BLOCK):
        self.cfg = cfg
        fp8 = bool(fp8_weights)
        eps = float(cfg["rms_norm_eps"])
        held = held_experts(cfg) if experts_held is None else experts_held

        def around(sublayer):
            def block(pn, pf, x):
                with jax.default_matmul_precision("highest"):
                    y = sublayer(pf, rmsnorm(x, eps, pn["gain"].astype(F32)))
                    return y if "residual_off" in faults else x + y
            return jax.jit(block)

        def embed(table, ids):
            return jnp.take(table, ids.astype(jnp.int32), axis=0).astype(F32)

        def head(pn, ph, x):
            if last is not None:
                x = x[:, -int(last):]
            w = ph["W"]
            block = math.gcd(w.shape[1], 4096)      # a block in float32
            with jax.default_matmul_precision("highest"):
                h = rmsnorm(x, eps, pn["gain"].astype(F32))
                scale = (448.0 / jnp.maximum(jnp.max(jnp.abs(
                    w.astype(F32))), 1e-30)) if fp8 else None

                def one(i):
                    cols = lax.dynamic_slice_in_dim(w, i * block, block, 1)
                    cols = cols.astype(F32)
                    if fp8:
                        cols = (cols * scale).astype(
                            jnp.float8_e4m3fn).astype(F32) / scale
                    return h @ cols
                out = lax.map(one, jnp.arange(w.shape[1] // block))
            return jnp.moveaxis(out, 0, -2).reshape(h.shape[:-1] + (-1,))

        self.programs = {
            "embed": jax.jit(embed),
            "attn": around(lambda p, u: attention(
                cfg, p, u, fp8, dense_attention,
                rotary_on="rotary_off" not in faults,
                query_block=query_block)),
            "ffn": around(lambda p, u: dense_ffn(cfg, p, u, fp8)),
            "moe": around(lambda p, u: moe(cfg, p, u, held, fp8)),
            "head": jax.jit(head)}

    def layers(self):
        """``(program, vertex names of its two parameter groups)`` in
        the order the stream passes them."""
        for i in range(int(self.cfg["num_hidden_layers"])):
            for half, kind in (("attn", "attn"),
                               ("ffn", "ffn" if is_dense(self.cfg, i)
                                else "moe")):
                yield kind, (f"L{i}_{half}_norm", f"L{i}_{kind}")

    def __call__(self, params: Dict, ids):
        """Float32 logits (batch, ``last`` or time, vocabulary) of the
        whole sequence ``ids`` (batch, time)."""
        x = self.programs["embed"](params["embed"]["W"], ids)
        for kind, names in self.layers():
            x = self.programs[kind](*(params[n] for n in names), x)
        return self.programs["head"](params["final_norm"], params["head"], x)


def forward(cfg: Dict, params: Dict, ids, experts_held=None,
            last: Optional[int] = None, **kw):
    return Forward(cfg, experts_held, last=last, **kw)(params, ids)
