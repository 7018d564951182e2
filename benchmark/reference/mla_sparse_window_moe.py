"""Plain float32 reference for the decoder of latent attention whose layers
are full ones, attending over the rows a learned indexer selects, or a
window's, each with a headwise output gate, on a plain pre-norm residual
path, with sigmoid-routed experts of which this chip holds a share
beside a shared expert (``families/mla_sparse_window_share.py`` counts
it, ``models/mla_moe_decoder.py`` builds the program's graph from a file
with ``kv_lora_rank`` and ``layer_types``).

The full-sequence forward pass in straightforward ``jax.numpy``, float32
under ``jax.default_matmul_precision("highest")``: no cache, no ring, no
kernels, keys and values decompressed from the latent row by ``Wkvb``
(nothing absorbed), the selection computed for every query from its own
float32 scores with ``lax.top_k``, the experts as a loop over the held
ids.  Queries go through the attention in blocks of ``QUERY_BLOCK`` so
that a sequence of 32,767 positions fits beside the model: a full
layer's block scores every position, a window layer's the ``window +
block - 1`` positions its queries can see.  It imports nothing of the
package.  The weights are the container's own parameter tree, read by
the vertex names the builder gives (``embed``, ``L<i>_attn_norm``,
``L<i>_attn``, ``L<i>_ffn_norm``, ``L<i>_ffn`` | ``L<i>_moe``,
``final_norm``, ``head``).

Per token, ``x_0`` the embedding; each layer ``x += Attn_i(rmsnorm(x))``,
then ``x += F(rmsnorm(x))``, ``F`` the dense feed-forward in the first
``first_k_dense_replace`` layers and the experts after; logits
``rmsnorm(x) W_head``.  ``Attn_i`` of the kind ``layer_types[i]`` names,
at the plain keys (``full_attention``) or the ``swa_`` ones
(``sliding_attention``), ``h`` the normed input:

- ``c_q = rmsnorm(h Wqa)``, ``q = c_q Wqb`` (heads of ``d_nope +
  d_rope``); ``[c_kv | k_r] = h Wkva``, ``c_kv = rmsnorm(c_kv)``,
  ``[k_nope | v] = c_kv Wkvb``; rotary over the interleaved pairs of
  ``q_r`` and the shared ``k_r`` (``rope_theta`` | ``swa_rope_theta``);
  scores ``(q_nope . k_nope + q_r . k_r) / sqrt(d_nope + d_rope)``.
- ``full_attention``: the indexer (``index_*``): ``qI = c_q WqI``
  (``index_n_heads`` of ``index_head_dim``), ``kI = LayerNorm(h WkI)``
  (gain and bias), both turned over the half-split pairs of their first
  ``qk_rope_head_dim`` numbers, ``w = h Ww``; ``I[t, s] = sum_j w[t, j]
  relu(qI[t, j] . kI[s])``; query ``t`` attends over the ``index_topk``
  positions ``s <= t`` of largest ``I[t, .]`` (all of them while they
  are fewer; equal scores: the lowest position).
- ``sliding_attention``: query ``t`` attends over ``t -
  sliding_window_size < s <= t``.
- the gate (``attention_gate_type`` | ``swa_attention_gate_type``
  ``headwise``): head ``h``'s output times ``sigmoid(h Wg)[h]``; ``out =
  concat(o) Wo``.
- ``F``: ``g = sigmoid(h Wr)`` over the source's expert count
  (``published.n_routed_experts``), the ``num_experts_per_tok`` largest
  of ``g + bias``, weights ``g`` there over their sum times
  ``routed_scaling_factor``; ``y = sum over the picks whose expert is
  held of w_i E_i(h) + E_shared(h)``.  What the absent experts would
  have added is left out, and that partial result goes on.

Controls: ``fp8_weights`` (every matrix first rounded to
``float8_e4m3fn``, scaled per tensor to the type's range),
``dense_attention`` (the selection left out: a full layer's query
attends over every position up to its own), ``all_full`` (no window: a
window layer sees every position up to its own) and ``no_gate``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.gqa_sparse_moe import index_scores, layernorm, select
from benchmark.reference.gqa_sparse_moe import rotary as rotary_half
from benchmark.reference.mla_moe_decoder import (F32, _load, dense_ffn, moe,
                                                 rmsnorm)

#: queries a block of the attention: a full layer's scores against 32,768
#: positions are (128 heads, 32, 32,768) float32, 0.54 GB
QUERY_BLOCK = 32


def held_experts(cfg: Dict) -> Optional[Sequence[int]]:
    """The ids the file says this chip holds, or None for all."""
    return cfg.get("builder_args", {}).get("experts_held")


def layer_kinds(cfg: Dict):
    return list(cfg["layer_types"][:int(cfg["num_hidden_layers"])])


def rotary(x, positions, theta: float):
    """``x`` (time, [heads,] dim) turned by ``positions`` (time,): pairs
    ``(2i, 2i+1)``, frequencies ``theta ** (-2i / dim)``."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    angle = jnp.asarray(positions, F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _sizes(cfg: Dict, sliding: bool):
    pre = "swa_" if sliding else ""
    return {k: int(cfg[pre + k]) for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")}


def attention(cfg: Dict, p: Dict, x, *, sliding: bool, fp8_weights=False,
              dense_attention=False, all_full=False, no_gate=False,
              faults: Sequence[str] = (), query_block: int = QUERY_BLOCK):
    """(batch, time, hidden) normed hidden states to the attention's
    output, one sequence after the other, queries in blocks.  ``faults``
    (tests): ``window_plus_one``, ``index_rope_all`` (the indexer's
    rotary over its whole head), ``index_query_from_x`` (the indexer's
    queries projected from the normed input instead of ``c_q``; the
    file's hidden size has to be its query rank).  The other keywords
    are :class:`Forward`'s controls."""
    z = _sizes(cfg, sliding)
    h, rank = z["num_attention_heads"], z["kv_lora_rank"]
    dn, dr, dv = (z[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                 "v_head_dim"))
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["swa_rope_theta" if sliding else "rope_theta"])
    gate_key = ("swa_" if sliding else "") + "attention_gate_type"
    gated = cfg.get(gate_key) == "headwise" and not no_gate
    window = None
    if sliding and not all_full:
        window = int(cfg["sliding_window_size"]) + (
            1 if "window_plus_one" in faults else 0)
    indexed = not sliding and "index_n_heads" in cfg
    w = {k: _load(p[k], fp8_weights) for k in p}
    scale = (dn + dr) ** -0.5

    def one(x):                                             # (time, hidden)
        t = x.shape[0]
        positions = jnp.arange(t)
        c_q = rmsnorm(x @ w["Wqa"], eps, w["q_gain"])
        kv = x @ w["Wkva"]
        c_kv = rmsnorm(kv[:, :rank], eps, w["kv_gain"])
        k_r = rotary(kv[:, rank:], positions, theta)
        # the key and the value halves of Wkvb apart: each (time, heads,
        # dim) array is made once, and the queries a block at a time
        wkvb = w["Wkvb"].reshape(rank, h, dn + dv)
        k_n = jnp.einsum("sr,rhd->shd", c_kv, wkvb[..., :dn])
        v = jnp.einsum("sr,rhd->shd", c_kv, wkvb[..., dn:])
        gate = jax.nn.sigmoid(x @ w["Wg"]) if gated else None
        if indexed:
            j, di = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
            r = di if "index_rope_all" in faults else dr

            def turn(a, pos):
                return jnp.concatenate([rotary_half(a[..., :r], pos, theta),
                                        a[..., r:]], -1)
            source = x if "index_query_from_x" in faults else c_q
            k_idx = turn(layernorm(x @ w["WkI"], eps, w["kI_gain"],
                                   w["kI_bias"]), positions)
            w_idx = x @ w["Ww"]
        block = min(int(query_block), t)
        pad = (-t) % block
        # the positions a block's queries can see: all of them, or the
        # window's span before the block's last query
        span = t if window is None else min(t + pad, window + block - 1)
        lead = span - block if window is not None else 0
        keys = (k_n, k_r, v)
        if window is not None:      # rows before position 0 and past the
            keys = tuple(jnp.pad(a, [(lead, pad)] + [(0, 0)] * (a.ndim - 1))
                         for a in keys)                     # last one

        def queries(args):
            cq, pos, extra = args
            q = (cq @ w["Wqb"]).reshape(-1, h, dn + dr)
            qn, qr = q[..., :dn], rotary(q[..., dn:], pos, theta)
            if window is None:
                kn, kr, vv, at = k_n, k_r, v, positions
            else:
                # key row i of the slice stands at pos[0] - lead + i
                kn, kr, vv = (lax.dynamic_slice_in_dim(a, pos[0], span)
                              for a in keys)
                at = pos[0] - lead + jnp.arange(span)
            keep = (at[None, :] <= pos[:, None]) & (at[None, :] >= 0)
            if window is not None:
                keep = keep & (at[None, :] > pos[:, None] - window)
            if indexed and not dense_attention:
                sb, wib = extra
                qib = turn((sb @ w["WqI"]).reshape(-1, j, di), pos)
                keep = select(index_scores(qib, wib, k_idx), keep,
                              int(cfg["index_topk"]))
            s = (jnp.einsum("qhd,shd->hqs", qn, kn)
                 + jnp.einsum("qhd,sd->hqs", qr, kr)) * scale
            s = jnp.where(keep[None], s, -jnp.inf)
            o = jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, -1), vv)
            return o

        blocks = lambda a: jnp.pad(
            a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
                (-1, block) + a.shape[1:])
        extra = ((blocks(source), blocks(w_idx)) if indexed
                 else (jnp.zeros((t + pad) // block), ) * 2)
        # a padded query stands past the last position and sees real
        # keys; its row is cut off below
        o = lax.map(queries, (blocks(c_q),
                              jnp.arange(t + pad).reshape(-1, block), extra))
        o = o.reshape((-1, h, dv))[:t]
        if gate is not None:
            o = o * gate[..., None]
        return o.reshape(t, h * dv) @ w["Wo"]

    return lax.map(one, x)


class Forward:
    """The forward pass for one configuration as jitted programs
    (``programs``: ``embed``, a sublayer of each kind with its norm
    before and the add after, ``head``): every layer of a kind has the
    same shapes, so each compiles once, and ``__call__`` runs nothing
    outside them (a caller may swap in ahead-of-time compiled ones).
    ``faults`` plants what a test wants caught (:func:`attention`)."""

    def __init__(self, cfg: Dict, experts_held=None, fp8_weights=False,
                 dense_attention=False, all_full=False, no_gate=False,
                 faults: Sequence[str] = (), last: Optional[int] = None,
                 query_block: int = QUERY_BLOCK):
        self.cfg = cfg
        fp8 = bool(fp8_weights)
        eps = float(cfg["rms_norm_eps"])
        held = held_experts(cfg) if experts_held is None else experts_held
        if held is None:
            held = list(range(int(cfg["n_routed_experts"])))

        def around(sublayer):
            def block(pn, pf, x):
                with jax.default_matmul_precision("highest"):
                    return x + sublayer(pf, rmsnorm(
                        x, eps, pn["gain"].astype(F32)))
            return jax.jit(block)

        def attn(sliding):
            return around(lambda p, u: attention(
                cfg, p, u, sliding=sliding, fp8_weights=fp8,
                dense_attention=dense_attention, all_full=all_full,
                no_gate=no_gate, faults=faults, query_block=query_block))

        def embed(table, ids):
            return jnp.take(table, ids.astype(jnp.int32), axis=0).astype(F32)

        def head(pn, ph, x):
            if last is not None:
                x = x[:, -int(last):]
            wh = ph["W"]
            cols = math.gcd(wh.shape[1], 4096)      # a block in float32
            with jax.default_matmul_precision("highest"):
                hn = rmsnorm(x, eps, pn["gain"].astype(F32))
                out = lax.map(lambda i: hn @ _load(lax.dynamic_slice_in_dim(
                    wh, i * cols, cols, 1), fp8),
                    jnp.arange(wh.shape[1] // cols))
            return jnp.moveaxis(out, 0, -2).reshape(hn.shape[:-1] + (-1,))

        self.programs = {
            "embed": jax.jit(embed),
            "full_attention": attn(False), "sliding_attention": attn(True),
            "ffn": around(lambda p, u: dense_ffn(cfg, p, u, fp8)),
            "moe": around(lambda p, u: moe(cfg, p, u, held, fp8)),
            "head": jax.jit(head)}

    def layers(self):
        """``(program, vertex names of its two parameter groups)`` in
        the order the stream passes them."""
        dense = int(self.cfg["first_k_dense_replace"])
        for i, kind in enumerate(layer_kinds(self.cfg)):
            yield kind, (f"L{i}_attn_norm", f"L{i}_attn")
            ffn = "ffn" if i < dense else "moe"
            yield ffn, (f"L{i}_ffn_norm", f"L{i}_{ffn}")

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
