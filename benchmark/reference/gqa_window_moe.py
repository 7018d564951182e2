"""Plain float32 reference for the decoder of dense grouped-query
attention whose layers are a window's or full ones (Cohere2's
``layer_types``), under ONE LayerNorm a block that feeds attention and
experts alike, with sigmoid-routed experts of which this chip holds a
share, several shared experts combined by their average, and a head tied
to the embedding's table (``families/gqa_window_share.py`` counts it,
``models/mla_moe_decoder.py`` builds the program's graph from a file with
``layer_types``).

The full-sequence forward pass in straightforward ``jax.numpy``, float32
under ``jax.default_matmul_precision("highest")``: no cache, no ring, no
kernels, the experts as a loop over the held ids that casts one expert's
matrices at a time.  Queries go through the attention in blocks of
``QUERY_BLOCK`` so that a sequence of 32,767 positions fits beside the
model: a full layer's block scores every position, a window layer's the
``sliding_window + block - 1`` positions its queries can see.  It imports
nothing of the package.  The weights are the container's own parameter
tree, read by the vertex names the builder gives (``embed``,
``L<i>_norm``, ``L<i>_attn``, ``L<i>_moe``, ``final_norm``; the head has
no parameter of its own).

Per token, ``x_0 = E[id]``, layer ``i`` of kind ``layer_types[i]``:

- ``n = LN(x)``: ``(x - mean(x)) * rsqrt(var(x) + layer_norm_eps) *
  gain``; one norm a layer: ``x' = x + Attn_i(n) + Moe(n)``.
- ``Attn_i``: ``q = n Wq`` (``num_attention_heads`` of ``head_dim``),
  ``k = n Wk``, ``v = n Wv`` (``num_key_value_heads``; query head ``h``
  reads key/value head ``h // group``), no bias, no norm.
  ``sliding_attention``: ``q`` and ``k`` turned by rotary over the
  interleaved pairs ``(2i, 2i+1)`` of the whole head (``rope_theta``),
  query ``t`` sees ``t - sliding_window < s <= t``.  ``full_attention``:
  no positional embedding, every ``s <= t``.  Softmax of ``q . k /
  sqrt(head_dim)``; ``out = ctx Wo``.
- ``Moe``: ``g = sigmoid(n Wr)`` over the source's expert count
  (``published.num_experts``), the ``num_experts_per_tok`` largest,
  weights ``g`` there over their sum (``norm_topk_prob``); ``y = sum over
  the picks whose expert is held of w_j E_j(n) + (1 / num_shared_experts)
  sum_s S_s(n)``, every expert ``(silu(n Wg) * (n Wu)) Wd``.  What the
  absent experts would have added is left out, and that partial result
  goes on.
- ``logits = logit_scale * LN_final(x_L) E^T``.

Controls: ``fp8_weights`` (every matrix first rounded to
``float8_e4m3fn``, scaled per tensor to the type's range), ``all_full``
(no window anywhere: a window layer sees every ``s <= t``, its rotary
kept) and ``rope_all`` (rotary on the full layers too).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.mla_moe_decoder import F32, _load, gated

#: queries a block of the attention: a full layer's scores against 32,768
#: positions are (128 heads, 32, 32,768) float32, 0.54 GB
QUERY_BLOCK = 32


def held_experts(cfg: Dict) -> Optional[Sequence[int]]:
    """The ids the file says this chip holds, or None for all."""
    return cfg.get("builder_args", {}).get("experts_held")


def router_width(cfg: Dict) -> int:
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def layernorm(x, eps, gain):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * gain


def rotary(x, positions, theta: float):
    """``x`` (time, heads, dim) turned by ``positions`` (time,): pairs
    ``(2i, 2i+1)``, frequencies ``theta ** (-2i / dim)``."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    angle = jnp.asarray(positions, F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def attention(cfg: Dict, p: Dict, x, *, window: Optional[int], rope: bool,
              fp8=False, query_block: int = QUERY_BLOCK):
    """(batch, time, hidden) normed hidden states to the attention's
    output, one sequence after the other, queries in blocks.  ``window``
    None: every position up to the query's own."""
    h, g, d = (int(cfg[k]) for k in ("num_attention_heads",
                                     "num_key_value_heads", "head_dim"))
    theta = float(cfg["rope_theta"])
    w = {k: _load(p[k], fp8) for k in p}

    def one(x):                                             # (time, hidden)
        t = x.shape[0]
        positions = jnp.arange(t)
        turn = (lambda a, pos: rotary(a, pos, theta)) if rope \
            else (lambda a, pos: a)
        k = turn((x @ w["Wk"]).reshape(t, g, d), positions)
        v = (x @ w["Wv"]).reshape(t, g, d)
        block = min(int(query_block), t)
        pad = (-t) % block
        # the positions a block's queries can see: all of them, or the
        # window's span before the block's last query
        span = t if window is None else min(t + pad,
                                            int(window) + block - 1)
        lead = span - block if window is not None else 0
        if window is not None:      # rows before position 0 and past the
            k, v = (jnp.pad(a, [(lead, pad), (0, 0), (0, 0)])   # last one
                    for a in (k, v))

        def queries(args):
            xb, pos = args
            q = turn((xb @ w["Wq"]).reshape(block, h, d), pos)
            if window is None:
                keys, values, at = k, v, positions
            else:
                # key row j of the slice stands at pos[0] - lead + j
                keys, values = (lax.dynamic_slice_in_dim(a, pos[0], span)
                                for a in (k, v))
                at = pos[0] - lead + jnp.arange(span)
            keep = (at[None, :] <= pos[:, None]) & (at[None, :] >= 0)
            if window is not None:
                keep = keep & (at[None, :] > pos[:, None] - int(window))
            s = jnp.einsum("tgrd,sgd->grts",
                           q.reshape(block, g, h // g, d),
                           keys) / math.sqrt(d)
            s = jnp.where(keep[None, None], s, -jnp.inf)
            o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, -1), values)
            return o.reshape(block, h * d) @ w["Wo"]

        blocks = lambda a: jnp.pad(
            a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
                (-1, block) + a.shape[1:])
        # a padded query stands past the last position and sees real
        # keys; its row is cut off below
        out = lax.map(queries, (blocks(x), jnp.arange(t + pad).reshape(
            -1, block)))
        return out.reshape(-1, x.shape[-1])[:t]

    return lax.map(one, x)


def routing(cfg: Dict, p: Dict, x, fp8=False):
    """(tokens, router width) weights: 0 for an expert the token did not
    choose."""
    score = {"sigmoid": jax.nn.sigmoid,
             "softmax": lambda a: jax.nn.softmax(a, axis=-1)}[
        cfg["expert_selection_fn"]]
    g = score(x @ _load(p["router"], fp8))
    _, idx = lax.top_k(g, int(cfg["num_experts_per_tok"]))
    chosen = jnp.zeros(g.shape, bool).at[
        jnp.arange(g.shape[0])[:, None], idx].set(True)
    w = jnp.where(chosen, g, 0.0)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w


def moe(cfg: Dict, p: Dict, x, experts_held: Optional[Sequence[int]] = None,
        fp8=False, shared_sum=False):
    """``p["Wg"]``/``p["Wu"]`` are (hidden, held * width) and ``p["Wd"]``
    (held * width, hidden): the held experts' matrices side by side, in
    the order of ``experts_held`` (default: every expert of the router's
    width); ``p["Sg"]``/``p["Su"]``/``p["Sd"]`` the shared experts' the
    same way."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    held = jnp.asarray(list(range(router_width(cfg)))
                       if experts_held is None else experts_held, jnp.int32)
    weights = routing(cfg, p, x, fp8)
    f = int(cfg["intermediate_size"])

    def sliced(names, i):
        return (_load(lax.dynamic_slice_in_dim(
            p[n], i * f, f, axis=0 if n.endswith("d") else 1), fp8)
            for n in names)

    def routed(i, y):       # one expert's matrices in float32 at a time
        w = jnp.take(weights, held[i], axis=1)
        return y + w[:, None] * gated(x, *sliced(("Wg", "Wu", "Wd"), i))

    y = lax.fori_loop(0, held.shape[0], routed, jnp.zeros_like(x))
    n_shared = int(cfg.get("num_shared_experts", 0))
    if n_shared:
        shared = lax.fori_loop(
            0, n_shared,
            lambda i, s: s + gated(x, *sliced(("Sg", "Su", "Sd"), i)),
            jnp.zeros_like(x))
        average = cfg.get("shared_expert_combination_strategy") == "average"
        y = y + (shared / n_shared if average and not shared_sum else shared)
    return y.reshape(shape)


class Forward:
    """The forward pass for one configuration as jitted programs
    (``programs``: ``embed``, a block of each kind of layer, ``logits``;
    ``head`` is there for whoever compiles one, and passes its input on):
    every layer of a kind has the same shapes, so each compiles once, and
    ``__call__`` runs nothing outside them (a caller may swap in
    ahead-of-time compiled ones).  ``faults`` plants what a test wants
    caught: ``window_plus_one``, ``shared_sum`` (the shared experts
    summed, not averaged), ``second_norm`` (the experts read a norm of
    the stream AFTER the attention was added: the sequential block),
    ``untied_head`` (the head reads another table than the embedding
    does: the embedding's rows in reverse)."""

    def __init__(self, cfg: Dict, experts_held=None, fp8_weights=False,
                 all_full=False, rope_all=False,
                 faults: Sequence[str] = (), last: Optional[int] = None,
                 query_block: int = QUERY_BLOCK):
        self.cfg = cfg
        fp8 = bool(fp8_weights)
        eps = float(cfg["layer_norm_eps"])
        held = held_experts(cfg) if experts_held is None else experts_held
        window = int(cfg["sliding_window"]) + (
            1 if "window_plus_one" in faults else 0)

        def block(sliding: bool):
            def run(pn, pa, pm, x):
                gain = pn["gain"].astype(F32)
                with jax.default_matmul_precision("highest"):
                    n = layernorm(x, eps, gain)
                    x = x + attention(
                        cfg, pa, n, fp8=fp8, query_block=query_block,
                        window=window if sliding and not all_full else None,
                        rope=sliding or rope_all)
                    if "second_norm" in faults:
                        n = layernorm(x, eps, gain)
                    return x + moe(cfg, pm, n, held, fp8,
                                   shared_sum="shared_sum" in faults)
            return jax.jit(run)

        def embed(table, ids):
            return jnp.take(table, ids.astype(jnp.int32), axis=0).astype(F32)

        def logits(pn, pe, x):
            if last is not None:
                x = x[:, -int(last):]
            table = pe["W"]                         # (vocabulary, hidden)
            if "untied_head" in faults:
                table = table[::-1]
            rows = math.gcd(table.shape[0], 4096)   # a block in float32
            with jax.default_matmul_precision("highest"):
                h = layernorm(x, eps, pn["gain"].astype(F32))
                scale = (448.0 / jnp.maximum(jnp.max(jnp.abs(
                    table.astype(F32))), 1e-30)) if fp8 else None

                def one(i):
                    part = lax.dynamic_slice_in_dim(
                        table, i * rows, rows, 0).astype(F32)
                    if fp8:
                        part = (part * scale).astype(
                            jnp.float8_e4m3fn).astype(F32) / scale
                    return h @ part.T
                out = lax.map(one, jnp.arange(table.shape[0] // rows))
            out = jnp.moveaxis(out, 0, -2).reshape(h.shape[:-1] + (-1,))
            return out * float(cfg.get("logit_scale", 1.0))

        self.programs = {"embed": jax.jit(embed),
                         "sliding_attention": block(True),
                         "full_attention": block(False),
                         "logits": jax.jit(logits),
                         "head": jax.jit(lambda pn, ph, x: x)}

    def layers(self):
        """``(program, vertex names of its parameter groups)`` in the
        order the stream passes them; the last one turns the stream into
        logits with the embedding's own table."""
        for i in range(int(self.cfg["num_hidden_layers"])):
            yield self.cfg["layer_types"][i], (f"L{i}_norm", f"L{i}_attn",
                                               f"L{i}_moe")
        yield "logits", ("final_norm", "embed")

    def __call__(self, params: Dict, ids):
        """Float32 logits (batch, ``last`` or time, vocabulary) of the
        whole sequence ``ids`` (batch, time)."""
        x = self.programs["embed"](params["embed"]["W"], ids)
        for kind, names in self.layers():
            x = self.programs[kind](*(params[n] for n in names), x)
        return x


def forward(cfg: Dict, params: Dict, ids, experts_held=None,
            last: Optional[int] = None, **kw):
    return Forward(cfg, experts_held, last=last, **kw)(params, ids)
