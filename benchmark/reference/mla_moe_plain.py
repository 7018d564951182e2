"""Plain float32 reference for the latent-attention, routed-expert
decoder on a plain pre-norm residual path, one chip's share of its
experts held (``families/mla_moe_share.py`` counts it,
``models/mla_moe_decoder.py`` builds the program's graph from a file
without ``hc_mult``).

The full-sequence forward pass in straightforward ``jax.numpy``, float32
under ``jax.default_matmul_precision("highest")``: no cache, keys and
values decompressed from the latent row (nothing absorbed), the experts
as a loop over the held ids.  The sublayers are DeepSeek-V3's, which
``reference/mla_moe_decoder.py`` states (``attention``, ``dense_ffn``,
``moe``, ``rmsnorm``: taken from there, where their equations are
written out); what is this file's is the residual path and the share.
It imports nothing of the package's layers.  The weights are the
container's own parameter tree, read by the vertex names the builder
gives (``embed``, ``L<i>_attn_norm``, ``L<i>_attn``, ``L<i>_ffn_norm``,
``L<i>_ffn`` | ``L<i>_moe``, ``final_norm``, ``head``).

Per token, ``x_0`` the embedding:

- each layer: ``x = x + attention(rmsnorm(x))``, then
  ``x = x + F(rmsnorm(x))`` with ``F`` the dense feed-forward in the
  first ``first_k_dense_replace`` layers and the expert layer after;
  logits ``rmsnorm(x) W_head``.
- the share: the router is as wide as the source's expert count
  (``published.n_routed_experts`` where the file gives one): ``g =
  sigmoid(x Wr)``, the ``k`` largest of ``g + bias`` over ALL of them,
  weights ``g`` there over the sum of all ``k`` (+1e-20) times
  ``routed_scaling_factor``; ``y = sum over the picks whose expert is
  held of w_i E_i(x) + E_shared(x)``.  What the absent experts would
  have added is left out, and that partial result goes on to the next
  layer.  ``experts_held`` defaults to the file's
  (``builder_args.experts_held``; every expert where it gives none).

``fp8_weights=True`` is the control (every matrix first rounded to
``float8_e4m3fn``, scaled per tensor to the type's range).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.mla_moe_decoder import (F32, attention, dense_ffn,
                                                 moe, rmsnorm)


def held_experts(cfg: Dict) -> Optional[Sequence[int]]:
    """The ids the file says this chip holds, or None for all."""
    return cfg.get("builder_args", {}).get("experts_held")


class Forward:
    """The forward pass for one configuration as five jitted programs
    (``programs``: ``embed``, a sublayer of each kind with its norm
    before and the add after, ``head``): every layer of a kind has the
    same shapes, so each compiles once, and ``__call__`` runs nothing
    outside them (a caller may swap in ahead-of-time compiled ones).
    ``faults`` plants what a test wants caught: ``no_shared_expert``,
    ``no_routed_scaling``, ``rotary_off``, ``residual_off`` (a sublayer's
    output goes on without the stream it read)."""

    def __init__(self, cfg: Dict, experts_held=None, fp8_weights=False,
                 faults: Sequence[str] = (), last: Optional[int] = None):
        self.cfg = cfg
        fp8 = bool(fp8_weights)
        eps = float(cfg["rms_norm_eps"])
        held = held_experts(cfg) if experts_held is None else experts_held
        if held is None:
            held = list(range(int(cfg["n_routed_experts"])))

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

                def one(j):
                    cols = lax.dynamic_slice_in_dim(w, j * block, block, 1)
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
                cfg, p, u, fp8, rotary_on="rotary_off" not in faults)),
            "ffn": around(lambda p, u: dense_ffn(cfg, p, u, fp8)),
            "moe": around(lambda p, u: moe(
                cfg, p, u, held, fp8,
                shared_on="no_shared_expert" not in faults,
                scaling_on="no_routed_scaling" not in faults)),
            "head": jax.jit(head)}

    def layers(self):
        """``(program, vertex names of its two parameter groups)`` in
        the order the stream passes them."""
        dense = int(self.cfg["first_k_dense_replace"])
        for i in range(int(self.cfg["num_hidden_layers"])):
            for half, kind in (("attn", "attn"),
                               ("ffn", "ffn" if i < dense else "moe")):
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
