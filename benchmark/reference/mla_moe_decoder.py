"""Plain float32 reference for the latent-attention, routed-expert,
hyper-connected decoder (``families/mla_moe_decoder.py`` counts it,
``models/mla_moe_decoder.py`` builds the program's graph).

The full-sequence forward pass in straightforward ``jax.numpy``, float32
under ``jax.default_matmul_precision("highest")`` (on a TPU a float32
product otherwise runs as one bf16 pass): no cache, keys and values
decompressed from the latent row (nothing absorbed), the experts as a
loop with masks, Sinkhorn as written.  It imports nothing of the
package's layers.  The architecture comes from the published keys in the
configuration's file; the weights are the container's own parameter
tree, read by the vertex names the builder gives (``embed``,
``L<i>_attn_read`` ... ``head``), each matrix taken to float32 where it
is used, one layer and one expert at a time, so that the reference fits
beside a model that fills the chip in bf16.

Equations (DeepSeek-V2/V3 for attention, routing and experts;
arXiv:2512.24880 for the residual path), per token, ``C`` hidden, ``n``
streams:

- stream: ``X_0`` = the embedding ``n`` times.  Around each sublayer
  ``F``: ``x~ = rmsnorm(vec X)`` (no gain, eps ``hc_eps``);
  ``H_pre = sigmoid(a_pre x~ phi_pre + b_pre)``,
  ``H_post = 2 sigmoid(a_post x~ phi_post + b_post)``,
  ``H_res = sinkhorn(exp(clip(a_res reshape(x~ phi_res) + b_res)))``
  (``hc_sinkhorn_iters`` rounds of rows then columns over sum + eps);
  ``u = H_pre X``, ``y = F(rmsnorm(u))``, ``X' = H_res X + H_post^T y``.
  After the last layer the streams are summed, normed, and meet the head.
- attention: ``c_q = rmsnorm(x Wqa)``, ``q = c_q Wqb``;
  ``[c_kv | k_r] = x Wkva``, ``c_kv = rmsnorm(c_kv)``,
  ``[k_nope | v] = c_kv Wkvb``; rotary (YaRN) on ``q_r`` and the shared
  ``k_r``; causal softmax of ``(q_nope k_nope + q_r k_r) s``;
  ``s = (d_nope + d_rope)^-1/2 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``.
- experts: ``g = sigmoid(x Wr)``; the ``k`` largest of ``g + bias``;
  weights ``g`` there over their sum (+1e-20) times
  ``routed_scaling_factor``; ``y = sum w_i E_i(x) + E_shared(x)``,
  ``E(x) = (silu(x Wg) * x Wu) Wd``.  ``experts_held`` restricts the sum
  to the experts one chip holds (routing stays over all).

``fp8_weights=True`` is the control the benchmark's comparison is set
against: every matrix is first rounded to ``float8_e4m3fn`` (scaled per
tensor to the type's range), which is what a program holding 8-bit
weights would compute with.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def _load(w, fp8: bool):
    w = jnp.asarray(w).astype(F32)
    if fp8 and w.ndim >= 2:
        scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
        w = (w * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    return w


def rmsnorm(x, eps, gain=None):
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y if gain is None else y * gain


def rotary_tables(cfg: Dict, positions):
    """cos and sin, (positions, d_rope // 2), with YaRN as DeepSeek's
    code computes it."""
    dim, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    extrapolated = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    s = cfg.get("rope_scaling")
    inv_freq, factor = extrapolated, 1.0
    if s:
        def dim_of(rotations):
            return (dim * math.log(s["original_max_position_embeddings"]
                                   / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))
        low = max(math.floor(dim_of(s["beta_fast"])), 0)
        high = min(math.ceil(dim_of(s["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                        / (high - low), 0, 1)
        inv_freq = extrapolated / s["factor"] * ramp + extrapolated * (1 - ramp)
        factor = (_mscale(s["factor"], s.get("mscale", 1))
                  / _mscale(s["factor"], s.get("mscale_all_dim", 0)))
    angle = jnp.asarray(positions, F32)[:, None] * inv_freq[None, :]
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 or not mscale else \
        0.1 * mscale * math.log(factor) + 1.0


def rotary(x, cos, sin):
    """``x`` (batch, time, [heads,] dim): pairs (2i, 2i+1) turned."""
    if x.ndim == 4:
        cos, sin = cos[:, None, :], sin[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def attention(cfg: Dict, p: Dict, x, fp8=False, rotary_on=True):
    b, t, _ = x.shape
    h = int(cfg["num_attention_heads"])
    dn, dr, dv = (int(cfg[k]) for k in ("qk_nope_head_dim",
                                        "qk_rope_head_dim", "v_head_dim"))
    rank, eps = int(cfg["kv_lora_rank"]), float(cfg["rms_norm_eps"])
    cos, sin = rotary_tables(cfg, jnp.arange(t))
    c_q = rmsnorm(x @ _load(p["Wqa"], fp8), eps, _load(p["q_gain"], fp8))
    q = (c_q @ _load(p["Wqb"], fp8)).reshape(b, t, h, dn + dr)
    kv = x @ _load(p["Wkva"], fp8)
    c_kv = rmsnorm(kv[..., :rank], eps, _load(p["kv_gain"], fp8))
    k_r = kv[..., rank:]
    q_n, q_r = q[..., :dn], q[..., dn:]
    if rotary_on:
        q_r, k_r = rotary(q_r, cos, sin), rotary(k_r, cos, sin)
    kvb = (c_kv @ _load(p["Wkvb"], fp8)).reshape(b, t, h, dn + dv)
    k_n, v = kvb[..., :dn], kvb[..., dn:]
    scale = (dn + dr) ** -0.5
    s = cfg.get("rope_scaling")
    if s and s.get("mscale_all_dim", 0):
        scale *= _mscale(s["factor"], s["mscale_all_dim"]) ** 2
    causal = jnp.tril(jnp.ones((t, t), bool))

    def head(args):
        qn, qr, kn, vv = args                    # (batch, time, dim)
        scores = (jnp.einsum("btd,bsd->bts", qn, kn)
                  + jnp.einsum("btd,bsd->bts", qr, k_r)) * scale
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("bts,bsd->btd", jax.nn.softmax(scores, -1), vv)

    ctx = lax.map(head, tuple(jnp.moveaxis(a, 2, 0)
                              for a in (q_n, q_r, k_n, v)))
    return jnp.moveaxis(ctx, 0, 2).reshape(b, t, h * dv) @ _load(p["Wo"], fp8)


def gated(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def dense_ffn(cfg, p, x, fp8=False):
    return gated(x, *(_load(p[k], fp8) for k in ("Wg", "Wu", "Wd")))


def routing(cfg: Dict, p: Dict, x, fp8=False, scaling_on=True):
    """(tokens, experts) weights: 0 for an expert the token did not
    choose."""
    g = jax.nn.sigmoid(x @ _load(p["router"], fp8))
    _, idx = lax.top_k(g + _load(p["router_bias"], fp8),
                       int(cfg["num_experts_per_tok"]))
    chosen = jnp.zeros(g.shape, bool).at[
        jnp.arange(g.shape[0])[:, None], idx].set(True)
    w = jnp.where(chosen, g, 0.0)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * (float(cfg["routed_scaling_factor"]) if scaling_on else 1.0)


def moe(cfg: Dict, p: Dict, x, experts_held: Optional[Sequence[int]] = None,
        fp8=False, shared_on=True, scaling_on=True):
    """``p["Wg"]``/``p["Wu"]`` are (hidden, held * width) and ``p["Wd"]``
    (held * width, hidden): the held experts' matrices side by side, in
    the order of ``experts_held`` (default: all)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    held = jnp.asarray(list(range(int(cfg["n_routed_experts"]))
                            if experts_held is None else experts_held)
                       , jnp.int32)
    weights = routing(cfg, p, x, fp8, scaling_on)

    def one(j, y):
        f = int(cfg["moe_intermediate_size"])
        mats = (lax.dynamic_slice_in_dim(p["Wg"], j * f, f, axis=1),
                lax.dynamic_slice_in_dim(p["Wu"], j * f, f, axis=1),
                lax.dynamic_slice_in_dim(p["Wd"], j * f, f, axis=0))
        w = jnp.take(weights, held[j], axis=1)
        return y + w[:, None] * gated(x, *(_load(m, fp8) for m in mats))

    y = lax.fori_loop(0, held.shape[0], one, jnp.zeros_like(x))
    if shared_on and int(cfg.get("n_shared_experts", 0)):
        y = y + gated(x, *(_load(p[k], fp8) for k in ("Sg", "Su", "Sd")))
    return y.reshape(shape)


def sinkhorn(m, iters, eps):
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def _coefficients(p, flat, which, shape):
    return (p[f"alpha_{which}"].astype(F32)
            * (flat @ p[f"phi_{which}"].astype(F32)).reshape(
                flat.shape[:-1] + shape) + p[f"b_{which}"].astype(F32))


def stream_read(cfg: Dict, p: Dict, X):
    n = int(cfg["hc_mult"])
    flat = rmsnorm(X.reshape(X.shape[:2] + (-1,)), float(cfg["hc_eps"]))
    h_pre = jax.nn.sigmoid(_coefficients(p, flat, "pre", (n,)))
    return jnp.einsum("btn,btnc->btc", h_pre, X)


def mixing(cfg: Dict, p: Dict, X):
    n = int(cfg["hc_mult"])
    flat = rmsnorm(X.reshape(X.shape[:2] + (-1,)), float(cfg["hc_eps"]))
    h_post = 2.0 * jax.nn.sigmoid(_coefficients(p, flat, "post", (n,)))
    h_res = sinkhorn(
        jnp.exp(jnp.clip(_coefficients(p, flat, "res", (n, n)),
                         float(cfg["mhc_h_res_clamp_min"]),
                         float(cfg["mhc_h_res_clamp_max"]))),
        int(cfg["hc_sinkhorn_iters"]), float(cfg["hc_eps"]))
    return h_post, h_res


def stream_write(cfg: Dict, p: Dict, X, y):
    h_post, h_res = mixing(cfg, p, X)
    return (jnp.einsum("btij,btjc->btic", h_res, X)
            + h_post[..., None] * y[:, :, None, :])


class Forward:
    """The forward pass for one configuration as five jitted programs
    (``programs``: ``embed``, a sublayer of each kind with the stream
    mixing around it, ``head``): every layer of a kind has the same
    shapes, so each compiles once, and ``__call__`` runs nothing
    outside them (a caller may swap in ahead-of-time compiled ones).
    ``faults`` plants what a test wants caught: ``no_shared_expert``,
    ``no_routed_scaling``, ``rotary_off``, ``streams_as_one``."""

    def __init__(self, cfg: Dict, experts_held=None, fp8_weights=False,
                 faults: Sequence[str] = (), last: Optional[int] = None):
        self.cfg = cfg
        fp8 = bool(fp8_weights)
        eps = float(cfg["rms_norm_eps"])
        n = int(cfg["hc_mult"])
        as_one = "streams_as_one" in faults

        def read(p, X):
            return jnp.mean(X, axis=2) if as_one else stream_read(cfg, p, X)

        def write(p, X, y):
            return (X + y[:, :, None, :] if as_one
                    else stream_write(cfg, p, X, y))

        def around(sublayer):
            def block(pr, pn, pf, pw, X):
                with jax.default_matmul_precision("highest"):
                    u = rmsnorm(read(pr, X), eps, pn["gain"].astype(F32))
                    return write(pw, X, sublayer(pf, u))
            return jax.jit(block)

        def embed(table, ids):
            x = jnp.take(table, ids.astype(jnp.int32), axis=0).astype(F32)
            return jnp.broadcast_to(x[:, :, None, :],
                                    x.shape[:2] + (n, x.shape[2]))

        def head(pn, ph, X):
            if last is not None:
                X = X[:, -int(last):]
            w = ph["W"]
            block = math.gcd(w.shape[1], 16384)   # a block in float32
            with jax.default_matmul_precision("highest"):
                h = rmsnorm(jnp.sum(X, axis=2), eps, pn["gain"].astype(F32))
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
                cfg, p, u, experts_held, fp8,
                shared_on="no_shared_expert" not in faults,
                scaling_on="no_routed_scaling" not in faults)),
            "head": jax.jit(head)}

    def layers(self):
        """``(program, vertex names of its four parameter groups)`` in
        the order the stream passes them."""
        dense = int(self.cfg["first_k_dense_replace"])
        for i in range(int(self.cfg["num_hidden_layers"])):
            for half, kind in (("attn", "attn"),
                               ("ffn", "ffn" if i < dense else "moe")):
                yield kind, (f"L{i}_{half}_read", f"L{i}_{half}_norm",
                             f"L{i}_{kind}", f"L{i}_{half}_write")

    def __call__(self, params: Dict, ids):
        """Float32 logits (batch, ``last`` or time, vocabulary) of the
        whole sequence ``ids`` (batch, time)."""
        X = self.programs["embed"](params["embed"]["W"], ids)
        for kind, names in self.layers():
            X = self.programs[kind](*(params[n] for n in names), X)
        return self.programs["head"](params["final_norm"], params["head"], X)


def forward(cfg: Dict, params: Dict, ids, experts_held=None,
            last: Optional[int] = None, **kw):
    return Forward(cfg, experts_held, last=last, **kw)(params, ids)
