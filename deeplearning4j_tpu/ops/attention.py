"""Pallas flash attention — the hot-op kernel tier.

The reference's hot ops live in native cuDNN helpers
(``deeplearning4j-cuda/``); this build's equivalents are XLA lowerings
(``ops/convolution.py``) plus, where fusion beyond XLA pays, hand-written
Pallas TPU kernels.  Attention is the canonical case: materializing the
(T, T) score matrix is HBM-bandwidth-bound, while the flash formulation
keeps score tiles in VMEM with streaming-softmax accumulators and only
ever writes the (T, d) output.

:func:`flash_attention` — blockwise attention over (batch, T, heads, d):
grid (batch*heads, q_blocks, k_blocks), with the innermost k-block loop
accumulating into VMEM scratch (running max / denominator / weighted
sum — the same log-sum-exp stream ``parallel/sequence.ring_attention``
runs ACROSS chips; this kernel is the within-chip tier of the same
algorithm).  f32 accumulation regardless of input dtype; causal masking
by block position; off-TPU (tests, CPU mesh) runs in Pallas interpret
mode.

:func:`flash_attention_partial` — the same kernel emitting UNNORMALIZED
(acc, m, l) partials so callers can fold in blocks computed elsewhere;
``parallel/sequence.ring_flash_attention`` builds on it.

Backward: by default a FUSED two-pass Pallas backward (dK/dV then dQ)
rebuilds P tiles in VMEM from the forward's saved per-row logsumexp —
O(T·d) memory end to end, so full training steps run at T=16384 where
the XLA attention path cannot even compile its forward.
``fused_backward=False`` falls back to recomputing through the XLA
formulation (`parallel/sequence._full_attention`).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_NEG_INF = -1e30


def _make_flash_kernel(*, mode: str, sm_scale: float,
                       causal: bool, block_q: int, block_k: int,
                       k_len: int, num_k_blocks: int, precision):
    """ONE streaming-softmax kernel body for all forward variants —
    ``mode``: "normalized" (out), "partials" (unnormalized acc + m + l),
    or "normalized_lse" (out + per-row logsumexp, the fused-backward
    forward).  Only the finalize step differs, so the numerically
    delicate core cannot drift between them."""
    if mode not in ("normalized", "partials", "normalized_lse"):
        raise ValueError(f"unknown kernel mode {mode!r}")

    def kernel(q_ref, k_ref, v_ref, *refs):
        if mode == "partials":
            o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
        elif mode == "normalized_lse":
            (o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
            m_ref = l_ref = None
        else:
            (o_ref, m_scr, l_scr, acc_scr), m_ref, l_ref = refs, None, None
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr[:], _NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr[:])
            acc_scr[:] = jnp.zeros_like(acc_scr[:])

        # Causal: a k block strictly above this q block's diagonal
        # contributes nothing — skip its compute (halves causal FLOPs).
        needed = (ki * block_k <= qi * block_q + block_q - 1) \
            if causal else (ki >= 0)

        @pl.when(needed)
        def _compute():
            q = q_ref[0].astype(jnp.float32)       # (block_q, d)
            k = k_ref[0].astype(jnp.float32)       # (block_k, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=precision) * sm_scale
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos < k_len, s, _NEG_INF)   # T padding
            if causal:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

            m_prev = m_scr[:, :1]                  # (block_q, 1)
            l_prev = l_scr[:, :1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            alive = m_new > _NEG_INF / 2
            p = jnp.where(alive, jnp.exp(s - m_new), 0.0)
            correction = jnp.where(alive, jnp.exp(m_prev - m_new), 0.0)
            l_new = l_prev * correction + jnp.sum(p, axis=-1,
                                                  keepdims=True)
            acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
                p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

        @pl.when(ki == num_k_blocks - 1)
        def _finalize():
            if mode == "partials":
                o_ref[0] = acc_scr[:]
                m_ref[0] = m_scr[:]
                l_ref[0] = l_scr[:]
            else:
                denom = jnp.maximum(l_scr[:, :1], 1e-30)
                o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
                if mode == "normalized_lse":
                    lse = m_scr[:, :1] + jnp.log(denom)
                    lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)

    return kernel


# ----------------------------------------------------------- shared plumbing
def _pad_to(x: Array, axis: int, multiple: int) -> Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _sds(shape, dtype, like: Array) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct carrying ``like``'s shard_map varying-axes tag
    (required for pallas_call under shard_map with vma checking)."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _clamp_block(block: int, t: int) -> int:
    """Clamp to the sequence, rounded UP to the f32 sublane tile (8):
    Mosaic cannot tile a (1, block, d) BlockSpec whose sublane dim isn't
    a multiple of 8; padding covers block > t."""
    return -(-min(block, max(8, t)) // 8) * 8


def _auto_block(t: int) -> int:
    """Default block size for sequence length ``t``: the largest tuned
    tile whose padding overhead (T rounds up to a block multiple; padded
    rows are masked but still computed) stays under 12.5%.  512 is the
    measured v5e optimum at large T (2.6x over 128 at T=8192 — bigger
    tiles amortize the logsumexp bookkeeping over more MXU work; 1024
    regresses, 2048 exceeds VMEM); odd lengths degrade gracefully
    (e.g. T=640 -> 128, zero padding) instead of paying up to 2.5x
    padded FLOPs."""
    for b in (512, 256, 128):
        if -(-t // b) * b <= t * 1.125:
            return b
    return 128


def _to_bhd(x: Array, block: int) -> Array:
    """(B, T, H, D) -> (B*H, T_padded, D_padded): T padded to the block
    multiple, D to the 128 lane width (zero padding is inert in q.k^T
    and p@v)."""
    B, T, H, D = x.shape
    x = jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, T, D)
    return _pad_to(_pad_to(x, 1, block), 2, 128)


def _validate_qkv(q: Array, k: Array, v: Array,
                  same_t: bool) -> None:
    if q.ndim != 4:
        raise ValueError(f"expected (batch, T, heads, d), got {q.shape}")
    if k.shape != v.shape:
        raise ValueError(f"k/v shapes differ: {k.shape} vs {v.shape}")
    if same_t and q.shape != k.shape:
        raise ValueError(f"q/k/v shapes differ: {q.shape} {k.shape} "
                         f"{v.shape}")
    if (q.shape[0], q.shape[2], q.shape[3]) != \
            (k.shape[0], k.shape[2], k.shape[3]):
        raise ValueError(
            f"q and k/v disagree on batch/heads/d: {q.shape} vs {k.shape}")


# ----------------------------------------------------------------- forward
def _flash_forward(q: Array, k: Array, v: Array, causal: bool,
                   sm_scale: float, block_q: int, block_k: int,
                   interpret: bool, precision,
                   with_lse: bool = False):
    B, T, H, D = q.shape
    bh = B * H
    # lcm, not max: both block sizes must divide the padded T or
    # floor-divided block counts silently drop trailing blocks
    pad_mult = math.lcm(block_q, block_k)
    qt = _to_bhd(q, pad_mult)
    kt, vt = _to_bhd(k, pad_mult), _to_bhd(v, pad_mult)
    Tp, Dp = qt.shape[1], qt.shape[2]
    nq, nk = Tp // block_q, Tp // block_k

    kernel = _make_flash_kernel(
        mode="normalized_lse" if with_lse else "normalized",
        sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, k_len=T, num_k_blocks=nk, precision=precision)
    out_shapes = [_sds((bh, Tp, Dp), q.dtype, qt)]
    out_specs = [pl.BlockSpec((1, block_q, Dp),
                              lambda b, qi, ki: (b, qi, 0))]
    if with_lse:
        out_shapes.append(_sds((bh, Tp, 128), jnp.float32, qt))
        out_specs.append(pl.BlockSpec((1, block_q, 128),
                                      lambda b, qi, ki: (b, qi, 0)))
    result = pl.pallas_call(
        kernel,
        out_shape=out_shapes if with_lse else out_shapes[0],
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=out_specs if with_lse else out_specs[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running denom
            pltpu.VMEM((block_q, Dp), jnp.float32),    # weighted sum
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = result[0] if with_lse else result

    def back(x, d_keep):
        x = x[:, :T, :d_keep].reshape(B, H, T, d_keep)
        return jnp.transpose(x, (0, 2, 1, 3))

    if with_lse:
        return back(out, D), back(result[1], 1)[..., 0]   # (B,T,H) lse
    return back(out, D)


def flash_attention_partial(q: Array, k: Array, v: Array, *,
                            causal: bool = False,
                            sm_scale: Optional[float] = None,
                            block_q: int = 128, block_k: int = 128,
                            interpret: Optional[bool] = None,
                            precision=None):
    """Unnormalized blockwise attention of ``q`` against ONE K/V segment
    (``k``/``v`` may have a different T than ``q``).

    Returns ``(acc, m, l)`` with ``acc`` (batch, Tq, heads, d) f32 —
    the exp-weighted value sum — and ``m``/``l`` (batch, Tq, heads) f32
    running max / denominator.  Partials from different K/V segments
    (e.g. ring-rotated shards) merge exactly via the log-sum-exp
    combination (see ``parallel/sequence.ring_flash_attention``); the
    final output is ``acc / l``.  ``causal`` masks by LOCAL positions —
    correct for the diagonal ring step where q and kv shards share their
    global offset.  Padded q rows are trimmed post-hoc, not masked
    in-kernel (their partials are garbage but never returned).  Not
    differentiable; callers own the VJP.
    """
    _validate_qkv(q, k, v, same_t=False)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = (float(sm_scale) if sm_scale is not None
             else 1.0 / float(np.sqrt(D)))
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    block_q = _clamp_block(block_q, Tq)
    block_k = _clamp_block(block_k, Tk)
    bh = B * H

    qt = _to_bhd(q, block_q)
    kt, vt = _to_bhd(k, block_k), _to_bhd(v, block_k)
    Tqp, Dp = qt.shape[1], qt.shape[2]
    nq, nk = Tqp // block_q, kt.shape[1] // block_k

    kernel = _make_flash_kernel(
        mode="partials", sm_scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, k_len=Tk, num_k_blocks=nk,
        precision=precision)
    acc, m, l = pl.pallas_call(
        kernel,
        out_shape=[
            _sds((bh, Tqp, Dp), jnp.float32, qt),
            _sds((bh, Tqp, 128), jnp.float32, qt),
            _sds((bh, Tqp, 128), jnp.float32, qt),
        ],
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, qi, ki: (b, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, Dp), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)

    def back(x, d_keep):
        x = x[:, :Tq, :d_keep].reshape(B, H, Tq, d_keep)
        return jnp.transpose(x, (0, 2, 1, 3))

    return back(acc, D), back(m, 1)[..., 0], back(l, 1)[..., 0]


# ------------------------------------------------------- fused backward
def _bwd_tile(q_ref, k_ref, v_ref, do_ref, L_ref, D_ref, qi, ki, *,
              sm_scale, causal, block_q, block_k, q_len, k_len,
              precision):
    """The shared P-rebuild tile math of BOTH backward kernels: returns
    (q, k, do, p, ds) for one (q-block, k-block) tile.  One body so the
    numerically delicate core cannot drift between dK/dV and dQ (the
    same invariant the forward keeps via _make_flash_kernel)."""
    q = q_ref[0].astype(jnp.float32)           # (block_q, d)
    k = k_ref[0].astype(jnp.float32)           # (block_k, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision) * sm_scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = (k_pos < k_len) & (q_pos < q_len)
    if causal:
        mask &= q_pos >= k_pos
    L = L_ref[0][:, :1]                        # (block_q, 1) logsumexp
    p = jnp.where(mask, jnp.exp(s - L), 0.0)
    do = do_ref[0].astype(jnp.float32)         # (block_q, d)
    dp = jax.lax.dot_general(
        do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)
    D = D_ref[0][:, :1]
    ds = p * (dp - D) * sm_scale
    return q, k, do, p, ds


def _make_dkdv_kernel(*, num_q_blocks: int, precision, **tile_kw):
    """Grid (bh, k_blocks, q_blocks): accumulate dK/dV for one k-block
    across all q-blocks, rebuilding P tiles from the saved logsumexp —
    no (T, T) materialization."""
    causal = tile_kw["causal"]
    block_q, block_k = tile_kw["block_q"], tile_kw["block_k"]

    def kernel(q_ref, k_ref, v_ref, do_ref, L_ref, D_ref, dk_ref, dv_ref,
               dk_scr, dv_scr):
        ki = pl.program_id(1)
        qi = pl.program_id(2)

        @pl.when(qi == 0)
        def _init():
            dk_scr[:] = jnp.zeros_like(dk_scr[:])
            dv_scr[:] = jnp.zeros_like(dv_scr[:])

        needed = (qi * block_q + block_q - 1 >= ki * block_k) \
            if causal else (qi >= 0)

        @pl.when(needed)
        def _compute():
            q, _, do, p, ds = _bwd_tile(
                q_ref, k_ref, v_ref, do_ref, L_ref, D_ref, qi, ki,
                precision=precision, **tile_kw)
            dv_scr[:] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)
            dk_scr[:] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)

        @pl.when(qi == num_q_blocks - 1)
        def _finalize():
            dk_ref[0] = dk_scr[:]
            dv_ref[0] = dv_scr[:]

    return kernel


def _make_dq_kernel(*, num_k_blocks: int, precision, **tile_kw):
    """Grid (bh, q_blocks, k_blocks): accumulate dQ for one q-block."""
    causal = tile_kw["causal"]
    block_q, block_k = tile_kw["block_q"], tile_kw["block_k"]

    def kernel(q_ref, k_ref, v_ref, do_ref, L_ref, D_ref, dq_ref, dq_scr):
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            dq_scr[:] = jnp.zeros_like(dq_scr[:])

        needed = (ki * block_k <= qi * block_q + block_q - 1) \
            if causal else (ki >= 0)

        @pl.when(needed)
        def _compute():
            _, k, _, _, ds = _bwd_tile(
                q_ref, k_ref, v_ref, do_ref, L_ref, D_ref, qi, ki,
                precision=precision, **tile_kw)
            dq_scr[:] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)

        @pl.when(ki == num_k_blocks - 1)
        def _finalize():
            dq_ref[0] = dq_scr[:]

    return kernel


def _row_stat_to_bhd(x: Array, block: int) -> Array:
    """(B, T, H) per-row statistic -> (B*H, T_padded, 128) lane-broadcast
    layout the backward kernels read as ``ref[0][:, :1]``."""
    B, T, H = x.shape
    x = jnp.transpose(x, (0, 2, 1)).reshape(B * H, T)
    x = _pad_to(x, 1, block)
    return jnp.broadcast_to(x[:, :, None], x.shape + (128,))


def flash_attention_bwd(q: Array, k: Array, v: Array, out: Array,
                        L: Array, g: Array, *, causal: bool,
                        sm_scale: float, block_q: int = 128,
                        block_k: int = 128,
                        interpret: Optional[bool] = None,
                        precision=None, D_row: Optional[Array] = None):
    """Fused flash backward: (dq, dk, dv) from the forward residuals
    ``out`` and the per-row logsumexp ``L = m + log(l)`` — two Pallas
    passes (dK/dV then dQ), O(T·d) memory, no (T, T) tensors.

    ``k``/``v`` may carry a different T than ``q`` (one K/V SEGMENT of a
    larger sequence): with a GLOBAL ``L``/``D_row``, the returned grads
    are this segment's exact contribution, and contributions from
    different segments SUM — the property the ring backward in
    ``parallel/sequence`` is built on.  ``D_row`` (rowsum(dO·out) per q
    row) defaults to being computed from ``out``/``g``; segment callers
    pass the global value."""
    if out is None and D_row is None:
        raise ValueError("flash_attention_bwd needs `out` (to derive "
                         "D = rowsum(dO*out)) or an explicit `D_row`")
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    block_q = _clamp_block(block_q, Tq)
    block_k = _clamp_block(block_k, Tk)
    bh = B * H

    qt = _to_bhd(q, block_q)
    kt, vt = _to_bhd(k, block_k), _to_bhd(v, block_k)
    dot = _to_bhd(g.astype(jnp.float32), block_q)
    Tqp, Dp = qt.shape[1], qt.shape[2]
    nq, nk = Tqp // block_q, kt.shape[1] // block_k

    # D_i = rowsum(dO * O): cheap elementwise, stays in XLA
    Drow = (D_row if D_row is not None
            else jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                         axis=-1))                            # (B, Tq, H)
    Lt = _row_stat_to_bhd(L, block_q)
    Dt = _row_stat_to_bhd(Drow, block_q)

    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, q_len=Tq, k_len=Tk, precision=precision)
    Tkp = kt.shape[1]
    dk, dv = pl.pallas_call(
        _make_dkdv_kernel(num_q_blocks=nq, **common),
        out_shape=[_sds((bh, Tkp, Dp), jnp.float32, qt)] * 2,
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_q, Dp), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, ki, qi: (b, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, Dp), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, ki, qi: (b, ki, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, Dp), jnp.float32),
            pltpu.VMEM((block_k, Dp), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, dot, Lt, Dt)

    dq = pl.pallas_call(
        _make_dq_kernel(num_k_blocks=nk, **common),
        out_shape=_sds((bh, Tqp, Dp), jnp.float32, qt),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_q, Dp), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dp),
                               lambda b, qi, ki: (b, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, Dp), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, Lt, Dt)

    def back(x, t):
        x = x[:, :t, :D].reshape(B, H, t, D)
        return jnp.transpose(x, (0, 2, 1, 3))

    # f32 out: segment callers (the ring backward) SUM contributions, and
    # rounding each one to a low input dtype first would compound n-fold;
    # the VJP boundary casts once
    return back(dq, Tq), back(dk, Tk), back(dv, Tk)


# --------------------------------------------------------------- custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                precision, fused_backward):
    return _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret, precision)


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
               precision, fused_backward):
    if not fused_backward:
        out = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                             interpret, precision)
        return out, (q, k, v, None, None)
    # normalized_lse mode: the kernel finalizes out in-VMEM and emits
    # only the one per-row logsumexp residual the backward needs.
    out, L = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                            interpret, precision, with_lse=True)
    return out, (q, k, v, out, L)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, precision,
               fused_backward, res, g):
    q, k, v, out, L = res
    if fused_backward:
        dq, dk, dv = flash_attention_bwd(
            q, k, v, out, L, g, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
            precision=precision)
        return (dq.astype(q.dtype), dk.astype(k.dtype),
                dv.astype(v.dtype))
    from ..parallel.sequence import _full_attention
    _, vjp = jax.vjp(
        lambda q, k, v: _full_attention(q, k, v, causal=causal,
                                        sm_scale=sm_scale), q, k, v)
    return vjp(g)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    precision: Optional[jax.lax.Precision] = None,
                    fused_backward: bool = True) -> Array:
    """Flash attention over (batch, T, heads, d_head) q/k/v.

    ``block_q``/``block_k`` default to an auto-tuned size (see
    ``_auto_block``: 512 at large T — measured 2.6x over 128 for
    fwd+fused-bwd at T=8192 on v5e — smaller when T would pad
    wastefully).

    ``interpret=None`` auto-selects: compiled Mosaic on TPU, Pallas
    interpret mode elsewhere (slow but exact — the CPU-mesh test path).
    ``precision``: MXU precision for the two dots — default matches
    XLA's fast-f32 path (bf16 passes, ~1e-3 abs error at randn scale);
    ``jax.lax.Precision.HIGHEST`` gives ~1e-6 at 3x the MXU work.
    ``fused_backward=True`` (default) differentiates through two fused
    Pallas passes (dK/dV then dQ) rebuilding P tiles from the saved
    logsumexp — O(T·d) backward memory; ``False`` falls back to
    recomputing through the XLA formulation (O(T²) scores under grad)."""
    _validate_qkv(q, k, v, same_t=True)
    scale = (float(sm_scale) if sm_scale is not None
             else 1.0 / float(np.sqrt(q.shape[-1])))
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    T = q.shape[1]
    block_q = _clamp_block(block_q if block_q is not None
                           else _auto_block(T), T)
    block_k = _clamp_block(block_k if block_k is not None
                           else _auto_block(T), T)
    return _flash_core(q, k, v, causal, scale, block_q, block_k,
                       bool(interpret), precision, bool(fused_backward))


# --------------------------------------------------------------------------
# KV-cache ring decode: the inference twin of flash_attention.
#
# Autoregressive serving keeps per-session K/V projections resident on
# device in fixed-capacity (batch, heads, cache_len, d) buffers plus an
# int32 write cursor; each decode step writes the new token's K/V at the
# cursor via ``lax.dynamic_update_slice`` INSIDE the compiled program (the
# cache never crosses the wire) and attends the new queries against the
# whole ring with exact cursor masking.
#
# Parity contract (the bit-match the serving tests assert): slots at
# positions > cursor + t are masked with ``_NEG_INF``; ``exp`` of those
# scores underflows to EXACTLY 0.0, so masked slots contribute exact
# additive/multiplicative zeros to the softmax denominator and the P·V
# reduction.  Adding structural zeros never re-pairs the surviving terms
# of a reduction, so the result is bitwise independent of the ring
# capacity — decoding one token at a time against a 32-slot ring matches
# the full-sequence forward against a 128-slot ring to the last ulp
# (``tests/test_decode.py`` pins this at float64).


def kv_ring_update(k_cache: Array, v_cache: Array, cursor,
                   k_new: Array, v_new: Array):
    """Write (batch, heads, T, d) new keys/values into the ring at the
    cursor.  ``cursor`` may be a traced int32 scalar — the write happens
    inside the compiled step, in place when XLA can alias the buffers.
    Callers guarantee ``cursor + T <= cache_len`` (``dynamic_update_slice``
    clamps out-of-range starts, which would silently overwrite the
    newest history — ``serving.sessions`` hops to a larger bucket
    first)."""
    zero = jnp.zeros((), jnp.int32)
    cursor = jnp.asarray(cursor, jnp.int32)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_new.astype(k_cache.dtype), (zero, zero, cursor, zero))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_new.astype(v_cache.dtype), (zero, zero, cursor, zero))
    return k_cache, v_cache


def kv_ring_attention(q: Array, k_cache: Array, v_cache: Array, cursor, *,
                      sm_scale: Optional[float] = None) -> Array:
    """Dense masked attention of (batch, T, heads, d) queries against a
    (batch, heads, cache_len, d) KV ring whose slot ``c`` is visible to
    query ``t`` iff ``c <= cursor + t`` (causality within the chunk plus
    unwritten/stale-slot masking in one predicate).

    Softmax runs in f32 (f64 under float64 inputs — the parity-test
    dtype); the context comes back in the query dtype.  O(T·cache_len)
    — the right tier for T=1 decode steps, where the score "matrix" is
    a single row and flash tiling has nothing to save."""
    if q.ndim != 4 or k_cache.ndim != 4:
        raise ValueError(
            f"kv_ring_attention wants (B,T,H,d) q and (B,H,C,d) cache, "
            f"got q {q.shape}, k {k_cache.shape}")
    scale = (float(sm_scale) if sm_scale is not None
             else 1.0 / float(np.sqrt(q.shape[-1])))
    acc = jnp.promote_types(q.dtype, jnp.float32)
    cap = k_cache.shape[2]
    t = q.shape[1]
    cursor = jnp.asarray(cursor, jnp.int32)
    # (B,T,H,d) x (B,H,C,d) -> (B,H,T,C), f32/f64 accumulation
    s = jnp.einsum("bthd,bhcd->bhtc", q.astype(acc),
                   k_cache.astype(acc)) * jnp.asarray(scale, acc)
    valid = (jnp.arange(cap, dtype=jnp.int32)[None, :]
             <= cursor + jnp.arange(t, dtype=jnp.int32)[:, None])
    s = jnp.where(valid[None, None], s, jnp.asarray(_NEG_INF, acc))
    # every query sees at least its own key, so the row max is finite
    # and masked slots exp to exactly 0.0
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    ctx = jnp.einsum("bhtc,bhcd->bthd", p, v_cache.astype(acc))
    return ctx.astype(q.dtype)


# ---------------------------------------------------------------------------
# Latent (MLA) ring: the cache holds one compressed row a token, shared by
# all heads, and decode attends in that latent space.  Two forms of one
# algorithm under ``latent_ring_attention``: the dense masked form in plain
# ``jax.numpy`` (any dtype, any backend; the ring read twice and the float32
# scores through HBM), and a Pallas kernel that streams each conversation's
# ring through VMEM once (a TPU, bfloat16 or float32: the token step and
# prefill chunks).  ``latent_ring_path`` picks between them from the
# arguments alone.
# ---------------------------------------------------------------------------

def latent_ring_update(c_ring: Array, r_ring: Array, cursor,
                       c_new: Array, r_new: Array):
    """Write (batch, T, rank) compressed keys/values and (batch, T, d_rope)
    rotary keys into their rings at the cursor (``kv_ring_update`` for a
    cache with no head axis).  Callers guarantee ``cursor + T <=
    capacity``."""
    zero = jnp.zeros((), jnp.int32)
    cursor = jnp.asarray(cursor, jnp.int32)
    c_ring = jax.lax.dynamic_update_slice(
        c_ring, c_new.astype(c_ring.dtype), (zero, cursor, zero))
    r_ring = jax.lax.dynamic_update_slice(
        r_ring, r_new.astype(r_ring.dtype), (zero, cursor, zero))
    return c_ring, r_ring


def _einsum_acc(spec: str, a: Array, b: Array, acc) -> Array:
    """``einsum`` of two arrays of one storage dtype, accumulated and
    returned in ``acc``.  XLA:CPU's runtime (jax 0.9.0) lacks some
    bf16 x bf16 -> f32 products, so there the operands are widened
    first: the same numbers."""
    if jax.default_backend() == "cpu":
        a, b = a.astype(acc), b.astype(acc)
    return jnp.einsum(spec, a, b, preferred_element_type=acc)


def _make_latent_kernel(*, sm_scale: float, block: int, num_blocks: int,
                        t: int, widen: bool):
    """The streaming softmax of ``_make_flash_kernel`` for one
    conversation's latent ring: every ``T x heads`` query row against one
    ``block`` of slots a grid step, scores from the latent and the rotary
    half together, the context folded from the SAME latent block.  Slot 0
    is visible to every row, so the running maximum is finite from the
    first block on and a masked score's ``exp`` is exactly 0.0."""

    # numpy scalars: float32 literals in the kernel under x64 too
    scale, masked = np.float32(sm_scale), np.float32(_NEG_INF)

    def dot(a, b, contract):
        if widen:           # XLA:CPU under the interpreter: _einsum_acc
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   preferred_element_type=jnp.float32)

    def kernel(cursor_ref, limit_ref, ql_ref, qr_ref, c_ref, r_ref, o_ref,
               m_scr, l_scr, acc_scr):
        ki = pl.program_id(1)

        @pl.when(ki == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr[:], masked)
            l_scr[:] = jnp.zeros_like(l_scr[:])
            acc_scr[:] = jnp.zeros_like(acc_scr[:])

        # a block wholly beyond the newest visible slot was not fetched
        # (the index map repeats the last needed one) and adds nothing
        @pl.when(ki * block <= cursor_ref[0] + (t - 1))
        def _fold():
            c = c_ref[0]                                   # (block, rank)
            s = (dot(ql_ref[0], c, ((1,), (1,)))
                 + dot(qr_ref[0], r_ref[0], ((1,), (0,)))) * scale
            slot = ki * block + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(slot <= limit_ref[:], s, masked)
            m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            correction = jnp.exp(m_prev - m_new)
            l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[:] = acc_scr[:] * correction + dot(
                p.astype(c.dtype), c, ((1,), (0,)))
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

        @pl.when(ki == num_blocks - 1)
        def _finalize():
            o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)

    return kernel


#: ring slots a grid step, largest first; the first that divides the
#: capacity and fits the kernel's VMEM serves.  Swept on a v5e at the
#: decode cell's shape (PERF.md, PR 32): the token step reads 64 rings
#: of 4,096 slots in 0.521 / 0.422 / 0.421 / 0.420 ms at 512 / 1,024 /
#: 2,048 / 4,096 slots a block with the cursor at 4,000 and in 0.216 /
#: 0.217 / 0.341 / 0.423 ms with it at 1,000 (a grid step costs
#: ~0.35 us whether its block is skipped or not)
_LATENT_BLOCKS = (1024, 512, 256, 128)
#: what the kernel may hold of a v5e core's 128 MiB of VMEM, and what
#: the block is chosen to stay under by the reckoning below
_LATENT_VMEM_LIMIT = 40 << 20
_LATENT_VMEM_BUDGET = 24 << 20


def _latent_vmem_bytes(rows: int, rank: int, d_rope: int, block: int,
                       itemsize: int) -> int:
    """VMEM of one grid step, reckoned high: ring blocks, queries and
    context double-buffered at 128 lanes; the float32 accumulator, both
    statistics and the rows' limits at 128 lanes each; and four float32
    (rows, block) tiles for the scores on their way to ``p``."""
    lanes = lambda d: -(-d // 128) * 128
    ring = 2 * block * (lanes(rank) + lanes(d_rope)) * itemsize
    ends = 2 * rows * (2 * lanes(rank) + lanes(d_rope)) * itemsize
    held = rows * (lanes(rank) + 3 * 128) * 4
    return ring + ends + held + 4 * rows * block * 4


def latent_ring_block(rows: int, rank: int, d_rope: int, capacity: int,
                      dtype) -> int:
    """Ring slots a grid step of the streamed form for ``rows = T x
    heads`` query rows, or 0 where no block both divides ``capacity``
    and fits."""
    itemsize = jnp.dtype(dtype).itemsize
    for block in _LATENT_BLOCKS:
        if capacity % block == 0 and _latent_vmem_bytes(
                rows, rank, d_rope, block, itemsize) <= _LATENT_VMEM_BUDGET:
            return block
    return 0


def _mosaic() -> bool:
    """Whether Mosaic compiles Pallas kernels here (a TPU); elsewhere
    they are interpreted, which is for tests and not a faster form."""
    return jax.default_backend() == "tpu"


def latent_ring_path(t: int, heads: int, rank: int, d_rope: int,
                     capacity: int, dtype) -> str:
    """``"streamed"`` or ``"dense"``: which form
    :func:`latent_ring_attention` takes for ``t`` new positions a row
    against rings of ``capacity`` slots stored in ``dtype``.  Streamed
    where Mosaic compiles the kernel (a TPU), the storage is bfloat16
    or float32 (Mosaic has no float64), the chunk is shorter than the
    ring (``output()`` from a zero ring, ``t == capacity``, is plain
    causal attention and stays dense) and a block divides the capacity
    with ``t x heads`` rows of accumulators in VMEM.  Also what
    ``latent_attention_steps_total{path}`` is labelled by."""
    streamed = (_mosaic()
                and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                         jnp.dtype(jnp.float32))
                and t < capacity and (t * heads) % 8 == 0
                and latent_ring_block(t * heads, rank, d_rope, capacity,
                                      dtype) > 0)
    return "streamed" if streamed else "dense"


def latent_ring_attention_streamed(q_lat: Array, q_rope: Array,
                                   c_ring: Array, r_ring: Array, cursor, *,
                                   sm_scale: float,
                                   block: Optional[int] = None,
                                   interpret: Optional[bool] = None
                                   ) -> Array:
    """:func:`latent_ring_attention` as one Pallas kernel that reads each
    conversation's ring once: grid (batch, ring blocks), a block of both
    rings a step, float32 scores and statistics that never leave VMEM,
    ``p`` rounded to the ring's dtype only for the context product, the
    context normalized at the last block.  Blocks wholly beyond
    ``cursor + T - 1`` are neither fetched nor computed (masked slots
    weigh exactly 0.0 in the dense form: the same mathematics).
    ``block`` defaults to :func:`latent_ring_block`'s; ``interpret=None``
    is Mosaic on a TPU and the Pallas interpreter elsewhere."""
    batch, t, heads, rank = q_lat.shape
    cap, d_rope = c_ring.shape[1], r_ring.shape[2]
    rows = t * heads
    if block is None:
        block = latent_ring_block(rows, rank, d_rope, cap, c_ring.dtype)
    if not block or cap % block:
        raise ValueError(f"no block of the streamed latent attention "
                         f"divides a ring of {cap} slots (block {block})")
    if interpret is None:
        # the backend itself, not _mosaic(): a test that steers the
        # predicate still runs the kernel interpreted
        interpret = jax.default_backend() != "tpu"
    num_blocks = cap // block
    cursor = jnp.asarray(cursor, jnp.int32).reshape(1)
    # newest slot each query row sees: rows run (position, head)
    limit = (cursor + jnp.repeat(jnp.arange(t, dtype=jnp.int32),
                                 heads))[:, None]

    def newest(k, cur):
        # non-negative int32s: truncating division is the floor
        return jnp.minimum(k, jax.lax.div(cur[0] + jnp.int32(t - 1),
                                          jnp.int32(block)))

    whole = lambda b, k, cur: (b, 0, 0)
    # XLA:TPU keeps a (batch, capacity, d_rope) array of d_rope < 128
    # with the capacity minor, so this view is a bitcast there, and a
    # (d_rope, block) tile fills its lanes wherever it is not
    r_slots_minor = jnp.swapaxes(r_ring, 1, 2)
    out = pl.pallas_call(
        _make_latent_kernel(sm_scale=float(sm_scale), block=block,
                            num_blocks=num_blocks, t=t,
                            widen=bool(interpret)),
        out_shape=_sds((batch, rows, rank), q_lat.dtype, q_lat),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, num_blocks),
            in_specs=[
                pl.BlockSpec((rows, 1), lambda b, k, cur: (0, 0)),
                pl.BlockSpec((1, rows, rank), whole),
                pl.BlockSpec((1, rows, d_rope), whole),
                pl.BlockSpec((1, block, rank),
                             lambda b, k, cur: (b, newest(k, cur), 0)),
                pl.BlockSpec((1, d_rope, block),
                             lambda b, k, cur: (b, 0, newest(k, cur))),
            ],
            out_specs=pl.BlockSpec((1, rows, rank), whole),
            scratch_shapes=[
                pltpu.VMEM((rows, 128), jnp.float32),    # running max
                pltpu.VMEM((rows, 128), jnp.float32),    # running denom
                pltpu.VMEM((rows, rank), jnp.float32),   # weighted sum
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_LATENT_VMEM_LIMIT),
        interpret=interpret,
    )(cursor, limit, q_lat.reshape(batch, rows, rank),
      q_rope.reshape(batch, rows, d_rope), c_ring, r_slots_minor)
    return out.reshape(batch, t, heads, rank)


def latent_ring_attention(q_lat: Array, q_rope: Array, c_ring: Array,
                          r_ring: Array, cursor, *,
                          sm_scale: float) -> Array:
    """Masked attention in the latent space: (batch, T, heads, rank)
    queries (the ``kv_b`` key half already absorbed) and (batch, T, heads,
    d_rope) rotary queries against a (batch, capacity, rank) latent ring
    and a (batch, capacity, d_rope) rotary-key ring that every head
    shares.  Slot ``c`` is visible to query ``t`` iff ``c <= cursor + t``.
    Scores and softmax in float32 (float64 under float64 inputs); the
    latent context (batch, T, heads, rank) comes back in the query
    dtype, to be taken through the value half of ``kv_b`` by the
    caller.  Two forms of one algorithm, chosen by
    :func:`latent_ring_path` from the arguments alone."""
    form = (latent_ring_attention_streamed
            if latent_ring_path(q_lat.shape[1], q_lat.shape[2],
                                q_lat.shape[3], r_ring.shape[2],
                                c_ring.shape[1], c_ring.dtype) == "streamed"
            else latent_ring_attention_dense)
    return form(q_lat, q_rope, c_ring, r_ring, cursor, sm_scale=sm_scale)


def latent_ring_attention_dense(q_lat: Array, q_rope: Array, c_ring: Array,
                                r_ring: Array, cursor, *,
                                sm_scale: float) -> Array:
    """:func:`latent_ring_attention` in plain ``jax.numpy``: every query
    against every slot, the scores masked, maxed, exponentiated and
    normalized as one (batch, heads, T, capacity) tensor, the ring read
    for the scores and again for the context.  Any dtype, any backend."""
    acc = jnp.promote_types(q_lat.dtype, jnp.float32)
    cap, t = c_ring.shape[1], q_lat.shape[1]
    cursor = jnp.asarray(cursor, jnp.int32)
    s = (_einsum_acc("bthr,bcr->bhtc", q_lat, c_ring, acc)
         + _einsum_acc("bthd,bcd->bhtc", q_rope, r_ring, acc))
    s = s * jnp.asarray(sm_scale, acc)
    valid = (jnp.arange(cap, dtype=jnp.int32)[None, :]
             <= cursor + jnp.arange(t, dtype=jnp.int32)[:, None])
    s = jnp.where(valid[None, None], s, jnp.asarray(_NEG_INF, acc))
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    ctx = _einsum_acc("bhtc,bcr->bthr", p.astype(c_ring.dtype), c_ring, acc)
    return ctx.astype(q_lat.dtype)


# ---------------------------------------------------------------------------
# Indexed sparse attention over a grouped-query key/value ring (the sparse
# attention of DeepSeek-V3.2): a small indexer scores every cached row for
# every query, the ``topk`` best are selected EXACTLY (ties to the lowest
# position), and the query heads attend over the selected rows alone.  The
# key/value ring is slots-major with a slot's heads as the rows of one
# tile: (batch, capacity, 2 x kv heads, d), the key heads first and the
# value heads after them, so that a slot's keys AND values are one
# contiguous read that one copy descriptor names (Mosaic slices a ring in
# HBM by whole tiles only, and bfloat16's tile is 8 rows of 128: a slot of
# 4 + 4 heads is exactly one), and a new row is written as the
# projections leave it; indexer keys (batch, capacity, d_index), one
# head.  Three forms of one algorithm under ``sparse_ring_attention``,
# picked by ``sparse_attention_path`` from the call's shapes alone:
#
# gathered  on a TPU, a token step over a ring much longer than ``topk``:
#           the selection as slot numbers, and a Pallas kernel that
#           fetches the selected slots out of the ring (left in HBM) by
#           copy descriptors it issues itself, one a slot, and attends
#           over what landed: the bytes that cross the HBM bus are the
#           selected rows', not the ring's;
# streamed  on a TPU, a chunk, or a token step over a shorter ring: the
#           selection as a mask, and Pallas kernels that stream the rings
#           through VMEM in blocks, so that neither the indexer's (heads,
#           chunk, slots) scores nor the attention's ever reach HBM;
#           blocks beyond the newest visible slot are neither fetched nor
#           computed;
# masked    the same mask over dense ``jax.numpy`` attention: any dtype,
#           any backend, ``output()`` from a zero ring.
#
# Where the ring holds no more than ``topk`` slots every visible row is
# selected and no score is computed: plain causal grouped-query attention.
# ---------------------------------------------------------------------------

def sparse_ring_update(kv_ring: Array, i_ring: Array, cursor,
                       k_new: Array, v_new: Array, i_new: Array):
    """Write (batch, T, kv heads x d) keys and values and (batch, T,
    d_index) indexer keys into the key/value ring (batch, capacity, 2 x
    kv heads, d) and the indexer's at the cursor.  Callers guarantee
    ``cursor + T <= capacity``."""
    zero = jnp.zeros((), jnp.int32)
    cursor = jnp.asarray(cursor, jnp.int32)
    by_head = lambda a: a.reshape(a.shape[:2] + (-1, kv_ring.shape[3]))
    rows = jnp.concatenate([by_head(k_new), by_head(v_new)], axis=2)
    return (jax.lax.dynamic_update_slice(
                kv_ring, rows.astype(kv_ring.dtype),
                (zero, cursor, zero, zero)),
            jax.lax.dynamic_update_slice(
                i_ring, i_new.astype(i_ring.dtype), (zero, cursor, zero)))


def visible_slots(cursor, t: int, capacity: int) -> Array:
    """(T, capacity) bool: slot ``c`` is visible to query ``t`` iff
    ``c <= cursor + t``."""
    return (jnp.arange(capacity, dtype=jnp.int32)[None, :]
            <= jnp.asarray(cursor, jnp.int32)
            + jnp.arange(t, dtype=jnp.int32)[:, None])


def indexer_scores(q_idx: Array, w_idx: Array, i_ring: Array) -> Array:
    """``I[b, t, s] = sum_j w[b, t, j] * relu(q[b, t, j] . k[b, s])`` in
    float32, (batch, T, capacity), for (batch, T, heads, d) indexer
    queries, (batch, T, heads) head weights and the (batch, capacity, d)
    indexer-key ring; invisible slots are scored like any other (the
    selection masks them).  A zero is +0.0 whatever the signs of the
    weights, so that equal scores are equal bit patterns."""
    w_idx = w_idx.astype(jnp.float32)
    if q_idx.shape[1] == 1:
        # the token step: one product for all heads, (batch, heads, slots)
        s = _einsum_acc("bjd,bsd->bjs", q_idx[:, 0], i_ring, jnp.float32)
        out = jnp.sum(jnp.maximum(s, 0.0) * w_idx[:, 0, :, None],
                      axis=1)[:, None]
    else:
        # head after head: no (heads, T, slots) array
        def head(acc, qw):
            q, w = qw                   # (batch, T, d), (batch, T)
            s = _einsum_acc("btd,bsd->bts", q, i_ring, jnp.float32)
            return acc + jnp.maximum(s, 0.0) * w[..., None], None
        out, _ = jax.lax.scan(
            head, jnp.zeros(q_idx.shape[:2] + i_ring.shape[1:2],
                            jnp.float32),
            (jnp.moveaxis(q_idx, 2, 0), jnp.moveaxis(w_idx, 2, 0)))
    return jnp.where(out == 0.0, 0.0, out)


def _sortable(scores: Array) -> Array:
    """float32 to uint32 whose unsigned order is the floats' order (the
    sign bit set on a positive number, every bit flipped on a negative
    one): the smallest key of a number is above 0, which is left for
    what may not be selected."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


#: bits of the key settled in one pass over the scores: 15 candidates
#: are counted a pass, 8 passes in all
_SELECT_BITS = 4


def _kth_largest_key(keys: Array, k: int) -> Array:
    """The ``k``-th largest of ``keys`` (uint32, (..., n)) along the last
    axis, exactly, by a radix search from the top bits: a pass counts
    the keys at or above each candidate and keeps the largest candidate
    that ``k`` keys reach.  0 where fewer than ``k`` keys are above 0."""
    prefix = jnp.zeros(keys.shape[:-1], jnp.uint32)
    for low in range(32 - _SELECT_BITS, -1, -_SELECT_BITS):
        digit = jnp.zeros(prefix.shape, jnp.uint32)
        for c in range(1, 1 << _SELECT_BITS):
            candidate = prefix | jnp.uint32(c << low)
            reached = jnp.sum(keys >= candidate[..., None], axis=-1,
                              dtype=jnp.int32) >= k
            digit = digit + reached.astype(jnp.uint32)
        prefix = prefix | (digit << low)
    return prefix


def select_mask(scores: Array, visible: Array, k: int) -> Array:
    """Bool like ``scores`` (..., n): along the last axis the ``k``
    largest visible scores, every visible one where they are no more
    than ``k``; of equal scores at the ``k``-th place the lowest
    positions.  Exact."""
    keys = jnp.where(visible, _sortable(scores), jnp.uint32(0))
    kth = _kth_largest_key(keys, k)[..., None]
    at_or_above = visible & (keys >= kth)

    def with_ties():
        above = keys > kth
        tied = visible & (keys == kth)
        room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
        before = jnp.cumsum(tied, axis=-1, dtype=jnp.int32) - tied
        return above | (tied & (before < room))

    # more than k at or above the k-th key: equal scores straddle the
    # k-th place, which a running count along the positions settles;
    # rare, so the count is taken only then
    crowded = jnp.any(jnp.sum(at_or_above, axis=-1, dtype=jnp.int32) > k)
    return jax.lax.cond(crowded, with_ties, lambda: at_or_above)


#: queries a grid step of the streamed selection: (rows, capacity)
#: float32 scores are one block, 4 MB at 32 queries of 32,768 slots
_SELECT_ROWS = 32


def select_mask_streamed(scores: Array, cursor, k: int, *,
                         interpret: Optional[bool] = None) -> Array:
    """:func:`select_mask` for a ring's scores (batch, T, capacity) with
    slot ``c`` visible to query ``t`` iff ``c <= cursor + t``, as one
    Pallas kernel; the mask comes back as 0/1 in bfloat16, which is what
    :func:`sparse_attention_streamed` reads.  A grid step holds a few
    queries' whole score rows in VMEM and finds each row's ``k``-th key
    by a binary search over its 32 bits (a compare and a count a bit,
    nothing read twice from HBM), then writes ``key >= k-th``.  A token
    step's single query is folded into 8 rows of an eighth of the ring
    each, so that it fills its vector registers.  Where equal scores
    straddle the ``k``-th place in some row (more than ``k`` at or above
    it: rare) the whole call falls back on :func:`select_mask`, which
    settles them by position."""
    batch, t, cap = scores.shape
    fold = 8 if t == 1 and cap % 1024 == 0 else 1       # rows a query
    rows = fold if fold > 1 else next(
        r for r in (_SELECT_ROWS, 16, 8, 4, 2, 1) if t % r == 0)
    width = cap // fold
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cursor = jnp.asarray(cursor, jnp.int32).reshape(1)
    lowest = np.int32(-2 ** 31)

    def kernel(cursor_ref, s_ref, keep_ref, count_ref):
        bits = jax.lax.bitcast_convert_type(s_ref[0], jnp.int32)
        # int32 whose SIGNED order is the floats' order
        keys = jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        slot = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
        if fold > 1:            # the rows are one query's eighths
            slot, newest = slot + row * width, cursor_ref[0]
        else:
            newest = cursor_ref[0] + pl.program_id(1) * rows + row
        visible = slot <= newest
        # what may not be selected sorts below every number
        keys = jnp.where(visible, keys, lowest)

        def count(hit):
            n = jnp.sum(hit.astype(jnp.int32), axis=-1, keepdims=True)
            return jnp.sum(n, axis=0, keepdims=True) if fold > 1 else n

        def narrow(i, prefix):
            # ``prefix`` holds the bits found so far in offset binary
            # (unsigned order); flipping the top bit gives the signed
            # number the keys compare with
            candidate = prefix | jnp.left_shift(np.int32(1), 31 - i)
            return jnp.where(count(keys >= (candidate ^ lowest)) >= k,
                             candidate, prefix)

        kth = jax.lax.fori_loop(
            0, 32, narrow,
            jnp.zeros((1 if fold > 1 else rows, 1), jnp.int32)) ^ lowest
        keep = visible & (keys >= kth)
        keep_ref[0] = keep.astype(keep_ref.dtype)
        count_ref[0] = jnp.broadcast_to(count(keep), (rows, 128))

    at = lambda b, q, cur: (b, q, 0)
    keep, count = pl.pallas_call(
        kernel,
        out_shape=[_sds((batch, t * fold, width), jnp.bfloat16, scores),
                   _sds((batch, t * fold, 128), jnp.int32, scores)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, t * fold // rows),
            in_specs=[pl.BlockSpec((1, rows, width), at)],
            out_specs=[pl.BlockSpec((1, rows, width), at),
                       pl.BlockSpec((1, rows, 128), at)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_SPARSE_VMEM_LIMIT),
        interpret=interpret,
    )(cursor, scores.astype(jnp.float32).reshape(batch, t * fold, width))
    return jax.lax.cond(
        jnp.any(count[..., 0] > k),
        lambda: select_mask(scores, visible_slots(cursor[0], t, cap)[None],
                            k).astype(jnp.bfloat16),
        lambda: keep.reshape(batch, t, cap))


def _grouped(q: Array, kv_heads: int) -> Array:
    """(batch, T, heads, d) queries as (batch, T, kv heads, group, d):
    query head ``h`` reads key/value head ``h // group``."""
    b, t, h, d = q.shape
    return q.reshape(b, t, kv_heads, h // kv_heads, d)


def _keys_values(kv_ring: Array):
    """The key heads and the value heads of a (batch, slots, 2 x kv
    heads, d) ring, (batch, slots, kv heads, d) each."""
    kv_heads = kv_ring.shape[2] // 2
    return kv_ring[:, :, :kv_heads], kv_ring[:, :, kv_heads:]


def _softmax_context(s: Array, keep: Array, v: Array, spec: str, dtype):
    """Softmax of the float32 scores ``s`` over their last axis with
    ``keep`` false entries left out, times ``v`` by ``spec``.  Every
    query keeps at least one entry, so the row maximum is finite and a
    masked entry weighs exactly 0.0."""
    s = jnp.where(keep, s, jnp.asarray(_NEG_INF, s.dtype))
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return _einsum_acc(spec, p.astype(v.dtype), v, s.dtype).astype(dtype)


def sparse_attention_masked(q: Array, kv_ring: Array, selected: Array, *,
                            sm_scale: float) -> Array:
    """The dense form: (batch, T, heads, d) queries against every slot
    of the (batch, capacity, 2 x kv heads, d) ring, ``selected`` (batch,
    T, capacity) saying which count.  Any dtype, any backend; the scores
    are one (batch, heads, T, capacity) array."""
    acc = jnp.promote_types(q.dtype, jnp.float32)
    k, v = _keys_values(kv_ring)
    qg = _grouped(q, k.shape[2])                             # (b, t, g, r, d)
    s = _einsum_acc("btgrd,bsgd->bgrts", qg, k, acc) \
        * jnp.asarray(sm_scale, acc)
    ctx = _softmax_context(s, selected[:, None, None], v,
                           "bgrts,bsgd->btgrd", q.dtype)
    return ctx.reshape(q.shape)


#: ring slots a grid step of the two streamed kernels, largest first
_SPARSE_BLOCKS = (1024, 512, 256, 128)
#: and of the indexer's kernel under a token step's single position
_INDEXER_TOKEN_BLOCKS = (8192, 4096, 2048) + _SPARSE_BLOCKS
_SPARSE_VMEM_LIMIT = 64 << 20

def sparse_ring_block(capacity: int) -> int:
    """Ring slots a grid step of the streamed form, or 0 where no block
    divides ``capacity``."""
    return next((b for b in _SPARSE_BLOCKS if capacity % b == 0), 0)


#: slots a selected one from which a token step fetches its rows by
#: descriptor (read off ``tools/sparse_attention_sweep.py --crossover``:
#: see ``sparse_attention_path``)
_GATHER_RATIO = 12
#: descriptors issued a turn of the gathered kernel's loop, most first
_GATHER_UNROLL = (32, 16, 8)


def sparse_attention_path(t: int, heads: int, kv_heads: int, d: int,
                          capacity: int, dtype, topk: int) -> str:
    """``"gathered"``, ``"streamed"`` or ``"masked"``: which form
    :func:`sparse_ring_attention` takes for ``t`` new positions a row
    against rings of ``capacity`` slots stored in ``dtype`` of which a
    query selects ``topk``.  Streamed where Mosaic compiles the kernels
    (a TPU), the storage is bfloat16 or float32, a head fills the 128
    lanes, the new positions are fewer than the ring's slots (more than
    one are padded to whole sublane tiles of 8), and a block divides the
    capacity; masked elsewhere.  Gathered where the streamed form's
    conditions hold, the call is a token step (``t == 1``: the queries
    of a chunk together select nearly every slot), and the ring is at
    least ``_GATHER_RATIO`` times ``topk`` long: the gathered kernel's
    time is set by the descriptors it issues, one a selected slot, and
    the list of slots it takes (on a v5e 0.40-0.42 ms and 0.08-0.16 ms
    for 8 conversations of 2,048 slots, out of rings of 4,096 to
    131,072 slots), the streamed kernel's by the ring's bytes (0.78 ms
    for 8 rings of 32,768 slots, 24 ns a slot): they cross near 21,000
    slots, ten times ``topk`` (``tools/sparse_attention_sweep.py``;
    PERF.md, PR 40).  Also what ``sparse_attention_steps_total{path}``
    is labelled by."""
    streamed = (_mosaic()
                and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                         jnp.dtype(jnp.float32))
                and d % 128 == 0 and t < capacity
                and heads % kv_heads == 0
                and sparse_ring_block(capacity) > 0)
    if not streamed:
        return "masked"
    gathered = (t == 1 and capacity >= _GATHER_RATIO * topk
                and topk % _GATHER_UNROLL[-1] == 0)
    return "gathered" if gathered else "streamed"


def _newest_block(k, cur, t: int, block: int):
    """Index map of a ring block: the block itself, or the newest one
    that holds a visible slot where this one holds none (its fetch is
    then a repeat, which Pallas leaves out).  Non-negative int32s:
    truncating division is the floor."""
    return jnp.minimum(k, jax.lax.div(cur[0] + jnp.int32(t - 1),
                                      jnp.int32(block)))


def _kernel_dot(widen: bool):
    def dot(a, b, contract):
        if widen:           # XLA:CPU under the interpreter: _einsum_acc
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   preferred_element_type=jnp.float32)
    return dot


def indexer_scores_streamed(q_idx: Array, w_idx: Array, i_ring: Array,
                            cursor, *, block: Optional[int] = None,
                            interpret: Optional[bool] = None) -> Array:
    """:func:`indexer_scores` as one Pallas kernel: grid (batch, ring
    blocks), a block of indexer keys a step, every head's product
    weighted and summed in VMEM, the (batch, T, capacity) float32 sum
    the only thing written.  A chunk's heads go one at a time, (T,
    block) each; the single position of a token step takes its heads as
    the rows of one product.  A block wholly beyond ``cursor + T - 1``
    is not fetched and reads 0."""
    batch, t, heads, d = q_idx.shape
    cap = i_ring.shape[1]
    # a token step's product is (heads, block): long blocks, or the grid
    # steps' own cost outweighs so narrow a ring's bytes
    block = block or (sparse_ring_block(cap) if t > 1 else next(
        (b for b in _INDEXER_TOKEN_BLOCKS if cap % b == 0), 0))
    if not block or cap % block:
        raise ValueError(f"no block of the streamed indexer divides a "
                         f"ring of {cap} slots (block {block})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dot = _kernel_dot(bool(interpret))
    cursor = jnp.asarray(cursor, jnp.int32).reshape(1)
    w_idx = w_idx.astype(jnp.float32)[..., None]
    if t > 1:       # (batch, heads, T, .): a product a head, rows = T
        q_idx, w_idx = jnp.swapaxes(q_idx, 1, 2), jnp.swapaxes(w_idx, 1, 2)
    products, rows = q_idx.shape[1], q_idx.shape[2]

    def kernel(cursor_ref, q_ref, w_ref, ring_ref, o_ref):
        ki = pl.program_id(1)

        @pl.when(ki * block <= cursor_ref[0] + (t - 1))
        def _score():
            ring = ring_ref[0]                          # (d, block)
            total = jnp.zeros((rows, block), jnp.float32)
            for j in range(products):
                s = dot(q_ref[0, j], ring, ((1,), (0,)))
                total = total + jnp.maximum(s, 0.0) * w_ref[0, j]
            if t == 1:                                  # rows are heads
                total = jnp.sum(total, axis=0, keepdims=True)
            o_ref[0] = jnp.where(total == 0.0, 0.0, total)

        @pl.when(ki * block > cursor_ref[0] + (t - 1))
        def _beyond():
            o_ref[0] = jnp.zeros((t, block), jnp.float32)

    whole = lambda b, k, cur: (b, 0, 0, 0)
    # the ring with its slots minor, as ``latent_ring_attention_streamed``
    # takes its narrow ring: a bitcast where XLA:TPU keeps it so, and a
    # (d, block) tile fills its lanes wherever it does not
    return pl.pallas_call(
        kernel,
        out_shape=_sds((batch, t, cap), jnp.float32, q_idx),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, cap // block),
            in_specs=[
                pl.BlockSpec((1, products, rows, d), whole),
                pl.BlockSpec((1, products, rows, 1), whole),
                pl.BlockSpec((1, d, block), lambda b, k, cur: (
                    b, 0, _newest_block(k, cur, t, block))),
            ],
            out_specs=pl.BlockSpec((1, t, block),
                                   lambda b, k, cur: (b, 0, k))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_SPARSE_VMEM_LIMIT),
        interpret=interpret,
    )(cursor, q_idx, w_idx, jnp.swapaxes(i_ring, 1, 2))


def _head_rows(kv_ref, row: int, rows: int, block: int, words: bool):
    """The (block, d) rows that row ``row`` of every slot's tile makes
    (a key or a value head), of a (1, block x rows, d) block of the ring
    in VMEM, slot after slot: a read of every ``rows``-th row.
    ``words``: the storage is 16 bits wide and Mosaic compiles this, so
    two rows share a sublane's 32-bit words; the words of every
    ``rows / 2``-th sublane are read and shifted (a bfloat16 is the
    upper half of the float32 of the same value), since a strided read
    of half-words is a shuffle a row (twice the streamed kernel's time
    when it was tried)."""
    if not words:
        return kv_ref[0, pl.ds(row, block, stride=rows), :]
    pairs = kv_ref.bitcast(jnp.uint32)[
        0, pl.ds(row // 2, block, stride=rows // 2), :]
    upper = pairs & jnp.uint32(0xFFFF0000) if row % 2 else pairs << 16
    return jax.lax.bitcast_convert_type(upper, jnp.float32).astype(
        kv_ref.dtype)


def _fold_ring_block(dot, q_at, kv_ref, keep, m_scr, l_scr, acc_scr, *,
                     kv_heads: int, rows: int, part: int, block: int,
                     words: bool, scale):
    """One block of a joined key/value ring folded into the streaming
    softmax of every query head: what the streamed kernels over such a
    ring share.  ``kv_ref`` is the (1, block x 2 x kv heads, d) block in
    VMEM, ``q_at((head, rows))`` the queries of a key/value head (``rows``
    of them, folded ``part`` at a time), ``keep`` (part, block) which
    slots those rows count; the scratches are (kv heads, rows, 128 | d)
    float32.  A row that has kept nothing yet holds its maximum at the
    mask's value: its entries are not exp(0)."""
    masked = np.float32(_NEG_INF)
    for g in range(kv_heads):
        k, v = (_head_rows(kv_ref, row, 2 * kv_heads, block, words)
                for row in (g, kv_heads + g))               # (block, d)
        for start in range(0, rows, part):
            at = (g, pl.ds(start, part))
            s = dot(q_at(at), k, ((1,), (1,))) * scale
            s = jnp.where(keep, s, masked)
            m_prev, l_prev = m_scr[at][:, :1], l_scr[at][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alive = m_new > masked / 2
            p = jnp.where(alive & keep, jnp.exp(s - m_new), 0.0)
            correction = jnp.where(alive, jnp.exp(m_prev - m_new), 0.0)
            l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[at] = acc_scr[at] * correction + dot(
                p.astype(v.dtype), v, ((1,), (0,)))
            m_scr[at] = jnp.broadcast_to(m_new, (part, 128))
            l_scr[at] = jnp.broadcast_to(l_new, (part, 128))


def sparse_attention_streamed(q: Array, kv_ring: Array, selected: Array,
                              cursor, *, sm_scale: float,
                              block: Optional[int] = None,
                              interpret: Optional[bool] = None) -> Array:
    """:func:`sparse_attention_masked` as one Pallas kernel that reads
    each conversation's ring once: grid (batch, ring blocks); a step
    takes one block of the ring, every slot's key and value heads (whole
    tiles: one contiguous read), and the block of ``selected`` (batch,
    T, capacity), and folds it into the streaming softmax of every query
    head, float32 scores that never leave VMEM.  A chunk's query heads
    go one at a time, (T, block) scores each; the single position of a
    token step takes a key/value head's whole group as rows.  Blocks
    wholly beyond ``cursor + T - 1`` are neither fetched nor computed; a
    block in which a query selected nothing adds nothing to it."""
    batch, t, heads, d = q.shape
    cap, kv_heads = kv_ring.shape[1], kv_ring.shape[2] // 2
    group = heads // kv_heads
    block = block or sparse_ring_block(cap)
    if not block or cap % block:
        raise ValueError(f"no block of the streamed sparse attention "
                         f"divides a ring of {cap} slots (block {block})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dot = _kernel_dot(bool(interpret))
    num_blocks = cap // block
    scale, masked = np.float32(sm_scale), np.float32(_NEG_INF)
    cursor = jnp.asarray(cursor, jnp.int32).reshape(1)
    # rows of a key/value head run (query head of the group, position);
    # they are folded ``part`` rows at a time, all of which share ``keep``
    part = group if t == 1 else t
    rows = group * t
    words = not interpret and kv_ring.dtype == jnp.bfloat16

    def kernel(cursor_ref, q_ref, kv_ref, keep_ref, o_ref,
               m_scr, l_scr, acc_scr):
        ki = pl.program_id(1)

        @pl.when(ki == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr[:], masked)
            l_scr[:] = jnp.zeros_like(l_scr[:])
            acc_scr[:] = jnp.zeros_like(acc_scr[:])

        @pl.when(ki * block <= cursor_ref[0] + (t - 1))
        def _fold():
            keep = keep_ref[0].astype(jnp.float32) != 0.0   # (t, block)
            if part != t:
                keep = jnp.broadcast_to(keep, (part, block))
            _fold_ring_block(dot, lambda at: q_ref[(0,) + at], kv_ref, keep,
                             m_scr, l_scr, acc_scr, kv_heads=kv_heads,
                             rows=rows, part=part, block=block, words=words,
                             scale=scale)

        @pl.when(ki == num_blocks - 1)
        def _finalize():
            o_ref[0] = (acc_scr[:] / l_scr[:, :, :1]).astype(o_ref.dtype)

    newest = lambda k, cur: _newest_block(k, cur, t, block)
    whole = lambda b, k, cur: (b, 0, 0, 0)
    # (batch, kv heads, group x T, d): a head's rows, query head major
    qg = jnp.transpose(_grouped(q, kv_heads), (0, 2, 3, 1, 4)).reshape(
        batch, kv_heads, rows, d)
    # the ring as rows of d, a slot's heads after one another: the same
    # bytes (a slot's tile is 8 of those rows), which strided reads take
    # apart in VMEM
    out = pl.pallas_call(
        kernel,
        out_shape=_sds(qg.shape, q.dtype, q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, num_blocks),
            in_specs=[
                pl.BlockSpec((1, kv_heads, rows, d), whole),
                pl.BlockSpec((1, block * 2 * kv_heads, d),
                             lambda b, k, cur: (b, newest(k, cur), 0)),
                pl.BlockSpec((1, t, block),
                             lambda b, k, cur: (b, 0, newest(k, cur))),
            ],
            out_specs=pl.BlockSpec((1, kv_heads, rows, d), whole),
            scratch_shapes=[
                pltpu.VMEM((kv_heads, rows, 128), jnp.float32),  # max
                pltpu.VMEM((kv_heads, rows, 128), jnp.float32),  # denom
                pltpu.VMEM((kv_heads, rows, d), jnp.float32),    # sum
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_SPARSE_VMEM_LIMIT),
        interpret=interpret,
    )(cursor, qg, kv_ring.reshape(batch, cap * 2 * kv_heads, d),
      selected.astype(jnp.bfloat16))
    out = out.reshape(batch, kv_heads, group, t, d)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(q.shape)


#: outputs :func:`selected_slots` finds at once (rows of its products)
_SLOT_LIST_RUN = 512


def selected_slots(selected: Array, topk: int, *,
                   interpret: Optional[bool] = None):
    """The selection of a token step as numbers: ``(slots (batch, topk)
    int32, count (batch,) int32)`` for ``selected`` (batch, capacity),
    nonzero where a slot is selected, at most ``topk`` a row.  The first
    ``count`` of a row's slots are its selected ones in rising order;
    the rest name slot 0 (a row that can be fetched and weighs nothing).
    One Pallas kernel, grid (batch,), no sort and no scatter: the ring's
    slots are taken 128 at a time; output ``j`` finds its group by
    comparing ``j`` with the groups' running counts, takes that group's
    128 marks by a one-hot product, ranks them by a product with a
    triangle, and keeps the lane whose rank is ``j``'s place in the
    group.  Every product is of whole numbers no larger than 128 in
    bfloat16 summed in float32: exact."""
    batch, cap = selected.shape
    lanes = math.gcd(cap, 128)
    groups = cap // lanes
    run = math.gcd(topk, _SLOT_LIST_RUN)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dot = _kernel_dot(bool(interpret))
    f32, bf16 = jnp.float32, jnp.bfloat16
    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape,
                                                        axis)

    def kernel(marks_ref, turned_ref, slots_ref, count_ref):
        marks, turned = marks_ref[0], turned_ref[0]     # (G, L), (L, G)
        mm = lambda a, b: dot(a, b, ((1,), (0,)))
        ones = jnp.ones((groups, lanes), bf16)
        # a group's count along its lanes; as a row over the groups, and
        # the running count after each group
        held = mm(marks, jnp.ones((lanes, lanes), bf16)).astype(bf16)
        counts = jnp.broadcast_to(jnp.sum(
            turned.astype(f32), axis=0, keepdims=True), (8, groups))
        rows8 = counts.astype(bf16)
        end = mm(rows8, (iota((groups, groups), 0)
                         <= iota((groups, groups), 1)).astype(bf16))[:1]
        start = end - counts[:1]
        total = mm(rows8, ones)                         # (8, L), all equal
        earlier = (iota((lanes, lanes), 0) < iota((lanes, lanes), 1)
                   ).astype(bf16)
        number = iota((lanes, lanes), 0).astype(bf16)
        # the outputs go down the sublanes, a run at a time, so that the
        # masks are the products' left sides and what never changes (the
        # marks, the triangle) their right: a result is (run, L) with
        # every lane the same, turned at the end into a row
        for at in range(0, topk, run):
            j = (at + iota((run, 1), 0)).astype(f32)
            # the marks of output j's group, lane by lane, and how many
            # of them come before each
            row = mm(((start <= j) & (j < end)).astype(bf16), marks)
            before = mm(row.astype(bf16), earlier)
            # the marks in, and the number of, the groups that end at or
            # before j: its group's first place, and its group
            ended = (end <= j).astype(bf16)
            hit = (row > 0.5) & (before == j - mm(ended, held))
            slot = mm(ended, ones) * lanes + mm(hit.astype(bf16), number)
            slot = jnp.where(j < total[:1], slot, 0.0)
            slots_ref[0, :, at:at + run] = slot.T[:8].astype(jnp.int32)
        count_ref[0] = total.astype(jnp.int32)

    marks = (selected != 0).astype(bf16).reshape(batch, groups, lanes)
    slots, count = pl.pallas_call(
        kernel,
        out_shape=[_sds((batch, 8, topk), jnp.int32, selected),
                   _sds((batch, 8, lanes), jnp.int32, selected)],
        grid=(batch,),
        in_specs=[pl.BlockSpec((1, groups, lanes), lambda b: (b, 0, 0)),
                  pl.BlockSpec((1, lanes, groups), lambda b: (b, 0, 0))],
        out_specs=[pl.BlockSpec((1, 8, topk), lambda b: (b, 0, 0)),
                   pl.BlockSpec((1, 8, lanes), lambda b: (b, 0, 0))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_SPARSE_VMEM_LIMIT),
        interpret=interpret,
    )(marks, jnp.swapaxes(marks, 1, 2))
    return slots[:, 0], count[:, 0, 0]


def sparse_attention_gathered(q: Array, kv_ring: Array, slots: Array,
                              count: Array, *, sm_scale: float,
                              interpret: Optional[bool] = None) -> Array:
    """A token step's attention over the slots its selection named,
    fetched by the kernel's own descriptors: (batch, 1, heads, d)
    queries, the (batch, capacity, 2 x kv heads, d) ring left in HBM,
    ``slots`` (batch, topk) int32 of which the first ``count`` (batch,)
    are selected (:func:`selected_slots`).  Grid (batch,); a step issues
    one copy a listed slot, the slot's tile of keys and values (one
    contiguous read) into its row of a (topk, 2 x kv heads, d) landing
    buffer in VMEM, all on one DMA semaphore, and waits once, for the
    buffer's bytes; then every key/value head's group attends over the
    landed rows, float32 scores and softmax, the rows past ``count``
    masked.  What bounds it is the descriptors the scalar core issues
    (about 20 ns each on a v5e, whatever they move), so the loop that
    issues them is unrolled as far as ``topk`` divides.  (Issuing the
    next conversation's descriptors inside the loop that attends over
    this one's rows, to hide the attention, was tried and was slower:
    493 us against 425.)  Same rows, same arithmetic as the streamed
    form; of the ring only ``batch x topk`` slots cross the HBM bus,
    which is what the kernel declares as its cost (the trace's account
    of HBM traffic charges an undeclared kernel every operand whole)."""
    batch, t, heads, d = q.shape
    kv_heads = kv_ring.shape[2] // 2
    group, topk = heads // kv_heads, slots.shape[1]
    if t != 1 or topk % _GATHER_UNROLL[-1]:
        raise ValueError(f"the gathered sparse attention takes one position "
                         f"a row and whole turns of {_GATHER_UNROLL[-1]} "
                         f"slots (t {t}, topk {topk})")
    unroll = next(u for u in _GATHER_UNROLL if topk % u == 0)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dot = _kernel_dot(bool(interpret))
    scale, masked = np.float32(sm_scale), np.float32(_NEG_INF)

    def kernel(slots_ref, count_ref, q_ref, ring_ref, o_ref, rows, sem):
        b = pl.program_id(0)

        def issue(turn, _):
            for u in range(unroll):
                n = turn * unroll + u
                pltpu.make_async_copy(ring_ref.at[b, slots_ref[b, n]],
                                      rows.at[n], sem).start()
            return 0

        jax.lax.fori_loop(0, topk // unroll, issue, 0)
        # one wait for all of them: a descriptor the size of the buffer
        pltpu.make_async_copy(rows, rows, sem).wait()
        live = jax.lax.broadcasted_iota(jnp.int32, (group, topk), 1) \
            < count_ref[b]
        for g in range(kv_heads):
            k, v = rows[:, g, :], rows[:, kv_heads + g, :]   # (topk, d)
            s = dot(q_ref[0, g], k, ((1,), (1,))) * scale
            s = jnp.where(live, s, masked)
            p = jnp.where(live, jnp.exp(
                s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
            ctx = dot(p.astype(v.dtype), v, ((1,), (0,)))
            o_ref[0, g] = (ctx / jnp.sum(p, axis=-1, keepdims=True)
                           ).astype(o_ref.dtype)

    itemsize = jnp.dtype(kv_ring.dtype).itemsize
    fetched = batch * topk * 2 * kv_heads * d * itemsize
    out = pl.pallas_call(
        kernel,
        out_shape=_sds((batch, kv_heads, group, d), q.dtype, q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch,),
            in_specs=[
                pl.BlockSpec((1, kv_heads, group, d),
                             lambda b, slots, count: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, kv_heads, group, d),
                                   lambda b, slots, count: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((topk, 2 * kv_heads, d), kv_ring.dtype),
                pltpu.SemaphoreType.DMA(()),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_SPARSE_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=4 * batch * heads * topk * d,
            transcendentals=batch * heads * topk,
            bytes_accessed=fetched + 2 * q.size * q.dtype.itemsize
            + slots.size * 4),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(slots.astype(jnp.int32), count.astype(jnp.int32),
      q.reshape(batch, kv_heads, group, d), kv_ring)
    return out.reshape(q.shape)


def sparse_ring_attention(q: Array, q_idx: Array, w_idx: Array,
                          kv_ring: Array, i_ring: Array,
                          cursor, *, topk: int, sm_scale: float,
                          scope=None):
    """Attention of (batch, T, heads, d) queries over the ``topk`` cached
    rows their indexer picks; the context, (batch, T, heads, d).
    ``q_idx`` (batch, T, index heads, d_index) and
    ``w_idx`` (batch, T, index heads) are the indexer's queries and head
    weights; slot ``c`` is visible to query ``t`` iff ``c <= cursor +
    t``.  The rings already hold the new positions.  ``scope(part)``,
    where given, is entered around each part (``indexer``, ``select``,
    ``sparse_attention``) so that a trace can tell them apart."""
    scope = scope or (lambda part: contextlib.nullcontext())
    batch, t, heads, d = q.shape
    cap, kv_heads = kv_ring.shape[1], kv_ring.shape[2] // 2
    path = sparse_attention_path(t, heads, kv_heads, d, cap, kv_ring.dtype,
                                 topk)
    cursor = jnp.asarray(cursor, jnp.int32)
    if path == "streamed" and t > 1 and t % 8:
        # the kernels take whole sublane tiles of positions: a chunk of
        # another length (a prompt's remainder) is padded with queries
        # whose rows are cut off again; what they read is nobody's
        pad = lambda a: jnp.pad(
            a, [(0, 0), (0, -t % 8)] + [(0, 0)] * (a.ndim - 2))
        return sparse_ring_attention(
            pad(q), pad(q_idx), pad(w_idx), kv_ring, i_ring, cursor,
            topk=topk, sm_scale=sm_scale, scope=scope)[:, :t]

    def attend(picked):
        with scope("sparse_attention"):
            if path == "gathered":
                return sparse_attention_gathered(q, kv_ring, *picked,
                                                 sm_scale=sm_scale)
            if path == "streamed":
                return sparse_attention_streamed(
                    q, kv_ring, picked, cursor, sm_scale=sm_scale)
            return sparse_attention_masked(q, kv_ring, picked,
                                           sm_scale=sm_scale)

    if cap <= topk:
        # every visible row is selected: no score decides anything
        return attend(jnp.broadcast_to(visible_slots(cursor, t, cap)[None],
                                       (batch, t, cap)))
    kernels = path != "masked"
    with scope("indexer"):
        scores = (indexer_scores_streamed(q_idx, w_idx, i_ring, cursor)
                  if kernels else indexer_scores(q_idx, w_idx, i_ring))
    with scope("select"):
        picked = (select_mask_streamed(scores, cursor, topk)
                  if kernels else select_mask(
                      scores, visible_slots(cursor, t, cap)[None], topk))
        if path == "gathered":      # the mask as (slot numbers, count)
            picked = selected_slots(picked[:, 0], topk)
    return attend(picked)


# ---------------------------------------------------------------------------
# Dense grouped-query attention over a joined key/value ring (the layout
# above: (batch, capacity, 2 x kv heads, d)), causal over a ring that grows
# or over the last ``window`` positions of a ring that WRAPS: position
# ``p`` lives in slot ``p mod capacity``, which for a ring that has not
# wrapped (a growing ring never does: ``cursor + T <= capacity``) is slot
# ``p``, so one rule of visibility serves both.  With ``last = cursor + T -
# 1`` the newest position written, slot ``c`` holds position ``base + c``
# where ``c <= last mod capacity`` and ``base + c - capacity`` elsewhere
# (``base = last - last mod capacity``, the position of slot 0 in the
# current lap; a negative position is a slot never written).  Query ``i``
# of the call stands at ``cursor + i`` and sees the positions ``p`` with
# ``0 <= p <= cursor + i`` and, under a window, ``p > cursor + i -
# window``.  A chunk is written before it is read, so a window ring holds
# ``window + chunk - 1`` slots or more (``window_ring_slots``): the oldest
# position the chunk's first query sees is not yet overwritten by its last.
# Two forms under ``gqa_ring_attention``, picked by ``gqa_attention_path``
# from the call's shapes alone: *streamed*, one Pallas kernel that brings
# the blocks holding a visible position through VMEM once (a TPU, the token
# step and prefill chunks), and *masked*, the rule as a mask over
# ``sparse_attention_masked`` (any dtype, any backend; the kernel's oracle).
# ---------------------------------------------------------------------------

#: query rows of a key/value head a grid step of the streamed form folds
#: (a chunk's positions are taken in tiles of this many rows over the
#: group's heads: each tile streams the ring once)
_GQA_QUERY_ROWS = 1024


def window_ring_slots(window: int, chunk: int) -> int:
    """Slots of a ring that serves a ``window`` under chunks of up to
    ``chunk`` positions written before they are read: ``window + chunk -
    1``, rounded up to whole blocks of the streamed form, the block the
    largest of which eight still span the ring (4,608 slots in blocks of
    512 for a window of 4,096 under chunks of 256: on a v5e a token
    step's grid step costs about 2.4 us whatever it folds, which 256
    slots, 1.3 us of bytes, do not hide; PERF.md, PR 41), and 128 at the
    least."""
    need = int(window) + max(int(chunk), 1) - 1
    block = next((b for b in _SPARSE_BLOCKS if 8 * b <= need),
                 _SPARSE_BLOCKS[-1])
    return -(-need // block) * block


def gqa_ring_update(kv_ring: Array, cursor, k_new: Array, v_new: Array, *,
                    wraps: bool = False) -> Array:
    """Write (batch, T, kv heads x d) keys and values into the ring
    (batch, capacity, 2 x kv heads, d) at positions ``cursor .. cursor + T
    - 1``.  ``wraps``: position ``p`` goes to slot ``p mod capacity`` (a
    chunk may straddle the end: its rows are scattered; a single position
    is one slice); otherwise callers guarantee ``cursor + T <=
    capacity``."""
    cap = kv_ring.shape[1]
    zero = jnp.zeros((), jnp.int32)
    cursor = jnp.asarray(cursor, jnp.int32)
    by_head = lambda a: a.reshape(a.shape[:2] + (-1, kv_ring.shape[3]))
    rows = jnp.concatenate([by_head(k_new), by_head(v_new)],
                           axis=2).astype(kv_ring.dtype)
    t = rows.shape[1]
    if wraps and t > 1:
        slots = (cursor + jnp.arange(t, dtype=jnp.int32)) % cap
        return kv_ring.at[:, slots].set(rows, unique_indices=True)
    start = cursor % cap if wraps else cursor
    return jax.lax.dynamic_update_slice(kv_ring, rows,
                                        (zero, start, zero, zero))


def _lap(cursor, t: int, capacity: int):
    """``(base, newest slot)`` of a ring whose newest position is
    ``cursor + t - 1``: slot ``c`` holds ``base + c`` up to the newest
    slot and ``base + c - capacity`` beyond it."""
    last = jnp.asarray(cursor, jnp.int32) + jnp.int32(t - 1)
    newest = last % jnp.int32(capacity)
    return last - newest, newest


def ring_visible(cursor, t: int, capacity: int,
                 window: Optional[int] = None) -> Array:
    """(T, capacity) bool: which slots of a ring written up to position
    ``cursor + T - 1`` query ``i`` (at ``cursor + i``) sees: the rule at
    the head of this section."""
    base, newest = _lap(cursor, t, capacity)
    slot = jnp.arange(capacity, dtype=jnp.int32)
    held = base + slot - jnp.where(slot > newest, jnp.int32(capacity), 0)
    at = (jnp.asarray(cursor, jnp.int32)
          + jnp.arange(t, dtype=jnp.int32))[:, None]
    keep = (held >= 0)[None, :] & (held[None, :] <= at)
    if window is not None:
        keep = keep & (held[None, :] > at - jnp.int32(window))
    return keep


def gqa_ring_attention_masked(q: Array, kv_ring: Array, cursor, *,
                              sm_scale: float,
                              window: Optional[int] = None) -> Array:
    """The plain form: (batch, T, heads, d) queries against every slot of
    the ring, the rule of visibility as a mask.  Any dtype, any backend;
    the scores are one (batch, heads, T, capacity) array."""
    keep = ring_visible(cursor, q.shape[1], kv_ring.shape[1], window)
    return sparse_attention_masked(
        q, kv_ring, jnp.broadcast_to(keep[None], (q.shape[0],) + keep.shape),
        sm_scale=sm_scale)


def _gqa_query_tile(t: int, group: int) -> int:
    """Positions a grid step of the streamed form takes: all of a token
    step's one, else the most whole sublane tiles of 8 that divide ``t``
    within ``_GQA_QUERY_ROWS`` rows of a key/value head."""
    if t == 1:
        return 1
    fits = [n for n in range(8, t + 1, 8)
            if t % n == 0 and n * group <= _GQA_QUERY_ROWS]
    return max(fits, default=8)


def gqa_attention_path(t: int, heads: int, kv_heads: int, d: int,
                       capacity: int, dtype) -> str:
    """``"streamed"`` or ``"masked"``: which form :func:`gqa_ring_attention`
    takes for ``t`` new positions a row against a ring of ``capacity``
    slots stored in ``dtype``.  Streamed where Mosaic compiles the kernel
    (a TPU), the storage is bfloat16 or float32, a head fills the 128
    lanes, the new positions are fewer than the ring's slots (``output()``
    from a zero ring is plain attention and stays masked; more than one
    are padded to whole sublane tiles of 8) and a block divides the
    capacity.  A window does not enter: the kernel serves both rules.
    Also what ``gqa_attention_steps_total{path}`` is labelled by."""
    streamed = (_mosaic()
                and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                         jnp.dtype(jnp.float32))
                and d % 128 == 0 and t < capacity
                and heads % kv_heads == 0
                and sparse_ring_block(capacity) > 0)
    return "streamed" if streamed else "masked"


def gqa_ring_attention_streamed(q: Array, kv_ring: Array, cursor, *,
                                sm_scale: float,
                                window: Optional[int] = None,
                                written: Optional[int] = None,
                                block: Optional[int] = None,
                                interpret: Optional[bool] = None) -> Array:
    """:func:`gqa_ring_attention_masked` as one Pallas kernel that reads
    each conversation's visible blocks once: grid (batch, query tiles,
    ring blocks).  A step takes one block of the ring, every slot's key
    and value heads (whole tiles: one contiguous read), works out which
    of its slots each query sees from the cursor alone (no mask is read)
    and folds it into the streaming softmax of every query head
    (``_fold_ring_block``, ``sparse_attention_streamed``'s).  The blocks
    are taken in the order of the positions they hold, from the one with
    the oldest position any query of the call sees (block 0 without a
    window; under a window the ring may have wrapped, and the walk wraps
    with it) to the one with the newest; the blocks before and beyond are
    neither fetched nor computed.  ``T`` is 1 or a multiple of 8;
    ``written`` (default ``T``) says how many of the queries' positions
    the ring holds, where the last queries are padding."""
    batch, t, heads, d = q.shape
    written = t if written is None else int(written)
    cap, kv_heads = kv_ring.shape[1], kv_ring.shape[2] // 2
    group = heads // kv_heads
    block = block or sparse_ring_block(cap)
    if not block or cap % block:
        raise ValueError(f"no block of the streamed attention divides a "
                         f"ring of {cap} slots (block {block})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dot = _kernel_dot(bool(interpret))
    num_blocks = cap // block
    scale, masked = np.float32(sm_scale), np.float32(_NEG_INF)
    tile = _gqa_query_tile(t, group)
    tiles = t // tile
    part = group if t == 1 else tile
    rows = group * tile
    words = not interpret and kv_ring.dtype == jnp.bfloat16

    cursor = jnp.asarray(cursor, jnp.int32)
    base, newest = _lap(cursor, written, cap)
    # the oldest position any query sees, the slots from there to the
    # newest one, and so the first block and how many the walk takes
    oldest = (jnp.zeros((), jnp.int32) if window is None
              else jnp.maximum(cursor - jnp.int32(window - 1), 0))
    first_slot = oldest % jnp.int32(cap)
    first = first_slot // jnp.int32(block)
    span = (first_slot - first * jnp.int32(block)
            + (cursor + written - oldest))
    walk = jnp.minimum((span - 1) // jnp.int32(block), num_blocks - 1)
    scalars = jnp.stack([cursor, base, newest, first, walk])

    def ring_block(k, s):
        """The ring block of grid step ``k``: the walk's, or its last
        one again (a repeat is not fetched)."""
        return (s[3] + jnp.minimum(k, s[4])) % jnp.int32(num_blocks)

    def kernel(s_ref, q_ref, kv_ref, o_ref, m_scr, l_scr, acc_scr):
        qi, ki = pl.program_id(1), pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr[:], masked)
            l_scr[:] = jnp.zeros_like(l_scr[:])
            acc_scr[:] = jnp.zeros_like(acc_scr[:])

        @pl.when(ki <= s_ref[4])
        def _fold():
            slot = (ring_block(ki, s_ref) * block
                    + jax.lax.broadcasted_iota(jnp.int32, (part, block), 1))
            held = s_ref[1] + slot - jnp.where(slot > s_ref[2],
                                               jnp.int32(cap), 0)
            at = s_ref[0] + qi * tile
            if t > 1:
                at = at + jax.lax.broadcasted_iota(jnp.int32,
                                                   (part, block), 0)
            keep = (held >= 0) & (held <= at)
            if window is not None:
                keep = keep & (held > at - jnp.int32(window))
            _fold_ring_block(dot, lambda at_: q_ref[(0, 0) + at_], kv_ref,
                             keep, m_scr, l_scr, acc_scr, kv_heads=kv_heads,
                             rows=rows, part=part, block=block, words=words,
                             scale=scale)

        @pl.when(ki == num_blocks - 1)
        def _finalize():
            o_ref[0, 0] = (acc_scr[:] / l_scr[:, :, :1]).astype(o_ref.dtype)

    whole = lambda b, i, k, s: (b, i, 0, 0, 0)
    # (batch, tiles, kv heads, group x tile, d): a head's rows, query head
    # major within a tile of positions
    qg = jnp.transpose(
        _grouped(q, kv_heads).reshape(batch, tiles, tile, kv_heads, group, d),
        (0, 1, 3, 4, 2, 5)).reshape(batch, tiles, kv_heads, rows, d)
    out = pl.pallas_call(
        kernel,
        out_shape=_sds(qg.shape, q.dtype, q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, tiles, num_blocks),
            in_specs=[
                pl.BlockSpec((1, 1, kv_heads, rows, d), whole),
                pl.BlockSpec((1, block * 2 * kv_heads, d),
                             lambda b, i, k, s: (b, ring_block(k, s), 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, kv_heads, rows, d), whole),
            scratch_shapes=[
                pltpu.VMEM((kv_heads, rows, 128), jnp.float32),  # max
                pltpu.VMEM((kv_heads, rows, 128), jnp.float32),  # denom
                pltpu.VMEM((kv_heads, rows, d), jnp.float32),    # sum
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_SPARSE_VMEM_LIMIT),
        interpret=interpret,
    )(scalars, qg, kv_ring.reshape(batch, cap * 2 * kv_heads, d))
    out = out.reshape(batch, tiles, kv_heads, group, tile, d)
    return jnp.transpose(out, (0, 1, 4, 2, 3, 5)).reshape(q.shape)


def gqa_ring_attention(q: Array, kv_ring: Array, cursor, *, sm_scale: float,
                       window: Optional[int] = None) -> Array:
    """Attention of (batch, T, heads, d) queries at positions ``cursor ..
    cursor + T - 1`` over a joined key/value ring that already holds
    them: causal, and within the last ``window`` positions (the query's
    own counted) where one is given; the context, (batch, T, heads, d).
    Two forms of one rule, chosen by :func:`gqa_attention_path` from the
    arguments alone."""
    batch, t, heads, d = q.shape
    path = gqa_attention_path(t, heads, kv_ring.shape[2] // 2, d,
                              kv_ring.shape[1], kv_ring.dtype)
    if path == "masked":
        return gqa_ring_attention_masked(q, kv_ring, cursor,
                                         sm_scale=sm_scale, window=window)
    if t > 1 and t % 8:
        # whole sublane tiles of positions: a chunk of another length (a
        # prompt's remainder) is padded with queries whose rows are cut
        # off again; the ring holds ``t`` positions, and the padded
        # queries stand beyond its newest and are nobody's
        q = jnp.pad(q, [(0, 0), (0, -t % 8), (0, 0), (0, 0)])
    return gqa_ring_attention_streamed(
        q, kv_ring, cursor, sm_scale=sm_scale, window=window,
        written=t)[:, :t]
