"""Sequence/context parallelism: ring attention, all-to-all (Ulysses)
attention, and a sequence-parallel LSTM scan.

The 2016-era reference's only long-sequence mechanism is truncated BPTT
(``MultiLayerNetwork.doTruncatedBPTT:1138``) on a single device; sequences
beyond one device's memory are out of its reach.  This module is the
TPU-native long-context tier the reference lacks: the time axis is sharded
over a mesh axis (``"seq"``), activations never materialize full-length on
any one chip, and the cross-device traffic is XLA collectives riding ICI.

Three primitives, all designed to run inside ``jax.shard_map`` over a mesh
with a ``seq`` axis (helpers that set up the shard_map are provided):

- :func:`ring_attention` — blockwise-softmax attention with the K/V blocks
  rotated around the ring via ``lax.ppermute`` (one hop per step, n_shards
  steps).  Communication overlaps compute; the softmax uses the streaming
  log-sum-exp accumulation so no (T, T) score matrix ever exists.  Peak
  memory per chip is O(T/n · T/n) scores + O(T/n) activations.
- :func:`ring_flash_attention` — the same ring, but each step's local
  block runs the Pallas flash kernel (``ops/attention.py``), removing
  the remaining O(T/n · T/n) score block: per-chip memory is O(T/n · d)
  — linear in sequence length across AND within chips.
- :func:`ulysses_attention` — the all-to-all alternative: two
  ``lax.all_to_all`` collectives swap the sharded axis from time to heads,
  each chip then attends over the FULL sequence for its head subset.  Best
  when heads % n_shards == 0 and ICI all-to-all bandwidth beats n ring hops.
- :func:`ring_lstm_scan` — sequence-parallel tBPTT for the recurrent
  family: the input projection (the big MXU matmul) and all elementwise
  work run sharded; the inherently-serial (H,4H) recurrent chain walks the
  ring, carries handed device-to-device via ``ppermute``.  Wall-clock of
  the recurrent chain stays serial (an RNN is a data dependence chain) but
  per-chip activation memory drops n_shards-fold — which is what bounds
  tBPTT window length in practice.

All primitives are differentiable (``ppermute``/``all_to_all`` have exact
transposes) so they compose with ``jax.value_and_grad`` train steps.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array

_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/where() NaN-free


def _ring_perm(n: int):
    """Cyclic +1 permutation: device i hands its block to device i+1."""
    return [(i, (i + 1) % n) for i in range(n)]


# --------------------------------------------------------------------- ring
def ring_attention(q: Array, k: Array, v: Array, *, axis_name: str,
                   causal: bool = False, sm_scale: Optional[float] = None
                   ) -> Array:
    """Blockwise ring attention over a sharded time axis.

    Args:
      q, k, v: this chip's time shard, shape (batch, t_local, heads, d_head).
        Shards are laid out in ring order: the chip at ``axis_index == j``
        holds global timesteps ``[j*t_local, (j+1)*t_local)``.
      axis_name: the mesh axis the sequence is sharded over.
      causal: mask attention to positions > the query's global position.
      sm_scale: softmax scale; default ``1/sqrt(d_head)``.

    Returns (batch, t_local, heads, d_head) — the attention output for this
    chip's queries, exactly equal (up to float assoc.) to full attention on
    the gathered sequence.

    Accumulation is float32 regardless of input dtype (bf16-safe).
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    t_local = q.shape[1]
    d = q.shape[-1]
    scale = float(sm_scale) if sm_scale is not None else 1.0 / float(np.sqrt(d))

    qf = q.astype(jnp.float32) * scale
    q_pos = my * t_local + jnp.arange(t_local)                 # global q idx

    o0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full(q.shape[:3], _NEG_INF, jnp.float32)          # running max
    l0 = jnp.zeros(q.shape[:3], jnp.float32)                   # running denom

    def body(carry, r):
        o, m, l, k_blk, v_blk = carry
        # After r rotations the resident block originated on chip (my - r).
        src = (my - r) % n
        s = jnp.einsum("bqhd,bkhd->bqhk", qf, k_blk.astype(jnp.float32))
        if causal:
            k_pos = src * t_local + jnp.arange(k_blk.shape[1])
            s = jnp.where(q_pos[None, :, None, None]
                          >= k_pos[None, None, None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # exp(_NEG_INF - _NEG_INF) would be 1; gate fully-masked rows to 0.
        alive = m_new > _NEG_INF / 2
        p = jnp.where(alive[..., None], jnp.exp(s - m_new[..., None]), 0.0)
        correction = jnp.where(alive, jnp.exp(m - m_new), 0.0)
        o = o * correction[..., None] \
            + jnp.einsum("bqhk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
        l = l * correction + jnp.sum(p, axis=-1)
        k_blk, v_blk = lax.ppermute((k_blk, v_blk), axis_name,
                                    _ring_perm(n))
        return (o, m_new, l, k_blk, v_blk), None

    # Fresh accumulators are replication-tracked as unvarying; the body
    # mixes in device-varying q/k/v, so the carry must enter varying.
    o0, m0, l0 = lax.pcast((o0, m0, l0), axis_name, to="varying")
    (o, _, l, _, _), _ = lax.scan(body, (o0, m0, l0, k, v),
                                  jnp.arange(n))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _full_attention(q: Array, k: Array, v: Array, *, causal: bool = False,
                    sm_scale: Optional[float] = None) -> Array:
    """Single-device reference attention (the correctness oracle for the
    sharded paths; also the n_shards==1 fast path)."""
    d = q.shape[-1]
    scale = float(sm_scale) if sm_scale is not None else 1.0 / float(np.sqrt(d))
    s = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        s = jnp.where(jnp.arange(tq)[None, :, None, None]
                      >= jnp.arange(tk)[None, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqhk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# --------------------------------------------------------------- ring+flash
def ring_flash_attention(q: Array, k: Array, v: Array, *, axis_name: str,
                         causal: bool = False,
                         sm_scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         interpret: Optional[bool] = None,
                         precision=None) -> Array:
    """Ring attention whose per-step LOCAL block runs the Pallas flash
    kernel — linear memory in sequence length both ACROSS chips (KV
    shards rotate, nothing gathers) and WITHIN each chip (score tiles
    live in VMEM, never materialized to HBM).  The einsum-based
    :func:`ring_attention` materializes a (batch, T/n, heads, T/n) score
    block per step; this variant removes that last quadratic term, so
    per-chip memory is O(T/n · d).

    Causality per ring step has exactly three cases — resident block from
    a PAST chip (fully visible), from THIS chip (locally causal: global
    offsets coincide), or from a FUTURE chip (fully masked, skipped) —
    so the kernel never needs global position plumbing.

    Differentiable: the custom VJP is a FUSED ring backward — the q-side
    package (q, dO, logsumexp, D, dq-accumulator) travels the ring and
    every chip folds its local kv shard's exact contribution through the
    Pallas backward kernels, so gradient memory is also O(T/n · d).

    ``interpret``/``precision`` thread through to the kernel —
    pass ``interpret=True`` when the mesh devices aren't the default
    backend (e.g. a CPU mesh on a TPU-attached host).
    """
    from ..ops.attention import _auto_block
    t_local = q.shape[1]          # per-shard T inside shard_map
    if block_q is None:
        block_q = _auto_block(t_local)
    if block_k is None:
        block_k = _auto_block(t_local)
    return _ring_flash_core(q, k, v, axis_name, causal, sm_scale,
                            block_q, block_k, interpret, precision)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _ring_flash_core(q, k, v, axis_name, causal, sm_scale, block_q,
                     block_k, interpret, precision):
    out, _ = _ring_flash_forward(q, k, v, axis_name, causal, sm_scale,
                                 block_q, block_k, interpret, precision)
    return out


def _ring_flash_forward(q, k, v, axis_name, causal, sm_scale, block_q,
                        block_k, interpret, precision):
    from ..ops.attention import flash_attention_partial

    n = lax.axis_size(axis_name)
    # axis_index lowers to partition-id; only materialize it when the
    # causal schedule needs it, so the non-causal program stays free of
    # it (older XLA SPMD partitioners reject stray partition-id ops).
    my = lax.axis_index(axis_name) if causal else None
    kwargs = dict(sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                  interpret=interpret, precision=precision)

    def merge(o1, m1, l1, o2, m2, l2):
        """Exact log-sum-exp combination of two unnormalized partials."""
        m = jnp.maximum(m1, m2)
        a1 = jnp.where(m1 > _NEG_INF / 2, jnp.exp(m1 - m), 0.0)
        a2 = jnp.where(m2 > _NEG_INF / 2, jnp.exp(m2 - m), 0.0)
        return (o1 * a1[..., None] + o2 * a2[..., None],
                m, l1 * a1 + l2 * a2)

    def body(carry, r):
        o, m, l, k_blk, v_blk = carry
        src = (my - r) % n if causal else None

        def visible(_):
            return flash_attention_partial(q, k_blk, v_blk, causal=False,
                                           **kwargs)

        def diagonal(_):
            return flash_attention_partial(q, k_blk, v_blk, causal=True,
                                           **kwargs)

        def masked(_):
            # fresh constants are replication-tracked as unvarying; the
            # kernel branches are varying — align the types for switch
            return lax.pcast(
                (jnp.zeros(q.shape, jnp.float32),
                 jnp.full(q.shape[:3], _NEG_INF, jnp.float32),
                 jnp.zeros(q.shape[:3], jnp.float32)),
                axis_name, to="varying")

        if causal:
            case = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
            po, pm, pl_ = lax.switch(case, [visible, diagonal, masked],
                                     operand=None)
        else:
            po, pm, pl_ = visible(None)
        o, m, l = merge(o, m, l, po, pm, pl_)
        k_blk, v_blk = lax.ppermute((k_blk, v_blk), axis_name,
                                    _ring_perm(n))
        return (o, m, l, k_blk, v_blk), None

    o0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full(q.shape[:3], _NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:3], jnp.float32)
    o0, m0, l0 = lax.pcast((o0, m0, l0), axis_name, to="varying")
    (o, m, l, _, _), _ = lax.scan(body, (o0, m0, l0, k, v),
                                  jnp.arange(n))
    l_safe = jnp.maximum(l, 1e-30)
    out = (o / l_safe[..., None]).astype(q.dtype)
    return out, m + jnp.log(l_safe)          # (out, per-row logsumexp)


def _ring_flash_fwd(q, k, v, axis_name, causal, sm_scale, block_q,
                    block_k, interpret, precision):
    out, L = _ring_flash_forward(q, k, v, axis_name, causal, sm_scale,
                                 block_q, block_k, interpret, precision)
    return out, (q, k, v, out, L)


def _ring_flash_bwd(axis_name, causal, sm_scale, block_q, block_k,
                    interpret, precision, res, g):
    """FUSED ring backward: the q-side package (q, dO, L, D, dq-accum)
    travels the ring; every chip folds its LOCAL kv shard's exact
    gradient contribution via the fused flash backward kernels, so
    backward memory stays O(T/n · d) per chip like the forward.

    Causality mirrors the forward's three cases from the kv side: a
    package from a LATER chip sees this kv shard fully (its q positions
    are all past it), the home package is locally causal, and a package
    from an EARLIER chip contributes nothing."""
    from ..ops.attention import flash_attention_bwd

    q, k, v, out, L = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    scale = (float(sm_scale) if sm_scale is not None
             else 1.0 / float(np.sqrt(q.shape[-1])))
    kwargs = dict(sm_scale=scale, block_q=block_q, block_k=block_k,
                  interpret=interpret, precision=precision)
    D_row = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)

    def contribution(local_causal):
        def fn(pkg):
            q_r, do_r, L_r, D_r = pkg
            # contributions come back f32 and accumulate in f32; the
            # single cast to input dtype happens at the VJP boundary
            return flash_attention_bwd(
                q_r, k, v, None, L_r, do_r, causal=local_causal,
                D_row=D_r, **kwargs)
        return fn

    def masked(pkg):
        # align vma with the kernel branches (fresh zeros are unvarying)
        return lax.pcast(
            (jnp.zeros(q.shape, jnp.float32),
             jnp.zeros(k.shape, jnp.float32),
             jnp.zeros(v.shape, jnp.float32)),
            axis_name, to="varying")

    def body(carry, r):
        (q_r, do_r, L_r, D_r, dq_r), dk_acc, dv_acc = carry
        src = (my - r) % n                   # package origin
        pkg = (q_r, do_r, L_r, D_r)
        if causal:
            # src > my: visitor's q positions all AFTER this kv -> full
            case = jnp.where(src == my, 1, jnp.where(src > my, 0, 2))
            dq_c, dk_c, dv_c = lax.switch(
                case, [contribution(False), contribution(True), masked],
                pkg)
        else:
            dq_c, dk_c, dv_c = contribution(False)(pkg)
        dk_acc = dk_acc + dk_c
        dv_acc = dv_acc + dv_c
        moved = lax.ppermute((q_r, do_r, L_r, D_r, dq_r + dq_c),
                             axis_name, _ring_perm(n))
        return (moved, dk_acc, dv_acc), None

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq0, dk0, dv0 = lax.pcast((dq0, dk0, dv0), axis_name, to="varying")
    carry0 = ((q, g, L, D_row, dq0), dk0, dv0)
    ((_, _, _, _, dq), dk, dv), _ = lax.scan(body, carry0, jnp.arange(n))
    # after n rotations the package (with its accumulated dq) is home
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_ring_flash_core.defvjp(_ring_flash_fwd, _ring_flash_bwd)


# ------------------------------------------------------------------ ulysses
def ulysses_attention(q: Array, k: Array, v: Array, *, axis_name: str,
                      causal: bool = False,
                      sm_scale: Optional[float] = None) -> Array:
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism.

    Input layout matches :func:`ring_attention` (time sharded, heads full).
    Two ``lax.all_to_all`` collectives re-shard from time-sharded to
    head-sharded, full attention runs per head subset over the WHOLE
    sequence, and the output is swapped back.  Requires
    ``heads % axis_size == 0``.
    """
    n = lax.axis_size(axis_name)
    h = q.shape[2]
    if h % n != 0:
        raise ValueError(f"heads={h} not divisible by seq shards={n}")

    def to_headshard(x):
        # (b, t_local, h, d) -> (b, n*t_local, h/n, d): gather time,
        # scatter heads.  tiled=True concatenates the gathered axis.
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_timeshard(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = to_headshard(q), to_headshard(k), to_headshard(v)
    out = _full_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return to_timeshard(out)


# --------------------------------------------------------- sequence-par LSTM
def ring_lstm_scan(W: Array, RW: Array, b: Array, x: Array,
                   carry: Tuple[Array, Array],
                   mask: Optional[Array] = None, *, afn, gate_fn,
                   axis_name: str) -> Tuple[Array, Tuple[Array, Array]]:
    """Sequence-parallel peephole-LSTM scan (the sharded twin of
    ``nn/layers/recurrent.lstm_scan``).

    ``x`` is this chip's (batch, t_local, n_in) time shard, ring order as in
    :func:`ring_attention`; ``carry`` is the (h, c) entering the FULL
    sequence (meaningful on chip 0, ignored elsewhere).  Returns this
    chip's (batch, t_local, H) outputs and the global final (h, c)
    (broadcast to every chip).

    The input projection runs ONCE per chip over its shard (one big MXU
    matmul over t_local instead of T timesteps — hoisted outside the round
    loop) and the per-round recurrent chain is ``jax.checkpoint``-ed, so
    under ``jax.grad`` each chip stores only its (b, t_local, 4H)
    projection plus one round's rematerialized residuals — O(T/n) per chip,
    the n-fold activation-memory reduction that lets tBPTT windows grow
    with the mesh.  The chain itself is walked in ring order, each chip
    scanning its shard from the carry ``ppermute``-d in from its left
    neighbor.  Every chip scans once per round and results are committed
    only on the owning round — SPMD lockstep with no data-dependent
    control flow, so the whole thing jits into one XLA program and
    differentiates cleanly.
    """
    from ..nn.layers.recurrent import lstm_scan_preact

    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)

    # Loop-invariant: project this chip's shard once, not once per round.
    xw = jnp.einsum("bti,ij->btj", x, W) + b
    inner = jax.checkpoint(functools.partial(
        lstm_scan_preact, afn=afn, gate_fn=gate_fn))

    def round_body(state, r):
        ring_carry, ys_acc = state
        out, fin = inner(RW, xw, ring_carry, mask=mask)
        mine = (my == r)
        ys_acc = jnp.where(mine, out, ys_acc)
        # Hand my final carry rightward; chip r+1 receives the only valid
        # one (chip r's) for the next round.  Chips that already ran keep
        # feeding garbage around the ring, but nothing downstream reads
        # it: commits are gated on `mine`.
        new_ring = lax.ppermute(fin, axis_name, _ring_perm(n))
        return (new_ring, ys_acc), None

    res_dtype = jnp.result_type(xw.dtype, RW.dtype)
    ys0 = jnp.zeros(x.shape[:2] + (RW.shape[0],), res_dtype)
    # The scan carry's dtype must be loop-invariant; mixed-precision inputs
    # (bf16 x, f32 weights) would otherwise promote it after round one.
    carry = jax.tree.map(lambda a: a.astype(res_dtype), carry)
    carry, ys0 = lax.pcast((carry, ys0), axis_name, to="varying")
    (ring_carry, ys), _ = lax.scan(round_body, (carry, ys0), jnp.arange(n))
    # After the last round chip (n-1)'s final — the global final — was
    # ppermuted onto chip 0; broadcast it everywhere.
    def bcast(leaf):
        return lax.psum(jnp.where(my == 0, leaf, jnp.zeros_like(leaf)),
                        axis_name)
    final_carry = jax.tree.map(bcast, ring_carry)
    return ys, final_carry


# ----------------------------------------------------------------- wrappers
class SequenceParallel:
    """Mesh-owning convenience wrapper: shards (batch, T, ...) arrays over a
    ``seq`` axis and runs the sharded primitives, so callers outside
    shard_map get gather-free long-context attention with a one-call API.

    The mesh may be 1-D ``("seq",)`` (pure context parallelism) or the
    caller can pass any mesh containing a ``seq`` axis.
    """

    def __init__(self, devices=None, mesh: Optional[Mesh] = None,
                 axis_name: str = "seq"):
        if mesh is None:
            devices = devices if devices is not None else jax.devices()
            mesh = Mesh(np.array(devices).reshape(len(devices)),
                        (axis_name,))
        self.mesh = mesh
        self.axis = axis_name
        self.n = mesh.shape[axis_name]

    def _sharded(self, fn, n_args: int):
        spec = P(None, self.axis)
        return jax.jit(jax.shard_map(
            fn, mesh=self.mesh, in_specs=(spec,) * n_args,
            out_specs=spec))

    @functools.cached_property
    def _ring(self):
        return {
            causal: self._sharded(
                functools.partial(ring_attention, axis_name=self.axis,
                                  causal=causal), 3)
            for causal in (False, True)}

    @functools.cached_property
    def _ulysses(self):
        return {
            causal: self._sharded(
                functools.partial(ulysses_attention, axis_name=self.axis,
                                  causal=causal), 3)
            for causal in (False, True)}

    @functools.cached_property
    def _ring_flash(self):
        # derive interpret from the MESH devices, not the default backend:
        # a CPU mesh on a TPU-attached host must not lower Mosaic for CPU
        interpret = all(d.platform != "tpu" for d in self.mesh.devices.flat)
        return {
            causal: self._sharded(
                functools.partial(ring_flash_attention,
                                  axis_name=self.axis, causal=causal,
                                  interpret=interpret), 3)
            for causal in (False, True)}

    def attention(self, q: Array, k: Array, v: Array, *,
                  causal: bool = False, impl: str = "ring") -> Array:
        """Full-shape (batch, T, heads, d) in and out; T % n_shards == 0.

        ``impl``: ``"ring"`` / ``"ulysses"`` shard the sequence over the
        mesh; ``"flash"`` runs the single-device Pallas flash kernel
        (``ops/attention.py``) — linear memory in T, no mesh required."""
        if impl == "flash":
            from ..ops.attention import flash_attention
            return flash_attention(q, k, v, causal=causal)
        if impl not in ("ring", "ulysses", "ring_flash"):
            raise ValueError(f"unknown impl {impl!r}; use 'ring', "
                             f"'ulysses', 'ring_flash', or 'flash'")
        if q.shape[1] % self.n:
            raise ValueError(
                f"sequence length {q.shape[1]} not divisible by "
                f"{self.n} seq shards")
        table = {"ring": self._ring, "ulysses": self._ulysses,
                 "ring_flash": self._ring_flash}[impl]
        return table[causal](q, k, v)
