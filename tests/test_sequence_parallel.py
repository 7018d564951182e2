"""Sequence/context parallelism tests on the 8-device virtual CPU mesh:
ring attention and Ulysses all-to-all attention vs the single-device
oracle, grads through the ring, and the sequence-parallel LSTM scan vs
``nn/layers/recurrent.lstm_scan``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.nn import activations as _act
from deeplearning4j_tpu.nn.layers.recurrent import lstm_scan
from deeplearning4j_tpu.parallel.sequence import (
    SequenceParallel, _full_attention, ring_attention, ring_lstm_scan,
    ulysses_attention)


def _qkv(b=2, t=32, h=8, d=16, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, t, h, d).astype(dtype))
                 for _ in range(3))


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), ("seq",))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sharded_attention_matches_full(causal, impl):
    q, k, v = _qkv()
    sp = SequenceParallel(devices=jax.devices()[:8])
    out = sp.attention(q, k, v, causal=causal, impl=impl)
    ref = _full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_odd_shard_counts():
    """Ring correctness must not depend on power-of-two shard counts."""
    q, k, v = _qkv(t=30)
    mesh = _mesh(3)
    fn = jax.jit(jax.shard_map(
        functools.partial(ring_attention, axis_name="seq", causal=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq")))
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v)),
        np.asarray(_full_attention(q, k, v, causal=True)),
        rtol=2e-5, atol=2e-5)


def test_ring_attention_gradients_match_full():
    """d(sum(attn))/d{q,k,v} through the ring (ppermute transposes) equals
    the single-device grads — the property that lets ring attention sit
    inside a jitted train step."""
    q, k, v = _qkv(t=16, h=4, d=8)
    mesh = _mesh(4)
    spec = (P(None, "seq"),) * 3

    ring = jax.shard_map(
        functools.partial(ring_attention, axis_name="seq", causal=True),
        mesh=mesh, in_specs=spec, out_specs=P(None, "seq"))

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(_full_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ulysses_requires_divisible_heads():
    q, k, v = _qkv(h=6)  # 6 heads, 8 shards
    sp = SequenceParallel(devices=jax.devices()[:8])
    with pytest.raises(ValueError):
        sp.attention(q, k, v, impl="ulysses")


def test_bf16_inputs_accumulate_f32():
    q, k, v = _qkv(dtype=np.float32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    sp = SequenceParallel(devices=jax.devices()[:8])
    out = sp.attention(qb, kb, vb, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = _full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.1, atol=0.1)


def test_ring_lstm_scan_matches_serial():
    """Sequence-parallel LSTM over 4 shards reproduces the serial
    lstm_scan outputs and final carry."""
    rng = np.random.RandomState(1)
    b, t, n_in, H = 3, 24, 5, 7
    W = jnp.asarray(rng.randn(n_in, 4 * H).astype(np.float64) * 0.3)
    RW = jnp.asarray(rng.randn(H, 4 * H + 3).astype(np.float64) * 0.3)
    bias = jnp.asarray(rng.randn(4 * H).astype(np.float64) * 0.1)
    x = jnp.asarray(rng.randn(b, t, n_in))
    carry = (jnp.asarray(rng.randn(b, H)), jnp.asarray(rng.randn(b, H)))
    afn, gate = _act.get("tanh"), _act.get("sigmoid")

    ref_out, ref_final = lstm_scan(W, RW, bias, x, carry, afn=afn,
                                   gate_fn=gate)

    mesh = _mesh(4)
    fn = jax.jit(jax.shard_map(
        functools.partial(ring_lstm_scan, afn=afn, gate_fn=gate,
                          axis_name="seq"),
        mesh=mesh,
        in_specs=(P(), P(), P(), P(None, "seq"), P()),
        out_specs=(P(None, "seq"), P())))
    out, final = fn(W, RW, bias, x, carry)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-9, atol=1e-9)
    for a, r in zip(final, ref_final):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-9, atol=1e-9)


def test_ring_lstm_scan_mixed_precision():
    """bf16 activations with f32 weights (the TPU compute-dtype pattern)
    must not trip the round-scan's carry dtype."""
    rng = np.random.RandomState(4)
    b, t, n_in, H = 2, 16, 4, 6
    W = jnp.asarray(rng.randn(n_in, 4 * H).astype(np.float32) * 0.3)
    RW = jnp.asarray(rng.randn(H, 4 * H + 3).astype(np.float32) * 0.3)
    bias = jnp.zeros(4 * H, jnp.float32)
    x = jnp.asarray(rng.randn(b, t, n_in)).astype(jnp.bfloat16)
    carry = (jnp.zeros((b, H), jnp.bfloat16), jnp.zeros((b, H), jnp.bfloat16))
    afn, gate = _act.get("tanh"), _act.get("sigmoid")

    mesh = _mesh(4)
    fn = jax.jit(jax.shard_map(
        functools.partial(ring_lstm_scan, afn=afn, gate_fn=gate,
                          axis_name="seq"),
        mesh=mesh,
        in_specs=(P(), P(), P(), P(None, "seq"), P()),
        out_specs=(P(None, "seq"), P())))
    out, _ = fn(W, RW, bias, x, carry)
    ref_out, _ = lstm_scan(W, RW, bias, x, carry, afn=afn, gate_fn=gate)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_attention_unknown_impl_raises():
    q, k, v = _qkv()
    sp = SequenceParallel(devices=jax.devices()[:8])
    with pytest.raises(ValueError, match="unknown impl"):
        sp.attention(q, k, v, impl="rings")


def test_ring_lstm_scan_masked():
    """Per-timestep masks thread through the sharded scan (masked steps
    hold state, emit zeros) identically to the serial path."""
    rng = np.random.RandomState(2)
    b, t, n_in, H = 2, 16, 4, 6
    W = jnp.asarray(rng.randn(n_in, 4 * H) * 0.3)
    RW = jnp.asarray(rng.randn(H, 4 * H + 3) * 0.3)
    bias = jnp.zeros(4 * H)
    x = jnp.asarray(rng.randn(b, t, n_in))
    mask = jnp.asarray((rng.rand(b, t) > 0.3).astype(np.float64))
    carry = (jnp.zeros((b, H)), jnp.zeros((b, H)))
    afn, gate = _act.get("tanh"), _act.get("sigmoid")

    ref_out, ref_final = lstm_scan(W, RW, bias, x, carry, afn=afn,
                                   gate_fn=gate, mask=mask)
    mesh = _mesh(4)
    fn = jax.jit(jax.shard_map(
        functools.partial(ring_lstm_scan, afn=afn, gate_fn=gate,
                          axis_name="seq"),
        mesh=mesh,
        in_specs=(P(), P(), P(), P(None, "seq"), P(), P(None, "seq")),
        out_specs=(P(None, "seq"), P())))
    out, final = fn(W, RW, bias, x, carry, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-9, atol=1e-9)
    for a, r in zip(final, ref_final):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-9, atol=1e-9)


def test_ring_lstm_grads_match_serial():
    """Backprop through the sequence-parallel scan (tBPTT over shards)."""
    rng = np.random.RandomState(3)
    b, t, n_in, H = 2, 8, 3, 4
    W = jnp.asarray(rng.randn(n_in, 4 * H) * 0.3)
    RW = jnp.asarray(rng.randn(H, 4 * H + 3) * 0.3)
    bias = jnp.zeros(4 * H)
    x = jnp.asarray(rng.randn(b, t, n_in))
    carry = (jnp.zeros((b, H)), jnp.zeros((b, H)))
    afn, gate = _act.get("tanh"), _act.get("sigmoid")

    mesh = _mesh(4)
    sp_scan = jax.shard_map(
        functools.partial(ring_lstm_scan, afn=afn, gate_fn=gate,
                          axis_name="seq"),
        mesh=mesh,
        in_specs=(P(), P(), P(), P(None, "seq"), P()),
        out_specs=(P(None, "seq"), P()))

    def loss_sp(W, RW, bias):
        out, _ = sp_scan(W, RW, bias, x, carry)
        return jnp.sum(out ** 2)

    def loss_ref(W, RW, bias):
        out, _ = lstm_scan(W, RW, bias, x, carry, afn=afn, gate_fn=gate)
        return jnp.sum(out ** 2)

    g_sp = jax.jit(jax.grad(loss_sp, argnums=(0, 1, 2)))(W, RW, bias)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(W, RW, bias)
    for a, r in zip(g_sp, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-8, atol=1e-8)
