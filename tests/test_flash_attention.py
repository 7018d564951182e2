"""Pallas flash-attention kernel tests (interpret mode on the CPU mesh;
the compiled Mosaic path is checked on the chip by ``chip_smoke.py``'s
``kernels`` phase)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.attention import flash_attention
from deeplearning4j_tpu.parallel.sequence import (SequenceParallel,
                                                  _full_attention)


def _qkv(b=1, t=64, h=2, d=16, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, t, h, d).astype(dtype))
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_oracle(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = _full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_ragged_length_and_uneven_blocks():
    """T not a multiple of the block size exercises the padding mask."""
    q, k, v = _qkv(t=50)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = _full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_non_lane_width_head_dim():
    """d not a multiple of 128 exercises the lane padding."""
    q, k, v = _qkv(t=32, d=24)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    ref = _full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16_inputs():
    q, k, v = _qkv(t=32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    ref = _full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.1, atol=0.1)


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_oracle(fused, causal):
    """Both backward paths — the fused two-pass Pallas kernels and the
    XLA-recompute fallback — must match the oracle."""
    q, k, v = _qkv(t=32, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=16,
                                       block_k=16,
                                       fused_backward=fused) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_full_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fused_backward_ragged_and_uneven_blocks(causal):
    """T not divisible by blocks + mismatched block sizes exercise the
    backward kernels' padding masks and lcm padding — in BOTH causal and
    non-causal modes (the k_pos/q_pos padding terms differ)."""
    q, k, v = _qkv(t=50, d=16)
    gf = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=causal, block_q=16,
                        block_k=32) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        _full_attention(q, k, v, causal=causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_mismatched_block_sizes():
    """block_q/block_k that don't divide each other exercise the lcm
    padding (a max-based pad silently drops trailing blocks)."""
    q, k, v = _qkv(t=128, d=16)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=48)
    ref = _full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_non_tile_aligned_t_defaults():
    """T=100 with default 128 blocks: the clamp must round the block to a
    sublane multiple, not to T itself."""
    q, k, v = _qkv(t=100, d=16)
    out = flash_attention(q, k, v, causal=True)
    ref = _full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_clamp_block_rounds_to_eight_rows_for_every_dtype():
    """The clamp bounds a block by the sequence and rounds it UP to 8
    rows; it does not look at the dtype.  A 16-row rule for bf16 was
    expected to be needed and is not: Mosaic on the v5e (libtpu 0.0.34)
    compiles (1, 8, 128) and (1, 40, 128) bf16 blocks, whole-sequence or
    not, and computes them right — ``chip_smoke.py``'s kernels phase
    checks T=40 and T=8 in bf16 on every chip run."""
    from deeplearning4j_tpu.ops.attention import _auto_block, _clamp_block
    assert _clamp_block(_auto_block(40), 40) == 40
    assert _clamp_block(_auto_block(8), 8) == 8
    assert _clamp_block(_auto_block(5), 5) == 8       # never below a tile
    assert _clamp_block(_auto_block(100), 100) == 104
    assert _clamp_block(24, 96) == 24                  # explicit, kept


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 3e-2),
                                       (jnp.float32, 1e-4)])
@pytest.mark.parametrize("t", [40, 8, 5])
def test_flash_odd_short_t_forward_and_gradient(t, dtype, tol):
    """Short odd T with default blocks, in both input widths: one block
    covers the (padded) sequence, padded rows and keys are masked in the
    forward and in both backward kernels."""
    q, k, v = (x.astype(dtype) for x in _qkv(t=t, d=16, seed=t))

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=True).astype(jnp.float32) ** 2)

    lf, gf = jax.value_and_grad(loss(flash_attention),
                                argnums=(0, 1, 2))(q, k, v)
    lr, gr = jax.value_and_grad(loss(_full_attention), argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    np.testing.assert_allclose(float(lf), float(lr), rtol=tol)
    for a, b in zip(gf, gr):
        assert a.dtype == dtype
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), rtol=0, atol=tol * scale)


def test_flash_rejects_bad_shapes():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="shapes differ"):
        flash_attention(q, k[:, :32], v)
    with pytest.raises(ValueError, match="batch, T, heads, d"):
        flash_attention(q[0], k[0], v[0])


def test_sequence_parallel_flash_impl():
    q, k, v = _qkv(t=48)
    sp = SequenceParallel(devices=jax.devices()[:8])
    out = sp.attention(q, k, v, causal=True, impl="flash")
    ref = _full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_partial_merges_to_full():
    """Partials over two KV halves merged via log-sum-exp equal full
    attention — the invariant ring_flash_attention is built on."""
    from deeplearning4j_tpu.ops.attention import flash_attention_partial
    q, k, v = _qkv(t=32, d=16)
    half = 16
    o1, m1, l1 = flash_attention_partial(q, k[:, :half], v[:, :half],
                                         block_q=16, block_k=16)
    o2, m2, l2 = flash_attention_partial(q, k[:, half:], v[:, half:],
                                         block_q=16, block_k=16)
    m = np.maximum(np.asarray(m1), np.asarray(m2))
    a1 = np.exp(np.asarray(m1) - m)
    a2 = np.exp(np.asarray(m2) - m)
    o = np.asarray(o1) * a1[..., None] + np.asarray(o2) * a2[..., None]
    l = np.asarray(l1) * a1 + np.asarray(l2) * a2
    ref = _full_attention(q, k, v)
    np.testing.assert_allclose(o / l[..., None], np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_attention_matches_full(causal):
    import functools
    from jax.sharding import Mesh, PartitionSpec as P
    from deeplearning4j_tpu.parallel.sequence import ring_flash_attention
    q, k, v = _qkv(t=32, h=2, d=16)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("seq",))
    fn = jax.jit(jax.shard_map(
        functools.partial(ring_flash_attention, axis_name="seq",
                          causal=causal, block_q=8, block_k=8),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq")))
    out = fn(q, k, v)
    ref = _full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_gradients_match_full(causal):
    """The FUSED ring backward (q-package rotation folding per-chip
    Pallas contributions) must match the single-device oracle."""
    import functools
    from jax.sharding import Mesh, PartitionSpec as P
    from deeplearning4j_tpu.parallel.sequence import ring_flash_attention
    q, k, v = _qkv(t=16, h=2, d=8)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("seq",))
    rf = jax.shard_map(
        functools.partial(ring_flash_attention, axis_name="seq",
                          causal=causal, block_q=8, block_k=8),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"))
    gf = jax.jit(jax.grad(lambda q, k, v: jnp.sum(rf(q, k, v) ** 2),
                          argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        _full_attention(q, k, v, causal=causal) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_bwd_segment_contributions_sum():
    """flash_attention_bwd over two KV segments with the GLOBAL L/D sums
    to the full backward — the invariant the ring backward relies on."""
    from deeplearning4j_tpu.ops.attention import (flash_attention_bwd,
                                                  flash_attention_partial)
    q, k, v = _qkv(t=32, d=16)
    rng = np.random.RandomState(9)
    g = jnp.asarray(rng.randn(*q.shape).astype(np.float32))
    acc, m, l = flash_attention_partial(q, k, v, block_q=16, block_k=16)
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    L = m + jnp.log(l_safe)
    D = jnp.sum(g * out, axis=-1)
    full = flash_attention_bwd(q, k, v, None, L, g, causal=False,
                               sm_scale=1.0 / 4.0, block_q=16, block_k=16,
                               D_row=D)
    half = 16
    seg0 = flash_attention_bwd(q, k[:, :half], v[:, :half], None, L, g,
                               causal=False, sm_scale=1.0 / 4.0,
                               block_q=16, block_k=16, D_row=D)
    seg1 = flash_attention_bwd(q, k[:, half:], v[:, half:], None, L, g,
                               causal=False, sm_scale=1.0 / 4.0,
                               block_q=16, block_k=16, D_row=D)
    np.testing.assert_allclose(np.asarray(seg0[0]) + np.asarray(seg1[0]),
                               np.asarray(full[0]), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(seg0[1]), np.asarray(seg1[1])], axis=1),
        np.asarray(full[1]), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(seg0[2]), np.asarray(seg1[2])], axis=1),
        np.asarray(full[2]), rtol=2e-5, atol=2e-5)


def test_sequence_parallel_ring_flash_impl():
    q, k, v = _qkv(t=64)
    sp = SequenceParallel(devices=jax.devices()[:8])
    out = sp.attention(q, k, v, causal=True, impl="ring_flash")
    ref = _full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
