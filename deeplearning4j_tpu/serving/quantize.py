"""int8 inference weights: per-tensor affine quantization over the
uint8 wire/affine-decode machinery (``datasets.normalizers.WireFormat``).

The serving pager's economics are set by resident bytes per model: a
float32 weight matrix costs 4 bytes/element of HBM that inference-only
traffic never needs at full precision.  This module stores each large
floating leaf as **uint8 + a WireFormat decode spec** — the exact
affine-decode contract the ingest wire uses (PR 3): on device,

    f32 = float32(u8) / denom * mult + add

with ``denom=255``, ``mult=max-min``, ``add=min`` per tensor, i.e.
per-tensor affine quantization with a 1/510 of the tensor's range
worst-case rounding error.  Resident weight bytes drop ~4x vs float32
(~2x vs bf16 residency), so the ``ModelRegistry`` pager fits
correspondingly more models under the same HBM budget.

Policy (standard int8 post-training practice): only floating leaves of
rank >= 2 with at least ``min_size`` elements quantize — weight
matrices and conv kernels.  Biases, BN statistics, gains and other
small 1-D leaves stay float32; they are byte-noise and quantizing them
costs disproportionate accuracy.

The decode runs inside the compiled serving executable (XLA fuses it
into the consuming matmul/conv), so the wire format never escapes the
device program, mirroring the ingest-v2 fused decode.  Accuracy is
gated by test (int8 top-1 must match f32 within a stated tolerance on
the tier-1 eval) — see ``tests/test_serving_registry.py``.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from .. import monitor as _monitor
from ..datasets.normalizers import WireFormat

#: Leaves smaller than this stay float32 (biases, BN stats).
MIN_QUANT_SIZE = 64


def quantize_leaf(w: np.ndarray) -> Tuple[np.ndarray, WireFormat]:
    """Per-tensor affine quantization of one weight tensor to uint8.

    ``q = round((w - min) / scale)`` with ``scale = (max - min) / 255``;
    the returned :class:`WireFormat` decodes back with the wire's exact
    expression ``f32(u8) / 255 * (max - min) + min``.
    """
    w = np.asarray(w, np.float32)
    lo = float(w.min())
    hi = float(w.max())
    if not np.isfinite(lo) or not np.isfinite(hi):
        raise ValueError("cannot quantize a tensor with non-finite values")
    if hi <= lo:
        # constant tensor: any scale decodes exactly to `lo` + q*0
        hi = lo + 1.0
        q = np.zeros(w.shape, np.uint8)
    else:
        scale = (hi - lo) / 255.0
        q = np.clip(np.rint((w - lo) / scale), 0, 255).astype(np.uint8)
    return q, WireFormat(denom=255.0, mult=hi - lo, add=lo)


def _eligible(a: np.ndarray, min_size: int) -> bool:
    return (np.issubdtype(a.dtype, np.floating) and a.ndim >= 2
            and a.size >= min_size)


def quantize_tree(params, min_size: int = MIN_QUANT_SIZE):
    """Quantize every eligible leaf of a parameter pytree.

    Returns ``(qparams, specs)``: a tree with eligible leaves replaced
    by uint8 arrays, plus a flat tuple of per-leaf decode specs
    (``(denom, mult, add)`` or ``None`` for passthrough leaves) aligned
    with the tree's flatten order — the trace-time constants
    :func:`dequantize_tree` closes over.
    """
    import jax
    leaves, treedef = jax.tree.flatten(params)
    qleaves: List[np.ndarray] = []
    specs: List[Optional[Tuple[float, float, float]]] = []
    for leaf in leaves:
        a = np.asarray(leaf)
        if _eligible(a, min_size):
            q, wf = quantize_leaf(a)
            qleaves.append(q)
            specs.append(wf.as_tuple())
        else:
            qleaves.append(a)
            specs.append(None)
    return jax.tree.unflatten(treedef, qleaves), tuple(specs)


def dequantize_tree(qparams, specs):
    """Traceable on-device decode: uint8 leaves affine-decode to float32
    with the wire expression (op order and f32 rounding match the host
    twin ``WireFormat.decode_host``); passthrough leaves are untouched."""
    import jax
    import jax.numpy as jnp
    leaves, treedef = jax.tree.flatten(qparams)
    if len(leaves) != len(specs):
        raise ValueError(
            f"quantization specs cover {len(specs)} leaves, tree has "
            f"{len(leaves)}: params changed shape after quantize_tree")
    out = []
    for leaf, spec in zip(leaves, specs):
        if spec is None:
            out.append(leaf)
        else:
            denom, mult, add = spec
            out.append(leaf.astype(jnp.float32) / jnp.float32(denom)
                       * jnp.float32(mult) + jnp.float32(add))
    return jax.tree.unflatten(treedef, out)


def dequantize_host(qparams, specs):
    """Host (numpy) twin of :func:`dequantize_tree` — same expression,
    same f32 rounding; used by parity tests and accuracy gates."""
    import jax
    leaves, treedef = jax.tree.flatten(qparams)
    out = []
    for leaf, spec in zip(leaves, specs):
        if spec is None:
            out.append(np.asarray(leaf))
        else:
            denom, mult, add = spec
            out.append(WireFormat(denom, mult, add).decode_host(
                np.asarray(leaf)))
    return jax.tree.unflatten(treedef, out)


def tree_nbytes(tree) -> int:
    """Total bytes of every leaf in a pytree (host or device arrays),
    by the leaf's own ``nbytes``: a device array is counted where it
    lies, not fetched (``InferenceEngine`` asks this of the model's
    whole parameter tree when it is built)."""
    import jax
    return int(sum(l.nbytes if hasattr(l, "nbytes") else np.asarray(l).nbytes
                   for l in jax.tree.leaves(tree)))


def quantized_output_jit(model, specs, name: str):
    """A ``watched_jit`` forward that takes the *quantized* params tree,
    decodes it on device, and runs the model's own inference forward —
    same calling convention as the model's ``_output_fn`` (and therefore
    the same AOT ``lower().compile()`` path ``compile_output`` uses).
    """
    # __wrapped__ is the pure fn under the model's watched_jit, so the
    # decode + forward fuse into ONE program instead of two dispatches
    inner = model._output_fn.__wrapped__

    def run(qparams, net_state, features, features_mask):
        return inner(dequantize_tree(qparams, specs), net_state,
                     features, features_mask)

    return _monitor.watched_jit(run, name=name)


def quantized_decode_jit(model, specs, name: str):
    """A ``watched_jit`` decode step over the quantized params tree —
    the ``_decode_step_fn`` analogue of :func:`quantized_output_jit`.
    Same calling convention as the container's decode step
    (``(qparams, net_state, carries, features)``), so the int8 engine
    hands it to ``SessionCache`` via the ``step_fn`` override.  KV-ring
    state itself stays in the activation dtype: only weights quantize.
    """
    inner = model._decode_step_fn.__wrapped__

    def run(qparams, net_state, carries, features):
        return inner(dequantize_tree(qparams, specs), net_state,
                     carries, features)

    return _monitor.watched_jit(run, name=name)
