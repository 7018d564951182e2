"""The routed experts' three products, grouped: each (token, pick) pair
goes through the one expert it chose.

The dense form in ``nn/layers/decoder.py`` multiplies every token by
every held expert and weights the unchosen ones 0: three plain products,
bound by the experts' bytes while the tokens are few (the token step).
For a long chunk its operations no longer hide under those bytes, and
this module's form takes over: the pairs are sorted by expert, the rows
of ``x`` gathered in that order, and a Pallas grouped matrix product
multiplies each contiguous group of rows by its own expert's block of
the matrix (the algorithm of
``jax.experimental.pallas.ops.tpu.megablox.gmm``): row tiles, a tile's
expert looked up from scalar-prefetched group offsets, a tile that
straddles two groups visited once for each with the other's rows masked.
Group sizes are data: an expert may receive no row or all of them, and
no pair is dropped.

The matrices are read where they lie: ``Wg``/``Wu`` (hidden, held x
width) and ``Wd`` (held x width, hidden), expert after expert, so an
expert's block is a column block of the first two and a row block of the
third, picked by the kernel's block index; nothing weight-sized is
copied or transposed.

:func:`moe_experts_path` picks the form from the call's shapes alone.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _mosaic, _sds

Array = jax.Array

#: rows a tile of the grouped product.  Swept on a v5e at the decode
#: cell's widths (``tools/moe_experts_sweep.py``; PERF.md, PR 34): one
#: layer's 2,048 tokens take 4.80 / 4.83 / 4.85 ms at 64 / 128 / 256
#: rows a tile, 2.48 / 2.45 / 2.48 ms at 512 tokens: an expert's block
#: (7.3 MB) sets the pace, not the tile
_TILE_ROWS = 128
#: tokens from which the grouped form serves.  Same sweep, milliseconds
#: a layer, dense against grouped: 1.94 / 1.92 at 64 tokens, 1.98 / 2.05
#: at 128, 2.20 / 2.18 at 256 (both forms read the experts' 1.41 GB
#: once and little else matters), 4.01 / 2.45 at 512, 8.24 / 2.88 at
#: 1,024, 16.46 / 4.83 at 2,048 (the dense form's operations, one FLOP a
#: weight byte a token, leave the shade of the bytes near 240 tokens by
#: the peaks).  The two tie up to 256; 512 is the first count measured
#: at which the grouped form wins, and the token step's 64 rows keep the
#: program they had
_GROUPED_MIN_TOKENS = 512
#: what the kernel may hold of a v5e core's 128 MiB of VMEM, and what
#: its column tile is chosen to stay under by the reckoning below
_GROUPED_VMEM_LIMIT = 64 << 20
_GROUPED_VMEM_BUDGET = 40 << 20


def _grouped_vmem_bytes(tm: int, k: int, tn: int, itemsize: int) -> int:
    """VMEM of one grid step, reckoned high: the row tile and the
    expert's block double-buffered, and four float32 (tm, tn) tiles: the
    output's two buffers and the product on its way to the masked
    store."""
    return 2 * (tm * k + k * tn) * itemsize + 4 * tm * tn * 4


def grouped_tile_columns(k: int, n: int, itemsize: int,
                         tm: int = _TILE_ROWS) -> int:
    """Columns a grid step of the grouped product for an expert block of
    (``k``, ``n``): all of them, or the largest halving that is a
    multiple of 128 lanes and fits the kernel's VMEM; 0 where none does
    (the contraction is not tiled)."""
    tn = n
    while tn % 128 == 0:
        if _grouped_vmem_bytes(tm, k, tn, itemsize) <= _GROUPED_VMEM_BUDGET:
            return tn
        tn //= 2
    return 0


def moe_experts_path(tokens: int, held: int, top_k: int, hidden: int,
                     width: int, dtype, train: bool) -> str:
    """``"grouped"`` or ``"dense"``: which form the routed experts'
    products take for ``tokens`` tokens through ``held`` held experts of
    (``hidden``, ``width``) with ``top_k`` picks a token.  Grouped where
    Mosaic compiles the kernel (a TPU), the storage is bfloat16 or
    float32 (Mosaic has no float64), the call is not training (the
    kernel has no VJP), a token leaves experts unchosen, the tokens are
    many enough that the dense form's operations no longer hide under
    the experts' bytes, and an expert's block tiles: both sizes whole
    lanes, the block within the kernel's VMEM.  Also what
    ``moe_experts_steps_total{path}`` is labelled by."""
    itemsize = jnp.dtype(dtype).itemsize
    grouped = (_mosaic() and not train
               and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                        jnp.dtype(jnp.float32))
               and top_k < held and tokens >= _GROUPED_MIN_TOKENS
               and hidden % 128 == 0 and width % 128 == 0
               and grouped_tile_columns(hidden, width, itemsize) > 0
               and grouped_tile_columns(width, hidden, itemsize) > 0)
    return "grouped" if grouped else "dense"


def group_tiles(sizes: Array, rows: int, tm: int):
    """What the kernel prefetches for groups of ``sizes`` (groups,) rows
    lying one after another in ``rows`` rows (a multiple of ``tm``):
    ``(group, tile, live, offsets)``.  A visit is one (row tile, group)
    pair whose rows meet; there are at most ``rows // tm + groups - 1``
    and ``live`` (1,) of them are real, in row order; ``group`` and
    ``tile`` (visits,) name each and repeat the last real one beyond
    ``live``, so that a spare grid step fetches nothing.  ``offsets``
    (groups + 1,) is where each group starts."""
    sizes = sizes.astype(jnp.int32)
    groups = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    live = visit_ends[-1]
    visit = jnp.minimum(jnp.arange(rows // tm + groups - 1, dtype=jnp.int32),
                        jnp.maximum(live - 1, 0))
    group = jnp.minimum(
        jnp.sum(visit_ends[None, :] <= visit[:, None], axis=1,
                dtype=jnp.int32), groups - 1)
    tile = first[group] + visit - (visit_ends[group] - tiles[group])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return group, tile, live[None], offsets


def _make_grouped_kernel(tm: int, widen: bool):
    def kernel(group_ref, tile_ref, live_ref, offsets_ref, lhs_ref, rhs_ref,
               out_ref):
        visit = pl.program_id(1)

        @pl.when(visit < live_ref[0])
        def _product():
            lhs, rhs = lhs_ref[...], rhs_ref[...]
            if widen:       # XLA:CPU under the interpreter: _einsum_acc
                lhs, rhs = lhs.astype(jnp.float32), rhs.astype(jnp.float32)
            acc = jax.lax.dot_general(lhs, rhs, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            group = group_ref[visit]
            row = tile_ref[visit] * tm + jax.lax.broadcasted_iota(
                jnp.int32, acc.shape, 0)
            mine = ((row >= offsets_ref[group])
                    & (row < offsets_ref[group + 1]))
            # rows of the tile's other groups keep what those visits
            # wrote (or will write: the tile stays in VMEM between them)
            out_ref[...] = jnp.where(mine, acc, out_ref[...])

    return kernel


def grouped_matmul(lhs: Array, rhs: Array, tiles, *, groups_along: int,
                   tm: int = _TILE_ROWS,
                   interpret: Optional[bool] = None) -> Array:
    """``lhs[offsets[g]:offsets[g + 1]] @ rhs_g`` for every group ``g``:
    ``lhs`` (rows, k) holds the groups' rows one after another (``tiles``
    from :func:`group_tiles` of the same ``rows`` and ``tm``); ``rhs``
    holds the groups' matrices side by side, (k, groups x n) with
    ``groups_along=1`` or one under another, (groups x k, n), with
    ``groups_along=0``.  Float32 accumulation and (rows, n) float32
    out; rows beyond the last group are left unwritten.
    Grid (column tiles, visits): a row tile's visits are consecutive, so
    its output block stays in VMEM from the first to the last of them,
    and an expert's block is fetched once a column tile."""
    rows, k = lhs.shape
    group, tile, live, offsets = tiles
    groups = offsets.shape[0] - 1
    n = rhs.shape[1] // groups if groups_along else rhs.shape[1]
    if rows % tm or rhs.shape != ((k, groups * n) if groups_along
                                  else (groups * k, n)):
        raise ValueError(f"grouped_matmul: lhs {lhs.shape}, rhs {rhs.shape} "
                         f"for {groups} groups along {groups_along}, "
                         f"tiles of {tm} rows")
    if interpret is None:
        # the backend itself, not _mosaic(): a test that steers the
        # predicate still runs the kernel interpreted
        interpret = jax.default_backend() != "tpu"
    tn = n if interpret else grouped_tile_columns(
        k, n, jnp.dtype(lhs.dtype).itemsize, tm)
    if not tn:
        raise ValueError(f"no column tile of a ({k}, {n}) expert block "
                         "fits the grouped product's VMEM")
    across = n // tn
    if groups_along:
        rhs_block = lambda j, v, group, tile, live, offsets: (
            0, group[v] * across + j)
    else:
        rhs_block = lambda j, v, group, tile, live, offsets: (group[v], j)
    return pl.pallas_call(
        _make_grouped_kernel(tm, widen=bool(interpret)),
        out_shape=_sds((rows, n), jnp.float32, lhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(across, group.shape[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, group, tile, live,
                             offsets: (tile[v], 0)),
                pl.BlockSpec((k, tn), rhs_block),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, group, tile, live,
                                   offsets: (tile[v], j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_GROUPED_VMEM_LIMIT),
        interpret=interpret,
    )(group, tile, live, offsets, lhs, rhs)


def grouped_experts(x: Array, idx: Array, w: Array, wg: Array, wu: Array,
                    wd: Array, *, held: Sequence[int], n_experts: int,
                    tm: int = _TILE_ROWS,
                    interpret: Optional[bool] = None) -> Array:
    """``sum_k w[t, k] * E_idx[t, k](x[t])`` over the picks whose expert
    is in ``held``, with ``E(x) = (silu(x Wg) * x Wu) Wd``: ``x``
    (tokens, hidden), ``idx``/``w`` (tokens, top_k) from the router, the
    matrices as :class:`MixtureOfExperts` stores them (the blocks in the
    order of ``held``).  Pairs are sorted by their expert's place in
    ``held`` (pairs of other experts last: another chip's part, computed
    by no tile); activations and the pick's weight are applied in
    float32 to the (pairs, width) rows; a token's results are summed in
    float32 and rounded once."""
    tokens, top_k = idx.shape
    pairs, n_held = tokens * top_k, len(held)
    rows = -(-pairs // tm) * tm
    place = np.full((n_experts,), n_held, np.int32)
    place[np.asarray(held, np.int64)] = np.arange(n_held, dtype=np.int32)
    slot = jnp.asarray(place)[idx]                       # (tokens, top_k)
    flat = jnp.pad(slot.reshape(-1), (0, rows - pairs),
                   constant_values=n_held)
    order = jnp.argsort(flat, stable=True)               # pair of each row
    sizes = jnp.sum(flat[:, None] == jnp.arange(n_held)[None, :], axis=0,
                    dtype=jnp.int32)
    tiles = group_tiles(sizes, rows, tm)
    xs = jnp.take(x, jnp.minimum(order // top_k, tokens - 1), axis=0)
    ws = jnp.take(jnp.pad(w.reshape(-1), (0, rows - pairs)), order)
    product = lambda lhs, rhs, along: grouped_matmul(
        lhs, rhs, tiles, groups_along=along, tm=tm, interpret=interpret)
    a = (jax.nn.silu(product(xs, wg, 1)) * product(xs, wu, 1)
         * ws[:, None].astype(jnp.float32)).astype(x.dtype)
    out = product(a, wd, 0)                              # (rows, hidden)
    row = jnp.argsort(order)[:pairs].reshape(tokens, top_k)
    picked = jnp.where((slot < n_held)[:, :, None],
                       jnp.take(out, row, axis=0), 0.0)
    return jnp.sum(picked, axis=1).astype(x.dtype)
