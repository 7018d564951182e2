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
no pair is dropped.  Under a share (the layer holds some of the experts
its router picks from) most pairs name an expert that lies on another
chip: the held pairs alone are laid in rows, a number of rows reckoned
from the shapes (:func:`grouped_rows`), and a step whose held pairs
outgrow them takes further rounds of as many.  A share's token step
sends few pairs to its chip (8 of 64 at 8 tokens of 8 picks, 16 of 128
experts held), and most held experts get none: the grouped form then
lays them in one tile of whole sublane tiles of 16 rows and fetches the
blocks of the held experts its picks name alone, where the dense form
reads every held expert.

The matrices are read where they lie: ``Wg``/``Wu`` (hidden, held x
width) and ``Wd`` (held x width, hidden), expert after expert, so an
expert's block is a column block of the first two and a row block of the
third, picked by the kernel's block index; nothing weight-sized is
copied or transposed.

:func:`moe_experts_path` picks the form from the call's shapes alone.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _einsum_acc, _mosaic, _sds

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
#: rows of a bfloat16 sublane tile: rows few enough to be one tile of the
#: grouped product are rounded to whole such tiles, not to ``_TILE_ROWS``
#: (a token step's 16 rows at 128 would multiply 8 times the rows, as
#: long at the peak as an expert's bytes at the command_a cell's widths)
_SUBLANE_ROWS = 16
#: a share's token step whose picks leave held experts without one: the
#: dense form reads every held expert, the grouped form the blocks of the
#: picked ones (:func:`grouped_step_wins`).  Swept on a v5e,
#: ``tools/moe_experts_sweep.py --step``, milliseconds a layer, dense /
#: grouped, by :func:`picked_share`: 16 of 128 held of (4,096, 4,096),
#: 2.149 / 0.970 at 0.403, 2.152 / 1.779 at 0.873, 2.157 / 1.926 at
#: 0.924, 2.162 / 2.179 at 0.955; of (2,048, 768), 0.214 / 0.119 at
#: 0.403, 0.212 / 0.230 at 0.644, 0.225 / 0.296 at 0.984; 8 of 256 of
#: (5,120, 1,536), 0.521 / 0.296 at 0.638, 0.524 / 0.443 at 0.782, 0.535
#: / 0.501 at 0.953, 0.533 / 0.568 at 0.983.  The crossing is 0.92-0.96,
#: under 0.64 and 0.95-0.98: no one share serves every width.  Read
#: whole, the grouped form takes 1.03, 1.31 and 1.07 times the dense
#: one: a grid step (a column tile of one expert's block) costs about
#: 1.3 us beside its bytes, which weighs on blocks of 3.1 MB and not on
#: blocks of 16 MB; laying the rows, the group tables and the sum of a
#: token's rows cost about 40 us a layer (the profiled pieces in PERF.md
#: section 5); XLA's product reads an expert matrix at about 750 GB/s
#: (467-481 us for 352 MB).  So reckoned, the crossing at 16 rows is
#: 0.927, 0.612 and 0.867 at those three widths (never the grouped form
#: where the sweep found it the slower), and 0.911 at (7,168, 2,048), where A.X-K1's 64 tokens (0.934)
#: stay dense
_GROUPED_STEP_S = 1.3e-6
_GROUPED_FIXED_S = 40e-6
_EXPERT_BYTES_PER_S = 750e9
#: rows at which the dense form's operations show beside the experts'
#: bytes.  Under a share (fewer experts held than the router is wide) it
#: is laid over the tokens that picked a held expert alone from as many
#: tokens, while those fit in as many rows.  Swept on a v5e
#: at one chip of sixteen's widths (hidden 7,168, 12 of 192 experts of
#: 2,048 held, 8 picks; ``tools/moe_experts_sweep.py --held 12``;
#: PERF.md, PR 36), milliseconds a layer, dense / over the held tokens'
#: rows (their number) / grouped: 1.51 / 1.49 (80) / 1.61 at 128 tokens
#: (all three at the experts' 1.06 GB), 1.61 / 1.55 (144) / 1.67 at
#: 256, 2.95 / 1.68 (256) / 1.80 at 512, 4.37 / 2.33 (384) / 1.97 at
#: 768, 11.82 / 5.59 (928) / 3.60 at 2,048.  XLA's product over the
#: (7,168, 24,576) matrix alone takes 479 / 481 / 487 / 522 / 611 /
#: 717 us at 128 / 144 / 208 / 256 / 320 / 384 rows (467 for a plain
#: pass over its bytes): its operations show from 256 rows, which is
#: where the dense form over all tokens stops being the best and the
#: form over the held rows stops in its turn; the grouped product's
#: kernel reads the same matrix in 500-510 us whatever its tiles
#: (475-490 with the product left out), so it wins only where rows are
#: many
_DENSE_TURN_ROWS = 256
#: deviations of an even router's count of such tokens kept as room
#: before rounding up to whole tiles: one chip of sixteen's 256 tokens
#: send 104.8 with a deviation of 7.9 and get 144 rows, five deviations,
#: which three steps in ten million outgrow (a second round, the same
#: sum)
_HELD_ROWS_SIGMAS = 4
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


def held_token_rows(tokens: int, top_k: int, held: int,
                    n_experts: int) -> int:
    """Rows for the tokens that picked one of ``held`` of ``n_experts``
    experts with their ``top_k`` picks, from these shapes alone: what an
    even router sends (a token misses every held expert with
    probability ``C(n - held, k) / C(n, k)``) and
    ``_HELD_ROWS_SIGMAS`` deviations of room, in whole sublane tiles of
    16, never more than the tokens."""
    miss = (math.comb(n_experts - held, top_k)
            / math.comb(n_experts, top_k))
    rows = math.ceil(tokens * (1 - miss) + _HELD_ROWS_SIGMAS
                     * math.sqrt(tokens * miss * (1 - miss)))
    return -(-min(rows, tokens) // _SUBLANE_ROWS) * _SUBLANE_ROWS


def picked_share(tokens: int, top_k: int, n_experts: int) -> float:
    """The share of a router's ``n_experts`` experts that an even router
    gives at least one of ``tokens`` tokens' ``top_k`` picks: each token
    picks a given expert with probability ``top_k / n_experts``."""
    return 1.0 - (1.0 - top_k / n_experts) ** tokens


def grouped_step_wins(tokens: int, top_k: int, held: int, n_experts: int,
                      hidden: int, width: int, itemsize: int,
                      tile: int) -> bool:
    """Whether the grouped form, reading the blocks of the held experts
    an even router gives a pick (:func:`picked_share`) in column tiles
    for ``tile`` rows, a grid step's cost each, and laying its rows
    besides, takes less time than the dense form reading every held
    expert: reckoned from the shapes and the sweep above.  It holds for
    8 tokens of 8 picks over 16 of 128 experts at both widths of those
    shares (0.403 picked) and for 32 over 8 of 256 (0.638); not for
    A.X-K1's 64 tokens over 12 of 192 (0.934)."""
    expert = 3 * hidden * width * itemsize
    steps = (2 * width // grouped_tile_columns(hidden, width, itemsize, tile)
             + hidden // grouped_tile_columns(width, hidden, itemsize, tile))
    dense = held * expert / _EXPERT_BYTES_PER_S
    grouped = (picked_share(tokens, top_k, n_experts) * held
               * (expert / _EXPERT_BYTES_PER_S + steps * _GROUPED_STEP_S)
               + _GROUPED_FIXED_S)
    return grouped < dense


def moe_experts_path(tokens: int, held: int, n_experts: int, top_k: int,
                     hidden: int, width: int, dtype, train: bool) -> str:
    """``"dense"``, ``"held_rows"`` or ``"grouped"``: which form the
    routed experts' products take for ``tokens`` tokens through ``held``
    held experts of (``hidden``, ``width``) with ``top_k`` picks a token
    of a router ``n_experts`` wide.  Dense off a TPU (where the other
    forms were measured), for float64 (Mosaic has none) and in training
    (the kernel has no VJP).  Grouped under a share (``held <
    n_experts``) wherever an even router leaves enough held experts
    without a pick that the blocks of the picked ones alone cost less
    than every held expert's (:func:`grouped_step_wins`).  ``tokens`` are those the layer is handed:
    a token step of a few conversations whose picks reach this share
    alone (a lightly loaded pool, a decode tier at low concurrency).  A
    chip of a loaded pool is handed the pool's picks for its experts,
    several an expert, and stays dense.
    Otherwise dense where a token may choose every held expert; under a
    share, from the tokens at which the dense form's operations no
    longer hide under the experts' bytes, the dense form laid over the
    tokens that picked a held expert alone (:func:`held_rows_experts`)
    while those rows are few enough that its operations hide again; and
    grouped from the tokens at which that form wins.  Grouped only where
    an expert's block tiles: both sizes whole lanes, the block within
    the kernel's VMEM.  Also what ``moe_experts_steps_total{path}`` is
    labelled by."""
    itemsize = jnp.dtype(dtype).itemsize
    if not (_mosaic() and not train
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))):
        return "dense"
    _, tile = grouped_rows(tokens, top_k, held, n_experts)
    tiles = (hidden % 128 == 0 and width % 128 == 0
             and grouped_tile_columns(hidden, width, itemsize, tile) > 0
             and grouped_tile_columns(width, hidden, itemsize, tile) > 0)
    if tiles and held < n_experts and grouped_step_wins(
            tokens, top_k, held, n_experts, hidden, width, itemsize, tile):
        return "grouped"
    if top_k >= held:
        return "dense"
    if held < n_experts and tokens >= _DENSE_TURN_ROWS and held_token_rows(
            tokens, top_k, held, n_experts) <= min(tokens - 1,
                                                   _DENSE_TURN_ROWS):
        return "held_rows"
    return "grouped" if tiles and tokens >= _GROUPED_MIN_TOKENS else "dense"


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
                   fetched: float, tm: int = _TILE_ROWS,
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
    and an expert's block is fetched once a column tile; a group with no
    row is not fetched.  The kernel declares its bytes
    (``pl.CostEstimate``): ``fetched`` groups' blocks (the caller's
    expectation of the groups that receive a row: the trace takes it for
    what a call read), its rows once a column tile and its output."""
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
    itemsize = jnp.dtype(lhs.dtype).itemsize
    cost = pl.CostEstimate(
        flops=int(2 * tm * k * n * max(rows // tm + fetched - 1, 1)),
        transcendentals=0,
        bytes_accessed=int(fetched * k * n * jnp.dtype(rhs.dtype).itemsize
                           + across * rows * k * itemsize + rows * n * 4))
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
        cost_estimate=cost,
        interpret=interpret,
    )(group, tile, live, offsets, lhs, rhs)


def grouped_rows(tokens: int, top_k: int, held: int, n_experts: int,
                 tm: int = _TILE_ROWS) -> Tuple[int, int]:
    """``(rows, tile)`` of the grouped form for ``tokens`` tokens of
    ``top_k`` picks through ``held`` of ``n_experts`` experts: the rows
    the pairs are laid in, a multiple of the tile, and the rows a tile.
    Every pair has a row where every expert is held.  Under a share the
    rows are a bound from these shapes alone: twice the held pairs an
    even router sends (``tokens x top_k x held / n_experts``), never
    more than the pairs; a step whose held pairs exceed it takes further
    rounds of as many rows.  Rows few enough (up to two tiles of ``tm``)
    are one tile of whole sublane tiles of 16, so that no group straddles
    two and a token step multiplies no more rows than it lays; more are
    tiles of ``tm``."""
    bound = tokens * top_k
    if held < n_experts:
        bound = min(bound, -(-2 * bound * held // n_experts))
    rows = -(-bound // _SUBLANE_ROWS) * _SUBLANE_ROWS
    if rows <= 2 * tm:
        return rows, rows
    return -(-bound // tm) * tm, tm


def held_pair_rows(slot: Array, n_held: int):
    """``(at, starts, ends)`` for ``slot`` (pairs,), each pair's place in
    the held experts or ``n_held`` for an expert that lies elsewhere:
    ``at`` (pairs,) is a held pair's row among all held pairs laid
    expert after expert, in pair order within one (its rank by a running
    count over the (held, pairs) one-hot; no sort), -1 for a pair of
    another chip's expert; ``starts``/``ends`` (held,) bound each
    expert's rows."""
    mine = slot[None, :] == jnp.arange(n_held, dtype=jnp.int32)[:, None]
    count = jnp.cumsum(mine, axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(count[:, -1])
    starts = ends - count[:, -1]
    at = jnp.sum(jnp.where(mine, count - 1 + starts[:, None], 0), axis=0)
    return jnp.where(slot < n_held, at, -1), starts, ends


def pairs_of_rows(at: Array, base, rows: int) -> Array:
    """The pair that lies in each of the ``rows`` rows from ``base`` on
    by ``at`` of :func:`held_pair_rows`: (rows,), 0 for a row beyond the
    last held pair.  A comparison of every row with every pair: a gather
    or a scatter of as many would cost more."""
    row = base + jnp.arange(rows, dtype=jnp.int32)
    pair = jnp.arange(at.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where(at[None, :] == row[:, None], pair[None, :], 0),
                   axis=1)


def sum_rows(pick: Array, rows: Array) -> Array:
    """``pick`` (n, rows) of 0 and 1 times ``rows`` (rows, hidden)
    float32, to float32's own rounding: ``rows`` is split into three
    bfloat16 terms that sum to it exactly, each product is exact on the
    matrix unit and accumulated in float32 (a float32 product at the
    highest precision takes six passes for the same numbers)."""
    pick, out, left = pick.astype(jnp.bfloat16), 0.0, rows
    for _ in range(3):
        term = left.astype(jnp.bfloat16)
        left = left - term.astype(jnp.float32)
        out = out + _einsum_acc("nr,rh->nh", pick, term, jnp.float32)
    return out


def dense_experts(x: Array, combine: Array, wg: Array, wu: Array,
                  wd: Array) -> Array:
    """Every token of ``x`` (tokens, hidden) through every held expert,
    three plain products, the unchosen experts weighted 0 by ``combine``
    (tokens, held).  A weight is spread over its expert's columns by a
    product with a 0/1 matrix, exact: the broadcast it stands for is laid
    out per expert, and the TPU's compiler writes it out and copies it
    into the products' layout (19 us a layer at one chip of sixteen's
    144 rows; PERF.md, PR 36)."""
    held = combine.shape[1]
    width = wg.shape[1] // held
    spread = (jnp.arange(held * width, dtype=jnp.int32)[None, :] // width
              == jnp.arange(held, dtype=jnp.int32)[:, None])
    a = (jax.nn.silu(x @ wg) * (x @ wu)
         * jnp.dot(combine.astype(x.dtype), spread.astype(x.dtype),
                   precision=jax.lax.Precision.HIGHEST))
    return a @ wd


def held_rows_experts(x: Array, picked: Array, combine: Array, wg: Array,
                      wu: Array, wd: Array, *, rows: int):
    """:func:`dense_experts` over the tokens that picked a held expert
    (``picked`` (tokens,) bool) alone, gathered into ``rows`` rows,
    whether that spilled (int32) and how many held experts' blocks it
    read (int32: all of them a round).  Under a share most tokens pick
    none, and the dense form's operations, which at a few hundred tokens no
    longer hide under the experts' bytes, shrink with its rows.  The
    other tokens' result is 0; a step with more such tokens than
    ``rows`` takes further rounds of as many: no token is left out."""
    at = jnp.where(picked, jnp.cumsum(picked, dtype=jnp.int32) - 1, -1)
    total = jnp.sum(picked, dtype=jnp.int32)

    def round_from(base):
        order = pairs_of_rows(at, base, rows)           # token of each row
        out = dense_experts(jnp.take(x, order, axis=0),
                            jnp.take(combine, order, axis=0), wg, wu, wd)
        _, back = _way_back(order, base, total, x.shape[0])
        # a token has one row in all rounds together: 0/1 times a
        # rounded result, summed with zeros, is that result
        return _einsum_acc("tr,rh->th", back.astype(x.dtype), out,
                           jnp.float32).astype(x.dtype)

    y, rounds = _rounds(round_from, rows, total)
    return y, (rounds > 1).astype(jnp.int32), rounds * combine.shape[1]


def _way_back(token_of_row: Array, base, total: Array, tokens: int):
    """``(live, back)`` for the rows from ``base`` on: which of them lie
    below ``total`` (rows,), and ``back`` (tokens, rows), 1 where a live
    row is its token's."""
    rows = token_of_row.shape[0]
    live = base + jnp.arange(rows, dtype=jnp.int32) < total
    token = jnp.arange(tokens, dtype=jnp.int32)
    return live, (token[:, None] == token_of_row[None, :]) & live[None, :]


def _rounds(round_from, rows: int, total: Array):
    """``round_from(base)`` summed over ``base`` = 0, ``rows``, ... below
    ``total``, and how many rounds that took (int32, at least 1).  The
    first round is not in the loop: it is the only one unless a router
    is skewed, and so starts from no array of zeros."""
    _, y = jax.lax.while_loop(
        lambda carry: carry[0] < total,
        lambda carry: (carry[0] + rows, carry[1] + round_from(carry[0])),
        (jnp.full((), rows, jnp.int32), round_from(0)))
    return y, jnp.maximum(-(-total // rows), 1)


def grouped_experts(x: Array, idx: Array, w: Array, wg: Array, wu: Array,
                    wd: Array, *, held: Sequence[int], n_experts: int,
                    tm: int = _TILE_ROWS,
                    interpret: Optional[bool] = None):
    """``sum_k w[t, k] * E_idx[t, k](x[t])`` over the picks whose expert
    is in ``held``, with ``E(x) = (silu(x Wg) * x Wu) Wd``, whether
    that spilled (int32: 1 where the held pairs exceeded the rows of
    :func:`grouped_rows`, a skewed router) and how many held experts'
    blocks the products fetched (int32: the groups that received a row,
    a round each whose rows they meet; an expert with no pick is not
    fetched): ``x`` (tokens, hidden),
    ``idx``/``w`` (tokens, top_k) from the router, the matrices as
    :class:`MixtureOfExperts` stores them (the blocks in the order of
    ``held``).  The pairs are laid in rows by their expert's place in
    ``held``; activations and the pick's weight are applied in float32
    to the (rows, width) products; a token's results are summed in
    float32 and rounded once.

    Where the rows are the pairs' own (every expert held, or a share too
    large to bound), the pairs are sorted (those of other experts last:
    computed by no tile), and a token's rows gathered and summed.  Where
    they are fewer, most pairs name an expert that lies on another chip,
    and nothing pair-sized is sorted, gathered or written: the held
    pairs' rows come from :func:`held_pair_rows` and
    :func:`pairs_of_rows`, and a token's rows are summed by a 0/1
    product.  No pair is dropped: held pairs beyond the rows of one round
    take the next, the same arithmetic on the next rows, so a skewed
    step costs the rounds it fills and rounds its numbers as any other
    (the dense form under a condition would round a spilled step's
    products another way, and hold a chunk's (tokens, held x width)
    products beside the grouped form's)."""
    tokens, top_k = idx.shape
    pairs, n_held = tokens * top_k, len(held)
    rows, tile = grouped_rows(tokens, top_k, n_held, n_experts, tm)
    # each pair's place in ``held``, ``n_held`` for an expert elsewhere:
    # compared, not looked up (a gather of the pairs costs more: 17 us
    # for 2,048 of them on a v5e)
    slot = jnp.min(jnp.where(
        idx[:, :, None] == jnp.asarray(held, jnp.int32),
        jnp.arange(n_held, dtype=jnp.int32), n_held), axis=-1)

    def experts_of(order, sizes):
        """The (rows, hidden) float32 results of the pairs ``order``
        (rows,) lying in groups of ``sizes`` (held,) rows."""
        tiles = group_tiles(sizes, rows, tile)
        product = lambda lhs, rhs, along: grouped_matmul(
            lhs, rhs, tiles, groups_along=along, tm=tile,
            fetched=n_held * picked_share(tokens, top_k, n_experts),
            interpret=interpret)
        xs = jnp.take(x, jnp.minimum(order // top_k, tokens - 1), axis=0)
        ws = jnp.take(jnp.pad(w.reshape(-1), (0, max(rows - pairs, 0))),
                      order)
        a = (jax.nn.silu(product(xs, wg, 1)) * product(xs, wu, 1)
             * ws[:, None].astype(jnp.float32)).astype(x.dtype)
        return product(a, wd, 0)

    if rows >= pairs:
        flat = jnp.pad(slot.reshape(-1), (0, rows - pairs),
                       constant_values=n_held)
        order = jnp.argsort(flat, stable=True)           # pair of each row
        sizes = jnp.sum(flat[:, None] == jnp.arange(n_held)[None, :],
                        axis=0, dtype=jnp.int32)
        out = experts_of(order, sizes)
        row = jnp.argsort(order)[:pairs].reshape(tokens, top_k)
        picked = jnp.where((slot < n_held)[:, :, None],
                           jnp.take(out, row, axis=0), 0.0)
        return (jnp.sum(picked, axis=1).astype(x.dtype),
                jnp.zeros((), jnp.int32),
                jnp.sum(sizes > 0, dtype=jnp.int32))

    at, starts, ends = held_pair_rows(slot.reshape(-1), n_held)
    total = ends[-1]

    def round_from(base):
        order = pairs_of_rows(at, base, rows)
        out = experts_of(order, jnp.clip(ends - base, 0, rows)
                         - jnp.clip(starts - base, 0, rows))
        live, back = _way_back(order // top_k, base, total, tokens)
        # the kernel leaves the rows beyond the last pair unwritten
        return sum_rows(back, jnp.where(live[:, None], out, 0.0))

    y, rounds = _rounds(round_from, rows, total)
    # an expert's rows lie in each round from its first row's to its last's
    read = jnp.sum(jnp.where(ends > starts, (ends - 1) // rows
                             - starts // rows + 1, 0), dtype=jnp.int32)
    return y.astype(x.dtype), (rounds > 1).astype(jnp.int32), read
