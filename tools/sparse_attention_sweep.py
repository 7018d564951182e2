"""The parts of the indexed sparse attention alone, on the chip, at a
cell's shapes: microseconds a call of the indexer's scores, of the
selection as a mask (the radix search), as row numbers by ``lax.top_k``
and as the slot list the gathered form takes, and of the attention in
its three forms, for the token step's one position and for a prefill
chunk.

    chiprun -- python tools/sparse_attention_sweep.py
    chiprun -- python tools/sparse_attention_sweep.py --rows 8 --slots 131072
    chiprun -- python tools/sparse_attention_sweep.py --crossover

What ``ops/attention.py:sparse_attention_path`` and PERF.md's section 5
quote.  The token step's rows, in the order the gathered form was built:

- ``descriptor fetch alone``: the gathered kernel's copies with no
  attention after them, one descriptor a selected slot (a slot's tile of
  keys and values, 2 KB in bfloat16) out of the ring left in HBM, one
  wait; with a descriptor a ring a slot (keys and values in rings of
  their own, 1 KB each) beside it, which is why the rings were joined;
- ``attention, gathered``: the same copies and the attention over what
  landed (``sparse_attention_gathered``);
- ``slot list from the mask``: ``selected_slots``;
- ``attention, streamed``: the ring streamed whole through the mask;
- ``--crossover``: gathered (list included) against streamed over rings
  of 4,096 to 131,072 slots, which is where ``_GATHER_RATIO`` is read.

``gather of one ring's rows`` is XLA's ``gather`` of the selected rows
out of a (rows, slots, 512) array, which the package never did.  It is
kept as the record of why, with what it measures said plainly: PR 37
timed it with the ring as the nudged argument, so its 1.11 ms out of
32,768-slot rings and 3.56 ms out of 131,072 were the gather and a pass
over the whole ring (the row ``..., the ring nudged`` still times that:
it grows with the ring because the pass does).  With the row numbers
nudged instead the same gather takes 0.29 ms, and 0.25 ms for the
joined ring's 2 KB slots: a fetch by row at about 15 ns a row, near the
kernel's own descriptors' pace (PERF.md, PR 40).

Times are of ``--calls`` back-to-back calls inside one jitted loop (a
call's launch would otherwise be most of a 50 us operation); each call's
input differs in its last bits so that nothing is hoisted.  Needs a
TPU; exits 1 without one.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, __file__.rsplit("/tools/", 1)[0])
from deeplearning4j_tpu.ops import attention as A        # noqa: E402


def timed(name, fn, *args, calls: int):
    """Microseconds a call of ``fn(*args)``: ``calls`` in one program,
    the first argument nudged a call so that none is hoisted, the best
    of three runs.  The nudge is a pass over the first argument: give a
    small one first, never a ring."""
    def many(*a):
        def body(i, acc):
            first = a[0]
            if jnp.issubdtype(first.dtype, jnp.floating):
                first = first + (i.astype(jnp.float32) * 1e-9).astype(
                    first.dtype)
            else:               # whole numbers: plus 0, which XLA cannot see
                first = first + (i >> 30).astype(first.dtype)
            out = fn(first, *a[1:])
            return acc + sum(jnp.sum(leaf.astype(jnp.float32)[..., :1])
                             for leaf in jax.tree.leaves(out))
        return lax.fori_loop(0, calls, body, jnp.zeros((), jnp.float32))
    try:
        program = jax.jit(many)
        jax.block_until_ready(program(*args))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(program(*args))
            best = min(best, time.perf_counter() - t0)
        micros = best / calls * 1e6
        print(f"{name}: {micros:.1f} us", flush=True)
        return micros
    except Exception as exc:            # a form the compiler refuses
        print(f"{name}: FAILED {type(exc).__name__}: {str(exc)[:200]}",
              flush=True)
        return None


def fetch_alone(nudge, slots, *rings, unroll: int = 8, flat: bool = False):
    """The gathered kernel's descriptors and its one wait, nothing else:
    ``slots`` (rows, topk) slot numbers, a copy a ring a slot into a
    landing buffer a ring.  ``flat``: the rings are (1, rows x slots,
    ...) and the numbers count through all conversations."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, topk = slots.shape
    n = len(rings)

    def kernel(slots_ref, nudge_ref, *rest):
        hbm, o_ref, landed, sem = (rest[:n], rest[n], rest[n + 1:-1],
                                   rest[-1])
        b = pl.program_id(0)

        def issue(turn, _):
            for u in range(unroll):
                i = turn * unroll + u
                for ring, buf in zip(hbm, landed):
                    pltpu.make_async_copy(
                        ring.at[0 if flat else b, slots_ref[b, i]],
                        buf.at[i], sem).start()
            return 0

        lax.fori_loop(0, topk // unroll, issue, 0)
        for buf in landed:
            pltpu.make_async_copy(buf, buf, sem).wait()
        o_ref[0] = nudge_ref[...] + sum(
            buf[0:8, 0, :].astype(jnp.float32) for buf in landed)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, 8, 128), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows,),
            in_specs=[pl.BlockSpec((8, 128), lambda b, s: (0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * n,
            out_specs=pl.BlockSpec((1, 8, 128), lambda b, s: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((topk,) + ring.shape[2:], ring.dtype)
                            for ring in rings]
            + [pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
    )(slots, nudge, *rings)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--slots", type=int, default=32768)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--index-heads", type=int, default=16)
    ap.add_argument("--index-dim", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--crossover", action="store_true",
                    help="the token step's gathered and streamed forms "
                    "over rings of 4,096 to 131,072 slots, nothing else")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"sparse_attention_sweep: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    b, k = args.rows, args.topk
    h, g, d = args.heads, args.kv_heads, args.head_dim
    j, di = args.index_heads, args.index_dim
    key = jax.random.PRNGKey(0)
    draw = lambda *shape, dtype=jnp.bfloat16: jax.random.normal(
        key, shape, dtype)
    scale = d ** -0.5
    run = functools.partial(timed, calls=args.calls)

    def token_forms(s: int, scores, kv_ring, cursor) -> dict:
        """The token step's attention over a ring of ``s`` slots: the
        list, the gathered form with it, the streamed form; with the
        list and the mask they were timed on."""
        q = draw(b, 1, h, d)
        visible = A.visible_slots(cursor, 1, s)[None]
        as_mask = jax.jit(lambda x: A.select_mask(x, visible, k))
        selected = as_mask(scores)
        as_list = jax.jit(lambda m: A.selected_slots(m[:, 0], k))
        slots, count = as_list(selected)
        row = {}
        row["slot list from the mask"] = run(
            f"slots={s} t=1 slot list from the mask (selected_slots)",
            lambda m: A.selected_slots(m[:, 0] > 0.5, k),
            selected.astype(jnp.bfloat16))
        row["attention, gathered"] = run(
            f"slots={s} t=1 attention, gathered kernel",
            lambda a, ring, at, n: A.sparse_attention_gathered(
                a, ring, at, n, sm_scale=scale), q, kv_ring, slots, count)
        row["attention, streamed"] = run(
            f"slots={s} t=1 attention, streamed kernel",
            lambda a, ring, m: A.sparse_attention_streamed(
                a, ring, m, cursor, sm_scale=scale), q, kv_ring, selected)
        return row, slots, selected

    if args.crossover:
        out = {"shape": vars(args)}
        for s in (4096, 8192, 16384, 32768, 65536, 131072):
            out[f"slots={s}"] = token_forms(
                s, draw(b, 1, s, dtype=jnp.float32), draw(b, s, 2 * g, d),
                jnp.asarray(s - 1, jnp.int32))[0]
        print(json.dumps(out))
        return 0

    s = args.slots
    kv_ring, i_ring = draw(b, s, 2 * g, d), draw(b, s, di)
    cursor = jnp.asarray(s - args.chunk - 1, jnp.int32)

    def parts(t: int) -> dict:
        q, q_idx, w_idx = draw(b, t, h, d), draw(b, t, j, di), draw(b, t, j)
        scores = draw(b, t, s, dtype=jnp.float32)
        visible = A.visible_slots(cursor, t, s)[None]
        row = {}
        row["indexer, jax.numpy"] = run(
            f"t={t} indexer scores, jax.numpy", A.indexer_scores,
            q_idx, w_idx, i_ring)
        row["indexer, streamed"] = run(
            f"t={t} indexer scores, streamed kernel",
            lambda a, w, r: A.indexer_scores_streamed(a, w, r, cursor),
            q_idx, w_idx, i_ring)
        row["select as a mask"] = run(
            f"t={t} select_mask (radix search, {A._SELECT_BITS} bits a "
            f"pass)", lambda x: A.select_mask(x, visible, k), scores)
        row["select as a mask, streamed"] = run(
            f"t={t} select_mask_streamed (binary search in VMEM)",
            lambda x: A.select_mask_streamed(x, cursor, k), scores)
        if t == 1:
            as_rows = lambda x: lax.top_k(
                jnp.where(visible[0], x, -jnp.inf), k)[1]
            row["select as rows"] = run(
                "t=1 selection as row numbers (lax.top_k)", as_rows,
                scores[:, 0])
            select_rows = jax.jit(as_rows)
            rows = select_rows(scores[:, 0])
            take = jax.vmap(lambda ring, at: ring[at])
            row["gather of one ring's rows"] = run(
                "t=1 XLA gather of one ring's selected rows",
                lambda at, ring: take(ring, at), rows, draw(b, s, g * d))
            row["gather of one ring's rows, the ring nudged"] = run(
                "t=1 XLA gather of one ring's selected rows, and a pass "
                "over the ring (what PR 37 timed)",
                lambda ring, at: take(ring, at), draw(b, s, g * d), rows)
            row["gather of the joined ring's slots"] = run(
                "t=1 XLA gather of the joined ring's selected slots",
                lambda at, ring: take(ring, at), rows, kv_ring)
            forms, slots, selected = token_forms(s, scores, kv_ring, cursor)
            nudge = jnp.zeros((8, 128), jnp.float32)
            row["descriptor fetch alone"] = run(
                "t=1 descriptor fetch alone, one a slot (2 KB)",
                fetch_alone, nudge, slots, kv_ring)
            row["descriptor fetch alone, a ring each"] = run(
                "t=1 descriptor fetch alone, keys and values in rings of "
                "their own (two a slot, 1 KB)", fetch_alone, nudge, slots,
                kv_ring[:, :, :g], kv_ring[:, :, g:])
            row["descriptor fetch alone, 32 a turn"] = run(
                "t=1 descriptor fetch alone, one a slot, 32 a turn of the "
                "loop", functools.partial(fetch_alone, unroll=32), nudge,
                slots, kv_ring)
            row["descriptor fetch alone, flat"] = run(
                "t=1 descriptor fetch alone, one a slot, numbered through "
                "all conversations", functools.partial(fetch_alone, flat=True),
                nudge, slots + s * jnp.arange(b, dtype=jnp.int32)[:, None],
                kv_ring.reshape((1, b * s) + kv_ring.shape[2:]))
            row["descriptors a call"] = b * k
            row.update(forms)
            row["attention, masked"] = run(
                "t=1 attention, masked jax.numpy",
                lambda a, ring, m: A.sparse_attention_masked(
                    a, ring, m, sm_scale=scale), q, kv_ring, selected)
        else:
            as_mask = jax.jit(lambda x: A.select_mask(x, visible, k))
            selected = as_mask(scores)
            row["attention, streamed"] = run(
                f"t={t} attention, streamed kernel",
                lambda a, ring, m: A.sparse_attention_streamed(
                    a, ring, m, cursor, sm_scale=scale), q, kv_ring, selected)
        return row

    out = {"shape": vars(args), "t=1": parts(1),
           f"t={args.chunk}": parts(args.chunk)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
