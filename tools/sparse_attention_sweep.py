"""The parts of the indexed sparse attention alone, on the chip, at a
cell's shapes: microseconds a call of the indexer's scores, of the
selection as row numbers (``lax.top_k``) and as a mask (the radix
search), of XLA's gather of the selected rows (which the package does
not do: the numbers are why), and of the attention in its two forms,
for the token step's one position and for a prefill chunk.

    chiprun -- python tools/sparse_attention_sweep.py
    chiprun -- python tools/sparse_attention_sweep.py --rows 8 --slots 131072

What ``ops/attention.py:sparse_attention_path`` and PERF.md's section 5
quote.  Times are of ``--calls``
back-to-back calls inside one jitted loop (a call's launch would
otherwise be most of a 50 us operation); each call's input differs in
its last bits so that nothing is hoisted.  Needs a TPU; exits 1 without
one.
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
    of three runs."""
    def many(*a):
        def body(i, acc):
            first = a[0]
            if jnp.issubdtype(first.dtype, jnp.floating):
                first = first + (i.astype(jnp.float32) * 1e-9).astype(
                    first.dtype)
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
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"sparse_attention_sweep: needs a TPU, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    b, s, k = args.rows, args.slots, args.topk
    h, g, d = args.heads, args.kv_heads, args.head_dim
    j, di = args.index_heads, args.index_dim
    key = jax.random.PRNGKey(0)
    draw = lambda *shape, dtype=jnp.bfloat16: jax.random.normal(
        key, shape, dtype)
    k_ring, v_ring, i_ring = draw(b, s, g * d), draw(b, s, g * d), \
        draw(b, s, di)
    cursor = jnp.asarray(s - args.chunk - 1, jnp.int32)
    scale = d ** -0.5
    run = functools.partial(timed, calls=args.calls)

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
        as_mask = jax.jit(lambda x: A.select_mask(x, visible, k))
        selected = as_mask(scores)
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
                "t=1 gather of one ring's selected rows",
                lambda ring: take(ring, rows), k_ring)
        row["attention, streamed"] = run(
            f"t={t} attention, streamed kernel",
            lambda a: A.sparse_attention_streamed(
                a, k_ring, v_ring, selected, cursor, sm_scale=scale), q)
        if t == 1:
            row["attention, masked"] = run(
                "t=1 attention, masked jax.numpy",
                lambda a: A.sparse_attention_masked(
                    a, k_ring, v_ring, selected, sm_scale=scale), q)
        return row

    out = {"shape": vars(args), "t=1": parts(1),
           f"t={args.chunk}": parts(args.chunk)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
