"""The routed experts' forms alone, on the chip: milliseconds a call
of ``MixtureOfExperts.forward`` (router and experts, no shared expert)
with the form forced, over token counts, row tiles and two routings:

    python tools/moe_experts_sweep.py [--tokens 64 128 ...] [--tiles 128]
    python tools/moe_experts_sweep.py --hidden 7168 --width 2048 \
        --experts 192 --held 12 --top-k 8 [--pieces]   # one chip of sixteen
    python tools/moe_experts_sweep.py --hidden 4096 --width 4096 \
        --experts 128 --held 16 --top-k 8 --step --tokens 8 16 32 64 \
        --routings even

What ``ops.experts._GROUPED_MIN_TOKENS``, ``_DENSE_TURN_ROWS``,
``_TILE_ROWS``, ``_GROUPED_STEP_S`` and ``_GROUPED_FIXED_S`` were set
from (PERF.md, sections 5 and 6).  Widths default
to the decode cell's (hidden 3,584, 64 experts of 1,024, 4 picks; one
layer, 1.41 GB in bfloat16).  ``even`` draws the router at random;
``one_expert`` biases expert 0 so that every token picks it (a quarter
of all rows in one group; under a share more held pairs than the rows of
one round, so the grouped form spills into further rounds).
``--held N`` holds the first ``N`` of ``--experts`` (a share: the router
keeps its width, most picks name experts that lie elsewhere; PERF.md,
PR 35); the grouped form then lays the held pairs alone in rows
(``ops.experts.grouped_rows``); a third column,
``grouped_all_pairs_ms``, times it with a row for every pair (the form
before PR 36: ``grouped_rows`` patched to the pair count), and a fourth,
``held_rows_ms``, the dense form over the tokens that picked a held
expert alone (``ops.experts.held_rows_experts``).  ``--step`` times a
share's token step instead: the dense form against the grouped form as
the program lays its rows (one tile of 16 rows at a few tokens), beside
the share of held experts an even router picks (``picked_share``) and
the share the grouped form read (``read_share``, its ``experts_read``
over the held experts); there a call is ``--chain`` layers of their
own weights one after another in one program, each fed the last one's
output, so that the host's launch of a call (~0.2 ms) does not hide
a layer's tenth of a millisecond.  ``--pieces``
adds, at ``--pieces-tokens``, each form's device operations of one call
in time order, from the profiler.  Each timing is the wall of
``--calls`` back-to-back dispatches of one jitted call after three warm
ones, divided by their number; the device runs them one after another.
Needs the chip: Mosaic compiles the kernel.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tokens", type=int, nargs="+",
                    default=[64, 128, 256, 512, 1024, 2048])
    ap.add_argument("--tiles", type=int, nargs="+", default=[64, 128, 256])
    ap.add_argument("--hidden", type=int, default=3584)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--held", type=int, default=None,
                    help="experts held, the first of --experts "
                         "(default: all)")
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--top-k", type=int, default=4)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--step", action="store_true",
                    help="a share's token step: dense against grouped "
                         "as the program lays its rows")
    ap.add_argument("--chain", type=int, default=4,
                    help="with --step: layers a timed call runs, each "
                         "with its own weights")
    ap.add_argument("--routings", nargs="+", default=["even", "one_expert"],
                    choices=["even", "one_expert"])
    ap.add_argument("--pieces", action="store_true",
                    help="each form's device operations of one call, "
                         "from the profiler")
    ap.add_argument("--pieces-tokens", type=int, nargs="+",
                    default=[256, 2048])
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "moe_experts_sweep.json"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.nn.layers import decoder
    from deeplearning4j_tpu.ops import experts

    device = jax.devices()[0]
    print("device", device.platform, device.device_kind, flush=True)
    if device.platform != "tpu":
        print("this needs the chip: Mosaic compiles the grouped product",
              file=sys.stderr)
        return 1
    layer = decoder.MixtureOfExperts(
        n_in=args.hidden, n_out=args.hidden, n_experts=args.experts,
        top_k=args.top_k, width=args.width, n_shared=0, routed_scaling=2.0,
        experts_held=(None if args.held is None
                      else list(range(args.held))),
        weight_init="distribution",
        dist=decoder.Distribution(kind="normal", std=0.02))
    params = layer.init_params(jax.random.PRNGKey(0), jnp.bfloat16)
    state = layer.init_state()
    # --step: further layers' weights, each drawn from a key of its own
    chained = [layer.init_params(jax.random.PRNGKey(i), jnp.bfloat16)
               for i in range(1, args.chain)] if args.step else []

    def clock(call, *inputs) -> tuple:
        for _ in range(3):
            y = jax.block_until_ready(call(*inputs))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = call(*inputs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.calls * 1e3, y

    def every_pair(tokens, top_k, held, n_experts, tm=128):
        return -(-tokens * top_k // tm) * tm, tm

    def timed(form: str, x, params, tm=None, all_pairs=False) -> tuple:
        path = mock.patch.object(decoder, "moe_experts_path",
                                 lambda *a, **k: form)
        product = mock.patch.object(
            decoder, "grouped_experts",
            functools.partial(experts.grouped_experts,
                              **({"tm": tm} if tm else {})))
        rows = mock.patch.object(
            experts, "grouped_rows",
            every_pair if all_pairs else experts.grouped_rows)
        with path, product, rows:
            result = clock(jax.jit(lambda p, x: layer.forward(
                p, state, x, train=False)), params, x)
            if not args.step:
                return result
            ps = [params] + [dict(q, router_bias=params["router_bias"])
                             for q in chained]

            def chain(ps, x):
                for q in ps:
                    x = x + 1e-3 * layer.forward(q, state, x, train=False)[0]
                return x

            ms, _ = clock(jax.jit(chain), ps, x)
            return ms / len(ps), result[1]

    def rel_l2(got, want) -> float:
        return round(float(
            jnp.linalg.norm((got - want).astype(jnp.float32))
            / jnp.linalg.norm(want.astype(jnp.float32))), 5)

    def pieces(form: str, x, p) -> list:
        """One call of the forced form under the profiler: its device
        operations of a microsecond or more in time order, ``[start_us,
        us, instruction, scope's last parts]`` (the products are the
        three long ones; what lies before them is the router and the
        laying of rows, what lies after the sum of a token's rows).  A
        piece timed alone in a chain reads the host's 0.2 ms a dispatch,
        not the device's microseconds."""
        import re
        import shutil
        import tempfile
        from benchmark import xplane
        with mock.patch.object(decoder, "moe_experts_path",
                               lambda *a, **k: form):
            call = jax.jit(lambda p, x: layer.forward(
                p, state, x, train=False)[0])
            text = call.lower(p, x).compile().as_text()
            jax.block_until_ready(call(p, x))
        scope = {}
        for line in text.splitlines():
            m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = ", line)
            tail = re.search(r'op_name="([^"]*)"', line)
            if m and tail:
                scope[m.group(1)] = "/".join(tail.group(1).split("/")[-2:])
        trace_dir = tempfile.mkdtemp()
        try:
            jax.profiler.start_trace(trace_dir)
            for _ in range(3):
                out = call(p, x)
            jax.block_until_ready(out)
            jax.profiler.stop_trace()
            events = xplane.read_events(xplane.find_trace(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if not events["modules"].get(0):
            return []                   # no device plane: not a TPU
        lo, hi, _ = sorted(events["modules"][0])[-1]
        return [[round(1e6 * (s - lo), 1), round(1e6 * (e - s), 1), name,
                 scope.get(name, "")]
                for name, s, e in sorted(events["devices"][0],
                                         key=lambda op: op[1])
                if lo <= s <= hi and e - s >= 1e-6]

    rows = []
    for routing in args.routings:
        bias = np.zeros((args.experts,), np.float32)
        if routing == "one_expert":
            bias[0] = 10.0
        p = dict(params, router_bias=jnp.asarray(bias, jnp.bfloat16))
        for tokens in args.tokens:
            x = jax.random.normal(jax.random.PRNGKey(tokens),
                                  (1, tokens, args.hidden), jnp.bfloat16)
            dense_ms, (want, _) = timed("dense", x, p)
            row = {"routing": routing, "tokens": tokens,
                   "dense_ms": round(dense_ms, 4)}
            if args.step:
                held = args.held or args.experts
                ms, (got, counts) = timed("grouped", x, p)
                row.update(
                    grouped_ms=round(ms, 4), grouped_rel_l2=rel_l2(got, want),
                    rows=experts.grouped_rows(tokens, args.top_k, held,
                                              args.experts),
                    picked_share=round(experts.picked_share(
                        tokens, args.top_k, args.experts), 4),
                    read_share=round(int(counts["experts_read"]) / held, 4),
                    spilled=int(counts["experts_spilled"]))
            for tm in () if args.step else args.tiles:
                ms, (got, counts) = timed("grouped", x, p, tm)
                row[f"grouped_tm{tm}_ms"] = round(ms, 4)
                row[f"grouped_tm{tm}_rel_l2"] = rel_l2(got, want)
                row["spilled"] = int(counts["experts_spilled"])
            if args.held is not None and not args.step:
                ms, (got, _) = timed("grouped", x, p, all_pairs=True)
                row["grouped_all_pairs_ms"] = round(ms, 4)
                row["grouped_all_pairs_rel_l2"] = rel_l2(got, want)
                ms, (got, counts) = timed("held_rows", x, p)
                row["held_rows_ms"] = round(ms, 4)
                row["held_rows_rel_l2"] = rel_l2(got, want)
                row["held_rows"] = experts.held_token_rows(
                    tokens, args.top_k, args.held, args.experts)
                row["held_rows_spilled"] = int(counts["experts_spilled"])
            print(json.dumps(row), flush=True)
            rows.append(row)
            if args.pieces and tokens in args.pieces_tokens:
                row["pieces"] = {
                    form: pieces(form, x, p)
                    for form in (("dense", "grouped")
                                 if args.held is None or args.step
                                 else ("dense", "held_rows", "grouped"))}
                for form, ops in row["pieces"].items():
                    print(json.dumps({
                        "routing": routing, "tokens": tokens, "form": form,
                        "busy_us": round(sum(op[1] for op in ops), 1),
                        "ops": [op for op in ops if op[1] >= 3]}),
                        flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump({"device": device.device_kind, "args": vars(args),
                   "rows": rows}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
