"""The routed experts' two forms alone, on the chip: milliseconds a call
of ``MixtureOfExperts.forward`` (router and experts, no shared expert)
with the form forced, over token counts, row tiles and two routings:

    python tools/moe_experts_sweep.py [--tokens 64 128 ...] [--tiles 128]
    python tools/moe_experts_sweep.py --hidden 7168 --width 2048 \
        --experts 192 --held 12 --top-k 8       # one chip of sixteen

What ``ops.experts._GROUPED_MIN_TOKENS`` and ``_TILE_ROWS`` were set
from (PERF.md, PR 34).  Widths default to the decode cell's (hidden
3,584, 64 experts of 1,024, 4 picks; one layer, 1.41 GB in bfloat16).
``even`` draws the router at random; ``one_expert`` biases expert 0 so
that every token picks it (a quarter of all rows in one group).
``--held N`` holds the first ``N`` of ``--experts`` (a share: the router
keeps its width, most picks name experts that lie elsewhere; PERF.md,
PR 35).  Each
timing is the wall of ``--calls`` back-to-back dispatches of one jitted
call after three warm ones, divided by their number; the device runs
them one after another.  Needs the chip: Mosaic compiles the kernel.
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

    def timed(form: str, x, params, tm=None) -> tuple:
        path = mock.patch.object(decoder, "moe_experts_path",
                                 lambda *a, **k: form)
        product = mock.patch.object(
            decoder, "grouped_experts",
            functools.partial(experts.grouped_experts,
                              **({"tm": tm} if tm else {})))
        with path, product:
            call = jax.jit(lambda p, x: layer.forward(
                p, state, x, train=False)[0])
            for _ in range(3):
                y = jax.block_until_ready(call(params, x))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = call(params, x)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.calls * 1e3, y

    rows = []
    for routing in ("even", "one_expert"):
        bias = np.zeros((args.experts,), np.float32)
        if routing == "one_expert":
            bias[0] = 10.0
        p = dict(params, router_bias=jnp.asarray(bias, jnp.bfloat16))
        for tokens in args.tokens:
            x = jax.random.normal(jax.random.PRNGKey(tokens),
                                  (1, tokens, args.hidden), jnp.bfloat16)
            dense_ms, want = timed("dense", x, p)
            row = {"routing": routing, "tokens": tokens,
                   "dense_ms": round(dense_ms, 4)}
            for tm in args.tiles:
                ms, got = timed("grouped", x, p, tm)
                err = float(jnp.linalg.norm((got - want).astype(jnp.float32))
                            / jnp.linalg.norm(want.astype(jnp.float32)))
                row[f"grouped_tm{tm}_ms"] = round(ms, 4)
                row[f"grouped_tm{tm}_rel_l2"] = round(err, 5)
            print(json.dumps(row), flush=True)
            rows.append(row)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump({"device": device.device_kind, "args": vars(args),
                   "rows": rows}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
