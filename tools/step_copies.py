"""The ``copy`` instructions of a decode cell's compiled steps, ahead of
time for a described v5e, without a chip:

    python tools/step_copies.py [--workload ax_k1.decode_b256_ctx1k] \\
        [--steps token_step,prefill_step] [--weights served|stored] \\
        [--layers N] [--min-mb 0.5] [--hlo DIR]

Builds the cell's net from shapes alone (nothing is drawn, nothing
runs), lowers ``cg.token_step`` / ``cg.prefill_step`` over
``ShapeDtypeStruct``s placed on one chip of a described ``v5e:2x2``
and compiles them with the TPU's own compiler (``JAX_PLATFORMS=cpu``
is enough: the topology is described, not attached).  Prints, a step,
the counts of the scheduled module's kernels (fusions, copies, custom
calls) and every ``copy`` of at least ``--min-mb`` by the bytes it
reads: its layout change, whether it is a kernel of its own (``alone``:
an instruction of a scheduled computation, or a fusion that holds
nothing else) or folded into a larger fusion's operand (``fused``: no
kernel, no time of its own), the parameter it reads where its operand
is one (through slices, bitcasts and prefetches), and its ``op_name``.

A copy of a parameter is a weight turned inside the step: the step
reads the matrix once to turn it and multiplies the turned form out of
VMEM with no fetch left to hide under (PERF.md, PR 38).  ``--weights
served`` (default) hands the step what a served net's
``token_step()`` hands it (``ComputationGraph.served_params``: the laid
forms where a layer lays its weights); ``--weights stored`` the plain
parameters, which the layer lays inside the step.  ``--layers`` cuts
the depth (the leading dense layer stays).  It is a tool: no cell runs
it; ``tests/test_latent_ring_kernel.py`` imports ``compile_step`` and
``copies``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from typing import Dict, List, Optional
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
            "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4,
            "u32": 4, "f64": 8, "s64": 8, "u64": 8}
#: what a value passes through unchanged on its way from a parameter
_PASSES = {"bitcast", "reshape", "get-tuple-element", "slice-start",
           "slice-done", "copy-start", "copy-done", "custom-call"}
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<type>\(.*?\)|\S+) "
    r"(?P<op>[\w\-]+)\((?P<args>.*?)\)(?:, |$)")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\](\{[^}]*\})?")


def describe_chip():
    """One chip of a described ``v5e:2x2`` as a sharding to place
    ``ShapeDtypeStruct``s on; raises where libtpu cannot describe it."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, chip):
    """``tree``'s shapes and dtypes as ``ShapeDtypeStruct``s on ``chip``."""
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree)


def abstract_net(cfg: Dict, slots: int, chip, layers: Optional[int] = None):
    """The served net of ``cfg`` (a file of ``benchmark/configs``) with
    ``ShapeDtypeStruct``s on ``chip`` for parameters and state, bf16
    as a TPU resolves them; ``layers`` cuts the depth."""
    import jax
    import jax.numpy as jnp
    from benchmark import nets
    if layers is not None:
        cfg = dict(cfg, num_hidden_layers=int(layers))
    conf = nets._resolve(cfg["builder"])(
        cfg, cache_len=slots, seed=1, dtype="bfloat16",
        **cfg.get("builder_args", {}))
    net = nets._resolve(cfg["container"])(conf)
    key = jax.random.PRNGKey(0)
    names = net._layer_names()
    net.params = {n: _placed(jax.eval_shape(
        lambda k, n=n: net.vertices[n].layer.init_params(k, jnp.bfloat16),
        key), chip) for n in names}
    net.net_state = {n: _placed(jax.eval_shape(
        lambda n=n: net.vertices[n].layer.init_state(jnp.bfloat16)), chip)
        for n in names}
    net._init_done = net._inference_only = True
    return net


def compile_step(net, step: str, rows: int, slots: int, chunk: int, chip,
                 weights: str = "served") -> str:
    """The scheduled HLO text of ``net``'s ``token_step`` (one id a
    row) or ``prefill_step`` (``chunk`` ids a row) over ``rows``
    conversations with rings of ``slots``, compiled for ``chip``."""
    import jax
    import jax.numpy as jnp
    placed = lambda tree: _placed(tree, chip)
    ids = lambda t: jax.ShapeDtypeStruct((rows, t), jnp.int32,
                                         sharding=chip)
    params = net.params
    if weights == "served" and net.laid_vertices():
        params = placed(jax.eval_shape(net.lay_weights, params))
    # the forms the layers' predicates pick on a TPU, not the CPU's
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            jax.enable_x64(False):
        carries = placed(jax.eval_shape(
            lambda: net._init_carries(rows, cache_len=slots)))
        if step == "token_step":
            counts = placed(jax.eval_shape(net.zero_expert_counts))
            lowered = net._token_step_fn.lower(
                params, net.net_state, carries, ids(1), counts)
        else:
            lowered = net._prefill_step_fn.lower(
                params, net.net_state, carries, ids(chunk))
        return lowered.compile().as_text()


# ------------------------------------------------------------ the HLO text
def _computations(text: str) -> Dict[str, dict]:
    """``{computation: {"entry": bool, "instructions": {name: (type, op,
    [operand names], line)}}}`` of a module's text."""
    out, current = {}, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%([\w.\-]+) \(", line)
        if head:
            current = out[head.group(2)] = {"entry": bool(head.group(1)),
                                            "instructions": {}}
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line) if current is not None else None
        if m:
            current["instructions"][m.group("name")] = (
                m.group("type"), m.group("op"),
                re.findall(r"%([\w.\-]+)", m.group("args")), line)
    return out


def _nbytes(array_type: str) -> int:
    m = _ARRAY.match(array_type)
    if not m:
        return 0
    n = 1
    for d in filter(None, m.group(2).split(",")):
        n *= int(d)
    return n * ITEMSIZE.get(m.group(1), 4)


def _layout(array_type: str) -> str:
    """``{2,1,0}`` of ``bf16[..]{2,1,0:T(8,128)(2,1)S(1)}``, with
    ``S(1)`` (VMEM) kept."""
    m = _ARRAY.match(array_type)
    layout = (m.group(3) or "{}") if m else "{}"
    order = layout.strip("{}").split(":")[0]
    return "{" + order + "}" + ("S(1)" if "S(1)" in layout else "")


def _op_name(line: str) -> str:
    m = re.search(r'op_name="([^"]*)"', line)
    return m.group(1).replace("\\'", "'") if m else ""


def copies(text: str) -> List[dict]:
    """Every ``copy`` instruction of a scheduled module, largest first:
    ``{"bytes", "shape", "from", "to", "alone", "parameter",
    "op_name"}``.  ``alone``: a kernel of its own; ``parameter``: the
    entry parameter's ``op_name`` where the copy reads one."""
    comps = _computations(text)
    # fused computation -> (calling computation, the fusion's operands)
    called = {}
    for cname, comp in comps.items():
        for _, (_, op, operands, line) in comp["instructions"].items():
            m = re.search(r"calls=%([\w.\-]+)", line)
            if op == "fusion" and m:
                called[m.group(1)] = (cname, operands)

    def source(cname, name, hops=0):
        """The entry parameter ``name`` of ``cname`` comes from, through
        what passes a value unchanged, or ""."""
        ins = comps[cname]["instructions"].get(name)
        if ins is None or hops > 32:
            return ""
        type_, op, operands, line = ins
        if op == "parameter":
            if comps[cname]["entry"]:
                return _op_name(line) or name
            if cname in called:
                caller, args = called[cname]
                index = int(re.search(r"parameter\((\d+)\)", line).group(1))
                return source(caller, args[index], hops + 1)
            return ""
        if op in _PASSES and operands and (
                op != "custom-call" or "ConcatBitcast" in line):
            # a ConcatBitcast joins slices of one array: any names it
            return source(cname, operands[0], hops + 1)
        return ""

    rows = []
    for cname, comp in comps.items():
        fused = cname in called
        only_moves = all(op in ("parameter", "copy", "bitcast")
                         for _, op, _, _ in comp["instructions"].values())
        nested = fused and called[cname][0] in called
        for name, (type_, op, operands, line) in \
                comp["instructions"].items():
            if op != "copy":
                continue
            operand = comp["instructions"].get(operands[0], ("",))[0] \
                if operands else ""
            rows.append({
                "bytes": _nbytes(type_),
                "shape": type_.split("{")[0],
                "from": _layout(operand) if operand else "?",
                "to": _layout(type_),
                "alone": (not fused) or (only_moves and not nested),
                "parameter": source(cname, operands[0]) if operands else "",
                "op_name": _op_name(line)})
    return sorted(rows, key=lambda r: -r["bytes"])


def kernel_counts(text: str) -> Dict[str, int]:
    """What a scheduled module launches: its fusions, ``copy``
    instructions, custom calls (Mosaic kernels among them) and loops."""
    comps = _computations(text)
    ops = [op for comp in comps.values()
           for _, op, _, _ in comp["instructions"].values()]
    return {"fusions": ops.count("fusion"), "copies": ops.count("copy"),
            "custom_calls": ops.count("custom-call"),
            "mosaic_kernels": text.count(
                'custom_call_target="tpu_custom_call"'),
            "whiles": ops.count("while")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="ax_k1.decode_b256_ctx1k")
    ap.add_argument("--steps", default="token_step,prefill_step")
    ap.add_argument("--weights", choices=("served", "stored"),
                    default="served")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--min-mb", type=float, default=0.5)
    ap.add_argument("--hlo", default=None,
                    help="directory to write each step's module text to")
    args = ap.parse_args(argv)

    import jax
    # the persistent cache cannot read an executable of a described
    # topology back
    jax.config.update("jax_enable_compilation_cache", False)
    from benchmark.run import HERE, Lookup
    lookup = Lookup([HERE])
    cell = lookup.data("workloads", args.workload)
    cfg = lookup.data("configs", cell["config"])
    traffic = lookup.data("traffic", cell["traffic"])
    rows, slots = traffic["rows"], traffic["ring_slots"]
    chip = describe_chip()
    net = abstract_net(cfg, slots, chip, args.layers)
    print(f"{args.workload}: {rows} rows, rings of {slots}, weights "
          f"{args.weights}; laying vertices {len(net.laid_vertices())}; "
          f"param bytes {sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(net.params))}",
          flush=True)
    for step in args.steps.split(","):
        t0 = time.perf_counter()
        text = compile_step(net, step, rows, slots,
                            traffic["prefill_chunk"], chip, args.weights)
        found = copies(text)
        alone = [r for r in found if r["alone"]]
        of_parameters = [r for r in alone
                         if r["parameter"].startswith("params[")]
        print(f"== {step}: compiled in {time.perf_counter() - t0:.1f} s; "
              f"{json.dumps(kernel_counts(text))}; copies alone "
              f"{len(alone)} reading {sum(r['bytes'] for r in alone) / 1e6:.2f}"
              f" MB, of weights {len(of_parameters)} reading "
              f"{sum(r['bytes'] for r in of_parameters) / 1e6:.2f} MB",
              flush=True)
        for r in found:
            if r["bytes"] < args.min_mb * 1e6:
                break
            print(f"  {r['bytes'] / 1e6:9.2f} MB  {r['shape']} {r['from']}"
                  f" -> {r['to']}  {'alone' if r['alone'] else 'fused'}  "
                  f"{r['parameter'] or '-'}  {r['op_name'][-72:]}")
        if args.hlo:
            os.makedirs(args.hlo, exist_ok=True)
            path = os.path.join(
                args.hlo, f"{args.workload}.{step}.{args.weights}.hlo.txt")
            with open(path, "w") as fh:
                fh.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
