"""Op-level HBM profile of a compiled train step (round-4 verdict item
6: ResNet-50 sits at the HBM roofline — record WHICH ops stream the
bytes, and whether any traffic is avoidable).

Method: AOT-compile the 1-step train program (the same executable the
bench's roofline uses), then parse the optimized HLO.  At the
post-fusion level, every instruction's operands and outputs are real
buffers — intra-fusion temporaries have been fused away — so
bytes(instr) = output bytes + sum(operand bytes) approximates that
instruction's HBM traffic (upper bound: operands resident in VMEM
across consumers are charged to each).  This is the same accounting
XLA's own cost model uses for "bytes accessed", but per-op instead of
aggregate.

This is a PROJECTION from HLO compiled for whatever backend runs it (the
CPU's, in the sandbox), not a measurement.  Since PR 39 the measurement
is the device trace's own bytes: every ``.xplane.pb`` from the chip
carries the TPU compiler's cost analysis of each instruction with its
memory spaces, and ``monitor/device_trace.py:reduce`` reports HBM bytes
by scope from it (``cost_by_scope``, ``hbm_bytes``;
``docs/OBSERVABILITY.md`` section 8).  This tool stays while ``bench.py``
reads it (ROADMAP design debts).

Reference analogue: the cuDNN tier's workspace/memory accounting
(``CudnnConvolutionHelper.java:64-140``) — the reference's only
memory-tuning surface.

Usage: python tools/hbm_profile.py
           [resnet|lenet|vgg|gather|glove|glove-naive] [top_n]

The audit defaults to the **TPU default precision policy**
(``mixed_bf16``: bf16 params + bf16 activations + fp32 masters in the
updater state) even on CPU, so the cost-model/HLO numbers reflect the
program the chip would actually run.  Set ``DL4J_TPU_PRECISION=fp32``
to audit the fp32 program instead and compare bytes side by side.

``gather`` profiles the epoch-cache v2 program
(``MultiLayerNetwork._gather_train_step``): on-device threefry epoch
permutation, per-step row gather from the resident uint8 cache, fused
decode to f32/bf16, scan over the epoch — the program whose HBM
behaviour the device-resident ingest rework is accountable for.
"""

import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import numpy as np

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "s32": 4, "u64": 8, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1}

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|u64|u32|s16|u16|s8|u8"
                       r"|pred)\[([0-9,]*)\]")


def shape_bytes(shape_str: str, by_dtype=None) -> int:
    """Total bytes of every array shape mentioned in an HLO type string
    (handles tuples by summing members).  When ``by_dtype`` (a dict) is
    given, per-dtype byte totals are accumulated into it as well."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = n * _DTYPE_BYTES[dtype]
        total += b
        if by_dtype is not None:
            by_dtype[dtype] = by_dtype.get(dtype, 0) + b
    return total


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+"
    r"([\w\-]+)\((.*)$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def profile_hlo(hlo_text: str):
    """Parse optimized HLO; return (rows, total_bytes, by_dtype) where
    rows are ALL (bytes, op_kind, name, out_shape) entries, largest
    first, and by_dtype maps HLO dtype tag -> traffic bytes.

    Computation-aware: instructions INSIDE fusion bodies
    (``%fused_computation*``) and scalar reducer/comparator regions are
    NOT HBM traffic — only the entry computation and control-flow
    bodies (while/cond) stream buffers.  Counting fusion-body
    instructions overstates traffic ~10x (measured vs the XLA cost
    model on ResNet-50).  Control-flow wrapper ops (while, tuple,
    get-tuple-element, parameter, constant) are skipped — their
    "operands" are whole state tuples, not streamed traffic."""
    shapes = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m:
            shapes[m.group(1)] = m.group(2)
    skip = {"parameter", "constant", "tuple", "get-tuple-element",
            "while", "conditional", "call", "bitcast", "copy-start",
            "copy-done", "after-all", "partition-id"}
    rows = []
    total = 0
    by_dtype = {}
    in_excluded = False
    depth = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # computation-block bookkeeping: a top-level "name (...) -> T {"
        # line opens a computation; exclude fusion bodies and scalar
        # regions (reducers, comparators, scatter combiners).
        if not line.startswith(" ") and stripped.endswith("{"):
            cname = stripped.split("(")[0].strip().lstrip("%")
            in_excluded = any(tag in cname for tag in
                              ("fused_computation", "region_",
                               "scatter_computation", "AddComputation",
                               "MaxComputation", "add_computation",
                               "max_computation", "and.reduce",
                               "or.reduce"))
            continue
        if not line.startswith(" ") and stripped == "}":
            in_excluded = False
            continue
        if in_excluded:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, out_shape, kind, rest = m.groups()
        if kind in skip or kind.endswith("-start"):
            continue      # -start halves pair with -done; count once
        out_b = shape_bytes(out_shape, by_dtype)
        if kind in ("slice", "dynamic-slice", "dynamic-update-slice",
                    "broadcast", "reshape", "transpose", "reverse"):
            # These read/write only the window/output, not the full
            # operand: charging operand bytes overstated slices to 42%
            # of ResNet's total.  (dynamic-update-slice writes a
            # window into an aliased buffer: window read + write.)
            shape_bytes(out_shape, by_dtype)
            b = 2 * out_b
        else:
            arg_str = rest.split(", calls=")[0].split(", metadata=")[0]
            b = out_b
            for op in _OPERAND_RE.findall(arg_str):
                if op in shapes:
                    b += shape_bytes(shapes[op], by_dtype)
        rows.append((b, kind, name, out_shape))
        total += b
    rows.sort(reverse=True)
    return rows, total, by_dtype


def _classify(kind: str, name: str, shape: str) -> str:
    if kind in ("convolution", "custom-call") and "conv" in name:
        return "conv"
    if kind == "fusion":
        return "fusion"
    if kind in ("dot",):
        return "matmul"
    if "scatter" in kind:
        return "scatter"
    return kind


def compiled_step(config: str):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.precision import default_compute_dtype
    cdt = default_compute_dtype()       # DL4J_TPU_PRECISION-aware

    if config == "resnet":
        from deeplearning4j_tpu.models.resnet import resnet50
        from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
        net = ComputationGraph(resnet50(compute_dtype=cdt)).init()
        fdt = jnp.dtype(net._pol().compute_dtype)
        batch = 128
        f = [jnp.zeros((1, batch, 224, 224, 3), fdt)]
        l = [jnp.zeros((1, batch, 1000), jnp.float32)]
    elif config == "vgg":
        from deeplearning4j_tpu.keras.trained_models import vgg16
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        net = MultiLayerNetwork(vgg16(compute_dtype=cdt)).init()
        fdt = jnp.dtype(net._pol().compute_dtype)
        batch = 256
        f = jnp.zeros((1, batch, 224, 224, 3), fdt)
        l = jnp.zeros((1, batch, 1000), jnp.float32)
    elif config == "gather":
        # epoch-cache v2: resident uint8 MNIST cache, device threefry
        # permutation, row gather + fused decode, one-epoch scan
        from deeplearning4j_tpu.models.lenet import lenet
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        net = MultiLayerNetwork(lenet(compute_dtype=cdt)).init()
        n, batch = 60000, 256
        f = jnp.zeros((n, 784), jnp.uint8)
        l = jnp.zeros((n, 10), jnp.float32)
        shuffle_key = jax.random.fold_in(net._rng_key, 0xFFFFFFFF)
        steps = n // batch
        args = (net.params, net.updater_state, net.net_state,
                net.iteration, f, l, net._rng_key, shuffle_key, 0, 1,
                steps, batch, True, 0, (255.0, 1.0, 0.0), 0, steps)
        return net._gather_train_step.lower(*args).compile(), net
    elif config in ("glove", "glove-naive"):
        # scatter-row audit for the embedding economics work: compile a
        # 1-chunk GloVe epoch twin and count its scatter instructions.
        # The fused dual-buffer path must show TWO scatters (one per
        # packed side table, sorted-unique); the naive reference shows
        # EIGHT (W/b/hW/hb x2 sides), each a colliding duplicate-row
        # scatter.  Same audit surface as the ResNet conv rows.
        from deeplearning4j_tpu.nlp.glove import (_glove_epoch,
                                                  _glove_epoch_fused)
        V, D, B = 20000, 128, 8192
        rows = jnp.zeros((B,), jnp.int32)
        cols = jnp.zeros((B,), jnp.int32)
        logx = jnp.zeros((B,), jnp.float32)
        fx = jnp.zeros((B,), jnp.float32)
        order = jnp.zeros((1, B), jnp.int32)
        lr = jnp.float32(0.05)
        if config == "glove":
            Sr = jnp.zeros((V, 2 * D + 2), jnp.float32)
            Sc = jnp.zeros((V, 2 * D + 2), jnp.float32)
            return _glove_epoch_fused.lower(Sr, Sc, rows, cols, logx,
                                            fx, order, lr).compile(), None
        W = jnp.zeros((V, D), jnp.float32)
        tabs = (W, W + 0, jnp.zeros((V,)), jnp.zeros((V,)), W + 0,
                W + 0, jnp.zeros((V,)), jnp.zeros((V,)))
        return _glove_epoch.lower(*tabs, rows, cols, logx, fx,
                                  order, lr).compile(), None
    else:
        from deeplearning4j_tpu.models.lenet import lenet
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        net = MultiLayerNetwork(lenet(compute_dtype=cdt)).init()
        fdt = jnp.dtype(net._pol().compute_dtype)
        batch = 256
        f = jnp.zeros((1, batch, 784), fdt)
        l = jnp.zeros((1, batch, 10), jnp.float32)
    args = (net.params, net.updater_state, net.net_state, net.iteration,
            f, l, None, None, net._rng_key)
    return net._multi_train_step.lower(*args).compile(), net


# The recorded fp32 LeNet row this campaign is measured against
# (round-5 record, deleted in PR 21: batch-256 hbm_bytes_per_step;
# ISSUE 7 acceptance).
BENCH_R05_LENET_BYTES = 117_648_384

# configs whose step comes from a network (fp32 twin is comparable)
_NET_CONFIGS = ("resnet", "lenet", "vgg", "gather")


def chip_posture_estimate(total_f32: float, f32_traffic: float,
                          moments_io: float, master_io: float,
                          masters: bool) -> float:
    """Project the fp32 program's traffic onto the chip under the bf16
    policy: every f32 buffer the fp32 program streams becomes bf16 on
    the TPU (activations, params, grads — x0.5) EXCEPT the updater
    moments, which the mixed policy keeps fp32 (restored at full width),
    plus one fp32 read + write of the master copies per step.  CPU-XLA
    cannot show this directly — it upcasts bf16 conv/dot to f32 through
    convert fusions, so the raw bf16-program cost model OVERSTATES chip
    traffic (measured: LeNet b256 366 MB bf16 vs 324 MB fp32)."""
    est = total_f32 - 0.5 * f32_traffic + 0.5 * moments_io
    if masters:
        est += master_io
    return est


def _policy_comparison(config: str, pol, cost_bytes_pol: float) -> None:
    """Compile the fp32 twin of ``config`` and print the CPU-posture
    bytes comparison (ISSUE 7 acceptance: LeNet bytes/step must
    measurably drop under the default TPU policy)."""
    import jax

    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.nn import precision

    prev = os.environ.get(precision._ENV)
    os.environ[precision._ENV] = precision.FP32
    try:
        compiled32, net32 = compiled_step(config)
    finally:
        os.environ[precision._ENV] = prev
    cost32 = compiled32.cost_analysis()
    if isinstance(cost32, list):
        cost32 = cost32[0]
    cost32_b = float(cost32.get("bytes accessed", 0.0))
    _, total32, by_dtype32 = profile_hlo(compiled32.as_text())
    moments_io = 2 * sum(int(l.size) * l.dtype.itemsize
                         for l in jax.tree.leaves(net32.updater_state))
    master_io = 2 * 4 * sum(int(l.size)
                            for l in jax.tree.leaves(net32.params))
    est = chip_posture_estimate(total32, by_dtype32.get("f32", 0),
                                moments_io, master_io,
                                pol.master_weights)
    ratio = est / total32 if total32 else 1.0
    print(f"\n# precision comparison ({pol.name} vs fp32, CPU posture)")
    print(f"#   fp32 program:   cost model {cost32_b:,.0f} B/step; "
          f"parsed {total32/1e6:.0f} MB")
    print(f"#   {pol.name} program: cost model {cost_bytes_pol:,.0f} "
          f"B/step (CPU convert overhead included)")
    print(f"#   chip-posture estimate (f32 traffic at policy widths, "
          f"moments fp32, masters r/w): {est:,.0f} B "
          f"= x{ratio:.3f} vs fp32")
    print(f"#   projected xla_cost_bytes_accessed on chip: "
          f"{cost32_b * ratio:,.0f} B/step")
    if config == "lenet":
        print(f"#   projected BENCH_r05 LeNet row: "
              f"{BENCH_R05_LENET_BYTES:,} -> "
              f"{BENCH_R05_LENET_BYTES * ratio:,.0f} B/step")
    g = monitor.gauge("hbm_profile_policy_bytes",
                      "CPU-posture precision-policy bytes comparison "
                      "(parsed HLO traffic per train step)")
    g.set(float(total32), config=config, program="fp32")
    g.set(float(est), config=config, program="chip_estimate")


def register_monitor_gauges(config: str, by_class: dict,
                            total: int) -> None:
    """Publish the profile into the runtime telemetry registry so a
    /metrics scrape (ui server) or ``monitor.snapshot()`` carries the
    per-op-class HBM totals alongside the live training metrics."""
    from deeplearning4j_tpu import monitor
    for cls, b in by_class.items():
        monitor.gauge("hbm_profile_bytes",
                      "per-op-class HBM bytes per train step (parsed "
                      "from optimized HLO)").set(float(b), config=config,
                                                 op_class=cls)
    monitor.gauge("hbm_profile_total_bytes",
                  "total parsed HBM bytes per train step").set(
                      float(total), config=config)


def main() -> int:
    config = sys.argv[1] if len(sys.argv) > 1 else "resnet"
    top_n = int(sys.argv[2]) if len(sys.argv) > 2 else 15
    # CPU-posture audit: compile the program the TPU default policy would
    # run unless the caller pinned a mode (DL4J_TPU_PRECISION=fp32 gives
    # the fp32 comparison row).
    os.environ.setdefault("DL4J_TPU_PRECISION", "mixed_bf16")
    from deeplearning4j_tpu.nn import precision
    pol = precision.named_policy(precision.env_mode())
    print(f"# precision policy: {pol.describe()}")
    compiled, _net = compiled_step(config)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo = compiled.as_text()
    all_rows, total, _by_dtype = profile_hlo(hlo)
    rows = all_rows[:top_n]
    print(f"# {config}: top {top_n} HBM-consuming ops "
          f"(parsed {total/1e6:.0f} MB/step; XLA cost model "
          f"{cost.get('bytes accessed', 0)/1e6:.0f} MB/step)")
    # compiler self-reported totals next to the parsed numbers — the
    # same xla_cost_* series the compile-watch publishes for every
    # executable (the AOT compile above already fed the gauges)
    try:
        mem = compiled.memory_analysis()
        peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        print(f"# cost model: {cost.get('flops', 0)/1e9:.2f} GFLOP/step; "
              f"memory_analysis peak HBM {peak/1e6:.0f} MB "
              f"(args {mem.argument_size_in_bytes/1e6:.0f} + outputs "
              f"{mem.output_size_in_bytes/1e6:.0f} + temps "
              f"{mem.temp_size_in_bytes/1e6:.0f} - aliased "
              f"{mem.alias_size_in_bytes/1e6:.0f})")
    except Exception:
        pass
    print(f"{'MB':>8}  {'%':>5}  {'class':<8} {'kind':<14} shape")
    by_class = defaultdict(int)
    for b, kind, name, shape in rows:
        cls = _classify(kind, name, shape)
        print(f"{b/1e6:8.1f}  {100*b/total:5.1f}  {cls:<8} {kind:<14} "
              f"{shape[:60]}  {name[:40]}")
    # class totals over ALL instructions, not just top-n
    for b, kind, name, shape in all_rows:
        by_class[_classify(kind, name, shape)] += b
    print("\n# traffic by op class (all instructions)")
    for cls, b in sorted(by_class.items(), key=lambda kv: -kv[1]):
        print(f"{b/1e6:8.1f} MB  {100*b/total:5.1f}%  {cls}")
    # the scatter-row audit line the embedding configs exist for: how
    # many distinct scatter-add sites the step issues (counted from HLO
    # metadata (op_name, source_line) — robust to CPU lowering scatters
    # into loop fusions), and whether the program carries the
    # sorted/unique promises that unlock the non-colliding path
    sites = set()
    for m in re.finditer(r"metadata=\{([^}]*)\}", hlo):
        md = m.group(1)
        op = re.search(r'op_name="([^" ]*)', md)
        if op and "scatter-add" in op.group(1):
            ln = re.search(r"source_line=(\d+)", md)
            sites.add((op.group(1), ln.group(1) if ln else "?"))
    if sites:
        print(f"\n# scatter audit: {len(sites)} scatter-add site(s) per "
              f"step; {hlo.count('unique_indices=true')} instruction(s) "
              f"marked unique_indices=true")
    register_monitor_gauges(config, by_class, total)
    if config in _NET_CONFIGS and pol.name != "fp32":
        _policy_comparison(config, pol,
                           float(cost.get("bytes accessed", 0.0)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
