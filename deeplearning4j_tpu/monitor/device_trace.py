"""The step program's work by name: scopes going in, device seconds by
scope coming out.

Going in.  Both containers annotate their step programs with
``jax.named_scope`` through :func:`scope`, under one grammar.  Each scope
is a single path component of the HLO ``op_name`` (no ``/`` inside):

=================  =====================================================
``layer.<name>``   one layer (``MultiLayerNetwork``: ``<index>_<Class>``)
                   or vertex (``ComputationGraph``: the vertex's name)
``loss``           the output layers' ``compute_score``
``reg``            the l1/l2 score term (``_reg_score``)
``update.<name>``  one layer's ``apply_layer_updates``: updater rule,
                   masters, bf16 re-derivation
``ingest.gather``  the minibatch gather (+ wire decode) of the
                   epoch-cache step
``health``         ``layer_stats`` + ``guard_select``
``precision.cast`` parameters and inputs cast to the compute dtype
=================  =====================================================

JAX wraps a scope in the transformations it was traced under, so one
scope per layer gives layer AND pass: ``jvp(layer.X)`` is the forward
pass under ``value_and_grad``, ``transpose(jvp(layer.X))`` the backward
pass, a bare ``layer.X`` the forward pass of ``output()``; what lies
outside the differentiated function (gather, update, reg, health) stays
bare.  Scopes are metadata only: JAX's compilation-cache key strips
debug info, so they change no key, no executable and no number, and an
executable cached BEFORE the scopes existed is served with its old
metadata (delete the cache directory once to see new scopes).

Coming out.  :func:`reduce` turns any ``.xplane.pb`` of the JAX
profiler into device seconds by ``(scope, pass)``.  This libtpu's device
events carry only a time and the HLO line as their name; the same file
holds, in plane ``/host:metadata``, the scheduled HLO module of every
traced program with each instruction's ``op_name``.
``jax.profiler.ProfileData`` does not expose those, a protobuf wire walk
does (:func:`hlo_modules`).  :func:`device_trace` is the one way the
program takes a trace::

    with monitor.device_trace(log_dir) as trace:
        net.fit(iterator, epochs=E); net.score()
    trace.report["by_group"]

``python -m deeplearning4j_tpu.monitor.device_trace <dir or file>``
prints the table of a trace taken earlier (``.xplane.pb`` or
``.xplane.pb.gz``).

Note: ``monitor.device_trace`` is the context manager (it shadows this
submodule as an attribute of the package); reach the functions here
with ``from deeplearning4j_tpu.monitor.device_trace import reduce``.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import gzip
import os
import re
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

from .metrics import registry
from .tracing import current_context, new_trace_id, tracer

# ----------------------------------------------------------------- grammar
GROUPS = ("layer", "update", "loss", "reg", "ingest", "health", "precision")
UNSCOPED = "unscoped"
PASSES = ("forward", "backward", "other")
#: bare scopes of these groups are a forward pass (``output()``, the
#: engine's buckets); every other bare scope is ``other``
_FORWARD_GROUPS = ("layer", "loss", "precision")
#: the host span that bounds a traced session (``DeviceTrace`` opens it)
SESSION_SPAN = "profiler/capture"

_WRAPPED = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$")
#: what a program span looks like on the profiler's host plane: the
#: ring's names (``fit/dispatch``, ``jit/compile/cg.train_step``), which
#: no event of the runtime's own resembles
_PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*(/[^\s/()]+)+$")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[float, float]


_open_scopes = threading.local()


def scope(group: str, name: Optional[str] = None):
    """``jax.named_scope`` of the grammar: ``<group>`` or
    ``<group>.<name>``, one path component."""
    if group not in GROUPS:
        raise ValueError(f"scope group {group!r} is not one of {GROUPS}")
    return _named(
        (group if name is None else f"{group}.{name}").replace("/", "_"))


@contextlib.contextmanager
def _named(label: str):
    import jax
    stack = _open_scopes.__dict__.setdefault("stack", [])
    stack.append(label)
    try:
        with jax.named_scope(label):
            yield
    finally:
        stack.pop()


def subscope(part: str):
    """A named part of the scope that is open on this thread:
    ``<group>.<name>.<part>``, again one path component, so the
    innermost rule of :func:`parse_op_name` gives the part a row of its
    own (``layer.L3_moe.experts``).  A layer calls it without knowing
    the name its container gave it; with no scope open it is the bare
    ``layer.<part>``."""
    stack = getattr(_open_scopes, "stack", None)
    outer = stack[-1] if stack else "layer"
    return scope(*f"{outer}.{part}".split(".", 1))


def parse_op_name(op_name: str) -> Tuple[str, str]:
    """``(scope, pass)`` of one HLO ``op_name``.  XLA joins the names of
    merged instructions with ``;``: the first wins.  The innermost
    component of the grammar names the scope; nothing of the grammar
    gives ``("unscoped", "other")``."""
    found = None
    for part in op_name.split(";", 1)[0].split("/"):
        wrappers = []
        m = _WRAPPED.match(part)
        while m:
            wrappers.append(m.group(1))
            part = m.group(2)
            m = _WRAPPED.match(part)
        if "jit" in wrappers or "pjit" in wrappers:
            continue                    # a jitted function named ``loss``
        if part.split(".", 1)[0] in GROUPS:
            found = (part, wrappers)
    if found is None:
        return UNSCOPED, "other"
    name, wrappers = found
    if "transpose" in wrappers:
        return name, "backward"
    if "jvp" in wrappers or name.split(".", 1)[0] in _FORWARD_GROUPS:
        return name, "forward"
    return name, "other"


# ----------------------------------------------------- protobuf wire walk
def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one serialized message: varints as
    ints, length-delimited fields as sub-views, fixed fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            value, i = _varint(buf, i)
            yield tag >> 3, value
        elif kind == 2:
            size, i = _varint(buf, i)
            yield tag >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")


def _all(buf, number: int) -> List:
    return [v for k, v in _fields(buf) if k == number]


def _one(buf, number: int):
    return next((v for k, v in _fields(buf) if k == number), b"")


def _text(buf, number: int) -> str:
    return bytes(_one(buf, number)).decode("utf-8", "replace")


def _packed(value) -> List[int]:
    """A repeated varint field: packed into one buffer, or one value."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def read_xspace(path: str) -> bytes:
    """The serialized ``XSpace`` at ``path`` (``.gz`` is unpacked)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        return fh.read()


#: how far a nameless instruction looks along its users for a name
_INHERIT_DEPTH = 4


def hlo_modules(path: str) -> Dict[str, Dict[str, Tuple]]:
    """``{module event name: {instruction name: (opcode, op_name,
    groups inside, inherited)}}`` of every program the trace at ``path``
    holds.  ``groups inside`` are the scope groups (``update``,
    ``health``, ...) of the instructions a ``fusion`` fuses, which is
    how work that never runs as a kernel of its own can still be found.
    An instruction XLA made without an ``op_name`` (a layout ``copy``,
    the ``copy-start``/``copy-done`` of a prefetch, an ``async-done``)
    takes the ``op_name`` of the first instruction with one among its
    users, looking up to four users along: the copy exists for its
    consumer; ``inherited`` says so.  Field numbers:
    ``XSpace.planes``=1; ``XPlane.name``=2, ``event_metadata``=4 (map
    entry: value=2); ``XEventMetadata.name``=2 (``jit_f(<fingerprint>)``,
    as on the ``XLA Modules`` line), ``stats``=5; the one bytes value
    (6) of an ``XStat`` is an ``HloProto``: ``hlo_module``=1,
    ``computations``=3 (``id``=5), ``instructions``=2, ``name``=1,
    ``opcode``=2, ``metadata``=7, ``op_name``=2, ``id``=35,
    ``operand_ids``=36, ``called_computation_ids``=38.  Instruction
    names are unique in a module, fused computations included."""
    return _hlo_modules(read_xspace(path))


def _hlo_modules(xspace: bytes) -> Dict[str, Dict[str, Tuple]]:
    space = memoryview(xspace)
    modules: Dict[str, Dict[str, Tuple]] = {}
    group_of: Dict[str, str] = {}

    def group(op_name: str) -> str:
        if op_name not in group_of:
            group_of[op_name] = parse_op_name(op_name)[0].split(".", 1)[0]
        return group_of[op_name]

    for plane in _all(space, 1):
        if _text(plane, 2) != "/host:metadata":
            continue
        for entry in _all(plane, 4):
            meta = _one(entry, 2)
            for stat in _all(meta, 5):
                module = _one(_one(stat, 6), 1)
                if not len(module):
                    continue
                table = modules.setdefault(_text(meta, 2), {})
                inside: Dict[int, set] = {}
                fusions = []
                for computation in _all(module, 3):
                    groups = inside.setdefault(_one(computation, 5) or 0,
                                               set())
                    by_id: Dict[int, str] = {}
                    users: Dict[int, List[int]] = {}
                    nameless = []
                    for ins in _all(computation, 2):
                        name, opcode = _text(ins, 1), _text(ins, 2)
                        op_name = _text(_one(ins, 7), 2)
                        ident = _one(ins, 35) or 0
                        table[name] = (opcode, op_name, (), False)
                        by_id[ident] = name
                        for operand in _packed(_one(ins, 36)):
                            users.setdefault(operand, []).append(ident)
                        if op_name:
                            groups.add(group(op_name))
                        elif opcode != "parameter":
                            nameless.append((name, ident))
                        if opcode == "fusion":
                            fusions.append((name, _packed(_one(ins, 38))))
                    for name, ident in nameless:
                        frontier = [ident]
                        for _ in range(_INHERIT_DEPTH):
                            frontier = [u for i in frontier
                                        for u in users.get(i, ())]
                            found = next((table[by_id[u]][1]
                                          for u in frontier
                                          if table[by_id[u]][1]
                                          and not table[by_id[u]][3]), "")
                            if found or not frontier:
                                break
                        if found:
                            table[name] = (table[name][0], found, (), True)
                for name, called in fusions:
                    opcode, op_name, _, inherited = table[name]
                    held = set().union(*(inside.get(c, ()) for c in called))
                    table[name] = (opcode, op_name, tuple(sorted(
                        held - {UNSCOPED, group(op_name)})), inherited)
    return modules


# ------------------------------------- the compiler's costs, by instruction
#: the memory space of ``memory_access_breakdown`` that is HBM (xprof's
#: ``MemorySpace``: 1 HBM, 2 CMEM, 3 VMEM, which is where a layout's
#: ``S(1)`` places an array on a v5e); operation 1 reads, 2 writes
HBM_SPACE = 1
COUNTED, ESTIMATED, UNCOUNTED = "counted", "estimated", "uncounted"
#: ``(flops, hbm_read_bytes, hbm_write_bytes, other_bytes, hlo_category,
#: how)`` of one instruction; ``how`` is one of the three above
Cost = Tuple[float, float, float, float, str, str]
_NOTHING = (0.0, 0.0, 0.0, 0.0)

_PROGRAM_ID = re.compile(r"\((\d+)\)$")
_ARRAY = re.compile(r"\b(pred|token|[a-z]+\d+[a-z0-9]*)\[([\d,]*)\]"
                    r"(\{[^{}]*\})?")
_ON_CHIP = re.compile(r"S\(\d+\)")
_ALIASED = re.compile(r"\{([\d, ]*)\}: \((\d+), \{[\d, ]*\}\)")
#: instructions whose stats are those of the computations they call
_CONTROL_FLOW = ("while", "conditional", "call")


def _closing(text: str, i: int) -> int:
    """Index after the ``)`` that closes the ``(`` at ``text[i]``; -1
    where the line was cut before it."""
    depth = 0
    for j in range(i, len(text)):
        c = text[j]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return -1


def parse_hlo_line(line: str) -> Optional[Tuple[str, str, str, str]]:
    """``(result shapes, opcode, operands, attributes)`` of one HLO line
    as the trace names a device event (``%name = <result> opcode(
    <operands>), <attributes>``), each as text; ``None`` where the line
    does not read so."""
    _, sep, rest = line.partition(" = ")
    if not sep:
        return None
    if rest.startswith("("):
        j = _closing(rest, 0)
    else:
        j = rest.find(" ")
    k = rest.find("(", j) if j > 0 else -1
    end = _closing(rest, k) if k > 0 else -1
    if end < 0:
        return None
    return (rest[:j], rest[j:k].strip(), rest[k + 1:end - 1],
            rest[end:].lstrip(", "))


def _hbm_arrays(text: str) -> List[float]:
    """Bytes of each array shape in ``text``, 0 for one whose layout
    places it in an ``S(n)`` space (on the chip, not in HBM)."""
    out = []
    for dtype, dims, layout in _ARRAY.findall(text):
        bits = re.search(r"\d+", dtype)
        size = (int(bits.group()) if bits else 8 * (dtype == "pred")) / 8.0
        for d in dims.split(","):
            size *= int(d) if d else 1
        out.append(0.0 if layout and _ON_CHIP.search(layout) else size)
    return out


#: how the HLO line of a Pallas kernel names its target
_MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def estimate_cost(line: str, category: str = "") -> Cost:
    """What an instruction without the compiler's numbers moves through
    HBM, from its HLO line alone: every operand and every result that
    the layout does not place in an ``S(n)`` space, counted whole and
    once (a result aliased to an operand,
    ``output_to_operand_aliasing``, is that operand again).  A Pallas
    kernel's ``tpu_custom_call`` reads so: "the ring at its capacity,
    read once"; one that fetches part of an operand it leaves in HBM
    declares its bytes instead (``cost_estimate=pl.CostEstimate(...)``,
    which the trace carries as the instruction's ``bytes_accessed``
    beside a breakdown that still holds every operand whole:
    :func:`instruction_costs` takes the declared count, less what the
    breakdown says is written, as what was read).  The ``-done`` of an asynchronous pair adds nothing (the
    ``-start`` holds the fetch).  No operations are guessed.  A line
    that cannot be read is ``uncounted``."""
    parts = parse_hlo_line(line)
    if parts is None:
        return _NOTHING + (category, UNCOUNTED)
    result, opcode, operands, attributes = parts
    if opcode.endswith("-done"):
        return _NOTHING + (category or opcode, ESTIMATED)
    written = _hbm_arrays(result)
    for out_index, _ in _ALIASED.findall(attributes):
        first = out_index.split(",")[0].strip()
        i = int(first) if first else 0
        if i < len(written):
            written[i] = 0.0
    return (0.0, sum(_hbm_arrays(operands)), sum(written), 0.0,
            category or opcode, ESTIMATED)


def instruction_costs(path: str) -> Dict[int, Dict[str, Cost]]:
    """``{program id: {HLO line: (flops, hbm_read_bytes,
    hbm_write_bytes, other_bytes, hlo_category, how)}}``: the
    compiler's own cost analysis of every instruction, which each
    ``/device:TPU:<n>`` plane carries as the stats of its
    ``XEventMetadata`` (``jax.profiler.ProfileData`` shows an event's own
    stats only).  The HLO line is the event's name on ``XLA Ops``; the
    program id is the number in the name of the ``XLA Modules`` event
    that contains it (two programs can hold the same line).  Field
    numbers: ``XPlane.event_metadata``=4 and ``stat_metadata``=5 (map
    entries: value=2); ``XStatMetadata.id``=1, ``name``=2;
    ``XEventMetadata.name``=2, ``stats``=5; ``XStat.metadata_id``=1,
    ``uint64_value``=3, ``int64_value``=4, ``str_value``=5,
    ``bytes_value``=6.  Stats read, by name: ``program_id``, ``flops``,
    ``bytes_accessed``, ``hlo_category`` and ``memory_access_breakdown``
    (a ``MemoryAccessBreakdown``: ``memory_accessed``=1, each with
    ``operation_type``=1 (1 read, 2 write), ``memory_space``=2,
    ``bytes_accessed``=3).  HBM is space 1; space 3 is where a layout's
    ``S(1)`` places an array, on the chip.  So bytes are counted once
    without further care: a ``copy-start`` / ``slice-start`` reads its
    source from space 1, its ``-done`` writes space 3 and the consumer
    reads space 3; in ``small_trace`` each ``copy-start`` has a second
    entry for the ``Async XLA Ops`` line with the same numbers, which
    the reduction never reads (leaf events of ``XLA Ops`` alone count).
    A ``tpu_custom_call`` whose ``bytes_accessed`` is under its
    breakdown's HBM bytes declared that count itself, and it is taken
    (the gathered sparse attention fetches 33.6 MB of a 537 MB ring).
    ``how``: ``counted`` from these stats; ``estimated`` where they are
    absent or all zero though the instruction has operands (custom
    calls: :func:`estimate_cost`); ``uncounted`` where there is nothing
    to go by.  A ``while``, ``conditional`` or ``call`` carries the
    stats of its body and counts nothing itself: where its body ran, the
    body's own events are the leaves; where it is a leaf (``ax_k1``'s
    loop over further rounds of a share's experts, which runs none) it
    did none of what its stats say."""
    return _instruction_costs(read_xspace(path))


def _instruction_costs(xspace: bytes) -> Dict[int, Dict[str, Cost]]:
    programs: Dict[int, Dict[str, Cost]] = {}
    for plane in _all(memoryview(xspace), 1):
        if not _DEVICE_PLANE.match(_text(plane, 2)):
            continue
        stat_names = {_one(meta, 1): _text(meta, 2)
                      for meta in (_one(entry, 2)
                                   for entry in _all(plane, 5))}
        for entry in _all(plane, 4):
            meta = _one(entry, 2)
            stats = {}
            for stat in _all(meta, 5):
                fields = dict(_fields(stat))
                stats[stat_names.get(fields.get(1))] = fields
            if "program_id" not in stats:
                continue
            line = _text(meta, 2)
            number = lambda name: next(
                (v for k, v in stats.get(name, {}).items()
                 if k in (3, 4)), 0)
            category = bytes(stats.get("hlo_category", {}).get(
                5, b"")).decode("utf-8", "replace")
            read = write = other = 0.0
            for accessed in _all(stats.get(
                    "memory_access_breakdown", {}).get(6, b""), 1):
                access = dict(_fields(accessed))
                size = float(access.get(3, 0))
                if access.get(2) != HBM_SPACE:
                    other += size
                elif access.get(1) == 2:
                    write += size
                else:
                    read += size
            flops = float(number("flops"))
            declared = float(number("bytes_accessed"))
            if _MOSAIC_CALL in line and 0 < declared < read + write:
                # a Pallas kernel that said what it moves
                # (``pl.CostEstimate``): the breakdown beside the
                # declared count is still every operand whole
                read = max(declared - write, 0.0)
            if category in _CONTROL_FLOW:
                # its stats are its body's, whose own events count: as
                # a leaf (a loop of no round) it did none of it
                cost = _NOTHING + (category, COUNTED)
            elif flops or read or write or other \
                    or number("bytes_accessed"):
                cost = (flops, read, write, other, category, COUNTED)
            else:
                parts = parse_hlo_line(line)
                if parts and not parts[2].strip():
                    # nothing in, nothing counted: an ``AllocateBuffer``
                    cost = _NOTHING + (category, COUNTED)
                else:
                    cost = estimate_cost(line, category)
            programs.setdefault(number("program_id"), {})[line] = cost
    return programs


# ------------------------------------------------------ interval arithmetic
def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _total(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _leaves(ops: List[Tuple[str, float, float]]) -> List:
    """Events of one line that contain no other event (a ``while`` spans
    its body, gaps and all).  Events on a line nest properly, so in
    start order, longest first on a tie, an event is a container exactly
    when the next one starts before it ends."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [op for i, op in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][1] >= op[2]
            or ops[i + 1][2] > op[2]]


def _split_gaps(busy: List[Interval], lo: float, hi: float,
                spans: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """The complement of ``busy`` in ``[lo, hi]`` split among the spans
    that cover it, piece by piece, the innermost (shortest) first; what
    no span covers is ``no_span``."""
    spans = sorted(spans, key=lambda s: s[2] - s[1])
    out: Dict[str, float] = {}
    at = lo
    for a, b in busy + [(hi, hi)]:
        pieces = [(at, a)] if a > at else []
        at = max(at, b)
        for name, s, e in spans:
            if not pieces:
                break
            if e <= pieces[0][0] or s >= pieces[-1][1]:
                continue
            covered = _clip(pieces, s, e)
            if covered:
                out[name] = out.get(name, 0.0) + _total(covered)
                pieces = [p for x, y in pieces
                          for p in ((x, min(y, s)), (max(x, e), y))
                          if p[1] > p[0]]
        if pieces:
            out["no_span"] = out.get("no_span", 0.0) + _total(pieces)
    return out


#: what stands for a neighbour where a gap touches its program's edge
PROGRAM_START, PROGRAM_END = "program-start", "program-end"
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
#: operations that end a fetch: the ``-done`` of an asynchronous pair,
#: and the ``ConcatBitcast`` that joins the dones of a weight fetched in
#: slices.  XLA gave them no name, so their scope is their consumer's
_ENDS_A_FETCH = re.compile(r"-done$|^ConcatBitcast$")


def _kind(event_name: str, opcode: str) -> str:
    """What an operation is called in ``gaps_in_program``: its opcode,
    but a custom call by its target (``ConcatBitcast``,
    ``tpu_custom_call``) and an ``async-start`` / ``async-done`` by the
    stem of its name (``slice-start``, ``slice-done``), which say what
    the opcode leaves out."""
    if opcode == "custom-call":
        target = _TARGET.search(event_name)
        return target.group(1) if target else opcode
    if opcode.startswith("async-"):
        return _instruction(event_name).split(".", 1)[0]
    return opcode


def _gaps_in_programs(ops: List[Tuple[str, float, float]], keys: List[Tuple],
                      launched: List[Tuple[float, float, str]],
                      lo: float, hi: float) -> Dict[Tuple[str, str], List]:
    """``{(scope that waited, kind of the operation before the gap):
    [seconds, gaps]}`` of one device's gaps that lie inside a program
    (an ``XLA Modules`` event of ``launched``), clipped to ``[lo, hi]``.
    ``ops`` are the leaf events in start order, ``keys`` their ``(scope,
    pass, opcode, ...)``.  A zero-length event (a ``ConcatBitcast``)
    fills no time, so it splits a gap and the wait after it bears its
    name.  Who waited: the operation that ends the gap, by its scope;
    but where the operation BEFORE the gap ends a fetch (``copy-done``,
    ``slice-done``, ``ConcatBitcast``), the consumer that fetch was
    made for, which is the scope such a nameless operation inherits: on
    the v5e the wait for a prefetched weight shows as a gap AFTER the
    done, and what follows the gap is as often the start of the next
    prefetch as the consumer itself.  Where a gap touches its program's
    edge the neighbour is ``program-start`` or ``program-end``."""
    out: Dict[Tuple[str, str], List] = {}
    starts = [s for s, _, _ in launched]
    at, before = lo, None                   # index of the leaf before
    for i in range(len(ops) + 1):
        s, e = (ops[i][1], ops[i][2]) if i < len(ops) else (hi, hi)
        a, b = max(at, lo), min(s, hi)
        if b > a:
            j = max(bisect.bisect_right(starts, a) - 1, 0)
            while j < len(launched) and launched[j][0] < b:
                ms, me, _ = launched[j]
                j += 1
                seconds = min(b, me) - max(a, ms)
                if seconds <= 0:
                    continue
                inside = lambda k: k is not None and k < len(ops) \
                    and ms <= ops[k][1] < me
                kind = _kind(ops[before][0], keys[before][2]) \
                    if inside(before) else PROGRAM_START
                if _ENDS_A_FETCH.search(kind):
                    waited = keys[before][0]
                else:
                    waited = keys[i][0] if inside(i) else PROGRAM_END
                row = out.setdefault((waited, kind), [0.0, 0])
                row[0] += seconds
                row[1] += 1
        if i < len(ops) and e >= at:
            at, before = e, i
    return out


# ---------------------------------------------------------------- reduction
def find_trace(path: str) -> Optional[str]:
    """``path`` itself if it is a file, else the newest ``.xplane.pb``
    under it."""
    if os.path.isfile(path):
        return path
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _instruction(event_name: str) -> str:
    """``%fusion.8 = bf16[...] fusion(...)`` -> ``fusion.8``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce(path: str, window: str = SESSION_SPAN) -> Optional[Dict]:
    """Device seconds by scope from the trace at ``path`` (a file, or a
    directory whose newest ``.xplane.pb`` is taken); ``None`` when it
    holds no TPU operation.

    Per TPU plane, the leaf events of ``XLA Ops`` are clipped to the
    traced session: the host span named ``window`` where the file has
    one (``DeviceTrace`` opens ``profiler/capture``; the benchmark's
    traces have ``bench/window``), else the extent of the device's own
    events.  Each leaf's instruction is looked up in the HLO module of
    the ``XLA Modules`` event that contains it and its ``op_name`` (its
    consumer's, where XLA gave it none: :func:`hlo_modules`;
    ``inherited_s`` is how many of the scoped seconds came that way)
    parsed by :func:`parse_op_name`; no module, no ``op_name`` or no scope
    of the grammar goes to ``unscoped``, never dropped.  Seconds are means
    over the TPU planes, event counts are totals.  Returns ``busy_s``,
    ``window_s``, ``idle_share``, ``devices``, ``by_scope`` (``[scope,
    pass, seconds, events]``, every row, largest first: they sum to
    ``busy_s``), ``by_pass``, ``by_group``, ``fused_in_by_group`` (a
    fusion's time goes to the scope of its own ``op_name``; this is, per
    group, the seconds of kernels that went to ANOTHER group and fuse
    instructions of this one: XLA fuses the updater into the
    weight-gradient kernels, where no time can be split off for it),
    ``unscoped_by_opcode`` (``[opcode, seconds, events]``) and
    ``idle_by_span``: the first
    device's idle time split among the program's spans
    (``monitor.span`` names on the profiler's host planes) that cover
    it, innermost first, ``no_span`` for the rest.

    Costs (PR 39), from the compiler's own numbers for each instruction
    (:func:`instruction_costs`), an event matched by the program that
    contains it and its HLO line: ``cost_by_scope`` (``[scope, pass,
    seconds, hbm_read_bytes, hbm_write_bytes, flops,
    estimated_seconds]``, the rows and order of ``by_scope``; a nameless
    copy's bytes go to its consumer's scope as its seconds do; an event
    clipped by the window counts its part), ``hbm_bytes`` and ``flops``
    (their sums; means over the TPU planes like the seconds),
    ``estimated_s`` (seconds of instructions the compiler did not count,
    custom calls, whose bytes are read off their operands' shapes:
    :func:`estimate_cost`) and ``uncounted_s`` (seconds with no count at
    all).  A prefetch is charged where it is STARTED and runs under
    whatever follows, so a row can read more bytes a second than the
    chip moves; the whole cannot.

    Idle time by cause (PR 39), from the device's own two lines, so the
    ~1 ms lead of its clock over the host's cannot move it: a gap
    between leaf operations is *in a program* where an ``XLA Modules``
    event covers it, else *between programs*.  ``idle_in_program_s`` and
    ``idle_between_programs_s`` (means over the TPU planes; they sum to
    ``window_s - busy_s``), and for the first device
    ``gaps_in_program`` (``[scope that waited, kind of the operation
    before the gap, seconds, gaps]``, largest first:
    :func:`_gaps_in_programs`; a wait for a weight fetched in slices
    reads ``[layer.L3_attn.latent_attention, ConcatBitcast, ...]``, the
    gap before a program's first operation ``program-start`` in the
    second place) and ``idle_between_by_span`` (the between-programs
    part alone, split among the host's spans as ``idle_by_span`` is:
    only this part can a change to the host recover)."""
    from jax.profiler import ProfileData
    path = find_trace(path)
    if path is None:
        return None
    t0 = time.perf_counter()
    devices: Dict[int, Dict[str, List]] = {}
    spans: List[Tuple[str, float, float]] = []
    xspace = read_xspace(path)
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"].extend(_leaves([
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]))
                elif line.name == "XLA Modules":
                    dev["modules"].extend(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if _PROGRAM_SPAN.match(ev.name):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    devices = {k: v for k, v in devices.items() if v["ops"]}
    if not devices:
        return None
    sessions = [(s, e) for name, s, e in spans if name == window]
    if sessions:
        lo, hi = min(s for s, _ in sessions), max(e for _, e in sessions)
    else:
        lo = min(s for d in devices.values() for _, s, _ in d["ops"])
        hi = max(e for d in devices.values() for _, _, e in d["ops"])
    spans = [s for s in spans if s[0] != window]

    modules = _hlo_modules(xspace)
    costs = _instruction_costs(xspace)
    n_dev = len(devices)
    rows: Dict[Tuple[str, str], List[float]] = {}
    opcodes: Dict[str, List[float]] = {}
    fused_in = {g: 0.0 for g in GROUPS}
    busy_s = inherited_s = uncounted_s = between_s = 0.0
    first = min(devices)
    for index, dev in devices.items():
        launched = sorted(dev["modules"])
        starts = [s for s, _, _ in launched]
        lookup: Dict[Tuple[str, str], Tuple] = {}
        keys = []                           # of each leaf, for the gaps
        busy = _union(_clip([(s, e) for _, s, e in dev["ops"]], lo, hi))
        busy_s += _total(busy)
        for event_name, s, e in dev["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            module = launched[i][2] if i >= 0 and s < launched[i][1] else ""
            key = lookup.get((module, event_name))
            if key is None:
                opcode, op_name, inside, inherited = \
                    modules.get(module, {}).get(
                        _instruction(event_name), ("", "", (), False))
                program = _PROGRAM_ID.search(module)
                cost = costs.get(int(program.group(1)) if program else 0,
                                 {}).get(event_name) \
                    or estimate_cost(event_name)
                if not opcode:      # no module: the line names it itself
                    opcode = (parse_hlo_line(event_name)
                              or ("", "unknown"))[1]
                key = lookup[(module, event_name)] = parse_op_name(
                    op_name) + (opcode, inside, inherited, cost)
            keys.append(key)
            seconds = min(e, hi) - max(s, lo)
            flops, read, written, _, _, how = key[5]
            if seconds <= 0:
                continue        # (a zero-length event carries no count)
            part = seconds / (e - s) / n_dev        # of a clipped event
            # [seconds, events, read, written, operations, estimated s]
            row = rows.setdefault(key[:2], [0.0, 0, 0.0, 0.0, 0.0, 0.0])
            row[0] += seconds / n_dev
            row[1] += 1
            row[2] += read * part
            row[3] += written * part
            row[4] += flops * part
            if how == ESTIMATED:
                row[5] += seconds / n_dev
            elif how == UNCOUNTED:
                uncounted_s += seconds / n_dev
            if key[0] == UNSCOPED:
                row = opcodes.setdefault(key[2], [0.0, 0])
                row[0] += seconds / n_dev
                row[1] += 1
            for group in key[3]:
                fused_in[group] += seconds / n_dev
            if key[4] and key[0] != UNSCOPED:
                inherited_s += seconds / n_dev
        # idle while no program ran: what neither an operation nor a
        # program covers
        covered = _union(busy + _clip([(s, e) for s, e, _ in launched],
                                      lo, hi))
        between_s += (hi - lo) - _total(covered)
        if index == first:
            idle = _split_gaps(busy, lo, hi, spans)
            idle_between = _split_gaps(covered, lo, hi, spans)
            gaps = _gaps_in_programs(dev["ops"], keys, launched, lo, hi)
    busy_s /= n_dev
    between_s /= n_dev
    order = sorted(rows.items(), key=lambda kv: -kv[1][0])
    by_scope = [[name, pass_, row[0], row[1]]
                for (name, pass_), row in order]
    cost_by_scope = [[name, pass_, row[0], row[2], row[3], row[4], row[5]]
                     for (name, pass_), row in order]
    by_pass = {p: 0.0 for p in PASSES}
    by_group = {g: 0.0 for g in GROUPS + (UNSCOPED,)}
    for name, pass_, sec, _ in by_scope:
        by_pass[pass_] += sec
        by_group[name.split(".", 1)[0]] += sec
    largest = lambda pairs: sorted(pairs, key=lambda r: -r[-1])
    return {
        "path": path,
        "devices": n_dev,
        "window_s": hi - lo,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (hi - lo) if hi > lo else 0.0,
        "by_scope": by_scope,
        "by_pass": by_pass,
        "by_group": by_group,
        "fused_in_by_group": fused_in,
        "inherited_s": inherited_s,
        "unscoped_by_opcode": sorted(
            ([k, sec, n] for k, (sec, n) in opcodes.items()),
            key=lambda r: -r[1]),
        "idle_by_span": largest([k, v] for k, v in idle.items()),
        "cost_by_scope": cost_by_scope,
        "hbm_bytes": sum(r[3] + r[4] for r in cost_by_scope),
        "flops": sum(r[5] for r in cost_by_scope),
        "estimated_s": sum(r[6] for r in cost_by_scope),
        "uncounted_s": uncounted_s,
        "idle_in_program_s": (hi - lo) - busy_s - between_s,
        "idle_between_programs_s": between_s,
        "gaps_in_program": sorted(
            ([scope, before, sec, n]
             for (scope, before), (sec, n) in gaps.items()),
            key=lambda r: -r[2]),
        "idle_between_by_span": largest(
            [k, v] for k, v in idle_between.items()),
        "reduce_s": time.perf_counter() - t0,
    }


def table(report: Dict, top: int = 15) -> str:
    """The report as text: shares of busy time by pass and by group, the
    ``top`` largest ``(scope, pass)`` rows, each with the HBM traffic and
    the operations the compiler counted for it over its seconds, and the
    idle time by cause and by span."""
    busy = report["busy_s"] or 1.0
    share = lambda s: f"{s:10.6f} s {100.0 * s / busy:6.2f}%"
    rate = lambda amount, s, unit: f"{amount / s / unit:8.1f}" if s else \
        f"{'-':>8}"
    out = [f"{report['path']}",
           f"devices {report['devices']}  window {report['window_s']:.6f} s"
           f"  busy {report['busy_s']:.6f} s  idle "
           f"{100.0 * report['idle_share']:.2f}%  (reduced in "
           f"{report['reduce_s']:.2f} s)",
           f"HBM traffic {report['hbm_bytes'] / 1e9:.4f} GB, "
           f"{rate(report['hbm_bytes'], report['busy_s'], 1e9).strip()} GB/s "
           f"while busy; {report['flops'] / 1e12:.4f} TFLOP, "
           f"{rate(report['flops'], report['busy_s'], 1e12).strip()} "
           f"TFLOP/s; estimated from shapes {share(report['estimated_s'])}"
           f"; uncounted {share(report['uncounted_s'])}",
           "by pass:"]
    out += [f"  {k:<28}{share(v)}" for k, v in report["by_pass"].items()]
    out.append("by group (own kernels | kernels of other groups that "
               "fuse its instructions):")
    out += [f"  {k:<28}{share(v)}"
            + (f"  | {share(report['fused_in_by_group'][k])}"
               if report["fused_in_by_group"].get(k) else "")
            for k, v in report["by_group"].items()]
    out.append(f"  of the scoped seconds {share(report['inherited_s'])} are "
               "nameless copies and async dones charged to their consumer")
    out.append(f"top {top} of {len(report['by_scope'])} (scope, pass) rows "
               "(HBM GB/s read + written, TFLOP/s; * over 1% of the row's "
               "seconds have bytes estimated from shapes):")
    out += [f"  {name:<40}{pass_:<9}{share(sec)}  {n:6d} events"
            f"  {rate(read + written, sec, 1e9)} GB/s"
            f"  {rate(flops, sec, 1e12)} TFLOP/s"
            f"{' *' if estimated > 0.01 * sec else ''}"
            for (name, pass_, sec, n), (_, _, _, read, written, flops,
                                        estimated)
            in zip(report["by_scope"][:top], report["cost_by_scope"])]
    if report["unscoped_by_opcode"]:
        out.append("unscoped by opcode:")
        out += [f"  {k:<28}{share(sec)}  {n} events"
                for k, sec, n in report["unscoped_by_opcode"][:top]]
    out.append("idle time of the first device by program span:")
    out += [f"  {k:<28}{v:10.6f} s" for k, v in report["idle_by_span"]]
    out.append(f"idle while a program ran {report['idle_in_program_s']:.6f}"
               f" s, between programs "
               f"{report['idle_between_programs_s']:.6f} s; the largest "
               "gaps inside programs (scope that waited, opcode before):")
    out += [f"  {scope:<40}{before:<22}{sec:10.6f} s  {n} gaps"
            for scope, before, sec, n in report["gaps_in_program"][:top]]
    out.append("idle time between programs by program span:")
    out += [f"  {k:<28}{v:10.6f} s"
            for k, v in report["idle_between_by_span"]]
    return "\n".join(out)


# ------------------------------------------------------------ taking a trace
class DeviceTrace:
    """One ``jax.profiler`` session that reduces itself when it stops.
    A context manager; ``ProfilerListener`` drives :meth:`start` and
    :meth:`stop` from its callbacks.  ``report`` is :func:`reduce`'s
    result (``None`` until stopped, and where the trace holds no TPU
    operation, as on the CPU)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.report: Optional[Dict] = None
        self.open = False
        self._t0 = 0.0
        self._ctx = None
        self._session = None

    def start(self) -> "DeviceTrace":
        if self.open:
            return self
        import jax.profiler
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the program's spans are TraceMe's
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.open = True
        self._t0 = time.time()
        self._ctx = current_context()
        self._session = jax.profiler.TraceAnnotation(SESSION_SPAN)
        self._session.__enter__()
        return self

    def stop(self) -> Optional[Dict]:
        """Close the session exactly once: a second call, or one after
        the profiler died with the run, does nothing and raises nothing
        over the original failure.  The window is recorded as a
        ``profiler/capture`` span of the ring."""
        if not self.open:
            return self.report
        self.open = False
        import jax.profiler
        self._session.__exit__(None, None, None)
        self._session = None
        try:
            jax.profiler.stop_trace()
            stopped = True
        except RuntimeError:
            stopped = False
        ctx = self._ctx
        tracer().record_span(
            SESSION_SPAN,
            trace_id=ctx.trace_id if ctx is not None else new_trace_id(),
            parent_id=ctx.span_id if ctx is not None else None,
            ts=self._t0, dur_ms=(time.time() - self._t0) * 1e3,
            log_dir=self.log_dir)
        if stopped:
            self.report = reduce(self.log_dir)
        if self.report is not None:
            publish(self.report)
        return self.report

    __enter__ = start

    def __exit__(self, *exc) -> None:
        self.stop()


def device_trace(log_dir: str) -> DeviceTrace:
    """``with monitor.device_trace(log_dir) as trace: ...`` — profile the
    body (device events and the program's spans; no Python tracer),
    reduce on exit, publish the gauges; ``trace.report`` has the
    numbers.  Listeners break ``fit``'s fused dispatch, so this, not
    ``ProfilerListener``, is how to profile the fused path."""
    return DeviceTrace(log_dir)


def publish(report: Dict) -> None:
    """The report as gauges: ``device_scope_seconds{scope,pass}``,
    ``device_scope_hbm_bytes{scope,pass}``, ``device_idle_seconds{span}``,
    ``device_idle_in_program_seconds``,
    ``device_idle_between_programs_seconds`` and ``device_busy_share`` of
    the last traced session."""
    reg = registry()
    seconds = reg.gauge("device_scope_seconds",
                        "device seconds by scope and pass in the last "
                        "traced session (mean over chips)")
    traffic = reg.gauge("device_scope_hbm_bytes",
                        "HBM bytes read and written by scope and pass in "
                        "the last traced session, as the compiler counted "
                        "them (mean over chips)")
    for name, pass_, sec, read, written, _, _ in report["cost_by_scope"]:
        seconds.set(sec, **{"scope": name, "pass": pass_})
        traffic.set(read + written, **{"scope": name, "pass": pass_})
    idle = reg.gauge("device_idle_seconds",
                     "idle seconds of the first chip in the last traced "
                     "session, by the program span that covers them")
    for name, sec in report["idle_by_span"]:
        idle.set(sec, span=name)
    reg.gauge("device_idle_in_program_seconds",
              "idle seconds of the last traced session that lay inside a "
              "running program: gaps of the compiler's schedule (mean over "
              "chips)").set(report["idle_in_program_s"])
    reg.gauge("device_idle_between_programs_seconds",
              "idle seconds of the last traced session in which no program "
              "ran: the host held the chip back (mean over chips)").set(
        report["idle_between_programs_s"])
    reg.gauge("device_busy_share",
              "share of the last traced session in which a device "
              "operation ran (mean over chips)").set(
        1.0 - report["idle_share"])


if __name__ == "__main__":
    import argparse
    import json
    ap = argparse.ArgumentParser(
        description="device seconds by scope from a profiler trace")
    ap.add_argument("path", help="an .xplane.pb file or a directory")
    ap.add_argument("--window", default=SESSION_SPAN,
                    help="host span that bounds the session")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    result = reduce(args.path, args.window)
    if result is None:
        raise SystemExit(f"no TPU operation in a trace under {args.path}")
    print(json.dumps(result) if args.json else table(result, args.top))
