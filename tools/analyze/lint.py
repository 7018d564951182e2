"""Project-native invariant linter: AST rules for the correctness
conventions twelve PRs of runtime growth rely on.

Rules (see ``docs/ANALYSIS.md`` for rationale and before/after
examples from the fixes this tool forced):

- **R1 traced purity** — no host-clock/RNG calls (``time.*``,
  ``random.*``, ``np.random.*``, ``datetime.now``) and no host syncs
  (``.item()``, ``.block_until_ready()``, ``jax.device_get``,
  ``float(arg)``/``int(arg)`` on a traced parameter) inside functions
  reachable from a ``jit`` / ``watched_jit`` / ``lax.scan`` root.  A
  host call in traced code either freezes a trace-time value into the
  compiled program or forces a device sync in the middle of a fused
  dispatch.
- **R2 atomic writes** — in crash-safety-scoped paths (``resilience/``,
  ``deploy/``, ``earlystopping/``, the serializer, the flight recorder,
  checkpoint listeners, broker persistence), no bare
  ``open(path, "w"/"wb")`` or ``zipfile.ZipFile(path, "w")`` on a
  filesystem path: final files must go through
  ``deeplearning4j_tpu.utils.fileio.atomic_write`` so SIGKILL never
  leaves a torn file where a valid one lived.
- **R3 blocking under lock** — no socket/queue/subprocess/sleep/
  device-sync call lexically inside a ``with <lock>:`` body, including
  through local helper functions (an intra-module fixpoint marks
  helpers that transitively block).  Locks must cover shared-state
  mutation only.
- **R4 registry drift** — the ``DL4J_TPU_*`` env-var set and the metric
  name set registered in code must exactly match the generated
  inventory block in ``docs/OBSERVABILITY.md`` (and every env var named
  in any doc must exist in code).  ``--write-registry`` regenerates the
  block; the check replaces hand-maintained lists.
- **R5 donation safety** — a value passed in a donated position
  (``donate_argnums``) of a jitted dispatch must not be read after the
  call: donation invalidates the buffer, and XLA is free to overwrite
  it in place.
- **R6 retrace risk** — the statically-visible jit cache busters
  ``watched_jit`` can only report after the fact: a jit constructed and
  invoked in one expression (fresh empty cache per call), a jit factory
  called inside a loop body, a non-hashable literal passed in a
  ``static_argnums`` position (``TypeError`` at dispatch), a static
  argument fed from the enclosing loop variable (one compile per
  iteration), and a traced function closing over module-level mutable
  state that is mutated elsewhere (the trace freezes a stale value).
- **R7 hidden host<->device transfers** — ``float()``/``int()``/
  ``bool()``/``np.asarray()``/``np.array()`` applied to a value that
  data-flows from a jitted dispatch or a ``jnp.*`` computation, in
  host code outside the audited sink scope (eval fast path, metrics
  decode, checkpoint host-snapshot): each such cast is a blocking
  device->host round trip hiding in a hot path.
- **R8 lockset guarded-field drift** — within one class, a ``self._x``
  attribute written both inside a ``with <lock>:`` region and bare (in
  any method other than ``__init__``), or guarded by two *disjoint*
  locks: the unguarded (or differently-guarded) write races every
  reader that trusts the lock.  Methods named ``*_locked`` are
  guarded-by-convention (the caller holds the lock).

R1 reachability and R3's blocking fixpoint are **whole-program**: the
cross-module call graph (``tools.analyze.callgraph``) resolves the
repo's own imports, so a traced helper or blocking primitive defined a
module away is still caught (``run``/``lint_file`` thread the global
seeds through; ``lint_source`` on one blob stays intra-module).

Suppressions: ``# dl4j-lint: disable=R3 <reason>`` on the finding's
line or the line above.  The reason is mandatory and audited — a
reasonless or unused suppression is itself a finding, so the invariant
set can only grow.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from tools.analyze.callgraph import method_owners, returned_closures

ALL_RULES = ("R1", "R2", "R3", "R4", "R5", "R6", "R7", "R8")

#: paths (relative, slash-normalized prefixes or exact files) under the
#: atomic-write contract (R2)
R2_SCOPE = (
    "deeplearning4j_tpu/resilience/",
    "deeplearning4j_tpu/deploy/",
    "deeplearning4j_tpu/earlystopping/",
    "deeplearning4j_tpu/utils/model_serializer.py",
    "deeplearning4j_tpu/monitor/flight_recorder.py",
    "deeplearning4j_tpu/optimize/listeners/listeners.py",
    "deeplearning4j_tpu/streaming/broker.py",
)

#: the one blessed implementation R2 routes everything through
R2_EXEMPT = ("deeplearning4j_tpu/utils/fileio.py",)

#: audited host-decode sink sites where R7 casts are the POINT — the
#: eval fast path decodes argmax indices, health/metrics decode the
#: packed stats vector, checkpoint/serializer snapshot params to host,
#: and the serving layer returns host arrays at the request boundary
R7_SINK_SCOPE = (
    "deeplearning4j_tpu/eval/",
    "deeplearning4j_tpu/monitor/health.py",
    "deeplearning4j_tpu/resilience/checkpoint.py",
    "deeplearning4j_tpu/utils/model_serializer.py",
    "deeplearning4j_tpu/deploy/store.py",
)

_SUPPRESS_RE = re.compile(
    r"#\s*dl4j-lint:\s*disable=([A-Za-z0-9,]+)\s*(.*?)\s*$")

_WRITE_MODES = {"w", "wb", "w+", "wb+", "wt"}

#: receiver names that mean "already a file object" for ZipFile(...)
_STREAM_HINTS = {"fh", "f", "fp", "buf", "buffer", "fileobj", "bio",
                 "stream", "out"}

#: dotted host calls banned in traced code (R1); prefixes match children
_R1_BANNED_PREFIXES = ("np.random.", "numpy.random.", "random.",
                      "jrandom.host_")
_R1_BANNED_EXACT = {
    "time.time", "time.perf_counter", "time.monotonic",
    "time.process_time", "time.sleep", "time.time_ns",
    "datetime.now", "datetime.datetime.now", "datetime.utcnow",
    "os.urandom", "jax.device_get", "device_get", "uuid.uuid4",
}
_R1_BANNED_METHODS = {"item", "block_until_ready"}

#: attribute calls that block (R3); ``get``/``put`` count only on
#: queue-hinted receivers, ``join`` only on thread-hinted receivers
_R3_BLOCK_ATTRS = {"recv", "recv_into", "recvfrom", "accept", "sendall",
                   "connect", "block_until_ready", "select"}
_R3_BLOCK_DOTTED = {"time.sleep", "socket.create_connection",
                    "subprocess.run", "subprocess.call",
                    "subprocess.check_call", "subprocess.check_output",
                    "subprocess.Popen", "select.select"}
_R3_QUEUE_HINTS = ("queue", "_q", "jobs", "inbox")

#: jit-root factories (R1/R5)
_JIT_FACTORIES = {"jit", "watched_jit"}


@dataclasses.dataclass
class Finding:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclasses.dataclass
class Suppression:
    line: int           # line the directive covers (directive or next)
    rules: Tuple[str, ...]
    reason: str
    directive_line: int
    used: bool = False


# --------------------------------------------------------------- helpers

def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_name(call: ast.Call) -> Optional[str]:
    return _dotted(call.func)


def _last_attr(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def parse_suppressions(source: str) -> List[Suppression]:
    # tokenize so directives in docstrings/string literals (e.g. the
    # examples in this module's own docs) are not treated as live
    out: List[Suppression] = []
    try:
        tokens = list(tokenize.generate_tokens(
            io.StringIO(source).readline))
    except (tokenize.TokenizeError, SyntaxError, IndentationError):
        return out
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _SUPPRESS_RE.search(tok.string)
        if not m:
            continue
        i = tok.start[0]
        rules = tuple(r.strip().upper() for r in m.group(1).split(",")
                      if r.strip())
        reason = m.group(2).strip()
        own_line = tok.line[:tok.start[1]].strip() == ""
        covered = i + 1 if own_line else i  # own-line: covers next
        out.append(Suppression(line=covered, rules=rules, reason=reason,
                               directive_line=i))
    return out


# ------------------------------------------------------------ module IR

class _FunctionInfo:
    def __init__(self, node: ast.FunctionDef, cls: Optional[str]):
        self.node = node
        self.cls = cls
        self.name = node.name
        self.qname = f"{cls}.{node.name}" if cls else node.name
        self.calls: Set[str] = set()       # resolved callee qnames
        self.blocking_sites: List[Tuple[int, str]] = []


class _ModuleIndex:
    """Per-module tables: functions (keyed by CLASS-QUALIFIED name, so
    two classes' same-named methods never conflate), intra-module call
    edges, jit roots, and donated-jit bindings."""

    def __init__(self, tree: ast.Module):
        #: qualified name ("Cls.meth" or bare for module-level/nested)
        #: -> info; bare-name view in :attr:`by_bare`
        self.functions: Dict[str, _FunctionInfo] = {}
        self.by_bare: Dict[str, List[str]] = {}
        #: class -> its bases / subclasses defined in this module
        self.bases: Dict[str, List[str]] = {}
        self.subs: Dict[str, List[str]] = {}
        self.jit_roots: Set[str] = set()
        # binding name -> donate arg positions
        self.donated: Dict[str, Tuple[int, ...]] = {}
        # binding name -> static arg positions (R6)
        self.static_bindings: Dict[str, Tuple[int, ...]] = {}
        # every name bound to a jit/watched_jit factory result (R7)
        self.jit_bindings: Set[str] = set()
        self._collect(tree)

    # -- collection -----------------------------------------------------
    def _collect(self, tree: ast.Module) -> None:
        cls_stack: List[Optional[str]] = [None]

        def visit(node: ast.AST) -> None:
            if isinstance(node, ast.ClassDef):
                for base in node.bases:
                    if isinstance(base, ast.Name):
                        self.bases.setdefault(node.name,
                                              []).append(base.id)
                        self.subs.setdefault(base.id,
                                             []).append(node.name)
                cls_stack.append(node.name)
                for child in node.body:
                    visit(child)
                cls_stack.pop()
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info = _FunctionInfo(node, cls_stack[-1])
                self.functions[info.qname] = info
                self.by_bare.setdefault(node.name, []).append(info.qname)
                self._scan_decorators(node, info.qname)
                for child in ast.iter_child_nodes(node):
                    visit(child)
                return
            if isinstance(node, ast.Call):
                self._scan_call(node)
            if isinstance(node, ast.Assign):
                self._scan_assign(node)
            for child in ast.iter_child_nodes(node):
                visit(child)

        visit(tree)
        # call edges, resolved class-aware once functions are known
        for info in self.functions.values():
            for sub in ast.walk(info.node):
                if isinstance(sub, ast.Call):
                    info.calls.update(self.resolve_callees(info.cls, sub))
            # whoever calls for a closure runs it
            info.calls.update(
                f"{info.cls}.{n}" if info.cls else n
                for n in returned_closures(info.node))

    def resolve_callees(self, cls: Optional[str],
                        call: ast.Call) -> List[str]:
        """Qualified names of the local functions a call may hit.  A
        ``self.x(...)`` goes through the class hierarchy as far as this
        module defines it (``callgraph.method_owners``: the caller's own
        class, else the nearest ancestor that defines ``x``, else every
        subclass that does); a bare name prefers module level; either
        falls back to a bare name that is unique in the module (an
        ambiguous one resolves to nothing: conservative
        under-approximation)."""
        func = call.func
        if isinstance(func, ast.Name):
            name = func.id
        elif isinstance(func, ast.Attribute) and \
                isinstance(func.value, ast.Name) and \
                func.value.id in ("self", "cls"):
            name = func.attr
            if cls is not None:
                owners = method_owners(
                    cls, name, self.bases, self.subs,
                    lambda c, n: f"{c}.{n}" in self.functions)
                if owners:
                    return [f"{c}.{name}" for c in owners]
        else:
            return []
        if name in self.functions:              # module-level / nested
            return [name]
        cands = self.by_bare.get(name, [])
        return cands if len(cands) == 1 else []

    def expand(self, names: Iterable[str]) -> Tuple[Set[str], Set[str]]:
        """Split seed names into (local qualified names, foreign bare
        names): a qname or bare name matching local functions expands to
        the matching qnames; anything else (imported helpers the global
        graph proved) stays bare for call-site matching."""
        local: Set[str] = set()
        foreign: Set[str] = set()
        for n in names:
            if n in self.functions:
                local.add(n)
            elif n in self.by_bare:
                local.update(self.by_bare[n])
            else:
                foreign.add(n)
        return local, foreign

    def _scan_decorators(self, node: ast.FunctionDef,
                         qname: str) -> None:
        for dec in node.decorator_list:
            name = _dotted(dec if not isinstance(dec, ast.Call)
                           else dec.func)
            if name and name.split(".")[-1] in _JIT_FACTORIES:
                self.jit_roots.add(qname)

    def _root_arg(self, call: ast.Call) -> Optional[str]:
        if call.args:
            return _last_attr(call.args[0])
        return None

    def _scan_call(self, call: ast.Call) -> None:
        name = _call_name(call)
        if name is None:
            return
        tail = name.split(".")[-1]
        if tail in _JIT_FACTORIES:
            root = self._root_arg(call)
            if root:
                self.jit_roots.add(root)
        elif tail == "scan" and name.split(".")[-2:-1] == ["lax"]:
            root = self._root_arg(call)
            if root:
                self.jit_roots.add(root)

    def _kw_positions(self, call: ast.Call,
                      kwarg: str) -> Optional[Tuple[int, ...]]:
        for kw in call.keywords:
            if kw.arg == kwarg:
                return self._int_positions(kw.value)
        return None

    @staticmethod
    def _int_positions(node: ast.AST) -> Optional[Tuple[int, ...]]:
        """Evaluate a donate_argnums expression: an int, a literal
        tuple/list of ints, or ``[tuple(]range(...)[)]``."""
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return (node.value,)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            vals = tuple(e.value for e in node.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, int))
            return vals or None
        if isinstance(node, ast.Call):
            name = _call_name(node) or ""
            if name in ("tuple", "list") and len(node.args) == 1:
                return _ModuleIndex._int_positions(node.args[0])
            if name == "range" and node.args and all(
                    isinstance(a, ast.Constant)
                    and isinstance(a.value, int) for a in node.args):
                return tuple(range(*(a.value for a in node.args))) or None
        return None

    def _scan_assign(self, node: ast.Assign) -> None:
        if not isinstance(node.value, ast.Call):
            return
        name = _call_name(node.value)
        if name is None or name.split(".")[-1] not in _JIT_FACTORIES:
            return
        donate = self._kw_positions(node.value, "donate_argnums")
        static = self._kw_positions(node.value, "static_argnums")
        for tgt in node.targets:
            bound = _last_attr(tgt)
            if bound:
                self.jit_bindings.add(bound)
                if donate:
                    self.donated[bound] = donate
                if static:
                    self.static_bindings[bound] = static

    # -- reachability ---------------------------------------------------
    def traced_functions(
            self, extra: Iterable[str] = ()) -> Dict[str, _FunctionInfo]:
        seen: Set[str] = set()
        roots, _ = self.expand(set(self.jit_roots) | set(extra))
        frontier = list(roots)
        while frontier:
            cur = frontier.pop()
            if cur in seen:
                continue
            seen.add(cur)
            frontier.extend(c for c in self.functions[cur].calls
                            if c not in seen)
        return {n: self.functions[n] for n in seen}


# ------------------------------------------------------------------ R1

def _walk_skipping_nested(fn: ast.FunctionDef) -> Iterable[ast.AST]:
    """Walk a function body without descending into nested function
    definitions (those are separately reachable if traced)."""
    stack: List[ast.AST] = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _check_r1(index: _ModuleIndex, path: str,
              extra_traced: Iterable[str] = ()) -> List[Finding]:
    out: List[Finding] = []
    for fname, info in index.traced_functions(extra_traced).items():
        params = {a.arg for a in info.node.args.args
                  + info.node.args.kwonlyargs
                  + info.node.args.posonlyargs}
        for node in _walk_skipping_nested(info.node):
            if not isinstance(node, ast.Call):
                continue
            dotted = _call_name(node)
            attr = (node.func.attr
                    if isinstance(node.func, ast.Attribute) else None)
            msg = None
            if dotted in _R1_BANNED_EXACT or (
                    dotted and dotted.startswith(_R1_BANNED_PREFIXES)):
                msg = f"host call `{dotted}(...)`"
            elif attr in _R1_BANNED_METHODS and not node.args:
                msg = f"host-sync `.{attr}()`"
            elif (dotted in ("float", "int") and len(node.args) == 1
                  and isinstance(node.args[0], ast.Name)
                  and node.args[0].id in params):
                msg = (f"`{dotted}({node.args[0].id})` host-syncs a "
                       "traced argument")
            if msg:
                out.append(Finding(
                    "R1", path, node.lineno,
                    f"traced purity: {msg} inside `{fname}`, which is "
                    "reachable from a jit/watched_jit/lax.scan root — "
                    "host calls freeze trace-time values or force a "
                    "device sync mid-dispatch"))
    return out


# ------------------------------------------------------------------ R2

def _write_mode_of(call: ast.Call) -> Optional[str]:
    if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant) \
            and isinstance(call.args[1].value, str):
        return call.args[1].value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant) \
                and isinstance(kw.value.value, str):
            return kw.value.value
    return None


def _check_r2(tree: ast.Module, path: str) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        mode = _write_mode_of(node)
        if mode is None or mode not in _WRITE_MODES:
            continue
        if name == "open":
            out.append(Finding(
                "R2", path, node.lineno,
                f"atomic writes: bare `open(..., {mode!r})` in a "
                "crash-safety-scoped path — route through "
                "utils.fileio.atomic_write (temp+fsync+rename) so a "
                "SIGKILL mid-write never leaves a torn file"))
        elif name and name.split(".")[-1] == "ZipFile":
            target = node.args[0] if node.args else None
            hint = _last_attr(target) if target is not None else None
            if hint is not None and hint.lower() in _STREAM_HINTS:
                continue     # already writing into a file object
            if isinstance(target, ast.Call):
                hint = _call_name(target) or ""
                if hint.split(".")[-1] in ("BytesIO", "StringIO"):
                    continue
            out.append(Finding(
                "R2", path, node.lineno,
                "atomic writes: `zipfile.ZipFile(path, 'w')` writes the "
                "final file in place — wrap utils.fileio.atomic_write "
                "and hand ZipFile the file object"))
    return out


# ------------------------------------------------------------------ R3

def _is_blocking_call(node: ast.Call, blocking_fns: Set[str],
                      xmod_fns: Set[str] = frozenset(),
                      cls: Optional[str] = None,
                      index: Optional[_ModuleIndex] = None
                      ) -> Optional[str]:
    """The blocking thing this call performs, or ``None``: a blocking
    primitive, a local function the fixpoint proved blocking (resolved
    class-aware through ``index`` — two classes' same-named methods
    never conflate), or an imported helper the whole-program graph
    proved blocking (``xmod_fns``, matched at module-alias call
    sites)."""
    dotted = _call_name(node)
    if dotted in _R3_BLOCK_DOTTED:
        return dotted
    if isinstance(node.func, ast.Attribute):
        attr = node.func.attr
        recv = _dotted(node.func.value) or ""
        if attr in _R3_BLOCK_ATTRS:
            return f"{recv}.{attr}" if recv else attr
        if attr in ("get", "put") and any(
                h in recv.lower() for h in _R3_QUEUE_HINTS):
            return f"{recv}.{attr}"
        if recv in ("self", "cls"):
            if index is not None:
                if blocking_fns.intersection(
                        index.resolve_callees(cls, node)):
                    return f"self.{attr}"
            elif attr in blocking_fns:       # no index: bare matching
                return f"self.{attr}"
            return None      # self-calls never match imported names
        # imported blocking helper called through a module alias
        # (``wire._recv_exact(...)``) — names come from the global graph
        if attr in xmod_fns:
            return f"{recv}.{attr}" if recv else attr
    if isinstance(node.func, ast.Name):
        if index is not None:
            if blocking_fns.intersection(index.resolve_callees(cls, node)):
                return node.func.id
        elif node.func.id in blocking_fns:
            return node.func.id
        if node.func.id in xmod_fns:
            return node.func.id
    return None


def _blocking_fixpoint(index: _ModuleIndex,
                       extra: Iterable[str] = ()) -> Tuple[Set[str],
                                                           Set[str]]:
    """(qualified names of module functions that transitively perform a
    blocking call, foreign bare names) — so R3 sees through local
    helpers like ``_recv_exact``.  ``extra`` seeds names the
    WHOLE-PROGRAM graph already proved blocking: local qnames from the
    cross-module fixpoint plus bare names of imported wire helpers."""
    blocking, xmod = index.expand(extra)
    changed = True
    while changed:
        changed = False
        for qname, info in index.functions.items():
            if qname in blocking:
                continue
            for node in ast.walk(info.node):
                if isinstance(node, ast.Call) and \
                        _is_blocking_call(node, blocking, xmod,
                                          cls=info.cls, index=index):
                    blocking.add(qname)
                    changed = True
                    break
    return blocking, xmod


def _lockish(expr: ast.AST) -> Optional[str]:
    name = _dotted(expr)
    if name is None:
        return None
    tail = name.split(".")[-1].lower()
    return name if "lock" in tail or tail in ("_mu", "_meta") else None


def _check_r3(tree: ast.Module, index: _ModuleIndex, path: str,
              extra_blocking: Iterable[str] = ()) -> List[Finding]:
    out: List[Finding] = []
    blocking_fns, xmod = _blocking_fixpoint(index, extra_blocking)
    # With sites paired with their enclosing class so self-calls
    # resolve against the right class's methods
    sites: List[Tuple[ast.With, Optional[str]]] = []
    seen_withs: Set[int] = set()
    for info in index.functions.values():
        for node in _walk_skipping_nested(info.node):
            if isinstance(node, ast.With):
                sites.append((node, info.cls))
                seen_withs.add(id(node))
    for node in ast.walk(tree):      # module/class-level With blocks
        if isinstance(node, ast.With) and id(node) not in seen_withs:
            sites.append((node, None))
    for node, cls in sites:
        lock_names = [n for n in
                      (_lockish(item.context_expr) for item in node.items)
                      if n]
        if not lock_names:
            continue
        for sub in node.body:
            for inner in ast.walk(sub):
                if isinstance(inner,
                              (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                if isinstance(inner, ast.Call):
                    what = _is_blocking_call(inner, blocking_fns, xmod,
                                             cls=cls, index=index)
                    if what:
                        out.append(Finding(
                            "R3", path, inner.lineno,
                            f"blocking under lock: `{what}(...)` runs "
                            f"while `{lock_names[0]}` is held — narrow "
                            "the lock to shared-state mutation; a "
                            "blocked holder stalls every other thread "
                            "on this lock"))
    return out


# ------------------------------------------------------------------ R5

def _check_r5(index: _ModuleIndex, tree: ast.Module,
              path: str) -> List[Finding]:
    out: List[Finding] = []
    if not index.donated:
        return out
    for info in index.functions.values():
        fn = info.node
        body_nodes = list(_walk_skipping_nested(fn))
        calls = []
        for node in body_nodes:
            if not isinstance(node, ast.Call):
                continue
            callee = _last_attr(node.func)
            if callee in index.donated:
                calls.append((node, index.donated[callee], callee))
        for call, positions, callee in calls:
            rebound = _rebound_names(fn, call)
            for pos in positions:
                if pos >= len(call.args):
                    continue
                arg = call.args[pos]
                if not isinstance(arg, ast.Name):
                    continue
                if arg.id in rebound:
                    continue
                for node in body_nodes:
                    if isinstance(node, ast.Name) and \
                            isinstance(node.ctx, ast.Load) and \
                            node.id == arg.id and \
                            node.lineno > call.lineno:
                        out.append(Finding(
                            "R5", path, node.lineno,
                            f"donation safety: `{arg.id}` was donated "
                            f"to `{callee}` (donate_argnums position "
                            f"{pos}, line {call.lineno}) and is read "
                            "afterwards — the donated buffer may "
                            "already be overwritten in place"))
                        break
    return out


def _rebound_names(fn: ast.FunctionDef, call: ast.Call) -> Set[str]:
    """Names assigned from the donated call's result (``a, b = f(a, b)``
    rebinds a and b — reads after that are the NEW buffers)."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and node.value is call:
            names: Set[str] = set()
            for tgt in node.targets:
                for sub in ast.walk(tgt):
                    if isinstance(sub, ast.Name):
                        names.add(sub.id)
            return names
    return set()


# ------------------------------------------------------------------ R6

#: mutating methods on module-level containers (R6 closure shape)
_R6_MUT_METHODS = {"append", "extend", "update", "setdefault", "pop",
                   "insert", "clear", "remove", "add", "popitem",
                   "discard", "appendleft"}

_R6_UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.SetComp,
                  ast.DictComp)


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _static_calls(node: ast.AST,
                  index: _ModuleIndex) -> Iterable[
                      Tuple[ast.Call, str, Tuple[int, ...]]]:
    """Calls (anywhere under ``node``) whose callee is a known
    ``static_argnums`` jit binding, with the static positions."""
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        callee = _last_attr(sub.func)
        if callee in index.static_bindings:
            yield sub, callee, index.static_bindings[callee]


def _check_r6(index: _ModuleIndex, tree: ast.Module, path: str,
              extra_traced: Iterable[str] = ()) -> List[Finding]:
    out: List[Finding] = []
    flagged_factory_lines: Set[int] = set()

    # -- R6 shape 1: a jit constructed and invoked in one expression —
    # the returned callable (and its compile cache) dies with the
    # statement, so every execution recompiles from scratch
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or \
                not isinstance(node.func, ast.Call):
            continue
        inner = _call_name(node.func)
        if inner and inner.split(".")[-1] in _JIT_FACTORIES:
            flagged_factory_lines.add(node.func.lineno)
            out.append(Finding(
                "R6", path, node.lineno,
                f"retrace risk: `{inner}(...)(...)` constructs and "
                "invokes a jit in one expression — the compile cache "
                "is thrown away with the callable, so this recompiles "
                "on every execution; bind the jitted function once and "
                "reuse it"))

    # -- R6 shape 2: a jit factory called inside a loop body — one
    # fresh cache (and compile) per iteration
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        for body_stmt in loop.body + loop.orelse:
            for sub in ast.walk(body_stmt):
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    continue
                if not isinstance(sub, ast.Call):
                    continue
                name = _call_name(sub)
                if name and name.split(".")[-1] in _JIT_FACTORIES and \
                        sub.lineno not in flagged_factory_lines:
                    flagged_factory_lines.add(sub.lineno)
                    out.append(Finding(
                        "R6", path, sub.lineno,
                        f"retrace risk: `{name}(...)` is called inside "
                        "a loop body — each iteration builds a fresh "
                        "jit with an empty cache; hoist the factory "
                        "out of the loop"))

    # -- R6 shape 3: non-hashable literal in a static_argnums position
    # (TypeError at dispatch: static args are cache keys)
    for call, callee, positions in _static_calls(tree, index):
        for pos in positions:
            if pos < len(call.args) and \
                    isinstance(call.args[pos], _R6_UNHASHABLE):
                out.append(Finding(
                    "R6", path, call.lineno,
                    f"retrace risk: call to `{callee}` passes a "
                    f"non-hashable literal at static_argnums position "
                    f"{pos} — static args are hashed as cache keys, "
                    "this raises TypeError at dispatch; pass a tuple "
                    "or hoist to a hashable constant"))

    # -- R6 shape 4: a static argument fed from the enclosing loop
    # variable — every iteration is a new cache key, so the loop
    # compiles once per step
    for loop in ast.walk(tree):
        if not isinstance(loop, ast.For):
            continue
        loop_vars = _names_in(loop.target)
        for call, callee, positions in _static_calls(loop, index):
            for pos in positions:
                if pos < len(call.args) and \
                        _names_in(call.args[pos]) & loop_vars:
                    out.append(Finding(
                        "R6", path, call.lineno,
                        f"retrace risk: call to `{callee}` passes loop "
                        f"variable(s) "
                        f"{sorted(_names_in(call.args[pos]) & loop_vars)}"
                        f" at static_argnums position {pos} — every "
                        "iteration is a new cache key, compiling once "
                        "per step; make the argument traced or hoist "
                        "it out of the loop"))

    # -- R6 shape 5: traced function closing over module-level mutable
    # state that is mutated elsewhere — the trace freezes the value it
    # saw at compile time, silently ignoring later mutation
    mutables: Dict[str, int] = {}
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign) and (
                isinstance(stmt.value, (ast.List, ast.Dict, ast.Set))
                or (isinstance(stmt.value, ast.Call)
                    and _call_name(stmt.value) in ("list", "dict",
                                                   "set"))):
            for tgt in stmt.targets:
                if isinstance(tgt, ast.Name):
                    mutables[tgt.id] = stmt.lineno
    mutated: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in _R6_MUT_METHODS and \
                isinstance(node.func.value, ast.Name) and \
                node.func.value.id in mutables:
            mutated.add(node.func.value.id)
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            tgts = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for tgt in tgts:
                if isinstance(tgt, ast.Subscript) and \
                        isinstance(tgt.value, ast.Name) and \
                        tgt.value.id in mutables:
                    mutated.add(tgt.value.id)
    if mutated:
        for fname, info in index.traced_functions(extra_traced).items():
            shadowed = {a.arg for a in info.node.args.args
                        + info.node.args.kwonlyargs
                        + info.node.args.posonlyargs}
            for node in _walk_skipping_nested(info.node):
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Store):
                    shadowed.add(node.id)
            seen_here: Set[str] = set()
            for node in _walk_skipping_nested(info.node):
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load) and \
                        node.id in mutated and \
                        node.id not in shadowed and \
                        node.id not in seen_here:
                    seen_here.add(node.id)
                    out.append(Finding(
                        "R6", path, node.lineno,
                        f"retrace risk: traced `{fname}` reads "
                        f"module-level mutable `{node.id}` (defined "
                        f"line {mutables[node.id]}) which is mutated "
                        "elsewhere in this module — the trace freezes "
                        "the value seen at compile time and ignores "
                        "the mutation; pass it as an argument"))
    return out


# ------------------------------------------------------------------ R7

_R7_CASTS = {"float", "int", "bool"}
_R7_NP_MODULES = {"np", "numpy", "onp"}
_R7_NP_FUNCS = {"asarray", "array"}


def _r7_device_source(call: ast.Call, index: _ModuleIndex) -> bool:
    """Does this call produce a device value: a ``jnp.*``/``jax.numpy.*``
    computation or an invocation of a jit/watched_jit binding (including
    ``self._step(...)``)?"""
    name = _call_name(call)
    if name is None:
        return False
    if name.startswith(("jnp.", "jax.numpy.")):
        return True
    return name.split(".")[-1] in index.jit_bindings


#: attribute accesses that read array METADATA, not array data — no
#: transfer happens (``int(x.shape[0])`` is host-side bookkeeping)
_R7_META_ATTRS = {"shape", "ndim", "size", "dtype", "sharding",
                  "itemsize", "nbytes"}


def _r7_base_name(node: ast.AST) -> Optional[ast.AST]:
    """Strip subscripts/attributes: ``out[0].loss`` -> ``out``; a chain
    through a metadata attribute (``x.shape[0]``) carries no device
    data, so it strips to nothing."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        if isinstance(node, ast.Attribute) and \
                node.attr in _R7_META_ATTRS:
            return None
        node = node.value
    return node


def _check_r7(index: _ModuleIndex, tree: ast.Module, path: str,
              extra_traced: Iterable[str] = ()) -> List[Finding]:
    out: List[Finding] = []
    traced = set(index.traced_functions(extra_traced))
    for fname, info in index.functions.items():
        if fname in traced:
            continue          # host syncs in traced code are R1's domain
        fn = info.node
        tainted: Set[str] = set()
        for node in _walk_skipping_nested(fn):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call) and \
                    _r7_device_source(node.value, index):
                for tgt in node.targets:
                    elts = tgt.elts if isinstance(
                        tgt, (ast.Tuple, ast.List)) else [tgt]
                    for elt in elts:
                        base = _r7_base_name(elt)
                        if isinstance(base, ast.Name):
                            tainted.add(base.id)
        for node in _walk_skipping_nested(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if name in _R7_CASTS and len(node.args) == 1 \
                    and not node.keywords:
                arg = node.args[0]
                what = f"{name}(...)"
            elif name and "." in name and \
                    name.split(".")[0] in _R7_NP_MODULES and \
                    name.split(".")[-1] in _R7_NP_FUNCS and node.args:
                arg = node.args[0]
                what = f"{name}(...)"
            else:
                continue
            base = _r7_base_name(arg)
            hit = None
            if isinstance(base, ast.Name) and base.id in tainted:
                hit = base.id
            elif isinstance(base, ast.Call) and \
                    _r7_device_source(base, index):
                hit = _call_name(base)
            if hit:
                out.append(Finding(
                    "R7", path, node.lineno,
                    f"hidden transfer: `{what}` on `{hit}`, which "
                    f"data-flows from a jitted dispatch/device "
                    f"computation in `{fname}` — each cast is a "
                    "blocking device->host round trip; batch the "
                    "decode into an audited sink (eval fast path, "
                    "metrics decode) or keep the value on device"))
    return out


# ------------------------------------------------------------------ R8

_R8_SENTINEL = "<locked-method>"


def _check_r8(tree: ast.Module, path: str) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            out += _check_r8_class(node, path)
    return out


def _check_r8_class(cls: ast.ClassDef, path: str) -> List[Finding]:
    # (lineno, lockset, method, attr) for every ``self.<attr> = ...``
    writes: List[Tuple[int, frozenset, str, str]] = []

    def visit(node: ast.AST, locks: frozenset, method: str) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return            # nested defs: their own scope
        if isinstance(node, ast.With):
            held = [n for n in (_lockish(i.context_expr)
                                for i in node.items) if n]
            inner = locks | frozenset(held) if held else locks
            for item in node.items:
                if item.optional_vars is not None:
                    visit(item.optional_vars, locks, method)
            for child in node.body:
                visit(child, inner, method)
            return
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            tgts = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for tgt in tgts:
                if isinstance(tgt, ast.Attribute) and \
                        isinstance(tgt.value, ast.Name) and \
                        tgt.value.id == "self" and \
                        _lockish(tgt) is None:
                    writes.append((node.lineno, locks, method, tgt.attr))
        for child in ast.iter_child_nodes(node):
            visit(child, locks, method)

    for item in cls.body:
        if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if item.name == "__init__":
            continue          # construction races nothing
        base = frozenset((_R8_SENTINEL,)) \
            if item.name.endswith("_locked") else frozenset()
        for child in item.body:
            visit(child, base, item.name)

    by_attr: Dict[str, List[Tuple[int, frozenset, str]]] = {}
    for lineno, locks, method, attr in writes:
        by_attr.setdefault(attr, []).append((lineno, locks, method))

    out: List[Finding] = []
    for attr, sites in sorted(by_attr.items()):
        guarded = [s for s in sites if s[1]]
        bare = [s for s in sites if not s[1]]
        if guarded and bare:
            g_line, g_locks, g_method = guarded[0]
            lock_name = next((n for n in sorted(g_locks)
                              if n != _R8_SENTINEL), _R8_SENTINEL)
            for lineno, _, method in bare:
                out.append(Finding(
                    "R8", path, lineno,
                    f"lockset drift: `self.{attr}` is written bare in "
                    f"`{cls.name}.{method}` but under `{lock_name}` in "
                    f"`{g_method}` (line {g_line}) — the unguarded "
                    "write races every reader that trusts the lock; "
                    "guard it or rename the method `*_locked`"))
        # disjoint real locksets: two writers each think they hold THE
        # lock, but they hold different ones
        real = [s for s in guarded if _R8_SENTINEL not in s[1]]
        for i in range(1, len(real)):
            if not (real[i][1] & real[0][1]):
                out.append(Finding(
                    "R8", path, real[i][0],
                    f"lockset drift: `self.{attr}` is written under "
                    f"`{sorted(real[i][1])[0]}` in "
                    f"`{cls.name}.{real[i][2]}` but under "
                    f"`{sorted(real[0][1])[0]}` in `{real[0][2]}` "
                    f"(line {real[0][0]}) — disjoint locks guard "
                    "nothing; pick one lock for this field"))
                break
    return out


# ----------------------------------------------------------- file driver

def _in_scope(path: str, scope: Sequence[str]) -> bool:
    norm = path.replace(os.sep, "/")
    return any(norm.endswith(s) if s.endswith(".py")
               else (s in norm) for s in scope)


def lint_source(source: str, path: str = "<string>",
                rules: Optional[Iterable[str]] = None,
                collect_suppressions: bool = False,
                extra_traced: Iterable[str] = (),
                extra_blocking: Iterable[str] = ()):
    """Lint one source blob.  ``rules`` defaults to every per-file rule
    (R4 is repo-level).  ``extra_traced``/``extra_blocking`` seed the
    intra-module reachability/fixpoint with names the whole-program
    graph proved traced/blocking (``run`` supplies them; a bare
    ``lint_source`` stays intra-module).  Returns findings, or
    ``(findings, suppressions)`` when ``collect_suppressions`` —
    already filtered through the suppression directives, with
    reasonless/unused directives reported as ``SUP`` findings."""
    active = set(rules) if rules is not None else {
        "R1", "R2", "R3", "R5", "R6", "R7", "R8"}
    tree = ast.parse(source)
    index = _ModuleIndex(tree)
    findings: List[Finding] = []
    if "R1" in active:
        findings += _check_r1(index, path, extra_traced)
    if "R2" in active:
        findings += _check_r2(tree, path)
    if "R3" in active:
        findings += _check_r3(tree, index, path, extra_blocking)
    if "R5" in active:
        findings += _check_r5(index, tree, path)
    if "R6" in active:
        findings += _check_r6(index, tree, path, extra_traced)
    if "R7" in active:
        findings += _check_r7(index, tree, path, extra_traced)
    if "R8" in active:
        findings += _check_r8(tree, path)

    sups = parse_suppressions(source)
    kept: List[Finding] = []
    for f in findings:
        suppressed = False
        for s in sups:
            if f.line == s.line and f.rule in s.rules:
                s.used = True
                suppressed = bool(s.reason)
                # a reasonless directive does NOT suppress: the reason
                # is the audited artifact
        if not suppressed:
            kept.append(f)
    for s in sups:
        if not s.reason:
            kept.append(Finding(
                "SUP", path, s.directive_line,
                "suppression without a reason — write `# dl4j-lint: "
                "disable=<rule> <why this is safe>`; the reason is the "
                "audited artifact"))
        elif not s.used and not any(r not in ALL_RULES for r in s.rules):
            kept.append(Finding(
                "SUP", path, s.directive_line,
                f"unused suppression for {','.join(s.rules)} — the "
                "finding it silenced is gone; delete the directive"))
    if collect_suppressions:
        return kept, sups
    return kept


def lint_file(path: str, repo_root: str,
              extra_traced: Iterable[str] = (),
              extra_blocking: Iterable[str] = ()) -> List[Finding]:
    rel = os.path.relpath(path, repo_root).replace(os.sep, "/")
    with open(path, encoding="utf-8") as fh:
        source = fh.read()
    rules = {"R1", "R3", "R5", "R6", "R8"}
    if not _in_scope(rel, R7_SINK_SCOPE):
        rules.add("R7")
    if _in_scope(rel, R2_SCOPE) and not _in_scope(rel, R2_EXEMPT):
        rules.add("R2")
    try:
        return lint_source(source, rel, rules,
                           extra_traced=extra_traced,
                           extra_blocking=extra_blocking)
    except SyntaxError as exc:
        return [Finding("SYN", rel, exc.lineno or 0,
                        f"syntax error: {exc.msg}")]


# ------------------------------------------------------------------- R4

REGISTRY_DOC = "docs/OBSERVABILITY.md"
REGISTRY_BEGIN = "<!-- dl4j-registry:begin -->"
REGISTRY_END = "<!-- dl4j-registry:end -->"

_ENV_RE = re.compile(r"DL4J_TPU_[A-Z0-9_]*[A-Z0-9](?![A-Z0-9_])")
#: quoted constants ending in "_" are env-name PREFIXES (e.g.
#: ``ENV_PREFIX = "DL4J_TPU_FAULT_"`` concatenated at runtime): doc
#: references to names under such a prefix are considered code-backed
_ENV_PREFIX_RE = re.compile(r"[\"'](DL4J_TPU_[A-Z0-9_]*_)[\"']")
_METRIC_FACTORIES = {"counter", "gauge", "histogram"}


def _code_files(root: str) -> List[str]:
    out: List[str] = []
    for base in ("deeplearning4j_tpu", "tools"):
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(root, base)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            out.extend(os.path.join(dirpath, f) for f in filenames
                       if f.endswith(".py"))
    bench = os.path.join(root, "bench.py")
    if os.path.exists(bench):
        out.append(bench)
    return sorted(out)


def _doc_files(root: str) -> List[str]:
    docs = [os.path.join(root, "README.md")]
    docdir = os.path.join(root, "docs")
    if os.path.isdir(docdir):
        docs += [os.path.join(docdir, f)
                 for f in sorted(os.listdir(docdir)) if f.endswith(".md")]
    return [d for d in docs if os.path.exists(d)]


def _metric_pattern(node: ast.Call,
                    consts: Dict[str, str]) -> Optional[str]:
    """Metric name (or ``<hole>`` pattern for f-strings) of a
    counter/gauge/histogram registration, resolving module-level string
    constants."""
    if not node.args:
        return None
    arg = node.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    if isinstance(arg, ast.Name) and arg.id in consts:
        return consts[arg.id]
    if isinstance(arg, ast.JoinedStr):
        parts = []
        for v in arg.values:
            if isinstance(v, ast.Constant):
                parts.append(str(v.value))
            else:
                parts.append("<*>")
        return "".join(parts)
    return None


def collect_code_registry(
        root: str) -> Tuple[Set[str], Set[str], Set[str]]:
    """(env var names, metric name patterns, env-name prefixes)
    read/registered in code."""
    envs: Set[str] = set()
    metrics: Set[str] = set()
    prefixes: Set[str] = set()
    for path in _code_files(root):
        with open(path, encoding="utf-8") as fh:
            source = fh.read()
        envs.update(_ENV_RE.findall(source))
        prefixes.update(_ENV_PREFIX_RE.findall(source))
        try:
            tree = ast.parse(source)
        except SyntaxError:
            continue
        consts = {
            tgt.id: node.value.value
            for node in ast.walk(tree) if isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
            for tgt in node.targets if isinstance(tgt, ast.Name)}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if name and name.split(".")[-1] in _METRIC_FACTORIES:
                pat = _metric_pattern(node, consts)
                if pat and re.fullmatch(r"[a-z][a-z0-9_<*>]*", pat):
                    metrics.add(pat)
    return envs, metrics, prefixes


def _registry_block(envs: Set[str], metrics: Set[str]) -> str:
    lines = [REGISTRY_BEGIN,
             "<!-- generated by `python -m tools.analyze "
             "--write-registry`; edits are overwritten and drift fails "
             "R4 -->",
             "", "| kind | name |", "|------|------|"]
    lines += [f"| env | `{e}` |" for e in sorted(envs)]
    lines += [f"| metric | `{m}` |" for m in sorted(metrics)]
    lines.append(REGISTRY_END)
    return "\n".join(lines)


def _parse_registry_block(text: str) -> Tuple[Set[str], Set[str]]:
    envs: Set[str] = set()
    metrics: Set[str] = set()
    for m in re.finditer(r"\|\s*(env|metric)\s*\|\s*`([^`]+)`\s*\|",
                         text):
        (envs if m.group(1) == "env" else metrics).add(m.group(2))
    return envs, metrics


def check_registry(root: str, write: bool = False) -> List[Finding]:
    """R4: code inventory vs the generated doc block, both directions,
    plus stale ``DL4J_TPU_*`` references anywhere in the docs."""
    findings: List[Finding] = []
    envs, metrics, prefixes = collect_code_registry(root)
    doc_path = os.path.join(root, REGISTRY_DOC)
    text = ""
    if os.path.exists(doc_path):
        with open(doc_path, encoding="utf-8") as fh:
            text = fh.read()
    begin, end = text.find(REGISTRY_BEGIN), text.find(REGISTRY_END)
    if write:
        block = _registry_block(envs, metrics)
        if begin != -1 and end != -1:
            new = text[:begin] + block + text[end + len(REGISTRY_END):]
        else:
            new = text.rstrip() + "\n\n## Registry inventory\n\n" \
                + block + "\n"
        # plain write: docs are not crash-safety scoped, and importing
        # utils.fileio would drag the whole (jax-importing) package into
        # what is otherwise a stdlib-only CI gate
        with open(doc_path, "w", encoding="utf-8") as fh:
            fh.write(new)
        text, begin = new, new.find(REGISTRY_BEGIN)
        end = new.find(REGISTRY_END)
    if begin == -1 or end == -1:
        findings.append(Finding(
            "R4", REGISTRY_DOC, 1,
            "registry drift: no generated inventory block — run "
            "`python -m tools.analyze --write-registry`"))
        return findings
    doc_envs, doc_metrics = _parse_registry_block(text[begin:end])
    line_of = text[:begin].count("\n") + 1
    for missing in sorted(envs - doc_envs):
        findings.append(Finding(
            "R4", REGISTRY_DOC, line_of,
            f"registry drift: env var `{missing}` is read in code but "
            "missing from the inventory — run --write-registry"))
    for stale in sorted(doc_envs - envs):
        if any(stale.startswith(p) for p in prefixes):
            continue
        findings.append(Finding(
            "R4", REGISTRY_DOC, line_of,
            f"registry drift: inventory names env var `{stale}` which "
            "nothing in code reads — run --write-registry"))
    for missing in sorted(metrics - doc_metrics):
        findings.append(Finding(
            "R4", REGISTRY_DOC, line_of,
            f"registry drift: metric `{missing}` is registered in code "
            "but missing from the inventory — run --write-registry"))
    for stale in sorted(doc_metrics - metrics):
        findings.append(Finding(
            "R4", REGISTRY_DOC, line_of,
            f"registry drift: inventory names metric `{stale}` which "
            "nothing in code registers — run --write-registry"))
    # stale env references in prose, any doc
    for doc in _doc_files(root):
        rel = os.path.relpath(doc, root).replace(os.sep, "/")
        with open(doc, encoding="utf-8") as fh:
            for lineno, line in enumerate(fh, start=1):
                for tok in set(_ENV_RE.findall(line)):
                    if tok not in envs and not any(
                            tok.startswith(p) for p in prefixes):
                        findings.append(Finding(
                            "R4", rel, lineno,
                            f"registry drift: doc references env var "
                            f"`{tok}` which nothing in code reads"))
    return findings


# ------------------------------------------------------------ repo runs

def run(root: str, rules: Optional[Iterable[str]] = None,
        write_registry: bool = False) -> List[Finding]:
    """Lint the whole repo.  Builds the cross-module call graph once so
    every per-file check sees the whole-program traced/blocking sets.
    Returns every surviving finding."""
    active = set(rules) if rules is not None else set(ALL_RULES)
    findings: List[Finding] = []
    if active & {"R1", "R2", "R3", "R5", "R6", "R7", "R8"}:
        from tools.analyze import callgraph
        prog = callgraph.load(root)
        g_traced = prog.traced()
        g_blocking = prog.blocking()
        g_block_imports = prog.blocking_imports(g_blocking)
        for path in _code_files(root):
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            mod = prog.by_path.get(rel)
            extra_traced: Set[str] = set()
            extra_blocking: Set[str] = set()
            if mod is not None:
                extra_traced = g_traced.get(mod.name, set())
                extra_blocking = (
                    set(g_blocking.get(mod.name, set()))
                    | g_block_imports.get(mod.name, set()))
            file_findings = lint_file(path, root,
                                      extra_traced=extra_traced,
                                      extra_blocking=extra_blocking)
            findings += [f for f in file_findings
                         if f.rule in active or f.rule in ("SUP", "SYN")]
    if "R4" in active:
        findings += check_registry(root, write=write_registry)
    return findings
