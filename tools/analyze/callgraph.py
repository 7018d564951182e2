"""Whole-program, import-resolved call graph for the analyzer v2.

The v1 linter (PR 13) computed jit-reachability and the blocking
fixpoint per module, so a traced helper imported from another module —
``ingest.device_decode`` called inside both containers' scan bodies —
or a blocking primitive wrapped one module away was invisible to R1/R3.
This module parses every code file once, resolves the repo's own
imports (absolute, aliased, and relative ``from ..monitor import x``
forms), and builds one directed call graph whose nodes are
``(module, function)`` pairs:

- **jit roots** are collected repo-wide (decorator form, assignment
  form, ``lax.scan`` bodies) with the root argument resolved through
  import aliases, then traced-ness propagates forward over the global
  edges;
- **blocking-ness** (R3's fixpoint) propagates backward from the
  blocking primitives over the same edges, so ``_recv_exact`` defined
  in a wire-utils module is caught at a ``with lock:`` site in another.

A ``self.x()`` resolves through the class hierarchy (bases resolved
like any import): the caller's own class, else the nearest ancestor
that defines ``x``, else EVERY descendant that does — a hook written in
a base class (``nn/network.py``'s step calls ``self._loss_fn``) reaches
the definitions in the package's subclasses.  A function that returns
one of its nested functions has an edge to it (whoever called for the
closure runs it).

Like the rest of ``tools.analyze``, this is stdlib-only (``ast`` +
``os``): importing it pulls neither jax nor numpy, so the CI gate stays
pre-pip-install.  Resolution is name-based and deliberately
conservative — a call that cannot be resolved simply contributes no
edge (the linter under-approximates rather than guessing).
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

ClassId = Tuple[str, str]  # (dotted module name, class name)
FuncId = Tuple[str, str]   # (dotted module name, QUALIFIED function
                           # name: "fn" at module level, "Cls.meth" for
                           # methods — two classes never conflate)

_JIT_FACTORIES = {"jit", "watched_jit"}
#: transformations that trace their first argument when the function
#: that applies them is traced (``value_and_grad(self._loss_fn, ...)``)
_TRANSFORMS = {"value_and_grad", "grad", "vmap", "jvp", "vjp",
               "checkpoint", "remat"}


def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def method_owners(start, name: str, bases: dict, subs: dict,
                  defines) -> list:
    """The classes whose ``name`` a ``self.name()`` written in class
    ``start`` may run: ``start`` itself where it defines it, else the
    nearest ancestors that do, else every descendant that does (a hook
    the base calls and its subclasses supply).  ``bases``/``subs`` map a
    class to its direct bases/subclasses; ``defines(cls, name)`` says
    whether a class body defines the method."""
    if defines(start, name):
        return [start]
    for links, climb in ((bases, True), (subs, False)):
        found, seen, frontier = [], {start}, [start]
        while frontier:
            cur = frontier.pop()
            for rel in links.get(cur, ()):
                if rel in seen:
                    continue
                seen.add(rel)
                if defines(rel, name):
                    found.append(rel)
                    if climb:
                        continue     # nearer definition shadows further
                frontier.append(rel)
        if found:
            return found
    return []


def returned_closures(fnode: ast.AST) -> Set[str]:
    """Names of functions nested in ``fnode`` that it returns."""
    nested = {n.name for n in ast.walk(fnode)
              if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
              and n is not fnode}
    return {n.value.id for n in ast.walk(fnode)
            if isinstance(n, ast.Return) and isinstance(n.value, ast.Name)
            and n.value.id in nested}


class ModuleNode:
    """One parsed code file: its functions, import aliases, and the
    (unresolved) jit-root argument expressions found in it."""

    def __init__(self, name: str, path: str, tree: ast.Module):
        self.name = name                 # dotted module name
        self.path = path                 # repo-relative path
        self.tree = tree
        self.package = name.rsplit(".", 1)[0] if "." in name else ""
        #: local alias -> dotted module name (``import x.y as m``,
        #: ``from pkg import submodule``)
        self.mod_aliases: Dict[str, str] = {}
        #: local alias -> (module, function) (``from pkg.mod import fn``)
        self.func_aliases: Dict[str, FuncId] = {}
        #: local alias -> (module, class) (``from pkg.mod import Cls``)
        self.class_aliases: Dict[str, ClassId] = {}
        #: class name -> ClassDef
        self.classes: Dict[str, ast.ClassDef] = {}
        #: QUALIFIED function name ("fn" / "Cls.meth") -> FunctionDef
        self.functions: Dict[str, ast.FunctionDef] = {}
        #: bare name -> qualified names (collision-aware resolution)
        self.by_bare: Dict[str, List[str]] = {}
        #: raw ``from X import a [as b]`` entries kept for second-pass
        #: resolution once the full module set is known
        self._from_imports: List[Tuple[str, str, str]] = []
        self._collect()

    # ------------------------------------------------------- collection
    def _collect(self) -> None:
        def visit(node: ast.AST, cls: Optional[str]) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    self.classes[child.name] = child
                    visit(child, child.name)
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    qname = f"{cls}.{child.name}" if cls else child.name
                    self.functions[qname] = child
                    self.by_bare.setdefault(child.name,
                                            []).append(qname)
                    # nested defs keep the class context, mirroring the
                    # per-module index so qnames agree across layers
                    visit(child, cls)
                else:
                    visit(child, cls)

        visit(self.tree, None)
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    local = alias.asname or alias.name.split(".")[0]
                    target = alias.name if alias.asname else \
                        alias.name.split(".")[0]
                    self.mod_aliases[local] = target
            elif isinstance(node, ast.ImportFrom):
                base = self._resolve_from_base(node)
                if base is None:
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    local = alias.asname or alias.name
                    self._from_imports.append((local, base, alias.name))

    def resolve_local(self, name: str, cls: Optional[str] = None,
                      via_self: bool = False) -> Optional[str]:
        """Qualified local function for a referenced name.  Bare-name
        references prefer module level; ``self.x`` references prefer
        the caller's own class; either falls back to a UNIQUE bare
        match (ambiguity resolves to nothing — conservative)."""
        if via_self and cls is not None:
            q = f"{cls}.{name}"
            if q in self.functions:
                return q
        if not via_self and name in self.functions:
            return name
        cands = self.by_bare.get(name, [])
        return cands[0] if len(cands) == 1 else None

    def _resolve_from_base(self, node: ast.ImportFrom) -> Optional[str]:
        if node.level == 0:
            return node.module
        # relative: strip (level - 1) trailing components off the package
        parts = self.package.split(".") if self.package else []
        up = node.level - 1
        if up > len(parts):
            return None
        base = parts[:len(parts) - up] if up else parts
        if node.module:
            base = base + node.module.split(".")
        return ".".join(base) if base else None


class Program:
    """The whole-program graph over every analyzer-scoped code file."""

    def __init__(self, root: str,
                 files: Optional[Sequence[str]] = None) -> None:
        self.root = root
        self.modules: Dict[str, ModuleNode] = {}
        self.by_path: Dict[str, ModuleNode] = {}
        from tools.analyze import lint as _lint
        paths = list(files) if files is not None else _lint._code_files(root)
        for path in paths:
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            try:
                with open(path, encoding="utf-8") as fh:
                    tree = ast.parse(fh.read())
            except (SyntaxError, OSError):
                continue        # per-file syntax errors surface as SYN
            mod = ModuleNode(_module_name(rel), rel, tree)
            self.modules[mod.name] = mod
            self.by_path[rel] = mod
        self._finish_imports()
        self._bases: Dict[ClassId, List[ClassId]] = {}
        self._subs: Dict[ClassId, List[ClassId]] = {}
        self._link_classes()
        self._edges: Dict[FuncId, Set[FuncId]] = {}
        self._build_edges()

    # ------------------------------------------------ import resolution
    def _finish_imports(self) -> None:
        for mod in self.modules.values():
            for local, base, name in mod._from_imports:
                as_module = f"{base}.{name}"
                if as_module in self.modules:
                    mod.mod_aliases[local] = as_module
                elif base in self.modules and \
                        name in self.modules[base].functions:
                    mod.func_aliases[local] = (base, name)
                elif base in self.modules and \
                        name in self.modules[base].classes:
                    mod.class_aliases[local] = (base, name)

    def _link_classes(self) -> None:
        for mod in self.modules.values():
            for cname, cnode in mod.classes.items():
                cid = (mod.name, cname)
                for expr in cnode.bases:
                    bid = self._resolve_class(mod, expr)
                    if bid is not None:
                        self._bases.setdefault(cid, []).append(bid)
                        self._subs.setdefault(bid, []).append(cid)

    def _resolve_class(self, mod: ModuleNode,
                       expr: ast.AST) -> Optional[ClassId]:
        if isinstance(expr, ast.Name):
            if expr.id in mod.classes:
                return (mod.name, expr.id)
            return mod.class_aliases.get(expr.id)
        dotted = _dotted(expr)
        if dotted and "." in dotted:
            head, tail = dotted.rsplit(".", 1)
            target = self._resolve_attr_base(mod, head)
            if target is not None and tail in self.modules[target].classes:
                return (target, tail)
        return None

    def resolve_self(self, mod: ModuleNode, cls: Optional[str],
                     name: str) -> List[FuncId]:
        """Every definition a ``self.name`` written in ``cls`` may
        mean: through the class hierarchy (:func:`method_owners`), else
        the module-level / unique-bare-name fallback."""
        if cls is not None:
            owners = method_owners(
                (mod.name, cls), name, self._bases, self._subs,
                lambda c, n: f"{c[1]}.{n}" in self.modules[c[0]].functions)
            if owners:
                return [(m, f"{c}.{name}") for m, c in owners]
        local = mod.resolve_local(name, cls, via_self=True)
        return [(mod.name, local)] if local is not None else []

    def _resolve_attr_base(self, mod: ModuleNode,
                           base: str) -> Optional[str]:
        """Map a dotted receiver (``_monitor``, ``jax.lax``,
        ``deeplearning4j_tpu.nn.ingest``) to a known module name."""
        parts = base.split(".")
        if parts[0] in mod.mod_aliases:
            cand = ".".join([mod.mod_aliases[parts[0]]] + parts[1:])
            return cand if cand in self.modules else None
        return base if base in self.modules else None

    def resolve_ref(self, mod: ModuleNode, ref: ast.AST,
                    caller_cls: Optional[str] = None) -> List[FuncId]:
        """The (module, function)s a reference to a callable (a call's
        ``func``, a jit factory's function argument) resolves to; empty
        where it cannot be resolved."""
        if isinstance(ref, ast.Name):
            local = mod.resolve_local(ref.id)
            if local is not None:
                return [(mod.name, local)]
            alias = mod.func_aliases.get(ref.id)
            return [alias] if alias is not None else []
        if isinstance(ref, ast.Attribute):
            if isinstance(ref.value, ast.Name) and \
                    ref.value.id in ("self", "cls"):
                return self.resolve_self(mod, caller_cls, ref.attr)
            base = _dotted(ref.value)
            if base is None:
                return []
            target = self._resolve_attr_base(mod, base)
            if target is not None and \
                    ref.attr in self.modules[target].functions:
                return [(target, ref.attr)]
        return []

    @staticmethod
    def _cls_of(qname: str) -> Optional[str]:
        return qname.split(".", 1)[0] if "." in qname else None

    # ----------------------------------------------------- graph build
    def _build_edges(self) -> None:
        for mod in self.modules.values():
            for qname, fnode in mod.functions.items():
                src = (mod.name, qname)
                cls = self._cls_of(qname)
                edges = self._edges.setdefault(src, set())
                for sub in ast.walk(fnode):
                    if not isinstance(sub, ast.Call):
                        continue
                    edges.update(self.resolve_ref(mod, sub.func, cls))
                    name = _dotted(sub.func)
                    if name and sub.args and \
                            name.split(".")[-1] in _TRANSFORMS:
                        edges.update(self.resolve_ref(mod, sub.args[0],
                                                      cls))
                for nested in returned_closures(fnode):
                    edges.add((mod.name,
                               f"{cls}.{nested}" if cls else nested))
                edges.discard(src)

    def jit_roots(self) -> Set[FuncId]:
        roots: Set[FuncId] = set()
        for mod in self.modules.values():
            for qname, fnode in mod.functions.items():
                cls = self._cls_of(qname)
                for dec in fnode.decorator_list:
                    name = _dotted(dec if not isinstance(dec, ast.Call)
                                   else dec.func)
                    if name and name.split(".")[-1] in _JIT_FACTORIES:
                        roots.add((mod.name, qname))
                for node in ast.walk(fnode):
                    roots.update(self._factory_roots(mod, node, cls))
            for node in ast.walk(mod.tree):   # module-scope factories
                roots.update(self._factory_roots(mod, node, None))
        return roots

    def _factory_roots(self, mod: ModuleNode, node: ast.AST,
                       caller_cls: Optional[str]) -> List[FuncId]:
        if not isinstance(node, ast.Call) or not node.args:
            return []
        name = _dotted(node.func)
        if name is None:
            return []
        tail = name.split(".")[-1]
        is_scan = (tail == "scan" and name.split(".")[-2:-1] == ["lax"])
        if tail not in _JIT_FACTORIES and not is_scan:
            return []
        return self.resolve_ref(mod, node.args[0], caller_cls)

    def traced(self) -> Dict[str, Set[str]]:
        """module name -> bare names of jit-reachable functions, via the
        GLOBAL graph (the cross-module extension of R1 reachability)."""
        seen: Set[FuncId] = set()
        frontier = list(self.jit_roots())
        while frontier:
            cur = frontier.pop()
            if cur in seen:
                continue
            seen.add(cur)
            frontier.extend(d for d in self._edges.get(cur, ())
                            if d not in seen)
        out: Dict[str, Set[str]] = {}
        for m, f in seen:
            out.setdefault(m, set()).add(f)
        return out

    def blocking(self) -> Dict[str, Set[str]]:
        """module name -> bare names of functions that (transitively,
        across modules) perform a blocking call — R3's global fixpoint."""
        from tools.analyze import lint as _lint
        blocking: Set[FuncId] = set()
        # seed: functions with a DIRECT blocking primitive call
        for mod in self.modules.values():
            for fname, fnode in mod.functions.items():
                for sub in ast.walk(fnode):
                    if isinstance(sub, ast.Call) and \
                            _lint._is_blocking_call(sub, set()):
                        blocking.add((mod.name, fname))
                        break
        # reverse propagation over resolved edges to fixpoint
        changed = True
        while changed:
            changed = False
            for src, dsts in self._edges.items():
                if src not in blocking and dsts & blocking:
                    blocking.add(src)
                    changed = True
        out: Dict[str, Set[str]] = {}
        for m, f in blocking:
            out.setdefault(m, set()).add(f)
        return out

    def blocking_imports(
            self, blocking: Optional[Dict[str, Set[str]]] = None
    ) -> Dict[str, Set[str]]:
        """module name -> bare callable names VISIBLE in that module
        through its own imports (``from wire import _recv_exact``,
        ``from .. import wire``) that resolve to a blocking function
        defined elsewhere.  These feed R3's intra-module matcher so
        ``wire._recv_exact(...)`` under a lock is caught at the call
        site; only names a module actually imports are matched, keeping
        the attr-call match precise."""
        if blocking is None:
            blocking = self.blocking()
        bset = {(m, f) for m, fs in blocking.items() for f in fs}
        out: Dict[str, Set[str]] = {}
        for mod in self.modules.values():
            names: Set[str] = set()
            for local, fid in mod.func_aliases.items():
                if fid in bset:
                    names.add(local)
            for target in mod.mod_aliases.values():
                tm = self.modules.get(target)
                if tm is not None:
                    # only module-level names are reachable through a
                    # module alias (methods carry a "Cls." prefix)
                    names.update(f for f in tm.functions
                                 if "." not in f and (target, f) in bset)
            out[mod.name] = names
        return out


def _module_name(rel: str) -> str:
    name = rel[:-3] if rel.endswith(".py") else rel
    name = name.replace("/", ".")
    if name.endswith(".__init__"):
        name = name[: -len(".__init__")]
    return name


def load(root: str, files: Optional[Sequence[str]] = None) -> Program:
    return Program(root, files=files)
