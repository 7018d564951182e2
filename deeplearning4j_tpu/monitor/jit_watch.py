"""``watched_jit``: a ``jax.jit`` wrapper that makes recompiles visible.

``jax.jit`` retraces whenever the abstract signature of the arguments
changes — tree structure, leaf shapes/dtypes, or a static argument's
value.  Silent shape churn (ragged final batches, per-length tbptt
windows) turns a "compiled once" training loop into one that recompiles
every few steps, and nothing in the stack reports it.  ``WatchedJit``
computes the same abstract signature jax uses for its cache key and
keeps a seen-set per wrapped function, so it can tell a first-time
compile from a cache hit *before* dispatching:

- ``jit_compiles_total{fn=...}`` / ``jit_cache_hits_total{fn=...}``
  counters in the global registry;
- ``jit_compile_ms{fn=...}`` histogram — wall time of each compiling
  call (trace + compile + first dispatch; subsequent calls bypass all
  bookkeeping except one set lookup and a counter inc);
- a ``jit/compile/<name>`` tracing span whose ``signature`` attribute is
  the exact abstract shape that triggered the retrace, so the trace dump
  answers *why* it recompiled.

Where set-up time goes is counted from JAX's own compile events
(``jax.monitoring``, one listener registered at import): seconds spent
tracing, lowering to MLIR and in the backend (compiling, or loading
from the persistent cache) add into
``jit_trace_seconds_total{fn}`` / ``jit_lower_seconds_total{fn}`` /
``jit_backend_seconds_total{fn}``, ``fn`` being the watched jit whose
first call is on the stack (``<name>/cost_analysis`` for the extra
lowering behind the cost gauges, ``unwatched`` for everything else,
such as eager operations and a leaf-by-leaf ``init()``).  They grow on
compiles only, never on a cached dispatch.

A call site that says what its traced function closes over
(``identity=``) joins the **executable store**
(``serving/compile_cache.py``, installed by ``compile_cache.enable()``):
the first call of a signature asks the store BEFORE tracing, and a hit
runs the loaded executable with no trace and no lowering.
``executable_store_total{fn, result}`` counts ``hit`` /
``miss_absent`` / ``miss_stale`` / ``miss_unreadable`` / ``written``;
the load's seconds add into ``executable_store_load_seconds_total{fn}``
and into ``jit_backend_seconds_total{fn}``.  A hit counts as a cache
hit, not a compile.  Without ``identity``, or with no store installed,
nothing below differs from a plain ``jax.jit``.

Python scalars are weak-typed under jit — a value change does **not**
retrace — so they hash as ``int[]``/``float[]``/``bool[]`` rather than
by value.  ``static_argnums`` values **do** retrace, so they hash by
``repr``.  The AOT path (``.lower(...).compile()``, used by bench.py and
tools/hbm_profile.py) is proxied: ``compile()`` is timed and counted,
but does not feed the seen-set since jax's jit cache and the AOT cache
are separate.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import threading
import time
import warnings
from typing import (Any, Callable, Dict, Optional, Sequence, Set, Tuple,
                    Union)

import jax
import jax.monitoring

from . import health
from .metrics import registry
from .tracing import tracer

COMPILES_TOTAL = "jit_compiles_total"
CACHE_HITS_TOTAL = "jit_cache_hits_total"
COMPILE_MS = "jit_compile_ms"
XLA_FLOPS = "xla_cost_flops"
XLA_BYTES = "xla_cost_bytes_accessed"
XLA_PEAK_HBM = "xla_cost_peak_hbm_bytes"
TRACE_SECONDS = "jit_trace_seconds_total"
LOWER_SECONDS = "jit_lower_seconds_total"
BACKEND_SECONDS = "jit_backend_seconds_total"
STORE_TOTAL = "executable_store_total"
STORE_LOAD_SECONDS = "executable_store_load_seconds_total"
UNWATCHED = "unwatched"

_SAN = None
_SAN_TRIED = False


def _sanitizer():
    """The runtime dispatch sanitizer, or ``None`` when the tools
    package is absent (stripped deployments).  The import is attempted
    once and cached; the armed check stays a cheap env read so an
    unarmed process pays one ``dict.get`` per dispatch."""
    global _SAN, _SAN_TRIED
    if not _SAN_TRIED:
        _SAN_TRIED = True
        try:
            from tools.analyze import sanitizer as _mod
            _SAN = _mod
        except Exception:
            _SAN = None
    return _SAN


_HELP = {
    COMPILES_TOTAL: "jitted-function compilations (first call per "
                    "abstract signature)",
    CACHE_HITS_TOTAL: "jitted-function calls served from the trace cache",
    COMPILE_MS: "wall time of each compiling call (trace + compile + "
                "first dispatch, ms)",
    XLA_FLOPS: "XLA cost_analysis flop estimate of the executable's "
               "most recent compile",
    XLA_BYTES: "XLA cost_analysis bytes-accessed estimate of the "
               "executable's most recent compile",
    XLA_PEAK_HBM: "compiler memory_analysis peak HBM (args + outputs + "
                  "temps - aliased) of the most recent AOT compile",
    TRACE_SECONDS: "seconds tracing Python to jaxprs, by the watched jit "
                   "that was compiling",
    LOWER_SECONDS: "seconds lowering jaxprs to MLIR modules, by the "
                   "watched jit that was compiling",
    BACKEND_SECONDS: "seconds in the backend (compile, or the load from "
                     "the persistent cache), by the watched jit that was "
                     "compiling",
    STORE_TOTAL: "executable-store lookups and writes of watched jits "
                 "that gave an identity, by result (hit, miss_absent, "
                 "miss_stale, miss_unreadable, written)",
    STORE_LOAD_SECONDS: "seconds deserializing and loading executables "
                        "from the executable store (also counted in "
                        "jit_backend_seconds_total)",
}

# ------------------------------------------------------- set-up counters
_PRUNE_EVERY = 4096
_HORIZON_S = 3600.0         # no single trace or compile lasts an hour
_compiling = threading.local()


@contextlib.contextmanager
def _compiling_as(name: str):
    """Charge this thread's compile events to ``name`` meanwhile."""
    names = _compiling.__dict__.setdefault("names", [])
    names.append(name)
    try:
        yield
    finally:
        names.pop()


def _on_compile_duration(event: str, duration: float, **_) -> None:
    """Add one of JAX's compile events into its counter.  A jit called
    inside a trace fires its own event inside the outer one, and a
    listener sees only ``(event, duration)`` at the end: so each event
    adds the part of ``[now - duration, now]`` that earlier (inner)
    events of its kind on this thread have not counted, and the seconds
    add up to wall time."""
    reg = registry()
    # one call per counter, by constant: tools/analyze reads the
    # registrations from the source
    if event == "/jax/core/compile/jaxpr_trace_duration":
        counter = reg.counter(TRACE_SECONDS, _HELP[TRACE_SECONDS])
    elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        counter = reg.counter(LOWER_SECONDS, _HELP[LOWER_SECONDS])
    elif event == "/jax/core/compile/backend_compile_duration":
        counter = reg.counter(BACKEND_SECONDS, _HELP[BACKEND_SECONDS])
    else:
        return
    start = time.perf_counter() - duration
    # (start, seconds) of what this thread has counted for this event
    # and no enclosing event has absorbed yet
    counted = _compiling.__dict__.setdefault("counted", {}).setdefault(
        event, [])
    inner = 0.0
    while counted and counted[-1][0] >= start:
        inner += counted.pop()[1]
    counted.append((start, duration))
    if len(counted) % _PRUNE_EVERY == 0:
        # siblings wait here for their parent (7,500 jnp calls inside
        # ResNet-50's one trace); top-level events would wait for ever,
        # so now and then forget what no open event can still enclose
        counted[:] = [c for c in counted if c[0] >= start - _HORIZON_S]
    names = _compiling.__dict__.get("names")
    counter.inc(max(0.0, duration - inner),
                fn=names[-1] if names else UNWATCHED)


jax.monitoring.register_event_duration_secs_listener(_on_compile_duration)


def _on_event(event: str, **_) -> None:
    """Count this thread's hits in JAX's persistent compilation cache:
    the executable store asks whether a compile was such a load."""
    if event == "/jax/compilation_cache/cache_hits":
        _compiling.__dict__["cache_hits"] = _cache_hits() + 1


def _cache_hits() -> int:
    return _compiling.__dict__.get("cache_hits", 0)


jax.monitoring.register_event_listener(_on_event)


def cost_values(obj: Any) -> Dict[str, float]:
    """The compiler's self-reported costs of an executable, by gauge
    name.  ``obj`` is anything with a ``cost_analysis()`` (a ``Lowered``
    on the implicit-jit path, a ``Compiled`` on the AOT path) and
    optionally a ``memory_analysis()`` (Compiled only): flops and bytes
    accessed from the first, peak HBM (argument + output + temp -
    aliased bytes) from the second.  Every probe is best-effort:
    backends that do not implement an analysis are silently skipped."""
    values: Dict[str, float] = {}
    try:
        cost = obj.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        if cost:
            flops = cost.get("flops")
            if flops is not None:
                values[XLA_FLOPS] = float(flops)
            nbytes = cost.get("bytes accessed",
                              cost.get("bytes_accessed"))
            if nbytes is not None:
                values[XLA_BYTES] = float(nbytes)
    except Exception:
        pass
    try:
        mem = obj.memory_analysis()
        if isinstance(mem, (list, tuple)):
            mem = mem[0] if mem else None
        if mem is not None:
            peak = (float(getattr(mem, "argument_size_in_bytes", 0.0))
                    + float(getattr(mem, "output_size_in_bytes", 0.0))
                    + float(getattr(mem, "temp_size_in_bytes", 0.0))
                    - float(getattr(mem, "alias_size_in_bytes", 0.0)))
            if peak > 0:
                values[XLA_PEAK_HBM] = peak
    except Exception:
        pass
    return values


def _publish_costs(name: str, values: Dict[str, float]) -> None:
    """Set the cost gauges of ``fn=name`` from :func:`cost_values`'s
    dict (taken now, or kept beside a stored executable)."""
    reg = registry()
    # one call per gauge, by constant: tools/analyze reads the
    # registrations from the source
    if XLA_FLOPS in values:
        reg.gauge(XLA_FLOPS, _HELP[XLA_FLOPS]).set(
            values[XLA_FLOPS], fn=name)
    if XLA_BYTES in values:
        reg.gauge(XLA_BYTES, _HELP[XLA_BYTES]).set(
            values[XLA_BYTES], fn=name)
    if XLA_PEAK_HBM in values:
        reg.gauge(XLA_PEAK_HBM, _HELP[XLA_PEAK_HBM]).set(
            values[XLA_PEAK_HBM], fn=name)


def publish_cost_analysis(name: str, obj: Any) -> None:
    """Publish compiler self-reported cost gauges for an executable:
    ``xla_cost_flops{fn=name}``, ``xla_cost_bytes_accessed{fn=name}``
    and ``xla_cost_peak_hbm_bytes{fn=name}`` (:func:`cost_values`)."""
    _publish_costs(name, cost_values(obj))


# Signature construction is on the dispatch hot path (every watched
# call, even steady-state cache hits), and ``str(treedef)`` on a
# params-sized pytree costs ~100µs — more than the jitted dispatch it
# wraps for single-token decode.  Treedefs and (dtype, shape) pairs are
# hashable and few, so both stringifications are memoised; a serving
# loop at a warm signature pays only dict lookups.  Bounded clears keep
# a pathological shape churn from growing the memos without bound.
_TREEDEF_STRS: dict = {}
_LEAF_DESCS: dict = {}
_MEMO_LIMIT = 4096


def _treedef_str(treedef) -> str:
    s = _TREEDEF_STRS.get(treedef)
    if s is None:
        if len(_TREEDEF_STRS) >= _MEMO_LIMIT:
            _TREEDEF_STRS.clear()
        s = _TREEDEF_STRS[treedef] = str(treedef)
    return s


def _leaf_desc(leaf: Any) -> str:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        try:
            desc = _LEAF_DESCS.get((dtype, shape))
        except TypeError:  # unhashable exotic dtype/shape: build direct
            return f"{dtype}[{','.join(str(d) for d in shape)}]"
        if desc is None:
            if len(_LEAF_DESCS) >= _MEMO_LIMIT:
                _LEAF_DESCS.clear()
            desc = f"{dtype}[{','.join(str(d) for d in shape)}]"
            _LEAF_DESCS[(dtype, shape)] = desc
        return desc
    # Weak-typed python scalars: value changes do not retrace.
    if isinstance(leaf, bool):
        return "bool[]"
    if isinstance(leaf, int):
        return "int[]"
    if isinstance(leaf, float):
        return "float[]"
    if isinstance(leaf, complex):
        return "complex[]"
    return repr(leaf)


def _signature(args: Tuple, kwargs: dict, static_argnums: Sequence[int],
               tree: Callable[[Any], str],
               leaf: Callable[[Any], str]) -> str:
    static = set(static_argnums or ())
    parts = []
    for i, arg in enumerate(args):
        if i in static:
            parts.append(f"static{i}={arg!r}")
        else:
            leaves, treedef = jax.tree_util.tree_flatten(arg)
            descs = ",".join(leaf(l) for l in leaves)
            parts.append(f"{tree(treedef)}:{descs}")
    for k in sorted(kwargs):
        leaves, treedef = jax.tree_util.tree_flatten(kwargs[k])
        descs = ",".join(leaf(l) for l in leaves)
        parts.append(f"{k}={tree(treedef)}:{descs}")
    return "; ".join(parts)


def abstract_signature(args: Tuple, kwargs: dict,
                       static_argnums: Sequence[int] = ()) -> str:
    """A string mirroring jax.jit's cache key for this call: static args
    by value, dynamic args by treedef + per-leaf ``dtype[shape]``."""
    return _signature(args, kwargs, static_argnums, _treedef_str,
                      _leaf_desc)


# ------------------------------------------------------ executable store
# ``serving.compile_cache.enable()`` installs the store; this module
# only asks it.  A store has ``key(identity, name, signature,
# static_argnums, donate_argnums) -> str``, ``load(key) -> (result,
# executable, costs)`` with ``result`` one of ``hit`` / ``miss_absent``
# / ``miss_stale`` / ``miss_unreadable``, and ``save(key, executable,
# costs, reloaded) -> bool`` (``reloaded``: JAX's own persistent cache
# served the compile).
_STORE = None
_UNSERVED = object()


def set_executable_store(store) -> None:
    """Install (or, with ``None``, remove) the process's executable
    store.  Watched jits built before or after see it at their next
    first call of a signature."""
    global _STORE
    _STORE = store


def executable_store():
    return _STORE


def _store_leaf(leaf: Any) -> str:
    """One leaf for the store's key: dtype, shape and weak type as
    tracing sees them, and where an array lives (the executable is
    compiled for those devices)."""
    try:
        desc = repr(jax.typeof(leaf))
    except Exception:
        return repr(leaf)
    sharding = getattr(leaf, "sharding", None)
    return desc if sharding is None else f"{desc}@{sharding!r}"


def store_signature(args: Tuple, kwargs: dict,
                    static_argnums: Sequence[int] = ()) -> str:
    """:func:`abstract_signature` with what else decides the compiled
    program: weak types and shardings.  Computed on first calls only."""
    return _signature(args, kwargs, static_argnums, str, _store_leaf)


def identity_digest(*parts: Any) -> str:
    """A digest of what a traced function reads besides its arguments,
    for ``watched_jit(..., identity=...)``: each part as canonical JSON
    where it has one, else its ``repr``."""
    h = hashlib.sha256()
    for part in parts:
        try:
            text = json.dumps(part, sort_keys=True)
        except (TypeError, ValueError):
            text = repr(part)
        h.update(text.encode())
        h.update(b"\x00")
    return h.hexdigest()


_PACKAGE = __name__.split(".")[0] + "."


def _foreign_code(value: Any) -> bool:
    """True where a configuration holds an object whose class is defined
    outside this package: its code is in nobody's digest."""
    if dataclasses.is_dataclass(value) and not isinstance(value, type):
        if not type(value).__module__.startswith(_PACKAGE):
            return True
        return any(_foreign_code(getattr(value, f.name))
                   for f in dataclasses.fields(value))
    if isinstance(value, dict):
        return any(_foreign_code(v) for v in value.values())
    if isinstance(value, (list, tuple)):
        return any(_foreign_code(v) for v in value)
    return False


def program_identity(model, *parts: Any) -> Optional[str]:
    """The ``identity`` of a container's programs: what the step
    builders and ``init()`` of a ``MultiLayerNetwork`` or a
    ``ComputationGraph`` read besides their arguments.  That is the
    conf's JSON without its seed (the seed reaches a program as a key,
    an argument), the resolved precision policy, the health
    configuration, the layer order, and ``parts`` (which program, which
    build).  ``None``, which opts the call out of the store, where the
    conf holds a layer, vertex or preprocessor class from outside the
    package, or an activation registered from outside: the store's
    header digests this package's sources only."""
    from ..nn import activations
    if _foreign_code(model.conf) or any(
            not getattr(fn, "__module__", "").startswith(_PACKAGE)
            for fn in activations._ACTIVATIONS.values()):
        return None
    conf = model.conf.to_dict()
    if isinstance(conf.get("conf"), dict):
        conf["conf"].pop("seed", None)
    cfg = health.config()
    return identity_digest(
        conf, model._pol().describe(),
        (cfg.enabled, cfg.policy, cfg.grad_norm_limit),
        health.layer_labels(model), *parts)


class _LoweredProxy:
    """Wraps ``jitted.lower(...)`` so the explicit AOT ``compile()`` is
    timed and counted like an implicit one."""

    def __init__(self, lowered, name: str, signature: str):
        self._lowered = lowered
        self._name = name
        self._signature = signature

    def compile(self, *args, **kwargs):
        reg = registry()
        t0 = time.perf_counter()
        with tracer().span(f"jit/compile/{self._name}", mode="aot",
                           signature=self._signature), \
                _compiling_as(self._name):
            compiled = self._lowered.compile(*args, **kwargs)
        elapsed = time.perf_counter() - t0
        reg.counter(COMPILES_TOTAL, _HELP[COMPILES_TOTAL]).inc(
            fn=self._name)
        reg.histogram(COMPILE_MS, _HELP[COMPILE_MS]).observe(
            elapsed * 1e3, fn=self._name)
        publish_cost_analysis(self._name, compiled)
        return compiled

    def __getattr__(self, item):
        return getattr(self._lowered, item)


class WatchedJit:
    """Callable wrapper around ``jax.jit(fn, ...)`` that records compile
    vs cache-hit telemetry into the global monitor registry/tracer.

    ``identity`` (bytes or str, or a callable giving one at the first
    call of a signature; ``None`` from the callable opts that call out)
    is a digest of everything ``fn`` reads that is not an argument.  A
    site that gives one is served by the executable store when one is
    installed; a site that gives none never touches it."""

    def __init__(self, fn: Callable, name: Optional[str] = None,
                 static_argnums: Sequence[int] = (),
                 donate_argnums: Sequence[int] = (),
                 identity: Union[None, bytes, str,
                                 Callable[[], Union[None, bytes, str]]]
                 = None, **jit_kwargs):
        self._fn = fn
        self.name = name or getattr(fn, "__name__", "jit_fn")
        self._static_argnums = tuple(static_argnums or ())
        self._donate_argnums = tuple(donate_argnums or ())
        jit_kw = dict(jit_kwargs)
        if self._static_argnums:
            jit_kw["static_argnums"] = self._static_argnums
        if self._donate_argnums:
            jit_kw["donate_argnums"] = self._donate_argnums
        self._jitted = jax.jit(fn, **jit_kw)
        self._seen: Set[str] = set()
        # the store keys on argnums alone: other jit options opt out
        self._identity = identity if not jit_kwargs else None
        # signature -> the executable the store serves it with (loaded
        # on a hit, compiled ahead of time on a miss)
        self._executables: Dict[str, Any] = {}
        self.__wrapped__ = fn

    def _run(self, signature, args, kwargs):
        """The call itself: the store's executable for this signature
        where there is one (it takes the dynamic arguments only), else
        the jitted function."""
        exe = self._executables.get(signature)
        if exe is None:
            return self._jitted(*args, **kwargs)
        dynamic = args
        if self._static_argnums:
            dynamic = tuple(a for i, a in enumerate(args)
                            if i not in self._static_argnums)
        try:
            return exe(*dynamic, **kwargs)
        except TypeError:
            # the same shapes under another weak type or placement are
            # another program (raised before anything runs or is
            # donated): jit decides
            del self._executables[signature]
            return self._jitted(*args, **kwargs)

    def _dispatch(self, signature, args, kwargs, san):
        """The actual call; when the sanitizer is armed and this
        function donates, verify each donated input buffer actually
        reports deleted afterwards (jax skips unusable donation with no
        warning — the silent HBM regression the audit exists for)."""
        if san is None or not self._donate_argnums \
                or not san.donation_audit():
            return self._run(signature, args, kwargs)
        donated = []
        for pos in self._donate_argnums:
            if pos < len(args):
                donated.extend(
                    leaf for leaf in jax.tree_util.tree_leaves(args[pos])
                    if isinstance(leaf, jax.Array))
        out = self._run(signature, args, kwargs)
        if donated:
            missed = sum(1 for leaf in donated if not leaf.is_deleted())
            san.record_donation(self.name, missed=missed,
                                total=len(donated))
        return out

    def _first_call_stored(self, store, signature, recompile, args,
                           kwargs, san):
        """The first call of a signature at a site that gave an
        identity: ask the store BEFORE tracing.  A hit runs the loaded
        executable and counts as a cache hit.  A miss does today's work
        ahead of time (``lower().compile()``), runs the executable and
        writes it.  ``_UNSERVED`` where the store cannot take the call
        at all; the caller then runs today's path."""
        reg = registry()
        try:
            identity = (self._identity() if callable(self._identity)
                        else self._identity)
            if identity is None:
                return _UNSERVED
            key = store.key(
                identity, self.name,
                store_signature(args, kwargs, self._static_argnums),
                self._static_argnums, self._donate_argnums)
            t0 = time.perf_counter()
            result, exe, costs = store.load(key)
            load_s = time.perf_counter() - t0
        except Exception as exc:
            warnings.warn(f"executable store: {self.name} is not served "
                          f"({type(exc).__name__}: {exc})")
            return _UNSERVED
        reg.counter(STORE_TOTAL, _HELP[STORE_TOTAL]).inc(
            fn=self.name, result=result)
        if result == "hit":
            reg.counter(STORE_LOAD_SECONDS, _HELP[STORE_LOAD_SECONDS]).inc(
                load_s, fn=self.name)
            reg.counter(BACKEND_SECONDS, _HELP[BACKEND_SECONDS]).inc(
                load_s, fn=self.name)
            if not recompile:
                _publish_costs(self.name, costs)
            reg.counter(CACHE_HITS_TOTAL, _HELP[CACHE_HITS_TOTAL]).inc(
                fn=self.name)
            if san is not None:
                san.record_dispatch(self.name, compiled=False,
                                    recompile=False)
            self._executables[signature] = exe
            return self._dispatch(signature, args, kwargs, san)
        if san is not None:
            san.record_dispatch(self.name, compiled=True,
                                recompile=recompile)
        t0 = time.perf_counter()
        cache_hits = _cache_hits()
        with tracer().span(f"jit/compile/{self.name}",
                           signature=signature, recompile=recompile), \
                _compiling_as(self.name):
            lowered = self._jitted.lower(*args, **kwargs)
            exe = lowered.compile()
            reloaded = _cache_hits() > cache_hits
            self._executables[signature] = exe
            out = self._dispatch(signature, args, kwargs, san)
        elapsed = time.perf_counter() - t0
        reg.counter(COMPILES_TOTAL, _HELP[COMPILES_TOTAL]).inc(fn=self.name)
        reg.histogram(COMPILE_MS, _HELP[COMPILE_MS]).observe(
            elapsed * 1e3, fn=self.name)
        costs = cost_values(lowered)
        if not recompile:
            _publish_costs(self.name, costs)
        # written while the device runs the call above
        try:
            if store.save(key, exe, costs, reloaded=reloaded):
                reg.counter(STORE_TOTAL, _HELP[STORE_TOTAL]).inc(
                    fn=self.name, result="written")
        except Exception as exc:
            warnings.warn(f"executable store: {self.name} was not "
                          f"written ({type(exc).__name__}: {exc})")
        return out

    def __call__(self, *args, **kwargs):
        signature = abstract_signature(args, kwargs, self._static_argnums)
        reg = registry()
        san = _sanitizer()
        if san is not None and not san.enabled():
            san = None
        if signature in self._seen:
            reg.counter(CACHE_HITS_TOTAL, _HELP[CACHE_HITS_TOTAL]).inc(
                fn=self.name)
            if san is not None:
                san.record_dispatch(self.name, compiled=False,
                                    recompile=False)
            return self._dispatch(signature, args, kwargs, san)
        recompile = bool(self._seen)
        self._seen.add(signature)
        if self._identity is not None and _STORE is not None:
            out = self._first_call_stored(_STORE, signature, recompile,
                                          args, kwargs, san)
            if out is not _UNSERVED:
                return out
        if san is not None:
            san.record_dispatch(self.name, compiled=True,
                                recompile=recompile)
        if not recompile:
            # Cost gauges for the first signature only: .lower() traces
            # without compiling or consuming donated buffers, and one
            # extra trace per WatchedJit bounds the overhead.
            try:
                with _compiling_as(f"{self.name}/cost_analysis"):
                    lowered = self._jitted.lower(*args, **kwargs)
                publish_cost_analysis(self.name, lowered)
            except Exception:
                pass
        t0 = time.perf_counter()
        with tracer().span(f"jit/compile/{self.name}",
                           signature=signature, recompile=recompile), \
                _compiling_as(self.name):
            out = self._dispatch(signature, args, kwargs, san)
        elapsed = time.perf_counter() - t0
        reg.counter(COMPILES_TOTAL, _HELP[COMPILES_TOTAL]).inc(fn=self.name)
        reg.histogram(COMPILE_MS, _HELP[COMPILE_MS]).observe(
            elapsed * 1e3, fn=self.name)
        return out

    def lower(self, *args, **kwargs) -> _LoweredProxy:
        signature = abstract_signature(args, kwargs, self._static_argnums)
        with _compiling_as(self.name):
            lowered = self._jitted.lower(*args, **kwargs)
        return _LoweredProxy(lowered, self.name, signature)

    @property
    def compile_count(self) -> int:
        return len(self._seen)

    def __getattr__(self, item):
        # Fallback for jitted-function attributes (e.g. clear_cache).
        return getattr(self._jitted, item)


def watched_jit(fn: Callable, name: Optional[str] = None,
                **kwargs) -> WatchedJit:
    """Drop-in for ``jax.jit(fn, ...)`` with compile-watch telemetry.
    Extra keyword arguments (``donate_argnums``, ``static_argnums``, …)
    pass through to ``jax.jit``; ``identity`` joins the executable
    store (:class:`WatchedJit`)."""
    return WatchedJit(fn, name=name, **kwargs)
