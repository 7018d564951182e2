"""Persistent on-disk XLA executable cache: one resolver for every
entry point.

Compiling is the slow part of a cold start: a respawned fleet worker
re-AOT-compiles its whole bucket ladder, and a ResNet-50 ``fit`` step
compiles for minutes before its first dispatch.  JAX's persistent
compilation cache turns the second compile of any program into a disk
read.  Its entry key is JAX's own (computation, compile options,
backend) digest, so unrelated models share one directory safely.

:func:`enable` is the ONE place the directory is decided, and the entry
points (``chip_smoke.py``, ``bench.py``, the fleet worker) call it
first, before anything compiles:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module
  sets no directory, so the cache can be placed from outside (a
  launcher, the fleet router via its workers' environment).
- unset: the fixed path ``<checkout>/.jax_compile_cache`` (git-ignored).
  The path is part of JAX's key, so it must not move between runs.

Either way the cache-relevant knobs are pinned (min-entry-size,
min-compile-time): they feed JAX's entry key, so every process that
wants HITS, not just writes, must use the same values.

JAX's cache is asked AFTER a program was traced and lowered: its key is
a digest of the lowered module.  A warm start therefore still derives
every program it then finds compiled (a third of ResNet-50's warm
set-up, PERF.md section 5).  The **executable store**
(:class:`ExecutableStore`, ``<cache dir>/executables/``, installed by
:func:`enable`) is asked BEFORE tracing, by the watched jits whose call
site says what the traced function closes over
(``monitor.watched_jit(..., identity=...)``: the containers' two staged
``init()`` programs and their gather train step).

- The key, ``sha256(identity, name, signature, static and donated
  argnums, device kind, device count)``, names one program at one call
  site; the signature is the arguments' tree, dtypes, shapes, weak
  types and shardings, and static arguments by value.
- The entry's header holds what must ALSO match before the entry may
  run: the jax and jaxlib versions, the backend's ``platform_version``,
  ``XLA_FLAGS``, ``LIBTPU_INIT_ARGS``, every ``DL4J_TPU_*`` variable,
  the JAX options that change a trace (x64, matmul precision, PRNG) and
  a digest of the CONTENTS of every ``.py`` file of this package.  A
  header that differs is ``miss_stale``: the program is compiled afresh
  and the entry overwritten, so the store holds one entry a program and
  does not grow with every edit.  No stale executable is ever run.
- An entry that cannot be read whole (truncated, corrupt, from another
  format) is ``miss_unreadable`` and overwritten, never an error.
  Writers use a temporary file and an atomic rename, so two processes
  writing one key leave one whole entry.
- What the store cannot see: a process that replaces a function of the
  package in memory (a test's ``monkeypatch``).  The files still match,
  so such a process must not share a store with others.

:func:`stats` counts both caches.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import struct
import tempfile
from typing import Any, Dict, Optional, Sequence, Tuple

from .. import monitor as _monitor
from ..monitor import jit_watch as _jit_watch

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

#: where the cache lives when the environment does not say
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")

#: the knob set pinned by :func:`enable`; every process that wants
#: cache HITS (not just writes) must use these exact values, because
#: they feed JAX's entry key.
_PINNED_CONFIG = {
    "jax_persistent_cache_min_entry_size_bytes": -1,
    "jax_persistent_cache_min_compile_time_secs": 0.0,
}


#: the executable store's directory under the cache directory
EXECUTABLES_DIR = "executables"
_MAGIC = b"DL4JTPU-EXE1\n"
_TMP_SUFFIX = ".tmp"
#: the JAX options that change what a function traces to
_TRACE_OPTIONS = ("jax_enable_x64", "jax_default_matmul_precision",
                  "jax_default_prng_impl", "jax_threefry_partitionable",
                  "jax_numpy_dtype_promotion", "jax_numpy_rank_promotion")
_PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _executables_dir(cache_dir: str) -> str:
    """Where the store of the cache at ``cache_dir`` lives.  (The
    header vouches for the package's FILES: a process that patches the
    package in memory, as a test does, must keep a store of its own.
    ``tests/conftest.py`` points this at a directory a test.)"""
    return os.path.join(cache_dir, EXECUTABLES_DIR)


def package_digest(root: str = _PACKAGE_ROOT) -> str:
    """sha256 over the relative path and CONTENTS (not mtimes) of every
    ``.py`` file under ``root``, in sorted order."""
    h = hashlib.sha256()
    paths = []
    for base, dirs, files in os.walk(root):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        paths.extend(os.path.join(base, f) for f in files
                     if f.endswith(".py"))
    for path in sorted(paths):
        h.update(os.path.relpath(path, root).encode())
        h.update(b"\x00")
        with open(path, "rb") as fh:
            h.update(fh.read())
        h.update(b"\x00")
    return h.hexdigest()


def _versions() -> Dict[str, str]:
    """The installation an executable was built by and for."""
    import jax
    import jaxlib
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "platform_version": str(
                jax.devices()[0].client.platform_version)}


def _devices() -> Tuple[str, int]:
    import jax
    return str(jax.devices()[0].device_kind), int(jax.device_count())


def _environment() -> Dict[str, Any]:
    """What the process's environment and JAX options put into a trace
    or a compile besides the program."""
    import jax
    env = {k: v for k, v in os.environ.items()
           if k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
           or k.startswith("DL4J_TPU_")}
    options = {k: str(getattr(jax.config, k, None))
               for k in _TRACE_OPTIONS}
    return {"env": env, "options": options}


class ExecutableStore:
    """Serialized executables by what their program is built from, asked
    before tracing (module docstring).  One file an entry:
    ``magic | u32 header length | header JSON | payload``; the header
    holds the installation the payload was built by and the payload's
    length and sha256, the payload is a pickle of
    ``jax.experimental.serialize_executable``'s bytes, the two trees,
    the ids of the devices it runs on and the cost gauges."""

    def __init__(self, directory: str, package_root: str = _PACKAGE_ROOT):
        self.directory = directory
        self._package_root = package_root
        self._header: Optional[Dict[str, Any]] = None

    def header(self) -> Dict[str, Any]:
        """What must match before an entry may run; taken once a store
        (the package's digest reads a few MB)."""
        if self._header is None:
            self._header = dict(
                _versions(), **_environment(),
                package=package_digest(self._package_root))
        return self._header

    def key(self, identity, name: str, signature: str,
            static_argnums: Sequence[int] = (),
            donate_argnums: Sequence[int] = ()) -> str:
        kind, count = _devices()
        if isinstance(identity, str):
            identity = identity.encode()
        h = hashlib.sha256()
        for part in (identity, name.encode(), signature.encode(),
                     repr((tuple(static_argnums),
                           tuple(donate_argnums))).encode(),
                     kind.encode(), str(count).encode()):
            h.update(struct.pack("<Q", len(part)))
            h.update(part)
        return h.hexdigest()

    def path(self, key: str) -> str:
        return os.path.join(self.directory, key)

    def load(self, key: str):
        """``(result, executable, costs)``: ``hit`` with the loaded
        ``jax.stages.Compiled`` and its cost gauges, else
        ``miss_absent`` / ``miss_stale`` / ``miss_unreadable`` with
        ``None`` twice.  Never raises."""
        try:
            fh = open(self.path(key), "rb")
        except OSError:
            return "miss_absent", None, None
        try:
            with fh:
                if fh.read(len(_MAGIC)) != _MAGIC:
                    return "miss_unreadable", None, None
                (n,) = struct.unpack("<I", fh.read(4))
                header = json.loads(fh.read(n))
                payload_len = header.pop("payload_bytes")
                payload_sha = header.pop("payload_sha256")
                if header != self.header():
                    return "miss_stale", None, None
                payload = fh.read()
            if len(payload) != payload_len or hashlib.sha256(
                    payload).hexdigest() != payload_sha:
                return "miss_unreadable", None, None
            import jax
            from jax.experimental import serialize_executable
            (serialized, in_tree, out_tree, device_ids,
             costs) = pickle.loads(payload)
            by_id = {d.id: d for d in jax.devices()}
            exe = serialize_executable.deserialize_and_load(
                serialized, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
            return "hit", exe, costs
        except Exception:
            return "miss_unreadable", None, None

    def save(self, key: str, executable, costs: Dict[str, float],
             reloaded: bool = False) -> bool:
        """Serialize ``executable`` and put it under ``key``, whole or
        not at all (temporary file, then an atomic rename).  Raises
        where the executable cannot be serialized.  ``False``, with
        nothing written, for an executable that XLA:CPU itself
        ``reloaded`` from JAX's persistent cache: serialized again it
        loses its kernels ("Function ... not found" when run; jax
        0.9.0).  The TPU's serializes again whole."""
        import jax
        from jax.experimental import serialize_executable
        if reloaded and jax.devices()[0].platform == "cpu":
            return False
        # the devices it was compiled for, in their order: a loaded
        # executable runs on exactly those (where jax's own serializer
        # finds the executable, beside it)
        device_ids = [d.id for d in
                      executable._executable._unloaded_executable.device_list]
        payload = pickle.dumps(
            serialize_executable.serialize(executable)
            + (device_ids, dict(costs)),
            protocol=pickle.HIGHEST_PROTOCOL)
        header = json.dumps(dict(
            self.header(), payload_bytes=len(payload),
            payload_sha256=hashlib.sha256(payload).hexdigest()),
            sort_keys=True).encode()
        os.makedirs(self.directory, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.directory, prefix=key + ".",
                                   suffix=_TMP_SUFFIX)
        try:
            with os.fdopen(fd, "wb") as fh:
                fh.write(_MAGIC)
                fh.write(struct.pack("<I", len(header)))
                fh.write(header)
                fh.write(payload)
            os.replace(tmp, self.path(key))
        except BaseException:
            with contextlib.suppress(OSError):
                os.unlink(tmp)
            raise
        return True


def signature(conf, policy) -> str:
    """Identity of (model conf, backend, bucket policy) — what a fleet
    worker reports in its ready line so a router can tell which workers
    compile identical executables: the autotuner's model signature when
    ``tools`` ships alongside the package, else the same recipe computed
    locally (stripped deployments must produce identical values)."""
    try:
        from tools.autotune import model_signature
        return model_signature(conf, policy)
    except ImportError:
        try:
            conf_txt = conf.to_json(indent=None)
        except Exception:
            conf_txt = repr(conf)
        import jax
        payload = "|".join((conf_txt, jax.default_backend(),
                            policy.describe()))
        return hashlib.sha256(payload.encode()).hexdigest()[:16]


def enable() -> str:
    """Turn JAX's persistent compilation cache on for this process and
    return its directory.  Call before the first compile.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already holds that
    directory and none is set here; otherwise the directory is
    :data:`DEFAULT_CACHE_DIR`."""
    import jax
    path = os.environ.get(ENV_CACHE_DIR, "").strip()
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    for knob, value in _PINNED_CONFIG.items():
        jax.config.update(knob, value)
    store = _jit_watch.executable_store()
    directory = _executables_dir(path)
    if not isinstance(store, ExecutableStore) \
            or store.directory != directory:
        _jit_watch.set_executable_store(ExecutableStore(directory))
    _observe(path)
    return path


def stats(path: str) -> dict:
    """``{"dir", "entries", "bytes", "executables"}`` for the cache at
    ``path``.  Entries are JAX ``*-cache`` files — the serialized
    executables, not the access-time sidecars; ``executables`` is
    ``{"entries", "bytes"}`` of the executable store beneath it
    (whole entries, not a writer's temporary files)."""
    out = {"dir": path, "entries": 0, "bytes": 0,
           "executables": {"entries": 0, "bytes": 0}}
    if not os.path.isdir(path):
        return out
    store_dir = _executables_dir(path)
    for base, dirs, files in os.walk(path):
        dirs[:] = [d for d in dirs if os.path.join(base, d) != store_dir]
        for name in files:
            if name.endswith("-atime"):
                continue
            out["entries"] += 1
            try:
                out["bytes"] += os.path.getsize(os.path.join(base, name))
            except OSError:
                pass
    if os.path.isdir(store_dir):
        for name in os.listdir(store_dir):
            if name.endswith(_TMP_SUFFIX):
                continue
            try:
                size = os.path.getsize(os.path.join(store_dir, name))
            except OSError:
                continue
            out["executables"]["entries"] += 1
            out["executables"]["bytes"] += size
    return out


def _observe(path: str) -> None:
    snap = stats(path)
    _monitor.gauge(
        "fleet_compile_cache_entries",
        "serialized executables in the persistent compile cache").set(
        snap["entries"])
    _monitor.gauge(
        "fleet_compile_cache_bytes",
        "bytes of serialized executables in the persistent compile "
        "cache").set(snap["bytes"])
    _monitor.gauge(
        "executable_store_entries",
        "whole entries in the executable store").set(
        snap["executables"]["entries"])
    _monitor.gauge(
        "executable_store_bytes",
        "bytes of the executable store's entries").set(
        snap["executables"]["bytes"])
