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
"""

from __future__ import annotations

import hashlib
import os

from .. import monitor as _monitor

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
    _observe(path)
    return path


def stats(path: str) -> dict:
    """``{"dir", "entries", "bytes"}`` for the cache at ``path``.
    Entries are JAX ``*-cache`` files — the serialized executables, not
    the access-time sidecars."""
    if not os.path.isdir(path):
        return {"dir": path, "entries": 0, "bytes": 0}
    entries = n_bytes = 0
    for base, _dirs, files in os.walk(path):
        for name in files:
            if name.endswith("-atime"):
                continue
            entries += 1
            try:
                n_bytes += os.path.getsize(os.path.join(base, name))
            except OSError:
                pass
    return {"dir": path, "entries": entries, "bytes": n_bytes}


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
