"""Native (C++) runtime bindings.

SURVEY.md §2.11: the reference's performance-critical tier is C++ loaded
over JavaCPP (ND4J backends, cuDNN helpers, datavec readers).  This
package binds the TPU build's C++ equivalents from ``native/`` via
ctypes:

- :class:`PjrtClient` — PJRT C API client (``native/pjrt_shim.cc``):
  dlopen a PJRT plugin, create a client, enumerate devices, compile and
  execute StableHLO from C++ (the ND4J-backend role, rebased onto PJRT).
- IDX / CIFAR binary decoders and :class:`NativePrefetcher` — the native
  ETL + async-prefetch role (``native/dataloader.cc``).

The shared library builds on demand with ``make`` (g++ is in the image;
the PJRT header comes from the image's tensorflow package).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import List, Optional, Sequence, Tuple

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libdl4jtpu_native.so")

def _default_plugin_paths():
    """PJRT plugins known to this installation: the libtpu wheel."""
    try:
        import libtpu
    except ImportError:
        return ()
    return (os.path.join(os.path.dirname(libtpu.__file__), "libtpu.so"),)


DEFAULT_PLUGIN_PATHS = _default_plugin_paths()

_lib: Optional[ctypes.CDLL] = None


def _source_digest() -> str:
    """sha256 over the files the library is built from."""
    h = hashlib.sha256()
    for name in sorted(os.listdir(_NATIVE_DIR)):
        if name == "Makefile" or name.endswith((".cc", ".h")):
            with open(os.path.join(_NATIVE_DIR, name), "rb") as fh:
                h.update(name.encode() + b"\0" + fh.read())
    return h.hexdigest()


def build_native(force: bool = False) -> str:
    """Compile ``native/`` into the shared library.  A no-op only when
    the library on disk was built from the CURRENT sources: the build
    records their digest beside the binary, and a binary without a
    matching record (copied from another tree, left by an older
    checkout) is rebuilt rather than loaded."""
    stamp = _LIB_PATH + ".src-sha256"
    digest = _source_digest()
    try:
        with open(stamp) as fh:
            current = (not force and os.path.exists(_LIB_PATH)
                       and fh.read().strip() == digest)
    except OSError:
        current = False
    if not current:
        proc = subprocess.run(["make", "-B"], cwd=_NATIVE_DIR,
                              capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                "native build failed:\n" + (proc.stderr or proc.stdout)
                [-2000:])
        with open(stamp, "w") as fh:
            fh.write(digest)
    return _LIB_PATH


def load_native() -> ctypes.CDLL:
    """Load (building if needed) the native library and declare ABIs."""
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(build_native())

    lib.dl4j_idx_info.restype = ctypes.c_int
    lib.dl4j_idx_info.argtypes = [ctypes.c_char_p,
                                  ctypes.POINTER(ctypes.c_int64),
                                  ctypes.c_int]
    lib.dl4j_idx_decode.restype = ctypes.c_int64
    lib.dl4j_idx_decode.argtypes = [ctypes.c_char_p,
                                    ctypes.POINTER(ctypes.c_float),
                                    ctypes.c_int64, ctypes.c_int]
    lib.dl4j_cifar_decode.restype = ctypes.c_int64
    lib.dl4j_cifar_decode.argtypes = [ctypes.c_char_p,
                                      ctypes.POINTER(ctypes.c_float),
                                      ctypes.POINTER(ctypes.c_int32),
                                      ctypes.c_int64]

    lib.dl4j_prefetcher_create.restype = ctypes.c_void_p
    lib.dl4j_prefetcher_create.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int, ctypes.c_uint64]
    lib.dl4j_prefetcher_next.restype = ctypes.c_int
    lib.dl4j_prefetcher_next.argtypes = [ctypes.c_void_p,
                                         ctypes.POINTER(ctypes.c_float),
                                         ctypes.POINTER(ctypes.c_float)]
    lib.dl4j_prefetcher_destroy.restype = None
    lib.dl4j_prefetcher_destroy.argtypes = [ctypes.c_void_p]

    lib.dl4j_pjrt_client_create.restype = ctypes.c_void_p
    lib.dl4j_pjrt_client_create.argtypes = [ctypes.c_char_p,
                                            ctypes.c_char_p, ctypes.c_int]
    lib.dl4j_pjrt_client_create_opts.restype = ctypes.c_void_p
    lib.dl4j_pjrt_client_create_opts.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_char_p,
        ctypes.c_int]
    lib.dl4j_pjrt_client_destroy.restype = None
    lib.dl4j_pjrt_client_destroy.argtypes = [ctypes.c_void_p]
    lib.dl4j_pjrt_api_version.restype = ctypes.c_int
    lib.dl4j_pjrt_api_version.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_int),
                                          ctypes.POINTER(ctypes.c_int)]
    lib.dl4j_pjrt_platform_name.restype = ctypes.c_int
    lib.dl4j_pjrt_platform_name.argtypes = [ctypes.c_void_p,
                                            ctypes.c_char_p, ctypes.c_int]
    lib.dl4j_pjrt_device_count.restype = ctypes.c_int
    lib.dl4j_pjrt_device_count.argtypes = [ctypes.c_void_p]
    lib.dl4j_pjrt_run_mlir.restype = ctypes.c_int
    lib.dl4j_pjrt_run_mlir.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)), ctypes.c_int,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
        ctypes.c_char_p, ctypes.c_int]

    lib.dl4j_pjrt_compile_cached.restype = ctypes.c_int64
    lib.dl4j_pjrt_compile_cached.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int), ctypes.c_char_p, ctypes.c_int]
    lib.dl4j_pjrt_cache_stats.restype = ctypes.c_int
    lib.dl4j_pjrt_cache_stats.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
    lib.dl4j_pjrt_cache_clear.restype = ctypes.c_int64
    lib.dl4j_pjrt_cache_clear.argtypes = [ctypes.c_void_p]
    lib.dl4j_pjrt_cache_evict.restype = ctypes.c_int64
    lib.dl4j_pjrt_cache_evict.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.dl4j_pjrt_exec_num_outputs.restype = ctypes.c_int
    lib.dl4j_pjrt_exec_num_outputs.argtypes = [ctypes.c_void_p,
                                               ctypes.c_int64]
    lib.dl4j_pjrt_exec_output_info.restype = ctypes.c_int
    lib.dl4j_pjrt_exec_output_info.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.c_int]
    lib.dl4j_pjrt_dtype_code.restype = ctypes.c_int
    lib.dl4j_pjrt_dtype_code.argtypes = [ctypes.c_char_p]
    lib.dl4j_pjrt_execute.restype = ctypes.c_int
    lib.dl4j_pjrt_execute.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
    lib.dl4j_pjrt_buffer_from_host.restype = ctypes.c_int64
    lib.dl4j_pjrt_buffer_from_host.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_char_p,
        ctypes.c_int]
    lib.dl4j_pjrt_buffer_free.restype = ctypes.c_int
    lib.dl4j_pjrt_buffer_free.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.dl4j_pjrt_execute_mixed.restype = ctypes.c_int
    lib.dl4j_pjrt_execute_mixed.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.c_char_p, ctypes.c_int]

    _lib = lib
    return lib


def _np_dtype_name(dt: "np.dtype") -> str:
    """Numpy (incl. ml_dtypes.bfloat16) dtype → shim dtype-name string."""
    name = np.dtype(dt).name
    return {"bool": "pred"}.get(name, name)


def _name_to_np(name: str):
    """Shim dtype-name → numpy dtype (bf16 via ml_dtypes)."""
    if name in ("bf16", "bfloat16"):
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    if name in ("pred", "bool"):
        return np.dtype(np.bool_)
    short = {"f16": "float16", "f32": "float32", "f64": "float64",
             "s8": "int8", "s16": "int16", "s32": "int32", "s64": "int64",
             "u8": "uint8", "u16": "uint16", "u32": "uint32",
             "u64": "uint64"}
    return np.dtype(short.get(name, name))


def _fptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


# ----------------------------------------------------------- data loading

def idx_decode(path: str, normalize: bool = True) -> np.ndarray:
    """Decode an IDX file natively; returns the shaped float32 array."""
    lib = load_native()
    dims = (ctypes.c_int64 * 4)()
    ndim = lib.dl4j_idx_info(path.encode(), dims, 4)
    if ndim < 0:
        raise ValueError(f"not an IDX file: {path}")
    shape = tuple(int(dims[i]) for i in range(ndim))
    out = np.empty(int(np.prod(shape)), np.float32)
    wrote = lib.dl4j_idx_decode(path.encode(), _fptr(out), out.size,
                                1 if normalize else 0)
    if wrote != out.size:
        raise ValueError(f"IDX decode failed for {path}")
    return out.reshape(shape)


def cifar_decode(path: str) -> Tuple[np.ndarray, np.ndarray]:
    """Decode a CIFAR-10 binary batch natively; (NHWC [0,1] images,
    int labels)."""
    lib = load_native()
    size = os.path.getsize(path)
    n = size // (1 + 3 * 32 * 32)
    images = np.empty((n, 32, 32, 3), np.float32)
    labels = np.empty(n, np.int32)
    got = lib.dl4j_cifar_decode(
        path.encode(), _fptr(images),
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), n)
    if got < 0:
        raise ValueError(f"CIFAR decode failed for {path}")
    return images[:got], labels[:got]


class NativePrefetcher:
    """Threaded C++ minibatch prefetcher (reference
    ``AsyncDataSetIterator`` role): per-epoch shuffle + batch gather run
    on a native thread, off the GIL.  Yields (features, labels) numpy
    pairs forever; bound memory (``capacity`` slots)."""

    def __init__(self, features: np.ndarray, labels: np.ndarray,
                 batch: int, capacity: int = 4, seed: int = 42):
        lib = load_native()
        # keep alive + enforce dense float32
        self._f = np.ascontiguousarray(features, np.float32) \
            .reshape(features.shape[0], -1)
        self._l = np.ascontiguousarray(labels, np.float32) \
            .reshape(labels.shape[0], -1)
        self.batch = int(batch)
        self._feat_shape = features.shape[1:]
        self._label_shape = labels.shape[1:]
        self._h = lib.dl4j_prefetcher_create(
            _fptr(self._f), _fptr(self._l), self._f.shape[0],
            self._f.shape[1], self._l.shape[1], self.batch,
            int(capacity), seed)
        if not self._h:
            raise ValueError("prefetcher creation failed (check batch <= n)")
        self._lib = lib

    def next(self) -> Tuple[np.ndarray, np.ndarray]:
        feats = np.empty((self.batch,) + tuple(self._feat_shape),
                         np.float32)
        labels = np.empty((self.batch,) + tuple(self._label_shape),
                          np.float32)
        rc = self._lib.dl4j_prefetcher_next(self._h, _fptr(feats),
                                            _fptr(labels))
        if rc != 0:
            raise RuntimeError("prefetcher stopped")
        return feats, labels

    def close(self) -> None:
        if self._h:
            self._lib.dl4j_prefetcher_destroy(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ------------------------------------------------------------ PJRT client

# Probe results per plugin path ("" = default search), cached for the
# process lifetime: (usable, reason).
_PLUGIN_PROBE_CACHE: dict = {}


def pjrt_plugin_usable(plugin_path: Optional[str] = None,
                       timeout: float = 90.0) -> Tuple[bool, str]:
    """Report whether creating a ``PjrtClient`` in this process is safe.

    A plugin can hard-``abort()`` the host process from inside
    ``PJRT_Client_Create`` when its environment is missing (no TPU
    system on the host) — a failure mode no ``try/except`` can catch.
    So the first creation attempt runs in a disposable subprocess; only
    if that survives does the caller dlopen the plugin in-process.
    Results are cached per path for the process lifetime.  The probe
    child takes the chip while it runs and releases it on exit, so it
    needs the chip to be free (:func:`_require_chip_free` is checked
    first).

    ``DL4J_TPU_PJRT=0`` marks every plugin unusable (``PjrtClient``
    raises; nothing falls back); ``DL4J_TPU_PJRT_PROBE=0`` skips
    the subprocess and trusts the plugin (production, where the probe's
    startup cost is unwanted and the environment is known good).
    """
    if os.environ.get("DL4J_TPU_PJRT", "").strip() == "0":
        return False, "disabled via DL4J_TPU_PJRT=0"
    if os.environ.get("DL4J_TPU_PJRT_PROBE", "").strip() == "0":
        return True, "probe skipped via DL4J_TPU_PJRT_PROBE=0"
    key = plugin_path or ""
    cached = _PLUGIN_PROBE_CACHE.get(key)
    if cached is not None:
        return cached
    import subprocess
    import sys
    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ, DL4J_TPU_PJRT_PROBE="0",
               PYTHONPATH=repo_root + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    code = ("import sys\n"
            "from deeplearning4j_tpu.nativeops import PjrtClient\n"
            "path = sys.argv[1] if len(sys.argv) > 1 else None\n"
            "c = PjrtClient(path)\n"
            "print(c.platform_name())\n"
            "c.close()\n")
    cmd = [sys.executable, "-c", code]
    if plugin_path:
        cmd.append(plugin_path)
    try:
        proc = subprocess.run(cmd, env=env, timeout=timeout,
                              capture_output=True, text=True)
        if proc.returncode == 0:
            result = (True, "ok: %s" % proc.stdout.strip())
        else:
            tail = (proc.stderr or proc.stdout or "").strip()
            result = (False,
                      "probe subprocess exited %d: %s"
                      % (proc.returncode, tail[-400:]))
    except subprocess.TimeoutExpired:
        result = (False, "probe subprocess timed out after %.0fs" % timeout)
    except OSError as exc:  # no interpreter / fork failure
        result = (False, "probe subprocess failed to start: %s" % exc)
    _PLUGIN_PROBE_CACHE[key] = result
    return result


def _require_chip_free(plugin_path: str) -> None:
    """One PJRT client per chip.  Where JAX's own backend in this
    process is the TPU, the probe child cannot open ``libtpu.so`` (it
    answers "The TPU is already in use by process with pid N"), and a
    second client beside JAX's in one process is not a supported
    configuration — so refuse at once, before the native build and the
    probe.  What runs on the v5e (PR 21's chip runs): JAX on the CPU as
    the StableHLO author and this client alone on the TPU."""
    if os.path.basename(plugin_path) != "libtpu.so":
        return
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "the native PJRT backend needs the chip to itself, and JAX "
            "in this process holds it (default backend 'tpu'): one "
            "client per chip.  Run the process with JAX_PLATFORMS=cpu so "
            "JAX only authors StableHLO on the host and the native "
            "client owns the TPU")


class PjrtClient:
    """C++ PJRT client handle (``native/pjrt_shim.cc``).  The compute
    path: ``run_mlir`` compiles a textual StableHLO module in C++ and
    executes it on the plugin's first device — no Python/JAX in the
    loop."""

    def __init__(self, plugin_path: Optional[str] = None,
                 create_options: Optional[List[Tuple[str, object]]] = None):
        plugin = plugin_path or next(
            (p for p in DEFAULT_PLUGIN_PATHS if os.path.exists(p)), None)
        if plugin is None:
            raise RuntimeError("no PJRT plugin found")
        _require_chip_free(plugin)      # before the (slow) native build
        lib = load_native()
        usable, reason = pjrt_plugin_usable(plugin_path)
        if not usable:
            raise RuntimeError("PJRT plugin unusable: " + reason)
        err = ctypes.create_string_buffer(2048)
        opts = create_options or []
        n = len(opts)
        keys = (ctypes.c_char_p * n)(*[k.encode() for k, _ in opts])
        strs = (ctypes.c_char_p * n)(
            *[v.encode() if isinstance(v, str) else b"" for _, v in opts])
        ints = (ctypes.c_int64 * n)(
            *[int(v) if not isinstance(v, str) else 0 for _, v in opts])
        is_int = (ctypes.c_int * n)(
            *[0 if isinstance(v, str) else 1 for _, v in opts])
        handle = lib.dl4j_pjrt_client_create_opts(
            plugin.encode(), keys, strs, ints, is_int, n, err, len(err))
        if not handle:
            raise RuntimeError(
                f"PJRT client creation failed: {err.value.decode()}")
        self.plugin_path = plugin
        self._h = handle
        self._lib = lib

    def api_version(self) -> Tuple[int, int]:
        major = ctypes.c_int()
        minor = ctypes.c_int()
        self._lib.dl4j_pjrt_api_version(self._h, ctypes.byref(major),
                                        ctypes.byref(minor))
        return major.value, minor.value

    def platform_name(self) -> str:
        buf = ctypes.create_string_buffer(256)
        n = self._lib.dl4j_pjrt_platform_name(self._h, buf, len(buf))
        if n < 0:
            raise RuntimeError(f"platform_name failed: "
                               f"{buf.value.decode()}")
        return buf.value.decode()

    def device_count(self) -> int:
        return self._lib.dl4j_pjrt_device_count(self._h)

    @staticmethod
    def default_compile_options() -> bytes:
        """Serialized 1-replica CompileOptionsProto (via jaxlib's
        bindings — config plumbing only; compile/execute stay in
        C++)."""
        try:
            from jaxlib import xla_client
            co = xla_client.CompileOptions()
            co.num_replicas = 1
            co.num_partitions = 1
            return co.SerializeAsString()
        except Exception:
            return b""

    # -------------------------------------------------- cached typed path
    def _dtype_codes(self):
        if not hasattr(self, "_codes"):
            names = ["pred", "s8", "s16", "s32", "s64", "u8", "u16", "u32",
                     "u64", "f16", "f32", "f64", "bf16"]
            self._codes = {n: self._lib.dl4j_pjrt_dtype_code(n.encode())
                           for n in names}
            self._code_to_name = {v: k for k, v in self._codes.items()}
            # the shim also answers to numpy-style long names
            for long in ["bool", "int8", "int16", "int32", "int64",
                         "uint8", "uint16", "uint32", "uint64", "float16",
                         "float32", "float64", "bfloat16"]:
                self._codes[long] = self._lib.dl4j_pjrt_dtype_code(
                    long.encode())
        return self._codes

    def compile_cached(self, mlir: str,
                       compile_options: Optional[bytes] = None
                       ) -> Tuple[int, bool]:
        """Compile a StableHLO module or fetch it from the C++ executable
        cache (key: program-text hash — shapes/dtypes are embedded in
        StableHLO, so the hash covers them; the
        ``CudnnConvolutionHelper.java:64-140`` descriptor/algo-cache
        role).  Returns (executable id, was_cache_hit)."""
        err = ctypes.create_string_buffer(2048)
        hit = ctypes.c_int()
        copts = (self.default_compile_options()
                 if compile_options is None else compile_options)
        exec_id = self._lib.dl4j_pjrt_compile_cached(
            self._h, mlir.encode(), copts, len(copts), ctypes.byref(hit),
            err, len(err))
        if exec_id < 0:
            raise RuntimeError(f"compile failed: {err.value.decode()}")
        return exec_id, bool(hit.value)

    def cache_clear(self) -> int:
        """Drop all cached executables (long-lived clients serving many
        program shapes own their memory policy; in-flight executions are
        safe — pinned entries destroy on completion).  Compiled ids
        become invalid."""
        return int(self._lib.dl4j_pjrt_cache_clear(self._h))

    def cache_evict(self, exec_id: int) -> bool:
        """Evict one cached executable by id (per-entry LRU support:
        callers like ``NativeModelRunner`` track recency and evict the
        coldest entry instead of dropping the whole cache).  In-flight
        executions finish safely; the id is invalid afterwards.  Returns
        True if the id was found and evicted."""
        return bool(self._lib.dl4j_pjrt_cache_evict(self._h, exec_id))

    def cache_stats(self) -> dict:
        hits = ctypes.c_int64()
        misses = ctypes.c_int64()
        entries = ctypes.c_int64()
        self._lib.dl4j_pjrt_cache_stats(self._h, ctypes.byref(hits),
                                        ctypes.byref(misses),
                                        ctypes.byref(entries))
        return {"hits": hits.value, "misses": misses.value,
                "entries": entries.value}

    def output_info(self, exec_id: int) -> List[Tuple[str, Tuple[int, ...]]]:
        """[(dtype_name, shape), ...] for a compiled executable's
        outputs."""
        self._dtype_codes()
        max_out, max_dims = 64, 512
        dtypes = (ctypes.c_int * max_out)()
        ranks = (ctypes.c_int * max_out)()
        dims = (ctypes.c_int64 * max_dims)()
        n = self._lib.dl4j_pjrt_exec_output_info(
            self._h, exec_id, dtypes, ranks, dims, max_out, max_dims)
        if n < 0:
            raise RuntimeError("output_info failed (bad exec id?)")
        out, cursor = [], 0
        for i in range(n):
            shape = tuple(int(dims[cursor + j]) for j in range(ranks[i]))
            cursor += ranks[i]
            out.append((self._code_to_name[dtypes[i]], shape))
        return out

    def execute(self, exec_id: int,
                inputs: Sequence[np.ndarray]) -> List[np.ndarray]:
        """Run a cached executable with typed arbitrary-rank inputs;
        returns the typed, shaped outputs."""
        codes = self._dtype_codes()
        ins = [np.ascontiguousarray(a) for a in inputs]
        n_in = len(ins)
        in_ptrs = (ctypes.c_void_p * n_in)(
            *[a.ctypes.data_as(ctypes.c_void_p) for a in ins])
        in_dtypes = (ctypes.c_int * n_in)(
            *[codes[_np_dtype_name(a.dtype)] for a in ins])
        in_ranks = (ctypes.c_int * n_in)(*[a.ndim for a in ins])
        all_dims = [d for a in ins for d in a.shape]
        in_dims = (ctypes.c_int64 * max(1, len(all_dims)))(*all_dims)
        info = self.output_info(exec_id)
        outs = [np.empty(shape, _name_to_np(name)) for name, shape in info]
        out_ptrs = (ctypes.c_void_p * len(outs))(
            *[a.ctypes.data_as(ctypes.c_void_p) for a in outs])
        out_sizes = (ctypes.c_int64 * len(outs))(*[a.nbytes for a in outs])
        err = ctypes.create_string_buffer(2048)
        rc = self._lib.dl4j_pjrt_execute(
            self._h, exec_id, in_ptrs, in_dtypes, in_ranks, in_dims, n_in,
            out_ptrs, out_sizes, len(outs), err, len(err))
        if rc != 0:
            raise RuntimeError(
                f"execute failed (rc={rc}): {err.value.decode()}")
        return outs

    def run(self, mlir: str, inputs: Sequence[np.ndarray],
            compile_options: Optional[bytes] = None) -> List[np.ndarray]:
        """compile_cached + execute in one call (repeat calls with the
        same program hit the executable cache)."""
        exec_id, _ = self.compile_cached(mlir, compile_options)
        return self.execute(exec_id, inputs)

    def buffer_from_host(self, array: np.ndarray) -> int:
        """Upload a host array to a persistent device buffer; returns its
        id for use in :meth:`execute_mixed`.  Model params upload once and
        stay device-resident (ND4J INDArray role)."""
        codes = self._dtype_codes()
        a = np.ascontiguousarray(array)
        # (the C call awaits transfer completion before returning, so `a`
        # only needs to stay alive for the duration of this call)
        dims = (ctypes.c_int64 * max(1, a.ndim))(*a.shape)
        err = ctypes.create_string_buffer(2048)
        buf_id = self._lib.dl4j_pjrt_buffer_from_host(
            self._h, a.ctypes.data_as(ctypes.c_void_p),
            codes[_np_dtype_name(a.dtype)], dims, a.ndim, err, len(err))
        if buf_id < 0:
            raise RuntimeError(
                f"buffer_from_host failed: {err.value.decode()}")
        return buf_id

    def buffer_free(self, buf_id: int) -> None:
        self._lib.dl4j_pjrt_buffer_free(self._h, buf_id)

    def execute_mixed(self, exec_id: int, arg_spec: Sequence,
                      ) -> List[np.ndarray]:
        """Run a cached executable where each argument is either a
        device-buffer id (int) or a host numpy array — the hot inference
        path transfers only the activation arguments."""
        codes = self._dtype_codes()
        spec = []
        for a in arg_spec:
            # bool subclasses int: True would silently rebind to buffer
            # id 1 (typically the first uploaded parameter) — reject, and
            # require host operands to arrive as arrays
            if isinstance(a, bool) or (isinstance(a, np.generic)
                                       and not isinstance(a, np.integer)):
                raise TypeError(
                    "execute_mixed arg_spec entries must be device-buffer"
                    f" ids (int) or numpy arrays; got {type(a).__name__}."
                    " Wrap host scalars with np.asarray(x)")
            spec.append(int(a) if isinstance(a, (int, np.integer))
                        else np.ascontiguousarray(a))
        n = len(spec)
        buf_ids = (ctypes.c_int64 * n)(
            *[a if isinstance(a, int) else -1 for a in spec])
        host = [a for a in spec if not isinstance(a, int)]
        n_host = len(host)
        host_ptrs = (ctypes.c_void_p * max(1, n_host))(
            *[a.ctypes.data_as(ctypes.c_void_p) for a in host])
        host_dtypes = (ctypes.c_int * max(1, n_host))(
            *[codes[_np_dtype_name(a.dtype)] for a in host])
        host_ranks = (ctypes.c_int * max(1, n_host))(
            *[a.ndim for a in host])
        all_dims = [d for a in host for d in a.shape]
        host_dims = (ctypes.c_int64 * max(1, len(all_dims)))(*all_dims)
        info = self.output_info(exec_id)
        outs = [np.empty(shape, _name_to_np(name)) for name, shape in info]
        out_ptrs = (ctypes.c_void_p * len(outs))(
            *[a.ctypes.data_as(ctypes.c_void_p) for a in outs])
        out_sizes = (ctypes.c_int64 * len(outs))(*[a.nbytes for a in outs])
        err = ctypes.create_string_buffer(2048)
        rc = self._lib.dl4j_pjrt_execute_mixed(
            self._h, exec_id, buf_ids, host_ptrs, host_dtypes, host_ranks,
            host_dims, n, out_ptrs, out_sizes, len(outs), err, len(err))
        if rc != 0:
            raise RuntimeError(
                f"execute_mixed failed (rc={rc}): {err.value.decode()}")
        return outs

    def run_mlir(self, mlir: str, inputs: Sequence[np.ndarray],
                 out_size: int,
                 compile_options: Optional[bytes] = None) -> np.ndarray:
        """Compile + execute a StableHLO module with flat f32 vector
        inputs of equal length; returns the flat f32 output.

        Every distinct program is kept in the executable cache so
        repeat calls skip compilation.  Long-lived clients streaming
        MANY distinct programs through this entry point must call
        :meth:`cache_clear` periodically (check :meth:`cache_stats`
        ``entries``), or device/host memory grows with the number of
        distinct programs compiled."""
        ins = [np.ascontiguousarray(a, np.float32).ravel()
               for a in inputs]
        n = ins[0].size
        if any(a.size != n for a in ins):
            raise ValueError("all inputs must have equal length")
        arr_t = ctypes.POINTER(ctypes.c_float) * len(ins)
        in_ptrs = arr_t(*[_fptr(a) for a in ins])
        out = np.empty(out_size, np.float32)
        err = ctypes.create_string_buffer(2048)
        copts = (self.default_compile_options()
                 if compile_options is None else compile_options)
        rc = self._lib.dl4j_pjrt_run_mlir(
            self._h, mlir.encode(), copts, len(copts), in_ptrs,
            len(ins), n, _fptr(out), out_size, err, len(err))
        if rc != 0:
            raise RuntimeError(
                f"run_mlir failed (rc={rc}): {err.value.decode()}")
        return out

    def close(self) -> None:
        if self._h:
            self._lib.dl4j_pjrt_client_destroy(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


__all__ = ["build_native", "load_native", "idx_decode", "cifar_decode",
           "NativePrefetcher", "PjrtClient", "DEFAULT_PLUGIN_PATHS",
           "pjrt_plugin_usable"]
