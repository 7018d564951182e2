"""Multi-host pod runtime: ONE ``jax.distributed`` mesh for every
parallel tier.

Before this module each parallel path built a private one-axis
``Mesh`` — ``parallel_wrapper`` (``("data",)``), ``zero`` (``("data",)``
doing double duty for batch *and* update sharding), ``pipeline``
(``("stage",)``) — and nothing spanned OS processes.  The
:class:`MeshRuntime` replaces all of them with one global device mesh
with named axes ``("data", "zero", "pipe")``:

- ``data``  — pure data parallelism (batch sharding + gradient/param
  all-reduce, the ParallelWrapper axis).
- ``zero``  — cross-replica *weight-update* sharding (arXiv:2004.13336,
  PAPERS.md): batches shard over ``data x zero`` flattened, but the
  updater state (and fp32 masters under ``mixed_bf16``) shards over
  ``zero`` only — per-process optimizer-state residency drops
  ~``1/zero_degree``, the paper's memory win, now across real
  processes.
- ``pipe``  — GPipe pipeline stages.

The wrappers no longer construct meshes: their legacy constructors call
:meth:`MeshRuntime.local` (``data=w`` / ``zero=w`` / ``pipe=S``), so
single-process semantics are unchanged while a caller holding a real
multi-process runtime can hand the SAME object to any of them and get
process-spanning ``NamedSharding``.

Distributed bootstrap (the ONE env/flag contract, shared with
``scaleout/dcn.py``): explicit flags take precedence over the
``COORDINATOR_ADDRESS`` / ``NUM_PROCESSES`` / ``PROCESS_ID`` env
variables (the PJRT distributed-runtime contract the cloud provisioner
emits).  :func:`ensure_distributed` is idempotent and *refuses* a
second initialization with a conflicting topology — two subsystems can
no longer race ``jax.distributed.initialize`` with different shapes.

Telemetry: ``mesh_updater_state_bytes{axis}`` gauges the per-process
addressable optimizer-state residency (the quantity the ZeRO axis
shrinks) and ``mesh_collective_seconds{axis,op}`` histograms measured
all-reduce / all-gather latencies per mesh axis
(:meth:`MeshRuntime.measure_collectives`).
"""

from __future__ import annotations

import errno
import os
import socket
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import monitor as _monitor

AXES = ("data", "zero", "pipe")

#: env contract (same variables ``cloud/provision.py`` emits and
#: ``scaleout/dcn.py`` historically read — there is now ONE reader)
ENV_COORDINATOR = "COORDINATOR_ADDRESS"
ENV_NUM_PROCESSES = "NUM_PROCESSES"
ENV_PROCESS_ID = "PROCESS_ID"

STATE_BYTES_GAUGE = "mesh_updater_state_bytes"
COLLECTIVE_HIST = "mesh_collective_seconds"
_HELP = {
    STATE_BYTES_GAUGE: "per-process addressable updater-state bytes by "
                       "sharding axis",
    COLLECTIVE_HIST: "measured cross-device collective latency by mesh "
                     "axis and op",
}

# one-process-wide record of what jax.distributed was initialized with,
# so a second subsystem cannot re-initialize with a conflicting topology
_initialized: Optional[Dict[str, object]] = None


def resolve_topology(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     env: Optional[Dict[str, str]] = None
                     ) -> Optional[Dict[str, object]]:
    """Resolve the distributed topology from explicit flags and the env,
    with documented precedence **flags > env** (a flag given alongside
    conflicting env wins silently — the operator's CLI is authoritative;
    the env is the provisioner's default).  Returns ``None`` when no
    coordinator is configured anywhere (single-process run), else
    ``{"coordinator", "num_processes", "process_id"}``."""
    env = os.environ if env is None else env
    coord = coordinator or env.get(ENV_COORDINATOR) or None
    if coord is None:
        return None
    n = num_processes if num_processes is not None else \
        int(env.get(ENV_NUM_PROCESSES, "1"))
    pid = process_id if process_id is not None else \
        int(env.get(ENV_PROCESS_ID, "0"))
    if n < 1:
        raise ValueError(f"num_processes must be >= 1, got {n}")
    if not 0 <= pid < n:
        raise ValueError(f"process_id {pid} out of range [0, {n})")
    return {"coordinator": coord, "num_processes": n, "process_id": pid}


def _enable_cpu_collectives() -> None:
    """CPU cross-process collectives need the gloo implementation; a
    no-op where the config knob (or the backend) doesn't exist."""
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass


def ensure_distributed(coordinator: Optional[str] = None,
                       num_processes: Optional[int] = None,
                       process_id: Optional[int] = None) -> bool:
    """Initialize ``jax.distributed`` exactly once for this process,
    from flags (authoritative) falling back to the env contract.

    Returns True when running multi-process (initialized now or
    already), False when no coordinator is configured (single-process
    no-op).  Raises ``RuntimeError`` if a previous call initialized a
    DIFFERENT topology — the conflicting-bootstrap bug this single code
    path exists to prevent."""
    global _initialized
    topo = resolve_topology(coordinator, num_processes, process_id)
    if topo is None:
        return False
    if _initialized is not None:
        if _initialized != topo:
            raise RuntimeError(
                f"jax.distributed already initialized with "
                f"{_initialized}; refusing conflicting topology {topo}")
        return topo["num_processes"] > 1
    if topo["num_processes"] == 1:
        # single-process degenerate case: nothing to coordinate; accept
        # the env shape without spinning up a coordinator (the
        # provisioner's NUM_PROCESSES=1 contract)
        _initialized = topo
        return False
    _enable_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=topo["coordinator"],
        num_processes=topo["num_processes"],
        process_id=topo["process_id"])
    _initialized = topo
    return True


def initialized_topology() -> Optional[Dict[str, object]]:
    """The topology this process bootstrapped with (None before any
    :func:`ensure_distributed`)."""
    return None if _initialized is None else dict(_initialized)


def _reset_bootstrap_for_tests() -> None:
    global _initialized
    _initialized = None


# --------------------------------------------------------- port helpers

def pick_coordinator_port(host: str = "127.0.0.1") -> int:
    """One candidate coordinator port from the OS.  The bind is released
    before returning, so the port can be stolen — callers that launch a
    coordinator must wrap the launch in :func:`retry_on_port_clash`
    instead of trusting a single probe (the one-shot probe is exactly
    the flake this helper replaces)."""
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


#: substrings that identify a coordinator bind failure in a worker's
#: output (jax/grpc spell EADDRINUSE several ways)
PORT_CLASH_MARKERS = ("EADDRINUSE", "Address already in use",
                      "address already in use", "Failed to bind",
                      "errno 98", os.strerror(errno.EADDRINUSE))


def is_port_clash(text: str) -> bool:
    """Does this (worker) output indicate the coordinator port was
    already taken?"""
    return any(m in text for m in PORT_CLASH_MARKERS)


def retry_on_port_clash(launch, attempts: int = 4):
    """Bind-with-retry for coordinator launches: call ``launch(port)``
    with a fresh candidate port per attempt; ``launch`` returns
    ``(ok, result)`` where ``ok=False`` means the coordinator failed to
    bind (:func:`is_port_clash` on its output) and the attempt should be
    retried.  Raises ``RuntimeError`` after ``attempts`` clashes."""
    last = None
    for _ in range(max(1, attempts)):
        port = pick_coordinator_port()
        ok, result = launch(port)
        if ok:
            return result
        last = result
    raise RuntimeError(
        f"coordinator port clashed {attempts} times; last result: "
        f"{str(last)[-500:]}")


# ------------------------------------------------------------- runtime

class MeshRuntime:
    """One global device mesh with axes ``("data", "zero", "pipe")``,
    handed to every parallel wrapper instead of private meshes.

    ``data``/``zero``/``pipe`` are the axis degrees; ``data=None``
    infers the largest degree that fits the device count given the
    other two.  ``coordinator``/``num_processes``/``process_id`` (or
    the env contract) bootstrap ``jax.distributed`` first, so
    ``jax.devices()`` sees the whole pod."""

    def __init__(self, data: Optional[int] = None, zero: int = 1,
                 pipe: int = 1, devices: Optional[Sequence] = None,
                 coordinator: Optional[str] = None,
                 num_processes: Optional[int] = None,
                 process_id: Optional[int] = None):
        if devices is None:
            ensure_distributed(coordinator, num_processes, process_id)
            devices = jax.devices()
        devices = list(devices)
        zero = int(zero)
        pipe = int(pipe)
        if zero < 1 or pipe < 1:
            raise ValueError(f"axis degrees must be >= 1 "
                             f"(zero={zero}, pipe={pipe})")
        if data is None:
            data = len(devices) // (zero * pipe)
        data = int(data)
        if data < 1:
            raise ValueError(
                f"mesh needs data >= 1: {len(devices)} device(s) cannot "
                f"fit zero={zero} x pipe={pipe}")
        n = data * zero * pipe
        if n > len(devices):
            raise ValueError(
                f"mesh {data}x{zero}x{pipe} = {n} devices > "
                f"{len(devices)} available")
        self.data_degree = data
        self.zero_degree = zero
        self.pipe_degree = pipe
        self.devices = devices[:n]
        self.mesh = Mesh(
            np.array(self.devices).reshape(data, zero, pipe), AXES)
        _monitor.gauge("mesh_process_count",
                       "processes participating in the pod mesh").set(
            self.process_count)
        for axis, degree in zip(AXES, (data, zero, pipe)):
            _monitor.gauge("mesh_axis_size",
                           "global mesh axis degree").set(degree,
                                                          axis=axis)

    # ---- single-process factory -----------------------------------------
    @classmethod
    def local(cls, data: int = 1, zero: int = 1, pipe: int = 1,
              devices: Optional[Sequence] = None) -> "MeshRuntime":
        """A runtime over this process's own devices with NO distributed
        bootstrap — what the wrappers' legacy constructors use, so old
        call sites keep their exact semantics."""
        if devices is None:
            devices = jax.devices()
        return cls(data=data, zero=zero, pipe=pipe, devices=devices)

    # ---- topology -------------------------------------------------------
    @property
    def dp_degree(self) -> int:
        """Total data-parallel replicas: the flattened data x zero
        extent batches shard over."""
        return self.data_degree * self.zero_degree

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def process_count(self) -> int:
        return jax.process_count()

    @property
    def is_multiprocess(self) -> bool:
        return self.process_count > 1

    def topology(self) -> Dict[str, int]:
        """The shape stamp pod checkpoints carry: a restore into a
        different shape must be refused, not misassembled."""
        return {"data": self.data_degree, "zero": self.zero_degree,
                "pipe": self.pipe_degree,
                "num_processes": self.process_count}

    def describe(self) -> str:
        return (f"mesh[data={self.data_degree},zero={self.zero_degree},"
                f"pipe={self.pipe_degree}]@{self.process_count}proc")

    # ---- sharding / staging ---------------------------------------------
    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def put(self, host_array, spec: P):
        """Stage a full host array onto the mesh under ``spec``.  Every
        process holds the identical full host value (SPMD staging);
        each contributes only its addressable shards, so this works
        when the sharding spans processes — where a plain
        ``jax.device_put`` cannot."""
        arr = np.asarray(host_array)
        sh = self.sharding(spec)
        if not self.is_multiprocess:
            return jax.device_put(jnp.asarray(arr), sh)
        return jax.make_array_from_callback(
            arr.shape, sh, lambda idx: arr[idx])

    def put_tree(self, tree, spec: P):
        """:meth:`put` over a pytree (None leaves pass through)."""
        return jax.tree.map(lambda a: self.put(a, spec), tree)

    def to_host(self, arr) -> np.ndarray:
        """Fetch an array to host.  Fully-replicated/addressable arrays
        come back whole; a process-spanning sharded array comes back as
        this process's addressable rows concatenated along axis 0 (the
        pod checkpoint's per-process payload)."""
        if getattr(arr, "is_fully_replicated", True) or \
                getattr(arr, "is_fully_addressable", True):
            return np.asarray(arr)
        shards = sorted(((s.index, s.data)
                         for s in arr.addressable_shards),
                        key=lambda t: (t[0][0].start or 0))
        seen = {}
        for idx, data in shards:
            start = idx[0].start or 0
            if start not in seen:
                seen[start] = np.asarray(data)
        return np.concatenate([seen[k] for k in sorted(seen)], axis=0)

    def addressable_state_bytes(self, tree) -> int:
        """Bytes of ``tree`` actually resident in THIS process (the
        per-process optimizer-state residency the ``zero`` axis
        shrinks).  Replicated copies across local devices count once;
        distinct shards sum."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            if not hasattr(leaf, "addressable_shards"):
                total += getattr(leaf, "nbytes", 0)
                continue
            seen = set()
            for s in leaf.addressable_shards:
                key = tuple((sl.start, sl.stop) for sl in s.index)
                if key in seen:
                    continue
                seen.add(key)
                total += s.data.nbytes
        return total

    def publish_state_bytes(self, tree, axis: str) -> int:
        """Gauge ``mesh_updater_state_bytes{axis=...}`` with this
        process's addressable residency of ``tree``."""
        nbytes = self.addressable_state_bytes(tree)
        _monitor.gauge(STATE_BYTES_GAUGE,
                       _HELP[STATE_BYTES_GAUGE]).set(nbytes, axis=axis)
        return nbytes

    # ---- collectives ----------------------------------------------------
    def barrier(self, name: str = "mesh_barrier") -> None:
        """Block until every process reaches this point (no-op
        single-process)."""
        if not self.is_multiprocess:
            return
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)

    def measure_collectives(self, size: int = 1 << 14,
                            repeats: int = 3) -> Dict[str, float]:
        """Measure all-reduce / all-gather wall time over each mesh axis
        with degree > 1 and publish ``mesh_collective_seconds{axis,op}``
        observations.  Returns ``{"{axis}/{op}": seconds}`` (best of
        ``repeats``) — the honest per-axis collective cost on THIS
        fabric (ICI, DCN, or gloo-over-localhost)."""
        from jax import lax
        out: Dict[str, float] = {}
        hist = _monitor.histogram(COLLECTIVE_HIST, _HELP[COLLECTIVE_HIST])
        for axis, degree in zip(AXES, (self.data_degree,
                                       self.zero_degree,
                                       self.pipe_degree)):
            if degree <= 1:
                continue
            host = np.arange(degree * size, dtype=np.float32
                             ).reshape(degree, size)
            x = self.put(host, P(axis))
            for op, fn in (("all_reduce",
                            lambda v, a=axis: lax.psum(v, a)),
                           ("all_gather",
                            lambda v, a=axis: lax.all_gather(
                                v, a, tiled=True))):
                # check_vma=False: an all_gather result is typed as
                # varying over its axis although every slot holds the
                # same value, and out_specs=P() is refused for it
                # dl4j-lint: disable=R6 one program per (axis, op) pair by design, compiled outside the timed region
                prog = jax.jit(jax.shard_map(
                    fn, mesh=self.mesh, in_specs=P(axis),
                    out_specs=P(), check_vma=False))
                jax.block_until_ready(prog(x))      # compile outside timing
                best = float("inf")
                for _ in range(max(1, repeats)):
                    t0 = time.perf_counter()
                    jax.block_until_ready(prog(x))
                    best = min(best, time.perf_counter() - t0)
                hist.observe(best, axis=axis, op=op)
                out[f"{axis}/{op}"] = best
        return out
