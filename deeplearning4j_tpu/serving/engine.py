"""Dynamic-batching inference engine: coalesce concurrent ``predict()``
calls into bucket-shaped batches served by AOT-compiled executables.

The serving problem (TF-Serving's batching scheduler, arXiv:1605.08695;
the MLPerf TPU-inference recipe, arXiv:1909.09756): accelerator
inference throughput comes from batch parallelism, but requests arrive
one at a time.  Single-request dispatch leaves the device idle between
tiny kernels; naive batching of whatever arrived recompiles per novel
shape.  This engine does the standard fix end to end:

1. ``predict()`` enqueues the request into a **bounded** queue and
   blocks on a future (queue full => callers block or get
   ``QueueFull`` — backpressure, never OOM).
2. A batcher thread coalesces compatible requests under a
   ``(max_batch_size, max_latency_ms)`` policy: the first request opens
   a window; the batch closes when it would overflow the ladder or the
   window expires.
3. The coalesced rows are zero-padded up to a fixed **bucket ladder**
   (powers-of-two batch sizes, optional timestep buckets for sequence
   inputs — see ``serving.bucketing``), so the model only ever sees a
   small, fixed set of shapes.
4. One **AOT executable per bucket** (``jit(...).lower().compile()``
   through ``monitor.watched_jit`` via the containers'
   ``compile_output``), warmed eagerly by ``warmup()`` — the hot path
   never traces or compiles, and ``jit_compiles_total{fn="mln.output"}``
   proves recompiles stay == bucket count under any shape churn.
5. Results are unpadded and routed back to per-request futures; a
   worker pool shards buckets across ``jax.devices()``.

Serving v2 adds the multi-tenant machinery (docs/SERVING.md):

- **SLO-aware admission** (``slo_p99_ms=``): requests are shed with
  :class:`SloShed` while the sliding-window p99 of
  ``serving_request_latency_ms`` exceeds the target — the latency
  signal, distinct from ``QueueFull``'s capacity signal, each with its
  own counter (``serving_shed_total`` vs ``serving_rejected_total``).
- **int8 weights** (``quantize="int8"``): resident params are
  per-tensor affine uint8 (``serving.quantize``, the PR-3 wire-decode
  expression) decoded inside the bucket executable — ~4x fewer
  resident bytes per model, so the registry pager fits more models.
- **Device paging** (``release_device_buffers``/``ensure_resident``):
  the per-worker placed weight buffers can be dropped and re-placed,
  which is what ``serving.registry.ModelRegistry`` drives LRU-style
  under an HBM budget.
- **Session state** (``predict_session``): per-session RNN carries
  cached on device (``serving.sessions.SessionCache``) so streaming
  traffic pays ONE single-timestep dispatch per request instead of
  full-sequence recompute.

Deployment (docs/DEPLOY.md) builds on the same weights-are-operands
fact the pager exploits: the engine holds **N versioned weight trees**
against ONE set of bucket executables.  ``stage_weights`` registers
version N+1 alongside N, ``set_canary`` routes a deterministic
fraction of requests to it (the batcher never mixes versions in one
batch), ``promote`` is an atomic pointer flip and ``rollback`` drops
the canary — none of which compiles anything, which
``serving_bucket_compiles_total`` proves.  Sessions opened before a
swap stay pinned to the version they started on
(``serving.sessions.SessionCache``).

The ``NativeModelRunner`` PJRT path is available as
``backend="native"``: same bucketer (the ladder bounds the runner's
per-shape executable cache), execution through the C++ PJRT client —
a second PJRT client, so it raises at once in a process whose JAX
backend already holds the TPU (one client per chip;
``nativeops._require_chip_free``).

Everything is instrumented through the ``monitor`` registry:
``serving_queue_depth``, ``serving_batch_fill_ratio``,
``serving_padding_waste_ratio`` and ``serving_request_latency_ms``
(reservoir p50/p95/p99/p999, labelled per model) all export through
``GET /metrics``.
"""

from __future__ import annotations

import itertools
import math
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import monitor as _monitor
from ..monitor.locks import make_lock
from .admission import (DEFAULT_TENANT, SloAdmissionController,
                        normalize_tenant, publish_tenant_telemetry)
from .bucketing import BucketPolicy, assemble_batch


class ServingError(RuntimeError):
    """Base class for serving-path failures."""


class QueueFull(ServingError):
    """Raised by non-blocking submits when the request queue is at
    capacity (the backpressure signal).  ``retry_after_s`` carries the
    drain-rate-derived wait the HTTP layer turns into a ``Retry-After``
    header."""

    def __init__(self, msg: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = float(retry_after_s)


class SloShed(ServingError):
    """Raised when admission control sheds the request: the engine's
    observed p99 latency exceeds its SLO target.  Distinct from
    :class:`QueueFull` — the queue may have room; admitting more load
    would break the latency target for everyone already admitted.
    ``tenant`` is the (normalized) tenant whose request was shed —
    under fair admission that is usually the over-share offender."""

    def __init__(self, msg: str, slo_p99_ms: float,
                 observed_p99_ms: float, tenant: str = DEFAULT_TENANT):
        super().__init__(msg)
        self.slo_p99_ms = float(slo_p99_ms)
        self.observed_p99_ms = float(observed_p99_ms)
        self.tenant = str(tenant)


class _Request:
    __slots__ = ("arrays", "n_rows", "sig", "version", "t_enqueue",
                 "t_wall", "t_dequeue", "ctx", "trace_id", "span_id",
                 "tenant", "future")

    def __init__(self, arrays, n_rows, sig, version,
                 tenant=DEFAULT_TENANT):
        self.arrays = arrays
        self.n_rows = n_rows
        self.sig = sig
        self.version = version
        self.tenant = tenant
        self.t_enqueue = time.perf_counter()
        self.t_wall = time.time()
        self.t_dequeue = self.t_enqueue
        # Trace identity is fixed at submit time on the caller's thread:
        # the request span parents under the caller's ambient context
        # (e.g. the HTTP server span) and its id is pre-allocated here so
        # the batch span can link it before the span is recorded.
        self.ctx = _monitor.current_context()
        self.trace_id = (self.ctx.trace_id if self.ctx is not None
                         else _monitor.new_trace_id())
        self.span_id = _monitor.tracer().next_span_id()
        self.future: Future = Future()


class _BatchJob:
    __slots__ = ("requests", "sig", "rows", "version")

    def __init__(self, requests, sig, rows, version):
        self.requests = requests
        self.sig = sig
        self.rows = rows
        self.version = version


class InferenceEngine:
    """Concurrent dynamic-batching front end for a trained
    ``MultiLayerNetwork`` or ``ComputationGraph``.

    >>> engine = InferenceEngine(net, max_batch_size=32,
    ...                          max_latency_ms=2.0).start()
    >>> engine.warmup((4,))              # compile every batch bucket
    >>> y = engine.predict(x)            # thread-safe, blocks on result
    >>> engine.stop()

    Knobs (see docs/SERVING.md): ``max_batch_size`` trades per-request
    latency for throughput; ``max_latency_ms`` bounds the coalescing
    wait; ``queue_capacity`` bounds admitted-but-unserved requests
    (callers block past it); ``timestep_buckets`` enables sequence
    padding; ``num_workers``/``devices`` shard buckets across
    accelerators; ``backend="native"`` serves through the C++ PJRT
    client; ``slo_p99_ms`` enables SLO-aware load shedding;
    ``quantize="int8"`` serves affine-quantized uint8 weights;
    ``session_ttl_s``/``max_sessions`` configure the device-resident
    RNN session cache behind :meth:`predict_session`.
    """

    def __init__(self, model, *, max_batch_size: int = 32,
                 max_latency_ms: float = 5.0, queue_capacity: int = 128,
                 timestep_buckets: Optional[Sequence[int]] = None,
                 num_workers: int = 1, devices=None,
                 backend: str = "aot", dtype=None, name: str = "default",
                 slo_p99_ms: Optional[float] = None,
                 tenants: Optional[dict] = None,
                 admission: Optional[SloAdmissionController] = None,
                 quantize: Optional[str] = None,
                 session_ttl_s: float = 300.0, max_sessions: int = 1024):
        from ..nn.computation_graph import ComputationGraph
        model.init()
        self._model = model
        self._is_graph = isinstance(model, ComputationGraph)
        self._n_inputs = (len(model.conf.network_inputs)
                          if self._is_graph else 1)
        self._policy = BucketPolicy(max_batch_size, timestep_buckets)
        self._max_latency_s = float(max_latency_ms) / 1000.0
        self._name = str(name)
        self._dtype = np.dtype(dtype if dtype is not None
                               else model.conf.conf.dtype)
        if backend not in ("aot", "native"):
            raise ValueError("backend must be 'aot' or 'native'")
        if quantize not in (None, "int8"):
            raise ValueError("quantize must be None or 'int8'")
        if quantize and backend == "native":
            raise ValueError(
                "quantize='int8' requires backend='aot' (the native "
                "runner uploads the model's own buffers)")
        self._backend = backend
        self._quantize = quantize
        self._qjit = None
        self._qparams = None
        self._qdecode = None
        if quantize == "int8":
            from . import quantize as _quant
            self._qparams, self._qspecs = _quant.quantize_tree(
                model.params)
            prefix = "cg" if self._is_graph else "mln"
            self._qjit = _quant.quantized_output_jit(
                model, self._qspecs, name=prefix + ".output_int8")
            if getattr(model, "has_kv_ring", lambda: False)():
                # int8 decode: same fused decode-inside-the-program
                # contract as output_int8, handed to SessionCache as
                # its step_fn override
                self._qdecode = _quant.quantized_decode_jit(
                    model, self._qspecs,
                    name=prefix + ".decode_step_int8")
        self._runner = None
        if backend == "native":
            if self._policy.timestep_buckets:
                raise ValueError(
                    "backend='native' does not thread features masks; "
                    "timestep bucketing requires backend='aot'")
            from ..nn.native_runtime import NativeModelRunner
            # the ladder bounds the distinct shapes this engine can emit,
            # so the runner's LRU cache sized to it never evicts
            self._runner = NativeModelRunner(
                model,
                max_shapes=max(self._policy.bucket_count(self._n_inputs),
                               4))
            num_workers = 1
        import jax
        devs = list(devices) if devices is not None else list(jax.devices())
        n_workers = max(1, min(int(num_workers), len(devs)))
        self._devices = devs[:n_workers]
        self._queue: "queue.Queue" = queue.Queue(maxsize=int(queue_capacity))
        self._dispatch_q: "queue.Queue" = queue.Queue(maxsize=2 * n_workers)
        self._compiled: dict = {}        # (worker_idx, bucket_key) -> fn
        # Versioned weights: version -> host (params, net_state).  The
        # sentinel tree ``None`` means "the model's own live weights"
        # (version 0 at construction); staged versions hold explicit
        # trees.  Executables are version-agnostic (weights are call
        # operands), so _placed caches device placements per
        # (worker, version) against ONE compiled set.
        self._weights: dict = {0: None}
        self._active_version = 0
        self._canary_version: Optional[int] = None
        self._canary_fraction = 0.0
        self._max_version_seen = 0
        self._session_pins: dict = {}    # retired version -> host tree
        self._route_counter = itertools.count()
        self._placed: dict = {}          # (worker_idx, version) -> placed
        self._placed_lock = make_lock("serving.engine.placed")
        self._compile_lock = make_lock("serving.engine.compile")
        self._running = False
        self._threads: List[threading.Thread] = []
        if admission is not None:
            # a pre-configured controller (observe-only mode, custom
            # windows, ...) overrides the slo_p99_ms shorthand
            self._admission: Optional[SloAdmissionController] = admission
        else:
            self._admission = (
                SloAdmissionController(slo_p99_ms, tenants=tenants)
                if slo_p99_ms else None)
        # rate limit for the per-tenant gauge/scoreboard publication
        self._tenant_pub_at = float("-inf")
        self._sessions = None
        self._session_opts = {"ttl_s": float(session_ttl_s),
                              "max_sessions": int(max_sessions)}
        self._session_lock = make_lock("serving.engine.session")
        # completion timestamps for the queue drain rate (Retry-After)
        self._done_times: "deque" = deque(maxlen=512)
        from .quantize import tree_nbytes
        self._model_bytes = tree_nbytes(
            (self._qparams, model.net_state) if self._quantize
            else (model.params, model.net_state))

    # ----------------------------------------------------------- identity
    @property
    def name(self) -> str:
        return self._name

    @property
    def slo_p99_ms(self) -> Optional[float]:
        return self._admission.slo_p99_ms if self._admission else None

    # ------------------------------------------------------------ metrics
    def _observe_queue_depth(self):
        _monitor.gauge("serving_queue_depth",
                       "admitted requests waiting to be batched").set(
            self._queue.qsize(), engine=self._name)

    def _observe_latency(self, latency_ms: float,
                         trace_hex: Optional[str] = None,
                         version: Optional[int] = None,
                         tenant: str = DEFAULT_TENANT) -> None:
        _monitor.histogram(
            "serving_request_latency_ms",
            "end-to-end request latency (enqueue -> result), per model"
        ).observe(latency_ms, exemplar=trace_hex, model=self._name)
        if version is not None:
            # separate series so the rollout controller can window p99
            # per weight version without perturbing the SLO signal
            _monitor.histogram(
                "serving_version_latency_ms",
                "request latency per served weight version").observe(
                latency_ms, model=self._name, version=str(version))
        # per-tenant latency series: exemplars only for the tenant's
        # slowest decile (windowed p90 cut), so /metrics points an
        # engineer at traces of the requests dragging that tenant's
        # tail — not at a uniformly random sample
        slow_ms = (self._admission.tenant_slow_threshold_ms(tenant)
                   if self._admission is not None else None)
        _monitor.histogram(
            "serving_tenant_latency_ms",
            "end-to-end request latency per tenant; exemplars pin the "
            "tenant's slowest-decile requests").observe(
            latency_ms,
            exemplar=(trace_hex or "") if (
                slow_ms is not None and latency_ms >= slow_ms) else "",
            model=self._name, tenant=tenant)
        if self._admission is not None:
            self._admission.observe(latency_ms, tenant=tenant)
            self._maybe_publish_tenants()
        self._done_times.append(time.monotonic())

    def _maybe_publish_tenants(self) -> None:
        """Refresh the per-tenant scoreboard gauges at most once per
        admission refresh interval (the completion path stays O(1))."""
        now = time.monotonic()
        interval = max(0.1, 2.0 * self._admission.refresh_s)
        if now - self._tenant_pub_at < interval:
            return
        self._tenant_pub_at = now
        publish_tenant_telemetry(self._admission, self._name)

    def _tenant(self, tenant) -> str:
        """Normalize a request's tenant id against the configured
        tenants (bounded label cardinality; see admission module)."""
        if self._admission is not None:
            return self._admission.normalize(tenant)
        return normalize_tenant(tenant)

    # ---------------------------------------------------------- lifecycle
    def start(self) -> "InferenceEngine":
        """Spawn the batcher and worker threads (idempotent)."""
        if self._running:
            return self
        self._running = True
        self._threads = [threading.Thread(
            target=self._batcher_loop,
            name=f"serving-batcher-{self._name}", daemon=True)]
        for i in range(len(self._devices)):
            self._threads.append(threading.Thread(
                target=self._worker_loop, args=(i,),
                name=f"serving-worker-{self._name}-{i}", daemon=True))
        for t in self._threads:
            t.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        """Stop batching, drain in-flight work, fail still-queued
        requests with ``ServingError``."""
        if not self._running and not self._threads:
            return
        self._running = False
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(0.0, deadline - time.monotonic()))
        self._threads = []
        for q in (self._queue, self._dispatch_q):
            while True:
                try:
                    item = q.get_nowait()
                except queue.Empty:
                    break
                reqs = (item.requests if isinstance(item, _BatchJob)
                        else [item])
                for r in reqs:
                    if isinstance(r, _Request) and not r.future.done():
                        r.future.set_exception(
                            ServingError("engine stopped"))
        self._observe_queue_depth()

    def __enter__(self) -> "InferenceEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---------------------------------------------------------- admission
    def _admit_or_shed(self, tenant=None) -> str:
        """Run the (per-tenant, fair) admission decision; returns the
        normalized tenant label, raises :class:`SloShed` on shed."""
        tenant = self._tenant(tenant)
        _monitor.counter(
            "serving_tenant_requests_total",
            "requests arriving at admission, per tenant").inc(
            engine=self._name, tenant=tenant)
        if self._admission is None:
            _monitor.counter(
                "serving_tenant_admitted_total",
                "requests admitted past SLO admission, per tenant").inc(
                engine=self._name, tenant=tenant)
            return tenant
        observed = self._admission.should_shed(tenant)
        if observed is not None:
            _monitor.counter(
                "serving_shed_total",
                "requests shed by SLO admission control "
                "(p99 over target)").inc(engine=self._name)
            _monitor.counter(
                "serving_tenant_shed_total",
                "requests shed by SLO admission control, per tenant"
            ).inc(engine=self._name, tenant=tenant)
            _monitor.record_incident("slo_shed", {
                "engine": self._name,
                "tenant": tenant,
                "observed_p99_ms": float(observed),
                "slo_p99_ms": float(self._admission.slo_p99_ms),
            })
            raise SloShed(
                f"shedding tenant {tenant!r}: observed p99 "
                f"{observed:.1f} ms exceeds the "
                f"{self._admission.slo_p99_ms:.1f} ms SLO; retry with "
                "backoff", self._admission.slo_p99_ms, observed,
                tenant=tenant)
        _monitor.counter(
            "serving_tenant_admitted_total",
            "requests admitted past SLO admission, per tenant").inc(
            engine=self._name, tenant=tenant)
        return tenant

    def drain_rate(self) -> float:
        """Completed requests per second over the recent completion
        window (0.0 with no evidence)."""
        done = list(self._done_times)
        if len(done) < 2:
            return 0.0
        span = done[-1] - done[0]
        if span <= 0:
            return 0.0
        return (len(done) - 1) / span

    @staticmethod
    def _retry_after(depth: int, rate: float) -> float:
        """Pure Retry-After math over pre-snapshotted inputs: queue
        depth over drain rate, clamped to [1, 60] s.  Static and
        argument-only so rejection paths can snapshot ``depth``/
        ``rate`` wherever is lock-safe and keep the computation itself
        free of queue/deque reads (lint rule R3)."""
        depth = max(1, int(depth))
        if rate <= 0:
            return 1.0
        return float(min(60.0, max(1.0, math.ceil(depth / rate))))

    def retry_after_s(self) -> float:
        """Suggested client wait before retrying a rejected request
        (the 429 ``Retry-After`` header value).  The drain-rate read
        comes FIRST — it only walks the completion deque — and the
        queue's own mutex is taken last and alone (``qsize()``), so
        this stays callable from rejection paths without ever nesting
        the queue mutex under another lock."""
        rate = self.drain_rate()
        return self._retry_after(self._queue.qsize(), rate)

    # ------------------------------------------------------------- submit
    def predict(self, features, timeout: Optional[float] = None,
                block: bool = True, version: Optional[int] = None,
                tenant: Optional[str] = None):
        """Blocking inference: enqueue, coalesce, return this request's
        rows (thread-safe; the engine batches concurrent callers).
        ``block=False`` rejects with ``QueueFull`` instead of waiting
        for queue space — the HTTP front end's policy, where the
        bounded queue IS the buffer and saturation must 429.
        ``version=`` pins the request to a specific staged weight
        version (the rollout controller's probe path); the default
        routes active/canary per the configured canary fraction.
        ``tenant=`` attributes the request to a tenant for fair
        admission and per-tenant telemetry (default: the public
        tenant)."""
        return self.predict_async(features, block=block,
                                  version=version,
                                  tenant=tenant).result(timeout)

    def predict_async(self, features, block: bool = True,
                      timeout: Optional[float] = None,
                      version: Optional[int] = None,
                      tenant: Optional[str] = None) -> Future:
        """Enqueue and return a ``Future``.  With ``block=False`` (or a
        ``timeout``) a full queue raises ``QueueFull`` instead of
        blocking — the explicit backpressure signal.  With an SLO
        configured, overload sheds with :class:`SloShed` regardless of
        queue room."""
        if not self._running:
            raise ServingError("engine not started (call start())")
        tenant = self._admit_or_shed(tenant)
        arrays = self._canonicalize(features)
        sig = self._signature(arrays)
        req = _Request(arrays, int(arrays[0].shape[0]), sig,
                       self._route_version(version), tenant=tenant)
        try:
            self._queue.put(req, block=block, timeout=timeout)
        except queue.Full:
            # Retry-After inputs are snapshotted here, after put()
            # has released the queue's internals: the drain-rate walk
            # must never run with the queue mutex pinned, and qsize()
            # is the only call that briefly re-takes it (R3).
            rate = self.drain_rate()
            depth = self._queue.qsize()
            _monitor.counter("serving_rejected_total",
                             "requests rejected at queue capacity").inc(
                engine=self._name)
            _monitor.record_incident("queue_full", {
                "engine": self._name,
                "queue_capacity": self._queue.maxsize,
            })
            raise QueueFull(
                f"serving queue at capacity "
                f"({self._queue.maxsize}); retry or raise "
                f"queue_capacity",
                self._retry_after(depth, rate)) from None
        _monitor.counter("serving_requests_total",
                         "requests admitted to the serving queue").inc(
            engine=self._name)
        self._observe_queue_depth()
        return req.future

    # ------------------------------------------------------------ sessions
    @property
    def sessions(self):
        """The engine's :class:`~deeplearning4j_tpu.serving.sessions.
        SessionCache` (created on first use; raises for models without
        carry support)."""
        with self._session_lock:
            if self._sessions is None:
                from .sessions import SessionCache
                step_fn = None
                if self._qdecode is not None:
                    # int8 engines step sessions through the quantized
                    # decode jit; hot-swap is forbidden for int8, so
                    # the live qparams/net_state are closure constants
                    qd, qp, ns = (self._qdecode, self._qparams,
                                  self._model.net_state)
                    if self._is_graph:
                        def step_fn(carries, *feats, **_kw):
                            return qd(qp, ns, carries, tuple(feats))
                    else:
                        def step_fn(carries, feats, **_kw):
                            return qd(qp, ns, carries, feats)
                self._sessions = SessionCache(
                    self._model, name=self._name,
                    version_fn=lambda: self._active_version,
                    weights_fn=self._weights_for_version,
                    step_fn=step_fn,
                    **self._session_opts)
            return self._sessions

    def predict_session(self, session_id: str, features,
                        tenant: Optional[str] = None):
        """Streaming inference: advance ``session_id``'s device-resident
        state tree (RNN carries, or KV-cache rings for decode models) by
        the given timesteps — ONE dispatch per step (per token for
        decode) — and return the output.  Subject to the same SLO
        admission as ``predict``; not queued/coalesced — session state
        is a chain, so each session serializes its own steps while
        distinct sessions run concurrently."""
        if not self._running:
            raise ServingError("engine not started (call start())")
        tenant = self._admit_or_shed(tenant)
        t0 = time.perf_counter()
        out = self.sessions.step(session_id, features,
                                 dtype=self._dtype)
        _monitor.counter("serving_requests_total",
                         "requests admitted to the serving queue").inc(
            engine=self._name)
        self._observe_latency((time.perf_counter() - t0) * 1000.0,
                              _monitor.current_trace_hex(),
                              tenant=tenant)
        return out

    # ------------------------------------------------- token generation
    def prefill_session(self, session_id: str, ids,
                        chunk: Optional[int] = None,
                        cache_len: Optional[int] = None) -> int:
        """Put a prompt's ids (batch, tokens) into ``session_id``'s
        state, ``chunk`` tokens a dispatch, computing no logits; a new
        session's rings get ``cache_len`` slots.  Returns the session's
        position (``SessionCache.prefill``)."""
        if not self._running:
            raise ServingError("engine not started (call start())")
        return self.sessions.prefill(session_id, ids, chunk=chunk,
                                     cache_len=cache_len)

    def fork_session(self, src: str, dst: str) -> None:
        """``dst`` becomes a copy of ``src``'s device state, version and
        position: a conversation that continues a prefilled prefix
        (``SessionCache.fork``)."""
        self.sessions.fork(src, dst)

    def generate(self, session_id: str, ids, max_new_tokens: int,
                 tenant: Optional[str] = None):
        """Greedy token generation in ``session_id``: integer ``ids``
        (batch, tokens not yet seen) in, ``max_new_tokens`` sampled ids
        a row out (``.ids``, on the host), sampled on the device and fed
        back there, one dispatch a token (``SessionCache.generate``).
        Admitted like ``predict_session``; the latency observed is the
        whole call's."""
        if not self._running:
            raise ServingError("engine not started (call start())")
        tenant = self._admit_or_shed(tenant)
        t0 = time.perf_counter()
        out = self.sessions.generate(session_id, ids, max_new_tokens)
        _monitor.counter("serving_requests_total",
                         "requests admitted to the serving queue").inc(
            engine=self._name)
        self._observe_latency((time.perf_counter() - t0) * 1000.0,
                              _monitor.current_trace_hex(),
                              tenant=tenant)
        return out

    # ------------------------------------------------------------- warmup
    def warmup(self, example_shape) -> int:
        """Eagerly AOT-compile every bucket executable on every worker.

        ``example_shape`` is ONE example's feature shape (no batch
        axis) — e.g. ``(784,)`` for an MLP, ``(T, n_in)`` for a
        sequence input — or a tuple/list of such shapes for multi-input
        graphs.  For sequence inputs (rank >= 2 with timestep bucketing
        enabled) axis 0 is time and is replaced by each ladder entry.
        Returns the number of executables compiled.
        """
        if self._is_graph and isinstance(example_shape, (list, tuple)) \
                and example_shape and isinstance(example_shape[0],
                                                 (list, tuple)):
            shapes = [tuple(s) for s in example_shape]
        else:
            shapes = [tuple(example_shape)]
        if len(shapes) != self._n_inputs:
            raise ValueError(f"expected {self._n_inputs} example shapes, "
                             f"got {len(shapes)}")
        per_input = []
        for shp in shapes:
            if self._policy.timestep_buckets and len(shp) >= 2:
                per_input.append([("seq", tuple(shp[1:]), tb)
                                  for tb in self._policy.timestep_buckets])
            else:
                per_input.append([("dense", tuple(shp), None)])
        n = 0
        for combo in itertools.product(*per_input):
            for bb in self._policy.batch_buckets:
                key = (tuple(combo), bb)
                for widx in range(len(self._devices)):
                    if self._ensure_executable(widx, key):
                        n += 1
        return n

    def warmup_decode(self, example_shape, chunk_lens=(1,)) -> int:
        """Pre-compile the single-dispatch decode step across the
        (batch, cache_len) bucket grid, plus the adjacent-bucket grow
        transitions, so after warmup every session token and every
        cache-len ladder hop is compile-free — the contract the armed
        ``serving.decode_step`` sanitizer asserts.

        ``example_shape`` is ONE token's feature shape (no batch/time
        axes) — e.g. ``(n_in,)`` — or a tuple of such shapes for
        multi-input graphs.  ``chunk_lens`` are the chunk lengths to
        warm (the default ``(1,)`` is pure autoregressive decode).
        Batches warm at the engine's batch-bucket ladder; sessions use
        the request's exact batch size, so clients should send
        ladder-sized batches (batch 1 is always on the ladder).  A hop
        that SKIPS ladder buckets (a chunk larger than the next bucket)
        still compiles once on first use.  Returns the number of fresh
        compiles this call caused.
        """
        model = self._model
        if not getattr(model, "has_kv_ring", lambda: False)():
            raise ServingError(
                "warmup_decode requires a model with KV-ring "
                "(causal_attention) layers")
        if self._is_graph and isinstance(example_shape, (list, tuple)) \
                and example_shape and isinstance(example_shape[0],
                                                 (list, tuple)):
            shapes = [tuple(s) for s in example_shape]
        else:
            shapes = [tuple(example_shape)]
        if len(shapes) != self._n_inputs:
            raise ValueError(f"expected {self._n_inputs} example shapes, "
                             f"got {len(shapes)}")
        from .bucketing import batch_ladder
        # the ladder of the rings that grow; one bucket, the layers' own
        # sizes, where none does (window rings alone)
        longest = model.max_cache_len()
        ladder = batch_ladder(longest) if longest else (None,)
        prefix = "cg" if self._is_graph else "mln"
        fns = ((prefix + ".decode_step_int8",) if self._qdecode is not None
               else (prefix + ".decode_step",)) + (prefix + ".decode_grow",)

        def _compiles() -> float:
            c = _monitor.counter("jit_compiles_total", "")
            return sum(c.value(fn=f) for f in fns)

        n0 = _compiles()
        for bb in self._policy.batch_buckets:
            for t in chunk_lens:
                t = int(t)
                feats = tuple(np.zeros((bb, t) + shp, self._dtype)
                              for shp in shapes)
                for i, cap in enumerate(ladder):
                    if cap is not None and t > cap:
                        continue
                    carries = model._init_carries(bb, cache_len=cap)
                    if self._qdecode is not None:
                        self._qdecode(self._qparams, model.net_state,
                                      carries,
                                      feats if self._is_graph
                                      else feats[0])
                    elif self._is_graph:
                        # the sessions' program: it donates its carries
                        model.decode_step(
                            model._init_carries(bb, cache_len=cap), *feats,
                            donate=True)
                    else:
                        model.decode_step(carries, feats[0])
                    if i + 1 < len(ladder):
                        model.grow_decode_carries(carries, ladder[i + 1])
        return int(_compiles() - n0)

    # ------------------------------------------------------------- paging
    def model_bytes(self) -> int:
        """Device bytes ONE worker's resident copy of this model costs
        (params + state; the uint8 tree when ``quantize="int8"``),
        times the number of live weight versions (a staged canary
        doubles the footprint until promote/rollback drops one tree) —
        the registry pager's accounting unit."""
        return self._model_bytes * max(1, len(self._weights))

    def resident_bytes(self) -> int:
        """Currently-placed device bytes across workers and versions
        (0 when paged out)."""
        with self._placed_lock:
            if self._backend == "native":
                return (self._runner.resident_bytes()
                        if self._runner is not None else 0)
            return self._model_bytes * len(self._placed)

    def is_resident(self) -> bool:
        return self.resident_bytes() > 0

    def ensure_resident(self) -> int:
        """Page this model's weights onto every worker device (no-op
        when already there) — every live version, so a staged canary
        survives a page-out/page-in cycle.  Returns resident bytes."""
        if self._backend == "native":
            self._runner.ensure_device_buffers()
            return self.resident_bytes()
        for widx in range(len(self._devices)):
            for v in list(self._weights):
                self._placed_params(widx, v)
        return self.resident_bytes()

    def release_device_buffers(self) -> int:
        """Drop every worker's placed weight buffers (the pager's evict
        primitive) — all versions.  Compiled bucket executables survive —
        they take the weights as call operands, so the next
        ``ensure_resident`` (or lazy ``_placed_params``) page-in reuses
        them without any recompilation; a paged-out standby/canary
        version re-places itself on the next request routed to it.
        Returns bytes released."""
        with self._placed_lock:
            if self._backend == "native":
                return (self._runner.free_device_buffers()
                        if self._runner is not None else 0)
            freed = self._model_bytes * len(self._placed)
            # in-flight dispatches hold their own references; dropping
            # ours lets the device free the buffers once they finish
            self._placed = {}
            return freed

    # ---------------------------------------------------------- deployment
    @property
    def active_version(self) -> int:
        return self._active_version

    @property
    def canary_version(self) -> Optional[int]:
        return self._canary_version

    @property
    def canary_fraction(self) -> float:
        return self._canary_fraction

    def versions(self) -> List[int]:
        """Servable weight versions currently staged (active + canary +
        staged), ascending."""
        return sorted(self._weights)

    def _require_swappable(self) -> None:
        if self._backend == "native":
            raise ServingError(
                "weight hot-swap requires backend='aot' (the native "
                "runner uploads the model's own buffers)")
        if self._quantize:
            raise ServingError(
                "weight hot-swap requires quantize=None: int8 engines "
                "bake per-tensor decode specs into the executable, so "
                "new weights would need a recompile — deploy the f32 "
                "engine and re-quantize offline instead")

    def stage_weights(self, params, net_state=None,
                      version: Optional[int] = None) -> int:
        """Register a new host weight tree as a servable version
        ALONGSIDE the active one (no routing change, no compile, no
        placement until traffic or ``ensure_resident`` touches it).
        ``version=None`` allocates the next monotonic version.
        Returns the version."""
        self._require_swappable()
        with self._placed_lock:
            if version is None:
                version = self._max_version_seen + 1
            version = int(version)
            if version <= self._max_version_seen:
                raise ValueError(
                    f"version {version} is not newer than "
                    f"{self._max_version_seen}; versions are monotonic")
            state = (net_state if net_state is not None
                     else self._model.net_state)
            self._weights[version] = (params, state)
            self._max_version_seen = version
        return version

    def set_canary(self, version: int, fraction: float = 0.1) -> None:
        """Route ``fraction`` of un-pinned predict traffic to
        ``version`` (deterministic counter-based split, so tests and
        canary windows are exact, not stochastic)."""
        fraction = min(1.0, max(0.0, float(fraction)))
        with self._placed_lock:
            if version not in self._weights:
                raise ValueError(
                    f"unknown weight version {version}; staged: "
                    f"{sorted(self._weights)}")
            if version == self._active_version:
                raise ValueError(
                    f"version {version} is already active")
            self._canary_version = int(version)
            self._canary_fraction = fraction
        _monitor.gauge(
            "deploy_canary_fraction",
            "fraction of predict traffic routed to the canary").set(
            fraction, model=self._name)

    def promote(self, version: Optional[int] = None) -> int:
        """Atomic pointer flip: make ``version`` (default: the canary)
        the active weights, retire the old active tree (kept only while
        in-flight sessions pin it) and clear the canary.  Swap wall
        time exports as ``deploy_swap_seconds``."""
        t0 = time.perf_counter()
        self._require_swappable()
        with self._placed_lock:
            if version is None:
                version = self._canary_version
            if version is None or version not in self._weights:
                raise ValueError(
                    f"cannot promote version {version}; staged: "
                    f"{sorted(self._weights)}")
            version = int(version)
            old = self._active_version
            self._active_version = version
            if self._canary_version == version:
                self._canary_version = None
                self._canary_fraction = 0.0
            if old != version and old in self._weights:
                self._retire_locked(old)
            self._purge_unpinned_locked()
        # eagerly place the new active tree so the first post-swap
        # request pays no host->device copy
        for widx in range(len(self._devices)):
            self._placed_params(widx, version)
        _monitor.histogram(
            "deploy_swap_seconds",
            "wall time of a weight promote (pointer flip + placement)"
        ).observe(time.perf_counter() - t0, model=self._name)
        # the version flip changes which live sessions count as pinned;
        # session gauges otherwise refresh only on set changes
        sessions = self._sessions
        if sessions is not None:
            sessions.refresh_gauges()
        _monitor.gauge(
            "deploy_version",
            "active served weight version").set(version, model=self._name)
        _monitor.gauge(
            "deploy_canary_fraction",
            "fraction of predict traffic routed to the canary").set(
            0.0, model=self._name)
        return version

    def rollback(self) -> Optional[int]:
        """Drop the canary: routing reverts to 100% active and the
        canary tree is discarded (kept only while sessions pin it).
        Returns the dropped version (None when no canary was set)."""
        with self._placed_lock:
            cv = self._canary_version
            self._canary_version = None
            self._canary_fraction = 0.0
            if cv is not None and cv in self._weights \
                    and cv != self._active_version:
                self._retire_locked(cv)
            self._purge_unpinned_locked()
        _monitor.gauge(
            "deploy_canary_fraction",
            "fraction of predict traffic routed to the canary").set(
            0.0, model=self._name)
        return cv

    def swap_weights(self, params, net_state=None,
                     version: Optional[int] = None) -> int:
        """Stage + promote in one call: immediately serve ``params`` as
        the active weights (zero-recompile — executables take weights
        as operands).  The canary path is ``stage_weights`` +
        ``set_canary`` + ``promote``/``rollback``."""
        v = self.stage_weights(params, net_state=net_state,
                               version=version)
        return self.promote(v)

    def warm_from_store(self, store, version: Optional[int] = None
                        ) -> Optional[int]:
        """Hydrate this engine's weights from a
        :class:`~deeplearning4j_tpu.deploy.store.VersionedWeightStore`
        snapshot (default: the latest) — the fleet worker's boot path,
        making the store the single source of truth for what a fresh
        process serves.  The store's monotonic stamp becomes the
        engine's active version when it is newer than anything staged;
        an empty store is a no-op (the init weights serve).  Returns
        the store version now active, or None."""
        from ..deploy.store import tree_from_flat
        if version is None:
            version = store.latest()
        if version is None:
            return None
        snap = store.load(int(version))
        params = tree_from_flat(self._model, snap.flat)
        if snap.version > self._max_version_seen:
            self.swap_weights(params, version=snap.version)
        else:
            self.swap_weights(params)
        return snap.version

    def _retire_locked(self, version: int) -> None:
        """Drop ``version`` from the servable set; its host tree is
        retained in ``_session_pins`` while an in-flight session is
        pinned to it (materializing the live-model sentinel if
        needed)."""
        if version in self._session_pinned_versions():
            self._session_pins[version] = self._host_weights(version)
        del self._weights[version]
        for key in [k for k in self._placed if k[1] == version]:
            del self._placed[key]

    def _purge_unpinned_locked(self) -> None:
        if not self._session_pins:
            return
        pinned = self._session_pinned_versions()
        for v in list(self._session_pins):
            if v not in pinned:
                del self._session_pins[v]

    def _session_pinned_versions(self):
        s = self._sessions
        return s.pinned_versions() if s is not None else set()

    def _route_version(self, version: Optional[int] = None) -> int:
        if version is not None:
            v = int(version)
            if v not in self._weights:
                raise ValueError(
                    f"unknown weight version {v}; staged: "
                    f"{sorted(self._weights)}")
            return v
        cv, frac = self._canary_version, self._canary_fraction
        if cv is not None and frac > 0.0:
            # deterministic evenly-interleaved split (no burst of
            # canary-only traffic): request i goes to the canary when
            # the running quota floor(i*frac) ticks up
            i = next(self._route_counter)
            if int((i + 1) * frac) > int(i * frac):
                return cv
        return self._active_version

    def _host_weights(self, version: int):
        tree = self._weights[version]
        if tree is None:   # live-model sentinel (initial version)
            if self._quantize:
                return (self._qparams, self._model.net_state)
            import jax
            # snapshot to host: the placed tuple must not alias the
            # live model's device buffers — a concurrent fit() donates
            # those, and a donated buffer dies under the serving
            # executable mid-request
            return (jax.tree_util.tree_map(np.asarray,
                                           self._model.params),
                    jax.tree_util.tree_map(np.asarray,
                                           self._model.net_state))
        return tree

    def _weights_for_version(self, version: int):
        """Host tree for a session pinned to ``version`` (None means
        "use the model's live weights" — the initial sentinel, or a
        version whose tree is gone)."""
        if version in self._weights:
            return (None if self._weights[version] is None
                    else self._weights[version])
        return self._session_pins.get(version)

    # ------------------------------------------------------- introspection
    def stats(self) -> dict:
        d = {
            "running": self._running,
            "queue_depth": self._queue.qsize(),
            "queue_capacity": self._queue.maxsize,
            "executables": len(self._compiled),
            "workers": len(self._devices),
            "devices": [str(d) for d in self._devices],
            "backend": self._backend,
            "quantize": self._quantize,
            "batch_buckets": list(self._policy.batch_buckets),
            "timestep_buckets": list(self._policy.timestep_buckets),
            "model_bytes": self.model_bytes(),
            "resident_bytes": self.resident_bytes(),
            "drain_rate_rps": round(self.drain_rate(), 2),
            "active_version": self._active_version,
            "canary_version": self._canary_version,
            "canary_fraction": self._canary_fraction,
            "versions": sorted(self._weights),
        }
        if self._admission is not None:
            d["admission"] = self._admission.snapshot()
            d["tenants"] = self._admission.tenant_snapshot()
        if self._sessions is not None:
            d["sessions"] = self._sessions.stats()
        return d

    def bucket_keys(self):
        """Warmed (signature, batch_bucket) keys (all workers)."""
        return sorted({k for (_, k) in self._compiled})

    # ------------------------------------------------------------ internals
    def _canonicalize(self, features) -> Tuple[np.ndarray, ...]:
        if self._is_graph and isinstance(features, (list, tuple)):
            arrays = tuple(np.asarray(f, dtype=self._dtype)
                           for f in features)
        else:
            arrays = (np.asarray(features, dtype=self._dtype),)
        if len(arrays) != self._n_inputs:
            raise ValueError(f"model expects {self._n_inputs} inputs, "
                             f"got {len(arrays)}")
        rows = {a.shape[0] for a in arrays}
        if len(rows) != 1:
            raise ValueError(f"inputs disagree on batch size: {rows}")
        n = rows.pop()
        if n < 1:
            raise ValueError("empty batch")
        if n > self._policy.max_batch_size:
            raise ValueError(
                f"request of {n} rows exceeds max_batch_size="
                f"{self._policy.max_batch_size}; split the request")
        for a in arrays:
            if a.ndim < 2:
                raise ValueError(
                    "features must include a batch axis: shape "
                    f"{a.shape}")
        return arrays

    def _signature(self, arrays) -> Tuple:
        sig = []
        for a in arrays:
            if self._policy.timestep_buckets and a.ndim >= 3:
                # validates length <= largest bucket too
                tb = self._policy.time_bucket(a.shape[1])
                sig.append(("seq", tuple(a.shape[2:]), tb))
            else:
                sig.append(("dense", tuple(a.shape[1:]), None))
        return tuple(sig)

    def _placed_params(self, widx: int, version: Optional[int] = None):
        if version is None:
            version = self._active_version
        with self._placed_lock:
            if version not in self._weights:
                # the version was promoted away or rolled back between
                # enqueue and dispatch: serve the active tree (what the
                # request would be routed to if resubmitted) instead of
                # failing a request that raced a control-plane flip
                version = self._active_version
            placed = self._placed.get((widx, version))
            if placed is None:
                import jax
                placed = jax.device_put(self._host_weights(version),
                                        self._devices[widx])
                self._placed[(widx, version)] = placed
            return placed

    def _ensure_executable(self, widx: int, key) -> bool:
        """Compile the bucket executable for (worker, key) if missing.
        Returns True when a compile happened."""
        if (widx, key) in self._compiled or self._backend == "native":
            return False
        with self._compile_lock:
            if (widx, key) in self._compiled:
                return False
            sig, bb = key
            params, state = self._placed_params(widx)
            feature_shapes, mask_shapes, any_mask = [], [], False
            for kind, trailing, tb in sig:
                if kind == "seq":
                    feature_shapes.append((bb, tb) + trailing)
                    mask_shapes.append((bb, tb))
                    any_mask = True
                else:
                    feature_shapes.append((bb,) + trailing)
                    mask_shapes.append(None)
            if self._quantize:
                fn = self._compile_quantized(
                    params, state, feature_shapes,
                    mask_shapes if any_mask else None)
            elif self._is_graph:
                fn = self._model.compile_output(
                    feature_shapes, dtype=self._dtype,
                    mask_shapes=tuple(mask_shapes) if any_mask else None,
                    mask_dtype=self._dtype, params=params, net_state=state)
            else:
                fn = self._model.compile_output(
                    feature_shapes[0], dtype=self._dtype,
                    mask_shape=mask_shapes[0], mask_dtype=self._dtype,
                    params=params, net_state=state)
            self._compiled[(widx, key)] = fn
            _monitor.counter(
                "serving_bucket_compiles_total",
                "AOT bucket executables compiled").inc(engine=self._name)
            _monitor.gauge(
                "serving_bucket_executables",
                "live AOT bucket executables").set(
                len(self._compiled), engine=self._name)
            return True

    def _compile_quantized(self, qparams, state, feature_shapes,
                           mask_shapes):
        """AOT-compile the decode+forward program for one bucket: same
        lowering contract as ``compile_output`` but against the uint8
        params tree (the decode fuses into the consuming matmul/conv)."""
        import jax
        dt = np.dtype(self._dtype)
        avals = tuple(jax.ShapeDtypeStruct(tuple(int(d) for d in s), dt)
                      for s in feature_shapes)
        mavals = None
        if mask_shapes is not None:
            mavals = tuple(
                None if s is None
                else jax.ShapeDtypeStruct(tuple(int(d) for d in s), dt)
                for s in mask_shapes)
        if self._is_graph:
            return self._qjit.lower(qparams, state, avals,
                                    mavals).compile()
        return self._qjit.lower(qparams, state, avals[0],
                                None if mavals is None
                                else mavals[0]).compile()

    def _batcher_loop(self):
        pending = None
        while True:
            if pending is not None:
                req, pending = pending, None
            else:
                try:
                    req = self._queue.get(timeout=0.05)
                except queue.Empty:
                    if not self._running:
                        return
                    continue
                req.t_dequeue = time.perf_counter()
                self._observe_queue_depth()
            batch, rows = [req], req.n_rows
            deadline = time.perf_counter() + self._max_latency_s
            while rows < self._policy.max_batch_size:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                nxt.t_dequeue = time.perf_counter()
                self._observe_queue_depth()
                if (nxt.sig != req.sig
                        or nxt.version != req.version
                        or rows + nxt.n_rows
                        > self._policy.max_batch_size):
                    pending = nxt  # seeds the next batch (FIFO-fair)
                    break
                batch.append(nxt)
                rows += nxt.n_rows
            job = _BatchJob(batch, req.sig, rows, req.version)
            while True:  # backpressure: wait for a worker slot
                try:
                    self._dispatch_q.put(job, timeout=0.05)
                    break
                except queue.Full:
                    if not self._running:
                        for r in batch:
                            if not r.future.done():
                                r.future.set_exception(
                                    ServingError("engine stopped"))
                        return

    def _worker_loop(self, widx: int):
        while True:
            try:
                job = self._dispatch_q.get(timeout=0.05)
            except queue.Empty:
                if not self._running:
                    return
                continue
            try:
                self._run_batch(widx, job)
            except Exception as exc:  # route failures to the callers
                for r in job.requests:
                    if not r.future.done():
                        r.future.set_exception(exc)

    def _run_batch(self, widx: int, job: _BatchJob):
        bb = self._policy.batch_bucket(job.rows)
        feats, masks, wastes = [], [], []
        for i, (kind, _trailing, tb) in enumerate(job.sig):
            x, m, _, waste = assemble_batch(
                [r.arrays[i] for r in job.requests], bb,
                tb if kind == "seq" else None, mask_dtype=self._dtype)
            feats.append(x)
            masks.append(m)
            wastes.append(waste)
        key = (job.sig, bb)
        self._ensure_executable(widx, key)
        t0 = time.perf_counter()
        if self._backend == "native":
            outs = self._runner.output(*feats)
            outs = outs if isinstance(outs, list) else [outs]
            outs = [np.asarray(o) for o in outs]
        else:
            params, state = self._placed_params(widx, job.version)
            fn = self._compiled[(widx, key)]
            if self._is_graph:
                fmasks = (tuple(masks)
                          if any(m is not None for m in masks) else None)
                outs = [np.asarray(o) for o in
                        fn(params, state, tuple(feats), fmasks)]
            else:
                outs = [np.asarray(fn(params, state, feats[0], masks[0]))]
        now = time.perf_counter()
        _monitor.histogram("serving_batch_ms",
                           "device dispatch wall time per batch").observe(
            (now - t0) * 1000.0, engine=self._name)
        _monitor.counter("serving_batches_total",
                         "coalesced batches dispatched").inc(
            engine=self._name)
        _monitor.histogram(
            "serving_batch_fill_ratio",
            "real rows / bucket rows per dispatched batch, per model"
        ).observe(job.rows / bb, model=self._name)
        _monitor.histogram(
            "serving_padding_waste_ratio",
            "padded elements carrying no real data, per batch, per model"
        ).observe(float(np.mean(wastes)), model=self._name)
        # time-unpad is only unambiguous with a single sequence input
        # (seq-to-seq outputs carry its time axis at the bucket length)
        seq_inputs = [i for i, (kind, _, _) in enumerate(job.sig)
                      if kind == "seq"]
        seq_i = seq_inputs[0] if len(seq_inputs) == 1 else None
        tb = job.sig[seq_i][2] if seq_i is not None else None
        self._record_batch_spans(job, t0, now)
        off = 0
        for r in job.requests:
            sl = [o[off:off + r.n_rows] for o in outs]
            if seq_i is not None:
                t_real = r.arrays[seq_i].shape[1]
                if t_real < tb:
                    sl = [o[:, :t_real]
                          if o.ndim >= 3 and o.shape[1] == tb else o
                          for o in sl]
            r.future.set_result(sl[0] if len(sl) == 1 else sl)
            self._observe_latency((now - r.t_enqueue) * 1000.0,
                                  f"{r.trace_id:032x}",
                                  version=job.version, tenant=r.tenant)
            off += r.n_rows

    def _record_batch_spans(self, job: _BatchJob, t_exec0: float,
                            t_done: float) -> None:
        """Reconstruct the request-level causality as trace spans: one
        ``serve/request`` span per member (parented under the context
        captured at submit time), with ``queue_wait`` / ``batch_assembly``
        / ``dispatch`` child segments, plus one ``serve/batch`` span that
        *links* every coalesced request span (batch-to-request causality
        is N:1, not parent/child — the batch belongs to no single
        request's trace)."""
        tr = _monitor.tracer()
        wall_now = time.time()

        def wall(t_perf: float) -> float:
            return wall_now - (time.perf_counter() - t_perf)

        for r in job.requests:
            parent = r.ctx.span_id if r.ctx is not None else None
            tr.record_span(
                "serve/request", trace_id=r.trace_id, span_id=r.span_id,
                parent_id=parent, ts=r.t_wall,
                dur_ms=(t_done - r.t_enqueue) * 1e3,
                model=self._name, rows=r.n_rows)
            for seg, seg_t0, seg_t1 in (
                    ("serve/queue_wait", r.t_enqueue, r.t_dequeue),
                    ("serve/batch_assembly", r.t_dequeue, t_exec0),
                    ("serve/dispatch", t_exec0, t_done)):
                tr.record_span(
                    seg, trace_id=r.trace_id, parent_id=r.span_id,
                    ts=wall(seg_t0),
                    dur_ms=max(0.0, (seg_t1 - seg_t0) * 1e3))
        lead = job.requests[0]
        tr.record_span(
            "serve/batch", trace_id=lead.trace_id,
            ts=wall(lead.t_dequeue),
            dur_ms=max(0.0, (t_done - lead.t_dequeue) * 1e3),
            links=[r.span_id for r in job.requests],
            model=self._name, rows=job.rows,
            n_requests=len(job.requests))
