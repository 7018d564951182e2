"""Device-resident per-session state trees for streaming inference.

PR 2's engine serves recurrent traffic by full-sequence recompute:
every request re-runs the whole conversation/series from t=0, so
request cost grows linearly with session length and a T-step session
pays O(T^2) total work.  The containers already have the O(1) primitive
— ``rnn_time_step`` (reference ``MultiLayerNetwork.rnnTimeStep:2230``)
carries hidden state between calls — but as a single mutable slot per
model instance it cannot serve concurrent sessions.

``SessionCache`` lifts that primitive to N concurrent sessions: each
session id owns a **state tree** that stays on device between requests
(the arrays returned by the jitted step are never fetched), so a
streaming request pays exactly ONE single-timestep dispatch — no host
round-trip for state, no recompute of the prefix.  The state tree is
whatever the model's carry contract says it is:

- **RNN carries** (h, c per layer) step through the containers'
  ``rnn_stateless_step`` under the ``serving.rnn_step`` sanitizer
  scenario (one dispatch per session step);
- **KV-cache rings** (``nn.layers.attention.CausalSelfAttention``:
  (batch, heads, cache_len, head_dim) K/V buffers + int32 cursor) step
  through ``decode_step`` under ``serving.decode_step`` (one dispatch
  per TOKEN — ``units=T`` for a T-token chunk), with a host-tracked
  position driving a powers-of-two **cache-len bucket ladder**: a
  session that outgrows its ring hops to the next bucket via ONE jitted
  ``grow_decode_carries`` dispatch (budgeted as the scenario's
  ``extra``), and after engine ``warmup_decode`` every hop is
  compile-free.  The host never reads the device cursor — position
  accounting is pure host arithmetic, so no sync point enters the hot
  path.
- **Capacity is about the rings that grow.**  A session's ``capacity``,
  the ladder, ``prefill(cache_len=)`` and the "does not fit" checks
  speak of the rings whose length follows the session's (latent rings,
  key/value rings of full attention, ``sparse_kv``).  A window layer's
  ring (``GroupedQueryAttention(window=...)``, kind ``window_kv``) is
  sized once by the layer at its window, wraps, and is neither grown
  by a hop nor asked whether a prompt fits: a session's position
  passes it by any amount.  A model whose rings are all windows has an
  empty ladder and no limit on a session's length.

Eviction (both counted in ``serving_session_evictions_total``):

- **TTL**: sessions idle longer than ``ttl_s`` are dropped on the next
  cache operation (abandoned conversations must not pin HBM forever) —
  dropping a decode session frees its KV ring's device bytes, visible
  in the ``serving_session_state_bytes`` gauge;
- **capacity**: at ``max_sessions`` the least-recently-used session is
  dropped first — the ``NativeModelRunner._execs`` LRU pattern applied
  to session state.

Thread safety: the cache map has its own lock; each session serializes
its steps on a per-session lock (state is a chain — two concurrent
steps for one session would fork it) while distinct sessions dispatch
concurrently.

Version pinning (docs/DEPLOY.md): a session's state tree is a function
of the weights that produced it, so advancing old state with new
weights after a hot-swap would chain two different models' dynamics.
Each session records the engine's active weight version at creation
(``version_fn``) and every subsequent step resolves that SAME version's
host tree (``weights_fn``) until the session ends or its TTL expires —
the engine retains a retired version's tree while any session pins it.
``serving_session_version_pinned`` gauges how many live sessions are
pinned behind the active version.

Error contract: a batch-size or state-structure mismatch raises
:class:`SessionStateError` naming the offending leaf path — and ONLY
raises; the stored state is untouched, so :meth:`clear` (or a matching
request) fully recovers the session slot.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import Counter, OrderedDict
from typing import Optional

import numpy as np

from .. import monitor as _monitor
from ..monitor.locks import make_lock
from .bucketing import batch_ladder
from .quantize import tree_nbytes


class SessionError(RuntimeError):
    """Session-path failures (unknown/expired ids are NOT errors — a new
    state tree is initialized; batch/structure mismatches, unsupported
    models, and overlong decode sessions are)."""


class SessionStateError(SessionError):
    """A request is incompatible with a session's stored state tree
    (batch-size change mid-session, or a state structure the current
    model no longer produces).  ``leaf_path`` names the first offending
    leaf (``jax.tree_util.keystr`` form, e.g. ``[0][0]`` for an MLN
    layer-0 carry or ``['attn'][0]`` for a graph vertex ring).  The
    stored state is left untouched: ``clear()`` the session — or send a
    matching request — to recover."""

    def __init__(self, message: str, leaf_path: Optional[str] = None):
        super().__init__(message)
        self.leaf_path = leaf_path


def _as_features(f, dtype) -> np.ndarray:
    """Features as a host array in ``dtype``; an array of integers
    (token ids) keeps its own."""
    if getattr(getattr(f, "dtype", None), "kind", "") in "iu":
        return np.asarray(f)
    return np.asarray(f, dtype=dtype)


@dataclasses.dataclass
class Generation:
    """What :meth:`SessionCache.generate` returns.  ``ids`` is on the
    host, (batch, new tokens); ``kept_logits`` stays on the device, one
    (2, vocabulary) float32 array a step (rows 0 and batch-1), for
    whoever compares them with a reference afterwards;
    ``expert_tokens`` is ``{vertex: (n_experts,) picks}`` over the
    call's steps, ``experts_spilled`` ``{vertex: steps}`` whose held
    pairs outgrew the grouped form's rows (more rounds, the same sum)."""

    ids: np.ndarray
    kept_logits: list
    expert_tokens: dict
    experts_spilled: dict


class _Session:
    __slots__ = ("carries", "batch", "last_used", "lock", "steps",
                 "version", "position", "capacity", "state_bytes")

    def __init__(self, carries, batch: int, version: Optional[int] = None,
                 capacity: int = 0):
        self.carries = carries
        self.batch = batch
        self.last_used = time.monotonic()
        self.lock = make_lock("serving.session")
        self.steps = 0
        self.version = version
        self.position = 0          # tokens already decoded (host-side)
        self.capacity = capacity   # current KV ring bucket (0 = RNN)
        self.state_bytes = tree_nbytes(carries)


class SessionCache:
    """Per-session device-resident state trees for one model.

    >>> cache = SessionCache(model, ttl_s=300.0, max_sessions=1024)
    >>> y0 = cache.step("sess-1", x_t0)     # one timestep, one dispatch
    >>> y1 = cache.step("sess-1", x_t1)     # state stayed on device
    >>> cache.clear("sess-1")               # end of conversation

    For models with KV-cache rings (``model.has_kv_ring()``) the step
    runs ``decode_step`` under the ``serving.decode_step`` scenario and
    ring capacity follows a powers-of-two bucket ladder up to the
    layers' ``cache_len``; a session decoding past the top of the
    ladder raises :class:`SessionError`.

    ``step_fn`` overrides the model-step callable — the int8 engine
    passes its quantized-decode jit; the signature must match the
    container step (``(carries, x, **kw)`` for MLN, ``(carries, *xs,
    **kw)`` for graphs) and return ``(out, new_carries)``.
    """

    def __init__(self, model, *, ttl_s: float = 300.0,
                 max_sessions: int = 1024, name: str = "default",
                 version_fn=None, weights_fn=None, step_fn=None):
        from ..nn.computation_graph import ComputationGraph
        model.init()
        model._require_carry_support("SessionCache")
        self._model = model
        self._is_graph = isinstance(model, ComputationGraph)
        self._ttl_s = float(ttl_s)
        self._max_sessions = int(max_sessions)
        if self._max_sessions < 1:
            raise ValueError("max_sessions must be >= 1")
        self._name = str(name)
        self._sessions: "OrderedDict[str, _Session]" = OrderedDict()
        self._lock = make_lock("serving.sessions.cache")
        # deployment hooks (set by InferenceEngine): version_fn() is the
        # engine's active weight version at session creation; weights_fn(v)
        # resolves the pinned version's host tree (None = live weights)
        self._version_fn = version_fn
        self._weights_fn = weights_fn
        self._step_fn = step_fn
        # decode tier: KV-ring models step through decode_step under the
        # per-token budget and ladder their ring capacity
        self._decode = bool(getattr(model, "has_kv_ring",
                                    lambda: False)())
        # ring state by kind, as the layers name theirs (``STATE_KIND``:
        # ``latent`` rings of compressed rows, ``sparse_kv`` key/value
        # rings with an indexer's beside them, ``kv`` key/value rings of
        # full attention, ``window_kv`` a window's rings, which wrap):
        # ``{kind: [vertices]}``
        self._ring_kinds: dict = {}
        if self._decode and self._is_graph:
            for n in model._layer_names():
                kind = getattr(model.vertices[n].layer, "STATE_KIND", "")
                if kind:
                    self._ring_kinds.setdefault(kind, []).append(n)
        self._scenario = ("serving.decode_step" if self._decode
                          else "serving.rnn_step")
        # the ladder of the rings that grow; empty where none does
        longest = model.max_cache_len() if self._decode else 0
        self._cache_ladder = batch_ladder(longest) if longest else ()

    # ------------------------------------------------------------- metrics
    # Refreshed when the session SET changes (create/evict/clear), not
    # per step: three labelled gauge writes plus a per-session sum cost
    # more than a decode dispatch, and nothing they publish moves while
    # an existing session steps (a ring grow defers its state_bytes
    # delta to the next set change; ``state_bytes()`` is always live).
    def _observe_active(self) -> None:
        _monitor.gauge("serving_sessions_active",
                       "live device-resident serving sessions").set(
            len(self._sessions), model=self._name)
        _monitor.gauge(
            "serving_session_state_bytes",
            "device bytes held by live session state trees "
            "(RNN carries + KV-cache rings)").set(
            sum(s.state_bytes for s in self._sessions.values()),
            model=self._name)
        for kind, vertices in self._ring_kinds.items():
            _monitor.gauge("serving_session_state_bytes", "").set(
                sum(tree_nbytes([s.carries[v] for v in vertices])
                    for s in self._sessions.values()),
                model=self._name, kind=kind)
        if self._version_fn is not None:
            active = self._version_fn()
            pinned = sum(1 for s in self._sessions.values()
                         if s.version is not None and s.version != active)
            _monitor.gauge(
                "serving_session_version_pinned",
                "live sessions pinned to a non-active weight version"
            ).set(pinned, model=self._name)

    def refresh_gauges(self) -> None:
        """Re-publish the session gauges outside a set change: the
        pinned count moves when the ENGINE's active version flips
        (promote/swap_weights), not when the session set does."""
        with self._lock:
            self._observe_active()

    def _count_eviction(self, reason: str) -> None:
        _monitor.counter("serving_session_evictions_total",
                         "sessions evicted from the device cache").inc(
            model=self._name, reason=reason)

    # ------------------------------------------------------- state checks
    def _check_state(self, session_id: str, sess: _Session,
                     batch: int) -> None:
        """Raise :class:`SessionStateError` naming the first offending
        leaf when the stored state tree cannot serve this request.
        Leaf-path naming works for ANY state tree (RNN carries, KV
        rings, future state classes) — no RNN assumptions."""
        import jax
        if sess.batch == batch:
            return
        path = None
        for kp, leaf in jax.tree_util.tree_flatten_with_path(
                sess.carries)[0]:
            shape = getattr(leaf, "shape", ())
            if len(shape) >= 1 and shape[0] == sess.batch:
                path = jax.tree_util.keystr(kp)
                break
        raise SessionStateError(
            f"session {session_id!r} holds state for batch size "
            f"{sess.batch} (first batch-carrying leaf: "
            f"{path or '<none>'}), got {batch}; clear() the session "
            "between unrelated sequences", leaf_path=path)

    def _check_structure(self, session_id: str, sess: _Session) -> None:
        """A session whose stored tree no longer matches the model's
        state structure (e.g. state injected from an older architecture)
        must fail with the offending path, not a jit tracer error."""
        import jax
        got = jax.tree.structure(sess.carries)
        want = jax.tree.structure(
            self._model._init_carries(sess.batch) if not sess.capacity
            else self._model._init_carries(sess.batch,
                                           cache_len=sess.capacity))
        if got == want:
            return
        got_paths = [jax.tree_util.keystr(kp) for kp, _ in
                     jax.tree_util.tree_flatten_with_path(sess.carries)[0]]
        want_paths = [jax.tree_util.keystr(kp) for kp, _ in
                      jax.tree_util.tree_flatten_with_path(
                          self._model._init_carries(sess.batch))[0]]
        odd = next((p for p in got_paths if p not in want_paths),
                   next((p for p in want_paths if p not in got_paths),
                        "<structure>"))
        raise SessionStateError(
            f"session {session_id!r} state tree does not match the "
            f"model's carry structure (offending leaf: {odd}); clear() "
            "the session", leaf_path=odd)

    # ------------------------------------------------------------ stepping
    def step(self, session_id: str, features,
             dtype=None) -> np.ndarray:
        """Advance ``session_id`` by the given timesteps and return the
        output for exactly those steps.

        2-D input ``(batch, features)`` is one timestep and returns
        ``(batch, n_out)``; 3-D ``(batch, time, features)`` advances by
        a chunk and returns ``(batch, time, n_out)``.  Unknown session
        ids start from zero state.  A batch-size change mid-session
        raises :class:`SessionStateError` naming the offending leaf
        (reference ``rnnTimeStep`` semantics) — call :meth:`clear`
        between unrelated sequences.
        """
        if self._is_graph:
            feats = (tuple(features) if isinstance(features, (list, tuple))
                     else (features,))
            arrays = tuple(_as_features(f, dtype) for f in feats)
            batch = int(arrays[0].shape[0])
            squeeze = arrays[0].ndim == 2
            if squeeze:   # (batch, feat) = one timestep
                arrays = tuple(a[:, None, :] if a.ndim == 2 else a
                               for a in arrays)
            steps = int(arrays[0].shape[1])
        else:
            x = _as_features(features, dtype)
            batch = int(x.shape[0])
            squeeze = x.ndim == 2
            if squeeze:   # (batch, feat) = one timestep
                x = x[:, None, :]
            steps = int(x.shape[1])
        sess = self._acquire(session_id, batch, steps)
        with sess.lock:
            self._check_state(session_id, sess, batch)
            # Version pinning: a session created before a weight swap
            # keeps stepping with the version its carries came from.
            kw = self._pinned_weights(sess)
            grow_to = 0
            if self._decode:
                grow_to = self._bucket_for(session_id, sess, steps)
            # ONE dispatch per token (+1 for a bucket hop): explicit-
            # state step, state stays on device — the budgeted contract
            # the armed sanitizer asserts (tools/analyze/budgets.json)
            with _monitor.sanitize_scenario(
                    self._scenario,
                    units=(steps if self._decode else 1),
                    extra=(1 if grow_to else 0)):
                if grow_to:
                    try:
                        sess.carries = self._model.grow_decode_carries(
                            sess.carries, grow_to)
                    except Exception:
                        # same typed-error contract as the step itself:
                        # a stored tree the model cannot grow gets
                        # diagnosed, never a raw tracer error
                        self._check_structure(session_id, sess)
                        raise
                    sess.capacity = grow_to
                    sess.state_bytes = tree_nbytes(sess.carries)
                out, sess.carries = self._dispatch(
                    session_id, sess, arrays if self._is_graph else x, kw)
            sess.position += steps
            sess.steps += 1
            sess.last_used = time.monotonic()
        _monitor.counter("serving_session_steps_total",
                         "single-dispatch session timesteps served").inc(
            model=self._name)
        if isinstance(out, list):
            out = [np.asarray(o) for o in out]
            return [o[:, -1] if squeeze and o.ndim == 3 else o
                    for o in out]
        out = np.asarray(out)
        return out[:, -1] if squeeze and out.ndim == 3 else out

    def _dispatch(self, session_id: str, sess: _Session, features, kw):
        """One compiled step of the session's state tree."""
        try:
            if self._step_fn is not None:
                if self._is_graph:
                    outs, new = self._step_fn(sess.carries, *features,
                                              **kw)
                else:
                    return self._step_fn(sess.carries, features, **kw)
            elif self._decode:
                if self._is_graph:
                    # the session keeps the new tree only: the step may
                    # update the rings in place
                    outs, new = self._model.decode_step(
                        sess.carries, *features, donate=True, **kw)
                else:
                    return self._model.decode_step(sess.carries, features,
                                                   **kw)
            else:
                if self._is_graph:
                    outs, new = self._model.rnn_stateless_step(
                        sess.carries, *features, **kw)
                else:
                    return self._model.rnn_stateless_step(
                        sess.carries, features, **kw)
        except SessionError:
            raise
        except Exception:
            # a state tree the step cannot consume surfaces as whatever
            # the tracer threw; diagnose against the model's expected
            # carry structure first (a mismatch raises the typed error
            # naming the leaf), and re-raise the original otherwise
            self._check_structure(session_id, sess)
            raise
        return (outs[0] if len(outs) == 1 else outs), new

    def _bucket_for(self, session_id: str, sess: _Session,
                    steps: int) -> int:
        """The ladder bucket this chunk needs, or 0 when the current
        ring already fits.  Raises past the top of the ladder."""
        need = sess.position + steps
        if need <= sess.capacity or not self._cache_ladder:
            return 0
        for cap in self._cache_ladder:
            if cap >= need and cap > sess.capacity:
                return cap
        raise SessionError(
            f"session {session_id!r} has decoded {sess.position} tokens; "
            f"{steps} more would exceed the model's cache_len "
            f"{self._cache_ladder[-1] if self._cache_ladder else 0} — "
            "clear() the session or raise the layer's cache_len")

    # ----------------------------------------------------- token models
    def _require_tokens(self) -> None:
        if not (self._decode and self._is_graph
                and len(self._model.conf.network_inputs) == 1
                and len(self._model.conf.network_outputs) == 1):
            raise SessionError(
                "token generation needs a graph with one input of ids, "
                "one output of logits and ring-carrying attention")

    def _pinned_weights(self, sess: _Session) -> dict:
        if self._weights_fn is not None and sess.version is not None:
            w = self._weights_fn(sess.version)
            if w is not None:
                return {"params": w[0], "net_state": w[1]}
        return {}

    def prefill(self, session_id: str, ids, chunk: Optional[int] = None,
                cache_len: Optional[int] = None) -> int:
        """Advance ``session_id`` over the prompt ``ids`` (batch, tokens)
        for its state alone, no logits: ``chunk`` tokens a dispatch (the
        remainder first, so that every later chunk has one shape), each
        under the ``serve/prefill_chunk`` span.  A new session gets a
        ring of ``cache_len`` slots (default: the model's) and keeps it;
        one that would outgrow its ring raises.  Returns the session's
        position."""
        self._require_tokens()
        ids = np.asarray(ids)
        if ids.ndim != 2 or ids.dtype.kind not in "iu":
            raise SessionError("prefill wants integer ids (batch, tokens)")
        ids = ids.astype(np.int32)
        batch, total = int(ids.shape[0]), int(ids.shape[1])
        chunk = int(chunk or total or 1)
        sess = self._acquire(session_id, batch, total,
                             capacity=int(cache_len or self._top()))
        with sess.lock:
            self._check_state(session_id, sess, batch)
            if self._overflows(sess, total):
                raise SessionError(
                    f"session {session_id!r} holds {sess.position} tokens "
                    f"in a ring of {sess.capacity}; {total} more do not fit")
            kw = self._pinned_weights(sess)
            first = total % chunk
            bounds = ([0, first] if first else [0]) + list(
                range(first + chunk, total + 1, chunk))
            with _monitor.sanitize_scenario(self._scenario,
                                            units=max(len(bounds) - 1, 1)):
                for lo, hi in zip(bounds, bounds[1:]):
                    with _monitor.span("serve/prefill_chunk",
                                       tokens=hi - lo):
                        sess.carries = self._model.prefill_step(
                            sess.carries, ids[:, lo:hi], **kw)
            chunks = Counter(hi - lo for lo, hi in zip(bounds, bounds[1:]))
            self._count_expert_steps(batch, chunks)
            sess.position += total
            self._count_attention_steps(
                sess, chunks, ("sparse_kv", "kv", "window_kv"),
                pinned=bool(kw))
            sess.steps += 1
            sess.last_used = time.monotonic()
            return sess.position

    def fork(self, src: str, dst: str) -> None:
        """Make ``dst`` a copy of ``src`` as it stands: a device copy of
        the state tree (one dispatch, under ``serve/fork``), with its
        weight version and position.  What a conversation that shares a
        prefilled prefix starts from; the donating steps of ``dst`` leave
        ``src`` intact.  An existing ``dst`` is dropped first, so that
        its bytes are free for the copy."""
        with self._lock:
            self._sweep_locked(time.monotonic())
            source = self._sessions.get(src)
            if source is not None and dst != src:
                self._sessions.pop(dst, None)
        if source is None:
            raise SessionError(f"no session {src!r} to fork")
        with source.lock, _monitor.span("serve/fork"):
            copy = _Session(self._model.fork_carries(source.carries),
                            source.batch, source.version, source.capacity)
            copy.position = source.position
            source.last_used = time.monotonic()
        with self._lock:
            while len(self._sessions) >= self._max_sessions:
                self._sessions.popitem(last=False)
                self._count_eviction("capacity")
            self._sessions[dst] = copy
            self._observe_active()

    def generate(self, session_id: str, ids,
                 max_new_tokens: int) -> Generation:
        """Greedy generation: feed ``ids`` (batch, tokens; the tokens
        the session has not seen yet, at least one), then each step's
        argmax, ``max_new_tokens`` times.  One dispatch a token
        (``cg.token_step``, under ``serve/decode_step`` and the
        ``serving.decode_step`` budget); the sampled ids go from step to
        step as device arrays, and the host's copy of step ``t`` is taken
        after step ``t + 1`` is dispatched (``serve/token_fetch``), so the
        device never waits for the host."""
        self._require_tokens()
        ids = np.asarray(ids)
        if ids.ndim != 2 or ids.dtype.kind not in "iu" or not ids.shape[1]:
            raise SessionError("generate wants integer ids (batch, tokens)")
        n = int(max_new_tokens)
        if n < 1:
            raise SessionError("max_new_tokens must be at least 1")
        batch, fed = int(ids.shape[0]), int(ids.shape[1])
        sess = self._acquire(session_id, batch, fed + n - 1,
                             capacity=self._top())
        model = self._model
        with sess.lock, _monitor.span("serve/generate", tokens=n):
            self._check_state(session_id, sess, batch)
            if self._overflows(sess, fed + n - 1):
                raise SessionError(
                    f"session {session_id!r} holds {sess.position} tokens "
                    f"in a ring of {sess.capacity}; {fed + n - 1} more do "
                    "not fit")
            kw = self._pinned_weights(sess)
            step_ids, counts = ids.astype(np.int32), None
            pending, host_ids, kept = None, [], []
            with _monitor.sanitize_scenario(self._scenario, units=n):
                for _ in range(n):
                    with _monitor.span("serve/decode_step"):
                        step_ids, logits, counts, sess.carries = \
                            model.token_step(sess.carries, step_ids,
                                             counts, **kw)
                    kept.append(logits)
                    if pending is not None:
                        with _monitor.span("serve/token_fetch"):
                            host_ids.append(np.asarray(pending))
                    pending = step_ids
                with _monitor.span("serve/token_fetch"):
                    host_ids.append(np.asarray(pending))
                    counts = np.asarray(counts)
            sess.position += fed + n - 1
            sess.steps += n
            sess.last_used = time.monotonic()
        _monitor.counter("serving_tokens_generated_total",
                         "tokens sampled by session generation").inc(
            batch * n, model=self._name)
        _monitor.counter("serving_session_steps_total",
                         "single-dispatch session timesteps served").inc(
            n, model=self._name)
        # the first step takes the ``fed`` ids, every later one its own
        by_length = Counter([fed] + [1] * (n - 1))
        self._count_attention_steps(
            sess, by_length, ("latent", "sparse_kv", "kv", "window_kv"),
            pinned=bool(kw))
        self._count_expert_steps(batch, by_length)
        tokens = _monitor.counter(
            "moe_expert_tokens_total",
            "tokens routed to each expert, by layer")
        # of those, the picks that named an expert this layer holds (a
        # share routes over more experts than it computes): summed here
        # from the same counts, no output of the step's own
        held_picks = _monitor.counter(
            "moe_held_picks_total",
            "picks that named an expert the layer holds, by layer")
        experts_held = _monitor.gauge(
            "moe_experts_held", "experts an expert layer holds, by layer")
        spilled = _monitor.counter(
            "moe_experts_spilled_total",
            "token steps whose held pairs outgrew the grouped form's rows "
            "and took further rounds, by layer")
        by_vertex = dict(zip(model._expert_vertices(), counts[:, :-1]))
        spills = dict(zip(by_vertex, counts[:, -1:].ravel().tolist()))
        for vertex, row in by_vertex.items():
            spilled.inc(spills[vertex], model=self._name, layer=vertex)
            for expert, picks in enumerate(row):
                if picks:
                    tokens.inc(int(picks), model=self._name, layer=vertex,
                               expert=str(expert))
            held = model.vertices[vertex].layer.held()
            held_picks.inc(int(row[held].sum()), model=self._name,
                           layer=vertex)
            experts_held.set(len(held), model=self._name, layer=vertex)
        return Generation(np.concatenate(host_ids, axis=1), kept, by_vertex,
                          spills)

    def _count_attention_steps(self, sess: _Session, by_length,
                               kinds, pinned: bool = False) -> None:
        """The launched steps of ``by_length`` (tokens a row -> steps)
        by the form their attention took, for the ring state of
        ``kinds``.  By the op's own predicate, asked here and not where
        the step is traced: a warm start loads ``cg.token_step`` and
        ``cg.prefill_step`` without tracing them.  The latent
        attention's count also says which weights its steps multiplied
        (``weights``): ``laid`` once by a served net
        (``ComputationGraph.served_params``), or ``stored``, laid inside
        the step (a net not prepared; ``pinned``: a deploy's version
        handed to the step as it is stored).  Also publishes
        ``sparse_attention_selected{layer}``: the rows a query at the
        session's position (already advanced by the caller) reads."""
        model = self._model
        laid = () if pinned else model.laid_vertices()
        dense = _monitor.counter(
            "gqa_attention_steps_total",
            "launched token steps and prefill chunks, by the kind of ring "
            "their dense grouped-query attention reads (kv: one that "
            "grows; window_kv: a window's, which wraps) and the form it "
            "took")
        counters = {
            "kv": dense, "window_kv": dense,
            "latent": _monitor.counter(
                "latent_attention_steps_total",
                "launched token steps, by the form their latent attention "
                "took and the weights it multiplied"),
            "sparse_kv": _monitor.counter(
                "sparse_attention_steps_total",
                "launched token steps and prefill chunks, by the form "
                "their indexed sparse attention took")}
        for kind in kinds:
            vertices = self._ring_kinds.get(kind, ())
            if not vertices:
                continue
            launched = counters[kind]
            for t, steps in by_length.items():
                forms = set()
                for v in vertices:
                    form = {"path": model.vertices[v].layer.attention_path(
                        t, sess.carries[v])}
                    if kind == "latent":
                        form["weights"] = "laid" if v in laid else "stored"
                    elif launched is dense:
                        form["kind"] = kind
                    forms.add(tuple(form.items()))
                for form in forms:
                    launched.inc(steps, **dict(form))
            if kind == "sparse_kv":
                selected = _monitor.gauge(
                    "sparse_attention_selected",
                    "cached rows a query of the newest position reads, "
                    "by layer")
                for v in vertices:
                    selected.set(min(sess.position,
                                     model.vertices[v].layer.topk),
                                 model=self._name, layer=v)

    def _count_expert_steps(self, batch: int, by_length) -> None:
        """``moe_experts_steps_total{path}``: the launched steps of
        ``by_length`` (tokens a row -> steps), by the form their routed
        experts took.  Asked of the layers' own predicate here, where
        steps are launched, and not where they are traced: a warm start
        loads ``cg.prefill_step`` and ``cg.token_step`` without tracing
        them."""
        experts = [self._model.vertices[v].layer
                   for v in self._model._expert_vertices()]
        if not experts:
            return
        launched = _monitor.counter(
            "moe_experts_steps_total",
            "launched prefill chunks and token steps, by the form their "
            "routed experts' products took")
        dtype = self._model._pol().compute_dtype
        for t, steps in by_length.items():
            for path in {layer.experts_path(batch * t, dtype)
                         for layer in experts}:
                launched.inc(steps, path=path)

    def _top(self) -> int:
        """The longest the rings that grow may get (0: none grows)."""
        return self._cache_ladder[-1] if self._cache_ladder else 0

    def _overflows(self, sess: _Session, more: int) -> bool:
        """Whether ``more`` positions would pass the capacity of the
        session's rings that grow (never, where none does: a window's
        ring wraps)."""
        return bool(self._cache_ladder) \
            and sess.position + more > sess.capacity

    def _acquire(self, session_id: str, batch: int,
                 steps: int = 1, capacity: int = 0) -> _Session:
        now = time.monotonic()
        with self._lock:
            changed = self._sweep_locked(now)
            sess = self._sessions.get(session_id)
            if sess is None:
                changed = True
                while len(self._sessions) >= self._max_sessions:
                    self._sessions.popitem(last=False)   # LRU out
                    self._count_eviction("capacity")
                if self._cache_ladder and not capacity:
                    capacity = self._cache_ladder[0]
                    for cap in self._cache_ladder:
                        if cap >= steps:
                            capacity = cap
                            break
                if self._decode:
                    carries = self._model._init_carries(
                        batch, cache_len=capacity)
                else:
                    carries = self._model._init_carries(batch)
                version = (self._version_fn()
                           if self._version_fn is not None else None)
                sess = self._sessions[session_id] = _Session(
                    carries, batch, version, capacity)
            else:
                self._sessions.move_to_end(session_id)   # LRU touch
            if changed:
                self._observe_active()
            return sess

    def _sweep_locked(self, now: float) -> bool:
        if self._ttl_s <= 0:
            return False
        dead = [sid for sid, s in self._sessions.items()
                if now - s.last_used > self._ttl_s]
        for sid in dead:
            del self._sessions[sid]
            self._count_eviction("ttl")
        return bool(dead)

    # ---------------------------------------------------------- management
    def clear(self, session_id: str) -> bool:
        """Drop one session's device state (end of conversation) — the
        documented recovery from :class:`SessionStateError`."""
        with self._lock:
            gone = self._sessions.pop(session_id, None) is not None
            self._observe_active()
        return gone

    def clear_all(self) -> None:
        with self._lock:
            self._sessions.clear()
            self._observe_active()

    def pinned_versions(self):
        """Weight versions pinned by at least one live session — what
        the engine consults before discarding a retired tree."""
        with self._lock:
            return {s.version for s in self._sessions.values()
                    if s.version is not None}

    def session_version(self, session_id: str) -> Optional[int]:
        """The weight version ``session_id`` is pinned to (None for
        unknown sessions or un-versioned caches)."""
        with self._lock:
            sess = self._sessions.get(session_id)
            return None if sess is None else sess.version

    def get_carries(self, session_id: str):
        """The session's state tree (device arrays), or None —
        ``rnn_get_previous_state`` lifted to named sessions."""
        with self._lock:
            sess = self._sessions.get(session_id)
            return None if sess is None else sess.carries

    def session_position(self, session_id: str) -> int:
        """Tokens decoded so far (host-tracked; 0 for unknown ids)."""
        with self._lock:
            sess = self._sessions.get(session_id)
            return 0 if sess is None else sess.position

    def session_capacity(self, session_id: str) -> int:
        """Current KV ring bucket (0 for RNN sessions/unknown ids)."""
        with self._lock:
            sess = self._sessions.get(session_id)
            return 0 if sess is None else sess.capacity

    def state_bytes(self) -> int:
        """Device bytes held by every live session's state tree — what
        TTL eviction frees (the registry's accounting sees the same
        number via the ``serving_session_state_bytes`` gauge)."""
        with self._lock:
            return sum(s.state_bytes for s in self._sessions.values())

    def __len__(self) -> int:
        with self._lock:
            return len(self._sessions)

    def stats(self) -> dict:
        with self._lock:
            now = time.monotonic()
            return {
                "sessions": len(self._sessions),
                "max_sessions": self._max_sessions,
                "ttl_s": self._ttl_s,
                "decode": self._decode,
                "state_bytes": sum(s.state_bytes
                                   for s in self._sessions.values()),
                "oldest_idle_s": round(
                    max((now - s.last_used for s in
                         self._sessions.values()), default=0.0), 3),
                "total_steps": sum(s.steps
                                   for s in self._sessions.values()),
                "pinned_versions": sorted(
                    {s.version for s in self._sessions.values()
                     if s.version is not None}),
            }
