"""Device-side training health: in-jit layer stats + divergence guards.

The reference samples per-layer statistics on the host
(``BaseStatsListener.java``) — our port runs those listeners AFTER the
jitted step returns, so per-step update magnitudes were explicitly
unobservable and any listener forced the fused one-dispatch-per-epoch
scan (``docs/INGEST.md``) back to per-step dispatch.  This module moves
the statistics INSIDE the compiled step instead, the TensorFlow-paper
position that health monitoring must live in the dataflow, not around
it:

- :func:`layer_stats` packs per-layer grad L2 norm, param L2 norm and
  update:param ratio plus a non-finite/explosion flag into ONE small
  f32 vector, built from values the step already holds in registers.
  On the scan paths the per-step vectors are stacked as an extra scan
  output, so full per-step health telemetry crosses the wire once per
  dispatch — the single-HLO-per-epoch invariant is untouched.
- :func:`guard_select` is the in-jit divergence guard: under policy
  ``skip_update`` a flagged step's updates are replaced by the identity
  update (pre-step params/updater/net state selected with
  ``jnp.where``) — the only place the pre-step values still exist,
  since the step donates its buffers.
- :func:`record_dispatch` is the host half: it decodes the packed
  stack, publishes ``train_health_*`` gauges, and enforces the policy
  (``abort`` raises :class:`TrainingDivergedError` with the offending
  layer and step; ``warn`` logs and marks the process diverged).

Packed vector layout for a model with L layers (all float32)::

    [loss, flag, grad_l2[0..L), param_l2[0..L), update_ratio[0..L)]

``flag`` is 1.0 when the step's loss, any per-layer grad norm, or any
per-layer update norm is non-finite, or any grad norm exceeds the
configured limit.  Under ``ParallelWrapper`` the stack is
``pmean``-reduced over the ``data`` axis, so a single worker's NaN
poisons (and therefore flags) the averaged vector.

The guard policy and grad-norm limit are read at TRACE time (they are
baked into the compiled program): configure health BEFORE the first
``fit`` of a network, or build a fresh network after reconfiguring.
When health is disabled (the default) the stats are still computed on
device — they are a few scalar reductions — but the host never fetches
the stack, so nothing blocks and nothing is published.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .metrics import registry

logger = logging.getLogger("deeplearning4j_tpu")

POLICIES = ("warn", "skip_update", "abort")
DEFAULT_GRAD_NORM_LIMIT = 1e6

_EPS = 1e-12

# gauge/counter names (the ``train_health_*`` series)
LOSS = "train_health_loss"
GRAD_L2 = "train_health_grad_l2"
PARAM_L2 = "train_health_param_l2"
UPDATE_RATIO = "train_health_update_ratio"
STATE = "train_health_state"
LAST_DISPATCH_TS = "train_health_last_dispatch_ts"
NONFINITE_TOTAL = "train_health_nonfinite_steps_total"
SKIPPED_TOTAL = "train_health_skipped_steps_total"

_HELP = {
    LOSS: "last device-observed per-step training loss",
    GRAD_L2: "last-step per-layer gradient L2 norm (computed in-jit)",
    PARAM_L2: "last-step per-layer parameter L2 norm (computed in-jit)",
    UPDATE_RATIO: "last-step per-layer update:param L2 ratio "
                  "(computed in-jit)",
    STATE: "training health state: 0 ok, 1 diverged (sticky until "
           "health reset)",
    LAST_DISPATCH_TS: "unix time of the most recent train-step dispatch",
    NONFINITE_TOTAL: "train steps flagged non-finite or grad-exploded "
                     "by the device-side guard",
    SKIPPED_TOTAL: "flagged train steps replaced by the identity update "
                   "(guard policy skip_update)",
}


class TrainingDivergedError(RuntimeError):
    """Raised by guard policy ``abort``: a dispatch contained a step
    whose loss/grad/update statistics were non-finite (or whose grad
    norm exceeded the limit).  ``step`` is the global iteration index of
    the first flagged step and ``layer`` the first offending layer label
    (``"loss"`` when the loss itself was the first non-finite value) —
    both decoded host-side from the packed stats vector."""

    def __init__(self, message: str, step: Optional[int] = None,
                 layer: Optional[str] = None):
        super().__init__(message)
        self.step = step
        self.layer = layer


class HealthConfig:
    """Immutable snapshot of the health-layer configuration."""

    __slots__ = ("enabled", "policy", "grad_norm_limit")

    def __init__(self, enabled: bool, policy: str,
                 grad_norm_limit: float):
        if policy not in POLICIES:
            raise ValueError(
                f"unknown guard policy {policy!r}; pick one of {POLICIES}")
        self.enabled = bool(enabled)
        self.policy = policy
        self.grad_norm_limit = float(grad_norm_limit)


_lock = threading.Lock()
_config: Optional[HealthConfig] = None   # None -> read the env


class _HostState:
    def __init__(self):
        self.lock = threading.Lock()
        self.diverged = False
        self.last: Optional[Dict[str, Any]] = None
        self.last_dispatch_ts: Optional[float] = None


_state = _HostState()


def _env_config() -> HealthConfig:
    raw = os.environ.get("DL4J_TPU_HEALTH", "0").strip().lower()
    enabled = raw not in ("", "0", "false", "off")
    policy = os.environ.get("DL4J_TPU_HEALTH_POLICY", "warn").strip() \
        .lower() or "warn"
    limit = float(os.environ.get("DL4J_TPU_GRAD_NORM_LIMIT",
                                 DEFAULT_GRAD_NORM_LIMIT))
    return HealthConfig(enabled, policy, limit)


def config() -> HealthConfig:
    """The active configuration: :func:`enable`/:func:`disable` override,
    else ``DL4J_TPU_HEALTH`` / ``DL4J_TPU_HEALTH_POLICY`` /
    ``DL4J_TPU_GRAD_NORM_LIMIT``."""
    with _lock:
        if _config is not None:
            return _config
    return _env_config()


def enable(policy: str = "warn",
           grad_norm_limit: float = DEFAULT_GRAD_NORM_LIMIT) -> None:
    """Turn the health layer on with the given guard policy
    (``warn`` / ``skip_update`` / ``abort``).  Call BEFORE the first fit
    of a network: the policy and limit are baked into the traced step."""
    global _config
    with _lock:
        _config = HealthConfig(True, policy, grad_norm_limit)


def disable() -> None:
    """Turn the health layer off (stats still computed in-jit, never
    fetched)."""
    global _config
    with _lock:
        _config = HealthConfig(False, "warn", DEFAULT_GRAD_NORM_LIMIT)


def enabled() -> bool:
    return config().enabled


def reset() -> None:
    """Forget overrides (back to env config) and clear the host-side
    state (diverged flag, last-dispatch snapshot).  Does not affect
    already-traced programs."""
    global _config
    with _lock:
        _config = None
    with _state.lock:
        _state.diverged = False
        _state.last = None
        _state.last_dispatch_ts = None


# ---------------------------------------------------------------- in-jit

def _l2(tree) -> Any:
    """f32 L2 norm over every leaf of a (possibly empty) pytree."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.float32(0.0)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def layer_stats(old_params, new_params, grads, loss,
                order: Optional[Sequence] = None):
    """Pack per-layer health statistics, INSIDE the jitted step.

    ``old_params``/``new_params``/``grads`` are the per-layer containers
    the step already holds: lists of param trees for
    ``MultiLayerNetwork`` or name-keyed dicts for ``ComputationGraph``.
    ``order`` is their keys in the packed vector's layer order (the
    shared step passes those of ``Network._layer_items()``: list indices,
    or vertex names in topological order); None is every index of a
    list.  Returns
    ``(vec, bad)`` — the packed ``[loss, flag, grad_l2*, param_l2*,
    update_ratio*]`` f32 vector and the traced scalar bool that feeds
    :func:`guard_select`.  The update norm is taken from ``old - new``
    (the step the updater actually applied), so a flagged step reports
    the would-be explosion even when the guard then skips it.
    """
    import jax
    import jax.numpy as jnp
    from .device_trace import scope
    cfg = config()
    keys = list(order) if order is not None else list(range(len(grads)))
    g_norms, p_norms, ratios = [], [], []
    with scope("health"):
        finite = jnp.isfinite(jnp.asarray(loss, jnp.float32))
        explode = jnp.asarray(False)
        limit = jnp.float32(cfg.grad_norm_limit)
        for k in keys:
            g = _l2(grads[k])
            p = _l2(old_params[k])
            u = _l2(jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                old_params[k], new_params[k]))
            g_norms.append(g)
            p_norms.append(p)
            ratios.append(u / (p + _EPS))
            finite = finite & jnp.isfinite(g) & jnp.isfinite(u)
            explode = explode | (g > limit)
        bad = (~finite) | explode
        vec = jnp.stack([jnp.asarray(loss, jnp.float32),
                         bad.astype(jnp.float32)]
                        + g_norms + p_norms + ratios)
    return vec, bad


def guard_select(bad, new, old):
    """In-jit half of the divergence guard: under policy ``skip_update``
    a flagged step's outputs are replaced leaf-for-leaf by the pre-step
    values (identity update, bit-identical params).  Under any other
    policy this is the identity function — the select never enters the
    program.  ``new``/``old`` are matching pytrees (typically the
    ``(params, updater_state, net_state)`` triple)."""
    if config().policy != "skip_update":
        return new
    import jax
    import jax.numpy as jnp
    from .device_trace import scope
    with scope("health"):
        return jax.tree.map(lambda n, o: jnp.where(bad, o, n), new, old)


# ------------------------------------------------------------- host side

def layer_labels(model) -> List[str]:
    """Per-layer labels matching the packed vector's layer order: list
    indices for ``MultiLayerNetwork``, topo-ordered vertex names for
    ``ComputationGraph`` (the same prefixes ``param_table()`` uses)."""
    layers = getattr(model, "layers", None)
    if layers is not None:
        return [str(i) for i in range(len(layers))]
    return [str(n) for n in model._layer_names()]


def _offender(row: np.ndarray, names: List[str],
              limit: float) -> tuple:
    """Decode the first offending (layer, reason) from a flagged step's
    packed vector."""
    L = len(names)
    if not np.isfinite(row[0]):
        return "loss", "non-finite loss"
    for j, n in enumerate(names):
        g = row[2 + j]
        r = row[2 + 2 * L + j]
        if not np.isfinite(g):
            return n, "non-finite gradient"
        if g > limit:
            return n, f"gradient L2 {g:.3g} > limit {limit:.3g}"
        if not np.isfinite(r):
            return n, "non-finite update"
    return "unknown", "flagged"


def record_dispatch(model, stack, first_iteration: int) -> None:
    """Host half of the health layer, called once per train dispatch
    with the packed per-step stats (shape ``(S, 2+3L)`` from the scan
    paths, ``(2+3L,)`` from the per-batch step).

    Always stamps the last-dispatch timestamp (no device sync).  When
    the health layer is enabled it additionally fetches the stack — the
    ONE small device->host transfer per dispatch — publishes the
    ``train_health_*`` gauges from the final step, stores the
    last-dispatch snapshot for ``GET /health`` and the listeners, and
    enforces the guard policy: ``abort`` raises
    :class:`TrainingDivergedError` decoded to the first flagged step and
    layer; ``warn``/``skip_update`` log and mark the process diverged.
    """
    now = time.time()
    with _state.lock:
        _state.last_dispatch_ts = now
    reg = registry()
    reg.gauge(LAST_DISPATCH_TS, _HELP[LAST_DISPATCH_TS]).set(now)
    cfg = config()
    if not cfg.enabled:
        return
    arr = np.atleast_2d(np.asarray(stack, dtype=np.float32))
    names = layer_labels(model)
    L = len(names)
    last = arr[-1]
    reg.gauge(LOSS, _HELP[LOSS]).set(float(last[0]))
    layers: Dict[str, Dict[str, float]] = {}
    for j, n in enumerate(names):
        stats = {"grad_l2": float(last[2 + j]),
                 "param_l2": float(last[2 + L + j]),
                 "update_ratio": float(last[2 + 2 * L + j])}
        layers[n] = stats
        reg.gauge(GRAD_L2, _HELP[GRAD_L2]).set(stats["grad_l2"], layer=n)
        reg.gauge(PARAM_L2, _HELP[PARAM_L2]).set(stats["param_l2"],
                                                 layer=n)
        reg.gauge(UPDATE_RATIO, _HELP[UPDATE_RATIO]).set(
            stats["update_ratio"], layer=n)
    flags = ~np.isfinite(arr[:, 1]) | (arr[:, 1] != 0.0)
    n_bad = int(flags.sum())
    snap: Dict[str, Any] = {
        "time": now,
        "model": type(model).__name__,
        "policy": cfg.policy,
        "first_iteration": int(first_iteration),
        "steps": int(arr.shape[0]),
        "flagged_steps": n_bad,
        "loss": float(last[0]),
        "layers": layers,
    }
    if n_bad:
        s = int(np.argmax(flags))
        step = int(first_iteration) + s
        layer, reason = _offender(arr[s], names, cfg.grad_norm_limit)
        snap["diverged_at"] = {"step": step, "layer": layer,
                               "reason": reason}
        reg.counter(NONFINITE_TOTAL, _HELP[NONFINITE_TOTAL]).inc(n_bad)
        reg.gauge(STATE, _HELP[STATE]).set(1.0)
        with _state.lock:
            _state.diverged = True
            _state.last = snap
        model._health_last = snap
        model._health_last_stack = arr
        msg = (f"training diverged at step {step} (layer {layer}: "
               f"{reason}); {n_bad}/{arr.shape[0]} steps in this "
               f"dispatch flagged, policy={cfg.policy}")
        # Dump the flight-recorder bundle BEFORE the abort unwinds: the
        # bundle must capture the spans/metrics as they are at the
        # moment of divergence (lazy import — flight_recorder imports
        # this module).
        from . import flight_recorder as _flight
        _flight.record_incident("divergence", dict(
            snap["diverged_at"], policy=cfg.policy,
            flagged_steps=n_bad, loss=snap["loss"]))
        if cfg.policy == "abort":
            raise TrainingDivergedError(msg, step=step, layer=layer)
        if cfg.policy == "skip_update":
            reg.counter(SKIPPED_TOTAL, _HELP[SKIPPED_TOTAL]).inc(n_bad)
        logger.warning(msg)
        return
    reg.gauge(STATE, _HELP[STATE]).set(1.0 if _state.diverged else 0.0)
    with _state.lock:
        _state.last = snap
    model._health_last = snap
    model._health_last_stack = arr


def last_for(model) -> Optional[Dict[str, Any]]:
    """The last recorded dispatch snapshot for this model (None when the
    health layer has not recorded one), the per-step device stats the
    listeners switch to when health is on."""
    return getattr(model, "_health_last", None)


def last_stack_for(model) -> Optional[np.ndarray]:
    """The full ``(S, 2+3L)`` per-step stats stack of the model's last
    recorded dispatch (tests/parity tooling)."""
    return getattr(model, "_health_last_stack", None)


def state() -> str:
    """``"ok"`` or ``"diverged"`` (sticky until :func:`reset`)."""
    with _state.lock:
        return "diverged" if _state.diverged else "ok"


def last_dispatch_timestamp() -> Optional[float]:
    with _state.lock:
        return _state.last_dispatch_ts


def snapshot() -> Dict[str, Any]:
    """The ``GET /health`` body: configuration, current state, and the
    last-dispatch per-layer statistics."""
    cfg = config()
    with _state.lock:
        return {
            "enabled": cfg.enabled,
            "policy": cfg.policy,
            "grad_norm_limit": cfg.grad_norm_limit,
            "state": "diverged" if _state.diverged else "ok",
            "last_dispatch_timestamp": _state.last_dispatch_ts,
            "last_dispatch": _state.last,
        }
