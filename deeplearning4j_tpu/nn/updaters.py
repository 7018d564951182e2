"""Updaters, learning-rate policies, and gradient normalization.

TPU-native equivalent of the reference's ``nn/updater/LayerUpdater.java`` plus
ND4J's ``GradientUpdater`` implementations (Sgd/Adam/AdaDelta/Nesterovs/
RmsProp/AdaGrad/NoOp — reference ``LayerUpdater.java:240-270``).  The DL4J
order of operations is reproduced exactly (reference ``BaseUpdater.update``):

1. l1/l2 regularization added to the raw gradient per param
   (``LayerUpdater.java:104``: ``gradient += l2 * param + l1 * sign(param)``)
2. gradient normalization (``LayerUpdater.java:182-225``):
   RenormalizeL2PerLayer / RenormalizeL2PerParamType /
   ClipElementWiseAbsoluteValue / ClipL2PerLayer / ClipL2PerParamType
3. learning-rate policy applied for the current iteration
   (``LayerUpdater.java:135-154``)
4. per-param updater transform producing the step that the step function
   subtracts from the params in place.

Everything is a pure function of ``(grads, params, state, iteration)`` so the
whole update fuses into the jitted train step (one XLA program — the "single
HLO graph" north star).  Updater state is a pytree mirroring the params,
which flattens to the single contiguous ``updaterState.bin`` view for
serialization parity (reference ``BaseUpdater.setStateViewArray:34-48``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .conf import serde as _serde

Array = jax.Array
ParamTree = Dict[str, Array]

_EPS_ADAGRAD = 1e-6
_EPS_ADAM = 1e-8
_EPS_ADADELTA = 1e-6
_EPS_RMSPROP = 1e-8


@_serde.register("updater_conf", custom=True)
@dataclasses.dataclass
class UpdaterConfig:
    """Serializable updater hyperparameters (subset of
    ``NeuralNetConfiguration`` fields that feed ``LayerUpdater``)."""

    updater: str = "sgd"              # sgd|adam|adadelta|nesterovs|rmsprop|adagrad|lars|none
    learning_rate: float = 0.1
    # lr policy (reference LearningRatePolicy enum)
    lr_policy: str = "none"           # none|exponential|inverse|step|poly|sigmoid|schedule
    lr_policy_decay_rate: float = 0.0
    lr_policy_power: float = 1.0
    lr_policy_steps: float = 1.0
    max_num_iterations: int = 1       # for poly
    lr_schedule: Optional[Dict[int, float]] = None  # iteration -> lr
    # momentum (nesterovs)
    momentum: float = 0.9
    momentum_schedule: Optional[Dict[int, float]] = None
    # adam
    adam_mean_decay: float = 0.9
    adam_var_decay: float = 0.999
    # rmsprop
    rms_decay: float = 0.95
    # adadelta
    rho: float = 0.95
    epsilon: float = 1e-6
    # lars (beyond the 2016 reference; the large-batch layer-wise
    # adaptive-rate technique of the MLPerf-on-TPU-pods literature)
    lars_trust_coefficient: float = 0.001
    lars_weight_decay: float = 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        # JSON object keys are strings; keep schedules serializable
        for k in ("lr_schedule", "momentum_schedule"):
            if d[k] is not None:
                d[k] = {str(i): v for i, v in d[k].items()}
        return d

    @staticmethod
    def from_dict(d: dict) -> "UpdaterConfig":
        d = dict(d)
        for k in ("lr_schedule", "momentum_schedule"):
            if d.get(k):
                d[k] = {int(i): v for i, v in d[k].items()}
        return UpdaterConfig(**d)


# ---------------------------------------------------------------------------
# Learning-rate policies (reference LayerUpdater.applyLrDecayPolicy)
# ---------------------------------------------------------------------------

def learning_rate_for(conf: UpdaterConfig, iteration: Array) -> Array:
    """Effective lr at ``iteration`` (traced scalar -> jit friendly)."""
    lr = jnp.asarray(conf.learning_rate, jnp.float32)
    it = jnp.asarray(iteration, jnp.float32)
    policy = conf.lr_policy.lower()
    if policy in ("none", ""):
        return lr
    decay = conf.lr_policy_decay_rate
    if policy == "exponential":
        return lr * jnp.power(decay, it)
    if policy == "inverse":
        return lr / jnp.power(1.0 + decay * it, conf.lr_policy_power)
    if policy == "step":
        return lr * jnp.power(decay, jnp.floor(it / conf.lr_policy_steps))
    if policy == "torchstep":
        # reference: every `steps` iterations multiply by decay
        return lr * jnp.power(decay, jnp.floor(it / conf.lr_policy_steps))
    if policy == "poly":
        frac = jnp.clip(it / max(conf.max_num_iterations, 1), 0.0, 1.0)
        return lr * jnp.power(1.0 - frac, conf.lr_policy_power)
    if policy == "sigmoid":
        return lr / (1.0 + jnp.exp(-decay * (it - conf.lr_policy_steps)))
    if policy == "schedule":
        # piecewise-constant: last schedule entry with key <= iteration wins
        sched = sorted((conf.lr_schedule or {}).items())
        out = lr
        for step, value in sched:
            out = jnp.where(it >= step, jnp.asarray(value, jnp.float32), out)
        return out
    raise ValueError(f"Unknown lr policy '{conf.lr_policy}'")


def momentum_for(conf: UpdaterConfig, iteration: Array) -> Array:
    mu = jnp.asarray(conf.momentum, jnp.float32)
    if conf.momentum_schedule:
        it = jnp.asarray(iteration, jnp.float32)
        for step, value in sorted(conf.momentum_schedule.items()):
            mu = jnp.where(it >= step, jnp.asarray(value, jnp.float32), mu)
    return mu


# ---------------------------------------------------------------------------
# Gradient normalization (reference LayerUpdater.java:182-225)
# ---------------------------------------------------------------------------

def normalize_gradients(grads: ParamTree, mode: Optional[str],
                        threshold: float = 1.0) -> ParamTree:
    """Apply a DL4J ``GradientNormalization`` mode over one layer's grads."""
    if not mode or mode.lower() in ("none",):
        return grads
    mode = mode.lower()
    leaves = jax.tree_util.tree_leaves(grads)
    if mode == "renormalizel2perlayer":
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
        scale = 1.0 / jnp.clip(norm, 1e-12, None)
        return jax.tree.map(lambda g: g * scale, grads)
    if mode == "renormalizel2perparamtype":
        return jax.tree.map(
            lambda g: g / jnp.clip(jnp.linalg.norm(g.ravel()), 1e-12, None),
            grads)
    if mode == "clipelementwiseabsolutevalue":
        return jax.tree.map(
            lambda g: jnp.clip(g, -threshold, threshold), grads)
    if mode == "clipl2perlayer":
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
        scale = jnp.where(norm > threshold, threshold / norm, 1.0)
        return jax.tree.map(lambda g: g * scale, grads)
    if mode == "clipl2perparamtype":
        def clip_one(g):
            norm = jnp.linalg.norm(g.ravel())
            return g * jnp.where(norm > threshold, threshold / norm, 1.0)
        return jax.tree.map(clip_one, grads)
    raise ValueError(f"Unknown gradient normalization '{mode}'")


# ---------------------------------------------------------------------------
# Per-param updaters (ND4J GradientUpdater equivalents)
# ---------------------------------------------------------------------------

MASTER_KEY = "_master"


def init_state(conf: UpdaterConfig, params: ParamTree,
               policy=None) -> ParamTree:
    """Zero-initialized updater state mirroring the param tree.

    Mirrors ND4J ``BaseUpdater`` state layout: adam keeps (m, v), nesterovs
    keeps velocity, adagrad keeps historical sum, etc.  State for stateless
    updaters is an empty tuple so the pytree stays jit-stable.

    With a mixed :class:`~..precision.PrecisionPolicy` the moments are
    stored in ``policy.updater_dtype`` (fp32 even for bf16 params) and an
    extra ``"_master"`` tree of fp32 master weights rides alongside —
    inside the updater state so it is donated/carried/sharded/serialized
    exactly like the moments (docs/PERFORMANCE.md).
    """
    name = conf.updater.lower()
    if policy is not None:
        sdtype = jnp.dtype(policy.updater_dtype)
        zeros = lambda: jax.tree.map(
            lambda p: jnp.zeros(jnp.shape(p), sdtype), params)
    else:
        zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    if name in ("sgd", "none", "noop"):
        state: ParamTree = {}
    elif name == "nesterovs":
        state = {"v": zeros()}
    elif name == "adagrad":
        state = {"h": zeros()}
    elif name == "rmsprop":
        state = {"cache": zeros()}
    elif name == "adam":
        state = {"m": zeros(), "v": zeros()}
    elif name == "adadelta":
        state = {"msg": zeros(), "msdx": zeros()}
    elif name == "lars":
        state = {"v": zeros()}
    else:
        raise ValueError(f"Unknown updater '{conf.updater}'")
    if policy is not None and policy.master_weights and any(
            jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating)
            and jnp.asarray(p).dtype.itemsize < 4
            for p in jax.tree_util.tree_leaves(params)):
        state[MASTER_KEY] = jax.tree.map(_master_of, params)
    return state


def _master_of(p: Array) -> Array:
    """The fp32 master of a parameter: exactly the value the parameter
    holds.  Inside a jitted ``init`` the parameter is ``round(x)`` of an
    fp32 ``x`` in the same program, and XLA, allowed excess precision,
    folds ``float32(bfloat16(x))`` to ``x`` (seen on the v5e, PR 28):
    ``reduce_precision`` says the rounding is meant.  On a value that is
    already rounded it changes nothing."""
    p = jnp.asarray(p)
    if not (jnp.issubdtype(p.dtype, jnp.floating) and p.dtype.itemsize < 4):
        return jnp.asarray(p, jnp.float32)
    info = jnp.finfo(p.dtype)
    return jax.lax.reduce_precision(p.astype(jnp.float32),
                                    exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def compute_update(conf: UpdaterConfig, grads: ParamTree, state: ParamTree,
                   iteration: Array,
                   params: Optional[ParamTree] = None
                   ) -> tuple[ParamTree, ParamTree]:
    """Turn raw (regularized, normalized) grads into the step to subtract.

    Returns ``(updates, new_state)``; caller does ``params -= updates``
    (reference ``NegativeGradientStepFunction`` semantics).  ``params``
    is only consulted by updaters whose step depends on the weights
    themselves (lars); tree-structure must then match ``grads``.
    """
    name = conf.updater.lower()
    lr = learning_rate_for(conf, iteration)

    if name in ("none", "noop"):
        return grads, state
    if name == "sgd":
        return jax.tree.map(lambda g: lr * g, grads), state
    if name == "nesterovs":
        mu = momentum_for(conf, iteration)
        v_prev = state["v"]
        v_new = jax.tree.map(lambda v, g: mu * v - lr * g, v_prev, grads)
        # reference Nesterovs.getGradient: step = mu*vPrev - (1+mu)*vNew,
        # subtracted from params by the step function
        updates = jax.tree.map(
            lambda vp, vn: mu * vp - (1.0 + mu) * vn, v_prev, v_new)
        return updates, {"v": v_new}
    if name == "adagrad":
        h_new = jax.tree.map(lambda h, g: h + jnp.square(g),
                             state["h"], grads)
        updates = jax.tree.map(
            lambda g, h: lr * g / (jnp.sqrt(h) + _EPS_ADAGRAD), grads, h_new)
        return updates, {"h": h_new}
    if name == "rmsprop":
        d = conf.rms_decay
        cache = jax.tree.map(
            lambda c, g: d * c + (1.0 - d) * jnp.square(g),
            state["cache"], grads)
        updates = jax.tree.map(
            lambda g, c: lr * g / (jnp.sqrt(c) + _EPS_RMSPROP), grads, cache)
        return updates, {"cache": cache}
    if name == "adam":
        b1, b2 = conf.adam_mean_decay, conf.adam_var_decay
        t = jnp.asarray(iteration, jnp.float32) + 1.0
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g,
                         state["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g),
                         state["v"], grads)
        # bias-corrected step (reference Adam.getGradient)
        alpha = lr * jnp.sqrt(1 - jnp.power(b2, t)) / (1 - jnp.power(b1, t))
        updates = jax.tree.map(
            lambda m_, v_: alpha * m_ / (jnp.sqrt(v_) + _EPS_ADAM), m, v)
        return updates, {"m": m, "v": v}
    if name == "adadelta":
        rho, eps = conf.rho, conf.epsilon or _EPS_ADADELTA
        msg = jax.tree.map(
            lambda a, g: rho * a + (1 - rho) * jnp.square(g),
            state["msg"], grads)
        updates = jax.tree.map(
            lambda g, a, d: g * jnp.sqrt(d + eps) / jnp.sqrt(a + eps),
            grads, msg, state["msdx"])
        msdx = jax.tree.map(
            lambda d, u: rho * d + (1 - rho) * jnp.square(u),
            state["msdx"], updates)
        return updates, {"msg": msg, "msdx": msdx}
    if name == "lars":
        # Layer-wise Adaptive Rate Scaling (You et al. 2017), the
        # large-batch recipe of the MLPerf TPU-pod scaling literature:
        # per-tensor trust ratio eta*||w|| / (||g|| + wd*||w||) scales the
        # momentum step so every layer moves proportionally to its
        # weight scale.
        if params is None:
            raise ValueError("lars needs the params tree (trust ratios "
                             "are weight-norm relative)")
        eta = conf.lars_trust_coefficient
        wd = conf.lars_weight_decay
        mu = momentum_for(conf, iteration)

        def one(w, g, v):
            w_norm = jnp.linalg.norm(w.ravel())
            g_norm = jnp.linalg.norm(g.ravel())
            trust = jnp.where(
                (w_norm > 0) & (g_norm > 0),
                eta * w_norm / (g_norm + wd * w_norm + 1e-12), 1.0)
            v_new = mu * v + lr * trust * (g + wd * w)
            return v_new

        v_new = jax.tree.map(one, params, grads, state["v"])
        return v_new, {"v": v_new}
    raise ValueError(f"Unknown updater '{conf.updater}'")


def updatable_params(layer, params: ParamTree) -> ParamTree:
    """Subset of a layer's params that go through the updater (excludes
    ``direct_update_params`` — those have no updater state, mirroring the
    reference's per-param ``Updater.NONE`` which is stateless)."""
    direct = set(layer.direct_update_params())
    if not direct:
        return params
    return {k: v for k, v in params.items() if k not in direct}


def apply_layer_updates(uconf: UpdaterConfig, layer, params: ParamTree,
                        state: ParamTree, grads: ParamTree,
                        iteration: Array) -> tuple[ParamTree, ParamTree]:
    """Full DL4J-order update for one layer's param tree: l1/l2 into grads,
    gradient normalization, per-param updater rule — with any
    ``layer.direct_update_params()`` routed around all of it and applied
    verbatim (``p -= g``; reference per-param ``Updater.NONE`` + lr 1.0,
    e.g. center-loss cL).

    When the updater state carries fp32 masters (mixed-precision policy,
    see :func:`init_state`), ALL updater math runs against the masters in
    fp32 and the storage-dtype params are re-derived by one cast at the
    end — the "cast-on-apply" step.  The bf16 params the forward pass
    reads are therefore always exactly ``master.astype(bf16)``.
    """
    if getattr(layer, "frozen", False):
        # feature-extractor layer: parameters (and updater state) fixed
        return dict(params), state
    masters = state.get(MASTER_KEY) if isinstance(state, dict) else None
    g = dict(grads)
    g_direct = {k: g.pop(k) for k in layer.direct_update_params() if k in g}
    if masters is not None:
        work = {k: masters[k] for k in g}
        g = {k: jnp.asarray(v, jnp.float32) for k, v in g.items()}
        mstate = {k: v for k, v in state.items() if k != MASTER_KEY}
    else:
        work = {k: params[k] for k in g}
        mstate = state
    g = regularize(g, work, layer.l1_by_param(), layer.l2_by_param())
    g = normalize_gradients(g, layer.gradient_normalization,
                            layer.gradient_normalization_threshold)
    updates, new_state = compute_update(
        uconf, g, mstate, iteration, params=work)
    new_params = dict(params)
    if masters is not None:
        new_masters = dict(masters)
        for k, u in updates.items():
            new_masters[k] = work[k] - u
            new_params[k] = new_masters[k].astype(params[k].dtype)
        new_state = dict(new_state)
        new_state[MASTER_KEY] = new_masters
    else:
        for k, u in updates.items():
            new_params[k] = params[k] - u
    for k, gd in g_direct.items():
        p = params[k]
        if (jnp.issubdtype(p.dtype, jnp.floating) and p.dtype.itemsize < 4):
            # sub-fp32 storage: accumulate the direct step in fp32 too
            new_params[k] = (p.astype(jnp.float32)
                             - jnp.asarray(gd, jnp.float32)).astype(p.dtype)
        else:
            new_params[k] = p - gd
    return new_params, new_state


def regularize(grads: ParamTree, params: ParamTree,
               l1_by_param: Dict[str, float],
               l2_by_param: Dict[str, float]) -> ParamTree:
    """Add l1/l2 penalties to raw grads, per param name.

    Reference ``LayerUpdater.postApply``: ``gradient += l2 * param`` and
    ``gradient += l1 * sign(param)`` — applied to weights but not biases
    unless bias regularization is configured (``getL1ByParam``).
    """
    out = {}
    for k, g in grads.items():
        l1 = l1_by_param.get(k, 0.0)
        l2 = l2_by_param.get(k, 0.0)
        if l2:
            g = g + l2 * params[k]
        if l1:
            g = g + l1 * jnp.sign(params[k])
        out[k] = g
    return out


def regularization_score(params: ParamTree, l1_by_param: Dict[str, float],
                         l2_by_param: Dict[str, float]) -> Array:
    """l1/l2 penalty term added to the loss score (reference
    ``BaseLayer.calcL2``/``calcL1``: 0.5*l2*||w||^2 + l1*||w||_1)."""
    total = jnp.asarray(0.0, jnp.float32)
    for k, p in params.items():
        l1 = l1_by_param.get(k, 0.0)
        l2 = l2_by_param.get(k, 0.0)
        if l2:
            total = total + 0.5 * l2 * jnp.sum(jnp.square(p))
        if l1:
            total = total + l1 * jnp.sum(jnp.abs(p))
    return total
