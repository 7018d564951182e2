"""Precision policy: storage/compute/updater dtypes + fp32 master weights.

One object answers every "which dtype?" question on the training hot
path (docs/PERFORMANCE.md).  A :class:`PrecisionPolicy` is resolved once
per network at ``init()`` from three sources, in precedence order:

1. ``DL4J_TPU_PRECISION`` env — the global switch.  Values:
   ``fp32``/``float32`` (everything fp32), ``bf16``/``bfloat16``
   (pure bf16: params, activations AND updater state — no masters),
   ``mixed_bf16``/``mixed`` (bf16 params + bf16 activations + fp32
   master copies carried in the updater state, cast-on-apply).
2. Explicit ``NeuralNetConfiguration`` fields: a non-default
   ``dtype`` and/or a ``compute_dtype``.  These reproduce the exact
   pre-policy semantics (e.g. fp32 params with bf16 matmuls when only
   ``compute_dtype="bfloat16"`` is set — no master copies, because the
   params already are the fp32 masters).
3. Backend default: **mixed_bf16 on TPU, fp32 everywhere else**.  CPU
   tier-1 numerics are therefore untouched by this module.

Master-weight contract: when ``master_weights`` is on, the updater
state for each layer carries an extra ``"_master"`` tree mirroring the
updatable params in fp32.  All updater math (l1/l2, gradient
normalization, momentum/Adam/etc.) runs against the fp32 masters; the
bf16 params the forward pass reads are re-derived each step via a
single cast (``param = master.astype(bf16)``).  Because the masters
live inside the (donated) updater-state carry they stay device-resident
across the fused ``lax.scan`` epoch, ship through ZeRO sharding
untouched, and serialize with the updater state — checkpoints always
store fp32 masters, so kill-and-resume stays bit-identical (bf16→fp32
round-trips losslessly).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp

_ENV = "DL4J_TPU_PRECISION"

# canonical mode names
FP32 = "fp32"
BF16 = "bf16"
MIXED_BF16 = "mixed_bf16"

_MODE_ALIASES = {
    "fp32": FP32, "float32": FP32, "f32": FP32,
    "bf16": BF16, "bfloat16": BF16, "pure_bf16": BF16,
    "mixed_bf16": MIXED_BF16, "mixed": MIXED_BF16,
    "bf16_fp32_master": MIXED_BF16,
}


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Resolved dtype decisions for one network instance."""

    param_dtype: jnp.dtype        # storage dtype of layer params / net state
    compute_dtype: jnp.dtype      # activations + matmul dtype
    updater_dtype: jnp.dtype      # momentum/Adam-moment storage dtype
    master_weights: bool          # carry fp32 masters in the updater state
    name: str                     # fp32 | bf16 | mixed_bf16 | custom

    @property
    def compute_name(self) -> Optional[str]:
        """String form for wire-transfer casts (``cast_for_transfer``)."""
        return "bfloat16" if self.compute_dtype == jnp.bfloat16 else None

    @property
    def downcasts_output(self) -> bool:
        """True when activations are below fp32 and outputs need an fp32
        cast before loss/softmax/metrics accumulation."""
        return (jnp.issubdtype(self.compute_dtype, jnp.floating)
                and jnp.dtype(self.compute_dtype).itemsize < 4)

    def describe(self) -> str:
        return "%s(param=%s,compute=%s,updater=%s,masters=%d)" % (
            self.name, jnp.dtype(self.param_dtype).name,
            jnp.dtype(self.compute_dtype).name,
            jnp.dtype(self.updater_dtype).name, int(self.master_weights))


_FP32_POLICY = PrecisionPolicy(jnp.dtype(jnp.float32), jnp.dtype(jnp.float32),
                               jnp.dtype(jnp.float32), False, FP32)
_BF16_POLICY = PrecisionPolicy(jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.bfloat16),
                               jnp.dtype(jnp.bfloat16), False, BF16)
_MIXED_POLICY = PrecisionPolicy(jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32), True, MIXED_BF16)
_NAMED = {FP32: _FP32_POLICY, BF16: _BF16_POLICY, MIXED_BF16: _MIXED_POLICY}


def on_tpu() -> bool:
    # a backend that fails to initialise raises here: answering False
    # would turn a broken chip into a quiet fp32 run on the CPU
    return any(d.platform == "tpu" for d in jax.devices())


def env_mode() -> Optional[str]:
    """Canonical mode requested via DL4J_TPU_PRECISION, or None."""
    raw = os.environ.get(_ENV, "").strip().lower()
    if not raw:
        return None
    mode = _MODE_ALIASES.get(raw)
    if mode is None:
        raise ValueError(
            "%s=%r not understood; expected one of %s"
            % (_ENV, raw, sorted(set(_MODE_ALIASES))))
    return mode


def named_policy(mode: str) -> PrecisionPolicy:
    return _NAMED[_MODE_ALIASES[mode]]


def default_compute_dtype() -> Optional[str]:
    """The compute dtype a freshly-built net would default to on this
    backend ("bfloat16" on TPU / under a bf16 env mode, else None).

    This is the shared replacement for the `_bf16_if_tpu()` helper that
    bench.py / examples / tools each used to carry privately.
    """
    mode = env_mode()
    if mode is None:
        mode = MIXED_BF16 if on_tpu() else FP32
    return "bfloat16" if mode in (BF16, MIXED_BF16) else None


def resolve_policy(gconf) -> PrecisionPolicy:
    """Resolve the policy for one network from its GlobalConfig.

    ``gconf`` needs ``.dtype`` (str, default "float32") and
    ``.compute_dtype`` (str or None) attributes.
    """
    conf_dtype = getattr(gconf, "dtype", "float32") or "float32"
    conf_compute = getattr(gconf, "compute_dtype", None)
    explicit = conf_dtype != "float32" or conf_compute is not None

    mode = env_mode()
    if mode is not None:
        # the env is the global switch; it wins over conf fields so a
        # single knob can flip an unmodified model zoo / bench / CI run.
        return _NAMED[mode]

    if explicit:
        param = jnp.dtype(conf_dtype)
        compute = jnp.dtype(conf_compute) if conf_compute else param
        low_param = (jnp.issubdtype(param, jnp.floating)
                     and param.itemsize < 4)
        # pre-policy semantics: fp32 params + bf16 compute has no master
        # copies (params ARE the masters).  Explicitly-requested low-
        # precision *storage* gets fp32 masters — the safe default.
        return PrecisionPolicy(
            param_dtype=param, compute_dtype=compute,
            updater_dtype=jnp.dtype(jnp.float32) if low_param else param,
            master_weights=low_param, name="custom")

    return _MIXED_POLICY if on_tpu() else _FP32_POLICY


def publish(policy: PrecisionPolicy) -> None:
    """Expose the resolved policy on the metrics registry (best-effort)."""
    try:
        from .. import monitor
        monitor.gauge("precision_param_bits").set(
            jnp.dtype(policy.param_dtype).itemsize * 8)
        monitor.gauge("precision_compute_bits").set(
            jnp.dtype(policy.compute_dtype).itemsize * 8)
        monitor.gauge("precision_master_weights").set(
            int(policy.master_weights))
    except Exception:
        pass
