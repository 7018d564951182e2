"""Weight initialization schemes and distributions.

TPU-native equivalent of the reference's ``nn/weights/WeightInit.java`` /
``WeightInitUtil.java`` and ``nn/conf/distribution/``.  Each scheme is a pure
function of a JAX PRNG key, so replica initialization under SPMD is
deterministic given the seed (the analogue of DL4J's shared ``Nd4j.getRandom``
seed when ``ParallelWrapper`` clones a model per device).

``init()`` of a container runs every layer's scheme inside jitted
programs whose values must equal the leaf-by-leaf ones bit for bit.  Run
one operation at a time, a draw and the arithmetic after it round
separately; inside one program XLA fuses them (``std * (sqrt(2) *
erfinv(u))`` reassociates, a division by a constant becomes a product
with its reciprocal), and it drops ``optimization_barrier`` before it
fuses.  So init is :func:`staged` into TWO programs with a real buffer
between them: the first makes what a scheme holds (:func:`_held`: the
normal draws and the schemes' scalars), the second takes those as
arguments and does the rest.  Run un-staged, ``_held`` changes nothing.

Shapes follow the JAX convention ``(fan_in, fan_out)`` for dense kernels and
``(H, W, C_in, C_out)`` (HWIO) for conv kernels; fan computation mirrors
``WeightInitUtil.initWeights``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import monitor as _monitor
from .conf import serde as _serde

Array = jax.Array


@_serde.register("distribution", custom=True)
@dataclasses.dataclass
class Distribution:
    """Config-serializable sampling distribution (``nn/conf/distribution/``).

    kind: "normal" (mean/std), "uniform" (lower/upper), "binomial"
    (n_trials/prob_success).
    """

    kind: str = "normal"
    mean: float = 0.0
    std: float = 1.0
    lower: float = -1.0
    upper: float = 1.0
    n_trials: int = 1
    prob_success: float = 0.5

    def sample(self, rng: jax.Array, shape: Sequence[int],
               dtype=jnp.float32) -> Array:
        if _is_sub_fp32(dtype):
            # Sample in fp32 and round once: identical draws whatever the
            # storage dtype (the mixed-precision policy's bf16 params start
            # exactly at round(fp32 init), matching the fp32 masters).
            return self.sample(rng, shape, jnp.float32).astype(dtype)
        if self.kind == "normal" or self.kind == "gaussian":
            if self.mean != 0.0 and getattr(_stage, "mode", None):
                # product and sum fuse into one rounding in a program
                raise NotStaged("normal distribution with a mean")
            return self.mean + self.std * _held(
                lambda: _normal(rng, shape, dtype))
        if self.kind == "uniform":
            return jax.random.uniform(rng, shape, dtype, self.lower, self.upper)
        if self.kind == "binomial":
            if getattr(_stage, "mode", None):
                # its logarithms of a constant probability are folded by
                # the compiler's evaluator, not the device's (v5e, PR 28)
                raise NotStaged("binomial distribution")
            return jax.random.binomial(
                rng, self.n_trials, self.prob_success, shape).astype(dtype)
        raise ValueError(f"Unknown distribution kind '{self.kind}'")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "Distribution":
        return Distribution(**d)


def _normal(rng: jax.Array, shape: Sequence[int], dtype) -> Array:
    """Standard normal draws of ``shape``; for a stack of keys ``(n,
    2)``, ``(n, *shape)``: one draw a key, as one operation (the routed
    experts of a layer, each from a key of its own)."""
    if rng.ndim == 2:
        return jax.vmap(lambda k: jax.random.normal(k, shape, dtype))(rng)
    return jax.random.normal(rng, shape, dtype)


class NotStaged(Exception):
    """This scheme's values would differ inside a staged program: the
    net initialises leaf by leaf."""


_stage = threading.local()


@contextlib.contextmanager
def _staging(mode: str, items: list):
    _stage.mode, _stage.items, _stage.taken = mode, items, 0
    try:
        yield
    finally:
        _stage.mode = None


def _held(make: Callable[[], Array]) -> Array:
    """A value that is finished before anything is computed from it.
    Un-staged: ``make()``.  While the first program of :func:`staged`
    is traced, ``make()`` is also one of its outputs; while the second
    is, the value is its next argument and ``make`` is not called."""
    mode = getattr(_stage, "mode", None)
    if mode is None:
        return make()
    if mode == "hold":
        _stage.items.append(make())
        return _stage.items[-1]
    _stage.taken += 1
    return _stage.items[_stage.taken - 1]


def _scalar(fn, *args) -> Array:
    """A scheme's scalar (``sqrt(2 / fan_in)``), held, and computed now
    even while tracing: by the device's own operation, as leaf by leaf."""
    def make():
        with jax.ensure_compile_time_eval():
            return fn(*args)
    return _held(make)


def staged(init: Callable):
    """``init(key) -> tree`` as two functions to jit: ``hold(key)``
    gives the list of what the schemes hold, and ``finish(key, held)``
    the tree, taking each held value from ``held`` in the same order
    (the same Python runs both times)."""
    def hold(key):
        items: list = []
        with _staging("hold", items):
            init(key)
        return items

    def finish(key, held):
        with _staging("take", list(held)):
            return init(key)

    return hold, finish


def init_programs(init: Callable, name: str,
                  identity: Callable[[str], Optional[str]]):
    """A container's ``init(key) -> (params, state, updater state)`` as
    its two staged programs, watched under ``<name>_held`` and
    ``<name>`` and served by the executable store where one is
    installed (``identity(part)`` says what they close over).  The
    returned ``run(key)`` falls back to ``init`` itself, leaf by leaf
    (``run.__wrapped__``), where a scheme cannot be staged or needs
    concrete values."""
    hold, finish = staged(init)
    hold_program = _monitor.watched_jit(
        hold, name=f"{name}_held", identity=lambda: identity("init_held"))
    finish_program = _monitor.watched_jit(
        finish, name=name, identity=lambda: identity("init"))

    def run(key):
        try:
            return finish_program(key, hold_program(key))
        except (jax.errors.JAXTypeError, NotStaged):
            return init(key)

    run.__wrapped__ = init
    return run


def _is_sub_fp32(dtype) -> bool:
    d = jnp.dtype(dtype)
    return jnp.issubdtype(d, jnp.floating) and d.itemsize < 4


def _fans(shape: Sequence[int]) -> tuple[float, float]:
    """(fan_in, fan_out) for dense (I,O) or conv HWIO kernels.

    Mirrors ``WeightInitUtil`` fan computation: for conv, receptive-field size
    multiplies channel fans.
    """
    if len(shape) == 2:
        return float(shape[0]), float(shape[1])
    if len(shape) >= 3:
        receptive = 1.0
        for s in shape[:-2]:
            receptive *= s
        return receptive * shape[-2], receptive * shape[-1]
    return float(shape[0]), float(shape[0])


def init_weights(rng: jax.Array, shape: Sequence[int], scheme: str = "xavier",
                 distribution: Optional[Distribution] = None,
                 dtype=jnp.float32) -> Array:
    """Initialize a weight tensor per a DL4J ``WeightInit`` scheme name.

    Supported (case-insensitive): zero, ones, xavier, xavier_uniform,
    xavier_fan_in, xavier_legacy, relu, relu_uniform, sigmoid_uniform,
    uniform, lecun_normal, lecun_uniform, normal, distribution, identity,
    var_scaling_* aliases.
    """
    if _is_sub_fp32(dtype):
        # Sample in fp32, round once to the storage dtype — bf16 params are
        # then exactly round(fp32 init), bit-matching the fp32 master copies
        # the mixed-precision updater carries (nn/precision.py).
        return init_weights(rng, shape, scheme, distribution,
                            jnp.float32).astype(dtype)
    scheme = scheme.lower()
    fan_in, fan_out = _fans(shape)
    shape = tuple(shape)

    if scheme == "zero":
        return jnp.zeros(shape, dtype)
    if scheme == "ones":
        return jnp.ones(shape, dtype)
    if scheme == "identity":
        if len(shape) != 2 or shape[0] != shape[1]:
            raise ValueError("identity init requires a square 2-D shape")
        return jnp.eye(shape[0], dtype=dtype)
    if scheme == "distribution":
        if distribution is None:
            raise ValueError("WeightInit 'distribution' requires a Distribution")
        return distribution.sample(rng, shape, dtype)
    def normal():
        return _held(lambda: _normal(rng, shape, dtype))

    if scheme == "xavier":
        # Gaussian with var = 2/(fanIn+fanOut) (WeightInitUtil XAVIER)
        std = _scalar(jnp.sqrt, 2.0 / (fan_in + fan_out))
        return std * normal()
    if scheme == "xavier_uniform":
        a = _scalar(jnp.sqrt, 6.0 / (fan_in + fan_out))
        return jax.random.uniform(rng, shape, dtype, -a, a)
    if scheme == "xavier_fan_in":
        return normal() / _scalar(jnp.sqrt, fan_in)
    if scheme == "xavier_legacy":
        return normal() * _scalar(jnp.sqrt, 1.0 / (fan_in + fan_out))
    if scheme in ("relu", "he_normal"):
        return normal() * _scalar(jnp.sqrt, 2.0 / fan_in)
    if scheme in ("relu_uniform", "he_uniform"):
        a = _scalar(jnp.sqrt, 6.0 / fan_in)
        return jax.random.uniform(rng, shape, dtype, -a, a)
    if scheme == "sigmoid_uniform":
        a = _scalar(lambda v: 4.0 * jnp.sqrt(v), 6.0 / (fan_in + fan_out))
        return jax.random.uniform(rng, shape, dtype, -a, a)
    if scheme == "uniform":
        # DL4J legacy UNIFORM: U(-a, a) with a = 1/sqrt(fanIn)
        a = _scalar(lambda v: 1.0 / jnp.sqrt(v), fan_in)
        return jax.random.uniform(rng, shape, dtype, -a, a)
    if scheme == "lecun_normal":
        return normal() / _scalar(jnp.sqrt, fan_in)
    if scheme == "lecun_uniform":
        a = _scalar(jnp.sqrt, 3.0 / fan_in)
        return jax.random.uniform(rng, shape, dtype, -a, a)
    if scheme == "normal":
        return jax.random.normal(rng, shape, dtype)
    raise ValueError(f"Unknown WeightInit scheme '{scheme}'")
