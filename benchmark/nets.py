"""What every driver needs from a configuration: the net built from its
file and the seed, seeded data in float32, and the comparison with the
plain reference that decides ``correct``.

The comparison runs outside the timed window on ``CHECK_EXAMPLES``
seeded examples at the configuration's full width, as one jitted
program (container and reference side by side, so one compile and one
cache entry), and measures two things:

``output``  ``net.output(x)`` (or the rows an engine served) against the
            reference's inference-mode probabilities;
``score``   in the ``fit`` cells, the training-mode score of the batch
            (data loss on the batch's own batch-norm statistics plus
            the l2 penalty), the number every train step reports.

The containers have no public call that returns a training-mode score
or a gradient, so the score is taken the way
``deeplearning4j_tpu/gradientcheck.py`` takes it: the container's own
``_loss_fn`` (the function every step builder differentiates) and
``_reg_score``.  Nothing in the program is changed for it.  The
gradient is NOT compared: at 8 examples it is too ill-conditioned to
judge anything (two float32 implementations each differ from a float64
one by 4-7% in some tensors; in bf16 the relative error measured on the
v5e is 1.3-1.7, PERF.md Findings PR 22), and a check that cannot fail
is not a check.  What holds the backward pass and the updater instead:
``units.scores_move`` in every run, and in ``tests/benchmark/`` the
reference's Nesterov steps on the CPU (PERF.md section 7).

Errors are relative L2 norms, ``|got - ref| / |ref|``, which one
outlying element cannot swing.  Bounds (``BOUNDS``) are per compute
dtype and are stated with their reason:

- float32: 2e-4.  Both sides compute in float32; they differ in the
  order of accumulations of up to ~25k terms (sqrt(K) * eps32 ~ 2e-5
  a layer) through up to 53 layers.
- bfloat16 (``mixed_bf16``): every activation is rounded to 8
  significant bits (relative 2^-9 a rounding, random in sign) some
  hundred times between input and loss.  Measured on the v5e over the
  seeds of PR 22's runs (PERF.md): ``output`` up to 0.022, ``score`` up
  to 7.7e-3; the bounds are about four times the largest measured.  An
  8-bit float type with 3 mantissa bits rounds 16 times more coarsely
  and would exceed them fourfold; dropping the float32 accumulation
  inside the convolutions or the float32 batch-norm statistics does
  too.
"""

from __future__ import annotations

import importlib
from typing import Dict, Tuple

import numpy as np

CHECK_EXAMPLES = 8

#: relative L2 error allowed, by the policy's compute dtype
BOUNDS = {
    "float32": {"output": 2e-4, "score": 2e-4},
    "bfloat16": {"output": 0.08, "score": 0.03},
}


def _resolve(spec: str):
    module, _, attr = spec.partition(":")
    return getattr(importlib.import_module(module), attr)


def build_net(cfg: Dict, seed: int):
    """The configuration's container, its conf built by the builder the
    file names, seeded from ``seed`` and trained at the file's
    ``learning_rate`` (the one the reference reads), both set on the
    built conf so that a builder that pins them is not touched (its
    layers share the conf's one updater), initialised on the device by
    the conf's own seeded init."""
    conf = _resolve(cfg["builder"])(**cfg.get("builder_args", {}))
    conf.conf.seed = int(seed)
    conf.conf.updater.learning_rate = float(cfg["learning_rate"])
    return _resolve(cfg["container"])(conf).init()


def images(cfg: Dict, n: int, seed: int, stream: int = 0
           ) -> Tuple[np.ndarray, np.ndarray]:
    """``n`` seeded float32 examples in [0, 1) with one-hot labels,
    drawn in float32 (never float64 and cast)."""
    rng = np.random.default_rng([int(seed), int(stream)])
    shape = (n, cfg["image_size"], cfg["image_size"], cfg["num_channels"])
    x = rng.random(shape, dtype=np.float32)
    y = np.zeros((n, cfg["num_classes"]), np.float32)
    y[np.arange(n), rng.integers(0, cfg["num_classes"], n)] = 1.0
    return x, y


def check_examples(cfg: Dict, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """The examples the comparison runs on, scaled by the file's
    ``check_input_scale``: at initialisation the nets are positively
    homogeneous in their input, and at the traffic's scale ResNet-50's
    untrained logits are in the hundreds, where a softmax is one-hot
    and says nothing of the arithmetic before it."""
    x, y = images(cfg, CHECK_EXAMPLES, seed, stream=0xC4EC)
    return x * np.float32(cfg.get("check_input_scale", 1.0)), y


def compute_dtype(net) -> str:
    return np.dtype(net._pol().compute_dtype).name


def _rel(got, ref):
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return jnp.linalg.norm((got - ref).ravel()) / jnp.maximum(
        jnp.linalg.norm(ref.ravel()), 1e-30)


def compare(cfg: Dict, net, x, y, got_output, train: bool) -> Dict:
    """Relative errors of ``got_output`` (probabilities for ``x``) and,
    when ``train``, of the container's training-mode score for the
    batch ``(x, y)``, against ``cfg``'s reference on the net's own
    parameter tree.  One jitted program."""
    import jax
    import jax.numpy as jnp
    reference = importlib.import_module(
        f"benchmark.reference.{cfg['reference']}")
    graph = hasattr(net, "vertices")

    def program(params, state, rng, x, y, got_output):
        out = {"output": _rel(got_output, reference.probabilities(
            cfg, params, state, x))}
        if train:
            feats, labs = ((x,), (y,)) if graph else (x, y)
            value = net._loss_fn(params, state, feats, labs, None, None,
                                 rng, True)[0] + net._reg_score(params)
            out["ref_score"] = reference.score(cfg, params, state, x, y)
            out["score"] = _rel(value, out["ref_score"])
        return out

    errs = jax.jit(program)(net.params, net.net_state, net._rng_key,
                            jnp.asarray(x), jnp.asarray(y),
                            jnp.asarray(got_output))
    return {k: float(v) for k, v in errs.items()}


def verdict(errors: Dict, dtype: str) -> bool:
    """True when every measured error is finite and within its bound."""
    bounds = BOUNDS[dtype]
    return all(np.isfinite(errors[k]) and errors[k] <= bounds[k]
               for k in bounds if k in errors)
