"""Plain float32 reference for the two image classifiers.

Forward pass, loss, gradient and Nesterov train steps of ResNet-50 (He et al.,
arXiv:1512.03385, Table 1) and VGG-16 (Simonyan and Zisserman,
arXiv:1409.1556, configuration D) in straightforward ``jax.numpy`` and
``lax.conv_general_dilated``, float32 throughout, under
``jax.default_matmul_precision("highest")`` (on a TPU a float32
convolution otherwise runs as one bf16 pass).  No kernels, no policy, no
fusion tricks.  The architecture comes from the sizes in the
configuration's file; the weights are the container's own parameter
tree (upcast to float32), read by the names ``models/resnet.py`` and
the layer order ``keras/trained_models.py:vgg16`` give them.

Departures from the papers, each one the program's own and noted in the
configuration's ``assumed``: XLA's SAME padding; l2 on every parameter
not named ``b`` (so batch-norm scale and shift decay too); no dropout
and no weight decay in VGG-16.

Batch norm uses the batch's statistics when ``train`` (biased
variance), the running ones otherwise.  The loss is the mean over the
batch of the multi-class cross-entropy; the score adds
``0.5 * l2 * sum(w^2)``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

_DN = ("NHWC", "HWIO", "NHWC")


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _conv(x, w, stride):
    return lax.conv_general_dilated(x, w, (stride, stride), "SAME",
                                    dimension_numbers=_DN,
                                    precision=lax.Precision.HIGHEST)


def _max_pool(x, window, stride, padding):
    return lax.reduce_window(x, -jnp.inf, lax.max,
                             (1, window, window, 1),
                             (1, stride, stride, 1), padding)


def _batch_norm(x, p, s, train, eps):
    if train:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    else:
        mean, var = s["mean"], s["var"]
    return (x - mean) * lax.rsqrt(var + eps) * p["gamma"] + p["beta"]


def _resnet_logits(cfg, params, state, x, train):
    eps = cfg["bn_eps"]

    def conv_bn(name, x, stride, relu=True):
        y = _conv(x, params[f"{name}_conv"]["W"], stride)
        y = _batch_norm(y, params[f"{name}_bn"], state[f"{name}_bn"],
                        train, eps)
        return jax.nn.relu(y) if relu else y

    x = conv_bn("stem", x, 2)
    x = _max_pool(x, 3, 2, "SAME")
    for s, blocks in enumerate(cfg["stage_blocks"]):
        for b in range(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            name = f"s{s}b{b}"
            y = conv_bn(f"{name}_a", x, stride)
            y = conv_bn(f"{name}_b", y, 1)
            y = conv_bn(f"{name}_c", y, 1, relu=False)
            if b == 0:
                x = conv_bn(f"{name}_sc", x, stride, relu=False)
            x = jax.nn.relu(x + y)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, params["fc"]["W"],
                   precision=lax.Precision.HIGHEST) + params["fc"]["b"]


def _vgg_logits(cfg, params, state, x, train):
    layers = [p for p in params if p]          # pools hold no parameters
    i = 0
    for widths in cfg["block_widths"]:
        for _ in widths:
            x = jax.nn.relu(_conv(x, layers[i]["W"], 1) + layers[i]["b"])
            i += 1
        x = _max_pool(x, 2, 2, "VALID")
    x = x.reshape(x.shape[0], -1)              # NHWC order, as the program
    n_dense = len(cfg["dense_widths"]) + 1
    for j in range(n_dense):
        x = jnp.dot(x, layers[i]["W"],
                    precision=lax.Precision.HIGHEST) + layers[i]["b"]
        if j < n_dense - 1:
            x = jax.nn.relu(x)
        i += 1
    return x


_LOGITS = {"resnet_v1_bottleneck": _resnet_logits, "vgg": _vgg_logits}


def logits(cfg: Dict, params, state, x, train: bool):
    with jax.default_matmul_precision("highest"):
        return _LOGITS[cfg["family"]](cfg, _f32(params), _f32(state),
                                      jnp.asarray(x, jnp.float32), train)


def probabilities(cfg: Dict, params, state, x):
    """What ``output()`` should return: inference-mode class
    probabilities."""
    return jax.nn.softmax(logits(cfg, params, state, x, False), axis=-1)


def _penalised(tree):
    """Leaves the program's l2 applies to: every one not named ``b``."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [leaf for path, leaf in flat
            if getattr(path[-1], "key", None) != "b"]


def data_loss(cfg: Dict, params, state, x, y):
    """Mean multi-class cross-entropy of one batch in training mode
    (batch norm on the batch's own statistics)."""
    logp = jax.nn.log_softmax(logits(cfg, params, state, x, True), axis=-1)
    return -jnp.mean(jnp.sum(jnp.asarray(y, jnp.float32) * logp, axis=-1))


def penalty(cfg: Dict, params):
    l2 = cfg.get("l2", 0.0)
    if not l2:
        return jnp.float32(0.0)
    return 0.5 * l2 * sum(jnp.sum(jnp.square(w))
                          for w in _penalised(_f32(params)))


def score(cfg: Dict, params, state, x, y):
    """The score a container's train step reports for one batch: the
    training-mode data loss plus the l2 penalty."""
    return data_loss(cfg, params, state, x, y) + penalty(cfg, params)


def score_and_grad(cfg: Dict, params, state, x, y):
    """``(score, gradient)`` as a container's train step defines them:
    the gradient is the data loss's alone (the program applies l2 in
    the updater), in float32 and in the shape of ``params``."""
    value, grad = jax.value_and_grad(
        lambda p: data_loss(cfg, p, state, x, y))(_f32(params))
    return value + penalty(cfg, params), grad


def nesterov_scores(cfg: Dict, params, state, x, y, steps: int):
    """The scores a container's ``fit`` reports over ``steps`` train
    steps on the one batch ``(x, y)``: step ``i`` reports the score at
    the parameters it starts from, then moves them.  The update is the
    configuration's (``learning_rate``, ``momentum``, ``l2``) in the
    form the program's Nesterov updater has, after ND4J's:
    ``g = grad + l2 * w`` (no decay on ``b``), ``v' = mu * v - lr * g``,
    ``w' = w - mu * v + (1 + mu) * v'``."""
    lr, mu, l2 = cfg["learning_rate"], cfg["momentum"], cfg.get("l2", 0.0)
    params = _f32(params)
    decay = jax.tree_util.tree_map_with_path(
        lambda path, w: 0.0 if getattr(path[-1], "key", None) == "b" else l2,
        params)
    velocity = jax.tree.map(jnp.zeros_like, params)
    scores = []
    for _ in range(steps):
        value, grad = score_and_grad(cfg, params, state, x, y)
        scores.append(value)
        new = jax.tree.map(lambda v, g, d, w: mu * v - lr * (g + d * w),
                           velocity, grad, decay, params)
        params = jax.tree.map(lambda w, v, n: w - mu * v + (1.0 + mu) * n,
                              params, velocity, new)
        velocity = new
    return jnp.stack(scores)
