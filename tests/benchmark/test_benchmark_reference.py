"""The plain reference against the containers at toy width on the CPU
(float32 on both sides): ``output()``, the training-mode score, the
gradient of one batch, and the scores of four Nesterov train steps."""

import json
import os

import numpy as np
import pytest

from benchtools import ROOT, TOY
from benchmark import nets


def _cfg(path, **changes):
    with open(path) as fh:
        cfg = json.load(fh)
    cfg.update(changes)
    return cfg


def _toy_vgg():
    return _cfg(os.path.join(TOY, "configs", "toy_vgg.json"))


def _toy_vgg_l2():
    """The toy net with weight decay, which VGG-16 as shipped has not."""
    return dict(_toy_vgg(), l2=0.05, builder_args={"l2": 0.05})


def _small_vgg16():
    """VGG-16 as shipped, every width as published, on 32x32 images."""
    return _cfg(os.path.join(ROOT, "benchmark", "configs", "vgg16.json"),
                image_size=32, num_classes=10,
                builder_args={"n_classes": 10, "height": 32, "width": 32})


def _small_resnet50():
    """ResNet-50 as shipped, every width as published, on 32x32 images."""
    return _cfg(os.path.join(ROOT, "benchmark", "configs", "resnet50.json"),
                image_size=32, num_classes=10, check_input_scale=1.0,
                builder_args={"n_classes": 10, "height": 32, "width": 32})


@pytest.mark.parametrize("make", [_toy_vgg, _small_vgg16,
                                  _small_resnet50])
def test_container_agrees_with_reference(make):
    cfg = make()
    net = nets.build_net(cfg, seed=5)
    assert nets.compute_dtype(net) == "float32"     # the CPU's policy
    x, y = nets.check_examples(cfg, seed=5)
    errors = nets.compare(cfg, net, x, y, net.output(x), train=True)
    assert nets.verdict(errors, "float32"), errors
    assert errors["output"] <= nets.BOUNDS["float32"]["output"]
    assert errors["score"] <= nets.BOUNDS["float32"]["score"]
    assert np.isfinite(errors["ref_score"])


def test_reference_gradient_is_the_containers_on_a_well_conditioned_net():
    """``score_and_grad`` stays in the reference for a later PR that can
    hold a gradient to it; on the shallow toy net (no batch norm) the
    two agree to float32 rounding."""
    import jax
    from benchmark.reference import convnet
    cfg = _toy_vgg()
    net = nets.build_net(cfg, seed=5)
    x, y = nets.check_examples(cfg, seed=5)
    _, ref = convnet.score_and_grad(cfg, net.params, net.net_state, x, y)
    got = jax.grad(lambda p: net._loss_fn(
        p, net.net_state, x, y, None, None, net._rng_key, True)[0])(
            net.params)
    errs = jax.tree.leaves(jax.tree.map(nets._rel, got, ref))
    assert max(float(e) for e in errs) < 2e-4


def _fit_scores(cfg, steps=4, seed=5, **wrong):
    """(scores ``fit`` reports over ``steps`` steps on the check batch,
    the reference's for the same steps with ``wrong`` put into its
    configuration)."""
    import jax
    from benchmark.reference import convnet
    from deeplearning4j_tpu.datasets.dataset import DataSet
    net = nets.build_net(cfg, seed=seed)
    x, y = nets.check_examples(cfg, seed=seed)
    want = jax.jit(lambda p, s: convnet.nesterov_scores(
        dict(cfg, **wrong), p, s, x, y, steps))(net.params, net.net_state)
    got = []
    for _ in range(steps):
        net.fit(DataSet(x, y))
        got.append(float(net.score()))
    return np.asarray(got), np.asarray(want)


def _toy_vgg_slower():
    """The file's learning rate, not the builder's 0.01, is what both
    sides train at (``nets.build_net`` sets it on the built conf)."""
    return dict(_toy_vgg(), learning_rate=0.003)


@pytest.mark.parametrize("make", [_toy_vgg, _toy_vgg_l2, _small_vgg16,
                                  _toy_vgg_slower])
def test_fit_follows_the_references_nesterov_steps(make):
    """The backward pass and the updater (learning rate, Nesterov
    momentum, l2 in the update) against the plain reference: four train
    steps on one batch report the reference's scores.  Measured
    agreement 3e-7 and less (float32 rounding); a momentum of 0.8 for
    0.9 is 1.1e-3 away, twice the learning rate 2e-2 (below).
    ResNet-50 is not held this way: from its untrained state two
    float32 implementations part by 0.06-3.4% after ONE step at a
    learning rate of 1e-3 (PERF.md section 7); it shares
    ``updaters.apply_layer_updates`` with the nets held here."""
    got, want = _fit_scores(make())
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max(), (got, want)
    assert np.ptp(got) > 0.05 * got.max()           # and training moved it


@pytest.mark.parametrize("wrong", [{"momentum": 0.8},
                                   {"learning_rate": 0.02}])
def test_a_wrong_update_is_caught(wrong):
    got, want = _fit_scores(_toy_vgg_l2(), **wrong)
    assert np.abs(got - want).max() > 1e-4 * np.abs(want).max()


def test_a_wrong_answer_is_caught():
    cfg = _toy_vgg()
    net = nets.build_net(cfg, seed=5)
    x, y = nets.check_examples(cfg, seed=5)
    good = np.asarray(net.output(x))
    # probabilities kept to 3 significant bits are far outside
    mantissa, exponent = np.frexp(good)
    rounded = np.ldexp(np.round(mantissa * 8) / 8, exponent)
    errors = nets.compare(cfg, net, x, y, rounded, train=False)
    assert not nets.verdict(errors, "float32")
    assert not nets.verdict({"output": float("nan")}, "float32")


def test_seed_sets_weights_and_data():
    cfg = _toy_vgg()
    a, b, c = (nets.build_net(cfg, s) for s in (1, 1, 2))
    wa, wb, wc = (np.asarray(n.params[0]["W"]) for n in (a, b, c))
    assert np.array_equal(wa, wb) and not np.array_equal(wa, wc)
    xa, ya = nets.images(cfg, 4, 1)
    xb, _ = nets.images(cfg, 4, 1)
    xc, _ = nets.images(cfg, 4, 2)
    assert xa.dtype == np.float32 and ya.sum() == 4
    assert np.array_equal(xa, xb) and not np.array_equal(xa, xc)
