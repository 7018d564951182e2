"""User-extension tier (reference ``TestCustomLayers`` /
``CustomActivation`` / ``CustomOutputLayer``): a user-defined layer
config, activation, and output head plug into the standard machinery —
config serde round-trip, gradient check, training — with no framework
changes."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (DataSet, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.gradientcheck import check_gradients
from deeplearning4j_tpu.nn import activations
from deeplearning4j_tpu.nn.conf import inputs, serde
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.base import FeedForwardLayerConfig


# ---- a user-defined layer: dense with a learned per-feature gate -------

@serde.register("test_gated_dense")
@dataclasses.dataclass
class GatedDenseLayer(FeedForwardLayerConfig):
    """W·x + b, elementwise-multiplied by sigmoid(g) with a learned gate
    vector g — the reference's CustomLayer pattern (own params, own
    forward, own hyperparameter)."""

    gate_bias: float = 0.0      # custom hyperparameter, must serde

    def param_order(self):
        return ("W", "b", "g")

    def init_params(self, rng, dtype=jnp.float32):
        params = super().init_params(rng, dtype)
        params["g"] = jnp.full((self.n_out,), self.gate_bias, dtype)
        return params

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        z = x @ params["W"] + params["b"]
        gated = self._activate(z) * (1.0 / (1.0 + jnp.exp(-params["g"])))
        return gated, state


def _conf(out_layer=None, activation="tanh"):
    return (NeuralNetConfiguration.builder().seed(12)
            .dtype("float64").updater("sgd").learning_rate(0.1)
            .activation(activation).weight_init("xavier").list()
            .layer(GatedDenseLayer(n_out=8, gate_bias=0.5))
            .layer(out_layer or OutputLayer(n_out=3))
            .set_input_type(inputs.feed_forward(5))
            .build())


def _ds(n=12, seed=0, separable=False):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 5)
    if separable:
        y = np.argmax(x[:, :3], axis=1)     # learnable rule
    else:
        y = rng.randint(0, 3, n)
    return DataSet(x, np.eye(3)[y])


def test_custom_layer_config_round_trips():
    conf = _conf()
    again = type(conf).from_json(conf.to_json())
    layer = again.layers[0]
    assert isinstance(layer, GatedDenseLayer)
    assert layer.gate_bias == 0.5
    assert layer.n_out == 8
    # predictions identical through the round trip
    net = MultiLayerNetwork(conf).init()
    net2 = MultiLayerNetwork(again).init()
    x = _ds().features
    np.testing.assert_allclose(np.asarray(net.output(x)),
                               np.asarray(net2.output(x)), atol=1e-12)


def test_custom_layer_gradients_check():
    net = MultiLayerNetwork(_conf()).init()
    assert check_gradients(net, _ds())


def test_custom_layer_trains():
    net = MultiLayerNetwork(_conf()).init()
    ds = _ds(n=120, seed=3, separable=True)
    s0 = net.score(ds)
    for _ in range(80):
        net.fit(ds)
    assert net.score(ds) < s0 * 0.6
    # the custom gate parameter actually moved
    g = np.asarray(net.params[0]["g"])
    assert not np.allclose(g, 0.5)


# ---- a user-defined activation -----------------------------------------

@pytest.fixture
def _registry_as_it_was():
    """A registered activation stays for the process, and while one
    from outside the package is there no net's programs are served by
    the executable store (``monitor.program_identity``): a later test of
    the store in the same worker would read no hit."""
    before = dict(activations._ACTIVATIONS)
    yield
    activations._ACTIVATIONS.clear()
    activations._ACTIVATIONS.update(before)


def test_custom_activation_by_name(_registry_as_it_was):
    activations.register("test_tanh_cubed",
                         lambda x: jnp.tanh(x) ** 3)
    # shadowing a built-in requires explicit consent
    with pytest.raises(ValueError):
        activations.register("relu", lambda x: x)
    conf = (NeuralNetConfiguration.builder().seed(5)
            .dtype("float64").updater("sgd").learning_rate(0.1)
            .activation("test_tanh_cubed").weight_init("xavier").list()
            .layer(DenseLayer(n_out=6))
            .layer(OutputLayer(n_out=3, activation="softmax"))
            .set_input_type(inputs.feed_forward(5))
            .build())
    net = MultiLayerNetwork(conf).init()
    assert check_gradients(net, _ds())   # autodiff through the custom fn
    # serde keeps the NAME, resolving through the registry on restore
    again = MultiLayerNetwork(type(conf).from_json(conf.to_json())).init()
    x = _ds().features
    np.testing.assert_allclose(np.asarray(net.output(x)),
                               np.asarray(again.output(x)), atol=1e-12)


# ---- a user-defined output head ----------------------------------------

@serde.register("test_scaled_output")
@dataclasses.dataclass
class ScaledOutputLayer(OutputLayer):
    """CustomOutputLayer pattern: reuse the stock loss machinery but
    scale the pre-activation (own forward + own pre_output)."""

    preout_scale: float = 2.0

    def pre_output(self, params, x):
        return (x @ params["W"] + params["b"]) * self.preout_scale

    def forward(self, params, state, x, *, train, rng=None, mask=None):
        return self._activate(self.pre_output(params, x)), state


def test_custom_output_layer_gradients_and_training():
    conf = _conf(out_layer=ScaledOutputLayer(n_out=3, preout_scale=1.5))
    assert isinstance(
        type(conf).from_json(conf.to_json()).layers[1], ScaledOutputLayer)
    net = MultiLayerNetwork(conf).init()
    assert check_gradients(net, _ds())
    ds = _ds(n=120, seed=4, separable=True)
    s0 = net.score(ds)
    for _ in range(80):
        net.fit(ds)
    assert net.score(ds) < s0 * 0.6
