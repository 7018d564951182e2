"""Updater / lr-policy / gradient-normalization unit tests (analogue of the
reference's updater tests in deeplearning4j-core/src/test/.../nn/updater/)."""

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import updaters


def _conf(**kw):
    return updaters.UpdaterConfig(**kw)


def _step(conf, grads, params=None, iters=1):
    state = updaters.init_state(conf, grads)
    p = params if params is not None else {k: jnp.zeros_like(v)
                                           for k, v in grads.items()}
    for i in range(iters):
        upd, state = updaters.compute_update(conf, grads, state, i)
        p = {k: p[k] - upd[k] for k in p}
    return p, state


def test_sgd_step():
    g = {"W": jnp.array([1.0, -2.0])}
    p, _ = _step(_conf(updater="sgd", learning_rate=0.1), g)
    np.testing.assert_allclose(np.asarray(p["W"]), [-0.1, 0.2], atol=1e-7)


def test_nesterov_momentum_accumulates():
    conf = _conf(updater="nesterovs", learning_rate=0.1, momentum=0.9)
    g = {"W": jnp.array([1.0])}
    state = updaters.init_state(conf, g)
    u1, state = updaters.compute_update(conf, g, state, 0)
    u2, state = updaters.compute_update(conf, g, state, 1)
    # second step is larger due to accumulated velocity
    assert abs(float(u2["W"][0])) > abs(float(u1["W"][0]))


def test_adam_bias_correction_first_step():
    conf = _conf(updater="adam", learning_rate=0.001)
    g = {"W": jnp.array([0.5])}
    u, _ = updaters.compute_update(
        conf, g, updaters.init_state(conf, g), jnp.asarray(0))
    # first-step bias-corrected Adam ~= lr * sign(g)
    np.testing.assert_allclose(abs(float(u["W"][0])), 0.001, rtol=0.05)


def test_adagrad_shrinks_effective_lr():
    conf = _conf(updater="adagrad", learning_rate=0.1)
    g = {"W": jnp.array([1.0])}
    state = updaters.init_state(conf, g)
    u1, state = updaters.compute_update(conf, g, state, 0)
    u2, state = updaters.compute_update(conf, g, state, 1)
    assert float(u2["W"][0]) < float(u1["W"][0])


def test_rmsprop_and_adadelta_finite():
    for name in ("rmsprop", "adadelta"):
        conf = _conf(updater=name, learning_rate=0.01)
        g = {"W": jnp.array([0.3, -0.7])}
        p, _ = _step(conf, g, iters=3)
        assert bool(jnp.all(jnp.isfinite(p["W"])))


def test_noop_returns_grad():
    conf = _conf(updater="none")
    g = {"W": jnp.array([0.3])}
    u, _ = updaters.compute_update(conf, g, {}, 0)
    np.testing.assert_allclose(np.asarray(u["W"]), [0.3])


# -------------------------------- lr policies ------------------------------

def test_lr_policy_exponential():
    conf = _conf(learning_rate=1.0, lr_policy="exponential",
                 lr_policy_decay_rate=0.5)
    assert float(updaters.learning_rate_for(conf, 0)) == 1.0
    assert abs(float(updaters.learning_rate_for(conf, 2)) - 0.25) < 1e-6


def test_lr_policy_step():
    conf = _conf(learning_rate=1.0, lr_policy="step",
                 lr_policy_decay_rate=0.1, lr_policy_steps=10)
    assert abs(float(updaters.learning_rate_for(conf, 5)) - 1.0) < 1e-6
    assert abs(float(updaters.learning_rate_for(conf, 15)) - 0.1) < 1e-6


def test_lr_policy_poly():
    conf = _conf(learning_rate=1.0, lr_policy="poly", lr_policy_power=1.0,
                 max_num_iterations=100)
    assert abs(float(updaters.learning_rate_for(conf, 50)) - 0.5) < 1e-6


def test_lr_policy_schedule():
    conf = _conf(learning_rate=0.1, lr_policy="schedule",
                 lr_schedule={0: 0.1, 10: 0.01, 20: 0.001})
    assert abs(float(updaters.learning_rate_for(conf, 5)) - 0.1) < 1e-7
    assert abs(float(updaters.learning_rate_for(conf, 15)) - 0.01) < 1e-7
    assert abs(float(updaters.learning_rate_for(conf, 25)) - 0.001) < 1e-7


def test_momentum_schedule():
    conf = _conf(momentum=0.5, momentum_schedule={10: 0.9})
    assert abs(float(updaters.momentum_for(conf, 0)) - 0.5) < 1e-7
    assert abs(float(updaters.momentum_for(conf, 10)) - 0.9) < 1e-7


# --------------------------- gradient normalization ------------------------

def test_renormalize_l2_per_layer():
    g = {"W": jnp.array([3.0]), "b": jnp.array([4.0])}
    out = updaters.normalize_gradients(g, "RenormalizeL2PerLayer")
    norm = np.sqrt(float(out["W"][0])**2 + float(out["b"][0])**2)
    np.testing.assert_allclose(norm, 1.0, atol=1e-6)


def test_clip_elementwise():
    g = {"W": jnp.array([3.0, -0.2])}
    out = updaters.normalize_gradients(g, "ClipElementWiseAbsoluteValue", 1.0)
    np.testing.assert_allclose(np.asarray(out["W"]), [1.0, -0.2], atol=1e-7)


def test_clip_l2_per_layer_only_when_above():
    g = {"W": jnp.array([0.3, 0.4])}  # norm 0.5 < 1.0 -> untouched
    out = updaters.normalize_gradients(g, "ClipL2PerLayer", 1.0)
    np.testing.assert_allclose(np.asarray(out["W"]), [0.3, 0.4], atol=1e-7)
    g2 = {"W": jnp.array([3.0, 4.0])}  # norm 5 -> scaled to 1
    out2 = updaters.normalize_gradients(g2, "ClipL2PerLayer", 1.0)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(out2["W"])), 1.0, atol=1e-6)


# --------------------------- regularization --------------------------------

def test_regularize_adds_l2_to_weights_only():
    params = {"W": jnp.array([2.0]), "b": jnp.array([3.0])}
    grads = {"W": jnp.array([0.0]), "b": jnp.array([0.0])}
    out = updaters.regularize(grads, params, {"W": 0.0, "b": 0.0},
                              {"W": 0.1, "b": 0.0})
    np.testing.assert_allclose(np.asarray(out["W"]), [0.2], atol=1e-7)
    np.testing.assert_allclose(np.asarray(out["b"]), [0.0], atol=1e-7)


def test_regularization_score():
    params = {"W": jnp.array([2.0, -1.0])}
    s = updaters.regularization_score(params, {"W": 0.5}, {"W": 0.1})
    # 0.5*0.1*(4+1) + 0.5*(2+1) = 0.25 + 1.5
    np.testing.assert_allclose(float(s), 1.75, atol=1e-6)


def test_serde_roundtrip():
    conf = _conf(updater="adam", learning_rate=0.01,
                 lr_schedule={0: 0.1, 5: 0.01})
    d = conf.to_dict()
    back = updaters.UpdaterConfig.from_dict(d)
    assert back == conf


# ----------------------------------------------------------------- lars

def test_lars_trust_ratio_scales_per_tensor():
    """LARS (You et al. 2017; the MLPerf TPU-pod large-batch recipe):
    step magnitude per tensor follows eta*||w||/(||g||+wd*||w||)."""
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.nn.updaters import (UpdaterConfig,
                                                compute_update, init_state)
    conf = UpdaterConfig(updater="lars", learning_rate=1.0, momentum=0.0,
                         lars_trust_coefficient=0.01,
                         lars_weight_decay=0.0)
    w = {"W": jnp.full((4, 4), 2.0), "b": jnp.full((4,), 0.5)}
    g = {"W": jnp.full((4, 4), 1.0), "b": jnp.full((4,), 1.0)}
    state = init_state(conf, w)
    updates, new_state = compute_update(conf, g, state, 0, params=w)
    # trust = eta * ||w|| / ||g||; step = lr * trust * g
    for k in ("W", "b"):
        w_norm = float(jnp.linalg.norm(w[k].ravel()))
        g_norm = float(jnp.linalg.norm(g[k].ravel()))
        expect = 0.01 * w_norm / g_norm
        np.testing.assert_allclose(np.asarray(updates[k]),
                                   expect * np.asarray(g[k]), rtol=1e-5)
    # momentum state recorded
    np.testing.assert_allclose(np.asarray(new_state["v"]["W"]),
                               np.asarray(updates["W"]))


def test_lars_momentum_and_weight_decay():
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.nn.updaters import (UpdaterConfig,
                                                compute_update, init_state)
    conf = UpdaterConfig(updater="lars", learning_rate=0.5, momentum=0.9,
                         lars_trust_coefficient=0.02,
                         lars_weight_decay=1e-4)
    w = {"W": jnp.ones((3, 3))}
    g = {"W": jnp.full((3, 3), 0.1)}
    state = init_state(conf, w)
    u1, s1 = compute_update(conf, g, state, 0, params=w)
    u2, s2 = compute_update(conf, g, s1, 1, params=w)
    # closed form of step 1: lr * trust * (g + wd*w), trust from RAW ||g||
    w_norm = float(jnp.linalg.norm(w["W"].ravel()))
    g_norm = float(jnp.linalg.norm(g["W"].ravel()))
    trust = 0.02 * w_norm / (g_norm + 1e-4 * w_norm + 1e-12)
    expect1 = 0.5 * trust * (np.asarray(g["W"]) + 1e-4 * np.asarray(w["W"]))
    np.testing.assert_allclose(np.asarray(u1["W"]), expect1, rtol=1e-5)
    # second step adds heavy-ball momentum of the first
    np.testing.assert_allclose(np.asarray(u2["W"]),
                               0.9 * expect1 + expect1, rtol=1e-5)


def test_lars_network_trains():
    """End-to-end: a net configured with updater('lars') fits."""
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import inputs
    from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder()
            .seed(3).updater("lars").learning_rate(2.0)
            .activation("tanh").weight_init("xavier").list()
            .layer(DenseLayer(n_out=16))
            .layer(OutputLayer(n_out=3))
            .set_input_type(inputs.feed_forward(4))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    X = rng.randn(128, 4).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[(X[:, 0] > 0).astype(int)
                                    + (X[:, 1] > 0).astype(int)]
    before = float(net.score(DataSet(X, y)))
    for _ in range(60):
        net.fit(DataSet(X, y))
    after = float(net.score(DataSet(X, y)))
    assert after < before * 0.7, (before, after)


@pytest.mark.parametrize("dtype", ["bfloat16", "float16", "float32"])
def test_masters_hold_exactly_what_the_parameters_hold(dtype):
    """``init_state``'s fp32 masters: the parameter's own value, also
    inside a program that made the parameter by rounding an fp32 value
    (where XLA may keep the excess precision of a plain cast)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn import precision, updaters
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 33), jnp.float32)
    policy = precision._MIXED_POLICY

    def masters(x):
        params = {"W": x.astype(dtype), "b": jnp.zeros((33,), dtype)}
        state = updaters.init_state(
            updaters.UpdaterConfig(updater="nesterovs"), params,
            policy=policy)
        return params, state

    for run in (masters, jax.jit(masters)):
        params, state = run(x)
        if dtype == "float32":
            assert updaters.MASTER_KEY not in state
            continue
        master = state[updaters.MASTER_KEY]["W"]
        assert master.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(master), np.asarray(params["W"]).astype(np.float32))
        assert state["v"]["W"].dtype == jnp.float32
