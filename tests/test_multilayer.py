"""MultiLayerNetwork integration tests: fit/output/score/serde/flat-params
(analogue of reference deeplearning4j-core/src/test/.../nn/multilayer/
MultiLayerTest.java and nn/conf serde tests)."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (DataSet, MultiLayerConfiguration,
                                MultiLayerNetwork, NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import inputs
from deeplearning4j_tpu.nn.layers.core import (ActivationLayer, DenseLayer,
                                               DropoutLayer, EmbeddingLayer,
                                               LossLayer, OutputLayer)


def _toy_classification(n=128, n_in=4, n_classes=3, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, n_in).astype(np.float32)
    y = (X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)
    Y = np.eye(n_classes, dtype=np.float32)[y]
    return DataSet(X, Y)


def _mlp_conf(updater="sgd", lr=0.5, **builder_kw):
    b = (NeuralNetConfiguration.builder()
         .seed(42).updater(updater).learning_rate(lr)
         .activation("tanh").weight_init("xavier"))
    return (b.list()
            .layer(DenseLayer(n_out=16))
            .layer(OutputLayer(n_out=3))
            .set_input_type(inputs.feed_forward(4))
            .build())


def test_n_in_inference():
    conf = _mlp_conf()
    assert conf.layers[0].n_in == 4
    assert conf.layers[1].n_in == 16


def test_global_defaults_inherited_and_overridable():
    conf = (NeuralNetConfiguration.builder()
            .activation("relu").l2(1e-4)
            .list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(DenseLayer(n_in=8, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_in=8, n_out=3))
            .build())
    assert conf.layers[0].activation == "relu"
    assert conf.layers[1].activation == "tanh"
    assert conf.layers[2].activation == "softmax"  # OutputLayer default
    assert conf.layers[0].l2 == 1e-4


@pytest.mark.parametrize("updater", ["sgd", "adam", "nesterovs", "rmsprop",
                                     "adagrad", "adadelta"])
def test_fit_decreases_score_all_updaters(updater):
    lr = {"sgd": 0.5, "adam": 0.01, "nesterovs": 0.1, "rmsprop": 0.01,
          "adagrad": 0.1, "adadelta": 1.0}[updater]
    ds = _toy_classification()
    net = MultiLayerNetwork(_mlp_conf(updater=updater, lr=lr)).init()
    s0 = net.score(ds)
    for _ in range(100):
        net.fit(ds)
    assert net.score(ds) < s0


def test_accuracy_on_separable_toy():
    ds = _toy_classification()
    net = MultiLayerNetwork(_mlp_conf()).init()
    for _ in range(300):
        net.fit(ds)
    assert net.evaluate(ds).accuracy() > 0.95


def test_output_deterministic_inference():
    ds = _toy_classification()
    conf = (NeuralNetConfiguration.builder().seed(1).drop_out(0.5)
            .list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=3))
            .build())
    net = MultiLayerNetwork(conf).init()
    out1 = net.output(ds.features)
    out2 = net.output(ds.features)
    np.testing.assert_allclose(out1, out2)  # no dropout at inference


def test_json_roundtrip_preserves_behavior():
    ds = _toy_classification()
    conf = _mlp_conf()
    net = MultiLayerNetwork(conf).init()
    net.fit(ds)
    j = conf.to_json()
    conf2 = MultiLayerConfiguration.from_json(j)
    assert conf2.to_json() == j
    net2 = MultiLayerNetwork(conf2).init()
    net2.set_flat_params(net.get_flat_params())
    np.testing.assert_allclose(net2.output(ds.features),
                               net.output(ds.features), atol=1e-6)


def test_flat_params_roundtrip():
    net = MultiLayerNetwork(_mlp_conf()).init()
    flat = net.get_flat_params()
    assert flat.size == net.num_params() == 4 * 16 + 16 + 16 * 3 + 3
    flat2 = flat + 1.0
    net.set_flat_params(flat2)
    np.testing.assert_allclose(net.get_flat_params(), flat2, atol=1e-6)


def test_flat_updater_state_roundtrip():
    ds = _toy_classification()
    net = MultiLayerNetwork(_mlp_conf(updater="adam", lr=0.01)).init()
    net.fit(ds)
    flat = net.get_flat_updater_state()
    assert flat.size == 2 * net.num_params()  # adam m+v
    net.set_flat_updater_state(flat * 0.5)
    np.testing.assert_allclose(net.get_flat_updater_state(), flat * 0.5,
                               atol=1e-6)


def test_seed_reproducibility():
    c1 = _mlp_conf()
    c2 = _mlp_conf()
    n1 = MultiLayerNetwork(c1).init()
    n2 = MultiLayerNetwork(c2).init()
    np.testing.assert_allclose(n1.get_flat_params(), n2.get_flat_params())


def test_param_table_names():
    net = MultiLayerNetwork(_mlp_conf()).init()
    table = net.param_table()
    assert set(table) == {"0_W", "0_b", "1_W", "1_b"}
    assert table["0_W"].shape == (4, 16)


def test_embedding_layer_lookup():
    conf = (NeuralNetConfiguration.builder().seed(0)
            .list()
            .layer(EmbeddingLayer(n_in=10, n_out=5))
            .layer(OutputLayer(n_in=5, n_out=2))
            .build())
    net = MultiLayerNetwork(conf).init()
    idx = np.array([[1], [3], [7]], np.int32)
    out = net.output(idx)
    assert out.shape == (3, 2)


def test_activation_and_dropout_layers_pass_through():
    conf = (NeuralNetConfiguration.builder().seed(0).activation("relu")
            .list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(ActivationLayer(activation="tanh"))
            .layer(DropoutLayer(dropout=0.5))
            .layer(OutputLayer(n_in=8, n_out=3))
            .build())
    net = MultiLayerNetwork(conf).init()
    out = net.output(np.zeros((2, 4), np.float32))
    assert out.shape == (2, 3)
    ds = _toy_classification()
    net.fit(ds)  # trains with dropout rng


def test_regression_mse_head():
    rng = np.random.RandomState(0)
    X = rng.randn(64, 3).astype(np.float32)
    W_true = rng.randn(3, 2).astype(np.float32)
    Y = X @ W_true
    conf = (NeuralNetConfiguration.builder().seed(0).updater("adam")
            .learning_rate(0.05)
            .list()
            .layer(OutputLayer(n_in=3, n_out=2, activation="identity",
                               loss="mse"))
            .build())
    net = MultiLayerNetwork(conf).init()
    ds = DataSet(X, Y)
    for _ in range(300):
        net.fit(ds)
    assert net.score(ds) < 1e-2


def test_loss_layer_headless():
    conf = (NeuralNetConfiguration.builder().seed(0)
            .list()
            .layer(DenseLayer(n_in=4, n_out=3, activation="identity"))
            .layer(LossLayer(loss="mse"))
            .build())
    net = MultiLayerNetwork(conf).init()
    ds = DataSet(np.random.RandomState(0).randn(8, 4).astype(np.float32),
                 np.random.RandomState(1).randn(8, 3).astype(np.float32))
    s0 = net.score(ds)
    for _ in range(50):
        net.fit(ds)
    assert net.score(ds) < s0


def test_clone_independent():
    ds = _toy_classification()
    net = MultiLayerNetwork(_mlp_conf()).init()
    other = net.clone()
    net.fit(ds)
    # clone unchanged by original's training
    assert not np.allclose(net.get_flat_params(), other.get_flat_params())


def test_fit_scan_matches_sequential_steps():
    """The scan-based multi-step (one dispatch = S sequential SGD steps,
    ``MultiLayerNetwork.fit_scan``) produces bitwise the same params as S
    separate ``fit`` dispatches — it is an execution strategy, not a
    different algorithm."""
    ds = _toy_classification()
    batches = [DataSet(ds.features[i * 32:(i + 1) * 32],
                       ds.labels[i * 32:(i + 1) * 32]) for i in range(4)]
    net_a = MultiLayerNetwork(_mlp_conf(updater="adam", lr=0.01)).init()
    net_b = MultiLayerNetwork(_mlp_conf(updater="adam", lr=0.01)).init()
    scores = net_a.fit_scan(batches)
    for b in batches:
        net_b.fit(b)
    np.testing.assert_allclose(net_a.get_flat_params(),
                               net_b.get_flat_params(), rtol=1e-6)
    assert net_a.iteration == net_b.iteration == 4
    assert scores.shape == (4,)
    assert np.all(np.isfinite(scores))


# ------------------------------------------------------------ scoreExamples

def test_score_examples_matches_single_example_score():
    """Reference contract (scoreExamples:1757): with regularization, the
    ith entry equals score() on a DataSet holding only example i."""
    conf = (NeuralNetConfiguration.builder().seed(3).updater("sgd")
            .learning_rate(0.1).l2(0.01).weight_init("xavier").list()
            .layer(DenseLayer(n_in=4, n_out=6, activation="tanh"))
            .layer(OutputLayer(n_in=6, n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    X = np.float32(rng.randn(7, 4))
    Y = np.float32(np.eye(3)[rng.randint(0, 3, 7)])
    per = net.score_examples(DataSet(X, Y), add_regularization_terms=True)
    assert per.shape == (7,)
    for i in range(7):
        single = net.score(DataSet(X[i:i + 1], Y[i:i + 1]))
        assert per[i] == pytest.approx(single, rel=1e-5)
    # without reg: mean equals unregularized data loss
    plain = net.score_examples(DataSet(X, Y), add_regularization_terms=False)
    assert (per - plain).std() == pytest.approx(0.0, abs=1e-6)
    assert per[0] - plain[0] > 0          # l2 term present


def test_score_examples_iterator_and_autoencoder_anomaly():
    """The reference use case: per-example reconstruction error ranks an
    outlier last (autoencoder anomaly detection)."""
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    conf = (NeuralNetConfiguration.builder().seed(1).updater("adam")
            .learning_rate(1e-2).weight_init("xavier").list()
            .layer(DenseLayer(n_in=8, n_out=3, activation="tanh"))
            .layer(OutputLayer(n_in=3, n_out=8, activation="identity",
                               loss="mse"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    X = np.float32(rng.randn(64, 8) * 0.1)
    net.fit(DataSet(X, X), epochs=200)
    probe = np.concatenate([X[:16], np.float32(np.ones((1, 8)) * 3.0)])
    scores = net.score_examples(DataSet(probe, probe),
                                add_regularization_terms=False)
    assert scores.argmax() == 16          # the outlier scores worst
    # iterator overload concatenates across batches
    it = ListDataSetIterator(DataSet(probe, probe), batch_size=5)
    np.testing.assert_allclose(net.score_examples(it), scores, rtol=1e-5)


def test_score_examples_empty_iterator():
    conf = (NeuralNetConfiguration.builder().seed(3).list()
            .layer(DenseLayer(n_in=4, n_out=6))
            .layer(OutputLayer(n_in=6, n_out=3))
            .build())
    net = MultiLayerNetwork(conf).init()
    out = net.score_examples(iter([]))
    assert out.shape == (0,)


# ---------------------------------------------------------- TransferLearning

def test_transfer_learning_freeze_and_new_head():
    """Freeze the feature extractor, swap the head for a new class count:
    frozen params stay bitwise identical through fine-tuning, the new
    head trains, and transferred weights carry over."""
    from deeplearning4j_tpu.nn.transfer import TransferLearning

    rng = np.random.RandomState(0)
    X = np.float32(rng.randn(200, 6))
    y3 = (X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)
    src = MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(1).updater("adam")
         .learning_rate(5e-3).weight_init("xavier").activation("tanh")
         .list()
         .layer(DenseLayer(n_in=6, n_out=16))
         .layer(DenseLayer(n_in=16, n_out=8))
         .layer(OutputLayer(n_in=8, n_out=3))
         .build())).init()
    src.fit(DataSet(X, np.float32(np.eye(3)[y3])), epochs=30)

    # new 2-class task on the same features
    y2 = (X[:, 0] + X[:, 1] > 0).astype(int)
    new = (TransferLearning.builder(src)
           .fine_tune_learning_rate(1e-2)
           .set_feature_extractor(1)          # freeze both dense layers
           .remove_output_layer()
           .add_layer(OutputLayer(n_in=8, n_out=2))
           .build())
    assert len(new.layers) == 3
    assert new.layers[0].frozen and new.layers[1].frozen
    assert not new.layers[2].frozen
    # transferred weights equal the source's
    np.testing.assert_array_equal(np.asarray(new.params[0]["W"]),
                                  np.asarray(src.params[0]["W"]))

    frozen_before = np.asarray(new.params[1]["W"]).copy()
    head_before = np.asarray(new.params[2]["W"]).copy()
    new.fit(DataSet(X, np.float32(np.eye(2)[y2])), epochs=40)
    np.testing.assert_array_equal(np.asarray(new.params[1]["W"]),
                                  frozen_before)       # frozen: unchanged
    assert not np.allclose(np.asarray(new.params[2]["W"]), head_before)
    acc = (new.predict(X) == y2).mean()
    assert acc > 0.85


def test_transfer_learning_frozen_flag_serializes(tmp_path):
    from deeplearning4j_tpu import (restore_multi_layer_network,
                                    write_model)
    from deeplearning4j_tpu.nn.transfer import TransferLearning

    src = MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(2).list()
         .layer(DenseLayer(n_in=4, n_out=5))
         .layer(OutputLayer(n_in=5, n_out=2))
         .build())).init()
    new = (TransferLearning.builder(src)
           .set_feature_extractor(0)
           .build())
    p = str(tmp_path / "tl.zip")
    write_model(new, p)
    again = restore_multi_layer_network(p)
    assert again.layers[0].frozen and not again.layers[1].frozen
    rng = np.random.RandomState(0)
    ds = DataSet(np.float32(rng.randn(8, 4)),
                 np.float32(np.eye(2)[rng.randint(0, 2, 8)]))
    w0 = np.asarray(again.params[0]["W"]).copy()
    again.fit(ds, epochs=3)
    np.testing.assert_array_equal(np.asarray(again.params[0]["W"]), w0)


def test_transfer_learning_validation():
    from deeplearning4j_tpu.nn.transfer import TransferLearning

    src = MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(3).list()
         .layer(DenseLayer(n_in=4, n_out=5))
         .layer(OutputLayer(n_in=5, n_out=2))
         .build())).init()
    with pytest.raises(ValueError, match="out of range"):
        TransferLearning.builder(src).remove_layers_from(7)
    with pytest.raises(ValueError, match="freeze"):
        (TransferLearning.builder(src).set_feature_extractor(5).build())
    with pytest.raises(ValueError, match="no layers"):
        TransferLearning.builder(src).remove_layers_from(0).build()


def test_transfer_fine_tune_lr_applies_to_kept_unfrozen_layers():
    """The lr override must reach kept unfrozen layers, whose updater
    confs were finalized (and de-aliased) at original build time."""
    from deeplearning4j_tpu.nn.transfer import TransferLearning
    src = MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(1).updater("sgd")
         .learning_rate(0.5).list()
         .layer(DenseLayer(n_in=4, n_out=5))
         .layer(DenseLayer(n_in=5, n_out=5))
         .layer(OutputLayer(n_in=5, n_out=2))
         .build())).init()
    new = (TransferLearning.builder(src)
           .fine_tune_learning_rate(1e-3)
           .set_feature_extractor(0)
           .build())
    assert new.layers[1].updater.learning_rate == pytest.approx(1e-3)
    assert new.layers[2].updater.learning_rate == pytest.approx(1e-3)
    # build() twice produces the same architecture (no duplicated head)
    b = TransferLearning.builder(src).remove_output_layer() \
        .add_layer(OutputLayer(n_in=5, n_out=4))
    n1, n2 = b.build(), b.build()
    assert len(n1.layers) == len(n2.layers) == 3
    assert len(src.conf.layers) == 3      # source conf untouched
    # chained transfer preserves earlier freezes by default
    first = (TransferLearning.builder(src).set_feature_extractor(0)
             .build())
    second = (TransferLearning.builder(first).remove_output_layer()
              .add_layer(OutputLayer(n_in=5, n_out=4)).build())
    assert second.layers[0].frozen
    with pytest.raises(ValueError, match="freeze"):
        # cannot freeze into the added-head range
        (TransferLearning.builder(src).remove_output_layer()
         .set_feature_extractor(2)
         .add_layer(OutputLayer(n_in=5, n_out=4)).build())


def test_frozen_respected_by_solver_path():
    """LBFGS/line-search solvers operate on the raveled param vector; the
    trainable mask must keep frozen layers fixed there too."""
    from deeplearning4j_tpu.nn.transfer import TransferLearning
    src = MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(2).updater("sgd")
         .learning_rate(0.1).weight_init("xavier").list()
         .layer(DenseLayer(n_in=4, n_out=6, activation="tanh"))
         .layer(OutputLayer(n_in=6, n_out=2))
         .build())).init()
    new = (TransferLearning.builder(src).set_feature_extractor(0).build())
    new.conf.conf.optimization_algo = "lbfgs"
    rng = np.random.RandomState(0)
    ds = DataSet(np.float32(rng.randn(32, 4)),
                 np.float32(np.eye(2)[rng.randint(0, 2, 32)]))
    w_frozen = np.asarray(new.params[0]["W"]).copy()
    s0 = new.score(ds)
    new.fit(ds, epochs=5)
    np.testing.assert_array_equal(np.asarray(new.params[0]["W"]), w_frozen)
    assert new.score(ds) < s0          # head still optimizes


# ------------------------------------------------ init() as staged programs
from deeplearning4j_tpu.nn.weights import Distribution

INIT_SCHEMES = ["zero", "ones", "xavier", "xavier_uniform", "xavier_fan_in",
                "xavier_legacy", "relu", "relu_uniform", "sigmoid_uniform",
                "uniform", "lecun_normal", "lecun_uniform", "normal",
                "identity"]
INIT_DISTS = {"dist_normal": Distribution("normal", 0.0, 0.3),
              "dist_normal_mean": Distribution("normal", 0.25, 0.3),
              "dist_uniform": Distribution("uniform", lower=-0.2, upper=0.7),
              "dist_binomial": Distribution("binomial", n_trials=5,
                                            prob_success=0.3)}


def _scheme_builder(scheme, updater):
    b = (NeuralNetConfiguration.builder().seed(1234).updater(updater)
         .learning_rate(0.1).activation("tanh"))
    return b.dist(INIT_DISTS[scheme]) if scheme in INIT_DISTS \
        else b.weight_init(scheme)


def assert_trees_bit_identical(got, ref):
    import jax
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("precision", ["fp32", "mixed_bf16"])
@pytest.mark.parametrize("scheme", INIT_SCHEMES + sorted(INIT_DISTS))
def test_init_programs_equal_leaf_by_leaf_bit_for_bit(scheme, precision,
                                                      monkeypatch):
    """``init()`` runs two staged programs; the values are the ones the
    same Python gives leaf by leaf, one operation at a time."""
    from deeplearning4j_tpu import monitor
    monkeypatch.setenv("DL4J_TPU_PRECISION", precision)
    conf = (_scheme_builder(scheme, "adam").list()
            .layer(DenseLayer(n_out=12))
            .layer(DenseLayer(n_out=12))        # square: identity fits
            .layer(OutputLayer(n_out=12))
            .set_input_type(inputs.feed_forward(12)).build())
    before = monitor.snapshot().get("jit_compiles_total", {}).get(
        "values", {}).get('{fn="mln.init"}', 0)
    net = MultiLayerNetwork(conf).init()
    assert net._pol().name == precision
    ref = net._init_program.__wrapped__(net._rng_key)
    assert_trees_bit_identical(
        (net.params, net.net_state, net.updater_state), ref)
    compiled = monitor.snapshot().get("jit_compiles_total", {}).get(
        "values", {}).get('{fn="mln.init"}', 0) - before
    # a normal distribution with a mean cannot be staged (its product
    # and sum would fuse into one rounding), nor a binomial one (its
    # constants fold differently on a TPU): they stay leaf by leaf
    assert compiled == (0 if scheme in ("dist_normal_mean",
                                        "dist_binomial") else 1)
    if precision == "mixed_bf16":
        assert net.params[0]["W"].dtype == jnp.bfloat16
        master = net.updater_state[0]["_master"]["W"]
        assert master.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(master), np.asarray(net.params[0]["W"],
                                           np.float32))


def test_second_process_loads_init_and_the_gather_step(tmp_path):
    """The first process on a conf derives and writes; a second one
    loads all three programs, traces nothing, and trains to the same
    bits; another seed is the same program (the key is an argument)."""
    import second_process
    first = second_process.run("mln", tmp_path)
    for fn in second_process.PROGRAMS["mln"]:
        assert first["results"][fn] == ["miss_absent", "written"]
        assert first["compiles"][fn] == 1
    # under <cache dir>/executables, one whole entry a program
    assert first["stored"]["entries"] == 3 and first["stored"]["bytes"] > 0
    assert "executables" in os.listdir(tmp_path)
    second = second_process.run("mln", tmp_path)
    for fn in second_process.PROGRAMS["mln"]:
        assert second["results"][fn] == ["hit"]
        assert second["trace_s"][fn] == 0 and second["lower_s"][fn] == 0
        assert second["compiles"][fn] == 0
        assert second["backend_s"][fn] == second["load_s"][fn] > 0
    assert second["params"] == first["params"]
    assert second["score"] == first["score"]
    third = second_process.run("mln", tmp_path, NET_SEED="8")
    assert all(r == ["hit"] for r in third["results"].values())
    assert third["params"] != first["params"]
    # the health configuration is read while tracing: part of the
    # identity, so another program under another key
    fourth = second_process.run("mln", tmp_path,
                                DL4J_TPU_HEALTH_POLICY="skip_update")
    assert fourth["results"]["mln.gather_train_step"] == [
        "miss_absent", "written"]
    # an executable the backend will not serialize (here the CPU's, for
    # the sort in a shuffled epoch) is compiled and run as ever, and
    # nothing is written
    fifth = second_process.run("mln", tmp_path, NET_SHUFFLE="1")
    assert fifth["results"]["mln.gather_train_step"] == ["miss_absent"]
    assert fifth["compiles"]["mln.gather_train_step"] == 1
    assert fifth["results"]["mln.init"] == ["hit"]
    assert np.isfinite(fifth["score"])
