"""ComputationGraph tests, modeled on the reference's
``gradientcheck/GradientCheckTestsComputationGraph.java`` and
``nn/graph/graphnodes`` vertex tests (SURVEY.md §4)."""

import numpy as np
import pytest

from deeplearning4j_tpu import DataSet, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.datasets.dataset import MultiDataSet
from deeplearning4j_tpu.gradientcheck import check_gradients_graph
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.conf import inputs
from deeplearning4j_tpu.nn.conf.computation_graph import (
    ComputationGraphConfiguration, ElementWiseVertex, L2NormalizeVertex,
    L2Vertex, LastTimeStepVertex, MergeVertex, ScaleVertex, ShiftVertex,
    StackVertex, SubsetVertex, UnstackVertex, DuplicateToTimeSeriesVertex)
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.recurrent import GravesLSTM, RnnOutputLayer


def _builder(seed=12345):
    return (NeuralNetConfiguration.builder().seed(seed)
            .dtype("float64").updater("sgd").learning_rate(0.1)
            .activation("tanh").weight_init("xavier").graph_builder())


def _ds(n=6, n_in=4, n_classes=3, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, n_in)
    Y = np.eye(n_classes)[rng.randint(0, n_classes, n)]
    return DataSet(X, Y)


# -------------------------------------------------------------- basic DAGs
@pytest.mark.parametrize("how", ["cache", "window", "batch", "fit_scan"])
def test_linear_graph_matches_multilayer(how):
    """A chain CG must compute exactly what the MLN computes with the same
    params (reference: CG with single path == MLN), and train to the same
    bits however the data arrives: both go through the one driver and
    the one step of ``nn/network.py``."""
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    g = (_builder().add_inputs("in")
         .add_layer("dense", DenseLayer(n_in=4, n_out=6), "in")
         .add_layer("out", OutputLayer(n_in=6, n_out=3), "dense")
         .set_outputs("out").build())
    cg = ComputationGraph(g).init()

    mln_conf = (NeuralNetConfiguration.builder().seed(12345)
                .dtype("float64").updater("sgd").learning_rate(0.1)
                .activation("tanh").weight_init("xavier").list()
                .layer(DenseLayer(n_in=4, n_out=6))
                .layer(OutputLayer(n_in=6, n_out=3)).build())
    mln = MultiLayerNetwork(mln_conf).init()
    cg.set_flat_params(mln.get_flat_params())

    ds = _ds(n=24)
    ds = DataSet(ds.features.astype(np.float32),
                 ds.labels.astype(np.float32))
    np.testing.assert_allclose(mln.output(ds.features), cg.output(ds.features),
                               rtol=1e-10)

    def train(net):
        if how == "fit_scan":
            return net.fit_scan(list(ListDataSetIterator(ds, 8)))
        net.fit(ListDataSetIterator(ds, 8), epochs=2, ingest=how)
        return net.score()

    np.testing.assert_array_equal(train(mln), train(cg))
    assert mln.iteration == cg.iteration == (3 if how == "fit_scan" else 6)
    np.testing.assert_array_equal(mln.get_flat_params(),
                                  cg.get_flat_params())
    np.testing.assert_array_equal(mln.get_flat_updater_state(),
                                  cg.get_flat_updater_state())


def test_topological_order_and_cycle_detection():
    g = (_builder().add_inputs("in")
         .add_layer("a", DenseLayer(n_in=4, n_out=4), "in")
         .add_layer("b", DenseLayer(n_in=4, n_out=4), "a")
         .add_layer("out", OutputLayer(n_in=4, n_out=3), "b")
         .set_outputs("out").build())
    order = g.topological_order()
    assert order.index("a") < order.index("b") < order.index("out")

    bad = (_builder().add_inputs("in"))
    bad.add_layer("a", DenseLayer(n_in=4, n_out=4), "in", "b")
    bad.add_layer("b", DenseLayer(n_in=4, n_out=4), "a")
    bad.add_layer("out", OutputLayer(n_in=4, n_out=3), "b")
    bad.set_outputs("out")
    with pytest.raises(ValueError, match="cycle"):
        bad.build()

    unknown = (_builder().add_inputs("in"))
    unknown.add_layer("a", DenseLayer(n_in=4, n_out=4), "nonexistent")
    unknown.add_layer("out", OutputLayer(n_in=4, n_out=3), "a")
    unknown.set_outputs("out")
    with pytest.raises(ValueError, match="unknown input"):
        unknown.build()


# ---------------------------------------------------------- vertex gradchecks
def test_merge_vertex_gradients():
    g = (_builder().add_inputs("in1", "in2")
         .add_layer("d1", DenseLayer(n_in=3, n_out=4), "in1")
         .add_layer("d2", DenseLayer(n_in=2, n_out=5), "in2")
         .add_vertex("merge", MergeVertex(), "d1", "d2")
         .add_layer("out", OutputLayer(n_in=9, n_out=3), "merge")
         .set_outputs("out").build())
    cg = ComputationGraph(g).init()
    rng = np.random.RandomState(0)
    mds = MultiDataSet(features=[rng.randn(5, 3), rng.randn(5, 2)],
                       labels=[np.eye(3)[rng.randint(0, 3, 5)]])
    assert check_gradients_graph(cg, mds)


def test_elementwise_and_skip_connection_gradients():
    g = (_builder().add_inputs("in")
         .add_layer("d1", DenseLayer(n_in=4, n_out=4), "in")
         .add_layer("d2", DenseLayer(n_in=4, n_out=4), "d1")
         .add_vertex("add", ElementWiseVertex(op="add"), "d1", "d2")
         .add_layer("out", OutputLayer(n_in=4, n_out=3), "add")
         .set_outputs("out").build())
    assert check_gradients_graph(ComputationGraph(g).init(), _ds())


@pytest.mark.parametrize("op", ["subtract", "product", "average", "max"])
def test_elementwise_ops_gradients(op):
    g = (_builder().add_inputs("in")
         .add_layer("d1", DenseLayer(n_in=4, n_out=4, activation="sigmoid"),
                    "in")
         .add_layer("d2", DenseLayer(n_in=4, n_out=4, activation="sigmoid"),
                    "in")
         .add_vertex("combine", ElementWiseVertex(op=op), "d1", "d2")
         .add_layer("out", OutputLayer(n_in=4, n_out=3), "combine")
         .set_outputs("out").build())
    assert check_gradients_graph(ComputationGraph(g).init(), _ds())


def test_subset_scale_shift_gradients():
    g = (_builder().add_inputs("in")
         .add_layer("d", DenseLayer(n_in=4, n_out=8), "in")
         .add_vertex("subset", SubsetVertex(from_index=2, to_index=5), "d")
         .add_vertex("scale", ScaleVertex(scale_factor=1.5), "subset")
         .add_vertex("shift", ShiftVertex(shift_factor=0.3), "scale")
         .add_layer("out", OutputLayer(n_in=4, n_out=3), "shift")
         .set_outputs("out").build())
    assert check_gradients_graph(ComputationGraph(g).init(), _ds())


def test_stack_unstack_gradients():
    g = (_builder().add_inputs("in1", "in2")
         .add_vertex("stack", StackVertex(), "in1", "in2")
         .add_layer("shared", DenseLayer(n_in=3, n_out=4), "stack")
         .add_vertex("u1", UnstackVertex(from_index=0, stack_size=2),
                     "shared")
         .add_vertex("u2", UnstackVertex(from_index=1, stack_size=2),
                     "shared")
         .add_vertex("merge", MergeVertex(), "u1", "u2")
         .add_layer("out", OutputLayer(n_in=8, n_out=3), "merge")
         .set_outputs("out").build())
    cg = ComputationGraph(g).init()
    rng = np.random.RandomState(0)
    mds = MultiDataSet(features=[rng.randn(5, 3), rng.randn(5, 3)],
                       labels=[np.eye(3)[rng.randint(0, 3, 5)]])
    assert check_gradients_graph(cg, mds)


def test_l2_vertices_gradients():
    g = (_builder().add_inputs("in1", "in2")
         .add_layer("d1", DenseLayer(n_in=3, n_out=4), "in1")
         .add_layer("d2", DenseLayer(n_in=3, n_out=4), "in2")
         .add_vertex("norm", L2NormalizeVertex(), "d1")
         .add_vertex("dist", L2Vertex(), "norm", "d2")
         .add_layer("out", OutputLayer(n_in=1, n_out=2,
                                       activation="sigmoid",
                                       loss="xent"), "dist")
         .set_outputs("out").build())
    cg = ComputationGraph(g).init()
    rng = np.random.RandomState(3)
    mds = MultiDataSet(features=[rng.randn(5, 3), rng.randn(5, 3)],
                       labels=[rng.randint(0, 2, (5, 2)).astype(float)])
    assert check_gradients_graph(cg, mds)


def test_multi_output_gradients():
    g = (_builder().add_inputs("in")
         .add_layer("trunk", DenseLayer(n_in=4, n_out=6), "in")
         .add_layer("out1", OutputLayer(n_in=6, n_out=3), "trunk")
         .add_layer("out2", OutputLayer(n_in=6, n_out=2,
                                        activation="identity", loss="mse"),
                    "trunk")
         .set_outputs("out1", "out2").build())
    cg = ComputationGraph(g).init()
    rng = np.random.RandomState(0)
    mds = MultiDataSet(features=[rng.randn(5, 4)],
                       labels=[np.eye(3)[rng.randint(0, 3, 5)],
                               rng.randn(5, 2)])
    assert check_gradients_graph(cg, mds)


# ------------------------------------------------------------- rnn vertices
def test_last_time_step_and_duplicate_gradients():
    g = (_builder().add_inputs("seq", "static")
         .add_layer("lstm", GravesLSTM(n_in=3, n_out=4), "seq")
         .add_vertex("last", LastTimeStepVertex(mask_input="seq"), "lstm")
         .add_vertex("dup", DuplicateToTimeSeriesVertex(
             reference_input="seq"), "static")
         .add_layer("rnnout", RnnOutputLayer(n_in=4, n_out=3), "lstm")
         .add_layer("ffout", OutputLayer(n_in=4, n_out=2), "last")
         .set_outputs("rnnout", "ffout").build())
    cg = ComputationGraph(g).init()
    rng = np.random.RandomState(0)
    t = 5
    lengths = rng.randint(2, t + 1, 4)
    fm = (np.arange(t)[None, :] < lengths[:, None]).astype(np.float64)
    Y1 = np.zeros((4, t, 3))
    idx = rng.randint(0, 3, (4, t))
    for i in range(4):
        Y1[i, np.arange(t), idx[i]] = 1.0
    mds = MultiDataSet(
        features=[rng.randn(4, t, 3), rng.randn(4, 2)],
        labels=[Y1, np.eye(2)[rng.randint(0, 2, 4)]],
        features_masks=[fm, None],
        labels_masks=[fm, None])
    assert check_gradients_graph(cg, mds)


def test_duplicate_to_time_series_forward():
    g = (_builder().add_inputs("seq", "static")
         .add_vertex("dup", DuplicateToTimeSeriesVertex(
             reference_input="seq"), "static")
         .add_vertex("merge", MergeVertex(), "seq", "dup")
         .add_layer("out", RnnOutputLayer(n_in=5, n_out=2), "merge")
         .set_outputs("out").build())
    cg = ComputationGraph(g).init()
    out = cg.output(np.random.randn(3, 7, 3), np.random.randn(3, 2))
    assert out.shape == (3, 7, 2)


# ----------------------------------------------------------------- training
def test_multi_input_training_learns():
    """XOR-of-two-inputs task through a merge graph."""
    rng = np.random.RandomState(0)
    a = rng.randint(0, 2, (200, 1)).astype(float)
    b_in = rng.randint(0, 2, (200, 1)).astype(float)
    y = np.eye(2)[(a[:, 0].astype(int) ^ b_in[:, 0].astype(int))]
    mds = MultiDataSet(features=[a, b_in], labels=[y])
    g = (NeuralNetConfiguration.builder().seed(7).updater("adam")
         .learning_rate(0.01).activation("relu").weight_init("xavier")
         .graph_builder()
         .add_inputs("a", "b")
         .add_vertex("merge", MergeVertex(), "a", "b")
         .add_layer("h", DenseLayer(n_in=2, n_out=16), "merge")
         .add_layer("out", OutputLayer(n_in=16, n_out=2), "h")
         .set_outputs("out").build())
    cg = ComputationGraph(g).init()
    s0 = None
    cg.fit(mds, epochs=300)
    preds = cg.predict(a, b_in)
    acc = (preds == y.argmax(1)).mean()
    assert acc > 0.95


# ------------------------------------------------------------------- serde
def test_graph_config_json_roundtrip():
    g = (_builder().add_inputs("in1", "in2")
         .add_layer("d1", DenseLayer(n_in=3, n_out=4), "in1")
         .add_vertex("merge", MergeVertex(), "d1", "in2")
         .add_layer("out", OutputLayer(n_in=6, n_out=3), "merge")
         .set_outputs("out").build())
    restored = ComputationGraphConfiguration.from_json(g.to_json())
    assert restored.network_inputs == ["in1", "in2"]
    assert isinstance(restored.vertices["merge"], MergeVertex)
    assert restored.vertices["merge"].inputs == ["d1", "in2"]
    assert restored.vertices["out"].layer.n_in == 6
    assert restored.topological_order() == g.topological_order()


def test_graph_model_serializer_roundtrip(tmp_path):
    from deeplearning4j_tpu.utils.model_serializer import (
        restore_computation_graph, write_model)
    g = (_builder().add_inputs("in")
         .add_layer("d", DenseLayer(n_in=4, n_out=5), "in")
         .add_layer("out", OutputLayer(n_in=5, n_out=3), "d")
         .set_outputs("out").build())
    cg = ComputationGraph(g).init()
    ds = _ds()
    cg.fit(ds)
    path = str(tmp_path / "cg.zip")
    write_model(cg, path)
    restored = restore_computation_graph(path)
    np.testing.assert_allclose(cg.output(ds.features),
                               restored.output(ds.features), rtol=1e-6)
    restored.fit(ds)  # restored model must keep training (updater state ok)


# ----------------------------------------------------------------- shapes
def test_shape_inference_infers_nin_and_preprocessors():
    g = (_builder().add_inputs("img")
         .add_layer("d", DenseLayer(n_out=10), "img")
         .add_layer("out", OutputLayer(n_out=3), "d")
         .set_outputs("out")
         .set_input_types(inputs.convolutional_flat(8, 8, 1)).build())
    assert g.vertices["d"].layer.n_in == 64
    assert g.vertices["out"].layer.n_in == 10


# -------------------------------------------------------------------- zoo
def test_resnet50_builds_with_canonical_param_count():
    from deeplearning4j_tpu.models.resnet import resnet50
    conf = resnet50(n_classes=1000, height=32, width=32)
    cg = ComputationGraph(conf).init()
    assert cg.num_params() == 25_557_032  # canonical ResNet-50
    out = cg.output(np.random.randn(2, 32, 32, 3).astype(np.float32))
    assert out.shape == (2, 1000)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-3)


# ----------------------------------------- graph rnnTimeStep + graph tBPTT

def _seq_graph(tbptt=None, back=None, seed=12345, n_in=3, n_out=3):
    b = (_builder(seed).add_inputs("seq")
         .add_layer("lstm1", GravesLSTM(n_in=n_in, n_out=4), "seq")
         .add_layer("lstm2", GravesLSTM(n_in=4, n_out=4), "lstm1")
         .add_layer("rnnout", RnnOutputLayer(n_in=4, n_out=n_out), "lstm2")
         .set_outputs("rnnout"))
    if tbptt:
        b = b.backprop_type("tbptt").t_bptt_forward_length(tbptt)
        if back:
            b = b.t_bptt_backward_length(back)
    return ComputationGraph(b.build()).init()


def _seq_batch(n=4, t=6, n_in=3, n_cls=3, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, t, n_in)
    Y = np.eye(n_cls)[rng.randint(0, n_cls, (n, t))]
    return MultiDataSet(features=[X], labels=[Y])


def test_graph_rnn_time_step_matches_full_sequence():
    cg = _seq_graph()
    mds = _seq_batch()
    full = cg.output(*mds.features)
    cg.rnn_clear_previous_state()
    stepped = [cg.rnn_time_step(mds.features[0][:, t])
               for t in range(mds.features[0].shape[1])]
    np.testing.assert_allclose(full, np.stack(stepped, axis=1),
                               rtol=1e-6, atol=1e-8)


def test_graph_rnn_time_step_chunked_matches():
    cg = _seq_graph()
    mds = _seq_batch()
    full = cg.output(*mds.features)
    cg.rnn_clear_previous_state()
    a = cg.rnn_time_step(mds.features[0][:, :2])
    b = cg.rnn_time_step(mds.features[0][:, 2:])
    np.testing.assert_allclose(full, np.concatenate([a, b], axis=1),
                               rtol=1e-6, atol=1e-8)


def test_graph_rnn_clear_state_resets():
    cg = _seq_graph()
    mds = _seq_batch()
    x0 = mds.features[0][:, 0]
    first = cg.rnn_time_step(x0)
    assert not np.allclose(first, cg.rnn_time_step(x0))
    cg.rnn_clear_previous_state()
    np.testing.assert_allclose(first, cg.rnn_time_step(x0))


def test_graph_rnn_state_get_set_and_batch_guard():
    cg = _seq_graph()
    mds = _seq_batch()
    cg.rnn_time_step(mds.features[0][:, 0])
    st = cg.rnn_get_previous_state("lstm1")
    assert st is not None
    cg.rnn_set_previous_state("lstm1", st)
    with pytest.raises(KeyError):
        cg.rnn_set_previous_state("rnnout_nope", st)
    with pytest.raises(ValueError):
        cg.rnn_time_step(mds.features[0][:1, 0])


def test_graph_tbptt_equals_standard_when_window_covers_sequence():
    mds = _seq_batch()
    a = _seq_graph(tbptt=6)
    b = _seq_graph()
    a.fit(mds)
    b.fit(mds)
    np.testing.assert_allclose(a.get_flat_params(), b.get_flat_params(),
                               rtol=1e-10)


def test_graph_tbptt_training_decreases_score():
    rng = np.random.RandomState(7)
    X = rng.randn(16, 12, 3)
    cls = (np.cumsum(X.sum(-1), axis=1) > 0).astype(int)
    Y = np.eye(3)[cls + 1]
    mds = MultiDataSet(features=[X], labels=[Y])
    cg = _seq_graph(tbptt=4)
    cg.fit(mds)
    s0 = cg.score(mds)
    cg.fit(mds, epochs=30)
    assert cg.score(mds) < s0 * 0.7
    assert cg.iteration == 31 * 3  # 12 steps / window 4 per fit call


def test_graph_tbptt_back_shorter_than_fwd_trains():
    rng = np.random.RandomState(9)
    X = rng.randn(8, 12, 3)
    cls = (np.cumsum(X.sum(-1), axis=1) > 0).astype(int)
    Y = np.eye(3)[cls + 1]
    mds = MultiDataSet(features=[X], labels=[Y])
    cg = _seq_graph(tbptt=6, back=3)
    cg.fit(mds)
    s0 = cg.score(mds)
    cg.fit(mds, epochs=25)
    assert cg.score(mds) < s0


def test_graph_tbptt_back_longer_than_fwd_raises():
    cg = _seq_graph(tbptt=4, back=6)
    with pytest.raises(ValueError):
        cg.fit(_seq_batch())


def test_graph_tbptt_sequence_level_labels_raise():
    cg = _seq_graph(tbptt=4)
    rng = np.random.RandomState(0)
    mds = MultiDataSet(features=[rng.randn(4, 6, 3)],
                       labels=[np.eye(3)[rng.randint(0, 3, 4)]])
    with pytest.raises(ValueError):
        cg.fit(mds)


def test_fit_scan_matches_sequential_fit():
    """Graph fit_scan == N sequential fit() calls, bitwise on params."""
    rng = np.random.RandomState(0)
    batches = [MultiDataSet([np.float32(rng.randn(6, 4))],
                            [np.float32(np.eye(3)[rng.randint(0, 3, 6)])])
               for _ in range(4)]
    def build():
        g = (_builder().add_inputs("in")
             .add_layer("d", DenseLayer(n_in=4, n_out=5), "in")
             .add_layer("out", OutputLayer(n_in=5, n_out=3), "d")
             .set_outputs("out").build())
        return ComputationGraph(g).init()
    seq, scan = build(), build()
    for b in batches:
        seq.fit(b)
    scores = scan.fit_scan(batches)
    assert scores.shape == (4,)
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(seq.params),
                    jax.tree_util.tree_leaves(scan.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fit_scan_mask_presence_per_index():
    """Mask presence is validated per input index across batches, not
    against batch 0 as a template."""
    from deeplearning4j_tpu.nn.layers.recurrent import GravesLSTM, RnnOutputLayer
    rng = np.random.RandomState(1)
    def mds(with_mask):
        m = np.ones((2, 5), np.float32) if with_mask else None
        return MultiDataSet([np.float32(rng.randn(2, 5, 3))],
                            [np.float32(rng.rand(2, 5, 2))],
                            [m], [m])
    g = (_builder().add_inputs("in")
         .add_layer("l", GravesLSTM(n_in=3, n_out=4), "in")
         .add_layer("out", RnnOutputLayer(n_in=4, n_out=2), "l")
         .set_outputs("out").build())
    net = ComputationGraph(g).init()
    with pytest.raises(ValueError, match="Mixed mask presence"):
        net.fit_scan([mds(True), mds(False)])
    with pytest.raises(ValueError, match="Mixed mask presence"):
        net.fit_scan([mds(False), mds(True)])
    net.fit_scan([mds(True), mds(True)])     # consistent masks train fine


def test_graph_score_examples_matches_single_example_score():
    """Reference ComputationGraph.scoreExamples: per-example scores sum
    output-layer losses; with reg each row equals score() on one example."""
    g = (_builder().add_inputs("in")
         .add_layer("d", DenseLayer(n_in=4, n_out=5), "in")
         .add_layer("out", OutputLayer(n_in=5, n_out=3), "d")
         .set_outputs("out").build())
    net = ComputationGraph(g).init()
    rng = np.random.RandomState(0)
    X = np.float64(rng.randn(6, 4))
    Y = np.float64(np.eye(3)[rng.randint(0, 3, 6)])
    per = net.score_examples(MultiDataSet([X], [Y]))
    assert per.shape == (6,)
    for i in range(3):
        single = net.score(MultiDataSet([X[i:i + 1]], [Y[i:i + 1]]))
        assert per[i] == pytest.approx(single, rel=1e-5)


def test_graph_score_examples_sums_multiple_outputs():
    g = (_builder().add_inputs("in")
         .add_layer("d", DenseLayer(n_in=4, n_out=5), "in")
         .add_layer("o1", OutputLayer(n_in=5, n_out=3), "d")
         .add_layer("o2", OutputLayer(n_in=5, n_out=2, loss="mse",
                                      activation="identity"), "d")
         .set_outputs("o1", "o2").build())
    net = ComputationGraph(g).init()
    rng = np.random.RandomState(1)
    X = np.float64(rng.randn(5, 4))
    Y1 = np.float64(np.eye(3)[rng.randint(0, 3, 5)])
    Y2 = np.float64(rng.randn(5, 2))
    both = net.score_examples(MultiDataSet([X], [Y1, Y2]),
                              add_regularization_terms=False)
    # equals the sum of single-output nets' per-example data losses
    g1 = (_builder().add_inputs("in")
          .add_layer("d", DenseLayer(n_in=4, n_out=5), "in")
          .add_layer("o1", OutputLayer(n_in=5, n_out=3), "d")
          .set_outputs("o1").build())
    n1 = ComputationGraph(g1).init()
    n1.params["d"], n1.params["o1"] = net.params["d"], net.params["o1"]
    g2 = (_builder().add_inputs("in")
          .add_layer("d", DenseLayer(n_in=4, n_out=5), "in")
          .add_layer("o2", OutputLayer(n_in=5, n_out=2, loss="mse",
                                       activation="identity"), "d")
          .set_outputs("o2").build())
    n2 = ComputationGraph(g2).init()
    n2.params["d"], n2.params["o2"] = net.params["d"], net.params["o2"]
    s1 = n1.score_examples(MultiDataSet([X], [Y1]),
                           add_regularization_terms=False)
    s2 = n2.score_examples(MultiDataSet([X], [Y2]),
                           add_regularization_terms=False)
    np.testing.assert_allclose(both, s1 + s2, rtol=1e-6)


def test_graph_transfer_learning_freeze_and_head_swap():
    """Graph transfer: freeze a vertex + ancestors, swap the output head
    for a new class count, fine-tune; frozen weights stay bitwise fixed
    and the source graph survives (no shared donated buffers)."""
    from deeplearning4j_tpu.nn.transfer import TransferLearning

    g = (_builder().add_inputs("in")
         .add_layer("d1", DenseLayer(n_in=4, n_out=8), "in")
         .add_layer("d2", DenseLayer(n_in=8, n_out=6), "d1")
         .add_layer("out", OutputLayer(n_in=6, n_out=3), "d2")
         .set_outputs("out").build())
    src = ComputationGraph(g).init()
    rng = np.random.RandomState(0)
    X = np.float64(rng.randn(60, 4))
    y3 = rng.randint(0, 3, 60)
    src.fit(MultiDataSet([X], [np.float64(np.eye(3)[y3])]))
    src_out_before = np.asarray(src.output(X))

    y2 = (X[:, 0] > 0).astype(int)
    new = (TransferLearning.graph_builder(src)
           .fine_tune_learning_rate(0.05)
           .set_feature_extractor("d1")
           .replace_output_layer("out", OutputLayer(n_in=6, n_out=2))
           .build())
    assert new.vertices["d1"].layer.frozen
    assert not new.vertices["d2"].layer.frozen
    assert not new.vertices["out"].layer.frozen
    assert new.vertices["out"].layer.n_out == 2
    np.testing.assert_array_equal(np.asarray(new.params["d1"]["W"]),
                                  np.asarray(src.params["d1"]["W"]))

    w_frozen = np.asarray(new.params["d1"]["W"]).copy()
    for _ in range(60):
        new.fit(MultiDataSet([X], [np.float64(np.eye(2)[y2])]))
    np.testing.assert_array_equal(np.asarray(new.params["d1"]["W"]),
                                  w_frozen)
    assert np.asarray(new.output(X)).shape == (60, 2)
    acc = np.asarray(new.output(X)).argmax(1)
    assert (acc == y2).mean() > 0.8
    # source graph unharmed by the fine-tune (deep-copied params)
    np.testing.assert_allclose(np.asarray(src.output(X)), src_out_before)


def test_graph_transfer_validation():
    from deeplearning4j_tpu.nn.transfer import TransferLearning

    g = (_builder().add_inputs("in")
         .add_layer("d", DenseLayer(n_in=4, n_out=5), "in")
         .add_layer("out", OutputLayer(n_in=5, n_out=2), "d")
         .set_outputs("out").build())
    net = ComputationGraph(g).init()
    b = TransferLearning.graph_builder(net)
    with pytest.raises(ValueError, match="unknown vertices"):
        b.set_feature_extractor("nope")
    with pytest.raises(ValueError, match="not a layer vertex"):
        b.replace_output_layer("in", OutputLayer(n_in=5, n_out=2))
    with pytest.raises(ValueError, match="frozen and replaced"):
        (TransferLearning.graph_builder(net)
         .set_feature_extractor("out")
         .replace_output_layer("out", OutputLayer(n_in=5, n_out=4))
         .build())


def test_graph_transfer_pretrain_flag_and_shape_inference():
    """Transferred nets keep the source's pretraining-done state, and a
    replacement head without n_in gets it from shape inference when the
    source graph was built with input types."""
    from deeplearning4j_tpu.nn.conf import inputs as _inputs
    from deeplearning4j_tpu.nn.layers.pretrain import AutoEncoder
    from deeplearning4j_tpu.nn.transfer import TransferLearning

    g = (_builder().add_inputs("in")
         .add_layer("ae", AutoEncoder(activation="sigmoid", n_out=5), "in")
         .add_layer("out", OutputLayer(n_out=3), "ae")
         .set_input_types(_inputs.feed_forward(4))
         .set_outputs("out").build())
    src = ComputationGraph(g).init()
    rng = np.random.RandomState(0)
    mds = MultiDataSet([np.float64(rng.rand(16, 4))],
                       [np.float64(np.eye(3)[rng.randint(0, 3, 16)])])
    src.pretrain(mds, epochs=1)
    assert src._pretrain_done
    new = (TransferLearning.graph_builder(src)
           .set_feature_extractor("ae")
           .replace_output_layer("out", OutputLayer(n_out=2))  # no n_in!
           .build())
    assert new._pretrain_done                      # flag carried over
    assert new.vertices["out"].layer.n_in == 5     # inferred
    w = np.asarray(new.params["ae"]["W"]).copy()
    new.fit(mds._replace(labels=[np.float64(np.eye(2)[
        rng.randint(0, 2, 16)])]) if hasattr(mds, "_replace") else
        MultiDataSet(mds.features,
                     [np.float64(np.eye(2)[rng.randint(0, 2, 16)])]))
    np.testing.assert_array_equal(np.asarray(new.params["ae"]["W"]), w)


# ------------------------------------------------ init() as staged programs
@pytest.mark.parametrize("precision", ["fp32", "mixed_bf16"])
@pytest.mark.parametrize("scheme", ["xavier", "relu", "lecun_normal",
                                    "uniform", "dist_normal",
                                    "dist_normal_mean"])
def test_graph_init_programs_equal_leaf_by_leaf_bit_for_bit(
        scheme, precision, monkeypatch):
    """Graph twin of the ``MultiLayerNetwork`` test (which runs every
    scheme): convolution, batch-norm state and dense vertices, in
    topological order, with updater state and masters."""
    import jax
    from test_multilayer import (_scheme_builder,
                                 assert_trees_bit_identical)
    from deeplearning4j_tpu.nn.layers.convolution import ConvolutionLayer
    from deeplearning4j_tpu.nn.layers.normalization import (
        BatchNormalization)
    monkeypatch.setenv("DL4J_TPU_PRECISION", precision)
    conf = (_scheme_builder(scheme, "nesterovs").graph_builder()
            .add_inputs("in")
            .add_layer("z_conv", ConvolutionLayer(
                n_out=8, kernel_size=(3, 3)), "in")
            .add_layer("bn", BatchNormalization(), "z_conv")
            .add_layer("a_dense", DenseLayer(n_out=10), "bn")
            .add_layer("out", OutputLayer(n_out=3), "a_dense")
            .set_outputs("out")
            .set_input_types(inputs.convolutional(8, 8, 3)).build())
    g = ComputationGraph(conf).init()
    assert g._pol().name == precision
    # topological order, not the sorted order a jitted dict comes in
    assert list(g.params) == g._layer_names() == list(g.updater_state)
    assert list(g.params)[0] == "z_conv"
    ref = g._init_program.__wrapped__(g._rng_key)
    got = (g.params, g.net_state, g.updater_state)
    for tree, want in zip(got, ref):
        assert set(tree) == set(want)
        for name in tree:
            assert_trees_bit_identical(tree[name], want[name])
    assert set(g.net_state["bn"]) == {"mean", "var"}


def test_second_process_loads_graph_init_and_the_gather_step(tmp_path):
    import second_process
    first = second_process.run("cg", tmp_path)
    for fn in second_process.PROGRAMS["cg"]:
        assert first["results"][fn] == ["miss_absent", "written"]
    second = second_process.run("cg", tmp_path)
    for fn in second_process.PROGRAMS["cg"]:
        assert second["results"][fn] == ["hit"]
        assert second["trace_s"][fn] == 0 and second["lower_s"][fn] == 0
        assert second["compiles"][fn] == 0
        assert second["backend_s"][fn] == second["load_s"][fn] > 0
    assert second["params"] == first["params"]
    assert second["score"] == first["score"]
    # the precision policy is part of the identity: other programs
    third = second_process.run("cg", tmp_path,
                               DL4J_TPU_PRECISION="mixed_bf16")
    for fn in second_process.PROGRAMS["cg"]:
        assert third["results"][fn][0] == "miss_absent"
        assert third["compiles"][fn] == 1
    # (the draws' program is the same HLO under either policy: JAX's own
    # cache reloads it, and XLA:CPU cannot serialize that again)
    assert third["results"]["cg.init"] == ["miss_absent", "written"]
    assert third["results"]["cg.gather_train_step"] == [
        "miss_absent", "written"]
