"""``chip_smoke.py`` on the CPU mesh: the same phase functions the chip
runs, at toy widths, with the Pallas kernels in interpret mode — so a
phase that breaks is caught here for free and the chip run is spent on
what only the chip can show.  Also: without a TPU the script must exit
non-zero and say which platform it found."""

import json

import jax
import pytest

import chip_smoke
from deeplearning4j_tpu import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import inputs
from deeplearning4j_tpu.nn.layers.convolution import ConvolutionLayer
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.normalization import BatchNormalization
from deeplearning4j_tpu.nn.layers.pooling import GlobalPoolingLayer


def _tiny_graph():
    """conv -> batch norm -> pool -> softmax: ResNet-50's layer kinds."""
    g = (NeuralNetConfiguration.builder().seed(3).updater("nesterovs")
         .learning_rate(0.05).graph_builder())
    g.add_inputs("input")
    g.add_layer("conv", ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                         convolution_mode="same",
                                         has_bias=False), "input")
    g.add_layer("bn", BatchNormalization(activation="relu"), "conv")
    g.add_layer("pool", GlobalPoolingLayer(pooling_type="avg"), "bn")
    g.add_layer("out", OutputLayer(n_out=5, activation="softmax",
                                   loss="mcxent"), "pool")
    g.set_outputs("out")
    g.set_input_types(inputs.convolutional(8, 8, 3))
    return g.build()


def _tiny_mln():
    return (NeuralNetConfiguration.builder().seed(4).updater("adam")
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(inputs.feed_forward(6))
            .build())


TOY = chip_smoke.Sizes(
    graph_conf=_tiny_graph, image=(8, 8, 3), classes=5, examples=16,
    batch=4, serve_max_batch=4, serve_clients=4,
    hidden=16, heads=2, cache_len=16, layers=2, vocab=7, train_t=16,
    train_batch=2, prefill=6, decode_steps=4,
    kernel_bthd=(1, 64, 2, 16), kernel_ref_t=32, kernel_short_ts=(40, 8),
    latent_shape=(2, 8, 128, 16, 256, 200),
    experts_shape=(40, 8, 32, 64, 2),
    experts_share_shape=(32, 24, 4, 32, 64, 3),
    sparse_shape=(2, 4, 2, 16, 3, 8, 256, 16, 8),
    gqa_shape=(2, 4, 2, 16, 8, 256, 8), interpret=True, mln_conf=_tiny_mln, mln_features=6, mln_classes=3)


def test_train_then_serve():
    net, info = chip_smoke.phase_train(TOY, expect_policy="fp32")
    assert info["steps_per_fit"] == 8
    served = chip_smoke.phase_serve(TOY, net)
    assert served["engine_backend"] == "aot"
    assert served["max_rel_err"] <= served["bound"]


def test_train_refuses_another_policy():
    with pytest.raises(AssertionError, match="precision policy"):
        chip_smoke.phase_train(TOY, expect_policy="mixed_bf16")


def test_decode():
    info = chip_smoke.phase_decode(TOY)
    # prefill 6 sits in the 8-slot ring; token 9 hops to 16: one grow
    assert info["dispatches_per_token"] == [1, 1, 2, 1]


def test_kernels():
    info = chip_smoke.phase_kernels(TOY)
    assert {"bfloat16_T40_max_rel_err", "bfloat16_T8_max_rel_err",
            "float32_T32_max_rel_err",
            "latent_streamed_max_rel_err",
            "experts_grouped_max_rel_err",
            "experts_share_grouped_max_rel_err",
            "sparse_streamed_max_rel_err"} <= set(info)


def test_four_chips():
    info = chip_smoke.phase_four_chips(TOY, jax.devices())
    assert len(set(info["devices"])) == 4


def test_main_exits_nonzero_and_names_the_platform_without_a_tpu(
        monkeypatch, capsys, tmp_path):
    # keep the process-global cache decision out of the test session
    monkeypatch.setattr(chip_smoke.compile_cache, "enable",
                        lambda: str(tmp_path))
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert "'cpu'" in err and "not 'tpu'" in err
    assert '"platform": "cpu"' in out
    # no result object: the last stdout line is not the ok line
    with pytest.raises(ValueError):
        json.loads(out.strip().splitlines()[-1])
