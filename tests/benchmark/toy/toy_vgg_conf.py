"""A VGG-family net at toy widths, built from the program's own layer
classes: the configuration of the toy cell that exists only under
``tests/benchmark/`` (the widths must match ``configs/toy_vgg.json``)."""

from deeplearning4j_tpu.nn.conf import inputs
from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
    NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers.convolution import (ConvolutionLayer,
                                                      SubsamplingLayer)
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer


def build(l2=0.0):
    lb = (NeuralNetConfiguration.builder().seed(1).updater("nesterovs")
          .learning_rate(1e-2).weight_init("relu").activation("identity")
          .l2(l2).list())
    for width in (8, 16):
        lb.layer(ConvolutionLayer(n_out=width, kernel_size=(3, 3),
                                  stride=(1, 1), convolution_mode="same",
                                  activation="relu"))
        lb.layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                  stride=(2, 2)))
    lb.layer(DenseLayer(n_out=32, activation="relu"))
    lb.layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
    lb.set_input_type(inputs.convolutional(16, 16, 3))
    return lb.build()
