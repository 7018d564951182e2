"""LeNet-5 MNIST model builder (BASELINE.json config #1).

The reference has no model zoo at 0.7.3; this mirrors the canonical DL4J
LeNet example config (conv 5x5x20 -> maxpool -> conv 5x5x50 -> maxpool ->
dense 500 -> softmax 10) used by its MNIST samples, expressed through the
same builder API.
"""

from __future__ import annotations

from ..nn.conf import inputs
from ..nn.conf.neural_net_configuration import (MultiLayerConfiguration,
                                                NeuralNetConfiguration)
from ..nn.layers.convolution import ConvolutionLayer, SubsamplingLayer
from ..nn.layers.core import DenseLayer, OutputLayer


def lenet(seed: int = 123, learning_rate: float = 1e-3,
          updater: str = "adam", n_classes: int = 10,
          height: int = 28, width: int = 28, channels: int = 1,
          compute_dtype: str | None = None) -> MultiLayerConfiguration:
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(updater).learning_rate(learning_rate)
         .weight_init("xavier").activation("identity"))
    if compute_dtype:
        b = b.compute_dtype(compute_dtype)
    return (b.list()
            .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5),
                                    stride=(1, 1), activation="identity"))
            .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                    stride=(2, 2)))
            .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5),
                                    stride=(1, 1), activation="identity"))
            .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                    stride=(2, 2)))
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=n_classes, activation="softmax",
                               loss="mcxent"))
            .set_input_type(inputs.convolutional_flat(height, width, channels))
            .build())
