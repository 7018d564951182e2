"""Serve a trained model through the C++ PJRT runtime: parameters upload
once into persistent device buffers, each request stages only the
activations, and executables are cached per input shape
(`nn/native_runtime.NativeModelRunner` — the cuDNN-helper/ND4J-backend
deployment role, with zero Python/JAX dispatch on the hot path).

One PJRT client per chip: on a machine with a TPU run this with
``JAX_PLATFORMS=cpu`` so JAX authors the StableHLO on the host and the
native client owns the chip; where JAX itself holds the TPU the runner
refuses at once and the example says why."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))   # run from anywhere

import numpy as np

from deeplearning4j_tpu import DataSet, MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import inputs
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer


def main():
    conf = (NeuralNetConfiguration.builder()
            .seed(9).updater("adam").learning_rate(0.02)
            .activation("relu").weight_init("xavier").list()
            .layer(DenseLayer(n_out=32))
            .layer(OutputLayer(n_out=4))
            .set_input_type(inputs.feed_forward(16))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    for _ in range(5):
        net.fit(DataSet(rng.randn(64, 16).astype(np.float32),
                        np.eye(4, dtype=np.float32)[
                            rng.randint(0, 4, 64)]))

    try:
        from deeplearning4j_tpu.nn.native_runtime import NativeModelRunner
        runner = NativeModelRunner(net)
    except RuntimeError as e:
        print(f"native PJRT client not available ({e}); skipping native "
              f"serve")
        return None

    with runner:
        for batch in (8, 8, 3):
            x = rng.randn(batch, 16).astype(np.float32)
            y = runner.output(x)
            # one compiled executable per distinct input shape; the
            # repeated batch-8 call reuses its entry (runner-side lookup
            # — the C++ cache's hit counter only moves on re-COMPILES)
            stats = runner.cache_stats()
            print(f"batch {batch}: native output {y.shape}, "
                  f"client cache {stats}")
        assert runner.cache_stats()["entries"] == 2   # 2 shapes, 3 calls
        jax_out = np.asarray(net.output(x))
        np.testing.assert_allclose(y, jax_out, rtol=2e-2, atol=2e-3)
    print("native output matches the JAX path")
    return True


if __name__ == "__main__":
    main()
