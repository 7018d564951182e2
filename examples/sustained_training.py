"""Sustained real-training proof through the PRODUCT API (not the bench
harness): LeNet-MNIST to high test accuracy, and a multi-thousand-step
ResNet-50 run — both with PerformanceListener + CheckpointListener +
StatsListener attached, so the full loop (listeners, checkpointing,
stats storage, eval) is exercised at real scale.

Reference analogue: the dl4j-examples training mains driving
``MultiLayerNetwork.fit`` with listeners attached
(``optimize/listeners/PerformanceListener.java:99-102`` is the metric
surface being exercised).

Prints one JSON line per config:
    {"config": ..., "epochs"/"steps": ..., "wall_s": ...,
     "samples_per_sec": ..., "accuracy": ..., "checkpoints": N,
     "stats_reports": N}
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))   # run from anywhere

import numpy as np


def _bf16_if_tpu():
    # shared backend-default from the precision module (DL4J_TPU_PRECISION
    # aware) — see docs/PERFORMANCE.md
    from deeplearning4j_tpu.nn.precision import default_compute_dtype
    return default_compute_dtype()


def _listeners(ckpt_dir, every_iter, stats_freq=50):
    from deeplearning4j_tpu.optimize.listeners.listeners import (
        CheckpointListener, PerformanceListener)
    from deeplearning4j_tpu.ui.stats_listener import StatsListener
    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
    storage = InMemoryStatsStorage()
    stats = StatsListener(storage, update_frequency=stats_freq)
    perf = PerformanceListener(frequency=100)
    ckpt = CheckpointListener(ckpt_dir,
                              save_every_n_iterations=every_iter,
                              keep_last=3)
    return [perf, ckpt, stats], storage, ckpt


def sustained_lenet(epochs: int = 15, batch: int = 256,
                    examples: int = 60000, target_acc: float = 0.99,
                    ckpt_every: int = 500, stats_freq: int = 50):
    """Full-MNIST LeNet through fit(iterator) (device epoch cache) to
    >= target accuracy, with the listener stack attached."""
    from deeplearning4j_tpu.datasets.mnist import MnistDataSetIterator
    from deeplearning4j_tpu.models.lenet import lenet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net = MultiLayerNetwork(lenet(compute_dtype=_bf16_if_tpu())).init()
    with tempfile.TemporaryDirectory() as ckpt_dir:
        listeners, storage, ckpt = _listeners(ckpt_dir,
                                              every_iter=ckpt_every,
                                              stats_freq=stats_freq)
        net.set_listeners(*listeners)
        it = MnistDataSetIterator(batch, examples)
        test = MnistDataSetIterator(500, 10000, train=False)

        t0 = time.perf_counter()
        net.fit(it, epochs=epochs)
        wall = time.perf_counter() - t0
        acc = net.evaluate(test).accuracy()
        n_ckpt = len(ckpt.saved)
        n_reports = sum(storage.num_update_records(s)
                        for s in storage.list_session_ids())
    return {"config": "lenet_mnist_sustained", "epochs": epochs,
            "iterations": net.iteration, "wall_s": round(wall, 2),
            "samples_per_sec": round(epochs * examples / wall, 1),
            "accuracy": round(float(acc), 4),
            "target_acc": target_acc, "reached": bool(acc >= target_acc),
            "checkpoints": n_ckpt, "stats_updates": n_reports}


def sustained_resnet(steps: int = 3000, batch: int = 128,
                     examples: int = 1280):
    """Multi-thousand-step ResNet-50 on synthetic ImageNet-shaped data
    through the graph fit(iterator) epoch cache, listener stack
    attached.  Features are stored bf16 on host when the chip computes
    in bf16 — the step's first action is the same cast, and bf16
    halves the corpus upload (examples=1280 halves it again at 10
    steps/epoch)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models.resnet import resnet50
    from deeplearning4j_tpu.nn.computation_graph import ComputationGraph

    net = ComputationGraph(resnet50(compute_dtype=_bf16_if_tpu())).init()
    rng = np.random.RandomState(0)
    f = rng.rand(examples, 224, 224, 3).astype(np.float32)
    if _bf16_if_tpu():
        import ml_dtypes
        f = f.astype(ml_dtypes.bfloat16)
    l = np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, examples)]
    it = ListDataSetIterator(DataSet(f, l), batch)
    steps_per_epoch = examples // batch
    epochs = max(1, steps // steps_per_epoch)

    with tempfile.TemporaryDirectory() as ckpt_dir:
        # A stats post on ResNet is a 102 MB param fetch plus a histogram
        # pass over 25.5M params; 500-iteration frequency keeps the
        # listener exercised without dominating wall
        listeners, storage, ckpt = _listeners(ckpt_dir, every_iter=1000,
                                              stats_freq=500)
        net.set_listeners(*listeners)
        print("# resnet warmup (upload + compile)...", file=sys.stderr,
              flush=True)
        net.fit(it, epochs=1)          # warmup epoch: compile + upload
        first_score = float(net.score())
        print("# resnet warmup done", file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        net.fit(it, epochs=epochs)
        wall = time.perf_counter() - t0
        final_score = float(net.score())
        n_ckpt = len(ckpt.saved)
        n_reports = sum(storage.num_update_records(s)
                        for s in storage.list_session_ids())
    return {"config": "resnet50_sustained", "steps": net.iteration,
            "timed_steps": epochs * steps_per_epoch,
            "wall_s": round(wall, 2),
            "samples_per_sec": round(
                epochs * steps_per_epoch * batch / wall, 1),
            "first_score": round(first_score, 4),
            "final_score": round(final_score, 4),
            "score_decreased": bool(final_score < first_score),
            "checkpoints": n_ckpt, "stats_updates": n_reports}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    which = argv[0] if argv else "both"
    kwargs = dict(kv.split("=") for kv in argv[1:])
    kwargs = {k: int(v) for k, v in kwargs.items()}
    if which in ("lenet", "both"):
        print(json.dumps(sustained_lenet(
            **{k: v for k, v in kwargs.items()
               if k in ("epochs", "batch", "examples")})), flush=True)
    if which in ("resnet", "both"):
        print(json.dumps(sustained_resnet(
            **{k: v for k, v in kwargs.items()
               if k in ("steps", "batch", "examples")})), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
