"""A process of its own that builds a small net, initialises it and fits
it through the epoch cache with the executable store on, for the tests
that ask what a SECOND such process derives (nothing: it loads).  Run as
``python second_process.py mln|cg``; ``JAX_COMPILATION_CACHE_DIR`` says
where the caches live.  Prints one JSON object."""

import hashlib
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROGRAMS = {"mln": ("mln.init_held", "mln.init", "mln.gather_train_step"),
            "cg": ("cg.init_held", "cg.init", "cg.gather_train_step")}


def run(kind: str, cache_dir: str, **env) -> dict:
    """One such process; its report."""
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), kind],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(cache_dir), **env),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _main(kind: str) -> None:
    sys.path.insert(0, REPO)
    import numpy as np
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.serving import compile_cache
    compile_cache.enable()
    import jax
    from deeplearning4j_tpu import (DataSet, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf import inputs
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer

    builder = (NeuralNetConfiguration.builder()
               .seed(int(os.environ.get("NET_SEED", "7")))
               .updater("nesterovs").learning_rate(0.1)
               .activation("tanh").weight_init("relu"))
    if kind == "mln":
        net = MultiLayerNetwork(
            builder.list().layer(DenseLayer(n_out=16))
            .layer(OutputLayer(n_out=3))
            .set_input_type(inputs.feed_forward(4)).build())
    else:
        net = ComputationGraph(
            builder.graph_builder().add_inputs("in")
            .add_layer("d", DenseLayer(n_in=4, n_out=16), "in")
            .add_layer("out", OutputLayer(n_in=16, n_out=3), "d")
            .set_outputs("out").build())
    net.init()
    rng = np.random.RandomState(0)
    x = rng.randn(64, 4).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 64)]
    # the CPU backend cannot serialize the sort of a shuffled epoch's
    # permutation ("`LessThan` is not serializable"): unshuffled unless
    # a test asks for exactly that refusal
    net.fit(ListDataSetIterator(
        DataSet(x, y), 16, shuffle=bool(os.environ.get("NET_SHUFFLE")),
        seed=3), epochs=2)
    digest = hashlib.sha256()
    for leaf in jax.tree.leaves((net.params, net.updater_state)):
        digest.update(np.asarray(leaf).tobytes())
    snap = monitor.snapshot()

    def by_fn(counter):
        values = snap.get(counter, {}).get("values", {})
        return {fn: sum(v for labels, v in values.items()
                        if f'fn="{fn}"' in labels)
                for fn in PROGRAMS[kind]}

    store = snap.get("executable_store_total", {}).get("values", {})
    print(json.dumps({
        "results": {fn: sorted(labels.split('result="')[1].rstrip('"}')
                               for labels in store
                               if f'fn="{fn}"' in labels)
                    for fn in PROGRAMS[kind]},
        "trace_s": by_fn("jit_trace_seconds_total"),
        "lower_s": by_fn("jit_lower_seconds_total"),
        "backend_s": by_fn("jit_backend_seconds_total"),
        "load_s": by_fn("executable_store_load_seconds_total"),
        "compiles": by_fn("jit_compiles_total"),
        "score": float(net.score()),
        "stored": compile_cache.stats(
            os.environ["JAX_COMPILATION_CACHE_DIR"])["executables"],
        "params": digest.hexdigest()}))


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    _main(sys.argv[1])
