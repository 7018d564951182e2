"""Device-resident session state tests: N single-timestep calls through
the SessionCache bit-match one full-sequence ``output()``, a session
request costs exactly ONE timestep dispatch (counted through the
compile-watch), TTL/capacity eviction, and the engine/HTTP routing."""

import time

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.conf import inputs
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.recurrent import (GravesLSTM,
                                                    RnnOutputLayer)
from deeplearning4j_tpu.serving import (InferenceEngine, SessionCache,
                                        SessionError)


def _rnn_model(n_in=3, n_out=3, hidden=8, seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .dtype("float64")
            .list()
            .layer(GravesLSTM(n_out=hidden))
            .layer(RnnOutputLayer(n_out=n_out, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(inputs.recurrent(n_in, 6))
            .build())
    return MultiLayerNetwork(conf).init()


def _rnn_graph(seed=11):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .dtype("float64")
            .graph_builder()
            .add_inputs("in")
            .add_layer("lstm", GravesLSTM(n_in=3, n_out=8), "in")
            .add_layer("out", RnnOutputLayer(n_in=8, n_out=2,
                                             activation="softmax",
                                             loss="mcxent"), "lstm")
            .set_outputs("out")
            .build())
    return ComputationGraph(conf).init()


def _step_dispatches(fn="mln.rnn_step"):
    """Total dispatches of the jitted step program = compiles + cache
    hits (the test_ingest.py dispatch-count idiom)."""
    c = monitor.counter("jit_compiles_total", "")
    h = monitor.counter("jit_cache_hits_total", "")
    return c.value(fn=fn) + h.value(fn=fn)


# ---- parity: N single steps == one full sequence -------------------------

def test_session_steps_bitmatch_full_sequence():
    """GravesLSTM in f64: T single-timestep calls through the session
    cache must reproduce one full-sequence output() to the last ulp —
    the recurrence is the same op chain either way."""
    model = _rnn_model()
    cache = SessionCache(model, name="parity")
    rng = np.random.RandomState(0)
    xs = rng.randn(2, 6, 3)
    full = np.asarray(model.output(xs))
    stepped = np.stack([cache.step("s", xs[:, t]) for t in range(6)],
                       axis=1)
    np.testing.assert_allclose(stepped, full, rtol=0, atol=1e-15)


def test_session_chunk_step_matches_full_sequence():
    model = _rnn_model()
    cache = SessionCache(model, name="chunks")
    rng = np.random.RandomState(1)
    xs = rng.randn(3, 6, 3)
    full = np.asarray(model.output(xs))
    a = cache.step("s", xs[:, :4])        # 3-D chunk keeps time axis
    b = cache.step("s", xs[:, 4:])
    np.testing.assert_allclose(np.concatenate([a, b], axis=1), full,
                               rtol=0, atol=1e-15)


def test_graph_session_parity():
    g = _rnn_graph()
    cache = SessionCache(g, name="graph")
    rng = np.random.RandomState(2)
    xs = rng.randn(2, 5, 3)
    full = np.asarray(g.output(xs))
    stepped = np.stack([cache.step("s", xs[:, t]) for t in range(5)],
                       axis=1)
    np.testing.assert_allclose(stepped, full, rtol=0, atol=1e-15)


# ---- the dispatch-count guarantee ----------------------------------------

def test_session_request_is_exactly_one_dispatch():
    """The headline serving-v2 economy: a session request executes ONE
    single-timestep dispatch of the jitted step program — no prefix
    recompute, no second dispatch for state management."""
    model = _rnn_model(seed=13)
    cache = SessionCache(model, name="dispatch")
    rng = np.random.RandomState(3)
    cache.step("s", rng.randn(2, 3))          # shape warm (compile)
    for _ in range(5):
        before = _step_dispatches()
        cache.step("s", rng.randn(2, 3))
        assert _step_dispatches() - before == 1


def test_full_sequence_baseline_dispatch_grows_with_history():
    """The naive alternative the cache replaces: re-running output() over
    the growing history costs one FULL-sequence dispatch per request and
    O(T) device work — bench.py --serve sweeps the collapse."""
    model = _rnn_model(seed=17)
    rng = np.random.RandomState(4)
    history = []
    work = []
    for _ in range(4):
        history.append(rng.randn(1, 1, 3))
        xs = np.concatenate(history, axis=1)
        model.output(xs)
        work.append(xs.shape[1])
    assert work == [1, 2, 3, 4]          # recomputed steps per request


# ---- eviction and guards -------------------------------------------------

def test_ttl_eviction_restarts_from_zero_state():
    model = _rnn_model()
    cache = SessionCache(model, name="ttl", ttl_s=0.05)
    rng = np.random.RandomState(5)
    x = rng.randn(1, 3)
    y0 = cache.step("s", x)
    cache.step("s", rng.randn(1, 3))          # state now non-zero
    time.sleep(0.1)                            # idle past TTL
    y2 = cache.step("s", x)                    # fresh zero-state session
    np.testing.assert_allclose(y2, y0, rtol=0, atol=1e-15)
    vals = monitor.snapshot().get("serving_session_evictions_total",
                                  {}).get("values", {})
    assert any('reason="ttl"' in k for k in vals)


def test_capacity_lru_eviction():
    model = _rnn_model()
    cache = SessionCache(model, name="cap", max_sessions=2, ttl_s=3600)
    rng = np.random.RandomState(6)
    cache.step("a", rng.randn(1, 3))
    cache.step("b", rng.randn(1, 3))
    cache.step("a", rng.randn(1, 3))          # touch: b is now LRU
    cache.step("c", rng.randn(1, 3))          # evicts b
    assert len(cache) == 2
    assert cache.get_carries("b") is None
    assert cache.get_carries("a") is not None


def test_batch_size_change_raises_and_clear_recovers():
    model = _rnn_model()
    cache = SessionCache(model, name="guard")
    rng = np.random.RandomState(7)
    cache.step("s", rng.randn(2, 3))
    with pytest.raises(SessionError):
        cache.step("s", rng.randn(3, 3))
    assert cache.clear("s")
    cache.step("s", rng.randn(3, 3))          # fresh state, new batch


# ---- engine integration --------------------------------------------------

def test_engine_predict_session_route():
    model = _rnn_model(seed=23)
    ref = _rnn_model(seed=23)
    rng = np.random.RandomState(8)
    xs = rng.randn(1, 4, 3)
    with InferenceEngine(model, max_batch_size=4,
                         timestep_buckets=(4, 8),
                         max_latency_ms=1.0, name="sess-eng") as eng:
        outs = np.stack([eng.predict_session("conv", xs[:, t])
                         for t in range(4)], axis=1)
        full = np.asarray(ref.output(xs))
        np.testing.assert_allclose(outs, full, rtol=0, atol=1e-15)
        assert eng.stats()["sessions"]["sessions"] == 1
