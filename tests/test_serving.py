"""Dynamic-batching inference engine tests: bucket ladder math,
padded-vs-unpadded parity (dense and RNN timestep buckets), concurrent
client correctness, AOT recompile accounting against the monitor
registry, backpressure at queue capacity, and the POST /predict HTTP
path on the UI server."""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.conf import inputs
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.recurrent import (GravesLSTM,
                                                    RnnOutputLayer)
from deeplearning4j_tpu.serving import (BucketPolicy, InferenceEngine,
                                        QueueFull, assemble_batch,
                                        batch_ladder)


# Served rows vs ``model.output`` for float32 models: a padded bucket takes
# another matmul tiling than the unpadded call, which reorders float32
# accumulations, so agreement is to a few float32 ulps (eps32 = 1.2e-7),
# not bitwise.  ``chip_smoke.py``'s serve phase states the same bound.
F32_PARITY = dict(rtol=1e-5, atol=1e-6)


def _dense_model(n_in=4, n_out=3, hidden=16, seed=42):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .list()
            .layer(DenseLayer(n_out=hidden))
            .layer(OutputLayer(n_out=n_out))
            .set_input_type(inputs.feed_forward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


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


# ---- bucket ladder / padding math ---------------------------------------

def test_batch_ladder():
    assert batch_ladder(32) == (1, 2, 4, 8, 16, 32)
    assert batch_ladder(24) == (1, 2, 4, 8, 16, 24)
    assert batch_ladder(1) == (1,)


def test_bucket_policy_rounding_and_rejection():
    p = BucketPolicy(max_batch_size=8, timestep_buckets=(4, 8))
    assert p.batch_bucket(1) == 1
    assert p.batch_bucket(3) == 4
    assert p.batch_bucket(8) == 8
    with pytest.raises(ValueError):
        p.batch_bucket(9)
    assert p.time_bucket(2) == 4
    assert p.time_bucket(5) == 8
    with pytest.raises(ValueError):
        p.time_bucket(9)


def test_assemble_batch_pads_and_masks():
    a = np.ones((2, 3, 5))
    b = np.ones((1, 3, 5)) * 2
    padded, mask, rows, waste = assemble_batch([a, b], 4, time_bucket=4)
    assert padded.shape == (4, 4, 5)
    assert mask.shape == (4, 4)
    # real rows carry a ones-mask over real steps, zeros beyond
    np.testing.assert_array_equal(mask[0], [1, 1, 1, 0])
    np.testing.assert_array_equal(mask[3], [0, 0, 0, 0])
    assert rows == 3
    assert 0.0 < waste < 1.0


# ---- padded-vs-unpadded parity ------------------------------------------

def test_dense_padded_parity_per_bucket():
    model = _dense_model()
    rng = np.random.RandomState(0)
    with InferenceEngine(model, max_batch_size=8,
                         max_latency_ms=1.0) as eng:
        eng.warmup((4,))
        for n in (1, 2, 3, 5, 8):
            x = rng.randn(n, 4)
            got = np.asarray(eng.predict(x, timeout=60.0))
            ref = np.asarray(model.output(x))
            assert got.shape == ref.shape
            np.testing.assert_allclose(got, ref, **F32_PARITY)


def test_rnn_timestep_bucket_parity():
    """Sequences padded to a timestep bucket with a ones/zeros mask must
    match the unpadded reference exactly (masked steps pass state through
    and emit zeros)."""
    model = _rnn_model()
    rng = np.random.RandomState(1)
    with InferenceEngine(model, max_batch_size=4,
                         timestep_buckets=(4, 8),
                         max_latency_ms=1.0) as eng:
        for n, t in ((1, 3), (2, 4), (3, 6), (4, 8)):
            x = rng.randn(n, t, 3)
            got = np.asarray(eng.predict(x, timeout=120.0))
            ref = np.asarray(model.output(x))
            assert got.shape == ref.shape     # time axis unpadded back
            # a float64 model: the padded path still agrees far below
            # the float32 bound
            np.testing.assert_allclose(got, ref, atol=1e-8)


def test_rnn_rejects_overlong_sequence():
    model = _rnn_model()
    with InferenceEngine(model, max_batch_size=4,
                         timestep_buckets=(4, 8),
                         max_latency_ms=1.0) as eng:
        with pytest.raises(ValueError):
            eng.predict(np.zeros((1, 9, 3)), timeout=30.0)


def test_graph_model_predict():
    conf = (NeuralNetConfiguration.builder().seed(3)
            .graph_builder()
            .add_inputs("in")
            .add_layer("d", DenseLayer(n_in=5, n_out=8), "in")
            .add_layer("out", OutputLayer(n_in=8, n_out=3), "d")
            .set_outputs("out")
            .build())
    net = ComputationGraph(conf).init()
    x = np.random.RandomState(2).randn(3, 5)
    with InferenceEngine(net, max_batch_size=4,
                         max_latency_ms=1.0) as eng:
        # single-output graphs unwrap to a bare array, like the MLN path
        got = eng.predict(x, timeout=60.0)
        ref = net.output(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   **F32_PARITY)


# ---- concurrency, coalescing, recompiles, backpressure ------------------

def test_concurrent_clients_get_own_rows():
    """Many concurrent callers with distinct inputs must each get back
    exactly their rows, bit-identical to a solo run."""
    model = _dense_model()
    rng = np.random.RandomState(3)
    xs = [rng.randn(rng.randint(1, 4), 4) for _ in range(24)]
    refs = [np.asarray(model.output(x)) for x in xs]
    outs = [None] * len(xs)
    errs = []

    def _batches_total():
        vals = monitor.snapshot().get("serving_batches_total",
                                      {}).get("values", {})
        return sum(vals.values())

    b0 = _batches_total()
    with InferenceEngine(model, max_batch_size=8,
                         max_latency_ms=5.0) as eng:
        eng.warmup((4,))

        def client(i):
            try:
                outs[i] = np.asarray(eng.predict(xs[i], timeout=60.0))
            except Exception as e:     # surfaced after join
                errs.append((i, e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errs
    for got, ref in zip(outs, refs):
        np.testing.assert_allclose(got, ref, **F32_PARITY)
    # coalescing happened: fewer batches than requests
    assert 0 < _batches_total() - b0 < len(xs)


def _compiles_total():
    snap = monitor.snapshot()
    vals = snap.get("serving_bucket_compiles_total", {}).get("values", {})
    return sum(vals.values())


def test_recompile_count_equals_warmed_buckets():
    """Warmup compiles exactly one executable per (bucket x worker) and
    serving traffic afterwards adds none — recompiles are bounded by the
    bucket count, observable through the monitor registry."""
    model = _dense_model()
    before = _compiles_total()
    with InferenceEngine(model, max_batch_size=8,
                         max_latency_ms=1.0, name="recount") as eng:
        warmed = eng.warmup((4,))
        assert warmed == len(batch_ladder(8))
        assert _compiles_total() - before == warmed
        rng = np.random.RandomState(4)
        for n in (1, 2, 3, 4, 5, 6, 7, 8):
            eng.predict(rng.randn(n, 4), timeout=60.0)
        # every request hit a warmed bucket: no new compiles
        assert _compiles_total() - before == warmed
        assert len(eng.bucket_keys()) == warmed


def test_backpressure_queue_full():
    """With the batcher unable to drain (engine constructed but its
    worker stalled by never starting), a bounded queue must reject
    non-blocking submits with QueueFull instead of growing without
    bound."""
    model = _dense_model()
    eng = InferenceEngine(model, max_batch_size=2, queue_capacity=4,
                          max_latency_ms=1000.0)
    eng._running = True           # accept submits without starting threads
    try:
        x = np.zeros((1, 4))
        for _ in range(4):
            eng.predict_async(x, block=False)
        with pytest.raises(QueueFull):
            eng.predict_async(x, block=False)
        with pytest.raises(QueueFull):
            eng.predict_async(x, block=True, timeout=0.05)
    finally:
        eng._running = False
    # the rejection was counted
    snap = monitor.snapshot()
    vals = snap.get("serving_rejected_total", {}).get("values", {})
    assert sum(vals.values()) >= 2


def test_predict_after_stop_raises():
    model = _dense_model()
    eng = InferenceEngine(model, max_batch_size=2)
    eng.start()
    eng.stop()
    with pytest.raises(Exception):
        eng.predict(np.zeros((1, 4)), timeout=5.0)


# ---- POST /predict over HTTP --------------------------------------------

def test_http_predict_roundtrip():
    from deeplearning4j_tpu.ui.server import UIServer
    model = _dense_model()
    srv = UIServer(port=0).start()
    try:
        with InferenceEngine(model, max_batch_size=8,
                             max_latency_ms=1.0) as eng:
            eng.warmup((4,))
            srv.attach_inference(eng)
            x = np.random.RandomState(5).randn(3, 4)
            req = urllib.request.Request(
                "http://127.0.0.1:%d/predict" % srv.port,
                data=json.dumps({"features": x.tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            body = json.loads(
                urllib.request.urlopen(req, timeout=60).read())
            ref = np.asarray(model.output(x))
            np.testing.assert_allclose(np.asarray(body["output"]), ref,
                                       **F32_PARITY)
            # serving metrics visible on the same server's /metrics
            txt = urllib.request.urlopen(
                "http://127.0.0.1:%d/metrics" % srv.port,
                timeout=30).read().decode()
            assert any(line.startswith("serving_request_latency_ms")
                       for line in txt.splitlines())
    finally:
        srv.stop()


def test_http_predict_errors():
    from deeplearning4j_tpu.ui.server import UIServer
    model = _dense_model()
    srv = UIServer(port=0).start()
    try:
        url = "http://127.0.0.1:%d/predict" % srv.port

        def post(payload):
            req = urllib.request.Request(
                url, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            return urllib.request.urlopen(req, timeout=30)

        # no engine attached -> 503
        with pytest.raises(urllib.error.HTTPError) as e:
            post({"features": [[0.0] * 4]})
        assert e.value.code == 503
        with InferenceEngine(model, max_batch_size=4,
                             max_latency_ms=1.0) as eng:
            srv.attach_inference(eng)
            # wrong feature width -> 400
            with pytest.raises(urllib.error.HTTPError) as e:
                post({"features": [[0.0, 1.0]]})
            assert e.value.code == 400
            # missing body keys -> 400
            with pytest.raises(urllib.error.HTTPError) as e:
                post({"wrong": 1})
            assert e.value.code == 400
    finally:
        srv.stop()


# ---- SLO-aware admission control -----------------------------------------

def test_admission_controller_sheds_and_self_heals():
    """Window semantics: p99 over target sheds; once hot samples age out
    of the sliding window, admission reopens (the lifetime histogram
    would stay poisoned forever)."""
    import time

    from deeplearning4j_tpu.serving import SloAdmissionController
    ctl = SloAdmissionController(10.0, window_s=0.2, min_samples=5,
                                 refresh_s=0.0)
    # cold start: no evidence, everything admitted
    assert ctl.should_shed() is None
    for _ in range(20):
        ctl.observe(50.0)                 # 5x over the 10 ms SLO
    assert ctl.should_shed() is not None
    assert ctl.snapshot()["window_p99_ms"] > 10.0
    time.sleep(0.3)                       # hot samples age out
    assert ctl.should_shed() is None


def test_engine_sheds_with_distinct_metric_and_slo_payload():
    from deeplearning4j_tpu.serving import SloShed

    def _shed_total():
        vals = monitor.snapshot().get("serving_shed_total",
                                      {}).get("values", {})
        return sum(vals.values())

    model = _dense_model()
    rng = np.random.RandomState(11)
    with InferenceEngine(model, max_batch_size=4, max_latency_ms=1.0,
                         name="slo-eng", slo_p99_ms=0.0001) as eng:
        eng.warmup((4,))
        before = _shed_total()
        shed = None
        for _ in range(200):
            try:
                eng.predict(rng.randn(1, 4), timeout=30.0)
            except SloShed as e:
                shed = e
                break
        assert shed is not None, "engine never shed under impossible SLO"
        assert shed.slo_p99_ms == 0.0001
        assert shed.observed_p99_ms > shed.slo_p99_ms
        assert _shed_total() > before


def test_queue_full_carries_retry_after():
    model = _dense_model()
    eng = InferenceEngine(model, max_batch_size=2, queue_capacity=2,
                          max_latency_ms=1000.0, name="retry")
    eng._running = True           # accept submits without starting threads
    try:
        x = np.zeros((1, 4))
        for _ in range(2):
            eng.predict_async(x, block=False)
        with pytest.raises(QueueFull) as e:
            eng.predict_async(x, block=False)
        assert 1.0 <= e.value.retry_after_s <= 60.0
    finally:
        eng._running = False


# ---- per-model labels and p999 -------------------------------------------

def test_latency_metric_labeled_per_model_with_p999():
    model = _dense_model()
    with InferenceEngine(model, max_batch_size=4, max_latency_ms=1.0,
                         name="labeled-model") as eng:
        eng.warmup((4,))
        eng.predict(np.random.RandomState(12).randn(2, 4), timeout=60.0)
    snap = monitor.snapshot()
    lat = snap.get("serving_request_latency_ms", {}).get("values", {})
    key = 'model="labeled-model"'
    assert any(key in k for k in lat)
    stats = next(v for k, v in lat.items() if key in k)
    assert "p999" in stats and stats["p999"] >= stats["p99"] >= 0
    for metric in ("serving_batch_fill_ratio",
                   "serving_padding_waste_ratio"):
        vals = snap.get(metric, {}).get("values", {})
        assert any(key in k for k in vals), metric
    # the exposition format shows the 0.999 quantile row
    txt = monitor.prometheus_text()
    assert 'quantile="0.999"' in txt


# ---- HTTP: registry routing, /models, Retry-After, shed payload ----------

def test_http_registry_routing_and_models_endpoint():
    from deeplearning4j_tpu.serving import ModelRegistry
    from deeplearning4j_tpu.ui.server import UIServer
    model_a = _dense_model(seed=31)
    model_b = _rnn_model(seed=32)
    reg = ModelRegistry()
    srv = UIServer(port=0).start()
    try:
        reg.register("dense", InferenceEngine(
            model_a, max_batch_size=4, max_latency_ms=1.0, name="dense"))
        reg.register("rnn", InferenceEngine(
            model_b, max_batch_size=4, timestep_buckets=(4, 8),
            max_latency_ms=1.0, name="rnn"))
        srv.attach_registry(reg)
        base = "http://127.0.0.1:%d" % srv.port

        def post(payload):
            req = urllib.request.Request(
                base + "/predict", data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            return json.loads(urllib.request.urlopen(req, timeout=60)
                              .read())

        x = np.random.RandomState(13).randn(2, 4)
        body = post({"model": "dense", "features": x.tolist()})
        np.testing.assert_allclose(np.asarray(body["output"]),
                                   np.asarray(model_a.output(x)),
                                   atol=1e-6)
        # session routing: two single-step calls chain device state
        ref = _rnn_model(seed=32)
        xs = np.random.RandomState(14).randn(1, 2, 3)
        o0 = post({"model": "rnn", "session": "conv-9",
                   "features": xs[:, 0].tolist()})
        o1 = post({"model": "rnn", "session": "conv-9",
                   "features": xs[:, 1].tolist()})
        full = np.asarray(ref.output(xs))
        np.testing.assert_allclose(
            np.stack([np.asarray(o0["output"]),
                      np.asarray(o1["output"])], axis=1),
            full, atol=1e-12)
        # unknown model -> 404 with the hosted list
        with pytest.raises(urllib.error.HTTPError) as e:
            post({"model": "nope", "features": x.tolist()})
        assert e.value.code == 404
        assert "dense" in json.loads(e.value.read())["models"]
        # /models hosting view
        models = json.loads(urllib.request.urlopen(
            base + "/models", timeout=30).read())
        assert set(models["models"]) == {"dense", "rnn"}
        assert models["models"]["dense"]["resident"] is True
    finally:
        srv.stop()
        reg.stop_all()


def test_http_429_has_retry_after_header():
    from deeplearning4j_tpu.ui.server import UIServer
    model = _dense_model()
    eng = InferenceEngine(model, max_batch_size=2, queue_capacity=1,
                          max_latency_ms=1000.0, name="h429")
    eng._running = True           # stalled engine: queue fills instantly
    srv = UIServer(port=0).start()
    try:
        srv.attach_inference(eng)
        url = "http://127.0.0.1:%d/predict" % srv.port
        eng.predict_async(np.zeros((1, 4)), block=False)   # fill queue
        req = urllib.request.Request(
            url, data=json.dumps({"features": [[0.0] * 4]}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 429
        assert int(e.value.headers["Retry-After"]) >= 1
        assert json.loads(e.value.read())["retry_after_s"] >= 1.0
    finally:
        eng._running = False
        srv.stop()


def test_http_shed_503_reports_slo():
    from deeplearning4j_tpu.ui.server import UIServer
    model = _dense_model()
    srv = UIServer(port=0).start()
    try:
        with InferenceEngine(model, max_batch_size=4, max_latency_ms=1.0,
                             name="h503", slo_p99_ms=0.0001) as eng:
            eng.warmup((4,))
            srv.attach_inference(eng)
            url = "http://127.0.0.1:%d/predict" % srv.port
            payload = json.dumps({"features": [[0.0] * 4]}).encode()
            shed_body = None
            for _ in range(200):
                req = urllib.request.Request(
                    url, data=payload,
                    headers={"Content-Type": "application/json"})
                try:
                    urllib.request.urlopen(req, timeout=30)
                except urllib.error.HTTPError as e:
                    assert e.code == 503
                    shed_body = json.loads(e.read())
                    break
            assert shed_body is not None, "no shed under impossible SLO"
            assert shed_body["shed"] is True
            assert shed_body["slo_p99_ms"] == 0.0001
            assert shed_body["observed_p99_ms"] > 0
    finally:
        srv.stop()
