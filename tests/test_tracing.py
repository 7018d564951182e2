"""Distributed-tracing tests: W3C traceparent propagation, pid-salted
span ids, queue-crossing causality in the serving engine, cross-process
trace stitching over the param-server wire, OpenMetrics exemplars on
``/metrics``, the ``/trace`` endpoint filters, the flight recorder, and
``tools/trace_view.py`` rendering."""

import json
import os
import re
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor.tracing import Tracer
from deeplearning4j_tpu.nn.conf import inputs
from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
    NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import InferenceEngine, SloShed


@pytest.fixture(autouse=True)
def _isolated_monitor():
    monitor.reset()
    yield
    monitor.reset()


@pytest.fixture
def flight_dir(tmp_path, monkeypatch):
    d = tmp_path / "flight"
    monkeypatch.setenv("DL4J_TPU_FLIGHT_DIR", str(d))
    monkeypatch.setenv("DL4J_TPU_FLIGHT_MIN_INTERVAL_S", "0")
    return d


def _dense_model(n_in=4, n_out=3, hidden=8, seed=42):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .list()
            .layer(DenseLayer(n_out=hidden))
            .layer(OutputLayer(n_out=n_out))
            .set_input_type(inputs.feed_forward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


def _mint():
    return monitor.TraceContext(monitor.new_trace_id(),
                                monitor.tracer().next_span_id())


# ---- TraceContext / traceparent ------------------------------------------

def test_traceparent_roundtrip():
    ctx = monitor.TraceContext(0x4BF92F3577B34DA6A3CE929D0E0E4736,
                               0x00F067AA0BA902B7)
    header = ctx.traceparent()
    assert header == ("00-4bf92f3577b34da6a3ce929d0e0e4736-"
                      "00f067aa0ba902b7-01")
    back = monitor.parse_traceparent(header)
    assert back == ctx and back.flags == 1


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-short-00f067aa0ba902b7-01",
    "00-" + "0" * 32 + "-00f067aa0ba902b7-01",       # zero trace id
    "00-4bf92f3577b34da6a3ce929d0e0e4736-" + "0" * 16 + "-01",
    "ff-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01",
    "00-4bf92f3577b34da6a3ce929d0e0e47zz-00f067aa0ba902b7-01",
])
def test_traceparent_rejects_invalid(bad):
    assert monitor.parse_traceparent(bad) is None


def test_span_ids_are_pid_salted_and_counter_stable():
    """Satellite 1: ids embed the pid in the top bits (no aliasing when
    multi-process dumps merge) while the low 40 bits stay a plain
    deterministic counter within a process."""
    t1, t2 = Tracer(), Tracer()
    ids1 = [t1.next_span_id() for _ in range(3)]
    ids2 = [t2.next_span_id() for _ in range(3)]
    assert ids1 == ids2  # deterministic per process
    salt = (os.getpid() & 0xFFFFFF) << 40
    for i, sid in enumerate(ids1):
        assert sid >> 40 == os.getpid() & 0xFFFFFF
        assert sid == salt | (i + 1)


def test_attach_detach_carries_causality_across_a_thread():
    ctx = _mint()
    tok = monitor.attach(ctx)
    try:
        assert monitor.current_context() == ctx
        with monitor.span("work") as sid:
            pass
    finally:
        monitor.detach(tok)
    assert monitor.current_context() is None
    (ev,) = monitor.tracer().events(name="work")
    assert ev["parent"] == ctx.span_id
    assert ev["trace"] == f"{ctx.trace_id:032x}"
    assert ev["id"] == sid
    # detached: a new span starts its own trace again
    with monitor.span("fresh"):
        pass
    (ev2,) = monitor.tracer().events(name="fresh")
    assert ev2["parent"] is None
    assert ev2["trace"] != ev["trace"]


def test_record_span_and_links():
    tr = monitor.tracer()
    a = tr.record_span("a", trace_id=7, ts=1.0, dur_ms=2.0)
    b = tr.record_span("b", trace_id=7, ts=1.0, dur_ms=1.0,
                       links=[a], parent_id=None)
    evs = {e["name"]: e for e in tr.events(trace_id=7)}
    assert evs["b"]["links"] == [a]
    assert evs["a"]["id"] == a and evs["a"]["trace"].endswith("7")
    assert b != a


def test_active_spans_visible_while_open():
    tr = monitor.tracer()
    with monitor.span("long/open"):
        active = tr.active_spans()
        assert [e["name"] for e in active] == ["long/open"]
        assert "dur_ms" not in active[0]
    assert tr.active_spans() == []


# ---- engine: queue-crossing causality ------------------------------------

def test_engine_queue_crossing_causality():
    """The request span must parent under the context active at submit
    time (on the caller's thread) even though the work completes on a
    batch worker thread; segment spans decompose the latency; the batch
    span *links* every coalesced request span."""
    model = _dense_model()
    rng = np.random.RandomState(0)
    ctx = _mint()
    with InferenceEngine(model, max_batch_size=8,
                         max_latency_ms=20.0) as eng:
        eng.warmup((4,))
        monitor.reset()
        tok = monitor.attach(ctx)
        try:
            futs = [eng.predict_async(rng.randn(2, 4)) for _ in range(2)]
            for f in futs:
                f.result(timeout=60.0)
        finally:
            monitor.detach(tok)
    trace_hex = f"{ctx.trace_id:032x}"
    reqs = monitor.tracer().events(trace_id=trace_hex,
                                   name="serve/request")
    assert len(reqs) == 2
    for ev in reqs:
        assert ev["parent"] == ctx.span_id
    req_ids = {ev["id"] for ev in reqs}
    # each request decomposes into the three segments, in its own trace
    for seg in ("serve/queue_wait", "serve/batch_assembly",
                "serve/dispatch"):
        segs = monitor.tracer().events(trace_id=trace_hex, name=seg)
        assert {e["parent"] for e in segs} <= req_ids
        assert len(segs) == 2
    batches = monitor.tracer().events(name="serve/batch")
    linked = set()
    for b in batches:
        linked.update(b.get("links", []))
    assert req_ids <= linked


# ---- HTTP: traceparent on /predict ---------------------------------------

def test_http_predict_traceparent_roundtrip():
    from deeplearning4j_tpu.ui.server import UIServer
    model = _dense_model()
    srv = UIServer(port=0).start()
    try:
        with InferenceEngine(model, max_batch_size=8,
                             max_latency_ms=1.0) as eng:
            eng.warmup((4,))
            srv.attach_inference(eng)
            url = "http://127.0.0.1:%d/predict" % srv.port
            client = _mint()
            req = urllib.request.Request(
                url,
                json.dumps({"features": [[0.1, 0.2, 0.3, 0.4]]}).encode(),
                {"Content-Type": "application/json",
                 "traceparent": client.traceparent()})
            resp = urllib.request.urlopen(req, timeout=60)
            json.loads(resp.read())
            echoed = monitor.parse_traceparent(
                resp.headers.get("traceparent"))
            # same trace as the client, but the SERVER span's id
            assert echoed is not None
            assert echoed.trace_id == client.trace_id
            assert echoed.span_id != client.span_id
            # engine request span parents under the server span
            reqs = monitor.tracer().events(
                trace_id=client.trace_id, name="serve/request")
            assert [e["parent"] for e in reqs] == [echoed.span_id]
            # the server span itself parents under the client header
            deadline = time.time() + 5
            http_evs = []
            while time.time() < deadline and not http_evs:
                http_evs = monitor.tracer().events(
                    trace_id=client.trace_id, name="http/predict")
                time.sleep(0.01)
            assert [e["parent"] for e in http_evs] == [client.span_id]

            # no header -> the server mints a fresh valid trace
            req2 = urllib.request.Request(
                url,
                json.dumps({"features": [[0.1, 0.2, 0.3, 0.4]]}).encode(),
                {"Content-Type": "application/json"})
            resp2 = urllib.request.urlopen(req2, timeout=60)
            resp2.read()
            minted = monitor.parse_traceparent(
                resp2.headers.get("traceparent"))
            assert minted is not None
            assert minted.trace_id != client.trace_id
    finally:
        srv.stop()


# ---- exemplars on /metrics -----------------------------------------------

_EXEMPLAR_RE = re.compile(
    r'_bucket\{[^}]*le="[^"]+"\} \d+ # \{trace_id="[0-9a-f]{32}"\} '
    r'[0-9.e+-]+ \d+\.\d+')


def test_histogram_exemplars_in_exposition():
    with monitor.span("req") :
        monitor.histogram("lat_ms", "t").observe(3.0)
        ctx = monitor.current_context()
    text = monitor.prometheus_text()
    assert f'# {{trace_id="{ctx.trace_id:032x}"}}' in text
    assert _EXEMPLAR_RE.search(text), text
    # cumulative bucket counts: every bucket at/above 3.0 counts it
    assert 'lat_ms_bucket{le="2.5"} 0' in text
    assert 'lat_ms_bucket{le="5"} 1' in text
    assert 'lat_ms_bucket{le="+Inf"} 1' in text


def test_serving_latency_exemplar_served_over_http():
    from deeplearning4j_tpu.ui.server import UIServer
    model = _dense_model()
    srv = UIServer(port=0).start()
    try:
        with InferenceEngine(model, max_batch_size=8,
                             max_latency_ms=1.0) as eng:
            eng.warmup((4,))
            eng.predict(np.zeros((1, 4)), timeout=60.0)
            text = urllib.request.urlopen(
                "http://127.0.0.1:%d/metrics" % srv.port,
                timeout=30).read().decode()
    finally:
        srv.stop()
    lat = [l for l in text.splitlines()
           if l.startswith("serving_request_latency_ms_bucket")
           and "# {" in l]
    assert lat, text
    assert _EXEMPLAR_RE.search(lat[0])


# ---- /trace endpoint ergonomics ------------------------------------------

def test_trace_endpoint_filters_and_chrome_format():
    from deeplearning4j_tpu.ui.server import UIServer
    ctx = _mint()
    with monitor.span("alpha/one", ctx=ctx):
        pass
    with monitor.span("alpha/two", ctx=ctx):
        pass
    with monitor.span("beta/one"):
        pass
    srv = UIServer(port=0).start()
    try:
        base = "http://127.0.0.1:%d/trace" % srv.port

        def get(qs=""):
            return urllib.request.urlopen(base + qs, timeout=30).read() \
                .decode()

        # ?format=chrome is a ready-to-load JSON array
        arr = json.loads(get("?format=chrome"))
        assert isinstance(arr, list) and len(arr) == 3
        assert all(ev["ph"] == "X" for ev in arr)
        # ?name= prefix filter
        arr = json.loads(get("?format=chrome&name=alpha/"))
        assert sorted(ev["name"] for ev in arr) == ["alpha/one",
                                                    "alpha/two"]
        # ?trace_id=
        arr = json.loads(get(
            f"?format=chrome&trace_id={ctx.trace_id:032x}"))
        assert len(arr) == 2
        # ?limit= keeps the newest
        arr = json.loads(get("?format=chrome&limit=1"))
        assert [ev["name"] for ev in arr] == ["beta/one"]
        # default stays JSONL (one event per line)
        lines = [l for l in get("?name=alpha/").splitlines() if l]
        assert len(lines) == 2 and all(
            json.loads(l)["ph"] == "X" for l in lines)
        # bad limit -> 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            get("?limit=nope")
        assert ei.value.code == 400
    finally:
        srv.stop()


# ---- cross-process: param-server push shares one trace_id -----------------

def _spawn_ps_server(dim):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "deeplearning4j_tpu.scaleout.param_server", "--serve",
         "--dim", str(dim)],
        stdout=subprocess.PIPE, text=True, env=env)
    info = json.loads(proc.stdout.readline())
    return proc, (info["host"], info["port"])


def test_param_server_push_stitches_one_trace_across_two_pids():
    """Acceptance: a real subprocess — the push's server-side span lands
    in the SAME 128-bit trace as the client-side span, recorded under a
    different OS pid."""
    from deeplearning4j_tpu.scaleout.param_server import (
        TcpParameterServerClient)
    proc, addr = _spawn_ps_server(dim=4)
    try:
        ctx = _mint()
        tok = monitor.attach(ctx)
        try:
            with TcpParameterServerClient(*addr) as client:
                client.push(np.ones(4))
                np.testing.assert_allclose(client.pull(), np.ones(4))
                dump = client.dump_trace()
        finally:
            monitor.detach(tok)
    finally:
        proc.terminate()
        proc.wait(timeout=10)
    trace_hex = f"{ctx.trace_id:032x}"
    local = monitor.tracer().events(trace_id=trace_hex)
    local_push = [e for e in local
                  if e["name"] == "param_server_client/push"]
    assert len(local_push) == 1
    remote = [e for e in dump["events"] if e.get("trace") == trace_hex]
    remote_push = [e for e in remote
                   if e["name"] == "param_server/push"]
    assert len(remote_push) == 1
    # server span parents under the client-side span: stitched, not
    # merely co-labelled
    assert remote_push[0]["parent"] == local_push[0]["id"]
    pids = {e["pid"] for e in local} | {e["pid"] for e in remote}
    assert os.getpid() in pids and dump["pid"] in pids
    assert len(pids) >= 2
    assert dump["pid"] != os.getpid()
    # pull propagated too
    assert any(e["name"] == "param_server/pull" for e in remote)


# ---- broker record propagation -------------------------------------------

def test_broker_dispatch_joins_callers_trace():
    from deeplearning4j_tpu.streaming.broker import (StreamBroker,
                                                     StreamProducer)
    broker = StreamBroker(port=0)
    try:
        prod = StreamProducer("127.0.0.1", broker.port)
        ctx = _mint()
        tok = monitor.attach(ctx)
        try:
            prod.create_topic("t", partitions=1)
            prod.produce("t", ["r1", "r2"], partition=0)
        finally:
            monitor.detach(tok)
        trace_hex = f"{ctx.trace_id:032x}"
        evs = monitor.tracer().events(trace_id=trace_hex, name="broker/")
        names = sorted(e["name"] for e in evs)
        assert names == ["broker/create_topic", "broker/produce"]
        assert all(e["parent"] == ctx.span_id for e in evs)
    finally:
        broker.close()


# ---- flight recorder ------------------------------------------------------

def test_flight_recorder_bundle_contents(flight_dir):
    with monitor.span("inflight"):
        monitor.histogram("m_ms", "h").observe(1.0)
        bundle = monitor.record_incident(
            "divergence", {"step": 3}, config={"policy": "abort"})
    assert bundle is not None and os.path.isdir(bundle)
    assert set(os.listdir(bundle)) == {"meta.json", "spans.json",
                                       "metrics.json", "health.json"}
    meta = json.load(open(os.path.join(bundle, "meta.json")))
    assert meta["kind"] == "divergence"
    assert meta["detail"] == {"step": 3}
    assert meta["config"] == {"policy": "abort"}
    assert meta["pid"] == os.getpid()
    spans = json.load(open(os.path.join(bundle, "spans.json")))
    # the still-open span is captured
    assert [e["name"] for e in spans["active"]] == ["inflight"]
    metrics = json.load(open(os.path.join(bundle, "metrics.json")))
    assert "m_ms" in metrics


def test_flight_recorder_bounded_and_rate_limited(flight_dir,
                                                  monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FLIGHT_KEEP", "2")
    assert monitor.record_incident("a") is not None
    assert monitor.record_incident("b") is not None
    assert monitor.record_incident("c") is not None
    kept = os.listdir(flight_dir)
    assert len(kept) == 2
    # rate limit: same kind inside the interval is dropped
    monkeypatch.setenv("DL4J_TPU_FLIGHT_MIN_INTERVAL_S", "3600")
    assert monitor.record_incident("c") is None


def test_flight_recorder_disabled(flight_dir, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FLIGHT_DISABLE", "1")
    assert monitor.record_incident("divergence") is None
    assert not flight_dir.exists()


def test_slo_shed_records_incident(flight_dir):
    """Acceptance: a seeded SLO shed produces a bundle."""
    model = _dense_model()
    eng = InferenceEngine(model, max_batch_size=4, slo_p99_ms=1.0).start()
    try:
        for _ in range(64):   # seed the admission window over the SLO
            eng._admission.observe(100.0)
        with pytest.raises(SloShed):
            eng.predict(np.zeros((1, 4)), timeout=10.0)
    finally:
        eng.stop()
    bundles = [d for d in os.listdir(flight_dir) if "slo_shed" in d]
    assert len(bundles) == 1
    meta = json.load(open(flight_dir / bundles[0] / "meta.json"))
    assert meta["detail"]["observed_p99_ms"] >= 1.0


def test_checkpoint_corruption_records_incident(flight_dir, tmp_path):
    from deeplearning4j_tpu.resilience.checkpoint import (
        CheckpointCorruptError, verify_checkpoint)
    bad = tmp_path / "checkpoint_000001.dl4jtpu.zip"
    bad.write_bytes(b"this is not a checkpoint zip")
    with pytest.raises(CheckpointCorruptError):
        verify_checkpoint(str(bad))
    bundles = [d for d in os.listdir(flight_dir)
               if "checkpoint_corrupt" in d]
    assert len(bundles) == 1


# ---- trace_view -----------------------------------------------------------

def test_trace_view_renders_bundle_and_dumps(flight_dir, tmp_path,
                                             capsys):
    from tools import trace_view
    with monitor.span("outer"):
        with monitor.span("inner"):
            pass
        bundle = monitor.record_incident("queue_full", {})
    out = tmp_path / "out.trace.json"
    assert trace_view.main([bundle, "-o", str(out)]) == 0
    events = json.loads(out.read_text())
    assert isinstance(events, list)
    names = {e["name"] for e in events}
    assert {"inner", "outer"} <= names
    # "outer" was still open at dump time -> rendered as unfinished
    open_evs = [e for e in events if e["args"].get("unfinished")]
    assert [e["name"] for e in open_evs] == ["outer"]
    assert all(ev["ph"] == "X" and "ts" in ev and "pid" in ev
               for ev in events)

    capsys.readouterr()  # drop the first call's summary line

    # a /trace JSONL dump converts too
    dump = tmp_path / "trace.jsonl"
    dump.write_text(monitor.trace_jsonl())
    assert trace_view.main([str(dump), "-o", "-"]) == 0
    arr = json.loads(capsys.readouterr().out)
    assert isinstance(arr, list) and arr

    # garbage exits non-zero
    junk = tmp_path / "junk.json"
    junk.write_text("{\"nope\": 1}")
    assert trace_view.main([str(junk), "-o", "-"]) == 1


# ---- ProfilerListener hardening ------------------------------------------

def test_profiler_listener_double_stop_guard(tmp_path, monkeypatch):
    import jax

    from deeplearning4j_tpu.optimize.listeners.listeners import (
        ProfilerListener)
    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, **kw: calls.__setitem__("start", calls["start"] + 1))

    def _stop():
        calls["stop"] += 1
        if calls["stop"] > 1:
            raise RuntimeError("profiling not started")
    monkeypatch.setattr(jax.profiler, "stop_trace", _stop)

    pl = ProfilerListener(str(tmp_path), start_iteration=0,
                          end_iteration=10)
    pl.iteration_done(None, 0)          # opens the capture window
    pl.stop()
    pl.stop()                            # idempotent: no second call
    assert calls == {"start": 1, "stop": 1}
    (ev,) = monitor.tracer().events(name="profiler/capture")
    assert ev["attrs"]["log_dir"] == str(tmp_path)

    # error path: a stop whose profiler call raises is swallowed and
    # still closes the window
    pl._trace.start()
    pl.stop()                            # raises inside, guarded
    assert pl._trace.open is False
    assert calls == {"start": 2, "stop": 2}
    assert pl.device_report() is None
