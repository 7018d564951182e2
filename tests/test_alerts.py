"""Alert-engine + step-attribution tests (docs/OBSERVABILITY.md
"Alerting" / "Step-time attribution"): rule evaluation against
synthetic registry states, the multi-window burn-rate math, hysteresis
damping in both directions, absence/staleness detection, the
``GET /alerts`` endpoint, the deploy gate hook, and the two seeded
end-to-end paths the ISSUE pins down — a NaN-divergence fit and a
``slow_worker`` fault must each fire/attribute within one evaluation
interval and leave a flight bundle behind."""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.monitor import alerts, attribution
from deeplearning4j_tpu.monitor.alerts import (AlertEngine, FIRING, OK,
                                               PENDING, Rule,
                                               default_rules)
from deeplearning4j_tpu.monitor.attribution import StepAttributor
from deeplearning4j_tpu.monitor.tracing import Tracer
from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
    NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.ui import UIServer


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    """Fresh registry/engine per test; flight bundles land in tmp with
    rate-limiting off so every firing transition can capture one."""
    monkeypatch.setenv("DL4J_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setenv("DL4J_TPU_FLIGHT_MIN_INTERVAL_S", "0")
    monitor.reset()
    faults.reset()
    yield
    monitor.reset()
    faults.reset()


def _net():
    conf = (NeuralNetConfiguration.builder()
            .seed(7).updater("sgd").learning_rate(0.1)
            .weight_init("xavier").list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=16, seed=0, nan=False):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype(np.float32)
    if nan:
        x[:] = np.nan
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)]
    return DataSet(x, y)


def _state(statuses, name):
    return next(s for s in statuses if s["name"] == name)


# ------------------------------------------------------------- rule basics

def test_rule_validation_rejects_unknown_kind_op_objective():
    with pytest.raises(ValueError):
        Rule("x", "gradient", "m")
    with pytest.raises(ValueError):
        Rule("x", "threshold", "m", op="!=")
    with pytest.raises(ValueError):
        Rule("x", "burn_rate", "m", objective=1.0)
    with pytest.raises(ValueError):
        AlertEngine([Rule("dup", "threshold", "m"),
                     Rule("dup", "threshold", "m")])


def test_threshold_rule_fires_on_worst_series():
    g = monitor.gauge("queue_depth", "t")
    g.set(2.0, pool="a")
    g.set(9.0, pool="b")
    eng = AlertEngine([Rule("deep", "threshold", "queue_depth",
                            op=">", threshold=5.0)], interval_s=0.1)
    st = _state(eng.evaluate_once(), "deep")
    assert st["state"] == FIRING
    assert st["value"] == 9.0
    assert "queue_depth" in st["reason"]
    # the engine publishes its own telemetry
    snap = monitor.snapshot()
    assert snap["alerts_firing"]["values"]['{rule="deep"}'] == 1.0
    key = '{rule="deep",state="firing"}'
    assert snap["alert_transitions_total"]["values"][key] == 1
    assert snap["alert_evaluations_total"]["values"][""] == 1


def test_threshold_rule_histogram_field():
    h = monitor.histogram("lat_ms", "t")
    for v in (5.0, 5.0, 5.0, 400.0):
        h.observe(v)
    eng = AlertEngine([Rule("p99", "threshold", "lat_ms", field="p99",
                            op=">", threshold=100.0)], interval_s=0.1)
    assert _state(eng.evaluate_once(), "p99")["state"] == FIRING


def test_increase_rule_preseeded_burst_fires_first_evaluation():
    monitor.counter("rejects_total", "t").inc(7)
    eng = AlertEngine([Rule("storm", "increase", "rejects_total",
                            op=">=", threshold=5.0, window_s=60.0,
                            clear_intervals=1)], interval_s=0.1)
    now = time.time()
    assert _state(eng.evaluate_once(now=now), "storm")["state"] == FIRING
    # quiet counter -> the windowed delta decays to 0 and the rule clears
    later = now + 120.0
    assert _state(eng.evaluate_once(now=later), "storm")["state"] == OK


def test_increase_rule_windowed_delta_uses_ring():
    c = monitor.counter("events_total", "t")
    c.inc(2)
    eng = AlertEngine([Rule("surge", "increase", "events_total",
                            op=">=", threshold=5.0, window_s=60.0)],
                      interval_s=0.1)
    now = time.time()
    assert _state(eng.evaluate_once(now=now), "surge")["state"] == OK
    c.inc(3)    # +3 within the window: 3 < 5 -> still ok
    assert _state(eng.evaluate_once(now=now + 10), "surge")["state"] == OK
    c.inc(4)    # +7 total within 60s of the t0 sample -> fires
    assert _state(eng.evaluate_once(now=now + 20),
                  "surge")["state"] == FIRING


# ---------------------------------------------------------- burn-rate math

def _slo_rule(**kw):
    kw.setdefault("slo_ms", 50.0)
    kw.setdefault("objective", 0.99)
    kw.setdefault("windows", ((60.0, 14.4), (300.0, 6.0)))
    kw.setdefault("min_events", 20)
    return Rule("burn", "burn_rate", "serving_version_latency_ms", **kw)


def test_burn_rate_fires_on_total_breach():
    h = monitor.histogram("serving_version_latency_ms", "t")
    for _ in range(30):
        h.observe(120.0, model="m", version="1")
    eng = AlertEngine([_slo_rule()], interval_s=0.1)
    st = _state(eng.evaluate_once(), "burn")
    assert st["state"] == FIRING
    # every observation bad -> burn = 1.0 / (1 - 0.99) = 100x
    assert st["value"] == pytest.approx(100.0)
    assert "burning error budget" in st["reason"]


def test_burn_rate_quiet_below_slo_and_min_events():
    h = monitor.histogram("serving_version_latency_ms", "t")
    for _ in range(30):
        h.observe(5.0, model="m", version="1")     # all within SLO
    eng = AlertEngine([_slo_rule()], interval_s=0.1)
    assert _state(eng.evaluate_once(), "burn")["state"] == OK

    monitor.reset()
    h = monitor.histogram("serving_version_latency_ms", "t")
    for _ in range(5):
        h.observe(500.0, model="m", version="1")   # bad but < min_events
    eng = AlertEngine([_slo_rule()], interval_s=0.1)
    assert _state(eng.evaluate_once(), "burn")["state"] == OK


def test_burn_rate_requires_every_window():
    """A fast-window blip alone must not page: after the burst ages out
    of the 60s window the fast burn drops below its 14.4x factor even
    though the 300s window still remembers the bad events."""
    h = monitor.histogram("serving_version_latency_ms", "t")
    for _ in range(15):
        h.observe(120.0, model="m", version="1")
    eng = AlertEngine([_slo_rule(min_events=10, clear_intervals=1)],
                      interval_s=0.1)
    now = time.time()
    assert _state(eng.evaluate_once(now=now), "burn")["state"] == FIRING
    for _ in range(200):                            # flood of good events
        h.observe(5.0, model="m", version="1")
    st = _state(eng.evaluate_once(now=now + 90.0), "burn")
    assert st["state"] == OK


# -------------------------------------------------------------- hysteresis

def test_hysteresis_for_and_clear_intervals():
    g = monitor.gauge("flappy", "t")
    g.set(10.0)
    eng = AlertEngine([Rule("flap", "threshold", "flappy", op=">",
                            threshold=5.0, for_intervals=2,
                            clear_intervals=2)], interval_s=0.1)
    assert _state(eng.evaluate_once(), "flap")["state"] == PENDING
    assert _state(eng.evaluate_once(), "flap")["state"] == FIRING
    g.set(0.0)                      # one clean eval is not enough
    assert _state(eng.evaluate_once(), "flap")["state"] == FIRING
    assert _state(eng.evaluate_once(), "flap")["state"] == OK
    # a single-interval blip never reaches firing
    g.set(10.0)
    assert _state(eng.evaluate_once(), "flap")["state"] == PENDING
    g.set(0.0)
    eng.evaluate_once()
    assert _state(eng.evaluate_once(), "flap")["state"] == OK
    key = '{rule="flap",state="firing"}'
    snap = monitor.snapshot()
    assert snap["alert_transitions_total"]["values"][key] == 1


# ----------------------------------------------------- absence / staleness

def test_absence_timestamp_gauge_staleness():
    monitor.gauge("train_health_last_dispatch_ts", "t").set(
        time.time() - 400.0)
    eng = AlertEngine([Rule("stall", "absence",
                            "train_health_last_dispatch_ts",
                            timestamp_gauge=True, stale_after_s=300.0,
                            for_intervals=1)], interval_s=0.1)
    st = _state(eng.evaluate_once(), "stall")
    assert st["state"] == FIRING
    assert st["value"] > 300.0
    monitor.gauge("train_health_last_dispatch_ts", "t").set(time.time())
    eng.evaluate_once()
    assert _state(eng.evaluate_once(), "stall")["state"] == OK


def test_absence_never_fires_before_metric_seen():
    eng = AlertEngine([Rule("gone", "absence", "heartbeat_total",
                            stale_after_s=10.0)], interval_s=0.1)
    now = time.time()
    assert _state(eng.evaluate_once(now=now), "gone")["state"] == OK
    assert _state(eng.evaluate_once(now=now + 100.0),
                  "gone")["state"] == OK


def test_absence_fires_when_seen_metric_goes_silent():
    monitor.counter("heartbeat_total", "t").inc()
    eng = AlertEngine([Rule("gone", "absence", "heartbeat_total",
                            stale_after_s=10.0, clear_intervals=1)],
                      interval_s=0.1)
    now = time.time()
    assert _state(eng.evaluate_once(now=now), "gone")["state"] == OK
    st = _state(eng.evaluate_once(now=now + 20.0), "gone")
    assert st["state"] == FIRING
    assert "no series" in st["reason"]
    monitor.counter("heartbeat_total", "t").inc()     # pulse -> recovers
    assert _state(eng.evaluate_once(now=now + 21.0),
                  "gone")["state"] == OK


# -------------------------------------------------- engine + default rules

def test_default_rules_quiet_on_clean_registry():
    eng = AlertEngine(default_rules(), interval_s=0.1)
    for _ in range(2):
        statuses = eng.evaluate_once()
    assert [s["name"] for s in statuses if s["state"] != OK] == []


def test_background_thread_evaluates_and_stops():
    eng = AlertEngine([Rule("noop", "threshold", "absent_metric",
                            threshold=1.0)], interval_s=0.05)
    assert not eng.running
    eng.start()
    assert eng.running
    deadline = time.time() + 5.0
    while time.time() < deadline:
        snap = monitor.snapshot()
        if snap.get("alert_evaluations_total",
                    {}).get("values", {}).get("", 0) >= 2:
            break
        time.sleep(0.02)
    eng.stop()
    assert not eng.running
    assert monitor.snapshot()["alert_evaluations_total"]["values"][""] >= 2


def test_firing_transition_captures_flight_bundle(tmp_path):
    monitor.gauge("train_health_state", "t").set(1.0)
    eng = AlertEngine(default_rules(), interval_s=0.1)
    st = _state(eng.evaluate_once(), "train_divergence")
    assert st["state"] == FIRING
    assert st["bundle"] is not None and os.path.isdir(st["bundle"])
    meta = json.loads(
        open(os.path.join(st["bundle"], "meta.json")).read())
    assert meta["kind"] == "alert_train_divergence"
    assert meta["detail"]["name"] == "train_divergence"
    assert os.path.exists(os.path.join(st["bundle"], "metrics.json"))


def test_gating_alerts_feed_the_deploy_gate():
    assert alerts.gating_alerts() == []          # no engine yet
    monitor.gauge("train_health_state", "t").set(1.0)
    monitor.counter("lockgraph_cycles_total", "t").inc()
    eng = alerts.engine(interval_s=0.1)          # global engine
    eng.evaluate_once()
    firing = eng.firing()
    assert "train_divergence" in firing
    assert "lockgraph_cycle" in firing
    # only gate_deploy rules block the canary: lockgraph_cycle does not
    assert alerts.gating_alerts() == ["train_divergence"]


# ------------------------------------------------------------ HTTP surface

def test_alerts_http_roundtrip():
    monitor.counter("serving_shed_total", "t").inc(9)
    eng = alerts.engine(interval_s=0.1)
    eng.evaluate_once()
    server = UIServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        body = json.loads(urllib.request.urlopen(base + "/alerts").read())
        assert body["running"] is False
        assert body["interval_s"] == 0.1
        assert body["firing"] == ["serving_shed_storm"]
        by_name = {r["name"]: r for r in body["rules"]}
        assert len(by_name) == len(default_rules())
        assert by_name["serving_shed_storm"]["state"] == "firing"
        assert by_name["serving_shed_storm"]["gate_deploy"] is True
        assert "serving_shed_total" in by_name["serving_shed_storm"]["reason"]
    finally:
        server.stop()


def test_alerts_endpoint_stub_without_engine():
    server = UIServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        body = json.loads(urllib.request.urlopen(base + "/alerts").read())
        assert body == {"running": False, "interval_s": None,
                        "firing": [], "rules": []}
    finally:
        server.stop()


def test_metrics_exposition_self_telemetry_and_trace_drop_header():
    server = UIServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        urllib.request.urlopen(base + "/metrics").read()
        body = urllib.request.urlopen(base + "/metrics").read().decode()
        # the first scrape's cost is visible in the second scrape
        assert "metrics_exposition_seconds" in body
        assert "metrics_exposition_bytes" in body
        resp = urllib.request.urlopen(base + "/trace")
        assert resp.headers["X-Trace-Dropped"] == "0"
    finally:
        server.stop()


# ------------------------------------------------------------- end-to-end

def test_nan_divergence_fit_fires_within_one_interval():
    """The ISSUE acceptance path: a seeded-NaN fit flips
    train_health_state -> the default train_divergence rule fires on the
    very next evaluation, reports via GET /alerts, and leaves a
    bundle."""
    monitor.health.enable(policy="warn")
    eng = alerts.engine(interval_s=0.1)
    eng.evaluate_once()                           # clean baseline
    assert eng.firing() == []
    net = _net()
    net.fit(ListDataSetIterator(_data(nan=True), 16), epochs=1)
    assert monitor.health.state() == "diverged"
    st = _state(eng.evaluate_once(), "train_divergence")
    assert st["state"] == FIRING
    assert st["bundle"] is not None and os.path.isdir(st["bundle"])
    server = UIServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        body = json.loads(urllib.request.urlopen(base + "/alerts").read())
        assert "train_divergence" in body["firing"]
    finally:
        server.stop()


# ------------------------------------------------------- step attribution

def _observe_steps(steps, step_ms, data_ms):
    h_step = monitor.histogram("phase_step_ms", "t")
    h_data = monitor.histogram("phase_data_ms", "t")
    for _ in range(steps):
        h_step.observe(step_ms)
        h_data.observe(data_ms)


def test_attributor_flags_slow_interval_with_dominant_component():
    att = StepAttributor(warmup_ticks=3)
    assert att.tick() is None                     # baseline snapshot only
    for _ in range(4):                            # clean intervals: 12ms/step
        _observe_steps(5, step_ms=10.0, data_ms=2.0)
        rec = att.tick()
        assert rec is not None and not rec["anomaly"]
    _observe_steps(5, step_ms=10.0, data_ms=300.0)
    rec = att.tick()
    assert rec["anomaly"] is True
    assert rec["dominant"] == "data"
    assert rec["per_step_ms"] > rec["threshold_ms"]
    assert "bundle" in rec and os.path.isdir(rec["bundle"])
    snap = monitor.snapshot()
    key = '{component="data"}'
    assert snap["train_step_anomalies_total"]["values"][key] == 1
    # the baseline did NOT absorb the anomaly: a repeat still fires
    _observe_steps(5, step_ms=10.0, data_ms=300.0)
    assert att.tick()["anomaly"] is True
    assert att.anomalies == 2


def test_attributor_quiet_without_steps():
    att = StepAttributor()
    att.tick()
    monitor.counter("unrelated_total", "t").inc()
    assert att.tick() is None                     # no steps -> no record


def _graph():
    from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
    conf = (NeuralNetConfiguration.builder()
            .seed(7).updater("sgd").learning_rate(0.1)
            .weight_init("xavier").graph_builder().add_inputs("in")
            .add_layer("d", DenseLayer(n_in=4, n_out=8, activation="tanh"),
                       "in")
            .add_layer("o", OutputLayer(n_in=8, n_out=3,
                                        activation="softmax",
                                        loss="mcxent"), "d")
            .set_outputs("o").build())
    return ComputationGraph(conf).init()


@pytest.mark.parametrize("build", [_net, _graph], ids=["mln", "graph"])
def test_slow_worker_fault_attributed_to_data_component(build):
    """DL4J_TPU_FAULT_SLOW_WORKER acceptance: an armed straggler stall
    lands in the timed data phase, so the attributor's anomaly names
    ``data`` as the dominant component, whichever container trains
    (the fault point sits once, in ``nn/network.py``)."""
    att = StepAttributor(warmup_ticks=3)
    net = build()
    ds = _data(n=32)
    net.fit(ds)                                   # compile outside baseline
    att.tick()
    for _ in range(5):
        net.fit(ds, epochs=2)
        rec = att.tick()
        assert rec is not None
    faults.configure(slow_worker_ms=500.0)
    try:
        net.fit(ds)
    finally:
        faults.configure()                        # disarm
    rec = att.tick()
    assert rec["anomaly"] is True
    assert rec["dominant"] == "data"
    assert rec["components_ms"]["data"] >= 500.0
    snap = monitor.snapshot()
    assert snap["fault_injections_total"]["values"][
        '{point="slow_worker_ms"}'] >= 1
    # ...and the standing slow_step_anomalies rule sees the counter
    eng = AlertEngine(default_rules(), interval_s=0.1)
    monitor.counter(attribution.ANOMALIES_TOTAL, "t").inc(
        2, component="data")                      # 1 real + 2 = 3 in window
    st = _state(eng.evaluate_once(), "slow_step_anomalies")
    assert st["state"] == FIRING


# ------------------------------------------------------------ tracer drops

def test_tracer_counts_ring_buffer_drops():
    t = Tracer(capacity=4)
    assert t.dropped_count() == 0
    for i in range(10):
        with t.span("s", i=i):
            pass
    assert t.dropped_count() == 6
    snap = monitor.snapshot()
    assert snap["trace_spans_dropped_total"]["values"][""] >= 6
    t.clear()
    assert t.dropped_count() == 0
