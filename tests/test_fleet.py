"""Fleet router + executable cache tests: consistent-hash ring
properties (determinism, balance, minimal remap — the affinity-remap
contract a respawn relies on), router pick/failover/route-fraction
logic against fake workers, the persistent compile-cache helpers,
``warm_from_store``, and (slow) a live K=2 subprocess fleet exercising
SIGKILL failover through the HTTP front door."""

import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import inputs
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.serving import compile_cache
from deeplearning4j_tpu.serving.bucketing import BucketPolicy
from deeplearning4j_tpu.serving.engine import InferenceEngine
from deeplearning4j_tpu.serving.fleet import (FLEET_SPECS, FleetRouter,
                                              HashRing, build_fleet_conf)


# ---- hash ring -----------------------------------------------------------

def _ring(nodes, vnodes=64):
    r = HashRing(vnodes=vnodes)
    for n in nodes:
        r.add(n)
    return r


def test_ring_lookup_deterministic_across_instances():
    a = _ring(["w0", "w1", "w2"])
    b = _ring(["w2", "w0", "w1"])      # insertion order must not matter
    keys = [f"conv-{i}" for i in range(200)]
    assert [a.lookup(k) for k in keys] == [b.lookup(k) for k in keys]


def test_ring_balance():
    r = _ring(["w0", "w1", "w2"])
    counts = {"w0": 0, "w1": 0, "w2": 0}
    for i in range(3000):
        counts[r.lookup(f"s{i}")] += 1
    for n, c in counts.items():
        assert c > 3000 * 0.15, (n, counts)


def test_ring_preference_is_failover_order():
    r = _ring(["w0", "w1", "w2"])
    pref = r.preference("conv-7")
    assert sorted(pref) == ["w0", "w1", "w2"]
    assert r.lookup("conv-7") == pref[0]
    assert r.lookup("conv-7", skip=(pref[0],)) == pref[1]


def test_ring_minimal_remap_and_return_home():
    """Removing one node only remaps that node's keys (the survivors'
    sessions never move), and re-adding it — a respawn keeps its rank —
    restores the original mapping exactly, so sessions return home."""
    r = _ring(["w0", "w1", "w2"])
    keys = [f"conv-{i}" for i in range(1000)]
    before = {k: r.lookup(k) for k in keys}
    r.remove("w1")
    after = {k: r.lookup(k) for k in keys}
    for k in keys:
        if before[k] != "w1":
            assert after[k] == before[k]          # survivors unmoved
        else:
            assert after[k] in ("w0", "w2")       # orphans rehomed
    moved = sum(1 for k in keys if before[k] == "w1")
    assert moved > 0
    r.add("w1")
    assert {k: r.lookup(k) for k in keys} == before


# ---- router pick logic (fake workers, no processes) ----------------------

class _FakeWorker:
    def __init__(self, rank):
        self.rank = rank
        self.name = f"w{rank}"
        self.healthy = True
        self.route_fraction = 1.0
        self.served = 0
        self.fail_streak = 0
        self.generation = 0

    def view(self):
        return {"name": self.name, "healthy": self.healthy}


def _router_with_fakes(n=3, **kw):
    router = FleetRouter(k=n, model="mlp", **kw)
    for rank in range(n):
        h = _FakeWorker(rank)
        router._workers[h.name] = h
        router._ring.add(h.name)
    return router


def test_pick_session_affinity_and_failover():
    router = _router_with_fakes(3)
    home = router.pick("conv-1").name
    for _ in range(10):
        assert router.pick("conv-1").name == home
    router._workers[home].healthy = False
    alt = router.pick("conv-1").name
    assert alt != home
    # failover is deterministic too (the ring successor)
    assert router.pick("conv-1").name == alt
    # already-tried candidates are skipped
    third = router.pick("conv-1", tried=(alt,)).name
    assert third not in (home, alt)
    assert router.pick("conv-1", tried=(alt, third)) is None


def test_pick_sessionless_deficit_round_robin():
    router = _router_with_fakes(3)
    picks = [router.pick().name for _ in range(300)]
    for name in ("w0", "w1", "w2"):
        assert 80 <= picks.count(name) <= 120, picks.count(name)


def test_pick_honours_route_fractions():
    router = _router_with_fakes(2)
    router.set_route_fraction("w1", 0.25)
    picks = [router.pick().name for _ in range(100)]
    # w1 carries ~1/5 of traffic at fraction 0.25 vs w0's 1.0
    assert 10 <= picks.count("w1") <= 30, picks.count("w1")
    router.set_route_fraction("w1", 0.0)
    assert all(router.pick().name == "w0" for _ in range(20))
    with pytest.raises(KeyError):
        router.set_route_fraction("nope", 0.5)


def test_handle_predict_fails_over_on_transport_error_only():
    router = _router_with_fakes(3)
    calls = []

    def forward(worker, payload):
        calls.append(worker.name)
        if len(calls) == 1:
            return None, None, {}          # transport failure
        return 200, {"ok": True}, {}

    router._forward = forward
    code, body, _ = router.handle_predict({"session": "conv-1",
                                           "features": [[0.0]]})
    assert code == 200 and body == {"ok": True}
    assert len(calls) == 2 and calls[0] != calls[1]
    # the failed worker is marked down so the next pick skips it
    assert not router._workers[calls[0]].healthy


def test_handle_predict_passes_worker_statuses_through():
    router = _router_with_fakes(2)
    router._forward = lambda w, p: (429, {"error": "shed"},
                                    {"Retry-After": "2"})
    code, body, headers = router.handle_predict({"features": [[0.0]]})
    assert code == 429 and headers["Retry-After"] == "2"


def test_handle_predict_503_when_exhausted():
    router = _router_with_fakes(2)
    router._forward = lambda w, p: (None, None, {})
    code, body, headers = router.handle_predict({"session": "s",
                                                 "features": [[0.0]]})
    assert code == 503 and "Retry-After" in headers
    assert sorted(body["tried"]) == ["w0", "w1"]


# ---- compile cache -------------------------------------------------------

def test_signature_deterministic_and_policy_sensitive():
    conf, kw, _ = build_fleet_conf("mlp")
    pol_a = BucketPolicy(kw["max_batch_size"], kw["timestep_buckets"])
    pol_b = BucketPolicy(kw["max_batch_size"] * 2,
                         kw["timestep_buckets"])
    conf2, _, _ = build_fleet_conf("mlp")
    assert compile_cache.signature(conf, pol_a) == \
        compile_cache.signature(conf2, pol_a)
    assert compile_cache.signature(conf, pol_a) != \
        compile_cache.signature(conf, pol_b)
    other, okw, _ = build_fleet_conf("lstm-small")
    assert compile_cache.signature(conf, pol_a) != \
        compile_cache.signature(
            other, BucketPolicy(okw["max_batch_size"],
                                okw["timestep_buckets"]))


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them: the
    resolver's decisions are process-global and must not leak into the
    rest of the suite."""
    import jax
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda knob, value: calls.__setitem__(knob, value))
    return calls


def test_compile_cache_placed_from_outside_sets_no_directory(
        tmp_path, monkeypatch, config_updates):
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert compile_cache.enable() == placed
    assert os.path.isdir(placed)
    assert "jax_compilation_cache_dir" not in config_updates
    # the knobs that feed JAX's entry key are pinned either way
    assert config_updates == compile_cache._PINNED_CONFIG


def test_compile_cache_unset_env_is_fixed_path_in_checkout(
        monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.enable() == os.path.join(repo,
                                                  ".jax_compile_cache")
    assert config_updates["jax_compilation_cache_dir"] == \
        compile_cache.DEFAULT_CACHE_DIR == compile_cache.enable()


def test_compile_cache_stats_counts_entries_not_atime_sidecars(tmp_path):
    assert compile_cache.stats(str(tmp_path / "absent"))["entries"] == 0
    (tmp_path / "k-cache").write_bytes(b"x" * 10)
    (tmp_path / "k-atime").write_bytes(b"y" * 4)
    s = compile_cache.stats(str(tmp_path))
    assert s["entries"] == 1 and s["bytes"] == 10


def test_spawn_worker_places_cache_through_the_environment(monkeypatch):
    """``cache_root`` reaches the worker as JAX_COMPILATION_CACHE_DIR —
    the variable its resolver honours — not as a flag."""
    from deeplearning4j_tpu.serving import fleet
    seen = {}

    def fake_popen(cmd, env, **kw):
        seen["cmd"], seen["env"] = cmd, env
        return object()

    monkeypatch.setattr(fleet.subprocess, "Popen", fake_popen)
    fleet.spawn_worker(0, model="mlp", cache_root="/some/cache")
    assert seen["env"]["JAX_COMPILATION_CACHE_DIR"] == "/some/cache"
    assert "--cache-root" not in seen["cmd"]


# ---- fleet model spec ----------------------------------------------------

def test_build_fleet_conf_shapes():
    conf, kw, warm = build_fleet_conf("lstm-small")
    s = FLEET_SPECS["lstm-small"]
    # one example is (T, n_in): axis 0 is time
    assert warm == (max(s["timestep_buckets"]), s["n_in"])
    assert kw == {"max_batch_size": s["max_batch"],
                  "timestep_buckets": s["timestep_buckets"]}
    _, mkw, mwarm = build_fleet_conf("mlp")
    assert mwarm == (FLEET_SPECS["mlp"]["n_in"],)
    assert mkw["timestep_buckets"] is None


# ---- warm_from_store -----------------------------------------------------

def _dense(seed=5, n_in=6, n_out=3, hidden=8):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .list()
            .layer(DenseLayer(n_out=hidden))
            .layer(OutputLayer(n_out=n_out))
            .set_input_type(inputs.feed_forward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


def test_warm_from_store_adopts_latest_version(tmp_path):
    from deeplearning4j_tpu.deploy.store import VersionedWeightStore
    store = VersionedWeightStore(str(tmp_path))
    src = _dense(seed=5)
    v = store.publish_model(src, source="test")

    eng = InferenceEngine(_dense(seed=99), max_batch_size=4,
                          max_latency_ms=1.0, name="warmtest").start()
    try:
        assert eng.warm_from_store(store) == v
        x = np.ones((1, 6), np.float32)
        np.testing.assert_allclose(np.asarray(eng.predict(x)),
                                   np.asarray(src.output(x)),
                                   rtol=1e-5, atol=1e-6)
    finally:
        eng.stop()


def test_warm_from_store_empty_store_is_noop(tmp_path):
    from deeplearning4j_tpu.deploy.store import VersionedWeightStore
    eng = InferenceEngine(_dense(seed=1), max_batch_size=4,
                          max_latency_ms=1.0, name="warmempty")
    assert eng.warm_from_store(
        VersionedWeightStore(str(tmp_path / "empty"))) is None


# ---- scale rules ---------------------------------------------------------

def test_fleet_rules_shape():
    from deeplearning4j_tpu.monitor.alerts import fleet_rules
    rules = fleet_rules(slo_p99_ms=80.0, queue_high=16.0)
    names = {r.name for r in rules}
    assert {"fleet_scale_out_p99", "fleet_scale_out_queue",
            "fleet_scale_in"} <= names
    # scale triggers must never gate deployments
    assert not any(r.gate_deploy for r in rules)
    out_p99 = next(r for r in rules if r.name == "fleet_scale_out_p99")
    assert out_p99.metric == "fleet_router_p99_ms"
    assert out_p99.threshold == 80.0


# ---- live fleet (subprocess workers) -------------------------------------

def _post(url, payload, timeout=20.0):
    req = urllib.request.Request(
        url + "/predict", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.getcode(), json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "{}")


@pytest.mark.slow
def test_live_fleet_affinity_sigkill_failover(tmp_path):
    """K=2 real worker processes behind the HTTP front door: session
    affinity holds, SIGKILL of the session's home worker costs zero
    5xx (ring-successor retry), the victim respawns at the same rank,
    and the session keeps answering throughout."""
    router = FleetRouter(2, model="mlp",
                         cache_root=str(tmp_path / "cache"),
                         health_interval_s=0.3)
    router.start()
    ui = router.serve()
    url = f"http://127.0.0.1:{ui.port}"
    spec = FLEET_SPECS["mlp"]
    feats = [[0.1] * spec["n_in"]]
    try:
        sid = "conv-live"
        home = router.pick(sid).name
        for _ in range(5):
            code, _ = _post(url, {"model": "fleet", "session": sid,
                                  "features": feats})
            assert code == 200
            assert router.pick(sid).name == home      # affinity held

        victim = router._workers[home]
        os.kill(victim.proc.pid, signal.SIGKILL)
        codes = [
            _post(url, {"model": "fleet", "session": sid,
                        "features": feats})[0]
            for _ in range(30)]
        assert all(c == 200 for c in codes), codes    # zero 5xx

        deadline = time.time() + 120
        while time.time() < deadline:
            h = router._workers.get(home)
            if h is not None and h.generation > 0 and h.healthy:
                break
            time.sleep(0.3)
        else:
            pytest.fail("worker was not respawned")
        # respawn kept the rank, so the session routes home again
        assert router.pick(sid).name == home
        code, _ = _post(url, {"model": "fleet", "session": sid,
                              "features": feats})
        assert code == 200
        assert router.status()["healthy"] == 2
    finally:
        try:
            ui.stop()
        except Exception:
            pass
        router.stop()


# ---- fleet canary (route-fraction ramp) ----------------------------------

def test_fleet_canary_ramps_then_done():
    from deeplearning4j_tpu.deploy import FleetCanary
    router = _router_with_fakes(2)
    canary = FleetCanary(router, "w1", schedule=(0.1, 0.5, 1.0))
    assert [canary.step() for _ in range(4)] == \
        ["ramp", "ramp", "ramp", "done"]
    assert router._workers["w1"].route_fraction == 1.0
    assert canary.status()["state"] == FleetCanary.DONE


def test_fleet_canary_aborts_on_p99_breach_and_on_unhealthy():
    from deeplearning4j_tpu.deploy import FleetCanary
    router = _router_with_fakes(2)
    canary = FleetCanary(router, "w1", schedule=(0.2, 1.0),
                         max_p99_ms=50.0, fallback_fraction=0.0)
    assert canary.step() == "ramp"
    router._latency_window.extend([100.0] * 10)    # p99 breach
    assert canary.step() == "abort"
    assert router._workers["w1"].route_fraction == 0.0
    assert canary.step() == "abort"                # pinned aborted

    router2 = _router_with_fakes(2)
    canary2 = FleetCanary(router2, "w1", schedule=(0.2, 1.0))
    canary2.step()
    router2._workers["w1"].healthy = False
    assert canary2.step() == "abort"
