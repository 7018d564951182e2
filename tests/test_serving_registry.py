"""Multi-model registry + int8 path tests: affine quantize/decode
round trips (host twin == traceable decode), the measured int8 accuracy
gate against f32 on the iris eval, LRU weight paging under an HBM byte
budget with residency/eviction telemetry, and engine paging safety
(executables survive page-out)."""

import numpy as np
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.iris import iris_dataset
from deeplearning4j_tpu.nn.conf import inputs
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.serving import (InferenceEngine, ModelRegistry,
                                        UnknownModel, dequantize_host,
                                        quantize_leaf, quantize_tree,
                                        tree_nbytes)
from deeplearning4j_tpu.serving.quantize import dequantize_tree


def _dense_model(n_in=4, n_out=3, hidden=16, seed=42):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .list()
            .layer(DenseLayer(n_out=hidden))
            .layer(OutputLayer(n_out=n_out))
            .set_input_type(inputs.feed_forward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


def _engine(seed, hidden=8, **kw):
    kw.setdefault("name", f"m{seed}")
    return InferenceEngine(_dense_model(hidden=hidden, seed=seed),
                           max_batch_size=4, max_latency_ms=1.0, **kw)


# ---- quantization math ---------------------------------------------------

def test_quantize_leaf_round_trip_error_bound():
    rng = np.random.RandomState(0)
    w = rng.randn(32, 16).astype(np.float32) * 3.0
    q, wf = quantize_leaf(w)
    assert q.dtype == np.uint8
    back = wf.decode_host(q)
    # per-tensor affine: worst-case error is half a quantization step
    step = (w.max() - w.min()) / 255.0
    assert float(np.abs(back - w).max()) <= step / 2 + 1e-6


def test_quantize_leaf_constant_and_nonfinite():
    q, wf = quantize_leaf(np.full((8, 8), 2.5, np.float32))
    np.testing.assert_allclose(wf.decode_host(q), 2.5, atol=1e-6)
    with pytest.raises(ValueError):
        quantize_leaf(np.array([[np.nan, 1.0]], np.float32))


def test_tree_nbytes_counts_a_device_array_where_it_lies():
    """``InferenceEngine`` counts its model's bytes when it is built: a
    leaf that says its ``nbytes`` is not fetched to the host for it (9.6
    GB of served weights took 13 s that way), one that does not is
    converted as before."""
    class OnTheDevice:
        nbytes = 96

        def __array__(self, *args, **kwargs):
            raise AssertionError("fetched to be counted")

    tree = {"w": OnTheDevice(), "b": np.zeros((3,), np.float32), "s": 1.5}
    assert tree_nbytes(tree) == 96 + 12 + 8
    import jax.numpy as jnp
    assert tree_nbytes([jnp.zeros((4, 5), jnp.bfloat16)]) == 40


def test_quantize_tree_policy_and_decode_twins():
    """Only rank>=2 leaves above the size floor quantize (biases stay
    f32), and the traceable device decode matches the host twin to a
    single f32 ulp (XLA may reassociate the affine expression)."""
    model = _dense_model(hidden=32)
    qparams, specs = quantize_tree(model.params)
    import jax
    leaves = jax.tree.leaves(qparams)
    assert any(np.asarray(l).dtype == np.uint8 for l in leaves)
    assert any(np.asarray(l).dtype != np.uint8 for l in leaves)  # biases
    assert tree_nbytes(qparams) < tree_nbytes(model.params)
    host = dequantize_host(qparams, specs)
    dev = jax.jit(lambda t: dequantize_tree(t, specs))(qparams)
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(dev)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b, np.asarray(a).dtype),
            rtol=0, atol=5e-7)


# ---- the int8 accuracy gate ----------------------------------------------

def test_int8_matches_f32_top1_on_iris():
    """The stated tolerance for the int8 path, measured on the full
    iris eval: top-1 accuracy delta <= 2% vs the f32 engine, top-1
    agreement >= 97%, softmax outputs within 0.02 absolute."""
    ds = iris_dataset()
    model = _dense_model(seed=5)
    model.fit(ds, epochs=20)
    twin = _dense_model(seed=5)
    twin.fit(ds, epochs=20)
    x = np.asarray(ds.features)
    labels = np.argmax(np.asarray(ds.labels), axis=1)
    p32, p8 = [], []
    with InferenceEngine(model, max_batch_size=32, max_latency_ms=1.0,
                         name="iris-f32") as e32, \
         InferenceEngine(twin, max_batch_size=32, max_latency_ms=1.0,
                         name="iris-i8", quantize="int8") as e8:
        for i in range(0, len(x), 32):
            chunk = x[i:i + 32]
            p32.append(np.asarray(e32.predict(chunk, timeout=60.0)))
            p8.append(np.asarray(e8.predict(chunk, timeout=60.0)))
    y32 = np.concatenate(p32)
    y8 = np.concatenate(p8)
    acc32 = float(np.mean(np.argmax(y32, 1) == labels))
    acc8 = float(np.mean(np.argmax(y8, 1) == labels))
    assert abs(acc32 - acc8) <= 0.02          # the accuracy-delta gate
    agree = float(np.mean(np.argmax(y32, 1) == np.argmax(y8, 1)))
    assert agree >= 0.97
    assert float(np.abs(y32 - y8).max()) < 0.02
    # the economics: the quantized resident tree is materially smaller
    assert e8.model_bytes() < 0.7 * e32.model_bytes()


# ---- engine paging primitives --------------------------------------------

def test_engine_page_out_and_back_is_lossless_and_compile_free():
    model = _dense_model()
    rng = np.random.RandomState(1)
    x = rng.randn(3, 4)

    def compiles():
        vals = monitor.snapshot().get("serving_bucket_compiles_total",
                                      {}).get("values", {})
        return sum(vals.values())

    with InferenceEngine(model, max_batch_size=4, max_latency_ms=1.0,
                         name="pager") as eng:
        eng.warmup((4,))
        ref = np.asarray(eng.predict(x, timeout=60.0))
        assert eng.is_resident()
        c0 = compiles()
        freed = eng.release_device_buffers()
        assert freed == eng.model_bytes()
        assert not eng.is_resident()
        # page back in lazily on the next request: same answer, and the
        # warmed executables were NOT invalidated by the round trip
        got = np.asarray(eng.predict(x, timeout=60.0))
        np.testing.assert_array_equal(got, ref)
        assert eng.is_resident()
        assert compiles() == c0


# ---- registry ------------------------------------------------------------

def test_registry_unknown_model_and_duplicate():
    reg = ModelRegistry()
    reg.register("a", _engine(1))
    try:
        with pytest.raises(UnknownModel):
            reg.get("nope")
        with pytest.raises(UnknownModel):
            reg.predict("nope", np.zeros((1, 4)))
        with pytest.raises(ValueError):
            reg.register("a", _engine(2))
    finally:
        reg.stop_all()


def test_registry_lru_pages_under_budget():
    """3 models under a 2-model budget: registration + traffic must keep
    resident bytes within budget by evicting exactly the LRU model, and
    a request for a paged-out model transparently pages it back in."""
    probe = _engine(99)
    per_model = probe.model_bytes()
    probe.stop()
    budget = 2 * per_model + per_model // 2
    reg = ModelRegistry(hbm_budget_bytes=budget)
    try:
        for s in (1, 2, 3):
            reg.register(f"m{s}", _engine(s))
        assert reg.resident_bytes() <= budget
        st = reg.stats()["models"]
        assert [st[f"m{s}"]["resident"] for s in (1, 2, 3)] == \
            [False, True, True]                   # m1 was the LRU
        rng = np.random.RandomState(2)
        y = reg.predict("m1", rng.randn(2, 4), timeout=60.0)
        assert np.asarray(y).shape == (2, 3)
        st = reg.stats()["models"]
        assert st["m1"]["resident"]
        assert not st["m2"]["resident"]           # new LRU paged out
        assert reg.resident_bytes() <= budget
        vals = monitor.snapshot().get("serving_model_evictions_total",
                                      {}).get("values", {})
        assert sum(vals.values()) >= 2
        vals = monitor.snapshot().get("serving_model_pageins_total",
                                      {}).get("values", {})
        assert sum(vals.values()) >= 4
    finally:
        reg.stop_all()


def test_registry_pinned_model_survives_pressure():
    probe = _engine(98)
    per_model = probe.model_bytes()
    probe.stop()
    reg = ModelRegistry(hbm_budget_bytes=per_model + per_model // 2)
    try:
        reg.register("pinned", _engine(1), pinned=True)
        reg.register("b", _engine(2))
        reg.register("c", _engine(3))
        st = reg.stats()["models"]
        assert st["pinned"]["resident"]           # never evicted
    finally:
        reg.stop_all()


def test_registry_no_budget_keeps_everything_resident():
    reg = ModelRegistry()
    try:
        for s in (1, 2, 3):
            reg.register(f"m{s}", _engine(s))
        assert all(v["resident"]
                   for v in reg.stats()["models"].values())
        assert len(reg) == 3 and "m2" in reg
    finally:
        reg.stop_all()


def test_registry_unregister_releases():
    reg = ModelRegistry()
    try:
        eng = reg.register("a", _engine(1))
        assert eng.is_resident()
        reg.unregister("a")
        assert not eng.is_resident()
        assert "a" not in reg
    finally:
        reg.stop_all()


# ---- deployment x paging -------------------------------------------------

def _bucket_compiles(name):
    total = 0.0
    snap = monitor.snapshot().get("serving_bucket_compiles_total", {})
    for labels, v in snap.get("values", {}).items():
        if f'engine="{name}"' in labels:
            total += v
    return total


def test_registry_model_bytes_counts_staged_canary():
    """A staged canary doubles the model's pageable footprint; promote
    retires the old tree and the footprint drops back to one copy."""
    eng = _engine(31)
    donor = _dense_model(hidden=8, seed=32)
    try:
        per = eng.model_bytes()
        v = eng.stage_weights(donor.params, net_state=donor.net_state)
        assert eng.model_bytes() == 2 * per
        eng.promote(v)
        assert eng.model_bytes() == per
    finally:
        eng.stop()


def test_registry_page_out_preserves_staged_canary():
    """HBM pressure from OTHER tenants pages out a model with a canary
    in flight: the staged tree must survive on host and come back on
    demand — an explicit canary-version request transparently re-pages
    BOTH versions in with zero new compiles."""
    probe = _engine(97)
    per = probe.model_bytes()
    probe.stop()
    reg = ModelRegistry(hbm_budget_bytes=int(2.5 * per))
    try:
        a = reg.register("ma", _engine(41, name="ma"))
        donor = _dense_model(hidden=8, seed=42)
        x = np.random.RandomState(3).randn(2, 4).astype(np.float32)
        ref_active = np.asarray(reg.predict("ma", x, timeout=60.0))
        cv = a.stage_weights(donor.params, net_state=donor.net_state)
        a.set_canary(cv, fraction=0.0)        # staged, not yet routed
        # pressure: two more tenants under a ~2.5-copy budget ->
        # "ma" (the LRU) pages out; its staged tree stays on host
        reg.register("mb", _engine(43, name="mb"))
        reg.register("mc", _engine(44, name="mc"))
        st = reg.stats()["models"]
        assert not st["ma"]["resident"]
        assert a.canary_version == cv          # control plane survives
        compiles0 = _bucket_compiles("ma")
        out = np.asarray(reg.predict("ma", x, timeout=60.0, version=cv))
        np.testing.assert_allclose(out, np.asarray(donor.output(x)),
                                   rtol=1e-5, atol=1e-6)
        assert _bucket_compiles("ma") == compiles0   # pure data motion
        st = reg.stats()["models"]
        assert st["ma"]["resident"]
        assert reg.resident_bytes() <= int(2.5 * per)
        # the active tree came back too, not just the canary
        np.testing.assert_allclose(
            np.asarray(reg.predict("ma", x, timeout=60.0, version=0)),
            ref_active, rtol=1e-5, atol=1e-6)
    finally:
        reg.stop_all()


def test_registry_swap_weights_keeps_budget_accounting():
    """registry.swap_weights: zero-recompile pointer flip through the
    registry, with the byte accounting re-run after the retire."""
    reg = ModelRegistry()
    try:
        eng = reg.register("sw", _engine(51, name="sw"))
        donor = _dense_model(hidden=8, seed=52)
        x = np.random.RandomState(5).randn(2, 4).astype(np.float32)
        np.asarray(reg.predict("sw", x, timeout=60.0))   # warm bucket
        compiles0 = _bucket_compiles("sw")
        v = reg.swap_weights("sw", donor.params,
                             net_state=donor.net_state)
        assert eng.active_version == v
        np.testing.assert_allclose(
            np.asarray(reg.predict("sw", x, timeout=60.0)),
            np.asarray(donor.output(x)), rtol=1e-5, atol=1e-6)
        assert _bucket_compiles("sw") == compiles0
        assert reg.stats()["models"]["sw"]["version"] == v
        # one copy resident again after the retire
        assert eng.model_bytes() == eng.resident_bytes()
    finally:
        reg.stop_all()


# ---- concurrent paging races ---------------------------------------------

def test_engine_concurrent_ensure_resident_single_copy():
    """Two (here: six) threads racing ``ensure_resident`` on a
    paged-out engine must land exactly ONE device copy — resident
    bytes equal one model, never a multiple."""
    import threading
    eng = _engine(91)
    try:
        per = eng.model_bytes()
        eng.ensure_resident()
        eng.release_device_buffers()
        assert not eng.is_resident()
        gate = threading.Barrier(6)
        errs = []

        def page():
            try:
                gate.wait(10)
                eng.ensure_resident()
            except Exception as e:          # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=page) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not errs
        assert eng.is_resident()
        assert eng.resident_bytes() == per
    finally:
        eng.stop()


def test_registry_concurrent_page_in_same_model_under_budget():
    """Eight threads hammering the same paged-out model under a tight
    budget: the registry may only ever hold one resident copy of it
    (no double-counted bytes), the budget holds throughout the race,
    and no request errors."""
    import threading
    probe = _engine(90)
    per = probe.model_bytes()
    probe.stop()
    budget = 2 * per + per // 2
    reg = ModelRegistry(hbm_budget_bytes=budget)
    try:
        for s in (1, 2, 3):
            reg.register(f"m{s}", _engine(s))
        assert not reg.stats()["models"]["m1"]["resident"]  # the LRU
        gate = threading.Barrier(8)
        errs = []
        x = np.zeros((1, 4), np.float32)

        def hit():
            try:
                gate.wait(10)
                for _ in range(5):
                    reg.predict("m1", x, timeout=60.0)
                    assert reg.resident_bytes() <= budget
            except Exception as e:
                errs.append(e)

        threads = [threading.Thread(target=hit) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errs
        eng = reg.get("m1")
        assert eng.is_resident()
        assert eng.resident_bytes() == per          # exactly one copy
        assert reg.resident_bytes() <= budget
    finally:
        reg.stop_all()


def test_registry_concurrent_pressure_never_evicts_pinned():
    """Concurrent traffic to two unpinned models under a budget that
    fits ~1.5 models must page them against each other — and never
    touch the pinned tenant, whose eviction counter stays at zero."""
    import threading
    probe = _engine(89)
    per = probe.model_bytes()
    probe.stop()
    reg = ModelRegistry(hbm_budget_bytes=2 * per + per // 2)
    try:
        reg.register("keep", _engine(1, name="keep"), pinned=True)
        reg.register("b", _engine(2, name="b"))
        reg.register("c", _engine(3, name="c"))
        gate = threading.Barrier(8)
        errs = []
        x = np.zeros((1, 4), np.float32)

        def churn(i):
            name = "b" if i % 2 else "c"
            try:
                gate.wait(10)
                for _ in range(4):
                    reg.predict(name, x, timeout=60.0)
            except Exception as e:
                errs.append(e)

        threads = [threading.Thread(target=churn, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errs
        assert reg.stats()["models"]["keep"]["resident"]
        vals = monitor.snapshot().get("serving_model_evictions_total",
                                      {}).get("values", {})
        assert not any("keep" in str(k) for k in vals)
    finally:
        reg.stop_all()
