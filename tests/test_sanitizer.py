"""Runtime dispatch sanitizer (``tools/analyze/sanitizer.py``) tests:
each contract is SEEDED with a real violation and must be caught —

- recompile after ``end_warmup`` (a new abstract signature reaching an
  already-compiled ``watched_jit``),
- a scenario exceeding its budgets.json dispatch ceiling (with the
  first-occurrence-is-warmup semantics proven on the way),
- a silently-unusable ``donate_argnums`` buffer (output has no
  aliasable slot, so jax drops the donation without a warning),

plus the off-switches: unarmed processes pay nothing, strict mode
raises at the detection site, ``DL4J_TPU_SANITIZE_DONATION=off``
disables the donation audit.
"""

import contextlib
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from deeplearning4j_tpu import monitor
from tools.analyze import sanitizer


@pytest.fixture
def armed(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_SANITIZE", "1")
    monkeypatch.delenv("DL4J_TPU_SANITIZE_STRICT", raising=False)
    monkeypatch.delenv("DL4J_TPU_SANITIZE_BUDGETS", raising=False)
    monkeypatch.delenv("DL4J_TPU_SANITIZE_DONATION", raising=False)
    sanitizer.reset()
    monitor.reset()
    yield
    sanitizer.reset()
    monitor.reset()


def _kinds():
    return sorted(v["kind"] for v in sanitizer.violations())


# --------------------------------------------------------- unarmed

def test_unarmed_is_inert(monkeypatch):
    monkeypatch.delenv("DL4J_TPU_SANITIZE", raising=False)
    sanitizer.reset()
    assert not sanitizer.enabled()
    assert isinstance(monitor.sanitize_scenario("x"),
                      contextlib.nullcontext)
    f = monitor.watched_jit(lambda x: x * 2, name="san_off")
    f(jnp.ones((2,)))
    sanitizer.end_warmup()          # end_warmup alone never violates
    f(jnp.ones((3,)))               # recompile, but nobody is watching
    assert sanitizer.violation_count() == 0


# -------------------------------------- seeded recompile after warmup

def test_recompile_after_warmup_is_caught(armed):
    f = monitor.watched_jit(lambda x: x * 2, name="san_recompile")
    f(jnp.ones((2,), jnp.float32))
    sanitizer.end_warmup()
    f(jnp.ones((2,), jnp.float32))          # cache hit: fine
    assert sanitizer.violation_count() == 0
    f(jnp.ones((3,), jnp.float32))          # seeded shape churn
    assert _kinds() == ["recompile_after_warmup"]
    assert sanitizer.violations()[0]["fn"] == "san_recompile"
    assert monitor.counter(sanitizer.RECOMPILES_TOTAL, "").value(
        fn="san_recompile") == 1
    assert monitor.counter(sanitizer.VIOLATIONS_TOTAL, "").value(
        kind="recompile_after_warmup") == 1


def test_recompile_before_end_warmup_is_free(armed):
    f = monitor.watched_jit(lambda x: x + 1, name="san_warm")
    f(jnp.ones((2,)))
    f(jnp.ones((3,)))               # warmup churn is expected
    assert sanitizer.violation_count() == 0


# ------------------------------------- a store hit is a cache hit

def test_executable_store_hit_counts_as_cache_hit_not_compile(
        armed, monkeypatch, tmp_path):
    """A signature served by the executable store was loaded, not
    derived: one dispatch against the scenario's budget like any other,
    no compile counted, and after warmup no ``recompile_after_warmup``
    (that contract is about shape churn that COMPILES)."""
    from deeplearning4j_tpu.monitor import jit_watch
    from deeplearning4j_tpu.serving.compile_cache import ExecutableStore
    budgets = tmp_path / "budgets.json"
    budgets.write_text(json.dumps(
        {"t.store": {"max_dispatches_per_unit": 1}}))
    monkeypatch.setenv("DL4J_TPU_SANITIZE_BUDGETS", str(budgets))

    def fresh():                    # what a new process builds
        jit_watch.set_executable_store(
            ExecutableStore(str(tmp_path / "executables")))
        return monitor.watched_jit(lambda x: x * 2 + 1, name="san_store",
                                   donate_argnums=(0,), identity="v1")

    writer = fresh()
    writer(jnp.ones((2,), jnp.float32))
    writer(jnp.ones((3,), jnp.float32))
    assert monitor.counter(jit_watch.COMPILES_TOTAL, "").value(
        fn="san_store") == 2        # two misses, compiled and written

    loader = fresh()
    with monitor.sanitize_scenario("t.store"):
        loader(jnp.ones((2,), jnp.float32))     # warmup occurrence: hit
    sanitizer.end_warmup()
    with monitor.sanitize_scenario("t.store"):
        x = jnp.ones((3,), jnp.float32)
        out = loader(x)             # a NEW signature after warmup: hit
    np.testing.assert_array_equal(out, 3.0)
    assert x.is_deleted()           # the donation audit saw it consumed
    assert sanitizer.violation_count() == 0
    assert monitor.counter(jit_watch.COMPILES_TOTAL, "").value(
        fn="san_store") == 2
    assert monitor.counter(jit_watch.CACHE_HITS_TOTAL, "").value(
        fn="san_store") == 2
    assert monitor.counter(jit_watch.STORE_TOTAL, "").value(
        fn="san_store", result="hit") == 2
    with monitor.sanitize_scenario("t.store"):
        loader(jnp.ones((3,), jnp.float32))
        loader(jnp.ones((3,), jnp.float32))     # over budget all the same
    assert _kinds() == ["dispatch_budget"]


# ------------------------------------------ seeded over-budget dispatch

def test_dispatch_budget_exceeded_is_caught(armed, monkeypatch,
                                            tmp_path):
    budgets = tmp_path / "budgets.json"
    budgets.write_text(json.dumps(
        {"t.unit": {"max_dispatches_per_unit": 1}}))
    monkeypatch.setenv("DL4J_TPU_SANITIZE_BUDGETS", str(budgets))
    f = monitor.watched_jit(lambda x: x * 2, name="san_budget")
    x = jnp.ones((2,), jnp.float32)

    with monitor.sanitize_scenario("t.unit"):
        f(x); f(x); f(x)            # first occurrence = warmup: free
    assert sanitizer.violation_count() == 0

    with monitor.sanitize_scenario("t.unit"):
        f(x)                        # within budget
    assert sanitizer.violation_count() == 0

    with monitor.sanitize_scenario("t.unit"):
        f(x); f(x)                  # seeded: fused path degraded
    assert _kinds() == ["dispatch_budget"]
    v = sanitizer.violations()[0]
    assert v["scenario"] == "t.unit"
    assert v["dispatches"] == 2 and v["ceiling"] == 1
    assert monitor.counter(sanitizer.BUDGET_EXCEEDED_TOTAL, "").value(
        scenario="t.unit") == 1


def test_units_and_extra_raise_the_ceiling(armed, monkeypatch,
                                           tmp_path):
    budgets = tmp_path / "budgets.json"
    budgets.write_text(json.dumps(
        {"t.fused": {"max_dispatches_per_unit": 1}}))
    monkeypatch.setenv("DL4J_TPU_SANITIZE_BUDGETS", str(budgets))
    f = monitor.watched_jit(lambda x: x * 2, name="san_units")
    x = jnp.ones((2,), jnp.float32)
    with monitor.sanitize_scenario("t.fused", units=3, extra=1):
        f(x)                        # warmup occurrence
    with monitor.sanitize_scenario("t.fused", units=3, extra=1):
        for _ in range(4):          # 3 units + 1 tail: exactly at ceiling
            f(x)
    assert sanitizer.violation_count() == 0


def test_unbudgeted_scenario_never_violates(armed):
    f = monitor.watched_jit(lambda x: x * 2, name="san_nobudget")
    x = jnp.ones((2,), jnp.float32)
    for _ in range(2):
        with monitor.sanitize_scenario("no.such.budget"):
            f(x); f(x); f(x)
    assert sanitizer.violation_count() == 0


# ------------------------------------------------ seeded donation miss

def test_unusable_donation_is_caught(armed):
    # the output (3,) cannot alias the donated (5,) input, so jax
    # silently keeps both buffers live — the exact regression the
    # audit exists for
    f = monitor.watched_jit(lambda a, b: b * 2.0,
                            name="san_donmiss", donate_argnums=(0,))
    f(jnp.ones((5,), jnp.float32), jnp.ones((3,), jnp.float32))
    assert _kinds() == ["donation_miss"]
    v = sanitizer.violations()[0]
    assert v["fn"] == "san_donmiss"
    assert v["missed"] == 1 and v["total"] == 1
    assert monitor.counter(sanitizer.DONATION_MISSES_TOTAL, "").value(
        fn="san_donmiss") == 1


def test_consumed_donation_is_clean(armed):
    f = monitor.watched_jit(lambda a: a + 1.0, name="san_donok",
                            donate_argnums=(0,))
    a = jnp.ones((4,), jnp.float32)
    f(a)
    assert a.is_deleted()           # donation actually happened
    assert sanitizer.violation_count() == 0


def test_donation_audit_off_switch(armed, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_SANITIZE_DONATION", "off")
    f = monitor.watched_jit(lambda a, b: b * 2.0,
                            name="san_donoff", donate_argnums=(0,))
    f(jnp.ones((5,), jnp.float32), jnp.ones((3,), jnp.float32))
    assert sanitizer.violation_count() == 0


# ------------------------------------------------------- strict mode

def test_strict_mode_raises_at_detection_site(armed, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_SANITIZE_STRICT", "1")
    f = monitor.watched_jit(lambda x: x * 2, name="san_strict")
    f(jnp.ones((2,), jnp.float32))
    sanitizer.end_warmup()
    with pytest.raises(sanitizer.SanitizerViolation,
                       match="recompile_after_warmup"):
        f(jnp.ones((3,), jnp.float32))


# --------------------------------------- product wiring: serving step

def test_serving_step_scenario_stays_within_budget(armed):
    """The real ``SessionCache.step`` path runs armed: one dispatch per
    RNN step, three steps past warmup, zero violations — and the
    scenario was genuinely entered (not vacuous)."""
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import inputs
    from deeplearning4j_tpu.nn.layers.recurrent import (GravesLSTM,
                                                        RnnOutputLayer)
    from deeplearning4j_tpu.serving import SessionCache

    conf = (NeuralNetConfiguration.builder().seed(7)
            .list()
            .layer(GravesLSTM(n_out=8))
            .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(inputs.recurrent(4, 6))
            .build())
    net = MultiLayerNetwork(conf).init()
    cache = SessionCache(net, name="san")
    rng = np.random.RandomState(0)
    for _ in range(3):
        cache.step("s1", rng.randn(2, 4))
    assert sanitizer.state()._seen_scenarios.get("serving.rnn_step",
                                                 0) == 3
    assert sanitizer.violation_count() == 0
