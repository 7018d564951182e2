"""Tests for the project-native analyzer (``tools/analyze/``):

- a true-positive fixture corpus that must trip every rule R1-R5,
- a known-clean corpus that must not (false-positive guard),
- the audited-suppression contract (reasonless and unused directives
  are findings; reasoned ones silence exactly their line),
- lockgraph unit tests (seeded A->B / B->A cycle between two threads,
  RLock reentry, zero-overhead-off factory), and
- the satellite regression: the REAL serving + param-server concurrent
  smoke stays lock-order acyclic under ``DL4J_TPU_LOCK_DEBUG=1``.
- the CI mirror: ``run(repo_root)`` reports zero findings at HEAD.
"""

import os
import threading

import numpy as np
import pytest

from tools.analyze import lockgraph
from tools.analyze.lint import (check_registry, collect_code_registry,
                                lint_source, run)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------- R1: traced purity

R1_HOT = '''
import time, random, jax
import numpy as np

def _helper(x):
    return x * time.time()          # reachable through step()

@jax.jit
def step(x):
    return _helper(x) + float(x)    # float() on a traced param

def loss(w):
    return w.sum().item()           # host sync

fast_loss = jax.jit(loss)

def body(carry, x):
    return carry + random.random(), x

out = jax.lax.scan(body, 0.0, xs)
'''


def test_r1_trips_on_traced_host_calls():
    fs = lint_source(R1_HOT, "fx.py", rules={"R1"})
    assert _rules(fs) == ["R1"]
    msgs = " ".join(f.message for f in fs)
    assert "time.time" in msgs           # via call graph
    assert "float(x)" in msgs            # host-sync on traced param
    assert ".item()" in msgs             # jit-wrapped by assignment
    assert "random.random" in msgs       # lax.scan body is a root
    assert len(fs) == 4


R1_CLEAN = '''
import time, jax
import numpy as np

def untraced_logger(x):
    return time.time(), float(x)    # host code: fine

@jax.jit
def step(x):
    shape = np.prod(x.shape)        # trace-time static math: fine
    def callback():                 # nested def is NOT scanned inline
        return time.time()
    return x * shape

def trainer(params, batch):
    t0 = time.perf_counter()        # around the dispatch, not in it
    out = step(batch)
    return out, time.perf_counter() - t0
'''


def test_r1_clean_corpus_silent():
    assert lint_source(R1_CLEAN, "fx.py", rules={"R1"}) == []


# ------------------------------------------------- R2: atomic writes

R2_HOT = '''
import zipfile

def save(path, data):
    with open(path, "w") as fh:     # bare final-file write
        fh.write(data)

def save_zip(path):
    zipfile.ZipFile(path, "w").writestr("a", b"x")
'''

R2_CLEAN = '''
import io, zipfile

def load(path):
    with open(path, "r") as fh:     # reads are fine
        return fh.read()

def append(path, line):
    with open(path, "a") as fh:     # appends are not final-file writes
        fh.write(line)

def to_buffer():
    buf = io.BytesIO()
    zipfile.ZipFile(buf, "w").writestr("a", b"x")   # stream target

def through_helper(path, data):
    with atomic_write(path, "wb") as fh:
        zipfile.ZipFile(fh, "w").writestr("a", data)
'''


def test_r2_trips_on_bare_writes():
    fs = lint_source(R2_HOT, "fx.py", rules={"R2"})
    assert _rules(fs) == ["R2"]
    assert len(fs) == 2


def test_r2_clean_corpus_silent():
    assert lint_source(R2_CLEAN, "fx.py", rules={"R2"}) == []


# -------------------------------------------- R3: blocking under lock

R3_HOT = '''
import subprocess, time

def _recv_exact(sock, n):
    return sock.recv(n)             # blocking primitive

class Client:
    def call(self):
        with self._lock:
            data = _recv_exact(self._sock, 4)   # transitive blocking
        return data

    def drain(self):
        with self._lock:
            item = self.job_queue.get()         # queue-hinted receiver

    def shell(self):
        with self._lock:
            subprocess.run(["ls"])

    def nap(self):
        with self._lock:
            time.sleep(1.0)
'''

R3_CLEAN = '''
import time

class Worker:
    def narrow(self):
        req = self.job_queue.get()      # blocking OUTSIDE the lock
        with self._lock:
            self._state.append(req)     # mutation only under lock
        time.sleep(0.01)

    def span_is_not_a_lock(self):
        with monitor.span("phase"):     # not lock-named: ignored
            time.sleep(0.01)

    def dict_get_is_fine(self):
        with self._lock:
            return self._table.get("k")  # not a queue receiver
'''


def test_r3_trips_on_blocking_under_lock():
    fs = lint_source(R3_HOT, "fx.py", rules={"R3"})
    assert _rules(fs) == ["R3"]
    msgs = " ".join(f.message for f in fs)
    assert "_recv_exact" in msgs        # fixpoint saw through the helper
    assert "job_queue.get" in msgs
    assert "subprocess.run" in msgs
    assert "time.sleep" in msgs
    assert len(fs) == 4


def test_r3_clean_corpus_silent():
    assert lint_source(R3_CLEAN, "fx.py", rules={"R3"}) == []


# ---------------------------------------------- R5: donation safety

R5_HOT = '''
import jax
step = jax.jit(_step, donate_argnums=(0,))

def train(params, batch):
    out = step(params, batch)
    norm = params.sum()             # read after donation
    return out, norm
'''

R5_CLEAN = '''
import jax
step = jax.jit(_step, donate_argnums=(0,))
epoch = jax.jit(_epoch, donate_argnums=tuple(range(2)))

def train(params, batch):
    params = step(params, batch)    # rebound: reads see the NEW buffer
    return params.sum()

def loop(a, b, xs):
    for x in xs:
        a, b = epoch(a, b, x)       # tuple(range(n)) resolved, rebound
    return a, b
'''


def test_r5_trips_on_read_after_donation():
    fs = lint_source(R5_HOT, "fx.py", rules={"R5"})
    assert _rules(fs) == ["R5"]
    assert "params" in fs[0].message


def test_r5_clean_corpus_silent():
    assert lint_source(R5_CLEAN, "fx.py", rules={"R5"}) == []


# ------------------------------------------- suppressions are audited

def test_reasoned_suppression_silences_its_line():
    src = '''
def save(path, data):
    # dl4j-lint: disable=R2 unit-test scratch file, torn writes are harmless
    with open(path, "w") as fh:
        fh.write(data)
'''
    assert lint_source(src, "fx.py", rules={"R2"}) == []


def test_reasonless_suppression_does_not_silence():
    src = '''
def save(path, data):
    with open(path, "w") as fh:  # dl4j-lint: disable=R2
        fh.write(data)
'''
    fs = lint_source(src, "fx.py", rules={"R2"})
    assert _rules(fs) == ["R2", "SUP"]   # finding survives + audited


def test_unused_suppression_is_a_finding():
    src = '''
def clean():
    # dl4j-lint: disable=R3 stale reason for a finding long since fixed
    return 1
'''
    fs = lint_source(src, "fx.py", rules={"R3"})
    assert _rules(fs) == ["SUP"]
    assert "unused" in fs[0].message


def test_directive_in_docstring_is_inert():
    src = '''
def doc():
    """Example: ``# dl4j-lint: disable=R3 some reason``."""
    return 1
'''
    assert lint_source(src, "fx.py", rules={"R3"}) == []


# --------------------------------------------- R4: registry drift

def _write(root, rel, content):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        fh.write(content)


CODE = '''
import os
from . import monitor as _monitor

FLAG = os.environ.get("DL4J_TPU_FOO", "")
PREFIX = "DL4J_TPU_DYN_"

def register():
    _monitor.counter("requests_total", "help").inc()
'''


def test_r4_roundtrip_and_both_drift_directions(tmp_path):
    root = str(tmp_path)
    _write(root, "deeplearning4j_tpu/mod.py", CODE)
    _write(root, "docs/OBSERVABILITY.md", "# Observability\n")

    # no inventory block yet -> one finding pointing at the fix
    fs = check_registry(root)
    assert len(fs) == 1 and "no generated inventory" in fs[0].message

    # --write-registry generates the block; the check then passes
    assert check_registry(root, write=True) == []
    assert check_registry(root) == []
    text = open(os.path.join(root, "docs/OBSERVABILITY.md")).read()
    assert "`DL4J_TPU_FOO`" in text
    assert "`requests_total`" in text

    # code drifts ahead of docs: new env + new metric -> two findings
    _write(root, "deeplearning4j_tpu/new.py",
           'import os\nX = os.environ.get("DL4J_TPU_BAR")\n'
           'from . import monitor as _m\n'
           'def f():\n    _m.gauge("depth", "help").set(1)\n')
    msgs = " ".join(f.message for f in check_registry(root))
    assert "DL4J_TPU_BAR" in msgs and "depth" in msgs

    # docs drift ahead of code: a prose reference to a ghost env var
    assert check_registry(root, write=True) == []
    _write(root, "docs/EXTRA.md",
           "Set `DL4J_TPU_GHOST=1` to enable nothing.\n"
           "`DL4J_TPU_DYN_ANYTHING` is prefix-backed and fine.\n")
    fs = check_registry(root)
    assert len(fs) == 1 and "DL4J_TPU_GHOST" in fs[0].message


def test_repo_registry_collects_lockgraph_metrics():
    envs, metrics, _prefixes = collect_code_registry(REPO_ROOT)
    assert "DL4J_TPU_LOCK_DEBUG" in envs
    assert "DL4J_TPU_LOCK_HOLD_MS" in envs
    assert {"lockgraph_cycles_total", "lockgraph_edges",
            "lockgraph_long_holds_total",
            "lockgraph_blocked_acquires_total"} <= metrics


# ------------------------------------------------- the CI gate mirror

def test_repo_is_clean_at_head():
    """`python -m tools.analyze --strict` exits 0 — same contract,
    in-process, so a regression fails here before CI sees it."""
    findings = run(REPO_ROOT)
    assert findings == [], "\n".join(str(f) for f in findings)


# ---------------------------------------------------- lockgraph unit

@pytest.fixture
def clean_graph():
    lockgraph.reset()
    yield lockgraph.graph()
    lockgraph.reset()


def test_seeded_ab_ba_cycle_detected(clean_graph):
    A = lockgraph.instrumented_lock("t.A")
    B = lockgraph.instrumented_lock("t.B")

    def ab():
        with A:
            with B:
                pass

    def ba():
        with B:
            with A:
                pass

    t1 = threading.Thread(target=ab)
    t1.start(); t1.join()
    t2 = threading.Thread(target=ba)
    t2.start(); t2.join()

    g = clean_graph
    assert g.edges()[("t.A", "t.B")] == 1
    assert g.edges()[("t.B", "t.A")] == 1
    assert len(g.cycles()) == 1
    with pytest.raises(AssertionError, match="t.A"):
        g.assert_acyclic()
    # rotation-invariant dedup: re-running the same interleaving does
    # not report a second cycle
    t3 = threading.Thread(target=ab)
    t3.start(); t3.join()
    assert len(g.cycles()) == 1


def test_rlock_reentry_is_not_an_edge(clean_graph):
    R = lockgraph.instrumented_lock("t.R", rlock=True)
    with R:
        with R:
            pass
    assert clean_graph.edges() == {}
    clean_graph.assert_acyclic()


def test_nested_distinct_names_make_one_edge(clean_graph):
    A = lockgraph.instrumented_lock("t.A")
    B = lockgraph.instrumented_lock("t.B")
    for _ in range(3):
        with A:
            with B:
                pass
    assert clean_graph.edges() == {("t.A", "t.B"): 3}
    clean_graph.assert_acyclic()


def test_factory_is_plain_lock_when_disabled(monkeypatch):
    from deeplearning4j_tpu.monitor.locks import make_lock
    monkeypatch.delenv("DL4J_TPU_LOCK_DEBUG", raising=False)
    lock = make_lock("t.off")
    assert isinstance(lock, type(threading.Lock()))
    monkeypatch.setenv("DL4J_TPU_LOCK_DEBUG", "1")
    lock = make_lock("t.on")
    assert isinstance(lock, lockgraph.InstrumentedLock)
    assert lock.name == "t.on"


# ------------------------------------------------- R6: retrace risk

R6_HOT = '''
import jax

step = jax.jit(_step, static_argnums=(1,))

def once(x):
    return jax.jit(lambda v: v * 2)(x)      # construct-and-call

def per_batch(fns, x):
    for f in fns:
        g = jax.jit(f)                      # factory in loop body
        x = g(x)
    return x

def bad_static(x):
    return step(x, [1, 2, 3])               # non-hashable static arg

def sweep(x, widths):
    out = []
    for w in widths:
        out.append(step(x, w))              # loop-var static arg
    return out

SCALES = {}

def set_scale(k, v):
    SCALES[k] = v

@jax.jit
def scaled(x):
    return x * SCALES["w"]                  # traced closure over mutated
'''


def test_r6_trips_on_all_retrace_shapes():
    fs = lint_source(R6_HOT, "fx.py", rules={"R6"})
    assert _rules(fs) == ["R6"]
    msgs = " ".join(f.message for f in fs)
    assert "constructs and invokes" in msgs
    assert "inside a loop body" in msgs
    assert "non-hashable literal" in msgs
    assert "loop variable" in msgs
    assert "module-level mutable" in msgs
    assert len(fs) == 5


R6_CLEAN = '''
import jax

step = jax.jit(_step, static_argnums=(1,))
gather = jax.jit(lambda d, i: d[i])         # bound once at module scope

def run(x, n):
    return step(x, n)                       # hashable static from caller

def loop(xs):
    out = []
    for x in xs:
        out.append(gather(x, 0))            # reuse of the bound jit
    return out

WIDTHS = (4, 8)                             # immutable: trace-safe

@jax.jit
def scaled(x):
    return x * WIDTHS[0]
'''


def test_r6_clean_corpus_silent():
    assert lint_source(R6_CLEAN, "fx.py", rules={"R6"}) == []


# --------------------------------------- R7: hidden host<->device

R7_HOT = '''
import jax
import jax.numpy as jnp
import numpy as np

step = jax.jit(_step)

def train_log(params, batch):
    loss = step(params, batch)
    return float(loss)                      # cast on a jit output

def norms(w):
    g = jnp.linalg.norm(w)
    return np.asarray(g)                    # full device->host copy

def flag(x):
    m = jnp.max(x)
    if bool(m):                             # blocking truthiness fetch
        return 1
    return 0

def count(x):
    return int(jnp.sum(x))                  # cast directly on jnp call
'''


def test_r7_trips_on_hidden_transfers():
    fs = lint_source(R7_HOT, "fx.py", rules={"R7"})
    assert _rules(fs) == ["R7"]
    msgs = " ".join(f.message for f in fs)
    assert "float(...)" in msgs
    assert "np.asarray(...)" in msgs
    assert "bool(...)" in msgs
    assert "int(...)" in msgs
    assert len(fs) == 4


R7_CLEAN = '''
import jax
import jax.numpy as jnp
import numpy as np

step = jax.jit(_step)

def meta(features):
    x = jnp.asarray(features)
    return int(x.shape[0])                  # metadata read: no transfer

def host_math(a):
    h = np.mean(a)                          # numpy stays on host
    return float(h)

def build():
    return np.asarray([1, 2, 3])            # host literal

@jax.jit
def traced(x):
    return x * 2                            # traced code is R1's domain
'''


def test_r7_clean_corpus_silent():
    assert lint_source(R7_CLEAN, "fx.py", rules={"R7"}) == []


# ----------------------------------- R8: lockset guarded-field drift

R8_HOT = '''
import threading

class Cache:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0                         # __init__ writes are free

    def bump(self):
        with self._lock:
            self._n += 1

    def reset(self):
        self._n = 0                         # bare write, guarded in bump


class Table:
    def grow(self):
        with self._row_lock:
            self._rows = []

    def shrink(self):
        with self._col_lock:
            self._rows = None               # disjoint lock for same field


class Registry:
    def _set_locked(self, v):
        self._val = v                       # guarded by *_locked convention

    def clobber(self):
        self._val = None                    # bare write
'''


def test_r8_trips_on_lockset_drift():
    fs = lint_source(R8_HOT, "fx.py", rules={"R8"})
    assert _rules(fs) == ["R8"]
    msgs = " ".join(f.message for f in fs)
    assert "Cache.reset" in msgs
    assert "disjoint locks" in msgs
    assert "Registry.clobber" in msgs
    assert len(fs) == 3


R8_CLEAN = '''
import threading

class Cache:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
        self._hits = 0                      # written ONLY in __init__

    def bump(self):
        with self._lock:
            self._n += 1

    def drain(self):
        with self._lock:
            self._n = 0                     # same lock everywhere

    def _fast_bump_locked(self):
        self._n += 1                        # caller-holds-lock convention

class Local:
    def compute(self):
        self._scratch = 1                   # never guarded anywhere
        return self._scratch
'''


def test_r8_clean_corpus_silent():
    assert lint_source(R8_CLEAN, "fx.py", rules={"R8"}) == []


# ------------------------------- whole-program cross-module analysis

def test_cross_module_traced_and_blocking_propagation(tmp_path):
    """R1 reaches a helper in ANOTHER module through a jit root's
    imported call, and R3's blocking fixpoint sees through an imported
    socket helper."""
    root = str(tmp_path)
    _write(root, "deeplearning4j_tpu/__init__.py", "")
    _write(root, "deeplearning4j_tpu/util.py", '''
import time

def helper(x):
    return x * time.time()

def recv_all(sock, n):
    return sock.recv(n)
''')
    _write(root, "deeplearning4j_tpu/hot.py", '''
import jax
from deeplearning4j_tpu.util import helper, recv_all

@jax.jit
def step(x):
    return helper(x)

class Client:
    def call(self):
        with self._lock:
            return recv_all(self._sock, 4)
''')
    fs = run(root, rules={"R1", "R3"})
    by_rule = {f.rule: f for f in fs}
    assert set(by_rule) == {"R1", "R3"}
    assert by_rule["R1"].path.endswith("util.py")      # helper is traced
    assert "time.time" in by_rule["R1"].message
    assert by_rule["R3"].path.endswith("hot.py")       # via import
    assert "recv_all" in by_rule["R3"].message


def test_cross_module_class_methods_not_conflated(tmp_path):
    """A self-call resolves against the caller's OWN class: a same-named
    blocking method on an unrelated class must not leak in."""
    root = str(tmp_path)
    _write(root, "deeplearning4j_tpu/__init__.py", "")
    _write(root, "deeplearning4j_tpu/pair.py", '''
class Server:
    def create(self):
        return {}

    def handle(self):
        with self._lock:
            return self.create()        # the LOCAL in-memory create

class NetClient:
    def create(self):
        return self._sock.recv(4)       # blocking, but a different class
''')
    assert run(root, rules={"R3"}) == []


def test_self_call_in_a_base_class_reaches_the_subclasses(tmp_path):
    """A step written once in a base class calls hooks its subclasses
    define in OTHER modules: the traced set rooted at the base's jit
    site reaches every subclass's definition (as a call and as the
    argument of a transformation), through a closure the base returns;
    a subclass calling up resolves to the base; an unrelated class with
    a same-named method stays out."""
    from tools.analyze import callgraph
    root = str(tmp_path)
    _write(root, "deeplearning4j_tpu/__init__.py", "")
    _write(root, "deeplearning4j_tpu/base.py", '''
import jax

class Net:
    def _reg(self, p):
        return p

    def _step_fn(self):
        def body(p, x):
            loss, g = jax.value_and_grad(self._loss_fn)(p, x)
            return self._update(p, g), loss
        return body

    def build(self):
        def step(p, x):
            return self._step_fn()(p, x)
        return jax.jit(step)
''')
    _write(root, "deeplearning4j_tpu/kinds.py", '''
import time
from deeplearning4j_tpu.base import Net

class Chain(Net):
    def _loss_fn(self, p, x):
        return self._reg(p) * time.time()

    def _update(self, p, g):
        return p - g

class Graph(Net):
    def _loss_fn(self, p, x):
        return p * x

    def _update(self, p, g):
        return p - g * time.perf_counter()

class Stranger:
    def _loss_fn(self, p, x):
        return time.time()
''')
    traced = callgraph.load(root).traced()
    assert traced["deeplearning4j_tpu.base"] >= {
        "Net.step", "Net._step_fn", "Net.body", "Net._reg"}
    assert traced["deeplearning4j_tpu.kinds"] == {
        "Chain._loss_fn", "Chain._update", "Graph._loss_fn",
        "Graph._update"}
    fs = run(root, rules={"R1"})
    assert sorted((f.path.rsplit("/", 1)[-1], f.line) for f in fs) == [
        ("kinds.py", 7), ("kinds.py", 17)]
    # one file holding base and subclass: the per-module index alone
    fs = lint_source('''
import time
import jax

class Net:
    def build(self):
        def step(x):
            return self._hook(x)
        return jax.jit(step)

class Kind(Net):
    def _hook(self, x):
        return x * time.time()
''', path="deeplearning4j_tpu/one.py", rules={"R1"})
    assert [f.line for f in fs] == [13]


def test_repo_traced_set_reaches_both_containers():
    """The step is written once in ``nn/network.py``; its jit sites
    still reach both containers' ``_forward`` and ``_loss_fn``, so R1
    keeps guarding them."""
    from tools.analyze import callgraph
    traced = callgraph.load(REPO_ROOT).traced()
    assert {"Network.step", "Network._step_fn", "Network.body",
            "Network._apply_updates", "Network._reg_score"} <= \
        traced["deeplearning4j_tpu.nn.network"]
    assert {"MultiLayerNetwork._forward", "MultiLayerNetwork._loss_fn",
            "MultiLayerNetwork._layer_items"} <= \
        traced["deeplearning4j_tpu.nn.multilayer"]
    assert {"ComputationGraph._forward", "ComputationGraph._loss_fn",
            "ComputationGraph._layer_items"} <= \
        traced["deeplearning4j_tpu.nn.computation_graph"]


# ------------------------------------------------ CLI exit codes

def test_cli_exit_codes(tmp_path):
    """0 = clean, 1 = findings, 2 = the analyzer itself failed — CI
    distinguishes 'dirty code' from 'the gate did not run'."""
    from tools.analyze.__main__ import main as analyze_main

    clean = str(tmp_path / "clean")
    _write(clean, "deeplearning4j_tpu/ok.py", "def f():\n    return 1\n")
    assert analyze_main(["--root", clean, "--rules", "R6"]) == 0

    dirty = str(tmp_path / "dirty")
    _write(dirty, "deeplearning4j_tpu/bad.py",
           "import jax\n\ndef f(x):\n    return jax.jit(lambda v: v)(x)\n")
    assert analyze_main(["--root", dirty, "--rules", "R6"]) == 1

    assert analyze_main(
        ["--root", str(tmp_path / "missing"), "--rules", "R6"]) == 2


# ------------------------- satellite: real concurrent smoke is acyclic

def test_serving_plus_param_server_smoke_stays_acyclic(monkeypatch):
    """The ROADMAP's race-free-serving bar, mechanically: run the real
    inference engine and the real TCP parameter server concurrently
    with every lock instrumented, and require the observed acquisition
    graph to be cycle-free (while actually observing nested holds, so
    the test cannot pass vacuously)."""
    monkeypatch.setenv("DL4J_TPU_LOCK_DEBUG", "1")
    lockgraph.reset()

    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf import inputs
    from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
    from deeplearning4j_tpu.scaleout.param_server import (
        ParameterServer, TcpParameterServer, TcpParameterServerClient)
    from deeplearning4j_tpu.serving import InferenceEngine

    conf = (NeuralNetConfiguration.builder().seed(7)
            .list()
            .layer(DenseLayer(n_out=8))
            .layer(OutputLayer(n_out=3))
            .set_input_type(inputs.feed_forward(4))
            .build())
    model = MultiLayerNetwork(conf).init()

    server = TcpParameterServer(ParameterServer(np.zeros(64)))
    errors = []

    def worker(seed):
        try:
            client = TcpParameterServerClient(server.host, server.port)
            rng = np.random.RandomState(seed)
            for _ in range(5):
                client.push(rng.randn(64) * 1e-3)
                client.pull()
            client.close()
        except Exception as exc:          # pragma: no cover
            errors.append(exc)

    rng = np.random.RandomState(3)
    with InferenceEngine(model, max_batch_size=4,
                         max_latency_ms=1.0) as eng:
        eng.warmup((4,))

        def caller():
            try:
                for _ in range(5):
                    eng.predict(rng.randn(2, 4), timeout=60.0)
            except Exception as exc:      # pragma: no cover
                errors.append(exc)

        threads = [threading.Thread(target=worker, args=(s,))
                   for s in (1, 2)]
        threads += [threading.Thread(target=caller) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    server.close()

    assert errors == []
    g = lockgraph.graph()
    # the dedup lock wraps the sharded chunk apply: nested holds DID
    # happen, so acyclicity below is a real statement
    assert ("scaleout.tcp.dedup", "scaleout.server.chunk") in g.edges()
    g.assert_acyclic()
    lockgraph.reset()


# --------------------------- R3: the retry-after recompute regression

# The shape bench/serving must never reship: recomputing the
# retry-after hint with a blocking queue op while HOLDING the queue
# mutex (the drain thread needs that mutex to make the queue drain —
# the hint computation would stall the very rate it reports).
R3_RETRY_HOT = '''
class Engine:
    def reject(self, item):
        with self._queue_lock:
            depth = self._queue.qsize()
            self._queue.put(item, timeout=0.5)
            return min(60.0, max(1.0, depth / self.drain_rate()))
'''

# The shipped shape: drain-rate and depth snapshotted with NO lock
# held; the arithmetic is pure.
R3_RETRY_CLEAN = '''
class Engine:
    @staticmethod
    def _retry_after(depth, rate):
        if rate <= 0.0:
            return 1.0
        return float(min(60.0, max(1.0, depth / rate)))

    def retry_after_s(self):
        rate = self.drain_rate()
        depth = self._queue.qsize()
        return self._retry_after(depth, rate)
'''


def test_r3_retry_after_recompute_under_queue_lock_trips():
    fs = lint_source(R3_RETRY_HOT, "fx.py", rules={"R3"})
    assert _rules(fs) == ["R3"]
    assert any("put" in f.message for f in fs)


def test_r3_retry_after_snapshot_shape_is_clean():
    assert lint_source(R3_RETRY_CLEAN, "fx.py", rules={"R3"}) == []


def test_r3_shipped_serving_sources_are_clean():
    """The real ``serving.engine`` + ``serving.fleet`` sources pass R3:
    every blocking call in the hot paths happens outside lock scopes
    (or carries an audited suppression)."""
    for rel in ("deeplearning4j_tpu/serving/engine.py",
                "deeplearning4j_tpu/serving/fleet.py"):
        path = os.path.join(REPO_ROOT, rel)
        with open(path) as fh:
            src = fh.read()
        assert lint_source(src, rel, rules={"R3"}) == [], rel
