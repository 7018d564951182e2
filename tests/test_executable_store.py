"""The executable store (``serving/compile_cache.ExecutableStore``) under
``monitor.watched_jit(..., identity=...)``: asked before tracing, a hit
runs the loaded executable (bit-identical outputs, donation kept, no
trace), every ingredient of the key and of the header decides, a broken
entry is a miss that is overwritten, two writers leave one whole entry,
and a site that gives no identity never touches the directory."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import jit_watch
from deeplearning4j_tpu.serving import compile_cache
from deeplearning4j_tpu.serving.compile_cache import ExecutableStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES = []


def _new_step():
    """A new function object each time, as a new process has: JAX's own
    in-process trace cache then plays no part."""
    def _step(params, it, x, n, scale=None):
        TRACES.append(n)                # runs when traced, never on a hit
        for _ in range(n):
            params = jax.tree.map(lambda a: a * 1.5 + x.sum(), params)
        return params, {"score": x.sum() + it}
    return _step


def _args(rows=6):
    params = [{"W": jnp.ones((4, 4)), "b": jnp.zeros((4,))}, {}]
    return params, 3, jnp.arange(float(rows))


def _watched(identity="conf-1", name="toy.step"):
    return monitor.watched_jit(_new_step(), name=name,
                               static_argnums=(3,), donate_argnums=(0,),
                               identity=identity)


def _results(fn="toy.step"):
    values = monitor.snapshot().get(jit_watch.STORE_TOTAL, {}).get(
        "values", {})
    return {labels.split('result="')[1].rstrip('"}'): int(v)
            for labels, v in values.items() if f'fn="{fn}"' in labels}


def _counter(name, fn="toy.step"):
    values = monitor.snapshot().get(name, {}).get("values", {})
    return sum(v for labels, v in values.items() if f'fn="{fn}"' in labels)


@pytest.fixture
def package(tmp_path):
    root = tmp_path / "pkg"
    (root / "sub").mkdir(parents=True)
    (root / "a.py").write_text("A = 1\n")
    (root / "sub" / "b.py").write_text("B = 2\n")
    (root / "notes.txt").write_text("not a source file\n")
    return root


@pytest.fixture
def install(tmp_path, package):
    """``install()`` puts a NEW store on the same directory into
    ``jit_watch``: what a fresh process's ``enable()`` does."""
    monitor.reset()
    del TRACES[:]

    def install():
        store = ExecutableStore(str(tmp_path / "executables"),
                                package_root=str(package))
        jit_watch.set_executable_store(store)
        return store

    yield install
    monitor.reset()


def _entries(store):
    return sorted(os.listdir(store.directory)) \
        if os.path.isdir(store.directory) else []


# ------------------------------------------------------------- the hit
def test_hit_is_bit_identical_donates_and_traces_nothing(install):
    store = install()
    first = _watched()
    params, it, x = _args()
    out_a = first(params, it, x, 2)
    assert _results() == {"miss_absent": 1, "written": 1}
    assert TRACES == [2] and len(_entries(store)) == 1
    assert params[0]["W"].is_deleted()          # the miss donates too
    assert _counter(jit_watch.COMPILES_TOTAL) == 1
    flops = monitor.snapshot()[jit_watch.XLA_FLOPS]["values"]

    monitor.reset()
    install()                                   # "a second process"
    second = _watched()
    params, it, x = _args()
    out_b = second(params, it, x, 2)
    assert _results() == {"hit": 1}
    assert TRACES == [2]                        # nothing was traced
    assert params[0]["W"].is_deleted()          # donation is kept
    for a, b in zip(jax.tree.leaves(out_a), jax.tree.leaves(out_b)):
        assert a.dtype == b.dtype
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    # a hit is a cache hit, not a compile; its load counts as backend
    assert _counter(jit_watch.COMPILES_TOTAL) == 0
    assert _counter(jit_watch.CACHE_HITS_TOTAL) == 1
    load_s = _counter(jit_watch.STORE_LOAD_SECONDS)
    assert load_s > 0
    assert _counter(jit_watch.BACKEND_SECONDS) == pytest.approx(load_s)
    assert _counter(jit_watch.TRACE_SECONDS) == 0
    assert _counter(jit_watch.LOWER_SECONDS) == 0
    # the cost gauges come from beside the executable
    assert monitor.snapshot()[jit_watch.XLA_FLOPS]["values"] == flops
    # later calls of the signature run the same loaded executable
    out_c = second(out_b[0], it, x, 2)
    assert TRACES == [2] and _results() == {"hit": 1}
    assert np.asarray(out_c[0][0]["b"]).shape == (4,)
    assert second.compile_count == 1


# ----------------------------------------- every ingredient decides
def _flip_identity(ctx):
    ctx["identity"] = "conf-2"


def _flip_signature(ctx):
    ctx["rows"] = 7


def _flip_weak_type(ctx):
    ctx["it"] = jnp.int32(3)            # same dtype[shape], not weak


def _flip_static(ctx):
    ctx["n"] = 3


def _flip_name(ctx):
    ctx["name"] = "toy.other"


def _flip_source(ctx):
    (ctx["package"] / "sub" / "b.py").write_text("B = 3\n")


def _flip_new_source(ctx):
    (ctx["package"] / "c.py").write_text("")


def _patch(attr, value):
    def flip(ctx):
        ctx["monkeypatch"].setattr(compile_cache, attr, value)
    return flip


def _versions_with(**changed):
    real = compile_cache._versions

    def versions():
        return dict(real(), **changed)
    return _patch("_versions", versions)


def _env(name, value):
    def flip(ctx):
        ctx["monkeypatch"].setenv(name, value)
    return flip


FLIPS = {
    # name: (flip, result expected of the lookup after it)
    "identity": (_flip_identity, "miss_absent"),
    "signature": (_flip_signature, "miss_absent"),
    "weak_type": (_flip_weak_type, "miss_absent"),
    "static_argument": (_flip_static, "miss_absent"),
    "name": (_flip_name, "miss_absent"),
    "device_count": (_patch("_devices", lambda: ("cpu", 64)),
                     "miss_absent"),
    "device_kind": (_patch("_devices", lambda: ("TPU v9", 8)),
                    "miss_absent"),
    "source_file_content": (_flip_source, "miss_stale"),
    "source_file_added": (_flip_new_source, "miss_stale"),
    "jax_version": (_versions_with(jax="0.0.1"), "miss_stale"),
    "jaxlib_version": (_versions_with(jaxlib="0.0.1"), "miss_stale"),
    "platform_version": (_versions_with(platform_version="other"),
                         "miss_stale"),
    "XLA_FLAGS": (_env("XLA_FLAGS", os.environ.get("XLA_FLAGS", "")
                       + " --xla_cpu_enable_fast_math=false"),
                  "miss_stale"),
    "LIBTPU_INIT_ARGS": (_env("LIBTPU_INIT_ARGS", "--xla_tpu_x=1"),
                         "miss_stale"),
    "DL4J_TPU_variable": (_env("DL4J_TPU_PRECISION", "bf16"),
                          "miss_stale"),
}


@pytest.mark.parametrize("flip", sorted(FLIPS))
def test_a_flipped_ingredient_is_a_miss_and_a_fresh_compile(
        flip, install, package, monkeypatch):
    ctx = {"identity": "conf-1", "rows": 6, "it": 3, "n": 2,
           "name": "toy.step", "package": package,
           "monkeypatch": monkeypatch}

    def call():
        params, _, x = _args(ctx["rows"])
        f = _watched(ctx["identity"], ctx["name"])
        return f(params, ctx["it"], x, ctx["n"])

    install()
    call()
    install()
    call()
    assert _results() == {"miss_absent": 1, "written": 1, "hit": 1}
    traced = len(TRACES)

    change, expected = FLIPS[flip]
    change(ctx)
    monitor.reset()
    store = install()
    out = call()
    # never the stale executable: the program was derived afresh
    assert _results(ctx["name"]) == {expected: 1, "written": 1}
    assert len(TRACES) == traced + 1
    assert _counter(jit_watch.COMPILES_TOTAL, ctx["name"]) == 1
    assert float(out[1]["score"]) == sum(range(ctx["rows"])) + 3
    # a stale entry was overwritten in place: one entry a program
    assert len(_entries(store)) == (1 if expected == "miss_stale" else 2)
    install()
    call()
    assert _results(ctx["name"]).get("hit") == 1


# ------------------------------------------------- broken entries
@pytest.mark.parametrize("damage", ["truncated", "header_cut", "empty",
                                    "garbage", "payload_bit"])
def test_an_unreadable_entry_falls_back_and_is_overwritten(damage,
                                                           install):
    store = install()
    params, it, x = _args()
    good = _watched()(params, it, x, 2)
    (name,) = _entries(store)
    path = os.path.join(store.directory, name)
    whole = open(path, "rb").read()
    broken = {"truncated": whole[:len(whole) // 2],
              "header_cut": whole[:20], "empty": b"",
              "garbage": b"not an entry at all" * 10,
              "payload_bit": whole[:-9] + bytes([whole[-9] ^ 1])
              + whole[-8:]}[damage]
    with open(path, "wb") as fh:
        fh.write(broken)

    monitor.reset()
    install()
    params, it, x = _args()
    out = _watched()(params, it, x, 2)         # a miss, never an error
    assert _results() == {"miss_unreadable": 1, "written": 1}
    for a, b in zip(jax.tree.leaves(good), jax.tree.leaves(out)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert open(path, "rb").read() == whole or \
        len(open(path, "rb").read()) == len(whole)
    install()
    params, it, x = _args()
    _watched()(params, it, x, 2)
    assert _results().get("hit") == 1


_WRITER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, {repo!r})
    import jax, jax.numpy as jnp
    jax.config.update("jax_enable_x64", {x64!r})   # as the reader has it
    from deeplearning4j_tpu.serving.compile_cache import ExecutableStore
    store = ExecutableStore({directory!r}, package_root={package!r})
    exe = jax.jit(lambda x: x * 2 + 1).lower(jnp.ones((64,))).compile()
    print("ready", flush=True)
    sys.stdin.readline()                 # both start together
    for _ in range(40):
        assert store.save("k" * 64, exe, {{"xla_cost_flops": 1.0}})
    print("done", flush=True)
""")


def test_two_processes_writing_one_key_leave_one_whole_entry(
        tmp_path, package, monkeypatch):
    monkeypatch.undo()              # stats() below: the real directory
    directory = str(tmp_path / "cache" / "executables")
    code = _WRITER.format(repo=REPO, directory=directory,
                          package=str(package),
                          x64=bool(jax.config.jax_enable_x64))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    writers = [subprocess.Popen([sys.executable, "-c", code], env=env,
                                stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE, text=True)
               for _ in range(2)]
    try:
        for w in writers:
            assert w.stdout.readline().strip() == "ready"
        for w in writers:
            w.stdin.write("go\n")
            w.stdin.flush()
        reader = ExecutableStore(directory, package_root=str(package))
        seen = set()
        while any(w.poll() is None for w in writers):
            seen.add(reader.load("k" * 64)[0])  # whole, or not yet there
        for w in writers:
            assert w.stdout.readline().strip() == "done"
            assert w.wait(timeout=60) == 0
    finally:
        for w in writers:
            w.kill()
    assert seen <= {"hit", "miss_absent"}
    assert os.listdir(directory) == ["k" * 64]  # no temporary file left
    result, exe, costs = reader.load("k" * 64)
    assert result == "hit" and costs == {"xla_cost_flops": 1.0}
    np.testing.assert_array_equal(exe(jnp.ones((64,))), 3.0)
    stats = compile_cache.stats(str(tmp_path / "cache"))
    assert stats["executables"]["entries"] == 1
    assert stats["executables"]["bytes"] == os.path.getsize(
        os.path.join(directory, "k" * 64))
    assert stats["entries"] == 0                # JAX's cache is not this


# --------------------------------------------- who stays outside
def test_a_site_without_identity_never_touches_the_directory(install):
    store = install()
    plain = monitor.watched_jit(_new_step(), name="toy.plain",
                                static_argnums=(3,), donate_argnums=(0,))
    params, it, x = _args()
    plain(params, it, x, 2)
    plain(_args()[0], it, x, 2)
    assert not os.path.exists(store.directory)
    assert _results("toy.plain") == {}
    assert _counter(jit_watch.COMPILES_TOTAL, "toy.plain") == 1
    assert _counter(jit_watch.CACHE_HITS_TOTAL, "toy.plain") == 1
    # an identity that opts out at call time stays outside as well
    opted = monitor.watched_jit(_new_step(), name="toy.opted",
                                static_argnums=(3,), identity=lambda: None)
    opted(_args()[0], it, x, 2)
    assert not os.path.exists(store.directory)
    assert _results("toy.opted") == {}


def test_with_no_store_installed_an_identity_changes_nothing(tmp_path):
    monitor.reset()
    assert jit_watch.executable_store() is None
    f = _watched(name="toy.nostore")
    params, it, x = _args()
    f(params, it, x, 2)
    assert _results("toy.nostore") == {}
    assert _counter(jit_watch.COMPILES_TOTAL, "toy.nostore") == 1
    # today's path: the cost gauges' lowering is charged apart
    assert _counter(jit_watch.LOWER_SECONDS,
                    "toy.nostore/cost_analysis") > 0
    monitor.reset()


def test_enable_installs_one_store_and_keeps_it(tmp_path, monkeypatch):
    """Where it lives, ``<cache dir>/executables``, is what the
    second-process tests show (``stats`` of that directory counts their
    entries); here the directory is the test's own (``conftest.py``)."""
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    monkeypatch.setattr(jax.config, "update", lambda knob, value: None)
    assert jit_watch.executable_store() is None
    assert compile_cache.enable() == placed
    store = jit_watch.executable_store()
    assert isinstance(store, ExecutableStore)
    compile_cache.enable()                      # again: the same store
    assert jit_watch.executable_store() is store
    assert compile_cache.stats(placed)["executables"] == {
        "entries": 0, "bytes": 0}


def test_executables_dir_is_under_the_cache_directory(monkeypatch):
    monkeypatch.undo()                          # the real function
    assert compile_cache._executables_dir("/x/cache") == \
        "/x/cache/executables"


def test_cpu_does_not_serialize_again_what_jaxs_cache_reloaded(install):
    """XLA:CPU serializes an executable it loaded from JAX's persistent
    cache into an entry that has lost its kernels; the store declines
    (the TPU's serializes again whole: PERF.md, PR 28)."""
    store = install()
    exe = jax.jit(lambda x: x + 1).lower(jnp.ones((3,))).compile()
    assert store.save("r" * 64, exe, {}, reloaded=True) is False
    assert _entries(store) == []
    assert store.save("r" * 64, exe, {}, reloaded=False) is True
    assert _entries(store) == ["r" * 64]


def test_package_digest_reads_contents_not_times(package):
    before = compile_cache.package_digest(str(package))
    os.utime(package / "a.py", (1, 1))
    (package / "notes.txt").write_text("still not a source file\n")
    assert compile_cache.package_digest(str(package)) == before
    (package / "a.py").write_text("A = 2\n")
    assert compile_cache.package_digest(str(package)) != before
