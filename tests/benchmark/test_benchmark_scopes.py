"""The traced window by the program's names (``benchmark/scopes.py``)
and what ``run.py`` does with it: readers get ``by_scope``,
``unscoped_ops`` and ``idle_by_span``; ``breakdown`` speaks scopes and
spans; ``xplane.py``'s numbers are what they were; a program whose
reduction raises or finds nothing costs the run nothing but the names.

The CPU has no device plane, so the traced toy runs here are handed the
trace the same toy cell wrote on the v5e
(``data/toy_fit_trace.xplane.pb.gz``, ``record_toy_fit_trace.py``) in
place of the one they wrote themselves."""

import gzip
import importlib
import json
import os
import shutil

import pytest

from benchtools import HERE, ROOT, TOY, run_toy
from benchmark import scopes, xplane

DATA = os.path.join(HERE, "data")
SMALL = os.path.join(DATA, "small_trace.xplane.pb")
TRACING = os.path.join(TOY, "BENCHMARK.tracing.json")
GROUPS = ("layer", "update", "loss", "reg", "ingest", "health", "precision")
PASSES = ("forward", "backward", "other")
#: the submodule; ``monitor.device_trace`` the attribute is a function
PROGRAM = importlib.import_module("deeplearning4j_tpu.monitor.device_trace")
PARENT_METRICS = {"dispatches_per_step", "fit_dispatch_ms",
                  "setup_trace_lower_s", "setup_backend_s"}


def _unpacked(name, directory):
    target = os.path.join(str(directory),
                          os.path.basename(name)[:-len(".gz")])
    with gzip.open(name, "rb") as src, open(target, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return target


@pytest.fixture(scope="module")
def toy_trace(tmp_path_factory):
    return _unpacked(os.path.join(DATA, "toy_fit_trace.xplane.pb.gz"),
                     tmp_path_factory.mktemp("toy_trace"))


@pytest.fixture
def chip_trace(toy_trace, monkeypatch):
    """Whatever the run traces, the harness finds the chip's trace."""
    monkeypatch.setattr(xplane, "find_trace", lambda trace_dir: toy_trace)


def _parses(name):
    """``<scope>/<pass>`` under docs/OBSERVABILITY.md section 8's
    grammar, or ``unscoped/<HLO instruction>``."""
    scope, _, rest = name.partition("/")
    if scope == "unscoped":
        return bool(rest) and "/" not in rest and " " not in rest
    return scope.split(".")[0] in GROUPS and not scope.endswith(".") \
        and rest in PASSES


def test_traced_toy_run_hands_a_reader_file_the_rows_by_scope(
        chip_trace, tmp_path):
    rc, result, lines = run_toy("toy_vgg.fit", trace=1, seed=5,
                                out_dir=tmp_path, manifest_path=TRACING)
    assert rc == 0 and result["correct"] is True
    busy = result["device"]["busy_s"]
    assert 0 < busy < result["device"]["window_s"]
    # the reader under the toy root summed every row it was handed: the
    # rows are names for the seconds xplane.py counted
    assert result["metrics"]["toy_scoped_s"]["value"] == pytest.approx(
        busy, rel=1e-9)
    assert PARENT_METRICS <= set(result["metrics"])
    (said,) = [l for l in lines if l.startswith("bench: trace by scope")]
    numbers = json.loads(said.split("by scope ", 1)[1])
    assert numbers["rows"] >= 6 and numbers["unscoped_ops"] >= 1
    assert 0 < numbers["unscoped_s"] < 0.2 * busy
    # the harness deleted what the run itself traced, after the readers
    assert not os.path.exists(tmp_path / "trace" / "toy_vgg.fit")


def test_breakdown_speaks_scopes_and_spans(chip_trace, tmp_path):
    _, result, _ = run_toy("toy_vgg.fit", trace=1, seed=5,
                           out_dir=tmp_path, manifest_path=TRACING)
    ops, gaps = (result["breakdown"][k] for k in ("device_ops",
                                                  "idle_gaps"))
    assert 1 <= len(ops) <= 10 and 1 <= len(gaps) <= 10
    assert all(_parses(name) for name, _ in ops), ops
    seconds = [s for _, s in ops]
    assert seconds == sorted(seconds, reverse=True) and seconds[-1] > 0
    assert sum(seconds) <= result["device"]["busy_s"] * (1 + 1e-9)
    names = [name for name, _ in ops]
    assert any(n.startswith("layer.") and n.endswith("/backward")
               for n in names)
    # idle time by the innermost span: the program's own, where it
    # opened one inside the benchmark's
    spans = dict(gaps)
    assert "fit/score_wait" in spans
    assert all(n == "no_span" or n.split("/")[0] in ("fit", "bench")
               for n in spans), spans
    assert sum(spans.values()) == pytest.approx(
        result["device"]["window_s"] - result["device"]["busy_s"],
        rel=1e-6)


def test_a_reduction_that_raises_costs_the_names_only(chip_trace, tmp_path,
                                                      monkeypatch):
    """A parent without ``monitor.device_trace.reduce``, or one that
    raises: the result line has every metric, ``breakdown`` keeps the
    compiler's names, and a ``bench:`` line says why."""
    def broken(path, window=None):
        raise RuntimeError("no HLO module in this trace")

    monkeypatch.setattr(PROGRAM, "reduce", broken)
    rc, result, lines = run_toy("toy_vgg.fit", trace=1, seed=5,
                                out_dir=tmp_path, manifest_path=TRACING)
    assert rc == 0 and result["correct"] is True
    assert PARENT_METRICS <= set(result["metrics"])
    assert "toy_scoped_s" not in result["metrics"]
    assert result["device"]["busy_s"] > 0
    assert any("reduction by scope failed (RuntimeError: no HLO module"
               in l for l in lines)
    ops = [name for name, _ in result["breakdown"]["device_ops"]]
    assert ops and not any("/" in name for name in ops)
    assert all(n == "no_span" or n.startswith("bench/")
               for n, _ in result["breakdown"]["idle_gaps"])


def test_a_reduction_that_finds_nothing_costs_the_names_only(
        chip_trace, tmp_path, monkeypatch):
    monkeypatch.setattr(PROGRAM, "reduce", lambda *a, **k: None)
    rc, result, lines = run_toy("toy_vgg.fit", trace=1, seed=5,
                                out_dir=tmp_path, manifest_path=TRACING)
    assert rc == 0 and PARENT_METRICS <= set(result["metrics"])
    assert any("no rows by scope" in l for l in lines)
    assert not any("/" in name
                   for name, _ in result["breakdown"]["device_ops"])


#: ``busy_s``, ``window_s``, ``idle_share`` as the parent's ``xplane.py``
#: (commit 47dab72) reads them, to the last bit
PARENT_READS = {
    "small": (0.0001862229999999715, 0.06525760600000002,
              0.9971463403055274),
    "toy": (0.00013868999999977483, 0.004663449, 0.970260208699661),
}


@pytest.mark.parametrize("which", ["small", "toy"])
def test_xplane_reads_what_the_parent_read(which, toy_trace):
    path = SMALL if which == "small" else toy_trace
    r = xplane.reduce_events(xplane.read_events(path))
    assert (r["busy_s"], r["window_s"], r["idle_share"]) == \
        PARENT_READS[which]
    # the file's other numbers are its own arithmetic still
    assert len(r["device_ops"]) <= 10
    assert all(n == "no_span" or n.startswith("bench/")
               for n, _ in r["idle_gaps"])


def test_a_trace_from_before_the_scopes_is_all_unscoped():
    """``small_trace.xplane.pb`` was recorded before the program had
    scopes (and is no program of the package's): every second is
    ``unscoped``, by instruction name, and nothing raises."""
    events = xplane.read_events(SMALL)
    reduced = xplane.reduce_events(events)
    named = scopes.view(SMALL, events)
    assert [row[:2] for row in named["by_scope"]] == [["unscoped", "other"]]
    assert named["by_scope"][0][2] == pytest.approx(reduced["busy_s"],
                                                    rel=1e-9)
    assert sum(s for _, s, _ in named["unscoped_ops"]) == pytest.approx(
        reduced["busy_s"], rel=1e-9)
    assert {n.split(".")[0] for n, _, _ in named["unscoped_ops"]} <= {
        "fusion", "copy", "copy-start", "copy-done"}
    assert dict(named["idle_by_span"]).keys() == {
        "bench/sleep", "bench/unit", "no_span"}
    reduced.update(named)
    ops = scopes.breakdown(reduced)["device_ops"]
    assert all(name.startswith("unscoped/") for name, _ in ops)


def test_a_trace_without_the_benchmarks_window_reads_nothing(tmp_path):
    """``tests/data/scope_trace.xplane.pb.gz`` is the program's own
    (``profiler/capture``, no ``bench/window``): ``xplane.py`` reads
    nothing from it, as at the parent, and the harness asks no further."""
    path = _unpacked(os.path.join(ROOT, "tests", "data",
                                  "scope_trace.xplane.pb.gz"), tmp_path)
    events = xplane.read_events(path)
    assert events["devices"][0] and events["modules"][0]
    assert events["spans"] == []
    assert xplane.reduce_events(events) is None


def test_rows_by_scope_and_unscoped_ops_agree(toy_trace):
    events = xplane.read_events(toy_trace)
    reduced = xplane.reduce_events(events)
    named = scopes.view(toy_trace, events)
    assert sum(r[2] for r in named["by_scope"]) == pytest.approx(
        reduced["busy_s"], rel=1e-9)
    (unscoped,) = [r for r in named["by_scope"] if r[0] == "unscoped"]
    assert sum(s for _, s, _ in named["unscoped_ops"]) == pytest.approx(
        unscoped[2], rel=1e-9)
    assert sum(n for _, _, n in named["unscoped_ops"]) == unscoped[3]
    groups = {r[0].split(".")[0] for r in named["by_scope"]}
    assert {"layer", "ingest"} <= groups <= set(GROUPS) | {"unscoped"}


def test_unscoped_ops_on_hand_made_events():
    """An operation is looked up in the module that ran it: ``fusion.1``
    is scoped in ``jit_step`` and nameless in ``jit_other``."""
    events = {
        "devices": {0: [("fusion.1", 1.0, 2.0), ("copy.2", 2.0, 2.5),
                        ("fusion.1", 11.0, 13.0), ("custom-call.7", 13.0,
                                                   14.0)],
                    1: [("fusion.1", 1.0, 2.0), ("copy.2", 2.0, 3.5)]},
        "modules": {0: [(0.5, 3.0, "jit_step(1)"), (10.0, 15.0,
                                                    "jit_other(2)")],
                    1: [(0.5, 4.0, "jit_step(1)")]}}
    modules = {"jit_step(1)": {
        "fusion.1": ("fusion", "jit(step)/jvp(layer.0_Dense)/dot", (),
                     False),
        "copy.2": ("copy", "", (), False)},
        "jit_other(2)": {"fusion.1": ("fusion", "jit(other)/mul", (),
                                      False)}}

    def parse(op_name):
        return ("layer.0_Dense", "forward") if "layer." in op_name \
            else ("unscoped", "other")

    rows = scopes.unscoped_ops(events, modules, parse, 0.0, 13.5)
    assert rows == [["copy.2", pytest.approx((0.5 + 1.5) / 2), 2],
                    ["fusion.1", pytest.approx(2.0 / 2), 1],
                    ["custom-call.7", pytest.approx(0.5 / 2), 1]]
