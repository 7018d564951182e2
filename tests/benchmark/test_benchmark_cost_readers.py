"""The three per-layer readers PR 39 added (``hbm_traffic_share``,
``idle_in_program_share``, ``idle_between_programs_share``) and what
they share (``benchmark/scope_costs.py``): on hand-made records, on the
parent's record and on no trace (they report nothing and raise
nothing), over the program's ``reduce`` of the recorded chip trace,
against ``BENCHMARK.json``, and through ``benchmark.run.main`` on the
toy ``fit`` cell with a manifest of this test's own
(``toy/BENCHMARK.costs.json``): on the CPU a trace has no device plane,
so the traced run reports none of the three and fails nothing."""

import importlib
import os

import pytest

from benchtools import ROOT, TOY, manifest, run_toy
from benchmark import run, scope_costs, xplane

#: the package's attribute ``monitor.device_trace`` is the context
#: manager, which shadows the module
device_trace = importlib.import_module(
    "deeplearning4j_tpu.monitor.device_trace")
BENCH = os.path.join(ROOT, "benchmark")
SMALL = os.path.join(ROOT, "tests", "benchmark", "data",
                     "small_trace.xplane.pb")
NEW = {"hbm_traffic_share": ("higher", "step program"),
       "idle_in_program_share": ("lower", "step program"),
       "idle_between_programs_share": ("lower", "device")}
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}
#: what the program's reduction hands over, by hand: 4 GB in 10 ms busy
REPORT = {"hbm_bytes": 4.0e9, "uncounted_s": 0.0001, "window_s": 0.0125,
          "idle_in_program_s": 0.002, "idle_between_programs_s": 0.0005,
          "busy_s": 0.010, "reduce_s": 0.0}


def _reader(name):
    return run.Lookup([BENCH]).module("layer_metrics", name)


@pytest.fixture
def reduced(monkeypatch, tmp_path):
    """A record whose trace is a file, and the reports ``reduce`` is
    made to return for it, most recent last (the list counts calls)."""
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(b"")
    calls = []

    def install(report):
        def reduce(trace_path, window):
            assert (trace_path, window) == (str(path), "bench/window")
            calls.append(report)
            return report
        monkeypatch.setattr(device_trace, "reduce", reduce)
        monkeypatch.setattr(scope_costs, "_reduced", {})
        return {"trace": {"path": str(path), "busy_s": 0.010},
                "peaks": PEAKS}
    install.calls = calls
    return install


def test_hand_made_report_reads_as_the_three_shares(reduced):
    record = reduced(dict(REPORT))
    assert _reader("hbm_traffic_share").read(record) == pytest.approx(
        100.0 * 4.0e9 / (0.010 * 819e9))                # 48.84%
    assert _reader("idle_in_program_share").read(record) == \
        pytest.approx(16.0)
    assert _reader("idle_between_programs_share").read(record) == \
        pytest.approx(4.0)
    # three readers, one reduction of the file
    assert len(reduced.calls) == 1


def test_a_hole_in_the_count_is_not_a_low_number(reduced):
    """More than 2% of busy time without a count: no traffic share; the
    idle shares do not depend on the count."""
    record = reduced(dict(REPORT, uncounted_s=0.00021))
    assert _reader("hbm_traffic_share").read(record) is None
    assert _reader("idle_in_program_share").read(record) == \
        pytest.approx(16.0)
    record = reduced(dict(REPORT, uncounted_s=0.00019))
    assert _reader("hbm_traffic_share").read(record) is not None


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("report", [
    None,
    {k: v for k, v in REPORT.items() if k not in scope_costs.KEYS},
    {"by_scope": [["unscoped", "other", 0.01, 3]], "busy_s": 0.01,
     "window_s": 0.0125, "idle_by_span": [], "reduce_s": 0.1},
], ids=["nothing_found", "no_new_key", "parents_report"])
def test_a_program_without_the_keys_reports_nothing(reduced, name, report):
    """The parent's ``reduce`` returns ``by_scope`` and ``idle_by_span``
    and none of PR 39's keys."""
    assert _reader(name).read(reduced(report)) is None


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("record", [
    {}, {"trace": None}, {"trace": {"busy_s": 0.01, "by_scope": []}},
    {"trace": {"path": "/no/such/file.xplane.pb", "busy_s": 0.01},
     "peaks": PEAKS},
], ids=["empty", "no_trace", "no_path", "file_gone"])
def test_no_trace_reports_nothing(name, record):
    assert _reader(name).read(record) is None


def test_a_reduction_that_raises_reports_nothing(reduced, monkeypatch,
                                                 capsys):
    record = reduced(dict(REPORT))

    def broken(path, window):
        raise ValueError("wire type 7 in an xplane file")
    monkeypatch.setattr(device_trace, "reduce", broken)
    for name in NEW:
        assert _reader(name).read(record) is None
    assert "the program's reduction failed" in capsys.readouterr().out


def test_traffic_share_needs_peaks_and_busy_seconds(reduced):
    record = reduced(dict(REPORT))
    assert _reader("hbm_traffic_share").read(
        {"trace": record["trace"]}) is None
    assert _reader("hbm_traffic_share").read(
        {"trace": dict(record["trace"], busy_s=0.0), "peaks": PEAKS}) is None


def test_over_the_recorded_chip_trace_the_readers_read_the_programs_sums(
        monkeypatch):
    """``small_trace``: three chained products whose operands the layout
    keeps on the chip, so 4 MiB and 4 bytes of HBM traffic in 186 us of
    busy time; nearly all of the window is the host's ``bench/sleep``,
    between programs."""
    monkeypatch.setattr(scope_costs, "_reduced", {})
    theirs = xplane.reduce_events(xplane.read_events(SMALL))
    record = {"trace": dict(theirs, path=SMALL), "peaks": PEAKS}
    mine = device_trace.reduce(SMALL, window="bench/window")
    assert _reader("hbm_traffic_share").read(record) == pytest.approx(
        100.0 * (2 * 2097152 + 4) / (theirs["busy_s"] * 819e9))
    inside = _reader("idle_in_program_share").read(record)
    between = _reader("idle_between_programs_share").read(record)
    assert inside == pytest.approx(
        100.0 * mine["idle_in_program_s"] / mine["window_s"])
    assert 0 < inside < 0.001 and between > 99.0
    # together they are the idle share the accepted metric reads
    idle = run.Lookup([BENCH]).module(
        "layer_metrics", "device_idle_share").read(record)
    assert inside + between == pytest.approx(idle, abs=1e-6)


@pytest.mark.parametrize("name", sorted(NEW))
def test_manifest_entry_says_what_the_reader_says(name):
    better, layer = NEW[name]
    m = manifest()
    (entry,) = [e for e in m["per_layer"] if e["name"] == name]
    reader = _reader(name)
    assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.BETTER) == \
        (entry["unit"], entry["source"], entry["layer"],
         entry["better"]) == ("%", "device_trace", layer, better)
    assert entry["moves"] == "throughput"
    # every cell, in the manifest's order
    assert entry["workloads"] == [w["name"] for w in m["workloads"]][:5]
    assert "scope_costs" in reader.__doc__


def test_new_entries_were_appended_after_the_nineteen_that_were_there():
    names = [e["name"] for e in manifest()["per_layer"]]
    assert names[18] == "sparse_select_share"
    assert names[19:22] == ["hbm_traffic_share", "idle_in_program_share",
                            "idle_between_programs_share"]
    assert len(names) == len(set(names))


def test_traced_toy_run_on_the_cpu_reports_none_of_the_three(tmp_path):
    rc, result, _ = run_toy(
        "toy_vgg.fit", 1, seconds=0.5, seed=3900000007, out_dir=tmp_path,
        manifest_path=os.path.join(TOY, "BENCHMARK.costs.json"))
    assert rc == 0 and result["correct"] is True
    assert not set(NEW) & set(result["metrics"])
    assert "dispatches_per_step" in result["metrics"]
