"""The three per-layer readers PR 24 added (``fit_dispatch_ms``,
``setup_trace_lower_s``, ``setup_backend_s``): on hand-made records, on
a record of a program that has no such span or counter (they report
nothing and raise nothing), against ``BENCHMARK.json``, and through
``benchmark.run.main`` on the toy ``fit`` cell with a manifest of this
test's own (``toy/BENCHMARK.tracing.json``)."""

import io
import json
import os
from contextlib import redirect_stdout

import pytest

from benchtools import (ACCEPTED_PER_LAYER, FIT_CELLS, ROOT, TOY,
                        manifest)
from benchmark import run

BENCH = os.path.join(ROOT, "benchmark")
NEW = {
    "fit_dispatch_ms": ("ms", "program_span", "containers", "throughput"),
    "setup_trace_lower_s": ("s", "program_counter", "containers",
                            "setup_s"),
    "setup_backend_s": ("s", "program_counter", "compile cache",
                        "setup_s"),
}
READS = {"fit_dispatch_ms": "``fit/dispatch``",
         "setup_trace_lower_s": "``jit_lower_seconds_total``",
         "setup_backend_s": "``jit_backend_seconds_total``"}


def _reader(name):
    return run.Lookup([BENCH]).module("layer_metrics", name)


def _counter(values):
    return {"type": "counter", "help": "", "values": values}


def test_fit_dispatch_ms_is_the_median_of_the_fit_dispatch_spans():
    spans = [{"name": "fit/dispatch", "dur_ms": d} for d in (2.0, 9.0, 3.0)]
    spans += [{"name": "fit/score_wait", "dur_ms": 1800.0},
              {"name": "fit/epoch", "dur_ms": 12.0}]
    assert _reader("fit_dispatch_ms").read({"spans": spans}) == 3.0


def test_setup_trace_lower_s_sums_both_counters_over_their_labels():
    before = {
        "jit_trace_seconds_total": _counter(
            {'{fn="cg.gather_train_step"}': 2.5, '{fn="unwatched"}': 1.0}),
        "jit_lower_seconds_total": _counter({'{fn="unwatched"}': 0.25}),
        "jit_backend_seconds_total": _counter({'{fn="unwatched"}': 40.0}),
    }
    record = {"monitor_before": before}
    assert _reader("setup_trace_lower_s").read(record) == 3.75
    assert _reader("setup_backend_s").read(record) == 40.0
    # one of the two is enough to report
    del before["jit_lower_seconds_total"]
    assert _reader("setup_trace_lower_s").read(record) == 3.5


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("record", [
    {}, {"spans": [], "monitor_before": {}},
    {"spans": None, "monitor_before": None},
    {"spans": [{"name": "fit/epoch", "dur_ms": 5.0}],
     "monitor_before": {"jit_compiles_total": _counter({"": 3.0})}},
], ids=["empty", "nothing", "none", "parent"])
def test_a_program_without_the_span_or_counter_reports_nothing(name, record):
    """What the parent commit hands these readers: a record with other
    spans and other counters."""
    assert _reader(name).read(record) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_manifest_entry_says_what_the_reader_says(name):
    unit, source, layer, moves = NEW[name]
    (entry,) = [e for e in manifest()["per_layer"] if e["name"] == name]
    reader = _reader(name)
    assert (reader.UNIT, reader.SOURCE, reader.LAYER) == \
        (entry["unit"], entry["source"], entry["layer"]) == \
        (unit, source, layer)
    assert reader.BETTER == entry["better"] == "lower"
    assert entry["moves"] == moves
    # a later cell that reports what the metric moves may join the list
    assert set(entry["workloads"]) >= set(FIT_CELLS)
    # each reader's docstring says what it reads
    assert READS[name] in reader.__doc__


def test_new_entries_were_put_at_the_end_of_the_list():
    """The list BEGINS with the accepted metrics in their accepted
    order, PR 24's three among them; what a later PR brings follows."""
    names = [e["name"] for e in manifest()["per_layer"]]
    accepted = [name for name, _ in ACCEPTED_PER_LAYER]
    assert names[:len(accepted)] == accepted
    assert accepted[-3:] == ["fit_dispatch_ms", "setup_trace_lower_s",
                             "setup_backend_s"]
    assert not set(names[len(accepted):]) & set(accepted)


def _run(trace, tmp_path):
    os.environ["BENCHMARK_OUT_DIR"] = str(tmp_path)
    buf = io.StringIO()
    try:
        with redirect_stdout(buf):
            rc = run.main(
                ["--workload", "toy_vgg.fit", "--seed", "5", "--seconds",
                 "0.5", "--trace", str(trace)],
                manifest_path=os.path.join(TOY, "BENCHMARK.tracing.json"),
                extra_roots=[TOY], require_tpu=False)
    finally:
        os.environ.pop("BENCHMARK_OUT_DIR", None)
    return rc, json.loads(buf.getvalue().strip().splitlines()[-1])


def test_traced_run_of_the_toy_fit_cell_reads_all_three(tmp_path):
    rc, result = _run(1, tmp_path)
    assert rc == 0 and result["correct"] is True
    metrics = result["metrics"]
    assert set(NEW) <= set(metrics)
    for name, (unit, _, _, _) in NEW.items():
        assert metrics[name]["unit"] == unit
        assert metrics[name]["value"] > 0
    # a launch of 8 toy steps on the CPU: well under a second
    assert metrics["fit_dispatch_ms"]["value"] < 1000
    # the net's init and the fused step were traced, lowered and
    # compiled (or loaded) in set-up
    assert metrics["setup_trace_lower_s"]["value"] < 120
    assert metrics["dispatches_per_step"]["value"] == pytest.approx(1 / 8)


def test_untraced_run_reports_end_to_end_metrics_only(tmp_path):
    rc, result = _run(0, tmp_path)
    assert rc == 0
    assert set(result["metrics"]) == {"throughput", "setup_s"}
