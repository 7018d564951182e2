"""The reader ``executable_store_hits`` (PR 28): on hand-made records,
on a record of a program that has no such counter (it reports nothing
and raises nothing), and through ``benchmark.run.main`` on the toy
``fit`` cell, twice in one checkout's cache: the second run loads what
the first wrote.  ``BENCHMARK.json`` has its line since PR 29, appended
after the accepted ones; the toy run brings a manifest of its own."""

import io
import json
import os
from contextlib import redirect_stdout

import pytest

import benchtools
from benchtools import ACCEPTED_PER_LAYER, FIT_CELLS, ROOT, TOY
from benchmark import run

BENCH = os.path.join(ROOT, "benchmark")


def _reader():
    return run.Lookup([BENCH]).module("layer_metrics",
                                      "executable_store_hits")


def _counter(values):
    return {"type": "counter", "help": "", "values": values}


def test_counts_hits_over_their_fn_labels_and_nothing_else():
    record = {"monitor_before": {"executable_store_total": _counter({
        '{fn="cg.init_held",result="hit"}': 1.0,
        '{fn="cg.init",result="hit"}': 1.0,
        '{fn="cg.gather_train_step",result="hit"}': 1.0,
        '{fn="cg.output",result="miss_absent"}': 1.0,
        '{fn="cg.output",result="written"}': 1.0})}}
    assert _reader().read(record) == 3.0
    first_run = {"monitor_before": {"executable_store_total": _counter({
        '{fn="cg.init",result="miss_absent"}': 1.0,
        '{fn="cg.init",result="written"}': 1.0})}}
    assert _reader().read(first_run) == 0.0


@pytest.mark.parametrize("record", [
    {}, {"monitor_before": {}}, {"monitor_before": None},
    {"monitor_before": {"jit_backend_seconds_total": _counter({"": 3.0})}},
], ids=["empty", "nothing", "none", "parent"])
def test_a_program_without_the_counter_reports_nothing(record):
    assert _reader().read(record) is None


def test_reader_says_what_it_is():
    reader = _reader()
    assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER) == \
        ("count", "higher", "program_counter", "compile cache")
    assert "``executable_store_total``" in reader.__doc__


def test_manifest_entry_says_what_the_reader_says():
    entries = benchtools.manifest()["per_layer"]
    (entry,) = [e for e in entries if e["name"] == "executable_store_hits"]
    reader = _reader()
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == (reader.UNIT, reader.BETTER, reader.SOURCE,
                                reader.LAYER)
    assert entry["moves"] == "setup_s"
    assert set(entry["workloads"]) >= set(FIT_CELLS)
    # appended: it comes after every accepted metric
    assert entries.index(entry) >= len(ACCEPTED_PER_LAYER)


def _run(tmp_path, manifest):
    os.environ["BENCHMARK_OUT_DIR"] = str(tmp_path)
    buf = io.StringIO()
    try:
        with redirect_stdout(buf):
            rc = run.main(
                ["--workload", "toy_vgg.fit", "--seed", "5", "--seconds",
                 "0.5", "--trace", "1"],
                manifest_path=manifest, extra_roots=[TOY],
                require_tpu=False)
    finally:
        os.environ.pop("BENCHMARK_OUT_DIR", None)
    return rc, json.loads(buf.getvalue().strip().splitlines()[-1])


def test_second_toy_run_in_a_cache_loads_what_the_first_wrote(
        tmp_path, monkeypatch):
    with open(os.path.join(TOY, "BENCHMARK.tracing.json")) as fh:
        manifest = json.load(fh)
    cells = [w["name"] for w in manifest["workloads"]]
    manifest["per_layer"].append({
        "name": "executable_store_hits", "unit": "count",
        "better": "higher", "source": "program_counter",
        "layer": "compile cache", "moves": "setup_s", "workloads": cells})
    path = tmp_path / "BENCHMARK.store.json"
    path.write_text(json.dumps(manifest))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))

    from deeplearning4j_tpu import monitor
    monitor.reset()                 # the registry is the process's
    rc, first = _run(tmp_path / "a", str(path))
    assert rc == 0 and first["correct"] is True
    assert first["metrics"]["executable_store_hits"]["value"] == 0
    monitor.reset()
    rc, second = _run(tmp_path / "b", str(path))
    assert rc == 0 and second["correct"] is True
    # the two staged init programs; the CPU backend does not serialize
    # the gather step (its shuffle sorts), the TPU's does: 3 there
    assert second["metrics"]["executable_store_hits"]["value"] >= 2
    assert second["metrics"]["setup_backend_s"]["value"] > 0
    monitor.reset()
