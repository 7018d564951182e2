"""Driven by data: everything is found by name, a toy configuration and
cell that exist only under ``tests/benchmark/`` come through the same
lookup, and ``BENCHMARK.json`` agrees with the files it names and with
the contract's limits."""

import os

import pytest

from benchtools import (ACCEPTED_PER_LAYER, FIT_CELLS, ROOT, TOY,
                        check_config_file, check_manifest, manifest)
from benchmark import run

MANIFEST = manifest()
BENCH = os.path.join(ROOT, "benchmark")


def test_toy_cell_is_found_only_through_the_extra_root():
    plain = run.Lookup([BENCH])
    with pytest.raises(FileNotFoundError):
        plain.path("workloads", "toy_vgg.fit")
    with pytest.raises(FileNotFoundError):
        plain.path("configs", "toy_vgg")
    both = run.Lookup([BENCH, TOY])
    cell = both.data("workloads", "toy_vgg.fit")
    assert both.path("configs", cell["config"]).startswith(TOY)
    assert both.path("traffic", cell["traffic"]).startswith(TOY)
    # the toy cell's driver and readers are the benchmark's own files
    assert both.path("drivers", cell["driver"], (".py",)).startswith(BENCH)
    assert both.module("end_to_end", "throughput").UNIT == "items/s"


def test_first_root_wins_and_other_data_suffixes_are_found(tmp_path):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "replay.csv").write_text("t,rows\n0.1,1\n")
    (tmp_path / "traffic" / "serve_open.json").write_text("{}")
    lookup = run.Lookup([BENCH, str(tmp_path)])
    assert lookup.path("traffic", "replay").endswith("replay.csv")
    assert lookup.path("traffic", "serve_open").startswith(BENCH)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    lookup = run.Lookup([BENCH])
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    data = lookup.data("workloads", cell)
    for key in ("config", "traffic", "chips", "why"):
        assert data[key] == entry[key]
    cfg = lookup.data("configs", data["config"])
    listed = next(c for c in MANIFEST["configs"]
                  if c["name"] == data["config"])
    assert listed["file"] == f"benchmark/configs/{data['config']}.json"
    # same source, same ``reduced`` (a list of key names, empty or not),
    # and every cut explained in the file
    check_config_file(listed, cfg)
    if cell in FIT_CELLS:
        assert cfg["reduced"] == [] and "published" not in cfg
    lookup.data("traffic", data["traffic"])
    driver = lookup.module("drivers", data["driver"])
    assert callable(driver.setup) and callable(driver.measure)


@pytest.mark.parametrize("cell", ["resnet50.fit_dp4", "resnet50.serve_open",
                                  "resnet50.serve_sweep"])
def test_workload_files_without_an_entry_resolve_too(cell):
    """The knee sweep (a mode, never a cell) and the two cells that
    PR 22 measured and could not admit (PERF.md: the four-chip one read
    no trace; the serving one holds too little memory at the ladder its
    traffic fills) are files only."""
    assert cell not in [w["name"] for w in MANIFEST["workloads"]]
    lookup = run.Lookup([BENCH])
    data = lookup.data("workloads", cell)
    lookup.data("configs", data["config"])
    lookup.data("traffic", data["traffic"])
    assert callable(lookup.module("drivers", data["driver"]).setup)


@pytest.mark.parametrize("group,kind", [("end_to_end", "end_to_end"),
                                        ("per_layer", "layer_metrics")])
def test_every_metric_has_a_reader_that_declares_the_same(group, kind):
    lookup = run.Lookup([BENCH])
    for entry in MANIFEST[group]:
        reader = lookup.module(kind, entry["name"])
        assert callable(reader.read)
        assert reader.UNIT == entry["unit"]
        assert reader.BETTER == entry["better"]
        assert reader.SOURCE == entry["source"]
        if group == "per_layer":
            assert reader.LAYER == entry["layer"]
            # ``moves`` is the manifest's alone: one reader serves cells
            # whose end-to-end metrics differ
            assert not hasattr(reader, "MOVES")


def test_a_reader_that_finds_nothing_returns_nothing():
    lookup = run.Lookup([BENCH])
    empty = {"trace": None, "spans": [], "phase": None, "steps": 0,
             "items": 0, "window_s": 0.0, "memory_peak_bytes": 0}
    for name in ("device_mfu", "device_idle_share", "collective_share",
                 "collective_exposed_share", "queue_wait_p50_ms",
                 "generator_lag_p99_ms", "data_stage_share",
                 "dispatches_per_step", "hbm_peak_gib",
                 "serve_p99_ms", "unit_stall_share"):
        assert lookup.module("layer_metrics", name).read(empty) is None
    for name in ("throughput", "latency_p50_ms", "latency_p95_ms"):
        assert lookup.module("end_to_end", name).read(empty) is None


def test_the_serving_files_run_the_ladder_their_rows_fill():
    """A bucket the window never reaches is set-up and memory that
    stand for nothing: the largest request has 8 rows, the engine
    coalesces a few requests, and the ladder ends at 32."""
    lookup = run.Lookup([BENCH])
    for name in ("serve_open", "serve_sweep"):
        traffic = lookup.data("traffic", name)
        assert traffic["max_batch_size"] == 32
        assert max(int(r) for r in traffic["rows_mix"]) <= 8
    assert lookup.data("traffic", "serve_sweep")["sweep_seeds"] >= 3


def test_manifest_meets_the_contract_limits():
    """The contract (``benchtools.check_manifest``) on the real manifest,
    with what only the real one has: its command, its two directories,
    and the accepted per-layer metrics at the head of the list."""
    m = MANIFEST
    assert m["command"][:2] == ["python3", "benchmark/run.py"]
    assert m["paths"] == ["benchmark", "tests/benchmark"]
    assert len(m["workloads"]) >= 2
    check_manifest(m, run.Lookup([BENCH]), accepted=ACCEPTED_PER_LAYER)
