"""The harness end to end on toy cells on the CPU, and ``run.py``
itself refusing to run where there is no TPU or no program."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchtools import ROOT, TOY, run_toy

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell,expected", [
    ("toy_vgg.fit", {"throughput", "setup_s"}),
    ("toy_vgg.serve", {"latency_p50_ms", "latency_p95_ms", "throughput",
                       "setup_s"}),
    ("toy_vgg.dp2", {"throughput", "setup_s"}),
])
def test_end_to_end_line(cell, expected, tmp_path):
    rc, result, _ = run_toy(cell, trace=0, out_dir=tmp_path)
    assert rc == 0
    assert set(result) - {"checks"} == RESULT_KEYS
    if "fit" in cell or "dp" in cell:
        # each number ``correct`` compared, beside its limit, comes last
        assert list(result)[-1] == "checks"
        assert set(result["checks"]) == {
            "output_rel_err", "score_rel_err", "nonfinite_scores",
            "score_range_rel"}
        for value, limit in result["checks"].values():
            assert isinstance(value, (int, float)) and limit[0] in "<>"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == expected
    for m in result["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] >= 1


@pytest.mark.parametrize("cell,expected,absent", [
    ("toy_vgg.fit",
     {"data_stage_share", "dispatches_per_step", "compiles_in_window",
      "cache_misses_warm", "unit_stall_share"},
     {"device_mfu", "device_idle_share", "collective_share"}),
    ("toy_vgg.serve",
     {"generator_lag_p99_ms", "queue_wait_p50_ms", "batch_rows_mean",
      "cache_misses_warm", "compiles_in_window", "dispatches_per_step",
      "serve_p99_ms"},
     {"device_idle_share"}),
])
def test_per_layer_line_on_cpu(cell, expected, absent, tmp_path):
    """A traced run reports the per-layer metrics whose readers find
    something; on the CPU there is no device plane, so every metric that
    comes from the device trace is left out, not invented."""
    rc, result, lines = run_toy(cell, trace=1, out_dir=tmp_path)
    assert rc == 0
    assert expected <= set(result["metrics"])
    assert not absent & set(result["metrics"])
    assert "breakdown" not in result
    assert "busy_s" not in result["device"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert any("no device operation" in l for l in lines)


def test_fit_counts_and_scores(tmp_path):
    rc, result, lines = run_toy("toy_vgg.fit", trace=1, out_dir=tmp_path)
    # 64 examples at batch 16, 2 epochs fused: 8 steps in one dispatch
    assert result["metrics"]["dispatches_per_step"]["value"] == \
        pytest.approx(1 / 8)
    scores = [l for l in lines if "first scores" in l]
    assert scores and "nan" not in scores[0]


def test_scores_that_do_not_move_are_not_correct(tmp_path, monkeypatch):
    """A train step that applies no update reports one score for ever,
    and the run says so instead of timing it."""
    from benchmark import units
    from deeplearning4j_tpu.nn import updaters
    assert units.scores_move([2.5, 2.1, 2.0])
    assert not units.scores_move([2.5, 2.5, 2.5])
    assert not units.scores_move([2.5])
    assert not units.scores_move([2.5, float("nan")])
    real = updaters.compute_update

    def no_update(conf, grads, state, iteration, params=None):
        import jax
        updates, new_state = real(conf, grads, state, iteration, params)
        return jax.tree.map(lambda u: 0.0 * u, updates), new_state

    monkeypatch.setattr(updaters, "compute_update", no_update)
    rc, result, _ = run_toy("toy_vgg.fit", trace=0, out_dir=tmp_path)
    assert rc == 0 and result["failed"] == 0
    assert result["correct"] is False


def test_same_seed_same_scores(tmp_path):
    first = run_toy("toy_vgg.fit", trace=0, out_dir=tmp_path)[2]
    again = run_toy("toy_vgg.fit", trace=0, out_dir=tmp_path)[2]
    other = run_toy("toy_vgg.fit", trace=0, seed=4, out_dir=tmp_path)[2]

    def head(lines):
        line = next(l for l in lines if "first scores" in l)
        return json.loads(line.split("first scores ")[1])[:3]

    assert head(first) == head(again) != head(other)


def _run_cli(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script, "--workload", "resnet50.fit_cached",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_the_cpu():
    proc = _run_cli(ROOT, "benchmark/run.py")
    assert proc.returncode == 1
    assert "platform 'cpu'" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_run_py_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path, "benchmark/run.py")
    assert proc.returncode == 2
    assert "not in this checkout" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_sweep_mode_writes_its_table(tmp_path):
    """The knee sweep is data too: a traffic file with stepped rates and
    a workload file, no entry in the manifest, no new switch."""
    from benchmark import run
    root = tmp_path / "extra"
    for kind in ("traffic", "workloads"):
        (root / kind).mkdir(parents=True)
    with open(os.path.join(TOY, "traffic", "toy_serve.json")) as fh:
        traffic = json.load(fh)
    traffic.update(sweep_rates_per_s=[50, 100], sweep_seeds=2,
                   knee_p99_ms=1000.0)
    (root / "traffic" / "toy_sweep.json").write_text(json.dumps(traffic))
    (root / "workloads" / "toy_vgg.sweep.json").write_text(json.dumps(
        {"name": "toy_vgg.sweep", "config": "toy_vgg",
         "traffic": "toy_sweep", "driver": "serve_open", "chips": 1,
         "why": "toy"}))
    os.environ["BENCHMARK_OUT_DIR"] = str(tmp_path / "out")
    try:
        rc = run.main(["--workload", "toy_vgg.sweep", "--seed", "1",
                       "--seconds", "0.5", "--trace", "0"],
                      manifest_path=os.path.join(TOY, "BENCHMARK.toy.json"),
                      extra_roots=[TOY, str(root)], require_tpu=False)
    finally:
        os.environ.pop("BENCHMARK_OUT_DIR", None)
    assert rc == 0
    with open(tmp_path / "out" / "knee_sweep.toy_vgg.sweep.json") as fh:
        table = json.load(fh)
    assert [(r["rate_per_s"], r["seed"]) for r in table["table"]] == [
        (50, 1), (50, 2), (100, 1), (100, 2)]
    assert table["knee_rate_per_s"] in (50, 100)
