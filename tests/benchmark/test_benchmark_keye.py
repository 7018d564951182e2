"""The sparse-attention cell's own pieces on the CPU: the configuration's
file against its source's widths and its three cuts, where the manifest
lists the cell, a toy cell through the ``decode_sparse`` driver and
``main()``, the family's operations and bytes counted by hand, the
driver's kernel counts, and the three new readers on hand-made
records."""

import importlib
import json
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchtools import (ACCEPTED_PER_LAYER, ROOT, TOY, check_config_file,
                        check_manifest, manifest, run_toy)
from benchmark import flops, run
from benchmark.drivers import decode_sparse
from benchmark.families import gqa_sparse_share as family

CELL = "keye_vl2_30b_a3b.decode_b8_ctx32k"
AX = "ax_k1.decode_b256_ctx1k"
XING = "xing4_29b_a4b.decode_b64_ctx4k"
TOY_MANIFEST = os.path.join(TOY, "BENCHMARK.sparse.json")
BENCH = os.path.join(ROOT, "benchmark")
LOOKUP = run.Lookup([BENCH])
SMALL = run.Lookup([BENCH, TOY]).data("configs", "toy_sparse")
NEW = ["indexer_roofline", "sparse_attention_roofline",
       "sparse_select_share"]


# ------------------------------------------------------ the configuration
def test_configuration_keeps_every_published_width_and_explains_its_cuts():
    cfg = LOOKUP.data("configs", "keye_vl2_30b_a3b")
    # the catalog row's config, every key but the three cut ones
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "KeyeVL2", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: cfg[k] for k in published} == published
    assert cfg["source"] == ("https://huggingface.co/Kwai-Keye/"
                             "Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert len(cfg["source"]) <= 200
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (6, 16, 18992)
    entry = next(c for c in manifest()["configs"]
                 if c["name"] == "keye_vl2_30b_a3b")
    check_config_file(entry, cfg)
    # the guide's floors: four layers, eight experts, an eighth of the ids
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["builder_args"]["experts_held"] == list(range(16))
    for word in ("8 v5e", "16 a chip", "data-parallel", "18,992", "6 of 48",
                 "bf16", "vision tower"):
        assert word in cfg["deployment"], word
    for key in ("depth", "share", "vocabulary", "qk_norm", "indexer",
                "selection", "rotary", "init", "sampling", "precision"):
        assert key in cfg["assumed"], key
    assert "num_local_experts" in cfg["assumed"]["share"]
    assert "block-granular" in cfg["assumed"]["selection"]
    traffic = LOOKUP.data("traffic", "decode_b8_ctx32k")
    assert (traffic["rows"], traffic["prompt_tokens"], traffic["ring_slots"],
            traffic["new_tokens"]) == (8, 32704, 32768, 64)
    assert traffic["prompt_tokens"] + traffic["new_tokens"] <= 32768 + 1
    # the experts see an eighth of what the deployment's would
    assert traffic["rows"] * cfg["num_experts_per_tok"] / 128 == 0.5


@pytest.mark.parametrize("which", ["real", "toy"])
def test_manifest_with_the_sparse_cell_meets_the_contract(which):
    if which == "real":
        check_manifest(manifest(), LOOKUP, accepted=ACCEPTED_PER_LAYER)
    else:
        with open(TOY_MANIFEST) as fh:
            check_manifest(json.load(fh), run.Lookup([BENCH, TOY]),
                           allowed_chips=(1,))


def test_the_cell_is_appended_to_every_list_it_joins():
    m = manifest()
    every = m["end_to_end"] + m["per_layer"]
    mine = {e["name"] for e in every if CELL in e.get("workloads", [CELL])}
    assert mine == {
        "throughput", "setup_s", "dispatches_per_step", "compiles_in_window",
        "device_mfu", "cache_misses_warm", "device_idle_share",
        "unit_stall_share", "hbm_peak_gib", "setup_trace_lower_s",
        "setup_backend_s", "executable_store_hits", "decode_dispatch_ms",
        "expert_load_max_share", *NEW}
    # not ``moe_experts_roofline``: the dense form at 8 tokens a step
    # reads every held expert, partly under its neighbours (PERF.md)
    assert next(e for e in every if e["name"] == "moe_experts_roofline")[
        "workloads"] == [XING, AX]
    # the driver takes an entry anywhere but at the end of its list as a
    # change to what was there: last in every list it joined
    for e in every:
        if CELL in e.get("workloads", []):
            assert e["workloads"][-1] == CELL and \
                e["workloads"].count(CELL) == 1
    assert m["configs"][-1]["name"] == "keye_vl2_30b_a3b"
    assert m["workloads"][-1] == {
        "name": CELL, "config": "keye_vl2_30b_a3b",
        "traffic": "decode_b8_ctx32k", "chips": 1,
        "why": LOOKUP.data("workloads", CELL)["why"]}
    assert [e["name"] for e in m["per_layer"]][-3:] == NEW
    for e in m["per_layer"][-3:]:
        assert (e["moves"], e["workloads"], e["layer"]) == (
            "throughput", [CELL], "step program")
    assert LOOKUP.data("workloads", CELL)["driver"] == "decode_sparse"
    for word in ("0.5 tokens", "6 layers", "2,048"):
        assert word in m["workloads"][-1]["why"], word


#: the manifest as PR 36 left it, by name and from the START of each
#: list: what ``conftest.py``'s two marked tests hold from the end
ACCEPTED = {
    "configs": ["resnet50", "vgg16", "xing4_29b_a4b", "ax_k1"],
    "workloads": ["resnet50.fit_cached", "vgg16.fit_cached", XING, AX],
    "end_to_end": ["throughput", "setup_s"],
    "per_layer": [
        "data_stage_share", "dispatches_per_step", "compiles_in_window",
        "device_mfu", "cache_misses_warm", "device_idle_share",
        "unit_stall_share", "hbm_peak_gib", "fit_dispatch_ms",
        "setup_trace_lower_s", "setup_backend_s", "executable_store_hits",
        "moe_experts_roofline", "mla_decode_roofline", "decode_dispatch_ms",
        "expert_load_max_share"]}
FIT_ONLY = ("data_stage_share", "fit_dispatch_ms")
DECODE_ONLY = ACCEPTED["per_layer"][12:]


@pytest.mark.parametrize("section", sorted(ACCEPTED))
def test_the_accepted_entries_stay_where_they_were(section):
    m = manifest()
    names = [e["name"] for e in m[section]]
    assert names[:len(ACCEPTED[section])] == ACCEPTED[section]
    assert len(names) == len(set(names))
    if section == "configs":
        return
    if section == "workloads":
        assert m["workloads"][3] == {
            "name": AX, "config": "ax_k1", "traffic": "decode_b256_ctx1k",
            "chips": 1, "why": LOOKUP.data("workloads", AX)["why"]}
        return
    # an accepted metric's cells, with this PR's cell taken off the end,
    # are the four accepted cells in their order, or the two of a kind
    for e in m[section][:len(ACCEPTED[section])]:
        if "workloads" not in e:
            assert e["name"] == "setup_s"
            continue
        cells = [c for c in e["workloads"] if c != CELL]
        assert cells == (ACCEPTED["workloads"][:2] if e["name"] in FIT_ONLY
                         else [XING, AX] if e["name"] in DECODE_ONLY
                         else ACCEPTED["workloads"])


def test_both_latent_decode_cells_are_listed_in_the_same_sixteen():
    every = manifest()["end_to_end"] + manifest()["per_layer"]
    listed = {cell: {e["name"] for e in every
                     if cell in e.get("workloads", [cell])}
              for cell in (XING, AX)}
    assert listed[AX] == listed[XING] == {
        "throughput", "setup_s", "dispatches_per_step", "compiles_in_window",
        "device_mfu", "device_idle_share", "unit_stall_share",
        "hbm_peak_gib", "cache_misses_warm", "setup_trace_lower_s",
        "setup_backend_s", "executable_store_hits", "moe_experts_roofline",
        "mla_decode_roofline", "decode_dispatch_ms", "expert_load_max_share"}
    # the share's cell right after the cell it joined, in every list
    for e in every:
        if AX in e.get("workloads", []):
            assert e["workloads"].index(AX) == e["workloads"].index(XING) + 1
    assert LOOKUP.data("workloads", AX)["driver"] == "decode_share"


# ----------------------------------------------------------- the toy cell
@pytest.fixture(scope="module")
def toy_runs(tmp_path_factory):
    out = tmp_path_factory.mktemp("sparse")
    return {trace: run_toy("toy_sparse.decode", trace, seconds=0.5,
                           seed=3700000019, out_dir=out,
                           manifest_path=TOY_MANIFEST)
            for trace in (0, 1)}


def test_toy_sparse_cell_end_to_end_line(toy_runs):
    rc, result, lines = toy_runs[0]
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"throughput", "setup_s"}
    assert result["attempted"] >= 3                     # units
    checks = result["checks"]
    assert sorted(checks) == [
        "logits_rel_err_median_row_first", "logits_rel_err_median_row_last",
        "logits_rel_err_overall_row_first",
        "logits_rel_err_overall_row_last", "nonfinite_logits"]
    # 45-token prompts against a selection of 16: the mechanism decides
    assert all(0 < checks[name][0] < 1e-4 and checks[name][1] == "<=0.0001"
               for name in checks if name != "nonfinite_logits")
    # no control was asked for: none is computed, compiled or noted
    assert not any("control" in l or "selection shared" in l for l in lines)


def test_toy_sparse_cell_per_layer_line(toy_runs):
    rc, result, _ = toy_runs[1]
    metrics = result["metrics"]
    assert rc == 0 and result["correct"] is True
    assert metrics["dispatches_per_step"]["value"] == pytest.approx(7 / 6)
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["decode_dispatch_ms"]["value"] > 0
    assert 100 / 8 <= metrics["expert_load_max_share"]["value"] <= 100
    # no device trace on the CPU: what reads one is left out, not zero
    for name in ("device_mfu", "device_idle_share", "moe_experts_roofline",
                 *NEW):
        assert name not in metrics


@pytest.mark.parametrize("control", ["fp8", "dense"])
def test_a_control_run_comes_out_not_correct(control, tmp_path, monkeypatch):
    """A control's reference is held to the cell's own limits through
    ``checks``: the program's logits pass them, the control's do not."""
    monkeypatch.setenv("BENCH_DECODE_CONTROL", control)
    rc, result, lines = run_toy("toy_sparse.decode", 0, seconds=0.2,
                                seed=3700000020, out_dir=tmp_path,
                                manifest_path=TOY_MANIFEST)
    assert result["correct"] is False and result["failed"] == 0
    checks = result["checks"]
    limit = decode_sparse.BOUNDS["float32"]["median"]
    mine = {n: v for n, v in checks.items() if not n.startswith("control_")}
    assert len(mine) == 5 and all(v <= float(l[2:]) for v, l in mine.values())
    theirs = {n: v for n, v in checks.items() if n.startswith("control_")}
    assert sorted(theirs) == [
        f"control_{control}_logits_rel_err_{kind}_{row}"
        for kind in ("median", "overall") for row in ("row_first",
                                                      "row_last")]
    assert all(v > 100 * limit and l == f"<={limit}"
               for v, l in theirs.values()), theirs
    # and the note on the selection both sides share: float32 on both,
    # the same sets
    shared = next(l for l in lines if "selection shared" in l)
    assert shared.count("'mean': 1.0, 'least': 1.0") == 2, shared


# --------------------------------------------- operations and bytes by hand
def test_operations_of_a_token_counted_by_hand():
    """hidden 64, 4 query / 2 key-value heads of 16, an indexer of 3
    heads of 8, topk 16, 4 held of 8 experts of 32, top-2, 2 layers,
    vocabulary 256, at a context of 40 rows (24 beyond the selection)
    and of 10 (all of them read)."""
    def by_hand(context):
        attention = (64 * 4 * 16 + 2 * 64 * 2 * 16          # q, k, v
                     + 2 * 4 * min(context, 16) * 16        # scores, context
                     + 4 * 16 * 64)                         # o
        indexer = 64 * 3 * 8 + 64 * 8 + 64 * 3 + 3 * context * 8
        moe = 64 * 8 + 3 * 64 * 32 * (2 * 4 / 8)
        return 2 * (attention + indexer + moe) + 64 * 256
    assert by_hand(40) == 2 * (14336 + 3200 + 6656) + 16384
    for context in (40, 10):
        layers = family.layers(SMALL, context=context)
        assert sum(l["macs"] for l in layers) == by_hand(context)
    assert [l["name"] for l in layers] == ["L0_attn", "L0_moe", "L1_attn",
                                           "L1_moe", "head"]
    # the harness's own entry point finds the family (default context)
    assert flops.macs_per_item(SMALL) == sum(
        l["macs"] for l in family.layers(SMALL, context=4096))


def test_kernel_counts_by_hand():
    idx = family.indexer_kernel(SMALL, rows=3, slots=64)
    assert idx["flops"] == 2 * (2 * 3 * 3 * 64 * 8)
    assert idx["bytes"] == 2 * 2 * (3 * 64 * 8 + 64 * (3 * 8 + 8 + 3))
    att = family.sparse_attention_kernel(SMALL, rows=3, selected=16)
    assert att["flops"] == 2 * (4 * 3 * 4 * 16 * 16)
    assert att["bytes"] == 2 * (3 * 16 * 2 * 2 * 16 * 2)
    # what decode_sessions asks every decode family for by this name
    assert family.mla_decode_kernel(SMALL, 3, 64) == att
    assert family.mla_decode_kernel(SMALL, 3, 8) == \
        family.sparse_attention_kernel(SMALL, 3, 8)
    # asked for by ``decode_sessions``; this cell's driver drops the count
    moe = family.moe_experts_kernel(SMALL, tokens=3, experts_touched=[3, 4],
                                    held_picks=[2.0, 1.5])
    assert moe["flops"] == 2 * (2.0 + 1.5) * 3 * 64 * 32
    assert moe["bytes"] == 2 * ((3 + 4) * 3 * 64 * 32 + 2 * 2 * 3 * 64)
    mean = family.moe_experts_kernel(SMALL, tokens=8, experts_touched=[4])
    assert mean["flops"] == 2 * (8 * 2 * 4 / 8) * 3 * 64 * 32


def test_the_real_cell_reads_what_the_issue_reckoned():
    cfg = LOOKUP.data("configs", "keye_vl2_30b_a3b")
    # a row a layer: 4.19 MB of indexer keys, 4.19 MB of selected rows
    idx = family.indexer_kernel(cfg, 8, 32768)
    assert idx["bytes"] / 1e9 == pytest.approx(
        6 * (8 * 4.194e-3 + 2.26e-3 * 2), rel=0.01)
    att = family.sparse_attention_kernel(cfg, 8, 2048)
    assert att["bytes"] == 6 * 8 * 2048 * 2 * 4 * 128 * 2
    assert (idx["bytes"] + att["bytes"]) / 1e9 == pytest.approx(
        0.40 + 0.027, abs=0.01)
    # dense attention over the same rings would read 3.42 GB
    assert 6 * 8 * 32768 * 2 * 4 * 128 * 2 / 1e9 == pytest.approx(3.22,
                                                                  abs=0.01)
    # the dense experts form reads all 16 held experts: 151 MB a layer
    moe = family.moe_experts_kernel(cfg, 8, [16] * 6)
    assert moe["bytes"] / 6 / 1e6 == pytest.approx(151, abs=0.5)
    # one held pick a token on average: 8 x 16 / 128
    assert moe["flops"] == 6 * 2.0 * 8 * 3 * 2048 * 768
    per_token = 2 * sum(l["macs"] for l in family.layers(cfg, context=32736))
    assert 0.5e9 < per_token < 1.5e9


# -------------------------------------------------------------- the driver
def test_the_driver_files_the_new_kernels_and_applies_its_limits(
        monkeypatch):
    traffic = LOOKUP.data("traffic", "decode_b8_ctx32k")
    cfg = LOOKUP.data("configs", "keye_vl2_30b_a3b")
    seen = []

    def measured(run, state):
        seen.append(os.environ.get("BENCH_DECODE_CONTROL"))
        return {"checks": {
            "logits_rel_err_median_row_first": [0.02, "<=0.05"],
            "logits_rel_err_overall_row_first": [0.03, "<=0.5"],
            "logits_rel_err_median_row_last": [0.02, "<=0.05"],
            "logits_rel_err_overall_row_last": [0.4, "<=0.5"],
            "nonfinite_logits": [0, "<=0"]},
            "correct": True, "trace_items": 8 * 64,
            "kernels": {"moe_experts": {"flops": 1.0, "bytes": 1.0},
                        "mla_decode": {"flops": 1.0, "bytes": 1.0}},
            "notes": []}
    monkeypatch.setattr(decode_sparse.base, "measure", measured)
    monkeypatch.delenv("BENCH_DECODE_CONTROL", raising=False)
    pol = types.SimpleNamespace(compute_dtype=np.dtype("float32"))
    net = types.SimpleNamespace(_pol=lambda: pol, params={})
    state = {"net": net, "ids": np.zeros((8, 5), np.int32),
             "last": types.SimpleNamespace(ids=np.ones((8, 3), np.int32))}
    fake = types.SimpleNamespace(traffic=traffic, cfg=cfg)
    record = decode_sparse.measure(fake, state)
    kernels = record["kernels"]
    # the experts' kernel is not this cell's to count (the module says why)
    assert sorted(kernels) == ["indexer", "sparse_attention"]
    assert kernels["indexer"] == {
        k: v * 64 for k, v in family.indexer_kernel(cfg, 8, 32768).items()}
    assert kernels["sparse_attention"] == {
        k: v * 64
        for k, v in family.sparse_attention_kernel(cfg, 8, 2048).items()}
    assert record["correct"] is False          # float32 limits: 1e-4
    pol.compute_dtype = jnp.bfloat16
    record = decode_sparse.measure(fake, state)
    limits = decode_sparse.BOUNDS["bfloat16"]
    assert record["checks"]["logits_rel_err_overall_row_last"][1] == \
        f"<={limits['overall']}"
    assert record["correct"] is False          # 0.4 overall
    assert not any(n.startswith("control_") for n in record["checks"])

    # a control: hidden from ``decode_sessions``, judged here by the
    # same limits, rows 0 and 7 over the prompt and all but the last id
    asked = []
    monkeypatch.setattr(decode_sparse.base, "reference_for",
                        lambda run, net, *a, **kw: asked.append(kw) or "ref")
    monkeypatch.setattr(
        decode_sparse.base, "compare", lambda run, state, ref: {
            "finite": True,
            "row_first": {"median": 0.06, "overall": 0.07, "max": 0.1},
            "row_last": {"median": 0.04, "overall": 0.08, "max": 0.1}})
    monkeypatch.setattr(
        decode_sparse, "_shared_selection", lambda run, net: (
            lambda params, ids: asked.append(ids.tolist()) or (0.995, 0.99)))
    monkeypatch.setattr(
        decode_sparse.base, "measure", lambda run, state: dict(
            measured(run, state), checks={
                "logits_rel_err_median_row_first": [0.02, "<=0.05"],
                "nonfinite_logits": [0, "<=0"]}))
    for name, kw in decode_sparse.CONTROLS.items():
        monkeypatch.setenv("BENCH_DECODE_CONTROL", name)
        del asked[:]
        record = decode_sparse.measure(fake, state)
        assert seen[-1] is None
        assert os.environ["BENCH_DECODE_CONTROL"] == name
        assert asked == [kw] + [[[0] * 5 + [1] * 2]] * 2
        assert record["checks"][
            f"control_{name}_logits_rel_err_median_row_first"] == [
                0.06, f"<={limits['median']}"]
        assert record["checks"][
            f"control_{name}_logits_rel_err_overall_row_last"] == [
                0.08, f"<={limits['overall']}"]
        assert record["correct"] is False
        assert any("'mean': 0.995, 'least': 0.99" in n
                   for n in record["notes"])
    monkeypatch.setenv("BENCH_DECODE_CONTROL", "none")
    record = decode_sparse.measure(fake, state)
    assert record["correct"] is True and len(record["checks"]) == 2


# ------------------------------------------------------------- the readers
PEAKS = {"flops_per_s_bf16": 100.0, "hbm_bytes_per_s": 10.0}


def record(rows, kernels):
    return {"trace": {"by_scope": rows, "devices": 1, "busy_s": 20.0},
            "peaks": PEAKS, "kernels": kernels}


ROWS = [["layer.L1_attn.indexer", "forward", 1.5, 10],
        ["layer.L2_attn.indexer", "forward", 0.5, 10],
        ["layer.L1_attn.sparse_attention", "forward", 3.0, 10],
        ["layer.L2_attn.sparse_attention", "forward", 1.0, 10],
        ["layer.L1_attn.select", "forward", 2.0, 3],
        ["layer.L2_attn.select", "forward", 3.0, 3],
        ["layer.L1_attn", "forward", 7.0, 3], ["unscoped", "other", 1.0, 1]]


@pytest.mark.parametrize("name,kernel,seconds", [
    ("indexer_roofline", "indexer", 2.0),
    ("sparse_attention_roofline", "sparse_attention", 4.0)])
def test_roofline_readers_on_hand_made_rows(name, kernel, seconds):
    read = LOOKUP.module("layer_metrics", name).read
    # bytes bound: 10 bytes at 10 bytes/s = 1 s of the scope's seconds
    assert read(record(ROWS, {kernel: {"flops": 50.0, "bytes": 10.0}})) \
        == pytest.approx(100.0 / seconds)
    # operations bound: 150 at 100/s = 1.5 s
    assert read(record(ROWS, {kernel: {"flops": 150.0, "bytes": 1.0}})) \
        == pytest.approx(150.0 / seconds)
    # nothing to read is None, never 0: no such scope (a program without
    # the mechanism), no count, no trace
    assert read(record(ROWS[4:], {kernel: {"flops": 1.0, "bytes": 1.0}})) \
        is None
    assert read(record(ROWS, {})) is None
    assert read({"trace": None, "peaks": PEAKS}) is None
    assert read({}) is None


def test_select_share_on_hand_made_rows():
    read = LOOKUP.module("layer_metrics", "sparse_select_share").read
    assert read(record(ROWS, {})) == pytest.approx(25.0)    # 5 s of 20
    assert read(record(ROWS[:4] + ROWS[6:], {})) is None    # no such scope
    assert read({"trace": {"by_scope": ROWS, "busy_s": 0.0}}) is None
    assert read({"trace": None}) is None and read({}) is None


def test_the_reference_is_independent_of_the_package():
    path = os.path.join(BENCH, "reference", "gqa_sparse_moe.py")
    with open(path) as fh:
        source = fh.read()
    assert "deeplearning4j_tpu" not in source.split('"""', 2)[2]
    module = importlib.import_module("benchmark.reference.gqa_sparse_moe")
    assert callable(module.forward) and callable(module.Forward)
    assert module.held_experts({"builder_args": {"experts_held": [3]}}) == [3]
    assert module.held_experts({}) is None
    assert module.router_width({"num_experts": 4,
                                "published": {"num_experts": 8}}) == 8
