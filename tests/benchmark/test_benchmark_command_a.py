"""The window-and-full cell's own pieces on the CPU: the configuration's
file against its source's values key by key and its three cuts, where
the manifest lists the cell (from the START of each list: nothing here
pins an end), a toy cell through the ``decode_mixed`` driver and
``main()`` with its three controls, the family's operations and bytes
counted by hand, the driver's kernel counts, and the three new readers
on hand-made records."""

import importlib
import json
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchtools import (ACCEPTED_PER_LAYER, ROOT, TOY, WIDTH,
                        check_config_file, check_manifest, manifest, run_toy)
from benchmark import flops, run
from benchmark.drivers import decode_mixed
from benchmark.families import gqa_window_share as family

CELL = "command_a_plus_05_2026.decode_b8_ctx32k_w4k"
CONFIG = "command_a_plus_05_2026"
KEYE = "keye_vl2_30b_a3b.decode_b8_ctx32k"
AX = "ax_k1.decode_b256_ctx1k"
XING = "xing4_29b_a4b.decode_b64_ctx4k"
TOY_MANIFEST = os.path.join(TOY, "BENCHMARK.window.json")
BENCH = os.path.join(ROOT, "benchmark")
LOOKUP = run.Lookup([BENCH])
SMALL = run.Lookup([BENCH, TOY]).data("configs", "toy_window")
NEW = ["window_attention_roofline", "full_attention_roofline",
       "window_state_share"]
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]

#: the catalog row's ``config`` (``command-a-plus-05-2026``), every key
#: but the three cut ones
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "layer_types": PERIOD * 8, "logit_scale": 1,
    "max_position_embeddings": 200000, "model_type": "cohere2_moe",
    "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_key_value_heads": 8,
    "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False}


# ------------------------------------------------------ the configuration
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_file_holds_the_sources_value(key):
    cfg = LOOKUP.data("configs", CONFIG)
    assert cfg[key] == PUBLISHED[key] and type(cfg[key]) is type(
        PUBLISHED[key])


def test_configuration_explains_its_three_cuts():
    cfg = LOOKUP.data("configs", CONFIG)
    assert cfg["source"] == ("https://huggingface.co/CohereLabs/"
                             "command-a-plus-05-2026/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                                "vocab_size": 262144}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 32768)
    entry = next(c for c in manifest()["configs"] if c["name"] == CONFIG)
    check_config_file(entry, cfg)
    # the guide's floors: a whole period and four layers, eight experts,
    # an eighth of the ids
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == PERIOD
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["builder_args"] == {"init_std": 0.02,
                                   "experts_held": list(range(16)),
                                   "max_chunk": 256}
    for word in ("8 v5e", "16 a chip", "data-parallel", "32,768", "4 of 32",
                 "seven further pipeline stages", "bf16", "vision tower",
                 "four shared experts"):
        assert word in cfg["deployment"], word
    for key in ("depth", "share", "vocabulary", "expert_width",
                "shared_average", "routing", "positions", "window", "norm",
                "unused_keys", "chunk", "init", "sampling", "precision"):
        assert key in cfg["assumed"], key
    # each reading the config leaves open names its other reading
    for key in ("expert_width", "shared_average", "positions", "window"):
        assert "other reading" in cfg["assumed"][key], key
    assert (cfg["reference"], cfg["family"]) == ("gqa_window_moe",
                                                 "gqa_window_share")
    # no width is cut, and none may be named
    for key in cfg["reduced"]:
        assert not WIDTH.search(key), key


def test_the_traffic_is_the_issues_table():
    cfg = LOOKUP.data("configs", CONFIG)
    traffic = LOOKUP.data("traffic", "decode_b8_ctx32k_w4k")
    other = LOOKUP.data("traffic", "decode_b8_ctx32k")
    # the other share's numbers, so the two attentions read side by side
    for key in ("rows", "prompt_tokens", "prefill_chunk", "ring_slots",
                "new_tokens", "trace_after_units", "trace_units", "item"):
        assert traffic[key] == other[key], key
    assert (traffic["rows"], traffic["prompt_tokens"], traffic["ring_slots"],
            traffic["new_tokens"]) == (8, 32704, 32768, 64)
    assert traffic["prompt_tokens"] + traffic["new_tokens"] <= 32768 + 1
    assert traffic["prefill_chunk"] <= cfg["builder_args"]["max_chunk"]
    # the experts see an eighth of what the deployment's would
    assert traffic["rows"] * cfg["num_experts_per_tok"] / 128 == 0.5
    cell = LOOKUP.data("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["driver"],
            cell["chips"]) == (CONFIG, "decode_b8_ctx32k_w4k",
                               "decode_mixed", 1)
    for word in ("0.5 tokens", "4 layers", "wrap", "closed loop"):
        assert word in cell["why"], word
    assert len(cell["why"]) <= 200


@pytest.mark.parametrize("which", ["real", "toy"])
def test_manifest_with_the_cell_meets_the_contract(which):
    if which == "real":
        check_manifest(manifest(), LOOKUP, accepted=ACCEPTED_PER_LAYER)
    else:
        with open(TOY_MANIFEST) as fh:
            check_manifest(json.load(fh), run.Lookup([BENCH, TOY]),
                           allowed_chips=(1,))


def test_the_cell_is_in_every_list_the_issue_names():
    m = manifest()
    every = m["end_to_end"] + m["per_layer"]
    mine = {e["name"] for e in every if CELL in e.get("workloads", [CELL])}
    assert mine >= {
        "throughput", "setup_s", "dispatches_per_step", "compiles_in_window",
        "device_mfu", "cache_misses_warm", "device_idle_share",
        "unit_stall_share", "hbm_peak_gib", "setup_trace_lower_s",
        "setup_backend_s", "executable_store_hits", "decode_dispatch_ms",
        "expert_load_max_share", "hbm_traffic_share",
        "idle_in_program_share", "idle_between_programs_share", *NEW}
    # not the experts' roofline (the dense form at 8 tokens a step reads
    # every held expert: PERF.md), nor the other attentions' metrics
    for name in ("moe_experts_roofline", "mla_decode_roofline",
                 "indexer_roofline", "sparse_attention_roofline",
                 "sparse_select_share", "data_stage_share",
                 "fit_dispatch_ms"):
        assert name not in mine, name
    # appended: after every cell that was there, once, in each list
    for e in every:
        cells = e.get("workloads", [])
        if CELL in cells:
            assert cells.count(CELL) == 1
            if KEYE in cells:
                assert cells.index(CELL) > cells.index(KEYE)
    names = [c["name"] for c in m["configs"]]
    assert names.index(CONFIG) > names.index("keye_vl2_30b_a3b")
    cells = [w["name"] for w in m["workloads"]]
    assert cells.index(CELL) > cells.index(KEYE)
    assert m["workloads"][cells.index(CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "decode_b8_ctx32k_w4k",
        "chips": 1, "why": LOOKUP.data("workloads", CELL)["why"]}
    layers = [e["name"] for e in m["per_layer"]]
    at = layers.index(NEW[0])
    assert layers[at:at + 3] == NEW
    assert at > layers.index("idle_between_programs_share")
    for e in m["per_layer"][at:at + 3]:
        assert (e["moves"], e["workloads"], e["unit"]) == (
            "throughput", [CELL], "%")
    assert [e["layer"] for e in m["per_layer"][at:at + 3]] == [
        "step program", "step program", "serving"]


#: the manifest as PR 40 left it, by name and from the START of each list
ACCEPTED = {
    "configs": ["resnet50", "vgg16", "xing4_29b_a4b", "ax_k1",
                "keye_vl2_30b_a3b"],
    "workloads": ["resnet50.fit_cached", "vgg16.fit_cached", XING, AX,
                  KEYE],
    "end_to_end": ["throughput", "setup_s"],
    "per_layer": [
        "data_stage_share", "dispatches_per_step", "compiles_in_window",
        "device_mfu", "cache_misses_warm", "device_idle_share",
        "unit_stall_share", "hbm_peak_gib", "fit_dispatch_ms",
        "setup_trace_lower_s", "setup_backend_s", "executable_store_hits",
        "moe_experts_roofline", "mla_decode_roofline", "decode_dispatch_ms",
        "expert_load_max_share", "indexer_roofline",
        "sparse_attention_roofline", "sparse_select_share",
        "hbm_traffic_share", "idle_in_program_share",
        "idle_between_programs_share"]}
#: the cells each accepted metric listed, in their order
FIT, DECODE = ACCEPTED["workloads"][:2], [XING, AX, KEYE]
LISTED = {
    "data_stage_share": FIT, "fit_dispatch_ms": FIT,
    "moe_experts_roofline": [XING, AX], "mla_decode_roofline": [XING, AX],
    "decode_dispatch_ms": DECODE, "expert_load_max_share": DECODE,
    "indexer_roofline": [KEYE], "sparse_attention_roofline": [KEYE],
    "sparse_select_share": [KEYE]}


@pytest.mark.parametrize("section", sorted(ACCEPTED))
def test_the_accepted_entries_keep_their_places(section):
    """What the marked tests of ``tests/conftest.py`` hold, from the
    start of each list: the names in their order, and each accepted
    metric's cells as a PREFIX of its list, whatever follows."""
    m = manifest()
    names = [e["name"] for e in m[section]]
    assert names[:len(ACCEPTED[section])] == ACCEPTED[section]
    assert len(names) == len(set(names))
    if section in ("configs", "workloads"):
        return
    for e in m[section][:len(ACCEPTED[section])]:
        if "workloads" not in e:
            assert e["name"] == "setup_s"
            continue
        was = LISTED.get(e["name"], ACCEPTED["workloads"])
        assert e["workloads"][:len(was)] == was, e["name"]
        assert len(e["workloads"]) == len(set(e["workloads"]))


@pytest.mark.parametrize("name", ["hbm_traffic_share",
                                  "idle_in_program_share",
                                  "idle_between_programs_share"])
def test_the_cost_readers_entries_say_what_the_readers_say(name):
    """``test_benchmark_cost_readers``' marked test, with the cells a
    prefix instead of the whole list."""
    m = manifest()
    (entry,) = [e for e in m["per_layer"] if e["name"] == name]
    reader = LOOKUP.module("layer_metrics", name)
    assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.BETTER) == (
        entry["unit"], entry["source"], entry["layer"], entry["better"])
    assert entry["moves"] == "throughput"
    cells = [w["name"] for w in m["workloads"]]
    assert entry["workloads"] == cells[:len(entry["workloads"])]
    assert len(entry["workloads"]) >= 5


# ----------------------------------------------------------- the toy cell
@pytest.fixture(scope="module")
def toy_runs(tmp_path_factory):
    out = tmp_path_factory.mktemp("window")
    return {trace: run_toy("toy_window.decode", trace, seconds=0.5,
                           seed=4100000021, out_dir=out,
                           manifest_path=TOY_MANIFEST)
            for trace in (0, 1)}


def test_toy_cell_end_to_end_line(toy_runs):
    rc, result, lines = toy_runs[0]
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"throughput", "setup_s"}
    assert result["attempted"] >= 3                     # units
    checks = result["checks"]
    assert sorted(checks) == [
        "logits_rel_err_median_row_first", "logits_rel_err_median_row_last",
        "logits_rel_err_overall_row_first",
        "logits_rel_err_overall_row_last", "nonfinite_logits"]
    # 300-token prompts against a window of 8 in rings of 128: the rings
    # wrapped twice, and the mechanism decides
    assert all(0 < checks[name][0] < 1e-4 and checks[name][1] == "<=0.0001"
               for name in checks if name != "nonfinite_logits")
    assert not any("control" in l for l in lines)
    assert any("set-up phases" in l and "the comparison" in l
               for l in lines)


def test_toy_cell_per_layer_line(toy_runs):
    rc, result, _ = toy_runs[1]
    metrics = result["metrics"]
    assert rc == 0 and result["correct"] is True
    assert metrics["dispatches_per_step"]["value"] == pytest.approx(7 / 6)
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["decode_dispatch_ms"]["value"] > 0
    assert 100 / 16 <= metrics["expert_load_max_share"]["value"] <= 100
    # 3 window rings of 128 slots beside a full ring of 320, a cursor each
    slot = 3 * 4 * 16 * 4
    assert metrics["window_state_share"]["value"] == pytest.approx(
        100 * 3 * (128 * slot + 4) / ((3 * 128 + 320) * slot + 4 * 4))
    assert metrics["window_state_share"]["unit"] == "%"
    # no device trace on the CPU: what reads one is left out, not zero
    for name in ("device_mfu", "device_idle_share", "moe_experts_roofline",
                 "mla_decode_roofline", *NEW[:2]):
        assert name not in metrics


@pytest.mark.parametrize("control", sorted(decode_mixed.CONTROLS))
def test_a_control_run_comes_out_not_correct(control, tmp_path, monkeypatch):
    """A control's reference is held to the cell's own limits through
    ``checks``: the program's logits pass them, the control's do not."""
    monkeypatch.setenv("BENCH_DECODE_CONTROL", control)
    rc, result, lines = run_toy("toy_window.decode", 0, seconds=0.2,
                                seed=4100000022, out_dir=tmp_path,
                                manifest_path=TOY_MANIFEST)
    assert result["correct"] is False and result["failed"] == 0
    checks = result["checks"]
    limit = decode_mixed.BOUNDS["float32"]["median"]
    mine = {n: v for n, v in checks.items() if not n.startswith("control_")}
    assert len(mine) == 5 and all(v <= float(l[2:]) for v, l in mine.values())
    theirs = {n: v for n, v in checks.items() if n.startswith("control_")}
    assert sorted(theirs) == [
        f"control_{control}_logits_rel_err_{kind}_{row}"
        for kind in ("median", "overall") for row in ("row_first",
                                                      "row_last")]
    assert all(v > 100 * limit and l == f"<={limit}"
               for v, l in theirs.values()), theirs
    assert any(f"control {control}" in l for l in lines)


# --------------------------------------------- operations and bytes by hand
def test_operations_of_a_token_counted_by_hand():
    """hidden 64, 4 query / 2 key-value heads of 16, a window of 8, 4
    held of 16 experts of 32 top-3 beside 4 shared ones, three window
    layers and a full one, vocabulary 256, at a context of 40 rows (32
    beyond the window) and of 5 (inside it)."""
    def by_hand(context):
        projections = 64 * 4 * 16 + 2 * 64 * 2 * 16 + 4 * 16 * 64
        window = projections + 2 * 4 * min(context, 8) * 16
        full = projections + 2 * 4 * context * 16
        moe = 64 * 16 + 3 * 64 * 32 * (3 * 4 / 16 + 4)
        return 3 * window + full + 4 * moe + 64 * 256
    assert by_hand(40) == 3 * 13312 + 17408 + 4 * 30208 + 16384
    for context in (40, 5):
        layers = family.layers(SMALL, context=context)
        assert sum(l["macs"] for l in layers) == by_hand(context)
    assert [l["name"] for l in layers] == [
        "L0_attn", "L0_moe", "L1_attn", "L1_moe", "L2_attn", "L2_moe",
        "L3_attn", "L3_moe", "head"]
    assert [l["kind"] for l in layers[::2]] == PERIOD + ["dense"]
    # the harness's own entry point finds the family (default context)
    assert flops.macs_per_item(SMALL) == sum(
        l["macs"] for l in family.layers(SMALL, context=4096))
    assert family.layer_kinds(SMALL) == PERIOD
    assert family.published_experts(SMALL) == 16


def test_kernel_counts_by_hand():
    win = family.window_attention_kernel(SMALL, rows=3, visible=8)
    assert win["flops"] == 3 * (4 * 3 * 4 * 8 * 16)
    assert win["bytes"] == 3 * (3 * 8 * 2 * 2 * 16 * 2)
    full = family.full_attention_kernel(SMALL, rows=3, visible=300.5)
    assert full["flops"] == 4 * 3 * 4 * 300.5 * 16
    assert full["bytes"] == 3 * 300.5 * 2 * 2 * 16 * 2
    # what decode_sessions asks every decode family for by this name
    assert family.mla_decode_kernel(SMALL, 3, 320) == \
        family.full_attention_kernel(SMALL, 3, 320)
    # asked for by ``decode_sessions``; this cell's driver drops the count
    moe = family.moe_experts_kernel(SMALL, tokens=3, experts_touched=[3, 4],
                                    held_picks=[2.0, 1.5])
    assert moe["flops"] == 2 * (2.0 + 1.5) * 3 * 64 * 32
    assert moe["bytes"] == 2 * ((3 + 4) * 3 * 64 * 32 + 2 * 2 * 3 * 64)
    mean = family.moe_experts_kernel(SMALL, tokens=8, experts_touched=[4])
    assert mean["flops"] == 2 * (8 * 3 * 4 / 16) * 3 * 64 * 32


def test_the_real_cell_reads_what_the_issue_reckoned():
    cfg = LOOKUP.data("configs", CONFIG)
    # the full ring 1.07 GB a step, each window 0.13 GB
    full = family.full_attention_kernel(cfg, 8, 32768)
    assert full["bytes"] == 8 * 32768 * 2 * 8 * 128 * 2
    assert full["bytes"] / 1e9 == pytest.approx(1.07, abs=0.01)
    win = family.window_attention_kernel(cfg, 8, 4096)
    assert win["bytes"] / 3 / 1e9 == pytest.approx(0.134, abs=0.001)
    # both are bound by their bytes: 1.31 ms and 0.16 ms a layer
    assert full["bytes"] / 819e9 * 1e3 == pytest.approx(1.31, abs=0.01)
    assert win["bytes"] / 3 / 819e9 * 1e3 == pytest.approx(0.164, abs=0.002)
    assert full["flops"] / 197e12 < full["bytes"] / 819e9
    # the dense experts form reads all 16 held experts: 1.61 GB a layer
    moe = family.moe_experts_kernel(cfg, 8, [16] * 4)
    assert moe["bytes"] / 4 / 1e9 == pytest.approx(1.61, abs=0.005)
    # one held pick a token on average (8 x 16 / 128) beside 4 shared
    layers = family.layers(cfg, context=32736)
    per_token = 2 * sum(l["macs"] for l in layers)
    experts = 2 * 4 * (4096 * 128 + 3 * 4096 * 4096 * (1 + 4))
    assert 2 * sum(l["macs"] for l in layers
                   if l["kind"] == "experts") == experts
    # 6.4 GFLOP a token: a third of it the full layer's 32k rows
    assert 6.0e9 < per_token < 6.8e9
    assert 2 * layers[6]["macs"] / per_token == pytest.approx(0.38, abs=0.01)


# -------------------------------------------------------------- the driver
def test_the_driver_files_the_new_kernels_and_applies_its_limits(
        monkeypatch):
    traffic = LOOKUP.data("traffic", "decode_b8_ctx32k_w4k")
    cfg = LOOKUP.data("configs", CONFIG)
    seen = []

    def measured(run, state):
        seen.append(os.environ.get("BENCH_DECODE_CONTROL"))
        return {"checks": {
            "logits_rel_err_median_row_first": [0.02, "<=0.05"],
            "logits_rel_err_overall_row_first": [0.03, "<=0.5"],
            "logits_rel_err_median_row_last": [0.02, "<=0.05"],
            "logits_rel_err_overall_row_last": [0.03, "<=0.5"],
            "nonfinite_logits": [0, "<=0"]},
            "correct": True, "trace_items": 8 * 64, "window_s": 1.0,
            "kernels": {"moe_experts": {"flops": 1.0, "bytes": 1.0},
                        "mla_decode": {"flops": 1.0, "bytes": 1.0}},
            "notes": []}
    monkeypatch.setattr(decode_mixed.base, "measure", measured)
    monkeypatch.delenv("BENCH_DECODE_CONTROL", raising=False)
    pol = types.SimpleNamespace(compute_dtype=np.dtype("float32"))
    net = types.SimpleNamespace(_pol=lambda: pol, params={})
    state = {"net": net, "ids": np.zeros((8, 5), np.int32),
             "setup_phases": {"build_net": 1.0},
             "last": types.SimpleNamespace(ids=np.ones((8, 3), np.int32))}
    fake = types.SimpleNamespace(traffic=traffic, cfg=cfg)
    record = decode_mixed.measure(fake, state)
    kernels = record["kernels"]
    # the experts' kernel is not this cell's to count (the module says why)
    assert sorted(kernels) == ["full_attention", "window_attention"]
    context = 32703 + 32.5
    assert kernels["full_attention"] == {
        k: v * 64
        for k, v in family.full_attention_kernel(cfg, 8, context).items()}
    assert kernels["window_attention"] == {
        k: v * 64
        for k, v in family.window_attention_kernel(cfg, 8, 4096).items()}
    assert record["correct"] is False          # float32 limits: 1e-4
    pol.compute_dtype = jnp.bfloat16
    record = decode_mixed.measure(fake, state)
    limits = decode_mixed.BOUNDS["bfloat16"]
    assert record["checks"]["logits_rel_err_overall_row_last"][1] == \
        f"<={limits['overall']}"
    assert record["correct"] is (0.02 <= limits["median"]
                                 and 0.03 <= limits["overall"])
    assert not any(n.startswith("control_") for n in record["checks"])

    # a control: hidden from ``decode_sessions``, judged here by the
    # same limits
    asked = []
    monkeypatch.setattr(decode_mixed.base, "reference_for",
                        lambda run, net, *a, **kw: asked.append(kw) or "ref")
    monkeypatch.setattr(
        decode_mixed.base, "compare", lambda run, state, ref: {
            "finite": True,
            "row_first": {"median": 0.9, "overall": 0.07, "max": 1.0},
            "row_last": {"median": 0.04, "overall": 0.9, "max": 1.0}})
    for name, kw in decode_mixed.CONTROLS.items():
        monkeypatch.setenv("BENCH_DECODE_CONTROL", name)
        del asked[:]
        record = decode_mixed.measure(fake, state)
        assert seen[-1] is None
        assert os.environ["BENCH_DECODE_CONTROL"] == name
        assert asked == [kw]
        assert record["checks"][
            f"control_{name}_logits_rel_err_median_row_first"] == [
                0.9, f"<={limits['median']}"]
        assert record["checks"][
            f"control_{name}_logits_rel_err_overall_row_last"] == [
                0.9, f"<={limits['overall']}"]
        assert record["correct"] is False
    monkeypatch.setenv("BENCH_DECODE_CONTROL", "none")
    record = decode_mixed.measure(fake, state)
    assert len(record["checks"]) == 5
    assert sorted(decode_mixed.CONTROLS) == ["all_full", "fp8", "rope_all"]


def test_the_limits_tell_bf16_from_the_fp8_control():
    bounds = decode_mixed.BOUNDS
    assert bounds["float32"] == {"median": 1e-4, "overall": 1e-4}
    assert set(bounds["bfloat16"]) == {"median", "overall"}
    assert 1e-3 < bounds["bfloat16"]["median"] <= 0.05
    assert bounds["bfloat16"]["median"] <= bounds["bfloat16"]["overall"]


# ------------------------------------------------------------- the readers
PEAKS = {"flops_per_s_bf16": 100.0, "hbm_bytes_per_s": 10.0}


def record(rows, kernels):
    return {"trace": {"by_scope": rows, "devices": 1, "busy_s": 20.0},
            "peaks": PEAKS, "kernels": kernels}


ROWS = [["layer.L0_attn.window_attention", "forward", 1.5, 10],
        ["layer.L1_attn.window_attention", "forward", 0.5, 10],
        ["layer.L3_attn.full_attention", "forward", 4.0, 10],
        ["layer.L1_attn", "forward", 7.0, 3], ["unscoped", "other", 1.0, 1]]


@pytest.mark.parametrize("name,kernel,seconds", [
    ("window_attention_roofline", "window_attention", 2.0),
    ("full_attention_roofline", "full_attention", 4.0)])
def test_roofline_readers_on_hand_made_rows(name, kernel, seconds):
    read = LOOKUP.module("layer_metrics", name).read
    # bytes bound: 10 bytes at 10 bytes/s = 1 s of the scope's seconds
    assert read(record(ROWS, {kernel: {"flops": 50.0, "bytes": 10.0}})) \
        == pytest.approx(100.0 / seconds)
    # operations bound: 150 at 100/s = 1.5 s
    assert read(record(ROWS, {kernel: {"flops": 150.0, "bytes": 1.0}})) \
        == pytest.approx(150.0 / seconds)
    # nothing to read is None, never 0: no such scope (the parent's
    # program), no count, no trace
    assert read(record(ROWS[3:], {kernel: {"flops": 1.0, "bytes": 1.0}})) \
        is None
    assert read(record(ROWS, {})) is None
    assert read({"trace": None, "peaks": PEAKS}) is None
    assert read({}) is None


def test_window_state_share_on_hand_made_gauges():
    read = LOOKUP.module("layer_metrics", "window_state_share").read
    gauge = lambda values: {"monitor_after": {
        "serving_session_state_bytes": {"kind": "gauge", "values": values}}}
    values = {'{model="m"}': 400.0, '{kind="window_kv",model="m"}': 100.0,
              '{kind="kv",model="m"}': 300.0}
    assert read(gauge(values)) == pytest.approx(25.0)
    # every kind a session holds counts: latent rings beside them
    assert read(gauge({**values, '{kind="latent",model="m"}': 100.0})) \
        == pytest.approx(20.0)
    # a program with no window rings (the parent's): nothing, never 0
    assert read(gauge({'{model="m"}': 400.0,
                       '{kind="sparse_kv",model="m"}': 400.0})) is None
    assert read(gauge({})) is None and read({}) is None
    assert read({"monitor_after": None}) is None


def test_the_reference_is_independent_of_the_package():
    path = os.path.join(BENCH, "reference", "gqa_window_moe.py")
    with open(path) as fh:
        source = fh.read()
    assert "deeplearning4j_tpu" not in source.split('"""', 2)[2]
    assert "pallas" not in source and "highest" in source
    module = importlib.import_module("benchmark.reference.gqa_window_moe")
    assert callable(module.forward) and callable(module.Forward)
    assert module.held_experts({"builder_args": {"experts_held": [3]}}) == [3]
    assert module.held_experts({}) is None
    assert module.router_width({"num_experts": 4,
                                "published": {"num_experts": 16}}) == 16
    kinds = [kind for kind, _ in module.Forward(SMALL).layers()]
    assert kinds == PERIOD + ["logits"]
