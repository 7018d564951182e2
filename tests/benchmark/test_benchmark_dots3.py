"""The sparse-and-window latent cell's own pieces on the CPU: the
configuration's file against its source's values key by key and its
three cuts, where the manifest lists the cell (from the START of each
list: nothing here pins an end), a toy cell through the
``decode_sparse_window`` driver and ``main()`` with its four controls,
the family's operations and bytes counted by hand, the driver's kernel
counts, and the two new readers on hand-made records."""

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
from benchmark.drivers import decode_sparse_window as driver
from benchmark.families import mla_sparse_window_share as family

CELL = "dots3_note_prev.decode_b32_ctx32k"
CONFIG = "dots3_note_prev"
XING = "xing4_29b_a4b.decode_b64_ctx4k"
AX = "ax_k1.decode_b256_ctx1k"
KEYE = "keye_vl2_30b_a3b.decode_b8_ctx32k"
COMMAND = "command_a_plus_05_2026.decode_b8_ctx32k_w4k"
TOY_MANIFEST = os.path.join(TOY, "BENCHMARK.dots3.json")
BENCH = os.path.join(ROOT, "benchmark")
LOOKUP = run.Lookup([BENCH])
SMALL = run.Lookup([BENCH, TOY]).data("configs", "toy_dots3")
NEW = ["sparse_latent_roofline", "window_latent_roofline"]
STATE = "window_latent_state_share"
FIRST = ["full_attention", "full_attention"] + ["sliding_attention"] * 3

#: the catalog row's ``config`` (``dots3-note-prev``), every key but the
#: three cut ones
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512,
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_key_value_heads": 128, "q_lora_rank": 1024,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 128}
#: 46 layers: two full ones, then periods of three window layers and a
#: full one
PUBLISHED["layer_types"] = ["full_attention"] + (
    ["full_attention"] + ["sliding_attention"] * 3) * 11 + [
        "full_attention"]


# ------------------------------------------------------ the configuration
@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_file_holds_the_sources_value(key):
    cfg = LOOKUP.data("configs", CONFIG)
    assert cfg[key] == PUBLISHED[key] and type(cfg[key]) is type(
        PUBLISHED[key])


def test_configuration_explains_its_three_cuts():
    cfg = LOOKUP.data("configs", CONFIG)
    assert cfg["source"] == ("https://huggingface.co/dots-studio/"
                             "dots3-note-prev/blob/main/config.json")
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 46,
                                "n_routed_experts": 256, "vocab_size": 152064}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 8, 19008)
    entry = next(c for c in manifest()["configs"] if c["name"] == CONFIG)
    check_config_file(entry, cfg)
    # the guide's floors: the leading dense layer and one whole period,
    # eight experts, an eighth of the ids
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == FIRST
    assert cfg["first_k_dense_replace"] == 1
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["builder_args"] == {"init_std": 0.02,
                                   "experts_held": list(range(8)),
                                   "max_chunk": 64, "router_bias_std": 0.05}
    for word in ("32 v5e", "8 a chip", "data-parallel", "19,008", "5 of 46",
                 "further pipeline stages", "bf16", "audio towers",
                 "the indexer", "the gates", "the shared expert"):
        assert word in cfg["deployment"], word
    for key in ("depth", "share", "vocabulary", "lora_rescale", "gate",
                "indexer", "selection", "window", "routing", "positions",
                "unused_keys", "chunk", "init", "sampling", "precision"):
        assert key in cfg["assumed"], key
    # each reading the config leaves open names its other reading
    for key in ("lora_rescale", "gate", "indexer", "window"):
        assert "other reading" in cfg["assumed"][key], key
    assert "N(0, 0.05)" in cfg["assumed"]["routing"]
    assert (cfg["reference"], cfg["family"]) == ("mla_sparse_window_moe",
                                                 "mla_sparse_window_share")
    # no width is cut, and none may be named
    for key in cfg["reduced"]:
        assert not WIDTH.search(key), key


def test_the_traffic_is_the_cells_table():
    cfg = LOOKUP.data("configs", CONFIG)
    traffic = LOOKUP.data("traffic", "decode_b32_ctx32k")
    other = LOOKUP.data("traffic", "decode_b8_ctx32k")
    # the other 32k cells' context, so the three attentions read side by
    # side
    for key in ("prompt_tokens", "ring_slots", "new_tokens",
                "trace_after_units", "trace_units", "item"):
        assert traffic[key] == other[key], key
    assert (traffic["rows"], traffic["prompt_tokens"],
            traffic["prefill_chunk"], traffic["ring_slots"],
            traffic["new_tokens"]) == (32, 32704, 64, 32768, 64)
    # 2,048 tokens a dispatch, 511 dispatches
    assert traffic["rows"] * traffic["prefill_chunk"] == 2048
    assert -(-(traffic["prompt_tokens"] - 1) // traffic["prefill_chunk"]) \
        == 511
    assert traffic["prompt_tokens"] + traffic["new_tokens"] <= 32768 + 1
    assert traffic["prefill_chunk"] <= cfg["builder_args"]["max_chunk"]
    # each held expert sees a token a step, where the pool's would see 32
    assert traffic["rows"] * cfg["num_experts_per_tok"] / 256 == 1.0
    cell = LOOKUP.data("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["driver"],
            cell["chips"]) == (CONFIG, "decode_b32_ctx32k",
                               "decode_sparse_window", 1)
    for word in ("2,048 of 32k", "513", "wrap", "closed loop",
                 "1 token each"):
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


#: the lists the cell joins, and those it stays off
LISTED_IN = [
    "throughput", "setup_s", "dispatches_per_step", "compiles_in_window",
    "device_mfu", "cache_misses_warm", "device_idle_share",
    "unit_stall_share", "hbm_peak_gib", "setup_trace_lower_s",
    "setup_backend_s", "executable_store_hits", "decode_dispatch_ms",
    "expert_load_max_share", "hbm_traffic_share", "idle_in_program_share",
    "idle_between_programs_share", "indexer_roofline", "sparse_select_share",
    *NEW, STATE]
KEPT_OFF = ["moe_experts_roofline", "mla_decode_roofline",
            "sparse_attention_roofline", "window_attention_roofline",
            "full_attention_roofline", "window_state_share",
            "data_stage_share", "fit_dispatch_ms"]


@pytest.mark.parametrize("name", LISTED_IN + KEPT_OFF)
def test_the_cell_is_in_every_list_it_joins(name):
    m = manifest()
    (entry,) = [e for e in m["end_to_end"] + m["per_layer"]
                if e["name"] == name]
    cells = entry.get("workloads", [CELL])
    assert (CELL in cells) is (name in LISTED_IN)
    if CELL in cells:
        # appended: after every cell that was there, once
        assert cells.count(CELL) == 1 and cells[-1] == CELL or \
            cells.index(CELL) > max(cells.index(c) for c in cells
                                    if c != CELL and c in (XING, AX, KEYE,
                                                           COMMAND))


def test_the_cell_and_the_new_metrics_are_appended():
    m = manifest()
    names = [c["name"] for c in m["configs"]]
    assert names.index(CONFIG) > names.index("command_a_plus_05_2026")
    cells = [w["name"] for w in m["workloads"]]
    assert cells.index(CELL) > cells.index(COMMAND)
    assert m["workloads"][cells.index(CELL)] == {
        "name": CELL, "config": CONFIG, "traffic": "decode_b32_ctx32k",
        "chips": 1, "why": LOOKUP.data("workloads", CELL)["why"]}
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0
    layers = [e["name"] for e in m["per_layer"]]
    at = layers.index(NEW[0])
    assert layers[at:at + 2] == NEW
    assert at > layers.index("window_state_share")
    for e in m["per_layer"][at:at + 2]:
        assert (e["moves"], e["workloads"], e["unit"], e["layer"],
                e["source"], e["better"]) == (
            "throughput", [CELL], "%", "step program", "device_trace",
            "higher")
        reader = LOOKUP.module("layer_metrics", e["name"])
        assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.BETTER) == (
            e["unit"], e["source"], e["layer"], e["better"])
    # the window rings' share of the session state, after the rooflines
    (entry,) = [e for e in m["per_layer"] if e["name"] == STATE]
    assert layers.index(STATE) == at + 2
    assert entry == {"name": STATE, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "serving",
                     "moves": "throughput", "workloads": [CELL]}
    reader = LOOKUP.module("layer_metrics", STATE)
    assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.BETTER) == (
        "%", "program_counter", "serving", "lower")


#: the manifest before this cell, by name and from the START of each list
ACCEPTED = {
    "configs": ["resnet50", "vgg16", "xing4_29b_a4b", "ax_k1",
                "keye_vl2_30b_a3b", "command_a_plus_05_2026"],
    "workloads": ["resnet50.fit_cached", "vgg16.fit_cached", XING, AX,
                  KEYE, COMMAND],
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
        "idle_between_programs_share", "window_attention_roofline",
        "full_attention_roofline", "window_state_share"]}
#: the cells each accepted metric listed, in their order
FIT, DECODE = ACCEPTED["workloads"][:2], [XING, AX, KEYE, COMMAND]
LISTED = {
    "data_stage_share": FIT, "fit_dispatch_ms": FIT,
    "moe_experts_roofline": [XING, AX], "mla_decode_roofline": [XING, AX],
    "decode_dispatch_ms": DECODE, "expert_load_max_share": DECODE,
    "indexer_roofline": [KEYE], "sparse_attention_roofline": [KEYE],
    "sparse_select_share": [KEYE], "window_attention_roofline": [COMMAND],
    "full_attention_roofline": [COMMAND], "window_state_share": [COMMAND]}


@pytest.mark.parametrize("section", sorted(ACCEPTED))
def test_the_accepted_entries_keep_their_places(section):
    """The names in their order, and each accepted metric's cells as a
    PREFIX of its list, whatever follows."""
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


# ----------------------------------------------------------- the toy cell
@pytest.fixture(scope="module")
def toy_runs(tmp_path_factory):
    out = tmp_path_factory.mktemp("dots3")
    # the traced run first, so that what the driver compiles after set-up
    # (``compiles_in_window`` counts it) is not found compiled by the
    # other run in the same process
    return {trace: run_toy("toy_dots3.decode", trace, seconds=0.5,
                           seed=4300000021, out_dir=out,
                           manifest_path=TOY_MANIFEST)
            for trace in (1, 0)}


def test_toy_cell_end_to_end_line(toy_runs):
    rc, result, lines = toy_runs[0]
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"throughput", "setup_s"}
    assert result["attempted"] >= 3                     # units
    checks = result["checks"]
    assert sorted(checks) == [
        "logits_rel_err_median_row_first", "logits_rel_err_median_row_last",
        "logits_rel_err_overall_row_first",
        "logits_rel_err_overall_row_last", "nonfinite_logits",
        "window_layer_rel_err_row_first"]
    # 300-token prompts against a window of 5 in rings of 128 and a topk
    # of 8: the rings wrapped twice, and the mechanisms decide
    assert all(0 < checks[name][0] < 1e-4 and checks[name][1] == "<=0.0001"
               for name in checks if name != "nonfinite_logits")
    assert not any("control" in l for l in lines)
    assert any("set-up phases" in l and "the comparison" in l
               for l in lines)
    assert any("selections that settled ties" in l for l in lines)


def test_toy_cell_per_layer_line(toy_runs):
    rc, result, _ = toy_runs[1]
    metrics = result["metrics"]
    assert rc == 0 and result["correct"] is True
    assert metrics["dispatches_per_step"]["value"] == pytest.approx(7 / 6)
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["decode_dispatch_ms"]["value"] > 0
    assert 100 / 16 <= metrics["expert_load_max_share"]["value"] <= 100
    # the three window layers' rings of 128 slots beside the two full
    # layers' of 320 (the latent row and the indexer's key), in both
    # sessions alike, the cursors' few bytes aside
    from deeplearning4j_tpu.ops.attention import joined_width
    window, full = 3 * 128 * joined_width(24, 8), 2 * 320 * (
        joined_width(16, 8) + 16)
    assert metrics[STATE]["value"] == pytest.approx(
        100 * window / (window + full), rel=1e-4)
    # no device trace on the CPU: what reads one is left out, not zero
    for name in ("device_mfu", "device_idle_share", "indexer_roofline",
                 "sparse_select_share", "moe_experts_roofline",
                 "mla_decode_roofline", *NEW):
        assert name not in metrics


def test_the_window_check_runs_the_token_steps_form(tmp_path, monkeypatch):
    """A fault planted in the window layers' token step alone (one
    position a dispatch: window + 1) fails the window layer's check,
    whose program side takes the timed path's forms, chunks and then
    one position a dispatch at the cell's rows."""
    from deeplearning4j_tpu.nn.layers import decoder
    served = decoder.window_latent_attention

    def wider(q, ring, cursor, *, window, **kw):
        return served(q, ring, cursor, window=window + (q.shape[1] == 1),
                      **kw)
    monkeypatch.setattr(decoder, "window_latent_attention", wider)
    rc, result, lines = run_toy("toy_dots3.decode", 0, seconds=0.2,
                                seed=4300000023, out_dir=tmp_path,
                                manifest_path=TOY_MANIFEST)
    error, limit = result["checks"]["window_layer_rel_err_row_first"]
    assert rc == 0 and error > 100 * float(limit[2:]), error
    assert result["correct"] is False
    assert any("the window layer's check by the forms {7: 'masked', "
               "1: 'masked'}" in l for l in lines), lines


@pytest.mark.parametrize("control", sorted(driver.CONTROLS))
def test_a_control_run_comes_out_not_correct(control, tmp_path, monkeypatch):
    """A control's reference is held to the cell's own limits through
    ``checks``: the program's logits pass them, the control's do not."""
    monkeypatch.setenv("BENCH_DECODE_CONTROL", control)
    rc, result, lines = run_toy("toy_dots3.decode", 0, seconds=0.2,
                                seed=4300000022, out_dir=tmp_path,
                                manifest_path=TOY_MANIFEST)
    assert result["correct"] is False and result["failed"] == 0
    checks = result["checks"]
    limit = driver.BOUNDS["float32"]["median"]
    mine = {n: v for n, v in checks.items() if not n.startswith("control_")}
    assert len(mine) == 6 and all(v <= float(l[2:]) for v, l in mine.values())
    theirs = {n: v for n, v in checks.items() if n.startswith("control_")}
    logits = [f"control_{control}_logits_rel_err_{kind}_{row}"
              for kind in ("median", "overall") for row in ("row_first",
                                                            "row_last")]
    window = f"control_{control}_window_layer_rel_err_row_first"
    assert sorted(theirs) == sorted(logits + [window])
    assert all(theirs[n][0] > 100 * limit and theirs[n][1] == f"<={limit}"
               for n in logits), theirs
    # the window layer's own check sees the window and the gate
    if control in ("all_full", "no_gate", "fp8"):
        assert theirs[window][0] > 100 * limit, theirs
    assert any(f"control {control}" in l for l in lines)
    # ``decode_mixed``'s own table of controls is its own again
    assert sorted(decode_mixed.CONTROLS) == ["all_full", "fp8", "rope_all"]


# --------------------------------------------- operations and bytes by hand
def test_operations_of_a_token_counted_by_hand():
    """hidden 64; full layers of 4 heads at ranks 64 / 16 (16 + 8 / 16)
    with an indexer of 4 heads of 16 picking 8; window layers of 2 heads
    at ranks 64 / 24 (24 + 8 / 16) over 5; a dense layer of 48, then 4
    held of 16 experts of 32 top-3 beside a shared one; vocabulary 256;
    at a context of 40 rows and of 3."""
    def by_hand(context):
        full_read, window_read = min(context, 8), min(context, 5)
        full = (64 * 64 + 64 * 4 * 24 + 64 * 24 + 4 * 16 * 16
                + 4 * full_read * 24 + 4 * full_read * 16
                + 4 * 16 * 16 + 4 * 16 * 64 + 64 * 4
                + 64 * 4 * 16 + 64 * 16 + 64 * 4 + 4 * context * 16)
        window = (64 * 64 + 64 * 2 * 32 + 64 * 32 + 2 * 24 * 24
                  + 2 * window_read * 32 + 2 * window_read * 24
                  + 2 * 24 * 16 + 2 * 16 * 64 + 64 * 2)
        moe = 64 * 16 + 3 * 64 * 32 * (3 * 4 / 16 + 1)
        return 2 * full + 3 * window + 3 * 64 * 48 + 4 * moe + 64 * 256
    for context in (40, 3):
        layers = family.layers(SMALL, context=context)
        assert sum(l["macs"] for l in layers) == pytest.approx(
            by_hand(context))
    assert [l["name"] for l in layers] == [
        "L0_attn", "L0_ffn", "L1_attn", "L1_moe", "L2_attn", "L2_moe",
        "L3_attn", "L3_moe", "L4_attn", "L4_moe", "head"]
    assert [l["kind"] for l in layers[:-1:2]] == FIRST
    # the harness's own entry point finds the family (default context)
    assert flops.macs_per_item(SMALL) == sum(
        l["macs"] for l in family.layers(SMALL, context=4096))
    assert family.layer_kinds(SMALL) == FIRST
    assert family.published_experts(SMALL) == 16


def test_kernel_counts_by_hand():
    idx = family.indexer_kernel(SMALL, rows=3, slots=300)
    matrices = 64 * 4 * 16 + 64 * 16 + 64 * 4
    assert idx["flops"] == 2 * 2.0 * 3 * (4 * 300 * 16 + matrices)
    assert idx["bytes"] == 2 * (3 * 300 * 16 + matrices) * 2
    sparse = family.sparse_latent_attention_kernel(SMALL, rows=3, selected=8)
    assert sparse["flops"] == 2 * 2 * 3 * 4 * 8 * (2 * 16 + 8)
    assert sparse["bytes"] == 2 * 3 * 8 * (16 + 8) * 2
    window = family.window_latent_attention_kernel(SMALL, rows=3, visible=5)
    assert window["flops"] == 3 * 2 * 3 * 2 * 5 * (2 * 24 + 8)
    assert window["bytes"] == 3 * 3 * 5 * (24 + 8) * 2
    # what decode_sessions asks every decode family for by this name
    assert family.mla_decode_kernel(SMALL, 3, 320) == \
        family.sparse_latent_attention_kernel(SMALL, 3, 8)
    moe = family.moe_experts_kernel(SMALL, tokens=3, experts_touched=[3, 4],
                                    held_picks=[2.0, 1.5])
    assert moe["flops"] == 2 * (2.0 + 1.5) * 3 * 64 * 32


def test_the_real_cell_reads_what_its_sizes_reckon():
    cfg = LOOKUP.data("configs", CONFIG)
    # the selected latent rows: 75.5 MB and 18.3 GFLOP a layer-step, at
    # the ridge (92 us under each roof)
    sparse = family.sparse_latent_attention_kernel(cfg, 32, 2048)
    assert sparse["bytes"] / 2 == 32 * 2048 * 576 * 2
    assert sparse["bytes"] / 2 / 1e6 == pytest.approx(75.5, abs=0.1)
    assert sparse["flops"] / 2 / 1e9 == pytest.approx(18.25, abs=0.05)
    assert sparse["bytes"] / 2 / 819e9 * 1e6 == pytest.approx(92, abs=1)
    assert sparse["flops"] / 2 / 197e12 * 1e6 == pytest.approx(92.6, abs=1)
    # the indexer keys read once a step: 0.54 GB over both full layers
    idx = family.indexer_kernel(cfg, 32, 32768)
    assert 32 * 32768 * 128 * 2 * 2 / 1e9 == pytest.approx(0.537, abs=0.001)
    assert idx["bytes"] / 1e9 == pytest.approx(0.537 + 2 * 0.019, abs=0.001)
    # the window rows: 0.11 GB a step over three layers
    window = family.window_latent_attention_kernel(cfg, 32, 513)
    assert window["bytes"] / 1e9 == pytest.approx(0.107, abs=0.001)
    layers = family.layers(cfg, context=32735.5)
    per_token = 2 * sum(l["macs"] for l in layers)
    # 4.6 GFLOP a token: 60% of it the two full layers, of which the
    # indexer's scores against 32k keys are 38%
    assert 4.5e9 < per_token < 4.7e9
    full = 2 * (layers[0]["macs"] + layers[2]["macs"])
    assert full / per_token == pytest.approx(0.60, abs=0.01)
    scores = 2 * 2 * 64 * 32735.5 * 128
    assert scores / full == pytest.approx(0.38, abs=0.01)


# -------------------------------------------------------------- the driver
def test_the_driver_files_the_new_kernels_and_applies_its_limits(
        monkeypatch):
    traffic = LOOKUP.data("traffic", "decode_b32_ctx32k")
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
            "correct": True, "trace_items": 32 * 64, "window_s": 1.0,
            "kernels": {"moe_experts": {"flops": 1.0, "bytes": 1.0},
                        "mla_decode": {"flops": 1.0, "bytes": 1.0}},
            "notes": []}
    monkeypatch.setattr(driver.base, "measure", measured)
    # the window layer's check: 0.01 for the program, 0.9 for a control
    windows = []
    monkeypatch.setattr(driver, "window_layer_error", lambda run, state, **kw:
                        windows.append(kw) or (0.9 if kw else 0.01))
    monkeypatch.delenv("BENCH_DECODE_CONTROL", raising=False)
    pol = types.SimpleNamespace(compute_dtype=np.dtype("float32"))
    net = types.SimpleNamespace(_pol=lambda: pol, params={})
    state = {"net": net, "ids": np.zeros((32, 5), np.int32),
             "setup_phases": {"build_net": 1.0},
             "window_check": {"paths": {64: "streamed", 1: "streamed"}},
             "last": types.SimpleNamespace(ids=np.ones((32, 3), np.int32),
                                           selection_tied={"L0_attn": 0})}
    fake = types.SimpleNamespace(traffic=traffic, cfg=cfg)
    record = driver.measure(fake, state)
    kernels = record["kernels"]
    assert sorted(kernels) == ["indexer", "sparse_latent_attention",
                               "window_latent_attention"]
    assert kernels["indexer"] == {
        k: v * 64 for k, v in family.indexer_kernel(cfg, 32, 32768).items()}
    assert kernels["sparse_latent_attention"] == {
        k: v * 64 for k, v in family.sparse_latent_attention_kernel(
            cfg, 32, 2048).items()}
    assert kernels["window_latent_attention"] == {
        k: v * 64 for k, v in family.window_latent_attention_kernel(
            cfg, 32, 513).items()}
    assert record["correct"] is False          # float32 limits: 1e-4
    pol.compute_dtype = jnp.bfloat16
    record = driver.measure(fake, state)
    limits = driver.BOUNDS["bfloat16"]
    assert record["checks"]["logits_rel_err_overall_row_last"][1] == \
        f"<={limits['overall']}"
    assert record["correct"] is (0.02 <= limits["median"]
                                 and 0.03 <= limits["overall"])
    assert record["checks"]["window_layer_rel_err_row_first"] == [
        0.01, f"<={driver.WINDOW_BOUNDS['bfloat16']}"]
    assert not any(n.startswith("control_") for n in record["checks"])

    # a control: hidden from ``decode_sessions``, judged by
    # ``decode_mixed``'s control step under this cell's limits
    asked = []
    monkeypatch.setattr(decode_mixed.base, "reference_for",
                        lambda run, net, *a, **kw: asked.append(kw) or "ref")
    monkeypatch.setattr(
        decode_mixed.base, "compare", lambda run, state, ref: {
            "finite": True,
            "row_first": {"median": 0.9, "overall": 0.07, "max": 1.0},
            "row_last": {"median": 0.04, "overall": 0.9, "max": 1.0}})
    for name, kw in driver.CONTROLS.items():
        monkeypatch.setenv("BENCH_DECODE_CONTROL", name)
        del asked[:]
        record = driver.measure(fake, state)
        assert seen[-1] is None
        assert os.environ["BENCH_DECODE_CONTROL"] == name
        assert asked == [kw]
        assert record["checks"][
            f"control_{name}_logits_rel_err_median_row_first"] == [
                0.9, f"<={limits['median']}"]
        assert windows[-1] == kw and record["checks"][
            f"control_{name}_window_layer_rel_err_row_first"][0] == 0.9
        assert record["correct"] is False
        assert sorted(decode_mixed.CONTROLS) == ["all_full", "fp8",
                                                 "rope_all"]
    monkeypatch.setenv("BENCH_DECODE_CONTROL", "none")
    record = driver.measure(fake, state)
    assert len(record["checks"]) == 6
    assert sorted(driver.CONTROLS) == ["all_full", "dense", "fp8", "no_gate"]


def test_the_limits_tell_bf16_from_the_fp8_control():
    """Between the readings on the v5e (PERF.md section 6), with room on
    both sides: the program's largest median 0.0907 and overall 0.0904
    over 12 rows, the ``fp8`` control's smallest 0.269 and 0.271; the
    window layer's check three times from each of its readings."""
    bounds = driver.BOUNDS
    assert bounds["float32"] == {"median": 1e-4, "overall": 1e-4}
    assert set(bounds["bfloat16"]) == {"median", "overall"}
    assert 1.5 * 0.0907 <= bounds["bfloat16"]["median"] <= 0.269 / 1.5
    assert 1.5 * 0.0904 <= bounds["bfloat16"]["overall"] <= 0.271 / 1.5
    # the first window layer alone, by the timed path's forms: the
    # program's largest 0.0042 over three seeds, ``fp8`` 0.0416
    assert driver.WINDOW_BOUNDS["float32"] == 1e-4
    assert 3 * 0.0042 <= driver.WINDOW_BOUNDS["bfloat16"] <= 0.0416 / 3


# ------------------------------------------------------------- the readers
PEAKS = {"flops_per_s_bf16": 100.0, "hbm_bytes_per_s": 10.0}


def record(rows, kernels):
    return {"trace": {"by_scope": rows, "devices": 1, "busy_s": 20.0},
            "peaks": PEAKS, "kernels": kernels}


ROWS = [["layer.L0_attn.sparse_latent_attention", "forward", 1.5, 10],
        ["layer.L1_attn.sparse_latent_attention", "forward", 0.5, 10],
        ["layer.L2_attn.window_latent_attention", "forward", 4.0, 10],
        ["layer.L0_attn.latent_attention", "forward", 9.0, 10],
        ["layer.L1_attn", "forward", 7.0, 3], ["unscoped", "other", 1.0, 1]]


@pytest.mark.parametrize("name,kernel,seconds", [
    ("sparse_latent_roofline", "sparse_latent_attention", 2.0),
    ("window_latent_roofline", "window_latent_attention", 4.0)])
def test_roofline_readers_on_hand_made_rows(name, kernel, seconds):
    read = LOOKUP.module("layer_metrics", name).read
    # bytes bound: 10 bytes at 10 bytes/s = 1 s of the scope's seconds
    assert read(record(ROWS, {kernel: {"flops": 50.0, "bytes": 10.0}})) \
        == pytest.approx(100.0 / seconds)
    # operations bound: 150 at 100/s = 1.5 s
    assert read(record(ROWS, {kernel: {"flops": 150.0, "bytes": 1.0}})) \
        == pytest.approx(150.0 / seconds)
    # nothing to read is None, never 0: no such scope (a program without
    # these layers), no count, no trace
    assert read(record(ROWS[3:], {kernel: {"flops": 1.0, "bytes": 1.0}})) \
        is None
    assert read(record(ROWS, {})) is None
    assert read({"trace": None, "peaks": PEAKS}) is None
    assert read({}) is None


@pytest.mark.parametrize("name,suffix", [
    ("mla_decode_roofline", ".latent_attention"),
    ("window_attention_roofline", ".window_attention"),
    ("sparse_attention_roofline", ".sparse_attention")])
def test_the_accepted_readers_do_not_read_the_new_scopes(name, suffix):
    """``.sparse_latent_attention`` and ``.window_latent_attention`` end
    in none of the accepted scopes' suffixes."""
    for row in ROWS[:3]:
        assert not row[0].endswith(suffix)
    read = LOOKUP.module("layer_metrics", name).read
    kernel = {"mla_decode_roofline": "mla_decode",
              "window_attention_roofline": "window_attention",
              "sparse_attention_roofline": "sparse_attention"}[name]
    assert read(record(ROWS[:3], {kernel: {"flops": 1.0, "bytes": 1.0}})) \
        is None


def test_window_latent_state_share_on_hand_made_gauges():
    read = LOOKUP.module("layer_metrics", STATE).read
    gauge = lambda values: {"monitor_after": {
        "serving_session_state_bytes": {"kind": "gauge", "values": values}}}
    values = {'{model="m"}': 400.0, '{kind="window_latent",model="m"}': 100.0,
              '{kind="sparse_latent",model="m"}': 300.0}
    assert read(gauge(values)) == pytest.approx(25.0)
    # every kind a session holds counts
    assert read(gauge({**values, '{kind="latent",model="m"}': 100.0})) \
        == pytest.approx(20.0)
    # grouped-query window rings are another kind, read by
    # ``window_state_share``; a program with no latent window rings (the
    # parent's, which serves this file with full layers alone): nothing,
    # never 0
    assert read(gauge({'{kind="window_kv",model="m"}': 100.0,
                       '{kind="kv",model="m"}': 300.0})) is None
    assert read(gauge({'{kind="latent",model="m"}': 400.0})) is None
    assert read(gauge({})) is None and read({}) is None
    assert read({"monitor_after": None}) is None


def test_the_reference_is_independent_of_the_package():
    path = os.path.join(BENCH, "reference", "mla_sparse_window_moe.py")
    with open(path) as fh:
        source = fh.read()
    assert "deeplearning4j_tpu" not in source.split('"""', 2)[2]
    assert "pallas" not in source and "highest" in source
    module = importlib.import_module(
        "benchmark.reference.mla_sparse_window_moe")
    assert callable(module.forward) and callable(module.Forward)
    assert module.held_experts({"builder_args": {"experts_held": [3]}}) == [3]
    assert module.held_experts({}) is None
    kinds = [kind for kind, _ in module.Forward(SMALL).layers()]
    assert kinds == ["full_attention", "ffn", "full_attention", "moe"] + [
        "sliding_attention", "moe"] * 3
