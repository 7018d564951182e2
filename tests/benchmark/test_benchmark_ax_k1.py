"""The share cell's own pieces on the CPU: the configuration's file
against its source's widths and its three cuts, a toy share through the
``decode_share`` driver and ``main()``, the share family's operations
and bytes counted by hand at a small size, the held experts' counts,
and the new reader on hand-made records."""

import importlib
import json
import os

import numpy as np
import pytest

from benchtools import (ACCEPTED_PER_LAYER, ROOT, TOY, check_manifest,
                        manifest, run_toy)
from benchmark import flops, run
from benchmark.drivers import decode_share
from benchmark.families import mla_moe_decoder as whole_family
from benchmark.families import mla_moe_share as family

CELL = "ax_k1.decode_b256_ctx1k"
XING = "xing4_29b_a4b.decode_b64_ctx4k"
TOY_MANIFEST = os.path.join(TOY, "BENCHMARK.share.json")
BENCH = os.path.join(ROOT, "benchmark")
LOOKUP = run.Lookup([BENCH])
SMALL = run.Lookup([BENCH, TOY]).data("configs", "toy_share")


# ------------------------------------------------------ the configuration
def test_configuration_keeps_every_published_width_and_explains_its_cuts():
    cfg = LOOKUP.data("configs", "ax_k1")
    published = {
        "hidden_size": 7168, "num_attention_heads": 64,
        "num_key_value_heads": 64, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "moe_intermediate_size": 2048,
        "intermediate_size": 18432, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "norm_topk_prob": True, "first_k_dense_replace": 1,
        "max_position_embeddings": 131072, "rope_theta": 10000,
        "rms_norm_eps": 1e-06, "scoring_func": "sigmoid",
        "topk_method": "none", "n_group": 8, "topk_group": 4,
        "moe_layer_freq": 1, "ep_size": 1, "seq_aux": True,
        "hidden_act": "silu", "attention_bias": False,
        "tie_word_embeddings": False, "model_type": "axk1"}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert cfg["source"] == \
        "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 192,
                                "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 12, 20480)
    # the guide's floors: four expert layers after the dense one, eight
    # experts a layer, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    args = cfg["builder_args"]
    assert args["experts_held"] == list(range(12))
    assert args["router_bias_std"] == 0.0 and "hc_alpha_init" not in args
    assert not any(k.startswith(("hc_", "mhc_")) for k in cfg)
    for word in ("16", "12", "data-parallel", "20,480", "6 of 61", "bf16"):
        assert word in cfg["deployment"], word
    for key in ("routing", "training_keys", "yarn", "init", "sampling",
                "precision", "share", "vocabulary", "depth"):
        assert key in cfg["assumed"], key
    assert "noaux_tc" in cfg["assumed"]["routing"]       # the other reading
    traffic = LOOKUP.data("traffic", "decode_b256_ctx1k")
    assert (traffic["rows"], traffic["prompt_tokens"], traffic["ring_slots"],
            traffic["new_tokens"], traffic["prefill_chunk"]) == (
                256, 960, 1024, 64, 8)
    assert traffic["prompt_tokens"] + traffic["new_tokens"] <= 1024 + 1
    # each held expert sees the rows it would see in the 16-chip pool
    assert traffic["rows"] * cfg["num_experts_per_tok"] / 192 == \
        pytest.approx(10.67, abs=0.01)


@pytest.mark.parametrize("which", ["real", "toy"])
def test_manifest_with_the_share_cell_meets_the_contract(which):
    if which == "real":
        check_manifest(manifest(), LOOKUP, accepted=ACCEPTED_PER_LAYER)
    else:
        with open(TOY_MANIFEST) as fh:
            check_manifest(json.load(fh), run.Lookup([BENCH, TOY]),
                           allowed_chips=(1,))


def test_the_cell_is_listed_wherever_the_other_decode_cell_is():
    m = manifest()
    every = m["end_to_end"] + m["per_layer"]
    mine = {e["name"] for e in every if CELL in e.get("workloads", [CELL])}
    theirs = {e["name"] for e in every if XING in e.get("workloads", [XING])}
    assert mine == theirs and len(mine) == 16
    # appended: after the cell it joins, in every list
    for e in every:
        if "workloads" in e and CELL in e["workloads"]:
            assert e["workloads"][-1] == CELL
    assert m["configs"][-1]["name"] == "ax_k1"
    assert m["workloads"][-1] == {
        "name": CELL, "config": "ax_k1", "traffic": "decode_b256_ctx1k",
        "chips": 1, "why": LOOKUP.data("workloads", CELL)["why"]}
    assert LOOKUP.data("workloads", CELL)["driver"] == "decode_share"


# ----------------------------------------------------------- the toy cell
@pytest.fixture(scope="module")
def toy_runs(tmp_path_factory):
    out = tmp_path_factory.mktemp("share")
    return {trace: run_toy("toy_share.decode", trace, seconds=0.5,
                           seed=3500000019, out_dir=out,
                           manifest_path=TOY_MANIFEST)
            for trace in (0, 1)}


def test_toy_share_cell_end_to_end_line(toy_runs):
    rc, result, lines = toy_runs[0]
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"throughput", "setup_s"}
    assert result["metrics"]["throughput"]["value"] > 0
    assert result["attempted"] >= 3                     # units
    checks = result["checks"]
    assert sorted(checks) == [
        "logits_rel_err_median_row_first", "logits_rel_err_median_row_last",
        "logits_rel_err_overall_row_first",
        "logits_rel_err_overall_row_last", "nonfinite_logits"]
    assert all(0 < checks[name][0] < 1e-4 and checks[name][1] == "<=0.0001"
               for name in checks if name != "nonfinite_logits")
    # the driver's own count: at most the 6 held experts a layer
    held = [l for l in lines if "held experts touched" in l]
    assert len(held) == 1
    touched = json.loads(held[0].split("by layer ")[1].split(", picks")[0])
    assert len(touched) == 2 and all(0 < t <= 6 for t in touched)


def test_toy_share_cell_per_layer_line(toy_runs):
    rc, result, _ = toy_runs[1]
    metrics = result["metrics"]
    assert rc == 0 and result["correct"] is True
    # 6 token steps and one fork a unit
    assert metrics["dispatches_per_step"]["value"] == pytest.approx(7 / 6)
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["decode_dispatch_ms"]["value"] > 0
    # 3 rows x 6 tokens x top-3 over a 24-wide router
    assert 100 / 24 <= metrics["expert_load_max_share"]["value"] <= 100
    # 3 rows x 3 picks x 6 of 24 held, over 6 experts: 0.375 when even
    assert 0 < metrics["held_expert_rows_mean"]["value"] <= 1.5
    for name in ("device_mfu", "device_idle_share", "moe_experts_roofline",
                 "mla_decode_roofline"):
        assert name not in metrics


# --------------------------------------------- operations and bytes by hand
def test_operations_of_a_token_counted_by_hand():
    """hidden 64, 4 heads of 16 + 8 / 16, ranks 48 and 32, 6 held of 24
    experts of 32, top-3 + 1 shared, dense 160, one stream, 1 + 2
    layers, vocabulary 256, at a context of 10 rows."""
    attention = (64 * 48 + 48 * 4 * 24 + 64 * 40      # q_a, q_b, kv_a
                 + 4 * 16 * 32                        # absorbed key half
                 + 4 * 10 * 40 + 4 * 10 * 32          # scores, context
                 + 4 * 32 * 16 + 4 * 16 * 64)         # value half, o
    dense = 3 * 64 * 160
    # the router over 24; 3 x 6 / 24 routed experts a token + the shared
    moe = 64 * 24 + 3 * 64 * 32 * (0.75 + 1)
    by_hand = 3 * attention + dense + 2 * moe + 64 * 256
    assert attention == 21312 and moe == 12288 and by_hand == 135616
    layers = family.layers(SMALL, context=10)
    assert sum(l["macs"] for l in layers) == by_hand
    assert [l["name"] for l in layers] == [
        "L0_attn", "L0_ffn", "L1_attn", "L1_moe", "L2_attn", "L2_moe", "head"]
    # the harness's own entry point finds the family (default context)
    assert flops.macs_per_item(SMALL) == sum(
        l["macs"] for l in family.layers(SMALL, context=4096))
    # no stream mixing, and the attention as the other family counts it
    streams = whole_family.layers(
        {**SMALL, "hc_mult": 4, "n_routed_experts": 24}, context=10)
    assert streams[0] == layers[0]


def test_kernel_counts_by_hand():
    moe = family.moe_experts_kernel(SMALL, tokens=3, experts_touched=[5, 4],
                                    held_picks=[2.0, 1.5])
    assert moe["flops"] == 2 * (2.0 + 1.5) * 3 * 64 * 32
    assert moe["bytes"] == 2 * ((5 + 4) * 3 * 64 * 32 + 2 * 2 * 3 * 64)
    # without counts: the mean, tokens x top_k x held / published
    mean = family.moe_experts_kernel(SMALL, tokens=8, experts_touched=[6])
    assert mean["flops"] == 2 * (8 * 3 * 6 / 24) * 3 * 64 * 32
    assert family.mla_decode_kernel is whole_family.mla_decode_kernel


def test_the_held_experts_are_counted_over_the_held_ids():
    """Picks by expert over the router's width in, the held experts that
    received a token and the picks on them a step out: never more than
    the layer holds, whatever the other chips' experts received."""
    picks = {"L1_moe": np.array([9] * 6 + [0, 4, 0, 2, 6, 0] + [7] * 12),
             "L2_moe": np.array([1] * 24)}
    touched, a_step = decode_share.held_counts(SMALL, picks, steps=4)
    assert touched == [3.0, 6.0]
    assert a_step == [3.0, 1.5]
    assert all(t <= SMALL["n_routed_experts"] for t in touched)


def test_the_real_cell_reads_what_the_issue_reckoned():
    cfg = LOOKUP.data("configs", "ax_k1")
    expert = 3 * 7168 * 2048 * 2                        # bytes, bf16
    moe = family.moe_experts_kernel(cfg, 256, [12] * 5)
    assert moe["bytes"] / 1e9 == pytest.approx(5.28 + 0.037, abs=0.01)
    assert moe["bytes"] <= 5 * (12 * expert + 2 * 256 * 7168 * 2)
    # 0.5 routed experts a token a layer: 128 picks of 256 rows x 8
    assert moe["flops"] == 5 * 2.0 * 128 * 3 * 7168 * 2048
    # what decode_sessions would have counted over the router's width
    wide = whole_family.moe_experts_kernel(
        {**cfg, "n_routed_experts": 192}, 256, [192] * 5)
    assert wide["bytes"] / moe["bytes"] == pytest.approx(16, rel=0.01)
    mla = family.mla_decode_kernel(cfg, 256, 1024)
    assert mla["bytes"] / 1e9 == pytest.approx(1.812 + 0.05 + 0.12, abs=0.02)
    # 121 operations a byte of ring: twice the other cell's 32 heads
    assert mla["flops"] / (256 * 1024 * 576 * 2 * 6) == \
        pytest.approx(121, rel=0.1)
    per_token = 2 * sum(l["macs"] for l in family.layers(cfg, context=992))
    assert 3e9 < per_token < 5e9
    held = sum(p for name, p in _parameters(cfg).items())
    assert held * 2 / 1e9 == pytest.approx(8.33, abs=0.01)


def _parameters(cfg):
    """Parameters this chip holds, by hand from the file's keys."""
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    attention = (c * cfg["q_lora_rank"] + cfg["q_lora_rank"]
                 + cfg["q_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                             + cfg["qk_rope_head_dim"])
                 + c * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                 + cfg["kv_lora_rank"]
                 + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                              + cfg["v_head_dim"])
                 + h * cfg["v_head_dim"] * c)
    expert = 3 * c * cfg["moe_intermediate_size"]
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {
        "attention and norms": n * (attention + 2 * c) + c,
        "dense": dense * 3 * c * cfg["intermediate_size"],
        "experts": (n - dense) * (
            (cfg["n_routed_experts"] + cfg["n_shared_experts"]) * expert
            + c * 192 + 192),
        "embedding and head": 2 * c * cfg["vocab_size"]}


# -------------------------------------------------------------- the reader
def test_held_expert_rows_mean_on_a_hand_made_record():
    read = LOOKUP.module("layer_metrics", "held_expert_rows_mean").read

    def snap(picks, held=None):
        out = {"moe_held_picks_total": {"values": {
            f'{{layer="{l}",model="m"}}': v for l, v in picks.items()}}}
        if held is not None:
            out["moe_experts_held"] = {"values": {
                f'{{layer="{l}",model="m"}}': v for l, v in held.items()}}
        return out
    before = snap({"L1": 1000.0, "L2": 500.0})
    after = snap({"L1": 1000.0 + 1280, "L2": 500.0 + 1280 + 256},
                 {"L1": 12.0, "L2": 12.0})
    record = {"monitor_before": before, "monitor_after": after, "steps": 10}
    # 2,816 picks over 24 held experts and 10 steps
    assert read(record) == pytest.approx(2816 / 240)
    # a program without the counter or the gauge reports nothing
    assert read({"monitor_before": {}, "monitor_after": {}, "steps": 10}) \
        is None
    assert read({**record, "monitor_after": snap({"L1": 2280.0})}) is None
    assert read({**record, "steps": 0}) is None
    assert read({}) is None


def test_the_reference_is_independent_of_the_package():
    for name in ("mla_moe_plain", "mla_moe_decoder"):
        with open(os.path.join(BENCH, "reference", f"{name}.py")) as fh:
            source = fh.read()
        assert "deeplearning4j_tpu" not in source.split('"""', 2)[2]
    module = importlib.import_module("benchmark.reference.mla_moe_plain")
    assert callable(module.forward) and callable(module.Forward)
    assert module.held_experts({"builder_args": {"experts_held": [3]}}) == [3]
    assert module.held_experts({}) is None
