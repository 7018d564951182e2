"""The decode cell's own pieces on the CPU: a toy cell through the same
driver and ``main()``, the manifests against the contract, the
family's operations and bytes counted by hand at a small size, and the
new readers on hand-made records."""

import importlib
import json
import os

import pytest

from benchtools import (ACCEPTED_PER_LAYER, ROOT, TOY, check_manifest,
                        manifest, run_toy)
from benchmark import flops, run
from benchmark.families import mla_moe_decoder as family

CELL = "xing4_29b_a4b.decode_b64_ctx4k"
TOY_MANIFEST = os.path.join(TOY, "BENCHMARK.decode.json")
BENCH = os.path.join(ROOT, "benchmark")
LOOKUP = run.Lookup([BENCH])


def reader(name):
    return LOOKUP.module("layer_metrics", name)


# ----------------------------------------------------------- the toy cell
@pytest.fixture(scope="module")
def toy_runs(tmp_path_factory):
    out = tmp_path_factory.mktemp("decode")
    return {trace: run_toy("toy_decoder.decode", trace, seconds=0.5,
                           seed=3000000019, out_dir=out,
                           manifest_path=TOY_MANIFEST)
            for trace in (0, 1)}


def test_toy_decode_cell_end_to_end_line(toy_runs):
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
    assert any("tokens/s by the median unit" in l for l in lines)


def test_toy_decode_cell_per_layer_line(toy_runs):
    rc, result, _ = toy_runs[1]
    metrics = result["metrics"]
    assert rc == 0 and result["correct"] is True
    # 6 token steps and one fork a unit
    assert metrics["dispatches_per_step"]["value"] == pytest.approx(7 / 6)
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["decode_dispatch_ms"]["value"] > 0
    # 3 rows x 6 tokens x top-2 over 8 experts: the busiest has its share
    assert 12.5 <= metrics["expert_load_max_share"]["value"] <= 100
    # no device trace on the CPU: what reads one is left out, not zero
    for name in ("device_mfu", "device_idle_share", "moe_experts_roofline",
                 "mla_decode_roofline"):
        assert name not in metrics


@pytest.mark.parametrize("which", ["real", "toy"])
def test_manifest_with_the_decode_cell_meets_the_contract(which):
    if which == "real":
        check_manifest(manifest(), LOOKUP, accepted=ACCEPTED_PER_LAYER)
    else:
        with open(TOY_MANIFEST) as fh:
            check_manifest(json.load(fh), run.Lookup([BENCH, TOY]),
                           allowed_chips=(1,))


def test_the_cell_is_listed_where_the_issue_says():
    m = manifest()
    listed = {e["name"] for e in m["end_to_end"] + m["per_layer"]
              if CELL in e.get("workloads", [CELL])}
    assert listed == {
        "throughput", "setup_s", "dispatches_per_step", "compiles_in_window",
        "device_mfu", "device_idle_share", "unit_stall_share",
        "hbm_peak_gib", "cache_misses_warm", "setup_trace_lower_s",
        "setup_backend_s", "executable_store_hits", "moe_experts_roofline",
        "mla_decode_roofline", "decode_dispatch_ms", "expert_load_max_share"}
    assert [e["name"] for e in m["per_layer"]][-4:] == [
        "moe_experts_roofline", "mla_decode_roofline", "decode_dispatch_ms",
        "expert_load_max_share"]


def test_configuration_keeps_every_published_width():
    cfg = LOOKUP.data("configs", "xing4_29b_a4b")
    published = {
        "hidden_size": 3584, "num_attention_heads": 32,
        "num_key_value_heads": 32, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "q_lora_rank": 768,
        "kv_lora_rank": 512, "moe_intermediate_size": 1024,
        "intermediate_size": 9216, "n_routed_experts": 64,
        "num_experts_per_tok": 4, "n_shared_experts": 1,
        "vocab_size": 131072, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "routed_scaling_factor": 2, "max_position_embeddings": 262144}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"]["factor"] == 64
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "num_nextn_predict_layers"]
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "first_k_dense_replace": 2,
                                "num_nextn_predict_layers": 1}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_nextn_predict_layers"]) == (6, 1, 0)
    assert len(cfg["source"]) <= 200 and "deployment" in cfg
    traffic = LOOKUP.data("traffic", "decode_b64_ctx4k")
    assert (traffic["rows"], traffic["prompt_tokens"], traffic["ring_slots"],
            traffic["new_tokens"]) == (64, 3968, 4096, 64)
    assert traffic["prompt_tokens"] + traffic["new_tokens"] <= 4096 + 1


# --------------------------------------------- operations and bytes by hand
SMALL = LOOKUP.__class__([BENCH, TOY]).data("configs", "toy_decoder")


def test_operations_of_a_token_counted_by_hand():
    """hidden 64, 4 heads of 16 + 8 / 16, ranks 48 and 32, 8 experts of
    32 top-2 + 1 shared, dense 160, 4 streams, 1 + 2 layers, vocabulary
    256, at a context of 10 rows."""
    attention = (64 * 48 + 48 * 4 * 24 + 64 * 40      # q_a, q_b, kv_a
                 + 4 * 16 * 32                        # absorbed key half
                 + 4 * 10 * 40 + 4 * 10 * 32          # scores, context
                 + 4 * 32 * 16 + 4 * 16 * 64)         # value half, o
    mixing = 2 * (256 * (4 + 4 + 16) + 256 + 16 * 64 + 256)
    dense, moe = 3 * 64 * 160, 64 * 8 + 3 * 64 * 32 * (2 + 1)
    by_hand = 3 * attention + dense + 2 * moe + 3 * mixing + 64 * 256
    assert attention == 21312 and mixing == 15360 and by_hand == 195008
    layers = family.layers(SMALL, context=10)
    assert sum(l["macs"] for l in layers) == by_hand
    assert [l["name"] for l in layers][:3] == ["L0_attn", "L0_ffn",
                                               "L0_mixing"]
    # the harness's own entry point finds the family (default context)
    assert flops.macs_per_item(SMALL) == sum(
        l["macs"] for l in family.layers(SMALL, context=4096))


def test_kernel_counts_by_hand():
    moe = family.moe_experts_kernel(SMALL, tokens=3, experts_touched=[5, 8])
    assert moe["flops"] == 2 * (2 * 3 * 2 * 3 * 64 * 32)
    assert moe["bytes"] == 2 * ((5 + 8) * 3 * 64 * 32 + 2 * 2 * 3 * 64)
    mla = family.mla_decode_kernel(SMALL, rows=3, ring_slots=32)
    q = 3 * 4
    assert mla["flops"] == 3 * 2 * q * (16 * 32 + 32 * 40 + 32 * 32)
    assert mla["bytes"] == 3 * 2 * (3 * 32 * 40 + 32 * 4 * 16
                                    + q * (16 + 8 + 32))


def test_the_real_cell_reads_what_the_issue_reckoned():
    cfg = LOOKUP.data("configs", "xing4_29b_a4b")
    moe = family.moe_experts_kernel(cfg, 64, [64] * 5)
    assert moe["bytes"] / 1e9 == pytest.approx(7.05, abs=0.01)
    mla = family.mla_decode_kernel(cfg, 64, 4096)
    assert mla["bytes"] / 1e9 == pytest.approx(1.81 + 0.025, abs=0.02)
    per_token = 2 * sum(l["macs"] for l in family.layers(cfg, context=4000))
    assert 3e9 < per_token < 5e9


# ------------------------------------------------------------- the readers
PEAKS = {"flops_per_s_bf16": 100.0, "hbm_bytes_per_s": 10.0}


def record(rows, kernels):
    return {"trace": {"by_scope": rows, "devices": 1}, "peaks": PEAKS,
            "kernels": kernels}


@pytest.mark.parametrize("name,suffix,kernel", [
    ("moe_experts_roofline", "experts", "moe_experts"),
    ("mla_decode_roofline", "latent_attention", "mla_decode")])
def test_roofline_readers_on_hand_made_rows(name, suffix, kernel):
    read = reader(name).read
    rows = [[f"layer.L1_x.{suffix}", "forward", 1.5, 10],
            [f"layer.L2_x.{suffix}", "forward", 0.5, 10],
            ["layer.L1_x.router", "forward", 9.0, 3],
            ["layer.L1_x", "forward", 7.0, 3], ["unscoped", "other", 1.0, 1]]
    # bytes bound: 10 bytes at 10 bytes/s = 1 s of the scope's 2 s
    assert read(record(rows, {kernel: {"flops": 50.0, "bytes": 10.0}})) \
        == pytest.approx(50.0)
    # operations bound: 150 at 100/s = 1.5 s of 2 s
    assert read(record(rows, {kernel: {"flops": 150.0, "bytes": 1.0}})) \
        == pytest.approx(75.0)
    # nothing to read is None, never 0: no such scope, no count, no trace
    assert read(record(rows[2:], {kernel: {"flops": 1.0, "bytes": 1.0}})) \
        is None
    assert read(record(rows, {})) is None
    assert read({"trace": None, "peaks": PEAKS}) is None
    assert read({}) is None


def test_dispatch_and_load_readers_on_hand_made_records():
    spans = [{"name": "serve/decode_step", "dur_ms": d} for d in (1, 2, 9)]
    assert reader("decode_dispatch_ms").read(
        {"spans": spans + [{"name": "serve/fork", "dur_ms": 50}]}) == 2
    assert reader("decode_dispatch_ms").read({"spans": []}) is None

    def snap(values):
        return {"moe_expert_tokens_total": {"values": {
            f'{{expert="{e}",layer="{l}",model="m"}}': v
            for (l, e), v in values.items()}}}
    before = snap({("L1", 0): 100.0, ("L1", 1): 0.0})
    after = snap({("L1", 0): 110.0, ("L1", 1): 30.0, ("L2", 0): 5.0,
                  ("L2", 1): 5.0})
    share = reader("expert_load_max_share").read(
        {"monitor_before": before, "monitor_after": after})
    assert share == pytest.approx(75.0)                  # L1: 30 of 40
    assert reader("expert_load_max_share").read(
        {"monitor_before": {}, "monitor_after": {}}) is None


def test_the_reference_is_independent_of_the_package():
    path = os.path.join(BENCH, "reference", "mla_moe_decoder.py")
    with open(path) as fh:
        source = fh.read()
    assert "deeplearning4j_tpu" not in source.split('"""', 2)[2]
    module = importlib.import_module("benchmark.reference.mla_moe_decoder")
    assert callable(module.forward) and callable(module.Forward)
