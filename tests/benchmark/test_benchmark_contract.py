"""The contract (``benchtools.check_manifest``) takes what the next PR
brings and refuses what it may not: the toy manifests pass; a copy of
the real manifest extended in a temporary directory with a cut
configuration, its cell and one appended per-layer metric passes; each
malformed copy fails with a message that names the entry at fault."""

import copy
import json
import os

import pytest

from benchtools import (ACCEPTED_PER_LAYER, ROOT, TOY, ManifestError,
                        check_config_file, check_manifest, manifest)
from benchmark import run

BENCH = os.path.join(ROOT, "benchmark")
SOURCE = "https://example.org/some-decoder/blob/main/config.json"
CUT = {
    "name": "cut_decoder", "source": SOURCE,
    "reduced": ["num_hidden_layers"],
    "published": {"num_hidden_layers": 40},
    "deployment": "each layer on one chip; of the 40 layers the 2 leading "
                  "dense ones and 6 expert layers are here, the other 32 "
                  "would lie on further chips as the stages of a pipeline",
    "assumed": {"weights": "random, from the seed"},
    "num_hidden_layers": 8, "hidden_size": 3584,
}
READER = '''"""A reader a later PR brings as a file: a kernel's seconds by name."""
LAYER = "kernels"
UNIT, BETTER, SOURCE = "%", "higher", "device_trace"


def read(record):
    return None
'''


@pytest.mark.parametrize("name", ["BENCHMARK.toy.json",
                                  "BENCHMARK.tracing.json"])
def test_toy_manifests_meet_the_contract(name):
    with open(os.path.join(TOY, name)) as fh:
        toy = json.load(fh)
    # the toy data-parallel cell runs on 2 of the CPU's virtual devices
    check_manifest(toy, run.Lookup([BENCH, TOY]), allowed_chips=(1, 2, 4))


@pytest.fixture
def extended(tmp_path):
    """``(manifest, lookup)``: the real manifest with what a
    ``model_config`` PR adds, its files under a temporary root."""
    for kind in ("configs", "workloads", "traffic", "layer_metrics"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "cut_decoder.json").write_text(json.dumps(CUT))
    cell = {"name": "cut_decoder.decode", "config": "cut_decoder",
            "traffic": "decode_heavy", "chips": 1,
            "why": "many sessions a dispatch over long latent caches"}
    (tmp_path / "workloads" / "cut_decoder.decode.json").write_text(
        json.dumps(dict(cell, driver="fit_cached")))
    (tmp_path / "traffic" / "decode_heavy.csv").write_text("t,tokens\n")
    (tmp_path / "layer_metrics" / "latent_attn_roofline.py").write_text(
        READER)
    m = copy.deepcopy(manifest())
    m["configs"].append({
        "name": "cut_decoder", "source": SOURCE,
        "file": "benchmark/configs/cut_decoder.json",
        "reduced": ["num_hidden_layers"], "why": "latent attention"})
    m["workloads"].append(cell)
    for e in m["end_to_end"] + m["per_layer"]:
        if e["name"] in ("throughput", "setup_trace_lower_s",
                         "setup_backend_s"):
            e["workloads"].append(cell["name"])
    m["per_layer"].append({
        "name": "latent_attn_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "throughput", "workloads": [cell["name"]]})
    return m, run.Lookup([BENCH, str(tmp_path)]), tmp_path


def test_extended_copy_of_the_real_manifest_meets_the_contract(extended):
    m, lookup, _ = extended
    check_manifest(m, lookup, accepted=ACCEPTED_PER_LAYER)


def _unexplained_cut(m, root):
    cfg = dict(CUT, reduced=["num_hidden_layers", "n_routed_experts"],
               n_routed_experts=8)
    (root / "configs" / "cut_decoder.json").write_text(json.dumps(cfg))
    m["configs"][-1]["reduced"] = cfg["reduced"]


def _new_entry_before_an_accepted_one(m, root):
    m["per_layer"].insert(3, m["per_layer"].pop())


def _unit_unlike_the_readers(m, root):
    m["per_layer"][-1]["unit"] = "share"


def _cell_without_a_per_layer_metric(m, root):
    for e in m["per_layer"]:
        if "cut_decoder.decode" in e["workloads"]:
            e["workloads"].remove("cut_decoder.decode")
    m["per_layer"].pop()        # it listed no other cell


@pytest.mark.parametrize("spoil,names", [
    (_unexplained_cut, ["configs/cut_decoder", "'n_routed_experts'"]),
    (_new_entry_before_an_accepted_one,
     ["per_layer/latent_attn_roofline", "'device_mfu'"]),
    (_unit_unlike_the_readers,
     ["per_layer/latent_attn_roofline", "'share'", "'%'"]),
    (_cell_without_a_per_layer_metric,
     ["workloads/cut_decoder.decode", "no per-layer metric"]),
])
def test_malformed_copy_fails_and_names_the_entry(extended, spoil, names):
    m, lookup, root = extended
    spoil(m, root)
    with pytest.raises(ManifestError) as err:
        check_manifest(m, lookup, accepted=ACCEPTED_PER_LAYER)
    for name in names:
        assert name in str(err.value)


@pytest.mark.parametrize("change,says", [
    ({"published": {}}, "published['num_hidden_layers']"),
    ({"num_hidden_layers": 40}, "runs at its published value"),
    ({"deployment": " "}, "deployment"),
    ({"published": {"num_hidden_layers": 40, "vocab_size": 151936}},
     "which reduced does not list"),
    ({"source": "elsewhere"}, "source"),
])
def test_a_cut_the_file_does_not_explain_fails(change, says):
    listed = {"name": "cut_decoder", "source": SOURCE,
              "reduced": ["num_hidden_layers"]}
    check_config_file(listed, CUT)
    with pytest.raises(ManifestError, match="configs/cut_decoder") as err:
        check_config_file(listed, dict(CUT, **change))
    assert says in str(err.value)


@pytest.mark.parametrize("key", ["hidden_size", "moe_intermediate_size",
                                 "kv_lora_rank", "qk_rope_head_dim",
                                 "num_experts_per_tok"])
def test_reduced_may_never_name_a_width(extended, key):
    m, lookup, _ = extended
    m["configs"][-1]["reduced"] = [key]
    with pytest.raises(ManifestError, match="no width is ever cut"):
        check_manifest(m, lookup, accepted=ACCEPTED_PER_LAYER)
