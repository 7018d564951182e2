"""``flops.py`` against hand counts, and the table of peaks."""

import json
import os

import pytest

from benchtools import ROOT
from benchmark import flops


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_resnet50_forward_multiply_adds():
    cfg = _cfg("resnet50")
    macs = flops.macs_per_item(cfg)
    # He et al. give 3.8e9 for the 50-layer net; v1.5 (stride on the
    # 3x3) would be 4.1e9
    assert 3.8e9 <= macs <= 4.1e9
    by_name = {l["name"]: l["macs"] for l in flops.layers(cfg)}
    assert by_name["stem"] == 112 * 112 * 7 * 7 * 3 * 64
    assert by_name["s0b0_b"] == 56 * 56 * 3 * 3 * 64 * 64
    assert by_name["s3b0_sc"] == 7 * 7 * 1024 * 2048
    assert by_name["fc"] == 2048 * 1000
    assert len(by_name) == 1 + 16 * 3 + 4 + 1       # 53 convs + fc


def test_vgg16_forward_multiply_adds():
    cfg = _cfg("vgg16")
    assert flops.macs_per_item(cfg) == pytest.approx(15.47e9, rel=2e-3)
    by_name = {l["name"]: l["macs"] for l in flops.layers(cfg)}
    assert by_name["block1_conv2"] == 224 * 224 * 9 * 64 * 64
    assert by_name["dense1"] == 7 * 7 * 512 * 4096
    assert len(by_name) == 16


def test_training_is_three_forward_passes():
    cfg = _cfg("vgg16")
    assert flops.flops_per_item(cfg, training=True) == \
        3 * flops.flops_per_item(cfg, training=False) == \
        6 * flops.macs_per_item(cfg)


def test_unknown_family_raises():
    with pytest.raises(KeyError):
        flops.layers({"family": "transformer"})


def test_peaks_v5e_row_and_unknown_kind():
    row = flops.chip_peaks("TPU v5 lite")
    assert row["flops_per_s_bf16"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["hbm_bytes"] == 16e9
    assert row["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        flops.chip_peaks("TPU v9")
    with pytest.raises(KeyError):
        flops.chip_peaks("cpu")
