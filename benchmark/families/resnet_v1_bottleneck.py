"""Convolution and dense layers of a bottleneck-v1 residual net, from the
sizes in a configuration's file."""

import math
from typing import Dict, List


def _same(n: int, stride: int) -> int:
    return math.ceil(n / stride)


def layers(cfg: Dict) -> List[Dict]:
    """He et al., arXiv:1512.03385, Table 1: 7x7/2 stem, 3x3/2 max
    pool, stages of 1x1 -> 3x3 -> 1x1(x expansion) bottlenecks with a
    1x1 projection on each stage's first block, global average pool,
    one dense layer.  The stride of a stage's first block sits on its
    first 1x1 (v1, as the paper and ``models/resnet.py`` have it)."""
    layers = []

    def conv(name, hw_in, k, c_in, c_out, stride):
        hw = _same(hw_in, stride)
        layers.append({"name": name, "kind": "conv",
                       "macs": hw * hw * k * k * c_in * c_out})
        return hw

    hw = conv("stem", cfg["image_size"], cfg["stem_kernel"],
              cfg["num_channels"], cfg["stem_width"], 2)
    hw = _same(hw, 2)                                   # 3x3/2 max pool
    c_in = cfg["stem_width"]
    exp = cfg["expansion"]
    for s, (blocks, width) in enumerate(zip(cfg["stage_blocks"],
                                            cfg["stage_widths"])):
        for b in range(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            name = f"s{s}b{b}"
            if b == 0:
                conv(f"{name}_sc", hw, 1, c_in, exp * width, stride)
            hw = conv(f"{name}_a", hw, 1, c_in, width, stride)
            conv(f"{name}_b", hw, 3, width, width, 1)
            conv(f"{name}_c", hw, 1, width, exp * width, 1)
            c_in = exp * width
    layers.append({"name": "fc", "kind": "dense",
                   "macs": c_in * cfg["num_classes"]})
    return layers
