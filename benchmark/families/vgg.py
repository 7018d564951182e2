"""Convolution and dense layers of a VGG-style net, from the sizes in a
configuration's file."""

from typing import Dict, List


def layers(cfg: Dict) -> List[Dict]:
    """Simonyan and Zisserman, arXiv:1409.1556, Table 1: blocks of 3x3/1
    same-padded convolutions, a 2x2/2 max pool after each block, then
    the dense top."""
    layers = []
    hw, c_in = cfg["image_size"], cfg["num_channels"]
    k = cfg["conv_kernel"]
    for b, widths in enumerate(cfg["block_widths"]):
        for i, w in enumerate(widths):
            layers.append({"name": f"block{b + 1}_conv{i + 1}",
                           "kind": "conv",
                           "macs": hw * hw * k * k * c_in * w})
            c_in = w
        hw //= 2
    n_in = hw * hw * c_in
    for i, w in enumerate(list(cfg["dense_widths"]) + [cfg["num_classes"]]):
        layers.append({"name": f"dense{i + 1}", "kind": "dense",
                       "macs": n_in * w})
        n_in = w
    return layers
