"""Operations from shapes, and the chip's peaks.

The yardstick for ``device_mfu``: multiply-adds of every convolution and
dense layer of a configuration, computed from the sizes in its file
under ``benchmark/configs/`` and from nothing in the program.  A forward
pass costs 2 operations per multiply-add; a training step costs three
forward passes' worth (forward, gradient of the input, gradient of the
weights).  Batch norm, activations, pooling and the updater are not
counted: they are what MFU charges the step for.

``python benchmark/flops.py`` prints the count for every configuration.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


def layers(cfg: Dict) -> List[Dict]:
    """Every convolution and dense layer of ``cfg`` with its forward
    multiply-adds for one example, from ``families/<family>.py``: a new
    kind of model brings its own file."""
    try:
        family = importlib.import_module(
            f"benchmark.families.{cfg['family']}")
    except (KeyError, ImportError):
        raise KeyError(f"no operation count for family "
                       f"{cfg.get('family')!r}; add "
                       f"benchmark/families/<family>.py") from None
    return family.layers(cfg)


def macs_per_item(cfg: Dict) -> int:
    return sum(l["macs"] for l in layers(cfg))


def flops_per_item(cfg: Dict, training: bool) -> int:
    return 2 * macs_per_item(cfg) * (3 if training else 1)


def chip_peaks(device_kind: str, path: str = None) -> Dict:
    """The row of ``peaks.json`` for ``device_kind``.  A kind the table
    does not hold raises: a share of another chip's peak is a wrong
    number."""
    with open(path or os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)
    try:
        return table["chips"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device_kind "
                       f"{device_kind!r}; add it to benchmark/peaks.json "
                       f"with its source") from None


if __name__ == "__main__":
    import glob
    import sys
    sys.path.insert(0, os.path.dirname(HERE))
    for f in sorted(glob.glob(os.path.join(HERE, "configs", "*.json"))):
        with open(f) as fh:
            c = json.load(fh)
        print(f"{c['name']}: {macs_per_item(c) / 1e9:.3f} G multiply-adds "
              f"forward, {flops_per_item(c, True) / 1e9:.1f} GFLOP a "
              f"training sample")
