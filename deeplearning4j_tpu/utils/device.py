"""Host-fetch helpers for device arrays.

``np.asarray`` on a jax Array is a SYNCHRONOUS device->host transfer:
fetching N arrays in a loop costs N serial round trips — a
StatsListener post or checkpoint write on ResNet-50 fetches ~320 param
arrays.  Starting all copies with ``copy_to_host_async`` before the
first blocking convert overlaps them into ~one round trip.
"""

from typing import Iterable, List

import numpy as np


def fetch_all(arrays: Iterable) -> List[np.ndarray]:
    """numpy copies of many device arrays, copies started async first."""
    arrays = list(arrays)
    for a in arrays:
        if hasattr(a, "copy_to_host_async"):
            a.copy_to_host_async()
    return [np.asarray(a) for a in arrays]
