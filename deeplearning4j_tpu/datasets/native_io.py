"""Gateway to the native (C++) data tier.

The reference's ETL bottoms out in native code (JavaCPP-wrapped readers;
SURVEY.md §2.11); here ``native/dataloader.cc`` plays that role.  Product
code asks this module for the native bindings and falls back to the
pure-Python readers when the shared library can't build (no g++ /
header) or when ``DL4J_TPU_NATIVE=0`` disables it — the same posture as
the reference's reflective cuDNN-helper load with an ND4J fallback
(``ConvolutionLayer.java:69-76``).  The fallback is not silent:
:func:`describe` says which tier runs and why, and ``chip_smoke.py``
prints it.
"""

from __future__ import annotations

import os
from typing import Optional

_native = None
_checked = False
_why_python = ""


def native_module() -> Optional[object]:
    """The ``nativeops`` module with a built+loaded shared library, or
    ``None`` when unavailable/disabled.  Probes once per process."""
    global _native, _checked, _why_python
    if os.environ.get("DL4J_TPU_NATIVE", "1") == "0":
        return None
    if not _checked:
        _checked = True
        try:
            from .. import nativeops
            nativeops.load_native()
            _native = nativeops
        except Exception as exc:
            _native = None
            _why_python = f"{type(exc).__name__}: {exc}".splitlines()[0]
    return _native


def native_available() -> bool:
    return native_module() is not None


def describe() -> str:
    """Which reader tier this process uses: ``"native"`` or
    ``"python (<reason>)"``."""
    if native_module() is not None:
        return "native"
    if os.environ.get("DL4J_TPU_NATIVE", "1") == "0":
        return "python (DL4J_TPU_NATIVE=0)"
    return f"python ({_why_python})"
