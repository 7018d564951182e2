"""What the ``<kernel>_roofline`` readers share: a kernel's least time on
the chip over the device seconds of its scopes in the traced window."""


def share(record, kernel: str, scope_suffix: str):
    """100 x the larger of ``record["kernels"][kernel]``'s bytes over
    the HBM peak and operations over the bf16 peak (counted from shapes
    by the cell's family file for the traced units), over the seconds of
    the ``layer.<vertex><scope_suffix>`` rows of
    ``record["trace"]["by_scope"]``.  ``None`` where there is nothing to
    read: no such scope, no count, no peaks, no trace."""
    trace, peaks = record.get("trace") or {}, record.get("peaks")
    counts = (record.get("kernels") or {}).get(kernel)
    rows = [r for r in trace.get("by_scope") or ()
            if r[0].startswith("layer.") and r[0].endswith(scope_suffix)]
    seconds = sum(r[2] for r in rows) * trace.get("devices", 1)
    if not rows or not counts or not peaks or seconds <= 0:
        return None
    least = max(counts["bytes"] / peaks["hbm_bytes_per_s"],
                counts["flops"] / peaks["flops_per_s_bf16"])
    return 100.0 * least / seconds
