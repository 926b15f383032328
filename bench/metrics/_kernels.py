"""Shared by the kernel metrics: a kernel's device time per step and its
share of the chip's roofline."""

from __future__ import annotations


def kernel_s_per_step(rec, kernel: str):
    """Device seconds of ``kernel``'s launches per step and chip, or None
    where the trace shows none."""
    trace = rec.get("trace")
    if not trace or not rec.get("steps"):
        return None
    hits = [t for name, t in trace["ops_s"].items() if kernel in name]
    if not hits:
        return None
    return sum(hits) / rec["steps"]


def roofline_share(rec, kernel: str, work):
    """100 x (least time the chip needs for ``work``'s operations and
    bytes) / (the kernel's time), per step and chip."""
    t = kernel_s_per_step(rec, kernel)
    if t is None or t <= 0:
        return None
    flops, nbytes = work(rec["aggregations"])
    if flops <= 0 and nbytes <= 0:
        return None
    peaks = rec["peaks"]
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
