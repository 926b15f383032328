"""The SpMM kernel's share of the chip's roofline, per step and chip.

The work is what the step's neighbourhood aggregations need, counted from
the batch's real sampled edges and receiving rows, not from the kernel's
padded ELL slots: the same work whatever implements it. Each real edge
reads one source row of the layer's input width and adds it (one add per
element); each receiving row is written once and divided by its count.
The compute peak is the chip's bf16 matrix peak, so the share is bound by
bytes.
"""

from metrics._kernels import roofline_share

KERNEL = "_spmm_ell_kernel"
F32 = 4


def work(aggregations):
    """(FLOPs, bytes) of the SpMM aggregations of one step and chip."""
    flops = nbytes = 0.0
    for a in aggregations:
        if a["kind"] != "spmm":
            continue
        w = a["width"]
        flops += (a["edges"] + a["rows"]) * w
        nbytes += F32 * (a["edges"] + a["rows"]) * w
    return flops, nbytes


def read(rec):
    return roofline_share(rec, KERNEL, work)
