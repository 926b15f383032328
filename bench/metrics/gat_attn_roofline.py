"""The GAT attention kernel's share of the chip's roofline, per step and
chip.

The work is what the step's attention aggregations need, counted from the
batch's real sampled edges and receiving rows: each edge reads the
source's H*F projected row and its H sender logits, forms H logits (add,
leaky ReLU, running max, subtract, exp, sum: 6 operations each) and
accumulates H*F weighted elements (2 each); each receiving row reads its
H receiver logits and writes its H*F outputs after one divide each.
"""

from metrics._kernels import roofline_share

KERNEL = "_gat_ell_kernel"
F32 = 4


def work(aggregations):
    """(FLOPs, bytes) of the attention aggregations of one step and chip."""
    flops = nbytes = 0.0
    for a in aggregations:
        if a["kind"] != "gat":
            continue
        hf, h = a["width"], a["heads"]
        flops += a["edges"] * (6 * h + 2 * hf) + a["rows"] * hf
        nbytes += F32 * (a["edges"] + a["rows"]) * (hf + h)
    return flops, nbytes


def read(rec):
    return roofline_share(rec, KERNEL, work)
