"""Device time of the GAT attention kernel's (``_gat_ell_kernel``)
launches per step and chip, from the trace."""

from metrics._kernels import kernel_s_per_step

KERNEL = "_gat_ell_kernel"


def read(rec):
    t = kernel_s_per_step(rec, KERNEL)
    return None if t is None else 1e3 * t
