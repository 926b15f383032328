"""Device time per step and chip of the kernels' custom-VJP backwards: the
operations compiled under a ``repro_kernel_vjp:<tag>`` scope (for GAT the
panel recompute and scatter-adds of ``_gat_panels_backward``), summed over
tags, within the window. Read from the window's trace file, which keeps
each device operation's op_name (``trace_scopes``)."""

import glob
import os

from harness.train import WINDOW_SPAN
from trace_scopes import vjp_seconds

# where bench/run.py leaves the traced window's profile
TRACE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".out",
    "trace")


def read(rec, trace_dir=TRACE_DIR):
    if not rec.get("trace") or not rec.get("steps"):
        return None
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        return None
    tags = vjp_seconds(paths[0], WINDOW_SPAN)
    if not tags:
        return None
    return 1e3 * sum(tags.values()) / rec["steps"]
