"""Shared by the benchmark's own tests: ``pytest bench/`` on the CPU.

Cells are shrunk to a toy graph and batch; every width, fanout, optimizer
and precision stays the configuration's.
"""

from __future__ import annotations

import copy
import dataclasses
import json
import os
import sys


BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the toy scale: graph and batch only
TOY_GRAPH = {"num_nodes": 3000, "num_edges": 75000, "num_train_nodes": 600}
TOY_BATCH = 8


def toy_cell(workload: str, mix: str | None = None):
    """``workload`` at the toy scale; ``mix`` puts another traffic file's
    mix (and its data-parallel width) in the cell's place."""
    from harness import spec

    cell = spec.load_cell(workload)
    config = copy.deepcopy(cell.config)
    config.update(TOY_GRAPH)
    traffic = cell.traffic
    if mix is not None:
        with open(os.path.join(BENCH, "traffic", f"{mix}.json")) as f:
            traffic = json.load(f)
    traffic = dict(traffic, batch_per_chip=TOY_BATCH)
    return dataclasses.replace(cell, config=config, traffic=traffic,
                               chips=int(traffic.get("data_parallel", 1)))
