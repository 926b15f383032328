"""Shared by the benchmark's own tests: ``pytest bench/`` on the CPU.

Cells of ``BENCHMARK.json`` are shrunk to a toy graph and batch; every
width, fanout, optimizer and precision stays the configuration's. The
heterogeneous fixture cells (``bench/models/rsage.py``) are toy-sized as
their files in ``data/hetero/`` state them, a checkout of their own.
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

HETERO_ROOT = os.path.join(BENCH, "tests", "data", "hetero")

# the toy scale: graph and batch only
TOY_GRAPH = {"num_nodes": 3000, "num_edges": 75000, "num_train_nodes": 600}
TOY_BATCH = 8


def toy_cell(workload: str, mix: str | None = None):
    """``workload`` at the toy scale; ``mix`` puts another traffic file's
    mix (and its data-parallel width) in the cell's place. A cell of
    ``data/hetero/BENCHMARK.json`` comes as its files state it."""
    from harness import spec

    fixtures = spec.load_benchmark(HETERO_ROOT)["workloads"]
    if workload in {w["name"] for w in fixtures}:
        return spec.load_cell(workload, root=HETERO_ROOT)
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
