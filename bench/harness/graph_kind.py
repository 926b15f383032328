"""What differs between a homogeneous and a heterogeneous training cell.

:func:`of` picks the kind once, from the configuration: one with a
``node_types`` key is heterogeneous (``harness.hetero``), any other is one
node type and one edge type (``harness.graph``, ``harness.train``,
``harness.reference``). Set-up, the window and the comparison are the same
code for both; they reach the graph, the program's store, its loader and
batches, and the reference's inputs only through these functions.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict

from harness import graph, reference, train


@dataclasses.dataclass(frozen=True)
class GraphKind:
    # (config, seed) -> the host graph, from bench/graphs/<generator>.py
    generate: Callable
    # host graph -> the program's store over it, reverse CSR filled
    program_store: Callable
    # (store, host graph, cell, seed) -> the program's loader
    make_loader: Callable
    # (program model, trim) -> (params, batch) -> (loss sum, seed count)
    loss: Callable
    # (batch, chips) -> one host dict per shard
    host_shards: Callable
    # (host graph, shard) -> rows, labels and edges that disagree
    batch_mismatches: Callable
    # (host graph, shard) -> the reference's inputs, from the graph
    reference_inputs: Callable
    # shard -> real rows and edges per hop
    real_counts: Callable


HOMOGENEOUS = GraphKind(
    generate=graph.generate, program_store=graph.program_store,
    make_loader=train.neighbor_loader, loss=train.program_loss,
    host_shards=train.host_shards, batch_mismatches=graph.batch_mismatches,
    reference_inputs=reference.reference_inputs,
    real_counts=reference.real_counts)


def of(config: Dict) -> GraphKind:
    if "node_types" in config:
        from harness import hetero

        return hetero.KIND
    return HOMOGENEOUS
