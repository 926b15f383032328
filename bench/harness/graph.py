"""The host graph a cell trains on, and the check of a batch against it.

A generator (``bench/graphs/<name>.py``, named by the configuration's
``graph_generator``) returns a :class:`HostGraph` (a heterogeneous one
``harness.hetero.HeteroHostGraph``) straight in the layout the program's
stores keep: incoming adjacency as CSR over destination
rows, edge ids numbered in that order. :func:`program_store` hands it to
the program's ``Data`` with the reverse-CSR cache filled, so the sampler
never sorts 62M edges at start-up.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict

import numpy as np

# Generation is split into this many chunks, each with its own stream
# derived from (seed, stream, chunk): the result never depends on how many
# cores the machine has.
CHUNKS = 16


@dataclasses.dataclass
class HostGraph:
    x: np.ndarray          # (N, F) float32 node features
    y: np.ndarray          # (N,) int64 labels
    indptr: np.ndarray     # (N+1,) int64: in-edges of node v are
    indices: np.ndarray    # (E,) int64    indices[indptr[v]:indptr[v+1]]
    train_nodes: np.ndarray  # (T,) int64 labelled seed nodes

    @property
    def num_nodes(self) -> int:
        return len(self.indptr) - 1

    @property
    def num_edges(self) -> int:
        return len(self.indices)

    def edge_dst(self, eid: np.ndarray) -> np.ndarray:
        return np.searchsorted(self.indptr, eid, side="right") - 1


def chunked(seed: int, stream: int, total: int,
            fill: Callable[[np.random.Generator, int], np.ndarray],
            workers: int = 8) -> np.ndarray:
    """``total`` draws made in CHUNKS fixed pieces on a thread pool (NumPy's
    generators release the GIL while filling)."""
    bounds = np.linspace(0, total, CHUNKS + 1).astype(np.int64)

    def piece(i):
        rng = np.random.default_rng([seed, stream, i])
        return fill(rng, int(bounds[i + 1] - bounds[i]))

    with ThreadPoolExecutor(max_workers=workers) as pool:
        parts = list(pool.map(piece, range(CHUNKS)))
    return np.concatenate(parts)


def generate(config: Dict, seed: int) -> HostGraph:
    from harness import spec

    gen = spec.load_module("graphs", config["graph_generator"])
    return gen.generate(config, seed)


def program_store(graph: HostGraph):
    """The program's ``Data`` over ``graph``, reverse-CSR cache filled."""
    from repro.data.data import Data
    from repro.data.graph_store import CSRGraph, DEFAULT_ETYPE

    n = graph.num_nodes
    dst = np.repeat(np.arange(n, dtype=np.int64), np.diff(graph.indptr))
    data = Data(x=graph.x, y=graph.y, num_nodes=n,
                edge_index=np.stack([graph.indices, dst]))
    # the edges are already in destination order, so edge ids are positions
    data._set_cache(DEFAULT_ETYPE, "rev_csr", CSRGraph(
        graph.indptr, graph.indices,
        np.arange(graph.num_edges, dtype=np.int64)))
    return data


def batch_mismatches(graph: HostGraph, b: Dict[str, np.ndarray]) -> int:
    """How many rows, labels and edges of one (single-shard) host batch
    disagree with the graph. ``b`` holds the batch's ``x``, ``y``, ``n_id``,
    ``e_id``, ``src``/``dst`` (local slots, the batch's edge order) and
    ``seed_slots``. Seeds must be labelled (training) nodes."""
    n_id, e_id = b["n_id"], b["e_id"]
    real = n_id >= 0
    bad = int((b["x"][~real] != 0).any(axis=1).sum())
    bad += int((b["x"][real] != graph.x[n_id[real]]).any(axis=1).sum())
    seeds = n_id[b["seed_slots"]]
    ok = seeds >= 0
    bad += int((~np.isin(seeds[ok], graph.train_nodes)).sum())
    bad += int((b["y"][ok] != graph.y[seeds[ok]]).sum())
    e_real = e_id >= 0
    src, dst = b["src"][e_real], b["dst"][e_real]
    ge = e_id[e_real]
    bad += int((n_id[src] != graph.indices[ge]).sum())
    bad += int((n_id[dst] != graph.edge_dst(ge)).sum())
    bad += int(((b["src"][~e_real] != 0) | (b["dst"][~e_real] != 0)).sum())
    return bad
