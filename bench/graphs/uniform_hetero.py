"""Uniform random heterogeneous graph at a configuration's published shape.

Per relation, ``num_edges`` directed edges whose endpoints are drawn
uniformly and independently, born in the stores' reverse-CSR layout as
``uniform.py`` makes them: the destination degrees from one count of
uniform draws, the sources drawn per edge slot already grouped by
destination. With ``reverse_edges`` a relation between two types gets its
reverse ``rev_<rel>`` and a relation of one type is made symmetric (its
edges and their reverses), as PyG's ``ToUndirected``; reverses are
regrouped by a stable counting sort on each 16-bit half of the source id,
no comparison sort. Features are standard normal per type, labels uniform
over the classes on the target type, and ``num_train_nodes`` labelled
target nodes are drawn without replacement.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness.graph import chunked
from harness.hetero import Adjacency, HeteroHostGraph

# independent streams of one seed; per type and per relation an offset
_Y, _TRAIN = 0, 1
_X, _DST, _SRC = 1000, 2000, 3000


def _counting_order(keys: np.ndarray) -> np.ndarray:
    """The stable order that groups ``keys`` (ids below 2**32) ascending:
    two passes of NumPy's radix sort, a counting sort per 16-bit digit."""
    if len(keys) and int(keys.max()) >= 2**32:
        raise ValueError("node ids must be below 2**32")
    low = np.argsort((keys & 0xFFFF).astype(np.uint16), kind="stable")
    high = np.argsort((keys[low] >> 16).astype(np.uint16), kind="stable")
    return low[high]


def _reverse(a: Adjacency, n_src: int) -> Adjacency:
    """The relation's reverse: in-edges of each former source, in the order
    of the edges they reverse."""
    dst = np.repeat(np.arange(len(a.indptr) - 1, dtype=np.int64),
                    np.diff(a.indptr))
    order = _counting_order(a.indices)
    indptr = np.zeros(n_src + 1, np.int64)
    np.cumsum(np.bincount(a.indices, minlength=n_src), out=indptr[1:])
    return Adjacency(indptr, dst[order])


def _symmetric(a: Adjacency, n: int) -> Adjacency:
    """A relation of one type with its reverse merged in: each node's
    in-edges are its own, then those of the edges it sends."""
    r = _reverse(a, n)
    own, sent = np.diff(a.indptr), np.diff(r.indptr)
    indptr = a.indptr + r.indptr
    indices = np.empty(a.num_edges + r.num_edges, np.int64)
    indices[np.arange(a.num_edges) + np.repeat(r.indptr[:-1], own)] = (
        a.indices)
    indices[np.arange(r.num_edges) + np.repeat(a.indptr[1:], sent)] = (
        r.indices)
    return Adjacency(indptr, indices)


def _draw(seed: int, i: int, n_src: int, n_dst: int, e: int) -> Adjacency:
    dst = chunked(seed, _DST + i, e, lambda r, k: r.integers(0, n_dst, k))
    indptr = np.zeros(n_dst + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=n_dst), out=indptr[1:])
    del dst
    indices = chunked(seed, _SRC + i, e, lambda r, k: r.integers(0, n_src, k))
    return Adjacency(indptr, indices)


def generate(config, seed: int) -> HeteroHostGraph:
    types = config["node_types"]
    n = {t: int(s["num_nodes"]) for t, s in types.items()}
    x = {}
    for i, (t, s) in enumerate(types.items()):
        f = int(s["num_features"])
        x[t] = chunked(seed, _X + i, n[t] * f, lambda r, k: r.standard_normal(
            k, dtype=np.float32)).reshape(n[t], f)
    reverse = bool(config.get("reverse_edges"))

    def relation(i):
        src, rel, dst, e = config["edge_types"][i]
        a = _draw(seed, i, n[src], n[dst], int(e))
        if not reverse:
            return [((src, rel, dst), a)]
        if src == dst:
            return [((src, rel, dst), _symmetric(a, n[src]))]
        return [((src, rel, dst), a), ((dst, f"rev_{rel}", src),
                                       _reverse(a, n[src]))]

    # relations one after another (chunked() fills each on all threads);
    # their reverses sort while the next relation draws
    with ThreadPoolExecutor(max_workers=2) as pool:
        parts = list(pool.map(relation, range(len(config["edge_types"]))))
    adj = dict(p for part in parts for p in part)
    target = config["target_type"]
    y = np.random.default_rng([seed, _Y]).integers(
        0, int(config["num_classes"]), n[target])
    train = np.random.default_rng([seed, _TRAIN]).permutation(n[target])[
        :int(config["num_train_nodes"])]
    return HeteroHostGraph(x=x, y=y, adj=adj, target_type=target,
                           train_nodes=np.sort(train))
