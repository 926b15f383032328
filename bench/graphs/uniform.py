"""Uniform random directed graph at a configuration's published shape.

``num_edges`` directed edges whose endpoints are drawn uniformly and
independently, features from a standard normal, labels uniform over the
classes, and ``num_train_nodes`` labelled seeds drawn without
replacement. The destination degrees come from one count of uniform
draws, the sources are drawn per edge slot already grouped by
destination, so the graph is born in the stores' reverse-CSR layout.
"""

from __future__ import annotations

import numpy as np

from harness.graph import HostGraph, chunked

# independent streams of one seed
_X, _DST, _SRC, _Y, _TRAIN = range(5)


def generate(config, seed: int) -> HostGraph:
    n = int(config["num_nodes"])
    e = int(config["num_edges"])
    f = int(config["num_features"])
    x = chunked(seed, _X, n * f, lambda r, k: r.standard_normal(
        k, dtype=np.float32)).reshape(n, f)
    dst = chunked(seed, _DST, e, lambda r, k: r.integers(0, n, k))
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=n), out=indptr[1:])
    del dst
    indices = chunked(seed, _SRC, e, lambda r, k: r.integers(0, n, k))
    y = np.random.default_rng([seed, _Y]).integers(
        0, int(config["num_classes"]), n)
    train = np.random.default_rng([seed, _TRAIN]).permutation(n)[
        :int(config["num_train_nodes"])]
    return HostGraph(x=x, y=y, indptr=indptr, indices=indices,
                     train_nodes=np.sort(train))
