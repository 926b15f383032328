"""Layer-wise trimming of BFS-ordered subgraphs — paper C8 (§2.3, Table 2).

A GNN on a k-hop sampled subgraph only needs hop-``h`` nodes during the first
``k - h`` layers: nodes sampled in later hops stop contributing to the seed
representations. PyG trims by slicing adjacency/features along the BFS
ordering on the fly ("zero-copy"). Here the sampler emits *budgeted, padded*
hops (static per-hop sizes), so trimming is a **static** ``lax.slice`` — free
at trace time, fused by XLA, and crucially shape-stable so the jit cache
never misses. This is the TPU/XLA rendition of the paper's zero-copy narrow.

Trimming no longer drops a loader-prefilled static-layout ELL cache: every
slot's in-edges come from exactly one hop (a block is the frontier exactly
once), so the trimmed graph's ELL is the parent's restricted to the rows of
kept-hop slots. The layout's buckets list their rows in ascending ranges
(``EdgeIndex._ell_ranges``, static pytree aux), so the kept rows form a
prefix of each bucket and the trim is a static slice of it, rounded up to a
row block — the kernels launch over the kept rows only, and their backward
panels shrink with them (see ``_trim_ell``). A bucket with no known ranges
(demand-filled, or a layout whose rows do not ascend) keeps its shape and
masks the dropped rows to capacity padding instead. Because ``EdgeIndex``
keys ``ell_pos`` to COO edge order and kept slots reference only kept
(prefix) edges, the trimmed cache serves *weighted* matmuls too —
per-layer ``edge_weight`` slices gather straight through the inherited
positions, no oracle detour. It equally serves the fused *attention* path
(``EdgeIndex.attend``): kept rows keep their neighbor slots, so deep GATs
keep the flash-GAT kernel on inner hops. A demand-filled *transpose* ELL
survives too (``_trim_ell_transpose`` — per-slot masking, since transpose
rows' out-edges don't form a hop prefix), keeping reversed-flow
(``target_to_source``) attends and transpose matmuls on the kernel.
``trim_to_layer_hetero`` applies the same per-(node type, edge type) —
deep hetero GNNs keep every relation on the fast path as they trim.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.edge_index import EdgeIndex
from repro.kernels.budgets import DEFAULT_BR


def trim_sizes(num_nodes_per_hop: Sequence[int],
               num_edges_per_hop: Sequence[int],
               layer: int) -> Tuple[int, int]:
    """(nodes, edges) still needed when entering GNN layer ``layer`` (0-based).

    With L = len(hops) - 1 total layers, at layer l we keep hops 0..L-l of
    nodes and hops 1..L-l of edges (edge hop h connects hop h-1/h nodes).
    """
    depth = len(num_edges_per_hop)
    keep_hops = depth - layer
    n_nodes = int(sum(num_nodes_per_hop[:keep_hops + 1]))
    n_edges = int(sum(num_edges_per_hop[:keep_hops]))
    return n_nodes, n_edges


def _trim_ell(ell, ranges, boundary: int):
    """Cut a static-layout bucketed ELL down to the rows that keep edges.

    ``boundary`` is the first slot whose in-edges are dropped (hop-``h``
    edges always point into the hop ``h-1`` block, so kept slots form a
    prefix of slot space). ``ranges`` holds each bucket's static row ranges
    (``EdgeIndex._ell_ranges``, or ``None``). A bucket with ranges keeps
    ``sum(clip(boundary - lo, 0, hi - lo))`` rows, a prefix of it: it is
    sliced statically to that count rounded up to a ``DEFAULT_BR`` row
    block, the rounded tail masked to capacity padding (``-1`` row ids,
    all-invalid slots), and dropped when it keeps none. A bucket without
    ranges keeps its shape and is masked row by row. Both are jit-stable
    (the cut is a Python int) and valid on tracer leaves. ``ell_pos`` is
    cut alike; the surviving slots' positions index the COO (BFS) edge
    order and point only at kept prefix edges, so the trimmed cache serves
    weighted matmuls against per-layer-sliced ``edge_weight`` vectors
    directly. Returns the trimmed buckets and their ranges.
    """
    if ell is None:
        return None, None
    if ranges is None:
        ranges = (None,) * len(ell)
    trimmed, trimmed_ranges = [], []
    for (row_ids, ell_idx, ell_pos), runs in zip(ell, ranges):
        if runs is None:
            keep = (row_ids >= 0) & (row_ids < boundary)
        else:
            kept = sum(min(max(boundary - lo, 0), hi - lo)
                       for lo, hi in runs)
            if not kept:
                continue
            cut = min(-(-kept // DEFAULT_BR) * DEFAULT_BR, row_ids.shape[0])
            row_ids, ell_idx, ell_pos = (row_ids[:cut], ell_idx[:cut],
                                         ell_pos[:cut])
            keep = np.arange(cut) < kept if cut > kept else None
            runs = tuple((lo, min(hi, boundary)) for lo, hi in runs
                         if lo < boundary)
        if keep is not None:
            # demand-filled buckets leave their row ids unpadded
            slots = jnp.pad(keep, (0, ell_idx.shape[0] - keep.shape[0]))
            row_ids = jnp.where(keep, row_ids, -1)
            ell_idx = jnp.where(slots[:, None], ell_idx, -1)
            ell_pos = jnp.where(slots[:, None], ell_pos, -1)
        trimmed.append((row_ids, ell_idx, ell_pos))
        trimmed_ranges.append(runs)
    return tuple(trimmed), tuple(trimmed_ranges)


def _trim_ell_transpose(ell, n_edges: int):
    """Mask a *transpose* (CSR-derived) bucketed ELL down to kept edges.

    Unlike the forward table, a transpose row's (source node's) out-edges
    span arbitrary hops, so kept slots do NOT form a row prefix — instead
    each slot is kept iff its COO-keyed ``ell_pos`` references a surviving
    (prefix) edge. Shape-stable elementwise ``where``, valid on tracers;
    rows whose slots all drop become empty rows (0 output, the oracle's
    empty-segment convention). Keeps reversed-flow (``transpose=True``)
    SpMM and fused-attention dispatch on the kernel for inner layers.
    """
    if ell is None:
        return None
    trimmed = []
    for row_ids, ell_idx, ell_pos in ell:
        keep = (ell_pos >= 0) & (ell_pos < n_edges)
        trimmed.append((row_ids,
                        jnp.where(keep, ell_idx, -1),
                        jnp.where(keep, ell_pos, -1)))
    return tuple(trimmed)


def _trim_edge_index(edge_index: EdgeIndex, n_src: int, n_dst: int,
                     n_edges: int, recv_boundary: int) -> EdgeIndex:
    """Static COO slice + ELL cuts; CSR/CSC caches are dropped (their edge
    dimension is data-dependent after a trim) and re-derived on demand."""
    ell, ell_ranges = _trim_ell(edge_index._ell, edge_index._ell_ranges,
                                recv_boundary)
    return EdgeIndex(
        edge_index.data[:, :n_edges], n_src, n_dst,
        edge_index.sort_order, edge_index.is_undirected,
        _ell=ell, _ell_t=_trim_ell_transpose(edge_index._ell_t, n_edges),
        _ell_ranges=ell_ranges)


def trim_to_layer(layer: int, num_nodes_per_hop: Sequence[int],
                  num_edges_per_hop: Sequence[int], x: jnp.ndarray,
                  edge_index, edge_attr: Optional[jnp.ndarray] = None):
    """Slice (x, edge_index[, edge_attr]) to what layer ``layer`` needs.

    Requires BFS ordering: node slots grouped by hop (seeds first), edge
    slots grouped by the hop that discovered them — exactly what
    ``repro.data.sampler`` produces. All sizes static -> jit-stable. A
    prefilled static-layout ELL cache survives the trim (cut to the kept
    rows, see ``_trim_ell``), so trimmed inner layers still hit the Pallas
    kernel, over the rows they keep.
    """
    n_nodes, n_edges = trim_sizes(num_nodes_per_hop, num_edges_per_hop, layer)
    x_t = x[:n_nodes]
    if isinstance(edge_index, EdgeIndex):
        keep_hops = len(num_edges_per_hop) - layer
        recv = int(sum(num_nodes_per_hop[:keep_hops]))
        ei_t = _trim_edge_index(edge_index, n_nodes, n_nodes, n_edges, recv)
    else:
        ei_t = edge_index[:, :n_edges]
    if edge_attr is not None:
        return x_t, ei_t, edge_attr[:n_edges]
    return x_t, ei_t, None


def trim_to_layer_hetero(
        layer: int,
        num_nodes_dict: Dict[str, Sequence[int]],
        num_edges_dict: Dict[Tuple[str, str, str], Sequence[int]],
        x_dict: Dict[str, jnp.ndarray],
        edge_index_dict: Dict[Tuple[str, str, str], jnp.ndarray],
        edge_attr_dict: Optional[Dict] = None):
    """Heterogeneous layer-wise trim: per node type and per edge type.

    ``num_nodes_dict``/``num_edges_dict`` are the hetero sampler's per-hop
    budgets. Each relation's edges are sliced by its own hop counts; the
    node/ELL boundaries come from its endpoint types. Per-relation
    static-layout ELL caches survive, cut to their kept rows (the hetero
    fast path on inner layers).
    """
    depth = len(next(iter(num_edges_dict.values())))
    keep = depth - layer
    n_nodes = {t: int(sum(v[:keep + 1])) for t, v in num_nodes_dict.items()}
    recv = {t: int(sum(v[:keep])) for t, v in num_nodes_dict.items()}
    x_t = {t: x[:n_nodes[t]] for t, x in x_dict.items()}
    ei_t = {}
    for et, ei in edge_index_dict.items():
        n_e = int(sum(num_edges_dict[et][:keep]))
        if isinstance(ei, EdgeIndex):
            ei_t[et] = _trim_edge_index(ei, n_nodes[et[0]], n_nodes[et[2]],
                                        n_e, recv[et[2]])
        else:
            ei_t[et] = ei[:, :n_e]
    if edge_attr_dict is not None:
        attr_t = {et: (None if a is None
                       else a[:int(sum(num_edges_dict[et][:keep]))])
                  for et, a in edge_attr_dict.items()}
        return x_t, ei_t, attr_t
    return x_t, ei_t
