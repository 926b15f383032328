"""The heterogeneous graph kind: typed nodes, typed relations.

A heterogeneous configuration (``bench/configs/<name>.json``) states:

  * ``node_types``     ``{type: {"num_nodes": n, "num_features": f}}``;
  * ``edge_types``     ``[[src, rel, dst, num_edges], ...]``;
  * ``reverse_edges``  true adds ``(dst, "rev_<rel>", src)`` for each
                       relation between two types and makes a relation of
                       one type symmetric, as PyG's ``ToUndirected``;
  * ``target_type``, ``num_classes``, ``num_train_nodes`` (labelled seeds
    of the target type) and ``graph_generator``.

Its traffic keeps the homogeneous keys; ``num_neighbors`` is a list, one
fanout per hop for every relation, or a dict of such lists keyed
``"src__rel__dst"``. The program's ``HeteroNeighborLoader`` has no
shards, so a heterogeneous cell takes one chip.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import graph
from harness.graph_kind import GraphKind
from harness.spec import SpecError

Relation = Tuple[str, str, str]


def rel_key(rel: Relation) -> str:
    """``"src__rel__dst"``: a relation's name in traffic files, parameter
    trees and records."""
    return "__".join(rel)


def relations(config) -> List[Relation]:
    """Every relation the graph holds, reverses included, in the order the
    configuration lists them (a relation's reverse right after it)."""
    out = []
    for src, rel, dst, _ in config["edge_types"]:
        out.append((src, rel, dst))
        if config.get("reverse_edges") and src != dst:
            out.append((dst, f"rev_{rel}", src))
    return out


@dataclasses.dataclass
class Adjacency:
    """One relation's incoming adjacency: the in-edges of destination ``v``
    are ``indices[indptr[v]:indptr[v+1]]`` (source ids); edge ids number
    them in that order."""
    indptr: np.ndarray     # (N_dst+1,) int64
    indices: np.ndarray    # (E,) int64

    @property
    def num_edges(self) -> int:
        return len(self.indices)

    def edge_dst(self, eid: np.ndarray) -> np.ndarray:
        return np.searchsorted(self.indptr, eid, side="right") - 1


@dataclasses.dataclass
class HeteroHostGraph:
    x: Dict[str, np.ndarray]          # per node type (N_t, F_t) float32
    y: np.ndarray                     # (N_target,) int64 labels
    adj: Dict[Relation, Adjacency]    # per relation, as relations() lists
    target_type: str
    train_nodes: np.ndarray           # (T,) int64 labelled target nodes

    @property
    def num_nodes(self) -> int:
        return sum(len(x) for x in self.x.values())

    @property
    def num_edges(self) -> int:
        return sum(a.num_edges for a in self.adj.values())


def program_store(g: HeteroHostGraph):
    """The program's ``HeteroData`` over ``g``, every relation's reverse-CSR
    cache filled, so its sampler sorts nothing."""
    from repro.data.data import HeteroData
    from repro.data.graph_store import CSRGraph

    data = HeteroData()
    for t, x in g.x.items():
        data.add_nodes(t, x, **({"y": g.y} if t == g.target_type else {}))
    for rel, a in g.adj.items():
        n_dst = len(a.indptr) - 1
        dst = np.repeat(np.arange(n_dst, dtype=np.int64), np.diff(a.indptr))
        data.add_edges(rel, np.stack([a.indices, dst]))
        # the store keeps max(N_src, N_dst) rows for a relation; the rows
        # past the destinations' are empty
        rows = max(len(g.x[rel[0]]), n_dst)
        indptr = np.concatenate(
            [a.indptr, np.full(rows - n_dst, a.num_edges, np.int64)])
        data._set_cache(rel, "rev_csr", CSRGraph(
            indptr, a.indices, np.arange(a.num_edges, dtype=np.int64)))
    return data


def fanouts(config, traffic) -> Dict[Relation, List[int]]:
    """The mix's fanouts per relation."""
    rels = relations(config)
    nn = traffic["num_neighbors"]
    if isinstance(nn, list):
        return {rel: list(nn) for rel in rels}
    keys = [rel_key(rel) for rel in rels]
    if set(nn) != set(keys):
        raise SpecError(f"num_neighbors names {sorted(nn)}; the graph's "
                        f"relations are {sorted(keys)}")
    return {rel: list(nn[k]) for rel, k in zip(rels, keys)}


def make_loader(store, g: HeteroHostGraph, cell, seed: int):
    """The program's ``HeteroNeighborLoader`` over ``store``, seeded on the
    target type's labelled nodes."""
    from repro.data.hetero_sampler import HeteroNeighborLoader

    if cell.chips != 1:
        raise SpecError(f"{cell.name}: a heterogeneous cell runs on one "
                        f"chip (HeteroNeighborLoader has no shards), not "
                        f"{cell.chips}")
    tr = cell.traffic
    return HeteroNeighborLoader(
        store, store, num_neighbors=fanouts(cell.config, tr),
        input_type=g.target_type, input_nodes=g.train_nodes,
        batch_size=int(tr["batch_per_chip"]),
        shuffle=bool(tr["shuffle"]), drop_last=bool(tr["drop_last"]),
        pipeline_depth=int(tr["pipeline_depth"]),
        prefetch=int(tr["prefetch"]), seed=seed)


def program_loss(model, trim: bool):
    """``(params, batch) -> (loss sum, real-seed count)``: NLL of the seeds'
    labels; a seed counts where its node id is real."""

    def loss_fn(params, batch):
        out = model.apply(
            params, batch.x_dict, batch.edge_index_dict,
            num_sampled_nodes_dict=batch.num_sampled_nodes_dict,
            num_sampled_edges_dict=batch.num_sampled_edges_dict, trim=trim)
        logp = jax.nn.log_softmax(batch.seed_output(out))
        nll = -jnp.take_along_axis(logp, batch.y[:, None], 1)[:, 0]
        seeds = batch.n_id_dict[batch.seed_type][batch.seed_slots]
        w = (seeds >= 0).astype(jnp.float32)
        return (nll * w).sum(), w.sum()

    return loss_fn


def host_shards(batch, chips: int) -> List[Dict]:
    """Host copy of a batch: one shard, with a dict per node type (``x``,
    ``n_id``) and per relation (``src``/``dst`` local slots in the batch's
    edge order, ``e_id``)."""
    x, data, n_id, e_id, seed_slots, y = jax.device_get(
        (batch.x_dict,
         {rel: ei.data for rel, ei in batch.edge_index_dict.items()},
         batch.n_id_dict, batch.e_id_dict, batch.seed_slots, batch.y))
    return [{"x": x, "src": {rel: d[0] for rel, d in data.items()},
             "dst": {rel: d[1] for rel, d in data.items()},
             "n_id": n_id, "e_id": e_id, "seed_slots": seed_slots, "y": y,
             "nodes_per_hop": {t: list(v) for t, v in
                               batch.num_sampled_nodes_dict.items()},
             "edges_per_hop": {rel: list(v) for rel, v in
                               batch.num_sampled_edges_dict.items()}}]


def batch_mismatches(g: HeteroHostGraph, b: Dict) -> int:
    """How many rows, labels and edges of a host batch disagree with the
    graph: every type's rows, the seeds' labels (seeds must be labelled
    target nodes) and every relation's edges."""
    bad = 0
    for t, n_id in b["n_id"].items():
        real = n_id >= 0
        x = b["x"][t]
        bad += int((x[~real] != 0).any(axis=1).sum())
        bad += int((x[real] != g.x[t][n_id[real]]).any(axis=1).sum())
    seeds = b["n_id"][g.target_type][b["seed_slots"]]
    ok = seeds >= 0
    bad += int((~np.isin(seeds[ok], g.train_nodes)).sum())
    bad += int((b["y"][ok] != g.y[seeds[ok]]).sum())
    for rel, e_id in b["e_id"].items():
        a = g.adj[rel]
        e_real = e_id >= 0
        src, dst = b["src"][rel][e_real], b["dst"][rel][e_real]
        ge = e_id[e_real]
        bad += int((b["n_id"][rel[0]][src] != a.indices[ge]).sum())
        bad += int((b["n_id"][rel[2]][dst] != a.edge_dst(ge)).sum())
        bad += int(((b["src"][rel][~e_real] != 0)
                    | (b["dst"][rel][~e_real] != 0)).sum())
    return bad


def reference_inputs(g: HeteroHostGraph, b: Dict) -> Dict[str, object]:
    """Device inputs of the reference for one host batch: each type's
    features gathered from the graph itself."""
    x = {}
    for t, n_id in b["n_id"].items():
        real = n_id >= 0
        xt = np.zeros((len(n_id), g.x[t].shape[1]), np.float32)
        xt[real] = g.x[t][n_id[real]]
        x[t] = jnp.asarray(xt)
    seeds = b["n_id"][g.target_type][b["seed_slots"]]
    w = (seeds >= 0).astype(np.float32)
    y = np.where(seeds >= 0, g.y[np.maximum(seeds, 0)], 0)
    return {
        "x": x,
        "src": {r: jnp.asarray(v.astype(np.int32)) for r, v in
                b["src"].items()},
        "dst": {r: jnp.asarray(v.astype(np.int32)) for r, v in
                b["dst"].items()},
        "valid": {r: jnp.asarray(e >= 0) for r, e in b["e_id"].items()},
        "seed_slots": jnp.asarray(b["seed_slots"].astype(np.int32)),
        "y": jnp.asarray(y.astype(np.int32)),
        "w": jnp.asarray(w),
        "nodes_per_hop": tuple(sorted(
            (t, tuple(int(n) for n in v))
            for t, v in b["nodes_per_hop"].items())),
        "edges_per_hop": tuple(sorted(
            (r, tuple(int(n) for n in v))
            for r, v in b["edges_per_hop"].items())),
    }


def _real_per_block(ids: np.ndarray, sizes) -> List[int]:
    bounds = np.cumsum([0] + list(sizes))
    return [int((ids[bounds[i]:bounds[i + 1]] >= 0).sum())
            for i in range(len(sizes))]


def real_counts(b: Dict) -> Dict[str, Dict]:
    """Real (not padding) rows per node type and edges per relation, per
    hop block of a shard."""
    return {"nodes": {t: _real_per_block(n_id, b["nodes_per_hop"][t])
                      for t, n_id in b["n_id"].items()},
            "edges": {r: _real_per_block(e_id, b["edges_per_hop"][r])
                      for r, e_id in b["e_id"].items()}}


KIND = GraphKind(
    generate=graph.generate, program_store=program_store,
    make_loader=make_loader, loss=program_loss, host_shards=host_shards,
    batch_mismatches=batch_mismatches, reference_inputs=reference_inputs,
    real_counts=real_counts)
