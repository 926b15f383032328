"""GraphSAGE made heterogeneous by ``to_hetero``, summed across relations.

The model shape of PyG ``examples/hetero/to_hetero_mag.py``: a SAGEConv
(mean aggregation, root weight) per relation and layer, each relation's
output summed into its destination type, ReLU between layers. The program
side is ``repro.core.hetero.to_hetero`` over ``SAGEConv``, run with
layer-wise trimming. The plain reference below follows the equations,
``h_v' = sum_r (W_l^r mean_{u in N_r(v)} h_u + b_l^r + W_r^r h_v)``, over
each relation's COO edges with segment sums: no kernel, no ELL table, no
grouped matmul. A harness fixture (``bench/tests/``), not a configuration
of ``BENCHMARK.json``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.hetero import rel_key, relations
from harness.reference import (dtype_of, glorot, matmul, nll_sum)


def dims(cfg):
    widths = {int(s["num_features"]) for s in cfg["node_types"].values()}
    if len(widths) != 1:
        raise ValueError(f"to_hetero SAGE takes one input width for every "
                         f"node type, the configuration states {widths}")
    L = int(cfg["num_layers"])
    return ([widths.pop()] + [int(cfg["hidden"])] * (L - 1)
            + [int(cfg["num_classes"])])


def _relations(cfg):
    rels = relations(cfg)
    lonely = set(cfg["node_types"]) - {r[2] for r in rels}
    if lonely:
        raise ValueError(f"node types {sorted(lonely)} receive no relation; "
                         f"set reverse_edges")
    return rels


def program_model(cfg):
    from repro.core.hetero import to_hetero
    from repro.nn.gnn.conv import SAGEConv

    if cfg["aggr"] != "mean" or cfg["cross_type_aggr"] != "sum":
        raise ValueError(f"rsage runs mean aggregation summed across "
                         f"relations, got {cfg['aggr']!r}, "
                         f"{cfg['cross_type_aggr']!r}")
    return to_hetero(lambda i, o: SAGEConv(i, o, aggr="mean"),
                     (list(cfg["node_types"]), _relations(cfg)), dims(cfg),
                     aggr="sum")


def init_params(key, cfg):
    """Glorot-uniform weights, zero biases, in the program's tree layout."""
    d, rels = dims(cfg), _relations(cfg)
    params = {}
    for i, k in enumerate(jax.random.split(key, len(d) - 1)):
        layer = {}
        for rel, kr in zip(rels, jax.random.split(k, len(rels))):
            k1, k2 = jax.random.split(kr)
            layer[rel_key(rel)] = {
                "lin_l": {"w": glorot(k1, (d[i], d[i + 1])),
                          "b": jnp.zeros((d[i + 1],), jnp.float32)},
                "lin_r": {"w": glorot(k2, (d[i], d[i + 1]))},
            }
        params[f"layer{i}"] = layer
    return params


def _kept(nodes, edges, depth, layer):
    """(node slots per type, edge slots per relation) that layer ``layer``
    reads: nodes of hops 0..L-l, edges of hops 1..L-l."""
    keep = depth - layer
    return ({t: sum(v[:keep + 1]) for t, v in nodes.items()},
            {r: sum(v[:keep]) for r, v in edges.items()})


def reference_loss(params, inp, cfg, numerics):
    """(loss sum, weight) of one batch, computed as ``numerics`` says
    (``harness.reference.NUMERICS``)."""
    dtype = dtype_of(numerics)
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    h = {t: x.astype(dtype) for t, x in inp["x"].items()}
    nodes, edges = dict(inp["nodes_per_hop"]), dict(inp["edges_per_hop"])
    L, depth = int(cfg["num_layers"]), len(next(iter(edges.values())))
    for layer in range(L):
        n, e = _kept(nodes, edges, depth, layer)
        h = {t: v[:n[t]] for t, v in h.items()}
        out = {}
        for rel in _relations(cfg):
            src_t, _, dst_t = rel
            k = e[rel]
            src, dst = inp["src"][rel][:k], inp["dst"][rel][:k]
            ok = inp["valid"][rel][:k]
            msg = jnp.where(ok[:, None], h[src_t][src], jnp.zeros((), dtype))
            tot = jax.ops.segment_sum(msg, dst, num_segments=n[dst_t])
            cnt = jax.ops.segment_sum(ok.astype(dtype), dst,
                                      num_segments=n[dst_t])
            agg = tot / jnp.maximum(cnt, 1)[:, None]
            q = p[f"layer{layer}"][rel_key(rel)]
            o = (matmul(agg, q["lin_l"]["w"], numerics) + q["lin_l"]["b"]
                 + matmul(h[dst_t], q["lin_r"]["w"], numerics))
            out[dst_t] = o if dst_t not in out else out[dst_t] + o
        h = out if layer == L - 1 else {t: jax.nn.relu(v)
                                        for t, v in out.items()}
    target = cfg["target_type"]
    return nll_sum(h[target][inp["seed_slots"]], inp["y"], inp["w"])


def _work(counts, rel, depth, layer):
    """(real receiving rows, real edges) of one relation in one layer."""
    keep = depth - layer
    return (sum(counts["nodes"][rel[2]][:keep]),
            sum(counts["edges"][rel][:keep]))


def aggregations(cfg, counts):
    """Per layer and relation: what the neighbourhood aggregation must
    read and write."""
    d = dims(cfg)
    depth = len(next(iter(counts["edges"].values())))
    out = []
    for layer in range(int(cfg["num_layers"])):
        for rel in _relations(cfg):
            rows, edges = _work(counts, rel, depth, layer)
            out.append({"layer": layer, "kind": "spmm",
                        "relation": rel_key(rel), "rows": rows,
                        "edges": edges, "width": d[layer], "heads": 1})
    return out


def step_flops(cfg, counts):
    """Forward + backward FLOPs of one batch's step, per relation as
    ``sage.py`` counts a layer: the projections (2 per multiply-add) and
    the mean aggregation (1 per added element, 1 per divided one); layer
    0 needs no input gradient."""
    d = dims(cfg)
    depth = len(next(iter(counts["edges"].values())))
    total = 0.0
    for layer in range(int(cfg["num_layers"])):
        for rel in _relations(cfg):
            rows, edges = _work(counts, rel, depth, layer)
            proj = 2 * (2.0 * rows * d[layer] * d[layer + 1])
            agg = float(edges + rows) * d[layer]
            total += proj + agg
            total += proj if layer == 0 else 2 * proj + agg
    return total
