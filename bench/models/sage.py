"""GraphSAGE (mean aggregation, root weight) for node classification.

The program side is ``repro.nn.gnn.models.make_model("sage", ...)`` run with
layer-wise trimming. The plain reference below follows the SAGEConv
equations, ``h_v' = W_l mean_{u in N(v)} h_u + b_l + W_r h_v`` with ReLU
between layers, over the sample's COO edges with segment sums: no kernel,
no ELL table, no cache.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import (dtype_of, glorot, layer_sizes, layer_work,
                               matmul, nll_sum)


def dims(cfg):
    L = int(cfg["num_layers"])
    return ([int(cfg["num_features"])] + [int(cfg["hidden"])] * (L - 1)
            + [int(cfg["num_classes"])])


def program_model(cfg):
    from repro.nn.gnn.models import make_model

    if cfg["aggr"] != "mean":
        raise ValueError(f"sage-products runs mean aggregation, got "
                         f"{cfg['aggr']!r}")
    return make_model("sage", int(cfg["num_features"]), int(cfg["hidden"]),
                      int(cfg["num_classes"]), int(cfg["num_layers"]))


def init_params(key, cfg):
    """Glorot-uniform weights, zero biases, in the program's tree layout."""
    d = dims(cfg)
    params = {}
    for i, k in enumerate(jax.random.split(key, len(d) - 1)):
        kl, kr = jax.random.split(k)
        params[f"conv{i}"] = {
            "lin_l": {"w": glorot(kl, (d[i], d[i + 1])),
                      "b": jnp.zeros((d[i + 1],), jnp.float32)},
            "lin_r": {"w": glorot(kr, (d[i], d[i + 1]))},
        }
    return params


def reference_loss(params, inp, cfg, numerics):
    """(loss sum, weight) of one shard, computed as ``numerics`` says
    (``harness.reference.NUMERICS``)."""
    L = int(cfg["num_layers"])
    dtype = dtype_of(numerics)
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    h = inp["x"].astype(dtype)
    for layer in range(L):
        n, e = layer_sizes(inp["nodes_per_hop"], inp["edges_per_hop"], layer)
        h = h[:n]
        src, dst, ok = inp["src"][:e], inp["dst"][:e], inp["valid"][:e]
        msg = jnp.where(ok[:, None], h[src], jnp.zeros((), dtype))
        tot = jax.ops.segment_sum(msg, dst, num_segments=n)
        cnt = jax.ops.segment_sum(ok.astype(dtype), dst, num_segments=n)
        agg = tot / jnp.maximum(cnt, 1)[:, None]
        q = p[f"conv{layer}"]
        h = (matmul(agg, q["lin_l"]["w"], numerics) + q["lin_l"]["b"]
             + matmul(h, q["lin_r"]["w"], numerics))
        if layer < L - 1:
            h = jax.nn.relu(h)
    return nll_sum(h[inp["seed_slots"]], inp["y"], inp["w"])


def aggregations(cfg, counts):
    """Per layer: what the neighbourhood aggregation must read and write."""
    d = dims(cfg)
    out = []
    for layer in range(int(cfg["num_layers"])):
        rows, edges = layer_work(counts, layer)
        out.append({"layer": layer, "kind": "spmm", "rows": rows,
                    "edges": edges, "width": d[layer], "heads": 1})
    return out


def step_flops(cfg, counts):
    """Forward + backward FLOPs of one shard's step: the projections
    (2 per multiply-add) and the mean aggregation (1 per added element,
    1 per divided one). Layer 0 needs no input gradient, so its backward
    is the weight gradients alone and no aggregation backward."""
    d = dims(cfg)
    total = 0.0
    for layer in range(int(cfg["num_layers"])):
        rows, edges = layer_work(counts, layer)
        proj = 2 * (2.0 * rows * d[layer] * d[layer + 1])  # lin_l + lin_r
        agg = float(edges + rows) * d[layer]
        total += proj + agg                                 # forward
        total += proj if layer == 0 else 2 * proj + agg     # backward
    return total
