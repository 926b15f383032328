"""GAT (additive attention, concatenated heads, head-averaged last layer).

The program side is ``repro.nn.gnn.models.make_model("gat", ...)`` (heads 4)
run with layer-wise trimming. The plain reference below follows the GATConv
equations over the sample's COO edges with segment ops:
``z = W h``, ``e_uv = leaky_relu(a_src . z_u + a_dst . z_v, 0.2)``,
``alpha_uv = softmax_u(e_uv)`` over v's incoming edges,
``h_v' = concat_heads(sum_u alpha_uv z_u) + b`` (the mean of the heads on
the last layer), ReLU between layers. No self-loops are added, as in the
program; a node with no incoming edge aggregates to 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.reference import (dtype_of, glorot, layer_sizes, layer_work,
                               matmul, nll_sum)

SLOPE = 0.2


def layers(cfg):
    """Per layer: (in width, heads, width per head, concat)."""
    L, H = int(cfg["num_layers"]), int(cfg["heads"])
    hid = int(cfg["hidden"])
    d = [int(cfg["num_features"])] + [hid] * (L - 1)
    out = []
    for i in range(L):
        last = i == L - 1
        f = int(cfg["num_classes"]) if last else hid // H
        out.append((d[i], H, f, not last))
    return out


def program_model(cfg):
    from repro.nn.gnn.models import make_model

    model = make_model("gat", int(cfg["num_features"]), int(cfg["hidden"]),
                       int(cfg["num_classes"]), int(cfg["num_layers"]))
    if any(c.heads != int(cfg["heads"]) for c in model.convs):
        raise ValueError(f"the program's GAT runs {model.convs[0].heads} "
                         f"heads, the configuration states {cfg['heads']}")
    return model


def init_params(key, cfg):
    """Glorot-uniform projections and attention vectors, zero biases, in
    the program's tree layout."""
    params = {}
    specs = layers(cfg)
    for i, k in enumerate(jax.random.split(key, len(specs))):
        fin, h, f, concat = specs[i]
        k1, k2, k3 = jax.random.split(k, 3)
        params[f"conv{i}"] = {
            "lin": {"w": glorot(k1, (fin, h * f))},
            "att_src": glorot(k2, (h, f)),
            "att_dst": glorot(k3, (h, f)),
            "bias": jnp.zeros((h * f if concat else f,), jnp.float32),
        }
    return params


def reference_loss(params, inp, cfg, numerics):
    """(loss sum, weight) of one shard, computed as ``numerics`` says
    (``harness.reference.NUMERICS``)."""
    dtype = dtype_of(numerics)
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    h = inp["x"].astype(dtype)
    specs = layers(cfg)
    for layer, (_, heads, f, concat) in enumerate(specs):
        n, e = layer_sizes(inp["nodes_per_hop"], inp["edges_per_hop"], layer)
        h = h[:n]
        src, dst, ok = inp["src"][:e], inp["dst"][:e], inp["valid"][:e]
        q = p[f"conv{layer}"]
        z = matmul(h, q["lin"]["w"], numerics).reshape(n, heads, f)
        a_s = (z * q["att_src"]).sum(-1)
        a_d = (z * q["att_dst"]).sum(-1)
        raw = a_s[src] + a_d[dst]                              # (E, H)
        logit = jnp.where(raw >= 0, raw, SLOPE * raw)
        logit = jnp.where(ok[:, None], logit, -jnp.inf)
        top = jax.lax.stop_gradient(
            jax.ops.segment_max(logit, dst, num_segments=n))
        top = jnp.where(jnp.isfinite(top), top, 0)
        ex = jnp.where(ok[:, None], jnp.exp(logit - top[dst]), 0)
        den = jax.ops.segment_sum(ex, dst, num_segments=n)
        num = jax.ops.segment_sum(ex[:, :, None] * z[src], dst,
                                  num_segments=n)
        out = num / jnp.maximum(den, 1e-16)[:, :, None]
        out = out.reshape(n, heads * f) if concat else out.mean(1)
        h = out + q["bias"]
        if layer < len(specs) - 1:
            h = jax.nn.relu(h)
    return nll_sum(h[inp["seed_slots"]], inp["y"], inp["w"])


def aggregations(cfg, counts):
    """Per layer: what the attention aggregation must read and write."""
    out = []
    for layer, (_, heads, f, _) in enumerate(layers(cfg)):
        rows, edges = layer_work(counts, layer)
        out.append({"layer": layer, "kind": "gat", "rows": rows,
                    "edges": edges, "width": heads * f, "heads": heads})
    return out


def step_flops(cfg, counts):
    """Forward + backward FLOPs of one shard's step: the projections and
    attention-vector dots (2 per multiply-add) on every input node of a
    layer, and per edge the logit (add, leaky ReLU, max, subtract, exp,
    sum: 6 per head) and the weighted sum (2 per element). The backward
    doubles the projections except on layer 0, which needs no input
    gradient, and takes twice the forward aggregation (the weighted-sum
    and softmax gradients). Head mean, biases and the loss are left out."""
    total = 0.0
    nodes = counts["nodes"]
    for layer, (fin, heads, f, _) in enumerate(layers(cfg)):
        rows, edges = layer_work(counts, layer)
        n_in = sum(nodes[:len(counts["edges"]) - layer + 1])
        hf = heads * f
        proj = 2.0 * n_in * fin * hf + 2.0 * (n_in + rows) * hf
        agg = float(edges) * (6 * heads + 2 * hf) + float(rows) * hf
        total += proj + agg
        total += (proj if layer == 0 else 2 * proj) + 2 * agg
    return total
