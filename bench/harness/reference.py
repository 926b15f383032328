"""Plain-JAX pieces the per-model references share.

Nothing here imports the program. Inputs are one shard of a sampled batch
as host arrays (the sample is the input, checked against the graph by
``harness.graph.batch_mismatches``) and features gathered from the graph
itself, never the program's copy.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# How a reference computes:
#   "highest"   float32 with float32 matmuls: the plain reference;
#   "default"   float32 with JAX's default TPU matmul precision, one
#               bfloat16 pass (the program's numerics: the witness);
#   "bfloat16"  every array and operation in bfloat16 (the control);
#   "int8"      matmul operands rounded to a symmetric per-tensor int8
#               grid, float32 elsewhere: one step below the bfloat16
#               operands of the program's default-precision matmuls.
NUMERICS = ("highest", "default", "bfloat16", "int8")


def dtype_of(numerics: str):
    """The storage type of a reference computed as ``numerics`` says."""
    if numerics not in NUMERICS:
        raise ValueError(f"unknown numerics {numerics!r}")
    return jnp.bfloat16 if numerics == "bfloat16" else jnp.float32


def int8_operand(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` rounded to 255 levels of a per-tensor scale; the gradient
    passes straight through, as in int8 training."""
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / 127.0
    q = jnp.round(x / jnp.maximum(scale, 1e-30)) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul(a: jnp.ndarray, b: jnp.ndarray, numerics: str) -> jnp.ndarray:
    if numerics == "int8":
        a, b = int8_operand(a), int8_operand(b)
    precision = (jax.lax.Precision.DEFAULT if numerics == "default"
                 else jax.lax.Precision.HIGHEST)
    return jnp.matmul(a, b, precision=precision)


def layer_sizes(nodes_per_hop: Sequence[int], edges_per_hop: Sequence[int],
                layer: int) -> tuple:
    """(input node slots, edge slots) that layer ``layer`` of an L-layer
    model reads on a BFS-ordered sample of L hops: nodes of hops 0..L-l,
    edges of hops 1..L-l. The outputs of the nodes dropped here cannot
    reach a seed, so the seeds' outputs are those of the whole sample."""
    keep = len(edges_per_hop) - layer
    return (int(sum(nodes_per_hop[:keep + 1])),
            int(sum(edges_per_hop[:keep])))


def reference_inputs(graph, b: Dict[str, np.ndarray]) -> Dict[str, object]:
    """Device inputs of the reference for one host batch shard."""
    n_id = b["n_id"]
    real = n_id >= 0
    x = np.zeros((len(n_id), graph.x.shape[1]), np.float32)
    x[real] = graph.x[n_id[real]]
    seeds = n_id[b["seed_slots"]]
    w = (seeds >= 0).astype(np.float32)
    y = np.where(seeds >= 0, graph.y[np.maximum(seeds, 0)], 0)
    return {
        "x": jnp.asarray(x),
        "src": jnp.asarray(b["src"].astype(np.int32)),
        "dst": jnp.asarray(b["dst"].astype(np.int32)),
        "valid": jnp.asarray(b["e_id"] >= 0),
        "seed_slots": jnp.asarray(b["seed_slots"].astype(np.int32)),
        "y": jnp.asarray(y.astype(np.int32)),
        "w": jnp.asarray(w),
        "nodes_per_hop": tuple(int(v) for v in b["nodes_per_hop"]),
        "edges_per_hop": tuple(int(v) for v in b["edges_per_hop"]),
    }


def nll_sum(logits: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray):
    """(sum of the weighted seeds' NLL, sum of weights), summed in f32."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None], 1)[:, 0]
    return (nll.astype(jnp.float32) * w).sum(), w.sum()


def glorot(key, shape, dtype=jnp.float32):
    lim = (6.0 / (shape[0] + shape[-1])) ** 0.5
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def real_counts(b: Dict[str, np.ndarray]) -> Dict[str, List[int]]:
    """Real (not padding) node and edge counts per hop block of a shard."""
    n_id, e_id = b["n_id"], b["e_id"]
    nb = np.cumsum([0] + list(b["nodes_per_hop"]))
    eb = np.cumsum([0] + list(b["edges_per_hop"]))
    return {
        "nodes": [int((n_id[nb[i]:nb[i + 1]] >= 0).sum())
                  for i in range(len(nb) - 1)],
        "edges": [int((e_id[eb[i]:eb[i + 1]] >= 0).sum())
                  for i in range(len(eb) - 1)],
    }


def layer_work(counts: Dict[str, List[int]], layer: int) -> tuple:
    """(real receiving rows, real edges) of layer ``layer``: edges of hops
    1..L-l into the nodes of hops 0..L-l-1, whose outputs the next layer
    (or the loss) reads."""
    keep = len(counts["edges"]) - layer
    return sum(counts["nodes"][:keep]), sum(counts["edges"][:keep])
