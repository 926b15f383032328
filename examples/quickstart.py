"""Quickstart: end-to-end mini-batch GNN training with the PyG 2.0 pipeline.

Builds a synthetic community graph (labels = community id), then runs the
full paper blueprint: Data (FeatureStore+GraphStore) -> NeighborLoader
(budgeted sampler) -> GraphSAGE -> jit'd train step with layer-wise
trimming. Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.data import Data
from repro.data.loader import NeighborLoader
from repro.nn.gnn.models import make_model


def make_community_graph(rng, n=2000, communities=4, feat=32,
                         p_in=0.02, p_out=0.002):
    """Stochastic block model + community-informative features."""
    comm = rng.integers(0, communities, n)
    src, dst = [], []
    n_edges = n * 10
    while len(src) < n_edges:
        a = rng.integers(0, n, n_edges)
        b = rng.integers(0, n, n_edges)
        same = comm[a] == comm[b]
        keep = rng.random(n_edges) < np.where(same, p_in * 50, p_out * 50)
        src.extend(a[keep].tolist())
        dst.extend(b[keep].tolist())
    src, dst = np.array(src[:n_edges]), np.array(dst[:n_edges])
    x = rng.standard_normal((n, feat)).astype(np.float32)
    x += np.eye(communities)[comm] @ rng.standard_normal(
        (communities, feat)).astype(np.float32) * 1.5
    return Data(x=x, edge_index=np.stack([src, dst]), y=comm), comm


def main(epochs=3, batch_size=128, lr=0.01):
    rng = np.random.default_rng(0)
    data, labels = make_community_graph(rng)
    n = len(labels)
    train_nodes = rng.permutation(n)[: n // 2]
    test_nodes = np.setdiff1d(np.arange(n), train_nodes)[:500]

    loader = NeighborLoader(data, data, num_neighbors=[10, 5],
                            batch_size=batch_size, input_nodes=train_nodes,
                            shuffle=True, prefetch=2)
    model = make_model("sage", 32, 64, 4, num_layers=2)
    params = model.init(jax.random.PRNGKey(0))

    # The loader's Batch is a pytree: passed into jit whole, its EdgeIndex
    # keeps the host-packed ELL caches, so on a TPU every aggregation runs
    # the Pallas kernel; its per-hop counts are static.
    @jax.jit
    def train_step(params, batch):
        def loss_fn(p):
            out = model.apply(p, batch.x, batch.edge_index,
                              num_sampled_nodes_per_hop=batch.num_sampled_nodes,
                              num_sampled_edges_per_hop=batch.num_sampled_edges,
                              trim=True)
            logp = jax.nn.log_softmax(out[batch.seed_slots])
            return -jnp.take_along_axis(logp, batch.y[:, None], 1).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params,
                                        grads)
        return params, loss

    for epoch in range(epochs):
        losses = []
        for batch in loader:
            params, loss = train_step(params, batch)
            losses.append(float(loss))
        print(f"epoch {epoch}: loss={np.mean(losses):.4f}")

    # full-batch evaluation (same model code — the paper's seamless
    # mini-batch <-> full-batch transition)
    from repro.core.edge_index import EdgeIndex
    csr = data.get_csr()
    full_ei = EdgeIndex.from_coo(
        np.repeat(np.arange(n), np.diff(csr.indptr)), csr.indices, n, n)
    out = model.apply(params, jnp.asarray(data.x), full_ei)
    acc = float((np.asarray(out.argmax(-1))[test_nodes]
                 == labels[test_nodes]).mean())
    print(f"test accuracy: {acc * 100:.1f}% (4 communities, chance=25%)")
    return acc


if __name__ == "__main__":
    main()
