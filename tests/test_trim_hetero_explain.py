"""Trimming (C8), heterogeneous MP (C4), explainability (C11)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.edge_index import EdgeIndex
from repro.core.explain import Explainer
from repro.core.hetero import GroupedLinear, HeteroConv, to_hetero
from repro.core.trim import trim_sizes, trim_to_layer
from repro.data.data import Data
from repro.data.loader import NeighborLoader
from repro.kernels.spmm import ops as spmm_ops
from repro.nn.gnn.conv import GATConv, SAGEConv
from repro.nn.gnn.models import make_model


# ------------------------------------------------------------------ trimming
def test_trim_sizes_monotone():
    nodes, edges = [9, 40, 80], [40, 80]
    n0, e0 = trim_sizes(nodes, edges, 0)
    n1, e1 = trim_sizes(nodes, edges, 1)
    assert (n0, e0) == (129, 120)
    assert (n1, e1) == (49, 40)
    assert n1 < n0 and e1 < e0


@pytest.mark.parametrize("model_name", ["gcn", "sage", "gin", "gat",
                                        "edgecnn"])
def test_trim_preserves_seed_outputs(rng, model_name):
    """The paper's invariant: trimming never changes seed representations."""
    n = 300
    ei = np.stack([rng.integers(0, n, 1500), rng.integers(0, n, 1500)])
    data = Data(x=rng.standard_normal((n, 16)).astype(np.float32),
                edge_index=ei, y=rng.integers(0, 3, n))
    loader = NeighborLoader(data, data, num_neighbors=[4, 3, 2],
                            batch_size=6)
    batch = next(iter(loader))
    model = make_model(model_name, 16, 32, 4, 3)
    params = model.init(jax.random.PRNGKey(0))
    full = model.apply(params, batch.x, batch.edge_index.data,
                       num_nodes=batch.num_nodes)
    trim = model.apply(params, batch.x, batch.edge_index.data,
                       num_sampled_nodes_per_hop=batch.num_sampled_nodes,
                       num_sampled_edges_per_hop=batch.num_sampled_edges,
                       trim=True)
    np.testing.assert_allclose(
        np.asarray(full[batch.seed_slots]),
        np.asarray(trim[batch.seed_slots]), rtol=1e-3, atol=1e-4)


def test_trim_reduces_flops(rng):
    """Trimmed execution must do strictly less dot work (jaxpr-counted)."""
    from repro.launch import jaxpr_stats
    n = 300
    ei = np.stack([rng.integers(0, n, 1500), rng.integers(0, n, 1500)])
    data = Data(x=rng.standard_normal((n, 16)).astype(np.float32),
                edge_index=ei)
    loader = NeighborLoader(data, data, num_neighbors=[4, 3, 2],
                            batch_size=6, labels_attr=None)
    batch = next(iter(loader))
    model = make_model("sage", 16, 32, 4, 3)
    params = model.init(jax.random.PRNGKey(0))
    f_full = jaxpr_stats.step_stats(
        lambda p: model.apply(p, batch.x, batch.edge_index.data,
                              num_nodes=batch.num_nodes), params)
    f_trim = jaxpr_stats.step_stats(
        lambda p: model.apply(
            p, batch.x, batch.edge_index.data,
            num_sampled_nodes_per_hop=batch.num_sampled_nodes,
            num_sampled_edges_per_hop=batch.num_sampled_edges, trim=True),
        params)
    assert f_trim["dot_flops"] < f_full["dot_flops"] * 0.8


# ------------------------------------------- trimming the static-layout ELL
ET_UB = ("user", "buys", "item")
ET_RU = ("item", "rev_buys", "user")


def _homo_batch(rng, fanouts, batch_size=6, n=300):
    data = Data(x=rng.standard_normal((n, 16)).astype(np.float32),
                edge_index=np.stack([rng.integers(0, n, 1500),
                                     rng.integers(0, n, 1500)]))
    return next(iter(NeighborLoader(data, data, num_neighbors=fanouts,
                                    batch_size=batch_size, prefill_ell=True,
                                    labels_attr=None, seed=0)))


def _cuts(parent_ell, boundary):
    """(bucket, kept rows, rows after the cut) per parent bucket that keeps
    a row: kept rows counted from the packed row ids, the cut rounded up
    to the 8-row block."""
    out = []
    for bucket in parent_ell:
        rows = np.asarray(bucket[0])
        kept = int(((rows >= 0) & (rows < boundary)).sum())
        if kept:
            out.append((bucket, kept, -(-kept // 8) * 8))
    return out


def _assert_cut_to_kept_rows(parent_ell, trimmed_ell, boundary):
    cuts = _cuts(parent_ell, boundary)
    assert len(trimmed_ell) == len(cuts)
    for ((r, i, p), kept, cut), trimmed in zip(cuts, trimmed_ell):
        r, i, p = (np.asarray(a) for a in (r, i, p))
        r_t, i_t, p_t = (np.asarray(a) for a in trimmed)
        assert r_t.shape == (cut,) and i_t.shape == p_t.shape == \
            (cut, i.shape[1])
        assert (r[:kept] < boundary).all(), "kept rows are not a prefix"
        np.testing.assert_array_equal(r_t[:kept], r[:kept])
        np.testing.assert_array_equal(i_t[:kept], i[:kept])
        np.testing.assert_array_equal(p_t[:kept], p[:kept])
        assert (r_t[kept:] == -1).all() and (i_t[kept:] == -1).all() \
            and (p_t[kept:] == -1).all()


@pytest.mark.parametrize("fanouts", [[4, 3, 2], [10, 5, 3]])
def test_trim_slices_static_ell_to_kept_rows(rng, fanouts):
    """Each trimmed layer's static-layout buckets hold exactly the rows it
    keeps, rounded up to 8, with their neighbor slots and positions; a
    bucket that keeps no row is gone ([10, 5, 3]: one K rung per hop)."""
    b = _homo_batch(rng, fanouts)
    full = b.edge_index._ell
    assert len(b.edge_index._ell_ranges) == len(full)
    assert None not in b.edge_index._ell_ranges
    x, ei = b.x, b.edge_index
    for layer in (1, 2):  # as the model trims: each from the last
        x, ei, _ = trim_to_layer(layer, b.num_sampled_nodes,
                                 b.num_sampled_edges, x, ei)
        recv = int(sum(b.num_sampled_nodes[:len(fanouts) - layer]))
        _assert_cut_to_kept_rows(full, ei._ell, recv)
        assert sum(r.shape[0] for r, _, _ in ei._ell) < \
            sum(r.shape[0] for r, _, _ in full)
    if fanouts == [10, 5, 3]:
        assert len(ei._ell) == 1 < len(full)


def _launches(ell, chunk):
    return sum(-(-int(r.shape[0]) // chunk) for r, _, _ in ell)


@pytest.mark.parametrize("model_name", ["sage", "gcn"])
def test_trimmed_spmm_model_launches_over_kept_rows(rng, monkeypatch,
                                                    model_name):
    """SpMM models (SAGE's mean, GCN's weighted sum through ``ell_pos``) on
    a trimmed static cache: one launch per SMEM chunk of the rows each
    layer keeps, fewer than over the whole table, same seed outputs."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    monkeypatch.setattr(spmm_ops, "MAX_PREFETCH_ELEMS", 64)  # 16-row chunks
    chunk = 16
    b = _homo_batch(rng, [4, 3, 2])
    model = make_model(model_name, 16, 8, 3, 3)
    params = model.init(jax.random.PRNGKey(2))
    calls = []
    real = spmm_ops.spmm_ell_pallas
    monkeypatch.setattr(spmm_ops, "spmm_ell_pallas",
                        lambda *a, **k: (calls.append(1), real(*a, **k))[1])
    full = model.apply(params, b.x, b.edge_index)
    assert len(calls) == 3 * _launches(b.edge_index._ell, chunk)
    del calls[:]
    trim = model.apply(params, b.x, b.edge_index,
                       num_sampled_nodes_per_hop=b.num_sampled_nodes,
                       num_sampled_edges_per_hop=b.num_sampled_edges,
                       trim=True)
    want = sum(-(-cut // chunk) for layer in range(3)
               for _, _, cut in _cuts(b.edge_index._ell, int(sum(
                   b.num_sampled_nodes[:3 - layer]))))
    assert len(calls) == want < 3 * _launches(b.edge_index._ell, chunk)
    np.testing.assert_allclose(np.asarray(full[b.seed_slots]),
                               np.asarray(trim[b.seed_slots]),
                               rtol=1e-3, atol=1e-4)


def test_hetero_trim_slices_each_relation(rng):
    """Per-relation static layouts ascend too: the hetero trim cuts each
    relation's buckets to the rows its destination type keeps, and drops
    a relation's bucket whose rows all fall past the boundary."""
    from repro.core.trim import trim_to_layer_hetero
    from repro.data.data import HeteroData
    from repro.data.hetero_sampler import HeteroNeighborLoader
    hd = HeteroData()
    hd.add_nodes("user", rng.standard_normal((120, 8)).astype(np.float32))
    hd.add_nodes("item", rng.standard_normal((160, 8)).astype(np.float32))
    ub = np.stack([rng.integers(0, 120, 900), rng.integers(0, 160, 900)])
    hd.add_edges(ET_UB, ub)
    hd.add_edges(ET_RU, ub[::-1])
    b = next(iter(HeteroNeighborLoader(
        hd, hd, num_neighbors={ET_UB: [3, 2], ET_RU: [3, 2]},
        input_type="item", input_nodes=np.arange(24), batch_size=8,
        prefill_ell=True, seed=0)))
    for ei in b.edge_index_dict.values():
        assert ei._ell_ranges is not None and None not in ei._ell_ranges
    _, ei_t = trim_to_layer_hetero(1, b.num_sampled_nodes_dict,
                                   b.num_sampled_edges_dict, b.x_dict,
                                   b.edge_index_dict)
    rows = {}
    for et, ei in b.edge_index_dict.items():
        recv = int(b.num_sampled_nodes_dict[et[2]][0])
        _assert_cut_to_kept_rows(ei._ell, ei_t[et]._ell, recv)
        rows[et] = (sum(r.shape[0] for r, _, _ in ei._ell),
                    sum(r.shape[0] for r, _, _ in ei_t[et]._ell))
    # items keep their 8 seeds; no user slot below the boundary receives
    assert rows[ET_UB][1] == 8 and rows[ET_RU][1] == 0 < rows[ET_RU][0]


def test_trim_masks_cache_without_static_ranges(rng):
    """Without static row ranges — a demand-filled cache, or a layout
    whose real rows do not ascend — the trim keeps the mask path: same
    shapes, dropped rows turned into capacity padding."""
    from repro.kernels.spmm.ops import ell_row_ranges
    b = _homo_batch(rng, [4, 3, 2])
    recv = int(sum(b.num_sampled_nodes[:2]))
    demand = EdgeIndex(b.edge_index.data, b.num_nodes,
                       b.num_nodes).fill_cache(ell=True)
    assert demand._ell_ranges is None
    unknown = dataclasses.replace(b.edge_index, _ell_ranges=None)
    for ei in (unknown, demand):
        _, ei_t, _ = trim_to_layer(1, b.num_sampled_nodes,
                                   b.num_sampled_edges, b.x, ei)
        assert [r.shape for r, _, _ in ei_t._ell] == \
               [r.shape for r, _, _ in ei._ell]
        for (r, i, _), (r_t, i_t, _) in zip(ei._ell, ei_t._ell):
            r, i, r_t, i_t = (np.asarray(a) for a in (r, i, r_t, i_t))
            keep, n = (r >= 0) & (r < recv), len(r)
            np.testing.assert_array_equal(r_t, np.where(keep, r, -1))
            np.testing.assert_array_equal(
                i_t[:n], np.where(keep[:, None], i[:n], -1))
            assert (i_t[n:] == -1).all()  # unpadded row ids' tail slots
    assert ell_row_ranges(np.array([1, 2, 3, 7, 8, -1, -1, -1])) == \
        ((1, 4), (7, 9))
    assert ell_row_ranges(np.full(8, -1)) == ()
    assert ell_row_ranges(np.array([5, 3, 4, -1])) is None
    assert ell_row_ranges(np.array([1, -1, 2, -1])) is None


# -------------------------------------------------------------------- hetero
def _hetero_fixture(rng):
    nt = ["a", "b"]
    et = [("a", "ab", "b"), ("b", "ba", "a")]
    x = {"a": jnp.asarray(rng.standard_normal((12, 8)).astype(np.float32)),
         "b": jnp.asarray(rng.standard_normal((9, 8)).astype(np.float32))}
    ei = {("a", "ab", "b"): jnp.asarray(np.stack(
        [rng.integers(0, 12, 30), rng.integers(0, 9, 30)]).astype(np.int32)),
        ("b", "ba", "a"): jnp.asarray(np.stack(
            [rng.integers(0, 9, 30), rng.integers(0, 12, 30)]).astype(
            np.int32))}
    return nt, et, x, ei


def test_hetero_conv_matches_manual(rng):
    nt, et, x, ei = _hetero_fixture(rng)
    convs = {t: SAGEConv(8, 16) for t in et}
    hc = HeteroConv(convs, aggr="sum")
    params = hc.init(jax.random.PRNGKey(0))
    out = hc.apply(params, x, ei, {"a": 12, "b": 9})
    manual_b = convs[et[0]].apply(params["a__ab__b"], (x["a"], x["b"]),
                                  ei[et[0]], num_nodes=9)
    np.testing.assert_allclose(np.asarray(out["b"]), np.asarray(manual_b),
                               rtol=1e-4, atol=1e-5)


def test_to_hetero_replicates_per_edge_type(rng):
    nt, et, x, ei = _hetero_fixture(rng)
    model = to_hetero(lambda i, o: SAGEConv(i, o), (nt, et), [8, 16, 4])
    params = model.init(jax.random.PRNGKey(0))
    # param structure: one conv per edge type per layer
    assert set(params["layer0"].keys()) == {"a__ab__b", "b__ba__a"}
    out = model.apply(params, x, ei)
    assert out["a"].shape == (12, 4) and out["b"].shape == (9, 4)
    g = jax.grad(lambda p: sum(
        (v ** 2).sum() for v in model.apply(p, x, ei).values()))(params)
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(g))


def test_grouped_linear_matches_loop(rng):
    types = ["t0", "t1", "t2"]
    x = {t: jnp.asarray(rng.standard_normal((5 + i, 12)).astype(np.float32))
         for i, t in enumerate(types)}
    gl = GroupedLinear(types, 12, 20)
    p = gl.init(jax.random.PRNGKey(0))
    out = gl.apply(p, x)
    for i, t in enumerate(types):
        np.testing.assert_allclose(np.asarray(out[t]),
                                   np.asarray(x[t] @ p["w"][i]), rtol=2e-4,
                                   atol=2e-4)


# ------------------------------------------------------------- explainability
def test_explainer_algorithms_produce_masks(rng):
    n, e, f = 30, 100, 8
    ei = EdgeIndex.from_coo(rng.integers(0, n, e).astype(np.int32),
                            rng.integers(0, n, e).astype(np.int32), n, n)
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    model = make_model("gcn", f, 16, 3, 2)
    params = model.init(jax.random.PRNGKey(0))
    for algo in ("saliency", "integrated_gradients", "gnn_explainer"):
        expl = Explainer(model, params, algorithm=algo, epochs=10)(
            x, ei, node_idx=5)
        assert expl.edge_mask.shape == (e,)
        assert np.isfinite(np.asarray(expl.edge_mask)).all()
        assert set(expl.metrics) == {"fidelity_plus", "fidelity_minus",
                                     "unfaithfulness"}


def test_attention_explainer_uses_gat(rng):
    n, e, f = 25, 80, 8
    ei = EdgeIndex.from_coo(rng.integers(0, n, e).astype(np.int32),
                            rng.integers(0, n, e).astype(np.int32), n, n)
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    model = make_model("gat", f, 16, 3, 2)
    params = model.init(jax.random.PRNGKey(0))
    expl = Explainer(model, params, algorithm="attention")(x, ei, node_idx=2)
    assert expl.edge_mask.shape == (e,)


def test_gnn_explainer_finds_planted_edge(rng):
    """A label fully determined by one edge must rank that edge top-3."""
    n, f = 12, 4
    # node 0's representation driven by node 1 through edge (1 -> 0)
    src = np.concatenate([[1], rng.integers(2, n, 20)]).astype(np.int32)
    dst = np.concatenate([[0], rng.integers(2, n, 20)]).astype(np.int32)
    ei = EdgeIndex.from_coo(src, dst, n, n)
    x = np.zeros((n, f), np.float32)
    x[1] = 10.0  # only node 1 carries signal
    model = make_model("sage", f, 8, 2, 1)
    params = model.init(jax.random.PRNGKey(1))
    expl = Explainer(model, params, algorithm="gnn_explainer", epochs=80)(
        jnp.asarray(x), ei, node_idx=0)
    assert 0 in expl.top_edges(3), "planted edge not in top-3"
