"""Jit-ready heterogeneous fast path: typed batches, per-edge-type static
ELL prefill, grouped projections, and hetero-aware trimming.

Covers the PR-3 chain:

    HeteroNeighborSampler (vectorised, static per-(hop, edge-type) bounds)
      -> HeteroNeighborLoader._make_batch (producer thread)
        -> EdgeIndex.from_coo_prefilled per relation (CSC/CSR + static ELL)
          -> jit'd HeteroGNN step (ONE trace across batches)
             -> per-relation propagate -> spmm_ell_pallas
             -> all per-type projections -> ONE grouped matmul per layer
      -> trim_to_layer_hetero keeps the ELL fast path on inner hops, cut to
         the rows each layer keeps
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.edge_index import EdgeIndex
from repro.core.hetero import HeteroConv, to_hetero
from repro.core.trim import trim_to_layer, trim_to_layer_hetero
from repro.data.data import Data, HeteroData
from repro.data.graph_store import DEFAULT_ETYPE
from repro.data.hetero_sampler import (HeteroBatch, HeteroNeighborLoader,
                                       HeteroNeighborSampler,
                                       hetero_static_slot_bounds)
from repro.data.loader import NeighborLoader
from repro.data.sampler import NeighborSampler
from repro.kernels.grouped_matmul import ops as gmm_ops
from repro.kernels.spmm import ops as spmm_ops
from repro.nn.gnn.conv import SAGEConv

ET_UB = ("user", "buys", "item")
ET_RU = ("item", "rev_buys", "user")
FANOUTS = {ET_UB: [3, 2], ET_RU: [3, 2]}


def _hetero_graph(rng, n_user=40, n_item=60, e=200):
    hd = HeteroData()
    hd.add_nodes("user", rng.standard_normal((n_user, 8)).astype(np.float32))
    hd.add_nodes("item", rng.standard_normal((n_item, 8)).astype(np.float32))
    ub = np.stack([rng.integers(0, n_user, e), rng.integers(0, n_item, e)])
    hd.add_edges(ET_UB, ub)
    hd.add_edges(ET_RU, ub[::-1])
    return hd


def _loader(hd, **kw):
    kw.setdefault("num_neighbors", FANOUTS)
    kw.setdefault("input_type", "item")
    kw.setdefault("input_nodes", np.arange(16))
    kw.setdefault("batch_size", 4)
    return HeteroNeighborLoader(hd, hd, **kw)


# ------------------------------------------------------- static slot bounds
def test_hetero_static_slot_bounds_layout():
    fan = {("u", "b", "i"): [2, 3], ("i", "r", "u"): [2, 2]}
    bounds = hetero_static_slot_bounds(4, fan, "i")
    # hop 0: only the seed type's frontier (slots [1,5)) receives edges —
    # via ("u","b","i") with fanout 2; that discovers 4*2=8 "u" slots
    # [1,9), which hop-1 ("i","r","u") expansion hits with fanout 2.
    assert bounds[("u", "b", "i")] == [(1, 5, 2)]
    assert bounds[("i", "r", "u")] == [(1, 9, 2)]


def test_bounds_match_realised_degrees(rng):
    """Realised per-slot in-degrees never exceed the static bounds (the
    invariant csr_to_ell_static enforces at pack time)."""
    hd = _hetero_graph(rng)
    s = HeteroNeighborSampler(hd, FANOUTS)
    bounds = s.slot_degree_bounds("item", 6)
    out = s.sample("item", np.arange(6))
    for et, bl in bounds.items():
        col = out.col[et][out.edge[et] >= 0]
        deg = np.bincount(col, minlength=len(out.node[et[2]]))
        for lo, hi, k in bl:
            assert deg[lo:hi].max(initial=0) <= k, (et, lo, hi, k)
        # every real edge lands inside a bounded range
        covered = np.zeros(len(out.node[et[2]]), bool)
        for lo, hi, _ in bl:
            covered[lo:hi] = True
        assert covered[col].all(), et


# ------------------------------------------------- hetero vs homogeneous
def test_hetero_sampler_matches_homogeneous_on_single_type(rng):
    """On a single-node-type graph the vectorised hetero sampler must be
    bit-identical to the homogeneous one (same rng stream, same dedup)."""
    n, e = 50, 300
    d = Data(x=rng.standard_normal((n, 8)).astype(np.float32),
             edge_index=np.stack([rng.integers(0, n, e),
                                  rng.integers(0, n, e)]))
    hs = HeteroNeighborSampler(d, {DEFAULT_ETYPE: [4, 3]}, seed=3)
    s = NeighborSampler(d, [4, 3], seed=3)
    seeds = np.arange(6)
    oh, o = hs.sample("node", seeds), s.sample(seeds)
    np.testing.assert_array_equal(oh.node["node"], o.node)
    np.testing.assert_array_equal(oh.row[DEFAULT_ETYPE], o.row)
    np.testing.assert_array_equal(oh.col[DEFAULT_ETYPE], o.col)
    np.testing.assert_array_equal(oh.edge[DEFAULT_ETYPE], o.edge)
    assert oh.num_sampled_nodes["node"] == o.num_sampled_nodes
    assert oh.num_sampled_edges[DEFAULT_ETYPE] == o.num_sampled_edges


def test_hetero_loader_matches_homogeneous_on_single_type(rng):
    """Loader-level parity: same seeds -> same features and aggregation."""
    n, e = 50, 300
    d = Data(x=rng.standard_normal((n, 8)).astype(np.float32),
             edge_index=np.stack([rng.integers(0, n, e),
                                  rng.integers(0, n, e)]))
    hb = next(iter(HeteroNeighborLoader(
        d, d, num_neighbors={DEFAULT_ETYPE: [4, 3]}, input_type="node",
        input_nodes=np.arange(8), batch_size=8, prefill_ell=True, seed=1)))
    b = next(iter(NeighborLoader(d, d, num_neighbors=[4, 3], batch_size=8,
                                 input_nodes=np.arange(8), prefill_ell=True,
                                 seed=1)))
    np.testing.assert_array_equal(np.asarray(hb.x_dict["node"]),
                                  np.asarray(b.x))
    fast = hb.edge_index_dict[DEFAULT_ETYPE].matmul(
        hb.x_dict["node"], force_pallas=True)
    ref = b.edge_index.matmul(b.x, force_pallas=False)
    np.testing.assert_allclose(np.asarray(fast), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- loader jit readiness
def test_hetero_loader_prefills_per_edge_type(rng):
    it = iter(_loader(_hetero_graph(rng), prefill_ell=True))
    b1, b2 = next(it), next(it)
    for b in (b1, b2):
        assert isinstance(b, HeteroBatch)
        for et, ei in b.edge_index_dict.items():
            assert ei._csr is not None and ei._csc is not None, et
            assert ei._ell is not None, et
            colptr, row, perm = (np.asarray(t) for t in ei._csc)
            np.testing.assert_array_equal(
                np.asarray(ei.dst)[perm], np.sort(np.asarray(ei.dst)))
            assert colptr[-1] == ei.num_edges
    # identical pytree structure + shapes across batches (no-recompile)
    assert (jax.tree_util.tree_structure(b1)
            == jax.tree_util.tree_structure(b2))
    assert ([l.shape for l in jax.tree_util.tree_leaves(b1)]
            == [l.shape for l in jax.tree_util.tree_leaves(b2)])


def test_hetero_loader_tail_batch(rng):
    """The silent-tail-drop bug: 10 seeds / batch 4 must yield the 2-seed
    tail with drop_last=False (its own cached-by-size static layout) and
    drop it only when asked."""
    hd = _hetero_graph(rng)
    kept = list(_loader(hd, input_nodes=np.arange(10), drop_last=False,
                        prefill_ell=True))
    dropped = list(_loader(hd, input_nodes=np.arange(10), drop_last=True))
    assert len(kept) == 3 and len(dropped) == 2
    assert len(_loader(hd, input_nodes=np.arange(10), drop_last=False)) == 3
    assert len(_loader(hd, input_nodes=np.arange(10), drop_last=True)) == 2
    tail = kept[-1]
    assert tail.seed_slots.shape == (2,)
    for et, ei in tail.edge_index_dict.items():
        assert ei._ell is not None, et
        fast = ei.matmul(tail.x_dict[et[0]], force_pallas=True)
        raw = EdgeIndex(ei.data, ei.num_src_nodes, ei.num_dst_nodes)
        np.testing.assert_allclose(
            np.asarray(fast),
            np.asarray(raw.matmul(tail.x_dict[et[0]], force_pallas=False)),
            rtol=1e-4, atol=1e-4)


def test_hetero_loader_single_trace_all_relations_pallas(rng, monkeypatch):
    """The acceptance path: prefetch-producer typed batches drive a jit'd
    HeteroGNN with ONE trace across batches, every edge type's aggregation
    dispatching to the Pallas ELL kernel and all per-type projections
    funnelling through one grouped matmul per layer."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    pallas_calls, gmm_calls, traces = [], [], []
    real_p = spmm_ops.spmm_ell_pallas
    monkeypatch.setattr(spmm_ops, "spmm_ell_pallas",
                        lambda *a, **k: (pallas_calls.append(1),
                                         real_p(*a, **k))[1])
    real_g = gmm_ops.grouped_matmul_pallas
    monkeypatch.setattr(gmm_ops, "grouped_matmul_pallas",
                        lambda *a, **k: (gmm_calls.append(1),
                                         real_g(*a, **k))[1])
    hd = _hetero_graph(rng)
    loader = _loader(hd, prefetch=2)
    net = to_hetero(lambda i, o: SAGEConv(i, o),
                    (["user", "item"], list(FANOUTS)), [8, 16, 4])
    params = net.init(jax.random.PRNGKey(0))

    @jax.jit
    def step(params, batch):
        traces.append(1)  # runs only while tracing
        out = net.apply(params, batch.x_dict, batch.edge_index_dict,
                        batch.num_nodes_dict)
        return batch.seed_output(out)

    it = iter(loader)
    b1, b2 = next(it), next(it)
    o1, o2 = step(params, b1), step(params, b2)
    assert len(traces) == 1, "second batch retraced: pytree not static"
    # 2 layers x 2 relations, each with >= 1 ELL bucket
    assert len(pallas_calls) >= 2 * len(FANOUTS), \
        "not every relation reached the Pallas ELL kernel"
    assert len(gmm_calls) == 2, \
        "per-type projections did not group into one matmul per layer"
    # numerics: per-conv (ungrouped) oracle path on cache-less EdgeIndex
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    ref_net = to_hetero(lambda i, o: SAGEConv(i, o),
                        (["user", "item"], list(FANOUTS)), [8, 16, 4],
                        grouped=False)
    for b, o in ((b1, o1), (b2, o2)):
        raw = {et: EdgeIndex(ei.data, ei.num_src_nodes, ei.num_dst_nodes)
               for et, ei in b.edge_index_dict.items()}
        ref = ref_net.apply(params, b.x_dict, raw, b.num_nodes_dict)
        np.testing.assert_allclose(np.asarray(o),
                                   np.asarray(b.seed_output(ref)),
                                   rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------- model layer
def test_hetero_conv_aggr_validation():
    convs = {ET_UB: SAGEConv(8, 16), ET_RU: SAGEConv(8, 16)}
    with pytest.raises(ValueError, match="unknown cross-type aggr"):
        HeteroConv(dict(convs), aggr="median")
    with pytest.raises(ValueError, match="unknown cross-type aggr"):
        to_hetero(lambda i, o: SAGEConv(i, o),
                  (["user", "item"], list(FANOUTS)), [8, 4], aggr="concat")
    assert HeteroConv(dict(convs), aggr="cat").aggr == "cat"


@pytest.mark.parametrize("aggr", ["sum", "mean", "max", "min", "cat"])
def test_grouped_projection_matches_per_conv(rng, aggr):
    """grouped=True (one grouped GEMM) == grouped=False (|E| separate convs)
    for every cross-type aggregation mode."""
    x = {"user": jnp.asarray(rng.standard_normal((12, 8)),
                             dtype=jnp.float32),
         "item": jnp.asarray(rng.standard_normal((9, 8)),
                             dtype=jnp.float32)}
    ei = {ET_UB: EdgeIndex.from_coo(rng.integers(0, 12, 30).astype(np.int32),
                                    rng.integers(0, 9, 30).astype(np.int32),
                                    12, 9),
          ET_RU: EdgeIndex.from_coo(rng.integers(0, 9, 30).astype(np.int32),
                                    rng.integers(0, 12, 30).astype(np.int32),
                                    9, 12)}
    convs = {et: SAGEConv(8, 16) for et in (ET_UB, ET_RU)}
    hc_g = HeteroConv(dict(convs), aggr=aggr, grouped=True)
    hc_s = HeteroConv(dict(convs), aggr=aggr, grouped=False)
    params = hc_g.init(jax.random.PRNGKey(0))
    out_g = hc_g.apply(params, x, ei)
    out_s = hc_s.apply(params, x, ei)
    assert set(out_g) == set(out_s)
    for t in out_g:
        np.testing.assert_allclose(np.asarray(out_g[t]),
                                   np.asarray(out_s[t]),
                                   rtol=2e-4, atol=2e-4)


def test_grouped_auto_off_for_raw_edge_arrays(rng, monkeypatch):
    """Raw (2, E) arrays can't take the grouped path; auto-detect must fall
    back to the per-conv path instead of crashing (even with Pallas
    dispatch on, which otherwise auto-enables grouping)."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    seen = []
    real = gmm_ops.grouped_matmul
    monkeypatch.setattr(gmm_ops, "grouped_matmul",
                        lambda *a, **k: (seen.append(1), real(*a, **k))[1])
    x = {"user": jnp.asarray(rng.standard_normal((12, 8)), jnp.float32),
         "item": jnp.asarray(rng.standard_normal((9, 8)), jnp.float32)}
    ei = {ET_UB: jnp.asarray(np.stack([rng.integers(0, 12, 30),
                                       rng.integers(0, 9, 30)]), jnp.int32),
          ET_RU: jnp.asarray(np.stack([rng.integers(0, 9, 30),
                                       rng.integers(0, 12, 30)]), jnp.int32)}
    hc = HeteroConv({et: SAGEConv(8, 16) for et in (ET_UB, ET_RU)})
    out = hc.apply(hc.init(jax.random.PRNGKey(0)), x, ei,
                   {"user": 12, "item": 9})
    assert not seen and out["item"].shape == (9, 16)


# ------------------------------------------------------------------ trimming
def test_hetero_trim_preserves_seed_outputs(rng):
    """The paper's invariant, hetero edition: layer-wise trimming never
    changes seed representations."""
    b = next(iter(_loader(_hetero_graph(rng), batch_size=8,
                          input_nodes=np.arange(24), prefill_ell=True)))
    net = to_hetero(lambda i, o: SAGEConv(i, o),
                    (["user", "item"], list(FANOUTS)), [8, 16, 4])
    params = net.init(jax.random.PRNGKey(0))
    full = net.apply(params, b.x_dict, b.edge_index_dict, b.num_nodes_dict)
    trim = net.apply(params, b.x_dict, b.edge_index_dict,
                     num_sampled_nodes_dict=b.num_sampled_nodes_dict,
                     num_sampled_edges_dict=b.num_sampled_edges_dict,
                     trim=True)
    np.testing.assert_allclose(np.asarray(b.seed_output(full)),
                               np.asarray(b.seed_output(trim)),
                               rtol=1e-3, atol=1e-4)
    # trimmed inner shapes actually shrink
    assert trim["item"].shape[0] < full["item"].shape[0] or \
        trim["user"].shape[0] < full["user"].shape[0]
    # the static-layout ELL caches are cut to the rows layer 1 keeps
    _, ei_t = trim_to_layer_hetero(1, b.num_sampled_nodes_dict,
                                   b.num_sampled_edges_dict, b.x_dict,
                                   b.edge_index_dict)
    assert sum(r.shape[0] for ei in ei_t.values() for r, _, _ in ei._ell) \
        < sum(r.shape[0] for ei in b.edge_index_dict.values()
              for r, _, _ in ei._ell)
    # trim without the edge budgets is a hard error, not an obscure crash
    with pytest.raises(ValueError, match="num_sampled_edges_dict"):
        net.apply(params, b.x_dict, b.edge_index_dict,
                  num_sampled_nodes_dict=b.num_sampled_nodes_dict,
                  trim=True)


def test_trim_keeps_ell_fast_path(rng, monkeypatch):
    """trim_to_layer must carry a masked static-layout ELL (not drop it) and
    the masked cache must agree with the oracle on the trimmed graph —
    including *weighted* matmuls, whose per-edge weights gather through the
    COO-keyed ``ell_pos`` instead of detouring to the oracle."""
    d = Data(x=rng.standard_normal((200, 16)).astype(np.float32),
             edge_index=np.stack([rng.integers(0, 200, 1200),
                                  rng.integers(0, 200, 1200)]))
    b = next(iter(NeighborLoader(d, d, num_neighbors=[4, 3], batch_size=8,
                                 prefill_ell=True)))
    x_t, ei_t, _ = trim_to_layer(1, b.num_sampled_nodes,
                                 b.num_sampled_edges, b.x, b.edge_index)
    assert ei_t._ell is not None
    # the static-layout buckets are cut to the rows layer 1 keeps: shorter
    # than the parent's, still row-block multiples, kept rows unchanged
    for (r_t, i_t, p_t), (r, i, p) in zip(ei_t._ell, b.edge_index._ell):
        n = r_t.shape[0]
        assert n % 8 == 0 and n < r.shape[0]
        kept = np.asarray(r_t) >= 0
        np.testing.assert_array_equal(np.asarray(r_t)[kept],
                                      np.asarray(r)[:n][kept])
        np.testing.assert_array_equal(np.asarray(i_t)[kept],
                                      np.asarray(i)[:n][kept])
        np.testing.assert_array_equal(np.asarray(p_t)[kept],
                                      np.asarray(p)[:n][kept])
    raw = EdgeIndex(ei_t.data, x_t.shape[0], x_t.shape[0])
    for reduce in ("sum", "mean", "max", "min"):
        fast = ei_t.matmul(x_t, reduce=reduce, force_pallas=True)
        ref = raw.matmul(x_t, reduce=reduce, force_pallas=False)
        np.testing.assert_allclose(np.asarray(fast), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
    # weighted matmul on the inherited (masked) ELL rides the Pallas kernel
    # — no oracle fallback — and still matches the oracle numerically
    calls = []
    real = spmm_ops.spmm_ell_pallas
    monkeypatch.setattr(spmm_ops, "spmm_ell_pallas",
                        lambda *a, **k: (calls.append(1), real(*a, **k))[1])
    w = jnp.asarray(rng.standard_normal(ei_t.num_edges).astype(np.float32))
    got = ei_t.matmul(x_t, edge_weight=w, force_pallas=True)
    assert calls, "weighted trimmed matmul fell back off the Pallas path"
    ref = raw.matmul(x_t, edge_weight=w, force_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
