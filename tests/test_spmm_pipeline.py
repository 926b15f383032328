"""The blocked-ELL SpMM pipeline: packing, buckets, kernel, dispatch.

Covers the full chain the TPU fast path takes:

    EdgeIndex.get_ell (cached, degree-bucketed packing)
      -> spmm_ell_bucketed (one launch per power-of-two-K bucket)
        -> spmm_ell_pallas (pipelined DMA kernel; interpret mode on CPU)

plus the vectorised host-side packing, the widened max/min fused
MessagePassing path, and the vectorised temporal sampler search.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.edge_index import EdgeIndex
from repro.core.message_passing import MessagePassing
from repro.data.sampler import _temporal_prefix
from repro.kernels.spmm import ops as spmm_ops, ref as spmm_ref
from repro.kernels.spmm.spmm import spmm_ell_pallas

REDUCES = ["sum", "mean", "max", "min"]


def _skewed_csr(rng, n_rows=37, n_cols=29):
    """Real-world-ish degrees: many small rows, a few hubs, some zeros."""
    deg = np.concatenate([rng.integers(0, 4, n_rows - 17),
                          rng.integers(5, 17, 15), [0, 53]])
    rng.shuffle(deg)
    indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int64)
    indices = rng.integers(0, n_cols, int(indptr[-1])).astype(np.int32)
    return indptr, indices


# ------------------------------------------------------------------- packing
def test_csr_to_ell_vectorized_matches_loop(rng):
    indptr, indices = _skewed_csr(rng)
    w = rng.standard_normal(len(indices)).astype(np.float32)
    ell_idx, ell_w = spmm_ops.csr_to_ell(indptr, indices, w)
    rows_pad, k = ell_idx.shape
    ref_idx = np.full((rows_pad, k), -1, np.int32)
    ref_w = np.zeros((rows_pad, k), np.float32)
    for r in range(len(indptr) - 1):  # the old per-row reference semantics
        lo, hi = int(indptr[r]), int(indptr[r + 1])
        take = min(hi - lo, k)
        ref_idx[r, :take] = indices[lo:lo + take]
        ref_w[r, :take] = w[lo:lo + take]
    np.testing.assert_array_equal(ell_idx, ref_idx)
    np.testing.assert_array_equal(ell_w, ref_w)


def test_csr_to_ell_truncates_to_k(rng):
    indptr, indices = _skewed_csr(rng)
    ell_idx, _ = spmm_ops.csr_to_ell(indptr, indices, k=3)
    assert ell_idx.shape[1] == 3
    deg = np.minimum(np.diff(indptr), 3)
    np.testing.assert_array_equal(
        (ell_idx[:len(deg)] >= 0).sum(1), deg)


def test_bucketed_packing_partitions_edges(rng):
    """Every edge in exactly one bucket; every row in at most one; K ladder
    is power-of-two multiples of min_k with <=2x padding waste per row."""
    indptr, indices = _skewed_csr(rng)
    buckets = spmm_ops.csr_to_ell_bucketed(indptr, indices, min_k=4)
    all_pos = np.concatenate([p[p >= 0] for _, _, p in buckets])
    assert sorted(all_pos.tolist()) == list(range(len(indices)))
    all_rows = np.concatenate([r for r, _, _ in buckets])
    assert len(set(all_rows.tolist())) == len(all_rows)
    deg = np.diff(indptr)
    for row_ids, ell_idx, pos in buckets:
        k = ell_idx.shape[1]
        assert k % 4 == 0 and (k // 4) & (k // 4 - 1) == 0  # 4 * 2^j
        assert ell_idx.shape[0] % 8 == 0  # block_rows padded
        np.testing.assert_array_equal(
            (ell_idx[:len(row_ids)] >= 0).sum(1), deg[row_ids])
        # degree fits the bucket: (k/2, k], except the first bucket (1..min_k)
        assert deg[row_ids].max() <= k
        if k > 4:
            assert deg[row_ids].min() > k // 2


def test_bucketed_empty_graph():
    assert spmm_ops.csr_to_ell_bucketed(np.zeros(5, np.int64),
                                        np.zeros(0, np.int32)) == []


# -------------------------------------------------------------------- kernel
@pytest.mark.parametrize("reduce", REDUCES)
def test_kernel_weighted_parity_all_reduces(rng, reduce):
    """Pallas (interpret) == ELL oracle with weights, incl. max/min."""
    rows, k, n, f = 16, 5, 23, 128
    ell = rng.integers(-1, n, (rows, k)).astype(np.int32)
    w = jnp.asarray(rng.standard_normal((rows, k)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    a = spmm_ref.spmm_ell(jnp.asarray(ell), w, x, reduce=reduce)
    b = spmm_ell_pallas(jnp.asarray(ell), w, x, reduce=reduce,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


def test_kernel_zero_degree_rows(rng):
    """All-padding rows produce the 0 fill in every reduce mode."""
    rows, k, n, f = 8, 4, 10, 128
    ell = rng.integers(0, n, (rows, k)).astype(np.int32)
    ell[2] = -1
    ell[5] = -1
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    for reduce in REDUCES:
        out = np.asarray(spmm_ell_pallas(jnp.asarray(ell), None, x,
                                         reduce=reduce, interpret=True))
        np.testing.assert_array_equal(out[2], 0.0)
        np.testing.assert_array_equal(out[5], 0.0)


@pytest.mark.slow
@pytest.mark.parametrize("reduce", REDUCES)
@pytest.mark.parametrize("shape", [(32, 9, 40, 256), (64, 33, 100, 128),
                                   (8, 2, 300, 384)])
def test_kernel_sweep_slow(rng, reduce, shape):
    """Wider (rows, K, N, F) sweep — excluded from tier-1 via `slow`."""
    rows, k, n, f = shape
    ell = rng.integers(-1, n, (rows, k)).astype(np.int32)
    w = jnp.asarray(rng.standard_normal((rows, k)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    a = spmm_ref.spmm_ell(jnp.asarray(ell), w, x, reduce=reduce)
    b = spmm_ell_pallas(jnp.asarray(ell), w, x, reduce=reduce,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------- bucketed dispatch
@pytest.mark.parametrize("reduce", REDUCES)
@pytest.mark.parametrize("weighted", [False, True])
def test_bucketed_spmm_matches_csr_oracle(rng, reduce, weighted):
    indptr, indices = _skewed_csr(rng)
    n_rows, n_cols = len(indptr) - 1, 29
    w = rng.standard_normal(len(indices)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((n_cols, 128)).astype(np.float32))
    buckets = spmm_ops.csr_to_ell_bucketed(indptr, indices)
    wj = jnp.asarray(w) if weighted else None
    a = spmm_ref.spmm_csr(jnp.asarray(indptr), jnp.asarray(indices), x, wj,
                          num_rows=n_rows, reduce=reduce)
    b = spmm_ops.spmm_ell_bucketed(buckets, x, wj, num_rows=n_rows,
                                   reduce=reduce, force_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("reduce", ["sum", "max"])
def test_bucketed_spmm_pallas_interpret(rng, reduce):
    indptr, indices = _skewed_csr(rng)
    n_rows, n_cols = len(indptr) - 1, 29
    w = jnp.asarray(rng.standard_normal(len(indices)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((n_cols, 128)).astype(np.float32))
    buckets = spmm_ops.csr_to_ell_bucketed(indptr, indices)
    a = spmm_ref.spmm_csr(jnp.asarray(indptr), jnp.asarray(indices), x, w,
                          num_rows=n_rows, reduce=reduce)
    b = spmm_ops.spmm_ell_bucketed(buckets, x, w, num_rows=n_rows,
                                   reduce=reduce, force_pallas=True,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


def test_spmm_ell_row_chunking(rng, monkeypatch):
    """Tables above the SMEM prefetch budget split into multiple launches
    along rows — results must be identical to a single launch."""
    monkeypatch.setattr(spmm_ops, "MAX_PREFETCH_ELEMS", 64)  # force chunking
    rows, k, n, f = 40, 5, 23, 128  # 40*5 > 64 -> 4 launches of 8+ rows
    ell = rng.integers(-1, n, (rows, k)).astype(np.int32)
    w = jnp.asarray(rng.standard_normal((rows, k)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    a = spmm_ref.spmm_ell(jnp.asarray(ell), w, x, reduce="sum")
    b = spmm_ops.spmm_ell(jnp.asarray(ell), w, x, reduce="sum",
                          force_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


# ------------------------------------------------------------ EdgeIndex + MP
def test_edge_index_ell_cache_demand_filled(rng):
    src = rng.integers(0, 20, 60).astype(np.int32)
    dst = rng.integers(0, 20, 60).astype(np.int32)
    ei = EdgeIndex.from_coo(src, dst, 20, 20)
    assert ei._ell is None
    ell = ei.get_ell()
    assert ell is not None and ei._ell is ell
    assert ei.get_ell() is ell  # memoised
    x = jnp.asarray(rng.standard_normal((20, 8)).astype(np.float32))
    out = ei.matmul(x, force_pallas=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ei.matmul(x, force_pallas=False)),
                               rtol=1e-4, atol=1e-4)


def test_undirected_ell_cache_shared(rng):
    """A == A^T: the transpose ELL request must reuse the forward packing."""
    src = rng.integers(0, 20, 50).astype(np.int32)
    dst = rng.integers(0, 20, 50).astype(np.int32)
    ei = EdgeIndex.from_coo(src, dst, 20, 20).to_undirected()
    fwd = ei.get_ell()
    assert ei.get_ell(transpose=True) is fwd
    assert ei._ell_t is None  # no second packing stored


def test_fill_cache_packs_ell_when_pallas_on(rng, monkeypatch):
    src = rng.integers(0, 12, 30).astype(np.int32)
    dst = rng.integers(0, 12, 30).astype(np.int32)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    ei = EdgeIndex.from_coo(src, dst, 12, 12).fill_cache()
    assert ei._ell is not None and ei._ell_t is not None
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    ei2 = EdgeIndex.from_coo(src, dst, 12, 12).fill_cache()
    assert ei2._ell is None  # oracle backend: no eager packing cost


def test_edge_index_ell_not_filled_under_jit(rng):
    """Tracing without a cache must fall back to the oracle, not crash."""
    src = rng.integers(0, 15, 40).astype(np.int32)
    dst = rng.integers(0, 15, 40).astype(np.int32)
    ei = EdgeIndex.from_coo(src, dst, 15, 15)
    x = jnp.asarray(rng.standard_normal((15, 4)).astype(np.float32))

    @jax.jit
    def f(x):
        return ei.matmul(x, force_pallas=True)

    out = f(x)
    assert ei._ell is None  # tracer guard held
    np.testing.assert_allclose(np.asarray(out), np.asarray(ei.matmul(x)),
                               rtol=1e-5, atol=1e-5)


def test_propagate_dispatches_to_pallas_ell(rng, monkeypatch):
    """MessagePassing.propagate with a sorted EdgeIndex must reach the
    Pallas ELL kernel (not the XLA oracle) when the Pallas path is forced —
    proven statically by the jaxpr dispatch auditor instead of a
    monkey-patched kernel spy."""
    from repro.analysis import audit_report

    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    n, e, f = 26, 90, 128
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    ei, _ = EdgeIndex.from_coo(src, dst, n, n).sort_by("col")
    mp = MessagePassing(aggr="sum")
    out = mp.propagate({}, ei, x)  # eager warm call packs the ELL cache
    # steady state (the jit-cached trace): fused kernel, zero oracle eqns
    report = audit_report(lambda x_: mp.propagate({}, ei, x_), x)
    report.assert_fused(expect_kernels=("_spmm_ell_kernel",))
    assert report.oracle_fallbacks == 0
    monkeypatch.delenv("REPRO_USE_PALLAS")
    ref_out = MessagePassing(aggr="sum").propagate({}, ei.data, x,
                                                   num_nodes=n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("aggr", ["max", "min"])
def test_fused_path_max_min(rng, aggr):
    """The widened fused predicate: max/min aggr == materialised path."""
    n, e, f = 30, 110, 8
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    ei = EdgeIndex.from_coo(src, dst, n, n)
    mp = MessagePassing(aggr=aggr)
    fused = mp.propagate({}, ei, x)
    raw = mp.propagate({}, ei.data, x, num_nodes=n)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(raw),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------- sampler
def test_temporal_prefix_matches_searchsorted(rng):
    """Vectorised binary search == per-row np.searchsorted (the old loop)."""
    for _ in range(50):
        n_edges = int(rng.integers(0, 200))
        n_rows = int(rng.integers(1, 20))
        cuts = np.sort(rng.integers(0, n_edges + 1, n_rows + 1))
        lo, hi = cuts[:-1], cuts[1:]
        t = np.zeros(n_edges, np.int64)
        for a, b in zip(lo, hi):
            t[a:b] = np.sort(rng.integers(0, 40, b - a))
        bound = rng.integers(-5, 45, n_rows)
        got = _temporal_prefix(t, lo.copy(), hi.copy(), bound)
        want = np.array(
            [a + np.searchsorted(t[a:b], bb, side="right")
             for a, b, bb in zip(lo, hi, bound)], np.int64)
        np.testing.assert_array_equal(got, want)
