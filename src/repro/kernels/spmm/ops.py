"""Public SpMM entry points with kernel/oracle dispatch + format packing.

Dispatch decision tree (see also ROADMAP.md):

    spmm over a sorted adjacency
    ├── CSR given directly (`spmm_csr`)          -> XLA segment oracle
    └── blocked-ELL given (`spmm_ell[_bucketed]`)
        ├── TPU backend, or `force_pallas=True`  -> Pallas pipelined kernel
        │     └── non-TPU backend               -> interpret mode (tests)
        └── otherwise                            -> jnp ELL oracle (XLA fuses)

Packing is host-side (shape decisions cannot trace): ``csr_to_ell`` pads
every row to one fixed K; ``csr_to_ell_bucketed`` instead groups rows into
power-of-two-K degree buckets so skewed real-world degree distributions do
not pay max-degree padding — one kernel launch per bucket, disjoint row
sets scattered back into a single output.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import budgets as hw_budgets, interpret_default, use_pallas
from repro.kernels.budgets import MAX_PREFETCH_ELEMS  # noqa: F401  re-export
from repro.kernels.spmm import ref
from repro.kernels.spmm.spmm import spmm_ell_pallas

# A degree bucket: (row_ids, ell_idx, ell_pos).
#   row_ids: (R_b,)      original row ids covered by this bucket
#   ell_idx: (R_pad, K)  int32 neighbor table, -1 = padding, R_pad % BR == 0
#   ell_pos: (R_pad, K)  int32 position of each slot in the CSR edge order
#                        (-1 = padding) — lets callers gather per-call edge
#                        weights without re-packing.
EllBucket = Tuple[np.ndarray, np.ndarray, np.ndarray]


def spmm_csr(indptr: jnp.ndarray, indices: jnp.ndarray, x: jnp.ndarray,
             weight: Optional[jnp.ndarray] = None, *, num_rows: int,
             reduce: str = "sum") -> jnp.ndarray:
    """CSR SpMM — jit-friendly; XLA path everywhere, Pallas on TPU via ELL.

    The CSR->ELL conversion requires host-side shape decisions, so the Pallas
    path is taken only when the caller pre-packs via :func:`csr_to_ell` /
    :func:`csr_to_ell_bucketed` (``EdgeIndex`` does this in its demand-filled
    ELL cache); direct CSR calls use the fused XLA oracle (itself the paper's
    "sorted segment reduction" fast path).
    """
    return ref.spmm_csr(indptr, indices, x, weight, num_rows=num_rows,
                        reduce=reduce)


def _ell_positions(starts: np.ndarray, deg: np.ndarray, k: int,
                   block_rows: int) -> np.ndarray:
    """Vectorised CSR -> ELL slot map: (R_pad, k) edge positions, -1 = pad.

    ``starts[i]`` is row i's first edge position, ``deg[i]`` its length —
    callers pass either the full CSR (``indptr[:-1], diff(indptr)``) or a
    row subset (one degree bucket). Rows longer than ``k`` truncate; the row
    count pads up to a ``block_rows`` multiple.
    """
    num_rows = len(deg)
    rows_pad = -(-max(num_rows, 1) // block_rows) * block_rows
    cols = np.arange(k)
    mask = cols[None, :] < np.minimum(deg, k)[:, None]
    pos = np.where(mask, starts[:, None] + cols[None, :], -1)
    if rows_pad > num_rows:
        pos = np.concatenate(
            [pos, np.full((rows_pad - num_rows, k), -1, pos.dtype)], axis=0)
    return pos.astype(np.int32)


def csr_to_ell(indptr: np.ndarray, indices: np.ndarray,
               weight: Optional[np.ndarray] = None, *, block_rows: int = 8,
               k: Optional[int] = None
               ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Host-side CSR -> blocked-ELL packing (rows padded to `k` neighbors).

    Fully vectorised (no per-row Python loop); rows longer than ``k`` are
    truncated, shorter rows padded with ``-1``.
    """
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    deg = np.diff(indptr)
    if k is None:
        k = max(int(deg.max()) if deg.size else 1, 1)
    hw_budgets.check_ell_rung(k, block_rows=block_rows,
                              context="csr_to_ell")
    pos = _ell_positions(indptr[:-1], deg, k, block_rows)
    mask = pos >= 0
    safe = np.where(mask, pos, 0)
    ell_idx = np.where(mask, indices[safe], -1).astype(np.int32)
    ell_w = None
    if weight is not None:
        ell_w = np.where(mask, np.asarray(weight)[safe], 0.0).astype(
            np.float32)
    return ell_idx, ell_w


def csr_to_ell_bucketed(indptr: np.ndarray, indices: np.ndarray, *,
                        block_rows: int = 8,
                        min_k: int = 4) -> List[EllBucket]:
    """CSR -> degree-bucketed blocked-ELL (power-of-two K ladder).

    Bucket ``j`` holds the rows with degree in ``(K_j/2, K_j]`` where
    ``K_j = min_k * 2**j`` (the first bucket takes degrees ``1..min_k``), so
    per-row padding waste is bounded by 2x instead of max-degree. Zero-degree
    rows appear in no bucket (their output is the reduce identity / 0 fill).
    Every edge appears in exactly one bucket and every row in at most one.
    """
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    deg = np.diff(indptr)
    buckets: List[EllBucket] = []
    if deg.size == 0 or int(deg.max()) == 0:
        return buckets
    max_deg = int(deg.max())
    lower, k = 0, min_k
    while lower < max_deg:
        sel = np.nonzero((deg > lower) & (deg <= k))[0]
        if sel.size:
            hw_budgets.check_ell_rung(k, block_rows=block_rows,
                                      context="csr_to_ell_bucketed")
            pos = _ell_positions(indptr[sel], deg[sel], k, block_rows)
            safe = np.where(pos >= 0, pos, 0)
            ell_idx = np.where(pos >= 0, indices[safe], -1).astype(np.int32)
            buckets.append((sel.astype(np.int32), ell_idx, pos))
        lower, k = k, k * 2
    return buckets


def ell_layout_from_bounds(bounds: Sequence[Tuple[int, int, int]], *,
                           min_k: int = 4, block_rows: int = 8
                           ) -> List[Tuple[np.ndarray, int]]:
    """Static row ranges + degree bounds -> a fixed power-of-two K ladder.

    ``bounds`` is ``[(start, stop, max_degree), ...]`` (e.g. the sampler's
    static per-hop in-degree bounds). Each range is assigned the smallest
    ladder rung ``K = min_k * 2**j >= max_degree``; ranges sharing a rung
    merge into one bucket, and every bucket's row list is capacity-padded to
    a ``block_rows`` multiple with ``-1`` row ids. The result depends only
    on the *bounds* — never on realised degrees — so every packing against
    it has identical shapes (the jit-ready layout). Every rung is validated
    against the declared SMEM/VMEM budgets at layout time
    (:func:`repro.kernels.budgets.check_ell_layout`): an unservable K ladder
    raises :class:`repro.kernels.budgets.BudgetError` here, on the host,
    instead of OOMing a launch later.
    """
    by_k: dict = {}
    for lo, hi, bound in bounds:
        if hi <= lo or bound <= 0:
            continue
        k = min_k
        while k < bound:
            k *= 2
        by_k.setdefault(k, []).append(np.arange(lo, hi))
    layout = []
    for k in sorted(by_k):
        rows = np.concatenate(by_k[k]).astype(np.int32)
        pad = -(-len(rows) // block_rows) * block_rows - len(rows)
        if pad:
            rows = np.concatenate([rows, np.full(pad, -1, np.int32)])
        layout.append((rows, k))
    hw_budgets.check_ell_layout(layout, block_rows=block_rows,
                                context="ell_layout_from_bounds")
    return layout


def csr_to_ell_static(indptr: np.ndarray, indices: np.ndarray,
                      layout: Sequence[Tuple[np.ndarray, int]], *,
                      block_rows: int = 8) -> List[EllBucket]:
    """Pack a CSR/CSC into a *fixed* bucket layout (capacity-padded).

    The shape-stable variant of :func:`csr_to_ell_bucketed`: bucket row sets
    and K widths come from ``layout`` (see :func:`ell_layout_from_bounds`)
    instead of the realised degree distribution, so every call returns
    buckets of identical shapes — batches packed this way share one jit
    trace. ``-1`` row ids are capacity padding (all-invalid slots; the
    consumer masks them out of the scatter). A realised degree above its
    bucket's K means the static bound was violated and raises.
    """
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    deg_all = np.diff(indptr)
    # layouts may be hand-built (not via ell_layout_from_bounds): validate
    # against the declared budgets here too — pack time is the last host-
    # side moment before these shapes hit a launch.
    hw_budgets.check_ell_layout(layout, block_rows=block_rows,
                                context="csr_to_ell_static")
    buckets: List[EllBucket] = []
    for row_ids, k in layout:
        row_ids = np.asarray(row_ids, np.int32)
        valid = row_ids >= 0
        safe = np.where(valid, row_ids, 0)
        deg = np.where(valid, deg_all[safe], 0)
        over = int(deg.max(initial=0))
        if over > k:
            raise ValueError(
                f"static ELL layout violated: realised degree {over} exceeds "
                f"bucket capacity K={k}")
        starts = np.where(valid, indptr[safe], 0)
        pos = _ell_positions(starts, deg, k, block_rows)
        if len(pos) > len(row_ids):  # layout not block-padded: pad ids too
            row_ids = np.concatenate([row_ids, np.full(
                len(pos) - len(row_ids), -1, np.int32)])
        safe_pos = np.where(pos >= 0, pos, 0)
        ell_idx = np.where(pos >= 0, indices[safe_pos], -1).astype(np.int32)
        buckets.append((row_ids, ell_idx, pos))
    return buckets


# MAX_PREFETCH_ELEMS (re-exported above from kernels.budgets, the single
# source of truth) bounds the scalar-prefetched neighbor table per launch;
# rows chunk above it. It stays a module-level name here so tests can
# monkeypatch the chunk rule per ops module without touching the declared
# hardware budgets.


def _spmm_ell_pallas_chunked(ell_idx: jnp.ndarray,
                             ell_w: Optional[jnp.ndarray], x: jnp.ndarray,
                             reduce: str, interpret: bool) -> jnp.ndarray:
    """The raw Pallas forward, row-chunked to the SMEM prefetch budget.

    Calls the module-global ``spmm_ell_pallas`` (not a captured reference) so
    test spies that monkeypatch the ops attribute still observe every launch.
    """
    rows, k = ell_idx.shape
    from repro.kernels.spmm.spmm import DEFAULT_BR
    # Launch-time backstop against the *declared* hardware budgets (the
    # pack-time check covers loader layouts; ad-hoc tables land here).
    hw_budgets.check_ell_rung(k, block_rows=DEFAULT_BR,
                              context="spmm_ell launch")
    chunk = max(MAX_PREFETCH_ELEMS // max(k, 1), DEFAULT_BR)
    chunk -= chunk % DEFAULT_BR
    if rows <= chunk:
        return spmm_ell_pallas(ell_idx, ell_w, x, reduce=reduce,
                               interpret=interpret)
    outs = []
    for lo in range(0, rows, chunk):
        hi = min(lo + chunk, rows)
        outs.append(spmm_ell_pallas(
            ell_idx[lo:hi], None if ell_w is None else ell_w[lo:hi], x,
            reduce=reduce, interpret=interpret))
    return jnp.concatenate(outs, axis=0)


def _spmm_ell_backward(ell_idx: jnp.ndarray, ell_w: Optional[jnp.ndarray],
                       x: jnp.ndarray, out: Optional[jnp.ndarray],
                       dy: jnp.ndarray, reduce: str
                       ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """VJP of the blocked-ELL SpMM w.r.t. (features, weights).

    The feature cotangent is a masked scatter-add over the *same* ELL table
    the forward consumed: gather ``dy`` by row, accumulate each slot's
    contribution into its neighbor column (``-1`` capacity/padding slots are
    dropped out of the scatter). The weight cotangent is the per-slot
    ``dy[row] . x[col]`` reduction. ``mean`` pre-scales ``dy`` by the
    per-row valid count; ``max``/``min`` route ``dy`` to the arg-extreme
    slots (ties split evenly — the same convention as ``lax.reduce_max``'s
    gradient, so kernel and oracle gradients agree).
    """
    mask = ell_idx >= 0
    n = x.shape[0]
    dy32 = dy.astype(jnp.float32)
    xg = x[jnp.maximum(ell_idx, 0)].astype(jnp.float32)  # (R, K, F)
    if reduce in ("sum", "mean"):
        if reduce == "mean":
            cnt = jnp.maximum(mask.sum(axis=1), 1).astype(jnp.float32)
            dy32 = dy32 / cnt[:, None]
        g = jnp.where(mask[..., None], dy32[:, None, :], 0.0)  # (R, K, F)
    else:  # max / min: dy flows only to the slots that achieved the output
        contrib = xg if ell_w is None else xg * ell_w[..., None].astype(
            jnp.float32)
        hit = mask[..., None] & (contrib == out.astype(jnp.float32)[:, None])
        ties = jnp.maximum(hit.sum(axis=1, keepdims=True), 1).astype(
            jnp.float32)
        g = jnp.where(hit, dy32[:, None, :] / ties, 0.0)
    gx = g if ell_w is None else g * ell_w[..., None].astype(jnp.float32)
    scatter_rows = jnp.where(mask, ell_idx, n).reshape(-1)
    dx = jnp.zeros((n, x.shape[1]), jnp.float32).at[scatter_rows].add(
        gx.reshape(-1, x.shape[1]), mode="drop").astype(x.dtype)
    dw = None
    if ell_w is not None:
        dw = jnp.where(mask, (g * xg).sum(-1), 0.0).astype(ell_w.dtype)
    return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _spmm_ell_pallas_diff(reduce: str, interpret: bool, ell_idx, ell_w, x):
    """Differentiable wrapper over the Pallas ELL forward (the custom VJP
    the ROADMAP promised): Pallas runs the forward, the backward is the
    masked scatter-add of :func:`_spmm_ell_backward` over the same table."""
    return _spmm_ell_pallas_chunked(ell_idx, ell_w, x, reduce, interpret)


def _spmm_ell_diff_fwd(reduce, interpret, ell_idx, ell_w, x):
    out = _spmm_ell_pallas_chunked(ell_idx, ell_w, x, reduce, interpret)
    keep_out = out if reduce in ("max", "min") else None
    return out, (ell_idx, ell_w, x, keep_out)


def _spmm_ell_diff_bwd(reduce, interpret, residuals, dy):
    ell_idx, ell_w, x, out = residuals
    # The named scope tags these gather/scatter eqns as the *kernel's own
    # backward* so the dispatch auditor (analysis.dispatch) never mistakes
    # them for an oracle fallback when walking a grad step.
    with jax.named_scope("repro_kernel_vjp:spmm_ell"):
        dx, dw = _spmm_ell_backward(ell_idx, ell_w, x, out, dy, reduce)
    d_idx = np.zeros(ell_idx.shape, jax.dtypes.float0)  # int operand: no ct
    return d_idx, dw, dx


_spmm_ell_pallas_diff.defvjp(_spmm_ell_diff_fwd, _spmm_ell_diff_bwd)


def spmm_ell(ell_idx: jnp.ndarray, ell_w: Optional[jnp.ndarray],
             x: jnp.ndarray, *, reduce: str = "sum",
             force_pallas: Optional[bool] = None,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    """Blocked-ELL SpMM: Pallas kernel on TPU (or when forced), oracle else.

    ``interpret=None`` auto-selects interpret mode off-TPU so a forced Pallas
    path stays runnable (and testable) on CPU containers. Tables larger than
    ``MAX_PREFETCH_ELEMS`` are split along rows into multiple launches so the
    scalar-prefetched neighbor table always fits SMEM. The Pallas branch is
    differentiable: a custom VJP computes the feature cotangent as a masked
    scatter-add over the same ELL table and the weight cotangent as per-slot
    ``dy[row] . x[col]``, so ``jax.grad`` through a kernel-dispatched step
    works (training and explainers ride the fast path).
    """
    take_pallas = use_pallas() if force_pallas is None else force_pallas
    if not take_pallas:
        return ref.spmm_ell(ell_idx, ell_w, x, reduce=reduce)
    if interpret is None:
        interpret = interpret_default()
    return _spmm_ell_pallas_diff(reduce, bool(interpret), ell_idx, ell_w, x)


def spmm_ell_bucketed(buckets: Sequence[EllBucket], x: jnp.ndarray,
                      weight: Optional[jnp.ndarray] = None, *,
                      num_rows: int, reduce: str = "sum",
                      force_pallas: Optional[bool] = None,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Degree-bucketed blocked-ELL SpMM: one kernel launch per bucket.

    ``weight`` is per-edge in whatever order ``ell_pos`` is keyed to (the
    packers emit packed/CSR order; ``EdgeIndex`` re-keys its caches to COO
    order); each bucket gathers its slots' weights through ``ell_pos``.
    Differentiable end to end: the per-bucket kernel carries a custom VJP
    and the weight gather / output scatter are plain XLA ops, so gradients
    flow to both ``x`` and ``weight``.
    Rows absent from every bucket (degree 0) keep the 0 fill — identical to
    the oracle's empty-segment convention for every reduce mode. ``-1`` row
    ids (capacity padding from :func:`csr_to_ell_static`) are masked out of
    the scatter, so bucket arrays may be tracers (jit-argument batches).
    """
    out = jnp.zeros((num_rows,) + x.shape[1:], x.dtype)
    for row_ids, ell_idx, ell_pos in buckets:
        w_b = None
        if weight is not None:
            mask = ell_pos >= 0
            w_b = jnp.where(mask,
                            jnp.asarray(weight)[jnp.maximum(ell_pos, 0)],
                            0.0).astype(jnp.float32)
        res = spmm_ell(jnp.asarray(ell_idx), w_b, x, reduce=reduce,
                       force_pallas=force_pallas, interpret=interpret)
        ids = jnp.asarray(row_ids)
        # Padding ids scatter out of bounds and are dropped.
        ids = jnp.where(ids >= 0, ids, num_rows)
        out = out.at[ids].set(res[: ids.shape[0]].astype(x.dtype),
                              mode="drop")
    return out
