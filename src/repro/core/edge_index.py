"""EdgeIndex — the paper's C1 contribution (PyG 2.0 §2.2).

A COO edge tensor of shape ``(2, E)`` that carries *metadata* (sort order,
undirectedness, node counts) and demand-filled *caches* (CSR / CSC
conversions, i.e. the adjacency and its transpose). Message passing inspects
this metadata to pick the optimal compute path:

* sorted-by-row  -> fused CSR segment/SpMM forward path
* sorted-by-col  -> fused CSC path (transposed flow)
* cached CSC     -> cheap backward (no re-derivation of ``A^T`` per step)
* undirected     -> ``A == A^T``; a single cache serves both directions
* cached ELL     -> degree-bucketed blocked-ELL packing feeding the Pallas
  pipelined SpMM kernel on TPU (the demand-filled TPU fast path); the same
  buckets serve the fused flash-GAT attention aggregation (:meth:`attend`)

This mirrors ``torch_geometric.EdgeIndex`` semantics adapted to JAX: the
object is a registered pytree (arrays are leaves, metadata is static), so it
can flow through ``jit`` boundaries; caches are jnp arrays computed once and
reused across layers/steps — exactly the paper's "filled based on demand, and
maintained and adjusted over its lifespan".
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SortOrder = Optional[str]  # None | "row" | "col"


def _count_sorted(index: jnp.ndarray, n: int) -> jnp.ndarray:
    """ptr[i] = number of entries < i, for a sorted index vector (CSR rowptr)."""
    # searchsorted over the sorted index gives the compressed pointer directly.
    return jnp.searchsorted(index, jnp.arange(n + 1), side="left").astype(jnp.int32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EdgeIndex:
    """COO edge index with metadata + CSR/CSC caches.

    Attributes:
      data:          int32 array of shape (2, E): row 0 = source, row 1 = dest.
      num_src_nodes: number of source nodes (rows of A).
      num_dst_nodes: number of destination nodes (cols of A).
      sort_order:    None | "row" | "col" — which coordinate `data` is sorted by.
      is_undirected: if True, A == A^T and one cache serves both directions.
      _csr / _csc:   optional cached (indptr, indices, perm) triples.
      _ell / _ell_t: optional cached degree-bucketed blocked-ELL packings of
                     the CSC (forward) / CSR (transpose) adjacency — tuples of
                     (row_ids, ell_idx, ell_pos) buckets feeding the Pallas
                     pipelined SpMM kernel. ``ell_pos`` slots index the
                     *original COO edge order* (the order callers pass
                     ``edge_weight`` in), so weighted matmuls gather per-call
                     weights directly — and a layer-trimmed cache keeps
                     serving them, because kept slots reference kept (prefix)
                     edges only.
      _ell_ranges:   static per-bucket row ranges of a static-layout ``_ell``
                     — ``((lo, hi), ...)`` ascending runs of the bucket's
                     real rows, or ``None`` for a bucket whose rows do not
                     ascend — set from the layout by
                     :meth:`from_coo_prefilled`. Pytree aux: batches packed
                     against one layout share it, so they share a trace.
                     Layer-wise trimming slices each bucket to the rows it
                     keeps from these (``repro.core.trim``).
    """

    data: jnp.ndarray
    num_src_nodes: int
    num_dst_nodes: int
    sort_order: SortOrder = None
    is_undirected: bool = False
    _csr: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = None
    _csc: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = None
    _ell: Optional[Tuple] = None
    _ell_t: Optional[Tuple] = None
    _ell_ranges: Optional[Tuple] = None

    # ------------------------------------------------------------------ pytree
    def tree_flatten(self):
        children = (self.data, self._csr, self._csc, self._ell, self._ell_t)
        aux = (self.num_src_nodes, self.num_dst_nodes, self.sort_order,
               self.is_undirected, self._ell_ranges)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, csr, csc, ell, ell_t = children
        ns, nd, so, undirected, ell_ranges = aux
        return cls(data, ns, nd, so, undirected, csr, csc, ell, ell_t,
                   ell_ranges)

    # ------------------------------------------------------------- constructors
    @classmethod
    def from_coo(cls, src, dst, num_src_nodes=None, num_dst_nodes=None,
                 sort_order: SortOrder = None, is_undirected: bool = False):
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        if (num_src_nodes is None or num_dst_nodes is None) and (
                isinstance(src, jax.core.Tracer)
                or isinstance(dst, jax.core.Tracer)):
            raise ValueError(
                "EdgeIndex.from_coo: num_src_nodes/num_dst_nodes must be "
                "passed explicitly when the edge arrays are traced (inside "
                "jit/vmap/grad). Node counts are static shape metadata and "
                "cannot be derived from a tracer's values.")
        if num_src_nodes is None:
            num_src_nodes = int(src.max()) + 1 if src.size else 0
        if num_dst_nodes is None:
            num_dst_nodes = int(dst.max()) + 1 if dst.size else 0
        return cls(jnp.stack([src, dst]), int(num_src_nodes), int(num_dst_nodes),
                   sort_order, is_undirected)

    @classmethod
    def from_coo_prefilled(cls, src, dst, num_src_nodes: int,
                           num_dst_nodes: int, *, ell_layout=None,
                           block_rows: int = 8) -> "EdgeIndex":
        """Host-side construct-with-caches: the jit-ready producer path.

        Sorts the COO by destination (and by source) in NumPy, building the
        CSC/CSR caches *before* the object ever reaches a jit boundary —
        so a per-batch ``EdgeIndex`` passed as a jit argument carries its
        conversions as pytree leaves instead of re-deriving them in-trace.
        With ``ell_layout`` (see ``kernels.spmm.ops.ell_layout_from_bounds``)
        it additionally packs the static-layout blocked-ELL cache, whose
        shapes depend only on the layout: batches built against the same
        layout share one jit trace and dispatch to the Pallas kernel.

        ``data`` keeps the caller's edge order (the sampler's BFS hop
        grouping, which layer-wise trimming slices); the destination-sorted
        layout lives in the caches, each carrying its own permutation.
        """
        from repro.kernels.spmm import ops as spmm_ops  # local import: no cycle
        src = np.asarray(src, np.int32)
        dst = np.asarray(dst, np.int32)
        perm_c = np.argsort(dst, kind="stable").astype(np.int32)
        colptr = np.searchsorted(dst[perm_c], np.arange(
            num_dst_nodes + 1)).astype(np.int32)
        csc_idx = src[perm_c]
        perm_r = np.argsort(src, kind="stable").astype(np.int32)
        rowptr = np.searchsorted(src[perm_r], np.arange(
            num_src_nodes + 1)).astype(np.int32)
        csr_idx = dst[perm_r]
        ell = ell_ranges = None
        if ell_layout is not None:
            ell = cls._ell_pos_to_coo(
                spmm_ops.csr_to_ell_static(colptr, csc_idx, ell_layout,
                                           block_rows=block_rows), perm_c)
            ell_ranges = tuple(spmm_ops.ell_row_ranges(rows)
                               for rows, _ in ell_layout)
        return cls(
            jnp.asarray(np.stack([src, dst])), int(num_src_nodes),
            int(num_dst_nodes), None, False,
            _csr=(jnp.asarray(rowptr), jnp.asarray(csr_idx),
                  jnp.asarray(perm_r)),
            _csc=(jnp.asarray(colptr), jnp.asarray(csc_idx),
                  jnp.asarray(perm_c)),
            _ell=ell, _ell_ranges=ell_ranges)

    # ----------------------------------------------------------------- accessors
    @property
    def src(self) -> jnp.ndarray:
        return self.data[0]

    @property
    def dst(self) -> jnp.ndarray:
        return self.data[1]

    @property
    def num_edges(self) -> int:
        return int(self.data.shape[1])

    def sparse_size(self) -> Tuple[int, int]:
        return (self.num_src_nodes, self.num_dst_nodes)

    # ------------------------------------------------------------------- sorting
    def sort_by(self, order: str) -> Tuple["EdgeIndex", jnp.ndarray]:
        """Return a copy sorted by 'row' (src) or 'col' (dst) + the permutation."""
        assert order in ("row", "col")
        if self.sort_order == order:
            return self, jnp.arange(self.num_edges, dtype=jnp.int32)
        key = self.src if order == "row" else self.dst
        # Stable sort keeps deterministic tie order (matches numpy/PyG).
        perm = jnp.argsort(key, stable=True).astype(jnp.int32)
        out = EdgeIndex(self.data[:, perm], self.num_src_nodes,
                        self.num_dst_nodes, order, self.is_undirected)
        return out, perm

    # -------------------------------------------------------------------- caches
    @staticmethod
    def _memoizable(triple) -> bool:
        """Never memoise tracers: a cache filled inside a jit trace would
        leak the tracer into later traces (the mutable-cache + jit hazard).
        Inside jit the conversion is recomputed — XLA CSE's it anyway."""
        return not any(isinstance(a, jax.core.Tracer) for a in triple)

    def get_csr(self) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """(rowptr, col, perm): perm maps CSR edge slots -> original COO slots.

        Fills and memoises the cache on first call (the paper's demand-filled
        cache). For undirected graphs a CSC cache doubles as CSR.
        """
        if self._csr is not None:
            return self._csr
        if self.is_undirected and self._csc is not None:
            colptr, row, perm = self._csc
            self._csr = (colptr, row, perm)
            return self._csr
        if self.sort_order == "row":
            rowptr = _count_sorted(self.src, self.num_src_nodes)
            perm = jnp.arange(self.num_edges, dtype=jnp.int32)
            out = (rowptr, self.dst, perm)
        else:
            sorted_ei, perm = self.sort_by("row")
            rowptr = _count_sorted(sorted_ei.src, self.num_src_nodes)
            out = (rowptr, sorted_ei.dst, perm)
        if self._memoizable(out):
            self._csr = out
        return out

    def get_csc(self) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """(colptr, row, perm): the transposed adjacency — the backward cache."""
        if self._csc is not None:
            return self._csc
        if self.is_undirected and self._csr is not None:
            rowptr, col, perm = self._csr
            self._csc = (rowptr, col, perm)
            return self._csc
        if self.sort_order == "col":
            colptr = _count_sorted(self.dst, self.num_dst_nodes)
            perm = jnp.arange(self.num_edges, dtype=jnp.int32)
            out = (colptr, self.src, perm)
        else:
            sorted_ei, perm = self.sort_by("col")
            colptr = _count_sorted(sorted_ei.dst, self.num_dst_nodes)
            out = (colptr, sorted_ei.src, perm)
        if self._memoizable(out):
            self._csc = out
        return out

    @staticmethod
    def _ell_pos_to_coo(buckets, perm) -> Tuple:
        """Re-key bucket ``ell_pos`` slots from packed (CSR/CSC) order to the
        original COO edge order via the cache permutation, so per-call
        ``edge_weight`` vectors can be gathered without an extra perm gather
        — and so the positions stay valid after a layer trim (kept slots
        reference only kept, prefix edges)."""
        perm = np.asarray(perm)
        out = []
        for r, i, p in buckets:
            p = np.asarray(p)
            p_coo = np.where(p >= 0, perm[np.maximum(p, 0)],
                             -1).astype(np.int32)
            out.append((jnp.asarray(r), jnp.asarray(i), jnp.asarray(p_coo)))
        return tuple(out)

    def get_ell(self, transpose: bool = False) -> Optional[Tuple]:
        """Degree-bucketed blocked-ELL packing of A (or A^T) for the Pallas
        SpMM kernel: a tuple of ``(row_ids, ell_idx, ell_pos)`` buckets
        (see ``kernels.spmm.ops.csr_to_ell_bucketed``); ``ell_pos`` is
        re-keyed to COO edge order (see :meth:`_ell_pos_to_coo`).

        The packing needs concrete (host) arrays — called with tracers it
        returns ``None`` and the caller falls back to the XLA oracle; filled
        eagerly once, the cached buckets become jit constants afterwards.
        """
        from repro.kernels.spmm import ops as spmm_ops  # local import: no cycle
        if self.is_undirected and transpose:  # A == A^T: one packing serves
            transpose = False
        cache = self._ell_t if transpose else self._ell
        if cache is not None:
            return cache
        indptr, indices, perm = (self.get_csr() if transpose
                                 else self.get_csc())
        if not self._memoizable((indptr, indices, perm)):
            return None
        buckets = self._ell_pos_to_coo(
            spmm_ops.csr_to_ell_bucketed(np.asarray(indptr),
                                         np.asarray(indices)), perm)
        if transpose:
            self._ell_t = buckets
        else:
            self._ell = buckets
        return buckets

    def fill_cache(self, ell: Optional[bool] = None) -> "EdgeIndex":
        """Eagerly fill the caches (used before entering a jit'd loop).

        ``ell`` additionally packs the blocked-ELL buckets for the Pallas
        fast path; the default (``None``) packs them exactly when dispatch
        would select that path (TPU backend or ``REPRO_USE_PALLAS=1``), so
        the documented "fill_cache() before jit" pattern reaches the kernel
        without an extra opt-in.
        """
        from repro.kernels import use_pallas
        self.get_csr()
        if not self.is_undirected:
            self.get_csc()
        if use_pallas() if ell is None else ell:
            self.get_ell()
            self.get_ell(transpose=True)
        return self

    # --------------------------------------------------------------------- spmm
    def matmul(self, x: jnp.ndarray, edge_weight: Optional[jnp.ndarray] = None,
               transpose: bool = False, reduce: str = "sum",
               force_pallas: Optional[bool] = None,
               interpret: Optional[bool] = None) -> jnp.ndarray:
        """Sparse(A or A^T) @ dense(x) using the best available path.

        ``A[dst, src] = w`` convention: forward message passing aggregates
        source features into destinations, i.e. ``out = A @ x`` with A of
        shape (num_dst, num_src).

        Dispatch: on TPU (or ``force_pallas=True``) the degree-bucketed
        blocked-ELL packing feeds the pipelined Pallas kernel; otherwise —
        or when packing is impossible (tracing without a filled ELL cache) —
        the fused XLA segment oracle runs. Both branches are differentiable:
        the Pallas branch carries a custom VJP (backward = masked scatter-add
        over the same buckets, with a per-slot ``dy[row] . x[col]`` cotangent
        scattered back into ``edge_weight`` in slot order), so jit'd
        ``jax.grad`` train steps ride the fast path too.
        """
        from repro.kernels.spmm import ops as spmm_ops  # local import: no cycle
        from repro.kernels import use_pallas
        num_rows = self.num_src_nodes if transpose else self.num_dst_nodes
        take_pallas = use_pallas() if force_pallas is None else force_pallas
        if take_pallas:
            ell = self.get_ell(transpose=transpose)
            if ell is not None:
                # ``ell_pos`` is keyed to COO edge order — the caller's
                # ``edge_weight`` order — so the buckets gather it directly
                # (valid on layer-trimmed caches too: kept slots only
                # reference kept, prefix edges).
                return spmm_ops.spmm_ell_bucketed(
                    ell, x, edge_weight, num_rows=num_rows, reduce=reduce,
                    force_pallas=take_pallas, interpret=interpret)
        if not transpose:
            colptr, row, perm = self.get_csc()
            w = None if edge_weight is None else edge_weight[perm]
            return spmm_ops.spmm_csr(colptr, row, x, w,
                                     num_rows=self.num_dst_nodes, reduce=reduce)
        rowptr, col, perm = self.get_csr()
        w = None if edge_weight is None else edge_weight[perm]
        return spmm_ops.spmm_csr(rowptr, col, x, w,
                                 num_rows=self.num_src_nodes, reduce=reduce)

    # ------------------------------------------------------------------ attend
    def attend(self, z: jnp.ndarray, alpha_src: jnp.ndarray,
               alpha_dst: jnp.ndarray, *, negative_slope: float = 0.2,
               logit=None, prior: Optional[jnp.ndarray] = None,
               edge_weight: Optional[jnp.ndarray] = None,
               transpose: bool = False, return_attention: bool = False,
               return_carry: bool = False,
               force_pallas: Optional[bool] = None,
               interpret: Optional[bool] = None):
        """Attention-weighted aggregation over A (or A^T), typed logits.

        ``out[i] = sum_j softmax_j(logit(j, i)) * w_ij * z[j]`` with ``z``
        of shape (N, H, F) and the logit operands dense per-node arrays —
        ``alpha_src`` keyed by the *message sender* nodes (gathered through
        the neighbor table), ``alpha_dst`` by the receivers (the table's
        rows). For ``transpose=True`` the roles ride the CSR-derived
        transpose table, so the caller passes the halves already swapped
        into sender/receiver position.

        ``logit`` selects the per-relation transform: ``None`` (the default)
        or :class:`~repro.kernels.attention.ops.AdditiveLogit` is GAT's
        additive leaky-relu over (N, H) halves (``negative_slope`` only
        applies here, back-compat); :class:`DotLogit` is the scaled dot
        product over (N, H, D) halves with an optional per-head ``prior``
        (HGT's ``mu[rel]``). ``return_carry=True`` skips the softmax divide
        and returns the :class:`SoftmaxCarry` ``(m, l, acc)`` instead, so
        several relations' carries merge into one cross-type softmax
        (``merge_carries`` + ``finalize_carry``).

        Mirrors :meth:`matmul`'s dispatch tree: with a (loader-prefilled or
        demand-filled) ELL cache and Pallas dispatch on, the fused flash
        kernel runs one launch per bucket (differentiable via the ops-level
        custom VJP — no ``(E, H, F)`` edge-message materialisation);
        otherwise — CPU/GPU, or tracing without a packed cache — the COO
        segment oracle runs. ``edge_weight`` (COO order — the folded
        explainer mask) multiplies messages *after* the softmax, no
        renormalisation. ``return_attention`` additionally returns the
        per-edge (E, H) coefficients, recovered on the fused path by
        scattering the panel softmax through the COO-keyed ``ell_pos``.
        """
        from repro.kernels import use_pallas
        from repro.kernels.attention import ops as attn_ops
        from repro.kernels.attention import ref as attn_ref
        num_rows = self.num_src_nodes if transpose else self.num_dst_nodes
        take_pallas = use_pallas() if force_pallas is None else force_pallas
        additive = logit is None or isinstance(logit, attn_ops.AdditiveLogit)
        if additive and not return_carry:
            # GAT fast path — byte-identical to the pre-typed-logit code.
            if logit is not None:
                negative_slope = logit.negative_slope
            if take_pallas:
                ell = self.get_ell(transpose=transpose)
                if ell is not None:
                    out = attn_ops.gat_attend_ell(
                        ell, alpha_src, alpha_dst, z, edge_weight,
                        num_rows=num_rows, negative_slope=negative_slope,
                        force_pallas=take_pallas, interpret=interpret)
                    if not return_attention:
                        return out
                    alpha = attn_ops.gat_alpha_ell(
                        ell, alpha_src, alpha_dst,
                        num_edges=self.num_edges,
                        negative_slope=negative_slope)
                    return out, alpha
            # COO oracle: CPU/GPU dispatch, or tracing w/o a packed cache.
            send, recv = (self.dst, self.src) if transpose else (self.src,
                                                                 self.dst)
            out, alpha = attn_ref.gat_attend_coo(
                send, recv, alpha_src, alpha_dst, z, num_rows=num_rows,
                negative_slope=negative_slope, edge_weight=edge_weight)
            return (out, alpha) if return_attention else out
        # Typed / carry path.
        spec = attn_ops.AdditiveLogit(negative_slope) if logit is None \
            else logit
        carry = None
        if take_pallas:
            ell = self.get_ell(transpose=transpose)
            if ell is not None:
                carry = attn_ops.attn_carry_ell(
                    ell, alpha_src, alpha_dst, z, edge_weight,
                    num_rows=num_rows, logit=spec, prior=prior,
                    force_pallas=take_pallas, interpret=interpret)
        if carry is None:
            send, recv = (self.dst, self.src) if transpose else (self.src,
                                                                 self.dst)
            a_s = alpha_src[..., None] if alpha_src.ndim == 2 else alpha_src
            a_d = alpha_dst[..., None] if alpha_dst.ndim == 2 else alpha_dst
            m, lsum, acc = attn_ref.attn_carry_coo(
                send, recv, a_s, a_d, z, num_rows=num_rows,
                logit_kind=attn_ops._logit_kind(spec),
                negative_slope=attn_ops._logit_slope(spec),
                prior=attn_ops._effective_prior(spec, prior, z.shape[1])
                if attn_ops._logit_kind(spec) == "dot" else None,
                edge_weight=edge_weight)
            carry = attn_ops.SoftmaxCarry(m, lsum, acc)
        if return_carry:
            return carry
        out = attn_ops.finalize_carry(carry, z.dtype)
        if return_attention:
            alpha = self.attend_alpha(
                alpha_src, alpha_dst, logit=spec, prior=prior,
                m=carry.m, l=carry.l, transpose=transpose,
                force_pallas=force_pallas)
            return out, alpha
        return out

    def attend_alpha(self, alpha_src: jnp.ndarray, alpha_dst: jnp.ndarray,
                     *, logit, prior: Optional[jnp.ndarray] = None,
                     m: jnp.ndarray, l: jnp.ndarray,
                     transpose: bool = False,
                     force_pallas: Optional[bool] = None) -> jnp.ndarray:
        """Per-edge attention (E, H) of this relation against *merged*
        softmax statistics ``(m, l)`` (from :meth:`attend`'s carry /
        ``merge_carries``) — the typed ``return_attention`` round trip.
        With a packed ELL cache the panels scatter through the COO-keyed
        ``ell_pos``; otherwise the COO fallback materialises the logits.
        """
        from repro.kernels import use_pallas
        from repro.kernels.attention import ops as attn_ops
        from repro.kernels.attention import ref as attn_ref
        take_pallas = use_pallas() if force_pallas is None else force_pallas
        ell = self.get_ell(transpose=transpose) if take_pallas else None
        if ell is not None:
            return attn_ops.attn_alpha_ell(
                ell, alpha_src, alpha_dst, num_edges=self.num_edges,
                logit=logit, prior=prior, m=m, l=l)
        send, recv = (self.dst, self.src) if transpose else (self.src,
                                                             self.dst)
        a_s = alpha_src[..., None] if alpha_src.ndim == 2 else alpha_src
        a_d = alpha_dst[..., None] if alpha_dst.ndim == 2 else alpha_dst
        kind = attn_ops._logit_kind(logit)
        heads = m.shape[1]
        return attn_ref.attn_alpha_coo(
            send, recv, a_s, a_d, m=m, l=l, logit_kind=kind,
            negative_slope=attn_ops._logit_slope(logit),
            prior=attn_ops._effective_prior(logit, prior, heads)
            if kind == "dot" else None)

    # ------------------------------------------------------------------ utility
    def to_undirected(self) -> "EdgeIndex":
        src = jnp.concatenate([self.src, self.dst])
        dst = jnp.concatenate([self.dst, self.src])
        n = max(self.num_src_nodes, self.num_dst_nodes)
        return EdgeIndex(jnp.stack([src, dst]), n, n, None, True)

    def validate(self) -> "EdgeIndex":
        """Host-side sanity check (not for use inside jit)."""
        d = np.asarray(self.data)
        if d.size:
            assert d.min() >= 0, "negative node index"
            assert d[0].max() < self.num_src_nodes, "src index out of range"
            assert d[1].max() < self.num_dst_nodes, "dst index out of range"
        if self.sort_order == "row":
            assert bool(np.all(np.diff(d[0]) >= 0)), "not sorted by row"
        if self.sort_order == "col":
            assert bool(np.all(np.diff(d[1]) >= 0)), "not sorted by col"
        return self


def coalesce(edge_index: EdgeIndex) -> EdgeIndex:
    """Remove duplicate edges (host-side helper, mirrors PyG coalesce)."""
    d = np.asarray(edge_index.data)
    key = d[0].astype(np.int64) * edge_index.num_dst_nodes + d[1]
    _, idx = np.unique(key, return_index=True)
    idx.sort()
    return EdgeIndex(jnp.asarray(d[:, idx]), edge_index.num_src_nodes,
                     edge_index.num_dst_nodes, None, edge_index.is_undirected)
