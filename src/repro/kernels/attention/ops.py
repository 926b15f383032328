"""Public fused-attention entry points with kernel/oracle dispatch.

The attention analogue of ``kernels.spmm.ops``: the same bucketed
blocked-ELL layout (``EllBucket`` triples from the SpMM packers, ``ell_pos``
keyed to COO edge order) drives a *fused* attention aggregation

    out[r, h] = sum_k softmax_k(logit(nbr, r))_k * w[r, k] * z[nbr, h]

per bucket: the Pallas flash kernel on TPU (or when forced), the panel
oracle elsewhere. Two families share the kernel body:

  * ``gat_attend_ell`` / ``gat_alpha_ell`` — GAT's additive leaky-relu
    logit, normalised per relation (unchanged public contract);
  * ``attn_carry_ell`` + ``merge_carries`` + ``finalize_carry`` — the typed
    path: a per-relation logit spec (:class:`AdditiveLogit` /
    :class:`DotLogit` with a per-head ``prior``) and an *unfinalised*
    :class:`SoftmaxCarry` ``(m, l, acc)`` out, so several relation launches
    into the same destination rows merge into ONE cross-type softmax.

The Pallas branches are differentiable at this level — ops-level
``jax.custom_vjp``s recompute the softmax over the same panels and run
their backward in XLA, exactly the PR-4 pattern for SpMM. The raw kernel
entry points stay forward-only behind the shared ``forward_only_pallas``
guard. Stabilizer convention: ``m`` (and the merged max) are
``stop_gradient`` constants — the finalized output is shift-invariant in
them, so the gradient is exact.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import budgets as hw_budgets, interpret_default, use_pallas
from repro.kernels.attention import ref
from repro.kernels.attention.gat_attention import (DEFAULT_BR,
                                                   attn_ell_pallas,
                                                   gat_ell_pallas)
# MAX_PREFETCH_ELEMS comes from the shared budget source of truth (a
# module-level name here so tests can monkeypatch this ops module's chunk
# rule independently of the SpMM one).
from repro.kernels.budgets import MAX_PREFETCH_ELEMS
from repro.kernels.spmm.ops import EllBucket


# ------------------------------------------------------------- logit specs
class AdditiveLogit(NamedTuple):
    """GAT's additive logit: ``leaky_relu(a_src[nbr] + a_dst[row])``.

    Per-head logit width ``LD == 1``; no prior.
    """
    negative_slope: float = 0.2


class DotLogit(NamedTuple):
    """HGT's dot logit: ``<k[nbr, h], q[row, h]> * scale * prior[h]``.

    Per-head logit width ``LD == head_dim``; ``scale`` (typically
    ``1/sqrt(D)``) is folded into the per-head ``prior`` row at launch.
    """
    scale: float = 1.0


LogitSpec = Union[AdditiveLogit, DotLogit]


class SoftmaxCarry(NamedTuple):
    """Running online-softmax state of one (or several merged) launches.

    ``m`` (R, H) masked logit max (-inf on rows with no valid neighbor;
    treated as a stop-gradient constant), ``l`` (R, H) exp-sum against
    ``m``, ``acc`` (R, H, F) the unnormalised weighted accumulator.
    ``finalize_carry`` turns it into the attention output; carries of
    different relations over the same rows combine via ``merge_carries``
    into one cross-type softmax.
    """
    m: jnp.ndarray
    l: jnp.ndarray
    acc: jnp.ndarray


def _logit_kind(logit: LogitSpec) -> str:
    return "add" if isinstance(logit, AdditiveLogit) else "dot"


def _logit_slope(logit: LogitSpec) -> float:
    return logit.negative_slope if isinstance(logit, AdditiveLogit) else 0.0


def _effective_prior(logit: LogitSpec, prior: Optional[jnp.ndarray],
                     heads: int) -> jnp.ndarray:
    """Fold the dot-logit scale into one (H,) f32 prior row.

    The additive logit has no prior semantics — the kernel carries (and
    ignores) a row of ones so the carry launch signature stays static.
    """
    base = (jnp.ones((heads,), jnp.float32) if prior is None
            else jnp.asarray(prior, jnp.float32))
    if isinstance(logit, DotLogit) and logit.scale != 1.0:
        base = base * jnp.float32(logit.scale)
    return base


def _gat_ell_pallas_chunked(ell_idx: jnp.ndarray, adst: jnp.ndarray,
                            ell_w: Optional[jnp.ndarray],
                            alpha_src: jnp.ndarray, z: jnp.ndarray,
                            negative_slope: float,
                            interpret: bool) -> jnp.ndarray:
    """The raw Pallas forward, row-chunked to the SMEM prefetch budget.

    Calls the module-global ``gat_ell_pallas`` (not a captured reference) so
    test spies that monkeypatch the ops attribute still observe every
    launch. Returns (R, H, F).
    """
    rows, k = ell_idx.shape
    heads, feat = z.shape[1], z.shape[2]
    z2d = z.reshape(z.shape[0], heads * feat)
    # Launch-time backstop against the *declared* hardware budgets (the
    # pack-time check covers loader layouts; ad-hoc buckets land here).
    hw_budgets.check_gat_bucket(rows, k, heads, feat,
                                weighted=ell_w is not None)
    chunk = max(MAX_PREFETCH_ELEMS // max(k, 1), DEFAULT_BR)
    chunk -= chunk % DEFAULT_BR
    if rows <= chunk:
        out = gat_ell_pallas(ell_idx, adst, ell_w, alpha_src, z2d,
                             negative_slope=negative_slope,
                             interpret=interpret)
        return out.reshape(rows, heads, feat)
    outs = []
    for lo in range(0, rows, chunk):
        hi = min(lo + chunk, rows)
        outs.append(gat_ell_pallas(
            ell_idx[lo:hi], adst[lo:hi],
            None if ell_w is None else ell_w[lo:hi], alpha_src, z2d,
            negative_slope=negative_slope, interpret=interpret))
    return jnp.concatenate(outs, axis=0).reshape(rows, heads, feat)


def _gat_panels_backward(ell_idx, adst, ell_w, alpha_src, z, dy,
                         negative_slope: float):
    """VJP of the fused attention w.r.t. (adst, ell_w, alpha_src, z).

    Recomputes the masked softmax over the *same* panels the forward
    consumed (cheap — (R, K, H)), then chains the softmax backward, the
    leaky-relu backward, and two masked scatter-adds back into the dense
    per-node operands. ``dy`` is (R, H, F).
    """
    mask = ell_idx >= 0
    safe = jnp.maximum(ell_idx, 0)
    a32 = alpha_src.astype(jnp.float32)
    raw = a32[safe] + adst.astype(jnp.float32)[:, None, :]    # (R, K, H)
    p = ref.gat_softmax_panels(ell_idx, adst, alpha_src,
                               negative_slope=negative_slope)
    p = p.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    zg = z[safe].astype(jnp.float32)                          # (R, K, H, F)
    dyz = jnp.einsum("rhf,rkhf->rkh", dy32, zg)               # dL/d(p*w)
    w32 = None if ell_w is None else ell_w.astype(jnp.float32)
    dp = dyz if w32 is None else dyz * w32[..., None]
    # masked-softmax backward over the K axis
    ds = p * (dp - (p * dp).sum(axis=1, keepdims=True))
    dlogit = ds * jnp.where(raw >= 0, 1.0, negative_slope)
    dlogit = jnp.where(mask[..., None], dlogit, 0.0)
    d_adst = dlogit.sum(axis=1).astype(adst.dtype)            # (R, H)
    n = alpha_src.shape[0]
    scatter_rows = jnp.where(mask, ell_idx, n).reshape(-1)
    d_asrc = jnp.zeros(alpha_src.shape, jnp.float32).at[scatter_rows].add(
        dlogit.reshape(-1, dlogit.shape[-1]), mode="drop").astype(
        alpha_src.dtype)
    pw = p if w32 is None else p * w32[..., None]
    contrib = jnp.einsum("rkh,rhf->rkhf", pw, dy32)
    d_z = jnp.zeros(z.shape, jnp.float32).at[scatter_rows].add(
        contrib.reshape(-1, z.shape[1], z.shape[2]), mode="drop").astype(
        z.dtype)
    d_w = None
    if ell_w is not None:
        d_w = jnp.where(mask, (p * dyz).sum(-1), 0.0).astype(ell_w.dtype)
    return d_adst, d_w, d_asrc, d_z


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _gat_ell_pallas_diff(negative_slope, interpret, ell_idx, adst, ell_w,
                         alpha_src, z):
    """Differentiable wrapper over the Pallas flash-GAT forward: Pallas runs
    the fused forward, the backward is the panel softmax VJP of
    :func:`_gat_panels_backward` over the same table."""
    return _gat_ell_pallas_chunked(ell_idx, adst, ell_w, alpha_src, z,
                                   negative_slope, interpret)


def _gat_ell_diff_fwd(negative_slope, interpret, ell_idx, adst, ell_w,
                      alpha_src, z):
    out = _gat_ell_pallas_chunked(ell_idx, adst, ell_w, alpha_src, z,
                                  negative_slope, interpret)
    return out, (ell_idx, adst, ell_w, alpha_src, z)


def _gat_ell_diff_bwd(negative_slope, interpret, residuals, dy):
    ell_idx, adst, ell_w, alpha_src, z = residuals
    # Tag the recompute + scatter-adds as the kernel's own backward so the
    # dispatch auditor never reads them as an oracle fallback in grad steps.
    with jax.named_scope("repro_kernel_vjp:gat_ell"):
        d_adst, d_w, d_asrc, d_z = _gat_panels_backward(
            ell_idx, adst, ell_w, alpha_src, z, dy, negative_slope)
    d_idx = np.zeros(ell_idx.shape, jax.dtypes.float0)  # int operand: no ct
    return d_idx, d_adst, d_w, d_asrc, d_z


_gat_ell_pallas_diff.defvjp(_gat_ell_diff_fwd, _gat_ell_diff_bwd)


def _bucket_gather(row_ids: jnp.ndarray, table: jnp.ndarray,
                   rows_pad: int) -> jnp.ndarray:
    """Gather a per-row table (any trailing shape) per bucket row; padding
    rows (-1 ids, capacity fill) get zeros — their slots are all-invalid,
    so the value never contributes."""
    ids = jnp.asarray(row_ids)
    vals = table[jnp.maximum(ids, 0)]
    mask = (ids >= 0).reshape((-1,) + (1,) * (vals.ndim - 1))
    vals = jnp.where(mask, vals, 0.0)
    if rows_pad > vals.shape[0]:
        pad = jnp.zeros((rows_pad - vals.shape[0],) + vals.shape[1:],
                        vals.dtype)
        vals = jnp.concatenate([vals, pad], axis=0)
    return vals


def _bucket_adst(row_ids: jnp.ndarray, alpha_dst: jnp.ndarray,
                 rows_pad: int) -> jnp.ndarray:
    """Gather the (R, H) receiver term per bucket row (GAT layout)."""
    return _bucket_gather(row_ids, alpha_dst, rows_pad)


def _bucket_ell_w(ell_pos, edge_weight) -> Optional[jnp.ndarray]:
    """Per-slot post-softmax weights gathered through COO-keyed ell_pos."""
    if edge_weight is None:
        return None
    pos = jnp.asarray(ell_pos)
    return jnp.where(pos >= 0,
                     jnp.asarray(edge_weight)[jnp.maximum(pos, 0)],
                     0.0).astype(jnp.float32)


# ------------------------------------------------------ typed carry launch
def _attn_ell_pallas_chunked(ell_idx, adst, ell_w, prior, alpha_src, z,
                             logit_kind: str, negative_slope: float,
                             interpret: bool):
    """The raw typed-carry Pallas forward, row-chunked to the SMEM budget.

    ``adst``/``alpha_src`` arrive natural-shaped — (R, H, LD) / (N, H, LD)
    — and are head-flattened here for the kernel. Calls the module-global
    ``attn_ell_pallas`` so test spies observe every launch. Returns the
    ``(m, l, acc)`` triple with ``acc`` reshaped to (R, H, F).
    """
    rows, k = ell_idx.shape
    heads, ld = alpha_src.shape[1], alpha_src.shape[2]
    feat = z.shape[2]
    a2d = alpha_src.reshape(alpha_src.shape[0], heads * ld)
    adst2d = adst.reshape(adst.shape[0], heads * ld)
    z2d = z.reshape(z.shape[0], heads * feat)
    prior2d = jnp.asarray(prior, jnp.float32).reshape(1, heads)
    # Launch-time backstop against the *declared* hardware budgets, over
    # the full typed shape (prior row + carry buffers included).
    hw_budgets.check_attn_bucket(rows, k, heads, feat, logit_dim=ld,
                                 weighted=ell_w is not None, carry=True)
    chunk = max(MAX_PREFETCH_ELEMS // max(k, 1), DEFAULT_BR)
    chunk -= chunk % DEFAULT_BR
    if rows <= chunk:
        acc, m, l = attn_ell_pallas(ell_idx, adst2d, ell_w, prior2d, a2d,
                                    z2d, logit_kind=logit_kind,
                                    negative_slope=negative_slope,
                                    interpret=interpret)
        return m, l, acc.reshape(rows, heads, feat)
    ms, ls, accs = [], [], []
    for lo in range(0, rows, chunk):
        hi = min(lo + chunk, rows)
        acc, m, l = attn_ell_pallas(
            ell_idx[lo:hi], adst2d[lo:hi],
            None if ell_w is None else ell_w[lo:hi], prior2d, a2d, z2d,
            logit_kind=logit_kind, negative_slope=negative_slope,
            interpret=interpret)
        ms.append(m)
        ls.append(l)
        accs.append(acc)
    return (jnp.concatenate(ms, axis=0), jnp.concatenate(ls, axis=0),
            jnp.concatenate(accs, axis=0).reshape(rows, heads, feat))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _attn_ell_carry_diff(logit_kind, negative_slope, interpret, ell_idx,
                         adst, ell_w, prior, alpha_src, z):
    """Differentiable wrapper over the typed-carry Pallas forward: Pallas
    runs the fused forward, the backward is ``jax.vjp`` of the panel carry
    reference over the same table (the merged-carry form: cotangents arrive
    for ``(m, l, acc)``, with the stop-gradded ``m`` contributing zero)."""
    return _attn_ell_pallas_chunked(ell_idx, adst, ell_w, prior, alpha_src,
                                    z, logit_kind, negative_slope, interpret)


def _attn_ell_carry_fwd(logit_kind, negative_slope, interpret, ell_idx,
                        adst, ell_w, prior, alpha_src, z):
    out = _attn_ell_pallas_chunked(ell_idx, adst, ell_w, prior, alpha_src,
                                   z, logit_kind, negative_slope, interpret)
    return out, (ell_idx, adst, ell_w, prior, alpha_src, z)


def _attn_ell_carry_bwd(logit_kind, negative_slope, interpret, residuals,
                        cts):
    ell_idx, adst, ell_w, prior, alpha_src, z = residuals
    have_w = ell_w is not None
    # Tag the recompute as the kernel's own backward so the dispatch
    # auditor never reads it as an oracle fallback in grad steps.
    with jax.named_scope("repro_kernel_vjp:attn_ell"):
        if have_w:
            def f(adst_, w_, prior_, asrc_, z_):
                return ref.attn_carry_panels(
                    ell_idx, adst_, w_, asrc_, z_, logit_kind=logit_kind,
                    negative_slope=negative_slope, prior=prior_)
            _, vjp = jax.vjp(f, adst, ell_w, prior, alpha_src, z)
            d_adst, d_w, d_prior, d_asrc, d_z = vjp(cts)
        else:
            def f(adst_, prior_, asrc_, z_):
                return ref.attn_carry_panels(
                    ell_idx, adst_, None, asrc_, z_, logit_kind=logit_kind,
                    negative_slope=negative_slope, prior=prior_)
            _, vjp = jax.vjp(f, adst, prior, alpha_src, z)
            d_adst, d_prior, d_asrc, d_z = vjp(cts)
            d_w = None
    d_idx = np.zeros(ell_idx.shape, jax.dtypes.float0)  # int operand: no ct
    return d_idx, d_adst, d_w, d_prior, d_asrc, d_z


_attn_ell_carry_diff.defvjp(_attn_ell_carry_fwd, _attn_ell_carry_bwd)


def attn_carry_ell(buckets: Sequence[EllBucket], alpha_src: jnp.ndarray,
                   alpha_dst: jnp.ndarray, z: jnp.ndarray,
                   edge_weight: Optional[jnp.ndarray] = None, *,
                   num_rows: int, logit: LogitSpec,
                   prior: Optional[jnp.ndarray] = None,
                   force_pallas: Optional[bool] = None,
                   interpret: Optional[bool] = None) -> SoftmaxCarry:
    """Bucketed typed-attention carry: one kernel launch per bucket.

    The typed generalisation of :func:`gat_attend_ell` that stops *before*
    the softmax divide: ``z`` is (N, H, F), ``alpha_src`` / ``alpha_dst``
    the (N_src, H, LD) / (N_dst, H, LD) logit operands (2-D inputs get an
    implicit LD=1 axis), ``logit`` the per-relation transform and ``prior``
    its optional per-head scale (``mu[rel]``; ``DotLogit.scale`` is folded
    in). Returns the dense-row :class:`SoftmaxCarry` — merge carries of
    other relations into the same rows with :func:`merge_carries`, then
    :func:`finalize_carry`. Differentiable end to end (the per-bucket
    kernel carries a custom VJP in the merged-carry form).
    """
    take_pallas = use_pallas() if force_pallas is None else force_pallas
    if alpha_src.ndim == 2:
        alpha_src = alpha_src[..., None]
    if alpha_dst.ndim == 2:
        alpha_dst = alpha_dst[..., None]
    heads, feat = z.shape[1], z.shape[2]
    kind = _logit_kind(logit)
    slope = _logit_slope(logit)
    prior_eff = _effective_prior(logit, prior, heads)
    m = jnp.full((num_rows, heads), -jnp.inf, jnp.float32)
    l = jnp.zeros((num_rows, heads), jnp.float32)
    acc = jnp.zeros((num_rows, heads, feat), jnp.float32)
    for row_ids, ell_idx, ell_pos in buckets:
        ell_idx = jnp.asarray(ell_idx)
        adst = _bucket_gather(row_ids, alpha_dst, ell_idx.shape[0])
        w_b = _bucket_ell_w(ell_pos, edge_weight)
        if take_pallas:
            itp = interpret_default() if interpret is None else interpret
            mb, lb, accb = _attn_ell_carry_diff(
                kind, float(slope), bool(itp), ell_idx, adst, w_b,
                prior_eff, alpha_src, z)
        else:
            mb, lb, accb = ref.attn_carry_panels(
                ell_idx, adst, w_b, alpha_src, z, logit_kind=kind,
                negative_slope=slope,
                prior=prior_eff if kind == "dot" else None)
        ids = jnp.asarray(row_ids)
        # Padding ids scatter out of bounds and are dropped.
        ids = jnp.where(ids >= 0, ids, num_rows)
        n_ids = ids.shape[0]
        m = m.at[ids].set(mb[:n_ids], mode="drop")
        l = l.at[ids].set(lb[:n_ids], mode="drop")
        acc = acc.at[ids].set(accb[:n_ids], mode="drop")
    return SoftmaxCarry(m, l, acc)


def merge_carries(carries: Sequence[SoftmaxCarry]) -> SoftmaxCarry:
    """Combine per-relation carries over the same rows into one softmax.

    ``M = max_r m_r``; ``l = sum_r l_r * exp(m_r - M)``; ``acc = sum_r
    acc_r * exp(m_r - M)`` — after ``finalize_carry`` this equals the
    softmax over the union of all relations' edges (the cross-type
    softmax). All stabilizers are stop-gradient constants: the finalized
    output is shift-invariant in them, so the merged custom-VJP gradient
    stays exact.
    """
    carries = list(carries)
    if len(carries) == 1:
        return carries[0]
    stabs = [jax.lax.stop_gradient(c.m) for c in carries]
    m = functools.reduce(jnp.maximum, stabs)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    l = jnp.zeros_like(carries[0].l)
    acc = jnp.zeros_like(carries[0].acc)
    for c, mr in zip(carries, stabs):
        scale = jnp.exp(mr - m_safe)  # exp(-inf) = 0: empty relation rows
        l = l + c.l * scale
        acc = acc + c.acc * scale[..., None]
    return SoftmaxCarry(m, l, acc)


def finalize_carry(carry: SoftmaxCarry,
                   dtype: Optional[jnp.dtype] = None) -> jnp.ndarray:
    """Normalise a (merged) carry: ``acc / max(l, 1e-16)`` — rows that saw
    no valid neighbor in any relation keep the 0 fill (the kernel's
    empty-segment convention)."""
    out = carry.acc / jnp.maximum(carry.l, 1e-16)[..., None]
    return out if dtype is None else out.astype(dtype)


def gat_attend_ell(buckets: Sequence[EllBucket], alpha_src: jnp.ndarray,
                   alpha_dst: jnp.ndarray, z: jnp.ndarray,
                   edge_weight: Optional[jnp.ndarray] = None, *,
                   num_rows: int, negative_slope: float = 0.2,
                   force_pallas: Optional[bool] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Bucketed fused GAT aggregation: one kernel launch per bucket.

    ``z`` is (N, H, F) per-head projected features, ``alpha_src`` /
    ``alpha_dst`` the dense (N_src, H) / (N_dst, H) logit halves.
    ``edge_weight`` (the folded explainer mask / per-edge weight) is per
    edge in COO order — each bucket gathers its slots' weights through
    ``ell_pos`` and applies them *after* the softmax (no renormalisation,
    matching the materialised path). Differentiable end to end: the
    per-bucket kernel carries a custom VJP and the gathers/scatters are
    plain XLA ops, so gradients flow to ``alpha_src``, ``alpha_dst``,
    ``z`` and ``edge_weight``. Rows absent from every bucket (degree 0)
    keep the 0 fill; ``-1`` row ids (capacity padding) are masked out of
    the scatter, so bucket arrays may be tracers (jit-argument batches).
    Returns (num_rows, H, F).
    """
    take_pallas = use_pallas() if force_pallas is None else force_pallas
    heads, feat = z.shape[1], z.shape[2]
    out = jnp.zeros((num_rows, heads, feat), z.dtype)
    for row_ids, ell_idx, ell_pos in buckets:
        ell_idx = jnp.asarray(ell_idx)
        adst = _bucket_adst(row_ids, alpha_dst, ell_idx.shape[0])
        w_b = None
        if edge_weight is not None:
            pos = jnp.asarray(ell_pos)
            w_b = jnp.where(pos >= 0,
                            jnp.asarray(edge_weight)[jnp.maximum(pos, 0)],
                            0.0).astype(jnp.float32)
        if take_pallas:
            itp = interpret_default() if interpret is None else interpret
            res = _gat_ell_pallas_diff(float(negative_slope), bool(itp),
                                       ell_idx, adst, w_b, alpha_src, z)
        else:
            res = ref.gat_attend_panels(ell_idx, adst, w_b, alpha_src, z,
                                        negative_slope=negative_slope)
        ids = jnp.asarray(row_ids)
        # Padding ids scatter out of bounds and are dropped.
        ids = jnp.where(ids >= 0, ids, num_rows)
        out = out.at[ids].set(res[: ids.shape[0]].astype(z.dtype),
                              mode="drop")
    return out


def gat_alpha_ell(buckets: Sequence[EllBucket], alpha_src: jnp.ndarray,
                  alpha_dst: jnp.ndarray, *, num_edges: int,
                  negative_slope: float = 0.2) -> jnp.ndarray:
    """Recover per-edge attention coefficients (E, H) from the ELL panels.

    The panels' softmax probabilities are scattered back to COO edge order
    through the COO-keyed ``ell_pos`` — the ``return_attention`` round trip.
    Pure XLA (the (E, H) result is inherently edge-level); padding slots
    scatter out of bounds and drop.
    """
    heads = alpha_src.shape[1]
    alpha = jnp.zeros((num_edges, heads), jnp.float32)
    for row_ids, ell_idx, ell_pos in buckets:
        ell_idx = jnp.asarray(ell_idx)
        adst = _bucket_adst(row_ids, alpha_dst, ell_idx.shape[0])
        p = ref.gat_softmax_panels(ell_idx, adst, alpha_src,
                                   negative_slope=negative_slope)
        pos = jnp.asarray(ell_pos)
        pos = jnp.where(pos >= 0, pos, num_edges).reshape(-1)
        alpha = alpha.at[pos].set(
            p.reshape(-1, heads).astype(jnp.float32), mode="drop")
    return alpha


def attn_alpha_ell(buckets: Sequence[EllBucket], alpha_src: jnp.ndarray,
                   alpha_dst: jnp.ndarray, *, num_edges: int,
                   logit: LogitSpec, prior: Optional[jnp.ndarray] = None,
                   m: jnp.ndarray, l: jnp.ndarray) -> jnp.ndarray:
    """Per-edge attention (E, H) against *merged* softmax statistics.

    The typed ``return_attention`` round trip: ``m`` / ``l`` are the
    (num_rows, H) carry stats after :func:`merge_carries`, so the returned
    coefficients of every relation into a destination row jointly sum to 1
    (cross-type softmax). Per-slot probabilities are scattered back to COO
    edge order through the COO-keyed ``ell_pos``; pure XLA.
    """
    if alpha_src.ndim == 2:
        alpha_src = alpha_src[..., None]
    if alpha_dst.ndim == 2:
        alpha_dst = alpha_dst[..., None]
    heads = m.shape[1]
    kind = _logit_kind(logit)
    slope = _logit_slope(logit)
    prior_eff = _effective_prior(logit, prior, heads) if kind == "dot" \
        else None
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    l_safe = jnp.maximum(l, 1e-16)
    alpha = jnp.zeros((num_edges, heads), jnp.float32)
    for row_ids, ell_idx, ell_pos in buckets:
        ell_idx = jnp.asarray(ell_idx)
        rows_pad = ell_idx.shape[0]
        adst = _bucket_gather(row_ids, alpha_dst, rows_pad)
        logits, mask = ref.attn_logit_panels(
            ell_idx, adst, alpha_src, logit_kind=kind,
            negative_slope=slope, prior=prior_eff)
        mrow = _bucket_gather(row_ids, m_safe, rows_pad)    # (R, H)
        lrow = jnp.maximum(_bucket_gather(row_ids, l_safe, rows_pad), 1e-16)
        p = jnp.where(mask[..., None],
                      jnp.exp(logits - mrow[:, None, :]) / lrow[:, None, :],
                      0.0)
        pos = jnp.asarray(ell_pos)
        pos = jnp.where(pos >= 0, pos, num_edges).reshape(-1)
        alpha = alpha.at[pos].set(
            p.reshape(-1, heads).astype(jnp.float32), mode="drop")
    return alpha
