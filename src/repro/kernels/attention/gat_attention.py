"""Fused typed-attention aggregation — blocked-ELL Pallas TPU kernel.

ONE kernel body (``_attn_ell_kernel``) fuses the whole attention
aggregation of a graph-attention layer over the same bucketed blocked-ELL
layout the SpMM kernel consumes:

    gather sender term[nbr] -> per-relation logit -> masked row softmax
      -> weighted accumulate of z[nbr]

in a single VMEM pass per row block (flash style): the softmax runs
*online* — a running max / running sum rescale the feature accumulator as
neighbor columns stream in — so the ``(E, H, F)`` edge-message tensor of the
materialised path is never built. Per neighbor column the kernel issues two
batches of async HBM->VMEM copies (the ``(1, F)`` feature row and the
``(1, H*LD)`` sender-term row of each neighbor), double-buffered exactly
like the SpMM kernel's pipelined gather, with the scalar-prefetched neighbor
table as the DMA address stream.

The logit transform is a static template parameter (``logit_kind``):

  * ``"add"`` — GAT's additive leaky-relu logit, ``LD = 1``
    (``leaky(alpha_src[nbr] + alpha_dst[row])``);
  * ``"dot"`` — HGT's scaled dot product, ``LD = head_dim``
    (``sum_d k[nbr, h, d] * q[row, h, d] * prior[h]`` — the relation prior
    ``mu[rel]/sqrt(D)`` enters as a ``(1, H)`` VMEM row).

``return_carry=True`` additionally emits the running softmax carry
``(m, l)`` next to the *unnormalised* accumulator, so several per-relation
launches targeting the same destination rows can be merged ops-side into
one cross-relation softmax (see ``kernels/attention/__init__.py`` for the
merge convention).

Layout: ``z`` arrives flattened to ``(N, H*F)`` so the head axis rides the
feature grid dimension (the per-head feature slice starts at ``h * F``) and
the DMA indexing stays 2-D. The receiver term is pre-gathered per bucket
row host/XLA-side (it is keyed by *row ids*, not by the neighbor table) and
enters as a dense ``(R, H*LD)`` VMEM panel.

Grid: ``(num_row_blocks, heads, num_feat_blocks)``; each (row, head, feat)
tile recomputes the cheap ``(BR, K)`` online softmax and is written once
(the tiny ``(BR, 1)`` carry blocks are revisited across feat tiles with
identical values).

``_gat_ell_kernel`` is a named delegator to the same body: Pallas reports
the kernel *function name* in the jaxpr, and the dispatch auditor / cost
table key on it — additive launches keep auditing as ``_gat_ell_kernel``,
typed carry launches as ``_attn_ell_kernel``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.budgets import DEFAULT_BF, DEFAULT_BR, lane_tiles
from repro.kernels.spmm.spmm import gather_table, lane_pick, pad_lanes
_NUM_SLOTS = 2  # double buffering


def _attn_ell_kernel(idx_sref, idx_ref, adst_ref, w_ref, *rest,
                     block_rows: int, block_feat: int, k: int, heads: int,
                     feat: int, negative_slope: float, has_weight: bool,
                     logit_kind: str = "add", logit_dim: int = 1,
                     return_carry: bool = False):
    """One (row_block, head, feat_block) tile: online-softmax accumulate.

    ``idx_sref``   full neighbor table flattened to (R*K,), scalar-prefetched
                   (SMEM) — the DMA address stream (flat, as in the SpMM
                   kernel).
    ``idx_ref``    (BR, K) VMEM panel of the same table — vectorized masking.
    ``adst_ref``   (BR, H*LD) VMEM panel: receiver term per bucket row
                   (alpha_dst for additive logits, q for dot logits), its
                   lanes zero-padded to whole 128-lane tiles.
    ``rest``       [prior_ref] asrc_hbm z_hbm out_ref [m_ref l_ref]
                   zgather agather sems — the prior operand and the carry
                   outputs exist only on ``return_carry`` launches.
    ``asrc_hbm``   (N, 1, H*LD padded) sender term, ``z_hbm`` (N, 1, H*F
                   padded) features: HBM tables with a unit axis, so that
                   each single-row DMA is tile-aligned (see the SpMM
                   kernel).
    ``zgather``    (2, BR, 1, BF) VMEM scratch — feature-row landing zone.
    ``agather``    (2, BR, 1, H*LD padded) VMEM scratch — sender rows.
    ``sems``       (2, 2, BR) DMA semaphores: [0] features, [1] alphas.
    """
    if return_carry:
        (prior_ref, asrc_hbm, z_hbm, out_ref, m_ref, l_ref, zgather,
         agather, sems) = rest
    else:
        asrc_hbm, z_hbm, out_ref, zgather, agather, sems = rest
    r_blk = pl.program_id(0)
    h = pl.program_id(1)
    f_blk = pl.program_id(2)
    row_base = r_blk * block_rows
    # z is (N, H*F): head h's feature block starts at h*F + f_blk*BF.
    f_start = h * feat + f_blk * block_feat

    def z_dma(slot, kk, r):
        nid = jnp.maximum(idx_sref[(row_base + r) * k + kk], 0)
        return pltpu.make_async_copy(
            z_hbm.at[pl.ds(nid, 1), :, pl.ds(f_start, block_feat)],
            zgather.at[slot, pl.ds(r, 1)],
            sems.at[0, slot, r],
        )

    def a_dma(slot, kk, r):
        nid = jnp.maximum(idx_sref[(row_base + r) * k + kk], 0)
        return pltpu.make_async_copy(
            asrc_hbm.at[pl.ds(nid, 1)],
            agather.at[slot, pl.ds(r, 1)],
            sems.at[1, slot, r],
        )

    def start_column(slot, kk):
        def body_r(r, carry):
            z_dma(slot, kk, r).start()
            a_dma(slot, kk, r).start()
            return carry
        jax.lax.fori_loop(0, block_rows, body_r, 0)

    def wait_column(slot, kk):
        def body_r(r, carry):
            z_dma(slot, kk, r).wait()
            a_dma(slot, kk, r).wait()
            return carry
        jax.lax.fori_loop(0, block_rows, body_r, 0)

    # Columns of the (BR, K) panels and this head's lanes of the (BR, H*LD)
    # ones are picked with iota masks: lane-dynamic slices do not lower on
    # the TPU.
    idx_panel = idx_ref[...]  # (BR, K)
    valid_panel = (idx_panel >= 0).astype(jnp.float32)
    lane_k = jax.lax.broadcasted_iota(jnp.int32, (block_rows, k), 1)
    lane_h = jax.lax.broadcasted_iota(jnp.int32, adst_ref.shape, 1)
    head_lanes = (lane_h >= h * logit_dim) & (lane_h < (h + 1) * logit_dim)
    adst = adst_ref[...].astype(jnp.float32)
    if logit_kind == "add":
        adst_col = lane_pick(head_lanes, adst)  # (BR, 1): this head
    else:  # dot: this head's query lanes (others zeroed) + scalar prior
        q_head = jnp.where(head_lanes, adst, 0.0)
        prior_col = lane_pick(
            jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1) == h,
            prior_ref[...].astype(jnp.float32))  # (1, 1)
    if has_weight:
        w_panel = w_ref[...].astype(jnp.float32)

    # Warm-up: put column 0 in flight before entering the steady state.
    start_column(0, 0)

    m0 = jnp.full((block_rows, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_rows, 1), jnp.float32)
    acc0 = jnp.zeros((block_rows, block_feat), jnp.float32)

    def body_k(kk, carry):
        m, l, acc = carry
        slot = jax.lax.rem(kk, _NUM_SLOTS)

        # Prefetch column kk+1 into the other slot while kk lands/computes.
        @pl.when(kk + 1 < k)
        def _():
            start_column(1 - slot, kk + 1)

        wait_column(slot, kk)
        ztile = zgather[slot].reshape(block_rows, block_feat).astype(
            jnp.float32)                             # (BR, BF)
        arows = agather[slot].reshape(adst_ref.shape).astype(
            jnp.float32)                             # (BR, H*LD padded)

        col = lane_k == kk
        valid = lane_pick(col, valid_panel) > 0.0    # (BR, 1)
        if logit_kind == "add":
            logit = lane_pick(head_lanes, arows) + adst_col
            logit = jnp.where(logit >= 0, logit, negative_slope * logit)
        else:  # dot: <k[nbr], q[row]> over this head's LD lanes, scaled
            logit = jnp.sum(arows * q_head, axis=1, keepdims=True) * prior_col
        logit = jnp.where(valid, logit, -jnp.inf)

        # Online softmax: rescale the accumulator by exp(m - m_new). While a
        # row has seen no valid neighbor m is -inf and every term is 0.
        m_new = jnp.maximum(m, logit)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(valid, jnp.exp(logit - m_safe), 0.0)    # (BR, 1)
        corr = jnp.exp(m - m_safe)  # exp(-inf) = 0 zeroes the empty prefix
        num = p if not has_weight else p * lane_pick(col, w_panel)
        return m_new, l * corr + p, acc * corr + num * ztile

    m, l, acc = jax.lax.fori_loop(0, k, body_k, (m0, l0, acc0))
    if return_carry:
        # Unnormalised carry out: the ops layer merges (m, l, acc) triples
        # across relation launches before the single finalize divide. The
        # (BR, 1) carry blocks are revisited per feat tile — same values.
        out_ref[...] = acc.astype(out_ref.dtype)
        m_ref[...] = m.astype(m_ref.dtype)
        l_ref[...] = l.astype(l_ref.dtype)
    else:
        # acc/l = sum_k softmax_k(logits) * w_k * z_k; empty rows stay 0.
        out_ref[...] = (acc / jnp.maximum(l, 1e-16)).astype(out_ref.dtype)


def _gat_ell_kernel(*args, **kwargs):
    """Additive-logit launch face of :func:`_attn_ell_kernel`.

    Its launches keep the name ``_gat_ell_kernel`` (``name=``), which the
    dispatch auditor's golden audits and the FLOP cost table key on.
    """
    return _attn_ell_kernel(*args, **kwargs)


def _head_table(z2d: jnp.ndarray, heads: int, feat: int, padded: int):
    """(N, H*F) -> (N, 1, H*padded): each head's lanes zero-padded to whole
    lane tiles, in the (N, 1, ·) layout the row-gather DMAs read. The out
    block is one head's ``(BR, BF)`` slice of the ``(R, H*padded)`` output,
    and a TPU block's last dimension must be a multiple of 128."""
    n = z2d.shape[0]
    z3 = z2d.reshape(n, heads, feat)
    if padded != feat:
        z3 = jnp.pad(z3, ((0, 0), (0, 0), (0, padded - feat)))
    return z3.reshape(n, 1, heads * padded)


def _unpad_heads(out: jnp.ndarray, heads: int, feat: int, padded: int):
    """(R, H*padded) -> (R, H*F): drop the padding lanes of every head."""
    if padded == feat:
        return out
    return out.reshape(out.shape[0], heads, padded)[:, :, :feat].reshape(
        out.shape[0], heads * feat)


@functools.partial(
    jax.jit, static_argnames=("negative_slope", "block_rows", "interpret"))
def _gat_ell_pallas_impl(ell_idx: jnp.ndarray, adst: jnp.ndarray,
                         ell_w: Optional[jnp.ndarray], alpha_src: jnp.ndarray,
                         z2d: jnp.ndarray, *, negative_slope: float = 0.2,
                         block_rows: int = DEFAULT_BR,
                         interpret: bool = False) -> jnp.ndarray:
    """Fused GAT aggregation over one blocked-ELL bucket.

    Args:
      ell_idx:   (R, K) int32 neighbor table, -1 = padding. R % BR == 0.
      adst:      (R, H) alpha_dst values of each bucket row (receiver term).
      ell_w:     optional (R, K) per-slot post-softmax weights (edge_mask /
                 edge_weight gathered through ``ell_pos``).
      alpha_src: (N, H) dense per-node sender term (gathered in-kernel).
      z2d:       (N, H*F) head-flattened features (gathered in-kernel).

    Returns ``(R, H*F)``: per row, head h's slice is the attention-weighted
    neighbor sum for that head.
    """
    rows, k = ell_idx.shape
    heads = adst.shape[1]
    hf = z2d.shape[1]
    assert hf % heads == 0, (hf, heads)
    feat = hf // heads
    fpad = lane_tiles(feat)
    hpad = lane_tiles(heads)
    block_feat = DEFAULT_BF
    assert rows % block_rows == 0, (rows, block_rows)
    assert k >= 1, "ELL table must have at least one neighbor column"
    nfb = fpad // block_feat
    grid = (rows // block_rows, heads, nfb)

    has_weight = ell_w is not None
    if ell_w is None:  # dummy operand keeps the signature static
        ell_w = jnp.zeros((block_rows, k), jnp.float32)

    kernel = functools.partial(
        _gat_ell_kernel, block_rows=block_rows, block_feat=block_feat, k=k,
        heads=heads, feat=fpad, negative_slope=float(negative_slope),
        has_weight=has_weight)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the neighbor table: DMA address stream
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, k), lambda i, h, j, idx: (i, 0)),
            pl.BlockSpec((block_rows, hpad), lambda i, h, j, idx: (i, 0)),
            pl.BlockSpec((block_rows, k), lambda i, h, j, idx: (i, 0))
            if has_weight else
            pl.BlockSpec((block_rows, k), lambda i, h, j, idx: (0, 0)),
            # alpha_src and z stay in HBM; the kernel DMA-gathers rows out.
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_rows, block_feat),
                               lambda i, h, j, idx: (i, h * nfb + j)),
        scratch_shapes=[
            pltpu.VMEM((_NUM_SLOTS, block_rows, 1, block_feat), z2d.dtype),
            pltpu.VMEM((_NUM_SLOTS, block_rows, 1, hpad), alpha_src.dtype),
            pltpu.SemaphoreType.DMA((2, _NUM_SLOTS, block_rows)),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, heads * fpad), z2d.dtype),
        interpret=interpret,
        name="_gat_ell_kernel",
    )(ell_idx.reshape(-1), ell_idx, pad_lanes(adst, hpad), ell_w,
      gather_table(alpha_src, hpad), _head_table(z2d, heads, feat, fpad))
    return _unpad_heads(out, heads, feat, fpad)


from repro.kernels import forward_only_pallas

_gat_ell_pallas_cv = forward_only_pallas(
    lambda negative_slope, block_rows, interpret, ell_idx, adst, ell_w,
    alpha_src, z2d:
        _gat_ell_pallas_impl(ell_idx, adst, ell_w, alpha_src, z2d,
                             negative_slope=negative_slope,
                             block_rows=block_rows, interpret=interpret),
    num_static=3,
    message=(
        "gat_ell_pallas is the raw Pallas kernel and has no backward rule. "
        "Differentiate through the ops-level entry points instead "
        "(repro.kernels.attention.ops.gat_attend_ell carries a custom VJP "
        "— the softmax backward over the same ELL panels), or set "
        "REPRO_USE_PALLAS=0 to dispatch the differentiable XLA oracle."))


def gat_ell_pallas(ell_idx: jnp.ndarray, adst: jnp.ndarray,
                   ell_w: Optional[jnp.ndarray], alpha_src: jnp.ndarray,
                   z2d: jnp.ndarray, *, negative_slope: float = 0.2,
                   block_rows: int = DEFAULT_BR,
                   interpret: bool = False) -> jnp.ndarray:
    """Fused GAT attention kernel (see :func:`_gat_ell_pallas_impl`).

    Forward-only: differentiating this raw entry point raises a clear
    ``NotImplementedError`` pointing at the ops-level wrapper (which carries
    the custom VJP) and the ``REPRO_USE_PALLAS`` fallback env var.
    """
    return _gat_ell_pallas_cv(float(negative_slope), block_rows, interpret,
                              ell_idx, adst, ell_w, alpha_src, z2d)


@functools.partial(
    jax.jit,
    static_argnames=("logit_kind", "negative_slope", "block_rows",
                     "interpret"),
)
def _attn_ell_pallas_impl(ell_idx: jnp.ndarray, adst: jnp.ndarray,
                          ell_w: Optional[jnp.ndarray], prior: jnp.ndarray,
                          alpha_src: jnp.ndarray, z2d: jnp.ndarray, *,
                          logit_kind: str, negative_slope: float = 0.2,
                          block_rows: int = DEFAULT_BR,
                          interpret: bool = False):
    """Typed-attention carry launch over one blocked-ELL bucket.

    Args:
      ell_idx:   (R, K) int32 neighbor table, -1 = padding. R % BR == 0.
      adst:      (R, H*LD) receiver term per bucket row (alpha_dst / q).
      ell_w:     optional (R, K) per-slot post-softmax weights.
      prior:     (1, H) per-head logit scale (mu[rel]/sqrt(D); used by the
                 dot logit, carried-but-ignored by the additive one).
      alpha_src: (N, H*LD) dense per-node sender term (gathered in-kernel).
      z2d:       (N, H*F) head-flattened features (gathered in-kernel).

    Returns ``(acc, m, l)`` float32: the *unnormalised* accumulator
    ``(R, H*F)`` plus the per-(row, head) running softmax max/denominator —
    mergeable across relation launches, finalized ops-side.
    """
    rows, k = ell_idx.shape
    heads = prior.shape[1]
    hl = adst.shape[1]
    hf = z2d.shape[1]
    assert hl % heads == 0, (hl, heads)
    assert hf % heads == 0, (hf, heads)
    logit_dim = hl // heads
    feat = hf // heads
    fpad = lane_tiles(feat)
    hlpad = lane_tiles(hl)
    block_feat = DEFAULT_BF
    assert rows % block_rows == 0, (rows, block_rows)
    assert k >= 1, "ELL table must have at least one neighbor column"
    nfb = fpad // block_feat
    grid = (rows // block_rows, heads, nfb)

    has_weight = ell_w is not None
    if ell_w is None:  # dummy operand keeps the signature static
        ell_w = jnp.zeros((block_rows, k), jnp.float32)

    kernel = functools.partial(
        _attn_ell_kernel, block_rows=block_rows, block_feat=block_feat, k=k,
        heads=heads, feat=fpad, negative_slope=float(negative_slope),
        has_weight=has_weight, logit_kind=logit_kind, logit_dim=logit_dim,
        return_carry=True)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, k), lambda i, h, j, idx: (i, 0)),
            pl.BlockSpec((block_rows, hlpad), lambda i, h, j, idx: (i, 0)),
            pl.BlockSpec((block_rows, k), lambda i, h, j, idx: (i, 0))
            if has_weight else
            pl.BlockSpec((block_rows, k), lambda i, h, j, idx: (0, 0)),
            pl.BlockSpec((1, heads), lambda i, h, j, idx: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, block_feat),
                         lambda i, h, j, idx: (i, h * nfb + j)),
            # (m, l) are laid out head-major, (H, R, 1): a (BR, 1) block of
            # an (R, H) array would break the last-dimension tiling rule.
            pl.BlockSpec((None, block_rows, 1),
                         lambda i, h, j, idx: (h, i, 0)),
            pl.BlockSpec((None, block_rows, 1),
                         lambda i, h, j, idx: (h, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((_NUM_SLOTS, block_rows, 1, block_feat), z2d.dtype),
            pltpu.VMEM((_NUM_SLOTS, block_rows, 1, hlpad), alpha_src.dtype),
            pltpu.SemaphoreType.DMA((2, _NUM_SLOTS, block_rows)),
        ],
    )

    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, heads * fpad), jnp.float32),
            jax.ShapeDtypeStruct((heads, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((heads, rows, 1), jnp.float32),
        ],
        interpret=interpret,
        name="_attn_ell_kernel",
    )(ell_idx.reshape(-1), ell_idx, pad_lanes(adst, hlpad), ell_w, prior,
      gather_table(alpha_src, hlpad), _head_table(z2d, heads, feat, fpad))
    return (_unpad_heads(acc, heads, feat, fpad), m[:, :, 0].T, l[:, :, 0].T)


_attn_ell_pallas_cv = forward_only_pallas(
    lambda logit_kind, negative_slope, block_rows, interpret, ell_idx, adst,
    ell_w, prior, alpha_src, z2d:
        _attn_ell_pallas_impl(ell_idx, adst, ell_w, prior, alpha_src, z2d,
                              logit_kind=logit_kind,
                              negative_slope=negative_slope,
                              block_rows=block_rows, interpret=interpret),
    num_static=4,
    message=(
        "attn_ell_pallas is the raw Pallas kernel and has no backward rule. "
        "Differentiate through the ops-level entry points instead "
        "(repro.kernels.attention.ops.attn_carry_ell carries a custom VJP "
        "over the merged-carry form), or set REPRO_USE_PALLAS=0 to dispatch "
        "the differentiable XLA oracle."))


def attn_ell_pallas(ell_idx: jnp.ndarray, adst: jnp.ndarray,
                    ell_w: Optional[jnp.ndarray], prior: jnp.ndarray,
                    alpha_src: jnp.ndarray, z2d: jnp.ndarray, *,
                    logit_kind: str = "dot", negative_slope: float = 0.2,
                    block_rows: int = DEFAULT_BR,
                    interpret: bool = False):
    """Typed-attention carry kernel (see :func:`_attn_ell_pallas_impl`).

    Forward-only: differentiating this raw entry point raises a clear
    ``NotImplementedError`` pointing at the ops-level wrapper (which carries
    the custom VJP) and the ``REPRO_USE_PALLAS`` fallback env var.
    """
    return _attn_ell_pallas_cv(str(logit_kind), float(negative_slope),
                               block_rows, interpret, ell_idx, adst, ell_w,
                               prior, alpha_src, z2d)
