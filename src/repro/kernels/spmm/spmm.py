"""Blocked-ELL SpMM Pallas TPU kernel — the message-passing fast path (C2).

TPU adaptation of PyG's CUDA scatter/SpMM message passing:

* TPUs have no atomics, so the CUDA scatter-add design does not port. Instead
  we exploit exactly the property the paper's `EdgeIndex` tracks — *sortedness*
  — to turn aggregation into a dense, maskable, per-row-block reduction.
* Layout: rows (destination nodes) are padded to a fixed neighbor budget `K`
  (blocked-ELL). Feature dim is tiled to the 128-lane VPU/MXU width; row
  blocks of `BR` live in VMEM together with a (BR, BF) fp32 accumulator.
* The neighbor gather is *pipelined*: the neighbor ids arrive via scalar
  prefetch (SMEM), and the kernel issues `BR` async HBM->VMEM copies per
  neighbor column into a double-buffered VMEM scratch — the copies for
  column ``k+1`` are in flight while column ``k`` is being accumulated.
  This replaces the previous design (one *synchronous* scalar dynamic-slice
  load per (row, neighbor), i.e. BR*K serialized HBM round trips per tile)
  with BR-wide batches of overlapped DMAs and a single vectorized
  (BR, BF) accumulation step per column.
* Skewed degree distributions do not pay max-degree padding: the host packs
  rows into power-of-two-K *degree buckets* (see ``ops.csr_to_ell_bucketed``)
  and launches this kernel once per bucket.

Grid: ``(num_row_blocks, num_feat_blocks)``; the `K` loop runs inside the
kernel so each (row, feat) tile is written exactly once.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# TPU-friendly defaults (declared in kernels.budgets, the budget source
# of truth): 8-row sublanes x 128-lane features, double-buffered DMA.
from repro.kernels.budgets import (DEFAULT_BF, DEFAULT_BR,
                                   DOUBLE_BUFFER_SLOTS as _NUM_SLOTS,
                                   lane_tiles)


def lane_pick(mask, panel):
    """``(BR, 1)`` column of an f32 ``(BR, L)`` panel where ``mask`` is set
    (exactly one lane per row)."""
    return jnp.sum(jnp.where(mask, panel, 0.0), axis=1, keepdims=True)


def _spmm_ell_kernel(idx_sref, idx_ref, w_ref, x_hbm, out_ref, gather, sems,
                     *, block_rows: int, block_feat: int, k: int,
                     has_weight: bool, reduce: str):
    """One (row_block, feat_block) tile: pipelined gather-accumulate.

    ``idx_sref``  full neighbor table flattened to (R*K,), scalar-prefetched
                  (SMEM) — the DMA address stream. SMEM pads the minor dim
                  of a 2-D table to 128 words, so a flat table holds
                  128/K times more rows in the same SMEM.
    ``idx_ref``   (BR, K) VMEM panel of the same table — vectorized masking.
    ``x_hbm``     (N, 1, F) features in HBM; one neighbor row per DMA.
    ``gather``    (2, BR, 1, BF) VMEM scratch — double-buffered landing zone.
    ``sems``      (2, BR) DMA semaphores — one per in-flight neighbor row.

    The unit axis makes a row the TPU tile's second-minor extent, so a
    single-row DMA is tile-aligned at both ends; a row slice of an (N, F)
    array is not, and the TPU compiler refuses it.
    """
    r_blk = pl.program_id(0)
    f_blk = pl.program_id(1)
    row_base = r_blk * block_rows
    f_start = f_blk * block_feat

    def column_dma(slot, kk, r):
        nid = jnp.maximum(idx_sref[(row_base + r) * k + kk], 0)
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(nid, 1), :, pl.ds(f_start, block_feat)],
            gather.at[slot, pl.ds(r, 1)],
            sems.at[slot, r],
        )

    def start_column(slot, kk):
        def body_r(r, carry):
            column_dma(slot, kk, r).start()
            return carry
        jax.lax.fori_loop(0, block_rows, body_r, 0)

    def wait_column(slot, kk):
        def body_r(r, carry):
            column_dma(slot, kk, r).wait()
            return carry
        jax.lax.fori_loop(0, block_rows, body_r, 0)

    idx_panel = idx_ref[...]  # (BR, K) — in VMEM; drives masks and counts
    valid_panel = (idx_panel >= 0).astype(jnp.float32)
    if has_weight:
        w_panel = w_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_rows, k), 1)

    if reduce in ("sum", "mean"):
        init = jnp.zeros((block_rows, block_feat), jnp.float32)
    elif reduce == "max":
        init = jnp.full((block_rows, block_feat), -jnp.inf, jnp.float32)
    else:  # min
        init = jnp.full((block_rows, block_feat), jnp.inf, jnp.float32)

    # Warm-up: put column 0 in flight before entering the steady state.
    start_column(0, 0)

    def body_k(kk, acc):
        slot = jax.lax.rem(kk, _NUM_SLOTS)

        # Prefetch column kk+1 into the other slot while kk lands/computes.
        @pl.when(kk + 1 < k)
        def _():
            start_column(1 - slot, kk + 1)

        wait_column(slot, kk)
        tile = gather[slot].reshape(block_rows, block_feat).astype(
            jnp.float32)  # (BR, BF)

        # Column kk of the (BR, K) panels as a (BR, 1) vector: a lane-
        # dynamic slice does not lower on the TPU, a masked lane sum does.
        col = lane == kk
        valid = lane_pick(col, valid_panel) > 0.0
        if has_weight:
            tile = tile * lane_pick(col, w_panel)
        if reduce in ("sum", "mean"):
            return acc + jnp.where(valid, tile, 0.0)
        if reduce == "max":
            return jnp.maximum(acc, jnp.where(valid, tile, -jnp.inf))
        return jnp.minimum(acc, jnp.where(valid, tile, jnp.inf))

    acc = jax.lax.fori_loop(0, k, body_k, init)

    if reduce == "mean":
        cnt = jnp.sum(valid_panel, axis=1, keepdims=True)
        acc = acc / jnp.maximum(cnt, 1.0)
    elif reduce in ("max", "min"):
        acc = jnp.where(jnp.isfinite(acc), acc, 0.0)

    out_ref[...] = acc.astype(out_ref.dtype)


def pad_lanes(a: jnp.ndarray, padded: int) -> jnp.ndarray:
    """(M, L) -> (M, padded) with zero lanes appended."""
    if padded == a.shape[1]:
        return a
    return jnp.pad(a, ((0, 0), (0, padded - a.shape[1])))


def gather_table(x: jnp.ndarray, padded: int) -> jnp.ndarray:
    """(N, F) -> (N, 1, padded): the HBM layout the row-gather DMAs read.

    A TPU DMA may not slice a row narrower than its 128-lane tile, so the
    table is zero-padded to whole tiles (``budgets.lane_tiles``) and the
    kernel's output sliced back.
    """
    return pad_lanes(x, padded).reshape(x.shape[0], 1, padded)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "reduce", "interpret"))
def _spmm_ell_pallas_impl(ell_idx: jnp.ndarray, ell_w: Optional[jnp.ndarray],
                          x: jnp.ndarray, *, block_rows: int = DEFAULT_BR,
                          reduce: str = "sum",
                          interpret: bool = False) -> jnp.ndarray:
    """Blocked-ELL SpMM: out[r] = reduce_k w[r,k] * x[ell_idx[r,k]].

    Args:
      ell_idx: (R, K) int32 neighbor table, -1 = padding. R % block_rows == 0.
      ell_w:   optional (R, K) weights.
      x:       (N, F) features; F is zero-padded to whole lane tiles.
    """
    rows, k = ell_idx.shape
    feat = x.shape[1]
    fpad = lane_tiles(feat)
    block_feat = DEFAULT_BF
    assert rows % block_rows == 0, (rows, block_rows)
    assert k >= 1, "ELL table must have at least one neighbor column"
    grid = (rows // block_rows, fpad // block_feat)

    has_weight = ell_w is not None
    if ell_w is None:  # dummy operand keeps the signature static
        ell_w = jnp.zeros((block_rows, k), x.dtype)

    kernel = functools.partial(
        _spmm_ell_kernel, block_rows=block_rows, block_feat=block_feat, k=k,
        has_weight=has_weight, reduce=reduce)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # the neighbor table: DMA address stream
        grid=grid,
        in_specs=[
            # Neighbor ids for this row block; full K panel in VMEM.
            pl.BlockSpec((block_rows, k), lambda i, j, idx: (i, 0)),
            pl.BlockSpec((block_rows, k), lambda i, j, idx: (i, 0))
            if has_weight else
            pl.BlockSpec((block_rows, k), lambda i, j, idx: (0, 0)),
            # Features stay in HBM; the kernel DMA-gathers rows out.
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_rows, block_feat),
                               lambda i, j, idx: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((_NUM_SLOTS, block_rows, 1, block_feat), x.dtype),
            pltpu.SemaphoreType.DMA((_NUM_SLOTS, block_rows)),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, fpad), x.dtype),
        interpret=interpret,
        name="_spmm_ell_kernel",
    )(ell_idx.reshape(-1), ell_idx, ell_w, gather_table(x, fpad))
    return out if fpad == feat else out[:, :feat]


from repro.kernels import forward_only_pallas

_spmm_ell_pallas_cv = forward_only_pallas(
    lambda block_rows, reduce, interpret, ell_idx, ell_w, x:
        _spmm_ell_pallas_impl(ell_idx, ell_w, x, block_rows=block_rows,
                              reduce=reduce, interpret=interpret),
    num_static=3,
    message=(
        "spmm_ell_pallas is the raw Pallas kernel and has no backward rule "
        "for this configuration. Differentiate through the ops-level entry "
        "points instead (repro.kernels.spmm.ops.spmm_ell / "
        "spmm_ell_bucketed carry a custom VJP over the same ELL buckets), "
        "or set REPRO_USE_PALLAS=0 to dispatch the differentiable XLA "
        "oracle."))


def spmm_ell_pallas(ell_idx: jnp.ndarray, ell_w: Optional[jnp.ndarray],
                    x: jnp.ndarray, *, block_rows: int = DEFAULT_BR,
                    reduce: str = "sum",
                    interpret: bool = False) -> jnp.ndarray:
    """Blocked-ELL SpMM Pallas kernel (see :func:`_spmm_ell_pallas_impl`).

    Forward-only: differentiating this raw entry point raises a clear
    ``NotImplementedError`` pointing at the ops-level wrappers (which carry
    the custom VJP) and the ``REPRO_USE_PALLAS`` fallback env var.
    """
    return _spmm_ell_pallas_cv(block_rows, reduce, interpret, ell_idx, ell_w,
                               x)
