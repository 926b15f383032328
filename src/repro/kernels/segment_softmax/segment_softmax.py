"""Segment-softmax Pallas TPU kernel over padded edge panels.

TPU adaptation of the CUDA segment softmax used for GAT edge attention:
edges sorted by destination are packed into (row, K) panels (same blocked-ELL
layout as the SpMM kernel), turning the ragged per-destination softmax into a
dense masked row softmax that vectorises over 128 lanes. Row blocks are tiled
into VMEM; max/sum reductions run on the VPU within a tile.

Grid: ``(num_row_blocks,)`` with the full K panel per block in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BR = 8


def _segment_softmax_kernel(val_ref, mask_ref, out_ref):
    vals = val_ref[...].astype(jnp.float32)
    mask = mask_ref[...] != 0
    neg = jnp.where(mask, vals, -jnp.inf)
    mx = jnp.max(neg, axis=1, keepdims=True)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    ex = jnp.where(mask, jnp.exp(vals - mx), 0.0)
    den = jnp.maximum(jnp.sum(ex, axis=1, keepdims=True), 1e-16)
    out_ref[...] = (ex / den).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _segment_softmax_pallas_impl(values: jnp.ndarray, mask: jnp.ndarray, *,
                                 block_rows: int = DEFAULT_BR,
                                 interpret: bool = False) -> jnp.ndarray:
    """Masked row softmax over (R, K) panels.

    Odd panel heights are padded (masked) up to the ``block_rows`` multiple
    — the same capacity-padding convention as the SpMM kernel — instead of
    asserting; padded rows are all-masked and come out as 0 rows, and the
    result is sliced back to the caller's R.
    """
    rows, k = values.shape
    pad = -rows % block_rows
    mask = mask.astype(jnp.int32)
    if pad:
        values = jnp.concatenate(
            [values, jnp.zeros((pad, k), values.dtype)], axis=0)
        mask = jnp.concatenate([mask, jnp.zeros((pad, k), mask.dtype)],
                               axis=0)
    grid = ((rows + pad) // block_rows,)
    out = pl.pallas_call(
        _segment_softmax_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, k), values.dtype),
        interpret=interpret,
        name="_segment_softmax_kernel",
    )(values, mask)
    return out[:rows] if pad else out


from repro.kernels import forward_only_pallas

_segment_softmax_pallas_cv = forward_only_pallas(
    lambda block_rows, interpret, values, mask:
        _segment_softmax_pallas_impl(values, mask, block_rows=block_rows,
                                     interpret=interpret),
    num_static=2,
    message=(
        "segment_softmax_pallas is the raw Pallas kernel and has no "
        "backward rule. Differentiate through the ops-level entry points "
        "instead (repro.kernels.segment_softmax.ops.segment_softmax_ell "
        "carries a custom VJP over the same panels, and the fused GAT path "
        "repro.kernels.attention.ops.gat_attend_ell differentiates end to "
        "end), or set REPRO_USE_PALLAS=0 to dispatch the differentiable "
        "XLA oracle."))


def segment_softmax_pallas(values: jnp.ndarray, mask: jnp.ndarray, *,
                           block_rows: int = DEFAULT_BR,
                           interpret: bool = False) -> jnp.ndarray:
    """Masked row softmax over (R, K) panels (rows padded to the block).

    Forward-only: differentiating this raw entry point raises a clear
    ``NotImplementedError`` pointing at the ops-level wrappers (which carry
    the custom VJP) and the ``REPRO_USE_PALLAS`` fallback env var.
    """
    return _segment_softmax_pallas_cv(block_rows, interpret, values, mask)
