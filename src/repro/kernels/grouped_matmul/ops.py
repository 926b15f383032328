"""Public grouped-matmul entry points: packing + kernel/oracle dispatch.

The Pallas branch is differentiable: a custom VJP runs the backward as two
grouped GEMMs that reuse the forward's tile->group table (the MegaBlocks
recipe — ``dx = dy @ w[g]^T`` through the same packed layout, ``dw[g]``
accumulated tile-wise and segment-summed per group), so hetero projection
stacks and MoE experts can train on the kernel path.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import use_pallas
from repro.kernels.grouped_matmul.grouped_matmul import grouped_matmul_pallas


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray,
                   *, force_pallas: Optional[bool] = None,
                   interpret: bool = False) -> jnp.ndarray:
    """Grouped GEMM over group-sorted rows. Dispatches kernel or XLA oracle.

    The XLA path is ``jax.lax.ragged_dot`` (native grouped matmul lowering).
    The Pallas path needs *concrete* ``group_sizes`` (row packing is a host
    shape decision); traced sizes fall back to the XLA path — same
    convention as the SpMM dispatch under tracing. The Pallas branch carries
    a custom VJP (two grouped GEMMs over the same tile->group table), so
    ``jax.grad`` through it works.
    """
    take_pallas = use_pallas() if force_pallas is None else force_pallas
    if take_pallas and isinstance(group_sizes, jax.core.Tracer):
        take_pallas = False  # packing needs host shapes
    if take_pallas:
        sizes = tuple(int(s) for s in np.asarray(group_sizes))
        return _grouped_matmul_diff(sizes, bool(interpret), x, w)
    # The named scope tags the XLA fallback for the dispatch auditor.
    with jax.named_scope("repro_oracle:grouped_matmul"):
        return jax.lax.ragged_dot(x, w, jnp.asarray(group_sizes, jnp.int32))


def _pack_plan(sizes: Tuple[int, ...], block_m: int = 128
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side packing plan: (src_rows, row_map, tile_group, total).

    Each group's rows pad to a ``block_m`` multiple so every M-tile belongs
    to exactly one group; ``src_rows`` maps packed slot -> original row
    (padding slots re-read row 0), ``row_map`` original row -> packed slot,
    ``tile_group`` M-tile -> group id.
    """
    sizes_a = np.asarray(sizes, np.int64)
    padded = -(-sizes_a // block_m) * block_m
    padded = np.maximum(padded, block_m)  # empty groups still occupy a tile
    total = int(padded.sum())
    src_rows = np.zeros(total, np.int64)
    row_map = np.zeros(int(sizes_a.sum()), np.int64)
    tile_group = np.zeros(total // block_m, np.int32)
    off_orig, off_pack, off_tile = 0, 0, 0
    for gi, (s, p) in enumerate(zip(sizes_a, padded)):
        s, p = int(s), int(p)
        src_rows[off_pack:off_pack + s] = np.arange(off_orig, off_orig + s)
        row_map[off_orig:off_orig + s] = np.arange(off_pack, off_pack + s)
        tile_group[off_tile:off_tile + p // block_m] = gi
        off_orig += s
        off_pack += p
        off_tile += p // block_m
    return src_rows, row_map, tile_group, total


def pack_rows(x: jnp.ndarray, group_sizes: jnp.ndarray, block_m: int = 128
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """Pad each group's rows to a multiple of ``block_m`` (host-side shapes).

    Returns (x_packed, tile_group, row_map) where ``row_map`` scatters packed
    rows back to original positions: ``out_orig = out_packed[row_map]``.
    NOTE: requires concrete ``group_sizes`` (host), as padding changes shapes.
    """
    sizes = tuple(int(s) for s in np.asarray(group_sizes))
    src_rows, row_map, tile_group, _ = _pack_plan(sizes, block_m)
    xp = jnp.take(x, jnp.asarray(src_rows), axis=0)
    return xp, jnp.asarray(tile_group), jnp.asarray(row_map), int(sum(sizes))


def _gmm_pallas_forward(sizes: Tuple[int, ...], interpret: bool,
                        x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Pack -> pad K/N to MXU tiles -> kernel -> unpack (the Pallas path).

    ``sizes`` is a static tuple (host shapes), so the plan is pure numpy —
    inside a trace the operands are tracers but the packing never is.
    """
    src_rows, row_map, tile_group, _ = _pack_plan(sizes)
    xp = jnp.take(x, jnp.asarray(src_rows), axis=0)
    row_map, tile_group = jnp.asarray(row_map), jnp.asarray(tile_group)
    k, n = x.shape[1], w.shape[2]
    kp, np_ = -(-k // 128) * 128, -(-n // 128) * 128
    if kp != k:
        xp = jnp.pad(xp, ((0, 0), (0, kp - k)))
        w = jnp.pad(w, ((0, 0), (0, kp - k), (0, 0)))
    if np_ != n:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, np_ - n)))
    out = grouped_matmul_pallas(xp, w, tile_group, interpret=interpret)
    return out[row_map, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _grouped_matmul_diff(sizes: Tuple[int, ...], interpret: bool, x, w):
    """Differentiable Pallas grouped matmul: forward on the MXU kernel, the
    backward as two grouped GEMMs reusing the forward tile->group table."""
    return _gmm_pallas_forward(sizes, interpret, x, w)


def _grouped_matmul_diff_fwd(sizes, interpret, x, w):
    return _gmm_pallas_forward(sizes, interpret, x, w), (x, w)


def _grouped_matmul_diff_bwd(sizes, interpret, residuals, dy):
    x, w = residuals
    # Scoped as the kernel's own backward so the dispatch auditor never
    # reads its scatters/segment-sums as an oracle fallback in grad steps.
    with jax.named_scope("repro_kernel_vjp:grouped_matmul"):
        # dx[m] = dy[m] @ w[g(m)]^T — the same grouped GEMM with w
        # transposed, over the identical tile->group table (shapes depend
        # only on `sizes`).
        dx = _gmm_pallas_forward(sizes, interpret, dy,
                                 jnp.swapaxes(w, 1, 2)).astype(x.dtype)
        # dw[g] = sum_{m in g} x[m]^T dy[m] — pack both operands into the
        # tiled layout with *zeros* in padding slots, contract per M-tile,
        # and segment-sum tiles into their groups (the second grouped GEMM).
        _, row_map, tile_group, total = _pack_plan(sizes)
        block_m = 128  # _pack_plan's tile height
        k, n = x.shape[1], dy.shape[1]
        xp = jnp.zeros((total, k), jnp.float32).at[jnp.asarray(row_map)].set(
            x.astype(jnp.float32))
        dyp = jnp.zeros((total, n), jnp.float32).at[jnp.asarray(row_map)].set(
            dy.astype(jnp.float32))
        per_tile = jnp.einsum("tmk,tmn->tkn",
                              xp.reshape(-1, block_m, k),
                              dyp.reshape(-1, block_m, n))
        dw = jax.ops.segment_sum(per_tile, jnp.asarray(tile_group),
                                 num_segments=w.shape[0]).astype(w.dtype)
    return dx, dw


_grouped_matmul_diff.defvjp(_grouped_matmul_diff_fwd,
                            _grouped_matmul_diff_bwd)
