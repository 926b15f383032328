"""Grouped-GEMM Pallas TPU kernel (paper C4; CUTLASS grouped GEMM -> MXU).

TPU adaptation:
* Every group's row count is padded (host side, in ops.py) to a multiple of
  the 128-row MXU tile, so each M-tile belongs to exactly one group — the
  MegaBlocks trick, which turns the ragged problem into a dense grid plus a
  tiny ``tile -> group`` table.
* The table rides in as a *scalar-prefetch* operand, so BlockSpec index maps
  can route each M-tile to its group's weight block while the MXU runs dense
  128x128x128 tiles.

Grid: ``(num_m_tiles, num_n_tiles, num_k_tiles)`` — K innermost so a VMEM
fp32 accumulator carries partial sums across K steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _gmm_kernel(tile_group_ref, x_ref, w_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        w_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret"))
def _grouped_matmul_pallas_impl(x: jnp.ndarray, w: jnp.ndarray,
                                tile_group: jnp.ndarray, *,
                                block_m: int = DEFAULT_BM,
                                block_n: int = DEFAULT_BN,
                                block_k: int = DEFAULT_BK,
                                interpret: bool = False) -> jnp.ndarray:
    """out[tile t] = x[tile t] @ w[tile_group[t]].

    Args:
      x: (M, K) with M % block_m == 0; rows pre-packed so that every M-tile
         belongs to a single group.
      w: (G, K, N) per-group weights; K % block_k == 0, N % block_n == 0.
      tile_group: (M // block_m,) int32 group id per M-tile.
    """
    m, kdim = x.shape
    g, _, n = w.shape
    assert m % block_m == 0 and kdim % block_k == 0 and n % block_n == 0
    n_m, n_n, n_k = m // block_m, n // block_n, kdim // block_k

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_m, n_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k, tg: (i, k)),
            # Route the weight block through the prefetched tile->group table.
            pl.BlockSpec((1, block_k, block_n),
                         lambda i, j, k, tg: (tg[i], k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k, tg: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )

    kernel = functools.partial(_gmm_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
        name="_gmm_kernel",
    )(tile_group, x, w)


from repro.kernels import forward_only_pallas

_grouped_matmul_pallas_cv = forward_only_pallas(
    lambda block_m, block_n, block_k, interpret, x, w, tile_group:
        _grouped_matmul_pallas_impl(x, w, tile_group, block_m=block_m,
                                    block_n=block_n, block_k=block_k,
                                    interpret=interpret),
    num_static=4,
    message=(
        "grouped_matmul_pallas is the raw pre-packed Pallas kernel and has "
        "no backward rule. Differentiate through "
        "repro.kernels.grouped_matmul.ops.grouped_matmul, whose custom VJP "
        "runs the backward as two grouped GEMMs over the same tile->group "
        "table, or set REPRO_USE_PALLAS=0 to dispatch the differentiable "
        "XLA path."))


def grouped_matmul_pallas(x: jnp.ndarray, w: jnp.ndarray,
                          tile_group: jnp.ndarray, *,
                          block_m: int = DEFAULT_BM,
                          block_n: int = DEFAULT_BN,
                          block_k: int = DEFAULT_BK,
                          interpret: bool = False) -> jnp.ndarray:
    """Grouped-GEMM Pallas kernel (see :func:`_grouped_matmul_pallas_impl`).

    Forward-only: differentiating this raw entry point raises a clear
    ``NotImplementedError`` naming the differentiable ops-level wrapper and
    the ``REPRO_USE_PALLAS`` fallback env var.
    """
    return _grouped_matmul_pallas_cv(block_m, block_n, block_k, interpret,
                                     x, w, tile_group)
