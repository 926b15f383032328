"""Hardware budget constants + static per-launch memory accounting.

The single source of truth for the SMEM/VMEM assumptions the Pallas kernels
bake into their grids (previously duplicated across ``kernels/spmm/ops.py``
and ``kernels/attention/ops.py``). Two layers:

  * constants — prefetch-table cap, default block shapes, declared per-core
    SMEM/VMEM budgets, double-buffer depth;
  * accounting — pure-Python cost models of one kernel launch
    (``ell_launch_usage`` / ``gat_launch_usage`` / ``gmm_launch_usage``) and
    the pack-time validators (``check_ell_rung`` / ``check_ell_layout`` /
    ``check_gat_bucket``) that raise :class:`BudgetError` *before* a layout
    that cannot launch reaches a kernel — on the loader's producer thread,
    not inside a trace.

``analysis.budgets`` builds its headroom reports on top of these models;
keeping them here (below the kernels) avoids a kernels -> analysis import
cycle. Everything is host-side numpy/ints: safe to call from packers.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

# --------------------------------------------------------------- constants
# The neighbor table rides scalar prefetch into SMEM, flattened to 1-D (a
# 2-D SMEM table pads its minor dim to 128 words). Bound the per-launch
# table and chunk the row dimension above it. 64k int32 = 256 KB per
# launch; shapes are host-known so the chunk loop is a static Python loop
# (one pallas_call per chunk, shared compiled kernel across equal-shaped
# chunks).
MAX_PREFETCH_ELEMS = 64 * 1024

# Declared per-core budgets, set from compiles of the kernels for a
# described TPU v5e ("TPU v5 lite", jax.experimental.topologies, v5e:2x2):
#   * SMEM is 1 MiB: a 64k-int32 prefetch table compiles, a 256k-int32 one
#     is refused ("Used 1.00M of 1.00M smem").
#   * VMEM is 128 MiB: a 129 MiB scratch is refused ("would exceed memory
#     (size=134217728)"). The kernels keep to a 16 MiB per-launch budget
#     (block + double-buffered gather + panels), far below it.
# CPU interpret runs enforce the same budgets the chip would.
SMEM_BYTES_PER_CORE = 1024 * 1024
VMEM_BYTES_PER_CORE = 16 * 1024 * 1024

# Default kernel block shapes: BR rows per grid step, BF feature lanes.
DEFAULT_BR = 8
DEFAULT_BF = 128
# Grouped-matmul MXU tiles (M, N, K).
GMM_BLOCK = (128, 128, 128)
# Gather scratch double-buffering depth (DMA slot count) in the ELL kernels.
DOUBLE_BUFFER_SLOTS = 2

_I32 = 4  # prefetch tables are int32


class BudgetError(ValueError):
    """A static layout/tiling exceeds a declared per-core memory budget.

    Subclasses ``ValueError`` so existing "bad layout" handling keeps
    working; raised at pack time (host side) with an actionable message —
    which rung/grid is over, by how much, and what to shrink.
    """


# -------------------------------------------------------------- accounting
def ell_chunk_rows(k: int, block_rows: int = DEFAULT_BR,
                   max_prefetch: int = MAX_PREFETCH_ELEMS) -> int:
    """Rows per launch after SMEM chunking (the ops-layer chunk rule)."""
    chunk = max(max_prefetch // max(k, 1), block_rows)
    return chunk - chunk % block_rows


def lane_tiles(width: int) -> int:
    """``width`` rounded up to whole 128-lane tiles (the kernels zero-pad
    gathered rows to this width)."""
    return -(-width // DEFAULT_BF) * DEFAULT_BF


def ell_launch_usage(rows: int, k: int, feat: int, *,
                     block_rows: int = DEFAULT_BR,
                     block_feat: int = DEFAULT_BF,
                     dtype_bytes: int = 4,
                     weighted: bool = False) -> Dict[str, int]:
    """Static SMEM/VMEM bytes of one (chunked) SpMM ELL launch."""
    launch_rows = min(rows, ell_chunk_rows(k, block_rows))
    bf = min(block_feat, lane_tiles(feat))
    smem = launch_rows * k * _I32                      # prefetched table
    vmem = (DOUBLE_BUFFER_SLOTS * block_rows * bf * dtype_bytes  # gather buf
            + block_rows * bf * dtype_bytes)                     # out block
    if weighted:
        vmem += block_rows * k * dtype_bytes                     # weights
    return {"smem_bytes": smem, "vmem_bytes": vmem,
            "launch_rows": launch_rows, "block_feat": bf}


def attn_launch_usage(rows: int, k: int, heads: int, feat: int, *,
                      logit_dim: int = 1,
                      block_rows: int = DEFAULT_BR,
                      block_feat: int = DEFAULT_BF,
                      dtype_bytes: int = 4,
                      weighted: bool = False,
                      carry: bool = False) -> Dict[str, int]:
    """Static SMEM/VMEM bytes of one (chunked) typed-attention launch.

    ``logit_dim`` is the per-head width of the attention operands: 1 for
    additive GAT logits, the head dim D for HGT's dot-product K/Q.  A typed
    launch additionally stages a ``(1, heads)`` prior row in VMEM, and a
    carry launch (``return_carry=True``) keeps the running ``(m, l)``
    softmax statistics as extra per-head output blocks.
    """
    launch_rows = min(rows, ell_chunk_rows(k, block_rows))
    bf = min(block_feat, lane_tiles(feat))
    al = lane_tiles(heads * logit_dim)
    smem = launch_rows * k * _I32
    vmem = (DOUBLE_BUFFER_SLOTS * block_rows * bf * dtype_bytes   # z gather
            + DOUBLE_BUFFER_SLOTS * block_rows * al * dtype_bytes  # alpha
            + block_rows * bf * dtype_bytes                       # out block
            + block_rows * al * dtype_bytes)                      # adst block
    if weighted:
        vmem += block_rows * k * dtype_bytes
    typed = logit_dim > 1 or carry
    if typed:
        vmem += heads * dtype_bytes                    # (1, H) prior row
    if carry:
        vmem += 2 * block_rows * dtype_bytes           # (BR, 1) m + l blocks
    return {"smem_bytes": smem, "vmem_bytes": vmem,
            "launch_rows": launch_rows, "block_feat": bf}


def gat_launch_usage(rows: int, k: int, heads: int, feat: int, *,
                     block_rows: int = DEFAULT_BR,
                     block_feat: int = DEFAULT_BF,
                     dtype_bytes: int = 4,
                     weighted: bool = False) -> Dict[str, int]:
    """Static SMEM/VMEM bytes of one (chunked) flash-GAT launch."""
    return attn_launch_usage(rows, k, heads, feat, logit_dim=1,
                             block_rows=block_rows, block_feat=block_feat,
                             dtype_bytes=dtype_bytes, weighted=weighted,
                             carry=False)


def gmm_launch_usage(k_dim: int, *, block: Tuple[int, int, int] = GMM_BLOCK,
                     dtype_bytes: int = 4) -> Dict[str, int]:
    """Static VMEM bytes of one grouped-matmul grid step (x/w/acc tiles)."""
    bm, bn, bk = block
    vmem = (bm * bk + bk * bn) * dtype_bytes + bm * bn * 4  # acc is f32
    return {"smem_bytes": 0, "vmem_bytes": vmem, "k_dim": k_dim}


# --------------------------------------------------------------- validators
def check_ell_rung(k: int, *, block_rows: int = DEFAULT_BR,
                   context: str = "ELL layout") -> None:
    """Reject a K rung whose *minimum* launch cannot fit the budgets.

    The chunker floors at one ``block_rows`` row block per launch, so a rung
    with ``block_rows * K`` table elements above ``MAX_PREFETCH_ELEMS`` (or
    its bytes above SMEM) can never be split small enough — fail at pack
    time instead of OOMing a launch.
    """
    min_table = block_rows * k
    if min_table > MAX_PREFETCH_ELEMS:
        raise BudgetError(
            f"{context}: K={k} rung needs a {min_table}-element prefetch "
            f"table even at one {block_rows}-row block per launch, over the "
            f"MAX_PREFETCH_ELEMS={MAX_PREFETCH_ELEMS} SMEM cap "
            f"(max K at block_rows={block_rows} is "
            f"{MAX_PREFETCH_ELEMS // block_rows}). Lower the degree bound "
            f"(sampler fanout) or split the range across buckets.")
    if min_table * _I32 > SMEM_BYTES_PER_CORE:
        raise BudgetError(
            f"{context}: K={k} rung's minimum prefetch table is "
            f"{min_table * _I32} bytes, over the per-core SMEM budget of "
            f"{SMEM_BYTES_PER_CORE} bytes. Lower the degree bound or "
            f"shrink block_rows.")


def check_ell_layout(layout: Sequence[Tuple[np.ndarray, int]], *,
                     block_rows: int = DEFAULT_BR,
                     feat: int = DEFAULT_BF,
                     context: str = "ELL layout") -> None:
    """Validate every rung of a static bucket layout against the budgets."""
    for rows, k in layout:
        check_ell_rung(int(k), block_rows=block_rows,
                       context=f"{context} (bucket of {len(rows)} rows)")
        usage = ell_launch_usage(len(rows), int(k), feat,
                                 block_rows=block_rows)
        if usage["vmem_bytes"] > VMEM_BYTES_PER_CORE:
            raise BudgetError(
                f"{context}: K={k} bucket needs {usage['vmem_bytes']} VMEM "
                f"bytes per launch, over the per-core budget of "
                f"{VMEM_BYTES_PER_CORE}. Shrink block_feat or block_rows.")


def check_attn_bucket(rows: int, k: int, heads: int, feat: int, *,
                      logit_dim: int = 1,
                      block_rows: int = DEFAULT_BR,
                      weighted: bool = False,
                      carry: bool = False) -> None:
    """Validate one typed-attention bucket's grid against the budgets.

    Covers the full typed launch shape — ``logit_dim``-wide alpha gathers,
    the ``(1, heads)`` prior row, and the ``(m, l)`` carry output buffers —
    so an unservable rung fails here (pack/trace time, host side), not when
    a launch finally OOMs.
    """
    context = ("typed-attention bucket" if (logit_dim > 1 or carry)
               else "flash-GAT bucket")
    check_ell_rung(k, block_rows=block_rows, context=context)
    usage = attn_launch_usage(rows, k, heads, feat, logit_dim=logit_dim,
                              block_rows=block_rows, weighted=weighted,
                              carry=carry)
    if usage["vmem_bytes"] > VMEM_BYTES_PER_CORE:
        raise BudgetError(
            f"{context} (rows={rows}, K={k}, heads={heads}, feat={feat}, "
            f"logit_dim={logit_dim}, carry={carry}): "
            f"{usage['vmem_bytes']} VMEM bytes per launch exceeds the "
            f"per-core budget of {VMEM_BYTES_PER_CORE}. Shrink the feature "
            f"block, head count, or per-head logit width per launch.")


def check_gat_bucket(rows: int, k: int, heads: int, feat: int, *,
                     block_rows: int = DEFAULT_BR,
                     weighted: bool = False) -> None:
    """Validate one flash-GAT bucket's grid against the budgets."""
    check_attn_bucket(rows, k, heads, feat, logit_dim=1,
                      block_rows=block_rows, weighted=weighted, carry=False)


def check_attn_layout(layout: Sequence[Tuple[np.ndarray, int]], *,
                      heads: int, feat: int, logit_dim: int,
                      block_rows: int = DEFAULT_BR,
                      weighted: bool = False,
                      carry: bool = True,
                      context: str = "typed-attention layout") -> None:
    """Pack-time validation of a static bucket layout for typed attention.

    Like :func:`check_ell_layout` but accounting the attention launch shape
    (prior row + carry buffers) per rung, so a layout that would only die
    inside an HGT launch is rejected when it is packed.
    """
    for rows, k in layout:
        try:
            check_attn_bucket(len(rows), int(k), heads, feat,
                              logit_dim=logit_dim, block_rows=block_rows,
                              weighted=weighted, carry=carry)
        except BudgetError as exc:
            raise BudgetError(f"{context}: {exc}") from None
