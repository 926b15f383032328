"""Main-path kernels compiled for a described TPU v5e chip (``v5e:2x2``).

Nothing runs here: each test lowers one Pallas kernel at real widths and
compiles it with the TPU compiler for a chip that is described, not
attached. The compiler refuses what the chip would refuse and interpret
mode hides: primitives Mosaic cannot lower, blocks that break the
(8, 128) tiling rule, SMEM or VMEM over capacity.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file. The persistent compile cache is off around
these compiles (an entry written for a described chip cannot be read back).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.attention.gat_attention import (attn_ell_pallas,
                                                   gat_ell_pallas)
from repro.kernels.budgets import MAX_PREFETCH_ELEMS, ell_chunk_rows
from repro.kernels.grouped_matmul.grouped_matmul import grouped_matmul_pallas
from repro.kernels.spmm.spmm import spmm_ell_pallas

ROWS, K = 1024, 16     # one ELL launch: 1024 receiver rows x 16 slots
NODES = 50_000         # rows of the HBM feature table the kernels gather


@pytest.fixture(scope="module")
def chip():
    """A ``SingleDeviceSharding`` on one described v5e chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # libtpu would otherwise write its logs outside the checkout
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield jax.sharding.SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


def _compile(chip, fn, *shapes, kernel: str) -> str:
    """Compile ``fn`` for the described chip; return the optimized HLO."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, f"{kernel}: no Mosaic kernel in the HLO"
    return hlo


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("reduce", ["sum", "mean", "max"])
@pytest.mark.parametrize("feat", [100, 256])
def test_spmm_ell_compiles(chip, feat, reduce, weighted):
    """SAGE/GCN/GIN aggregation at ogbn-products' input (100) and hidden
    (256) widths; 100 exercises the lane padding to 128."""
    def fn(idx, w, x):
        return spmm_ell_pallas(idx, w if weighted else None, x, reduce=reduce)

    _compile(chip, fn, ((ROWS, K), jnp.int32), ((ROWS, K), jnp.float32),
             ((NODES, feat), jnp.float32), kernel="_spmm_ell_kernel")


@pytest.mark.parametrize("heads,feat", [(4, 64), (1, 256)])
def test_gat_ell_compiles(chip, heads, feat):
    """Additive (GAT) attention: F=64 is narrower than one lane tile."""
    def fn(idx, adst, asrc, z):
        return gat_ell_pallas(idx, adst, None, asrc, z)

    _compile(chip, fn, ((ROWS, K), jnp.int32), ((ROWS, heads), jnp.float32),
             ((NODES, heads), jnp.float32),
             ((NODES, heads * feat), jnp.float32), kernel="_gat_ell_kernel")


@pytest.mark.parametrize("heads,head_dim", [(8, 32), (2, 32)])
def test_attn_dot_carry_compiles(chip, heads, head_dim):
    """HGT's dot-product carry launch at the paper's hidden 256 / 8 heads
    and upstream PyG's HGT example's hidden 64 / 2 heads."""
    hd = heads * head_dim

    def fn(idx, q, prior, k, v):
        return attn_ell_pallas(idx, q, None, prior, k, v, logit_kind="dot")

    _compile(chip, fn, ((ROWS, K), jnp.int32), ((ROWS, hd), jnp.float32),
             ((1, heads), jnp.float32), ((NODES, hd), jnp.float32),
             ((NODES, hd), jnp.float32), kernel="_attn_ell_kernel")


def test_gmm_compiles(chip):
    """Grouped GEMM in f32: 4096 packed rows over 4 groups of 256x256."""
    def fn(x, w, tile_group):
        return grouped_matmul_pallas(x, w, tile_group)

    _compile(chip, fn, ((4096, 256), jnp.float32),
             ((4, 256, 256), jnp.float32), ((4096 // 128,), jnp.int32),
             kernel="_gmm_kernel")


def test_largest_smem_chunk_compiles(chip):
    """The largest launch ``ell_chunk_rows`` allows: a full
    ``MAX_PREFETCH_ELEMS`` neighbor table in SMEM."""
    rows = ell_chunk_rows(4)
    assert rows * 4 == MAX_PREFETCH_ELEMS

    def fn(idx, x):
        return spmm_ell_pallas(idx, None, x, reduce="sum")

    _compile(chip, fn, ((rows, 4), jnp.int32), ((NODES, 256), jnp.float32),
             kernel="_spmm_ell_kernel")
