"""jaxpr FLOP counter + HLO collective parser (roofline instrumentation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_analysis, jaxpr_stats


def test_dot_flops_exact():
    def f(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    stats = jaxpr_stats.step_stats(f, a, b)
    assert stats["dot_flops"] == 2 * 32 * 64 * 128


def test_scan_multiplies_trip_count():
    def f(x):
        def body(c, _):
            return c @ c, None

        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    stats = jaxpr_stats.step_stats(f, x)
    assert stats["dot_flops"] == 7 * 2 * 16 * 16 * 16


def test_nested_scan_and_remat():
    def inner(x):
        def body(c, _):
            return c @ c, None

        return jax.lax.scan(body, x, None, length=3)[0]

    def f(x):
        def body(c, _):
            return jax.checkpoint(inner)(c), None

        return jax.lax.scan(body, x, None, length=5)[0]

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    stats = jaxpr_stats.step_stats(f, x)
    assert stats["dot_flops"] == 5 * 3 * 2 * 8 * 8 * 8


def test_grad_counts_fwd_and_bwd():
    def f(w, x):
        return ((x @ w) ** 2).sum()

    w = jax.ShapeDtypeStruct((16, 24), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    fwd = jaxpr_stats.step_stats(f, w, x)["dot_flops"]
    both = jaxpr_stats.step_stats(jax.grad(f, argnums=(0, 1)), w, x)[
        "dot_flops"]
    assert both >= 2.9 * fwd  # fwd + dW + dX matmuls


def test_batched_dot_general():
    def f(a, b):
        return jnp.einsum("bik,bkj->bij", a, b)

    a = jax.ShapeDtypeStruct((4, 8, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
    stats = jaxpr_stats.step_stats(f, a, b)
    assert stats["dot_flops"] == 4 * 2 * 8 * 16 * 32


def test_pallas_spmm_cost_exact():
    """pallas_call eqns are costed by the per-kernel analytic model (the
    kernel body is opaque to the generic eqn walk)."""
    from repro.kernels.budgets import lane_tiles
    from repro.kernels.spmm import ops as spmm_ops

    rng = np.random.default_rng(3)
    n, f = 64, 32
    indptr = np.arange(n + 1) * 4
    indices = rng.integers(0, n, 4 * n).astype(np.int32)
    ell_idx, _ = spmm_ops.csr_to_ell(indptr, indices)

    def fwd(x):
        return spmm_ops.spmm_ell(jnp.asarray(ell_idx), None, x,
                                 force_pallas=True, interpret=True)

    x = jax.ShapeDtypeStruct((n, f), jnp.float32)
    stats = jaxpr_stats.step_stats(fwd, x)
    r, k = ell_idx.shape
    # the launch runs at the lane-padded width: F zero-padded to 128 lanes
    assert stats["pallas_flops"] == 2 * r * k * lane_tiles(f)
    assert stats["pallas_flops"] <= stats["total_flops"]  # + glue eltwise
    assert stats["major_bytes"] >= r * k * 4  # at least the prefetch table


def test_pallas_cost_generic_fallback():
    """An unknown kernel name still contributes (out-elems, io-bytes)."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def fwd(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((8, 16), jnp.float32),
            interpret=True)(x)

    stats = jaxpr_stats.step_stats(
        fwd, jax.ShapeDtypeStruct((8, 16), jnp.float32))
    assert stats["pallas_flops"] == 8 * 16
    assert stats["major_bytes"] == 2 * 8 * 16 * 4


@pytest.mark.parametrize("interpret", [True, False])
def test_audit_names_launches_and_counts_interpret(interpret):
    """Launches are keyed by ``name=``, else by the kernel function's name,
    and interpret-mode launches are counted (a chip run must show 0)."""
    from jax.experimental import pallas as pl

    from repro.analysis import audit_jaxpr

    def double_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def fwd(x):
        out = jax.ShapeDtypeStruct((8, 128), jnp.float32)
        named = pl.pallas_call(double_kernel, out_shape=out,
                               interpret=interpret, name="_named_kernel")(x)
        return pl.pallas_call(double_kernel, out_shape=out,
                              interpret=interpret)(named)

    report = audit_jaxpr(jax.make_jaxpr(fwd)(
        jax.ShapeDtypeStruct((8, 128), jnp.float32)))
    assert report.kernel_launches == {"_named_kernel": 1, "double_kernel": 1}
    assert report.interpret_launches == (2 if interpret else 0)


def test_chip_peaks_unknown_kind_raises():
    from repro.launch.mesh import chip_peaks

    assert chip_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peak table"):
        chip_peaks("cpu")


SAMPLE_HLO = """
HloModule test

%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %x = f32[8] get-tuple-element(%p), index=1
  %ar = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8]) tuple(%i, %ar)
}

ENTRY %main (a: f32[128,64]) -> f32[128,64] {
  %a = f32[128,64] parameter(0)
  %ag = f32[128,64]{1,0} all-gather(f32[128,16]{1,0} %a), dimensions={1}
  %init = (s32[], f32[8]) tuple(s32[] constant(0), f32[8] constant(0))
  %w = (s32[], f32[8]) while(%init), condition=%cond, body=%body
  ROOT %r = f32[128,64] copy(%ag)
}
"""


def test_hlo_collectives_with_trip_counts():
    stats = hlo_analysis.collective_stats(SAMPLE_HLO)
    # all-gather in entry: once, operand f32[128,16] = 8192 B
    assert stats["all-gather"]["count"] == 1
    assert stats["all-gather"]["bytes"] == 128 * 16 * 4
    # all-reduce inside the while body: 12 executions x 32 B
    assert stats["all-reduce"]["count"] == 12
    assert stats["all-reduce"]["bytes"] == 12 * 8 * 4


def test_sharding_specs_divisible_for_all_archs():
    """Every param spec must divide evenly on the production meshes."""
    from jax.sharding import AbstractMesh
    from repro.configs import ARCH_IDS, get_config
    from repro.distributed import sharding as shard_lib
    from repro.launch import specs as specs_lib

    for mesh_shape, axes in (((16, 16), ("data", "model")),
                             ((2, 16, 16), ("pod", "data", "model"))):
        mesh = AbstractMesh(mesh_shape, axes)
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            params = specs_lib.abstract_params(cfg)
            for profile in ("2d", "fsdp"):
                flat = jax.tree_util.tree_flatten_with_path(params)[0]
                for path, leaf in flat:
                    spec = shard_lib.param_spec(path, leaf, mesh, profile)
                    for dim, ax in enumerate(spec):
                        if ax is None:
                            continue
                        n = shard_lib._axis_size(mesh, ax)
                        assert leaf.shape[dim] % n == 0, (
                            arch, profile, path, leaf.shape, spec)
