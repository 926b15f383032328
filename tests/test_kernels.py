"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.

All kernels execute in interpret mode on this CPU container (the kernel
body runs in Python) — the same code lowers to Mosaic on a real TPU.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import ref as attn_ref
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.grouped_matmul import ops as gmm_ops, ref as gmm_ref
from repro.kernels.segment_softmax import ref as ss_ref
from repro.kernels.segment_softmax.segment_softmax import \
    segment_softmax_pallas
from repro.kernels.spmm import ops as spmm_ops, ref as spmm_ref
from repro.kernels.spmm.spmm import spmm_ell_pallas


# --------------------------------------------------------------------- spmm
@pytest.mark.parametrize("reduce", ["sum", "mean", "max", "min"])
@pytest.mark.parametrize("shape", [(8, 3, 16, 128), (16, 7, 50, 256),
                                   (24, 1, 10, 384)])
def test_spmm_ell_kernel_sweep(rng, reduce, shape):
    rows, k, n, f = shape
    ell = rng.integers(-1, n, (rows, k)).astype(np.int32)
    w = rng.standard_normal((rows, k)).astype(np.float32)
    x = rng.standard_normal((n, f)).astype(np.float32)
    use_w = None if reduce in ("max", "min") else jnp.asarray(w)
    a = spmm_ref.spmm_ell(jnp.asarray(ell), use_w, jnp.asarray(x),
                          reduce=reduce)
    b = spmm_ell_pallas(jnp.asarray(ell), use_w, jnp.asarray(x),
                        reduce=reduce, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_spmm_dtypes(rng, dtype):
    ell = rng.integers(-1, 20, (8, 4)).astype(np.int32)
    x = rng.standard_normal((20, 128)).astype(dtype)
    a = spmm_ref.spmm_ell(jnp.asarray(ell), None, jnp.asarray(x))
    b = spmm_ell_pallas(jnp.asarray(ell), None, jnp.asarray(x),
                        interpret=True)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=1e-2,
                               atol=1e-2)


def test_csr_to_ell_roundtrip(rng):
    indptr = np.array([0, 2, 2, 5, 6])
    indices = np.array([1, 3, 0, 2, 4, 5])
    w = rng.standard_normal(6).astype(np.float32)
    x = rng.standard_normal((8, 128)).astype(np.float32)
    ell, ellw = spmm_ops.csr_to_ell(indptr, indices, w)
    a = spmm_ref.spmm_csr(jnp.asarray(indptr), jnp.asarray(indices),
                          jnp.asarray(x), jnp.asarray(w), num_rows=4)
    b = spmm_ops.spmm_ell(jnp.asarray(ell), jnp.asarray(ellw),
                          jnp.asarray(x), force_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b)[:4], rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("reduce", ["sum", "mean", "max", "min"])
def test_spmm_ell_grad_matches_oracle(rng, reduce):
    """The ops-level custom VJP: kernel-path gradients (features AND
    weights) == XLA-oracle gradients for every reduce mode."""
    rows, k, n, f = 16, 5, 23, 128
    ell = jnp.asarray(rng.integers(-1, n, (rows, k)).astype(np.int32))
    w = jnp.asarray(rng.standard_normal((rows, k)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))

    def loss(fn, x_, w_):
        out = fn(ell, w_, x_)
        return (out * jnp.sin(jnp.arange(out.size).reshape(out.shape))).sum()

    kernel = lambda e, w_, x_: spmm_ops.spmm_ell(
        e, w_, x_, reduce=reduce, force_pallas=True, interpret=True)
    oracle = lambda e, w_, x_: spmm_ref.spmm_ell(e, w_, x_, reduce=reduce)
    gk = jax.grad(lambda x_, w_: loss(kernel, x_, w_), argnums=(0, 1))(x, w)
    go = jax.grad(lambda x_, w_: loss(oracle, x_, w_), argnums=(0, 1))(x, w)
    for a, b in zip(gk, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_spmm_ell_grad_row_chunked(rng, monkeypatch):
    """The VJP covers the multi-launch (SMEM row-chunked) forward too."""
    monkeypatch.setattr(spmm_ops, "MAX_PREFETCH_ELEMS", 64)
    rows, k, n, f = 40, 5, 23, 128
    ell = jnp.asarray(rng.integers(-1, n, (rows, k)).astype(np.int32))
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    gk = jax.grad(lambda x_: spmm_ops.spmm_ell(
        ell, None, x_, force_pallas=True, interpret=True).sum())(x)
    go = jax.grad(lambda x_: spmm_ref.spmm_ell(ell, None, x_).sum())(x)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(go), rtol=1e-4,
                               atol=1e-4)


def test_raw_spmm_kernel_grad_raises_actionable(rng):
    """Differentiating the raw Pallas kernel must fail with a clear
    NotImplementedError naming the fallback env var — not an opaque
    'no differentiation rule for pallas_call' trace error."""
    ell = jnp.asarray(rng.integers(-1, 10, (8, 4)).astype(np.int32))
    x = jnp.asarray(rng.standard_normal((10, 128)).astype(np.float32))
    with pytest.raises(NotImplementedError, match="REPRO_USE_PALLAS"):
        jax.grad(lambda x_: spmm_ell_pallas(ell, None, x_,
                                            interpret=True).sum())(x)


# ----------------------------------------------------------- grouped matmul
@pytest.mark.parametrize("g,k,n", [(4, 128, 128), (8, 256, 384),
                                   (3, 100, 72)])
def test_gmm_kernel_sweep(rng, g, k, n):
    sizes = rng.integers(0, 200, g).astype(np.int32)
    sizes[0] = max(sizes[0], 1)
    m = int(sizes.sum())
    x = rng.standard_normal((m, k)).astype(np.float32)
    w = (rng.standard_normal((g, k, n)) * 0.05).astype(np.float32)
    a = gmm_ref.grouped_matmul(jnp.asarray(x), jnp.asarray(w),
                               jnp.asarray(sizes))
    b = gmm_ops.grouped_matmul(jnp.asarray(x), jnp.asarray(w),
                               jnp.asarray(sizes), force_pallas=True,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)


def test_gmm_xla_path_matches(rng):
    sizes = np.array([64, 0, 130], np.int32)
    x = rng.standard_normal((194, 64)).astype(np.float32)
    w = (rng.standard_normal((3, 64, 32)) * 0.1).astype(np.float32)
    a = gmm_ref.grouped_matmul_dense(jnp.asarray(x), jnp.asarray(w),
                                     jnp.asarray(sizes))
    b = gmm_ops.grouped_matmul(jnp.asarray(x), jnp.asarray(w),
                               jnp.asarray(sizes), force_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)


def test_gmm_grad_matches_oracle(rng):
    """The grouped-matmul custom VJP (two grouped GEMMs over the forward
    tile->group table) == oracle gradients, incl. an empty group."""
    sizes = np.array([40, 0, 130], np.int32)
    m = int(sizes.sum())
    x = jnp.asarray(rng.standard_normal((m, 64)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((3, 64, 32)) * 0.1).astype(
        np.float32))

    def loss(fn, x_, w_):
        out = fn(x_, w_)
        return (out * jnp.sin(jnp.arange(out.size).reshape(out.shape))).sum()

    kernel = lambda x_, w_: gmm_ops.grouped_matmul(
        x_, w_, jnp.asarray(sizes), force_pallas=True, interpret=True)
    oracle = lambda x_, w_: gmm_ref.grouped_matmul(x_, w_,
                                                   jnp.asarray(sizes))
    gk = jax.grad(lambda x_, w_: loss(kernel, x_, w_), argnums=(0, 1))(x, w)
    go = jax.grad(lambda x_, w_: loss(oracle, x_, w_), argnums=(0, 1))(x, w)
    for a, b in zip(gk, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-3)


def test_gmm_traced_sizes_fall_back_to_xla(rng):
    """Traced group_sizes can't drive host-side packing: the Pallas branch
    must fall back to the XLA path instead of dying on a tracer->numpy
    conversion."""
    sizes = np.array([12, 20], np.int32)
    x = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((2, 16, 8)) * 0.1).astype(
        np.float32))

    @jax.jit
    def f(x_, w_, sizes_):
        return gmm_ops.grouped_matmul(x_, w_, sizes_, force_pallas=True,
                                      interpret=True)

    got = f(x, w, jnp.asarray(sizes))  # sizes traced: jit argument
    want = gmm_ref.grouped_matmul(x, w, jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


def test_raw_gmm_kernel_grad_raises_actionable(rng):
    sizes = np.array([128, 128], np.int32)
    x = jnp.asarray(rng.standard_normal((256, 128)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((2, 128, 128)) * 0.1).astype(
        np.float32))
    _, tile_group, _, _ = gmm_ops.pack_rows(x, sizes)
    with pytest.raises(NotImplementedError, match="REPRO_USE_PALLAS"):
        jax.grad(lambda x_: gmm_ops.grouped_matmul_pallas(
            x_, w, tile_group, interpret=True).sum())(x)


# ----------------------------------------------------------- fused attention
@pytest.mark.parametrize("shape", [(8, 3, 16, 2, 8), (16, 7, 50, 1, 128),
                                   (24, 5, 30, 4, 16),
                                   # the GAT cell's 4 heads x 128 at K 16
                                   (16, 16, 60, 4, 128),
                                   # a head wider than one lane tile
                                   (16, 5, 30, 2, 200),
                                   (16, 6, 40, 8, 16)])
def test_gat_ell_kernel_sweep(rng, shape):
    """Fused flash-GAT kernel == panel oracle across (R, K, N, H, F)."""
    from repro.kernels.attention import ref as gat_ref
    from repro.kernels.attention.gat_attention import gat_ell_pallas
    rows, k, n, h, f = shape
    ell = rng.integers(-1, n, (rows, k)).astype(np.int32)
    ell[3] = -1  # an all-padding row must come out as a 0 row
    adst = jnp.asarray(rng.standard_normal((rows, h)).astype(np.float32))
    asrc = jnp.asarray(rng.standard_normal((n, h)).astype(np.float32))
    z = jnp.asarray(rng.standard_normal((n, h, f)).astype(np.float32))
    w = jnp.asarray(rng.random((rows, k)).astype(np.float32))
    for w_ in (None, w):
        a = gat_ref.gat_attend_panels(jnp.asarray(ell), adst, w_, asrc, z)
        b = gat_ell_pallas(jnp.asarray(ell), adst, w_, asrc,
                           z.reshape(n, h * f), interpret=True)
        np.testing.assert_allclose(np.asarray(a).reshape(rows, h * f),
                                   np.asarray(b), rtol=1e-5, atol=1e-5)
        assert np.abs(np.asarray(b)[3]).max() == 0.0


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("logit_kind,heads,logit_dim,feat",
                         [("dot", 8, 32, 32), ("add", 4, 1, 16)])
def test_attn_ell_carry_kernel_sweep(rng, logit_kind, heads, logit_dim, feat,
                                     weighted):
    """Raw carry launch == panel oracle: the unnormalised accumulator and the
    per-(row, head) softmax carry ``(m, l)``."""
    from repro.kernels.attention import ref as gat_ref
    from repro.kernels.attention.gat_attention import attn_ell_pallas
    rows, k, n = 16, 6, 40
    ell = rng.integers(-1, n, (rows, k)).astype(np.int32)
    ell[3] = -1  # an all-padding row keeps m = -inf, l = 0, acc = 0
    adst = rng.standard_normal((rows, heads, logit_dim)).astype(np.float32)
    asrc = rng.standard_normal((n, heads, logit_dim)).astype(np.float32)
    z = rng.standard_normal((n, heads, feat)).astype(np.float32)
    prior = (rng.random(heads) + 0.5).astype(np.float32)
    w = jnp.asarray(rng.random((rows, k)).astype(np.float32)) if weighted \
        else None
    m_ref, l_ref, acc_ref = gat_ref.attn_carry_panels(
        jnp.asarray(ell), jnp.asarray(adst), w, jnp.asarray(asrc),
        jnp.asarray(z), logit_kind=logit_kind, prior=jnp.asarray(prior))
    acc, m, l = attn_ell_pallas(
        jnp.asarray(ell), jnp.asarray(adst.reshape(rows, -1)), w,
        jnp.asarray(prior[None]), jnp.asarray(asrc.reshape(n, -1)),
        jnp.asarray(z.reshape(n, -1)), logit_kind=logit_kind, interpret=True)
    for got, want in ((acc, np.asarray(acc_ref).reshape(rows, -1)),
                      (m, m_ref), (l, l_ref)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(m)[3] == -np.inf)
    assert np.all(np.asarray(l)[3] == 0.0)
    assert np.abs(np.asarray(acc)[3]).max() == 0.0


def test_gat_ell_grad_matches_oracle(rng):
    """The ops-level custom VJP: kernel-path gradients (alphas, weights AND
    features) == panel-oracle gradients."""
    from repro.kernels.attention import ops as attn_ops, ref as gat_ref
    rows, k, n, h, f = 16, 5, 23, 2, 16
    ell = jnp.asarray(rng.integers(-1, n, (rows, k)).astype(np.int32))
    adst = jnp.asarray(rng.standard_normal((rows, h)).astype(np.float32))
    asrc = jnp.asarray(rng.standard_normal((n, h)).astype(np.float32))
    z = jnp.asarray(rng.standard_normal((n, h, f)).astype(np.float32))
    w = jnp.asarray(rng.random((rows, k)).astype(np.float32))

    def loss(fn, adst_, w_, asrc_, z_):
        out = fn(adst_, w_, asrc_, z_)
        return (out * jnp.sin(jnp.arange(out.size).reshape(out.shape))).sum()

    kernel = lambda a_, w_, s_, z_: attn_ops._gat_ell_pallas_diff(
        0.2, True, ell, a_, w_, s_, z_)
    oracle = lambda a_, w_, s_, z_: gat_ref.gat_attend_panels(
        ell, a_, w_, s_, z_, negative_slope=0.2)
    gk = jax.grad(functools.partial(loss, kernel),
                  argnums=(0, 1, 2, 3))(adst, w, asrc, z)
    go = jax.grad(functools.partial(loss, oracle),
                  argnums=(0, 1, 2, 3))(adst, w, asrc, z)
    for a, b in zip(gk, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_gat_ell_grad_row_chunked(rng, monkeypatch):
    """The VJP covers the multi-launch (SMEM row-chunked) forward too."""
    from repro.kernels.attention import ops as attn_ops, ref as gat_ref
    monkeypatch.setattr(attn_ops, "MAX_PREFETCH_ELEMS", 64)
    rows, k, n, h, f = 40, 5, 23, 2, 16
    ell = jnp.asarray(rng.integers(-1, n, (rows, k)).astype(np.int32))
    adst = jnp.asarray(rng.standard_normal((rows, h)).astype(np.float32))
    asrc = jnp.asarray(rng.standard_normal((n, h)).astype(np.float32))
    z = jnp.asarray(rng.standard_normal((n, h, f)).astype(np.float32))
    gk = jax.grad(lambda z_: attn_ops._gat_ell_pallas_diff(
        0.2, True, ell, adst, None, asrc, z_).sum())(z)
    go = jax.grad(lambda z_: gat_ref.gat_attend_panels(
        ell, adst, None, asrc, z_).sum())(z)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(go), rtol=1e-4,
                               atol=1e-4)


def test_raw_gat_kernel_grad_raises_actionable(rng):
    from repro.kernels.attention.gat_attention import gat_ell_pallas
    ell = jnp.asarray(rng.integers(-1, 10, (8, 4)).astype(np.int32))
    adst = jnp.asarray(rng.standard_normal((8, 2)).astype(np.float32))
    asrc = jnp.asarray(rng.standard_normal((10, 2)).astype(np.float32))
    z2d = jnp.asarray(rng.standard_normal((10, 16)).astype(np.float32))
    with pytest.raises(NotImplementedError, match="REPRO_USE_PALLAS"):
        jax.grad(lambda z_: gat_ell_pallas(ell, adst, None, asrc, z_,
                                           interpret=True).sum())(z2d)


# ----------------------------------------------------------- segment softmax
@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_segment_softmax_property(seed):
    """Each segment's outputs sum to 1 (where the segment is non-empty)."""
    r = np.random.default_rng(seed)
    rows, k = 16, int(r.integers(2, 20))
    vals = r.standard_normal((rows, k)).astype(np.float32)
    mask = r.random((rows, k)) > 0.4
    out = np.asarray(segment_softmax_pallas(
        jnp.asarray(vals), jnp.asarray(mask), interpret=True))
    ref = np.asarray(ss_ref.segment_softmax_ell(jnp.asarray(vals),
                                                jnp.asarray(mask)))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    sums = (out * mask).sum(1)
    nonempty = mask.any(1)
    np.testing.assert_allclose(sums[nonempty], 1.0, rtol=1e-4)
    assert (np.abs(out[~mask]) < 1e-7).all()


def test_segment_softmax_pads_odd_panel_heights(rng):
    """Regression: R % block_rows != 0 used to hard-assert; now the panel
    is capacity-padded (masked) to the block multiple and sliced back."""
    vals = jnp.asarray(rng.standard_normal((10, 16)).astype(np.float32))
    mask = jnp.asarray(rng.random((10, 16)) < 0.7)
    out = segment_softmax_pallas(vals, mask, interpret=True)
    ref = ss_ref.segment_softmax_ell(vals, mask)
    assert out.shape == (10, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-6)


def test_segment_softmax_ell_ops_grad_matches_oracle(rng):
    """The ops-level padded-panel entry differentiates on the Pallas branch
    (custom VJP over the same panels) and matches the oracle gradient."""
    from repro.kernels.segment_softmax import ops as ss_ops
    vals = jnp.asarray(rng.standard_normal((12, 8)).astype(np.float32))
    mask = jnp.asarray(rng.random((12, 8)) < 0.7)
    gk = jax.grad(lambda v: (ss_ops.segment_softmax_ell(
        v, mask, force_pallas=True, interpret=True) ** 2).sum())(vals)
    go = jax.grad(lambda v: (ss_ref.segment_softmax_ell(
        v, mask) ** 2).sum())(vals)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(go), rtol=1e-4,
                               atol=1e-5)


def test_raw_segment_softmax_grad_raises_actionable(rng):
    vals = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
    mask = jnp.ones((8, 8), bool)
    with pytest.raises(NotImplementedError, match="REPRO_USE_PALLAS"):
        jax.grad(lambda v: segment_softmax_pallas(
            v, mask, interpret=True).sum())(vals)


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,s,h,d,causal", [
    (1, 128, 2, 64, True), (2, 256, 4, 64, True), (2, 128, 2, 128, False),
    (1, 384, 8, 32, True)])
def test_flash_attention_sweep(rng, b, s, h, d, causal):
    q = rng.standard_normal((b, s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, h, d)).astype(np.float32)
    v = rng.standard_normal((b, s, h, d)).astype(np.float32)
    a = attn_ref.mha_reference(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=causal)
    out = flash_attention_pallas(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), causal=causal,
                                 block_q=128, block_kv=128, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(out), rtol=2e-4,
                               atol=2e-4)


def test_raw_flash_attention_grad_raises_actionable(rng):
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 16)).astype(np.float32))
    with pytest.raises(NotImplementedError, match="REPRO_USE_PALLAS"):
        jax.grad(lambda q_: flash_attention_pallas(
            q_, q, q, causal=True, interpret=True).sum())(q)


def test_flash_attention_bf16(rng):
    b, s, h, d = 1, 128, 2, 64
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)),
                             jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    a = attn_ref.mha_reference(q, k, v, causal=True)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(out, np.float32), rtol=3e-2,
                               atol=3e-2)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_triangular_attention_property(seed):
    """Diagonal-banded causal schedule == reference, at ~half the FLOPs."""
    r = np.random.default_rng(seed)
    b, s = int(r.integers(1, 3)), int(r.integers(20, 400))
    hkv = int(r.choice([1, 2]))
    h = hkv * int(r.choice([1, 2]))
    d = int(r.choice([16, 32]))
    q = r.standard_normal((b, s, h, d)).astype(np.float32)
    k = r.standard_normal((b, s, hkv, d)).astype(np.float32)
    v = r.standard_normal((b, s, hkv, d)).astype(np.float32)
    a = attn_ref.mha_reference(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=True)
    t = attn_ref.mha_chunked_causal(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), block=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(t), rtol=3e-4,
                               atol=3e-4)


def test_triangular_attention_halves_flops():
    from repro.launch import jaxpr_stats
    q = jax.ShapeDtypeStruct((1, 4096, 2, 64), jnp.float32)
    rect = jaxpr_stats.step_stats(
        lambda q, k, v: attn_ref.mha_chunked(q, k, v, causal=True,
                                             block_q=512, block_kv=512),
        q, q, q)["dot_flops"]
    tri = jaxpr_stats.step_stats(
        lambda q, k, v: attn_ref.mha_chunked_causal(q, k, v, block=512),
        q, q, q)["dot_flops"]
    n = 8
    assert abs(tri / rect - (n + 1) / (2 * n)) < 0.01


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_chunked_attention_property(seed):
    """Double-blocked chunked attention == reference for random GQA shapes."""
    r = np.random.default_rng(seed)
    b = int(r.integers(1, 3))
    s = int(r.integers(10, 300))
    hkv = int(r.choice([1, 2]))
    h = hkv * int(r.choice([1, 2, 4]))
    d = int(r.choice([16, 32]))
    q = r.standard_normal((b, s, h, d)).astype(np.float32)
    k = r.standard_normal((b, s, hkv, d)).astype(np.float32)
    v = r.standard_normal((b, s, hkv, d)).astype(np.float32)
    a = attn_ref.mha_reference(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=True)
    c = attn_ref.mha_chunked(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             causal=True, block_q=64, block_kv=96)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=3e-4,
                               atol=3e-4)
