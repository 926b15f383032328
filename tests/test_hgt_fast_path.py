"""Typed-attention fast path: HGT rides the generalised flash kernel (PR 9).

The acceptance chain for the typed-attention tentpole:

    loader-prefilled hetero batch
      -> jit'd HGT value_and_grad train step, Pallas dispatch on
        -> ONE grouped matmul for all K/Q/V projections (3·|T| groups)
        -> one carry-mode `_attn_ell_kernel` launch per relation
           (scaled dot logits x the typed prior mu[rel])
        -> per-destination-type `merge_carries`: the cross-type softmax
           over ALL incoming edges, no cross-relation materialisation
      == COO-oracle AND hand-rolled dense-softmax outputs/grads,
         ONE trace across batches

plus the merged `return_attention` round trip (alphas sum to 1 *across*
relations), hetero layer trimming keeping seed outputs, the carry
merge/finalize unit contract, and the regression that GAT's additive path
stayed bit-identical through the refactor.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.edge_index import EdgeIndex
from repro.core.hetero import HGTConv, hgt
from repro.core.trim import trim_to_layer_hetero
from repro.data.data import HeteroData
from repro.data.hetero_sampler import HeteroNeighborLoader
from repro.kernels.attention import ops as attn_ops
from repro.kernels.attention import ref as attn_ref

ET_UB = ("user", "buys", "item")
ET_RU = ("item", "rev_buys", "user")
FANOUTS = {ET_UB: [3, 2], ET_RU: [3, 2]}


def _spy(monkeypatch, module, name):
    calls = []
    real = getattr(module, name)
    monkeypatch.setattr(module, name,
                        lambda *a, **k: (calls.append(1), real(*a, **k))[1])
    return calls


def _hetero_inputs(rng, n_user=30, n_item=40, e=180, feat=12):
    x = {"user": jnp.asarray(rng.standard_normal((n_user, feat)),
                             jnp.float32),
         "item": jnp.asarray(rng.standard_normal((n_item, feat)),
                             jnp.float32)}
    ub = np.stack([rng.integers(0, n_user, e).astype(np.int32),
                   rng.integers(0, n_item, e).astype(np.int32)])
    edges = {ET_UB: ub, ET_RU: ub[::-1]}
    nn = {"user": n_user, "item": n_item}
    return x, edges, nn


def _cached_ei(edges, nn):
    out = {}
    for (src_t, _, dst_t), arr in edges.items():
        ei = EdgeIndex.from_coo(arr[0], arr[1], nn[src_t], nn[dst_t])
        out[(src_t, _, dst_t)] = ei.fill_cache()
    return out


def _raw_ei(edges, nn):
    return {et: EdgeIndex(jnp.asarray(np.ascontiguousarray(arr)),
                          nn[et[0]], nn[et[2]])
            for et, arr in edges.items()}


def _dense_hgt(conv, params, x_dict, edges, nn, edge_mask=None):
    """Hand-rolled materialised HGT forward: per-node cross-type softmax
    over the explicit (E, H) logits of the union of relations."""
    T = len(conv.node_types)
    H, D = conv.heads, conv.head_dim
    ti = {t: i for i, t in enumerate(conv.node_types)}
    k, q, v = {}, {}, {}
    for t, x in x_dict.items():
        k[t] = (x @ params["w_kqv"][ti[t]]
                + params["b_kqv"][ti[t]]).reshape(-1, H, D)
        q[t] = (x @ params["w_kqv"][T + ti[t]]
                + params["b_kqv"][T + ti[t]]).reshape(-1, H, D)
        v[t] = (x @ params["w_kqv"][2 * T + ti[t]]
                + params["b_kqv"][2 * T + ti[t]]).reshape(-1, H, D)
    scale = float(D) ** -0.5
    per_dst = {}
    for r, et in enumerate(conv.edge_types):
        if et not in edges:
            continue
        src_t, _, dst_t = et
        src, dst = jnp.asarray(edges[et][0]), jnp.asarray(edges[et][1])
        k_rel = jnp.einsum("nhd,hde->nhe", k[src_t], params["a_rel"][r])
        v_rel = jnp.einsum("nhd,hde->nhe", v[src_t], params["m_rel"][r])
        logits = ((k_rel[src] * q[dst_t][dst]).sum(-1) * scale
                  * params["mu"][r][None, :])
        w = (None if edge_mask is None else edge_mask.get(et))
        per_dst.setdefault(dst_t, []).append((logits, dst, v_rel[src], w))
    out = {}
    for t, chunks in per_dst.items():
        logits = jnp.concatenate([c[0] for c in chunks])
        dst = jnp.concatenate([c[1] for c in chunks])
        msg = jnp.concatenate([c[2] for c in chunks])
        n = nn[t]
        mx = jax.lax.stop_gradient(
            jax.ops.segment_max(logits, dst, num_segments=n))
        mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
        ex = jnp.exp(logits - mx[dst])
        den = jax.ops.segment_sum(ex, dst, num_segments=n)
        alpha = ex / jnp.maximum(den[dst], 1e-16)
        if any(c[3] is not None for c in chunks):
            w = jnp.concatenate([
                c[3] if c[3] is not None else jnp.ones(c[0].shape[0])
                for c in chunks])
            alpha = alpha * w[:, None]
        agg = jax.ops.segment_sum(msg * alpha[..., None], dst,
                                  num_segments=n)
        h = jax.nn.gelu(agg.reshape(n, H * D))
        o = h @ params["w_out"][ti[t]] + params["b_out"][ti[t]]
        x = x_dict[t]
        if conv.in_features == conv.out_features:
            gate = jax.nn.sigmoid(params["skip"][ti[t]])
            o = gate * o.astype(x.dtype) + (1.0 - gate) * x
        out[t] = o
    for t in x_dict:
        out.setdefault(t, x_dict[t])
    return out


# ----------------------------------------------------------- forward parity
@pytest.mark.parametrize("heads", [1, 2, 4])
def test_hgt_fused_matches_dense_and_oracle(rng, monkeypatch, heads):
    """Fused HGT == hand-rolled dense cross-type softmax == COO oracle."""
    feat = 12
    x, edges, nn = _hetero_inputs(rng, feat=feat)
    conv = HGTConv(feat, 8 * heads, (["user", "item"], [ET_UB, ET_RU]),
                   heads=heads)
    params = conv.init(jax.random.PRNGKey(0))
    want = _dense_hgt(conv, params, x, edges, nn)

    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    calls = _spy(monkeypatch, attn_ops, "attn_ell_pallas")
    got = conv.apply(params, x, _cached_ei(edges, nn), nn)
    assert len(calls) >= len(edges), \
        "not every relation's typed attention hit the fused kernel"
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    oracle = conv.apply(params, x, _raw_ei(edges, nn), nn)
    for t in want:
        np.testing.assert_allclose(np.asarray(got[t]), np.asarray(want[t]),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(oracle[t]),
                                   np.asarray(want[t]), rtol=1e-4,
                                   atol=1e-5)


def test_hgt_skip_gate_residual_active(rng, monkeypatch):
    """in==out dims engage the sigmoid(skip)-gated residual; forcing the
    gate towards 0 must pull outputs towards the inputs."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    feat = 16
    x, edges, nn = _hetero_inputs(rng, feat=feat)
    conv = HGTConv(feat, feat, (["user", "item"], [ET_UB, ET_RU]), heads=4)
    params = conv.init(jax.random.PRNGKey(1))
    closed = dict(params, skip=jnp.full((2,), -30.0))
    out = conv.apply(closed, x, _raw_ei(edges, nn), nn)
    for t in x:
        np.testing.assert_allclose(np.asarray(out[t]), np.asarray(x[t]),
                                   rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- grad parity
@pytest.mark.parametrize("masked", [False, True])
def test_hgt_grad_parity_fused_vs_oracle(rng, monkeypatch, masked):
    """jax.grad through the carry kernel's custom VJP == autodiff through
    the COO oracle, for params, features, and the per-relation mask."""
    feat = 12
    x, edges, nn = _hetero_inputs(rng, feat=feat)
    mask = ({et: jnp.asarray(rng.random(arr.shape[1]), jnp.float32)
             for et, arr in edges.items()} if masked else None)
    conv = HGTConv(feat, 16, (["user", "item"], [ET_UB, ET_RU]), heads=2)
    params = conv.init(jax.random.PRNGKey(2))

    def loss(p, x_, ei):
        out = conv.apply(p, x_, ei, nn, edge_mask_dict=mask)
        return sum((o ** 2).mean() for o in out.values())

    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    calls = _spy(monkeypatch, attn_ops, "attn_ell_pallas")
    bwd = _spy(monkeypatch, attn_ref, "attn_carry_panels")
    gk = jax.grad(loss, argnums=(0, 1))(params, x, _cached_ei(edges, nn))
    assert calls, "grad step never reached the fused typed-attention kernel"
    assert bwd, "grad step never ran the carry-panel backward"

    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    go = jax.grad(loss, argnums=(0, 1))(params, x, _raw_ei(edges, nn))
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), gk, go)
    max_diff = max(jax.tree_util.tree_leaves(diffs))
    assert max_diff <= 1e-5, f"kernel-grad != oracle-grad: {max_diff}"


# ---------------------------------------------------------- return_attention
def test_hgt_return_attention_cross_relation_simplex(rng, monkeypatch):
    """Merged alphas: each destination node's coefficients sum to 1
    *jointly across relations*, and fused == oracle coefficients."""
    feat = 12
    x, edges, nn = _hetero_inputs(rng, feat=feat)
    conv = HGTConv(feat, 16, (["user", "item"], [ET_UB, ET_RU]), heads=2)
    params = conv.init(jax.random.PRNGKey(3))

    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    out_k, alpha_k = conv.apply(params, x, _cached_ei(edges, nn), nn,
                                return_attention=True)
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    out_o, alpha_o = conv.apply(params, x, _raw_ei(edges, nn), nn,
                                return_attention=True)
    for et in edges:
        np.testing.assert_allclose(np.asarray(alpha_k[et]),
                                   np.asarray(alpha_o[et]), rtol=1e-4,
                                   atol=1e-6)
    for t in out_k:
        np.testing.assert_allclose(np.asarray(out_k[t]),
                                   np.asarray(out_o[t]), rtol=1e-4,
                                   atol=1e-5)
    # per-node row sums ACROSS relations == 1 (the cross-type softmax)
    for t, n in nn.items():
        tot = jnp.zeros((n, conv.heads))
        for et, arr in edges.items():
            if et[2] != t:
                continue
            dst = jnp.asarray(arr[1])
            tot = tot.at[dst].add(alpha_k[et])
        deg = np.zeros(n)
        for et, arr in edges.items():
            if et[2] == t:
                np.add.at(deg, arr[1], 1)
        rows = np.asarray(tot)[deg > 0]
        np.testing.assert_allclose(rows, np.ones_like(rows), rtol=1e-4,
                                   atol=1e-5)


# ------------------------------------------------- carry merge unit contract
def test_merge_carries_is_union_softmax(rng):
    """Merging per-relation carries == one softmax over the edge union;
    all-empty rows finalize to exact zeros (no NaN from -inf maxima)."""
    n, h, f = 10, 2, 4
    logits1 = jnp.asarray(rng.standard_normal((n, h)), jnp.float32) * 3
    logits2 = jnp.asarray(rng.standard_normal((n, h)), jnp.float32) * 3
    z1 = jnp.asarray(rng.standard_normal((n, h, f)), jnp.float32)
    z2 = jnp.asarray(rng.standard_normal((n, h, f)), jnp.float32)

    # honest single-edge carries: m = logit, l = exp(0) = 1, acc = z
    c1 = attn_ops.SoftmaxCarry(logits1, jnp.ones_like(logits1), z1)
    c2 = attn_ops.SoftmaxCarry(logits2, jnp.ones_like(logits2), z2)
    merged = attn_ops.merge_carries([c1, c2])
    got = attn_ops.finalize_carry(merged)
    w1 = jax.nn.softmax(jnp.stack([logits1, logits2]), axis=0)
    want = w1[0][..., None] * z1 + w1[1][..., None] * z2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    # empty rows: m = -inf, l = 0, acc = 0 — merge + finalize stay finite
    neg = jnp.full((n, h), -jnp.inf)
    empty = attn_ops.SoftmaxCarry(neg, jnp.zeros_like(neg),
                                  jnp.zeros_like(z1))
    still = attn_ops.finalize_carry(attn_ops.merge_carries([empty, c1]))
    np.testing.assert_allclose(np.asarray(still), np.asarray(z1), rtol=1e-5,
                               atol=1e-6)
    both = attn_ops.finalize_carry(attn_ops.merge_carries([empty, empty]))
    assert np.isfinite(np.asarray(both)).all()
    np.testing.assert_array_equal(np.asarray(both),
                                  np.zeros_like(np.asarray(both)))


# ------------------------------------------------- loader single-trace step
def test_hgt_loader_step_single_trace_grad_parity(rng, monkeypatch):
    """The acceptance criterion: a jit'd 2-layer HGT train step over
    HeteroNeighborLoader batches runs the fused kernel forward and backward
    with ONE trace across batches, gradients == COO oracle <= 1e-5."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    calls = _spy(monkeypatch, attn_ops, "attn_ell_pallas")
    bwd = _spy(monkeypatch, attn_ref, "attn_carry_panels")
    n_user, n_item, e, feat, hidden = 80, 120, 600, 8, 8
    hd = HeteroData()
    hd.add_nodes("user",
                 rng.standard_normal((n_user, feat)).astype(np.float32))
    hd.add_nodes("item",
                 rng.standard_normal((n_item, feat)).astype(np.float32))
    ub = np.stack([rng.integers(0, n_user, e), rng.integers(0, n_item, e)])
    hd.add_edges(ET_UB, ub)
    hd.add_edges(ET_RU, ub[::-1])
    loader = HeteroNeighborLoader(
        hd, hd, num_neighbors=FANOUTS, input_type="item",
        input_nodes=np.arange(n_item), batch_size=6, prefill_ell=True,
        seed=0)
    net = hgt((["user", "item"], list(FANOUTS)), [feat, hidden, hidden],
              heads=2)
    params = net.init(jax.random.PRNGKey(4))
    traces = []

    def loss_fn(p, ei_dict, batch):
        out = net.apply(p, batch.x_dict, ei_dict, batch.num_nodes_dict)
        return (batch.seed_output(out) ** 2).mean()

    @jax.jit
    def step(p, batch):
        traces.append(1)
        return jax.value_and_grad(loss_fn)(p, batch.edge_index_dict, batch)

    it = iter(loader)
    b1, b2 = next(it), next(it)
    for b in (b1, b2):
        loss_k, grad_k = step(params, b)
        assert calls, "train step never reached the typed-attention kernel"
        assert bwd, "train step never ran the carry-panel backward"
        monkeypatch.setenv("REPRO_USE_PALLAS", "0")
        raw = {et: EdgeIndex(ei.data, ei.num_src_nodes, ei.num_dst_nodes)
               for et, ei in b.edge_index_dict.items()}
        loss_o, grad_o = jax.value_and_grad(loss_fn)(params, raw, b)
        monkeypatch.setenv("REPRO_USE_PALLAS", "1")
        np.testing.assert_allclose(float(loss_k), float(loss_o), rtol=1e-5)
        diffs = jax.tree_util.tree_map(
            lambda a, b_: float(jnp.abs(a - b_).max()), grad_k, grad_o)
        max_diff = max(jax.tree_util.tree_leaves(diffs))
        assert max_diff <= 1e-5, f"kernel-grad != oracle-grad: {max_diff}"
    assert len(traces) == 1, "second batch retraced the HGT grad step"


# -------------------------------------------------------------------- trim
def test_hgt_trim_preserves_seed_outputs(rng, monkeypatch):
    """Layer-wise hetero trimming of the HGT stack: inner hops keep the
    fused typed kernel and seed representations are unchanged."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    n_user, n_item, e, feat = 120, 160, 900, 8
    hd = HeteroData()
    hd.add_nodes("user",
                 rng.standard_normal((n_user, feat)).astype(np.float32))
    hd.add_nodes("item",
                 rng.standard_normal((n_item, feat)).astype(np.float32))
    ub = np.stack([rng.integers(0, n_user, e), rng.integers(0, n_item, e)])
    hd.add_edges(ET_UB, ub)
    hd.add_edges(ET_RU, ub[::-1])
    b = next(iter(HeteroNeighborLoader(
        hd, hd, num_neighbors=FANOUTS, input_type="item",
        input_nodes=np.arange(24), batch_size=8, prefill_ell=True, seed=0)))
    net = hgt((["user", "item"], list(FANOUTS)), [feat, 8, 8], heads=2)
    params = net.init(jax.random.PRNGKey(5))
    calls = _spy(monkeypatch, attn_ops, "attn_ell_pallas")
    full = net.apply(params, b.x_dict, b.edge_index_dict, b.num_nodes_dict)
    full_calls = len(calls)
    assert full_calls, "untrimmed HGT batch missed the fused kernel"
    del calls[:]
    trim = net.apply(params, b.x_dict, b.edge_index_dict,
                     num_sampled_nodes_dict=b.num_sampled_nodes_dict,
                     num_sampled_edges_dict=b.num_sampled_edges_dict,
                     trim=True)
    assert calls, "trimmed inner HGT layers fell off the fused kernel path"
    np.testing.assert_allclose(np.asarray(b.seed_output(full)),
                               np.asarray(b.seed_output(trim)), rtol=1e-3,
                               atol=1e-4)
    # the static-layout ELL caches are cut to the rows layer 1 keeps
    _, ei_t = trim_to_layer_hetero(1, b.num_sampled_nodes_dict,
                                   b.num_sampled_edges_dict, b.x_dict,
                                   b.edge_index_dict)
    assert sum(r.shape[0] for ei in ei_t.values() for r, _, _ in ei._ell) \
        < sum(r.shape[0] for ei in b.edge_index_dict.values()
              for r, _, _ in ei._ell)


# ------------------------------------------------------ GAT bit-identity
def test_gat_attend_bit_identical_through_typed_refactor(rng, monkeypatch):
    """Regression: the typed-logit hooks must not perturb GAT. The default
    attend, the explicit AdditiveLogit attend, and the direct
    gat_attend_ell call produce BIT-IDENTICAL arrays."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    n, e, h, f = 40, 200, 2, 8
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    z = jnp.asarray(rng.standard_normal((n, h, f)), jnp.float32)
    a_src = jnp.asarray(rng.standard_normal((n, h)), jnp.float32)
    a_dst = jnp.asarray(rng.standard_normal((n, h)), jnp.float32)
    ei = EdgeIndex.from_coo(src, dst, n, n).fill_cache()

    default = ei.attend(z, a_src, a_dst)
    typed = ei.attend(z, a_src, a_dst,
                      logit=attn_ops.AdditiveLogit(negative_slope=0.2))
    direct = attn_ops.gat_attend_ell(ei.get_ell(), a_src, a_dst, z,
                                     num_rows=n)
    assert np.array_equal(np.asarray(default), np.asarray(typed)), \
        "AdditiveLogit attend diverged from the default GAT path"
    assert np.array_equal(np.asarray(default), np.asarray(direct)), \
        "EdgeIndex.attend diverged from the raw gat_attend_ell entry"
    # ... and the COO route too (no packed cache, oracle dispatch)
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    raw = EdgeIndex(ei.data, n, n)
    d0 = raw.attend(z, a_src, a_dst)
    t0 = raw.attend(z, a_src, a_dst,
                    logit=attn_ops.AdditiveLogit(negative_slope=0.2))
    assert np.array_equal(np.asarray(d0), np.asarray(t0)), \
        "AdditiveLogit diverged from the default path on the COO oracle"
