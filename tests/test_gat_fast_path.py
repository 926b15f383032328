"""Fused attention fast path: GAT rides the flash-GAT Pallas kernel (this PR).

The acceptance chain for the attention tentpole:

    loader-prefilled batch (homogeneous or hetero)
      -> jit'd GATConv value_and_grad train step, Pallas dispatch on
        -> forward: the fused flash-GAT ELL kernel (spy-counted), no
           (E, H, F) edge-message materialisation
        -> backward: the ops-level custom VJP (softmax backward over the
           same panels, spy-counted)
      == materialised-oracle outputs and gradients, ONE trace across batches

plus `return_attention` recovering per-edge alpha through the COO-keyed
``ell_pos``, the explainer's ``edge_mask`` staying fused on GAT, the
``flow="target_to_source"`` transpose dispatch, hetero per-relation
dispatch, trimmed deep GATs, and a slow-marked parity sweep across the
bucketed K ladder.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.edge_index import EdgeIndex
from repro.core.explain import Explainer
from repro.core.hetero import to_hetero
from repro.data.data import Data, HeteroData
from repro.data.hetero_sampler import HeteroNeighborLoader
from repro.data.loader import NeighborLoader
from repro.kernels.attention import ops as attn_ops
from repro.kernels.segment_softmax import ref as sm_ref
from repro.nn.gnn.conv import GATConv
from repro.nn.gnn.models import make_model

ET_UB = ("user", "buys", "item")
ET_RU = ("item", "rev_buys", "user")
FANOUTS = {ET_UB: [3, 2], ET_RU: [3, 2]}


def _spy(monkeypatch, module, name):
    calls = []
    real = getattr(module, name)
    monkeypatch.setattr(module, name,
                        lambda *a, **k: (calls.append(1), real(*a, **k))[1])
    return calls


def _random_graph(rng, n, e):
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    return src, dst


def _materialised_gat(params, x, src, dst, n, heads, f_head, concat=True,
                      negative_slope=0.2, edge_weight=None):
    """The pre-refactor (E, H, F)-materialising GAT forward, as oracle."""
    z = (x @ params["lin"]["w"]).reshape(-1, heads, f_head)
    a_src = (z * params["att_src"]).sum(-1)
    a_dst = (z * params["att_dst"]).sum(-1)
    logits = jax.nn.leaky_relu(a_src[src] + a_dst[dst], negative_slope)
    alpha = sm_ref.segment_softmax(logits, dst, n)
    msg = z[src] * alpha[..., None]
    if edge_weight is not None:
        msg = msg * edge_weight[:, None, None]
    out = jax.ops.segment_sum(msg, dst, num_segments=n)
    out = out.reshape(n, heads * f_head) if concat else out.mean(1)
    return out + params["bias"], alpha


# ----------------------------------------------------------- forward parity
@pytest.mark.parametrize("heads,concat", [(1, True), (4, True), (2, False)])
def test_gat_fused_forward_matches_materialised(rng, monkeypatch, heads,
                                                concat):
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    calls = _spy(monkeypatch, attn_ops, "gat_ell_pallas")
    n, e, f_in, f_out = 40, 220, 12, 8
    src, dst = _random_graph(rng, n, e)
    x = jnp.asarray(rng.standard_normal((n, f_in)).astype(np.float32))
    conv = GATConv(f_in, f_out, heads=heads, concat=concat)
    params = conv.init(jax.random.PRNGKey(0))
    ei = EdgeIndex.from_coo(src, dst, n, n).fill_cache()
    got = conv.apply(params, x, ei)
    assert calls, "fused GAT forward never reached the Pallas kernel"
    want, _ = _materialised_gat(params, x, src, dst, n, heads,
                                conv.out_per_head, concat=concat)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_gat_return_attention_roundtrip_ell_pos(rng, monkeypatch):
    """Per-edge alpha recovered through the COO-keyed ell_pos == the
    materialised softmax coefficients, in COO edge order."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    calls = _spy(monkeypatch, attn_ops, "gat_ell_pallas")
    n, e = 30, 150
    src, dst = _random_graph(rng, n, e)
    x = jnp.asarray(rng.standard_normal((n, 10)).astype(np.float32))
    conv = GATConv(10, 8, heads=2)
    params = conv.init(jax.random.PRNGKey(1))
    ei = EdgeIndex.from_coo(src, dst, n, n).fill_cache()
    got, alpha = conv.apply(params, x, ei, return_attention=True)
    assert calls, "return_attention dropped off the fused path"
    want, want_alpha = _materialised_gat(params, x, src, dst, n, 2, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(alpha), np.asarray(want_alpha),
                               rtol=1e-4, atol=1e-6)


# ------------------------------------------------------------- grad parity
@pytest.mark.parametrize("weighted,concat", [(False, True), (True, True),
                                             (True, False)])
def test_gat_grad_parity_fused_vs_materialised(rng, monkeypatch, weighted,
                                               concat):
    """jax.grad through the fused kernel's custom VJP == autodiff through
    the materialised oracle, for params, features and the edge mask."""
    n, e, f_in, f_out = 35, 180, 10, 8
    src, dst = _random_graph(rng, n, e)
    x = jnp.asarray(rng.standard_normal((n, f_in)).astype(np.float32))
    mask = (jnp.asarray(rng.random(e).astype(np.float32)) if weighted
            else None)
    conv = GATConv(f_in, f_out, heads=2, concat=concat)
    params = conv.init(jax.random.PRNGKey(2))

    def loss(p, x_, m_, ei):
        out = conv.apply(p, x_, ei, edge_mask=m_)
        return (out ** 2).mean()

    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    calls = _spy(monkeypatch, attn_ops, "gat_ell_pallas")
    bwd = _spy(monkeypatch, attn_ops, "_gat_panels_backward")
    ei = EdgeIndex.from_coo(src, dst, n, n).fill_cache()
    argnums = (0, 1, 2) if weighted else (0, 1)
    gk = jax.grad(loss, argnums=argnums)(params, x, mask, ei)
    assert calls, "grad step never reached the fused kernel forward"
    assert bwd, "grad step never ran the panel softmax backward"

    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    raw = EdgeIndex(ei.data, n, n)
    go = jax.grad(loss, argnums=argnums)(params, x, mask, raw)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), gk, go)


def test_gat_flow_target_to_source(rng, monkeypatch):
    """Regression: flow="target_to_source" used to be silently ignored. It
    now aggregates along reversed edges (transpose dispatch), on both the
    materialised and the fused path."""
    n, e, f = 28, 140, 10
    src, dst = _random_graph(rng, n, e)
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    conv = GATConv(f, 8, heads=2, flow="target_to_source")
    params = conv.init(jax.random.PRNGKey(3))
    # oracle: the forward-flow conv on the reversed edge list
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    want, _ = _materialised_gat(params, x, dst, src, n, 2, 4)
    got_raw = conv.apply(params, x, np.stack([src, dst]), num_nodes=n)
    np.testing.assert_allclose(np.asarray(got_raw), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    calls = _spy(monkeypatch, attn_ops, "gat_ell_pallas")
    ei = EdgeIndex.from_coo(src, dst, n, n).fill_cache()
    got = conv.apply(params, x, ei)
    assert calls, "reversed flow missed the fused kernel (transpose table)"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


# ------------------------------------------------- loader single-trace step
def test_gat_loader_step_single_trace_grad_parity(rng, monkeypatch):
    """The acceptance criterion: a jit'd GATConv train step over
    NeighborLoader batches runs the fused kernel forward and backward with
    ONE trace across batches, gradients == materialised oracle <= 1e-5."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    calls = _spy(monkeypatch, attn_ops, "gat_ell_pallas")
    bwd = _spy(monkeypatch, attn_ops, "_gat_panels_backward")
    n, e, feat, hidden = 200, 1200, 16, 8
    data = Data(x=rng.standard_normal((n, feat)).astype(np.float32),
                edge_index=np.stack(_random_graph(rng, n, e)))
    loader = NeighborLoader(data, data, num_neighbors=[4, 2], batch_size=8,
                            prefill_ell=True, labels_attr=None, seed=0)
    conv = GATConv(feat, hidden, heads=2)
    params = conv.init(jax.random.PRNGKey(4))
    traces = []

    def loss_fn(p, ei, batch):
        out = conv.apply(p, batch.x, ei)
        return (out[batch.seed_slots] ** 2).mean()

    @jax.jit
    def step(p, batch):
        traces.append(1)
        return jax.value_and_grad(loss_fn)(p, batch.edge_index, batch)

    it = iter(loader)
    b1, b2 = next(it), next(it)
    for b in (b1, b2):
        loss_k, grad_k = step(params, b)
        assert calls, "train step never reached the fused attention kernel"
        assert bwd, "train step never ran the fused attention backward"
        # materialised oracle on a cache-less EdgeIndex: no Pallas anywhere
        monkeypatch.setenv("REPRO_USE_PALLAS", "0")
        raw = EdgeIndex(b.edge_index.data, b.num_nodes, b.num_nodes)
        loss_o, grad_o = jax.value_and_grad(loss_fn)(params, raw, b)
        monkeypatch.setenv("REPRO_USE_PALLAS", "1")
        np.testing.assert_allclose(float(loss_k), float(loss_o), rtol=1e-5)
        diffs = jax.tree_util.tree_map(
            lambda a, b_: float(jnp.abs(a - b_).max()), grad_k, grad_o)
        max_diff = max(jax.tree_util.tree_leaves(diffs))
        assert max_diff <= 1e-5, f"kernel-grad != oracle-grad: {max_diff}"
    assert len(traces) == 1, "second batch retraced the GAT grad step"


# ------------------------------------------------------ explainer edge_mask
def test_explainer_edge_mask_gat_stays_fused(rng, monkeypatch):
    """Gradient-based explainers on GAT under REPRO_USE_PALLAS=1 send their
    soft mask down the fused path (spy-counted — the mask folds into the
    post-softmax weight, no (E, H, F) materialisation) and agree with the
    oracle-path attributions."""
    n, e, f = 30, 100, 8
    src, dst = _random_graph(rng, n, e)
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    model = make_model("gat", f, 16, 3, 2)
    params = model.init(jax.random.PRNGKey(0))

    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    calls = _spy(monkeypatch, attn_ops, "gat_ell_pallas")
    ei = EdgeIndex.from_coo(src, dst, n, n)
    fast = Explainer(model, params, algorithm="saliency")(x, ei, node_idx=5)
    assert calls, "GAT explainer gradients bypassed the fused kernel"
    assert np.isfinite(np.asarray(fast.edge_mask)).all()

    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    ref = Explainer(model, params, algorithm="saliency")(
        x, EdgeIndex.from_coo(src, dst, n, n), node_idx=5)
    np.testing.assert_allclose(np.asarray(fast.edge_mask),
                               np.asarray(ref.edge_mask), rtol=1e-3,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(fast.node_mask),
                               np.asarray(ref.node_mask), rtol=1e-3,
                               atol=1e-4)


def test_attention_explainer_roundtrip_fused(rng, monkeypatch):
    """The 'attention' explanation algorithm (GAT coefficient capture) uses
    return_attention — on the fused path the coefficients come back through
    ell_pos and must match the oracle's."""
    n, e, f = 24, 90, 6
    src, dst = _random_graph(rng, n, e)
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    model = make_model("gat", f, 8, 2, 2)
    params = model.init(jax.random.PRNGKey(1))
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    ei = EdgeIndex.from_coo(src, dst, n, n).fill_cache()
    fast = Explainer(model, params, algorithm="attention")(x, ei, node_idx=3)
    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    ref = Explainer(model, params, algorithm="attention")(
        x, EdgeIndex.from_coo(src, dst, n, n), node_idx=3)
    np.testing.assert_allclose(np.asarray(fast.edge_mask),
                               np.asarray(ref.edge_mask), rtol=1e-4,
                               atol=1e-6)


# ------------------------------------------------------------------ hetero
def test_hetero_gat_per_relation_fused(rng, monkeypatch):
    """Every relation of a hetero GAT dispatches the fused attention kernel
    (typed loader batches, one trace) and matches the per-conv oracle."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    calls = _spy(monkeypatch, attn_ops, "gat_ell_pallas")
    hd = HeteroData()
    hd.add_nodes("user", rng.standard_normal((40, 8)).astype(np.float32))
    hd.add_nodes("item", rng.standard_normal((60, 8)).astype(np.float32))
    ub = np.stack([rng.integers(0, 40, 200), rng.integers(0, 60, 200)])
    hd.add_edges(ET_UB, ub)
    hd.add_edges(ET_RU, ub[::-1])
    loader = HeteroNeighborLoader(
        hd, hd, num_neighbors=FANOUTS, input_type="item",
        input_nodes=np.arange(16), batch_size=4, prefill_ell=True, seed=0)
    metadata = (["user", "item"], list(FANOUTS))
    net = to_hetero(lambda i, o: GATConv(i, o, heads=2), metadata,
                    [8, 16, 4])
    params = net.init(jax.random.PRNGKey(0))
    traces = []

    @jax.jit
    def step(p, batch):
        traces.append(1)

        def loss_fn(p):
            out = net.apply(p, batch.x_dict, batch.edge_index_dict,
                            batch.num_nodes_dict)
            return (batch.seed_output(out) ** 2).mean()

        return jax.value_and_grad(loss_fn)(p)

    it = iter(loader)
    b1, b2 = next(it), next(it)
    results = [(b, step(params, b)) for b in (b1, b2)]
    assert len(traces) == 1, "second typed batch retraced the grad step"
    assert len(calls) >= 2 * len(FANOUTS), \
        "not every relation's attention hit the fused kernel"

    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    for b, (loss_k, grad_k) in results:
        raw = {et: EdgeIndex(ei.data, ei.num_src_nodes, ei.num_dst_nodes)
               for et, ei in b.edge_index_dict.items()}

        def ref_loss(p):
            out = net.apply(p, b.x_dict, raw, b.num_nodes_dict)
            return (b.seed_output(out) ** 2).mean()

        loss_o, grad_o = jax.value_and_grad(ref_loss)(params)
        np.testing.assert_allclose(float(loss_k), float(loss_o), rtol=1e-4)
        jax.tree_util.tree_map(
            lambda a, b_: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=2e-3, atol=2e-4),
            grad_k, grad_o)


# -------------------------------------------------------------------- trim
def test_deep_gat_trim_keeps_kernel_and_seed_outputs(rng, monkeypatch):
    """Layer-wise trimming of a deep GAT: inner hops keep the fused kernel,
    launched over only the static-layout ELL rows each layer keeps, and
    seed representations and gradients are unchanged."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    monkeypatch.setattr(attn_ops, "MAX_PREFETCH_ELEMS", 64)  # 16-row chunks
    chunk = 16
    n, e, feat = 300, 2400, 12
    data = Data(x=rng.standard_normal((n, feat)).astype(np.float32),
                edge_index=np.stack(_random_graph(rng, n, e)))
    loader = NeighborLoader(data, data, num_neighbors=[4, 3, 2],
                            batch_size=6, prefill_ell=True,
                            labels_attr=None, seed=0)
    batch = next(iter(loader))
    model = make_model("gat", feat, 8, 3, 3)
    params = model.init(jax.random.PRNGKey(5))
    calls = _spy(monkeypatch, attn_ops, "gat_ell_pallas")
    full = model.apply(params, batch.x, batch.edge_index)
    full_calls = len(calls)
    table = batch.edge_index._ell
    assert full_calls == 3 * sum(-(-int(r.shape[0]) // chunk)
                                 for r, _, _ in table), \
        "untrimmed GAT batch missed the fused kernel"
    del calls[:]
    trim_kw = dict(num_sampled_nodes_per_hop=batch.num_sampled_nodes,
                   num_sampled_edges_per_hop=batch.num_sampled_edges,
                   trim=True)
    trim = model.apply(params, batch.x, batch.edge_index, **trim_kw)
    # layer l keeps the rows below its receiving boundary, a prefix of
    # each bucket, cut to a multiple of 8 rows and launched chunk by chunk
    want = 0
    for layer in range(3):
        recv = int(sum(batch.num_sampled_nodes[:3 - layer]))
        for r, _, _ in table:
            r = np.asarray(r)
            kept = int(((r >= 0) & (r < recv)).sum())
            want += -(-(-(-kept // 8) * 8) // chunk)
    assert len(calls) == want < full_calls, \
        "trimmed GAT layers did not launch over exactly their kept rows"
    np.testing.assert_allclose(
        np.asarray(full[batch.seed_slots]),
        np.asarray(trim[batch.seed_slots]), rtol=1e-3, atol=1e-4)

    def seed_loss(p, **kw):
        out = model.apply(p, batch.x, batch.edge_index, **kw)
        return (out[batch.seed_slots] ** 2).sum()

    g_full = jax.grad(seed_loss)(params)
    g_trim = jax.grad(functools.partial(seed_loss, **trim_kw))(params)
    jax.tree_util.tree_map(
        lambda a, b_: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-3, atol=1e-4),
        g_full, g_trim)


def test_trimmed_gat_step_compiles_once_per_layout(rng, monkeypatch):
    """The trimmed buckets' static row ranges ride the pytree aux: a jitted
    trimmed GAT train step over loader batches of one layout traces once,
    on single batches and on two shards stacked by ``stack_batches``."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    n, e, feat = 300, 2400, 12
    data = Data(x=rng.standard_normal((n, feat)).astype(np.float32),
                edge_index=np.stack(_random_graph(rng, n, e)))
    model = make_model("gat", feat, 8, 3, 3)
    params = model.init(jax.random.PRNGKey(7))

    def loss_fn(p, batch):
        out = model.apply(p, batch.x, batch.edge_index,
                          num_sampled_nodes_per_hop=batch.num_sampled_nodes,
                          num_sampled_edges_per_hop=batch.num_sampled_edges,
                          trim=True)
        return (out[batch.seed_slots] ** 2).mean()

    traces, stacked_traces = [], []

    @jax.jit
    def step(p, batch):
        traces.append(1)
        return jax.value_and_grad(loss_fn)(p, batch)

    @jax.jit
    def stacked_step(p, stacked):
        stacked_traces.append(1)
        shards = [jax.tree_util.tree_map(lambda l, i=i: l[i], stacked)
                  for i in range(2)]
        return jax.value_and_grad(
            lambda p_: sum(loss_fn(p_, s) for s in shards))(p)

    def loader(shards):
        return iter(NeighborLoader(data, data, num_neighbors=[4, 3, 2],
                                   batch_size=6 * shards, shards=shards,
                                   prefill_ell=True, labels_attr=None,
                                   seed=0))

    single = loader(1)
    b1, b2 = next(single), next(single)
    losses = [float(step(params, b)[0]) for b in (b1, b2)]
    assert len(traces) == 1, "second batch of one layout retraced"
    stacked = loader(2)
    s1, s2 = next(stacked), next(stacked)
    stacked_losses = [float(stacked_step(params, s)[0]) for s in (s1, s2)]
    assert len(stacked_traces) == 1, "second stacked batch retraced"
    # the stacked loader's first batch holds the single loader's two
    np.testing.assert_allclose(stacked_losses[0], sum(losses), rtol=1e-5)


def test_trimmed_transpose_ell_serves_reversed_flow(rng, monkeypatch):
    """The transpose (CSR-derived) ELL now survives a layer trim as a
    per-slot masked cache: reversed-flow GAT attend AND transpose matmul
    on a trimmed EdgeIndex stay on the kernel and match the COO oracle of
    the trimmed graph."""
    from repro.core.trim import trim_to_layer
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    n, e, feat = 300, 2400, 12
    data = Data(x=rng.standard_normal((n, feat)).astype(np.float32),
                edge_index=np.stack(_random_graph(rng, n, e)))
    loader = NeighborLoader(data, data, num_neighbors=[4, 3, 2],
                            batch_size=6, prefill_ell=True,
                            labels_attr=None, seed=0)
    batch = next(iter(loader))
    batch.edge_index.fill_cache()  # packs the transpose ELL (host CSR)
    x, ei_t, _ = trim_to_layer(1, batch.num_sampled_nodes,
                               batch.num_sampled_edges, batch.x,
                               batch.edge_index)
    assert ei_t._ell_t is not None, "trim dropped the transpose ELL"
    conv = GATConv(feat, 8, heads=2, flow="target_to_source")
    params = conv.init(jax.random.PRNGKey(6))
    calls = _spy(monkeypatch, attn_ops, "gat_ell_pallas")
    got = conv.apply(params, x, ei_t)
    assert calls, "trimmed reversed-flow GAT fell off the fused kernel"
    got_mm = ei_t.matmul(x, transpose=True, force_pallas=True,
                         interpret=True)

    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    raw = EdgeIndex(ei_t.data, ei_t.num_src_nodes, ei_t.num_dst_nodes)
    want = conv.apply(params, x, raw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    want_mm = raw.matmul(x, transpose=True, force_pallas=False)
    np.testing.assert_allclose(np.asarray(got_mm), np.asarray(want_mm),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------- slow grad sweep
@pytest.mark.slow
@pytest.mark.parametrize("heads,concat,weighted", [
    (1, True, False), (2, True, True), (4, False, True), (3, False, False)])
def test_gat_parity_sweep_k_ladder(rng, monkeypatch, heads, concat,
                                   weighted):
    """Fused-vs-materialised forward AND grad parity on a skewed-degree
    graph whose demand-filled ELL spans several K-ladder buckets."""
    n = 64
    deg = np.concatenate([rng.integers(0, 4, 40), rng.integers(5, 17, 20),
                          [0, 1, 29, 53]])
    rng.shuffle(deg)
    dst = np.repeat(np.arange(n), deg).astype(np.int32)
    e = len(dst)
    src = rng.integers(0, n, e).astype(np.int32)
    x = jnp.asarray(rng.standard_normal((n, 12)).astype(np.float32))
    mask = (jnp.asarray(rng.random(e).astype(np.float32)) if weighted
            else None)
    conv = GATConv(12, 8 * heads if concat else 8, heads=heads,
                   concat=concat)
    params = conv.init(jax.random.PRNGKey(heads))

    def loss(p, x_, m_, ei):
        return (conv.apply(p, x_, ei, edge_mask=m_) ** 2).mean()

    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    calls = _spy(monkeypatch, attn_ops, "gat_ell_pallas")
    ei = EdgeIndex.from_coo(src, dst, n, n).fill_cache()
    assert len(ei.get_ell()) >= 3, "degree skew produced too few buckets"
    out_k = conv.apply(params, x, ei, edge_mask=mask)
    gk = jax.grad(loss, argnums=(0, 1))(params, x, mask, ei)
    assert len(calls) >= 3, "not every K bucket launched the kernel"

    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    raw = EdgeIndex(ei.data, n, n)
    out_o = conv.apply(params, x, raw, edge_mask=mask)
    go = jax.grad(loss, argnums=(0, 1))(params, x, mask, raw)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_o),
                               rtol=1e-4, atol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), gk, go)
