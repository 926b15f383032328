"""Path-based sharding rules: param/cache pytrees -> PartitionSpec trees.

The 2-D scheme (DESIGN.md §5):
  * ``model`` axis: tensor parallel — attention heads, FFN hidden, MoE
    experts, vocab.
  * ``data`` axis: FSDP — every param additionally shards its largest
    remaining axis over ``data``; gradients reduce-scatter over ``data``.
  * ``pod`` axis (multi-pod): pure data parallel; params replicated across
    pods, gradient all-reduce on DCN only.

Rules are matched on the flattened param path (e.g. ``body/sub0/mixer/wq``),
with the scanned-stack leading period axis handled automatically (specs are
shifted right by one when the leaf has an extra leading dim).
"""

from __future__ import annotations

import re
from contextlib import contextmanager
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (regex on path, spec WITHOUT the scan axis). First match wins.
_PARAM_RULES: Tuple[Tuple[str, P], ...] = (
    # embeddings / head
    (r"embed$",            P("model", "data")),      # (vocab, d)
    (r"lm_head$",          P("data", "model")),      # (d, vocab)
    (r"(final_norm|enc_norm)$", P(None)),
    # attention
    (r"mixer/wq$",         P("data", "model", None)),  # (d, H, hd)
    (r"mixer/wk$",         P("data", "model", None)),
    (r"mixer/wv$",         P("data", "model", None)),
    (r"mixer/wo$",         P("model", None, "data")),  # (H, hd, d)
    (r"cross/wq$",         P("data", "model", None)),
    (r"cross/wk$",         P("data", "model", None)),
    (r"cross/wv$",         P("data", "model", None)),
    (r"cross/wo$",         P("model", None, "data")),
    (r"mixer/b[qkv]$",     P("model", None)),
    (r"(q_norm|k_norm)$",  P(None)),
    # dense FFN
    (r"ffn/w_in$",         P("data", None, "model")),  # (d, 2, ff)
    (r"ffn/w_out$",        P("model", "data")),        # (ff, d)
    (r"(shared|dense)/w_in$",  P("data", None, "model")),
    (r"(shared|dense)/w_out$", P("model", "data")),
    # MoE
    (r"ffn/router$",       P("data", None)),           # (d, E)
    # expert stacks: experts -> model (EP), d -> data (FSDP)
    (r"ffn/w_in$",         P("model", "data", None, None)),
    (r"ffn/w_out$",        P("model", None, "data")),
    # mamba
    (r"mixer/in_proj$",    P("data", None, "model")),  # (d, 2, di)
    (r"mixer/conv_w$",     P(None, "model")),          # (k, di)
    (r"mixer/conv_b$",     P("model")),
    (r"mixer/x_proj$",     P("model", None)),          # (di, r+2s)
    (r"mixer/dt_proj_w$",  P(None, "model")),          # (r, di)
    (r"mixer/dt_proj_b$",  P("model")),
    (r"mixer/A_log$",      P("model", None)),          # (di, st)
    (r"mixer/D$",          P("model")),
    (r"mixer/out_proj$",   P("model", "data")),        # (di, d)
    # norms
    (r"norm", P(None)),
)

# MoE expert tensors share the "ffn/w_in|w_out" names with dense FFN but have
# one more dim; disambiguate by rank (see _match).
_MOE_W_IN = P("model", "data", None, None)   # (E, d, 2, f)
_MOE_W_OUT = P("model", None, "data")        # (E, f, d)
_FFN_W_IN = P("data", None, "model")         # (d, 2, ff)
_FFN_W_OUT = P("model", "data")              # (ff, d)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(p.name)
        else:
            parts.append(str(p))
    return "/".join(parts)


def _match(path: str, ndim: int) -> P:
    if re.search(r"ffn/w_in$", path):
        base = _MOE_W_IN if ndim >= 4 else _FFN_W_IN
    elif re.search(r"ffn/w_out$", path):
        base = _MOE_W_OUT if ndim >= 3 else _FFN_W_OUT
    else:
        base = None
        for pat, spec in _PARAM_RULES:
            if re.search(pat, path):
                base = spec
                break
        if base is None:
            base = P()  # replicate by default
    return base


def _axis_size(mesh: Mesh, ax) -> int:
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


def _fix_spec(spec, shape, mesh: Mesh, lock_dims=()) -> P:
    """Repair a spec for divisibility: explicit in_shardings must divide
    evenly (GSPMD pads only propagated intermediates, not arguments).

    For each dim whose assigned axis does not divide, the axis migrates to
    the largest free dim that divides (e.g. GQA: kv_heads=8 < model=16 ->
    the ``model`` axis moves from the head dim to head_dim — head_dim
    tensor parallelism). Dims in ``lock_dims`` (the scan axis) never
    receive a migrated axis.
    """
    spec = list(spec)
    for i, ax in enumerate(spec):
        if ax is None:
            continue
        if shape[i] % _axis_size(mesh, ax) == 0:
            continue
        spec[i] = None
        n = _axis_size(mesh, ax)
        for j in sorted(range(len(shape)), key=lambda j: -shape[j]):
            if j == i or j in lock_dims or spec[j] is not None:
                continue
            if shape[j] % n == 0 and shape[j] >= n:
                spec[j] = ax
                break
    return P(*spec)


def _fsdp_spec(shape, mesh: Mesh, lock_dims=()) -> P:
    """Pure-FSDP spec: the largest divisible dim carries all non-pod axes.

    §Perf profile: at ≥1k tokens/device, per-param compute (6·tokens/chips
    FLOPs) dwarfs per-param FSDP traffic (~4 bytes), so sharding *weights*
    across all chips and batch across all chips beats tensor parallelism —
    TP's per-layer activation all-reduces are what dominate the baseline
    collective term.
    """
    axes = ("data", "model")  # flattened within-pod FSDP axis
    n = _axis_size(mesh, axes)
    spec = [None] * len(shape)
    cands = sorted((j for j in range(len(shape)) if j not in lock_dims),
                   key=lambda j: -shape[j])
    for j in cands:
        if shape[j] % n == 0 and shape[j] >= n:
            spec[j] = axes
            return P(*spec)
    for sub in ("data", "model"):
        m = _axis_size(mesh, sub)
        for j in cands:
            if shape[j] % m == 0 and shape[j] >= m:
                spec[j] = sub
                return P(*spec)
    return P(*spec)


def param_spec(path, leaf, mesh: Mesh = None, profile: str = "2d") -> P:
    """PartitionSpec for one param leaf, accounting for the scan axis."""
    ps = _path_str(path)
    ndim = leaf.ndim
    in_body = ps.startswith("body/") or "/body/" in ps or ps.startswith(
        "encoder/")
    if profile == "fsdp" and mesh is not None:
        return _fsdp_spec(leaf.shape, mesh,
                          lock_dims=(0,) if in_body else ())
    if profile == "ep" and mesh is not None:
        # expert tensors: experts -> 'model' (EP), hidden -> 'data' (FSDP);
        # everything else: FSDP over data only (model axis reserved for EP)
        base_ndim = ndim - (1 if in_body else 0)
        # expert weights: E -> 'model' (EP), d -> 'data' (FSDP).
        # (§Perf iteration 5 tried FSDP on the expert-hidden f dim instead —
        # hypothesis: avoid gathering weights whose contraction dim is
        # sharded. REFUTED: arctic 21.5->30.0s, deepseek 9.1->22.0s — XLA's
        # chosen schedule for the d-sharded layout (one weight all-gather
        # amortised across the fused GLU pair) beats per-matmul activation
        # psums. Reverted; kept for the record.)
        if re.search(r"ffn/w_in$", ps) and base_ndim >= 4:
            spec = (None, "model", "data", None, None)[-ndim:] \
                if in_body else ("model", "data", None, None)
            return _fix_spec(spec, leaf.shape, mesh,
                             lock_dims=(0,) if in_body else ())
        if re.search(r"ffn/w_out$", ps) and base_ndim >= 3:
            spec = (None, "model", None, "data")[-ndim:] \
                if in_body else ("model", None, "data")
            return _fix_spec(spec, leaf.shape, mesh,
                             lock_dims=(0,) if in_body else ())
        spec = [None] * ndim
        cands = sorted((j for j in range(ndim)
                        if not (in_body and j == 0)),
                       key=lambda j: -leaf.shape[j])
        for j in cands:
            if leaf.shape[j] % mesh.shape["data"] == 0 and \
                    leaf.shape[j] >= mesh.shape["data"]:
                spec[j] = "data"
                break
        return P(*spec)
    base = _match(ps, ndim - (1 if in_body else 0))
    spec = tuple(base)
    if in_body:
        spec = (None,) + spec  # period-stack axis replicated
    # pad/truncate to rank
    spec = (spec + (None,) * ndim)[:ndim]
    if mesh is not None:
        return _fix_spec(spec, leaf.shape, mesh,
                         lock_dims=(0,) if in_body else ())
    return P(*spec)


def param_shardings(mesh: Mesh, params_shape, profile: str = "2d") -> Any:
    """NamedSharding tree matching ``params_shape`` (shapes or arrays)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, param_spec(path, leaf, mesh, profile)), params_shape)


# ----------------------------------------------------------------- batches
def batch_spec(mesh: Mesh, shape_len: int = 2, profile: str = "2d") -> P:
    """Token batches: batch axis over ('pod','data') when pods exist;
    the fsdp profile spreads batch over every axis."""
    if profile == "fsdp":
        axes = (("pod", "data", "model") if "pod" in mesh.axis_names
                else ("data", "model"))
    else:
        axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    return P(axes, *([None] * (shape_len - 1)))


def batch_shardings(mesh: Mesh, batch_shape, profile: str = "2d") -> Any:
    def spec(leaf):
        b = leaf.shape[0]
        for prof in ((profile, "2d") if profile != "2d" else ("2d",)):
            cand = batch_spec(mesh, len(leaf.shape), prof)
            n = _axis_size(mesh, cand[0]) if cand[0] else 1
            if b % n == 0:
                return NamedSharding(mesh, cand)
        return NamedSharding(mesh, P(*([None] * len(leaf.shape))))
    return jax.tree_util.tree_map(spec, batch_shape)


def _dp_size(mesh: Mesh) -> int:
    size = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        size *= mesh.shape["pod"]
    return size


# ------------------------------------------------------------------ caches
def cache_spec(path, leaf, mesh: Mesh, batch: int) -> P:
    """KV/SSM cache sharding.

    Batch shards over data when divisible; otherwise (long_500k batch=1)
    the sequence axis of KV caches shards over data instead.
    """
    ps = _path_str(path)
    ndim = leaf.ndim
    dp = mesh.shape["data"]
    batch_ok = batch % dp == 0
    in_body = ps.startswith("body/") or "/body/" in ps

    if re.search(r"(self|cross)/[kv]$", ps):  # (B, S, Hkv, hd)
        spec = (("data" if batch_ok else None),
                (None if batch_ok else "data"), "model", None)
    elif re.search(r"self/conv$", ps):        # (B, k-1, di)
        spec = (("data" if batch_ok else None), None, "model")
    elif re.search(r"self/ssm$", ps):         # (B, di, st)
        spec = (("data" if batch_ok else None), "model", None)
    else:
        spec = ()
    if in_body:
        spec = (None,) + tuple(spec)
    spec = (tuple(spec) + (None,) * ndim)[:ndim]
    return _fix_spec(spec, leaf.shape, mesh,
                     lock_dims=(0,) if in_body else ())


def cache_shardings(mesh: Mesh, cache_shape, batch: int) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, cache_spec(path, leaf, mesh, batch)), cache_shape)


# -------------------------------------------------- activation constraints
# GSPMD drops propagated shardings inside nested scan/while bodies (observed
# in §Perf iteration 1: fully-replicated global-batch attention logits being
# all-reduced per block). Production JAX frameworks pin every major
# activation with with_sharding_constraint; these hooks do the same. The
# training entry points set the context at trace time; without it the
# model is constraint-free (the paper-faithful baseline + single-device
# tests).

_ACT_CTX: Optional[Tuple[Mesh, str]] = None


@contextmanager
def activation_sharding(mesh: Mesh, profile: str = "2d"):
    global _ACT_CTX
    old = _ACT_CTX
    _ACT_CTX = (mesh, profile)
    try:
        yield
    finally:
        _ACT_CTX = old


def _dp_axes(mesh: Mesh, profile: str):
    if profile == "fsdp":
        return tuple(mesh.axis_names)  # batch over every axis
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _act_spec(kind: str, mesh: Mesh, profile: str) -> Optional[P]:
    dp = _dp_axes(mesh, profile)
    if profile == "ep":
        # expert-parallel: batch over data only; experts own 'model';
        # attention replicated across 'model' (heads rarely divide 16);
        # logits vocab-sharded over 'model'.
        if kind == "btd":
            return P(dp, None, None)
        if kind == "bshd":
            return P(dp, None, None, None)
        if kind == "btv":
            return P(dp, None, "model")
        if kind == "btf":
            return P(dp, None, None)
        if kind == "ecd":
            return P("model", None, None)
        if kind == "te":
            return P(dp, None)
        return None
    tp = None if profile == "fsdp" else "model"
    if kind == "btd":     # (B, S, D) hidden states
        return P(dp, None, None)
    if kind == "bshd":    # (B, S, H, Dh) attention heads
        return P(dp, None, tp, None)
    if kind == "btv":     # (B, S, V) logits
        return P(dp, None, tp)
    if kind == "btf":     # (B, S, F) ffn / mamba inner
        return P(dp, None, tp)
    if kind == "ecd":     # (E, C, D) MoE expert buffers
        return P(tp, dp if profile == "fsdp" else None, None)
    if kind == "te":      # (T, E) router logits/probs
        return P(dp, None)
    return None


def constrain(x, kind: str):
    """Pin an activation's sharding (no-op outside a sharding context)."""
    if _ACT_CTX is None:
        return x
    mesh, profile = _ACT_CTX
    spec = _act_spec(kind, mesh, profile)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


# ----------------------------------------------- data-parallel GNN (PR 10)
# The mesh train step's scheme is deliberately simpler than the LM rules
# above: every model/optimizer leaf replicates (P()), every batch leaf
# shards its leading shard axis over the 1-D "data" mesh. The loader's
# ``stack_batches`` produces exactly that leading axis.

def replicated_shardings(mesh: Mesh, tree: Any) -> Any:
    """NamedSharding(P()) for every leaf — params/opt state on a data mesh."""
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), tree)


def data_batch_spec(leaf, axis_name: str = "data") -> P:
    """Leading-axis shard spec for one stacked-batch leaf."""
    return P(axis_name, *([None] * (jnp.ndim(leaf) - 1)))


def data_batch_shardings(mesh: Mesh, batch: Any,
                         axis_name: str = "data") -> Any:
    """Shard every stacked-batch leaf's leading shard axis over the mesh."""
    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(mesh, data_batch_spec(leaf, axis_name)),
        batch)


# ------------------------------------------------------------- train state
def state_shardings(mesh: Mesh, state_shape, profile: str = "2d") -> Any:
    """TrainState sharding: params/mu/nu share param specs; step replicated."""
    from repro.train.optimizer import TrainState
    return TrainState(
        step=NamedSharding(mesh, P()),
        params=param_shardings(mesh, state_shape.params, profile),
        mu=param_shardings(mesh, state_shape.mu, profile),
        nu=param_shardings(mesh, state_shape.nu, profile),
    )
