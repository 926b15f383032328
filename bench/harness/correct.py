"""The comparison that decides ``correct`` for a training cell.

Set-up drives the program's own step through its first three steps and
keeps, before step 4 can overwrite them: the loss of each step, the first
gradient as the optimizer got it (Adam's first moment after step 1 over
``1 - beta1``), and the parameters' change after step 3. Once the window
has closed, :func:`reference_run` repeats those three steps in plain JAX
(``bench/models/<model>.py``'s reference, no kernel) on the same sampled
batches, with features and labels taken from the graph itself, Adam
written out below, and float32 matmuls at ``highest`` precision.
:func:`compare` turns the two into the numbers that are held to the
cell's limits (``bench/limits/<workload>.json``).
"""

from __future__ import annotations

import json
import math
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import graph_kind

CHECK_STEPS = 3
# A leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone: it is left out of the
# parameter-change comparison (none of the current models has one).
STILL_LEAF = 1e-3


def adam_reference(params, grads, mu, nu, t: int, opt: Dict):
    """One step of the configuration's Adam (constant rate, no clipping,
    decoupled weight decay on matrices), float32 throughout."""
    b1, b2 = (float(b) for b in opt["betas"])
    lr, eps, wd = float(opt["lr"]), float(opt["eps"]), float(
        opt["weight_decay"])
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        d = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        if p.ndim >= 2:
            d = d + wd * p
        return p - lr * d, m, v

    out = jax.tree_util.tree_map(one, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(
        lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


_GRAD_FNS: Dict = {}


def _grad_fn(model_mod, cfg, hops, numerics):
    key = (model_mod.__name__, json.dumps(cfg, sort_keys=True), hops,
           numerics)
    if key not in _GRAD_FNS:
        def loss(params, inp):
            inp = dict(inp, nodes_per_hop=hops[0], edges_per_hop=hops[1])
            return model_mod.reference_loss(params, inp, cfg, numerics)
        _GRAD_FNS[key] = jax.jit(jax.value_and_grad(loss, has_aux=True))
    return _GRAD_FNS[key]


def reference_run(model_mod, cfg, graph, batches: List[List[Dict]], params0,
                  *, numerics: str = "highest",
                  seed_weight: Optional[Callable] = None,
                  shards: Optional[List[int]] = None) -> Dict:
    """Three reference steps from ``params0`` on ``batches`` (per step, the
    host shards). ``numerics`` (``harness.reference.NUMERICS``) other than
    "highest" computes loss and gradients in a lower precision (the
    control and the witness); ``seed_weight`` rewrites each shard's seed
    weights and ``shards`` keeps only those shards (planted faults)."""
    opt = cfg["optimizer"]
    reference_inputs = graph_kind.of(cfg).reference_inputs
    p = jax.tree_util.tree_map(jnp.asarray, params0)
    mu = jax.tree_util.tree_map(jnp.zeros_like, p)
    nu = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, g1 = [], None
    with jax.default_matmul_precision("highest"):
        for t, step_shards in enumerate(batches, start=1):
            loss_sum, weight, gsum = 0.0, 0.0, None
            for i, b in enumerate(step_shards):
                if shards is not None and i not in shards:
                    continue
                inp = reference_inputs(graph, b)
                if seed_weight is not None:
                    inp["w"] = seed_weight(inp["w"])
                hops = (inp.pop("nodes_per_hop"), inp.pop("edges_per_hop"))
                (ls, w), g = _grad_fn(model_mod, cfg, hops, numerics)(p, inp)
                g = jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.float32), g)
                loss_sum = loss_sum + ls.astype(jnp.float32)
                weight = weight + w
                gsum = g if gsum is None else jax.tree_util.tree_map(
                    jnp.add, gsum, g)
            weight = jnp.maximum(weight, 1e-12)
            grads = jax.tree_util.tree_map(lambda a: a / weight, gsum)
            losses.append(float(loss_sum / weight))
            if t == 1:
                g1 = jax.device_get(grads)
            p, mu, nu = adam_reference(p, grads, mu, nu, t, opt)
    p = jax.device_get(p)
    delta = jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        p, params0)
    return {"losses": losses, "g1": g1, "delta": delta}


def program_readings(losses, params0, mu1, params3, cfg) -> Dict:
    """The program's side: losses, first gradient from Adam's first
    moment, and the parameters' change over three steps."""
    b1 = float(cfg["optimizer"]["betas"][0])
    g1 = jax.tree_util.tree_map(
        lambda m: np.asarray(m, np.float64) / (1.0 - b1), mu1)
    delta = jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        params3, params0)
    return {"losses": [float(v) for v in losses], "g1": g1, "delta": delta}


def _leaf_norms(tree) -> List[float]:
    return [float(np.linalg.norm(np.asarray(a, np.float64).ravel()))
            for a in jax.tree_util.tree_leaves(tree)]


def leaf_gaps(got, want) -> List[float]:
    """Per leaf, |‖got‖ - ‖want‖| over the larger of ‖want‖ and the
    median leaf's ‖want‖ (inf where ``got`` is not finite)."""
    g, w = _leaf_norms(got), _leaf_norms(want)
    med = float(np.median(w))
    return [abs(a - b) / max(b, med, 1e-30) if math.isfinite(a)
            else float("inf") for a, b in zip(g, w)]


def norm_gap(got, want, keep: Optional[List[bool]] = None) -> float:
    """The worst leaf's gap (:func:`leaf_gaps`), over the leaves ``keep``
    marks."""
    gaps = leaf_gaps(got, want)
    return max((x for i, x in enumerate(gaps) if keep is None or keep[i]),
               default=0.0)


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers held to the limits."""
    loss_gap = 0.0
    for a, b in zip(prog["losses"], ref["losses"]):
        gap = abs(a - b) / max(abs(b), 1e-30)
        loss_gap = max(loss_gap, gap if math.isfinite(a) else float("inf"))
    gnorm = _leaf_norms(ref["g1"])
    med = float(np.median(gnorm))
    keep = [n >= STILL_LEAF * med for n in gnorm]
    return {"loss_gap": loss_gap,
            "grad_gap": norm_gap(prog["g1"], ref["g1"]),
            "update_gap": norm_gap(prog["delta"], ref["delta"], keep)}
