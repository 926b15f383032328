"""Smoke run of the loader-fed GraphSAGE train step on TPU chips.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the data-parallel mesh step only

One chip: a graph at ogbn-products' published shape is generated from
``--seed``, the ``NeighborLoader`` feeds the GraphSAGE configuration of
upstream PyG's ogbn-products example into a jitted grad step, and the run
checks that

  * every loss is finite and the step compiled once for every batch;
  * the step's jaxpr launches ``_spmm_ell_kernel`` with no XLA-oracle eqns
    and no interpret-mode launches (``repro.analysis.audit_report``);
  * the kernel step's loss and gradients on the first batch match the same
    step on the XLA oracle (a loader without packed ELL caches) within
    1e-4 relative.

``--chips 4``: the ``MeshTrainer`` shard_map step over a 4-device data mesh
against single-device gradient accumulation over the same shards (param
max-diff <= 1e-5), with per-device memory to show each device holds its own
batch shard.

It fails with a non-zero exit, and prints no result line, when JAX finds no
TPU or when ``REPRO_USE_PALLAS`` turns the kernels off. On success the last
line of stdout is one JSON object naming the device. Step times are host
clock around ``block_until_ready`` and only informational.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# ogbn-products (OGB; Hu et al., 2020): published node/edge counts, feature
# width and class count. The edges are generated as directed src -> dst
# pairs with uniform endpoints (no degree skew).
NUM_NODES = 2_449_029
NUM_EDGES = 61_859_140
IN_FEATURES = 100
NUM_CLASSES = 47
# Upstream PyG examples/ogbn_products_sage.py: 3 SAGE layers (mean aggr),
# hidden 256, fanouts [15, 10, 5], batch 1024.
HIDDEN = 256
NUM_LAYERS = 3
FANOUTS = [15, 10, 5]
BATCH_SIZE = 1024
# Loader pipelining as the step benchmarks run it.
PIPELINE_DEPTH = 2
PREFETCH = 2

STEPS = 5            # grad steps on one chip
MESH_STEPS = 3       # mesh steps on four chips
LR = 1e-3            # step size of the smoke run's parameter updates
GRAD_RTOL = 1e-4     # kernel vs XLA oracle, relative to the oracle's max
MESH_PARAM_TOL = 1e-5


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check_devices(chips: int):
    """Fail unless JAX sees ``chips`` TPU devices and the kernels are on."""
    import jax

    from repro.kernels import USE_PALLAS_ENV, use_pallas

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is "
                           f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SmokeFailure(f"needs {chips} TPU devices, found {len(devices)}")
    if not use_pallas():
        raise SmokeFailure(f"{USE_PALLAS_ENV}={os.environ[USE_PALLAS_ENV]!r} "
                           f"turns the Pallas kernels off")
    return devices


def build_graph(seed: int):
    """The ogbn-products-shaped graph from ``seed``, with its CSC built."""
    import numpy as np

    from repro.data.data import Data

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    edge_index = rng.integers(0, NUM_NODES, (2, NUM_EDGES), dtype=np.int64)
    x = rng.standard_normal((NUM_NODES, IN_FEATURES), dtype=np.float32)
    y = rng.integers(0, NUM_CLASSES, NUM_NODES)
    data = Data(x=x, edge_index=edge_index, y=y, num_nodes=NUM_NODES)
    data.get_rev_csr()  # the sampler walks incoming edges
    log(f"graph: {NUM_NODES} nodes, {NUM_EDGES} edges, {IN_FEATURES}-d "
        f"features, {NUM_CLASSES} classes, built in "
        f"{time.perf_counter() - t0:.1f} s")
    return data


def make_loader(data, seed: int, *, batch_size: int, shards: int = 1,
                prefill_ell=None):
    from repro.data.loader import NeighborLoader

    return NeighborLoader(data, data, num_neighbors=FANOUTS,
                          batch_size=batch_size, shuffle=True,
                          pipeline_depth=PIPELINE_DEPTH, prefetch=PREFETCH,
                          prefill_ell=prefill_ell, shards=shards, seed=seed)


def make_model():
    from repro.nn.gnn.models import make_model as make_gnn

    return make_gnn("sage", IN_FEATURES, HIDDEN, NUM_CLASSES,
                    num_layers=NUM_LAYERS)


def seed_nll(model, params, batch):
    """Per-seed negative log-likelihood of the seeds' labels."""
    import jax
    import jax.numpy as jnp

    out = model.apply(params, batch.x, batch.edge_index,
                      num_sampled_nodes_per_hop=batch.num_sampled_nodes,
                      num_sampled_edges_per_hop=batch.num_sampled_edges,
                      trim=True)
    logp = jax.nn.log_softmax(out[batch.seed_slots])
    return -jnp.take_along_axis(logp, batch.y[:, None], 1)[:, 0]


def make_grad_step(model, traces: list):
    """Jitted ``(params, batch) -> (loss, grads)``; appends to ``traces``
    each time JAX traces it."""
    import jax

    @jax.jit
    def grad_step(params, batch):
        traces.append(1)
        return jax.value_and_grad(
            lambda p: seed_nll(model, p, batch).mean())(params)

    return grad_step


def max_rel_diff(got, want) -> float:
    """Largest leaf-wise max|got - want| / max|want|."""
    import jax
    import numpy as np

    worst = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        scale = max(float(np.abs(b).max()), 1e-30)
        worst = max(worst, float(np.abs(a - b).max()) / scale)
    return worst


def same_sample(a, b) -> bool:
    import numpy as np

    return (np.array_equal(np.asarray(a.n_id), np.asarray(b.n_id))
            and np.array_equal(np.asarray(a.seed_slots),
                               np.asarray(b.seed_slots)))


def run_one_chip(data, seed: int) -> None:
    import jax
    import numpy as np

    from repro.analysis import audit_report

    model = make_model()
    params = model.init(jax.random.PRNGKey(seed))
    traces, oracle_traces = [], []
    step = make_grad_step(model, traces)
    oracle_step = make_grad_step(model, oracle_traces)
    sgd = jax.jit(lambda p, g: jax.tree_util.tree_map(
        lambda a, b: a - LR * b, p, g))

    it = iter(make_loader(data, seed, batch_size=BATCH_SIZE))
    try:
        batches = [next(it) for _ in range(STEPS)]
    finally:
        it.close()
    b0 = batches[0]
    log(f"batch: {BATCH_SIZE} seeds, fanouts {FANOUTS}, {b0.num_nodes} node "
        f"slots, {b0.edge_index.num_edges} edge slots, "
        f"{len(b0.edge_index.get_ell())} packed ELL buckets")

    # ---- train: one jitted grad step per batch, loss printed each step
    params0 = params
    step_ms = []
    t0 = time.perf_counter()
    for i, batch in enumerate(batches):
        ts = time.perf_counter()
        loss, grads = step(params, batch)
        jax.block_until_ready((loss, grads))
        took = time.perf_counter() - ts
        if i == 0:
            compile_s = time.perf_counter() - t0
            loss0, grads0 = loss, grads
        else:
            step_ms.append(took * 1e3)
        loss = float(loss)
        log(f"step {i}: loss {loss!r}")
        if not np.isfinite(loss):
            raise SmokeFailure(f"step {i}: loss is not finite ({loss})")
        params = sgd(params, grads)
    jax.block_until_ready(params)

    # ---- the kernels ran: zero oracle eqns, zero interpret launches
    report = audit_report(step, params0, b0)
    report.assert_fused(expect_kernels=("_spmm_ell_kernel",))
    if report.interpret_launches:
        raise SmokeFailure(f"{report.interpret_launches} pallas_call "
                           f"launches run in interpret mode")
    if len(traces) != 1:
        raise SmokeFailure(f"grad step traced {len(traces)} times over "
                           f"{len(batches)} batches")

    # ---- the same first batch on the XLA oracle (no packed ELL caches)
    oit = iter(make_loader(data, seed, batch_size=BATCH_SIZE,
                           prefill_ell=False))
    try:
        ob0 = next(oit)
    finally:
        oit.close()
    if not same_sample(b0, ob0):
        raise SmokeFailure("oracle loader sampled a different first batch")
    ts = time.perf_counter()
    oloss, ograds = oracle_step(params0, ob0)
    jax.block_until_ready((oloss, ograds))
    oracle_compile_s = time.perf_counter() - ts
    oreport = audit_report(oracle_step, params0, ob0)
    if oreport.total_kernel_launches or not oreport.oracle_fallbacks:
        raise SmokeFailure(f"oracle step is not on the XLA oracle: "
                           f"{oreport.summary()}")
    loss_rel = abs(float(loss0) - float(oloss)) / max(abs(float(oloss)),
                                                      1e-30)
    grad_rel = max_rel_diff(grads0, ograds)
    log(f"oracle parity: loss {float(loss0)!r} vs {float(oloss)!r} "
        f"(rel {loss_rel:.3e}), grads max rel diff {grad_rel:.3e}, "
        f"limit {GRAD_RTOL}")
    if loss_rel > GRAD_RTOL or grad_rel > GRAD_RTOL:
        raise SmokeFailure(f"kernel step disagrees with the XLA oracle: "
                           f"loss rel {loss_rel:.3e}, grad rel "
                           f"{grad_rel:.3e} > {GRAD_RTOL}")

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    log(f"device_kind: {dev.device_kind}")
    log(f"device_count: {len(jax.devices())}")
    log(f"compile_s (first call: trace + compile + one step): "
        f"{compile_s:.1f}; oracle step: {oracle_compile_s:.1f}")
    log(f"steady step ms (host clock around block_until_ready, "
        f"informational): {[round(t, 1) for t in step_ms]}")
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    log(f"kernel launches per step (audit): {report.kernel_launches}; "
        f"oracle eqns: {report.oracle_fallbacks}; interpret launches: "
        f"{report.interpret_launches}")
    log(f"trace_count: {len(traces)}")


def run_four_chips(data, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis import audit_jaxpr
    from repro.launch.mesh import data_parallel_mesh
    from repro.launch.train import MeshTrainer
    from repro.train import optimizer as opt_lib

    d = 4
    model = make_model()
    params = model.init(jax.random.PRNGKey(seed))
    cfg = opt_lib.OptConfig(lr=LR, weight_decay=0.0, warmup_steps=1,
                            total_steps=100)

    def loss_fn(p, shard):
        mask = shard.seed_mask.astype(jnp.float32)
        return (seed_nll(model, p, shard) * mask).sum(), mask.sum()

    shard_grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))

    @jax.jit
    def finish(state, grads, loss_sum, weight):
        w = jnp.maximum(weight, 1e-12)
        grads = jax.tree_util.tree_map(lambda g: g / w, grads)
        state, metrics = opt_lib.apply_updates(state, grads, cfg)
        metrics["loss"] = loss_sum / w
        return state, metrics

    def oracle(state, stacked):
        """Single-device gradient accumulation over the same shards, one
        shard at a time (one device holds one shard's activations)."""
        acc = None
        for i in range(d):
            shard = jax.tree_util.tree_map(lambda l, i=i: l[i], stacked)
            (loss_sum, weight), grads = shard_grads(state.params, shard)
            now = (grads, loss_sum, weight)
            acc = now if acc is None else jax.tree_util.tree_map(
                jnp.add, acc, now)
        return finish(state, *acc)

    mesh = data_parallel_mesh(d)
    trainer = MeshTrainer(loss_fn, cfg, mesh=mesh)
    state0 = opt_lib.init_state(params, cfg)
    s_mesh, s_orc = trainer.replicate_state(state0), state0

    it = iter(make_loader(data, seed, batch_size=d * BATCH_SIZE, shards=d))
    try:
        stacked = [next(it) for _ in range(MESH_STEPS)]
    finally:
        it.close()
    log(f"global batch {d}x{BATCH_SIZE} seeds, fanouts {FANOUTS}, "
        f"{stacked[0].x.shape[1]} node slots per shard")

    loss_diff, step_ms = 0.0, []
    for i, b in enumerate(stacked):
        placed = trainer.shard_batch(b)
        ts = time.perf_counter()
        s_mesh, m_mesh = trainer.step(s_mesh, placed)
        jax.block_until_ready(s_mesh)
        step_ms.append((time.perf_counter() - ts) * 1e3)
        s_orc, m_orc = oracle(s_orc, b)
        lm, lo = float(m_mesh["loss"]), float(m_orc["loss"])
        log(f"mesh step {i}: loss {lm!r}, oracle loss {lo!r}")
        if not (np.isfinite(lm) and np.isfinite(lo)):
            raise SmokeFailure(f"mesh step {i}: loss is not finite")
        loss_diff = max(loss_diff, abs(lm - lo))
    param_diff = max(
        float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(s_mesh.params),
            jax.tree_util.tree_leaves(s_orc.params)))
    log(f"mesh parity: param max-diff {param_diff!r}, loss max-diff "
        f"{loss_diff!r}, limit {MESH_PARAM_TOL}")
    if param_diff > MESH_PARAM_TOL:
        raise SmokeFailure(f"mesh step params differ from the accumulation "
                           f"oracle by {param_diff} > {MESH_PARAM_TOL}")

    report = audit_jaxpr(trainer.step_jaxpr(s_mesh, placed))
    report.assert_fused(expect_kernels=("_spmm_ell_kernel",),
                        expect_collectives={"psum": 1})
    if report.interpret_launches:
        raise SmokeFailure(f"{report.interpret_launches} pallas_call "
                           f"launches run in interpret mode")
    if trainer.trace_count != 1:
        raise SmokeFailure(f"mesh step traced {trainer.trace_count} times")

    shards = {s.device: s.data.shape for s in placed.x.addressable_shards}
    log(f"device_kind: {jax.devices()[0].device_kind}")
    log(f"device_count: {len(jax.devices())}")
    for dev in mesh.devices.flat:
        ms = dev.memory_stats() or {}
        log(f"device {dev.id}: batch.x shard {shards.get(dev)}, "
            f"bytes_in_use {ms.get('bytes_in_use')}, peak_bytes_in_use "
            f"{ms.get('peak_bytes_in_use')}")
    if len(shards) != d:
        raise SmokeFailure(f"batch shards sit on {len(shards)} devices, "
                           f"not {d}")
    log(f"mesh step ms (host clock, informational): "
        f"{[round(t, 1) for t in step_ms]}")
    log(f"kernel launches per step (audit): {report.kernel_launches}; "
        f"collectives: {report.collective_eqns}; trace_count: "
        f"{trainer.trace_count}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        devices = check_devices(args.chips)
        from repro.launch import compile_cache

        log(f"compile cache: {compile_cache.enable(ROOT)}")
        data = build_graph(args.seed)
        if args.chips == 1:
            run_one_chip(data, args.seed)
        else:
            run_four_chips(data, args.seed)
    except Exception as exc:  # noqa: BLE001 - any failure fails the run
        import traceback

        traceback.print_exc()
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
