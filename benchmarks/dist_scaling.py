"""Data-parallel mesh scaling trajectory (PR 10) -> ``BENCH_dist.json``.

One cell, four mesh sizes: the ``MeshTrainer`` shard_map train step runs a
loader-fed GCN step on 1/2/4/8 forced host-platform devices with a fixed
*global* batch, recording per-mesh step time, seed throughput and scaling
efficiency (vs. the 1-device step), plus:

  * grad/loss parity of the 4-device step against the single-device
    gradient-accumulation oracle over the same shards (max |delta| across
    updated params);
  * trace_count per mesh size (must be 1 — one compilation serves every
    batch, tail included);
  * per-step collective traffic of the raw ``psum`` all-reduce vs the
    int8 / top-k compressed all-reduce, read off the step jaxpr by
    ``launch/jaxpr_stats.analyze_jaxpr`` (``collective_bytes``).

Honesty note: the container exposes ``host_cpu_count`` CPU cores (typically
1), and forced host-platform devices *timeshare* those cores — wall-clock
scaling efficiency on this box therefore measures shard_map dispatch
overhead, not parallel speedup, and is recorded as-is with the core count
beside it. On real multi-chip hardware the same cell measures true scaling.

On the CPU backend the device count must be forced *before* jax
initialises, so ``run()`` re-execs itself in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` when the environment
names the CPU platform (``JAX_PLATFORMS=cpu``) and ``XLA_FLAGS`` does not
force the count yet. It decides from the environment alone: a parent that
touched jax would hold a TPU and its child could not get it. Anywhere else
it runs in-process on the devices it finds, over the mesh sizes they allow
(the 4-device parity and traffic records need at least 4).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

MAX_DEVICES = 8
GLOBAL_BATCH = 32
FANOUTS = [4, 2]
STEPS_PER_MESH = 4


def _build_problem():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import synthetic_graph
    from repro.data.data import Data
    from repro.data.loader import NeighborLoader
    from repro.nn.gnn.conv import gcn_norm

    edge_index, x, y = synthetic_graph(4096, 8, 64, seed=11)
    data = Data(x=x, edge_index=edge_index,
                y=y.astype(np.float32))

    def make_loader(shards):
        return NeighborLoader(
            data, data, num_neighbors=FANOUTS, batch_size=GLOBAL_BATCH,
            input_nodes=np.arange(GLOBAL_BATCH * STEPS_PER_MESH),
            prefill_ell=False, drop_last=False, shards=shards, seed=0)

    def loss_fn(params, batch):
        ew, _ = gcn_norm(batch.edge_index, batch.num_nodes,
                         add_self_loops=False)
        h = jax.nn.relu(batch.edge_index.matmul(
            batch.x @ params["w1"], edge_weight=ew))
        out = batch.edge_index.matmul(h @ params["w2"], edge_weight=ew)
        err = ((out[batch.seed_slots] - batch.y[:, None]) ** 2).sum(axis=-1)
        mask = batch.seed_mask.astype(jnp.float32)
        return (err * mask).sum(), mask.sum()

    rng = np.random.default_rng(3)
    params = {
        "w1": jnp.asarray(rng.standard_normal((64, 32)) * 0.1, jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((32, 1)) * 0.1, jnp.float32)}
    return make_loader, loss_fn, params


def _inner(out_path: str = "BENCH_dist.json") -> None:
    import jax

    from benchmarks.common import append_cell, emit, time_fn
    from repro.data.loader import stack_batches
    from repro.launch.mesh import data_parallel_mesh
    from repro.launch.train import MeshTrainer
    from repro.train import optimizer as opt_lib

    sizes = [d for d in (1, 2, 4, 8) if d <= len(jax.devices())]
    make_loader, loss_fn, params = _build_problem()
    cfg = opt_lib.OptConfig(lr=1e-2, warmup_steps=1, total_steps=100)
    state0 = opt_lib.init_state(params, cfg)

    meshes, batches_by_d, trainers = {}, {}, {}
    per_mesh = {}
    base_us = None
    for d in sizes:
        mesh = data_parallel_mesh(d)
        trainer = MeshTrainer(loss_fn, cfg, mesh=mesh)
        batches = list(make_loader(shards=d))
        if d == 1:  # shards=1 keeps the plain unstacked batch (back-compat)
            batches = [stack_batches([b]) for b in batches]
        state = state0
        for b in batches:  # one epoch: every signature seen, still 1 trace
            state, _ = trainer.step(state, b)
        us = time_fn(trainer.step, state, batches[0], warmup=1, iters=3)
        if base_us is None:
            base_us = us
        thru = GLOBAL_BATCH / (us / 1e6)
        eff = base_us / (us * d)
        per_mesh[str(d)] = {
            "step_us": us, "seeds_per_s": thru,
            "scaling_efficiency": eff,
            "speedup_vs_1dev": base_us / us,
            "trace_count": trainer.trace_count,
        }
        emit(f"dist/step_{d}dev_us", us,
             f"eff={eff:.2f} traces={trainer.trace_count}")
        meshes[d], trainers[d], batches_by_d[d] = mesh, trainer, batches

    rec = {
        "cell": "dist_scaling",
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "host_cpu_count": os.cpu_count(),
        "devices": len(jax.devices()),
        "global_batch": GLOBAL_BATCH,
        "fanouts": FANOUTS,
        "per_mesh": per_mesh,
        "note": ("forced host devices timeshare host_cpu_count cores; "
                 "wall-clock efficiency on this box measures dispatch "
                 "overhead, not parallel speedup"),
    }
    if 4 in sizes:
        rec.update(_parity_and_traffic(trainers, meshes, batches_by_d,
                                       loss_fn, cfg, state0))
    append_cell(out_path, rec)


def _parity_and_traffic(trainers, meshes, batches_by_d, loss_fn, cfg,
                        state0) -> dict:
    """4-device parity vs gradient accumulation + collective bytes."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import emit
    from repro.launch import jaxpr_stats
    from repro.launch.train import MeshTrainer
    from repro.train import optimizer as opt_lib

    # ---- 4-device grad/loss parity vs single-device accumulation ----
    d = 4

    def oracle_step(state, stacked):
        def total(p):
            ls = ws = 0.0
            for i in range(d):
                shard = jax.tree_util.tree_map(lambda l, i=i: l[i], stacked)
                l, w = loss_fn(p, shard)
                ls, ws = ls + l, ws + w
            return ls, ws
        (loss_sum, weight), grads = jax.value_and_grad(
            total, has_aux=True)(state.params)
        w = jnp.maximum(weight, 1e-12)
        grads = jax.tree_util.tree_map(lambda g: g / w, grads)
        state, metrics = opt_lib.apply_updates(state, grads, cfg)
        metrics["loss"] = loss_sum / w
        return state, metrics

    oracle = jax.jit(oracle_step)
    s_mesh = s_orc = state0
    loss_diff = 0.0
    for b in batches_by_d[d]:
        s_mesh, m_mesh = trainers[d].step(s_mesh, b)
        s_orc, m_orc = oracle(s_orc, b)
        loss_diff = max(loss_diff,
                        abs(float(m_mesh["loss"]) - float(m_orc["loss"])))
    param_diff = max(
        float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(s_mesh.params),
            jax.tree_util.tree_leaves(s_orc.params)))
    emit("dist/parity_param_maxdiff", param_diff * 1e6,
         f"loss_diff={loss_diff:.2e}")

    # ---- compressed vs raw all-reduce traffic (per step, from jaxpr) ----
    b0 = batches_by_d[d][0]
    comm = {}
    for method, tr in (
            ("raw", trainers[d]),
            ("int8", MeshTrainer(loss_fn, cfg, mesh=meshes[d],
                                 compression="int8")),
            ("topk_1pct", MeshTrainer(loss_fn, cfg, mesh=meshes[d],
                                      compression="topk",
                                      compression_ratio=0.01))):
        stats = jaxpr_stats.analyze_jaxpr(tr.step_jaxpr(state0, b0))
        comm[method] = int(stats["collective_bytes"])
        emit(f"dist/collective_bytes_{method}", comm[method])

    return {
        "parity_4dev": {"param_maxdiff": param_diff,
                        "loss_maxdiff": loss_diff, "tolerance": 1e-5,
                        "pass": bool(param_diff <= 1e-5
                                     and loss_diff <= 1e-5)},
        "collective_bytes_per_step": comm,
        "compression_saving_int8":
            1.0 - comm["int8"] / max(comm["raw"], 1),
    }


def run(out_path: str = "BENCH_dist.json") -> None:
    """Entry point for run.py: re-exec with forced host devices on CPU."""
    from repro.launch.mesh import HOST_DEVICE_FLAG, host_device_flag

    flags = os.environ.get("XLA_FLAGS", "")
    if os.environ.get("JAX_PLATFORMS") != "cpu" or HOST_DEVICE_FLAG in flags:
        _inner(out_path)
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"{flags} {host_device_flag(MAX_DEVICES)}".strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.dist_scaling", "--inner",
         out_path], cwd=root, env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"dist_scaling re-exec failed (rc={proc.returncode})")


if __name__ == "__main__":
    if "--inner" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--inner"]
        _inner(*args)
    else:
        run(*sys.argv[1:])
