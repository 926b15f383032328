"""The system under test, driven as a user trains with it.

One :class:`Trainer` owns the program's loader over the graph (the graph
kind's: ``NeighborLoader`` or ``HeteroNeighborLoader``), the program's
model, its Adam (``repro.train.optimizer``) and either a jitted
single-device step or ``repro.launch.train.MeshTrainer`` over a
data-parallel mesh. Every optimizer step, in set-up and in the window
alike, goes through :meth:`Trainer.step`: the next loader batch, the jitted
loss + gradients + update, then ``block_until_ready``.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Host spans the benchmark records around its calls into the program.
SPANS = ("loader.next", "step.dispatch", "step.block")
WINDOW_SPAN = "bench.window"


class Spans:
    """Totals of host time per span; with ``trace`` also profiler spans on
    the device trace's clock."""

    def __init__(self, trace: bool = False):
        self.trace = trace
        self.total: Dict[str, float] = defaultdict(float)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        if self.trace:
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.total[name] += time.perf_counter() - t


def opt_config(cfg):
    """The configuration's optimizer as the program's ``OptConfig``."""
    from repro.train import optimizer as opt_lib

    o = cfg["optimizer"]
    if o["name"] != "adam" or o["schedule"] != "constant":
        raise ValueError(f"unsupported optimizer {o}")
    b1, b2 = o["betas"]
    clip = o["grad_clip"]
    # warmup 0 and a horizon no run reaches keep the program's warmup +
    # cosine schedule at exactly the constant rate in float32.
    return opt_lib.OptConfig(
        lr=float(o["lr"]), b1=float(b1), b2=float(b2), eps=float(o["eps"]),
        weight_decay=float(o["weight_decay"]),
        grad_clip=float("inf") if clip is None else float(clip),
        warmup_steps=0, total_steps=2**31 - 1)


def program_loss(model, trim: bool):
    """``(params, shard) -> (loss sum, real-seed count)``: NLL of the
    seeds' labels, the contract ``MeshTrainer`` takes."""

    def loss_fn(params, batch):
        out = model.apply(params, batch.x, batch.edge_index,
                          num_sampled_nodes_per_hop=batch.num_sampled_nodes,
                          num_sampled_edges_per_hop=batch.num_sampled_edges,
                          trim=trim)
        logp = jax.nn.log_softmax(out[batch.seed_slots])
        nll = -jnp.take_along_axis(logp, batch.y[:, None], 1)[:, 0]
        w = batch.seed_mask.astype(jnp.float32)
        return (nll * w).sum(), w.sum()

    return loss_fn


def neighbor_loader(store, graph, cell, seed: int):
    """The program's ``NeighborLoader`` over ``store`` as the cell's mix
    says, one shard per chip."""
    from repro.data.loader import NeighborLoader

    tr = cell.traffic
    return NeighborLoader(
        store, store, num_neighbors=list(tr["num_neighbors"]),
        batch_size=int(tr["batch_per_chip"]) * cell.chips,
        input_nodes=graph.train_nodes,
        shuffle=bool(tr["shuffle"]), drop_last=bool(tr["drop_last"]),
        pipeline_depth=int(tr["pipeline_depth"]),
        prefetch=int(tr["prefetch"]), shards=cell.chips, seed=seed)


class Trainer:
    """Loader + model + optimizer + step of one cell, on ``chips`` devices.

    ``kind`` (``harness.graph_kind``) makes the loader and the loss.
    ``step_hook`` wraps the built step (the fault tests break it there).
    """

    def __init__(self, cell, kind, model_mod, store, graph, params,
                 seed: int, *,
                 step_hook: Optional[Callable] = None,
                 loss_hook: Optional[Callable] = None):
        from repro.train import optimizer as opt_lib

        cfg, tr = cell.config, cell.traffic
        self.chips = cell.chips
        self.seeds_per_step = int(tr["batch_per_chip"]) * self.chips
        # first: a kind that cannot serve the cell refuses it here
        self.loader = kind.make_loader(store, graph, cell, seed)
        self.model = model_mod.program_model(cfg)
        self.opt_cfg = opt_config(cfg)
        loss_fn = kind.loss(self.model, bool(cfg["trim"]))
        if loss_hook is not None:
            loss_fn = loss_hook(loss_fn)
        state = opt_lib.init_state(params, self.opt_cfg)
        self.traces: List[int] = []
        if self.chips == 1:
            self.mesh_trainer = None
            self.state = state
            self._step = self._single_step(loss_fn)
        else:
            from repro.launch.mesh import data_parallel_mesh
            from repro.launch.train import MeshTrainer

            mt = MeshTrainer(loss_fn, self.opt_cfg,
                             mesh=data_parallel_mesh(self.chips))
            self.mesh_trainer = mt
            self.state = mt.replicate_state(state)

            def mesh_step(state, batch):
                state, metrics = mt.step(state, mt.shard_batch(batch))
                return state, metrics["loss"]

            self._step = mesh_step
        if step_hook is not None:
            self._step = step_hook(self._step)
        self._batches = self._epochs()

    def _single_step(self, loss_fn):
        from repro.train import optimizer as opt_lib

        opt_cfg, traces = self.opt_cfg, self.traces

        def step(state, batch):
            traces.append(1)
            (loss_sum, weight), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch)
            weight = jnp.maximum(weight, 1e-12)
            grads = jax.tree_util.tree_map(lambda g: g / weight, grads)
            state, _ = opt_lib.apply_updates(state, grads, opt_cfg)
            return state, loss_sum / weight

        return jax.jit(step, donate_argnums=(0,))

    @property
    def trace_count(self) -> int:
        if self.mesh_trainer is not None:
            return self.mesh_trainer.trace_count
        return len(self.traces)

    def _epochs(self):
        while True:
            it = iter(self.loader)
            try:
                yield from it
            finally:
                it.close()

    def step(self, spans: Spans):
        """One optimizer step: returns (the batch it trained on, its loss)."""
        with spans("loader.next"):
            batch = next(self._batches)
        with spans("step.dispatch"):
            self.state, loss = self._step(self.state, batch)
        with spans("step.block"):
            jax.block_until_ready((self.state, loss))
        return batch, loss

    def audit(self, batch) -> Dict[str, Any]:
        """Pallas launches and collectives in one step's jaxpr."""
        from repro.analysis import audit_jaxpr, audit_report

        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (self.state, batch))
        if self.mesh_trainer is None:
            rep = audit_report(self._step, *shapes)
        else:
            rep = audit_jaxpr(self.mesh_trainer.step_jaxpr(*shapes))
        return {"kernel_launches": dict(rep.kernel_launches),
                "collectives": dict(rep.collective_eqns),
                "oracle_eqns": int(rep.oracle_fallbacks),
                "interpret_launches": int(rep.interpret_launches)}

    def temp_bytes(self, batch) -> Optional[int]:
        """The compiled single-device step's temporaries (the compiler's
        memory analysis; the compile comes from the cache)."""
        if self.mesh_trainer is not None:
            return None
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (self.state, batch))
        stats = self._step.lower(*shapes).compile().memory_analysis()
        return None if stats is None else int(stats.temp_size_in_bytes)

    def close(self) -> None:
        """Stop the loader's producer and stage threads."""
        self._batches.close()


def host_shards(batch, chips: int) -> List[Dict[str, np.ndarray]]:
    """Host copy of a (stacked, with ``chips`` > 1) batch, one dict per
    shard: what the reference and the graph check read."""
    x, data, n_id, e_id, seed_slots, y = jax.device_get(
        (batch.x, batch.edge_index.data, batch.n_id, batch.e_id,
         batch.seed_slots, batch.y))
    if chips == 1:
        x, data, n_id, e_id, seed_slots, y = (
            a[None] for a in (x, data, n_id, e_id, seed_slots, y))
    return [{"x": x[i], "src": data[i][0], "dst": data[i][1],
             "n_id": n_id[i], "e_id": e_id[i], "seed_slots": seed_slots[i],
             "y": y[i], "nodes_per_hop": list(batch.num_sampled_nodes),
             "edges_per_hop": list(batch.num_sampled_edges)}
            for i in range(chips)]
