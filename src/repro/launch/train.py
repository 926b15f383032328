"""Training driver: ``python -m repro.launch.train --arch <id> [--smoke]``.

On the CPU container this trains the reduced (smoke) configs end-to-end —
the same code path a TPU deployment uses with the full configs + production
mesh (sharding applied when the mesh has >1 device). Fault tolerance is
live: interrupt and re-run with the same --ckpt-dir to resume.

This module also hosts :class:`MeshTrainer`, the data-parallel mesh wrapper
for the GNN stack (PR 10): give it any ``loss_fn(params, batch) ->
(loss_sum, weight)`` and it builds the jit'd ``shard_map`` train step over
a 1-D ``("data",)`` mesh — no model changes, redco-style ergonomics.
"""

from __future__ import annotations

import argparse
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis.retrace import RetraceSentinel
from repro.configs import get_config
from repro.distributed import compression as comp_lib
from repro.distributed.elastic import StragglerMonitor, elastic_resize
from repro.distributed.sharding import (data_batch_shardings,
                                        replicated_shardings)
from repro.launch.mesh import data_parallel_mesh, make_local_mesh
from repro.nn.lm import model as model_lib
from repro.train import data_pipeline, optimizer as opt_lib, steps
from repro.train.loop import train_loop


class MeshTrainer:
    """Data-parallel ``shard_map`` train step over a 1-D device mesh.

    Wraps an existing per-shard loss function and the loader's stacked
    batches (``NeighborLoader(shards=D)``) into a train step with
    ``train_loop``-compatible shape ``step(state, batch) -> (state,
    metrics)``:

      * **batch** shards: every leaf of the stacked batch pytree splits its
        leading shard axis over the ``data`` mesh axis (one loader shard
        per device);
      * **params replicate**: the TrainState enters and leaves with spec
        ``P()`` on every leaf;
      * **gradients reduce once**: the whole local-grad pytree goes through
        a single fused ``psum`` over ``data`` (or, with ``compression=``,
        through :func:`repro.distributed.compression.compressed_allreduce`
        — per-device error-feedback residuals live on the trainer, stacked
        along the shard axis, never in the checkpoint).

    The loss contract makes the sharded step *numerically identical* to
    single-device gradient accumulation over the same shards:
    ``loss_fn(params, shard_batch)`` returns ``(loss_sum, weight)`` — an
    unnormalised loss total and its weight (e.g. real-seed count, so -1
    pad seeds drop out via ``batch.seed_mask``). The step computes
    ``psum(grads)/psum(weight)`` and ``psum(loss_sum)/psum(weight)``:
    sums commute with the device split, so parity holds to float
    round-off (the tier-1 tests pin <=1e-5; observed exact).

    One trace serves every batch: the step is jit'd once, batches keep
    static shapes (the loader pads non-dividing seed tails), and the
    built-in :class:`RetraceSentinel` counts compilations —
    ``trainer.trace_count`` must stay 1 across an epoch.

    ``save``/``restore`` checkpoint the replicated state; ``restore`` goes
    through :func:`repro.distributed.elastic.elastic_resize`, so a run
    checkpointed on an N-device mesh continues bit-identically on this
    trainer's M-device mesh (error feedback restarts from zero residuals).
    """

    def __init__(self, loss_fn: Callable[[Any, Any], Tuple[jnp.ndarray,
                                                           jnp.ndarray]],
                 opt_cfg: "opt_lib.OptConfig", *,
                 mesh: Optional[Mesh] = None,
                 compression: Optional[str] = None,
                 compression_ratio: float = 0.01,
                 retrace_budget: Optional[int] = 1):
        self.loss_fn = loss_fn
        self.opt_cfg = opt_cfg
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        if len(self.mesh.axis_names) != 1:
            raise ValueError(f"MeshTrainer needs a 1-D data mesh, got axes "
                             f"{self.mesh.axis_names}")
        self.axis_name = self.mesh.axis_names[0]
        self.num_devices = self.mesh.devices.size
        if compression is not None and \
                compression not in comp_lib.COMPRESSION_METHODS:
            raise ValueError(
                f"compression must be None or one of "
                f"{comp_lib.COMPRESSION_METHODS}, got {compression!r}")
        self.compression = compression
        self.compression_ratio = float(compression_ratio)
        self._residual = None  # lazily built stacked (D, ...) zeros
        self._sentinel = RetraceSentinel(budget=retrace_budget)
        self._step = self._sentinel.wrap(jax.jit(self._build()),
                                         name="mesh_step")

    # ---- step construction ----
    def _build(self):
        axis = self.axis_name
        loss_fn = self.loss_fn
        opt_cfg = self.opt_cfg
        compression = self.compression
        ratio = self.compression_ratio

        def _local_grads(params, shard_batch):
            def local_loss(p):
                loss_sum, weight = loss_fn(p, shard_batch)
                return loss_sum, weight
            grad_fn = jax.value_and_grad(local_loss, has_aux=True)
            (loss_sum, weight), grads = grad_fn(params)
            return grads, loss_sum, weight

        def _finish(state, grads_sum, loss_sum, weight):
            weight = jnp.maximum(weight, 1e-12)
            grads = jax.tree_util.tree_map(lambda g: g / weight, grads_sum)
            state, metrics = opt_lib.apply_updates(state, grads, opt_cfg)
            metrics = dict(metrics)
            metrics["loss"] = loss_sum / weight
            return state, metrics

        if compression is None:
            def _shard_body(state, stacked):
                shard = jax.tree_util.tree_map(lambda l: l[0], stacked)
                grads, loss_sum, weight = _local_grads(state.params, shard)
                # one fused all-reduce: the grad pytree + the two loss
                # scalars reduce in a single psum of one flat vector (a
                # psum of a pytree binds one collective per leaf)
                flat, unflatten = ravel_pytree((grads, loss_sum, weight))
                grads, loss_sum, weight = unflatten(
                    jax.lax.psum(flat, axis))
                return _finish(state, grads, loss_sum, weight)

            return jax.shard_map(_shard_body, mesh=self.mesh,
                                 in_specs=(P(), P(axis)),
                                 out_specs=(P(), P()),
                                 check_vma=False)

        def _shard_body_compressed(state, stacked, residual):
            shard = jax.tree_util.tree_map(lambda l: l[0], stacked)
            local_res = jax.tree_util.tree_map(lambda l: l[0], residual)
            grads, loss_sum, weight = _local_grads(state.params, shard)
            loss_sum, weight = jax.lax.psum((loss_sum, weight), axis)
            grads, new_res = comp_lib.compressed_allreduce(
                grads, local_res, axis_name=axis, method=compression,
                ratio=ratio)
            state, metrics = _finish(state, grads, loss_sum, weight)
            residual = jax.tree_util.tree_map(lambda l: l[None], new_res)
            return state, metrics, residual

        return jax.shard_map(_shard_body_compressed, mesh=self.mesh,
                             in_specs=(P(), P(axis), P(axis)),
                             out_specs=(P(), P(), P(axis)),
                             check_vma=False)

    # ---- data/state placement ----
    def _check_stacked(self, batch):
        leaves = jax.tree_util.tree_leaves(batch)
        bad = [l.shape for l in leaves
               if l.ndim == 0 or l.shape[0] != self.num_devices]
        if bad:
            raise ValueError(
                f"stacked batch leading dim must equal the mesh size "
                f"{self.num_devices} (loader shards=); got leaf shapes "
                f"{bad[:3]} — build the loader with "
                f"shards={self.num_devices}")

    def shard_batch(self, batch):
        """Place a stacked batch: leading shard axis over the mesh."""
        self._check_stacked(batch)
        return jax.device_put(batch, data_batch_shardings(
            self.mesh, batch, self.axis_name))

    def replicate_state(self, state):
        """Place a TrainState replicated (spec P()) on the mesh."""
        return jax.device_put(state, replicated_shardings(self.mesh, state))

    def _init_residual(self, params):
        d = self.num_devices
        res = jax.tree_util.tree_map(
            lambda p: jnp.zeros((d,) + p.shape, jnp.float32), params)
        return jax.device_put(res, data_batch_shardings(
            self.mesh, res, self.axis_name))

    # ---- the train_loop-compatible step ----
    def step(self, state, batch):
        self._check_stacked(batch)
        if self.compression is None:
            return self._step(state, batch)
        if self._residual is None:
            self._residual = self._init_residual(state.params)
        state, metrics, self._residual = self._step(
            state, batch, self._residual)
        return state, metrics

    __call__ = step

    # ---- introspection (dispatch audits / retrace accounting) ----
    @property
    def trace_count(self) -> int:
        return self._sentinel.count("mesh_step")

    def step_jaxpr(self, state, batch):
        """The step's closed jaxpr (for audit_jaxpr / jaxpr_stats)."""
        if self.compression is None:
            return jax.make_jaxpr(self._build())(state, batch)
        residual = (self._residual if self._residual is not None
                    else self._init_residual(state.params))
        return jax.make_jaxpr(self._build())(state, batch, residual)

    # ---- checkpoint / elastic resize ----
    def save(self, ckpt_dir, step: int, state, **kw):
        from repro.distributed import checkpoint as ckpt_lib
        return ckpt_lib.save_checkpoint(ckpt_dir, step, state, **kw)

    def restore(self, ckpt_dir, abstract_state, *, step=None):
        """Restore onto *this* trainer's mesh (any saved mesh size).

        Params/opt state come back bit-identical and replicated; the
        compressor residual — per-device state, deliberately outside the
        checkpoint — restarts from zeros (elastic resize contract).
        """
        state, step = elastic_resize(ckpt_dir, abstract_state, self.mesh,
                                     step=step)
        self._residual = None
        return state, step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compressed-grads", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    opt_cfg = opt_lib.OptConfig(lr=args.lr, warmup_steps=10,
                                total_steps=args.steps)
    params = model_lib.init_model(jax.random.PRNGKey(0), cfg)
    state = opt_lib.init_state(params, opt_cfg)
    n_params = sum(l.size for l in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M "
          f"steps={args.steps} batch={args.batch}x{args.seq}")

    batches = data_pipeline.synthetic_batches(
        cfg, args.batch, args.seq, enc_len=args.seq)
    monitor = StragglerMonitor(num_hosts=1)
    if args.compressed_grads:
        from repro.distributed import compression as comp_lib
        step_c = jax.jit(steps.make_train_step_compressed(cfg, opt_cfg))
        residual = comp_lib.init_residual(params)

        def train_step(state, batch):
            nonlocal residual
            state, metrics, residual = step_c(state, batch, residual)
            return state, metrics
    else:
        train_step = jax.jit(steps.make_train_step(cfg, opt_cfg),
                             donate_argnums=(0,))

    out = train_loop(state, train_step, batches, num_steps=args.steps,
                     ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     monitor=monitor)
    first = out["history"][0][1] if out["history"] else float("nan")
    last = out["history"][-1][1] if out["history"] else float("nan")
    print(f"done: loss {first:.4f} -> {last:.4f}")
    return out


if __name__ == "__main__":
    main()
