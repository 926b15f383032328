"""Fault-tolerant training loop: checkpoint/restart, stragglers, compression.

``train_loop`` is the production driver skeleton: resume-from-latest,
periodic (optionally async) checkpointing, per-step host timing into the
StragglerMonitor, optional error-feedback int8 gradient compression at the
pod boundary. ``SimulatedFailure`` lets tests kill the loop at an exact step
and assert bit-exact resume. Storage-layer faults compose from below: a
loader with ``on_batch_error="skip"`` simply yields fewer batches, the loop
rides an exhausted iterator out cleanly, and ``loader=`` snapshots the
loader's ``health`` counters into logs and the returned dict.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np

from repro import trace
from repro.analysis.retrace import RetraceSentinel
from repro.distributed import checkpoint as ckpt_lib
from repro.distributed import compression as comp_lib
from repro.distributed.elastic import StragglerMonitor
from repro.train import optimizer as opt_lib


class SimulatedFailure(RuntimeError):
    pass


LOADER_STAGES = ("sample", "gather", "pack")


def _loader_stage_means() -> str:
    """`` loader ms/batch: sample=.. gather=.. pack=..`` from the program's
    spans (``repro.trace``) while tracing is on; empty otherwise."""
    if not trace.enabled():
        return ""
    spans = trace.totals()["spans"]
    parts = []
    for stage in LOADER_STAGES:
        n, seconds = spans.get(f"loader.{stage}", (0, 0.0))
        if n:
            parts.append(f"{stage}={1e3 * seconds / n:.1f}")
    return f" loader ms/batch: {' '.join(parts)}" if parts else ""


def train_loop(state: opt_lib.TrainState,
               train_step: Callable,
               batches: Iterator[Any], *,
               num_steps: int,
               ckpt_dir: Optional[str] = None,
               ckpt_every: int = 50,
               async_ckpt: bool = False,
               keep: int = 3,
               monitor: Optional[StragglerMonitor] = None,
               fail_at: Optional[int] = None,
               log_every: int = 10,
               loader: Optional[Any] = None,
               retrace_budget: Optional[int] = None,
               log_fn: Callable = print) -> Dict[str, Any]:
    """Run ``num_steps`` steps (resuming from the latest checkpoint if any).

    Returns {'state': final_state, 'history': [(step, loss), ...],
    'loader_health': ..., 'trace_signatures': ...}. A loader running with
    ``on_batch_error="skip"`` yields fewer batches than seed batches under
    store faults; the loop treats an exhausted iterator as end-of-data
    (logged, not crashed) and, when ``loader`` is given, snapshots its
    ``health`` counters (retries, skipped batches, degraded rows) into the
    result and the periodic log.

    ``retrace_budget`` arms a :class:`RetraceSentinel` around
    ``train_step``: every call's abstract signature (batch pytree + leaf
    avals) is recorded, and a batch whose shapes/static aux force a fresh
    compilation beyond the budget raises :class:`RetraceError` with a
    leaf-level signature diff — loudly, instead of silently recompiling
    every step. ``None`` records without enforcing.
    """
    sentinel = RetraceSentinel(budget=retrace_budget)
    train_step = sentinel.wrap(train_step, name="train_step")
    start = 0
    if ckpt_dir is not None:
        latest = ckpt_lib.latest_step(ckpt_dir)
        if latest is not None:
            state = ckpt_lib.restore_checkpoint(ckpt_dir, latest, state)
            start = latest
            log_fn(f"[resume] from step {latest}")
    history = []
    pending = None
    for step in range(start, num_steps):
        try:
            batch = next(batches)
        except StopIteration:
            # skipped batches (loader on_batch_error="skip") can exhaust
            # the epoch early — end the run cleanly instead of crashing
            log_fn(f"[data] iterator exhausted at step {step} "
                   f"(skipped batches?) — stopping")
            break
        t0 = time.perf_counter()
        state, metrics = train_step(state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        if monitor is not None:
            monitor.record(0, dt)
        loss = float(metrics["loss"])
        history.append((step + 1, loss))
        if (step + 1) % log_every == 0:
            health = ("" if loader is None or not hasattr(loader, "health")
                      else f" health={dict(loader.health)}")
            log_fn(f"step {step + 1}: loss={loss:.4f} "
                   f"({dt * 1e3:.0f} ms){health}{_loader_stage_means()}")
        if ckpt_dir is not None and (step + 1) % ckpt_every == 0:
            if pending is not None:
                pending.join()
            pending = ckpt_lib.save_checkpoint(
                ckpt_dir, step + 1, state, keep=keep,
                async_write=async_ckpt)
        if fail_at is not None and (step + 1) == fail_at:
            if pending is not None:
                pending.join()
            raise SimulatedFailure(f"injected failure at step {step + 1}")
    if pending is not None:
        pending.join()
    loader_health = (dict(loader.health)
                     if loader is not None and hasattr(loader, "health")
                     else None)
    return {"state": state, "history": history,
            "loader_health": loader_health,
            "trace_signatures": sentinel.count("train_step")}


# EF-int8-compressed train steps live in repro.train.steps
# (make_train_step_compressed); the loop composes with them by carrying the
# residual pytree through `state.extras`-style threading in the caller.
