"""Readings that set a cell's limits: the program, its witness, its
controls and the planted faults, each against the plain reference, on
many seeds.

    python3 bench/control.py --workload <cell> --seeds 11,12,13,...

For each seed, in one process on the cell's chips: set-up as a run makes
it (the program's first three steps on the seed's graph and batches),
then the reference of those steps, and beside it, each put in the
program's place and compared with the reference in the same way:

  * ``program``   the program's own readings (the lower reading);
  * ``default``   the reference at the program's numerics, float32 with
                  JAX's default TPU matmul precision (a witness: where it
                  reads as the program does, the program's gap is the
                  rounding of its numerics);
  * ``bfloat16``  the reference wholly in bfloat16, the precision below
                  the configurations' float32 at default precision (the
                  control: the upper reading);
  * ``int8``      the reference with int8 matmul operands, one step below
                  the bfloat16 operands of the program's default-precision
                  matmuls (read beside the control);
  * ``half``      the reference with half of each shard's seeds left out,
                  the mean taken over the rest (a planted fault);
  * ``no_psum``   on a mesh: the reference of shard 0 alone, as a device
                  whose gradient exchange was left out would step.

A step that returns its state unchanged reads 1 on ``grad_gap`` and
``update_gap`` by construction and needs no run. One JSON line per seed,
with the leaf that gave each kind's worst ``grad_gap`` and ``update_gap``,
and a last line with the largest program and witness readings and the
smallest control and fault readings. The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp

import run
from harness import correct as correct_lib
from harness import spec
from harness.train import Spans

READINGS = ("loss_gap", "grad_gap", "update_gap")


def half_seeds(w):
    """Seed weights with the second half of the shard's seeds zeroed."""
    keep = jnp.arange(w.shape[0]) < w.shape[0] // 2
    return jnp.where(keep, w, 0.0)


def worst_leaves(got, ref, params0):
    """The leaf paths with the largest gradient and change gaps."""
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params0)[0]]
    out = {}
    for key in ("g1", "delta"):
        gaps = correct_lib.leaf_gaps(got[key], ref[key])
        i = max(range(len(gaps)), key=gaps.__getitem__)
        out[key] = [names[i], gaps[i]]
    return out


def readings_for_seed(cell, seed: int):
    session = run.setup(cell, seed, Spans())
    session.trainer.close()
    session.trainer.state = None
    args = (session.model_mod, cell.config, session.graph, session.shards,
            session.params0)
    ref = correct_lib.reference_run(*args)
    runs = {"program": correct_lib.program_readings(
        session.losses, session.params0, session.mu1, session.params3,
        cell.config)}
    for numerics in ("default", "bfloat16", "int8"):
        runs[numerics] = correct_lib.reference_run(*args, numerics=numerics)
    runs["half"] = correct_lib.reference_run(*args, seed_weight=half_seeds)
    if cell.chips > 1:
        runs["no_psum"] = correct_lib.reference_run(*args, shards=[0])
    out = {"seed": seed}
    for kind, got in runs.items():
        out[kind] = correct_lib.compare(got, ref)
        out[kind]["worst"] = worst_leaves(got, ref, session.params0)
    out["batch_mismatches"] = sum(
        session.kind.batch_mismatches(session.graph, s)
        for step in session.shards for s in step)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one process for all")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        run.check_devices(cell.chips)
    except run.NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 3
    run.enable_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        row = readings_for_seed(cell, seed)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for kind in ("program", "default"):
        summary[f"{kind}_max"] = {k: max(r[kind][k] for r in rows)
                                  for k in READINGS}
    for kind in ("bfloat16", "int8", "half", "no_psum"):
        if kind in rows[0]:
            summary[f"{kind}_min"] = {k: min(r[kind][k] for r in rows)
                                      for k in READINGS}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
