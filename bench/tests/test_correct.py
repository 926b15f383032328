"""The comparison that decides ``correct`` fails what it must.

Each test drives a whole run of a toy-sized cell on the CPU (the look for
a chip skipped), with the timed path broken underneath, and sees
``correct`` come out false; the unbroken run comes out true. The control,
the reference computed in bfloat16 in the program's place, must fail each
cell's limits too. The cells are the benchmark's GAT cell and the
heterogeneous fixture ``rsage-toy.train`` (``data/hetero/``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH, HETERO_ROOT, toy_cell

SEED = 2**31 + 99
CELLS = ["gat-products.train", "rsage-toy.train"]


def _run(cell, **hooks):
    import run

    return run.run_cell(cell, SEED, 0.5, False, require_tpu=False, **hooks)


def _failed(result):
    return sorted(k for k, c in result["checks"].items()
                  if not c["value"] <= c["limit"])


def _without_seeds(batch, slots):
    """``batch`` with the seeds at ``slots`` marked as padding."""
    if hasattr(batch, "n_id_dict"):
        n_id = dict(batch.n_id_dict)
        n_id[batch.seed_type] = n_id[batch.seed_type].at[slots].set(-1)
        return dataclasses.replace(batch, n_id_dict=n_id)
    return dataclasses.replace(batch, n_id=batch.n_id.at[slots].set(-1))


def _altered_row(batch):
    """``batch`` with one feature row changed: a seed's, or on a typed
    graph the first author's."""
    if hasattr(batch, "x_dict"):
        x = dict(batch.x_dict)
        x["author"] = x["author"].at[1].add(1.0)
        return dataclasses.replace(batch, x_dict=x)
    row = batch.seed_slots[0]
    return dataclasses.replace(batch, x=batch.x.at[row].add(1.0))


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    result = _run(toy_cell(workload))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_state_left_unchanged_fails(workload):
    def hook(step):
        def unchanged(state, batch):
            keep = jax.tree_util.tree_map(jnp.copy, state)
            _, loss = step(state, batch)
            return keep, loss
        return unchanged

    result = _run(toy_cell(workload), step_hook=hook)
    assert not result["correct"]
    assert {"grad_gap", "update_gap"} <= set(_failed(result))


@pytest.mark.parametrize("workload", CELLS)
def test_half_batch_fails(workload):
    """Half of the seeds left out of the loss, the mean over the rest."""
    def hook(loss_fn):
        def half(params, batch):
            drop = batch.seed_slots[batch.seed_slots.shape[0] // 2:]
            return loss_fn(params, _without_seeds(batch, drop))
        return half

    result = _run(toy_cell(workload), loss_hook=hook)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_altered_batch_fails(workload, monkeypatch):
    """One feature row altered where the loader produces it."""
    from harness import train

    produce = train.Trainer._epochs

    def altered(self):
        for b in produce(self):
            yield _altered_row(b)

    monkeypatch.setattr(train.Trainer, "_epochs", altered)
    result = _run(toy_cell(workload))
    assert not result["correct"]
    assert "batch_mismatches" in _failed(result)


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_limits(workload):
    """The control, the reference computed in bfloat16 (the precision below
    the configuration's float32 at default precision) in the program's
    place, fails the cell's limits."""
    import run
    from harness import correct as correct_lib
    from harness.train import Spans

    cell = toy_cell(workload)
    session = run.setup(cell, SEED, Spans())
    session.trainer.close()
    args = (session.model_mod, cell.config, session.graph, session.shards,
            session.params0)
    ref = correct_lib.reference_run(*args)
    ctrl = correct_lib.reference_run(*args, numerics="bfloat16")
    readings = correct_lib.compare(ctrl, ref)
    limits = cell.limits["limits"]
    assert any(readings[k] > limits[k] for k in limits), (readings, limits)


_DP_SCRIPT = r"""
import json, sys
sys.path[:0] = [{tests!r}, {bench!r}]
import jax
import conftest, run
cell = conftest.toy_cell("gat-products.train", "train-dp4-b1024-f15-10-5")
out = {{}}
out["sound"] = run.run_cell(cell, {seed}, 0.5, False, require_tpu=False)
jax.lax.psum = lambda x, axis_name, **kw: x   # the exchange left out
out["no_psum"] = run.run_cell(cell, {seed}, 0.5, False, require_tpu=False)
print(json.dumps({{k: [v["correct"], v["checks"]] for k, v in out.items()}}))
"""


def test_exchange_left_out_fails():
    """Four virtual CPU devices, the GAT cell under the four-way
    data-parallel mix: the mesh step without its gradient psum steps each
    device on its own shard."""
    code = _DP_SCRIPT.format(tests=os.path.join(BENCH, "tests"),
                             bench=BENCH, seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"][0], out["sound"][1]
    assert not out["no_psum"][0], out["no_psum"][1]


def test_misdirected_edge_fails(monkeypatch):
    """One ``writes`` edge pointed at another paper where the sampler makes
    it, so the loader packs it into every layout of the batch."""
    from repro.data.hetero_sampler import HeteroNeighborSampler

    sample = HeteroNeighborSampler.sample
    writes = ("author", "writes", "paper")

    def misdirected(self, *args, **kwargs):
        out = sample(self, *args, **kwargs)
        col = out.col[writes]
        col[0] = 2 if col[0] == 1 else 1
        return out

    monkeypatch.setattr(HeteroNeighborSampler, "sample", misdirected)
    result = _run(toy_cell("rsage-toy.train"))
    assert not result["correct"]
    assert "batch_mismatches" in _failed(result)


def test_hetero_cell_on_four_chips_is_refused():
    """The program's heterogeneous loader has no shards."""
    from harness import spec

    cell = dataclasses.replace(toy_cell("rsage-toy.train"), chips=4)
    with pytest.raises(spec.SpecError, match="one chip"):
        _run(cell)


def test_config_added_from_files_alone():
    """A second heterogeneous configuration (three types in a cycle, no
    reverses, a fanout list per relation, three layers) with its mix and
    limits, found by name: nothing in the harness names it."""
    import glob

    from harness import spec

    cell = spec.load_cell("rsage-cycle.train", root=HETERO_ROOT)
    for path in glob.glob(os.path.join(BENCH, "harness", "*.py")) + [
            os.path.join(BENCH, "run.py")]:
        with open(path) as f:
            assert "rsage" not in f.read(), path
    result = _run(cell)
    assert result["correct"], result["checks"]
    assert cell.config["model"] == "rsage"
