"""The program's tracer (``repro.trace``) and the loader's spans and
counters: off records nothing, on totals every thread's spans, the loader's
stage spans share a batch id, and tracing never changes a batch."""

import glob
import os
import sys
import threading

import jax
import numpy as np
import pytest

from repro import trace
from repro.data.data import Data
from repro.data.loader import NeighborLoader
from repro.train.loop import train_loop


@pytest.fixture(autouse=True)
def _tracer_off():
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


def _graph(rng, n=300, e=2400):
    x = rng.standard_normal((n, 8)).astype(np.float32)
    return Data(x=x, edge_index=np.stack([rng.integers(0, n, e),
                                          rng.integers(0, n, e)]),
                y=rng.integers(0, 4, n))


def _loader(data, **kw):
    args = dict(num_neighbors=[4, 3], batch_size=16, shuffle=True,
                prefetch=2, pipeline_depth=2, seed=7)
    args.update(kw)
    return NeighborLoader(data, data, **args)


def test_off_records_nothing_and_shares_one_noop():
    assert not trace.enabled()
    a, b = trace.span("x", batch=1), trace.span("y")
    assert a is b
    with a:
        trace.count("c", 5)
    assert trace.totals() == {"spans": {}, "counters": {}}


def test_on_totals_nested_spans_and_counters_from_threads():
    """More threads than cores, switching often: a lost update would show
    as a count short of the total."""
    trace.enable()
    workers, rounds = 2 * (os.cpu_count() or 4), 200

    def work():
        for _ in range(rounds):
            with trace.span("outer", batch=0):
                with trace.span("inner"):
                    trace.count("items", 2)

    threads = [threading.Thread(target=work) for _ in range(workers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    n = workers * rounds
    tot = trace.totals()
    assert tot["spans"]["outer"][0] == tot["spans"]["inner"][0] == n
    assert tot["spans"]["outer"][1] >= tot["spans"]["inner"][1] > 0
    assert tot["counters"] == {"items": 2 * n}
    # totals() hands out a copy
    tot["counters"]["items"] = 0
    assert trace.totals()["counters"]["items"] == 2 * n


def test_reset_clears_and_keeps_the_switch():
    trace.enable()
    with trace.span("s"):
        trace.count("c", 1)
    trace.reset()
    assert trace.totals() == {"spans": {}, "counters": {}}
    assert trace.enabled()
    trace.disable()
    with trace.span("s"):
        pass
    assert trace.totals()["spans"] == {}


def _host_spans(path):
    """{bare span name: [batch id or None]} from a profiler trace."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if not name.startswith("loader."):
                    continue
                ids = dict(ev.stats)
                batch = ids.get("batch")
                if batch is None and "#batch=" in ev.name:
                    batch = ev.name.split("#batch=", 1)[1].rstrip("#")
                out.setdefault(name, []).append(
                    None if batch is None else int(batch))
    return out


def test_pipelined_loader_spans_share_batch_ids(rng, tmp_path):
    loader = _loader(_graph(rng))
    trace.enable()
    with jax.profiler.trace(str(tmp_path)):
        batches = list(loader)
    n = len(batches)
    assert n == len(loader) > 2
    tot = trace.totals()["spans"]
    for stage in ("sample", "gather", "pack", "queue_put"):
        assert tot[f"loader.{stage}"][0] == n, stage
    assert tot["loader.wait"][0] == n + 1  # the last get takes the end mark
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = _host_spans(path)
    for stage in ("sample", "gather", "pack", "queue_put"):
        assert sorted(spans[f"loader.{stage}"]) == list(range(n)), stage


@pytest.mark.parametrize("depth,prefetch", [(2, 2), (1, 0)])
def test_counters_are_what_the_batches_hold(rng, depth, prefetch):
    loader = _loader(_graph(rng), pipeline_depth=depth, prefetch=prefetch)
    trace.enable()
    batches = list(loader)
    tot = trace.totals()
    edges = sum(int((np.asarray(b.e_id) >= 0).sum()) for b in batches)
    nbytes = sum(int(a.nbytes) for b in batches
                 for a in jax.tree_util.tree_leaves(b))
    assert tot["counters"] == {"loader.sampled_edges": edges,
                               "loader.put_bytes": nbytes}
    # real edges, not the sampler's padded budgets
    assert 0 < edges <= sum(sum(b.num_sampled_edges) for b in batches)


def test_hetero_loader_counts_every_edge_type(rng):
    from repro.data.data import HeteroData
    from repro.data.hetero_sampler import HeteroNeighborLoader

    ub = ("user", "buys", "item")
    ru = ("item", "rev_buys", "user")
    hd = HeteroData()
    hd.add_nodes("user", rng.standard_normal((40, 8)).astype(np.float32))
    hd.add_nodes("item", rng.standard_normal((60, 8)).astype(np.float32))
    edges = np.stack([rng.integers(0, 40, 200), rng.integers(0, 60, 200)])
    hd.add_edges(ub, edges)
    hd.add_edges(ru, edges[::-1])
    loader = HeteroNeighborLoader(
        hd, hd, num_neighbors={ub: [3, 2], ru: [3, 2]}, input_type="item",
        input_nodes=np.arange(16), batch_size=4, prefetch=2,
        pipeline_depth=2)
    trace.enable()
    batches = list(loader)
    tot = trace.totals()
    assert tot["spans"]["loader.pack"][0] == len(batches) == 4
    edges = sum(int((np.asarray(e) >= 0).sum()) for b in batches
                for e in b.e_id_dict.values())
    assert tot["counters"]["loader.sampled_edges"] == edges > 0


def test_batches_are_bit_identical_with_tracing_on(rng):
    data = _graph(rng)
    off = list(_loader(data))
    trace.enable()
    on = list(_loader(data))
    assert len(on) == len(off)
    for a, b in zip(off, on):
        la, ta = jax.tree_util.tree_flatten(a)
        lb, tb = jax.tree_util.tree_flatten(b)
        assert ta == tb
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_train_loop_logs_loader_stage_means_when_on(rng):
    loader = _loader(_graph(rng), prefetch=0, pipeline_depth=1)

    def step(state, batch):
        return state, {"loss": jax.numpy.float32(0.0)}

    logs = []
    train_loop(0, step, iter(loader), num_steps=2, log_every=1,
               log_fn=logs.append)
    assert logs and not any("loader ms/batch" in m for m in logs)
    trace.enable()
    logs.clear()
    train_loop(0, step, iter(loader), num_steps=2, log_every=1,
               log_fn=logs.append)
    assert all("loader ms/batch: sample=" in m and " gather=" in m
               and " pack=" in m for m in logs)
