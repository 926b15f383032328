"""Self-check of the yardstick on the CPU: the trace reduction on a small
recorded TPU trace, the work functions against hand counts, the graph
generator against the program's own store, and the spec's files."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT

TINY_TRACE = os.path.join(BENCH, "tests", "data", "tiny.xplane.pb")
SPANS = ("loader.next", "step.dispatch", "step.block")


# ---------------------------------------------------------------- trace
def test_interval_union_and_gaps():
    import trace_reduce as tr

    busy = tr.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (5, 5)])
    assert busy == [(0, 2.5), (3, 4)]
    assert tr.clip(busy, 1, 3.5) == [(1, 2.5), (3, 3.5)]
    assert tr.gaps(busy, -1, 6) == [(-1, 0), (2.5, 3), (4, 6)]


def test_reduce_recorded_tpu_trace():
    """A trace recorded on one v5e: three steps of a jitted SpMM kernel and
    matmul under the benchmark's own spans."""
    import trace_reduce as tr

    red = tr.reduce(TINY_TRACE, "bench.window", SPANS)
    spans, devices = tr.read_events(TINY_TRACE, SPANS + ("bench.window",))
    lo, hi = spans["bench.window"][0]
    assert red["devices"] == len(devices) == 1
    assert red["window_s"] == pytest.approx(hi - lo)
    ops = [(n, max(s, lo), min(e, hi)) for n, s, e in devices[0]
           if e > lo and s < hi]
    # busy is the union: no more than the summed durations, no less than
    # the longest op, and it leaves an idle share
    total = sum(e - s for _, s, e in ops)
    assert max(e - s for _, s, e in ops) <= red["busy_s"] <= total + 1e-12
    assert 0 < red["busy_s"] < red["window_s"]
    assert sum(red["ops_s"].values()) == pytest.approx(total)
    # the kernel shows under its own name, three launches long
    kern = [n for n in red["ops_s"] if "_spmm_ell_kernel" in n]
    assert kern, sorted(red["ops_s"])
    assert len([1 for n, _, _ in ops if n in kern]) == 3
    # every idle gap is labelled, the longest first
    assert red["idle_gaps"] and all(
        lab in SPANS + ("host",) for lab, _ in red["idle_gaps"])
    lens = [t for _, t in red["idle_gaps"]]
    assert lens == sorted(lens, reverse=True)
    assert sum(t for _, t in red["idle_gaps"]) <= (
        red["window_s"] - red["busy_s"] + 1e-9)


# ---------------------------------------------------------------- work
def test_spmm_work_hand_count():
    from harness import spec

    m = spec.load_module("metrics", "spmm_ell_roofline")
    aggs = [{"kind": "spmm", "edges": 10, "rows": 4, "width": 3, "heads": 1},
            {"kind": "gat", "edges": 99, "rows": 9, "width": 8, "heads": 2}]
    # 10 edges and 4 rows of 3 float32 elements: 42 elements
    assert m.work(aggs) == (42.0, 168.0)


def test_gat_work_hand_count():
    from harness import spec

    m = spec.load_module("metrics", "gat_attn_roofline")
    aggs = [{"kind": "gat", "edges": 5, "rows": 2, "width": 8, "heads": 2}]
    # per edge 6*2 + 2*8 = 28 flops; per row 8; bytes 4 * 7 * (8 + 2)
    assert m.work(aggs) == (5 * 28 + 2 * 8.0, 280.0)


def test_roofline_share_and_mfu_arithmetic():
    from harness import spec

    rec = {"steps": 2, "window_s": 4.0, "chips": 1, "step_flops": 2e12,
           "peaks": {"flops_per_s": 1e15, "hbm_bytes_per_s": 1e12},
           "trace": {"ops_s": {"_spmm_ell_kernel": 0.5}, "window_s": 4.0,
                     "busy_s": 3.0},
           "aggregations": [{"kind": "spmm", "edges": 1e9, "rows": 0,
                             "width": 25, "heads": 1}]}
    roof = spec.load_module("metrics", "spmm_ell_roofline").read(rec)
    # 1e11 bytes at 1e12 B/s = 0.1 s against 0.25 s of kernel per step
    assert roof == pytest.approx(40.0)
    assert spec.load_module("metrics", "spmm_ell_ms").read(rec) == 250.0
    assert spec.load_module("metrics", "gat_attn_ms").read(rec) is None
    assert spec.load_module("metrics", "step_mfu").read(
        rec) == pytest.approx(100 * 1e12 / 1e15)
    assert spec.load_module("metrics", "device_idle_share").read(
        rec) == pytest.approx(25.0)


def test_peak_hbm_adds_the_step_temporaries():
    from harness import spec

    peak = spec.load_module("metrics", "peak_hbm_gb")
    assert peak.read({"memory_peak_bytes": 1_390_000_000,
                      "step_temp_bytes": 11_520_000_000}) == pytest.approx(
        12.91)
    assert peak.read({"memory_peak_bytes": 1_390_000_000,
                      "step_temp_bytes": None}) is None


def test_sage_step_flops_hand_count():
    from harness import spec

    sage = spec.load_module("models", "sage")
    cfg = {"num_features": 2, "hidden": 3, "num_classes": 5, "num_layers": 2}
    # hop blocks: [null+seeds, hop 1, hop 2] real nodes / [hop 1, hop 2] edges
    counts = {"nodes": [1, 4, 6], "edges": [4, 6]}
    # layer 0: rows 5, edges 10, 2 -> 3: proj 2*2*5*2*3 = 120, agg 15*2
    # layer 1: rows 1, edges 4, 3 -> 5: proj 2*2*1*3*5 = 60, agg 5*3
    want = (120 + 30) + 120 + (60 + 15) + (120 + 15)
    assert sage.step_flops(cfg, counts) == want
    aggs = sage.aggregations(cfg, counts)
    assert [(a["rows"], a["edges"], a["width"]) for a in aggs] == [
        (5, 10, 2), (1, 4, 3)]


# ---------------------------------------------------------------- graph
def test_graph_matches_program_store():
    """The generator's reverse CSR is what the program's store derives
    from the same edges, and the seed alone fixes the graph."""
    from harness.graph import generate, program_store
    from repro.data.data import Data

    cfg = {"graph_generator": "uniform", "num_nodes": 500,
           "num_edges": 4000, "num_features": 3, "num_classes": 7,
           "num_train_nodes": 50}
    g = generate(cfg, 2**33 + 5)
    store = program_store(g)
    dst = np.repeat(np.arange(500), np.diff(g.indptr))
    want = Data(x=g.x, y=g.y, num_nodes=500,
                edge_index=np.stack([g.indices, dst])).get_rev_csr()
    got = store.get_rev_csr()
    for a, b in ((got.indptr, want.indptr), (got.indices, want.indices),
                 (got.edge_id, want.edge_id)):
        np.testing.assert_array_equal(a, b)
    again = generate(cfg, 2**33 + 5)
    np.testing.assert_array_equal(again.x, g.x)
    np.testing.assert_array_equal(again.indices, g.indices)
    assert not np.array_equal(generate(cfg, 1).indices, g.indices)
    assert len(np.unique(g.train_nodes)) == 50


def _pairs(adj):
    """A relation's edges as sorted (src, dst) rows."""
    dst = np.repeat(np.arange(len(adj.indptr) - 1), np.diff(adj.indptr))
    pairs = np.stack([adj.indices, dst], 1)
    return pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]


def test_hetero_graph_matches_program_store():
    """Every relation's reverse CSR, reverses and the symmetric relation
    included, is what the program's store derives from the same edges;
    reverses hold the flipped edges; the seed alone fixes the graph."""
    from harness.graph import generate
    from harness.hetero import program_store, relations
    from repro.data.data import HeteroData

    cfg = {"graph_generator": "uniform_hetero",
           "node_types": {"paper": {"num_nodes": 300, "num_features": 3},
                          "author": {"num_nodes": 70000,
                                     "num_features": 2}},
           "edge_types": [["author", "writes", "paper", 5000],
                          ["paper", "cites", "paper", 2000]],
           "reverse_edges": True, "target_type": "paper",
           "num_classes": 4, "num_train_nodes": 30}
    g = generate(cfg, 2**33 + 5)
    assert list(g.adj) == relations(cfg) == [
        ("author", "writes", "paper"), ("paper", "rev_writes", "author"),
        ("paper", "cites", "paper")]
    store = program_store(g)
    plain = HeteroData()
    for t, x in g.x.items():
        plain.add_nodes(t, x)
    for rel, a in g.adj.items():
        dst = np.repeat(np.arange(len(a.indptr) - 1), np.diff(a.indptr))
        plain.add_edges(rel, np.stack([a.indices, dst]))
        got, want = store.get_rev_csr(rel), plain.get_rev_csr(rel)
        for x, y in ((got.indptr, want.indptr), (got.indices, want.indices),
                     (got.edge_id, want.edge_id)):
            np.testing.assert_array_equal(x, y)
    writes = _pairs(g.adj[("author", "writes", "paper")])
    rev = _pairs(g.adj[("paper", "rev_writes", "author")])[:, ::-1]
    np.testing.assert_array_equal(
        rev[np.lexsort((rev[:, 1], rev[:, 0]))], writes)
    cites = _pairs(g.adj[("paper", "cites", "paper")])
    assert len(cites) == 4000
    flipped = cites[:, ::-1]
    np.testing.assert_array_equal(
        flipped[np.lexsort((flipped[:, 1], flipped[:, 0]))], cites)
    again = generate(cfg, 2**33 + 5)
    for rel in g.adj:
        np.testing.assert_array_equal(again.adj[rel].indices,
                                      g.adj[rel].indices)
    np.testing.assert_array_equal(again.x["author"], g.x["author"])
    other = generate(cfg, 1)
    assert not np.array_equal(other.adj[("paper", "cites", "paper")].indices,
                              cites)
    assert len(np.unique(g.train_nodes)) == 30 and g.y.shape == (300,)


def test_rsage_on_one_type_counts_as_sage():
    """On one node type and one relation the heterogeneous SAGE's work is
    the homogeneous SAGE's."""
    from harness import spec

    sage = spec.load_module("models", "sage")
    rsage = spec.load_module("models", "rsage")
    counts = {"nodes": [1, 4, 6], "edges": [4, 6]}
    cfg = {"num_features": 2, "hidden": 3, "num_classes": 5, "num_layers": 2}
    rel = ("n", "to", "n")
    hcfg = dict(cfg, node_types={"n": {"num_nodes": 9, "num_features": 2}},
                edge_types=[["n", "to", "n", 9]], reverse_edges=False)
    hcounts = {"nodes": {"n": counts["nodes"]},
               "edges": {rel: counts["edges"]}}
    assert rsage.step_flops(hcfg, hcounts) == sage.step_flops(cfg, counts)
    got = rsage.aggregations(hcfg, hcounts)
    assert [dict(a, relation=None) for a in got] == [
        dict(a, relation=None) for a in sage.aggregations(cfg, counts)]
    assert {a["relation"] for a in got} == {"n__to__n"}


def test_hetero_record_counts_each_relation():
    """The per-layer record of a heterogeneous cell carries one aggregation
    per layer and relation, from the batch's real rows and edges."""
    import run
    from conftest import toy_cell
    from harness import spec
    from harness.hetero import rel_key, relations
    from harness.train import Spans

    cell = toy_cell("rsage-toy.train")
    session = run.setup(cell, 2**31 + 7, Spans())
    session.trainer.close()
    rec = run.per_layer_record(
        session, {"steps": 4, "window_s": 2.0, "spans_s": {}}, None, None,
        0, None, "TPU v5 lite")
    rels = [rel_key(r) for r in relations(cell.config)]
    aggs = rec["aggregations"]
    assert [(a["layer"], a["relation"]) for a in aggs] == [
        (layer, r) for layer in range(2) for r in rels]
    shard = session.shards[0][0]
    for a in aggs:
        rel = next(r for r in relations(cell.config) if rel_key(r) == a[
            "relation"])
        budget = sum(shard["edges_per_hop"][rel][:2 - a["layer"]])
        assert 0 <= a["edges"] <= budget
        if rel[2] == "paper":
            assert a["edges"] > 0
        elif a["layer"] == 1:  # the last layer reads edges into seeds only
            assert a["edges"] == 0
    assert rec["step_flops"] > 0
    assert 0 < spec.load_module("metrics", "step_mfu").read(rec) < 100


# ---------------------------------------------------------------- spec
def test_every_cell_resolves_by_name():
    from harness import spec

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        spec.load_module("models", cell.config["model"])
        spec.load_module("graphs", cell.config["graph_generator"])
        assert cell.limits["limits"] and set(cell.limits["limits"]) <= {
            "loss_gap", "grad_gap", "update_gap"}
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.load_module("metrics", m["name"]).read)
    assert spec.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v9 imaginary")
