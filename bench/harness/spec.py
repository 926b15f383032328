"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by name:

  * ``BENCHMARK.json`` ``configs[].file``      the configuration's sizes;
  * ``bench/models/<config "model">.py``       its program model and plain
                                               reference;
  * ``bench/traffic/<traffic>.json``           one traffic mix;
  * ``bench/limits/<workload>.json``           the limits that decide
                                               ``correct`` in that cell;
  * ``bench/metrics/<metric>.py``              one per-layer metric reader.

Adding a configuration, a mix, a cell or a metric adds files and entries
only; nothing here names any of them. A configuration with a
``node_types`` key is heterogeneous (``harness.hetero``); any other is
one node type and one edge type (``harness.graph``). The harness picks
the kind once, from the configuration (``harness.graph_kind``).

A model module (``bench/models/<name>.py``) provides:

  * ``program_model(cfg)``   the program's model, whose ``apply`` the
                             timed step calls;
  * ``init_params(key, cfg)``  weights from the seed, in the program
                             model's tree layout;
  * ``reference_loss(params, inp, cfg, numerics)``  (loss sum, seed
                             weight) of one shard in plain JAX, with
                             ``inp`` from the kind's ``reference_inputs``;
  * ``aggregations(cfg, counts)``  per layer (and, heterogeneous, per
                             relation) the real receiving rows, real
                             edges, width and heads of each aggregation;
  * ``step_flops(cfg, counts)``  forward and backward FLOPs of one
                             shard's step,

where ``counts`` is the kind's ``real_counts`` of a shard. Homogeneous,
``inp`` holds ``x``, ``src``, ``dst`` and ``valid`` as arrays and
``counts`` per-hop lists ``nodes`` and ``edges``; heterogeneous, each of
those is a dict by node type (``x``, ``nodes``) or by relation
(``src``, ``dst``, ``valid``, ``edges``; relations are
``(src, rel, dst)`` tuples), and ``nodes_per_hop``/``edges_per_hop``
are sorted ``(type or relation, per-hop counts)`` pairs. Both hold
``seed_slots``, ``y`` and ``w`` (1 for a real seed, 0 for padding).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """The benchmark's files do not describe the requested cell."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _read_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}") from None


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: Dict[str, Any], workload: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, with its mix and
    limits from ``<root>/bench/``."""
    bench = load_benchmark(root)
    bench_dir = os.path.join(root, os.path.basename(BENCH_DIR))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      f"{w['traffic']}.json"))
    if int(traffic.get("data_parallel", 1)) != int(w["chips"]):
        raise SpecError(f"{workload}: traffic {w['traffic']!r} is data-"
                        f"parallel over {traffic.get('data_parallel', 1)} "
                        f"devices but the cell asks for {w['chips']} chips")
    limits = _read_json(os.path.join(bench_dir, "limits", f"{workload}.json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, e2e_names)]
    return Cell(name=workload, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                limits=limits, end_to_end=e2e, per_layer=per_layer)


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold ``-``)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"missing file bench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(device_kind: str) -> Dict[str, Any]:
    """Published peaks of one chip of ``device_kind``; unknown is an error."""
    table = _read_json(os.path.join(BENCH_DIR, "peaks.json"))
    kinds = table["chips"]
    if device_kind not in kinds:
        raise SpecError(f"no published peaks for device kind "
                        f"{device_kind!r} in bench/peaks.json "
                        f"(have {sorted(kinds)})")
    return kinds[device_kind]
