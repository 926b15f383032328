"""On-chip training benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine that holds the cell's chips:

1. fail (exit 3, no result) unless JAX's devices are TPUs, at least as many
   as the cell asks for, with the Pallas kernels on;
2. turn on JAX's persistent compilation cache (``<checkout>/.jax_cache``,
   or ``$JAX_COMPILATION_CACHE_DIR``);
3. build the cell's graph and weights from ``--seed``;
4. set-up: the program's first optimizer steps, through the window's own
   call, compile the step and are kept for the comparison; a few more
   steps let the loader's queue reach its steady depth;
5. measure ``--seconds`` of whole optimizer steps with the real loader
   running (with ``--trace 1`` under the JAX profiler);
6. compare the first steps with the plain reference (``harness.correct``)
   and print one JSON result line, last on stdout.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (``bench/metrics/<name>.py``).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

from harness import correct as correct_lib  # noqa: E402
from harness import graph_kind, spec  # noqa: E402
from harness.train import SPANS, WINDOW_SPAN, Spans, Trainer  # noqa: E402

TRACE_DIR = os.path.join(BENCH, ".out", "trace")


class NoChip(Exception):
    """JAX sees no usable accelerator for this cell."""


def note(msg: str) -> None:
    """A progress line on stderr, with seconds since the process began."""
    print(f"bench: {time.perf_counter() - T0:8.2f} s  {msg}", file=sys.stderr,
          flush=True)


def check_devices(chips: int):
    import jax

    from repro.kernels import USE_PALLAS_ENV, use_pallas

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees "
                     f"{len(devices)}")
    if not use_pallas():
        raise NoChip(f"{USE_PALLAS_ENV}={os.environ.get(USE_PALLAS_ENV)!r} "
                     f"turns the Pallas kernels off")
    return devices


def enable_compile_cache() -> str:
    import jax

    from repro.launch import compile_cache

    path = compile_cache.enable(ROOT)
    # cache every program, however quick to compile, so that a second run
    # of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Session:
    """One cell's program, set up from a seed, with what its first steps
    left for the comparison."""
    cell: Any
    kind: graph_kind.GraphKind
    model_mod: Any
    graph: Any
    trainer: Trainer
    params0: Any
    losses: List[float]
    mu1: Any
    params3: Any
    shards: List[List[Dict]]  # per check step, per shard host batch


def setup(cell, seed: int, spans: Spans, *,
          step_hook: Optional[Callable] = None,
          loss_hook: Optional[Callable] = None) -> Session:
    """Graph, weights and the program's first ``CHECK_STEPS`` steps."""
    import jax

    kind = graph_kind.of(cell.config)
    model_mod = spec.load_module("models", cell.config["model"])
    graph = kind.generate(cell.config, seed)
    note(f"graph: {graph.num_nodes} nodes, {graph.num_edges} edges")
    store = kind.program_store(graph)
    note("program store")
    init = jax.jit(lambda k: model_mod.init_params(k, cell.config))
    params = init(jax.random.PRNGKey(seed))
    want = jax.eval_shape(model_mod.program_model(cell.config).init,
                          jax.random.PRNGKey(0))
    if (jax.tree_util.tree_structure(params)
            != jax.tree_util.tree_structure(want)
            or any(a.shape != b.shape for a, b in zip(
                jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(want)))):
        raise spec.SpecError(f"bench/models/{cell.config['model']}.py makes "
                             f"weights that do not fit the program's model")
    params0 = jax.device_get(params)
    note("weights")
    trainer = Trainer(cell, kind, model_mod, store, graph, params, seed,
                      step_hook=step_hook, loss_hook=loss_hook)
    del params
    losses, shards, mu1 = [], [], None
    try:
        for k in range(correct_lib.CHECK_STEPS):
            batch, loss = trainer.step(spans)
            losses.append(float(loss))
            if k == 0:
                mu1 = jax.device_get(trainer.state.mu)
            shards.append(kind.host_shards(batch, cell.chips))
            del batch
            note(f"check step {k + 1}: loss {losses[-1]!r}")
        params3 = jax.device_get(trainer.state.params)
    except BaseException:
        trainer.close()
        raise
    return Session(cell, kind, model_mod, graph, trainer, params0, losses,
                   mu1, params3, shards)


def settle(session: Session, spans: Spans) -> None:
    """Steps until the loader's prefetch queue and stage pipeline are at
    their steady depth, so the window starts in steady state."""
    tr = session.cell.traffic
    for _ in range(int(tr["prefetch"]) + int(tr["pipeline_depth"])):
        session.trainer.step(spans)


def measure(session: Session, spans: Spans, seconds: float,
            trace: bool) -> Dict[str, Any]:
    """Whole optimizer steps for at least ``seconds``: one rate over all of
    them and all of the window."""
    import jax

    trainer = session.trainer
    spans.total.clear()
    compiled = trainer.trace_count
    losses, last = [], None
    scope = (jax.profiler.TraceAnnotation(WINDOW_SPAN) if trace
             else contextlib.nullcontext())
    t0 = time.perf_counter()
    with scope:
        while True:
            last, loss = trainer.step(spans)
            losses.append(loss)
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), last)
    return {"steps": len(losses), "window_s": window_s,
            "spans_s": dict(spans.total),
            "losses": [float(v) for v in losses],
            "compiles": trainer.trace_count - compiled,
            "last_batch": shapes}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        note(f"memory_stats {d}: {stats}")
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def verify(session: Session, window: Dict[str, Any]):
    """The numbers compared, each with its limit, and whether all hold."""
    ref = correct_lib.reference_run(
        session.model_mod, session.cell.config, session.graph,
        session.shards, session.params0)
    prog = correct_lib.program_readings(
        session.losses, session.params0, session.mu1, session.params3,
        session.cell.config)
    readings = correct_lib.compare(prog, ref)
    readings["batch_mismatches"] = sum(
        session.kind.batch_mismatches(session.graph, s)
        for step in session.shards for s in step)
    readings["window_compiles"] = window["compiles"]
    readings["nonfinite_losses"] = sum(
        not math.isfinite(v) for v in session.losses + window["losses"])
    limits = dict(session.cell.limits["limits"])
    for exact in ("batch_mismatches", "window_compiles", "nonfinite_losses"):
        limits.setdefault(exact, 0)
    checks = {name: {"value": readings[name], "limit": limit}
              for name, limit in limits.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def start_trace() -> None:
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)


def stop_trace() -> Dict[str, Any]:
    import jax

    import trace_reduce

    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    return trace_reduce.reduce(paths[0], WINDOW_SPAN, SPANS)


def per_layer_record(session: Session, window: Dict[str, Any],
                     trace: Optional[Dict], audit: Optional[Dict],
                     peak: int, temp: Optional[int],
                     device_kind: str) -> Dict[str, Any]:
    """What the per-layer metric readers read."""
    cfg = session.cell.config
    counts = [session.kind.real_counts(s)
              for step in session.shards for s in step]
    per_shard = [session.model_mod.aggregations(cfg, c) for c in counts]
    aggs = []
    for layer in range(len(per_shard[0])):
        rows = [p[layer] for p in per_shard]
        a = dict(rows[0])
        a["rows"] = sum(r["rows"] for r in rows) / len(rows)
        a["edges"] = sum(r["edges"] for r in rows) / len(rows)
        aggs.append(a)
    flops = sum(session.model_mod.step_flops(cfg, c) for c in counts)
    return {"cell": session.cell.name, "chips": session.cell.chips,
            "config": cfg, "steps": window["steps"],
            "window_s": window["window_s"], "spans_s": window["spans_s"],
            "seeds_per_step": session.trainer.seeds_per_step,
            "trace": trace, "audit": audit, "aggregations": aggs,
            "step_flops": flops / correct_lib.CHECK_STEPS,
            "peaks": spec.load_peaks(device_kind),
            "memory_peak_bytes": peak, "step_temp_bytes": temp}


def read_per_layer(cell, rec) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer:
        value = spec.load_module("metrics", m["name"]).read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, step_hook: Optional[Callable] = None,
        loss_hook: Optional[Callable] = None) -> Dict[str, Any]:
    """One run of one cell; returns the result object. ``require_tpu``
    False (tests only) skips the look for a chip."""
    cell = spec.load_cell(workload)
    return run_cell(cell, seed, seconds, trace, require_tpu=require_tpu,
                    step_hook=step_hook, loss_hook=loss_hook)


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, step_hook: Optional[Callable] = None,
             loss_hook: Optional[Callable] = None) -> Dict[str, Any]:
    import jax

    if require_tpu:
        devices = check_devices(cell.chips)
        enable_compile_cache()
    else:
        devices = jax.devices()
    spans = Spans(trace=trace)
    session = setup(cell, seed, spans, step_hook=step_hook,
                    loss_hook=loss_hook)
    try:
        if trace:
            start_trace()
        settle(session, spans)
        setup_s = time.perf_counter() - T0
        note("set-up done; window opens")
        window = measure(session, spans, seconds, trace)
        reduced = stop_trace() if trace else None
        peak = memory_peak_bytes(cell.chips)
        audit = session.trainer.audit(window["last_batch"]) if trace else None
        temp = (session.trainer.temp_bytes(window["last_batch"]) if trace
                else None)
    finally:
        session.trainer.close()
    session.trainer.state = None
    gc.collect()
    note(f"window: {window['steps']} steps in {window['window_s']:.3f} s")
    ok, checks = verify(session, window)
    note("reference compared")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak, "step_temp_bytes": temp}
    result: Dict[str, Any] = {"correct": ok, "attempted": window["steps"],
                              "failed": checks["nonfinite_losses"]["value"]}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        rec = per_layer_record(session, window, reduced, audit, peak,
                               temp, devices[0].device_kind)
        result["metrics"] = read_per_layer(cell, rec)
        result["device"] = device
        result["breakdown"] = {
            "device_ops": [[n, t] for n, t in reduced["device_ops"]],
            "idle_gaps": [[n, t] for n, t in reduced["idle_gaps"]]}
    else:
        seeds = window["steps"] * session.trainer.seeds_per_step
        result["metrics"] = {
            "train_seeds_per_s": {"value": seeds / window["window_s"],
                                  "unit": "seeds/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr, flush=True)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
