import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh (16x16 single-pod or 2x16x16
two-pod), the sharded step function (train / prefill / decode per the shape
kind), lowers it against pure ShapeDtypeStruct inputs, compiles, and records:

  * ``memory_analysis()``   — per-device bytes (proves it fits)
  * ``cost_analysis()``     — HLO FLOPs / bytes for the roofline
  * collective byte counts  — parsed from the optimized HLO text
  * the three roofline terms + dominant bottleneck (§Roofline)

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]
"""

import argparse
import json
import re
import time
import traceback
from contextlib import nullcontext as _nullcontext
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, canonical, get_config
from repro.distributed import sharding as shard_lib
from repro.launch import hlo_analysis, jaxpr_stats
from repro.launch import specs as specs_lib
from repro.launch.mesh import chip_peaks, make_production_mesh
from repro.train import steps as steps_lib

# The production meshes are TPU v5e pods; the dry run compiles on host
# devices, so the target chip is named here rather than read off a device.
TARGET_DEVICE_KIND = "TPU v5 lite"

_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2, "u16": 2, "f8e4m3": 1,
    "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b(f32|bf16|f16|f64|s64|s32|s16|s8|u64|u32|u16|u8|"
                       r"pred|f8e4m3|f8e5m2)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def parse_collectives(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in the optimized HLO."""
    out = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"=\s+[^ ]+\s+([a-z0-9-]+)\(", stripped)
        if not m:
            continue
        op = m.group(1)
        # normalise start/done pairs (async collectives) to the base op;
        # count only the -start (or the sync form) to avoid double counting.
        base = op.replace("-start", "")
        if base not in _COLLECTIVES or op.endswith("-done"):
            continue
        shapes = _SHAPE_RE.findall(stripped)
        if not shapes:
            continue
        lhs, rhs = shapes[0], shapes[1:]
        operands = rhs if rhs else [lhs]
        nbytes = sum(_shape_bytes(dt, dims) for dt, dims in operands)
        out[base]["count"] += 1
        out[base]["bytes"] += nbytes
    return out


def build_step(arch: str, shape_name: str, mesh, profile: str = "2d",
               remat=True):
    """Returns (jit_fn, abstract_args) for the cell."""
    cfg = get_config(arch)
    shape = specs_lib.SHAPES[shape_name]
    batch = specs_lib.input_specs(arch, shape_name)
    batch_sh = shard_lib.batch_shardings(mesh, batch, profile)

    if shape.kind == "train":
        state, ocfg = specs_lib.abstract_state(cfg)
        state_sh = shard_lib.state_shardings(mesh, state, profile)
        fn = steps_lib.make_train_step(cfg, ocfg, remat=remat)
        jit_fn = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None), donate_argnums=(0,))
        return jit_fn, (state, batch)

    params = specs_lib.abstract_params(cfg)
    params_sh = shard_lib.param_shardings(mesh, params, profile)
    cache = specs_lib.abstract_cache(cfg, shape)
    cache_sh = shard_lib.cache_shardings(mesh, cache, shape.global_batch)

    if shape.kind == "prefill":
        fn = steps_lib.make_prefill_step(cfg)
        jit_fn = jax.jit(fn, in_shardings=(params_sh, batch_sh, cache_sh),
                         out_shardings=(None, cache_sh), donate_argnums=(2,))
        return jit_fn, (params, batch, cache)

    fn = steps_lib.make_decode_step(cfg)
    tok = batch["tokens"]
    tok_sh = shard_lib.batch_shardings(mesh, {"t": tok})["t"]
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    pos_sh = NamedSharding(mesh, P())
    jit_fn = jax.jit(fn, in_shardings=(params_sh, tok_sh, cache_sh, pos_sh),
                     out_shardings=(None, cache_sh), donate_argnums=(2,))
    return jit_fn, (params, tok, cache, pos)


def roofline_terms(flops: float, bytes_acc: float, coll: dict,
                   n_chips: int) -> dict:
    """Three roofline terms in seconds.

    flops/bytes are *global* (jaxpr-level), so divide by chips. HLO
    collective payloads are *per-device* shard sizes, so the per-chip link
    time is simply sum(local_payload * ring_factor) / link_bw; we also report
    collective_bytes scaled to global so the prescribed
    ``collective_bytes / (chips * link_bw)`` formula yields the same time.
    Ring all-reduce moves ~2x its payload per link; other collectives ~1x.
    """
    ring = {"all-reduce": 2.0}
    local_link_bytes = sum(
        v["bytes"] * ring.get(name, 1.0) for name, v in coll.items())
    coll_bytes_global = local_link_bytes * n_chips
    peaks = chip_peaks(TARGET_DEVICE_KIND)
    t_compute = flops / n_chips / peaks["peak_flops_bf16"]
    t_memory = bytes_acc / n_chips / peaks["hbm_bytes_per_s"]
    t_coll = coll_bytes_global / n_chips / peaks["ici_bytes_per_s_per_link"]
    dom = max(("compute", t_compute), ("memory", t_memory),
              ("collective", t_coll), key=lambda kv: kv[1])[0]
    return {
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dom,
        "collective_bytes": coll_bytes_global,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path, profile: str = "2d", remat=True) -> dict:
    cfg = get_config(arch)
    shape = specs_lib.SHAPES[shape_name]
    skip = specs_lib.cell_applicable(cfg, shape)
    mesh_tag = "2pod" if multi_pod else "1pod"
    rec = {"arch": cfg.name, "shape": shape_name, "mesh": mesh_tag,
           "profile": profile, "remat": str(remat),
           "status": "skipped", "reason": skip}
    if skip:
        return rec

    n_chips = 512 if multi_pod else 256
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    # activation sharding constraints are part of the optimized profiles;
    # the '2d' baseline stays constraint-free (paper-faithful naive SPMD)
    act_ctx = (shard_lib.activation_sharding(mesh, profile)
               if profile != "2d" else _nullcontext())
    with mesh, act_ctx:
        jit_fn, args = build_step(arch, shape_name, mesh, profile, remat)
        # exact global FLOPs/bytes from the jaxpr (scan-aware; XLA:CPU
        # cost_analysis counts while bodies once — see jaxpr_stats docstring)
        stats = jaxpr_stats.step_stats(jit_fn, *args)
        lowered = jit_fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    coll = hlo_analysis.collective_stats(hlo)
    flops = float(stats["total_flops"])
    bytes_acc = float(stats["major_bytes"])
    mflops = specs_lib.model_flops(cfg, shape)
    terms = roofline_terms(flops, bytes_acc, coll, n_chips)

    mem_rec = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes", "peak_memory_in_bytes"):
        try:
            mem_rec[attr] = int(getattr(mem, attr))
        except Exception:
            pass

    rec.update(
        status="ok", n_chips=n_chips,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        hlo_flops=flops, hlo_bytes=bytes_acc,
        dot_flops=float(stats["dot_flops"]),
        elementwise_flops=float(stats["elementwise_flops"]),
        xla_cost_flops=float(cost.get("flops", 0.0)),
        xla_cost_bytes=float(cost.get("bytes accessed", 0.0)),
        model_flops=mflops,
        useful_ratio=(mflops / flops if flops else None),
        collectives=coll, memory=mem_rec,
        params=cfg.param_count(), active_params=cfg.active_param_count(),
        bytes_per_device=(
            (mem_rec.get("argument_size_in_bytes", 0)
             + mem_rec.get("temp_size_in_bytes", 0)
             - mem_rec.get("alias_size_in_bytes", 0)) / n_chips
            if mem_rec else None),
        **terms,
    )
    return rec


def cells(multi_pod: bool):
    for arch in ARCH_IDS:
        for shape_name in specs_lib.SHAPES:
            yield arch, shape_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--profile", default="2d", choices=["2d", "fsdp", "ep"])
    ap.add_argument("--remat", default="true",
                    choices=["true", "false", "dots"])
    ap.add_argument("--moe-impl", default="scatter",
                    choices=["scatter", "gather"])
    ap.add_argument("--attn-impl", default="rect", choices=["rect", "tri"])
    args = ap.parse_args()
    remat = {"true": True, "false": False, "dots": "dots"}[args.remat]
    from repro.nn.lm import moe as moe_mod
    moe_mod.set_moe_impl(args.moe_impl)
    from repro.kernels.flash_attention import ops as attn_ops
    attn_ops.set_attention_impl(args.attn_impl)

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    todo = (list(cells(args.multi_pod)) if args.all
            else [(canonical(args.arch), args.shape)])
    for arch, shape_name in todo:
        tag = "2pod" if args.multi_pod else "1pod"
        variant = ""
        if args.profile != "2d":
            variant += f"__{args.profile}"
        if args.remat != "true":
            variant += f"__remat-{args.remat}"
        if args.moe_impl != "scatter":
            variant += f"__moe-{args.moe_impl}"
        if args.attn_impl != "rect":
            variant += f"__attn-{args.attn_impl}"
        path = out_dir / (f"{canonical(arch)}__{shape_name}__{tag}"
                          f"{variant}.json")
        if path.exists() and not args.force:
            print(f"[skip cached] {path.name}")
            continue
        print(f"[run] {arch} x {shape_name} x {tag}{variant}", flush=True)
        try:
            rec = run_cell(arch, shape_name, args.multi_pod, out_dir,
                           profile=args.profile, remat=remat)
        except Exception as e:  # record failures — they are bugs to fix
            rec = {"arch": arch, "shape": shape_name, "mesh": tag,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        path.write_text(json.dumps(rec, indent=2))
        status = rec["status"]
        extra = ""
        if status == "ok":
            extra = (f" compile={rec['compile_s']}s dom={rec['dominant']}"
                     f" tc={rec['t_compute_s']:.4f} tm={rec['t_memory_s']:.4f}"
                     f" tl={rec['t_collective_s']:.4f}")
        print(f"[done] {path.name}: {status}{extra}", flush=True)


if __name__ == "__main__":
    main()
