"""Benchmark aggregator: one function per paper table/claim.

Prints ``name,us_per_call,derived`` CSV rows (see per-module docstrings for
protocols). Every suite runs in this one process, so on a TPU the process
that holds the chip is the one that uses it. Any suite that raises makes the
run exit non-zero. The dry-run roofline table is not part of this run:
``python -m repro.launch.dryrun --all`` then ``python -m
benchmarks.roofline``.
"""

from __future__ import annotations

import os
import sys
import traceback


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.enable(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import (chaos_recovery, dist_scaling,
                            explainer_fidelity, fastpath_audit,
                            grouped_matmul_bench, sampler_throughput,
                            spmm_bench, store_scaling,
                            table12_compile_trim)

    suites = [
        ("table12_compile_trim", table12_compile_trim.run),
        ("sampler_throughput", sampler_throughput.run),
        ("store_scaling", store_scaling.run),
        ("grouped_matmul", grouped_matmul_bench.run),
        ("spmm", spmm_bench.run),
        ("spmm_loader_step", spmm_bench.run_loader_step),
        ("spmm_train_step", spmm_bench.run_train_step),
        ("spmm_hetero_step", spmm_bench.run_hetero_step),
        ("spmm_gat_step", spmm_bench.run_gat_step),
        ("spmm_hgt_step", spmm_bench.run_hgt_step),
        ("dist_scaling", dist_scaling.run),
        ("fastpath_audit", fastpath_audit.run),
        ("explainer_fidelity", explainer_fidelity.run),
        ("chaos_recovery", chaos_recovery.run),
    ]
    failed = []
    for name, fn in suites:
        print(f"# ---- {name} ----", flush=True)
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
