"""Benchmark entry point: one function per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [--only exp1,exp2,...]

Prints each table and a final ``name,us_per_call,derived`` CSV summary; all
payloads are also saved under artifacts/results/*.json.
"""

from __future__ import annotations

import argparse
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all")
    args = ap.parse_args()
    only = args.only.split(",") if args.only != "all" else None

    from benchmarks import chaos_bench, controller_bench, exp1_accuracy, exp2_placement
    from benchmarks import exp3456, exp7_ablations, kernel_bench, kernels_bench
    from benchmarks import load_harness, placement_bench, roofline_report, serve_bench
    from benchmarks import training_bench

    stages = {
        "exp1": exp1_accuracy.main,
        "exp2": exp2_placement.main,
        "placement_search": lambda: placement_bench.main(["--quick"]),
        "training_engine": lambda: training_bench.main(["--quick"]),
        "serving": lambda: serve_bench.main(["--quick"]),
        "load_harness": lambda: load_harness.main(["--quick"]),
        "controller": lambda: controller_bench.main(["--quick"]),
        "chaos": lambda: chaos_bench.main(["--quick"]),
        "exp3": exp3456.exp3_interpolation,
        "exp4": exp3456.exp4_extrapolation,
        "exp5": exp3456.exp5_unseen_patterns,
        "exp6": exp3456.exp6_unseen_benchmarks,
        "exp7": exp7_ablations.main,
        # renamed from "kernels": this is the per-op microbenchmark lane, as
        # opposed to "kernel_sweep" (the fused sweep kernel's gated bench)
        "kernels_micro": kernels_bench.main,
        "kernel_sweep": lambda: kernel_bench.main(["--quick"]),
        "roofline": lambda: (roofline_report.main("single"), roofline_report.main("multi")),
    }
    timings = []
    failures = []
    for name, fn in stages.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            fn()
            timings.append((name, time.time() - t0, "ok"))
        except Exception as e:
            traceback.print_exc()
            timings.append((name, time.time() - t0, f"FAIL:{type(e).__name__}"))
            failures.append(name)

    print("\nname,us_per_call,derived")
    for name, secs, status in timings:
        print(f"{name},{secs * 1e6:.0f},{status}")
    if failures:
        raise SystemExit(f"failed stages: {failures}")


if __name__ == "__main__":
    main()
