"""Benchmark harness — one entry per paper table/figure + the roofline.

Prints ``name,us_per_call,derived`` CSV (one line per measurement).
    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: iteration,sampler,md,serve,"
                         "convergence,scaling,roofline,kernels,fault")
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes / fewer iters")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_convergence, bench_fault, bench_iteration, bench_kernels,
        bench_md, bench_sampler, bench_scaling, bench_serve, roofline,
    )

    suites = {
        "fault": lambda: bench_fault.run(quick=args.quick),
        "sampler": lambda: bench_sampler.run(),
        "kernels": lambda: bench_kernels.run(quick=args.quick),
        "md": lambda: bench_md.run(iters=3 if args.quick else 5),
        "serve": lambda: bench_serve.run(steps=10 if args.quick else 25),
        "iteration": lambda: bench_iteration.run(
            batch_size=8 if args.quick else 16),
        "convergence": lambda: bench_convergence.run(
            steps=40 if args.quick else 60),  # 60: ~15 min on 1 CPU core
        "scaling": lambda: bench_scaling.run(
            device_counts=(1, 2) if args.quick else (1, 2, 4)),
        "roofline": lambda: roofline.run(),
    }

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites.items():
        if only and name not in only:
            continue
        try:
            for row in fn():
                print(",".join(str(x) for x in row), flush=True)
        except Exception:  # noqa: BLE001 — report and continue
            failures += 1
            print(f"{name}_FAILED,0,error", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
