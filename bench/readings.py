"""Readings that set the limits of the output check, in one process.

    python3 bench/readings.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...] [--modes sound control half_batch ...]

For every mode and seed this runs the cell's driver as ``bench/run.py``
does (set-up, a window of ``--seconds``, the comparison with the plain
reference) and prints one JSON line with the numbers compared.  Modes:
``sound`` is the program as the configuration states it; ``control`` one
precision step below it (``benchlib.faults.control``); ``bf16_path`` the
program on its own bfloat16 precision path; ``highest`` and ``default``
the program with its matmuls at that JAX precision, witnesses of what the
TPU's default precision costs; any other mode is a fault of
``benchlib.faults`` planted under the timed path.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from benchlib import cells, faults  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--modes", nargs="+", default=["sound", "control"])
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)

    import jax

    devices = run.require_chips(jax, cell.chips)
    run.use_compile_cache()
    counter = run.CompileCounter(jax)
    for mode in args.modes:
        for seed in args.seeds:
            opts = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
            ctx = run.Context(cell, opts, jax, devices, counter)
            if mode == "bf16_path":
                ctx.precision = "bf16"
            elif mode == "control":
                control = faults.control(cell.config,
                                         jax.default_backend())
                ctx.reference_control = control.get("reference_operands")
                ctx.matmul_precision = control.get("matmul_precision",
                                                   ctx.matmul_precision)
            elif mode in ("highest", "default"):
                ctx.matmul_precision = mode
            elif mode != "sound":
                ctx.plant = faults.plant(mode)
            try:
                out = ctx.run_driver()
                line = {"mode": mode, "seed": seed,
                        "checks": {k: v for k, (v, _) in
                                   out["checks"].items()},
                        "failed": out["failed"],
                        "notes": out.get("notes"),
                        "end_to_end": out["end_to_end"]}
            except Exception as exc:  # a control that crashes has failed
                line = {"mode": mode, "seed": seed, "error": repr(exc)}
            print(json.dumps(line), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
