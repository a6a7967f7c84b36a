"""Shared helpers of the harness tests: a small configuration of the
program, a context that skips the look for a chip, and one driver run."""
from __future__ import annotations

import argparse
import copy
import json
import math
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402
from benchlib import cells, faults  # noqa: E402

SMALL = {"dim": 8, "num_rbf": 5, "num_fourier": 5, "num_blocks": 1}

TRAIN_TRAFFIC = {"driver": "train", "crystals": 12, "batch": 4, "buckets": 2,
                 "data_seed": 0, "total_steps": 100}
MD_TRAFFIC = {"driver": "md", "replicas": 4, "smallest": 8, "largest": 20,
              "data_seed": 0, "dt": 0.001, "skin": 0.5, "max_group": 2,
              "warm_steps": 2, "checked_steps": 2, "reference_block": 2}


def small_cell(monkeypatch, workload: str, traffic: dict,
               config: str | None = None) -> cells.Cell:
    """``workload``'s configuration and limits at the small widths, on
    ``traffic``: the program's preset is narrowed alike.  ``config`` names
    another ``BENCHMARK.json`` configuration to run under those limits."""
    from repro.configs import chgnet_mptrj as C

    cell = cells.load_cell(workload)
    base = cell.config
    if config is not None:
        entry = next(c for c in cells.benchmark()["configs"]
                     if c["name"] == config)
        base = cells._json(ROOT, entry["file"])
    cfg = dict(copy.deepcopy(base), **SMALL)
    preset = f"_SMALL_{cfg['preset']}"
    monkeypatch.setattr(C, preset,
                        getattr(C, cfg["preset"]).with_(**SMALL),
                        raising=False)
    cfg["preset"] = preset
    return cells.Cell(name=workload, chips=1, config=cfg, traffic=traffic,
                      limits=cell.limits, end_to_end=cell.end_to_end,
                      per_layer=cell.per_layer)


def drive(cell: cells.Cell, seed: int, mode: str = "sound",
          seconds: float = 1.0) -> dict:
    """One run of the cell's driver on the CPU, as ``bench/run.py`` makes
    it after its look for a chip; ``mode`` as in ``bench/readings.py``."""
    import jax

    opts = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    ctx = run.Context(cell, opts, jax, jax.devices()[:1],
                      run.CompileCounter(jax))
    if mode == "control":
        control = faults.control(cell.config,
                                 jax.default_backend())
        ctx.reference_control = control.get("reference_operands")
        ctx.matmul_precision = control.get("matmul_precision",
                                           ctx.matmul_precision)
    elif mode != "sound":
        ctx.plant = faults.plant(mode)
    out = ctx.run_driver()
    out["correct"] = all(math.isfinite(v) and v <= lim
                         for v, lim in out["checks"].values())
    return out


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
