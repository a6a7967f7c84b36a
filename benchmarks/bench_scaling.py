"""Scaling + load-balance benchmark (paper Fig. 9/10, DESIGN.md §6).

Two phases:

  A. Straggler analysis (host-only, deterministic, ENFORCED): on a
     skewed synthetic dataset, compare the per-step straggler ratio
     (max/mean predicted shard cost — the step-time multiplier the
     slowest shard imposes on a synchronous mesh) of three DP sharders:
       - naive: random global batch, contiguous equal-count split
         (DefaultSampler — the seed behaviour);
       - pair:  the paper's Fig. 4 smallest+largest pairing
         (LoadBalanceSampler);
       - lpt:   cost-model LPT bin packing (CostBalanceSampler).
     The bar ``mean straggler(lpt) < mean straggler(naive)`` must hold
     for every device count (exit code 1 otherwise) — CI runs this on
     every push.

  B. Throughput sweep (in this process, report-only on CPU where host
     "devices" share cores): atoms/s of the balanced StepPlan path vs
     the naive iterator over sub-meshes of ``jax.devices()`` — the first
     ``n`` devices for each swept ``n`` that exists.  One process holds
     every device, so on a TPU host the sweep times the chips, never a
     child's CPU fallback; on CPU, ``XLA_FLAGS=
     --xla_force_host_platform_device_count`` provides the devices.

    PYTHONPATH=src python benchmarks/bench_scaling.py --quick \
        --json bench_scaling.json
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.batching.balance import (  # noqa: E402
    crystal_slots_for, shard_cost_totals, straggler_ratio,
)
from repro.batching.cost import DEFAULT_COST_MODEL  # noqa: E402
from repro.data import SyntheticConfig, make_dataset  # noqa: E402
from repro.data.sampler import (  # noqa: E402
    CostBalanceSampler, DefaultSampler, LoadBalanceSampler,
)

# skewed size distribution: heavier lognormal tail than the MPtrj-like
# default (sigma 0.7), so equal-count shards are badly cost-imbalanced
SKEW_SIGMA = 1.1


def _hist(ratios: np.ndarray, edges=(1.0, 1.1, 1.25, 1.5, 2.0, 3.0)):
    """Straggler-ratio histogram: counts per [edge_i, edge_i+1) bin
    (last bin is open-ended)."""
    counts = np.histogram(ratios, bins=list(edges) + [np.inf])[0]
    return {f">={lo:g}": int(c) for lo, c in zip(edges, counts)}


def run_straggler_analysis(
    device_counts=(2, 4, 8),
    *,
    num_crystals: int = 256,
    global_batch: int = 32,
    seed: int = 0,
) -> dict:
    """Phase A: per-step straggler ratios of the three sharders."""
    ds = make_dataset(SyntheticConfig(
        num_crystals=num_crystals, lognormal_sigma=SKEW_SIGMA, seed=seed))
    costs = DEFAULT_COST_MODEL.predict_dataset(ds)
    out: dict = {}
    for n_dev in device_counts:
        slots = crystal_slots_for(global_batch, n_dev)
        samplers = {
            "naive": DefaultSampler(costs, seed),
            "pair": LoadBalanceSampler(costs, seed),
            "lpt": CostBalanceSampler(costs, seed, max_items=slots),
        }
        per = {}
        for name, sampler in samplers.items():
            ratios = []
            for _idx, shards in sampler.epoch(global_batch, n_dev):
                ratios.append(straggler_ratio(
                    shard_cost_totals(costs, shards)))
            ratios = np.asarray(ratios)
            per[name] = {
                "mean": float(ratios.mean()),
                "max": float(ratios.max()),
                "p90": float(np.quantile(ratios, 0.9)),
                "hist": _hist(ratios),
            }
        out[str(n_dev)] = per
    return out


def _throughput(n, batch, steps, mode, quick):
    """atoms/s of ``steps`` train steps on the first ``n`` devices."""
    import jax
    from jax.sharding import Mesh

    from repro.batching import ladder_for
    from repro.core.chgnet import CHGNetConfig
    from repro.data import BalancedBatchIterator, BatchIterator
    from repro.train import TrainConfig, Trainer

    ds = make_dataset(SyntheticConfig(
        num_crystals=64 if quick else 128, max_atoms=20 if quick else 32,
        lognormal_sigma=SKEW_SIGMA, seed=0))
    caps = ladder_for(ds, -(-batch // n))
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",)) if n > 1 else None
    cfg = (CHGNetConfig(dim=16, num_blocks=1) if quick
           else CHGNetConfig(readout="direct"))
    tr = Trainer(cfg, TrainConfig(global_batch=batch), mesh=mesh)
    stack = mesh is not None
    if mode == "balanced":
        it = BalancedBatchIterator(ds, batch, n, caps, num_micro=1,
                                   stack=stack)
    else:
        it = BatchIterator(ds, batch, n, caps, load_balance=False,
                           stack=stack)
    cyc = itertools.cycle(iter(it))
    tr.train(itertools.islice(cyc, 2))  # warmup/compile
    t0 = time.perf_counter()
    tr.train(itertools.islice(cyc, steps))
    dt = (time.perf_counter() - t0) / steps
    atoms_step = batch * float(np.mean([c.num_atoms for c in ds.crystals]))
    return {"n": n, "mode": mode, "batch": batch, "step_s": dt,
            "atoms_per_s": atoms_step / dt,
            "platform": jax.devices()[0].platform}


def run_throughput_sweep(device_counts=(1, 2, 4), *, batch=16, steps=4,
                         quick=False) -> list[dict]:
    """Phase B: atoms/s vs mesh size, balanced vs naive (report-only on
    CPU — forced host devices share the same cores).  Device counts
    beyond ``jax.device_count()`` are skipped."""
    import jax

    rows = []
    for n in device_counts:
        if n > jax.device_count():
            print(f"phase B: skip n={n}, only {jax.device_count()} "
                  f"devices", file=sys.stderr)
            continue
        for mode in ("naive", "balanced"):
            rows.append(_throughput(n, batch, steps, mode, quick))
    return rows


def run(device_counts=(1, 2, 4), strong_batch: int = 32,
        weak_per_dev: int = 8):
    """Legacy Fig. 10 entry point (kept for bench-suite callers): rows of
    (name, usec, note) from the throughput sweep."""
    rows = []
    for r in run_throughput_sweep(device_counts, batch=strong_batch,
                                  steps=2, quick=True):
        rows.append((f"fig10_{r['mode']}_n{r['n']}", r["step_s"] * 1e6,
                     f"atoms/s={r['atoms_per_s']:.0f}"))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small model/dataset + fewer device counts (CI)")
    ap.add_argument("--json", default=None, help="write results to file")
    ap.add_argument("--devices", default=None,
                    help="comma-separated device counts (straggler phase)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--skip-throughput", action="store_true",
                    help="phase A only (no jax runs)")
    args = ap.parse_args()

    if args.devices:
        dev_a = tuple(int(x) for x in args.devices.split(","))
    else:
        dev_a = (2, 4) if args.quick else (2, 4, 8)
    batch = args.batch or (16 if args.quick else 32)
    steps = args.steps or (2 if args.quick else 4)

    straggler = run_straggler_analysis(
        dev_a, num_crystals=128 if args.quick else 256, global_batch=batch)
    for n_dev, per in straggler.items():
        print(f"devices={n_dev}: " + "  ".join(
            f"{k}: mean={v['mean']:.3f} max={v['max']:.3f}"
            for k, v in per.items()))

    # ENFORCED bar: LPT balanced beats naive even-count sharding on the
    # skewed dataset at every device count
    violations = [
        n_dev for n_dev, per in straggler.items()
        if not per["lpt"]["mean"] < per["naive"]["mean"]
    ]

    throughput = []
    if not args.skip_throughput:
        dev_b = (1, 2) if args.quick else (1, 2, 4)
        throughput = run_throughput_sweep(dev_b, batch=batch, steps=steps,
                                          quick=args.quick)
        for r in throughput:
            print(f"n={r['n']} mode={r['mode']}: "
                  f"step={r['step_s'] * 1e3:.1f}ms "
                  f"atoms/s={r['atoms_per_s']:.0f}")

    result = {
        "straggler": straggler,
        "throughput": throughput,
        "enforced": {"lpt_mean_lt_naive_mean": not violations},
    }
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(result, fh, indent=2)
        print(f"wrote {args.json}")

    if violations:
        print(f"FAIL: lpt straggler >= naive at device counts "
              f"{violations}", file=sys.stderr)
        return 1
    print("straggler bar OK: lpt < naive at every device count")
    return 0


if __name__ == "__main__":
    sys.exit(main())
