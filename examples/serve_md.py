"""Serve FastCHGNet for molecular-dynamics batched inference (Table II
scenario) through the ``repro.serve`` engine: Verlet skin-radius
neighbor-list reuse, multi-replica batched stepping, and a persistent
compiled serve step per capacity bucket.

    PYTHONPATH=src python examples/serve_md.py \
        [--steps 20] [--atoms 16] [--replicas 4]
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import chgnet_mptrj as C
from repro.core.chgnet import chgnet_init
from repro.core.neighbors import Crystal
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import BatchedMD, ServeEngine


def make_crystal(num_atoms: int, seed: int) -> Crystal:
    rng = np.random.default_rng(seed)
    a = (num_atoms * 14.0) ** (1 / 3)
    return Crystal(
        lattice=np.eye(3) * a,
        frac_coords=rng.random((num_atoms, 3)),
        atomic_numbers=rng.integers(1, 60, num_atoms),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--atoms", type=int, default=16)
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--dt", type=float, default=1e-3)
    ap.add_argument("--skin", type=float, default=0.5)
    args = ap.parse_args()
    enable_compile_cache()

    # independent replicas of slightly different sizes — the bucket ladder
    # groups them so each group is one device program per step
    crystals = [
        make_crystal(args.atoms + 2 * (i % 3), seed=i)
        for i in range(args.replicas)
    ]

    cfg = C.FAST_FS_HEAD
    params = chgnet_init(jax.random.PRNGKey(0), cfg)
    serve = ServeEngine.for_structures(params, cfg, crystals)
    md = BatchedMD(serve, crystals, dt=args.dt, skin=args.skin)

    md.step(1)  # warm the compile cache before timing
    times = []
    for step in range(args.steps):
        t0 = time.perf_counter()
        out = md.step(1)
        times.append(time.perf_counter() - t0)
        if step % 5 == 0:
            fmax = max(float(np.abs(f).max()) for f in out["forces"])
            print(f"step {step:3d}: E0={out['energy'][0]:9.3f} eV  "
                  f"|F|max={fmax:7.3f} eV/A  t={times[-1] * 1e3:.1f} ms")

    stats = md.stats()
    rate = args.replicas * len(times) / sum(times)
    print(f"\n{args.replicas} replicas x {len(times)} steps: "
          f"{rate:.1f} replica-steps/s "
          f"({np.mean(times) * 1e3:.2f} ms/batched step)")
    print(f"padding waste {stats['mean_padding_waste']:.1%}, "
          f"compiled steps {stats['compile_cache_entries']}, "
          f"nlist rebuilds {stats['nlist_rebuilds']}/{stats['nlist_updates']}")


if __name__ == "__main__":
    main()
