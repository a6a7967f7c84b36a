"""Quickstart: build a synthetic crystal batch, run FastCHGNet, train a
few steps, run one MD inference step.

    PYTHONPATH=src python examples/quickstart.py
"""
import itertools

import jax

from repro.batching import capacity_for
from repro.configs import chgnet_mptrj as C
from repro.core.chgnet import chgnet_apply, chgnet_init, param_count
from repro.data import BatchIterator, SyntheticConfig, make_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.train import TrainConfig, Trainer


def main():
    enable_compile_cache()
    # 1. data: synthetic MPtrj-like crystals with analytic E/F/sigma/magmom
    ds = make_dataset(SyntheticConfig(num_crystals=64, max_atoms=24, seed=0))
    caps = capacity_for(ds, per_device_batch=8)
    print(f"dataset: {len(ds)} crystals, per-batch caps {caps}")

    # 2. model: FastCHGNet (direct F/S heads, fused blocks)
    cfg = C.FAST_FS_HEAD
    params = chgnet_init(jax.random.PRNGKey(0), cfg)
    print(f"FastCHGNet params: {param_count(params):,} (paper: 429.1K)")

    # 3. one forward pass
    batch = next(iter(BatchIterator(ds, 8, 1, caps)))
    out = chgnet_apply(params, cfg, batch)
    print("forward:", {k: tuple(v.shape) for k, v in out.items()})

    # 4. a few training steps (Huber loss, Adam, Eq. 14 LR)
    tr = Trainer(cfg, TrainConfig(global_batch=8, total_steps=100, loss=C.LOSS))
    hist = tr.train(itertools.islice(
        itertools.cycle(iter(BatchIterator(ds, 8, 1, caps))), 10))
    print(f"train: loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
          f"over {len(hist)} steps")

    # 5. MD-style serve step
    pred = chgnet_apply(tr.params, cfg, batch)
    print(f"serve: energy[0] = {float(pred['energy'][0]):.3f} eV")


if __name__ == "__main__":
    main()
