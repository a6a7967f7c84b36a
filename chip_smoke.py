"""On-chip smoke run of FastCHGNet at the paper's widths and batch size.

    python chip_smoke.py               # one TPU chip: the four phases below
    python chip_smoke.py --four-chips  # one four-chip host: DP training
                                       # and its one-chip comparison only

One-chip phases, in order, all in this one process:

  1. train, default tier: the launcher (``repro.launch.train.main``) with
     ``--batch 128 --crystals 256 --steps 4``; losses must be finite;
  2. train, fused tier: the same run with ``--conv-impl fused``; the
     lowered train step must hold Mosaic kernels (``tpu_custom_call``);
  3. agreement: on one batch of 128 and one set of parameters, the fused
     and default tiers' energy, forces and stress against a plain float32
     reference tier evaluated at the highest matmul precision;
  4. serve: ``ServeEngine`` + ``BatchedMD`` over 16 replicas of 20 to 200
     atoms, 10 MD steps after warm-up; forces must be finite and one
     replica's must match the reference tier.

Every model runs at the published widths (dim 64, 31 + 31 bases, 3
blocks, 6 A / 3 A cutoffs) with random weights from a fixed seed.  Lines
before the last report what this run saw — compile seconds and
persistent-cache hits, step wall times after warm-up, losses, peak device
bytes, deviations — as smoke observations, not benchmark metrics.  The
last line of standard output is one JSON object naming the device.  The
script stops at the first failure with a non-zero exit, and refuses to
run where JAX finds no TPU or where ``REPRO_KERNELS_INTERPRET`` would
interpret the kernels.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

BATCH = 128           # configs/chgnet_mptrj.py BATCH_SIZE
CRYSTALS = 256
TRAIN_STEPS = 4       # one warm-up step plus three
MD_REPLICAS = 16
MD_STEPS = 10
SEED = 0

# Agreement bounds: the largest |tier - reference| over the largest
# |reference| of each output (energy, forces, stress).  The reference runs
# every matmul at float32 ("highest"); the tiers run at the chip's default
# matmul precision, which rounds f32 operands through bfloat16 passes.
AGREE_BOUND = 5e-2
# Four-chip data parallelism vs the one-chip exact accumulation: the loss
# and the parameter update (relative L2 over all parameters).
DP_LOSS_BOUND = 1e-4
DP_UPDATE_BOUND = 1e-2


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require_tpu():
    """Refuse to run anywhere but on a TPU with compiled kernels."""
    if os.environ.get("REPRO_KERNELS_INTERPRET", "") not in ("", "0"):
        fail("REPRO_KERNELS_INTERPRET is set: the Pallas kernels would run "
             "in interpret mode, not as Mosaic kernels on the chip")
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"no TPU found: JAX's default backend is {backend!r}")
    return jax


def say(phase: str, msg: str):
    print(f"[{phase}] {msg}", flush=True)


class CompileLog:
    """Counts compiles, their seconds and persistent-cache hits per phase
    from JAX's monitoring events."""

    def __init__(self, jax):
        self.reset()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def reset(self):
        self.hits = self.misses = self.programs = 0
        self.seconds = 0.0

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def report(self, phase: str):
        say(phase, f"compile: {self.programs} programs, "
                   f"{self.seconds:.1f} s; persistent cache {self.hits} "
                   f"hits, {self.misses} misses")
        self.reset()


def peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def check_history(phase: str, hist, steps: int):
    if len(hist) != steps:
        fail(f"{phase}: {len(hist)} steps ran, expected {steps}")
    losses = [h["loss"] for h in hist]
    if not all(math.isfinite(x) for x in losses):
        fail(f"{phase}: non-finite loss {losses}")
    times = [h["step_s"] for h in hist]
    say(phase, "losses " + " ".join(f"{x:.6f}" for x in losses))
    say(phase, "step wall s (first includes compile) "
        + " ".join(f"{t:.3f}" for t in times))


def rel_dev(x, ref) -> float:
    import numpy as np

    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(x - ref)) / max(np.max(np.abs(ref)), 1e-30))


def reference_cfg(C):
    """Plain float32 jax.numpy tier: unfused convs, scatter sums, two-GEMM
    GatedMLP — the same model and readout as the tiers under test."""
    return C.FAST_FS_HEAD.with_(mlp_impl="ref", agg_impl="scatter",
                                conv_impl="unfused")


def run_reference(jax, cfg, params, batch):
    from repro.core.chgnet import chgnet_apply

    with jax.default_matmul_precision("highest"):
        out = jax.jit(chgnet_apply, static_argnums=1)(params, cfg, batch)
        return jax.device_get(out)


def first_batch(jax):
    """The launcher's dataset, its first 128 crystals packed at the
    launcher's batch-128 capacity, on the device."""
    import numpy as np

    from repro.batching import capacity_for
    from repro.data import SyntheticConfig, build_device_batch, make_dataset

    ds = make_dataset(SyntheticConfig(num_crystals=CRYSTALS, seed=SEED))
    caps = capacity_for(ds, BATCH)
    say("setup", f"batch of {BATCH}: capacities {caps}")
    return jax.device_put(build_device_batch(ds, np.arange(BATCH), caps))


def phase_train(jax, log, conv_impl: str, batch):
    from repro.configs import chgnet_mptrj as C
    from repro.launch.train import main as train_main

    phase = f"train/{conv_impl}"
    argv = ["--arch", "chgnet", "--batch", str(BATCH), "--crystals",
            str(CRYSTALS), "--steps", str(TRAIN_STEPS), "--conv-impl",
            conv_impl]
    say(phase, "launcher argv: " + " ".join(argv))
    hist = train_main(argv)
    check_history(phase, hist, TRAIN_STEPS)
    log.report(phase)
    say(phase, f"peak device bytes {peak_bytes(jax)}")
    if conv_impl == "fused":
        model_cfg = C.FAST_FS_HEAD.with_(conv_impl="fused")
        check_mosaic(phase, lowered_train_step_text(jax, model_cfg, batch))


def lowered_train_step_text(jax, model_cfg, batch) -> str:
    """StableHLO of the train step for ``model_cfg`` on ``batch``
    (lowered, not run)."""
    import jax.numpy as jnp

    from repro.configs import chgnet_mptrj as C
    from repro.train import TrainConfig, Trainer, make_chgnet_step_fns

    train_cfg = TrainConfig(global_batch=BATCH, total_steps=TRAIN_STEPS,
                            loss=C.LOSS)
    tr = Trainer(model_cfg, train_cfg)
    step, _, _ = make_chgnet_step_fns(model_cfg, train_cfg)
    return step.lower(tr.params, tr.opt_state, batch,
                      jnp.asarray(0)).as_text()


def check_mosaic(phase: str, text: str):
    """The Pallas kernels lowered to Mosaic custom calls, not to the
    interpreter's plain XLA ops."""
    n = text.count("tpu_custom_call")
    if n == 0:
        fail(f"{phase}: the lowered train step holds no tpu_custom_call")
    say(phase, f"lowered train step holds {n} tpu_custom_call sites")


def phase_agreement(jax, log, batch):
    import numpy as np

    from repro.configs import chgnet_mptrj as C
    from repro.core.chgnet import chgnet_apply, chgnet_init

    phase = "agreement"
    params = chgnet_init(jax.random.PRNGKey(SEED), C.FAST_FS_HEAD)
    ref = run_reference(jax, reference_cfg(C), params, batch)
    worst = 0.0
    for name, cfg in (("fused", C.FAST_FUSED), ("default", C.FAST_FS_HEAD)):
        out = jax.device_get(
            jax.jit(chgnet_apply, static_argnums=1)(params, cfg, batch))
        devs = {k: rel_dev(out[k], ref[k])
                for k in ("energy", "forces", "stress")}
        for k, v in out.items():
            if not np.all(np.isfinite(v)):
                fail(f"{phase}: {name} tier {k} is not finite")
        say(phase, f"{name} vs reference, max rel deviation: "
            + " ".join(f"{k} {v:.3e}" for k, v in devs.items()))
        worst = max(worst, *devs.values())
    if worst > AGREE_BOUND:
        fail(f"{phase}: deviation {worst:.3e} over the bound {AGREE_BOUND}")
    say(phase, f"largest deviation {worst:.3e} within {AGREE_BOUND}")
    log.report(phase)
    say(phase, f"peak device bytes {peak_bytes(jax)}")
    return params


def phase_serve(jax, log, params):
    import numpy as np

    from examples.serve_md import make_crystal
    from repro.batching import BatchCapacities, batch_crystals
    from repro.configs import chgnet_mptrj as C
    from repro.core.neighbors import build_graph
    from repro.serve import BatchedMD, ServeEngine

    phase = "serve"
    cfg = C.FAST_FS_HEAD
    sizes = np.linspace(20, 200, MD_REPLICAS).round().astype(int)
    crystals = [make_crystal(int(n), seed=i) for i, n in enumerate(sizes)]
    say(phase, f"{MD_REPLICAS} replicas, atoms {sizes.min()}..{sizes.max()}"
               f" ({int(sizes.sum())} total)")
    serve = ServeEngine.for_structures(params, cfg, crystals)
    md = BatchedMD(serve, crystals, dt=1e-3, skin=0.5)
    t0 = time.perf_counter()
    md.step(1)
    say(phase, f"warm-up step {time.perf_counter() - t0:.3f} s "
               f"(includes compile)")
    times = []
    for i in range(MD_STEPS):
        if i == MD_STEPS - 1:  # forces of the last step refer to these
            snap = dataclasses.replace(md.replicas[0].crystal)
        t0 = time.perf_counter()
        out = md.step(1)
        times.append(time.perf_counter() - t0)
    if not all(np.all(np.isfinite(f)) for f in out["forces"]):
        fail(f"{phase}: non-finite forces")
    say(phase, f"{MD_STEPS} MD steps, wall s per step: "
        + " ".join(f"{t:.4f}" for t in times))
    stats = md.stats()
    say(phase, f"compile-cache entries {stats['compile_cache_entries']}, "
               f"nlist rebuilds {stats['nlist_rebuilds']}/"
               f"{stats['nlist_updates']}")
    graph = build_graph(snap, cfg.r_cut_atom, cfg.r_cut_bond)
    align = lambda n: max(64, -(-n // 64) * 64)
    caps = BatchCapacities(atoms=align(snap.num_atoms),
                           bonds=align(graph.num_bonds),
                           angles=align(graph.num_angles))
    ref = run_reference(jax, reference_cfg(C), params,
                        batch_crystals([snap], [graph], caps))
    dev = rel_dev(out["forces"][0], ref["forces"][:snap.num_atoms])
    if dev > AGREE_BOUND:
        fail(f"{phase}: replica 0 forces deviate {dev:.3e} from the "
             f"reference, over the bound {AGREE_BOUND}")
    say(phase, f"replica 0 ({snap.num_atoms} atoms) forces vs reference: "
               f"max rel deviation {dev:.3e} within {AGREE_BOUND}")
    log.report(phase)
    say(phase, f"peak device bytes {peak_bytes(jax)}")


def _params_l2(jax, a, b) -> float:
    import numpy as np

    return math.sqrt(sum(
        float(np.sum((np.asarray(x, np.float64)
                      - np.asarray(y, np.float64)) ** 2))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))))


def phase_four_chips(jax, log):
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.batching import capacity_for
    from repro.configs import chgnet_mptrj as C
    from repro.data import (
        BalancedBatchIterator, SyntheticConfig, make_dataset,
    )
    from repro.data.pipeline import place
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import main as train_main
    from repro.train import TrainConfig, Trainer

    n_dev = len(jax.devices())
    if n_dev != 4:
        fail(f"--four-chips needs 4 devices, JAX sees {n_dev}")
    phase = "dp4/train"
    argv = ["--arch", "chgnet", "--batch", str(C.LARGE_BATCH),
            "--crystals", str(C.LARGE_BATCH), "--steps", "3",
            "--balance", C.BALANCE, "--accum", str(C.ACCUM_MICROS)]
    say(phase, "launcher argv: " + " ".join(argv))
    check_history(phase, train_main(argv), 3)
    log.report(phase)
    say(phase, f"peak device bytes (device 0) {peak_bytes(jax)}")

    # one step at a global batch one chip holds (4 x 128): data parallel
    # on the four-chip mesh vs the exact accumulation path on one chip
    phase = "dp4/compare"
    global_batch = 4 * BATCH
    ds = make_dataset(SyntheticConfig(num_crystals=global_batch, seed=SEED))
    caps = capacity_for(ds, BATCH)
    idx = np.arange(global_batch)
    tcfg = TrainConfig(global_batch=global_batch, total_steps=10,
                       loss=C.LOSS)
    mesh = make_host_mesh()
    tr4 = Trainer(C.FAST_FS_HEAD, tcfg, mesh=mesh, seed=SEED)
    p0 = jax.device_get(tr4.params)
    plan4 = BalancedBatchIterator(ds, global_batch, 4, caps,
                                  num_micro=1).plan_step(idx)
    h4 = tr4.train([place(plan4, NamedSharding(mesh, P("data")))])
    tr1 = Trainer(C.FAST_FS_HEAD, tcfg, seed=SEED)
    plan1 = BalancedBatchIterator(ds, global_batch, 1, caps, num_micro=4,
                                  stack=False).plan_step(idx)
    h1 = tr1.train([plan1])
    l4, l1 = h4[0]["loss"], h1[0]["loss"]
    p4, p1 = jax.device_get(tr4.params), jax.device_get(tr1.params)
    loss_dev = abs(l4 - l1) / abs(l1)
    upd_dev = _params_l2(jax, p4, p1) / _params_l2(jax, p1, p0)
    say(phase, f"loss 4-chip {l4:.8f} 1-chip {l1:.8f} rel {loss_dev:.3e}")
    say(phase, f"parameter update |p4 - p1| / |p1 - p0| = {upd_dev:.3e}")
    if not (math.isfinite(l4) and loss_dev <= DP_LOSS_BOUND):
        fail(f"{phase}: loss deviation {loss_dev:.3e} over {DP_LOSS_BOUND}")
    if not upd_dev <= DP_UPDATE_BOUND:
        fail(f"{phase}: update deviation {upd_dev:.3e} over "
             f"{DP_UPDATE_BOUND}")
    log.report(phase)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only four-chip data-parallel training and "
                         "its one-chip comparison")
    args = ap.parse_args(argv)
    jax = require_tpu()
    sys.path[:0] = [os.path.join(HERE, "src"), HERE]
    from repro.launch.compile_cache import enable_compile_cache

    say("setup", f"compile cache {enable_compile_cache()}")
    log = CompileLog(jax)
    dev = jax.devices()[0]
    say("setup", f"{len(jax.devices())} x {dev.device_kind} "
                 f"({dev.platform}), jax {jax.__version__}")
    if args.four_chips:
        phase_four_chips(jax, log)
    else:
        batch = first_batch(jax)
        phase_train(jax, log, "unfused", batch)
        phase_train(jax, log, "fused", batch)
        params = phase_agreement(jax, log, batch)
        phase_serve(jax, log, params)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
