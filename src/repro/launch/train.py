"""Production training launcher.

Two modes:
  - ``--arch chgnet``: train FastCHGNet on the synthetic dataset with the
    full substrate (load-balance sampler, prefetch, checkpoint/restart,
    straggler watch) across all local devices (DP shard_map).
  - ``--arch <lm-id>``: build + run the LM train step (smoke config on
    CPU; the full config is exercised by dryrun.py).

The CHGNet run uses every device of this one process: a data-parallel
mesh over all local devices when there are several, a plain jitted step
on one.  ``main(argv)`` returns the per-step metric history (each entry
with its wall seconds, ``step_s``), so a caller can drive the launcher
in-process.

    PYTHONPATH=src python -m repro.launch.train --arch chgnet --steps 50
"""
from __future__ import annotations

import argparse
import itertools
from functools import partial

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def train_chgnet(args):
    from repro.batching import capacity_for, ladder_for
    from repro.configs import chgnet_mptrj as C
    from repro.data import (
        BatchIterator, Prefetcher, SyntheticConfig, make_dataset,
    )
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import (
        ChaosMonkey, ChaosSchedule, GracefulShutdown, PreemptionError,
        clear_resume_marker, latest_valid_step, read_resume_marker,
        run_with_restarts,
    )
    from repro.train import TrainConfig, Trainer

    n_dev = jax.device_count()
    ds = make_dataset(SyntheticConfig(num_crystals=args.crystals, seed=0))
    # ceil: non-divisible batches put up to ceil(batch/n_dev) samples on a
    # shard, so capacities must be sized for that, not the floor
    per_dev = -(-args.batch // n_dev)
    # one worst-case capacity (single compiled step) or a bucket ladder
    # (less padding waste, <= args.buckets compiled step shapes)
    caps = (capacity_for(ds, per_dev) if args.buckets <= 1
            else ladder_for(ds, per_dev, num_buckets=args.buckets))
    mesh = make_host_mesh() if n_dev > 1 else None
    model_cfg = C.FAST_FS_HEAD if args.readout == "direct" else C.FAST_WO_HEAD
    # fused message-passing megakernels (DESIGN.md §3) — every batch from
    # repro.batching satisfies the §1 layout they require — and the
    # end-to-end precision policy (DESIGN.md §4; "mixed" = f32 master
    # params/accum, bf16 compute + dynamic loss scaling)
    model_cfg = model_cfg.with_(conv_impl=args.conv_impl,
                                precision=args.precision,
                                bond_store=args.bond_store,
                                bond_features=args.bond_features,
                                stress_mode=args.stress_mode,
                                table_residency=args.table_residency)
    train_cfg = TrainConfig(global_batch=args.batch, total_steps=args.steps,
                            loss=C.LOSS, grad_reduce=args.grad_reduce,
                            cost_refit_every=args.cost_refit_every,
                            rollback_on_divergence=args.rollback_on_divergence)
    print(f"devices={n_dev} init_lr={train_cfg.init_lr:.2e} "
          f"readout={args.readout} conv_impl={args.conv_impl} "
          f"precision={args.precision} bond_store={args.bond_store} "
          f"bond_features={args.bond_features} "
          f"stress_mode={args.stress_mode} async_ckpt={args.async_ckpt}")
    if args.ckpt:
        marker = read_resume_marker(args.ckpt)
        if marker:
            print(f"resuming after preemption at step {marker['step']} "
                  f"({marker.get('reason', '?')})")
            clear_resume_marker(args.ckpt)
    # one monkey for the whole run: `fired` persists across restarts so
    # each scheduled fault fires exactly once (DESIGN.md §8)
    monkey = None
    if args.chaos:
        monkey = ChaosMonkey(
            ChaosSchedule.parse(args.chaos, seed=args.chaos_seed),
            ckpt_dir=args.ckpt)
    shutdown = GracefulShutdown().install()
    history: list[dict] = []

    def prefetch(stream, tr):
        # stacked (n_dev, ...) batches go straight to their shards: one
        # slice per device over the data axis, never the whole batch on
        # device 0 first
        device = None if tr.mesh is None \
            else NamedSharding(tr.mesh, P(tr.mesh.axis_names[0]))
        return Prefetcher(stream, device=device)

    def one_pass(tr):
        if args.balance == "cost" or args.accum > 1:
            # cost-model bin packing + gradient accumulation (DESIGN.md
            # §6): StepPlans re-bin-pack over the surviving mesh if a
            # device drops mid-run (elastic_train)
            from repro.data import BalancedBatchIterator
            from repro.runtime import elastic_train

            def batches_fn(num_devices):
                it = BalancedBatchIterator(
                    ds, args.batch, num_devices, caps,
                    num_micro=max(args.accum, 1),
                    stack=tr.mesh is not None)
                # live cost-model refits (DESIGN.md §6): the Trainer times
                # each microbatch and pushes the refit coefficients back
                # into the iterator's LPT bin packing
                tr.on_cost_model = it.update_cost_model
                tr.on_quarantine = it.add_quarantine
                stream = itertools.islice(
                    itertools.cycle(iter(it)), max(args.steps - tr.step, 0))
                if monkey is not None:
                    # wrap INSIDE the Prefetcher so transient faults hit
                    # the worker's retry/quarantine path (DESIGN.md §8)
                    stream = monkey.wrap_batches(stream, start_step=tr.step)
                return prefetch(stream, tr)

            hist = elastic_train(tr, batches_fn, max_steps=args.steps,
                                 fault_injector=monkey)
        else:
            it = BatchIterator(ds, args.batch, n_dev, caps,
                               stack=n_dev > 1, load_balance=True,
                               tag_indices=args.rollback_on_divergence)
            tr.on_quarantine = it.add_quarantine
            stream = itertools.islice(
                itertools.cycle(iter(it)), args.steps - tr.step)
            if monkey is not None:
                stream = monkey.wrap_batches(stream, start_step=tr.step)
            hist = tr.train(prefetch(stream, tr), fault_injector=monkey)
        return hist

    def loop(start):
        tr = Trainer(model_cfg, train_cfg, mesh=mesh, ckpt_dir=args.ckpt,
                     ckpt_every=args.ckpt_every,
                     async_ckpt=args.async_ckpt, shutdown=shutdown)
        tr.maybe_restore()
        hist = []
        while True:
            before = tr.step
            hist = one_pass(tr)
            # each history entry pairs with the step's wall seconds, from
            # dispatch to its loss on the host
            walls = tr.straggler.times[len(tr.straggler.times) - len(hist):]
            history.extend(dict(h, step_s=t) for h, t in zip(hist, walls))
            # a divergence rollback consumes stream batches while moving
            # tr.step backwards, so an exhausted stream can leave the run
            # short of --steps: rebuild the stream and keep going as long
            # as each pass makes net progress
            if tr.step >= args.steps or tr.step <= before:
                break
        tr.save(wait=True)
        tr.close()
        if hist:
            print(f"steps {tr.step - len(hist)}..{tr.step}: "
                  f"loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}; "
                  f"stragglers={tr.straggler.flags}")
        return tr.step

    try:
        # resume from the newest VALID checkpoint: a crash mid-write (or a
        # chaos ckpt_* event) leaves a corrupt newest file that restore
        # skips, so the resume step must skip it too
        run_with_restarts(
            loop, resume_step_fn=lambda: (latest_valid_step(args.ckpt) or 0)
            if args.ckpt else 0,
            max_restarts=3)
    except PreemptionError as exc:
        print(f"preempted at step {exc.step}; checkpoint + resume marker "
              f"written to {args.ckpt}")
    finally:
        shutdown.uninstall()
    return history


def train_lm(args):
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_smoke
    from repro.models.api import family_fns
    from repro.optim import adam_init, adam_update

    cfg = get_smoke(args.arch)
    fns = family_fns(cfg)
    params = fns.init(cfg, jax.random.PRNGKey(0))
    opt = adam_init(params)
    rng = np.random.default_rng(0)
    kw = dict(ssd_chunk=8) if cfg.family == "hybrid" else {}

    # donate params/opt (rebound every iteration) so the weights and
    # moments never exist twice — same contract as the CHGNet train steps
    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt, *batch):
        loss, grads = jax.value_and_grad(
            lambda p: fns.loss(cfg, p, *batch, **kw))(params)
        params, opt = adam_update(grads, opt, params, 1e-3)
        return params, opt, loss

    b, s = 4, 32
    for i in range(args.steps):
        if fns.token_input:
            x = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)))
        else:
            x = jnp.asarray(rng.normal(0, 1, (b, s, cfg.d_model)),
                            jnp.float32)
        labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)))
        batch = [x, labels]
        if fns.has_positions:
            shape = (b, s, 3) if fns.positions_3d else (b, s)
            pos = jnp.broadcast_to(
                jnp.arange(s)[None, :, None] if fns.positions_3d
                else jnp.arange(s)[None, :], shape).astype(jnp.int32)
            batch.append(pos)
        params, opt, loss = step(params, opt, *batch)
        if i % max(1, args.steps // 10) == 0:
            print(f"  step {i:3d} loss {float(loss):.4f}")
    return args.steps


def main(argv: list[str] | None = None):
    """Parse ``argv`` (default: the command line) and run; the CHGNet run
    returns its per-step metric history."""
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chgnet")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--crystals", type=int, default=128)
    ap.add_argument("--readout", default="direct",
                    choices=["direct", "autodiff"])
    ap.add_argument("--conv-impl", default="unfused",
                    choices=["unfused", "fused"],
                    help="fused = message-passing megakernels (DESIGN.md §3)")
    ap.add_argument("--precision", default="f32",
                    choices=["f32", "bf16", "mixed"],
                    help="end-to-end precision policy (DESIGN.md §4); "
                         "mixed = f32 params/accum, bf16 compute")
    ap.add_argument("--bond-store", default="directed",
                    choices=["directed", "undirected"],
                    help="undirected = half-graph bond store with mirror "
                         "maps (DESIGN.md §5): geometry/RBF/embed GEMM "
                         "and e^a/e^b run once per pair (Eu = E/2)")
    ap.add_argument("--bond-features", default="directed",
                    choices=["directed", "undirected"],
                    help="trunk compute representation (DESIGN.md §10): "
                         "undirected = symmetrized bond_conv/angle_update "
                         "over Eu/Au rows (halves every bond/angle-level "
                         "GEMM; requires --bond-store undirected)")
    ap.add_argument("--stress-mode", default="mlp",
                    choices=["mlp", "bond_virial"],
                    help="direct-readout stress tier (DESIGN.md §7): mlp = "
                         "pooled S-head MLP; bond_virial = per-bond virial "
                         "from the force head's n_ij (no stress params; "
                         "fused into the force megakernel epilogue when "
                         "--conv-impl fused)")
    ap.add_argument("--table-residency", default="auto",
                    choices=["auto", "vmem", "hbm"],
                    help="operand-table residency of the Pallas kernels "
                         "(DESIGN.md §9): vmem = whole-array resident; "
                         "hbm = tables stay in HBM, streamed with "
                         "double-buffered DMA (10k+-atom structures); "
                         "auto = per-launch byte estimate vs the VMEM "
                         "budget (REPRO_VMEM_BUDGET_MB)")
    ap.add_argument("--grad-reduce", default="bucketed",
                    choices=["plain", "bucketed", "compressed"])
    ap.add_argument("--cost-refit-every", type=int, default=0,
                    help="refit the LPT cost model from live per-microbatch "
                         "step timings every K optimizer steps (0 = off; "
                         "only meaningful with --balance cost / --accum)")
    ap.add_argument("--balance", default="pair",
                    choices=["pair", "cost"],
                    help="DP sharding: pair = paper Fig. 4 "
                         "smallest+largest pairing (equal counts); cost = "
                         "LPT bin packing over the per-crystal cost model "
                         "(DESIGN.md §6), with rebalance-on-fault")
    ap.add_argument("--accum", type=int, default=1,
                    help="microbatches per optimizer step (DESIGN.md §6 "
                         "gradient accumulation across capacity buckets); "
                         ">1 implies the balanced StepPlan path")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--async-ckpt", action="store_true",
                    help="write checkpoints from a background thread "
                         "(DESIGN.md §8): the step loop only pays for the "
                         "host snapshot; serialize/fsync/prune overlap "
                         "training")
    ap.add_argument("--rollback-on-divergence", action="store_true",
                    help="NaN/loss-spike streaks restore the newest valid "
                         "checkpoint, halve the LR, and quarantine the "
                         "streak's batches (DESIGN.md §8)")
    ap.add_argument("--chaos", default=None,
                    help="fault-injection schedule, e.g. "
                         "'nan@5,sigterm@12,ckpt_bitflip@20,drop@7:0' "
                         "(runtime.chaos; kinds: crash drop sigterm "
                         "straggler ckpt_truncate ckpt_bitflip nan "
                         "transient prefetch_crash)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--buckets", type=int, default=2,
                    help="capacity buckets (1 = single worst-case pad)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.arch == "chgnet":
        return train_chgnet(args)
    return train_lm(args)


if __name__ == "__main__":
    main()
