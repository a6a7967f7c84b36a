"""Training driver: the launcher's data path and train step, timed.

Set-up builds what ``python -m repro.launch.train`` builds for the cell's
batch and chips: the synthetic dataset, the capacity-bucket ladder, the
batch iterator cycled over its first epoch, the ``Prefetcher`` and one
``Trainer``.  The benchmark's weights replace the Trainer's own.  The
first epoch's steps (at least three) run through ``Trainer.train`` on that
same feed: they warm every bucket shape, and the first three are the ones
the plain reference follows.  The window then keeps calling
``Trainer.train`` on the same feed until ``--seconds`` have passed and a
whole epoch has been stepped, so that where the window starts in the
epoch does not move the rate.

Traffic keys: ``crystals``, ``batch``, ``buckets``, ``data_seed`` (the
structures; the label constants come from ``--seed``), ``total_steps``
(the cosine schedule's length), and for several chips ``balance`` and
``accum`` as the launcher's ``--balance cost --accum N``.
"""
from __future__ import annotations

import gc
import itertools
import shutil
import tempfile
import time

import numpy as np

from benchlib import (cells, compare, crystals, flops, reference, spans,
                      trace, weights)

REFERENCE_STEPS = 3


def _program_dataset(structures, r_cut_atom: float, r_cut_bond: float):
    from repro.core.neighbors import Crystal, build_graph
    from repro.data.synthetic import SyntheticConfig, SyntheticDataset

    cs = [Crystal(lattice=s.lattice, frac_coords=s.frac_coords,
                  atomic_numbers=s.atomic_numbers, energy=s.energy,
                  forces=s.forces, stress=s.stress, magmoms=s.magmoms)
          for s in structures]
    return SyntheticDataset(
        crystals=cs,
        graphs=[build_graph(c, r_cut_atom, r_cut_bond) for c in cs],
        cfg=SyntheticConfig(num_crystals=len(cs)))


def _micro_batches(item):
    """The packed batches of one feed item (a batch, or a StepPlan's)."""
    from repro.batching.balance import StepPlan
    from repro.data.pipeline import TaggedBatch

    if isinstance(item, TaggedBatch):
        item = item.batch
    return item.micro if isinstance(item, StepPlan) else [item]


class Feed:
    """The launcher's stream, read step by step.  Keeps, per step, the
    dataset indices it trained on, and per packed batch its real and
    capacity atoms, bonds and angles (a host count from the masks)."""

    def __init__(self, prefetcher, annotate, plan_indices=None):
        self.prefetcher = prefetcher
        self._plan_indices = plan_indices
        self._it = iter(prefetcher)
        self._annotate = annotate
        self.indices: list[np.ndarray] = []
        self.rows: list[tuple] = []   # per step: (atoms, bonds, angles) real
        self.bond_fill: list[tuple] = []  # per step: (real, capacity) bonds
        self._counted: dict[int, tuple] = {}

    def _count(self, item):
        key = id(item)
        if key not in self._counted:
            real = np.zeros(3)
            cap = 0
            for b in _micro_batches(item):
                masks = (b.atom_mask, b.bond_mask, b.angle_mask)
                real += [float(np.sum(np.asarray(m))) for m in masks]
                cap += int(np.asarray(b.bond_mask).size)
            self._counted[key] = (tuple(real), cap)
        return self._counted[key]

    def next(self):
        with self._annotate("bench.data_next"):
            item = next(self._it)
        idx = getattr(item, "indices", None)
        if idx is None:  # a StepPlan does not tag its rows
            plans = self._plan_indices
            idx = plans[len(self.indices) % len(plans)]
        self.indices.append(np.asarray(idx))
        real, cap = self._count(item)
        self.rows.append(real)
        self.bond_fill.append((real[1], cap))
        return item

    def take(self, n: int):
        for _ in range(n):
            yield self.next()

    def until(self, deadline: float, epoch: int):
        """Steps until ``deadline`` has passed and whole epochs are done."""
        k = 0
        while not (time.perf_counter() >= deadline and k % epoch == 0):
            k += 1
            yield self.next()

    def close(self):
        self.prefetcher.close()


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.batching import ladder_for
    from repro.configs import chgnet_mptrj as C
    from repro.core.chgnet import chgnet_init
    from repro.data import BalancedBatchIterator, BatchIterator, Prefetcher
    from repro.launch.mesh import make_host_mesh
    from repro.train import TrainConfig, Trainer

    cell, tf, cfg = ctx.cell, ctx.cell.traffic, ctx.cell.config
    chips = cell.chips
    annotate = jax.profiler.TraceAnnotation
    model_cfg = cells.model_config(C, cfg, ctx.precision)
    spec = crystals.SyntheticSpec(num_crystals=tf["crystals"],
                                  r_cut_atom=cfg["r_cut_atom"],
                                  r_cut_bond=cfg["r_cut_bond"])
    structures, ref_graphs = crystals.synthetic_set(
        spec, tf["data_seed"], label_seed=ctx.seed)
    ds = _program_dataset(structures, cfg["r_cut_atom"], cfg["r_cut_bond"])
    batch = tf["batch"]
    caps = ladder_for(ds, -(-batch // chips), num_buckets=tf["buckets"])
    train_cfg = TrainConfig(global_batch=batch, total_steps=tf["total_steps"],
                            loss=C.LOSS)
    mesh = make_host_mesh() if chips > 1 else None
    tr = Trainer(model_cfg, train_cfg, mesh=mesh)
    w0 = weights.make_weights(cfg, ctx.seed, ctx.devices[0])
    want = jax.tree.structure(jax.eval_shape(
        lambda: chgnet_init(jax.random.PRNGKey(0), model_cfg)))
    if jax.tree.structure(w0) != want:
        raise SystemExit("bench: the benchmark's weight layout no longer "
                         "matches the program's parameters")
    w0_host = jax.device_get(w0)
    # the Trainer's state, with the benchmark's weights in it
    tr.params = jax.tree.map(lambda w, p: w.astype(p.dtype), w0, tr.params)
    if "master" in tr.opt_state:
        tr.opt_state["master"] = jax.tree.map(jnp.copy, w0)
    ctx.plant("trainer", tr)
    epoch = len(ds) // batch
    plans = None
    if tf.get("balance") == "cost":
        it = BalancedBatchIterator(ds, batch, chips, caps,
                                   num_micro=tf["accum"], stack=chips > 1)
        # its rows: the iterator's own epoch order (its seed, 0), sliced
        perm = np.random.default_rng(0).permutation(len(ds))
        plans = [perm[k * batch:(k + 1) * batch] for k in range(epoch)]
    else:
        it = BatchIterator(ds, batch, chips, caps, stack=chips > 1,
                           load_balance=True, tag_indices=True)
    device = None if mesh is None else NamedSharding(mesh, P("data"))
    feed = Feed(Prefetcher(itertools.cycle(iter(it)), device=device),
                annotate, plans)

    # the first epoch: warms every bucket; the reference follows 3 steps
    hist = tr.train(feed.take(1))
    grad1 = jax.tree.map(lambda m: m / (1.0 - train_cfg.adam.b1),
                         jax.device_get(tr.opt_state["mu"]))
    hist += tr.train(feed.take(REFERENCE_STEPS - 1))
    params3 = jax.device_get(tr.params)
    losses = [h["loss"] for h in hist]
    warm = max(epoch, REFERENCE_STEPS)
    hist += tr.train(feed.take(warm - REFERENCE_STEPS))
    jax.block_until_ready(tr.params)
    step0 = len(feed.rows)

    logdir = tempfile.mkdtemp(prefix="bench_trace_") if ctx.trace else None
    if ctx.trace:
        _annotate_steps(tr, annotate)
        profiler = ctx.profile(logdir)
        profiler.__enter__()
    ctx.window_starts()
    t0 = time.perf_counter()
    with annotate("bench.window"):
        window_hist = tr.train(feed.until(t0 + ctx.seconds, epoch))
        jax.block_until_ready(tr.params)
    t1 = time.perf_counter()
    if ctx.trace:
        profiler.__exit__(None, None, None)
    compiles = ctx.compiles_in_window()
    feed.close()
    peak = ctx.memory_peak_bytes()

    steps = len(window_hist)
    rows = np.sum(feed.rows[step0:], axis=0) if steps else np.zeros(3)
    real_bonds = sum(r for r, _ in feed.bond_fill[step0:])
    cap_bonds = sum(c for _, c in feed.bond_fill[step0:])
    failed = sum(1 for h in window_hist if not np.isfinite(h["loss"]))
    readings = {
        "window_s": t1 - t0, "steps": steps,
        "fill.train": (real_bonds, cap_bonds),
        "flops.train": flops.train_step_flops(cfg, *rows),
    }
    reduced = None
    if ctx.trace:
        try:
            reduced = trace.reduce(trace.load(trace.find_xplane(logdir)),
                                   steps=steps)
            readings["spans"] = spans.reduce(spans.load(
                trace.find_xplane(logdir), op_names=True))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
    ref_indices = feed.indices[:REFERENCE_STEPS]

    # the program's state is freed before the reference runs
    del tr, feed, it, ds, w0
    gc.collect()
    checks, notes = _check(ctx, cfg, tf, train_cfg, structures, ref_graphs,
                           ref_indices, w0_host, grad1, params3, losses)
    return {
        "attempted": steps, "failed": failed,
        "end_to_end": {"train_atoms_per_s": float(rows[0]) / (t1 - t0),
                       "train_hbm_peak_gb": peak / 1e9},
        "memory_peak_bytes": peak, "compiles_in_window": compiles,
        "readings": readings, "trace": reduced, "checks": checks,
        "notes": notes,
    }


def _annotate_steps(tr, annotate):
    """Host spans around the step call and its blocking read (trace runs
    only; the untimed runs keep the Trainer untouched)."""
    import jax

    step = tr._train_step

    def traced(*args):
        with annotate("bench.step_call"):
            out = step(*args)
        with annotate("bench.blocking_read"):
            jax.block_until_ready(out[2]["loss"])
        return out

    tr._train_step = traced


def _reference_steps(spec, train_cfg, tf, structures, graphs, indices, w0):
    """The plain reference's steps from ``w0`` over the same rows: per step
    the loss, the first step's clipped gradient, and the final weights."""
    import jax

    w = train_cfg.loss
    loss_spec = reference.LossSpec(w.energy, w.force, w.stress, w.magmom,
                                   w.huber_delta)
    opt = reference.OptSpec(init_lr=train_cfg.init_lr,
                            total_steps=train_cfg.total_steps)
    ref = reference.Trainer(spec, loss_spec, opt)
    params = jax.tree.map(np.asarray, w0)
    mu = jax.tree.map(np.zeros_like, params)
    nu = jax.tree.map(np.zeros_like, params)
    block = tf.get("reference_block", len(indices[0]))
    losses, grad1 = [], None
    for k, idx in enumerate(indices):
        idx = np.sort(idx)
        denoms = reference.denominators(
            [structures[i].num_atoms for i in idx])
        blocks = [reference.flat_graph([structures[i] for i in part],
                                       [graphs[i] for i in part],
                                       num_slots=block)
                  for part in np.array_split(idx, -(-len(idx) // block))]
        params, mu, nu, loss, grads = ref.step(params, mu, nu, blocks,
                                               denoms, k)
        losses.append(loss)
        if k == 0:
            grad1 = jax.device_get(grads)
    return losses, grad1, jax.device_get(params)


def _check(ctx, cfg, tf, train_cfg, structures, graphs, indices, w0, grad1,
           params3, losses):
    """The reference's first three steps from the same weights on the same
    rows; the gaps of each loss, of the first gradient's leaf norms and of
    the three steps' parameter change.  With ``ctx.reference_control``
    set, the reference at that lower matmul precision stands in the
    program's place."""
    import jax

    args = (train_cfg, tf, structures, graphs, indices, w0)
    ref_losses, ref_grad1, ref_params = _reference_steps(
        reference.ModelSpec.from_config(cfg), *args)
    if ctx.reference_control:
        losses, grad1, params3 = _reference_steps(
            reference.ModelSpec.from_config(cfg, ctx.reference_control),
            *args)
    moved = compare.moved_leaves(ref_grad1)
    delta_p = jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b,
                           params3, w0)
    delta_r = jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b,
                           ref_params, w0)
    grad_gaps = compare.leaf_gaps(grad1, ref_grad1)
    update_gaps = compare.leaf_gaps(delta_p, delta_r, moved)
    lim = ctx.cell.limits
    checks = {
        "loss_gap": (compare.loss_gap(losses[:REFERENCE_STEPS], ref_losses),
                     lim["loss_gap"]),
        "grad_gap": (max(grad_gaps.values()), lim["grad_gap"]),
        "update_gap": (max(update_gaps.values()), lim["update_gap"]),
    }
    worst = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:3]
    notes = {"grad_worst": worst(grad_gaps),
             "update_worst": worst(update_gaps),
             "left_out": sum(not m for m in moved)}
    return checks, notes
