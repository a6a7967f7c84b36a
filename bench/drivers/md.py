"""MD driver: many replicas stepped by ``BatchedMD`` through ``ServeEngine``.

Set-up builds the replicas (sizes evenly spaced, structures from the
traffic's ``data_seed``), the benchmark's weights from ``--seed``,
``ServeEngine.for_structures`` and ``BatchedMD`` as the program's own
example does, and runs ``warm_steps`` MD steps, which compile every
replica group's shape.  The window then calls ``BatchedMD.step(1)`` until
``--seconds`` have passed.  Every call's input positions and returned
forces and energies are kept (a host copy, microseconds a step).

After the window the reference checks, for a sample of the window's steps
drawn from ``--seed`` (the last step always among them), every replica's
forces and energies at the positions the program stepped from, and
replays the integrator over every step from the program's own forces,
which must give the positions the program stepped to.

Traffic keys: ``replicas``, ``smallest``, ``largest``, ``data_seed``,
``dt``, ``skin``, ``max_group``, ``warm_steps``, ``checked_steps``.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np

from benchlib import (cells, compare, crystals, flops, reference, spans,
                      trace, weights)


class Recorder:
    """Wraps ``BatchedMD.step``: per call, the input positions and the
    returned forces and energies, and (in traced runs) host spans and the
    fill of every packed group."""

    def __init__(self, md, annotate, traced: bool):
        self.md, self.annotate = md, annotate
        self.positions, self.forces, self.energies = [], [], []
        self.fill = [0.0, 0]  # real bonds, capacity bonds of packed groups
        if traced:
            self._instrument()

    def step(self):
        self.positions.append([r.crystal.frac_coords.copy()
                               for r in self.md.replicas])
        with self.annotate("bench.md_step"):
            out = self.md.step(1)
        self.forces.append(out["forces"])
        self.energies.append(np.asarray(out["energy"]).copy())

    def _instrument(self):
        engine, serve, span = self.md.serve.engine, self.md.serve, \
            self.annotate
        pack, step_fn = engine.pack, serve.step_fn

        def counted_pack(*a, **k):
            with span("bench.pack"):
                batch, info = pack(*a, **k)
            mask = np.asarray(batch.bond_mask)
            self.fill[0] += float(mask.sum())
            self.fill[1] += int(mask.size)
            return batch, info

        def spanned_step_fn(*a, **k):
            fn = step_fn(*a, **k)

            def call(*x):
                with span("bench.step_call"):
                    return fn(*x)
            return call

        engine.pack, serve.step_fn = counted_pack, spanned_step_fn
        for r in self.md.replicas:
            update = r.nlist.update

            def spanned(c, update=update):
                with span("bench.nlist"):
                    return update(c)
            r.nlist.update = spanned


def _graph_rows(graphs) -> tuple:
    return (sum(g.num_bonds for g in graphs),
            sum(g.num_angles for g in graphs))


def run(ctx) -> dict:
    import jax

    from repro.configs import chgnet_mptrj as C
    from repro.core.neighbors import Crystal
    from repro.serve import BatchedMD, ServeEngine

    cell, tf, cfg = ctx.cell, ctx.cell.traffic, ctx.cell.config
    annotate = jax.profiler.TraceAnnotation
    model_cfg = cells.model_config(C, cfg, ctx.precision)
    sizes = crystals.md_replica_sizes(tf["replicas"], tf["smallest"],
                                      tf["largest"])
    structures = [crystals.md_replica(int(n), tf["data_seed"] + i)
                  for i, n in enumerate(sizes)]
    program = [Crystal(lattice=s.lattice, frac_coords=s.frac_coords.copy(),
                       atomic_numbers=s.atomic_numbers) for s in structures]
    params = weights.make_weights(cfg, ctx.seed, ctx.devices[0])
    serve = ServeEngine.for_structures(params, model_cfg, program)
    md = BatchedMD(serve, program, dt=tf["dt"], skin=tf["skin"],
                   max_group=tf["max_group"])
    ctx.plant("md", md)
    rec = Recorder(md, annotate, bool(ctx.trace))
    for _ in range(tf["warm_steps"]):
        rec.step()
    first = len(rec.forces)
    rec.fill = [0.0, 0]
    before = md.stats()

    logdir = tempfile.mkdtemp(prefix="bench_trace_") if ctx.trace else None
    if ctx.trace:
        profiler = ctx.profile(logdir)
        profiler.__enter__()
    ctx.window_starts()
    t0 = time.perf_counter()
    with annotate("bench.window"):
        while time.perf_counter() < t0 + ctx.seconds:
            rec.step()
    t1 = time.perf_counter()
    if ctx.trace:
        profiler.__exit__(None, None, None)
    compiles = ctx.compiles_in_window()
    final = [r.crystal.frac_coords.copy() for r in md.replicas]
    peak = ctx.memory_peak_bytes()
    steps = len(rec.forces) - first
    atoms = int(sizes.sum())
    failed = sum(int(not np.all(np.isfinite(f)))
                 for fs in rec.forces[first:] for f in fs)
    params_host = jax.device_get(params)
    stats = md.stats()
    notes = {k: stats[k] for k in ("nlist_rebuilds", "nlist_updates",
                                   "compile_cache_entries")}

    # per-step rows for the FLOP count: the graphs of the stepped
    # positions, counted by the benchmark's own neighbor search
    readings = {"window_s": t1 - t0, "steps": steps,
                "fill.md": tuple(rec.fill),
                "nlist.md": {k: stats[k] - before[k]
                             for k in ("nlist_rebuilds", "nlist_updates")}}
    reduced = None
    if ctx.trace:
        try:
            reduced = trace.reduce(trace.load(trace.find_xplane(logdir)),
                                   steps=steps)
            # op names live in the trace-viewer file, which an MD window
            # passes the size cap of: host spans and busy time only
            readings["spans"] = spans.reduce(spans.load(
                trace.find_xplane(logdir), op_names=False))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
    del md, serve, params
    gc.collect()
    checks, rows = _check(ctx, cfg, tf, structures, rec, first, final,
                          params_host)
    readings["flops.md"] = steps * flops.serve_step_flops(cfg, atoms, *rows)
    return {
        "attempted": steps * len(structures), "failed": failed,
        "end_to_end": {"md_atom_steps_per_s": atoms * steps / (t1 - t0)},
        "memory_peak_bytes": peak, "compiles_in_window": compiles,
        "readings": readings, "trace": reduced, "checks": checks,
        "notes": notes,
    }


def replay_gap(structures, rec, dt: float, final) -> float:
    """Largest distance (A) between the positions the program stepped to
    and those its own forces give under the NVE update it states:
    v += F dt; x += v dt (unit masses, velocities from rest)."""
    worst = 0.0
    v = [np.zeros((s.num_atoms, 3)) for s in structures]
    after = rec.positions[1:] + [final]
    for pos, forces, nxt in zip(rec.positions, rec.forces, after):
        for i, s in enumerate(structures):
            v[i] += forces[i] * dt
            cart = pos[i] @ s.lattice + v[i] * dt
            want = (cart @ np.linalg.inv(s.lattice)) % 1.0
            d = nxt[i] - want
            d -= np.round(d)
            worst = max(worst, float(np.max(np.linalg.norm(d @ s.lattice,
                                                           axis=-1))))
    return worst


def _predict(params, spec, snaps, graphs, block):
    """Reference forces (per structure) and energies, ``block`` structures
    at a time."""
    forces, energies = [], []
    for b in range(0, len(snaps), block):
        part = snaps[b:b + block]
        out = reference.predict(params, spec, reference.flat_graph(
            part, graphs[b:b + block], num_slots=block))
        offs = np.cumsum([0] + [s.num_atoms for s in part])
        forces += [out["forces"][i:j] for i, j in zip(offs, offs[1:])]
        energies += list(out["energy"][:len(part)])
    return forces, np.asarray(energies)


def _check(ctx, cfg, tf, structures, rec, first, final, params):
    """Gaps of the sampled steps' forces and energies, and of the replayed
    positions.  With ``ctx.reference_control`` set, the reference at that
    lower matmul precision stands in the program's place."""
    spec = reference.ModelSpec.from_config(cfg)
    control = ctx.reference_control and reference.ModelSpec.from_config(
        cfg, ctx.reference_control)
    window = list(range(first, len(rec.forces)))
    rng = np.random.default_rng(weights.seed_key(ctx.seed))
    picked = sorted(set(rng.choice(window[:-1] or window,
                                   tf["checked_steps"] - 1).tolist())
                    | {window[-1]})
    f_gap = e_gap = 0.0
    rows = None
    block = tf["reference_block"]
    for t in picked:
        snaps = [crystals.Structure(s.lattice, pos, s.atomic_numbers)
                 for s, pos in zip(structures, rec.positions[t])]
        graphs = [crystals.neighbors(s.lattice, s.frac_coords,
                                     cfg["r_cut_atom"], cfg["r_cut_bond"])
                  for s in snaps]
        rows = rows or _graph_rows(graphs)
        ref_f, ref_e = _predict(params, spec, snaps, graphs, block)
        prog_f, prog_e = (rec.forces[t], rec.energies[t]) if not control \
            else _predict(params, control, snaps, graphs, block)
        f_gap = max(f_gap, compare.force_gap(prog_f, ref_f))
        e_gap = max(e_gap, compare.energy_gap(
            prog_e, ref_e, [s.num_atoms for s in snaps]))
    lim = ctx.cell.limits
    checks = {
        "force_gap": (f_gap, lim["force_gap"]),
        "energy_gap": (e_gap, lim["energy_gap"]),
        "position_gap": (replay_gap(structures, rec, tf["dt"], final),
                         lim["position_gap"]),
    }
    return checks, rows
