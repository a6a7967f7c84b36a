"""Batched MD serving engine (the paper's Table II workload, productionized).

Charge-informed MD is a *serving* workload: millions of one-step E/F/sigma
predictions with a direct-force readout.  Three levers over the naive
"rebuild the neighbor list and re-jit every step" loop:

  1. **Verlet skin reuse** (``repro.core.neighbors.VerletNeighborList``):
     candidate pairs are built once with ``r_cut + skin`` and only
     re-measured per step; the O(N^2 * images) image search runs only when
     an atom has moved more than ``skin/2``.
  2. **Multi-replica batching**: many independent simulations are stepped
     as *one* padded batch per capacity bucket — one device program per
     group instead of one per replica.
  3. **Persistent compiled serve step per bucket**: step functions are
     memoized in the shared ``repro.batching`` compile cache keyed on
     ``(bucket, slots, config)``, so group membership can change freely
     without re-tracing.

Every batch leaving the pack path satisfies the sorted-segment layout
(DESIGN.md §1) — the Verlet refilter preserves bond order and packing
canonicalizes + validates — so the serve step can run any
``CHGNetConfig.agg_impl`` ("scatter" | "matmul" | "sorted" | "pallas")
and ``conv_impl`` ("unfused" | "fused", the DESIGN.md §3 message-passing
megakernels) unchanged; set ``validate_layout=False`` to skip the
per-batch check in tight MD loops.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.batching import (
    BatchCapacities,
    BatchingEngine,
    CapacityLadder,
    CompileCache,
    atom_offsets,
    ladder_from_stats,
)
from repro.core.chgnet import CHGNetConfig, chgnet_apply
from repro.core.neighbors import (
    Crystal,
    GraphIndices,
    VerletNeighborList,
    build_graph,
)
from repro.runtime import spans


def _next_pow2(k: int) -> int:
    return 1 << max(0, (k - 1).bit_length())


def structure_ladder(
    graphs: list[GraphIndices],
    crystals: list[Crystal],
    *,
    num_buckets: int = 3,
    margin: float = 1.5,
    align: int = 32,
) -> CapacityLadder:
    """Per-structure capacity ladder sized from observed structures.

    ``margin`` leaves headroom for bond/angle-count fluctuation as atoms
    move during MD (the overflow path still catches outliers without
    truncating).
    """
    atoms = np.array([c.num_atoms for c in crystals])
    bonds = np.array([g.num_bonds for g in graphs])
    angles = np.array([g.num_angles for g in graphs])
    return ladder_from_stats(
        atoms, bonds, angles, per_device_batch=1,
        num_buckets=num_buckets, margin=margin, align=align,
    )


class ServeEngine:
    """One-step E/F/sigma/magmom prediction over bucketed padded batches.

    ``precision`` overrides ``model_cfg.precision`` for serving (DESIGN.md
    §4): MD inference typically wants ``"mixed"`` — bf16 GEMM/VMEM
    operands halve the activation footprint per replica slot while the
    accum-pinned reductions keep E/F/sigma at f32 quality, and outputs
    are f32 either way (``output_dtype``), so integrators see no change.
    Params may stay f32 (training layout): the model casts per-use.
    """

    def __init__(
        self,
        params,
        model_cfg: CHGNetConfig,
        ladder: CapacityLadder,
        *,
        cache: CompileCache | None = None,
        validate_layout: bool = True,
        precision: str | None = None,
    ):
        if precision is not None:
            model_cfg = model_cfg.with_(precision=precision)
        self.params = params
        self.model_cfg = model_cfg
        self.engine = BatchingEngine(ladder, cache,
                                     validate_layout=validate_layout)

    @classmethod
    def for_structures(
        cls,
        params,
        model_cfg: CHGNetConfig,
        crystals: list[Crystal],
        graphs: list[GraphIndices] | None = None,
        validate_layout: bool = True,
        precision: str | None = None,
        **ladder_kw,
    ) -> "ServeEngine":
        graphs = graphs or [
            build_graph(c, model_cfg.r_cut_atom, model_cfg.r_cut_bond)
            for c in crystals
        ]
        return cls(params, model_cfg,
                   structure_ladder(graphs, crystals, **ladder_kw),
                   validate_layout=validate_layout, precision=precision)

    def admission_check(self, caps: BatchCapacities) -> None:
        """Refuse early (clear error) what the vmem tier cannot serve.

        Under ``table_residency="vmem"`` a batch whose operand tables
        exceed the VMEM budget would only fail deep inside kernel
        lowering (or OOM the device); check at admission instead and
        point at the fix.  ``"auto"`` (the default) and ``"hbm"`` admit
        ANY capacity — the tables stream through the DESIGN.md §9
        double-buffered DMA tier, so 10k+-atom structures pack and serve
        instead of erroring.
        """
        cfg = self.model_cfg
        if cfg.table_residency != "vmem":
            return
        from repro.kernels.ops import estimate_table_bytes, vmem_budget_bytes

        table_bytes = estimate_table_bytes(
            caps.atoms, caps.bonds, caps.angles, cfg.dim,
            num_und=caps.und_cap if cfg.bond_store == "undirected" else None,
        )
        budget = vmem_budget_bytes()
        if table_bytes > budget:
            raise ValueError(
                f"batch capacities {caps} need ~{table_bytes} operand-table "
                f"bytes, over the {budget}-byte VMEM budget; serve with "
                f"table_residency='auto' (or 'hbm') to stream tables from "
                f"HBM (DESIGN.md §9)"
            )

    def step_fn(self, caps: BatchCapacities, num_slots: int):
        """Persistent compiled serve step for (bucket, slots, config).

        The batch argument is donated (each packed batch is consumed
        exactly once), so its buffers back the outputs instead of a fresh
        allocation per MD step; params stay undonated — every replica
        group reuses them.
        """
        cfg = self.model_cfg

        def build():
            return jax.jit(lambda p, b: chgnet_apply(p, cfg, b),
                           donate_argnums=(1,))

        return self.engine.compiled("serve", caps, num_slots, cfg, build)

    def predict(
        self,
        crystals: list[Crystal],
        graphs: list[GraphIndices] | None = None,
    ) -> dict:
        """Predict E/F/sigma/magmom for a list of structures as one batch.

        Returns host-side per-structure arrays: ``energy`` (R,), ``forces``
        a list of (N_i, 3), ``stress`` (R, 3, 3), ``magmom`` list of (N_i,).
        """
        if graphs is None:
            graphs = [
                build_graph(c, self.model_cfg.r_cut_atom,
                            self.model_cfg.r_cut_bond)
                for c in crystals
            ]
        slots = _next_pow2(len(crystals))
        bucket = self.engine.ladder.bucket_for(
            max(c.num_atoms for c in crystals),
            max(g.num_bonds for g in graphs),
            max(g.num_angles for g in graphs),
        )
        caps = bucket.scaled(slots)
        self.admission_check(caps)
        batch, _ = self.engine.pack(
            crystals, graphs, caps=caps, num_crystal_slots=slots
        )
        out = self.step_fn(bucket, slots)(self.params, batch)
        jax.block_until_ready(out["forces"])
        offs = atom_offsets(crystals)
        forces = np.asarray(out["forces"])
        magmom = np.asarray(out["magmom"])
        return {
            "energy": np.asarray(out["energy"])[: len(crystals)],
            "forces": [
                forces[o:o + c.num_atoms] for o, c in zip(offs, crystals)
            ],
            "stress": np.asarray(out["stress"])[: len(crystals)],
            "magmom": [
                magmom[o:o + c.num_atoms] for o, c in zip(offs, crystals)
            ],
        }

    def stats(self) -> dict:
        return self.engine.stats()


@dataclasses.dataclass
class _Replica:
    crystal: Crystal
    velocities: np.ndarray
    nlist: VerletNeighborList
    inv_lattice: np.ndarray


class BatchedMD:
    """Multi-replica MD: independent simulations stepped as padded batches.

    Replicas are grouped per step by their capacity bucket; each group is
    packed into one batch (slots padded to a power of two so the compile
    cache stays small) and stepped by the persistent compiled serve
    function.  Integration is the toy NVE velocity update of the seed's
    ``examples/serve_md.py`` (unit masses) — the point here is the serving
    substrate, not the integrator.
    """

    def __init__(
        self,
        serve: ServeEngine,
        crystals: list[Crystal],
        *,
        dt: float = 1e-3,
        skin: float = 0.5,
        max_group: int = 16,
    ):
        self.serve = serve
        self.dt = dt
        self.max_group = max_group
        cfg = serve.model_cfg
        self.replicas = [
            _Replica(
                crystal=c,
                velocities=np.zeros((c.num_atoms, 3)),
                nlist=VerletNeighborList(
                    c, cfg.r_cut_atom, cfg.r_cut_bond, skin
                ),
                inv_lattice=np.linalg.inv(c.lattice),
            )
            for c in crystals
        ]
        self.steps_done = 0

    @property
    def num_replicas(self) -> int:
        return len(self.replicas)

    def _grouped(self, graphs: list[GraphIndices]):
        """Group replica ids by per-structure bucket, chunked to max_group."""
        ladder = self.serve.engine.ladder
        by_bucket: dict[BatchCapacities, list[int]] = {}
        for i, (r, g) in enumerate(zip(self.replicas, graphs)):
            b = ladder.bucket_for(
                r.crystal.num_atoms, g.num_bonds, g.num_angles
            )
            by_bucket.setdefault(b, []).append(i)
        for bucket, ids in by_bucket.items():
            for s in range(0, len(ids), self.max_group):
                yield bucket, ids[s:s + self.max_group]

    def step(self, n_steps: int = 1) -> dict:
        """Advance every replica ``n_steps``; returns last-step outputs.

        Each step is the host span ``repro.md.step`` (``step_num`` =
        ``steps_done``) holding ``repro.md.nlist`` (every replica's
        neighbor-list update), per replica group ``repro.md.pack`` and
        ``repro.md.dispatch``, then ``repro.md.collect`` (waiting for the
        device and the D2H copies) and ``repro.md.integrate``.
        """
        last = {}
        for _ in range(n_steps):
            with spans.step("md.step", self.steps_done):
                last = self._step_once()
            self.steps_done += 1
        return last

    def _step_once(self) -> dict:
        with spans.span("md.nlist"):
            graphs = [r.nlist.update(r.crystal) for r in self.replicas]
        energies = np.zeros(self.num_replicas)
        forces_by_replica: list[np.ndarray | None] = [None] * self.num_replicas
        # dispatch every group first (jax dispatch is async) so device
        # compute of group k overlaps host packing of group k+1 ...
        dispatched = []
        for bucket, ids in self._grouped(graphs):
            crystals = [self.replicas[i].crystal for i in ids]
            slots = _next_pow2(len(ids))
            caps = bucket.scaled(slots)
            with spans.span("md.pack"):
                batch, _ = self.serve.engine.pack(
                    crystals, graphs=[graphs[i] for i in ids],
                    caps=caps, num_crystal_slots=slots,
                )
            with spans.span("md.dispatch"):
                out = self.serve.step_fn(bucket, slots)(
                    self.serve.params, batch
                )
            dispatched.append((ids, crystals, out))
        # ... then collect (np.asarray blocks per output)
        with spans.span("md.collect"):
            for ids, crystals, out in dispatched:
                f = np.asarray(out["forces"])
                e = np.asarray(out["energy"])
                offs = atom_offsets(crystals)
                for k, i in enumerate(ids):
                    na = crystals[k].num_atoms
                    forces_by_replica[i] = f[offs[k]:offs[k] + na]
                    energies[i] = e[k]
        # toy NVE update (unit masses) — exercises the serve path
        with spans.span("md.integrate"):
            for r, f in zip(self.replicas, forces_by_replica):
                r.velocities += f * self.dt
                cart = r.crystal.cart_coords() + r.velocities * self.dt
                r.crystal.frac_coords = (cart @ r.inv_lattice) % 1.0
        return {"energy": energies, "forces": forces_by_replica}

    def stats(self) -> dict:
        s = self.serve.stats()
        s.update(
            steps_done=self.steps_done,
            nlist_rebuilds=sum(r.nlist.rebuilds for r in self.replicas),
            nlist_updates=sum(r.nlist.updates for r in self.replicas),
            nlist_singleton_bonds=sum(
                r.nlist.singleton_bonds for r in self.replicas),
        )
        return s
