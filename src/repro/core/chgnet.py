"""CHGNet / FastCHGNet model (paper §II-B, §III).

Pure-JAX functional model: ``chgnet_init`` builds the parameter pytree,
``chgnet_apply`` runs the forward pass. Two readout modes:

  - readout="autodiff" (reference CHGNet): E from the energy head;
      F_i = -dE/d(x_i),  sigma = (1/V) dE/d(eps)  via jax.grad — this makes
      the *training* backward pass a second-order derivative (the cost the
      paper eliminates).
  - readout="direct" (FastCHGNet "F/S head"): Force/Stress heads (C1).

Block variant ("reference" | "fast") and GatedMLP impl ("ref" | "packed" |
"pallas") select the paper's other model-level optimizations;
``CHGNetConfig.precision`` selects the end-to-end precision policy
(DESIGN.md §4) governing param storage, compute, accumulation, and
output dtypes across the model, kernels, optimizer, and trainer.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.precision import resolve_policy

from . import basis, heads
from .graph import CrystalGraphBatch
from .interaction import (
    gated_mlp_init,
    interaction_block_apply,
    interaction_block_init,
    linear_apply,
    linear_init,
)

MAX_Z = 95  # elements supported (MPtrj has 89)
EV_A3_TO_GPA = heads.EV_A3_TO_GPA  # eV/A^3 -> GPa (defined once in heads)


@dataclasses.dataclass(frozen=True)
class CHGNetConfig:
    """Model + implementation-tier selection.

    ``precision`` selects the end-to-end :class:`repro.precision.
    PrecisionPolicy` (DESIGN.md §4): ``"f32"`` (everything float32, the
    reference), ``"mixed"`` (f32 parameter storage / accumulation, bf16
    GEMM + kernel VMEM operands — the recommended training policy), or
    ``"bf16"`` (bf16 storage too; the optimizer keeps f32 master weights,
    see ``optim.adam``).  The policy governs the cast boundaries in
    ``chgnet_apply``/``_trunk``, the LayerNorm/reduction accumulation
    dtype in ``core.interaction``/``core.heads``, and the operand dtype
    of every Pallas kernel behind ``mlp_impl``/``agg_impl``/``conv_impl``
    — it composes with all of those tier knobs.
    """

    dim: int = 64
    num_rbf: int = 31
    num_fourier: int = 31
    num_blocks: int = 3          # full interaction blocks (+1 final atom conv)
    r_cut_atom: float = 6.0
    r_cut_bond: float = 3.0
    envelope_p: int = 8
    readout: str = "direct"      # "direct" (F/S heads) | "autodiff" (reference)
    block_variant: str = "fast"  # "fast" (dep. elimination) | "reference"
    mlp_impl: str = "packed"     # "ref" | "packed" | "pallas"
    agg_impl: str = "scatter"    # "scatter" | "matmul" | "sorted" | "pallas"
    # "fused": one Pallas megakernel per conv (gather -> GatedMLP ->
    # envelope -> reduce over sorted CSR rows; also fuses the direct force
    # readout).  Requires the DESIGN.md §1 sorted-segment layout (any batch
    # from repro.batching / repro.serve); subsumes mlp_impl/agg_impl at the
    # conv call sites (angle_update and per-crystal sums still honor them).
    # See DESIGN.md §3.
    conv_impl: str = "unfused"   # "unfused" | "fused"
    # "undirected": undirected-bond redundancy bypass (DESIGN.md §5) —
    # geometry, the smooth-RBF basis, the packed bond-embed GEMM, and the
    # e^a/e^b envelope tables all run at the undirected capacity Eu ≈ E/2;
    # directed views materialize through the batch's bond_pair/bond_sign
    # mirror maps (cheap gathers; inside the megakernels when conv_impl=
    # "fused").  Composes with every other tier knob; "directed" keeps the
    # reference twice-stored layout.
    bond_store: str = "directed"  # "directed" | "undirected"
    envelope_impl: str = "factored"  # "factored" | "reference"
    # end-to-end precision policy (DESIGN.md §4), see class docstring
    precision: str = "f32"       # "f32" | "bf16" | "mixed"
    # Direct-readout stress tier (DESIGN.md §7).  "mlp": per-crystal MLP on
    # pooled atom features (FastCHGNet S head; extra stress_head params).
    # "bond_virial": physically-motivated per-bond virial
    # sigma = 1/(2V) sum_ij n_ij d_ij x_hat⊗x_hat sharing the force head's
    # n_ij — NO stress parameters; with conv_impl="fused" the accumulation
    # runs inside the force-readout megakernel epilogue (single launch).
    # Ignored under readout="autodiff" (stress comes from dE/d(strain)).
    stress_mode: str = "mlp"     # "mlp" | "bond_virial"
    stress_scale: float = 0.1
    # Operand-table residency tier of the Pallas kernels (DESIGN.md §9).
    # "vmem": tables whole-array VMEM-resident (the classic lowering);
    # "hbm": tables stay in HBM and stream through double-buffered DMA
    # ping/pong scratch — batch size becomes HBM-bounded (10k+-atom
    # structures); "auto" (default): each kernel launch estimates its
    # padded operand-table bytes against the VMEM budget
    # (kernels.ops.vmem_budget_bytes) and picks — small batches keep the
    # exact vmem lowering, oversized ones transparently stream.
    table_residency: str = "auto"  # "auto" | "vmem" | "hbm"
    # Symmetric half-graph trunk (DESIGN.md §10).  "undirected" makes the
    # undirected representation the COMPUTE representation, not just the
    # storage one: ``e`` lives at Eu ≈ E/2 rows from bond-embed through
    # every interaction block (symmetrized bond_conv scatters each Au-row
    # message to BOTH undirected destinations through the sym-incidence
    # store), and ``a`` lives at the Au == A/2 dedup rows (swap-symmetrized
    # angle_update) — halving every bond- and angle-level GEMM in the
    # trunk.  Requires ``bond_store="undirected"`` (the mirror maps ARE
    # the compute indices here); directed views of ``e`` materialize only
    # at the heads boundary.  This is a distinct model variant, not a
    # re-layout: directed bond_conv produces e_ij != e_ji, the symmetric
    # trunk by construction does not (parameter shapes are identical, so
    # checkpoints carry over).
    bond_features: str = "directed"  # "directed" | "undirected"

    def __post_init__(self):
        # dataclasses.replace (with_) re-runs this, so every derived config
        # is revalidated too
        if self.bond_features not in ("directed", "undirected"):
            raise ValueError(
                f"bond_features must be 'directed' or 'undirected', "
                f"got {self.bond_features!r}")
        if self.bond_features == "undirected" and \
                self.bond_store != "undirected":
            raise ValueError(
                'bond_features="undirected" (the symmetric half-graph '
                "trunk, DESIGN.md §10) requires the undirected bond store: "
                'pass bond_store="undirected" as well — the bond_pair / '
                "angle_pair mirror maps are its compute indices, got "
                f"bond_store={self.bond_store!r}")

    def with_(self, **kw) -> "CHGNetConfig":
        return dataclasses.replace(self, **kw)


def chgnet_init(key, cfg: CHGNetConfig, dtype=None):
    """Build the parameter pytree in ``cfg.precision``'s param dtype
    (``dtype`` overrides; pass ``jnp.float32`` explicitly for the legacy
    behavior regardless of policy)."""
    if dtype is None:
        dtype = resolve_policy(cfg.precision).param
    n_keys = 8 + cfg.num_blocks
    ks = jax.random.split(key, n_keys)
    params = {
        # Feature embedding (Eq. 2). The three bond linears are PACKED into
        # one (num_rbf -> 3*dim) weight (Fig. 3a): [e^0 | e^a | e^b].
        "atom_embed": jax.random.normal(ks[0], (MAX_Z, cfg.dim), dtype) * 0.02,
        "bond_embed": linear_init(ks[1], cfg.num_rbf, 3 * cfg.dim, dtype),
        "angle_embed": linear_init(ks[2], cfg.num_fourier, cfg.dim, dtype),
        # rbf_freqs feed the accum-pinned basis (DESIGN.md §4): they are
        # STORED at accum precision under every policy — a bf16 round-trip
        # would perturb the trainable frequencies by ~0.4% per step
        "rbf_freqs": basis.rbf_frequencies(cfg.num_rbf).astype(jnp.float32),
        "blocks": [
            interaction_block_init(ks[3 + i], cfg.dim, dtype)
            for i in range(cfg.num_blocks)
        ],
        # final block: atom conv only (CHGNet v0.3.0 has a last atom update)
        "final_block": interaction_block_init(ks[3 + cfg.num_blocks], cfg.dim, dtype),
        "energy_head": heads.energy_head_init(ks[4 + cfg.num_blocks], cfg.dim, dtype),
        "magmom_head": heads.magmom_head_init(ks[5 + cfg.num_blocks], cfg.dim, dtype),
    }
    if cfg.readout == "direct":
        params["force_head"] = heads.force_head_init(
            ks[6 + cfg.num_blocks], cfg.dim, dtype
        )
        if cfg.stress_mode == "mlp":
            params["stress_head"] = heads.stress_head_init(
                ks[7 + cfg.num_blocks], cfg.dim, cfg.stress_scale, dtype
            )
        # stress_mode="bond_virial" shares the force head's n_ij — no
        # stress parameters exist in that tier (DESIGN.md §7)
    return params


def param_count(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Forward trunk: embeddings + interaction blocks -> (v, e, a, geometry)
# ---------------------------------------------------------------------------

def _trunk(params, cfg: CHGNetConfig, graph: CrystalGraphBatch,
           displacement=None, strain=None):
    policy = resolve_policy(cfg.precision)
    env = (
        basis.envelope_factored
        if cfg.envelope_impl == "factored"
        else basis.envelope_reference
    )
    # bond_store="undirected" (DESIGN.md §5): geometry, RBF, and the bond
    # embedding run ONCE per undirected pair (Eu ≈ E/2); only e^0 is
    # expanded to the directed store (it seeds e, which bond_conv updates
    # per directed bond) — e^a/e^b stay at Eu for the whole trunk.
    # Angle-pair dedup rides along: theta / Fourier / angle-embed run at
    # the Au == Na/2 dedup rows and expand via angle_pair below.
    with jax.named_scope("basis"):
        if cfg.bond_store == "undirected":
            vec_und, dist_und, vec, dist, _cos, theta = \
                basis.compute_geometry_undirected(
                    graph, displacement=displacement, strain=strain,
                    angle_rows="undirected",
                )
            rbf_dist = dist_und
        elif cfg.bond_store == "directed":
            vec, dist, _cos, theta = basis.compute_geometry(
                graph, displacement=displacement, strain=strain
            )
            vec_und = dist_und = None
            rbf_dist = dist
        else:
            raise ValueError(f"unknown bond store {cfg.bond_store!r}")
        if cfg.mlp_impl == "pallas":
            from repro.kernels import ops as kops

            rbf = kops.fused_rbf(
                rbf_dist, params["rbf_freqs"], cfg.r_cut_atom,
                cfg.envelope_p
            )
            four = kops.fused_fourier(theta, cfg.num_fourier)
        else:
            rbf = basis.smooth_rbf(
                rbf_dist, params["rbf_freqs"], cfg.r_cut_atom,
                cfg.envelope_p, envelope=env,
            )
            four = basis.fourier_basis(theta, cfg.num_fourier)

    # PRECISION BOUNDARY (DESIGN.md §4): geometry + basis above run in
    # f32 (accum-pinned); everything from the embedding GEMMs through the
    # interaction blocks runs at the policy's compute dtype.  Parameters
    # follow via the cast-to-compute views in linear/gated_mlp_apply.
    with jax.named_scope("embed"):
        cd = policy.compute
        rbf = policy.cast_compute(rbf)
        four = policy.cast_compute(four)

        # Feature embedding (packed bond linear -> e0 / e_a / e_b).
        # Undirected store: the (rbf -> 3*dim) GEMM runs at Eu; e^a/e^b
        # keep that granularity (the blocks never update them), e^0
        # expands once.
        packed = linear_apply(params["bond_embed"], rbf)  # (Nb|Nu, 3*dim)
        e0, e_a, e_b = jnp.split(packed, 3, axis=-1)
        v = params["atom_embed"].astype(cd)[graph.atom_z] \
            * graph.atom_mask[..., None].astype(cd)
        if cfg.bond_store == "undirected":
            # angle-pair dedup: ``four`` is at the Au dedup rows — embed
            # once per unordered (ij, ik) pair, expand through angle_pair,
            # and re-mask (padded angles carry pair=0)
            a_und = linear_apply(params["angle_embed"], four) \
                * graph.und_angle_mask[..., None].astype(cd)
            umask = graph.und_mask[..., None].astype(cd)
            e_a = e_a * umask
            e_b = e_b * umask
            if cfg.bond_features == "undirected":
                # symmetric trunk (DESIGN.md §10): e stays Eu-resident and
                # a stays Au-resident for the whole trunk — the blocks
                # consume them through the mirror maps / sym-incidence
                # store
                a = a_und
                e = e0 * umask
            else:
                a = a_und[graph.angle_pair] \
                    * graph.angle_mask[..., None].astype(cd)
                e = e0[graph.bond_pair] \
                    * graph.bond_mask[..., None].astype(cd)
        else:
            a = linear_apply(params["angle_embed"], four) \
                * graph.angle_mask[..., None].astype(cd)
            e = e0 * graph.bond_mask[..., None].astype(cd)

    for i, blk in enumerate(params["blocks"]):
        with jax.named_scope(f"block{i}"):
            v, e, a = interaction_block_apply(
                blk, graph, v, e, a, e_a, e_b,
                variant=cfg.block_variant,
                mlp_impl=cfg.mlp_impl,
                agg_impl=cfg.agg_impl,
                conv_impl=cfg.conv_impl,
                bond_store=cfg.bond_store,
                bond_features=cfg.bond_features,
                table_residency=cfg.table_residency,
            )
    # last block updates atoms only (matches CHGNet's final atom conv)
    from .interaction import atom_conv

    with jax.named_scope("final_block"), jax.named_scope("atom_conv"):
        v = atom_conv(
            params["final_block"], graph, v, e, e_a,
            mlp_impl=cfg.mlp_impl, agg_impl=cfg.agg_impl,
            conv_impl=cfg.conv_impl, bond_store=cfg.bond_store,
            bond_features=cfg.bond_features,
            table_residency=cfg.table_residency,
        )
    # vec_und/dist_und (None for the directed store) ride along for the
    # bond_virial stress tier's undirected half-geometry path (§5/§7)
    return v, e, a, vec, dist, vec_und, dist_und


def _volume(lattice):
    return jnp.abs(jnp.linalg.det(lattice))


# ---------------------------------------------------------------------------
# Public forward passes
# ---------------------------------------------------------------------------

def chgnet_apply(params, cfg: CHGNetConfig, graph: CrystalGraphBatch):
    """Full prediction: energy (B,), forces (A,3), stress (B,3,3), magmom (A,).

    readout="direct": one forward pass, no derivatives (FastCHGNet).
    readout="autodiff": forces/stress by differentiating the energy
    (reference CHGNet) — training through this is second-order.

    All outputs are cast to the precision policy's ``output_dtype``
    (f32 for every built-in policy, DESIGN.md §4) so downstream
    consumers — losses, MD integrators, serving — see one dtype
    regardless of ``cfg.precision``.
    """
    policy = resolve_policy(cfg.precision)

    def _out(d):
        return {k: policy.cast_output(x) for k, x in d.items()}

    if cfg.readout == "direct":
        v, e, a, vec, dist, vec_und, dist_und = _trunk(params, cfg, graph)
        with jax.named_scope("readout"):
            if cfg.bond_features == "undirected":
                # heads boundary (DESIGN.md §10): the force/stress heads
                # read per-directed-bond features; expand the Eu-resident e
                # ONCE
                e = e[graph.bond_pair] \
                    * graph.bond_mask[..., None].astype(e.dtype)
            energy = heads.energy_head_apply(params["energy_head"], graph, v)
            magmom = heads.magmom_head_apply(params["magmom_head"], graph, v)
            if cfg.stress_mode == "bond_virial":
                # single-pass force + stress (DESIGN.md §7): with conv_impl=
                # "fused" both come out of ONE megakernel launch
                forces, stress = heads.force_virial_head_apply(
                    params["force_head"], graph, e, vec, dist,
                    vec_und=vec_und, dist_und=dist_und,
                    agg_impl=cfg.agg_impl, conv_impl=cfg.conv_impl,
                    bond_store=cfg.bond_store,
                    table_residency=cfg.table_residency)
            elif cfg.stress_mode == "mlp":
                forces = heads.force_head_apply(
                    params["force_head"], graph, e, vec, dist,
                    agg_impl=cfg.agg_impl, conv_impl=cfg.conv_impl,
                    table_residency=cfg.table_residency)
                stress = heads.stress_head_apply(params["stress_head"],
                                                 graph, v)
            else:
                raise ValueError(f"unknown stress mode {cfg.stress_mode!r}")
        return _out({"energy": energy, "forces": forces, "stress": stress,
                     "magmom": magmom})

    if cfg.readout == "autodiff":
        def energy_of(disp, strain):
            v = _trunk(
                params, cfg, graph, displacement=disp, strain=strain
            )[0]
            with jax.named_scope("readout"):
                e_tot = heads.energy_head_apply(params["energy_head"], graph,
                                                v)
            return jnp.sum(e_tot), v

        disp0 = jnp.zeros_like(graph.frac_coords)
        strain0 = jnp.zeros_like(graph.lattice)
        (de_ddisp, de_dstrain), v = jax.grad(
            energy_of, argnums=(0, 1), has_aux=True
        )(disp0, strain0)
        with jax.named_scope("readout"):
            energy = heads.energy_head_apply(params["energy_head"], graph, v)
            magmom = heads.magmom_head_apply(params["magmom_head"], graph, v)
            forces = -de_ddisp * graph.atom_mask[..., None]
            vol = _volume(graph.lattice)[:, None, None]
            stress = de_dstrain / (vol + 1e-12) * EV_A3_TO_GPA
            stress = stress * graph.crystal_mask[:, None, None]
        return _out({"energy": energy, "forces": forces, "stress": stress,
                     "magmom": magmom})

    raise ValueError(f"unknown readout {cfg.readout!r}")


@partial(jax.jit, static_argnums=(1,))
def chgnet_apply_jit(params, cfg: CHGNetConfig, graph: CrystalGraphBatch):
    return chgnet_apply(params, cfg, graph)
