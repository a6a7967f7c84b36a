"""FLOPs a CHGNet pass requires, counted from its GEMMs over real rows.

Each (m -> n) GEMM over r rows is 2 r m n FLOPs.  Rows are the real atoms,
bonds and angles of the structures processed; padded rows and recomputed
work do not count.  Elementwise work (basis functions, LayerNorm, gating,
segment sums) is left out: it is small next to the GEMMs and bound by
bandwidth, not by the MXU, so a FLOP share of it would flatter no one.
"""
from __future__ import annotations


def gemm_flops(rows: int, d_in: int, d_out: int) -> float:
    return 2.0 * rows * d_in * d_out


def _magmom_head_flops(cfg: dict, atoms: int) -> float:
    d = cfg["dim"]
    return gemm_flops(atoms, d, d) + gemm_flops(atoms, d, 1)


def forward_flops(cfg: dict, atoms: int, bonds: int, angles: int) -> float:
    """One forward pass of ``cfg`` (a ``configs/*.json`` dict) over real
    rows, up to and including the readout heads the configuration has."""
    d, nb = cfg["dim"], cfg["num_blocks"]
    trunk = (gemm_flops(bonds, cfg["num_rbf"], 3 * d)       # bond embed
             + gemm_flops(angles, cfg["num_fourier"], d))  # angle embed
    block = (gemm_flops(bonds, 3 * d, 2 * d)       # atom conv GatedMLP
             + gemm_flops(atoms, d, d)             # atom out
             + gemm_flops(angles, 4 * d, 2 * d)    # bond conv GatedMLP
             + gemm_flops(bonds, d, d)             # bond out
             + gemm_flops(angles, 4 * d, 2 * d))   # angle update GatedMLP
    final = gemm_flops(bonds, 3 * d, 2 * d) + gemm_flops(atoms, d, d)
    heads = (gemm_flops(atoms, d, d) * 2 + gemm_flops(atoms, d, 1)  # energy
             + _magmom_head_flops(cfg, atoms))
    if cfg["readout"] == "direct":
        heads += (gemm_flops(bonds, d, d) + gemm_flops(bonds, d, 1)  # force
                  + gemm_flops(atoms, d, d) + gemm_flops(atoms, d, 9))
    return trunk + nb * block + final + heads


def train_step_flops(cfg: dict, atoms: int, bonds: int, angles: int) -> float:
    """Forward and backward of a training step.

    A GEMM of cost F costs F forward, F for an input-only gradient and 2F
    for a full backward (input and weight gradients).  Under the direct
    readout every GEMM runs forward and full backward: 3F.

    Under the autodiff readout the forces and stress are -dE/dx and dE/de,
    so the loss holds the inner backward of the energy to displacement and
    strain (input gradients only, one GEMM of the transposed weight per
    GEMM, as ``serve_step_flops`` counts it), and training differentiates
    that again.  Each GEMM on the energy path then costs F forward, F in
    the inner backward, and 4F in the outer backward, which takes the
    full backward of both the forward GEMM and its transpose in the inner
    backward: 6F.  The magmom head is not on the energy path: 3F.
    """
    f = forward_flops(cfg, atoms, bonds, angles)
    if cfg["readout"] == "direct":
        return 3.0 * f
    magmom = _magmom_head_flops(cfg, atoms)
    return 6.0 * (f - magmom) + 3.0 * magmom


def serve_step_flops(cfg: dict, atoms: int, bonds: int, angles: int) -> float:
    """One MD force evaluation: the forward, plus for the autodiff readout
    the backward to the positions (input gradients only: one GEMM of each
    size)."""
    f = forward_flops(cfg, atoms, bonds, angles)
    return f if cfg["readout"] == "direct" else 2.0 * f


def conv_gemm_flops(dim: int, bonds: int, angles: int) -> float:
    """Bond- and angle-level GEMMs of one interaction block (bond conv
    GatedMLP, bond out, angle update GatedMLP): the arithmetic of
    ``benchmarks/bench_iteration.py`` ``trunk_gemm_flops``."""
    return (gemm_flops(angles, 4 * dim, 2 * dim) + gemm_flops(bonds, dim, dim)
            + gemm_flops(angles, 4 * dim, 2 * dim))
