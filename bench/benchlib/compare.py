"""The numbers that decide ``correct``, each a gap between the program and
the plain reference (0 where they agree)."""
from __future__ import annotations

import numpy as np


def loss_gap(program: list[float], reference: list[float]) -> float:
    """Largest relative gap of the per-step losses."""
    return max(abs(p - r) / abs(r) for p, r in zip(program, reference))


def _leaf_norms(tree) -> list[float]:
    import jax

    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


def leaf_gaps(program, reference, keep=None) -> dict:
    """Per leaf, the gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's.
    ``keep``: per-leaf booleans; leaves marked False are left out."""
    import jax

    p, r = _leaf_norms(program), _leaf_norms(reference)
    keep = keep or [True] * len(r)
    median = float(np.median([x for x, k in zip(r, keep) if k]))
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(reference)[0]]
    return {n: abs(a - b) / max(b, median)
            for n, a, b, k in zip(paths, p, r, keep) if k}


def leaf_norm_gap(program, reference, keep=None) -> float:
    """The worst leaf's gap (see :func:`leaf_gaps`)."""
    return max(leaf_gaps(program, reference, keep).values())


def moved_leaves(reference_grad, floor: float = 1e-3) -> list[bool]:
    """Leaves whose reference gradient norm is at least ``floor`` of the
    median leaf's.  The others (a gradient nought to rounding, as a bias
    under LayerNorm) move under Adam by round-off alone."""
    r = _leaf_norms(reference_grad)
    median = float(np.median(r))
    return [x >= floor * median for x in r]


def force_gap(program: list[np.ndarray], reference: list[np.ndarray]) -> float:
    """Worst structure's largest force-component gap over its largest
    reference force component."""
    return max(float(np.max(np.abs(p - r)) / max(np.max(np.abs(r)), 1e-30))
               for p, r in zip(program, reference))


def energy_gap(program, reference, atoms) -> float:
    """Largest energy gap per atom, in eV/atom."""
    program, reference = np.asarray(program), np.asarray(reference)
    return float(np.max(np.abs(program - reference) / np.asarray(atoms)))
