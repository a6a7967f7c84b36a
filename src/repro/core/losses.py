"""Multi-target Huber loss (paper §IV: prefactors E:2, F:1.5, S:0.1, M:0.1).

Energy is supervised per-atom (meV/atom convention); all reductions are
mask-aware so padding never contributes.

Precision (DESIGN.md §4): predictions and targets are upcast to f32
BEFORE the Huber/error terms, and ``_masked_mean`` reduces in f32 — so
the loss value and every reported MAE metric are comparable across
precision policies, and the long masked sums over padded capacities
never accumulate in bf16 (where the many padded-slot zeros plus rounding
would dominate the mean).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .graph import CrystalGraphBatch


@dataclasses.dataclass(frozen=True)
class LossWeights:
    energy: float = 2.0
    force: float = 1.5
    stress: float = 0.1
    magmom: float = 0.1
    huber_delta: float = 0.1


def huber(x, delta):
    absx = jnp.abs(x)
    quad = 0.5 * x * x
    lin = delta * (absx - 0.5 * delta)
    return jnp.where(absx <= delta, quad, lin)


def _masked_mean(x, mask):
    # f32-pinned reduction: metrics stay comparable across precision
    # policies (DESIGN.md §4)
    x = x.astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    return jnp.sum(x * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _f32(x):
    return x.astype(jnp.float32)


def _error_terms(pred: dict, graph: CrystalGraphBatch):
    """Masked f32 error terms shared by the mean- and sum-reduced losses."""
    n = jnp.maximum(_f32(graph.n_atoms_per_crystal), 1.0)
    # upcast BEFORE the error terms so Huber's quadratic/linear branch
    # decision and the MAEs are taken in f32 for every policy
    e_err = (_f32(pred["energy"]) - _f32(graph.energy)) / n  # eV/atom
    f_err = _f32(pred["forces"]) - _f32(graph.forces)
    s_err = _f32(pred["stress"]) - _f32(graph.stress)
    m_err = _f32(pred["magmom"]) - _f32(graph.magmoms)

    cmask = graph.crystal_mask
    amask = graph.atom_mask
    fmask = amask[..., None] * jnp.ones_like(f_err)
    smask = cmask[:, None, None] * jnp.ones_like(s_err)
    return (e_err, cmask), (f_err, fmask), (s_err, smask), (m_err, amask)


@jax.named_scope("loss")
def chgnet_loss(pred: dict, graph: CrystalGraphBatch, w: LossWeights):
    """Returns (scalar loss, metrics dict with per-target MAEs), under the
    device scope ``loss``."""
    (e_err, cmask), (f_err, fmask), (s_err, smask), (m_err, amask) = \
        _error_terms(pred, graph)

    l_e = _masked_mean(huber(e_err, w.huber_delta), cmask)
    l_f = _masked_mean(huber(f_err, w.huber_delta), fmask)
    l_s = _masked_mean(huber(s_err, w.huber_delta), smask)
    l_m = _masked_mean(huber(m_err, w.huber_delta), amask)
    loss = w.energy * l_e + w.force * l_f + w.stress * l_s + w.magmom * l_m

    metrics = {
        "loss": loss,
        "mae_e_per_atom": _masked_mean(jnp.abs(e_err), cmask),
        "mae_f": _masked_mean(jnp.abs(f_err), fmask),
        "mae_s": _masked_mean(jnp.abs(s_err), smask),
        "mae_m": _masked_mean(jnp.abs(m_err), amask),
    }
    return loss, metrics


# ---------------------------------------------------------------------------
# Global-denominator reduction for gradient accumulation (DESIGN.md §6)
# ---------------------------------------------------------------------------

def global_denominators(num_crystals: int, num_atoms: int) -> dict:
    """Loss denominators of a *global* batch with the given real counts.

    Matches ``_masked_mean``'s per-term mask totals exactly: crystals for
    energy, 3*atoms for forces, 9*crystals for stress, atoms for magmoms
    (each clamped to >= 1, like ``_masked_mean``).  Passed unchanged to
    every microbatch of one optimizer step, so the per-microbatch losses
    of :func:`chgnet_loss_sums` SUM to the single-big-batch
    :func:`chgnet_loss` — and therefore so do their gradients.
    """
    c = float(max(num_crystals, 1))
    a = float(max(num_atoms, 1))
    return {
        "energy": np.float32(c),
        "force": np.float32(3.0 * a),
        "stress": np.float32(9.0 * c),
        "magmom": np.float32(a),
    }


@jax.named_scope("loss")
def chgnet_loss_sums(pred: dict, graph: CrystalGraphBatch, w: LossWeights,
                     denoms: dict):
    """Partial loss of one microbatch against GLOBAL denominators.

    Returns ``(loss, sums)``: ``loss`` is this microbatch's masked Huber
    sums divided by the step-wide ``denoms`` (see
    :func:`global_denominators`), so losses — and gradients — are exactly
    additive across the microbatches of one optimizer step regardless of
    how unevenly the balancer split it.  ``sums`` carries the unweighted
    absolute-error sums (plus the loss itself) for metric aggregation via
    :func:`metrics_from_sums`.  An all-padding shard (a device idled by
    an uneven bucket group) contributes exactly zero to both.
    """
    (e_err, cmask), (f_err, fmask), (s_err, smask), (m_err, amask) = \
        _error_terms(pred, graph)

    def msum(x, mask):
        return jnp.sum(x.astype(jnp.float32) * mask.astype(jnp.float32))

    loss = (
        w.energy * msum(huber(e_err, w.huber_delta), cmask) / denoms["energy"]
        + w.force * msum(huber(f_err, w.huber_delta), fmask) / denoms["force"]
        + w.stress * msum(huber(s_err, w.huber_delta), smask) / denoms["stress"]
        + w.magmom * msum(huber(m_err, w.huber_delta), amask) / denoms["magmom"]
    )
    sums = {
        "loss": loss,
        "abs_e": msum(jnp.abs(e_err), cmask),
        "abs_f": msum(jnp.abs(f_err), fmask),
        "abs_s": msum(jnp.abs(s_err), smask),
        "abs_m": msum(jnp.abs(m_err), amask),
    }
    return loss, sums


def metrics_from_sums(sums: dict, denoms: dict) -> dict:
    """Accumulated microbatch sums -> the ``chgnet_loss`` metrics dict."""
    return {
        "loss": sums["loss"],
        "mae_e_per_atom": sums["abs_e"] / denoms["energy"],
        "mae_f": sums["abs_f"] / denoms["force"],
        "mae_s": sums["abs_s"] / denoms["stress"],
        "mae_m": sums["abs_m"] / denoms["magmom"],
    }
