"""The benchmark's own traffic: synthetic crystals, their labels, MD replicas.

Copied from the program's generators (``data/synthetic.py`` and
``examples/serve_md.py``) so that no later change to the program moves the
yardstick.  Nothing here imports the program: structures are plain numpy
(:class:`Structure`), and the neighbor search is the benchmark's own
brute-force image scan (:func:`neighbors`), which the labels and the
plain reference both use.

The graph semantics are CHGNet's: a directed bond (i, j, image) for every
pair with 0 < |r_j + image @ L - r_i| <= r_cut_atom, and an angle for every
ordered pair of distinct short bonds (<= r_cut_bond) sharing a center.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# Morse parameters (eV, 1/A, A) of the label potential
_DE, _A, _R0 = 0.5, 1.3, 2.6
EV_A3_TO_GPA = 160.21766


@dataclasses.dataclass
class Structure:
    lattice: np.ndarray          # (3, 3) rows are lattice vectors, A
    frac_coords: np.ndarray      # (N, 3) in [0, 1)
    atomic_numbers: np.ndarray   # (N,) int
    energy: float | None = None  # eV
    forces: np.ndarray | None = None   # (N, 3) eV/A
    stress: np.ndarray | None = None   # (3, 3) GPa
    magmoms: np.ndarray | None = None  # (N,)

    @property
    def num_atoms(self) -> int:
        return int(self.frac_coords.shape[0])


@dataclasses.dataclass
class Graph:
    """Directed bonds and angles of one structure (benchmark-side)."""

    center: np.ndarray   # (E,) int
    nbr: np.ndarray      # (E,) int
    image: np.ndarray    # (E, 3) int
    dist: np.ndarray     # (E,) float64
    angle_ij: np.ndarray  # (A,) int, bond index
    angle_ik: np.ndarray  # (A,) int, bond index

    @property
    def num_bonds(self) -> int:
        return int(self.center.shape[0])

    @property
    def num_angles(self) -> int:
        return int(self.angle_ij.shape[0])


def neighbors(lattice: np.ndarray, frac: np.ndarray, r_cut: float,
              r_cut_bond: float) -> Graph:
    """All directed bonds within ``r_cut`` over periodic images, and the
    angles between short bonds, by a plain scan of every image."""
    lat = np.asarray(lattice, np.float64)
    frac = np.asarray(frac, np.float64)
    cart = frac @ lat
    # images needed per axis: r_cut over the spacing of lattice planes
    spacing = 1.0 / np.linalg.norm(np.linalg.inv(lat), axis=0)
    reach = np.ceil(r_cut / spacing).astype(int)
    grid = np.stack(np.meshgrid(*[np.arange(-m, m + 1) for m in reach],
                                indexing="ij"), -1).reshape(-1, 3)
    vec = (cart[None, :, None, :] + (grid @ lat)[None, None]
           - cart[:, None, None, :])
    dist = np.sqrt(np.sum(vec * vec, axis=-1))
    ci, nj, mi = np.nonzero((dist <= r_cut) & (dist > 1e-8))
    d = dist[ci, nj, mi]
    short = np.nonzero(d <= r_cut_bond)[0]
    ij, ik = [], []
    for c in np.unique(ci[short]):
        grp = short[ci[short] == c]
        a, b = np.meshgrid(grp, grp, indexing="ij")
        off = a != b
        ij.append(a[off])
        ik.append(b[off])
    cat = (lambda xs: np.concatenate(xs) if xs else np.zeros(0, np.int64))
    return Graph(center=ci, nbr=nj, image=grid[mi], dist=d,
                 angle_ij=cat(ij), angle_ik=cat(ik))


# ---------------------------------------------------------------------------
# Training traffic: the synthetic MPtrj-like set of data/synthetic.py
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SyntheticSpec:
    num_crystals: int = 256
    min_atoms: int = 2
    max_atoms: int = 64
    lognormal_mu: float = 2.2
    lognormal_sigma: float = 0.7
    vol_per_atom: float = 14.0
    num_elements: int = 89
    r_cut_atom: float = 6.0
    r_cut_bond: float = 3.0


def _morse(r):
    e = np.exp(-_A * (r - _R0))
    return _DE * (e * e - 2.0 * e)


def _morse_dr(r):
    e = np.exp(-_A * (r - _R0))
    return _DE * (-2.0 * _A * e * e + 2.0 * _A * e)


def _generate(rng: np.random.Generator, spec: SyntheticSpec) -> Structure:
    n = int(np.clip(rng.lognormal(spec.lognormal_mu, spec.lognormal_sigma),
                    spec.min_atoms, spec.max_atoms))
    a = (n * spec.vol_per_atom) ** (1.0 / 3.0)
    lat = np.eye(3) * a + rng.normal(0.0, 0.03 * a, (3, 3))
    frac = rng.random((n, 3))
    z = rng.integers(1, spec.num_elements + 1, n)
    return Structure(lattice=lat, frac_coords=frac, atomic_numbers=z)


def _label(s: Structure, g: Graph, offsets: np.ndarray,
           magmom_w: np.ndarray) -> None:
    """Exact energy, forces, virial stress and magmoms of the Morse pair
    potential plus element offsets (as ``data/synthetic.py`` labels)."""
    lat = s.lattice
    cart = s.frac_coords @ lat
    vec = cart[g.nbr] + g.image.astype(np.float64) @ lat - cart[g.center]
    dist = np.linalg.norm(vec, axis=-1)
    n = s.num_atoms
    s.energy = float(0.5 * np.sum(_morse(dist))
                     + np.sum(offsets[s.atomic_numbers]))
    dphi = _morse_dr(dist)
    f = np.zeros((n, 3))
    np.add.at(f, g.center, dphi[:, None] * vec / dist[:, None])
    s.forces = f
    vol = abs(np.linalg.det(lat))
    outer = vec[:, :, None] * vec[:, None, :]
    s.stress = (0.5 * np.sum((dphi / dist)[:, None, None] * outer, axis=0)
                / vol * EV_A3_TO_GPA)
    rho = np.zeros(n)
    np.add.at(rho, g.center, np.exp(-dist))
    s.magmoms = np.log1p(np.exp(rho)) * magmom_w[s.atomic_numbers]


def synthetic_set(spec: SyntheticSpec, data_seed: int,
                  label_seed: int) -> tuple[list[Structure], list[Graph]]:
    """``spec.num_crystals`` labelled structures.

    The structures (sizes and geometry) come from ``data_seed``, in the
    same draw order as ``data/synthetic.py``; the label constants (element
    energy offsets, magmom weights) from ``label_seed``.  So every label
    seed gives the same sizes, and the same padded work, with other labels.
    """
    rng = np.random.default_rng(data_seed)
    rng.normal(-3.0, 1.0, spec.num_elements + 1)       # keep the draw order
    np.abs(rng.normal(0.5, 0.3, spec.num_elements + 1))
    structures = [_generate(rng, spec) for _ in range(spec.num_crystals)]
    lrng = np.random.default_rng(label_seed)
    offsets = lrng.normal(-3.0, 1.0, spec.num_elements + 1)
    magmom_w = np.abs(lrng.normal(0.5, 0.3, spec.num_elements + 1))
    graphs = []
    for s in structures:
        g = neighbors(s.lattice, s.frac_coords, spec.r_cut_atom,
                      spec.r_cut_bond)
        _label(s, g, offsets, magmom_w)
        graphs.append(g)
    return structures, graphs


# ---------------------------------------------------------------------------
# MD traffic: the replicas of examples/serve_md.py
# ---------------------------------------------------------------------------

def md_replica(num_atoms: int, seed: int) -> Structure:
    """Cubic cell at 14 A^3 per atom, uniform positions, Z in [1, 60)."""
    rng = np.random.default_rng(seed)
    a = (num_atoms * 14.0) ** (1 / 3)
    return Structure(lattice=np.eye(3) * a,
                     frac_coords=rng.random((num_atoms, 3)),
                     atomic_numbers=rng.integers(1, 60, num_atoms))


def md_replica_sizes(count: int, smallest: int, largest: int) -> np.ndarray:
    """Evenly spaced replica sizes, as ``chip_smoke.py`` builds them."""
    return np.linspace(smallest, largest, count).round().astype(int)
