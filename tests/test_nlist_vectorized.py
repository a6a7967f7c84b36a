"""Exactness of the loop-free graph assembly (``core/neighbors.py``).

``_build_angles`` is checked against the per-atom loop it replaced, kept
here as the plain reference; its angle dedup maps and the Verlet refilter's
bond mirror maps are checked against ``build_angle_mirror_maps`` and
``build_mirror_maps`` on the same bonds, step by step over a drifting MD
run with boundary wraps and a forced rebuild.  A hand-placed pair whose
two directions fall on opposite sides of 6 A runs the singleton fallback.
"""
import jax
import numpy as np
import pytest

from repro.core import neighbors
from repro.core.chgnet import CHGNetConfig, chgnet_init
from repro.core.neighbors import (
    Crystal,
    VerletNeighborList,
    build_angle_mirror_maps,
    build_graph,
    build_mirror_maps,
)

FIELDS = ("bond_center", "bond_nbr", "bond_image", "angle_ij", "angle_ik",
          "bond_pair", "bond_sign", "und_rep", "angle_pair", "und_angle_rep")


def _loop_build_angles(bond_center, bond_dist, r_cut_bond, n):
    """The per-atom loop ``_build_angles`` replaced (plain reference)."""
    short = np.nonzero(bond_dist <= r_cut_bond)[0]
    angle_ij_list, angle_ik_list = [], []
    if short.size > 0:
        centers_short = bond_center[short]
        order = np.argsort(centers_short, kind="stable")
        short_sorted = short[order]
        centers_sorted = centers_short[order]
        starts = np.searchsorted(centers_sorted, np.arange(n), side="left")
        ends = np.searchsorted(centers_sorted, np.arange(n), side="right")
        for a in range(n):
            grp = short_sorted[starts[a]:ends[a]]
            d = grp.shape[0]
            if d < 2:
                continue
            jj, kk = np.meshgrid(grp, grp, indexing="ij")
            off = ~np.eye(d, dtype=bool)
            angle_ij_list.append(jj[off].ravel())
            angle_ik_list.append(kk[off].ravel())
    if angle_ij_list:
        return (np.concatenate(angle_ij_list).astype(np.int32),
                np.concatenate(angle_ik_list).astype(np.int32))
    return np.zeros((0,), np.int32), np.zeros((0,), np.int32)


def _assert_identical(got, want, what=""):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype, what
        assert np.array_equal(g, w), what


def _check_angles(bond_center, bond_dist, r_cut_bond, n):
    ij, ik, pair, rep = neighbors._build_angles(
        bond_center, bond_dist, r_cut_bond)
    _assert_identical((ij, ik),
                      _loop_build_angles(bond_center, bond_dist,
                                         r_cut_bond, n), "angles")
    _assert_identical((pair, rep), build_angle_mirror_maps(ij, ik),
                      "angle maps")
    return ij.size


def _spy_angles(monkeypatch):
    """Record every ``_build_angles`` call's arguments."""
    calls = []
    real = neighbors._build_angles

    def spy(bond_center, bond_dist, r_cut_bond):
        calls.append((bond_center.copy(), bond_dist.copy(), r_cut_bond))
        return real(bond_center, bond_dist, r_cut_bond)

    monkeypatch.setattr(neighbors, "_build_angles", spy)
    return calls


def _random_crystal(n, seed, density=14.0):
    rng = np.random.default_rng(seed)
    a = (n * density) ** (1 / 3)
    return Crystal(lattice=np.eye(3) * a + rng.normal(0, 0.03 * a, (3, 3)),
                   frac_coords=rng.random((n, 3)),
                   atomic_numbers=rng.integers(1, 60, n))


@pytest.mark.parametrize("n", [1, 2, 5, 17, 64, 200])
def test_angles_match_loop_on_random_crystals(n, monkeypatch):
    calls = _spy_angles(monkeypatch)
    g = build_graph(_random_crystal(n, seed=n))
    (center, dist, r_cut_bond), = calls
    _check_angles(center, dist, r_cut_bond, n)
    _assert_identical((g.angle_pair, g.und_angle_rep),
                      build_angle_mirror_maps(g.angle_ij, g.angle_ik))


@pytest.mark.parametrize("case", ["zero_one_two", "none_short", "no_bonds",
                                  "one_big_group"])
def test_angles_match_loop_on_hand_made_groups(case):
    """Centers with 0, 1 and 2 short bonds, no short bond, no bond."""
    if case == "zero_one_two":
        # atom 0: two short bonds; 1: one short, one long; 2: none short;
        # 3: three short bonds; 4: no bond at all
        center = np.array([0, 0, 1, 1, 2, 3, 3, 3], np.int32)
        dist = np.array([2.0, 2.9, 1.5, 4.0, 5.0, 3.0, 0.9, 2.2])
        n, want = 5, 2 + 6
    elif case == "none_short":
        center = np.array([0, 0, 1, 2, 2], np.int32)
        dist = np.full(5, 4.5)
        n, want = 3, 0
    elif case == "no_bonds":
        center, dist, n, want = np.zeros(0, np.int32), np.zeros(0), 2, 0
    else:
        center = np.repeat(np.arange(3, dtype=np.int32), [1, 9, 1])
        dist = np.linspace(0.5, 2.9, 11)
        n, want = 3, 9 * 8
    assert _check_angles(center, dist, 3.0, n) == want


@pytest.mark.parametrize("cap_mode", ["symmetric", "per_center"])
@pytest.mark.parametrize("cap", [3, 8])
def test_angles_match_loop_on_capped_graphs(cap_mode, cap, monkeypatch):
    calls = _spy_angles(monkeypatch)
    crystal = _random_crystal(24, seed=cap)
    g = build_graph(crystal, max_nbr_per_atom=cap, cap_mode=cap_mode)
    (center, dist, r_cut_bond), = calls
    assert np.array_equal(center, g.bond_center)
    assert _check_angles(center, dist, r_cut_bond, 24) == g.num_angles > 0
    _assert_identical((g.bond_pair, g.bond_sign, g.und_rep),
                      build_mirror_maps(g.bond_center, g.bond_nbr,
                                        g.bond_image))


def _reference_graph(nl, g, n, dist):
    """The refilter's bonds assembled the way ``build_graph`` does: mirror
    maps by ``build_mirror_maps`` (a sort) and angles by the loop."""
    ij, ik = _loop_build_angles(g.bond_center, dist, nl.r_cut_bond, n)
    return (g.bond_center, g.bond_nbr, g.bond_image, ij, ik,
            *build_mirror_maps(g.bond_center, g.bond_nbr, g.bond_image),
            *build_angle_mirror_maps(ij, ik))


def test_verlet_update_equals_reference_over_drifting_md(monkeypatch):
    """Every step of a drifting run (atoms wrapping across the cell's
    faces, one forced rebuild) gives the reference's graph field for
    field, and the same graph as a from-scratch ``build_graph``."""
    rng = np.random.default_rng(7)
    n = 16
    crystal = _random_crystal(n, seed=3)
    crystal.frac_coords[:4, 0] = [0.002, 0.998, 0.001, 0.999]
    nl = VerletNeighborList(crystal, skin=0.6)
    calls = _spy_angles(monkeypatch)
    inv = np.linalg.inv(crystal.lattice)
    wrapped = 0
    for step in range(24):
        g = nl.update(crystal)
        dist = calls[-1][1]
        got = tuple(getattr(g, f) for f in FIELDS)
        _assert_identical(got, _reference_graph(nl, g, n, dist), f"{step}")
        full = build_graph(crystal)
        _assert_identical(got, tuple(getattr(full, f) for f in FIELDS),
                          f"build_graph {step}")
        assert nl.singleton_bonds == 0
        move = rng.normal(0, 0.02, (n, 3))
        move[:4, 0] = [-0.01, 0.01, -0.01, 0.01]  # across the x faces
        if step == 12:
            move[5] += [0.8, 0.0, 0.0]  # past skin / 2: a rebuild
        frac = (crystal.cart_coords() + move) @ inv
        wrapped += int(np.sum((frac < 0) | (frac >= 1)))
        crystal.frac_coords = frac % 1.0
    assert wrapped > 0
    assert nl.rebuilds >= 2 and nl.updates == 24


def test_refilter_measures_with_the_current_lattice(monkeypatch):
    """A lattice strained inside ``needs_rebuild``'s tolerance is no
    rebuild, and the refilter measures with it, not the rebuild's."""
    crystal = _random_crystal(12, seed=5)
    nl = VerletNeighborList(crystal, skin=0.5)
    crystal.lattice = crystal.lattice * (1.0 + 1e-9)
    assert not nl.needs_rebuild(crystal)
    calls = _spy_angles(monkeypatch)
    g = nl.update(crystal)
    assert nl.rebuilds == 1
    cart = crystal.cart_coords()
    vec = (cart[g.bond_nbr] + g.bond_image.astype(np.float64)
           @ crystal.lattice - cart[g.bond_center])
    assert np.array_equal(calls[-1][1], np.linalg.norm(vec, axis=-1))


def _asymmetric_crystal(kept="canonical"):
    """Two atoms a, b whose pair across the x face measures 6.0 from a to
    b and 6.000000000000001 from b to a: one direction survives the 6 A
    cutoff.  ``kept`` says which orientation of the pair that is: a is
    atom 0 when it is the canonical one, atom 1 when it is the mirror."""
    frac = np.array([[0.4205663934229092, 0.5, 0.5],
                     [0.02056639342290926, 0.5, 0.5]])
    if kept == "mirror":
        frac = frac[::-1].copy()
    return Crystal(lattice=np.eye(3) * 10.0, frac_coords=frac,
                   atomic_numbers=np.array([3, 8]))


@pytest.mark.parametrize("kept", ["canonical", "mirror"])
def test_asymmetric_pair_takes_the_singleton_fallback(kept, monkeypatch):
    crystal = _asymmetric_crystal(kept)
    a, b = (0, 1) if kept == "canonical" else (1, 0)
    cart = crystal.cart_coords()
    shift = np.array([1.0, 0.0, 0.0]) @ crystal.lattice
    assert np.linalg.norm((cart[b] + shift) - cart[a]) <= 6.0
    assert np.linalg.norm((cart[a] - shift) - cart[b]) > 6.0
    nl = VerletNeighborList(crystal, skin=0.5)
    calls = _spy_angles(monkeypatch)
    g = nl.update(crystal)
    assert nl.singleton_bonds == 1
    assert g.num_bonds == 3 and g.num_undirected == 2
    lone = np.flatnonzero(g.bond_image[:, 0] == 1)
    assert g.bond_center[lone].tolist() == [a]
    assert g.bond_sign[lone].tolist() == [1.0] and lone in g.und_rep
    got = tuple(getattr(g, f) for f in FIELDS)
    _assert_identical(got, _reference_graph(nl, g, 2, calls[-1][1]))
    full = build_graph(crystal)
    _assert_identical(got, tuple(getattr(full, f) for f in FIELDS))


def test_md_stats_count_singleton_bonds():
    from repro.serve import BatchedMD, ServeEngine

    symmetric = _random_crystal(4, seed=11)
    asymmetric = _asymmetric_crystal("mirror")
    cfg = CHGNetConfig(readout="direct")
    params = chgnet_init(jax.random.PRNGKey(0), cfg)
    serve = ServeEngine.for_structures(params, cfg, [symmetric, asymmetric])
    counts = []
    for crystal in (symmetric, asymmetric):
        md = BatchedMD(serve, [crystal], dt=1e-3)
        md.step(1)
        counts.append(md.stats()["nlist_singleton_bonds"])
    assert counts == [0, 1]
