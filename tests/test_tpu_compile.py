"""Compile rehearsal of the Pallas megakernels for a TPU v5e (no chip needed).

Interpret mode (every other kernel test) accepts tilings, slices and VMEM
use that Mosaic refuses, so these tests AOT-compile each megakernel for a
*described* v5e chip at CHGNet's published width (dim 64):

  - at the paper's batch-128 capacities, in the residency tier that
    ``table_residency="auto"`` selects there (``hbm``);
  - at the largest batch whose tables ``auto`` still keeps VMEM-resident,
    in the ``vmem`` tier.

Each compiled program must contain the Mosaic kernel (``tpu_custom_call``).
The topology is described inside a module fixture — never at import — so
pytest-xdist workers all collect the same tests and only the worker running
this file loads the TPU compiler.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.batching import capacity_for
from repro.data import SyntheticConfig, make_dataset
from repro.kernels import ops

DIM = 64
BATCH = 128  # configs/chgnet_mptrj.py BATCH_SIZE
CRYSTALS = 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def rungs():
    """Batch capacities keyed by rung name: ``w1`` is the launcher's
    batch-128 capacity; ``vmem`` is the largest power-of-two batch whose
    operand tables ``auto`` keeps VMEM-resident."""
    ds = make_dataset(SyntheticConfig(num_crystals=CRYSTALS, seed=0))
    w1 = capacity_for(ds, BATCH)
    vmem = None
    b = 1
    while b <= BATCH:
        caps = capacity_for(ds, b)
        if ops.estimate_table_bytes(caps.atoms, caps.bonds, caps.angles,
                                    DIM) > ops.vmem_budget_bytes():
            break
        vmem, b = (caps, b), 2 * b
    assert vmem is not None, "no batch fits the vmem tier"
    assert ops.estimate_table_bytes(w1.atoms, w1.bonds, w1.angles, DIM) \
        > ops.vmem_budget_bytes(), "batch 128 should stream from HBM"
    return {"w1": (w1, BATCH), "vmem": vmem}


@pytest.fixture
def compiled_text(monkeypatch, one_chip):
    """Compile ``fn`` for the described chip with Mosaic kernels (not the
    interpreter) and return the compiled program's text."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    return compile_


def _f(*shape):
    return shape, jnp.float32


def _i(*shape):
    return shape, jnp.int32


def _gated(d_in):
    return [_f(d_in, 2 * DIM), _f(2 * DIM), _f(2 * DIM), _f(2 * DIM)]


# (rung, residency): batch 128 in the tier auto resolves to, and the
# largest vmem rung in the vmem tier
CASES = [("w1", "auto"), ("vmem", "vmem")]


@pytest.mark.parametrize("rung,residency", CASES)
@pytest.mark.parametrize("store", ["directed", "undirected", "sym"])
def test_atom_conv_compiles(compiled_text, rungs, rung, residency, store):
    caps, _ = rungs[rung]
    a, e, eu = caps.atoms, caps.bonds, caps.und_cap
    rows = e if store == "directed" else eu
    e_rows = eu if store == "sym" else e

    def fn(v, ee, ea, w, b, s, o, ctr, nbr, offs, pair):
        return ops.fused_atom_conv(
            v, ee, ea, w, b, s, o, ctr, nbr, offs,
            pair=None if store == "directed" else pair,
            und_features=store == "sym", table_residency=residency)

    text = compiled_text(fn, _f(a, DIM), _f(e_rows, DIM), _f(rows, DIM),
                         *_gated(3 * DIM), _i(e), _i(e), _i(a + 1), _i(e))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rung,residency", CASES)
@pytest.mark.parametrize("store", ["directed", "undirected"])
def test_bond_conv_compiles(compiled_text, rungs, rung, residency, store):
    caps, _ = rungs[rung]
    a, e, ang = caps.atoms, caps.bonds, caps.angles
    eb_rows = e if store == "directed" else caps.und_cap

    def fn(v, ee, aa, eb, w, b, s, o, ij, ik, ctr, offs, pair):
        return ops.fused_bond_conv(
            v, ee, aa, eb, w, b, s, o, ij, ik, ctr, offs,
            pair=None if store == "directed" else pair,
            table_residency=residency)

    text = compiled_text(fn, _f(a, DIM), _f(e, DIM), _f(ang, DIM),
                         _f(eb_rows, DIM), *_gated(4 * DIM), _i(ang),
                         _i(ang), _i(ang), _i(e + 1), _i(e))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rung,residency", CASES)
def test_sym_msg_accum_compile(compiled_text, rungs, rung, residency):
    caps, _ = rungs[rung]
    a, eu, au = caps.atoms, caps.und_cap, caps.und_angle_cap
    ic = caps.angles  # two incidences per dedup angle

    def fn(v, ee, a_u, eb, w, b, s, o, ctr, du1, du2, rep, dest, offs):
        return ops.fused_sym_bond_conv(
            v, ee, a_u, eb, w, b, s, o, ctr, du1, du2, rep, dest, offs,
            table_residency=residency)

    text = compiled_text(fn, _f(a, DIM), _f(eu, DIM), _f(au, DIM),
                         _f(eu, DIM), *_gated(4 * DIM), _i(au), _i(au),
                         _i(au), _i(ic), _i(ic), _i(eu + 1))
    # phase A (messages) and phase B (accumulation) are two launches
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("rung,residency", CASES)
@pytest.mark.parametrize("virial", [False, True])
def test_force_readout_compiles(compiled_text, rungs, rung, residency,
                                virial):
    caps, n_cry = rungs[rung]
    a, e = caps.atoms, caps.bonds

    def fn(ee, xh, dist, w1, b1, w2, b2, ctr, cry, offs):
        if virial:
            return ops.fused_force_virial_readout(
                ee, xh, dist, w1, b1, w2, b2, ctr, cry, offs, a, n_cry,
                table_residency=residency)
        return ops.fused_force_readout(ee, xh, w1, b1, w2, b2, ctr, offs, a,
                                       table_residency=residency)

    text = compiled_text(fn, _f(e, DIM), _f(e, 3), _f(e), _f(DIM, DIM),
                         _f(DIM), _f(DIM, 1), _f(1), _i(e), _i(e),
                         _i(a + 1))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rung,residency", CASES)
def test_segment_sum_compiles(compiled_text, rungs, rung, residency):
    caps, _ = rungs[rung]
    a, e = caps.atoms, caps.bonds

    def fn(vals, seg, offs):
        return ops.fused_segment_sum(vals, seg, offs, a,
                                     table_residency=residency)

    text = compiled_text(fn, _f(e, DIM), _i(e), _i(a + 1))
    assert "tpu_custom_call" in text


def test_rungs_straddle_the_vmem_budget(rungs):
    """The two rungs really exercise both tiers: batch 128 is over the
    auto budget, the vmem rung under it, and their capacities grow with
    the batch."""
    (w1, b1), (small, b_small) = rungs["w1"], rungs["vmem"]
    assert b_small < b1
    assert small.bonds < w1.bonds and small.angles < w1.angles
    assert np.all(np.array([w1.atoms, w1.bonds, w1.angles]) % 256 == 0)
