"""The program's own measurement: host spans on the profiler's clock (one
MD step, one training step), the packing counters kept without reading
the device, and the device scopes in the compiled train step."""
import glob
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.batching.pack as pack_mod
from repro.batching import (
    BatchCapacities,
    BatchingEngine,
    CapacityLadder,
    CompileCache,
)
from repro.configs import chgnet_mptrj as C
from repro.core.chgnet import chgnet_init
from repro.core.neighbors import Crystal, build_graph
from repro.data import (
    BatchIterator,
    Prefetcher,
    SyntheticConfig,
    capacity_for,
    make_dataset,
)
from repro.serve import BatchedMD, ServeEngine
from repro.train import TrainConfig, Trainer
from repro.train.trainer import make_chgnet_step_fns

SMALL = C.FAST_FS_HEAD.with_(dim=8, num_rbf=5, num_fourier=5, num_blocks=1)


def _crystal(n, seed):
    rng = np.random.default_rng(seed)
    a = (n * 14.0) ** (1 / 3)
    return Crystal(lattice=np.eye(3) * a, frac_coords=rng.random((n, 3)),
                   atomic_numbers=rng.integers(1, 60, n))


def _spans(logdir):
    """The trace's ``repro.*`` host spans: ``(start, end, name, thread,
    step_num)``."""
    from jax.profiler import ProfileData

    path = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    step = dict(ev.stats).get("step_num")
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, (plane.name, i), step))
    return out


def _named(spans, name):
    return [s for s in spans if s[2] == name]


def _inside(child, parent):
    return (child[3] == parent[3] and parent[0] <= child[0]
            and child[1] <= parent[1])


def test_md_step_emits_its_spans_nested_in_the_step(tmp_path):
    cs = [_crystal(n, i) for i, n in enumerate((6, 8, 10))]
    params = chgnet_init(jax.random.PRNGKey(0), SMALL)
    md = BatchedMD(ServeEngine.for_structures(params, SMALL, cs), cs,
                   max_group=2)
    md.step(1)  # compiles every group's step outside the trace
    # move one replica past skin / 2 so its neighbor list rebuilds
    md.replicas[0].crystal.frac_coords = (
        md.replicas[0].crystal.frac_coords + 0.1) % 1.0
    with jax.profiler.trace(str(tmp_path)):
        md.step(1)
    spans = _spans(tmp_path)
    (step,) = _named(spans, "repro.md.step")
    assert step[4] == 1  # steps_done before this step
    for name in ("repro.md.nlist", "repro.md.collect", "repro.md.integrate"):
        (one,) = _named(spans, name)
        assert _inside(one, step), name
    (nlist,) = _named(spans, "repro.md.nlist")
    rebuilds = _named(spans, "repro.nlist.rebuild")
    assert rebuilds and all(_inside(r, nlist) for r in rebuilds)
    packs = _named(spans, "repro.md.pack")
    dispatches = _named(spans, "repro.md.dispatch")
    groups = md.stats()["batches_packed"] // 2
    assert len(packs) == len(dispatches) == groups >= 2
    assert all(_inside(s, step) for s in packs + dispatches)
    h2d = _named(spans, "repro.pack.h2d")
    assert len(h2d) == groups
    assert all(any(_inside(u, p) for p in packs) for u in h2d)


def test_train_step_emits_its_spans_and_the_data_wait(tmp_path):
    ds = make_dataset(SyntheticConfig(num_crystals=8, max_atoms=8, seed=0))
    batches = list(BatchIterator(ds, 4, 1, capacity_for(ds, 4)))
    tr = Trainer(SMALL, TrainConfig(global_batch=4, total_steps=10),
                 ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=1)
    tr.train(batches[:1])  # compiles outside the trace
    with jax.profiler.trace(str(tmp_path / "trace")):
        tr.train(Prefetcher(iter(batches[1:2])))
    spans = _spans(tmp_path / "trace")
    (step,) = _named(spans, "repro.train.step")
    assert step[4] == 1
    for name in ("repro.train.dispatch", "repro.train.loss_read",
                 "repro.train.ckpt"):
        (one,) = _named(spans, name)
        assert _inside(one, step), name
    waits = _named(spans, "repro.data.wait")
    assert waits and all(w[3] == step[3] for w in waits)
    assert not any(_inside(w, step) for w in waits)
    produce = _named(spans, "repro.data.produce")
    # the prefetch worker's thread: the batch, then the end of its source
    assert len(produce) == 2 and all(p[3] != step[3] for p in produce)


class _Sealed:
    """A packed device array that the host may not read."""

    def __init__(self, arr):
        self.arr = arr

    def _read(self, *a, **k):
        raise AssertionError("the host read a packed device array")

    __array__ = __float__ = __int__ = __bool__ = __len__ = _read
    __iter__ = __getitem__ = sum = item = tolist = _read


def test_pack_counts_without_reading_the_device(monkeypatch):
    """The counters come from the graphs on the host: the pack path runs
    under the device-to-host transfer guard (enforced where the device is
    not the host) with every uploaded array sealed against reads."""
    cs = [_crystal(n, i) for i, n in enumerate((4, 6, 9))]
    gs = [build_graph(c, 6.0, 3.0) for c in cs]
    lad = CapacityLadder(buckets=(BatchCapacities(32, 4096, 16384),))
    eng = BatchingEngine(lad, CompileCache())
    monkeypatch.setattr(pack_mod, "jnp", types.SimpleNamespace(
        asarray=lambda x: _Sealed(jnp.asarray(x))))
    with jax.transfer_guard_device_to_host("disallow"):
        b1, _ = eng.pack(cs[:2], gs[:2])
        b2, _ = eng.pack(cs[2:], gs[2:])
    monkeypatch.undo()
    masks = {"atoms": "atom_mask", "bonds": "bond_mask",
             "angles": "angle_mask"}
    for key, field in masks.items():
        m1, m2 = (np.asarray(getattr(b, field).arr) for b in (b1, b2))
        assert eng.packed[key] == int(m1.sum() + m2.sum())
        assert eng.capacity[key] == m1.size + m2.size
    waste = [1.0 - sum(float(np.sum(getattr(b, f).arr))
                       for f in masks.values())
             / sum(getattr(b, f).arr.size for f in masks.values())
             for b in (b1, b2)]
    assert eng.mean_padding_waste == pytest.approx(np.mean(waste),
                                                   abs=1e-12)
    assert eng.stats()["packed"] == eng.packed


def test_compiled_train_step_names_the_program_scopes():
    # two blocks: the last block's angle update feeds nothing, and XLA
    # drops it
    cfg = SMALL.with_(num_blocks=2)
    ds = make_dataset(SyntheticConfig(num_crystals=4, max_atoms=8, seed=0))
    batch = next(iter(BatchIterator(ds, 4, 1, capacity_for(ds, 4))))
    tcfg = TrainConfig(global_batch=4, total_steps=10)
    train_step, _, _ = make_chgnet_step_fns(cfg, tcfg, donate=False)
    tr = Trainer(cfg, tcfg)
    text = train_step.lower(tr.params, tr.opt_state, batch,
                            jnp.asarray(0)).compile().as_text()
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    for scope in ("jvp(basis)", "jvp(embed)", "jvp(block0)/atom_conv",
                  "jvp(block0)/bond_conv", "jvp(block0)/angle_update",
                  "jvp(final_block)/atom_conv", "jvp(readout)",
                  "jvp(loss)", "transpose(jvp(block0))",
                  "transpose(jvp(readout))", "/optimizer/"):
        assert scope in names, scope
