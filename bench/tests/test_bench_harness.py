"""The harness's files and arithmetic, on the CPU: every cell,
configuration, traffic, limit and metric loads by name; the benchmark
refuses to run without a TPU; the FLOP count and the batch-fill count
match their definitions on a packed batch."""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from harness_util import BENCH_DIR, ROOT, SMALL, benchmark
from benchlib import cells, crystals, flops, peaks

BENCH = benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_by_name(workload):
    cell = cells.load_cell(workload)
    assert cell.driver().run
    assert cell.end_to_end and cell.per_layer
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    for metric in cell.per_layer:
        assert callable(cells.reader(metric["name"]))
    assert cell.limits and all(v >= 0 for v in cell.limits.values())


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_matches_the_program_preset(config):
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import chgnet_mptrj as C

    cfg = cells._json(ROOT, config["file"])
    assert cfg["name"] == config["name"] and config["reduced"] == []
    assert cells.model_config(C, cfg, None) is getattr(C, cfg["preset"])


def test_benchmark_names_and_units():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [x["name"] for x in metrics + BENCH["workloads"] + BENCH["configs"]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    assert all(re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
               for m in metrics)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    workloads = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= workloads
    assert all(0 < m["bound"] <= 0.25 for m in BENCH["end_to_end"])


def test_peak_table_refuses_unknown_kinds():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        peaks.peaks_for("cpu")


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "fs_train_b128", "--seed", str(2**33 + 1), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


@pytest.fixture(scope="module")
def launcher_batches():
    """The launcher's first epoch at batch 128: 256 crystals of the seed-0
    synthetic set, the two-bucket ladder, the load-balanced iterator."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.batching import ladder_for
    from repro.data import BatchIterator

    drv = cells._module(os.path.join(BENCH_DIR, "drivers", "train.py"),
                        "bench_driver_train_test")
    structures, _ = crystals.synthetic_set(
        crystals.SyntheticSpec(num_crystals=256), data_seed=0, label_seed=0)
    ds = drv._program_dataset(structures, 6.0, 3.0)
    it = BatchIterator(ds, 128, 1, ladder_for(ds, 128, num_buckets=2),
                       stack=False, load_balance=True, tag_indices=True)
    tagged = list(it)
    return drv, [t.batch for t in tagged], tagged


def test_batch_fill_is_the_mask_count(launcher_batches):
    drv, batches, tagged = launcher_batches

    class Source:
        def __iter__(self):
            return iter(tagged)

        def close(self):
            pass

    feed = drv.Feed(Source(), annotate=lambda name: _Null())
    feed.next()
    assert feed.bond_fill[0] == (97068, 737152)
    assert feed.rows[0][1] == np.sum(batches[0].bond_mask)
    read = cells.reader("batch_fill.train")
    assert read({"fill.train": feed.bond_fill[0]}) == \
        pytest.approx(100 * 97068 / 737152)


def test_flops_match_the_gemm_arithmetic(launcher_batches):
    _, batches, _ = launcher_batches
    b = batches[0]
    bonds, angles = int(np.sum(b.bond_mask)), int(np.sum(b.angle_mask))
    atoms = int(np.sum(b.atom_mask))
    d = 64
    # benchmarks/bench_iteration.py trunk_gemm_flops, directed features
    expect = (angles * 2 * (4 * d) * (2 * d) + bonds * 2 * d * d
              + angles * 2 * (4 * d) * (2 * d))
    assert flops.conv_gemm_flops(d, bonds, angles) == expect
    cfg = cells._json(BENCH_DIR, "configs", "chgnet_fs.json")
    fwd = flops.forward_flops(cfg, atoms, bonds, angles)
    assert fwd > 3 * expect
    assert flops.train_step_flops(cfg, atoms, bonds, angles) == 3 * fwd
    ad = dict(cfg, readout="autodiff")
    assert flops.serve_step_flops(ad, atoms, bonds, angles) == \
        2 * flops.forward_flops(ad, atoms, bonds, angles)


def _hand_count(readout: str, atoms: int, bonds: int, angles: int) -> float:
    """A training step's GEMM FLOPs at the ``SMALL`` widths (dim 8, 5 + 5
    bases, one block), written out GEMM by GEMM: 2 rows d_in d_out each."""
    energy_path = (2 * bonds * 5 * 24 + 2 * angles * 5 * 8          # embeds
                   + 2 * bonds * 24 * 16 + 2 * atoms * 8 * 8        # block
                   + 2 * angles * 32 * 16 + 2 * bonds * 8 * 8
                   + 2 * angles * 32 * 16
                   + 2 * bonds * 24 * 16 + 2 * atoms * 8 * 8        # final
                   + 2 * (2 * atoms * 8 * 8) + 2 * atoms * 8)       # energy
    magmom = 2 * atoms * 8 * 8 + 2 * atoms * 8
    if readout == "direct":
        heads = (2 * bonds * 8 * 8 + 2 * bonds * 8                  # force
                 + 2 * atoms * 8 * 8 + 2 * atoms * 8 * 9)           # stress
        return 3 * (energy_path + magmom + heads)
    # autodiff: forward, inner backward to the positions and strain, and
    # the outer backward of both on the energy path; the magmom head 3x
    return 6 * energy_path + 3 * magmom


@pytest.mark.parametrize("config", ["chgnet_fs", "chgnet_autodiff"])
def test_train_flops_match_a_hand_count(config):
    cfg = dict(cells._json(BENCH_DIR, "configs", f"{config}.json"), **SMALL)
    rows = (37, 412, 1290)
    assert flops.train_step_flops(cfg, *rows) == _hand_count(
        cfg["readout"], *rows)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
