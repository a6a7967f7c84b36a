"""The training driver on the CPU at small widths: a sound run is correct;
the lower-precision control and each fault a one-chip training cell can
have are not.  The same holds for the autodiff readout's second-order
step."""
import pytest

from harness_util import TRAIN_TRAFFIC, drive, small_cell


@pytest.mark.parametrize("mode,correct", [
    ("sound", True),
    ("control", False),
    ("half_batch", False),
    ("unchanged", False),
])
def test_train_check(monkeypatch, mode, correct):
    out = drive(small_cell(monkeypatch, "fs_train_b128", TRAIN_TRAFFIC),
                seed=2**33 + 11, mode=mode)
    assert out["correct"] is correct, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compiles_in_window"] == 0


def test_train_window_holds_whole_epochs(monkeypatch):
    out = drive(small_cell(monkeypatch, "fs_train_b128", TRAIN_TRAFFIC),
                seed=3, seconds=0.5)
    epoch = TRAIN_TRAFFIC["crystals"] // TRAIN_TRAFFIC["batch"]
    assert out["attempted"] % epoch == 0 and out["attempted"] >= epoch



@pytest.mark.parametrize("mode,correct", [
    ("sound", True),
    ("half_batch", False),
    ("unchanged", False),
])
def test_autodiff_train_check(monkeypatch, mode, correct):
    """The driver runs a configuration with ``"readout": "autodiff"``
    (forces = -dE/dx, stress = dE/de, trained through second-order
    derivatives) to a result, under the one-chip training cell's limits.
    Its control is left out: at these widths it reads correct."""
    cell = small_cell(monkeypatch, "fs_train_b128", TRAIN_TRAFFIC,
                      config="chgnet_autodiff")
    assert cell.config["readout"] == "autodiff"
    out = drive(cell, seed=2**33 + 17, mode=mode)
    assert out["correct"] is correct, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compiles_in_window"] == 0
    assert out["readings"]["flops.train"] > 0
