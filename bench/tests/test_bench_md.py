"""The MD driver on the CPU at small widths: a sound run is correct; the
lower-precision control and each fault the MD cells can have are not."""
import pytest

from harness_util import MD_TRAFFIC, drive, small_cell

SEED = 2**33 + 7
CASES = [(w, mode, correct)
         for w in ("fs_md_64rep", "ad_md_64rep")
         for mode, correct in (("sound", True), ("control", False),
                               ("altered_force", False), ("unchanged", False))]


@pytest.mark.parametrize("workload,mode,correct", CASES)
def test_md_check(monkeypatch, workload, mode, correct):
    out = drive(small_cell(monkeypatch, workload, MD_TRAFFIC), seed=SEED,
                mode=mode)
    assert out["correct"] is correct, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["compiles_in_window"] == 0


@pytest.mark.parametrize("workload", ["fs_md_64rep", "ad_md_64rep"])
def test_control_separates_from_sound(monkeypatch, workload):
    """On the same seed the control's force and energy gaps read at least
    three times the sound program's."""
    cell = small_cell(monkeypatch, workload, MD_TRAFFIC)
    sound = drive(cell, seed=SEED)["checks"]
    control = drive(cell, seed=SEED, mode="control")["checks"]
    for number in ("force_gap", "energy_gap"):
        assert control[number][0] >= 3 * sound[number][0], (sound, control)
