"""Runs the training driver's four-chip data-parallel path on four virtual
CPU devices at small widths and prints, per mode, whether the check read
it correct.  The traffic is the four-chip mix ``train_b2048_4chip`` (its
cost balance and accumulation) at a small batch, under the one-chip
training cell's configuration and limits.  ``test_bench_dp.py`` starts it
in a process of its own, because the device count is fixed when JAX
starts.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python bench/tests/dp_modes.py sound no_exchange ...
"""
import json
import sys

from harness_util import BENCH_DIR, drive, small_cell
from benchlib import cells

CHIPS = 4


class _Patch:
    def setattr(self, obj, name, value, raising=True):
        setattr(obj, name, value)


if __name__ == "__main__":
    traffic = cells._json(BENCH_DIR, "traffic", "train_b2048_4chip.json")
    # three steps an epoch, as at full size
    traffic.update(crystals=48, batch=16, reference_block=8)
    cell = small_cell(_Patch(), "fs_train_b128", traffic)
    cell.chips = CHIPS
    for mode in sys.argv[1:]:
        out = drive(cell, seed=2**33 + 13, mode=mode)
        print(json.dumps({"mode": mode, "correct": out["correct"],
                          "steps": out["attempted"]}), flush=True)
