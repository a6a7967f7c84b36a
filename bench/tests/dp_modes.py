"""Runs the training driver's four-chip data-parallel path on four virtual
CPU devices at small widths and prints, per mode, whether the check read
it correct.  ``test_bench_train.py`` starts it in a process of its own,
because the device count is fixed when JAX starts.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python bench/tests/dp_modes.py sound no_exchange ...
"""
import json
import sys

from harness_util import TRAIN_TRAFFIC, drive, small_cell

DP_TRAFFIC = dict(TRAIN_TRAFFIC, crystals=48, batch=16, balance="cost",
                  accum=2, reference_block=8)


class _Patch:
    def setattr(self, obj, name, value, raising=True):
        setattr(obj, name, value)


if __name__ == "__main__":
    cell = small_cell(_Patch(), "fs_train_b128", DP_TRAFFIC)
    cell.chips = 4
    for mode in sys.argv[1:]:
        out = drive(cell, seed=2**33 + 13, mode=mode)
        print(json.dumps({"mode": mode, "correct": out["correct"],
                          "steps": out["attempted"]}), flush=True)
