"""The training driver's four-chip data-parallel path, on four virtual CPU
devices at small widths (in a process of its own: the device count is
fixed when JAX starts)."""
import json
import os
import subprocess
import sys

from harness_util import BENCH_DIR, ROOT


def test_data_parallel_path_on_four_devices():
    """The four-chip path (cost-balanced accumulation over a mesh): sound
    is correct; each fault a data-parallel cell can have is not."""
    modes = {"sound": True, "no_exchange": False, "half_batch": False,
             "unchanged": False}
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "tests", "dp_modes.py"),
         *modes], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = {r["mode"]: r["correct"] for r in map(json.loads,
                                                 proc.stdout.splitlines())}
    assert got == modes
