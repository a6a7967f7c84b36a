"""The reduction from trace events to busy time, idle gaps and exposed
collective time, checked against a plain timeline sweep: on a trace
recorded on the chip (``bench/data``) and on a small hand-made one."""
import glob
import os

import numpy as np
import pytest

from harness_util import BENCH_DIR
from benchlib import cells, trace

RECORDED = sorted(glob.glob(os.path.join(BENCH_DIR, "data", "*.json.gz")))


def _sweep(events, steps):
    """Busy, idle-by-span and exposed-collective time on a 1-us grid."""
    w = next(h for h in events["host"] if h[2] == trace.WINDOW_SPAN)
    t0 = w[0]
    n = int(np.ceil(w[1] / 1e3))
    busy, exposed, idle = [], [], {}
    for evs in events["devices"].values():
        on = np.zeros(n, bool)
        coll = np.zeros(n, bool)
        other = np.zeros(n, bool)
        for s, d, name in evs:
            lo = max(int(np.floor((s - t0) / 1e3)), 0)
            hi = min(int(np.ceil((s + d - t0) / 1e3)), n)
            if hi <= lo:
                continue
            on[lo:hi] = True
            (coll if trace.is_collective(name) else other)[lo:hi] = True
        busy.append(on.sum() * 1e-6)
        exposed.append((coll & ~other).sum() * 1e-6 / steps)
    return {"busy_s": np.mean(busy), "window_s": w[1] * 1e-9,
            "exposed_collective_s_per_step": np.mean(exposed)}


def _check(events, steps):
    got = trace.reduce(events, steps=steps)
    want = _sweep(events, steps)
    n_dev = len(events["devices"])
    # the grid rounds each operation's two ends out to whole microseconds
    n_ops = max(len(v) for v in events["devices"].values())
    slack = 2e-6 * n_ops
    assert got["window_s"] == pytest.approx(want["window_s"], abs=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], abs=slack)
    assert got["exposed_collective_s_per_step"] == pytest.approx(
        want["exposed_collective_s_per_step"], abs=slack)
    assert 0.0 <= got["idle_share"] <= 1.0
    idle = sum(s for _, s in got["idle_gaps"])
    assert idle <= got["window_s"] - got["busy_s"] + 1e-9
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
    assert n_dev >= 1
    return got


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_reduction_on_a_chip_trace(path):
    events = trace.read(path)
    got = _check(events, steps=3)
    assert got["busy_s"] > 0


def _two_devices():
    ms = 1_000_000
    return {
        "host": [[0, 100 * ms, "bench.window"],
                 [0, 10 * ms, "bench.data_next"],
                 [50 * ms, 30 * ms, "bench.md_step"],
                 [55 * ms, 5 * ms, "bench.nlist"]],
        "devices": {
            "/device:TPU:0": [[10 * ms, 30 * ms, "fusion.1"],
                              [30 * ms, 20 * ms, "all-reduce.2"],
                              [90 * ms, 20 * ms, "fusion.3"]],
            "/device:TPU:1": [[10 * ms, 40 * ms, "all-reduce.2"]],
        },
    }


def test_reduction_on_a_hand_made_trace():
    got = _check(_two_devices(), steps=2)
    # device 0 busy 10-50 and 90-100 ms; device 1 busy 10-50 ms
    assert got["busy_s"] == pytest.approx((0.050 + 0.040) / 2)
    # exposed all-reduce: device 0 40-50 ms, device 1 10-50 ms, per step
    assert got["exposed_collective_s_per_step"] == pytest.approx(
        (0.010 + 0.040) / 2 / 2)
    gaps = dict(got["idle_gaps"])
    # device 0 idle 0-10 ms (data_next) and 50-90 ms (md_step); device 1
    # idle 0-10 ms and 50-100 ms, whose middle falls in md_step too
    assert gaps == pytest.approx({"data_next": 0.010,
                                  "md_step": (0.040 + 0.050) / 2})


def test_exposed_collective_reader_on_a_hand_made_trace():
    """``dp_exposed_collective_ms``: the all-reduce's exposed time per
    step, averaged over the two devices, in ms; nothing to read where no
    collective ran."""
    read = cells.reader("dp_exposed_collective_ms")
    events = _two_devices()
    got = read({"trace": trace.reduce(events, steps=2)})
    assert got == pytest.approx(1e3 * (0.010 + 0.040) / 2 / 2)
    for evs in events["devices"].values():
        for ev in evs:
            ev[2] = ev[2].replace("all-reduce", "fusion")
    assert read({"trace": trace.reduce(events, steps=2)}) is None
    assert read({"trace": None}) is None
