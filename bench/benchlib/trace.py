"""From a profiler trace to device busy time, idle gaps and collectives.

Two steps, kept apart so that the second can be checked on a recorded
trace without a chip:

  1. :func:`load` reads the ``.xplane.pb`` that ``jax.profiler.trace``
     wrote and keeps, per device, the operations of its "XLA Ops" line, and
     the host spans whose names start with ``bench.`` (the harness's own
     ``TraceAnnotation`` spans).  Times are nanoseconds on the profiler's
     common clock.
  2. :func:`reduce` clips everything to the window span, takes the union
     of each device's operation intervals (busy), the gaps between them
     (idle, each named by the innermost harness span open at its middle),
     and the part of each collective during which no other operation ran
     on that device (exposed collective time).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# XLA's names for cross-device collectives, as they appear in op names
COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(m in low for m in COLLECTIVE_MARKS)


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str) -> dict:
    """Events of one trace: ``{"devices": {plane: [[start, dur, name]]},
    "host": [[start, dur, name]]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        [ev.start_ns, ev.duration_ns, ev.name]
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.start_ns, ev.duration_ns, ev.name]
                            for ev in line.events
                            if ev.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def save(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _innermost(spans, t):
    """Name of the shortest harness span (other than the window) open at
    time ``t``, or "host" where none is."""
    best = None
    for s, d, name in spans:
        if name != WINDOW_SPAN and s <= t <= s + d and (
                best is None or d < best[0]):
            best = (d, name)
    return best[1][len(HOST_PREFIX):] if best else "host"


def reduce(events: dict, *, steps: int, top: int = 10) -> dict:
    """Busy and idle time, the breakdown and the collectives of the window.

    ``steps``: the number of steps the traced window ran, for per-step
    collective time.  Returns seconds (``busy_s`` averaged over devices)."""
    win = [h for h in events["host"] if h[2] == WINDOW_SPAN]
    if not win or not events["devices"]:
        raise ValueError("trace holds no window span or no device plane")
    w0 = win[0][0]
    w1 = w0 + win[0][1]
    busy, exposed, op_time = [], [], defaultdict(float)
    gaps = defaultdict(float)
    for _, evs in sorted(events["devices"].items()):
        ops = _clip([[s, s + d] for s, d, _ in evs], w0, w1)
        union = _union(ops)
        busy.append(_length(union))
        for s, d, name in evs:
            op_time[name] += _length(_clip([[s, s + d]], w0, w1))
        coll = _union(_clip([[s, s + d] for s, d, n in evs
                             if is_collective(n)], w0, w1))
        other = _union(_clip([[s, s + d] for s, d, n in evs
                              if not is_collective(n)], w0, w1))
        exposed.append(_length(coll) - _overlap(coll, other))
        edges = [w0] + [t for iv in union for t in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[_innermost(events["host"], (s + e) / 2)] += e - s
    n_dev = len(busy)
    ns = 1e-9
    rank = lambda d: sorted(([k, v * ns / n_dev] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": sum(busy) / n_dev * ns,
        "idle_share": 1.0 - sum(busy) / n_dev / (w1 - w0),
        "exposed_collective_s_per_step": sum(exposed) / n_dev * ns
        / max(steps, 1),
        "collective_ops": sum(1 for evs in events["devices"].values()
                              for _, _, n in evs if is_collective(n)),
        "device_ops": rank(op_time),
        "idle_gaps": rank(gaps),
    }


def _overlap(a, b):
    """Total length of the intersection of two sorted disjoint interval
    lists."""
    i = j = 0
    total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
