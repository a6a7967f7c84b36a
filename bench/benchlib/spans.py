"""From a profiler trace to the program's own spans and device scopes.

The program opens host spans named ``repro.*`` (``repro.runtime.spans``;
a step marker carries a ``step_num`` stat) and names its device stages
with ``jax.named_scope`` (``basis``, ``embed``, ``block<i>``,
``final_block``, ``readout``, ``loss``, ``optimizer``), which XLA keeps
in every operation's ``op_name`` (``transpose(jvp(...))`` for the
backward).  Two steps, kept apart so that the second can be checked on a
recorded trace without a chip:

  1. :func:`load` reads the ``.xplane.pb`` that ``jax.profiler.trace``
     wrote: the window span, every ``repro.*`` host span with the host
     line (thread) it ran on, and per device the operations of its "XLA
     Ops" line, each named by its ``op_name`` ("" where it has none).
     Times are nanoseconds on the profiler's common clock.  The op_name
     is the trace's own ``tf_op`` stat, which the TPU keeps in the event
     metadata that ``ProfileData`` does not show: it is read from the
     trace-viewer file the profiler writes beside the ``.xplane.pb``,
     matched by device and ``device_offset_ps``, and only where that
     file holds every op of the window's devices.
  2. :func:`reduce` takes, inside the window, each span's count, total
     and self time (its duration less the spans nested in it on its
     thread), the device's idle gaps split over the innermost span the
     stepping thread was in, and the device time of the operations under
     each program scope, forward and backward.

A trace without the program's spans or scopes (an older program) reduces
to empty ``spans`` and ``device=None``; nothing here raises for it.
"""
from __future__ import annotations

import bisect
import gzip
import json
import os
import re
from collections import defaultdict

PREFIX = "repro."
WINDOW_SPAN = "bench.window"
# the program's device stages; a block's op also names its update
STAGES = re.compile(r"^(basis|embed|block\d+|final_block|readout|loss|"
                    r"optimizer)$")
UPDATES = ("atom_conv", "bond_conv", "sym_bond_conv", "angle_update",
           "sym_angle_update")
# stage -> the group a device-share metric reads
GROUPS = {"basis": "basis", "embed": "embed", "final_block": "blocks",
          "readout": "readout", "loss": "loss", "optimizer": "optimizer"}


def load(path: str, op_names: bool = True) -> dict:
    """Events of one trace: ``{"window": [start, dur] | None, "spans":
    [[start, dur, name, line, step_num]], "devices": {plane: [[start,
    dur, op_name]]}}``; ``line`` numbers the host lines (threads),
    ``step_num`` is None on spans that are not step markers.  Every
    op_name is "" without ``op_names``, and where the trace-viewer file
    lacks some of the device's ops (it caps its size, which a long MD
    window passes)."""
    from jax.profiler import ProfileData

    viewer = _viewer_ops(path) if op_names else {}
    data = ProfileData.from_file(path)
    window, found, devices, line_no = None, [], {}, 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        [ev.start_ns, ev.duration_ns, (plane.name,
                                                       _offset(ev))]
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN and window is None:
                        window = [ev.start_ns, ev.duration_ns]
                    elif ev.name.startswith(PREFIX):
                        step = dict(ev.stats).get("step_num")
                        found.append([ev.start_ns, ev.duration_ns, ev.name,
                                      line_no,
                                      None if step is None else int(step)])
                line_no += 1
    _name_ops(devices, viewer)
    return {"window": window, "spans": found, "devices": devices}


def _name_ops(devices: dict, viewer: dict) -> None:
    """Replace each op's ``(plane, device_offset_ps)`` by its op_name from
    ``viewer``; by "" everywhere where ``viewer`` lacks any of them."""
    complete = bool(viewer) and all(op[2] in viewer
                                    for ops in devices.values()
                                    for op in ops)
    for ops in devices.values():
        for op in ops:
            op[2] = viewer[op[2]] if complete else ""


def _offset(ev):
    for key, value in ev.stats:
        if key == "device_offset_ps":
            return int(value)
    return None


def _viewer_ops(path: str) -> dict:
    """``{(device plane, device_offset_ps): op_name or ""}`` of the "XLA
    Ops" events in the trace-viewer file beside ``path``; {} where there
    is none."""
    viewer = path[:-len(".xplane.pb")] + ".trace.json.gz"
    if not path.endswith(".xplane.pb") or not os.path.exists(viewer):
        return {}
    with gzip.open(viewer, "rt") as f:
        events = json.load(f).get("traceEvents", [])
    planes, lines = {}, set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            planes[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name" \
                and e["args"]["name"] == "XLA Ops":
            lines.add((e["pid"], e["tid"]))
    out = {}
    for e in events:
        args = e.get("args") or {}
        if e.get("ph") == "X" and (e.get("pid"), e.get("tid")) in lines \
                and "device_offset_ps" in args:
            key = (planes.get(e["pid"]), int(args["device_offset_ps"]))
            out[key] = args.get("tf_op", "").rstrip(":")
    return out


def save(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def scope_of(op_name: str):
    """``(stage, update, direction)`` of an operation from its op_name:
    ``jit(step)/transpose(jvp(block0))/atom_conv/mul`` gives ``("block0",
    "atom_conv", "bwd")``; ``(None, None, dir)`` where no program stage
    names it."""
    parts = [p for p in re.split(r"[/()]", op_name) if p]
    direction = "bwd" if "transpose" in parts else "fwd"
    for i, p in enumerate(parts):
        if STAGES.match(p):
            nxt = parts[i + 1] if i + 1 < len(parts) else None
            return p, nxt if nxt in UPDATES else None, direction
    return None, None, direction


def group_of(stage) -> str:
    if stage is None:
        return "unscoped"
    return GROUPS.get(stage, "blocks")


def _self_times(spans):
    """Per span (index into ``spans``), its duration less the spans
    nested directly in it on the same thread."""
    out = [d for _, d, *_ in spans]
    by_line = defaultdict(list)
    for i, (s, d, _, line, _) in enumerate(spans):
        by_line[line].append((s, -d, i))
    for items in by_line.values():
        stack = []
        for s, neg_d, i in sorted(items):
            while stack and s >= spans[stack[-1]][0] + spans[stack[-1]][1]:
                stack.pop()
            if stack:
                out[stack[-1]] -= -neg_d
            stack.append(i)
    return out


def _innermost_segments(spans):
    """One thread's spans as sorted, disjoint ``[start, end, name]``
    segments, each named by the innermost span open in it."""
    segs, stack, t = [], [], None
    for s, d, name, *_ in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, inner = stack.pop()
            segs.append([t, end, inner])
            t = end
        if stack:
            segs.append([t, s, stack[-1][1]])
        t = s
        stack.append((s + d, name))
    while stack:
        end, inner = stack.pop()
        segs.append([t, end, inner])
        t = end
    return [x for x in segs if x[1] > x[0]]


def _stepping_spans(spans):
    """The spans of the host line (thread) that holds the most step
    markers, or the most spans where no line holds a marker."""
    markers, total = defaultdict(int), defaultdict(int)
    for _, _, _, line, step in spans:
        total[line] += 1
        markers[line] += step is not None
    if not total:
        return []
    line = max(total, key=lambda k: (markers[k], total[k]))
    return [x for x in spans if x[3] == line]


def reduce(events: dict) -> dict:
    """The window's spans, idle split and device time per scope, seconds.

    Returns ``{"window_s", "steps": {marker: count}, "spans": {name:
    {"count", "total_s", "self_s"}}, "idle": {span or "host": s}, "device":
    None | {"busy_s", "stages": {group: s}, "scopes": {"<stage>[/<update>]
    <fwd|bwd>": s}}}``.  Spans count where they start inside the window;
    device times are op durations clipped to the window, averaged over
    devices; ``device`` is None where no operation names a program stage."""
    ns = 1e-9
    if not events.get("window"):
        return {"window_s": None, "steps": {}, "spans": {}, "idle": {},
                "device": None}
    w0, wd = events["window"]
    w1 = w0 + wd
    inside = [x for x in events["spans"] if w0 <= x[0] < w1]
    selfs = _self_times(inside)
    spans = defaultdict(lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0})
    steps = defaultdict(int)
    for x, own in zip(inside, selfs):
        rec = spans[x[2]]
        rec["count"] += 1
        rec["total_s"] += x[1] * ns
        rec["self_s"] += own * ns
        if x[4] is not None:
            steps[x[2]] += 1

    segs = _innermost_segments(_stepping_spans(events["spans"]))
    ends = [e for _, e, _ in segs]
    idle = defaultdict(float)
    stage_t, scope_t = defaultdict(float), defaultdict(float)
    busy, scoped = 0.0, False
    devs = events.get("devices") or {}
    for ops in devs.values():
        cur = w0
        for s, d, op in sorted(ops):
            lo, hi = max(s, w0), min(s + d, w1)
            if hi > lo:
                busy += hi - lo
                stage, update, direction = scope_of(op)
                scoped = scoped or stage is not None
                stage_t[group_of(stage)] += hi - lo
                key = stage if update is None else f"{stage}/{update}"
                scope_t[f"{key or 'unscoped'} {direction}"] += hi - lo
            if s > cur:
                _idle(idle, cur, min(s, w1), segs, ends)
            cur = max(cur, min(s + d, w1))
        if w1 > cur:
            _idle(idle, cur, w1, segs, ends)
    n = max(len(devs), 1)
    device = None
    if scoped:
        device = {"busy_s": busy * ns / n,
                  "stages": {k: v * ns / n for k, v in stage_t.items()},
                  "scopes": {k: v * ns / n for k, v in scope_t.items()}}
    return {"window_s": wd * ns, "steps": dict(steps),
            "spans": {k: dict(v) for k, v in spans.items()},
            "idle": {k: v * ns / n for k, v in idle.items()},
            "device": device}


def _idle(idle, a0, a1, segs, ends):
    """Split the idle interval [a0, a1] over the segments it meets; what
    no segment covers is "host"."""
    if a1 <= a0:
        return
    covered = 0
    i = bisect.bisect_right(ends, a0)
    while i < len(segs) and segs[i][0] < a1:
        s, e, name = segs[i]
        t = min(a1, e) - max(a0, s)
        idle[name] += t
        covered += t
        i += 1
    if a1 - a0 > covered:
        idle["host"] += a1 - a0 - covered


def per_step_ms(read: dict, span: str, marker: str):
    """Total time of ``span`` per ``marker`` step in the window, ms; None
    where the trace holds neither."""
    red = read.get("spans")
    if not red:
        return None
    steps = red["steps"].get(marker)
    rec = red["spans"].get(span)
    if not steps or rec is None:
        return None
    return 1e3 * rec["total_s"] / steps


def device_share(read: dict, group: str):
    """Share of the window's device op time under ``group``'s scopes, %;
    None where no operation names a program stage."""
    red = read.get("spans")
    dev = red and red["device"]
    if not dev or not dev["busy_s"]:
        return None
    return 100.0 * dev["stages"].get(group, 0.0) / dev["busy_s"]
