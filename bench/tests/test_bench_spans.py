"""The reduction from the program's spans and device scopes to per-step
span times, the idle split and per-scope device time: on a small
hand-made trace, on traces of a program without spans (recorded on the
chip before the program had them), and on a chip trace that has them
(``bench/data/spans_*.json.gz``)."""
import glob
import os

import pytest

from harness_util import BENCH_DIR
from benchlib import cells, spans, trace
import spans_run

MS = 1_000_000
OLD = sorted(p for p in glob.glob(os.path.join(BENCH_DIR, "data",
                                               "*.json.gz"))
             if not os.path.basename(p).startswith("spans_"))
WITH_SPANS = sorted(glob.glob(os.path.join(BENCH_DIR, "data",
                                           "spans_*.json.gz")))


def _hand_made():
    """Two MD steps on the main thread (line 0) and a worker span on
    line 1; one device whose ops carry op_names."""
    step = lambda s, d, n: [s * MS, d * MS, "repro.md.step", 0, n]
    sp = lambda s, d, name, line=0: [s * MS, d * MS, "repro." + name, line,
                                     None]
    return {
        "window": [0, 100 * MS],
        "spans": [
            step(0, 50, 0), sp(0, 20, "md.nlist"), sp(5, 10, "nlist.rebuild"),
            sp(20, 10, "md.pack"), sp(25, 5, "pack.h2d"),
            sp(30, 2, "md.dispatch"), sp(35, 10, "md.collect"),
            step(50, 45, 1), sp(50, 30, "md.nlist"), sp(80, 10, "md.pack"),
            sp(0, 100, "data.produce", line=1),
            # outside the window: not counted
            sp(120, 5, "md.nlist"),
        ],
        "devices": {"/device:TPU:0": [
            [10 * MS, 10 * MS, "jit(f)/jvp(block0)/atom_conv/dot_general"],
            [32 * MS, 8 * MS, "jit(f)/transpose(jvp(block0))/atom_conv/mul"],
            [40 * MS, 5 * MS, "jit(f)/jvp(readout)/reduce_sum"],
            [45 * MS, 2 * MS, "jit(f)/optimizer/add"],
            [47 * MS, 3 * MS, "jit(f)/convert_element_type"],
            [96 * MS, 10 * MS, "jit(f)/final_block/atom_conv/add"],
        ]},
    }


def test_spans_self_time_and_steps():
    red = spans.reduce(_hand_made())
    assert red["window_s"] == pytest.approx(0.1)
    assert red["steps"] == {"repro.md.step": 2}
    sp = red["spans"]
    assert sp["repro.md.nlist"]["count"] == 2
    assert sp["repro.md.nlist"]["total_s"] == pytest.approx(0.050)
    # step 0 holds nlist 20 + pack 10 + dispatch 2 + collect 10 ms; step 1
    # nlist 30 + pack 10 ms; the worker's span is on another thread
    assert sp["repro.md.step"]["self_s"] == pytest.approx(0.008 + 0.005)
    assert sp["repro.md.nlist"]["self_s"] == pytest.approx(0.040)
    assert sp["repro.md.pack"]["self_s"] == pytest.approx(0.015)
    assert sp["repro.data.produce"]["self_s"] == pytest.approx(0.1)
    got = spans_run.numbers({"spans": red})
    assert got["md_nlist_ms_per_step"] == pytest.approx(25.0)
    assert got["md_pack_ms_per_step"] == pytest.approx(10.0)
    assert got["md_collect_ms_per_step"] == pytest.approx(5.0)
    # no train.step marker in this trace
    assert "train_data_wait_ms_per_step" not in got


def test_idle_goes_to_the_innermost_span_of_the_stepping_thread():
    red = spans.reduce(_hand_made())
    # device busy 10-20, 32-50, 96-100 ms; idle 0-10, 20-32, 50-96 ms, cut
    # by the main thread's innermost spans; the worker's span is not it
    assert red["idle"] == pytest.approx({
        "repro.md.nlist": 0.005 + 0.030,   # 0-5, 50-80
        "repro.nlist.rebuild": 0.005,      # 5-10
        "repro.md.pack": 0.005 + 0.010,    # 20-25, 80-90
        "repro.pack.h2d": 0.005,           # 25-30
        "repro.md.dispatch": 0.002,        # 30-32
        "repro.md.step": 0.005,            # 90-95: the step's own code
        "host": 0.001,                     # 95-96: no step open
    })
    assert sum(red["idle"].values()) == pytest.approx(0.1 - 0.032)


def test_device_time_per_scope_forward_and_backward():
    dev = spans.reduce(_hand_made())["device"]
    # op time clipped to the window: 10 + 8 + 5 + 2 + 3 + 4 ms
    assert dev["busy_s"] == pytest.approx(0.032)
    assert dev["stages"] == pytest.approx({
        "blocks": 0.022, "readout": 0.005, "optimizer": 0.002,
        "unscoped": 0.003})
    assert dev["scopes"] == pytest.approx({
        "block0/atom_conv fwd": 0.010, "block0/atom_conv bwd": 0.008,
        "readout fwd": 0.005, "optimizer fwd": 0.002, "unscoped fwd": 0.003,
        "final_block/atom_conv fwd": 0.004})
    got = spans_run.numbers({"spans": spans.reduce(_hand_made())})
    share = {g: got[f"device_share.train.{g}"]
             for g in ("blocks", "readout", "optimizer", "unscoped")}
    assert share == pytest.approx({"blocks": 100 * 22 / 32,
                                   "readout": 100 * 5 / 32,
                                   "optimizer": 100 * 2 / 32,
                                   "unscoped": 100 * 3 / 32})


def _viewer_file(tmp_path, ops):
    """A trace-viewer file beside a (never read) ``.xplane.pb``: device
    ops as ``(line name, device_offset_ps, tf_op or None)``."""
    import gzip
    import json

    meta = [{"ph": "M", "pid": 3, "name": "process_name",
             "args": {"name": "/device:TPU:0"}}]
    meta += [{"ph": "M", "pid": 3, "tid": tid, "name": "thread_name",
              "args": {"name": name}}
             for tid, name in ((3, "XLA Ops"), (4, "Async XLA Ops"))]
    tids = {"XLA Ops": 3, "Async XLA Ops": 4}
    evs = [{"ph": "X", "pid": 3, "tid": tids[line], "ts": off / 1e6,
            "dur": 1.0, "name": "op",
            "args": dict({"device_offset_ps": str(off)},
                         **({"tf_op": op + ":"} if op else {}))}
           for line, off, op in ops]
    path = tmp_path / "host.xplane.pb"
    with gzip.open(tmp_path / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": meta + evs}, f)
    return str(path)


def test_op_names_come_from_the_viewer_file_only_when_it_is_whole(tmp_path):
    path = _viewer_file(tmp_path, [
        ("XLA Ops", 10, "jit(s)/jvp(block0)/atom_conv/dot_general"),
        ("XLA Ops", 20, None),
        ("Async XLA Ops", 10, "jit(s)/jvp(readout)/add")])
    viewer = spans._viewer_ops(path)
    dev = "/device:TPU:0"
    assert viewer == {(dev, 10): "jit(s)/jvp(block0)/atom_conv/dot_general",
                      (dev, 20): ""}
    ops = {dev: [[0, 5, (dev, 10)], [5, 5, (dev, 20)]]}
    spans._name_ops(ops, viewer)
    assert [op[2] for op in ops[dev]] == [viewer[(dev, 10)], ""]
    # an op the viewer file lacks (it caps its size): no op is named
    ops = {dev: [[0, 5, (dev, 10)], [5, 5, (dev, 30)]]}
    spans._name_ops(ops, viewer)
    assert [op[2] for op in ops[dev]] == ["", ""]
    assert spans._viewer_ops(str(tmp_path / "none.xplane.pb")) == {}


@pytest.mark.parametrize("op_name, want", [
    ("jit(train_step)/jvp(block2)/bond_conv/dot_general",
     ("block2", "bond_conv", "fwd")),
    ("jit(train_step)/transpose(jvp(block0))/sym_angle_update/mul",
     ("block0", "sym_angle_update", "bwd")),
    ("jit(train_step)/transpose(jvp(readout))/add_any",
     ("readout", None, "bwd")),
    ("jit(train_step)/optimizer/sqrt", ("optimizer", None, "fwd")),
    ("jit(train_step)/jvp(loss)/abs", ("loss", None, "fwd")),
    ("jit(train_step)/jvp(basis)/sin", ("basis", None, "fwd")),
    ("jit(train_step)/convert_element_type", (None, None, "fwd")),
    ("", (None, None, "fwd")),
])
def test_scope_of_op_names(op_name, want):
    assert spans.scope_of(op_name) == want


def test_numbers_are_left_out_without_their_reading():
    empty = spans.reduce({"window": None, "spans": [], "devices": {}})
    assert spans_run.numbers({}) == {}
    assert spans_run.numbers({"spans": empty, "counters": {}}) == {}
    share = lambda c: spans_run.numbers({"counters": c}).get(
        "nlist_rebuild_share.md")
    assert share({"nlist_rebuilds": 3, "nlist_updates": 12}) == \
        pytest.approx(25.0)
    assert share({"nlist_rebuilds": 0, "nlist_updates": 0}) is None


def test_the_traced_window_is_reduced_before_its_trace_is_removed(tmp_path):
    """``SpanContext``'s profiler session leaves the reduction of the
    program's spans and the window's counter deltas as it closes."""
    import argparse

    import jax

    import run
    from repro.runtime import spans as program_spans

    class Program:
        def __init__(self):
            self.n = 0

        def stats(self):
            return {"nlist_updates": 4 * self.n, "nlist_rebuilds": self.n,
                    "packed": {"atoms": 10 * self.n}, "waste": 0.5}

    cell = cells.load_cell("fs_md_64rep")
    opts = argparse.Namespace(seed=1, seconds=1.0, trace=1)
    ctx = spans_run.SpanContext(cell, opts, jax, jax.devices()[:1],
                                run.CompileCounter(jax))
    prog = Program()
    ctx.plant("md", prog)
    prog.n = 2  # before the window: not counted
    with ctx.profile(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for i in range(3):
                with program_spans.step("md.step", i):
                    with program_spans.span("md.nlist"):
                        prog.n += 1
    assert ctx._program is None
    assert ctx.counters == {"nlist_updates": 12, "nlist_rebuilds": 3,
                            "packed.atoms": 30}
    red = ctx.spans
    assert red["steps"] == {"repro.md.step": 3}
    assert red["spans"]["repro.md.nlist"]["count"] == 3
    got = spans_run.numbers({"spans": red, "counters": ctx.counters})
    assert got["nlist_rebuild_share.md"] == pytest.approx(25.0)
    assert got["md_nlist_ms_per_step"] > 0


@pytest.mark.parametrize("path", OLD, ids=[os.path.basename(p) for p in OLD])
def test_a_program_without_spans_reads_nothing(path):
    """A chip trace of the program before it had spans or scopes (device
    ops named by their HLO text): none of the span numbers is read."""
    events = trace.read(path)
    win = next(h for h in events["host"] if h[2] == trace.WINDOW_SPAN)
    red = spans.reduce({"window": win[:2], "spans": [],
                        "devices": events["devices"]})
    assert red["device"] is None and red["steps"] == {}
    assert set(red["idle"]) <= {"host"}
    assert spans_run.numbers({"spans": red}) == {}


@pytest.mark.parametrize("path", WITH_SPANS,
                         ids=[os.path.basename(p) for p in WITH_SPANS])
def test_reduction_on_a_chip_trace_with_spans(path):
    events = spans.read(path)
    red = spans.reduce(events)
    assert red["steps"], "no step marker in the recorded trace"
    for rec in red["spans"].values():
        assert 0 <= rec["self_s"] <= rec["total_s"] + 1e-12
    dev = red["device"]
    assert dev is not None and dev["busy_s"] > 0
    assert sum(dev["stages"].values()) == pytest.approx(dev["busy_s"])
    assert sum(dev["scopes"].values()) == pytest.approx(dev["busy_s"])
    # idle time: the window less the union of the device's ops
    busy = trace.reduce(events, steps=1)["busy_s"]
    assert sum(red["idle"].values()) == pytest.approx(
        red["window_s"] - busy, rel=1e-6)


# each span reader of BENCHMARK.json, and the recorded chip trace of a
# cell it reads
SPAN_READERS = {
    "md_nlist_ms_per_step": "spans_md_64rep_fs.json.gz",
    "md_pack_ms_per_step": "spans_md_64rep_fs.json.gz",
    "md_collect_ms_per_step": "spans_md_64rep_fs.json.gz",
    "train_data_wait_ms_per_step": "spans_train_b128_fs.json.gz",
    "device_share.train.blocks": "spans_train_b128_fs.json.gz",
    "device_share.train.readout": "spans_train_b128_fs.json.gz",
    "device_share.train.optimizer": "spans_train_b128_fs.json.gz",
    "device_share.train.unscoped": "spans_train_b128_fs.json.gz",
}


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_on_a_chip_trace_with_spans(name):
    """Each reader gives, from the driver's reading of a recorded chip
    trace, the number ``spans_run.py`` prints under its name."""
    red = spans.reduce(spans.read(os.path.join(BENCH_DIR, "data",
                                               SPAN_READERS[name])))
    got = cells.reader(name)({"spans": red})
    assert got is not None and got >= 0
    assert got == spans_run.NUMBERS[name]({"spans": red})
    if name.startswith("device_share."):
        assert got <= 100


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_reads_nothing_without_program_spans(name):
    """On a chip trace of the program before it had spans or scopes, and
    on a run without a trace, no span reader returns a number."""
    events = trace.read(OLD[0])
    win = next(h for h in events["host"] if h[2] == trace.WINDOW_SPAN)
    red = spans.reduce({"window": win[:2], "spans": [],
                        "devices": events["devices"]})
    read = cells.reader(name)
    assert read({"spans": red}) is None
    assert read({}) is None


def test_rebuild_share_reads_the_window_counts():
    read = cells.reader("nlist_rebuild_share.md")
    assert read({"nlist.md": {"nlist_rebuilds": 3, "nlist_updates": 12}}) \
        == pytest.approx(25.0)
    assert read({"nlist.md": {"nlist_rebuilds": 0, "nlist_updates": 640}}) \
        == 0.0
    assert read({"nlist.md": {"nlist_rebuilds": 0, "nlist_updates": 0}}) \
        is None
    assert read({}) is None
