"""One traced run of a cell, read through the program's own spans, device
scopes and counters.

    python3 bench/spans_run.py --workload <name> --seed <n> --seconds <s>

Runs the cell's driver as ``bench/run.py --trace 1`` does.  As the traced
window's profiler session closes, before the driver removes the trace,
the trace is reduced with ``benchlib.spans`` (the ``repro.*`` host spans,
the device's idle time split over them, device time per program scope),
and the program's counters are read against their values as the session
opened.  The last line of standard output is one JSON object: ``correct``,
the end-to-end numbers, ``numbers`` (the per-step span times, device
shares and rebuild share below), ``counters`` and the whole reduction.
The benchmark's own ``--trace 1`` runs read the same numbers through
the readers in ``bench/metrics``, which call the functions ``NUMBERS``
calls.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from benchlib import cells, spans, trace  # noqa: E402

MD_STEP, TRAIN_STEP = "repro.md.step", "repro.train.step"
NUMBERS = {
    "md_nlist_ms_per_step":
        lambda r: spans.per_step_ms(r, "repro.md.nlist", MD_STEP),
    "md_pack_ms_per_step":
        lambda r: spans.per_step_ms(r, "repro.md.pack", MD_STEP),
    "md_collect_ms_per_step":
        lambda r: spans.per_step_ms(r, "repro.md.collect", MD_STEP),
    "nlist_rebuild_share.md": lambda r: _share(
        r.get("counters", {}), "nlist_rebuilds", "nlist_updates"),
    "train_data_wait_ms_per_step":
        lambda r: spans.per_step_ms(r, "repro.data.wait", TRAIN_STEP),
    "device_share.train.blocks": lambda r: spans.device_share(r, "blocks"),
    "device_share.train.readout": lambda r: spans.device_share(r, "readout"),
    "device_share.train.optimizer":
        lambda r: spans.device_share(r, "optimizer"),
    "device_share.train.unscoped":
        lambda r: spans.device_share(r, "unscoped"),
}


def _share(counters: dict, part: str, whole: str):
    """``part`` over ``whole`` in percent; None without ``whole``."""
    return (100.0 * counters[part] / counters[whole]
            if counters.get(whole) else None)


def numbers(read: dict) -> dict:
    """The ``NUMBERS`` that ``read`` (``{"spans": reduction, "counters":
    deltas}``) holds; those it lacks are left out."""
    out = {name: f(read) for name, f in NUMBERS.items()}
    return {k: v for k, v in out.items() if v is not None}


def counters(program) -> dict:
    """The program object's integer counters (``stats()``), nested groups
    flattened as ``<group>.<key>``; {} for an object without ``stats``."""
    out = {}
    stats = program.stats() if hasattr(program, "stats") else {}
    for k, v in stats.items():
        if isinstance(v, dict):
            out.update({f"{k}.{kk}": vv for kk, vv in v.items()
                        if isinstance(vv, int)})
        elif isinstance(v, int):
            out[k] = v
    return out


class SpanContext(run.Context):
    """``run.Context`` whose profiler session, as it closes, leaves the
    reduction in ``self.spans`` and the window's counter deltas in
    ``self.counters``.  The driver hands its program object to ``plant``
    (``BatchedMD``, ``Trainer``); it is held only until the session
    closes."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.spans, self.counters, self._program = None, {}, None
        self.plant = lambda kind, obj: setattr(self, "_program", obj)

    def profile(self, logdir: str):
        return _Reduced(self, super().profile(logdir), logdir)


class _Reduced:
    def __init__(self, ctx: SpanContext, session, logdir: str):
        self.ctx, self.session, self.logdir = ctx, session, logdir

    def __enter__(self):
        self.before = counters(self.ctx._program)
        return self.session.__enter__()

    def __exit__(self, *exc):
        out = self.session.__exit__(*exc)
        ctx = self.ctx
        after = counters(ctx._program)
        ctx._program = None
        ctx.counters = {k: after[k] - self.before[k]
                        for k in after if k in self.before}
        # op names come from the trace-viewer file, which only the training
        # window keeps whole: an MD window's passes the file's size cap
        names = ctx.cell.traffic["driver"] == "train"
        ctx.spans = spans.reduce(spans.load(trace.find_xplane(self.logdir),
                                            op_names=names))
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)

    import jax

    devices = run.require_chips(jax, cell.chips)
    run.use_compile_cache()
    opts = argparse.Namespace(seed=args.seed, seconds=args.seconds, trace=1)
    ctx = SpanContext(cell, opts, jax, devices, run.CompileCounter(jax))
    out = ctx.run_driver()
    read = {"spans": ctx.spans, "counters": ctx.counters}
    checks = out["checks"]
    result = {
        "workload": args.workload, "seed": args.seed,
        "correct": all(math.isfinite(v) and v <= lim
                       for v, lim in checks.values()),
        "end_to_end": out["end_to_end"], "numbers": numbers(read),
        "counters": ctx.counters, "idle_gaps": out["trace"]["idle_gaps"],
        "reduction": ctx.spans,
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
