"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` workload) names a configuration
(``bench/configs``), a traffic mix (``bench/traffic``, which names its
driver in ``bench/drivers``) and the limits of its output check
(``bench/limits``).  Set-up makes the data and the weights from
``--seed``, builds the program's own objects and warms every shape the
window uses; the window then drives the program for ``--seconds``; after
it, the program's state is freed and the plain reference checks what the
window produced.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` runs the window under the profiler and reports its
per-layer metrics (``bench/metrics``) and a breakdown.

The last line of standard output is one JSON object; the last lines of
standard error give each number compared beside its limit.  Without a TPU,
or with fewer chips than the cell asks for, it exits 1 and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from benchlib import cells  # noqa: E402


def _process_age_s() -> float:
    """Seconds since this process started (its start time in /proc)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, a
    fixed path, given to the program through the variable its
    ``enable_compile_cache`` reads."""
    path = os.path.join(cells.ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    sys.path.insert(0, os.path.join(cells.ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    import jax

    enable_compile_cache()
    # keep every entry: each cell's programs must survive the others'
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def require_chips(jax, chips: int):
    """The first ``chips`` TPU devices, or exit without a result."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX's backend is "
                         f"{backend!r}); this benchmark runs only on the chip")
    devices = jax.devices()
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell asks for {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Backend compilations and persistent-cache hits, from JAX's
    monitoring events."""

    def __init__(self, jax):
        self.compiles = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class Context:
    """What a driver gets: the cell, the seed, the window, the devices, and
    the clocks and counters the harness keeps."""

    def __init__(self, cell, args, jax, devices, compiles):
        self.cell, self.jax, self.devices = cell, jax, devices
        self.seed, self.seconds, self.trace = args.seed, args.seconds, args.trace
        self.compiles = compiles
        self.setup_s = None
        # the lower-precision controls and planted faults (bench/readings.py
        # and the tests set them; a benchmark run never does): the
        # program's own precision path, the reference at a lower matmul
        # precision in the program's place, a fault under the timed path
        self.precision = None
        self.matmul_precision = cell.config["matmul_precision"]
        self.reference_control = None
        self.plant = lambda kind, obj: None

    def window_starts(self) -> None:
        """Called by the driver as the first timed step begins."""
        self.setup_s = _process_age_s()
        self.setup_compiles = self._compiles_before = self.compiles.compiles

    def compiles_in_window(self) -> int:
        return self.compiles.compiles - self._compiles_before

    def memory_peak_bytes(self) -> int:
        """Peak device memory of the fullest chip: buffers in use plus the
        region the runtime reserves for program temporaries (a compiled
        step's activations live there, outside ``peak_bytes_in_use``)."""
        def peak(d):
            s = d.memory_stats() or {}
            return int(s.get("peak_bytes_in_use", 0)) + int(
                s.get("peak_bytes_reserved", 0))
        return max(peak(d) for d in self.devices)

    def run_driver(self) -> dict:
        """The cell's driver, at the matmul precision the configuration
        states ("default": JAX's own, one bf16 pass on the TPU)."""
        if self.matmul_precision == "default":
            return self.cell.driver().run(self)
        with self.jax.default_matmul_precision(self.matmul_precision):
            return self.cell.driver().run(self)

    def profile(self, logdir: str):
        """Profiler context for the traced window: device ops and the
        harness's own host spans, no Python tracer."""
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        return self.jax.profiler.trace(logdir, profiler_options=opts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)

    import jax

    devices = require_chips(jax, cell.chips)
    from benchlib.peaks import peaks_for

    peaks = peaks_for(devices[0].device_kind)
    log(f"compile cache {use_compile_cache()}")
    ctx = Context(cell, args, jax, devices, CompileCounter(jax))
    out = ctx.run_driver()
    log(f"memory stats of the first chip {devices[0].memory_stats()}")
    log(f"set-up {ctx.setup_s:.3f} s with {ctx.setup_compiles} compilations "
        f"and {ctx.compiles.hits} persistent-cache hits; "
        f"{out['compiles_in_window']} compilations inside the window")

    if args.trace:
        read = dict(out["readings"], peaks=peaks, chips=cell.chips,
                    trace=out["trace"])
        metrics = {}
        for m in cell.per_layer:
            value = cells.reader(m["name"])(read)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    checks = out["checks"]
    correct = all(math.isfinite(v) and v <= lim for v, lim in checks.values())
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace:
        tr = out["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
