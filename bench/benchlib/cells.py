"""Everything a cell is, found by name: its entry in ``BENCHMARK.json``,
its configuration, its traffic, its limits, its driver and the readers of
its per-layer metrics.  Adding a cell, a traffic mix, a configuration or a
metric adds files here and edits none."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict     # configs/<config>.json
    traffic: dict    # traffic/<traffic>.json
    limits: dict     # limits/<workload>.json: {number: limit}
    end_to_end: list  # BENCHMARK.json metrics this cell reports
    per_layer: list

    def driver(self):
        name = self.traffic["driver"]
        return _module(os.path.join(BENCH_DIR, "drivers", f"{name}.py"),
                       f"bench_driver_{name}")


def benchmark() -> dict:
    return _json(ROOT, "BENCHMARK.json")


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in e2e_names and _reports(m, name)]
    return Cell(
        name=name, chips=entry["chips"],
        config=_json(ROOT, config["file"]),
        traffic=_json(BENCH_DIR, "traffic", f"{entry['traffic']}.json"),
        limits=_json(BENCH_DIR, "limits", f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer)


def reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    return _module(os.path.join(BENCH_DIR, "metrics", f"{metric}.py"),
                   f"bench_metric_{metric.replace('.', '_')}").read


def model_config(C, cfg: dict, precision):
    """The preset a configuration file names, checked against the file's
    widths; ``precision`` (the control) switches its precision path."""
    model_cfg = getattr(C, cfg["preset"])
    for key in ("dim", "num_rbf", "num_fourier", "num_blocks", "r_cut_atom",
                "r_cut_bond", "envelope_p", "readout", "precision"):
        if getattr(model_cfg, key) != cfg[key]:
            raise SystemExit(f"bench: preset {cfg['preset']} has {key}="
                             f"{getattr(model_cfg, key)!r}, the configuration"
                             f" file {cfg[key]!r}")
    return model_cfg if precision is None else model_cfg.with_(
        precision=precision)
