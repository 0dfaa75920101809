"""What a run is made of, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; its files are
``configs/<config>.json`` and ``workloads/<cell>.json``, and the workload
names the driver (``drivers/<driver>.py``) that plays it. Each metric is a
reader ``metrics/<metric>.py`` with ``read(run) -> float | None``. Adding a
cell, a configuration or a metric therefore takes new files and new
``BENCHMARK.json`` entries, and no edit here.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Run:
    """Everything one run produced; drivers fill it, readers read it."""
    cell: dict
    config: dict
    workload: dict
    seed: int
    seconds: float
    traced: bool
    t_start: float                 # perf_counter at process start
    setup_s: float | None = None
    attempted: int = 0
    failed: int = 0
    compared: list = dataclasses.field(default_factory=list)
    memory_peak_bytes: int = 0
    peaks: object = None           # peaks.Peaks of the device
    trace: object = None           # trace.Trace of the traced window
    breakdown: dict | None = None
    data: dict = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(
            v == v and v <= lim for _, v, lim in self.compared)


class Benchmark:
    def __init__(self, root: str = ROOT, base: str = HERE):
        self.root, self.base = root, base
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no cell {name!r}; cells: "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config_entry(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no configuration {name!r}")

    def config(self, cell: dict) -> dict:
        with open(os.path.join(self.root,
                               self.config_entry(cell["config"])["file"])) \
                as f:
            return json.load(f)

    def workload(self, cell: dict) -> dict:
        with open(os.path.join(self.base, "workloads",
                               cell["name"] + ".json")) as f:
            return json.load(f)

    def metrics(self, cell: dict, traced: bool) -> list:
        """The metric entries this cell reports: its end-to-end metrics in
        an untraced run, its per-layer metrics in a traced one."""
        e2e = [m for m in self.spec["end_to_end"]
               if cell["name"] in m.get("workloads", [cell["name"]])]
        if not traced:
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if cell["name"] in m.get("workloads", [cell["name"]])
                and m["moves"] in moved]

    def reader(self, metric: str):
        return load_module(os.path.join(self.base, "metrics",
                                        metric + ".py")).read

    def driver(self, name: str):
        return importlib.import_module(f"chipbench.drivers.{name}")


def load_module(path: str):
    """Import a file by path (metric names hold dots)."""
    name = "chipbench_file_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(bench: Benchmark, run: Run) -> dict:
    """``{name: {"value", "unit"}}`` for every metric of the run's kind
    whose reader found something to read."""
    out = {}
    for m in bench.metrics(run.cell, run.traced):
        value = bench.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)
