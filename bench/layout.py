"""Where the benchmark finds a cell, its parts and its metrics, by name.

``BENCHMARK.json`` at the checkout's root lists the cells and metrics.
Every part of a cell is a file named after it under ``bench/``:

* ``configs/<config>.json``  the configuration's sizes, as run;
* ``traffic/<mix>.json``     the parameters of the traffic generator;
* ``workloads/<cell>.json``  engine settings and the limit of the check;
* ``metrics/<metric>.py``    a reader with ``read(run) -> float | None``;
* ``kernels/<backend>.json`` device op-name patterns of linear kernels.

So a new cell, configuration, mix, metric or kernel backend is new files
and an entry in ``BENCHMARK.json``; no file that is there changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

CELL_KEYS = {"engine", "check"}


@dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic: str
    chips: int
    config: dict
    mix: dict
    engine: dict
    check: dict


@dataclass
class Run:
    """What a metric reader reads: the window as the benchmark recorded
    it (``bench.loop``), and the profiler trace when one was taken."""

    cell: Cell
    setup_s: float
    w0: float
    w1: float
    steps: list
    reqs: dict
    trace: object
    peaks: dict | None


class Benchmark:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.dir = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _json(self, kind: str, name: str) -> dict:
        path = self.dir / kind / f"{name}.json"
        if not path.is_file():
            raise KeyError(f"no {kind} file for {name!r} at {path}")
        return json.loads(path.read_text())

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.spec["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        config = dict(self._json("configs", entry["config"]),
                      name=entry["config"])
        settings = self._json("workloads", name)
        if set(settings) != CELL_KEYS:
            raise ValueError(f"workload file of {name!r} must hold exactly "
                             f"{sorted(CELL_KEYS)}")
        return Cell(name=name, config_name=entry["config"],
                    traffic=entry["traffic"], chips=int(entry["chips"]),
                    config=config, mix=self._json("traffic",
                                                  entry["traffic"]),
                    engine=settings["engine"], check=settings["check"])

    def metrics_for(self, cell: Cell, *, per_layer: bool) -> list[dict]:
        group = self.spec["per_layer" if per_layer else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or cell.name in m["workloads"]]

    def reader(self, metric: str):
        path = self.dir / "metrics" / f"{metric}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_metric_{re.sub(r'[^A-Za-z0-9_]', '_', metric)}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read

    def read_metrics(self, cell: Cell, run: Run, *,
                     per_layer: bool) -> dict:
        """Each applicable metric that its reader finds; a reader that
        finds nothing to read returns None and the metric is left out."""
        out = {}
        for m in self.metrics_for(cell, per_layer=per_layer):
            value = self.reader(m["name"])(run)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def kernel_patterns(self) -> list[re.Pattern]:
        pats = []
        for path in sorted((self.dir / "kernels").glob("*.json")):
            pats += [re.compile(p)
                     for p in json.loads(path.read_text())["patterns"]]
        return pats
