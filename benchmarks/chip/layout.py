"""Find every piece of a cell by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each piece sits in a file of
its own under the benchmark's directory::

    configs/<config>.json     the deployment (path given by BENCHMARK.json)
    clouds/<generator>.py     the generator a configuration's cloud names
    traffic/<traffic>.json    the traffic mix; its "driver" names a module
    drivers/<driver>.py       one general generator per driver kind
    metrics/<metric>.py       one reader per per-layer metric
    limits/<cell>.json        the limit of each number the check compares

A later cell, mix, driver or metric is a new file and a new entry in
``BENCHMARK.json``; no existing file changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent.parent

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its pieces resolved."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell reports with --trace 1


def _load_module(path: Path, kind: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module at {path}")
    mod_name = "benchmarks_chip_" + re.sub(r"\W", "_", f"{kind}_{path.stem}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Layout:
    """``BENCHMARK.json`` at ``root`` and the files it names.  ``bench_dir``
    holds the cloud, traffic, driver, metric and limit files."""

    def __init__(self, root=ROOT, bench_dir=None):
        self.root = Path(root)
        self.bench_dir = Path(bench_dir) if bench_dir else BENCH_DIR
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())

    def _entry(self, group: str, name: str) -> dict:
        for e in self.bench[group]:
            if e["name"] == name:
                return e
        names = [e["name"] for e in self.bench[group]]
        raise KeyError(f"no {group} entry named {name!r}; have {names}")

    def config(self, name: str) -> dict:
        return json.loads((self.root / self._entry("configs", name)["file"])
                          .read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench_dir / "traffic" / f"{name}.json")
                          .read_text())

    def limits(self, workload: str) -> dict:
        return json.loads((self.bench_dir / "limits" / f"{workload}.json")
                          .read_text())["limits"]

    def cloud(self, generator: str):
        return _load_module(self.bench_dir / "clouds" / f"{generator}.py",
                            "cloud")

    def driver(self, kind: str):
        return _load_module(self.bench_dir / "drivers" / f"{kind}.py",
                            "driver")

    def metric_reader(self, name: str):
        return _load_module(self.bench_dir / "metrics" / f"{name}.py",
                            "metric")

    def end_to_end_for(self, workload: str) -> list:
        return [m for m in self.bench["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer_for(self, workload: str) -> list:
        """Per-layer metrics a cell reports: those listing it, and those
        with no list whose end-to-end metric the cell reports."""
        reported = {m["name"] for m in self.end_to_end_for(workload)}
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if workload in m["workloads"]:
                    out.append(m)
            elif m["moves"] in reported:
                out.append(m)
        return out

    def cell(self, workload: str) -> Cell:
        w = self._entry("workloads", workload)
        return Cell(
            name=w["name"],
            chips=int(w["chips"]),
            config_name=w["config"],
            config=self.config(w["config"]),
            traffic_name=w["traffic"],
            traffic=self.traffic(w["traffic"]),
            limits=self.limits(w["name"]),
            end_to_end=self.end_to_end_for(w["name"]),
            per_layer=self.per_layer_for(w["name"]),
        )
