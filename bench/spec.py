"""BENCHMARK.json and the files it names, found by name.

A cell joins a configuration (bench/configs/<config>.json), a traffic
mix (bench/traffic/<mix>.json) and its correctness limits
(bench/checks/<cell>.json); each metric is read by
bench/metrics/<metric>.py, or a split metric `<name>.<part>` by
bench/metrics/<name>.py.  Adding any of them means adding files and
entries, never editing the harness.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Spec:
    data: dict
    traffic_dir: Path = HERE / "traffic"
    checks_dir: Path = HERE / "checks"

    @classmethod
    def load(cls, path: Path = ROOT / "BENCHMARK.json", **dirs) -> "Spec":
        return cls(json.loads(Path(path).read_text()), **dirs)

    @property
    def workloads(self) -> List[dict]:
        return self.data["workloads"]

    def cell(self, name: str) -> dict:
        for c in self.workloads:
            if c["name"] == name:
                return c
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((ROOT / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def mix(self, name: str) -> dict:
        return json.loads((self.traffic_dir / f"{name}.json").read_text())

    def check(self, cell: str) -> dict:
        return json.loads((self.checks_dir / f"{cell}.json").read_text())

    def metrics(self, cell: str, traced: bool) -> List[dict]:
        """The metrics this cell reports in a run: end-to-end ones without
        the trace, per-layer ones with it."""
        group = self.data["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or cell in m["workloads"]]


def reader(metric: str):
    """bench/metrics/<metric>.py's read(run) function.  A metric split by
    the cells it serves (`<name>.<part>`) is read by bench/metrics/<name>.py
    where it has no file of its own."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{metric.split('.')[0]}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
