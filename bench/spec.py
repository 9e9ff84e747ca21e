"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or metric
lives in a file of its own, found here by name:

  configs/<config>.json        sizes as run, source, plain reference
  traffic/<traffic>.json       the job; its ``kind`` picks drivers/<kind>.py
  limits/<workload>.json       the limit of each number that decides
                               ``correct``
  metrics/<metric>.py          one reader per per-layer metric
  references/<reference>.py    the plain reference a configuration names

So a later cell, traffic mix or metric is added as files and entries,
without editing any file that is already here.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from dataclasses import dataclass, field
from typing import Any, Dict, List

BENCH = pathlib.Path(__file__).resolve().parent


class SpecError(RuntimeError):
    pass


def load_json(path: pathlib.Path) -> Any:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with everything its names point to."""
    name: str
    chips: int
    root: pathlib.Path
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)

    def bench_dir(self) -> pathlib.Path:
        return self.root / "bench"

    def driver(self):
        kind = self.traffic["kind"]
        return load_module(self.bench_dir() / "drivers" / f"{kind}.py",
                           f"bench_driver_{kind}")

    def reference(self):
        ref = self.config["reference"]
        return load_module(self.bench_dir() / "references" / f"{ref}.py",
                           f"bench_reference_{ref}")

    def metric_reader(self, name: str):
        return load_module(self.bench_dir() / "metrics" / f"{name}.py",
                           f"bench_metric_{name.replace('.', '_')}")


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root: pathlib.Path, workload: str) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    b = root / "bench"
    return Cell(
        name=workload, chips=int(w["chips"]), root=root,
        config=load_json(root / cfg_entry["file"]),
        traffic=load_json(b / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(b / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def load_peaks(device_kind: str) -> Dict[str, Any]:
    peaks = load_json(BENCH / "peaks.json")
    if device_kind not in peaks:
        raise SpecError(f"device_kind {device_kind!r} is not in "
                        f"bench/peaks.json (have {sorted(peaks)})")
    return peaks[device_kind]
