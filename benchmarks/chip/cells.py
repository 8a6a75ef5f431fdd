"""Finding a cell's pieces by name.

``BENCHMARK.json`` names each cell's configuration, traffic mix and
metrics; this module finds the files that hold them:

- ``configs/<config>.json``: the configuration as it is run;
- ``traffic/<traffic>.json``: the traffic mix's parameters;
- ``metrics/<metric>.py``: a reader with ``read(ctx) -> float | None``;
- ``limits/<workload>.json``: the limit of each number the check compares;
- ``reference/<name>.py``, named by the configuration's ``reference``
  key: the plain reference, a module with a ``Reference`` class.

A cell, a configuration, a mix or a metric is added by adding its files
and its entries in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Dict, List, Mapping, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, ModuleType]
    reference: ModuleType


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load(path: pathlib.Path, module: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        module.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, here: pathlib.Path = HERE) -> ModuleType:
    path = here / "metrics" / f"{name}.py"
    mod = _load(path, f"_bench_metric_{name}")
    if not callable(getattr(mod, "read", None)):
        raise AttributeError(f"{path} defines no read(ctx)")
    return mod


def load_reference(name: str, here: pathlib.Path = HERE) -> ModuleType:
    path = here / "reference" / f"{name}.py"
    mod = _load(path, f"_bench_reference_{name}")
    if not callable(getattr(mod, "Reference", None)):
        raise AttributeError(f"{path} defines no Reference")
    return mod


def _applies(metric: Mapping, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, bench: Optional[dict] = None,
            here: pathlib.Path = HERE, limits: Optional[dict] = None
            ) -> Cell:
    """The cell ``workload`` of ``bench`` (``BENCHMARK.json`` by
    default) with its files; ``limits`` stands in for the cell's limits
    file where given."""
    bench = bench if bench is not None else load_benchmark(here.parents[1])
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    config = _json(here / "configs" / f"{w['config']}.json")
    traffic = _json(here / "traffic" / f"{w['traffic']}.json")
    if limits is None:
        limits = _json(here / "limits" / f"{workload}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved and _applies(m, workload)]
    readers = {m["name"]: load_reader(m["name"], here) for m in per_layer}
    return Cell(workload, int(w["chips"]), config, traffic, limits, e2e,
                per_layer, readers, load_reference(config["reference"], here))
