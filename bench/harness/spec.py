"""`BENCHMARK.json` and the files it names, found by name.

A cell names a configuration (its file is given in `configs`) and a
traffic mix (`traffic/<mix>.json`); a per-layer metric is read by
`layers/<metric>.py`, which defines ``read(run) -> float | None``.
Adding a cell, configuration, mix or metric adds files and entries and
edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the mix file's contents
    end_to_end: list      # metric entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(cell_name: str) -> Cell:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"bench: no workload {cell_name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[cell_name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    return Cell(cell_name, w["chips"], config, traffic,
                [m for m in bench["end_to_end"] if _reports(m, cell_name)],
                [m for m in bench["per_layer"] if _reports(m, cell_name)])


def reader(metric: str):
    """The `read` function of `bench/layers/<metric>.py`."""
    path = os.path.join(BENCH, "layers", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_layer_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
