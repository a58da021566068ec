"""``BENCHMARK.json`` and the files it names, found by name.

A cell ``<config>.<traffic>`` reads ``bench/configs/<config>.json`` (the
file its configuration entry names) and ``bench/traffic/<traffic>.json``;
a per-layer metric ``<name>`` is read by ``bench/metrics/<name>.py``,
which defines ``read(ctx) -> float | None``. Adding a cell, a mix or a
metric adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    """One workload with everything the harness reads for it."""

    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list  # metric entries that this cell reports with --trace 0
    per_layer: list  # metric entries that this cell reports with --trace 1


def load(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(spec: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``; raises KeyError for an unknown name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    config = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(root / config["file"]) as f:
        cfg = json.load(f)
    with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
        mix = json.load(f)
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    return Cell(workload, int(w["chips"]), cfg, mix, e2e, layer)


def reader(name: str):
    """The ``read`` function of per-layer metric ``name``."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
