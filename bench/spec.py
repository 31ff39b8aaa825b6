"""``BENCHMARK.json`` and the files it names, found by name.

A cell (``workloads`` entry) names a configuration and a traffic mix.
The configuration's file is the one its ``configs`` entry gives; the mix
is ``bench/traffic/<traffic>.json``; a per-layer metric ``<name>`` is
read by ``bench/metrics/<name>.py``, whose ``read(ctx)`` returns a
number or None.  Adding any of them takes files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """BENCHMARK.json, or a file it names, is missing or inconsistent."""


def load(root: Path = ROOT) -> dict:
    """BENCHMARK.json of the tree at root."""
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no {path}")
    return json.loads(path.read_text())


def cell(bm: dict, name: str) -> dict:
    """The workloads entry named name."""
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(bm: dict, name: str, root: Path = ROOT) -> dict:
    """The file of configuration name."""
    for c in bm["configs"]:
        if c["name"] == name:
            return json.loads((Path(root) / c["file"]).read_text())
    raise SpecError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    """bench/traffic/<name>.json."""
    path = Path(root) / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic file {path}")
    return json.loads(path.read_text())


def metrics_for(bm: dict, section: str, cell_name: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in bm[section]
            if cell_name in m.get("workloads", [cell_name])]


def reader(name: str, root: Path = ROOT):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no metric reader {path}")
    sp = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read
