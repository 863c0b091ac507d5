"""Find a cell, its configuration, its traffic mix and its per-layer
metrics by NAME. Nothing else in the harness knows a file path: a later
PR adds a cell by adding files here and entries in ``BENCHMARK.json``.
"""

from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]          # benchmark/
CHECKOUT = ROOT.parent


class BenchmarkFileError(ValueError):
    pass


def _load(kind: str, name: str) -> dict:
    path = ROOT / kind / f"{name}.json"
    if not path.is_file():
        known = sorted(p.stem for p in (ROOT / kind).glob("*.json"))
        raise BenchmarkFileError(
            f"no {kind[:-1] if kind.endswith('s') else kind} named "
            f"{name!r}: {path} does not exist (known: {known})")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    cell = _load("cells", name)
    cell["name"] = name
    cell["config_file"] = load_config(cell["config"])
    cell["traffic_file"] = load_traffic(cell["traffic"])
    return cell


def load_config(name: str) -> dict:
    return _load("configs", name)


def load_traffic(name: str) -> dict:
    return _load("traffic", name)


def load_layer_metric(name: str) -> dict:
    m = _load("layer_metrics", name)
    m["name"] = name
    return m


def benchmark_json() -> dict:
    with open(CHECKOUT / "BENCHMARK.json") as f:
        return json.load(f)
