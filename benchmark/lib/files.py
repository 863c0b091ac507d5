"""Find a cell, its configuration, its traffic mix and its per-layer
metrics by NAME, and the code they name the same way: an architecture
(``architectures/<name>.py``), a traffic kind (``kinds/<name>.py``), the
modules of reducers (``reducers/*.py``). Nothing else in the harness knows
a file path: a later PR adds a cell, a model family, a kernel's roofline
or a loop by adding files here and entries in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib
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


def known_modules(directory: str) -> list[str]:
    return sorted(p.stem for p in (ROOT / directory).glob("*.py")
                  if not p.stem.startswith("_"))


def load_module(directory: str, name, what: str):
    """The module ``<directory>/<name>.py`` (``benchmark/`` is on
    ``sys.path``, so it imports as ``<directory>.<name>``); an unknown
    name is an error that lists the known ones."""
    known = known_modules(directory)
    if name not in known:
        raise BenchmarkFileError(
            f"{what} names {directory[:-1]} {name!r}: there is no "
            f"benchmark/{directory}/{name}.py (known: {known})")
    return importlib.import_module(f"{directory}.{name}")


def load_cell(name: str) -> dict:
    """The cell with its files, and the code they name: ``arch`` (the
    configuration's ``architecture``) and ``job`` (the traffic's ``kind``).
    Both are found, or the error raised, before any device work; so is a
    ``check`` key the architecture declares (``CHECK_KEYS``) and the
    configuration lacks."""
    cell = _load("cells", name)
    cell["name"] = name
    cell["config_file"] = load_config(cell["config"])
    cell["traffic_file"] = load_traffic(cell["traffic"])
    cell["arch"] = load_module(
        "architectures", cell["config_file"].get("architecture"),
        f"configuration {cell['config']}")
    cell["job"] = load_module("kinds", cell["traffic_file"].get("kind"),
                              f"traffic mix {cell['traffic']}")
    demanded = tuple(getattr(cell["arch"], "CHECK_KEYS", ()))
    lacking = [key for key in demanded
               if key not in cell["config_file"].get("check", {})]
    if lacking:
        raise BenchmarkFileError(
            f"architecture {cell['config_file']['architecture']!r} demands "
            f"{lacking} of the 'check' of configuration {cell['config']}, "
            f"which lacks it (its reference returns a mask; it demands: "
            f"{list(demanded)})")
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
