"""Discovery by name: the benchmark's cells, configurations, traffic
mixes, limits and per-layer metric readers.

Everything that belongs to one configuration, traffic mix or metric is
a file of its own, so that a later change adds a cell with new files and
new entries in ``BENCHMARK.json`` and edits nothing:

- ``BENCHMARK.json`` (the checkout's root): cells, and each
  configuration's file;
- ``traffic/<mix>.json``: one traffic mix, read by ``traffic.py``;
- ``limits/<cell>.json``: the limits of the comparison that decides
  ``correct`` in that cell;
- ``metrics/<metric>.py``: a ``read(record)`` that returns the metric's
  value, or ``None`` where the run holds nothing to read.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    """``BENCHMARK.json`` of the checkout at ``root``."""
    return load_json(root / "BENCHMARK.json")


def _one(entries: list, name: str, what: str) -> dict:
    hits = [e for e in entries if e["name"] == name]
    if len(hits) != 1:
        known = ", ".join(e["name"] for e in entries)
        raise KeyError(f"no {what} named {name!r} (known: {known})")
    return hits[0]


def workload(bench: dict, name: str) -> dict:
    return _one(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    """The configuration file of the configuration ``name``."""
    return load_json(root / _one(bench["configs"], name,
                                 "configuration")["file"])


def traffic(name: str, base: pathlib.Path = HERE) -> dict:
    return load_json(base / "traffic" / f"{name}.json")


def limits(cell: str, base: pathlib.Path = HERE) -> dict:
    return load_json(base / "limits" / f"{cell}.json")


def load_module(name: str, base: pathlib.Path = HERE):
    """The module ``metrics/<name>.py``."""
    path = base / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str, base: pathlib.Path = HERE):
    """The ``read`` function of ``metrics/<name>.py``."""
    return load_module(name, base).read


def metrics_for(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports:
    those without a ``workloads`` key and those that list it."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]
