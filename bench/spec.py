"""Finds the benchmark's pieces by name.

``BENCHMARK.json`` at the checkout's root names the cells; each cell's
configuration, traffic mix and per-layer metrics live in files of their
own under ``bench/``:

* ``bench/configs/<config>.json`` — the configuration as it is run, and
  ``bench/configs/<config>.py`` beside it — its plain reference
  (``init``, ``forward``, ``layers``);
* ``bench/traffic/<traffic>.json`` — the parameters the one traffic
  generator (``traffic.py``) reads;
* ``bench/metrics/<metric>.py`` — a reader ``read(ctx)`` of one per-layer
  metric, returning ``None`` when there is nothing to read.

A later change adds a cell, a mix or a metric by adding such files and
``BENCHMARK.json`` entries, without editing anything that is here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _load_module(path: Path, tag: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{tag}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in bm['workloads']]})")


def metrics_of(bm: dict, kind: str, cell: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports:
    those that list it under ``workloads``, and those that list none."""
    return [m for m in bm[kind]
            if "workloads" not in m or cell in m["workloads"]]


def config(name: str) -> dict:
    return _load_json(BENCH / "configs" / f"{name}.json")


def reference(name: str):
    return _load_module(BENCH / "configs" / f"{name}.py", "ref")


def traffic(name: str) -> dict:
    return _load_json(BENCH / "traffic" / f"{name}.json")


def metric_reader(name: str):
    return _load_module(BENCH / "metrics" / f"{name}.py", "metric").read
