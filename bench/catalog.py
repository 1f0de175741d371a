"""Find a cell's pieces by name.

``BENCHMARK.json`` names the cells; everything that belongs to one
configuration, one traffic mix, one cell's limits or one per-layer metric is
a file of its own under ``bench/``, found by that name:

  configs/<config>.json   sizes of a configuration, as ``file`` in BENCHMARK.json
  traffic/<traffic>.json  a traffic mix's parameters
  limits/<workload>.json  the limits of a cell's correctness comparison
  metrics/<metric>.py     a per-layer metric's reader, ``read(ctx)``

A later change adds a cell, a mix or a metric by adding files and entries;
none of these lookups changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def workload(bm: dict, name: str) -> dict:
    return _by_name(bm["workloads"], name, "workload")


def config(bm: dict, name: str, root: Path = ROOT) -> dict:
    entry = _by_name(bm["configs"], name, "configuration")
    with open(Path(root) / entry["file"]) as f:
        return json.load(f)


def _json(bench: Path, sub: str, name: str) -> dict:
    with open(Path(bench) / sub / f"{name}.json") as f:
        return json.load(f)


def traffic(name: str, bench: Path = BENCH) -> dict:
    return _json(bench, "traffic", name)


def limits(workload_name: str, bench: Path = BENCH) -> dict:
    return _json(bench, "limits", workload_name)


def metrics_of(bm: dict, workload_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in bm[kind]
            if workload_name in m.get("workloads", [workload_name])]


def reader(metric: str, bench: Path = BENCH):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = Path(bench) / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path
    )
    if spec is None:
        raise KeyError(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
