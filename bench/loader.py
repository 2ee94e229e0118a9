"""Finds a cell's parts by the names ``BENCHMARK.json`` gives them.

* configuration ``<name>``: ``configs/<name>.json``
* traffic mix ``<name>``: ``traffic/<name>.json``
* metric ``<name>``: ``metrics/<name>.py``, whose ``read(run)`` returns
  the value, or ``None`` where the run holds nothing to read.

A new cell, model or metric is therefore a new file and a new entry;
no existing file changes.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bm: dict, name: str) -> dict:
    for cell in bm["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, base: str = HERE) -> dict:
    with open(os.path.join(base, "configs", f"{name}.json")) as f:
        return json.load(f)


def traffic(name: str, base: str = HERE) -> dict:
    with open(os.path.join(base, "traffic", f"{name}.json")) as f:
        return json.load(f)


def metric_reader(name: str, base: str = HERE):
    path = os.path.join(base, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bm: dict, cell: str, traced: bool) -> list:
    """The metric entries a run of ``cell`` reports: the end-to-end ones
    untraced, the per-layer ones traced.  An entry without ``workloads``
    belongs to every cell that reports the metric it moves."""
    e2e = [m for m in bm["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in reported
                             else [])]
