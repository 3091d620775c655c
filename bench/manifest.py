"""Find a cell's configuration, traffic mix and metric readers by name.

A later change adds a cell, a configuration, a mix or a metric by adding a
file and an entry in ``BENCHMARK.json``; nothing here names one of them.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path


def load_manifest(root: Path) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    known = sorted(e["name"] for e in entries)
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json (known: {known})")


def resolve_cell(root: Path, manifest: dict, workload: str) -> dict:
    """The cell's entry with its configuration and traffic files loaded,
    and the metric entries it reports under each ``--trace`` setting."""
    cell = _by_name(manifest["workloads"], workload, "workload")
    cfg_entry = _by_name(manifest["configs"], cell["config"], "config")
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)

    def reported(metrics: list) -> list:
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": reported(manifest["end_to_end"]),
            "per_layer": reported(manifest["per_layer"])}


def load_reader(root: Path, metric: str):
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    if spec is None or spec.loader is None:
        raise SystemExit(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
