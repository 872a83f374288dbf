"""What the benchmark is made of, found by name.

``BENCHMARK.json`` (repo root) names cells, configurations and metrics;
everything that belongs to one of them lives in a file of its own:

    benchmark/configs/<config>.json    sizes, guarantees, driver by name
    benchmark/traffic/<traffic>.json   generator by name and its parameters
    benchmark/metrics/<metric>.json    reader by name and its arguments
    benchmark/drivers/<driver>.py      run(env) -> Outcome
    benchmark/generators/<name>.py     seeded inputs
    benchmark/readers/<reader>.py      read(artefacts, **args) -> float | None

A later PR adds files and ``BENCHMARK.json`` entries; nothing here is
edited to make room for them.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import re
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_json(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = ROOT) -> dict:
    """The ``workloads`` entry called ``name`` with its configuration
    entry, configuration file and traffic file loaded beside it."""
    bench = benchmark_json(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(
            f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    entry = dict(cells[name])
    configs = {c["name"]: c for c in bench["configs"]}
    entry["config_entry"] = configs[entry["config"]]
    entry["config_file"] = _load(
        os.path.join(root, entry["config_entry"]["file"]))
    entry["traffic_file"] = _load(os.path.join(
        root, "benchmark", "traffic", entry["traffic"] + ".json"))
    return entry


def metrics_for(cell_name: str, kind: str, root: str = ROOT) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    out = []
    for m in benchmark_json(root)[kind]:
        if "workloads" not in m or cell_name in m["workloads"]:
            out.append(m)
    return out


def metric_file(name: str, root: str = ROOT) -> dict:
    return _load(os.path.join(root, "benchmark", "metrics", name + ".json"))


def load_module(kind: str, name: str):
    """``benchmark.<kind>.<name>`` — drivers, generators, readers."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    return importlib.import_module(f"benchmark.{kind}.{name}")


def listing(root: str = ROOT) -> Dict[str, List[str]]:
    """Every file the harness would find, by directory, and which of
    them ``BENCHMARK.json`` names — ``run.py --list`` prints it."""
    bench = benchmark_json(root)

    def stems(sub: str, ext: str) -> List[str]:
        pat = os.path.join(root, "benchmark", sub, "*" + ext)
        return sorted(
            os.path.basename(p)[: -len(ext)] for p in glob.glob(pat)
            if not os.path.basename(p).startswith("_"))

    return {
        "cells": [w["name"] for w in bench["workloads"]],
        "configs": stems("configs", ".json"),
        "configs_in_manifest": [c["name"] for c in bench["configs"]],
        "traffic": stems("traffic", ".json"),
        "traffic_in_manifest": sorted(
            {w["traffic"] for w in bench["workloads"]}),
        "metrics": stems("metrics", ".json"),
        "metrics_in_manifest": [m["name"] for m in bench["per_layer"]],
        "drivers": stems("drivers", ".py"),
        "generators": stems("generators", ".py"),
        "readers": stems("readers", ".py"),
    }
