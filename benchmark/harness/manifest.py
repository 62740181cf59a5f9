"""Find a cell's files by the names in BENCHMARK.json.

Nothing here lists cells, configurations, traffic mixes or metrics: a
cell's configuration is ``configs/<config>.json`` (or the ``file`` its
entry names), its traffic or job ``traffic/<traffic>.json``, and a
per-layer metric's reader ``layer_metrics/<name>.py``.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def manifest() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(man: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")


def config_of(man: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in man["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(ROOT, c["file"]))
    raise SystemExit(f"benchmark: no config {name!r} in BENCHMARK.json")


def traffic_of(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(BENCH_DIR, "traffic", name + ".json"))


def metrics_of(man: Dict[str, Any], group: str, cell_name: str,
               reported: List[str]) -> List[Dict[str, Any]]:
    """The metrics of ``group`` that this cell reports: those that list
    it under ``workloads`` and, of those that list nothing, the ones
    whose end-to-end metric (``moves``, or the metric itself) the cell
    reports."""
    out = []
    for m in man[group]:
        cells = m.get("workloads")
        if cells is not None:
            if cell_name in cells:
                out.append(m)
        elif m.get("moves", m["name"]) in reported or m["name"] == "setup_s":
            out.append(m)
    return out


def end_to_end_names(man: Dict[str, Any], cell_name: str) -> List[str]:
    return [m["name"] for m in man["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def load_reader(name: str):
    """The reader module of one per-layer metric, by the metric's name."""
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: per-layer metric {name!r} has no "
                         f"reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(kind: str):
    """Model construction is the one place code branches: one module
    per configuration ``kind`` under ``harness/kinds``."""
    path = os.path.join(BENCH_DIR, "harness", "kinds", kind + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: configuration kind {kind!r} has no "
                         f"builder at {path}")
    return importlib.import_module("harness.kinds." + kind)
