"""BENCHMARK.json and the files it names, found by name.

A cell's configuration is the file its ``configs`` entry names; its
traffic is ``bench/traffic/<traffic>.json``; the limits of its
correctness check are ``bench/limits/<workload>.json``; each per-layer
metric is read by ``bench/metrics/<metric>.py``. A later cell, mix or
metric is new files and new entries, never an edit of these.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Optional[dict]
    end_to_end: List[dict]
    per_layer: List[dict]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(workload: str, root: str = ROOT) -> Cell:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _json(os.path.join(root, conf["file"]))
    traffic = _json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    lpath = os.path.join(BENCH, "limits", workload + ".json")
    limits = _json(lpath) if os.path.exists(lpath) else None
    return Cell(workload, w["chips"], config, traffic, limits,
                [m for m in spec["end_to_end"] if _applies(m, workload)],
                [m for m in spec["per_layer"] if _applies(m, workload)])


def reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def peaks(kind: str) -> dict:
    """The chip's published peaks, by ``device_kind``; unknown is an
    error, never a default."""
    table = _json(os.path.join(BENCH, "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][kind]
