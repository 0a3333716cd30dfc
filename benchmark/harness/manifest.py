"""Finds a cell's files by the names in BENCHMARK.json, and refuses a name
it cannot find with a message that says which file it looked for.

    BENCHMARK.json                      the manifest
    benchmark/configs/<config>.json     one model configuration
    benchmark/traffic/<traffic>.json    one job or traffic mix
    benchmark/cells/<workload>.json     what belongs to the cell alone:
                                        engine or mesh settings, and the
                                        sizes its compile showed
    benchmark/layer_metrics/<name>.py   one per-layer metric: read(ctx)

A later PR adds entries and files; it edits none that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_KINDS = ("train", "serve_closed", "serve_open")


class ManifestError(ValueError):
    """BENCHMARK.json, or a file it names, is missing or malformed."""


def check_name(name: Any, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(
            f"{what} {name!r} is not a name: at most 64 letters, digits, "
            "'_', '.' and '-', starting with a letter, a digit or '_'")
    return name


def check_unit(unit: Any, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ManifestError(
            f"{what} has the unit {unit!r}: a unit is 1 to 16 letters, "
            "digits, '_', '/', '%', '.' and '-', with no space")
    return unit


def load_json(path: str, what: str) -> Dict[str, Any]:
    if not os.path.isfile(path):
        raise ManifestError(
            f"{what}: looked for {os.path.relpath(path, ROOT)} and did "
            "not find it")
    with open(path) as f:
        try:
            obj = json.load(f)
        except json.JSONDecodeError as e:
            raise ManifestError(
                f"{what}: {os.path.relpath(path, ROOT)} is not JSON: {e}"
            ) from None
    if not isinstance(obj, dict):
        raise ManifestError(
            f"{what}: {os.path.relpath(path, ROOT)} is not a JSON object")
    return obj


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    bound: Optional[float] = None       # end-to-end only
    layer: Optional[str] = None         # per-layer only
    moves: Optional[str] = None         # per-layer only
    workloads: Optional[tuple] = None   # None: every cell

    def reported_by(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files its names lead to."""
    name: str
    config_name: str
    traffic_name: str
    chips: int
    why: str
    config: Dict[str, Any]              # benchmark/configs/<config>.json
    traffic: Dict[str, Any]             # benchmark/traffic/<traffic>.json
    settings: Dict[str, Any]            # benchmark/cells/<name>.json
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _metric(entry: Dict[str, Any], per_layer: bool) -> Metric:
    name = check_name(entry.get("name"), "metric")
    unit = check_unit(entry.get("unit"), f"metric {name!r}")
    if entry.get("better") not in ("lower", "higher"):
        raise ManifestError(f"metric {name!r}: 'better' is "
                            f"{entry.get('better')!r}, not lower or higher")
    if entry.get("source") not in SOURCES:
        raise ManifestError(f"metric {name!r}: source "
                            f"{entry.get('source')!r} is none of {SOURCES}")
    cells = entry.get("workloads")
    return Metric(name=name, unit=unit, better=entry["better"],
                  source=entry["source"], bound=entry.get("bound"),
                  layer=entry.get("layer") if per_layer else None,
                  moves=entry.get("moves") if per_layer else None,
                  workloads=tuple(cells) if cells is not None else None)


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"), "the manifest")


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload`` with its configuration, traffic and
    settings read, and the metrics it reports."""
    check_name(workload, "workload")
    manifest = load_manifest(root)
    entries = {w["name"]: w for w in manifest.get("workloads", [])}
    if workload not in entries:
        raise ManifestError(
            f"BENCHMARK.json has no workload {workload!r} "
            f"(it has: {', '.join(sorted(entries))})")
    w = entries[workload]
    config_name = check_name(w.get("config"), "config")
    traffic_name = check_name(w.get("traffic"), "traffic")
    if w.get("chips") not in (1, 4):
        raise ManifestError(f"workload {workload!r}: chips is "
                            f"{w.get('chips')!r}, not 1 or 4")
    configs = {c["name"]: c for c in manifest.get("configs", [])}
    if config_name not in configs:
        raise ManifestError(
            f"workload {workload!r} names the config {config_name!r}, "
            "which BENCHMARK.json's configs do not list")
    config = load_json(os.path.join(root, configs[config_name]["file"]),
                       f"config {config_name!r}")
    traffic = load_json(
        os.path.join(root, "benchmark", "traffic", traffic_name + ".json"),
        f"traffic {traffic_name!r}")
    if traffic.get("kind") not in TRAFFIC_KINDS:
        raise ManifestError(
            f"traffic {traffic_name!r}: kind {traffic.get('kind')!r} is "
            f"none of {TRAFFIC_KINDS}")
    settings = load_json(
        os.path.join(root, "benchmark", "cells", workload + ".json"),
        f"the settings of cell {workload!r}")
    e2e = [m for m in (_metric(e, False)
                       for e in manifest.get("end_to_end", []))
           if m.reported_by(workload)]
    names = {m.name for m in e2e}
    layer = []
    for e in manifest.get("per_layer", []):
        m = _metric(e, True)
        if not m.reported_by(workload):
            continue
        if m.moves not in names:
            raise ManifestError(
                f"per-layer metric {m.name!r} moves {m.moves!r}, which "
                f"cell {workload!r} does not report")
        layer.append(m)
    return Cell(name=workload, config_name=config_name,
                traffic_name=traffic_name, chips=w["chips"],
                why=w.get("why", ""), config=config, traffic=traffic,
                settings=settings, end_to_end=e2e, per_layer=layer)


def load_reader(metric: str, root: str = ROOT) -> Callable:
    """``read(ctx)`` of benchmark/layer_metrics/<metric>.py."""
    check_name(metric, "per-layer metric")
    path = os.path.join(root, "benchmark", "layer_metrics", metric + ".py")
    if not os.path.isfile(path):
        raise ManifestError(
            f"per-layer metric {metric!r}: looked for its reader "
            f"{os.path.relpath(path, root)} and did not find it")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise ManifestError(
            f"{os.path.relpath(path, root)} defines no read(ctx)")
    return mod.read
