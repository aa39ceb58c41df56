"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; a configuration is
``benchmarks/configs/<config>.json`` (or the ``file`` the manifest
gives), a mix is ``benchmarks/traffic/<traffic>.json`` and a per-layer
metric is ``benchmarks/metrics/<name>.json``.  A later PR adds a
deployment, a mix or a metric by adding files and manifest entries; no
file here is edited for it.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class ManifestError(Exception):
    pass


def _load(path: Path) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise ManifestError(f"no such file: {path}") from None
    except json.JSONDecodeError as exc:
        raise ManifestError(f"{path}: {exc}") from None


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # manifest entries reported in this cell
    per_layer: List[dict]       # manifest entries merged with their files


def load_manifest(root: Path = ROOT) -> dict:
    return _load(root / "BENCHMARK.json")


def load_config(manifest: dict, name: str, root: Path = ROOT) -> dict:
    for entry in manifest["configs"]:
        if entry["name"] == name:
            return _load(root / entry["file"])
    raise ManifestError(f"configuration {name!r} is not in BENCHMARK.json")


def load_traffic(name: str, here: Path = HERE) -> dict:
    return _load(here / "traffic" / f"{name}.json")


def load_metric(name: str, here: Path = HERE) -> dict:
    return _load(here / "metrics" / f"{name}.json")


def _in_cell(entry: dict, cell: str, reports: set) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    moves = entry.get("moves")
    return moves is None or moves in reports


def load_cell(name: str, root: Path = ROOT, here: Path = HERE) -> Cell:
    manifest = load_manifest(root)
    for w in manifest["workloads"]:
        if w["name"] == name:
            break
    else:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise ManifestError(f"workload {name!r} is not in BENCHMARK.json "
                            f"(known: {known})")
    e2e = [m for m in manifest["end_to_end"] if _in_cell(m, name, set())]
    reports = {m["name"] for m in e2e}
    layer = []
    for m in manifest["per_layer"]:
        if _in_cell(m, name, reports):
            layer.append({**load_metric(m["name"], here), **m})
    return Cell(name=name, chips=int(w["chips"]),
                config=load_config(manifest, w["config"], root),
                traffic=load_traffic(w["traffic"], here),
                end_to_end=e2e, per_layer=layer)
