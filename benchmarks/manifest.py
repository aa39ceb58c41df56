"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; a configuration is
``benchmarks/configs/<config>.json`` (or the ``file`` the manifest
gives), a mix is ``benchmarks/traffic/<traffic>.json`` and a per-layer
metric is ``benchmarks/metrics/<name>.json``.  A later PR adds a
deployment, a mix or a metric by adding files and manifest entries; no
file here is edited for it.

What the fleet and the jobs look like, and what a right answer is, the
harness knows only through the configuration's deployment module: the
file's ``"deployment": "<name>"`` is ``benchmarks/deployments/<name>.py``,
and a file that names none gets ``uniform`` (one node shape x one job
shape: ``c1m-5k`` and ``mock-10k``).  ``run.py``, ``client.py`` and
``check.py`` call the module for everything in ``DEPLOYMENT_API`` and know
no key of ``cluster`` or ``jobs`` themselves.

To add a deployment: (1) ``benchmarks/deployments/<name>.py`` with every
function of ``DEPLOYMENT_API`` (import what serves from ``uniform`` or
``check``; the constraint or port evaluator is the module's own: numpy
and the standard library, nothing of ``nomad_tpu`` but the structs that
``make_nodes`` and ``make_job`` build); (2) its configuration file with
``"deployment": "<name>"``, its ``limits`` and what it ``assumed``; (3) in
``BENCHMARK.json`` an entry under ``configs``, a cell under
``workloads``, and the cell's name appended to the ``workloads`` list of
every end-to-end and per-layer metric it reports that carries such a
list (``placed_per_s`` and the ``.tput`` metrics for a standing backlog,
``submit_to_placed_p50_ms`` and the ``.lat`` ones for an open loop).
``benchmarks/tests/test_benchmarks.py`` does exactly that to a copy of the
tree with the test deployment under ``tests/data``.
"""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import List

# What a deployment module provides; each is given the configuration (the
# file's content, shrunk in a dry run).
DEPLOYMENT_API = (
    "make_nodes",     # (config) -> the program's Node structs, in device order
    "node_indices",   # (config, node ids) -> int64 array, -1 = not of the fleet
    "backlog_ids",    # (config, seed) -> job ids of a standing backlog
    "request_id",     # (config, kind, i, seed) -> i-th job id, kind warm | req
    "make_job",       # (config, job id) -> the program's Job struct
    "wants",          # (config, job id) -> allocations a complete eval leaves
    "placed_job",     # (config, job id, node indices, alloc rows) -> PlacedJob
    "compare",        # (served, config) -> {name: {"value", "limit"}}, over
                      # the module's own capacity ([N, D], rows may differ)
    "control_jobs",   # (config, served jobs, seed) -> the control's PlacedJobs
    "shrink",         # (config) -> its fleet and jobs cut to a tiny size
                      # (--dry-run-cpu); ``server`` stays as the file has it
)

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
    deployment: ModuleType      # the configuration's deployment module


def load_manifest(root: Path = ROOT) -> dict:
    return _load(root / "BENCHMARK.json")


def load_config(manifest: dict, name: str, root: Path = ROOT) -> dict:
    for entry in manifest["configs"]:
        if entry["name"] == name:
            return _load(root / entry["file"])
    raise ManifestError(f"configuration {name!r} is not in BENCHMARK.json")


def load_deployment(config: dict, here: Path = HERE) -> ModuleType:
    """The configuration's deployment module, held to ``DEPLOYMENT_API``.
    Importing one starts no JAX backend and touches nothing of the
    program."""
    name = config.get("deployment", "uniform")
    whose = f"configuration {config.get('name')!r}"
    path = here / "deployments" / f"{name}.py"
    if not (isinstance(name, str) and name.isidentifier() and path.is_file()):
        raise ManifestError(f"{whose} names deployment {name!r}: no {path}")
    try:
        module = importlib.import_module(f"benchmarks.deployments.{name}")
    except Exception as exc:
        raise ManifestError(f"{whose}: deployment {name!r} does not import: "
                            f"{exc!r}") from exc
    missing = [f for f in DEPLOYMENT_API
               if not callable(getattr(module, f, None))]
    if missing:
        raise ManifestError(f"{whose}: deployment {name!r} lacks "
                            + ", ".join(missing))
    return module


def shrunk(cell: "Cell") -> dict:
    """A copy of the cell's configuration at the dry run's size.  The
    deployment module cuts its fleet and its jobs; how the server runs
    (``server``) is the configuration file's to say, so a ``shrink`` that
    changes it is refused."""
    config = json.loads(json.dumps(cell.config))
    server = json.dumps(config.get("server"), sort_keys=True)
    config = cell.deployment.shrink(config)
    if json.dumps(config.get("server"), sort_keys=True) != server:
        raise ManifestError(
            f"configuration {config.get('name')!r}: its deployment's shrink "
            f"changed `server` (was {server}); a dry run cuts the fleet and "
            f"the jobs, not how the server runs")
    return config


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
    config = load_config(manifest, w["config"], root)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=load_traffic(w["traffic"], here),
                end_to_end=e2e, per_layer=layer,
                deployment=load_deployment(config, here))
