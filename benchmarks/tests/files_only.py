"""A copy of the tree with the test deployment (``data/pools3.py`` and
``data/pools3.json``) added to it the way a later PR adds a deployment:
new files and manifest entries, no file that is there edited.

``python benchmarks/tests/files_only.py <dir> [--batch-size N] [run.py's
arguments]`` builds the copy in ``<dir>`` and runs its
``benchmarks/run.py`` on the cell ``pools3.bulk`` there; the tests call
``build`` themselves.  ``--batch-size`` is written into the copy's new
configuration file (``server.batch_size``; the file under ``data`` says
64): how the server runs is the configuration file's to say, never the
module's.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import sys
from pathlib import Path
from typing import Dict, Optional

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELL = "pools3.bulk"


def digests(root: Path) -> Dict[str, str]:
    """sha256 of every file under ``root`` but the manifest, by its path."""
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and p.name != "BENCHMARK.json"}


def copy_tree(dst: Path) -> Dict[str, str]:
    """The benchmark and the program as committed, and their digests."""
    junk = shutil.ignore_patterns("__pycache__", "*.pyc", ".pytest_cache")
    for part in ("benchmarks", "nomad_tpu"):
        shutil.copytree(ROOT / part, dst / part, ignore=junk)
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    return digests(dst)


def add_pools3(root: Path, batch_size: Optional[int] = None) -> None:
    """The deployment's module, its configuration file and the manifest's
    entries: a configuration, a cell, and the cell's name on the
    ``workloads`` list of every metric the standing-backlog cell reports."""
    shutil.copy(DATA / "pools3.py", root / "benchmarks/deployments/pools3.py")
    config = json.loads((DATA / "pools3.json").read_text())
    if batch_size is not None:
        config["server"]["batch_size"] = batch_size
    (root / "benchmarks/configs/pools3.json").write_text(
        json.dumps(config, indent=1))
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({
        "name": "pools3", "source": "a test deployment, not a cell",
        "file": "benchmarks/configs/pools3.json", "reduced": [],
        "why": "three node pools, three job templates, a version "
               "constraint, distinct_hosts, distinct_property"})
    m["workloads"].append({
        "name": CELL, "config": "pools3", "traffic": "bulk", "chips": 1,
        "why": "the proof that a deployment is added as files only"})
    for entry in m["end_to_end"] + m["per_layer"]:
        if "c1m-5k.bulk" in entry.get("workloads", ()):
            entry["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(m, indent=1))


def build(dst: Path, batch_size: Optional[int] = None) -> Dict[str, str]:
    """The copy with the deployment added; returns the digests of what
    was there before it was."""
    before = copy_tree(dst)
    add_pools3(dst, batch_size)
    return before


def main(argv) -> int:
    dst, rest, batch_size = Path(argv[1]).resolve(), argv[2:], None
    if rest[:1] == ["--batch-size"]:
        batch_size, rest = int(rest[1]), rest[2:]
    if dst.exists():
        shutil.rmtree(dst)
    before = build(dst, batch_size)
    after = digests(dst)
    edited = [p for p in before if after.get(p) != before[p]]
    added = sorted(set(after) - set(before))
    print(f"files_only: {len(before)} files copied, {len(edited)} edited, "
          f"added {added}", file=sys.stderr, flush=True)
    if edited:
        return 1
    run = str(dst / "benchmarks/run.py")
    os.chdir(dst)
    os.execv(sys.executable, [sys.executable, run, "--workload", CELL,
                              *rest])


if __name__ == "__main__":
    sys.exit(main(sys.argv))
