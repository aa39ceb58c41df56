"""The cell ``constrained-5k.bulk`` through ``run.py`` at the dry run's
size on the CPU backend, sound and with each of its constraint guarantees
broken underneath.  ``python -m pytest benchmarks/tests -q``; nothing here
is a device number."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

from benchmarks import manifest  # noqa: E402

CELL = "constrained-5k.bulk"


def _run(*argv):
    return subprocess.run(
        [sys.executable, *argv], cwd=str(ROOT), capture_output=True,
        text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))


def _compared(stderr: str) -> dict:
    return dict(re.findall(r"^compared (\w+): (\S+) ", stderr, re.M))


def test_the_cell_is_in_the_manifest_as_the_issue_gives_it():
    m = manifest.load_manifest()
    (w,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == ("constrained-5k",
                                                       "bulk", 1)
    cell = manifest.load_cell(CELL)
    assert cell.deployment.__name__ == "benchmarks.deployments.constrained"
    names = {spec["name"] for spec in cell.per_layer}
    standing = {e["name"] for e in m["per_layer"]
                if "c1m-5k.bulk" in e.get("workloads", ())}
    assert standing <= names                 # every .tput metric
    assert {n for n in names if n.endswith(".cons")} == {
        "constraint_rows_ms_per_batch.cons",
        "host_constraint_rows_per_batch.cons",
        "multi_round_specs_per_batch.cons", "spec_passes_per_batch.cons",
        "device_rounds_per_batch.cons"}
    assert {e["name"] for e in cell.end_to_end} == {"placed_per_s", "setup_s"}
    c = cell.config
    assert c["cluster"]["nodes"] == 5000 and c["cluster"]["racks"] == 125
    tpl = c["jobs"]["templates"]
    per_20 = {t: c["jobs"]["mix"].count(t) for t in tpl}
    assert per_20 == {"web": 4, "api": 4, "db": 4, "batch": 3, "cache": 5}
    tasks = sum(per_20[t] * (c["jobs"]["jobs"] // 20) * tpl[t]["count"]
                for t in tpl)
    assert c["jobs"]["jobs"] == 2000 and tasks == 50000
    assert c["server"]["batch_size"] == 64 and c["reduced"] == []
    assert c["limits"]["score_gap"] == 0.01
    # Between its sound readings (at most 0.022) and the control's (0.083
    # and more), not the contract's 0.005: PERF.md section 6, PR 29.
    assert 0.022 < c["limits"]["score_sum_rel"] < 0.083


def test_dry_run_of_the_cell_is_correct():
    proc = _run(str(ROOT / "benchmarks/run.py"), "--workload", CELL,
                "--seed", "2900000011", "--seconds", "3", "--dry-run-cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "correct: True" in proc.stderr
    assert proc.stdout.strip() == ""          # never a result line
    compared = _compared(proc.stderr)
    assert float(compared["score_gap"]) == 0.0
    for name in ("allocs_on_excluded_nodes",
                 "distinct_hosts_mates_on_one_node",
                 "distinct_property_mates_on_one_rack", "infeasible_allocs",
                 "job_mates_on_one_node", "job_mates_in_one_distinct_group",
                 "nodes_over_capacity", "evals_wrong_count"):
        assert compared[name] == "0", name
    assert float(compared["oracle_routed"]) == 0.0
    assert "score_sum_rel" in compared
    counts = json.loads(re.search(r"^counts: (.*)$", proc.stderr,
                                  re.M).group(1))
    layers = counts["layers"]
    assert layers["oracle_routed_evals.tput"] == 0.0
    assert layers["spec_passes_per_batch.cons"] >= layers[
        "evals_per_batch.tput"] > 0
    assert layers["host_constraint_rows_per_batch.cons"] > 0
    assert layers["device_rounds_per_batch.cons"] >= 1.0


@pytest.mark.parametrize("script,fault,caught_by", [
    ("faulty_run.py", "constraint_broken", "allocs_on_excluded_nodes"),
    ("faulty_run.py", "mates_on_one_node",
     "distinct_hosts_mates_on_one_node"),
    ("faulty_rack.py", None, "distinct_property_mates_on_one_rack"),
])
def test_a_planted_fault_on_each_constraint_guarantee_is_caught(
        script, fault, caught_by):
    """An allocation moved to a node a constraint excludes; two job-mates
    on one node under ``distinct_hosts``; two in one rack under
    ``distinct_property`` (on a node that is otherwise legal)."""
    argv = [str(HERE / script)] + ([fault] if fault else []) + [CELL]
    proc = _run(*argv)
    assert proc.returncode == 1, proc.stderr[-3000:]
    assert "correct: False" in proc.stderr
    over = [ln.split()[1].rstrip(":") for ln in proc.stderr.splitlines()
            if "<-- OVER" in ln]
    assert caught_by in over, over
    if script == "faulty_rack.py":
        # Only the rack guarantee (and the choice it spoils) can see it.
        assert set(over) <= {
            caught_by, "job_mates_in_one_distinct_group", "score_gap",
            "score_sum_rel"}, over
