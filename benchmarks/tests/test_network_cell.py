"""The cell ``mock-10k-net.bulk``: in the manifest as ISSUE 41 gives it,
correct through ``run.py`` at the dry run's size on the CPU backend, and
its network guarantees each able to come out not ``correct``.
``python -m pytest benchmarks/tests -q``; nothing here is a device
number."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import check, manifest, reference  # noqa: E402

CELL = "mock-10k-net.bulk"
# Two of ISSUE 41's four: ``BENCHMARK.json`` holds at most 128 per-layer
# metrics. The other two counters are held by tier-1 tests.
NET_METRICS = {"net_offers_ms_per_batch.net",
               "net_offer_failures_per_batch.net"}
NET_CHECKS = ("port_collisions_on_one_node", "dynamic_ports_out_of_range",
              "allocs_missing_their_network", "nodes_over_bandwidth")


def test_the_cell_is_in_the_manifest_as_the_issue_gives_it():
    m = manifest.load_manifest()
    (w,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == ("mock-10k-net",
                                                       "bulk", 1)
    (c,) = [c for c in m["configs"] if c["name"] == "mock-10k-net"]
    assert c["file"] == "benchmarks/configs/mock-10k-net.json"
    assert c["reduced"] == ["jobs"]
    cell = manifest.load_cell(CELL)
    assert cell.deployment.__name__ == "benchmarks.deployments.network"
    assert {e["name"] for e in cell.end_to_end} == {"placed_per_s", "setup_s"}
    names = {spec["name"] for spec in cell.per_layer}
    standing = {e["name"] for e in m["per_layer"]
                if e["name"].endswith(".tput")}
    assert standing | NET_METRICS == names
    for name in ("compiles_in_window.tput", "oracle_routed_evals.tput",
                 "device_busy_ms_per_batch.tput", "placement_roofline.tput"):
        assert name in names
    cfg = cell.config
    plain = manifest.load_config(m, "mock-10k")
    assert cfg["cluster"]["nodes"] == 10000
    assert cfg["jobs"]["jobs"] == 5000 and cfg["jobs"]["group_count"] == 10
    task = cfg["jobs"]["task"]
    assert task["network"] == {"mbits": 50,
                               "dynamic_ports": ["http", "admin"]}
    assert [s["port_label"] for s in task["services"]] == ["http", "admin"]
    # mock-10k's fleet and job body, the ask added; its limits.
    assert {k: v for k, v in task.items()
            if k not in ("network", "services")} == plain["jobs"]["task"]
    assert cfg["limits"]["score_gap"] == plain["limits"]["score_gap"]
    assert cfg["limits"]["score_sum_rel"] == plain["limits"]["score_sum_rel"]
    assert cfg["server"] == plain["server"]


def test_dry_run_of_the_cell_is_correct():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/run.py"), "--workload", CELL,
         "--seed", "4100000011", "--seconds", "20", "--dry-run-cpu"],
        cwd=str(ROOT), capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "correct: True" in proc.stderr
    compared = dict(re.findall(r"^compared (\w+): (\S+) ", proc.stderr, re.M))
    for name in NET_CHECKS + ("infeasible_allocs", "nodes_over_capacity",
                              "evals_wrong_count"):
        assert compared[name] == "0", name
    assert float(compared["score_gap"]) == 0.0
    layers = json.loads(re.search(r"^counts: (.*)$", proc.stderr,
                                  re.M).group(1))["layers"]
    assert layers["compiles_in_window.tput"] == 0.0
    assert layers["net_offer_failures_per_batch.net"] == 0.0
    assert layers["net_offers_ms_per_batch.net"] > 0


def _sound(config):
    """Every job of the dry-size backlog placed as the reference places
    it, each allocation holding two ports of its node's own and 50 Mbit."""
    dep = manifest.load_deployment(config)
    cap = dep.capacity(config)
    task = config["jobs"]["task"]
    ask = np.asarray([task["cpu"], task["memory_mb"],
                      task["ephemeral_disk_mb"], task["network"]["mbits"]],
                     dtype=np.float64)
    jids = dep.backlog_ids(config, 1)
    count = dep.wants(config, jids[0])
    placed = reference.greedy(cap, [ask] * len(jids), [count] * len(jids))
    next_port = {}
    jobs = []
    for jid, nodes in zip(jids, placed):
        ports = []
        for node in nodes.tolist():
            p = next_port.get(node, 20000)
            next_port[node] = p + 2
            ports.append([p, p + 1])
        jobs.append(dep.NetPlacedJob(
            jid, ask, nodes, mbits=np.full(len(nodes), ask[3]), ports=ports,
            whole=np.ones(len(nodes), dtype=bool)))
    return dep, check.Served(jobs=jobs)


def _plant(fault, served):
    job = served.jobs[-1]
    if fault == "port_collision":
        # The port an earlier job's allocation holds on the same node.
        node = int(job.nodes[0])
        taken = next(p for other in served.jobs[:-1]
                     for n, p in zip(other.nodes.tolist(), other.ports)
                     if n == node)
        ports = [list(p) for p in job.ports]
        ports[0][1] = taken[0]
        job = replace(job, ports=ports)
    elif fault == "bandwidth_overcommit":
        mbits = job.mbits.copy()
        mbits[0] = 2000.0
        job = replace(job, mbits=mbits)
    served.jobs[-1] = job
    return served


@pytest.mark.parametrize("fault,caught_by", [
    (None, None),
    ("port_collision", "port_collisions_on_one_node"),
    ("bandwidth_overcommit", "nodes_over_bandwidth"),
])
def test_a_planted_network_fault_is_not_correct(fault, caught_by):
    cell = manifest.load_cell(CELL)
    config = manifest.shrunk(cell)
    dep, served = _sound(config)
    compared = dep.compare(_plant(fault, served), config)
    over = {n for n, v in compared.items() if v["value"] > v["limit"]}
    if fault is None:
        assert check.correct(compared), over
    else:
        assert not check.correct(compared)
        assert over == {caught_by}, over


def test_a_program_without_the_mirror_is_refused_at_once_on_a_tpu(
        monkeypatch):
    """The parent's with-network program hangs compiling on a fleet in
    use (PERF.md section 7, row 1): on a TPU the deployment refuses such
    a program before the first node registers, so that the run exits 2
    at once; on the CPU backend it runs (the dry run above)."""
    import jax

    from nomad_tpu.ops import resident

    cell = manifest.load_cell(CELL)
    config = manifest.shrunk(cell)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert len(cell.deployment.make_nodes(config)) == 400
    monkeypatch.delattr(resident, "NET_DIMS")
    with pytest.raises(manifest.ManifestError, match="network mirror"):
        cell.deployment.make_nodes(config)
