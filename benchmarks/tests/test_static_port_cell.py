"""The cell ``mock-10k-static.bulk``: in the manifest beside
``mock-10k-net.bulk``, a deployment module that meets
``DEPLOYMENT_API``, correct through ``run.py`` at the dry run's size on
the CPU backend (its control not), its five port guarantees each able
to come out not ``correct``, and the rows its comparison walks in
commit order.
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

CELL = "mock-10k-static.bulk"
PORT_CHECKS = ("port_collisions_on_one_node", "dynamic_ports_out_of_range",
               "allocs_missing_their_network", "static_port_not_the_ask",
               "nodes_over_bandwidth")


def _config():
    return manifest.shrunk(manifest.load_cell(CELL))


def test_the_module_meets_the_deployment_api():
    cell = manifest.load_cell(CELL)
    dep = manifest.load_deployment(cell.config)
    assert dep.__name__ == "benchmarks.deployments.static_ports"
    for name in manifest.DEPLOYMENT_API:
        assert callable(getattr(dep, name)), name
    config = _config()
    jids = dep.backlog_ids(config, 4300000017)
    assert len(jids) == config["jobs"]["jobs"] == 24
    assert [dep.static_value(config, j) for j in jids[:9]] == [
        8889, 8890, 8891, 8892, 8893, 8894, 8895, 8896, 8889]
    job = dep.make_job(config, jids[3])
    (nr,) = job.task_groups[0].tasks[0].resources.networks
    assert [(p.label, p.value) for p in nr.reserved_ports] == [("lb", 8892)]
    assert sorted(p.label for p in nr.dynamic_ports) == ["admin", "http"]
    assert nr.mbits == 50


def test_the_cell_is_in_the_manifest_beside_the_network_cell():
    m = manifest.load_manifest()
    (w,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == ("mock-10k-static",
                                                       "bulk", 1)
    (c,) = [c for c in m["configs"] if c["name"] == "mock-10k-static"]
    assert c["file"] == "benchmarks/configs/mock-10k-static.json"
    assert c["reduced"] == ["jobs"]
    # In every list that names mock-10k-net.bulk, and in no other.
    lists = [e for e in m["end_to_end"] + m["per_layer"] if "workloads" in e]
    assert {e["name"] for e in lists if CELL in e["workloads"]} == {
        e["name"] for e in lists if "mock-10k-net.bulk" in e["workloads"]}
    cell = manifest.load_cell(CELL)
    assert {e["name"] for e in cell.end_to_end} == {"placed_per_s", "setup_s"}
    net = manifest.load_cell("mock-10k-net.bulk")
    assert {e["name"] for e in cell.per_layer} == {
        e["name"] for e in net.per_layer}
    cfg, plain = cell.config, net.config
    assert cfg["deployment"] == "static_ports"
    assert cfg["jobs"]["task"]["network"]["static_port"] == {
        "label": "lb", "value": 8889, "values": 8}
    # mock-10k-net's fleet, body, limits and guarantees, the static port
    # added.
    assert cfg["cluster"] == plain["cluster"]
    assert cfg["server"] == plain["server"]
    assert cfg["limits"]["score_gap"] == plain["limits"]["score_gap"]
    assert cfg["limits"]["score_sum_rel"] == plain["limits"]["score_sum_rel"]
    task = {k: v for k, v in cfg["jobs"]["task"].items() if k != "network"}
    assert task == {k: v for k, v in plain["jobs"]["task"].items()
                    if k != "network"}
    assert {k: v for k, v in cfg["jobs"]["task"]["network"].items()
            if k != "static_port"} == plain["jobs"]["task"]["network"]
    assert set(plain["guarantees"]) < set(cfg["guarantees"])
    assert "static_ports" in cfg["guarantees"]


def test_dry_run_of_the_cell_is_correct_and_its_control_is_not():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/run.py"), "--workload", CELL,
         "--seed", "4300000011", "--seconds", "20", "--dry-run-cpu",
         "--control", "sampled"],
        cwd=str(ROOT), capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "\ncorrect: True" in proc.stderr
    assert "control sampled correct: False" in proc.stderr
    compared = dict(re.findall(r"^compared (\w+): (\S+) ", proc.stderr, re.M))
    for name in PORT_CHECKS + ("infeasible_allocs", "nodes_over_capacity",
                               "evals_wrong_count",
                               "job_mates_in_one_distinct_group"):
        assert compared[name] == "0", name
    assert float(compared["score_gap"]) == 0.0
    layers = json.loads(re.search(r"^counts: (.*)$", proc.stderr,
                                  re.M).group(1))["layers"]
    assert layers["compiles_in_window.tput"] == 0.0
    assert layers["net_offer_failures_per_batch.net"] == 0.0


def _sound(config):
    """The dry-size backlog placed as the reference places it, each
    allocation holding its job's static value, two dynamic ports of its
    node's own and 50 Mbit."""
    dep = manifest.load_deployment(config)
    cap = dep.capacity(config)
    task = config["jobs"]["task"]
    ask = np.asarray([task["cpu"], task["memory_mb"],
                      task["ephemeral_disk_mb"], task["network"]["mbits"]],
                     dtype=np.float64)
    n = cap.shape[0]
    used = np.zeros_like(cap)
    held = {}
    next_port = {}
    jobs = []
    for jid in dep.backlog_ids(config, 1):
        value = dep.static_value(config, jid)
        taken = held.setdefault(value, np.zeros(n, dtype=bool))
        (nodes,) = reference.greedy(cap, [ask], [dep.wants(config, jid)],
                                    used0=used, feasible=[~taken],
                                    distinct=[np.arange(n)])
        np.add.at(used, nodes, ask)
        taken[nodes] = True
        dynamic = []
        for node in nodes.tolist():
            p = next_port.get(node, 20000)
            next_port[node] = p + 2
            dynamic.append([p, p + 1])
        jobs.append(dep.StaticPlacedJob(
            jid, ask, nodes, mbits=np.full(len(nodes), ask[3]),
            ports=[[value] + d for d in dynamic],
            whole=np.ones(len(nodes), dtype=bool), asked=value,
            dynamic=dynamic, static=np.full(len(nodes), value)))
    return dep, check.Served(jobs=jobs)


def _plant(fault, served):
    """One allocation of the last job made wrong in one way."""
    job = served.jobs[-1]
    ports = [list(p) for p in job.ports]
    if fault == "shared_lb":
        # Onto a node where an earlier job holds the same static value.
        node = next(int(n) for other in served.jobs[:-1]
                    if other.asked == job.asked for n in other.nodes
                    if n not in job.nodes)
        nodes = job.nodes.copy()
        nodes[0] = node
        job = replace(job, nodes=nodes)
    elif fault == "wrong_lb":
        static = job.static.copy()
        static[0] = job.asked + 1
        ports[0][0] = job.asked + 1
        job = replace(job, static=static, ports=ports)
    elif fault == "dynamic_out_of_range":
        dynamic = [list(d) for d in job.dynamic]
        dynamic[0][0] = 19999
        ports[0][1] = 19999
        job = replace(job, dynamic=dynamic, ports=ports)
    elif fault == "missing_label":
        whole = job.whole.copy()
        whole[0] = False
        job = replace(job, whole=whole)
    elif fault == "bandwidth_overcommit":
        mbits = job.mbits.copy()
        mbits[0] = 2000.0
        job = replace(job, mbits=mbits)
    served.jobs[-1] = job
    return served


@pytest.mark.parametrize("fault,caught_by", [
    (None, set()),
    ("shared_lb", {"port_collisions_on_one_node", "infeasible_allocs"}),
    ("wrong_lb", {"static_port_not_the_ask"}),
    ("dynamic_out_of_range", {"dynamic_ports_out_of_range"}),
    ("missing_label", {"allocs_missing_their_network"}),
    ("bandwidth_overcommit", {"nodes_over_bandwidth"}),
])
def test_a_planted_port_fault_is_not_correct(fault, caught_by):
    config = _config()
    dep, served = _sound(config)
    compared = dep.compare(_plant(fault, served), config)
    over = {n for n, v in compared.items() if v["value"] > v["limit"]}
    assert check.correct(compared) == (fault is None), over
    # A moved allocation also moves the scores; every port check that
    # catches it is named.
    assert over & set(PORT_CHECKS + ("infeasible_allocs",)) == caught_by


def test_the_rows_follow_commit_order():
    """A job's feasible row leaves out every node on which an earlier
    job's allocation (or the node's reservation) holds its static value,
    and no other; its distinct row is the node index."""
    config = _config()
    dep, served = _sound(config)
    jobs = dep.with_rows(config, served.jobs)
    n = config["cluster"]["nodes"]
    for k, job in enumerate(jobs):
        held = np.zeros(n, dtype=bool)
        for earlier in jobs[:k]:
            for node, ports in zip(earlier.nodes.tolist(), earlier.ports):
                held[node] |= job.asked in ports
        np.testing.assert_array_equal(job.feasible, ~held)
        np.testing.assert_array_equal(job.distinct, np.arange(n))
    # The same value asked again: the second job's row excludes the
    # first's nodes.
    first, again = jobs[0], jobs[8]
    assert first.asked == again.asked
    assert not again.feasible[first.nodes].any()
    # A value the nodes reserve is held everywhere.
    reserved = replace(served.jobs[0], asked=22)
    (row,) = [j.feasible for j in dep.with_rows(config, [reserved])]
    assert not row.any()
