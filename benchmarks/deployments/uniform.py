"""The deployment of one node shape x one job shape: every node a copy of
``cluster.node``, every job ``jobs.task`` at ``jobs.group_count``, with
job-level constraints that hold on every node.  It is ``c1m-5k``'s and
``mock-10k``'s, and what a configuration gets that names no deployment.

A deployment module is everything the harness knows of what the fleet and
the jobs look like and of what a right answer is; ``manifest.py`` lists
what one has to provide.  Both processes build nodes and jobs from the
same configuration file and the same seed, so the client's job bodies and
the server's node table agree without a word passed.  Nothing of the
program is imported until a node or a job body is built.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from benchmarks import check, reference

DRY = {"nodes": 400, "jobs": 24, "group_count": 10}


def node_id(i: int) -> str:
    return f"node-{i:05d}"


def job_id(kind: str, i: int) -> str:
    return f"{kind}-{i:05d}"


def make_nodes(config: dict) -> List:
    """The nodes, in device order: the order they are registered in."""
    from nomad_tpu.structs import structs as s

    c = config["cluster"]
    nd, rv = c["node"], c["node"]["reserved"]
    net = nd.get("network")
    networks, reserved_networks = [], []
    if net:
        networks = [s.NetworkResource(device=net["device"], cidr=net["cidr"],
                                      mbits=net["mbits"])]
        reserved_networks = [s.NetworkResource(
            device=net["device"], ip=net["ip"], mbits=net["reserved_mbits"],
            reserved_ports=[s.Port(label, value)
                            for label, value in net["reserved_ports"]])]
    base = s.Node(
        id=node_id(0), datacenter=c["datacenter"], name=node_id(0),
        attributes=dict(nd["attributes"]),
        links=dict(nd.get("links", {})), meta=dict(nd.get("meta", {})),
        resources=s.Resources(cpu=nd["cpu"], memory_mb=nd["memory_mb"],
                              disk_mb=nd["disk_mb"], iops=nd["iops"],
                              networks=networks),
        reserved=s.Resources(cpu=rv["cpu"], memory_mb=rv["memory_mb"],
                             disk_mb=rv["disk_mb"],
                             networks=reserved_networks),
        node_class=nd["node_class"], status=s.NODE_STATUS_READY)
    base.compute_class()
    nodes = []
    for i in range(c["nodes"]):
        node = base.copy()
        node.id = node.name = node_id(i)
        nodes.append(node)
    return nodes


def node_indices(config: dict, node_ids: Sequence[str]) -> np.ndarray:
    """The device's node index of each id; -1 for an id not of the fleet."""
    idx = np.fromiter((int(nid[5:]) for nid in node_ids), dtype=np.int64,
                      count=len(node_ids))
    idx[(idx < 0) | (idx >= config["cluster"]["nodes"])] = -1
    return idx


def backlog_ids(config: dict, seed: int) -> List[str]:
    """A standing backlog's jobs, in the order they are registered."""
    return [job_id("job", i) for i in range(int(config["jobs"]["jobs"]))]


def request_id(config: dict, kind: str, i: int, seed: int) -> str:
    """The i-th job of an open loop (``req``) or of its warm-up (``warm``)."""
    return job_id(kind, i)


def wants(config: dict, jid: str) -> int:
    """The allocations a complete evaluation of the job leaves running."""
    return int(config["jobs"]["group_count"])


def make_job(config: dict, jid: str):
    """The job body as the configuration file gives it: what places (count,
    ask, constraints, ephemeral disk) and what only rides the record (env,
    meta, restart policy), which register, raft append and fsync carry."""
    from nomad_tpu.structs import structs as s

    j, t = config["jobs"], config["jobs"]["task"]
    g = j.get("group", {})
    restart = g.get("restart_policy")
    group = s.TaskGroup(
        name=g.get("name", "tg"), count=wants(config, jid),
        ephemeral_disk=s.EphemeralDisk(size_mb=t["ephemeral_disk_mb"]),
        tasks=[s.Task(
            name=t.get("name", "t"), driver=t["driver"],
            config={"command": "/bin/date"},
            env=dict(t.get("env", {})), meta=dict(t.get("meta", {})),
            resources=s.Resources(cpu=t["cpu"], memory_mb=t["memory_mb"]),
            log_config=s.LogConfig())],
        meta=dict(g.get("meta", {})))
    if restart:
        group.restart_policy = s.RestartPolicy(
            attempts=restart["attempts"], interval=restart["interval_s"],
            delay=restart["delay_s"], mode=restart["mode"])
    job = s.Job(
        region="global", id=jid, name=jid, type=j["type"],
        priority=j["priority"], datacenters=[config["cluster"]["datacenter"]],
        constraints=[s.Constraint(lt, rt, op)
                     for lt, op, rt in j["constraints"]],
        task_groups=[group], meta=dict(j.get("meta", {})))
    job.canonicalize()
    return job


def placed_job(config: dict, jid: str, nodes: np.ndarray,
               rows: Sequence) -> reference.PlacedJob:
    """One complete job's served allocations as plain arrays: the ask as
    the program committed it and the node index of every allocation."""
    res = rows[0].resources
    ask = np.asarray([res.cpu, res.memory_mb, res.disk_mb], dtype=np.float64)
    return reference.PlacedJob(jid, ask, nodes)


def capacity(config: dict) -> np.ndarray:
    """[N, 3] usable capacity per node (cpu, memory, disk): resources
    minus reservation."""
    c = config["cluster"]
    nd, rv = c["node"], c["node"]["reserved"]
    row = [nd["cpu"] - rv["cpu"], nd["memory_mb"] - rv["memory_mb"],
           nd["disk_mb"] - rv["disk_mb"]]
    return np.tile(np.asarray(row, dtype=np.float64), (c["nodes"], 1))


def compare(served: check.Served, config: dict) -> Dict[str, Dict[str, float]]:
    return check.compare(served, capacity(config), config["limits"])


def control_jobs(config: dict, served_jobs: Sequence[reference.PlacedJob],
                 seed: int) -> List[reference.PlacedJob]:
    return check.control_jobs(capacity(config), served_jobs, seed)


def shrink(config: dict) -> dict:
    """The configuration cut to a tiny size, for ``--dry-run-cpu``."""
    config["cluster"]["nodes"] = DRY["nodes"]
    config["jobs"]["group_count"] = DRY["group_count"]
    if config["jobs"]["jobs"]:
        config["jobs"]["jobs"] = DRY["jobs"]
    return config
