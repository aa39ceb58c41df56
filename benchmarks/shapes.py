"""Nodes and jobs of a configuration file, as the program's own structs.

Both processes build them from the same file, so the client's job
bodies and the server's node table agree without a word passed.
"""
from __future__ import annotations

from typing import List


def node_id(i: int) -> str:
    return f"node-{i:05d}"


def job_id(kind: str, i: int) -> str:
    return f"{kind}-{i:05d}"


def make_nodes(config: dict) -> List:
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


def make_job(config: dict, jid: str, group_count: int):
    """The job body as the configuration file gives it: what places (count,
    ask, constraints, ephemeral disk) and what only rides the record (env,
    meta, restart policy), which register, raft append and fsync carry."""
    from nomad_tpu.structs import structs as s

    j, t = config["jobs"], config["jobs"]["task"]
    g = j.get("group", {})
    restart = g.get("restart_policy")
    group = s.TaskGroup(
        name=g.get("name", "tg"), count=group_count,
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
