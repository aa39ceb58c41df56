"""``uniform``'s fleet and job with the job made whole: upstream's
``mock.Job()`` asks its task's network for bandwidth and two dynamic
ports, and names them in two services (``mock-10k-net``).

Every node is ``cluster.node`` (``mock.Node()``: eth0 at 1,000 Mbit on a
single-IP CIDR, 1 Mbit and port 22 reserved); every job is ``uniform``'s
body plus ``jobs.task.network`` (the Mbit and the dynamic port labels) and
``jobs.task.services``.  What places is compared as ``uniform`` compares
it, over a fourth capacity dimension, the bandwidth, which is a hard
dimension and is not scored (v0.6 ``ScoreFit`` scores cpu and memory).
The ports are held to the configuration's guarantees by exact checks of
this module's own: numpy and the standard library, nothing of the program.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Sequence

import numpy as np

from benchmarks import check, manifest, reference
from benchmarks.deployments import uniform
from benchmarks.deployments.uniform import (  # noqa: F401
    backlog_ids, node_indices, request_id, shrink, wants)


@dataclass
class NetPlacedJob(reference.PlacedJob):
    """A ``PlacedJob`` with what each allocation's network holds: its
    Mbit, its ports' values, and whether it carries exactly the ask (its
    labelled ports and its Mbit)."""
    mbits: np.ndarray = None                      # [count] float64
    ports: List[List[int]] = field(default_factory=list)
    whole: np.ndarray = None                      # [count] bool


def _needs_the_resident_network_mirror() -> None:
    """On a TPU, a with-network batch on a program without the resident
    network mirror (``nomad_tpu.ops.resident.NET_DIMS``) compiles one
    program per power of two of the nodes in use and did not finish one
    in 900 s (PERF.md section 7, row 1): such a program cannot run this
    deployment, and the run says so at once instead of hanging."""
    import jax

    from nomad_tpu.ops import resident

    if (jax.default_backend() == "tpu"
            and getattr(resident, "NET_DIMS", None) is None):
        raise manifest.ManifestError(
            "deployment 'network' needs the device program's resident "
            "network mirror (nomad_tpu.ops.resident.NET_DIMS), which this "
            "checkout lacks: its with-network program grows with the "
            "fleet's usage and does not compile on the TPU in the window")


def make_nodes(config: dict) -> List:
    _needs_the_resident_network_mirror()
    return uniform.make_nodes(config)


def make_job(config: dict, jid: str):
    """``uniform``'s body, its task asking ``jobs.task.network`` and
    carrying ``jobs.task.services``, which name the ask's ports."""
    from nomad_tpu.structs import structs as s

    job = uniform.make_job(config, jid)
    t = config["jobs"]["task"]
    net = t["network"]
    task = job.task_groups[0].tasks[0]
    task.resources.networks = [s.NetworkResource(
        mbits=net["mbits"],
        dynamic_ports=[s.Port(label) for label in net["dynamic_ports"]])]
    task.services = [
        s.Service(name=sv["name"], port_label=sv["port_label"],
                  tags=list(sv.get("tags", [])),
                  checks=[s.ServiceCheck(
                      name=c["name"], type=c["type"], command=c["command"],
                      args=list(c.get("args", [])),
                      interval=c["interval_s"], timeout=c["timeout_s"])
                      for c in sv.get("checks", [])])
        for sv in t["services"]]
    return job


def _network_of(row):
    """(Mbit, [(label, value)]) over the allocation's task networks."""
    mbits, ports = 0, []
    for tr in (row.task_resources or {}).values():
        for nr in tr.networks or []:
            mbits += nr.mbits
            ports += [(p.label, p.value) for p in nr.reserved_ports]
            ports += [(p.label, p.value) for p in nr.dynamic_ports]
    return mbits, ports


def placed_job(config: dict, jid: str, nodes: np.ndarray,
               rows: Sequence) -> NetPlacedJob:
    """The ask as committed, over cpu, memory, disk and bandwidth, and
    every allocation's Mbit and port values from its own row."""
    net = config["jobs"]["task"]["network"]
    want = sorted(net["dynamic_ports"])
    res = rows[0].resources
    held = [_network_of(r) for r in rows]
    ask = np.asarray([res.cpu, res.memory_mb, res.disk_mb, held[0][0]],
                     dtype=np.float64)
    return NetPlacedJob(
        jid, ask, nodes,
        mbits=np.asarray([m for m, _ in held], dtype=np.float64),
        ports=[[v for _, v in p] for _, p in held],
        whole=np.asarray([m == net["mbits"]
                          and sorted(lb for lb, _ in p) == want
                          for m, p in held], dtype=bool))


def capacity(config: dict) -> np.ndarray:
    """[N, 4] usable capacity per node: ``uniform``'s three dimensions and
    the bandwidth less its reservation."""
    nd = config["cluster"]["node"]
    bw = nd["network"]["mbits"] - nd["network"]["reserved_mbits"]
    cap = uniform.capacity(config)
    return np.concatenate([cap, np.full((cap.shape[0], 1), float(bw))],
                          axis=1)


def ports_held(config: dict, jobs: Sequence[NetPlacedJob]
               ) -> Dict[str, int]:
    """The network guarantees, counted straight from the served
    allocations: ports held twice on one node (the node's reserved ports
    count as held), dynamic ports outside the dynamic range, allocations
    without exactly the ask's labelled ports and Mbit, nodes whose
    allocations' Mbit exceed the usable bandwidth."""
    c = config["cluster"]
    net = c["node"]["network"]
    lo, hi = net["dynamic_range"]
    reserved = [value for _, value in net["reserved_ports"]]
    n = c["nodes"]
    mbits = np.zeros(n, dtype=np.float64)
    by_node: Dict[int, List[int]] = {}
    out_of_range = missing = 0
    for job in jobs:
        np.add.at(mbits, job.nodes, job.mbits)
        missing += int((~job.whole).sum())
        for node, ports in zip(job.nodes.tolist(), job.ports):
            by_node.setdefault(node, list(reserved)).extend(ports)
            out_of_range += sum(1 for v in ports if not lo <= v < hi)
    collisions = sum(len(ports) - len(set(ports))
                     for ports in by_node.values())
    usable = net["mbits"] - net["reserved_mbits"]
    return {"port_collisions_on_one_node": collisions,
            "dynamic_ports_out_of_range": out_of_range,
            "allocs_missing_their_network": missing,
            "nodes_over_bandwidth": int((mbits > usable).sum())}


def compare(served: check.Served, config: dict) -> Dict[str, Dict[str, float]]:
    """``check.compare`` over the four dimensions, every standing name
    kept, and the network guarantees as exact checks of their own."""
    out = check.compare(served, capacity(config), config["limits"])
    for name, value in ports_held(config, served.jobs).items():
        out[name] = {"value": value, "limit": 0}
    return out


def control_jobs(config: dict, served_jobs: Sequence[NetPlacedJob],
                 seed: int) -> List[NetPlacedJob]:
    return check.control_jobs(capacity(config), served_jobs, seed)
