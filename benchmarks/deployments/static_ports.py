"""``network``'s fleet and job with a static port beside the dynamic ones
(``mock-10k-static``): the task's network asks ``jobs.task.network
.static_port`` as well, the v0.6 network stanza's ``port "lb" { static =
8889 }``, at one of a few values chosen by the job's index.

Every node is ``network``'s; every job is ``network``'s body with the
static port in its task network's reserved ports.  A static port is held
once per node, so it binds where it is held: a job's feasible nodes are
those on which no earlier job's allocation (and no node reservation)
holds its value, and its allocations go one per node.  What places is
compared as ``network`` compares it, over those rows, walked in commit
order from the served allocations; the ports are held to the
configuration's guarantees by exact checks of this module's own: numpy
and the standard library, nothing of the program.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, List, Sequence

import numpy as np

from benchmarks import check, reference
from benchmarks.deployments import network
from benchmarks.deployments.network import capacity, make_nodes  # noqa: F401
from benchmarks.deployments.uniform import (  # noqa: F401
    backlog_ids, node_indices, request_id, shrink, wants)


@dataclass
class StaticPlacedJob(network.NetPlacedJob):
    """A ``NetPlacedJob`` with the static value the job asks, each
    allocation's dynamic port values apart, and the value its static
    label holds (-1 where it holds none)."""
    asked: int = -1
    dynamic: List[List[int]] = field(default_factory=list)
    static: np.ndarray = None                     # [count] int64


def static_value(config: dict, jid: str) -> int:
    """The static port job ``jid`` asks: the first value plus the job's
    index modulo the number of values."""
    sp = config["jobs"]["task"]["network"]["static_port"]
    return int(sp["value"]) + int(jid.rsplit("-", 1)[1]) % int(sp["values"])


def make_job(config: dict, jid: str):
    """``network``'s body, its task network asking the static port too."""
    from nomad_tpu.structs import structs as s

    job = network.make_job(config, jid)
    label = config["jobs"]["task"]["network"]["static_port"]["label"]
    nr = job.task_groups[0].tasks[0].resources.networks[0]
    nr.reserved_ports = [s.Port(label, static_value(config, jid))]
    return job


def _held(row):
    """(Mbit, [(label, value)] reserved, [(label, value)] dynamic) over
    the allocation's task networks."""
    mbits, reserved, dynamic = 0, [], []
    for tr in (row.task_resources or {}).values():
        for nr in tr.networks or []:
            mbits += nr.mbits
            reserved += [(p.label, p.value) for p in nr.reserved_ports]
            dynamic += [(p.label, p.value) for p in nr.dynamic_ports]
    return mbits, reserved, dynamic


def placed_job(config: dict, jid: str, nodes: np.ndarray,
               rows: Sequence) -> StaticPlacedJob:
    """The ask as committed, over cpu, memory, disk and bandwidth, and
    every allocation's Mbit and port values from its own row, its static
    label's value kept apart from its dynamic ports."""
    net = config["jobs"]["task"]["network"]
    label = net["static_port"]["label"]
    want = (sorted(net["dynamic_ports"]), [label])
    res = rows[0].resources
    held = [_held(r) for r in rows]
    ask = np.asarray([res.cpu, res.memory_mb, res.disk_mb, held[0][0]],
                     dtype=np.float64)
    return StaticPlacedJob(
        jid, ask, nodes,
        mbits=np.asarray([m for m, _, _ in held], dtype=np.float64),
        ports=[[v for _, v in r + d] for _, r, d in held],
        whole=np.asarray([m == net["mbits"]
                          and (sorted(lb for lb, _ in d),
                               [lb for lb, _ in r]) == want
                          for m, r, d in held], dtype=bool),
        asked=static_value(config, jid),
        dynamic=[[v for _, v in d] for _, _, d in held],
        static=np.asarray([next((v for lb, v in r if lb == label), -1)
                           for _, r, _ in held], dtype=np.int64))


def _reserved(config: dict, values) -> Dict[int, np.ndarray]:
    """{value: [N] bool} held by the nodes' own reservations."""
    n = config["cluster"]["nodes"]
    reserved = {value for _, value in
                config["cluster"]["node"]["network"]["reserved_ports"]}
    return {v: np.full(n, v in reserved) for v in values}


def with_rows(config: dict, jobs: Sequence[StaticPlacedJob]
              ) -> List[StaticPlacedJob]:
    """The jobs, in commit order, each with its rows: feasible where no
    earlier job's allocation and no node reservation holds its static
    value, distinct by node (a second allocation on a node would hold the
    value twice)."""
    held = _reserved(config, {j.asked for j in jobs})
    hosts = np.arange(config["cluster"]["nodes"])
    out = []
    for job in jobs:
        out.append(replace(job, feasible=~held[job.asked], distinct=hosts))
        for node, ports in zip(job.nodes.tolist(), job.ports):
            for v in ports:
                if v in held:
                    held[v][node] = True
    return out


def ports_held(config: dict, jobs: Sequence[StaticPlacedJob]
               ) -> Dict[str, int]:
    """The network guarantees, counted straight from the served
    allocations: ports held twice on one node (any value, the node's
    reserved ports included), dynamic ports outside the dynamic range,
    allocations without exactly the ask's labelled ports and Mbit, static
    ports that are not the job's value, nodes whose allocations' Mbit
    exceed the usable bandwidth."""
    c = config["cluster"]
    net = c["node"]["network"]
    lo, hi = net["dynamic_range"]
    reserved = [value for _, value in net["reserved_ports"]]
    mbits = np.zeros(c["nodes"], dtype=np.float64)
    by_node: Dict[int, List[int]] = {}
    out_of_range = missing = not_the_ask = 0
    for job in jobs:
        np.add.at(mbits, job.nodes, job.mbits)
        missing += int((~job.whole).sum())
        not_the_ask += int((job.static != job.asked).sum())
        for node, ports in zip(job.nodes.tolist(), job.ports):
            by_node.setdefault(node, list(reserved)).extend(ports)
        out_of_range += sum(1 for ports in job.dynamic for v in ports
                            if not lo <= v < hi)
    collisions = sum(len(ports) - len(set(ports))
                     for ports in by_node.values())
    usable = net["mbits"] - net["reserved_mbits"]
    return {"port_collisions_on_one_node": collisions,
            "dynamic_ports_out_of_range": out_of_range,
            "allocs_missing_their_network": missing,
            "static_port_not_the_ask": not_the_ask,
            "nodes_over_bandwidth": int((mbits > usable).sum())}


def twin_used(config: dict, cap: np.ndarray,
              jobs: Sequence[StaticPlacedJob]) -> np.ndarray:
    """[N, D] usage after the reference has placed the same jobs itself,
    in the same order, each static value held where the twin put it: the
    rows the served allocations give are the program's, and the twin's
    own placements (ties broken by node index) hold other nodes."""
    held = _reserved(config, {j.asked for j in jobs})
    hosts = np.arange(cap.shape[0])
    used = np.zeros_like(cap)
    for job in jobs:
        (nodes,) = reference.greedy(
            cap, [job.ask], [len(job.nodes)], used0=used,
            feasible=[~held[job.asked]], distinct=[hosts])
        np.add.at(used, nodes, job.ask)
        held[job.asked][nodes] = True
    return used


def compare(served: check.Served, config: dict) -> Dict[str, Dict[str, float]]:
    """``check.compare`` over ``network``'s four dimensions and each job's
    rows, every standing name kept, ``score_sum_rel`` against a twin that
    holds its own ports, and the network guarantees as exact checks of
    their own."""
    served = replace(served, jobs=with_rows(config, served.jobs))
    cap = capacity(config)
    out = check.compare(served, cap, config["limits"])
    got = np.zeros_like(cap)
    for job in served.jobs:
        np.add.at(got, job.nodes, job.ask)
    got_sum = reference.scorefit_sum(got, cap)
    ref_sum = reference.scorefit_sum(twin_used(config, cap, served.jobs), cap)
    out["score_sum_rel"]["value"] = abs(got_sum - ref_sum) / max(ref_sum,
                                                                 1e-12)
    for name, value in ports_held(config, served.jobs).items():
        out[name] = {"value": value, "limit": 0}
    return out


def control_jobs(config: dict, served_jobs: Sequence[StaticPlacedJob],
                 seed: int) -> List[StaticPlacedJob]:
    return check.control_jobs(capacity(config),
                              with_rows(config, served_jobs), seed)
