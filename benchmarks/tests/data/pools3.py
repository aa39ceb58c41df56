"""A test deployment, not a cell: the proof that a deployment which is not
one node shape x one job shape goes into the harness as files only.

Node pools that differ in capacity, ``node_class``, attributes and meta,
interleaved in the fixed order ``cluster.order``; every node also carries
``meta.rack``, one of ``cluster.racks`` values by its block of the order.
Job templates with asks and counts of their own, the same multiset for
every seed in an order the seed draws; a template's constraints may be
comparisons the device encodes as integers, a ``version`` constraint (the
host-side row of ``ops/encode._constraint_row``), ``distinct_hosts`` and
``distinct_property``.

The rows the comparison needs are computed here from the configuration
file alone, by this module's own evaluator: numpy and the standard
library, nothing of the program.
"""
from __future__ import annotations

import random
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks import check, reference
from benchmarks.deployments.uniform import node_id, node_indices  # noqa: F401

DRY = {"nodes": 60, "jobs": 24}


# -- the fleet ------------------------------------------------------------------


def _pool_of(config: dict) -> List[str]:
    order = config["cluster"]["order"]
    return [order[i % len(order)] for i in range(config["cluster"]["nodes"])]


def _rack_of(config: dict) -> np.ndarray:
    c = config["cluster"]
    return (np.arange(c["nodes"]) // len(c["order"])) % c["racks"]


def make_nodes(config: dict) -> List:
    """The nodes, in device order: the order they are registered in."""
    from nomad_tpu.structs import structs as s

    c = config["cluster"]
    shapes = {}
    nodes = []
    for i, (pool, rack) in enumerate(zip(_pool_of(config), _rack_of(config))):
        base = shapes.get((pool, rack))
        if base is None:
            nd = c["pools"][pool]
            rv = nd["reserved"]
            base = shapes[(pool, rack)] = s.Node(
                datacenter=c["datacenter"],
                attributes=dict(nd["attributes"]),
                meta=dict(nd["meta"], rack=f"r{rack:03d}"),
                resources=s.Resources(cpu=nd["cpu"], memory_mb=nd["memory_mb"],
                                      disk_mb=nd["disk_mb"], iops=nd["iops"]),
                reserved=s.Resources(cpu=rv["cpu"], memory_mb=rv["memory_mb"],
                                     disk_mb=rv["disk_mb"]),
                node_class=nd["node_class"], status=s.NODE_STATUS_READY)
            base.compute_class()
        node = base.copy()
        node.id = node.name = node_id(i)
        nodes.append(node)
    return nodes


def _per_node(config: dict, of_pool) -> np.ndarray:
    rows = {name: of_pool(nd) for name, nd in config["cluster"]["pools"].items()}
    return np.asarray([rows[p] for p in _pool_of(config)], dtype=np.float64)


def capacity(config: dict) -> np.ndarray:
    """[N, 3] usable capacity per node (cpu, memory, disk)."""
    return _per_node(config, lambda nd: [
        nd[k] - nd["reserved"][k] for k in ("cpu", "memory_mb", "disk_mb")])


def reserved(config: dict) -> np.ndarray:
    """[N, 3] what each node holds back, which ScoreFit counts as used."""
    return _per_node(config, lambda nd: [
        nd["reserved"][k] for k in ("cpu", "memory_mb", "disk_mb")])


# -- the jobs -------------------------------------------------------------------


def _drawn(config: dict, n: int, seed: int) -> List[str]:
    mix = config["jobs"]["mix"]
    names = [mix[i % len(mix)] for i in range(n)]
    random.Random(seed).shuffle(names)
    return names


def backlog_ids(config: dict, seed: int) -> List[str]:
    n = int(config["jobs"]["jobs"])
    return [f"job-{i:05d}-{t}" for i, t in enumerate(_drawn(config, n, seed))]


def request_id(config: dict, kind: str, i: int, seed: int) -> str:
    mix = config["jobs"]["mix"]
    return f"{kind}-{i:05d}-{mix[(i + seed) % len(mix)]}"


def _template(config: dict, jid: str) -> dict:
    return config["jobs"]["templates"][jid.rsplit("-", 1)[1]]


def wants(config: dict, jid: str) -> int:
    return int(_template(config, jid)["count"])


def make_job(config: dict, jid: str):
    from nomad_tpu.structs import structs as s

    j, tpl = config["jobs"], _template(config, jid)
    t = tpl["task"]
    group = s.TaskGroup(
        name="tg", count=int(tpl["count"]),
        ephemeral_disk=s.EphemeralDisk(size_mb=t["ephemeral_disk_mb"]),
        tasks=[s.Task(name="t", driver=t["driver"],
                      config={"command": "/bin/date"},
                      resources=s.Resources(cpu=t["cpu"],
                                            memory_mb=t["memory_mb"]),
                      log_config=s.LogConfig())])
    job = s.Job(
        region="global", id=jid, name=jid, type=j["type"],
        priority=j["priority"], datacenters=[config["cluster"]["datacenter"]],
        constraints=[s.Constraint(lt, rt, op)
                     for lt, op, rt in tpl["constraints"]],
        task_groups=[group])
    job.canonicalize()
    return job


# -- the evaluator ----------------------------------------------------------------


def _resolve(target: str, pool: dict) -> Optional[str]:
    """``${attr.x}``, ``${meta.x}`` and ``${node.class}`` of a pool; any
    other text is a literal.  None: the node has no such property."""
    if not target.startswith("${"):
        return target
    kind, _, key = target[2:-1].partition(".")
    if kind == "attr":
        return pool["attributes"].get(key)
    if kind == "meta":
        return pool["meta"].get(key)
    if target == "${node.class}":
        return pool["node_class"]
    raise ValueError(f"pools3 cannot resolve {target!r}")


def _version(text: str) -> Tuple[int, ...]:
    return tuple(int(part) for part in text.strip().split("."))


def _holds(lval: str, op: str, rval: str) -> bool:
    if op in ("=", "==", "is"):
        return lval == rval
    if op in ("!=", "not"):
        return lval != rval
    if op == "version":
        # "<op> <version>" clauses joined by commas, all of which hold.
        for clause in rval.split(","):
            sign, _, want = clause.strip().rpartition(" ")
            have, want = _version(lval), _version(want)
            if not {"": have == want, "=": have == want, ">": have > want,
                    ">=": have >= want, "<": have < want,
                    "<=": have <= want}[sign.strip()]:
                return False
        return True
    raise ValueError(f"pools3 cannot evaluate operand {op!r}")


def rows(config: dict, jid: str
         ) -> Tuple[Optional[np.ndarray], Optional[np.ndarray]]:
    """(feasibility row, distinct row) of a job: the nodes its constraints
    admit (None: every node) and the groups no two of its allocations may
    share (None: soft anti-affinity only)."""
    c = config["cluster"]
    admits = {name: True for name in c["pools"]}
    distinct = None
    for lt, op, rt in _template(config, jid)["constraints"]:
        if op == "distinct_hosts":
            distinct = np.arange(c["nodes"])
        elif op == "distinct_property":
            if lt != "${meta.rack}":
                raise ValueError(f"pools3 knows distinct_property of "
                                 f"${{meta.rack}} only, not {lt!r}")
            distinct = _rack_of(config)
        else:
            for name, pool in c["pools"].items():
                lval, rval = _resolve(lt, pool), _resolve(rt, pool)
                admits[name] &= (lval is not None and rval is not None
                                 and _holds(lval, op, rval))
    if all(admits.values()):
        return None, distinct
    return np.asarray([admits[p] for p in _pool_of(config)]), distinct


# -- the served answers and the comparison -----------------------------------------


def placed_job(config: dict, jid: str, nodes: np.ndarray,
               alloc_rows: Sequence) -> reference.PlacedJob:
    res = alloc_rows[0].resources
    ask = np.asarray([res.cpu, res.memory_mb, res.disk_mb], dtype=np.float64)
    feasible, distinct = rows(config, jid)
    return reference.PlacedJob(jid, ask, nodes, feasible, distinct)


def compare(served: check.Served, config: dict) -> Dict[str, Dict[str, float]]:
    """``check.compare`` over this fleet, less ``score_sum_rel``: ties
    among unlike nodes and racks are broken by the kernel's jitter and by
    node order in the twin, so sound runs read up to 0.0054 and the control
    from 0.0002 up (CPU dry runs, PERF.md section 6, PR 28): no limit
    separates them, and a test deployment may say so here.  A cell's
    deployment keeps every standing name."""
    out = check.compare(served, capacity(config),
                        dict(config["limits"], score_sum_rel=None),
                        reserved(config))
    del out["score_sum_rel"]
    return out


def control_jobs(config: dict, served_jobs: Sequence[reference.PlacedJob],
                 seed: int) -> List[reference.PlacedJob]:
    return check.control_jobs(capacity(config), served_jobs, seed,
                              reserved(config))


def shrink(config: dict) -> dict:
    """The fleet and the backlog at a tiny size; every template and every
    pool stays."""
    config["cluster"]["nodes"] = DRY["nodes"]
    config["jobs"]["jobs"] = DRY["jobs"]
    return config
