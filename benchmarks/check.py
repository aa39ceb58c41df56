"""The comparison that decides ``correct``.

What the timed path produced -- every allocation of every job that was
complete when the window had closed and the worker had parked -- is held
against the plain reference (``reference.py``) and against the
configuration's guarantees.  Each number compared has a limit of its own;
the two that are not exact take theirs from the configuration file, where
``PERF.md`` gives the readings they were set from.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Sequence

import numpy as np

from benchmarks import reference


@dataclass
class Served:
    """The timed path's answers, as plain arrays."""
    jobs: List[reference.PlacedJob]               # commit order
    wrong_count: int = 0                          # complete evals without `count` live allocs
    readback_mismatch: int = 0                    # HTTP sample jobs that differ from state
    device: Dict[str, float] = field(default_factory=dict)   # counters that must be 0
    failed_requests: int = 0


def capacity(config: dict) -> np.ndarray:
    """[N, 3] usable capacity per node (cpu, memory, disk): resources
    minus reservation."""
    c = config["cluster"]
    nd, rv = c["node"], c["node"]["reserved"]
    row = [nd["cpu"] - rv["cpu"], nd["memory_mb"] - rv["memory_mb"],
           nd["disk_mb"] - rv["disk_mb"]]
    return np.tile(np.asarray(row, dtype=np.float64), (c["nodes"], 1))


def ask_of(config: dict) -> np.ndarray:
    t = config["jobs"]["task"]
    return np.asarray([t["cpu"], t["memory_mb"], t["ephemeral_disk_mb"]],
                      dtype=np.float64)


def compare(served: Served, config: dict) -> Dict[str, Dict[str, float]]:
    """{name: {"value", "limit"}} for every number compared."""
    limits = config["limits"]
    cap = capacity(config)
    rep = reference.replay(cap, served.jobs)
    twin_nodes = reference.greedy(
        cap, [j.ask for j in served.jobs], [len(j.nodes) for j in served.jobs])
    twin_used = np.zeros_like(cap)
    for job, nodes in zip(served.jobs, twin_nodes):
        np.add.at(twin_used, nodes, job.ask)
    ref_sum = reference.scorefit_sum(twin_used, cap)
    got_sum = reference.scorefit_sum(rep.used, cap)
    over = int((rep.used > cap).any(axis=1).sum())
    out = {
        "score_gap": {"value": rep.widest_gap,
                      "limit": limits["score_gap"]},
        "score_sum_rel": {"value": abs(got_sum - ref_sum) / max(ref_sum, 1e-12),
                          "limit": limits["score_sum_rel"]},
        "infeasible_allocs": {"value": rep.infeasible, "limit": 0},
        "job_mates_on_one_node": {"value": rep.repeated, "limit": 0},
        "nodes_over_capacity": {"value": over, "limit": 0},
        "evals_wrong_count": {"value": served.wrong_count, "limit": 0},
        "readback_mismatch": {"value": served.readback_mismatch, "limit": 0},
        "failed_requests": {"value": served.failed_requests, "limit": 0},
    }
    for name, value in served.device.items():
        out[name] = {"value": value, "limit": 0}
    return out


def correct(compared: Dict[str, Dict[str, float]]) -> bool:
    return all(v["value"] <= v["limit"] for v in compared.values())


def control_jobs(config: dict, served_jobs: Sequence[reference.PlacedJob],
                 seed: int) -> List[reference.PlacedJob]:
    """The reference put in the program's place with one guarantee broken:
    the same jobs placed over log2(N) sampled candidates, not all nodes."""
    cap = capacity(config)
    nodes = reference.greedy(
        cap, [j.ask for j in served_jobs], [len(j.nodes) for j in served_jobs],
        seed=seed, candidates=reference.candidate_limit(cap.shape[0]))
    return [reference.PlacedJob(j.key, j.ask, n)
            for j, n in zip(served_jobs, nodes)]
