"""The comparison that decides ``correct``.

What the timed path produced -- every allocation of every job that was
complete when the window had closed and the worker had parked -- is held
against the plain reference (``reference.py``) and against the
configuration's guarantees.  Each number compared has a limit of its own;
the two that are not exact take theirs from the configuration file, where
``PERF.md`` gives the readings they were set from.

Nothing here knows what a fleet or a job looks like: the capacity, each
job's rows and the limits come from the configuration's deployment module
(``deployments/<name>.py``), which calls ``compare`` and may add names of
its own to what it returns.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence

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


def compare(served: Served, cap: np.ndarray, limits: dict,
            reserved: Optional[np.ndarray] = None
            ) -> Dict[str, Dict[str, float]]:
    """{name: {"value", "limit"}} for every number compared, over the
    capacity ``cap`` ([N, D], rows may differ; with ``reserved`` where
    they do, see ``reference.score_after``) and each job's own rows."""
    rep = reference.replay(cap, served.jobs, reserved=reserved)
    twin_nodes = reference.greedy(
        cap, [j.ask for j in served.jobs], [len(j.nodes) for j in served.jobs],
        feasible=[j.feasible for j in served.jobs],
        distinct=[j.distinct for j in served.jobs], reserved=reserved)
    twin_used = np.zeros_like(cap)
    for job, nodes in zip(served.jobs, twin_nodes):
        np.add.at(twin_used, nodes, job.ask)
    ref_sum = reference.scorefit_sum(twin_used, cap, reserved)
    got_sum = reference.scorefit_sum(rep.used, cap, reserved)
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
    if any(j.distinct is not None for j in served.jobs):
        out["job_mates_in_one_distinct_group"] = {"value": rep.shared,
                                                  "limit": 0}
    for name, value in served.device.items():
        out[name] = {"value": value, "limit": 0}
    return out


def correct(compared: Dict[str, Dict[str, float]]) -> bool:
    return all(v["value"] <= v["limit"] for v in compared.values())


def control_jobs(cap: np.ndarray, served_jobs: Sequence[reference.PlacedJob],
                 seed: int, reserved: Optional[np.ndarray] = None
                 ) -> List[reference.PlacedJob]:
    """The reference put in the program's place with one guarantee broken:
    the same jobs placed over log2(N) sampled candidates, not all nodes."""
    nodes = reference.greedy(
        cap, [j.ask for j in served_jobs], [len(j.nodes) for j in served_jobs],
        seed=seed, candidates=reference.candidate_limit(cap.shape[0]),
        feasible=[j.feasible for j in served_jobs],
        distinct=[j.distinct for j in served_jobs], reserved=reserved)
    return [replace(j, nodes=n) for j, n in zip(served_jobs, nodes)]
