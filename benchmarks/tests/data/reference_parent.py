"""The plain reference: greedy best-fit placement over ALL feasible nodes.

Independent of the program: numpy and float64 only, no import of
``nomad_tpu``, no table or score the program made.  It is the
unlimited-candidate oracle (upstream's stack with the LimitIterator cap
lifted), copied in substance from ``bench.py``'s validated numpy twin:

- ScoreFit (nomad/structs/funcs.go ScoreFit): with the ask added,
  ``20 - (10**free_cpu + 10**free_mem)`` clipped to [0, 18], free shares
  taken against the node's resources minus its reservation;
- job anti-affinity (scheduler/rank.go JobAntiAffinityIterator): 20.0 off
  for every allocation of the same job already on the node, so a job
  whose count is at most the number of feasible nodes lands on distinct
  nodes, the best ``count`` of them;
- feasibility: every dimension given (cpu, memory, disk) fits.

Three things are computed from it:

``replay``   follows the served placements job by job in commit order and
             reads, for each job, the widest gap by which a node the
             program chose scores below a feasible node it left out;
``greedy``   places the same jobs itself (the twin), for the aggregate
             ScoreFit sum the repo's 0.5% contract is stated on;
``sampled``  the control: the same greedy over log2(N) sampled candidates
             per placement, upstream's own approximation and the step
             that would tempt a later PR.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

PENALTY = 20.0
CPU, MEM = 0, 1


@dataclass
class PlacedJob:
    """One job's served placements: the ask per allocation over the
    capacity dimensions and the node index of each allocation."""
    key: str
    ask: np.ndarray          # [D] float64
    nodes: np.ndarray        # [count] int64


def score_after(after: np.ndarray, cap: np.ndarray) -> np.ndarray:
    """ScoreFit of each node once it carries ``after`` ([N, D])."""
    free_cpu = 1.0 - after[:, CPU] / cap[:, CPU]
    free_mem = 1.0 - after[:, MEM] / cap[:, MEM]
    return np.clip(20.0 - (10.0 ** free_cpu + 10.0 ** free_mem), 0.0, 18.0)


def scorefit_sum(used: np.ndarray, cap: np.ndarray) -> float:
    """Aggregate ScoreFit over nodes that carry anything (bench.py
    binpack_scores): the order-free basis for comparing two engines."""
    carries = used.any(axis=1)
    return float(np.where(carries, score_after(used, cap), 0.0).sum())


def _fits(after: np.ndarray, cap: np.ndarray) -> np.ndarray:
    return np.all(after <= cap, axis=1)


@dataclass
class Replay:
    widest_gap: float        # score units; 0 when every choice was a best one
    worst_job: str
    infeasible: int          # allocations on a node they did not fit
    repeated: int            # allocations sharing a node with a job-mate
                             # while a fresh feasible node was left
    used: np.ndarray         # [N, D] usage after every served job


def replay(cap: np.ndarray, jobs: Sequence[PlacedJob],
           used0: Optional[np.ndarray] = None) -> Replay:
    used = np.zeros_like(cap) if used0 is None else used0.copy()
    widest, worst, infeasible, repeated = 0.0, "", 0, 0
    for job in jobs:
        after = used + job.ask
        fits = _fits(after, cap)
        base = score_after(after, cap)
        counts = np.bincount(job.nodes, minlength=cap.shape[0])
        chosen = counts > 0
        n_feasible = int(fits.sum())
        if len(job.nodes) <= n_feasible:
            infeasible += int(counts[~fits].sum())
            repeated += int((counts[chosen] - 1).sum())
            left = fits & ~chosen
            ok = chosen & fits
            if left.any() and ok.any():
                gap = float(base[left].max() - base[ok].min())
                if gap > widest:
                    widest, worst = gap, job.key
        else:
            # More allocations than feasible nodes: the rounds wrap and
            # the bound above does not hold; follow it one by one.
            gap, bad = _replay_one_by_one(cap, used, job)
            infeasible += bad
            if gap > widest:
                widest, worst = gap, job.key
        np.add.at(used, job.nodes, job.ask)
    return Replay(widest, worst, infeasible, repeated, used)


def _replay_one_by_one(cap, used, job):
    used = used.copy()
    cnt = np.zeros(cap.shape[0])
    widest, bad = 0.0, 0
    for node in job.nodes:
        after = used + job.ask
        fits = _fits(after, cap)
        eff = np.where(fits, score_after(after, cap) - PENALTY * cnt, -np.inf)
        if not fits[node]:
            bad += 1
        else:
            widest = max(widest, float(eff.max() - eff[node]))
        used[node] += job.ask
        cnt[node] += 1.0
    return widest, bad


def greedy(cap: np.ndarray, asks: Sequence[np.ndarray], counts: Sequence[int],
           used0: Optional[np.ndarray] = None,
           candidates: Optional[int] = None, seed: int = 0
           ) -> List[np.ndarray]:
    """Place the jobs in order; returns each job's node indices.

    ``candidates=None`` scores every feasible node (the reference).  With
    a number, each placement scores that many sampled feasible nodes (the
    control)."""
    used = np.zeros_like(cap) if used0 is None else used0.copy()
    rng = np.random.default_rng(seed)
    out = []
    for ask, count in zip(asks, counts):
        after = used + ask
        fits = _fits(after, cap)
        base = score_after(after, cap)
        feasible = np.flatnonzero(fits)
        if candidates is None and count <= len(feasible):
            # Distinct nodes, the best `count`: a stable sort keeps ties
            # in node order, as a first-come argmax would.
            order = feasible[np.argsort(-base[feasible], kind="stable")]
            nodes = order[:count]
        elif candidates is not None and count <= len(feasible):
            nodes = np.empty(count, dtype=np.int64)
            free = np.ones(cap.shape[0], dtype=bool)
            for k in range(count):
                pool = feasible[free[feasible]]
                pick = pool[rng.integers(0, len(pool),
                                         size=min(candidates, len(pool)))]
                best = pick[int(np.argmax(base[pick]))]
                nodes[k] = best
                free[best] = False
        else:
            nodes = _greedy_one_by_one(cap, used, ask, count)
        np.add.at(used, nodes, ask)
        out.append(nodes)
    return out


def _greedy_one_by_one(cap, used, ask, count):
    used = used.copy()
    cnt = np.zeros(cap.shape[0])
    nodes = []
    for _ in range(count):
        after = used + ask
        fits = _fits(after, cap)
        eff = np.where(fits, score_after(after, cap) - PENALTY * cnt, -np.inf)
        i = int(np.argmax(eff))
        if not np.isfinite(eff[i]):
            break
        nodes.append(i)
        used[i] += ask
        cnt[i] += 1.0
    return np.asarray(nodes, dtype=np.int64)


def candidate_limit(n_nodes: int) -> int:
    """Upstream's service-job candidate cap: max(2, ceil(log2 N))
    (scheduler/stack.go SetNodes)."""
    return max(2, int(math.ceil(math.log2(max(2, n_nodes)))))
