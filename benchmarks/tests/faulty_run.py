"""Drives a dry run with the timed path broken underneath: the plan each
eval submits is altered on its way to the plan queue.  Used by the tests;
``python faulty_run.py <fault> <workload>`` exits 1 when ``correct`` came
out false.  ``constraint_broken`` and ``mates_on_one_node`` are for a
deployment whose module gives each job's ``rows``.  The tree it drives is
the one it lies in, so a copy of the tree with a deployment added is
broken the same way."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def move_first(plan, dst: str) -> None:
    """The plan's first placement lands on ``dst`` instead of where the
    device put it."""
    if plan.alloc_slabs:
        slab = plan.alloc_slabs[0]
        slab.node_ids = [dst] + list(slab.node_ids)[1:]
    else:
        src = next(iter(plan.node_allocation))
        alloc = plan.node_allocation[src].pop(0)
        if not plan.node_allocation[src]:
            del plan.node_allocation[src]
        alloc.node_id = dst
        plan.node_allocation.setdefault(dst, []).append(alloc)


def placed_on(plan) -> list:
    if plan.alloc_slabs:
        return list(plan.alloc_slabs[0].node_ids)
    return list(plan.node_allocation)


def install(fault: str, workload: str) -> None:
    from benchmarks import manifest
    from nomad_tpu.server import worker

    cell = manifest.load_cell(workload)
    dep = cell.deployment
    config = manifest.shrunk(cell)
    node_ids = [n.id for n in dep.make_nodes(config)]
    real = worker._MuxPlanner.submit_plan
    seen = {"n": 0}

    def broken(self, plan):
        seen["n"] += 1
        if not plan.eval_id or not (plan.node_allocation or plan.alloc_slabs):
            return real(self, plan)
        job_id = plan.job.id if plan.job is not None else ""
        if fault in ("constraint_broken", "mates_on_one_node"):
            # Every plan of a job that has the constraint is broken.
            feasible, distinct = dep.rows(config, job_id)
            taken = placed_on(plan)
            if fault == "constraint_broken" and feasible is not None:
                move_first(plan, next(
                    nid for nid, ok in zip(node_ids, feasible)
                    if not ok and nid not in taken))
            elif (fault == "mates_on_one_node" and distinct is not None
                  and len(set(distinct)) == len(distinct)):  # distinct_hosts
                move_first(plan, taken[1])
            return real(self, plan)
        if job_id.startswith("warm-") or seen["n"] % 10:
            return real(self, plan)        # one plan in ten is broken
        if fault == "answer_altered":
            # One placement lands on an empty node.
            taken = placed_on(plan)
            move_first(plan, next(nid for nid in reversed(node_ids)
                                  if nid not in taken))
        elif fault == "half_left_out":
            for slab in plan.alloc_slabs:
                half = len(slab.ids) // 2
                for col in ("ids", "names", "node_ids", "prev_ids"):
                    setattr(slab, col, list(getattr(slab, col))[:half])
            for nid in list(plan.node_allocation)[::2]:
                del plan.node_allocation[nid]
        elif fault == "state_unchanged":
            plan.node_allocation.clear()
            plan.alloc_slabs.clear()
        return real(self, plan)

    worker._MuxPlanner.submit_plan = broken


def main() -> int:
    install(sys.argv[1], sys.argv[2])
    from benchmarks import run

    return run.main(["--workload", sys.argv[2], "--seed", "97",
                     "--seconds", "3", "--dry-run-cpu"])


if __name__ == "__main__":
    sys.exit(main())
