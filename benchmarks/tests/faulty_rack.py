"""``faulty_run.py``'s one missing fault for a deployment with
``distinct_property``: every plan of a job that must spread one per rack
has its first placement moved into the rack of its second, onto a node
the job's constraints admit and no job-mate holds.  Legal by every other
check (feasible, one per host, within capacity), so only the rack
guarantee can catch it.  ``python faulty_rack.py <workload>`` exits 1
when ``correct`` came out false."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import faulty_run  # noqa: E402  (puts the tree's root on sys.path)


def install(workload: str) -> None:
    from benchmarks import manifest
    from nomad_tpu.server import worker

    cell = manifest.load_cell(workload)
    dep = cell.deployment
    config = manifest.shrunk(cell)
    node_ids = [n.id for n in dep.make_nodes(config)]
    index = {nid: i for i, nid in enumerate(node_ids)}
    real = worker._MuxPlanner.submit_plan

    def broken(self, plan):
        if plan.eval_id and plan.job is not None \
                and (plan.node_allocation or plan.alloc_slabs):
            feasible, distinct = dep.rows(config, plan.job.id)
            taken = faulty_run.placed_on(plan)
            racked = (distinct is not None
                      and len(set(distinct.tolist())) < len(distinct))
            if racked and len(taken) > 1:
                rack = distinct[index[taken[1]]]
                faulty_run.move_first(plan, next(
                    nid for i, nid in enumerate(node_ids)
                    if distinct[i] == rack and nid not in taken
                    and (feasible is None or feasible[i])))
        return real(self, plan)

    worker._MuxPlanner.submit_plan = broken


def main() -> int:
    install(sys.argv[1])
    from benchmarks import run

    return run.main(["--workload", sys.argv[1], "--seed", "97",
                     "--seconds", "3", "--dry-run-cpu"])


if __name__ == "__main__":
    sys.exit(main())
