"""Drives a dry run with the timed path broken underneath: the plan each
eval submits is altered on its way to the plan queue.  Used by the tests;
``python faulty_run.py <fault> <workload>`` exits 1 when ``correct`` came
out false."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def install(fault: str) -> None:
    from nomad_tpu.server import worker

    real = worker._MuxPlanner.submit_plan
    seen = {"n": 0}

    def broken(self, plan):
        seen["n"] += 1
        if not plan.eval_id or not (plan.node_allocation or plan.alloc_slabs):
            return real(self, plan)
        job_id = plan.job.id if plan.job is not None else ""
        if job_id.startswith("warm-") or seen["n"] % 10:
            return real(self, plan)        # one plan in ten is broken
        if fault == "answer_altered":
            # One placement lands on an empty node instead of where the
            # device put it.
            if plan.alloc_slabs:
                slab = plan.alloc_slabs[0]
                taken = set(slab.node_ids)
                dst = next(f"node-{i:05d}" for i in range(399, -1, -1)
                           if f"node-{i:05d}" not in taken)
                slab.node_ids = [dst] + list(slab.node_ids)[1:]
            else:
                dst = "node-00399"
                src = next(iter(plan.node_allocation))
                allocs = plan.node_allocation.pop(src)
                for a in allocs:
                    a.node_id = dst
                plan.node_allocation.setdefault(dst, []).extend(allocs)
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
    install(sys.argv[1])
    from benchmarks import run

    return run.main(["--workload", sys.argv[2], "--seed", "97",
                     "--seconds", "3", "--dry-run-cpu"])


if __name__ == "__main__":
    sys.exit(main())
