"""PlanQueue: leader-only priority queue of submitted plans with futures
(reference: nomad/plan_queue.go:29-180)."""
from __future__ import annotations

import heapq
import itertools
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

from ..structs import structs as s

# Bound on the per-job last-apply fence table: evictions fold into the
# global floor, so the map cannot grow with job cardinality (dispatch
# workloads mint a unique child job id per dispatch).
JOB_APPLY_CAP = 16384


class PlanFuture:
    """Future for a submitted plan's result.

    claim()/cancel() close the abandoned-plan race: a submitter whose
    wait timed out cancels the future, and the applier claims it before
    evaluating — so a plan is either cancelled (never applied; the
    submitter may safely replan without double-committing placements) or
    claimed (the applier owns it; the submitter must keep waiting).

    The future also carries the round trip's own clock: six
    ``perf_counter`` stamps (enqueued, claimed by the applier, evaluate
    done, commit entered, applied, responded; 0.0 = never reached), read
    by the submitter once ``wait()`` returns (``WorkerPlanner.submit_plan``
    turns them into the ``plan.queue_wait`` / ``.commit_wait`` /
    ``.respond`` / ``.wake`` samples), and ``trace_parent``, the submitter's
    ``worker.submit_plan`` span id while the tracer is armed (0
    otherwise), which the applier's spans take as their parent."""

    def __init__(self, trace_parent: int = 0):
        self._event = threading.Event()
        self._result: Optional[s.PlanResult] = None
        self._error: Optional[Exception] = None
        self._state_l = threading.Lock()
        self._claimed = False
        self._cancelled = False
        self.trace_parent = trace_parent
        self.t_enqueued = time.perf_counter()
        self.t_claimed = self.t_evaluated = 0.0
        self.t_commit = self.t_applied = self.t_responded = 0.0

    def claim(self) -> bool:
        """Applier-side: take ownership; False if already cancelled."""
        with self._state_l:
            if self._cancelled:
                return False
            self._claimed = True
            self.t_claimed = time.perf_counter()
            return True

    def cancel(self) -> bool:
        """Submitter-side: abandon; False if the applier already owns it
        (the plan may still commit — keep waiting)."""
        with self._state_l:
            if self._claimed:
                return False
            self._cancelled = True
            return True

    def respond(self, result: Optional[s.PlanResult], error: Optional[Exception]):
        self._result = result
        self._error = error
        self.t_responded = time.perf_counter()
        self._event.set()
        return self

    def wait(self, timeout: Optional[float] = None) -> s.PlanResult:
        if not self._event.wait(timeout):
            raise TimeoutError("plan future timed out")
        if self._error is not None:
            raise self._error
        return self._result


@dataclass(order=True)
class _PendingPlans:
    """One queue item: the plans one submission handed over, in its
    order, each with its own future (a single plan is a list of one)."""

    sort_key: Tuple[int, int, int]
    plans: List[Tuple[s.Plan, PlanFuture]] = field(compare=False)


class PlanQueue:
    def __init__(self):
        self._l = threading.Lock()
        self._cond = threading.Condition(self._l)
        self._enabled = False
        self._heap: List[_PendingPlans] = []
        self._seq = itertools.count()
        # Per-job last plan-apply index (stale-snapshot fence): a worker
        # may reuse a cached snapshot for job J only if it covers J's
        # newest committed plan — the broker serializes evals per job,
        # but an eval CREATED before J's previous plan applied can be
        # DEQUEUED after it, and scheduling J from a snapshot that
        # misses J's own placements would double-place them (capacity
        # re-checks can't catch same-job duplication).  Plans with no
        # attributable job bump the global floor instead; so do LRU
        # evictions past JOB_APPLY_CAP (conservative: unknown jobs then
        # require a snapshot past the evicted apply, never an older
        # one).
        self._job_apply: "OrderedDict[str, int]" = OrderedDict()
        self._apply_floor = 0

    def note_applied(self, job_id: str, index: int) -> None:
        with self._l:
            if job_id:
                if index > self._job_apply.get(job_id, 0):
                    self._job_apply[job_id] = index
                self._job_apply.move_to_end(job_id)
                while len(self._job_apply) > JOB_APPLY_CAP:
                    _, evicted = self._job_apply.popitem(last=False)
                    if evicted > self._apply_floor:
                        self._apply_floor = evicted
            elif index > self._apply_floor:
                self._apply_floor = index

    def applied_index_for(self, job_id: str) -> int:
        with self._l:
            return max(self._job_apply.get(job_id, 0), self._apply_floor)

    def enabled(self) -> bool:
        with self._l:
            return self._enabled

    def set_enabled(self, enabled: bool) -> None:
        with self._l:
            self._enabled = enabled
            if not enabled:
                # Pending submitters must hear about the discard — a
                # silent drop would hang their future.wait() forever
                # (plan_queue.go Flush responds with an error).
                for item in self._heap:
                    for _, future in item.plans:
                        future.respond(None, RuntimeError(
                            "plan queue is disabled (leadership lost)"))
                self._heap = []
            self._cond.notify_all()

    def enqueue(self, plan: s.Plan, trace_parent: int = 0) -> PlanFuture:
        """(plan_queue.go:95)."""
        return self.enqueue_group([plan], trace_parent)[0]

    def enqueue_group(self, plans: List[s.Plan],
                      trace_parent: int = 0) -> List[PlanFuture]:
        """One queue item for the plans of one submission (a batch's
        plans, in spec order): the applier dequeues them together and
        decides them in that order.  The item's priority is the highest
        of its plans'; each plan has its own future."""
        futures = [PlanFuture(trace_parent) for _ in plans]
        with self._l:
            if not self._enabled:
                raise RuntimeError("plan queue is disabled")
            heapq.heappush(self._heap, _PendingPlans(
                (-max(plan.priority for plan in plans), 0, next(self._seq)),
                list(zip(plans, futures))))
            self._cond.notify_all()
        return futures

    def dequeue(self, timeout: Optional[float] = None
                ) -> Optional[List[Tuple[s.Plan, PlanFuture]]]:
        """The next submission's (plan, future) pairs, in its order."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._l:
            while True:
                if not self._enabled:
                    return None
                if self._heap:
                    return heapq.heappop(self._heap).plans
                remaining = None if deadline is None else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return None
                self._cond.wait(remaining if remaining is not None else 1.0)

    def depth(self) -> int:
        with self._l:
            return len(self._heap)
