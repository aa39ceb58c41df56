"""Plan applier: the single serialization point of the optimistic
scheduler (reference: nomad/plan_apply.go:27-371).

One thread dequeues plans, re-checks per-node fit against a state snapshot,
makes the partial/gang-commit decision, applies the committed subset
through the log, and *optimistically* applies it to its local snapshot so
verification of plan N+1 can overlap the apply of plan N.

TPU-native departure: the reference verifies nodes with a worker pool of
NumCPU/2 goroutines (plan_apply.go:49-53); here the per-node AllocsFit
re-check is one call into the vectorized kernel (ops/kernels.py
batch_allocs_fit) when the plan touches many nodes, falling back to the
scalar path for small plans.

Pipelined commit (ISSUE 10): on a multi-voter cluster each raft apply
waits a replication round trip, and a strictly serial applier caps
cluster-wide plan throughput at 1/RTT.  The applier therefore overlaps
the COMMIT of plan N with the EVALUATION of plan N+1 — the reference's
async-commit overlap (plan_apply.go:55-120), realized here as a bounded
pool of commit waiters plus an **optimistic in-flight overlay**: the
placements of not-yet-visible committed plans are added to every fit
re-check, so a node can never be over-committed by two plans racing
through the pipeline.  The overlay is conservative (pending REMOVALS are
ignored), so the re-check can only be stricter than the truth.  Plans
carrying preemptions keep the strict serial path: their staleness fence
reads live alloc rows that an in-flight plan could still change.

Group submission (ISSUE 32): a queue item is one SUBMISSION, one plan or
a batch's plans in spec order (PlanQueue.enqueue_group).  Consecutive
plans that propose nothing but NEW placements carrying combined
resources (slab prototypes, or per-object allocations not yet in the
store), with or without ports and bandwidth, are decided by ONE fit
re-check over all their nodes.  Adding placements only grows a node's
usage, its bandwidth and its set of held ports, so if every touched
node fits with all of the run's placements added, no port is held twice
and no dimension is exceeded by any prefix either: each plan fits in
sequence and commits whole.  If not, the pass decides nothing and the
plans take the single-plan route one by one.  A group is committed as
one raft entry PER PLAN written back to back under one fsync
(RaftLog.apply_many), and each plan is answered with its own result.
The single-plan route is the run of one.
"""
from __future__ import annotations

import itertools
import logging
import os
import queue as _queue
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import fault
from ..structs import structs as s
from ..utils import tracing
from ..structs.funcs import allocs_fit
from .fsm import MessageType
from .plan_queue import PlanFuture, PlanQueue
from ..utils.telemetry import NULL_TELEMETRY
from .raft import RaftLog

# Above this many touched nodes the vectorized fit re-check is used.
VECTORIZE_THRESHOLD = 64


def _pipeline_depth() -> int:
    """Concurrent in-flight plan commits (1 restores the strictly
    serial applier)."""
    from ..utils import knobs

    return max(1, knobs.get_int("NOMAD_TPU_PLAN_PIPELINE"))


def _has_ports(alloc: s.Allocation) -> bool:
    """Whether the alloc reserves network resources (allocs_fit owns
    port and bandwidth accounting, so such a node is re-checked by it)."""
    if alloc.resources is not None and alloc.resources.networks:
        return True
    return any(tr.networks for tr in alloc.task_resources.values())


def _replaced_ids(plan: s.Plan, node_id: str) -> set:
    """Ids of the rows on ``node_id`` that the plan takes out or
    replaces: its stops, its preemptions and its in-place updates
    (plan_apply.go:342, RemoveAllocs of all three)."""
    return {a.id for a in itertools.chain(
        plan.node_update.get(node_id, ()),
        plan.node_preemptions.get(node_id, ()),
        plan.node_allocation.get(node_id, ()))}


def _usage(alloc: s.Allocation) -> np.ndarray:
    return np.array(s.alloc_usage_vec(alloc), dtype=np.int64)


class _InflightPlan:
    """One in-flight plan's placements, in the forms the fit re-check
    reads: ``parts`` = (node-id column, usage vector) per slab and per
    distinct per-object usage, for the array route; ``net_nodes`` = the
    nodes an entry with network resources lands on (always re-checked by
    allocs_fit); ``by_node()`` = (alloc, count) lists per node, built on
    first use by a per-node route."""

    __slots__ = ("result", "parts", "net_nodes", "_by_node")

    def __init__(self, result: s.PlanResult):
        self.result = result
        self._by_node: Optional[Dict[str, list]] = None
        net: set = set()
        same_usage: Dict[tuple, List[str]] = {}
        for node_id, allocs in result.node_allocation.items():
            for alloc in allocs:
                same_usage.setdefault(
                    s.alloc_usage_vec(alloc), []).append(node_id)
                if _has_ports(alloc):
                    net.add(node_id)
        self.parts: List[Tuple[List[str], tuple]] = []
        for slab in result.alloc_slabs:
            self.parts.append((slab.node_ids, s.alloc_usage_vec(slab.proto)))
            if _has_ports(slab.proto):
                net.update(slab.node_ids)
        self.parts.extend((ids, vec) for vec, ids in same_usage.items())
        self.net_nodes = net

    def by_node(self) -> Dict[str, List[Tuple[s.Allocation, int]]]:
        out = self._by_node
        if out is None:
            out = {}
            for node_id, allocs in self.result.node_allocation.items():
                for alloc in allocs:
                    out.setdefault(node_id, []).append((alloc, 1))
            for slab in self.result.alloc_slabs:
                for node_id, adds in slab.node_adds().items():
                    out.setdefault(node_id, []).extend(adds)
            self._by_node = out
        return out


class _InflightOverlay:
    """Placements of plans whose raft commit is still in flight, keyed
    by plan: the fit re-check adds them to each touched node's proposed
    set so pipelined plans cannot jointly over-commit a node."""

    def __init__(self):
        self._l = threading.Lock()
        self._plans: Dict[int, _InflightPlan] = {}

    def add(self, token: int, result: s.PlanResult) -> None:
        entry = _InflightPlan(result)
        with self._l:
            self._plans[token] = entry

    def remove(self, token: int) -> None:
        with self._l:
            self._plans.pop(token, None)

    def snapshot(self) -> List[_InflightPlan]:
        """Every in-flight plan, read under ONE lock per fit re-check
        (entries are immutable once added)."""
        with self._l:
            return list(self._plans.values())


def _pending_map(inflight: List[_InflightPlan], node_ids
                 ) -> Dict[str, List[Tuple[s.Allocation, int]]]:
    """The captured overlay's (alloc, count) entries for ``node_ids``."""
    if not inflight:
        return {}
    by_node = [p.by_node() for p in inflight]
    return {nid: [e for m in by_node for e in m.get(nid, ())]
            for nid in node_ids}


class _Fits:
    """One fit re-check's verdicts: ``fit[i]`` for mirror row
    ``rows[i]`` (the array route) plus a per-node dict (the per-node
    routes).  The node-id dict callers read is only built on demand."""

    __slots__ = ("cols", "rows", "fit", "scalar", "indexed", "adds")

    def __init__(self, cols=None, scalar: Optional[Dict[str, bool]] = None):
        self.cols = cols
        # The plan's slab adds per node (PlanApplier._slab_node_adds),
        # when a per-node route read them: the guard's run reuses them.
        self.adds: Optional[Dict] = None
        # Slab rows whose mirror rows came from an indexed node column's
        # integers (no string handled).
        self.indexed = 0
        self.rows = np.zeros(0, dtype=np.int64)
        self.fit = np.zeros(0, dtype=bool)
        self.scalar: Dict[str, bool] = scalar if scalar is not None else {}

    def all_fit(self) -> bool:
        return bool(self.fit.all()) and all(self.scalar.values())

    def array_ids(self) -> List[str]:
        ids = self.cols.node_ids if self.rows.size else ()
        return [ids[r] for r in self.rows.tolist()]

    def as_dict(self) -> Dict[str, bool]:
        out = dict(zip(self.array_ids(), self.fit.tolist()))
        out.update(self.scalar)
        return out


# plan.apply's contiguous stages, in order: the payloads built, the
# entries through the codec, the rest of the log's write phase (its
# lock, the WAL writes), the one durability wait, the sequencer wait
# and FSM applies, and what follows the applies.  Samples
# ``plan.apply.<stage>`` and, armed, spans of the same names; they sum
# to ``plan.apply``.
APPLY_STAGES = ("entry", "encode", "write", "sync", "fsm", "post")


class PlanApplier:
    def __init__(self, plan_queue: PlanQueue, raft: RaftLog,
                 logger: Optional[logging.Logger] = None,
                 metrics=None, blocked_evals=None):
        self.plan_queue = plan_queue
        self.raft = raft
        self.metrics = metrics if metrics is not None else NULL_TELEMETRY
        # Preempted jobs' follow-up evals are handed here after a
        # preemption plan commits, so displaced work reschedules.
        self.blocked_evals = blocked_evals
        self.logger = logger or logging.getLogger("nomad_tpu.plan_apply")
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # Pipelined-commit state (see module docstring): a bounded pool
        # of commit waiters + the in-flight placement overlay + a
        # drain condition the serial (preemption) path waits on.
        self.pipeline_depth = _pipeline_depth()
        self._overlay = _InflightOverlay()
        self._commit_q: "_queue.Queue" = _queue.Queue()
        self._commit_threads: List[threading.Thread] = []
        self._inflight = 0
        self._inflight_cv = threading.Condition()
        self._token_seq = 0
        self._fit_guard_reads = 0

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(target=self.run, daemon=True,
                                        name="plan-applier")
        self._thread.start()
        for i in range(self.pipeline_depth):
            t = threading.Thread(target=self._commit_loop, daemon=True,
                                 name=f"plan-commit-{i}")
            t.start()
            self._commit_threads.append(t)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        for _ in self._commit_threads:
            self._commit_q.put(None)
        for t in self._commit_threads:
            t.join(timeout=5.0)
        self._commit_threads = []

    def run(self) -> None:
        """The planApply hot loop (plan_apply.go:42-120).

        The fit re-check reads the LIVE store plus the in-flight
        overlay: every alloc an earlier plan added is either already
        applied (visible in the store — overlay entries are removed
        only AFTER their raft apply returns) or still in the overlay,
        which is the one consistency property the optimistic re-check
        needs.  Concurrent non-plan writes (client status, node
        transitions) make individual reads at-least-as-fresh as any
        snapshot taken at dequeue time.  Commit waits run on the waiter
        pool so evaluation of plan N+1 overlaps the (multi-voter,
        round-trip-priced) commit of plan N — the reference's async
        overlap, plan_apply.go:55-120.

        A queue item is one submission: one plan, or a batch's plans in
        spec order.  Its consecutive groupable plans are decided
        together (_process_plans); every other plan ends the run before
        it and is decided alone.  The runs are taken in the submission's
        order, each after the one before it has committed."""
        while not self._stop.is_set():
            item = self.plan_queue.dequeue(timeout=0.2)
            if item is None:
                continue
            claimed = []
            for plan, future in item:
                if future.claim():
                    claimed.append((plan, future))
                else:
                    # Submitter gave up (RPC deadline) before we started:
                    # skipping here is what makes its replan safe.
                    self.logger.warning("plan for eval %s was cancelled "
                                        "before apply; dropping", plan.eval_id)
            for n, run in enumerate(self._runs(claimed)):
                serial = bool(run[0][0].node_preemptions)
                if n or serial:
                    # A submission's entries reach the log in its own
                    # order: a run starts when the run before it has
                    # committed.  And the preemption staleness fence
                    # reads live alloc rows (modify_index equality): an
                    # in-flight plan could still change them, so
                    # preemption plans run strictly serial against a
                    # quiesced pipeline.
                    self._drain_inflight()
                self._process_plans(run, pipelined=not serial)

    @staticmethod
    def _groupable(plan: s.Plan) -> bool:
        """Whether all the plan proposes is new placements that carry
        combined resources: slab prototypes, and per-object allocations
        not yet in the store (``create_index`` 0), ports or not.  Then
        the run only adds to each node's usage and held ports, so one
        re-check with all of the run's placements added decides every
        plan of it (module docstring).  An in-place update is a copy of
        a stored row with ``resources`` None and that row's
        ``create_index``: it replaces the row, can shrink or grow it,
        and is never grouped; nor are evictions, preemptions or a gang."""
        return not (plan.node_update or plan.node_preemptions
                    or plan.all_at_once) and all(
            slab.proto.resources is not None
            for slab in plan.alloc_slabs) and all(
            alloc.resources is not None and not alloc.create_index
            for allocs in plan.node_allocation.values() for alloc in allocs)

    @classmethod
    def _runs(cls, pairs: List[Tuple[s.Plan, PlanFuture]]):
        """A submission's (plan, future) pairs cut into runs, order kept:
        consecutive groupable plans together, any other plan alone."""
        run: List[Tuple[s.Plan, PlanFuture]] = []
        for pair in pairs:
            if cls._groupable(pair[0]):
                run.append(pair)
                continue
            if run:
                yield run
                run = []
            yield [pair]
        if run:
            yield run

    def _process_plans(self, pairs: List[Tuple[s.Plan, PlanFuture]],
                       pipelined: bool) -> None:
        """Decide and commit one run of a submission: one plan, or
        several groupable plans by one fit re-check, one raft write
        phase and one fsync.  The run's samples and spans (plan.evaluate,
        plan.apply and, by its submitter, the hand-offs) are emitted
        once, whatever its length."""
        snap = self.raft.fsm.state
        plans = [plan for plan, _ in pairs]
        # Branch before building span attrs (the disarmed per-plan
        # path pays one load + comparison only).
        tr = tracing.TRACER
        try:
            # The submitter's worker.submit_plan span (another thread)
            # is the parent: it caused this work.
            with tracing.timed(self.metrics, "plan.evaluate", cpu=True,
                               attrs=lambda: tracing.plan_attrs(plans),
                               parent_id=pairs[0][1].trace_parent):
                results = self._evaluate_plans(snap, plans)
        except Exception as exc:  # pragma: no cover — defensive
            self.logger.exception("plan evaluation failed")
            for _, future in pairs:
                future.respond(None, exc)
            return
        if results is None:
            # Some row of the group does not fit with all of the group's
            # rows added: the pass decided nothing.  One by one, each
            # when its predecessor has committed (so in the store, and
            # the log in the submission's order).
            for pair in pairs:
                self._drain_inflight()
                self._process_plans([pair], pipelined)
            return
        # What lies between this stamp and _commit's is the hand-off to
        # the commit pool (plan.commit_wait, emitted by the submitter).
        t_evaluated = time.perf_counter()
        applied = (self.raft.applied_index()
                   if any(plan.snapshot_index for plan in plans) else 0)
        commits = []
        for (plan, future), result in zip(pairs, results):
            future.t_evaluated = t_evaluated
            # Staleness + conflict telemetry for the stale-snapshot
            # worker pool: how far behind the log this plan's snapshot
            # was, and whether the optimistic-concurrency re-check had
            # to reject part of it (the submitter replans the rejected
            # remainder off refreshed state — the requeue path).
            if plan.snapshot_index:
                self.metrics.add_sample(
                    "plan.staleness",
                    max(0, applied - plan.snapshot_index))
            if result.refresh_index:
                self.metrics.incr_counter("plan.conflict")
                if tr is not None:
                    tr.event("plan.conflict", eval_id=plan.eval_id,
                             snapshot_index=plan.snapshot_index,
                             refresh_index=result.refresh_index)
            if result.node_update or result.node_allocation \
                    or result.alloc_slabs:
                commits.append((plan, result, future))
            else:
                future.respond(result, None)
        if not commits:
            return
        if not pipelined or self.pipeline_depth <= 1 \
                or not self._commit_threads:
            self._commit(commits, snap)
            return
        # Hand the commit wait to the pool: the overlay entries make the
        # not-yet-visible placements count against every later fit
        # re-check until the raft applies land.
        with self._inflight_cv:
            while self._inflight >= self.pipeline_depth \
                    and not self._stop.is_set():
                self._inflight_cv.wait(0.2)
            self._inflight += 1
            tokens = range(self._token_seq + 1,
                           self._token_seq + 1 + len(commits))
            self._token_seq = tokens[-1]
        for token, (_, result, _) in zip(tokens, commits):
            self._overlay.add(token, result)
        self._commit_q.put((commits, snap, tokens))

    def _commit(self, commits: List[Tuple[s.Plan, s.PlanResult, PlanFuture]],
                snap, tokens=()) -> None:
        """Apply the run's results through the log and answer each
        plan's future with its own outcome, none before the fsync that
        covers the run has returned (RaftLog.apply_many)."""
        t_commit = time.perf_counter()
        for _, _, future in commits:
            future.t_commit = t_commit
        plans = [plan for plan, _, _ in commits]
        # plan.apply and its six stages, from one set of stamps: the
        # stages tile it (APPLY_STAGES).
        stages = tracing.Stages("plan.apply.")
        ap = tracing.timed(self.metrics, "plan.apply", cpu=True,
                           attrs=lambda: tracing.plan_attrs(plans),
                           parent_id=commits[0][2].trace_parent)
        try:
            with ap:
                stages.begin("entry", ap.start)
                outcomes = self.apply_plans(
                    [(plan, result) for plan, result, _ in commits], snap,
                    stages)
        except Exception as exc:  # pragma: no cover — defensive
            outcomes = [exc] * len(commits)
        finally:
            # Remove only now: the FSM applies are visible in the live
            # store (or the plans failed and never will be) — there is
            # no window where a placement is in neither.
            for token in tokens:
                self._overlay.remove(token)
        stages.end(ap.end)
        for name in APPLY_STAGES:
            self.metrics.add_sample("plan.apply." + name,
                                    stages.seconds.get(name, 0.0) * 1000.0)
        stages.lay(ap.start, APPLY_STAGES, ap.span_id)
        for (plan, result, future), index in zip(commits, outcomes):
            # plan.respond (the submitter's sample) runs from the end of
            # plan.apply to the run's last answer.
            future.t_applied = ap.end
            if isinstance(index, Exception):
                self.logger.error("failed to apply plan for eval %s",
                                  plan.eval_id, exc_info=index)
                future.respond(None, index)
                continue
            # The program's own count of what it placed (a client can
            # only count complete evals x their group count).
            self.metrics.incr_counter(
                "plan.allocs_committed",
                sum(len(slab) for slab in result.alloc_slabs)
                + sum(len(allocs)
                      for allocs in result.node_allocation.values()))
            result.alloc_index = index
            if result.refresh_index:
                # Partial commit: ensure the scheduler sees at least
                # its own placements (plan_apply.go:187-193).
                result.refresh_index = max(result.refresh_index, index)
            future.respond(result, None)

    def _commit_loop(self) -> None:
        while True:
            item = self._commit_q.get()
            if item is None:
                return
            commits, snap, tokens = item
            try:
                self._commit(commits, snap, tokens)
            finally:
                with self._inflight_cv:
                    self._inflight -= 1
                    self._inflight_cv.notify_all()

    def _drain_inflight(self) -> None:
        with self._inflight_cv:
            while self._inflight and not self._stop.is_set():
                self._inflight_cv.wait(0.2)

    # -- evaluation --------------------------------------------------------

    def _evaluate_plans(self, snap, plans: List[s.Plan]
                        ) -> Optional[List[s.PlanResult]]:
        """One fit re-check for a run of plans.  One plan: evaluate_plan.
        Several (all groupable: new placements only): their slabs, and
        their per-object allocations merged per node in submission
        order, are re-checked as ONE plan's, by the routes that are
        there (a node where ports are placed takes the per-node route,
        allocs_fit over existing + proposed), against one read of the
        overlay and of the mirror.  Usage, bandwidth and held ports only
        grow along the run, so if every touched node fits with all of
        the run's placements added, every plan fits in sequence and each
        commits whole; if any does not, the pass decides nothing (None,
        counted by ``nomad.plan.group_undecided``) and the caller takes
        the plans one by one.  ``nomad.plan.submitted`` counts the plans
        each pass decided."""
        if len(plans) == 1:
            results = [self.evaluate_plan(snap, plans[0])]
        else:
            together = s.Plan(alloc_slabs=[
                slab for plan in plans for slab in plan.alloc_slabs])
            merged = together.node_allocation
            for plan in plans:
                for node_id, allocs in plan.node_allocation.items():
                    merged.setdefault(node_id, []).extend(allocs)
            fits = self._evaluate_nodes(snap, together, len(plans))
            results = ([self._whole(plan) for plan in plans]
                       if fits.all_fit() else None)
            if results is None:
                self.metrics.incr_counter("plan.group_undecided")
        self.metrics.incr_counter("plan.submitted", len(results or ()))
        return results

    @staticmethod
    def _whole(plan: s.Plan) -> s.PlanResult:
        """Every touched node fits: the plan commits as proposed, and
        no per-node verdict has to exist as a Python object."""
        result = s.PlanResult(node_update={}, node_allocation={})
        for proposed, kept in (
                (plan.node_update, result.node_update),
                (plan.node_allocation, result.node_allocation),
                (plan.node_preemptions, result.node_preemptions)):
            kept.update((nid, allocs) for nid, allocs in proposed.items()
                        if allocs)
        result.alloc_slabs.extend(plan.alloc_slabs)
        return result

    def evaluate_plan(self, snap, plan: s.Plan) -> s.PlanResult:
        """Determine the committable subset (plan_apply.go:202
        evaluatePlan): per-node fit re-check, partial or gang commit.
        Columnar alloc slabs (the TPU batch path) are kept whole on a full
        commit and filtered per node on a partial one."""
        fits = self._evaluate_nodes(snap, plan)
        if fits.all_fit():
            return self._whole(plan)

        result = s.PlanResult(node_update={}, node_allocation={})
        gang_failed = False
        ok_nodes = set()
        for node_id, fit in fits.as_dict().items():
            if not fit:
                if plan.all_at_once:
                    # gang semantics: all or nothing
                    result.node_update = {}
                    result.node_allocation = {}
                    result.node_preemptions = {}
                    gang_failed = True
                    break
                continue
            ok_nodes.add(node_id)
            if plan.node_update.get(node_id):
                result.node_update[node_id] = plan.node_update[node_id]
            if plan.node_allocation.get(node_id):
                result.node_allocation[node_id] = plan.node_allocation[node_id]
            if plan.node_preemptions.get(node_id):
                result.node_preemptions[node_id] = plan.node_preemptions[node_id]

        if not gang_failed:
            for slab in plan.alloc_slabs:
                filtered = slab.filter_nodes(ok_nodes)
                if len(filtered):
                    result.alloc_slabs.append(filtered)

        result.refresh_index = max(
            snap.table_index("nodes"), snap.table_index("allocs"))
        return result

    @staticmethod
    def _slab_node_adds(plan: s.Plan
                        ) -> Dict[str, List[Tuple[s.Allocation, int]]]:
        """Per-node (allocation, count) additions proposed by the plan's
        slabs (``AllocSlab.node_adds``: a network slab's rows read in
        place)."""
        out: Dict[str, List[Tuple[s.Allocation, int]]] = {}
        for slab in plan.alloc_slabs:
            for nid, adds in slab.node_adds().items():
                out.setdefault(nid, []).extend(adds)
        return out

    def _evaluate_nodes(self, snap, plan: s.Plan, n_plans: int = 1) -> _Fits:
        """The per-node fit re-check of ``plan``, which stands for
        ``n_plans`` submitted plans (a run's slabs taken together,
        _evaluate_plans).  Off the columnar mirror each touched node
        takes one of two routes, chosen from what the plan itself
        proposes there (_fit_columnar); a store without the mirror is
        walked.  Differential guard: its cadence
        (NOMAD_TPU_COLUMNAR_GUARD_EVERY) counts PLANS DECIDED; a pass in
        which the count crosses a multiple of it has ALL of its verdicts
        re-derived from the store's own rows, and they must agree (tests
        pin the cadence to 1: every tier-1 pass is double-checked).  A
        run's pass that found a row unfit decides nothing and is not
        counted: its plans are re-checked, and counted, one by one."""
        from ..state import columnar as colmod

        # Overlay FIRST, store second: a pipelined sibling whose commit
        # lands between the two reads is then counted TWICE (its
        # placements in the overlay snapshot AND in the store rows) —
        # conservative — instead of in neither view, which would let
        # two plans jointly over-commit a node.
        inflight = self._overlay.snapshot()
        columns_fn = getattr(snap, "columns", None)
        cols = (columns_fn() if columns_fn is not None and colmod.enabled()
                else None)
        if cols is None:
            adds = self._slab_node_adds(plan)
            out = _Fits(scalar=self._walk(snap, plan, inflight,
                                          self._touched(plan), adds))
            out.adds = adds
        else:
            out = self._fit_columnar(snap, plan, cols, inflight)
        self.metrics.incr_counter("plan.fit.rows_array", len(out.rows))
        self.metrics.incr_counter("plan.fit.rows_scalar", len(out.scalar))
        indexed = out.indexed
        every = colmod.guard_every()
        if cols is not None and every > 0 \
                and (n_plans == 1 or out.all_fit()):
            before = self._fit_guard_reads
            self._fit_guard_reads += n_plans
            if self._fit_guard_reads // every != before // every:
                start = time.perf_counter()
                tr = tracing.TRACER
                # Under plan.evaluate on this thread: its eval ids.
                with tracing.NOOP if tr is None else tr.span(
                        "plan.evaluate.guard", start=start):
                    out = self._guard_fit(snap, plan, cols, inflight, out)
                self.metrics.measure_since("plan.evaluate.guard", start)
        # After the guard's run, so right before the pass's
        # plan.evaluate sample: a window's edge (benchmarks read the
        # counter per such sample) then falls between them rarely.
        self.metrics.incr_counter("plan.fit.rows_indexed", indexed)
        return out

    @staticmethod
    def _touched(plan: s.Plan) -> List[str]:
        touched = {*plan.node_update, *plan.node_allocation,
                   *plan.node_preemptions}
        for slab in plan.alloc_slabs:
            touched.update(slab.node_ids)
        return list(touched)

    def _walk(self, snap, plan: s.Plan, inflight: List[_InflightPlan],
              node_ids: List[str], adds: Optional[Dict] = None
              ) -> Dict[str, bool]:
        """``_evaluate_nodes_walk`` over ``node_ids``; ``adds``: the
        plan's ``_slab_node_adds``, made here when not given."""
        return self._evaluate_nodes_walk(
            snap, plan, node_ids,
            self._slab_node_adds(plan) if adds is None else adds,
            _pending_map(inflight, node_ids))

    def _evaluate_nodes_walk(self, snap, plan: s.Plan,
                             node_ids: List[str], slab_adds: Dict,
                             overlay: Dict[str, list]) -> Dict[str, bool]:
        """allocs_fit's verdict per node.  A node where the plan or the
        overlay places network resources is decided by
        _evaluate_node_plan straight away (the vectorized route would
        compute that verdict too, after array work and a jit call on
        this thread); the others by one kernel call when there are
        VECTORIZE_THRESHOLD of them."""
        arrays = [nid for nid in node_ids
                  if not any(_has_ports(a) for a in itertools.chain(
                      plan.node_allocation.get(nid, ()),
                      (p for p, _ in slab_adds.get(nid, ())),
                      (p for p, _ in overlay.get(nid, ()))))]
        fits = (self._evaluate_nodes_vectorized(snap, plan, arrays,
                                                slab_adds, overlay)
                if len(arrays) >= VECTORIZE_THRESHOLD else {})
        return {nid: fits[nid] if nid in fits else self._evaluate_node_plan(
                    snap, plan, nid, slab_adds, overlay=overlay)
                for nid in node_ids}

    def _fit_columnar(self, snap, plan: s.Plan, cols,
                      inflight: List[_InflightPlan],
                      adds: Optional[Dict] = None) -> _Fits:
        """Fit re-check off the PR 9 columnar mirror: capacity, reserved,
        eligibility and LIVE USAGE come straight from the store's numpy
        columns (O(changed) fold) instead of walking every touched
        node's alloc objects.  A node takes the ARRAY route when all the
        plan proposes there is slab placements without network
        resources, no in-flight placement on it carries any, its mirror
        row exists, and the plan has enough such placements to repay
        array operations (columnar.ARRAY_MIN_ROWS): all such rows are
        decided by one gather and one comparison.  Every other node
        (evictions, preemptions and their staleness fence, per-object
        allocations, ports, rows the mirror dropped) takes the per-node
        route (_fit_scalar_rows)."""
        from ..state import columnar as colmod

        usage = snap.column_usage(cols)
        # Ordered set: dict keys.
        scalar: Dict[str, None] = dict.fromkeys(itertools.chain(
            plan.node_update, plan.node_allocation, plan.node_preemptions))
        array_slabs = []
        for slab in plan.alloc_slabs:
            proto = slab.proto
            if proto.resources is None or _has_ports(proto):
                scalar.update(dict.fromkeys(slab.node_ids))
            elif len(slab.node_ids):
                array_slabs.append(slab)
        if sum(len(slab.node_ids) for slab in array_slabs) \
                < colmod.ARRAY_MIN_ROWS:
            # Too few rows to repay the array operations' fixed cost.
            for slab in array_slabs:
                scalar.update(dict.fromkeys(slab.node_ids))
            array_slabs = []
        forced = scalar
        if any(p.net_nodes for p in inflight):
            forced = set(scalar).union(*(p.net_nodes for p in inflight))

        parts: List[Tuple[np.ndarray, tuple]] = []
        indexed = 0
        for slab in array_slabs:
            ids = slab.node_ids
            rows = colmod.gather_index(cols.row_of, ids)
            if type(ids) is s.NodeColumn:   # rows from its integers
                indexed += len(ids)
            bad = (rows < 0) | (rows >= cols.n)
            if forced:
                bad |= np.fromiter(map(forced.__contains__, ids),
                                   bool, len(ids))
            if bad.any():
                scalar.update(dict.fromkeys(
                    ids[i] for i in np.flatnonzero(bad).tolist()))
                rows = rows[~bad]
            parts.append((rows, s.alloc_usage_vec(slab.proto)))

        out = _Fits(cols)
        out.indexed = indexed
        all_rows = (np.concatenate([rows for rows, _ in parts])
                    if parts else out.rows)
        if all_rows.size:
            uniq, inv = np.unique(all_rows, return_inverse=True)
            need = cols.res[uniq] + usage[uniq]
            off = 0
            for rows, vec in parts:
                colmod.add_counts(need, inv[off:off + len(rows)], vec)
                off += len(rows)
            for pending in inflight:
                for ids, vec in pending.parts:
                    rows = colmod.gather_index(cols.row_of, ids)
                    pos = np.searchsorted(uniq, rows)
                    pos[pos == len(uniq)] = 0
                    colmod.add_counts(need, pos[uniq[pos] == rows], vec)
            out.rows = uniq
            out.fit = cols.eligible[uniq] & np.all(need <= cols.cap[uniq],
                                                   axis=1)
        if scalar:
            out.adds = self._slab_node_adds(plan) if adds is None else adds
            out.scalar = self._fit_scalar_rows(
                snap, plan, cols, usage, list(scalar), out.adds,
                _pending_map(inflight, scalar))
        return out

    def _fit_scalar_rows(self, snap, plan: s.Plan, cols, usage: np.ndarray,
                         node_ids: List[str], slab_adds: Dict,
                         overlay_map: Dict[str, list]) -> Dict[str, bool]:
        """The per-node route of the columnar re-check: plan removals,
        per-object adds and the overlay stay host-side Python (small),
        with a fallback to allocs_fit for port-reserving allocs and rows
        the mirror dropped."""
        out: Dict[str, bool] = {}
        for node_id in node_ids:
            if not self._preemptions_fresh(snap, plan, node_id):
                out[node_id] = False
                continue
            adds = plan.node_allocation.get(node_id, [])
            slab_here = slab_adds.get(node_id, [])
            overlay = overlay_map.get(node_id, ())
            if not adds and not slab_here:
                out[node_id] = True  # evict-only always fits
                continue
            row = cols.row_of.get(node_id)
            if (row is None or row >= cols.n
                    or any(_has_ports(a) for a in adds)
                    or any(p.resources is None or _has_ports(p)
                           for p, _ in slab_here)
                    or any(_has_ports(p) for p, _ in overlay)):
                # Port accounting / dropped mirror rows: scalar walk for
                # this node only.
                out[node_id] = self._evaluate_node_plan(
                    snap, plan, node_id, slab_adds, overlay=overlay_map)
                continue
            if not cols.eligible[row]:
                out[node_id] = False
                continue
            need = cols.res[row] + usage[row]
            # The rows the plan takes out or replaces here: stops,
            # preemptions and in-place updates (a stored row's copy with
            # its create_index; a new placement has none).
            for aid in {a.id for a in itertools.chain(
                    plan.node_update.get(node_id, ()),
                    plan.node_preemptions.get(node_id, ()),
                    (a for a in adds if a.create_index))}:
                live = snap.alloc_by_id(None, aid)
                if (live is not None and not live.terminal_status()
                        and live.node_id == node_id):
                    need = need - _usage(live)
            for alloc in adds:
                need = need + _usage(alloc)
            for proto, cnt in slab_here:
                need = need + cnt * _usage(proto)
            for proto, cnt in overlay:
                need = need + cnt * _usage(proto)
            out[node_id] = bool(np.all(need <= cols.cap[row]))
        return out

    def _guard_fit(self, snap, plan: s.Plan, cols,
                   inflight: List[_InflightPlan], out: _Fits) -> _Fits:
        """The differential guard of one re-check.  Array rows are held
        against the store's own rows (StateStore.fit_reference_rows:
        nodes_table and the alloc tables, nothing of the mirror, no
        Allocation materialized) plus the plan's and the overlay's adds
        — allocs_fit's arithmetic for rows without ports; per-node rows
        against the walk.  On a disagreement the full walk arbitrates:
        both passes read LIVE state, so a concurrent write (pipelined
        sibling commit, client status) between them yields a benign
        divergence that a re-run of the columnar pass will not reproduce
        against the walk's (newer) view; a real mirror bug will, is
        counted, and the walk's verdicts win."""
        from ..state import columnar as colmod

        array_ids = out.array_ids()
        scalar_ids = list(out.scalar)
        agree = True
        if array_ids:
            ready, cap, used = snap.fit_reference_rows(
                array_ids, out.rows, cols.row_of)
            pos_of = {nid: i for i, nid in enumerate(array_ids)}
            for slab in plan.alloc_slabs:
                colmod.add_node_counts(used, pos_of, slab.node_ids,
                                       s.alloc_usage_vec(slab.proto))
            for pending in inflight:
                for ids, vec in pending.parts:
                    colmod.add_node_counts(used, pos_of, ids, vec)
            agree = np.array_equal(
                ready & np.all(used <= cap, axis=1), out.fit)
        adds = out.adds
        if agree and scalar_ids:
            agree = self._walk(snap, plan, inflight, scalar_ids,
                               adds) == out.scalar
        if agree:
            return out
        if adds is None:
            adds = self._slab_node_adds(plan)
        ref = self._walk(snap, plan, inflight, array_ids + scalar_ids, adds)
        first = out.as_dict()
        if self._fit_columnar(snap, plan, cols, inflight,
                              adds).as_dict() != ref:
            bad = [nid for nid, fit in ref.items() if first.get(nid) != fit]
            colmod.note_guard_mismatch(
                "plan_fit", f"{len(bad)} node verdicts", Nodes=len(bad))
            self.logger.error(
                "columnar plan-fit guard mismatch on %d nodes "
                "(first: %s); using the walk's verdicts", len(bad), bad[:3])
        return _Fits(scalar=ref)

    def _preemptions_fresh(self, snap, plan: s.Plan, node_id: str) -> bool:
        """Optimistic-concurrency fence for preemption: every alloc the
        plan evicts must still exist, still be live, and be UNCHANGED
        (modify_index) since the scheduler's snapshot — a concurrent
        client update, stop, or re-plan rejects this node's commit and
        the scheduler replans against fresh state."""
        for preempted in plan.node_preemptions.get(node_id, []):
            existing = snap.alloc_by_id(None, preempted.id)
            if (existing is None or existing.terminal_status()
                    or existing.modify_index != preempted.modify_index):
                return False
        return True

    def _evaluate_node_plan(self, snap, plan: s.Plan, node_id: str,
                            slab_adds: Optional[Dict],
                            overlay: Dict[str, list]) -> bool:
        """(plan_apply.go:327 evaluateNodePlan).  ``overlay`` is the
        pre-captured in-flight placement snapshot (see _evaluate_nodes:
        it must be read BEFORE the store)."""
        if not self._preemptions_fresh(snap, plan, node_id):
            return False
        slab_here = (slab_adds or {}).get(node_id, [])
        if not plan.node_allocation.get(node_id) and not slab_here:
            return True  # evict-only always fits
        node = snap.node_by_id(None, node_id)
        if node is None or node.status != s.NODE_STATUS_READY or node.drain:
            return False
        proposed = snap.live_rows_on_node(node_id,
                                          _replaced_ids(plan, node_id))
        proposed.extend(plan.node_allocation.get(node_id, ()))
        for proto, cnt in slab_here:
            proposed.extend([proto] * cnt)
        # In-flight overlay: placements committed by pipelined siblings
        # but not yet visible in the store count against this node too.
        for proto, cnt in overlay.get(node_id, ()):
            proposed.extend([proto] * cnt)
        try:
            fit, _, _ = allocs_fit(node, proposed)
        except ValueError:
            return False
        return fit

    def _evaluate_nodes_vectorized(
        self, snap, plan: s.Plan, node_ids: List[str],
        slab_adds: Optional[Dict], overlay: Dict[str, list],
    ) -> Dict[str, bool]:
        """Batched re-check: one kernel call replaces the reference's
        NumCPU/2 verification pool (scalar network checks retained
        host-side)."""
        from ..ops.kernels import batch_allocs_fit
        import jax.numpy as jnp

        n = len(node_ids)
        capacity = np.zeros((n, 4), dtype=np.int64)
        used = np.zeros((n, 4), dtype=np.int64)
        ok_static = np.ones(n, dtype=bool)

        def res_vec(r: Optional[s.Resources]) -> np.ndarray:
            if r is None:
                return np.zeros(4, dtype=np.int64)
            return np.array([r.cpu, r.memory_mb, r.disk_mb, r.iops], dtype=np.int64)

        slab_adds = slab_adds or {}
        alloc_only: List[bool] = []
        scalar_fallback: Dict[str, bool] = {}
        for i, node_id in enumerate(node_ids):
            if not self._preemptions_fresh(snap, plan, node_id):
                # Stale preempted alloc: the staleness fence stays
                # host-side (by-id lookups), only the fit math vectorizes.
                alloc_only.append(False)
                ok_static[i] = False
                continue
            slab_here = slab_adds.get(node_id, [])
            if not plan.node_allocation.get(node_id) and not slab_here:
                alloc_only.append(True)
                continue
            alloc_only.append(False)
            node = snap.node_by_id(None, node_id)
            if node is None or node.status != s.NODE_STATUS_READY or node.drain:
                ok_static[i] = False
                continue
            capacity[i] = res_vec(node.resources)
            if node.reserved is not None:
                used[i] += res_vec(node.reserved)
            proposed = snap.live_rows_on_node(
                node_id, _replaced_ids(plan, node_id))
            proposed.extend(plan.node_allocation.get(node_id, ()))
            has_networks = False
            for alloc in proposed:
                if alloc.resources is not None:
                    used[i] += res_vec(alloc.resources)
                    has_networks = has_networks or bool(alloc.resources.networks)
                else:
                    used[i] += res_vec(alloc.shared_resources)
                    for tr in alloc.task_resources.values():
                        used[i] += res_vec(tr)
                        has_networks = has_networks or bool(tr.networks)
            for proto, cnt in slab_here:
                used[i] += cnt * res_vec(proto.resources)
            for proto, cnt in overlay.get(node_id, ()):
                # An in-flight per-object alloc may still carry only its
                # per-task resources: the canonical usage basis sums them.
                used[i] += cnt * _usage(proto)
            if has_networks:
                # Port/bandwidth accounting stays host-side: full scalar
                # re-check for nodes whose existing allocations hold
                # network resources (the walk keeps nodes where ports
                # are placed away from this route).
                scalar_fallback[node_id] = self._evaluate_node_plan(
                    snap, plan, node_id, slab_adds, overlay=overlay)

        # Pad the node axis to the next power of two: XLA compiles per
        # shape, and gang-scale plans otherwise mint a fresh compile for
        # every distinct touched-node count — measured as the dominant
        # serial applier cost under the multi-server gang workload.
        # Zero rows trivially fit and are sliced away below.
        padded = 1 << (n - 1).bit_length()
        if padded != n:
            capacity = np.concatenate(
                [capacity, np.zeros((padded - n, 4), dtype=np.int64)])
            used = np.concatenate(
                [used, np.zeros((padded - n, 4), dtype=np.int64)])
        fit, _ = batch_allocs_fit(
            jnp.asarray(capacity, dtype=jnp.int32),
            jnp.asarray(used, dtype=jnp.int32))
        fit = np.asarray(fit)[:n]
        out: Dict[str, bool] = {}
        for i, node_id in enumerate(node_ids):
            if alloc_only[i]:
                out[node_id] = True
            elif node_id in scalar_fallback:
                out[node_id] = scalar_fallback[node_id]
            else:
                out[node_id] = bool(ok_static[i] and fit[i])
        return out

    # -- apply -------------------------------------------------------------

    def apply_plan(self, plan: s.Plan, result: s.PlanResult, snap) -> int:
        """Commit the result through the log (plan_apply.go:123-175
        applyPlan): a run of one."""
        index, = self.apply_plans([(plan, result)], snap)
        if isinstance(index, Exception):
            raise index
        return index

    def apply_plans(self, items: List[Tuple[s.Plan, s.PlanResult]],
                    snap, stages: Optional[tracing.Stages] = None) -> list:
        """Commit a run's results through the log, ONE entry per plan,
        written back to back under one fsync (RaftLog.apply_many).
        Returns per plan, in order, its apply index or the exception
        that failed it: a plan whose entry could not be built or whose
        FSM apply raised fails alone.  ``stages``: the caller's stamper
        with ``entry`` running; left with ``post`` running."""
        if stages is None:
            stages = tracing.Stages("plan.apply.")
        outcomes: list = [None] * len(items)
        staged = []
        for pos, (plan, result) in enumerate(items):
            try:
                staged.append((pos, *self._plan_entry(plan, result, snap)))
            except Exception as exc:
                outcomes[pos] = exc
        stages.begin("write")
        applied = self.raft.apply_many(
            [(MessageType.APPLY_PLAN_RESULTS, payload)
             for _, payload, _, _ in staged])
        stages.begin("post")
        # The log's own split of the call (one set of stamps, its
        # own); what it does not name stays ``write``, which on a log
        # that splits nothing (multi-voter) is the whole call.
        timing = getattr(applied, "timing", None) or {}
        for name in ("encode", "sync", "fsm"):
            stages.carve(name, timing.get(name, 0.0), "write")
        for (pos, _, preempted, preemption_evals), outcome in zip(
                staged, applied):
            if isinstance(outcome, Exception):
                outcomes[pos] = outcome
                continue
            plan, result = items[pos]
            outcomes[pos] = index = outcome[1]
            self._plan_applied(plan, result, index, preempted,
                               preemption_evals)
        return outcomes

    def _plan_entry(self, plan: s.Plan, result: s.PlanResult, snap):
        """The plan's APPLY_PLAN_RESULTS payload, the allocs it preempts
        and their jobs' follow-up evals."""
        import time as _time

        # Fault point BEFORE the raft commit: an injected crash here is a
        # leader dying mid-plan-apply.  Nothing has been accepted yet, so
        # the invariant under test is that the submitting worker nacks,
        # the eval redelivers, and the replan commits everything — no
        # accepted placement is ever lost, no placement double-applies.
        act = fault.faultpoint("plan.apply", eval_id=plan.eval_id)
        if act is not None:
            if act.kind == "delay":
                _time.sleep(act.delay)
            elif act.kind in ("error", "crash", "step_down"):
                act.raise_injected()

        allocs: List[s.Allocation] = []
        for update_list in result.node_update.values():
            allocs.extend(update_list)
        for alloc_list in result.node_allocation.values():
            for alloc in alloc_list:
                # Log-entry slimming: every placement embeds the full
                # Job tree the payload already carries once — strip it
                # on a COPY (the scheduler still holds the originals)
                # and let upsert_plan_results re-denormalize.  Only
                # same-job, non-terminal placements qualify (that is
                # the exact condition the reattach checks).
                if (alloc.job is not None and plan.job is not None
                        and alloc.job_id == plan.job.id
                        and not alloc.terminal_status()):
                    alloc = alloc.copy()
                    alloc.job = None
                allocs.append(alloc)
        preempted: List[s.Allocation] = []
        for evicted_list in result.node_preemptions.values():
            allocs.extend(evicted_list)
            preempted.extend(evicted_list)
        now = _time.time()
        for alloc in allocs:
            if alloc.create_time == 0:
                alloc.create_time = now
        for slab in result.alloc_slabs:
            if slab.proto.create_time == 0:
                slab.proto.create_time = now

        # eval_id rides the payload for event-stream correlation: stop/
        # evict/lost updates keep their ORIGINAL placement eval on the
        # alloc row (AppendUpdate), so the driving eval travels separately.
        payload = {"job": plan.job, "allocs": allocs,
                   "eval_id": plan.eval_id}
        if result.alloc_slabs:
            payload["slabs"] = result.alloc_slabs
        preemption_evals: List[s.Evaluation] = []
        if preempted:
            # ONE raft apply carries the evictions, the placements, and
            # the preempted jobs' follow-up evals — evict + place land
            # atomically with the reschedule breadcrumb.
            preemption_evals = s.preemption_follow_up_evals(
                preempted, snap.latest_index(),
                job_lookup=lambda jid: snap.job_by_id(None, jid))
            payload["preemption_evals"] = preemption_evals
        return payload, preempted, preemption_evals

    def _plan_applied(self, plan: s.Plan, result: s.PlanResult, index: int,
                      preempted: List[s.Allocation],
                      preemption_evals: List[s.Evaluation]) -> None:
        """What follows a plan's raft apply at ``index``."""
        # Stale-snapshot fence bookkeeping: workers may not reuse a
        # cached snapshot for this job below this index (worker.py
        # _snapshot_covering).
        self.plan_queue.note_applied(
            plan.job.id if plan.job is not None else "", index)
        # Residency index plumbing (ops/resident.py): record the newest
        # plan-apply index so NodeStateDelta events can line residency
        # churn up against plan traffic.  sys.modules lookup keeps the
        # server import-light — if the ops package (and jax) was never
        # loaded, there is no resident cache to notify.
        import sys as _sys

        res_mod = _sys.modules.get("nomad_tpu.ops.resident")
        if res_mod is not None:
            res_mod.note_plan_applied(index)
        eb = self.raft.fsm.state.event_broker
        if eb is not None:
            # One plan-level summary on top of the per-alloc/slab events
            # the state store published during the apply: the decision
            # record (what this eval's plan did), keyed by eval.  This
            # publish runs after raft.apply returns, outside the
            # raft-serialized apply path, so a concurrent apply may have
            # already published a higher index — clamp keeps the stream
            # monotonic; PlanIndex preserves the true apply index.
            placed = (sum(len(v) for v in result.node_allocation.values())
                      + sum(len(sl.ids) for sl in result.alloc_slabs))
            eb.publish_one(
                s.TOPIC_PLAN, "PlanApplied", plan.eval_id, index,
                {"Placed": placed,
                 "Updated": sum(len(v) for v in result.node_update.values()),
                 "Preempted": len(preempted),
                 "Partial": bool(result.refresh_index),
                 "PlanIndex": index},
                eval_id=plan.eval_id, clamp=True)
        if preemption_evals:
            for ev in preemption_evals:
                ev.snapshot_index = index
            if self.blocked_evals is not None:
                self.blocked_evals.block_preempted(preemption_evals)
