"""Scheduling workers (reference: nomad/worker.go:55-538).

Worker        — per-eval loop: dequeue → wait-for-index → snapshot →
                scheduler.process → ack/nack; implements the scheduler's
                Planner interface by submitting to the plan queue and
                writing evals through the log.
BatchWorker   — the TPU-native replacement: drains the broker into
                fixed-size batches and invokes the 'tpu-batch' scheduler
                once per batch (batching replaces worker concurrency,
                SURVEY.md §2.9).
"""
from __future__ import annotations

import contextlib
import functools
import logging
import os
import threading
import time
from typing import List, Optional, Tuple

from ..scheduler import new_scheduler
from ..structs import structs as s
from ..utils import tracing
from ..utils.backoff import Backoff, wait_until
from ..utils.telemetry import NULL_TELEMETRY
from .eval_broker import EvalBroker, EvalBrokerError
from .fsm import MessageType
from .plan_queue import PlanQueue
from .raft import RaftLog

# How long to wait for raft catch-up to an eval's modify index
# (worker.go:229 waitForIndex; default timeout 5s).
DEQUEUE_TIMEOUT = 0.5
RAFT_SYNC_LIMIT = 5.0


def stale_snapshot_enabled() -> bool:
    """Stale-snapshot scheduling (the reference's optimistic-concurrency
    design, PAPER.md L3): workers REUSE a recent state snapshot instead
    of copying the whole store per eval, as long as it covers the eval's
    trigger indexes — any staleness it carries is caught by the plan
    applier's per-node re-check, which partially commits and refreshes
    the scheduler.  Default on; NOMAD_TPU_STALE_SNAPSHOT=0 restores the
    snapshot-per-eval path."""
    from ..utils import knobs

    return knobs.get_bool("NOMAD_TPU_STALE_SNAPSHOT")


def _stale_snapshot_max_lag() -> int:
    """How many raft entries a reused snapshot may lag the applied index
    before the worker refreshes anyway — bounds the conflict rate under
    churn without giving up cross-eval reuse."""
    from ..utils import knobs

    return knobs.get_int("NOMAD_TPU_STALE_SNAPSHOT_LAG")


def submit_plans(worker: "Worker",
                 items: List[Tuple["WorkerPlanner", s.Plan]]) -> list:
    """One submission to the plan queue: each plan stamped with its own
    eval's token and snapshot index, every eval's nack clock held for
    the wait (the queue is unbounded), ONE queue item (one plan:
    ``enqueue``; a batch's plans: ``enqueue_group``, which the applier
    decides together), one wait.  Returns ``(result, refreshed state)``
    per plan, in order; a plan that failed raises, once every future of
    the submission has been answered."""
    plans = [plan for _, plan in items]
    for planner, plan in items:
        plan.eval_token = planner.token
        if planner.snapshot_index is not None:
            plan.snapshot_index = planner.snapshot_index
    with worker._nack_clocks_held(
            [(planner.eval, planner.token) for planner, _ in items]):
        tr = tracing.TRACER
        submit_span = tracing.NOOP if tr is None else tr.span(
            "worker.submit_plan", **tracing.plan_attrs(plans))
        with submit_span as sp:
            # Armed, the span id rides the futures: the applier's
            # spans (other threads) name it as their parent.
            parent = 0 if tr is None else sp.span_id
            queue = worker.plan_queue
            futures = ([queue.enqueue(plans[0], trace_parent=parent)]
                       if len(plans) == 1
                       else queue.enqueue_group(plans, trace_parent=parent))
            results, failed = [], None
            for future in futures:
                try:
                    results.append(future.wait())
                except Exception as exc:
                    results.append(None)
                    failed = failed or exc
            _emit_round_trip(worker.metrics, futures[-1], tracing.now(), tr)
    if failed is not None:
        raise failed
    return [(result, planner._refreshed(result))
            for (planner, _), result in zip(items, results)]


def _emit_round_trip(metrics, future, t_woke: float, tr) -> None:
    """A submission's hand-offs, from the stamps its last future
    collected: queue_wait (enqueued → claimed by the applier),
    commit_wait (evaluate done → _commit entered; 0 for a plan with
    nothing to commit), respond (plan.apply done → the submission's
    last future answered) and wake (answered → this thread running
    again).  With plan.evaluate and plan.apply they sum to the round
    trip.  A remote future (follower scheduling) carries no stamps
    and emits nothing."""
    t_claimed = getattr(future, "t_claimed", 0.0)
    if not t_claimed or not future.t_responded:
        return
    t_commit = future.t_commit or future.t_evaluated
    t_applied = future.t_applied or t_commit
    metrics.add_sample("plan.queue_wait",
                       (t_claimed - future.t_enqueued) * 1000.0)
    metrics.add_sample("plan.commit_wait",
                       (t_commit - future.t_evaluated) * 1000.0)
    metrics.add_sample("plan.respond",
                       (future.t_responded - t_applied) * 1000.0)
    metrics.add_sample("plan.wake", (t_woke - future.t_responded) * 1000.0)
    if tr is not None:
        # Recorded inside worker.submit_plan: it is their parent.
        tr.record("plan.queue_wait", future.t_enqueued, t_claimed)
        tr.record("plan.commit_wait", future.t_evaluated, t_commit)
        tr.record("plan.respond", t_applied, future.t_responded)
        tr.record("plan.wake", future.t_responded, t_woke)


class WorkerPlanner:
    """The scheduler.Planner implementation workers hand to schedulers
    (worker.go:300-499)."""

    def __init__(self, worker: "Worker", ev: s.Evaluation, token: str,
                 snapshot_index: Optional[int] = None):
        self.worker = worker
        self.eval = ev
        self.token = token
        # The applied index captured WHEN the scheduler's snapshot was
        # taken (worker.go:262 w.snapshotIndex).  Blocked evals must
        # carry this — not the apply index at creation time — or a
        # capacity change landing while the eval is inside the scheduler
        # looks already-seen to BlockedEvals._missed_unblock and the
        # eval sleeps forever.
        self.snapshot_index = snapshot_index

    def submit_plan(self, plan: s.Plan):
        """(worker.go:300 SubmitPlan) — pause the nack timer while in the
        unbounded plan queue, attach the eval token for fencing."""
        return submit_plans(self.worker, [(self, plan)])[0]

    def _refreshed(self, result: Optional[s.PlanResult]):
        """The state a scheduler retries on after a partial commit."""
        if result is None or not result.refresh_index:
            return None
        # Wait for our state to catch up, then hand a refreshed
        # snapshot to the scheduler (worker.go:335-350).  The
        # refresh also replaces the worker's stale-snapshot cache —
        # a conflict means the cached view lost its bet.
        w = self.worker
        w.wait_for_index(result.refresh_index, RAFT_SYNC_LIMIT)
        idx = w.raft.applied_index()
        state = w.raft.fsm.state.snapshot()
        if w._stale_ok:
            w._snap_cache = (idx, state)
        self.snapshot_index = idx
        return state

    def update_eval(self, ev: s.Evaluation) -> None:
        self.worker.apply_eval_updates([ev])

    def _snapshot_index(self) -> int:
        if self.snapshot_index is not None:
            return self.snapshot_index
        return self.worker.raft.applied_index()

    def create_eval(self, ev: s.Evaluation) -> None:
        ev.snapshot_index = self._snapshot_index()
        self.worker.apply_eval_updates([ev])

    def reblock_eval(self, ev: s.Evaluation) -> None:
        """(worker.go:470 ReblockEval) — update snapshot index and hand it
        to the blocked tracker via the broker requeue path."""
        ev.snapshot_index = self._snapshot_index()
        self.worker.reblock_eval_update(ev, self.token)


class Worker:
    """One scheduling worker (count = num_schedulers, config.go:250)."""

    def __init__(
        self,
        broker: EvalBroker,
        plan_queue: PlanQueue,
        raft: RaftLog,
        schedulers: Optional[List[str]] = None,
        blocked_evals=None,
        logger: Optional[logging.Logger] = None,
        time_table=None,
        metrics=None,
    ):
        self.broker = broker
        self.plan_queue = plan_queue
        self.raft = raft
        self.metrics = metrics if metrics is not None else NULL_TELEMETRY
        self.blocked_evals = blocked_evals
        self.time_table = time_table
        self.schedulers = schedulers or [
            s.JOB_TYPE_SERVICE, s.JOB_TYPE_BATCH, s.JOB_TYPE_SYSTEM, s.JOB_TYPE_CORE]
        self.logger = logger or logging.getLogger("nomad_tpu.worker")
        self._stop = threading.Event()
        self._paused = False
        self._pause_cond = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        # Jittered idle backoff for a disabled broker (follower workers):
        # a fixed 50ms nap synchronized every worker's retry into one
        # thundering dequeue per tick.
        self._idle_backoff = Backoff(base=0.02, max_delay=0.5)
        # Stale-snapshot cache: (applied index at snapshot time, the
        # snapshot).  Reused across evals while it covers the eval's
        # trigger indexes and isn't too far behind the log — the paper's
        # schedule-anywhere-off-a-snapshot discipline; plan-apply's
        # re-check owns correctness.  Per-worker (no lock needed).
        self._stale_ok = stale_snapshot_enabled()
        self._snap_cache: Optional[Tuple[int, object]] = None
        self._snap_max_lag = _stale_snapshot_max_lag()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(target=self.run, daemon=True, name="worker")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self.set_pause(False)
        if self._thread is not None:
            self._thread.join(timeout=5.0)

    def set_pause(self, paused: bool) -> None:
        """The leader pauses 3/4 of workers (leader.go:114-120)."""
        with self._pause_cond:
            self._paused = paused
            self._pause_cond.notify_all()

    def _check_paused(self) -> None:
        with self._pause_cond:
            while self._paused and not self._stop.is_set():
                self._pause_cond.wait(0.5)

    # -- loop --------------------------------------------------------------

    # How many ready evals one dequeue drains.  Each eval is still
    # scheduled/acked individually (latency and nack semantics are
    # per-eval, unlike BatchWorker's one-kernel-per-batch), but the
    # FIRST eval's fresh snapshot covers its batch-mates' trigger
    # indexes — they were all written before the dequeue — so under
    # backlog the stale-snapshot cache turns one O(cluster) store copy
    # into GREEDY_BATCH evals' worth of scheduling.  Idle brokers
    # return a single eval (or none); the latency-optimal light-load
    # path is unchanged.
    GREEDY_BATCH = 8

    def run(self) -> None:
        while not self._stop.is_set():
            self._check_paused()
            for ev, token in self._dequeue_batch():
                if self._stop.is_set():
                    # Shutting down mid-batch: hand undone evals back
                    # for redelivery instead of scheduling into a
                    # stopping server.
                    try:
                        self.broker.nack(ev.id, token)
                    except EvalBrokerError:
                        pass
                    continue
                # The nack deadline guards PROCESSING, not in-worker
                # queue wait (paused at dequeue below): resume as this
                # eval's turn starts.  A resume failure means the
                # delivery already burned (broker flushed on leadership
                # loss) — skip rather than double-schedule.
                try:
                    self.broker.resume_nack_timeout(ev.id, token)
                except EvalBrokerError:
                    continue
                self.process_eval(ev, token)

    def _dequeue_batch(self) -> List[Tuple[s.Evaluation, str]]:
        try:
            batch = self.broker.dequeue_batch(
                self.schedulers, self.GREEDY_BATCH, DEQUEUE_TIMEOUT)
        except EvalBrokerError:
            time.sleep(self._idle_backoff.next_delay())
            return []
        self._idle_backoff.reset()
        # Pause every batch-mate's nack deadline: the clock must cover
        # one eval's processing (the single-dequeue contract), not its
        # wait behind up to GREEDY_BATCH-1 predecessors — a mid-batch
        # expiry would redeliver an eval this worker is still going to
        # schedule, and same-job double placement is exactly what the
        # capacity re-check cannot catch.
        for ev, token in batch:
            try:
                self.broker.pause_nack_timeout(ev.id, token)
            except EvalBrokerError:
                pass
        return batch

    # The unit of the UNSUFFIXED worker.invoke_scheduler histogram is one
    # scheduler invocation.  For this worker that's one eval; BatchWorker
    # overrides to False because its invocations are whole batches
    # (emitted by TPUBatchScheduler._emit_batch_stats) and mixing its
    # per-eval system/core timings into the same key would conflate two
    # units of work in one percentile window.
    unsuffixed_invoke_sample = True

    def process_eval(self, ev: s.Evaluation, token: str) -> None:
        """Dequeue→schedule→ack cycle (worker.go:106-227)."""
        # Branch on the tracer before building attrs: delivery_attempts
        # takes the broker lock, which the disarmed path must not pay.
        tr = tracing.TRACER
        attempt_span = tracing.NOOP if tr is None else tr.span(
            "worker.attempt", eval_id=ev.id, eval_type=ev.type,
            attempt=self.broker.delivery_attempts(ev.id))
        unsuffixed = (self.metrics if self.unsuffixed_invoke_sample
                      else NULL_TELEMETRY)
        with attempt_span as sp:
            try:
                with self.metrics.measure("worker.wait_for_index"), \
                        tracing.span("worker.wait_for_index"):
                    self.wait_for_index(ev.modify_index, RAFT_SYNC_LIMIT)
                with unsuffixed.measure("worker.invoke_scheduler"), \
                        self.metrics.measure(
                            f"worker.invoke_scheduler.{ev.type}"), \
                        tracing.span("worker.invoke_scheduler"):
                    self.invoke_scheduler(ev, token)
                self.broker.ack(ev.id, token)
            except Exception as exc:
                self.logger.exception("eval %s failed; nacking", ev.id)
                sp.set(nack_reason=f"{type(exc).__name__}: {exc}")
                self.record_eval_failure(ev, exc)
                try:
                    self.broker.nack(ev.id, token)
                except EvalBrokerError:
                    pass

    def record_eval_failure(self, ev: s.Evaluation, exc: Exception) -> None:
        self.record_eval_failures([ev], exc)

    def record_eval_failures(self, evs: List[s.Evaluation],
                             exc: Exception) -> None:
        """Persist WHY these delivery attempts burned onto the evals, so
        ``eval-status`` shows it — without this, the worker-side traceback
        is the only artifact of a nacked attempt.  One raft apply for the
        whole batch (the FSM handler takes a list), and recorded BEFORE
        the nacks: while an eval is outstanding the broker's enqueue
        dedup ignores the status write's enqueue hook, so the update
        can't double-queue it."""
        failed = []
        for ev in evs:
            attempt = self.broker.delivery_attempts(ev.id)
            f = ev.copy()
            f.status_description = (
                f"scheduler error on delivery attempt {attempt}: "
                f"{type(exc).__name__}: {exc}")
            failed.append(f)
        try:
            self.apply_eval_updates(failed)
        except Exception:
            # Recording forensics must never mask the nack itself (e.g.
            # leadership was lost — the next leader redelivers anyway).
            self.logger.debug("could not record failure reason for %d "
                              "evals", len(failed), exc_info=True)

    @contextlib.contextmanager
    def _nack_clocks_held(self, batch):
        """Hold the batch's nack clocks; resuming restarts each eval's
        full timeout (the WorkerPlanner.submit_plan discipline)."""
        held = []
        for ev, token in batch:
            try:
                self.broker.pause_nack_timeout(ev.id, token)
                held.append((ev.id, token))
            except EvalBrokerError:
                pass
        try:
            yield
        finally:
            for eval_id, token in held:
                try:
                    self.broker.resume_nack_timeout(eval_id, token)
                except EvalBrokerError:
                    pass

    # -- leader-write hooks ------------------------------------------------
    # The two write surfaces workers/planners touch beyond plan
    # submission.  On a leader-local worker they go straight through the
    # log; FollowerWorker (server/follower_sched.py) overrides both to
    # forward over the wire, which is what lets one WorkerPlanner serve
    # both sides.

    def apply_eval_updates(self, evals: List[s.Evaluation]) -> None:
        self.raft.apply(MessageType.EVAL_UPDATE, {"evals": evals})

    def apply_eval_statuses(self, evals: List[s.Evaluation]) -> None:
        """A batch's eval statuses: one EVAL_UPDATE entry per eval, in
        the order given (the batch's plan order), written back to back
        under ONE fsync.  An entry each, not one entry for the list: an
        eval's ``modify_index`` then still says where its completion
        fell among the batch's, which is how a reader of the store
        (the benchmark's replay in commit order) orders a batch's
        plans."""
        failed = None
        for outcome in self.raft.apply_many(
                [(MessageType.EVAL_UPDATE, {"evals": [ev]}) for ev in evals]):
            if isinstance(outcome, Exception):
                failed = failed or outcome
        if failed is not None:
            raise failed

    def reblock_eval_update(self, ev: s.Evaluation, token: str) -> None:
        self.apply_eval_updates([ev])
        if self.blocked_evals is not None:
            self.blocked_evals.reblock(ev, token)

    def wait_for_index(self, index: int, timeout: float) -> bool:
        """Wait for log catch-up (worker.go:229).  Backed-off polling:
        sub-millisecond first checks for the common just-behind case,
        ramping to a coarse interval so a genuinely stalled log doesn't
        pin a core.  The relaxed read keeps M polling workers off the
        raft lock (it under-reports by at most an in-flight entry,
        which the next poll observes)."""
        return wait_until(
            lambda: self.raft.applied_index_relaxed() >= index,
            timeout, initial=0.0005, max_interval=0.005)

    def sched_name(self, ev: s.Evaluation) -> str:
        """Scheduler-registry name for an eval (overridable: the batch
        worker swaps in vectorized implementations)."""
        return ev.type

    def _required_index(self, ev: s.Evaluation) -> int:
        """The lowest applied index a snapshot must cover to schedule
        ``ev`` safely — the eval's TRIGGER indexes, not its own write
        index (requiring ev.modify_index would force a fresh snapshot
        for every eval created after the cache, defeating reuse under
        exactly the backlog conditions reuse exists for):

        - ``job_modify_index``  — the job write the eval reconciles
          (job register/update/deregister paths stamp it);
        - ``node_modify_index`` — the node transition (node evals);
        - ``snapshot_index``    — what the last scheduling attempt saw,
          raised to the UNBLOCK index by BlockedEvals on re-admission
          (preemption follow-ups and requeues ride this too);
        - the job's newest committed plan (plan_queue.applied_index_for)
          — broker per-job serialization orders eval N+1's DEQUEUE
          after eval N's plan apply, but not its CREATION, and a
          snapshot missing the job's own placements would double-place
          them (capacity re-checks cannot catch same-job duplication).
        """
        if ev.type == s.JOB_TYPE_CORE:
            # GC sweeps must see current state: a pinned stale cache
            # would hide newly-terminal rows from the core scheduler
            # indefinitely (GC is rare; a fresh snapshot is cheap).
            return self.raft.applied_index()
        return max(ev.trigger_index(),
                   self.plan_queue.applied_index_for(ev.job_id))

    def _snapshot_covering(self, required: int) -> Tuple[int, object]:
        """(index, snapshot) with index >= required.  With stale-snapshot
        scheduling enabled the cached snapshot is reused while it covers
        ``required`` and lags the log by at most the configured bound —
        dropping the O(cluster) store copy from the per-eval path; any
        capacity staleness is the plan applier's re-check problem
        (optimistic concurrency).  Index is read BEFORE the snapshot is
        taken so a blocked eval's snapshot_index never overstates what
        the scheduler saw."""
        if self._stale_ok:
            cached = self._snap_cache
            if cached is not None and cached[0] >= required \
                    and self.raft.applied_index_relaxed() - cached[0] \
                    <= self._snap_max_lag:
                self.metrics.incr_counter("worker.snapshot_reuse")
                return cached
        snapshot_index = self.raft.applied_index()
        snap = self.raft.fsm.state.snapshot()
        if self._stale_ok:
            self._snap_cache = (snapshot_index, snap)
            self.metrics.incr_counter("worker.snapshot_fresh")
        return snapshot_index, snap

    def invoke_scheduler(self, ev: s.Evaluation, token: str) -> None:
        """(worker.go:262): snapshot state, instantiate by eval type."""
        required = self._required_index(ev)
        # The fence is a WAIT, not just a cache-choice input: with
        # multi-voter raft the FSM applier is asynchronous, so even a
        # leader-local fresh snapshot can predate a committed plan
        # still draining (e.g. pre-failover plans under the restored
        # fence floor).  Covered already ⇒ the first poll returns
        # immediately; a wedged applier raises and the eval nacks.
        if not self.wait_for_index(required, RAFT_SYNC_LIMIT):
            raise RuntimeError(
                f"state did not reach fence {required} within "
                f"{RAFT_SYNC_LIMIT}s for eval {ev.id}")
        snapshot_index, snap = self._snapshot_covering(required)
        planner = WorkerPlanner(self, ev, token,
                                snapshot_index=snapshot_index)
        sched_name = self.sched_name(ev)
        if ev.type == s.JOB_TYPE_CORE:
            from .core_sched import CoreScheduler

            CoreScheduler(self.logger, snap, planner, self.raft,
                          time_table=self.time_table).process(ev)
            return
        sched = new_scheduler(sched_name, self.logger, snap, planner)
        sched.process(ev)


class _MuxPlanner:
    """Routes planner calls to the owning eval's WorkerPlanner."""

    def __init__(self, worker: "Worker", batch, snapshot_index: int):
        self.worker = worker
        # The plans of the submission being gathered (submit_plans).
        self._group: Optional[list] = None
        self.planners = {
            ev.id: WorkerPlanner(worker, ev, token,
                                 snapshot_index=snapshot_index)
            for ev, token in batch}

    def submit_plan(self, plan):
        """One eval's plan on its way to the plan queue: the one place a
        plan is handed over, whichever way the batch submits (what wraps
        it, as the benchmark's fault harness does, meets every plan).
        Alone, the plan is submitted and waited for; inside
        ``submit_plans`` it joins the batch's one submission and its
        answer comes with the group's."""
        item = (self.planners[plan.eval_id], plan)
        if self._group is None:
            return submit_plans(self.worker, [item])[0]
        self._group.append(item)
        return None

    def submit_plans(self, plans):
        """The batch's plans as one submission, each handed over through
        ``submit_plan`` (a list of one is ``submit_plan``)."""
        if len(plans) == 1:
            return [self.submit_plan(plans[0])]
        self._group = items = []
        try:
            for plan in plans:
                self.submit_plan(plan)
        finally:
            self._group = None
        return submit_plans(self.worker, items)

    def update_eval(self, ev):
        p = self.planners.get(ev.id) or next(iter(self.planners.values()))
        p.update_eval(ev)

    def update_evals(self, evs):
        """The batch's eval statuses in one write (one fsync)."""
        self.worker.apply_eval_statuses(evs)

    def create_eval(self, ev):
        p = self.planners.get(ev.previous_eval) or next(iter(self.planners.values()))
        p.create_eval(ev)

    def reblock_eval(self, ev):
        p = self.planners.get(ev.id) or next(iter(self.planners.values()))
        p.reblock_eval(ev)


class _BatchCtx:
    """One in-flight batch of the pipelined drain: broker tokens +
    scheduler + its prepared/dispatched state."""

    __slots__ = ("batch", "sched", "prep", "attempts", "t0")

    def __init__(self, batch, sched, prep, attempts, t0):
        self.batch = batch
        self.sched = sched
        self.prep = prep
        self.attempts = attempts
        # Start of the batch's PROCESSING (before wait_for_index /
        # snapshot / prepare), so the pipelined latency samples cover
        # the same window the serial path's measure() does.
        self.t0 = t0


def pipeline_enabled() -> bool:
    """Opt-in double-buffered batch drain (NOMAD_TPU_PIPELINE=1): while
    batch k's device pass is in flight the worker dequeues + runs the
    host phases of batch k+1, then finalizes k before k+1's usage delta
    is built — see ops/batch_sched.schedule_stream for the ordering
    argument.  Off by default: the serial drain is the long-soaked
    path."""
    from ..utils import knobs

    return knobs.get_bool("NOMAD_TPU_PIPELINE")


class BatchWorker(Worker):
    """Drains evals in batches into the TPU batch scheduler.

    Service and batch evals are batched (their placement logic is the
    generic scheduler's); system evals run through the vectorized
    'tpu-system' pass; core evals stay on the oracle path.
    """

    # Batch invocations own the unsuffixed worker.invoke_scheduler key
    # (see Worker.unsuffixed_invoke_sample).
    unsuffixed_invoke_sample = False

    def __init__(self, *args, max_batch: int = 64, mesh=None, **kwargs):
        super().__init__(*args, **kwargs)
        self.max_batch = max_batch
        # Optional device mesh: placement passes run node-sharded over it
        # (each federated region schedules on its own slice).
        self.mesh = mesh
        # Seconds of the running cycle that its named parts have taken
        # so far (_cycle; this thread's alone).
        self._cycle_named = 0.0
        # Before this process compiles anything: JAX latches the
        # persistent cache off at the first compile without a directory.
        from ..utils.platform import ensure_compile_cache

        ensure_compile_cache()

    def sched_name(self, ev: s.Evaluation) -> str:
        if ev.type == s.JOB_TYPE_SYSTEM:
            from ..ops import system_batch  # noqa: F401 — registers it

            return "tpu-system"
        return super().sched_name(ev)

    def run(self) -> None:
        from ..ops import batch_sched  # noqa: F401 — registers 'tpu-batch'

        pipelined = pipeline_enabled()
        while not self._stop.is_set():
            self._check_paused()
            self._cycle(pipelined)

    def _cycle(self, pipelined: bool) -> None:
        """One turn of the loop: dequeue a batch, process it, poll
        system/core.  A serial turn that took a batch is one
        ``worker.cycle`` sample (entry of the dequeue → return of the
        poll), tiled by ``worker.dequeue``, ``.wait_for_index``,
        ``.snapshot``, ``.invoke_scheduler``, ``.release`` and ``.ack``;
        what they leave is ``worker.cycle.unnamed``.  In a standing
        backlog the cycles abut."""
        t0 = tracing.now()
        try:
            batch = self.broker.dequeue_batch(
                [s.JOB_TYPE_SERVICE, s.JOB_TYPE_BATCH],
                self.max_batch, DEQUEUE_TIMEOUT)
        except EvalBrokerError:
            time.sleep(self._idle_backoff.next_delay())
            return
        t_got = tracing.now()
        self._idle_backoff.reset()
        serial = bool(batch) and not pipelined
        if serial:
            self.metrics.add_sample("worker.dequeue", (t_got - t0) * 1000.0)
            self._cycle_named = t_got - t0
            with self.metrics.measure("worker.invoke_scheduler.batch"):
                self.process_batch(batch)
        elif batch:
            # Per-batch latency samples are taken at each batch's
            # finish (one drain spans many batches — a single
            # measure() here would corrupt the histogram).
            self._process_batches_pipelined(batch)
        # Always also poll system/core (zero timeout) so a sustained
        # service/batch stream cannot starve them.
        self._poll_system_core()
        if serial:
            t_end = tracing.now()
            self.metrics.add_sample("worker.cycle", (t_end - t0) * 1000.0)
            self.metrics.add_sample(
                "worker.cycle.unnamed",
                max(0.0, t_end - t0 - self._cycle_named) * 1000.0)
            # No eval ids: the dequeue that returned a batch began
            # before its evals existed (the wait for work is in it).
            tracing.record("worker.dequeue", t0, t_got, num_evals=len(batch))
            tracing.record("worker.cycle", t0, t_end, num_evals=len(batch))

    def _poll_system_core(self) -> None:
        try:
            ev, token = self.broker.dequeue(
                [s.JOB_TYPE_SYSTEM, s.JOB_TYPE_CORE], 0)
        except EvalBrokerError:
            return
        if ev is not None:
            self.process_eval(ev, token)

    def process_batch(self, batch: List[Tuple[s.Evaluation, str]]) -> None:
        tr = tracing.TRACER
        if tr is None:
            self._process_batch(batch)
            return
        with tr.span("worker.process_batch",
                     num_evals=len(batch),
                     **tracing.eval_id_attrs(
                         (ev for ev, _ in batch), len(batch))) as sp:
            stats = self._process_batch(batch)
            if stats is not None and stats.device_ran:
                # Fused-path forensics at the worker boundary: which
                # program shape served the batch and what it cost on the
                # link (the single-fetch contract is auditable per batch
                # from the span tree alone).
                sp.set(fused=stats.fused, quantized=stats.quantized,
                       fetch_bytes=stats.fetch_bytes,
                       commit_s=round(stats.commit_seconds, 4))

    def _compile_guard(self, batch):
        """XLA compilation runs outside the nack clock: a cold shape
        bucket compiles for tens of seconds on a TPU (69 s for the
        64-spec bucket at 10k nodes, chip run of PR 21) — past the 60 s
        nack timeout, which redelivered the whole batch mid-compile.
        The clock guards processing; kernels.program_call enters this
        only around a program signature's first invocation."""
        from ..ops import kernels

        return kernels.compile_guard(
            functools.partial(self._nack_clocks_held, batch))

    def _snapshot(self):
        """The batch's state snapshot, timed: sample ``worker.snapshot``
        always, a span of the same two stamps when the tracer is armed."""
        with tracing.timed(self.metrics, "worker.snapshot",
                           annotate=True) as t:
            snap = self.raft.fsm.state.snapshot()
        self._cycle_named += t.end - t.start
        return snap

    def _process_batch(self, batch: List[Tuple[s.Evaluation, str]]):
        """Returns the batch's BatchStats, or None when the batch was
        nacked."""
        max_index = max(ev.modify_index for ev, _ in batch)
        with tracing.timed(self.metrics, "worker.wait_for_index") as t:
            self.wait_for_index(max_index, RAFT_SYNC_LIMIT)
        self._cycle_named += t.end - t.start
        # Always a fresh snapshot on the batch path: the device-resident
        # usage mirror advances by inter-snapshot deltas, and a reused
        # pre-apply snapshot would hide the previous batch's own
        # placements from the next batch's usage encode (conflict churn
        # the per-eval stale-snapshot pool tolerates, the batched kernel
        # path should not).
        snapshot_index = self.raft.applied_index()
        snap = self._snapshot()

        # One scheduler instance per batch; per-eval planners for correct
        # token fencing on ack/nack.
        from ..ops.batch_sched import TPUBatchScheduler

        mux = _MuxPlanner(self, batch, snapshot_index)
        sched = TPUBatchScheduler(self.logger, snap, mux, mesh=self.mesh,
                                  metrics=self.metrics,
                                  snapshot_index=snapshot_index)
        tr = tracing.TRACER
        # Attempt numbers belong to THIS delivery, so capture them before
        # scheduling: a nack-timeout firing mid-batch redelivers the eval
        # and bumps the counter, and reading it afterwards would stamp
        # this delivery's marker with the next delivery's number.
        attempts = {} if tr is None else {
            ev.id: self.broker.delivery_attempts(ev.id)
            for ev, _ in batch}
        t_call = tracing.now()
        try:
            with self._compile_guard(batch):
                stats = sched.schedule_batch([ev for ev, _ in batch])
        except Exception as exc:
            self.logger.exception("batch scheduling failed; nacking batch")
            self.record_eval_failures([ev for ev, _ in batch], exc)
            for ev, token in batch:
                if tr is not None:
                    # Per-eval attempt marker with the nack reason: the
                    # batch path's twin of the worker.attempt span, so a
                    # redelivered eval's trace explains every burn.
                    tr.event("worker.attempt", eval_id=ev.id,
                             attempt=attempts[ev.id],
                             nack_reason=f"{type(exc).__name__}: {exc}")
                try:
                    self.broker.nack(ev.id, token)
                except EvalBrokerError:
                    pass
            return None
        # What the scheduler call took beside the batch itself: its
        # stats published (~90 sink calls) and, mostly, its frames
        # released (the batch's AllocMetrics, tensors and plans freed).
        t_ret = tracing.now()
        release = max(0.0, t_ret - t_call - stats.total_seconds)
        self.metrics.add_sample("worker.release", release * 1000.0)
        tracing.record("worker.release", t_ret - release, t_ret)
        self._cycle_named += stats.total_seconds + release
        with tracing.timed(self.metrics, "worker.ack",
                           num_evals=len(batch)) as t:
            self._ack_batch(batch, attempts)
        self._cycle_named += t.end - t.start
        return stats

    def _ack_batch(self, batch, attempts) -> None:
        """Ack every eval of a scheduled batch, with its per-delivery
        ``worker.attempt`` marker when the tracer was armed as the
        batch's attempt numbers were taken."""
        tr = tracing.TRACER if attempts else None
        for ev, token in batch:
            try:
                self.broker.ack(ev.id, token)
            except EvalBrokerError as exc:
                # The delivery burned anyway (typically a nack timeout
                # redelivered the eval mid-batch) — the marker must say
                # so, not read as a clean success.
                if tr is not None:
                    tr.event("worker.attempt", eval_id=ev.id,
                             attempt=attempts[ev.id],
                             nack_reason=f"ack failed: {exc}")
            else:
                if tr is not None:
                    # One worker.attempt marker per delivery, same as the
                    # per-eval Worker's span.
                    tr.event("worker.attempt", eval_id=ev.id,
                             attempt=attempts[ev.id])

    # -- pipelined drain (NOMAD_TPU_PIPELINE=1) ----------------------------
    #
    # The double-buffered twin of _process_batch built on the split-phase
    # TPUBatchScheduler API: while batch k's device pass is in flight the
    # broker is polled for batch k+1, whose host phases (wait-for-index,
    # snapshot, reconciliation, spec dedup) run during k's device time.
    # k is then fetched + finalized + acked BEFORE k+1's usage delta is
    # built from a fresh snapshot, so the resident delta feed always
    # reflects k's applied plans.  Per-batch failures nack that batch
    # only, exactly like the serial path.

    def _process_batches_pipelined(
            self, batch: List[Tuple[s.Evaluation, str]]) -> None:
        pending = self._pipeline_start(batch)
        while pending is not None and not self._stop.is_set():
            if self._paused:
                # Honor a pause request mid-stream: settle the in-flight
                # batch and hand control back to run()'s pause wait.
                break
            try:
                nxt = self.broker.dequeue_batch(
                    [s.JOB_TYPE_SERVICE, s.JOB_TYPE_BATCH],
                    self.max_batch, 0)
            except EvalBrokerError:
                nxt = None
            if not nxt:
                break
            ctx = self._pipeline_prepare(nxt)   # overlaps pending's device
            self._pipeline_finish(pending)
            # Anti-starvation between pipelined batches: a sustained
            # service/batch stream must not lock out system/core evals
            # (same guarantee the serial run() loop gives per batch).
            self._poll_system_core()
            pending = (self._pipeline_dispatch(ctx)
                       if ctx is not None else None)
        if pending is not None:  # drain done / stop / pause
            self._pipeline_finish(pending)

    def _pipeline_start(self, batch) -> Optional[_BatchCtx]:
        ctx = self._pipeline_prepare(batch)
        if ctx is None:
            return None
        return self._pipeline_dispatch(ctx)

    def _pipeline_prepare(self, batch) -> Optional[_BatchCtx]:
        from ..ops.batch_sched import TPUBatchScheduler

        t0 = tracing.now()
        tr = tracing.TRACER
        attempts = {} if tr is None else {
            ev.id: self.broker.delivery_attempts(ev.id)
            for ev, _ in batch}
        try:
            max_index = max(ev.modify_index for ev, _ in batch)
            self.wait_for_index(max_index, RAFT_SYNC_LIMIT)
            snapshot_index = self.raft.applied_index()
            snap = self._snapshot()
            mux = _MuxPlanner(self, batch, snapshot_index)
            sched = TPUBatchScheduler(self.logger, snap, mux,
                                      mesh=self.mesh, metrics=self.metrics,
                                      snapshot_index=snapshot_index)
            prep = sched._prepare_batch([ev for ev, _ in batch])
            return _BatchCtx(batch, sched, prep, attempts, t0)
        except Exception as exc:
            self._nack_batch(batch, attempts, exc)
            return None

    def _pipeline_dispatch(self, ctx: _BatchCtx) -> Optional[_BatchCtx]:
        try:
            # Fresh snapshot for the usage delta: the previous batch's
            # plans are applied by now (its _pipeline_finish ran first).
            ctx.sched.state = self.raft.fsm.state.snapshot()
            with self._compile_guard(ctx.batch):
                ctx.sched._dispatch_prepared(ctx.prep)
            return ctx
        except Exception as exc:
            self._nack_batch(ctx.batch, ctx.attempts, exc)
            return None

    def _pipeline_finish(self, ctx: _BatchCtx) -> None:
        tr = tracing.TRACER
        try:
            stats = ctx.sched._complete_prepared(ctx.prep)
        except Exception as exc:
            self._nack_batch(ctx.batch, ctx.attempts, exc)
            return
        ctx.sched._emit_batch_stats(stats)
        # Wall-clock latency of THIS batch (dequeue → acked), which in a
        # pipelined drain includes neighbor batches' host phases
        # interleaved on this thread — an eval-experienced latency, same
        # spirit as the serial measure() but not directly comparable to
        # it under sustained overlap.
        self.metrics.add_sample("worker.invoke_scheduler.batch",
                                (tracing.now() - ctx.t0) * 1000.0)
        if tr is not None:
            # Retroactive span (the pipelined phases interleave batches,
            # so a nested context-managed span would mis-stack).
            tr.record("worker.process_batch", ctx.t0, tracing.now(),
                      num_evals=len(ctx.batch), pipelined=True,
                      fused=stats.fused, fetch_bytes=stats.fetch_bytes,
                      **tracing.eval_id_attrs(
                          (ev for ev, _ in ctx.batch), len(ctx.batch)))
        self._ack_batch(ctx.batch, ctx.attempts)

    def _nack_batch(self, batch, attempts, exc: Exception) -> None:
        tr = tracing.TRACER
        self.logger.exception("batch scheduling failed; nacking batch")
        self.record_eval_failures([ev for ev, _ in batch], exc)
        for ev, token in batch:
            if tr is not None:
                tr.event("worker.attempt", eval_id=ev.id,
                         attempt=attempts.get(ev.id, 0),
                         nack_reason=f"{type(exc).__name__}: {exc}")
            try:
                self.broker.nack(ev.id, token)
            except EvalBrokerError:
                pass
