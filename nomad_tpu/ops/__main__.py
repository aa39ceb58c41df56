"""`python -m nomad_tpu.ops --selfcheck`: fast oracle/kernel agreement
checks runnable without a test harness (CI smoke; seconds on CPU).

Covers:

- the preemption subsystem: the batched eviction-set kernel
  (ops/preempt.py) must produce exactly the oracle's
  (scheduler/preempt.py) eviction set for every (task-group, node) pair
  of a seeded random 64x64 cluster;
- the degradation plane: a breaker drill injects one corrupted kernel
  batch (fault point ``ops.kernel_result``) and asserts the circuit
  breaker trips, every eval still completes via the CPU oracle, and a
  clean half-open probe restores the device path;
- the device-resident node-state cache: encode → delta-apply →
  differential verify against a fresh full encode (the guard, armed at
  every hit) → staleness-fence fallback for an old snapshot → breaker
  trip on injected resident corruption (fault point
  ``ops.resident_state``);
- the node-mesh production path (ISSUE 8): sharded cold encode →
  sharded delta apply with the per-shard guard → corruption on one
  shard attributed + breaker trip → oracle carries — run on a virtual
  8-device CPU mesh in a subprocess;
- the struct codec (ISSUE 11): seeded-corpus round-trip parity with
  the reflection-msgpack path, encode→corrupt→decode clean rejection,
  and native/python string-column twin agreement.
"""
from __future__ import annotations

import argparse
import sys

from ..utils import knobs
from .preempt import selfcheck


def breaker_drill(seed: int = 0, log=print) -> bool:
    """Inject one corrupted kernel batch; assert trip → oracle fallback →
    recovery.  Uses a private breaker with a fake clock so the drill is
    instant and never touches the process-wide breaker."""
    from .. import fault, mock
    from ..scheduler import Harness
    from ..structs import structs as s
    from .batch_sched import TPUBatchScheduler
    from .breaker import KernelCircuitBreaker

    clock = [0.0]
    brk = KernelCircuitBreaker(threshold=0.9, window=8, min_checks=1,
                               cooldown=5.0, clock=lambda: clock[0])
    h = Harness()
    for _ in range(8):
        node = mock.node()
        node.resources.networks = []
        node.reserved.networks = []
        node.compute_class()
        h.state.upsert_node(h.next_index(), node)

    def run_batch():
        jobs = []
        for _ in range(2):
            job = mock.job()
            for tg in job.task_groups:
                for t in tg.tasks:
                    t.resources.networks = []
            job.task_groups[0].count = 2
            h.state.upsert_job(h.next_index(), job)
            jobs.append(job)
        evals = [s.Evaluation(
            id=s.generate_uuid(), priority=j.priority, type=j.type,
            triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=j.id,
            status=s.EVAL_STATUS_PENDING) for j in jobs]
        sched = TPUBatchScheduler(h.logger, h.snapshot(), h, breaker=brk)
        stats = sched.schedule_batch(evals)
        placed = all(
            len([a for a in h.state.allocs_by_job(None, j.id, True)
                 if not a.terminal_status()]) == 2 for j in jobs)
        return stats, placed

    def check(cond, msg):
        if not cond:
            log(f"breaker drill: FAIL — {msg}")
        return cond

    with fault.scenario({"seed": seed, "faults": [
            {"point": "ops.kernel_result", "action": "corrupt",
             "times": 1}]}):
        stats, placed = run_batch()
    if not (check(stats.kernel_rejects == 1, "corrupt batch not rejected")
            and check(placed, "oracle fallback did not place the batch")
            and check(brk.state == "open",
                      f"breaker {brk.state!r}, expected open")):
        return False

    stats2, placed2 = run_batch()
    if not (check(stats2.oracle_routed > 0, "open breaker did not route "
                                            "evals through the oracle")
            and check(placed2, "oracle-routed batch did not place")):
        return False

    clock[0] += 10.0  # past cooldown: next batch is the half-open probe
    stats3, placed3 = run_batch()
    if not (check(stats3.oracle_routed == 0, "probe batch did not take "
                                             "the device path")
            and check(placed3, "probe batch did not place")
            and check(brk.state == "closed",
                      f"breaker {brk.state!r} after clean probe")):
        return False
    log(f"breaker drill: OK — trip on corrupt batch (seed {seed}), "
        "oracle fallback placed everything, clean probe re-closed "
        f"(trips={brk.trips})")
    return True


def tracing_drill(seed: int = 0, log=print) -> bool:
    """Run one batch with tracing armed and assert the span tree: the
    batch.schedule root must contain encode/device/finalize phase spans
    with monotonic timestamps and an eval-id index entry per eval; then
    a breaker-tripped (corrupted) batch must produce an
    ``batch.oracle_routed`` span.  Always disarms tracing on exit."""
    from .. import fault, mock
    from ..scheduler import Harness
    from ..structs import structs as s
    from ..utils import tracing
    from .batch_sched import TPUBatchScheduler
    from .breaker import KernelCircuitBreaker

    def check(cond, msg):
        if not cond:
            log(f"tracing drill: FAIL — {msg}")
        return cond

    brk = KernelCircuitBreaker(threshold=0.9, window=8, min_checks=1,
                               cooldown=3600.0)
    h = Harness()
    for _ in range(8):
        node = mock.node()
        node.resources.networks = []
        node.reserved.networks = []
        node.compute_class()
        h.state.upsert_node(h.next_index(), node)

    def run_batch():
        job = mock.job()
        for tg in job.task_groups:
            for t in tg.tasks:
                t.resources.networks = []
        job.task_groups[0].count = 2
        h.state.upsert_job(h.next_index(), job)
        ev = s.Evaluation(
            id=s.generate_uuid(), priority=job.priority, type=job.type,
            triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
            status=s.EVAL_STATUS_PENDING)
        sched = TPUBatchScheduler(h.logger, h.snapshot(), h, breaker=brk)
        sched.schedule_batch([ev])
        return ev

    tracing.enable()
    try:
        ev = run_batch()
        spans = tracing.trace_for_eval(ev.id)
        names = [sp["Name"] for sp in spans]
        roots = [sp for sp in spans if sp["Name"] == "batch.schedule"]
        if not (check(roots, "no batch.schedule root span")
                and check(all(n in names for n in
                              ("batch.encode", "batch.device",
                               "batch.finalize")),
                          f"phase spans missing from {names}")):
            return False
        by_name = {sp["Name"]: sp for sp in spans}
        root_id = roots[0]["SpanID"]
        if not (check(all(by_name[n]["ParentID"] == root_id for n in
                          ("batch.encode", "batch.device",
                           "batch.finalize")),
                      "phase spans not parented under batch.schedule")
                and check(by_name["batch.encode"]["Start"]
                          <= by_name["batch.device"]["Start"]
                          <= by_name["batch.finalize"]["Start"],
                          "phase timestamps not monotonic")):
            return False

        with fault.scenario({"seed": seed, "faults": [
                {"point": "ops.kernel_result", "action": "corrupt",
                 "times": 1}]}):
            ev2 = run_batch()
        spans2 = tracing.trace_for_eval(ev2.id)
        routed = [sp for sp in spans2
                  if sp["Name"] == "batch.oracle_routed"]
        fires = [sp for sp in spans2 if sp["Name"] == "fault.fire"]
        if not (check(routed, "corrupted batch produced no "
                              "batch.oracle_routed span")
                and check(routed[0]["Attrs"].get("reason")
                          == "kernel_reject", f"bad attrs {routed[0]}")
                and check(brk.state == "open",
                          f"breaker {brk.state!r}, expected open")
                and check(fires, "fault.fire span not correlated into "
                                 "the eval trace")):
            return False

        ev3 = run_batch()  # breaker open: routed through the oracle
        routed3 = [sp for sp in tracing.trace_for_eval(ev3.id)
                   if sp["Name"] == "batch.oracle_routed"]
        if not (check(routed3, "open-breaker batch produced no "
                               "batch.oracle_routed span")
                and check(routed3[0]["Attrs"].get("reason")
                          == "breaker_open", f"bad attrs {routed3[0]}")):
            return False
    finally:
        tracing.disable()
    log(f"tracing drill: OK — span tree has encode/device/finalize under "
        f"batch.schedule ({len(spans)} spans for one eval), corrupt batch "
        "traced as oracle_routed(kernel_reject) + fault.fire, open "
        "breaker traced as oracle_routed(breaker_open)")
    return True


def residency_drill(seed: int = 0, log=print) -> bool:
    """Device-resident cache drill: cold encode installs the mirror, a
    second batch takes the delta path with the differential guard armed
    at EVERY hit (so delta-apply is verified against a fresh full
    encode), a stale snapshot falls back over the staleness fence, and
    injected resident corruption trips a private breaker."""
    import os

    from .. import fault, mock
    from ..scheduler import Harness
    from ..structs import structs as s
    from . import resident
    from .batch_sched import TPUBatchScheduler
    from .breaker import KernelCircuitBreaker

    def check(cond, msg):
        if not cond:
            log(f"residency drill: FAIL — {msg}")
        return cond

    saved = {k: os.environ.get(k) for k in
             ("NOMAD_TPU_RESIDENT", "NOMAD_TPU_RESIDENT_GUARD_EVERY")}
    os.environ["NOMAD_TPU_RESIDENT"] = "1"
    os.environ["NOMAD_TPU_RESIDENT_GUARD_EVERY"] = "1"
    resident.reset_counters()
    brk = KernelCircuitBreaker(threshold=0.9, window=8, min_checks=1,
                               cooldown=3600.0)
    try:
        h = Harness()
        for _ in range(8):
            node = mock.node()
            node.resources.networks = []
            node.reserved.networks = []
            node.compute_class()
            h.state.upsert_node(h.next_index(), node)

        def make_batch_job():
            job = mock.job()
            for tg in job.task_groups:
                for t in tg.tasks:
                    t.resources.networks = []
            job.task_groups[0].count = 2
            h.state.upsert_job(h.next_index(), job)
            return job

        def run_batch(state=None, job=None):
            if job is None:
                job = make_batch_job()
            ev = s.Evaluation(
                id=s.generate_uuid(), priority=job.priority, type=job.type,
                triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
                status=s.EVAL_STATUS_PENDING)
            sched = TPUBatchScheduler(
                h.logger, state if state is not None else h.snapshot(),
                h, breaker=brk)
            stats = sched.schedule_batch([ev])
            placed = len([a for a in
                          h.state.allocs_by_job(None, job.id, True)
                          if not a.terminal_status()]) == 2
            return stats, placed

        s1, p1 = run_batch()
        if not (check(s1.full_reencodes == 1 and not s1.resident_hits,
                      f"cold batch should full-encode ({s1!r})")
                and check(p1, "cold batch did not place")):
            return False
        s2, p2 = run_batch()
        if not (check(s2.resident_hits == 1,
                      f"second batch should take the delta path ({s2!r})")
                and check(p2, "delta batch did not place")
                and check(resident.GUARD_RUNS >= 1
                          and resident.GUARD_MISMATCHES == 0,
                          "differential guard did not verify the delta "
                          "apply against a fresh encode")):
            return False

        # Staleness fence: a snapshot two batches old must full-encode
        # without touching the (newer) mirror.  The fence job registers
        # BEFORE the snapshot so the stale world can see it.
        fence_job = make_batch_job()
        stale = h.snapshot()
        run_batch()
        run_batch()
        cached = resident._STATE.alloc_index
        s3, p3 = run_batch(state=stale, job=fence_job)
        if not (check(s3.staleness_fences == 1 and s3.full_reencodes == 1,
                      f"stale snapshot did not take the fence ({s3!r})")
                and check(p3, "fenced batch did not place")
                and check(resident._STATE.alloc_index == cached,
                          "fence regressed the resident mirror")):
            return False

        # Injected resident corruption: guard catches it, breaker trips,
        # the batch still places from the fresh full encode.
        with fault.scenario({"seed": seed, "faults": [
                {"point": "ops.resident_state", "action": "corrupt",
                 "times": 1}]}):
            s4, p4 = run_batch()
        if not (check(resident.GUARD_MISMATCHES == 1,
                      "guard missed the injected corruption")
                and check(brk.state == "open",
                          f"breaker {brk.state!r}, expected open")
                and check(p4, "corrupted-mirror batch did not place")):
            return False
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        resident.reset_counters()
    log("residency drill: OK — cold encode installed the mirror, delta "
        "apply verified bit-identical by the guard, stale snapshot took "
        "the fence, injected corruption tripped the breaker "
        f"(guard runs={resident.GUARD_RUNS or 'reset'})")
    return True


def columnar_drill(seed: int = 0, log=print) -> bool:
    """Columnar state-store drill (ISSUE 9): the first snapshot cold-
    builds the store's numpy mirror and the encode slices it (guard
    armed at EVERY encode, so the column-built buffers are verified
    bit-identical against the object walk), incremental node/alloc
    writes keep parity, an injected column corruption is caught by the
    guard and trips the breaker, and the oracle carries the next
    batch."""
    import os

    from .. import fault, mock
    from ..scheduler import Harness
    from ..state import columnar
    from ..structs import structs as s
    from .batch_sched import TPUBatchScheduler
    from .breaker import KernelCircuitBreaker

    def check(cond, msg):
        if not cond:
            log(f"columnar drill: FAIL — {msg}")
        return cond

    saved = {k: os.environ.get(k) for k in
             ("NOMAD_TPU_COLUMNAR", "NOMAD_TPU_COLUMNAR_GUARD_EVERY")}
    os.environ["NOMAD_TPU_COLUMNAR"] = "1"
    os.environ["NOMAD_TPU_COLUMNAR_GUARD_EVERY"] = "1"
    columnar.reset_counters()
    brk = KernelCircuitBreaker(threshold=0.9, window=8, min_checks=1,
                               cooldown=3600.0)
    try:
        h = Harness()
        for _ in range(8):
            node = mock.node()
            node.resources.networks = []
            node.reserved.networks = []
            node.compute_class()
            h.state.upsert_node(h.next_index(), node)

        def run_batch():
            job = mock.job()
            for tg in job.task_groups:
                for t in tg.tasks:
                    t.resources.networks = []
            job.task_groups[0].count = 2
            h.state.upsert_job(h.next_index(), job)
            ev = s.Evaluation(
                id=s.generate_uuid(), priority=job.priority, type=job.type,
                triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
                status=s.EVAL_STATUS_PENDING)
            sched = TPUBatchScheduler(h.logger, h.snapshot(), h,
                                      breaker=brk)
            stats = sched.schedule_batch([ev])
            placed = len([a for a in
                          h.state.allocs_by_job(None, job.id, True)
                          if not a.terminal_status()]) == 2
            return stats, placed

        # 1. Cold build + first columnar encode, guard-verified.
        _, p1 = run_batch()
        if not (check(columnar.COLUMNAR_ENCODES >= 1,
                      "first batch did not take the columnar encode")
                and check(columnar.GUARD_RUNS >= 1
                          and columnar.GUARD_MISMATCHES == 0,
                          "guard did not verify the cold column build")
                and check(p1, "cold columnar batch did not place")):
            return False

        # 2. Incremental writes (status flip + a fresh node) re-key the
        # static cache; the columnar re-encode must still match the
        # walk bit-for-bit.
        some_node = h.state.nodes(None)[0]
        h.state.update_node_drain(h.next_index(), some_node.id, True)
        h.state.update_node_drain(h.next_index(), some_node.id, False)
        extra = mock.node()
        extra.resources.networks = []
        extra.reserved.networks = []
        extra.compute_class()
        h.state.upsert_node(h.next_index(), extra)
        guard_before = columnar.GUARD_RUNS
        _, p2 = run_batch()
        if not (check(columnar.GUARD_RUNS > guard_before
                      and columnar.GUARD_MISMATCHES == 0,
                      "guard did not verify the incremental re-encode")
                and check(p2, "incremental batch did not place")):
            return False

        # 3. Injected column corruption: the guard catches it, feeds
        # the breaker, and the batch proceeds on the walk's buffers.
        extra2 = mock.node()
        extra2.resources.networks = []
        extra2.reserved.networks = []
        extra2.compute_class()
        h.state.upsert_node(h.next_index(), extra2)  # force re-encode
        with fault.scenario({"seed": seed, "faults": [
                {"point": "state.columns", "action": "corrupt",
                 "times": 1}]}):
            _, p3 = run_batch()
        if not (check(columnar.GUARD_MISMATCHES == 1,
                      "guard missed the injected column corruption")
                and check(brk.state == "open",
                          f"breaker {brk.state!r}, expected open")
                and check(p3, "corrupted-column batch did not place")):
            return False

        # 4. Open breaker: the oracle carries the next batch.
        s4, p4 = run_batch()
        if not (check(s4.oracle_routed > 0,
                      "open breaker did not route through the oracle")
                and check(p4, "oracle-carried batch did not place")):
            return False
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        columnar.reset_counters()
    log("columnar drill: OK — cold column build verified bit-identical "
        "to the object walk, incremental writes kept parity, injected "
        "corruption tripped the breaker, oracle carried the next batch")
    return True


def wal_drill(seed: int = 0, log=print) -> bool:
    """Native group-commit WAL drill (ISSUE 9): append through the
    FileLog, crash mid-frame via the ``wal.fsync`` fault point (a torn
    partial record is left on disk), and recover — the torn tail is
    truncated, committed entries survive, the crashed entry never
    applied, and post-recovery appends land cleanly."""
    import os
    import shutil
    import tempfile

    from .. import fault, mock
    from ..server.fsm import FSM, MessageType
    from ..server.raft import FileLog

    def check(cond, msg):
        if not cond:
            log(f"wal drill: FAIL — {msg}")
        return cond

    d = tempfile.mkdtemp(prefix="nomad-tpu-waldrill-")
    try:
        flog = FileLog(FSM(), d)
        native = flog._nwal is not None
        node = mock.node()
        node.compute_class()
        flog.apply(MessageType.NODE_REGISTER, {"node": node})
        applied = flog.applied_index()

        job = mock.job()
        crashed = False
        with fault.scenario({"seed": seed, "faults": [
                {"point": "wal.fsync", "action": "crash", "times": 1}]}):
            try:
                flog.apply(MessageType.JOB_REGISTER, {"job": job})
            except Exception:
                crashed = True
        flog.close()
        if not check(crashed, "injected mid-frame crash did not fire"):
            return False
        wal_file = os.path.join(d, "wal.crc" if native else "wal.log")
        torn_size = os.path.getsize(wal_file)

        flog2 = FileLog(FSM(), d)
        if not (check(flog2.applied_index() == applied,
                      "recovery lost or invented entries")
                and check(flog2.fsm.state.node_by_id(None, node.id)
                          is not None, "committed entry lost")
                and check(flog2.fsm.state.job_by_id(None, job.id) is None,
                          "torn entry applied")
                and check(os.path.getsize(wal_file) < torn_size,
                          "torn tail was not truncated")):
            flog2.close()
            return False
        flog2.apply(MessageType.JOB_REGISTER, {"job": job})
        applied2 = flog2.applied_index()
        flog2.close()

        flog3 = FileLog(FSM(), d)
        ok = (check(flog3.applied_index() == applied2,
                    "post-recovery append did not survive")
              and check(flog3.fsm.state.job_by_id(None, job.id)
                        is not None, "post-recovery entry lost"))
        flog3.close()
        if not ok:
            return False
    finally:
        shutil.rmtree(d, ignore_errors=True)
    log(f"wal drill: OK — {'native' if native else 'fallback'} WAL "
        "crashed mid-frame, recovery truncated the torn tail, committed "
        "entries survived, post-recovery appends land cleanly")
    return True


def fused_drill(seed: int = 0, log=print) -> bool:
    """Fused score-and-commit drill (PR 6): a cold batch through the
    fused single-dispatch path must place with exactly ONE ``batch.fetch``
    span; the identical problem through the CPU oracle must place the
    same per-job counts with no node overcommitted; quantized resource
    rows must round-trip bit-exactly (and a corrupted codebook must be
    caught); a corrupted fused result buffer must trip the breaker and
    route the batch to the oracle."""
    import os

    import numpy as np

    from .. import fault, mock
    from ..scheduler import Harness
    from ..scheduler.generic import GenericScheduler
    from ..structs import structs as s
    from ..utils import tracing
    from . import encode, resident
    from .batch_sched import TPUBatchScheduler
    from .breaker import KernelCircuitBreaker

    def check(cond, msg):
        if not cond:
            log(f"fused drill: FAIL — {msg}")
        return cond

    saved = {"NOMAD_TPU_QUANT": knobs.raw("NOMAD_TPU_QUANT")}
    os.environ["NOMAD_TPU_QUANT"] = "1"
    brk = KernelCircuitBreaker(threshold=0.9, window=8, min_checks=1,
                               cooldown=3600.0)
    try:
        # Twin harnesses over an identical fleet + identical jobs: one
        # scheduled by the fused device path, one by the oracle.
        nodes = []
        for _ in range(8):
            node = mock.node()
            node.resources.networks = []
            node.reserved.networks = []
            node.compute_class()
            nodes.append(node)
        h_dev, h_orc = Harness(), Harness()
        for node in nodes:
            h_dev.state.upsert_node(h_dev.next_index(), node.copy())
            h_orc.state.upsert_node(h_orc.next_index(), node.copy())
        jobs = []
        for _ in range(3):
            job = mock.job()
            for tg in job.task_groups:
                for t in tg.tasks:
                    t.resources.networks = []
            job.task_groups[0].count = 2
            jobs.append(job)
        for h in (h_dev, h_orc):
            for job in jobs:
                h.state.upsert_job(h.next_index(), job)

        def mk_evals():
            return [s.Evaluation(
                id=s.generate_uuid(), priority=j.priority, type=j.type,
                triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=j.id,
                status=s.EVAL_STATUS_PENDING) for j in jobs]

        # 1. Cold fused batch, tracing armed: one batch.fetch span, the
        # batch placed, and the stats say fused ran.
        evals = mk_evals()
        tracing.enable()
        try:
            sched = TPUBatchScheduler(h_dev.logger, h_dev.snapshot(),
                                      h_dev, breaker=brk)
            stats = sched.schedule_batch(evals)
            fetches = [sp for sp in tracing.trace_for_eval(evals[0].id)
                       if sp["Name"] == "batch.fetch"]
        finally:
            tracing.disable()
        if not (check(stats.fused == 1, f"batch did not run fused ({stats!r})")
                and check(len(fetches) == 1,
                          f"{len(fetches)} batch.fetch spans, expected "
                          "exactly 1 (single-transfer contract)")):
            return False

        # 2. Oracle parity on the twin harness: same per-job placement
        # counts, no node overcommitted on either side.
        for ev in mk_evals():
            GenericScheduler(h_orc.logger, h_orc.snapshot(),
                             h_orc, batch=False).process(ev)
        for job in jobs:
            n_dev = len([a for a in
                         h_dev.state.allocs_by_job(None, job.id, True)
                         if not a.terminal_status()])
            n_orc = len([a for a in
                         h_orc.state.allocs_by_job(None, job.id, True)
                         if not a.terminal_status()])
            if not check(n_dev == n_orc == 2,
                         f"placement parity broke for {job.id}: fused "
                         f"{n_dev} vs oracle {n_orc} (want 2)"):
                return False
        for h in (h_dev, h_orc):
            for node in h.state.nodes(None):
                used = np.zeros(2, dtype=np.int64)
                for a in h.state.allocs_by_node(None, node.id):
                    if a.terminal_status():
                        continue
                    res = a.resources
                    if res is None:
                        # Oracle-path allocs carry per-task resources
                        # only (the combined total is filled at apply).
                        used += (
                            sum(t.cpu for t in a.task_resources.values()),
                            sum(t.memory_mb
                                for t in a.task_resources.values()))
                    else:
                        used += (res.cpu, res.memory_mb)
                if not check(
                        used[0] <= node.resources.cpu
                        and used[1] <= node.resources.memory_mb,
                        f"node {node.id} overcommitted ({used})"):
                    return False

        # 3. Quantization round-trip bound: the bench-shape rows must
        # quantize exactly; a corrupted codebook must be caught and feed
        # the breaker.
        resident.reset_counters()
        cap = np.tile(np.array([4000, 8192, 102400, 150]), (8, 1))
        base_used = np.tile(np.array([100, 128, 0, 0]), (8, 1))
        q = encode.quantize_resource_rows(cap, base_used)
        if not (check(q is not None, "bench-shape rows did not quantize")
                and check(resident.check_quant_roundtrip(
                              cap, q.cap_q, q.scale[0], what="capacity"),
                          "exact quantization failed the round-trip bound")
                and check(np.array_equal(
                              encode.dequantize_rows(q.used_q, q.scale[1]),
                              base_used),
                          "used baseline did not round-trip")):
            return False
        bad_brk = KernelCircuitBreaker(threshold=0.9, window=8,
                                       min_checks=1, cooldown=3600.0)
        corrupt = np.array(q.cap_q)
        corrupt[0, 0] += 1
        if not (check(not resident.check_quant_roundtrip(
                          cap, corrupt, q.scale[0], breaker=bad_brk,
                          what="capacity"),
                      "corrupted codebook passed the round-trip bound")
                and check(resident.QUANT_MISMATCHES == 1,
                          "quant mismatch counter did not move")
                and check(bad_brk.agreement() < 1.0,
                          "quant mismatch did not feed the breaker")):
            return False

        # 4. Corrupted fused result buffer: validation rejects it, the
        # breaker trips, the oracle carries the batch.  Fresh jobs — the
        # step-1 jobs already placed, so their evals would be no-ops.
        jobs2 = []
        for _ in range(2):
            job = mock.job()
            for tg in job.task_groups:
                for t in tg.tasks:
                    t.resources.networks = []
            job.task_groups[0].count = 1
            jobs2.append(job)
            h_dev.state.upsert_job(h_dev.next_index(), job)
        evals2 = [s.Evaluation(
            id=s.generate_uuid(), priority=j.priority, type=j.type,
            triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=j.id,
            status=s.EVAL_STATUS_PENDING) for j in jobs2]
        with fault.scenario({"seed": seed, "faults": [
                {"point": "ops.kernel_result", "action": "corrupt",
                 "times": 1}]}):
            sched = TPUBatchScheduler(h_dev.logger, h_dev.snapshot(),
                                      h_dev, breaker=brk)
            stats2 = sched.schedule_batch(evals2)
        if not (check(stats2.kernel_rejects == 1,
                      f"corrupt fused batch not rejected ({stats2!r})")
                and check(stats2.oracle_routed == len(jobs2),
                          "rejected fused batch did not route to the "
                          "oracle")
                and check(brk.state == "open",
                          f"breaker {brk.state!r}, expected open")):
            return False
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        resident.reset_counters()
    log("fused drill: OK — single-fetch fused batch placed with oracle "
        "parity and no overcommit, quantized rows round-tripped exactly "
        "(corruption caught), corrupt fused buffer tripped the breaker "
        "and the oracle carried the batch")
    return True


def residue_drill(seed: int = 0, log=print) -> bool:
    """Host-residue drill (ISSUE 13): the donated device-resident usage
    mirror round-trips bit-identical to the host mirror across delta
    batches (and produces the same placements as the sparse-delta upload
    path at a pinned seed), the int8 quantization guard catches an
    out-of-range dimension, and the native packed-result decode agrees
    with its python twins on a seeded corpus."""
    import os
    import random

    import numpy as np

    from .. import mock
    from ..scheduler import Harness
    from ..structs import structs as s
    from . import decode as decode_mod
    from . import encode, resident
    from .batch_sched import TPUBatchScheduler

    def check(cond, msg):
        if not cond:
            log(f"residue drill: FAIL — {msg}")
        return cond

    saved = {k: os.environ.get(k) for k in
             ("NOMAD_TPU_RESIDENT", "NOMAD_TPU_RESIDENT_DEVICE",
              "NOMAD_TPU_RESIDENT_GUARD_EVERY", "NOMAD_TPU_RNG_SEED",
              "NOMAD_TPU_DECODE_GUARD_EVERY")}
    os.environ["NOMAD_TPU_RESIDENT"] = "1"
    os.environ["NOMAD_TPU_RESIDENT_GUARD_EVERY"] = "1"
    os.environ["NOMAD_TPU_RNG_SEED"] = str(1234567 + seed)
    os.environ["NOMAD_TPU_DECODE_GUARD_EVERY"] = "1"
    resident.reset_counters()
    decode_mod.reset_counters()
    try:
        # 1. Donated round-trip parity: the same 4-batch stream through
        # the donated device mirror and the sparse-delta upload path
        # must place identically, and the device mirror must bit-match
        # the host mirror after every donated apply.
        def run_stream(device_mirror: bool):
            os.environ["NOMAD_TPU_RESIDENT_DEVICE"] = (
                "1" if device_mirror else "0")
            resident.invalidate()
            h = Harness()
            for i in range(8):
                node = mock.node()
                # Pinned ids: the two streams build separate harnesses
                # and their placements compare by node identity.
                node.id = f"residue-node-{i:02d}"
                node.name = node.id
                node.resources.networks = []
                node.reserved.networks = []
                node.compute_class()
                h.state.upsert_node(h.next_index(), node)
            placements = []
            for _ in range(4):
                job = mock.job()
                for tg in job.task_groups:
                    for t in tg.tasks:
                        t.resources.networks = []
                job.task_groups[0].count = 2
                h.state.upsert_job(h.next_index(), job)
                ev = s.Evaluation(
                    id=s.generate_uuid(), priority=job.priority,
                    type=job.type,
                    triggered_by=s.EVAL_TRIGGER_JOB_REGISTER,
                    job_id=job.id, status=s.EVAL_STATUS_PENDING)
                TPUBatchScheduler(h.logger, h.snapshot(), h
                                  ).schedule_batch([ev])
                placements.append(sorted(
                    a.node_id for a in
                    h.state.allocs_by_job(None, job.id, True)))
            st = resident._STATE
            dev_ok = True
            if device_mirror:
                dev_ok = (st is not None and st.used_dev is not None
                          and np.array_equal(
                              np.asarray(st.used_dev).astype(np.int64),
                              st.used))
            return placements, dev_ok

        pl_dev, dev_ok = run_stream(True)
        applies = resident.DEV_APPLIES
        installs = resident.DEV_INSTALLS
        pl_delta, _ = run_stream(False)
        if not (check(installs == 1,
                      f"expected ONE device-mirror install, got "
                      f"{installs}")
                and check(applies >= 3,
                          f"donated delta applies did not run ({applies})")
                and check(dev_ok,
                          "device mirror diverged from the host mirror "
                          "after donated applies")
                and check(pl_dev == pl_delta,
                          "donated-mirror placements differ from the "
                          "delta-upload path")
                and check(resident.DEV_GUARD_MISMATCHES == 0
                          and resident.GUARD_MISMATCHES == 0,
                          "mirror guards reported mismatches")):
            return False

        # 2. int8 guard: a scale codebook pushed out of range must fail
        # the round-trip bound (exact-or-absent discipline).
        cap = np.tile(np.array([4000, 8192, 102400, 150]), (8, 1))
        q = encode.quantize_resource_rows(cap, np.zeros_like(cap))
        if not (check(q is not None and q.cap_tag == "i8",
                      f"bench-shape capacity did not quantize int8 "
                      f"({None if q is None else q.cap_tag})")
                and check(resident.check_quant_roundtrip(
                              cap, q.cap_q, q.scale[0], what="capacity"),
                          "exact int8 rows failed the round-trip bound")):
            return False
        bad_scale = np.array(q.scale[0])
        bad_scale[1] <<= 1   # out-of-range dimension: dequant overshoots
        if not check(not resident.check_quant_roundtrip(
                         cap, q.cap_q, bad_scale, what="capacity"),
                     "out-of-range scale dimension passed the guard"):
            return False

        # 3. Native-decode twin agreement on a seeded COO corpus (guard
        # pinned at 1 above, so EVERY native call is twin-verified).
        rng = random.Random(seed)
        n_specs, n_real = 17, 203
        rows_l, cols_l, cnt_l = [], [], []
        for u in range(n_specs):
            for _ in range(rng.randrange(0, 9)):
                rows_l.append(u)
                cols_l.append(rng.randrange(n_real))
                cnt_l.append(rng.randrange(1, 4))
        rows = np.array(rows_l, dtype=np.int32)
        cols = np.array(cols_l, dtype=np.int32)
        cnts = np.array(cnt_l, dtype=np.int32)
        scores = np.array([rng.random() * 18 for _ in rows_l],
                          dtype=np.float32)
        coll = np.array([rng.randrange(0, 3) for _ in rows_l],
                        dtype=np.int32)
        off, exp = decode_mod.expand_coo(rows, cols, cnts, n_specs,
                                         n_real, int(cnts.sum()))
        ref_off, ref_exp = decode_mod._expand_twin(rows, cols, cnts,
                                                   n_specs, n_real)
        ls = decode_mod.last_scores(rows, cols, scores, coll, n_specs,
                                    n_real)
        ref_ls = decode_mod._last_scores_twin(rows, cols, scores, coll,
                                              n_specs, n_real)
        if not (check(np.array_equal(off, ref_off)
                      and np.array_equal(exp, ref_exp),
                      "native expand diverged from the numpy twin")
                and check(all(np.array_equal(a, b)
                              for a, b in zip(ls, ref_ls)),
                          "native last-scores diverged from the twin")
                and check(decode_mod.GUARD_MISMATCHES == 0,
                          "decode guard reported mismatches")):
            return False
        native_note = ("native" if decode_mod.NATIVE_CALLS else
                       "python-twin (toolchain unavailable)")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        resident.reset_counters()
        decode_mod.reset_counters()
    log("residue drill: OK — donated mirror round-tripped bit-identical "
        "(one install, in-place applies, placements == delta path), "
        "out-of-range int8 scale caught by the round-trip guard, "
        f"packed-result decode twins agree ({native_note})")
    return True


def mesh_drill_child(seed: int = 0, log=print, n_devices: int = 8) -> bool:
    """Node-mesh residue drill body (requires ``n_devices`` jax devices
    — the parent ``mesh_drill`` provisions a virtual CPU mesh): sharded
    cold encode installs the DONATED per-shard usage mirror (ISSUE 14),
    N delta batches catch it up in place via shard-routed donated
    scatter-adds with the differential guard armed at every hit, the
    device mirror bit-compares against the host walk, ONE corrupted
    mirror row is attributed to its owning shard id (guard event) and
    trips the breaker, and the open breaker routes the next batch
    through the CPU oracle which still places everything."""
    import os

    import jax
    import numpy as np

    from .. import fault, mock
    from ..parallel import make_node_mesh
    from ..scheduler import Harness
    from ..server import event_broker
    from ..structs import structs as s
    from . import resident
    from .batch_sched import TPUBatchScheduler
    from .breaker import KernelCircuitBreaker

    def check(cond, msg):
        if not cond:
            log(f"mesh drill: FAIL — {msg}")
        return cond

    devs = jax.devices()
    if not check(len(devs) >= n_devices,
                 f"need {n_devices} devices, have {len(devs)}"):
        return False
    mesh = make_node_mesh(devs[:n_devices])
    saved = {k: os.environ.get(k) for k in
             ("NOMAD_TPU_RESIDENT", "NOMAD_TPU_RESIDENT_GUARD_EVERY",
              "NOMAD_TPU_RESIDENT_DEVICE")}
    os.environ["NOMAD_TPU_RESIDENT"] = "1"
    os.environ["NOMAD_TPU_RESIDENT_GUARD_EVERY"] = "1"
    os.environ["NOMAD_TPU_RESIDENT_DEVICE"] = "1"
    resident.reset_counters()
    brk = KernelCircuitBreaker(threshold=0.9, window=8, min_checks=1,
                               cooldown=3600.0)
    h = Harness()
    broker = event_broker.EventBroker(
        index_source=lambda: h.state.latest_index())
    event_broker.register(broker)
    event_broker.clear_recent()
    try:
        for _ in range(16):
            node = mock.node()
            node.resources.networks = []
            node.reserved.networks = []
            node.compute_class()
            h.state.upsert_node(h.next_index(), node)

        def run_batch():
            job = mock.job()
            for tg in job.task_groups:
                for t in tg.tasks:
                    t.resources.networks = []
            job.task_groups[0].count = 2
            h.state.upsert_job(h.next_index(), job)
            ev = s.Evaluation(
                id=s.generate_uuid(), priority=job.priority, type=job.type,
                triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
                status=s.EVAL_STATUS_PENDING)
            sched = TPUBatchScheduler(h.logger, h.snapshot(), h,
                                      mesh=mesh, breaker=brk)
            stats = sched.schedule_batch([ev])
            placed = len([a for a in
                          h.state.allocs_by_job(None, job.id, True)
                          if not a.terminal_status()]) == 2
            return stats, placed

        s1, p1 = run_batch()
        if not (check(s1.mesh_shards == n_devices and s1.fused == 1,
                      f"cold batch did not run the fused mesh pass "
                      f"({s1!r})")
                and check(s1.full_reencodes == 1,
                          f"cold batch should full-encode ({s1!r})")
                and check(p1, "cold mesh batch did not place")
                and check(resident.DEV_INSTALLS == 1,
                          f"sharded mirror should install exactly once "
                          f"({resident.DEV_INSTALLS})")):
            return False
        s2, p2 = run_batch()
        st = resident._STATE
        if not (check(s2.resident_hits == 1,
                      f"second batch should take the sharded delta path "
                      f"({s2!r})")
                and check(p2, "delta batch did not place")
                and check(resident.DEV_APPLIES >= 1,
                          "no shard-routed donated delta apply ran")
                and check(resident.DEV_INSTALLS == 1,
                          "delta batch reinstalled the mirror instead "
                          "of applying in place")
                and check(st is not None and st.used_dev is not None
                          and np.array_equal(
                              np.asarray(st.used_dev).astype(np.int64),
                              st.used),
                          "sharded device mirror diverged from the "
                          "host walk")
                and check(resident.GUARD_RUNS >= 1
                          and resident.GUARD_MISMATCHES == 0,
                          "per-shard guard did not verify the delta "
                          "apply")):
            return False
        with fault.scenario({"seed": seed, "faults": [
                {"point": "ops.resident_state", "action": "corrupt",
                 "times": 1}]}):
            s3, p3 = run_batch()
        mismatch_events = [
            e for e in event_broker.recent()
            if e.type == "NodeStateDelta"
            and e.payload.get("Reason") == "guard_mismatch"]
        bad_shards = (mismatch_events[-1].payload.get("Shards")
                      if mismatch_events else None)
        if not (check(resident.GUARD_MISMATCHES == 1,
                      "guard missed the injected shard corruption")
                and check(bad_shards is not None and len(bad_shards) == 1
                          and 0 <= bad_shards[0] < n_devices,
                          f"corruption not attributed to its owning "
                          f"shard id (event Shards={bad_shards})")
                and check(brk.state == "open",
                          f"breaker {brk.state!r}, expected open")
                and check(p3, "corrupted-shard batch did not place")):
            return False
        s4, p4 = run_batch()
        if not (check(s4.oracle_routed > 0,
                      "open breaker did not route the mesh batch "
                      "through the oracle")
                and check(p4, "oracle-carried batch did not place")):
            return False
    finally:
        event_broker.unregister(broker)
        event_broker.clear_recent()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        resident.reset_counters()
    log(f"mesh drill: OK — {n_devices}-shard fused cold encode installed "
        "the donated per-shard mirror and placed, shard-routed donated "
        "applies landed on the owning shards (device mirror bit-matched "
        f"the host walk, guard verified), injected corruption was "
        f"attributed to shard {bad_shards[0]} and tripped the breaker, "
        "and the oracle carried the next batch")
    return True


def mesh_drill(seed: int = 0, log=print, n_devices: int = 8,
               deadline_s: int = 420) -> bool:
    """Parent half of the mesh drill: provision an ``n_devices`` virtual
    CPU mesh in a throwaway subprocess (the same
    xla_force_host_platform_device_count recipe tests/conftest.py and
    the driver dryrun use — the current process may already have a
    single-device backend initialized) and run ``mesh_drill_child``
    there."""
    import subprocess

    from ..utils.platform import virtual_mesh_env

    env = virtual_mesh_env(n_devices)
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "nomad_tpu.ops", "--mesh-drill-child",
             "--seed", str(seed)],
            env=env, timeout=deadline_s, capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        log(f"mesh drill: FAIL — child exceeded {deadline_s}s deadline")
        return False
    for line in (proc.stdout or "").splitlines():
        log(line)
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()[-5:]
        for line in tail:
            log(f"mesh drill child stderr: {line}")
        log(f"mesh drill: FAIL — child rc={proc.returncode}")
        return False
    return True


def codec_drill(seed: int = 0, log=print) -> bool:
    """Struct-codec drill (ISSUE 11): a seeded corpus of hot-type
    payloads must (1) round-trip bit-equal to the reflection-msgpack
    path, (2) REJECT cleanly — CodecError, never a silent misread or a
    crash — under truncation and header/tag corruption, and (3) agree
    byte-for-byte between the native string-column pack and its
    pure-Python twin."""
    import os
    import random

    from .. import codec, mock
    from ..api.codec import to_wire
    from ..codec import CodecError
    from ..codec import native as cnative
    from ..structs import structs as s

    def check(cond, msg):
        if not cond:
            log(f"codec drill: FAIL — {msg}")
        return cond

    rng = random.Random(seed)

    def corpus_item(i):
        job = mock.job()
        alloc = s.Allocation(
            id=s.generate_uuid(), job_id=job.id, job=job,
            name=f"{job.id}.tg[{i}]", node_id=s.generate_uuid(),
            task_resources={"t": s.Resources(cpu=100, memory_mb=128)})
        slab = s.AllocSlab(proto=alloc, ids=s.LazyUuids(8),
                           names=s.LazyNames(8, f"{job.id}.tg"),
                           node_ids=[s.generate_uuid() for _ in range(8)])
        ev = s.Evaluation(id=s.generate_uuid(), job_id=job.id,
                          priority=rng.randrange(1, 100))
        return {"evals": [ev], "allocs": [alloc], "slabs": [slab],
                "job": job, "eval_id": ev.id}

    corpus = [corpus_item(i) for i in range(8)]

    # 1. Round-trip parity with the msgpack path on every item.
    for payload in corpus:
        got = codec.decode(codec.encode(payload))
        if not check(to_wire(got["job"]) == to_wire(payload["job"])
                     and to_wire(got["allocs"]) == to_wire(
                         payload["allocs"])
                     and list(got["slabs"][0].ids)
                     == list(payload["slabs"][0].ids),
                     "round trip diverged from the source payload"):
            return False

    # 2. encode -> corrupt -> decode must reject cleanly.
    rejected = accepted = 0
    for payload in corpus:
        blob = codec.encode(payload)
        cuts = [rng.randrange(1, len(blob)) for _ in range(16)]
        for k in cuts:
            try:
                codec.decode(blob[:k])
                return check(False, f"truncation at {k} was accepted")
            except CodecError:
                rejected += 1
        # Header/tag corruption: magic, version, and a value tag.
        for pos in (0, 1, 2):
            bad = bytearray(blob)
            bad[pos] ^= 0xFF
            try:
                codec.decode(bytes(bad))
                accepted += 1  # content-byte flips may legally decode
            except CodecError:
                rejected += 1
    if not check(rejected > 0, "no corruption was rejected"):
        return False

    # 3. Native/python twin agreement on the seeded column corpus.
    runs_before = cnative.GUARD_RUNS
    saved = knobs.raw("NOMAD_TPU_CODEC_GUARD_EVERY")
    os.environ["NOMAD_TPU_CODEC_GUARD_EVERY"] = "1"
    try:
        for payload in corpus:
            cols = [list(payload["slabs"][0].node_ids),
                    [s.generate_uuid() for _ in range(64)]]
            for col in cols:
                encoded = [x.encode() for x in col]
                py = cnative._py_pack_strs(encoded)
                if not check(cnative.pack_strs(col) == py,
                             "native pack diverged from python twin"):
                    return False
                got, end = cnative.unpack_strs(py, 0, len(col))
                if not check(got == col and end == len(py),
                             "native unpack diverged from python twin"):
                    return False
        if not check(cnative.GUARD_MISMATCHES == 0,
                     "differential guard counted a mismatch"):
            return False
    finally:
        if saved is None:
            os.environ.pop("NOMAD_TPU_CODEC_GUARD_EVERY", None)
        else:
            os.environ["NOMAD_TPU_CODEC_GUARD_EVERY"] = saved
    native_used = cnative._get_lib() is not None and not \
        cnative._native_disabled
    log("codec drill: OK — corpus round-tripped bit-equal, "
        f"{rejected} corruptions rejected cleanly ({accepted} benign "
        "content flips decoded), native/python twins agree "
        f"({'native' if native_used else 'python-twin-only'}, "
        f"{cnative.GUARD_RUNS - runs_before} guarded calls)")
    return True


def follower_drill(seed: int = 0, log=print) -> bool:
    """Follower-read scheduling drill (ISSUE 10): boot a 3-voter
    in-process cluster, pause the leader's LOCAL workers so only
    follower workers can schedule, submit a job, and verify the plan
    was forwarded by a follower, applied by the LEADER's serialized
    plan-apply, and is visible on all three FSMs.  Then the
    lagging-follower streaming-install drill: compact the leader past
    the log horizon with a tiny chunk size and verify a fresh joiner
    catches up via CHUNKED InstallSnapshot."""
    import os
    import time

    from ..server import Server, ServerConfig
    from ..structs import structs as s

    def check(cond, msg):
        if not cond:
            log(f"follower drill: FAIL — {msg}")
        return cond

    def wait_until(pred, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return True
            time.sleep(0.02)
        return pred()

    saved = knobs.raw("NOMAD_TPU_SNAPSHOT_CHUNK")
    servers = []
    fresh = None
    try:
        first = None
        for i in range(3):
            # num_schedulers=0: NO server runs a leader-local worker —
            # follower_schedulers=1 gives each a follower-read worker,
            # so the drill's eval can only complete via the follower
            # path (the leader's own follower worker parks while it
            # leads).
            srv = Server(ServerConfig(
                node_name=f"drill-s{i + 1}", enable_rpc=True,
                bootstrap_expect=3, start_join=[first] if first else [],
                num_schedulers=0, follower_schedulers=1,
                min_heartbeat_ttl=60.0))
            if first is None:
                first = srv.config.rpc_advertise
            servers.append(srv)
        for srv in servers:
            srv.start()
        if not check(wait_until(lambda: any(
                x.is_leader() and x.raft.is_raft_leader()
                for x in servers)), "no leader elected"):
            return False
        leader = next(x for x in servers if x.is_leader())
        followers = [x for x in servers if x is not leader]
        if not check(wait_until(lambda: all(
                len(x.raft.peers) == 3 for x in servers)),
                "voter config did not converge"):
            return False

        node = s.Node(
            id="drill-node", datacenter="dc1", name="drill-node",
            attributes={"kernel.name": "linux", "driver.exec": "1"},
            resources=s.Resources(cpu=4000, memory_mb=8192,
                                  disk_mb=100 * 1024, iops=1000),
            reserved=s.Resources(), status=s.NODE_STATUS_READY)
        leader.node_register(node)
        jid = "drill-job"
        job = s.Job(
            region="global", id=jid, name=jid, type=s.JOB_TYPE_SERVICE,
            priority=50, datacenters=["dc1"],
            task_groups=[s.TaskGroup(
                name="tg", count=2,
                ephemeral_disk=s.EphemeralDisk(size_mb=10),
                tasks=[s.Task(name="t", driver="exec",
                              config={"command": "/bin/date"},
                              resources=s.Resources(cpu=100,
                                                    memory_mb=128),
                              log_config=s.LogConfig())])])
        _, eval_id = leader.job_register(job)
        if not check(wait_until(lambda: (
                (ev := leader.state.eval_by_id(None, eval_id)) is not None
                and ev.status == s.EVAL_STATUS_COMPLETE)),
                "eval did not complete via follower scheduling"):
            return False
        forwarded = sum(f.leader_channel.stats()["ForwardedPlans"]
                        for f in followers)
        if not (check(forwarded >= 1,
                      "no plan was forwarded by a follower")
                and check(wait_until(lambda: all(
                    len(x.state.allocs_by_job(None, jid)) == 2
                    for x in servers)),
                    "placements not visible on every FSM")):
            return False

        # Lagging-follower streaming install: compact the leader past
        # the horizon, then join a FRESH server — with a 1KB chunk
        # ceiling the install must arrive in multiple chunks.
        os.environ["NOMAD_TPU_SNAPSHOT_CHUNK"] = "1024"
        leader.raft.snapshot()
        chunks_before = _counter_total(leader,
                                       "nomad.raft.snapshot.chunks_sent")
        fresh = Server(ServerConfig(
            node_name="drill-fresh", enable_rpc=True, bootstrap_expect=3,
            start_join=[leader.config.rpc_advertise], num_schedulers=0))
        fresh.start()
        if not check(wait_until(lambda: fresh.state.job_by_id(
                None, jid) is not None, timeout=20.0),
                "fresh joiner did not receive the snapshot"):
            return False
        if not check(wait_until(
                lambda: fresh.raft.base_index >= leader.raft.base_index,
                timeout=10.0), "joiner's log base did not advance"):
            return False
        chunks = _counter_total(leader, "nomad.raft.snapshot.chunks_sent")
        if not check(chunks - chunks_before >= 2,
                     f"snapshot was not chunked ({chunks - chunks_before}"
                     " chunks sent)"):
            return False
    finally:
        if saved is None:
            os.environ.pop("NOMAD_TPU_SNAPSHOT_CHUNK", None)
        else:
            os.environ["NOMAD_TPU_SNAPSHOT_CHUNK"] = saved
        if fresh is not None:
            fresh.shutdown()
        for srv in servers:
            srv.shutdown()
    log("follower drill: OK — 3-voter cluster scheduled on a follower "
        f"({forwarded} plan(s) forwarded to the leader's plan-apply, "
        "visible on all FSMs), and a lagging joiner caught up via "
        f"streaming InstallSnapshot ({chunks - chunks_before} chunks)")
    return True


def _counter_total(server, key: str) -> int:
    sink = server.metrics.sink
    if not hasattr(sink, "latest"):
        return 0
    return int((sink.latest().get("CounterTotals") or {}).get(key, 0))


def chaos_drill(seed: int = 0, log=print) -> bool:
    """Cluster chaos drill (ISSUE 12): a 3-voter in-process cluster
    under the safety auditor — partition a follower (both directions
    via the net plane), commit writes it cannot see, verify it lags,
    heal, verify catch-up, and finish with the auditor's converged
    fingerprint cross-check at ZERO violations."""
    import os
    import time

    from .. import fault
    from ..loadgen.auditor import SafetyAuditor
    from ..server import Server, ServerConfig
    from ..server.rpc import ConnPool
    from ..structs import structs as s

    def check(cond, msg):
        if not cond:
            log(f"chaos drill: FAIL — {msg}")
        return cond

    def wait_until(pred, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if pred():
                return True
            time.sleep(0.02)
        return pred()

    def make_job(jid):
        return s.Job(
            region="global", id=jid, name=jid, type=s.JOB_TYPE_SERVICE,
            priority=50, datacenters=["dc1"],
            task_groups=[s.TaskGroup(
                name="tg", count=1,
                ephemeral_disk=s.EphemeralDisk(size_mb=10),
                tasks=[s.Task(name="t", driver="exec",
                              config={"command": "/bin/date"},
                              resources=s.Resources(cpu=100,
                                                    memory_mb=128),
                              log_config=s.LogConfig())])])

    # Slowed elections: a partitioned VOTER must not campaign during
    # the short split (term inflation would turn the drill into an
    # election-churn test).
    saved = {k: os.environ.get(k) for k in
             ("NOMAD_TPU_RAFT_ELECTION_MIN_S",
              "NOMAD_TPU_RAFT_ELECTION_MAX_S", "NOMAD_TPU_EVENTS")}
    os.environ["NOMAD_TPU_RAFT_ELECTION_MIN_S"] = "8.0"
    os.environ["NOMAD_TPU_RAFT_ELECTION_MAX_S"] = "12.0"
    os.environ["NOMAD_TPU_EVENTS"] = "1"
    servers = []
    auditor = None
    pool = ConnPool()
    pool.chaos_exempt = True
    try:
        first = None
        for i in range(3):
            srv = Server(ServerConfig(
                node_name=f"chaos-s{i + 1}", enable_rpc=True,
                bootstrap_expect=3, start_join=[first] if first else [],
                num_schedulers=0, min_heartbeat_ttl=60.0))
            if first is None:
                first = srv.config.rpc_advertise
            servers.append(srv)
        for srv in servers:
            srv.start()
        if not check(wait_until(lambda: any(
                x.is_leader() and x.raft.is_raft_leader()
                for x in servers)), "no leader elected"):
            return False
        leader = next(x for x in servers if x.is_leader())
        victim = next(x for x in servers if x is not leader)
        if not check(wait_until(lambda: all(
                len(x.raft.peers) == 3 for x in servers)),
                "voter config did not converge"):
            return False

        auditor = SafetyAuditor(
            leader, [x.config.rpc_advertise for x in servers
                     if x is not leader],
            pool=pool, interval=0.25)
        auditor.start()
        leader.job_register(make_job("chaos-pre"))
        if not check(wait_until(lambda: victim.state.job_by_id(
                None, "chaos-pre") is not None),
                "pre-partition write did not replicate"):
            return False

        # Split (both directions: every in-process pool is stamped).
        fault.net_partition("drill", [[leader.config.rpc_advertise],
                                      [victim.config.rpc_advertise]])
        leader.job_register(make_job("chaos-during"))
        time.sleep(0.8)
        if not check(victim.state.job_by_id(None, "chaos-during") is None,
                     "partitioned follower saw a write it cannot have"):
            return False
        fault.net_heal("drill")
        if not check(wait_until(lambda: victim.state.job_by_id(
                None, "chaos-during") is not None, timeout=20.0),
                "healed follower did not catch up"):
            return False
        report = auditor.finalize()
        trace = fault.net().trace()
        if not (check(report["violation_count"] == 0,
                      f"auditor violations: {report['violations']}")
                and check(report["checks"]["fingerprint_matches"] >= 1,
                          "no cross-server fingerprint match recorded")
                and check(("net.partition", "drill", "split") in trace
                          and ("net.partition", "drill", "heal") in trace,
                          f"partition trace incomplete: {trace}")):
            return False
    finally:
        if auditor is not None:
            auditor.stop()
        fault.net_disarm()
        pool.close()
        for srv in servers:
            srv.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    log("chaos drill: OK — partitioned follower blinded then healed and "
        "caught up, auditor recorded 0 violations with "
        f"{report['checks']['fingerprint_matches']} cross-server "
        "fingerprint matches")
    return True


def analysis_drill(seed: int = 0, log=print) -> bool:
    """Invariant-analysis drill (ISSUE 15), three legs:

    1. the static pass is CLEAN on the tree (zero unsuppressed
       violations — the gate tests/test_analysis.py enforces);
    2. the runtime lock-order sanitizer catches a seeded inversion
       (A→B in one thread, B→A in another ⇒ cycle + witness) and is
       acyclic-silent on the well-ordered control;
    3. the native twin/fuzz corpora run clean under ASan+UBSan
       (graceful skip when the toolchain lacks the sanitizer
       runtimes).
    """
    from ..analysis import run_checks
    from ..native.__main__ import run_sanitized
    from ..utils import lockcheck

    def check(cond, msg):
        if not cond:
            log(f"analysis drill: FAIL — {msg}")
        return bool(cond)

    ok = True
    # 1. lint clean.
    active, suppressed = run_checks()
    ok = check(not active,
               f"static pass found {len(active)} unsuppressed "
               f"violation(s): "
               + "; ".join(v.key for v in active[:4])) and ok

    # 2. seeded lock-order inversion caught, witness printed.
    was_armed = lockcheck.armed()
    if not was_armed:
        lockcheck.arm()
    try:
        lockcheck.reset()
        a = lockcheck.make_tracked("drill:lock_a")
        b = lockcheck.make_tracked("drill:lock_b")
        with a:
            with b:
                pass
        ok = check(lockcheck.find_cycle() is None,
                   "well-ordered acquisitions reported a cycle") and ok
        import threading as _threading

        def invert():
            with b:
                with a:
                    pass

        t = _threading.Thread(target=invert, name="drill-invert")
        t.start()
        t.join(5)
        cycle = lockcheck.find_cycle()
        ok = check(cycle is not None,
                   "seeded A→B / B→A inversion not detected") and ok
        if cycle is not None:
            caught = False
            try:
                lockcheck.assert_acyclic()
            except lockcheck.LockOrderError as exc:
                caught = ("drill:lock_a" in str(exc)
                          and "drill:lock_b" in str(exc))
            ok = check(caught, "witness chain missing the seeded "
                               "locks") and ok
    finally:
        lockcheck.reset()
        if not was_armed:
            lockcheck.disarm()

    # 3. sanitized native corpus.
    verdict = run_sanitized(seed=seed, log=log)
    if verdict == "skip":
        log("analysis drill: ASan corpus leg SKIPPED (no sanitizer "
            "toolchain)")
    else:
        ok = check(verdict == "ok", verdict) and ok

    if ok:
        log("analysis drill: OK — lint clean, seeded inversion caught "
            "with witness, sanitized native corpus "
            + ("skipped" if verdict == "skip" else "clean"))
    return ok


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python -m nomad_tpu.ops")
    parser.add_argument("--selfcheck", action="store_true",
                        help="run the oracle-vs-kernel agreement checks")
    parser.add_argument("--mesh-drill-child", action="store_true",
                        help=argparse.SUPPRESS)  # subprocess entry
    parser.add_argument("--nodes", type=int, default=64)
    parser.add_argument("--specs", type=int, default=64)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    if args.mesh_drill_child:
        return 0 if mesh_drill_child(seed=args.seed) else 1
    if not args.selfcheck:
        parser.print_help()
        return 2
    ok = selfcheck(n_nodes=args.nodes, n_specs=args.specs, seed=args.seed)
    ok = breaker_drill(seed=args.seed) and ok
    ok = tracing_drill(seed=args.seed) and ok
    ok = residency_drill(seed=args.seed) and ok
    ok = columnar_drill(seed=args.seed) and ok
    ok = codec_drill(seed=args.seed) and ok
    ok = wal_drill(seed=args.seed) and ok
    ok = fused_drill(seed=args.seed) and ok
    ok = residue_drill(seed=args.seed) and ok
    ok = follower_drill(seed=args.seed) and ok
    ok = chaos_drill(seed=args.seed) and ok
    ok = mesh_drill(seed=args.seed) and ok
    ok = analysis_drill(seed=args.seed) and ok
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
