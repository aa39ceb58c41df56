#!/usr/bin/env python3
"""Chip smoke: the served placement path, once, on the accelerator.

One process that holds the chip runs an ``Agent`` (server only, durable
``data_dir`` so raft is the ``FileLog`` with the native group-commit WAL,
``server { use_tpu_batch_worker = true }``) at ``BASELINE.json`` config 2
— 10,000 ready nodes of the ``mock.node()`` shape (4,000 MHz / 8,192 MB,
no networks) kept alive by heartbeat clients, 100 service jobs x count
1,000 (250 MHz / 256 MB) submitted over HTTP ``PUT /v1/jobs`` — waits
under a hard deadline until every eval is terminal, reads the result
back over HTTP, and fails unless

- the result is right: 100,000 live allocations, the placement-integrity
  sweep clean, 1,000 distinct alloc names per queried job, and the first
  10 jobs agree with the CPU oracle on the same inputs (placed counts
  equal to ``GenericScheduler``'s, aggregate ScoreFit within 0.5% of the
  unlimited-candidate oracle's — the repo's score contract);
- the DEVICE did the work: every scheduler invocation is a fused device
  batch, the breaker stayed closed with no trip, no eval was routed to
  the oracle, rejected, redelivered or failed, no node expired, and the
  donated device usage mirror matched the host at every batch
  (``NOMAD_TPU_RESIDENT_GUARD_EVERY=1``).

With more than one device a second leg runs the same load node-sharded
over ``make_node_mesh(jax.devices())`` and must place bit-identically.

Jobs arrive in two bursts (the oracle sample, then the rest), each
submitted while the worker is paused exactly as it is mid-batch, so the
batch composition — and with the pinned tie-break seed every placement —
is the same in every run and on every leg.

Without a TPU the script exits non-zero before it starts a server.
``--dry-run-cpu`` is the only other mode: a tiny size on the CPU backend
for debugging and the tier-1 test; it never prints the pass line.

Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import heapq
import json
import os
import queue
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent))

FULL = SimpleNamespace(nodes=10_000, jobs=100, count=1_000, sample_jobs=10)
DRY = SimpleNamespace(nodes=200, jobs=4, count=50, sample_jobs=2)
RNG_SEED = 20260926            # pinned tie-break jitter: legs must agree
WAVE_DEADLINE_S = 300.0        # per burst, cold compile included
HEARTBEAT_CLIENTS = 8
SCORE_BUDGET_PCT = 0.5         # BASELINE.json: <=0.5% bin-pack regression
NODE_MHZ = 4000 - 100          # mock.node(): resources.cpu - reserved.cpu

PFX = "nomad."
K_INVOKE = PFX + "worker.invoke_scheduler"
K_DEVICE = K_INVOKE + ".device"
# Per-batch samples the watcher splits out, by printed name.
PHASE_KEYS = {"encode": K_INVOKE + ".encode", "device": K_DEVICE,
              "fetch": K_INVOKE + ".fetch",
              "finalize": K_INVOKE + ".finalize", "total": K_INVOKE}


def say(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Every check runs and prints; any failure fails the script."""

    def __init__(self) -> None:
        self.failed = []

    def check(self, ok: bool, what: str) -> None:
        say(f"  {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(what)


class CompileLog:
    """XLA compile seconds and persistent-cache traffic, as JAX itself
    reports them (jax.monitoring); read as deltas around a phase."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_writes = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def snapshot(self):
        return (self.compile_s, self.compiles, self.cache_hits,
                self.cache_writes)

    def since(self, then=(0.0, 0, 0, 0)) -> str:
        now = self.snapshot()
        return (f"XLA compile {now[0] - then[0]:.1f}s over "
                f"{now[1] - then[1]} programs, persistent-cache hits "
                f"{now[2] - then[2]}, writes {now[3] - then[3]}")


def cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.endswith("-atime"))
    except OSError:
        return 0


def make_nodes(n: int):
    from nomad_tpu import mock

    base = mock.node()
    base.resources.networks = []
    base.reserved.networks = []
    base.compute_class()
    nodes = []
    for i in range(n):
        node = base.copy()
        node.id = node.name = f"smoke-node-{i:05d}"
        nodes.append(node)
    return nodes


def make_jobs(n: int, count: int):
    from nomad_tpu.structs import structs as s

    return [s.Job(
        region="global", id=f"smoke-job-{i:03d}", name=f"smoke-job-{i:03d}",
        type=s.JOB_TYPE_SERVICE, priority=50, datacenters=["dc1"],
        task_groups=[s.TaskGroup(
            name="tg", count=count,
            ephemeral_disk=s.EphemeralDisk(size_mb=10),
            tasks=[s.Task(
                name="t", driver="exec", config={"command": "/bin/date"},
                resources=s.Resources(cpu=250, memory_mb=256),
                log_config=s.LogConfig())])]) for i in range(n)]


class Fleet:
    """Registers the nodes in a fixed order (the node order is the
    device's node index, which the tie-break jitter is keyed on) and
    keeps them alive the way loadgen's heartbeat clients do: renew at
    ~70% of each granted TTL."""

    def __init__(self, server) -> None:
        self.server = server
        self.stop = threading.Event()
        self.errors = []
        self._inbox = [queue.SimpleQueue() for _ in range(HEARTBEAT_CLIENTS)]
        self._threads = [
            threading.Thread(target=self._client, args=(q,), daemon=True,
                             name=f"smoke-hb-{i}")
            for i, q in enumerate(self._inbox)]
        for t in self._threads:
            t.start()

    def register(self, nodes) -> None:
        for i, node in enumerate(nodes):
            _, ttl = self.server.node_register(node)
            self._inbox[i % HEARTBEAT_CLIENTS].put(
                (time.monotonic() + 0.7 * ttl, node.id))

    def _client(self, inbox) -> None:
        from nomad_tpu.structs import structs as s

        due = []
        while not self.stop.is_set():
            while not inbox.empty():
                heapq.heappush(due, inbox.get())
            now = time.monotonic()
            while due and due[0][0] <= now:
                _, node_id = heapq.heappop(due)
                try:
                    _, ttl = self.server.node_update_status(
                        node_id, s.NODE_STATUS_READY)
                except Exception as exc:  # reported by the leg, fails it
                    self.errors.append(f"{node_id}: {exc!r}")
                    ttl = 1.0
                heapq.heappush(due, (now + max(0.2, 0.7 * ttl), node_id))
            self.stop.wait(0.1)

    def close(self) -> None:
        self.stop.set()
        for t in self._threads:
            t.join(timeout=5.0)


class Watcher:
    """Polls the server's own metrics sink and ``/v1/broker/stats`` while
    the load runs: per-batch phase samples (each batch emits its samples
    at once, so a step of the lifetime count is one batch), the last
    value of every gauge, and the highest delivery-attempt count and
    failed-queue depth the broker ever reported."""

    def __init__(self, sink, api) -> None:
        self.sink = sink
        self.api = api
        self.stop = threading.Event()
        self.gauges = {}
        self.rows = []             # {phase: ms} per step of the batch count
        self.max_attempts = 0
        self.max_failed = 0
        self.errors = []
        self._seen = {}
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="smoke-watch")
        self._thread.start()

    def poll_metrics(self) -> None:
        latest = self.sink.latest()
        self.gauges.update(latest["Gauges"])
        totals = latest["SampleTotals"]
        n_dev = totals.get(K_DEVICE, (0, 0.0))[0]
        n_seen = self._seen.get(K_DEVICE, (0, 0.0))[0]
        if n_dev > n_seen:
            row = {"batches": n_dev - n_seen}
            for ph, key in PHASE_KEYS.items():
                cnt, tot = totals.get(key, (0, 0.0))
                row[ph] = tot - self._seen.get(key, (0, 0.0))[1]
                self._seen[key] = (cnt, tot)
            self.rows.append(row)

    def _run(self) -> None:
        last_broker = 0.0
        while not self.stop.is_set():
            try:
                self.poll_metrics()
                if time.monotonic() - last_broker >= 0.5:
                    last_broker = time.monotonic()
                    st = self.api.system.broker_stats()
                    hist = st.get("DeliveryAttempts") or {}
                    self.max_attempts = max(
                        [self.max_attempts] + [int(k) for k in hist])
                    self.max_failed = max(
                        self.max_failed, st["ByState"]["failed"])
            except Exception as exc:
                self.errors.append(repr(exc))
            self.stop.wait(0.05)

    def close(self) -> None:
        self.stop.set()
        self._thread.join(timeout=10.0)
        self.poll_metrics()


def set_workers_paused(server, paused: bool) -> None:
    from nomad_tpu.server.worker import DEQUEUE_TIMEOUT

    for w in server.workers:
        w.set_pause(paused)
    if paused:
        # A worker blocked in dequeue parks at its next loop turn.
        time.sleep(2 * DEQUEUE_TIMEOUT + 0.2)


def submit_burst(server, api, jobs) -> float:
    """PUT the jobs while the worker is parked, release it, and wait for
    every eval of those jobs to complete.  Returns seconds from release
    to settled; raises on the deadline and on a blocked, failed or
    cancelled eval (a placement that did not happen)."""
    from nomad_tpu.structs import structs as s

    set_workers_paused(server, True)
    for job in jobs:
        api.jobs.register(job)
    want = {job.id for job in jobs}
    t0 = time.monotonic()
    set_workers_paused(server, False)
    while True:
        evals, _ = api.evaluations.list()
        mine = [e for e in evals if e.job_id in want]
        bad = [e for e in mine if e.status in (
            s.EVAL_STATUS_BLOCKED, s.EVAL_STATUS_FAILED,
            s.EVAL_STATUS_CANCELLED)]
        if bad:
            raise RuntimeError(
                f"{len(bad)} evals {bad[0].status} (e.g. {bad[0].id}: "
                f"{bad[0].status_description})")
        done = {e.job_id for e in mine
                if e.status == s.EVAL_STATUS_COMPLETE}
        if done == want and len(mine) == len(done):
            return time.monotonic() - t0
        if time.monotonic() - t0 > WAVE_DEADLINE_S:
            raise RuntimeError(
                f"deadline: {len(want - done)} of {len(want)} jobs have no "
                f"complete eval after {WAVE_DEADLINE_S:.0f}s")
        time.sleep(0.25)


def live_placements(state, jobs):
    """{job id: sorted node ids of its live allocs} from a snapshot."""
    return {job.id: sorted(
        a.node_id for a in state.allocs_by_job(None, job.id, True)
        if not a.terminal_status()) for job in jobs}


def fleet_arrays(nodes):
    """The reference's view of the fleet: each node's usable cpu and
    memory (resources less the reservation) and its row."""
    import numpy as np

    cap = np.array([[n.resources.cpu - n.reserved.cpu,
                     n.resources.memory_mb - n.reserved.memory_mb]
                    for n in nodes], dtype=np.float64)
    return cap, {n.id: i for i, n in enumerate(nodes)}


def scorefit_sum(state, nodes) -> float:
    """Aggregate final-state ScoreFit over the nodes that carry a live
    allocation in ``state``: the order-free basis for comparing two
    engines' bin-packing on the same fleet."""
    import numpy as np

    from benchmarks import reference

    cap, row_of = fleet_arrays(nodes)
    used = np.zeros_like(cap)
    for nid, row in state.alloc_rows(None):
        if row.terminal_status():
            continue
        res = row.resources
        if res is None:
            # Oracle-path allocs carry per-task resources only (the
            # combined total is normally filled at plan apply).
            tasks = row.task_resources.values()
            used[row_of[nid]] += (sum(t.cpu for t in tasks),
                                  sum(t.memory_mb for t in tasks))
        else:
            used[row_of[nid]] += (res.cpu, res.memory_mb)
    return reference.scorefit_sum(used, cap)


def exact_reference(nodes, jobs):
    """The unlimited-candidate oracle — the kernel's exact objective —
    as ``benchmarks/reference.py``'s numpy twin places it: (ScoreFit
    sum, allocations placed)."""
    import numpy as np

    from benchmarks import reference

    cap, _ = fleet_arrays(nodes)
    groups = [tg for job in jobs for tg in job.task_groups]
    asks = [np.array([sum(t.resources.cpu for t in tg.tasks),
                      sum(t.resources.memory_mb for t in tg.tasks)],
                     dtype=np.float64) for tg in groups]
    used = np.zeros_like(cap)
    placed = 0
    for ask, rows in zip(asks, reference.greedy(
            cap, asks, [tg.count for tg in groups])):
        np.add.at(used, rows, ask)
        placed += len(rows)
    return reference.scorefit_sum(used, cap), placed


def run_oracle(nodes, jobs, unlimited=False):
    """GenericScheduler on a fresh copy of the nodes and jobs, one eval
    after another; returns its state store.  ``unlimited`` lifts the
    LimitIterator candidate cap (select.go:5-44, stack.go:124-137): true
    greedy best-fit through the full iterator stack, O(N x placements)
    in Python, so only at a small size."""
    from nomad_tpu.scheduler import Harness, new_service_scheduler
    from nomad_tpu.scheduler import select as select_mod
    from nomad_tpu.structs import structs as s

    h = Harness()
    for node in nodes:
        h.state.upsert_node(h.next_index(), node.copy())
    set_limit = select_mod.LimitIterator.set_limit
    lifted = []

    def no_limit(self, limit):
        lifted.append(limit)
        set_limit(self, 10**9)

    if unlimited:
        select_mod.LimitIterator.set_limit = no_limit
    try:
        for job in jobs:
            h.state.upsert_job(h.next_index(), job.copy())
            h.process(new_service_scheduler, s.Evaluation(
                id=s.generate_uuid(), priority=job.priority, type=job.type,
                triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
                status=s.EVAL_STATUS_PENDING))
    finally:
        select_mod.LimitIterator.set_limit = set_limit
    if unlimited and not lifted:
        # The stack no longer routes through set_limit: the "unlimited"
        # chain would silently be the sampled one.
        raise RuntimeError("LimitIterator.set_limit never called; "
                           "lifting the candidate cap had no effect")
    return h.state


def oracle_reference(nodes, jobs):
    """The plain reference on a fresh copy of the same nodes and jobs.

    Placed counts come from the oracle as deployed:
    scheduler.testing.Harness + GenericScheduler.  Its ScoreFit sum is
    NOT the 0.5% contract's basis — it scores log2(N) sampled candidates
    per placement, and the accidental spreading inflates a sum of the
    convex 10^freeFrac.  The contract's basis is the unlimited-candidate
    oracle — the kernel's exact objective — through the numpy twin of
    that chain, which the caller validates against the real chain at a
    small size.

    Returns ({job id: placed}, sampled ScoreFit sum, exact ScoreFit sum).
    """
    state = run_oracle(nodes, jobs)
    placed = {jid: len(ids)
              for jid, ids in live_placements(state, jobs).items()}
    exact_sum, _ = exact_reference(nodes, jobs)
    return placed, scorefit_sum(state, nodes), exact_sum


def validate_exact_reference(ck) -> None:
    """The numpy twin equals the REAL GenericScheduler chain with the
    candidate limit lifted, at a size where that chain can run."""
    n, j, c = 200, 2, 50
    nodes, jobs = make_nodes(n), make_jobs(j, c)
    state = run_oracle(nodes, jobs, unlimited=True)
    real_sum = scorefit_sum(state, nodes)
    real_placed = sum(
        len(ids) for ids in live_placements(state, jobs).values())
    twin_sum, twin_placed = exact_reference(nodes, jobs)
    ck.check(real_placed == twin_placed
             and abs(real_sum - twin_sum) <= 1e-6 * real_sum,
             f"exact reference: numpy twin equals the real unlimited "
             f"GenericScheduler chain at {n} nodes x {j * c} "
             f"({twin_sum:.4f} vs {real_sum:.4f})")


def drive(server, api, size, nodes, jobs, compile_log, ck):
    """The load and the answers: two bursts, the oracle comparison in
    between, then the reads.  Returns the final placements."""
    from nomad_tpu.loadgen.auditor import integrity_sweep

    sample, rest = jobs[:size.sample_jobs], jobs[size.sample_jobs:]
    c0 = compile_log.snapshot()
    wall = submit_burst(server, api, sample)
    say(f"  burst A ({len(sample)} jobs, cold) settled in {wall:.1f}s; "
        + compile_log.since(c0))

    # Same inputs, two engines: the server holds exactly the sample jobs
    # now, and so will the oracle's fresh state.
    served_state = server.state.snapshot()
    served = {jid: len(ids) for jid, ids in
              live_placements(served_state, sample).items()}
    served_score = scorefit_sum(served_state, nodes)
    t0 = time.monotonic()
    oracle, sampled_score, exact_score = oracle_reference(nodes, sample)
    delta_pct = abs(served_score - exact_score) / exact_score * 100.0
    say(f"  reference on the sample in {time.monotonic() - t0:.1f}s: "
        f"ScoreFit sum served {served_score:.2f}, exact oracle "
        f"{exact_score:.2f} ({delta_pct:.4f}%), sampled oracle "
        f"{sampled_score:.2f} (spreads more; not the contract's basis)")
    ck.check(served == oracle
             and all(v == size.count for v in served.values()),
             f"placed counts equal GenericScheduler's on the "
             f"{len(sample)}-job sample ({size.count} each)")
    ck.check(delta_pct <= SCORE_BUDGET_PCT,
             f"aggregate ScoreFit within {SCORE_BUDGET_PCT}% of the "
             f"unlimited-candidate oracle")

    c1 = compile_log.snapshot()
    wall = submit_burst(server, api, rest)
    say(f"  burst B ({len(rest)} jobs) settled in {wall:.1f}s; "
        + compile_log.since(c1))

    for job in (jobs[0], jobs[len(jobs) // 2], jobs[-1]):
        stubs, _ = api.jobs.allocations(job.id)
        names = {a["Name"] for a in stubs}
        ck.check(len(stubs) == size.count == len(names)
                 and all(a["DesiredStatus"] == "run" for a in stubs),
                 f"GET /v1/job/{job.id}/allocations: {len(stubs)} allocs, "
                 f"{len(names)} distinct names, all desired run")
    state = server.state.snapshot()
    placements = live_placements(state, jobs)
    per_node = {}
    for ids in placements.values():
        for nid in ids:
            per_node[nid] = per_node.get(nid, 0) + 1
    for nid in (nodes[0].id, max(per_node, key=per_node.get), nodes[-1].id):
        allocs, _ = api.nodes.allocations(nid)
        cpu = sum(a.resources.cpu for a in allocs)
        ck.check(len(allocs) == per_node.get(nid, 0) and cpu <= NODE_MHZ,
                 f"GET /v1/node/{nid}/allocations: {len(allocs)} allocs, "
                 f"{cpu} of {NODE_MHZ} MHz")
    intervals, _ = api.get("/v1/metrics")
    ck.check(any(K_DEVICE in iv["Samples"] for iv in intervals),
             "GET /v1/metrics reports the device batches")

    total = sum(len(v) for v in placements.values())
    ck.check(total == size.jobs * size.count,
             f"{total} live allocations of {size.jobs * size.count}")
    sweep = integrity_sweep(state, {j.id for j in jobs}, strict=True)
    ck.check(sweep["jobs_checked"] == size.jobs and not any(
        sweep[k] for k in ("overplaced_jobs", "duplicate_alloc_names",
                           "overcommitted_nodes")),
        f"integrity sweep clean over {sweep['jobs_checked']} jobs "
        f"{sweep['detail'] or ''}")
    return placements


def device_checks(server, watcher, fleet, mesh, ck) -> None:
    """The device did the work — from the server's own metrics sink."""
    import jax

    from nomad_tpu.ops import resident

    latest = server.metrics.sink.latest()
    counters, samples = latest["CounterTotals"], latest["SampleTotals"]
    gauges = watcher.gauges
    for row in watcher.rows:
        say(f"  device batch x{row['batches']}: " + "  ".join(
            f"{ph} {row[ph] / 1000.0:.3f}s" for ph in PHASE_KEYS))
    n_batches = samples.get(K_DEVICE, (0, 0.0))[0]
    ck.check(n_batches >= 1
             and samples.get(K_INVOKE, (0, 0.0))[0] == n_batches,
             f"every scheduler invocation ran on the device "
             f"({n_batches} batches)")
    ck.check(counters.get(PFX + "batch.fused", 0) == n_batches,
             "every batch was one fused dispatch")
    ck.check(gauges.get(PFX + "breaker.state") == 0
             and gauges.get(PFX + "breaker.trips") == 0,
             "breaker closed, 0 trips")
    for key in ("breaker.oracle_routed", "breaker.kernel_rejects",
                "broker.nack", "heartbeat.invalidate"):
        ck.check(not counters.get(PFX + key), f"no {key}")
    ck.check(watcher.max_attempts <= 1 and watcher.max_failed == 0,
             f"no eval delivered more than once (max attempts "
             f"{watcher.max_attempts}, failed queue {watcher.max_failed})")
    for key in ("batch.resident_guard_mismatches",
                "batch.resident_dev_mismatches"):
        ck.check(not gauges.get(PFX + key), f"no {key}")
    ck.check(resident.GUARD_RUNS >= 1 and resident.DEV_APPLIES >= 1,
             f"device usage mirror checked against the host "
             f"({resident.GUARD_RUNS} guard runs, {resident.DEV_APPLIES} "
             f"donated delta applies)")
    ck.check(not fleet.errors and not watcher.errors,
             f"heartbeat clients and watcher ran clean "
             f"{(fleet.errors + watcher.errors)[:2] or ''}")
    if mesh is not None:
        n_dev = mesh.devices.size
        ck.check(gauges.get(PFX + "batch.mesh_shards") == n_dev
                 and counters.get(PFX + "batch.mesh_passes") == n_batches,
                 f"every batch was a {n_dev}-shard mesh pass")
        holders = set()
        for arr in jax.live_arrays():
            if (arr.dtype == "uint8" and arr.ndim == 2
                    and arr.shape[0] == n_dev):
                holders |= {sh.device for sh in arr.addressable_shards
                            if sh.data.shape[0] == 1}
        ck.check(holders == set(mesh.devices.flat),
                 f"every device holds a shard of the static buffer "
                 f"({len(holders)} of {n_dev})")
    say(f"  compile signatures (batch.compiles): "
        f"{gauges.get(PFX + 'batch.compiles')}")
    for dev in (mesh.devices.flat if mesh is not None
                else jax.devices()[:1]):
        stats = dev.memory_stats() or {}
        say(f"  {dev}: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'not reported')}")


def run_leg(label, size, nodes, jobs, mesh, compile_log, ck):
    """One server lifetime: start, load, read back, check.  Returns the
    final {job: node ids} placements, None if the load did not settle."""
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api.client import NomadAPI
    from nomad_tpu.native import native_wal_available
    from nomad_tpu.ops import resident
    from nomad_tpu.server.raft import FileLog

    say(f"== {label}: {size.nodes} nodes x {size.jobs} jobs x "
        f"{size.count} ==")
    data_dir = tempfile.mkdtemp(prefix="nomad-tpu-smoke-")
    cfg = AgentConfig()
    cfg.server.enabled = True
    cfg.server.use_tpu_batch_worker = True
    cfg.data_dir = data_dir
    cfg.ports.http = cfg.ports.rpc = 0
    agent = Agent(cfg)
    server = agent.server
    # AgentConfig is file-shaped and has no mesh field; an embedding
    # application sets ServerConfig.device_mesh before the workers start.
    server.config.device_mesh = mesh
    resident.reset_counters()
    agent.start()
    fleet = Fleet(server)
    watcher = None
    placements = None
    try:
        t_wait = time.monotonic() + 30.0
        while not server.is_leader() and time.monotonic() < t_wait:
            time.sleep(0.02)
        ck.check(server.is_leader(), "server is leader")
        ck.check(isinstance(server.raft, FileLog),
                 "raft log is the durable FileLog")
        if native_wal_available():
            ck.check(getattr(server.raft, "_nwal", None) is not None,
                     "FileLog runs the native group-commit WAL")
        api = NomadAPI(f"http://127.0.0.1:{agent.http.port}")
        t0 = time.monotonic()
        fleet.register(nodes)
        say(f"  registered {len(nodes)} nodes in "
            f"{time.monotonic() - t0:.1f}s")
        watcher = Watcher(server.metrics.sink, api)
        try:
            placements = drive(server, api, size, nodes, jobs, compile_log,
                               ck)
        except RuntimeError as exc:
            ck.check(False, f"load settled: {exc}")
    finally:
        if watcher is not None:
            watcher.close()
        fleet.close()
        agent.shutdown()
        shutil.rmtree(data_dir, ignore_errors=True)
    device_checks(server, watcher, fleet, mesh, ck)
    return placements


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="tiny size on the CPU backend; not a chip result")
    args = ap.parse_args(argv)
    size = DRY if args.dry_run_cpu else FULL
    if args.dry_run_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["NOMAD_TPU_RESIDENT_GUARD_EVERY"] = "1"
    os.environ["NOMAD_TPU_RNG_SEED"] = str(RNG_SEED)

    import jax

    from nomad_tpu import native
    from nomad_tpu.utils.platform import ensure_compile_cache

    ensure_compile_cache()
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    say(f"platform: {dev['platform']}")
    say(f"device_kind: {dev['kind']}")
    say(f"device_count: {dev['count']}")
    if args.dry_run_cpu:
        say("DRY RUN on the CPU backend at a tiny size: NOT a chip "
            "result; no pass line will be printed")
    elif dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform == "
              f"{dev['platform']!r}); refusing to run on anything else",
              file=sys.stderr)
        return 2

    cache_dir = jax.config.jax_compilation_cache_dir
    entries0 = cache_entries(cache_dir) if cache_dir else 0
    say(f"compile cache: {cache_dir or 'disabled'} "
        f"({entries0} entries before)")

    ck = Checks()
    have_gxx = shutil.which("g++") is not None
    report = native.load_report()
    say("native libraries: " + ", ".join(
        f"{name} {'loaded' if why is None else 'NOT loaded'}"
        for name, why in report.items())
        + ("" if have_gxx else "  (g++ missing: the python twins carry)"))
    if have_gxx:
        for name, why in report.items():
            ck.check(why is None, f"native {name} builds and loads"
                     + (f": {why}" if why else ""))

    validate_exact_reference(ck)
    compile_log = CompileLog()
    nodes = make_nodes(size.nodes)
    jobs = make_jobs(size.jobs, size.count)
    t0 = time.monotonic()
    single = run_leg("single-device leg", size, nodes, jobs, None,
                     compile_log, ck)
    if len(devices) > 1:
        from nomad_tpu.parallel import make_node_mesh

        meshed = run_leg(f"mesh leg ({len(devices)} devices)", size, nodes,
                         jobs, make_node_mesh(devices), compile_log, ck)
        ck.check(single is not None and meshed == single,
                 "mesh placements equal the single-device leg's, job by "
                 "job and node by node")
    else:
        say("mesh leg: skipped (1 device)")
    say(compile_log.since() + " in total")
    if cache_dir:
        say(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} "
            f"entries after, {entries0} before)")
    say(f"wall {time.monotonic() - t0:.1f}s")

    if ck.failed:
        print(f"chip_smoke: {len(ck.failed)} check(s) FAILED: "
              + "; ".join(ck.failed), file=sys.stderr)
        return 1
    if args.dry_run_cpu:
        say("dry run complete: every check passed on the CPU backend "
            "(not a chip result)")
        return 0
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
