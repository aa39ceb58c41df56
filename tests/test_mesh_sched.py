"""Node-mesh production path tests (ISSUE 8 tentpole).

The fused sharded program (parallel/sharded.sharded_fused_pass, driven
by TPUBatchScheduler._dispatch_mesh) must be BIT-IDENTICAL to the
single-chip fused program — same placements, same per-alloc AllocMetric
scores — under a pinned tie-break seed (NOMAD_TPU_RNG_SEED), on the
8-device virtual CPU mesh conftest forces.  Exactness is by
construction (k_cand ≥ max count ⇒ every round's global top-k lies in
the gathered local top-k candidates), so these are equality tests, not
budget tests.

Plus the PR 5/6 composition on the mesh: single-dispatch/single-fetch
(one ``batch.fetch`` span), device-resident usage deltas landing on the
owning shard, the per-shard differential guard feeding the breaker with
the offending shard id, the staleness fence, non-divisible mesh sizes
padding the node axis up instead of silently falling back, and the
double-buffered schedule_stream driving the mesh dispatch/fetch split.
"""
import random

import jax
import numpy as np
import pytest

from nomad_tpu import fault, mock
from nomad_tpu.ops import batch_sched, resident
from nomad_tpu.ops.batch_sched import TPUBatchScheduler
from nomad_tpu.ops.breaker import KernelCircuitBreaker
from nomad_tpu.parallel import make_node_mesh
from nomad_tpu.scheduler import Harness
from nomad_tpu.structs import structs as s
from nomad_tpu.utils import tracing


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest must force 8 virtual devices"
    return make_node_mesh(jax.devices()[:8])


def make_node(rng=None):
    node = mock.node()
    node.resources.networks = []
    node.reserved.networks = []
    if rng is not None:
        node.resources.cpu = rng.choice([2000, 4000, 8000])
        node.resources.memory_mb = rng.choice([4096, 8192, 16384])
    node.compute_class()
    return node


def make_job(count, rng=None, constrained=False):
    job = mock.job()
    job.task_groups[0].count = count
    for tg in job.task_groups:
        for t in tg.tasks:
            t.resources.networks = []
            if rng is not None:
                t.resources.cpu = rng.choice([100, 250, 500])
                t.resources.memory_mb = rng.choice([64, 256, 512])
    if constrained:
        tg = job.task_groups[0]
        tg.constraints = list(tg.constraints) + [
            s.Constraint("${attr.kernel.name}", "linux", "="),
            s.Constraint("", "", s.CONSTRAINT_DISTINCT_HOSTS),
        ]
    return job


def reg_eval(job):
    return s.Evaluation(
        id=s.generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
        status=s.EVAL_STATUS_PENDING)


def build_twin_problem(seed, n_nodes=24, n_jobs=4, max_count=4,
                       constrained=False):
    rng = random.Random(seed)
    nodes = [make_node(rng) for _ in range(n_nodes)]
    jobs = [make_job(rng.randint(1, max_count), rng,
                     constrained=constrained and i % 2 == 0)
            for i in range(n_jobs)]
    harnesses = []
    for _ in range(2):
        h = Harness()
        for node in nodes:
            h.state.upsert_node(h.next_index(), node.copy())
        for job in jobs:
            h.state.upsert_job(h.next_index(), job)
        harnesses.append(h)
    return harnesses[0], harnesses[1], jobs


def placements_with_scores(h, jobs):
    """(job, tg) → sorted [(node_id, sorted score items)]: the
    bit-identity basis — same kernel ⇒ same slots AND same per-node
    AllocMetric score entries."""
    out = {}
    for job in jobs:
        for a in h.state.allocs_by_job(None, job.id, True):
            if a.terminal_status():
                continue
            scores = tuple(sorted((a.metrics.scores or {}).items()))
            out.setdefault((job.id, a.task_group), []).append(
                (a.node_id, scores))
    return {k: sorted(v) for k, v in out.items()}


def run_batch(h, jobs, monkeypatch, mesh=None, seed=1234, breaker=None):
    monkeypatch.setenv("NOMAD_TPU_RNG_SEED", str(seed))
    kw = {}
    if mesh is not None:
        kw["mesh"] = mesh
    if breaker is not None:
        kw["breaker"] = breaker
    sched = TPUBatchScheduler(h.logger, h.snapshot(), h, **kw)
    return sched.schedule_batch([reg_eval(j) for j in jobs])


class TestMeshBitIdentity:
    @pytest.mark.parametrize("seed", [0, 7])
    def test_mesh_vs_single_chip_bit_identical(self, mesh, seed,
                                               monkeypatch):
        h_mesh, h_single, jobs = build_twin_problem(seed)
        st_m = run_batch(h_mesh, jobs, monkeypatch, mesh=mesh, seed=seed)
        st_s = run_batch(h_single, jobs, monkeypatch, seed=seed)
        assert st_m.mesh_shards == 8 and st_m.fused == 1
        assert st_s.mesh_shards == 0
        pm = placements_with_scores(h_mesh, jobs)
        ps = placements_with_scores(h_single, jobs)
        assert pm == ps
        assert sum(len(v) for v in pm.values()) > 0

    def test_mesh_constrained_distinct_hosts_identical(self, mesh,
                                                       monkeypatch):
        h_mesh, h_single, jobs = build_twin_problem(
            11, n_nodes=20, n_jobs=6, constrained=True)
        run_batch(h_mesh, jobs, monkeypatch, mesh=mesh, seed=11)
        run_batch(h_single, jobs, monkeypatch, seed=11)
        assert (placements_with_scores(h_mesh, jobs)
                == placements_with_scores(h_single, jobs))

    def test_mesh_single_fetch_span(self, mesh, monkeypatch):
        """Single-dispatch/single-fetch contract on the mesh path:
        exactly one ``batch.fetch`` span per healthy batch."""
        h_mesh, _h, jobs = build_twin_problem(3)
        evals = [reg_eval(j) for j in jobs]
        monkeypatch.setenv("NOMAD_TPU_RNG_SEED", "3")
        tracing.enable()
        try:
            sched = TPUBatchScheduler(h_mesh.logger, h_mesh.snapshot(),
                                      h_mesh, mesh=mesh)
            stats = sched.schedule_batch(evals)
            fetches = [sp for sp in tracing.trace_for_eval(evals[0].id)
                       if sp["Name"] == "batch.fetch"]
        finally:
            tracing.disable()
        assert stats.mesh_shards == 8
        assert len(fetches) == 1

    def test_nonuniform_mesh_pads_node_axis_up(self, monkeypatch):
        """A mesh whose size does not divide the 128-row pad (3 devices)
        pads the node axis up to lcm(128, D) — MISSING-filled shards are
        infeasible by construction — instead of abandoning the mesh; the
        result stays bit-identical to single-chip."""
        mesh3 = make_node_mesh(jax.devices()[:3])
        h_mesh, h_single, jobs = build_twin_problem(5, n_nodes=18)
        passes = batch_sched.MESH_PASSES
        st_m = run_batch(h_mesh, jobs, monkeypatch, mesh=mesh3, seed=5)
        assert batch_sched.MESH_PASSES == passes + 1
        assert st_m.mesh_shards == 3
        run_batch(h_single, jobs, monkeypatch, seed=5)
        assert (placements_with_scores(h_mesh, jobs)
                == placements_with_scores(h_single, jobs))

    def test_mesh_network_batch_identical(self, mesh, monkeypatch):
        """Network asks (bandwidth / port accounting) on the mesh path:
        the per-node port/bandwidth state shards like the usage rows and
        placements stay bit-identical to single-chip."""
        nodes = []
        for _ in range(12):
            n = mock.node()          # keeps its mock networks
            n.compute_class()
            nodes.append(n)
        jobs = []
        for _ in range(3):
            j = mock.job()           # tasks keep network asks
            j.task_groups[0].count = 2
            jobs.append(j)
        hs = []
        for _ in range(2):
            h = Harness()
            for n in nodes:
                h.state.upsert_node(h.next_index(), n.copy())
            for j in jobs:
                h.state.upsert_job(h.next_index(), j)
            hs.append(h)
        st_m = run_batch(hs[0], jobs, monkeypatch, mesh=mesh, seed=42)
        run_batch(hs[1], jobs, monkeypatch, seed=42)
        assert st_m.device_ran and st_m.mesh_shards == 8
        pm = placements_with_scores(hs[0], jobs)
        assert pm == placements_with_scores(hs[1], jobs)
        assert sum(len(v) for v in pm.values()) == 6

    def test_mesh_contended_multi_pass_identical(self, mesh, monkeypatch):
        """A contended batch over nodes on three shards: a spec asks
        more allocs than there are nodes, so it takes three or more
        passes (one alloc per node a pass), and the fleet fills before
        the last job is done.  Placements, scores and pass counts stay
        bit-identical to the single chip."""
        rng = random.Random(31)
        nodes = [make_node(rng) for _ in range(40)]
        jobs = [make_job(count) for count in (100, 60)]
        for job in jobs:
            for t in job.task_groups[0].tasks:
                t.resources.cpu, t.resources.memory_mb = 1200, 256
        hs = []
        for _ in range(2):
            h = Harness()
            for node in nodes:
                h.state.upsert_node(h.next_index(), node.copy())
            for job in jobs:
                h.state.upsert_job(h.next_index(), job)
            hs.append(h)
        st_m = run_batch(hs[0], jobs, monkeypatch, mesh=mesh, seed=31)
        st_s = run_batch(hs[1], jobs, monkeypatch, seed=31)
        assert st_m.mesh_shards == 8 and st_s.mesh_shards == 0
        assert st_s.rounds >= 3
        assert ((st_m.rounds, st_m.spec_passes, st_m.multi_round_specs)
                == (st_s.rounds, st_s.spec_passes, st_s.multi_round_specs))
        pm = placements_with_scores(hs[0], jobs)
        assert pm == placements_with_scores(hs[1], jobs)
        placed = sum(len(v) for v in pm.values())
        assert 100 < placed < 160, "the fleet must fill mid-batch"

    def test_mesh_static_port_identical(self, mesh, monkeypatch):
        """Static ports on the mesh: three ``mock.job()``s each ask port
        8889 beside their dynamic ones, 48 allocs for 40 nodes on three
        shards.  A node holds the port once, so the reserved-port bits
        bind placement; it stays bit-identical to the single chip."""
        nodes = []
        for _ in range(40):
            node = mock.node()       # keeps its mock networks
            node.compute_class()
            nodes.append(node)
        jobs = []
        for _ in range(3):
            job = mock.job()
            job.task_groups[0].count = 16
            job.task_groups[0].tasks[0].resources.networks[0] \
                .reserved_ports = [s.Port("lb", 8889)]
            jobs.append(job)
        hs = []
        for _ in range(2):
            h = Harness()
            for node in nodes:
                h.state.upsert_node(h.next_index(), node.copy())
            for job in jobs:
                h.state.upsert_job(h.next_index(), job)
            hs.append(h)
        st_m = run_batch(hs[0], jobs, monkeypatch, mesh=mesh, seed=8)
        run_batch(hs[1], jobs, monkeypatch, seed=8)
        assert st_m.device_ran and st_m.mesh_shards == 8
        pm = placements_with_scores(hs[0], jobs)
        assert pm == placements_with_scores(hs[1], jobs)
        on = [node_id for v in pm.values() for node_id, _ in v]
        assert len(on) == len(set(on)) == 40

    @pytest.mark.slow
    @pytest.mark.parametrize("seed", range(8))
    def test_mesh_fuzz_bit_identical(self, mesh, seed, monkeypatch):
        """Slow fuzz sweep: randomized fleets/jobs (heterogeneous
        resources, mixed counts, constraint/distinct mixes) stay
        bit-identical — placements AND scores — between the mesh and
        single-chip fused programs under the pinned seed."""
        rng = random.Random(1000 + seed)
        h_mesh, h_single, jobs = build_twin_problem(
            2000 + seed,
            n_nodes=rng.randint(9, 60),
            n_jobs=rng.randint(2, 10),
            max_count=rng.randint(2, 12),
            constrained=bool(seed % 2))
        run_batch(h_mesh, jobs, monkeypatch, mesh=mesh, seed=seed)
        run_batch(h_single, jobs, monkeypatch, seed=seed)
        pm = placements_with_scores(h_mesh, jobs)
        ps = placements_with_scores(h_single, jobs)
        assert pm == ps


class TestMeshResident:
    """Sharded-resident composition, mirroring tests/test_resident.py:
    delta apply on the owning shard, per-shard guard, fence, breaker."""

    def _harness(self, n_nodes=12):
        h = Harness()
        for _ in range(n_nodes):
            h.state.upsert_node(h.next_index(), make_node())
        return h

    def _run(self, h, mesh, brk=None, state=None, job=None):
        if job is None:
            job = make_job(2)
            h.state.upsert_job(h.next_index(), job)
        kw = {"breaker": brk} if brk is not None else {}
        sched = TPUBatchScheduler(
            h.logger, state if state is not None else h.snapshot(),
            h, mesh=mesh, **kw)
        stats = sched.schedule_batch([reg_eval(job)])
        placed = len([a for a in h.state.allocs_by_job(None, job.id, True)
                      if not a.terminal_status()])
        return stats, placed

    def test_mesh_delta_path_with_guard(self, mesh, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", "1")
        resident.reset_counters()
        h = self._harness()
        s1, p1 = self._run(h, mesh)
        assert s1.full_reencodes == 1 and not s1.resident_hits
        assert p1 == 2
        s2, p2 = self._run(h, mesh)
        assert s2.resident_hits == 1 and p2 == 2
        assert s2.mesh_shards == 8
        assert resident.GUARD_RUNS >= 1
        assert resident.GUARD_MISMATCHES == 0
        resident.reset_counters()

    def test_mesh_staleness_fence(self, mesh, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", "0")
        resident.reset_counters()
        h = self._harness()
        self._run(h, mesh)
        fence_job = make_job(2)
        h.state.upsert_job(h.next_index(), fence_job)
        stale = h.snapshot()
        self._run(h, mesh)
        self._run(h, mesh)
        cached = resident._STATE.alloc_index
        s3, p3 = self._run(h, mesh, state=stale, job=fence_job)
        assert s3.staleness_fences == 1 and s3.full_reencodes == 1
        assert p3 == 2
        assert resident._STATE.alloc_index == cached, \
            "fence must not regress the mirror"
        resident.reset_counters()

    def test_mesh_guard_trip_attributes_shard(self, mesh, monkeypatch,
                                              caplog):
        """Injected mirror corruption: the per-shard differential guard
        catches it, names the offending shard id, feeds the breaker,
        and the batch still places from the fresh full encode."""
        import logging

        monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", "1")
        resident.reset_counters()
        brk = KernelCircuitBreaker(threshold=0.9, window=8, min_checks=1,
                                   cooldown=3600.0)
        h = self._harness()
        self._run(h, mesh, brk=brk)
        self._run(h, mesh, brk=brk)
        with caplog.at_level(logging.ERROR, "nomad_tpu.ops.resident"):
            with fault.scenario({"seed": 5, "faults": [
                    {"point": "ops.resident_state", "action": "corrupt",
                     "times": 1}]}):
                s3, p3 = self._run(h, mesh, brk=brk)
        assert resident.GUARD_MISMATCHES == 1
        assert brk.state == "open"
        assert p3 == 2, "corrupted-mirror batch must still place"
        assert "mesh shards [" in caplog.text, \
            "guard mismatch must attribute the owning shard"
        resident.reset_counters()

    def test_mesh_schedule_stream_pipelined(self, mesh, monkeypatch):
        """The prepare/dispatch/complete split drives the mesh dispatch
        asynchronously: a double-buffered stream of batches places
        everything with resident delta hits after the cold batch — over
        the DONATED sharded mirror (default on), whose guard-at-every-
        hit bit-compare proves usage is never optimistic (batch k's
        placements land in the mirror only after k finalizes)."""
        monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", "1")
        monkeypatch.setenv("NOMAD_TPU_RESIDENT_DEVICE", "1")
        resident.reset_counters()
        h = self._harness(n_nodes=16)
        jobs, batches = [], []
        for _ in range(4):
            job = make_job(2)
            h.state.upsert_job(h.next_index(), job)
            jobs.append(job)
            batches.append([reg_eval(job)])
        sched = TPUBatchScheduler(h.logger, h.snapshot(), h, mesh=mesh)
        stats = sched.schedule_stream(
            batches, state_source=lambda: h.snapshot())
        assert len(stats) == 4
        assert all(st.mesh_shards == 8 for st in stats)
        assert sum(st.resident_hits for st in stats) >= 3
        assert resident.GUARD_MISMATCHES == 0
        assert resident.DEV_INSTALLS == 1, (
            "the sharded mirror must install once and ride the stream "
            "in place")
        assert resident.DEV_APPLIES >= 2
        assert resident.DEV_GUARD_MISMATCHES == 0
        st_res = resident._STATE
        assert st_res is not None and st_res.used_dev is not None
        np.testing.assert_array_equal(
            np.asarray(st_res.used_dev).astype(np.int64), st_res.used)
        for job in jobs:
            live = [a for a in h.state.allocs_by_job(None, job.id, True)
                    if not a.terminal_status()]
            assert len(live) == 2
        resident.reset_counters()


class TestMeshDonatedMirror:
    """ISSUE 14: the donated per-shard usage mirror on the node mesh.

    The [n_pad, 4] usage matrix lives node-sharded on the mesh (one
    donated [n_local, 4] buffer per shard), is caught up in place by
    shard-routed donated scatter-adds, and is loaned into
    ``sharded_fused_pass`` as a donated arg returned aliased — so the
    replicated per-batch u_rows/u_vals upload disappears.  These pin
    (a) bit-identity of placements AND of the mirror vs the sparse
    delta-upload path after N donated applies, (b) the loan protocol
    under a dispatch exception (slot empties, next batch reinstalls),
    and (c) the NOMAD_TPU_RESIDENT_DEVICE=0 kill-switch."""

    def _harness(self, n_nodes=12):
        h = Harness()
        for i in range(n_nodes):
            node = make_node()
            node.id = f"mesh-dev-{i:02d}"
            node.name = node.id
            h.state.upsert_node(h.next_index(), node)
        return h

    def _stream(self, h, mesh, batches=5, brk=None, count=2, rng=None):
        placements = []
        for _ in range(batches):
            job = make_job(count if rng is None
                           else rng.randint(1, count), rng)
            h.state.upsert_job(h.next_index(), job)
            kw = {"breaker": brk} if brk is not None else {}
            sched = TPUBatchScheduler(h.logger, h.snapshot(), h,
                                      mesh=mesh, **kw)
            sched.schedule_batch([reg_eval(job)])
            placements.append(sorted(
                (a.node_id, tuple(sorted((a.metrics.scores or {}).items())))
                for a in h.state.allocs_by_job(None, job.id, True)
                if not a.terminal_status()))
        return placements

    def test_donated_applies_bit_identical_to_delta_path(self, mesh,
                                                         monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_RNG_SEED", "991")
        monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", "1")

        monkeypatch.setenv("NOMAD_TPU_RESIDENT_DEVICE", "1")
        resident.reset_counters()
        h_dev = self._harness()
        pl_dev = self._stream(h_dev, mesh)
        assert resident.DEV_INSTALLS == 1, (
            "the sharded mirror must install exactly once and then "
            "round-trip in place through the fused mesh program")
        assert resident.DEV_APPLIES >= 4
        st = resident._STATE
        assert st is not None and st.used_dev is not None
        # Physically sharded: every mesh device holds its slice.
        assert len(st.used_dev.sharding.device_set) == 8
        np.testing.assert_array_equal(
            np.asarray(st.used_dev).astype(np.int64), st.used)
        host_mirror = st.used.copy()

        resident.reset_counters()
        monkeypatch.setenv("NOMAD_TPU_RESIDENT_DEVICE", "0")
        h_dl = self._harness()
        pl_dl = self._stream(h_dl, mesh)
        assert resident.DEV_INSTALLS == 0 and resident.DEV_APPLIES == 0
        assert pl_dev == pl_dl
        np.testing.assert_array_equal(resident._STATE.used, host_mirror)
        resident.reset_counters()

    def test_loan_exception_empties_slot_and_reinstalls(self, mesh,
                                                        monkeypatch):
        """A dispatch exception between take and give consumes the
        donated loan: the slot must be EMPTY afterwards (never a dead
        handle) and the next batch reinstalls from host and places."""
        import nomad_tpu.parallel.sharded as shmod

        monkeypatch.setenv("NOMAD_TPU_RESIDENT_DEVICE", "1")
        # Lenient breaker: the injected dispatch failure must feed it
        # WITHOUT opening it, so the next batch exercises the reinstall
        # path rather than the oracle route.
        brk = KernelCircuitBreaker(threshold=0.1, window=32,
                                   min_checks=16, cooldown=3600.0)
        resident.reset_counters()
        h = self._harness()
        self._stream(h, mesh, batches=2, brk=brk)
        assert resident.DEV_INSTALLS == 1

        orig = shmod.sharded_fused_pass

        def boom(*a, **k):
            raise RuntimeError("injected mesh dispatch failure")

        monkeypatch.setattr(shmod, "sharded_fused_pass", boom)
        job = make_job(2)
        h.state.upsert_job(h.next_index(), job)
        sched = TPUBatchScheduler(h.logger, h.snapshot(), h, mesh=mesh,
                                  breaker=brk)
        with pytest.raises(RuntimeError):
            sched.schedule_batch([reg_eval(job)])
        st = resident._STATE
        assert st is not None and st.used_dev is None, (
            "the consumed loan must leave the slot empty")

        monkeypatch.setattr(shmod, "sharded_fused_pass", orig)
        pl = self._stream(h, mesh, batches=1, brk=brk)
        assert len(pl[0]) == 2
        assert resident.DEV_INSTALLS == 2, (
            "the batch after a consumed loan must reinstall from host")
        st = resident._STATE
        assert st is not None and st.used_dev is not None
        np.testing.assert_array_equal(
            np.asarray(st.used_dev).astype(np.int64), st.used)
        resident.reset_counters()

    def test_kill_switch_keeps_delta_upload_path(self, mesh, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_RESIDENT_DEVICE", "0")
        monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", "1")
        resident.reset_counters()
        h = self._harness()
        pl = self._stream(h, mesh, batches=3)
        assert all(len(p) == 2 for p in pl)
        assert resident.DEV_INSTALLS == 0 and resident.DEV_APPLIES == 0
        assert resident.HITS >= 2, "delta path must still serve hits"
        assert resident.GUARD_MISMATCHES == 0
        resident.reset_counters()

    @pytest.mark.slow
    @pytest.mark.parametrize("seed", range(6))
    def test_donated_mirror_fuzz_bit_identical(self, mesh, seed,
                                               monkeypatch):
        """Slow multi-seed fuzz: randomized fleets + job streams place
        bit-identically — placements AND AllocMetric scores — between
        the donated sharded mirror and the delta-upload path, with the
        guard at every hit proving the mirror never drifts."""
        monkeypatch.setenv("NOMAD_TPU_RNG_SEED", str(3000 + seed))
        monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", "1")
        rng = random.Random(7000 + seed)
        n_nodes = rng.randint(10, 40)
        n_batches = rng.randint(3, 7)
        max_count = rng.randint(2, 6)

        out = []
        for flag in ("1", "0"):
            monkeypatch.setenv("NOMAD_TPU_RESIDENT_DEVICE", flag)
            resident.reset_counters()
            h = self._harness(n_nodes=n_nodes)
            out.append(self._stream(
                h, mesh, batches=n_batches, count=max_count,
                rng=random.Random(5000 + seed)))
            assert resident.GUARD_MISMATCHES == 0
            assert resident.DEV_GUARD_MISMATCHES == 0
        assert out[0] == out[1]
        resident.reset_counters()


@pytest.mark.slow
def test_multichip_dryrun_composition(mesh):
    """``__graft_entry__``'s multichip dry run, in process on the
    8-device virtual mesh: two batches through the fused mesh program
    with the resident delta feed.  Deadline-guarded, so that a hang
    fails here instead of stalling the suite."""
    import signal

    import __graft_entry__ as g  # repo root is on sys.path via conftest

    def _timeout(signum, frame):
        raise TimeoutError("the multichip dry run exceeded 120s")

    old = signal.signal(signal.SIGALRM, _timeout)
    signal.alarm(120)
    try:
        g._dryrun_multichip_impl(8)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
