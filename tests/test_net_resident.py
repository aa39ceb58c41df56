"""The resident network mirror (ops/resident.py ``NET_DIMS``): a batch
with network asks takes the resident path, folds what the allocations'
networks hold from the state store's feed one delta per allocation write,
and runs a device program whose shapes do not depend on how many nodes
carry allocations.

On the CPU at a small size: 64 ``mock.node()``s, a standing load of
port-holding allocations on 8 of them, then batches of ``mock.job()``
with its network ask (50 Mbit, dynamic ports http and admin), seeded.
"""
import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.ops import kernels, resident
from nomad_tpu.ops.batch_sched import TPUBatchScheduler
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.generic import GenericScheduler
from nomad_tpu.scheduler.stack import GenericStack
from nomad_tpu.structs import structs as s
from nomad_tpu.structs.network import MAX_DYNAMIC_PORT, MIN_DYNAMIC_PORT
from nomad_tpu.server.fsm import FSM
from nomad_tpu.server.plan_apply import PlanApplier
from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.server.raft import RaftLog
from nomad_tpu.utils import tracing
from nomad_tpu.utils.telemetry import InmemSink, Telemetry

NODES, STANDING = 64, 8


@pytest.fixture(autouse=True)
def _fresh_resident(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_RESIDENT", "1")
    monkeypatch.setenv("NOMAD_TPU_RNG_SEED", "41")
    resident.reset_counters()
    yield
    resident.reset_counters()


def reg_eval(job):
    return s.Evaluation(
        id=s.generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
        status=s.EVAL_STATUS_PENDING)


def net_job(count, reserved=()):
    """``mock.job()``, its network ask kept, at ``count``; ``reserved``
    adds static ports to the ask."""
    job = mock.job()
    job.task_groups[0].count = count
    nr = job.task_groups[0].tasks[0].resources.networks[0]
    nr.reserved_ports = [s.Port(f"r{p}", p) for p in reserved]
    return job


def held_alloc(owner, node, cpu, mbits=10, reserved=(), dynamic=()):
    """A live allocation holding bandwidth and ports on ``node``."""
    net = s.NetworkResource(
        device="eth0", ip="192.168.0.100", mbits=mbits,
        reserved_ports=[s.Port(f"r{p}", p) for p in reserved],
        dynamic_ports=[s.Port(f"d{p}", p) for p in dynamic])
    alloc = mock.alloc()
    alloc.job, alloc.job_id, alloc.node_id = owner, owner.id, node.id
    alloc.task_group = "web"
    alloc.task_resources = {"web": s.Resources(cpu=cpu, memory_mb=64,
                                               networks=[net])}
    alloc.resources = s.Resources(cpu=cpu, memory_mb=64, networks=[net])
    return alloc


def standing_fleet(seed, n_nodes=NODES):
    """64 nodes (or ``n_nodes``); the first 8 each carry one
    port-holding allocation of a size of its own (so that their scores
    differ): a dynamic-range port on each, and port 8080 on every other
    one."""
    h = Harness()
    nodes = []
    for _ in range(n_nodes):
        node = mock.node()
        h.state.upsert_node(h.next_index(), node)
        nodes.append(node)
    owner = mock.job()
    h.state.upsert_job(h.next_index(), owner)
    rng = random.Random(seed)
    allocs = [held_alloc(owner, node, cpu=100 + 53 * i,
                         reserved=(8080,) if i % 2 else (),
                         dynamic=(21000 + rng.randrange(1000),))
              for i, node in enumerate(nodes[:STANDING])]
    h.state.upsert_allocs(h.next_index(), allocs)
    return h, nodes


def device_batch(h, jobs):
    for j in jobs:
        h.state.upsert_job(h.next_index(), j)
    h.last_snapshot = h.snapshot()
    sched = TPUBatchScheduler(h.logger, h.last_snapshot, h)
    return sched.schedule_batch([reg_eval(j) for j in jobs])


def oracle_batch(h, jobs):
    for j in jobs:
        h.state.upsert_job(h.next_index(), j)
    for j in jobs:
        GenericScheduler(h.logger, h.snapshot(), h,
                         batch=False).process(reg_eval(j))


@pytest.fixture
def exhaustive_oracle(monkeypatch):
    """GenericScheduler with its candidate limit lifted to the fleet: the
    best node over all of them, as the device path places."""
    set_nodes = GenericStack.set_nodes

    def every_node(self, base_nodes):
        set_nodes(self, base_nodes)
        self.limit.set_limit(max(2, len(base_nodes)))

    monkeypatch.setattr(GenericStack, "set_nodes", every_node)


def live(h, job_id=None):
    allocs = (h.state.allocs_by_job(None, job_id, True) if job_id
              else h.state.allocs(None))
    return [a for a in allocs if not a.terminal_status()]


def node_order(h):
    return [n.id for n in h.state.nodes(None)]


def load_profile(h):
    """Sorted (cpu, Mbit) in use per node: equal between two runs that
    differ only in how ties between like nodes were broken."""
    per = {nid: [0, 0] for nid in node_order(h)}
    for a in live(h):
        per[a.node_id][0] += s.alloc_usage_vec(a)[0]
        per[a.node_id][1] += s.alloc_net_vec(a)[0]
    return sorted(map(tuple, per.values()))


def recompute_net(state):
    """[nodes, 2] Mbit and dynamic-range ports the live allocations hold,
    from the state store, by node order."""
    order = [n.id for n in state.nodes(None)]
    out = np.zeros((len(order), 2), dtype=np.int64)
    for i, nid in enumerate(order):
        mbits, held = 0, set()
        for a in state.allocs_by_node(None, nid):
            if a.terminal_status():
                continue
            for tr in a.task_resources.values():
                if tr.networks:
                    nr = tr.networks[0]
                    mbits += nr.mbits
                    held |= {p.value for p in
                             nr.reserved_ports + nr.dynamic_ports
                             if MIN_DYNAMIC_PORT <= p.value < MAX_DYNAMIC_PORT}
        out[i] = (mbits, len(held))
    return out


def assert_mirror_is_the_state(h):
    """The mirror as the last batch read it, against the state store of
    that batch's snapshot."""
    st = resident._STATE
    assert st is not None and st.net is not None
    want = recompute_net(h.last_snapshot)
    n = len(want)
    np.testing.assert_array_equal(st.net[:n], want)
    assert not st.net[n:].any()
    np.testing.assert_array_equal(np.asarray(st.net_dev)[:n], want)


def fused_signatures():
    return {sig for kind, sig in kernels._COMPILE_SIGS
            if kind == "fused_pass"}


def touched_nodes(h):
    return len({a.node_id for a in live(h)})


@pytest.mark.parametrize("seed", [3, 4])
def test_placements_equal_the_oracle_s(seed, exhaustive_oracle):
    """Per job the same count, and after every batch the same load on
    the fleet up to which of like nodes took it (score ties)."""
    h_d, _ = standing_fleet(seed)
    h_o, _ = standing_fleet(seed)
    for b in range(4):
        jobs = [net_job(10 + 5 * b) for _ in range(2)]
        stats = device_batch(h_d, [j.copy() for j in jobs])
        oracle_batch(h_o, jobs)
        assert stats.fused == 1 and stats.oracle_routed == 0
        for j in jobs:
            assert len(live(h_d, j.id)) == len(live(h_o, j.id)) == j.task_groups[0].count
        assert load_profile(h_d) == load_profile(h_o)


@pytest.mark.parametrize("seed", [5, 6])
def test_every_port_in_range_and_unique_per_node(seed):
    h, nodes = standing_fleet(seed)
    for _ in range(3):
        device_batch(h, [net_job(20), net_job(20)])
    by_node = {}
    for a in live(h):
        for tr in a.task_resources.values():
            for nr in tr.networks:
                by_node.setdefault(a.node_id, []).extend(
                    p.value for p in nr.reserved_ports + nr.dynamic_ports)
                assert all(MIN_DYNAMIC_PORT <= p.value < MAX_DYNAMIC_PORT
                           for p in nr.dynamic_ports)
    for node in nodes:
        ports = by_node.get(node.id, []) + [22]
        assert len(ports) == len(set(ports)), node.id


@pytest.mark.parametrize("guard_every", ["0", "1"])
def test_the_mirror_is_the_state_after_every_batch(guard_every, monkeypatch):
    """Folded (the guard off) or guarded at every batch, the resident
    network mirror and its device twin equal a recompute from the state
    store: as batches place, after allocations stop and free their
    ports, and after a node registers (a rebuild: one walk)."""
    monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", guard_every)
    h, nodes = standing_fleet(7)
    stats = device_batch(h, [net_job(12), net_job(12)])
    assert stats.net_usage_walks == 1          # the cold build
    assert_mirror_is_the_state(h)
    for _ in range(2):
        stats = device_batch(h, [net_job(12), net_job(12)])
        assert stats.resident_hits == 1 and stats.net_usage_walks == 0
        assert stats.net_delta_words > 0
        assert_mirror_is_the_state(h)
    # Half of one job's allocations stop: their ports and Mbit free.
    job_id = live(h)[-1].job_id
    stopped = []
    for a in live(h, job_id)[::2]:
        done = s._fast_copy(a)
        done.client_status = s.ALLOC_CLIENT_STATUS_COMPLETE
        stopped.append(done)
    h.state.update_allocs_from_client(h.next_index(), stopped)
    stats = device_batch(h, [net_job(12)])
    assert stats.resident_hits == 1 and stats.net_usage_walks == 0
    assert_mirror_is_the_state(h)
    # A node registers: the nodes table moves, the mirror is rebuilt.
    h.state.upsert_node(h.next_index(), mock.node())
    stats = device_batch(h, [net_job(12)])
    assert stats.net_usage_walks == 1
    assert_mirror_is_the_state(h)
    stats = device_batch(h, [net_job(12)])
    assert stats.net_usage_walks == 0
    assert_mirror_is_the_state(h)
    assert resident.GUARD_MISMATCHES == 0
    assert resident.DEV_GUARD_MISMATCHES == 0


def recompute_ports(state, ports):
    """{port: [nodes] holders}: how many ports of the live allocations'
    first task networks hold each value, from the state store, by node
    order."""
    order = [n.id for n in state.nodes(None)]
    out = {p: np.zeros(len(order), dtype=np.int64) for p in ports}
    for i, nid in enumerate(order):
        for a in state.allocs_by_node(None, nid):
            if a.terminal_status():
                continue
            for tr in a.task_resources.values():
                if tr.networks:
                    nr = tr.networks[0]
                    for p in nr.reserved_ports + nr.dynamic_ports:
                        if p.value in out:
                            out[p.value][i] += 1
    return out


def assert_columns_are_the_state(h, ports):
    """The mirror holds a column for each of ``ports`` and no other, each
    equal to a recompute from the last batch's snapshot."""
    st = resident._STATE
    assert st is not None and sorted(st.ports) == sorted(ports)
    for port, want in recompute_ports(h.last_snapshot, ports).items():
        n = len(want)
        np.testing.assert_array_equal(st.ports[port][:n], want)
        assert not st.ports[port][n:].any()


def static_jobs(*ports, count=8):
    return [net_job(count, reserved=(p,)) for p in ports]


@pytest.mark.parametrize("guard_every", ["0", "1"])
def test_the_port_columns_are_the_state_after_every_batch(guard_every,
                                                          monkeypatch):
    """Folded (the guard off) or guarded at every batch, each asked
    static port's column equals a recompute from the state store: built
    with the mirror in its one walk, folded as batches place, after
    allocations stop and free their ports, and after a node registers
    (the rebuild's one walk).  A warm batch walks nothing and serves
    every port it asks from a column."""
    monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", guard_every)
    h, nodes = standing_fleet(13)
    # 8080 is held on every other standing node already.
    stats = device_batch(h, static_jobs(8889, 8080))
    assert stats.net_usage_walks == 1 and stats.port_columns == 2
    assert_columns_are_the_state(h, [8080, 8889])
    for _ in range(2):
        stats = device_batch(h, static_jobs(8889, 8080))
        assert stats.resident_hits == 1 and stats.net_usage_walks == 0
        assert stats.port_columns == 2
        assert_columns_are_the_state(h, [8080, 8889])
    # Half of a job's allocations stop: their ports free.
    job_id = live(h)[-1].job_id
    stopped = []
    for a in live(h, job_id)[::2]:
        done = s._fast_copy(a)
        done.client_status = s.ALLOC_CLIENT_STATUS_COMPLETE
        stopped.append(done)
    h.state.update_allocs_from_client(h.next_index(), stopped)
    stats = device_batch(h, static_jobs(8889))
    assert stats.resident_hits == 1 and stats.net_usage_walks == 0
    assert stats.port_columns == 1
    assert_columns_are_the_state(h, [8080, 8889])
    # A node registers: the mirror is rebuilt with the asked port's
    # column alone, in one walk; a port asked later costs one more.
    h.state.upsert_node(h.next_index(), mock.node())
    stats = device_batch(h, static_jobs(8889))
    assert stats.net_usage_walks == 1 and stats.port_columns == 1
    assert_columns_are_the_state(h, [8889])
    stats = device_batch(h, static_jobs(8889, 8080))
    assert stats.net_usage_walks == 1 and stats.port_columns == 2
    assert_columns_are_the_state(h, [8080, 8889])
    stats = device_batch(h, static_jobs(8080))
    assert stats.net_usage_walks == 0 and stats.port_columns == 1
    assert_columns_are_the_state(h, [8080, 8889])
    assert resident.GUARD_MISMATCHES == 0
    for port in (8080, 8889):
        held = recompute_ports(h.state, [port])[port]
        assert held.max() == 1      # the port once a node


def test_a_drifted_port_column_is_caught_by_the_guard(monkeypatch):
    """A column that drifted from the state (here a holder on every node
    of the fleet that no allocation is) reads a mismatch at the guard's
    walk: the mirror is dropped, the breaker fed, and the batch placed
    from the walk, every port still once a node."""
    monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", "1")
    h, _ = standing_fleet(16)
    device_batch(h, static_jobs(8889, count=20))
    resident._STATE.ports[8889][:NODES] += 1
    stats = device_batch(h, static_jobs(8889, count=20))
    assert resident.GUARD_MISMATCHES == 1
    assert stats.fused == 1 and stats.port_columns == 0
    assert recompute_ports(h.state, [8889])[8889].max() == 1
    assert len(live(h)) == STANDING + 40


def test_a_fleet_that_asks_no_static_port_builds_no_column():
    """Dynamic ports only: the mirror folds Mbit and the dynamic-range
    count, and builds no port column."""
    h, _ = standing_fleet(14)
    for _ in range(2):
        stats = device_batch(h, [net_job(10), net_job(10)])
        assert stats.port_columns == 0
    assert resident._STATE.net is not None and resident._STATE.ports == {}


def test_the_program_does_not_grow_with_the_nodes_in_use():
    """Batches of the same two jobs' shape while the nodes that carry
    allocations go from 8 to 60: one fused-program signature."""
    h, _ = standing_fleet(8)
    device_batch(h, [net_job(30), net_job(30)])
    first = fused_signatures()
    seen = [touched_nodes(h)]
    while touched_nodes(h) < 60:
        stats = device_batch(h, [net_job(30), net_job(30)])
        assert stats.fused == 1 and stats.net_usage_walks == 0
        seen.append(touched_nodes(h))
        assert len(seen) < 12, seen
    assert seen[0] < 60 <= seen[-1]
    assert fused_signatures() == first


@pytest.mark.parametrize("how", ["held_static", "held_dynamic", "in_batch"])
def test_a_static_port_ask_avoids_a_used_port(how):
    """A job asking a static port lands on no node where it is in use:
    held by a standing allocation as a static port (8080, on every other
    standing node) or as a dynamic one, or taken by a job earlier in the
    same batch.  The mirror is warm, the port's column with it, so the
    batch reads who holds the port from the column and walks nothing."""
    h, nodes = standing_fleet(9)
    port = {"held_static": 8080, "in_batch": 9090}.get(how)
    if how == "held_dynamic":
        port = next(p.value for a in live(h) for tr in
                    a.task_resources.values() for nr in tr.networks
                    for p in nr.dynamic_ports)
    stats = device_batch(h, [net_job(2, reserved=(port,))])
    assert stats.net_usage_walks == 1       # the mirror and the column
    users = {a.node_id for a in live(h) for tr in a.task_resources.values()
             for nr in tr.networks
             for p in nr.reserved_ports + nr.dynamic_ports
             if p.value == port}
    jobs = [net_job(30, reserved=(port,))]
    if how == "in_batch":
        jobs.append(net_job(30, reserved=(port,)))
    stats = device_batch(h, jobs)
    assert stats.fused == 1 and stats.oracle_routed == 0
    assert stats.net_usage_walks == 0 and stats.port_columns == 1
    # The column the batch read: a holder on each node that uses the
    # port, static or dynamic.
    order = node_order(h)
    held = resident._STATE.ports[port]
    assert {order[i] for i in np.nonzero(held)[0]} == users
    holders = {}
    for j in jobs:
        placed = live(h, j.id)
        assert len(placed) == 30
        for a in placed:
            assert a.node_id not in users, (how, a.node_id)
            holders.setdefault(a.node_id, 0)
            holders[a.node_id] += 1
    assert max(holders.values()) == 1      # the port once a node
    if how != "in_batch":
        assert users


def test_a_multi_ip_node_sends_network_specs_to_the_oracle(monkeypatch):
    """The gate's answer is kept by the nodes table's raft index: a
    second batch on the same fleet walks no node, and a node that
    registers with a multi-IP CIDR sends the next network batch to the
    oracle."""
    h, _ = standing_fleet(10)
    walks, routed = [], []
    walk = TPUBatchScheduler._walk_networks_simple
    route = TPUBatchScheduler._route_through_oracle
    monkeypatch.setattr(TPUBatchScheduler, "_walk_networks_simple",
                        lambda self: walks.append(1) or walk(self))
    monkeypatch.setattr(
        TPUBatchScheduler, "_route_through_oracle",
        lambda self, scheds: routed.extend(ev.id for ev, _ in scheds)
        or route(self, scheds))
    for _ in range(2):
        device_batch(h, [net_job(5)])
    assert len(walks) == 1 and not routed
    node = mock.node()
    node.resources.networks[0].cidr = "10.0.0.0/24"
    h.state.upsert_node(h.next_index(), node)
    job = net_job(5)
    device_batch(h, [job])
    assert len(walks) == 2 and len(routed) == 1
    assert len(live(h, job.id)) == 5


def test_the_offers_are_a_span_when_the_tracer_is_armed():
    """``batch.finalize.offers``: one span a batch, as long as the time
    the batch's offers took (``K.finalize.offers``), none failed."""
    h, _ = standing_fleet(11)
    tracing.enable()
    try:
        stats = device_batch(h, [net_job(10), net_job(10)])
        spans = [sp for sp in tracing.recent(1000)
                 if sp["Name"] == "batch.finalize.offers"]
    finally:
        tracing.disable()
    assert stats.finalize_offers_seconds > 0
    assert stats.net_offer_failures == 0
    (span,) = spans
    assert span["End"] - span["Start"] == pytest.approx(
        stats.finalize_offers_seconds)


def test_the_static_port_bits_are_a_span_inside_encode():
    """``batch.encode.static_ports``: one span for a batch that asks a
    static port, none for one that does not, a child of ``batch.encode``
    inside its stage ``specs``."""
    h, _ = standing_fleet(15)
    tracing.enable()
    try:
        device_batch(h, [net_job(10)])
        device_batch(h, static_jobs(8889))
        spans = tracing.recent(5000)
    finally:
        tracing.disable()
    (span,) = [sp for sp in spans if sp["Name"] == "batch.encode.static_ports"]
    (parent,) = [sp for sp in spans if sp["SpanID"] == span["ParentID"]]
    assert parent["Name"] == "batch.encode"
    (specs,) = [sp for sp in spans if sp["Name"] == "batch.encode.specs"
                and sp["ParentID"] == parent["SpanID"]]
    assert (specs["Start"] - 1e-6 <= span["Start"] <= span["End"]
            <= specs["End"] + 1e-6)


class AppliedPlanner:
    """The harness as planner, but a batch's plans go through a real
    plan queue and applier on the harness's store, as one submission
    (``submit_plans``), the way a served batch worker hands them over."""

    def __init__(self, h):
        self.h = h
        self.sink = InmemSink()
        raft = RaftLog(FSM(state=h.state))
        # the log goes on from the harness's own indexes
        raft._last_index = raft._applied = h.next_index() + 1000
        raft._apply_next = raft._last_index + 1
        self.applier = PlanApplier(PlanQueue(), raft,
                                   metrics=Telemetry(self.sink))
        self.submissions = []

    def submit_plans(self, plans):
        self.submissions.append(len(plans))
        futures = self.applier.plan_queue.enqueue_group(plans)
        return [(future.wait(30.0), None) for future in futures]

    def submit_plan(self, plan):
        return self.submit_plans([plan])[0]

    def __getattr__(self, name):        # eval updates: the harness's
        return getattr(self.h, name)


def test_a_batch_of_network_jobs_is_one_group_at_the_applier(monkeypatch):
    """One scheduler batch of port-asking jobs, its plans one submission
    to the real applier: ONE fit re-check decides them all (one
    ``plan.evaluate`` sample, ``plan.submitted`` = the batch's plans),
    every port is held once on its node, and the guard's walk over the
    group's port-bearing nodes (more than ``VECTORIZE_THRESHOLD``)
    decides them with allocs_fit: no ``batch_allocs_fit`` call on the
    applier's thread, nothing compiled during the submission."""
    import jax.monitoring as mon

    from nomad_tpu.server import plan_apply

    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "1")
    h, nodes = standing_fleet(12, n_nodes=160)
    jobs = [net_job(60) for _ in range(8)]
    for j in jobs:
        h.state.upsert_job(h.next_index(), j)
    planner = AppliedPlanner(h)
    kernel_calls, compiles = [], []
    batch_allocs_fit = kernels.batch_allocs_fit
    monkeypatch.setattr(kernels, "batch_allocs_fit", lambda *a: (
        kernel_calls.append(1), batch_allocs_fit(*a))[1])
    submit = planner.submit_plans

    def counted(plans):
        def on_event(event, secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(secs)

        mon.register_event_duration_secs_listener(on_event)
        try:
            return submit(plans)
        finally:
            mon.unregister_event_duration_listener(on_event)

    planner.submit_plans = counted
    planner.applier.plan_queue.set_enabled(True)
    planner.applier.start()
    try:
        stats = TPUBatchScheduler(h.logger, h.snapshot(), planner
                                  ).schedule_batch([reg_eval(j) for j in jobs])
    finally:
        planner.applier.plan_queue.set_enabled(False)
        planner.applier.stop()
    assert stats.fused == 1 and stats.oracle_routed == 0
    assert stats.net_offer_failures == 0
    assert planner.submissions == [len(jobs)]
    latest = planner.sink.latest()
    counters, samples = latest["CounterTotals"], latest["SampleTotals"]
    assert samples["nomad.plan.evaluate"][0] == 1
    assert counters["nomad.plan.submitted"] == len(jobs)
    assert counters.get("nomad.plan.conflict", 0) == 0
    assert counters.get("nomad.plan.group_undecided", 0) == 0
    assert samples["nomad.plan.evaluate.guard"][0] == 1
    assert samples["nomad.plan.apply"][0] == 1
    touched = {a.node_id for j in jobs for a in live(h, j.id)}
    assert counters["nomad.plan.fit.rows_scalar"] == len(touched) \
        >= plan_apply.VECTORIZE_THRESHOLD
    assert kernel_calls == [] and compiles == []
    by_node = {}
    for j in jobs:
        placed = live(h, j.id)
        assert len(placed) == j.task_groups[0].count
    for a in live(h):
        for tr in a.task_resources.values():
            for nr in tr.networks:
                by_node.setdefault(a.node_id, []).extend(
                    p.value for p in nr.reserved_ports + nr.dynamic_ports)
    for nid, ports in by_node.items():
        ports = ports + [22]
        assert len(ports) == len(set(ports)), nid


def test_a_batch_commits_its_port_asking_placements_as_network_slabs(
        monkeypatch):
    """Every placement of a port-asking job is a row of a network slab
    (``batch.net_slab_rows`` = the allocations placed, no per-object
    allocation in the plans), the resident guard at every hit holds the
    mirror's Mbit, dynamic counts and port columns folded from those
    slabs to the walk with no mismatch, and finalize seeds each node's
    ``NetworkIndex`` from the earlier slabs' columns: no Allocation of a
    network-slab row is materialized inside ``_finalize_build``."""
    monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", "1")
    h, _ = standing_fleet(17)
    inside, materialized, read = [], [], []
    materialize = s.AllocSlab.materialize
    row_networks = s.AllocSlab.row_networks
    build = TPUBatchScheduler._finalize_build

    def counted_materialize(slab, i):
        if inside:
            materialized.append(i)
        return materialize(slab, i)

    def counted_row_networks(slab, i):
        if inside:
            read.append(i)
        return row_networks(slab, i)

    def counted_build(self, *args, **kwargs):
        inside.append(1)
        try:
            return build(self, *args, **kwargs)
        finally:
            inside.pop()

    monkeypatch.setattr(s.AllocSlab, "materialize", counted_materialize)
    monkeypatch.setattr(s.AllocSlab, "row_networks", counted_row_networks)
    monkeypatch.setattr(TPUBatchScheduler, "_finalize_build", counted_build)
    runs = resident.GUARD_RUNS
    batches = []
    for _ in range(3):
        jobs = static_jobs(8889, 8080, count=10) + [net_job(10)]
        stats = device_batch(h, jobs)
        assert stats.fused == 1 and stats.net_offer_failures == 0
        plans = h.plans[-len(jobs):]
        assert all(not plan.node_allocation for plan in plans)
        assert all(slab.ips for plan in plans for slab in plan.alloc_slabs)
        assert stats.net_slab_rows == sum(
            len(slab) for plan in plans for slab in plan.alloc_slabs) == 30
        assert_columns_are_the_state(h, [8080, 8889])
        assert_mirror_is_the_state(h)
        batches.append(jobs)
    # (Read by job only now: a by-id read caches the rows it makes.)
    for jobs in batches:
        assert all(len(live(h, j.id)) == 10 for j in jobs)
    assert read
    assert not materialized, len(materialized)
    assert resident.GUARD_RUNS > runs
    assert resident.GUARD_MISMATCHES == 0
    assert resident.DEV_GUARD_MISMATCHES == 0
    for port in (8080, 8889):
        assert recompute_ports(h.state, [port])[port].max() == 1
