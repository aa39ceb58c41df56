"""The plan applier's fit re-check: its two routes against the walk.

``PlanApplier.evaluate_plan`` decides a touched node either by the ARRAY
route (slab placements only: one gather off the columnar mirror, one
comparison for all such rows) or by the per-node route (evictions,
preemptions, per-object allocations, ports, rows the mirror dropped).
``_evaluate_nodes_walk`` (allocs_fit over each node's materialized
allocations) is the reference: for seeded random stores and plans of
every shape the routes meet, the ``PlanResult`` has to equal the one
built from the walk's verdicts, with the differential guard off (so a
wrong route cannot hide behind the guard's arbitration) and at cadence
1 (so the guard's own reference has to agree too).  The guard tests
plant a fault in the mirror and pin that a guard run materializes
nothing."""
from __future__ import annotations

import random

import pytest

from nomad_tpu import mock
from nomad_tpu.server.fsm import FSM
from nomad_tpu.server.plan_apply import PlanApplier
from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.server.raft import RaftLog
from nomad_tpu.state import StateStore, columnar
from nomad_tpu.structs import structs as s
from nomad_tpu.utils.telemetry import InmemSink, Telemetry

SEEDS = (11, 12, 13)
ROWS_ARRAY = "nomad.plan.fit.rows_array"
ROWS_SCALAR = "nomad.plan.fit.rows_scalar"
GUARD = "nomad.plan.evaluate.guard"


# -- the world --------------------------------------------------------------


def _alloc(job, node_id, cpu=500, mem=256, combined=True):
    """A per-object allocation; ``combined=False`` is the plan-internal
    form that carries per-task resources only."""
    task = s.Resources(cpu=cpu, memory_mb=mem)
    return s.Allocation(
        id=s.generate_uuid(), eval_id="ev-obj", job_id=job.id,
        node_id=node_id, task_group="web", name=f"{job.id}.web[0]",
        resources=task.copy() if combined else None,
        task_resources={"web": task},
        shared_resources=s.Resources(),
        desired_status=s.ALLOC_DESIRED_STATUS_RUN,
        client_status=s.ALLOC_CLIENT_STATUS_PENDING)


def _slab(job, node_ids, cpu=500, mem=256, ev_id="ev-slab"):
    k = len(node_ids)
    proto = _alloc(job, "", cpu, mem)
    proto.id, proto.name, proto.eval_id = "", "", ev_id
    return s.AllocSlab(proto=proto, ids=s.generate_uuids(k),
                       names=[f"{job.id}.web[{i}]" for i in range(k)],
                       node_ids=list(node_ids))


class World:
    """A seeded store that already carries load in every form the
    re-check reads: per-object rows, a slab drained into the by-node
    index and a slab whose indexing is still pending."""

    def __init__(self, seed, n_nodes=10, networks=False):
        self.rng = random.Random(seed)
        self.store = StateStore()
        self.job = mock.job()
        self.store.upsert_job(1, self.job)
        self.job = self.store.job_by_id(None, self.job.id)
        self.nodes = []
        for i in range(n_nodes):
            node = mock.node()
            node.id = f"node-{i:02d}"
            if not networks:
                node.resources.networks = []
                node.reserved.networks = []
            self.store.upsert_node(2 + i, node)
            self.nodes.append(node)
        self.ids = [n.id for n in self.nodes]
        self.index = 100
        self.objects = [_alloc(self.job, nid, 300, 200)
                        for nid in self.rng.sample(self.ids, n_nodes // 2)]
        self.store.upsert_allocs(self._next(), self.objects, owned=True)
        self.store.upsert_slabs(self._next(), [_slab(
            self.job, self.rng.choices(self.ids, k=n_nodes), 200, 100,
            "ev-old-1")])
        self.store._materialize_pending()
        self.store.upsert_slabs(self._next(), [_slab(
            self.job, self.rng.choices(self.ids, k=n_nodes), 200, 100,
            "ev-old-2")])
        self.sink = InmemSink()
        self.applier = PlanApplier(PlanQueue(), RaftLog(FSM(state=self.store)),
                                   metrics=Telemetry(self.sink))
        self.snap = self.store      # what evaluate_plan reads

    def _next(self):
        self.index += 1
        return self.index

    def plan(self, **kw):
        return s.Plan(eval_id=s.generate_uuid(), job=self.job, **kw)

    def free_cpu(self, node_id):
        """Unreserved, unused cpu on a node, by the store's own rows."""
        node = self.store.node_by_id(None, node_id)
        used = sum(s.alloc_usage_vec(row)[0]
                   for nid, row in self.store.alloc_rows(None)
                   if nid == node_id and not row.terminal_status())
        return node.resources.cpu - node.reserved.cpu - used

    def counters(self):
        totals = self.sink.latest()["CounterTotals"]
        return totals.get(ROWS_ARRAY, 0), totals.get(ROWS_SCALAR, 0)


# -- the cases: each returns (plan, expectations) ----------------------------
#
# Expectations: ``scalar`` = how many touched nodes take the per-node
# route (None: do not pin), ``partial`` = whether some node must be
# rejected.


def case_slab_only(w):
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.rng.sample(w.ids, 6)))
    return plan, dict(scalar=0, partial=False)


def case_two_slabs_overlapping(w):
    """Two slabs share nodes; one node is filled to the last MHz by the
    two together and one is over by the smaller ask, so a verdict turns
    on each slab's own per-node count."""
    full, over = w.ids[4], w.ids[5]
    wide, narrow = [], []
    for node, extra in ((full, 0), (over, 1)):
        free = w.free_cpu(node)
        assert free % 100 == 0 and free >= 600
        wide += [node] * (free // 600)
        narrow += [node] * ((free - 300 * (free // 600)) // 100 + extra)
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.ids[:4] + wide, 300, 10))
    plan.append_slab(_slab(w.job, narrow + w.ids[2:4] + w.ids[6:8], 100, 30))
    return plan, dict(scalar=0, partial=True, rejected={over})


def case_slab_and_object_on_one_node(w):
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.ids[:5]))
    plan.append_alloc(_alloc(w.job, w.ids[2], combined=False))
    return plan, dict(scalar=1, partial=False)


def case_evictions(w):
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.ids))
    for victim in w.objects[:2]:
        plan.append_update(victim, s.ALLOC_DESIRED_STATUS_STOP, "test")
    return plan, dict(scalar=2, partial=False)


def case_eviction_makes_room(w):
    victim = w.objects[0]
    ask = w.free_cpu(victim.node_id) + 200     # fits only once evicted
    plan = w.plan()
    plan.append_update(victim, s.ALLOC_DESIRED_STATUS_STOP, "test")
    plan.append_alloc(_alloc(w.job, victim.node_id, ask, 64))
    return plan, dict(scalar=1, partial=False)


def _preemption(w):
    victim = w.store.alloc_by_id(None, w.objects[0].id)
    plan = w.plan(priority=80)
    plan.append_preempted_alloc(victim)
    plan.append_alloc(_alloc(w.job, victim.node_id))
    plan.append_slab(_slab(w.job, [nid for nid in w.ids
                                   if nid != victim.node_id][:4]))
    return plan, victim


def case_preemption_fresh(w):
    plan, _ = _preemption(w)
    return plan, dict(scalar=1, partial=False)


def case_preemption_stale(w):
    plan, victim = _preemption(w)
    moved = s._fast_copy(victim)
    moved.client_status = s.ALLOC_CLIENT_STATUS_RUNNING
    w.store.update_allocs_from_client(w._next(), [moved])
    return plan, dict(scalar=1, partial=True)


def _slab_row(w, ev_id):
    """A row of the stored plain slab ``ev_id`` (drained or still
    pending), built from the slab itself so the store's table keeps the
    slab as its entry."""
    slabs = [*w.store.allocs_table.values(), *w.store._pending_slabs]
    slab = next(v for v in slabs if type(v) is s.AllocSlab
                and v.proto.eval_id == ev_id)
    return slab.materialize(w.rng.randrange(len(slab)))


def case_slab_row_evicted_makes_room(w):
    """The stopped row is a slab's: the node fits the new ask only once
    that row is taken out by its own id.  The ask reserves a port, so
    the node is decided by allocs_fit over the store's rows, with no
    by-id read of the victim before it."""
    victim = _slab_row(w, "ev-old-1")
    ask = _alloc(w.job, victim.node_id,
                 w.free_cpu(victim.node_id) + victim.resources.cpu, 64)
    ask.resources.networks = [s.NetworkResource(
        device="eth0", mbits=10, reserved_ports=[s.Port("main", 6000)])]
    plan = w.plan()
    plan.append_update(victim, s.ALLOC_DESIRED_STATUS_STOP, "test")
    plan.append_alloc(ask)
    return plan, dict(scalar=1, partial=False)


def case_slab_row_preempted_makes_room(w):
    victim = _slab_row(w, "ev-old-1")
    ask = w.free_cpu(victim.node_id) + victim.resources.cpu
    plan = w.plan(priority=80)
    plan.append_preempted_alloc(victim)
    plan.append_alloc(_alloc(w.job, victim.node_id, ask, 64))
    return plan, dict(scalar=1, partial=False)


def case_slab_row_updated_in_place(w):
    """An in-place update of a pending slab's row that grows it into all
    of its node's free cpu: the old row must not count beside it."""
    victim = _slab_row(w, "ev-old-2")
    updated = victim.copy()
    updated.resources = None
    updated.task_resources = {"web": s.Resources(
        cpu=victim.resources.cpu + w.free_cpu(victim.node_id),
        memory_mb=victim.resources.memory_mb)}
    plan = w.plan()
    plan.append_alloc(updated)
    return plan, dict(scalar=1, partial=False)


def case_port_reserving_alloc(w):
    plan = w.plan()
    ported = mock.alloc()
    ported.job_id, ported.node_id = w.job.id, w.ids[1]
    plan.append_alloc(ported)
    plan.append_slab(_slab(w.job, w.ids[:4]))
    return plan, dict(scalar=1, partial=None)


def case_port_collision(w):
    plan = w.plan()
    for _ in range(2):      # both reserve port 5000 on one node
        ported = mock.alloc()
        ported.id = s.generate_uuid()
        ported.job_id, ported.node_id = w.job.id, w.ids[1]
        plan.append_alloc(ported)
    return plan, dict(scalar=1, partial=True)


def case_slab_with_ports(w):
    plan = w.plan()
    slab = _slab(w.job, w.ids[:3])
    slab.proto.resources.networks = [s.NetworkResource(
        device="eth0", mbits=10, reserved_ports=[s.Port("main", 6000)])]
    plan.append_slab(slab)
    plan.append_slab(_slab(w.job, w.ids[2:6]))
    return plan, dict(scalar=3, partial=None)


def case_node_unknown_to_the_store(w):
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.ids[:3] + ["node-ghost"]))
    return plan, dict(scalar=1, partial=True)


def case_node_registered_after_the_snapshot(w):
    """The mirror's row index is shared with the parent store, so a
    snapshot meets ids whose row lies beyond its own cursor."""
    w.snap = w.store.snapshot()
    late = mock.node()
    late.id = "node-late"
    w.store.upsert_node(w._next(), late)
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.ids[:3] + [late.id]))
    return plan, dict(scalar=1, partial=True)


def case_draining_node(w):
    w.store.update_node_drain(w._next(), w.ids[0], True)
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.ids[:4]))
    return plan, dict(scalar=0, partial=True)


def case_down_node(w):
    w.store.update_node_status(w._next(), w.ids[3], s.NODE_STATUS_DOWN)
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.ids[:6]))
    plan.append_alloc(_alloc(w.job, w.ids[3]))
    return plan, dict(scalar=1, partial=True)


def _over_capacity(w, **kw):
    full = w.ids[4]
    fits = w.free_cpu(full) // 500
    plan = w.plan(**kw)
    plan.append_slab(_slab(w.job, w.ids[:4] + [full] * (fits + 1)))
    return plan


def case_over_capacity_partial(w):
    return _over_capacity(w), dict(scalar=0, partial=True)


def case_over_capacity_gang(w):
    return (_over_capacity(w, all_at_once=True),
            dict(scalar=0, partial=True, gang=True))


def case_exactly_full(w):
    node = w.ids[4]
    free = w.free_cpu(node)
    plan = w.plan()
    plan.append_slab(_slab(w.job, [node] * (free // 100), 100, 1))
    if free % 100:
        plan.append_slab(_slab(w.job, [node], free % 100, 1))
    return plan, dict(scalar=0, partial=False)


def _inflight(w, result):
    w.applier._overlay.add(1, result)


def case_inflight_overlay(w):
    """A pipelined sibling's not-yet-visible placements fill a node the
    plan would otherwise fit on: the array route has to add them."""
    node = w.ids[4]
    free = w.free_cpu(node)
    sibling = s.PlanResult(alloc_slabs=[_slab(
        w.job, [node] * (free // 500) + w.ids[:2], 500, 10, "ev-sib")])
    sibling.node_allocation[w.ids[5]] = [
        _alloc(w.job, w.ids[5], w.free_cpu(w.ids[5]), 10, combined=False)]
    _inflight(w, sibling)
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.ids[:7]))
    return plan, dict(scalar=0, partial=True, rejected={node, w.ids[5]})


def case_inflight_overlay_with_ports(w):
    ported = mock.alloc()
    ported.job_id, ported.node_id = w.job.id, w.ids[1]
    sibling = s.PlanResult(node_allocation={w.ids[1]: [ported]},
                           alloc_slabs=[_slab(w.job, w.ids[:3], 100, 10)])
    _inflight(w, sibling)
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.ids[:5]))
    return plan, dict(scalar=1, partial=None)


def case_evict_only(w):
    plan = w.plan()
    for victim in w.objects[:3]:
        plan.append_update(victim, s.ALLOC_DESIRED_STATUS_STOP, "test")
    return plan, dict(scalar=3, partial=False)


def case_many_rows(w):
    """Above the walk's VECTORIZE_THRESHOLD, so the reference is the
    walk's batched kernel path."""
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.rng.choices(w.ids, k=200), 50, 20))
    plan.append_alloc(_alloc(w.job, w.ids[7], combined=False))
    return plan, dict(scalar=1, partial=None)


CASES = {name[5:]: fn for name, fn in sorted(globals().items())
         if name.startswith("case_")}
WIDE = {"many_rows": 80}
NETWORKED = {"port_reserving_alloc", "port_collision", "slab_with_ports",
             "inflight_overlay_with_ports", "slab_row_evicted_makes_room"}


# -- the reference ----------------------------------------------------------


def result_from_verdicts(snap, plan, fits):
    """evaluatePlan's partial/gang decision over per-node verdicts: the
    loop ``evaluate_plan`` ran before the array route existed."""
    result = s.PlanResult(node_update={}, node_allocation={})
    partial = gang_failed = False
    ok_nodes = set()
    for node_id, fit in fits.items():
        if not fit:
            partial = True
            if plan.all_at_once:
                result.node_update = {}
                result.node_allocation = {}
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
    if gang_failed:
        result.node_preemptions = {}
    else:
        for slab in plan.alloc_slabs:
            if not partial:
                result.alloc_slabs.append(slab)
            else:
                filtered = slab.filter_nodes(ok_nodes)
                if len(filtered):
                    result.alloc_slabs.append(filtered)
    if partial:
        result.refresh_index = max(
            snap.table_index("nodes"), snap.table_index("allocs"))
    return result


def walk_verdicts(w, plan):
    applier = w.applier
    return applier._walk(w.snap, plan, applier._overlay.snapshot(),
                         applier._touched(plan))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("min_rows", [1, columnar.ARRAY_MIN_ROWS])
@pytest.mark.parametrize("case", sorted(CASES))
def test_routes_agree_with_the_walk(case, min_rows, seed, monkeypatch):
    """``min_rows`` 1 sends every eligible row down the array route, so
    the small plans below exercise it; at the shipped value they fall
    under it and all but ``many_rows`` take the per-node route."""
    monkeypatch.setattr(columnar, "ARRAY_MIN_ROWS", min_rows)
    w = World(seed, n_nodes=WIDE.get(case, 10), networks=case in NETWORKED)
    plan, expect = CASES[case](w)
    touched = len(w.applier._touched(plan))
    mismatches = columnar.USAGE_GUARD_MISMATCHES

    # Guard off: the routes' own verdicts, nothing arbitrated.
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "0")
    got = w.applier.evaluate_plan(w.snap, plan)
    n_array, n_scalar = w.counters()
    assert n_array + n_scalar == touched
    if case in WIDE:
        assert n_array == touched - expect["scalar"]
    elif min_rows > 1:
        assert n_array == 0
    elif expect["scalar"] is not None:
        assert n_scalar == expect["scalar"]

    # The walk materializes every touched node's allocations (and
    # drains the pending slab), so it runs after the pass it judges.
    fits = walk_verdicts(w, plan)
    assert len(fits) == touched
    want = result_from_verdicts(w.snap, plan, fits)
    assert got == want
    if expect["partial"] is not None:
        assert (not all(fits.values())) == expect["partial"]
        assert bool(got.refresh_index) == expect["partial"]
    if "rejected" in expect:
        assert {nid for nid, fit in fits.items() if not fit} \
            == expect["rejected"]
    if expect.get("gang"):
        assert not (got.alloc_slabs or got.node_allocation or got.node_update)

    # Guard at every plan, on the store the walk has now drained: the
    # same result, and the guard's reference agreed with the routes.
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "1")
    assert w.applier.evaluate_plan(w.snap, plan) == want
    assert sum(w.counters()) == 2 * touched
    assert w.sink.latest()["SampleTotals"][GUARD][0] == 1
    assert columnar.USAGE_GUARD_MISMATCHES == mismatches


def test_mirrorless_store_is_walked(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR", "0")
    w = World(5)
    plan, _ = case_over_capacity_partial(w)
    got = w.applier.evaluate_plan(w.snap, plan)
    assert got == result_from_verdicts(w.snap, plan, walk_verdicts(w, plan))
    assert w.counters() == (0, len(w.applier._touched(plan)))
    assert GUARD not in w.sink.latest()["SampleTotals"]


# -- the guard --------------------------------------------------------------


def _plant(cols, column, row):
    if column == "usage":
        cols.usage[row] += 10 ** 6
    else:
        cols.cap[row] = 0


@pytest.mark.parametrize("column", ["usage", "cap"])
def test_guard_catches_a_fault_in_the_mirror(column, monkeypatch):
    """One perturbed mirror row turns the array route's verdict for that
    node; the reference reads the tables, disagrees, and the walk's
    verdicts win."""
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "1")
    w = World(7, n_nodes=columnar.ARRAY_MIN_ROWS + 6)
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.ids))
    want = result_from_verdicts(
        w.snap, plan, dict.fromkeys(w.applier._touched(plan), True))
    cols = w.store.columns()
    w.store.column_usage(cols)
    victim = plan.alloc_slabs[0].node_ids[0]
    _plant(cols, column, cols.row_of[victim])
    before = columnar.USAGE_GUARD_MISMATCHES
    epoch = columnar.EPOCH

    got = w.applier.evaluate_plan(w.snap, plan)
    assert columnar.USAGE_GUARD_MISMATCHES == before + 1
    assert columnar.EPOCH == epoch + 1      # every mirror rebuilds
    assert got == want and not got.refresh_index

    # Unguarded, the fault would have cost the node its placements.
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "0")
    cols = w.store.columns()
    w.store.column_usage(cols)
    _plant(cols, column, cols.row_of[victim])
    bad = w.applier.evaluate_plan(w.snap, plan)
    assert bad.refresh_index > 0
    assert victim not in {nid for sl in bad.alloc_slabs for nid in sl.node_ids}


def test_guard_run_materializes_nothing(monkeypatch):
    """Committed slabs on the touched nodes, one drained into the by-node
    index and one still pending: a guarded re-check reads their usage and
    leaves the live store's tables exactly as they were."""
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "1")
    w = World(9, n_nodes=columnar.ARRAY_MIN_ROWS + 6)
    store = w.store
    plan = w.plan()
    plan.append_slab(_slab(w.job, w.ids))

    def shape():
        values = list(store.allocs_table.values())
        return (len(values),
                sum(type(v) is s.Allocation for v in values),
                sum(type(v) is s.AllocSlab for v in values),
                len(store._pending_slabs))

    before = shape()
    assert before[2] == len(w.ids) and before[3] == 1
    got = w.applier.evaluate_plan(store, plan)
    assert w.sink.latest()["SampleTotals"][GUARD][0] == 1
    assert w.counters() == (len(w.ids), 0)
    assert shape() == before
    assert got.alloc_slabs == plan.alloc_slabs and not got.refresh_index
    # ... and what it read is what the walk reads.
    ids = w.applier._touched(plan)
    cols = store.columns()
    rows = columnar.gather_index(cols.row_of, ids)
    _, _, used = store.fit_reference_rows(ids, rows, cols.row_of)
    for i, nid in enumerate(ids):
        node = store.node_by_id(None, nid)
        want = [node.reserved.cpu, node.reserved.memory_mb,
                node.reserved.disk_mb, node.reserved.iops]
        for a in store.allocs_by_node_terminal(None, nid, False):
            for d, v in enumerate(s.alloc_usage_vec(a)):
                want[d] += v
        assert used[i].tolist() == want, nid
