"""A batch's plans go to the applier as one group.

The unit that crosses from the batch worker to raft is the batch's list:
one queue item (``PlanQueue.enqueue_group``), one fit re-check for all of
its groupable plans' rows (``PlanApplier._evaluate_plans``), one raft
entry per plan written back to back under ONE fsync
(``RaftLog.apply_many``), and the batch's completed evals in one
``EVAL_UPDATE``.  What a plan means does not change, so the reference
throughout is the same plans taken one after another, each submitted
when its predecessor has been answered: two seeded worlds, one per
route, must end with equal per-plan results, indexes included.  The
durability tests run on a ``FileLog`` in a temporary directory."""
from __future__ import annotations

import collections
import copy
import os
import threading
import time

import pytest

from nomad_tpu import fault, mock
from nomad_tpu.server.fsm import FSM, MessageType
from nomad_tpu.server.plan_apply import PlanApplier
from nomad_tpu.server.plan_queue import PlanFuture, PlanQueue
from nomad_tpu.server.raft import FileLog, NotLeaderError
from nomad_tpu.state import columnar
from nomad_tpu.structs import structs as s
from nomad_tpu.utils.telemetry import InmemSink, Telemetry

from test_plan_fit_routes import World, _alloc, _slab

SEEDS = (21, 22)
SUBMITTED = "nomad.plan.submitted"
EVALUATE = "nomad.plan.evaluate"
APPLY = "nomad.plan.apply"
FSYNC = "nomad.raft.fsync"
GUARD = "nomad.plan.evaluate.guard"
ROWS_ARRAY = "nomad.plan.fit.rows_array"
ROWS_SCALAR = "nomad.plan.fit.rows_scalar"
UNDECIDED = "nomad.plan.group_undecided"
WIDE = columnar.ARRAY_MIN_ROWS + 16


# -- the cases: each returns the plans of one submission, in order ----------
#
# Every case draws from the world's own seeded generator only, so two
# worlds of one seed build the same submission.


def _placing(w, node_ids, cpu=100, mem=10, **kw):
    plan = w.plan(**kw)
    plan.append_slab(_slab(w.job, node_ids, cpu, mem, plan.eval_id))
    return plan


def case_all_fit(w):
    return [_placing(w, w.rng.sample(w.ids, 6)) for _ in range(6)]


def case_all_fit_two_slabs_a_plan(w):
    plans = []
    for _ in range(4):
        plan = _placing(w, w.rng.sample(w.ids, 5))
        plan.append_slab(_slab(w.job, w.rng.choices(w.ids, k=4), 50, 5,
                               plan.eval_id))
        plans.append(plan)
    return plans


def case_two_plans_overfill_a_node(w):
    """The first fits the node alone, the two together do not: taken in
    sequence the first commits whole and the second loses that node."""
    node = w.ids[4]
    free = w.free_cpu(node)
    first = _placing(w, [node] * (free // 200) + w.ids[:3], 100, 10)
    second = _placing(w, [node] * (free // 100 - free // 200 + 1) + w.ids[5:8],
                      100, 10)
    return [_placing(w, w.ids[:4]), first, second, _placing(w, w.ids[6:9])]


def case_draining_node(w):
    w.store.update_node_drain(w._next(), w.ids[2], True)
    return [_placing(w, w.ids[:4]), _placing(w, w.ids[4:8]),
            _placing(w, w.ids[1:3])]


def case_unknown_node(w):
    return [_placing(w, w.ids[:3]), _placing(w, w.ids[3:5] + ["node-ghost"]),
            _placing(w, w.ids[5:9])]


def case_split_by_a_preemption_plan(w):
    victim = w.store.alloc_by_id(None, w.objects[0].id)
    pre = w.plan(priority=80)
    pre.append_preempted_alloc(victim)
    pre.append_alloc(_alloc(w.job, victim.node_id))
    return [_placing(w, w.ids[:4]), _placing(w, w.ids[2:6]), pre,
            _placing(w, w.ids[4:8]), _placing(w, w.ids[6:10])]


def case_split_by_a_node_update_plan(w):
    stop = w.plan()
    stop.append_update(w.objects[1], s.ALLOC_DESIRED_STATUS_STOP, "test")
    stop.append_slab(_slab(w.job, w.ids[:3], 100, 10, stop.eval_id))
    return [_placing(w, w.ids[:5]), stop, _placing(w, w.ids[3:8]),
            _placing(w, w.ids[5:9])]


def case_split_by_a_per_object_plan(w):
    obj = w.plan()
    obj.append_alloc(_alloc(w.job, w.ids[3], combined=False))
    return [_placing(w, w.ids[:5]), _placing(w, w.ids[1:6]), obj,
            _placing(w, w.ids[3:8])]


def case_split_by_a_network_plan(w):
    """A slab whose prototype reserves a static port: a new placement
    like any other, so since ports stopped mattering to grouping the
    four fit in one pass."""
    net = _placing(w, w.ids[:3])
    net.alloc_slabs[0].proto.resources.networks = [s.NetworkResource(
        device="eth0", mbits=10, reserved_ports=[s.Port("main", 6000)])]
    return [_placing(w, w.ids[:5]), net, _placing(w, w.ids[3:8]),
            _placing(w, w.ids[2:9])]


def case_split_by_an_in_place_update(w):
    """A copy of a stored row, ``resources`` None and the row's own
    ``create_index``: it replaces the row, so it is never grouped."""
    inplace = w.plan()
    update = w.store.alloc_by_id(None, w.objects[2].id).copy()
    assert update.create_index
    update.eval_id, update.job, update.resources = inplace.eval_id, None, None
    update.task_resources = {"web": s.Resources(cpu=350, memory_mb=200)}
    inplace.append_alloc(update)
    return [_placing(w, w.ids[:5]), inplace, _placing(w, w.ids[3:8]),
            _placing(w, w.ids[2:9])]


def _ported(w, node_id, static=(), dynamic=(), mbits=10, cpu=50):
    """A new per-object allocation as the batch path builds one for a
    network ask: combined resources and a concrete offer on the node's
    device and address."""
    alloc = _alloc(w.job, node_id, cpu, 10)
    net = s.NetworkResource(
        device="eth0", ip="192.168.0.100", mbits=mbits,
        reserved_ports=[s.Port(f"s{p}", p) for p in static],
        dynamic_ports=[s.Port(f"d{p}", p) for p in dynamic])
    alloc.task_resources["web"].networks = [net]
    alloc.resources.networks = [net.copy()]
    return alloc


def _per_object(w, allocs):
    plan = w.plan()
    for alloc in allocs:
        alloc.eval_id = plan.eval_id
        plan.append_alloc(alloc)
    return plan


def case_ports_all_fit(w):
    """Six plans of port-asking allocations, a static port and two
    dynamic ones each, on overlapping nodes; no port twice on a node."""
    ports = iter(range(21000, 22000))
    plans = []
    for i in range(6):
        plans.append(_per_object(w, [
            _ported(w, nid, static=(7000 + i,),
                    dynamic=(next(ports), next(ports)))
            for nid in w.rng.sample(w.ids, 4)]))
    return plans


def case_ports_static_collision(w):
    """Two plans reserve static port 8000 on one node: the first takes
    it, and in sequence the second loses that node."""
    node = w.ids[3]
    return [_per_object(w, [_ported(w, nid, dynamic=(21000 + i,))
                            for i, nid in enumerate(w.ids[:4])]),
            _per_object(w, [_ported(w, nid, static=(8000,))
                            for nid in [node] + w.ids[5:7]]),
            _per_object(w, [_ported(w, nid, static=(8000,))
                            for nid in [node] + w.ids[7:9]])]


def case_ports_over_bandwidth(w):
    """Each fits the node's 1,000 Mbit alone, the two together do not."""
    node = w.ids[6]
    return [_per_object(w, [_ported(w, node, mbits=600),
                            _ported(w, w.ids[1], mbits=600)]),
            _per_object(w, [_ported(w, w.ids[2], mbits=100)]),
            _per_object(w, [_ported(w, node, mbits=500),
                            _ported(w, w.ids[3], mbits=500)])]


def _net_placing(w, node_ids, static=(), mbits=10):
    """A plan of one network slab as the batch path builds one
    (``AllocSlab.of_offers``): per row an offer on the node's device and
    address, the static ports asked and two dynamic ports of its own."""
    plan = w.plan()
    proto = _alloc(w.job, "", 50, 10)
    proto.id, proto.name, proto.eval_id = "", "", plan.eval_id
    ask = s.NetworkResource(
        mbits=mbits, reserved_ports=[s.Port(f"s{p}", p) for p in static],
        dynamic_ports=[s.Port("d0", 0), s.Port("d1", 0)])
    proto.task_resources["web"].networks = [ask]
    rows = []
    for _ in node_ids:
        d0, d1 = w.rng.sample(range(21000, 60000), 2)
        rows.append([s.NetworkResource(
            device="eth0", ip="192.168.0.100", mbits=mbits,
            reserved_ports=[s.Port(f"s{p}", p) for p in static],
            dynamic_ports=[s.Port("d0", d0), s.Port("d1", d1)])])
    plan.append_slab(s.AllocSlab.of_offers(
        proto, rows, ids=s.generate_uuids(len(node_ids)),
        names=[f"{w.job.id}.web[{i}]" for i in range(len(node_ids))],
        node_ids=list(node_ids)))
    return plan


def case_net_slabs_all_fit(w):
    """Six plans of network slabs, a static port of its own and two
    dynamic ones a row, on overlapping nodes."""
    return [_net_placing(w, w.rng.sample(w.ids, 4), static=(7000 + i,))
            for i in range(6)]


def case_net_slabs_static_collision(w):
    """Two network slabs reserve static port 8000 on one node: in
    sequence the later plan loses that node."""
    node = w.ids[3]
    return [_net_placing(w, w.ids[:4]),
            _net_placing(w, [node] + w.ids[5:7], static=(8000,)),
            _net_placing(w, [node] + w.ids[7:9], static=(8000,))]


def case_gang_plan_among_them(w):
    return [_placing(w, w.ids[:4]), _placing(w, w.ids[4:8], all_at_once=True),
            _placing(w, w.ids[2:6]), _placing(w, w.ids[3:9])]


def case_wide_all_fit(w):
    """Enough rows for the array route: the group's rows together, no
    plan of it alone."""
    return [_placing(w, w.rng.sample(w.ids, 30), 20, 5) for _ in range(5)]


def case_wide_overfill(w):
    node = w.ids[7]
    free = w.free_cpu(node)
    plans = [_placing(w, w.rng.sample(w.ids, 30), 20, 5) for _ in range(3)]
    plans.append(_placing(w, [node] * (free // 100 + 1) + w.ids[:20], 100, 5))
    plans.append(_placing(w, w.rng.sample(w.ids, 30), 20, 5))
    return plans


CASES = {name[5:]: fn for name, fn in sorted(globals().items())
         if name.startswith("case_")}
NETWORKED = {"split_by_a_network_plan", "ports_all_fit",
             "ports_static_collision", "ports_over_bandwidth",
             "net_slabs_all_fit", "net_slabs_static_collision"}
# name -> (plans the group pass decides in one pass, evaluate passes);
# None = the pass decides nothing and every plan goes alone.
ONE_PASS = {"all_fit": (6, 1), "all_fit_two_slabs_a_plan": (4, 1),
            "wide_all_fit": (5, 1), "split_by_a_network_plan": (4, 1),
            "ports_all_fit": (6, 1), "net_slabs_all_fit": (6, 1)}
# name -> group passes that decided nothing (``nomad.plan.group_undecided``)
UNDECIDED_PASSES = dict.fromkeys(
    ["draining_node", "ports_over_bandwidth", "ports_static_collision",
     "net_slabs_static_collision", "two_plans_overfill_a_node",
     "unknown_node", "wide_overfill"], 1)


def _world(case, seed):
    w = World(seed, n_nodes=WIDE if case.startswith("wide") else 10,
              networks=case in NETWORKED)
    # The world wrote its rows at indexes of its own; the log goes on
    # from there, as a server's does.
    raft = w.applier.raft
    raft._last_index = raft._applied = w.index
    raft._apply_next = w.index + 1
    return w


def shape(result):
    """What a plan's answer says, without the slabs' random ids."""
    if result is None:
        return None
    return (result.refresh_index, result.alloc_index,
            [sorted(collections.Counter(slab.node_ids).items())
             for slab in result.alloc_slabs],
            {nid: len(v) for nid, v in result.node_allocation.items()},
            {nid: len(v) for nid, v in result.node_update.items()},
            {nid: len(v) for nid, v in result.node_preemptions.items()})


def usage_by_node(w):
    used = collections.Counter()
    for nid, row in w.store.alloc_rows(None):
        if not row.terminal_status():
            used[nid] += s.alloc_usage_vec(row)[0]
    return used


class served:
    """The world's applier running behind its queue, as the server runs
    it: the plan-applier thread and the commit pool."""

    def __init__(self, w):
        self.w = w

    def __enter__(self):
        self.w.applier.plan_queue.set_enabled(True)
        self.w.applier.start()
        return self.w.applier.plan_queue

    def __exit__(self, *exc):
        self.w.applier.plan_queue.set_enabled(False)
        self.w.applier.stop()


def one_after_another(w, plans):
    """The parent's route: a plan is enqueued when its predecessor has
    been answered."""
    with served(w) as queue:
        return [queue.enqueue(plan).wait(30.0) for plan in plans]


def as_one_group(w, plans):
    with served(w) as queue:
        return [f.wait(30.0) for f in queue.enqueue_group(plans)]


def ports(alloc):
    return sorted((n.mbits, *(p.value for p in n.reserved_ports
                              + n.dynamic_ports))
                  for tr in alloc.task_resources.values()
                  for n in tr.networks)


def logged(w):
    """What each entry the world's log applies says, without the random
    ids and the time stamps: its index, type and plan, and per
    allocation its node and ports (a network slab's rows included);
    filled in as the log applies."""
    entries = []
    fsm_apply = w.applier.raft.fsm.apply

    def apply(index, msg_type, payload):
        entries.append((index, msg_type.name, payload.get("eval_id"),
                        sorted((a.node_id, ports(a))
                               for a in payload.get("allocs", ())),
                        [sorted(collections.Counter(slab.node_ids).items())
                         for slab in payload.get("slabs", ())],
                        [sorted((a.node_id, ports(a)) for a in slab.allocs())
                         for slab in payload.get("slabs", ()) if slab.ips]))
        return fsm_apply(index, msg_type, payload)

    w.applier.raft.fsm.apply = apply
    return entries


def by_plan(entries, plans):
    """``logged``'s entries with each plan's eval id as its position in
    the submission."""
    pos = {plan.eval_id: i for i, plan in enumerate(plans)}
    return [(index, kind, pos.get(eval_id), *rest)
            for index, kind, eval_id, *rest in entries]


def totals(w):
    latest = w.sink.latest()
    counters, samples = latest["CounterTotals"], latest["SampleTotals"]
    return {key: counters.get(key, 0)
            for key in (SUBMITTED, ROWS_ARRAY, ROWS_SCALAR, UNDECIDED)} | {
        key: samples.get(key, (0, 0.0))[0]
        for key in (EVALUATE, APPLY, GUARD)}


# -- the group pass against the sequence ------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("guard", ["0", "1"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_group_results_equal_the_sequence(case, guard, seed, monkeypatch):
    """Guard off, so a wrong group verdict cannot hide behind the
    guard's arbitration; guard at cadence 1, so the guard's reference
    has to agree with every pass as well."""
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", guard)
    mismatches = columnar.USAGE_GUARD_MISMATCHES
    ref, got = _world(case, seed), _world(case, seed)
    ref_plans, got_plans = CASES[case](ref), CASES[case](got)
    ref_log, got_log = logged(ref), logged(got)
    want = [shape(r) for r in one_after_another(ref, ref_plans)]
    results = as_one_group(got, got_plans)
    assert [shape(r) for r in results] == want
    # the log the sequence writes: an entry per plan that commits, in
    # the submission's order, the same allocations and ports
    assert by_plan(got_log, got_plans) == by_plan(ref_log, ref_plans)
    assert len(got_log) == sum(1 for r in results
                               if r.alloc_slabs or r.node_allocation)
    assert usage_by_node(got) == usage_by_node(ref)
    assert got.applier.raft.applied_index() == ref.applier.raft.applied_index()
    assert columnar.USAGE_GUARD_MISMATCHES == mismatches
    # each plan keeps its own slabs, in its own order
    for plan, result in zip(got_plans, results):
        for slab in result.alloc_slabs:
            assert slab.proto.eval_id == plan.eval_id
    n = len(got_plans)
    seen = totals(got)
    assert seen[SUBMITTED] == n == totals(ref)[SUBMITTED]
    assert seen[UNDECIDED] == UNDECIDED_PASSES.get(case, 0)
    if case in ONE_PASS:
        assert (n, seen[EVALUATE]) == ONE_PASS[case]
        assert seen[APPLY] == 1
        assert seen[GUARD] == int(guard)
        assert all(not shape(r)[0] for r in results)
    else:
        assert seen[EVALUATE] > 1


def test_the_overfilled_node_costs_the_second_plan_alone():
    w = _world("two_plans_overfill_a_node", 5)
    plans = case_two_plans_overfill_a_node(w)
    node = w.ids[4]
    lead, first, second, last = as_one_group(w, plans)
    for whole, plan in ((lead, plans[0]), (first, plans[1]), (last, plans[3])):
        assert not whole.refresh_index
        assert whole.alloc_slabs == plan.alloc_slabs
    assert second.refresh_index >= second.alloc_index > first.alloc_index
    kept = {nid for slab in second.alloc_slabs for nid in slab.node_ids}
    assert node not in kept and kept == set(w.ids[5:8])
    # one group pass that decided nothing, then each plan on its own
    assert totals(w)[EVALUATE] == 1 + len(plans)
    assert totals(w)[SUBMITTED] == len(plans)


@pytest.mark.parametrize("case,node", [("ports_static_collision", 3),
                                       ("ports_over_bandwidth", 6)])
def test_a_node_the_group_overfills_on_ports_costs_the_last_plan_alone(
        case, node):
    """The pass decides nothing, once; then in sequence the earlier plan
    commits whole and the later one loses the contested node, keeping
    its others."""
    w = _world(case, 5)
    plans = CASES[case](w)
    results = as_one_group(w, plans)
    assert totals(w)[UNDECIDED] == 1
    assert totals(w)[EVALUATE] == 1 + len(plans)
    for whole, plan in zip(results[:-1], plans):
        assert not whole.refresh_index
        assert whole.node_allocation == plan.node_allocation
    last = results[-1]
    assert last.refresh_index >= last.alloc_index > results[-2].alloc_index
    assert set(last.node_allocation) == set(plans[-1].node_allocation) - {
        w.ids[node]}


def test_a_node_the_group_overfills_with_network_slabs_costs_the_last_plan_alone():
    """As with per-object allocations: the pass decides nothing, once;
    in sequence the later slab loses the contested node, and its partial
    commit keeps each row's ports and IP with the row's node (the slab
    cut by rows, every column alike), in the result and in the store."""
    w = _world("net_slabs_static_collision", 5)
    plans = case_net_slabs_static_collision(w)
    results = as_one_group(w, plans)
    assert totals(w)[UNDECIDED] == 1
    assert totals(w)[EVALUATE] == 1 + len(plans)
    for whole, plan in zip(results[:-1], plans):
        assert not whole.refresh_index
        assert whole.alloc_slabs == plan.alloc_slabs
    last = results[-1]
    assert last.refresh_index >= last.alloc_index > results[-2].alloc_index
    (cut,) = last.alloc_slabs
    (asked,) = plans[-1].alloc_slabs
    assert list(cut.node_ids) == w.ids[7:9] == list(asked.node_ids)[1:]
    assert len(cut.ips) == 2 and len(cut.dyn_ports) == 4 * 2 * 2
    for j, i in enumerate((1, 2)):
        got, want = cut.materialize(j), asked.materialize(i)
        assert (got.id, got.node_id, got.task_resources, got.resources) \
            == (want.id, want.node_id, want.task_resources, want.resources)
        stored = w.store.alloc_by_id(None, got.id)
        assert (stored.node_id, stored.task_resources) \
            == (got.node_id, got.task_resources)
    holders = [a for a in w.store.allocs_by_node(None, w.ids[3])
               if 8000 in ports(a)[0][1:]]
    assert len(holders) == 1


@pytest.mark.parametrize("case,runs", [
    ("split_by_a_preemption_plan", [2, 1, 2]),
    ("split_by_a_node_update_plan", [1, 1, 2]),
    ("split_by_a_per_object_plan", [2, 1, 1]),
    ("split_by_an_in_place_update", [1, 1, 2]),
    ("gang_plan_among_them", [1, 1, 2]),
])
def test_a_plan_that_is_not_groupable_ends_the_run(case, runs):
    """Order kept, the odd plan alone on today's route."""
    w = _world(case, 3)
    plans = CASES[case](w)
    pairs = [(plan, PlanFuture()) for plan in plans]
    cut = list(PlanApplier._runs(pairs))
    assert [len(run) for run in cut] == runs
    assert [pair for run in cut for pair in run] == pairs
    for run in cut:
        assert len(run) == 1 or all(
            PlanApplier._groupable(plan) for plan, _ in run)
    results = as_one_group(w, plans)
    assert all(r is not None for r in results)
    assert totals(w)[EVALUATE] == len(runs)
    assert totals(w)[SUBMITTED] == len(plans)


@pytest.mark.parametrize("case,array", [("all_fit", False),
                                        ("wide_all_fit", True)])
def test_array_min_rows_is_consulted_on_the_groups_rows(case, array,
                                                        monkeypatch):
    """No plan of either group has ``ARRAY_MIN_ROWS`` rows; the wide
    group's rows together do."""
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "0")
    w = _world(case, 4)
    plans = CASES[case](w)
    rows = sum(len(slab.node_ids) for p in plans for slab in p.alloc_slabs)
    assert all(len(p.alloc_slabs[0].node_ids) < columnar.ARRAY_MIN_ROWS
               for p in plans)
    assert (rows >= columnar.ARRAY_MIN_ROWS) == array
    as_one_group(w, plans)
    touched = len({nid for p in plans for slab in p.alloc_slabs
                   for nid in slab.node_ids})
    seen = totals(w)
    assert (seen[ROWS_ARRAY], seen[ROWS_SCALAR]) == (
        (touched, 0) if array else (0, touched))


def test_a_group_of_one_is_a_single_plan():
    """``enqueue`` is ``enqueue_group`` of one: same samples, same
    counters, ``nomad.plan.submitted`` +1."""
    a, b = World(6), World(6)
    as_one_group(a, [_placing(a, a.ids[:4])])
    one_after_another(b, [_placing(b, b.ids[:4])])
    assert totals(a) == totals(b)
    assert totals(a)[SUBMITTED] == totals(a)[EVALUATE] == 1
    la, lb = a.sink.latest(), b.sink.latest()
    assert set(la["SampleTotals"]) == set(lb["SampleTotals"])
    assert set(la["CounterTotals"]) == set(lb["CounterTotals"])


def test_an_empty_plan_of_a_group_is_answered_without_an_entry():
    w = World(8)
    plans = [_placing(w, w.ids[:3]), w.plan(), _placing(w, w.ids[3:6])]
    before = w.applier.raft.applied_index()
    first, empty, last = as_one_group(w, plans)
    assert not (empty.alloc_slabs or empty.node_allocation
                or empty.refresh_index or empty.alloc_index)
    assert (first.alloc_index, last.alloc_index) == (before + 1, before + 2)


# -- the guard ---------------------------------------------------------------


def test_guard_catches_a_fault_in_a_group_pass(monkeypatch):
    """A mirror row that reads emptier than it is lets a group fit that
    does not: the guarded pass holds ALL of the group's rows, with all
    of its slabs' adds, against the store's own rows, the walk's
    verdicts win and the group is taken plan by plan."""
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "1")
    ref, got = _world("wide_overfill", 9), _world("wide_overfill", 9)
    want = [shape(r) for r in
            one_after_another(ref, case_wide_overfill(ref))]
    plans = case_wide_overfill(got)
    cols = got.store.columns()
    got.store.column_usage(cols)
    cols.cap[cols.row_of[got.ids[7]]] += 10 ** 6
    before = columnar.USAGE_GUARD_MISMATCHES
    results = as_one_group(got, plans)
    assert columnar.USAGE_GUARD_MISMATCHES == before + 1
    assert [shape(r) for r in results] == want
    assert want[3][0] > 0       # the overfilling plan was cut, not waved on

    # Unguarded, the planted row would have let every plan through whole.
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "0")
    bad = _world("wide_overfill", 9)
    plans = case_wide_overfill(bad)
    cols = bad.store.columns()
    bad.store.column_usage(cols)
    cols.cap[cols.row_of[bad.ids[7]]] += 10 ** 6
    assert all(not r.refresh_index for r in as_one_group(bad, plans))


def test_guard_cadence_counts_plans(monkeypatch):
    """Cadence 4: a pass in which the count of decided plans crosses a
    multiple of 4 is guarded, whole."""
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "4")
    w = World(10)
    applier = w.applier

    def submit(n):
        as_one_group(w, [_placing(w, w.rng.sample(w.ids, 3), 10, 1)
                         for _ in range(n)])
        return applier._fit_guard_reads, totals(w)[GUARD]

    assert submit(3) == (3, 0)
    assert submit(3) == (6, 1)      # crossed 4
    assert submit(1) == (7, 1)
    assert submit(1) == (8, 2)      # reached 8
    assert submit(9) == (17, 3)     # crossed 12 and 16 in one pass: one run
    # a pass that decides nothing is not counted; its plans are
    node = w.ids[0]
    over = [_placing(w, [node] * (w.free_cpu(node) // 100 + 1), 100, 1)
            for _ in range(2)]
    reads = applier._fit_guard_reads
    as_one_group(w, over)
    assert applier._fit_guard_reads == reads + 2


# -- durability and ordering, on a FileLog -----------------------------------


class Durable:
    """A FileLog under a plan applier, one node and one job registered
    through the log."""

    def __init__(self, data_dir, payloads=None):
        self.sink = InmemSink()
        self.log = FileLog(FSM(), str(data_dir), snapshot_entries=0,
                           snapshot_bytes=0)
        self.log.metrics = Telemetry(self.sink)
        self.applier = PlanApplier(PlanQueue(), self.log,
                                   metrics=Telemetry(self.sink))
        if payloads is None:
            self.job = mock.job()
            self.nodes = []
            for i in range(6):
                node = mock.node()
                node.id = f"node-{i:02d}"
                node.resources.networks = []
                node.reserved.networks = []
                self.nodes.append(node)
            payloads = [(MessageType.NODE_REGISTER, {"node": node})
                        for node in self.nodes]
            payloads.append((MessageType.JOB_REGISTER, {"job": self.job}))
        self.setup = payloads
        for msg_type, payload in payloads:
            self.log.apply(msg_type, payload)
        self.store = self.log.fsm.state

    def plans(self, n):
        job = self.store.job_by_id(None, self.job.id)
        ids = [node.id for node in self.nodes]
        out = []
        for i in range(n):
            plan = s.Plan(eval_id=s.generate_uuid(), job=job)
            plan.append_slab(_slab(job, ids[i % 3:i % 3 + 3], 100, 10,
                                   plan.eval_id))
            out.append(plan)
        return out

    def entries(self, plans):
        return [(MessageType.APPLY_PLAN_RESULTS,
                 self.applier._plan_entry(plan, PlanApplier._whole(plan),
                                          self.store)[0])
                for plan in plans]

    def fsyncs(self):
        return self.sink.latest()["SampleTotals"].get(FSYNC, (0, 0.0))[0]


def placed_by_eval(store, plans):
    return [sorted(a.node_id for a in store.allocs_by_eval(None, p.eval_id))
            for p in plans]


def wal_bytes(data_dir):
    out = {}
    for name in sorted(os.listdir(data_dir)):
        if name.startswith("wal"):
            with open(os.path.join(data_dir, name), "rb") as fh:
                out[name] = fh.read()
    return out


def test_apply_many_is_consecutive_under_one_fsync_and_replays(tmp_path):
    d = Durable(tmp_path)
    plans = d.plans(5)
    entries = d.entries(plans)
    first = d.log.applied_index() + 1
    fsyncs = d.fsyncs()
    outcomes = d.log.apply_many(entries)
    assert [index for _, index in outcomes] == list(range(first, first + 5))
    assert d.fsyncs() == fsyncs + 1
    assert d.log.applied_index() == first + 4
    want = placed_by_eval(d.store, plans)
    assert all(len(nodes) == 3 for nodes in want)
    d.log.close()

    again = FileLog(FSM(), str(tmp_path), snapshot_entries=0,
                    snapshot_bytes=0)
    try:
        assert again.applied_index() == first + 4
        assert placed_by_eval(again.fsm.state, plans) == want
        for p, index in zip(plans, range(first, first + 5)):
            rows = again.fsm.state.allocs_by_eval(None, p.eval_id)
            assert {a.create_index for a in rows} == {index}
    finally:
        again.close()


def test_apply_many_writes_the_records_apply_writes(tmp_path):
    """Byte for byte: the same payloads at the same indexes, one log
    written entry by entry and one as a group, so either replays on the
    other's reader."""
    a = Durable(tmp_path / "a")
    entries = a.entries(a.plans(4))
    b = Durable(tmp_path / "b", payloads=a.setup)
    # (an FSM apply stamps its index into the payload's slabs)
    for msg_type, payload in copy.deepcopy(entries):
        a.log.apply(msg_type, payload)
    b.log.apply_many(entries)
    assert a.log.applied_index() == b.log.applied_index()
    a.log.close()
    b.log.close()
    files = wal_bytes(tmp_path / "a")
    assert files and any(files.values())
    assert files == wal_bytes(tmp_path / "b")


def test_no_future_is_answered_before_the_sync_returns(tmp_path):
    """The last entry's write is held up (a delay at ``wal.fsync``, the
    fault point in front of every write): entries before it are already
    in the file, and nobody has been told."""
    d = Durable(tmp_path)
    plans = d.plans(4)
    pairs = [(plan, PlanFuture()) for plan in plans]
    stamps = {}
    sync = d.log._sync_persist

    def stamped_sync(seq, msg_type, entries=1):
        stamps["answered_at_sync"] = [f.t_responded for _, f in pairs]
        stamps["entries"] = entries
        sync(seq, msg_type, entries)
        stamps["synced"] = time.perf_counter()

    d.log._sync_persist = stamped_sync
    last = d.log.applied_index() + len(plans)
    with fault.scenario({"seed": 1, "faults": [
            {"point": "wal.fsync", "action": "delay", "delay": 0.4,
             "match": {"index": last}}]}):
        worker = threading.Thread(
            target=d.applier._process_plans, args=(pairs, False))
        worker.start()
        time.sleep(0.2)
        assert all(f.t_responded == 0.0 for _, f in pairs)
        assert d.log.applied_index() == last - len(plans)
        worker.join(10.0)
        assert not worker.is_alive()
    assert stamps["entries"] == len(plans)
    assert stamps["answered_at_sync"] == [0.0] * len(plans)
    for _, future in pairs:
        assert future.t_responded >= stamps["synced"]
        assert future.wait(0).alloc_index > 0
    assert d.log.applied_index() == last


def test_a_crash_mid_group_answers_every_future_and_leaves_whole_entries(
        tmp_path):
    d = Durable(tmp_path)
    plans = d.plans(5)
    pairs = [(plan, PlanFuture()) for plan in plans]
    base = d.log.applied_index()
    with fault.scenario({"seed": 1, "faults": [
            {"point": "wal.fsync", "action": "crash",
             "match": {"index": base + 3}}]}):
        d.applier._process_plans(pairs, False)
    for _, future in pairs:
        with pytest.raises(fault.InjectedFault):
            future.wait(0)
    # nothing of the group reached the FSM; the log takes nothing more
    assert d.log.applied_index() == base
    assert placed_by_eval(d.store, plans) == [[]] * 5
    with pytest.raises(NotLeaderError):
        d.log.apply(MessageType.JOB_REGISTER, {"job": mock.job()})
    # no overlay entry outlives its plan
    assert d.applier._overlay.snapshot() == []
    d.log.close()

    again = FileLog(FSM(), str(tmp_path), snapshot_entries=0,
                    snapshot_bytes=0)
    try:
        # the durable prefix: the two whole entries written before it
        assert again.applied_index() == base + 2
        got = placed_by_eval(again.fsm.state, plans)
        assert [len(nodes) for nodes in got] == [3, 3, 0, 0, 0]
        again.apply(MessageType.JOB_REGISTER, {"job": mock.job()})
        assert again.applied_index() == base + 3
    finally:
        again.close()


def test_a_failed_sync_fails_the_group_and_every_later_apply(tmp_path):
    d = Durable(tmp_path)
    plans = d.plans(3)
    base = d.log.applied_index()

    def broken(seq):
        raise OSError("disk gone")

    d.log._do_sync_persist = broken
    outcomes = d.applier.apply_plans(
        [(p, PlanApplier._whole(p)) for p in plans], d.store)
    assert all(isinstance(o, OSError) for o in outcomes)
    assert d.log.applied_index() == base
    assert d.log._sync_inflight == 0
    with pytest.raises(NotLeaderError):
        d.log.apply(MessageType.JOB_REGISTER, {"job": mock.job()})
    d.log.close()


def test_an_fsm_apply_that_raises_fails_its_plan_alone(tmp_path):
    d = Durable(tmp_path)
    plans = d.plans(4)
    pairs = [(plan, PlanFuture()) for plan in plans]
    base = d.log.applied_index()
    fsm_apply = d.log.fsm.apply

    def apply(index, msg_type, payload):
        if index == base + 2:
            raise ValueError("bad entry")
        return fsm_apply(index, msg_type, payload)

    d.log.fsm.apply = apply
    d.applier._process_plans(pairs, False)
    with pytest.raises(ValueError):
        pairs[1][1].wait(0)
    for i in (0, 2, 3):
        assert pairs[i][1].wait(0).alloc_index == base + 1 + i
    assert d.log.applied_index() == base + 4
    assert [len(n) for n in placed_by_eval(d.store, plans)] == [3, 0, 3, 3]
    d.log.apply(MessageType.JOB_REGISTER, {"job": mock.job()})
    d.log.close()


def test_a_write_that_fails_alone_releases_its_index(tmp_path):
    d = Durable(tmp_path)
    plans = d.plans(3)
    base = d.log.applied_index()
    with fault.scenario({"seed": 1, "faults": [
            {"point": "raft.apply", "action": "error", "times": 1,
             "match": {"index": base + 2}}]}):
        first, second, third = d.log.apply_many(d.entries(plans))
    assert isinstance(second, fault.InjectedFault)
    assert (first[1], third[1]) == (base + 1, base + 2)
    assert d.log.applied_index() == base + 2
    d.log.close()


# -- the queue ----------------------------------------------------------------


def test_a_disabled_queue_answers_every_future_of_a_pending_group():
    w = World(2)
    queue = w.applier.plan_queue
    queue.set_enabled(True)
    futures = queue.enqueue_group([_placing(w, w.ids[:3]) for _ in range(4)])
    single = queue.enqueue(_placing(w, w.ids[:3]))
    assert queue.depth() == 2
    queue.set_enabled(False)
    for future in futures + [single]:
        with pytest.raises(RuntimeError, match="disabled"):
            future.wait(1.0)
    with pytest.raises(RuntimeError):
        queue.enqueue_group([_placing(w, w.ids[:3])])


def test_a_groups_priority_is_its_highest_plans():
    w = World(2)
    queue = w.applier.plan_queue
    queue.set_enabled(True)
    low = _placing(w, w.ids[:2], priority=30)
    group = [_placing(w, w.ids[:2], priority=20),
             _placing(w, w.ids[:2], priority=70)]
    queue.enqueue(low)
    queue.enqueue_group(group)
    assert [plan for plan, _ in queue.dequeue(0)] == group
    assert [plan for plan, _ in queue.dequeue(0)] == [low]


# -- the served path ----------------------------------------------------------


class _Parking(threading.Condition):
    """A worker's pause condition that says when the worker waits on it:
    parked, between two dequeues."""

    def __init__(self, parked: threading.Event) -> None:
        super().__init__()
        self.parked = parked

    def wait(self, timeout=None):
        self.parked.set()
        return super().wait(timeout)


def test_a_batch_writes_its_statuses_once_before_any_ack():
    """Four jobs and one unblocked eval in one batch of the BatchWorker:
    one group on the plan queue, every nack clock paused while it waits
    and running after it; the statuses of the evals that complete in ONE
    write to the log (``apply_many``: an entry each, in the order of
    their plans, one fsync) ahead of every ack; the eval that blocks
    again in a write of its own."""
    import conftest
    from nomad_tpu.server import Server, ServerConfig

    srv = Server(ServerConfig(num_schedulers=1, use_tpu_batch_worker=True,
                              batch_size=8))
    srv.start()
    try:
        def small_node(cpu):
            node = mock.node()
            node.resources = s.Resources(cpu=cpu, memory_mb=8192,
                                         disk_mb=100 * 1024, iops=150)
            node.reserved = s.Resources()
            node.resources.networks = []
            srv.node_register(node)

        def job(count, cpu=500):
            j = conftest.batch_job(count)
            j.type = s.JOB_TYPE_SERVICE
            for t in j.task_groups[0].tasks:
                t.resources.cpu = cpu
            return j

        def status(eval_id):
            ev = srv.state.eval_by_id(None, eval_id)
            return ev.status if ev is not None else None

        small_node(1000)
        # two of four fit: the eval completes and leaves a blocked eval
        big = job(4)
        _, big_eval = srv.job_register(big)
        assert conftest.wait_for(
            lambda: status(big_eval) == s.EVAL_STATUS_COMPLETE, 60.0)
        blocked_id = srv.state.eval_by_id(None, big_eval).blocked_eval
        assert blocked_id and conftest.wait_for(
            lambda: srv.blocked_evals.stats()["total_blocked"] == 1, 10.0)
        # The first eval's ack follows its status write: it must land
        # before the log below starts, or it reads as the batch's first.
        assert conftest.wait_for(
            lambda: srv.eval_broker.stats()["total_unacked"] == 0, 10.0)

        log = []
        fsm_apply = srv.raft.fsm.apply

        def recording_apply(index, msg_type, payload):
            if msg_type == MessageType.EVAL_UPDATE:
                log.append(("EVAL_UPDATE",
                            {ev.id: ev.status for ev in payload["evals"]}))
            return fsm_apply(index, msg_type, payload)

        srv.raft.fsm.apply = recording_apply
        apply_many = srv.raft.apply_many

        def recording_many(entries):
            log.append(("write", [
                [ev.id for ev in payload["evals"]]
                if msg_type == MessageType.EVAL_UPDATE else msg_type.name
                for msg_type, payload in entries]))
            return apply_many(entries)

        srv.raft.apply_many = recording_many
        ack = srv.eval_broker.ack

        def recording_ack(eval_id, token):
            log.append(("ack", eval_id))
            return ack(eval_id, token)

        srv.eval_broker.ack = recording_ack
        clocks = {}
        enqueue_group = srv.plan_queue.enqueue_group

        def paused(eval_ids):
            with srv.eval_broker._l:
                return {eid: srv.eval_broker.unack[eid].paused
                        for eid in eval_ids}

        def recording_group(plans, trace_parent=0):
            clocks["ids"] = [plan.eval_id for plan in plans]
            clocks["waiting"] = paused(clocks["ids"])
            return enqueue_group(plans, trace_parent)

        srv.plan_queue.enqueue_group = recording_group
        apply_eval_updates = srv.workers[0].apply_eval_updates

        def recording_updates(evals):
            if "after" not in clocks and "ids" in clocks:
                clocks["after"] = paused(clocks["ids"])
            return apply_eval_updates(evals)

        srv.workers[0].apply_eval_updates = recording_updates

        # Parked means waiting on the pause condition: a dequeue in
        # progress has ended, so no eval below is taken before all five
        # are ready.
        parked = []
        for w in srv.workers:
            parked.append(threading.Event())
            w._pause_cond = _Parking(parked[-1])
            w.set_pause(True)
        assert all(p.wait(10.0) for p in parked)
        small_node(700)             # unblocks: room for one more of two
        jobs = [job(1, cpu=50) for _ in range(4)]
        eval_ids = [srv.job_register(j)[1] for j in jobs]
        assert conftest.wait_for(
            lambda: srv.eval_broker.stats()["total_ready"] == 5, 10.0)
        for w in srv.workers:
            w.set_pause(False)
        assert conftest.wait_for(
            lambda: all(status(e) == s.EVAL_STATUS_COMPLETE
                        for e in eval_ids)
            and srv.eval_broker.stats()["total_unacked"] == 0, 60.0)
        indexes = {
            e: (srv.state.eval_by_id(None, e).modify_index,
                min(a.create_index
                    for a in srv.state.allocs_by_eval(None, e)))
            for e in eval_ids}
    finally:
        srv.shutdown()

    # one group for the batch's plans, clocks held while it waited
    assert set(eval_ids) <= set(clocks["ids"]) and blocked_id in clocks["ids"]
    assert all(clocks["waiting"].values()) and len(clocks["waiting"]) == 5
    assert not any(clocks["after"].values())
    # the four that completed: one write of four entries, in the order
    # of their plans, ahead of every ack
    writes = [entry for kind, entry in log if kind == "write"]
    plans = next(w for w in writes if len(w) == 5)
    assert plans == ["APPLY_PLAN_RESULTS"] * 5
    statuses = next(w for w in writes if len(w) == 4)
    assert sorted(eid for (eid,) in statuses) == sorted(eval_ids)
    assert [eid for (eid,) in statuses] == [
        eid for eid in clocks["ids"] if eid != blocked_id]
    assert sum(1 for w in writes if len(w) > 1) == 2
    updates = [entry for kind, entry in log if kind == "EVAL_UPDATE"]
    done = [u for u in updates if any(
        u.get(eid) == s.EVAL_STATUS_COMPLETE for eid in eval_ids)]
    assert [list(u) for u in done] == statuses
    first_ack = next(i for i, (kind, _) in enumerate(log) if kind == "ack")
    assert all(log.index(("EVAL_UPDATE", u)) < first_ack for u in done)
    # an eval's completion index orders it as its plan's index does
    by_status = sorted(eval_ids, key=lambda e: indexes[e][0])
    assert by_status == sorted(eval_ids, key=lambda e: indexes[e][1])
    # the eval that blocked again wrote its own entry
    own = [u for u in updates if blocked_id in u]
    assert own and all(set(u) == {blocked_id} for u in own)
    assert own[0][blocked_id] == s.EVAL_STATUS_BLOCKED
    assert log.index(("EVAL_UPDATE", own[0])) < first_ack
