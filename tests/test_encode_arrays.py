"""Encode's two array routes against the loops they replaced.

(a) A host-evaluated feasibility row is decided once per computed class
and gathered by class code, then kept with the static cluster tensors it
was decided on (``encode._host_row``).  The per-node evaluation
(``_check_on_node`` on every node) stays here as the reference.

(b) The resident usage mirror folds the state store's delta feed as
arrays (``resident._feed_rows``: one index gather, one scatter-add); the
per-tuple fold it replaced stays here as the reference, as does the
per-tuple shard routing of ``encode.route_shard_deltas``.

Nothing here is a device number."""
import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.ops import batch_sched, encode, resident
from nomad_tpu.ops.batch_sched import TPUBatchScheduler
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.structs import structs as s


# -- (a) host constraint rows ------------------------------------------------

def make_node(i):
    node = mock.node()
    node.resources.networks = []
    node.reserved.networks = []
    node.id = f"node-id-{i:03d}"
    node.name = f"node-{i:02d}"
    node.node_class = ("small-a", "medium-b", "large-c")[i % 3]
    node.attributes["nomad.version"] = ("0.4.1", "0.5.6", "0.6.0")[
        (i // 3) % 3]
    node.attributes["driver.exec"] = "1"
    if i % 4:
        node.meta["rack"] = f"r{i % 5}"
    else:
        node.meta.pop("rack", None)
    return node


def fleet(n=48):
    """Nine computed classes and more (class x version x rack), every
    eighth node with no computed class; those differ among themselves,
    so deciding them as one class would be wrong."""
    nodes = []
    for i in range(n):
        node = make_node(i)
        if i % 8 == 7:
            node.attributes["nomad.version"] = ("0.4.1", "0.6.0")[
                (i // 8) % 2]
            node.computed_class = ""
        else:
            node.compute_class()
        nodes.append(node)
    assert len({n.computed_class for n in nodes}) > 9
    return nodes


def spec_with(constraints):
    job = mock.job()
    for tg in job.task_groups:
        for t in tg.tasks:
            t.resources.networks = []
    job.constraints = [s.Constraint(ltarget=lt, operand=op, rtarget=rt)
                       for lt, op, rt in constraints]
    job.task_groups[0].constraints = []
    return encode.build_spec(job, job.task_groups[0], batch_penalty=False)


def encoded(nodes, spec):
    targets, literals = encode.collect_attr_targets([spec])
    ct = encode.encode_cluster(nodes, targets)
    encode.finalize_codebooks(ct, literals)
    return ct


def per_node(nodes, constraint):
    lt, op, rt = constraint
    ctx = EvalContext(state=None, plan=s.Plan())
    con = s.Constraint(ltarget=lt, operand=op, rtarget=rt)
    return np.array([encode._check_on_node(ctx, con, node)
                     for node in nodes])


HOST_CONSTRAINTS = [
    pytest.param(("${attr.nomad.version}", "version", ">= 0.5.0"),
                 id="version"),
    pytest.param(("${node.class}", "regexp", "^(small|medium)-"),
                 id="regexp"),
    pytest.param(("${node.unique.name}", "regexp", "^node-[0-3][157]$"),
                 id="escaped"),
    pytest.param(("${meta.rack}", "regexp", "^r[12]$"),
                 id="resolves_on_some_nodes"),
    pytest.param(("${node.class}", "!=", "${meta.rack}"),
                 id="interpolated_rhs_on_some_nodes"),
    pytest.param(("${attr.nomad.version}", "set_contains", "0.5.6"),
                 id="set_contains"),
]


@pytest.mark.parametrize("constraint", HOST_CONSTRAINTS)
def test_class_gather_row_equals_the_per_node_evaluation(constraint):
    nodes = fleet()
    spec = spec_with([constraint])
    ct = encoded(nodes, spec)
    st = encode.encode_specs([spec], ct, nodes)
    want = per_node(nodes, constraint)
    assert 0 < want.sum() < len(nodes)
    np.testing.assert_array_equal(st.precomp[0, :len(nodes)], want)
    # Padding columns stay true (ineligible there by other means).
    assert st.precomp[0, len(nodes):].all()
    assert len(st.row_stamps) == 1 and st.rows_reused == 0

    # The next batch, on a clone that differs in usage alone, is served
    # the kept row: nothing is evaluated, the answer is the same.
    later = encode.with_usage(ct, ct.used + 1)
    st2 = encode.encode_specs([spec, spec], later, nodes)
    assert len(st2.row_stamps) == 2 and st2.rows_reused == 2
    np.testing.assert_array_equal(st2.precomp[1, :len(nodes)], want)


def test_class_checks_run_once_per_class_and_escaped_ones_per_node(
        monkeypatch):
    nodes = fleet()
    classes = len({n.computed_class for n in nodes if n.computed_class})
    loners = sum(1 for n in nodes if not n.computed_class)
    calls = []
    real = encode._check_on_node
    monkeypatch.setattr(
        encode, "_check_on_node",
        lambda ctx, con, node: calls.append(node.id) or real(ctx, con, node))
    spec = spec_with([("${attr.nomad.version}", "version", ">= 0.5.0"),
                      ("${node.unique.name}", "regexp", "^node-1")])
    ct = encoded(nodes, spec)
    encode.encode_specs([spec], ct, nodes)
    # One check per class present, the class-less nodes each on their
    # own (their class code's representative is checked twice), then
    # every node for the constraint that escapes class semantics.
    assert len(calls) == classes + 1 + loners + len(nodes)


def test_driver_rows_take_the_same_kept_row_path():
    nodes = fleet()
    for i, node in enumerate(nodes):
        # Two truthy spellings: the column cannot lower to one compare.
        node.attributes["driver.exec"] = ("1", "true", "0")[i % 3]
        if node.computed_class:
            node.compute_class()
    spec = spec_with([])
    assert spec.drivers == {"exec"}
    ct = encoded(nodes, spec)
    st = encode.encode_specs([spec], ct, nodes)
    want = np.array([i % 3 != 2 for i in range(len(nodes))])
    np.testing.assert_array_equal(st.precomp[0, :len(nodes)], want)
    assert len(st.row_stamps) == 1 and st.rows_reused == 0
    st2 = encode.encode_specs([spec], encode.with_usage(ct, ct.used), nodes)
    assert st2.rows_reused == 1
    np.testing.assert_array_equal(st2.precomp[0, :len(nodes)], want)


def test_kept_rows_are_bounded(monkeypatch):
    monkeypatch.setattr(encode, "HOST_ROWS_KEPT", 3)
    nodes = fleet(16)
    cons = [("${attr.nomad.version}", "version", f">= 0.{i}.0")
            for i in range(5)]
    spec = spec_with(cons)
    ct = encoded(nodes, spec)
    st = encode.encode_specs([spec], ct, nodes)
    want = np.logical_and.reduce([per_node(nodes, c) for c in cons])
    np.testing.assert_array_equal(st.precomp[0, :len(nodes)], want)
    assert len(ct._host_rows.rows) == 3


def reg_eval(job):
    return s.Evaluation(
        id=s.generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
        status=s.EVAL_STATUS_PENDING)


def versioned_job():
    job = mock.job()
    job.task_groups[0].count = 2
    for t in job.task_groups[0].tasks:
        t.resources.networks = []
    job.constraints.append(s.Constraint(
        ltarget="${attr.nomad.version}", operand="version",
        rtarget=">= 0.5.0"))
    return job


def schedule(h, job):
    h.state.upsert_job(h.next_index(), job)
    sched = TPUBatchScheduler(h.logger, h.snapshot(), h)
    return sched.schedule_batch([reg_eval(job)])


@pytest.mark.parametrize("change", ["usage_alone", "a_node_registers",
                                    "a_node_changes_an_attribute"])
def test_a_kept_row_lives_as_long_as_the_node_tables_index(change):
    """The row is kept with the static tensors in ``_CLUSTER_CACHE``,
    whose key holds the node table's index."""
    resident.reset_counters()
    h = Harness()
    nodes = fleet(12)
    for node in nodes:
        h.state.upsert_node(h.next_index(), node)
    first = schedule(h, versioned_job())
    assert (first.precomp_rows, first.constraint_row_reuse) == (1, 0)

    if change == "a_node_registers":
        extra = make_node(12)           # (12 // 3) % 3: version 0.5.6
        extra.compute_class()
        h.state.upsert_node(h.next_index(), extra)
        nodes.append(extra)
    elif change == "a_node_changes_an_attribute":
        moved = h.state.node_by_id(None, nodes[0].id).copy()
        assert moved.attributes["nomad.version"] == "0.4.1"
        moved.attributes["nomad.version"] = "0.6.0"
        moved.compute_class()
        h.state.upsert_node(h.next_index(), moved)
        nodes[0] = moved

    job = versioned_job()
    second = schedule(h, job)
    assert second.precomp_rows == 1
    assert second.constraint_row_reuse == (1 if change == "usage_alone"
                                           else 0)
    # Whatever was served, it is the per-node answer on today's fleet.
    key = ("${attr.nomad.version}", "version", ">= 0.5.0")
    base = next(b for b in reversed(list(
        batch_sched._CLUSTER_CACHE._d.values())) if key in b._host_rows.rows)
    by_id = {n.id: n for n in nodes}
    want = per_node([by_id[nid] for nid in base.node_ids], key)
    np.testing.assert_array_equal(base._host_rows.rows[key], want)
    placed = {a.node_id for a in h.state.allocs_by_job(None, job.id, True)}
    assert len(placed) == 2
    assert all(by_id[nid].attributes["nomad.version"] != "0.4.1"
               for nid in placed)
    resident.reset_counters()


# -- (b) the resident mirror's fold -------------------------------------------

def per_tuple_fold(entries, node_index, used):
    """The fold the arrays replaced: one Python iteration per usage
    row.  Returns (touched, rows handed to the device)."""
    touched, dev_rows = set(), []
    for entry in entries:
        if len(entry) == 3:
            pairs = [(entry[1], entry[2])]
        else:
            vec = s.alloc_usage_vec(entry[1].proto)
            pairs = [(nid, vec) for nid in entry[1].node_ids]
        for nid, vec in pairs:
            i = node_index.get(nid)
            if i is None:
                continue
            for d in range(4):
                used[i, d] += vec[d]
            touched.add(i)
            dev_rows.append((i, tuple(vec)))
    return touched, dev_rows


def slab_on(job, node_ids, cpu, mem):
    proto = s.Allocation(job_id=job.id, job=job, task_group="web",
                         resources=s.Resources(cpu=cpu, memory_mb=mem))
    k = len(node_ids)
    return s.AllocSlab(proto=proto, ids=s.generate_uuids(k),
                       names=[f"a[{i}]" for i in range(k)],
                       node_ids=list(node_ids))


def write_feed(h, job, rng, fleet_ids, mix):
    """Alloc writes of the kind ``mix`` names, through the store."""
    st = h.state
    if mix in ("thousand_row_slabs", "mixed"):
        st.upsert_slabs(h.next_index(), [
            slab_on(job, rng.choices(fleet_ids, k=1000), 20 + j, 15)
            for j in range(3)])
    if mix in ("ten_row_slabs", "mixed"):
        for j in range(2):
            # Eight nodes of the mirror's fleet and two it does not hold.
            st.upsert_slabs(h.next_index(), [
                slab_on(job, rng.sample(fleet_ids[:-3], 8) + fleet_ids[-2:],
                        500, 256 + j)])
    if mix in ("single_row_transitions", "mixed"):
        a, b = fleet_ids[0], fleet_ids[1]
        alloc = s.Allocation(id=s.generate_uuid(), job_id=job.id, job=job,
                             node_id=a, task_group="web",
                             resources=s.Resources(cpu=100, memory_mb=200))
        st.upsert_allocs(h.next_index(), [alloc])
        resized = s._fast_copy(alloc)
        resized.resources = s.Resources(cpu=130, memory_mb=260)
        st.upsert_allocs(h.next_index(), [resized])
        moved = s._fast_copy(resized)
        moved.node_id = b
        st.upsert_allocs(h.next_index(), [moved])
        done = s._fast_copy(moved)
        done.client_status = s.ALLOC_CLIENT_STATUS_COMPLETE
        st.update_allocs_from_client(h.next_index(), [done])
        keeper = s.Allocation(id=s.generate_uuid(), job_id=job.id, job=job,
                              node_id=fleet_ids[2], task_group="web",
                              resources=s.Resources(cpu=7, memory_mb=9))
        stray = s._fast_copy(keeper)
        stray.id, stray.node_id = s.generate_uuid(), fleet_ids[-1]
        st.upsert_allocs(h.next_index(), [keeper, stray])


@pytest.fixture
def mirror(monkeypatch):
    """A store of 40 nodes of which the mirror's fleet holds 37, the
    resident slot installed cold with its device twin, and what the
    device apply is handed captured.  The guard is off: a guard run
    compacts ``touched`` to what the walk finds."""
    monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", "0")
    resident.reset_counters()
    h = Harness()
    nodes = []
    for i in range(40):
        node = make_node(i)
        node.compute_class()
        h.state.upsert_node(h.next_index(), node)
        nodes.append(node)
    job = mock.job()
    h.state.upsert_job(h.next_index(), job)
    base = encode.encode_cluster_static(nodes[:37], [])
    encode.finalize_codebooks(base, {})
    key = (h.state.store_uid, h.state.table_index("nodes"), base.n_pad)

    def acquire():
        snap = h.snapshot()
        return resident.acquire(
            snap, key, base,
            lambda: TPUBatchScheduler(h.logger, snap,
                                      h)._live_allocs_by_node())

    _, _, info = acquire()
    assert info["full_reencode"]
    import jax

    handed = []
    resident._STATE.used_dev = jax.device_put(
        resident._STATE.used.astype(np.int32))
    apply = resident._apply_device_deltas

    def capture(dev, rows, vals, mesh=None):
        handed.append((np.array(rows), np.array(vals)))
        return apply(dev, rows, vals, mesh=mesh)

    monkeypatch.setattr(resident, "_apply_device_deltas", capture)
    yield h, job, base, [n.id for n in nodes], acquire, handed
    resident.reset_counters()


@pytest.mark.parametrize("mix", ["thousand_row_slabs", "ten_row_slabs",
                                 "single_row_transitions", "mixed"])
def test_array_fold_equals_the_per_tuple_fold(mirror, mix, monkeypatch):
    h, job, base, store_ids, acquire, handed = mirror
    rng = random.Random(30)
    st = resident._STATE
    cursor = st.alloc_index
    want_used = st.used.copy()
    # The last three of ``store_ids`` the mirror's fleet does not hold.
    write_feed(h, job, rng, store_ids, mix)
    entries = h.state.alloc_log_since(cursor)
    assert entries
    want_touched, want_dev = per_tuple_fold(
        entries, base._node_index, want_used)
    want_touched |= st.touched

    used, touched, info = acquire()
    assert info["resident_hit"] and not info["guard_ran"]
    np.testing.assert_array_equal(st.used, want_used)
    np.testing.assert_array_equal(used, want_used)
    assert st.touched == want_touched and touched == sorted(want_touched)
    assert info["delta_rows"] == st.delta_rows == len(want_dev)
    (rows, vals), = handed
    assert rows.tolist() == [i for i, _ in want_dev]
    assert vals.tolist() == [list(v) for _, v in want_dev]
    # Writes on nodes the fleet does not hold were dropped.
    assert len(want_dev) < sum(
        len(e[1].node_ids) if len(e) == 2 else 1 for e in entries)
    # The independent references agree: the object walk with the host
    # mirror, the host mirror with its device twin.
    monkeypatch.setenv("NOMAD_TPU_RESIDENT_GUARD_EVERY", "1")
    _, _, info = acquire()
    assert info["guard_ran"] and not info["guard_mismatch"]
    assert resident.DEV_GUARD_MISMATCHES == 0 and st.used_dev is not None


def per_tuple_route(dev_rows, shards, n_local, dims=4):
    """``route_shard_deltas`` as it was: a list per shard."""
    per_rows = [[] for _ in range(shards)]
    per_vals = [[] for _ in range(shards)]
    for i, vec in dev_rows:
        s_i = i // n_local
        if 0 <= s_i < shards:
            per_rows[s_i].append(i - s_i * n_local)
            per_vals[s_i].append(vec)
    k_b = encode.pow2_bucket(max(1, max(len(r) for r in per_rows)))
    rows = np.full((shards, k_b), -1, dtype=np.int32)
    vals = np.zeros((shards, k_b, dims), dtype=np.int32)
    for s_i in range(shards):
        k = len(per_rows[s_i])
        if k:
            rows[s_i, :k] = per_rows[s_i]
            vals[s_i, :k] = per_vals[s_i]
    return rows, vals


@pytest.mark.parametrize("mix", ["thousand_row_slabs", "ten_row_slabs",
                                 "single_row_transitions", "mixed"])
def test_shard_routing_equals_the_per_tuple_routing(mirror, mix):
    """The same feed through the two-shard mesh route, down to the
    donated per-shard scatter-add on two (forced host) devices."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nomad_tpu.parallel import sharded as shmod

    h, job, base, store_ids, _, _ = mirror
    cursor = resident._STATE.alloc_index
    write_feed(h, job, random.Random(31), store_ids, mix)
    entries = h.state.alloc_log_since(cursor)
    want_used = np.zeros((base.n_pad, 4), dtype=np.int64)
    _, dev_rows = per_tuple_fold(entries, base._node_index, want_used)
    rows, vals = resident._feed_rows(entries, base._node_index)

    n_local = base.n_pad // 2
    got = encode.route_shard_deltas(rows, vals, 2, n_local)
    want = per_tuple_route(dev_rows, 2, n_local)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)

    mesh = shmod.make_node_mesh(jax.devices()[:2])
    dev = jax.device_put(np.zeros((base.n_pad, 4), dtype=np.int32),
                         NamedSharding(mesh, P(shmod.NODE_AXIS)))
    dev = resident._apply_device_deltas(dev, rows, vals, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(dev), want_used)


def test_an_empty_feed_folds_nothing(mirror):
    h, job, base, store_ids, acquire, handed = mirror
    st = resident._STATE
    before = st.used.copy()
    # An index bump with no alloc write (a job registers).
    _, _, info = acquire()
    assert info["resident_hit"] and info["delta_rows"] == 0
    np.testing.assert_array_equal(st.used, before)
    rows, vals = resident._feed_rows([], base._node_index)
    assert rows.shape == (0,) and vals.shape == (0, 4)
    got_rows, got_vals = encode.route_shard_deltas(rows, vals, 2, 64)
    assert got_rows.shape == (2, 8) and (got_rows == -1).all()
    assert not got_vals.any()
