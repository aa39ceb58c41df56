"""A slab's node column as the index array the device returned.

``structs.NodeColumn`` is rows ``idx`` of one encoded fleet's
``NodeTable``: a sequence of node-id strings to whatever reads it as
one, while the readers on the commit path take what they want from the
integers: the struct codec the entry's bytes (``codec.native.pack_column``:
one gather of pre-packed ids), ``columnar.gather_index`` the mirror rows
(``table.rows_in(index)[idx]``).  Nothing that is written or decided may
change, so the reference throughout is the same slab with the plain list
of its strings: equal bytes, equal rows, equal verdicts, equal stores.
The guards' references read the strings, and have to catch a planted
fault in what the integer routes stand on."""
from __future__ import annotations

import copy
import json
import random
import uuid

import numpy as np
import pytest

import conftest

from nomad_tpu import codec, mock
from nomad_tpu.api.codec import to_wire
from nomad_tpu.codec import native
from nomad_tpu.ops import breaker, resident
from nomad_tpu.server import raft as raft_mod
from nomad_tpu.server.fsm import FSM, MessageType
from nomad_tpu.server.raft import FileLog
from nomad_tpu.state import StateStore, columnar
from nomad_tpu.structs import structs as s

import test_plan_fit_routes as routes
import test_plan_group as group

ROWS_INDEXED = "nomad.plan.fit.rows_indexed"


def _uuid_fleet(n):
    return [str(uuid.UUID(int=(i + 1) * 0x9E3779B97F4A7C15F39CC0605CEDC834
                          % (1 << 128))) for i in range(n)]


FLEETS = {
    "uuid": _uuid_fleet(40),                        # one width: 1 + 36
    "node-5d": [f"node-{i:05d}" for i in range(40)],  # one width: 1 + 10
    # mixed widths: a two-byte length prefix and non-ASCII ids too
    "mixed": (["n1", "node-22", "x" * 200, "ü-node", "rack-7/ü"]
              + [f"host-{i}" for i in range(35)]),
}


def _proto(job, ev_id="ev-col"):
    proto = routes._alloc(job, "", 100, 10)
    proto.id, proto.name, proto.eval_id = "", "", ev_id
    return proto


def _slab_pair(job, table, idx):
    """The same slab twice: its node column as integers, and as the
    list of the strings they stand for."""
    idx = np.asarray(idx, dtype=np.int32)
    k = len(idx)
    ids, names = s.LazyUuids(k, "0" * 24), s.LazyNames(k, f"{job.id}.web")
    col = s.NodeColumn(table, idx)
    return (s.AllocSlab(proto=_proto(job), ids=ids, names=names, node_ids=col),
            s.AllocSlab(proto=_proto(job), ids=ids, names=names,
                        node_ids=[table.ids[i] for i in idx.tolist()]))


def columnize(plan_or_slabs, table):
    """Every list node column of the plan's slabs becomes the indexed
    column of the same strings over ``table``."""
    slabs = getattr(plan_or_slabs, "alloc_slabs", plan_or_slabs)
    where = {nid: i for i, nid in enumerate(table.ids.tolist())}
    for slab in slabs:
        slab.node_ids = s.NodeColumn(table, np.array(
            [where[nid] for nid in slab.node_ids], dtype=np.int32))
    return plan_or_slabs


def world_table(w, seed=0, extra=("node-ghost", "node-late")):
    """The world's fleet in another order than the store's row index,
    with the ids some case names that the store does not hold."""
    ids = list(w.ids) + list(extra)
    random.Random(seed).shuffle(ids)
    return s.NodeTable(ids)


# -- (a) the bytes ------------------------------------------------------------


@pytest.mark.parametrize("rows", [0, 1, 33])
@pytest.mark.parametrize("fleet", sorted(FLEETS))
class TestBytes:
    def _pair(self, fleet, rows):
        table = s.NodeTable(FLEETS[fleet])
        idx = random.Random(rows).choices(range(len(table.ids)), k=rows)
        if fleet == "mixed" and rows:
            idx[0] = 2      # the 200-byte id: a two-byte varint
        return _slab_pair(mock.job(), table, idx)

    def test_plan_entry_is_byte_identical_and_decodes_to_a_list(
            self, fleet, rows):
        col, ref = self._pair(fleet, rows)
        job = mock.job()
        blobs = [raft_mod._encode_entry(
            7, MessageType.APPLY_PLAN_RESULTS,
            {"job": job, "allocs": [], "eval_id": "ev", "slabs": [slab]})
            for slab in (col, ref)]
        assert blobs[0] == blobs[1] and codec.is_frame(blobs[0])
        back = raft_mod._decode_entry(blobs[0])[2]["slabs"][0].node_ids
        assert type(back) is list and back == list(ref.node_ids)
        if rows:
            assert native.COLUMN_PACKS > 0

    def test_msgpack_entry_is_byte_identical(self, fleet, rows, monkeypatch):
        """The kill switch's tagged-msgpack tree (``NOMAD_TPU_CODEC=0``)
        materializes the column like the other lazy columns."""
        col, ref = self._pair(fleet, rows)
        monkeypatch.setenv("NOMAD_TPU_CODEC", "0")
        codec.reset()       # the switch is read once
        try:
            assert not codec.enabled()
            blobs = [raft_mod._encode_entry(
                7, MessageType.APPLY_PLAN_RESULTS, {"slabs": [slab]})
                for slab in (col, ref)]
        finally:
            monkeypatch.delenv("NOMAD_TPU_CODEC")
            codec.reset()
        assert blobs[0] == blobs[1] and not codec.is_frame(blobs[0])
        back = raft_mod._decode_entry(blobs[0])[2]["slabs"][0].node_ids
        assert type(back) is list and back == list(ref.node_ids)

    def test_snapshot_is_byte_identical_and_restores_a_list(
            self, fleet, rows):
        col, ref = self._pair(fleet, rows)
        store = StateStore()
        store.upsert_slabs(5, [col])
        first = store.persist()
        col.node_ids = list(col.node_ids)
        assert store.persist() == first
        if rows:
            back = StateStore.restore(first)._pending_slabs[0].node_ids
            assert type(back) is list and back == list(ref.node_ids)

    def test_to_wire_tree_is_equal(self, fleet, rows):
        col, ref = self._pair(fleet, rows)
        assert to_wire(col) == to_wire(ref)
        assert type(to_wire(col).get("NodeIDs", [])) is list    # omitted empty
        assert json.dumps(to_wire(col)) == json.dumps(to_wire(ref))


def test_the_packed_table_is_one_array_for_ids_of_one_width():
    for fleet, ids in FLEETS.items():
        table = s.NodeTable(ids)
        native.pack_column(s.NodeColumn(table, np.arange(3, dtype=np.int32)))
        one_width = len({len(i.encode()) for i in ids}) == 1
        assert (type(table.packed) is not list) == one_width, fleet


# -- (b) the rows -------------------------------------------------------------


def test_gather_index_equals_the_string_route_and_recomputes_a_gap():
    ids = FLEETS["node-5d"]
    table = s.NodeTable(ids)
    rng = random.Random(3)
    col = s.NodeColumn(table, np.array(rng.choices(range(40), k=200),
                                       dtype=np.int32))
    # an index in another order than the table, without one table node
    order = [nid for nid in ids if nid != ids[7]]
    rng.shuffle(order)
    index = {nid: row for row, nid in enumerate(order)}
    got = columnar.gather_index(index, col)
    assert got.dtype == np.int64
    assert got.tolist() == columnar.gather_index(index, list(col)).tolist()
    assert (got == -1).sum() == list(col).count(ids[7]) > 0
    kept = table.rows_in(index)
    assert table.rows_in(index) is kept         # same size: the kept answer
    # the index gains the node: the answer with a -1 is computed again
    index[ids[7]] = len(index)
    again = columnar.gather_index(index, col)
    assert again.min() >= 0
    assert again.tolist() == columnar.gather_index(index, list(col)).tolist()
    whole = table.rows_in(index)
    assert whole is not kept
    # complete: kept for good, whatever the (append-only) index gains
    index["node-new"] = len(index)
    assert table.rows_in(index) is whole
    # another index object gets its own answer; a few are kept
    for k in range(s.NodeTable.PERMS_KEPT + 2):
        other = {nid: row + k for row, nid in enumerate(ids)}
        assert columnar.gather_index(other, col).tolist() == [
            other[nid] for nid in col]
    assert len(table._perms) == s.NodeTable.PERMS_KEPT


def test_gather_index_keeps_the_string_route_for_lists():
    index = {"a": 0, "b": 1}
    assert columnar.gather_index(index, ["b", "zz", "a"]).tolist() == [1, -1, 0]
    assert columnar.gather_index(index, []).tolist() == []


# -- (c) a sequence of strings ------------------------------------------------


def test_sequence_semantics():
    ids = FLEETS["mixed"]
    table = s.NodeTable(ids)
    idx = np.array([4, 0, 0, 2, 39, 4], dtype=np.int32)
    col = s.NodeColumn(table, idx)
    want = [ids[i] for i in idx.tolist()]
    assert len(col) == 6 and bool(col)
    assert [col[i] for i in range(6)] == want
    assert col[-1] == want[-1] and col[-6] == want[0]
    assert all(type(x) is str for x in col)
    with pytest.raises(IndexError):
        col[6]
    assert list(col) == want == [x for x in col]
    assert ids[2] in col and ids[1] not in col
    assert col == want and want == col and col != want[:-1]
    assert col == s.NodeColumn(table, idx.copy())
    part = col[1:4]
    assert type(part) is s.NodeColumn and part.table is table
    assert list(part) == want[1:4] and len(col[:0]) == 0 and not col[:0]
    assert type(col[:]) is s.NodeColumn and list(col[::2]) == want[::2]
    assert col.__lazy_strs__
    # item reads before and after the strings are made and kept
    fresh = s.NodeColumn(table, idx)
    assert [fresh[i] for i in (0, 3, -1)] == [want[0], want[3], want[-1]]
    kept = fresh.strings()
    assert kept == want and fresh.strings() is kept
    assert [fresh[i] for i in (0, 3, -1)] == [want[0], want[3], want[-1]]
    assert fresh[2:].strings() == want[2:]
    assert set(col) == set(want) and dict.fromkeys(col) == dict.fromkeys(want)


def test_slab_methods_read_the_column_as_strings():
    job = mock.job()
    table = s.NodeTable(FLEETS["node-5d"])
    col, ref = _slab_pair(job, table, [3, 3, 9, 1, 3, 9])
    assert col == ref and len(col) == 6
    assert col.node_counts() == ref.node_counts() == {
        "node-00003": 3, "node-00009": 2, "node-00001": 1}
    keep = {"node-00009", "node-00001"}
    assert col.filter_nodes(keep) == ref.filter_nodes(keep)
    assert col.filter_nodes(keep).node_ids == ["node-00009", "node-00001",
                                               "node-00009"]
    for i in range(6):
        a, b = col.materialize(i), ref.materialize(i)
        assert (a.id, a.name, a.node_id) == (b.id, b.name, b.node_id)
        assert type(a.node_id) is str
    assert [a.node_id for a in col.allocs()] == list(ref.node_ids)


# -- (d) the applier decides and commits the same -----------------------------


@pytest.mark.parametrize("seed", group.SEEDS)
@pytest.mark.parametrize("guard", ["0", "1"])
@pytest.mark.parametrize("case", sorted(group.CASES))
def test_group_results_equal_those_of_the_lists(case, guard, seed,
                                                monkeypatch):
    """test_plan_group's submissions, their slabs' node columns as
    integers against the same with lists: per-plan results, indexes,
    store contents and counters equal."""
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", guard)
    mismatches = columnar.USAGE_GUARD_MISMATCHES
    ref, got = group._world(case, seed), group._world(case, seed)
    ref_plans, got_plans = group.CASES[case](ref), group.CASES[case](got)
    table = world_table(got, seed)
    for plan in got_plans:
        columnize(plan, table)
    want = [group.shape(r) for r in group.as_one_group(ref, ref_plans)]
    results = group.as_one_group(got, got_plans)
    assert [group.shape(r) for r in results] == want
    assert group.usage_by_node(got) == group.usage_by_node(ref)
    assert got.applier.raft.applied_index() == ref.applier.raft.applied_index()
    assert columnar.USAGE_GUARD_MISMATCHES == mismatches
    seen, base = group.totals(got), group.totals(ref)
    assert {k: seen[k] for k in (group.SUBMITTED, group.ROWS_ARRAY,
                                 group.ROWS_SCALAR, group.EVALUATE,
                                 group.APPLY)} == {
        k: base[k] for k in (group.SUBMITTED, group.ROWS_ARRAY,
                             group.ROWS_SCALAR, group.EVALUATE, group.APPLY)}
    # the mirrors fold the committed columns to the same usage
    cols_got, cols_ref = got.store.columns(), ref.store.columns()
    assert np.array_equal(got.store.column_usage(cols_got)[:cols_got.n],
                          ref.store.column_usage(cols_ref)[:cols_ref.n])
    indexed = got.sink.latest()["CounterTotals"].get(ROWS_INDEXED, 0)
    assert ref.sink.latest()["CounterTotals"].get(ROWS_INDEXED, 0) == 0
    if case in ("wide_all_fit",):
        assert indexed == sum(len(slab.node_ids) for plan in got_plans
                              for slab in plan.alloc_slabs)
    elif not case.startswith("wide"):
        assert indexed == 0     # under ARRAY_MIN_ROWS: the per-node route


@pytest.mark.parametrize("seed", routes.SEEDS)
@pytest.mark.parametrize("min_rows", [1, columnar.ARRAY_MIN_ROWS])
@pytest.mark.parametrize("case", sorted(routes.CASES))
def test_routes_agree_with_the_walk_on_columns(case, min_rows, seed,
                                               monkeypatch):
    """test_plan_fit_routes' plans with their slabs' node columns (and
    an in-flight sibling's) as integers: the result is the one built
    from the walk's verdicts, guard off and guard at every plan, and the
    counter says which rows came from integers."""
    monkeypatch.setattr(columnar, "ARRAY_MIN_ROWS", min_rows)
    w = routes.World(seed, n_nodes=routes.WIDE.get(case, 10),
                     networks=case in routes.NETWORKED)
    plan, expect = routes.CASES[case](w)
    table = world_table(w, seed)
    columnize(plan, table)
    for pending in w.applier._overlay.snapshot():
        columnize(pending.result.alloc_slabs, table)
        pending.parts[:len(pending.result.alloc_slabs)] = [
            (slab.node_ids, vec) for slab, (_, vec) in
            zip(pending.result.alloc_slabs, pending.parts)]
    mismatches = columnar.USAGE_GUARD_MISMATCHES

    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "0")
    got = w.applier.evaluate_plan(w.snap, plan)
    fits = routes.walk_verdicts(w, plan)
    want = routes.result_from_verdicts(w.snap, plan, fits)
    assert got == want
    if expect["partial"] is not None:
        assert bool(got.refresh_index) == expect["partial"]
    n_array, _ = w.counters()
    indexed = w.sink.latest()["CounterTotals"].get(ROWS_INDEXED, 0)
    if n_array:
        assert indexed == sum(
            len(slab.node_ids) for slab in plan.alloc_slabs
            if not slab.proto.resources.networks)
    else:
        assert indexed == 0

    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "1")
    assert w.applier.evaluate_plan(w.snap, plan) == want
    assert columnar.USAGE_GUARD_MISMATCHES == mismatches


# -- (e) the guards guard no less ---------------------------------------------


def test_plan_fit_guard_catches_a_wrong_permutation(monkeypatch):
    """The kept permutation sends an overfilled node's rows to a node
    with room: the array route waves the plan through, the guard's
    reference reads the column's strings, disagrees, and the walk's
    verdicts win."""
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "1")
    n = columnar.ARRAY_MIN_ROWS + 6

    def overfilling(w):
        """One row on every node and the fullest node filled past its
        last MHz; ``spare`` could take those rows as well."""
        free = {nid: w.free_cpu(nid) for nid in w.ids}
        full, spare = min(free, key=free.get), max(free, key=free.get)
        assert free[spare] >= 500 * (free[full] // 500 + 2)
        plan = w.plan()
        plan.append_slab(routes._slab(
            w.job, w.ids + [full] * (w.free_cpu(full) // 500 + 1)))
        table = world_table(w, 1, extra=())
        columnize(plan, table)
        # plant: the two nodes' mirror rows change places
        row_of = w.store.columns().row_of
        perm = table.rows_in(row_of).copy()
        where = table.ids.tolist()
        a, b = where.index(full), where.index(spare)
        perm[a], perm[b] = perm[b], perm[a]
        table._perms = ((row_of, perm, True, len(row_of)),)
        return plan, full

    w = routes.World(7, n_nodes=n)
    plan, full = overfilling(w)
    fits = dict.fromkeys(w.applier._touched(plan), True)
    fits[full] = False
    want = routes.result_from_verdicts(w.snap, plan, fits)
    before = columnar.USAGE_GUARD_MISMATCHES
    got = w.applier.evaluate_plan(w.snap, plan)
    assert columnar.USAGE_GUARD_MISMATCHES == before + 1
    assert got == want and got.refresh_index > 0
    assert full not in {nid for sl in got.alloc_slabs for nid in sl.node_ids}

    # Unguarded, the planted permutation lets the overfill through.
    monkeypatch.setenv("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "0")
    bad = routes.World(7, n_nodes=n)
    plan, _ = overfilling(bad)
    assert not bad.applier.evaluate_plan(bad.snap, plan).refresh_index


def test_codec_guard_catches_a_wrong_packed_table():
    """Every guarded call (the suite's cadence is 1) compares the
    gathered bytes with the Python twin over the materialized strings: a
    mismatch is counted, the twin's bytes are written, the route is off
    for the process and the breaker is fed."""
    native.reset_counters()
    try:
        table = s.NodeTable(FLEETS["node-5d"])
        col, ref = _slab_pair(mock.job(), table, [5, 6, 7, 5])
        good = raft_mod._encode_entry(1, MessageType.APPLY_PLAN_RESULTS,
                                      {"slabs": [ref]})
        assert raft_mod._encode_entry(1, MessageType.APPLY_PLAN_RESULTS,
                                      {"slabs": [col]}) == good
        assert native.GUARD_MISMATCHES == 0 and table.packed is not None
        table.packed = np.roll(table.packed, 1)     # every id the next one's
        runs = native.GUARD_RUNS
        checks = list(breaker.BREAKER._checks)
        blob = raft_mod._encode_entry(1, MessageType.APPLY_PLAN_RESULTS,
                                      {"slabs": [col]})
        assert blob == good
        assert native.GUARD_RUNS > runs and native.GUARD_MISMATCHES == 1
        assert native._native_disabled
        assert list(breaker.BREAKER._checks) == checks + [False]
        # the route is off: no gather, still the same bytes
        packs = native.COLUMN_PACKS
        assert raft_mod._encode_entry(1, MessageType.APPLY_PLAN_RESULTS,
                                      {"slabs": [col]}) == good
        assert native.COLUMN_PACKS == packs
    finally:
        native.reset_counters()


# -- (f) the mirrors fold the same usage --------------------------------------


def _twin_stores(seed, columns):
    """test_columnar's randomized world (node registrations, per-object
    writes, stops and slabs of 1..150 rows), its slabs' node columns as
    lists or as integers over a table that also names a node no store
    holds."""
    rng = random.Random(seed)
    store = StateStore()
    index = 0
    pool = []
    def node(k):
        n = mock.node()
        n.id = f"node-{k:02d}"
        return n

    for k in range(8):
        index += 1
        pool.append(node(k))
        store.upsert_node(index, pool[-1])
    table_ids = [n.id for n in pool]
    late = [node(8 + k) for k in range(4)]
    table_ids += [n.id for n in late] + ["node-nobody-registers"]
    rng.shuffle(table_ids)
    table = s.NodeTable(table_ids)
    where = {nid: i for i, nid in enumerate(table_ids)}
    live = []
    for step in range(60):
        index += 1
        op = rng.randrange(5)
        if op == 0 and late:
            pool.append(late.pop())
            store.upsert_node(index, pool[-1])
        elif op == 1:
            al = mock.alloc()
            al.id = f"alloc-{step}"
            al.node_id = rng.choice(pool).id
            al.resources = s.Resources(cpu=rng.randrange(1, 200),
                                       memory_mb=rng.randrange(64))
            store.upsert_allocs(index, [al])
            live.append(al.id)
        elif op == 2 and live:
            stop = store.alloc_by_id(None, live.pop(
                rng.randrange(len(live)))).copy()
            stop.desired_status = s.ALLOC_DESIRED_STATUS_STOP
            store.upsert_allocs(index, [stop])
        else:
            proto = mock.alloc()
            proto.resources = s.Resources(cpu=3, memory_mb=2, disk_mb=1)
            cnt = rng.choice([1, 9, 63, 64, 150])
            # some placements on nodes the store does not hold yet
            names = [rng.choice(table_ids) for _ in range(cnt)]
            node_ids = (s.NodeColumn(table, np.array(
                [where[nid] for nid in names], dtype=np.int32))
                if columns else names)
            store.upsert_slabs(index, [s.AllocSlab(
                proto=proto, ids=s.LazyUuids(cnt, f"{step:024d}"),
                names=s.LazyNames(cnt, "j.tg"), node_ids=node_ids,
                prev_ids=[])])
        if step % 9 == 0:       # fold now: later slabs meet a grown index
            store.column_usage(store.columns())
    return store


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_usage_mirror_and_resident_feed_fold_columns_as_lists(seed):
    ref, got = _twin_stores(seed, False), _twin_stores(seed, True)
    rc, gc = ref.columns(), got.columns()
    assert rc.node_ids[:rc.n] == gc.node_ids[:gc.n]
    assert np.array_equal(ref.column_usage(rc)[:rc.n],
                          got.column_usage(gc)[:gc.n])
    # ... and it is the usage of the store's own rows
    want = np.zeros((gc.n, 4), dtype=np.int64)
    for nid, row in got.alloc_rows(None):
        if not row.terminal_status() and nid in gc.row_of:
            want[gc.row_of[nid]] += s.alloc_usage_vec(row)
    assert np.array_equal(got.column_usage(gc)[:gc.n], want)
    # the resident mirror's feed: one usage row per allocation write
    node_index = {nid: i for i, nid in enumerate(reversed(gc.node_ids[2:gc.n]))}
    for since in (0, 20, 45):
        r_rows, r_vals = resident._feed_rows(
            ref.alloc_log_since(since), node_index)
        g_rows, g_vals = resident._feed_rows(
            got.alloc_log_since(since), node_index)
        assert r_rows.dtype == g_rows.dtype == np.int64
        assert np.array_equal(r_rows, g_rows) and len(g_rows)
        assert np.array_equal(r_vals, g_vals)
    assert resident._feed_rows([], node_index)[0].shape == (0,)


def test_a_groups_wal_is_byte_identical_and_replays_to_an_equal_store(
        tmp_path):
    """A bulk-shaped group (one 1,000-row slab a plan) written by
    ``apply_many`` under one fsync: the log written from indexed
    columns is, byte for byte, the log written from the lists, and
    replays to the same rows."""
    a = group.Durable(tmp_path / "a")
    b = group.Durable(tmp_path / "b", payloads=a.setup)
    job = a.store.job_by_id(None, a.job.id)
    ids = [node.id for node in a.nodes]
    rng = random.Random(5)
    plans = []
    for _ in range(8):
        plan = s.Plan(eval_id=s.generate_uuid(), job=job)
        plan.append_slab(routes._slab(job, rng.choices(ids, k=1000), 1, 1,
                                      plan.eval_id))
        plans.append(plan)
    entries = a.entries(plans)
    as_columns = copy.deepcopy(entries)     # before any FSM apply stamps it
    table = s.NodeTable(list(reversed(ids)))
    for _, payload in as_columns:
        columnize(payload["slabs"], table)
    a.log.apply_many(entries)
    packs = native.COLUMN_PACKS
    b.log.apply_many(as_columns)
    assert native.COLUMN_PACKS == packs + len(plans)
    want = group.placed_by_eval(a.store, plans)
    assert group.placed_by_eval(b.store, plans) == want
    a.log.close()
    b.log.close()
    files = group.wal_bytes(tmp_path / "a")
    assert files and any(files.values())
    assert files == group.wal_bytes(tmp_path / "b")
    again = FileLog(FSM(), str(tmp_path / "b"), snapshot_entries=0,
                    snapshot_bytes=0)
    try:
        assert group.placed_by_eval(again.fsm.state, plans) == want
    finally:
        again.close()


# -- (g) the served path ------------------------------------------------------


def test_served_batch_commits_columns_and_the_wal_replays_them(tmp_path):
    """One job of 80 placements through the served device path on the
    CPU backend, durable: the committed slab's node column is the
    device's integers, the fit re-check took every row from them, and a
    replay of the WAL (which decodes plain lists) ends with the same
    allocations on the same nodes."""
    count = 80
    mismatches = (native.GUARD_MISMATCHES, columnar.USAGE_GUARD_MISMATCHES)
    with conftest.served_job(data_dir=tmp_path, count=count,
                             nodes=16) as (agent, job, _eval_id):
        srv = agent.server
        slabs = [e[1] for e in srv.state.alloc_log_since(0) if len(e) == 2]
        assert slabs and sum(len(slab) for slab in slabs) == count
        for slab in slabs:
            assert type(slab.node_ids) is s.NodeColumn
            assert set(slab.node_ids) <= {n.id for n in srv.state.nodes(None)}
        totals = srv.metrics.sink.latest()["CounterTotals"]
        assert totals[ROWS_INDEXED] == count
        assert totals["nomad.plan.allocs_committed"] == count
        assert (native.GUARD_MISMATCHES,
                columnar.USAGE_GUARD_MISMATCHES) == mismatches

        def rows(state):
            return sorted((a.id, a.name, a.node_id, a.create_index)
                          for a in state.allocs_by_job(None, job.id, True))

        want = rows(srv.state)
        assert len(want) == count
        usage = srv.state.column_usage(srv.state.columns()).copy()
    again = FileLog(FSM(), str(tmp_path))
    try:
        state = again.fsm.state
        for entry in state.alloc_log_since(0):
            if len(entry) == 2:
                assert type(entry[1].node_ids) is list
        assert rows(state) == want
        cols = state.columns()
        assert np.array_equal(state.column_usage(cols)[:cols.n],
                              usage[:cols.n])
    finally:
        again.close()
