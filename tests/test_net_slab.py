"""Port-asking placements as network slabs (``structs.AllocSlab``'s
``ips`` and ``dyn_ports`` columns): the form itself, its codec (layout
version 2, version-1 frames still read), the binary snapshot and a WAL
replay.

The reference for a row is the allocation the batch path built per slot
before network slabs: the node's ``NetworkIndex`` seeded from the state,
``assign_network`` per networked task with the eval's seeded generator,
each task's resources copied with the offer as its network, their sum by
``Resources.add`` from the task group's disk (``per_object_rows``)."""
from __future__ import annotations

import random

import pytest

from nomad_tpu import mock
from nomad_tpu.codec import gen, schema
from nomad_tpu.server.fsm import FSM, MessageType
from nomad_tpu.server.log_codec import decode_payload, encode_payload
from nomad_tpu.server.raft import FileLog
from nomad_tpu.state.state_store import StateStore
from nomad_tpu.structs import structs as s
from nomad_tpu.structs.network import NetworkIndex

from test_net_resident import device_batch, net_job, standing_fleet

# A frame the layout-version-1 build wrote (two per-object allocations
# holding a static and two dynamic ports, and a slab without networks).
GOLDEN_V1 = (
    "c101539ce714e9f7479508030506616c6c6f63730702090307616c6c6f632d3007646566"
    "61756c74066576616c2d310a6a6f622e7765625b305d066e6f64652d30036a6f62000377"
    "65620101e8078004ac020001010465746830000d3139322e3136382e302e313030640101"
    "026c62f28a010201046874747090c802010561646d696e92c80201010000ac0200000103"
    "77656201e8078004000001010465746830000d3139322e3136382e302e31303064010102"
    "6c62f28a010201046874747090c802010561646d696e92c802000372756e000770656e64"
    "696e67000000000000000000000000f83f090307616c6c6f632d310764656661756c7406"
    "6576616c2d310a6a6f622e7765625b315d066e6f64652d31036a6f6200037765620101e8"
    "078004ac020001010465746830000d3139322e3136382e302e313030640101026c62f28a"
    "0102010468747470e0d403010561646d696ee2d40301010000ac020000010377656201e8"
    "078004000001010465746830000d3139322e3136382e302e313030640101026c62f28a01"
    "02010468747470e0d403010561646d696ee2d403000372756e000770656e64696e670000"
    "00000000000000000000f83f0505736c616273070109020101000764656661756c740665"
    "76616c2d310000036a6f6200037765620101281e00000000010377656201281e00000000"
    "0372756e000770656e64696e670000000000000000000000000000000206736c61622d30"
    "06736c61622d310002096a6f622e64625b305d096a6f622e64625b315d0002066e6f6465"
    "2d33066e6f64652d3400000e0e05076576616c5f696405066576616c2d31")

V1_FINGERPRINT = "539ce714e9f74795"


def three_task_job(count):
    """``mock.job()`` with two more tasks: ``sidecar`` asks 10 Mbit on
    the same device, static port 9000 and a dynamic ``probe``; ``log``
    asks no network.  So a row has two offers, and its combined
    resources merge them by device."""
    job = net_job(count)
    tg = job.task_groups[0]
    web = tg.tasks[0]
    sidecar = web.copy()
    sidecar.name = "sidecar"
    sidecar.resources = s.Resources(cpu=50, memory_mb=32, networks=[
        s.NetworkResource(mbits=10, reserved_ports=[s.Port("admin2", 9000)],
                          dynamic_ports=[s.Port("probe", 0)])])
    log = web.copy()
    log.name = "log"
    log.resources = s.Resources(cpu=20, memory_mb=16)
    tg.tasks = [web, sidecar, log]
    return job


def per_object_rows(state, eval_id, tg, node_ids):
    """What the per-object branch built for these slots, in order."""
    rng = random.Random(eval_id)
    indexes = {}
    out = []
    for node_id in node_ids:
        idx = indexes.get(node_id)
        if idx is None:
            idx = indexes[node_id] = NetworkIndex()
            idx.set_node(state.node_by_id(None, node_id))
            idx.add_allocs([a for a in state.allocs_by_node(None, node_id)
                            if not a.terminal_status()])
        task_resources = {}
        total = s.Resources(disk_mb=tg.ephemeral_disk.size_mb)
        for t in tg.tasks:
            res = t.resources.copy()
            if t.resources.networks:
                offer, err = idx.assign_network(t.resources.networks[0], rng)
                assert offer is not None, err
                idx.add_reserved(offer)
                res.networks = [offer]
            task_resources[t.name] = res
            total.add(res)
        out.append((task_resources, total))
    return out


def offers_for(job, node_ids, seed=0):
    """Offers per row for ``job``'s networked tasks, each row's picked on
    a fresh ``mock.node()`` (the form's tests; the fit is not theirs)."""
    rng = random.Random(seed)
    tasks = [t for t in job.task_groups[0].tasks if t.resources.networks]
    rows = []
    for _ in node_ids:
        idx = NetworkIndex()
        idx.set_node(mock.node())
        row = []
        for t in tasks:
            offer, err = idx.assign_network(t.resources.networks[0], rng)
            assert offer is not None, err
            idx.add_reserved(offer)
            row.append(offer)
        rows.append(row)
    return rows


def proto_of(job, eval_id="eval-1"):
    tg = job.task_groups[0]
    combined = s.Resources(disk_mb=tg.ephemeral_disk.size_mb)
    for t in tg.tasks:
        combined.add(t.resources)
    return s.Allocation(
        eval_id=eval_id, job_id=job.id, task_group=tg.name,
        resources=combined,
        task_resources={t.name: t.resources.copy() for t in tg.tasks},
        shared_resources=s.Resources(disk_mb=tg.ephemeral_disk.size_mb))


def network_slab(job, node_ids, seed=0, eval_id="eval-1"):
    return s.AllocSlab.of_offers(
        proto_of(job, eval_id), offers_for(job, node_ids, seed),
        ids=s.LazyUuids(len(node_ids)),
        names=s.LazyNames(len(node_ids), f"{job.name}.web"),
        node_ids=list(node_ids), prev_ids=[])


def rows_of(store):
    """Every live row, materialized, by id."""
    return {a.id: a for a in store.allocs(None)}


# -- the form ---------------------------------------------------------------


@pytest.mark.parametrize("seed", [31, 32])
def test_a_rows_materialization_is_the_per_object_allocation(seed):
    """Field by field: each committed row's task resources (its two
    offers: IP, Mbit, static and dynamic ports; the third task's
    resources untouched) and its combined resources, equal to what the
    per-object branch built for the same slot with the same generator,
    against the same state."""
    h, _ = standing_fleet(seed)
    job = three_task_job(12)
    before = h.snapshot()
    stats = device_batch(h, [job])
    assert stats.fused == 1 and stats.oracle_routed == 0
    assert stats.net_offer_failures == 0 and stats.net_slab_rows == 12
    plan = h.plans[-1]
    assert not plan.node_allocation
    (slab,) = plan.alloc_slabs
    assert len(slab) == 12 and len(slab.ips) == 24
    assert len(slab.dyn_ports) == 4 * 12 * 3      # int32 [12, 3]
    want = per_object_rows(before, plan.eval_id, job.task_groups[0],
                           list(slab.node_ids))
    for i, (task_resources, total) in enumerate(want):
        a = slab.materialize(i)
        assert a.node_id == slab.node_ids[i] and a.job_id == job.id
        assert a.name == f"{job.name}.web[{i}]"
        assert list(a.task_resources) == ["web", "sidecar", "log"]
        for name in a.task_resources:
            assert a.task_resources[name] == task_resources[name], name
        assert a.resources == total
        assert a.resources.networks[0].mbits == 60
        assert [p.value for p in a.resources.networks[0].reserved_ports] \
            == [9000]
        # the stored row reads the same
        assert h.state.alloc_by_id(None, a.id).task_resources \
            == task_resources


def test_the_rows_network_reads_agree_with_their_materialization():
    """``row_net_usage``, ``row_ports``, ``row_networks`` and a
    ``SlabRow`` (the feed's, the offers' seed's and the fit re-check's
    readers, no Allocation built) against the materialized rows read by
    ``alloc_net_held``."""
    job = three_task_job(8)
    slab = network_slab(job, [f"node-{i % 3}" for i in range(8)], seed=7)
    rows = slab.allocs()
    want = [s.alloc_net_held(a) for a in rows]
    assert slab.row_net_usage() == [vec for vec, _ in want]
    assert [sorted(p) for p in slab.row_ports()] \
        == [sorted(ports) for _, ports in want]
    for i, a in enumerate(rows):
        row = slab.row(i)
        assert (row.id, row.name, row.node_id, row.create_index) \
            == (a.id, a.name, a.node_id, a.create_index)
        assert row.task_resources == a.task_resources
        assert s.alloc_usage_vec(row) == s.alloc_usage_vec(a)
        assert not row.terminal_status()
    for i, a in enumerate(rows):
        assert slab.row_networks(i) == [
            (nr.ip, nr.device, nr.mbits,
             [p.value for p in nr.reserved_ports + nr.dynamic_ports])
            for nr in (tr.networks[0] for tr in a.task_resources.values()
                       if tr.networks)]


def test_the_feed_reads_a_network_slabs_entry_as_its_rows():
    """The resident feed's network and port deltas of a network slab's
    one entry equal those of its allocations written one by one (a
    single-row entry each, ``alloc_net_held``)."""
    from nomad_tpu.ops import resident

    job = three_task_job(6)
    nodes = [f"node-{i}" for i in range(4)]
    slab = network_slab(job, [nodes[i % 4] for i in range(6)], seed=9)
    index = {nid: i for i, nid in enumerate(nodes)}
    singles = [(7, a.node_id, s.alloc_usage_vec(a), *s.alloc_net_held(a))
               for a in slab.allocs()]
    for feed in (resident._feed_net_rows, resident._feed_port_rows):
        pairs = [sorted(zip(rows.tolist(), map(str, vals.tolist())))
                 for rows, vals in (feed([(7, slab)], index),
                                    feed(singles, index))]
        assert pairs[0] == pairs[1] and pairs[0], feed.__name__


def test_a_partial_cut_keeps_the_columns_aligned():
    job = three_task_job(6)
    nodes = ["a", "b", "c", "a", "b", "c"]
    slab = network_slab(job, nodes, seed=3)
    cut = slab.filter_nodes({"b", "c"})
    assert list(cut.node_ids) == ["b", "c", "b", "c"]
    kept = [1, 2, 4, 5]
    for j, i in enumerate(kept):
        a, b = cut.materialize(j), slab.materialize(i)
        assert (a.id, a.node_id, a.task_resources, a.resources) \
            == (b.id, b.node_id, b.task_resources, b.resources)


# -- the codec --------------------------------------------------------------


def test_a_network_slab_round_trips_the_codec():
    job = three_task_job(5)
    slab = network_slab(job, ["n1", "n2", "n1", "n3", "n2"], seed=5)
    blob = encode_payload({"job": None, "allocs": [], "eval_id": "eval-1",
                           "slabs": [slab]})
    assert blob[:2] == bytes((schema.MAGIC, 2))
    (back,) = decode_payload(blob)["slabs"]
    assert back.proto == slab.proto
    for col in ("ids", "names", "node_ids", "prev_ids", "ips"):
        assert list(getattr(back, col)) == list(getattr(slab, col)), col
    assert back.dyn_ports == slab.dyn_ports
    for i in range(len(slab)):
        assert back.materialize(i) == slab.materialize(i)


def test_the_version_1_layout_keeps_its_fingerprint():
    assert schema.VERSION == 2
    assert schema.FINGERPRINTS[1].hex() == V1_FINGERPRINT
    assert schema.FINGERPRINT == schema.FINGERPRINTS[2] != \
        schema.FINGERPRINTS[1]


def test_a_version_1_frame_of_per_object_network_allocations_decodes():
    blob = bytes.fromhex(GOLDEN_V1)
    assert blob[1] == 1
    payload = decode_payload(blob)
    assert payload["eval_id"] == "eval-1"
    first, second = payload["allocs"]
    for alloc, dyn in ((first, 21000), (second, 30000)):
        (nr,) = alloc.task_resources["web"].networks
        assert (nr.device, nr.ip, nr.mbits) == ("eth0", "192.168.0.100", 50)
        assert [(p.label, p.value) for p in nr.reserved_ports] \
            == [("lb", 8889)]
        assert [(p.label, p.value) for p in nr.dynamic_ports] \
            == [("http", dyn), ("admin", dyn + 1)]
    (slab,) = payload["slabs"]
    assert (slab.ips, slab.dyn_ports) == ([], b"")
    assert list(slab.node_ids) == ["node-3", "node-4"]
    assert slab.materialize(1).node_id == "node-4"


def test_a_slab_without_networks_encodes_as_before_but_the_version():
    """The version-1 frame's payload written again: the same bytes but
    the header (version and fingerprint) and, at the slab's end, its two
    empty columns (an empty string column and an empty blob)."""
    old = bytes.fromhex(GOLDEN_V1)
    new = encode_payload(decode_payload(old))
    body = gen._BODY_START
    assert new[:body] == bytes((schema.MAGIC, 2)) + schema.FINGERPRINTS[2]
    end = old.index(b"\x05\x07eval_id")    # the key after the slab list
    assert new[body:] == old[body:end] + b"\x00\x00\x00" + old[end:]


def test_a_version_2_frame_is_refused_by_a_version_1_decoder(monkeypatch):
    """A build that reads layout 1 alone refuses a version-2 frame by its
    version; a version-2 body under a version-1 header is refused by the
    fingerprint: never misread."""
    job = three_task_job(2)
    blob = encode_payload({"slabs": [network_slab(job, ["n1", "n2"])]})
    relabelled = bytes((schema.MAGIC, 1)) + blob[2:]
    with pytest.raises(gen.CodecError, match="fingerprint"):
        gen.decode_frame(relabelled)
    monkeypatch.setattr(gen, "FINGERPRINTS", {1: schema.FINGERPRINTS[1]})
    with pytest.raises(gen.CodecError, match="unsupported codec version 2"):
        gen.decode_frame(blob)


# -- the snapshot and the log -----------------------------------------------


def store_with_slabs(network=True):
    store = StateStore()
    job = three_task_job(6)
    store.upsert_job(1, job)
    for i in range(4):
        node = mock.node()
        node.id = f"node-{i}"
        store.upsert_node(2 + i, node)
    ids = [f"node-{i % 4}" for i in range(6)]
    if network:
        store.upsert_slabs(10, [network_slab(job, ids, seed=1,
                                             eval_id="ev-1")])
    plain = s.AllocSlab(proto=proto_of(mock.job(), "ev-2"),
                        ids=s.LazyUuids(3), names=s.LazyNames(3, "x.web"),
                        node_ids=ids[:3])
    plain.proto.task_resources = {"web": s.Resources(cpu=10, memory_mb=10)}
    plain.proto.resources = s.Resources(cpu=10, memory_mb=10)
    store.upsert_slabs(11, [plain])
    return store, job


def test_the_binary_snapshot_restores_the_rows():
    store, _ = store_with_slabs()
    # One row updated by its client: the slab persists without it, the
    # row as itself.  (Read off the slab: a read through the store
    # would cache every row back into its table as an Allocation.)
    slab = next(v for v in store.allocs_table.values()
                if type(v) is s.AllocSlab and v.ips)
    done = slab.materialize(2)
    done.client_status = s.ALLOC_CLIENT_STATUS_RUNNING
    store.update_allocs_from_client(12, [done])
    blob = store.persist()
    assert blob[:8] == StateStore.SNAP3_MAGIC
    back = StateStore.restore(blob)
    assert rows_of(back) == rows_of(store)
    for nid in ("node-0", "node-1"):
        assert sorted(map(repr, back.node_networks(nid))) \
            == sorted(map(repr, store.node_networks(nid)))


def test_only_a_snapshot_holding_network_slabs_is_refused_by_an_older_reader(
        monkeypatch):
    """A store without network slabs persists as before (``NTPUSNP2``,
    no column keys), one with them as ``NTPUSNP3``; a reader that knows
    only ``NTPUSNP2`` restores the first and refuses the second."""
    import msgpack

    plain, _ = store_with_slabs(network=False)
    old_form = plain.persist()
    assert old_form[:8] == StateStore.SNAP2_MAGIC
    doc = msgpack.unpackb(old_form[8:], raw=False)
    assert [sorted(sd) for sd in doc["slabs"]] == [sorted(
        ("proto", "job_ref", "ids", "names", "node_ids", "prev_ids", "ci",
         "mi", "dead"))]
    new_form = store_with_slabs()[0].persist()
    assert new_form[:8] == StateStore.SNAP3_MAGIC

    monkeypatch.setattr(StateStore, "SNAP3_MAGIC", b"NTPUSNP?")
    assert rows_of(StateStore.restore(old_form)) == rows_of(plain)
    with pytest.raises(ValueError):
        StateStore.restore(new_form)


def test_the_msgpack_form_tags_a_network_slab_for_older_readers_to_refuse(
        monkeypatch):
    """Under ``NOMAD_TPU_CODEC=0`` a log entry is a tagged msgpack tree:
    a network slab round-trips under a tag of its own, which a decoder
    that predates the columns does not know and refuses, where it would
    drop the columns; a slab without networks keeps its old tag."""
    import msgpack

    from nomad_tpu import codec
    from nomad_tpu.server import log_codec

    job = three_task_job(4)
    net = network_slab(job, ["node-0", "node-1", "node-0"])
    plain = s.AllocSlab(proto=proto_of(mock.job()), ids=["a", "b"],
                        names=["x.web[0]", "x.web[1]"],
                        node_ids=["node-0", "node-1"])
    monkeypatch.setenv("NOMAD_TPU_CODEC", "0")
    codec.reset()       # the switch is read once
    try:
        blob = encode_payload({"slabs": [net, plain]})
        plain_blob = encode_payload({"slabs": [plain]})
    finally:
        monkeypatch.delenv("NOMAD_TPU_CODEC")
        codec.reset()
    assert not codec.is_frame(blob)
    tags = [sd["__t"] for sd in msgpack.unpackb(blob, raw=False)["slabs"]]
    assert tags == ["AllocSlab.net", "AllocSlab"]
    back = decode_payload(blob)["slabs"]
    assert back[0].allocs() == net.allocs()
    assert back[1].allocs() == plain.allocs()

    monkeypatch.delitem(log_codec._TYPES, "AllocSlab.net")
    with pytest.raises(ValueError, match="unknown payload type"):
        decode_payload(blob)
    assert decode_payload(plain_blob)["slabs"][0].allocs() == plain.allocs()


def test_a_snapshot_and_a_wal_replay_on_one_data_dir_restore_the_rows(
        tmp_path):
    """Slabs before a snapshot come back from it, slabs after it from
    the WAL, on the same data_dir: every row as it was."""
    log = FileLog(FSM(), str(tmp_path), snapshot_entries=0,
                  snapshot_bytes=0)
    job = three_task_job(4)
    log.apply(MessageType.JOB_REGISTER, {"job": job})
    nodes = []
    for i in range(3):
        node = mock.node()
        node.id = f"node-{i}"
        nodes.append(node.id)
        log.apply(MessageType.NODE_REGISTER, {"node": node})
    job = log.fsm.state.job_by_id(None, job.id)

    def place(seed, eval_id):
        log.apply(MessageType.APPLY_PLAN_RESULTS, {
            "job": job, "allocs": [], "eval_id": eval_id,
            "slabs": [network_slab(job, nodes + nodes[:1], seed=seed,
                                   eval_id=eval_id)]})

    place(1, "ev-1")
    log.snapshot()
    place(2, "ev-2")
    want = rows_of(log.fsm.state)
    assert len(want) == 8
    log.close()
    again = FileLog(FSM(), str(tmp_path), snapshot_entries=0,
                    snapshot_bytes=0)
    try:
        got = rows_of(again.fsm.state)
        assert got.keys() == want.keys()
        for aid, a in want.items():
            b = got[aid]
            assert (b.node_id, b.task_resources, b.resources) \
                == (a.node_id, a.task_resources, a.resources)
    finally:
        again.close()
