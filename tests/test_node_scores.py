"""A spec's commit-time scores as the two arrays the device returned.

``structs.NodeScores`` is rows ``idx`` of one encoded fleet's
``NodeTable`` and their binpack scores, plus the sparse anti-affinity
part: to whatever reads ``AllocMetric.scores`` it is the dictionary the
batch path used to build (``nid + ".binpack"`` per committed node), and
the struct codec writes that dictionary's bytes from the integers
(``codec.native.pack_scores``: one gather of pre-packed keys).  Nothing
that is written or read may change, so the reference throughout is the
dictionary of the old comprehension: equal mappings, equal bytes, equal
wire trees.  The codec guard's reference reads the strings, and has to
catch a planted fault in what the gather stands on."""
from __future__ import annotations

import json
import urllib.request

import numpy as np
import pytest

import conftest

from nomad_tpu import codec, mock
from nomad_tpu.api.codec import from_wire, to_wire
from nomad_tpu.codec import native, schema
from nomad_tpu.ops import batch_sched, breaker
from nomad_tpu.ops import decode as decode_mod
from nomad_tpu.ops.batch_sched import TPUBatchScheduler
from nomad_tpu.scheduler import Harness
from nomad_tpu.server import log_codec
from nomad_tpu.server import raft as raft_mod
from nomad_tpu.server.fsm import MessageType
from nomad_tpu.state import StateStore
from nomad_tpu.structs import structs as s
from nomad_tpu.utils.telemetry import InmemSink, Telemetry

import test_fused as fused
import test_node_column as column

FLEETS = column.FLEETS
PENALTY = 20.0

# name -> (scored nodes, how many of them carry a same-job collision)
CASES = {"empty": (0, 0), "one": (1, 0), "one+anti": (1, 1),
         "many": (33, 0), "many+anti": (33, 5)}


def old_dict(ids, col, sc32, co, penalty):
    """What ``_finalize_device_outputs`` built before: the comprehension
    and its anti-affinity loop, verbatim, over a spec's slices of
    ``decode.last_scores``' outputs."""
    scores = {}
    if len(col):
        names = ids[col].tolist()
        scores = {nid + ".binpack": sc for nid, sc in
                  zip(names, sc32.tolist())}
        if (co > 0).any():
            pen = float(penalty)
            for j in np.nonzero(co > 0)[0].tolist():
                scores[names[j] + ".job-anti-affinity"] = -pen * int(co[j])
    return scores


def make_scores(table, col, sc32, co, penalty):
    """The same slices as the batch path hands them over now."""
    pos = np.nonzero(co > 0)[0]
    return s.NodeScores(table, col, sc32.astype(np.float64), pos,
                        -float(penalty) * co[pos].astype(np.float64))


def _arrays(fleet, case, seed=0):
    k, n_anti = CASES[case]
    rng = np.random.default_rng(seed + k)
    n = len(FLEETS[fleet])
    col = rng.permutation(n)[:k].astype(np.int32)
    if fleet == "mixed" and k:
        col[col == 2] = col[0]
        col[0] = 2          # the 200-byte id: a two-byte varint
    sc32 = (rng.random(k) * 18.0).astype(np.float32)
    co = np.zeros(k, dtype=np.int32)
    co[rng.permutation(k)[:n_anti]] = rng.integers(1, 4, n_anti)
    return col, sc32, co


def _pair(fleet, case):
    table = s.NodeTable(FLEETS[fleet])
    col, sc32, co = _arrays(fleet, case)
    return (make_scores(table, col, sc32, co, PENALTY),
            old_dict(table.ids, col, sc32, co, PENALTY))


def _slab(job, scores, table, col):
    proto = column._proto(job)
    proto.metrics = s.AllocMetric(nodes_evaluated=40, scores=scores)
    k = max(1, len(col))
    idx = np.resize(col, k) if len(col) else np.zeros(1, dtype=np.int32)
    return s.AllocSlab(proto=proto, ids=s.LazyUuids(k, "0" * 24),
                       names=s.LazyNames(k, f"{job.id}.web"),
                       node_ids=s.NodeColumn(table, idx))


def _slab_pair(fleet, case):
    table = s.NodeTable(FLEETS[fleet])
    col, sc32, co = _arrays(fleet, case)
    job = mock.job()
    return (_slab(job, make_scores(table, col, sc32, co, PENALTY),
                  table, col),
            _slab(job, old_dict(table.ids, col, sc32, co, PENALTY),
                  table, col))


@pytest.fixture
def counters():
    native.reset_counters()
    yield
    native.reset_counters()


# -- (a) the mapping ----------------------------------------------------------


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("fleet", sorted(FLEETS))
class TestMapping:
    def test_reads_as_the_dictionary(self, fleet, case):
        got, want = _pair(fleet, case)
        built = s.SCORE_MAPS_BUILT
        assert len(got) == len(want) == sum(CASES[case])
        assert bool(got) == bool(want)
        assert got._map is None and s.SCORE_MAPS_BUILT == built
        assert list(got) == list(want)                  # order too
        assert list(got.keys()) == list(want.keys())
        assert list(got.values()) == list(want.values())
        assert list(got.items()) == list(want.items())
        assert sorted(got.items()) == sorted(want.items())
        assert all(type(k) is str and type(v) is float
                   for k, v in got.items())
        for key, value in want.items():
            assert key in got and got[key] == value == got.get(key)
        assert "no-such-node.binpack" not in got
        assert got.get("no-such-node.binpack") is None
        assert got.get("no-such-node.binpack", 1.5) == 1.5
        with pytest.raises(KeyError):
            got["no-such-node.binpack"]
        assert dict(got) == want and type(dict(got)) is dict
        assert {**got} == want
        # one dictionary, made once and kept
        assert got.as_dict() is got.as_dict()
        assert s.SCORE_MAPS_BUILT == built + 1

    def test_equals_the_dictionary_from_either_side(self, fleet, case):
        got, want = _pair(fleet, case)
        again, _ = _pair(fleet, case)
        assert got == want and want == got and got == again
        assert not got != want and not want != got
        other = dict(want)
        other["another.binpack"] = 1.0
        assert got != other and other != got
        assert got != list(want) and got != None    # noqa: E711
        metric = s.AllocMetric(nodes_evaluated=3, scores=got)
        assert metric == s.AllocMetric(nodes_evaluated=3, scores=want)
        assert repr(got) == f"NodeScores({want!r})"
        with pytest.raises(TypeError):
            hash(got)
        with pytest.raises(TypeError):
            got["x.binpack"] = 1.0      # read-only


# -- (b) the bytes ------------------------------------------------------------


@pytest.mark.parametrize("no_native", [False, True],
                         ids=["native", "no-native"])
@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("fleet", sorted(FLEETS))
def test_log_payload_is_byte_identical_and_decodes_to_the_dictionary(
        fleet, case, no_native, monkeypatch, counters):
    if no_native:
        monkeypatch.setenv("NOMAD_TPU_NO_NATIVE", "1")
        monkeypatch.setattr(native, "_lib_resolved", False)
        monkeypatch.setattr(native, "_lib", None)
    new, ref = _slab_pair(fleet, case)
    job = mock.job()
    blobs = [log_codec.encode_payload({"job": job, "alloc_slabs": [slab]})
             for slab in (new, ref)]
    assert blobs[0] == blobs[1] and codec.is_frame(blobs[0])
    entries = [raft_mod._encode_entry(
        7, MessageType.APPLY_PLAN_RESULTS,
        {"job": job, "allocs": [], "eval_id": "ev", "slabs": [slab]})
        for slab in (new, ref)]
    assert entries[0] == entries[1]
    assert native.GUARD_MISMATCHES == 0
    if sum(CASES[case]):
        assert native.SCORE_PACKS == 2
    back = log_codec.decode_payload(blobs[0])["alloc_slabs"][0]
    scores = back.proto.metrics.scores
    assert type(scores) is dict and scores == ref.proto.metrics.scores
    assert list(scores.items()) == list(ref.proto.metrics.scores.items())
    assert (native.NATIVE_PACKS == 0) == no_native


@pytest.mark.parametrize("case", ["one+anti", "many", "many+anti"])
@pytest.mark.parametrize("fleet", sorted(FLEETS))
def test_unguarded_encode_makes_no_string(fleet, case, monkeypatch,
                                          counters):
    monkeypatch.setenv("NOMAD_TPU_CODEC_GUARD_EVERY", "0")
    new, ref = _slab_pair(fleet, case)
    built = s.SCORE_MAPS_BUILT
    assert (log_codec.encode_payload({"alloc_slabs": [new]})
            == log_codec.encode_payload({"alloc_slabs": [ref]}))
    assert native.SCORE_PACKS == 1 and native.GUARD_RUNS == 0
    assert new.proto.metrics.scores._map is None
    assert s.SCORE_MAPS_BUILT == built
    table = new.proto.metrics.scores.table
    one_width = len({len(i.encode()) for i in FLEETS[fleet]}) == 1
    for suffix in (".binpack", ".job-anti-affinity")[:1 + ("anti" in case)]:
        assert (type(table.packed_keys[suffix]) is not list) == one_width


# -- (c) the guard ------------------------------------------------------------


def test_codec_guard_catches_a_wrong_key_table(counters):
    """Every guarded call (the suite's cadence is 1) compares the
    gathered bytes with the Python loop over the materialized
    dictionary: a mismatch is counted, the loop's bytes are written, the
    route is off for the process and the breaker is fed."""
    new, ref = _slab_pair("node-5d", "many+anti")
    good = log_codec.encode_payload({"alloc_slabs": [ref]})
    assert log_codec.encode_payload({"alloc_slabs": [new]}) == good
    assert native.GUARD_MISMATCHES == 0 and native.SCORE_PACKS == 1
    keys = new.proto.metrics.scores.table.packed_keys
    keys[".binpack"] = np.roll(keys[".binpack"], 1)     # the next node's
    runs = native.GUARD_RUNS
    checks = list(breaker.BREAKER._checks)
    assert log_codec.encode_payload({"alloc_slabs": [new]}) == good
    assert native.GUARD_RUNS > runs and native.GUARD_MISMATCHES == 1
    assert native._native_disabled
    assert list(breaker.BREAKER._checks) == checks + [False]
    # the route is off: no gather, still the same bytes
    packs = native.SCORE_PACKS
    assert log_codec.encode_payload({"alloc_slabs": [new]}) == good
    assert native.SCORE_PACKS == packs


# -- (d) the schema -----------------------------------------------------------


def test_schema_fingerprint_and_version_are_the_parents():
    """``AllocMetric.scores`` keeps its declared type: frames written
    before this object existed decode.  The layout version they were
    written in keeps its fingerprint; version 2 only appended
    ``AllocSlab``'s network columns (a version-1 build refuses its
    frames by their version byte)."""
    assert schema.FINGERPRINTS[1].hex() == "539ce714e9f74795"
    assert schema.VERSION == 2
    assert schema.ADDED == {2: {"AllocSlab": ("ips", "dyn_ports")}}
    assert s.AllocMetric().scores == {} and type(s.AllocMetric().scores) is dict


# -- (e) the generic readers --------------------------------------------------


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("fleet", sorted(FLEETS))
class TestReaders:
    def test_to_wire_tree_and_json_are_equal(self, fleet, case):
        new, ref = _slab_pair(fleet, case)
        tree = to_wire(new.proto)
        assert tree == to_wire(ref.proto)
        assert json.dumps(tree) == json.dumps(to_wire(ref.proto))
        wired = tree["Metrics"]["Scores"]
        assert type(wired) is dict
        wired["mine.binpack"] = 0.0     # a copy: the caller's to change
        assert "mine.binpack" not in new.proto.metrics.scores
        back = from_wire(s.Allocation, to_wire(new.proto))
        assert back.metrics.scores == ref.proto.metrics.scores

    def test_msgpack_entry_is_byte_identical(self, fleet, case,
                                             monkeypatch):
        """The kill switch's tagged-msgpack tree (``NOMAD_TPU_CODEC=0``)
        writes the map as its dictionary."""
        new, ref = _slab_pair(fleet, case)
        monkeypatch.setenv("NOMAD_TPU_CODEC", "0")
        codec.reset()       # the switch is read once
        try:
            assert not codec.enabled()
            blobs = [log_codec.encode_payload({"alloc_slabs": [slab]})
                     for slab in (new, ref)]
        finally:
            monkeypatch.delenv("NOMAD_TPU_CODEC")
            codec.reset()
        assert blobs[0] == blobs[1] and not codec.is_frame(blobs[0])
        back = log_codec.decode_payload(blobs[0])["alloc_slabs"][0]
        assert back.proto.metrics.scores == ref.proto.metrics.scores

    def test_snapshot_is_byte_identical_and_restores_the_dictionary(
            self, fleet, case):
        new, ref = _slab_pair(fleet, case)
        store = StateStore()
        store.upsert_slabs(5, [new])
        first = store.persist()
        new.proto.metrics.scores = ref.proto.metrics.scores
        assert store.persist() == first
        back = StateStore.restore(first)._pending_slabs[0].proto
        assert type(back.metrics.scores) is dict
        assert back.metrics.scores == ref.proto.metrics.scores

    def test_copies_share_it_and_score_node_replaces_it(self, fleet, case):
        new, ref = _slab_pair(fleet, case)
        scores = new.proto.metrics.scores
        built = s.SCORE_MAPS_BUILT
        metric = new.proto.metrics.copy()
        assert metric.scores is scores and metric is not new.proto.metrics
        assert new.proto.copy().metrics.scores is scores
        assert new.materialize(0).metrics.scores is scores
        assert s.SCORE_MAPS_BUILT == built      # nobody read a string
        plain = ref.proto.metrics.copy()
        assert plain.scores == ref.proto.metrics.scores
        assert plain.scores is not ref.proto.metrics.scores
        # the oracle's score_node adds into a plain dictionary
        node = mock.node()
        node.id = FLEETS[fleet][int(scores.idx[0])] if len(scores) else "n"
        for m in (metric, plain):
            m.score_node(node, "binpack", 0.25)
            m.score_node(node, "job-anti-affinity", -2.0)
        assert type(metric.scores) is dict and metric.scores == plain.scores
        assert metric.scores != ref.proto.metrics.scores
        assert scores == ref.proto.metrics.scores       # untouched


def test_alloc_status_prints_the_scores():
    from nomad_tpu.cli import commands

    new, ref = _slab_pair("uuid", "many+anti")
    lines = commands.format_alloc_metrics(new.proto.metrics, "")
    assert lines == commands.format_alloc_metrics(ref.proto.metrics, "")
    assert sum("Score" in line for line in lines) == 38


# -- (f) the batch path -------------------------------------------------------


def test_fused_batch_hands_over_arrays_equal_to_the_old_dictionary(
        monkeypatch):
    """One fused batch on the CPU backend: every placed spec's
    ``metrics.scores`` is a NodeScores, equal to what the old
    comprehension builds from the decode pass's outputs; the counters
    say so, and no map is turned into strings until a reader asks."""
    h = Harness()
    nodes = [fused.make_node() for _ in range(6)]
    for node in nodes:
        h.state.upsert_node(h.next_index(), node)
    # count 9 on 6 nodes: same-job collisions, so anti-affinity entries
    jobs = [fused.make_job(9), fused.make_job(2), fused.make_job(4)]
    for job in jobs:
        h.state.upsert_job(h.next_index(), job)
    decoded = []
    real = decode_mod.last_scores

    def last_scores(*args, **kw):
        out = real(*args, **kw)
        decoded.append(out)
        return out

    monkeypatch.setattr(decode_mod, "last_scores", last_scores)
    monkeypatch.setenv("NOMAD_TPU_RNG_SEED", "1234")
    sink = InmemSink(interval=60.0)
    sched = TPUBatchScheduler(h.logger, h.snapshot(), h)
    sched.metrics = Telemetry(sink)
    batch_sched._publish_score_maps(Telemetry(InmemSink()))   # settle
    built = s.SCORE_MAPS_BUILT
    mismatches = native.GUARD_MISMATCHES
    stats = sched.schedule_batch([fused.reg_eval(j) for j in jobs])
    assert stats.fused and stats.score_columns == len(jobs)
    totals = sink.latest()["CounterTotals"]
    assert totals["nomad.batch.score_columns"] == len(jobs)
    assert totals["nomad.batch.score_maps_built"] == 0
    assert s.SCORE_MAPS_BUILT == built

    assert len(decoded) == 1
    s_off, s_col, s_sc, s_co = decoded[0]
    assert s_sc.dtype == np.float32
    anti = 0
    for u, job in enumerate(jobs):
        live = [a for a in h.state.allocs_by_job(None, job.id, True)
                if not a.terminal_status()]
        assert len(live) == job.task_groups[0].count
        scores = live[0].metrics.scores
        assert type(scores) is s.NodeScores
        assert all(a.metrics.scores is scores for a in live)
        lo, hi = int(s_off[u]), int(s_off[u + 1])
        want = old_dict(scores.table.ids, s_col[lo:hi], s_sc[lo:hi],
                        s_co[lo:hi], 20.0)
        assert len(scores) == len(want) > 0
        assert s.SCORE_MAPS_BUILT == built      # len() made no string
        built += 1
        assert scores == want and list(scores.items()) == list(want.items())
        assert s.SCORE_MAPS_BUILT == built
        anti += sum(k.endswith(".job-anti-affinity") for k in want)
        assert {k.rsplit(".", 1)[0] for k in want} <= {n.id for n in nodes}
    assert anti > 0
    # the next batch publishes what the readers above built
    sched = TPUBatchScheduler(h.logger, h.snapshot(), h)
    sched.metrics = Telemetry(sink)
    sched.schedule_batch([fused.reg_eval(fused.make_job(0))])
    totals = sink.latest()["CounterTotals"]
    assert totals["nomad.batch.score_maps_built"] == len(jobs)
    assert native.GUARD_MISMATCHES == mismatches


def test_served_job_logs_the_map_and_the_wal_replays_a_dictionary(tmp_path):
    """One job through the served device path, durable: the committed
    prototype holds a NodeScores, its log entry went through the gather
    under the guard (cadence 1) without a mismatch, and a replay of the
    WAL ends with the same scores as a plain dictionary."""
    from nomad_tpu.server.fsm import FSM
    from nomad_tpu.server.raft import FileLog

    native.reset_counters()
    with conftest.served_job(data_dir=tmp_path, count=12,
                             nodes=5) as (agent, job, _eval_id):
        srv = agent.server
        allocs = srv.state.allocs_by_job(None, job.id, True)
        scores = allocs[0].metrics.scores
        assert type(scores) is s.NodeScores and len(scores) >= 5
        assert native.SCORE_PACKS >= 1 and native.GUARD_MISMATCHES == 0
        totals = srv.metrics.sink.latest()["CounterTotals"]
        assert totals["nomad.batch.score_columns"] == 1
        want = dict(scores)
        with urllib.request.urlopen(
                agent.http.address + f"/v1/allocation/{allocs[0].id}",
                timeout=30) as resp:
            tree = json.loads(resp.read())
        assert tree["Metrics"]["Scores"] == want
    again = FileLog(FSM(), str(tmp_path))
    try:
        back = again.fsm.state.allocs_by_job(None, job.id, True)
        assert len(back) == 12
        assert type(back[0].metrics.scores) is dict
        assert back[0].metrics.scores == want
    finally:
        again.close()
        native.reset_counters()
