"""Cluster-scale chaos scenarios (ISSUE 12): deterministic network
partitions, crash-restart recovery, and the continuous safety auditor.

Fast fixed-seed scenarios run in tier-1 under the ``chaos`` marker
(including the subprocess kill+restart smoke soak); the full 3-server
soak is additionally marked ``slow``.
"""
import os
import time

import pytest

from nomad_tpu import fault, mock
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.fsm import FSM, MessageType
from nomad_tpu.server.raft import FileLog, MultiRaft
from nomad_tpu.server.rpc import ConnPool, DialError
from nomad_tpu.structs import structs as s

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _always_disarm():
    """No scenario — rule plane OR net plane — may leak across tests."""
    yield
    fault.disarm()
    fault.net_disarm()


def wait_until(predicate, timeout=60.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


def make_node():
    n = mock.node()
    n.resources.networks = []
    n.reserved.networks = []
    return n


def make_job(count=2):
    j = mock.job()
    j.task_groups[0].count = count
    for t in j.task_groups[0].tasks:
        t.resources.networks = []
    return j


# ---------------------------------------------------------------------------
# the net plane itself
# ---------------------------------------------------------------------------


class TestNetPlane:
    def test_partition_blocks_both_directions_and_heals(self):
        plane = fault.net_partition("p", [["a:1"], ["b:2", "c:3"]])
        assert plane.blocked("a:1", "b:2")
        assert plane.blocked("c:3", "a:1")
        assert not plane.blocked("b:2", "c:3")   # same group
        assert not plane.blocked("a:1", "d:4")   # unlisted → unaffected
        fault.net_heal("p")
        assert not plane.blocked("a:1", "b:2")
        trace = plane.trace()
        assert ("net.partition", "p", "split") in trace
        assert ("net.partition", "p", "heal") in trace

    def test_wildcard_group_isolates_most_specific(self):
        """A ["*"] catch-all group composes with a literal group: the
        listed address is cut off from EVERYONE (the subprocess-isolate
        shape), including unidentified client pools."""
        plane = fault.net_partition("iso", [["*"], ["b:2"]])
        assert plane.blocked("", "b:2")
        assert plane.blocked("b:2", "a:1")
        assert not plane.blocked("a:1", "c:3")
        fault.net_heal()

    def test_asymmetric_rule_seeded_determinism(self):
        """A src→dst drop rule fires one direction only, and the same
        seed yields the same decision sequence — the reproducibility
        contract carried over from the rule plane."""
        def run(seed):
            plane = fault.net_arm({"seed": seed, "rules": [
                {"src": "a:1", "dst": "b:2", "action": "drop",
                 "prob": 0.5}]})
            fires = []
            for _ in range(64):
                fires.append(plane.check("send", "a:1", "b:2") is not None)
                # reverse direction never fires
                assert plane.check("send", "b:2", "a:1") is None
            fault.net_disarm()
            return fires

        a, b, c = run(5), run(5), run(6)
        assert a == b
        assert 0 < sum(a) < 64
        assert a != c

    def test_flap_windows_deterministic_and_scheduled(self):
        w = fault.flap_windows(9, count=3, period=1.0, duty=0.5)
        assert w == fault.flap_windows(9, count=3, period=1.0, duty=0.5)
        assert w != fault.flap_windows(10, count=3, period=1.0, duty=0.5)
        assert all(b > a for a, b in w)
        # A flapping partition honors its windows against the plane's
        # arm anchor: shift the anchor to step through the schedule.
        plane = fault.net_arm()
        plane.partition("flap", [["a:1"], ["b:2"]], windows=[(10.0, 11.0)])
        assert not plane.blocked("a:1", "b:2")    # before the window
        plane._anchor -= 10.5                      # inside the window
        assert plane.blocked("a:1", "b:2")
        plane._anchor -= 5.0                       # past it → healed
        assert not plane.blocked("a:1", "b:2")

    def test_reorder_is_bounded_delay(self):
        plane = fault.net_arm({"seed": 1, "rules": [
            {"action": "reorder", "max_delay": 0.5}]})
        act = plane.check("send", "x", "y")
        assert act is not None
        action, delay = act
        assert action == "reorder" and 0.0 <= delay <= 0.5


class TestDialBackoff:
    def test_dead_peer_dials_gate_instead_of_hammering(self):
        """First dial to a dead address fails for real; an immediate
        second attempt fails FAST from the local backoff gate without
        touching a socket (the redial-storm fix)."""
        pool = ConnPool(timeout=0.5)
        dead = "127.0.0.1:1"
        with pytest.raises(DialError) as e1:
            pool.call(dead, "Status.Ping", {})
        assert "backoff" not in str(e1.value)
        gate = pool._dial_gate[dead]
        assert gate[1] > time.monotonic() - 0.001
        with pytest.raises(DialError) as e2:
            pool.call(dead, "Status.Ping", {})
        assert "dial backoff" in str(e2.value)
        # The gate expires (capped, jittered) and real dials resume.
        time.sleep(max(0.0, gate[1] - time.monotonic()) + 0.01)
        with pytest.raises(DialError) as e3:
            pool.call(dead, "Status.Ping", {})
        assert "dial backoff" not in str(e3.value)
        pool.close()

    def test_gate_clears_on_success(self):
        srv = Server(ServerConfig(enable_rpc=True, num_schedulers=0))
        srv.start()
        pool = ConnPool(timeout=2.0)
        try:
            addr = srv.config.rpc_advertise
            # Seed a (expired) gate entry, then a successful dial must
            # clear it entirely.
            from nomad_tpu.utils.backoff import Backoff
            pool._dial_gate[addr] = [Backoff(), 0.0]
            assert pool.call(addr, "Status.Ping", {}) == {"ok": True}
            assert addr not in pool._dial_gate
        finally:
            pool.close()
            srv.shutdown()


# ---------------------------------------------------------------------------
# msgpack residue counter (ROADMAP item 1 residual, ISSUE 12 satellite)
# ---------------------------------------------------------------------------


class TestMsgpackMethodCounter:
    def test_hot_methods_never_ride_msgpack_between_codec_peers(self):
        from nomad_tpu import codec
        from nomad_tpu.api.codec import to_wire

        srv = Server(ServerConfig(enable_rpc=True, num_schedulers=0))
        srv.start()
        pool = ConnPool()
        try:
            before = codec.msgpack_methods()
            addr = srv.config.rpc_advertise
            node = make_node()
            pool.call(addr, "Node.Register", {"Node": to_wire(node)})
            pool.call(addr, "Job.Register",
                      {"Job": to_wire(make_job(1))})
            pool.call(addr, "Status.Ping", {})
            delta = {m: n - before.get(m, 0)
                     for m, n in codec.msgpack_methods().items()
                     if n - before.get(m, 0) > 0}
            hot = {m: n for m, n in delta.items()
                   if m.startswith(codec.HOT_METHOD_PREFIXES)}
            assert hot == {}, (
                f"hot methods rode the msgpack fallback: {hot}")
        finally:
            pool.close()
            srv.shutdown()

    def test_legacy_peer_frames_are_counted_per_method(self):
        from nomad_tpu import codec

        srv = Server(ServerConfig(enable_rpc=True, num_schedulers=0))
        srv.start()
        pool = ConnPool()
        try:
            addr = srv.config.rpc_advertise
            # Pin the address legacy: every frame is reflection msgpack
            # and must show up in the per-method residue profile.
            pool._legacy_addrs.add(addr)
            before = codec.msgpack_methods().get("Status.Ping", 0)
            pool.call(addr, "Status.Ping", {})
            pool.call(addr, "Status.Ping", {})
            assert codec.msgpack_methods().get(
                "Status.Ping", 0) - before == 2
        finally:
            pool.close()
            srv.shutdown()


# ---------------------------------------------------------------------------
# cluster harness (in-process, test_cluster-style)
# ---------------------------------------------------------------------------


def make_cluster(tmp_path, n=3, num_schedulers=0, env=None,
                 monkeypatch=None):
    if env:
        for k, v in env.items():
            monkeypatch.setenv(k, v)
    servers = []
    first = None
    for i in range(n):
        cfg = ServerConfig(
            node_name=f"chaos-{i + 1}",
            data_dir=str(tmp_path / f"s{i + 1}"),
            enable_rpc=True, bootstrap_expect=n,
            start_join=[first] if first else [],
            num_schedulers=num_schedulers,
            min_heartbeat_ttl=60.0)
        srv = Server(cfg)
        if first is None:
            first = srv.config.rpc_advertise
        servers.append(srv)
    for srv in servers:
        srv.start()
    return servers


def wait_for_leader(servers, timeout=30.0):
    assert wait_until(lambda: any(
        srv.is_leader() and srv.raft.is_raft_leader()
        for srv in servers), timeout), "no leader elected"
    return next(srv for srv in servers
                if srv.is_leader() and srv.raft.is_raft_leader())


class TestPartitionHealInstallSnapshot:
    def test_partitioned_follower_catches_up_via_chunked_install(
            self, tmp_path, monkeypatch):
        """Split a follower from the leader (both directions), commit
        writes and compact the leader's log past the follower's
        horizon, heal — the follower must catch up via CHUNKED
        InstallSnapshot, converging to an identical FSM fingerprint."""
        servers = make_cluster(
            tmp_path, 3, monkeypatch=monkeypatch,
            env={
                # A partitioned VOTER must not campaign inside the
                # short split (term inflation would measure election
                # churn, not catch-up).
                "NOMAD_TPU_RAFT_ELECTION_MIN_S": "8.0",
                "NOMAD_TPU_RAFT_ELECTION_MAX_S": "12.0",
                "NOMAD_TPU_SNAPSHOT_CHUNK": "512",
            })
        try:
            leader = wait_for_leader(servers)
            victim = next(srv for srv in servers if srv is not leader)
            assert wait_until(lambda: all(
                len(srv.raft.peers) == 3 for srv in servers))

            job0 = make_job(1)
            leader.job_register(job0)
            assert wait_until(lambda: victim.state.job_by_id(
                None, job0.id) is not None)

            fault.net_partition(
                "split", [[leader.config.rpc_advertise],
                          [victim.config.rpc_advertise]])
            jobs = [make_job(1) for _ in range(5)]
            for job in jobs:
                leader.job_register(job)
            # The split is real: the follower sees none of it.
            time.sleep(0.3)
            assert all(victim.state.job_by_id(None, j.id) is None
                       for j in jobs)
            # Compact the leader past the follower's log position so
            # heal-time catch-up MUST take the snapshot path.
            leader.raft.snapshot()
            assert isinstance(leader.raft, MultiRaft)
            assert leader.raft.base_index > 0
            chunks_before = int((leader.metrics.sink.latest()
                                 .get("CounterTotals") or {})
                                .get("nomad.raft.snapshot.chunks_sent", 0))

            fault.net_heal("split")
            assert wait_until(lambda: all(
                victim.state.job_by_id(None, j.id) is not None
                for j in jobs), 30.0), "healed follower did not catch up"
            assert wait_until(
                lambda: victim.raft.base_index >= leader.raft.base_index,
                10.0)
            chunks = int((leader.metrics.sink.latest()
                          .get("CounterTotals") or {})
                         .get("nomad.raft.snapshot.chunks_sent", 0))
            assert chunks - chunks_before >= 2, \
                "catch-up was not a chunked InstallSnapshot"
            # Split it AGAIN (determinism of repeated split/heal) and
            # verify the converged fingerprints agree.
            fault.net_partition(
                "split2", [[leader.config.rpc_advertise],
                           [victim.config.rpc_advertise]])
            job_z = make_job(1)
            leader.job_register(job_z)
            time.sleep(0.2)
            assert victim.state.job_by_id(None, job_z.id) is None
            fault.net_heal("split2")
            assert wait_until(lambda: victim.state.job_by_id(
                None, job_z.id) is not None, 20.0)

            def converged():
                li, lfp = leader.fsm_fingerprint()
                vi, vfp = victim.fsm_fingerprint()
                return li == vi and lfp == vfp

            assert wait_until(converged, 10.0), \
                "FSM fingerprints did not converge after heal"
        finally:
            for srv in servers:
                srv.shutdown()


class TestLeaderKillInFlight:
    def test_leader_death_with_inflight_plans_no_double_placement(
            self, tmp_path):
        """Kill the leader while pipelined plans are in flight through
        its applier: after the survivors elect, every pending eval is
        restored and completes, and NO job ends with more live allocs
        than its count or a duplicate name — the PR 10 fences (token
        fence, post-failover floor) across a real failover."""
        servers = make_cluster(tmp_path, 3, num_schedulers=1)
        try:
            leader = wait_for_leader(servers)
            for srv in servers:
                srv.eval_broker.initial_nack_delay = 0.1
            for _ in range(4):
                leader.node_register(make_node())

            # Widen the in-flight window: every plan commit pays a
            # delay inside the leader's raft apply.
            fault.arm({"seed": 3, "faults": [
                {"point": "raft.apply", "action": "delay", "delay": 0.25,
                 "match": {"msg_type": "APPLY_PLAN_RESULTS"}}]})
            jobs = [make_job(2) for _ in range(4)]
            for job in jobs:
                leader.job_register(job)
            time.sleep(0.3)  # plans now mid-pipeline
            leader.shutdown()
            fault.disarm()

            survivors = [srv for srv in servers if srv is not leader]
            new_leader = wait_for_leader(survivors, timeout=45.0)

            def settled():
                for job in jobs:
                    live = [a for a in new_leader.state.allocs_by_job(
                                None, job.id, True)
                            if not a.terminal_status()]
                    if len(live) != 2:
                        return False
                return True

            assert wait_until(settled, 90.0), \
                "jobs did not settle at their exact count after failover"
            # The invariant, explicitly: never MORE than count, never a
            # duplicate name, on every survivor.
            for srv in survivors:
                for job in jobs:
                    live = [a for a in srv.state.allocs_by_job(
                                None, job.id, True)
                            if not a.terminal_status()]
                    assert len(live) <= 2
                    assert len({a.name for a in live}) == len(live)
        finally:
            for srv in servers:
                srv.shutdown()


# ---------------------------------------------------------------------------
# torn walseg recovery (FileLog)
# ---------------------------------------------------------------------------


class TestTornWalsegRecovery:
    def _apply_nodes(self, log, count):
        nodes = [make_node() for _ in range(count)]
        for n in nodes:
            log.apply(MessageType.NODE_REGISTER, {"node": n})
        return nodes

    def test_torn_sealed_segment_recovers_durable_prefix_exactly(
            self, tmp_path, monkeypatch):
        """A crash between the WAL roll and the snapshot blob leaves
        sealed walseg files as the only copy of their entries; a torn
        tail in one (partial disk write) must recover the longest
        decodable prefix EXACTLY — earlier entries intact, the torn
        record dropped, and later appends durable at the right index."""
        d = str(tmp_path / "wal")
        fsm = FSM()
        log = FileLog(fsm, d, snapshot_entries=0, snapshot_bytes=0)
        nodes = self._apply_nodes(log, 4)
        # Crash mid-snapshot: the roll seals the WAL into walseg files,
        # then the blob persist dies — segments stay behind.
        def boom(snap_store, index):
            raise OSError("injected crash before snapshot blob")
        monkeypatch.setattr(log, "_persist_snapshot_blob", boom)
        with pytest.raises(OSError):
            log.snapshot()
        log.close()
        segs = [os.path.join(d, f) for f in os.listdir(d)
                if f.startswith("walseg-")]
        assert segs, "crash-before-blob left no sealed segments"
        # Tear the tail of the (single) sealed segment: the last
        # record's bytes are partially lost.
        seg = segs[0]
        size = os.path.getsize(seg)
        with open(seg, "r+b") as fh:
            fh.truncate(size - 7)

        log2 = FileLog(FSM(), d, snapshot_entries=0, snapshot_bytes=0)
        try:
            # Exactly the durable prefix: 1-3 recovered, entry 4 (torn)
            # gone, nothing invented.
            assert log2.applied_index() == 3
            for n in nodes[:3]:
                assert log2.fsm.state.node_by_id(None, n.id) is not None
            assert log2.fsm.state.node_by_id(None, nodes[3].id) is None
            # The index is reusable and appends stay durable.
            extra = make_node()
            _, idx = log2.apply(MessageType.NODE_REGISTER, {"node": extra})
            assert idx == 4
        finally:
            log2.close()

        log3 = FileLog(FSM(), d, snapshot_entries=0, snapshot_bytes=0)
        try:
            assert log3.applied_index() == 4
            assert log3.fsm.state.node_by_id(None, extra.id) is not None
        finally:
            log3.close()


# ---------------------------------------------------------------------------
# the chaos_soak smoke tier: a REAL subprocess kill+restart under load
# ---------------------------------------------------------------------------


class TestChaosSoakSmoke:
    def _assert_clean(self, rep, expect_events):
        aud = rep.get("auditor") or {}
        assert aud.get("violation_count") == 0, aud.get("violations")
        assert (aud.get("checks") or {}).get("fingerprint_matches", 0) >= 1
        chaos = rep.get("chaos") or {}
        events = chaos.get("events") or []
        assert len(events) == expect_events
        assert not any(ev.get("error") for ev in events), events
        kinds = {ev["kind"] for ev in events}
        assert kinds == {"partition", "kill"}
        kill = next(ev for ev in events if ev["kind"] == "kill")
        assert kill.get("restarted_after_s") is not None
        assert chaos.get("unrecovered") == 0, events
        integ = rep["integrity"]
        assert integ["overplaced_jobs"] == 0
        assert integ["duplicate_alloc_names"] == 0
        assert integ["overcommitted_nodes"] == 0
        assert rep["sustained"]["stragglers_after_drain"] == 0
        # The satellite proof: no hot method on the msgpack fallback.
        assert (rep.get("codec") or {}).get("hot_msgpack_methods") == {}

    def test_smoke_soak_fixed_seed_zero_violations(self):
        """The tier-1 chaos gate: one split/heal cycle plus one REAL
        subprocess SIGKILL+restart (recovering from the follower's own
        raft store) under bounded offered load, with the continuous
        auditor asserting every invariant live — zero violations, zero
        stragglers, recovery inside the bound."""
        from nomad_tpu.loadgen.harness import run_scenario
        from nomad_tpu.loadgen.scenario import get_scenario

        rep = run_scenario(get_scenario("chaos_smoke"))
        self._assert_clean(rep, expect_events=2)

    @pytest.mark.slow
    def test_full_soak_three_servers(self):
        """The chaos_soak scenario: 3 servers,
        kills + repeated partitions, zero violations."""
        from nomad_tpu.loadgen.harness import run_scenario
        from nomad_tpu.loadgen.scenario import get_scenario

        rep = run_scenario(get_scenario("chaos_soak"))
        self._assert_clean(rep, expect_events=3)
