"""Server runtime: composes the log/FSM, broker, plan pipeline, workers,
heartbeats, periodic dispatch, and GC into the control plane, and exposes
the RPC endpoint surface as methods
(reference: nomad/server.go:78-305, nomad/leader.go:28-641,
nomad/*_endpoint.go).
"""
from __future__ import annotations

import logging
import math
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .. import fault
from ..structs import structs as s
from ..tenancy import QuotaLedger, RateLimiter
from ..utils import blackbox, contprof, knobs, telemetry, tracing
from ..utils.telemetry import Telemetry
from . import event_broker as event_stream
from .blocked_evals import BlockedEvals
from .core_sched import CoreScheduler
from .eval_broker import BrokerLimitError, EvalBroker
from .event_broker import EventBroker
from .fsm import FSM, MessageType, TimeTable
from .heartbeat import HeartbeatTimers
from .periodic import PeriodicDispatch, derive_job
from .plan_apply import PlanApplier
from .plan_queue import PlanQueue
from .raft import FileLog, InmemLog, MultiRaft, NotLeaderError, RaftLog
from ..utils.tlsutil import TLSConfig, client_context, server_context
from .vault import ServerVaultClient, VaultConfig, VaultError
from .worker import BatchWorker, Worker


@dataclass
class ServerConfig:
    """(reference: nomad/config.go)."""

    region: str = "global"
    datacenter: str = "dc1"
    node_name: str = "server-1"
    rpc_advertise: str = "127.0.0.1:4647"
    data_dir: str = ""                  # empty → in-memory log (dev mode)
    # RPC / clustering (nomad/config.go RPCAddr, BootstrapExpect, serf join)
    enable_rpc: bool = False            # start the TCP RPC listener
    rpc_bind: str = "127.0.0.1"
    rpc_port: int = 0                   # 0 → ephemeral
    bootstrap_expect: int = 1
    start_join: List[str] = field(default_factory=list)
    # Cross-region federation joins (serf WAN, nomad/serf.go): membership
    # only — never part of this region's raft quorum.
    wan_join: List[str] = field(default_factory=list)
    num_schedulers: int = 1
    use_tpu_batch_worker: bool = False
    batch_size: int = 64
    # Optional jax.sharding.Mesh this region's batch scheduler shards its
    # node axis over — each federated region owns its device slice (the
    # multi-slice/DCN story, SURVEY §2.9 last row): requests forward
    # between regions host-side (rpc.go:263), and each region's placement
    # loop runs on its OWN mesh with ICI collectives inside the slice.
    device_mesh: object = None
    eval_nack_timeout: float = 60.0
    eval_delivery_limit: int = 3
    # Eval-broker admission control (ISSUE 7): bounded pending queue +
    # per-job coalescing.  0 = unbounded (historical behavior); the env
    # knobs let operators bound a running deployment without code.
    broker_max_pending: int = field(default_factory=lambda: knobs.get_int(
        "NOMAD_TPU_BROKER_MAX_PENDING"))
    broker_coalesce: bool = field(default_factory=lambda: knobs.get_bool(
        "NOMAD_TPU_BROKER_COALESCE"))
    broker_bypass_priority: int = field(default_factory=lambda: knobs.get_int(
        "NOMAD_TPU_BROKER_BYPASS_PRIO", s.JOB_MAX_PRIORITY))
    # Multi-tenant serving plane (ROADMAP item 3): cluster-wide default
    # fair-dequeue objective (drf | weighted-rr | fifo); a Namespace
    # row's objective field overrides per tenant.
    tenancy_objective: str = field(default_factory=lambda: knobs.get_str(
        "NOMAD_TPU_TENANCY_OBJECTIVE", s.TENANCY_OBJECTIVE_DRF))
    # Follower-read scheduling (ISSUE 10): on a multi-raft cluster every
    # server also runs FollowerWorkers that, while the server is a
    # follower, pull evals from the leader's broker over RPC, schedule
    # off the locally replicated FSM, and forward plans to the leader's
    # serialized plan-apply (server/follower_sched.py).  Default on —
    # they idle on single-voter servers and on the leader.
    follower_scheduling: bool = field(default_factory=lambda: knobs.get_bool(
        "NOMAD_TPU_FOLLOWER_SCHED"))
    # Follower workers per server; 0 → num_schedulers.
    follower_schedulers: int = 0
    # Join as a NON-VOTING member (the reference's non_voting_server):
    # replicated like a voter — so follower-read scheduling works — but
    # never counted toward quorum and never campaigning.  The shape for
    # scaling scheduler capacity without scaling commit latency.
    non_voting: bool = False
    # Force MultiRaft even for a cluster seed with bootstrap_expect=1 —
    # the shape a deterministic leader takes when follower-scheduler
    # servers will join it later (the loadgen multi-server scenario).
    force_multi_raft: bool = False
    # Heartbeat TTL jitter fraction (thundering-herd dispersal).
    heartbeat_ttl_jitter: float = field(default_factory=lambda: knobs.get_float(
        "NOMAD_TPU_HEARTBEAT_JITTER"))
    # Retry cadence for queued (failed) Vault revocations
    # (vault.go:1104 revokeDaemon — 5 minutes there; shorter default so
    # a failed revoke clears quickly and tests can observe it).
    vault_revoke_interval: float = 5.0
    min_heartbeat_ttl: float = 10.0
    max_heartbeats_per_second: float = 50.0
    failed_eval_unblock_interval: float = 60.0
    eval_gc_interval: float = 300.0
    enabled_schedulers: List[str] = field(default_factory=lambda: [
        s.JOB_TYPE_SERVICE, s.JOB_TYPE_BATCH, s.JOB_TYPE_SYSTEM, s.JOB_TYPE_CORE])
    vault: Optional[VaultConfig] = None
    tls: Optional[TLSConfig] = None


def _job_usage_vec(job: s.Job) -> Tuple[int, int, int, int]:
    """A job's total resource ask on the alloc_usage_vec basis
    (cpu, memory_mb, disk_mb, iops): per-taskgroup task sums × count.
    The node-units admission gate prices a submission with this before
    any alloc exists to fold into the per-ns usage."""
    cpu = mem = disk = iops = 0
    for tg in job.task_groups:
        c = m = d = i = 0
        for task in tg.tasks:
            r = task.resources
            if r is None:
                continue
            c += r.cpu
            m += r.memory_mb
            d += r.disk_mb
            i += r.iops
        cpu += c * tg.count
        mem += m * tg.count
        disk += d * tg.count
        iops += i * tg.count
    return (cpu, mem, disk, iops)


class Server:
    """A single control-plane server (nomad/server.go:78 Server)."""

    def __init__(self, config: Optional[ServerConfig] = None,
                 logger: Optional[logging.Logger] = None,
                 vault_api=None):
        self.config = config or ServerConfig()
        self.logger = logger or logging.getLogger("nomad_tpu.server")
        # Telemetry (go-metrics role): in-memory sink surfaced via
        # agent-info + /v1/metrics; hot paths measure through it
        # (server.go:292-305 periodic emitters + MeasureSince call sites).
        self.metrics = Telemetry()
        # Opt-in eval-lifecycle tracing (utils/tracing.py): process-wide,
        # off by default; NOMAD_TPU_TRACE=1 arms it at construction so
        # /v1/trace/* works without code changes.
        if not tracing.enabled() and knobs.get_bool("NOMAD_TPU_TRACE"):
            tracing.enable()
        # Same construction-time arming for the host-attribution
        # profiler and the incident flight recorder — both are
        # process-wide, None-when-disarmed planes like the tracer.
        contprof.maybe_arm_from_env()
        blackbox.maybe_arm_from_env()
        blackbox.register_server(self)
        telemetry.watch_gc(self)
        # Vault client (nomad/vault.go:234); vault_api injects the fake
        # in tests (vault_testing.go role).
        self.vault = ServerVaultClient(self.config.vault or VaultConfig(),
                                       api=vault_api,
                                       logger=self.logger.getChild("vault"))
        # Must precede raft construction: WAL replay fires FSM hooks that
        # consult leadership.
        self._leader = False
        self._shutdown = threading.Event()

        self.eval_broker = EvalBroker(
            nack_timeout=self.config.eval_nack_timeout,
            delivery_limit=self.config.eval_delivery_limit,
            metrics=self.metrics,
            max_pending=self.config.broker_max_pending,
            coalesce=self.config.broker_coalesce,
            bypass_priority=self.config.broker_bypass_priority)
        self.eval_broker.set_objective(self.config.tenancy_objective)
        # Tenancy enforcement (ROADMAP item 3): leader-side alloc-quota
        # reservation book and the per-tenant API token buckets the HTTP
        # layer consults.  Both are policy mirrors of committed
        # Namespace rows, pushed through the FSM namespace hook.
        self.quota_ledger = QuotaLedger()
        # Node-units reservation book (the quota_node_units field):
        # same ledger mechanics with fractional counts — a tenant's
        # dominant-resource share of the cluster, scaled to nodes-worth.
        self.node_units_ledger = QuotaLedger()
        self.api_limiter = RateLimiter()
        # Cluster capacity mirror for DRF dominant shares and node-units
        # admission: recomputed only when the nodes table index moves.
        self._capacity_node_index = -1
        self._cluster_capacity: Tuple[int, int, int, int] = (0, 0, 0, 0)
        self._cluster_nodes = 0
        self.blocked_evals = BlockedEvals(self.eval_broker)
        self.plan_queue = PlanQueue()
        self.time_table = TimeTable()

        self.fsm = FSM(
            logger=self.logger,
            on_eval_update=self._fsm_eval_updated,
            on_unblock=self._fsm_unblock,
            on_job_register=self._fsm_job_registered,
            on_job_deregister=self._fsm_job_deregistered,
            on_alloc_terminal=self._fsm_alloc_terminal,
            on_namespace_update=self._fsm_namespace_updated,
        )

        # RPC listener + connection pool (nomad/server.go:250 setupRPC).
        # Bound in __init__ so the advertised address is known before raft
        # construction; served from start().
        self.rpc = None
        self.pool = None
        self._members: Dict[str, Dict] = {}
        self._members_lock = threading.Lock()
        # Incarnation for this server's own member record (serf's
        # refutation counter): bumped past any gossiped 'left' about us.
        self._status_time = 1
        # Per-thread marker set while serving a request that was already
        # forwarded once (endpoints.py); blocks a second hop.
        self._fwd_ctx = threading.local()
        if self.config.enable_rpc:
            from .rpc import ConnPool, RPCServer

            tls_cfg = self.config.tls or TLSConfig()
            self.pool = ConnPool(tls_context=client_context(tls_cfg))
            self.rpc = RPCServer(host=self.config.rpc_bind,
                                 port=self.config.rpc_port,
                                 logger=self.logger.getChild("rpc"),
                                 tls_context=server_context(tls_cfg),
                                 metrics=self.metrics)
            # Advertise the configured host (never a wildcard bind) with
            # the actually-bound port (config.go AdvertiseAddrs).
            adv_host = ""
            if self.config.rpc_advertise:
                adv_host = self.config.rpc_advertise.rsplit(":", 1)[0]
            if not adv_host or adv_host == "0.0.0.0":
                adv_host = (self.config.rpc_bind
                            if self.config.rpc_bind != "0.0.0.0"
                            else "127.0.0.1")
            self.config.rpc_advertise = f"{adv_host}:{self.rpc.port}"
            # Chaos identity (ISSUE 12): the pool carries this server's
            # advertised address so named partition groups and
            # asymmetric net rules can tell its traffic apart — one
            # process hosting several servers enforces a partition on
            # every side it owns.
            self.pool.local_addr = self.config.rpc_advertise
        # Subprocess chaos arming: a follower child spawned into a
        # partition/flap scenario arms its own net plane from the env
        # (the parent can also drive it live over Chaos.SetNet).
        chaos_spec = (knobs.get_str("NOMAD_TPU_CHAOS_NET") or "").strip()
        if chaos_spec and not fault.net_armed():
            import json as _json

            try:
                fault.net_arm(_json.loads(chaos_spec))
            except (ValueError, KeyError) as e:
                self.logger.warning(
                    "ignoring malformed NOMAD_TPU_CHAOS_NET: %s", e)

        # Consensus (server.go:257 setupRaft): multi-server raft when
        # clustering is configured, else the single-voter WAL / in-memory
        # log (raftInmem dev path).
        multi = self.config.enable_rpc and (
            self.config.bootstrap_expect > 1 or bool(self.config.start_join)
            or self.config.force_multi_raft)
        if multi:
            raft_dir = (os.path.join(self.config.data_dir, "raft")
                        if self.config.data_dir else None)
            self.raft: RaftLog = MultiRaft(
                self.fsm, self.config.rpc_advertise, self.pool,
                data_dir=raft_dir, logger=self.logger.getChild("raft"))
        elif self.config.data_dir:
            self.raft = FileLog(self.fsm, self.config.data_dir)
        else:
            self.raft = InmemLog(self.fsm)

        self.raft.metrics = self.metrics

        if self.rpc is not None:
            from .endpoints import register_endpoints

            register_endpoints(self, self.rpc)
            if isinstance(self.raft, MultiRaft):
                self.rpc.raft_handler = self.raft.handle_message

        # Cluster event stream (event_broker.py): constructed always,
        # armed (attached to the state store + global registry) only via
        # NOMAD_TPU_EVENTS=1 or the first /v1/event/stream subscriber —
        # disarmed, every state write pays one attribute load + branch.
        # Relaxed index source: external events are clamped monotonic by
        # the broker anyway, and heartbeat-expiry publishes must not
        # queue on the raft lock behind the apply stream.
        self.event_broker = EventBroker(
            metrics=self.metrics,
            index_source=self.raft.applied_index_relaxed)
        self._events_enabled = False
        self._events_lock = threading.Lock()
        if knobs.get_bool("NOMAD_TPU_EVENTS"):
            self.enable_event_stream()

        self.plan_applier = PlanApplier(self.plan_queue, self.raft, self.logger,
                                        metrics=self.metrics,
                                        blocked_evals=self.blocked_evals)
        self.heartbeat = HeartbeatTimers(
            on_expire=self._heartbeat_expired,
            min_ttl=self.config.min_heartbeat_ttl,
            max_per_second=self.config.max_heartbeats_per_second,
            logger=self.logger,
            metrics=self.metrics,
            ttl_jitter=self.config.heartbeat_ttl_jitter)
        if self._events_enabled:
            self.heartbeat.event_broker = self.event_broker
        self.periodic = PeriodicDispatch(self._periodic_dispatch, self.logger)

        self.workers: List[Worker] = []
        self.follower_workers: List[Worker] = []
        self.leader_channel = None
        self._reaper_threads: List[threading.Thread] = []

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        """Boot: serve RPC, start raft + membership, start workers, and
        monitor leadership (server.go:250-284 setupRPC/setupRaft/setupSerf/
        setupWorkers + leader.go:28 monitorLeadership)."""
        if self.rpc is not None:
            self.rpc.start()
            self._merge_members([self._self_member()])
        # Every server keeps its own Vault token alive regardless of
        # leadership (vault.go:467 renewalLoop starts at construction).
        if self.vault.enabled and (self.config.vault or VaultConfig()).token:
            self.vault.start_renewal()
        if isinstance(self.raft, MultiRaft):
            self.raft.start()
            self._maybe_bootstrap()
        if self.rpc is not None and (self.config.start_join
                                     or self.config.wan_join):
            t = threading.Thread(target=self._join_loop, daemon=True,
                                 name="serf-join")
            t.start()
        t = threading.Thread(target=self._emit_metrics_loop, daemon=True,
                             name="metrics-emitter")
        t.start()
        for i in range(self.config.num_schedulers):
            if self.config.use_tpu_batch_worker:
                worker: Worker = BatchWorker(
                    self.eval_broker, self.plan_queue, self.raft,
                    blocked_evals=self.blocked_evals, logger=self.logger,
                    time_table=self.time_table,
                    metrics=self.metrics,
                    max_batch=self.config.batch_size,
                    mesh=self.config.device_mesh)
            else:
                worker = Worker(
                    self.eval_broker, self.plan_queue, self.raft,
                    schedulers=self.config.enabled_schedulers,
                    blocked_evals=self.blocked_evals, logger=self.logger,
                    time_table=self.time_table,
                    metrics=self.metrics)
            self.workers.append(worker)
        # Follower-read scheduling (ISSUE 10): one FollowerWorker pool
        # per multi-raft server.  They park while this server leads
        # (the local pool above owns the broker) and pull from the
        # leader over RPC otherwise, so no leadership-transition
        # choreography is needed — both pools exist, exactly one is
        # active.
        if (self.config.follower_scheduling and self.pool is not None
                and isinstance(self.raft, MultiRaft)
                and (self.config.follower_schedulers
                     or self.config.num_schedulers) > 0):
            from .follower_sched import FollowerWorker, LeaderChannel

            self.leader_channel = LeaderChannel(
                self.pool, self.leader_address,
                my_addr=self.config.rpc_advertise, metrics=self.metrics)
            n = self.config.follower_schedulers or self.config.num_schedulers
            for _ in range(n):
                self.follower_workers.append(FollowerWorker(
                    self.raft, self.leader_channel, self.is_leader,
                    logger=self.logger, metrics=self.metrics))
        self.raft.notify_leadership(self._leadership_changed)
        for worker in self.workers:
            worker.start()
        for worker in self.follower_workers:
            worker.start()

    # -- cluster event stream ----------------------------------------------

    def enable_event_stream(self) -> None:
        """Arm the event broker: attach it to the state store write path
        and the process-wide external-publisher registry.  Idempotent;
        stays armed for the server's lifetime so a subscriber that
        disconnects can resume against a ring that kept buffering."""
        with self._events_lock:
            if self._events_enabled:
                return
            self._events_enabled = True
            self.fsm.event_broker = self.event_broker
            self.fsm.state.event_broker = self.event_broker
            # Writes applied before arming were never buffered: raise
            # the broker's gap horizon so a stale resume errors with the
            # oldest index instead of silently replaying nothing.  Attach
            # BEFORE reading the horizon — applied_index() serializes on
            # the raft lock the FSM applies under, so any apply that
            # missed the just-attached broker is ≤ the index read here
            # (an apply that both published and landed ≤ horizon only
            # costs a false resume error, never a silent gap).
            self.event_broker.mark_armed(self.raft.applied_index())
            # Per-server publishers get this server's broker directly
            # (note_external is only for genuinely process-wide sources:
            # the breaker and the fault plane).  heartbeat may not exist
            # yet on the NOMAD_TPU_EVENTS=1 construction path; __init__
            # re-attaches it right after construction.
            self.eval_broker.event_broker = self.event_broker
            hb = getattr(self, "heartbeat", None)
            if hb is not None:
                hb.event_broker = self.event_broker
            event_stream.register(self.event_broker)

    def event_stream_subscribe(self, topics=None, from_index: int = 0,
                               replay_all: bool = False):
        """Subscribe to the cluster event stream (Event.Stream /
        /v1/event/stream).  Arms the broker on first use.  Raises
        event_broker.EventIndexError when ``from_index`` is below the
        ring's buffered horizon; ``replay_all`` is the no-gap-check
        backlog dump (whatever the ring still holds)."""
        self.enable_event_stream()
        return self.event_broker.subscribe(topics=topics,
                                           from_index=from_index,
                                           replay_all=replay_all)

    def shutdown(self) -> None:
        self._shutdown.set()
        self._leader = False
        blackbox.unregister_server(self)
        telemetry.unwatch_gc(self)
        event_stream.unregister(self.event_broker)
        self.event_broker.close()
        for worker in self.workers:
            worker.stop()
        for worker in self.follower_workers:
            worker.stop()
        self.plan_applier.stop()
        self.eval_broker.set_enabled(False)
        self.blocked_evals.set_enabled(False)
        self.plan_queue.set_enabled(False)
        self.periodic.set_enabled(False)
        self.heartbeat.set_enabled(False)
        self.vault.stop()
        self.raft.close()
        if self.rpc is not None:
            self.rpc.shutdown()
        if self.pool is not None:
            self.pool.close()

    # -- membership (serf-lite: nomad/serf.go over the RPC port) -----------

    def _self_member(self) -> Dict:
        return {"Name": self.config.node_name,
                "Addr": self.config.rpc_advertise,
                "Region": self.config.region,
                "Status": "alive",
                "StatusTime": self._status_time,
                "NonVoter": self.config.non_voting}

    def members(self) -> List[Dict]:
        """(serf.Members / nomad/serf.go peer table)."""
        with self._members_lock:
            return sorted(self._members.values(),
                          key=lambda m: (m.get("Region", ""), m["Name"]))

    def join(self, addresses: List[str]) -> int:
        """Operator-initiated join (agent_endpoint.go Join → serf.Join):
        dial each address's Serf.Join, merge the replies; returns how many
        answered.  Each dial gets two backed-off retries — Serf.Join is an
        idempotent membership merge, and `nomad server-join` should
        survive one transient dial failure."""
        from ..utils.backoff import Backoff, retry

        if self.pool is None:
            raise ValueError("RPC is not enabled")
        me = self._self_member()
        joined = 0
        for addr in addresses:
            try:
                reply = retry(
                    lambda a=addr: self.pool.call(a, "Serf.Join",
                                                  {"Member": me},
                                                  timeout=2.0),
                    retries=2, backoff=Backoff(base=0.1, max_delay=0.5))
                self._merge_members(reply.get("Members") or [])
                joined += 1
            except Exception as e:
                self.logger.warning("server: join %s failed: %s", addr, e)
        return joined

    def force_leave(self, name: str) -> bool:
        """Mark a member as left (serf.RemoveFailedNode /
        agent_endpoint.go ForceLeave) and gossip it: the record carries a
        bumped StatusTime so peers' merges keep 'left' over stale 'alive'
        views.  A same-region raft peer set is untouched (voter removal is
        a config change, not a gossip eviction)."""
        changed = False
        with self._members_lock:
            for key, m in list(self._members.items()):
                if m["Name"] == name:
                    m["Status"] = "left"
                    m["StatusTime"] = int(m.get("StatusTime", 1)) + 1
                    changed = True
            view = list(self._members.values())
        if changed and self.pool is not None:
            threading.Thread(target=self._push_members, args=(view,),
                             daemon=True).start()
        return changed

    def membership_join(self, member: Dict) -> Dict:
        """Handle a Serf.Join from a peer: merge, gossip the change, and
        return the full member list (serf.go:51 nodeJoin)."""
        self._merge_members([member])
        return {"Members": self.members()}

    def _merge_members(self, incoming: List[Dict]) -> None:
        """Merge member records; on change, push our view to peers (the
        gossip dissemination step) and re-check bootstrap
        (serf.go:91 maybeBootstrap)."""
        added = []
        with self._members_lock:
            for m in incoming:
                name = m.get("Name")
                if not name or not m.get("Addr"):
                    continue
                # Names are only unique within a region (serf WAN names
                # members "name.region"); key by both so two regions'
                # default-named servers cannot overwrite each other.
                key = (name, m.get("Region", ""))
                old = self._members.get(key)
                if old is None:
                    added.append(m)
                    self._members[key] = dict(m)
                    continue
                # Refutation (serf alive/suspect semantics): a 'left'
                # about OURSELVES while we are alive gets out-bid by
                # bumping our incarnation past it and re-gossiping.
                if (name == self.config.node_name
                        and m.get("Region", "") == self.config.region
                        and m.get("Status") != "alive"
                        and int(m.get("StatusTime", 1)) >= self._status_time):
                    self._status_time = int(m.get("StatusTime", 1)) + 1
                    refreshed = self._self_member()
                    self._members[key] = refreshed
                    added.append(refreshed)  # gossip the refutation
                    continue
                # Conflict resolution: the record with the newer
                # StatusTime wins, so a gossiped 'left' is not
                # resurrected by a peer's stale 'alive' view.
                if int(m.get("StatusTime", 1)) >= \
                        int(old.get("StatusTime", 1)):
                    if m.get("Status") != old.get("Status"):
                        added.append(m)  # status change gossips onward
                    self._members[key] = dict(m)
            view = list(self._members.values())
        if not added:
            return
        self.logger.info("server: membership now %d members (+%s)",
                         len(view), ",".join(m["Name"] for m in added))
        self._maybe_bootstrap()
        if self.pool is not None:
            threading.Thread(target=self._push_members, args=(view,),
                             daemon=True).start()

    def _push_members(self, view: List[Dict]) -> None:
        """Anti-entropy push: send every member we know to every peer.
        Receivers that learn nothing new do not re-push, so this
        terminates."""
        me = self.config.rpc_advertise
        for m in view:
            addr = m["Addr"]
            if addr == me:
                continue
            for peer in view:
                try:
                    self.pool.call(addr, "Serf.Join", {"Member": peer},
                                   timeout=1.0)
                except Exception:
                    break  # peer unreachable; heartbeat/rejoin recovers

    def _maybe_bootstrap(self) -> None:
        """Initial cluster formation + config growth (serf.go:91
        maybeBootstrap).

        Only a *seed* server (no start_join) may adopt the initial voter
        set from its gossip view, and only once bootstrap_expect members
        are alive.  A joining server waits to be added by the leader via a
        replicated CONFIG entry — self-assembling a quorum from a private
        member view could create a second, disjoint quorum (split-brain).
        After bootstrap, the leader proposes a config change whenever
        gossip surfaces members that are not yet voters (raft AddVoter)."""
        if not isinstance(self.raft, MultiRaft):
            return
        with self._members_lock:
            # WAN members of other regions are never raft voters
            # (serf.go: per-region raft, WAN gossip for federation only).
            # Non-voting members (non_voting_server) replicate but never
            # join the quorum configuration.
            addrs = [m["Addr"] for m in self._members.values()
                     if m.get("Region", self.config.region)
                     == self.config.region and not m.get("NonVoter")]
            learner_addrs = [m["Addr"] for m in self._members.values()
                             if m.get("Region", self.config.region)
                             == self.config.region and m.get("NonVoter")]
        if not self.raft._bootstrapped:
            if self.config.start_join or self.config.non_voting:
                return
            if len(addrs) >= self.config.bootstrap_expect:
                self.raft.bootstrap(addrs)
            return
        if self.raft.is_raft_leader():
            for addr in learner_addrs:
                self.raft.add_learner(addr)
            new = sorted(set(self.raft.peers) | set(addrs))
            if new != sorted(self.raft.peers):
                def _propose():
                    try:
                        self.raft.propose_config(new)
                    except Exception as e:
                        self.logger.warning(
                            "server: config change failed: %s", e)
                threading.Thread(target=_propose, daemon=True).start()

    def _join_loop(self) -> None:
        """Retry start_join addresses until each answers — indefinitely,
        with capped backoff, like the agent's retry_join: a cluster whose
        members boot far apart must still converge."""
        pending = list(self.config.start_join) + list(self.config.wan_join)
        me = self._self_member()
        delay = 0.25
        attempts = 0
        while not self._shutdown.is_set() and pending:
            still = []
            for addr in pending:
                try:
                    reply = self.pool.call(addr, "Serf.Join", {"Member": me},
                                           timeout=1.0)
                    self._merge_members(reply.get("Members") or [])
                except Exception:
                    still.append(addr)
            pending = still
            if pending:
                attempts += 1
                if attempts % 20 == 0:
                    self.logger.warning(
                        "server: still unable to join %s after %d attempts",
                        ",".join(pending), attempts)
                self._shutdown.wait(delay)
                delay = min(delay * 1.5, 5.0)

    def is_leader(self) -> bool:
        return self._leader

    @property
    def state(self):
        return self.fsm.state

    # -- chaos/audit surface (ISSUE 12) ------------------------------------

    def consistent_snapshot(self):
        """A copy-on-write state snapshot taken at a raft ENTRY
        boundary: the raft lock serializes with the applier (MultiRaft
        applies committed chunks under it), so a multi-write apply like
        APPLY_PLAN_RESULTS can never be observed half-landed.  The
        snapshot itself is O(1); everything expensive happens on the
        immutable copy afterwards."""
        lock = getattr(self.raft, "_l", None)
        if lock is not None:
            with lock:
                return self.state.snapshot()
        return self.state.snapshot()

    def fsm_fingerprint(self) -> Tuple[int, str]:
        """(committed-prefix index, state digest) for the safety
        auditor's cross-server check.  The index label is the
        snapshot's own latest write index — internally consistent with
        the hashed content by construction, and equal across servers
        that applied the same prefix (entries that never touch the
        store don't bump it on any server)."""
        snap = self.consistent_snapshot()
        return snap.latest_index(), snap.fingerprint()

    # -- leadership --------------------------------------------------------

    def _leadership_changed(self, leader: bool) -> None:
        if leader:
            self._establish_leadership()
        else:
            self._revoke_leadership()

    def _establish_leadership(self) -> None:
        """(leader.go:110 establishLeadership)."""
        self._leader = True
        self.eval_broker.set_enabled(True)
        self.plan_queue.set_enabled(True)
        # Follower-read fence floor (ISSUE 10): the previous leader's
        # per-job plan fences died with its PlanQueue, but election
        # safety guarantees every COMMITTED plan is ≤ our LOG's last
        # index right now (fence_index — NOT the applied index, which
        # the async FSM applier may still be draining toward).  Raising
        # the global floor makes every remote dequeue carry a fence ≥
        # this index, so a lagging follower replicates past all
        # pre-failover plans before scheduling — without it, a follower
        # could schedule a job off a snapshot missing that job's own
        # committed placements (the one staleness the applier's
        # capacity re-check cannot catch).
        self.plan_queue.note_applied("", self.raft.fence_index())
        self.blocked_evals.set_enabled(True)
        self.periodic.set_enabled(True)
        self.heartbeat.set_enabled(True)
        self.plan_applier.start()
        self._restore_tenancy()
        self._restore_evals()
        self._restore_periodic_dispatcher()
        self._start_reapers()
        # Vault activates with leadership (vault.go:290 SetActive): the
        # revocation queue is ours to drain now; on loss it clears.
        self.vault.set_active(True)
        self._restore_revoking_accessors()
        # Reconcile voters with members discovered while we were a
        # follower (leader.go establishes raft config on leadership).
        self._maybe_bootstrap()

    def _restore_revoking_accessors(self) -> None:
        """Revoke accessors whose allocation OR node is already terminal
        or gone — the previous leader may have died mid-revocation
        (leader.go:221-260 restoreRevokingAccessors checks both)."""
        if not self.vault.enabled:
            return
        stale = []
        for acc in self.state.vault_accessors(None):
            alloc = self.state.alloc_by_id(None, acc.alloc_id)
            if alloc is None or alloc.terminal_status():
                stale.append(acc)
                continue
            node = self.state.node_by_id(None, acc.node_id)
            if node is None or node.terminal_status():
                stale.append(acc)
        if stale:
            threading.Thread(target=self._revoke_accessors,
                             args=(stale,), daemon=True).start()

    def _revoke_leadership(self) -> None:
        self._leader = False
        self.vault.set_active(False)
        self.eval_broker.set_enabled(False)
        self.plan_queue.set_enabled(False)
        self.blocked_evals.set_enabled(False)
        self.periodic.set_enabled(False)
        self.heartbeat.set_enabled(False)
        self.plan_applier.stop()

    def _restore_tenancy(self) -> None:
        """Reseed the tenancy plane from restored state on leadership:
        fairness/rate policy from committed Namespace rows, and a
        conservative quota-ledger rebuild from every non-terminal
        eval's job (over-reserving is safe — extra 429s near the limit;
        under-reserving could let a failover breach quota)."""
        for ns in self.state.namespaces(None):
            self._fsm_namespace_updated(ns.name, ns)
        entries = []
        unit_entries = []
        seen = set()
        self._refresh_capacity()
        cap, nodes = self._cluster_capacity, self._cluster_nodes
        for ev in self.state.evals(None):
            if ev.terminal_status() or ev.job_id in seen:
                continue
            seen.add(ev.job_id)
            job = self.state.job_by_id(None, ev.job_id)
            if job is None:
                continue
            ns = job.namespace or "default"
            count = sum(tg.count for tg in job.task_groups)
            entries.append((job.id, ns, count))
            if nodes > 0:
                unit_entries.append(
                    (job.id, ns,
                     self._node_units(_job_usage_vec(job), cap, nodes)))
        self.quota_ledger.rebuild(entries)
        self.node_units_ledger.rebuild(unit_entries)
        self.eval_broker.note_usage_changed(self.state.namespace_usage())

    def _restore_evals(self) -> None:
        """Re-enqueue pending and re-block blocked evals from state
        (leader.go:195 restoreEvals)."""
        for ev in self.state.evals(None):
            if ev.should_enqueue():
                self.eval_broker.enqueue(ev)
            elif ev.should_block():
                self.blocked_evals.block(ev)

    def _restore_periodic_dispatcher(self) -> None:
        """Track periodic jobs + catch up missed launches (leader.go:150)."""
        now = time.time()
        for job in self.state.jobs_by_periodic(None, True):
            self.periodic.add(job)
            launch = self.state.periodic_launch_by_id(None, job.id)
            last = launch.launch if launch else 0.0
            nxt = job.periodic.next(last)
            if last and 0 < nxt <= now:
                self.periodic.force_run(job.id)

    def _start_reapers(self) -> None:
        """Duplicate-blocked-eval reaper, failed-eval unblock, periodic GC
        core evals (leader.go:157-193)."""

        def dup_reaper():
            while self._leader and not self._shutdown.is_set():
                dups = self.blocked_evals.get_duplicates(timeout=0.5)
                if not dups:
                    continue
                cancelled = []
                for dup in dups:
                    ev = dup.copy()
                    ev.status = s.EVAL_STATUS_CANCELLED
                    ev.status_description = (
                        f"existing blocked evaluation exists for job {ev.job_id!r}")
                    cancelled.append(ev)
                self.raft.apply(MessageType.EVAL_UPDATE, {"evals": cancelled})

        def shed_reaper():
            # Broker-coalesced duplicates: the broker absorbed their
            # trigger into the kept eval; cancel them through the log so
            # eval-status tells the story (and they never look pending).
            while self._leader and not self._shutdown.is_set():
                shed = self.eval_broker.get_shed(timeout=0.5)
                if not shed:
                    continue
                cancelled = []
                for dup in shed:
                    ev = dup.copy()
                    ev.status = s.EVAL_STATUS_CANCELLED
                    ev.status_description = (
                        f"coalesced with a pending evaluation for job "
                        f"{ev.job_id!r} (broker admission control)")
                    cancelled.append(ev)
                try:
                    self.raft.apply(MessageType.EVAL_UPDATE,
                                    {"evals": cancelled})
                except NotLeaderError:
                    return

        def failed_unblocker():
            while self._leader and not self._shutdown.is_set():
                self._shutdown.wait(self.config.failed_eval_unblock_interval)
                if self._leader and not self._shutdown.is_set():
                    self.blocked_evals.unblock_failed()

        def gc_scheduler():
            while self._leader and not self._shutdown.is_set():
                self._shutdown.wait(self.config.eval_gc_interval)
                if not (self._leader and not self._shutdown.is_set()):
                    return
                for core_job in (s.CORE_JOB_EVAL_GC, s.CORE_JOB_JOB_GC,
                                 s.CORE_JOB_NODE_GC):
                    self._create_core_eval(core_job)

        def vault_revoke_daemon():
            # Retry failed revocations until the token TTLs out
            # (vault.go:1104 revokeDaemon; 5-min cadence there, shorter
            # here so tests observe it).
            while self._leader and not self._shutdown.is_set():
                self._shutdown.wait(self.config.vault_revoke_interval)
                if not (self._leader and not self._shutdown.is_set()):
                    return
                try:
                    done = self.vault.tick_revocations()
                except Exception:
                    self.logger.exception("vault revoke daemon")
                    continue
                if done:
                    self._deregister_accessor_rows(done)

        for target in (dup_reaper, shed_reaper, failed_unblocker,
                       gc_scheduler, vault_revoke_daemon):
            t = threading.Thread(target=target, daemon=True)
            t.start()
            self._reaper_threads.append(t)

    def _emit_metrics_loop(self, interval: float = 1.0) -> None:
        """Periodic gauge emission (server.go:292-305 EmitStats of the
        broker, plan queue, blocked evals, and heartbeat timers; metric
        names per the reference telemetry doc)."""
        tenant_top = knobs.get_int("NOMAD_TPU_TENANCY_METRICS_TOP", 10)
        while not self._shutdown.is_set():
            try:
                self._feed_tenancy(tenant_top)
                b = self.eval_broker.stats()
                self.metrics.set_gauge("broker.total_ready",
                                       b.get("total_ready", 0))
                self.metrics.set_gauge("broker.total_unacked",
                                       b.get("total_unacked", 0))
                self.metrics.set_gauge("broker.total_waiting",
                                       b.get("total_waiting", 0))
                self.metrics.set_gauge("broker.pending",
                                       self.eval_broker.pending_count())
                bl = self.blocked_evals.stats()
                self.metrics.set_gauge("blocked_evals.total_blocked",
                                       bl.get("total_blocked", 0))
                self.metrics.set_gauge("blocked_evals.total_escaped",
                                       bl.get("total_escaped", 0))
                self.metrics.set_gauge("plan.queue_depth",
                                       self.plan_queue.depth())
                self.metrics.set_gauge("heartbeat.active",
                                       self.heartbeat.active())
                self.metrics.set_gauge("raft.applied_index",
                                       self.raft.applied_index())
                if self.leader_channel is not None:
                    self.metrics.set_gauge(
                        "plan.forward.inflight",
                        self.leader_channel.inflight())
                if isinstance(self.raft, MultiRaft) and not self._leader:
                    # Replication debt of this follower's FSM vs the
                    # commit horizon the leader has shown it (a lower
                    # bound on true leader lag; the per-dequeue
                    # follower.snapshot_lag samples carry the exact
                    # leader-applied delta).
                    self.metrics.set_gauge(
                        "follower.snapshot_lag",
                        max(0, self.raft.commit_index
                            - self.raft.applied_index_relaxed()))
                if self._events_enabled:
                    es = self.event_broker.stats()
                    self.metrics.set_gauge("events.ring_depth",
                                           es["depth"])
                    self.metrics.set_gauge("events.subscribers",
                                           es["subscribers"])
                    self.metrics.set_gauge("events.dropped",
                                           es["evicted"])
                    self.metrics.set_gauge("events.max_subscriber_lag",
                                           es["max_subscriber_lag"])
                # Breaker state must survive interval rolls while evals
                # are quiet — the open-and-idle window is exactly the
                # one worth observing.  sys.modules, not an import: the
                # ops package drags in jax, which an oracle-only server
                # never needs.
                brk_mod = sys.modules.get("nomad_tpu.ops.breaker")
                if brk_mod is not None:
                    self.metrics.set_gauge(
                        "breaker.state",
                        brk_mod.STATE_CODE.get(brk_mod.BREAKER.state, 0))
                    self.metrics.set_gauge("breaker.trips",
                                           brk_mod.BREAKER.trips)
                prof = contprof.PROFILER
                if prof is not None:
                    for sub, share in prof.shares(30.0).items():
                        self.metrics.set_gauge(f"cpu.{sub}", share)
                    gil = prof.gil_pressure_ms()
                    self.metrics.set_gauge("runtime.gil_delay_p50_ms",
                                           gil["p50"])
                    self.metrics.set_gauge("runtime.gil_delay_p99_ms",
                                           gil["p99"])
                self._watch_plan_slo()
            except Exception:  # never kill the emitter
                self.logger.exception("metrics emit failed")
            self._shutdown.wait(interval)

    def _watch_plan_slo(self) -> None:
        """Plan-apply p99 SLO watch: when NOMAD_TPU_BLACKBOX_SLO_PLAN_P99_MS
        is set (>0) and the current interval's plan.apply p99 breaches
        it, auto-capture a flight-recorder bundle.  note_trigger's
        per-reason rate limit keeps a sustained breach from flooding."""
        slo_ms = knobs.get_float("NOMAD_TPU_BLACKBOX_SLO_PLAN_P99_MS", 0.0)
        if not slo_ms or slo_ms <= 0 or not blackbox.enabled():
            return
        latest = self.metrics.sink.latest()
        summ = latest.get("Samples", {}).get("nomad.plan.apply")
        if not summ or not summ.get("count"):
            return
        p99 = summ.get("p99", 0.0)
        if p99 > slo_ms:
            blackbox.note_trigger(
                "slo.plan_apply_p99",
                {"P99Ms": round(p99, 3), "SloMs": slo_ms,
                 "Count": summ.get("count", 0),
                 "Node": self.config.node_name})

    def _feed_tenancy(self, tenant_top: int) -> None:
        """Per-tick tenancy upkeep, piggybacked on the metrics cadence:
        drain the state store's dirty per-ns usage fold into the DRF
        scorer (O(changed tenants)), refresh the cluster-capacity
        mirror when the nodes table moved, and emit the busiest
        tenants' ``tenant.*`` gauges (knob-capped — a 1k-tenant fleet
        must not mint 4k gauge keys)."""
        dirty = self.state.drain_ns_dirty()
        if dirty:
            usage = self.state.namespace_usage()
            self.eval_broker.note_usage_changed(
                {ns: usage.get(ns, (0, 0, 0, 0, 0)) for ns in dirty})
        self._refresh_capacity()
        if tenant_top <= 0:
            return
        counters = self.eval_broker.tenant_counters()
        busiest = sorted(counters.items(),
                         key=lambda kv: (-kv[1][0], kv[0]))[:tenant_top]
        cap, nodes = self._cluster_capacity, self._cluster_nodes
        for ns, (pending, dequeued, shed, rejects) in busiest:
            self.metrics.set_gauge(f"tenant.pending.{ns}", pending)
            self.metrics.set_gauge(f"tenant.dequeued.{ns}", dequeued)
            self.metrics.set_gauge(f"tenant.shed.{ns}", shed)
            self.metrics.set_gauge(f"tenant.rejects.{ns}", rejects)
            if nodes > 0:
                self.metrics.set_gauge(
                    f"tenant.node_units.{ns}",
                    self._node_units(
                        self.state.namespace_usage_one(ns)[:4], cap, nodes))

    def _refresh_capacity(self) -> None:
        """Keep the cluster-capacity mirror current: recompute the
        4-vector total + non-terminal node count only when the nodes
        table index moved (O(1) otherwise), and push it into the
        broker's DRF scorer.  Shared by the metrics tick and the
        node-units admission gate."""
        node_index = self.state.table_index("nodes")
        if node_index == self._capacity_node_index:
            return
        self._capacity_node_index = node_index
        cap = [0, 0, 0, 0]
        nodes = 0
        for node in self.state.nodes(None):
            if node.terminal_status():
                continue
            nodes += 1
            res = node.resources
            if res is None:
                continue
            cap[0] += res.cpu
            cap[1] += res.memory_mb
            cap[2] += res.disk_mb
            cap[3] += res.iops
        self._cluster_capacity = tuple(cap)
        self._cluster_nodes = nodes
        self.eval_broker.set_cluster_capacity(self._cluster_capacity)

    @staticmethod
    def _node_units(usage: Tuple[int, int, int, int],
                    cap: Tuple[int, int, int, int], nodes: int) -> float:
        """Nodes-worth of dominant-resource usage (the quota_node_units
        basis, structs.Namespace): max over dimensions of usage/capacity,
        scaled by the node count — 'this tenant occupies X nodes' even
        when its footprint is spread thin across many."""
        share = max((u / c) for u, c in zip(usage, cap) if c > 0) \
            if any(cap) else 0.0
        return share * nodes

    def _create_core_eval(self, core_job: str) -> None:
        ev = s.Evaluation(
            id=s.generate_uuid(), priority=s.JOB_MAX_PRIORITY,
            type=s.JOB_TYPE_CORE, triggered_by=s.EVAL_TRIGGER_SCHEDULED,
            job_id=core_job, status=s.EVAL_STATUS_PENDING)
        self.raft.apply(MessageType.EVAL_UPDATE, {"evals": [ev]})

    # -- FSM hooks (leader side) ------------------------------------------

    def _fsm_eval_updated(self, ev: s.Evaluation) -> None:
        if not self._leader:
            return
        self.time_table.witness(self.raft.applied_index())
        if ev.terminal_status():
            # The job's driving eval is done: its placements are live in
            # the per-ns usage fold (or never will be), so the admission
            # reservations made for it have served their purpose.
            self.quota_ledger.release(ev.job_id)
            self.node_units_ledger.release(ev.job_id)
        if ev.should_enqueue():
            self.eval_broker.enqueue(ev)
        elif ev.should_block():
            self.blocked_evals.block(ev)
        elif (ev.status == s.EVAL_STATUS_COMPLETE
              and not ev.failed_tg_allocs):
            # Successful eval → untrack any blocked eval for the job
            # (fsm.go applyUpdateEval).
            self.blocked_evals.untrack(ev.job_id)

    def _fsm_unblock(self, computed_class: str, index: int) -> None:
        if self._leader:
            self.blocked_evals.unblock(computed_class, index)

    def _fsm_job_registered(self, job: s.Job) -> None:
        if self._leader and job.is_periodic() and not job.stopped():
            self.periodic.add(job)

    def _fsm_job_deregistered(self, job_id: str) -> None:
        if self._leader:
            self.periodic.remove(job_id)
            self.quota_ledger.release(job_id)
            self.node_units_ledger.release(job_id)

    def _fsm_namespace_updated(self, name: str,
                               ns: Optional[s.Namespace]) -> None:
        """Committed Namespace row changed: refresh the policy mirrors.
        Runs on every server (the rate limiter guards each HTTP front
        door; fairness weights matter only while leading but are cheap
        to keep warm)."""
        if ns is None:
            self.eval_broker.drop_namespace_policy(name)
            self.api_limiter.drop(name)
            return
        self.eval_broker.set_namespace_policy(
            name, ns.dequeue_weight, ns.objective)
        self.api_limiter.configure(name, ns.api_rate, float(ns.api_burst))

    def _fsm_alloc_terminal(self, alloc_id: str) -> None:
        """Terminal alloc ⇒ revoke its derived Vault tokens
        (vault.go RevokeTokens on alloc terminal)."""
        if not self._leader or not self.vault.enabled:
            return
        accessors = self.state.vault_accessors_by_alloc(None, alloc_id)
        if accessors:
            threading.Thread(target=self._revoke_accessors,
                             args=(accessors,), daemon=True).start()

    def _revoke_accessors(self, accessors) -> None:
        done = self.vault.revoke_accessors([a.accessor for a in accessors])
        # Failed revocations queue for retry until the token TTLs out
        # (vault.go storeForRevocation; drained by vault_revoke_daemon).
        failed = [a for a in accessors if a.accessor not in done]
        if failed:
            self.vault.store_for_revocation([a.accessor for a in failed])
        if not done:
            return
        to_remove = [a for a in accessors if a.accessor in done]
        try:
            self.raft.apply(MessageType.VAULT_ACCESSOR_DEREGISTER,
                            {"accessors": to_remove})
        except NotLeaderError:
            pass  # new leader's restore pass re-revokes (idempotent)

    def _deregister_accessor_rows(self, accessor_ids) -> None:
        """Drop accessor rows for ids revoked by the retry daemon."""
        wanted = set(accessor_ids)
        rows = [a for a in self.state.vault_accessors(None)
                if a.accessor in wanted]
        if not rows:
            return
        try:
            self.raft.apply(MessageType.VAULT_ACCESSOR_DEREGISTER,
                            {"accessors": rows})
        except NotLeaderError:
            pass

    # -- heartbeat / periodic callbacks ------------------------------------

    def _heartbeat_expired(self, node_id: str) -> None:
        """Missed heartbeat ⇒ node down ⇒ node evals (heartbeat.go:86)."""
        try:
            self.node_update_status(node_id, s.NODE_STATUS_DOWN)
        except KeyError:
            pass

    def _periodic_dispatch(self, parent: s.Job, derived: s.Job,
                           launch_time: float) -> None:
        """Register the derived child job + record the launch
        (periodic.go:435 createEval)."""
        if parent.periodic and parent.periodic.prohibit_overlap:
            # A previous launch is still active if any derived child job
            # (id prefix "<parent>/periodic-") has a live eval or alloc
            # (periodic.go shouldDispatch via RunningChildren).
            from .periodic import PERIODIC_LAUNCH_SUFFIX
            prefix = parent.id + PERIODIC_LAUNCH_SUFFIX
            for child in self.state.jobs_by_id_prefix(None, prefix):
                if any(not ev.terminal_status()
                       for ev in self.state.evals_by_job(None, child.id)):
                    return
                if any(not a.terminal_status()
                       for a in self.state.allocs_by_job(None, child.id)):
                    return
        # Explicit own region: a derived child must never region-route
        # away from its parent (periodic.go children are region-local).
        self.job_register(derived, region=self.config.region)
        self.raft.apply(MessageType.PERIODIC_LAUNCH_UPSERT,
                        {"job_id": parent.id, "launch": launch_time})

    # ======================================================================
    # RPC endpoint surface (reference: nomad/*_endpoint.go)
    # ======================================================================

    def regions(self) -> List[str]:
        """Distinct regions known through membership (region_endpoint.go
        List over serf WAN members)."""
        out = {self.config.region}
        for m in self.members():
            r = m.get("Region")
            if r:
                out.add(r)
        return sorted(out)

    def region_info(self) -> List[Dict]:
        """Per-region detail rows for the /v1/regions?detail surface:
        name, alive server count, and best-known leader address.  The
        home region answers from local raft state; remote leaders are a
        best-effort bounded Status.Leader probe against one alive member
        ("" when the region is unreachable — this endpoint must never
        hang on a dark region)."""
        by_region: Dict[str, List[Dict]] = {}
        for m in list(self.members()) + [self._self_member()]:
            r = m.get("Region", "")
            if r and m.get("Status", "alive") == "alive":
                rows = by_region.setdefault(r, [])
                if not any(x.get("Name") == m.get("Name") for x in rows):
                    rows.append(m)
        out = []
        probe_timeout = knobs.get_float("NOMAD_TPU_REGION_PROBE_TIMEOUT")
        for region in sorted(by_region):
            members = by_region[region]
            leader = ""
            if region == self.config.region:
                leader = self.leader_address()
            elif self.pool is not None:
                for m in members:
                    try:
                        reply = self.pool.call(
                            m["Addr"], "Status.Leader", {},
                            timeout=probe_timeout)
                        # Status.Leader replies with the bare address
                        # string (status_endpoint.go), not a dict.
                        leader = (reply if isinstance(reply, str)
                                  else (reply or {}).get("Leader", ""))
                        break
                    except Exception:
                        continue
            out.append({"Name": region, "Servers": len(members),
                        "Leader": leader})
        return out

    def _forward_region(self, region: str, wire_method: str, body: Dict):
        """Route a request to any alive server of another region
        (nomad/rpc.go:263 forwardRegion over the WAN member table).  Does
        NOT consume the one leader-forward hop: the remote server may
        still forward to its own region's leader.

        Partition tolerance contract: a down region degrades to a typed
        ``NoPathToRegion`` carrying a retry_after hint — never a hang and
        never a silent generic error.  The walk makes a bounded number of
        rounds over the region's known servers with the shared jittered
        Backoff between rounds; within a round only DIAL failures rotate
        (the request was never sent, so trying the next server cannot
        double-apply).  The dials ride ``self.pool``, so the per-address
        dial-backoff gate armed by raft replication and leader forwarding
        is shared with the federation path: a region that just went dark
        fails fast locally instead of re-paying connect timeouts."""
        from .rpc import DialError, NoPathToRegion
        from ..utils.backoff import Backoff

        if getattr(self._fwd_ctx, "region_hop", False):
            # This request already took its region hop; stale member
            # records must not bounce it between regions.
            raise ValueError(
                f"request for region {region!r} arrived at "
                f"{self.config.region!r} after a region forward")
        candidates = [m for m in self.members()
                      if m.get("Region") == region
                      and m.get("Status", "alive") == "alive"]
        if not candidates or self.pool is None:
            raise ValueError(f"no servers known in region {region!r}")
        body = dict(body)
        body["Region"] = region
        body["__region_hop__"] = True
        rounds = max(1, knobs.get_int("NOMAD_TPU_REGION_DIAL_ROUNDS"))
        bo = Backoff(base=0.05, max_delay=2.0)
        last: Optional[Exception] = None
        for round_no in range(rounds):
            if round_no and self._shutdown.wait(bo.next_delay()):
                break
            for m in candidates:
                try:
                    return self.pool.call(m["Addr"], wire_method, body)
                except DialError as e:
                    # Only DIAL failures rotate — the request was never
                    # sent.  A post-send transport error may have applied
                    # remotely; retrying could double-apply a write, and
                    # application errors must propagate as-is.
                    last = e
        retry_after = min(knobs.get_float("NOMAD_TPU_REGION_RETRY_AFTER_CAP"),
                          0.5 + 0.5 * rounds)
        raise NoPathToRegion(region, retry_after, rounds=rounds,
                             detail=str(last) if last else "")

    def _forward(self, wire_method: str, body: Dict):
        """Re-issue a write that hit NotLeaderError as a wire RPC to the
        leader (nomad/rpc.go:178 forward) — this is what lets the HTTP API
        of a follower serve writes.  Raises NotLeaderError when there is no
        known leader, no wire transport, or the request already took its
        one forwarding hop (the reference's Forwarded flag: a request must
        not chain through a trail of stale leader pointers)."""
        leader = self.leader_address()
        if (self.pool is None or not leader
                or leader == self.config.rpc_advertise
                or getattr(self._fwd_ctx, "active", False)):
            raise NotLeaderError(leader)
        body = dict(body)
        body["__forwarded__"] = True
        return self.pool.call(leader, wire_method, body)

    # -- Job ---------------------------------------------------------------

    def _check_tenant_admission(self, job: s.Job) -> None:
        """Per-tenant front-door gate, leader-side, BEFORE the raft
        write (composes with the global broker cap inside
        check_admission): the namespace's pending-eval quota, then an
        atomic check+reserve of its live-alloc quota in the ledger.
        Rejections raise BrokerLimitError → 429 + Retry-After; a
        bypass-priority submission (core GC, repair) skips both."""
        ns = job.namespace or "default"
        row = self.state.namespace_by_name(None, ns)
        self.eval_broker.check_admission(
            job.priority, namespace=ns,
            ns_max_pending=row.max_pending_evals if row is not None else 0)
        if row is None or job.priority >= self.eval_broker.bypass_priority:
            return
        count = sum(tg.count for tg in job.task_groups)
        quota = row.max_live_allocs
        if quota > 0:
            live = self.state.namespace_usage_one(ns)[4]
            if not self.quota_ledger.check_and_reserve(
                    ns, job.id, count, live, quota):
                self.eval_broker.note_quota_reject(ns)
                asked = live + self.quota_ledger.reserved(ns) + count
                retry_after = min(5.0, 0.2 + 0.3 * (asked / quota))
                raise BrokerLimitError(retry_after, asked, quota,
                                       namespace=ns)
        units_quota = row.quota_node_units
        if units_quota > 0:
            # Node-units gate (ROADMAP item 3's open item): the tenant's
            # dominant-resource share of the cluster, in nodes-worth,
            # must stay under quota_node_units counting this job's ask.
            self._refresh_capacity()
            cap, nodes = self._cluster_capacity, self._cluster_nodes
            if nodes > 0:
                used = self._node_units(
                    self.state.namespace_usage_one(ns)[:4], cap, nodes)
                ask = self._node_units(_job_usage_vec(job), cap, nodes)
                if not self.node_units_ledger.check_and_reserve(
                        ns, job.id, ask, used, units_quota):
                    # Roll back the alloc-count reservation made above:
                    # this registration is rejected, so nothing will
                    # ever release it otherwise.
                    self.quota_ledger.release(job.id)
                    self.eval_broker.note_quota_reject(ns)
                    asked = used + self.node_units_ledger.reserved(ns) + ask
                    retry_after = min(
                        5.0, 0.2 + 0.3 * (asked / units_quota))
                    raise BrokerLimitError(
                        retry_after, math.ceil(asked),
                        math.ceil(units_quota), namespace=ns)

    def job_register(self, job: s.Job, region: str = "") -> Tuple[int, str]:
        """(job_endpoint.go:47 Register): validate → log JobRegister → eval
        unless periodic/parameterized.  Returns (modify_index, eval_id).

        A request whose effective region (explicit arg, else Job.Region)
        differs from this server's routes to that region
        (rpc.go:263 forwardRegion).  An EXPLICIT region always routes (and
        errors if unknown); a job-file region only routes when that region
        is actually federated — otherwise it registers locally, so a
        default-region job file still works on a renamed cluster."""
        target = region or job.region
        if target and target != self.config.region and (
                region or target in self.regions()):
            reply = self._forward_region(target, "Job.Register",
                                         {"Job": job})
            return reply["Index"], reply["EvalID"]
        job = job.copy()
        job.canonicalize()
        problems = job.validate()
        if problems:
            raise ValueError("job validation failed: " + "; ".join(problems))

        # Admission control at the front door (429-style NACK): reject
        # BEFORE the raft write while the broker is saturated — once the
        # job + eval are persisted there is nothing left to shed.  Only
        # evals-to-be are gated (periodic/parameterized registrations
        # enqueue nothing).
        if self._leader and not job.is_periodic() \
                and not job.is_parameterized():
            self._check_tenant_admission(job)

        # The two raft applies (job, then its eval), timed as one:
        # sample ``job.register`` always, a span of the same two stamps
        # when the tracer is armed (tagged with the eval id, so the
        # eval's timeline starts here, under the HTTP request).
        tr = tracing.TRACER
        t0 = tracing.now()
        if tr is None:
            out = self._job_register_apply(job, None)
            t1 = tracing.now()
        else:
            with tr.span("job.register", start=t0, job_id=job.id) as sp:
                out = self._job_register_apply(job, sp)
            t1 = sp.end
        self.metrics.add_sample("job.register", (t1 - t0) * 1000.0)
        return out

    def _job_register_apply(self, job: s.Job, sp) -> Tuple[int, str]:
        try:
            _, index = self.raft.apply(MessageType.JOB_REGISTER, {"job": job})
        except NotLeaderError:
            reply = self._forward("Job.Register", {"Job": job})
            return reply["Index"], reply["EvalID"]

        eval_id = ""
        if not job.is_periodic() and not job.is_parameterized():
            ev = s.Evaluation(
                id=s.generate_uuid(),
                priority=job.priority,
                type=job.type,
                namespace=job.namespace,
                triggered_by=s.EVAL_TRIGGER_JOB_REGISTER,
                job_id=job.id,
                job_modify_index=index,
                status=s.EVAL_STATUS_PENDING,
            )
            # Open the eval.e2e umbrella (submit → broker ack) before
            # the eval write so the span covers enqueue + queue wait.
            if sp is not None:
                sp.set(eval_id=ev.id)
                tracing.mark(ev.id, job_id=job.id, submit="job_register",
                             priority=job.priority,
                             namespace=job.namespace)
            _, eval_index = self.raft.apply(MessageType.EVAL_UPDATE, {"evals": [ev]})
            eval_id = ev.id
        return index, eval_id

    def job_deregister(self, job_id: str, purge: bool = True,
                       region: str = "") -> Tuple[int, str]:
        """(job_endpoint.go Deregister)."""
        if region and region != self.config.region:
            reply = self._forward_region(region, "Job.Deregister",
                                         {"JobID": job_id, "Purge": purge})
            return reply["Index"], reply["EvalID"]
        job = self.state.job_by_id(None, job_id)
        if job is None:
            raise KeyError(f"job not found: {job_id}")
        try:
            _, index = self.raft.apply(MessageType.JOB_DEREGISTER,
                                       {"job_id": job_id, "purge": purge})
        except NotLeaderError:
            reply = self._forward("Job.Deregister",
                                  {"JobID": job_id, "Purge": purge})
            return reply["Index"], reply["EvalID"]
        eval_id = ""
        if not job.is_periodic() and not job.is_parameterized():
            ev = s.Evaluation(
                id=s.generate_uuid(), priority=job.priority, type=job.type,
                namespace=job.namespace,
                triggered_by=s.EVAL_TRIGGER_JOB_DEREGISTER, job_id=job_id,
                job_modify_index=index, status=s.EVAL_STATUS_PENDING)
            self.raft.apply(MessageType.EVAL_UPDATE, {"evals": [ev]})
            eval_id = ev.id
        return index, eval_id

    def job_list(self, prefix: str = "", region: str = "",
                 min_index: int = 0,
                 max_wait: float = 0.0) -> Tuple[List[s.Job], int]:
        """Region-routed job listing (reads forward like writes —
        rpc.go:178 forwards every RPC, reads included).  Blocking-query
        semantics run at the OWNING region (min_index/max_wait travel
        with the forward, rpc.go:340 blockingRPC).  Returns (jobs, index)."""
        if region and region != self.config.region:
            from ..api.codec import ensure
            reply = self._forward_region(
                region, "Job.List",
                {"Prefix": prefix, "MinQueryIndex": min_index,
                 "MaxQueryTime": max_wait})
            return ([ensure(s.Job, j) for j in reply["Jobs"] or []],
                    int(reply.get("Index", 0)))
        self._block_on_table("jobs", min_index, max_wait)
        jobs = (self.state.jobs_by_id_prefix(None, prefix) if prefix
                else self.state.jobs(None))
        return jobs, self.state.table_index("jobs")

    def _block_on_table(self, table: str, min_index: int,
                        max_wait: float) -> None:
        """Server-side long-poll on a state table (rpc.go:340
        blockingRPC)."""
        if min_index <= 0 or max_wait <= 0:
            return
        from ..state.state_store import WatchSet
        deadline = time.time() + min(max_wait, 300.0)
        while self.state.table_index(table) <= min_index:
            remaining = deadline - time.time()
            if remaining <= 0:
                return
            ws = WatchSet()
            # register interest, then wait for the next write
            getattr(self.state, "jobs")(ws)
            ws.watch(timeout=min(remaining, 1.0))

    def job_get(self, job_id: str, region: str = "",
                min_index: int = 0,
                max_wait: float = 0.0) -> Optional[s.Job]:
        if region and region != self.config.region:
            from ..api.codec import ensure
            reply = self._forward_region(
                region, "Job.Get",
                {"JobID": job_id, "MinQueryIndex": min_index,
                 "MaxQueryTime": max_wait})
            data = reply.get("Job")
            return ensure(s.Job, data) if data else None
        self._block_on_table("jobs", min_index, max_wait)
        return self.state.job_by_id(None, job_id)

    def job_summary(self, job_id: str) -> Optional[s.JobSummary]:
        return self.state.job_summary_by_id(None, job_id)

    def job_allocations(self, job_id: str, all_allocs: bool = False) -> List[s.Allocation]:
        return self.state.allocs_by_job(None, job_id, all_allocs)

    def job_evaluations(self, job_id: str) -> List[s.Evaluation]:
        return self.state.evals_by_job(None, job_id)

    def job_plan(self, job: s.Job, diff: bool = True) -> s.JobPlanResponse:
        """Dry-run scheduling (job_endpoint.go:~490 Plan): run the scheduler
        synchronously against a snapshot with a no-op planner, returning the
        annotated job diff + placement forensics (nothing is committed)."""
        from ..scheduler import Harness, new_scheduler
        from ..scheduler.annotate import annotate
        from ..structs.diff import job_diff

        old_job = self.state.job_by_id(None, job.id)
        job = job.copy()
        job.canonicalize()
        snap = self.state.snapshot()
        index = self.raft.applied_index() + 1
        snap.upsert_job(index, job)

        harness = Harness(snap)
        harness._next_index = index + 1
        ev = s.Evaluation(
            id=s.generate_uuid(), priority=job.priority, type=job.type,
            triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
            job_modify_index=index, status=s.EVAL_STATUS_PENDING,
            annotate_plan=True)
        sched = new_scheduler(job.type, self.logger, snap.snapshot(), harness)
        sched.process(ev)
        plan = harness.plans[0] if harness.plans else ev.make_plan(job)

        # The scheduler records placement forensics on a *copy* of the eval
        # handed to Planner.UpdateEval (scheduler/util.go setStatus) — read
        # the updated eval from the harness, like job_endpoint.go Plan does.
        updated = next((e for e in reversed(harness.evals) if e.id == ev.id), ev)
        resp = s.JobPlanResponse(
            annotations=plan.annotations,
            failed_tg_allocs=dict(updated.failed_tg_allocs),
            job_modify_index=old_job.job_modify_index if old_job else 0,
            created_evals=list(harness.create_evals))
        if diff:
            resp.diff = job_diff(old_job, job)
            annotate(resp.diff, plan.annotations)
        if job.is_periodic():
            resp.next_periodic_launch = job.periodic.next(s.now())
        return resp

    def periodic_force(self, job_id: str) -> Optional[s.Job]:
        if not self._leader:
            reply = self._forward("Periodic.Force", {"JobID": job_id})
            child_id = reply.get("ChildJobID", "")
            if not child_id:
                return None
            child = self.state.job_by_id(None, child_id)
            return child or s.Job(id=child_id, name=child_id)
        return self.periodic.force_run(job_id)

    def job_evaluate(self, job_id: str) -> Tuple[int, str]:
        """Force a new evaluation for an existing job
        (job_endpoint.go Evaluate)."""
        job = self.state.job_by_id(None, job_id)
        if job is None:
            raise KeyError(f"job not found: {job_id}")
        if job.is_periodic():
            raise ValueError("can't evaluate periodic job")
        if job.is_parameterized():
            raise ValueError("can't evaluate parameterized job")
        if self._leader:
            self._check_tenant_admission(job)
        ev = s.Evaluation(
            id=s.generate_uuid(), priority=job.priority, type=job.type,
            namespace=job.namespace,
            triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
            job_modify_index=job.modify_index, status=s.EVAL_STATUS_PENDING)
        tr = tracing.TRACER
        if tr is not None:
            tr.mark(ev.id, job_id=job.id, submit="job_evaluate",
                    priority=job.priority, namespace=job.namespace)
        try:
            _, index = self.raft.apply(MessageType.EVAL_UPDATE, {"evals": [ev]})
        except NotLeaderError:
            reply = self._forward("Job.Evaluate", {"JobID": job_id})
            return reply["Index"], reply["EvalID"]
        return index, ev.id

    def job_dispatch(self, job_id: str, payload: bytes,
                     meta: Dict[str, str]) -> Tuple[int, str, str]:
        """Dispatch an instance of a parameterized job
        (job_endpoint.go Dispatch): validate meta keys against the
        parameterized config, derive a child job carrying the payload,
        register it and create its eval.  Returns
        (index, dispatched_job_id, eval_id)."""
        parent = self.state.job_by_id(None, job_id)
        if parent is None:
            raise KeyError(f"job not found: {job_id}")
        if not parent.is_parameterized():
            raise ValueError(f"job {job_id!r} is not parameterized")
        cfg = parent.parameterized_job
        if cfg.payload == "required" and not payload:
            raise ValueError("payload is required by this parameterized job")
        if cfg.payload == "forbidden" and payload:
            raise ValueError("payload is forbidden by this parameterized job")
        if len(payload) > 16 * 1024:
            raise ValueError("payload exceeds maximum size of 16KiB")
        keys = set(meta)
        required = set(cfg.meta_required)
        allowed = required | set(cfg.meta_optional)
        if required - keys:
            raise ValueError(
                "missing required dispatch metadata: "
                + ", ".join(sorted(required - keys)))
        if keys - allowed:
            raise ValueError(
                "dispatch metadata not allowed: "
                + ", ".join(sorted(keys - allowed)))

        child = parent.copy()
        child.parent_id = parent.id
        child.id = f"{parent.id}/dispatch-{int(s.now())}-{s.generate_uuid()[:8]}"
        child.name = child.id
        child.parameterized_job = None
        child.payload = payload
        child.meta = dict(parent.meta)
        child.meta.update(meta)
        child.status = s.JOB_STATUS_PENDING
        if self._leader:
            self._check_tenant_admission(child)
        try:
            _, index = self.raft.apply(MessageType.JOB_REGISTER, {"job": child})
        except NotLeaderError:
            reply = self._forward("Job.Dispatch",
                                  {"JobID": job_id, "Payload": payload,
                                   "Meta": meta})
            return (reply["Index"], reply["DispatchedJobID"],
                    reply["EvalID"])
        ev = s.Evaluation(
            id=s.generate_uuid(), priority=child.priority, type=child.type,
            namespace=child.namespace,
            triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=child.id,
            job_modify_index=index, status=s.EVAL_STATUS_PENDING)
        self.raft.apply(MessageType.EVAL_UPDATE, {"evals": [ev]})
        return index, child.id, ev.id

    def node_evaluate(self, node_id: str) -> List[str]:
        """Force re-evaluation of all jobs with allocs on a node
        (node_endpoint.go Evaluate)."""
        node = self.state.node_by_id(None, node_id)
        if node is None:
            raise KeyError(f"node not found: {node_id}")
        try:
            return self._create_node_evals(node_id, node.modify_index)
        except NotLeaderError:
            return self._forward("Node.Evaluate",
                                 {"NodeID": node_id})["EvalIDs"]

    # -- status / operator -------------------------------------------------

    def leader_address(self) -> str:
        """Best-known leader RPC address (Status.Leader,
        status_endpoint.go)."""
        if isinstance(self.raft, MultiRaft):
            return self.raft.leader_addr or ""
        return self.config.rpc_advertise if self.is_leader() else ""

    def peer_addresses(self) -> List[str]:
        if isinstance(self.raft, MultiRaft):
            return list(self.raft.peers)
        return [self.config.rpc_advertise]

    def trace_for_eval_fanout(self, eval_id: str,
                              timeout: float = 1.0) -> Tuple[List, str]:
        """Spans for an eval, checking the local tracer first and then
        fanning out to peer servers over Status.TraceEval (the tracer is
        per-process: a follower-scheduled eval's spans live only on the
        scheduling follower, which 404'd leader-side trace links before
        this).  Best-effort and bounded: a dark follower is skipped, the
        first peer with spans wins.  Returns (spans, source_addr) — an
        empty list with source "" when nobody has the trace."""
        spans = tracing.trace_for_eval(eval_id)
        if spans:
            return spans, self.config.rpc_advertise
        if self.pool is None:
            return [], ""
        me = self.config.rpc_advertise
        for addr in self.peer_addresses():
            if addr == me:
                continue
            try:
                reply = self.pool.call(addr, "Status.TraceEval",
                                       {"EvalID": eval_id},
                                       timeout=timeout)
            except Exception:
                continue  # dark follower: skip, keep fanning out
            got = (reply or {}).get("Spans") or []
            if got:
                return got, addr
        return [], ""

    def operator_raft_remove_peer(self, address: str) -> None:
        """Remove a (possibly dead) server from the raft voter set
        (operator_endpoint.go RaftRemovePeerByAddress →
        api/operator.go:69): forwards to the leader, which replicates a
        new configuration without the peer."""
        if not address:
            raise ValueError("missing peer address")
        if self._leader:
            try:
                self._remove_peer_as_leader(address)
                return
            except NotLeaderError:
                pass  # stepped down mid-flight: forward like everyone else
        try:
            self._forward("Operator.RaftRemovePeerByAddress",
                          {"Address": address})
        except Exception as e:
            # The wire encodes errors as "<TypeName>: <message>"
            # (rpc.py): re-raise the leader's typed errors by TYPE so
            # the HTTP layer maps them to 404/400 regardless of which
            # server served the request (message wording may change;
            # the type prefix is the contract).
            msg = str(e)
            if msg.startswith("KeyError"):
                # Preserve the leader's message (it may be a different
                # KeyError than the peer-membership check).
                raise KeyError(msg.split(": ", 1)[-1].strip("'")) from e
            if msg.startswith("ValueError"):
                raise ValueError(msg.split(": ", 1)[-1]) from e
            raise
        return

    def _remove_peer_as_leader(self, address: str) -> None:
        if address == self.config.rpc_advertise:
            raise ValueError(
                "refusing to remove the current leader; remove it from "
                "another server after leadership moves")
        peers = [p for p in self.raft.peers if p != address]
        if len(peers) == len(self.raft.peers):
            raise KeyError(f"peer not found: {address}")
        self.raft.propose_config(peers)

    def raft_configuration(self) -> Dict:
        leader = self.leader_address()
        servers = []
        members = self.members() or [self._self_member()]
        for m in members:
            servers.append({
                "ID": m["Name"],
                "Node": m["Name"],
                "Address": m["Addr"],
                "Leader": m["Addr"] == leader if leader else (
                    m["Name"] == self.config.node_name and self.is_leader()),
                "Voter": True,
            })
        return {"Servers": servers, "Index": self.raft.applied_index()}

    # -- Node --------------------------------------------------------------

    def node_register(self, node: s.Node) -> Tuple[int, float]:
        """(node_endpoint.go Register): returns (index, heartbeat_ttl)."""
        node = node.copy()
        if not node.id:
            raise ValueError("missing node ID for client registration")
        existed = self.state.node_by_id(None, node.id)
        if not node.status:
            node.status = s.NODE_STATUS_INIT
        try:
            _, index = self.raft.apply(MessageType.NODE_REGISTER,
                                       {"node": node})
        except NotLeaderError:
            reply = self._forward("Node.Register", {"Node": node})
            return reply["Index"], reply["HeartbeatTTL"]
        ttl = self.heartbeat.reset_heartbeat_timer(node.id)
        # Transitions create node evals (node_endpoint.go:165).
        if existed is not None and existed.status != node.status:
            self._create_node_evals(node.id, index)
        return index, ttl

    def node_deregister(self, node_id: str) -> int:
        try:
            _, index = self.raft.apply(MessageType.NODE_DEREGISTER,
                                       {"node_id": node_id})
        except NotLeaderError:
            return self._forward("Node.Deregister", {"NodeID": node_id})["Index"]
        self.heartbeat.clear_heartbeat_timer(node_id)
        self._create_node_evals(node_id, index)
        # Deregistered node: same revocation sweep as the down
        # transition (node_endpoint.go:254-264).
        self._revoke_node_accessors(node_id)
        return index

    def node_update_status(self, node_id: str, status: str) -> Tuple[int, float]:
        """(node_endpoint.go:277 UpdateStatus) — heartbeat + transitions."""
        node = self.state.node_by_id(None, node_id)
        if node is None:
            raise KeyError(f"node not found: {node_id}")
        if not self._leader:
            # Forward even when the status is unchanged: the heartbeat TTL
            # timer lives on the leader, and a follower acking a heartbeat
            # without resetting it would let the leader mark a healthy
            # node down (node_endpoint.go:277 forwards before anything).
            reply = self._forward("Node.UpdateStatus",
                                  {"NodeID": node_id, "Status": status})
            return reply["Index"], reply["HeartbeatTTL"]
        # Relaxed: the common no-transition heartbeat must not queue on
        # the raft lock behind the apply stream (at harness scale that
        # convoy starved renewals into expiry).
        index = self.raft.applied_index_relaxed()
        if node.status != status:
            _, index = self.raft.apply(
                MessageType.NODE_UPDATE_STATUS,
                {"node_id": node_id, "status": status})
            if self._should_create_node_evals(node.status, status):
                self._create_node_evals(node_id, index)
        ttl = 0.0
        if status != s.NODE_STATUS_DOWN:
            ttl = self.heartbeat.reset_heartbeat_timer(node_id)
        else:
            self.heartbeat.clear_heartbeat_timer(node_id)
            # A down node's tasks can no longer guard their secrets:
            # revoke every accessor derived for allocs on it
            # (node_endpoint.go:339-351).
            self._revoke_node_accessors(node_id)
        return index, ttl

    def _revoke_node_accessors(self, node_id: str) -> None:
        if not self.vault.enabled:
            return
        accessors = self.state.vault_accessors_by_node(None, node_id)
        if accessors:
            threading.Thread(target=self._revoke_accessors,
                             args=(accessors,), daemon=True).start()

    @staticmethod
    def _should_create_node_evals(old: str, new: str) -> bool:
        """(structs.go ShouldDrainNode/transition table)."""
        if old == new:
            return False
        if new in (s.NODE_STATUS_DOWN,):
            return True
        if old == s.NODE_STATUS_DOWN and new == s.NODE_STATUS_READY:
            return True
        if old == s.NODE_STATUS_INIT and new == s.NODE_STATUS_READY:
            return True
        return False

    def node_update_drain(self, node_id: str, drain: bool) -> int:
        node = self.state.node_by_id(None, node_id)
        if node is None:
            raise KeyError(f"node not found: {node_id}")
        try:
            _, index = self.raft.apply(
                MessageType.NODE_UPDATE_DRAIN,
                {"node_id": node_id, "drain": drain})
        except NotLeaderError:
            return self._forward("Node.UpdateDrain",
                                 {"NodeID": node_id, "Drain": drain})["Index"]
        if drain:
            self._create_node_evals(node_id, index)
        return index

    def _create_node_evals(self, node_id: str, node_index: int) -> List[str]:
        """One eval per job with allocs on the node, plus system jobs
        (node_endpoint.go:803 createNodeEvals)."""
        allocs = self.state.allocs_by_node(None, node_id)
        job_ids = {a.job_id for a in allocs}
        evals: List[s.Evaluation] = []
        for job_id in job_ids:
            job = self.state.job_by_id(None, job_id)
            if job is None:
                continue
            evals.append(s.Evaluation(
                id=s.generate_uuid(), priority=job.priority, type=job.type,
                namespace=job.namespace,
                triggered_by=s.EVAL_TRIGGER_NODE_UPDATE, job_id=job_id,
                node_id=node_id, node_modify_index=node_index,
                status=s.EVAL_STATUS_PENDING))
        for job in self.state.jobs_by_scheduler(None, s.JOB_TYPE_SYSTEM):
            if job.id in job_ids or job.stopped():
                continue
            evals.append(s.Evaluation(
                id=s.generate_uuid(), priority=job.priority, type=job.type,
                namespace=job.namespace,
                triggered_by=s.EVAL_TRIGGER_NODE_UPDATE, job_id=job.id,
                node_id=node_id, node_modify_index=node_index,
                status=s.EVAL_STATUS_PENDING))
        if evals:
            self.raft.apply(MessageType.EVAL_UPDATE, {"evals": evals})
        return [e.id for e in evals]

    def node_get(self, node_id: str) -> Optional[s.Node]:
        return self.state.node_by_id(None, node_id)

    def node_list(self) -> List[s.Node]:
        return self.state.nodes(None)

    def node_get_allocs(self, node_id: str) -> List[s.Allocation]:
        return self.state.allocs_by_node(None, node_id)

    def derive_vault_token(self, alloc_id: str, task_names: List[str]
                           ) -> Dict[str, Dict]:
        """Derive per-task Vault tokens for a client
        (node_endpoint.go DeriveVaultToken → vault.go DeriveToken):
        validates the alloc, mints tokens, and registers the accessors
        through the log so a leader failover can still revoke them."""
        from ..state.state_store import VaultAccessor

        if not self._leader:
            # Forward before minting: a follower must not create tokens it
            # cannot register for revocation.
            reply = self._forward(
                "Node.DeriveVaultToken",
                {"AllocID": alloc_id, "Tasks": list(task_names)})
            return reply["Tasks"]
        alloc = self.state.alloc_by_id(None, alloc_id)
        if alloc is None:
            raise KeyError(f"allocation {alloc_id!r} not found")
        if alloc.terminal_status():
            raise VaultError("cannot derive token for terminal allocation")
        if alloc.job is None:
            alloc = alloc.copy()
            alloc.job = self.state.job_by_id(None, alloc.job_id)
        # Response-wrapped by default (vault.go getWrappingFn): the client
        # receives a single-use wrapping token, never the raw secret on
        # the wire; the accessor still registers server-side BEFORE
        # distribution so failover revocation works even if the client
        # never unwraps.  VaultConfig.wrap_derived_tokens=False restores
        # plain tokens for non-embedded clients that have no vault_addr
        # to unwrap against (ADVICE r5).
        wrapped = getattr(self.vault.config, "wrap_derived_tokens", True)
        tokens = self.vault.derive_token(alloc, task_names, wrapped=wrapped)
        accessors = [VaultAccessor(
            accessor=info["accessor"], alloc_id=alloc_id,
            node_id=alloc.node_id, task=task,
            creation_ttl=int(info.get("ttl", 0)),
        ) for task, info in tokens.items()]
        try:
            self.raft.apply(MessageType.VAULT_ACCESSOR_REGISTER,
                            {"accessors": accessors})
        except NotLeaderError:
            # Leadership lost between mint and registration: the tokens
            # exist in Vault but no replica knows about them — revoke
            # immediately rather than leak live credentials for their
            # full TTL (vault.go revokes on registration failure).
            self.vault.revoke_accessors([a.accessor for a in accessors])
            raise
        return tokens

    def node_get_client_allocs(self, node_id: str, min_index: int = 0,
                               max_wait: float = 0.0) -> Tuple[List[s.Allocation], int]:
        """Blocking-query variant the client's watchAllocations long-polls
        (node_endpoint.go:585 GetClientAllocs + rpc.go:340 blockingRPC):
        waits until the allocs table passes min_index or max_wait elapses,
        then returns (allocs, index)."""
        from ..state.state_store import WatchSet
        deadline = time.time() + max_wait
        while True:
            ws = WatchSet()
            allocs = self.state.allocs_by_node(ws, node_id)
            index = max(self.state.table_index("allocs"),
                        self.state.table_index("nodes"))
            if index > min_index or max_wait <= 0:
                return allocs, index
            remaining = deadline - time.time()
            if remaining <= 0:
                return allocs, index
            ws.watch(timeout=min(remaining, 1.0))

    def node_update_allocs(self, allocs: List[s.Allocation]) -> int:
        """Client alloc status sync (node_endpoint.go:657 UpdateAlloc)."""
        try:
            _, index = self.raft.apply(MessageType.ALLOC_CLIENT_UPDATE,
                                       {"allocs": allocs})
        except NotLeaderError:
            return self._forward(
                "Node.UpdateAlloc", {"Allocs": list(allocs)})["Index"]
        return index

    # -- Eval --------------------------------------------------------------

    def _require_leader(self) -> None:
        """Leader-only subsystems (broker/plan queue) live on the leader;
        callers on a follower get NotLeaderError (these calls are not
        forwarded — the in-process worker/plan pipeline only runs on the
        leader, matching nomad/worker.go's leader-local dequeue)."""
        if not self._leader:
            raise NotLeaderError(self.leader_address())

    def eval_dequeue(self, schedulers: List[str],
                     timeout: float = 0.0) -> Tuple[Optional[s.Evaluation], str]:
        self._require_leader()
        return self.eval_broker.dequeue(schedulers, timeout)

    def eval_dequeue_batch(self, schedulers: List[str], max_batch: int,
                           timeout: float = 0.0) -> Dict:
        """Remote-worker dequeue (Eval.DequeueBatch): up to ``max_batch``
        ready evals plus, per eval, the delivery-attempt count and the
        job's PLAN FENCE — the raft index of its newest committed plan
        (PlanQueue.applied_index_for).  A follower scheduler must cover
        ``max(eval.trigger_index(), fence)`` with its local log before
        scheduling (the follower-read staleness guard,
        server/follower_sched.py).  ``AppliedIndex`` carries the
        leader's applied index for the follower snapshot-lag gauge."""
        self._require_leader()
        batch = self.eval_broker.dequeue_batch(
            schedulers, max(1, min(int(max_batch), 32)), timeout)
        items = []
        for ev, token in batch:
            items.append({
                "eval": ev, "token": token,
                "attempts": self.eval_broker.delivery_attempts(ev.id),
                "fence": self.plan_queue.applied_index_for(ev.job_id),
            })
        return {"items": items,
                "applied_index": self.raft.applied_index_relaxed()}

    def eval_update(self, evals: List[s.Evaluation]) -> int:
        """Apply an EVAL_UPDATE on behalf of a remote worker
        (Eval.Update — the wire twin of WorkerPlanner.update_eval /
        create_eval / record_eval_failures)."""
        _, index = self.raft.apply(MessageType.EVAL_UPDATE,
                                   {"evals": evals})
        return index

    def eval_reblock(self, ev: s.Evaluation, token: str) -> int:
        """Apply + reblock on behalf of a remote worker (Eval.Reblock):
        the blocked-eval tracker is leader-local state, so the update
        and the reblock must land on the same server."""
        self._require_leader()
        _, index = self.raft.apply(MessageType.EVAL_UPDATE,
                                   {"evals": [ev]})
        self.blocked_evals.reblock(ev, token)
        return index

    def eval_pause_nack(self, eval_id: str, token: str) -> None:
        self._require_leader()
        self.eval_broker.pause_nack_timeout(eval_id, token)

    def eval_resume_nack(self, eval_id: str, token: str) -> None:
        self._require_leader()
        self.eval_broker.resume_nack_timeout(eval_id, token)

    def eval_ack(self, eval_id: str, token: str) -> None:
        if not self._leader:
            self._forward("Eval.Ack", {"EvalID": eval_id, "Token": token})
            return
        self.eval_broker.ack(eval_id, token)

    def eval_nack(self, eval_id: str, token: str) -> None:
        if not self._leader:
            self._forward("Eval.Nack", {"EvalID": eval_id, "Token": token})
            return
        self.eval_broker.nack(eval_id, token)

    def eval_get(self, eval_id: str) -> Optional[s.Evaluation]:
        return self.state.eval_by_id(None, eval_id)

    def eval_list(self) -> List[s.Evaluation]:
        return self.state.evals(None)

    def eval_allocations(self, eval_id: str) -> List[s.Allocation]:
        return self.state.allocs_by_eval(None, eval_id)

    # -- Alloc -------------------------------------------------------------

    def alloc_get(self, alloc_id: str) -> Optional[s.Allocation]:
        return self.state.alloc_by_id(None, alloc_id)

    def alloc_list(self) -> List[s.Allocation]:
        return self.state.allocs(None)

    # -- Plan --------------------------------------------------------------

    def plan_submit(self, plan: s.Plan):
        """(Plan.Submit → PlanQueue, plan_endpoint.go).

        Token fence: a plan whose eval token no longer matches the
        broker's OUTSTANDING delivery is a stale worker's submission —
        the nack deadline fired and the eval was redelivered (possibly
        to another server; follower-read deliveries run against the
        full deadline with no mid-flight pause).  Rejecting it here is
        what makes redelivery safe: same-job double placement is the
        one staleness the applier's capacity re-check cannot catch.
        Plans without a token (tests, direct operators) pass."""
        self._require_leader()
        if plan.eval_id and plan.eval_token:
            token, outstanding = self.eval_broker.outstanding(plan.eval_id)
            if outstanding and token != plan.eval_token:
                raise RuntimeError(
                    f"plan token fence: eval {plan.eval_id} was "
                    "redelivered; stale delivery's plan rejected")
        return self.plan_queue.enqueue(plan)

    # -- System ------------------------------------------------------------

    def system_gc(self) -> None:
        try:
            self._create_core_eval(s.CORE_JOB_FORCE_GC)
        except NotLeaderError:
            self._forward("System.GarbageCollect", {})

    def system_reconcile_summaries(self) -> None:
        try:
            self.raft.apply(MessageType.RECONCILE_JOB_SUMMARIES, {})
        except NotLeaderError:
            self._forward("System.ReconcileJobSummaries", {})

    # -- Namespace (tenancy plane) -----------------------------------------

    def namespace_upsert(self, ns: s.Namespace, region: str = "") -> int:
        """Register/update a tenant through raft (like jobs): validate →
        log NAMESPACE_UPSERT; policy mirrors refresh via the FSM hook.
        Namespaces are REGION-SCOPED (each region's raft owns its tenant
        rows and enforces their quotas locally): an explicit ``region``
        routes over the federation, like jobs."""
        if region and region != self.config.region:
            reply = self._forward_region(region, "Namespace.Upsert",
                                         {"Namespace": ns})
            return reply["Index"]
        ns = ns.copy()
        problems = ns.validate()
        if problems:
            raise ValueError(
                "namespace validation failed: " + "; ".join(problems))
        try:
            _, index = self.raft.apply(MessageType.NAMESPACE_UPSERT,
                                       {"namespace": ns})
        except NotLeaderError:
            reply = self._forward("Namespace.Upsert", {"Namespace": ns})
            return reply["Index"]
        return index

    def namespace_delete(self, name: str, region: str = "") -> int:
        if region and region != self.config.region:
            reply = self._forward_region(region, "Namespace.Delete",
                                         {"Name": name})
            return reply["Index"]
        if name == s.DEFAULT_NAMESPACE:
            raise ValueError("cannot delete the default namespace")
        if self.state.namespace_by_name(None, name) is None:
            raise KeyError(f"namespace not found: {name}")
        try:
            _, index = self.raft.apply(MessageType.NAMESPACE_DELETE,
                                       {"name": name})
        except NotLeaderError:
            reply = self._forward("Namespace.Delete", {"Name": name})
            return reply["Index"]
        return index

    def namespace_list(self, region: str = "") -> List[s.Namespace]:
        if region and region != self.config.region:
            reply = self._forward_region(region, "Namespace.List", {})
            return reply["Namespaces"]
        return self.state.namespaces(None)

    def namespace_status(self, name: str, region: str = "") -> Dict:
        """One tenant's row + live usage + broker counters — the
        namespace-status CLI/HTTP read."""
        if region and region != self.config.region:
            return self._forward_region(region, "Namespace.Status",
                                        {"Name": name})
        row = self.state.namespace_by_name(None, name)
        if row is None:
            raise KeyError(f"namespace not found: {name}")
        cpu, mem, disk, iops, live = self.state.namespace_usage_one(name)
        self._refresh_capacity()
        cap, nodes = self._cluster_capacity, self._cluster_nodes
        return {
            "Namespace": row,
            "Usage": {"CPU": cpu, "MemoryMB": mem, "DiskMB": disk,
                      "IOPS": iops, "LiveAllocs": live,
                      "NodeUnits": self._node_units(
                          (cpu, mem, disk, iops), cap, nodes)},
            "ReservedAllocs": self.quota_ledger.reserved(name),
            "ReservedNodeUnits": self.node_units_ledger.reserved(name),
            "PendingEvals": self.eval_broker.ns_pending_count(name),
        }

    def broker_stats(self) -> Dict:
        """The /v1/broker/stats saturation surface: broker admission /
        coalesce state plus the plan-queue depth (the two stages whose
        backlogs say whether the control plane is keeping up)."""
        out = self.eval_broker.extended_stats()
        out["PlanQueueDepth"] = self.plan_queue.depth()
        out["BlockedEvals"] = self.blocked_evals.stats()
        # Follower-read scheduling surface (ISSUE 10): what THIS server
        # is forwarding to the leader, and how far its replicated FSM
        # lags the commit horizon it knows about.
        fs: Dict = {"Enabled": bool(self.follower_workers),
                    "IsLeader": self._leader}
        if self.leader_channel is not None:
            fs.update(self.leader_channel.stats())
        if isinstance(self.raft, MultiRaft):
            fs["SnapshotLag"] = max(
                0, self.raft.commit_index
                - self.raft.applied_index_relaxed())
        out["FollowerSched"] = fs
        return out

    def stats(self) -> Dict:
        out = {
            "leader": self._leader,
            "applied_index": self.raft.applied_index(),
            "broker": self.eval_broker.stats(),
            "blocked": self.blocked_evals.stats(),
            "plan_queue_depth": self.plan_queue.depth(),
            "heartbeat_active": self.heartbeat.active(),
        }
        if self._events_enabled:
            out["events"] = self.event_broker.stats()
        sink = self.metrics.sink
        if hasattr(sink, "latest"):
            latest = sink.latest()
            out["metrics_gauges"] = latest["Gauges"]
            out["metrics_samples"] = {
                k: f"count={v['count']} mean={v['mean']}ms"
                for k, v in latest["Samples"].items()}
        return out
