"""L1 state store: the in-memory MVCC database behind the control plane.

Behavioral parity with the reference StateStore over go-memdb
(nomad/state/state_store.go:55-1880, schema nomad/state/schema.go:45-422):
every table tracks a raft index, readers take snapshots, blocking queries
wait on watchsets, and `upsert_plan_results` is how committed plans land.

Design departure for the TPU build: instead of radix-tree MVCC we keep plain
dict tables plus explicit secondary indexes; `snapshot()` shallow-copies the
tables and element-copies the secondary-index sets (O(rows), acceptable for
the per-batch snapshot cadence of the batch scheduler; copy-on-write sets
are the planned optimization if per-eval snapshots become hot).  Objects are
treated as immutable once inserted (every write path inserts fresh copies),
which gives the scheduler the same isolated world-view the reference gets
from memdb.  The
scheduler-visible subset (nodes, jobs, allocs-by-node/job, evals) is the
sync boundary that ops/encode.py mirrors into device tensors.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from ..structs import structs as s
from ..utils import knobs as _knobs
from . import columnar

# Shared immutable empty result for index misses (never mutated).
_EMPTY_SET: Set[str] = set()

# Stands in for a node the table does not hold: not ready, no capacity.
_NO_NODE = s.Node()

# Usage-delta log bound (ops/resident.py delta feed): entries beyond the
# cap are trimmed oldest-first and the floor rises, forcing consumers
# whose cached index fell off to full re-encode.  Counted in alloc rows
# (a slab entry weighs len(slab)).
ALLOC_LOG_CAP = _knobs.get_int("NOMAD_TPU_ALLOC_LOG_CAP")


def _released(ports: Tuple[int, ...]) -> Tuple[int, ...]:
    """The feed's port values for a write that frees ``ports``."""
    return tuple(-v for v in ports)


# Number of historical job versions retained (reference: structs.go
# JobTrackedVersions = 6).
JOB_TRACKED_VERSIONS = 6


@dataclass
class PeriodicLaunch:
    """Last launch time of a periodic job (reference: structs.go:4200 region)."""

    id: str = ""
    launch: float = 0.0
    create_index: int = 0
    modify_index: int = 0


@dataclass
class VaultAccessor:
    """A derived Vault token accessor (reference: structs.go VaultAccessor)."""

    accessor: str = ""
    alloc_id: str = ""
    node_id: str = ""
    task: str = ""
    creation_ttl: int = 0
    create_index: int = 0


class WatchSet:
    """Collects watch subscriptions during a query; `watch` blocks until any
    watched table changes (reference: go-memdb WatchSet + state/notify.go).

    The granularity is per-table: any write to a watched table wakes the
    watcher, which then re-runs its query and compares indexes — the same
    re-run loop blockingRPC uses (nomad/rpc.go:340).  Each watch set owns an
    Event registered with every watched store so a write to *any* of them
    wakes the waiter.
    """

    def __init__(self) -> None:
        self._entries: List[Tuple["StateStore", str, int]] = []
        self._event = threading.Event()

    def add(self, store: "StateStore", table: str) -> None:
        self._entries.append((store, table, store.table_index(table)))
        store._register_watcher(self._event)

    def watch(self, timeout: Optional[float] = None) -> bool:
        """Block until any watched table advances; True on timeout."""
        if not self._entries:
            return True
        import time as _time

        end = None if timeout is None else _time.monotonic() + timeout
        try:
            while True:
                for st, table, idx in self._entries:
                    if st.table_index(table) > idx:
                        return False
                remaining = None if end is None else end - _time.monotonic()
                if remaining is not None and remaining <= 0:
                    return True
                self._event.clear()
                # Re-register in case a store's notify cleared us out.
                for st, _, _ in self._entries:
                    st._register_watcher(self._event)
                # Re-check after registration to close the race with a write
                # that landed between the index check and registration.
                if any(st.table_index(table) > idx for st, table, idx in self._entries):
                    return False
                self._event.wait(remaining)
        finally:
            for st, _, _ in self._entries:
                st._unregister_watcher(self._event)

    def close(self) -> None:
        """Unregister without blocking (for queries that returned
        immediately and will never wait)."""
        for st, _, _ in self._entries:
            st._unregister_watcher(self._event)


class StateStore:
    """The authoritative in-memory database of cluster state."""

    # Cluster event stream (server/event_broker.py): attached by the
    # Server when streaming is armed, None otherwise — every write path
    # below pays one attribute load + branch while disarmed (the
    # fault.py cost discipline).  Class attribute so snapshots created
    # via __new__ read None without per-snapshot bookkeeping.
    event_broker = None

    TABLES = (
        "nodes",
        "jobs",
        "job_summary",
        "evals",
        "allocs",
        "periodic_launch",
        "vault_accessors",
        "deployment",
        "namespaces",
    )

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._watchers: Set[threading.Event] = set()
        # Store-lineage id: snapshots inherit it, distinct stores differ —
        # table indexes are only meaningful within one lineage (cache keys
        # derived from them must not collide across stores).
        self.store_uid: str = s.generate_uuid()
        self.nodes_table: Dict[str, s.Node] = {}
        self.jobs_table: Dict[str, s.Job] = {}
        self.job_versions: Dict[str, List[s.Job]] = {}
        self.job_summary_table: Dict[str, s.JobSummary] = {}
        self.evals_table: Dict[str, s.Evaluation] = {}
        self.allocs_table: Dict[str, s.Allocation] = {}
        self.periodic_launch_table: Dict[str, PeriodicLaunch] = {}
        self.vault_accessors_table: Dict[str, VaultAccessor] = {}
        self.deployments_table: Dict[str, s.Deployment] = {}
        self.namespaces_table: Dict[str, s.Namespace] = {}
        self._indexes: Dict[str, int] = {}
        # Per-namespace usage fold (tenancy plane): immutable 5-tuples
        # (cpu, mem_mb, disk_mb, iops, live_allocs) maintained at the
        # SAME three sites that feed the usage-delta log, so the fold is
        # O(changed) per write, never a table walk.  _ns_dirty is the
        # change feed the broker's fair-dequeue scorer drains (only
        # touched tenants get re-scored).  Rebuilt from alloc rows on
        # restore (the fold, like the delta log, is not persisted).
        self._ns_usage: Dict[str, Tuple[int, int, int, int, int]] = {}
        self._ns_dirty: Set[str] = set()
        # Secondary indexes (reference: schema.go secondary memdb indexes)
        self._allocs_by_node: Dict[str, Set[str]] = defaultdict(set)
        self._allocs_by_job: Dict[str, Set[str]] = defaultdict(set)
        self._allocs_by_eval: Dict[str, Set[str]] = defaultdict(set)
        self._evals_by_job: Dict[str, Set[str]] = defaultdict(set)
        self._vault_by_alloc: Dict[str, Set[str]] = defaultdict(set)
        self._vault_by_node: Dict[str, Set[str]] = defaultdict(set)
        # Slabs whose by-id table rows and per-node index cells have not
        # been built yet (see _upsert_slabs_impl / _materialize_pending):
        # bulk batch commits never read them in-batch, so the per-alloc
        # indexing cost lands on the first reader that needs it.
        self._pending_slabs: List[s.AllocSlab] = []
        self._pending_by_job: Dict[str, List[s.AllocSlab]] = {}
        # Usage-delta log (the ops/resident.py delta feed): every alloc
        # write appends the per-node resource-usage delta it caused, so a
        # consumer holding a device-resident usage mirror at raft index K
        # can catch up with allocs_since(K) — O(changed) instead of a
        # full O(cluster) table walk.  Entries are immutable tuples
        # (index, node_id, (cpu, mem, disk, iops)) for single rows or
        # (index, slab) for bulk slab inserts (expanded lazily at read).
        # _alloc_log_floor is the highest index whose deltas are NO
        # LONGER fully present; allocs_since(i) answers None for
        # i < floor.  The list is SHARED with snapshots behind a length
        # cursor (_alloc_log_len): appends past a snapshot's cursor are
        # invisible to it, writes by a non-owning store copy-on-write
        # first, and trims replace the list object (copy-on-trim) so
        # cursors into the old one stay valid — snapshot() stays O(1)
        # for the feed instead of copying up to ALLOC_LOG_CAP entries.
        self._alloc_log: List[tuple] = []
        self._alloc_log_len: int = 0
        self._alloc_log_owned: bool = True
        self._alloc_log_floor: int = 0
        self._alloc_log_weight: int = 0
        # Columnar mirror of the node table + live-usage matrix
        # (state/columnar.py): node writes maintain it incrementally,
        # usage derives lazily from the delta log above, snapshots share
        # it copy-on-write, and ops/encode slices it instead of walking
        # node objects.  None = not built yet / invalidated by a
        # structural change (rebuilt by the owner at the next
        # snapshot()/columns() call).
        self._columns: Optional[columnar.ClusterColumns] = None

    # -- snapshot ----------------------------------------------------------

    def snapshot(self) -> "StateSnapshot":
        """An immutable point-in-time view (state_store.go:55)."""
        with self._lock:
            snap = StateSnapshot.__new__(StateSnapshot)
            snap._lock = threading.RLock()
            snap._cond = threading.Condition(snap._lock)
            snap._watchers = set()
            snap.store_uid = self.store_uid
            snap.nodes_table = dict(self.nodes_table)
            snap.jobs_table = dict(self.jobs_table)
            snap.job_versions = {k: list(v) for k, v in self.job_versions.items()}
            snap.job_summary_table = dict(self.job_summary_table)
            snap.evals_table = dict(self.evals_table)
            snap.allocs_table = dict(self.allocs_table)
            snap.periodic_launch_table = dict(self.periodic_launch_table)
            snap.vault_accessors_table = dict(self.vault_accessors_table)
            snap.deployments_table = dict(self.deployments_table)
            snap.namespaces_table = dict(self.namespaces_table)
            # Per-ns usage: values are immutable tuples, shallow copy is
            # a full fork; a snapshot's hypothetical writes never dirty
            # the parent's change feed.
            snap._ns_usage = dict(self._ns_usage)
            snap._ns_dirty = set(self._ns_dirty)
            snap._indexes = dict(self._indexes)
            # Secondary-index SETS are immutable by contract (mutators go
            # through _idx_add/_idx_discard which REPLACE the set), so a
            # snapshot shares them behind a shallow dict copy — the
            # go-memdb O(1)-ish snapshot property instead of deep-copying
            # every per-key id set (O(cluster) per snapshot, VERDICT r1
            # weak #8).
            snap._allocs_by_node = defaultdict(set, self._allocs_by_node)
            snap._allocs_by_job = defaultdict(set, self._allocs_by_job)
            snap._allocs_by_eval = defaultdict(set, self._allocs_by_eval)
            snap._evals_by_job = defaultdict(set, self._evals_by_job)
            snap._vault_by_alloc = defaultdict(set, self._vault_by_alloc)
            snap._vault_by_node = defaultdict(set, self._vault_by_node)
            # Pending slabs are immutable post-insert; each store drains
            # its own copy of the list into its own dicts independently.
            snap._pending_slabs = list(self._pending_slabs)
            snap._pending_by_job = {k: list(v)
                                    for k, v in self._pending_by_job.items()}
            # Usage-delta log: share the list behind a length cursor
            # (entries are immutable; parent appends land past the
            # cursor, parent trims replace the list object, and a
            # snapshot write copies its prefix first) — O(1) instead of
            # copying up to ALLOC_LOG_CAP entries per snapshot.
            snap._alloc_log = self._alloc_log
            snap._alloc_log_len = self._alloc_log_len
            snap._alloc_log_owned = False
            snap._alloc_log_floor = self._alloc_log_floor
            snap._alloc_log_weight = self._alloc_log_weight
            # Columnar mirror: O(1) share behind copy-on-write (array
            # refs + a private row cursor; see columnar.ClusterColumns.
            # share).  Built here on first use so the mirror warms on
            # the OWNING store and survives the snapshot.
            snap._columns = None
            if columnar.enabled():
                cols = self._ensure_columns_locked()
                if cols is not None:
                    self._col_fold_if_stale(cols)
                    snap._columns = cols.share()
            # Ready-node memo (scheduler/util.ready_nodes_in_dcs): the
            # DICT OBJECT is shared between this store and every
            # snapshot cut from the same node-table state, so the first
            # reader to pay the O(cluster) ready walk warms ALL of them
            # — without this, a fresh snapshot per batch re-pays the
            # walk every time (ISSUE 14: ~1s/batch at 1M nodes in the
            # mesh steady stream; the base store itself never computes
            # the memo because scheduling always runs off snapshots).
            # Any node write pops only the WRITER's reference (_bump):
            # the writer diverges from the shared memo, every other
            # holder's frozen table still matches it.  Entries are
            # (list, dict) tuples the reader copies before returning.
            snap._ready_nodes_cache = self.__dict__.setdefault(
                "_ready_nodes_cache", {})
            # Writes to a snapshot (job_plan dry runs, scheduler harness
            # worlds) are hypothetical: they must never publish events.
            snap.event_broker = None
            return snap

    # -- columnar mirror ---------------------------------------------------

    def _ensure_columns_locked(self) -> Optional[columnar.ClusterColumns]:
        """Return the columnar mirror, cold-building it when absent or
        epoch-stale.  Snapshots never build (the mirror must warm on the
        owning store, not die with a per-batch view).  Caller holds the
        lock."""
        cols = self._columns
        if cols is not None and cols.epoch == columnar.EPOCH:
            return cols
        if isinstance(self, StateSnapshot):
            return None
        self._columns = columnar.ClusterColumns.build(self)
        return self._columns

    def columns(self) -> Optional[columnar.ClusterColumns]:
        """The columnar node/usage mirror for the encode path, or None
        when disabled/unavailable (callers fall back to the object
        walk)."""
        if not columnar.enabled():
            return None
        with self._lock:
            return self._ensure_columns_locked()

    def column_usage(self, cols: columnar.ClusterColumns):
        """Catch ``cols``' usage matrix up with this store's alloc
        writes (O(changed) via the delta feed; full row-walk rebuild on
        a feed gap) and return it.  Rows beyond ``cols.n`` are
        padding."""
        with self._lock:
            if not cols.fold_usage(self):
                cols.rebuild_usage(self)
            return cols.usage

    #: Un-folded delta-suffix length (log entries) past which snapshot()
    #: folds the OWNER's usage cursor forward before sharing.  Folding
    #: on every snapshot would pay a [n, 4] COW copy even for batches
    #: that never read usage (the resident delta path); never folding
    #: lets the cursor fall off the bounded log's trim floor, silently
    #: degrading every usage read to a full O(all allocs) row-walk
    #: rebuild — the exact cost the mirror removes.
    COL_FOLD_BACKLOG = 4096

    def _col_fold_if_stale(self, cols: columnar.ClusterColumns) -> None:
        """Owner-side usage-cursor maintenance at snapshot time (caller
        holds the lock): one amortized fold/rebuild here keeps every
        per-batch snapshot view's fold O(recent) instead of each view
        independently re-scanning the whole suffix."""
        import bisect

        if cols.usage_index < self._alloc_log_floor:
            cols.rebuild_usage(self)
            return
        start = bisect.bisect_right(self._alloc_log, cols.usage_index,
                                    0, self._alloc_log_len,
                                    key=lambda e: e[0])
        if self._alloc_log_len - start > self.COL_FOLD_BACKLOG:
            if not cols.fold_usage(self):
                cols.rebuild_usage(self)

    def _col_node_upserted(self, node: s.Node, existing: Optional[s.Node]
                           ) -> None:
        """upsert_node hook (caller holds the lock): append or update the
        mirror row.  A datacenter/computed-class change on an existing
        node could reorder the first-seen codebooks, so it drops the
        mirror for rebuild instead."""
        cols = self._columns
        if cols is None:
            return
        if existing is None:
            # Fold BEFORE appending: the backfill below reads the
            # tables' current truth for this node, so any still-pending
            # log entries for it must land first or they'd double-count.
            if not cols.fold_usage(self):
                cols.rebuild_usage(self)
            row = cols.append_node(node)
            self._col_backfill_usage(cols, node.id, row)
        elif not cols.update_node(node):
            self._columns = None

    @staticmethod
    def _slab_node_set(slab: s.AllocSlab) -> frozenset:
        """Cached node-id membership set for one slab (built once;
        slab node_ids are immutable post-insert)."""
        ns = getattr(slab, "_node_set", None)
        if ns is None:
            ns = frozenset(slab.node_ids)
            slab._node_set = ns
        return ns

    def _col_backfill_usage(self, cols: columnar.ClusterColumns,
                            node_id: str, row: int) -> None:
        """A node registered AFTER allocs referencing it: seed its fresh
        usage row from the live rows already in the tables (the object
        walk counts them, so the mirror must too)."""
        # Materialize pending slabs ONLY when one actually references
        # this node: unconditionally draining a million-row pending slab
        # to backfill a node whose allocs are all standalone rows would
        # defeat the lazy-slab discipline.  Membership goes through a
        # per-slab frozenset cached on the slab (an undeclared attr,
        # like _id_idx, so it stays off the wire codec) — a linear scan
        # of a 10M-entry node_ids list per node registration would stall
        # the store lock for hundreds of ms.
        if self._pending_slabs and any(
                node_id in self._slab_node_set(slab)
                for slab in self._pending_slabs):
            self._materialize_pending()
        if self._idx_get(self._allocs_by_node, node_id):
            cols.usage[row] = self._indexed_usage(node_id, {})

    def _indexed_usage(self, node_id: str, vec_of: Dict[int, tuple]
                       ) -> Tuple[int, int, int, int]:
        """Summed usage of ``node_id``'s non-terminal rows in the by-node
        index, materializing none: a slab-backed id reads its slab's
        proto.  ``vec_of`` memoizes the vector per row object across one
        caller's pass (caller holds the lock, so the rows stay alive and
        their ``id()`` unique)."""
        c = m = d = io = 0
        get = self.allocs_table.get
        for aid in self._idx_get(self._allocs_by_node, node_id):
            v = get(aid)
            if v is None:
                continue
            vec = vec_of.get(id(v))
            if vec is None:
                r = v.proto if type(v) is s.AllocSlab else v
                vec = ((0, 0, 0, 0) if r.terminal_status()
                       else self._usage_vec(r))
                vec_of[id(v)] = vec
            c += vec[0]
            m += vec[1]
            d += vec[2]
            io += vec[3]
        return c, m, d, io

    def fit_reference_rows(self, node_ids: List[str], rows: np.ndarray,
                           row_of: Dict[str, int]
                           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``(ready, capacity, used)`` per node, read from the tables
        themselves: status/drain/resources/reserved off ``nodes_table``,
        and ``used`` = reserved + the usage of every non-terminal alloc
        row the store holds for the node (by-node index + by-id rows,
        plus the slabs whose indexing is still deferred).  This is the
        reference the plan applier's differential guard holds the
        columnar mirror against, so of the mirror it shares the row
        INDEX alone (``rows[i]`` = ``row_of[node_ids[i]]``, which finds
        a pending slab's placements on the asked nodes without a dict
        probe per placement) and reads none of its columns; it
        materializes no Allocation, caches nothing back into
        ``allocs_table`` and leaves pending slabs pending."""
        vec = columnar.ClusterColumns._vec
        ready, cap, reserved, live = [], [], [], []
        with self._lock:
            vec_of: Dict[int, tuple] = {}
            for nid in node_ids:
                node = self.nodes_table.get(nid)
                if node is None:
                    node = _NO_NODE
                ready.append(node.ready())
                cap.append(vec(node.resources))
                reserved.append(vec(node.reserved))
                live.append(self._indexed_usage(nid, vec_of))
            shape = (len(node_ids), columnar.RES_DIMS)
            cap = np.array(cap, dtype=np.int64).reshape(shape)
            used = (np.array(reserved, dtype=np.int64).reshape(shape)
                    + np.array(live, dtype=np.int64).reshape(shape))
            pending = [slab for slab in self._pending_slabs
                       if not slab.proto.terminal_status()]
            if pending:
                # Position among node_ids of each mirror row; the extra
                # last slot is what a placement without a row (-1) reads.
                pos = np.full(len(row_of) + 1, -1, dtype=np.int64)
                pos[rows] = np.arange(len(rows))
                hits: Dict[tuple, List[np.ndarray]] = {}
                for slab in pending:
                    hit = pos[columnar.slab_rows(slab, row_of)]
                    hits.setdefault(self._usage_vec(slab.proto),
                                    []).append(hit[hit >= 0])
                for usage, found in hits.items():
                    columnar.add_counts(used, np.concatenate(found), usage)
        return np.array(ready, dtype=bool), cap, used

    # -- immutable index-set updates ---------------------------------------
    #
    # Index values are never mutated in place: additions/removals build a
    # replacement value, which is what lets snapshot() share the index
    # dicts shallowly.  A value is EITHER a canonical set OR a cons chain
    # `(parent_value, item_or_items)` produced by the O(1) bulk-append
    # path (_idx_append): the TPU batch scheduler commits hundreds of
    # thousands of slab allocs per pass, and building a replacement set
    # per touched node was the single largest host cost at bench scale.
    # Readers go through _idx_get, which flattens a chain once and
    # path-compresses it back into the reading store's dict (safe: the
    # replacement has identical contents, and each store/snapshot owns
    # its dict while sharing the immutable values).

    @staticmethod
    def _idx_get(idx: Dict[str, object], key: str) -> Set[str]:
        cur = idx.get(key)
        if cur is None:
            return _EMPTY_SET
        if type(cur) is set:
            return cur
        out: Set[str] = set()
        stack = [cur]
        while stack:
            v = stack.pop()
            if v is None:
                continue
            if type(v) is set:
                out |= v
            else:  # cons cell (parent, item_or_items)
                stack.append(v[0])
                items = v[1]
                if type(items) is str:
                    out.add(items)
                else:
                    out.update(items)
        idx[key] = out
        return out

    @classmethod
    def _idx_add(cls, idx: Dict[str, object], key: str, item: str) -> None:
        cur = cls._idx_get(idx, key)
        idx[key] = {item} if not cur else cur | {item}

    @classmethod
    def _idx_update(cls, idx: Dict[str, object], key: str, items) -> None:
        cur = cls._idx_get(idx, key)
        idx[key] = set(items) if not cur else cur | set(items)

    @staticmethod
    def _idx_append(idx: Dict[str, object], key: str, items) -> None:
        """O(1) bulk append: cons `items` (an id or a sequence of ids,
        all NEW — never already present) onto the current value.  Always
        a cons, even on a fresh key: `items` may be a lazy column
        (structs._LazyStrs) whose strings must not materialize on the
        commit path — flatten happens on first read (_idx_get)."""
        cur = idx.get(key)
        if cur is None and type(items) is str:
            idx[key] = {items}
        else:
            idx[key] = (cur, items)

    @classmethod
    def _idx_discard(cls, idx: Dict[str, object], key: str, item: str) -> None:
        cur = cls._idx_get(idx, key)
        if cur and item in cur:
            idx[key] = cur - {item}

    # -- index bookkeeping -------------------------------------------------

    def _bump(self, table: str, index: int) -> None:
        self._indexes[table] = index
        if table == "nodes":
            # Drop the memoized ready-node list (scheduler/util.py
            # ready_nodes_in_dcs): node writes are the only thing that
            # changes it, and the stale-snapshot worker pool reuses one
            # snapshot across many evals — the memo is what makes that
            # reuse O(1) instead of an O(cluster) walk per eval.
            self.__dict__.pop("_ready_nodes_cache", None)

    # -- lazy slab resolution ---------------------------------------------
    #
    # Bulk plan commits store the AllocSlab object itself as the table
    # value for each of its alloc ids — zero per-alloc objects at insert
    # time.  By-id reads materialize the full Allocation (and cache it
    # back); bulk reads enumerate each slab once.

    def _materialize_pending(self) -> None:
        """Flush deferred slab indexing (see _upsert_slabs_impl): build
        the by-id table rows and per-node index cells for every pending
        slab.  Lazy id columns are materialized once here and cached
        back onto the slab (deterministic values — an independent drain
        of a snapshot's copy produces equal strings)."""
        pending = self._pending_slabs
        if not pending:
            return
        self._pending_slabs = []
        self._pending_by_job = {}
        self._drain_slabs(pending)

    def _drain_slabs(self, slabs) -> None:
        """Shared drain body for the full (_materialize_pending) and
        per-job (_materialize_job_pending) paths: build the by-id table
        rows and per-node index cells; lazy id columns materialize once
        and cache back onto the slab."""
        table = self.allocs_table
        by_node = self._allocs_by_node
        get = by_node.get
        for slab in slabs:
            ids = slab.ids
            if type(ids) is not list:
                ids = list(ids)
                slab.ids = ids
            for nid, aid in zip(slab.node_ids, ids):
                cur = get(nid)
                by_node[nid] = {aid} if cur is None else (cur, aid)
            for aid in ids:
                table[aid] = slab

    def _materialize_job_pending(self, job_id: str) -> None:
        """Per-job partial drain of the deferred slab indexing: build
        the by-id table rows and per-node index cells for ``job_id``'s
        pending slabs ONLY, leaving every other slab deferred — the
        same referenced-only discipline as _node_usage_row's membership
        check.  A phase-1 ``allocs_by_job`` on a fresh job must not pay
        an O(cluster) drain of an unrelated warm million-row slab on
        every snapshot (ISSUE 14: that drain was the dominant host cost
        of the mesh steady state, ~2s/batch at 1M warm allocs)."""
        slabs = self._pending_by_job.pop(job_id, None)
        if not slabs:
            return
        gone = {id(sl) for sl in slabs}
        self._pending_slabs = [sl for sl in self._pending_slabs
                               if id(sl) not in gone]
        self._drain_slabs(slabs)

    def _get_alloc(self, alloc_id: str) -> Optional[s.Allocation]:
        """allocs_table read with slab materialization + cache-back.
        Caller holds the lock (or owns an immutable snapshot)."""
        v = self.allocs_table.get(alloc_id)
        if v is None and self._pending_slabs:
            self._materialize_pending()
            v = self.allocs_table.get(alloc_id)
        if type(v) is s.AllocSlab:
            v = v.materialize(v.id_index(alloc_id))
            self.allocs_table[alloc_id] = v
        return v

    def table_index(self, table: str) -> int:
        with self._lock:
            return self._indexes.get(table, 0)

    def latest_index(self) -> int:
        with self._lock:
            return max(self._indexes.values(), default=0)

    def fingerprint(self) -> str:
        """Deterministic digest of the REPLICATED core state (nodes,
        jobs, allocs, evals) — two FSMs that applied the same committed
        log prefix must return the same hex string (the ISSUE 12 safety
        auditor's cross-server divergence check).  Only fields that ride
        the log are hashed: everything here is stamped by a raft apply,
        never by leader-local clocks or broker bookkeeping.  Call on a
        consistent snapshot (Server.consistent_snapshot) so a
        mid-entry read cannot manufacture a false divergence."""
        import hashlib

        h = hashlib.sha256()

        def w(*parts) -> None:
            h.update("\x1f".join(str(p) for p in parts).encode())
            h.update(b"\x1e")

        for n in sorted(self.nodes(None), key=lambda x: x.id):
            w("node", n.id, n.status, int(n.drain), n.modify_index)
        for j in sorted(self.jobs(None), key=lambda x: x.id):
            w("job", j.id, int(j.stop), j.version, j.modify_index)
        for a in sorted(self.allocs(None), key=lambda x: x.id):
            w("alloc", a.id, a.name, a.job_id, a.node_id, a.task_group,
              a.desired_status, a.client_status, a.modify_index)
        for e in sorted(self.evals(None), key=lambda x: x.id):
            w("eval", e.id, e.status, e.job_id, e.modify_index)
        return h.hexdigest()

    def _notify(self) -> None:
        with self._cond:
            self._cond.notify_all()
            watchers, self._watchers = self._watchers, set()
        for event in watchers:
            event.set()

    def _register_watcher(self, event: threading.Event) -> None:
        with self._lock:
            self._watchers.add(event)

    def _unregister_watcher(self, event: threading.Event) -> None:
        with self._lock:
            self._watchers.discard(event)

    # -- nodes -------------------------------------------------------------

    def upsert_node(self, index: int, node: s.Node) -> None:
        """(state_store.go:413) — preserves create_index on update."""
        with self._lock:
            existing = self.nodes_table.get(node.id)
            node = node.copy()
            if existing is not None:
                node.create_index = existing.create_index
            else:
                node.create_index = index
            node.modify_index = index
            self.nodes_table[node.id] = node
            self._col_node_upserted(node, existing)
            self._bump("nodes", index)
        eb = self.event_broker
        if eb is not None:
            eb.publish_one(
                s.TOPIC_NODE,
                "NodeRegistered" if existing is None else "NodeUpdated",
                node.id, index,
                {"Status": node.status, "Datacenter": node.datacenter})
        self._notify()

    def delete_node(self, index: int, node_id: str) -> None:
        with self._lock:
            if node_id not in self.nodes_table:
                raise KeyError(f"node not found: {node_id}")
            del self.nodes_table[node_id]
            # Deletion shifts every later row: drop the mirror (the
            # owner rebuilds at the next snapshot()/columns() call).
            self._columns = None
            self._bump("nodes", index)
        eb = self.event_broker
        if eb is not None:
            eb.publish_one(s.TOPIC_NODE, "NodeDeregistered", node_id, index)
        self._notify()

    def update_node_status(self, index: int, node_id: str, status: str) -> None:
        """(state_store.go:473)."""
        with self._lock:
            existing = self.nodes_table.get(node_id)
            if existing is None:
                raise KeyError(f"node not found: {node_id}")
            node = existing.copy()
            node.status = status
            node.modify_index = index
            self.nodes_table[node_id] = node
            if self._columns is not None:
                self._columns.set_eligible(node_id, node.ready())
            self._bump("nodes", index)
        eb = self.event_broker
        if eb is not None:
            eb.publish_one(s.TOPIC_NODE, "NodeStatusUpdated", node_id, index,
                           {"Status": status, "Previous": existing.status})
        self._notify()

    def update_node_drain(self, index: int, node_id: str, drain: bool) -> None:
        """(state_store.go:508)."""
        with self._lock:
            existing = self.nodes_table.get(node_id)
            if existing is None:
                raise KeyError(f"node not found: {node_id}")
            node = existing.copy()
            node.drain = drain
            node.modify_index = index
            self.nodes_table[node_id] = node
            if self._columns is not None:
                self._columns.set_eligible(node_id, node.ready())
            self._bump("nodes", index)
        eb = self.event_broker
        if eb is not None:
            eb.publish_one(s.TOPIC_NODE, "NodeDrainUpdated", node_id, index,
                           {"Drain": drain})
        self._notify()

    def node_by_id(self, ws: Optional[WatchSet], node_id: str) -> Optional[s.Node]:
        if ws is not None:
            ws.add(self, "nodes")
        with self._lock:
            return self.nodes_table.get(node_id)

    def nodes(self, ws: Optional[WatchSet] = None) -> List[s.Node]:
        if ws is not None:
            ws.add(self, "nodes")
        with self._lock:
            return list(self.nodes_table.values())

    def nodes_by_id_prefix(self, ws: Optional[WatchSet], prefix: str) -> List[s.Node]:
        if ws is not None:
            ws.add(self, "nodes")
        with self._lock:
            return [n for nid, n in self.nodes_table.items() if nid.startswith(prefix)]

    # -- jobs --------------------------------------------------------------

    def upsert_job(self, index: int, job: s.Job) -> None:
        """(state_store.go:585) — bumps version on change, keeps bounded
        version history, maintains the job summary."""
        with self._lock:
            job = job.copy()
            existing = self.jobs_table.get(job.id)
            if existing is not None:
                job.create_index = existing.create_index
                job.modify_index = index
                job.job_modify_index = index
                job.version = existing.version + 1
            else:
                job.create_index = index
                job.modify_index = index
                job.job_modify_index = index
                job.version = 0
            job.status = self._get_job_status(job, eval_delete=False)

            self._update_summary_with_job(index, job)
            self._upsert_job_version(index, job)
            self.jobs_table[job.id] = job
            self._bump("jobs", index)
        eb = self.event_broker
        if eb is not None:
            eb.publish_one(s.TOPIC_JOB, "JobRegistered", job.id, index,
                           {"Type": job.type, "Status": job.status,
                            "Version": job.version, "Stop": job.stop,
                            "Namespace": job.namespace})
        self._notify()

    def _upsert_job_version(self, index: int, job: s.Job) -> None:
        history = self.job_versions.setdefault(job.id, [])
        history.insert(0, job)
        history.sort(key=lambda j: -j.version)
        del history[JOB_TRACKED_VERSIONS:]

    def delete_job(self, index: int, job_id: str) -> None:
        """(state_store.go:653) — removes job, versions, summary."""
        with self._lock:
            if job_id not in self.jobs_table:
                raise KeyError(f"job not found: {job_id}")
            del self.jobs_table[job_id]
            self.job_versions.pop(job_id, None)
            self.job_summary_table.pop(job_id, None)
            self.periodic_launch_table.pop(job_id, None)
            self._bump("jobs", index)
            self._bump("job_summary", index)
        eb = self.event_broker
        if eb is not None:
            eb.publish_one(s.TOPIC_JOB, "JobDeregistered", job_id, index)
        self._notify()

    def job_by_id(self, ws: Optional[WatchSet], job_id: str) -> Optional[s.Job]:
        if ws is not None:
            ws.add(self, "jobs")
        with self._lock:
            return self.jobs_table.get(job_id)

    def jobs(self, ws: Optional[WatchSet] = None) -> List[s.Job]:
        if ws is not None:
            ws.add(self, "jobs")
        with self._lock:
            return list(self.jobs_table.values())

    def jobs_by_id_prefix(self, ws: Optional[WatchSet], prefix: str) -> List[s.Job]:
        if ws is not None:
            ws.add(self, "jobs")
        with self._lock:
            return [j for jid, j in self.jobs_table.items() if jid.startswith(prefix)]

    def jobs_by_periodic(self, ws: Optional[WatchSet], periodic: bool) -> List[s.Job]:
        if ws is not None:
            ws.add(self, "jobs")
        with self._lock:
            return [j for j in self.jobs_table.values() if j.is_periodic() == periodic]

    def jobs_by_scheduler(self, ws: Optional[WatchSet], sched_type: str) -> List[s.Job]:
        if ws is not None:
            ws.add(self, "jobs")
        with self._lock:
            return [j for j in self.jobs_table.values() if j.type == sched_type]

    def jobs_by_gc(self, ws: Optional[WatchSet], gc: bool) -> List[s.Job]:
        if ws is not None:
            ws.add(self, "jobs")
        with self._lock:
            out = []
            for j in self.jobs_table.values():
                # batch jobs (and parameterized/periodic children) are GC-able
                gcable = j.type == s.JOB_TYPE_BATCH or j.parent_id != ""
                if gcable == gc:
                    out.append(j)
            return out

    def job_versions_by_id(self, ws: Optional[WatchSet], job_id: str) -> List[s.Job]:
        if ws is not None:
            ws.add(self, "jobs")
        with self._lock:
            return list(self.job_versions.get(job_id, []))

    def job_by_id_and_version(
        self, ws: Optional[WatchSet], job_id: str, version: int
    ) -> Optional[s.Job]:
        for j in self.job_versions_by_id(ws, job_id):
            if j.version == version:
                return j
        return None

    # -- job summaries -----------------------------------------------------

    def upsert_job_summary(self, index: int, summary: s.JobSummary) -> None:
        with self._lock:
            summary = summary.copy()
            summary.modify_index = index
            if summary.create_index == 0:
                summary.create_index = index
            self.job_summary_table[summary.job_id] = summary
            self._bump("job_summary", index)
        self._notify()

    def delete_job_summary(self, index: int, job_id: str) -> None:
        with self._lock:
            self.job_summary_table.pop(job_id, None)
            self._bump("job_summary", index)
        self._notify()

    def job_summary_by_id(self, ws: Optional[WatchSet], job_id: str) -> Optional[s.JobSummary]:
        if ws is not None:
            ws.add(self, "job_summary")
        with self._lock:
            return self.job_summary_table.get(job_id)

    def job_summaries(self, ws: Optional[WatchSet] = None) -> List[s.JobSummary]:
        if ws is not None:
            ws.add(self, "job_summary")
        with self._lock:
            return list(self.job_summary_table.values())

    def _update_summary_with_job(self, index: int, job: s.Job) -> None:
        """Create/extend the summary when a job is upserted
        (state_store.go:2159)."""
        summary = self.job_summary_table.get(job.id)
        if summary is None:
            summary = s.JobSummary(job_id=job.id, create_index=index)
        else:
            summary = summary.copy()
        changed = False
        for tg in job.task_groups:
            if tg.name not in summary.summary:
                summary.summary[tg.name] = s.TaskGroupSummary()
                changed = True
        if changed or summary.modify_index == 0:
            summary.modify_index = index
            self.job_summary_table[job.id] = summary
            self._bump("job_summary", index)

    # -- periodic launches -------------------------------------------------

    def upsert_periodic_launch(self, index: int, launch: PeriodicLaunch) -> None:
        with self._lock:
            existing = self.periodic_launch_table.get(launch.id)
            launch = PeriodicLaunch(launch.id, launch.launch,
                                    existing.create_index if existing else index, index)
            self.periodic_launch_table[launch.id] = launch
            self._bump("periodic_launch", index)
        self._notify()

    def delete_periodic_launch(self, index: int, job_id: str) -> None:
        with self._lock:
            self.periodic_launch_table.pop(job_id, None)
            self._bump("periodic_launch", index)
        self._notify()

    def periodic_launch_by_id(self, ws: Optional[WatchSet], job_id: str) -> Optional[PeriodicLaunch]:
        if ws is not None:
            ws.add(self, "periodic_launch")
        with self._lock:
            return self.periodic_launch_table.get(job_id)

    def periodic_launches(self, ws: Optional[WatchSet] = None) -> List[PeriodicLaunch]:
        if ws is not None:
            ws.add(self, "periodic_launch")
        with self._lock:
            return list(self.periodic_launch_table.values())

    # -- evals -------------------------------------------------------------

    def upsert_evals(self, index: int, evals: List[s.Evaluation]) -> None:
        """(state_store.go:1123) — also syncs queued counts into summaries
        and cancels blocked evals obsoleted by a successful one."""
        with self._lock:
            jobs: Dict[str, str] = {}
            for ev in evals:
                self._nested_upsert_eval(index, ev)
                jobs.setdefault(ev.job_id, "")
            self._set_job_statuses(index, jobs, eval_delete=False)
            self._bump("evals", index)
        eb = self.event_broker
        if eb is not None:
            eb.publish([eb.make_event(
                s.TOPIC_EVAL, "EvalUpdated", ev.id, index,
                {"Status": ev.status, "JobID": ev.job_id,
                 "TriggeredBy": ev.triggered_by, "NodeID": ev.node_id,
                 "Namespace": ev.namespace},
                eval_id=ev.id) for ev in evals])
        self._notify()

    def _nested_upsert_eval(self, index: int, ev: s.Evaluation) -> None:
        ev = ev.copy()
        existing = self.evals_table.get(ev.id)
        if existing is not None:
            ev.create_index = existing.create_index
        else:
            ev.create_index = index
        ev.modify_index = index

        summary = self.job_summary_table.get(ev.job_id)
        if summary is not None and ev.queued_allocations:
            summary = summary.copy()
            changed = False
            for tg, num in ev.queued_allocations.items():
                tgs = summary.summary.get(tg)
                if tgs is not None and tgs.queued != num:
                    tgs.queued = num
                    changed = True
            if changed:
                summary.modify_index = index
                self.job_summary_table[ev.job_id] = summary
                self._bump("job_summary", index)

        # A successful eval cancels the job's blocked evals.
        if ev.status == s.EVAL_STATUS_COMPLETE and not ev.failed_tg_allocs:
            for eid in list(self._idx_get(self._evals_by_job, ev.job_id)):
                blocked = self.evals_table.get(eid)
                if blocked is not None and blocked.status == s.EVAL_STATUS_BLOCKED:
                    cancelled = blocked.copy()
                    cancelled.status = s.EVAL_STATUS_CANCELLED
                    cancelled.status_description = f"evaluation {ev.id!r} successful"
                    cancelled.modify_index = index
                    self.evals_table[eid] = cancelled

        self.evals_table[ev.id] = ev
        self._idx_add(self._evals_by_job, ev.job_id, ev.id)

    def delete_eval(self, index: int, eval_ids: List[str], alloc_ids: List[str]) -> None:
        """(state_store.go:1235) — GC path for evals + their allocs."""
        deleted: List[str] = []
        with self._lock:
            jobs: Dict[str, str] = {}
            for eid in eval_ids:
                ev = self.evals_table.pop(eid, None)
                if ev is None:
                    continue
                self._idx_discard(self._evals_by_job, ev.job_id, eid)
                jobs.setdefault(ev.job_id, "")
                deleted.append(eid)
            for aid in alloc_ids:
                self._remove_alloc(aid, index)
            self._bump("evals", index)
            self._bump("allocs", index)
            self._set_job_statuses(index, jobs, eval_delete=True)
        eb = self.event_broker
        if eb is not None and deleted:
            eb.publish([eb.make_event(s.TOPIC_EVAL, "EvalDeleted", eid,
                                      index, eval_id=eid)
                        for eid in deleted])
        self._notify()

    def eval_by_id(self, ws: Optional[WatchSet], eval_id: str) -> Optional[s.Evaluation]:
        if ws is not None:
            ws.add(self, "evals")
        with self._lock:
            return self.evals_table.get(eval_id)

    def evals_by_id_prefix(self, ws: Optional[WatchSet], prefix: str) -> List[s.Evaluation]:
        if ws is not None:
            ws.add(self, "evals")
        with self._lock:
            return [e for eid, e in self.evals_table.items() if eid.startswith(prefix)]

    def evals_by_job(self, ws: Optional[WatchSet], job_id: str) -> List[s.Evaluation]:
        if ws is not None:
            ws.add(self, "evals")
        with self._lock:
            return [self.evals_table[eid] for eid in self._idx_get(self._evals_by_job, job_id)
                    if eid in self.evals_table]

    def evals(self, ws: Optional[WatchSet] = None) -> List[s.Evaluation]:
        if ws is not None:
            ws.add(self, "evals")
        with self._lock:
            return list(self.evals_table.values())

    # -- allocs ------------------------------------------------------------

    def upsert_allocs(self, index: int, allocs: List[s.Allocation],
                      owned: bool = False) -> None:
        """(state_store.go:1435).  ``owned=True`` means the caller hands the
        objects over (plan apply constructs fresh allocs): the store inserts
        them directly, exactly like go-memdb inserting the FSM's pointers."""
        eb = self.event_broker
        events: Optional[List[s.Event]] = [] if eb is not None else None
        with self._lock:
            self._upsert_allocs_impl(index, allocs, owned, events=events)
        if events:
            eb.publish(events)
        self._notify()

    @staticmethod
    def _alloc_event_type(alloc: s.Allocation,
                          existing: Optional[s.Allocation]) -> str:
        """Event type for one alloc write: the transition an operator
        cares about, not the table mechanics."""
        if alloc.client_status == s.ALLOC_CLIENT_STATUS_LOST:
            return "AllocLost"
        if alloc.desired_status == s.ALLOC_DESIRED_STATUS_EVICT:
            return "AllocEvicted"
        if alloc.desired_status == s.ALLOC_DESIRED_STATUS_STOP:
            return "AllocStopped"
        if existing is None:
            return "AllocPlaced"
        return "AllocUpdated"

    def _upsert_allocs_impl(self, index: int, allocs: List[s.Allocation],
                            owned: bool = False,
                            events: Optional[List[s.Event]] = None,
                            plan_eval_id: str = "") -> None:
        eb = self.event_broker
        jobs: Dict[str, str] = {}
        summary_cache: Dict[str, s.JobSummary] = {}
        # Fresh-alloc index additions are BATCHED per key: _idx_add's
        # copy-on-write union is O(|index value|), so adding N fresh
        # allocs of one job one-by-one copies a growing set N times —
        # O(N^2) (measured: the preempt bench's 70k-filler insert spent
        # 133s here, which is what timed config_preempt out).  Fresh ids
        # are never already present, so one O(1) _idx_append cons per
        # touched key replaces the per-alloc unions.
        new_by_node: Dict[str, List[str]] = {}
        new_by_job: Dict[str, List[str]] = {}
        new_by_eval: Dict[str, List[str]] = {}
        for alloc in allocs:
            # Shallow copy unless owned: stored objects are immutable
            # snapshots by convention (go-memdb inserts the caller's pointer
            # outright, state_store.go:1435); the copy only isolates the
            # top-level index/status fields this method mutates below.
            if not owned:
                alloc = s._fast_copy(alloc)
            existing = self._get_alloc(alloc.id)
            if existing is None:
                alloc.create_index = index
                alloc.modify_index = index
                alloc.alloc_modify_index = index
            else:
                alloc.create_index = existing.create_index
                alloc.modify_index = index
                alloc.alloc_modify_index = index
                # The client is the authority on these fields — keep them,
                # EXCEPT when the scheduler is marking the alloc lost
                # (state_store.go:1480-1489).
                alloc.task_states = existing.task_states
                if alloc.client_status != s.ALLOC_CLIENT_STATUS_LOST:
                    alloc.client_status = existing.client_status
                    alloc.client_description = existing.client_description
            self._update_summary_with_alloc(index, alloc, existing, summary_cache)
            if alloc.job is None and existing is not None:
                alloc.job = existing.job
            self._log_transition(index, existing, alloc)
            self.allocs_table[alloc.id] = alloc
            if events is not None:
                events.append(eb.make_event(
                    s.TOPIC_ALLOC, self._alloc_event_type(alloc, existing),
                    alloc.id, index,
                    {"JobID": alloc.job_id, "NodeID": alloc.node_id,
                     "TaskGroup": alloc.task_group,
                     "DesiredStatus": alloc.desired_status,
                     "ClientStatus": alloc.client_status,
                     "Namespace": alloc.namespace},
                    eval_id=plan_eval_id or alloc.eval_id))
            # Index only keys that actually changed: _idx_add's copy-on-
            # write set union is O(|index|), so the previously
            # unconditional re-add of 10k evictions against a 70k-alloc
            # job copied the whole id set per alloc (measured 17s of a
            # 33s preemption-bench finalize).  Updates keep node/job ids;
            # in-place updates re-home eval_id, which stays covered.
            if existing is None:
                new_by_node.setdefault(alloc.node_id, []).append(alloc.id)
                new_by_job.setdefault(alloc.job_id, []).append(alloc.id)
                new_by_eval.setdefault(alloc.eval_id, []).append(alloc.id)
            else:
                if alloc.node_id != existing.node_id:
                    self._idx_add(self._allocs_by_node, alloc.node_id,
                                  alloc.id)
                if alloc.job_id != existing.job_id:
                    self._idx_add(self._allocs_by_job, alloc.job_id,
                                  alloc.id)
                if alloc.eval_id != existing.eval_id:
                    self._idx_add(self._allocs_by_eval, alloc.eval_id,
                                  alloc.id)

            if alloc.job is not None:
                forced = ""
                if not alloc.terminal_status():
                    forced = s.JOB_STATUS_RUNNING
                jobs[alloc.job_id] = jobs.get(alloc.job_id) or forced
        for idx_dict, new_ids in ((self._allocs_by_node, new_by_node),
                                  (self._allocs_by_job, new_by_job),
                                  (self._allocs_by_eval, new_by_eval)):
            for key, ids in new_ids.items():
                self._idx_append(idx_dict, key,
                                 ids[0] if len(ids) == 1 else ids)
        self._set_job_statuses(index, jobs, eval_delete=False)
        self._bump("allocs", index)

    def update_allocs_from_client(self, index: int, allocs: List[s.Allocation]) -> None:
        """Merge client-authoritative fields (state_store.go:1367)."""
        eb = self.event_broker
        events: Optional[List[s.Event]] = [] if eb is not None else None
        with self._lock:
            for client_alloc in allocs:
                existing = self._get_alloc(client_alloc.id)
                if existing is None:
                    continue
                updated = s._fast_copy(existing)
                updated.client_status = client_alloc.client_status
                updated.client_description = client_alloc.client_description
                updated.task_states = {
                    k: v.copy() for k, v in client_alloc.task_states.items()
                }
                updated.modify_index = index
                self._update_summary_with_alloc(index, updated, existing)
                self._log_transition(index, existing, updated)
                self.allocs_table[client_alloc.id] = updated
                if events is not None:
                    events.append(eb.make_event(
                        s.TOPIC_ALLOC, "AllocClientUpdated", updated.id,
                        index,
                        {"JobID": updated.job_id, "NodeID": updated.node_id,
                         "ClientStatus": updated.client_status,
                         "Previous": existing.client_status},
                        eval_id=updated.eval_id))
                forced = "" if updated.terminal_status() else s.JOB_STATUS_RUNNING
                self._set_job_statuses(index, {existing.job_id: forced}, eval_delete=False)
            self._bump("allocs", index)
        if events:
            eb.publish(events)
        self._notify()

    def _remove_alloc(self, alloc_id: str, index: int = 0) -> None:
        if self._pending_slabs:
            self._materialize_pending()
        alloc = self.allocs_table.pop(alloc_id, None)
        if alloc is None:
            return
        if type(alloc) is s.AllocSlab:
            i = alloc.id_index(alloc_id)
            node_id = alloc.node_ids[i]
            proto = alloc.proto
            job_id, eval_id = proto.job_id, proto.eval_id
            # A network slab's row holds ports of its own.
            row = alloc.materialize(i) if alloc.ips else proto
        else:
            node_id, job_id, eval_id = alloc.node_id, alloc.job_id, alloc.eval_id
            row = alloc
        if index and not row.terminal_status():
            c, m, d, i = self._usage_vec(row)
            (bw, dyn), ports = self._net_held(row)
            self._log_usage(index, node_id, (-c, -m, -d, -i), (-bw, -dyn),
                            _released(ports))
            self._ns_fold(row.namespace, -c, -m, -d, -i, -1)
        self._idx_discard(self._allocs_by_node, node_id, alloc_id)
        self._idx_discard(self._allocs_by_job, job_id, alloc_id)
        self._idx_discard(self._allocs_by_eval, eval_id, alloc_id)

    def alloc_by_id(self, ws: Optional[WatchSet], alloc_id: str) -> Optional[s.Allocation]:
        if ws is not None:
            ws.add(self, "allocs")
        with self._lock:
            return self._get_alloc(alloc_id)

    def allocs_by_id_prefix(self, ws: Optional[WatchSet], prefix: str) -> List[s.Allocation]:
        if ws is not None:
            ws.add(self, "allocs")
        with self._lock:
            if self._pending_slabs:
                self._materialize_pending()
            return [self._get_alloc(aid) for aid in list(self.allocs_table)
                    if aid.startswith(prefix)]

    def allocs_by_node(self, ws: Optional[WatchSet], node_id: str) -> List[s.Allocation]:
        if ws is not None:
            ws.add(self, "allocs")
        with self._lock:
            if self._pending_slabs:
                self._materialize_pending()
            return [self._get_alloc(aid) for aid in self._idx_get(self._allocs_by_node, node_id)
                    if aid in self.allocs_table]

    def allocs_by_node_terminal(
        self, ws: Optional[WatchSet], node_id: str, terminal: bool
    ) -> List[s.Allocation]:
        """(state_store.go:1592) — the scheduler's ProposedAllocs source."""
        return [a for a in self.allocs_by_node(ws, node_id)
                if a.terminal_status() == terminal]

    def _node_entries(self, node_id: str):
        """``(table value, alloc id)`` of every row on ``node_id``, the
        deferred slab indexing flushed first.  Caller holds the lock."""
        if self._pending_slabs:
            self._materialize_pending()
        get = self.allocs_table.get
        for aid in self._idx_get(self._allocs_by_node, node_id):
            v = get(aid)
            if v is not None:
                yield v, aid

    def live_rows_on_node(self, node_id: str, leave_out=()) -> list:
        """``allocs_by_node_terminal(None, node_id, False)`` less the
        rows whose ids are in ``leave_out`` (what the plan stops,
        preempts or updates in place), for the plan applier's per-node
        re-check, which reads the rows of every node it touches, pass
        after pass: a slab's row is its prototype (``AllocSlab.row``: a
        network slab's read in place), none is materialized or cached
        back into the table.  A plain slab's rows share one prototype,
        so a row is left out by the id it is stored under, not by the
        id of what stands for it."""
        out = []
        with self._lock:
            for v, aid in self._node_entries(node_id):
                if aid in leave_out:
                    continue
                if type(v) is s.AllocSlab:
                    if v.proto.terminal_status():
                        continue
                    v = v.row(v.id_index(aid))
                elif v.terminal_status():
                    continue
                out.append(v)
        return out

    def node_networks(self, node_id: str) -> List[tuple]:
        """``held_networks()`` of every live row on ``node_id``, what
        ``NetworkIndex.add_allocs`` reads: a network slab's rows from its
        columns (``AllocSlab.row``), no Allocation materialized."""
        out: List[tuple] = []
        for row in self.live_rows_on_node(node_id):
            out.extend(row.held_networks())
        return out

    def allocs_by_job(self, ws: Optional[WatchSet], job_id: str, all_allocs: bool = False) -> List[s.Allocation]:
        """(state_store.go:1615).  When all_allocs is False, allocs from a
        previous incarnation of a re-registered job are filtered to the
        summary's create_index."""
        if ws is not None:
            ws.add(self, "allocs")
        with self._lock:
            if self._pending_slabs:
                self._materialize_job_pending(job_id)
            out = [self._get_alloc(aid) for aid in self._idx_get(self._allocs_by_job, job_id)
                   if aid in self.allocs_table]
            if all_allocs:
                return out
            summary = self.job_summary_table.get(job_id)
            if summary is None:
                return out
            return [a for a in out
                    if a.job is None or a.job.create_index == summary.create_index]

    def allocs_by_eval(self, ws: Optional[WatchSet], eval_id: str) -> List[s.Allocation]:
        if ws is not None:
            ws.add(self, "allocs")
        with self._lock:
            if self._pending_slabs:
                self._materialize_pending()
            return [self._get_alloc(aid) for aid in self._idx_get(self._allocs_by_eval, eval_id)
                    if aid in self.allocs_table]

    def allocs(self, ws: Optional[WatchSet] = None) -> List[s.Allocation]:
        if ws is not None:
            ws.add(self, "allocs")
        with self._lock:
            if self._pending_slabs:
                self._materialize_pending()
            return [self._get_alloc(aid) for aid in list(self.allocs_table)]

    # -- non-materializing row reads (batch encode path) -------------------
    #
    # The TPU batch scheduler only needs (node_id, resources, status)
    # per alloc to encode cluster usage; materializing every slab slot
    # into a throwaway snapshot each batch would re-pay the per-alloc
    # cost the slabs exist to avoid.  These return the shared slab PROTO
    # as the row for slot entries (node_id supplied separately) — rows
    # are read-only by contract.  A network slab's rows differ in their
    # ports, so each is read in place (``structs.SlabRow``): these are
    # the full walks the usage and port references read.

    @staticmethod
    def _slab_rows(slab: s.AllocSlab, positions):
        """``(node_id, row)`` of ``slab``'s rows at ``positions``."""
        if not slab.ips:
            proto = slab.proto
            return [(slab.node_ids[i], proto) for i in positions]
        return [(slab.node_ids[i], s.SlabRow(slab, i)) for i in positions]

    def alloc_rows(self, ws: Optional[WatchSet] = None
                   ) -> List[Tuple[str, s.Allocation]]:
        """(node_id, row) for every alloc, without slab materialization."""
        if ws is not None:
            ws.add(self, "allocs")
        with self._lock:
            out = []
            # Pending slabs (deferred indexing) have no replaced/removed
            # entries yet — emit their rows directly, no drain needed.
            for slab in self._pending_slabs:
                out.extend(self._slab_rows(slab, range(len(slab))))
            seen_slabs = set()
            table = self.allocs_table
            for aid, v in table.items():
                if type(v) is s.AllocSlab:
                    if id(v) in seen_slabs:
                        continue
                    seen_slabs.add(id(v))
                    # One pass over the slab's columns; ids whose table
                    # entry was replaced (client update) or removed are
                    # skipped — their real row is seen via its own entry.
                    out.extend(self._slab_rows(
                        v, [i for i, aid2 in enumerate(v.ids)
                            if table.get(aid2) is v]))
                else:
                    out.append((v.node_id, v))
            return out

    def alloc_rows_by_job(self, ws: Optional[WatchSet], job_id: str
                          ) -> List[Tuple[str, s.Allocation]]:
        """(node_id, row) for a job's allocs, without materialization."""
        if ws is not None:
            ws.add(self, "allocs")
        with self._lock:
            out = []
            for slab in self._pending_by_job.get(job_id, ()):
                out.extend(self._slab_rows(slab, range(len(slab))))
            for aid in self._idx_get(self._allocs_by_job, job_id):
                v = self.allocs_table.get(aid)
                if v is None:
                    continue
                if type(v) is s.AllocSlab:
                    out.extend(self._slab_rows(v, (v.id_index(aid),)))
                else:
                    out.append((v.node_id, v))
            return out

    # -- usage-delta feed (ops/resident.py) --------------------------------
    #
    # Caller holds the lock for every _log_* helper.  The vectors use
    # the canonical structs.alloc_usage_vec basis (same as
    # ops/encode.apply_alloc_usage's numpy twin), so a consumer
    # replaying the feed lands on bit-identical usage rows.

    _usage_vec = staticmethod(s.alloc_usage_vec)
    _net_held = staticmethod(s.alloc_net_held)

    def _log_ensure_owned(self) -> None:
        """Copy-on-write for a snapshot's shared log prefix: the first
        write by a non-owning store takes a private copy so the parent's
        feed never sees hypothetical (dry-run) deltas."""
        if not self._alloc_log_owned:
            self._alloc_log = self._alloc_log[:self._alloc_log_len]
            self._alloc_log_owned = True

    def _log_trim(self) -> None:
        if self._alloc_log_weight <= ALLOC_LOG_CAP:
            return
        # Drop the oldest half (by weight) and raise the floor to the
        # last dropped entry's index: a consumer cached at/under the
        # floor can no longer be answered and must full re-encode.
        # Copy-on-trim: the survivor slice is a NEW list, so snapshot
        # cursors into the old object stay valid.
        target = ALLOC_LOG_CAP // 2
        log = self._alloc_log
        drop = 0
        while drop < len(log) and self._alloc_log_weight > target:
            entry = log[drop]
            self._alloc_log_weight -= (len(entry[1].ids)
                                       if len(entry) == 2 else 1)
            self._alloc_log_floor = max(self._alloc_log_floor, entry[0])
            drop += 1
        self._alloc_log = log[drop:]
        self._alloc_log_len = len(self._alloc_log)

    def _log_usage(self, index: int, node_id: str,
                   delta: Tuple[int, int, int, int],
                   net: Tuple[int, int] = (0, 0),
                   ports: Tuple[int, ...] = ()) -> None:
        """One row's entry: ``(index, node_id, delta)``, with the change
        of what its networks hold (``structs.alloc_net_vec``) as a fourth
        element when there is one, and as a fifth, when the write takes
        or frees a port, the port values: ``v`` taken, ``-v`` freed."""
        if ((delta == (0, 0, 0, 0) and net == (0, 0) and not ports)
                or not node_id):
            return
        self._log_ensure_owned()
        self._alloc_log.append(
            (index, node_id, delta, net, ports) if ports
            else (index, node_id, delta) if net == (0, 0)
            else (index, node_id, delta, net))
        self._alloc_log_len += 1
        self._alloc_log_weight += 1
        self._log_trim()

    def _log_slab(self, index: int, slab: s.AllocSlab) -> None:
        if not slab.ids:
            return
        self._log_ensure_owned()
        self._alloc_log.append((index, slab))
        self._alloc_log_len += 1
        self._alloc_log_weight += len(slab.ids)
        self._log_trim()
        # Tenant fold: one amortized update per slab, n identical live
        # rows sharing the proto's usage vector.
        proto = slab.proto
        if not proto.terminal_status():
            n = len(slab.ids)
            c, m, d, i = self._usage_vec(proto)
            self._ns_fold(proto.namespace, c * n, m * n, d * n, i * n, n)

    def _log_transition(self, index: int, existing: Optional[s.Allocation],
                        updated: s.Allocation) -> None:
        """Log the usage delta of one alloc write (old row → new row),
        including node moves."""
        old_live = existing is not None and not existing.terminal_status()
        new_live = not updated.terminal_status()
        if old_live and new_live and existing.node_id == updated.node_id:
            ov, nv = self._usage_vec(existing), self._usage_vec(updated)
            on, op = self._net_held(existing)
            nn, np_ = self._net_held(updated)
            self._log_usage(index, updated.node_id,
                            (nv[0] - ov[0], nv[1] - ov[1],
                             nv[2] - ov[2], nv[3] - ov[3]),
                            (nn[0] - on[0], nn[1] - on[1]),
                            () if op == np_ else _released(op) + np_)
            if nv != ov:
                self._ns_fold(updated.namespace, nv[0] - ov[0],
                              nv[1] - ov[1], nv[2] - ov[2], nv[3] - ov[3], 0)
            return
        if old_live:
            c, m, d, i = self._usage_vec(existing)
            (bw, dyn), ports = self._net_held(existing)
            self._log_usage(index, existing.node_id, (-c, -m, -d, -i),
                            (-bw, -dyn), _released(ports))
            self._ns_fold(existing.namespace, -c, -m, -d, -i, -1)
        if new_live:
            v = self._usage_vec(updated)
            net, ports = self._net_held(updated)
            self._log_usage(index, updated.node_id, v, net, ports)
            self._ns_fold(updated.namespace, v[0], v[1], v[2], v[3], 1)

    def alloc_log_since(self, index: int) -> Optional[List[tuple]]:
        """The usage-delta log's raw entries with raft index > ``index``
        — ``(index, node_id, delta[, net[, ports]])`` per single row
        (``net`` when what its networks hold changed, ``ports`` when the
        write took or freed a port), ``(index, slab)`` per bulk
        insert, unexpanded — or None when the log can no longer
        answer.  The array readers' feed (columnar.fold_usage, the
        resident mirror): a slab stays one entry, so nothing is paid per
        allocation here."""
        import bisect

        with self._lock:
            if index < self._alloc_log_floor:
                return None
            # Entries are appended with non-decreasing raft indexes, so
            # the skip to the first relevant entry is a bisect, not a
            # full O(log-size) scan.  The slice is bounded by this
            # store's length cursor: a shared parent list may have grown
            # past it (those entries belong to a newer world).
            log, n = self._alloc_log, self._alloc_log_len
            start = bisect.bisect_right(log, index, 0, n,
                                        key=lambda e: e[0])
            return log[start:n]

    def allocs_since(self, index: int
                     ) -> Optional[List[Tuple[str, Tuple[int, int, int, int]]]]:
        """Per-node usage deltas for every alloc write with raft index
        > ``index``: ``alloc_log_since`` expanded to one ``(node_id,
        delta)`` tuple per (entry, node).  Returns None when the log can
        no longer answer (the requested index fell below the trim floor,
        or predates this store's log), which forces the consumer to full
        re-encode."""
        with self._lock:
            entries = self.alloc_log_since(index)
            if entries is None:
                return None
            out: List[Tuple[str, Tuple[int, int, int, int]]] = []
            for entry in entries:
                if len(entry) == 2:  # (index, slab): expand per node
                    slab = entry[1]
                    vec = self._usage_vec(slab.proto)
                    for nid, cnt in slab.node_counts().items():
                        out.append((nid, (vec[0] * cnt, vec[1] * cnt,
                                          vec[2] * cnt, vec[3] * cnt)))
                else:
                    out.append((entry[1], entry[2]))
            return out

    # -- vault accessors ---------------------------------------------------

    def upsert_vault_accessors(self, index: int, accessors: List[VaultAccessor]) -> None:
        with self._lock:
            for acc in accessors:
                acc = dataclasses.replace(acc, create_index=index)
                self.vault_accessors_table[acc.accessor] = acc
                self._idx_add(self._vault_by_alloc, acc.alloc_id, acc.accessor)
                self._idx_add(self._vault_by_node, acc.node_id, acc.accessor)
            self._bump("vault_accessors", index)
        self._notify()

    def delete_vault_accessors(self, index: int, accessors: List[VaultAccessor]) -> None:
        with self._lock:
            for acc in accessors:
                stored = self.vault_accessors_table.pop(acc.accessor, None)
                if stored is not None:
                    self._idx_discard(self._vault_by_alloc, stored.alloc_id,
                                      acc.accessor)
                    self._idx_discard(self._vault_by_node, stored.node_id,
                                      acc.accessor)
            self._bump("vault_accessors", index)
        self._notify()

    # -- deployments -------------------------------------------------------

    def upsert_deployment(self, index: int, deployment: s.Deployment,
                          cancel_prior: bool = False) -> None:
        """(state_store.go:221 UpsertDeployment).  cancel_prior marks any
        other ACTIVE deployment of the same job cancelled
        (state_store.go:266 cancelPriorDeployments)."""
        cancelled: List[str] = []
        with self._lock:
            d = deployment.copy()
            existing = self.deployments_table.get(d.id)
            if existing is None:
                d.create_index = index
            else:
                d.create_index = existing.create_index
            d.modify_index = index
            if cancel_prior:
                for other in list(self.deployments_table.values()):
                    if (other.id != d.id and other.job_id == d.job_id
                            and other.active()):
                        upd = other.copy()
                        upd.status = s.DEPLOYMENT_STATUS_CANCELLED
                        upd.status_description = (
                            "made obsolete by a newer deployment")
                        upd.modify_index = index
                        self.deployments_table[other.id] = upd
                        cancelled.append(other.id)
            self.deployments_table[d.id] = d
            self._bump("deployment", index)
        eb = self.event_broker
        if eb is not None:
            events = [eb.make_event(
                s.TOPIC_DEPLOYMENT, "DeploymentUpserted", d.id, index,
                {"JobID": d.job_id, "Status": d.status})]
            events.extend(eb.make_event(
                s.TOPIC_DEPLOYMENT, "DeploymentStatusUpdated", did, index,
                {"Status": s.DEPLOYMENT_STATUS_CANCELLED})
                for did in cancelled)
            eb.publish(events)
        self._notify()

    def update_deployment_status(self, index: int,
                                 update: s.DeploymentStatusUpdate) -> None:
        """Apply a status transition (structs.go:379 DeploymentUpdates)."""
        with self._lock:
            existing = self.deployments_table.get(update.deployment_id)
            if existing is None:
                return
            d = existing.copy()
            d.status = update.status
            d.status_description = update.status_description
            d.modify_index = index
            self.deployments_table[d.id] = d
            self._bump("deployment", index)
        eb = self.event_broker
        if eb is not None:
            eb.publish_one(s.TOPIC_DEPLOYMENT, "DeploymentStatusUpdated",
                           d.id, index,
                           {"JobID": d.job_id, "Status": d.status})
        self._notify()

    def deployment_by_id(self, ws: Optional[WatchSet],
                         deployment_id: str) -> Optional[s.Deployment]:
        """(state_store.go:311)."""
        if ws is not None:
            ws.add(self, "deployment")
        with self._lock:
            return self.deployments_table.get(deployment_id)

    def deployments(self, ws: Optional[WatchSet] = None) -> List[s.Deployment]:
        """(state_store.go:298)."""
        if ws is not None:
            ws.add(self, "deployment")
        with self._lock:
            return list(self.deployments_table.values())

    def deployments_by_job(self, ws: Optional[WatchSet],
                           job_id: str) -> List[s.Deployment]:
        """(state_store.go:330 DeploymentsByJobID)."""
        if ws is not None:
            ws.add(self, "deployment")
        with self._lock:
            return [d for d in self.deployments_table.values()
                    if d.job_id == job_id]

    def latest_deployment_by_job(self, ws: Optional[WatchSet],
                                 job_id: str) -> Optional[s.Deployment]:
        """Newest deployment of a job by create index
        (state_store.go LatestDeploymentByJobID)."""
        out = self.deployments_by_job(ws, job_id)
        return max(out, key=lambda d: d.create_index) if out else None

    def delete_deployment(self, index: int, deployment_id: str) -> None:
        with self._lock:
            if self.deployments_table.pop(deployment_id, None) is not None:
                self._bump("deployment", index)
        self._notify()

    # -- namespaces (tenancy plane) -----------------------------------------

    def upsert_namespace(self, index: int, ns: s.Namespace) -> None:
        """Register/update a tenant (raft NAMESPACE_UPSERT apply)."""
        with self._lock:
            ns = ns.copy()
            existing = self.namespaces_table.get(ns.name)
            ns.create_index = (existing.create_index
                               if existing is not None else index)
            ns.modify_index = index
            self.namespaces_table[ns.name] = ns
            self._bump("namespaces", index)
        eb = self.event_broker
        if eb is not None:
            eb.publish_one(s.TOPIC_NAMESPACE, "NamespaceUpserted", ns.name,
                           index,
                           {"Namespace": ns.name,
                            "DequeueWeight": ns.dequeue_weight,
                            "MaxLiveAllocs": ns.max_live_allocs,
                            "MaxPendingEvals": ns.max_pending_evals})
        self._notify()

    def delete_namespace(self, index: int, name: str) -> None:
        with self._lock:
            if self.namespaces_table.pop(name, None) is not None:
                self._bump("namespaces", index)
        eb = self.event_broker
        if eb is not None:
            eb.publish_one(s.TOPIC_NAMESPACE, "NamespaceDeleted", name,
                           index, {"Namespace": name})
        self._notify()

    def namespace_by_name(self, ws: Optional[WatchSet],
                          name: str) -> Optional[s.Namespace]:
        if ws is not None:
            ws.add(self, "namespaces")
        with self._lock:
            return self.namespaces_table.get(name)

    def namespaces(self, ws: Optional[WatchSet] = None) -> List[s.Namespace]:
        if ws is not None:
            ws.add(self, "namespaces")
        with self._lock:
            return list(self.namespaces_table.values())

    def namespace_usage(self) -> Dict[str, Tuple[int, int, int, int, int]]:
        """Per-tenant (cpu, mem_mb, disk_mb, iops, live_allocs) fold —
        values are immutable tuples, the dict copy is a full fork."""
        with self._lock:
            return dict(self._ns_usage)

    def namespace_usage_one(
            self, name: str) -> Tuple[int, int, int, int, int]:
        """One tenant's usage row without forking the whole dict — the
        per-submit quota check's read."""
        with self._lock:
            return self._ns_usage.get(name or "default", (0, 0, 0, 0, 0))

    def drain_ns_dirty(self) -> Set[str]:
        """Namespaces whose usage changed since the last drain — the
        O(changed) feed behind the broker's DRF re-score."""
        with self._lock:
            dirty = self._ns_dirty
            self._ns_dirty = set()
            return dirty

    def _ns_fold(self, ns: str, dc: int, dm: int, dd: int, di: int,
                 dn: int) -> None:
        """Fold one alloc-write delta into the tenant's usage row.
        Caller holds the lock."""
        key = ns or "default"
        cur = self._ns_usage.get(key)
        if cur is None:
            cur = (0, 0, 0, 0, 0)
        self._ns_usage[key] = (cur[0] + dc, cur[1] + dm, cur[2] + dd,
                               cur[3] + di, cur[4] + dn)
        self._ns_dirty.add(key)

    def _rebuild_ns_usage(self) -> None:
        """Recompute the per-tenant fold from alloc rows (restore path —
        the fold, like the usage-delta log, is not persisted)."""
        usage: Dict[str, Tuple[int, int, int, int, int]] = {}
        vec = self._usage_vec
        for _nid, row in self.alloc_rows():
            if row.terminal_status():
                continue
            c, m, d, i = vec(row)
            key = row.namespace or "default"
            cur = usage.get(key, (0, 0, 0, 0, 0))
            usage[key] = (cur[0] + c, cur[1] + m, cur[2] + d,
                          cur[3] + i, cur[4] + 1)
        with self._lock:
            self._ns_usage = usage
            self._ns_dirty = set(usage)

    def vault_accessors(self, ws: Optional[WatchSet]) -> List[VaultAccessor]:
        if ws is not None:
            ws.add(self, "vault_accessors")
        with self._lock:
            return list(self.vault_accessors_table.values())

    def vault_accessor(self, ws: Optional[WatchSet], accessor: str) -> Optional[VaultAccessor]:
        if ws is not None:
            ws.add(self, "vault_accessors")
        with self._lock:
            return self.vault_accessors_table.get(accessor)

    def vault_accessors_by_alloc(self, ws: Optional[WatchSet], alloc_id: str) -> List[VaultAccessor]:
        if ws is not None:
            ws.add(self, "vault_accessors")
        with self._lock:
            return [self.vault_accessors_table[a] for a in self._idx_get(self._vault_by_alloc, alloc_id)
                    if a in self.vault_accessors_table]


    def vault_accessors_by_node(self, ws: Optional[WatchSet], node_id: str) -> List[VaultAccessor]:
        if ws is not None:
            ws.add(self, "vault_accessors")
        with self._lock:
            return [self.vault_accessors_table[a] for a in self._idx_get(self._vault_by_node, node_id)
                    if a in self.vault_accessors_table]

    # -- plan application --------------------------------------------------

    def upsert_plan_results(self, index: int, job: Optional[s.Job],
                            allocs: List[s.Allocation],
                            slabs: Optional[List[s.AllocSlab]] = None,
                            eval_id: str = "") -> None:
        """Apply a committed plan: denormalize the job onto allocs, rebuild
        combined resources, and upsert (state_store.go:89).  Columnar
        alloc slabs (the TPU batch path's bulk placements) are inserted in
        O(columns) — see _upsert_slabs_impl.  ``eval_id`` is the DRIVING
        eval of the plan: stop/evict/lost updates keep the original
        placement eval on the alloc row itself (AppendUpdate semantics),
        so the event stream needs the driving eval passed explicitly to
        correlate "which eval did this" across the incident timeline."""
        eb = self.event_broker
        events: Optional[List[s.Event]] = [] if eb is not None else None
        with self._lock:
            for alloc in allocs:
                if alloc.job is None and not alloc.terminal_status():
                    alloc.job = job
                if alloc.resources is None:
                    total = s.Resources()
                    for task_res in alloc.task_resources.values():
                        total.add(task_res)
                    total.add(alloc.shared_resources)
                    alloc.resources = total
            # Plan-result allocs are owned by the state store from here on
            # (the FSM decoded/constructed them; nothing else mutates them).
            self._upsert_allocs_impl(index, allocs, owned=True,
                                     events=events, plan_eval_id=eval_id)
            if slabs:
                for slab in slabs:
                    p = slab.proto
                    if p.job is None and not p.terminal_status():
                        p.job = job
                self._upsert_slabs_impl(index, slabs, events=events)
        if events:
            eb.publish(events)
        self._notify()

    def upsert_slabs(self, index: int, slabs: List[s.AllocSlab]) -> None:
        """Bulk columnar insert (the TPU batch placement path)."""
        eb = self.event_broker
        events: Optional[List[s.Event]] = [] if eb is not None else None
        with self._lock:
            self._upsert_slabs_impl(index, slabs, events=events)
        if events:
            eb.publish(events)
        self._notify()

    def _upsert_slabs_impl(self, index: int, slabs: List[s.AllocSlab],
                           events: Optional[List[s.Event]] = None) -> None:
        """Insert a fresh-allocation slab: the table value for each alloc
        id is the slab OBJECT itself (no per-alloc wrapper), per-alloc
        work is three index inserts, and everything else (summary, job
        status, create/modify indexes) is amortized across the slab.
        Slab allocs are always NEW (fresh uuids from the batch scheduler)
        — the update/merge semantics of _upsert_allocs_impl don't apply."""
        jobs: Dict[str, str] = {}
        for slab in slabs:
            ids = slab.ids
            if not ids:
                continue
            slab.create_index = index
            slab.modify_index = index
            proto = slab.proto
            self._idx_append(self._allocs_by_job, proto.job_id, ids)
            self._idx_append(self._allocs_by_eval, proto.eval_id, ids)
            # The usage log gets ONE entry per slab (expanded lazily by
            # allocs_since readers).
            self._log_slab(index, slab)
            self._install_slab(slab)
            if events is not None:
                # ONE event per slab, not per alloc: a 1M-ask batch must
                # not turn into 1M ring entries.  The count + job/eval
                # keys are what incident reconstruction needs.
                events.append(self.event_broker.make_event(
                    s.TOPIC_ALLOC, "AllocPlacedBulk", proto.job_id, index,
                    {"JobID": proto.job_id, "TaskGroup": proto.task_group,
                     "Count": len(ids), "Namespace": proto.namespace},
                    eval_id=proto.eval_id))
            self._update_summary_bulk(index, proto, len(ids))
            if proto.job is not None:
                forced = ("" if proto.terminal_status()
                          else s.JOB_STATUS_RUNNING)
                jobs[proto.job_id] = jobs.get(proto.job_id) or forced
        self._set_job_statuses(index, jobs, eval_delete=False)
        self._bump("allocs", index)

    def _install_slab(self, slab: s.AllocSlab) -> None:
        """The per-alloc work of a slab — by-id table rows and per-node
        index cells — is DEFERRED to the first reader that needs it
        (_materialize_pending): bulk batch commits never query their own
        slabs in-batch, and this loop was the single largest host cost
        of the whole scheduling pass at 1M asks.  A network slab is
        indexed now: the next batch's offers read the rows of each node
        they land on (node_networks), and a deferred slab would cost
        every batch's snapshot a drain of all of them."""
        if slab.ips:
            self._drain_slabs((slab,))
            return
        self._pending_slabs.append(slab)
        self._pending_by_job.setdefault(slab.proto.job_id, []).append(slab)

    def _update_summary_bulk(self, index: int, proto: s.Allocation,
                             n: int) -> None:
        """n fresh pending allocs of one (job, tg) — the bulk equivalent of
        n _update_summary_with_alloc(existing=None) calls."""
        job = proto.job
        if job is None:
            return
        summary = self.job_summary_table.get(proto.job_id)
        if summary is None or summary.create_index != job.create_index:
            return
        tgs_ref = summary.summary.get(proto.task_group)
        if tgs_ref is None:
            return
        if proto.client_status != s.ALLOC_CLIENT_STATUS_PENDING:
            return
        summary = summary.copy()
        tgs = summary.summary[proto.task_group]
        tgs.starting += n
        tgs.queued = max(0, tgs.queued - n)
        summary.modify_index = index
        self.job_summary_table[proto.job_id] = summary
        self._bump("job_summary", index)

    # -- job status machinery ---------------------------------------------

    def _set_job_statuses(self, index: int, jobs: Dict[str, str], eval_delete: bool) -> None:
        """(state_store.go:1968)."""
        for job_id, forced in jobs.items():
            job = self.jobs_table.get(job_id)
            if job is None:
                continue
            self._set_job_status(index, job, eval_delete, forced)

    def _set_job_status(self, index: int, job: s.Job, eval_delete: bool, forced: str) -> None:
        """(state_store.go:1993)."""
        old_status = job.status if index != job.create_index else ""
        new_status = forced or self._get_job_status(job, eval_delete)
        if old_status == new_status:
            return
        updated = job.copy()
        updated.status = new_status
        updated.modify_index = index
        self.jobs_table[job.id] = updated
        self._bump("jobs", index)

        # Roll the transition into the parent's children summary.
        if updated.parent_id:
            psummary = self.job_summary_table.get(updated.parent_id)
            if psummary is not None:
                psummary = psummary.copy()
                if psummary.children is None:
                    psummary.children = s.JobChildrenSummary()
                ch = psummary.children
                deltas = {s.JOB_STATUS_PENDING: "pending",
                          s.JOB_STATUS_RUNNING: "running",
                          s.JOB_STATUS_DEAD: "dead"}
                if old_status in deltas:
                    setattr(ch, deltas[old_status], getattr(ch, deltas[old_status]) - 1)
                if new_status in deltas:
                    setattr(ch, deltas[new_status], getattr(ch, deltas[new_status]) + 1)
                psummary.modify_index = index
                self.job_summary_table[updated.parent_id] = psummary
                self._bump("job_summary", index)

    def _get_job_status(self, job: s.Job, eval_delete: bool) -> str:
        """(state_store.go:2092)."""
        has_alloc = False
        for slab in self._pending_by_job.get(job.id, ()):
            has_alloc = True
            if not slab.proto.terminal_status():
                return s.JOB_STATUS_RUNNING
        for aid in self._idx_get(self._allocs_by_job, job.id):
            alloc = self.allocs_table.get(aid)
            if alloc is None:
                continue
            if type(alloc) is s.AllocSlab:
                # Status fields live on the shared proto (a client update
                # replaces the table entry with a real object) — no
                # materialize.
                alloc = alloc.proto
            has_alloc = True
            if not alloc.terminal_status():
                return s.JOB_STATUS_RUNNING

        has_eval = False
        for eid in self._idx_get(self._evals_by_job, job.id):
            ev = self.evals_table.get(eid)
            if ev is None:
                continue
            has_eval = True
            if not ev.terminal_status():
                return s.JOB_STATUS_PENDING

        if job.type == s.JOB_TYPE_SYSTEM:
            return s.JOB_STATUS_DEAD if job.stop else s.JOB_STATUS_RUNNING

        if eval_delete or has_eval or has_alloc:
            return s.JOB_STATUS_DEAD

        if job.is_periodic() or job.is_parameterized():
            return s.JOB_STATUS_DEAD if job.stop else s.JOB_STATUS_RUNNING

        return s.JOB_STATUS_PENDING

    def _update_summary_with_alloc(
        self, index: int, alloc: s.Allocation, existing: Optional[s.Allocation],
        cache: Optional[Dict[str, s.JobSummary]] = None,
    ) -> None:
        """(state_store.go:2296).

        ``cache`` lets a bulk upsert copy each job's summary once per batch
        instead of once per alloc (the copy dominated bulk-insert cost)."""
        if alloc.job is None:
            return
        summary = cache.get(alloc.job_id) if cache is not None else None
        if summary is None:
            summary = self.job_summary_table.get(alloc.job_id)
            if summary is None:
                return
            if summary.create_index != alloc.job.create_index:
                return
            summary = summary.copy()
            if cache is not None:
                cache[alloc.job_id] = summary
        tgs = summary.summary.get(alloc.task_group)
        if tgs is None:
            return

        changed = False
        if existing is None:
            if alloc.client_status == s.ALLOC_CLIENT_STATUS_PENDING:
                tgs.starting += 1
                if tgs.queued > 0:
                    tgs.queued -= 1
                changed = True
        elif existing.client_status != alloc.client_status:
            inc = {
                s.ALLOC_CLIENT_STATUS_RUNNING: "running",
                s.ALLOC_CLIENT_STATUS_FAILED: "failed",
                s.ALLOC_CLIENT_STATUS_PENDING: "starting",
                s.ALLOC_CLIENT_STATUS_COMPLETE: "complete",
                s.ALLOC_CLIENT_STATUS_LOST: "lost",
            }
            dec = {
                s.ALLOC_CLIENT_STATUS_RUNNING: "running",
                s.ALLOC_CLIENT_STATUS_PENDING: "starting",
                s.ALLOC_CLIENT_STATUS_LOST: "lost",
            }
            if alloc.client_status in inc:
                f = inc[alloc.client_status]
                setattr(tgs, f, getattr(tgs, f) + 1)
            if existing.client_status in dec:
                f = dec[existing.client_status]
                setattr(tgs, f, getattr(tgs, f) - 1)
            changed = True

        if changed:
            summary.modify_index = index
            self.job_summary_table[alloc.job_id] = summary
            self._bump("job_summary", index)

    # -- reconcile / maintenance ------------------------------------------

    def reconcile_job_summaries(self, index: int) -> None:
        """Rebuild all summaries from allocs (state_store.go:1883)."""
        with self._lock:
            if self._pending_slabs:
                self._materialize_pending()
            for job in list(self.jobs_table.values()):
                summary = s.JobSummary(job_id=job.id, create_index=job.create_index,
                                       modify_index=index)
                for tg in job.task_groups:
                    summary.summary[tg.name] = s.TaskGroupSummary()
                for aid in self._idx_get(self._allocs_by_job, job.id):
                    alloc = self.allocs_table.get(aid)
                    if type(alloc) is s.AllocSlab:
                        alloc = alloc.proto
                    if alloc is None or alloc.task_group not in summary.summary:
                        continue
                    tgs = summary.summary[alloc.task_group]
                    cs = alloc.client_status
                    if cs == s.ALLOC_CLIENT_STATUS_FAILED:
                        tgs.failed += 1
                    elif cs == s.ALLOC_CLIENT_STATUS_LOST:
                        tgs.lost += 1
                    elif cs == s.ALLOC_CLIENT_STATUS_COMPLETE:
                        tgs.complete += 1
                    elif cs == s.ALLOC_CLIENT_STATUS_RUNNING:
                        tgs.running += 1
                    elif cs == s.ALLOC_CLIENT_STATUS_PENDING:
                        tgs.starting += 1
                self.job_summary_table[job.id] = summary
            self._bump("job_summary", index)
        self._notify()

    # -- persistence (FSM snapshot support) --------------------------------

    #: v2 binary snapshot magic (state/columnar.py container format).
    #: Legacy blobs are bare msgpack maps whose first byte can never be
    #: ASCII "N", so an 8-byte prefix sniff is unambiguous.
    SNAP2_MAGIC = b"NTPUSNP2"
    #: The same layout holding network slabs (their ``ips`` and
    #: ``dyn_ports`` columns).  Written only then, so a build that
    #: predates the columns refuses such a snapshot (an unknown magic
    #: reaches its legacy msgpack decode, which raises) rather than
    #: restoring those rows without their ports; every other snapshot
    #: stays ``NTPUSNP2``.
    SNAP3_MAGIC = b"NTPUSNP3"

    def persist(self) -> bytes:
        """Serialize all tables for an FSM snapshot (fsm.go:568
        Snapshot).  Columnar-enabled stores write the v2 binary format
        (struct-of-arrays node section, slabs kept columnar,
        length-prefixed dtype+shape+bytes numpy columns — a 1M-node
        cluster persists in seconds); ``NOMAD_TPU_COLUMNAR=0`` restores
        the legacy per-object msgpack blob."""
        if columnar.enabled():
            return self._persist_columnar()
        return self._persist_legacy()

    @staticmethod
    def _slab_col_spec(col):
        """Wire form of one slab string column: lazy formulaic columns
        ship as their 3-field generator spec (1M ids -> ~40 bytes)."""
        if isinstance(col, s.LazyUuids):
            return {"lz": "u", "p": col.prefix, "n": col.n}
        if isinstance(col, s.LazyNames):
            return {"lz": "n", "p": col.prefix, "n": col.n}
        return list(col)

    @staticmethod
    def _slab_col_load(v):
        if isinstance(v, dict):
            if v["lz"] == "u":
                return s.LazyUuids(v["n"], v["p"])
            return s.LazyNames(v["n"], v["p"])
        return v

    def _persist_columnar(self) -> bytes:
        """v2: msgpack envelope of {tables, nodes SoA, standalone
        allocs, columnar slabs, numpy columns}.  Slabs are NOT
        materialized — their protos ship once and the string columns
        ship as columns (lazy ones as generator specs), a network slab's
        two with them (magic ``NTPUSNP3``), which is where the 1M-alloc
        win lives; restore re-installs them as pending slabs (the
        lazy-rehydration path readers already drain; a network slab is
        indexed at once)."""
        import msgpack

        from ..api.codec import to_wire
        from ..server.log_codec import encode_payload

        with self._lock:
            # Shared job trees referenced from alloc rows/protos are
            # deduplicated by identity into one list (the legacy
            # alloc_jobs discipline).
            alloc_jobs: List[s.Job] = []
            job_ref_by_identity: Dict[int, int] = {}

            def ref_job(j: s.Job) -> int:
                r = job_ref_by_identity.get(id(j))
                if r is None:
                    r = job_ref_by_identity[id(j)] = len(alloc_jobs)
                    alloc_jobs.append(j)
                return r

            table = self.allocs_table
            allocs_out: Dict[str, s.Allocation] = {}
            alloc_job_refs: Dict[str, int] = {}
            slab_docs: List[dict] = []
            seen_slabs: Set[int] = set()

            def slab_doc(slab: s.AllocSlab, dead: List[int]) -> dict:
                proto = slab.proto
                jr = None
                if proto.job is not None:
                    jr = ref_job(proto.job)
                    proto = s._fast_copy(proto)
                    proto.job = None
                doc = {"proto": to_wire(proto), "job_ref": jr,
                       "ids": self._slab_col_spec(slab.ids),
                       "names": self._slab_col_spec(slab.names),
                       "node_ids": list(slab.node_ids),
                       "prev_ids": self._slab_col_spec(slab.prev_ids),
                       "ci": slab.create_index, "mi": slab.modify_index,
                       "dead": dead}
                if slab.ips:
                    doc["ips"] = list(slab.ips)
                    doc["dyn_ports"] = slab.dyn_ports
                return doc

            for aid, v in table.items():
                if type(v) is s.AllocSlab:
                    if id(v) in seen_slabs:
                        continue
                    seen_slabs.add(id(v))
                    # Slots whose table entry was replaced (client
                    # update cache-back) or removed persist through
                    # their own row / not at all.
                    dead = [i for i, aid2 in enumerate(v.ids)
                            if table.get(aid2) is not v]
                    slab_docs.append(slab_doc(v, dead))
                else:
                    a = v
                    if a.job is not None:
                        alloc_job_refs[aid] = ref_job(a.job)
                        a = s._fast_copy(a)
                        a.job = None
                    allocs_out[aid] = a
            # Pending slabs (deferred indexing) are disjoint from table
            # values and have no replaced slots by construction.
            for slab in self._pending_slabs:
                slab_docs.append(slab_doc(slab, []))

            # Node table as struct-of-arrays: scalar fields as parallel
            # lists (one C-speed msgpack pack), resource 4-vectors as
            # binary arrays, networks sparse (absent on fleet nodes).
            nodes = list(self.nodes_table.values())
            n = len(nodes)
            cap = np.zeros((n, columnar.RES_DIMS), dtype=np.int64)
            resv = np.zeros((n, columnar.RES_DIMS), dtype=np.int64)
            res_present: List[bool] = []
            nets: Dict[str, list] = {}
            rnets: Dict[str, list] = {}
            for i, nd in enumerate(nodes):
                r = nd.resources
                if r is not None:
                    cap[i] = (r.cpu, r.memory_mb, r.disk_mb, r.iops)
                    if r.networks:
                        nets[str(i)] = [to_wire(x) for x in r.networks]
                rv = nd.reserved
                if rv is None:
                    res_present.append(False)
                else:
                    res_present.append(True)
                    resv[i] = (rv.cpu, rv.memory_mb, rv.disk_mb, rv.iops)
                    if rv.networks:
                        rnets[str(i)] = [to_wire(x) for x in rv.networks]
            node_soa = {
                "id": [nd.id for nd in nodes],
                "name": [nd.name for nd in nodes],
                "datacenter": [nd.datacenter for nd in nodes],
                "http_addr": [nd.http_addr for nd in nodes],
                "node_class": [nd.node_class for nd in nodes],
                "computed_class": [nd.computed_class for nd in nodes],
                "status": [nd.status for nd in nodes],
                "status_description": [nd.status_description
                                       for nd in nodes],
                "drain": [nd.drain for nd in nodes],
                "status_updated_at": [nd.status_updated_at for nd in nodes],
                "create_index": [nd.create_index for nd in nodes],
                "modify_index": [nd.modify_index for nd in nodes],
                "attributes": [nd.attributes for nd in nodes],
                "meta": [nd.meta for nd in nodes],
                "links": [nd.links for nd in nodes],
                "cap": columnar.pack_array(cap),
                "res": columnar.pack_array(resv),
                "res_present": res_present,
                "networks": nets,
                "res_networks": rnets,
            }

            tables_blob = encode_payload({
                "jobs": self.jobs_table,
                "job_versions": self.job_versions,
                "job_summary": self.job_summary_table,
                "evals": self.evals_table,
                "periodic_launch": self.periodic_launch_table,
                "vault_accessors": self.vault_accessors_table,
                "deployments": self.deployments_table,
                "namespaces": self.namespaces_table,
                "indexes": self._indexes,
            }, subsystem="snapshot")
            allocs_blob = encode_payload({
                "rows": allocs_out,
                "jobs": alloc_jobs,
                "refs": alloc_job_refs,
            }, subsystem="snapshot")

            # Numeric columns ride along when the mirror is warm so the
            # restored store encodes without a cold column build.
            col_blob = col_meta = None
            cols = (self._ensure_columns_locked()
                    if columnar.enabled() else None)
            if cols is not None and cols.epoch == columnar.EPOCH:
                if not cols.fold_usage(self):
                    cols.rebuild_usage(self)
                col_blob = columnar.pack_columns(cols)
                col_meta = {"dc": list(cols.dc_book)[:cols.dc_len],
                            "class": list(cols.class_book)[:cols.class_len],
                            "usage_index": cols.usage_index}

            doc = {"tables": tables_blob, "nodes": node_soa,
                   "allocs": allocs_blob, "slabs": slab_docs,
                   "columns": col_blob, "colmeta": col_meta}
            net = any("ips" in sd for sd in slab_docs)
            magic = self.SNAP3_MAGIC if net else self.SNAP2_MAGIC
            return magic + msgpack.packb(doc, use_bin_type=True)

    def _persist_legacy(self) -> bytes:
        """Legacy per-object msgpack snapshot (the pre-columnar format;
        still written under ``NOMAD_TPU_COLUMNAR=0`` and always
        readable)."""
        with self._lock:
            if self._pending_slabs:
                self._materialize_pending()
            # Slab entries are materialized for the snapshot blob ONLY
            # (no cache-back): the blob format stays plain Allocation
            # rows (fsm.go:568) while the live table keeps its compact
            # columnar form.  Embedded job trees are deduplicated by
            # object identity into one shared list — pickle's memo table
            # used to encode each shared proto.job once, but the msgpack
            # codec walks values independently, so a 100k-alloc store
            # would otherwise re-encode the multi-KB Job tree per alloc.
            alloc_jobs: List[s.Job] = []
            job_ref_by_identity: Dict[int, int] = {}
            allocs_out: Dict[str, s.Allocation] = {}
            alloc_job_refs: Dict[str, int] = {}
            for aid, v in self.allocs_table.items():
                a = (v.materialize(v.id_index(aid))
                     if type(v) is s.AllocSlab else v)
                if a.job is not None:
                    ref = job_ref_by_identity.get(id(a.job))
                    if ref is None:
                        ref = job_ref_by_identity[id(a.job)] = len(alloc_jobs)
                        alloc_jobs.append(a.job)
                    a = s._fast_copy(a)
                    a.job = None
                    alloc_job_refs[aid] = ref
                allocs_out[aid] = a
            payload = {
                "nodes": self.nodes_table,
                "jobs": self.jobs_table,
                "job_versions": self.job_versions,
                "job_summary": self.job_summary_table,
                "evals": self.evals_table,
                "allocs": allocs_out,
                "alloc_jobs": alloc_jobs,
                "alloc_job_refs": alloc_job_refs,
                "periodic_launch": self.periodic_launch_table,
                "vault_accessors": self.vault_accessors_table,
                "deployments": self.deployments_table,
                "namespaces": self.namespaces_table,
                "indexes": self._indexes,
            }
            # Whitelisted msgpack trees (server/log_codec), never pickle:
            # a corrupt or attacker-written snapshot file can only inject
            # data types from the structs whitelist, not code.
            from ..server.log_codec import encode_payload

            return encode_payload(payload, subsystem="snapshot")

    @classmethod
    def restore(cls, blob: bytes) -> "StateStore":
        """Rebuild a store (and its secondary indexes) from a snapshot
        (fsm.go:582 Restore).  Sniffs the v2 magic; legacy msgpack blobs
        keep restoring through the old path (upgrade compatibility in
        both directions)."""
        if blob[:len(cls.SNAP2_MAGIC)] in (cls.SNAP2_MAGIC, cls.SNAP3_MAGIC):
            return cls._restore_columnar(blob)
        from ..server.log_codec import decode_payload

        payload = decode_payload(blob, subsystem="snapshot")
        store = cls()
        store.nodes_table = payload["nodes"]
        store.jobs_table = payload["jobs"]
        store.job_versions = payload["job_versions"]
        store.job_summary_table = payload["job_summary"]
        store.evals_table = payload["evals"]
        store.allocs_table = payload["allocs"]
        # Re-attach the deduplicated job trees (shared objects restored
        # as shared objects — one Job instance per ref).
        alloc_jobs = payload.get("alloc_jobs", [])
        for aid, ref in payload.get("alloc_job_refs", {}).items():
            alloc = store.allocs_table.get(aid)
            if alloc is not None and 0 <= ref < len(alloc_jobs):
                alloc.job = alloc_jobs[ref]
        store.periodic_launch_table = payload["periodic_launch"]
        store.vault_accessors_table = payload["vault_accessors"]
        store.deployments_table = payload.get("deployments", {})
        # Pre-tenancy snapshots carry no namespaces table (.get: both
        # formats restore across versions; jobs/evals/allocs inside them
        # decode with namespace="default" via the dataclass default).
        store.namespaces_table = payload.get("namespaces", {})
        store._indexes = payload["indexes"]
        for ev in store.evals_table.values():
            store._evals_by_job[ev.job_id].add(ev.id)
        for alloc in store.allocs_table.values():
            store._allocs_by_node[alloc.node_id].add(alloc.id)
            store._allocs_by_job[alloc.job_id].add(alloc.id)
            store._allocs_by_eval[alloc.eval_id].add(alloc.id)
        for acc in store.vault_accessors_table.values():
            store._vault_by_alloc[acc.alloc_id].add(acc.accessor)
            store._vault_by_node[acc.node_id].add(acc.accessor)
        # The usage-delta log is not persisted: the restored store starts
        # an empty log with the floor at the restored allocs index, so
        # any resident consumer from before the restore full re-encodes.
        store._alloc_log_floor = store._indexes.get("allocs", 0)
        store._rebuild_ns_usage()
        return store

    @classmethod
    def _restore_columnar(cls, blob: bytes) -> "StateStore":
        """v2 restore: node objects rebuilt struct-of-arrays-fast
        (``__new__`` + direct ``__dict__``), slabs re-installed as
        PENDING (per-alloc table rows and node-index cells rehydrate
        lazily on first read, exactly like a live bulk commit), numpy
        columns installed from their binary section."""
        import msgpack

        from ..api.codec import from_wire
        from ..server.log_codec import decode_payload

        doc = msgpack.unpackb(blob[len(cls.SNAP2_MAGIC):], raw=False)
        store = cls()
        t = decode_payload(doc["tables"], subsystem="snapshot")
        store.jobs_table = t["jobs"]
        store.job_versions = t["job_versions"]
        store.job_summary_table = t["job_summary"]
        store.evals_table = t["evals"]
        store.periodic_launch_table = t["periodic_launch"]
        store.vault_accessors_table = t["vault_accessors"]
        store.deployments_table = t["deployments"]
        store.namespaces_table = t.get("namespaces", {})
        store._indexes = t["indexes"]

        # -- nodes: SoA -> objects without dataclass __init__ ----------
        nd = doc["nodes"]
        ids = nd["id"]
        n = len(ids)
        cap = columnar.unpack_array(memoryview(nd["cap"]), 0)[0].tolist()
        resv = columnar.unpack_array(memoryview(nd["res"]), 0)[0].tolist()
        res_present = nd["res_present"]
        nets = nd["networks"] or {}
        rnets = nd["res_networks"] or {}

        def mk_nets(lst):
            return [from_wire(s.NetworkResource, x) for x in lst]

        new = object.__new__
        R, ND = s.Resources, s.Node
        names, dcs = nd["name"], nd["datacenter"]
        https, ncls, ccls = nd["http_addr"], nd["node_class"], \
            nd["computed_class"]
        sts, stsd, drains = nd["status"], nd["status_description"], \
            nd["drain"]
        supd, cidx, midx = nd["status_updated_at"], nd["create_index"], \
            nd["modify_index"]
        attrs, metas, links = nd["attributes"], nd["meta"], nd["links"]
        nodes_table = store.nodes_table
        for i in range(n):
            c = cap[i]
            r = new(R)
            r.__dict__ = {"cpu": c[0], "memory_mb": c[1], "disk_mb": c[2],
                          "iops": c[3],
                          "networks": (mk_nets(nets[str(i)])
                                       if str(i) in nets else [])}
            if res_present[i]:
                v = resv[i]
                rv = new(R)
                rv.__dict__ = {"cpu": v[0], "memory_mb": v[1],
                               "disk_mb": v[2], "iops": v[3],
                               "networks": (mk_nets(rnets[str(i)])
                                            if str(i) in rnets else [])}
            else:
                rv = None
            node = new(ND)
            node.__dict__ = {
                "id": ids[i], "datacenter": dcs[i], "name": names[i],
                "http_addr": https[i], "attributes": attrs[i],
                "resources": r, "reserved": rv, "links": links[i],
                "meta": metas[i], "node_class": ncls[i],
                "computed_class": ccls[i], "drain": drains[i],
                "status": sts[i], "status_description": stsd[i],
                "status_updated_at": supd[i], "create_index": cidx[i],
                "modify_index": midx[i],
            }
            nodes_table[ids[i]] = node

        # -- standalone alloc rows (eager: the small set) ---------------
        a = decode_payload(doc["allocs"], subsystem="snapshot")
        alloc_jobs = a["jobs"]
        store.allocs_table = a["rows"]
        for aid, ref in a["refs"].items():
            row = store.allocs_table.get(aid)
            if row is not None and 0 <= ref < len(alloc_jobs):
                row.job = alloc_jobs[ref]
        for alloc in store.allocs_table.values():
            store._allocs_by_node[alloc.node_id].add(alloc.id)
            store._allocs_by_job[alloc.job_id].add(alloc.id)
            store._allocs_by_eval[alloc.eval_id].add(alloc.id)

        # -- slabs: re-install as pending (lazy rehydration) ------------
        for sd in doc["slabs"]:
            proto = from_wire(s.Allocation, sd["proto"])
            jr = sd.get("job_ref")
            if jr is not None and 0 <= jr < len(alloc_jobs):
                proto.job = alloc_jobs[jr]
            slab = s.AllocSlab(
                proto=proto,
                ids=cls._slab_col_load(sd["ids"]),
                names=cls._slab_col_load(sd["names"]),
                node_ids=sd["node_ids"],
                prev_ids=cls._slab_col_load(sd["prev_ids"]),
                create_index=sd["ci"], modify_index=sd["mi"],
                ips=sd.get("ips") or [], dyn_ports=sd.get("dyn_ports") or b"")
            dead = sd.get("dead")
            if dead:
                deadset = set(dead)
                slab = slab.take(i for i in range(len(slab.ids))
                                 if i not in deadset)
            store._install_slab(slab)
            store._idx_append(store._allocs_by_job, proto.job_id, slab.ids)
            store._idx_append(store._allocs_by_eval, proto.eval_id,
                              slab.ids)

        for ev in store.evals_table.values():
            store._evals_by_job[ev.job_id].add(ev.id)
        for acc in store.vault_accessors_table.values():
            store._vault_by_alloc[acc.alloc_id].add(acc.accessor)
            store._vault_by_node[acc.node_id].add(acc.accessor)

        # -- numpy columns (warm encode start) --------------------------
        if doc.get("columns") is not None:
            cm = doc["colmeta"]
            store._columns = columnar.unpack_columns(
                doc["columns"], ids, cm["dc"], cm["class"],
                cm["usage_index"])
        store._alloc_log_floor = store._indexes.get("allocs", 0)
        store._rebuild_ns_usage()
        return store


class StateSnapshot(StateStore):
    """A point-in-time view; writes to a snapshot do not affect the parent
    store.  The plan applier uses this for optimistic local application
    (plan_apply.go:166)."""
