"""Replicated log: the consensus layer under the FSM.

The reference uses hashicorp/raft with a boltdb log store and an in-memory
option for dev/tests (nomad/server.go:91-95 raftInmem, nomad/raft_rpc.go).
This module provides the same shape:

- ``RaftLog``        — the log interface the server applies through.
- ``InmemLog``       — in-memory log (tests / dev mode), like raftInmem.
- ``FileLog``        — single-voter durable WAL with length-prefixed
                       entries (whitelisted msgpack trees via
                       server/log_codec — never pickle, so a corrupt or
                       attacker-written WAL/snapshot can only inject
                       data, not code), fsync batching, and
                       snapshot+truncate — filling boltdb's role.
- ``ReplicatedLog``  — leader-append + follower-replication over a
                       transport callable; majority commit.  Single-voter
                       by default; multi-server replication uses the RPC
                       layer's raft channel (server/rpc.py).

Leadership is modeled explicitly (leader_ch notifications) so the leader
loop (server/leader.py-equivalent logic inside server.py) can
enable/disable the broker exactly as the reference does
(nomad/leader.go:28-120).
"""
from __future__ import annotations

import os
import struct
import threading
import time
from typing import Callable, List, Optional, Tuple

from .. import fault
from ..utils import tracing
from ..utils.telemetry import NULL_TELEMETRY
from .fsm import FSM, MessageType
from .log_codec import decode_payload, encode_payload


def _fire_apply_fault(index: int, msg_type) -> Optional[str]:
    """``raft.apply`` fault point, shared by the single-voter and
    multi-voter apply paths.  Returns "step_down" for the caller to
    translate into its own leadership demotion; crash/error raise here;
    delay sleeps here.  Ctx exposed to rules: the prospective log
    ``index`` and the message type name (e.g. ``"APPLY_PLAN_RESULTS"``)."""
    act = fault.faultpoint(
        "raft.apply", index=index,
        msg_type=getattr(msg_type, "name", str(msg_type)))
    if act is None:
        return None
    if act.kind == "delay":
        import time as _time
        _time.sleep(act.delay)
        return None
    if act.kind == "step_down":
        return "step_down"
    act.raise_injected()
    return None


def _encode_entry(index, msg_type, payload):
    return encode_payload({"i": int(index), "t": int(msg_type),
                           "p": payload})


def _decode_entry(blob):
    """Decode one WAL record; raises on anything that is not a
    well-formed msgpack entry (callers treat that as a corrupt tail)."""
    d = decode_payload(blob)
    return d["i"], d["t"], d["p"]

_LEN = struct.Struct("<Q")

# Number of FSM snapshots retained (reference: server.go:51
# snapshotsRetained = 2).
SNAPSHOTS_RETAINED = 2


def _env_int(name: str, default: int) -> int:
    from ..utils import knobs

    return knobs.get_int(name, default)


def _env_float(name: str, default: float) -> float:
    from ..utils import knobs

    return knobs.get_float(name, default)


class RaftLog:
    """Single-voter commit path: append → fsync (durable impls) → apply."""

    # Telemetry handle, assigned by the owning Server after construction
    # (class default keeps standalone/test construction zero-config).
    metrics = NULL_TELEMETRY

    def __init__(self, fsm: FSM):
        self.fsm = fsm
        # RLock: index assignment/persist run under this lock; FSM-apply
        # hooks may consult applied_index() on the same thread.
        self._l = threading.RLock()
        self._last_index = 0
        self._applied = 0
        self._leader = True  # single-voter: always leader
        self._leader_listeners: List[Callable[[bool], None]] = []
        # Apply sequencer: entries apply to the FSM in strict index
        # order AFTER their durability wait (see apply()).  A sync
        # covers the whole written prefix, so durability of entry N
        # implies durability of everything below it — the wait here is
        # only for apply ORDERING, never for a lower entry's fsync.
        self._apply_cv = threading.Condition()
        self._apply_next = 1
        self._apply_failed = False
        # Codec time of the entries written under the current hold of
        # the log lock (a durable log's _persist adds to it).
        self._encode_seconds = 0.0

    # -- leadership --------------------------------------------------------

    def is_leader(self) -> bool:
        return self._leader

    def notify_leadership(self, cb: Callable[[bool], None]) -> None:
        self._leader_listeners.append(cb)
        cb(self._leader)

    def _set_leader(self, leader: bool) -> None:
        if leader == self._leader:
            return
        self._leader = leader
        for cb in self._leader_listeners:
            cb(leader)

    # -- log ---------------------------------------------------------------

    def applied_index(self) -> int:
        with self._l:
            return self._applied

    def fence_index(self) -> int:
        """Upper bound on every COMMITTED entry's index, safe for the
        follower-read fence floor at leadership establishment.  For the
        single-voter log applied == last; MultiRaft overrides with the
        last LOG index — with async FSM apply the applied index can lag
        committed entries still draining, and a floor below a committed
        plan would let a lagging follower stale double-place."""
        return self.applied_index()

    def applied_index_relaxed(self) -> int:
        """Lock-free lower bound on :meth:`applied_index`.  ``_applied``
        is stamped AFTER each FSM apply (GIL-ordered), so this never
        reports an entry whose state is not yet visible — it may lag an
        in-flight apply by one entry.  For hot read paths (heartbeat
        grants, wait-for-index polling, external event stamping) where
        queueing on the raft lock behind the apply stream is the
        dominant cost; anything that needs the serializes-with-applies
        guarantee (event-broker arming horizon) stays on the locked
        read."""
        applied = getattr(self, "_applied", None)
        return applied if applied is not None else self.applied_index()

    def apply(self, msg_type: MessageType, payload: dict):
        """Append + commit + apply one entry; returns (result, index)
        (the raftApply path, nomad/rpc.go raftApply → fsm.Apply): a
        group of one through :meth:`apply_many`."""
        outcome, = self.apply_many([(msg_type, payload)])
        if isinstance(outcome, Exception):
            raise outcome
        return outcome

    def apply_many(self, entries: List[Tuple[MessageType, dict]]) -> list:
        """Append + commit + apply ``entries`` back to back, one log
        entry each, under ONE durability wait; returns per entry, in
        order, ``(result, index)`` or the exception that failed it.

        Three phases, preserving durability-before-visibility while
        letting concurrent appliers share one fsync:

        1. Under the log lock: assign each entry the next index and
           WRITE it (file order == index order, so the durable prefix is
           always gap-free; a group's indexes are consecutive).  No
           fsync here — holding the lock across the fsync made group
           commit structurally impossible (appends were never
           concurrent) and serialized one fsync per apply.  An entry
           whose write fails releases its index and fails alone, unless
           the failure poisoned the log (below).
        2. Outside the lock: ONE wait for durability, on the last
           written entry (_sync_persist): a sync covers the whole
           written prefix, and concurrent waiters coalesce into one
           group-commit fsync.  Nothing of the group is applied or
           answered before it returns.
        3. Apply sequencer: FSM applies run in strict index order,
           AFTER durability — nothing external (event stream, blocking
           queries, applied_index readers) can observe state a crash
           would erase.  Waiting for entry N-1's APPLY never waits on
           another fsync.  An FSM apply that raises fails its own entry
           and the sequencer moves on.

        A durability failure poisons the log (fsync failure is fatal —
        the reference panics): no entry of the group was applied, no
        retry can double-apply, every entry of the group fails (those
        already written stay in the file as a prefix of whole entries
        nobody was told about, which recovery replays — the
        entry-durable, ack-lost case) and every queued/later apply fails
        too.

        The call's four stages ride the returned list as ``timing``
        (seconds: ``encode``, the group's entries through the codec;
        ``write``, the rest of phase 1; ``sync``, phase 2; ``fsm``,
        phase 3), from one set of stamps, two an entry; they sum to the
        ``raft.apply`` sample, one a call."""
        t0 = time.perf_counter()
        outcomes = _Outcomes([None] * len(entries))
        written: List[Tuple[int, int]] = []     # (position, index)
        token = poisoned = None
        with self._l:
            self._encode_seconds = 0.0
            for pos, (msg_type, payload) in enumerate(entries):
                try:
                    index, token = self._append(msg_type, payload)
                except Exception as exc:
                    outcomes[pos] = exc
                    if getattr(self, "_wal_failed", False):
                        poisoned = exc
                        break
                else:
                    written.append((pos, index))
            encode = self._encode_seconds
        t_written = time.perf_counter()
        if written and token is not None:
            try:
                if poisoned is None:
                    self._sync_persist(token, entries[written[-1][0]][0],
                                       len(written))
                else:
                    self._release_persist(len(written))
            except Exception as exc:
                poisoned = exc
            if poisoned is not None:
                with self._l:
                    self._wal_failed = True
                with self._apply_cv:
                    # The written entries will never apply: every later
                    # (higher-index) applier queued behind them must fail
                    # rather than wait forever.
                    self._apply_failed = True
                    self._apply_cv.notify_all()
        if poisoned is not None:
            return [poisoned if out is None else out for out in outcomes]
        tr = tracing.TRACER
        t_synced = t_entry = time.perf_counter()
        for pos, index in written:
            msg_type, payload = entries[pos]
            with self._apply_cv:
                while self._apply_next != index:
                    if self._apply_failed:
                        break
                    self._apply_cv.wait()
                if self._apply_next != index:
                    outcomes[pos] = NotLeaderError(
                        "write-ahead log failed; restart to recover "
                        "from the durable prefix")
                    continue
                try:
                    outcomes[pos] = (
                        self.fsm.apply(index, msg_type, payload), index)
                except Exception as exc:
                    # Propagates to this entry's one caller exactly as
                    # before; ALWAYS advance: the sequencer must not
                    # wedge every later apply behind the dead index.
                    outcomes[pos] = exc
                finally:
                    self._applied = index  # visible only now: post-durability
                    self._apply_next = index + 1
                    self._apply_cv.notify_all()
            # Branch before building attrs: the disarmed commit path pays
            # one load + comparison, no getattr/dict/timestamp.  Armed,
            # a span an entry: its sequencer wait and FSM apply alone.
            if tr is not None:
                t_prev, t_entry = t_entry, time.perf_counter()
                tr.record("raft.apply", t_prev, t_entry, index=index,
                          msg_type=getattr(msg_type, "name", str(msg_type)))
        t_end = time.perf_counter()
        self.metrics.add_sample("raft.apply", (t_end - t0) * 1000.0)
        outcomes.timing = {"encode": encode,
                           "write": t_written - t0 - encode,
                           "sync": t_synced - t_written,
                           "fsm": t_end - t_synced}
        return outcomes

    def _append(self, msg_type: MessageType, payload: dict):
        """Assign the next index and write the entry (caller holds the
        log lock); returns (index, durability token)."""
        if not self._leader:
            raise NotLeaderError("not the leader")
        if getattr(self, "_wal_failed", False):
            # A durability failure already poisoned this log: the
            # durable prefix is unknown, so NO further applies are
            # accepted — restart to recover from it.
            raise NotLeaderError("write-ahead log failed; restart "
                                 "to recover from the durable prefix")
        # Fault point BEFORE append: an injected crash here models the
        # leader dying before the entry commits — nothing persists,
        # nothing applies, and the caller's retry path must cope.
        if _fire_apply_fault(self._last_index + 1, msg_type) is not None:
            raise NotLeaderError("injected step-down")
        self._last_index += 1
        try:
            return self._last_index, self._persist(
                self._last_index, msg_type, payload)
        except Exception:
            # Nothing reached the log (writes roll back torn frames):
            # release the index so the apply sequencer never waits on a
            # permanently-missing entry.
            self._last_index -= 1
            raise

    def _persist(self, index: int, msg_type: MessageType, payload: dict):
        return None  # in-memory: nothing to do

    def _sync_persist(self, token, msg_type, entries: int = 1) -> None:
        pass  # in-memory: nothing to wait for

    def _release_persist(self, entries: int) -> None:
        pass  # in-memory: no durability tokens

    def snapshot(self) -> None:
        pass

    def close(self) -> None:
        pass


class NotLeaderError(Exception):
    pass


class _Outcomes(list):
    """What ``apply_many`` returns: the per-entry outcomes, and the
    call's stage totals as ``timing`` (None where nothing was timed)."""

    timing = None


class InmemLog(RaftLog):
    """In-memory log for dev/tests (raftInmem analogue)."""


class FileLog(RaftLog):
    """Durable single-voter WAL + snapshots.

    Layout in ``data_dir``:
      wal.crc         — CRC-framed records via the native group-commit WAL
                        (nomad_tpu/native/wal.cc) when the toolchain is
                        available: concurrent appends coalesce into one
                        fsync (~10x append throughput under RPC-handler
                        concurrency, the raft-boltdb single-writer role)
      wal.log         — legacy length-prefixed fallback (pure Python),
                        used when native is unavailable; replayed before
                        wal.crc on recovery so upgrades are seamless
      walseg-<idx>.*  — sealed WAL segments rolled at a snapshot: fully
                        fsynced, immutable, deleted once the snapshot
                        blob that covers them is durable (a crash
                        mid-snapshot leaves them for replay — nothing
                        is ever lost to an unfinished snapshot)
      snapshot-<idx>  — FSM snapshot taken at <idx>
    Recovery: newest snapshot restore, then sealed-segment + WAL replay
    of entries > idx.

    Automatic snapshotting (ISSUE 10 / ROADMAP item 2): a live server
    compacts itself — a background thread watches entry/byte thresholds
    and snapshots OFF the apply path (the expensive FSM serialization
    runs on a copy-on-write state snapshot outside the log lock, while
    appends keep flowing into a freshly rolled segment).  Thresholds:
    ``NOMAD_TPU_FILELOG_SNAPSHOT_ENTRIES`` (default 8192, the
    hashicorp/raft SnapshotThreshold), ``_BYTES`` (default 64MB of WAL),
    ``_INTERVAL`` (check cadence, default 1s); 0 entries AND 0 bytes
    disables.  Operator/test-invoked :meth:`snapshot` runs the same
    implementation synchronously.
    """

    def __init__(self, fsm: FSM, data_dir: str, fsync: bool = True,
                 snapshot_entries: Optional[int] = None,
                 snapshot_bytes: Optional[int] = None,
                 snapshot_interval: Optional[float] = None):
        super().__init__(fsm)
        self.data_dir = data_dir
        self.fsync = fsync
        os.makedirs(data_dir, exist_ok=True)
        self.wal_path = os.path.join(data_dir, "wal.log")
        self._nwal = None
        try:
            from ..native import NativeWAL, NativeUnavailable

            try:
                self._nwal = NativeWAL(os.path.join(data_dir, "wal.crc"),
                                       fsync=fsync)
            except NativeUnavailable:
                self._nwal = None
        except ImportError:  # pragma: no cover
            self._nwal = None
        self._recover()
        self._fh = (open(self.wal_path, "ab") if self._nwal is None
                    else None)
        # Pure-Python group-commit state (the fallback twin of
        # native/wal.cc's written/synced seq + single-syncer dance):
        # writes happen in index order under the raft lock; the fsync
        # wait runs outside it so concurrent appliers share one fsync.
        self._py_cv = threading.Condition()
        self._py_written = 0
        self._py_synced = 0
        self._py_sync_in_flight = False
        # Automatic snapshotting state.  _sync_inflight counts appliers
        # holding a durability token (between _persist and the end of
        # _sync_persist): the WAL roll at a snapshot waits it to zero —
        # with the log lock held no new tokens mint, so the old
        # handles/files are quiescent when swapped.
        self._sync_inflight = 0
        self._entries_since_snap = 0
        self._bytes_since_snap = 0
        self._snap_serial = threading.Lock()
        self._snap_stop = threading.Event()
        self._snap_thread: Optional[threading.Thread] = None
        self.snapshot_entries = (snapshot_entries
                                 if snapshot_entries is not None
                                 else _env_int(
                                     "NOMAD_TPU_FILELOG_SNAPSHOT_ENTRIES",
                                     8192))
        self.snapshot_bytes = (snapshot_bytes
                               if snapshot_bytes is not None
                               else _env_int(
                                   "NOMAD_TPU_FILELOG_SNAPSHOT_BYTES",
                                   64 << 20))
        self.snapshot_interval = (snapshot_interval
                                  if snapshot_interval is not None
                                  else _env_float(
                                      "NOMAD_TPU_FILELOG_SNAPSHOT_INTERVAL",
                                      1.0))
        if (self.snapshot_entries > 0 or self.snapshot_bytes > 0) \
                and self.snapshot_interval > 0:
            self._snap_thread = threading.Thread(
                target=self._auto_snapshot_loop, daemon=True,
                name="filelog-snapshot")
            self._snap_thread.start()

    # -- recovery ----------------------------------------------------------

    def _snapshot_files(self) -> List[Tuple[int, str]]:
        out = []
        for name in os.listdir(self.data_dir):
            if name.startswith("snapshot-"):
                try:
                    idx = int(name.split("-", 1)[1])
                except ValueError:
                    continue
                out.append((idx, os.path.join(self.data_dir, name)))
        return sorted(out)

    def _segment_files(self) -> List[str]:
        out = []
        for name in os.listdir(self.data_dir):
            if name.startswith("walseg-"):
                out.append(os.path.join(self.data_dir, name))
        return sorted(out)

    def _recover(self) -> None:
        snap_idx = 0
        snaps = self._snapshot_files()
        if snaps:
            snap_idx, path = snaps[-1]
            with open(path, "rb") as fh:
                self.fsm.restore(fh.read())
            self._last_index = snap_idx
            self._applied = snap_idx

        # Sealed segments first (rolled at snapshots; a crash between the
        # roll and the snapshot blob's fsync leaves their entries ONLY
        # here), then the active logs.  Segments fully covered by the
        # snapshot are deleted — replaying them again would only re-filter.
        entries: List[Tuple[int, int, dict]] = []
        for seg in self._segment_files():
            if seg.endswith(".crc"):
                got = self._read_crc_entries(snap_idx, path=seg)
            else:
                got = self._read_legacy_entries(snap_idx, path=seg)
            if got:
                entries.extend(got)
            else:
                try:
                    os.unlink(seg)
                except OSError:  # pragma: no cover — cleanup best-effort
                    pass

        # Gather entries from BOTH active logs and apply in index order: a
        # node toggled between native and fallback modes may have newer
        # entries in either file.
        entries.extend(self._read_legacy_entries(snap_idx))
        if self._nwal is not None:
            # Native log replay (CRC + torn-tail handling done at open).
            # A CRC-valid record that still fails to decode (garbage or a
            # pre-msgpack-format file) ends replay at the last good entry
            # — and the log is REWRITTEN to that good prefix, so entries
            # appended after this boot land after valid records and stay
            # recoverable (leaving the bad record in place would strand
            # every later append behind it on the next replay).
            good_blobs = []
            bad = False
            for blob in self._nwal.records():
                try:
                    index, msg_type, payload = _decode_entry(blob)
                except Exception:
                    bad = True
                    break
                good_blobs.append(blob)
                if index > snap_idx:
                    entries.append((index, msg_type, payload))
            if bad:
                self._nwal.reset()
                for blob in good_blobs:
                    self._nwal.append(blob)
        else:
            # Native unavailable on THIS boot but a wal.crc exists from a
            # previous one: replay it through the pure-Python CRC reader —
            # silently ignoring it would roll back committed entries and
            # reuse their indexes.
            entries.extend(self._read_crc_entries(snap_idx))
        # Same-index duplicates can only be identical payloads (an index
        # is written to exactly one log at append time); keep the first.
        entries.sort(key=lambda e: e[0])
        prev_index = None
        for index, msg_type, payload in entries:
            if index == prev_index:
                continue
            prev_index = index
            self.fsm.apply(index, MessageType(msg_type), payload)
            self._last_index = index
        self._applied = self._last_index
        self._apply_next = self._last_index + 1

    def _read_crc_entries(self, snap_idx: int, path: Optional[str] = None):
        """Pure-Python reader for the native wal.crc format
        ([u32 len][u32 crc32(payload)][payload]); validates CRCs and
        truncates a torn/corrupt tail exactly like wal.cc recover().
        ``path`` reads a sealed segment instead of the active log."""
        import struct as _struct
        import zlib

        out = []
        path = path or os.path.join(self.data_dir, "wal.crc")
        if not os.path.exists(path):
            return out
        size = os.path.getsize(path)
        good = 0
        with open(path, "rb") as fh:
            while True:
                header = fh.read(8)
                if len(header) < 8:
                    break
                length, crc = _struct.unpack("<II", header)
                if length > size - fh.tell():
                    break
                blob = fh.read(length)
                if len(blob) < length or (zlib.crc32(blob) & 0xFFFFFFFF) != crc:
                    break
                try:
                    index, msg_type, payload = _decode_entry(blob)
                except Exception:
                    break  # undecodable record — treat as corrupt tail
                good = fh.tell()
                if index > snap_idx:
                    out.append((index, msg_type, payload))
        if good < size:
            with open(path, "r+b") as fh:
                fh.truncate(good)
        return out

    def _read_legacy_entries(self, snap_idx: int,
                             path: Optional[str] = None):
        wal_path = path or self.wal_path
        out = []
        if not os.path.exists(wal_path):
            return out
        good_offset = 0
        torn = False
        wal_size = os.path.getsize(wal_path)
        with open(wal_path, "rb") as fh:
            while True:
                header = fh.read(_LEN.size)
                if len(header) < _LEN.size:
                    torn = len(header) > 0
                    break
                (length,) = _LEN.unpack(header)
                if length > wal_size - fh.tell():
                    # length prefix runs past EOF — torn tail (don't even
                    # attempt the read: a garbage prefix can claim GBs)
                    torn = True
                    break
                blob = fh.read(length)
                if len(blob) < length:
                    torn = True
                    break  # torn tail write — discard
                try:
                    index, msg_type, payload = _decode_entry(blob)
                except Exception:
                    # Length-valid but undecodable (garbage flush, or a
                    # pre-msgpack-format record): corrupt tail — truncate
                    # so appends follow the last good record.
                    torn = True
                    break
                good_offset = fh.tell()
                if index <= snap_idx:
                    continue
                out.append((index, msg_type, payload))
        # Truncate the torn tail so subsequent appends follow the last good
        # record — otherwise new fsynced entries land after garbage and are
        # unreachable on the next replay (silent loss).
        if torn:
            with open(wal_path, "r+b") as fh:
                fh.truncate(good_offset)
        return out

    # -- persistence -------------------------------------------------------

    def _persist(self, index: int, msg_type: MessageType, payload: dict):
        """WRITE one entry (buffered, index order — caller holds the
        raft lock) and return the durability token _sync_persist waits
        on outside the lock."""
        t_enc = time.perf_counter()
        blob = _encode_entry(index, msg_type, payload)
        self._encode_seconds += time.perf_counter() - t_enc
        # Fault point ``wal.fsync``: a crash here models the process
        # dying mid-frame — a torn partial record is left on disk (the
        # recovery path must truncate it) and the entry never applies.
        act = fault.faultpoint("wal.fsync", index=index,
                              msg_type=getattr(msg_type, "name",
                                               str(msg_type)))
        if act is not None:
            if act.kind == "delay":
                time.sleep(act.delay)
            else:
                self._write_torn_frame(blob)
                # Crash semantics: this process's log is DEAD.  Without
                # the poison, a caller catching the injected error could
                # keep appending — in the O_APPEND fallback those frames
                # land AFTER the torn one, get acked durable, and are
                # then silently truncated away with the bad tail at the
                # next recovery.
                self._wal_failed = True
                act.raise_injected()
        if self._nwal is not None:
            token = self._nwal.write(blob)
        else:
            pos = self._fh.tell()
            try:
                self._fh.write(_LEN.pack(len(blob)))
                self._fh.write(blob)
                self._fh.flush()
            except OSError:
                # Roll the torn frame back (ENOSPC): left mid-log it would
                # strand later appends behind it — recovery truncates at
                # the first bad frame.
                try:
                    self._fh.seek(pos)
                    self._fh.truncate(pos)
                except OSError:  # pragma: no cover — disk truly gone
                    pass
                raise
            with self._py_cv:
                self._py_written += 1
                token = self._py_written
        # Auto-snapshot accounting (caller holds the raft lock) + the
        # durability-token guard: inflight is raised ONLY once the write
        # succeeded, and _sync_persist's finally lowers it — the WAL
        # roll waits it to zero before swapping handles.
        self._entries_since_snap += 1
        self._bytes_since_snap += len(blob) + _LEN.size
        with self._py_cv:
            self._sync_inflight += 1
        return token

    def _sync_persist(self, seq: int, msg_type, entries: int = 1) -> None:
        """Wait (outside the raft lock) until the entry written as
        ``seq`` — the last of ``entries`` written back to back — is
        durable.  Concurrent callers coalesce into one fsync — natively
        via wal.cc's group commit, in the fallback via the same
        written/synced-seq single-syncer dance in Python."""
        t0 = time.perf_counter()
        try:
            self._do_sync_persist(seq)
        finally:
            self._release_persist(entries)
        self.metrics.measure_since("raft.fsync", t0)
        if msg_type == MessageType.APPLY_PLAN_RESULTS:
            # The loadgen report's plan_apply_fsync percentiles: the
            # durability wait specifically on the plan-apply path.
            self.metrics.measure_since("raft.fsync.plan", t0)

    def _release_persist(self, entries: int) -> None:
        """Hand back the durability tokens of ``entries`` written
        entries (the WAL roll waits for none to be out)."""
        with self._py_cv:
            self._sync_inflight -= entries
            self._py_cv.notify_all()

    def _do_sync_persist(self, seq: int) -> None:
        if self._nwal is not None:
            self._nwal.sync_to(seq)
        elif self.fsync:
            with self._py_cv:
                while True:
                    if getattr(self, "_py_failed", False):
                        # Sticky: a failed fsync may have dropped dirty
                        # pages AND cleared the kernel error state
                        # (fsyncgate) — a retry would return success
                        # and falsely ack never-written entries.
                        raise OSError("wal fsync previously failed")
                    if self._py_synced >= seq:
                        break
                    if not self._py_sync_in_flight:
                        self._py_sync_in_flight = True
                        cover = self._py_written
                        self._py_cv.release()
                        try:
                            os.fsync(self._fh.fileno())
                        except OSError:
                            self._py_cv.acquire()
                            self._py_sync_in_flight = False
                            self._py_failed = True
                            self._py_cv.notify_all()
                            raise
                        self._py_cv.acquire()
                        self._py_sync_in_flight = False
                        self._py_cv.notify_all()
                        if cover > self._py_synced:
                            self._py_synced = cover
                        break
                    self._py_cv.wait()

    def _write_torn_frame(self, blob: bytes) -> None:
        """Simulate a crash mid-append: leave a partial frame (header +
        truncated payload) at the tail of whichever log is active."""
        frame = _LEN.pack(len(blob)) + blob if self._nwal is None else (
            struct.pack("<II", len(blob), 0xDEADBEEF) + blob)
        torn = frame[:max(4, len(frame) // 2)]
        path = (os.path.join(self.data_dir, "wal.crc")
                if self._nwal is not None else self.wal_path)
        try:
            with open(path, "ab") as fh:
                fh.write(torn)
                fh.flush()
        except OSError:  # pragma: no cover — fault plumbing best-effort
            pass

    def _roll_wal(self, index: int) -> List[str]:
        """Seal the active WAL into immutable ``walseg-<index>`` files
        and open fresh logs (caller holds the raft lock).  Everything
        sealed is made durable FIRST — a durability token issued before
        the roll resolves against an already-fsynced prefix, never
        against the fresh (empty) log.  Returns the sealed paths for
        deletion once the snapshot blob that covers them is durable."""
        # Quiesce durability waiters: appends are blocked by the raft
        # lock, so the token set only drains; waiters never need the
        # raft lock, so this cannot deadlock.
        with self._py_cv:
            while self._sync_inflight:
                self._py_cv.wait(0.05)
        segs: List[str] = []
        if self._nwal is not None:
            try:
                self._nwal.sync()
            except OSError:
                self._wal_failed = True
                raise
            self._nwal.close()
            crc_path = os.path.join(self.data_dir, "wal.crc")
            if os.path.exists(crc_path) and os.path.getsize(crc_path):
                seg = os.path.join(self.data_dir,
                                   f"walseg-{index:012d}.crc")
                os.replace(crc_path, seg)
                segs.append(seg)
            from ..native import NativeWAL

            self._nwal = NativeWAL(crc_path, fsync=self.fsync)
            # Legacy records from a pre-native boot are covered too.
            if os.path.exists(self.wal_path) \
                    and os.path.getsize(self.wal_path):
                seg = os.path.join(self.data_dir,
                                   f"walseg-{index:012d}.log")
                os.replace(self.wal_path, seg)
                segs.append(seg)
        else:
            if self.fsync:
                try:
                    os.fsync(self._fh.fileno())
                except OSError:
                    with self._py_cv:
                        self._py_failed = True
                        self._py_cv.notify_all()
                    self._wal_failed = True
                    raise
            self._fh.close()
            if os.path.exists(self.wal_path) \
                    and os.path.getsize(self.wal_path):
                seg = os.path.join(self.data_dir,
                                   f"walseg-{index:012d}.log")
                os.replace(self.wal_path, seg)
                segs.append(seg)
            self._fh = open(self.wal_path, "ab")
            with self._py_cv:
                self._py_synced = self._py_written
                self._py_cv.notify_all()
        self._entries_since_snap = 0
        self._bytes_since_snap = 0
        return segs

    def _persist_snapshot_blob(self, snap_store, index: int) -> None:
        """Serialize + persist the FSM snapshot — the expensive step,
        run OUTSIDE the log lock so appends keep flowing into the fresh
        segment (and the seam the off-apply-path tests hook to prove
        it)."""
        blob = snap_store.persist()
        path = os.path.join(self.data_dir, f"snapshot-{index}")
        tmp = path + ".tmp"
        with open(tmp, "wb") as fh:
            fh.write(blob)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)

    def _snapshot_impl(self) -> bool:
        """One FSM snapshot + WAL compaction (fsm.go:568 +
        snapshotsRetained=2), apply-path-friendly: the log lock is held
        only for the sequencer drain, an O(1) copy-on-write state
        snapshot, and the segment roll; the serialization and the
        fsyncs run outside it."""
        t0 = time.perf_counter()
        with self._snap_serial:
            # Quiesce-at-index loop: the sequencer drain must run
            # WITHOUT the log lock — a live server's FSM-apply hooks
            # read applied_index() (which takes it), so holding it
            # across the drain deadlocks against the very entries being
            # drained.  Instead: read the target index, wait for the
            # sequencer to pass it lock-free, then re-acquire and
            # verify nothing new was assigned; retry on a moving
            # target (a saturated log just postpones compaction to the
            # watcher's next tick).
            for _attempt in range(50):
                with self._l:
                    if getattr(self, "_wal_failed", False):
                        return False
                    index = self._last_index
                with self._apply_cv:
                    while (self._apply_next <= index
                           and not self._apply_failed):
                        self._apply_cv.wait(timeout=1.0)
                with self._l:
                    if getattr(self, "_wal_failed", False):
                        return False
                    if self._last_index != index:
                        continue  # new appends landed; chase the target
                    snap_store = self.fsm.state.snapshot()
                    segs = self._roll_wal(index)
                    break
            else:
                return False  # never quiesced; retry on the next tick
            # Everything below runs while appends flow into the fresh
            # segment.  A crash anywhere here is safe: the sealed
            # segments still hold every entry the unfinished snapshot
            # would have covered.
            self._persist_snapshot_blob(snap_store, index)
            for seg in segs:
                try:
                    os.unlink(seg)
                except OSError:  # pragma: no cover — cleanup best-effort
                    pass
            # Retain only the most recent snapshots.
            for _old_idx, old_path in \
                    self._snapshot_files()[:-SNAPSHOTS_RETAINED]:
                try:
                    os.unlink(old_path)
                except OSError:  # pragma: no cover
                    pass
        self.metrics.incr_counter("raft.snapshot")
        self.metrics.measure_since("raft.snapshot.persist", t0)
        return True

    def _auto_snapshot_loop(self) -> None:
        """Threshold watcher (hashicorp/raft runSnapshots): snapshots
        on the dedicated thread, never on an applier's."""
        import logging as _logging

        while not self._snap_stop.wait(self.snapshot_interval):
            with self._l:
                due = (not getattr(self, "_wal_failed", False) and (
                    (self.snapshot_entries > 0
                     and self._entries_since_snap >= self.snapshot_entries)
                    or (self.snapshot_bytes > 0
                        and self._bytes_since_snap >= self.snapshot_bytes)))
            if not due:
                continue
            try:
                if self._snapshot_impl():
                    self.metrics.incr_counter("raft.snapshot.auto")
            except Exception:
                _logging.getLogger("nomad_tpu.raft").exception(
                    "automatic FSM snapshot failed")

    def snapshot(self) -> None:
        """Write an FSM snapshot and compact the WAL (operator/test
        entry point; the automatic path runs the same implementation)."""
        self._snapshot_impl()

    def close(self) -> None:
        self._snap_stop.set()
        if self._snap_thread is not None:
            self._snap_thread.join(timeout=2.0)
        if self._nwal is not None:
            self._nwal.close()
        if self._fh is not None:
            self._fh.close()


# ---------------------------------------------------------------------------
# Multi-server replication (hashicorp/raft equivalent)
# ---------------------------------------------------------------------------

# Log entries are [index, term, msg_type, payload_blob] lists (msgpack-ready
# for the wire).  msg_type NOOP_TYPE marks the leader's term-establishment
# no-op entry (hashicorp/raft LogNoop): it commits prior-term entries
# without feeding the FSM.  CONFIG_TYPE entries carry the voter set
# (hashicorp/raft LogConfiguration): membership changes replicate through
# the log so every server's quorum derives from a committed configuration,
# never from its private gossip view (which could yield disjoint quorums).
NOOP_TYPE = -1
CONFIG_TYPE = -2


class RaftTimeoutError(Exception):
    """Apply could not reach quorum within the timeout (the reference's
    raft.Apply(…, timeout) ErrEnqueueTimeout/leadership-lost errors)."""


class _ApplyFuture:
    """Resolution of one leader-appended log entry: the FSM result once the
    entry commits, or an error if leadership was lost first.  Fixes the
    round-1 race where concurrent apply() callers could lose their result
    to a sibling thread advancing commit_index."""

    __slots__ = ("_ev", "result", "error")

    def __init__(self):
        self._ev = threading.Event()
        self.result = None
        self.error: Optional[Exception] = None

    def resolve(self, result) -> None:
        self.result = result
        self._ev.set()

    def fail(self, exc: Exception) -> None:
        self.error = exc
        self._ev.set()

    def wait(self, timeout: float):
        if not self._ev.wait(timeout):
            raise RaftTimeoutError("raft apply timed out awaiting quorum")
        if self.error is not None:
            raise self.error
        return self.result


class _RaftStore:
    """Durable raft state: current term + vote, the entry log, and FSM
    snapshots (the raft-boltdb log store + stable store + snapshot store
    roles, nomad/server.go:91-95).  ``data_dir=None`` keeps everything in
    memory (the raftInmem dev path).

    Layout:
      meta            — msgpack {term, voted_for}, rewritten + fsynced
      wal             — length-prefixed msgpack [index, term, type, blob]
      snapshot-<idx>-<term> — FSM snapshot through <idx>
    """

    def __init__(self, data_dir: Optional[str]):
        self.dir = data_dir
        self._fh = None
        if self.dir:
            os.makedirs(self.dir, exist_ok=True)

    # -- load --------------------------------------------------------------

    def load(self):
        """Returns (term, voted_for, peers, base_index, base_term, entries,
        snapshot_blob_or_None)."""
        import msgpack
        term, voted = 0, None
        peers: List[str] = []
        base_index, base_term = 0, 0
        entries: List[list] = []
        snap_blob = None
        if not self.dir:
            return term, voted, peers, base_index, base_term, entries, snap_blob

        meta_path = os.path.join(self.dir, "meta")
        if os.path.exists(meta_path):
            with open(meta_path, "rb") as fh:
                meta = msgpack.unpackb(fh.read(), raw=False)
            term, voted = meta.get("term", 0), meta.get("voted_for")
            peers = meta.get("peers") or []

        snaps = self._snapshot_files()
        if snaps:
            (base_index, base_term), path = snaps[-1]
            with open(path, "rb") as fh:
                snap_blob = fh.read()

        wal_path = os.path.join(self.dir, "wal")
        if os.path.exists(wal_path):
            good = 0
            size = os.path.getsize(wal_path)
            with open(wal_path, "rb") as fh:
                while True:
                    header = fh.read(_LEN.size)
                    if len(header) < _LEN.size:
                        torn = len(header) > 0
                        break
                    (length,) = _LEN.unpack(header)
                    if length > size - fh.tell():
                        torn = True
                        break
                    blob = fh.read(length)
                    if len(blob) < length:
                        torn = True
                        break
                    entry = msgpack.unpackb(blob, raw=False)
                    good = fh.tell()
                    if entry[0] <= base_index:
                        continue  # covered by the snapshot
                    entries.append(entry)
                else:
                    torn = False
            if torn:
                with open(wal_path, "r+b") as fh:
                    fh.truncate(good)
        self._fh = open(os.path.join(self.dir, "wal"), "ab") if self.dir else None
        return term, voted, peers, base_index, base_term, entries, snap_blob

    def _snapshot_files(self):
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("snapshot-"):
                parts = name.split("-")
                try:
                    idx, term = int(parts[1]), int(parts[2])
                except (IndexError, ValueError):
                    continue
                out.append(((idx, term), os.path.join(self.dir, name)))
        return sorted(out)

    # -- persist -----------------------------------------------------------

    def save_meta(self, term: int, voted_for: Optional[str],
                  peers: Optional[List[str]] = None) -> None:
        if not self.dir:
            return
        import msgpack
        path = os.path.join(self.dir, "meta")
        tmp = path + ".tmp"
        with open(tmp, "wb") as fh:
            fh.write(msgpack.packb({"term": term, "voted_for": voted_for,
                                    "peers": peers or []},
                                   use_bin_type=True))
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)

    def append(self, entries: List[list]) -> None:
        if self._fh is None:
            return
        import msgpack
        for e in entries:
            blob = msgpack.packb(e, use_bin_type=True)
            self._fh.write(_LEN.pack(len(blob)))
            self._fh.write(blob)
        self._fh.flush()
        os.fsync(self._fh.fileno())

    def rewrite(self, entries: List[list]) -> None:
        """Conflict truncation / compaction: replace the whole WAL.

        Built atomically (tmp + fsync + rename): truncating the live WAL
        in place would let a crash mid-rewrite wipe already-acked entries
        — a follower counted toward an entry's commit quorum must never
        silently lose it."""
        if not self.dir:
            return
        import msgpack
        path = os.path.join(self.dir, "wal")
        tmp = path + ".tmp"
        with open(tmp, "wb") as fh:
            for e in entries:
                blob = msgpack.packb(e, use_bin_type=True)
                fh.write(_LEN.pack(len(blob)))
                fh.write(blob)
            fh.flush()
            os.fsync(fh.fileno())
        if self._fh is not None:
            self._fh.close()
        os.replace(tmp, path)
        self._fh = open(path, "ab")

    def save_snapshot(self, index: int, term: int, blob: bytes) -> None:
        if not self.dir:
            return
        path = os.path.join(self.dir, f"snapshot-{index}-{term}")
        tmp = path + ".tmp"
        with open(tmp, "wb") as fh:
            fh.write(blob)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
        for _, old in self._snapshot_files()[:-SNAPSHOTS_RETAINED]:
            os.unlink(old)

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


class MultiRaft(RaftLog):
    """Leader election + log replication across servers over the RPC raft
    channel (reference: hashicorp/raft beneath nomad/server.go setupRaft,
    transported via raft_rpc.go:34-90 RaftLayer on the shared RPC port).

    Raft's core, implemented fully: randomized election timeouts, term-voted
    RequestVote with persisted term/vote, AppendEntries with prev-entry
    consistency check and follower conflict truncation, per-peer replicator
    threads driving next/match indexes, majority commit restricted to
    current-term entries, InstallSnapshot for peers behind the compaction
    horizon, ordered FSM apply, and per-index apply futures so every
    ``apply`` caller receives its own FSM result.

    Entry payloads cross the wire as whitelisted msgpack trees
    (server/log_codec.py), never pickle — a raft peer can only inject data,
    not code.

    ``apply`` blocks until the entry is committed by a majority and applied
    locally, then returns (result, index) — identical semantics to the
    single-voter path so the Server code above it does not change.
    """

    # Election timeout must comfortably exceed worst-case scheduling
    # latency for the first post-election heartbeat — too tight and a
    # loaded host deposes every new leader before its heartbeat lands
    # (the reference runs 500ms-1s timeouts against 100ms heartbeats).
    HEARTBEAT_INTERVAL = 0.05
    ELECTION_TIMEOUT = (0.30, 0.60)
    APPLY_TIMEOUT = 10.0
    REPLICATE_BATCH = 512
    # Auto-compact once the in-memory log exceeds this many entries
    # (hashicorp/raft SnapshotThreshold, default 8192).
    SNAPSHOT_THRESHOLD = 8192

    def __init__(self, fsm: FSM, my_addr: str, pool,
                 data_dir: Optional[str] = None, logger=None):
        super().__init__(fsm)
        import logging as _logging
        import random

        self.logger = logger or _logging.getLogger("nomad_tpu.raft")
        self.my_addr = my_addr
        self.pool = pool
        self._rand = random.Random(hash(my_addr) & 0xFFFFFF)
        self._leader = False  # starts as follower, unlike single-voter
        # Timing knobs (instance-level env overrides of the class
        # defaults): a GIL-bound in-process cluster under measurement
        # load can starve the leader's heartbeat threads past the stock
        # 0.3-0.6s window — depositions mid-benchmark measure election
        # churn, not scheduling.  The loadgen harness slows elections
        # down (NOMAD_TPU_RAFT_ELECTION_MIN_S/MAX_S) the way the
        # reference tunes raft_multiplier on loaded hardware.
        self.HEARTBEAT_INTERVAL = _env_float(
            "NOMAD_TPU_RAFT_HEARTBEAT_S", type(self).HEARTBEAT_INTERVAL)
        self.ELECTION_TIMEOUT = (
            _env_float("NOMAD_TPU_RAFT_ELECTION_MIN_S",
                       type(self).ELECTION_TIMEOUT[0]),
            _env_float("NOMAD_TPU_RAFT_ELECTION_MAX_S",
                       type(self).ELECTION_TIMEOUT[1]))

        self.store = _RaftStore(data_dir)
        (self.term, self.voted_for, saved_peers, self.base_index,
         self.base_term, self.log, snap_blob) = self.store.load()
        if snap_blob is not None:
            self.fsm.restore(snap_blob)
        # Only the snapshot prefix is known-committed at boot; WAL entries
        # beyond it may be uncommitted and are re-committed by the leader.
        self.commit_index = self.base_index
        self._last_index = self.base_index  # last *applied*
        self._applied = self.base_index

        self.leader_addr: Optional[str] = None
        self.state = "follower"
        # The voter set comes from the persisted committed configuration;
        # a fresh server has none and cannot campaign until it is either
        # gossip-bootstrapped (initial cluster formation) or added to the
        # cluster through a replicated CONFIG entry.
        self.peers: List[str] = saved_peers or [my_addr]
        self._bootstrapped = bool(saved_peers)
        # Non-voting members (the reference's non_voting_server, ISSUE
        # 10): replicated like voters — they receive AppendEntries /
        # InstallSnapshot and apply the FSM, which is what follower-read
        # scheduling needs — but they are never counted toward quorum
        # and never campaign.  Scheduling capacity scales with learner
        # count while commit latency stays pinned to the voter set.
        self.learners: List[str] = []

        self._futures: dict = {}           # index -> _ApplyFuture
        # Leader-appended entries keep their ORIGINAL payload object so
        # the local FSM apply skips re-decoding its own blob (the
        # single-voter path shares objects the same way; followers
        # decode from the replicated blob as before).  Entries are
        # dropped at apply and at conflict truncation — a truncated
        # index may be refilled by a DIFFERENT leader's entry.
        self._local_payloads: dict = {}    # index -> payload
        self._next: dict = {}              # peer -> next index to send
        self._match: dict = {}             # peer -> highest replicated index
        self._repl_events: dict = {}       # peer -> threading.Event
        self._repl_threads: dict = {}      # peer -> Thread

        self._last_contact = 0.0
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        # Follower-side async apply (ISSUE 10): raft only requires a
        # follower to APPEND before acking — applying committed entries
        # can happen after the reply.  Doing it inline put every FSM
        # apply inside the leader's quorum round trip (a loaded
        # follower's apply time became plan-apply latency cluster-wide);
        # the applier thread drains commit_index in small chunks so
        # incoming AppendEntries interleave on the lock.
        self._apply_kick = threading.Event()
        # Leadership transitions are delivered to callbacks strictly in
        # the order they occurred, by one dispatcher thread.  Spawning a
        # thread per transition could reorder a win-then-step-down into
        # step-down-then-win, leaving the server side believing it leads
        # while raft follows.
        import queue as _queue
        self._leader_q: "_queue.Queue" = _queue.Queue()

    def _leader_dispatch_loop(self) -> None:
        import queue as _queue
        while not self._stop.is_set():
            try:
                val = self._leader_q.get(timeout=0.5)
            except _queue.Empty:
                continue
            try:
                self._set_leader(val)
            except Exception:
                # A raising leadership callback (e.g. an establish-time
                # apply losing leadership mid-flight) must not kill the
                # dispatcher — later transitions still need delivery.
                self.logger.exception("raft: leadership callback failed")

    # -- log shape helpers (caller holds self._l) --------------------------

    def _last_log_index(self) -> int:
        return self.base_index + len(self.log)

    def _term_at(self, index: int) -> int:
        if index == self.base_index:
            return self.base_term
        if index < self.base_index or index > self._last_log_index():
            return -1  # unknown (compacted away / beyond end)
        return self.log[index - self.base_index - 1][1]

    def _entries_from(self, index: int, limit: int) -> List[list]:
        start = index - self.base_index - 1
        return self.log[start:start + limit]

    # -- lifecycle ---------------------------------------------------------

    # Entries applied per lock hold by the async applier: small enough
    # that an incoming AppendEntries (which only needs the lock for the
    # append) never waits behind a long committed backlog.
    APPLY_CHUNK = 16

    def start(self) -> None:
        import time as _time
        self._last_contact = _time.monotonic()
        for target, name in ((self._ticker, "raft-ticker"),
                             (self._leader_dispatch_loop, "raft-leadership"),
                             (self._apply_loop, "raft-applier")):
            t = threading.Thread(target=target, name=name, daemon=True)
            t.start()
            self._threads.append(t)

    def _apply_loop(self) -> None:
        """Follower-side committed-entry applier: drains ``commit_index``
        OUTSIDE the AppendEntries reply path.  Chunked lock holds keep
        appends interleaving; ordering is preserved because _apply_to
        only ever advances _last_index under the lock (the leader's
        inline _advance_commit applies through the same guard, so a
        freshly promoted leader and this thread cannot double-apply)."""
        while not self._stop.is_set():
            if not self._apply_kick.wait(0.05):
                continue
            self._apply_kick.clear()
            while True:
                with self._l:
                    if self._last_index >= self.commit_index:
                        break
                    self._apply_to(min(self.commit_index,
                                       self._last_index + self.APPLY_CHUNK))

    def close(self) -> None:
        self._stop.set()
        with self._l:
            self._fail_futures(NotLeaderError("shutting down"))
        for ev in self._repl_events.values():
            ev.set()
        self.store.close()

    def bootstrap(self, peers: List[str]) -> None:
        """Adopt the *initial* voter set and enable elections (serf.go:91
        maybeBootstrap).  No-op once a configuration exists: later voter
        changes must replicate through the log (propose_config) so every
        server's quorum derives from a committed config — unilateral
        adoption of a private gossip view could produce disjoint quorums
        and split-brain."""
        with self._l:
            if self._bootstrapped:
                return
            self.peers = sorted(set(peers) | {self.my_addr})
            self._bootstrapped = True
            self._persist_meta()

    def propose_config(self, peers: List[str]) -> None:
        """Leader-only voter-set change via a replicated CONFIG log entry
        (hashicorp/raft AddVoter; single-config approximation — the leader
        uses the new config as soon as it is appended, followers on
        apply)."""
        import msgpack
        with self._l:
            if self.state != "leader":
                raise NotLeaderError(self.leader_addr or "")
            peers = sorted(set(peers) | {self.my_addr})
            if peers == self.peers:
                return
            index = self._last_log_index() + 1
            entry = [index, self.term,
                     CONFIG_TYPE, msgpack.packb(peers, use_bin_type=True)]
            self.log.append(entry)
            self.store.append([entry])
            fut = _ApplyFuture()
            self._futures[index] = fut
            self._adopt_peers(peers)
            self._advance_commit()
        self._kick_replicators()
        fut.wait(self.APPLY_TIMEOUT)

    def _adopt_peers(self, peers: List[str]) -> None:
        # caller holds self._l
        added = [p for p in peers if p not in self.peers]
        self.peers = list(peers)
        self._bootstrapped = True
        self._persist_meta()
        if self.state == "leader":
            for p in added:
                if p != self.my_addr:
                    self._start_replicator(p)

    def _quorum(self) -> int:
        return len(self.peers) // 2 + 1

    def is_raft_leader(self) -> bool:
        with self._l:
            return self.state == "leader"

    def fence_index(self) -> int:
        """Last LOG index: election safety puts every committed entry
        at or below it, and unlike the applied index it cannot lag the
        async applier (see RaftLog.fence_index)."""
        with self._l:
            return self._last_log_index()

    # -- persistence helpers (caller holds self._l) ------------------------

    def _persist_meta(self) -> None:
        self.store.save_meta(self.term, self.voted_for,
                             self.peers if self._bootstrapped else [])

    # -- RPC entry (RPCServer.raft_handler) --------------------------------

    def handle_message(self, msg: dict) -> dict:
        if self._stop.is_set():
            raise RuntimeError("raft: node is shut down")
        kind = msg.get("kind")
        if kind == "request_vote":
            return self._on_request_vote(msg)
        if kind == "append_entries":
            return self._on_append_entries(msg)
        if kind == "install_snapshot":
            return self._on_install_snapshot(msg)
        raise ValueError(f"unknown raft message kind {kind!r}")

    # -- election ----------------------------------------------------------

    def _election_timeout(self) -> float:
        lo, hi = self.ELECTION_TIMEOUT
        return lo + self._rand.random() * (hi - lo)

    def add_learner(self, addr: str) -> None:
        """Leader-side: attach a non-voting member to the replication
        fan-out (no CONFIG entry — learners are not part of the
        committed quorum configuration)."""
        with self._l:
            if (addr == self.my_addr or addr in self.peers
                    or addr in self.learners):
                return
            self.learners.append(addr)
            if self.state == "leader":
                self._start_replicator(addr)

    def _ticker(self) -> None:
        import time as _time
        timeout = self._election_timeout()
        while not self._stop.is_set():
            _time.sleep(0.015)
            with self._l:
                # Non-members never campaign: a learner receives the
                # committed voter config (it is not in it), and a voter
                # removed from the config must not start elections its
                # quorum can't win.
                campaigning_ok = (self._bootstrapped
                                  and self.state != "leader"
                                  and self.my_addr in self.peers)
                since = _time.monotonic() - self._last_contact
            if campaigning_ok and since >= timeout:
                self._run_election()
                timeout = self._election_timeout()

    def _run_election(self) -> None:
        import time as _time
        with self._l:
            self.state = "candidate"
            self.term += 1
            term = self.term
            self.voted_for = self.my_addr
            self._persist_meta()
            self.leader_addr = None
            last_index = self._last_log_index()
            last_term = self._term_at(last_index)
            peers = [p for p in self.peers if p != self.my_addr]
            self._last_contact = _time.monotonic()
        votes = 1
        lock = threading.Lock()
        done = threading.Event()

        def ask(peer):
            nonlocal votes
            try:
                from .rpc import RPC_RAFT
                reply = self.pool.call(peer, "raft", {
                    "kind": "request_vote", "term": term,
                    "candidate": self.my_addr,
                    "last_log_index": last_index, "last_log_term": last_term,
                }, channel=RPC_RAFT, timeout=0.5)
            except Exception:
                return
            step_down = False
            with self._l:
                if reply.get("term", 0) > self.term:
                    self._step_down(reply["term"])
                    step_down = True
            if step_down:
                done.set()
                return
            with lock:
                if reply.get("granted"):
                    votes += 1
                    if votes >= self._quorum():
                        done.set()

        threads = [threading.Thread(target=ask, args=(p,), daemon=True)
                   for p in peers]
        for t in threads:
            t.start()
        if not peers:
            done.set()
        done.wait(timeout=0.6)
        with self._l:
            if self.state == "candidate" and self.term == term \
                    and votes >= self._quorum():
                self._become_leader()
                # Callbacks (broker enable, eval restore, …) run on the
                # ordered dispatcher thread, outside the raft lock: they
                # may apply entries themselves.
                self._leader_q.put(True)

    def _become_leader(self) -> None:
        # caller holds self._l
        self.state = "leader"
        self.leader_addr = self.my_addr
        self.logger.info("raft: %s won election for term %d",
                         self.my_addr, self.term)
        last = self._last_log_index()
        for p in self.peers:
            if p == self.my_addr:
                continue
            self._next[p] = last + 1
            self._match[p] = 0
        # Term-establishment entry (Raft §5.4.2 — a leader never counts
        # replicas of old-term entries toward commitment directly).  It
        # carries the current voter configuration so every follower adopts
        # and persists the committed config (hashicorp/raft re-ships its
        # LogConfiguration the same way).
        import msgpack
        cfg = [last + 1, self.term, CONFIG_TYPE,
               msgpack.packb(self.peers, use_bin_type=True)]
        self.log.append(cfg)
        self.store.append([cfg])
        for p in self.peers + self.learners:
            if p != self.my_addr:
                self._start_replicator(p)
        self._advance_commit()

    def _start_replicator(self, peer: str) -> None:
        # caller holds self._l.  Replicator threads are per-(peer, term):
        # a thread from an older term is already exiting (its term check
        # fails), so only an alive *current-term* thread short-circuits.
        old = self._repl_threads.get(peer)
        if old is not None and old[0] == self.term and old[1].is_alive():
            self._repl_events[peer].set()
            return
        self._next.setdefault(peer, self._last_log_index() + 1)
        self._match.setdefault(peer, 0)
        ev = threading.Event()
        ev.set()
        self._repl_events[peer] = ev
        t = threading.Thread(target=self._replicate_peer,
                             args=(peer, self.term, ev),
                             name=f"raft-repl-{peer}", daemon=True)
        self._repl_threads[peer] = (self.term, t)
        t.start()

    def _step_down(self, term: int) -> None:
        # caller holds self._l
        was_leader = self.state == "leader"
        if term > self.term:
            self.term = term
            self.voted_for = None
            self._persist_meta()
        self.state = "follower"
        self._fail_futures(NotLeaderError(self.leader_addr or ""))
        for ev in self._repl_events.values():
            ev.set()  # wake replicators so they observe the term change
        if was_leader:
            self._leader_q.put(False)

    def _fail_futures(self, exc: Exception) -> None:
        # caller holds self._l
        for fut in self._futures.values():
            fut.fail(exc)
        self._futures.clear()

    def _on_request_vote(self, msg: dict) -> dict:
        import time as _time
        with self._l:
            if msg["term"] < self.term:
                return {"granted": False, "term": self.term}
            if msg["term"] > self.term:
                self._step_down(msg["term"])
            my_last = self._last_log_index()
            up_to_date = (
                msg["last_log_term"], msg["last_log_index"]
            ) >= (self._term_at(my_last), my_last)
            if up_to_date and self.voted_for in (None, msg["candidate"]):
                self.voted_for = msg["candidate"]
                self._persist_meta()  # durable before granting (Raft §5.2)
                self._last_contact = _time.monotonic()
                return {"granted": True, "term": self.term}
            return {"granted": False, "term": self.term}

    # -- leader replication ------------------------------------------------

    def _replicate_peer(self, peer: str, term: int, kick: threading.Event,
                        ) -> None:
        """Per-peer replication loop (hashicorp/raft replicate()): ships
        missing entries / heartbeats, falls back to InstallSnapshot when the
        peer is behind the compaction horizon."""
        from .rpc import RPC_RAFT
        while not self._stop.is_set():
            with self._l:
                if self.state != "leader" or self.term != term:
                    return
                ni = self._next.get(peer, self.base_index + 1)
                snapshot_needed = ni <= self.base_index
                if not snapshot_needed:
                    entries = self._entries_from(ni, self.REPLICATE_BATCH)
                    prev_index = ni - 1
                    prev_term = self._term_at(prev_index)
                    commit = self.commit_index
            try:
                if snapshot_needed:
                    self._send_snapshot(peer, term)
                    continue
                reply = self.pool.call(peer, "raft", {
                    "kind": "append_entries", "term": term,
                    "leader": self.my_addr,
                    "prev_log_index": prev_index,
                    "prev_log_term": prev_term,
                    "entries": entries,
                    "leader_commit": commit,
                }, channel=RPC_RAFT, timeout=2.0)
            except Exception:
                kick.clear()
                kick.wait(0.1)
                continue
            with self._l:
                if reply.get("term", 0) > self.term:
                    self._step_down(reply["term"])
                    return
                if self.state != "leader" or self.term != term:
                    return
                if reply.get("success"):
                    sent_through = prev_index + len(entries)
                    self._match[peer] = max(self._match.get(peer, 0),
                                            sent_through)
                    self._next[peer] = sent_through + 1
                    self._advance_commit()
                    more = self._next[peer] <= self._last_log_index()
                else:
                    # Consistency check failed: back up using the
                    # follower's hint (accelerated log backtracking).  A
                    # hint behind our compaction horizon means the entries
                    # it needs are gone — ship a snapshot instead.
                    hint = reply.get("match", prev_index - 1)
                    if hint < self.base_index:
                        self._next[peer] = self.base_index
                    else:
                        self._next[peer] = max(self.base_index + 1,
                                               min(hint + 1, ni - 1))
                    more = True
            if not more:
                kick.clear()
                kick.wait(self.HEARTBEAT_INTERVAL)

    def _snapshot_chunk_size(self) -> int:
        """Bytes per InstallSnapshot chunk (streaming install,
        ISSUE 10): a follower far behind the horizon catches up off the
        PR 9 binary (NTPUSNP2) blob incrementally instead of one giant
        frame — each chunk stays well under the RPC frame cap and
        refreshes the follower's leader-contact clock, so a multi-GB
        install can no longer starve its election timer or blow the
        64MB frame limit."""
        return max(1, _env_int("NOMAD_TPU_SNAPSHOT_CHUNK", 4 << 20))

    def _send_snapshot(self, peer: str, term: int) -> None:
        """InstallSnapshot for a peer behind the log horizon: one frame
        for small blobs (wire-compatible with pre-streaming followers),
        chunked offset/total/done frames past the chunk size."""
        from .rpc import RPC_RAFT
        with self._l:
            if self.state != "leader" or self.term != term:
                return
            blob = self.fsm.snapshot()
            last_index = self._last_index
            last_term = self._term_at(last_index)
            if last_term < 0:
                last_term = self.base_term
            peers = list(self.peers)
        chunk = self._snapshot_chunk_size()
        base = {"kind": "install_snapshot", "term": term,
                "leader": self.my_addr,
                "last_index": last_index, "last_term": last_term,
                "peers": peers}  # config rides the snapshot
        try:
            if len(blob) <= chunk:
                reply = self.pool.call(
                    peer, "raft", dict(base, data=blob),
                    channel=RPC_RAFT, timeout=10.0)
            else:
                total = len(blob)
                reply = None
                for off in range(0, total, chunk):
                    with self._l:
                        if self.state != "leader" or self.term != term:
                            return
                    reply = self.pool.call(peer, "raft", dict(
                        base, data=blob[off:off + chunk], offset=off,
                        total=total, done=off + chunk >= total,
                    ), channel=RPC_RAFT, timeout=10.0)
                    self.metrics.incr_counter("raft.snapshot.chunks_sent")
                    if reply.get("term", 0) > term \
                            or not reply.get("success", False):
                        break  # demoted, or receiver lost the sequence
        except Exception:
            self._repl_events[peer].clear()
            self._repl_events[peer].wait(0.2)
            return
        with self._l:
            if reply is not None and reply.get("term", 0) > self.term:
                self._step_down(reply["term"])
                return
            if reply is None or not reply.get("success", True):
                # Receiver aborted (restart/sequence loss): the
                # replicator loop retries the install from offset 0.
                return
            self._match[peer] = max(self._match.get(peer, 0), last_index)
            self._next[peer] = last_index + 1
            self._advance_commit()

    def _kick_replicators(self) -> None:
        with self._l:
            events = list(self._repl_events.values())
        for ev in events:
            ev.set()

    def _advance_commit(self) -> None:
        """Majority-match commit advancement; only current-term entries
        commit by counting (Raft §5.4.2).  Caller holds self._l."""
        if self.state != "leader":
            return
        matches = sorted(
            [self._last_log_index()]
            + [self._match.get(p, 0) for p in self.peers if p != self.my_addr]
        )
        n = matches[len(matches) - self._quorum()]
        if n > self.commit_index and self._term_at(n) == self.term:
            self.commit_index = n
            if self._threads:
                # FSM application (and future resolution) runs on the
                # dedicated applier thread: replicator reply handling
                # holding the raft lock through every committed entry's
                # FSM apply made lock waits — and therefore the NEXT
                # replication round — scale with apply cost.
                self._apply_kick.set()
            else:  # not start()ed (unit-test harness): inline
                self._apply_to(self.commit_index)

    def _apply_to(self, target: int) -> None:
        """Apply committed entries through ``target`` in index order,
        resolving apply futures.  Caller holds self._l."""
        from .log_codec import decode_payload
        while self._last_index < target:
            idx = self._last_index + 1
            _eidx, _eterm, mt, blob = self.log[idx - self.base_index - 1]
            result = None
            if mt == CONFIG_TYPE:
                import msgpack
                peers = msgpack.unpackb(blob, raw=False)
                if peers != self.peers:
                    self._adopt_peers(peers)
                else:
                    self._bootstrapped = True
                    self._persist_meta()
            elif mt != NOOP_TYPE:
                payload = self._local_payloads.pop(idx, None)
                try:
                    result = self.fsm.apply(
                        idx, MessageType(mt),
                        payload if payload is not None
                        else decode_payload(blob))
                except Exception:
                    self.logger.exception("raft: fsm apply failed at %d", idx)
            self._last_index = idx
            self._applied = idx
            fut = self._futures.pop(idx, None)
            if fut is not None:
                fut.resolve(result)
        if len(self.log) > self.SNAPSHOT_THRESHOLD:
            self._compact()

    # -- follower side -----------------------------------------------------

    def _on_append_entries(self, msg: dict) -> dict:
        import time as _time
        with self._l:
            if msg["term"] < self.term:
                return {"success": False, "term": self.term}
            if msg["term"] > self.term or self.state != "follower":
                self._step_down(msg["term"])
                self.term = msg["term"]
                self._persist_meta()
            self.leader_addr = msg["leader"]
            self._last_contact = _time.monotonic()

            prev_index = msg["prev_log_index"]
            prev_term = msg["prev_log_term"]
            entries = [list(e) for e in msg["entries"]]
            # Anything at or before our snapshot base is already committed
            # here; skip those entries and anchor at the base.
            if prev_index < self.base_index:
                entries = [e for e in entries if e[0] > self.base_index]
                prev_index = self.base_index
                prev_term = self.base_term
            if prev_index > self._last_log_index():
                return {"success": False, "term": self.term,
                        "match": self._last_log_index()}
            if self._term_at(prev_index) != prev_term:
                return {"success": False, "term": self.term,
                        "match": max(self.base_index, prev_index - 1)}
            # Truncate conflicts, then append the new suffix with ONE
            # durable write (one fsync per RPC, not per entry).
            append_from = None
            for k, e in enumerate(entries):
                pos = e[0] - self.base_index - 1
                if pos < len(self.log):
                    if self.log[pos][1] != e[1]:
                        del self.log[pos:]
                        self.store.rewrite(self.log)
                        # A different leader refills these indexes: the
                        # cached local payloads no longer describe them.
                        for cached in [i for i in self._local_payloads
                                       if i >= e[0]]:
                            del self._local_payloads[cached]
                        append_from = k
                        break
                    # identical entry already present — skip
                else:
                    append_from = k
                    break
            if append_from is not None:
                new = entries[append_from:]
                self.log.extend(new)
                self.store.append(new)
            new_commit = min(msg["leader_commit"], self._last_log_index())
            if new_commit > self.commit_index:
                self.commit_index = new_commit
                if self._threads:
                    # Ack now, apply async: the applier thread owns the
                    # FSM catch-up (see _apply_loop) so a busy
                    # follower's apply time never rides the leader's
                    # quorum wait.
                    self._apply_kick.set()
                else:  # not start()ed (unit-test harness): inline
                    self._apply_to(new_commit)
            return {"success": True, "term": self.term,
                    "match": self._last_log_index()}

    def _on_install_snapshot(self, msg: dict) -> dict:
        import time as _time
        with self._l:
            if msg["term"] < self.term:
                return {"term": self.term}
            if msg["term"] > self.term or self.state != "follower":
                self._step_down(msg["term"])
                self.term = msg["term"]
                self._persist_meta()
            self.leader_addr = msg["leader"]
            self._last_contact = _time.monotonic()
            if "offset" in msg:
                # Streaming install: buffer chunks until done.  The key
                # pins one specific snapshot transfer; any sequence
                # break (leader restart, interleaved transfer) replies
                # success=False and the leader restarts from offset 0.
                key = (msg["term"], msg["last_index"], msg["total"])
                rx = getattr(self, "_snap_rx", None)
                if msg["offset"] == 0:
                    rx = self._snap_rx = {"key": key, "chunks": [],
                                          "received": 0}
                if (rx is None or rx["key"] != key
                        or rx["received"] != msg["offset"]):
                    self._snap_rx = None
                    return {"term": self.term, "success": False}
                rx["chunks"].append(msg["data"])
                rx["received"] += len(msg["data"])
                if not msg.get("done"):
                    return {"term": self.term, "success": True}
                self._snap_rx = None
                if rx["received"] != msg["total"]:
                    return {"term": self.term, "success": False}
                msg = dict(msg, data=b"".join(rx["chunks"]))
            self.fsm.restore(msg["data"])
            if msg.get("peers"):
                self._adopt_peers(list(msg["peers"]))
            self.base_index = msg["last_index"]
            self.base_term = msg["last_term"]
            self.log = []
            self._local_payloads.clear()
            self.store.save_snapshot(self.base_index, self.base_term,
                                     msg["data"])
            self.store.rewrite([])
            self.commit_index = self.base_index
            self._last_index = self.base_index
            self._applied = self.base_index
            return {"term": self.term, "success": True}

    # -- compaction --------------------------------------------------------

    def _compact(self) -> None:
        """Snapshot the FSM at the applied index and drop covered entries.
        Caller holds self._l."""
        applied = self._last_index
        if applied <= self.base_index:
            return
        blob = self.fsm.snapshot()
        new_base_term = self._term_at(applied)
        self.log = self.log[applied - self.base_index:]
        self.base_index = applied
        self.base_term = new_base_term
        self.store.save_snapshot(applied, new_base_term, blob)
        self.store.rewrite(self.log)

    def snapshot(self) -> None:
        with self._l:
            self._compact()

    # -- the apply path ----------------------------------------------------

    def apply_many(self, entries: List[Tuple[MessageType, dict]]) -> list:
        """Each entry through :meth:`apply`, in order: on the multi-voter
        log every entry waits its own replication round trip."""
        outcomes: list = []
        for msg_type, payload in entries:
            try:
                outcomes.append(self.apply(msg_type, payload))
            except Exception as exc:
                outcomes.append(exc)
        return outcomes

    def apply(self, msg_type: MessageType, payload: dict):
        from .log_codec import encode_payload
        t0 = time.perf_counter()
        # Encode OUTSIDE the raft lock: concurrent appliers pay their
        # own codec time instead of convoying every append behind it
        # (an entry is pure data; index assignment below still orders
        # the log).
        blob = encode_payload(payload)
        with self._l:
            if self.state != "leader":
                raise NotLeaderError(self.leader_addr or "")
            if _fire_apply_fault(self._last_log_index() + 1,
                                 msg_type) is not None:
                # Injected step-down: a real demotion — the cluster
                # re-elects (possibly us) via the normal election timer.
                self._step_down(self.term)
                raise NotLeaderError(self.leader_addr or "")
            index = self._last_log_index() + 1
            entry = [index, self.term, int(msg_type), blob]
            self.log.append(entry)
            self.store.append([entry])
            fut = _ApplyFuture()
            self._futures[index] = fut
            self._local_payloads[index] = payload
            self._advance_commit()  # single-voter clusters commit here
        self._kick_replicators()
        result = fut.wait(self.APPLY_TIMEOUT)
        self.metrics.measure_since("raft.apply", t0)
        tr = tracing.TRACER
        if tr is not None:
            tr.record("raft.apply", t0, time.perf_counter(), index=index,
                      msg_type=getattr(msg_type, "name", str(msg_type)))
        return result, index
