"""Columnar numpy mirrors of the state store's node + alloc tables.

At 1M nodes the control plane's residual host cost is walking Python
objects: ``ops/encode.encode_cluster_static`` loops a million ``Node``
dataclasses to build device buffers, and the usage matrix is rebuilt
from a million alloc rows on every cold encode.  This module keeps the
scheduler-visible numeric columns **inside the StateStore**, maintained
incrementally at every write path, so the encode slices arrays instead
of walking objects (ROADMAP item 2's slab/columnar state-store lift).

Representation (one ``ClusterColumns`` per store/snapshot):

- **Node columns** — ``cap``/``res`` ``[capy, 4] int64`` (resources /
  reserved), ``eligible [capy] bool`` (``status==ready and not drain``),
  ``dc_code``/``class_code [capy] int32`` against append-only codebooks
  whose codes are assigned in node-insertion order — exactly the
  first-seen order the object walk's ``setdefault`` produces, which is
  what makes the column-built buffers bit-identical to the walk.
- **Usage matrix** — ``usage [capy, 4] int64``: summed live-alloc usage
  per node row.  NOT maintained by per-write hooks: it is *derived* from
  the store's existing bounded usage-delta log (``allocs_since``, the
  PR 5 ``_alloc_log`` discipline) and caught up lazily at read time —
  bulk slab commits stay O(1) on the write path, and the fold is
  O(changed allocs) per read.

Sharing discipline (the proven ``_alloc_log`` copy-on-write shape):
``snapshot()`` shallow-copies the container (array refs shared, private
``n``/cursor/ownership metadata) in O(1).  Appends are cursor-safe (a
snapshot never reads rows >= its recorded ``n``) so only the creator
store appends in place; any in-place row update or usage fold first
copies the arrays it touches when they are shared.  Codebooks and the
row index are append-only and never copied.

Invalidation: structural changes that could reorder codebooks (node
delete, an existing node changing datacenter/computed-class) drop the
container outright; the owning store rebuilds it on the next
``snapshot()``/``ensure_columns()``.  A columnar-guard mismatch
(ops/encode) bumps the module epoch, invalidating every container in
the process.

Env knobs:

- ``NOMAD_TPU_COLUMNAR``              — 0 disables the columnar path
  (object-walk encode + legacy msgpack FSM snapshots; the kill-switch)
- ``NOMAD_TPU_COLUMNAR_GUARD_EVERY``  — differential-guard cadence in
  columnar static encodes (default 16; 0 disables; tests pin 1)
"""
from __future__ import annotations

import itertools
import logging
import os
import struct
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..structs.structs import NodeColumn

logger = logging.getLogger("nomad_tpu.state.columnar")

RES_DIMS = 4

# Below this many rows a Python loop over them beats array operations:
# each numpy call has a fixed cost, and on a server whose other threads
# want the interpreter lock every call that releases it can wait a
# switch interval to get it back (measured in the served stream path at
# ten rows per plan, PERF.md PR 27).
ARRAY_MIN_ROWS = 64

# Guard epoch: bumped on a columnar-guard mismatch (ops/encode); every
# container built under an older epoch is invalid and rebuilt by its
# owning store before the columnar path is trusted again.
EPOCH = 0

# Module counters (telemetry bridge + tests/selfcheck).
GUARD_RUNS = 0
GUARD_MISMATCHES = 0
COLUMNAR_ENCODES = 0
WALK_ENCODES = 0
REBUILDS = 0
# Usage-matrix reads through ops/batch_sched._columnar_usage and its
# own walk-compare guard (same cadence knob as the static guard).
USAGE_READS = 0
USAGE_GUARD_RUNS = 0
USAGE_GUARD_MISMATCHES = 0


def enabled() -> bool:
    from ..utils import knobs

    return knobs.get_bool("NOMAD_TPU_COLUMNAR")


def guard_every() -> int:
    from ..utils import knobs

    return knobs.get_int("NOMAD_TPU_COLUMNAR_GUARD_EVERY")


def bump_epoch() -> None:
    global EPOCH
    EPOCH += 1


def note_guard_mismatch(kind: str, detail: str, breaker=None,
                        **payload) -> None:
    """The shared degrade-on-mismatch protocol for BOTH columnar guards
    (static encode and usage matrix): count, bump the epoch (every
    mirror in the process rebuilds before being trusted again), log,
    trace, publish a ColumnarGuardMismatch event, and feed the PR 2
    breaker.  One protocol, two callers — a change to the response must
    not let the guards diverge."""
    from .. import fault
    from ..utils import tracing

    global GUARD_MISMATCHES, USAGE_GUARD_MISMATCHES
    if kind == "static":
        GUARD_MISMATCHES += 1
    else:
        USAGE_GUARD_MISMATCHES += 1
    bump_epoch()
    logger.error(
        "columnar %s guard diverged from the object walk (%s); "
        "rebuilding the mirror and feeding the breaker", kind, detail)
    tracing.event("columnar.guard_mismatch", kind=kind, detail=detail,
                  **{k.lower(): v for k, v in payload.items()})
    fault.note_event_stream(
        "Node", "ColumnarGuardMismatch", detail,
        dict(payload, Kind=kind, Field=detail))
    if breaker is not None:
        breaker.record(False)


def reset_counters() -> None:
    global GUARD_RUNS, GUARD_MISMATCHES, COLUMNAR_ENCODES, WALK_ENCODES
    global REBUILDS, USAGE_READS, USAGE_GUARD_RUNS, USAGE_GUARD_MISMATCHES
    GUARD_RUNS = GUARD_MISMATCHES = 0
    COLUMNAR_ENCODES = WALK_ENCODES = REBUILDS = 0
    USAGE_READS = USAGE_GUARD_RUNS = USAGE_GUARD_MISMATCHES = 0


def gather_index(index: Dict[str, int], keys) -> np.ndarray:
    """``index[key]`` for every key as one int64 array, -1 where the
    key is absent (one C-level pass: no Python frame per key).  An
    indexed node column (structs.NodeColumn) is answered from its
    integers, ``table.rows_in(index)[idx]``: no string handled, and
    ``index`` must then be an append-only row index (NodeTable.rows_in
    keeps the table's rows per index object)."""
    if type(keys) is NodeColumn:
        return keys.table.rows_in(index)[keys.idx]
    return np.fromiter(map(index.get, keys, itertools.repeat(-1)),
                       np.int64, len(keys))


def _strings(keys):
    """``keys`` as strings.  The guards' references read an indexed
    column's strings, never its integers: they are the route a wrong
    table or permutation must not share."""
    return keys.strings() if type(keys) is NodeColumn else keys


def slab_rows(slab, row_of: Dict[str, int]) -> np.ndarray:
    """``row_of`` row of each of the slab's placements, cached on the
    slab (an undeclared attr, like ``_id_idx``, so it stays off the wire
    codec) for as long as the caller keeps reading through the same
    index: a row index is append-only and a slab's node column immutable
    post-insert, so a complete answer never changes.  For readers that
    meet the same slab again and again (the plan applier's guard over
    still-pending slabs, which is why this reads the column's strings);
    one-shot readers call gather_index."""
    cached = getattr(slab, "_rows", None)
    if cached is not None and cached[0] is row_of:
        return cached[1]
    rows = gather_index(row_of, _strings(slab.node_ids))
    if rows.size and rows.min() >= 0:
        slab._rows = (row_of, rows)
    return rows


def add_counts(out: np.ndarray, pos: np.ndarray, vec) -> None:
    """``out[p] += vec`` once per occurrence of ``p`` in ``pos``."""
    if pos.size:
        out += (np.bincount(pos, minlength=len(out))[:, None]
                * np.asarray(vec, dtype=np.int64))


def add_node_counts(out: np.ndarray, pos_of: Dict[str, int], node_ids,
                    vec) -> None:
    """``out[pos_of[nid]] += vec`` once per occurrence of ``nid`` in
    ``node_ids`` (a slab's node column); ids ``pos_of`` does not hold
    are skipped.  The plan-fit guard's reference."""
    pos = gather_index(pos_of, _strings(node_ids))
    add_counts(out, pos[pos >= 0], vec)


class ClusterColumns:
    """Columnar mirror of one store's node table + live-usage matrix.

    One instance per store/snapshot; numpy arrays are SHARED between a
    parent and its snapshots behind copy-on-write flags, codebooks and
    the row index are shared append-only (each view trims by its own
    recorded lengths/cursor).
    """

    __slots__ = (
        "n", "capy", "node_ids", "row_of",
        "cap", "res", "eligible", "dc_code", "class_code",
        "dc_book", "class_book", "dc_len", "class_len",
        "usage", "usage_index",
        "_owned_static", "_owned_elig", "_owned_usage", "_can_append",
        "epoch",
    )

    def __init__(self, capy: int = 256):
        self.n = 0
        self.capy = capy
        self.node_ids: List[str] = []
        self.row_of: Dict[str, int] = {}
        self.cap = np.zeros((capy, RES_DIMS), dtype=np.int64)
        self.res = np.zeros((capy, RES_DIMS), dtype=np.int64)
        self.eligible = np.zeros(capy, dtype=bool)
        self.dc_code = np.full(capy, -1, dtype=np.int32)
        self.class_code = np.full(capy, -1, dtype=np.int32)
        self.dc_book: Dict[str, int] = {}
        self.class_book: Dict[str, int] = {}
        self.dc_len = 0
        self.class_len = 0
        self.usage = np.zeros((capy, RES_DIMS), dtype=np.int64)
        self.usage_index = 0        # allocs-table index the fold reached
        self._owned_static = True
        self._owned_elig = True
        self._owned_usage = True
        self._can_append = True
        self.epoch = EPOCH

    # -- sharing -----------------------------------------------------------

    def share(self) -> "ClusterColumns":
        """O(1) snapshot view: array refs shared, private metadata.  The
        parent loses in-place-write ownership (its next row update or
        usage fold copies first); the view can never append in place."""
        view = ClusterColumns.__new__(ClusterColumns)
        view.n = self.n
        view.capy = self.capy
        view.node_ids = self.node_ids          # append-only, trim by n
        view.row_of = self.row_of              # append-only, check < n
        view.cap = self.cap
        view.res = self.res
        view.eligible = self.eligible
        view.dc_code = self.dc_code
        view.class_code = self.class_code
        # Codebooks are COPIED (they are small — distinct dcs/classes,
        # not nodes): the owner appends to its dicts under the store
        # lock, but the view's codebook READS happen off-lock at encode
        # time, and iterating a dict the owner is growing raises in
        # CPython.  row_of/node_ids stay shared — the view only does
        # single get()/index reads bounded by its cursor, which are
        # GIL-atomic against appends.
        view.dc_book = (dict(self.dc_book)
                        if len(self.dc_book) == self.dc_len else
                        {k: v for k, v in self.dc_book.items()
                         if v < self.dc_len})
        view.class_book = (dict(self.class_book)
                           if len(self.class_book) == self.class_len else
                           {k: v for k, v in self.class_book.items()
                            if v < self.class_len})
        view.dc_len = self.dc_len
        view.class_len = self.class_len
        view.usage = self.usage
        view.usage_index = self.usage_index
        view._owned_static = False
        view._owned_elig = False
        view._owned_usage = False
        view._can_append = False
        view.epoch = self.epoch
        self._owned_static = False
        self._owned_elig = False
        self._owned_usage = False
        return view

    def _own_static(self) -> None:
        if not self._owned_static:
            self.cap = self.cap.copy()
            self.res = self.res.copy()
            self.dc_code = self.dc_code.copy()
            self.class_code = self.class_code.copy()
            self._owned_static = True

    def _own_elig(self) -> None:
        """Eligibility has its own ownership: status/drain flips are the
        common in-place write, and copying one bool column beats paying
        the full static-array copy per (snapshot, flip) pair."""
        if not self._owned_elig:
            self.eligible = self.eligible.copy()
            self._owned_elig = True

    def _own_usage(self) -> None:
        if not self._owned_usage:
            self.usage = self.usage.copy()
            self._owned_usage = True

    def _own_append(self) -> None:
        """A view (snapshot) that appends needs private copies of the
        append-only structures too — the shared ones belong to the
        creator store's future."""
        if not self._can_append:
            self._own_static()
            self._own_elig()
            self._own_usage()
            self.node_ids = list(self.node_ids[:self.n])
            self.row_of = {nid: i for i, nid in enumerate(self.node_ids)}
            self.dc_book = dict(list(self.dc_book.items())[:self.dc_len])
            self.class_book = dict(
                list(self.class_book.items())[:self.class_len])
            self._can_append = True

    def _grow(self, need: int) -> None:
        new_capy = max(need, self.capy * 2, 256)

        def g2(a, fill=0):
            out = np.full((new_capy, RES_DIMS), fill, dtype=a.dtype)
            out[:self.n] = a[:self.n]
            return out

        def g1(a, fill):
            out = np.full(new_capy, fill, dtype=a.dtype)
            out[:self.n] = a[:self.n]
            return out

        self.cap = g2(self.cap)
        self.res = g2(self.res)
        self.usage = g2(self.usage)
        self.eligible = g1(self.eligible, False)
        self.dc_code = g1(self.dc_code, -1)
        self.class_code = g1(self.class_code, -1)
        self.capy = new_capy
        # Fresh private arrays: ownership regained for free.
        self._owned_static = True
        self._owned_elig = True
        self._owned_usage = True

    # -- node write hooks (caller holds the store lock) --------------------

    @staticmethod
    def _vec(r) -> Tuple[int, int, int, int]:
        if r is None:
            return (0, 0, 0, 0)
        return (r.cpu, r.memory_mb, r.disk_mb, r.iops)

    def append_node(self, node) -> int:
        """New node row; returns the row index.  Caller must have folded
        the usage log first (see StateStore.upsert_node) so the backfill
        it performs next cannot double-count pending log entries."""
        self._own_append()
        if self.n >= self.capy:
            self._grow(self.n + 1)
        i = self.n
        self.cap[i] = self._vec(node.resources)
        self.res[i] = self._vec(node.reserved)
        self.eligible[i] = node.ready()
        dc = self.dc_book.setdefault(node.datacenter, self.dc_len)
        if dc == self.dc_len:
            self.dc_len += 1
        cc = self.class_book.setdefault(node.computed_class, self.class_len)
        if cc == self.class_len:
            self.class_len += 1
        self.dc_code[i] = dc
        self.class_code[i] = cc
        self.usage[i] = 0
        self.node_ids.append(node.id)
        self.row_of[node.id] = i
        self.n = i + 1
        return i

    def update_node(self, node) -> bool:
        """In-place row update for an existing node.  Returns False when
        the update could reorder a codebook (datacenter/computed-class
        change) — the caller drops the container and rebuilds."""
        i = self.row_of.get(node.id)
        if i is None or i >= self.n:
            return False
        dc = self.dc_book.get(node.datacenter)
        cc = self.class_book.get(node.computed_class)
        if (dc is None or dc != self.dc_code[i]
                or cc is None or cc != self.class_code[i]):
            return False
        self._own_static()
        self._own_elig()
        self.cap[i] = self._vec(node.resources)
        self.res[i] = self._vec(node.reserved)
        self.eligible[i] = node.ready()
        return True

    def set_eligible(self, node_id: str, eligible: bool) -> None:
        i = self.row_of.get(node_id)
        if i is None or i >= self.n:
            return
        self._own_elig()
        self.eligible[i] = eligible

    def add_usage(self, node_id: str, vec: Tuple[int, int, int, int]) -> None:
        i = self.row_of.get(node_id)
        if i is None or i >= self.n:
            return
        self._own_usage()
        u = self.usage
        u[i, 0] += vec[0]
        u[i, 1] += vec[1]
        u[i, 2] += vec[2]
        u[i, 3] += vec[3]

    # -- usage fold (caller holds the store lock) --------------------------

    def fold_usage(self, store) -> bool:
        """Catch the usage matrix up with the store's alloc writes via
        the bounded usage-delta feed — O(changed allocs).  Returns False
        when the feed can no longer answer (cursor fell below the trim
        floor): the caller rebuilds from a full row walk."""
        snap_index = store.table_index("allocs")
        if snap_index <= self.usage_index:
            return True
        entries = store.alloc_log_since(self.usage_index)
        if entries is None:
            return False
        from ..structs.structs import alloc_usage_vec

        self._own_usage()
        row_of, n, u = self.row_of, self.n, self.usage
        for entry in entries:
            if len(entry) != 2:     # (index, node_id, delta[, net[, ports]])
                self._add_row(u, row_of.get(entry[1]), n, entry[2])
                continue
            slab = entry[1]         # (index, slab): its node column
            vec = alloc_usage_vec(slab.proto)
            if len(slab.node_ids) < ARRAY_MIN_ROWS:
                for nid in slab.node_ids:
                    self._add_row(u, row_of.get(nid), n, vec)
            else:
                # One scatter-add, no per-allocation Python.
                rows = gather_index(row_of, slab.node_ids)
                np.add.at(u, rows[(rows >= 0) & (rows < n)],
                          np.array(vec, dtype=np.int64))
        self.usage_index = snap_index
        return True

    @staticmethod
    def _add_row(u: np.ndarray, i: Optional[int], n: int, vec) -> None:
        if i is not None and i < n:
            u[i, 0] += vec[0]
            u[i, 1] += vec[1]
            u[i, 2] += vec[2]
            u[i, 3] += vec[3]

    def rebuild_usage(self, store) -> None:
        """Full usage rebuild from the store's live alloc rows (feed gap
        or cold build)."""
        from ..structs.structs import alloc_usage_vec

        self._own_usage()
        self.usage[:self.n] = 0
        row_of, n, u = self.row_of, self.n, self.usage
        for nid, row in store.alloc_rows(None):
            if row.terminal_status():
                continue
            i = row_of.get(nid)
            if i is None or i >= n:
                continue
            c, m, d, io = alloc_usage_vec(row)
            u[i, 0] += c
            u[i, 1] += m
            u[i, 2] += d
            u[i, 3] += io
        self.usage_index = store.table_index("allocs")

    # -- codebook views ----------------------------------------------------

    def dc_codebook(self) -> Dict[str, int]:
        if len(self.dc_book) == self.dc_len:
            return dict(self.dc_book)
        out: Dict[str, int] = {}
        for k, v in self.dc_book.items():
            if v >= self.dc_len:
                break
            out[k] = v
        return out

    def class_codebook(self) -> Dict[str, int]:
        if len(self.class_book) == self.class_len:
            return dict(self.class_book)
        out: Dict[str, int] = {}
        for k, v in self.class_book.items():
            if v >= self.class_len:
                break
            out[k] = v
        return out

    # -- construction ------------------------------------------------------

    @classmethod
    def build(cls, store) -> "ClusterColumns":
        """Cold build from the store's tables (caller holds the lock)."""
        global REBUILDS
        REBUILDS += 1
        nodes = list(store.nodes_table.values())
        cols = cls(capy=max(256, len(nodes)))
        for node in nodes:
            cols.append_node(node)
        cols.rebuild_usage(store)
        return cols


# ---------------------------------------------------------------------------
# Binary array framing — [u16 dtype-str len][dtype str][u8 ndim]
# [u64 dim]*ndim [u64 payload len][payload bytes] — the length-prefixed
# dtype+shape+bytes format the FSM snapshot's column sections use.
# ---------------------------------------------------------------------------

_U16 = struct.Struct("<H")
_U8 = struct.Struct("<B")
_U64 = struct.Struct("<Q")


def pack_array(a: np.ndarray) -> bytes:
    a = np.ascontiguousarray(a)
    dt = a.dtype.str.encode("ascii")
    parts = [_U16.pack(len(dt)), dt, _U8.pack(a.ndim)]
    for d in a.shape:
        parts.append(_U64.pack(d))
    payload = a.tobytes()
    parts.append(_U64.pack(len(payload)))
    parts.append(payload)
    return b"".join(parts)


def unpack_array(buf: memoryview, off: int) -> Tuple[np.ndarray, int]:
    (dtl,) = _U16.unpack_from(buf, off)
    off += 2
    dt = np.dtype(bytes(buf[off:off + dtl]).decode("ascii"))
    off += dtl
    (ndim,) = _U8.unpack_from(buf, off)
    off += 1
    shape = []
    for _ in range(ndim):
        (d,) = _U64.unpack_from(buf, off)
        shape.append(d)
        off += 8
    (plen,) = _U64.unpack_from(buf, off)
    off += 8
    a = np.frombuffer(buf[off:off + plen], dtype=dt).reshape(shape).copy()
    return a, off + plen


def pack_columns(cols: ClusterColumns) -> bytes:
    """Serialize the numeric columns (node order implied by the nodes
    section) for the FSM snapshot's binary column section."""
    n = cols.n
    parts = [
        pack_array(cols.cap[:n]),
        pack_array(cols.res[:n]),
        pack_array(cols.eligible[:n]),
        pack_array(cols.dc_code[:n]),
        pack_array(cols.class_code[:n]),
        pack_array(cols.usage[:n]),
    ]
    return b"".join(parts)


def unpack_columns(blob: bytes, node_ids: List[str],
                   dc_names: List[str], class_names: List[str],
                   usage_index: int) -> ClusterColumns:
    buf = memoryview(blob)
    off = 0
    cap, off = unpack_array(buf, off)
    res, off = unpack_array(buf, off)
    eligible, off = unpack_array(buf, off)
    dc_code, off = unpack_array(buf, off)
    class_code, off = unpack_array(buf, off)
    usage, off = unpack_array(buf, off)
    n = len(node_ids)
    cols = ClusterColumns(capy=max(256, n))
    cols.n = n
    cols.cap[:n] = cap
    cols.res[:n] = res
    cols.eligible[:n] = eligible
    cols.dc_code[:n] = dc_code
    cols.class_code[:n] = class_code
    cols.usage[:n] = usage
    cols.node_ids = list(node_ids)
    cols.row_of = {nid: i for i, nid in enumerate(node_ids)}
    cols.dc_book = {name: i for i, name in enumerate(dc_names)}
    cols.class_book = {name: i for i, name in enumerate(class_names)}
    cols.dc_len = len(dc_names)
    cols.class_len = len(class_names)
    cols.usage_index = usage_index
    return cols
