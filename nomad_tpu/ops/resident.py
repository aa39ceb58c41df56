"""Device-resident cluster node-state cache (PR 5 tentpole).

The batch scheduler used to rebuild per-node alloc USAGE from a full
state-store walk every ``schedule_batch`` — O(cluster) host work per
batch even when only a handful of allocs changed since the last one.
This module keeps the usage matrix RESIDENT between batches, keyed by
the same static-cluster cache key batch_sched already maintains
(store lineage + nodes-table raft index + constraint vocabulary), and
catches it up with the state store's usage-delta feed
(``StateStore.alloc_log_since``, folded as arrays: one index gather and
one scatter-add per batch, no Python per allocation) — O(changed allocs)
per batch, the Megatron/Pathways persistent-device-state trick applied
to the scheduler's cluster mirror.

Correctness machinery:

- **Staleness fence**: a scheduler running against a snapshot OLDER
  than the resident state (its allocs index is behind the cached one —
  e.g. a replayed eval or a harness snapshot) full re-encodes from its
  own snapshot and leaves the resident state untouched.
- **Feed gap**: when ``alloc_log_since`` cannot answer (the cached index
  fell off the bounded log, or a restore reset the feed) the cache is
  rebuilt from a full walk and the event stream gets a
  ``NodeStateDelta`` summary so operators see residency churn.
- **Differential guard**: every ``NOMAD_TPU_RESIDENT_GUARD_EVERY``
  delta hits (default 64) the full walk runs anyway and must match the
  resident matrix bit-for-bit.  A mismatch feeds the PR 2 circuit
  breaker (``record(False)``), invalidates the cache, publishes the
  mismatch on the event stream, and the batch proceeds on the fresh
  full encode — corruption degrades, never mis-places.

Scope: usage rows (capacity/attrs/eligibility invalidate via the
nodes-table index in the cache key) and, once a batch with network asks
has asked for it (``acquire(with_net=True)``), what the live allocations'
networks hold on each node: Mbit and ports in the dynamic range
(``NET_DIMS``, ``structs.alloc_net_vec``), folded from the same feed one
delta per allocation write, with a device twin of its own.  A fleet that
never sees a network ask never builds it.  Beside it, one column per
static port value a batch has asked for (``acquire(ports=...)``): how
many of each node's live allocations hold that value, as any port of
theirs.  A column is built by one walk the batch that first asks for it,
then folded from the feed's port values (``structs.alloc_net_held``; a
write's entry carries them when it takes or frees a port); the guard
holds every column to the walk too.  A fleet that never asks for a
static port builds no column and folds nothing more.  Dynamic port
values are not mirrored: the host picks them at finalize.

Env knobs:

- ``NOMAD_TPU_RESIDENT``              — 0 disables residency (full
  re-encode every batch: the reference the differential guard
  compares against)
- ``NOMAD_TPU_RESIDENT_GUARD_EVERY``  — differential-guard cadence in
  delta hits (0 disables the guard)
- ``NOMAD_TPU_ALLOC_LOG_CAP``         — state-store feed bound (see
  state/state_store.py)

Fault point: ``ops.resident_state`` (action ``corrupt``) perturbs one
resident usage row after a delta apply — the chaos twin of device/host
mirror drift, caught by the differential guard.
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import fault
from ..utils import tracing

logger = logging.getLogger("nomad_tpu.ops.resident")

RES_DIMS = 4
# Per node, over its live allocations: bandwidth in use (Mbit) and ports
# held in the dynamic range (structs.alloc_net_vec); the node's own
# reservation is in the static tensors' baseline.
NET_DIMS = 2


def enabled() -> bool:
    from ..utils import knobs

    return knobs.get_bool("NOMAD_TPU_RESIDENT")


def device_mirror_enabled() -> bool:
    """NOMAD_TPU_RESIDENT_DEVICE (default ON): keep a DEVICE twin of the
    usage mirror, caught up in place by donated scatter-adds and passed
    to the fused kernel as a donated argument — the usage matrix never
    re-materializes and never crosses the link after install (ISSUE 13:
    the arxiv 2603.09555 O(1)-state-carry discipline applied to the
    resident cache).  On a node mesh (ISSUE 14) the twin is SHARDED —
    one donated [n_local, 4] buffer per shard under the mesh's
    NamedSharding, caught up by shard-routed donated scatter-adds — so
    the replicated per-batch u_rows/u_vals upload disappears from the
    mesh steady state too.  0 keeps the sparse-delta upload path."""
    from ..utils import knobs

    return knobs.get_bool("NOMAD_TPU_RESIDENT_DEVICE")


def guard_every() -> int:
    from ..utils import knobs

    return knobs.get_int("NOMAD_TPU_RESIDENT_GUARD_EVERY")


_DELTA_APPLY = None
# Per-mesh donated shard-routed delta-apply programs, keyed by the mesh
# device-id tuple (tiny LRU: a process rarely schedules over more than a
# couple of meshes, but a long-lived multi-region server must not grow
# compiled entries without bound — evictions feed the
# batch.program_cache_evictions gauge).
_DELTA_APPLY_MESH = None


def _delta_apply_fn():
    """The donated scatter-add that keeps the device mirror caught up:
    jitted once, donate_argnums=(0,) aliases input to output so the
    apply is IN PLACE on device (measured 0.014ms vs 96ms for the
    copying form on a 10M-row mirror).  Delta rows are pow2-bucketed by
    the caller so the jit cache holds a fixed handful of shapes."""
    global _DELTA_APPLY
    if _DELTA_APPLY is None:
        import functools

        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, donate_argnums=(0,))
        def _apply(used_dev, rows, vals):
            valid = rows >= 0
            idx = jnp.where(valid, rows, jnp.int32(used_dev.shape[0]))
            return used_dev.at[idx].add(vals, mode="drop")

        _DELTA_APPLY = _apply
    return _DELTA_APPLY


def _delta_apply_mesh_fn(mesh):
    """The SHARDED twin of the donated scatter-add (ISSUE 14): the
    mirror is one [n_pad, 4] array sharded over the mesh's node axis —
    physically one donated [n_local, 4] buffer per device — and the
    host routes the global delta stream into per-shard
    ``(local_row, vals)`` runs (encode.route_shard_deltas, O(changed))
    whose leading axis shards the same way, so each device applies ONLY
    the rows it owns with no cross-shard traffic and no re-layout.
    donate_argnums=(0,) aliases every shard's buffer in place, exactly
    the single-chip loan discipline per shard."""
    global _DELTA_APPLY_MESH
    from ..utils.lru import LRU

    if _DELTA_APPLY_MESH is None:
        _DELTA_APPLY_MESH = LRU(8)
    key = tuple(d.id for d in mesh.devices.flat)
    fn = _DELTA_APPLY_MESH.get(key)
    if fn is None:
        import functools

        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..parallel import sharded as shmod

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(shmod.NODE_AXIS), P(shmod.NODE_AXIS),
                      P(shmod.NODE_AXIS)),
            out_specs=P(shmod.NODE_AXIS))
        def _apply_shard(used_l, rows_l, vals_l):
            r = rows_l.reshape(-1)
            v = vals_l.reshape(-1, RES_DIMS)
            valid = r >= 0
            idx = jnp.where(valid, r, jnp.int32(used_l.shape[0]))
            return used_l.at[idx].add(v, mode="drop")

        fn = jax.jit(_apply_shard, donate_argnums=(0,))
        _DELTA_APPLY_MESH.put(key, fn)
    return fn


class ResidentState:
    """One cached (static key → usage matrix) residency slot."""

    __slots__ = ("key", "used", "alloc_index", "touched", "hits",
                 "delta_rows", "since_guard", "used_dev", "dev_mesh",
                 "net", "net_dev", "ports")

    def __init__(self, key: Tuple, used: np.ndarray, alloc_index: int,
                 touched: set):
        self.key = key
        self.used = used                # [n_pad, 4] int64, owned by us
        self.alloc_index = alloc_index  # allocs-table raft index mirrored
        self.touched = touched          # rows that may differ from base
        self.hits = 0
        self.delta_rows = 0
        self.since_guard = 0
        # Device twin of ``used`` (int32): installed lazily by
        # take_device_used, caught up in place by donated scatter-adds,
        # LOANED to the kernel (donated) and handed back via
        # give_device_used — None while out on loan or dropped.
        self.used_dev = None
        # Placement of the device twin: None for the single-chip layout,
        # the jax Mesh when the buffer is node-sharded (one donated
        # [n_local, 4] buffer per shard).  A taker asking for a
        # different placement drops the handle and reinstalls — a
        # single-chip mirror must never flow into the sharded kernel or
        # vice versa.
        self.dev_mesh = None
        # The network mirror ([n_pad, NET_DIMS] int64) at the same
        # alloc_index, None until a network batch asks for it; its
        # device twin (int32, single-chip) is loaned like ``used_dev``.
        self.net = None
        self.net_dev = None
        # {static port value: int32 [n_pad] holders per node} at the same
        # alloc_index, one column per value a batch has asked for.
        self.ports: Dict[int, np.ndarray] = {}


# Single residency slot (the steady-state workload schedules one cluster
# shape; a key change — node churn, new constraint vocabulary — replaces
# it wholesale), guarded by a lock: BatchWorker pipelining keeps batches
# ordered, but tests/harnesses may race schedulers.
_STATE: Optional[ResidentState] = None
_LOCK = threading.Lock()

# Module counters (telemetry bridge + tests).
HITS = 0
FULL_REENCODES = 0
STALENESS_FALLBACKS = 0
GUARD_RUNS = 0
GUARD_MISMATCHES = 0
# Device-mirror counters: donated delta applies, installs (host→device
# uploads — should stay ~1 per mirror lifetime), and device-vs-host
# guard mismatches (drift in the donated buffer itself).
DEV_APPLIES = 0
DEV_INSTALLS = 0
DEV_GUARD_MISMATCHES = 0
# Host→device bytes the mirror machinery moved (installs + routed delta
# uploads): batch_sched samples this around each dispatch so BatchStats
# h2d_bytes can show the transfer the donated protocol removes from the
# steady state.
DEV_H2D_BYTES = 0
# Quantization round-trip guard (PR 6): every quantized static upload is
# dequantized host-side and bit-compared against the exact rows before
# the buffer ships — the mirror-drift guard extended to the narrow-dtype
# wire representation.  A mismatch feeds the breaker and disables
# quantization for that batch (the int32 path is always correct).
QUANT_CHECKS = 0
QUANT_MISMATCHES = 0

# Last plan-apply index noted by the plan applier (server/plan_apply.py
# index plumbing): rides the NodeStateDelta event payloads so operators
# can line residency churn up against plan traffic.
LAST_PLAN_INDEX = 0


def note_plan_applied(index: int) -> None:
    """Plan-applier hook: record the newest apply index.  The resident
    fence itself keys off the snapshot's allocs-table index (the delta
    feed is raft-index addressed); this breadcrumb is observability."""
    global LAST_PLAN_INDEX
    if index > LAST_PLAN_INDEX:
        LAST_PLAN_INDEX = index


def invalidate() -> None:
    global _STATE
    with _LOCK:
        _STATE = None


def reset_counters() -> None:
    """Test helper: zero the module counters and drop the cache."""
    global HITS, FULL_REENCODES, STALENESS_FALLBACKS, GUARD_RUNS
    global GUARD_MISMATCHES, QUANT_CHECKS, QUANT_MISMATCHES
    global DEV_APPLIES, DEV_INSTALLS, DEV_GUARD_MISMATCHES, DEV_H2D_BYTES
    invalidate()
    HITS = FULL_REENCODES = STALENESS_FALLBACKS = 0
    GUARD_RUNS = GUARD_MISMATCHES = 0
    QUANT_CHECKS = QUANT_MISMATCHES = 0
    DEV_APPLIES = DEV_INSTALLS = DEV_GUARD_MISMATCHES = 0
    DEV_H2D_BYTES = 0


def _mesh_key(mesh):
    """Placement identity for the device mirror: None (single-chip) or
    the mesh's device-id tuple — two separately constructed meshes over
    the same devices are the same placement."""
    return (None if mesh is None
            else tuple(d.id for d in mesh.devices.flat))


def take_device_used(key: Tuple, snap_index: int, host_used: np.ndarray,
                     mesh=None, what: str = "used"):
    """Loan the device usage mirror out for donation into the kernel.

    Returns the [n_pad, 4] int32 device array — installed from
    ``host_used`` on first use — or None when the resident slot does
    not exactly match ``(key, snap_index)`` (the caller then ships
    sparse deltas as before).  The slot's handle is cleared while the
    loan is out: donation consumes the buffer, so an exception between
    take and give must leave the slot empty (rebuilt from host on the
    next take), never holding a dead handle.

    ``mesh``: when set, the mirror installs (and must already be)
    node-sharded over it — physically one donated [n_local, 4] buffer
    per shard under ``NamedSharding(mesh, P(NODE_AXIS))``.  A held
    handle whose placement differs from the request is dropped and
    reinstalled: a single-chip buffer must never flow into the sharded
    kernel or vice versa.

    ``what="net"`` loans the network mirror's twin the same way
    (single-chip only; ``host_used`` is then the [n_pad, NET_DIMS]
    network mirror)."""
    global DEV_INSTALLS, DEV_H2D_BYTES
    if not device_mirror_enabled():
        return None
    attr = what + "_dev"
    with _LOCK:
        st = _STATE
        if (st is None or st.key != key
                or st.alloc_index != snap_index):
            return None
        dev = getattr(st, attr)
        setattr(st, attr, None)
        if what == "used":
            if (dev is not None
                    and _mesh_key(st.dev_mesh) != _mesh_key(mesh)):
                dev = None      # placement mismatch: reinstall below
            st.dev_mesh = mesh
    if dev is None:
        import jax

        from .kernels import note_signature

        src = np.ascontiguousarray(host_used, dtype=np.int32)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel import sharded as shmod

            dev = jax.device_put(
                src, NamedSharding(mesh, P(shmod.NODE_AXIS)))
            shards = mesh.devices.size
        else:
            dev = jax.device_put(src)
            shards = 0
        note_signature("resident_install", (host_used.shape, shards))
        DEV_INSTALLS += 1
        DEV_H2D_BYTES += src.nbytes
        tracing.event("resident.device_install", rows=host_used.shape[0],
                      shards=shards)
    return dev


def give_device_used(key: Tuple, snap_index: int, dev,
                     what: str = "used") -> None:
    """Hand the loaned (kernel-aliased) device mirror back.  Dropped
    when the slot moved on while the loan was out — the mirror is then
    reinstalled from host at the next take."""
    attr = what + "_dev"
    with _LOCK:
        st = _STATE
        if (st is not None and st.key == key and getattr(st, attr) is None
                and st.alloc_index == snap_index):
            setattr(st, attr, dev)


def check_quant_roundtrip(exact: np.ndarray, quantized: np.ndarray,
                          scale: np.ndarray, breaker=None,
                          what: str = "rows") -> bool:
    """Bit-exact round-trip bound for quantized resource rows: the
    dequantized matrix must equal the exact one (the quantizer only
    quantizes when it can be exact, so any difference is corruption or a
    codebook bug).  Mismatch ⇒ breaker feed + event, caller falls back
    to the int32 wire path.  Cost: one [n, 4] integer compare."""
    from .encode import dequantize_rows

    global QUANT_CHECKS, QUANT_MISMATCHES
    QUANT_CHECKS += 1
    back = dequantize_rows(quantized, scale)
    if np.array_equal(back, np.asarray(exact, dtype=np.int64)):
        return True
    QUANT_MISMATCHES += 1
    bad = int((back != exact).any(axis=-1).sum())
    logger.error(
        "quantized %s failed the round-trip bound on %d rows; shipping "
        "exact int32 rows and feeding the breaker", what, bad)
    tracing.event("resident.quant_mismatch", rows=bad, what=what)
    _publish("quant_mismatch", Rows=bad, What=what)
    if breaker is not None:
        breaker.record(False)
    return False


def _apply_device_deltas(used_dev, rows, vals, mesh=None):
    """Catch the device mirror up with one donated scatter-add of the
    usage rows ``(rows int[k], vals int[k, 4])`` (no-op when the mirror
    is absent or nothing changed).  Rows are bucketed to powers of two
    so the jit cache stays a fixed handful of shapes.

    With ``mesh`` the mirror is node-sharded: the global delta stream is
    routed into per-shard (local_row, vals) runs host-side
    (encode.route_shard_deltas — one numpy pass, O(changed)) and applied
    by the per-shard donated scatter-add, so every shard touches only
    the rows it owns."""
    global DEV_APPLIES, DEV_H2D_BYTES
    if used_dev is None or not len(rows):
        return used_dev
    from .encode import pow2_bucket, route_shard_deltas
    from .kernels import program_call

    try:
        if mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel import sharded as shmod

            d = mesh.devices.size
            n_l = used_dev.shape[0] // d
            rows, vals = route_shard_deltas(rows, vals, d, n_l)
            DEV_APPLIES += 1
            DEV_H2D_BYTES += rows.nbytes + vals.nbytes
            spec = NamedSharding(mesh, P(shmod.NODE_AXIS))
            with program_call("resident_delta_mesh",
                              (used_dev.shape, rows.shape[1], d)):
                return _delta_apply_mesh_fn(mesh)(
                    used_dev, jax.device_put(rows, spec),
                    jax.device_put(vals, spec))
        k = len(rows)
        k_b = pow2_bucket(k)
        rows_b = np.full(k_b, -1, dtype=np.int32)
        rows_b[:k] = rows
        vals_b = np.zeros((k_b, vals.shape[1]), dtype=np.int32)
        vals_b[:k] = vals
        DEV_APPLIES += 1
        DEV_H2D_BYTES += rows_b.nbytes + vals_b.nbytes
        with program_call("resident_delta", (used_dev.shape, k_b)):
            return _delta_apply_fn()(used_dev, rows_b, vals_b)
    except Exception:
        # The donated input is consumed even on failure — a dead handle
        # must not linger in the slot (the next take reinstalls from
        # host).
        logger.exception("donated delta apply failed; dropping the "
                         "device mirror")
        return None


def _feed_rows(entries, node_index: Dict[str, int]
               ) -> Tuple[np.ndarray, np.ndarray]:
    """The usage-delta feed's raw entries (StateStore.alloc_log_since)
    as ``(rows int64[k], vals int64[k, 4])``: one usage row per
    allocation write, in feed order; writes on nodes the fleet does not
    hold are dropped.  Each entry's node ids go through one index
    gather (an indexed column's is ``perm[idx]``, no string handled;
    runs of single-row entries share one) and a slab's usage vector is
    repeated over its node column — no Python per allocation, and at a
    stream batch's one or two ten-row slabs no slower than a lookup per
    row (PERF.md PR 30)."""
    from ..state.columnar import gather_index
    from ..structs.structs import alloc_usage_vec

    parts: List[np.ndarray] = []
    singles: List[str] = []
    vecs: List[Tuple] = []
    counts: List[int] = []
    for entry in entries:
        if len(entry) != 2:     # (index, node_id, delta[, net[, ports]])
            singles.append(entry[1])
            vecs.append(entry[2])
            counts.append(1)
            continue
        if singles:
            parts.append(gather_index(node_index, singles))
            singles = []
        slab = entry[1]         # (index, slab): its node column
        parts.append(gather_index(node_index, slab.node_ids))
        vecs.append(alloc_usage_vec(slab.proto))
        counts.append(len(slab.node_ids))
    if singles:
        parts.append(gather_index(node_index, singles))
    rows = (np.concatenate(parts) if parts
            else np.zeros(0, dtype=np.int64))
    vals = np.repeat(np.array(vecs, dtype=np.int64).reshape(-1, RES_DIMS),
                     counts, axis=0)
    known = rows >= 0
    if not known.all():
        rows, vals = rows[known], vals[known]
    return rows, vals


def _feed_net_rows(entries, node_index: Dict[str, int]
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """The feed's network deltas as ``(rows int64[k], vals int64[k,
    NET_DIMS])``: one row per allocation write that changed what a
    node's networks hold (a single row's fourth element; a network
    slab's rows, ``AllocSlab.row_net_usage``, each like a single row; a
    slab whose prototype holds any, over its node column), in feed
    order; writes on nodes the fleet does not hold are dropped."""
    from ..state.columnar import gather_index
    from ..structs.structs import alloc_net_vec

    nids: List[str] = []
    vecs: List[Tuple] = []
    parts: List[np.ndarray] = []
    for entry in entries:
        if len(entry) >= 4:
            nids.append(entry[1])
            vecs.append(entry[3])
        elif len(entry) == 2:
            slab = entry[1]
            if slab.ips:
                nids.extend(slab.node_ids)
                vecs.extend(slab.row_net_usage())
                continue
            vec = alloc_net_vec(slab.proto)
            if vec != (0, 0):
                parts.append(gather_index(node_index, slab.node_ids))
                parts.append(np.array([vec], dtype=np.int64).repeat(
                    len(slab.node_ids), axis=0))
    rows = np.concatenate([gather_index(node_index, nids)] + parts[::2])
    vals = np.concatenate(
        [np.array(vecs, dtype=np.int64).reshape(-1, NET_DIMS)]
        + parts[1::2])
    known = rows >= 0
    if not known.all():
        rows, vals = rows[known], vals[known]
    return rows, vals


def _feed_port_rows(entries, node_index: Dict[str, int]
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """The feed's port values as ``(rows int64[k], values int64[k])``:
    one per port an allocation write took (``v``) or freed (``-v``), a
    single row's fifth element, a network slab's rows' own
    (``AllocSlab.row_ports``, each like a single row) or a slab
    prototype's over its node column, in feed order; writes on nodes the
    fleet does not hold are dropped."""
    from ..state.columnar import gather_index
    from ..structs.structs import alloc_net_held

    nids: List[str] = []
    values: List[int] = []
    parts: List[np.ndarray] = []
    for entry in entries:
        if len(entry) == 5:
            nids.extend([entry[1]] * len(entry[4]))
            values.extend(entry[4])
        elif len(entry) == 2:
            slab = entry[1]
            if slab.ips:
                for nid, ports in zip(slab.node_ids, slab.row_ports()):
                    nids.extend([nid] * len(ports))
                    values.extend(ports)
                continue
            ports = alloc_net_held(slab.proto)[1]
            if ports:
                idx = gather_index(node_index, slab.node_ids)
                parts.append(np.repeat(idx, len(ports)))
                parts.append(np.tile(np.asarray(ports, dtype=np.int64),
                                     len(idx)))
    rows = np.concatenate([gather_index(node_index, nids)] + parts[::2])
    vals = np.concatenate([np.asarray(values, dtype=np.int64)]
                          + parts[1::2])
    known = rows >= 0
    if not known.all():
        rows, vals = rows[known], vals[known]
    return rows, vals


def _fold_ports(columns: Dict[int, np.ndarray], entries,
                node_index: Dict[str, int]) -> None:
    """Fold the feed's port values into the columns they are of."""
    rows, vals = _feed_port_rows(entries, node_index)
    if not len(rows):
        return
    for port, col in columns.items():
        np.add.at(col, rows[vals == port], 1)
        np.subtract.at(col, rows[vals == -port], 1)


def _port_held(columns: Dict[int, np.ndarray], ports, n_pad: int
               ) -> np.ndarray:
    """[n_pad, len(ports)] bool: where some live allocation holds each
    of ``ports``, in that order."""
    out = np.zeros((n_pad, len(ports)), dtype=bool)
    for j, port in enumerate(ports):
        out[:, j] = columns[port] > 0
    return out


def _publish(etype_reason: str, **payload) -> None:
    """NodeStateDelta summary on the PR 4 event stream (one branch while
    disarmed, via the fault-module indirection that avoids importing the
    server package)."""
    fault.note_event_stream(
        "Node", "NodeStateDelta", etype_reason,
        dict(payload, Reason=etype_reason, PlanIndex=LAST_PLAN_INDEX))


def _full_usage(base, rows_fn) -> Tuple[np.ndarray, set]:
    """The reference rebuild: base reserved-only usage + every live
    alloc row from a full state walk, on the canonical
    structs.alloc_usage_vec basis (the same one the delta feed logs).
    Returns (used int64, touched)."""
    from ..structs.structs import alloc_usage_vec

    used = np.asarray(base.used, dtype=np.int64).copy()
    touched: set = set()
    node_index = base._node_index  # type: ignore[attr-defined]
    for nid, rows in rows_fn().items():
        i = node_index.get(nid)
        if i is None:
            continue
        for row in rows:
            c, m, d, io = alloc_usage_vec(row)
            used[i, 0] += c
            used[i, 1] += m
            used[i, 2] += d
            used[i, 3] += io
        touched.add(i)
    return used, touched


def _full_net(base, rows_fn, ports=()
              ) -> Tuple[np.ndarray, Dict[int, np.ndarray]]:
    """The reference rebuild of the network mirror from a full state
    walk, on ops/encode.apply_alloc_usage's basis and independent of the
    feed: per node, the Mbit of its live allocs' first task networks,
    and the ports in the dynamic range they hold, as a set, less those
    the node itself reserves (the static baseline's); and, in the same
    pass, a column for each of ``ports``: per node, how many of those
    networks' ports hold the value."""
    from ..structs.network import MAX_DYNAMIC_PORT, MIN_DYNAMIC_PORT

    net = np.zeros((base.n_pad, NET_DIMS), dtype=np.int64)
    columns = {p: np.zeros(base.n_pad, dtype=np.int32) for p in ports}
    node_index = base._node_index  # type: ignore[attr-defined]
    nodes = base._nodes            # type: ignore[attr-defined]
    for nid, rows in rows_fn().items():
        i = node_index.get(nid)
        if i is None:
            continue
        mbits, held = 0, set()
        for row in rows:
            for _, _, m, values in row.held_networks():
                mbits += m
                for value in values:
                    if MIN_DYNAMIC_PORT <= value < MAX_DYNAMIC_PORT:
                        held.add(value)
                    col = columns.get(value)
                    if col is not None:
                        col[i] += 1
        if held and nodes[i].reserved is not None:
            for nr in nodes[i].reserved.networks or []:
                held.difference_update(
                    p.value for p in nr.reserved_ports + nr.dynamic_ports)
        net[i] = (mbits, len(held))
    return net, columns


def _usage_source(base, rows_fn, usage_fn) -> Tuple[np.ndarray, set]:
    """Full live-usage rows for a cold build / fence / feed-gap rebuild:
    the columnar mirror slice when the caller supplied one (O(changed)
    via the store's delta feed, ISSUE 9), the object walk otherwise.
    The DIFFERENTIAL GUARD below never uses ``usage_fn`` — it must stay
    an independent accumulation path (the mirror and this cache both
    ride the same delta log; the guard's job is to catch that log
    lying, so it re-derives from the alloc rows themselves)."""
    if usage_fn is not None:
        out = usage_fn()
        if out is not None:
            used, touched = out
            return used, set(touched)
    return _full_usage(base, rows_fn)


def acquire(state, cache_key: Tuple, base, rows_fn,
            breaker=None, shards: int = 0, usage_fn=None,
            with_net: bool = False, ports=()
            ) -> Tuple[np.ndarray, List[int], Dict]:
    """Produce the live usage matrix for this batch.

    ``state`` is the scheduler's snapshot, ``cache_key`` the residency
    key ``(store_uid, nodes_table_index, n_pad)`` — the usage matrix
    depends only on the node set (and pad geometry), NOT the batch's
    constraint vocabulary, so the mirror survives vocabulary changes
    that re-key the static tensor cache — ``base`` the finalized static
    ClusterTensors, ``rows_fn`` a callable returning {node_id: [live
    alloc rows]} for the full-walk fallback.

    ``shards``: node-mesh size when the scheduler runs the sharded
    path; the differential guard then bit-compares PER SHARD SLICE and
    reports the offending shard ids alongside the breaker feed (the
    mirror itself stays one host matrix — on device each shard holds
    only its slice, so attribution is what operators need to map a
    mismatch to hardware).

    ``with_net``: the batch has network asks; the network mirror is
    built (one walk) if the slot has none yet and handed back as
    ``info["net"]`` ([n_pad, NET_DIMS] int64).  Once built it is folded
    with every batch's feed, and the guard holds it to ``_full_net``
    from the same walk as the usage.  ``ports``: the static port values
    the batch asks for (with ``with_net``); ``info["port_held"]`` is
    then ``[n_pad, len(ports)]`` bool, set where a live allocation
    holds the value, read from the mirror's columns (a column the slot
    lacks is built first, all of a batch's in one walk).

    Returns ``(used int64 [n_pad, 4], touched_rows sorted list, info)``
    where info carries the BatchStats counters:
    ``resident_hit``/``delta_rows``/``full_reencode``/``fence``/
    ``guard_ran``/``guard_mismatch`` (+ ``guard_bad_shards`` on a
    sharded mismatch), ``net_walks`` (walks the network mirror
    itself needed: a build, a fence, a rebuild, a port column's build)
    and ``port_columns`` (asked ports served from the mirror's columns).
    """
    global _STATE, HITS, FULL_REENCODES, STALENESS_FALLBACKS
    global GUARD_RUNS, GUARD_MISMATCHES

    info = {"resident_hit": False, "delta_rows": 0, "full_reencode": False,
            "fence": False, "guard_ran": False, "guard_mismatch": False,
            "delta_apply_s": 0.0, "net_walks": 0, "port_columns": 0}
    snap_index = state.table_index("allocs")
    walked: List[Dict] = []
    ports = sorted(ports) if with_net else []

    def walk():
        # One state walk a batch at most, shared by the usage and the
        # network rebuilds and by the guard.
        if not walked:
            walked.append(rows_fn())
        return walked[0]

    def net_walk(new_ports=()) -> Tuple[np.ndarray, Dict[int, np.ndarray]]:
        info["net_walks"] += 1
        return _full_net(base, walk, new_ports)

    def served(net, columns, from_mirror):
        info["net"] = net
        info["port_held"] = _port_held(columns, ports, base.n_pad)
        if from_mirror:
            info["port_columns"] = len(ports)

    def fenced(used, touched):
        if with_net:
            served(*net_walk(ports), False)
        return used, sorted(touched), info

    with _LOCK:
        st = _STATE
        if (st is not None and st.key != cache_key
                and st.key[0] == cache_key[0]
                and cache_key[1] < st.key[1]):
            # Key mismatch because the SNAPSHOT's nodes-table index is
            # older than the mirror's (a replayed eval against a
            # pre-node-churn world): same staleness fence as below — a
            # one-off full encode that must NOT clobber the newer mirror.
            STALENESS_FALLBACKS += 1
            info["fence"] = True
            info["full_reencode"] = True
            used, touched = _usage_source(base, walk, usage_fn)
            tracing.event("resident.fence", snap_nodes_index=cache_key[1],
                          cached_nodes_index=st.key[1])
            _publish("staleness_fence", SnapshotNodesIndex=cache_key[1],
                     CachedNodesIndex=st.key[1])
            return fenced(used, touched)
        if st is not None and st.key == cache_key:
            if snap_index < st.alloc_index:
                # Staleness fence: this snapshot predates the resident
                # mirror — serve it a one-off full encode and leave the
                # cache at its newer position.
                STALENESS_FALLBACKS += 1
                info["fence"] = True
                info["full_reencode"] = True
                used, touched = _usage_source(base, walk, usage_fn)
                tracing.event("resident.fence", snap_index=snap_index,
                              cached_index=st.alloc_index)
                _publish("staleness_fence", SnapshotIndex=snap_index,
                         CachedIndex=st.alloc_index)
                return fenced(used, touched)

            entries = (state.alloc_log_since(st.alloc_index)
                       if snap_index > st.alloc_index else [])
            if entries is not None:
                used = st.used
                node_index = base._node_index  # type: ignore[attr-defined]
                rows, vals = _feed_rows(entries, node_index)
                np.add.at(used, rows, vals)
                st.touched.update(np.unique(rows).tolist())
                track_dev = st.used_dev is not None
                if st.net is not None:
                    net_rows, net_vals = _feed_net_rows(entries, node_index)
                    np.add.at(st.net, net_rows, net_vals)
                if st.ports:
                    _fold_ports(st.ports, entries, node_index)
                st.alloc_index = snap_index
                st.hits += 1
                st.delta_rows += len(rows)
                st.since_guard += 1
                HITS += 1
                info["resident_hit"] = True
                info["delta_rows"] = len(rows)

                act = fault.faultpoint("ops.resident_state")
                if act is not None and act.kind == "corrupt":
                    row = (sorted(st.touched)[act.rng.randrange(
                        len(st.touched))] if st.touched
                        else act.rng.randrange(used.shape[0]))
                    dim = act.rng.randrange(RES_DIMS)
                    bump = 1 + act.rng.randrange(1000)
                    used[row, dim] += bump
                    st.touched.add(row)
                    if track_dev:
                        # The chaos twin of mirror drift perturbs the
                        # DEVICE copy identically, so host and device
                        # stay consistent with each other and the
                        # host-vs-walk guard below catches both.
                        vec = np.zeros((1, RES_DIMS), dtype=np.int64)
                        vec[0, dim] = bump
                        rows = np.append(rows, row)
                        vals = np.concatenate([vals, vec])

                if track_dev or st.net_dev is not None:
                    import time as _time

                    t_da = _time.monotonic()
                    if track_dev:
                        st.used_dev = _apply_device_deltas(
                            st.used_dev, rows, vals, mesh=st.dev_mesh)
                    if st.net_dev is not None and len(net_rows):
                        from .encode import pow2_bucket

                        st.net_dev = _apply_device_deltas(
                            st.net_dev, net_rows, net_vals)
                        # The upload: the padded rows and their values.
                        info["net_delta_words"] = (pow2_bucket(
                            len(net_rows)) * (1 + NET_DIMS))
                    info["delta_apply_s"] = _time.monotonic() - t_da

                every = guard_every()
                if every > 0 and st.since_guard >= every:
                    st.since_guard = 0
                    GUARD_RUNS += 1
                    info["guard_ran"] = True
                    for attr, host in (("used_dev", used),
                                       ("net_dev", st.net)):
                        dev = getattr(st, attr)
                        if dev is None:
                            continue
                        # Device-mirror drift guard: the donated buffer
                        # must bit-match the host mirror it twins —
                        # drift here is an aliasing/donation bug (or
                        # real device corruption), caught independently
                        # of the host-vs-walk compare below.
                        dev_host = np.asarray(dev)
                        if not np.array_equal(
                                dev_host.astype(np.int64), host):
                            global DEV_GUARD_MISMATCHES
                            DEV_GUARD_MISMATCHES += 1
                            bad_mask = (dev_host.astype(np.int64)
                                        != host).any(axis=1)
                            bad = int(bad_mask.sum())
                            dev_bad_shards: List[int] = []
                            if shards > 0 and attr == "used_dev":
                                n_l = max(1, host.shape[0] // shards)
                                dev_bad_shards = sorted(
                                    {int(r) // n_l
                                     for r in np.nonzero(bad_mask)[0]})
                            logger.error(
                                "device %s mirror diverged from the "
                                "host mirror on %d rows%s; dropping the "
                                "donated buffer and feeding the breaker",
                                "usage" if attr == "used_dev" else
                                "network", bad,
                                (f" (mesh shards {dev_bad_shards})"
                                 if dev_bad_shards else ""))
                            tracing.event("resident.device_mismatch",
                                          rows=bad, shards=dev_bad_shards)
                            _publish("device_mirror_mismatch", Rows=bad,
                                     AllocIndex=snap_index,
                                     Shards=dev_bad_shards)
                            if breaker is not None:
                                breaker.record(False)
                            setattr(st, attr, None)
                    ref_used, ref_touched = _full_usage(base, walk)
                    ref_net, ref_ports = (_full_net(base, walk, st.ports)
                                          if st.net is not None
                                          else (None, {}))
                    bad_rows = np.nonzero(
                        (used != ref_used).any(axis=1))[0]
                    if ref_net is not None:
                        bad_rows = np.union1d(bad_rows, np.nonzero(
                            (st.net != ref_net).any(axis=1))[0])
                    for port, col in ref_ports.items():
                        bad_rows = np.union1d(bad_rows, np.nonzero(
                            st.ports[port] != col)[0])
                    if len(bad_rows):
                        GUARD_MISMATCHES += 1
                        info["guard_mismatch"] = True
                        bad = int(len(bad_rows))
                        bad_shards: List[int] = []
                        if shards > 0:
                            n_l = max(1, used.shape[0] // shards)
                            bad_shards = sorted(
                                {int(r) // n_l for r in bad_rows})
                            info["guard_bad_shards"] = bad_shards
                        logger.error(
                            "resident usage mirror diverged from full "
                            "re-encode on %d node rows%s; invalidating "
                            "and feeding the breaker", bad,
                            (f" (mesh shards {bad_shards})"
                             if bad_shards else ""))
                        tracing.event("resident.guard_mismatch", rows=bad,
                                      shards=bad_shards)
                        _publish("guard_mismatch", Rows=bad,
                                 AllocIndex=snap_index,
                                 Shards=bad_shards)
                        if breaker is not None:
                            breaker.record(False)
                        _STATE = None
                        info["resident_hit"] = False
                        info["full_reencode"] = True
                        if with_net:
                            served(*net_walk(ports), False)
                        return ref_used, sorted(ref_touched), info
                    if breaker is not None:
                        breaker.record(True)
                    # Guard pass doubles as touched-set compaction:
                    # rows whose allocs all stopped drop out.
                    st.touched = set(ref_touched)

                if with_net:
                    new_ports = [p for p in ports if p not in st.ports]
                    if st.net is None or new_ports:
                        net, columns = net_walk(new_ports)
                        if st.net is None:
                            st.net = net
                        st.ports.update(columns)
                    served(st.net.copy(), st.ports, True)
                # Hand the caller a copy: the resident matrix keeps
                # advancing under later batches while the device pass /
                # forensics of THIS batch still read their snapshot.
                return used.copy(), sorted(st.touched), info

        # Miss, key change, or feed gap: full rebuild + (re)install.
        reason = ("feed_gap" if st is not None and st.key == cache_key
                  else ("key_change" if st is not None else "cold"))
        FULL_REENCODES += 1
        info["full_reencode"] = True
        used, touched = _usage_source(base, walk, usage_fn)
        _STATE = ResidentState(cache_key, used, snap_index, set(touched))
        if with_net:
            _STATE.net, _STATE.ports = net_walk(ports)
            served(_STATE.net.copy(), _STATE.ports, True)
        tracing.event("resident.full_reencode", reason=reason,
                      alloc_index=snap_index)
        if reason != "cold":
            _publish(reason, AllocIndex=snap_index,
                     Nodes=int(base.n_real))
        return used.copy(), sorted(touched), info
