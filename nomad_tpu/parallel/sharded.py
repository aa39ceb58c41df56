"""Device-mesh scale-out of the batch scheduler (SURVEY.md §2.9).

The scaling axis of this workload is nodes × task-groups, and the node axis
is embarrassingly shardable: each device holds a node shard and runs the
single-chip placement pass (``ops/kernels.placement_pass``) over it; only
the few operations that need the whole fleet go through ``MeshSeam``: a
local top-k per spec whose k·D candidates are all-gathered over ICI, and
the ``psum`` / ``pmax`` / ``pmin`` of counts and per-value tables.

Multi-slice (DCN) is the analogue of the reference's multi-region
federation (nomad/rpc.go:263 forwardRegion): each slice owns a region's
nodes; cross-slice placement goes through region forwarding, not through
the mesh — so this module only ever shards within a slice.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.kernels import (
    NEG_INF,
    PassSeam,
    _disabled_dp,
    _disabled_net,
    dp_tensors,
    jitter_seed,
    net_tensors,
    pack_scalars,
    placement_pass,
    spec_major,
)

# Mesh axis name: 'nodes' shards the node dimension of the score matrix
# (intra-slice, rides ICI).
NODE_AXIS = "nodes"


def make_node_mesh(devices=None) -> Mesh:
    """A 1-D mesh over the node axis."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), (NODE_AXIS,))


def _mark_varying(x):
    """Mark a freshly-created array as node-axis-varying inside the
    mapped function (shard_map's varying-manual-axes typing)."""
    return lax.pcast(x, (NODE_AXIS,), to="varying")


class MeshSeam(PassSeam):
    """The node mesh's side of the pass seam, inside the mapped function:
    this shard holds ``n_l`` rows of an ``n_pad``-node table, and what
    needs the whole fleet is a collective over ``NODE_AXIS``.  Every
    collective's result is replicated, so the pass's ``lax.cond`` skips
    take one branch on every shard."""

    def __init__(self, shard, n_l: int, n_pad: int, k_cand: int):
        super().__init__(shard * n_l + jnp.arange(n_l, dtype=jnp.int32),
                         n_pad)
        # ``global index + 1``, 0 for empty: the shards' slot records are
        # disjoint, and ONE end-of-loop psum merges them.
        self.slot_ids = self.node_idx + 1
        self.shard = shard
        self.k_cand = k_cand
        self.d = n_pad // n_l

    def total(self, local):
        return lax.psum(local, NODE_AXIS)

    def any_node(self, mask):
        return lax.psum(jnp.any(mask).astype(jnp.int32), NODE_AXIS) > 0

    def select(self, scored, ok, k):
        # Local top-k_cand → ICI all-gather → global rank → this shard's
        # slice: the only cross-shard traffic of a pass is [D, k_cand]
        # floats.  Exact because k ≤ remaining ≤ count ≤ k_cand (or
        # k_cand is the whole shard): the global top-k lies inside the
        # gathered candidates, whose order (shard, local index) is the
        # global node order, and both sorts are stable, so the selection
        # and its tie order are the single chip's.
        loc_scores, loc_idx = lax.top_k(scored, self.k_cand)
        all_scores = lax.all_gather(loc_scores, NODE_AXIS, tiled=True)
        c_total = self.k_cand * self.d
        order = jnp.argsort(-all_scores)
        ranks = jnp.zeros(c_total, dtype=jnp.int32).at[order].set(
            jnp.arange(c_total, dtype=jnp.int32))
        sel_cand = (all_scores > NEG_INF / 2) & (ranks < k)
        my_sel = lax.dynamic_slice(
            sel_cand, (self.shard * self.k_cand,), (self.k_cand,))
        return (jnp.zeros(scored.shape[0], dtype=bool).at[loc_idx].set(my_sel)
                & ok)

    def value_max(self, table):
        return lax.pmax(table, NODE_AXIS)

    def value_min(self, table):
        return lax.pmin(table, NODE_AXIS)

    def value_union(self, hit):
        return lax.psum(hit.astype(jnp.int32), NODE_AXIS) > 0

    def slot_offset(self, offset, placed):
        # The single chip's ascending-node commit order: allocs placed
        # so far + the lower shards' commits of this pass.
        counts = lax.all_gather(placed, NODE_AXIS)            # [D]
        lower = jnp.arange(self.d, dtype=jnp.int32) < self.shard
        return offset + jnp.sum(jnp.where(lower, counts, 0))


# -- fused single-dispatch mesh program ------------------------------------
#
# The multi-device twin of ops/kernels.fused_pass: ONE device dispatch
# over node-sharded packed static buffers + a replicated dynamic buffer
# runs unpack (+ dequantize) → per-shard usage-delta scatter-adds →
# per-shard feasibility → the single-chip spec-major loop and pass
# (ops/kernels.spec_major, placement_pass) over the shard, through
# ``MeshSeam`` → a commit-ordered slot record → slot→COO gather → ONE
# packed result buffer (replicated, fetched from one device).
#
# Exactness: batch_sched sizes ``k_cand ≥ max(count)`` (or the whole
# shard), which ``MeshSeam.select`` needs to pick the single chip's
# nodes; the tie-jitter is keyed on the GLOBAL node index.  Each shard
# records its own commits at their global positions (``slot_offset``)
# as ``global index + 1``, and one end-of-loop psum merges the disjoint
# partials into the replicated [U, M] record the COO gather
# (ops/kernels._slots_coo_gather, the single-chip program's own) reads.

# Compiled sharded-fused programs keyed by (mesh devices, metas, static
# shape/flags): the production hot loop must not re-trace per batch.
# Touch-on-hit LRU with eviction accounting (utils/lru.py): a long-lived
# server seeing many mesh/meta shapes recycles programs instead of
# growing without bound, and the batch.program_cache_evictions gauge
# shows it happening.
from ..utils.lru import LRU as _LRU

_FUSED_MESH_CACHE = _LRU(16)


def _mesh_cache_key(mesh) -> Tuple:
    return tuple(d.id for d in mesh.devices.flat)


def sharded_fused_pass(
    mesh: Mesh,
    static_shards,          # [D, B] uint8 — NamedSharding P(NODE_AXIS)
    dyn_buf,                # [Bd] uint8 — replicated
    used_dev=None,          # [n_pad, 4] int32 — DONATED sharded mirror
    *,
    meta_s,                 # PER-SHARD static layout (n_l-row shapes)
    meta_d,
    u_pad: int,
    n_pad: int,
    with_networks: bool,
    with_dp: bool,
    with_scores: bool,
    max_nnz: int,
    slot_m: int,
    k_cand: int,
    max_rounds: int = 256,
):
    """Fused node-sharded score-and-commit: returns
    ``(packed result buffer, (slots, slot_scores, slot_coll), feas,
    result layout meta, used_out)`` exactly like ops/kernels.fused_pass
    — the caller's fetch/decode/forensics paths are shared with the
    single-chip program.  ``slots``/scores are replicated [U, M]
    (overflow source); ``feas`` stays node-sharded [U, n_pad].

    ``used_dev`` (optional, ISSUE 14): the DONATED node-sharded
    device-resident usage mirror — one [n_local, 4] buffer per shard
    under ``NamedSharding(mesh, P(NODE_AXIS))``.  When present the
    per-batch replicated ``u_rows``/``u_vals`` usage upload AND the
    on-device global→local row remap disappear: each shard's usage
    state IS its mirror slice, and the buffer rides back out aliased as
    ``used_out`` for ops/resident.py's loan protocol (None when no
    mirror was passed — the sparse-delta path)."""
    from ..ops.kernels import fused_layout, fused_window

    d = mesh.devices.size
    assert n_pad % d == 0, f"mesh size {d} must divide node pad {n_pad}"
    assert slot_m > 0, "the fused mesh pass requires a slot record"
    use_used_dev = used_dev is not None
    assert not (use_used_dev and with_networks), \
        "sharded usage mirror is gated to non-network batches"
    k_cand = min(k_cand, n_pad // d)
    compact_u16 = (not with_scores and u_pad <= 65536
                   and n_pad <= 65536 and max_rounds < 65536)
    window_nnz = fused_window(max_nnz, with_scores=with_scores,
                              compact_u16=compact_u16)
    meta = fused_layout(u_pad, window_nnz=window_nnz,
                        with_scores=with_scores, compact_u16=compact_u16)
    key = (_mesh_cache_key(mesh), meta_s, meta_d, u_pad, n_pad,
           with_networks, with_dp, with_scores, slot_m, k_cand,
           max_rounds, window_nnz, compact_u16, use_used_dev)
    from ..ops import kernels as _kernels

    fn = _FUSED_MESH_CACHE.get(key)
    if fn is None:
        fn = _build_fused_mesh_fn(
            mesh, meta_s=meta_s, meta_d=meta_d, u_pad=u_pad, n_pad=n_pad,
            with_networks=with_networks, with_dp=with_dp,
            with_scores=with_scores, slot_m=slot_m, k_cand=k_cand,
            max_rounds=max_rounds, window_nnz=window_nnz,
            compact_u16=compact_u16, use_used_dev=use_used_dev)
        _FUSED_MESH_CACHE.put(key, fn)
    if not use_used_dev:
        # Shardable dummy ([1, 4] per device) keeps one program shape;
        # the aliased output is discarded.
        used_dev = jnp.zeros((d, 4), dtype=jnp.int32)
    with _kernels.program_call("sharded_fused_pass", key):
        buf, slots, sscores, scoll, feas, used_out = fn(
            static_shards, dyn_buf, used_dev)
    return (buf, (slots, sscores, scoll), feas, meta,
            (used_out if use_used_dev else None))



def _build_fused_mesh_fn(mesh, *, meta_s, meta_d, u_pad, n_pad,
                         with_networks, with_dp, with_scores, slot_m,
                         k_cand, max_rounds, window_nnz, compact_u16,
                         use_used_dev=False):
    from ..ops import xfer
    from ..ops.kernels import _slots_coo_gather, feasibility_matrix

    d = mesh.devices.size
    n_l = n_pad // d

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(NODE_AXIS), P(), P(NODE_AXIS)),
        out_specs=(P(), P(), P(), P(), P(None, NODE_AXIS), P(NODE_AXIS)),
    )
    def _run(sbuf_l, dyn, used_dev_l):
        ds = xfer.unpack_device(sbuf_l.reshape(-1), meta_s)
        dd = xfer.unpack_device(dyn, meta_d)
        # Quantized resource rows: one exact integer multiply per shard
        # (the device twin of encode.dequantize_rows; [2, 4] codebook —
        # row 0 capacity, row 1 used baseline).
        if "res_scale" in ds:
            scale = ds.pop("res_scale")
            ds["cap"] = ds.pop("cap_q").astype(jnp.int32) * scale[0][None, :]
            ds["used_base"] = (ds.pop("used_base_q").astype(jnp.int32)
                               * scale[1][None, :])
        # Same materialization barrier as the single-chip program: keep
        # the packed-buffer decode out of the while/scan body.
        ds = dict(zip(ds.keys(),
                      lax.optimization_barrier(tuple(ds.values()))))
        dd = dict(zip(dd.keys(),
                      lax.optimization_barrier(tuple(dd.values()))))
        shard = lax.axis_index(NODE_AXIS)

        if use_used_dev:
            # The shard's usage state IS its slice of the donated
            # sharded mirror (ops/resident.py keeps it caught up in
            # place with shard-routed donated scatter-adds): no
            # per-batch usage upload, no global→local row remap.  The
            # buffer rides back out unchanged so XLA aliases it
            # input→output per shard.
            used0 = used_dev_l
        else:
            # Usage deltas carry GLOBAL node rows; each shard applies
            # only the rows it owns (the owning-shard scatter-add).
            lrow = dd["u_rows"] - shard * n_l
            uvalid = (dd["u_rows"] >= 0) & (lrow >= 0) & (lrow < n_l)
            uidx = jnp.where(uvalid, lrow, jnp.int32(n_l))
            used0 = ds["used_base"].at[uidx].add(dd["u_vals"],
                                                 mode="drop")

        # Per-(job, node) counts, local scatter of the global sparse set.
        jrow = jnp.clip(dd["jc_rows"], 0, u_pad - 1)
        jcol = dd["jc_cols"] - shard * n_l
        jvalid = (dd["jc_rows"] >= 0) & (jcol >= 0) & (jcol < n_l)
        jcol = jnp.where(jvalid, jcol, jnp.int32(n_l))
        jc0 = jnp.zeros((u_pad, n_l), dtype=jnp.int32).at[jrow, jcol].add(
            jnp.where(jvalid, dd["jc_vals"], 0), mode="drop")

        precomp = dd["precomp"]
        if precomp.shape != (1, 1):
            precomp = lax.dynamic_slice(
                precomp, (jnp.int32(0), shard * n_l), (u_pad, n_l))
        feas_l = feasibility_matrix(
            ds["attr"], ds["elig"], ds["dc"], dd["c_attr"], dd["c_op"],
            dd["c_rhs"], dd["dc_mask"], precomp)

        both = {**ds, **dd}
        if with_networks:
            net = net_tensors(
                both,
                ds["bw_used_base"].at[uidx].add(dd["u_bw"], mode="drop"),
                ds["dyn_free_base"].at[uidx].add(dd["u_dyn"], mode="drop"),
                ds["port_words_base"].at[uidx].set(dd["u_ports"],
                                                   mode="drop"))
        else:
            net = _disabled_net(u_pad, n_l)
        dp = dp_tensors(both) if with_dp else _disabled_dp(u_pad, n_l)

        count_r = dd["count"]
        jit_seed = jitter_seed(jax.random.PRNGKey(dd["rng_seed"][0]))
        place_pass = placement_pass(
            feas_l, ds["cap"], ds["denom"], dd["ask"], count_r,
            dd["penalty"], dd["dh"], dd["ji"], net, dp, jit_seed,
            MeshSeam(shard, n_l, n_pad, k_cand), use_net=with_networks,
            use_dp=with_dp, with_scores=with_scores, slot_m=slot_m)

        # ``placement_pass``'s carry.  Slot mode leaves the [U, N]
        # placement and score carries as (1, 1) placeholders; a slot is
        # 0 until a shard records ``global index + 1`` in it.
        one = jnp.zeros((1, 1), dtype=jnp.int32)
        sscore_shape = (u_pad, slot_m) if with_scores else (1, 1)
        carry = (used0, jc0, count_r, one,
                 net.bw_used, net.port_words, net.dyn_free, dp.used0,
                 one.astype(jnp.float32), one,
                 _mark_varying(jnp.zeros((u_pad, slot_m), dtype=jnp.int32)),
                 _mark_varying(jnp.zeros(sscore_shape, dtype=jnp.float32)),
                 _mark_varying(jnp.zeros(sscore_shape, dtype=jnp.int32)))
        (_used, _jc, remaining, _pl, _bw, _pw, _df, _dpu, _cs, _cc,
         slots_p, sscores_p, scoll_p), passes = spec_major(
            place_pass, carry, lambda c: c[2], u_pad, max_rounds)

        # Disjoint per-shard partials → ONE psum yields the replicated
        # commit-ordered record; +1/-1 encoding keeps empty slots at -1.
        slots_full = lax.psum(slots_p, NODE_AXIS) - 1
        sscores_full = lax.psum(sscores_p, NODE_AXIS)
        scoll_full = lax.psum(scoll_p, NODE_AXIS)
        coo_win, nnz = _slots_coo_gather(
            slots_full, sscores_full, scoll_full, out_rows=window_nnz,
            with_scores=with_scores, compact_u16=compact_u16)
        feas_count = lax.psum(
            jnp.sum(feas_l.astype(jnp.int32), axis=1), NODE_AXIS)
        buf, _ = xfer.pack_device({
            "unplaced": remaining,
            "feas_count": feas_count,
            "scalars": pack_scalars(nnz, passes),
            "coo": coo_win,
        })
        return buf, slots_full, sscores_full, scoll_full, feas_l, used_dev_l

    # The donated mirror (arg 2) aliases input→output per shard; with
    # the dummy it is neither donated nor meaningful.
    return jax.jit(_run,
                   donate_argnums=(2,) if use_used_dev else ())
