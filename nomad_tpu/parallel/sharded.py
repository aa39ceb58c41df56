"""Device-mesh scale-out of the batch scheduler (SURVEY.md §2.9).

The scaling axis of this workload is nodes × task-groups, and the node axis
is embarrassingly shardable: each device scores its node shard, reduces to a
local top-k per spec, and the k·D candidates are all-gathered over ICI —
the moral equivalent of sequence parallelism for this workload.  The
sequential commit loop then runs on the merged candidate set (U × k·D ≪
U × N), preserving capacity feedback.

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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.kernels import (
    DPTensors,
    NetTensors,
    PlacementResult,
    _score_fit,
    dp_best_per_value,
    dp_columns,
    dp_read_back,
    dp_used_lookup,
    dp_used_update,
    jitter_seed,
    pack_scalars,
    spec_major,
    tie_jitter,
    value_codes,
)
from ..ops.encode import MISSING

NEG_INF = -1e30

# Mesh axis names: 'nodes' shards the node dimension of the score matrix
# (intra-slice, rides ICI); 'batch' is reserved for sharding the spec axis
# across data-parallel replicas.
NODE_AXIS = "nodes"
BATCH_AXIS = "batch"


def make_node_mesh(devices=None) -> Mesh:
    """A 1-D mesh over the node axis."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), (NODE_AXIS,))


def _mark_varying(x):
    """Mark a freshly-created array as node-axis-varying inside the
    mapped function (shard_map's varying-manual-axes typing)."""
    return lax.pcast(x, (NODE_AXIS,), to="varying")


def sharded_placement_rounds(
    mesh: Mesh,
    feas: jax.Array,           # [U, N] bool — sharded on N
    used0: jax.Array,          # [N, 4] int32
    capacity: jax.Array,       # [N, 4] int32
    denom: jax.Array,          # [N, 2] float32
    ask: jax.Array,            # [U, 4] int32 — replicated
    count: jax.Array,          # [U] int32
    penalty: jax.Array,        # [U] float32
    distinct_hosts: jax.Array, # [U] bool
    job_index: jax.Array,      # [U] int32 → row in job_counts
    job_counts0: jax.Array,    # [J, N] int32 — sharded on N
    rng_key: jax.Array,
    k_cand: int = 64,
    max_rounds: int = 256,
    net: NetTensors = None,
    dp: DPTensors = None,
) -> PlacementResult:
    """The single-chip `placement_rounds` semantics, node-sharded over the
    mesh: anti-affinity collisions, distinct_hosts, per-(job,node) counts,
    network port/bandwidth accounting, distinct_property and the
    spec-major capacity-feedback loop (``kernels.spec_major``: each spec
    placed to its end before the next) all run on sharded state.

    Per spec, each shard scores its node shard (binpack − penalty·collisions
    + the same jitter the single-chip kernel uses), takes a local top-k_cand,
    and the k_cand·D candidates are all-gathered over ICI; the global top-k
    selection and shard-local commit follow.  As long as a spec commits
    ≤ k_cand allocs in a pass (one alloc per node per pass — the
    anti-affinity bound), the selection is *identical* to the single-chip
    kernel's full-argsort commit, including tie-breaks: gathered candidate
    order is (shard, local index) = global node order, and both paths use
    stable sorts.  Specs needing more than k_cand·D per pass under-commit
    that pass and finish in their later passes (progress loop).

    ``net`` shards its per-node state (bw_cap/bw_used/dyn_free/port_words)
    over the mesh and replicates the per-spec asks — feasibility and
    commits are shard-local, mirroring ops/kernels.py (rank.go:190-238).
    ``dp`` replicates the per-spec used-value bitsets; the within-pass
    best-per-value dedup runs as pmax/pmin all-reduces over the value
    axis, between the single-chip kernel's own per-value table and its
    read-back (``kernels.ValueCodes``), so every shard keeps the winner
    the single-chip pass picks (propertyset.go:150).

    Ref: scheduler/rank.go:247 (anti-affinity), feasible.go:148
    (distinct_hosts), SURVEY.md §2.9 node-axis sharding.
    """
    u_pad, n_pad = feas.shape
    d = mesh.devices.size
    assert n_pad % d == 0, (
        f"mesh size {d} must divide node axis {n_pad} (pad N up)")
    k_cand = min(k_cand, n_pad // d)
    use_net = net is not None
    use_dp = dp is not None
    if net is None:
        net = NetTensors(
            active=jnp.zeros(1, dtype=bool),
            mbits=jnp.zeros(1, dtype=jnp.int32),
            dyn_need=jnp.zeros(1, dtype=jnp.int32),
            resv_words=jnp.zeros((1, 1), dtype=jnp.uint32),
            bw_cap=jnp.zeros(n_pad, dtype=jnp.int32),
            bw_used=jnp.zeros(n_pad, dtype=jnp.int32),
            dyn_free=jnp.zeros(n_pad, dtype=jnp.int32),
            port_words=jnp.zeros((n_pad, 1), dtype=jnp.uint32),
        )
    if dp is None:
        dp = DPTensors(
            col=jnp.full(1, -1, dtype=jnp.int32),
            active=jnp.zeros(1, dtype=bool),
            used0=jnp.zeros((1, 1), dtype=bool),
            attr_values=jnp.full((n_pad, 1), MISSING, dtype=jnp.int32),
        )
    v_pad = dp.used0.shape[1]

    # Identical tie-break jitter to the single-chip kernel: the hash is
    # keyed on the GLOBAL node index, so each shard computes its slice
    # directly — no [U, N] matrix to materialize or shard.
    jit_seed = jitter_seed(rng_key)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(None, NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
                  P(NODE_AXIS), P(None), P(None), P(None), P(None),
                  P(None), P(None, NODE_AXIS), P(),
                  # net: per-spec replicated, per-node sharded
                  P(None), P(None), P(None), P(None),
                  P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS), P(NODE_AXIS),
                  # dp: per-spec replicated, node attrs sharded
                  P(None), P(None), P(None), P(NODE_AXIS)),
        out_specs=(P(None, NODE_AXIS), P(None), P(NODE_AXIS), P()),
    )
    def _run(feas_l, used_l, cap_l, denom_l, ask_r, count_r, penalty_r,
             dh_r, job_index_r, jc_l, jit_seed_r,
             net_active_r, net_mbits_r, dyn_need_r, resv_words_r,
             bw_cap_l, bw_used_l0, dyn_free_l0, port_words_l0,
             dp_col_r, dp_active_r, dp_used0_r, dp_attr_l):
        n_l = used_l.shape[0]
        shard = lax.axis_index(NODE_AXIS)
        c_total = k_cand * d
        big_idx = jnp.int32(n_pad + 1)
        gidx = shard * n_l + jnp.arange(n_l, dtype=jnp.int32)
        dp_codes_l = dp_columns(dp_attr_l, dp_col_r) if use_dp else None

        def place_pass(carry, u):
            (used, jc, remaining, placements,
             bw_used, port_words, dyn_free, dp_used) = carry
            cap_left = cap_l - used
            fits = jnp.all(ask_r[u][None, :] <= cap_left, axis=1)
            collisions = jc[job_index_r[u]]            # [N_l] int32
            ok = feas_l[u] & fits
            ok = ok & jnp.where(dh_r[u], collisions == 0, True)

            if use_net:
                bw_ok = bw_used + net_mbits_r[u] <= bw_cap_l
                resv_hit = jnp.any(
                    (port_words & resv_words_r[u][None, :]) != 0, axis=1)
                dyn_ok = dyn_free >= dyn_need_r[u]
                ok = ok & jnp.where(net_active_r[u],
                                    bw_ok & ~resv_hit & dyn_ok, True)

            if use_dp:
                codes = dp_codes_l[u]                  # [N_l]
                vc = value_codes(codes, v_pad)
                dp_ok = ((codes != MISSING)
                         & ~dp_used_lookup(vc, dp_used[u]))
                ok = ok & jnp.where(dp_active_r[u], dp_ok, True)

            score = _score_fit(used, ask_r[u], denom_l)
            score = score - penalty_r[u] * collisions.astype(jnp.float32)
            score = score + tie_jitter(jit_seed_r, u, gidx)
            scored = jnp.where(ok, score, NEG_INF)

            # Local top-k_cand, then the ICI all-gather: the only
            # cross-shard traffic in the hot loop is [D, k_cand] floats.
            loc_scores, loc_idx = lax.top_k(scored, k_cand)
            all_scores = lax.all_gather(
                loc_scores, NODE_AXIS, tiled=True)     # [D*k_cand]
            n_ok, n_fits = lax.psum(
                jnp.stack([jnp.sum(ok.astype(jnp.int32)),
                           jnp.sum(fits.astype(jnp.int32))]), NODE_AXIS)
            k = jnp.minimum(remaining[u], n_ok)
            # The single-chip pass's two skips, as what they count: a
            # pass with nothing left to place, or over a fleet with no
            # room for the ask on capacity alone, did not run.
            ran = (remaining[u] > 0) & (n_fits > 0)

            order = jnp.argsort(-all_scores)
            ranks = jnp.zeros(c_total, dtype=jnp.int32).at[order].set(
                jnp.arange(c_total, dtype=jnp.int32))
            sel_cand = (all_scores > NEG_INF / 2) & (ranks < k)
            my_sel = lax.dynamic_slice(sel_cand, (shard * k_cand,), (k_cand,))
            sel = jnp.zeros(n_l, dtype=bool).at[loc_idx].set(my_sel) & ok

            if use_dp:
                # Cross-shard within-pass value dedup: the best-scored
                # selected node per property value wins globally (ties by
                # lowest GLOBAL node index), via pmax/pmin over the value
                # axis — bit-identical to the single-chip per-value best.
                neg = jnp.float32(NEG_INF)
                sel_score = jnp.where(sel, scored, neg)
                best_g = lax.pmax(
                    dp_best_per_value(vc, sel_score, neg, largest=True),
                    NODE_AXIS)
                cand_dp = sel & (sel_score >= dp_read_back(
                    vc, best_g, neg, largest=True))
                idx_g = lax.pmin(
                    dp_best_per_value(
                        vc, jnp.where(cand_dp, gidx, big_idx), big_idx,
                        largest=False),
                    NODE_AXIS)
                keep = cand_dp & (gidx == dp_read_back(
                    vc, idx_g, big_idx, largest=False))
                sel = jnp.where(dp_active_r[u], keep, sel)

            sel_i = sel.astype(jnp.int32)
            used = used + sel_i[:, None] * ask_r[u][None, :]
            jc = jc.at[job_index_r[u]].add(sel_i)
            placements = placements.at[u].add(sel_i)
            placed = lax.psum(jnp.sum(sel_i), NODE_AXIS)
            remaining = remaining.at[u].add(-placed)

            if use_net:
                commit_net = net_active_r[u]
                bw_used = bw_used + jnp.where(commit_net,
                                              sel_i * net_mbits_r[u], 0)
                port_words = jnp.where(
                    (commit_net & sel)[:, None],
                    port_words | resv_words_r[u][None, :], port_words)
                dyn_free = dyn_free - jnp.where(
                    commit_net, sel_i * dyn_need_r[u], 0)
            if use_dp:
                dp_upd = lax.psum(
                    dp_used_update(vc, sel & dp_active_r[u]).astype(
                        jnp.int32), NODE_AXIS) > 0
                dp_used = lax.dynamic_update_index_in_dim(
                    dp_used, dp_used[u] | dp_upd, u, axis=0)

            return (used, jc, remaining, placements,
                    bw_used, port_words, dyn_free, dp_used), placed, ran

        placements0 = _mark_varying(
            jnp.zeros((u_pad, n_l), dtype=jnp.int32))
        carry = (used_l, jc_l, count_r, placements0,
                 bw_used_l0, port_words_l0, dyn_free_l0, dp_used0_r)
        (used, jc, remaining, placements, _bw, _pw, _df, _dpu), passes = \
            spec_major(place_pass, carry, lambda c: c[2], u_pad, max_rounds)
        return placements, remaining, used, passes.most

    placements, unplaced, used_after, rounds = _run(
        feas, used0, capacity, denom, ask, count, penalty, distinct_hosts,
        job_index, job_counts0, jit_seed,
        net.active, net.mbits, net.dyn_need, net.resv_words,
        net.bw_cap, net.bw_used, net.dyn_free, net.port_words,
        dp.col, dp.active, dp.used0, dp.attr_values)
    return PlacementResult(
        placements=placements, unplaced=unplaced,
        used_after=used_after, rounds=rounds)


# -- fused single-dispatch mesh pass (ISSUE 8 tentpole) ---------------------
#
# The multi-device twin of ops/kernels.fused_pass: ONE device dispatch
# over node-sharded packed static buffers + a replicated dynamic buffer
# runs unpack (+ dequantize) → per-shard usage-delta scatter-adds →
# per-shard feasibility → the local-top-k + ICI-all-gather capacity-
# feedback commit loop (spec-major, ops/kernels.spec_major: the one loop
# all three programs share) → a commit-ordered slot record → slot→COO gather
# → ONE packed result buffer (replicated, fetched from one device).
#
# Exactness: per pass a spec commits at most ``remaining ≤ count``
# allocs, so with ``k_cand ≥ max(count)`` (or k_cand == the whole shard)
# the global top-``remaining`` of any pass lies inside the gathered
# local top-k_cand candidates — the selection, tie-jitter (keyed on
# GLOBAL node index) and commit order are bit-identical to the
# single-chip kernel.  batch_sched sizes k_cand that way, so the mesh
# path is exact by construction, not within a budget.
#
# Slot-record merge: each shard records ITS OWN committed nodes at their
# global commit positions (per-commit position = allocs placed so far +
# lower-shard count prefix + within-shard ascending-node rank — the
# single-chip kernel's ascending-node commit order), encoded as
# ``global_index + 1`` with 0 for empty, so positions are disjoint
# across shards and ONE end-of-loop psum produces the replicated
# [U, M] record the COO gather (ops/kernels._slots_coo_gather, the very
# same expression the single-chip fused program uses) consumes.

# Compiled sharded-fused programs keyed by (mesh devices, metas, static
# shape/flags): the production hot loop must not re-trace per batch the
# way the legacy eager shard_map side path did.  Touch-on-hit LRU with
# eviction accounting (utils/lru.py): a long-lived server seeing many
# mesh/meta shapes recycles programs instead of growing without bound,
# and the batch.program_cache_evictions gauge shows it happening.
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
    from ..ops.kernels import (
        _score_fit as score_fit,
        _slots_coo_gather,
        feasibility_matrix,
    )

    d = mesh.devices.size
    n_l = n_pad // d
    c_total = k_cand * d
    big_idx = jnp.int32(n_pad + 1)

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
        gidx = shard * n_l + jnp.arange(n_l, dtype=jnp.int32)

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

        if with_networks:
            bw_used0 = ds["bw_used_base"].at[uidx].add(
                dd["u_bw"], mode="drop")
            dyn_free0 = ds["dyn_free_base"].at[uidx].add(
                dd["u_dyn"], mode="drop")
            port_words0 = ds["port_words_base"].at[uidx].set(
                dd["u_ports"], mode="drop")
        else:
            bw_used0 = jnp.zeros(n_l, dtype=jnp.int32)
            dyn_free0 = jnp.zeros(n_l, dtype=jnp.int32)
            port_words0 = jnp.zeros((n_l, 1), dtype=jnp.uint32)
        if with_dp:
            dp_used_init = dd["dp_used"]
            v_pad = dp_used_init.shape[1]
            dp_codes_l = dp_columns(ds["attr"], dd["dp_col"])
        else:
            dp_used_init = jnp.zeros((1, 1), dtype=bool)
            v_pad = 1

        cap_l = ds["cap"]
        denom_l = ds["denom"]
        ask_r = dd["ask"]
        count_r = dd["count"]
        key = jax.random.PRNGKey(dd["rng_seed"][0])
        jit_seed_r = jitter_seed(key)
        d_arange = jnp.arange(d, dtype=jnp.int32)

        def place_pass(carry, u):
            (used, jc, remaining, bw_used, port_words, dyn_free, dp_used,
             slots, sscores, scoll) = carry
            cap_left = cap_l - used
            fits = jnp.all(ask_r[u][None, :] <= cap_left, axis=1)
            collisions = jc[dd["ji"][u]]
            ok = feas_l[u] & fits
            ok = ok & jnp.where(dd["dh"][u], collisions == 0, True)

            if with_networks:
                bw_ok = bw_used + dd["net_mbits"][u] <= ds["bw_cap"]
                resv_hit = jnp.any(
                    (port_words & dd["resv_words"][u][None, :]) != 0,
                    axis=1)
                dyn_ok = dyn_free >= dd["dyn_need"][u]
                ok = ok & jnp.where(dd["net_active"][u],
                                    bw_ok & ~resv_hit & dyn_ok, True)
            if with_dp:
                codes = dp_codes_l[u]
                vc = value_codes(codes, v_pad)
                dp_ok = ((codes != MISSING)
                         & ~dp_used_lookup(vc, dp_used[u]))
                ok = ok & jnp.where(dd["dp_active"][u], dp_ok, True)

            base_score = score_fit(used, ask_r[u], denom_l)
            score = (base_score
                     - dd["penalty"][u] * collisions.astype(jnp.float32))
            score = score + tie_jitter(jit_seed_r, u, gidx)
            scored = jnp.where(ok, score, NEG_INF)

            # Local top-k_cand → ICI all-gather → global top-k select
            # (identical to sharded_placement_rounds; exact because
            # k ≤ remaining ≤ count ≤ k_cand).
            loc_scores, loc_idx = lax.top_k(scored, k_cand)
            all_scores = lax.all_gather(loc_scores, NODE_AXIS, tiled=True)
            n_ok, n_fits = lax.psum(
                jnp.stack([jnp.sum(ok.astype(jnp.int32)),
                           jnp.sum(fits.astype(jnp.int32))]), NODE_AXIS)
            k = jnp.minimum(remaining[u], n_ok)
            ran = (remaining[u] > 0) & (n_fits > 0)
            order = jnp.argsort(-all_scores)
            ranks = jnp.zeros(c_total, dtype=jnp.int32).at[order].set(
                jnp.arange(c_total, dtype=jnp.int32))
            sel_cand = (all_scores > NEG_INF / 2) & (ranks < k)
            my_sel = lax.dynamic_slice(
                sel_cand, (shard * k_cand,), (k_cand,))
            sel = jnp.zeros(n_l, dtype=bool).at[loc_idx].set(my_sel) & ok

            if with_dp:
                neg = jnp.float32(NEG_INF)
                sel_score = jnp.where(sel, scored, neg)
                best_g = lax.pmax(
                    dp_best_per_value(vc, sel_score, neg, largest=True),
                    NODE_AXIS)
                cand_dp = sel & (sel_score >= dp_read_back(
                    vc, best_g, neg, largest=True))
                idx_g = lax.pmin(
                    dp_best_per_value(
                        vc, jnp.where(cand_dp, gidx, big_idx), big_idx,
                        largest=False),
                    NODE_AXIS)
                keep = cand_dp & (gidx == dp_read_back(
                    vc, idx_g, big_idx, largest=False))
                sel = jnp.where(dd["dp_active"][u], keep, sel)

            sel_i = sel.astype(jnp.int32)
            placed_l = jnp.sum(sel_i)
            counts_g = lax.all_gather(placed_l, NODE_AXIS)      # [D]
            # The total comes from psum, not jnp.sum(counts_g): an
            # all_gather result is typed node-varying, and ``remaining``
            # / the while-loop progress flag must stay replicated-typed
            # to match their carry inputs and the P() out_specs.
            placed = lax.psum(placed_l, NODE_AXIS)
            # Global commit positions in the single-chip kernel's
            # ascending-node order: allocs placed so far + lower-shard
            # prefix + within-shard ascending-node rank.
            prefix = jnp.sum(jnp.where(d_arange < shard, counts_g, 0))
            offset = count_r[u] - remaining[u]
            pos_l = jnp.cumsum(sel_i)
            dest = jnp.where(sel, offset + prefix + pos_l - 1,
                             jnp.int32(slot_m))
            slots = slots.at[u, dest].set(gidx + 1, mode="drop")
            if with_scores:
                sscores = sscores.at[u, dest].set(base_score, mode="drop")
                scoll = scoll.at[u, dest].set(collisions, mode="drop")

            used = used + sel_i[:, None] * ask_r[u][None, :]
            jc = jc.at[dd["ji"][u]].add(sel_i)
            remaining = remaining.at[u].add(-placed)
            if with_networks:
                commit_net = dd["net_active"][u]
                bw_used = bw_used + jnp.where(
                    commit_net, sel_i * dd["net_mbits"][u], 0)
                port_words = jnp.where(
                    (commit_net & sel)[:, None],
                    port_words | dd["resv_words"][u][None, :], port_words)
                dyn_free = dyn_free - jnp.where(
                    commit_net, sel_i * dd["dyn_need"][u], 0)
            if with_dp:
                dp_upd = lax.psum(
                    dp_used_update(vc, sel & dd["dp_active"][u]).astype(
                        jnp.int32), NODE_AXIS) > 0
                dp_used = lax.dynamic_update_index_in_dim(
                    dp_used, dp_used[u] | dp_upd, u, axis=0)
            return (used, jc, remaining, bw_used, port_words, dyn_free,
                    dp_used, slots, sscores, scoll), placed, ran

        sscore_shape = (u_pad, slot_m) if with_scores else (1, 1)
        carry = (used0, jc0, count_r,
                 bw_used0, port_words0, dyn_free0, dp_used_init,
                 _mark_varying(jnp.zeros((u_pad, slot_m), dtype=jnp.int32)),
                 _mark_varying(jnp.zeros(sscore_shape, dtype=jnp.float32)),
                 _mark_varying(jnp.zeros(sscore_shape, dtype=jnp.int32)))
        (used, jc, remaining, _bw, _pw, _df, _dpu, slots_p, sscores_p,
         scoll_p), passes = spec_major(
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


def sharded_schedule_step(
    mesh: Mesh,
    feas: jax.Array,
    used: jax.Array,
    capacity: jax.Array,
    denom: jax.Array,
    ask: jax.Array,
    count: jax.Array,
    k: int = 64,
) -> Tuple[jax.Array, jax.Array]:
    """Convenience wrapper: one full-semantics scheduling step over the mesh
    with default job bookkeeping (one job per spec, standard service
    anti-affinity penalty, no distinct_hosts)."""
    u_pad, n_pad = feas.shape
    result = sharded_placement_rounds(
        mesh, feas, used, capacity, denom, ask, count,
        penalty=jnp.full((u_pad,), 20.0, dtype=jnp.float32),
        distinct_hosts=jnp.zeros((u_pad,), dtype=bool),
        job_index=jnp.arange(u_pad, dtype=jnp.int32),
        job_counts0=jnp.zeros((u_pad, n_pad), dtype=jnp.int32),
        rng_key=jax.random.PRNGKey(0),
        k_cand=k,
    )
    return result.placements, result.used_after
