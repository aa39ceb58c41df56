"""The TPU batch scheduler: drains evaluations into fixed-size batches and
scores all pending task groups against all candidate nodes in one vectorized
pass (BASELINE.json north star).

Architecture (SURVEY.md §2.9 'batching replaces concurrency'):

- Host side reuses the oracle's reconciliation exactly — diffAllocs, stop/
  migrate/lost handling, in-place updates (generic_sched.go:350) — so every
  semantic except the placement inner loop is shared code with the CPU
  oracle.
- The placement inner loop (generic_sched.go:434 computePlacements ×
  stack.Select) is replaced: all (job, tg) placement asks across the whole
  eval batch are deduped into PlacementSpecs, encoded to SoA tensors, and
  placed by ops/kernels.py in one device invocation.
- Results flow back through the normal Plan/submit path unchanged, keeping
  the plan-apply optimistic-concurrency contract (plan_apply.go:42).

The per-JobID serialization invariant (eval_broker.go:56) is preserved by
construction: a batch never contains two evals for the same job (the broker
already guarantees at most one outstanding eval per job).
"""
from __future__ import annotations

import hashlib
import logging
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import fault
from ..scheduler.generic import GenericScheduler
from ..utils import knobs, telemetry, tracing
from ..utils.platform import ensure_compile_cache
from ..utils.telemetry import NULL_TELEMETRY
from ..scheduler.scheduler import register_scheduler
from ..scheduler.util import AllocTuple, ready_nodes_in_dcs, set_status
from ..structs import structs as s
from . import breaker as breaker_mod
from . import encode, kernels, xfer
from . import resident
from .breaker import HALF_OPEN, KernelIntegrityError

logger = logging.getLogger("nomad_tpu.ops.batch_sched")

# Count of placement passes that ran node-sharded over a Mesh (test /
# telemetry introspection for the multi-slice path).  Since ISSUE 8 the
# mesh path is the fused single-dispatch/single-fetch program
# (parallel/sharded.sharded_fused_pass) — slot-mode AllocMetric scores
# ride the same packed buffer as on the single-chip path, so the old
# mesh_score_gap_passes gauge (ADVICE r5) is gone: no mesh pass can
# drop scores anymore.
MESH_PASSES = 0

# Budget for the commit-ordered slot record on the mesh path ([U, M]
# int32 + optional f32/i32 score rows, replicated per device).  A batch
# whose record would exceed this falls back to the single-chip program
# (which has its own matrix-mode fallback) with a warning — pathological
# shapes degrade, they never mis-place or drop scores vs single-chip.
MESH_SLOT_BUDGET_BYTES = 512 << 20

# Static cluster-tensor cache: (nodes index, attr targets, literals,
# with_networks) → finalized ClusterTensors (see _place_on_device).
# Touch-on-hit LRUs (utils/lru.py): bounded like before, but hot
# entries survive churn and evictions feed the
# batch.program_cache_evictions gauge.
from ..utils import lru as lru_mod
from ..utils.lru import LRU

_CLUSTER_CACHE = LRU(4)

# The constraint vocabulary (attr targets + literals) a fleet's batches
# have used so far, by (store, nodes index, with_networks, pad): every
# batch encodes against the union.  The vocabulary shapes the static
# cluster tensors and so selects the program; a batch that happens to
# lack one of the fleet's usual targets (a drain's 16-evaluation tail
# with no job of one template, 3 attr columns where every batch before
# had 4) would otherwise re-encode the fleet, upload it again and compile
# a program of its own (chip run, PERF.md section 6, PR 29).  A superset
# encodes exactly what the subset does; a change of the nodes table
# starts a new entry.
_VOCABULARY = LRU(4)

# Whether every node's networks are simple enough for the device's port
# accounting (TPUBatchScheduler._cluster_networks_simple), by (store
# lineage, nodes-table raft index).
_NETWORKS_SIMPLE = LRU(4)


def _widen_vocabulary(key, attr_targets, literals):
    targets, lits = _VOCABULARY.get(key, ((), {}))
    targets = tuple(targets) + tuple(
        t for t in attr_targets if t not in targets)
    lits = {t: lits.get(t, frozenset()) | frozenset(literals.get(t, ()))
            for t in (*lits, *literals)}
    _VOCABULARY.put(key, (targets, lits))
    return list(targets), {t: set(vs) for t, vs in lits.items()}

# Device-resident copies of the packed static cluster buffer, keyed by
# CONTENT digest (not store identity): a rebuilt-but-identical cluster —
# e.g. repeated runs on fresh state stores — skips the multi-MB upload
# entirely (link cost per upload: not measured on the current chip).
_DEVICE_STATIC_CACHE = LRU(4)


def validate_device_outputs(spec_list, ct, unplaced_arr, coo_rows,
                            coo_cols, coo_counts) -> Optional[str]:
    """Structural-invariant check on kernel outputs, run before any
    placement is materialized into a plan.  A healthy kernel satisfies
    all of these by construction; a corrupted result (bad HBM, a
    miscompiled shape bucket, an injected ``ops.kernel_result`` fault)
    breaks at least one.  Returns a description of the first violation,
    or None.  Cost: a few O(U + nnz) numpy passes — noise next to the
    device round-trip."""
    n_specs = len(spec_list)
    counts = np.array([sp.count for sp in spec_list], dtype=np.int64)
    up = np.asarray(unplaced_arr[:n_specs], dtype=np.int64)
    if up.shape[0] < n_specs:
        return f"unplaced vector too short ({up.shape[0]} < {n_specs})"
    if (up < 0).any():
        u = int(np.argmax(up < 0))
        return f"negative unplaced count ({int(up[u])}) for spec {u}"
    if (up > counts).any():
        u = int(np.argmax(up > counts))
        return (f"unplaced {int(up[u])} exceeds ask count "
                f"{int(counts[u])} for spec {u}")
    cr = np.asarray(coo_rows, dtype=np.int64)
    cc = np.asarray(coo_cols, dtype=np.int64)
    cv = np.asarray(coo_counts, dtype=np.int64)
    live = (cr >= 0) & (cr < n_specs)
    # A negative node index on a live row would WRAP via Python negative
    # indexing downstream (all_nodes[i] / node_ids[i]) and silently land
    # allocations on a node that never passed feasibility — reject it
    # explicitly instead of letting the placed-sum check infer it.
    if (live & (cc < 0)).any():
        i = int(np.argmax(live & (cc < 0)))
        return (f"negative node index ({int(cc[i])}) in placement "
                f"output for spec {int(cr[i])}")
    valid = live & (cc < ct.n_real)
    if (cv[valid] < 0).any():
        return "negative commit count in placement output"
    placed = np.zeros(n_specs, dtype=np.int64)
    if valid.any():
        np.add.at(placed, cr[valid], cv[valid])
    bad = placed + up != counts
    if bad.any():
        u = int(np.argmax(bad))
        return (f"placed ({int(placed[u])}) + unplaced ({int(up[u])}) != "
                f"asks ({int(counts[u])}) for spec {u}")
    return None


def _corrupt_outputs(rng, spec_list, unplaced_arr, coo_counts):
    """``ops.kernel_result`` corrupt action: seeded, detectable damage to
    the device outputs (the chaos twin of a flaky accelerator).  Returns
    writable, corrupted copies."""
    unplaced_arr = np.array(unplaced_arr)
    coo_counts = np.array(coo_counts)
    u = rng.randrange(len(spec_list))
    mode = rng.randrange(3)
    if mode == 0:
        unplaced_arr[u] = -3
    elif mode == 1:
        unplaced_arr[u] = spec_list[u].count + 5
    elif len(coo_counts):
        i = rng.randrange(len(coo_counts))
        coo_counts[i] = coo_counts[i] + spec_list[u].count + 1
    else:
        unplaced_arr[u] = -1
    return unplaced_arr, coo_counts


class _TouchedNodeIds:
    """Lazy view of the node ids whose usage rows the resident/columnar
    encode touched (row indices into the encode layout).  The only
    consumers are the preemption dispatch gate (``len`` — any live
    allocs at all?) and its candidate enumeration (iteration, paid only
    when preemption actually has unplaced high-priority work) — the old
    per-batch ``{node_ids[i]: True for i in touched}`` comprehension
    materialized a million-entry dict per steady batch at 1M warm
    allocs (ISSUE 14)."""

    __slots__ = ("_node_ids", "_rows")

    def __init__(self, node_ids, rows):
        self._node_ids = node_ids
        self._rows = rows

    def __len__(self) -> int:
        return len(self._rows)

    def __iter__(self):
        ids = self._node_ids
        return (ids[i] for i in self._rows)


class _CollectingScheduler(GenericScheduler):
    """A GenericScheduler whose placement loop *collects* asks instead of
    selecting nodes — everything else (diff, stops, in-place updates,
    rolling limits, blocked evals) is inherited oracle behavior."""

    def __init__(self, logger_, state, planner, batch: bool):
        super().__init__(logger_, state, planner, batch)
        # Placement asks in bulk (columnar) form: per task group,
        # (tg, names-or-count, prev-ids-or-None).  The register fast path
        # stores just the COUNT — names are formulaic '<job>.<tg>[i]'
        # (util.go:22) and get materialized at finalize only for the
        # placements that actually happen; the oracle-diff path keeps
        # explicit name/prev lists.
        self.pending_bulk: List[Tuple] = []
        self.nodes_by_dc: Dict[str, int] = {}
        # Shared per-batch cache of dc-tuple → nodes-by-dc counts, injected
        # by TPUBatchScheduler (one full node scan per distinct dc set per
        # batch instead of per eval).
        self.dc_cache: Optional[Dict[Tuple[str, ...], Dict[str, int]]] = None

    def _set_nodes_by_dc(self) -> None:
        dcs = tuple(self.job.datacenters)
        if self.dc_cache is not None and dcs in self.dc_cache:
            self.nodes_by_dc = self.dc_cache[dcs]
        else:
            _, by_dc = ready_nodes_in_dcs(self.state, self.job.datacenters)
            self.nodes_by_dc = by_dc
            if self.dc_cache is not None:
                self.dc_cache[dcs] = by_dc

    def _compute_job_allocs(self) -> None:
        """Register fast path: a job with NO existing allocations (the
        common high-volume case the batch scheduler exists for) places
        every materialized instance — the diff is the identity
        (util.go:70: existing empty ⇒ all required names → place), so the
        name dict, AllocTuples, taint scan and in-place machinery are all
        skipped.  Anything with history takes the inherited oracle path."""
        job = self.job
        if (job is None or job.stopped() or self.eval.annotate_plan
                or self.state.allocs_by_job(None, self.eval.job_id, True)):
            super()._compute_job_allocs()
            return
        bulk = []
        for tg in job.task_groups:
            if tg.count <= 0:
                continue
            self.queued_allocs[tg.name] = tg.count
            bulk.append((tg, tg.count, None))
        self.pending_bulk = bulk
        if bulk:
            self._set_nodes_by_dc()

    def _compute_placements(self, place: List[AllocTuple]) -> None:
        self._set_nodes_by_dc()
        by_tg: Dict[str, Tuple[s.TaskGroup, List[str], List[Optional[str]]]] = {}
        order: List[Tuple[s.TaskGroup, List[str], List[Optional[str]]]] = []
        for tup in place:
            ent = by_tg.get(tup.task_group.name)
            if ent is None:
                ent = (tup.task_group, [], [])
                by_tg[tup.task_group.name] = ent
                order.append(ent)
            ent[1].append(tup.name)
            ent[2].append(tup.alloc.id if tup.alloc is not None else None)
        self.pending_bulk = [
            (tg, names,
             prevs if any(p is not None for p in prevs) else None)
            for tg, names, prevs in order]


# The contiguous stages of three of a batch's phases, each timed by a
# tracing.Stages: samples ``worker.invoke_scheduler.<phase>.<stage>``
# that sum to the phase's own, and spans ``batch.<phase>.<stage>``.
#
# The device call (live spans, children of ``batch.device``): stage +
# dispatch run in ``_dispatch_device`` / ``_dispatch_mesh``, the other
# three in ``_fetch_device`` (one thread, possibly with another batch's
# prepare in between when the drain is pipelined).
DEVICE_STAGES = ("stage", "dispatch", "wait", "fetch", "decode")
# Encode, up to the dispatch point: the fleet list, the vocabulary and
# the cluster tensors (cache lookup or static encode); the usage rows
# (the resident mirror's acquire, else the usage walk); the specs'
# tensors, the plan and the padding; the sparse job-count,
# distinct_property and dynamic entries; the mirror loan and the two
# host packs.
ENCODE_STAGES = ("nodes", "resident", "specs", "sparse", "pack")
# Expand (span ``batch.metrics``), accumulated over the spec loop: the
# preemption commit; a spec's NodeColumn and unplaced count; its
# ``scores`` dictionary; the failure forensics, their memo, the tail.
EXPAND_STAGES = ("preempt", "slots", "scores", "failures")


# structs.SCORE_MAPS_BUILT as of the last batch that published it.
_score_maps_published = 0
_score_maps_l = threading.Lock()


def _publish_score_maps(metrics) -> None:
    """Counter ``batch.score_maps_built`` by what the process's total
    gained since the last batch's call (as telemetry.publish_gc_pauses:
    a NodeScores is turned into strings on whichever thread reads it;
    0 when nobody did, so the key is always there)."""
    global _score_maps_published
    with _score_maps_l:
        built = s.SCORE_MAPS_BUILT
        gained = built - _score_maps_published
        _score_maps_published = built
    metrics.incr_counter("batch.score_maps_built", gained)


class _PreparedBatch:
    """One batch between prepare and complete: the host-phase outputs
    plus the in-flight device handle (schedule_stream pipelining keeps
    at most one of these between dispatch and complete)."""

    __slots__ = ("evals", "scheds", "specs", "spec_list", "stats", "t0",
                 "c0", "handle", "probe", "routed")

    def __init__(self, evals):
        self.evals = evals
        self.scheds = []
        self.specs = {}
        self.spec_list = []
        self.stats = BatchStats()
        self.t0 = time.perf_counter()
        self.c0 = time.thread_time()    # this thread's CPU clock at t0
        self.handle = None      # _dispatch_device output (device in flight)
        self.probe = False      # this batch is the breaker's half-open probe
        self.routed = False     # breaker-open: already oracle-processed

    def close(self) -> "BatchStats":
        """The batch's last stamp: its wall time and, at the same two
        points, the CPU time of this thread inside it."""
        self.stats.total_seconds = time.perf_counter() - self.t0
        self.stats.cpu_seconds = time.thread_time() - self.c0
        self.stats.num_evals = len(self.evals)
        return self.stats


class TPUBatchScheduler:
    """Factory-registered 'tpu-batch' scheduler.

    process(eval) handles one eval (worker compatibility);
    schedule_batch(evals) is the high-throughput entry the batch worker
    drains the broker into.
    """

    def __init__(self, logger_: logging.Logger, state, planner, mesh=None,
                 preemption_enabled: Optional[bool] = None, breaker=None,
                 metrics=None, snapshot_index: Optional[int] = None):
        self.logger = logger_
        self.state = state
        self.planner = planner
        # Raft applied index captured when ``state`` was snapshotted
        # (worker plumbing): rides the batch.schedule span so residency
        # fence events can be lined up against plan-apply indexes.
        self.snapshot_index = snapshot_index
        self.metrics = metrics if metrics is not None else NULL_TELEMETRY
        # Optional jax.sharding.Mesh: when set, the placement loop runs
        # node-sharded over THIS scheduler's device slice
        # (parallel/sharded.py) — each federated region schedules on its
        # own mesh, the device-level twin of multi-region federation
        # (SURVEY §2.9 last row; reference nomad/rpc.go:263).
        self.mesh = mesh
        if preemption_enabled is None:
            from ..scheduler.preempt import preemption_enabled_default

            preemption_enabled = preemption_enabled_default()
        # Priority-tier preemption (scheduler/preempt.py semantics, batched
        # by ops/preempt.py): when the main placement pass leaves
        # high-priority asks unplaced, a second device pass computes
        # eviction sets over strictly-lower-priority allocs.
        self.preemption_enabled = preemption_enabled
        # Per-batch preemption commits: (job, tg) key → list of
        # (node_id, victim allocs) consumed by _finalize.
        self._preempt_plan: Dict[Tuple[str, str],
                                 List[Tuple[str, List[s.Allocation]]]] = {}
        self._allocs_by_node: Dict[str, List[s.Allocation]] = {}
        # TPU-path circuit breaker (ops/breaker.py): process-wide by
        # default so trips survive the per-batch scheduler construction;
        # tests inject their own instance.
        self.breaker = breaker if breaker is not None else breaker_mod.BREAKER
        ensure_compile_cache()

    # -- single-eval compatibility ----------------------------------------

    def process(self, ev: s.Evaluation) -> None:
        self.schedule_batch([ev])

    # -- batch path --------------------------------------------------------

    def schedule_batch(self, evals: List[s.Evaluation]) -> "BatchStats":
        """Run the host phase for every eval, one device placement pass for
        all of them, then finalize plans/statuses per eval.  Wraps the
        batch in a `batch.schedule` span and bridges the resulting
        BatchStats into telemetry (the nomad.worker.invoke_scheduler.*
        family + breaker counters) so the repr is no longer the only
        artifact of a batch."""
        tr = tracing.TRACER
        if tr is None:
            stats = self._schedule_batch(evals)
        else:
            with tr.span("batch.schedule", annotate=True,
                         num_evals=len(evals),
                         **tracing.eval_id_attrs(evals, len(evals))) as sp:
                stats = self._schedule_batch(evals)
                sp.set(num_specs=stats.num_specs, num_asks=stats.num_asks,
                       breaker_state=stats.breaker_state,
                       oracle_routed=stats.oracle_routed,
                       resident_hits=stats.resident_hits,
                       delta_rows=stats.delta_rows,
                       h2d_bytes=stats.h2d_bytes,
                       delta_apply_s=round(stats.delta_apply_seconds, 6),
                       cpu_ms=round(stats.cpu_seconds * 1000.0, 4))
                if self.snapshot_index is not None:
                    sp.set(snapshot_index=self.snapshot_index)
        self._emit_batch_stats(stats)
        return stats

    def _emit_batch_stats(self, stats: "BatchStats") -> None:
        m = self.metrics
        # All timing samples in milliseconds, like every measure_since
        # sibling in the family (DEFAULT_BUCKETS is ms-calibrated).
        m.add_sample("worker.invoke_scheduler",
                     stats.total_seconds * 1000.0)
        m.add_sample("worker.invoke_scheduler.cpu",
                     stats.cpu_seconds * 1000.0)
        m.add_sample("worker.invoke_scheduler.prepare",
                     stats.prepare_seconds * 1000.0)
        # The collector's pauses since the worker's last batch, on
        # whichever thread they fell (0.0 when nothing was collected).
        telemetry.publish_gc_pauses(m)
        _publish_score_maps(m)
        # Device-path phases only when the kernel actually ran: oracle-
        # routed or ask-less batches would otherwise flood the percentile
        # windows with zeros exactly when the device path is degraded.
        if stats.device_ran:
            m.add_sample("worker.invoke_scheduler.encode",
                         stats.encode_seconds * 1000.0)
            m.add_sample("worker.invoke_scheduler.device",
                         stats.device_seconds * 1000.0)
            m.add_sample("worker.invoke_scheduler.expand",
                         stats.metrics_seconds * 1000.0)
            # Each of the three split into its contiguous stages, which
            # sum to it; every stage on every batch, 0 where it did not
            # run.
            for phase, names, seconds in (
                    ("encode", ENCODE_STAGES, stats.encode_stage_seconds),
                    ("device", DEVICE_STAGES, stats.device_stage_seconds),
                    ("expand", EXPAND_STAGES, stats.expand_stage_seconds)):
                for name in names:
                    m.add_sample(
                        f"worker.invoke_scheduler.{phase}.{name}",
                        seconds.get(name, 0.0) * 1000.0)
            # Inside encode.specs: the host-evaluated rows.
            m.add_sample("worker.invoke_scheduler.encode.constraint_rows",
                         stats.constraint_rows_seconds * 1000.0)
            m.add_sample("worker.invoke_scheduler.rounds", stats.rounds)
            # Published on every batch, 0 where nothing applies, so that
            # a metric over them reads 0 and not nothing.
            m.incr_counter("batch.precomp_rows", stats.precomp_rows)
            m.incr_counter("batch.constraint_row_reuse",
                           stats.constraint_row_reuse)
            m.incr_counter("batch.dp_specs", stats.dp_specs)
            m.incr_counter("batch.score_columns", stats.score_columns)
            m.incr_counter("batch.dp_dense_specs", stats.dp_dense_specs)
            m.incr_counter("batch.multi_round_specs",
                           stats.multi_round_specs)
            m.incr_counter("batch.spec_passes", stats.spec_passes)
            m.incr_counter("batch.net_usage_walks", stats.net_usage_walks)
            m.incr_counter("batch.net_delta_words", stats.net_delta_words)
            m.incr_counter("batch.port_columns", stats.port_columns)
            # Bytes are a COUNTER (rate-derivable total), not a sample:
            # the percentile histogram's buckets are ms-calibrated and
            # would quantize MB-scale values into the top bucket.
            m.incr_counter("batch.fetch_bytes", stats.fetch_bytes)
            # Host→device transfer accounting (ISSUE 14): split
            # single-chip vs mesh so the sharded-mirror win is
            # observable in /v1/metrics.
            m.incr_counter("batch.mesh_h2d_bytes" if stats.mesh_shards
                           else "batch.h2d_bytes", stats.h2d_bytes)
            if stats.delta_apply_seconds:
                m.add_sample(
                    "batch.mesh_delta_apply" if stats.mesh_shards
                    else "batch.delta_apply",
                    stats.delta_apply_seconds * 1000.0)
            if stats.fused:
                m.incr_counter("batch.fused", stats.fused)
            if stats.quantized:
                m.incr_counter("batch.quantized", stats.quantized)
        if not stats.oracle_routed:
            m.add_sample("worker.invoke_scheduler.finalize",
                         stats.finalize_seconds * 1000.0)
            # finalize per stage, each summed over the batch's evals
            # (they sum to .finalize less the loop's own overhead).
            m.add_sample("worker.invoke_scheduler.finalize.build",
                         stats.finalize_build_seconds * 1000.0)
            m.add_sample("worker.invoke_scheduler.finalize.submit",
                         stats.finalize_submit_seconds * 1000.0)
            m.add_sample("worker.invoke_scheduler.finalize.status",
                         stats.finalize_status_seconds * 1000.0)
            # Inside build: the network offers.
            m.add_sample("worker.invoke_scheduler.finalize.offers",
                         stats.finalize_offers_seconds * 1000.0)
            m.incr_counter("batch.net_offer_failures",
                           stats.net_offer_failures)
            m.incr_counter("batch.net_slab_rows", stats.net_slab_rows)
        m.add_sample("worker.invoke_scheduler.asks", stats.num_asks)
        # Residency counters: per-batch samples plus the process-lifetime
        # gauges (ops/resident.py module counters).
        if stats.resident_hits:
            m.incr_counter("batch.resident_hits", stats.resident_hits)
            m.add_sample("batch.delta_rows", stats.delta_rows)
        if stats.full_reencodes:
            m.incr_counter("batch.full_reencodes", stats.full_reencodes)
        if stats.staleness_fences:
            m.incr_counter("batch.staleness_fences", stats.staleness_fences)
        if stats.pipeline_overlap_s:
            m.add_sample("batch.pipeline_overlap",
                         stats.pipeline_overlap_s * 1000.0)
        if resident.GUARD_MISMATCHES:
            m.set_gauge("batch.resident_guard_mismatches",
                        resident.GUARD_MISMATCHES)
        if resident.DEV_GUARD_MISMATCHES:
            m.set_gauge("batch.resident_dev_mismatches",
                        resident.DEV_GUARD_MISMATCHES)
        if resident.DEV_APPLIES:
            m.set_gauge("batch.resident_dev_applies", resident.DEV_APPLIES)
        # Compile-cache audit (ISSUE 13): distinct placement-program
        # signatures seen process-wide — an upper bound on XLA compiles
        # (tests/test_fused.py asserts a ceiling over a steady stream).
        m.set_gauge("batch.compiles", kernels.compile_signatures())
        # Compiled-program / device-buffer cache recycling (ISSUE 14
        # satellite): nonzero churn at steady state means the LRU caps
        # are too small for the workload's shape diversity.
        if lru_mod.EVICTIONS:
            m.set_gauge("batch.program_cache_evictions",
                        lru_mod.EVICTIONS)
        if stats.mesh_shards:
            m.incr_counter("batch.mesh_passes", 1)
            m.set_gauge("batch.mesh_shards", stats.mesh_shards)
        m.set_gauge("breaker.trips", self.breaker.trips)
        # Live breaker, not stats.breaker_state: batches that never reach
        # the breaker gate (empty spec_list) leave stats at the "closed"
        # default and must not report healthy while the breaker is open.
        m.set_gauge("breaker.state",
                    breaker_mod.STATE_CODE.get(self.breaker.state, 0))
        if stats.oracle_routed:
            m.incr_counter("breaker.oracle_routed", stats.oracle_routed)
        if stats.kernel_rejects:
            m.incr_counter("breaker.kernel_rejects", stats.kernel_rejects)

    def _schedule_batch(self, evals: List[s.Evaluation]) -> "BatchStats":
        """Serial path: prepare → dispatch → complete in one call.  The
        double-buffered schedule_stream() drives the same three phases
        with batch k+1's prepare overlapping batch k's device pass."""
        prep = self._prepare_batch(evals)
        self._dispatch_prepared(prep)
        return self._complete_prepared(prep)

    # -- pipelined batch API -----------------------------------------------

    def schedule_stream(self, batches, state_source=None) -> List["BatchStats"]:
        """Async double-buffered pipeline over a stream of eval batches:
        batch k's device pass is dispatched without blocking (JAX async
        dispatch), batch k+1's host reconciliation/spec phases run while
        k computes, then k is fetched + finalized before k+1's usage
        delta is built and dispatched — so the delta feed always reflects
        k's applied plans (no optimistic usage).

        ``state_source`` (callable → state snapshot) is re-invoked before
        each prepare and again before each dispatch, so the dispatch-time
        encode sees every plan the previous batch applied.  Instance
        bookkeeping (_preempt_plan, _allocs_by_node) is per-batch-in-
        flight: the prepare(k+1) → complete(k) → dispatch(k+1) ordering
        keeps at most one batch between dispatch and complete.

        Exceptions propagate after the in-flight batch is completed;
        callers that need per-batch nack semantics (the BatchWorker)
        drive _prepare_batch/_dispatch_prepared/_complete_prepared
        directly.

        Accounting note: a pipelined batch's ``total_seconds`` is its
        wall-clock LATENCY (prepare → finalize), which includes the
        neighbor batches' host phases interleaved on this thread — the
        per-batch samples measure what an eval experiences, and their
        sum exceeds the stream's wall time by design.  Throughput claims
        come from the stream's own elapsed time, never from summing
        batch totals."""
        out: List[BatchStats] = []
        pending = None
        try:
            for evals in batches:
                if state_source is not None:
                    self.state = state_source()
                t_prep = time.perf_counter()
                prep = self._prepare_batch(evals)
                overlap = (time.perf_counter() - t_prep
                           if pending is not None else 0.0)
                if pending is not None:
                    out.append(self._finish_stream(pending))
                    pending = None
                if state_source is not None:
                    self.state = state_source()
                prep.stats.pipeline_overlap_s = overlap
                self._dispatch_prepared(prep)
                pending = prep
        except BaseException:
            # A later batch's prepare/dispatch failing must not strand
            # the dispatched in-flight batch: its device results would
            # never be fetched, its plans never submitted, and a
            # half-open probe it carries never resolved.
            if pending is not None:
                try:
                    out.append(self._finish_stream(pending))
                except Exception:
                    self.logger.exception(
                        "in-flight batch failed during stream unwind")
            raise
        if pending is not None:
            out.append(self._finish_stream(pending))
        return out

    def _finish_stream(self, prep) -> "BatchStats":
        stats = self._complete_prepared(prep)
        tr = tracing.TRACER
        if tr is not None:
            tr.record("batch.schedule", prep.t0, time.perf_counter(),
                      num_evals=stats.num_evals, num_specs=stats.num_specs,
                      resident_hits=stats.resident_hits,
                      pipeline_overlap_s=round(stats.pipeline_overlap_s, 4),
                      cpu_ms=round(stats.cpu_seconds * 1000.0, 4),
                      **tracing.eval_id_attrs(prep.evals, len(prep.evals)))
        self._emit_batch_stats(stats)
        return stats

    def _prepare_batch(self, evals: List[s.Evaluation]) -> "_PreparedBatch":
        """Stage 1: host reconciliation per eval and the dedup of their
        asks into specs (phase 1 and 2), timed as one: ``prepare_seconds``
        from ``prep.t0``, and a ``batch.prepare`` span of the same two
        stamps, parent of batch.phase1/2, when the tracer is armed."""
        prep = _PreparedBatch(evals)
        tr = tracing.TRACER
        if tr is None:
            self._fill_prepared(prep, evals)
            t_end = time.perf_counter()
        else:
            with tr.span("batch.prepare", annotate=True, start=prep.t0,
                         num_evals=len(evals)) as sp:
                self._fill_prepared(prep, evals)
            t_end = sp.end
        prep.stats.prepare_seconds = t_end - prep.t0
        return prep

    def _fill_prepared(self, prep: "_PreparedBatch",
                       evals: List[s.Evaluation]) -> None:
        stats = prep.stats

        # Phase 1: host reconciliation per eval (shared oracle code).
        t_phase1 = time.perf_counter()
        dc_cache: Dict[Tuple[str, ...], Dict[str, int]] = {}
        scheds: List[Tuple[s.Evaluation, _CollectingScheduler]] = []
        for ev in evals:
            sched = _CollectingScheduler(
                self.logger, self.state, self.planner,
                batch=(ev.type == s.JOB_TYPE_BATCH))
            sched.dc_cache = dc_cache
            sched.eval = ev
            sched.job = self.state.job_by_id(None, ev.job_id)
            sched.plan = ev.make_plan(sched.job)
            from ..scheduler.context import EvalContext

            sched.ctx = EvalContext(self.state, sched.plan, self.logger)
            from ..scheduler.stack import GenericStack

            sched.stack = GenericStack(sched.batch, sched.ctx)
            if sched.job is not None and not sched.job.stopped():
                sched.stack.set_job(sched.job)
            sched._compute_job_allocs()
            scheds.append((ev, sched))
        stats.phase1_seconds = time.perf_counter() - t_phase1
        tr = tracing.TRACER
        if tr is not None:
            tr.record("batch.phase1", t_phase1,
                      t_phase1 + stats.phase1_seconds,
                      num_evals=len(evals))
        t_phase2 = time.perf_counter()

        # Phase 2: dedup placement asks into specs.
        specs: Dict[Tuple[str, str], encode.PlacementSpec] = {}
        spec_evs: Dict[Tuple[str, str], s.Evaluation] = {}
        for ev, sched in scheds:
            for tg, names_or_count, prevs in sched.pending_bulk:
                key = (sched.job.id, tg.name)
                spec = specs.get(key)
                if spec is None:
                    spec = encode.build_spec(sched.job, tg, sched.batch)
                    if spec.dp_target is not None:
                        spec.dp_used_values = self._dp_used_values(sched, spec)
                    specs[key] = spec
                    spec_evs[key] = ev
                spec.count += (names_or_count if isinstance(names_or_count, int)
                               else len(names_or_count))

        # Gate: specs the device path cannot express route their whole
        # eval through the oracle instead of being silently mis-placed
        # (VERDICT r1 missing #5 — network/distinct_property fidelity).
        oracle_eval_ids = self._gate_oracle_evals(specs, spec_evs)
        if oracle_eval_ids:
            for key in [k for k, ev in spec_evs.items()
                        if ev.id in oracle_eval_ids]:
                del specs[key]
            kept = []
            for ev, sched in scheds:
                if ev.id in oracle_eval_ids:
                    self.logger.info(
                        "batch: eval %s routed through oracle", ev.id)
                    self._route_through_oracle([(ev, sched)])
                else:
                    kept.append((ev, sched))
            scheds = kept
            evals = [ev for ev, _ in scheds]

        spec_list = sorted(specs.values(), key=lambda sp: -sp.priority)
        stats.num_specs = len(spec_list)
        stats.num_asks = sum(sp.count for sp in spec_list)
        stats.phase2_seconds = time.perf_counter() - t_phase2
        if tr is not None:
            tr.record("batch.phase2", t_phase2,
                      t_phase2 + stats.phase2_seconds,
                      num_specs=stats.num_specs, num_asks=stats.num_asks)

        prep.evals = evals
        prep.scheds = scheds
        prep.specs = specs
        prep.spec_list = spec_list

    def _dispatch_prepared(self, prep: "_PreparedBatch") -> None:
        """Stage 2: breaker gate + encode/delta-build + async device
        dispatch.  On return the device pass is in flight (or the batch
        was routed to the oracle / has no asks); nothing has blocked on
        device results yet."""
        stats = prep.stats
        self._preempt_plan = {}
        if not prep.spec_list:
            return

        # Circuit breaker gate: while OPEN every eval takes the CPU
        # oracle (correct, slower); HALF-OPEN lets this one batch
        # probe the device path and its verdict resolves the probe.
        if not self.breaker.allow_kernel():
            stats.breaker_state = self.breaker.state
            stats.oracle_routed = len(prep.scheds)
            self.logger.info(
                "batch: kernel breaker %s; routing %d evals through "
                "the CPU oracle", stats.breaker_state, len(prep.scheds))
            tracing.event("batch.oracle_routed", reason="breaker_open",
                          breaker_state=stats.breaker_state,
                          num_evals=len(prep.scheds))
            self._route_through_oracle(prep.scheds)
            prep.routed = True
            return
        prep.probe = self.breaker.state == HALF_OPEN
        try:
            prep.handle = self._dispatch_device(prep.spec_list)
        except Exception:
            # A host-side encode/upload error must still feed the
            # breaker and resolve an outstanding probe before
            # propagating (the worker nacks the batch).
            self.breaker.record(False)
            if prep.probe:
                self.breaker.on_probe(False)
            raise

    def _complete_prepared(self, prep: "_PreparedBatch") -> "BatchStats":
        """Stage 3: blocking fetch of the device results, breaker
        bookkeeping, and per-eval plan finalize/submit."""
        stats = prep.stats
        scheds = prep.scheds
        tr = tracing.TRACER

        if prep.routed:
            return prep.close()

        # Per-spec flat slot lists (node id per placement), expanded on
        # the numpy side in _fetch_device.
        expanded: Dict[Tuple[str, str], List[str]] = {}
        unplaced: Dict[Tuple[str, str], int] = {}
        per_spec_metrics: Dict[Tuple[str, str], s.AllocMetric] = {}

        if prep.handle is not None:
            probe = prep.probe
            try:
                expanded, unplaced, per_spec_metrics, kstats = \
                    self._fetch_device(prep.handle)
            except KernelIntegrityError as e:
                # Corrupt kernel output: reject the whole device result,
                # feed the breaker, and degrade this batch to the oracle
                # — scheduling continues, nothing mis-places.
                self.breaker.record(False)
                if probe:
                    self.breaker.on_probe(False)
                self.logger.error(
                    "batch: kernel output rejected (%s); routing %d evals "
                    "through the CPU oracle", e, len(scheds))
                stats.kernel_rejects = 1
                stats.oracle_routed = len(scheds)
                stats.breaker_state = self.breaker.state
                # The encode DID run (and may have consumed/advanced the
                # resident mirror) — the degraded batch must still report
                # its residency truthfully.
                self._apply_resident_stats(
                    stats, prep.handle.get("resident") or {})
                tracing.event("batch.oracle_routed", reason="kernel_reject",
                              breaker_state=stats.breaker_state,
                              num_evals=len(scheds), detail=str(e))
                self._route_through_oracle(scheds)
                return prep.close()
            except Exception:
                # A raw device error (OOM, XLA failure — what a genuinely
                # flaky accelerator throws) keeps its existing propagate-
                # to-worker/nack semantics, but must still feed the
                # breaker and resolve an outstanding probe — otherwise a
                # probe batch dying here wedges the breaker half-open.
                self.breaker.record(False)
                if probe:
                    self.breaker.on_probe(False)
                raise
            # Validation passed ⇒ one clean check; every preemption
            # kernel-vs-oracle comparison feeds the same window.
            self.breaker.record(True)
            agree = kstats.get("preempt_agree", 0)
            disagree = kstats.get("preempt_checked", 0) - agree
            if agree:
                self.breaker.record(True, n=agree)
            if disagree:
                self.breaker.record(False, n=disagree)
            if probe:
                self.breaker.on_probe(disagree == 0)
            stats.breaker_state = self.breaker.state
            stats.device_ran = True
            stats.device_seconds = kstats["device_seconds"]
            stats.encode_seconds = kstats["encode_seconds"]
            stats.metrics_seconds = kstats["metrics_seconds"]
            stats.device_stage_seconds = kstats["stage_seconds"]
            stats.encode_stage_seconds = kstats["encode_stage_seconds"]
            stats.expand_stage_seconds = kstats["expand_stage_seconds"]
            stats.rounds = kstats["rounds"]
            stats.spec_passes = kstats.get("spec_passes", 0)
            stats.multi_round_specs = kstats.get("multi_round_specs", 0)
            stats.precomp_rows = kstats["precomp_rows"]
            stats.constraint_rows_seconds = kstats["constraint_rows_seconds"]
            stats.constraint_row_reuse = kstats["constraint_row_reuse"]
            stats.dp_specs = kstats["dp_specs"]
            stats.score_columns = kstats["score_columns"]
            stats.dp_dense_specs = kstats["dp_dense_specs"]
            stats.commit_seconds = kstats.get("commit_seconds", 0.0)
            stats.dispatch_seconds = kstats.get("dispatch_seconds", 0.0)
            stats.fetch_seconds = kstats.get("fetch_seconds", 0.0)
            stats.fetch_bytes = kstats.get("fetch_bytes", 0)
            stats.fused = kstats.get("fused", 0)
            stats.quantized = kstats.get("quantized", 0)
            stats.mesh_shards = kstats.get("mesh_shards", 0)
            stats.h2d_bytes = kstats.get("h2d_bytes", 0)
            stats.preempt_placed = kstats.get("preempt_placed", 0)
            stats.preempt_evicted = kstats.get("preempt_evicted", 0)
            stats.preempt_checked = kstats.get("preempt_checked", 0)
            stats.preempt_agree = kstats.get("preempt_agree", 0)
            self._apply_resident_stats(stats, kstats.get("resident") or {})

        # Phase 3: materialize allocs into each eval's plan, submit the
        # batch's plans together, settle each eval from its own result.
        t_final = time.perf_counter()
        self._finalize(scheds, prep.specs, expanded, per_spec_metrics, stats)
        stats.finalize_seconds = time.perf_counter() - t_final
        if tr is not None:
            tr.record("batch.finalize", t_final,
                      t_final + stats.finalize_seconds)

        return prep.close()

    @staticmethod
    def _apply_resident_stats(stats: "BatchStats", res_info: Dict) -> None:
        stats.resident_hits = 1 if res_info.get("resident_hit") else 0
        stats.delta_rows = res_info.get("delta_rows", 0)
        stats.full_reencodes = 1 if res_info.get("full_reencode") else 0
        stats.staleness_fences = 1 if res_info.get("fence") else 0
        stats.delta_apply_seconds = res_info.get("delta_apply_s", 0.0)
        stats.net_usage_walks = res_info.get("net_walks", 0)
        stats.net_delta_words = res_info.get("net_delta_words", 0)
        stats.port_columns = res_info.get("port_columns", 0)

    def _route_through_oracle(self, scheds) -> None:
        """Degraded path: process each eval with the CPU GenericScheduler
        against live state — identical semantics to the per-eval gate
        fallback, used when the breaker is open or a kernel result was
        rejected."""
        tr = tracing.TRACER
        for ev, _sched in scheds:
            oracle = GenericScheduler(
                self.logger, self.state, self.planner,
                batch=(ev.type == s.JOB_TYPE_BATCH),
                preemption_enabled=self.preemption_enabled)
            if tr is None:
                oracle.process(ev)
            else:
                with tr.span("oracle.process", eval_id=ev.id):
                    oracle.process(ev)

    # -- gating + distinct_property context --------------------------------

    def _gate_oracle_evals(self, specs, spec_evs) -> set:
        """Eval IDs whose specs the device kernel cannot express."""
        out = set()
        simple_networks: Optional[bool] = None
        for key, sp in specs.items():
            reason = sp.needs_oracle
            if not reason and sp.net_active:
                if simple_networks is None:
                    simple_networks = self._cluster_networks_simple()
                if not simple_networks:
                    reason = "multi-device/multi-IP node networks"
            if reason:
                out.add(spec_evs[key].id)
        return out

    def _cluster_networks_simple(self) -> bool:
        """Device port accounting assumes ≤1 network device per node with a
        single-IP CIDR (the common fingerprinted shape); anything richer
        keeps the oracle's per-IP iteration (network.go:245).  Read once
        per nodes table: kept by (store lineage, nodes-table raft index),
        which a node's registration or update moves."""
        table_index = getattr(self.state, "table_index", None)
        key = (getattr(self.state, "store_uid", None),
               table_index("nodes") if table_index is not None else None)
        simple = (_NETWORKS_SIMPLE.get(key)
                  if None not in key else None)
        if simple is None:
            simple = self._walk_networks_simple()
            if None not in key:
                _NETWORKS_SIMPLE.put(key, simple)
        return simple

    def _walk_networks_simple(self) -> bool:
        import ipaddress
        for node in self.state.nodes(None):
            nets = [nr for nr in (node.resources.networks or []) if nr.device]
            if len(nets) > 1:
                return False
            if nets and nets[0].cidr:
                try:
                    if ipaddress.ip_network(
                            nets[0].cidr, strict=False).num_addresses > 1:
                        return False
                except ValueError:
                    return False
        return True

    def _dp_used_values(self, sched, spec) -> set:
        """Existing + proposed − cleared property values for the spec's
        distinct_property constraint (propertyset.go:57 semantics), taken
        from state and this eval's plan after reconciliation."""
        from ..scheduler.propertyset import PropertySet

        con = next(c for c in spec.constraints
                   if c.operand == s.CONSTRAINT_DISTINCT_PROPERTY)
        ps = PropertySet(sched.ctx, spec.job)
        if con in spec.job.constraints:
            ps.set_job_constraint(con)
        else:
            ps.set_tg_constraint(con, spec.tg.name)
        ps.populate_proposed()
        return ((ps.existing_values | ps.proposed_values)
                - ps.cleared_values)

    # -- device pass -------------------------------------------------------

    def _place_on_device(self, spec_list: List[encode.PlacementSpec]):
        return self._fetch_device(self._dispatch_device(spec_list))

    def _natural_plan(self, spec_list, ct, st, mesh: Optional[bool] = None
                      ) -> Tuple[int, int, int, int]:
        """(u_pad, slot_m, max_nnz, host rows or not) of this batch: the
        canonical bucketing (encode.shape_plan, ISSUE 13 compile-cache
        audit: one bucketing for the single-chip and the mesh path) at
        ``st``'s spec pad, for the mesh's slot budget when the batch goes
        to the mesh, and whether encode built a host-row matrix."""
        if mesh is None:
            mesh = self.mesh is not None
        _, slot_m, max_nnz = encode.shape_plan(
            st.u_pad, ct.n_pad, ct.n_real,
            max((sp.count for sp in spec_list), default=1),
            int(sum(sp.count for sp in spec_list)), mesh=mesh,
            **({"slot_budget_bytes": MESH_SLOT_BUDGET_BYTES} if mesh
               else {}))
        return st.u_pad, slot_m, max_nnz, int(st.precomp.shape != (1, 1))

    def _live_allocs_by_node(self) -> Dict[str, List[s.Allocation]]:
        """Full state walk: every live alloc row grouped by node — the
        reference usage basis (and the resident cache's rebuild/guard
        input)."""
        allocs_by_node: Dict[str, List[s.Allocation]] = defaultdict(list)
        alloc_rows = getattr(self.state, "alloc_rows", None)
        if alloc_rows is not None:
            for node_id, row in alloc_rows(None):
                if not row.terminal_status():
                    allocs_by_node[node_id].append(row)
        else:  # non-StateStore State implementations (test doubles)
            for alloc in self.state.allocs(None):
                if not alloc.terminal_status():
                    allocs_by_node[alloc.node_id].append(alloc)
        return allocs_by_node

    def _columnar_usage(self, base):
        """Live usage rows sliced from the store's columnar mirror
        (state/columnar.py): base reserved-only usage + the
        fold-on-read usage matrix — O(changed allocs) instead of the
        full alloc-row walk.  Returns ``(used int64 [n_pad, 4],
        touched_rows set)`` or None when the mirror is unavailable
        (disabled, invalidated, network batch, or a non-StateStore
        double).  Every ``NOMAD_TPU_COLUMNAR_GUARD_EVERY`` reads the
        object walk runs anyway and must match bit-for-bit — a mismatch
        feeds the breaker, bumps the columnar epoch, and this batch
        proceeds on the walk's rows."""
        from ..state import columnar as colmod

        if getattr(base, "_with_networks", False):
            return None
        columns_fn = getattr(self.state, "columns", None)
        if columns_fn is None:
            return None
        cols = columns_fn()
        if cols is None or cols.n != base.n_real:
            return None
        usage = self.state.column_usage(cols)[:cols.n]
        used = np.asarray(base.used, dtype=np.int64).copy()
        used[:cols.n] += usage
        touched = set(np.nonzero(usage.any(axis=1))[0].tolist())
        colmod.USAGE_READS += 1
        every = colmod.guard_every()
        if every > 0 and colmod.USAGE_READS % every == 0:
            colmod.USAGE_GUARD_RUNS += 1
            ref_used, ref_touched = resident._full_usage(
                base, self._live_allocs_by_node)
            if not np.array_equal(used, ref_used):
                bad = int((used != ref_used).any(axis=1).sum())
                colmod.note_guard_mismatch("usage", "usage",
                                           breaker=self.breaker, Rows=bad)
                return ref_used, set(ref_touched)
            if self.breaker is not None:
                self.breaker.record(True)
            # The walk's touched set is authoritative: it also covers
            # nodes whose live allocs net to zero usage.
            return used, set(ref_touched)
        return used, touched

    @staticmethod
    def _asked_ports(spec_list) -> List[int]:
        """The static ports the batch's network specs ask for, sorted:
        the order of their bits."""
        return sorted({p for sp in spec_list if sp.net_active
                       for p in sp.resv_ports})

    def _with_net_usage(self, ct, base, net_used, spec_list, *,
                        port_held: Optional[np.ndarray]):
        """``ct`` with what the fleet's allocations hold on their nodes'
        networks (``net_used``, the resident network mirror's rows) and,
        per node, one bit for each static port the batch's specs ask for,
        set where the port is reserved or held — the only ports a pass
        can collide on (dynamic ones are counted, and picked at
        finalize).  ``port_held``: None when ``ct.port_words`` already
        carries what the allocations hold (the walk built it); otherwise
        ``ct.port_words`` is the nodes' reservations only, and this is
        where the allocations hold each asked port (``[n_pad, ports]``
        bool, from the resident mirror's port columns).  Returns ``(ct,
        port_bits)``: ``{port: bit}`` in the order the bits are laid
        out."""
        import dataclasses as _dc

        ports = self._asked_ports(spec_list)
        port_bits = {p: j for j, p in enumerate(ports)}
        words = encode.pow2_bucket(max(1, -(-len(ports) // 32)), minimum=1)
        bits = np.zeros((ct.n_pad, len(ports)), dtype=bool)
        for p, j in port_bits.items():
            bits[:, j] = (ct.port_words[:, p >> 5] >> np.uint32(p & 31)) & 1
        if port_held is not None:
            bits |= port_held
        port_words = np.zeros((ct.n_pad, words), dtype=np.uint32)
        for j in range(len(ports)):
            port_words[:, j >> 5] |= bits[:, j].astype(np.uint32) << np.uint32(
                j & 31)
        new = _dc.replace(
            ct, port_words=port_words,
            bw_used=(base.bw_used + net_used[:, 0]).astype(np.int32),
            dyn_free=(base.dyn_free - net_used[:, 1]).astype(np.int32))
        encode._carry_host_attrs(ct, new)
        return new, port_bits

    def _dispatch_device(self, spec_list: List[encode.PlacementSpec]):
        """Host encode + async device dispatch: everything up to (but
        not including) the blocking fetch.  Returns the in-flight handle
        _fetch_device consumes — the split point the double-buffered
        pipeline overlaps across batches."""
        enc = tracing.Stages("batch.encode.")     # ENCODE_STAGES
        enc.begin("nodes")
        # Host→device transfer accounting (ISSUE 14 satellite): the
        # resident mirror's own uploads (installs + routed delta
        # applies) happen inside acquire/take below; sample the module
        # counter around the dispatch so BatchStats.h2d_bytes carries
        # the whole per-batch H2D picture.
        h2d0 = resident.DEV_H2D_BYTES
        # All DCs across the batch: nodes are encoded once.
        all_nodes = [n for n in self.state.nodes(None)]

        attr_targets, literals = encode.collect_attr_targets(spec_list)
        with_networks = any(sp.net_active for sp in spec_list)
        # The node mesh keeps its own network protocol: per touched node
        # sparse rows over the static port bitmaps (parallel/sharded.py).
        mesh_net = with_networks and self.mesh is not None
        # Node-axis pad multiple: the TPU lane width (128), raised to a
        # common multiple of the mesh size when this scheduler schedules
        # over a Mesh — MISSING-filled pad shards are infeasible by
        # construction (ineligible rows), so the mesh path never falls
        # back to single-chip over divisibility (ISSUE 8 satellite).
        pad_m = self._node_pad_multiple()
        # Static cluster tensors are cached across batches keyed by the
        # nodes-table raft index (+ the constraint vocabulary + the pad
        # geometry): a stable fleet re-encodes nothing; only alloc usage
        # is layered on per batch (SURVEY §2.2 incremental device mirror).
        base = None
        cache_key = None
        table_index = getattr(self.state, "table_index", None)
        store_uid = getattr(self.state, "store_uid", None)
        if table_index is not None and store_uid is not None:
            attr_targets, literals = _widen_vocabulary(
                (store_uid, table_index("nodes"), with_networks, pad_m),
                attr_targets, literals)
            lit_key = tuple(sorted(
                (t, tuple(sorted(vs))) for t, vs in literals.items()))
            # Slot layout (store_uid, nodes_index, ...) is relied on by
            # ops/resident.py's old-nodes-index staleness fence.
            cache_key = (store_uid, table_index("nodes"),
                         tuple(attr_targets), lit_key, with_networks,
                         pad_m)
            base = _CLUSTER_CACHE.get(cache_key)
        if base is None:
            # Columnar path (ISSUE 9): slice the store's numpy mirrors
            # instead of walking a node object per row; differential
            # guard + object-walk fallback live inside.
            base = encode.build_cluster_static(
                self.state, all_nodes, attr_targets, literals,
                with_networks=with_networks, node_pad_multiple=pad_m,
                breaker=self.breaker)
            if cache_key is not None:
                _CLUSTER_CACHE.put(cache_key, base)
        node_index = base._node_index  # type: ignore[attr-defined]

        # Usage rows: device-resident delta path (ops/resident.py) when
        # eligible — O(changed allocs) via the state store's usage-delta
        # feed — otherwise the full O(cluster) walk + layer.
        resident_info: Dict = {}
        net_used = None         # [n_pad, NET_DIMS]: what allocs' networks hold
        use_resident = (resident.enabled() and not mesh_net
                        and cache_key is not None
                        and getattr(self.state, "alloc_log_since", None)
                        is not None)
        if use_resident:
            # The usage mirror depends only on the node set, not the
            # batch's constraint vocabulary — key it by (store lineage,
            # nodes index, pad geometry) so residency survives
            # vocabulary changes; ``shards`` lets the differential
            # guard attribute a mismatch to the owning mesh shard.
            # Feed read, fold and device delta apply, inside encode.
            enc.begin("resident")
            used, touched, resident_info = resident.acquire(
                self.state, cache_key[:2] + (base.n_pad,), base,
                self._live_allocs_by_node, breaker=self.breaker,
                shards=(self.mesh.devices.size
                        if self.mesh is not None else 0),
                usage_fn=lambda: self._columnar_usage(base),
                with_net=with_networks, ports=self._asked_ports(spec_list))
            enc.begin("specs")
            ct = encode.with_usage(base, used)
            net_used = resident_info.pop("net", None)
            port_held = resident_info.pop("port_held", None)
            # The preemption pass only needs WHICH nodes may carry live
            # allocs (it re-materializes candidate rows from state);
            # avoid the full row walk the resident path just saved.
            self._allocs_by_node = _TouchedNodeIds(base.node_ids, touched)
        else:
            enc.begin("resident")       # off the mirror: the usage walk
            port_held = None            # the walk's port words hold all
            cu = (self._columnar_usage(base)
                  if not with_networks else None)
            if cu is not None:
                used, touched_set = cu
                ct = encode.with_usage(base, used)
                self._allocs_by_node = _TouchedNodeIds(base.node_ids,
                                                       touched_set)
                touched = sorted(touched_set)
            else:
                allocs_by_node = self._live_allocs_by_node()
                self._allocs_by_node = allocs_by_node
                ct = (encode.apply_alloc_usage(base, allocs_by_node)
                      if allocs_by_node else base)
                touched = sorted(i for i in (node_index.get(nid)
                                             for nid in allocs_by_node)
                                 if i is not None)
                if with_networks:
                    resident_info["net_walks"] = 1
                    net_used = np.stack(
                        [ct.bw_used - base.bw_used,
                         base.dyn_free - ct.dyn_free], axis=1)
            enc.begin("specs")
        port_bits = None
        port_stamps = []
        if with_networks and not mesh_net:
            t_ports = tracing.now()
            ct, port_bits = self._with_net_usage(
                ct, base, net_used, spec_list, port_held=port_held)
            if port_bits:
                port_stamps.append((t_ports, tracing.now()))
        st = encode.encode_specs(spec_list, ct, all_nodes,
                                 port_bits=port_bits)
        st.port_stamps = port_stamps
        # The batch's shape plan, or the compiled plan of its shape class
        # that covers it (kernels.choose_plan: a drain's tail batch runs
        # the full batches' program instead of compiling its own).
        with_dp = any(sp.dp_target is not None for sp in spec_list)
        u_pad, slot_m, max_nnz, host_rows = kernels.choose_plan(
            (ct.n_pad, with_networks, with_dp, self.mesh is not None),
            self._natural_plan(spec_list, ct, st))
        st = encode.pad_specs(st, u_pad, bool(host_rows), ct.n_pad)

        enc.begin("sparse")
        # Existing per-(job, node) alloc counts for anti-affinity/distinct,
        # uploaded SPARSE and scattered dense on device: the dense U×N
        # matrix is mostly zeros.
        jc_entries: Dict[Tuple[int, int], int] = {}
        rows_by_job = getattr(self.state, "alloc_rows_by_job", None)
        for j, job_id in enumerate(st.job_ids):
            if rows_by_job is not None:
                job_rows = rows_by_job(None, job_id)
            else:
                job_rows = [(a.node_id, a) for a in
                            self.state.allocs_by_job(None, job_id, False)]
            for node_id, row in job_rows:
                if row.terminal_status():
                    continue
                idx = node_index.get(node_id)
                if idx is not None:
                    jc_entries[(j, idx)] = jc_entries.get((j, idx), 0) + 1
        k_jc = encode.pow2_bucket(max(1, len(jc_entries)), minimum=8)
        jc_rows = np.full(k_jc, -1, dtype=np.int32)
        jc_cols = np.zeros(k_jc, dtype=np.int32)
        jc_vals = np.zeros(k_jc, dtype=np.int32)
        for i, ((j, n), v) in enumerate(jc_entries.items()):
            jc_rows[i], jc_cols[i], jc_vals[i] = j, n, v

        # Upload split (ops/kernels.py _device_schedule): the multi-MB static
        # cluster tensors ship once and live on device keyed by content
        # digest; the per-batch dynamic buffer carries only the U-sized
        # spec tensors plus sparse alloc-usage deltas.
        static = {
            "attr": ct.attr_values, "elig": ct.eligible, "dc": ct.dc_code,
            "denom": ct.score_denom,
        }
        # Quantized resource rows (encode.quantize_resource_rows): the
        # two [n_pad, 4] matrices ship int16/int8 + a per-dimension scale
        # codebook when exactly representable — half/quarter the link
        # bytes and device HBM for the resident static mirror.  Memoized
        # on the cached static tensors; the round-trip bound check
        # (resident.check_quant_roundtrip) runs once per static encode
        # and on mismatch the batch falls back to exact int32 rows.
        # quant_enabled() is re-read EVERY batch (the runtime kill-switch
        # convention resident.enabled() follows); only the
        # computed rows are memoized on the cached static tensors.
        quant = None
        if encode.quant_enabled():
            quant = getattr(base, "_quant_rows", False)
            if quant is False:
                quant = encode.quantize_resource_rows(ct.capacity,
                                                      base.used)
                if quant is not None and not self._quant_roundtrip_ok(
                        ct, base, quant):
                    quant = None
                base._quant_rows = quant  # type: ignore[attr-defined]
        if quant is not None:
            static.update(cap_q=quant.cap_q, used_base_q=quant.used_q,
                          res_scale=quant.scale)
        else:
            static.update(cap=ct.capacity.astype(np.int32),
                          used_base=base.used.astype(np.int32))
        if with_networks:
            static.update(bw_cap=ct.bw_cap, bw_used_base=base.bw_used,
                          dyn_free_base=base.dyn_free)
            if mesh_net:
                static.update(port_words_base=base.port_words)

        # Sparse usage deltas over the static reserved-only baseline: one
        # row per node carrying live allocs this batch (``touched`` comes
        # from the resident cache on the delta path, from the full walk
        # otherwise).
        k_u = encode.pow2_bucket(max(1, len(touched)), minimum=8)
        u_rows = np.full(k_u, -1, dtype=np.int32)
        u_vals = np.zeros((k_u, 4), dtype=np.int32)
        if touched:
            tr = np.asarray(touched, dtype=np.int64)
            u_rows[:len(touched)] = tr.astype(np.int32)
            u_vals[:len(touched)] = (ct.used[tr] - base.used[tr]).astype(
                np.int32)

        dyn = {
            "c_attr": st.constraint_attr, "c_op": st.constraint_op,
            "c_rhs": st.constraint_rhs, "dc_mask": st.dc_mask,
            "precomp": st.precomp,
            "ask": st.ask.astype(np.int32), "count": st.count,
            "penalty": st.penalty, "dh": st.distinct_hosts,
            "ji": st.job_index,
            "jc_rows": jc_rows, "jc_cols": jc_cols, "jc_vals": jc_vals,
            "u_rows": u_rows, "u_vals": u_vals,
            # Tie-break jitter seed: random per batch, overridable with
            # NOMAD_TPU_RNG_SEED for deterministic placement reproduction
            # (the differential tests pin placements bit-identical
            # under a fixed seed).
            # raw + explicit int(): a malformed pin must fail LOUDLY
            # at dispatch, not silently fall through to a random seed
            # the operator believes is deterministic.
            "rng_seed": np.array(
                [(int(rng_pin) if (rng_pin := knobs.raw(
                    "NOMAD_TPU_RNG_SEED"))
                  else int.from_bytes(s.generate_uuid()[:8].encode(),
                                      "big")) & 0x7FFFFFFF],
                dtype=np.int32),
        }
        if with_networks:
            dyn.update(net_active=st.net_active, net_mbits=st.net_mbits,
                       dyn_need=st.dyn_need, resv_words=st.resv_words)
        if mesh_net:
            u_bw = np.zeros(k_u, dtype=np.int32)
            u_dyn = np.zeros(k_u, dtype=np.int32)
            u_ports = np.zeros((k_u, ct.port_words.shape[1]),
                               dtype=np.uint32)
            if touched:
                u_bw[:len(touched)] = ct.bw_used[tr] - base.bw_used[tr]
                u_dyn[:len(touched)] = ct.dyn_free[tr] - base.dyn_free[tr]
                u_ports[:len(touched)] = ct.port_words[tr]
            dyn.update(u_bw=u_bw, u_dyn=u_dyn, u_ports=u_ports)
        elif with_networks:
            # What the fleet's allocations hold, whole, unless the
            # resident network mirror's device twin is loaned below; the
            # ports the batch asks for are usage too, and ride here when
            # there are any (else the static pack holds the zero rows).
            dyn.update(bw_used=ct.bw_used, dyn_free=ct.dyn_free)
            (dyn if port_bits else static)["port_words"] = ct.port_words
        if with_dp:
            dyn.update(dp_col=st.dp_col, dp_active=st.dp_active,
                       dp_used=st.dp_used)

        enc.begin("pack")
        if self.mesh is not None:
            # Sharded donated-mirror eligibility (ISSUE 14): when the
            # resident slot matches this batch exactly, _dispatch_mesh
            # loans the node-sharded device mirror into the fused
            # program instead of shipping the replicated u_rows/u_vals
            # delta upload.  The take itself happens inside, AFTER the
            # slot-budget check, so a degraded batch never strands a
            # loan.
            res_key = snap_index = None
            if use_resident:
                res_key = cache_key[:2] + (base.n_pad,)
                snap_index = self.state.table_index("allocs")
            handle = self._dispatch_mesh(
                spec_list, all_nodes, ct, st, static, dyn,
                with_networks=with_networks, with_dp=with_dp,
                quantized=0 if quant is None else 1, enc=enc,
                resident_info=resident_info, res_key=res_key,
                snap_index=snap_index, used_host=used
                if res_key is not None else None, h2d0=h2d0,
                slot_m=slot_m, max_nnz=max_nnz)
            if handle is not None:
                return handle
            # Slot-record budget exceeded (pathological count skew):
            # degrade to the single-chip program below, at its own plan.
            _, slot_m, max_nnz, _ = self._natural_plan(spec_list, ct, st,
                                                       mesh=False)
            if mesh_net:
                # The single-chip protocol, over every port (the specs'
                # reserved ports are numbered as they are).
                del static["port_words_base"]
                del dyn["u_bw"], dyn["u_dyn"], dyn["u_ports"]
                dyn.update(bw_used=ct.bw_used, dyn_free=ct.dyn_free,
                           port_words=ct.port_words)

        # Donated device-resident usage mirror (ISSUE 13): when the
        # resident slot exactly matches this batch's (key, allocs
        # index), the usage matrix is LOANED to the kernel as a donated
        # argument instead of riding the dyn buffer as sparse deltas —
        # the per-batch usage upload disappears and the mirror round-
        # trips in place (the kernel returns the aliased buffer).
        # The mesh path has its own sharded twin of this loan inside
        # _dispatch_mesh (ISSUE 14); this branch is the single-chip
        # layout only.
        used_dev = net_dev = None
        res_key = snap_index = None
        if use_resident and self.mesh is None:
            res_key = cache_key[:2] + (base.n_pad,)
            snap_index = self.state.table_index("allocs")
            used_dev = resident.take_device_used(res_key, snap_index,
                                                 used)
            if used_dev is not None and net_used is not None:
                net_dev = resident.take_device_used(
                    res_key, snap_index, net_used, what="net")
        if used_dev is not None:
            del dyn["u_rows"], dyn["u_vals"]
        if net_dev is not None:
            del dyn["bw_used"], dyn["dyn_free"]
        if with_networks:
            resident_info["net_delta_words"] = resident_info.get(
                "net_delta_words", 0) + sum(
                    dyn[k].size for k in ("bw_used", "dyn_free",
                                          "port_words", "u_bw", "u_dyn",
                                          "u_ports") if k in dyn)

        sbuf, meta_s = xfer.pack_host(static)
        dbuf, meta_d = xfer.pack_host(dyn)
        t1 = enc.end()      # encode ends where the device call begins

        # slot_m and max_nnz are the plan's, chosen above; see
        # encode.shape_plan for the slot-mode and score-carry rules
        # (commit-score side-outputs: [U, M] commit-aligned slot buffers
        # in slot mode, two [U, N] carries otherwise; slot mode builds
        # the COO payload with one U×M pass instead of a nonzero over
        # the U×N matrix).
        with_scores = encode.carries_scores(st.u_pad, ct.n_real)
        stages = tracing.Stages("batch.device.", live=True)
        with stages:
            # stage: content digest of the static pack, the device-side
            # cache of it, and the uploads (static on a miss, dynamic
            # always).
            stages.begin("stage", t1)
            digest = (hashlib.blake2b(sbuf.tobytes(),
                                      digest_size=16).hexdigest(), meta_s)
            static_dev = _DEVICE_STATIC_CACHE.get(digest)
            static_h2d = 0
            if static_dev is None:
                static_dev = jax.device_put(sbuf)
                static_h2d = sbuf.nbytes
            _DEVICE_STATIC_CACHE.put(digest, static_dev)
            dyn_dev = jax.device_put(dbuf)

            # dispatch: the program call until it returns (asynchronous:
            # the host's cost only) and the mirror loan handed back.
            stages.begin("dispatch")
            # Score + commit + compaction as ONE device dispatch
            # emitting ONE packed result buffer, fetched in a single
            # transfer by _fetch_device (the aux overflow source stays
            # device-resident, touched only on window overflow).
            fused_buf, fused_aux, feas, fused_meta, used_out, net_out = \
                kernels.fused_pass(
                    static_dev, dyn_dev, used_dev, net_dev,
                    meta_s=meta_s, meta_d=meta_d, u_pad=st.u_pad,
                    n_pad=ct.n_pad, with_networks=with_networks,
                    with_dp=with_dp, with_scores=with_scores,
                    max_nnz=max_nnz, slot_m=slot_m)
            fused_overflow = ("slots" if slot_m else "coo", fused_aux)
            if used_out is not None:
                # The kernel aliased the donated mirror back out — return
                # the loan so the next batch's delta apply lands in place.
                resident.give_device_used(res_key, snap_index, used_out)
            if net_out is not None:
                resident.give_device_used(res_key, snap_index, net_out,
                                          what="net")
        # Device pass is dispatched (JAX async); the blocking fetch lives
        # in _fetch_device so a pipelining caller can overlap host work.
        return {
            "spec_list": spec_list, "all_nodes": all_nodes, "ct": ct,
            "st": st, "feas": feas,
            "fused_buf": fused_buf, "fused_meta": fused_meta,
            "fused_overflow": fused_overflow,
            "quantized": 0 if quant is None else 1,
            "with_scores": with_scores, "max_nnz": max_nnz,
            "enc": enc, "t1": t1, "stages": stages,
            "resident": resident_info,
            "h2d_bytes": (dbuf.nbytes + static_h2d
                          + (resident.DEV_H2D_BYTES - h2d0)),
        }

    def _quant_roundtrip_ok(self, ct, base, quant) -> bool:
        """Quantized-rows round-trip bound, run once per static encode.
        On a mesh the check runs PER SHARD SLICE — exactly the rows each
        device will dequantize — so a corrupt codebook is attributed to
        its owning shard before anything ships."""
        if self.mesh is None:
            return (resident.check_quant_roundtrip(
                        ct.capacity, quant.cap_q, quant.scale[0],
                        breaker=self.breaker, what="capacity")
                    and resident.check_quant_roundtrip(
                        base.used, quant.used_q, quant.scale[1],
                        breaker=self.breaker, what="used baseline"))
        d = self.mesh.devices.size
        n_l = ct.n_pad // d
        for s_i in range(d):
            sl = slice(s_i * n_l, (s_i + 1) * n_l)
            if not (resident.check_quant_roundtrip(
                        ct.capacity[sl], quant.cap_q[sl], quant.scale[0],
                        breaker=self.breaker,
                        what=f"capacity shard {s_i}")
                    and resident.check_quant_roundtrip(
                        base.used[sl], quant.used_q[sl], quant.scale[1],
                        breaker=self.breaker,
                        what=f"used baseline shard {s_i}")):
                return False
        return True

    def _fetch_device(self, handle):
        """Blocking fetch + decode + shared post-processing of an
        in-flight _dispatch_device / _dispatch_mesh handle."""
        with handle["stages"]:      # closes the stage an error leaves open
            return self._fetch_decode(handle)

    def _fetch_decode(self, handle):
        stages = handle["stages"]
        spec_list = handle["spec_list"]
        all_nodes = handle["all_nodes"]
        ct, st = handle["ct"], handle["st"]
        feas = handle["feas"]
        with_scores = handle["with_scores"]
        max_nnz = handle["max_nnz"]

        # wait: until the result is ready on the device (compute drains
        # here).  The one transfer is queued behind the compute first,
        # exactly as the device_get below would queue it, so waiting for
        # the compute costs the transfer no host round trip.  fetch: the
        # rest of the transfer and the unpack.
        t_disp = stages.begin("wait")
        result_buf = handle["fused_buf"]
        result_buf.copy_to_host_async()
        result_buf.block_until_ready()
        stages.begin("fetch")
        # The WHOLE batch result — summary + COO placement payload +
        # score side-outputs — in ONE device transfer (the "exactly
        # one batch.fetch span per batch" tracing assertion pins it).
        # Only when nnz overflows the payload window (>8MB of
        # placements) does a second fetch of the overflow source
        # run, inside the same span.
        with tracing.span("batch.fetch", annotate=True, fused=1):
            raw = np.asarray(jax.device_get(result_buf))
            fetch_bytes = raw.nbytes
            summary = xfer.unpack_host(raw, handle["fused_meta"])
            nnz = int(summary["scalars"][0])
            coo_win = summary["coo"]
            if nnz <= coo_win.shape[0]:
                coo = coo_win[:nnz]
            else:
                kind, aux = handle["fused_overflow"]
                logger.info(
                    "fused fetch overflow: nnz %d > window %d; one "
                    "extra %s fetch", nnz, coo_win.shape[0], kind)
                nnz_b = min(max_nnz, encode.pow2_bucket(nnz, minimum=8))
                if kind == "coo":
                    ov_coo = aux[:nnz_b]
                else:
                    # Slot mode: dispatch a right-sized slot→COO
                    # gather over the device-resident record and
                    # prefix-fetch it — bytes proportional to the
                    # actual placements, not the [U, M] record.
                    slots_d, sscores_d, scoll_d = aux
                    ov_coo, _ = kernels.slots_to_coo(
                        slots_d, sscores_d, scoll_d, out_rows=nnz_b,
                        with_scores=with_scores,
                        compact_u16=coo_win.dtype == np.uint16)
                coo = np.asarray(jax.device_get(ov_coo))[:nnz]
                fetch_bytes += nnz_b * coo.shape[1] * coo.dtype.itemsize
        # decode: from here to the device_seconds stamp in
        # _finalize_device_outputs (COO split, validation, expansion,
        # the forensics fetch).
        stages.begin("decode")
        # Wall time of the whole score-and-commit dispatch: upload +
        # device compute + the result transfer (t1 marks the post-encode
        # dispatch point in _dispatch_device).  dispatch_seconds is the
        # host-side gap between that point and the start of the blocking
        # fetch — the async-dispatch overhead; device compute itself
        # drains inside the blocking fetch.
        commit_seconds = time.perf_counter() - handle["t1"]
        fetch_seconds = time.perf_counter() - t_disp
        dispatch_seconds = max(0.0, commit_seconds - fetch_seconds)
        scalars = dict(zip(kernels.SCALARS,
                           (int(v) for v in summary["scalars"])))
        rounds = scalars["rounds"]
        unplaced_arr = summary["unplaced"]
        feas_count = summary["feas_count"]
        # Unified COO decode (slot mode arrives as per-alloc COO with
        # counts ≡ 1, built on device from the commit-aligned slot
        # record; matrix mode as per-(spec, node) aggregates).
        coo_rows, coo_cols, coo_counts = coo[:, 0], coo[:, 1], coo[:, 2]
        if with_scores:
            coo_scores = np.ascontiguousarray(coo[:, 3]).view(np.float32)
            coo_coll = coo[:, 4]
        else:
            coo_scores = np.zeros(len(coo), dtype=np.float32)
            coo_coll = np.zeros(len(coo), dtype=np.int32)

        expanded, unplaced, metrics, kstats = self._finalize_device_outputs(
            spec_list, all_nodes, ct, st, feas, unplaced_arr, feas_count,
            coo_rows, coo_cols, coo_counts, coo_scores, coo_coll,
            rounds, with_scores, handle["enc"], handle["t1"], stages)
        kstats["spec_passes"] = scalars["spec_passes"]
        kstats["multi_round_specs"] = scalars["multi_round_specs"]
        kstats["commit_seconds"] = commit_seconds
        kstats["dispatch_seconds"] = dispatch_seconds
        kstats["fetch_seconds"] = (fetch_seconds
                                   + kstats.get("fetch_seconds", 0.0))
        kstats["fetch_bytes"] = fetch_bytes + kstats.get("fetch_bytes", 0)
        kstats["fused"] = 1
        kstats["quantized"] = handle.get("quantized", 0)
        kstats["mesh_shards"] = handle.get("mesh_shards", 0)
        kstats["h2d_bytes"] = handle.get("h2d_bytes", 0)
        kstats["resident"] = handle.get("resident") or {}
        return expanded, unplaced, metrics, kstats

    def _node_pad_multiple(self) -> int:
        """Node-axis pad multiple: 128 (TPU lane width), raised to the
        least common multiple with the mesh size so a mesh scheduler's
        shards always divide evenly (satellite: no silent single-chip
        fallback on divisibility — pad rows are ineligible, hence
        infeasible by construction)."""
        import math

        if self.mesh is None:
            return 128
        d = self.mesh.devices.size
        return 128 * d // math.gcd(128, d)

    def _dispatch_mesh(self, spec_list, all_nodes, ct, st, static, dyn,
                       *, with_networks, with_dp, quantized, enc,
                       resident_info, res_key=None, snap_index=None,
                       used_host=None, h2d0=0, slot_m=0, max_nnz=0):
        """Node-sharded twin of the fused dispatch: the SAME static/dyn
        tensor dicts, but the static pack is split into per-shard
        buffers placed on their owning device (NamedSharding over the
        node axis — a 1M-node cluster never materializes unsharded on
        any device), and the whole batch result — summary, COO
        placements, slot-mode AllocMetric scores — comes back as the
        same single packed buffer `_fetch_device` already decodes.  One
        dispatch, one fetch, per batch; bit-identical placements and
        scores to the single-chip program (k_cand ≥ max count ⇒ the
        per-round global top-k lies inside the gathered local top-k
        candidates — see parallel/sharded.py).

        Usage state (ISSUE 14): when ``res_key`` identifies a matching
        resident slot, the node-sharded donated usage mirror is LOANED
        into the fused program (one [n_local, 4] donated buffer per
        shard, returned aliased and handed back) — the replicated
        per-batch u_rows/u_vals upload and the on-device global→local
        row remap both disappear.  Otherwise the sparse deltas ship in
        the dyn buffer and the kernel scatter-adds them onto the owning
        shard, exactly as before (cold batches, fences,
        NOMAD_TPU_RESIDENT_DEVICE=0).

        Returns None when the slot record would blow its budget
        (pathological count skew): the caller degrades to the
        single-chip program — without ever taking the mirror loan."""
        global MESH_PASSES
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel import sharded as shmod

        mesh = self.mesh
        d = mesh.devices.size
        n_l = ct.n_pad // d
        # ``slot_m`` / ``max_nnz``: the batch's plan as _dispatch_device
        # chose it (_natural_plan with the mesh's slot budget, or the
        # compiled plan that covers it).  Slot-mode scores whenever the
        # single-chip path would carry them: the score threshold is
        # taken at the SINGLE-CHIP pad (128), not the mesh's lcm(128, D)
        # pad-up — otherwise a non-power-of-two mesh could cross the
        # 16M boundary and drop scores exactly where the reference
        # path still carries them (encode.shape_plan's n_pad_ref rule).
        with_scores = encode.carries_scores(st.u_pad, ct.n_real)
        if not slot_m:
            self.logger.warning(
                "mesh slot record for %d specs x %d max count exceeds "
                "budget; batch takes the single-chip path", st.u_pad,
                max((sp.count for sp in spec_list), default=1))
            return None
        # pow2(max(64, max count)), read off the slot bucket so that a
        # covering plan brings its own candidate width.
        k_cand = min(n_l, max(64, slot_m))

        # Loan the sharded donated mirror (installs it node-sharded on
        # first use).  From here to sharded_fused_pass returning, an
        # exception leaves the slot EMPTY — the next take reinstalls
        # from host, never a dead handle (the PR 13 loan protocol).
        used_dev = None
        if res_key is not None and not with_networks:
            used_dev = resident.take_device_used(
                res_key, snap_index, used_host, mesh=mesh)
        if used_dev is not None:
            # The mirror carries the live usage: the replicated sparse
            # delta upload drops out of the dyn buffer entirely.
            del dyn["u_rows"], dyn["u_vals"]

        # Per-shard static packs: node-axis arrays sliced to the owning
        # shard, the [4] scale codebook replicated into each (every
        # shard dequantizes its own rows — the quant round-trip guard in
        # _dispatch_device already verified each shard's slice).
        sbuf, meta_s = xfer.pack_host_sharded(
            static, d, replicate=("res_scale",))         # [D, B]
        dbuf, meta_d = xfer.pack_host(dyn)
        t1 = enc.end()

        stages = tracing.Stages("batch.device.", live=True)
        with stages:
            stages.begin("stage", t1)
            digest = (hashlib.blake2b(sbuf.tobytes(),
                                      digest_size=16).hexdigest(),
                      meta_s, shmod._mesh_cache_key(mesh))
            static_dev = _DEVICE_STATIC_CACHE.get(digest)
            static_h2d = 0
            if static_dev is None:
                static_dev = jax.device_put(
                    sbuf, NamedSharding(mesh, P(shmod.NODE_AXIS)))
                static_h2d = sbuf.nbytes
            _DEVICE_STATIC_CACHE.put(digest, static_dev)
            dyn_dev = jax.device_put(dbuf, NamedSharding(mesh, P()))

            stages.begin("dispatch")
            fused_buf, aux, feas, fused_meta, used_out = \
                shmod.sharded_fused_pass(
                    mesh, static_dev, dyn_dev, used_dev, meta_s=meta_s,
                    meta_d=meta_d, u_pad=st.u_pad, n_pad=ct.n_pad,
                    with_networks=with_networks, with_dp=with_dp,
                    with_scores=with_scores, max_nnz=max_nnz,
                    slot_m=slot_m, k_cand=k_cand)
            if used_out is not None:
                # The program aliased every shard's donated buffer back
                # out — return the loan so the next batch's shard-routed
                # delta apply lands in place.
                resident.give_device_used(res_key, snap_index, used_out)
        MESH_PASSES += 1
        return {
            "spec_list": spec_list, "all_nodes": all_nodes, "ct": ct,
            "st": st, "feas": feas, "fused_buf": fused_buf,
            "fused_meta": fused_meta,
            "fused_overflow": ("slots", aux),
            "quantized": quantized, "mesh_shards": d,
            "with_scores": with_scores, "max_nnz": max_nnz,
            "enc": enc, "t1": t1, "stages": stages,
            "resident": resident_info,
            "h2d_bytes": (dbuf.nbytes + static_h2d
                          + (resident.DEV_H2D_BYTES - h2d0)),
        }

    def _finalize_device_outputs(self, spec_list, all_nodes, ct, st, feas,
                                 unplaced_arr, feas_count, coo_rows,
                                 coo_cols, coo_counts, coo_scores, coo_coll,
                                 rounds, with_scores, enc, t1, stages):
        """Shared device→host post-processing for the single-chip and
        mesh placement paths: lazy failure-forensics row fetch, COO →
        per-spec slots, AllocMetric assembly.  ``stages`` arrives with
        its ``decode`` stage running; the device_seconds stamp below
        closes it.  ``enc``: encode's stages, closed at ``t1``."""
        # Chaos hook: corrupt the fetched kernel outputs (the damage a
        # flaky accelerator / bad HBM would do), THEN validate — the
        # validation below is exactly what protects production from the
        # real version of this fault.
        act = fault.faultpoint("ops.kernel_result")
        if act is not None and act.kind == "corrupt":
            unplaced_arr, coo_counts = _corrupt_outputs(
                act.rng, spec_list, unplaced_arr, coo_counts)
        problem = validate_device_outputs(
            spec_list, ct, unplaced_arr, coo_rows, coo_cols, coo_counts)
        if problem is not None:
            raise KernelIntegrityError(problem)
        from . import decode as decode_mod

        # COO → per-spec placement slots: entries arrive grouped by
        # ascending spec, so per-spec extents are searchsorted slices;
        # the expansion of counts into per-alloc node indexes (and the
        # last-commit score dedup below) run in native/decode.cc behind
        # differential-guarded numpy/python twins — at the north-star
        # shape these two passes were the largest host residue left
        # after the fused kernel (ISSUE 13 tentpole item c).
        valid = (coo_rows >= 0) & (coo_cols < ct.n_real)
        vr, vc = coo_rows[valid], coo_cols[valid]
        vcnt = coo_counts[valid]
        u_lo = np.searchsorted(vr, np.arange(len(spec_list)), side="left")
        u_hi = np.searchsorted(vr, np.arange(len(spec_list)), side="right")
        # The fleet's id table rides the static tensors (one per
        # encoding, encode._carry_host_attrs); tensors built elsewhere
        # get one for the batch.
        node_table = getattr(ct, "_node_table", None)
        if node_table is None:
            node_table = s.NodeTable(ct.node_ids)
        total_asks = int(sum(sp.count for sp in spec_list))
        exp_off, exp_idx = decode_mod.expand_coo(
            coo_rows, coo_cols, coo_counts, len(spec_list), ct.n_real,
            total_asks, breaker=self.breaker)
        if with_scores:
            s_off, s_col, s_sc, s_co = decode_mod.last_scores(
                coo_rows, coo_cols, coo_scores, coo_coll,
                len(spec_list), ct.n_real, breaker=self.breaker)
            # What a score map holds, once for the batch: the scores as
            # the doubles .tolist() would give, and whether any node had
            # a same-job collision at all.
            s_sc = s_sc.astype(np.float64)
            any_co = bool((s_co > 0).any())

        # used_after is reconstructed host-side from used0 + committed
        # placements × asks — exact (integer adds, same order-free sum the
        # kernel computes) and ~1MB of link traffic cheaper than shipping
        # the [N, 4] matrix in the summary.  Only failure forensics needs
        # it (cap_left attribution in _fill_failure_metrics).
        failed_u = np.nonzero(unplaced_arr[:st.u_real] > 0)[0]
        used_after = None
        if len(failed_u):
            used_after = np.asarray(ct.used, dtype=np.int64).copy()
            if len(vr):
                np.add.at(used_after, vc.astype(np.int64),
                          vcnt.astype(np.int64)[:, None]
                          * np.asarray(st.ask)[vr.astype(np.int64)])

        # Priority-tier preemption dispatch: the eviction-set kernel for
        # the asks the capacity loop left unplaced goes in flight NOW so
        # its outputs ride the SAME device fetch as the lazy feasibility
        # forensics rows below — at most ONE extra transfer per batch
        # beyond the main result fetch, even on the fallback path.
        preempt_stats = {}
        preempt_ctx = None
        if (self.preemption_enabled and used_after is not None
                and len(self._allocs_by_node)):
            # Writable copy: the fetched summary buffer is read-only, and
            # the commit pass decrements the counts it fills.
            unplaced_arr = np.array(unplaced_arr)
            preempt_ctx = self._preempt_dispatch(
                spec_list, ct, st, feas, unplaced_arr, used_after)

        # Feasibility rows are fetched lazily, only for failed specs whose
        # feasible count is below their EVALUATED count (= ready nodes in
        # their DCs) — i.e. some constraint actually filtered a node.  The
        # common capacity-exhaustion failure derives everything from
        # placements without moving a row across the link.
        feas_rows: Dict[int, np.ndarray] = {}
        node_facts = None
        need_rows: List[int] = []
        if len(failed_u):
            # Explicit dtypes: np.array([]) would default to float64 on an
            # empty cluster and break the boolean mask math.
            node_facts = {
                "ready": np.array([n.ready() for n in all_nodes],
                                  dtype=bool),
                "dc": np.array([n.datacenter for n in all_nodes],
                               dtype=object),
                "class_codes": None,
                "class_names": None,
                # dcs tuple → (evaluated mask, count): the np.isin over
                # an object array costs ~ms at 50k nodes — once per DC
                # set per batch, NOT once per failed spec.
                "evaluated": {},
            }

            def _evaluated_mask(sp) -> np.ndarray:
                dcs = tuple(sp.datacenters)
                ent = node_facts["evaluated"].get(dcs)
                if ent is None:
                    ent = node_facts["ready"] & np.isin(
                        node_facts["dc"], list(dcs))
                    node_facts["evaluated"][dcs] = ent
                return ent

            def _evaluated_count(sp) -> int:
                return int(_evaluated_mask(sp).sum())

            need_rows = [int(u) for u in failed_u
                         if feas_count[u] < _evaluated_count(spec_list[u])]

        # ONE batched device fetch for everything this phase still needs
        # from the device: forensics feasibility rows AND the preemption
        # kernel outputs, together (span: batch.fetch_forensics — the
        # main result already came back under the batch.fetch span).
        kstats_fetch_s = 0.0
        kstats_fetch_b = 0
        if need_rows or preempt_ctx is not None:
            gets = {}
            if need_rows:
                gets["feas_rows"] = feas[jnp.asarray(
                    np.array(need_rows, dtype=np.int32))]
            if preempt_ctx is not None:
                gets["preempt"] = preempt_ctx["dev"]
            t_fx = time.perf_counter()
            with tracing.span("batch.fetch_forensics", annotate=True,
                              feas_rows=len(need_rows),
                              preempt=int(preempt_ctx is not None)):
                fetched = jax.device_get(gets)
            kstats_fetch_s = time.perf_counter() - t_fx
            if need_rows:
                rows_np = np.asarray(fetched["feas_rows"])
                kstats_fetch_b += rows_np.nbytes
                feas_rows = {u: rows_np[i]
                             for i, u in enumerate(need_rows)}
            if preempt_ctx is not None:
                kstats_fetch_b += sum(
                    np.asarray(a).nbytes
                    for a in jax.tree_util.tree_leaves(fetched["preempt"]))
        device_seconds = stages.end() - t1
        exp = tracing.Stages("batch.metrics.")    # EXPAND_STAGES
        t_metrics = exp.begin("preempt")

        # Preemption commit (host greedy pass over the fetched eviction
        # sets; mutates unplaced_arr/used_after so the failure forensics
        # below see the post-preemption truth).
        if preempt_ctx is not None:
            with tracing.span("batch.preempt", annotate=True):
                preempt_stats = self._preempt_commit(
                    preempt_ctx, fetched["preempt"], spec_list, ct,
                    unplaced_arr, used_after)

        expanded: Dict[Tuple[str, str], List[str]] = {}
        unplaced: Dict[Tuple[str, str], int] = {}
        metrics: Dict[Tuple[str, str], s.AllocMetric] = {}
        # Failure-metric memo: specs that placed NOTHING and had no
        # feasibility row fetched produce a metric fully determined by
        # (spec shape, feas_count, unplaced) and the batch-global state —
        # uniform fleets fail by the hundreds with identical signatures,
        # so the vectorized-but-per-spec forensics run once per shape.
        fail_cache: Dict[Tuple, s.AllocMetric] = {}
        score_columns = 0
        # Stamps per spec, none per allocation: two for a spec placed
        # whole, four for one that left asks unplaced.
        for u, sp in enumerate(spec_list):
            exp.begin("slots")
            key = (sp.job.id, sp.tg.name)
            lo, hi = int(u_lo[u]), int(u_hi[u])
            # The spec's slots stay the integers the device returned
            # (a sequence of node ids to whoever reads strings).
            expanded[key] = s.NodeColumn(
                node_table, exp_idx[int(exp_off[u]):int(exp_off[u + 1])])
            unplaced[key] = int(unplaced_arr[u])

            n_unplaced = unplaced[key]
            sig = None
            if n_unplaced > 0:
                exp.begin("failures")       # the memo
            if n_unplaced > 0 and lo == hi and feas_rows.get(u) is None:
                sig = (sp.ask.tobytes(), tuple(sp.datacenters),
                       tuple((c.ltarget, c.operand, c.rtarget)
                             for c in sp.constraints),
                       tuple(sorted(sp.drivers)), bool(sp.distinct_hosts),
                       sp.dp_target, int(feas_count[u]), n_unplaced,
                       # Network shape: _net_exhaust_dim's attribution
                       # depends on all of these, so specs that fail for
                       # different network reasons must not share a metric.
                       bool(sp.net_active), int(sp.net_mbits),
                       int(sp.dyn_count), int(sp.resv_in_dyn),
                       tuple(sp.resv_ports))
                cached = fail_cache.get(sig)
                if cached is not None:
                    metrics[key] = cached.copy()
                    continue

            # AllocMetric parity from kernel side-outputs
            # (structs.go:4074-4172 contract; VERDICT r1 weak #7).
            exp.begin("scores")
            m = s.AllocMetric()
            m.nodes_evaluated = ct.n_real
            m.nodes_filtered = ct.n_real - int(feas_count[u])
            # Commit-time scores per placed node — the oracle's pure
            # binpack entry (rank.go:139) plus a separate anti-affinity
            # entry when the node had same-job collisions (rank.go:167).
            # Slot-mode COO carries one entry per ALLOC, so a node
            # committed in multiple rounds appears several times — the
            # decode pass deduped keeping the LAST commit's score
            # (matrix-mode semantics: commit_scores[u, n] was
            # overwritten per commit; score_node ADDS, so summed
            # per-commit scores would break the 0-18 ScoreFit bound).
            # The map stays those arrays (structs.NodeScores: a dict to
            # whoever reads one; the log codec writes it from the
            # integers): two slices a spec, no string, no boxed float.
            if with_scores:
                s_lo, s_hi = int(s_off[u]), int(s_off[u + 1])
                if s_hi > s_lo:
                    anti_pos = anti = ()
                    if any_co:
                        co_seg = s_co[s_lo:s_hi]
                        anti_pos = np.nonzero(co_seg > 0)[0]
                        anti = (-float(sp.anti_affinity_penalty)
                                * co_seg[anti_pos].astype(np.float64))
                    m.scores = s.NodeScores(
                        node_table, s_col[s_lo:s_hi], s_sc[s_lo:s_hi],
                        anti_pos, anti)
                    score_columns += 1
            if n_unplaced > 0:
                exp.begin("failures")
                placed_row = np.zeros(ct.n_real, dtype=np.int32)
                placed_row[vc[lo:hi]] = vcnt[lo:hi]
                self._fill_failure_metrics(
                    m, sp, all_nodes, ct, feas_rows.get(u), placed_row,
                    used_after, node_facts)
                m.coalesced_failures = n_unplaced - 1
                if sig is not None:
                    fail_cache[sig] = m
            metrics[key] = m

        # The tail, up to the stamp that ends expand.
        exp.begin("failures")
        dp_specs = int(st.dp_active.sum())
        metrics_seconds = exp.end() - t_metrics
        kstats = {
            "device_seconds": device_seconds,
            "encode_seconds": sum(enc.seconds.values()),
            "metrics_seconds": metrics_seconds,
            "rounds": rounds,
            "fetch_seconds": kstats_fetch_s,
            "fetch_bytes": kstats_fetch_b,
            "stage_seconds": stages.seconds,
            "encode_stage_seconds": enc.seconds,
            "expand_stage_seconds": exp.seconds,
            # The host-evaluated feasibility rows encode supplied for
            # this batch (encode._host_row), the time in them, and how
            # many came from a kept row; the specs that carry a
            # distinct_property.
            "precomp_rows": len(st.row_stamps),
            "constraint_rows_seconds": sum(b - a for a, b in st.row_stamps),
            "constraint_row_reuse": st.rows_reused,
            "dp_specs": dp_specs,
            # The specs whose scores were handed over as arrays.
            "score_columns": score_columns,
            # Of those, the ones whose program read its per-value tables
            # in the dense form: all or none, by the dispatched v_pad.
            "dp_dense_specs": (dp_specs if kernels.dp_dense(
                st.dp_used.shape[1]) else 0),
        }
        kstats.update(preempt_stats)
        tr = tracing.TRACER
        if tr is not None:
            # Phase spans from the timers already taken above: t1 marks
            # the encode→device boundary, t_metrics the device→host one.
            t0 = t1 - kstats["encode_seconds"]
            parent = tr.record("batch.encode", t0, t1).span_id
            enc.lay(t0, ENCODE_STAGES, parent)
            for a, b in st.row_stamps:
                tr.record("batch.encode.constraint_rows", a, b,
                          parent_id=parent)
            for a, b in st.port_stamps:
                tr.record("batch.encode.static_ports", a, b,
                          parent_id=parent)
            tr.record("batch.device", t1, t1 + device_seconds,
                      span_id=stages.parent_id, rounds=rounds)
            parent = tr.record(
                "batch.metrics", t_metrics, t_metrics + metrics_seconds,
                preempt_placed=kstats.get("preempt_placed", 0)).span_id
            exp.lay(t_metrics, EXPAND_STAGES, parent)
        return expanded, unplaced, metrics, kstats

    # -- preemption pass ----------------------------------------------------

    def _preempt_dispatch(self, spec_list, ct, st, feas,
                          unplaced_arr, used_after) -> Optional[Dict]:
        """Batched eviction-set pass for the asks the capacity loop left
        unplaced: ONE kernel invocation computes, for every still-failing
        (task-group, node) pair, the minimal set of strictly-lower-
        priority allocs to evict and the post-eviction fit score
        (ops/preempt.py — the device twin of scheduler/preempt.py).

        This half only DISPATCHES: the returned ctx's ``dev`` entry is
        the in-flight device computation (eviction sets + the preempting
        specs' static feasibility rows — constraints/dc/eligibility
        still bind a preempting placement), which the caller fetches in
        its single combined forensics fetch before _preempt_commit runs
        the host greedy pass.  None when no spec qualifies.

        Specs with network asks, distinct_hosts, or distinct_property
        keep the no-preemption result: their feasibility state after an
        eviction is not expressible in this kernel's inputs."""
        from ..scheduler import preempt as preempt_oracle
        from . import preempt as preempt_ops

        pu = [u for u in range(st.u_real)
              if unplaced_arr[u] > 0
              and spec_list[u].priority > 0
              and not spec_list[u].net_active
              and spec_list[u].dp_target is None
              and not spec_list[u].distinct_hosts]
        if not pu:
            return None

        state = self.state

        def prio_of(a: s.Allocation) -> int:
            return preempt_oracle.alloc_priority(a, state)

        # Materialized candidate rows, NOT self._allocs_by_node: the
        # usage-encoding rows are shared slab PROTOS for slab-backed
        # allocs (state.alloc_rows contract) — one object with no id —
        # while a victim must carry its real id/node_id/modify_index or
        # the plan applier's staleness fence rejects every commit.  Paid
        # only when preemption actually has unplaced high-priority work.
        allocs_by_node = {
            nid: state.allocs_by_node_terminal(None, nid, False)
            for nid in self._allocs_by_node
        }
        prio, sizes, sorted_allocs = preempt_ops.encode_alloc_tensors(
            ct.node_ids, allocs_by_node, prio_of, n_pad=ct.n_pad)
        capacity = np.asarray(ct.capacity, dtype=np.int64)
        free = np.clip(capacity - used_after, -(2 ** 31), 2 ** 31 - 1)
        denom = np.asarray(ct.score_denom, dtype=np.float32)
        ask = np.asarray(st.ask, dtype=np.int64)[pu].astype(np.int32)
        jp = np.array([spec_list[u].priority for u in pu], dtype=np.int32)

        pu_idx = jnp.asarray(np.array(pu, dtype=np.int32))
        dev = (preempt_ops.eviction_sets(
                   jnp.asarray(free.astype(np.int32)),
                   jnp.asarray(used_after.astype(np.int32)),
                   jnp.asarray(denom),
                   jnp.asarray(prio), jnp.asarray(sizes),
                   jnp.asarray(ask), jnp.asarray(jp)),
               feas[pu_idx])
        return {"pu": pu, "sorted_allocs": sorted_allocs,
                "prio_of": prio_of, "free": free, "ask": ask, "jp": jp,
                "dev": dev}

    def _preempt_commit(self, ctx, fetched, spec_list, ct,
                        unplaced_arr, used_after) -> Dict[str, int]:
        """Host half of the preemption pass, over the FETCHED kernel
        outputs: commit greedily in the batch's priority order — best
        effective score (post-eviction binpack minus the preemption
        discount) first, at most ONE preempting placement per node per
        batch (a second eviction on the same node would need the
        post-first-eviction state the kernel did not see).  Every commit
        is cross-checked against the scalar oracle on identical inputs;
        the agreement counters surface in BatchStats (the
        kernel-vs-oracle eviction-set agreement tests/test_preempt.py
        holds)."""
        from ..scheduler import preempt as preempt_oracle

        pu = ctx["pu"]
        sorted_allocs = ctx["sorted_allocs"]
        prio_of = ctx["prio_of"]
        free = ctx["free"]
        ask = ctx["ask"]
        jp = ctx["jp"]
        (mask_np, feasible, n_evict, score), feas_rows = fetched
        mask_np = np.asarray(mask_np)
        feasible = np.asarray(feasible) & np.asarray(feas_rows)
        n_evict = np.asarray(n_evict)
        eff = np.asarray(score) - (
            preempt_oracle.PREEMPTION_SCORE_PENALTY
            + preempt_oracle.PREEMPTION_PER_ALLOC_PENALTY * n_evict)

        placed = evicted = checked = agree = 0
        dirty = np.zeros(ct.n_pad, dtype=bool)
        for p, u in enumerate(pu):
            sp = spec_list[u]
            key = (sp.job.id, sp.tg.name)
            need = int(unplaced_arr[u])
            ok = feasible[p] & ~dirty
            ok[ct.n_real:] = False
            n_ok = int(ok.sum())
            if need <= 0 or n_ok == 0:
                continue
            cand = np.nonzero(ok)[0]
            order = cand[np.argsort(-eff[p][cand], kind="stable")]
            commits = self._preempt_plan.setdefault(key, [])
            for i in order[:need].tolist():
                victims = [sorted_allocs[i][a]
                           for a in np.nonzero(mask_np[p, i])[0]]
                checked += 1
                if self._preempt_oracle_agrees(
                        sorted_allocs[i], free[i], ask[p], int(jp[p]),
                        victims, prio_of):
                    agree += 1
                else:  # pragma: no cover — differential safety net
                    self.logger.warning(
                        "preempt kernel/oracle disagreement on node %s; "
                        "skipping commit", ct.node_ids[i])
                    continue
                commits.append((ct.node_ids[i], victims))
                dirty[i] = True
                placed += 1
                evicted += len(victims)
                # Keep the forensics usage honest: the ask lands, the
                # victims leave.
                used_after[i] += ask[p].astype(np.int64)
                for v in victims:
                    used_after[i] -= np.array(
                        preempt_oracle.alloc_size(v), dtype=np.int64)
                unplaced_arr[u] -= 1

        return {"preempt_placed": placed, "preempt_evicted": evicted,
                "preempt_checked": checked, "preempt_agree": agree}

    @staticmethod
    def _preempt_oracle_agrees(node_allocs_sorted, free_vec, ask_vec,
                               priority, kernel_victims, prio_of) -> bool:
        """Replay the scalar oracle (scheduler/preempt.py greedy prefix +
        reverse trim) on EXACTLY the kernel's inputs and compare sets."""
        from ..scheduler import preempt as preempt_oracle

        cand = [a for a in node_allocs_sorted if prio_of(a) < priority]
        free = tuple(int(x) for x in free_vec)
        ask = tuple(int(x) for x in ask_vec)
        if all(ask[d] <= free[d] for d in range(4)):
            return False  # fits without eviction — kernel must not commit
        chosen = preempt_oracle.select_eviction_prefix(
            free, ask, [preempt_oracle.alloc_size(a) for a in cand])
        if not chosen:
            return False
        return [cand[j].id for j in chosen] == [a.id for a in kernel_victims]

    def _fill_failure_metrics(self, m, sp, nodes, ct, feas_row, placed_row,
                              used_after, node_facts) -> None:
        """Per-class/per-constraint/per-dimension forensics for a failed
        placement, matching the oracle's filter_node/exhausted_node
        accounting: chain order job constraints → drivers → tg/task
        constraints (feasible.go), class-cache attribution ("computed
        class ineligible" after the first failure of a class,
        feasible.go:597), distinct checks before capacity (stack order),
        and Resources.superset dimension names (rank.go).

        The common case — no filtered nodes, capacity exhaustion only —
        is fully vectorized (one pass of numpy per failed spec); the
        python checkers run only over the filtered-node subset.
        ``feas_row`` may be None when the device reported zero filtered
        nodes (the feasibility row was not fetched — every evaluated node
        was feasible)."""
        n_real = ct.n_real
        feas_r = (feas_row[:n_real].astype(bool) if feas_row is not None
                  else np.ones(n_real, dtype=bool))
        placed_r = placed_row[:n_real]
        dcs = tuple(sp.datacenters)
        evaluated = node_facts["evaluated"].get(dcs)
        if evaluated is None:
            evaluated = node_facts["ready"] & np.isin(
                node_facts["dc"], list(dcs))
            node_facts["evaluated"][dcs] = evaluated
        m.nodes_evaluated = int(evaluated.sum())
        m.nodes_filtered = 0

        # -- exhausted (feasible, evaluated, uncommitted): vectorized ----
        exh_mask = evaluated & feas_r & (placed_r == 0)
        if exh_mask.any():
            # cap_left is per-batch; the over/first_dim compare is keyed
            # by the spec's ask vector — one [n, 4] pass per DISTINCT ask
            # per batch, not per failed spec (uniform fleets fail by the
            # hundreds with identical asks).
            ask_cache = node_facts.setdefault("ask_over", {})
            ask_key = sp.ask.tobytes()
            ent = ask_cache.get(ask_key)
            if ent is None:
                cap_left = node_facts.get("cap_left")
                if cap_left is None:
                    cap_left = ct.capacity[:n_real] - used_after[:n_real]
                    node_facts["cap_left"] = cap_left
                over = sp.ask[None, :] > cap_left      # [n, 4]
                ent = (over.any(axis=1), np.argmax(over, axis=1))
                ask_cache[ask_key] = ent
            any_over, first_dim = ent
            dim_names = ("cpu exhausted", "memory exhausted",
                         "disk exhausted", "iops exhausted")
            capacity_exh = exh_mask & any_over
            n_cap_exh = int(capacity_exh.sum())
            if n_cap_exh:
                # Counters + per-dimension and per-class tallies in bulk:
                # classes are interned to int codes once per batch so the
                # per-spec tally is a bincount, not an object-array sort.
                m.nodes_exhausted += n_cap_exh
                dims = np.bincount(first_dim[capacity_exh], minlength=4)
                for di, cnt in enumerate(dims):
                    if cnt:
                        m.dimension_exhausted[dim_names[di]] = (
                            m.dimension_exhausted.get(dim_names[di], 0)
                            + int(cnt))
                if node_facts.get("class_codes") is None:
                    names: List[str] = []
                    index: Dict[str, int] = {}
                    codes = np.empty(len(nodes), dtype=np.int32)
                    for i2, n2 in enumerate(nodes):
                        cls = n2.node_class or ""
                        code = index.get(cls)
                        if code is None:
                            code = index[cls] = len(names)
                            names.append(cls)
                        codes[i2] = code
                    node_facts["class_codes"] = codes
                    node_facts["class_names"] = names
                codes = node_facts["class_codes"][:n_real]
                names = node_facts["class_names"]
                if len(names) > 1 or names[0]:
                    counts = np.bincount(codes[capacity_exh],
                                         minlength=len(names))
                    for code, cnt in enumerate(counts):
                        if cnt and names[code]:
                            m.class_exhausted[names[code]] = (
                                m.class_exhausted.get(names[code], 0)
                                + int(cnt))
            # The rarer non-capacity blocks keep per-node attribution.
            rest = np.nonzero(exh_mask & ~any_over)[0]
            for i in rest:
                node = nodes[i]
                if sp.distinct_hosts or sp.dp_target is not None:
                    # Distinct checks precede BinPack in the oracle chain:
                    # blocked nodes are FILTERED, not exhausted
                    # (feasible.go:272).
                    m.filter_node(
                        node,
                        s.CONSTRAINT_DISTINCT_HOSTS if sp.distinct_hosts
                        else s.CONSTRAINT_DISTINCT_PROPERTY)
                elif sp.net_active:
                    m.exhausted_node(node, self._net_exhaust_dim(sp, ct, i))
                else:
                    m.exhausted_node(node, "resources exhausted")

        # -- filtered (evaluated, infeasible): python checkers on subset --
        filt_idx = np.nonzero(evaluated & ~feas_r)[0]
        if len(filt_idx) == 0:
            return
        from ..scheduler.context import EvalContext
        from ..scheduler.feasible import ConstraintChecker, DriverChecker
        from .encode import _escapes_class

        # The real oracle checkers record filter reasons straight into m.
        eval_ctx = EvalContext(state=None, plan=s.Plan())
        eval_ctx.metrics = m
        strip = (s.CONSTRAINT_DISTINCT_HOSTS, s.CONSTRAINT_DISTINCT_PROPERTY)
        job_cons = [c for c in sp.job.constraints if c.operand not in strip]
        tg_cons = [c for c in sp.constraints
                   if c not in sp.job.constraints and c.operand not in strip]
        job_checker = ConstraintChecker(eval_ctx, job_cons)
        tg_checker = ConstraintChecker(eval_ctx, tg_cons)
        driver_checker = DriverChecker(eval_ctx, sp.drivers)
        # FeasibilityWrapper's class cache: once a computed class is known
        # ineligible (for a non-escaping reason), later nodes of the class
        # are filtered as "computed class ineligible" (feasible.go:627).
        cacheable = all(not _escapes_class(c) for c in job_cons + tg_cons)
        ineligible_classes: set = set()
        for i in filt_idx:
            node = nodes[i]
            if cacheable and node.computed_class in ineligible_classes:
                m.filter_node(node, "computed class ineligible")
                continue
            ok = (job_checker.feasible(node)
                  and driver_checker.feasible(node)
                  and tg_checker.feasible(node))
            if ok:
                # Disagreement with the device matrix can only come from
                # encode-side handling; attribute generically.
                m.filter_node(node, "constraint")
            elif cacheable and node.computed_class:
                ineligible_classes.add(node.computed_class)

    def _net_exhaust_dim(self, sp, ct, i) -> str:
        """The oracle's network error strings (network.go:245) derived
        from encoded state."""
        if ct.bw_cap is not None and ct.bw_cap[i] < 0:
            return "network: no networks available"
        if ct.bw_cap is not None and sp.net_mbits > 0 and (
                ct.bw_used[i] + sp.net_mbits > ct.bw_cap[i]):
            return "network: bandwidth exceeded"
        if sp.resv_ports:
            return "network: reserved port collision"
        return "network: dynamic port selection failed"

    # -- finalize ----------------------------------------------------------

    def _net_index(self, node_id: str, cache: Dict):
        """Per-batch NetworkIndex for a node, seeded from state and mutated
        as offers commit — so concrete dynamic-port values assigned at
        finalize never collide within the batch (device-side capacity
        accounting guarantees feasibility).  The seed reads the networks
        the node's live allocations hold, a network slab's rows from its
        columns (StateStore.node_networks): no Allocation is built."""
        from ..structs.network import NetworkIndex

        idx = cache.get(node_id)
        if idx is None:
            idx = NetworkIndex()
            node = self.state.node_by_id(None, node_id)
            if node is not None:
                idx.set_node(node)
                for held in self.state.node_networks(node_id):
                    idx.add_held(*held)
            cache[node_id] = idx
        return idx

    def _finalize(self, scheds, specs, expanded, per_spec_metrics,
                  stats) -> None:
        """Materialize every eval's assigned slots into its plan, hand
        the batch's plans to the planner as ONE submission, then settle
        each eval from its own result and write the statuses of all that
        completed in ONE write (``planner.update_evals``: one fsync) —
        generic_sched.go:104 Process, a batch at a time.  The three
        passes (build the plans, the submission's round trip with any
        oracle retry after it, the status writes) are timed into
        ``stats`` and, armed, recorded as batch.finalize.* spans: build
        per eval, submit and status per batch."""
        tr = tracing.TRACER
        net_index_cache: Dict[str, "NetworkIndex"] = {}
        t_in = t_sub = time.perf_counter()
        for ev, sched in scheds:
            t_built = self._finalize_build(
                ev, sched, specs, expanded, per_spec_metrics, net_index_cache,
                stats)
            if tr is not None:
                tr.record("batch.finalize.build", t_sub, t_built,
                          eval_id=ev.id)
            t_sub = t_built

        # A plan that proposes nothing is not submitted; its eval
        # completes with the others.
        submitting = [(ev, sched) for ev, sched in scheds
                      if not sched.plan.is_no_op() or ev.annotate_plan]
        retried = set()
        if submitting:
            plans = [sched.plan for _, sched in submitting]
            submit = getattr(self.planner, "submit_plans", None)
            outcomes = (submit(plans) if submit is not None else
                        [self.planner.submit_plan(plan) for plan in plans])
            for (ev, sched), (result, new_state) in zip(submitting, outcomes):
                if self._finalize_settle(ev, sched, result, new_state):
                    retried.add(ev.id)
        t_st = time.perf_counter()

        completed = _EvalUpdates()
        for ev, sched in scheds:
            if ev.id not in retried:
                self._finalize_status(ev, sched, completed)
        if completed.evals:
            update = getattr(self.planner, "update_evals", None)
            if update is not None:
                update(completed.evals)
            else:
                for new_eval in completed.evals:
                    self.planner.update_eval(new_eval)
        t_out = time.perf_counter()
        stats.finalize_build_seconds = t_sub - t_in
        stats.finalize_submit_seconds = t_st - t_sub
        stats.finalize_status_seconds = t_out - t_st
        if tr is not None and stats.finalize_offers_seconds:
            # Laid from the build pass's start, its length the offers'
            # total over the batch (Stages.lay's convention).
            tr.record("batch.finalize.offers", t_in,
                      t_in + stats.finalize_offers_seconds)
        if tr is not None:
            ids = tracing.eval_id_attrs((ev for ev, _ in scheds), len(scheds))
            tr.record("batch.finalize.submit", t_sub, t_st, **ids)
            tr.record("batch.finalize.status", t_st, t_out, **ids)

    def _finalize_build(self, ev, sched, specs, expanded, per_spec_metrics,
                        net_index_cache, stats) -> float:
        """Slots → plan (slab or per-alloc), blocked and follow-up evals.
        Returns the stamp at which the plan stood ready to submit."""
        # Prototype alloc per spec: the metric, task_resources, resources and
        # shared_resources objects are shared by every alloc of the spec —
        # legal because stored objects are immutable snapshots by convention
        # (go-memdb shares pointers the same way) and the batch path never
        # mutates them post-construction.  Per-alloc cost: one shallow copy +
        # a bulk-generated uuid.
        fast_copy = s._fast_copy
        for tg, names_or_count, prevs in sched.pending_bulk:
            key = (sched.job.id, tg.name)
            slots = expanded.get(key, [])
            if isinstance(names_or_count, int):
                n_asks = names_or_count
                names = None   # formulaic; generated below only as needed
            else:
                names = names_or_count
                n_asks = len(names)
            metric = per_spec_metrics.get(key, s.AllocMetric())
            metric.nodes_available = sched.nodes_by_dc
            combined = s.Resources(disk_mb=tg.ephemeral_disk.size_mb)
            for t in tg.tasks:
                combined.add(t.resources)
            proto = s.Allocation(
                eval_id=ev.id,
                job_id=sched.job.id,
                task_group=tg.name,
                metrics=metric,
                resources=combined,
                task_resources={t.name: t.resources.copy() for t in tg.tasks},
                desired_status=s.ALLOC_DESIRED_STATUS_RUN,
                client_status=s.ALLOC_CLIENT_STATUS_PENDING,
                shared_resources=s.Resources(
                    disk_mb=tg.ephemeral_disk.size_mb),
            )
            spec = specs.get(key)
            net_asks = spec.net_asks if spec is not None else {}
            k = min(len(slots), n_asks)
            appended = 0
            if not net_asks:
                # Columnar fast path: ONE AllocSlab per (job, tg) instead
                # of k Allocation objects — the prototype is stored once
                # and per-alloc columns carry only id/name/node/prev
                # (structs.AllocSlab; the host-side bottleneck at bench
                # scale was exactly this materialization loop).  Ids and
                # formulaic names are LAZY columns: the strings only
                # exist if something reads them (structs._LazyStrs).
                if k:
                    slab = s.AllocSlab(
                        proto=proto,
                        ids=s.LazyUuids(k),
                        names=(s.LazyNames(
                                   k, f"{sched.job.name}.{tg.name}")
                               if names is None
                               else (names[:k] if k < len(names)
                                     else names)),
                        node_ids=slots[:k] if k < len(slots) else slots,
                        prev_ids=([p or "" for p in prevs[:k]]
                                  if prevs is not None else []),
                    )
                    sched.plan.append_slab(slab)
                    appended = k
            elif k:
                appended = self._net_slabs(
                    ev, sched, tg, proto, net_asks, slots[:k], names, prevs,
                    net_index_cache, stats)
            # Placements won by the preemption pass: explicit allocs (not
            # slab rows — each carries eviction dependencies), with the
            # victims staged into Plan.node_preemptions so the applier
            # commits evict + place atomically and can reject on a stale
            # victim.
            extra = self._preempt_plan.get(key) or []
            if extra:
                take = min(len(extra), n_asks - appended)
                base = appended
                extra_ids = s.generate_uuids(take)
                for i in range(take):
                    node_id, victims = extra[i]
                    alloc = fast_copy(proto)
                    alloc.id = extra_ids[i]
                    alloc.name = (names[base + i] if names is not None
                                  else f"{sched.job.name}.{tg.name}"
                                       f"[{base + i}]")
                    alloc.node_id = node_id
                    if prevs is not None and prevs[base + i]:
                        alloc.previous_allocation = prevs[base + i]
                    for victim in victims:
                        sched.plan.append_preempted_alloc(victim)
                    sched.plan.append_alloc(alloc)
                    appended += 1

            # Any slot that did not yield a plan alloc — including a failed
            # host-side network offer — is a placement failure and must
            # produce a blocked eval (generic_sched.go:218), not a silent
            # under-placement.
            if appended < n_asks:
                if sched.failed_tg_allocs is None:
                    sched.failed_tg_allocs = {}
                sched.failed_tg_allocs[tg.name] = metric

        # Blocked eval for failures (generic_sched.go:218-227).
        if (ev.status != s.EVAL_STATUS_BLOCKED and sched.failed_tg_allocs
                and sched.blocked is None):
            sched._create_blocked_eval(plan_failure=False)

        # Rolling-update limit reached: spawn the follow-up eval
        # (generic_sched.go:232-240).
        if sched.limit_reached and sched.next_eval is None:
            sched.next_eval = ev.next_rolling_eval(sched.job.update.stagger)
            self.planner.create_eval(sched.next_eval)

        return time.perf_counter()

    def _net_slabs(self, ev, sched, tg, proto, net_asks, slots, names,
                   prevs, net_index_cache, stats) -> int:
        """A port-asking spec's placements as network slabs: each slot's
        concrete offers (IP and dynamic port values per networked task;
        the device reserved ports, bandwidth and dynamic capacity, the
        host picks the numbers: rank.go:199 assign + network.go:245)
        become a row of the columns of one AllocSlab
        (``AllocSlab.of_offers``), no Allocation per slot.  Rows whose
        offers landed on the same devices share a slab.  A slot whose
        offer fails drops its row and counts in ``net_offer_failures``.
        Returns the rows written."""
        import random as _random

        net_rng = _random.Random(ev.id)
        asks = [net_asks[t.name] for t in tg.tasks if t.name in net_asks]
        # device of each networked task's offer -> (slot positions, offers)
        groups: Dict[tuple, Tuple[List[int], List[list]]] = {}
        t_offer = time.perf_counter()
        for i, node_id in enumerate(slots):
            idx = self._net_index(node_id, net_index_cache)
            offers = []
            for ask in asks:
                offer, err = idx.assign_network(ask, net_rng)
                if offer is None:
                    self.logger.warning(
                        "batch: network offer failed on %s: %s", node_id,
                        err)
                    break
                idx.add_reserved(offer)
                offers.append(offer)
            if len(offers) < len(asks):
                stats.net_offer_failures += 1
                continue
            kept, rows = groups.setdefault(
                tuple(o.device for o in offers), ([], []))
            kept.append(i)
            rows.append(offers)
        k = len(slots)
        prefix = f"{sched.job.name}.{tg.name}"
        placed = 0
        for kept, rows in groups.values():
            whole = len(kept) == k
            sched.plan.append_slab(s.AllocSlab.of_offers(
                proto, rows,
                ids=s.LazyUuids(len(kept)),
                names=(s.LazyNames(k, prefix) if names is None and whole
                       else [names[i] if names is not None
                             else f"{prefix}[{i}]" for i in kept]),
                node_ids=(slots if whole
                          else s.NodeColumn(slots.table, slots.idx[kept])
                          if type(slots) is s.NodeColumn
                          else [slots[i] for i in kept]),
                prev_ids=([prevs[i] or "" for i in kept]
                          if prevs is not None else [])))
            placed += len(kept)
        stats.finalize_offers_seconds += time.perf_counter() - t_offer
        stats.net_slab_rows += placed
        return placed

    def _finalize_settle(self, ev, sched, result, new_state) -> bool:
        """An eval's own result of the submission.  True when a conflict
        sent the eval through the oracle, alone and after the batch's
        plans, which then wrote the eval's status itself."""
        from ..scheduler.util import adjust_queued_allocations

        adjust_queued_allocations(self.logger, result, sched.queued_allocs)

        if new_state is not None or (
                result is not None and not result.full_commit(sched.plan)[0]):
            # Conflict: fall back to the oracle for this eval — the batch
            # optimism is reconciled exactly as Nomad reconciles optimistic
            # concurrency, by refresh-and-retry (plan_apply.go:27-41).
            self.logger.info("batch plan conflict for eval %s; oracle retry", ev.id)
            retry_state = new_state if new_state is not None else self.state
            oracle = GenericScheduler(self.logger, retry_state, self.planner,
                                      batch=(ev.type == s.JOB_TYPE_BATCH),
                                      preemption_enabled=self.preemption_enabled)
            oracle.process(ev)
            return True
        return False

    def _finalize_status(self, ev, sched, completed: "_EvalUpdates") -> None:
        """The eval's status: a reblock is its own raft write; an eval
        that completes joins ``completed``, the batch's one write."""
        if ev.status == s.EVAL_STATUS_BLOCKED and sched.failed_tg_allocs:
            e = sched.ctx.eligibility()
            new_eval = ev.copy()
            new_eval.escaped_computed_class = e.has_escaped()
            new_eval.class_eligibility = e.get_classes()
            self.planner.reblock_eval(new_eval)
            return

        set_status(self.logger, completed, ev, sched.next_eval, sched.blocked,
                   sched.failed_tg_allocs, s.EVAL_STATUS_COMPLETE, "",
                   sched.queued_allocs)


class _EvalUpdates:
    """Stands in for the planner in ``set_status``: keeps the evals it
    would have written, for one write."""

    def __init__(self) -> None:
        self.evals: List[s.Evaluation] = []

    def update_eval(self, ev: s.Evaluation) -> None:
        self.evals.append(ev)


class BatchStats:
    """Instrumentation for one batch pass (telemetry parity: the
    nomad.worker.invoke_scheduler metrics family)."""

    def __init__(self) -> None:
        self.num_evals = 0
        self.num_specs = 0
        self.num_asks = 0
        self.encode_seconds = 0.0
        self.device_seconds = 0.0
        # prepare = phase1 + phase2 (the whole of _prepare_batch).
        self.prepare_seconds = 0.0
        self.phase1_seconds = 0.0
        self.phase2_seconds = 0.0
        # encode_seconds, device_seconds and metrics_seconds split into
        # ENCODE_STAGES, DEVICE_STAGES, EXPAND_STAGES (name → seconds).
        self.encode_stage_seconds: Dict[str, float] = {}
        self.device_stage_seconds: Dict[str, float] = {}
        self.expand_stage_seconds: Dict[str, float] = {}
        self.metrics_seconds = 0.0
        self.finalize_seconds = 0.0
        # finalize split, one pass over the batch's evals each: building
        # the plans, their one submission's round trip (oracle retries
        # on conflict with it), and the status writes.
        self.finalize_build_seconds = 0.0
        self.finalize_submit_seconds = 0.0
        self.finalize_status_seconds = 0.0
        # Inside the build pass: picking concrete ports for network asks
        # (the node's NetworkIndex and assign_network), summed over the
        # batch, and the slots whose offer could not be made.
        self.finalize_offers_seconds = 0.0
        self.net_offer_failures = 0
        # Rows written through network slabs (the offers' columns).
        self.net_slab_rows = 0
        self.total_seconds = 0.0
        # CPU time of the worker's thread between total_seconds' two
        # stamps (time.thread_time: waits for the interpreter lock, the
        # device, the applier excluded).
        self.cpu_seconds = 0.0
        # Most passes any one spec of the batch took; the passes summed
        # over its specs; the specs that took more than one.
        self.rounds = 0
        self.spec_passes = 0
        self.multi_round_specs = 0
        # Host-evaluated feasibility rows encode supplied, the time in
        # them (part of encode_seconds), how many were served from a
        # kept row, and distinct_property specs.
        self.precomp_rows = 0
        self.constraint_rows_seconds = 0.0
        self.constraint_row_reuse = 0
        self.dp_specs = 0
        self.dp_dense_specs = 0
        # Specs whose AllocMetric.scores stayed the device's arrays
        # (structs.NodeScores).
        self.score_columns = 0
        # Fused score-and-commit path (PR 6): whether this batch ran the
        # single-dispatch/single-fetch program, the wall time of that
        # dispatch (upload → device compute → result transfer), the wall
        # time and bytes of all device→host fetches, and whether the
        # static resource rows shipped quantized (int16/int8 + scale
        # codebook, exact by construction).
        self.fused = 0
        self.quantized = 0
        # Mesh size when this batch ran the node-sharded fused program
        # (parallel/sharded.sharded_fused_pass); 0 on the single-chip
        # path.
        self.mesh_shards = 0
        self.commit_seconds = 0.0
        # Host-side async-dispatch gap between the post-encode dispatch
        # point and the start of the blocking fetch (device compute
        # drains inside the fetch, so this is pure host overhead).
        self.dispatch_seconds = 0.0
        self.fetch_seconds = 0.0
        self.fetch_bytes = 0
        # Host→device transfer accounting (ISSUE 14): bytes this batch
        # moved up the link (dyn buffer + any static upload + resident
        # mirror installs/delta uploads) and the wall time of the
        # donated delta apply that replaced the per-batch usage upload.
        self.h2d_bytes = 0
        self.delta_apply_seconds = 0.0
        # Preemption pass counters (batch_sched._preempt_pass): placements
        # won by eviction, allocs evicted, and the kernel-vs-oracle
        # eviction-set agreement tally.
        self.preempt_placed = 0
        self.preempt_evicted = 0
        self.preempt_checked = 0
        self.preempt_agree = 0
        # Degradation counters (ops/breaker.py): evals routed through the
        # CPU oracle by the breaker/integrity check, kernel results
        # rejected by validation, and the breaker state after this batch.
        self.oracle_routed = 0
        self.kernel_rejects = 0
        self.breaker_state = "closed"
        # True only when _place_on_device ran to completion — gates the
        # encode/device/rounds telemetry samples.
        self.device_ran = False
        # Device-resident node-state cache (ops/resident.py): whether the
        # usage rows came from the delta path this batch, how many feed
        # entries were applied, full re-encodes (cold/key-change/feed-gap/
        # guard-mismatch) and staleness-fence fallbacks.
        self.resident_hits = 0
        self.delta_rows = 0
        self.full_reencodes = 0
        self.staleness_fences = 0
        # Network usage (ops/resident.py NET_DIMS): walks of every live
        # alloc the batch's network state needed (a build, a fence, the
        # off-mirror path, the first ask of a static port), the
        # network-usage words sent to the device (the mirror's delta
        # upload, or rows uploaded whole), and the asked static ports
        # whose holders came from the mirror's port columns.
        self.net_usage_walks = 0
        self.net_delta_words = 0
        self.port_columns = 0
        # Host time of THIS batch's prepare phase that ran while the
        # previous batch's device pass was still in flight
        # (schedule_stream double-buffering; 0 on the serial path).
        self.pipeline_overlap_s = 0.0

    def __repr__(self) -> str:
        extra = ""
        if self.preempt_checked:
            extra = (f" preempt={self.preempt_placed}p/"
                     f"{self.preempt_evicted}e "
                     f"agree={self.preempt_agree}/{self.preempt_checked}")
        if self.oracle_routed or self.breaker_state != "closed":
            extra += (f" breaker={self.breaker_state}"
                      f" oracle_routed={self.oracle_routed}")
        if self.resident_hits or self.full_reencodes or self.staleness_fences:
            extra += (f" resident={'hit' if self.resident_hits else 'miss'}"
                      f" delta_rows={self.delta_rows}"
                      f" full_reencodes={self.full_reencodes}")
            if self.staleness_fences:
                extra += f" fences={self.staleness_fences}"
        if self.pipeline_overlap_s:
            extra += f" overlap={self.pipeline_overlap_s:.3f}s"
        if self.mesh_shards:
            extra += f" mesh_shards={self.mesh_shards}"
        if self.device_ran:
            extra += (f" fused={self.fused} quantized={self.quantized} "
                      f"commit={self.commit_seconds:.3f}s "
                      f"fetch={self.fetch_seconds:.3f}s/"
                      f"{self.fetch_bytes}B h2d={self.h2d_bytes}B")
            if self.delta_apply_seconds:
                extra += f" delta_apply={self.delta_apply_seconds:.4f}s"
        return (f"BatchStats(evals={self.num_evals} specs={self.num_specs} "
                f"asks={self.num_asks} phase1={self.phase1_seconds:.3f}s "
                f"phase2={self.phase2_seconds:.3f}s "
                f"encode={self.encode_seconds:.3f}s "
                f"device={self.device_seconds:.3f}s "
                f"metrics={self.metrics_seconds:.3f}s "
                f"finalize={self.finalize_seconds:.3f}s "
                f"total={self.total_seconds:.3f}s "
                f"rounds={self.rounds}{extra})")


def new_tpu_batch_scheduler(logger_, state, planner) -> TPUBatchScheduler:
    return TPUBatchScheduler(logger_, state, planner)


register_scheduler("tpu-batch", new_tpu_batch_scheduler)
