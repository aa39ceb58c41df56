"""Tensor encoding: lowers the scheduler-visible state into SoA device
tensors (SURVEY.md §7 step 1).

Reference semantics being encoded:
- node capacity / usage / score denominators — nomad/structs/funcs.go:60,123
- attribute constraint targets — scheduler/feasible.go:397-458
- computed-class dedup for non-vectorizable ops — scheduler/feasible.go:597,
  scheduler/context.go:46 (EvalCache) — version/regex/set_contains checks are
  evaluated host-side once per (constraint, computed-class) and shipped as
  boolean rows, exactly the caching structure the reference uses.

Ordered interning: each attribute key gets its own codebook whose codes are
assigned in sorted-value order, so lexical <,<=,>,>= lower to integer
compares on device.
"""
from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field, fields, replace
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..structs import structs as s
from ..scheduler.context import EvalContext
from ..scheduler.feasible import (
    check_constraint,
    resolve_constraint_target,
    _parse_bool,
)
from ..scheduler.util import task_group_constraints

logger = logging.getLogger("nomad_tpu.ops.encode")

# Constraint op codes on device (order matters: see ops/kernels.py).
OP_TRUE = 0       # padding / pass-through
OP_EQ = 1
OP_NE = 2
OP_LT = 3
OP_LE = 4
OP_GT = 5
OP_GE = 6
OP_PRECOMP = 7    # gather from the host-precomputed boolean row

# Sentinel for "value missing on node" — any comparison with it fails.
MISSING = np.int32(-1)
# Sentinel rhs for "literal not representable": EQ always false, NE true.
UNKNOWN_RHS = np.int32(-2)

RES_DIMS = 4  # cpu, memory_mb, disk_mb, iops — structs.Resources.TENSOR_DIMS

# Port geometry comes from the host NetworkIndex (structs/network.py ←
# network.go:19-22): the device capacity accounting and the host's
# concrete port assignment at finalize must agree exactly.
from ..structs.network import (  # noqa: E402
    MAX_DYNAMIC_PORT,
    MAX_VALID_PORT,
    MIN_DYNAMIC_PORT,
)

PORT_WORDS = MAX_VALID_PORT // 32          # uint32 words per node bitmap


# -- quantized resource rows (PR 6, int8-everywhere in PR 13) ---------------
#
# The static cluster upload ships two [n_pad, 4] int32 resource matrices
# (capacity + reserved-only usage baseline) over the host↔device
# link, and they sit in HBM for the life of the device cache.
# Quantizing them to int16 (int8 where ranges allow) halves/quarters
# both costs.  The scheme is EXACT or absent: a per-dimension power-of-
# two scale codebook is chosen so every value is divisible by its scale
# and the scaled value fits the narrow dtype; if any dimension cannot be
# represented exactly, quantization is skipped for the whole matrix pair
# (placements must stay bit-identical to the float/int32 oracle — the
# ≤0.5%-target-0.0% score-delta discipline).  Dequantization on device
# is one integer multiply fused into the unpack.
#
# Each matrix carries its OWN [4] scale row (the codebook ships [2, 4]:
# row 0 capacity, row 1 used-baseline) and scales are pushed per
# dimension toward the int8 range first, falling back to the int16 range
# per dimension when divisibility forbids the extra shifts — so a
# capacity column divisible by 1024 rides int8 even when the reserved
# baseline next to it only divides by 4.  A matrix is int8 when ALL its
# scaled dimensions fit int8, int16 otherwise; the two matrices choose
# independently.

def quant_enabled() -> bool:
    from ..utils import knobs

    return knobs.get_bool("NOMAD_TPU_QUANT")


@dataclass
class QuantizedRows:
    """Exactly-quantized (capacity, used-baseline) resource rows plus the
    per-matrix, per-dimension scale codebook.  ``cap_tag``/``used_tag``
    are the xfer dtype tags the quantized matrices ship as ("i16" or
    "i8"); ``scale`` is [2, 4] int32 (row 0 capacity, row 1 used)."""

    cap_q: np.ndarray      # [n_pad, 4] int16/int8
    used_q: np.ndarray     # [n_pad, 4] int16/int8
    scale: np.ndarray      # [2, 4] int32 — power-of-two per matrix/dim
    cap_tag: str
    used_tag: str

    @property
    def tag(self) -> str:  # widest of the pair (back-compat summary)
        return "i8" if self.cap_tag == self.used_tag == "i8" else "i16"


def _quant_one(mat: np.ndarray) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Per-dimension exact power-of-two scales for ONE [n, 4] matrix,
    pushed into the int8 range where divisibility allows, int16
    otherwise; None when even the int16 range cannot be exact."""
    scale = np.ones(RES_DIMS, dtype=np.int64)
    for d in range(RES_DIMS):
        col = mat[:, d]
        m = int(col.max(initial=0))
        s16 = 1
        while m // s16 > np.iinfo(np.int16).max:
            s16 <<= 1
        s8 = s16
        while m // s8 > np.iinfo(np.int8).max:
            s8 <<= 1
        if s8 == 1 or not (col % s8).any():
            scale[d] = s8
        elif s16 == 1 or not (col % s16).any():
            scale[d] = s16
        else:
            return None
    return mat // scale, scale


def quantize_resource_rows(capacity: np.ndarray,
                           used: np.ndarray) -> Optional[QuantizedRows]:
    """Quantize the [n, 4] capacity/used matrices to the narrowest exact
    integer representation, or return None when exactness is impossible
    for either matrix (a value not divisible by the scale its range
    requires).  Scales and dtypes are chosen per matrix."""
    cap = np.asarray(capacity, dtype=np.int64)
    use = np.asarray(used, dtype=np.int64)
    if (cap < 0).any() or (use < 0).any():
        return None
    qc = _quant_one(cap)
    qu = _quant_one(use)
    if qc is None or qu is None:
        return None
    cap_s, cap_scale = qc
    use_s, use_scale = qu

    def _pick(m):
        if m.max(initial=0) <= np.iinfo(np.int8).max:
            return np.int8, "i8"
        return np.int16, "i16"

    cap_dt, cap_tag = _pick(cap_s)
    use_dt, use_tag = _pick(use_s)
    return QuantizedRows(
        cap_q=cap_s.astype(cap_dt), used_q=use_s.astype(use_dt),
        scale=np.stack([cap_scale, use_scale]).astype(np.int32),
        cap_tag=cap_tag, used_tag=use_tag)


def dequantize_rows(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    """Host-side inverse (the round-trip bound check and tests);
    the device-side twin is one multiply in kernels._device_schedule.
    ``scale`` is the matrix's own [4] codebook row."""
    return q.astype(np.int64) * np.asarray(scale, dtype=np.int64)


def _res_vec(r: Optional[s.Resources]) -> np.ndarray:
    if r is None:
        return np.zeros(RES_DIMS, dtype=np.int64)
    return np.array([r.cpu, r.memory_mb, r.disk_mb, r.iops], dtype=np.int64)


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pow2_bucket(x: int, minimum: int = 8) -> int:
    """Next power of two ≥ x (≥ minimum): batch axes are bucketed so every
    differently-sized eval batch hits a warm XLA compile cache instead of
    recompiling (SURVEY.md §7 hard-part vi, padding/recompilation
    discipline)."""
    v = minimum
    while v < x:
        v <<= 1
    return v


def route_shard_deltas(rows: np.ndarray, vals: np.ndarray, shards: int,
                       n_local: int):
    """Split a global usage-delta run ``(rows int[k], vals int[k, dims])``
    into per-shard (local_row, vals) runs for the donated per-shard
    scatter-add (ops/resident.py mesh mirror): one stable sort by
    owning shard over the changed rows — O(changed), never O(cluster),
    no Python per row — emitting ``rows [D, k_b] int32`` (-1 padding)
    and ``vals [D, k_b, dims] int32`` whose leading axis shards over the
    node mesh (``NamedSharding(mesh, P(NODE_AXIS))`` hands each device
    exactly its run, in the order the rows came).  ``k_b`` is the pow2
    bucket of the LARGEST per-shard run so the donated apply jit holds a
    fixed handful of shapes regardless of how deltas skew across
    shards."""
    rows = np.asarray(rows, dtype=np.int64)
    shard = rows // n_local
    owned = (shard >= 0) & (shard < shards)
    if not owned.all():
        rows, vals, shard = rows[owned], vals[owned], shard[owned]
    order = np.argsort(shard, kind="stable")
    shard = shard[order]
    counts = np.bincount(shard, minlength=shards)
    k_b = pow2_bucket(max(1, int(counts.max(initial=0))))
    # Position of each row inside its shard's run.
    pos = np.arange(len(shard)) - (np.cumsum(counts) - counts)[shard]
    out_rows = np.full((shards, k_b), -1, dtype=np.int32)
    out_vals = np.zeros((shards, k_b, vals.shape[1]), dtype=np.int32)
    out_rows[shard, pos] = rows[order] - shard * n_local
    out_vals[shard, pos] = vals[order]
    return out_rows, out_vals


def carries_scores(u_pad: int, n_real: int) -> bool:
    """shape_plan's ``with_scores`` rule, for a caller that brings its
    plan's other two buckets itself (a compiled plan that covers the
    batch's own, kernels.choose_plan)."""
    return u_pad * max(128, round_up(n_real, 128)) <= 16_000_000


def shape_plan(u_pad: int, n_pad: int, n_real: int, max_count: int,
               total_asks: int, *, mesh: bool = False,
               slot_budget_bytes: int = 64 << 20
               ) -> Tuple[bool, int, int]:
    """THE canonical shape-class plan for a placement dispatch — ONE
    pow2 bucketing of (score carry, slot record, COO capacity) shared by
    the single-chip and mesh paths (ISSUE 13 compile-cache audit: two
    call sites deriving these independently is how silent recompiles are
    born).  Returns ``(with_scores, slot_m, max_nnz)``.

    - ``with_scores``: the [U, M]/[U, N] commit-score side-outputs are
      carried while U × N stays under ~16M cells; N is evaluated at the
      SINGLE-CHIP reference pad (128-multiple of ``n_real``), so a mesh
      pad-up or mesh→single-chip fallback can never cross the boundary
      where the reference path still carries scores.
    - ``slot_m``: the commit-aligned slot record's minor axis (pow2 of
      the max ask count), or 0 when the record would exceed
      ``slot_budget_bytes`` (the caller then compacts from the [U, N]
      matrix — or, on the mesh, falls back to single-chip).  The
      single-chip path also turns slots off beyond 65536 node rows
      (matrix nonzero stays cheaper there); the mesh REQUIRES slots.
    - ``max_nnz``: COO capacity — per-ALLOC entries in slot mode (a node
      committed in two rounds appears twice), per-(spec, node)
      aggregates otherwise.
    """
    with_scores = carries_scores(u_pad, n_real)
    slot_m = 0
    if mesh or n_pad <= 65536:
        m_b = pow2_bucket(max(8, max_count), minimum=8)
        slot_bytes = 4 + (8 if with_scores else 0)
        if u_pad * m_b * slot_bytes <= slot_budget_bytes:
            slot_m = m_b
    max_nnz = pow2_bucket(
        max(8, total_asks if slot_m
            else min(total_asks, u_pad * n_pad)), minimum=8)
    return with_scores, slot_m, max_nnz


@dataclass
class ClusterTensors:
    """Device view of the node fleet.

    All arrays are padded to ``n_pad`` (multiple of 128 — TPU lane width);
    padding rows are marked ineligible.
    """

    node_ids: List[str]                 # dense index → node id (host only)
    n_real: int
    n_pad: int
    capacity: np.ndarray                # [n_pad, 4] int32 — node.resources
    used: np.ndarray                    # [n_pad, 4] int32 — reserved + live allocs
    score_denom: np.ndarray             # [n_pad, 2] float32 — (cpu, mem) minus reserved
    eligible: np.ndarray                # [n_pad] bool — ready & not draining
    dc_code: np.ndarray                 # [n_pad] int32
    class_code: np.ndarray              # [n_pad] int32
    attr_values: np.ndarray             # [n_pad, n_attrs] int32 ordered codes
    attr_index: Dict[str, int]          # target string → column
    dc_codebook: Dict[str, int]
    value_codebooks: Dict[str, Dict[str, int]]
    job_count_rows: Dict[str, np.ndarray] = field(default_factory=dict)
    # Network accounting (SURVEY §7 hard-part iii): first-device bandwidth
    # (-1 = no device), used-port bitmaps as uint32 words, free-dynamic-port
    # counts.  Only materialized when the batch contains network asks
    # (w == PORT_WORDS); otherwise w == 1 and the kernel's network checks
    # compile away.  Whether a cluster's networks are simple enough for
    # this path is decided by TPUBatchScheduler._cluster_networks_simple.
    bw_cap: np.ndarray = None           # [n_pad] int32
    bw_used: np.ndarray = None          # [n_pad] int32
    dyn_free: np.ndarray = None         # [n_pad] int32
    port_words: np.ndarray = None       # [n_pad, w] uint32


def encode_cluster(
    nodes: Sequence[s.Node],
    attr_targets: Sequence[str],
    allocs_by_node: Optional[Dict[str, List[s.Allocation]]] = None,
    node_pad_multiple: int = 128,
    with_networks: bool = False,
) -> ClusterTensors:
    """Build the cluster-side tensors.

    attr_targets: every ``${...}``/literal LTarget referenced by any
    vectorizable constraint in the batch; each becomes one int32 column.

    with_networks: also build port bitmaps + bandwidth/dynamic-port
    accounting (only when the batch actually asks for networks — the
    bitmaps are 8KB per node).
    """
    ct = encode_cluster_static(nodes, attr_targets,
                               node_pad_multiple=node_pad_multiple,
                               with_networks=with_networks)
    if allocs_by_node:
        ct = apply_alloc_usage(ct, allocs_by_node)
    return ct


def _resolve_attr_rows(nodes: Sequence[s.Node],
                       attr_targets: Sequence[str]):
    """Per-node resolution of the batch's attribute targets (the second
    loop of the object walk, shared with the columnar path — string
    attr resolution has no columnar form)."""
    value_sets: Dict[str, Set[str]] = {t: set() for t in attr_targets}
    if not attr_targets:
        # One shared empty row: finalize_codebooks only reads these.
        return [{}] * len(nodes), value_sets
    resolved: List[Dict[str, Optional[str]]] = []
    for node in nodes:
        row: Dict[str, Optional[str]] = {}
        for t in attr_targets:
            val, ok = resolve_constraint_target(t, node)
            if ok and isinstance(val, str):
                row[t] = val
                value_sets[t].add(val)
            else:
                row[t] = None
        resolved.append(row)
    return resolved, value_sets


def encode_cluster_static(
    nodes: Sequence[s.Node],
    attr_targets: Sequence[str],
    node_pad_multiple: int = 128,
    with_networks: bool = False,
) -> ClusterTensors:
    """The alloc-independent cluster tensors: capacity, reserved-only
    usage, eligibility, dc/class codes, attribute columns, reserved-port
    bitmaps.  Cacheable across batches keyed by the nodes-table raft
    index (SURVEY §2.2: the scheduler-visible state is mirrored into
    device tensors incrementally); per-batch alloc usage is layered on
    with apply_alloc_usage()."""
    n_real = len(nodes)
    n_pad = max(node_pad_multiple, round_up(n_real, node_pad_multiple))

    capacity = np.zeros((n_pad, RES_DIMS), dtype=np.int64)
    used = np.zeros((n_pad, RES_DIMS), dtype=np.int64)
    score_denom = np.ones((n_pad, 2), dtype=np.float32)
    eligible = np.zeros(n_pad, dtype=bool)
    dc_code = np.full(n_pad, MISSING, dtype=np.int32)
    class_code = np.full(n_pad, MISSING, dtype=np.int32)

    w = PORT_WORDS if with_networks else 1
    # bw_cap = -1 marks "no network device": any network ask (even 0 mbits)
    # fails the bandwidth check there, matching the oracle's
    # "no networks available" (network.go:245).
    bw_cap = np.full(n_pad, -1 if with_networks else 0, dtype=np.int32)
    bw_used = np.zeros(n_pad, dtype=np.int32)
    dyn_free = np.zeros(n_pad, dtype=np.int32)
    port_words = np.zeros((n_pad, w), dtype=np.uint32)

    dc_codebook: Dict[str, int] = {}
    class_codebook: Dict[str, int] = {}
    node_ids: List[str] = []

    for i, node in enumerate(nodes):
        node_ids.append(node.id)
        capacity[i] = _res_vec(node.resources)
        reserved = _res_vec(node.reserved)
        used[i] = reserved
        denom_cpu = float(capacity[i][0] - reserved[0])
        denom_mem = float(capacity[i][1] - reserved[1])
        score_denom[i] = (denom_cpu, denom_mem)
        eligible[i] = node.ready()
        dc_code[i] = dc_codebook.setdefault(node.datacenter, len(dc_codebook))
        class_code[i] = class_codebook.setdefault(node.computed_class, len(class_codebook))

        if with_networks:
            nets = [nr for nr in (node.resources.networks or []) if nr.device]
            if nets:
                bw_cap[i] = nets[0].mbits
            used_ports: Set[int] = set()

            def _account(nr: s.NetworkResource, i=i, used_ports=used_ports):
                bw_used[i] += nr.mbits
                for p in list(nr.reserved_ports) + list(nr.dynamic_ports):
                    if 0 <= p.value < MAX_VALID_PORT:
                        used_ports.add(p.value)

            if node.reserved is not None:
                for nr in node.reserved.networks or []:
                    _account(nr)
            for p in used_ports:
                port_words[i, p >> 5] |= np.uint32(1 << (p & 31))
            in_dyn = sum(1 for p in used_ports
                         if MIN_DYNAMIC_PORT <= p < MAX_DYNAMIC_PORT)
            dyn_free[i] = (MAX_DYNAMIC_PORT - MIN_DYNAMIC_PORT) - in_dyn

    # Ordered value codebooks per attribute target: collect node values, sort,
    # assign ranks — integer compare ≡ lexical compare.
    attr_index = {t: j for j, t in enumerate(attr_targets)}
    resolved, value_sets = _resolve_attr_rows(nodes, attr_targets)

    value_codebooks: Dict[str, Dict[str, int]] = {
        t: {} for t in attr_targets
    }
    attr_values = np.full((n_pad, max(1, len(attr_targets))), MISSING, dtype=np.int32)
    # NOTE: codes are finalized in finalize_codebooks() once constraint
    # literals are known; store raw values for now.
    return_raw = resolved

    ct = ClusterTensors(
        node_ids=node_ids,
        n_real=n_real,
        n_pad=n_pad,
        capacity=capacity,
        used=used,
        score_denom=score_denom,
        eligible=eligible,
        dc_code=dc_code,
        class_code=class_code,
        attr_values=attr_values,
        attr_index=attr_index,
        dc_codebook=dc_codebook,
        value_codebooks=value_codebooks,
        bw_cap=bw_cap,
        bw_used=bw_used,
        dyn_free=dyn_free,
        port_words=port_words,
    )
    ct._raw_rows = return_raw          # type: ignore[attr-defined]
    ct._value_sets = value_sets        # type: ignore[attr-defined]
    ct._class_codebook = class_codebook  # type: ignore[attr-defined]
    ct._nodes = list(nodes)            # type: ignore[attr-defined]
    ct._with_networks = with_networks  # type: ignore[attr-defined]
    ct._node_index = {nid: i for i, nid in enumerate(node_ids)}  # type: ignore[attr-defined]
    ct._node_table = s.NodeTable(node_ids)  # type: ignore[attr-defined]
    ct._host_rows = _HostRows()        # type: ignore[attr-defined]
    return ct


def encode_cluster_static_columnar(
    cols,
    nodes: Sequence[s.Node],
    attr_targets: Sequence[str],
    node_pad_multiple: int = 128,
) -> ClusterTensors:
    """``encode_cluster_static`` built by SLICING the state store's
    columnar mirror (state/columnar.ClusterColumns) instead of walking a
    node object per row — bit-identical output by construction (codes
    are assigned in the same first-seen order the walk's ``setdefault``
    produces; the columnar guard in :func:`build_cluster_static` pins
    it).  Network batches keep the object walk (port bitmaps have no
    columnar form), as does any store without a warm mirror."""
    n_real = cols.n
    n_pad = max(node_pad_multiple, round_up(n_real, node_pad_multiple))

    capacity = np.zeros((n_pad, RES_DIMS), dtype=np.int64)
    capacity[:n_real] = cols.cap[:n_real]
    used = np.zeros((n_pad, RES_DIMS), dtype=np.int64)
    used[:n_real] = cols.res[:n_real]
    score_denom = np.ones((n_pad, 2), dtype=np.float32)
    score_denom[:n_real, 0] = cols.cap[:n_real, 0] - cols.res[:n_real, 0]
    score_denom[:n_real, 1] = cols.cap[:n_real, 1] - cols.res[:n_real, 1]
    eligible = np.zeros(n_pad, dtype=bool)
    eligible[:n_real] = cols.eligible[:n_real]
    dc_code = np.full(n_pad, MISSING, dtype=np.int32)
    dc_code[:n_real] = cols.dc_code[:n_real]
    class_code = np.full(n_pad, MISSING, dtype=np.int32)
    class_code[:n_real] = cols.class_code[:n_real]

    node_ids = list(cols.node_ids[:n_real])
    attr_index = {t: j for j, t in enumerate(attr_targets)}
    resolved, value_sets = _resolve_attr_rows(nodes, attr_targets)
    attr_values = np.full((n_pad, max(1, len(attr_targets))), MISSING,
                          dtype=np.int32)

    ct = ClusterTensors(
        node_ids=node_ids,
        n_real=n_real,
        n_pad=n_pad,
        capacity=capacity,
        used=used,
        score_denom=score_denom,
        eligible=eligible,
        dc_code=dc_code,
        class_code=class_code,
        attr_values=attr_values,
        attr_index=attr_index,
        dc_codebook=cols.dc_codebook(),
        value_codebooks={t: {} for t in attr_targets},
        bw_cap=np.zeros(n_pad, dtype=np.int32),
        bw_used=np.zeros(n_pad, dtype=np.int32),
        dyn_free=np.zeros(n_pad, dtype=np.int32),
        port_words=np.zeros((n_pad, 1), dtype=np.uint32),
    )
    ct._raw_rows = resolved            # type: ignore[attr-defined]
    ct._value_sets = value_sets        # type: ignore[attr-defined]
    ct._class_codebook = cols.class_codebook()  # type: ignore[attr-defined]
    ct._nodes = nodes if type(nodes) is list else list(nodes)  # type: ignore[attr-defined]
    ct._with_networks = False          # type: ignore[attr-defined]
    ct._node_index = {nid: i for i, nid in enumerate(node_ids)}  # type: ignore[attr-defined]
    ct._node_table = s.NodeTable(node_ids)  # type: ignore[attr-defined]
    ct._host_rows = _HostRows()        # type: ignore[attr-defined]
    ct._columnar = True                # type: ignore[attr-defined]
    return ct


def _static_mismatch(ct: ClusterTensors, ref: ClusterTensors) -> str:
    """First difference between a column-built and a walk-built static
    encode, or '' when bit-identical.  Everything the device pass (and
    the codebook-dependent spec lowering) consumes is compared."""
    if ct.node_ids != ref.node_ids:
        return "node_ids order"
    for name in ("capacity", "used", "score_denom", "eligible",
                 "dc_code", "class_code", "attr_values"):
        if not np.array_equal(getattr(ct, name), getattr(ref, name)):
            return name
    if ct.dc_codebook != ref.dc_codebook:
        return "dc_codebook"
    if ct.value_codebooks != ref.value_codebooks:
        return "value_codebooks"
    if getattr(ct, "_class_codebook", None) != getattr(
            ref, "_class_codebook", None):
        return "class_codebook"
    return ""


def build_cluster_static(
    state,
    nodes: Sequence[s.Node],
    attr_targets: Sequence[str],
    literals: Dict[str, Set[str]],
    node_pad_multiple: int = 128,
    with_networks: bool = False,
    breaker=None,
) -> ClusterTensors:
    """Static cluster tensors + finalized codebooks, via the store's
    columnar mirror when available (``NOMAD_TPU_COLUMNAR``), the object
    walk otherwise.  Every ``NOMAD_TPU_COLUMNAR_GUARD_EVERY`` columnar
    encodes the walk runs anyway and the outputs are bit-compared: a
    mismatch feeds the breaker, bumps the columnar epoch (every mirror
    in the process rebuilds before being trusted again), and the batch
    proceeds on the walk's buffers — corruption degrades, never
    mis-places.  Fault point ``state.columns`` (action ``corrupt``)
    perturbs one column-built row, the chaos twin of mirror drift."""
    from .. import fault
    from ..state import columnar as colmod

    cols = None
    if not with_networks:
        columns_fn = getattr(state, "columns", None)
        if columns_fn is not None:
            cols = columns_fn()
        if cols is not None and cols.n != len(nodes):
            cols = None  # mirror out of step with the caller's node list
    if cols is None:
        colmod.WALK_ENCODES += 1
        ct = encode_cluster_static(nodes, attr_targets,
                                   node_pad_multiple=node_pad_multiple,
                                   with_networks=with_networks)
        finalize_codebooks(ct, literals)
        return ct

    colmod.COLUMNAR_ENCODES += 1
    ct = encode_cluster_static_columnar(
        cols, nodes, attr_targets, node_pad_multiple=node_pad_multiple)
    finalize_codebooks(ct, literals)

    act = fault.faultpoint("state.columns")
    if act is not None and act.kind == "corrupt":
        row = act.rng.randrange(max(1, ct.n_real))
        ct.capacity[row, act.rng.randrange(RES_DIMS)] += \
            1 + act.rng.randrange(1000)

    every = colmod.guard_every()
    if every > 0 and colmod.COLUMNAR_ENCODES % every == 0:
        colmod.GUARD_RUNS += 1
        ref = encode_cluster_static(nodes, attr_targets,
                                    node_pad_multiple=node_pad_multiple)
        finalize_codebooks(ref, literals)
        bad = _static_mismatch(ct, ref)
        if bad:
            colmod.note_guard_mismatch("static", bad, breaker=breaker,
                                       Nodes=int(ref.n_real))
            return ref
        if breaker is not None:
            breaker.record(True)
    return ct


def _carry_host_attrs(ct: ClusterTensors, new: ClusterTensors) -> None:
    """Host-only attributes of the static tensors (undeclared, so they
    stay off the dataclass) that a per-batch clone shares with them:
    none depends on usage, and ``_host_rows`` must be the SAME object so
    a row built under one batch's clone is found by the next."""
    for attr in ("_raw_rows", "_value_sets", "_class_codebook", "_nodes",
                 "_with_networks", "_node_index", "_node_table",
                 "_host_rows"):
        if hasattr(ct, attr):
            setattr(new, attr, getattr(ct, attr))


def apply_alloc_usage(
    ct: ClusterTensors,
    allocs_by_node: Dict[str, List[s.Allocation]],
) -> ClusterTensors:
    """Layer live-allocation usage onto (a shallow copy of) the static
    cluster tensors — the cached static part is never mutated.

    Resource usage adds each alloc's combined (or per-task) resources —
    the numpy twin of structs.alloc_usage_vec (the delta feed's canonical
    basis; the resident differential guard pins their bit-equality, so a
    change to either must land in both); network accounting re-derives
    each TOUCHED node's used-port set from reserved + alloc networks,
    exactly like the fused loop this replaces."""
    import dataclasses as _dc

    new = _dc.replace(
        ct,
        used=ct.used.copy(),
        bw_used=ct.bw_used.copy(),
        dyn_free=ct.dyn_free.copy(),
        port_words=(ct.port_words.copy()
                    if getattr(ct, "_with_networks", False) else ct.port_words),
    )
    _carry_host_attrs(ct, new)

    node_index = new._node_index
    nodes = new._nodes
    with_networks = getattr(ct, "_with_networks", False)
    used = new.used
    for nid, allocs in allocs_by_node.items():
        i = node_index.get(nid)
        if i is None:
            continue
        for alloc in allocs:
            if alloc.resources is not None:
                used[i] += _res_vec(alloc.resources)
            else:
                used[i] += _res_vec(alloc.shared_resources)
                for tr in alloc.task_resources.values():
                    used[i] += _res_vec(tr)
        if with_networks:
            node = nodes[i]
            new.bw_used[i] = 0
            new.port_words[i, :] = 0
            used_ports: Set[int] = set()

            def _account(nr: s.NetworkResource):
                new.bw_used[i] += nr.mbits
                for p in list(nr.reserved_ports) + list(nr.dynamic_ports):
                    if 0 <= p.value < MAX_VALID_PORT:
                        used_ports.add(p.value)

            if node.reserved is not None:
                for nr in node.reserved.networks or []:
                    _account(nr)
            for alloc in allocs:
                for tr in alloc.task_resources.values():
                    if tr.networks:
                        _account(tr.networks[0])
            for p in used_ports:
                new.port_words[i, p >> 5] |= np.uint32(1 << (p & 31))
            in_dyn = sum(1 for p in used_ports
                         if MIN_DYNAMIC_PORT <= p < MAX_DYNAMIC_PORT)
            new.dyn_free[i] = (MAX_DYNAMIC_PORT - MIN_DYNAMIC_PORT) - in_dyn
    return new


def with_usage(ct: ClusterTensors, used) -> ClusterTensors:
    """Clone the static cluster tensors with a caller-provided usage
    matrix — the device-resident delta path's twin of apply_alloc_usage
    (ops/resident.py maintains ``used`` incrementally instead of walking
    every live alloc).  Network accounting keeps the static baseline
    (TPUBatchScheduler._with_net_usage layers the resident network
    mirror on for a batch with network asks)."""
    import dataclasses as _dc

    new = _dc.replace(ct, used=used)
    _carry_host_attrs(ct, new)
    return new


def finalize_codebooks(ct: ClusterTensors, literals: Dict[str, Set[str]]) -> None:
    """Merge constraint literals into the per-target value sets, assign
    ordered codes, and fill the attr matrix."""
    for target, vals in literals.items():
        if target in ct._value_sets:  # type: ignore[attr-defined]
            ct._value_sets[target].update(vals)  # type: ignore[attr-defined]
    for target, vals in ct._value_sets.items():  # type: ignore[attr-defined]
        ct.value_codebooks[target] = {v: i for i, v in enumerate(sorted(vals))}
    for i, row in enumerate(ct._raw_rows):  # type: ignore[attr-defined]
        for target, j in ct.attr_index.items():
            val = row[target]
            if val is not None:
                ct.attr_values[i, j] = ct.value_codebooks[target][val]


# Operand → op-code for the vectorizable subset (feasible.go:433-458).
_VECTOR_OPS = {
    "=": OP_EQ, "==": OP_EQ, "is": OP_EQ,
    "!=": OP_NE, "not": OP_NE,
    "<": OP_LT, "<=": OP_LE, ">": OP_GT, ">=": OP_GE,
}


@dataclass
class PlacementSpec:
    """One unique (job, task group) placement spec with its expansion count —
    the reference's materializeTaskGroups dedup (util.go:22) turned into the
    batch axis."""

    job: s.Job
    tg: s.TaskGroup
    count: int = 0                      # expansion count (asks)
    ask: np.ndarray = None              # [4] int64
    priority: int = 50
    anti_affinity_penalty: float = 20.0
    distinct_hosts: bool = False
    drivers: Set[str] = field(default_factory=set)
    constraints: List[s.Constraint] = field(default_factory=list)
    datacenters: List[str] = field(default_factory=list)
    # Network asks (rank.go:190-238 per-task offer assignment):
    net_active: bool = False
    net_mbits: int = 0
    dyn_count: int = 0
    resv_ports: List[int] = field(default_factory=list)
    resv_in_dyn: int = 0
    net_asks: Dict[str, s.NetworkResource] = field(default_factory=dict)
    # distinct_property (propertyset.go:11): at most one natively; the
    # used-value set is filled by the batch scheduler from plan context.
    dp_target: Optional[str] = None
    dp_used_values: Set[str] = field(default_factory=set)
    # Non-empty → this spec cannot run on the device path; the owning eval
    # routes through the oracle instead of being silently mis-placed.
    needs_oracle: str = ""


def build_spec(job: s.Job, tg: s.TaskGroup, batch_penalty: bool) -> PlacementSpec:
    tup = task_group_constraints(tg)
    all_constraints = list(job.constraints) + list(tup.constraints)
    spec = PlacementSpec(
        job=job,
        tg=tg,
        count=0,
        ask=_res_vec(tup.size),
        priority=job.priority,
        anti_affinity_penalty=10.0 if batch_penalty else 20.0,
        distinct_hosts=any(
            c.operand == s.CONSTRAINT_DISTINCT_HOSTS for c in all_constraints),
        drivers=tup.drivers,
        constraints=all_constraints,
        datacenters=list(job.datacenters),
    )

    # Network asks: first network per task, like the oracle (rank.go:199).
    for t in tg.tasks:
        if t.resources is not None and t.resources.networks:
            ask_net = t.resources.networks[0]
            spec.net_asks[t.name] = ask_net
            spec.net_mbits += ask_net.mbits
            spec.dyn_count += len(ask_net.dynamic_ports)
            spec.resv_ports.extend(p.value for p in ask_net.reserved_ports)
    spec.net_active = bool(spec.net_asks)
    if spec.net_active:
        if len(spec.resv_ports) != len(set(spec.resv_ports)):
            spec.needs_oracle = "conflicting reserved ports within task group"
        if any(p < 0 or p >= MAX_VALID_PORT for p in spec.resv_ports):
            spec.needs_oracle = "reserved port out of range"
        spec.resv_in_dyn = sum(
            1 for p in set(spec.resv_ports)
            if MIN_DYNAMIC_PORT <= p < MAX_DYNAMIC_PORT)

    dp_cons = [c for c in all_constraints
               if c.operand == s.CONSTRAINT_DISTINCT_PROPERTY]
    if len(dp_cons) > 1:
        spec.needs_oracle = "multiple distinct_property constraints"
    elif dp_cons:
        con = dp_cons[0]
        if con in job.constraints and len(job.task_groups) > 1:
            # Job-level distinct_property spans task groups; the per-spec
            # used-value bitset cannot share across specs — oracle instead.
            spec.needs_oracle = "job-level distinct_property, multiple groups"
        else:
            spec.dp_target = con.ltarget
    return spec


@dataclass
class SpecTensors:
    """Device view of the unique placement specs, padded to ``u_pad``."""

    specs: List[PlacementSpec]
    u_real: int
    u_pad: int
    ask: np.ndarray              # [u_pad, 4] int32
    count: np.ndarray            # [u_pad] int32
    priority: np.ndarray         # [u_pad] int32
    penalty: np.ndarray          # [u_pad] float32
    distinct_hosts: np.ndarray   # [u_pad] bool
    dc_mask: np.ndarray          # [u_pad, n_dcs] bool
    constraint_attr: np.ndarray  # [u_pad, k_max] int32 column index
    constraint_op: np.ndarray    # [u_pad, k_max] int32 op code
    constraint_rhs: np.ndarray   # [u_pad, k_max] int32 rhs code
    precomp: np.ndarray          # [u_pad, n_pad] bool — non-vectorizable ANDs
    job_index: np.ndarray        # [u_pad] int32 — same-job specs share a row
    job_ids: List[str]
    # Network asks (zeros when the batch has none; w matches ct.port_words,
    # whose bits are ports or, with ``port_bits``, the batch's ports):
    net_active: np.ndarray = None   # [u_pad] bool
    net_mbits: np.ndarray = None    # [u_pad] int32
    dyn_need: np.ndarray = None     # [u_pad] int32 — dynamic + resv-in-dyn
    resv_words: np.ndarray = None   # [u_pad, w] uint32
    # distinct_property (V=1 when unused):
    dp_col: np.ndarray = None       # [u_pad] int32 — attr column or -1
    dp_active: np.ndarray = None    # [u_pad] bool
    dp_used: np.ndarray = None      # [u_pad, V] bool — value codes in use
    # (start, end) ``perf_counter`` stamps of each host-evaluated row
    # (``_host_row``) the batch's specs took, and how many of them were
    # served from a kept row with no node and no class evaluated.
    row_stamps: List[Tuple[float, float]] = field(default_factory=list)
    rows_reused: int = 0
    # (start, end) of the build of the batch's static-port bits
    # (TPUBatchScheduler._with_net_usage), where it asks any.
    port_stamps: List[Tuple[float, float]] = field(default_factory=list)


def encode_specs(
    specs: List[PlacementSpec],
    ct: ClusterTensors,
    nodes: Sequence[s.Node],
    spec_pad_multiple: int = 8,
    port_bits: Optional[Dict[int, int]] = None,
) -> SpecTensors:
    """Lower specs to tensors; split constraints into vectorizable triples
    and host-precomputed boolean rows (cached per computed class, mirroring
    EvalCache / FeasibilityWrapper semantics).

    ``port_bits``: the bit of each static port the batch asks for in
    ``ct.port_words`` (TPUBatchScheduler._with_net_usage); None when the
    words are the whole port space, a bit per port."""
    u_real = len(specs)
    u_pad = pow2_bucket(u_real, spec_pad_multiple)
    k_max = pow2_bucket(
        max([1] + [len(sp.constraints) + len(sp.drivers) for sp in specs]),
        minimum=2)

    ask = np.zeros((u_pad, RES_DIMS), dtype=np.int64)
    count = np.zeros(u_pad, dtype=np.int32)
    priority = np.zeros(u_pad, dtype=np.int32)
    penalty = np.zeros(u_pad, dtype=np.float32)
    distinct = np.zeros(u_pad, dtype=bool)
    n_dcs = pow2_bucket(max(1, len(ct.dc_codebook)), minimum=2)
    dc_mask = np.zeros((u_pad, n_dcs), dtype=bool)
    c_attr = np.zeros((u_pad, k_max), dtype=np.int32)
    c_op = np.zeros((u_pad, k_max), dtype=np.int32)   # OP_TRUE padding
    c_rhs = np.zeros((u_pad, k_max), dtype=np.int32)
    # Lazily materialized: most batches have no host-precomputed rows, and
    # a trivially-true [1,1] broadcast saves a U×N upload to the device.
    precomp = None

    row_stamps: List[Tuple[float, float]] = []
    rows_reused = 0
    eval_ctx = EvalContext(state=None, plan=s.Plan())  # caches only

    def _and_host_row(u, key, check, by_class=True):
        nonlocal precomp, rows_reused
        if precomp is None:
            precomp = np.ones((u_pad, ct.n_pad), dtype=bool)
        t_a = time.perf_counter()
        row, reused = _host_row(ct, nodes, key, check, by_class)
        precomp[u, :ct.n_real] &= row
        rows_reused += reused
        row_stamps.append((t_a, time.perf_counter()))

    def _and_driver_row(u, driver):
        key = f"driver.{driver}"

        def check(node):
            val = node.attributes.get(key)
            return bool(val is not None and _parse_bool(val))

        _and_host_row(u, ("driver", driver), check)

    job_ids: List[str] = []
    job_row: Dict[str, int] = {}
    job_index = np.zeros(u_pad, dtype=np.int32)

    w = ct.port_words.shape[1] if ct.port_words is not None else 1
    net_active = np.zeros(u_pad, dtype=bool)
    net_mbits = np.zeros(u_pad, dtype=np.int32)
    dyn_need = np.zeros(u_pad, dtype=np.int32)
    resv_words = np.zeros((u_pad, w), dtype=np.uint32)
    dp_col = np.full(u_pad, -1, dtype=np.int32)
    dp_active = np.zeros(u_pad, dtype=bool)
    v_max = 1
    for sp in specs:
        if sp.dp_target is not None and sp.dp_target in ct.value_codebooks:
            v_max = max(v_max, len(ct.value_codebooks[sp.dp_target]) + 1)
    v_pad = pow2_bucket(v_max, minimum=2) if v_max > 1 else 1
    dp_used = np.zeros((u_pad, v_pad), dtype=bool)

    for u, sp in enumerate(specs):
        ask[u] = sp.ask
        count[u] = sp.count
        priority[u] = sp.priority
        penalty[u] = sp.anti_affinity_penalty
        distinct[u] = sp.distinct_hosts
        for dc in sp.datacenters:
            code = ct.dc_codebook.get(dc)
            if code is not None:
                dc_mask[u, code] = True
        job_index[u] = job_row.setdefault(sp.job.id, len(job_row))

        if sp.net_active and (w > 1 or port_bits is not None):
            net_active[u] = True
            net_mbits[u] = sp.net_mbits
            dyn_need[u] = sp.dyn_count + sp.resv_in_dyn
            for p in set(sp.resv_ports):
                b = p if port_bits is None else port_bits[p]
                resv_words[u, b >> 5] |= np.uint32(1 << (b & 31))

        if sp.dp_target is not None:
            col = ct.attr_index.get(sp.dp_target)
            if col is not None:
                dp_col[u] = col
                dp_active[u] = True
                codebook = ct.value_codebooks.get(sp.dp_target, {})
                for val in sp.dp_used_values:
                    code = codebook.get(val)
                    if code is not None:
                        dp_used[u, code] = True

        k = 0
        # Drivers lower to EQ checks on interned "driver.X" columns when the
        # column exists; otherwise to precomp rows.
        for driver in sorted(sp.drivers):
            target = "${attr.driver." + driver + "}"
            col = ct.attr_index.get(target)
            if col is None:
                _and_driver_row(u, driver)
                continue
            # truthy values per strconv.ParseBool; precompute truth set codes
            truthy = {
                code for val, code in ct.value_codebooks[target].items()
                if _parse_bool(val)
            }
            if len(truthy) == 1:
                c_attr[u, k] = col
                c_op[u, k] = OP_EQ
                c_rhs[u, k] = next(iter(truthy))
                k += 1
            else:
                _and_driver_row(u, driver)

        for con in sp.constraints:
            if con.operand in (s.CONSTRAINT_DISTINCT_HOSTS,
                               s.CONSTRAINT_DISTINCT_PROPERTY):
                continue
            op_code = _VECTOR_OPS.get(con.operand)
            col = ct.attr_index.get(con.ltarget)
            rhs_literal = not con.rtarget.startswith("${")
            if op_code is not None and col is not None and rhs_literal:
                code = ct.value_codebooks[con.ltarget].get(con.rtarget, None)
                c_attr[u, k] = col
                c_op[u, k] = op_code
                c_rhs[u, k] = UNKNOWN_RHS if code is None else code
                k += 1
            else:
                # Host-evaluated per computed class (or per node if escaped):
                # the same caching the reference does (feasible.go:597).
                _and_host_row(
                    u, (con.ltarget, con.operand, con.rtarget),
                    lambda node, con=con: _check_on_node(eval_ctx, con,
                                                         node),
                    by_class=not _escapes_class(con))

    st = SpecTensors(
        specs=specs,
        u_real=u_real,
        u_pad=u_pad,
        ask=ask,
        count=count,
        priority=priority,
        penalty=penalty,
        distinct_hosts=distinct,
        dc_mask=dc_mask,
        constraint_attr=c_attr,
        constraint_op=c_op,
        constraint_rhs=c_rhs,
        precomp=(precomp if precomp is not None
                 else np.ones((1, 1), dtype=bool)),
        job_index=job_index,
        job_ids=list(job_row),
        net_active=net_active,
        net_mbits=net_mbits,
        dyn_need=dyn_need,
        resv_words=resv_words,
        dp_col=dp_col,
        dp_active=dp_active,
        dp_used=dp_used,
        row_stamps=row_stamps,
        rows_reused=rows_reused,
    )
    return st


def pad_specs(st: SpecTensors, u_pad: int, precomp: bool, n_pad: int
              ) -> SpecTensors:
    """``st`` with its spec axis padded to ``u_pad`` and, with
    ``precomp``, its host-row matrix materialized: the shapes of a program
    that is already compiled (kernels.choose_plan).  Padding rows ask for
    nothing (count 0, no constraint, no distinct_property); an all-true
    host-row matrix excludes nothing."""
    if precomp and st.precomp.shape == (1, 1):
        st = replace(st, precomp=np.ones((st.u_pad, n_pad), dtype=bool))
    grow = u_pad - st.u_pad
    if not grow:
        return st
    fills = {"precomp": True, "dp_col": -1}
    grown = {
        f.name: np.pad(v, [(0, grow)] + [(0, 0)] * (v.ndim - 1),
                       constant_values=fills.get(f.name, 0))
        for f in fields(st)
        if isinstance(v := getattr(st, f.name), np.ndarray) and v.ndim
        and v.shape[0] == st.u_pad and v.shape != (1, 1)}
    return replace(st, u_pad=u_pad, **grown)


def _escapes_class(constraint: s.Constraint) -> bool:
    from ..structs.node_class import _target_escapes

    return _target_escapes(constraint.ltarget) or _target_escapes(constraint.rtarget)


# Host rows kept per static encode before the oldest goes: a row is one
# bool per node, and a fleet's jobs share a small vocabulary of version,
# regexp and set constraints.
HOST_ROWS_KEPT = 256


class _HostRows:
    """The host-evaluated feasibility rows of one static encode, and the
    fleet's computed classes as ``(first, inverse, loners)``: a
    representative node of each class code, every node's position among
    them, and the nodes that have no computed class."""

    __slots__ = ("rows", "groups")

    def __init__(self) -> None:
        self.rows: Dict[Tuple, np.ndarray] = {}
        self.groups = None


def _host_row(ct: ClusterTensors, nodes: Sequence[s.Node], key: Tuple,
              check, by_class: bool) -> Tuple[np.ndarray, bool]:
    """One host-evaluated feasibility row, ``check(node)`` for every
    node, and whether it was served from a kept row.

    A row depends on node attributes alone, so it is kept under ``key``
    with the static cluster tensors it was decided on (``_host_rows``,
    shared by every per-batch clone): the tensors' cache key holds the
    node table's index, so a node that registers, drains or changes an
    attribute drops the rows with the tensors (batch_sched
    ``_CLUSTER_CACHE``).  With ``by_class`` the check runs once per
    computed class present in the fleet and is gathered by class code —
    upstream's FeasibilityWrapper / EvalCache unit of caching
    (feasible.go:597) — and per node only for nodes without a computed
    class; a check that escapes class semantics runs on every node."""
    kept: _HostRows = ct._host_rows  # type: ignore[attr-defined]
    row = kept.rows.get(key)
    if row is not None:
        return row, True
    if by_class:
        if kept.groups is None:
            _, first, inverse = np.unique(
                ct.class_code[:ct.n_real], return_index=True,
                return_inverse=True)
            loners = np.flatnonzero(np.fromiter(
                (not nodes[i].computed_class for i in first),
                bool, len(first))[inverse])
            kept.groups = (first.tolist(), inverse, loners.tolist())
        first, inverse, loners = kept.groups
        row = np.fromiter((check(nodes[i]) for i in first),
                          bool, len(first))[inverse]
        for i in loners:
            row[i] = check(nodes[i])
    else:
        row = np.fromiter(map(check, nodes), bool, len(nodes))
    row.flags.writeable = False
    if len(kept.rows) >= HOST_ROWS_KEPT:
        del kept.rows[next(iter(kept.rows))]
    kept.rows[key] = row
    return row, False


def _check_on_node(eval_ctx: EvalContext, con: s.Constraint, node: s.Node) -> bool:
    lval, lok = resolve_constraint_target(con.ltarget, node)
    if not lok:
        return False
    rval, rok = resolve_constraint_target(con.rtarget, node)
    if not rok:
        return False
    return check_constraint(eval_ctx, con.operand, lval, rval)


def collect_attr_targets(specs: List[PlacementSpec]) -> Tuple[List[str], Dict[str, Set[str]]]:
    """The set of constraint LTargets that lower to int compares, plus the
    literal RHS values to merge into each codebook."""
    targets: List[str] = []
    literals: Dict[str, Set[str]] = {}
    seen: Set[str] = set()
    for sp in specs:
        for driver in sp.drivers:
            t = "${attr.driver." + driver + "}"
            if t not in seen:
                seen.add(t)
                targets.append(t)
                literals.setdefault(t, set())
        if sp.dp_target is not None and sp.dp_target not in seen:
            seen.add(sp.dp_target)
            targets.append(sp.dp_target)
            literals.setdefault(sp.dp_target, set()).update(sp.dp_used_values)
        for con in sp.constraints:
            if con.operand not in _VECTOR_OPS:
                continue
            if con.rtarget.startswith("${"):
                continue
            if con.ltarget not in seen:
                seen.add(con.ltarget)
                targets.append(con.ltarget)
            literals.setdefault(con.ltarget, set()).add(con.rtarget)
    return targets, literals
