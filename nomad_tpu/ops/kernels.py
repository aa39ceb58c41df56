"""TPU batch-scheduling kernels: vectorized feasibility + scoring +
spec-major placement passes (SURVEY.md §7 steps 2-3).

Re-derivation of the reference iterator chain (scheduler/stack.go:37) as
masked tensor ops:

- feasibility  F[U,N] = AND_k check(op_k)  — ConstraintChecker/DriverChecker
  (feasible.go:355,92) as integer compares over ordered-interned codes,
  AND'ed with host-precomputed rows for version/regex/set_contains.
- scoring      S[U,N] = score_fit(used+ask) − penalty·collisions
  — BinPackIterator + JobAntiAffinityIterator (rank.go:130,247) as one fused
  elementwise expression over the whole matrix.
- placement    masked rank-and-commit passes with capacity feedback — the
  only sequential part.  The batch's specs are placed in order, each to
  its end before the next starts (``spec_major``), as the reference
  processes one evaluation after another; anti-affinity (20 > max
  binpack 18) means at most one alloc of a job lands per node per pass,
  so a pass places min(remaining, feasible) allocs of its spec and most
  specs take exactly one.

Everything is jittable; no data-dependent Python control flow
(lax.while_loop / lax.scan / lax.fori_loop only), static shapes from the
padded encodings in ops/encode.py.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .encode import (
    MISSING,
    OP_EQ,
    OP_GE,
    OP_GT,
    OP_LE,
    OP_LT,
    OP_NE,
    OP_PRECOMP,
    OP_TRUE,
    UNKNOWN_RHS,
)

NEG_INF = -1e30

# -- compile-cache audit (ISSUE 13) -----------------------------------------
#
# Recompiles are the silent killer at 10M nodes: one stray shape bucket
# costs tens of seconds of XLA time.  Every distinct static signature the
# placement programs are invoked with is recorded here — a new signature
# is (at most) one fresh XLA compile, an old one is a guaranteed cache
# hit — so `compile_signatures()` is an upper bound on placement-program
# compiles that a test can assert a ceiling on (tests/test_fused.py: a
# steady stream must stay within a fixed handful of shapes).
_COMPILE_SIGS = set()
COMPILES = 0


def note_signature(kind: str, sig: tuple) -> bool:
    """Record one program invocation signature; True when it is new
    (i.e. this call may trigger an XLA compile)."""
    global COMPILES
    key = (kind, sig)
    if key in _COMPILE_SIGS:
        return False
    _COMPILE_SIGS.add(key)
    COMPILES += 1
    return True


def compile_signatures() -> int:
    return COMPILES


# The calling thread's "XLA is about to compile" hook: a context-manager
# factory entered around every FIRST invocation of a program signature
# (a jit call traces and compiles synchronously, then enqueues).  The
# BatchWorker installs one that holds its evals' nack clocks — a cold
# shape bucket compiles for tens of seconds on a TPU, past the broker's
# nack timeout, and the clock guards processing, not compilation.
_COMPILE_GUARD = threading.local()


@contextlib.contextmanager
def compile_guard(guard):
    """Install ``guard`` for this thread's program calls."""
    prev = getattr(_COMPILE_GUARD, "fn", None)
    _COMPILE_GUARD.fn = guard
    try:
        yield
    finally:
        _COMPILE_GUARD.fn = prev


@contextlib.contextmanager
def program_call(kind: str, sig: tuple):
    """Wrap ONE placement-program invocation: records its signature
    (note_signature) and, when the signature is new — this call
    compiles — runs it inside the thread's compile guard."""
    guard = (getattr(_COMPILE_GUARD, "fn", None)
             if note_signature(kind, sig) else None)
    if guard is None:
        yield
    else:
        with guard():
            yield


def signature_kinds() -> dict:
    """Distinct recorded signatures per program kind — the debugging
    view behind the `batch.compiles` gauge: when a compile ceiling
    trips, this names WHICH program family leaked shapes."""
    out: dict = {}
    for kind, _sig in _COMPILE_SIGS:
        out[kind] = out.get(kind, 0) + 1
    return out


def reset_compile_signatures() -> None:
    """Test helper: zero the audit (does NOT clear jit caches)."""
    global COMPILES
    _COMPILE_SIGS.clear()
    COMPILES = 0
    with _PLAN_LOCK:
        _PLANS.clear()
        _PADDED.clear()


# -- shape-plan reuse ---------------------------------------------------------
#
# A batch's shape plan is (u_pad, slot_m, max_nnz, host rows): the pow2
# buckets of its specs, of its largest count and of its asks
# (encode.shape_plan), and whether it uploads a [U, N] matrix of
# host-evaluated constraint rows.  Each new plan is a new program, tens of
# seconds of XLA on a TPU, and a backlog drained in full batches ends in
# ONE smaller batch: the tail of 16 evaluations behind 29 batches of 64
# waited 20 s for a program of its own (chip run, PERF.md section 6, PR
# 29) where the full batch's program places it in 30 ms with 48 padding
# rows.  So a plan that has not been dispatched yet is served by the
# smallest dispatched plan of its shape class that is at least as large in
# every bucket; padding rows, unused slots and an all-true host-row matrix
# are inert, so placements are unchanged.  A shape that keeps coming earns
# its own program after ``PLAN_REUSE_LIMIT`` padded batches.
_PLANS: dict = {}      # shape class -> {plan, ...} dispatched so far
_PADDED: dict = {}     # (shape class, natural plan) -> batches served padded
_PLAN_LOCK = threading.Lock()
PLAN_REUSE_LIMIT = 16


def choose_plan(shape_class: tuple, natural: Tuple[int, int, int, int]
                ) -> Tuple[int, int, int, int]:
    """The plan to dispatch a batch with, given its own (``natural``)
    plan and everything else that selects the program (``shape_class``:
    node pad, networks, distinct_property, mesh).  Records the returned
    plan as dispatched."""
    with _PLAN_LOCK:
        seen = _PLANS.setdefault(shape_class, set())
        if natural not in seen:
            fits = [p for p in seen
                    if all(a >= b for a, b in zip(p, natural))
                    and bool(p[1]) == bool(natural[1])]
            served = _PADDED.get((shape_class, natural), 0)
            if fits and served < PLAN_REUSE_LIMIT:
                _PADDED[(shape_class, natural)] = served + 1
                return min(fits)
            seen.add(natural)
        return natural


def jitter_seed(rng_key: jnp.ndarray) -> jnp.ndarray:
    """One uint32 tie-break seed from a PRNG key (a single scalar draw;
    the per-(u, n) values come from the counter-based hash below)."""
    return jax.random.bits(rng_key, (), jnp.uint32)


def tie_jitter(seed: jnp.ndarray, u: jnp.ndarray,
               node_idx: jnp.ndarray) -> jnp.ndarray:
    """Deterministic per-(spec, node) tie-break jitter in [0, 1e-3).

    murmur3-style integer mix (fmix32) over (seed, u, node index): ~6
    integer ops per element versus ~48 for threefry — the full-matrix
    ``jax.random.uniform([U, N])`` this replaced cost 2.6s and a 256MB
    HBM buffer at the 1024x65536 mega-batch shape, dominating the whole
    device pass; now each committing spec hashes only its own row.

    Keyed on the GLOBAL node index, so a node shard computing its slice
    (parallel/sharded.py) gets bit-identical values to the single-chip
    kernel.  Decorrelates ties exactly like the reference's node
    shuffling (util.go:325) — magnitude too small to reorder materially
    different scores; avalanche quality is ample for tie-breaking.
    """
    x = (node_idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
         + u.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B) + seed)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return (x >> 8).astype(jnp.float32) * jnp.float32(1e-3 / (1 << 24))


def _byte_histogram_dense(cand: jnp.ndarray, byte: jnp.ndarray
                          ) -> jnp.ndarray:
    """hist[b] = #cand nodes whose current byte == b, as a [256, N]
    compare-and-reduce with N minor — a dense VPU reduction, the right
    shape for the TPU's lane-parallel units (no scatter, which the TPU
    backend serializes)."""
    bins = jnp.arange(256, dtype=jnp.uint32)
    return jnp.sum(cand[None, :] & (byte[None, :] == bins[:, None]),
                   axis=1, dtype=jnp.int32)


def _byte_histogram_scatter(cand: jnp.ndarray, byte: jnp.ndarray
                            ) -> jnp.ndarray:
    """Same histogram as a 256-bin scatter-add: N index-adds instead of
    256·N compares — 55x faster than the dense form on the CPU backend
    (measured 1.9ms vs 105ms per 4-pass select at N=65536), where
    scatter lowers to efficient serial stores."""
    return jnp.zeros(256, dtype=jnp.int32).at[byte.astype(jnp.int32)].add(
        cand.astype(jnp.int32))


def _byte_histogram(cand: jnp.ndarray, byte: jnp.ndarray) -> jnp.ndarray:
    """Backend-dispatched at trace time (jit caches are per-backend, so
    the choice is consistent for the lifetime of a compiled program).
    Both forms are exact, so placements are bit-identical either way —
    pinned by tests/test_tpu_kernels.py."""
    from ..utils.platform import is_tpu_platform

    if is_tpu_platform(jax.default_backend()):
        return _byte_histogram_dense(cand, byte)
    return _byte_histogram_scatter(cand, byte)


def _select_top_k(scored: jnp.ndarray, ok: jnp.ndarray,
                  k: jnp.ndarray) -> jnp.ndarray:
    """Boolean mask of the k highest-scored ok nodes, without a sort.

    Exact radix-quantile select on the monotone bit-space image of f32:
    IEEE-754 floats map to uint32 such that float order == unsigned
    order (set the sign bit for non-negatives, invert negatives), then
    the k-th largest value T is found byte-by-byte — 4 histogram passes
    (dense compare-and-reduce on TPU, scatter-add on CPU; see
    _byte_histogram), versus the 45 sequential threshold-bisection
    reduce passes this replaced (each a loop-carried [N] pass — latency-
    bound at ~2.7ms/select, the dominant device cost at N ≈ 50k).

    Selection is exact: nodes strictly above T are taken outright and
    the == T band fills in node-index order (cumsum), the same tie order
    a stable argsort over (-score) yields — so placements are
    bit-identical to both the argsort and bisection kernels, which the
    oracle/sharded differential tests pin down.
    """
    bits = lax.bitcast_convert_type(scored, jnp.uint32)
    ordered = jnp.where((bits >> 31) == 0,
                        bits | jnp.uint32(0x80000000), ~bits)
    bins_i = jnp.arange(256, dtype=jnp.int32)

    def radix_pass(cand, byte, above):
        hist = _byte_histogram(cand, byte)
        cnt_ge = above + jnp.cumsum(hist[::-1])[::-1]
        # cnt_ge is non-increasing in b and cnt_ge[0] >= k (the top-k all
        # carry the known prefix or better), so the threshold byte is the
        # last b with cnt_ge[b] >= k.
        t_b = jnp.sum((cnt_ge >= k).astype(jnp.int32)) - 1
        above = above + jnp.sum(jnp.where(bins_i > t_b, hist, 0))
        return t_b.astype(jnp.uint32), above

    above = jnp.int32(0)
    t1, above = radix_pass(ok, ordered >> 24, above)
    cand = ok & ((ordered >> 24) == t1)
    t2, above = radix_pass(cand, (ordered >> 16) & 0xFF, above)
    p16 = (t1 << 8) | t2
    cand = ok & ((ordered >> 16) == p16)
    t3, above = radix_pass(cand, (ordered >> 8) & 0xFF, above)
    p24 = (p16 << 8) | t3
    cand = ok & ((ordered >> 8) == p24)
    t4, above = radix_pass(cand, ordered & 0xFF, above)
    thresh = (p24 << 8) | t4

    # T is exactly the k-th largest ok value; `above` (< k) of the ok
    # nodes are strictly greater.  Fill the remainder from the == T band
    # in node-index order.  (A lax.cond skipping the cumsum when the
    # band exactly fills the need measured SLOWER end-to-end — the cond
    # breaks fusion; keep the straight-line form.)
    sel_gt = ok & (ordered > thresh)
    band = ok & (ordered == thresh)
    need = k - jnp.sum(sel_gt.astype(jnp.int32))
    csum = jnp.cumsum(band.astype(jnp.int32))
    return sel_gt | (band & (csum <= need))


@functools.partial(jax.jit, static_argnames=())
def feasibility_matrix(
    attr_values: jnp.ndarray,   # [N, K] int32 ordered codes, -1 missing
    eligible: jnp.ndarray,      # [N] bool
    dc_code: jnp.ndarray,       # [N] int32
    c_attr: jnp.ndarray,        # [U, Kc] int32 column index
    c_op: jnp.ndarray,          # [U, Kc] int32 op code
    c_rhs: jnp.ndarray,         # [U, Kc] int32 rhs code
    dc_mask: jnp.ndarray,       # [U, D] bool
    precomp: jnp.ndarray,       # [U, N] bool
) -> jnp.ndarray:
    """F[U, N]: static feasibility of spec u on node n.

    Scans over the (small) constraint axis, ANDing one vectorized compare at
    a time — peak memory stays at one [U, N] buffer.
    """
    n = attr_values.shape[0]
    u = c_attr.shape[0]
    kc = c_attr.shape[1]

    # Datacenter membership (readyNodesInDCs, util.go:224): gather each
    # node's dc bit from the spec's allowed-DC mask.
    dc_ok = jnp.take_along_axis(
        dc_mask, jnp.broadcast_to(dc_code[None, :], (u, n)), axis=1
    )  # [U, N]

    init = precomp & dc_ok & eligible[None, :]

    def body(carry, k):
        attr_col = c_attr[:, k]                       # [U]
        vals = attr_values[:, attr_col].T             # [U, N]
        rhs = c_rhs[:, k][:, None]                    # [U, 1]
        op = c_op[:, k][:, None]                      # [U, 1]

        missing = vals == MISSING
        unknown_rhs = rhs == UNKNOWN_RHS

        ok = jnp.where(op == OP_EQ, (vals == rhs) & ~unknown_rhs,
             jnp.where(op == OP_NE, (vals != rhs) | unknown_rhs,
             jnp.where(op == OP_LT, vals < rhs,
             jnp.where(op == OP_LE, vals <= rhs,
             jnp.where(op == OP_GT, vals > rhs,
             jnp.where(op == OP_GE, vals >= rhs,
                       jnp.ones_like(vals, dtype=bool)))))))
        # A missing LHS fails any real constraint (resolveConstraintTarget
        # returns !ok, feasible.go:383-391); OP_TRUE padding passes.
        ok = jnp.where(op == OP_TRUE, True, ok & ~missing)
        return carry & ok, None

    f, _ = lax.scan(body, init, jnp.arange(kc))
    return f


def _pow10(x: jnp.ndarray) -> jnp.ndarray:
    """10^x for the scoring sites.  Measured end-to-end, jnp.power with
    a constant base is NOT the bottleneck XLA's fusion makes it look
    like in isolation — an exp(x·ln10) rewrite benchmarked 8.7x faster
    standalone but REGRESSED the full placement program ~40% (fusion
    changed); keep the direct form and benchmark end-to-end before
    touching this again."""
    return jnp.power(10.0, x)


def _score_fit(
    used: jnp.ndarray,         # [N, 4] int32 — current usage incl. reserved
    ask: jnp.ndarray,          # [4] int32
    denom: jnp.ndarray,        # [N, 2] float32 — cpu/mem capacity minus reserved
) -> jnp.ndarray:
    """Google best-fit-v3 over all nodes at once (funcs.go:123 ScoreFit):
    20 − (10^freeCpuFrac + 10^freeMemFrac), clamped to [0, 18]."""
    after = used[:, :2].astype(jnp.float32) + ask[:2].astype(jnp.float32)
    safe_denom = jnp.where(denom == 0.0, 1.0, denom)
    frac = 1.0 - after / safe_denom
    frac = jnp.where(denom == 0.0, -jnp.inf, frac)
    total = _pow10(frac[:, 0]) + _pow10(frac[:, 1])
    score = 20.0 - total
    score = jnp.nan_to_num(score, nan=0.0, posinf=18.0, neginf=0.0)
    return jnp.clip(score, 0.0, 18.0)


class PlacementResult(NamedTuple):
    placements: jnp.ndarray   # [U, N] int32 — allocs of spec u committed on node n
    unplaced: jnp.ndarray     # [U] int32 — counts that found no feasible node
    used_after: jnp.ndarray   # [N, 4] int32 — final node usage
    rounds: jnp.ndarray       # [] int32 — most passes any one spec took
                              # (``passes.most``)
    # AllocMetric side-outputs (structs.go:4074 contract): the PURE
    # binpack score (rank.go:138 score_node "binpack") and the job
    # collision count at commit time — the host derives the separate
    # "job-anti-affinity" score entry from the latter (rank.go:167).
    commit_scores: jnp.ndarray = None      # [U, N] float32
    commit_collisions: jnp.ndarray = None  # [U, N] int32
    # Compact slot record (slot_m > 0): slots[u, j] = node index of spec
    # u's j-th committed alloc, appended in commit order — the COO
    # payload is built from THIS (one pass over U×M cells) instead of a
    # nonzero/compaction pass over the [U, N] matrix (measured 0.5s at
    # the 1024×10048 north-star shape vs ~50ms from slots).  -1 padding
    # beyond each spec's placed count.
    slots: jnp.ndarray = None              # [U, M] int32
    # Commit-aligned score side-outputs (slot_m > 0 AND with_scores):
    # the binpack score / collision count of each slot's commit — the
    # [U, N] commit_scores/commit_collisions carries compile away
    # entirely in this mode.
    slot_scores: jnp.ndarray = None        # [U, M] float32
    slot_coll: jnp.ndarray = None          # [U, M] int32
    # Pass accounting of the spec-major loop (see ``spec_major``).
    passes: "PassCounts" = None


class PassCounts(NamedTuple):
    """What ``spec_major`` counted over one batch."""

    most: jnp.ndarray     # [] int32 — most passes any one spec took
    total: jnp.ndarray    # [] int32 — passes summed over the specs
    multi: jnp.ndarray    # [] int32 — specs that took more than one pass


def spec_major(place_pass, carry, remaining_of, u_pad: int, max_rounds: int):
    """The placement loop, shared by the single-chip program and the mesh
    program (parallel/sharded.py): the batch's specs in order
    (host pre-sorts by priority desc — the broker's priority heap,
    eval_broker.go:43), each placed to its end before the next starts.

    ``place_pass(carry, u) -> (carry, placed, ran)`` is one pass of spec
    ``u`` over the node table: ``placed`` allocations committed, ``ran``
    false when the pass did not count (nothing left to place, or no node
    has room for the ask on capacity alone — the capacity early-exit).
    A spec is passed over again while its last pass ran and placed
    something, ``remaining_of(carry)[u]`` is above 0 and it has had fewer
    than ``max_rounds`` passes; then the next spec starts on the fleet
    as this one left it, which is what the reference's one evaluation
    after another does.  One flat ``lax.while_loop``: a spec that is done
    in one pass costs one iteration.

    Returns ``(carry, PassCounts)``."""
    zero = jnp.int32(0)

    def cond(state):
        return state[1] < u_pad

    def body(state):
        carry, u, passes, most, total, multi = state
        carry, placed, ran = place_pass(carry, u)
        passes = passes + ran.astype(jnp.int32)
        more = (ran & (placed > 0) & (remaining_of(carry)[u] > 0)
                & (passes < max_rounds))
        return (carry, jnp.where(more, u, u + 1),
                jnp.where(more, passes, zero), jnp.maximum(most, passes),
                total + ran.astype(jnp.int32),
                multi + (~more & (passes > 1)).astype(jnp.int32))

    carry, _, _, most, total, multi = lax.while_loop(
        cond, body, (carry, zero, zero, zero, zero, zero))
    return carry, PassCounts(most=most, total=total, multi=multi)


class NetTensors(NamedTuple):
    """Per-spec network asks + per-node port/bandwidth state
    (SURVEY §7 hard-part iii; reference rank.go:190-238 + network.go)."""

    active: jnp.ndarray      # [U] bool
    mbits: jnp.ndarray       # [U] int32
    dyn_need: jnp.ndarray    # [U] int32 — dynamic ports + reserved-in-dyn-range
    resv_words: jnp.ndarray  # [U, W] uint32 — reserved-port bitmask
    bw_cap: jnp.ndarray      # [N] int32
    bw_used: jnp.ndarray     # [N] int32
    dyn_free: jnp.ndarray    # [N] int32
    port_words: jnp.ndarray  # [N, W] uint32 — node used-port bitmaps


class DPTensors(NamedTuple):
    """distinct_property state (propertyset.go:11): per-spec property
    column + used-value-code bitsets."""

    col: jnp.ndarray         # [U] int32 — attr column, -1 = none
    active: jnp.ndarray      # [U] bool
    used0: jnp.ndarray       # [U, V] bool
    attr_values: jnp.ndarray  # [N, K] int32 — node attribute codes


def net_tensors(d: dict, bw_used, dyn_free, port_words) -> NetTensors:
    """A batch's ``NetTensors`` from its unpacked program buffers ``d``
    (the per-spec asks, each node's bandwidth cap) and the per-node state
    the program made of them."""
    return NetTensors(
        active=d["net_active"], mbits=d["net_mbits"],
        dyn_need=d["dyn_need"], resv_words=d["resv_words"],
        bw_cap=d["bw_cap"], bw_used=bw_used,
        dyn_free=dyn_free, port_words=port_words)


def dp_tensors(d: dict) -> DPTensors:
    """A batch's ``DPTensors`` from its unpacked program buffers ``d``."""
    return DPTensors(col=d["dp_col"], active=d["dp_active"],
                     used0=d["dp_used"], attr_values=d["attr"])


def _disabled_net(u_pad: int, n_pad: int) -> NetTensors:
    # Size-1 placeholders: with use_net=False the kernel never touches
    # these (python-level `if`, not jnp.where), so they only exist to
    # keep the carry pytree structure stable.
    return NetTensors(
        active=jnp.zeros(1, dtype=bool),
        mbits=jnp.zeros(1, dtype=jnp.int32),
        dyn_need=jnp.zeros(1, dtype=jnp.int32),
        resv_words=jnp.zeros((1, 1), dtype=jnp.uint32),
        bw_cap=jnp.zeros(1, dtype=jnp.int32),
        bw_used=jnp.zeros(1, dtype=jnp.int32),
        dyn_free=jnp.zeros(1, dtype=jnp.int32),
        port_words=jnp.zeros((1, 1), dtype=jnp.uint32),
    )


def _disabled_dp(u_pad: int, n_pad: int) -> DPTensors:
    return DPTensors(
        col=jnp.full(1, -1, dtype=jnp.int32),
        active=jnp.zeros(1, dtype=bool),
        used0=jnp.zeros((1, 1), dtype=bool),
        attr_values=jnp.full((1, 1), MISSING, dtype=jnp.int32),
    )


# The most value codes (``v_pad``, a static shape) up to which a pass reads
# and writes its per-property-value tables densely (``ValueCodes``).
# From one reading on the chip (TPU v5 lite, PERF.md section 6, PR 38):
# the seven accesses of a pass alone in a 2,000-iteration loop at n_pad
# 5,120, u_pad 64 took 247-278 us a pass in the scatter/gather form at
# every v_pad from 128 to 8,192 (7.5 ns an element, one at a time) and
# 6.5 / 8.9 / 14.1 / 26.7 / 51.6 us in the dense form at v_pad 128 / 256 /
# 512 / 1,024 / 2,048: linear at 0.025 us a value, so the forms cross
# near v_pad 10,000 whatever n_pad is (both are linear in it).  1,024 is
# the largest bucket read at a ninefold margin; the bound stays a tenth
# of the crossing because [v_pad, n_pad] is also what the compiler may
# have to hold if a reduction does not fuse (268 MB as f32 at the
# 65,536-node bucket).  A property with a value per node
# (``${node.unique.name}``: v_pad 8,192 on 5,000 nodes) keeps the
# scatter/gather form.
DP_DENSE_MAX_V = 1024


def dp_dense(v_pad: int) -> bool:
    """Whether a program with ``v_pad`` value codes takes the dense form
    of the four accesses below: decided at trace time from that static
    shape alone, the same on every backend."""
    return v_pad <= DP_DENSE_MAX_V


class ValueCodes(NamedTuple):
    """One pass's view of a spec's property column: every node's value
    code, and how the per-value tables (``[v_pad]``) are reached from it.

    ``hot[v, n] = (code[n] == v)``, ``[v_pad, n_pad]`` with ``n_pad``
    minor, makes every access below a dense compare-and-reduce on the
    VPU, the shape ``_byte_histogram_dense`` uses and for its reason: the
    TPU backend runs a scatter or a gather of ``n_pad`` elements through
    a ``[v_pad]`` table one element at a time.  ``hot`` None keeps the
    scatter/gather form, which costs ``n_pad`` steps whatever ``v_pad``
    is (a property with as many values as nodes would make the dense
    form quadratic) and is the reference the tests hold the dense form
    to.  ``max``, ``min`` and ``any`` are exact and exactly one ``v`` is
    hot per node, so both forms give the same bits, ``fill`` included."""

    code: jnp.ndarray     # [N] int32 in [0, v_pad)
    hot: jnp.ndarray      # [v_pad, N] bool, or None
    v_pad: int


def value_codes(codes: jnp.ndarray, v_pad: int) -> ValueCodes:
    """``codes`` ([N] int32, MISSING = -1) clipped into the tables' range,
    in the form ``dp_dense`` gives this ``v_pad``."""
    code = jnp.clip(codes, 0, v_pad - 1)
    hot = None
    if dp_dense(v_pad):
        hot = code[None, :] == jnp.arange(v_pad, dtype=jnp.int32)[:, None]
    return ValueCodes(code=code, hot=hot, v_pad=v_pad)


def dp_used_lookup(vc: ValueCodes, used_row: jnp.ndarray) -> jnp.ndarray:
    """[N] bool: is the node's value in the spec's used set
    (``used_row[code]``)."""
    if vc.hot is None:
        return used_row[vc.code]
    return jnp.any(vc.hot & used_row[:, None], axis=0)


def dp_best_per_value(vc: ValueCodes, vals: jnp.ndarray, fill, *,
                      largest: bool) -> jnp.ndarray:
    """[v_pad]: the largest (or smallest) of ``vals`` over the nodes of
    each value, ``fill`` for a value no node has (``fill`` is the
    reduction's identity for every ``vals`` the callers pass)."""
    if vc.hot is None:
        table = jnp.full(vc.v_pad, fill, dtype=vals.dtype).at[vc.code]
        return table.max(vals) if largest else table.min(vals)
    spread = jnp.where(vc.hot, vals[None, :], fill)
    return (jnp.max if largest else jnp.min)(spread, axis=1)


def dp_read_back(vc: ValueCodes, table: jnp.ndarray, fill, *,
                 largest: bool) -> jnp.ndarray:
    """[N]: each node's own value's entry of a per-value table
    (``table[code]``); ``fill`` and ``largest`` as the table was made."""
    if vc.hot is None:
        return table[vc.code]
    spread = jnp.where(vc.hot, table[:, None], fill)
    return (jnp.max if largest else jnp.min)(spread, axis=0)


def dp_used_update(vc: ValueCodes, hit: jnp.ndarray) -> jnp.ndarray:
    """[v_pad] bool: the values of the nodes in ``hit``."""
    if vc.hot is None:
        return jnp.zeros(vc.v_pad, dtype=bool).at[vc.code].max(hit)
    return jnp.any(vc.hot & hit[None, :], axis=1)


def dp_columns(attr_values: jnp.ndarray, col: jnp.ndarray) -> jnp.ndarray:
    """[U, N]: every spec's property column, taken once a batch (a gather
    of U contiguous rows) so that a pass reads its own as a row slice
    instead of a strided column of ``attr_values`` ([N, K])."""
    with jax.named_scope("property_columns"):
        return attr_values.T[jnp.clip(col, 0, attr_values.shape[1] - 1)]


class PassSeam:
    """Where one placement pass differs when its node table is one shard
    of a node mesh (``parallel/sharded.MeshSeam``).  This is the single
    chip's side, where every node is local: each method is the identity
    or the local reduction the single-chip program always made, so the
    seam traces no op of its own."""

    def __init__(self, node_idx: jnp.ndarray, n_nodes: int):
        self.node_idx = node_idx          # [N] int32 global id of each row
        self.slot_ids = node_idx          # what a slot records for a row
        self.big_idx = jnp.int32(n_nodes + 1)  # above every node id

    def total(self, local: jnp.ndarray) -> jnp.ndarray:
        """A count over the local rows, as the count over every node."""
        return local

    def any_node(self, mask: jnp.ndarray) -> jnp.ndarray:
        """Whether any node holds ``mask``."""
        return jnp.any(mask)

    def select(self, scored: jnp.ndarray, ok: jnp.ndarray,
               k: jnp.ndarray) -> jnp.ndarray:
        """The local rows among the k highest-scored ok nodes."""
        return _select_top_k(scored, ok, k)

    def value_max(self, table: jnp.ndarray) -> jnp.ndarray:
        """A per-value table of maxima, over every node."""
        return table

    def value_min(self, table: jnp.ndarray) -> jnp.ndarray:
        """A per-value table of minima, over every node."""
        return table

    def value_union(self, hit: jnp.ndarray) -> jnp.ndarray:
        """A per-value bitset, or-ed over every node."""
        return hit

    def slot_offset(self, offset: jnp.ndarray,
                    placed: jnp.ndarray) -> jnp.ndarray:
        """Where the local rows' commits start in the spec's slot row:
        ``offset`` allocs placed before this pass, ``placed`` of them
        committed on the local rows this pass."""
        return offset


def placement_pass(feas, capacity, denom, ask, count, penalty,
                   distinct_hosts, job_index, net: NetTensors,
                   dp: DPTensors, jit_seed, seam: PassSeam, *,
                   use_net: bool, use_dp: bool, with_scores: bool,
                   slot_m: int):
    """``place_pass(carry, u) -> (carry, placed, ran)`` for ``spec_major``:
    one pass of spec ``u`` over the node table, written once for the
    single-chip program and the fused mesh program, which hands over
    its node shard and a ``seam`` of its own.  The carry is ``(used,
    job_counts, remaining, placements, bw_used, port_words, dyn_free,
    dp_used, commit_scores, commit_coll, slots, slot_scores,
    slot_coll)``.

    A pass commits the spec's top-k scored nodes (k = remaining count,
    bounded by feasible nodes), one alloc per node.  Network accounting
    per (spec, node): bandwidth fit, reserved-port bitmap conflict, and
    dynamic-port-capacity checks, with commit updates to all three
    (rank.go:190-238; concrete dynamic port *values* are assigned
    host-side at finalize, which device-side capacity accounting makes
    safe).  distinct_property: a per-spec used-value bitset masks
    feasibility; a within-pass per-value best (``ValueCodes``: dense up
    to ``DP_DENSE_MAX_V`` value codes, scatter/gather above) keeps only
    the best-ranked node per property value (propertyset.go:150), and
    the nodes it drops wait for the spec's next pass."""
    v_pad = dp.used0.shape[1]
    node_idx = seam.node_idx
    big_idx = seam.big_idx
    dp_codes = dp_columns(dp.attr_values, dp.col) if use_dp else None

    def place_pass(carry, u):
        def try_place(carry):
            (used, job_counts, remaining_count, placements,
             bw_used, port_words, dyn_free, dp_used, commit_scores,
             commit_coll, slots, slot_scores, slot_coll) = carry

            with jax.named_scope("prefix"):
                cap_left = capacity - used                   # [N, 4]
                fits = jnp.all(ask[u][None, :] <= cap_left, axis=1)
                collisions = job_counts[job_index[u]]        # [N] int32
                ok = feas[u] & fits
                ok = ok & jnp.where(distinct_hosts[u], collisions == 0,
                                    True)

                # Network feasibility (bandwidth + reserved conflicts +
                # dynamic capacity); statically absent when the batch has
                # no network asks.
                if use_net:
                    bw_ok = bw_used + net.mbits[u] <= net.bw_cap
                    resv_hit = jnp.any(
                        (port_words & net.resv_words[u][None, :]) != 0,
                        axis=1)
                    dyn_ok = dyn_free >= net.dyn_need[u]
                    ok = ok & jnp.where(net.active[u],
                                        bw_ok & ~resv_hit & dyn_ok, True)

            # distinct_property feasibility: node must have the property
            # and its value must be unused (propertyset.go:150).
            if use_dp:
                with jax.named_scope("dp_feasible"):
                    codes = dp_codes[u]                           # [N]
                    vc = value_codes(codes, v_pad)
                    dp_ok = ((codes != MISSING)
                             & ~dp_used_lookup(vc, dp_used[u]))
                    ok = ok & jnp.where(dp.active[u], dp_ok, True)
            else:
                codes = None

            # Commit the top-k scored nodes (k = remaining count, bounded
            # by feasible nodes) — one alloc per node this pass.
            k = jnp.minimum(remaining_count[u],
                            seam.total(jnp.sum(ok).astype(jnp.int32)))
            carry, placed = lax.cond(
                k > 0, lambda c: commit(c, ok, collisions, codes, k),
                lambda c: (c, jnp.int32(0)), carry)
            # Capacity early-exit, per spec: a pass over a fleet on which
            # no node has room for the ask on capacity alone does not
            # count (net/dp/constraints are stricter, so this is a
            # necessary condition only and placements are unchanged).
            return carry, placed, seam.any_node(fits)

        def commit(carry, ok, collisions, codes, k):
            (used, job_counts, remaining_count, placements,
             bw_used, port_words, dyn_free, dp_used, commit_scores,
             commit_coll, slots, slot_scores, slot_coll) = carry
            with jax.named_scope("score"):
                base_score = _score_fit(used, ask[u], denom)
                score = (base_score
                         - penalty[u] * collisions.astype(jnp.float32))
                score = score + tie_jitter(jit_seed, u, node_idx)
                scored = jnp.where(ok, score, NEG_INF)

            # The k best ok nodes, ties to the lowest node index: on one
            # chip a radix select instead of a full argsort (same
            # selection, ~100x less device work at N≈50k).
            with jax.named_scope("select"):
                sel = seam.select(scored, ok, k)

            # Within-pass value dedup for distinct_property: among
            # selected nodes sharing a property value, keep only the
            # best-scored (ties by lowest node index — stable-sort order).
            if use_dp:
                with jax.named_scope("dp_dedup"):
                    # Made anew from the codes, not handed over from
                    # try_place: a [v_pad, N] operand of the cond would
                    # be written out and read back once a pass.
                    vc = value_codes(codes, v_pad)
                    neg = jnp.float32(NEG_INF)
                    sel_score = jnp.where(sel, scored, neg)
                    best_per_code = seam.value_max(dp_best_per_value(
                        vc, sel_score, neg, largest=True))
                    cand_dp = sel & (sel_score >= dp_read_back(
                        vc, best_per_code, neg, largest=True))
                    best_idx = seam.value_min(dp_best_per_value(
                        vc, jnp.where(cand_dp, node_idx, big_idx), big_idx,
                        largest=False))
                    keep_dp = cand_dp & (node_idx == dp_read_back(
                        vc, best_idx, big_idx, largest=False))
                    sel = jnp.where(dp.active[u], keep_dp, sel)

            with jax.named_scope("commit_usage"):
                sel_i = sel.astype(jnp.int32)
                placed = jnp.sum(sel_i)
                used = used + sel_i[:, None] * ask[u][None, :]
                job_counts = job_counts.at[job_index[u]].add(sel_i)
                if not slot_m:
                    # The dense [U, N] placement matrix only feeds the
                    # matrix-form compaction; in slot mode the slot record
                    # IS the placement output, so the carry compiles away.
                    placements = placements.at[u].add(sel_i)

            if slot_m:
                # Compact slot record: append this commit's node indices
                # to spec u's slot row in ascending-node order — the COO
                # payload is built from this, no nonzero pass later.
                with jax.named_scope("slots"):
                    pos = jnp.cumsum(sel.astype(jnp.int32))
                    offset = seam.slot_offset(        # placed so far
                        count[u] - remaining_count[u], placed)
                    dest = jnp.where(sel, offset + pos - 1,
                                     jnp.int32(slot_m))
                    slots = slots.at[u, dest].set(seam.slot_ids,
                                                  mode="drop")
                    if with_scores:
                        # Commit-aligned score record: same dest scatter,
                        # so the [U, N] score carries below compile away.
                        slot_scores = slot_scores.at[u, dest].set(
                            base_score, mode="drop")
                        slot_coll = slot_coll.at[u, dest].set(
                            collisions, mode="drop")

            with jax.named_scope("commit_usage"):
                placed = seam.total(placed)
                remaining_count = remaining_count.at[u].add(-placed)

                if use_net:
                    commit_net = net.active[u]
                    bw_used = bw_used + jnp.where(commit_net,
                                                  sel_i * net.mbits[u], 0)
                    port_words = jnp.where(
                        (commit_net & sel)[:, None],
                        port_words | net.resv_words[u][None, :],
                        port_words)
                    dyn_free = dyn_free - jnp.where(
                        commit_net, sel_i * net.dyn_need[u], 0)
            if use_dp:
                with jax.named_scope("dp_update"):
                    dp_upd = seam.value_union(
                        dp_used_update(vc, sel & dp.active[u]))
                    dp_used = lax.dynamic_update_index_in_dim(
                        dp_used, dp_used[u] | dp_upd, u, axis=0)
            # Commit-time AllocMetric side-outputs: pure binpack score and
            # the collision count behind any anti-affinity penalty.
            if with_scores and not slot_m:
                with jax.named_scope("commit_usage"):
                    commit_scores = commit_scores.at[u].set(jnp.where(
                        sel, base_score, commit_scores[u]))
                    commit_coll = commit_coll.at[u].set(jnp.where(
                        sel, collisions, commit_coll[u]))
            return (used, job_counts, remaining_count, placements,
                    bw_used, port_words, dyn_free, dp_used,
                    commit_scores, commit_coll, slots, slot_scores,
                    slot_coll), placed

        # Two-level skip, both REAL branches on TPU (the loop over specs
        # is sequential, not vmapped, so lax.cond doesn't get batched
        # into a select):
        #  - outer: remaining_count[u] == 0 (a padding row, or a spec
        #    with nothing to place) skips even the feasibility/fit
        #    prefix — a scalar test;
        #  - inner (in try_place): k == 0 (no feasible node under
        #    remaining capacity) skips the scoring transcendentals and
        #    the top-k select.
        # Neither branch commits anything, so placements stay
        # bit-identical to the unguarded kernel.  Both predicates are
        # global counts, the same on every shard of a mesh, so the
        # shards take one branch together.
        return lax.cond(
            carry[2][u] > 0, try_place,
            lambda c: (c, jnp.int32(0), jnp.bool_(False)), carry)

    return place_pass


def placement_rounds(
    feas: jnp.ndarray,         # [U, N] bool — static feasibility
    used0: jnp.ndarray,        # [N, 4] int32 — usage incl. reserved
    capacity: jnp.ndarray,     # [N, 4] int32
    denom: jnp.ndarray,        # [N, 2] float32
    ask: jnp.ndarray,          # [U, 4] int32
    count: jnp.ndarray,        # [U] int32
    penalty: jnp.ndarray,      # [U] float32
    distinct_hosts: jnp.ndarray,  # [U] bool
    job_index: jnp.ndarray,    # [U] int32 → row in job_counts
    job_counts0: jnp.ndarray,  # [J, N] int32 — existing allocs per (job, node)
    rng_key: jnp.ndarray,
    max_rounds: int = 256,
    net: "NetTensors" = None,
    dp: "DPTensors" = None,
    with_scores: bool = True,
    slot_m: int = 0,
) -> PlacementResult:
    """The sequential heart of the batch scheduler (see
    ``_placement_rounds_impl``).  ``net``/``dp`` default to None, which
    statically compiles the network/distinct_property code OUT of the
    program (a disabled-but-present path still costs per-spec gathers
    and scatters inside the scan).  ``with_scores=False`` drops the
    [U, N] commit-score/collision side-outputs (mega-batch shapes: two
    extra carry buffers of that size cost real HBM and compile time;
    counts in the result stay exact)."""
    u_pad, n_pad = feas.shape
    use_net = net is not None
    use_dp = dp is not None
    if net is None:
        net = _disabled_net(u_pad, n_pad)
    if dp is None:
        dp = _disabled_dp(u_pad, n_pad)
    return _placement_rounds_impl(
        feas, used0, capacity, denom, ask, count, penalty, distinct_hosts,
        job_index, job_counts0, rng_key, net, dp, max_rounds=max_rounds,
        with_scores=with_scores, use_net=use_net, use_dp=use_dp,
        slot_m=slot_m)


@functools.partial(jax.jit, static_argnames=("max_rounds", "with_scores",
                                             "use_net", "use_dp", "slot_m"))
def _placement_rounds_impl(
    feas: jnp.ndarray,
    used0: jnp.ndarray,
    capacity: jnp.ndarray,
    denom: jnp.ndarray,
    ask: jnp.ndarray,
    count: jnp.ndarray,
    penalty: jnp.ndarray,
    distinct_hosts: jnp.ndarray,
    job_index: jnp.ndarray,
    job_counts0: jnp.ndarray,
    rng_key: jnp.ndarray,
    net: NetTensors,
    dp: DPTensors,
    max_rounds: int = 256,
    with_scores: bool = True,
    use_net: bool = False,
    use_dp: bool = False,
    slot_m: int = 0,
) -> PlacementResult:
    """The sequential heart of the batch scheduler.

    Specs are placed in order, each to its end before the next starts
    (``spec_major``).  One pass of a spec places at most one alloc per
    node (justified by the anti-affinity penalty: a second same-job alloc
    on a node scores ≤ −2, below any empty feasible node), committing to
    its top-k scored nodes under remaining capacity; the spec is passed
    over again until its count is met, a pass places nothing (capacity
    exhausted) or ``max_rounds`` passes are spent.  ``rounds`` in the
    result is the most passes any one spec took.  What one pass does,
    network and distinct_property accounting included, is
    ``placement_pass``'s.
    """
    u_pad, n_pad = feas.shape

    jit_seed = jitter_seed(rng_key)
    place_pass = placement_pass(
        feas, capacity, denom, ask, count, penalty, distinct_hosts,
        job_index, net, dp, jit_seed,
        PassSeam(jnp.arange(n_pad, dtype=jnp.int32), n_pad),
        use_net=use_net, use_dp=use_dp, with_scores=with_scores,
        slot_m=slot_m)

    placements0 = jnp.zeros((u_pad, n_pad) if not slot_m else (1, 1),
                            dtype=jnp.int32)
    # Matrix-form score carries only when scores are wanted AND no slot
    # record exists (slot mode carries commit-aligned [U, M] scores
    # instead — two dense [U, N] buffers cheaper).
    score_shape = ((u_pad, n_pad) if with_scores and not slot_m
                   else (1, 1))
    scores0 = jnp.zeros(score_shape, dtype=jnp.float32)
    coll0 = jnp.zeros(score_shape, dtype=jnp.int32)
    slots0 = jnp.full((u_pad, slot_m) if slot_m else (1, 1), -1,
                      dtype=jnp.int32)
    sscore_shape = (u_pad, slot_m) if with_scores and slot_m else (1, 1)
    sscores0 = jnp.zeros(sscore_shape, dtype=jnp.float32)
    scoll0 = jnp.zeros(sscore_shape, dtype=jnp.int32)
    carry = (used0, job_counts0, count, placements0,
             net.bw_used, net.port_words, net.dyn_free, dp.used0, scores0,
             coll0, slots0, sscores0, scoll0)
    (used, job_counts, remaining, placements,
     _bw, _pw, _df, _dpu, commit_scores, commit_coll, slots, slot_scores,
     slot_coll), passes = spec_major(
        place_pass, carry, lambda c: c[2], u_pad, max_rounds)

    return PlacementResult(
        placements=placements,
        unplaced=remaining,
        used_after=used,
        rounds=passes.most,
        commit_scores=commit_scores,
        commit_collisions=commit_coll,
        slots=slots,
        slot_scores=slot_scores,
        slot_coll=slot_coll,
        passes=passes,
    )


# The result buffer's scalar row, in order (shared by the two fused
# programs' packers and the host's decode).
SCALARS = ("nnz", "rounds", "spec_passes", "multi_round_specs")


def pack_scalars(nnz, passes: PassCounts) -> jnp.ndarray:
    return jnp.stack([nnz, passes.most, passes.total,
                      passes.multi]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "meta_s", "meta_d", "u_pad", "n_pad", "with_networks", "with_dp",
    "with_scores", "max_rounds", "slot_m", "use_used_dev"),
    donate_argnums=(2, 3))
def _device_schedule(
    static_buf: jnp.ndarray,          # packed uint8, device-cached (xfer)
    dyn_buf: jnp.ndarray,             # packed uint8, per-batch upload
    used_dev: jnp.ndarray,            # [n_pad, 4] int32 DONATED mirror
    net_dev: jnp.ndarray,             # [n_pad, 2] int32 DONATED, or None
    *,
    meta_s,
    meta_d,
    u_pad: int,
    n_pad: int,
    with_networks: bool,
    with_dp: bool,
    with_scores: bool,
    max_rounds: int = 256,
    slot_m: int = 0,
    use_used_dev: bool = False,
):
    """Unpack + feasibility + placement rounds.

    The upload is split so the link carries only what changed: the
    static cluster buffer (attr/elig/dc/cap/denom + network baselines —
    the multi-MB part) is uploaded once per fleet state and cached as a
    device array by the caller; the per-batch dynamic buffer holds the
    U-sized spec tensors plus SPARSE alloc-usage deltas scattered onto
    the static baselines here.

    ``use_used_dev``: the usage matrix arrives as the DONATED
    device-resident mirror (ops/resident.py keeps it caught up in place
    via donated scatter-adds) instead of baseline+deltas — no per-batch
    usage upload, no materialized sum, and the caller gets the aliased
    array back to return to the resident slot.  With it off the donated
    slot is a [1, 4] dummy.

    Network asks (``with_networks``) read each node's bandwidth in use
    and free dynamic ports as the static baseline (the node's own
    reservation) plus what its live allocations hold: ``net_dev``, the
    DONATED device twin of the resident network mirror, aliased back out
    like ``used_dev``, or, without it (None), the rows the host uploaded
    whole (``bw_used``, ``dyn_free``).  ``port_words`` holds, per node,
    only the static ports the batch's specs ask for (one bit each, in the
    order ``encode.encode_specs`` numbered them): no argument's shape
    depends on how many nodes carry allocations."""
    from . import xfer

    d = xfer.unpack_device(static_buf, meta_s)
    d.update(xfer.unpack_device(dyn_buf, meta_d))
    # Quantized resource rows (ops/encode.py quantize_resource_rows):
    # the static buffer carries int16/int8 capacity + used-baseline plus
    # a [2, 4] per-matrix, per-dimension power-of-two scale codebook
    # (row 0 capacity, row 1 used); dequantization is one exact integer
    # multiply, so the placement math below is bit-identical to the
    # int32 path.  Keyed on the (static) meta, so the branch specializes
    # at trace time.
    if "res_scale" in d:
        scale = d.pop("res_scale")
        d["cap"] = d.pop("cap_q").astype(jnp.int32) * scale[0][None, :]
        d["used_base"] = (d.pop("used_base_q").astype(jnp.int32)
                          * scale[1][None, :])
    # Materialize the unpacked arrays before they enter the placement
    # while/scan: without the barrier XLA fuses the slice+bitcast decode
    # of the packed buffer into the loop BODY and re-decodes the whole
    # buffer every spec iteration (measured: 0.88s vs 0.04s for the same
    # placement program at U=1024, N=64k).
    d = dict(zip(d.keys(), lax.optimization_barrier(tuple(d.values()))))
    job_counts = scatter_job_counts(
        d["jc_rows"], d["jc_cols"], d["jc_vals"], u_pad=u_pad, n_pad=n_pad)
    feas = feasibility_matrix(
        d["attr"], d["elig"], d["dc"], d["c_attr"], d["c_op"], d["c_rhs"],
        d["dc_mask"], d["precomp"])
    if use_used_dev:
        used0 = used_dev
    else:
        # Alloc usage arrives as sparse (node, 4-dim) deltas over the
        # static reserved-only baseline; -1 rows are padding.  Padding
        # routes to an out-of-bounds index under mode="drop" — clipping
        # it to a real row would put DUPLICATE indices in the scatter.
        uvalid = d["u_rows"] >= 0
        uidx = jnp.where(uvalid, d["u_rows"], jnp.int32(n_pad))
        used0 = d["used_base"].at[uidx].add(d["u_vals"], mode="drop")
    net = None
    if with_networks:
        if net_dev is not None:
            bw_used = d["bw_used_base"] + net_dev[:, 0]
            dyn_free = d["dyn_free_base"] - net_dev[:, 1]
        else:
            bw_used, dyn_free = d["bw_used"], d["dyn_free"]
        net = net_tensors(d, bw_used, dyn_free, d["port_words"])
    dp = dp_tensors(d) if with_dp else None
    key = jax.random.PRNGKey(d["rng_seed"][0])
    result = placement_rounds(
        feas, used0, d["cap"], d["denom"], d["ask"], d["count"],
        d["penalty"], d["dh"], d["ji"], job_counts, key,
        max_rounds=max_rounds, net=net, dp=dp, with_scores=with_scores,
        slot_m=slot_m)
    # The donated mirrors ride back out UNCHANGED so XLA aliases them
    # input→output: the caller re-installs the very same device buffers
    # into the resident slot (zero copies across the batch round-trip).
    return result, feas, used_dev, net_dev


def _slots_coo_gather(slots: jnp.ndarray, slot_scores: jnp.ndarray,
                      slot_coll: jnp.ndarray, *, out_rows: int,
                      with_scores: bool, compact_u16: bool):
    """COO from the commit-aligned slot record: a GATHER over the output
    rows (searchsorted on the per-spec prefix sums) instead of a nonzero
    over the U×N placement matrix — 0.5s → ~15ms at the 1024×10048
    north-star shape; a scatter formulation of the same thing measured
    0.26s (XLA CPU scatters are serial and bounds-checked).

    Shared contract with the node-mesh program: the sharded fused pass
    (parallel/sharded.sharded_fused_pass) builds the SAME commit-ordered
    slot record (per-shard partials at globally disjoint positions,
    merged by one psum) and runs this very expression on it, so the two
    paths' COO payloads — and therefore placements and AllocMetric
    scores — are byte-identical by construction.

    Entries are per-ALLOC (counts ≡ 1, so a node committed in two
    rounds appears twice), rows ascending by construction (per-spec
    contiguous slot prefixes in spec order), scores aligned with their
    commits.  Rows beyond nnz are -1 padding (the host reads only the
    [:nnz] prefix).  Returns (coo [out_rows, C], nnz)."""
    u_pad, m = slots.shape
    valid_src = slots >= 0                          # [U, M] — contiguous
    placed = jnp.sum(valid_src, axis=1).astype(jnp.int32)
    csum = jnp.cumsum(placed)                       # [U]
    nnz = csum[-1]
    i = jnp.arange(out_rows, dtype=jnp.int32)
    u = jnp.searchsorted(csum, i, side="right").astype(jnp.int32)
    offs = csum - placed                            # per-spec start
    uc = jnp.clip(u, 0, u_pad - 1)
    j = jnp.clip(i - offs[uc], 0, m - 1)
    valid = i < nnz
    rows = jnp.where(valid, uc, -1)
    cols = jnp.where(valid, slots[uc, j], 0)
    counts = valid.astype(jnp.int32)
    dt = jnp.uint16 if compact_u16 else jnp.int32
    coo_cols = [rows.astype(dt), cols.astype(dt), counts.astype(dt)]
    if with_scores:
        sc = jnp.where(valid, slot_scores[uc, j], 0.0)
        co = jnp.where(valid, slot_coll[uc, j], 0)
        coo_cols += [lax.bitcast_convert_type(sc, jnp.int32), co]
    return jnp.stack(coo_cols, axis=1), nnz


@functools.partial(jax.jit, static_argnames=("out_rows", "with_scores",
                                             "compact_u16"))
def slots_to_coo(slots: jnp.ndarray, slot_scores: jnp.ndarray,
                 slot_coll: jnp.ndarray, *, out_rows: int,
                 with_scores: bool, compact_u16: bool):
    """Standalone jitted slot→COO gather for the fused overflow path:
    when nnz exceeds the payload window, the host dispatches this over
    the device-resident slot record and prefix-fetches exactly the rows
    it needs — fetch bytes stay proportional to placements, not to the
    [U, M] record size."""
    return _slots_coo_gather(slots, slot_scores, slot_coll,
                             out_rows=out_rows, with_scores=with_scores,
                             compact_u16=compact_u16)


def _compact_from_slots(result: PlacementResult, *, out_rows: int,
                        with_scores: bool, compact_u16: bool):
    return _slots_coo_gather(result.slots, result.slot_scores,
                             result.slot_coll, out_rows=out_rows,
                             with_scores=with_scores,
                             compact_u16=compact_u16)


def _compact_coo(result: PlacementResult, *, u_pad: int, n_pad: int,
                 with_scores: bool, max_nnz: int, compact_u16: bool):
    """COO compaction by a nonzero over the [U, N] placement matrix
    (the fused program's matrix mode, slot_m == 0).  Returns
    (coo [max_nnz, C], nnz scalar)."""
    rows, cols = jnp.nonzero(result.placements, size=max_nnz, fill_value=-1)
    valid = rows >= 0
    nnz = jnp.sum(valid.astype(jnp.int32))
    r = jnp.clip(rows, 0, u_pad - 1)
    c = jnp.clip(cols, 0, n_pad - 1)
    counts = jnp.where(valid, result.placements[r, c], 0)
    dt = jnp.uint16 if compact_u16 else jnp.int32
    coo_cols = [rows.astype(dt), cols.astype(dt), counts.astype(dt)]
    if with_scores:
        sc = jnp.where(valid, result.commit_scores[r, c], 0.0)
        co = jnp.where(valid, result.commit_collisions[r, c], 0)
        coo_cols += [lax.bitcast_convert_type(sc, jnp.int32), co]
    return jnp.stack(coo_cols, axis=1), nnz


# Fused result-buffer COO window: the single transfer carries at most
# this many payload bytes; batches whose nnz exceeds the window (rare —
# it takes >8MB of placements) pay one extra prefix fetch from the
# device-resident full COO.
FUSED_WINDOW_BYTES = 8 << 20


def fused_window(max_nnz: int, *, with_scores: bool,
                 compact_u16: bool) -> int:
    bytes_per_row = (5 if with_scores else 3) * (2 if compact_u16 else 4)
    window = max_nnz
    while window * bytes_per_row > FUSED_WINDOW_BYTES and window > 8:
        window //= 2
    return window


def fused_layout(u_pad: int, *, window_nnz: int, with_scores: bool,
                 compact_u16: bool):
    """Layout of the fused score-and-commit result buffer: summary
    (unplaced + feas_count + the ``SCALARS`` row) AND the COO placement
    payload window in ONE packed uint8 buffer, so the whole batch
    result crosses the link in a single transfer (ops/xfer.py layout():
    both sides compute the offsets independently)."""
    from . import xfer

    ncols = 5 if with_scores else 3
    return xfer.layout({
        "unplaced": ("i32", (u_pad,)),
        "feas_count": ("i32", (u_pad,)),
        "scalars": ("i32", (len(SCALARS),)),
        "coo": ("u16" if compact_u16 else "i32", (window_nnz, ncols)),
    })


@functools.partial(jax.jit, static_argnames=(
    "meta_s", "meta_d", "u_pad", "n_pad", "with_networks", "with_dp",
    "with_scores", "max_nnz", "max_rounds", "slot_m", "compact_u16",
    "window_nnz", "use_used_dev"),
    donate_argnums=(2, 3))
def _fused_score_commit(
    static_buf: jnp.ndarray,
    dyn_buf: jnp.ndarray,
    used_dev: jnp.ndarray,
    net_dev: jnp.ndarray,
    *,
    meta_s,
    meta_d,
    u_pad: int,
    n_pad: int,
    with_networks: bool,
    with_dp: bool,
    with_scores: bool,
    max_nnz: int,
    max_rounds: int = 256,
    slot_m: int = 0,
    compact_u16: bool = False,
    window_nnz: int = 0,
    use_used_dev: bool = False,
):
    """ONE device dispatch for the whole batch: unpack (+ dequantize) →
    feasibility → spec-major capacity-feedback placement passes → COO
    compaction (from the commit-aligned slot record when slot_m) →
    single packed result buffer.  ``used_dev`` is the DONATED
    device-resident usage mirror (a [1, 4] dummy when use_used_dev is
    off) and ``net_dev`` the donated network mirror (or None), returned
    aliased as the last two outputs."""
    result, feas, used_out, net_out = _device_schedule(
        static_buf, dyn_buf, used_dev, net_dev, meta_s=meta_s,
        meta_d=meta_d,
        u_pad=u_pad, n_pad=n_pad, with_networks=with_networks,
        with_dp=with_dp, with_scores=with_scores, max_rounds=max_rounds,
        slot_m=slot_m, use_used_dev=use_used_dev)
    from . import xfer

    feas_count = jnp.sum(feas, axis=1).astype(jnp.int32)
    if slot_m:
        # The payload window is gathered directly (no full-size COO is
        # ever materialized); the raw slot record rides along as the
        # overflow source — device-resident, fetched only when nnz
        # exceeds the window.
        coo_win, nnz = _compact_from_slots(
            result, out_rows=window_nnz, with_scores=with_scores,
            compact_u16=compact_u16)
        aux = (result.slots, result.slot_scores, result.slot_coll)
    else:
        coo_full, nnz = _compact_coo(
            result, u_pad=u_pad, n_pad=n_pad, with_scores=with_scores,
            max_nnz=max_nnz, compact_u16=compact_u16)
        coo_win = coo_full[:window_nnz]
        aux = coo_full
    buf, _ = xfer.pack_device({
        "unplaced": result.unplaced,
        "feas_count": feas_count,
        "scalars": pack_scalars(nnz, result.passes),
        "coo": coo_win,
    })
    return buf, aux, feas, used_out, net_out


def fused_pass(
    static_buf: jnp.ndarray,
    dyn_buf: jnp.ndarray,
    used_dev: jnp.ndarray = None,
    net_dev: jnp.ndarray = None,
    *,
    meta_s,
    meta_d,
    u_pad: int,
    n_pad: int,
    with_networks: bool,
    with_dp: bool,
    with_scores: bool,
    max_nnz: int,
    max_rounds: int = 256,
    slot_m: int = 0,
):
    """Fused score-and-commit entry: returns (packed result buffer,
    full COO on device, feas on device, result layout meta, used_out).
    The caller fetches the packed buffer with ONE jax.device_get and
    decodes host-side with xfer.unpack_host(buf, meta).  ``aux`` is the
    device-resident overflow source — the full COO (matrix mode) or the
    raw slot record triple (slot mode) — touched only when nnz
    overflows the payload window; ``feas`` only for the rare lazy
    failure-forensics rows.  ``used_dev`` (optional) is the donated
    device-resident usage mirror; ``used_out`` is the aliased buffer to
    hand back to ops/resident.py (None when no mirror was passed — the
    sparse-delta upload path); ``net_dev`` / ``net_out`` the same for
    the network mirror."""
    compact_u16 = (not with_scores and u_pad <= 65536
                   and n_pad <= 65536 and max_rounds < 65536)
    window_nnz = fused_window(max_nnz, with_scores=with_scores,
                              compact_u16=compact_u16)
    use_used_dev = used_dev is not None
    if used_dev is None:
        used_dev = jnp.zeros((1, 4), dtype=jnp.int32)
    with program_call("fused_pass", (
            meta_s, meta_d, u_pad, n_pad, with_networks, with_dp,
            with_scores, max_nnz, max_rounds, slot_m, compact_u16,
            window_nnz, use_used_dev, net_dev is not None)):
        buf, aux, feas, used_out, net_out = _fused_score_commit(
            static_buf, dyn_buf, used_dev, net_dev, meta_s=meta_s,
            meta_d=meta_d,
            u_pad=u_pad, n_pad=n_pad, with_networks=with_networks,
            with_dp=with_dp, with_scores=with_scores, max_nnz=max_nnz,
            max_rounds=max_rounds, slot_m=slot_m, compact_u16=compact_u16,
            window_nnz=window_nnz, use_used_dev=use_used_dev)
    meta = fused_layout(u_pad, window_nnz=window_nnz,
                        with_scores=with_scores, compact_u16=compact_u16)
    return (buf, aux, feas, meta, (used_out if use_used_dev else None),
            net_out)


@functools.partial(jax.jit, static_argnames=("u_pad", "n_pad"))
def scatter_job_counts(
    rows: jnp.ndarray,   # [K] int32, -1 padding
    cols: jnp.ndarray,   # [K] int32
    vals: jnp.ndarray,   # [K] int32
    u_pad: int,
    n_pad: int,
) -> jnp.ndarray:
    """Build the dense per-(job,node) count matrix on device from a sparse
    host upload — the dense matrix is U×N and mostly zeros."""
    valid = rows >= 0
    r = jnp.clip(rows, 0, u_pad - 1)
    c = jnp.clip(cols, 0, n_pad - 1)
    out = jnp.zeros((u_pad, n_pad), dtype=jnp.int32)
    return out.at[r, c].add(jnp.where(valid, vals, 0))


@jax.jit
def batch_allocs_fit(
    capacity: jnp.ndarray,   # [N, 4] int32
    used: jnp.ndarray,       # [N, 4] int32 — proposed usage incl. reserved
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Vectorized plan-verification re-check (plan_apply.go:327
    evaluateNodePlan / funcs.go:60 AllocsFit): fit[n] plus the first
    exhausted dimension index (-1 if fit)."""
    over = used > capacity                    # [N, 4]
    fit = ~jnp.any(over, axis=1)
    first_dim = jnp.argmax(over, axis=1).astype(jnp.int32)
    return fit, jnp.where(fit, -1, first_dim)
