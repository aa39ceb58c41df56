"""Multi-chip sharding tests on the virtual 8-device CPU mesh
(SURVEY.md §4 item 3: multi-node without a real cluster)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu.parallel import (
    make_node_mesh,
    sharded_placement_rounds,
    sharded_schedule_step,
)
from nomad_tpu.ops.kernels import placement_rounds

# Heavy integration/differential module: quick tier skips it (pytest.ini).
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must force 8 virtual devices"
    return make_node_mesh()


def _mk_problem(n=256, u=4, seed=0):
    rng = np.random.default_rng(seed)
    capacity = np.tile(np.array([4000, 8192, 102400, 150], dtype=np.int32), (n, 1))
    used = np.zeros((n, 4), dtype=np.int32)
    used[:, 0] = rng.integers(0, 2000, n)
    used[:, 1] = rng.integers(0, 4096, n)
    denom = capacity[:, :2].astype(np.float32)
    feas = rng.random((u, n)) < 0.8
    ask = np.tile(np.array([500, 256, 150, 0], dtype=np.int32), (u, 1))
    count = np.full(u, 20, dtype=np.int32)
    return feas, used, capacity, denom, ask, count


def _mk_full_problem(n=256, u=12, j=6, seed=11, tight=False):
    """Non-trivial problem: multiple specs per job (anti-affinity collisions
    matter), distinct_hosts on some specs, pre-existing job counts, and
    counts high enough to need capacity feedback across specs."""
    rng = np.random.default_rng(seed)
    capacity = np.tile(np.array([4000, 8192, 102400, 150], dtype=np.int32), (n, 1))
    used = np.zeros((n, 4), dtype=np.int32)
    used[:, 0] = rng.integers(0, 3000 if tight else 2000, n)
    used[:, 1] = rng.integers(0, 6144 if tight else 4096, n)
    denom = capacity[:, :2].astype(np.float32)
    feas = rng.random((u, n)) < 0.7
    ask = np.stack([
        np.array([rng.integers(200, 900), rng.integers(128, 1024), 150, 0],
                 dtype=np.int32)
        for _ in range(u)
    ])
    count = rng.integers(4, 24, u).astype(np.int32)
    penalty = np.where(rng.random(u) < 0.5, 20.0, 10.0).astype(np.float32)
    distinct = rng.random(u) < 0.3
    job_index = rng.integers(0, j, u).astype(np.int32)
    job_counts = (rng.random((j, n)) < 0.05).astype(np.int32)
    return (feas, used, capacity, denom, ask, count, penalty, distinct,
            job_index, job_counts)


@pytest.mark.parametrize("seed,tight,k_cand", [
    (11, False, 8),   # k_cand·D = 64 < N=256: real local-top-k truncation
    (23, True, 16),   # tight capacity + truncation
    (57, False, 32),  # full candidate set (k_cand·D == N)
])
def test_sharded_placements_equal_single_chip(mesh, seed, tight, k_cand):
    """Differential test (VERDICT r1 item 2): the node-sharded kernel must
    produce *identical* placements to the single-chip kernel — same
    anti-affinity, distinct_hosts, job_counts, and round-loop semantics.
    k_cand < N/D cases exercise the local top-k candidate truncation (the
    kernel's only approximation axis); counts stay ≤ k_cand so equality is
    guaranteed."""
    (feas, used, capacity, denom, ask, count, penalty, distinct,
     job_index, job_counts) = _mk_full_problem(seed=seed, tight=tight)
    count = np.minimum(count, k_cand)  # equality guarantee: commit ≤ k_cand
    key = jax.random.PRNGKey(seed)

    single = placement_rounds(
        jnp.asarray(feas), jnp.asarray(used), jnp.asarray(capacity),
        jnp.asarray(denom), jnp.asarray(ask), jnp.asarray(count),
        jnp.asarray(penalty), jnp.asarray(distinct), jnp.asarray(job_index),
        jnp.asarray(job_counts), key)

    shard = sharded_placement_rounds(
        mesh, jnp.asarray(feas), jnp.asarray(used), jnp.asarray(capacity),
        jnp.asarray(denom), jnp.asarray(ask), jnp.asarray(count),
        jnp.asarray(penalty), jnp.asarray(distinct), jnp.asarray(job_index),
        jnp.asarray(job_counts), key, k_cand=k_cand)

    np.testing.assert_array_equal(
        np.asarray(shard.placements), np.asarray(single.placements))
    np.testing.assert_array_equal(
        np.asarray(shard.unplaced), np.asarray(single.unplaced))
    np.testing.assert_array_equal(
        np.asarray(shard.used_after), np.asarray(single.used_after))
    # sanity: the problem actually exercised the semantics
    assert np.asarray(single.placements).sum() > 0
    assert np.asarray(single.rounds) >= 1


def test_sharded_distinct_hosts_and_anti_affinity(mesh):
    """Distinct-hosts specs never land on a node that already holds an alloc
    of the same job; anti-affinity spreads same-job specs."""
    (feas, used, capacity, denom, ask, count, penalty, distinct,
     job_index, job_counts) = _mk_full_problem(seed=99)
    distinct[:] = True
    result = sharded_placement_rounds(
        mesh, jnp.asarray(feas), jnp.asarray(used), jnp.asarray(capacity),
        jnp.asarray(denom), jnp.asarray(ask), jnp.asarray(count),
        jnp.asarray(penalty), jnp.asarray(distinct), jnp.asarray(job_index),
        jnp.asarray(job_counts), jax.random.PRNGKey(7), k_cand=32)
    placements = np.asarray(result.placements)
    # per (job, node): existing count + all placements of that job ≤ 1 + ...
    # distinct_hosts ⇒ a spec's placements avoid nodes with prior job allocs,
    # and no node receives two allocs of the same job in total.
    j = job_counts.shape[0]
    for ji in range(j):
        total = job_counts[ji].copy()
        for u_i in np.where(job_index == ji)[0]:
            total = total + placements[u_i]
        assert total.max() <= 1, f"job {ji} violated distinct_hosts"


def test_sharded_schedule_step_end_to_end(mesh):
    feas, used, capacity, denom, ask, count = _mk_problem(seed=5)
    placements, used_after = sharded_schedule_step(
        mesh, jnp.asarray(feas), jnp.asarray(used), jnp.asarray(capacity),
        jnp.asarray(denom), jnp.asarray(ask), jnp.asarray(count), k=16)
    placements = np.asarray(placements)
    used_after = np.asarray(used_after)
    # all counts placed (capacity is ample)
    assert placements.sum() == count.sum()
    # no overcommit on any node/dim
    assert np.all(used_after <= capacity)
    # placements only on feasible nodes
    for u_i in range(4):
        assert np.all(feas[u_i][placements[u_i] > 0])


def _mk_net_tensors(n, u, seed=0, w=4):
    """Small-port-space NetTensors: per-spec bandwidth/reserved-port/dyn
    asks + per-node port state (mirrors ops/kernels.NetTensors shapes)."""
    import jax.numpy as jnp

    from nomad_tpu.ops.kernels import NetTensors

    rng = np.random.default_rng(seed)
    active = rng.random(u) < 0.7
    mbits = np.where(active, rng.integers(10, 200, u), 0).astype(np.int32)
    dyn_need = np.where(active, rng.integers(0, 3, u), 0).astype(np.int32)
    resv_words = np.zeros((u, w), dtype=np.uint32)
    for i in range(u):
        if active[i] and rng.random() < 0.6:
            bit = int(rng.integers(0, 32 * w))
            resv_words[i, bit // 32] |= np.uint32(1 << (bit % 32))
    bw_cap = rng.integers(100, 1000, n).astype(np.int32)
    bw_cap[rng.random(n) < 0.1] = -1           # no network device
    bw_used = rng.integers(0, 100, n).astype(np.int32)
    dyn_free = rng.integers(0, 50, n).astype(np.int32)
    port_words = np.zeros((n, w), dtype=np.uint32)
    for i in range(n):
        for _ in range(int(rng.integers(0, 4))):
            bit = int(rng.integers(0, 32 * w))
            port_words[i, bit // 32] |= np.uint32(1 << (bit % 32))
    return NetTensors(
        active=jnp.asarray(active), mbits=jnp.asarray(mbits),
        dyn_need=jnp.asarray(dyn_need), resv_words=jnp.asarray(resv_words),
        bw_cap=jnp.asarray(bw_cap), bw_used=jnp.asarray(bw_used),
        dyn_free=jnp.asarray(dyn_free), port_words=jnp.asarray(port_words))


def _mk_dp_tensors(n, u, seed=0, v=16, k_attr=2):
    """DPTensors: per-spec distinct_property columns + used-value bitsets
    over a small interned value space."""
    import jax.numpy as jnp

    from nomad_tpu.ops.encode import MISSING
    from nomad_tpu.ops.kernels import DPTensors

    rng = np.random.default_rng(seed)
    col = rng.integers(0, k_attr, u).astype(np.int32)
    active = rng.random(u) < 0.6
    used0 = (rng.random((u, v)) < 0.15)
    attr = rng.integers(0, v, (n, k_attr)).astype(np.int32)
    attr[rng.random((n, k_attr)) < 0.05] = MISSING
    return DPTensors(col=jnp.asarray(col), active=jnp.asarray(active),
                     used0=jnp.asarray(used0), attr_values=jnp.asarray(attr))


@pytest.mark.parametrize("seed", [3, 17])
def test_sharded_networks_equal_single_chip(mesh, seed):
    """Feature parity (VERDICT r2 item 3): bandwidth, reserved-port and
    dynamic-capacity accounting on the sharded path must produce the
    SAME placements as the single-chip kernel."""
    (feas, used, capacity, denom, ask, count, penalty, distinct,
     job_index, job_counts) = _mk_full_problem(seed=seed)
    count = np.minimum(count, 16)
    u, n = feas.shape
    net = _mk_net_tensors(n, u, seed=seed)
    key = jax.random.PRNGKey(seed)

    single = placement_rounds(
        jnp.asarray(feas), jnp.asarray(used), jnp.asarray(capacity),
        jnp.asarray(denom), jnp.asarray(ask), jnp.asarray(count),
        jnp.asarray(penalty), jnp.asarray(distinct), jnp.asarray(job_index),
        jnp.asarray(job_counts), key, net=net)
    shard = sharded_placement_rounds(
        mesh, jnp.asarray(feas), jnp.asarray(used), jnp.asarray(capacity),
        jnp.asarray(denom), jnp.asarray(ask), jnp.asarray(count),
        jnp.asarray(penalty), jnp.asarray(distinct), jnp.asarray(job_index),
        jnp.asarray(job_counts), key, k_cand=16, net=net)

    np.testing.assert_array_equal(
        np.asarray(shard.placements), np.asarray(single.placements))
    np.testing.assert_array_equal(
        np.asarray(shard.unplaced), np.asarray(single.unplaced))
    assert np.asarray(single.placements).sum() > 0


@pytest.mark.parametrize("seed", [5, 29])
def test_sharded_distinct_property_equal_single_chip(mesh, seed):
    """distinct_property parity: the cross-shard best-per-value dedup
    (pmax/pmin) must keep exactly the winner the single-chip
    scatter-max/min picks, including global-node-index tie-breaks."""
    (feas, used, capacity, denom, ask, count, penalty, distinct,
     job_index, job_counts) = _mk_full_problem(seed=seed)
    count = np.minimum(count, 16)
    u, n = feas.shape
    dp = _mk_dp_tensors(n, u, seed=seed)
    key = jax.random.PRNGKey(seed)

    single = placement_rounds(
        jnp.asarray(feas), jnp.asarray(used), jnp.asarray(capacity),
        jnp.asarray(denom), jnp.asarray(ask), jnp.asarray(count),
        jnp.asarray(penalty), jnp.asarray(distinct), jnp.asarray(job_index),
        jnp.asarray(job_counts), key, dp=dp)
    shard = sharded_placement_rounds(
        mesh, jnp.asarray(feas), jnp.asarray(used), jnp.asarray(capacity),
        jnp.asarray(denom), jnp.asarray(ask), jnp.asarray(count),
        jnp.asarray(penalty), jnp.asarray(distinct), jnp.asarray(job_index),
        jnp.asarray(job_counts), key, k_cand=16, dp=dp)

    np.testing.assert_array_equal(
        np.asarray(shard.placements), np.asarray(single.placements))
    np.testing.assert_array_equal(
        np.asarray(shard.unplaced), np.asarray(single.unplaced))
    placed_dp = np.asarray(
        single.placements)[np.asarray(dp.active)].sum()
    assert placed_dp > 0, "no dp-active spec placed; test is vacuous"


def test_sharded_under_commit_converges_to_single_chip(mesh):
    """k_cand under-commit path (VERDICT r2 item 3): a spec needing more
    than k_cand·D placements per round under-commits and finishes over
    later rounds.  Each round contributes at most k_cand nodes PER SHARD,
    so a shard holding more than k_cand x rounds of the global top-count
    legitimately trades those slots to other shards' next-best nodes —
    the under-commit result is an approximation, not a bit-copy.  What
    must hold exactly: full placement (ample capacity), exact unplaced
    accounting, no overcommit, and bin-pack quality within the 0.5%
    budget of the single-chip kernel's global top-count selection."""
    n, u = 1024, 1
    rng = np.random.default_rng(41)
    capacity = np.tile(np.array([4000, 8192, 102400, 150], np.int32), (n, 1))
    used = np.zeros((n, 4), np.int32)
    # Distinct per-node usage ⇒ distinct binpack scores ⇒ no f32 ties.
    used[:, 0] = rng.permutation(n) * 3
    used[:, 1] = rng.permutation(n) * 4
    denom = capacity[:, :2].astype(np.float32)
    feas = (rng.random((u, n)) < 0.9)
    ask = np.array([[500, 256, 150, 0]], np.int32)
    count = np.array([300], np.int32)          # ≫ k_cand·D = 64
    penalty = np.array([20.0], np.float32)
    distinct = np.zeros(u, bool)
    job_index = np.zeros(u, np.int32)
    job_counts = np.zeros((u, n), np.int32)
    key = jax.random.PRNGKey(13)

    single = placement_rounds(
        jnp.asarray(feas), jnp.asarray(used), jnp.asarray(capacity),
        jnp.asarray(denom), jnp.asarray(ask), jnp.asarray(count),
        jnp.asarray(penalty), jnp.asarray(distinct), jnp.asarray(job_index),
        jnp.asarray(job_counts), key)
    shard = sharded_placement_rounds(
        mesh, jnp.asarray(feas), jnp.asarray(used), jnp.asarray(capacity),
        jnp.asarray(denom), jnp.asarray(ask), jnp.asarray(count),
        jnp.asarray(penalty), jnp.asarray(distinct), jnp.asarray(job_index),
        jnp.asarray(job_counts), key, k_cand=8)

    assert int(np.asarray(shard.rounds)) > int(np.asarray(single.rounds)), \
        "under-commit path not exercised (increase count or drop k_cand)"
    placements = np.asarray(shard.placements)
    np.testing.assert_array_equal(
        np.asarray(shard.unplaced), np.asarray(single.unplaced))
    assert placements.sum() == int(np.asarray(single.placements).sum()) == 300
    assert np.all(np.asarray(shard.used_after) <= capacity)

    def quality(used_after_arr):
        frac = 1.0 - used_after_arr[:, :2].astype(np.float64) / denom
        score = 20.0 - (10.0 ** frac[:, 0] + 10.0 ** frac[:, 1])
        return np.clip(score, 0.0, 18.0).sum()

    q_single = quality(np.asarray(single.used_after))
    q_shard = quality(np.asarray(shard.used_after))
    assert q_shard >= 0.995 * q_single


def test_sharded_contended_multi_round_at_4k_nodes(mesh):
    """Contended multi-round workload at 4k virtual nodes (VERDICT r2
    item 3): many specs compete for scarce capacity across rounds.  The
    sharded result must respect every invariant (no overcommit, exact
    unplaced accounting, distinct_hosts) and its bin-pack quality must
    track the single-chip kernel."""
    n, u, j = 4096, 24, 8
    rng = np.random.default_rng(77)
    capacity = np.tile(np.array([4000, 8192, 102400, 150], np.int32), (n, 1))
    used = np.zeros((n, 4), np.int32)
    used[:, 0] = rng.integers(1000, 3500, n)   # 80-95% contended fleet
    used[:, 1] = rng.integers(2048, 7168, n)
    denom = capacity[:, :2].astype(np.float32)
    feas = (rng.random((u, n)) < 0.8)
    ask = np.stack([
        np.array([rng.integers(300, 800), rng.integers(256, 1024), 150, 0],
                 np.int32) for _ in range(u)])
    count = rng.integers(64, 256, u).astype(np.int32)
    penalty = np.full(u, 20.0, np.float32)
    distinct = rng.random(u) < 0.25
    job_index = rng.integers(0, j, u).astype(np.int32)
    job_counts = np.zeros((j, n), np.int32)
    key = jax.random.PRNGKey(19)

    single = placement_rounds(
        jnp.asarray(feas), jnp.asarray(used), jnp.asarray(capacity),
        jnp.asarray(denom), jnp.asarray(ask), jnp.asarray(count),
        jnp.asarray(penalty), jnp.asarray(distinct), jnp.asarray(job_index),
        jnp.asarray(job_counts), key)
    shard = sharded_placement_rounds(
        mesh, jnp.asarray(feas), jnp.asarray(used), jnp.asarray(capacity),
        jnp.asarray(denom), jnp.asarray(ask), jnp.asarray(count),
        jnp.asarray(penalty), jnp.asarray(distinct), jnp.asarray(job_index),
        jnp.asarray(job_counts), key, k_cand=16)

    placements = np.asarray(shard.placements)
    used_after = np.asarray(shard.used_after)
    # Exact accounting: capacity respected, unplaced + placed == count.
    assert np.all(used_after <= capacity)
    np.testing.assert_array_equal(
        placements.sum(axis=1) + np.asarray(shard.unplaced),
        count)
    # distinct_hosts respected
    for u_i in np.where(distinct)[0]:
        assert placements[u_i].max() <= 1
    # Same total throughput and bin-pack quality within the 0.5% budget
    # of the single-chip kernel (ordering may differ under contention
    # when specs exceed k_cand·D per round).
    single_placed = int(np.asarray(single.placements).sum())
    shard_placed = int(placements.sum())
    assert shard_placed >= 0.995 * single_placed

    def quality(used_after_arr):
        frac = 1.0 - used_after_arr[:, :2].astype(np.float64) / denom
        score = 20.0 - (10.0 ** frac[:, 0] + 10.0 ** frac[:, 1])
        return np.clip(score, 0.0, 18.0).sum()

    q_single = quality(np.asarray(single.used_after))
    q_shard = quality(used_after)
    assert q_shard >= 0.995 * q_single


def test_driver_dryrun_composition(mesh):
    """Pin the EXACT composition the driver's multichip artifact runs —
    ``jax.jit`` over ``functools.partial(sharded_placement_rounds, mesh)``
    with the dryrun's shapes — so a regression in that path (r03: the
    artifact hung while the direct-call tests stayed green) fails in CI,
    not in the driver. Deadline-guarded: a recurrence of the hang must
    FAIL here, not stall the suite."""
    import signal

    import __graft_entry__ as g  # repo root is on sys.path via conftest

    def _timeout(signum, frame):
        raise TimeoutError("dryrun composition exceeded 120s — "
                           "the r03 hang is back")

    old = signal.signal(signal.SIGALRM, _timeout)
    signal.alarm(120)
    try:
        g._dryrun_multichip_impl(8)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
