"""``distinct_property`` inside a placement pass: the dense one-hot form
of the four per-value-table accesses (``ops/kernels.ValueCodes``) held to
the scatter/gather form, which stays for ``v_pad`` above
``kernels.DP_DENSE_MAX_V`` and is the reference here.

Held: each access in both forms called directly, equal bit for bit; which
form a traced program holds on either side of the limit; a 64-spec batch
of the ``constrained-5k`` deployment's shrunk fleet placed identically by
both forms through the single-chip program and the fused mesh program.
Nothing here is a device number."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.deployments import constrained
from nomad_tpu.ops import kernels
from nomad_tpu.ops.batch_sched import TPUBatchScheduler
from nomad_tpu.ops.encode import MISSING
from nomad_tpu.parallel import make_node_mesh, sharded

from test_constrained import harness_with, reg_eval, shrunk_config
from test_mesh_sched import placements_with_scores

LIMIT = kernels.DP_DENSE_MAX_V
NEG = np.float32(kernels.NEG_INF)


def both_forms(codes, v_pad):
    """The same codes as ``ValueCodes`` in the dense and in the
    scatter/gather form, whatever ``value_codes`` would choose."""
    code = jnp.clip(jnp.asarray(codes), 0, v_pad - 1)
    hot = code[None, :] == jnp.arange(v_pad, dtype=jnp.int32)[:, None]
    return (kernels.ValueCodes(code, hot, v_pad),
            kernels.ValueCodes(code, None, v_pad))


def same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    return a.tobytes() == b.tobytes()


@pytest.mark.parametrize("n_pad", [128, 640, 5120])
@pytest.mark.parametrize("v_pad", [2, 8, 128, 256, LIMIT, 2 * LIMIT])
@pytest.mark.parametrize("seed", [1, 38])
def test_each_access_dense_equals_scatter_gather(seed, v_pad, n_pad):
    rng = np.random.default_rng(seed * 1000003 + v_pad * 7 + n_pad)
    codes = rng.integers(0, v_pad, size=n_pad).astype(np.int32)
    codes[rng.random(n_pad) < 0.05] = MISSING     # clipped to value 0
    # Few distinct scores, so nodes of one value tie; NEG_INF where a node
    # is not selected, as the pass masks it.
    scores = rng.integers(0, 4, size=n_pad).astype(np.float32) * 4.5
    node_idx = np.arange(n_pad, dtype=np.int32)
    big_idx = np.int32(n_pad + 1)
    selections = {"some": rng.random(n_pad) < 0.3,
                  "all": np.ones(n_pad, bool),
                  "empty": np.zeros(n_pad, bool)}
    used_rows = {"some": rng.random(v_pad) < 0.4,
                 "all_used": np.ones(v_pad, bool),
                 "none_used": np.zeros(v_pad, bool)}
    dense, plain = both_forms(codes, v_pad)

    for name, row in used_rows.items():
        assert same_bits(kernels.dp_used_lookup(dense, jnp.asarray(row)),
                         kernels.dp_used_lookup(plain, jnp.asarray(row))), name
    for name, sel in selections.items():
        for active in (True, False):                # an inactive spec
            hit = jnp.asarray(sel & active)
            assert same_bits(kernels.dp_used_update(dense, hit),
                             kernels.dp_used_update(plain, hit)), name
        sel_score = jnp.asarray(np.where(sel, scores, NEG))
        best = [kernels.dp_best_per_value(vc, sel_score, NEG, largest=True)
                for vc in (dense, plain)]
        assert same_bits(*best), name
        back = [kernels.dp_read_back(vc, best[1], NEG, largest=True)
                for vc in (dense, plain)]
        assert same_bits(*back), name
        cand = sel & (np.asarray(sel_score) >= np.asarray(back[1]))
        cidx = jnp.asarray(np.where(cand, node_idx, big_idx))
        first = [kernels.dp_best_per_value(vc, cidx, big_idx, largest=False)
                 for vc in (dense, plain)]
        assert same_bits(*first), name
        assert same_bits(*[
            kernels.dp_read_back(vc, first[1], big_idx, largest=False)
            for vc in (dense, plain)]), name
        # What the pass keeps: per value the best score, the lowest node
        # index among its ties.
        keep = cand & (node_idx == np.asarray(kernels.dp_read_back(
            dense, first[0], big_idx, largest=False)))
        code = np.clip(codes, 0, v_pad - 1)
        for v in np.unique(code[sel]):
            of_v = np.flatnonzero(sel & (code == v))
            want = of_v[np.argmax(scores[of_v])]    # first of the best
            assert np.flatnonzero(keep & (code == v)).tolist() == [want]
        assert keep.sum() == len(np.unique(code[sel]))


def _tiny_problem(v_pad, n=128, u=4):
    rng = np.random.default_rng(3)
    capacity = np.tile(np.array([4000, 8192, 100000, 150], np.int32), (n, 1))
    used = np.zeros((n, 4), np.int32)
    used[:, 0] = rng.integers(0, 2000, n)
    used[:, 1] = rng.integers(0, 4000, n)
    dp = kernels.DPTensors(
        col=jnp.asarray(np.array([0, -1, 0, -1], np.int32)),
        active=jnp.asarray(np.array([True, False, True, False])),
        used0=jnp.zeros((u, v_pad), bool),
        attr_values=jnp.asarray(
            (np.arange(n, dtype=np.int32) // 8)[:, None]))
    args = (jnp.ones((u, n), bool), jnp.asarray(used), jnp.asarray(capacity),
            jnp.asarray(capacity[:, :2].astype(np.float32)),
            jnp.asarray(np.tile(np.array([300, 600, 10, 0], np.int32),
                                (u, 1))),
            jnp.full(u, 5, jnp.int32), jnp.full(u, 20.0, jnp.float32),
            jnp.zeros(u, bool), jnp.arange(u, dtype=jnp.int32),
            jnp.zeros((u, n), jnp.int32), jax.random.PRNGKey(5))
    return args, dp


DP_SCOPES = ("dp_feasible", "dp_dedup", "dp_update")


def _primitives_by_scope(jaxpr, found):
    """{scope: {primitive names}} over a jaxpr and every jaxpr inside it."""
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack).split("/")
        for scope in DP_SCOPES:
            if scope in stack:
                found.setdefault(scope, set()).add(eqn.primitive.name)
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list))
                        else (param,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives_by_scope(inner, found)
    return found


@pytest.mark.parametrize("v_pad,serial", [(LIMIT, False), (2 * LIMIT, True)],
                         ids=["at_the_limit", "above_the_limit"])
def test_which_form_a_traced_program_holds(v_pad, serial):
    """Up to the limit no scatter and no gather is left inside the pass's
    ``dp_*`` scopes; above it the serial form is all there."""
    args, dp = _tiny_problem(v_pad)
    jaxpr = jax.make_jaxpr(
        lambda *a: kernels.placement_rounds(*a, dp=dp, slot_m=8))(*args)
    found = _primitives_by_scope(jaxpr.jaxpr, {})
    assert set(found) == set(DP_SCOPES)
    for scope, prims in found.items():
        has_serial = any(p.startswith("scatter") or p == "gather"
                         for p in prims)
        assert has_serial == serial, (scope, sorted(prims))
    assert kernels.dp_dense(v_pad) == (not serial)


def _batch_of_64(seed):
    config = shrunk_config()
    config["jobs"]["jobs"] = 64
    nodes = constrained.make_nodes(config)
    ids = constrained.backlog_ids(config, seed)
    assert {j.rsplit("-", 1)[1] for j in ids} == set(
        config["jobs"]["templates"])
    return nodes, [constrained.make_job(config, jid) for jid in ids]


@pytest.fixture
def force_limit(monkeypatch):
    """Set ``DP_DENSE_MAX_V`` and drop every traced program, which holds
    the form the limit gave it when it was traced."""
    def force(limit):
        monkeypatch.setattr(kernels, "DP_DENSE_MAX_V", limit)
        jax.clear_caches()
        sharded._FUSED_MESH_CACHE.clear()
    yield force
    jax.clear_caches()
    sharded._FUSED_MESH_CACHE.clear()


def test_a_64_spec_batch_places_identically_in_both_forms(force_limit,
                                                          monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_RNG_SEED", "38")
    nodes, jobs = _batch_of_64(38)
    mesh = make_node_mesh(jax.devices()[:2])
    placed = {}
    for limit in (0, 2 ** 30):
        force_limit(limit)
        for name, kw in (("single", {}), ("mesh", {"mesh": mesh})):
            h = harness_with(nodes, jobs)
            stats = TPUBatchScheduler(h.logger, h.snapshot(), h, **kw) \
                .schedule_batch([reg_eval(j) for j in jobs])
            assert stats.device_ran and stats.oracle_routed == 0
            assert stats.num_specs == 64 and stats.fused == 1
            assert stats.mesh_shards == (2 if kw else 0)
            assert stats.dp_specs > 0 and stats.multi_round_specs > 0
            assert stats.dp_dense_specs == (stats.dp_specs if limit else 0)
            placed[limit, name] = (
                placements_with_scores(h, jobs), stats.spec_passes,
                stats.multi_round_specs, stats.rounds)
    want = placed[0, "single"]
    assert sum(len(v) for v in want[0].values()) > 0
    for key, got in placed.items():
        assert got == want, key
