"""The ``constrained-5k`` deployment at a tiny size on the CPU backend, and
the placement order it forced.

The device program places a batch's specs in order, each to its end
before the next (``ops/kernels.spec_major``), which is what the plain
reference (``benchmarks/reference.py``) replays and what upstream's one
evaluation after another does.  Held here: the batch path against the
reference and the CPU ``GenericScheduler`` oracle at 1, 4 and 64
evaluations to a batch; a batch that holds a multi-pass
``distinct_property`` spec against the same specs placed one batch each;
the deployment module's own constraint evaluator against the program's
feasibility matrix.  Nothing here is a device number."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, manifest
from benchmarks.deployments import constrained
from nomad_tpu.ops import encode
from nomad_tpu.ops.batch_sched import TPUBatchScheduler
from nomad_tpu.ops.kernels import (
    DPTensors,
    feasibility_matrix,
    placement_rounds,
)
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.generic import GenericScheduler
from nomad_tpu.structs import structs as s

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def shrunk_config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "constrained-5k.json")) as fh:
        config = json.load(fh)
    return constrained.shrink(config)


def reg_eval(job):
    return s.Evaluation(
        id=s.generate_uuid(), priority=job.priority, type=job.type,
        triggered_by=s.EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
        status=s.EVAL_STATUS_PENDING)


def harness_with(nodes, jobs):
    h = Harness()
    for node in nodes:
        h.state.upsert_node(h.next_index(), node.copy())
    for job in jobs:
        h.state.upsert_job(h.next_index(), job)
    return h


def live_nodes(h, job):
    return [a.node_id for a in h.state.allocs_by_job(None, job.id, True)
            if not a.terminal_status()]


def compare_served(h, config, ids, jobs):
    """What the harness's store holds, through the module's ``compare``."""
    served = check.Served(jobs=[])
    for jid, job in zip(ids, jobs):
        rows = [a for a in h.state.allocs_by_job(None, job.id, True)
                if not a.terminal_status()]
        if len(rows) != constrained.wants(config, jid):
            served.wrong_count += 1
        served.jobs.append(constrained.placed_job(
            config, jid, constrained.node_indices(
                config, [a.node_id for a in rows]), rows))
    return constrained.compare(served, config)


def test_the_shrunk_fleet_keeps_every_pool_template_and_stanza():
    config = shrunk_config()
    full = manifest.load_config(manifest.load_manifest(), "constrained-5k")
    assert set(config["cluster"]["pools"]) == set(full["cluster"]["pools"])
    assert set(config["jobs"]["templates"]) == set(full["jobs"]["templates"])
    pools = np.bincount(constrained._pool_index(config))
    assert len(pools) == 3 and pools.min() > 0
    drawn = {jid.rsplit("-", 1)[1]
             for jid in constrained.backlog_ids(config, 1)}
    assert drawn == set(config["jobs"]["templates"])
    ops = {op for tpl in config["jobs"]["templates"].values()
           for _, op, _ in tpl["constraints"]}
    assert ops == {"=", "version", "regexp", "distinct_hosts",
                   "distinct_property"}
    for jid in constrained.backlog_ids(config, 1)[:20]:
        feasible, distinct = constrained.rows(config, jid)
        admitted = (config["cluster"]["nodes"] if feasible is None
                    else int(feasible.sum()))
        groups = (admitted if distinct is None else len(set(
            distinct[feasible if feasible is not None else slice(None)])))
        assert constrained.wants(config, jid) <= min(admitted, groups)


@pytest.mark.parametrize("seed", [7, 2900000011])
@pytest.mark.parametrize("batch_size", [1, 4, 64])
def test_batch_path_against_reference_and_oracle(batch_size, seed,
                                                 monkeypatch):
    """Every job of the shrunk backlog through ``TPUBatchScheduler`` in
    batches of ``batch_size``; the served placements are replayed by the
    plain reference in commit order with the module's own rows."""
    monkeypatch.setenv("NOMAD_TPU_RNG_SEED", str(seed & 0x7FFFFFFF))
    config = shrunk_config()
    nodes = constrained.make_nodes(config)
    ids = constrained.backlog_ids(config, seed)
    jobs = [constrained.make_job(config, jid) for jid in ids]
    h = harness_with(nodes, jobs)
    multi_pass = routed = 0
    for lo in range(0, len(jobs), batch_size):
        sched = TPUBatchScheduler(h.logger, h.snapshot(), h)
        stats = sched.schedule_batch(
            [reg_eval(j) for j in jobs[lo:lo + batch_size]])
        multi_pass += stats.multi_round_specs
        routed += stats.oracle_routed
        assert stats.spec_passes >= stats.num_specs >= 1
        assert stats.rounds >= 1
    assert routed == 0
    assert multi_pass > 0, "no spec took a second pass: nothing was tested"

    compared = compare_served(h, config, ids, jobs)
    over = {k: v for k, v in compared.items() if v["value"] > v["limit"]}
    assert not over, over
    assert compared["score_gap"]["limit"] == 0.01
    for name in ("allocs_on_excluded_nodes",
                 "distinct_hosts_mates_on_one_node",
                 "distinct_property_mates_on_one_rack",
                 "infeasible_allocs", "nodes_over_capacity",
                 "evals_wrong_count"):
        assert compared[name] == {"value": 0, "limit": 0}, name

    # The CPU oracle, one evaluation after another: placed counts equal.
    h_o = harness_with(nodes, jobs)
    for job in jobs:
        GenericScheduler(h_o.logger, h_o.snapshot(), h_o,
                         batch=False).process(reg_eval(job))
    for job in jobs:
        assert len(live_nodes(h, job)) == len(live_nodes(h_o, job)), job.id


def _two_spec_problem():
    """16 nodes in 4 racks of 4, scores falling with the node index (the
    fuller a node the better it scores).  Spec 0 asks 3 allocations, one
    per rack: its top-3 all lie in rack 0, so it takes three passes and
    ends on nodes 0, 4 and 8.  Spec 1 is plain, asks 3 and competes for
    the same nodes: placed after spec 0 it takes the nodes spec 0 has
    just filled further (0 and 4; 8 still trails node 1)."""
    n, u = 16, 2
    capacity = np.tile(np.array([4000, 8192, 100000, 150], np.int32), (n, 1))
    used = np.zeros((n, 4), np.int32)
    used[:, 0] = 2000 - 100 * np.arange(n)
    used[:, 1] = 4000 - 200 * np.arange(n)
    return dict(
        feas=np.ones((u, n), bool), used=used, capacity=capacity,
        denom=capacity[:, :2].astype(np.float32),
        ask=np.array([[300, 600, 10, 0], [200, 400, 10, 0]], np.int32),
        count=np.array([3, 3], np.int32),
        penalty=np.full(u, 20.0, np.float32),
        distinct=np.zeros(u, bool), job_index=np.arange(u, dtype=np.int32),
        job_counts=np.zeros((u, n), np.int32),
        dp=dict(col=np.array([0, -1], np.int32),
                active=np.array([True, False]),
                used0=np.zeros((u, 8), bool),
                attr_values=(np.arange(n, dtype=np.int32) // 4)[:, None]))


def _place(p, count, used):
    dp = DPTensors(**{k: jnp.asarray(v) for k, v in p["dp"].items()})
    return placement_rounds(
        jnp.asarray(p["feas"]), jnp.asarray(used), jnp.asarray(p["capacity"]),
        jnp.asarray(p["denom"]), jnp.asarray(p["ask"]), jnp.asarray(count),
        jnp.asarray(p["penalty"]), jnp.asarray(p["distinct"]),
        jnp.asarray(p["job_index"]), jnp.asarray(p["job_counts"]),
        jax.random.PRNGKey(5), dp=dp)


def test_a_batch_with_a_multi_pass_spec_equals_its_specs_one_batch_each():
    p = _two_spec_problem()
    both = _place(p, p["count"], p["used"])
    # The same two rows (so the same tie-break jitter), one spec at a time.
    first = _place(p, np.array([3, 0], np.int32), p["used"])
    second = _place(p, np.array([0, 3], np.int32), first.used_after)
    placed = np.asarray(both.placements)
    np.testing.assert_array_equal(placed[0], np.asarray(first.placements)[0])
    np.testing.assert_array_equal(placed[1], np.asarray(second.placements)[1])
    np.testing.assert_array_equal(np.asarray(both.used_after),
                                  np.asarray(second.used_after))
    assert np.flatnonzero(placed[0]).tolist() == [0, 4, 8]
    # Node 4 is the better for spec 1 only once spec 0's second pass has
    # filled it further: a round-major loop gives spec 1 its one round
    # before that and reads [0, 1, 2].
    assert np.flatnonzero(placed[1]).tolist() == [0, 1, 4]
    # rounds: the most passes one spec took; the passes over all specs.
    assert int(both.rounds) == int(first.rounds) == 3
    assert int(second.rounds) == 1
    assert int(both.passes.total) == 4 and int(both.passes.multi) == 1
    assert int(np.asarray(both.unplaced).sum()) == 0


@pytest.mark.parametrize("template", ["web", "api", "db", "batch", "cache"])
def test_the_modules_evaluator_agrees_with_the_programs_feasibility(template):
    """The reference's rows and the device's feasibility matrix are made
    from the same stanzas by unlike code; they may differ only where one
    of them is wrong."""
    config = shrunk_config()
    nodes = constrained.make_nodes(config)
    jid = f"job-00000-{template}"
    job = constrained.make_job(config, jid)
    spec = encode.build_spec(job, job.task_groups[0], batch_penalty=False)
    assert spec.needs_oracle == ""
    targets, literals = encode.collect_attr_targets([spec])
    ct = encode.encode_cluster(nodes, targets)
    encode.finalize_codebooks(ct, literals)
    st = encode.encode_specs([spec], ct, nodes)
    feas = np.asarray(feasibility_matrix(
        jnp.asarray(ct.attr_values), jnp.asarray(ct.eligible),
        jnp.asarray(ct.dc_code), jnp.asarray(st.constraint_attr),
        jnp.asarray(st.constraint_op), jnp.asarray(st.constraint_rhs),
        jnp.asarray(st.dc_mask), jnp.asarray(st.precomp)))[0, :len(nodes)]
    feasible, distinct = constrained.rows(config, jid)
    want = np.ones(len(nodes), bool) if feasible is None else feasible
    np.testing.assert_array_equal(feas, want)
    assert 0 < want.sum()
    host_rows = {"api": 1, "batch": 1}.get(template, 0)
    assert len(st.row_stamps) == host_rows
    ops = {op for _, op, _ in
           config["jobs"]["templates"][template]["constraints"]}
    assert spec.distinct_hosts == ("distinct_hosts" in ops)
    assert (spec.dp_target == "${meta.rack}") == ("distinct_property" in ops)
    if "distinct_property" in ops:
        racks = np.asarray(ct.attr_values)[:len(nodes),
                                           ct.attr_index["${meta.rack}"]]
        # One code per rack, the same partition as the module's.
        assert len(set(zip(racks.tolist(), distinct.tolist()))) \
            == len(set(distinct.tolist())) == config["cluster"]["racks"]


def test_a_plan_not_yet_compiled_is_served_by_a_compiled_one_that_covers_it():
    """kernels.choose_plan: (u_pad, slot_m, max_nnz) buckets."""
    from nomad_tpu.ops import kernels

    kernels.reset_compile_signatures()
    cls, other = (128, False, True, False), (128, False, False, False)
    full, tail = (64, 64, 2048, 1), (16, 64, 512, 1)
    try:
        assert kernels.choose_plan(cls, tail) == tail      # nothing covers
        kernels.reset_compile_signatures()
        assert kernels.choose_plan(cls, full) == full
        assert kernels.choose_plan(other, tail) == tail    # another program
        for _ in range(kernels.PLAN_REUSE_LIMIT - 1):
            assert kernels.choose_plan(cls, tail) == full
        # A tail that built no host-evaluated row uploads all-true ones.
        assert kernels.choose_plan(cls, (16, 64, 512, 0)) == full
        assert kernels.choose_plan(cls, tail) == full
        # A shape that keeps coming earns its own program, and keeps it.
        assert kernels.choose_plan(cls, tail) == tail
        assert kernels.choose_plan(cls, tail) == tail
        assert kernels.choose_plan(cls, (8, 8, 8, 0)) == tail  # least cover
        # Larger in one bucket, or without a slot record: not covered.
        for plan in ((16, 128, 512, 1), (16, 0, 512, 1)):
            assert kernels.choose_plan(cls, plan) == plan
        assert kernels.choose_plan(other, (64, 64, 2048, 0)) \
            == (64, 64, 2048, 0)
        assert kernels.choose_plan(other, full) == full    # rows: no cover
    finally:
        kernels.reset_compile_signatures()


def test_a_drains_tail_batch_runs_the_full_batches_program(monkeypatch):
    """32 evaluations, then the 8 that are left: the tail is placed by the
    program the full batch compiled (no new signature), padded with rows
    that ask for nothing, and as the reference would place it."""
    from nomad_tpu.ops import kernels

    monkeypatch.setenv("NOMAD_TPU_RNG_SEED", "11")
    config = shrunk_config()
    nodes = constrained.make_nodes(config)
    ids = constrained.backlog_ids(config, 11)
    jobs = [constrained.make_job(config, jid) for jid in ids]
    h = harness_with(nodes, jobs)
    kernels.reset_compile_signatures()
    try:
        for lo, hi in ((0, 32), (32, 40)):
            before = kernels.signature_kinds().get("fused_pass", 0)
            stats = TPUBatchScheduler(h.logger, h.snapshot(), h) \
                .schedule_batch([reg_eval(j) for j in jobs[lo:hi]])
            assert stats.device_ran and stats.oracle_routed == 0
            assert stats.num_specs == hi - lo
            programs = kernels.signature_kinds()["fused_pass"]
            assert programs == (before if lo else before + 1)
    finally:
        kernels.reset_compile_signatures()
    compared = compare_served(h, config, ids, jobs)
    assert not {k: v for k, v in compared.items()
                if v["value"] > v["limit"]}, compared


def test_a_batch_that_lacks_a_usual_constraint_target_keeps_the_program(
        monkeypatch):
    """The fleet's batches encode against every constraint target and
    literal its batches have used so far: a batch with no ``db`` job (no
    ``${meta.tier}`` column of its own) runs the program and the static
    tensors of the batches before it, and is placed as the reference
    would place it."""
    from nomad_tpu.ops import kernels

    monkeypatch.setenv("NOMAD_TPU_RNG_SEED", "13")
    config = shrunk_config()
    nodes = constrained.make_nodes(config)
    ids = constrained.backlog_ids(config, 13)
    ids = ids[:24] + [j for j in ids[24:] if not j.endswith("-db")][:8]
    assert any(j.endswith("-db") for j in ids[:24])
    jobs = [constrained.make_job(config, jid) for jid in ids]
    h = harness_with(nodes, jobs)
    kernels.reset_compile_signatures()
    try:
        for lo, hi in ((0, 24), (24, 32)):
            TPUBatchScheduler(h.logger, h.snapshot(), h).schedule_batch(
                [reg_eval(j) for j in jobs[lo:hi]])
            assert kernels.signature_kinds()["fused_pass"] == 1
    finally:
        kernels.reset_compile_signatures()
    compared = compare_served(h, config, ids, jobs)
    assert not {k: v for k, v in compared.items()
                if v["value"] > v["limit"]}, compared
