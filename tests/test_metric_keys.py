"""Every sink key a benchmark metric file names is a key the program
publishes.

A per-layer metric is a data file ``benchmarks/metrics/<name>.json``
naming a reader and a sink key; a key the program has renamed reads as a
silent ``null`` (a sample) or a silent 0 (a counter that must read 0) in
the ledger.  One job is served on the CPU backend through the path the
benchmark drives (durable Agent, ``PUT /v1/jobs``, BatchWorker), then the
three events whose counters a healthy job never touches are provoked (an
operator's snapshot, a rejected kernel result, a plan that does not fit),
and every ``key`` and ``per`` of a sink reader has to be there."""
import glob
import json
import os

import pytest

import conftest

from nomad_tpu import fault, mock
from nomad_tpu.structs import structs as s

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SINK_READERS = ("sample_mean", "counter_delta", "counter_per_sample")


def _sink_metrics():
    out = []
    for path in sorted(glob.glob(
            os.path.join(ROOT, "benchmarks", "metrics", "*.json"))):
        with open(path) as fh:
            spec = json.load(fh)
        if spec.get("reader") in SINK_READERS:
            out.append(pytest.param(spec, id=os.path.basename(path)[:-5]))
    return out


METRICS = _sink_metrics()


@pytest.fixture(scope="module")
def sink(tmp_path_factory):
    data_dir = tmp_path_factory.mktemp("metric-keys")
    with conftest.served_job(data_dir=data_dir) as (agent, job, _eval_id):
        srv = agent.server
        # raft.snapshot: the operator's snapshot the benchmark takes at
        # the end of set-up.
        srv.raft.snapshot()
        # plan.conflict: a plan whose allocation cannot fit its node.
        node = next(iter(srv.state.nodes(None)))
        hog = mock.alloc()
        hog.job_id, hog.node_id = job.id, node.id
        hog.resources = s.Resources(cpu=node.resources.cpu * 4,
                                    memory_mb=node.resources.memory_mb * 4)
        hog.task_resources = {}
        plan = s.Plan(eval_id=s.generate_uuid(), job=job)
        plan.append_alloc(hog)
        result = srv.plan_submit(plan).wait(30.0)
        assert result.refresh_index > 0
        # breaker.oracle_routed: one corrupted kernel result, rejected
        # and served by the oracle.
        before_reject = srv.metrics.sink.latest()
        with fault.scenario({"seed": 1, "faults": [
                {"point": "ops.kernel_result", "action": "corrupt",
                 "times": 1}]}):
            second = conftest.batch_job(2)
            conftest.put_job(agent, second)
            assert conftest.wait_for(
                lambda: len(srv.state.allocs_by_job(None, second.id, True))
                == 2, 60.0)
            # The batch's stats reach the sink after its plans commit.
            assert conftest.wait_for(
                lambda: "nomad.breaker.oracle_routed"
                in srv.metrics.sink.latest()["CounterTotals"], 30.0)
        after_reject = srv.metrics.sink.latest()
        # batch.net_* and finalize.offers: one job with mock.job()'s
        # network ask (50 Mbit, dynamic ports http and admin), on a node
        # that has its network.
        srv.node_register(mock.node())
        net_job = mock.job()
        net_job.task_groups[0].count = 2
        conftest.put_job(agent, net_job)
        offers = "nomad.worker.invoke_scheduler.finalize.offers"
        assert conftest.wait_for(
            lambda: srv.metrics.sink.latest()["SampleTotals"].get(
                offers, (0, 0.0))[1] > 0, 60.0)
        assert len(srv.state.allocs_by_job(None, net_job.id, True)) == 2
        # batch.port_columns: one more, its network asking a static port
        # beside the dynamic ones.
        static_job = mock.job()
        static_job.task_groups[0].count = 1
        static_job.task_groups[0].tasks[0].resources.networks[0] \
            .reserved_ports = [s.Port("lb", 8889)]
        conftest.put_job(agent, static_job)
        columns = "nomad.batch.port_columns"
        assert conftest.wait_for(
            lambda: srv.metrics.sink.latest()["CounterTotals"].get(
                columns, 0) > 0, 60.0)
        assert len(srv.state.allocs_by_job(None, static_job.id, True)) == 1
        latest = srv.metrics.sink.latest()
        # plan.group_undecided: two such hogs' plans as one submission,
        # whose group pass finds the node unfit and decides nothing.
        group = []
        for _ in range(2):
            hog = hog.copy()
            hog.id = s.generate_uuid()
            group.append(s.Plan(eval_id=s.generate_uuid(), job=job))
            group[-1].append_alloc(hog)
        futures = srv.plan_queue.enqueue_group(group)
        assert all(f.wait(30.0).refresh_index > 0 for f in futures)
        yield {"samples": set(latest["SampleTotals"]),
               "counters": set(latest["CounterTotals"]),
               "before_reject": before_reject,
               "after_reject": after_reject, "latest": latest,
               "undecided": srv.metrics.sink.latest()}


def test_there_are_sink_metrics():
    assert len(METRICS) >= 44


@pytest.mark.parametrize("spec", METRICS)
def test_metric_file_names_a_published_key(sink, spec):
    reader = spec["reader"]
    if reader == "sample_mean":
        assert spec["key"] in sink["samples"], spec
    elif reader == "counter_delta":
        assert spec["key"] in sink["counters"], spec
    else:
        assert spec["key"] in sink["counters"], spec
        assert spec["per"] in sink["samples"], spec


NET_KEYS = [
    # (kind, key, check): the network job's batch picked its offers (a
    # sample of the time), made every one (no failure) and built the
    # resident network mirror with the one walk a cold build takes; the
    # static-port job's batch read its port's holders from the mirror's
    # column; the network job's two placements (at least: the sink may
    # be read before the static-port job's batch has published all of
    # its keys) were written as network slab rows.  Four of the six
    # have no metric file (``BENCHMARK.json`` holds at most 128
    # per-layer metrics), so only this test holds their keys.
    ("SampleTotals", "nomad.worker.invoke_scheduler.finalize.offers",
     lambda v: v[1] > 0),
    ("CounterTotals", "nomad.batch.net_offer_failures", lambda v: v == 0),
    ("CounterTotals", "nomad.batch.net_usage_walks", lambda v: v >= 1),
    ("CounterTotals", "nomad.batch.net_delta_words", lambda v: v >= 0),
    ("CounterTotals", "nomad.batch.port_columns", lambda v: v >= 1),
    ("CounterTotals", "nomad.batch.net_slab_rows", lambda v: v >= 2),
]


@pytest.mark.parametrize("kind,key,check", NET_KEYS,
                         ids=[k for _, k, _ in NET_KEYS])
def test_a_served_network_job_publishes_its_network_keys(sink, kind, key,
                                                         check):
    totals = sink["latest"][kind]
    assert key in totals and check(totals[key]), (key, totals.get(key))


def test_a_group_pass_that_decides_nothing_is_counted(sink):
    """Counter ``plan.group_undecided`` (no metric file: ``per_layer`` is
    full) counts the group passes that found a node unfit and decided
    nothing: none while the served jobs ran (a plan a batch), one for
    the two hogs' plans submitted as one group."""
    key = "nomad.plan.group_undecided"
    assert sink["latest"]["CounterTotals"].get(key, 0) == 0
    assert sink["undecided"]["CounterTotals"][key] == 1


def test_fit_recheck_publishes_its_routes_and_its_guard(sink):
    """The applier's fit re-check counts the rows each route decided on
    every plan (the hog's per-object plan took the per-node route) and,
    at the suite's guard cadence of 1, times every guard run."""
    assert {"nomad.plan.fit.rows_array",
            "nomad.plan.fit.rows_scalar"} <= sink["counters"]
    assert "nomad.plan.evaluate.guard" in sink["samples"]


@pytest.mark.parametrize("name", ["plans_per_submission.tput",
                                  "plans_per_submission.lat"])
def test_plans_per_submission_reads_what_every_plan_path_publishes(
        sink, name):
    """``nomad.plan.submitted`` counts the plans each pass of the
    applier's fit re-check decided, one pass to a ``nomad.plan.evaluate``
    sample: the served jobs' batches held one plan each, and the hog's
    plan went through ``plan_submit``, so every pass decided one."""
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           name + ".json")) as fh:
        spec = json.load(fh)
    assert (spec["reader"], spec["key"], spec["per"]) == (
        "counter_per_sample", "nomad.plan.submitted", "nomad.plan.evaluate")
    latest = sink["latest"]
    passes = latest["SampleTotals"][spec["per"]][0]
    assert passes >= 2
    assert latest["CounterTotals"][spec["key"]] == passes
    # the samples of one submission: one of each to a pass
    for key in ("nomad.plan.queue_wait", "nomad.plan.commit_wait",
                "nomad.plan.wake"):
        assert latest["SampleTotals"][key][0] <= passes


@pytest.mark.parametrize("name", [
    "plan_fit_indexed_rows_per_submission.tput",
    "plan_fit_indexed_rows_per_submission.lat"])
def test_indexed_rows_counter_is_published_by_every_columnar_pass(
        sink, name):
    """``nomad.plan.fit.rows_indexed`` counts, per pass of the columnar
    fit re-check, the slab rows whose mirror rows came from an indexed
    node column's integers: published by every such pass, 0 for the
    served jobs' two-row plans (under ``ARRAY_MIN_ROWS``: the per-node
    route reads strings) and for the hog's per-object plan."""
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           name + ".json")) as fh:
        spec = json.load(fh)
    assert (spec["reader"], spec["key"], spec["per"]) == (
        "counter_per_sample", "nomad.plan.fit.rows_indexed",
        "nomad.plan.evaluate")
    assert sink["latest"]["CounterTotals"][spec["key"]] == 0


def test_fused_counter_counts_the_batches_the_device_answered(sink):
    """``nomad.batch.fused`` is what tells a batch the device program
    answered from an oracle-routed one (the benchmark's
    ``batches_not_fused``, chip_smoke.py): one per device batch, none
    for the batch whose kernel result was rejected."""
    K = "nomad.worker.invoke_scheduler"

    def read(snap):
        samples, counters = snap["SampleTotals"], snap["CounterTotals"]
        return (samples[K][0], samples[K + ".device"][0],
                counters.get("nomad.batch.fused", 0),
                counters.get("nomad.breaker.oracle_routed", 0))

    calls0, device0, fused0, routed0 = read(sink["before_reject"])
    assert device0 >= 1 and fused0 == device0 == calls0
    calls1, device1, fused1, routed1 = read(sink["after_reject"])
    assert calls1 == calls0 + 1 and routed1 == routed0 + 1
    assert (device1, fused1) == (device0, fused0)
