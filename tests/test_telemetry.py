"""Telemetry tests (reference: armon/go-metrics usage; metric names per
website/source/docs/agent/telemetry.html.md)."""
import gc
import time

import pytest

import conftest

from nomad_tpu import mock
from nomad_tpu.ops.batch_sched import ENCODE_STAGES, EXPAND_STAGES
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.plan_apply import APPLY_STAGES
from nomad_tpu.structs import structs as s
from nomad_tpu.utils import telemetry
from nomad_tpu.utils.telemetry import (EXACT_WINDOW, InmemSink, Telemetry,
                                       _Histogram, render_prometheus)


def wait_until(pred, timeout=20.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


class TestSink:
    def test_gauge_counter_sample_aggregation(self):
        sink = InmemSink(interval=60.0)
        t = Telemetry(sink)
        t.set_gauge("broker.total_ready", 3)
        t.incr_counter("rpc.query")
        t.incr_counter("rpc.query")
        t.add_sample("plan.evaluate", 12.5)
        t.add_sample("plan.evaluate", 7.5)
        latest = sink.latest()
        assert latest["Gauges"]["nomad.broker.total_ready"] == 3
        assert latest["Counters"]["nomad.rpc.query"]["count"] == 2
        samp = latest["Samples"]["nomad.plan.evaluate"]
        assert samp["count"] == 2 and samp["mean"] == 10.0
        assert samp["min"] == 7.5 and samp["max"] == 12.5

    def test_measure_records_milliseconds(self):
        sink = InmemSink(interval=60.0)
        t = Telemetry(sink)
        with t.measure("worker.invoke_scheduler.service"):
            time.sleep(0.02)
        samp = sink.latest()["Samples"]["nomad.worker.invoke_scheduler.service"]
        assert samp["count"] == 1 and samp["min"] >= 15.0

    def test_interval_ring_rolls(self):
        sink = InmemSink(interval=0.05, retain=3)
        for i in range(5):
            sink.set_gauge("g", i)
            time.sleep(0.06)
        data = sink.data()
        assert len(data) <= 3


class TestHistogramPercentiles:
    def test_small_n_quantiles_are_exact(self):
        h = _Histogram()
        for v in range(1, 101):  # 1..100, well inside the exact window
            h.add(float(v))
        assert h.percentile(0.50) == 51.0
        assert h.percentile(0.95) == 96.0
        assert h.percentile(0.99) == 100.0

    def test_large_n_quantiles_bounded_by_bucket_width(self):
        h = _Histogram()
        n = EXACT_WINDOW * 8  # force the bucketed estimator
        for i in range(n):
            h.add(100.0 * (i + 1) / n)  # uniform on (0, 100]
        # true p50/p95 are 50/95; the containing buckets are (25, 50]
        # and (50, 100], so the estimate may be off by a bucket width
        # but must stay inside the containing bucket's bounds.
        assert 25.0 <= h.percentile(0.50) <= 50.0
        assert 50.0 <= h.percentile(0.95) <= 100.0
        # quantiles never escape the observed range
        assert h.min <= h.percentile(0.01) <= h.percentile(0.99) <= h.max

    def test_summary_carries_quantiles_through_sink(self):
        sink = InmemSink(interval=60.0)
        t = Telemetry(sink)
        for v in (1.0, 2.0, 3.0, 4.0, 100.0):
            t.add_sample("plan.evaluate", v)
        samp = sink.latest()["Samples"]["nomad.plan.evaluate"]
        for q in ("p50", "p95", "p99"):
            assert q in samp
        assert samp["p50"] == 3.0
        assert samp["p99"] == 100.0

    def test_empty_histogram_percentiles(self):
        h = _Histogram()
        assert h.percentile(0.5) == 0.0


def parse_prometheus(text):
    """Parse exposition text into {name: value} + {name: type}; quantile
    series keep their label in the key (`name{quantile="0.5"}`)."""
    values, types = {}, {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split()
            types[name] = typ
            continue
        assert not line.startswith("#"), line
        key, val = line.rsplit(" ", 1)
        values[key] = float(val)
    return values, types


class TestPrometheusRendering:
    def test_render_gauges_counters_summaries(self):
        sink = InmemSink(interval=60.0)
        t = Telemetry(sink)
        t.set_gauge("broker.total_ready", 3)
        t.incr_counter("rpc.request", 2)
        t.incr_counter("rpc.request", 1)
        for v in (5.0, 10.0, 15.0):
            t.add_sample("plan.evaluate", v)
        values, types = parse_prometheus(render_prometheus(sink.latest()))

        assert values["nomad_broker_total_ready"] == 3.0
        assert types["nomad_broker_total_ready"] == "gauge"
        assert values["nomad_rpc_request_total"] == 3.0
        assert types["nomad_rpc_request_total"] == "counter"
        assert types["nomad_plan_evaluate"] == "summary"
        assert values['nomad_plan_evaluate{quantile="0.5"}'] == 10.0
        assert values["nomad_plan_evaluate_sum"] == 30.0
        assert values["nomad_plan_evaluate_count"] == 3.0

    def test_counters_and_sample_totals_monotonic_across_rolls(self):
        """Scrapers need monotonic series: counter totals and summary
        _sum/_count must accumulate across interval rolls even though
        the interval aggregates reset."""
        sink = InmemSink(interval=0.05, retain=2)
        t = Telemetry(sink)
        t.incr_counter("rpc.request", 5)
        t.add_sample("plan.evaluate", 10.0)
        time.sleep(0.07)  # force an interval roll
        t.incr_counter("rpc.request", 2)
        t.add_sample("plan.evaluate", 30.0)
        values, _ = parse_prometheus(render_prometheus(sink.latest()))
        assert values["nomad_rpc_request_total"] == 7.0
        assert values["nomad_plan_evaluate_count"] == 2.0
        assert values["nomad_plan_evaluate_sum"] == 40.0
        # the quantile estimate itself is interval-local (newest only)
        assert values['nomad_plan_evaluate{quantile="0.5"}'] == 30.0
        # a key whose interval rolled quiet keeps its _sum/_count series
        time.sleep(0.07)
        sink.set_gauge("g", 1)  # rolls the interval; no fresh samples
        values, _ = parse_prometheus(render_prometheus(sink.latest()))
        assert values["nomad_plan_evaluate_count"] == 2.0
        assert values["nomad_plan_evaluate_sum"] == 40.0
        assert 'nomad_plan_evaluate{quantile="0.5"}' not in values

    def test_metric_names_sanitized(self):
        sink = InmemSink(interval=60.0)
        sink.set_gauge("worker.invoke_scheduler._core", 1.0)
        values, _ = parse_prometheus(render_prometheus(sink.latest()))
        assert values["worker_invoke_scheduler__core"] == 1.0

    def test_http_prometheus_endpoint(self):
        """Acceptance: /v1/metrics?format=prometheus serves valid
        exposition including p50/p95/p99 for nomad.plan.evaluate and
        nomad.worker.invoke_scheduler, plus the broker gauges."""
        import urllib.request

        from nomad_tpu.agent.agent import Agent

        cfg = conftest.dev_test_config()
        cfg.client.enabled = False
        agent = Agent(cfg)
        agent.start()
        try:
            # Quantiles render from the newest sink interval only; stretch
            # it so a slow CI box can't roll the scheduling samples out of
            # the window before the scrape below.
            agent.server.metrics.sink.interval = 3600.0
            node = mock.node()
            node.resources.networks = []
            node.reserved.networks = []
            agent.server.node_register(node)
            job = mock.job()
            for t in job.task_groups[0].tasks:
                t.resources.networks = []
            agent.server.job_register(job)
            assert wait_until(lambda: agent.server.state.allocs_by_job(
                None, job.id, True))
            assert wait_until(lambda: "nomad.broker.total_ready"
                              in agent.server.metrics.sink.latest()["Gauges"])

            with urllib.request.urlopen(
                    agent.http.address
                    + "/v1/metrics?format=prometheus") as resp:
                assert resp.headers["Content-Type"].startswith("text/plain")
                values, types = parse_prometheus(resp.read().decode())

            assert "nomad_broker_total_ready" in values
            for base in ("nomad_plan_evaluate",
                         "nomad_worker_invoke_scheduler"):
                assert types[base] == "summary"
                for q in ("0.5", "0.95", "0.99"):
                    assert f'{base}{{quantile="{q}"}}' in values, (base, q)
                assert values[f"{base}_count"] >= 1.0
        finally:
            agent.shutdown()


class TestServedBatchKeys:
    """One job over HTTP through the BatchWorker: the always-on samples
    and the counter of ISSUE 26, under their published names."""

    NEW_SAMPLES = (
        ["worker.invoke_scheduler.device." + st for st in
         ("stage", "dispatch", "wait", "fetch", "decode")]
        + ["worker.invoke_scheduler.prepare",
           "worker.invoke_scheduler.expand",
           "worker.invoke_scheduler.batch", "worker.snapshot",
           "worker.wait_for_index",
           "worker.invoke_scheduler.finalize.build",
           "worker.invoke_scheduler.finalize.submit",
           "worker.invoke_scheduler.finalize.status",
           "plan.queue_wait", "plan.evaluate", "plan.commit_wait",
           "plan.apply", "plan.wake", "broker.wait",
           "http.request.PUT.jobs", "job.register"]
        # ISSUE 39: the cycle, the stages inside plan.apply, encode and
        # expand, the hand-back, and the three CPU clocks
        + ["worker.dequeue", "worker.release", "worker.ack", "worker.cycle",
           "worker.cycle.unnamed", "plan.respond", "plan.evaluate.cpu",
           "plan.apply.cpu", "worker.invoke_scheduler.cpu"]
        + ["plan.apply." + st for st in APPLY_STAGES]
        + ["worker.invoke_scheduler.encode." + st for st in ENCODE_STAGES]
        + ["worker.invoke_scheduler.expand." + st for st in EXPAND_STAGES])

    @pytest.fixture(scope="class")
    def latest(self):
        with conftest.served_job(count=2) as (agent, _job, _eval_id):
            sink = agent.server.metrics.sink
            # the cycle closes just after the ack the helper waited for
            assert wait_until(
                lambda: "nomad.worker.cycle"
                in sink.latest()["SampleTotals"], 10.0)
            yield sink.latest()

    @pytest.mark.parametrize("key", NEW_SAMPLES)
    def test_sample_is_published_once_per_unit(self, latest, key):
        count, total = latest["SampleTotals"]["nomad." + key]
        assert count == 1, (key, count)     # one batch, one plan, one job
        assert total >= 0.0

    def test_allocs_committed_counts_the_plan(self, latest):
        assert latest["CounterTotals"]["nomad.plan.allocs_committed"] == 2

    @pytest.mark.parametrize("key", ["worker.invoke_scheduler.commit",
                                     "worker.invoke_scheduler.fetch",
                                     "worker.invoke_scheduler.phase1",
                                     "worker.invoke_scheduler.phase2"])
    def test_overlapping_samples_are_gone(self, latest, key):
        assert "nomad." + key not in latest["SampleTotals"]

    def test_splits_sum_to_their_wholes(self, latest):
        tot = {k[len("nomad."):]: v[1]
               for k, v in latest["SampleTotals"].items()}
        k = "worker.invoke_scheduler"
        device = sum(tot[f"{k}.device.{st}"] for st in
                     ("stage", "dispatch", "wait", "fetch", "decode"))
        assert device == pytest.approx(tot[k + ".device"], rel=0.05)
        finalize = sum(tot[f"{k}.finalize.{st}"]
                       for st in ("build", "submit", "status"))
        assert finalize == pytest.approx(tot[k + ".finalize"], rel=0.05)
        assert finalize <= tot[k + ".finalize"]
        trip = sum(tot["plan." + st] for st in
                   ("queue_wait", "evaluate", "commit_wait", "apply",
                    "wake"))
        assert trip <= tot[k + ".finalize.submit"]
        batch = sum(tot[f"{k}.{st}"] for st in
                    ("prepare", "encode", "device", "expand", "finalize"))
        assert batch == pytest.approx(tot[k], rel=0.05)
        assert tot[k] <= tot[k + ".batch"]


    @pytest.mark.parametrize("key", ["runtime.gc_pause_ms",
                                     "runtime.gc_full_pause_ms"])
    def test_collector_pauses_are_published_with_every_batch(
            self, latest, key):
        """By 0.0 when nothing was collected: the key is always there."""
        assert latest["CounterTotals"]["nomad." + key] >= 0.0


class TestCollectorPauses:
    """The process-wide ``gc.callbacks`` entry (utils/telemetry.py)."""

    @pytest.fixture
    def alone(self, monkeypatch):
        """No watcher of another test's (a server it never shut down)."""
        monkeypatch.setattr(telemetry, "_gc_watchers", set())
        had = telemetry._on_gc in gc.callbacks
        if had:
            gc.callbacks.remove(telemetry._on_gc)
        yield
        assert telemetry._on_gc not in gc.callbacks
        if had:
            gc.callbacks.append(telemetry._on_gc)

    def test_a_forced_collection_raises_both_totals(self, alone):
        owner = object()
        telemetry.watch_gc(owner)
        try:
            before = telemetry.GC_PAUSE_MS, telemetry.GC_FULL_PAUSE_MS
            gc.collect()
            assert telemetry.GC_PAUSE_MS > before[0]
            assert telemetry.GC_FULL_PAUSE_MS > before[1]
            young = telemetry.GC_FULL_PAUSE_MS
            gc.collect(0)       # a young collection is not a full one
            assert telemetry.GC_FULL_PAUSE_MS == young
            sink = InmemSink()
            m = Telemetry(sink=sink)
            telemetry.publish_gc_pauses(m)
            telemetry.publish_gc_pauses(m)      # nothing since: by 0.0
            totals = sink.latest()["CounterTotals"]
            assert totals["nomad.runtime.gc_pause_ms"] > 0.0
            assert 0.0 < totals["nomad.runtime.gc_full_pause_ms"] \
                <= totals["nomad.runtime.gc_pause_ms"]
            gc.collect()
            telemetry.publish_gc_pauses(m)
            after = sink.latest()["CounterTotals"]
            assert after["nomad.runtime.gc_full_pause_ms"] \
                > totals["nomad.runtime.gc_full_pause_ms"]
        finally:
            telemetry.unwatch_gc(owner)

    def test_two_servers_share_one_callback_and_the_last_removes_it(
            self, alone):
        first = Server(ServerConfig(num_schedulers=0))
        second = Server(ServerConfig(num_schedulers=0))
        try:
            assert gc.callbacks.count(telemetry._on_gc) == 1
            first.shutdown()
            assert gc.callbacks.count(telemetry._on_gc) == 1
        finally:
            first.shutdown()
            second.shutdown()
        assert telemetry._on_gc not in gc.callbacks

    def test_a_full_collection_is_a_span_when_armed(self, alone):
        from nomad_tpu.utils import tracing

        owner = object()
        telemetry.watch_gc(owner)
        tracing.enable()
        try:
            gc.collect()
            # not from the callback, which may run under the tracer's lock
            assert not [x for x in tracing.recent(10)
                        if x["Name"] == "runtime.gc"]
            telemetry.publish_gc_pauses(Telemetry(sink=InmemSink()))
            (sp,) = [x for x in tracing.recent(10)
                     if x["Name"] == "runtime.gc"]
            assert sp["Attrs"]["generation"] == 2
            assert sp["Attrs"]["collected"] >= 0
            assert sp["End"] > sp["Start"]
        finally:
            tracing.disable()
            telemetry.unwatch_gc(owner)


class TestServerEmitters:
    def test_hot_path_metrics_emitted(self):
        srv = Server(ServerConfig(num_schedulers=1))
        srv.start()
        try:
            node = mock.node()
            node.resources.networks = []
            node.reserved.networks = []
            srv.node_register(node)
            job = mock.job()
            job.task_groups[0].count = 2
            for t in job.task_groups[0].tasks:
                t.resources.networks = []
            srv.job_register(job)
            assert wait_until(lambda: len(
                srv.state.allocs_by_job(None, job.id, True)) == 2)

            def emitted():
                latest = srv.metrics.sink.latest()
                g, samp = latest["Gauges"], latest["Samples"]
                return ("nomad.broker.total_ready" in g
                        and "nomad.plan.queue_depth" in g
                        and "nomad.heartbeat.active" in g
                        and any(k.startswith("nomad.worker.invoke_scheduler")
                                for k in samp)
                        and "nomad.plan.evaluate" in samp
                        and "nomad.plan.apply" in samp)

            assert wait_until(emitted, 10.0), \
                srv.metrics.sink.latest()
            stats = srv.stats()
            assert "metrics_gauges" in stats and "metrics_samples" in stats
        finally:
            srv.shutdown()

    def test_metrics_http_endpoint(self, tmp_path):
        from nomad_tpu.agent.agent import Agent
        from nomad_tpu.agent.config import AgentConfig
        import json
        import urllib.request

        cfg = conftest.dev_test_config()
        cfg.client.enabled = False
        agent = Agent(cfg)
        agent.start()
        try:
            assert wait_until(lambda: bool(
                agent.server.metrics.sink.latest()["Gauges"]))
            with urllib.request.urlopen(
                    agent.http.address + "/v1/metrics") as resp:
                data = json.loads(resp.read())
            assert data and "Gauges" in data[-1]
            assert "nomad.broker.total_ready" in data[-1]["Gauges"]
        finally:
            agent.shutdown()
