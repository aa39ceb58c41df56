"""Eval-lifecycle tracing plane (nomad_tpu/utils/tracing.py): span
mechanics, the end-to-end trace of an eval through the TPU batch
pipeline, the HTTP query surface, and the chaos-correlation contract
(nack-redelivered evals show per-attempt spans with the nack reason)."""
import json
import time
import urllib.error
import urllib.request

import pytest

import conftest

from nomad_tpu import fault, mock
from nomad_tpu.ops.batch_sched import ENCODE_STAGES, EXPAND_STAGES
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.plan_apply import APPLY_STAGES
from nomad_tpu.structs import structs as s
from nomad_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _fresh_tracer():
    """Every test gets its own armed store; nothing leaks into tier-1."""
    tracing.enable()
    yield
    tracing.disable()
    fault.disarm()


def wait_until(predicate, timeout=60.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


def make_node():
    n = mock.node()
    n.resources.networks = []
    n.reserved.networks = []
    return n


def make_job(count=2):
    j = mock.job()
    j.task_groups[0].count = count
    for t in j.task_groups[0].tasks:
        t.resources.networks = []
    return j


class TestTracerMechanics:
    def test_disabled_is_inert(self):
        tracing.disable()
        assert not tracing.enabled()
        with tracing.span("anything", eval_id="e1") as sp:
            sp.set(k="v")  # the no-op singleton tolerates attrs
        tracing.event("thing", eval_id="e1")
        tracing.record("thing", 0.0, 1.0, eval_id="e1")
        assert tracing.recent(10) == []
        assert tracing.trace_for_eval("e1") == []

    def test_nesting_parents_and_eval_inheritance(self):
        with tracing.span("outer", eval_id="e1") as outer:
            with tracing.span("inner") as inner:
                pass
            tracing.event("marker")
        spans = tracing.trace_for_eval("e1")
        by_name = {sp["Name"]: sp for sp in spans}
        # children inherit the eval id and parent pointer
        assert set(by_name) == {"outer", "inner", "marker"}
        assert by_name["inner"]["ParentID"] == by_name["outer"]["SpanID"]
        assert by_name["marker"]["ParentID"] == by_name["outer"]["SpanID"]
        assert by_name["outer"]["ParentID"] == 0
        for sp in spans:
            assert sp["End"] >= sp["Start"]

    def test_batch_eval_ids_index_under_every_member(self):
        with tracing.span("batch", eval_ids=["a", "b"]):
            pass
        assert [sp["Name"] for sp in tracing.trace_for_eval("a")] == ["batch"]
        assert [sp["Name"] for sp in tracing.trace_for_eval("b")] == ["batch"]

    def test_eval_ids_capped_per_span(self):
        ids = [f"e{i}" for i in range(200)]
        with tracing.span("batch", eval_ids=ids):
            pass
        (sp,) = tracing.trace_for_eval("e0")
        assert len(sp["Attrs"]["eval_ids"]) == tracing.MAX_EVAL_IDS_PER_SPAN
        assert sp["Attrs"]["eval_ids_elided"] == 200 - \
            tracing.MAX_EVAL_IDS_PER_SPAN
        # ids past the cap are not indexed; ids within it are
        assert tracing.trace_for_eval("e199") == []
        assert tracing.trace_for_eval(
            f"e{tracing.MAX_EVAL_IDS_PER_SPAN - 1}")

    def test_exception_recorded_on_span(self):
        with pytest.raises(ValueError):
            with tracing.span("boom", eval_id="e2"):
                raise ValueError("kapow")
        (sp,) = tracing.trace_for_eval("e2")
        assert sp["Attrs"]["error"] == "ValueError"
        assert "kapow" in sp["Attrs"]["error_detail"]

    def test_store_is_bounded(self):
        tr = tracing.enable(capacity=32, max_evals=4)
        for i in range(100):
            tr.event("tick", eval_id=f"e{i}")
        assert len(tr.recent(1000)) <= 32
        # LRU eval index: only the newest ids are retained
        assert tracing.trace_for_eval("e0") == []
        assert tracing.trace_for_eval("e99")

    def test_ring_evictions_are_counted(self):
        tr = tracing.enable(capacity=32)
        for i in range(40):
            tr.event("tick")
        assert tr.recorded == 40
        assert tr.dropped == tracing.dropped() == 8
        assert len(tr.recent(1000)) == 32

    def test_explicit_parent_and_reserved_id(self):
        tr = tracing.TRACER
        with tracing.span("cause") as cause:
            pass
        # another thread's work names its cause; default = enclosing span
        with tracing.span("effect", parent_id=cause.span_id):
            tracing.event("inner")
        tracing.record("late", 1.0, 2.0, parent_id=cause.span_id)
        rid = tr.reserve_id()
        tracing.record("child", 1.0, 1.5, parent_id=rid)
        tracing.record("parent", 1.0, 2.0, span_id=rid)
        by = {sp["Name"]: sp for sp in tracing.recent(10)}
        assert by["effect"]["ParentID"] == by["cause"]["SpanID"]
        assert by["inner"]["ParentID"] == by["effect"]["SpanID"]
        assert by["late"]["ParentID"] == by["cause"]["SpanID"]
        assert by["child"]["ParentID"] == by["parent"]["SpanID"] == rid

    def test_span_takes_and_returns_caller_stamps(self):
        t0 = tracing.now()
        with tracing.span("stamped", start=t0, annotate=True) as sp:
            pass
        (out,) = [x for x in tracing.recent(5) if x["Name"] == "stamped"]
        assert out["Start"] == t0 and out["End"] == sp.end >= t0
        asp = tracing.TRACER.span("finished", start=t0)
        asp.__enter__()
        asp.finish(t0 + 1.0)
        (out,) = [x for x in tracing.recent(5) if x["Name"] == "finished"]
        assert (out["Start"], out["End"]) == (t0, t0 + 1.0)
        assert tracing.TRACER.current() is None

    def test_stages_tile_accumulate_and_lay_back_to_back(self):
        st = tracing.Stages("fam.")
        t0 = st.begin("a", 10.0)
        st.begin("b", 10.5)
        st.begin("a", 11.0)             # begun again: accumulates
        assert st.end(12.0) == 12.0
        assert st.seconds == {"a": 1.5, "b": 0.5}
        st.carve("c", 0.25, "a")        # a callee's own stamps inside a
        st.carve("d", 9.0, "b")         # never more than the stage holds
        assert st.seconds == {"a": 1.25, "b": 0.0, "c": 0.25, "d": 0.5}
        assert sum(st.seconds.values()) == 12.0 - t0
        parent = tracing.TRACER.record("fam", t0, 12.0).span_id
        st.lay(t0, ("a", "c", "missing", "d"), parent)
        laid = [sp for sp in tracing.recent(10)
                if sp["Name"].startswith("fam.")]
        assert [sp["Name"] for sp in laid] == ["fam.a", "fam.c",
                                               "fam.missing", "fam.d"]
        assert all(sp["ParentID"] == parent for sp in laid)
        assert laid[0]["Start"] == t0 and laid[-1]["End"] == 12.0
        for a, b in zip(laid, laid[1:]):
            assert a["End"] == b["Start"]

    def test_live_stages_are_children_of_the_reserved_parent(self):
        st = tracing.Stages("dev.", live=True)
        with st:
            t0 = st.begin("x")
            tracing.event("inside")
            st.begin("y")
        tracing.record("dev", t0, tracing.now(), span_id=st.parent_id)
        by = {sp["Name"]: sp for sp in tracing.recent(10)}
        assert by["dev.x"]["ParentID"] == by["dev.y"]["ParentID"] \
            == by["dev"]["SpanID"]
        assert by["inside"]["ParentID"] == by["dev.x"]["SpanID"]
        assert by["dev.x"]["End"] == by["dev.y"]["Start"]
        assert set(st.seconds) == {"x", "y"}

    @pytest.mark.parametrize("armed", [True, False])
    def test_timed_sample_and_span_share_their_stamps(self, armed):
        from nomad_tpu.utils.telemetry import InmemSink, Telemetry

        if not armed:
            tracing.disable()
        sink = InmemSink()
        calls = []
        with tracing.timed(Telemetry(sink=sink), "stage.x", cpu=True,
                           attrs=lambda: calls.append(1) or {"k": "v"},
                           parent_id=7) as t:
            sum(range(20000))
        totals = sink.latest()["SampleTotals"]
        count, total = totals["nomad.stage.x"]
        assert count == 1
        assert total == pytest.approx((t.end - t.start) * 1000.0)
        cpu = totals["nomad.stage.x.cpu"][1]
        assert 0.0 < cpu <= total + 1.0
        if not armed:
            assert calls == [] and t.span is tracing.NOOP
            assert t.span_id == 0
            return
        (sp,) = [x for x in tracing.recent(5) if x["Name"] == "stage.x"]
        assert (sp["Start"], sp["End"]) == (t.start, t.end)
        assert sp["SpanID"] == t.span_id and sp["ParentID"] == 7
        assert sp["Attrs"]["k"] == "v"
        assert sp["Attrs"]["cpu_ms"] == pytest.approx(cpu, abs=1e-3)

    def test_fault_fire_correlation(self):
        with fault.scenario({"seed": 3, "faults": [
                {"point": "heartbeat.deliver", "action": "drop",
                 "times": 1}]}):
            with tracing.span("lifecycle", eval_id="e3"):
                fault.faultpoint("heartbeat.deliver", node_id="n1")
        spans = tracing.trace_for_eval("e3")
        fires = [sp for sp in spans if sp["Name"] == "fault.fire"]
        assert len(fires) == 1
        assert fires[0]["Attrs"] == {"point": "heartbeat.deliver",
                                     "rule": 0, "action": "drop",
                                     "eval_id": "e3"}


class TestEvalLifecycleTrace:
    def test_single_eval_batch_pipeline_trace(self):
        """Acceptance: one eval through TPUBatchScheduler yields a
        queryable trace covering enqueue → dequeue → batch phases →
        plan-submit → apply, with monotonic timestamps."""
        srv = Server(ServerConfig(num_schedulers=1,
                                  use_tpu_batch_worker=True,
                                  batch_size=8))
        srv.start()
        try:
            for _ in range(3):
                srv.node_register(make_node())
            job = make_job(2)
            _, eval_id = srv.job_register(job)
            assert wait_until(
                lambda: srv.state.eval_by_id(None, eval_id) is not None
                and srv.state.eval_by_id(None, eval_id).status
                == s.EVAL_STATUS_COMPLETE, timeout=30.0)
            assert wait_until(
                lambda: len(srv.state.allocs_by_job(None, job.id, True))
                == 2, timeout=30.0)
            # the ack event lands just after the status write — wait for it
            assert wait_until(
                lambda: any(sp["Name"] == "broker.ack"
                            for sp in tracing.trace_for_eval(eval_id)),
                timeout=10.0)

            spans = tracing.trace_for_eval(eval_id)
            names = [sp["Name"] for sp in spans]
            for expected in ("broker.enqueue", "broker.dequeue",
                             "batch.schedule", "batch.phase1",
                             "batch.finalize", "worker.submit_plan",
                             "plan.evaluate", "plan.apply", "broker.ack"):
                assert expected in names, (expected, names)
            by_name = {sp["Name"]: sp for sp in spans}
            # timestamps are monotonic along the lifecycle ordering
            order = ["broker.enqueue", "broker.dequeue", "batch.schedule",
                     "worker.submit_plan", "plan.evaluate", "plan.apply"]
            starts = [by_name[n]["Start"] for n in order]
            assert starts == sorted(starts), list(zip(order, starts))
            for sp in spans:
                assert sp["End"] >= sp["Start"]
            # phases are parented under the batch.schedule root, the two
            # prepare phases through batch.prepare
            root = by_name["batch.schedule"]["SpanID"]
            assert by_name["batch.prepare"]["ParentID"] == root
            assert by_name["batch.phase1"]["ParentID"] == \
                by_name["batch.prepare"]["SpanID"]
            assert by_name["batch.finalize"]["ParentID"] == root
        finally:
            srv.shutdown()


class TestServedBatchSpanTree:
    """One job served over HTTP through the BatchWorker: the span tree
    under the two spans that were opaque (the device call, the plan
    round trip), broker wait, and the request that started it all."""

    @pytest.fixture(scope="class")
    def served(self):
        tracing.enable()
        try:
            with conftest.served_job() as (agent, job, eval_id):
                assert wait_until(
                    lambda: any(sp["Name"] == "broker.ack" for sp in
                                tracing.trace_for_eval(eval_id)),
                    timeout=10.0)
                yield {"eval_id": eval_id,
                       "timeline": tracing.trace_for_eval(eval_id),
                       "all": tracing.recent(4096)}
        finally:
            tracing.disable()

    @staticmethod
    def _one(spans, name):
        found = [sp for sp in spans if sp["Name"] == name]
        assert len(found) == 1, (name, [sp["Name"] for sp in spans])
        return found[0]

    def test_device_stages_tile_the_device_call(self, served):
        spans = served["timeline"]
        device = self._one(spans, "batch.device")
        stages = [self._one(spans, "batch.device." + name) for name in
                  ("stage", "dispatch", "wait", "fetch", "decode")]
        # children of batch.device (an id reserved before they ran),
        # in order, first at its start and last at its end
        for sp in stages:
            assert sp["ParentID"] == device["SpanID"], sp
        assert stages[0]["Start"] == device["Start"]
        assert stages[-1]["End"] == device["End"]
        length = device["End"] - device["Start"]
        assert length > 0
        gaps = 0.0
        for a, b in zip(stages, stages[1:]):
            assert b["Start"] >= a["End"], (a, b)
            gaps += b["Start"] - a["End"]
        assert gaps < 0.05 * length, (gaps, length)
        covered = sum(sp["End"] - sp["Start"] for sp in stages)
        assert covered == pytest.approx(length - gaps, rel=1e-6)
        # the existing fetch span sits inside the fetch stage
        fetch = self._one(spans, "batch.fetch")
        assert fetch["ParentID"] == stages[3]["SpanID"]

    def test_plan_spans_name_the_submit_span_across_threads(self, served):
        spans = served["timeline"]
        submit = self._one(spans, "worker.submit_plan")
        for name in ("plan.queue_wait", "plan.evaluate",
                     "plan.commit_wait", "plan.apply", "plan.wake"):
            sp = self._one([x for x in served["all"]
                            if x["ParentID"] == submit["SpanID"]], name)
            assert submit["Start"] <= sp["Start"]
            assert sp["End"] <= submit["End"]
        # the raft apply under plan.apply, on the commit thread
        apply_ = self._one(spans, "plan.apply")
        assert any(sp["Name"] == "raft.apply"
                   and sp["ParentID"] == apply_["SpanID"]
                   for sp in served["all"])
        # the five stages leave little of the round trip unnamed
        named = sum(sp["End"] - sp["Start"] for sp in served["all"]
                    if sp["ParentID"] == submit["SpanID"])
        assert named <= (submit["End"] - submit["Start"]) * (1 + 1e-9)

    def test_finalize_and_prepare_stages(self, served):
        spans = served["timeline"]
        finalize = self._one(spans, "batch.finalize")
        build, submit, status = (
            self._one(spans, "batch.finalize." + name)
            for name in ("build", "submit", "status"))
        assert finalize["Start"] <= build["Start"]
        assert build["End"] == submit["Start"]
        assert submit["End"] == status["Start"]
        assert status["End"] <= finalize["End"]
        # worker.submit_plan is what batch.finalize.submit brackets
        inner = self._one(spans, "worker.submit_plan")
        assert submit["Start"] <= inner["Start"]
        assert inner["End"] <= submit["End"]
        prepare = self._one(spans, "batch.prepare")
        for name in ("batch.phase1", "batch.phase2"):
            assert self._one(spans, name)["ParentID"] == prepare["SpanID"]
        self._one(served["all"], "worker.snapshot")

    def test_broker_dequeue_carries_wait_ms(self, served):
        spans = served["timeline"]
        enq = self._one(spans, "broker.enqueue")
        deq = self._one(spans, "broker.dequeue")
        wait_ms = deq["Attrs"]["wait_ms"]
        assert wait_ms >= 0.0
        # ready → dequeued, so no longer than enqueue → dequeue
        assert wait_ms <= (deq["Start"] - enq["Start"]) * 1000.0 + 1.0

    def test_http_request_leads_the_timeline(self, served):
        spans = served["timeline"]
        first = spans[0]
        assert first["Name"] == "http.request", [sp["Name"] for sp in spans]
        assert first["Attrs"]["method"] == "PUT"
        assert first["Attrs"]["route"] == "jobs"
        register = self._one(spans, "job.register")
        assert register["ParentID"] == first["SpanID"]
        assert first["Start"] <= register["Start"]
        assert register["End"] <= first["End"]


K = "worker.invoke_scheduler"
# parent sample → the samples that tile it (ISSUE 39)
FAMILIES = {
    "plan.apply": ["plan.apply." + st for st in APPLY_STAGES],
    K + ".encode": [K + ".encode." + st for st in ENCODE_STAGES],
    K + ".expand": [K + ".expand." + st for st in EXPAND_STAGES],
    "worker.cycle": ["worker.dequeue", "worker.wait_for_index",
                     "worker.snapshot", K, "worker.release", "worker.ack",
                     "worker.cycle.unnamed"],
}
# span family → (parent span, prefix, stages laid back to back over it)
LAID = [("plan.apply", "plan.apply.", APPLY_STAGES),
        ("batch.encode", "batch.encode.", ENCODE_STAGES),
        ("batch.metrics", "batch.metrics.", EXPAND_STAGES)]
# span → its parent, among the spans ISSUE 39 adds
PARENTS = ([(prefix + st, parent) for parent, prefix, names in LAID
            for st in names]
           + [("plan.respond", "worker.submit_plan"),
              ("worker.release", "worker.process_batch"),
              ("worker.ack", "worker.process_batch")])


class TestServedStagesTile:
    """One job served by a durable agent, tracer armed: every parent
    stage ISSUE 39 opened is tiled by its children, as samples (their
    sums) and as spans (their parents, across the applier's thread
    hand-off included)."""

    @pytest.fixture(scope="class")
    def served(self, tmp_path_factory):
        tracing.enable()
        try:
            with conftest.served_job(
                    data_dir=tmp_path_factory.mktemp("tile")) as (
                        agent, _job, eval_id):
                sink = agent.server.metrics.sink
                assert wait_until(
                    lambda: "nomad.worker.cycle"
                    in sink.latest()["SampleTotals"], timeout=10.0)
                yield {"totals": {k[len("nomad."):]: v for k, v in
                                  sink.latest()["SampleTotals"].items()},
                       "timeline": tracing.trace_for_eval(eval_id),
                       "all": tracing.recent(4096)}
        finally:
            tracing.disable()

    @pytest.mark.parametrize("parent", sorted(FAMILIES))
    def test_children_sum_to_their_parent(self, served, parent):
        tot = served["totals"]
        count, whole = tot[parent]
        assert count == 1               # one batch, one submission
        for child in FAMILIES[parent]:
            assert tot[child][0] == 1, child
            assert tot[child][1] >= 0.0, child
        assert sum(tot[c][1] for c in FAMILIES[parent]) == pytest.approx(
            whole, rel=1e-9, abs=1e-9)

    @pytest.mark.parametrize("child,parent", PARENTS)
    def test_new_spans_hang_under_their_parents(self, served, child,
                                                parent):
        (sp,) = [x for x in served["all"] if x["Name"] == child]
        (up,) = [x for x in served["all"] if x["Name"] == parent]
        assert sp["ParentID"] == up["SpanID"]
        assert up["Start"] <= sp["Start"]
        assert sp["End"] <= up["End"] + 1e-9

    @pytest.mark.parametrize("parent,prefix,names", LAID)
    def test_stage_spans_lie_back_to_back_over_the_parent(
            self, served, parent, prefix, names):
        (up,) = [x for x in served["all"] if x["Name"] == parent]
        kids = [[x for x in served["all"] if x["Name"] == prefix + n][0]
                for n in names]
        assert kids[0]["Start"] == up["Start"]
        for a, b in zip(kids, kids[1:]):
            assert a["End"] == b["Start"]
        assert kids[-1]["End"] == pytest.approx(up["End"], abs=1e-9)

    @pytest.mark.parametrize("name", ["plan.evaluate", "plan.apply",
                                      "batch.schedule"])
    def test_cpu_time_beside_wall_time(self, served, name):
        (sp,) = [x for x in served["all"] if x["Name"] == name]
        assert 0.0 <= sp["Attrs"]["cpu_ms"] <= sp["DurationMs"] + 1.0
        key = {"batch.schedule": K}.get(name, name)
        assert served["totals"][key + ".cpu"][1] == pytest.approx(
            sp["Attrs"]["cpu_ms"], abs=1e-3)

    def test_one_raft_apply_sample_a_call_one_span_an_entry(self, served):
        """The plan's entry: a ``raft.apply`` span with its index under
        ``plan.apply`` covering the sequencer wait and the FSM apply
        alone (no longer than plan.apply.fsm), and the sample counted per
        ``apply_many`` call with ``raft.fsync``."""
        (up,) = [x for x in served["all"] if x["Name"] == "plan.apply"]
        (fsm,) = [x for x in served["all"]
                  if x["Name"] == "plan.apply.fsm"]
        (entry,) = [x for x in served["all"] if x["Name"] == "raft.apply"
                    and x["ParentID"] == up["SpanID"]]
        assert entry["Attrs"]["index"] > 0
        assert entry["Attrs"]["msg_type"] == "APPLY_PLAN_RESULTS"
        assert up["Start"] <= entry["Start"] and entry["End"] <= up["End"]
        assert entry["DurationMs"] <= fsm["DurationMs"] + 1e-3
        tot = served["totals"]
        assert tot["raft.apply"][0] == tot["raft.fsync"][0]

    def test_respond_ends_where_wake_starts(self, served):
        by = {sp["Name"]: sp for sp in served["all"]}
        assert by["plan.apply"]["End"] == by["plan.respond"]["Start"]
        assert by["plan.respond"]["End"] == by["plan.wake"]["Start"]

    def test_the_cycle_spans(self, served):
        by = {sp["Name"]: sp for sp in served["all"]}
        cycle, deq = by["worker.cycle"], by["worker.dequeue"]
        assert cycle["Start"] == deq["Start"]
        assert deq["End"] <= by["worker.process_batch"]["Start"]
        assert by["worker.process_batch"]["End"] <= cycle["End"]
        # today's parentage stands: the batch's span is still a root
        assert by["worker.process_batch"]["ParentID"] == 0
        assert by["worker.ack"]["Attrs"]["num_evals"] == 1


def test_disarmed_no_new_site_allocates_a_span(monkeypatch):
    """The sites ISSUE 39 added cost their stamps and samples while the
    tracer is disarmed: not one Span is built for a served job."""
    tracing.disable()
    built = []
    init = tracing.Span.__init__

    def counting(self, *args, **kwargs):
        built.append(args[2] if len(args) > 2 else "?")
        init(self, *args, **kwargs)

    monkeypatch.setattr(tracing.Span, "__init__", counting)
    with conftest.served_job() as (agent, _job, _eval_id):
        sink = agent.server.metrics.sink
        assert wait_until(lambda: "nomad.worker.cycle"
                          in sink.latest()["SampleTotals"], timeout=10.0)
        totals = sink.latest()["SampleTotals"]
    for parent, children in FAMILIES.items():
        for key in [parent] + children:
            assert "nomad." + key in totals, key
    assert built == []


class TestTraceHTTP:
    def test_trace_endpoints(self):
        from nomad_tpu.agent.agent import Agent

        cfg = conftest.dev_test_config()
        cfg.client.enabled = False
        agent = Agent(cfg)
        agent.start()
        try:
            agent.server.node_register(make_node())
            job = make_job(1)
            _, eval_id = agent.server.job_register(job)
            assert wait_until(
                lambda: agent.server.state.allocs_by_job(None, job.id,
                                                         True), timeout=30.0)
            assert wait_until(
                lambda: tracing.trace_for_eval(eval_id), timeout=10.0)

            with urllib.request.urlopen(
                    agent.http.address + f"/v1/trace/eval/{eval_id}") as r:
                body = json.loads(r.read())
            assert body["EvalID"] == eval_id
            assert any(sp["Name"] == "broker.enqueue"
                       for sp in body["Spans"])
            assert all("DurationMs" in sp for sp in body["Spans"])

            with urllib.request.urlopen(
                    agent.http.address + "/v1/traces?recent=5") as r:
                body = json.loads(r.read())
            assert body["Enabled"] is True
            assert body["Dropped"] == 0
            assert 0 < len(body["Spans"]) <= 5

            # unknown eval → 404
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    agent.http.address + "/v1/trace/eval/nope")
            assert exc.value.code == 404
        finally:
            agent.shutdown()

    def test_traces_endpoint_reports_disabled(self):
        from nomad_tpu.agent.agent import Agent

        tracing.disable()
        cfg = conftest.dev_test_config()
        cfg.client.enabled = False
        agent = Agent(cfg)
        agent.start()
        try:
            with urllib.request.urlopen(
                    agent.http.address + "/v1/traces") as r:
                body = json.loads(r.read())
            assert body == {"Enabled": False, "Dropped": 0, "Spans": []}
        finally:
            agent.shutdown()


@pytest.mark.chaos
class TestChaosTraceCorrelation:
    def test_nack_redelivery_shows_two_attempts_with_reason(self):
        """A plan-apply crash burns delivery attempt 1; the broker
        redelivers and attempt 2 completes.  The eval's trace must show
        BOTH worker attempt spans, the first carrying the nack reason."""
        srv = Server(ServerConfig(num_schedulers=1))
        srv.eval_broker.initial_nack_delay = 0.1
        srv.start()
        try:
            for _ in range(3):
                srv.node_register(make_node())
            fault.arm({"seed": 21, "faults": [
                {"point": "plan.apply", "action": "crash", "times": 1}]})
            job = make_job(2)
            _, eval_id = srv.job_register(job)
            assert wait_until(
                lambda: srv.state.eval_by_id(None, eval_id).status
                == s.EVAL_STATUS_COMPLETE, timeout=30.0)
            assert fault.trace() == [("plan.apply", 0, "crash")]
            # attempt spans finish just after the status write
            assert wait_until(
                lambda: sum(sp["Name"] == "worker.attempt"
                            for sp in tracing.trace_for_eval(eval_id))
                >= 2, timeout=10.0)

            spans = tracing.trace_for_eval(eval_id)
            attempts = [sp for sp in spans
                        if sp["Name"] == "worker.attempt"]
            assert len(attempts) == 2, [sp["Name"] for sp in spans]
            attempts.sort(key=lambda sp: sp["Start"])
            assert attempts[0]["Attrs"]["attempt"] == 1
            assert attempts[1]["Attrs"]["attempt"] == 2
            assert "InjectedFault" in attempts[0]["Attrs"]["nack_reason"]
            assert "nack_reason" not in attempts[1]["Attrs"]
            # the broker recorded the redelivery decision too
            nacks = [sp for sp in spans if sp["Name"] == "broker.nack"]
            assert len(nacks) == 1
            assert nacks[0]["Attrs"]["outcome"] == "requeue"
            # and the injected fault itself is correlated into the trace
            fires = [sp for sp in spans if sp["Name"] == "fault.fire"]
            assert len(fires) == 1
            assert fires[0]["Attrs"]["point"] == "plan.apply"
        finally:
            srv.shutdown()
