"""Test configuration.

Force JAX onto a virtual 8-device CPU platform so multi-chip sharding tests
(`shard_map` over a Mesh) run without TPU hardware, per the reference test
strategy of simulating multi-node in-process (SURVEY.md §4 item 3).
Must run before jax is imported anywhere.
"""
import contextlib
import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Tests are correctness drives on the CPU backend, whatever the machine
# holds; must be set before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
# Columnar differential guard at EVERY encode (ISSUE 9 acceptance: the
# whole suite verifies the column-built buffers bit-identical to the
# object walk; a single mismatch trips the breaker and fails the
# asserting tests).  Respect an explicit override from the environment.
os.environ.setdefault("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "1")
# Struct-codec native/python twin differential guard at EVERY call
# (ISSUE 11): the whole suite bit-compares the C++ string-column pack
# against the pure-Python twin; one mismatch disables native and fails
# the asserting tests.
os.environ.setdefault("NOMAD_TPU_CODEC_GUARD_EVERY", "1")
# Packed-result decode native/twin differential guard at EVERY call
# (ISSUE 13): every COO expand / last-commit-score dedup in the suite is
# bit-compared against the numpy/python twins.
os.environ.setdefault("NOMAD_TPU_DECODE_GUARD_EVERY", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

@pytest.fixture(autouse=True)
def _fresh_breaker():
    """The kernel circuit breaker is process-wide by design (trips must
    survive the per-batch scheduler construction); between tests that
    makes it shared state — one test's device failures would open it
    and route its neighbours' evals through the CPU oracle."""
    from nomad_tpu.ops import breaker

    breaker.reset_for_tests()
    yield


# -- chaos trace dumps --------------------------------------------------------
# Chaos scenarios (`@pytest.mark.chaos`) run with the eval-lifecycle
# tracing plane armed; when one fails — the probabilistic sweeps fail
# rarely and only under particular seeds — the recent span timeline is
# dumped (bounded) to stderr so the failure is diagnosable from the
# pytest log alone, without re-running the seed locally.

CHAOS_DUMP_SPANS = 120
CHAOS_DUMP_EVENTS = 80


@pytest.fixture(autouse=True)
def _chaos_tracing(request):
    if request.node.get_closest_marker("chaos") is None:
        yield
        return
    from nomad_tpu.server import event_broker
    from nomad_tpu.utils import knobs, lockcheck, tracing

    tracing.enable()
    # Arm the cluster event stream for every server the test constructs
    # (NOMAD_TPU_EVENTS is read at Server construction) and clear the
    # process-global forensic tail so a failure dump shows THIS test's
    # incident, not the previous one's.
    prev = os.environ.get("NOMAD_TPU_EVENTS")
    os.environ["NOMAD_TPU_EVENTS"] = "1"
    event_broker.clear_recent()
    # Runtime lock-order sanitizer (ISSUE 15): chaos tests construct
    # full servers under induced concurrency — every lock they create
    # is instrumented, and teardown asserts the accumulated acquisition
    # graph has no cycle (the witness chain prints on failure).  The
    # env knob lets a run opt out (NOMAD_TPU_LOCKCHECK=0/false/no/off,
    # the registry's falsy set); an operator arming the whole session
    # (NOMAD_TPU_LOCKCHECK=1) keeps the sanitizer armed and the env var
    # intact after teardown.
    prev_lockcheck = os.environ.get("NOMAD_TPU_LOCKCHECK")
    lock_sanitize = knobs.get_bool("NOMAD_TPU_LOCKCHECK", True)
    was_armed = lockcheck.armed()
    if lock_sanitize:
        lockcheck.arm()
        os.environ["NOMAD_TPU_LOCKCHECK"] = "1"
    try:
        yield
        if lock_sanitize:
            lockcheck.assert_acyclic()
    finally:
        if lock_sanitize and not was_armed:
            lockcheck.disarm()
        if prev_lockcheck is None:
            os.environ.pop("NOMAD_TPU_LOCKCHECK", None)
        else:
            os.environ["NOMAD_TPU_LOCKCHECK"] = prev_lockcheck
        if prev is None:
            os.environ.pop("NOMAD_TPU_EVENTS", None)
        else:
            os.environ["NOMAD_TPU_EVENTS"] = prev
        tracing.disable()


def _format_trace(spans):
    t0 = min(sp["Start"] for sp in spans)
    lines = []
    for sp in spans:
        lines.append(
            "  +{:10.2f}ms {:9.2f}ms  {:<26} {}".format(
                (sp["Start"] - t0) * 1000.0, sp["DurationMs"],
                sp["Name"], sp["Attrs"]))
    return "\n".join(lines)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    # After the call phase, before fixture teardown disarms the tracer.
    if (rep.when == "call" and rep.failed
            and item.get_closest_marker("chaos") is not None):
        from nomad_tpu.server import event_broker
        from nomad_tpu.utils import tracing

        spans = tracing.recent(CHAOS_DUMP_SPANS)
        print(f"\n-- chaos trace timeline for {item.nodeid} "
              f"(last {len(spans)} spans) --", file=sys.__stderr__)
        if spans:
            print(_format_trace(spans), file=sys.__stderr__)
        else:
            print("  (no spans recorded)", file=sys.__stderr__)
        # The cluster event timeline next to the trace: spans say where
        # time went, events say what the cluster state DID.
        events = event_broker.recent(CHAOS_DUMP_EVENTS)
        print(f"-- chaos event timeline for {item.nodeid} "
              f"(last {len(events)} events) --", file=sys.__stderr__)
        if events:
            for ev in events:
                extra = f" eval={ev.eval_id[:8]}" if ev.eval_id else ""
                print(f"  @{ev.index:<6} {ev.topic}/{ev.type:<22} "
                      f"{ev.key[:16]}{extra} {ev.payload}",
                      file=sys.__stderr__)
        else:
            print("  (no events recorded)", file=sys.__stderr__)


def dev_test_config():
    """AgentConfig.dev() with an ephemeral HTTP port: dev() binds the
    standard 4646 for CLI parity, which concurrent test agents must not
    share."""
    from nomad_tpu.agent import AgentConfig

    cfg = AgentConfig.dev()
    cfg.ports.http = 0
    return cfg


def wait_for(predicate, timeout=30.0, interval=0.02):
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return bool(predicate())


def batch_job(count=2):
    """mock.job() without its network ask (the device path's shape)."""
    from nomad_tpu import mock

    job = mock.job()
    job.task_groups[0].count = count
    for t in job.task_groups[0].tasks:
        t.resources.networks = []
    return job


def put_job(agent, job):
    """``PUT /v1/jobs`` over the agent's HTTP port; returns the eval id."""
    import json
    import urllib.request

    from nomad_tpu.api.codec import to_wire

    req = urllib.request.Request(
        agent.http.address + "/v1/jobs", method="PUT",
        data=json.dumps({"Job": to_wire(job)}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())["EvalID"]


@contextlib.contextmanager
def served_job(data_dir=None, count=2, nodes=3):
    """One job through the served device path on the CPU backend: an
    Agent (server only, BatchWorker) takes ``PUT /v1/jobs`` over HTTP and
    the block yields ``(agent, job, eval_id)`` once the eval is complete,
    its allocations are in the state store and the broker has acked it.
    ``data_dir`` makes the server durable (FileLog + WAL: raft.fsync)."""
    from nomad_tpu import mock
    from nomad_tpu.agent.agent import Agent
    from nomad_tpu.structs import structs as s

    cfg = dev_test_config()
    cfg.client.enabled = False
    cfg.server.use_tpu_batch_worker = True
    cfg.server.batch_size = 8
    if data_dir is not None:
        cfg.dev_mode = False
        cfg.data_dir = cfg.server.data_dir = str(data_dir)
        # Outside dev mode the server listens on ports.rpc (4647):
        # ephemeral, for durable agents of test files run side by side.
        cfg.ports.rpc = 0
    agent = Agent(cfg)
    agent.start()
    try:
        srv = agent.server
        for _ in range(nodes):
            node = mock.node()
            node.resources.networks = []
            node.reserved.networks = []
            srv.node_register(node)
        job = batch_job(count)
        eval_id = put_job(agent, job)

        def done():
            ev = srv.state.eval_by_id(None, eval_id)
            return (ev is not None and ev.status == s.EVAL_STATUS_COMPLETE
                    and len(srv.state.allocs_by_job(None, job.id, True))
                    == count
                    and srv.eval_broker.stats()["total_unacked"] == 0)

        assert wait_for(done, 60.0), f"eval {eval_id} did not complete"
        yield agent, job, eval_id
    finally:
        agent.shutdown()
