#!/usr/bin/env python3
"""One run of one cell: ``python3 benchmarks/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

This process holds the chip and is the server: one ``Agent`` (server
only, ``use_tpu_batch_worker``, shipped defaults, durable ``data_dir``,
single voter).  It runs no generator, no heartbeat client and no poller:
those live in the client process (``client.py``), a child that never
touches a JAX backend.  What the nodes and the jobs look like and what a
right answer is comes from the configuration's deployment module
(``manifest.DEPLOYMENT_API``).  This process registers the nodes (set-up),
parks and releases the worker for set-up batches, reads the metrics sink
at the window's two edges, and, once the window has closed, holds what
the timed path produced against the plain reference.

Refuses to run without a TPU.  ``--dry-run-cpu`` is the one other mode:
a tiny size on the CPU backend for debugging and the tests; it prints
counts only and never a device metric or a result line.

Last line of stdout: one JSON object (see the repository's PERF.md).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import random
import shutil
import subprocess
import sys
import time
from pathlib import Path

T_PROCESS = time.monotonic()
ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmarks import manifest  # noqa: E402

DRY = {"batch_size": 4, "warmup_batches": [1, 2, 4]}
SETUP_DEADLINE_S = 900.0
PARK_DEADLINE_S = 120.0
K = "nomad.worker.invoke_scheduler"


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Abort(Exception):
    """The run cannot produce a result."""


# -- the client process -----------------------------------------------------


class ClientProc:
    def __init__(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = str(ROOT) + os.pathsep + env.get("PYTHONPATH", "")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "benchmarks.client"], cwd=str(ROOT),
            env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, bufsize=1)

    def send(self, **msg) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def recv(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise Abort(f"client process ended (exit {self.proc.poll()})")
        return json.loads(line)

    def call(self, **msg) -> dict:
        self.send(**msg)
        while True:
            reply = self.recv()
            if reply.get("reply") == msg["cmd"]:
                if not reply.get("ok"):
                    raise Abort(f"client {msg['cmd']}: "
                                f"{reply.get('error') or reply}\n"
                                f"{reply.get('traceback', '')}")
                return reply

    def close(self) -> dict:
        reply = {}
        try:
            if self.proc.poll() is None:
                self.send(cmd="quit")
                reply = self.recv()
        except (Abort, OSError, ValueError):
            pass
        try:
            self.proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        return reply


# -- the server -------------------------------------------------------------


class CompileLog:
    """XLA compiles as JAX itself reports them (jax.monitoring)."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs


def set_workers_paused(server, paused: bool) -> None:
    for w in server.workers:
        w.set_pause(paused)


def wait_parked(server, deadline_s: float = PARK_DEADLINE_S) -> None:
    """After a pause: until no eval is out with a worker, and a worker
    blocked in dequeue has come round to park."""
    from nomad_tpu.server.worker import DEQUEUE_TIMEOUT

    t_end = time.monotonic() + deadline_s
    time.sleep(2 * DEQUEUE_TIMEOUT + 0.2)
    while server.eval_broker.stats()["total_unacked"] > 0:
        if time.monotonic() > t_end:
            raise Abort("worker did not park")
        time.sleep(0.05)


def start_agent(config: dict, data_dir: str, batch_size: int):
    from nomad_tpu.agent import Agent, AgentConfig

    cfg = AgentConfig()
    cfg.server.enabled = True
    cfg.server.use_tpu_batch_worker = True
    cfg.server.batch_size = batch_size
    cfg.data_dir = data_dir
    cfg.ports.http = cfg.ports.rpc = 0
    agent = Agent(cfg)
    agent.start()
    t_end = time.monotonic() + 30.0
    while not agent.server.is_leader():
        if time.monotonic() > t_end:
            raise Abort("server did not become leader")
        time.sleep(0.02)
    return agent


def register_nodes(server, nodes):
    """In a fixed order: the node order is the device's node index, and
    the tie-break is keyed on it."""
    out = []
    for node in nodes:
        _, ttl = server.node_register(node)
        out.append([node.id, ttl])
    return out


# -- reading the timed path's answers back ------------------------------------


def collect_served(server, dep, config, job_ids):
    """What the timed path produced: every complete job's allocations from
    the state store, in commit order, as the deployment's plain arrays."""
    from benchmarks import check
    from nomad_tpu.structs import structs as s

    snap = server.state.snapshot()
    served = check.Served(jobs=[])
    keyed = []
    # Commit order: a batch finalizes its evals one after another, plan
    # first and the eval's completion after it, so the completion's raft
    # index orders the plans.
    complete = {e.job_id: e.modify_index for e in snap.evals(None)
                if e.status == s.EVAL_STATUS_COMPLETE}
    for jid in job_ids:
        if jid not in complete:
            continue
        rows = [(nid, r) for nid, r in snap.alloc_rows_by_job(None, jid)
                if not r.terminal_status()]
        if len(rows) != dep.wants(config, jid):
            served.wrong_count += 1
        if not rows:
            continue
        nodes = dep.node_indices(config, [nid for nid, _ in rows])
        if nodes.min() < 0:
            served.wrong_count += 1
            continue
        keyed.append((complete[jid], dep.placed_job(
            config, jid, nodes, [r for _, r in rows])))
    keyed.sort(key=lambda kv: kv[0])
    served.jobs = [job for _, job in keyed]
    return served, snap


def compare_readback(served, snap, readback) -> None:
    """The HTTP sample against the state store, row by row."""
    for jid, stubs in readback.items():
        mine = sorted((a.name, a.node_id) for a in
                      snap.allocs_by_job(None, jid, True)
                      if not a.terminal_status())
        theirs = sorted((name, node) for name, node, desired, _ in stubs
                        if desired == "run")
        names = {name for name, _ in theirs}
        if mine != theirs or len(names) != len(theirs) or not theirs:
            served.readback_mismatch += 1


def device_counters(sink1, n_failed_evals) -> dict:
    counters, samples = sink1["CounterTotals"], sink1["SampleTotals"]
    gauges = sink1["Gauges"]
    batches = samples.get(K + ".device", (0, 0.0))[0]
    invocations = samples.get(K, (0, 0.0))[0]
    pfx = "nomad."
    return {
        "oracle_routed": counters.get(pfx + "breaker.oracle_routed", 0.0),
        "kernel_rejects": counters.get(pfx + "breaker.kernel_rejects", 0.0),
        "breaker_trips": gauges.get(pfx + "breaker.trips", 0.0) or 0.0,
        "broker_nacks": counters.get(pfx + "broker.nack", 0.0),
        "node_expiries": counters.get(pfx + "heartbeat.invalidate", 0.0),
        "batches_off_device": float(invocations - batches),
        "batches_not_fused": float(
            batches - counters.get(pfx + "batch.fused", 0.0)),
        "resident_mismatches": float(
            (gauges.get(pfx + "batch.resident_guard_mismatches") or 0)
            + (gauges.get(pfx + "batch.resident_dev_mismatches") or 0)),
        "failed_evals": float(n_failed_evals),
    }


# -- one run -----------------------------------------------------------------


def run(args) -> int:
    cell = manifest.load_cell(args.workload)
    config, mix = cell.config, dict(cell.traffic)
    dry = args.dry_run_cpu
    if dry:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = manifest.shrunk(cell)
        config["server"]["batch_size"] = min(
            DRY["batch_size"], int(config["server"]["batch_size"]))
        if isinstance(mix.get("warmup_batches"), list):
            mix["warmup_batches"] = DRY["warmup_batches"]
    for item in args.mix:
        key, _, value = item.partition("=")
        mix[key] = json.loads(value)
    seed = int(args.seed)
    # The tie-break jitter is keyed on this: the state at window open is a
    # function of --seed alone.
    os.environ["NOMAD_TPU_RNG_SEED"] = str(seed & 0x7FFFFFFF or 1)
    if not dry:
        # At a fixed path inside the checkout: the path is part of the key.
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              str(ROOT / ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    run_dir = ROOT / ".bench_run" / f"run-{os.getpid()}"
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)

    import logging

    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    client = ClientProc()
    try:
        import jax

        # Programs that compile in under a second are cached too: every
        # run is a new process, and set-up is most of what a check costs.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        say(f"device: {device}")
        if dry:
            say("DRY RUN on the CPU backend at a tiny size: NOT a chip "
                "result; no result line will be printed")
        elif device["platform"] != "tpu" or len(devices) < cell.chips:
            say(f"run.py: needs {cell.chips} TPU chip(s), found "
                f"{device}; refusing to run on anything else")
            return 2
        compile_log = CompileLog()
        if args.trace:
            from nomad_tpu.utils import tracing

            tracing.enable(capacity=1 << 18)
        result = drive(args, cell, config, mix, seed, client, compile_log,
                       device, run_dir)
    except Abort as exc:
        say(f"run.py: {exc}")
        return 1
    finally:
        client.close()
        shutil.rmtree(run_dir, ignore_errors=True)
    if dry:
        say("dry run complete (not a chip result): "
            + json.dumps(result["counts"]))
        return 0 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0


def drive(args, cell, config, mix, seed, client, compile_log, device,
          run_dir):
    import jax

    from benchmarks import check, readers
    from nomad_tpu.native import native_wal_available
    from nomad_tpu.server.raft import FileLog

    dep = cell.deployment
    batch_size = int(config["server"]["batch_size"])
    agent = start_agent(config, str(run_dir / "data"), batch_size)
    server = agent.server
    try:
        if not isinstance(server.raft, FileLog):
            raise Abort("raft log is not the durable FileLog")
        if native_wal_available() and getattr(server.raft, "_nwal",
                                              None) is None:
            raise Abort("FileLog does not run the native group-commit WAL")
        client.call(cmd="init", config=config, seed=seed,
                    http_port=agent.http.port,
                    rpc_addr=server.config.rpc_advertise)
        nodes = dep.make_nodes(config)
        n_nodes = len(nodes)
        t0 = time.monotonic()
        ttls = register_nodes(server, nodes)
        t_reg = time.monotonic()
        say(f"registered {len(nodes)} nodes in {t_reg - t0:.1f}s")
        client.call(cmd="heartbeats", nodes=ttls, t_registered=t_reg)
        del nodes

        job_ids = []            # every job sent, set-up's with the window's
        loop = mix["loop"]
        run_msg = {"cmd": "run", "mix": mix, "seconds": args.seconds,
                   "seed": seed, "batch_size": batch_size}
        if loop == "standing_backlog":
            backlog = dep.backlog_ids(config, seed)
            n_jobs = len(backlog)
            set_workers_paused(server, True)
            wait_parked(server)
            rep = client.call(cmd="submit", job_ids=backlog,
                              threads=int(mix["setup_submitters"]))
            say(f"registered {n_jobs} jobs in {rep['seconds']:.1f}s")
            job_ids += backlog
            run_msg["total_evals"] = n_jobs
        elif loop == "open":
            # Warm-up batches of the window's own shapes: each is
            # registered with the worker parked, so it is one batch.
            k = 0
            for size in mix["warmup_batches"]:
                ids = [dep.request_id(config, "warm", k + i, seed)
                       for i in range(size)]
                k += size
                set_workers_paused(server, True)
                wait_parked(server)
                rep = client.call(cmd="submit", job_ids=ids, threads=1)
                set_workers_paused(server, False)
                client.call(cmd="wait", evals=rep["evals"],
                            timeout=SETUP_DEADLINE_S)
                job_ids += ids
            run_msg["job_prefix"] = "req"
        else:
            raise Abort(f"unknown loop kind {loop!r}")

        # The raft log snapshots itself every 8,192 entries or 64 MiB; an
        # operator's snapshot here puts every run's window at the same
        # distance from the next one.
        server.raft.snapshot()
        gc.collect()

        client.send(**run_msg)
        if loop == "standing_backlog":
            set_workers_paused(server, False)
        sink0 = sink1 = None
        t_open = t_close = None
        compiles0 = 0
        trace_dir = str(run_dir / "trace")
        tracing_on = False
        marker_pc = None
        while True:
            msg = client.recv()
            if msg.get("event") == "open":
                t_open = time.monotonic()
                gc0 = [g["collections"] for g in gc.get_stats()]
                sink0 = server.metrics.sink.latest()
                compiles0 = compile_log.compiles
                setup_s = t_open - T_PROCESS
                if args.trace:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1
                    jax.profiler.start_trace(trace_dir, profiler_options=opts)
                    tracing_on = True
                    with jax.profiler.TraceAnnotation("bench_clock_marker"):
                        marker_pc = time.perf_counter()
                    trace_t0 = time.perf_counter()
            elif msg.get("event") == "close":
                t_close = time.monotonic()
                if tracing_on:
                    trace_t1 = time.perf_counter()
                    jax.profiler.stop_trace()
                    tracing_on = False
                sink1 = server.metrics.sink.latest()
                compiles1 = compile_log.compiles
                gc1 = [g["collections"] for g in gc.get_stats()]
            elif msg.get("reply") == "run":
                if not msg.get("ok"):
                    raise Abort(f"client run: {msg.get('error')}\n"
                                f"{msg.get('traceback', '')}")
                cres = msg
                break
        if sink0 is None or sink1 is None:
            raise Abort("the window never opened")
        if cres.get("heartbeat_errors"):
            raise Abort(f"heartbeats failed: {cres['heartbeat_errors']}")
        if cres.get("errors"):
            say(f"client errors: {cres['errors']}")

        # The window is closed.  Park the worker so that the state stands
        # still, then read the peak before anything of the check runs.
        set_workers_paused(server, True)
        wait_parked(server)
        stats = devices_memory_peak(jax)
        sink_end = server.metrics.sink.latest()
        if loop == "open":
            job_ids += cres["job_ids"]
            in_window = cres["jobs_in_window"]
        else:
            in_window = backlog
        t_park = time.monotonic()
        failed_evals = server.broker_stats()["ByState"]["failed"]
        served, snap = collect_served(server, dep, config, job_ids)
        t_collect = time.monotonic()
        # A sample, drawn from the seed, of the window's jobs that the
        # program called complete, with the last of them in it.
        done = {j.key for j in served.jobs}
        pool = [j for j in in_window if j in done]
        sample = random.Random(seed).sample(
            pool, min(len(pool), int(mix["readback_sample_jobs"])))
        if pool and served.jobs[-1].key in pool \
                and served.jobs[-1].key not in sample:
            sample.append(served.jobs[-1].key)
        readback = client.call(cmd="readback", job_ids=sample)["jobs"]
        compare_readback(served, snap, readback)
        del snap
        say(f"after the window: parked in {t_park - t_close:.1f}s, state "
            f"read in {t_collect - t_park:.1f}s, {len(sample)} jobs read "
            f"back over HTTP in {time.monotonic() - t_collect:.1f}s")
        served.device = device_counters(sink_end, failed_evals)
        served.failed_requests = int(cres.get("failed", 0))
        spans = host_spans(args.trace)
    finally:
        t_down = time.monotonic()
        agent.shutdown()
        say(f"agent shut down in {time.monotonic() - t_down:.1f}s")
    del server, agent

    t_check = time.monotonic()
    compared = dep.compare(served, config)
    is_correct = check.correct(compared)
    control = None
    if args.control:
        # The reference in the program's place with one guarantee broken,
        # through the same comparison: it has to come out not correct.
        served.jobs = dep.control_jobs(config, served.jobs, seed)
        control = dep.compare(served, config)
        for name in [n for n in ("score_gap", "score_sum_rel") if n in control]:
            say(f"control {args.control} {name}: {control[name]['value']!r} "
                f"(limit {control[name]['limit']!r})")
        say(f"control {args.control} correct: {check.correct(control)}")
    say(f"reference and comparison took {time.monotonic() - t_check:.1f}s "
        f"over {len(served.jobs)} jobs")

    window_s = cres["t_close"] - cres["t_open"]
    e2e = {"setup_s": setup_s}
    if cres.get("latency_ms"):
        lat = cres["latency_ms"]
        e2e["submit_to_placed_p50_ms"] = readers.percentile(lat, 0.50)
    else:
        e2e["placed_per_s"] = cres["placed"] / window_s
    counts = {"attempted": cres["attempted"], "failed": cres["failed"],
              "placed": cres["placed"], "window_s": window_s,
              "jobs_checked": len(served.jobs),
              "batches": sink1["SampleTotals"].get(K + ".device", (0, 0))[0]
              - sink0["SampleTotals"].get(K + ".device", (0, 0))[0],
              "compiles_in_window": compiles1 - compiles0,
              "drained": bool(cres.get("drained"))}
    counts["gc_collections"] = [b - a for a, b in zip(gc0, gc1)]
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    device_out = dict(device, memory_peak_bytes=stats)
    breakdown = reduced = None
    if args.trace:
        from benchmarks import trace as tracemod

        xplane = tracemod.find_xplane(trace_dir)
        if xplane is None:
            raise Abort("the profiler wrote no trace")
        loaded = tracemod.load(xplane)
        if loaded["marker_s"] is None:
            raise Abort("clock marker not found in the trace")
        off = loaded["marker_s"] - marker_pc      # trace clock - perf_counter
        leaf, outer = [], []
        for name, a, b in spans:
            if name == "batch.schedule":
                outer.append((a + off, b + off))
            else:
                leaf.append((name, a + off, b + off))
        reduced = tracemod.reduce(loaded, trace_t0 + off, trace_t1 + off,
                                  leaf, outer)
        device_out["busy_s"] = reduced["busy_s"]
        device_out["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
    # Every run reads the layers the sink and the client can give (for
    # reading a spread); the traced run's are the benchmark's metrics.
    ctx = {"sink0": sink0, "sink1": sink1, "client": cres,
           "harness": {"compiles_in_window": compiles1 - compiles0},
           "trace": reduced,
           "shapes": {"nodes": n_nodes,
                      "device_kind": device["kind"]}}
    layers = {spec["name"]: {"value": v, "unit": spec["unit"]}
              for spec in cell.per_layer
              if (v := readers.read(ctx, spec)) is not None}
    counts["layers"] = {k: round(v["value"], 4) for k, v in layers.items()}
    say(f"counts: {json.dumps(counts)}")
    if args.trace:
        metrics = layers
    else:
        metrics = {name: {"value": e2e[name], "unit": units[name]}
                   for name in units if name in e2e}
    for name, v in compared.items():
        say(f"compared {name}: {v['value']!r} (limit {v['limit']!r})"
            + ("" if v["value"] <= v["limit"] else "  <-- OVER"))
    say(f"correct: {is_correct}")
    result = {"correct": is_correct, "attempted": int(cres["attempted"]),
              "failed": int(cres["failed"]), "metrics": metrics,
              "device": device_out, "counts": counts}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control is not None:
        result["control"] = {"kind": args.control,
                             "correct": check.correct(control),
                             "compared": control}
    result["compared"] = compared
    return result


def devices_memory_peak(jax) -> int:
    peak = 0
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


SPAN_NAMES = {"batch.encode": "encode (host)",
              "batch.device": "inside the device call",
              "batch.finalize": "finalize and plan apply (host)",
              "batch.schedule": "batch.schedule"}


def host_spans(traced: int):
    """The program's own batch spans (perf_counter clock), traced run only."""
    if not traced:
        return []
    from nomad_tpu.utils import tracing

    tr = tracing.TRACER
    if tr is None:
        return []
    return [(SPAN_NAMES[sp["Name"]], sp["Start"], sp["End"])
            for sp in tr.recent(1 << 18) if sp["Name"] in SPAN_NAMES]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="tiny size on the CPU backend; not a chip result")
    ap.add_argument("--mix", action="append", default=[], metavar="KEY=JSON",
                    help="override one parameter of the traffic file (only "
                         "for sweeps and trials, never a benchmark run)")
    ap.add_argument("--control", choices=("sampled",), default="",
                    help="also hold the control against the same limits "
                         "(not part of a benchmark run)")
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = float(manifest.load_manifest()["run_seconds"])
    try:
        import nomad_tpu  # noqa: F401  the system under test
    except ImportError as exc:
        say(f"run.py: the program is not in this checkout: {exc}")
        return 3
    try:
        return run(args)
    except manifest.ManifestError as exc:
        say(f"run.py: {exc}")
        return 2


if __name__ == "__main__":
    sys.exit(main())
