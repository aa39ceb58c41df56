"""The client process: everything that runs while the window is open and
is not the server.

It never touches a JAX backend (the parent starts it with
``JAX_PLATFORMS=cpu`` and it checks that no backend came up).  It keeps
the nodes alive by heartbeat over the TCP RPC the real client agent
uses, registers jobs over HTTP ``PUT /v1/jobs``, follows evaluations and
allocations over HTTP, and keeps every clock an end-to-end metric is
made of.  The parent drives it with JSON lines on stdin and reads JSON
lines from stdout; ``time.monotonic()`` is the machine's clock, so both
processes read the same one.

One general generator reads a traffic mix's parameters; a new mix of a
known ``loop`` kind is a data file and no code.  What a job is called,
what its body holds and how many allocations it wants come from the
configuration's deployment module (``manifest.DEPLOYMENT_API``).
"""
from __future__ import annotations

import heapq
import http.client
import json
import math
import queue
import random
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from benchmarks import manifest

HEARTBEAT_RENEW = 0.7       # renew at this share of the granted TTL
POLL_S = 0.05


def arrival_gaps(n: int, rate: float, law: str, seed: int) -> List[float]:
    """``n`` inter-arrival gaps.  Every seed gets the same set of gaps in
    another order, so the work of a window does not depend on the seed:
    for ``poisson`` the set is the exponential law's quantiles."""
    if law == "poisson":
        gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    else:
        raise ValueError(f"unknown arrival law {law!r}")
    random.Random(seed).shuffle(gaps)
    return gaps


class Http:
    """One request per connection: the server closes each."""

    def __init__(self, host: str, port: int, timeout: float = 120.0):
        self.host, self.port, self.timeout = host, port, timeout

    def call(self, method: str, path: str, body: Optional[bytes] = None
             ) -> Tuple[int, dict, bytes]:
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout)
        try:
            headers = {"Content-Type": "application/json"} if body else {}
            conn.request(method, path, body=body, headers=headers)
            resp = conn.getresponse()
            return resp.status, dict(resp.getheaders()), resp.read()
        finally:
            conn.close()

    def get_json(self, path: str):
        status, headers, raw = self.call("GET", path)
        if status != 200:
            raise RuntimeError(f"GET {path}: {status} {raw[:200]!r}")
        return json.loads(raw), int(headers.get("X-Nomad-Index") or 0)


class Heartbeats:
    """Renews every node's TTL over ``Node.UpdateStatus`` and records how
    late each renewal left against its due time."""

    def __init__(self, rpc_addr: str, threads: int = 2):
        from nomad_tpu.server.rpc import RemoteServerRPC

        self.stop = threading.Event()
        self.errors: List[str] = []
        self.late: List[Tuple[float, float]] = []   # (sent at, seconds late)
        self._lock = threading.Lock()
        self._rpcs = [RemoteServerRPC([rpc_addr]) for _ in range(threads)]
        self._heaps: List[list] = [[] for _ in range(threads)]
        self._threads: List[threading.Thread] = []

    def start(self, due: List[Tuple[float, str]]) -> None:
        for i, item in enumerate(due):
            self._heaps[i % len(self._heaps)].append(item)
        for i, heap in enumerate(self._heaps):
            heapq.heapify(heap)
            t = threading.Thread(target=self._run, args=(i,), daemon=True,
                                 name=f"bench-hb-{i}")
            t.start()
            self._threads.append(t)

    def _run(self, i: int) -> None:
        from nomad_tpu.structs import structs as s

        heap, rpc = self._heaps[i], self._rpcs[i]
        while not self.stop.is_set():
            now = time.monotonic()
            if not heap or heap[0][0] > now:
                wait = 0.05 if not heap else min(0.05, heap[0][0] - now)
                self.stop.wait(max(wait, 0.001))
                continue
            due, node = heapq.heappop(heap)
            try:
                _, ttl = rpc.node_update_status(node, s.NODE_STATUS_READY)
            except Exception as exc:    # reported to the parent, fails the run
                with self._lock:
                    self.errors.append(f"{node}: {exc!r}")
                ttl = 5.0
            with self._lock:
                self.late.append((now, now - due))
            heapq.heappush(heap, (now + max(0.2, HEARTBEAT_RENEW * ttl), node))

    def close(self) -> None:
        self.stop.set()
        for t in self._threads:
            t.join(timeout=10.0)
        for rpc in self._rpcs:
            rpc.pool.close()

    def late_in(self, t0: float, t1: float) -> List[float]:
        with self._lock:
            return [late for at, late in self.late if t0 <= at < t1]


class Client:
    def __init__(self, init: dict):
        self.config = init["config"]
        self.http = Http("127.0.0.1", init["http_port"])
        self.rpc_addr = init["rpc_addr"]
        self.seed = int(init["seed"])
        self.dep = manifest.load_deployment(self.config)
        self.heartbeats: Optional[Heartbeats] = None

    # -- pieces ------------------------------------------------------------

    def body(self, jid: str) -> bytes:
        from nomad_tpu.api.codec import to_wire

        job = self.dep.make_job(self.config, jid)
        return json.dumps({"Job": to_wire(job)}).encode()

    def register(self, jid: str, body: bytes) -> Tuple[str, int]:
        status, _, raw = self.http.call("PUT", "/v1/jobs", body)
        if status != 200:
            raise RuntimeError(f"PUT /v1/jobs {jid}: {status} {raw[:200]!r}")
        reply = json.loads(raw)
        return reply["EvalID"], int(reply["EvalCreateIndex"])

    def follow(self, eval_id: str, index: int, want: int,
               deadline: float) -> bool:
        """Blocking queries until the eval is complete and ``want``
        allocations of it read back, all desired ``run``."""
        while time.monotonic() < deadline:
            ev, index = self.http.get_json(
                f"/v1/evaluation/{eval_id}?index={index}&wait=5s")
            if ev["Status"] == "complete":
                allocs, _ = self.http.get_json(
                    f"/v1/evaluation/{eval_id}/allocations")
                return (len(allocs) == want and all(
                    a["DesiredStatus"] == "run" for a in allocs))
            if ev["Status"] in ("failed", "cancelled", "blocked"):
                return False
        return False

    def wants(self, jid: str) -> int:
        return self.dep.wants(self.config, jid)

    def complete_evals(self) -> Tuple[int, int, float]:
        """(evals complete, the allocations they wanted, the instant the
        state was read): the state is read somewhere inside the request, so
        the instant is its middle."""
        t0 = time.monotonic()
        evals, _ = self.http.get_json("/v1/evaluations")
        t1 = time.monotonic()
        done = [self.wants(e["JobID"]) for e in evals
                if e["Status"] == "complete"]
        return len(done), sum(done), 0.5 * (t0 + t1)

    def broker(self) -> dict:
        return self.http.get_json("/v1/broker/stats")[0]

    # -- commands ----------------------------------------------------------

    def cmd_heartbeats(self, msg: dict) -> dict:
        self.heartbeats = Heartbeats(self.rpc_addr)
        t0 = msg["t_registered"]
        self.heartbeats.start([(t0 + HEARTBEAT_RENEW * ttl, node)
                               for node, ttl in msg["nodes"]])
        return {"ok": True}

    def cmd_submit(self, msg: dict) -> dict:
        """Register jobs in set-up, ``threads`` at a time."""
        jids = list(msg["job_ids"])
        todo: "queue.SimpleQueue[str]" = queue.SimpleQueue()
        for jid in jids:
            todo.put(jid)
        evals: Dict[str, Tuple[str, int]] = {}
        errors: List[str] = []

        def work() -> None:
            while True:
                try:
                    jid = todo.get_nowait()
                except queue.Empty:
                    return
                try:
                    evals[jid] = self.register(jid, self.body(jid))
                except Exception as exc:
                    errors.append(repr(exc))

        t0 = time.monotonic()
        threads = [threading.Thread(target=work, daemon=True)
                   for _ in range(int(msg.get("threads", 1)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return {"ok": not errors, "errors": errors[:3], "evals": evals,
                "seconds": time.monotonic() - t0}

    def cmd_wait(self, msg: dict) -> dict:
        """Wait until every named eval is complete with its allocations."""
        deadline = time.monotonic() + float(msg["timeout"])
        bad = []
        for jid, (eval_id, index) in msg["evals"].items():
            if not self.follow(eval_id, index, self.wants(jid), deadline):
                bad.append(jid)
        return {"ok": not bad, "bad": bad[:5]}

    def cmd_readback(self, msg: dict) -> dict:
        """``GET /v1/job/<id>/allocations`` for a sample of jobs."""
        out = {}
        for jid in msg["job_ids"]:
            stubs, _ = self.http.get_json(f"/v1/job/{jid}/allocations")
            out[jid] = [[a["Name"], a["NodeID"], a["DesiredStatus"],
                         a["CreateIndex"]] for a in stubs]
        return {"ok": True, "jobs": out}

    def cmd_run(self, msg: dict) -> dict:
        loop = msg["mix"]["loop"]
        runner = {"standing_backlog": self.run_backlog,
                  "open": self.run_open}.get(loop)
        if runner is None:
            raise ValueError(f"unknown loop kind {loop!r}")
        result = runner(msg)
        hb = self.heartbeats
        late = hb.late_in(result["t_open"], result["t_close"]) if hb else []
        result["heartbeat_late_ms"] = [x * 1000.0 for x in late]
        result["heartbeat_errors"] = hb.errors[:3] if hb else []
        return result

    # -- the two loops -----------------------------------------------------

    def run_backlog(self, msg: dict) -> dict:
        """No arrivals: the window opens when the worker takes the first
        batch after the warm-up batches and closes ``seconds`` later, or
        at the drain if that comes first."""
        mix, seconds = msg["mix"], float(msg["seconds"])
        total, batch = int(msg["total_evals"]), int(msg["batch_size"])
        open_ready = total - (int(mix["warmup_batches"]) + 1) * batch
        ready_max = 0
        while True:
            st = self.broker()["ByState"]
            if st["ready"] <= open_ready:
                break
            time.sleep(POLL_S)
        c0, wanted0, t_open = self.complete_evals()
        emit({"event": "open", "t": t_open})
        ready_max = st["ready"]
        drained_at = None
        while time.monotonic() < t_open + seconds:
            # Half a second between looks while batches are still to be
            # taken; once the last one is out, POLL_S, so that the drain is
            # stamped to that and not to the half second.
            nap = POLL_S if st["ready"] == 0 else 0.5
            time.sleep(min(nap, max(0.0, t_open + seconds - time.monotonic())))
            st = self.broker()["ByState"]
            if st["ready"] + st["unacked"] == 0:
                drained_at = time.monotonic()
                break
        c1, wanted1, t_close = self.complete_evals()
        if drained_at is not None:
            t_close = drained_at
        emit({"event": "close", "t": t_close})
        return {"ok": True, "t_open": t_open, "t_close": t_close,
                "evals_open": c0, "evals_close": c1,
                "placed": wanted1 - wanted0,
                "attempted": c1 - c0, "failed": 0,
                "drained": drained_at is not None,
                "broker_ready_open": ready_max}

    def run_open(self, msg: dict) -> dict:
        """Open loop at a fixed rate.  A dispatcher hands each request to
        a worker at its due time; latency runs from the due time."""
        mix, seconds = msg["mix"], float(msg["seconds"])
        rate, preroll = float(mix["rate_per_s"]), float(mix["preroll_s"])
        n = int(round(rate * (preroll + seconds)))
        gaps = arrival_gaps(n, rate, mix["arrival_law"], int(msg["seed"]))
        # The set of gaps sums to about n / rate; scale so that the last
        # arrival falls just inside the window whatever the order.
        scale = (preroll + seconds) * n / ((n + 1.0) * sum(gaps))
        ids = [self.dep.request_id(self.config, msg["job_prefix"], i,
                                   self.seed) for i in range(n)]
        bodies = [self.body(jid) for jid in ids]
        rows: List[dict] = [None] * n          # type: ignore[list-item]
        todo: "queue.SimpleQueue" = queue.SimpleQueue()
        straggle = float(mix["straggler_wait_s"])

        t_start = time.monotonic() + 0.2
        t_open, t_close = t_start + preroll, t_start + preroll + seconds
        hard_deadline = t_close + straggle

        def work() -> None:
            while True:
                item = todo.get()
                if item is None:
                    return
                i, due = item
                row = {"due": due, "sent": time.monotonic(), "ok": False}
                rows[i] = row
                try:
                    eval_id, index = self.register(ids[i], bodies[i])
                    row["acked"] = time.monotonic()
                    row["ok"] = self.follow(eval_id, index, self.wants(ids[i]),
                                            hard_deadline)
                except Exception as exc:
                    row["error"] = repr(exc)
                row["done"] = time.monotonic()

        workers = [threading.Thread(target=work, daemon=True,
                                    name=f"bench-submit-{k}")
                   for k in range(int(mix["workers"]))]
        for t in workers:
            t.start()
        due, opened = t_start, False
        for i, gap in enumerate(gaps):
            due += gap * scale
            if not opened and due >= t_open:
                opened = True
                delay = t_open - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                emit({"event": "open", "t": t_open})
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            todo.put((i, due))
        delay = t_close - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        emit({"event": "close", "t": t_close})
        for _ in workers:
            todo.put(None)
        for t in workers:
            t.join(timeout=max(1.0, hard_deadline + 10.0 - time.monotonic()))
        inwin = [i for i, r in enumerate(rows) if r is not None
                 and t_open <= r["due"] < t_close]
        worst = (hard_deadline - t_open) * 1000.0
        lat, late, ack = [], [], []
        failed = placed = 0
        for i in inwin:
            r = rows[i]
            late.append((r["sent"] - r["due"]) * 1000.0)
            if "acked" in r:
                ack.append((r["acked"] - r["sent"]) * 1000.0)
            if r["ok"]:
                lat.append((r["done"] - r["due"]) * 1000.0)
                placed += self.wants(ids[i])
            else:
                failed += 1
                lat.append(worst)
        errors = [r["error"] for r in rows if r and "error" in r]
        return {"ok": True, "t_open": t_open, "t_close": t_close,
                "attempted": len(inwin), "failed": failed,
                "latency_ms": lat, "generator_late_ms": late,
                "register_ack_ms": ack, "errors": errors[:3],
                "placed": placed, "job_ids": ids,
                "jobs_in_window": [ids[i] for i in inwin]}


_OUT_LOCK = threading.Lock()


def emit(obj: dict) -> None:
    with _OUT_LOCK:
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()


def backend_initialised() -> bool:
    """Whether this process brought a JAX backend up (it must not)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return bool(getattr(xla_bridge, "_backends", None))


def main() -> int:
    client: Optional[Client] = None
    for line in sys.stdin:
        msg = json.loads(line)
        cmd = msg["cmd"]
        try:
            if cmd == "init":
                client = Client(msg)
                reply = {"ok": True}
            elif cmd == "quit":
                if client is not None and client.heartbeats is not None:
                    client.heartbeats.close()
                emit({"ok": True, "reply": "quit",
                      "backend_initialised": backend_initialised()})
                return 0
            else:
                reply = getattr(client, "cmd_" + cmd)(msg)
        except Exception as exc:            # the parent fails the run on it
            import traceback

            reply = {"ok": False, "error": repr(exc),
                     "traceback": traceback.format_exc()[-2000:]}
        reply["reply"] = cmd
        emit(reply)
    return 0


if __name__ == "__main__":
    sys.exit(main())
