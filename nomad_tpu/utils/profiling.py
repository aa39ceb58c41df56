"""Runtime profiling: the pprof-equivalent debug surface plus JAX device
tracing.

The reference mounts net/http/pprof under /debug/pprof when enableDebug
is set (command/agent/http.go:173-178) — CPU profiles, heap profiles, and
goroutine stacks.  The equivalents here:

- profile:   sampling profiler over a bounded window — stacks of EVERY
             live thread sampled at ~200Hz and aggregated (pprof's CPU
             profile is also a sampler; a cProfile hook would only see
             the handler's own thread).
- heap:      tracemalloc top allocation sites (started lazily on first
             request; subsequent requests diff against a live tracer).
- threads:   stack dump of every live thread (goroutine-dump analogue).
- trace:     jax.profiler device trace written to a directory for
             TensorBoard/XProf — the device-side replacement for pprof
             the SURVEY calls for ("JAX profiler + XLA HLO dumps replace
             pprof for device side", SURVEY.md §5).

All captures are bounded and lock-free with respect to the runtime: the
CPU profiler uses the interpreter's global profile hook for its window;
heap/threads are point-in-time snapshots.
"""
from __future__ import annotations

import io
import sys
import threading
import time
import traceback
from typing import Dict, Optional

from . import tracing

_profile_lock = threading.Lock()


def cpu_profile(seconds: float = 1.0, sort: str = "cumulative",
                top: int = 60, hz: float = 200.0) -> str:
    """Sample every live thread's stack for ``seconds`` and render an
    aggregated report: per-frame inclusive/leaf sample counts across ALL
    threads (cProfile's hook is per-thread — it would only ever see this
    handler sleeping).  Serialized by a module lock so concurrent profile
    requests don't double the sampling load."""
    seconds = max(0.05, min(float(seconds), 30.0))
    interval = 1.0 / max(1.0, min(hz, 1000.0))
    if not _profile_lock.acquire(timeout=0.1):
        raise RuntimeError("another cpu profile is in progress")
    try:
        me = threading.get_ident()
        inclusive: dict = {}
        leaf: dict = {}
        samples = 0
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            for tid, frame in sys._current_frames().items():
                if tid == me:
                    continue
                depth = 0
                f = frame
                first = True
                while f is not None and depth < 64:
                    code = f.f_code
                    # co_qualname is 3.11+; co_name on older runtimes
                    key = (code.co_filename, code.co_firstlineno,
                           getattr(code, "co_qualname", code.co_name))
                    inclusive[key] = inclusive.get(key, 0) + 1
                    if first:
                        leaf[key] = leaf.get(key, 0) + 1
                        first = False
                    f = f.f_back
                    depth += 1
            samples += 1
            time.sleep(interval)
        out = io.StringIO()
        out.write(f"{samples} samples over {seconds:.2f}s "
                  f"({len(inclusive)} function calls observed)\n\n")
        out.write(f"{'incl':>8} {'leaf':>8}  function\n")
        ranked = sorted(inclusive.items(),
                        key=lambda kv: -(leaf.get(kv[0], 0) if sort == "leaf"
                                         else kv[1]))
        for key, n in ranked[:top]:
            fname, lineno, qual = key
            out.write(f"{n:>8} {leaf.get(key, 0):>8}  "
                      f"{qual} ({fname}:{lineno})\n")
        return out.getvalue()
    finally:
        _profile_lock.release()


_heap_started = False


def heap_profile(top: int = 40) -> Dict:
    """tracemalloc snapshot of the top allocation sites.

    The tracer is started on the first request (like pprof's heap
    profile, which is always-on in Go; Python's tracer costs ~2x alloc
    overhead, so it's opt-in via first use of this endpoint)."""
    global _heap_started
    import tracemalloc

    if not _heap_started:
        tracemalloc.start(10)
        _heap_started = True
        return {"status": "tracer started; re-request for data"}
    snap = tracemalloc.take_snapshot()
    stats = snap.statistics("lineno")[:top]
    current, peak = tracemalloc.get_traced_memory()
    return {
        "current_bytes": current,
        "peak_bytes": peak,
        "top": [
            {
                "site": str(st.traceback[0]) if st.traceback else "?",
                "size_bytes": st.size,
                "count": st.count,
            }
            for st in stats
        ],
    }


def thread_dump() -> str:
    """Stack trace of every live thread — the goroutine-dump analogue
    (pprof /debug/pprof/goroutine?debug=2)."""
    frames = sys._current_frames()
    by_id = {t.ident: t for t in threading.enumerate()}
    out = io.StringIO()
    for tid, frame in sorted(frames.items()):
        t = by_id.get(tid)
        name = t.name if t is not None else "?"
        daemon = " daemon" if (t is not None and t.daemon) else ""
        out.write(f"thread {tid} [{name}]{daemon}:\n")
        traceback.print_stack(frame, file=out)
        out.write("\n")
    return out.getvalue()


#: The host event that joins a capture to the span clock: written at
#: ``start()`` while its ``tracing.now()`` reading is taken, so
#: ``trace clock - perf_counter = marker's start in the trace -
#: marker_perf_counter`` for every span of the interval.
CLOCK_MARKER = "nomad_tpu_clock_marker"
HOST_SPANS_FILE = "host_spans.json"


class DeviceTracer:
    """Bounded jax.profiler trace sessions (device-side profiling).

    One active trace at a time; the trace directory is returned so the
    operator can pull it into TensorBoard/XProf.  A capture carries its
    own join to the eval-lifecycle spans (utils/tracing.py): the clock
    marker above, and, when the tracer is armed, the interval's spans
    beside the trace in ``host_spans.json``."""

    def __init__(self, base_dir: Optional[str] = None):
        import os
        import tempfile

        self.base_dir = base_dir or os.path.join(
            tempfile.gettempdir(), "nomad_tpu_traces")
        self._lock = threading.Lock()
        self._active_dir: Optional[str] = None
        self._started_at = 0.0
        self._marker_pc = 0.0

    def start(self) -> str:
        import os

        import jax

        with self._lock:
            if self._active_dir is not None:
                raise RuntimeError(
                    f"trace already active in {self._active_dir}")
            d = os.path.join(self.base_dir, time.strftime("%Y%m%d-%H%M%S"))
            os.makedirs(d, exist_ok=True)
            jax.profiler.start_trace(d)
            with jax.profiler.TraceAnnotation(CLOCK_MARKER):
                self._marker_pc = tracing.now()
            self._active_dir = d
            self._started_at = time.monotonic()
            return d

    def stop(self) -> Dict:
        import json
        import os

        import jax

        with self._lock:
            if self._active_dir is None:
                raise RuntimeError("no active trace")
            jax.profiler.stop_trace()
            t_stop = tracing.now()
            d, self._active_dir = self._active_dir, None
            info = {"dir": d,
                    "duration_s": round(time.monotonic() - self._started_at,
                                        3),
                    "marker": CLOCK_MARKER,
                    "marker_perf_counter": self._marker_pc}
            tr = tracing.TRACER
            if tr is not None:
                # The interval's spans, on the span clock: add
                # (marker's start in the trace - marker_perf_counter)
                # to put them on the trace's.
                spans = [sp for sp in tr.recent(tr.capacity)
                         if sp["End"] >= self._marker_pc
                         and sp["Start"] <= t_stop]
                path = os.path.join(d, HOST_SPANS_FILE)
                with open(path, "w") as fh:
                    json.dump({"marker": CLOCK_MARKER,
                               "marker_perf_counter": self._marker_pc,
                               "dropped": tr.dropped, "spans": spans},
                              fh, default=str)
                info["host_spans"] = path
            return info

    def capture(self, seconds: float = 1.0) -> Dict:
        """start → sleep → stop in one bounded call (the /trace?seconds=N
        endpoint shape)."""
        seconds = max(0.05, min(float(seconds), 30.0))
        d = self.start()
        try:
            time.sleep(seconds)
        finally:
            info = self.stop()
        info["dir"] = d
        return info


_tracer_lock = threading.Lock()
_tracer: Optional[DeviceTracer] = None


def get_tracer() -> DeviceTracer:
    """Process-wide tracer singleton: the jax profiler is process-global,
    so two DeviceTracer instances started concurrently would corrupt each
    other's sessions."""
    global _tracer
    with _tracer_lock:
        if _tracer is None:
            _tracer = DeviceTracer()
        return _tracer
