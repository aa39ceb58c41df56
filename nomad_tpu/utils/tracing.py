"""Eval-lifecycle tracing plane: cheap structured spans threaded through
the scheduling pipeline (broker → worker → batch scheduler → plan
applier → raft), queryable per eval.

Why not logs: at batch scale, "where did eval X spend its time" is a
join across six subsystems on four threads.  Spans carry ids, parents,
``perf_counter`` timestamps, and attrs; everything touching one evaluation
tags ``eval_id`` (batch spans tag ``eval_ids``), so the whole lifecycle
— enqueue → dequeue → batch phases → plan submit → apply — comes back
from one index lookup (``/v1/trace/eval/<id>`` in agent/http.py).

Cost discipline (the ``fault.py`` contract): the plane is **off by
default** and the only production state is off.  Every instrumented
site reads one module global (``TRACER``) and branches; disarmed there
are no locks, no allocations, no timestamps.  Arm process-wide with
``tracing.enable()`` (tests, the selfcheck drill) or the
``NOMAD_TPU_TRACE=1`` env var (read at server construction).

Threading model: spans nest via a thread-local stack (parent linkage
within a thread); an eval's lifecycle *crosses* threads (RPC handler →
worker → plan applier), so cross-thread correlation is by ``eval_id``
attr.  Where one span *causes* work on another thread (a submitted plan
→ the applier's evaluate/apply) the cause's span id travels with the
work and the far side passes it as ``parent_id=``, so self time (a
span less its children) is computable across the hand-off.
``trace_for_eval`` returns every span tagged with the eval, sorted by
start time — the timeline.

One clock with the device trace: batch-level spans opened with
``annotate=True`` also enter a ``jax.profiler.TraceAnnotation`` of the
same name, so a profiler capture's host line carries them on the
trace's own clock (free while no capture is running).

Correlation with the chaos plane: ``fault.py`` reports every rule fire
here (``note_fault`` → a ``fault.fire`` span carrying the same
(point, rule, action) triple that ``fault.trace()`` records), and
``ops/breaker.py`` reports state transitions (``breaker.transition``
spans) — so a trace of a chaos-injected eval shows *which* injected
fault and breaker movement shaped its path.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

__all__ = [
    "Span", "Tracer", "TRACER", "NOOP", "now",
    "enable", "disable", "enabled", "span", "event", "record",
    "trace_for_eval", "recent", "dropped", "note_fault", "mark",
    "close_mark", "Stages", "timed",
]

#: The span clock.  ``time.perf_counter()``: monotonic like
#: ``time.monotonic()`` (immune to NTP steps) but highest-resolution,
#: so sub-millisecond phase spans don't quantize.  Callers feeding
#: already-measured timestamps into :func:`record` must use THIS clock
#: (``tracing.now()``) — mixing bases corrupts span ordering and the
#: wall-clock backdating.
now = time.perf_counter

# Bounded-store defaults: the recency ring holds ~4k completed spans;
# independently, the eval index (LRU over the last ~1k distinct eval
# ids) pins ≤256 spans per indexed eval even after they leave the ring,
# so the armed-plane worst case is ~256k retained spans, not 4k.
DEFAULT_CAPACITY = 4096
DEFAULT_MAX_EVALS = 1024
MAX_SPANS_PER_EVAL = 256
# A batch span tags every member eval; at bench scale a batch can carry
# 1k+ evals, and indexing/serializing millions of ids per phase span
# under the tracer lock would swamp the armed plane.  Beyond this cap
# the span keeps the first N ids (indexed + serialized) plus an
# `eval_ids_elided` count.
MAX_EVAL_IDS_PER_SPAN = 128
# Cross-thread umbrella marks (eval.e2e: RPC submit → broker ack): an
# eval whose ack never comes (leadership churn) must not pin its mark
# forever, so the mark table is a bounded LRU.
MAX_MARKS = 4096


class Span:
    """One completed (or in-flight) operation.  ``start``/``end`` are
    ``tracing.now()`` (``time.perf_counter()``) — comparable across
    threads, immune to wall clock steps; ``wall`` is the wall-clock
    start kept only as the epoch anchor for humans."""

    __slots__ = ("span_id", "parent_id", "name", "start", "end", "wall",
                 "attrs")

    def __init__(self, span_id: int, parent_id: int, name: str,
                 start: float, attrs: Dict[str, Any]):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start = start
        self.end = start
        self.wall = time.time()
        self.attrs = attrs

    def set(self, **attrs: Any) -> None:
        """Attach attrs mid-span (e.g. the nack reason on failure)."""
        self.attrs.update(attrs)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "SpanID": self.span_id,
            "ParentID": self.parent_id,
            "Name": self.name,
            "Start": self.start,
            "End": self.end,
            "DurationMs": round((self.end - self.start) * 1000.0, 4),
            "Wall": self.wall,
            "Attrs": self.attrs,
        }


class _EvalBucket:
    """Per-eval span index entry: the retained spans plus how many were
    evicted once the MAX_SPANS_PER_EVAL cap was hit."""

    __slots__ = ("spans", "dropped")

    def __init__(self) -> None:
        self.spans: List[Span] = []
        self.dropped = 0


class _NoopSpan:
    """Shared do-nothing span/context-manager handed out while tracing is
    disabled — call sites keep one code path."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs: Any) -> None:
        pass


#: Shared disabled-plane singleton; call sites that must not even build
#: the attrs dict while disarmed branch on TRACER and use this directly.
NOOP = _NOOP = _NoopSpan()


_TRACE_ANNOTATION = None


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` (imported on first use: this
    module must stay importable without touching JAX)."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(name)


class _ActiveSpan:
    """Context manager pushing/popping one span on the thread-local
    stack; an exception escaping the block is recorded on the span.
    ``finish(end)`` closes it at a stamp the caller already took, so a
    sample and its span share their two ``perf_counter`` reads."""

    __slots__ = ("tracer", "sp", "_ann")

    def __init__(self, tracer: "Tracer", sp: Span, annotate: bool):
        self.tracer = tracer
        self.sp = sp
        self._ann = _annotation(sp.name) if annotate else None

    def __enter__(self) -> Span:
        self.tracer._push(self.sp)
        if self._ann is not None:
            self._ann.__enter__()
        return self.sp

    def __exit__(self, etype, evalue, tb) -> bool:
        if etype is not None:
            self.sp.attrs.setdefault("error", etype.__name__)
            self.sp.attrs.setdefault("error_detail", str(evalue))
        self.finish()
        return False

    def finish(self, end: Optional[float] = None) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self.tracer._pop(self.sp, end)


class Tracer:
    """The armed state: a bounded ring of completed spans plus an LRU
    index eval_id → spans.  All mutation under one lock; span creation
    itself (the common case) takes the lock once at finish."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 max_evals: int = DEFAULT_MAX_EVALS):
        self._l = threading.Lock()
        self._seq = itertools.count(1)
        self._spans: deque = deque(maxlen=max(16, capacity))
        # Spans ever stored; what the ring no longer holds is
        # ``dropped`` (a reader of ``recent()`` must know whether the
        # interval it wants was evicted).
        self.recorded = 0
        self._by_eval: "OrderedDict[str, _EvalBucket]" = OrderedDict()
        self.max_evals = max(1, max_evals)
        self._local = threading.local()
        # eval_id → (tracing.now() submit time, attrs): the open end of
        # a cross-thread umbrella span (mark/close_mark).
        self._marks: "OrderedDict[str, tuple]" = OrderedDict()

    # -- thread-local span stack ------------------------------------------

    def _stack(self) -> List[Span]:
        stk = getattr(self._local, "stack", None)
        if stk is None:
            stk = self._local.stack = []
        return stk

    def _push(self, sp: Span) -> None:
        self._stack().append(sp)

    def _pop(self, sp: Span, end: Optional[float] = None) -> None:
        stk = self._stack()
        if stk and stk[-1] is sp:
            stk.pop()
        elif sp in stk:  # defensive: mis-nested exit
            stk.remove(sp)
        sp.end = now() if end is None else end
        self._record(sp)

    def current(self) -> Optional[Span]:
        stk = getattr(self._local, "stack", None)
        return stk[-1] if stk else None

    # -- span creation -----------------------------------------------------

    def reserve_id(self) -> int:
        """A span id handed out ahead of its span: children recorded
        first name it as ``parent_id=``, the parent is ``record``ed
        afterwards with ``span_id=`` (the batch.device children)."""
        return next(self._seq)

    def _new_span(self, name: str, attrs: Dict[str, Any],
                  parent_id: Optional[int] = None,
                  span_id: Optional[int] = None,
                  start: Optional[float] = None) -> Span:
        evs = attrs.get("eval_ids")
        if evs is not None and len(evs) > MAX_EVAL_IDS_PER_SPAN:
            attrs["eval_ids"] = list(evs[:MAX_EVAL_IDS_PER_SPAN])
            attrs["eval_ids_elided"] = len(evs) - MAX_EVAL_IDS_PER_SPAN
        parent = self.current()
        if not parent_id:
            parent_id = parent.span_id if parent is not None else 0
        # Inherit the eval correlation key from the enclosing span so
        # inner spans (wait_for_index, phases) need not repeat it.
        if parent is not None and "eval_id" not in attrs \
                and "eval_ids" not in attrs:
            pev = parent.attrs.get("eval_id")
            if pev is not None:
                attrs["eval_id"] = pev
            else:
                pevs = parent.attrs.get("eval_ids")
                if pevs is not None:
                    attrs["eval_ids"] = pevs
        sp = Span(span_id or next(self._seq), parent_id, name, now(), attrs)
        if start is not None:
            # A stamp the caller already took: backdate the wall clock
            # along with the monotonic start.
            sp.wall -= sp.start - start
            sp.start = start
        return sp

    def span(self, name: str, *, parent_id: Optional[int] = None,
             annotate: bool = False, start: Optional[float] = None,
             **attrs: Any) -> _ActiveSpan:
        """``parent_id``: the span on ANOTHER thread that caused this one
        (default: the enclosing span on this thread).  ``annotate``: also
        enter a profiler TraceAnnotation (batch-level spans only).
        ``start``: a ``tracing.now()`` stamp the caller already took."""
        return _ActiveSpan(
            self, self._new_span(name, attrs, parent_id, start=start),
            annotate)

    def event(self, name: str, **attrs: Any) -> Span:
        """Zero-duration span (a point-in-time lifecycle marker:
        broker enqueue/ack, a breaker transition, a fault fire)."""
        sp = self._new_span(name, attrs)
        self._record(sp)
        return sp

    def record(self, name: str, start: float, end: float, *,
               parent_id: Optional[int] = None,
               span_id: Optional[int] = None, **attrs: Any) -> Span:
        """Retroactively record a completed span from already-measured
        ``tracing.now()`` timestamps (the batch scheduler's phase
        timers)."""
        sp = self._new_span(name, attrs, parent_id, span_id, start)
        sp.end = end
        self._record(sp)
        return sp

    # -- cross-thread umbrella marks ---------------------------------------

    def mark(self, eval_id: str, **attrs: Any) -> None:
        """Open an umbrella: remember WHEN (``tracing.now()``) this eval
        was submitted, so whichever thread later closes it can record
        one span covering the whole client-visible lifecycle."""
        with self._l:
            self._marks[eval_id] = (now(), attrs)
            self._marks.move_to_end(eval_id)
            while len(self._marks) > MAX_MARKS:
                self._marks.popitem(last=False)

    def close_mark(self, eval_id: str, name: str = "eval.e2e",
                   **attrs: Any) -> None:
        """Close the umbrella opened by :meth:`mark` — records one
        retroactive ``eval.e2e`` span (submit → now) stitching client
        RPC → broker → worker → plan-apply across threads.  No-op when
        no mark exists (evals born inside the scheduler)."""
        with self._l:
            entry = self._marks.pop(eval_id, None)
        if entry is None:
            return
        start, mark_attrs = entry
        merged = dict(mark_attrs)
        merged.update(attrs)
        merged["eval_id"] = eval_id
        self.record(name, start, now(), **merged)

    # -- storage / query ---------------------------------------------------

    def _record(self, sp: Span) -> None:
        keys = []
        ev = sp.attrs.get("eval_id")
        if ev is not None:
            keys.append(ev)
        evs = sp.attrs.get("eval_ids")
        if evs:
            keys.extend(evs)
        with self._l:
            self._spans.append(sp)
            self.recorded += 1
            for key in keys:
                bucket = self._by_eval.get(key)
                if bucket is None:
                    bucket = self._by_eval[key] = _EvalBucket()
                    while len(self._by_eval) > self.max_evals:
                        self._by_eval.popitem(last=False)
                else:
                    self._by_eval.move_to_end(key)
                bucket.spans.append(sp)
                if len(bucket.spans) > MAX_SPANS_PER_EVAL:
                    # Drop the OLDEST span: the terminal spans (ack/nack,
                    # final attempt) answer "how did this eval end" and
                    # must survive.
                    del bucket.spans[0]
                    bucket.dropped += 1

    def trace_for_eval(self, eval_id: str) -> List[Dict[str, Any]]:
        with self._l:
            bucket = self._by_eval.get(eval_id)
            spans = list(bucket.spans) if bucket is not None else []
            dropped = bucket.dropped if bucket is not None else 0
        spans.sort(key=lambda sp: sp.start)
        out = [sp.to_dict() for sp in spans]
        if dropped and out:
            # Flag the (new) head of a truncated timeline on the rendered
            # copy only — the Span's attrs dict is shared across the
            # buckets of every eval in the batch.
            out[0] = dict(out[0], Attrs=dict(out[0]["Attrs"],
                                             trace_truncated=dropped))
        return out

    def recent(self, n: int = 100) -> List[Dict[str, Any]]:
        """The last ``n`` completed spans, oldest first."""
        if n <= 0:  # spans[-0:] would return everything
            return []
        with self._l:
            spans = list(self._spans)
        return [sp.to_dict() for sp in spans[-n:]]

    @property
    def capacity(self) -> int:
        return self._spans.maxlen

    @property
    def dropped(self) -> int:
        """Spans the recency ring has evicted."""
        return max(0, self.recorded - self._spans.maxlen)


# -- process-wide arming ------------------------------------------------------

# The single global every instrumented site reads.  ``None`` ⇒ disabled
# ⇒ one load + one comparison per site (the fault.py discipline).
TRACER: Optional[Tracer] = None


def enable(capacity: int = DEFAULT_CAPACITY,
           max_evals: int = DEFAULT_MAX_EVALS) -> Tracer:
    global TRACER
    TRACER = Tracer(capacity=capacity, max_evals=max_evals)
    return TRACER


def disable() -> None:
    global TRACER
    TRACER = None


def enabled() -> bool:
    return TRACER is not None


def span(name: str, **attrs: Any):
    """``with tracing.span("worker.attempt", eval_id=...) as sp:`` —
    the no-op singleton when disabled."""
    tr = TRACER
    if tr is None:
        return _NOOP
    return tr.span(name, **attrs)


def eval_id_attrs(evals, total: int) -> Dict[str, Any]:
    """Correlation attrs for a batch span without materializing more ids
    than the span retains — callers may hold million-eval batches.
    ``evals`` is any iterable of objects with ``.id``; ``total`` is the
    full batch size."""
    ids = [ev.id for ev, _ in zip(evals, range(MAX_EVAL_IDS_PER_SPAN))]
    out: Dict[str, Any] = {"eval_ids": ids}
    if total > len(ids):
        out["eval_ids_elided"] = total - len(ids)
    return out


def plan_attrs(plans) -> Dict[str, Any]:
    """Correlation attrs for the spans of one plan submission:
    ``eval_id`` for one plan, ``eval_ids`` for a batch's plans (the
    tracer keeps the first ``MAX_EVAL_IDS_PER_SPAN``)."""
    if len(plans) == 1:
        return {"eval_id": plans[0].eval_id}
    return {"eval_ids": [plan.eval_id for plan in plans]}


class Stages:
    """The contiguous stages of one parent, timed for the sink always
    and for the tracer when it is armed, from one set of stamps.

    A boundary is ONE ``perf_counter`` stamp that closes the running
    stage and opens the next (``begin``), so the stages tile their parent
    and a stage's sample and span share their stamps.  A stage begun
    again (a loop over specs) accumulates: ``seconds`` maps a stage to
    its total.  Disarmed, a boundary costs its stamp and a dict store.

    Spans, armed, are ``<prefix><stage>``.  ``live``: every run of a
    stage is a live span, entered as a profiler TraceAnnotation too (the
    device call's stages, which a device capture shows on its own
    clock), child of the parent span the caller records afterwards under
    ``parent_id``, reserved here.  Otherwise ``lay`` records one span a
    stage once the parent is known, its length the stage's total, back
    to back from the parent's start: the true intervals where each stage
    ran once, in order.  ``with stages:`` closes whatever stage an
    exception left open."""

    __slots__ = ("prefix", "seconds", "parent_id", "_tr", "_open", "_name",
                 "_t")

    def __init__(self, prefix: str, live: bool = False) -> None:
        self.prefix = prefix
        self.seconds: Dict[str, float] = {}
        self._tr = TRACER if live else None
        self.parent_id = (self._tr.reserve_id()
                          if self._tr is not None else 0)
        self._open = None
        self._name = ""
        self._t = 0.0

    def begin(self, name: str, t: Optional[float] = None) -> float:
        """Open ``name`` at ``t`` (now when not given), closing the
        stage that was running at the same stamp."""
        if t is None:
            t = now()
        if self._name:
            self.end(t)
        self._name, self._t = name, t
        if self._tr is not None:
            self._open = self._tr.span(self.prefix + name,
                                       parent_id=self.parent_id,
                                       annotate=True, start=t)
            self._open.__enter__()
        return t

    def end(self, t: Optional[float] = None) -> float:
        if t is None:
            t = now()
        if self._name:
            self.seconds[self._name] = (self.seconds.get(self._name, 0.0)
                                        + t - self._t)
            self._name = ""
            if self._open is not None:
                self._open.finish(t)
                self._open = None
        return t

    def carve(self, name: str, seconds: float, out_of: str) -> None:
        """Give ``name`` the ``seconds`` that a callee stamped inside
        the stage ``out_of`` (no more than it holds): the sum stays."""
        seconds = min(seconds, self.seconds.get(out_of, 0.0))
        self.seconds[out_of] = self.seconds.get(out_of, 0.0) - seconds
        self.seconds[name] = self.seconds.get(name, 0.0) + seconds

    def lay(self, start: float, names, parent_id: int) -> None:
        """Armed: one span a stage of ``names``, in that order, back to
        back from ``start``, children of ``parent_id``."""
        tr = TRACER
        if tr is None:
            return
        for name in names:
            end = start + self.seconds.get(name, 0.0)
            tr.record(self.prefix + name, start, end, parent_id=parent_id)
            start = end

    def __enter__(self) -> "Stages":
        return self

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


class timed:
    """``with tracing.timed(metrics, "plan.apply", cpu=True, ...) as t:``
    one stage as a sink sample ``key`` (ms) always and, armed, a span of
    the same name from the same two stamps (``t.start``, ``t.end``;
    ``t.span`` is the open span, NOOP disarmed, ``t.span_id`` its id,
    0 disarmed).  ``attrs`` is called for
    the span's attrs only when the tracer is armed.  ``cpu``: also read
    ``time.thread_time()`` at the two stamps: sample ``<key>.cpu`` and
    attr ``cpu_ms``, the CPU time of THIS thread inside the stage.  Wall
    less CPU is the time the thread did not run: the interpreter lock,
    the disk, the device, a condition."""

    __slots__ = ("metrics", "key", "cpu", "attrs", "kw", "start", "end",
                 "span", "span_id", "_active", "_c0")

    def __init__(self, metrics, key: str, *, cpu: bool = False,
                 attrs=None, **kw: Any) -> None:
        self.metrics = metrics
        self.key = key
        self.cpu = cpu
        self.attrs = attrs
        self.kw = kw
        self.span = _NOOP
        self.span_id = 0
        self._active = None

    def __enter__(self) -> "timed":
        tr = TRACER
        self.start = now()
        if self.cpu:
            self._c0 = time.thread_time()
        if tr is not None:
            attrs = self.attrs() if self.attrs is not None else {}
            self._active = tr.span(self.key, start=self.start, **self.kw,
                                   **attrs)
            self.span = self._active.__enter__()
            self.span_id = self.span.span_id
        return self

    def __exit__(self, etype, evalue, tb) -> bool:
        cpu_ms = ((time.thread_time() - self._c0) * 1000.0
                  if self.cpu else 0.0)
        if self._active is None:
            self.end = now()
        else:
            if self.cpu:
                self.span.attrs["cpu_ms"] = round(cpu_ms, 4)
            self._active.__exit__(etype, evalue, tb)
            self.end = self.span.end
        self.metrics.add_sample(self.key, (self.end - self.start) * 1000.0)
        if self.cpu:
            self.metrics.add_sample(self.key + ".cpu", cpu_ms)
        return False


def event(name: str, **attrs: Any) -> None:
    tr = TRACER
    if tr is not None:
        tr.event(name, **attrs)


def record(name: str, start: float, end: float, **attrs: Any) -> None:
    tr = TRACER
    if tr is not None:
        tr.record(name, start, end, **attrs)


def trace_for_eval(eval_id: str) -> List[Dict[str, Any]]:
    tr = TRACER
    return tr.trace_for_eval(eval_id) if tr is not None else []


def recent(n: int = 100) -> List[Dict[str, Any]]:
    tr = TRACER
    return tr.recent(n) if tr is not None else []


def dropped() -> int:
    tr = TRACER
    return tr.dropped if tr is not None else 0


def mark(eval_id: str, **attrs: Any) -> None:
    tr = TRACER
    if tr is not None:
        tr.mark(eval_id, **attrs)


def close_mark(eval_id: str, name: str = "eval.e2e", **attrs: Any) -> None:
    tr = TRACER
    if tr is not None:
        tr.close_mark(eval_id, name, **attrs)


def note_fault(point: str, rule_index: int, action: str) -> None:
    """Called by fault.FaultPlane.fire on every rule fire: the tracing
    twin of the plane's own trace(), attached to the current span so a
    chaos-shaped eval's timeline shows which injection hit it."""
    tr = TRACER
    if tr is not None:
        tr.event("fault.fire", point=point, rule=rule_index,
                 action=action)
