"""Per-layer metric readers.

A metric is a data file ``benchmarks/metrics/<name>.json`` that names one
of the readers below and what it reads: a sink key, a field of the
client's samples, a program in the trace.  A reader that finds nothing to
read returns ``None`` and the metric is left out of the line; none ever
returns 0 for a share of a roofline.

The context a reader gets:

``sink0`` / ``sink1``  the server's metrics sink (``InmemSink.latest()``)
                       at the window's two edges
``client``             what the client process measured
``harness``            what the harness counted (compiles in the window)
``trace``              the reduced profiler trace of the window, or None
``shapes``             nodes, specs and asks of one batch, device kind
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional

from benchmarks import roofline


def _sample_delta(ctx: dict, key: str):
    c0, s0 = ctx["sink0"]["SampleTotals"].get(key, (0, 0.0))
    c1, s1 = ctx["sink1"]["SampleTotals"].get(key, (0, 0.0))
    return c1 - c0, s1 - s0


def _counter_delta(ctx: dict, key: str) -> float:
    return (ctx["sink1"]["CounterTotals"].get(key, 0.0)
            - ctx["sink0"]["CounterTotals"].get(key, 0.0))


def sample_mean(ctx: dict, spec: dict) -> Optional[float]:
    """Mean of a sink sample over the window (sum delta / count delta)."""
    n, total = _sample_delta(ctx, spec["key"])
    return total / n if n > 0 else None


def counter_delta(ctx: dict, spec: dict) -> Optional[float]:
    """A counter's increase over the window; absent counters read 0."""
    return float(_counter_delta(ctx, spec["key"]))


def counter_per_sample(ctx: dict, spec: dict) -> Optional[float]:
    """A counter's increase per sample of another key (evals per batch:
    broker dequeues over scheduler invocations)."""
    n, _ = _sample_delta(ctx, spec["per"])
    return _counter_delta(ctx, spec["key"]) / n if n > 0 else None


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (loadgen/report's arithmetic)."""
    ordered = sorted(values)
    idx = min(len(ordered) - 1, max(0, math.ceil(q * len(ordered)) - 1))
    return float(ordered[idx])


def client_percentile(ctx: dict, spec: dict) -> Optional[float]:
    values = ctx["client"].get(spec["field"]) or []
    return percentile(values, float(spec["q"])) if values else None


def client_mean(ctx: dict, spec: dict) -> Optional[float]:
    values = ctx["client"].get(spec["field"]) or []
    return sum(values) / len(values) if values else None


def client_value(ctx: dict, spec: dict) -> Optional[float]:
    v = ctx["client"].get(spec["field"])
    return None if v is None else float(v)


def harness_value(ctx: dict, spec: dict) -> Optional[float]:
    v = ctx["harness"].get(spec["field"])
    return None if v is None else float(v)


def _batches(ctx: dict) -> int:
    return _sample_delta(ctx, "nomad.worker.invoke_scheduler.device")[0]


def trace_busy_per_batch(ctx: dict, spec: dict) -> Optional[float]:
    """Device-busy milliseconds per batch: the union of device-op
    intervals in the traced window over the batches that ran in it."""
    tr, n = ctx.get("trace"), _batches(ctx)
    if not tr or n <= 0 or tr["busy_s"] <= 0:
        return None
    return tr["busy_s"] * 1000.0 / n


def trace_roofline(ctx: dict, spec: dict) -> Optional[float]:
    """Share (%) of the roofline: the least time the chip could take for
    the placement passes of the window, reckoned from their shapes, over
    the traced time of the placement program."""
    tr, n = ctx.get("trace"), _batches(ctx)
    if not tr or n <= 0:
        return None
    took = sum(v for k, v in tr["module_s"].items()
               if spec["program"] in k)
    if took <= 0:
        return None
    sh = ctx["shapes"]
    _, total_asks = _sample_delta(ctx, "nomad.worker.invoke_scheduler.asks")
    evals = _counter_delta(ctx, "nomad.broker.dequeue")
    if total_asks <= 0 or evals <= 0:
        return None
    work = roofline.placement_work(nodes=sh["nodes"], specs=evals,
                                   asks=total_asks)
    least = roofline.least_seconds(work, sh["device_kind"])
    return 100.0 * least["seconds"] / took


READERS: Dict[str, Callable[[dict, dict], Optional[float]]] = {
    f.__name__: f for f in (
        sample_mean, counter_delta, counter_per_sample,
        client_percentile, client_mean, client_value, harness_value,
        trace_busy_per_batch, trace_roofline)}


def read(ctx: dict, spec: dict) -> Optional[float]:
    try:
        reader = READERS[spec["reader"]]
    except KeyError:
        raise KeyError(f"metric {spec.get('name')!r}: unknown reader "
                       f"{spec.get('reader')!r}") from None
    value = reader(ctx, spec)
    if value is None or (isinstance(value, float) and math.isnan(value)):
        return None
    return value
