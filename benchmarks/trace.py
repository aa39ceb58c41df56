"""Reduction from a profiler trace to device metrics.

``load`` reads the ``.xplane.pb`` JAX wrote with nothing but JAX and
returns plain events; ``reduce`` works on those, so the tests check it on
a small recorded list.  Busy time is the union of the intervals in which
an operation ran on a device, averaged over the devices used.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]        # name, start s, end s (trace clock)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLOCK_MARKER = "bench_clock_marker"


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> Dict:
    """{"devices": {plane: {"ops": [Event], "modules": [Event]}},
        "marker_s": start of the host clock marker or None,
        "lines": {plane: [line names]}}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    lines: Dict[str, List[str]] = {}
    marker = None
    for plane in data.planes:
        lines[plane.name] = [ln.name for ln in plane.lines]
        is_dev = plane.name.startswith("/device:") and "TPU" in plane.name
        for line in plane.lines:
            if is_dev and line.name in (OPS_LINE, MODULES_LINE):
                kind = "ops" if line.name == OPS_LINE else "modules"
                bucket = devices.setdefault(
                    plane.name, {"ops": [], "modules": []})[kind]
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    bucket.append((ev.name, start,
                                   start + ev.duration_ns * 1e-9))
            elif not is_dev and marker is None:
                for ev in line.events:
                    if ev.name == CLOCK_MARKER:
                        marker = ev.start_ns * 1e-9
                        break
    return {"devices": devices, "marker_s": marker, "lines": lines}


def short_name(name: str) -> str:
    """An operation's own name: the trace prints the whole HLO line
    (``%while.95 = (s32[...]) while(...)``); keep ``while.95``."""
    head = name.split(" = ", 1)[0].split("(", 1)[0].strip()
    return head.lstrip("%") or name[:40]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    return [(n, max(a, t0), min(b, t1)) for n, a, b in events
            if b > t0 and a < t1]


def module_of(op: Event, modules: Sequence[Event]) -> str:
    """The program an operation ran in: the module event that holds it."""
    _, a, b = op
    for name, ma, mb in modules:
        if ma <= a and b <= mb + 1e-9:
            return name.split("(")[0]
    return ""


def reduce(trace: Dict, t0: float, t1: float,
           spans: Sequence[Tuple[str, float, float]] = (),
           outer: Sequence[Tuple[float, float]] = ()) -> Dict:
    """Device metrics of the window [t0, t1) on the trace's clock.

    ``spans`` are the host's leaf spans (name, start, end) on the same
    clock and ``outer`` the batch spans that hold them.  An idle gap is
    split by the leaf spans that cover it; what a batch covers beyond its
    leaves is ``other batch work (host)`` and the rest ``between
    batches``."""
    devs = trace["devices"]
    if not devs:
        return {"busy_s": 0.0, "window_s": t1 - t0, "devices": 0,
                "module_s": {}, "module_launches": {}, "device_ops": [],
                "idle_gaps": []}
    busy_total, per_op, per_module = 0.0, {}, {}
    gaps: Dict[str, float] = {}
    launches: Dict[str, int] = {}
    for plane, ev in devs.items():
        ops = [e for e in clip(ev["ops"], t0, t1)]
        mods = sorted(clip(ev["modules"], t0, t1), key=lambda e: e[1])
        busy = union((a, b) for _, a, b in ops)
        busy_total += sum(b - a for a, b in busy)
        mi = 0
        for op in sorted(ops, key=lambda e: e[1]):
            while mi < len(mods) and mods[mi][2] < op[1]:
                mi += 1
            mod = module_of(op, mods[mi:mi + 2])
            key = f"{mod}/{short_name(op[0])}" if mod else short_name(op[0])
            per_op[key] = per_op.get(key, 0.0) + (op[2] - op[1])
        for name, a, b in mods:
            short = name.split("(")[0]
            per_module[short] = per_module.get(short, 0.0) + (b - a)
            launches[short] = launches.get(short, 0) + 1
        edges = [t0] + [x for ab in busy for x in ab] + [t1]
        for ga, gb in zip(edges[0::2], edges[1::2]):
            if gb - ga <= 0:
                continue
            left = gb - ga
            for name, sa, sb in spans:
                ov = min(gb, sb) - max(ga, sa)
                if ov > 0:
                    gaps[name] = gaps.get(name, 0.0) + ov
                    left -= ov
            held = sum(max(0.0, min(gb, ob) - max(ga, oa)) for oa, ob in outer)
            other = max(0.0, held - (gb - ga - left)) if outer else 0.0
            for name, part in (("other batch work (host)", other),
                               ("between batches", left - other)):
                if part > 0:
                    gaps[name] = gaps.get(name, 0.0) + part
    n = len(devs)
    top = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": busy_total / n, "window_s": t1 - t0, "devices": n,
            "module_s": {k: v / n for k, v in per_module.items()},
            "module_launches": launches,
            "device_ops": top(per_op),
            "idle_gaps": top({k: v / n for k, v in gaps.items()})}
