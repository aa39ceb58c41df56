"""Zero-reflection struct codec: ONE generated wire format for RPC, the
raft log, and FSM snapshots (ROADMAP item 1).

An early follower-scale run named the residual: reflection-msgpack
codec + replication cost per log entry roughly cancels one follower's
entire scheduling gain.  This package removes the reflection: per-type
encoders/decoders are GENERATED from the dataclass schemas once
(codec/gen.py), emit flat length-prefixed binary layouts, and serve as
the one codec for

- the RPC layer         (server/rpc.py, codec channel + per-frame tag),
- raft/WAL log entries  (server/log_codec.py, sniffing decode), and
- FSM snapshot sections (state/state_store.py table blobs).

Every frame starts with the 0xC1 magic — a byte msgpack never emits —
so the frame itself carries its codec tag: binary frames and
reflection-msgpack frames interleave freely in one stream/log/snapshot,
which is what makes rollout and the ``NOMAD_TPU_CODEC=0`` kill switch
safe (disable only stops ENCODING; decode always accepts both).

Inner string-column loops optionally drop to C++
(native/codec.cc via codec/native.py) with a differential-guarded
pure-Python twin, per the native/wal.cc precedent.

Env knobs:

- ``NOMAD_TPU_CODEC=0``            — kill switch: encode msgpack
  everywhere (decode still accepts codec frames already on disk/wire)
- ``NOMAD_TPU_CODEC_GUARD_EVERY``  — native-twin differential guard
  cadence (default 512; tests pin 1)
- ``NOMAD_TPU_NO_NATIVE=1``        — force the pure-Python twin
"""
from __future__ import annotations

import os
import time
from typing import Dict, Optional

from ..utils.telemetry import InmemSink, Telemetry
from . import native  # noqa: F401 — re-exported for guard counters
from .gen import CodecError, decode_frame, encode_frame, is_frame
from .schema import FINGERPRINT, MAGIC, VERSION

__all__ = [
    "CodecError", "MAGIC", "VERSION", "FINGERPRINT", "enabled",
    "encode", "decode", "is_frame", "stats", "reset",
    "metrics_latest", "merge_metrics", "native",
    "note_msgpack_method", "msgpack_methods", "hot_msgpack_methods",
]

_enabled_cache: Optional[bool] = None


def enabled() -> bool:
    """The kill switch (read once, reset() re-reads): default ON."""
    global _enabled_cache
    if _enabled_cache is None:
        from ..utils import knobs

        _enabled_cache = knobs.get_bool("NOMAD_TPU_CODEC")
    return _enabled_cache


# -- per-subsystem accounting ------------------------------------------------
#
# The ISSUE 11 observability contract: codec.encode_seconds /
# codec.decode_seconds histograms per subsystem (rpc/raft/snapshot),
# surfaced through /v1/metrics and the loadgen report.  Counters are
# process-global (each follower subprocess reports its own through
# Status.Metrics); the benign-race increments below trade perfect
# accuracy for zero hot-path locking — the histograms (locked inside
# InmemSink) carry the percentiles.

_SUBSYSTEMS = ("rpc", "raft", "snapshot", "other")

# One long interval: codec percentiles must survive a whole bench or
# loadgen run, like the harness pins the server sink's interval.
TELEMETRY = Telemetry(sink=InmemSink(interval=3600.0), prefix="nomad")


def _fresh_counters() -> Dict[str, Dict[str, float]]:
    return {sub: {"encodes": 0, "decodes": 0, "fallbacks": 0,
                  "encode_seconds": 0.0, "decode_seconds": 0.0,
                  "encode_bytes": 0, "decode_bytes": 0}
            for sub in _SUBSYSTEMS}


_COUNTERS = _fresh_counters()


def encode(obj, subsystem: str = "other") -> bytes:
    """One codec frame (magic + version + value tree).  Raises
    CodecError on schema drift — callers fall back to msgpack and the
    fallback is counted."""
    c = _COUNTERS.get(subsystem) or _COUNTERS["other"]
    t0 = time.monotonic()
    try:
        blob = encode_frame(obj)
    except CodecError:
        c["fallbacks"] += 1
        raise
    dt = time.monotonic() - t0
    c["encodes"] += 1
    c["encode_seconds"] += dt
    c["encode_bytes"] += len(blob)
    TELEMETRY.add_sample(f"codec.{subsystem}.encode_seconds", dt)
    return blob


def decode(blob: bytes, subsystem: str = "other"):
    """Strict decode of one codec frame (see gen.decode_frame)."""
    c = _COUNTERS.get(subsystem) or _COUNTERS["other"]
    t0 = time.monotonic()
    obj = decode_frame(blob)
    dt = time.monotonic() - t0
    c["decodes"] += 1
    c["decode_seconds"] += dt
    c["decode_bytes"] += len(blob)
    TELEMETRY.add_sample(f"codec.{subsystem}.decode_seconds", dt)
    return obj


def note_msgpack(subsystem: str, op: str, t0: float,
                 nbytes: int = 0) -> None:
    """Account a msgpack-path frame under the same time-split (the
    encode/decode seconds per leg the loadgen report records must cover
    BOTH codecs, or the split lies during mixed-codec rollout)."""
    c = _COUNTERS.get(subsystem) or _COUNTERS["other"]
    dt = time.monotonic() - t0
    c[f"{op}s"] += 1
    c[f"{op}_seconds"] += dt
    c[f"{op}_bytes"] += nbytes
    TELEMETRY.add_sample(f"codec.{subsystem}.{op}_seconds", dt)


# Per-RPC-method msgpack frame counts (ISSUE 12 satellite): which
# methods still ride the reflection fallback.  The ROADMAP item 1
# residual named Status/Serf control frames — this counter is the
# standing proof they never show up on a hot path (the loadgen report
# surfaces it per leg; the chaos gate asserts hot prefixes stay at 0).
_MSGPACK_METHODS: Dict[str, int] = {}

# Wire-method prefixes that constitute the scheduling hot path; a
# msgpack frame carrying one of these between codec-negotiated peers
# means the fallback leaked into the hot loop.
HOT_METHOD_PREFIXES = ("Eval.", "Plan.", "Node.", "Job.", "Alloc.")


def note_msgpack_method(method: str) -> None:
    # Benign-race increment, same trade as the counters above.
    _MSGPACK_METHODS[method] = _MSGPACK_METHODS.get(method, 0) + 1


def msgpack_methods() -> Dict[str, int]:
    """Cumulative msgpack-framed request counts by wire method."""
    return dict(_MSGPACK_METHODS)


def hot_msgpack_methods() -> Dict[str, int]:
    """The subset of msgpack-framed methods on the scheduling hot path
    — empty is the healthy (and gated) state for a codec fleet."""
    return {m: n for m, n in _MSGPACK_METHODS.items()
            if m.startswith(HOT_METHOD_PREFIXES)}


def stats() -> Dict[str, Dict[str, float]]:
    """Cumulative per-subsystem split; loadgen legs diff two snapshots."""
    return {sub: dict(vals) for sub, vals in _COUNTERS.items()}


def stats_delta(before: Dict[str, Dict[str, float]]
                ) -> Dict[str, Dict[str, float]]:
    now = stats()
    return {sub: {k: round(v - before.get(sub, {}).get(k, 0), 6)
                  for k, v in vals.items()}
            for sub, vals in now.items()}


def metrics_latest() -> Dict:
    """The codec sink's newest interval, /v1/metrics-shaped."""
    return TELEMETRY.sink.latest()


def merge_metrics(latest: Dict) -> Dict:
    """Merge the codec histograms/totals into a server sink's
    ``latest()`` summary (the /v1/metrics + Status.Metrics bridge: the
    codec accounts process-globally, the servers render per-sink)."""
    mine = metrics_latest()
    for section in ("Samples", "Counters", "Gauges",
                    "CounterTotals", "SampleTotals"):
        vals = mine.get(section)
        if vals:
            latest.setdefault(section, {}).update(vals)
    return latest


def reset() -> None:
    """Test/selfcheck hook: re-read the kill switch, zero counters."""
    global _enabled_cache, _COUNTERS
    _enabled_cache = None
    _COUNTERS = _fresh_counters()
    _MSGPACK_METHODS.clear()
    TELEMETRY.sink = InmemSink(interval=3600.0)
    native.reset_counters()
