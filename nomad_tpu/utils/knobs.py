"""Central registry for every ``NOMAD_TPU_*`` environment knob.

The repo grew ~60 env knobs across four PR generations, each read with
its own inline ``os.environ.get(...)`` idiom and its own parsing quirks
("" vs unset, ``("1", "true")`` vs ``not in ("0", "false")``).  Two
failure modes followed: knob semantics drifted between read sites, and
the README table drifted from the code.  This module is the single
authority:

- every knob is **declared once** here (name, type, default, one-line
  doc) — reads of undeclared names raise :class:`UnknownKnobError`;
- every read goes through :func:`get_bool` / :func:`get_int` /
  :func:`get_float` / :func:`get_str` / :func:`raw` — the static
  analysis pass (``python -m nomad_tpu.analysis``) fails the tree on
  any ad-hoc ``os.environ`` read of a ``NOMAD_TPU_*`` name outside
  this file;
- the README "Env knobs" table is **generated** from the registry
  (:func:`render_readme_table`) and asserted in sync by the same pass.

Parsing semantics (the one place that decides):

- values are re-read from ``os.environ`` on every call — knobs are
  runtime kill-switches, never cached at import;
- bool: unset or empty ⇒ default; otherwise anything except
  ``0/false/no/off`` (case-insensitive) is true;
- int/float: unset, empty, or unparseable ⇒ default (a malformed knob
  must degrade to the default, not crash a server mid-flight) — but an
  unparseable value warns ONCE per name on stderr so an operator typo
  (``NOMAD_TPU_ALLOC_LOG_CAP=256k``) cannot silently run with the
  default;
- save/restore sites (arm a knob for a drill, restore after) use
  :func:`raw`, which returns the verbatim env value or ``None``.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Iterator, Optional

__all__ = [
    "Knob", "UnknownKnobError", "registered", "lookup", "raw",
    "get_bool", "get_int", "get_float", "get_str",
    "render_readme_table",
]

_FALSY = ("0", "false", "no", "off")


class UnknownKnobError(KeyError):
    """A NOMAD_TPU_* name was read that is not declared in the registry
    — declare it in utils/knobs.py (with a doc line) before use."""


@dataclass(frozen=True)
class Knob:
    name: str
    kind: str          # "bool" | "int" | "float" | "str"
    default: object    # None ⇒ "unset" is meaningful to the caller
    doc: str
    # Shown in the README default column when the real default is
    # computed elsewhere (class attribute, sibling config field).
    default_label: Optional[str] = None

    def default_text(self) -> str:
        if self.default_label is not None:
            return self.default_label
        if self.default is None:
            return "unset"
        if self.kind == "bool":
            return "1" if self.default else "0"
        return str(self.default)


_REGISTRY: Dict[str, Knob] = {}


def _knob(name: str, kind: str, default, doc: str,
          default_label: Optional[str] = None) -> None:
    _REGISTRY[name] = Knob(name, kind, default, doc, default_label)


# ---------------------------------------------------------------------------
# The registry.  Grouped roughly by subsystem; insertion order is the
# README table order.
# ---------------------------------------------------------------------------

# -- device hot path --------------------------------------------------------
_knob("NOMAD_TPU_QUANT", "bool", True,
      "Quantized int8/int16 static resource rows (exact-or-absent "
      "round-trip, guarded)")
_knob("NOMAD_TPU_RNG_SEED", "int", None,
      "Pin the per-batch tie-break jitter seed for deterministic "
      "placement reproduction")
_knob("NOMAD_TPU_PREEMPTION", "bool", False,
      "Default for schedulers constructed without an explicit "
      "preemption flag")
_knob("NOMAD_TPU_NO_COMPILE_CACHE", "bool", False,
      "Disable the persistent XLA compilation cache")
_knob("NOMAD_TPU_PIPELINE", "bool", False,
      "Pipelined BatchWorker drain: prepare batch k+1 overlaps batch "
      "k's device pass")

# -- device-resident state --------------------------------------------------
_knob("NOMAD_TPU_RESIDENT", "bool", True,
      "Device-resident usage cache (delta scatter-adds instead of "
      "per-batch re-encode)")
_knob("NOMAD_TPU_RESIDENT_DEVICE", "bool", True,
      "Donated on-device usage mirror (single-chip and per-shard mesh "
      "twins); 0 keeps the sparse-delta upload")
_knob("NOMAD_TPU_RESIDENT_GUARD_EVERY", "int", 64,
      "Resident-mirror differential-guard cadence in hits (0 disables "
      "the guard)")
_knob("NOMAD_TPU_ALLOC_LOG_CAP", "int", 262144,
      "Usage-delta log bound in alloc rows; overflow forces consumers "
      "to full re-encode")

# -- TPU-path circuit breaker -----------------------------------------------
_knob("NOMAD_TPU_BREAKER_THRESHOLD", "float", 0.9,
      "Minimum kernel/oracle agreement ratio before the breaker opens")
_knob("NOMAD_TPU_BREAKER_WINDOW", "int", 64,
      "Sliding agreement window (checks)")
_knob("NOMAD_TPU_BREAKER_MIN_CHECKS", "int", 8,
      "Checks required in-window before the breaker may trip")
_knob("NOMAD_TPU_BREAKER_COOLDOWN", "float", 10.0,
      "Seconds open before a half-open probe")
_knob("NOMAD_TPU_BREAKER_DISABLE", "bool", False,
      "1 ⇒ the breaker never trips (forensics only — degradation "
      "routing stays off)")

# -- columnar store / codec / native twins ----------------------------------
_knob("NOMAD_TPU_COLUMNAR", "bool", True,
      "Columnar numpy mirrors of the node table + binary NTPUSNP2 "
      "snapshots; 0 restores the object walk and legacy blobs")
_knob("NOMAD_TPU_COLUMNAR_GUARD_EVERY", "int", 16,
      "Columnar-vs-walk differential-guard cadence in encodes (tests "
      "pin 1)")
_knob("NOMAD_TPU_CODEC", "bool", True,
      "Generated struct codec for RPC/raft/snapshots; 0 encodes "
      "msgpack (decode sniffs both forever)")
_knob("NOMAD_TPU_CODEC_GUARD_EVERY", "int", 512,
      "Native/python string-column twin bit-compare cadence (tests "
      "pin 1)")
_knob("NOMAD_TPU_DECODE_GUARD_EVERY", "int", 64,
      "Native packed-result-decode twin bit-compare cadence (tests "
      "pin 1)")
_knob("NOMAD_TPU_NO_NATIVE", "bool", False,
      "Force the pure-Python fallbacks for every native (C++) "
      "component")
_knob("NOMAD_TPU_NATIVE_CACHE", "str", None,
      "Content-addressed native .so build cache",
      default_label="~/.cache/nomad_tpu/native")
_knob("NOMAD_TPU_NATIVE_ASAN", "bool", False,
      "Build the native components with ASan+UBSan and run them under "
      "the sanitizer runtimes (selfcheck corpus leg)")

# -- control plane ----------------------------------------------------------
_knob("NOMAD_TPU_STALE_SNAPSHOT", "bool", True,
      "Workers reuse a cached snapshot when it covers the eval's "
      "trigger indexes + plan fence; 0 restores snapshot-per-eval")
_knob("NOMAD_TPU_STALE_SNAPSHOT_LAG", "int", 512,
      "Max raft entries a reused snapshot may lag the applied index")
_knob("NOMAD_TPU_PLAN_PIPELINE", "int", 8,
      "Concurrent in-flight plan commits (1 restores the strictly "
      "serial applier)")
_knob("NOMAD_TPU_BROKER_MAX_PENDING", "int", 0,
      "Eval-broker admission bound (0 = unbounded historical "
      "behavior); overflow 429-NACKs with Retry-After")
_knob("NOMAD_TPU_BROKER_COALESCE", "bool", True,
      "Per-job coalescing of deferred duplicate evals")
_knob("NOMAD_TPU_BROKER_BYPASS_PRIO", "int", None,
      "Priority at or above which admission control is bypassed",
      default_label="JOB_MAX_PRIORITY (100)")
_knob("NOMAD_TPU_FOLLOWER_SCHED", "bool", True,
      "Follower-read scheduling: FollowerWorkers on non-leader "
      "servers pull evals and forward plans")
_knob("NOMAD_TPU_REMOTE_NACK_PAUSE", "bool", False,
      "Follower workers pause/resume the broker nack deadline over "
      "the wire (short-deadline deployments)")
_knob("NOMAD_TPU_HEARTBEAT_JITTER", "float", 0.1,
      "Upward heartbeat-TTL jitter fraction (thundering-herd "
      "dispersal)")

# -- raft / WAL / snapshots -------------------------------------------------
_knob("NOMAD_TPU_RAFT_HEARTBEAT_S", "float", None,
      "Leader heartbeat interval override (loaded measurement "
      "clusters slow elections)",
      default_label="RaftNode.HEARTBEAT_INTERVAL")
_knob("NOMAD_TPU_RAFT_ELECTION_MIN_S", "float", None,
      "Election timeout lower bound override",
      default_label="RaftNode.ELECTION_TIMEOUT[0]")
_knob("NOMAD_TPU_RAFT_ELECTION_MAX_S", "float", None,
      "Election timeout upper bound override",
      default_label="RaftNode.ELECTION_TIMEOUT[1]")
_knob("NOMAD_TPU_FILELOG_SNAPSHOT_ENTRIES", "int", 8192,
      "Auto-snapshot threshold: WAL entries since the last snapshot "
      "(0 disables)")
_knob("NOMAD_TPU_FILELOG_SNAPSHOT_BYTES", "int", 64 << 20,
      "Auto-snapshot threshold: WAL bytes since the last snapshot")
_knob("NOMAD_TPU_FILELOG_SNAPSHOT_INTERVAL", "float", 1.0,
      "Auto-snapshot watcher poll interval (seconds)")
_knob("NOMAD_TPU_SNAPSHOT_CHUNK", "int", 4 << 20,
      "InstallSnapshot streaming chunk size in bytes")

# -- observability / events / chaos -----------------------------------------
_knob("NOMAD_TPU_TRACE", "bool", False,
      "Arm the eval-lifecycle tracing plane at server construction")
_knob("NOMAD_TPU_EVENTS", "bool", False,
      "Arm the cluster event stream at server construction (also "
      "armed lazily by the first subscriber)")
_knob("NOMAD_TPU_EVENTS_RING", "int", 4096,
      "Event-stream ring buffer size")
_knob("NOMAD_TPU_CHAOS", "bool", False,
      "Register the Chaos.* control RPC endpoints (never on a "
      "production wire surface)")
_knob("NOMAD_TPU_CHAOS_NET", "str", "",
      "JSON net-chaos spec armed at server construction "
      "(partitions/rules/seed)")
_knob("NOMAD_TPU_LOCKCHECK", "bool", False,
      "Arm the runtime lock-order sanitizer (utils/lockcheck.py): "
      "instrumented locks record acquisition order, teardown asserts "
      "acyclicity and prints the witness cycle")
_knob("NOMAD_TPU_CONTPROF", "bool", False,
      "Arm the continuous host-attribution profiler "
      "(utils/contprof.py) at server construction: a low-Hz sampler "
      "classifies every thread's stack into subsystem CPU-share "
      "gauges (nomad.cpu.<subsystem>)")
_knob("NOMAD_TPU_CONTPROF_HZ", "float", 10.0,
      "Continuous-profiler sampling rate in Hz (clamped to 1-100)")
_knob("NOMAD_TPU_CONTPROF_RING", "int", 120,
      "Continuous-profiler ring: how many 5s aggregation windows are "
      "retained for the /v1/profile/continuous surface")
_knob("NOMAD_TPU_CONTPROF_GIL_MS", "float", 5.0,
      "GIL-pressure probe requested sleep in milliseconds (the probe "
      "measures scheduling-delay jitter against it; 0 disables the "
      "probe thread)")
_knob("NOMAD_TPU_BLACKBOX", "bool", False,
      "Arm the incident flight recorder (utils/blackbox.py) at "
      "server construction: breaker opens, auditor violations, lock "
      "cycles and plan-apply SLO breaches capture a JSON bundle")
_knob("NOMAD_TPU_BLACKBOX_DIR", "str", None,
      "Flight-recorder bundle directory",
      default_label="<tmpdir>/nomad_tpu_blackbox")
_knob("NOMAD_TPU_BLACKBOX_MIN_INTERVAL_S", "float", 30.0,
      "Flight recorder: minimum seconds between two auto-captures "
      "for the same trigger reason (dedup/rate limit)")
_knob("NOMAD_TPU_BLACKBOX_MAX_BUNDLES", "int", 32,
      "Flight recorder: hard cap on auto-captured bundles per "
      "process (operator-forced captures are exempt)")
_knob("NOMAD_TPU_BLACKBOX_SLO_PLAN_P99_MS", "float", 0.0,
      "Plan-apply p99 SLO in milliseconds watched by the metrics "
      "emitter; a breach auto-captures a flight-recorder bundle "
      "(0 disables the watch)")

# -- multi-tenant serving plane ---------------------------------------------
_knob("NOMAD_TPU_TENANCY_OBJECTIVE", "str", "drf",
      "Cluster-wide default fair-dequeue objective "
      "(drf | weighted-rr | fifo); a Namespace row's objective field "
      "overrides per tenant")
_knob("NOMAD_TPU_TENANCY_METRICS_TOP", "int", 10,
      "How many busiest tenants get per-tenant tenant.* gauges each "
      "metrics tick (0 disables)")

# -- region federation ------------------------------------------------------
_knob("NOMAD_TPU_REGION_DIAL_ROUNDS", "int", 2,
      "Cross-region forwarding: how many full passes over the target "
      "region's known servers before giving up with NoPathToRegion")
_knob("NOMAD_TPU_REGION_RETRY_AFTER_CAP", "float", 5.0,
      "Cap on the retry_after hint carried by NoPathToRegion (seconds)")
_knob("NOMAD_TPU_REGION_PROBE_TIMEOUT", "float", 1.0,
      "Timeout for best-effort cross-region leader probes in the "
      "/v1/regions detail surface (seconds)")

# -- loadgen ----------------------------------------------------------------
_knob("NOMAD_TPU_SWITCH_INTERVAL", "float", None,
      "sys.setswitchinterval override applied for loadgen "
      "measurement runs")
_knob("NOMAD_TPU_LG_PROFILE", "bool", False,
      "Start the sampling profiler in loadgen follower children")


# ---------------------------------------------------------------------------
# accessors
# ---------------------------------------------------------------------------

_UNSET = object()


def lookup(name: str) -> Knob:
    knob = _REGISTRY.get(name)
    if knob is None:
        raise UnknownKnobError(
            f"{name} is not declared in nomad_tpu/utils/knobs.py — "
            f"register it (with a doc line) before reading it")
    return knob


def registered() -> Iterator[Knob]:
    """All knobs in declaration (= README table) order."""
    return iter(_REGISTRY.values())


def raw(name: str) -> Optional[str]:
    """Verbatim env value (or None) for save/restore around drills and
    bench phases.  Registry-checked like every other accessor."""
    lookup(name)
    return os.environ.get(name)


def _resolve_default(name: str, default):
    if default is _UNSET:
        return lookup(name).default
    lookup(name)
    return default


def get_bool(name: str, default=_UNSET) -> bool:
    dflt = _resolve_default(name, default)
    val = os.environ.get(name)
    if val is None:
        return bool(dflt)
    val = val.strip().lower()
    if val == "":
        return bool(dflt)
    return val not in _FALSY


_WARNED_MALFORMED: set = set()


def _warn_malformed(name: str, val: str, kind: str, dflt) -> None:
    if name in _WARNED_MALFORMED:
        return
    _WARNED_MALFORMED.add(name)
    import sys

    print(f"nomad_tpu: malformed {kind} knob {name}={val!r} — "
          f"using default {dflt!r}", file=sys.stderr)


def get_int(name: str, default=_UNSET) -> Optional[int]:
    dflt = _resolve_default(name, default)
    val = os.environ.get(name)
    if val is None or not val.strip():
        return dflt
    try:
        return int(val)
    except ValueError:
        _warn_malformed(name, val, "int", dflt)
        return dflt


def get_float(name: str, default=_UNSET) -> Optional[float]:
    dflt = _resolve_default(name, default)
    val = os.environ.get(name)
    if val is None or not val.strip():
        return dflt
    try:
        return float(val)
    except ValueError:
        _warn_malformed(name, val, "float", dflt)
        return dflt


def get_str(name: str, default=_UNSET) -> Optional[str]:
    dflt = _resolve_default(name, default)
    val = os.environ.get(name)
    if val is None:
        return dflt
    return val


# ---------------------------------------------------------------------------
# README table generation
# ---------------------------------------------------------------------------

TABLE_BEGIN = "<!-- knob-table:begin (generated by python -m nomad_tpu.analysis --write-knob-table) -->"
TABLE_END = "<!-- knob-table:end -->"


def render_readme_table() -> str:
    """The README env-knob table, generated so it cannot drift.  The
    analysis pass asserts the README section between the markers equals
    this rendering byte-for-byte."""
    lines = [
        TABLE_BEGIN,
        "",
        "| Knob | Type | Default | Meaning |",
        "|---|---|---|---|",
    ]
    for knob in registered():
        lines.append(
            f"| `{knob.name}` | {knob.kind} | `{knob.default_text()}` "
            f"| {knob.doc} |")
    lines.append("")
    lines.append(TABLE_END)
    return "\n".join(lines)
