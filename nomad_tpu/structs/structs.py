"""L0 data model: the host-side dataclasses AND the device-side tensor schema
contract for the TPU batch scheduler.

Behavioral parity with the reference data model (nomad/structs/structs.go:
Node:756, Job:1189, TaskGroup:2130, Task:2616, Allocation:3820,
Evaluation:4244, Plan:4477, PlanResult:4581), re-designed as Python
dataclasses.  Resource quantities are deliberately 4 scalar ints
(cpu, memory_mb, disk_mb, iops) so they lower directly to int32 SoA tensors
``node_res[N,4]`` / ``tg_ask[B,4]`` in nomad_tpu/ops/encode.py.
"""
from __future__ import annotations

import copy as _copylib
import dataclasses
import os as _os
import struct as _struct
import threading as _threading
import time
import uuid
from collections.abc import Mapping
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

# ---------------------------------------------------------------------------
# Constants (reference: nomad/structs/structs.go)
# ---------------------------------------------------------------------------

# Job types (structs.go:1160-1166)
JOB_TYPE_SERVICE = "service"
JOB_TYPE_BATCH = "batch"
JOB_TYPE_SYSTEM = "system"
JOB_TYPE_CORE = "_core"

# Job statuses (structs.go:1168-1177)
JOB_STATUS_PENDING = "pending"
JOB_STATUS_RUNNING = "running"
JOB_STATUS_DEAD = "dead"

JOB_MIN_PRIORITY = 1
JOB_DEFAULT_PRIORITY = 50
JOB_MAX_PRIORITY = 100

# Core job IDs used by the internal GC scheduler (structs.go / core_sched.go)
CORE_JOB_EVAL_GC = "eval-gc"
CORE_JOB_NODE_GC = "node-gc"
CORE_JOB_JOB_GC = "job-gc"
CORE_JOB_FORCE_GC = "force-gc"

# Node statuses (structs.go:698-707)
NODE_STATUS_INIT = "initializing"
NODE_STATUS_READY = "ready"
NODE_STATUS_DOWN = "down"

# Allocation desired statuses (structs.go:3806-3808)
ALLOC_DESIRED_STATUS_RUN = "run"
ALLOC_DESIRED_STATUS_STOP = "stop"
ALLOC_DESIRED_STATUS_EVICT = "evict"

# Allocation client statuses (structs.go:3812-3816)
ALLOC_CLIENT_STATUS_PENDING = "pending"
ALLOC_CLIENT_STATUS_RUNNING = "running"
ALLOC_CLIENT_STATUS_COMPLETE = "complete"
ALLOC_CLIENT_STATUS_FAILED = "failed"
ALLOC_CLIENT_STATUS_LOST = "lost"

# Evaluation statuses (structs.go:4230-4242)
EVAL_STATUS_BLOCKED = "blocked"
EVAL_STATUS_PENDING = "pending"
EVAL_STATUS_COMPLETE = "complete"
EVAL_STATUS_FAILED = "failed"
EVAL_STATUS_CANCELLED = "canceled"

# Evaluation trigger reasons (structs.go:4218-4228)
EVAL_TRIGGER_JOB_REGISTER = "job-register"
EVAL_TRIGGER_JOB_DEREGISTER = "job-deregister"
EVAL_TRIGGER_PERIODIC_JOB = "periodic-job"
EVAL_TRIGGER_NODE_UPDATE = "node-update"
EVAL_TRIGGER_SCHEDULED = "scheduled"
EVAL_TRIGGER_ROLLING_UPDATE = "rolling-update"
EVAL_TRIGGER_MAX_PLANS = "max-plan-attempts"
EVAL_TRIGGER_PREEMPTION = "preemption"

ALLOC_PREEMPTED = "preempted by a higher-priority allocation"

# Constraint operands (structs.go:3286-3294)
CONSTRAINT_DISTINCT_PROPERTY = "distinct_property"
CONSTRAINT_DISTINCT_HOSTS = "distinct_hosts"
CONSTRAINT_REGEX = "regexp"
CONSTRAINT_VERSION = "version"
CONSTRAINT_SET_CONTAINS = "set_contains"

# Task states (structs.go:2900-2910)
TASK_STATE_PENDING = "pending"
TASK_STATE_RUNNING = "running"
TASK_STATE_DEAD = "dead"

# Default resource values (structs.go:918-935 DefaultResources)
DEFAULT_RESOURCES_CPU = 100
DEFAULT_RESOURCES_MEMORY_MB = 10
DEFAULT_RESOURCES_DISK_MB = 300
DEFAULT_RESOURCES_IOPS = 0

# Periodic spec types (structs.go:1718-1724)
PERIODIC_SPEC_CRON = "cron"
PERIODIC_SPEC_TEST = "_internal_test"

# Restart policy modes (structs.go:1956-1963)
RESTART_POLICY_MODE_DELAY = "delay"
RESTART_POLICY_MODE_FAIL = "fail"


# Buffered entropy for generate_uuid: one urandom syscall per 64 ids.
# The control plane mints several ids per eval (eval id, dequeue token,
# alloc ids, follow-up evals), and at load-harness saturation the
# per-call urandom syscall showed up in the profile.  Cleared in forked
# children so two processes can never slice the same pool.
_uuid_hex_pool = ""
_uuid_pool_lock = _threading.Lock()
if hasattr(_os, "register_at_fork"):
    def _clear_uuid_pool() -> None:
        global _uuid_hex_pool
        _uuid_hex_pool = ""
    _os.register_at_fork(after_in_child=_clear_uuid_pool)


def generate_uuid() -> str:
    """Random UUID for IDs (reference: nomad/structs/funcs.go:158).

    Buffered os.urandom + slicing: ~5x faster than uuid.uuid4() on the
    bulk-alloc hot path, same 8-4-4-4-12 format, OS-quality entropy."""
    global _uuid_hex_pool
    with _uuid_pool_lock:
        pool = _uuid_hex_pool
        if len(pool) < 32:
            pool = _os.urandom(1024).hex()
        h, _uuid_hex_pool = pool[:32], pool[32:]
    return f"{h[:8]}-{h[8:12]}-{h[12:16]}-{h[16:20]}-{h[20:]}"


_native_uuids = None  # resolved in the background; False = unavailable
_native_uuids_resolving = False


def _resolve_native_uuids() -> None:
    global _native_uuids
    try:
        from ..native import generate_uuids as _ng

        _ng(1)  # force build/load; may raise NativeUnavailable
        _native_uuids = _ng
    except Exception:
        _native_uuids = False


def generate_uuids(n: int) -> List[str]:
    """Bulk UUIDs for the bulk-placement hot path: native formatter
    (nomad_tpu/native/ids.cc, ~2.3x end to end) once available, else one
    urandom read + python hex slicing.  The native build/load runs in a
    BACKGROUND thread kicked off by the first bulk call — a cold cache
    means a g++ invocation, which must not stall plan materialization."""
    global _native_uuids_resolving
    if _native_uuids is None and n >= 64 and not _native_uuids_resolving:
        _native_uuids_resolving = True
        import threading as _threading

        _threading.Thread(target=_resolve_native_uuids,
                          name="native-uuids-build", daemon=True).start()
    if _native_uuids and n >= 64:
        return _native_uuids(n)
    hx = _os.urandom(16 * n).hex()
    return [
        f"{h[:8]}-{h[8:12]}-{h[12:16]}-{h[16:20]}-{h[20:]}"
        for h in (hx[32 * i:32 * i + 32] for i in range(n))
    ]


# ---------------------------------------------------------------------------
# Resources
# ---------------------------------------------------------------------------


def _fast_copy(obj):
    """Shallow field copy (== dataclasses.replace with no changes — none of
    these dataclasses define __post_init__) without re-running __init__ or
    copy.copy's __reduce_ex__ dispatch."""
    cls = obj.__class__
    new = cls.__new__(cls)
    new.__dict__.update(obj.__dict__)
    return new


def alloc_usage_vec(alloc) -> "Tuple[int, int, int, int]":
    """The CANONICAL per-alloc usage basis, (cpu, memory_mb, disk_mb,
    iops): combined ``resources`` when present, ``shared_resources`` +
    per-task resources otherwise.  The state store's usage-delta feed
    and the device-resident mirror (ops/resident.py) both use this
    function; ops/encode.apply_alloc_usage is its numpy twin and the
    resident differential guard pins their equality bit-for-bit — any
    change here must land there too."""
    r = alloc.resources
    if r is not None:
        return (r.cpu, r.memory_mb, r.disk_mb, r.iops)
    cpu = mem = disk = iops = 0
    sr = alloc.shared_resources
    if sr is not None:
        cpu, mem, disk, iops = sr.cpu, sr.memory_mb, sr.disk_mb, sr.iops
    for tr in alloc.task_resources.values():
        cpu += tr.cpu
        mem += tr.memory_mb
        disk += tr.disk_mb
        iops += tr.iops
    return (cpu, mem, disk, iops)


def alloc_net_vec(alloc) -> "Tuple[int, int]":
    """What an alloc's networks hold on its node, (Mbit, ports in the
    dynamic range), over the first network of each task: the basis of
    ops/encode.apply_alloc_usage's network accounting.  The state
    store's usage-delta feed logs it beside ``alloc_usage_vec`` and the
    resident network mirror (ops/resident.py) folds it."""
    return alloc_net_held(alloc)[0]


def alloc_net_held(alloc) -> "Tuple[Tuple[int, int], Tuple[int, ...]]":
    """``alloc_net_vec`` and, from the same pass, the values of every
    port the alloc's networks hold (first network of each task, reserved
    and dynamic, 0 left out): what the resident mirror's port columns
    count, one holder per value."""
    from .network import MAX_DYNAMIC_PORT, MIN_DYNAMIC_PORT

    mbits = in_dyn = 0
    ports = []
    for tr in alloc.task_resources.values():
        if tr.networks:
            nr = tr.networks[0]
            mbits += nr.mbits
            for p in nr.reserved_ports + nr.dynamic_ports:
                if p.value:
                    ports.append(p.value)
                    if MIN_DYNAMIC_PORT <= p.value < MAX_DYNAMIC_PORT:
                        in_dyn += 1
    return (mbits, in_dyn), tuple(ports)


@dataclass
class Port:
    label: str = ""
    value: int = 0


@dataclass
class NetworkResource:
    """A network interface / bandwidth+port ask (structs.go:1071-1158)."""

    device: str = ""
    cidr: str = ""
    ip: str = ""
    mbits: int = 0
    reserved_ports: List[Port] = field(default_factory=list)
    dynamic_ports: List[Port] = field(default_factory=list)

    def copy(self) -> "NetworkResource":
        return NetworkResource(
            device=self.device,
            cidr=self.cidr,
            ip=self.ip,
            mbits=self.mbits,
            reserved_ports=[Port(p.label, p.value) for p in self.reserved_ports],
            dynamic_ports=[Port(p.label, p.value) for p in self.dynamic_ports],
        )

    def add(self, delta: "NetworkResource") -> None:
        self.reserved_ports.extend(Port(p.label, p.value) for p in delta.reserved_ports)
        self.mbits += delta.mbits

    def port_labels(self) -> Dict[str, int]:
        labels: Dict[str, int] = {}
        for p in self.reserved_ports:
            labels[p.label] = p.value
        for p in self.dynamic_ports:
            labels[p.label] = p.value
        return labels


@dataclass
class Resources:
    """Resource ask/capacity.  The 4 scalar dims are the tensor schema:
    column order (cpu, memory_mb, disk_mb, iops) is shared with
    ops/encode.py (reference: structs.go:900-1069)."""

    cpu: int = 0
    memory_mb: int = 0
    disk_mb: int = 0
    iops: int = 0
    networks: List[NetworkResource] = field(default_factory=list)

    # Tensor column order contract.
    TENSOR_DIMS = ("cpu", "memory_mb", "disk_mb", "iops")

    def copy(self) -> "Resources":
        return Resources(
            cpu=self.cpu,
            memory_mb=self.memory_mb,
            disk_mb=self.disk_mb,
            iops=self.iops,
            networks=[n.copy() for n in self.networks],
        )

    def net_index(self, n: NetworkResource) -> int:
        """Index of the first network with the same device — including the
        empty device, so device-less asks merge (structs.go:1012)."""
        for idx, existing in enumerate(self.networks):
            if existing.device == n.device:
                return idx
        return -1

    def superset(self, other: "Resources") -> tuple[bool, str]:
        """Whether self >= other on every scalar dimension; returns the
        exhausted dimension name otherwise (structs.go:1024-1040)."""
        if self.cpu < other.cpu:
            return False, "cpu exhausted"
        if self.memory_mb < other.memory_mb:
            return False, "memory exhausted"
        if self.disk_mb < other.disk_mb:
            return False, "disk exhausted"
        if self.iops < other.iops:
            return False, "iops exhausted"
        return True, ""

    def add(self, delta: Optional["Resources"]) -> None:
        """Accumulate delta, merging networks by device (structs.go:1042)."""
        if delta is None:
            return
        self.cpu += delta.cpu
        self.memory_mb += delta.memory_mb
        self.disk_mb += delta.disk_mb
        self.iops += delta.iops
        for n in delta.networks:
            idx = self.net_index(n)
            if idx == -1:
                self.networks.append(n.copy())
            else:
                self.networks[idx].add(n)

    def as_tuple(self) -> tuple[int, int, int, int]:
        return (self.cpu, self.memory_mb, self.disk_mb, self.iops)


# ---------------------------------------------------------------------------
# Node
# ---------------------------------------------------------------------------


@dataclass
class Node:
    """A fingerprinted client machine (structs.go:756-898)."""

    id: str = ""
    datacenter: str = "dc1"
    name: str = ""
    http_addr: str = ""
    attributes: Dict[str, str] = field(default_factory=dict)
    resources: Resources = field(default_factory=Resources)
    reserved: Optional[Resources] = None
    links: Dict[str, str] = field(default_factory=dict)
    meta: Dict[str, str] = field(default_factory=dict)
    node_class: str = ""
    computed_class: str = ""
    drain: bool = False
    status: str = NODE_STATUS_INIT
    status_description: str = ""
    status_updated_at: float = 0.0
    create_index: int = 0
    modify_index: int = 0

    def terminal_status(self) -> bool:
        """Whether the node is down — allocs on it are lost (structs.go:888)."""
        return self.status == NODE_STATUS_DOWN

    def ready(self) -> bool:
        return self.status == NODE_STATUS_READY and not self.drain

    def compute_class(self) -> None:
        from .node_class import compute_node_class

        self.computed_class = compute_node_class(self)

    def copy(self) -> "Node":
        n = _fast_copy(self)
        n.attributes = dict(self.attributes)
        n.meta = dict(self.meta)
        n.links = dict(self.links)
        n.resources = self.resources.copy()
        n.reserved = self.reserved.copy() if self.reserved else None
        return n

    def stat_values(self) -> Dict[str, str]:
        return {"id": self.id, "datacenter": self.datacenter, "name": self.name,
                "class": self.node_class, "drain": str(self.drain), "status": self.status}


# ---------------------------------------------------------------------------
# Job / TaskGroup / Task
# ---------------------------------------------------------------------------


@dataclass
class Constraint:
    """A scheduling constraint (structs.go:3296-3349)."""

    ltarget: str = ""
    rtarget: str = ""
    operand: str = "="

    def copy(self) -> "Constraint":
        return Constraint(self.ltarget, self.rtarget, self.operand)

    def __str__(self) -> str:
        return f"{self.ltarget} {self.operand} {self.rtarget}"


@dataclass
class RestartPolicy:
    """Task restart behavior within a task group (structs.go:1965-2012)."""

    attempts: int = 2
    interval: float = 60.0  # seconds (reference uses ns durations)
    delay: float = 15.0
    mode: str = RESTART_POLICY_MODE_DELAY

    def copy(self) -> "RestartPolicy":
        return _fast_copy(self)


@dataclass
class EphemeralDisk:
    """Shared task-group disk ask (structs.go:3357-3409)."""

    sticky: bool = False
    size_mb: int = 300
    migrate: bool = False

    def copy(self) -> "EphemeralDisk":
        return _fast_copy(self)


@dataclass
class UpdateStrategy:
    """Rolling-update policy (structs.go:1702-1716)."""

    stagger: float = 0.0  # seconds between rolling batches
    max_parallel: int = 0

    def rolling(self) -> bool:
        return self.stagger > 0 and self.max_parallel > 0

    def copy(self) -> "UpdateStrategy":
        return _fast_copy(self)


@dataclass
class PeriodicConfig:
    """Cron-style periodic launch config (structs.go:1726-1810)."""

    enabled: bool = False
    spec: str = ""
    spec_type: str = PERIODIC_SPEC_CRON
    prohibit_overlap: bool = False

    def copy(self) -> "PeriodicConfig":
        return _fast_copy(self)

    def next(self, from_time: float) -> float:
        """Next launch time strictly after from_time, or 0 if none."""
        if self.spec_type == PERIODIC_SPEC_CRON:
            from ..utils.cron import cron_next

            return cron_next(self.spec, from_time)
        if self.spec_type == PERIODIC_SPEC_TEST:
            # test spec: comma-separated unix timestamps; return the first
            # one after from_time (structs.go PeriodicConfig.Next test path)
            for part in self.spec.split(","):
                part = part.strip()
                if not part:
                    continue
                t = float(part)
                if t > from_time:
                    return t
            return 0.0
        return 0.0


@dataclass
class ParameterizedJobConfig:
    """Dispatchable-job config (structs.go:1860+ in later refs; minimal here)."""

    payload: str = ""
    meta_required: List[str] = field(default_factory=list)
    meta_optional: List[str] = field(default_factory=list)

    def copy(self) -> "ParameterizedJobConfig":
        return ParameterizedJobConfig(self.payload, list(self.meta_required), list(self.meta_optional))


@dataclass
class LogConfig:
    """Task log rotation config (structs.go:2540-2576)."""

    max_files: int = 10
    max_file_size_mb: int = 10

    def copy(self) -> "LogConfig":
        return _fast_copy(self)


@dataclass
class ServiceCheck:
    """Health check for a registered service (structs.go:2250-2360)."""

    name: str = ""
    type: str = ""  # http | tcp | script
    command: str = ""
    args: List[str] = field(default_factory=list)
    path: str = ""
    protocol: str = ""
    port_label: str = ""
    interval: float = 10.0
    timeout: float = 3.0
    initial_status: str = ""

    def copy(self) -> "ServiceCheck":
        c = _fast_copy(self)
        c.args = list(self.args)
        return c


@dataclass
class Service:
    """A service advertised by a task (structs.go:2362-2470)."""

    name: str = ""
    port_label: str = ""
    tags: List[str] = field(default_factory=list)
    checks: List[ServiceCheck] = field(default_factory=list)

    def copy(self) -> "Service":
        return Service(self.name, self.port_label, list(self.tags),
                       [c.copy() for c in self.checks])


@dataclass
class TaskArtifact:
    """Remote artifact to fetch before task start (structs.go:3196-3280)."""

    getter_source: str = ""
    getter_options: Dict[str, str] = field(default_factory=dict)
    relative_dest: str = ""

    def copy(self) -> "TaskArtifact":
        return TaskArtifact(self.getter_source, dict(self.getter_options), self.relative_dest)


TEMPLATE_CHANGE_MODE_NOOP = "noop"
TEMPLATE_CHANGE_MODE_SIGNAL = "signal"
TEMPLATE_CHANGE_MODE_RESTART = "restart"


@dataclass
class Template:
    """Rendered template block (structs.go:2914-3020)."""

    source_path: str = ""
    dest_path: str = ""
    embedded_tmpl: str = ""
    change_mode: str = "restart"  # noop | signal | restart
    change_signal: str = ""
    splay: float = 5.0
    perms: str = "0644"

    def copy(self) -> "Template":
        return _fast_copy(self)


@dataclass
class Vault:
    """Vault policy ask for a task (structs.go:4120-4180 region)."""

    policies: List[str] = field(default_factory=list)
    env: bool = True
    change_mode: str = "restart"
    change_signal: str = ""

    def copy(self) -> "Vault":
        v = _fast_copy(self)
        v.policies = list(self.policies)
        return v


@dataclass
class DispatchPayloadConfig:
    file: str = ""

    def copy(self) -> "DispatchPayloadConfig":
        return _fast_copy(self)


@dataclass
class Task:
    """A unit of work executed by a driver (structs.go:2616-2790)."""

    name: str = ""
    driver: str = ""
    user: str = ""
    config: Dict[str, Any] = field(default_factory=dict)
    env: Dict[str, str] = field(default_factory=dict)
    services: List[Service] = field(default_factory=list)
    vault: Optional[Vault] = None
    templates: List[Template] = field(default_factory=list)
    constraints: List[Constraint] = field(default_factory=list)
    resources: Resources = field(default_factory=Resources)
    dispatch_payload: Optional[DispatchPayloadConfig] = None
    meta: Dict[str, str] = field(default_factory=dict)
    kill_timeout: float = 5.0
    log_config: LogConfig = field(default_factory=LogConfig)
    artifacts: List[TaskArtifact] = field(default_factory=list)
    leader: bool = False

    def copy(self) -> "Task":
        return Task(
            name=self.name,
            driver=self.driver,
            user=self.user,
            config=dict(self.config),
            env=dict(self.env),
            services=[s.copy() for s in self.services],
            vault=self.vault.copy() if self.vault else None,
            templates=[t.copy() for t in self.templates],
            constraints=[c.copy() for c in self.constraints],
            resources=self.resources.copy(),
            dispatch_payload=self.dispatch_payload.copy() if self.dispatch_payload else None,
            meta=dict(self.meta),
            kill_timeout=self.kill_timeout,
            log_config=self.log_config.copy(),
            artifacts=[a.copy() for a in self.artifacts],
            leader=self.leader,
        )


@dataclass
class TaskGroup:
    """A colocated set of tasks; the scheduler's placement unit
    (structs.go:2130-2248)."""

    name: str = ""
    count: int = 1
    constraints: List[Constraint] = field(default_factory=list)
    restart_policy: RestartPolicy = field(default_factory=RestartPolicy)
    tasks: List[Task] = field(default_factory=list)
    ephemeral_disk: EphemeralDisk = field(default_factory=EphemeralDisk)
    meta: Dict[str, str] = field(default_factory=dict)

    def copy(self) -> "TaskGroup":
        return TaskGroup(
            name=self.name,
            count=self.count,
            constraints=[c.copy() for c in self.constraints],
            restart_policy=self.restart_policy.copy(),
            tasks=[t.copy() for t in self.tasks],
            ephemeral_disk=self.ephemeral_disk.copy(),
            meta=dict(self.meta),
        )

    def lookup_task(self, name: str) -> Optional[Task]:
        for t in self.tasks:
            if t.name == name:
                return t
        return None


@dataclass
class Job:
    """A declarative workload specification (structs.go:1189-1560)."""

    region: str = "global"
    namespace: str = "default"
    id: str = ""
    parent_id: str = ""
    name: str = ""
    type: str = JOB_TYPE_SERVICE
    priority: int = JOB_DEFAULT_PRIORITY
    all_at_once: bool = False
    datacenters: List[str] = field(default_factory=list)
    constraints: List[Constraint] = field(default_factory=list)
    task_groups: List[TaskGroup] = field(default_factory=list)
    update: UpdateStrategy = field(default_factory=UpdateStrategy)
    periodic: Optional[PeriodicConfig] = None
    parameterized_job: Optional[ParameterizedJobConfig] = None
    payload: bytes = b""
    meta: Dict[str, str] = field(default_factory=dict)
    vault_token: str = ""
    status: str = JOB_STATUS_PENDING
    status_description: str = ""
    stop: bool = False
    stable: bool = False
    version: int = 0
    submit_time: float = 0.0
    create_index: int = 0
    modify_index: int = 0
    job_modify_index: int = 0

    def copy(self) -> "Job":
        j = _fast_copy(self)
        j.datacenters = list(self.datacenters)
        j.constraints = [c.copy() for c in self.constraints]
        j.task_groups = [tg.copy() for tg in self.task_groups]
        j.update = self.update.copy()
        j.periodic = self.periodic.copy() if self.periodic else None
        j.parameterized_job = self.parameterized_job.copy() if self.parameterized_job else None
        j.meta = dict(self.meta)
        return j

    def stopped(self) -> bool:
        return self.stop

    def is_periodic(self) -> bool:
        return self.periodic is not None and self.periodic.enabled

    def is_parameterized(self) -> bool:
        return self.parameterized_job is not None

    def lookup_task_group(self, name: str) -> Optional[TaskGroup]:
        for tg in self.task_groups:
            if tg.name == name:
                return tg
        return None

    def required_signals(self) -> Dict[str, Dict[str, List[str]]]:
        signals: Dict[str, Dict[str, List[str]]] = {}
        for tg in self.task_groups:
            for task in tg.tasks:
                sigs: List[str] = []
                if task.vault and task.vault.change_mode == "signal":
                    sigs.append(task.vault.change_signal)
                for tmpl in task.templates:
                    if tmpl.change_mode == "signal":
                        sigs.append(tmpl.change_signal)
                if sigs:
                    signals.setdefault(tg.name, {})[task.name] = sigs
        return signals

    def validate(self) -> List[str]:
        """Structural validation; returns a list of problems
        (reference behavior: structs.go:1334 Job.Validate)."""
        problems: List[str] = []
        if not self.region:
            problems.append("job region is empty")
        if not self.id:
            problems.append("job ID is empty")
        if not self.name:
            problems.append("job name is empty")
        if self.type not in (JOB_TYPE_SERVICE, JOB_TYPE_BATCH, JOB_TYPE_SYSTEM):
            problems.append(f"job type '{self.type}' is invalid")
        if not (JOB_MIN_PRIORITY <= self.priority <= JOB_MAX_PRIORITY):
            problems.append(
                f"job priority must be between [{JOB_MIN_PRIORITY}, {JOB_MAX_PRIORITY}]")
        if not self.datacenters:
            problems.append("job must specify at least one datacenter")
        if not self.task_groups:
            problems.append("job must have at least one task group")
        seen: Dict[str, int] = {}
        for tg in self.task_groups:
            if not tg.name:
                problems.append("task group name is empty")
            if tg.name in seen:
                problems.append(f"task group '{tg.name}' defined more than once")
            seen[tg.name] = 1
            if tg.count < 0:
                problems.append(f"task group '{tg.name}' has negative count")
            if self.type == JOB_TYPE_SYSTEM and tg.count not in (0, 1):
                problems.append(
                    f"system job task group '{tg.name}' should have count 1, not {tg.count}")
            if not tg.tasks:
                problems.append(f"task group '{tg.name}' has no tasks")
            tseen: Dict[str, int] = {}
            for task in tg.tasks:
                if not task.name:
                    problems.append(f"task name empty in group '{tg.name}'")
                if task.name in tseen:
                    problems.append(f"task '{task.name}' defined more than once")
                tseen[task.name] = 1
                if not task.driver:
                    problems.append(f"task '{task.name}' must specify a driver")
        if self.type == JOB_TYPE_SYSTEM and self.periodic and self.periodic.enabled:
            problems.append("periodic is not allowed on system jobs")
        for c in self.constraints:
            if c.operand in (CONSTRAINT_DISTINCT_HOSTS, CONSTRAINT_DISTINCT_PROPERTY):
                pass
            elif not c.operand:
                problems.append(f"constraint missing operand: {c}")
        return problems

    def canonicalize(self) -> None:
        """Fill defaults (reference behavior: structs.go:1286 Job.Canonicalize)."""
        if not self.name:
            self.name = self.id
        if not self.region:
            self.region = "global"
        if not self.namespace:
            self.namespace = DEFAULT_NAMESPACE
        if not self.datacenters:
            self.datacenters = ["dc1"]
        for tg in self.task_groups:
            if tg.count == 0 and self.type != JOB_TYPE_SYSTEM:
                tg.count = 1


# ---------------------------------------------------------------------------
# Task events / states
# ---------------------------------------------------------------------------

TASK_SETUP_FAILURE = "Setup Failure"
TASK_DRIVER_FAILURE = "Driver Failure"
TASK_RECEIVED = "Received"
TASK_FAILED_VALIDATION = "Failed Validation"
TASK_STARTED = "Started"
TASK_TERMINATED = "Terminated"
TASK_KILLING = "Killing"
TASK_KILLED = "Killed"
TASK_RESTARTING = "Restarting"
TASK_NOT_RESTARTING = "Not Restarting"
TASK_DOWNLOADING_ARTIFACTS = "Downloading Artifacts"
TASK_ARTIFACT_DOWNLOAD_FAILED = "Failed Artifact Download"
TASK_SIGNALING = "Signaling"
TASK_RESTART_SIGNAL = "Restart Signaled"
TASK_SIBLING_FAILED = "Sibling task failed"


@dataclass
class TaskEvent:
    """An event in a task's lifecycle (structs.go:3030-3190)."""

    type: str = ""
    time: float = 0.0
    message: str = ""
    driver_error: str = ""
    exit_code: int = 0
    signal: int = 0
    kill_timeout: float = 0.0
    restart_reason: str = ""
    failed_sibling: str = ""
    # Marks the event as failing the task (structs.go TaskEvent.FailsTask);
    # alloc_runner folds it into TaskState.failed.
    failed: bool = False
    # Delay before a restart is attempted (structs.go TaskEvent.StartDelay).
    start_delay: float = 0.0

    def copy(self) -> "TaskEvent":
        return _fast_copy(self)

    def display_message(self) -> str:
        """Human-readable one-liner for CLI/alloc-status (the reference CLI
        formats events per type in command/alloc_status.go)."""
        if self.message:
            return self.message
        if self.type == TASK_TERMINATED:
            return f"Exit Code: {self.exit_code}"
        if self.type == TASK_DRIVER_FAILURE and self.driver_error:
            return self.driver_error
        if self.type == TASK_KILLING and self.kill_timeout:
            return f"Kill Timeout: {self.kill_timeout}s"
        if self.type == TASK_RESTARTING:
            parts = []
            if self.restart_reason:
                parts.append(self.restart_reason)
            parts.append(f"Task restarting in {self.start_delay:.1f}s")
            return " - ".join(parts)
        if self.type == TASK_SIBLING_FAILED and self.failed_sibling:
            return f"Sibling task {self.failed_sibling!r} failed"
        return ""


@dataclass
class TaskState:
    """Client-side task state (structs.go:2928-3010)."""

    state: str = TASK_STATE_PENDING
    failed: bool = False
    started_at: float = 0.0
    finished_at: float = 0.0
    events: List[TaskEvent] = field(default_factory=list)

    def copy(self) -> "TaskState":
        t = _fast_copy(self)
        t.events = [e.copy() for e in self.events]
        return t

    def successful(self) -> bool:
        """Task is dead and its terminating event did not fail
        (structs.go:2980 TaskState.Successful)."""
        if self.state != TASK_STATE_DEAD:
            return False
        if not self.events:
            return False
        last = self.events[-1]
        return last.type == TASK_TERMINATED and last.exit_code == 0


# ---------------------------------------------------------------------------
# AllocMetric — user-visible placement forensics
# ---------------------------------------------------------------------------


@dataclass
class AllocMetric:
    """Placement forensics surfaced in alloc-status; the batched TPU kernel
    must preserve this contract via side-output counters
    (structs.go:4074-4172)."""

    nodes_evaluated: int = 0
    nodes_filtered: int = 0
    nodes_available: Dict[str, int] = field(default_factory=dict)
    class_filtered: Dict[str, int] = field(default_factory=dict)
    constraint_filtered: Dict[str, int] = field(default_factory=dict)
    nodes_exhausted: int = 0
    class_exhausted: Dict[str, int] = field(default_factory=dict)
    dimension_exhausted: Dict[str, int] = field(default_factory=dict)
    scores: Dict[str, float] = field(default_factory=dict)
    allocation_time: float = 0.0
    coalesced_failures: int = 0

    def copy(self) -> "AllocMetric":
        m = _fast_copy(self)
        m.nodes_available = dict(self.nodes_available)
        m.class_filtered = dict(self.class_filtered)
        m.constraint_filtered = dict(self.constraint_filtered)
        m.class_exhausted = dict(self.class_exhausted)
        m.dimension_exhausted = dict(self.dimension_exhausted)
        # A NodeScores is immutable: shared, not turned into strings.
        if type(self.scores) is not NodeScores:
            m.scores = dict(self.scores)
        return m

    def evaluate_node(self) -> None:
        self.nodes_evaluated += 1

    def filter_node(self, node: Optional[Node], constraint: str) -> None:
        self.nodes_filtered += 1
        if node is not None and node.node_class:
            self.class_filtered[node.node_class] = self.class_filtered.get(node.node_class, 0) + 1
        if constraint:
            self.constraint_filtered[constraint] = self.constraint_filtered.get(constraint, 0) + 1

    def exhausted_node(self, node: Optional[Node], dimension: str) -> None:
        self.nodes_exhausted += 1
        if node is not None and node.node_class:
            self.class_exhausted[node.node_class] = self.class_exhausted.get(node.node_class, 0) + 1
        if dimension:
            self.dimension_exhausted[dimension] = self.dimension_exhausted.get(dimension, 0) + 1

    def score_node(self, node: Node, name: str, score: float) -> None:
        key = f"{node.id}.{name}"
        if type(self.scores) is NodeScores:
            self.scores = dict(self.scores.as_dict())
        self.scores[key] = self.scores.get(key, 0.0) + score


# ---------------------------------------------------------------------------
# Allocation
# ---------------------------------------------------------------------------


@dataclass
class Allocation:
    """A placed task group on a node (structs.go:3820-4070)."""

    id: str = ""
    namespace: str = "default"
    eval_id: str = ""
    name: str = ""
    node_id: str = ""
    job_id: str = ""
    job: Optional[Job] = None
    task_group: str = ""
    resources: Optional[Resources] = None
    shared_resources: Optional[Resources] = None
    task_resources: Dict[str, Resources] = field(default_factory=dict)
    metrics: Optional[AllocMetric] = None
    desired_status: str = ALLOC_DESIRED_STATUS_RUN
    desired_description: str = ""
    client_status: str = ALLOC_CLIENT_STATUS_PENDING
    client_description: str = ""
    task_states: Dict[str, TaskState] = field(default_factory=dict)
    previous_allocation: str = ""
    create_index: int = 0
    modify_index: int = 0
    alloc_modify_index: int = 0
    create_time: float = 0.0

    def copy(self) -> "Allocation":
        a = _fast_copy(self)
        a.job = self.job.copy() if self.job else None
        a.resources = self.resources.copy() if self.resources else None
        a.shared_resources = self.shared_resources.copy() if self.shared_resources else None
        a.task_resources = {k: v.copy() for k, v in self.task_resources.items()}
        a.metrics = self.metrics.copy() if self.metrics else None
        a.task_states = {k: v.copy() for k, v in self.task_states.items()}
        return a

    def terminal_status(self) -> bool:
        """Desired stop/evict, else terminal client status (structs.go:3945)."""
        if self.desired_status in (ALLOC_DESIRED_STATUS_STOP, ALLOC_DESIRED_STATUS_EVICT):
            return True
        return self.client_status in (
            ALLOC_CLIENT_STATUS_COMPLETE,
            ALLOC_CLIENT_STATUS_FAILED,
            ALLOC_CLIENT_STATUS_LOST,
        )

    def held_networks(self) -> List[tuple]:
        """``(ip, device, Mbit, port values)`` of the first network of
        each task, reserved ports before dynamic: what the readers of
        held ports and bandwidth take of a row (``NetworkIndex``, the
        resident network mirror's reference).  ``SlabRow`` reads a
        network slab's row the same way, from the slab's columns."""
        out = []
        for tr in self.task_resources.values():
            if tr.networks:
                nr = tr.networks[0]
                out.append((nr.ip, nr.device, nr.mbits,
                            [p.value for p in nr.reserved_ports]
                            + [p.value for p in nr.dynamic_ports]))
        return out

    def client_terminal_status(self) -> bool:
        return self.client_status in (
            ALLOC_CLIENT_STATUS_COMPLETE,
            ALLOC_CLIENT_STATUS_FAILED,
            ALLOC_CLIENT_STATUS_LOST,
        )

    def ran_successfully(self) -> bool:
        """All task states finished successfully (structs.go:3974)."""
        if not self.task_states:
            return False
        return all(ts.successful() for ts in self.task_states.values())

    def stub(self) -> "AllocListStub":
        return AllocListStub(
            id=self.id,
            eval_id=self.eval_id,
            name=self.name,
            node_id=self.node_id,
            job_id=self.job_id,
            task_group=self.task_group,
            desired_status=self.desired_status,
            desired_description=self.desired_description,
            client_status=self.client_status,
            client_description=self.client_description,
            task_states={k: v.copy() for k, v in self.task_states.items()},
            create_index=self.create_index,
            modify_index=self.modify_index,
            create_time=self.create_time,
        )


@dataclass
class AllocListStub:
    """Lightweight allocation view for list endpoints (structs.go:4044)."""

    id: str = ""
    eval_id: str = ""
    name: str = ""
    node_id: str = ""
    job_id: str = ""
    task_group: str = ""
    desired_status: str = ""
    desired_description: str = ""
    client_status: str = ""
    client_description: str = ""
    task_states: Dict[str, TaskState] = field(default_factory=dict)
    create_index: int = 0
    modify_index: int = 0
    create_time: float = 0.0


# ---------------------------------------------------------------------------
# Evaluation
# ---------------------------------------------------------------------------


@dataclass
class Evaluation:
    """A scheduling work item: 'job X needs reconciling' (structs.go:4244-4475)."""

    id: str = ""
    namespace: str = "default"
    priority: int = JOB_DEFAULT_PRIORITY
    type: str = JOB_TYPE_SERVICE
    triggered_by: str = ""
    job_id: str = ""
    job_modify_index: int = 0
    node_id: str = ""
    node_modify_index: int = 0
    status: str = EVAL_STATUS_PENDING
    status_description: str = ""
    wait: float = 0.0  # seconds to delay before processing
    next_eval: str = ""
    previous_eval: str = ""
    blocked_eval: str = ""
    failed_tg_allocs: Dict[str, AllocMetric] = field(default_factory=dict)
    class_eligibility: Dict[str, bool] = field(default_factory=dict)
    escaped_computed_class: bool = False
    annotate_plan: bool = False
    queued_allocations: Dict[str, int] = field(default_factory=dict)
    snapshot_index: int = 0
    create_index: int = 0
    modify_index: int = 0

    def copy(self) -> "Evaluation":
        e = _fast_copy(self)
        e.failed_tg_allocs = {k: v.copy() for k, v in self.failed_tg_allocs.items()}
        e.class_eligibility = dict(self.class_eligibility)
        e.queued_allocations = dict(self.queued_allocations)
        return e

    def terminal_status(self) -> bool:
        return self.status in (EVAL_STATUS_COMPLETE, EVAL_STATUS_FAILED, EVAL_STATUS_CANCELLED)

    def trigger_index(self) -> int:
        """The lowest applied index a state snapshot must cover for a
        scheduler to SEE what this eval was created about: the job
        write, the node transition, or the capacity change / previous
        attempt recorded in snapshot_index (BlockedEvals raises it to
        the unblock index on re-admission).  Shared by the
        stale-snapshot worker fence (worker.py _required_index) and the
        broker's coalescing guard — an eval may only absorb another if
        its own trigger index covers the other's."""
        return max(self.job_modify_index, self.node_modify_index,
                   self.snapshot_index)

    def should_enqueue(self) -> bool:
        """Whether the eval belongs in the broker's ready queue (structs.go:4404)."""
        return self.status == EVAL_STATUS_PENDING

    def should_block(self) -> bool:
        return self.status == EVAL_STATUS_BLOCKED

    def make_plan(self, job: Optional[Job]) -> "Plan":
        """Create an empty plan for this eval (structs.go:4418 MakePlan)."""
        plan = Plan(
            eval_id=self.id,
            priority=self.priority,
            job=job,
            node_update={},
            node_allocation={},
        )
        if job is not None:
            plan.all_at_once = job.all_at_once
        return plan

    def next_rolling_eval(self, wait: float) -> "Evaluation":
        """Follow-up eval for a rolling update (structs.go:4440)."""
        return Evaluation(
            id=generate_uuid(),
            namespace=self.namespace,
            priority=self.priority,
            type=self.type,
            triggered_by=EVAL_TRIGGER_ROLLING_UPDATE,
            job_id=self.job_id,
            job_modify_index=self.job_modify_index,
            status=EVAL_STATUS_PENDING,
            wait=wait,
            previous_eval=self.id,
        )

    def create_blocked_eval(self, class_eligibility: Dict[str, bool],
                            escaped: bool) -> "Evaluation":
        """Blocked eval to retry placement when capacity appears
        (structs.go:4494 CreateBlockedEval)."""
        return Evaluation(
            id=generate_uuid(),
            namespace=self.namespace,
            priority=self.priority,
            type=self.type,
            triggered_by=self.triggered_by,
            job_id=self.job_id,
            job_modify_index=self.job_modify_index,
            status=EVAL_STATUS_BLOCKED,
            previous_eval=self.id,
            class_eligibility=class_eligibility,
            escaped_computed_class=escaped,
        )

    def create_failed_follow_up_eval(self, wait: float) -> "Evaluation":
        """Follow-up after hitting the delivery limit (structs.go:4460)."""
        return Evaluation(
            id=generate_uuid(),
            namespace=self.namespace,
            priority=self.priority,
            type=self.type,
            triggered_by="failed-follow-up",
            job_id=self.job_id,
            job_modify_index=self.job_modify_index,
            status=EVAL_STATUS_PENDING,
            wait=wait,
            previous_eval=self.id,
        )


def preemption_follow_up_evals(
    preempted: List["Allocation"], snapshot_index: int,
    job_lookup=None,
) -> List["Evaluation"]:
    """One BLOCKED follow-up eval per distinct evicted job, so preempted
    work re-enters the scheduler when capacity appears (the plan-apply /
    Harness halves share this so their eval shapes agree).  job_lookup
    (job_id -> Job) recovers priority/type; plan copies strip the job."""
    seen: Dict[str, Evaluation] = {}
    for alloc in preempted:
        if alloc.job_id in seen:
            continue
        job = alloc.job
        if job is None and job_lookup is not None:
            job = job_lookup(alloc.job_id)
        seen[alloc.job_id] = Evaluation(
            id=generate_uuid(),
            priority=job.priority if job is not None else JOB_DEFAULT_PRIORITY,
            type=job.type if job is not None else JOB_TYPE_SERVICE,
            triggered_by=EVAL_TRIGGER_PREEMPTION,
            job_id=alloc.job_id,
            status=EVAL_STATUS_BLOCKED,
            status_description=ALLOC_PREEMPTED,
            snapshot_index=snapshot_index,
        )
    return list(seen.values())


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------


# Deployment statuses (structs.go:3688-3694).
DEPLOYMENT_STATUS_RUNNING = "running"
DEPLOYMENT_STATUS_FAILED = "failed"
DEPLOYMENT_STATUS_SUCCESSFUL = "successful"
DEPLOYMENT_STATUS_CANCELLED = "cancelled"
DEPLOYMENT_STATUS_PAUSED = "paused"


@dataclass
class DeploymentState:
    """Per-task-group deployment progress (structs.go:3757-3790)."""

    promoted: bool = False
    requires_promotion: bool = False
    desired_canaries: int = 0
    desired_total: int = 0
    placed_allocs: int = 0
    healthy_allocs: int = 0
    unhealthy_allocs: int = 0

    def copy(self) -> "DeploymentState":
        return _fast_copy(self)


@dataclass
class Deployment:
    """Tracks a job version's rollout (structs.go:3698-3755).

    At this reference version the scheduler never CREATES deployments
    (`grep CreatedDeployment scheduler/` is empty — SURVEY.md §2.1);
    the struct + state-store surface exist for the API contract."""

    id: str = ""
    job_id: str = ""
    job_version: int = 0
    job_modify_index: int = 0
    job_create_index: int = 0
    task_groups: Dict[str, DeploymentState] = field(default_factory=dict)
    status: str = DEPLOYMENT_STATUS_RUNNING
    status_description: str = ""
    create_index: int = 0
    modify_index: int = 0

    def active(self) -> bool:
        """(structs.go:3747-3752)."""
        return self.status in (DEPLOYMENT_STATUS_RUNNING,
                               DEPLOYMENT_STATUS_PAUSED)

    def copy(self) -> "Deployment":
        c = _fast_copy(self)
        c.task_groups = {k: v.copy() for k, v in self.task_groups.items()}
        return c


@dataclass
class DeploymentStatusUpdate:
    """A status transition carried in a plan (structs.go:379,3795)."""

    deployment_id: str = ""
    status: str = ""
    status_description: str = ""


# ---------------------------------------------------------------------------
# Namespace (multi-tenant serving plane)
# ---------------------------------------------------------------------------

#: The implicit tenant every pre-tenancy job/eval/alloc belongs to.
#: Wire frames and snapshots written before the field existed decode to
#: this via the dataclass default, so mixed-version clusters agree.
DEFAULT_NAMESPACE = "default"

#: Per-namespace fairness objectives for the broker's tenant dequeue
#: (Gavel-style pluggable policy; "" on a Namespace inherits the
#: cluster-wide NOMAD_TPU_TENANCY_OBJECTIVE knob).
TENANCY_OBJECTIVE_DRF = "drf"
TENANCY_OBJECTIVE_WRR = "weighted-rr"
TENANCY_OBJECTIVE_FIFO = "fifo"
TENANCY_OBJECTIVES = (TENANCY_OBJECTIVE_DRF, TENANCY_OBJECTIVE_WRR,
                      TENANCY_OBJECTIVE_FIFO)


@dataclass
class Namespace:
    """A tenant: quota + fairness configuration, registered through raft
    like jobs and persisted in both snapshot formats.  All quota fields
    use 0 = unlimited so the implicit "default" namespace (and any
    namespace created with bare defaults) never throttles anything —
    pre-tenancy behavior is the zero value."""

    name: str = ""
    description: str = ""
    #: Max nodes-worth of dominant-resource usage (fractional ok):
    #: a tenant whose dominant share exceeds quota_node_units/cluster
    #: nodes is over quota for admission purposes.
    quota_node_units: float = 0.0
    #: Max live (non-terminal) allocations in committed state.
    max_live_allocs: int = 0
    #: Max evals pending in the broker (admission front door).
    max_pending_evals: int = 0
    #: Token-bucket API submit rate (requests/second) in agent/http.
    api_rate: float = 0.0
    #: Bucket depth; 0 derives a burst of max(1, 2*api_rate).
    api_burst: int = 0
    #: Fair-dequeue weight: a weight-2 tenant is charged half as much
    #: virtual time / dominant share as a weight-1 tenant.
    dequeue_weight: float = 1.0
    #: Per-tenant fairness objective override ("" inherits the global
    #: knob): drf | weighted-rr | fifo.
    objective: str = ""
    create_index: int = 0
    modify_index: int = 0

    def copy(self) -> "Namespace":
        return _fast_copy(self)

    def validate(self) -> List[str]:
        problems: List[str] = []
        if not self.name:
            problems.append("namespace name is empty")
        if self.dequeue_weight <= 0:
            problems.append("namespace dequeue_weight must be positive")
        if self.objective and self.objective not in TENANCY_OBJECTIVES:
            problems.append(
                f"namespace objective '{self.objective}' is invalid "
                f"(want one of {', '.join(TENANCY_OBJECTIVES)})")
        if (self.quota_node_units < 0 or self.max_live_allocs < 0
                or self.max_pending_evals < 0 or self.api_rate < 0
                or self.api_burst < 0):
            problems.append("namespace quota fields must be >= 0")
        return problems


class _LazyStrs:
    """A lazily-generated string column for AllocSlab: values are
    formulaic (prefix + ordinal) and materialized only when read.  The
    batch scheduler commits hundreds of thousands of slab allocs per
    pass; generating every id/name string eagerly was a measurable slice
    of the plan-materialization hot path, and most are never read
    individually.  ``__lazy_strs__`` marks instances for the wire codec
    (api/codec.to_wire), which materializes them to plain lists."""

    __lazy_strs__ = True
    __slots__ = ("n",)

    def __init__(self, n: int) -> None:
        self.n = n

    def _make(self, i: int) -> str:
        raise NotImplementedError

    def __len__(self) -> int:
        return self.n

    def __bool__(self) -> bool:
        return self.n > 0

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self._make(j) for j in range(*i.indices(self.n))]
        if i < 0:
            i += self.n
        if not 0 <= i < self.n:
            raise IndexError(i)
        return self._make(i)

    def __iter__(self):
        make = self._make
        return (make(i) for i in range(self.n))


class LazyUuids(_LazyStrs):
    """Formulaic uuid column: one random uuid prefix (first 24 chars,
    8-4-4-4- groups) + the ordinal as the final 12 hex digits — still
    canonical 36-char uuid form, unique across slabs by the ~76 random
    prefix bits."""

    __slots__ = ("prefix",)

    def __init__(self, n: int, prefix: Optional[str] = None) -> None:
        super().__init__(n)
        self.prefix = prefix if prefix is not None else generate_uuid()[:24]

    def _make(self, i: int) -> str:
        return f"{self.prefix}{i:012x}"


class LazyNames(_LazyStrs):
    """Formulaic alloc names '<job>.<tg>[i]' (reference
    structs.go AllocName / scheduler/util.go:22)."""

    __slots__ = ("prefix",)

    def __init__(self, n: int, prefix: str) -> None:
        super().__init__(n)
        self.prefix = prefix

    def _make(self, i: int) -> str:
        return f"{self.prefix}[{i}]"


class NodeTable:
    """The node ids of one encoded fleet, in the row order of its
    tensors: what every NodeColumn cut from that encoding shares.  Made
    once with the fleet's static tensors (ops/encode), not per batch; a
    slab that outlives the encoding keeps it alive."""

    __slots__ = ("ids", "packed", "packed_keys", "_perms")

    # Row permutations kept, one per index dict asked about (the store's
    # mirror, the resident mirror, a snapshot's private copy).
    PERMS_KEPT = 4

    def __init__(self, node_ids) -> None:
        import numpy as np

        self.ids = np.array(node_ids, dtype=object)
        # Each id as the struct codec writes it; filled by the codec the
        # first time it packs a column of this table (codec/native.py).
        self.packed = None
        # Each id with a score key's suffix, as the codec writes the
        # key: {suffix: array or list}, filled the same way.
        self.packed_keys: dict = {}
        self._perms: tuple = ()

    def rows_in(self, index: Dict[str, int]):
        """``index[id]`` of every id of the table as one int64 array, -1
        where the index lacks it, kept per index OBJECT.  ``index`` must
        be append-only (a row index: a key never moves or leaves), so an
        answer without a -1 stays right, and one with a -1 is computed
        again once the index has grown."""
        n = len(index)
        for kept, perm, complete, size in self._perms:
            if kept is index and (complete or size == n):
                return perm
        # At call time, not import time: state/ stands on this module.
        from ..state.columnar import gather_index

        perm = gather_index(index, self.ids)
        entry = (index, perm, bool(perm.size == 0 or perm.min() >= 0), n)
        self._perms = (entry,) + tuple(
            e for e in self._perms if e[0] is not index
        )[:self.PERMS_KEPT - 1]
        return perm


class NodeColumn(_LazyStrs):
    """A slab's node column as the device returned it: rows ``idx`` of
    the encoded fleet ``table``.  A sequence of node-id strings to
    whatever reads it as one; readers that want mirror rows or log bytes
    take them from the integers (state/columnar.gather_index,
    codec/native.pack_column) and handle no string."""

    __slots__ = ("table", "idx", "_strs")

    def __init__(self, table: NodeTable, idx) -> None:
        super().__init__(len(idx))
        self.table = table
        self.idx = idx
        self._strs: Optional[List[str]] = None

    def strings(self) -> List[str]:
        """The column as the list of its strings, made by one gather
        (no Python call per item) on first use and kept: a slab is read
        as strings again and again by the same few readers (the
        plan-fit guard's reference, by-node indexing).  Read-only."""
        strs = self._strs
        if strs is None:
            strs = self._strs = self.table.ids[self.idx].tolist()
        return strs

    def _make(self, i: int) -> str:
        strs = self._strs
        return strs[i] if strs is not None else self.table.ids[self.idx[i]]

    def __getitem__(self, i):
        if isinstance(i, slice):
            return NodeColumn(self.table, self.idx[i])
        return super().__getitem__(i)

    def __iter__(self):
        return iter(self.strings())

    def __eq__(self, other):
        if isinstance(other, (list, _LazyStrs)):
            return list(self) == list(other)
        return NotImplemented

    __hash__ = None


# NodeScores turned into their dictionary of strings, process-wide, on
# whichever thread asked (the batch worker publishes what it gained).
SCORE_MAPS_BUILT = 0


class NodeScores(Mapping):
    """A spec's commit-time scores as the device returned them: rows
    ``idx`` of the encoded fleet ``table`` and their float64 binpack
    scores, plus the sparse anti-affinity part (positions ``anti_pos``
    of ``idx`` and the penalties ``anti``; usually empty).  ``idx``
    names no row twice (decode.last_scores keeps a node's last commit),
    so the arrays' lengths are the dictionary's.  To a reader
    it IS the dictionary ``AllocMetric.scores`` holds on the oracle
    path: ``<node id>.binpack`` for every row in order, then
    ``<node id>.job-anti-affinity`` in position order; made by one
    gather on first such use and kept.  The log codec writes the same
    bytes from the integers (codec/native.pack_scores) and makes no
    string.  Read-only: ``AllocMetric.score_node`` replaces it by its
    dictionary before it adds."""

    __slots__ = ("table", "idx", "binpack", "anti_pos", "anti", "_map")

    BINPACK = ".binpack"
    ANTI_AFFINITY = ".job-anti-affinity"

    def __init__(self, table: NodeTable, idx, binpack,
                 anti_pos=(), anti=()) -> None:
        self.table = table
        self.idx = idx
        self.binpack = binpack
        self.anti_pos = anti_pos
        self.anti = anti
        self._map: Optional[Dict[str, float]] = None

    def as_dict(self) -> Dict[str, float]:
        """The scores as the dictionary of their strings, made once and
        kept; shared, so read-only (``dict(x)`` is a copy)."""
        m = self._map
        if m is None:
            global SCORE_MAPS_BUILT
            ids = self.table.ids[self.idx]
            m = dict(zip((ids + self.BINPACK).tolist(),
                         self.binpack.tolist()))
            if len(self.anti_pos):
                m.update(zip((ids[self.anti_pos]
                              + self.ANTI_AFFINITY).tolist(),
                             self.anti.tolist()))
            self._map = m
            SCORE_MAPS_BUILT += 1
        return m

    def __len__(self) -> int:
        return len(self.idx) + len(self.anti_pos)

    def __getitem__(self, key):
        return self.as_dict()[key]

    def __iter__(self):
        return iter(self.as_dict())

    def __contains__(self, key) -> bool:
        return key in self.as_dict()

    def get(self, key, default=None):
        return self.as_dict().get(key, default)

    def keys(self):
        return self.as_dict().keys()

    def values(self):
        return self.as_dict().values()

    def items(self):
        return self.as_dict().items()

    def __eq__(self, other):
        if isinstance(other, NodeScores):
            other = other.as_dict()
        if isinstance(other, dict):
            return self.as_dict() == other
        return NotImplemented

    __hash__ = None

    def __repr__(self) -> str:
        return f"NodeScores({self.as_dict()!r})"


@dataclass
class AllocSlab:
    """Columnar batch of placements sharing one prototype allocation.

    The TPU batch scheduler places tens of thousands of near-identical
    task-group instances per device dispatch; materializing a full
    Allocation object per placement is the dominant host-side cost at
    that scale.  A slab stores the shared prototype ONCE plus per-alloc
    columns (id, name, node, previous-alloc) and materializes Allocation
    objects lazily on read — the same pointer-sharing go-memdb relies on
    (the reference inserts the FSM's pointers outright,
    state_store.go:1435), taken to its SoA conclusion.

    ``prev_ids`` uses "" for "no previous allocation" so the slab stays
    a plain data-only msgpack tree on the replicated log (log_codec).

    A NETWORK slab (``ips`` not empty) holds placements whose tasks ask
    for a network, each with its own offer.  The prototype's task
    networks are then a template: the first network of each networked
    task (the tasks whose prototype resources carry one, in the
    prototype's task order) with the offer's device, Mbit and reserved
    ports, its dynamic port labels at value 0 and no IP.  What differs
    per row lives in two columns: ``ips``, each networked task's IP, row
    by row (``[k, T]`` flattened, T networked tasks), and ``dyn_ports``,
    the dynamic port values as int32 ``[k, n_dyn]`` packed
    little-endian (the labels in template order over the networked
    tasks).  ``materialize(i)`` builds the allocation with that row's
    offers, its ``task_resources`` and combined ``resources`` as the
    per-object form holds them.  A slab without networks carries
    neither column."""

    proto: Optional[Allocation] = None
    ids: List[str] = field(default_factory=list)
    names: List[str] = field(default_factory=list)
    node_ids: List[str] = field(default_factory=list)
    prev_ids: List[str] = field(default_factory=list)
    create_index: int = 0
    modify_index: int = 0
    ips: List[str] = field(default_factory=list)
    dyn_ports: bytes = b""

    def __len__(self) -> int:
        return len(self.ids)

    @classmethod
    def of_offers(cls, proto: Allocation, offers: List[list],
                  **columns) -> "AllocSlab":
        """The network slab of rows whose networked tasks (those of
        ``proto`` whose resources carry a network, in its task order)
        got ``offers``, one list a row, every row's on the same devices:
        a copy of ``proto`` whose task networks are the template (the
        first row's offers without IP and dynamic port values) and
        whose resources are their sum, the offers' IPs and dynamic port
        values as the two columns.  ``columns``: ids, names, node_ids,
        prev_ids."""
        task_resources = dict(proto.task_resources)
        networked = [name for name, tr in task_resources.items()
                     if tr.networks]
        for name, offer in zip(networked, offers[0]):
            tn = offer.copy()
            tn.ip = ""
            for p in tn.dynamic_ports:
                p.value = 0
            tr = task_resources[name].copy()
            tr.networks = [tn]
            task_resources[name] = tr
        total = Resources(disk_mb=proto.shared_resources.disk_mb
                          if proto.shared_resources else 0)
        for tr in task_resources.values():
            total.add(tr)
        template = _fast_copy(proto)
        template.task_resources = task_resources
        template.resources = total
        dyn = [p.value for row in offers for o in row for p in o.dynamic_ports]
        return cls(proto=template, ips=[o.ip for row in offers for o in row],
                   dyn_ports=_struct.pack(f"<{len(dyn)}i", *dyn), **columns)

    def materialize(self, i: int) -> Allocation:
        a = _fast_copy(self.proto)
        a.id = self.ids[i]
        a.name = self.names[i]
        a.node_id = self.node_ids[i]
        if self.prev_ids and self.prev_ids[i]:
            a.previous_allocation = self.prev_ids[i]
        a.create_index = self.create_index
        a.modify_index = self.modify_index
        a.alloc_modify_index = self.modify_index
        if self.ips:
            a.task_resources, a.resources = self._row_resources(i)
        return a

    # -- network slabs ---------------------------------------------------

    def _template(self) -> tuple:
        """``(tasks, T, n_dyn)`` of a network slab: per prototype task
        ``(name, resources, template network or None)``; kept on the
        slab (an undeclared attr, so it stays off the wire codec)."""
        t = getattr(self, "_tmpl", None)
        if t is None:
            tasks = [(name, tr, tr.networks[0] if tr.networks else None)
                     for name, tr in self.proto.task_resources.items()]
            nets = [tn for _, _, tn in tasks if tn is not None]
            t = self._tmpl = (tasks, len(nets),
                              sum(len(tn.dynamic_ports) for tn in nets))
        return t

    def _row_ports(self, i: int, n_dyn: int) -> tuple:
        return (_struct.unpack_from(f"<{n_dyn}i", self.dyn_ports,
                                    4 * n_dyn * i) if n_dyn else ())

    def _row_resources(self, i: int) -> tuple:
        """Row ``i``'s task resources and combined resources, built as
        the per-object form builds them: each networked task's resources
        with the row's offer as its one network, summed by
        ``Resources.add`` from the task group's disk."""
        tasks, n_net, n_dyn = self._template()
        dyn = self._row_ports(i, n_dyn)
        shared = self.proto.shared_resources
        total = Resources(disk_mb=shared.disk_mb if shared else 0)
        task_resources: Dict[str, Resources] = {}
        j = n_net * i
        q = 0
        for name, tr, tn in tasks:
            if tn is not None:
                n = len(tn.dynamic_ports)
                offer = NetworkResource(
                    tn.device, tn.cidr, self.ips[j], tn.mbits,
                    [Port(p.label, p.value) for p in tn.reserved_ports],
                    [Port(p.label, v)
                     for p, v in zip(tn.dynamic_ports, dyn[q:q + n])])
                tr = Resources(tr.cpu, tr.memory_mb, tr.disk_mb, tr.iops,
                               [offer])
                j += 1
                q += n
            task_resources[name] = tr
            total.add(tr)
        return task_resources, total

    def row_net_usage(self) -> List[tuple]:
        """What each row's networks hold, as ``alloc_net_vec`` reads an
        allocation: (Mbit, ports in the dynamic range) per row, from the
        template and one read of the port column."""
        from .network import MAX_DYNAMIC_PORT, MIN_DYNAMIC_PORT

        k = len(self)
        tasks, _, n_dyn = self._template()
        nets = [tn for _, _, tn in tasks if tn is not None]
        mbits = sum(tn.mbits for tn in nets)
        fixed = sum(1 for tn in nets for p in tn.reserved_ports
                    if MIN_DYNAMIC_PORT <= p.value < MAX_DYNAMIC_PORT)
        dyn = self._row_ports(0, k * n_dyn)
        if all(MIN_DYNAMIC_PORT <= v < MAX_DYNAMIC_PORT for v in dyn):
            # Every dynamic port the offers pick is in the range.
            return [(mbits, fixed + n_dyn)] * k
        return [(mbits, fixed + sum(
                    1 for v in dyn[r * n_dyn:(r + 1) * n_dyn]
                    if MIN_DYNAMIC_PORT <= v < MAX_DYNAMIC_PORT))
                for r in range(k)]

    def row_ports(self) -> List[tuple]:
        """The port values each row holds, as ``alloc_net_held`` reads an
        allocation (first network of each task, 0 left out; reserved
        before dynamic), from the template and one read of the port
        column."""
        k = len(self)
        tasks, _, n_dyn = self._template()
        fixed = tuple(p.value for _, _, tn in tasks if tn is not None
                      for p in tn.reserved_ports if p.value)
        dyn = self._row_ports(0, k * n_dyn)
        rows = [fixed + dyn[r * n_dyn:(r + 1) * n_dyn] for r in range(k)]
        if 0 in dyn:
            rows = [tuple(v for v in row if v) for row in rows]
        return rows

    def row_networks(self, i: int) -> List[tuple]:
        """``Allocation.held_networks`` of row ``i``, without
        materializing it."""
        tasks, n_net, n_dyn = self._template()
        dyn = self._row_ports(i, n_dyn)
        out = []
        j = n_net * i
        q = 0
        for _, _, tn in tasks:
            if tn is None:
                continue
            n = len(tn.dynamic_ports)
            out.append((self.ips[j], tn.device, tn.mbits,
                        [p.value for p in tn.reserved_ports]
                        + list(dyn[q:q + n])))
            j += 1
            q += n
        return out

    # -- rows ------------------------------------------------------------

    def id_index(self, alloc_id: str) -> int:
        """Column index of an alloc id; the reverse map is built lazily on
        first by-id access (bulk inserts never need it — undeclared attr,
        so it stays off the wire codec)."""
        idx = getattr(self, "_id_idx", None)
        if idx is None:
            idx = {aid: i for i, aid in enumerate(self.ids)}
            self._id_idx = idx
        return idx[alloc_id]

    def allocs(self) -> List[Allocation]:
        return [self.materialize(i) for i in range(len(self.ids))]

    def node_counts(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for nid in self.node_ids:
            counts[nid] = counts.get(nid, 0) + 1
        return counts

    def row(self, i: int):
        """Row ``i`` as a reader of usage and held networks sees it: the
        prototype, or for a network slab the row read in place
        (``SlabRow``)."""
        return SlabRow(self, i) if self.ips else self.proto

    def node_adds(self) -> Dict[str, List[tuple]]:
        """Per node, ``(row, count)`` pairs that stand for the slab's
        rows there: the prototype and its count, or for a network slab
        each row read in place (its ports are its own)."""
        if not self.ips:
            return {nid: [(self.proto, cnt)]
                    for nid, cnt in self.node_counts().items()}
        out: Dict[str, List[tuple]] = {}
        for i, nid in enumerate(self.node_ids):
            out.setdefault(nid, []).append((SlabRow(self, i), 1))
        return out

    def take(self, rows) -> "AllocSlab":
        """The slab of rows ``rows`` (positions, in order), every column
        cut alike."""
        rows = list(rows)
        ips = self.ips
        dyn_ports = self.dyn_ports
        if ips:
            _, n_net, n_dyn = self._template()
            ips = [ips[n_net * i + j] for i in rows for j in range(n_net)]
            w = 4 * n_dyn
            dyn_ports = b"".join(dyn_ports[w * i:w * i + w] for i in rows)
        return AllocSlab(
            proto=self.proto,
            ids=[self.ids[i] for i in rows],
            names=[self.names[i] for i in rows],
            node_ids=[self.node_ids[i] for i in rows],
            prev_ids=[self.prev_ids[i] for i in rows] if self.prev_ids else [],
            create_index=self.create_index,
            modify_index=self.modify_index,
            ips=ips,
            dyn_ports=dyn_ports,
        )

    def filter_nodes(self, keep: set) -> "AllocSlab":
        """Slab restricted to placements on ``keep`` nodes (partial plan
        commit, plan_apply.go:242)."""
        return self.take(i for i, nid in enumerate(self.node_ids)
                         if nid in keep)


class SlabRow:
    """Row ``i`` of a network slab read in place, for the readers of an
    allocation's usage and held networks (the plan applier's per-node
    fit re-check, the full walks of the usage and network references),
    which would otherwise build an Allocation per row they read.  Its
    fields are the prototype's but ``id``, ``name``, ``node_id``, the
    indexes and ``task_resources``, which are the row's (the last built
    on first read, as ``materialize`` builds it); ``resources`` is the
    prototype's, whose four quantities are the row's and whose networks
    are the template.  ``held_networks()`` reads its networks from the
    columns.  Not a dataclass: it never reaches the log or the API."""

    __slots__ = ("slab", "i", "_task_resources")

    def __init__(self, slab: AllocSlab, i: int) -> None:
        self.slab = slab
        self.i = i
        self._task_resources: Optional[Dict[str, Resources]] = None

    def __getattr__(self, name):
        return getattr(self.slab.proto, name)

    @property
    def id(self) -> str:
        return self.slab.ids[self.i]

    @property
    def name(self) -> str:
        return self.slab.names[self.i]

    @property
    def node_id(self) -> str:
        return self.slab.node_ids[self.i]

    @property
    def create_index(self) -> int:
        return self.slab.create_index

    @property
    def modify_index(self) -> int:
        return self.slab.modify_index

    @property
    def resources(self) -> Optional[Resources]:
        return self.slab.proto.resources

    @property
    def task_resources(self) -> Dict[str, Resources]:
        tr = self._task_resources
        if tr is None:
            tr = self._task_resources = self.slab._row_resources(self.i)[0]
        return tr

    def held_networks(self) -> List[tuple]:
        return self.slab.row_networks(self.i)


@dataclass
class Plan:
    """The scheduler's proposed state mutation, submitted for optimistic
    apply (structs.go:4477-4570)."""

    eval_id: str = ""
    eval_token: str = ""
    # Applied index of the snapshot the scheduler planned against
    # (optimistic concurrency, PAPER.md L3): the plan applier samples
    # apply_index − snapshot_index as plan staleness, the telemetry for
    # how far behind stale-snapshot workers run.
    snapshot_index: int = 0
    priority: int = 0
    all_at_once: bool = False
    job: Optional[Job] = None
    node_update: Dict[str, List[Allocation]] = field(default_factory=dict)
    node_allocation: Dict[str, List[Allocation]] = field(default_factory=dict)
    alloc_slabs: List[AllocSlab] = field(default_factory=list)
    # Evictions of strictly-lower-priority allocs this plan makes room
    # with (scheduler/preempt.py): committed atomically with the
    # placements, rejected if a preempted alloc changed underneath
    # (plan_apply.py optimistic-concurrency re-check).
    node_preemptions: Dict[str, List[Allocation]] = field(default_factory=dict)
    annotations: Optional["PlanAnnotations"] = None

    def append_update(
        self,
        alloc: Allocation,
        desired_status: str,
        desired_description: str,
        client_status: str = "",
    ) -> None:
        """Mark an existing alloc for stop/evict (structs.go:4520 AppendUpdate).

        If the plan has no job (job deregistration) the alloc's job is adopted
        so the applier can identify what is being stopped; the staged update
        itself is normalized (job + combined resources stripped)."""
        new_alloc = alloc.copy()
        if self.job is None and new_alloc.job is not None:
            self.job = new_alloc.job
        new_alloc.job = None
        new_alloc.resources = None
        new_alloc.desired_status = desired_status
        new_alloc.desired_description = desired_description
        if client_status:
            new_alloc.client_status = client_status
        self.node_update.setdefault(alloc.node_id, []).append(new_alloc)

    def pop_update(self, alloc: Allocation) -> None:
        """Remove a staged eviction (used by in-place update speculation,
        structs.go:4546 PopUpdate)."""
        updates = self.node_update.get(alloc.node_id, [])
        if updates and updates[-1].id == alloc.id:
            updates.pop()
            if not updates:
                self.node_update.pop(alloc.node_id, None)

    def append_alloc(self, alloc: Allocation) -> None:
        self.node_allocation.setdefault(alloc.node_id, []).append(alloc)

    def append_preempted_alloc(self, alloc: Allocation) -> None:
        """Stage an eviction that makes room for a higher-priority
        placement.  The copy keeps the victim's modify_index — the plan
        applier's staleness fence (reject if it moved underneath)."""
        new_alloc = alloc.copy()
        new_alloc.job = None
        new_alloc.resources = None
        new_alloc.desired_status = ALLOC_DESIRED_STATUS_EVICT
        new_alloc.desired_description = ALLOC_PREEMPTED
        self.node_preemptions.setdefault(alloc.node_id, []).append(new_alloc)

    def append_slab(self, slab: AllocSlab) -> None:
        self.alloc_slabs.append(slab)

    def is_no_op(self) -> bool:
        return (not self.node_update and not self.node_allocation
                and not self.alloc_slabs and not self.node_preemptions)

    def total_allocs(self) -> int:
        return (sum(len(v) for v in self.node_allocation.values())
                + sum(len(v) for v in self.node_update.values())
                + sum(len(v) for v in self.node_preemptions.values())
                + sum(len(sl) for sl in self.alloc_slabs))


@dataclass
class PlanResult:
    """The subset of a plan the leader committed (structs.go:4581-4620)."""

    node_update: Dict[str, List[Allocation]] = field(default_factory=dict)
    node_allocation: Dict[str, List[Allocation]] = field(default_factory=dict)
    alloc_slabs: List[AllocSlab] = field(default_factory=list)
    node_preemptions: Dict[str, List[Allocation]] = field(default_factory=dict)
    refresh_index: int = 0
    alloc_index: int = 0

    def full_commit(self, plan: Plan) -> tuple[bool, int, int]:
        """Whether every proposed alloc was committed (structs.go:4604)."""
        expected = 0
        actual = 0
        for node, allocs in plan.node_update.items():
            expected += len(allocs)
            actual += len(self.node_update.get(node, []))
        for node, allocs in plan.node_allocation.items():
            expected += len(allocs)
            actual += len(self.node_allocation.get(node, []))
        for node, allocs in plan.node_preemptions.items():
            expected += len(allocs)
            actual += len(self.node_preemptions.get(node, []))
        expected += sum(len(sl) for sl in plan.alloc_slabs)
        actual += sum(len(sl) for sl in self.alloc_slabs)
        return actual == expected, expected, actual


@dataclass
class PlanAnnotations:
    """Dry-run plan diff annotations for the plan CLI (structs.go:4625)."""

    desired_tg_updates: Dict[str, "DesiredUpdates"] = field(default_factory=dict)


@dataclass
class DesiredUpdates:
    ignore: int = 0
    place: int = 0
    migrate: int = 0
    stop: int = 0
    in_place_update: int = 0
    destructive_update: int = 0


# ---------------------------------------------------------------------------
# Job diff wire types (diff.go:14-200; the diff engine lives in diff.py)
# ---------------------------------------------------------------------------

DIFF_TYPE_NONE = "None"
DIFF_TYPE_ADDED = "Added"
DIFF_TYPE_DELETED = "Deleted"
DIFF_TYPE_EDITED = "Edited"


@dataclass
class FieldDiff:
    type: str = DIFF_TYPE_NONE
    name: str = ""
    old: str = ""
    new: str = ""
    annotations: List[str] = field(default_factory=list)


@dataclass
class ObjectDiff:
    type: str = DIFF_TYPE_NONE
    name: str = ""
    fields: List[FieldDiff] = field(default_factory=list)
    objects: List["ObjectDiff"] = field(default_factory=list)


@dataclass
class TaskDiff:
    type: str = DIFF_TYPE_NONE
    name: str = ""
    fields: List[FieldDiff] = field(default_factory=list)
    objects: List[ObjectDiff] = field(default_factory=list)
    annotations: List[str] = field(default_factory=list)


@dataclass
class TaskGroupDiff:
    type: str = DIFF_TYPE_NONE
    name: str = ""
    fields: List[FieldDiff] = field(default_factory=list)
    objects: List[ObjectDiff] = field(default_factory=list)
    tasks: List[TaskDiff] = field(default_factory=list)
    updates: Dict[str, int] = field(default_factory=dict)


@dataclass
class JobDiff:
    type: str = DIFF_TYPE_NONE
    id: str = ""
    fields: List[FieldDiff] = field(default_factory=list)
    objects: List[ObjectDiff] = field(default_factory=list)
    task_groups: List[TaskGroupDiff] = field(default_factory=list)


@dataclass
class JobPlanResponse:
    """Dry-run result returned by Job.Plan (structs.go JobPlanResponse):
    the annotated diff plus placement forensics, no state mutated."""

    annotations: Optional[PlanAnnotations] = None
    failed_tg_allocs: Dict[str, AllocMetric] = field(default_factory=dict)
    job_modify_index: int = 0
    created_evals: List["Evaluation"] = field(default_factory=list)
    diff: Optional[JobDiff] = None
    next_periodic_launch: float = 0.0


# ---------------------------------------------------------------------------
# Job summary
# ---------------------------------------------------------------------------


@dataclass
class TaskGroupSummary:
    """Per-TG alloc status counts (structs.go:1680-1700)."""

    queued: int = 0
    complete: int = 0
    failed: int = 0
    running: int = 0
    starting: int = 0
    lost: int = 0


@dataclass
class JobSummary:
    """Materialized per-job alloc summary (structs.go:1640-1678)."""

    job_id: str = ""
    summary: Dict[str, TaskGroupSummary] = field(default_factory=dict)
    children: Optional["JobChildrenSummary"] = None
    create_index: int = 0
    modify_index: int = 0

    def copy(self) -> "JobSummary":
        s = _fast_copy(self)
        s.summary = {k: dataclasses.replace(v) for k, v in self.summary.items()}
        s.children = dataclasses.replace(self.children) if self.children else None
        return s


@dataclass
class JobChildrenSummary:
    pending: int = 0
    running: int = 0
    dead: int = 0


# -- cluster event stream (reference: nomad/stream, the 1.0 event broker) ----

TOPIC_NODE = "Node"
TOPIC_JOB = "Job"
TOPIC_EVAL = "Eval"
TOPIC_ALLOC = "Alloc"
TOPIC_DEPLOYMENT = "Deployment"
TOPIC_PLAN = "Plan"
TOPIC_BREAKER = "Breaker"
TOPIC_FAULT = "Fault"
TOPIC_NAMESPACE = "Namespace"

EVENT_TOPICS = (TOPIC_NODE, TOPIC_JOB, TOPIC_EVAL, TOPIC_ALLOC,
                TOPIC_DEPLOYMENT, TOPIC_PLAN, TOPIC_BREAKER, TOPIC_FAULT,
                TOPIC_NAMESPACE)


@dataclass
class Event:
    """One structured state-change event (structs/event.go Event): a
    (topic, type, key) triple stamped with the raft index of the write
    that produced it, a payload stub, and — when the write happened
    under a traced span — the correlating eval/span ids from the
    tracing plane, so an event timeline joins against
    ``/v1/trace/eval/<id>``."""

    topic: str = ""
    type: str = ""
    key: str = ""
    index: int = 0
    payload: Dict[str, object] = field(default_factory=dict)
    eval_id: str = ""
    span_id: int = 0
    wall: float = 0.0

    def to_wire_dict(self) -> Dict[str, object]:
        return {"Topic": self.topic, "Type": self.type, "Key": self.key,
                "Index": self.index, "Payload": self.payload,
                "EvalID": self.eval_id, "SpanID": self.span_id,
                "Wall": self.wall}


def now() -> float:
    return time.time()
