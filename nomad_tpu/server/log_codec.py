"""Codec for replicated-log entry payloads and FSM snapshot sections.

The reference transports Raft log entries as msgpack-encoded data-only
structs (nomad/fsm.go:115 decodes each entry with the structs codec;
nomad/structs/structs.go:4637-4665 codec handles).  Since ISSUE 11 the
default encoding is the generated struct codec (nomad_tpu/codec): flat
per-type layouts, no reflection walk per entry — the leader's entry
encode and every follower's apply decode are the two biggest per-plan
costs an early follower-scale run charged to this module's msgpack
path.

Compatibility is per frame: codec blobs carry the 0xC1 magic (a byte
msgpack never emits), so ``decode_payload`` sniffs and accepts BOTH
formats forever — WALs, sealed segments, and snapshots written before
the upgrade (or by an ``NOMAD_TPU_CODEC=0`` peer) replay unchanged, and
flipping the kill switch never strands data in either direction.

The msgpack fallback keeps the original tagged-tree form: dataclass
instances are tagged with their type name and re-hydrated through the
reflection wire codec — never pickled, so a peer on the raft channel
can only produce whitelisted data types, not code.  The struct codec
enforces the same whitelist through its type-id registry
(nomad_tpu/codec/schema.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict

import msgpack

from .. import codec
from ..api.codec import from_wire, to_wire
from ..state.state_store import PeriodicLaunch, VaultAccessor
from ..structs import structs as _structs

_TAG = "__t"
_DATA = "__d"

# Whitelist of decodable payload types: every dataclass in the structs
# module plus the state-store row types the FSM applies.
_TYPES: Dict[str, type] = {
    name: obj
    for name, obj in vars(_structs).items()
    if isinstance(obj, type) and dataclasses.is_dataclass(obj)
}
_TYPES["PeriodicLaunch"] = PeriodicLaunch
_TYPES["VaultAccessor"] = VaultAccessor
# A network slab (an AllocSlab with ``ips`` and ``dyn_ports`` columns)
# goes under a tag of its own: a build that predates the columns does
# not know the tag and refuses the entry, where its ``from_wire`` would
# drop the two columns and restore every row without its ports.
_NET_SLAB = "AllocSlab.net"
_TYPES[_NET_SLAB] = _structs.AllocSlab


def _enc(v: Any) -> Any:
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        tag = type(v).__name__
        if tag == "AllocSlab" and v.ips:
            tag = _NET_SLAB
        return {_TAG: tag, _DATA: to_wire(v)}
    if isinstance(v, dict):
        return {k: _enc(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_enc(x) for x in v]
    return v


def _dec(v: Any) -> Any:
    if isinstance(v, dict):
        tag = v.get(_TAG)
        if tag is not None and _DATA in v:
            cls = _TYPES.get(tag)
            if cls is None:
                raise ValueError(f"log codec: unknown payload type {tag!r}")
            return from_wire(cls, v[_DATA])
        return {k: _dec(x) for k, x in v.items()}
    if isinstance(v, list):
        return [_dec(x) for x in v]
    return v


def encode_payload(payload: dict, subsystem: str = "raft") -> bytes:
    """One log-entry/snapshot-section blob.  Struct codec by default;
    the reflection-msgpack tree under ``NOMAD_TPU_CODEC=0`` or when the
    payload holds something outside the generated schema (counted as a
    codec fallback)."""
    if codec.enabled():
        try:
            return codec.encode(payload, subsystem)
        except codec.CodecError:
            pass  # fall through to the tagged-msgpack tree
    t0 = time.monotonic()
    blob = msgpack.packb(_enc(payload), use_bin_type=True)
    codec.note_msgpack(subsystem, "encode", t0, len(blob))
    return blob


def decode_payload(blob: bytes, subsystem: str = "raft") -> dict:
    """Sniffing decode: 0xC1-tagged struct-codec frames and legacy
    msgpack trees both decode, regardless of the kill switch."""
    if codec.is_frame(blob):
        return codec.decode(blob, subsystem)
    t0 = time.monotonic()
    out = _dec(msgpack.unpackb(blob, raw=False))
    codec.note_msgpack(subsystem, "decode", t0, len(blob))
    return out
