"""Type registry + schema fingerprint for the struct codec.

Every decodable type gets a STABLE numeric id derived from the sorted
registry order (deterministic for two peers running the same code), and
the whole registry folds into one 8-byte schema fingerprint exchanged in
the codec channel handshake: peers whose struct schemas diverge (a
rolling upgrade that added a field) negotiate the connection down to the
reflection-msgpack wire format instead of misreading each other's flat
layouts.  This is the codec twin of server/log_codec's whitelist — a
peer can only produce registered data types, never code.
"""
from __future__ import annotations

import dataclasses
import hashlib
import typing
from typing import Dict, List, Tuple

from ..state.state_store import PeriodicLaunch, VaultAccessor
from ..structs import structs as _structs

#: Frame magic: 0xC1 is the one byte the msgpack spec never emits, so a
#: frame's first byte IS the per-frame codec tag — binary struct frames
#: start 0xC1, reflection-msgpack frames never do.
MAGIC = 0xC1

#: Flat-layout schema version carried in every frame after the magic.
#: 2: AllocSlab gained its network columns, ``ips`` and ``dyn_ports``.
VERSION = 2

#: The fields each version added to a type's layout, by type name.  A
#: frame of an older version is decoded with the layouts it was written
#: with (a type's fields less those added since; they are appended, so
#: the old layout is a prefix) and those fields at their defaults.
ADDED: Dict[int, Dict[str, Tuple[str, ...]]] = {
    2: {"AllocSlab": ("ips", "dyn_ports")},
}


def _registry() -> List[Tuple[str, type]]:
    types = {
        name: obj
        for name, obj in vars(_structs).items()
        if isinstance(obj, type) and dataclasses.is_dataclass(obj)
    }
    types["PeriodicLaunch"] = PeriodicLaunch
    types["VaultAccessor"] = VaultAccessor
    return sorted(types.items())


_REGISTRY = _registry()

#: type -> id and id -> type (ids are positions in the sorted registry).
TYPE_IDS: Dict[type, int] = {cls: i for i, (_, cls) in enumerate(_REGISTRY)}
TYPES_BY_ID: List[type] = [cls for _, cls in _REGISTRY]


def _type_repr(hint) -> str:
    """Stable textual form of a field's type hint (typing reprs are
    stable enough across processes running the same interpreter)."""
    return repr(hint)


def fields_of(cls: type, version: int = VERSION) -> List[dataclasses.Field]:
    """``cls``'s fields in the layout of ``version``."""
    later = {name for v, added in ADDED.items() if v > version
             for name in added.get(cls.__name__, ())}
    return [f for f in dataclasses.fields(cls) if f.name not in later]


def schema_fingerprint(version: int = VERSION) -> bytes:
    """8-byte digest of every registered type's (name, fields, hints) in
    the layouts of ``version``: two peers agree on the flat layouts iff
    their fingerprints match."""
    h = hashlib.sha256()
    h.update(bytes([version]))
    for name, cls in _REGISTRY:
        h.update(name.encode())
        try:
            hints = typing.get_type_hints(cls)
        except Exception:
            hints = {}
        for f in fields_of(cls, version):
            h.update(f.name.encode())
            h.update(_type_repr(hints.get(f.name, "?")).encode())
    return h.digest()[:8]


#: Every layout version this build decodes, with its fingerprint; it
#: encodes the newest.
FINGERPRINTS: Dict[int, bytes] = {
    v: schema_fingerprint(v) for v in range(1, VERSION + 1)}
FINGERPRINT = FINGERPRINTS[VERSION]
