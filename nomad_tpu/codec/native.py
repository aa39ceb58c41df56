"""String-column pack/unpack: the codec's inner framing loop, with an
optional C++ fast path (nomad_tpu/native/codec.cc, the ``native/wal.cc``
precedent) and a pure-Python twin kept bit-identical.

Where it pays: AllocSlab's non-formulaic columns (node_ids — tens of
thousands of 36-char uuids per gang plan) and every ``List[str]`` field
crossing the RPC/raft/snapshot codec.  The layout is per-string varint
length + utf8 bytes, preceded by the column count written by the caller.

Differential guard (the columnar/resident discipline): every
``NOMAD_TPU_CODEC_GUARD_EVERY``-th native call is re-run through the
Python twin and bit-compared.  A mismatch disables the native path for
the process, feeds the PR 2 kernel circuit breaker
(``ops.breaker.BREAKER``), and logs — wrong bytes must never reach a
peer quietly.  ``NOMAD_TPU_NO_NATIVE=1`` forces the twin.

A slab committed by the device path carries its node column as integers
(structs.NodeColumn); ``pack_column`` writes the same bytes for it by
one gather of the fleet's pre-packed ids, under the same guard.  Its
prototype's score map is integers and floats too (structs.NodeScores);
``pack_scores`` writes a ``Dict[str, float]``'s pairs, that one by the
same gather with the scores' own bytes beside each key.
"""
from __future__ import annotations

import ctypes
import logging
import os
import struct
from typing import List, Tuple

from ..structs.structs import NodeScores

logger = logging.getLogger("nomad_tpu.codec")

GUARD_RUNS = 0
GUARD_MISMATCHES = 0
NATIVE_PACKS = 0
NATIVE_UNPACKS = 0
COLUMN_PACKS = 0
SCORE_PACKS = 0

_guard_counter = 0
_native_disabled = False
_lib = None
_lib_resolved = False


def guard_every() -> int:
    from ..utils import knobs

    return knobs.get_int("NOMAD_TPU_CODEC_GUARD_EVERY")


def reset_counters() -> None:
    global GUARD_RUNS, GUARD_MISMATCHES, NATIVE_PACKS, NATIVE_UNPACKS
    global COLUMN_PACKS, SCORE_PACKS, _guard_counter, _native_disabled
    GUARD_RUNS = GUARD_MISMATCHES = 0
    NATIVE_PACKS = NATIVE_UNPACKS = COLUMN_PACKS = SCORE_PACKS = 0
    _guard_counter = 0
    _native_disabled = False


def _get_lib():
    """Build/load codec.cc lazily; None when unavailable (twin carries)."""
    global _lib, _lib_resolved
    if _lib_resolved:
        return _lib
    _lib_resolved = True
    try:
        from ..native import NativeUnavailable, _load

        lib = _load("nomadcodec", "codec.cc")
        lib.ncodec_packed_size.restype = ctypes.c_long
        lib.ncodec_packed_size.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_long]
        lib.ncodec_pack_strs.restype = ctypes.c_long
        lib.ncodec_pack_strs.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int32), ctypes.c_long,
            ctypes.c_char_p, ctypes.c_long]
        lib.ncodec_split_strs.restype = ctypes.c_long
        lib.ncodec_split_strs.argtypes = [
            ctypes.c_char_p, ctypes.c_long, ctypes.c_long, ctypes.c_long,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]
        _lib = lib
    except Exception as e:  # NativeUnavailable or toolchain breakage
        logger.debug("codec: native unavailable (%s); python twin carries",
                     e)
        _lib = None
    return _lib


def _note_mismatch(op: str) -> None:
    global GUARD_MISMATCHES, _native_disabled
    GUARD_MISMATCHES += 1
    _native_disabled = True
    logger.error(
        "codec: native %s diverged from the python twin — native path "
        "DISABLED for this process, breaker fed", op)
    try:
        from ..ops import breaker as _breaker

        _breaker.BREAKER.record(False)
    except Exception:  # pragma: no cover — breaker optional in tools
        pass


# -- python twins ------------------------------------------------------------


def _py_pack_strs(encoded: List[bytes]) -> bytes:
    w = bytearray()
    for e in encoded:
        n = len(e)
        while n > 0x7F:
            w.append(0x80 | (n & 0x7F))
            n >>= 7
        w.append(n)
        w += e
    return bytes(w)


_pd = struct.Struct("<d").pack


def _py_pack_scores(scores) -> bytes:
    """A ``Dict[str, float]``'s pairs as the struct codec lays them:
    varint length + utf8 key, 8-byte little-endian double."""
    w = bytearray()
    for k, x in scores.items():
        e = k.encode("utf-8")
        n = len(e)
        while n > 0x7F:
            w.append(0x80 | (n & 0x7F))
            n >>= 7
        w.append(n)
        w += e
        w += _pd(x)
    return bytes(w)


def _py_split_strs(b: bytes, p: int, n: int) -> Tuple[List[str], int]:
    from .gen import CodecError

    out = []
    ln = len(b)
    for _ in range(n):
        size = 0
        shift = 0
        while True:
            if p >= ln:
                raise CodecError("truncated string column")
            c = b[p]
            p += 1
            size |= (c & 0x7F) << shift
            if c < 0x80:
                break
            shift += 7
            if shift > 35:
                raise CodecError("string length varint overflow")
        e = p + size
        if e > ln:
            raise CodecError("truncated string column")
        out.append(b[p:e].decode("utf-8"))
        p = e
    return out, p


# -- public entry points -----------------------------------------------------


def pack_strs(strs) -> bytes:
    """Pack a string column (varint len + utf8 per item); caller writes
    the count.  Native when available, differential-guarded."""
    global NATIVE_PACKS
    encoded = [s.encode("utf-8") for s in strs]
    lib = None if _native_disabled else _get_lib()
    if lib is None or not encoded:
        return _py_pack_strs(encoded)
    n = len(encoded)
    lens = (ctypes.c_int32 * n)(*map(len, encoded))
    concat = b"".join(encoded)
    total = lib.ncodec_packed_size(lens, n)
    out = ctypes.create_string_buffer(total)
    written = lib.ncodec_pack_strs(concat, lens, n, out, total)
    if written != total:  # pragma: no cover — C-side invariant
        _note_mismatch("pack_strs(size)")
        return _py_pack_strs(encoded)
    NATIVE_PACKS += 1
    result = out.raw
    if _guard_due() and result != _py_pack_strs(encoded):
        _note_mismatch("pack_strs")
        return _py_pack_strs(encoded)
    return result


def _guard_due() -> bool:
    """Count one guarded call; True on every ``guard_every()``-th."""
    global GUARD_RUNS, _guard_counter
    every = guard_every()
    if every <= 0:
        return False
    _guard_counter += 1
    if _guard_counter < every:
        return False
    _guard_counter = 0
    GUARD_RUNS += 1
    return True


def _packed_table(table, suffix: str = ""):
    """Each id of ``table`` (structs.NodeTable) as ``pack_strs`` writes
    it, made once per table: one fixed-width ``S`` array when every
    packed id has the same length (a uuid fleet, ``node-00001``), else a
    list of ``bytes``.  With a ``suffix``, each id + suffix: a score
    map's keys, kept per suffix beside the plain ids."""
    packed = table.packed_keys.get(suffix) if suffix else table.packed
    if packed is None:
        import numpy as np

        packed = [_py_pack_strs([(s + suffix).encode("utf-8")])
                  for s in table.ids]
        widths = set(map(len, packed))
        if len(widths) == 1:
            packed = np.frombuffer(b"".join(packed),
                                   dtype=f"S{widths.pop()}")
        if suffix:
            table.packed_keys[suffix] = packed
        else:
            table.packed = packed
    return packed


def pack_column(col) -> bytes:
    """``pack_strs(list(col))`` for an indexed node column
    (structs.NodeColumn), byte for byte, as one gather of the table's
    pre-packed ids by the column's integers: no string is made or
    encoded.  Guarded like the native pack: every
    ``NOMAD_TPU_CODEC_GUARD_EVERY``-th call is compared with the Python
    twin over the materialized strings, and a mismatch turns this route
    off for the process with the native one."""
    global COLUMN_PACKS
    if _native_disabled or not len(col):
        return pack_strs(list(col))
    packed = _packed_table(col.table)
    if type(packed) is list:
        result = b"".join(map(packed.__getitem__, col.idx.tolist()))
    else:
        result = packed[col.idx].tobytes()
    COLUMN_PACKS += 1
    if _guard_due():
        twin = _py_pack_strs([s.encode("utf-8") for s in col])
        if result != twin:
            _note_mismatch("pack_column")
            return twin
    return result


def _gather_pairs(keys, idx, values) -> bytes:
    """``keys[i]`` then ``values``' own 8 bytes, for every ``i`` of
    ``idx`` in order: one record array for fixed-width keys."""
    import numpy as np

    values = np.asarray(values, dtype="<f8")
    if type(keys) is list:
        vb = values.tobytes()
        out = [b""] * (2 * len(idx))
        out[0::2] = map(keys.__getitem__, idx.tolist())
        out[1::2] = [vb[i:i + 8] for i in range(0, len(vb), 8)]
        return b"".join(out)
    rec = np.empty(len(idx), dtype=[("k", keys.dtype), ("v", "<f8")])
    rec["k"] = keys[idx]
    rec["v"] = values
    return rec.tobytes()


def pack_scores(scores) -> bytes:
    """The pairs of a ``Dict[str, float]`` field; caller writes the
    count.  For a structs.NodeScores the bytes the loop over its
    dictionary would write, from its integers and floats by one gather
    of the table's pre-packed keys: no string is made or encoded.
    Guarded like ``pack_column``, and turned off with it."""
    global SCORE_PACKS
    if (type(scores) is not NodeScores or _native_disabled
            or not len(scores)):
        return _py_pack_scores(scores)
    result = _gather_pairs(_packed_table(scores.table, scores.BINPACK),
                           scores.idx, scores.binpack)
    if len(scores.anti_pos):
        result += _gather_pairs(
            _packed_table(scores.table, scores.ANTI_AFFINITY),
            scores.idx[scores.anti_pos], scores.anti)
    SCORE_PACKS += 1
    if _guard_due():
        twin = _py_pack_scores(scores)
        if result != twin:
            _note_mismatch("pack_scores")
            return twin
    return result


def unpack_strs(b: bytes, p: int, n: int) -> Tuple[List[str], int]:
    """Parse ``n`` packed strings from ``b`` at ``p``; returns
    (strings, new position).  Native length scan when available."""
    global NATIVE_UNPACKS
    from .gen import CodecError

    if n > len(b) - p:  # each string costs >= 1 byte
        raise CodecError("string column count exceeds frame")
    lib = None if _native_disabled else _get_lib()
    if lib is None or n == 0 or not isinstance(b, bytes):
        return _py_split_strs(b, p, n)
    lens = (ctypes.c_int32 * n)()
    offs = (ctypes.c_int32 * n)()
    # The WHOLE frame + start offset cross the ABI (ctypes passes the
    # bytes object's internal buffer, no copy) — slicing b[p:] here
    # would memcpy the remaining frame once per string-column field.
    end = lib.ncodec_split_strs(b, p, len(b), n, lens, offs)
    if end < 0:
        raise CodecError("malformed string column")
    NATIVE_UNPACKS += 1
    out = [b[offs[i]:offs[i] + lens[i]].decode("utf-8")
           for i in range(n)]
    if _guard_due():
        twin, twin_end = _py_split_strs(b, p, n)
        if twin != out or twin_end != end:
            _note_mismatch("unpack_strs")
            return twin, twin_end
    return out, end
