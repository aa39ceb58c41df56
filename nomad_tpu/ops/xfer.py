"""Host↔device transfer packing.

jax.device_put of a pytree issues one transfer per leaf, so a batch
upload of ~25 small arrays pays ~25 link round trips (the cost of one
on the current chip: not measured).  This module packs an arbitrary dict of arrays into ONE
uint8 buffer (one transfer each way) with a deterministic layout both
sides compute independently:

- host→device: pack_host() → device_put → unpack_device() under jit
  (static slices + bitcasts that XLA fuses into the consuming kernel).
- device→host: pack_device() under jit → one device_get → unpack_host()
  (zero-copy numpy views).

layout() is the single source of truth for offsets: given {name: (tag,
shape)} it returns the meta tuple, identical on both sides, so the
device can pack results the host knows how to slice without shipping the
meta across the link.

Reference analogue: the msgpack wire codec (nomad/rpc.go:59) batches
whole request structs into one frame rather than a field at a time; this
is the same idea at the device-link boundary.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

# dtype tag → numpy dtype
_DTYPES = {
    "i32": np.int32,
    "u32": np.uint32,
    "f32": np.float32,
    "i16": np.int16,
    "u16": np.uint16,
    "i8": np.int8,
    "u8": np.uint8,
    "b1": np.bool_,
}

# (name, tag, shape, byte offset)
Meta = Tuple[Tuple[str, str, Tuple[int, ...], int], ...]


def _tag(dtype) -> str:
    dtype = np.dtype(dtype)
    for tag, dt in _DTYPES.items():
        if dtype == dt:
            return tag
    raise TypeError(f"unsupported pack dtype {dtype}")


def _nbytes(tag: str, shape: Tuple[int, ...]) -> int:
    nelem = int(np.prod(shape, dtype=np.int64)) if shape else 1
    return nelem * np.dtype(_DTYPES[tag]).itemsize


def layout(items: Dict[str, Tuple[str, Tuple[int, ...]]]) -> Meta:
    """Deterministic buffer layout: sorted by name, 4-byte aligned."""
    metas: List[Tuple[str, str, Tuple[int, ...], int]] = []
    off = 0
    for name in sorted(items):
        tag, shape = items[name]
        metas.append((name, tag, tuple(shape), off))
        nbytes = _nbytes(tag, shape)
        off += nbytes + ((-nbytes) % 4)
    return tuple(metas)


def total_bytes(meta: Meta) -> int:
    if not meta:
        return 0
    name, tag, shape, off = meta[-1]
    nbytes = _nbytes(tag, shape)
    return off + nbytes + ((-nbytes) % 4)


def pack_host(arrays: Dict[str, np.ndarray]) -> Tuple[np.ndarray, Meta]:
    """Concatenate host arrays into one uint8 buffer + layout meta."""
    meta = layout({n: (_tag(a.dtype), tuple(a.shape))
                   for n, a in arrays.items()})
    buf = np.zeros(total_bytes(meta), dtype=np.uint8)
    for name, tag, shape, off in meta:
        a = np.ascontiguousarray(arrays[name])
        raw = a.view(np.uint8).reshape(-1)
        buf[off:off + raw.size] = raw
    return buf, meta


def pack_host_sharded(arrays: Dict[str, np.ndarray], shards: int,
                      replicate: Tuple[str, ...] = ()
                      ) -> Tuple[np.ndarray, Meta]:
    """Per-shard packing for node-mesh uploads: every array is split
    into ``shards`` equal slices along its leading axis — except the
    ``replicate`` names, which are copied whole into every shard (e.g.
    the [4] quantization scale codebook) — and each slice set packs
    into one uint8 row of the returned [shards, B] buffer.  All rows
    share the same layout by construction, so the single returned meta
    describes every shard; placed with ``NamedSharding(mesh,
    P(node_axis))`` each device receives exactly its slice and unpacks
    it with the shared ``unpack_device``.
    """
    for name, arr in arrays.items():
        if name not in replicate and arr.shape[0] % shards:
            # A non-replicated array whose leading axis doesn't divide
            # the mesh would be silently truncated into wrong slices —
            # fail loudly instead (either pad the axis or list the
            # array in ``replicate``).
            raise ValueError(
                f"pack_host_sharded: array {name!r} leading axis "
                f"{arr.shape[0]} not divisible by {shards} shards")
    rows: List[np.ndarray] = []
    meta: Meta = ()
    for s_i in range(shards):
        sl: Dict[str, np.ndarray] = {}
        for name, arr in arrays.items():
            if name in replicate:
                sl[name] = arr
            else:
                n_l = arr.shape[0] // shards
                sl[name] = np.ascontiguousarray(
                    arr[s_i * n_l:(s_i + 1) * n_l])
        buf, meta = pack_host(sl)
        rows.append(buf)
    return np.stack(rows), meta


def unpack_device(buf: jnp.ndarray, meta: Meta) -> Dict[str, jnp.ndarray]:
    """Slice + bitcast each array out of the packed device buffer.

    Runs under jit (meta is static): XLA sees static slices of one input
    and fuses them into the consumers — no materialized copies."""
    out: Dict[str, jnp.ndarray] = {}
    for name, tag, shape, off in meta:
        np_dtype = _DTYPES[tag]
        nbytes = _nbytes(tag, shape)
        itemsize = np.dtype(np_dtype).itemsize
        if np_dtype in (np.uint8, np.bool_):
            arr = lax.slice(buf, (off,), (off + nbytes,))
            if np_dtype == np.bool_:
                arr = arr.astype(jnp.bool_)
            out[name] = arr.reshape(shape)
        elif itemsize == 1:   # int8: same-width bitcast, no regroup
            raw = lax.slice(buf, (off,), (off + nbytes,))
            out[name] = lax.bitcast_convert_type(
                raw, jnp.dtype(np_dtype)).reshape(shape)
        else:
            # Group the bytes into itemsize-wide words and bitcast; the
            # slice stays at nbytes (offsets are 4-aligned by layout(),
            # and nbytes is always a multiple of itemsize).
            raw = lax.slice(buf, (off,), (off + nbytes,))
            words = raw.reshape(-1, itemsize)
            arr = lax.bitcast_convert_type(words, jnp.dtype(np_dtype))
            out[name] = arr.reshape(shape)
    return out


def pack_device(arrays: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, Meta]:
    """Device-side packing under jit: bitcast every array to uint8 and
    concatenate.  The caller fetches the single buffer with one
    device_get and unpacks host-side with unpack_host()."""
    meta = layout({n: (_tag(np.bool_ if a.dtype == jnp.bool_
                            else np.dtype(a.dtype)), tuple(a.shape))
                   for n, a in arrays.items()})
    chunks: List[jnp.ndarray] = []
    pos = 0
    for name, tag, shape, off in meta:
        a = arrays[name]
        if a.dtype == jnp.bool_:
            a = a.astype(jnp.uint8)
        if a.dtype == jnp.uint8:
            raw = a.reshape(-1)
        else:
            raw = lax.bitcast_convert_type(a, jnp.uint8).reshape(-1)
        pad = (-raw.size) % 4
        if pad:
            raw = jnp.concatenate([raw, jnp.zeros(pad, dtype=jnp.uint8)])
        assert pos == off, "layout mismatch"
        chunks.append(raw)
        pos = off + raw.size
    buf = (jnp.concatenate(chunks) if chunks
           else jnp.zeros(0, dtype=jnp.uint8))
    return buf, meta


def unpack_host(buf: np.ndarray, meta: Meta) -> Dict[str, np.ndarray]:
    """numpy-view unpack of a fetched pack_device buffer (zero-copy for
    word-aligned dtypes)."""
    out: Dict[str, np.ndarray] = {}
    for name, tag, shape, off in meta:
        np_dtype = _DTYPES[tag]
        nbytes = _nbytes(tag, shape)
        raw = buf[off:off + nbytes]
        if np_dtype == np.bool_:
            out[name] = raw.view(np.uint8).astype(bool).reshape(shape)
        else:
            out[name] = raw.view(np_dtype).reshape(shape)
    return out
