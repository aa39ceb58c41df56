"""Native (C++) runtime components, bound via ctypes.

The compute path of this framework is JAX/XLA; the runtime around it uses
native code where the hot path warrants it (the task's analogue of the
reference's performance-critical Go internals).  First component: the
group-commit WAL behind the raft log (wal.cc) — every raft apply pays an
fsync, and the native WAL coalesces concurrent appends into one.

Build model: sources ship in this package and are compiled on first use
with g++ into a content-addressed .so under ~/.cache/nomad_tpu/native
(no pybind11 in this image — plain C ABI + ctypes).  Everything degrades
gracefully: if the toolchain is missing or the build fails, importers
fall back to the pure-Python implementations.

Set NOMAD_TPU_NO_NATIVE=1 to force the Python fallbacks.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Iterator, Optional

_HERE = os.path.dirname(__file__)
_BUILD_LOCK = threading.Lock()
_LIBS = {}


class NativeUnavailable(Exception):
    """The native library could not be built/loaded on this host."""


def _disabled() -> bool:
    from ..utils import knobs

    return knobs.get_bool("NOMAD_TPU_NO_NATIVE")


def _sanitized() -> bool:
    """ASan/UBSan build mode (ISSUE 15): the native components compile
    with -fsanitize=address,undefined and the twin/fuzz corpora run
    against them in a sanitizer-preloaded subprocess (see __main__.py
    and sanitizer_env()).  Never the production mode — the selfcheck
    corpus leg arms it explicitly."""
    from ..utils import knobs

    return knobs.get_bool("NOMAD_TPU_NATIVE_ASAN")


SANITIZE_FLAGS = ["-fsanitize=address,undefined",
                  "-fno-sanitize-recover=all",
                  "-fno-omit-frame-pointer", "-g"]


def sanitizer_env() -> dict:
    """Environment for a child process that loads sanitized .so's into
    a stock python: the ASan/UBSan runtimes must be first in the link
    order, which for a ctypes-loaded library means LD_PRELOAD.  Leak
    checking is off — the interpreter's own allocations would drown
    the signal; the corpus leg is after buffer/UB bugs in our code."""
    libs = []
    for lib in ("libasan.so", "libubsan.so"):
        try:
            path = subprocess.run(
                ["g++", f"-print-file-name={lib}"],
                capture_output=True, timeout=30,
                check=True).stdout.decode().strip()
        except (subprocess.CalledProcessError,
                subprocess.TimeoutExpired, FileNotFoundError):
            continue
        if path and os.path.isabs(path):
            libs.append(path)
    env = dict(os.environ)
    env["NOMAD_TPU_NATIVE_ASAN"] = "1"
    if libs:
        env["LD_PRELOAD"] = ":".join(libs)
    env["ASAN_OPTIONS"] = ("detect_leaks=0:abort_on_error=1:"
                           + env.get("ASAN_OPTIONS", ""))
    env["UBSAN_OPTIONS"] = ("halt_on_error=1:"
                            + env.get("UBSAN_OPTIONS", ""))
    return env


def _build(name: str, source: str) -> str:
    """Compile ``source`` (a .cc in this package) into a cached .so and
    return its path.  Content-addressed: recompiles only when the source
    changes; sanitized builds cache under a distinct name."""
    from ..utils import knobs

    src_path = os.path.join(_HERE, source)
    with open(src_path, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    cache_dir = (knobs.get_str("NOMAD_TPU_NATIVE_CACHE")
                 or os.path.expanduser("~/.cache/nomad_tpu/native"))
    os.makedirs(cache_dir, exist_ok=True)
    sanitized = _sanitized()
    suffix = "-asan" if sanitized else ""
    so_path = os.path.join(cache_dir, f"lib{name}-{digest}{suffix}.so")
    if os.path.exists(so_path):
        return so_path
    tmp = so_path + f".tmp{os.getpid()}"
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread"]
    if sanitized:
        cmd += SANITIZE_FLAGS
    cmd += [src_path, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError) as exc:
        detail = ""
        if isinstance(exc, subprocess.CalledProcessError):
            detail = exc.stderr.decode(errors="replace")[:500]
        raise NativeUnavailable(f"g++ build failed for {source}: {exc} "
                                f"{detail}") from exc
    os.replace(tmp, so_path)
    return so_path


def _load(name: str, source: str) -> ctypes.CDLL:
    if _disabled():
        raise NativeUnavailable("disabled via NOMAD_TPU_NO_NATIVE")
    with _BUILD_LOCK:
        lib = _LIBS.get(name)
        if lib is None:
            lib = ctypes.CDLL(_build(name, source))
            _LIBS[name] = lib
        return lib


# Every native component: name → (library, source).  The bindings live
# with their users (wal/ids below, codec/native.py, ops/decode.py).
COMPONENTS = {
    "wal": ("nomadwal", "wal.cc"),
    "codec": ("nomadcodec", "codec.cc"),
    "decode": ("nomaddecode", "decode.cc"),
    "ids": ("nomadids", "ids.cc"),
}


def load_report() -> dict:
    """Build/load every native component now: {name: None when loaded,
    else why not}.  Start-up diagnostics (chip_smoke.py) — the importers
    themselves fall back to their python twins silently."""
    out = {}
    for comp, (name, source) in COMPONENTS.items():
        try:
            _load(name, source)
            out[comp] = None
        except NativeUnavailable as exc:
            out[comp] = str(exc)
    return out


# ---------------------------------------------------------------------------
# Group-commit WAL (wal.cc)
# ---------------------------------------------------------------------------


def _wal_lib() -> ctypes.CDLL:
    lib = _load("nomadwal", "wal.cc")
    if not getattr(lib, "_nwal_typed", False):
        lib.nwal_open.restype = ctypes.c_void_p
        lib.nwal_open.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                  ctypes.c_char_p, ctypes.c_int]
        lib.nwal_entry_count.restype = ctypes.c_long
        lib.nwal_entry_count.argtypes = [ctypes.c_void_p]
        lib.nwal_append.restype = ctypes.c_int
        lib.nwal_append.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_uint32]
        lib.nwal_write.restype = ctypes.c_uint64
        lib.nwal_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                   ctypes.c_uint32]
        lib.nwal_sync_seq.restype = ctypes.c_int
        lib.nwal_sync_seq.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.nwal_iter_start.restype = None
        lib.nwal_iter_start.argtypes = [ctypes.c_void_p]
        lib.nwal_iter_next.restype = ctypes.c_int
        lib.nwal_iter_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint32)]
        lib.nwal_reset.restype = ctypes.c_int
        lib.nwal_reset.argtypes = [ctypes.c_void_p]
        lib.nwal_sync.restype = ctypes.c_int
        lib.nwal_sync.argtypes = [ctypes.c_void_p]
        lib.nwal_close.restype = None
        lib.nwal_close.argtypes = [ctypes.c_void_p]
        lib._nwal_typed = True
    return lib


class NativeWAL:
    """CRC-framed append-only record log with group-commit fsync.

    Records are opaque bytes; framing, CRC validation, torn/corrupt-tail
    truncation at open, and fsync coalescing across threads live in
    wal.cc.  Raises NativeUnavailable if the toolchain is missing."""

    def __init__(self, path: str, fsync: bool = True):
        self._lib = _wal_lib()
        err = ctypes.create_string_buffer(256)
        self._h = self._lib.nwal_open(path.encode(), 1 if fsync else 0,
                                      err, len(err))
        if not self._h:
            raise OSError(f"nwal_open({path}): "
                          f"{err.value.decode(errors='replace')}")
        self.path = path

    def __len__(self) -> int:
        return int(self._lib.nwal_entry_count(self._h))

    def append(self, record: bytes) -> None:
        """Durable when this returns (group-commit fsync)."""
        rc = self._lib.nwal_append(self._h, record, len(record))
        if rc != 0:
            raise OSError(f"nwal_append failed on {self.path}")

    def write(self, record: bytes) -> int:
        """Write one record WITHOUT waiting for durability; returns its
        seq for :meth:`sync_to`.  The raft log calls this under its
        apply lock (file order == index order for the durable prefix)
        and syncs outside it so concurrent appliers share one fsync."""
        seq = self._lib.nwal_write(self._h, record, len(record))
        if seq == 0:
            raise OSError(f"nwal_write failed on {self.path}")
        return seq

    def sync_to(self, seq: int) -> None:
        """Block until records through ``seq`` are durable (group
        commit across concurrent callers)."""
        if self._lib.nwal_sync_seq(self._h, seq) != 0:
            raise OSError(f"nwal_sync_seq failed on {self.path}")

    def records(self) -> Iterator[bytes]:
        """Iterate all records from the start.  Not safe to interleave
        with concurrent iteration (single cursor), appends are fine."""
        self._lib.nwal_iter_start(self._h)
        data = ctypes.POINTER(ctypes.c_uint8)()
        length = ctypes.c_uint32()
        while True:
            rc = self._lib.nwal_iter_next(self._h, ctypes.byref(data),
                                          ctypes.byref(length))
            if rc == 0:
                return
            if rc < 0:
                raise OSError(f"nwal_iter_next failed on {self.path}")
            yield ctypes.string_at(data, length.value)

    def sync(self) -> None:
        """fsync everything written so far (segment-seal barrier: the
        raft log calls this before rolling the WAL at a snapshot)."""
        if self._lib.nwal_sync(self._h) != 0:
            raise OSError(f"nwal_sync failed on {self.path}")

    def reset(self) -> None:
        """Truncate to empty (post-snapshot)."""
        if self._lib.nwal_reset(self._h) != 0:
            raise OSError(f"nwal_reset failed on {self.path}")

    def close(self) -> None:
        if self._h:
            self._lib.nwal_close(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover — destructor best-effort
        try:
            self.close()
        except Exception:
            pass


def native_wal_available() -> bool:
    """True when the native WAL can be built/loaded on this host."""
    try:
        _wal_lib()
        return True
    except NativeUnavailable:
        return False


# ---------------------------------------------------------------------------
# Bulk UUID generation (ids.cc)
# ---------------------------------------------------------------------------


def _ids_lib() -> ctypes.CDLL:
    lib = _load("nomadids", "ids.cc")
    if not getattr(lib, "_nids_typed", False):
        lib.nids_generate.restype = ctypes.c_int
        lib.nids_generate.argtypes = [ctypes.c_char_p, ctypes.c_long]
        lib._nids_typed = True
    return lib


def generate_uuids(n: int) -> list:
    """n standard-form uuids from one native call (~8x the pure-Python
    bulk path at batch sizes).  Raises NativeUnavailable without the
    toolchain — callers keep their Python fallback."""
    lib = _ids_lib()
    buf = ctypes.create_string_buffer(36 * n)
    if lib.nids_generate(buf, n) != 0:
        raise OSError("nids_generate failed")
    s = buf.raw.decode("ascii")
    return [s[i * 36:(i + 1) * 36] for i in range(n)]
