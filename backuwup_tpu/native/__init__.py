"""ctypes binding for the native single-thread dedup pipeline.

The library (built by the Makefile here) plays the role of the
reference's native `fastcdc` + SIMD `blake3` crates
(``dir_packer.rs:246-311``): the honest single-thread CPU baseline for the
device pipeline's throughput target, and a fast host fallback.  It is
built on first use when a C compiler is available, with
``-march=native``, so the file's name carries a hash of the tracked
sources and of this host's CPU flags: a library built from other sources
or for another CPU (a working tree copied to another machine) has
another name and is never loaded.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
from pathlib import Path
from typing import List, Optional, Tuple

import numpy as np

from ..ops.blake3_cpu import blake3_hash

_DIR = Path(__file__).resolve().parent
_SOURCES = ("cdc_blake3.c", "Makefile")


class NativeUnavailable(RuntimeError):
    pass


_lib: Optional[ctypes.CDLL] = None
_failed: Optional[NativeUnavailable] = None  # one build attempt per process


def _cpu_flags() -> str:
    """This host's CPU feature line (what ``-march=native`` compiles
    for); the machine type alone where /proc/cpuinfo is not readable."""
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    return platform.machine() + line
    except OSError:
        pass
    return platform.machine()


def _lib_path() -> Path:
    h = hashlib.sha256()
    for name in _SOURCES:
        h.update((_DIR / name).read_bytes())
    h.update(_cpu_flags().encode())
    return _DIR / f"libbkw_native-{h.hexdigest()[:16]}.so"


def _build(path: Path) -> None:
    """Build to a private name, then rename: several processes (xdist
    workers) may find the library missing at once."""
    tmp = path.with_name(f"{path.stem}.{os.getpid()}.tmp.so")
    try:
        subprocess.run(["make", "-C", str(_DIR), "-s", f"LIB={tmp.name}"],
                       check=True, capture_output=True)
        os.replace(tmp, path)
    finally:
        tmp.unlink(missing_ok=True)
    for old in _DIR.glob("libbkw_native*.so"):
        if old != path and ".tmp" not in old.name:
            old.unlink(missing_ok=True)


def load() -> ctypes.CDLL:
    """Load (building if missing) the native library for these sources
    and this CPU; raises :class:`NativeUnavailable` when it cannot be
    built."""
    global _lib, _failed
    if _lib is not None:
        return _lib
    if _failed is not None:
        raise _failed
    path = _lib_path()
    if not path.exists():
        try:
            _build(path)
        except (OSError, subprocess.CalledProcessError) as e:
            detail = getattr(e, "stderr", b"") or b""
            _failed = NativeUnavailable(
                f"cannot build native library: {e} "
                f"{detail.decode(errors='replace')[-400:]}")
            raise _failed
    lib = ctypes.CDLL(str(path))
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.bkw_blake3.argtypes = [u8p, ctypes.c_size_t, u8p]
    lib.bkw_blake3.restype = None
    common = [u8p, ctypes.c_size_t, ctypes.c_uint64, ctypes.c_uint64,
              ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32, u64p, u64p]
    lib.bkw_chunk.argtypes = common + [ctypes.c_size_t]
    lib.bkw_chunk.restype = ctypes.c_long
    lib.bkw_manifest.argtypes = common + [u8p, ctypes.c_size_t]
    lib.bkw_manifest.restype = ctypes.c_long
    _lib = lib
    return lib


def available() -> bool:
    try:
        load()
        return True
    except NativeUnavailable:
        return False


def _u8(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def blake3_native(data: bytes) -> bytes:
    lib = load()
    arr = np.frombuffer(bytes(data), dtype=np.uint8)
    out = np.zeros(32, dtype=np.uint8)
    lib.bkw_blake3(_u8(arr) if len(arr) else _u8(out), len(arr), _u8(out))
    return out.tobytes()


def host_digest(data, oracle=blake3_hash) -> bytes:
    """BLAKE3 of one input on the host, for what is hashed where it is
    built (a tree node as the packer emits it, a transfer's whole file):
    the C library where it loads (6 us at 100 bytes, 0.3 ms at 147 KB,
    GIL released), else ``oracle`` — the scalar reference by default
    (0.14 ms and 180 ms for the same two), which suits metadata-sized
    inputs; a caller of whole files passes the numpy batch engine."""
    if available():
        return blake3_native(data)
    return oracle(data)


def _cap(n: int, min_size: int) -> int:
    return max(4, n // max(min_size, 1) + 2)


def chunk_native(data, params) -> List[Tuple[int, int]]:
    """Chunk one stream; bit-identical to ops.cdc_cpu.chunk_stream."""
    lib = load()
    arr = np.frombuffer(bytes(data), dtype=np.uint8)
    cap = _cap(len(arr), params.min_size)
    offs = np.zeros(cap, dtype=np.uint64)
    lens = np.zeros(cap, dtype=np.uint64)
    k = lib.bkw_chunk(
        _u8(arr) if len(arr) else _u8(offs.view(np.uint8)), len(arr),
        params.min_size, params.desired_size, params.max_size,
        params.mask_s, params.mask_l,
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), cap)
    if k < 0:
        raise RuntimeError("native chunk capacity overflow")
    return [(int(offs[i]), int(lens[i])) for i in range(k)]


def manifest_native(data, params):
    """Chunk + digest one stream single-threaded; returns
    (chunks, digests-bytes-list)."""
    lib = load()
    arr = np.frombuffer(bytes(data), dtype=np.uint8)
    cap = _cap(len(arr), params.min_size)
    offs = np.zeros(cap, dtype=np.uint64)
    lens = np.zeros(cap, dtype=np.uint64)
    digs = np.zeros(cap * 32, dtype=np.uint8)
    k = lib.bkw_manifest(
        _u8(arr) if len(arr) else _u8(digs), len(arr),
        params.min_size, params.desired_size, params.max_size,
        params.mask_s, params.mask_l,
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        _u8(digs), cap)
    if k < 0:
        raise RuntimeError("native manifest capacity overflow")
    chunks = [(int(offs[i]), int(lens[i])) for i in range(k)]
    digests = [digs[32 * i:32 * (i + 1)].tobytes() for i in range(k)]
    return chunks, digests
