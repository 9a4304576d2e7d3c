"""ctypes binding for the benchmark's own copy of the C dedup pipeline.

Built on first use into ``benchmark/_build/`` (git-ignored) with
``-march=native``; the file's name carries a hash of the source and of
this host's CPU flags, so a library built elsewhere is never loaded.
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

from .gear import CDCParams

_SRC = Path(__file__).resolve().parent / "cdc_blake3.c"
_BUILD = Path(__file__).resolve().parents[1] / "_build"
_lib: Optional[ctypes.CDLL] = None


def _cpu_flags() -> str:
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    return platform.machine() + line
    except OSError:
        pass
    return platform.machine()


def load() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    h = hashlib.sha256(_SRC.read_bytes() + _cpu_flags().encode())
    path = _BUILD / f"libbench_ref-{h.hexdigest()[:16]}.so"
    if not path.exists():
        _BUILD.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(f"{path.stem}.{os.getpid()}.tmp.so")
        try:
            subprocess.run(
                [os.environ.get("CC", "cc"), "-O3", "-march=native", "-fPIC",
                 "-shared", "-o", str(tmp), str(_SRC)],
                check=True, capture_output=True)
            os.replace(tmp, path)
        finally:
            tmp.unlink(missing_ok=True)
    lib = ctypes.CDLL(str(path))
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.bkw_blake3.argtypes = [u8p, ctypes.c_size_t, u8p]
    lib.bkw_blake3.restype = None
    lib.bkw_manifest.argtypes = [
        u8p, ctypes.c_size_t, ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32, u64p, u64p,
        u8p, ctypes.c_size_t]
    lib.bkw_manifest.restype = ctypes.c_long
    _lib = lib
    return lib


def _u8(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def blake3(data) -> bytes:
    lib = load()
    arr = np.frombuffer(data, dtype=np.uint8)
    out = np.zeros(32, dtype=np.uint8)
    lib.bkw_blake3(_u8(arr) if len(arr) else _u8(out), len(arr), _u8(out))
    return out.tobytes()


def manifest(data, params: CDCParams) -> List[Tuple[int, int, bytes]]:
    """Chunk + digest one stream: [(offset, length, digest), ...]."""
    lib = load()
    arr = np.frombuffer(data, dtype=np.uint8)
    cap = max(4, len(arr) // max(params.min_size, 1) + 2)
    offs = np.zeros(cap, dtype=np.uint64)
    lens = np.zeros(cap, dtype=np.uint64)
    digs = np.zeros(cap * 32, dtype=np.uint8)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    k = lib.bkw_manifest(
        _u8(arr) if len(arr) else _u8(digs), len(arr),
        params.min_size, params.desired_size, params.max_size,
        params.mask_s, params.mask_l,
        offs.ctypes.data_as(u64p), lens.ctypes.data_as(u64p),
        _u8(digs), cap)
    if k < 0:
        raise RuntimeError("reference manifest capacity overflow")
    raw = digs.tobytes()
    return [(int(offs[i]), int(lens[i]), raw[32 * i:32 * (i + 1)])
            for i in range(k)]
