"""One segment of a streamed file, resident in HBM from upload to digest.

``TpuBackend.manifest_stream`` drives this: a window of the stream goes
to the device once, straight from a view of what ``read()`` returned,
and stays there until its chunks are digested.  The host keeps positions
and views; it copies nothing but the one chunk a segment that straddles
the carry and the new window.

Layout of the resident buffer (one fixed shape per ``segment_bytes``, so
every program that reads it compiles once)::

    [ zeros ... carry | window ............ | slack ]
    0          front-c  front        front+w         size

* ``front`` bytes in front of the window hold the carry — the last,
  still open chunk of the previous segment (at most ``max_size`` bytes),
  right-aligned so that carry and window are contiguous — and with it the
  gear hash's 31-byte halo.  The carry arrives by a device slice of the
  previous buffer (:func:`_next_resident`), never by a second upload.
* the window is uploaded in power-of-two blocks (views of the caller's
  buffer, no host copy; a tail shorter than the smallest block is padded
  on the host) and written in place (:func:`_put_block`).
* ``slack`` zeros past the window keep the digest gather's over-read and
  the last scan slice in bounds (``lax.dynamic_slice`` clamps an
  out-of-range start without a word).

The scan (:func:`..cdc_tpu._scan_segment`, unchanged) reads fixed slices
of the buffer; each window byte is scanned once, because the carry's
candidates are kept from the scan that first saw them.  The digest is the
batched route's ``_gather_digest``: chunk spans are sliced out of HBM in
tiles of one height per leaf class, only ``(offset, length)`` rows go up
and 32 bytes a chunk come down.

Which programs can ever run is a function of the geometry alone
(:class:`Geometry`): :func:`warm` runs every one of them once, reached
or not, the first time a process streams with that geometry.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import defaults
from ..obs import profile as obs_profile
from .blake3_tpu import _leaf_bucket
from .cdc_cpu import gear_hashes as gear_hashes_np
from .cdc_tpu import (
    _HALO,
    TpuCdcScanner,
    _decode_words,
    _round_up,
    _scan_segment,
    _segment_bucket,
)
from .gear import CDCParams
from .pipeline import CHUNK_LEN, _gather_digest

# smallest block the window is uploaded in; a shorter tail is padded on
# the host (the only bytes of a window the host copies)
BLOCK_MIN = 64 * 1024
# A leaf class digests in tiles of ONE height, the tallest of these whose
# tile (rows x padded chunk bytes) stays within TILE_BYTES; a class's last
# tile is padded with empty rows.  Every (height, class) pair is a program
# the first streamed file traces, lowers and loads (~2 s each on a v5e
# with a warm compile cache), so one height a class, not _row_tiles' four:
# padding a tile costs a few ms a segment (a 128 x 256 KiB tile runs
# 4.8 ms, 37 us a row against 35 us in a 512-row tile; PERF.md, PR 26).
_TILE_HEIGHTS = (128, 32, 8)  # 128 is DevicePipeline's b_bucket too
TILE_BYTES = 128 * defaults.MiB


@dataclass(frozen=True)
class Geometry:
    """Every shape the route compiles, from ``segment_bytes``, the CDC
    parameters and the scanner's slice size."""

    segment_bytes: int
    front: int          # bytes in front of the window: carry + halo
    scan_slice: int     # bytes one _scan_segment launch covers
    n_slices: int       # launches that cover a full window
    k_cap: int          # the scan's sparse-word capacity
    size: int           # the resident buffer
    classes: Tuple[Tuple[int, int], ...]  # (L, tile height) per leaf class
    rows: int           # meta / accumulator rows of one segment

    @classmethod
    def of(cls, params: CDCParams, scanner: TpuCdcScanner,
           segment_bytes: int) -> "Geometry":
        if segment_bytes < 1:
            raise ValueError("segment_bytes must be positive")
        front = _round_up(params.max_size + _HALO, BLOCK_MIN)
        scan_slice = min(scanner.segment_size,
                         _segment_bucket(segment_bytes))
        n_slices = -(-segment_bytes // scan_slice)
        # a chunk of exactly min_size, or a file's short last chunk,
        # rides in the class above: one class fewer to compile
        lo = _leaf_bucket(params.min_size + 1)
        hi = _leaf_bucket(params.max_size)
        if hi not in defaults.BLAKE3_LEAF_BUCKETS:
            raise ValueError("max_size exceeds the largest leaf bucket")
        classes = tuple(
            (L, next(h for h in _TILE_HEIGHTS
                     if h == 8 or h * L * CHUNK_LEN <= TILE_BYTES))
            for L in defaults.BLAKE3_LEAF_BUCKETS if lo <= L <= hi)
        slack = max(hi * CHUNK_LEN, BLOCK_MIN)
        size = front + n_slices * scan_slice + slack
        if size >= 1 << 31:
            raise ValueError("segment too large for 32-bit chunk offsets")
        # every chunk of carry + window, plus each class's padded tile
        chunks = (front + segment_bytes) // params.min_size + 2
        rows = _round_up(chunks + sum(B for _L, B in classes), 512)
        return cls(segment_bytes, front, scan_slice, n_slices,
                   scanner._k_cap(scan_slice), size, classes, rows)

    def blocks(self, n: int) -> List[int]:
        """Upload block sizes for a window of ``n`` bytes: descending
        powers of two, at most one short tail (< BLOCK_MIN) left over."""
        out = []
        size = 1 << (self.segment_bytes.bit_length() - 1)
        while size >= BLOCK_MIN:
            while n >= size:
                out.append(size)
                n -= size
            size >>= 1
        return out

    def block_sizes(self) -> List[int]:
        out = [BLOCK_MIN]
        while out[-1] * 2 <= self.segment_bytes:
            out.append(out[-1] * 2)
        return out


# --- device programs: all shapes fixed by the geometry ---------------------

@functools.partial(jax.jit, static_argnames=("front",),
                   donate_argnames=("prev",))
def _next_resident(prev: jnp.ndarray, end: jnp.ndarray, carry: jnp.ndarray,
                   *, front: int) -> jnp.ndarray:
    """The next segment's buffer: zeros, with the ``carry`` bytes that
    end at ``prev[end]`` right-aligned against the window."""
    slot = jax.lax.dynamic_slice(prev, (end - front,), (front,))
    keep = jnp.arange(front, dtype=jnp.int32) >= front - carry
    slot = jnp.where(keep, slot, jnp.uint8(0))
    return jnp.concatenate(
        [slot, jnp.zeros(prev.shape[0] - front, dtype=jnp.uint8)])


@functools.partial(jax.jit, donate_argnames=("buf",))
def _put_block(buf: jnp.ndarray, block: jnp.ndarray,
               pos: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dynamic_update_slice(buf, block, (pos,))


@functools.partial(jax.jit, static_argnames=("size",))
def _resident_slice(buf: jnp.ndarray, start: jnp.ndarray,
                    *, size: int) -> jnp.ndarray:
    return jax.lax.dynamic_slice(buf, (start,), (size,))


class ResidentStream:
    """The device half of one streamed file: the resident buffer, where
    the carry lies in it, and the counters the report reads (bytes
    uploaded and carried, windows, digest tiles by class)."""

    def __init__(self, params: CDCParams, scanner: TpuCdcScanner,
                 segment_bytes: int):
        self.params = params
        self.geo = Geometry.of(params, scanner, segment_bytes)
        warm(self.geo)
        self.buf = jnp.zeros(self.geo.size, dtype=jnp.uint8)
        self.window = 0  # bytes of the window now resident
        self.carry = 0   # bytes of carry in front of it

    # --- upload ------------------------------------------------------------

    def load(self, window: memoryview, carry: int) -> None:
        """Make ``window`` resident behind the last ``carry`` bytes of
        what was resident before.  Returns when the bytes are in HBM, so
        nothing on the device still reads the caller's buffer."""
        geo = self.geo
        if len(window) > geo.segment_bytes:
            raise ValueError("read() returned more than it was asked for")
        if self.window or self.carry:
            self.buf = _next_resident(
                self.buf, np.int32(geo.front + self.window), np.int32(carry),
                front=geo.front)
            obs_profile.stream_bytes("carried", carry)
        host = np.frombuffer(window, dtype=np.uint8)
        blocks, pos = [], 0
        for size in geo.blocks(len(host)):
            blocks.append(jax.device_put(host[pos:pos + size]))
            pos += size
        if pos < len(host):
            tail = np.zeros(BLOCK_MIN, dtype=np.uint8)
            tail[:len(host) - pos] = host[pos:]
            blocks.append(jax.device_put(tail))
        pos = geo.front
        for block in blocks:
            self.buf = _put_block(self.buf, block, np.int32(pos))
            pos += block.shape[0]
        self.buf.block_until_ready()
        # on the CPU backend a device_put of aligned host memory aliases
        # it: the blocks must be dead before the caller lets go of the
        # window (the runtime drops its hold at the next dispatch)
        for block in blocks:
            block.delete()
        self.window, self.carry = len(host), carry
        obs_profile.stream_bytes("uploaded", pos - geo.front)
        obs_profile.stream_segment()

    # --- scan --------------------------------------------------------------

    def scan(self) -> list:
        """Scan the resident window, one ``_scan_segment`` launch per
        slice: ``(outputs, n_valid, overflowed)`` each, for
        :meth:`candidates`.  A slice is launched when the one before has
        run (its count is read), so one launch's temporaries (1.1 GiB at
        128 MiB) are in HBM at a time."""
        geo, p = self.geo, self.params
        scanned = []
        for k in range(-(-self.window // geo.scan_slice)):
            n_valid = min(geo.scan_slice, self.window - k * geo.scan_slice)
            ext = _resident_slice(
                self.buf, np.int32(geo.front - _HALO + k * geo.scan_slice),
                size=_HALO + geo.scan_slice)
            out = _scan_segment(
                ext, jnp.int32(n_valid), jnp.uint32(p.mask_s),
                jnp.uint32(p.mask_l), k_cap=geo.k_cap)
            obs_profile.dispatch("scan", actual_bytes=n_valid,
                                 padded_bytes=geo.scan_slice)
            obs_profile.dispatch("select", actual_bytes=n_valid,
                                 padded_bytes=geo.scan_slice)
            # the first download: int() waits for the program
            scanned.append((out, n_valid, int(out[3]) > geo.k_cap))
        return scanned

    def candidates(self, scanned: list, window: memoryview,
                   tail: bytes) -> Tuple[np.ndarray, np.ndarray]:
        """Candidate positions of the window, relative to its start:
        ``(pos_l, is_s)``.  A slice whose sparse capacity overflowed
        (adversarial data) is rescanned by the numpy oracle from the
        host's view, ``tail`` being the bytes in front of the window."""
        geo, p = self.geo, self.params
        all_pos, all_s = [], []
        for k, (out, n_valid, overflow) in enumerate(scanned):
            base = k * geo.scan_slice
            if overflow:
                seg = window[base:base + n_valid]
                halo = bytes(window[max(0, base - _HALO):base]) if base \
                    else tail
                h = gear_hashes_np(seg, halo)
                pos = np.nonzero((h & np.uint32(p.mask_l)) == 0)[0].astype(
                    np.int64)
                is_s = (h[pos] & np.uint32(p.mask_s)) == 0
                pos = pos + base
                obs_profile.stream_bytes("host_assembled", n_valid)
            else:
                widx, wl, ws, _count = out
                pos, is_s = _decode_words(widx, wl, ws, geo.k_cap, base)
            all_pos.append(pos)
            all_s.append(is_s)
        if not all_pos:
            return np.empty(0, dtype=np.int64), np.empty(0, dtype=bool)
        return np.concatenate(all_pos), np.concatenate(all_s)

    # --- gather + digest ---------------------------------------------------

    def tile_rows(self, offs: np.ndarray, lens: np.ndarray):
        """Chunks as offsets from the resident carry's first byte and
        lengths.  Returns the ``(2, rows)`` meta array (buffer offsets,
        lengths), the tiles ``(start, B, L, bytes)`` and each chunk's
        row.  A class's chunks fill its tiles in order: its rows are
        consecutive and only its last tile is padded."""
        geo = self.geo
        limits = np.array([L * CHUNK_LEN for L, _B in geo.classes])
        cls = np.searchsorted(limits, lens, side="left")
        if lens.size and int(cls.max()) >= len(limits):
            raise ValueError("chunk longer than max_size")
        meta = np.zeros((2, geo.rows), dtype=np.int32)
        row_of = np.empty(len(lens), dtype=np.int64)
        tiles = []
        start = 0
        for j, (L, B) in enumerate(geo.classes):
            idxs = np.nonzero(cls == j)[0]
            n = len(idxs)
            meta[0, start:start + n] = geo.front - self.carry + offs[idxs]
            meta[1, start:start + n] = lens[idxs]
            row_of[idxs] = start + np.arange(n)
            sizes = lens[idxs]
            for pos in range(0, n, B):
                tiles.append((start + pos, B, L,
                              int(sizes[pos:pos + B].sum())))
            start += _round_up(n, B)
        return meta, tiles, row_of

    def digest(self, meta: np.ndarray, tiles: list,
               row_of: np.ndarray) -> List[bytes]:
        """Upload the rows, run every tile against the resident buffer,
        download the accumulator: each chunk's 32-byte digest."""
        meta_d = jax.device_put(meta)
        obs_profile.stream_bytes("uploaded", meta.nbytes)
        acc = jnp.zeros((self.geo.rows, 8), dtype=jnp.uint32)
        for start, B, L, actual in tiles:
            acc = _gather_digest(self.buf, meta_d, np.int32(start), acc,
                                 B=B, L=L)
            padded = B * L * CHUNK_LEN
            obs_profile.dispatch("gather", actual_bytes=actual,
                                 padded_bytes=padded)
            obs_profile.dispatch("digest", actual_bytes=actual,
                                 padded_bytes=padded)
            obs_profile.stream_digest_tile(L, actual, padded)
        raw = np.asarray(acc).astype("<u4").tobytes()
        return [raw[32 * r:32 * r + 32] for r in row_of.tolist()]


@functools.lru_cache(maxsize=None)
def warm(geo: Geometry) -> None:
    """Run every program of the route once at this geometry's shapes, so
    that a later file of any length, and a night with any chunk counts,
    compiles nothing.  Once per process and geometry: the first streamed
    file pays it, inside whatever the caller counts as set-up."""
    buf = jnp.zeros(geo.size, dtype=jnp.uint8)
    buf = _next_resident(buf, np.int32(geo.front), np.int32(0),
                         front=geo.front)
    sizes = geo.block_sizes()
    zeros = np.zeros(sizes[-1], dtype=np.uint8)
    for size in sizes:
        buf = _put_block(buf, jax.device_put(zeros[:size]),
                         np.int32(geo.front))
    ext = _resident_slice(buf, np.int32(geo.front - _HALO),
                          size=_HALO + geo.scan_slice)
    _scan_segment(ext, jnp.int32(0), jnp.uint32(0), jnp.uint32(0),
                  k_cap=geo.k_cap)
    meta_d = jax.device_put(np.zeros((2, geo.rows), dtype=np.int32))
    acc = jnp.zeros((geo.rows, 8), dtype=jnp.uint32)
    for L, B in geo.classes:
        acc = _gather_digest(buf, meta_d, np.int32(0), acc, B=B, L=L)
    acc.block_until_ready()
