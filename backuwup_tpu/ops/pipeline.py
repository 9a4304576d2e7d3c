"""Device-resident dedup pipeline: scan+select -> gather chunks -> digest.

Composes the TPU kernels into the full chunk+hash step that the engine's
batched route runs and ``chip_smoke.py`` drives.  One driver takes a pack
batch to the chip (:meth:`DevicePipeline.manifest_segments_mesh`): each
bucketed batch is ONE shard-mapped program over the pipeline's mesh (a
mesh of one device on one chip) —

1. fused gear-hash scan + on-device FastCDC cut selection of a resident
   byte batch (:func:`..ops.cdc_tpu.scan_select_batch`),
2. chunk meta derived on device and every chunk's 1 KiB leaves digested
   out of HBM in one flat leaf pool (:mod:`.digest_pool`),
3. with a device index, the digest accumulator handed to it on the mesh
   —

and the only downloads are the packed cut list, the digests and the
found-flags.  Beside it: the host-tiled path
(:meth:`DevicePipeline.manifest_segments`: cuts come down, ``(B, L*1024)``
digest tiles of B in {8, 32, 128} rows and pow2 leaf buckets go up), the
exact fall-back for a shard whose pool overflowed, whose
``_gather_digest`` tiles are also the streaming route's digest
(:mod:`.resident`).

Shapes are closed sets so the whole pipeline compiles a small closed set
of programs (first-run cost, then the persistent cache) — data-dependent
shapes were the round-2 throughput killer: every novel (B, L) combo paid
a 20-40 s XLA compile.

The reference executes the same logical pipeline one byte / one chunk at a
time on the CPU (``dir_packer.rs:246-311``).
"""

from __future__ import annotations

import functools
from collections import Counter, OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import journal as obs_journal
from ..obs import profile as obs_profile
from ..obs import trace as obs_trace
from .blake3_tpu import (
    _batch_bucket,
    _leaf_bucket,
    blake3_many_tpu,
    digest_padded,
)
from .cdc_cpu import chunk_stream as chunk_stream_cpu
from .cdc_tpu import (
    _HALO,
    TpuCdcScanner,
    _round_up,
    _scan_segment,
    _segment_bucket,
    scan_select_batch,
)
from .gear import CDCParams

CHUNK_LEN = 1024

# cap on one vmapped-scan dispatch (rows x row bytes)
_SCAN_DISPATCH_BYTES = 128 * 1024 * 1024
# a long stream's leaf-pool digest is compiled for its length rounded up
# to this step (at most four programs between the scan segment and the
# packer's batch_bytes)
_POOL_STREAM_STEP = 32 * 1024 * 1024

# programs of the batched route this process has compiled ahead (see
# DevicePipeline.compile_side_by_side); process-wide, as jit's caches are
_RAN: set = set()
# their kinds (the first element of a key), the longest to compile first
_COMPILE_ORDER = ("mesh", "pool", "scan", "tiny")


def _pad_to(arr: np.ndarray, n: int) -> np.ndarray:
    out = np.zeros(n, dtype=arr.dtype)
    out[:len(arr)] = arr
    return out


def _blake3_host(data: bytes) -> bytes:
    from .blake3_cpu import blake3_hash
    return blake3_hash(data)


def _decode_cut_row(row: np.ndarray):
    """One packed scan+select row -> (overflow, [(offset, length)...]).

    Shared by every collector so the cut decode exists exactly once.
    Vectorized: the python per-chunk loop dominated many-small-file
    batches.
    """
    overflow, n_cuts = int(row[0]), int(row[1])
    if overflow:
        return True, []
    ends = row[2:2 + n_cuts].astype(np.int64)
    offs = np.empty(n_cuts, dtype=np.int64)
    if n_cuts:
        offs[0] = 0
        np.add(ends[:-1], 1, out=offs[1:])
    lens = ends - offs + 1
    return False, list(zip(offs.tolist(), lens.tolist()))


def _async_to_host(arr) -> None:
    """Start a device->host copy in the background when the runtime
    supports it; ``np.asarray`` later completes (or performs) it."""
    try:
        arr.copy_to_host_async()
    except AttributeError:
        pass


def _row_tiles(count: int, cap: int = 128) -> List[int]:
    """Decompose a chunk count into digest tile heights from
    {512, 128, 32, 8} clamped to ``cap`` (the pipeline's ``b_bucket``).

    Big tiles amortize the per-op overhead of the unrolled BLAKE3 program
    (small-lane dispatches are latency-bound); the closed set keeps the
    compiled-program universe finite.  Padding waste is bounded: at most
    one partially-filled tile per size class.  The 512 tier only engages
    when the pipeline raises ``b_bucket`` (small-chunk configs whose
    (B=128, L<=256) tiles are tiny-lane and dispatch-bound).
    """
    out: List[int] = []
    rem = count
    if cap >= 512:
        while rem >= 512:
            out.append(512)
            rem -= 512
        if rem >= 256:
            out.append(512)
            rem = 0
    if cap >= 128:
        while rem >= 128:
            out.append(128)
            rem -= 128
        if rem >= 64:
            out.append(128)
            rem = 0
    if cap >= 32:
        while rem >= 32:
            out.append(32)
            rem -= 32
        if rem >= 16:
            out.append(32)
            rem = 0
    while rem > 0:
        out.append(8)
        rem -= 8
    return out


@functools.partial(jax.jit, static_argnames=("B", "L"),
                   donate_argnames=("acc",))
def _gather_digest(flat: jnp.ndarray, meta: jnp.ndarray, start: jnp.ndarray,
                   acc: jnp.ndarray, *, B: int, L: int) -> jnp.ndarray:
    """Fused HBM gather + batched BLAKE3 for one (B, L) chunk tile.

    ``meta`` is the (3, total) i32 array of [offsets; lengths; starts]
    covering every tile of the batch — uploaded once; each tile call
    slices its ``[start, start+B)`` window on device (``start`` is traced,
    so varying tile layouts never recompile — only (B, L) combinations
    do), gathers the chunk spans out of the resident ``flat`` stream,
    digests, and writes the root chaining values into the donated ``acc``
    at the same window.  One fixed-shape ``acc`` download then returns
    every tile's digests — no variable-shape concatenation, no per-tile
    transfers.
    """
    offs = jax.lax.dynamic_slice(meta[0], (start,), (B,))
    lens = jax.lax.dynamic_slice(meta[1], (start,), (B,))
    span = L * CHUNK_LEN

    def one(off):
        return jax.lax.dynamic_slice(flat, (off,), (span,))

    buf = jax.vmap(one)(offs)
    root = digest_padded(buf, lens, L=L)
    return jax.lax.dynamic_update_slice(acc, root, (start, jnp.int32(0)))


@functools.partial(jax.jit, static_argnames=("l_bucket",))
def gather_chunks(stream: jnp.ndarray, offsets: jnp.ndarray,
                  *, l_bucket: int) -> jnp.ndarray:
    """(B,) chunk offsets -> (B, l_bucket*1024) u8 padded chunk buffers.

    Chunks are sliced from the resident stream; callers mask true lengths
    via the ``lens`` argument of :func:`digest_padded`, so over-read bytes
    beyond each chunk are ignored by the masked BLAKE3 scan.
    """
    span = l_bucket * CHUNK_LEN

    def one(off):
        return jax.lax.dynamic_slice(stream, (off,), (span,))

    return jax.vmap(one)(offsets.astype(jnp.int32))


class DevicePipeline:
    """Chunk + fingerprint segments that already live (or land) in HBM."""

    def __init__(self, params: Optional[CDCParams] = None,
                 l_bucket: int = 3072, b_bucket: int = 128,
                 mesh=None, mesh_axis: str = "data"):
        self.params = params or CDCParams()
        self.scanner = TpuCdcScanner(self.params)
        if self.params.max_size > l_bucket * CHUNK_LEN:
            raise ValueError("l_bucket smaller than max chunk size")
        self.l_bucket = l_bucket
        self.b_bucket = b_bucket
        # mesh for the shard-mapped driver (manifest_segments_mesh);
        # lazily defaults to a single axis over every local device
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        # per-device peak bytes in flight across the mesh dispatch window
        self.mesh_hbm_high_water: dict = {}
        self._nv_cache: OrderedDict = OrderedDict()
        from . import digest_pool
        from .blake3_tpu import pallas_digest_available
        from .scan_fused import fused_scan_available
        # the platform sets the kernels' forms (a TPU: the Mosaic scan and
        # leaf kernels, checked here or raising; the CPU configuration:
        # their XLA forms), and the leaf pool is checked in that form
        self.fused = fused_scan_available()
        self.pallas_digest = pallas_digest_available()
        digest_pool._pool_digest_probe(self.pallas_digest)
        # read, with the two above and scan_fused._V2_SELECTED, by
        # benchmark/deployment.py's ``kernels`` (ROADMAP D16)
        self.pool_digest = True

    # --- scan + select (device) -------------------------------------------

    def _caps(self, padded: int) -> Tuple[int, int, int]:
        """(s_cap, l_cap, cut_cap) for a padded row length.

        Candidate capacity is 4x the expectation: every gather/search in
        the parallel cut selection scales with ``l_cap``, and 16x slack
        measured ~3x slower end-to-end.  Density is binomial
        (sigma/mu ~= 1/sqrt(mu)), so 4x overflows only on adversarial
        gear-aligned data — which already needs the oracle fallback.
        """
        p = self.params
        l_cap = max(512, _round_up(4 * max(1, padded >> p.mask_l_bits), 512))
        cut_cap = padded // p.min_size + 1
        return l_cap, l_cap, cut_cap

    def _nv_device(self, nv: np.ndarray) -> jnp.ndarray:
        nv = np.asarray(nv, dtype=np.int32)
        key = nv.tobytes()
        nv_d = self._nv_cache.get(key)
        if nv_d is None:
            # LRU: evict the coldest entry; the old wholesale clear()
            # dropped hot entries (e.g. the full-batch nv that recurs on
            # every steady-state dispatch) on every 65th distinct shape
            while len(self._nv_cache) >= 64:
                self._nv_cache.popitem(last=False)
            nv_d = self._nv_cache[key] = jnp.asarray(nv)
        else:
            self._nv_cache.move_to_end(key)
        return nv_d

    def scan_select_dispatch(self, buf_d: jnp.ndarray,
                             nv: np.ndarray) -> jnp.ndarray:
        """Dispatch the fused scan+select; returns the device packed-cuts
        array and starts its async download."""
        p = self.params
        padded = int(buf_d.shape[1]) - _HALO
        s_cap, l_cap, cut_cap = self._caps(padded)
        with obs_trace.span("pipeline.scan_select_dispatch"):
            packed_d = scan_select_batch(
                buf_d, self._nv_device(nv),
                min_size=p.min_size, desired_size=p.desired_size,
                max_size=p.max_size, mask_s=p.mask_s, mask_l=p.mask_l,
                s_cap=s_cap, l_cap=l_cap, cut_cap=cut_cap, fused=self.fused)
        _async_to_host(packed_d)
        actual = int(np.asarray(nv, dtype=np.int64).sum())
        padded_total = int(buf_d.shape[0]) * padded
        obs_profile.dispatch("scan", actual_bytes=actual,
                             padded_bytes=padded_total)
        obs_profile.dispatch("select", actual_bytes=actual,
                             padded_bytes=padded_total)
        return packed_d

    def scan_select_collect(self, packed_d: jnp.ndarray, buf_d: jnp.ndarray,
                            nv: np.ndarray) -> List[List[tuple]]:
        """Packed device cuts -> per-row [(offset, length)...] chunk lists.

        Overflowed rows (sparse capacity exceeded — adversarial data) are
        re-chunked with the CPU oracle to stay bit-identical."""
        with obs_trace.span("pipeline.cut_collect"):
            packed = np.asarray(packed_d)
        nv = np.asarray(nv, dtype=np.int32)
        per_row: List[List[tuple]] = []
        for r in range(packed.shape[0]):
            overflow, chunks = _decode_cut_row(packed[r])
            if overflow:
                row_bytes = bytes(np.asarray(
                    buf_d[r, _HALO:_HALO + int(nv[r])]))
                per_row.append(chunk_stream_cpu(row_bytes, self.params))
            else:
                per_row.append(chunks)
        return per_row

    # --- gather + digest (device) -----------------------------------------

    def digest_dispatch(self, buf_d: jnp.ndarray,
                        per_row: List[List[tuple]]):
        """Dispatch gather+digest tiles for one resident batch; returns an
        opaque pending handle for :meth:`digest_collect`."""
        row = int(buf_d.shape[1])
        span_max = self.l_bucket * CHUNK_LEN
        flat = jnp.pad(buf_d.reshape(-1), (0, span_max))
        groups: dict = {}
        for r, chunks in enumerate(per_row):
            base = r * row + _HALO
            for ci, (off, ln) in enumerate(chunks):
                groups.setdefault(self._chunk_bucket(ln), []).append(
                    (base + off, ln, r, ci))
        if not groups:
            return None
        tiles: List[tuple] = []  # (start, Bb, Lb, [(r, ci)...])
        offs_parts: List[np.ndarray] = []
        lens_parts: List[np.ndarray] = []
        start = 0
        for Lb, items in sorted(groups.items()):
            pos = 0
            for Bb in _row_tiles(len(items), self.b_bucket):
                part = items[pos:pos + Bb]
                pos += Bb
                o = np.zeros(Bb, dtype=np.int32)
                ln_arr = np.zeros(Bb, dtype=np.int32)
                for q, (off, ln, _r, _ci) in enumerate(part):
                    o[q] = off
                    ln_arr[q] = ln
                offs_parts.append(o)
                lens_parts.append(ln_arr)
                tiles.append((start, Bb, Lb,
                              [(r, ci) for _o, _l, r, ci in part]))
                start += Bb
        # one meta upload; per-tile starts are sliced from it on device so
        # tile layout never recompiles _gather_digest, and the total is
        # padded to a power of two so neither does meta's shape
        starts = np.array([st for st, _b, _l, _t in tiles], dtype=np.int32)
        total = 256
        while total < max(start, len(starts)):
            total *= 2
        meta = jnp.asarray(np.stack([
            _pad_to(np.concatenate(offs_parts), total),
            _pad_to(np.concatenate(lens_parts), total),
            _pad_to(starts, total)]))
        acc = jnp.zeros((total, 8), dtype=jnp.uint32)
        with obs_trace.span("pipeline.digest_dispatch"):
            for i, (_st, Bb, Lb, _tags) in enumerate(tiles):
                acc = _gather_digest(flat, meta, meta[2, i], acc,
                                     B=Bb, L=Lb)
                tile_actual = int(lens_parts[i].sum())
                tile_padded = Bb * Lb * CHUNK_LEN
                obs_profile.dispatch("gather", actual_bytes=tile_actual,
                                     padded_bytes=tile_padded)
                obs_profile.dispatch("digest", actual_bytes=tile_actual,
                                     padded_bytes=tile_padded)
        _async_to_host(acc)
        return acc, tiles

    def digest_collect(self, pending,
                       per_row: List[List[tuple]]
                       ) -> List[Tuple[List[tuple], np.ndarray]]:
        """Pending digest handle -> per-row (chunks, digests)."""
        if pending is None:
            return [(chunks, np.zeros((0, 32), dtype=np.uint8))
                    for chunks in per_row]
        acc, tiles = pending
        with obs_trace.span("pipeline.digest_collect"):
            allcv = np.asarray(acc)
        dig8 = np.ascontiguousarray(allcv.astype("<u4")).view(
            np.uint8).reshape(-1, 32)
        digests_per_row = [np.zeros((len(c), 32), dtype=np.uint8)
                           for c in per_row]
        for st, _Bb, _Lb, tags in tiles:
            for q, (r, ci) in enumerate(tags):
                digests_per_row[r][ci] = dig8[st + q]
        return [(per_row[r], digests_per_row[r])
                for r in range(len(per_row))]

    # --- composed drivers --------------------------------------------------

    def manifest_segments(self, segments):
        """The host-tiled path over resident ``(buf_d, nv)`` batches
        (generator): each batch's cut list comes down before its digest
        tiles are laid out on the host and dispatched, two round trips a
        batch.  The mesh driver's exact fall-back for a shard whose pool
        overflowed; yields each batch's per-row results in order."""
        for buf_d, nv in segments:
            per_row = self.scan_select_collect(
                self.scan_select_dispatch(buf_d, nv), buf_d, nv)
            yield self.digest_collect(
                self.digest_dispatch(buf_d, per_row), per_row)

    def _ensure_mesh(self):
        """The driver's mesh: the one given or attached, else one axis
        over every local device (the engine's dedup mesh shape), taken
        for the call and not kept: a device index met later still brings
        its own (``TpuBackend._rides_mesh_of``)."""
        if self.mesh is not None:
            return self.mesh
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()), (self.mesh_axis,))

    def _row_sharding(self):
        """Rows over the mesh axis: how a batch goes to the mesh driver."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return NamedSharding(self._ensure_mesh(), P(self.mesh_axis))

    def _mesh_program(self, buf_sh, nv_sh, emit_queries: bool,
                      lower: bool = False):
        """The shard-mapped manifest program over one sharded
        ``(B, _HALO + padded)`` batch; the batch's shape, its sharding's
        mesh and this pipeline's selections name the program.
        ``lower``: the arguments are shapes, and the program is traced
        and lowered for them, not run."""
        from .digest_pool import leaf_capacity
        from .manifest_device import scan_digest_batch_pool_mesh, tier_plan

        p = self.params
        mesh = buf_sh.sharding.mesh
        bs = int(buf_sh.shape[0]) // int(mesh.devices.size)
        padded = int(buf_sh.shape[1]) - _HALO
        s_cap, l_cap, cut_cap = self._caps(padded)
        return scan_digest_batch_pool_mesh(
            buf_sh, nv_sh, mesh=mesh, axis=self.mesh_axis,
            min_size=p.min_size, desired_size=p.desired_size,
            max_size=p.max_size, mask_s=p.mask_s, mask_l=p.mask_l,
            s_cap=s_cap, l_cap=l_cap, cut_cap=cut_cap, fused=self.fused,
            leaf_cap=leaf_capacity(bs * padded, bs * cut_cap),
            tiers=tier_plan(p, bs * padded, bs),
            pallas_digest=self.pallas_digest, emit_queries=emit_queries,
            lower=lower)

    def manifest_segments_mesh(self, segments, strict_overflow: bool = False,
                               window: int = 4, dedup=None):
        """The pipelined batch driver (generator): a zero-round-trip
        manifest, data-parallel over the row axis with ``shard_map`` (on
        one chip, a mesh of one device).

        Each batch is padded to a row multiple of the mesh size with
        zero rows (``nv=0`` rows produce no cuts), resharded ``P(axis)``,
        and run through
        :func:`backuwup_tpu.ops.manifest_device.scan_digest_batch_pool_mesh`
        — per-shard leaf pools, per-shard tier cascades, and per-shard
        overflow flags, so a pool overflow re-runs ONLY the affected
        shard's rows on the host-tiled path.  The only downloads are the
        packed cuts + digest accumulator, whose async copies overlap
        later batches' compute.  ``window`` bounds batches in
        flight; per-device bytes in flight are tracked against
        ``bkw_mesh_hbm_highwater_bytes`` and ``mesh_hbm_high_water``.
        A row whose sparse candidate capacity overflowed re-chunks on
        the CPU oracle; ``strict_overflow`` turns either fall-back into
        an error (a parity test must not compare oracle with oracle).

        With ``dedup`` (a ``MeshDedupIndex``) each batch's digest
        accumulator is handed to the sharded dedup table ON DEVICE
        (``classify_dispatch``) — zero per-batch host round trips — and
        the generator yields ``(rows, flags)`` where ``flags[r]`` is the
        per-chunk device found-vector (truthy = key resident before that
        batch's insert) or ``None`` when the device could not classify
        the row (shard fallback, candidate overflow, lost lanes);
        ``MeshDedupIndex.resolve_hints`` turns the raw flags into final
        dup hints.  Without ``dedup`` it yields plain rows.
        """
        sharding = self._row_sharding()
        D = int(sharding.mesh.devices.size)
        it = iter(segments)
        pending: deque = deque()
        state = {"in_flight": 0}

        def dispatch():
            for buf, nv in it:
                B0 = int(buf.shape[0])
                row = int(buf.shape[1])
                nv = np.asarray(nv, dtype=np.int32)
                B = -(-max(B0, 1) // D) * D
                if B != B0:
                    if isinstance(buf, np.ndarray):
                        buf = np.pad(buf, ((0, B - B0), (0, 0)))
                    else:
                        buf = jnp.pad(buf, ((0, B - B0), (0, 0)))
                    nv = np.pad(nv, (0, B - B0))
                bs = B // D
                padded = row - _HALO
                cut_cap = self._caps(padded)[2]
                with obs_trace.span("pipeline.mesh_dispatch"):
                    rets = self._mesh_program(
                        jax.device_put(buf, sharding),
                        jax.device_put(nv, sharding), dedup is not None)
                    if dedup is not None:
                        packed, acc, ovf, q = rets
                        found_d, lost_d = dedup.classify_dispatch(q)
                    else:
                        packed, acc, ovf = rets
                        found_d = lost_d = None
                for a in (packed, acc, ovf, found_d, lost_d):
                    if a is not None:
                        _async_to_host(a)
                # accounting: ONE launch per stage (the shard_map program)
                # in the unlabeled families, plus each device's share in
                # the mesh families — per-shard actual bytes come from its
                # contiguous nv slice, padded bytes are its row span
                actual = int(nv.sum(dtype=np.int64))
                for stage in ("scan", "select", "gather", "digest"):
                    obs_profile.dispatch(stage, actual_bytes=actual,
                                         padded_bytes=B * padded)
                per_dev = nv.reshape(D, bs).sum(axis=1, dtype=np.int64)
                for d in range(D):
                    for stage in ("scan", "select", "gather", "digest"):
                        obs_profile.dispatch_device(
                            stage, d, actual_bytes=int(per_dev[d]),
                            padded_bytes=bs * padded)
                # per-device bytes in flight: row buffer + packed cuts +
                # digest accumulator + ovf flag (+ dedup query/value lanes)
                foot = (bs * row + bs * (2 + cut_cap) * 4
                        + bs * cut_cap * 32 + 4)
                if dedup is not None:
                    foot += bs * cut_cap * (16 + 4)
                state["in_flight"] += foot
                for d in range(D):
                    obs_profile.hbm_high_water(d, state["in_flight"])
                    if state["in_flight"] > self.mesh_hbm_high_water.get(d, 0):
                        self.mesh_hbm_high_water[d] = state["in_flight"]
                pending.append((buf, nv, B0, cut_cap, foot,
                                packed, acc, ovf, found_d, lost_d))
                return True
            return False

        for _ in range(window):
            dispatch()
        while pending:
            (buf, nv, B0, cut_cap, foot, packed_d, acc_d, ovf_d,
             found_d, lost_d) = pending.popleft()
            dispatch()
            with obs_trace.span("pipeline.mesh_collect"):
                packed = np.asarray(packed_d)
                ovf = np.asarray(ovf_d)  # (D,) per-shard flags
            state["in_flight"] -= foot
            B = packed.shape[0]
            bs = B // D
            if ovf.any() and strict_overflow:
                raise RuntimeError("pool capacity overflow in mesh manifest")
            bad = set(np.nonzero(ovf)[0].tolist())
            dig8 = None
            if len(bad) < D:
                acc = np.asarray(acc_d)
                dig8 = np.ascontiguousarray(acc.astype("<u4")).view(
                    np.uint8).reshape(B, cut_cap, 32)
            found = lost = None
            if found_d is not None:
                with obs_trace.span("pipeline.mesh_collect"):
                    found = np.asarray(found_d).reshape(B, cut_cap)
                    lost = np.asarray(lost_d).reshape(B, cut_cap)
                n_real = int(packed[packed[:, 0] == 0, 1].sum())
                obs_profile.dispatch("index", actual_bytes=32 * n_real,
                                     padded_bytes=32 * B * cut_cap)
                for d in range(D):
                    sl = packed[d * bs:(d + 1) * bs]
                    obs_profile.dispatch_device(
                        "index", d,
                        actual_bytes=32 * int(sl[sl[:, 0] == 0, 1].sum()),
                        padded_bytes=32 * bs * cut_cap)
                # tiered front (dedupstore.TieredDedupIndex): each
                # collected batch is one promotion-clock window
                note = getattr(dedup, "note_window", None)
                if note is not None:
                    note(n_real, int((lost != 0).sum()))
            hb = buf if isinstance(buf, np.ndarray) else None
            out: List = [None] * B
            flags: List = [None] * B
            with obs_trace.span("batch.decode"):
                for s in range(D):
                    r0, r1 = s * bs, (s + 1) * bs
                    if s in bad:
                        # per-shard fallback: ONLY this shard's rows re-run
                        # on the host-tiled path (the tentpole's whole point:
                        # adversarial data costs one shard, not the batch)
                        if hb is None:
                            hb = np.asarray(buf)
                        obs_profile.mesh_host_rerun(
                            "shard", max(0, min(r1, B0) - r0))
                        (sub,) = self.manifest_segments(
                            [(jnp.asarray(hb[r0:r1]), nv[r0:r1])])
                        for r in range(r0, min(r1, B0)):
                            out[r] = sub[r - r0]
                        continue
                    for r in range(r0, min(r1, B0)):
                        overflow, chunks = _decode_cut_row(packed[r])
                        if overflow:
                            if strict_overflow:
                                raise RuntimeError(
                                    "candidate overflow in scan+select")
                            obs_profile.mesh_host_rerun("row", 1)
                            if hb is None:
                                hb = np.asarray(buf)
                            rowb = bytes(hb[r, _HALO:_HALO + int(nv[r])])
                            chunks = chunk_stream_cpu(rowb, self.params)
                            digs = np.stack([np.frombuffer(
                                _blake3_host(rowb[o:o + ln]), dtype=np.uint8)
                                for o, ln in chunks]) if chunks else \
                                np.zeros((0, 32), dtype=np.uint8)
                            out[r] = (chunks, digs)
                            continue
                        out[r] = (chunks, dig8[r, :len(chunks)].copy())
                        if found is not None \
                                and not lost[r, :len(chunks)].any():
                            flags[r] = found[r, :len(chunks)] != 0
            if dedup is not None:
                yield out[:B0], flags[:B0]
            else:
                yield out[:B0]

    def _route(self, sizes):
        """One batch's stream indices by length: (empty, tiny, long,
        {padded length: [idx...]} for the resident batch drivers)."""
        p = self.params
        empty: List[int] = []
        tiny: List[int] = []
        long: List[int] = []
        groups: dict = {}
        for i, n in enumerate(sizes):
            if n == 0:
                empty.append(i)
            elif n <= p.min_size:
                # sub-min streams are always exactly one chunk (select_cuts
                # first rule), so the scan is skipped entirely — many tiny
                # files cost one batched digest, not 64 KiB-padded scans
                tiny.append(i)
            elif n > self.scanner.segment_size:
                long.append(i)
            else:
                groups.setdefault(_segment_bucket(n), []).append(i)
        return empty, tiny, long, groups

    def _first_use_jobs(self, sizes, emit_queries: bool) -> dict:
        """{program key: a thunk that traces and lowers it} for every
        program one batch of streams of these ``sizes`` runs: a digest
        batch a leaf class of tiny files, two scans and a leaf pool a
        long stream, the manifest program a bucket shape
        (``emit_queries``: with the hand-off to a device index).  Each
        lowers the jitted callable the data goes through, with the same
        static arguments, dtypes and shardings, so the batch's own call
        finds it compiled."""
        p = self.params
        spec = jax.ShapeDtypeStruct
        _empty, tiny, long, groups = self._route(sizes)
        jobs: dict = {}
        for L, n in Counter(_leaf_bucket(sizes[i]) for i in tiny).items():
            B = _batch_bucket(n)
            jobs["tiny", B, L] = lambda B=B, L=L: digest_padded.lower(
                spec((B, L * CHUNK_LEN), jnp.uint8), spec((B,), jnp.int32),
                L=L)
        seg = self.scanner.segment_size
        for n in {sizes[i] for i in long}:
            for part in {seg, n % seg} - {0}:
                padded = _segment_bucket(part)
                jobs["scan", padded] = \
                    lambda padded=padded: _scan_segment.lower(
                        spec((_HALO + padded,), jnp.uint8),
                        spec((), jnp.int32), spec((), jnp.uint32),
                        spec((), jnp.uint32),
                        k_cap=self.scanner._k_cap(padded))
            step = -(-n // _POOL_STREAM_STEP) * _POOL_STREAM_STEP
            jobs["pool", step] = lambda step=step: self._pool_program(
                spec((step + CHUNK_LEN,), jnp.uint8),
                *[spec((step // p.min_size + 1,), jnp.int32)] * 2,
                lower=True)
        sharding = self._row_sharding()
        D = int(sharding.mesh.devices.size)
        for padded, _part, B in self._batch_shapes(groups):
            shape = (-(-B // D) * D, _HALO + padded)
            jobs["mesh", shape, emit_queries] = \
                lambda shape=shape: self._mesh_program(
                    spec(shape, jnp.uint8, sharding=sharding),
                    spec(shape[:1], jnp.int32, sharding=sharding),
                    emit_queries, lower=True)
        return jobs

    def compile_side_by_side(self, batches, emit_queries: bool) -> None:
        """Compile every program that batches of streams of these sizes
        (``batches``: a list of lists of lengths) need and this process
        has not compiled: traced and lowered here, one after the other
        (tracing holds the interpreter lock, and threads that trace side
        by side only take turns at it, at twice the cost), each handed
        to a thread of its own as soon as it is lowered, where XLA
        compiles it outside the lock.  The programs compile side by side
        instead of in the order the backup reaches them, and the batch's
        own call finds them in ``jit``'s caches.  Nothing to do once
        they are compiled."""
        jobs: dict = {}
        for sizes in batches:
            jobs.update(self._first_use_jobs(sizes, emit_queries))
        # the longest to compile first: the last one lowered is the one
        # whose compile nothing overlaps
        todo = [job for key, job in sorted(
            jobs.items(), key=lambda kv: _COMPILE_ORDER.index(kv[0][0]))
            if key not in _RAN]
        if len(todo) > 1:
            with obs_trace.span("batch.compile"), \
                    ThreadPoolExecutor(len(todo)) as pool:
                failed = []
                futs = []
                for job in todo:
                    try:
                        futs.append(pool.submit(job().compile))
                    except Exception as e:  # noqa: BLE001
                        failed.append(e)
                failed += [e for e in (f.exception() for f in futs) if e]
                for e in failed:
                    # left to the batch itself, which meets the fault
                    # where its cause can be told
                    obs_journal.emit("compile_ahead_failed", error=repr(e))
        _RAN.update(jobs)

    def _manifest_prepass(self, streams, out: List) -> dict:
        """Route a stream batch: fills ``out`` for empty/tiny/long streams
        (the non-batched shapes) and returns the {padded_len: [idx...]}
        groups the resident batch drivers consume."""
        sizes = [len(s) for s in streams]
        empty, tiny, long, groups = self._route(sizes)
        for i in empty:
            out[i] = ([], np.zeros((0, 32), dtype=np.uint8))
        for i in long:
            # long stream: segmented device scan, then resident digest
            s, n = streams[i], sizes[i]
            with obs_trace.span("batch.long_stream"):
                chunks = self.scanner.chunk_stream(s)
                obs_profile.dispatch("scan", actual_bytes=n, padded_bytes=n)
                obs_profile.dispatch("select", actual_bytes=n,
                                     padded_bytes=n)
                dev = jnp.asarray(np.frombuffer(bytes(s), dtype=np.uint8))
                out[i] = (chunks, self.digest_chunks(dev, chunks))
        obs_profile.batch_files("long", len(long))
        obs_profile.batch_files("tiny", len(tiny))
        obs_profile.batch_files("bucketed",
                                sum(len(g) for g in groups.values()))
        if tiny:
            with obs_trace.span("batch.tiny_digest"):
                digs = blake3_many_tpu([streams[i] for i in tiny])
            # a launch a leaf class, rows padded to a power of two: what
            # ``bucketed_batches`` uploads
            classes = Counter(_leaf_bucket(sizes[i]) for i in tiny)
            obs_profile.dispatch(
                "digest", count=len(classes),
                actual_bytes=sum(sizes[i] for i in tiny),
                padded_bytes=sum(_batch_bucket(n) * L * CHUNK_LEN
                                 for L, n in classes.items()))
            for i, d in zip(tiny, digs):
                out[i] = ([(0, sizes[i])],
                          np.frombuffer(d, dtype=np.uint8).reshape(1, 32))
        return groups

    @staticmethod
    def _batch_shapes(groups: dict):
        """(padded length, stream indices, rows) of every resident batch
        the grouped streams make, in dispatch order."""
        for padded, idxs in sorted(groups.items()):
            max_rows = max(1, _SCAN_DISPATCH_BYTES // (_HALO + padded))
            # pow2 row padding, clamped by the dispatch budget (largest
            # pow2 <= max_rows): a lone 128 MiB stream must not balloon
            # to 8 identical rows, and a full part must not double past
            # the budget — so slice by the pow2 cap itself
            b_cap = 1 << (max_rows.bit_length() - 1)
            for s0 in range(0, len(idxs), b_cap):
                part = idxs[s0:s0 + b_cap]
                B = min(8, b_cap)
                while B < len(part):
                    B *= 2
                yield padded, part, B

    def _bucketed_batches(self, streams, groups: dict, batch_rows: deque):
        """Generator of (host buf, nv) resident batches for the grouped
        streams; appends each batch's stream indices to ``batch_rows``."""
        for padded, part, B in self._batch_shapes(groups):
            with obs_trace.span("batch.stage"):
                buf = np.zeros((B, _HALO + padded), dtype=np.uint8)
                nv = np.zeros(B, dtype=np.int32)
                for r, i in enumerate(part):
                    d = np.frombuffer(bytes(streams[i]), dtype=np.uint8)
                    buf[r, _HALO:_HALO + len(d)] = d
                    nv[r] = len(d)
            batch_rows.append(part)
            yield buf, nv

    def manifest_batch(self, streams, dedup=None):
        """Chunk + fingerprint a batch of independent streams, resident.

        Each stream's bytes are staged into HBM exactly once: streams are
        bucketed by padded length and each bucketed batch runs the mesh
        driver's one program (scan, select, leaf-pool digest, and with
        ``dedup`` the on-device hand-off to the index); at most
        ``window`` batches, each bounded by the dispatch budget, are in
        flight.  Returns ``(out, flags)``: a ``(chunks, digests)`` pair a
        stream, bit-identical to the CPU oracle pipeline, and stream i's
        per-chunk device found-vector, or ``None`` where the device did
        not classify it (no ``dedup``; empty/tiny/long streams, shard
        fallbacks, lost lanes — the host authority resolves those via
        ``MeshDedupIndex.resolve_hints``).
        """
        out: List[Optional[Tuple[List[tuple], np.ndarray]]] = [None] * len(streams)
        flags: List[Optional[np.ndarray]] = [None] * len(streams)
        groups = self._manifest_prepass(streams, out)
        batch_rows: deque = deque()
        gen = self._bucketed_batches(streams, groups, batch_rows)
        for got in self.manifest_segments_mesh(gen, dedup=dedup):
            rows, rowflags = got if dedup is not None else (got, None)
            for r, i in enumerate(batch_rows.popleft()):
                out[i] = rows[r]
                if rowflags is not None:
                    flags[i] = rowflags[r]
        return out, flags

    def _chunk_bucket(self, n_bytes: int) -> int:
        """Smallest leaf bucket (power of two, >=16 chunks) holding a chunk;
        bounds padding waste to <2x instead of all-chunks-at-max."""
        need = max(1, -(-n_bytes // CHUNK_LEN))
        b = 16
        while b < need:
            b *= 2
        return min(b, self.l_bucket) if need <= self.l_bucket else need

    def _pool_program(self, flat, offs, lens, lower: bool = False):
        """The leaf pool over one padded stream (``_POOL_STREAM_STEP``
        bytes a step, ``CHUNK_LEN`` of slack) and its chunk table; the
        two shapes and this pipeline's selections name the program.
        ``lower``: the arguments are shapes, and the program is traced
        and lowered for them, not run."""
        from .digest_pool import leaf_capacity, pool_digest
        from .manifest_device import tier_plan

        padded = int(flat.shape[0]) - CHUNK_LEN
        return (pool_digest.lower if lower else pool_digest)(
            flat, offs, lens,
            leaf_cap=leaf_capacity(padded, int(offs.shape[0])),
            tiers=tier_plan(self.params, padded, 1),
            pallas=self.pallas_digest)

    def _digest_chunks_pool(self, stream: jnp.ndarray,
                            chunks: List[tuple]) -> Optional[np.ndarray]:
        """The chunks of one resident stream through the leaf pool
        (:func:`..digest_pool.pool_digest`), as the mesh program digests
        a batch: ONE program a stream-length step where the class tiles
        below are one for every (rows, leaves) pair a file's chunk
        lengths happen to fill (six for a 136 MiB file at 1 MiB chunks,
        each a first backup's compile).  The stream is padded to the
        next multiple of ``_POOL_STREAM_STEP`` so that files of
        different lengths share programs.  None where the tier cascade
        overflowed (lengths far from the expected histogram): the caller
        falls back to the tiles, bit-exact either way."""
        n = int(stream.shape[0])
        padded = -(-n // _POOL_STREAM_STEP) * _POOL_STREAM_STEP
        cap = padded // self.params.min_size + 1
        if len(chunks) > cap:
            return None
        meta = np.zeros((2, cap), dtype=np.int32)
        meta[:, :len(chunks)] = np.asarray(chunks, dtype=np.int32).T
        acc, ovf = self._pool_program(
            jnp.pad(stream, (0, padded + CHUNK_LEN - n)),
            jnp.asarray(meta[0]), jnp.asarray(meta[1]))
        for stage in ("gather", "digest"):
            obs_profile.dispatch(stage, actual_bytes=int(meta[1].sum()),
                                 padded_bytes=padded)
        if int(np.asarray(ovf)[0]):
            return None
        got = np.asarray(acc)[:len(chunks)]
        return np.ascontiguousarray(got.astype("<u4")).view(
            np.uint8).reshape(len(chunks), 32)

    def digest_chunks(self, stream: jnp.ndarray, chunks: List[tuple]) -> np.ndarray:
        """Gather + digest chunk spans of a resident stream; (N, 32) u8:
        through the leaf pool, and where its tier cascade overflowed
        through (B, L) size tiles, so device work scales with actual
        bytes, not worst-case chunk size.
        """
        if not chunks:
            return np.zeros((0, 32), dtype=np.uint8)
        pooled = self._digest_chunks_pool(stream, chunks)
        if pooled is not None:
            return pooled
        # slack so the fixed-span gathers never clamp (dynamic_slice clips
        # out-of-range starts, which would shift data)
        stream = jnp.pad(stream, (0, self.l_bucket * CHUNK_LEN))
        out = np.zeros((len(chunks), 32), dtype=np.uint8)
        groups: dict = {}
        for i, (off, ln) in enumerate(chunks):
            groups.setdefault(self._chunk_bucket(ln), []).append(i)
        for L, idxs in sorted(groups.items()):
            pos = 0
            for bb in _row_tiles(len(idxs), self.b_bucket):
                part = idxs[pos:pos + bb]
                pos += bb
                offs = np.zeros(bb, dtype=np.int32)
                lens = np.zeros(bb, dtype=np.int32)
                for j, i in enumerate(part):
                    offs[j], lens[j] = chunks[i]
                buf = gather_chunks(stream, jnp.asarray(offs), l_bucket=L)
                root = digest_padded(buf.reshape(bb, L * CHUNK_LEN),
                                     jnp.asarray(lens), L=L)
                tile_actual = int(lens.sum())
                tile_padded = bb * L * CHUNK_LEN
                obs_profile.dispatch("gather", actual_bytes=tile_actual,
                                     padded_bytes=tile_padded)
                obs_profile.dispatch("digest", actual_bytes=tile_actual,
                                     padded_bytes=tile_padded)
                got = np.ascontiguousarray(np.asarray(root).astype("<u4"))
                got = got.view(np.uint8).reshape(bb, 32)
                for j, i in enumerate(part):
                    out[i] = got[j]
        return out
