"""ChunkerBackend: one dedup-pipeline contract, CPU and TPU executions.

``BASELINE.json`` pins the seam: a backend turns raw bytes into chunk
manifests (cut points + BLAKE3 fingerprints); everything above it — snapshot
builder, packfiles, peer exchange — is backend-agnostic.  The reference has
only the sequential CPU form (``dir_packer.rs:246-311``); here:

* :class:`CpuBackend` — the numpy oracle pipeline.
* :class:`TpuBackend` — device gear-scan (:mod:`.cdc_tpu`) + batched
  device BLAKE3 (:mod:`.blake3_tpu`).  Files are processed as batches so
  fingerprinting amortizes into a few bucketed compiles.
* :func:`select_backend` — picks TPU when an accelerator is attached,
  otherwise CPU; both produce bit-identical manifests, so the choice is
  pure policy.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Callable, Iterable, List, Optional, Sequence

import numpy as np

from .. import defaults
from ..erasure import gf_cpu
from ..erasure.stripe import Stripe
from ..obs import profile as obs_profile
from ..obs import trace as obs_trace
from .blake3_cpu import blake3_many
from .blake3_tpu import blake3_many_tpu
from .cdc_cpu import chunk_stream as chunk_stream_cpu
from .cdc_cpu import cuts_to_chunks, select_cuts
from .cdc_tpu import TpuCdcScanner
from .gear import GEAR_WINDOW, CDCParams


@dataclass(frozen=True)
class ChunkRef:
    """One chunk of one stream: location + fingerprint."""

    offset: int
    length: int
    hash: bytes


class ChunkerBackend:
    """Contract: ``manifest(data) -> [ChunkRef...]``, batched over streams."""

    name = "abstract"

    def chunk(self, data) -> List[tuple]:
        raise NotImplementedError

    def digest_many(self, datas: Sequence[bytes]) -> List[bytes]:
        raise NotImplementedError

    # --- erasure coding (erasure/; same routing pattern as digest_many:
    # the numpy oracle is the default, TpuBackend overrides with the
    # batched device kernel, and both are bit-identical) ------------------

    def encode_shards(self, stripes, m: int):
        """Reed-Solomon parity: (B, k, L) data shards -> (B, m, L)."""
        stripes = np.asarray(stripes, dtype=np.uint8)
        b, k, ln = stripes.shape
        if m == 0 or b == 0:
            return np.zeros((b, m, ln), dtype=np.uint8)
        parity_rows = gf_cpu.generator_matrix(k, m)[k:]
        return np.stack([gf_cpu.gf_matmul(parity_rows, s) for s in stripes])

    def decode_shards(self, stripes, k: int, m: int, present):
        """Recover data shards from survivors: ``stripes`` is (B, k, L)
        with rows ordered by the sorted ``present`` indices."""
        stripes = np.asarray(stripes, dtype=np.uint8)
        if stripes.shape[0] == 0:
            return stripes
        cols = sorted(set(int(i) for i in present))
        rec = gf_cpu.decode_matrix(k, m, cols)[:, cols]
        return np.stack([gf_cpu.gf_matmul(rec, s) for s in stripes])

    def encode_stripe(self, data: bytes, k: int, m: int,
                      missing: Sequence[int] = (),
                      rand=os.urandom) -> Stripe:
        """The send stage's executor-thread half of one sealed packfile:
        its k + m shard ``containers``, coded when this returns, and
        ``challenge_tables()`` for the audit tables of the ``missing``
        shards, the ones about to be placed (nonces and windows from
        ``rand``).  Here the host composition over ``encode_shards`` and
        ``digest_many``; :class:`TpuBackend` keeps the packfile on the
        device instead.  Byte-identical containers either way."""
        return Stripe(data, k, m, self, missing, rand)

    def manifest_many(self, streams: Sequence[bytes]) -> List[List[ChunkRef]]:
        """Chunk + fingerprint a batch of streams in one pipeline pass.

        Dispatch accounting (obs/profile.py, exact on the CPU fallback):
        one scan + one select per stream, one gather per stream that
        produced chunks, one batched digest per call with pieces."""
        all_chunks = []  # (stream_idx, offset, length)
        pieces = []
        for i, data in enumerate(streams):
            n = len(data)
            obs_profile.dispatch("scan", actual_bytes=n, padded_bytes=n)
            obs_profile.dispatch("select", actual_bytes=n, padded_bytes=n)
            gathered = 0
            for off, ln in self.chunk(data):
                all_chunks.append((i, off, ln))
                pieces.append(bytes(data[off:off + ln]))
                gathered += ln
            if gathered:
                obs_profile.dispatch("gather", actual_bytes=gathered,
                                     padded_bytes=gathered)
        if pieces:
            total = sum(len(p) for p in pieces)
            obs_profile.dispatch("digest", actual_bytes=total,
                                 padded_bytes=total)
        digests = self.digest_many(pieces)
        out: List[List[ChunkRef]] = [[] for _ in streams]
        for (i, off, ln), h in zip(all_chunks, digests):
            out[i].append(ChunkRef(offset=off, length=ln, hash=h))
        return out

    def manifest(self, data) -> List[ChunkRef]:
        return self.manifest_many([data])[0]

    def prepare_batches(self, batches: Iterable[Sequence[int]],
                        dedup) -> None:
        """The lengths of the files of every pack batch a backup is about
        to hand to :meth:`manifest_many_classified`, before the first
        (an iterable the packer cuts from its scan of the tree: read it
        only to use it).  A backend that compiles programs per shape
        compiles them side by side here (:class:`TpuBackend`); the
        others have nothing to do."""

    def manifest_many_classified(self, streams: Sequence[bytes], dedup):
        """Manifest + dedup-classify one batch in a single call.

        Returns ``(manifests, hints)`` where ``hints`` aligns with the
        flattened refs (row-major over streams) — the packer's dup-hint
        contract.  Base backends run the two passes back to back against
        ``dedup.classify_insert``; :class:`TpuBackend` overrides with the
        mesh pipeline, which hands digests to the sharded table on device
        mid-manifest."""
        out = self.manifest_many(streams)
        hashes = [r.hash for refs in out for r in refs]
        if hashes:
            obs_profile.dispatch("index", actual_bytes=32 * len(hashes),
                                 padded_bytes=32 * len(hashes))
        return out, dedup.classify_insert(hashes)

    def manifest_stream(self, read: Callable[[int], bytes],
                        segment_bytes: int = 256 * 1024 * 1024,
                        emit: Optional[Callable] = None) -> List[ChunkRef]:
        """Chunk + fingerprint a stream without holding it in memory.

        ``read(n)`` returns up to ``n`` bytes ('' at EOF).  Works because a
        CDC cut depends only on bytes up to the cut: chunking a prefix gives
        final chunks except the last (whose end might be EOF-forced), which
        is carried into the next segment.  Bit-identical to chunking the
        whole stream at once.  ``emit(ref, chunk)`` fires per final chunk
        as soon as it is fingerprinted (lets the caller pack blobs
        incrementally); the returned list is the full manifest.  ``chunk``
        is a read-only view into the segment's buffer, not a copy: slicing
        a segment into ~3,600 ``bytes`` cost 0.05 or 0.8 s a backup with
        the allocator's mood (PERF.md section 6, PR 25), and most chunks
        are duplicates whose bytes nobody reads.  A caller that keeps a
        chunk past the call copies it (``bytes(chunk)``).

        One span per step per segment, never one per chunk (``stream.*``):
        obs/profile.py ``STREAM_GROUPS`` folds them into host preparation,
        device wait and emit.  Backends with nothing to make resident
        (CPU, native) run this; :class:`TpuBackend` has its own.
        """
        out: List[ChunkRef] = []
        carry = b""
        base = 0  # absolute offset of carry[0]
        while True:
            with obs_trace.span("stream.read"):
                segment = read(segment_bytes)
                eof = not segment
                buf = carry + segment
            chunks = self.chunk(buf)
            obs_profile.dispatch("scan", actual_bytes=len(buf),
                                 padded_bytes=len(buf))
            obs_profile.dispatch("select", actual_bytes=len(buf),
                                 padded_bytes=len(buf))
            if eof:
                final, carry, next_base = chunks, b"", base
            elif len(chunks) > 1:
                final = chunks[:-1]
                last_off = chunks[-1][0]
                carry, next_base = buf[last_off:], base + last_off
            else:
                # single chunk that may still grow: carry everything
                final, carry, next_base = [], buf, base
            with obs_trace.span("stream.slice"):
                view = memoryview(buf)
                pieces = [view[off:off + ln] for off, ln in final]
            if pieces:
                total = sum(len(p) for p in pieces)
                obs_profile.dispatch("gather", actual_bytes=total,
                                     padded_bytes=total)
                obs_profile.dispatch("digest", actual_bytes=total,
                                     padded_bytes=total)
            digests = self._stream_digest(pieces)
            with obs_trace.span("stream.emit"):
                for h, (off, ln), data in zip(digests, final, pieces):
                    ref = ChunkRef(offset=base + off, length=ln, hash=h)
                    out.append(ref)
                    if emit is not None:
                        emit(ref, data)
            base = next_base
            if eof:
                break
        return out

    def _stream_digest(self, pieces: Sequence[bytes]) -> List[bytes]:
        """``manifest_stream``'s digest step (``digest_many`` unless a
        backend times it apart from its other callers)."""
        return self.digest_many(pieces)


class CpuBackend(ChunkerBackend):
    name = "cpu"

    def __init__(self, params: Optional[CDCParams] = None):
        self.params = params or CDCParams()

    def chunk(self, data):
        return chunk_stream_cpu(data, self.params)

    def digest_many(self, datas):
        return blake3_many(datas)


class NativeBackend(ChunkerBackend):
    """Host fast path: the C pipeline (``native/cdc_blake3.c``) via ctypes.

    Same bit-exact manifests as :class:`CpuBackend` (tests pin C vs spec
    oracle) at ~30x the numpy oracle's throughput — the engine's default
    on hosts without an accelerator.  Raises
    :class:`backuwup_tpu.native.NativeUnavailable` at construction when no
    C toolchain/library is present; callers fall back to CpuBackend.
    """

    name = "native"

    def __init__(self, params: Optional[CDCParams] = None):
        from .. import native
        self.params = params or CDCParams()
        native.load()  # raises NativeUnavailable without a toolchain
        self._native = native

    def chunk(self, data):
        return self._native.chunk_native(data, self.params)

    def digest_many(self, datas):
        return [self._native.blake3_native(bytes(d)) for d in datas]

    def manifest_many(self, streams):
        out = []
        for data in streams:
            chunks, digests = self._native.manifest_native(
                bytes(data), self.params)
            # the C pipeline fuses the whole chain into one host call per
            # stream: it counts once under every stage
            n = len(data)
            for stage in ("scan", "select", "gather", "digest"):
                obs_profile.dispatch(stage, actual_bytes=n, padded_bytes=n)
            out.append([ChunkRef(offset=off, length=ln, hash=h)
                        for (off, ln), h in zip(chunks, digests)])
        return out


class TpuBackend(ChunkerBackend):
    """Device-resident execution: ``manifest_many`` stages each batch into
    HBM once and runs scan -> cut -> HBM-to-HBM chunk gather -> batched
    digest (:meth:`DevicePipeline.manifest_batch`) — no per-chunk host
    slicing; ``manifest_stream`` does the same for one resident segment
    of a long file at a time; ``encode_stripe`` keeps a sealed packfile
    resident while the send stage codes and audits it.  ``chunk``/
    ``digest_many`` remain as the op-level seams the parity tests pin
    (``digest_many`` also serves the seal-time audit table and repair)."""

    name = "tpu"

    def __init__(self, params: Optional[CDCParams] = None):
        _install_jax_hooks()
        self.params = params or CDCParams()
        self._scanner = TpuCdcScanner(self.params)
        self._pipeline = None
        self._mesh = None
        self._mesh_axis = "data"

    @property
    def pipeline(self):
        if self._pipeline is None:
            from .pipeline import CHUNK_LEN, DevicePipeline
            l_bucket = max(16, -(-self.params.max_size // CHUNK_LEN))
            self._pipeline = DevicePipeline(self.params, l_bucket=l_bucket,
                                            mesh=self._mesh,
                                            mesh_axis=self._mesh_axis)
        return self._pipeline

    def attach_mesh(self, mesh, axis: str = "data") -> None:
        """Share the dedup mesh with the manifest pipeline so the
        classified path shards its batches over the same axis and can
        hand digest accumulators to the table without leaving the mesh
        (the engine calls this when it builds its MeshDedupIndex)."""
        self._mesh = mesh
        self._mesh_axis = axis
        if self._pipeline is not None and self._pipeline.mesh is None:
            self._pipeline.mesh = mesh
            self._pipeline.mesh_axis = axis

    def chunk(self, data):
        return self._scanner.chunk_stream(data)

    def digest_many(self, datas):
        return blake3_many_tpu(datas)

    def manifest_stream(self, read, segment_bytes: int = 256 * 1024 * 1024,
                        emit: Optional[Callable] = None) -> List[ChunkRef]:
        """The base method's contract (same chunks, same digests, the
        same read-only views handed to ``emit``), with the segment
        resident on the device (:mod:`.resident`): each window of the
        stream is uploaded once, straight from a view of what ``read``
        returned, scanned where it lies, and its chunks are gathered and
        digested out of HBM.  Only candidate words and 32 bytes a chunk
        come down; only ``(offset, length)`` rows go up a second time.

        The host keeps positions and views.  The carry, the last and
        still open chunk of a segment, moves to the front of the next
        resident buffer by a device slice, and its cut candidates are
        kept, so no byte is scanned or uploaded twice.  At EOF the carry
        is the last chunk as it stands.  The one chunk a segment that
        starts in the carry and ends in the new window is assembled on
        the host (at most ``max_size`` bytes).

        Spans, one per step per segment: ``stream.read``,
        ``stream.upload``, ``cdc.scan``, ``cdc.decode``,
        ``stream.select_cuts``, ``stream.slice`` (views; inside it
        ``stream.boundary_chunk``, the host's one copy), ``blake3.stage``
        (chunk rows), ``blake3.digest``, ``stream.emit``.
        """
        from .resident import ResidentStream

        dev = ResidentStream(self.params, self._scanner, segment_bytes)
        out: List[ChunkRef] = []
        base = 0  # absolute offset of the carry's first byte
        carry = memoryview(b"")  # host view of the carry's bytes
        # the carry's cut candidates, relative to its first byte
        pos_l = np.empty(0, dtype=np.int64)
        is_s = np.empty(0, dtype=bool)
        while True:
            with obs_trace.span("stream.read"):
                window = memoryview(read(segment_bytes)).toreadonly()
            c, w = len(carry), len(window)
            eof = w == 0
            if eof:
                # nothing cut the carry while more could follow: it is
                # the stream's last chunk, and already resident
                final = [(0, c)] if c else []
                origin = dev.carry + dev.window - c
            else:
                with obs_trace.span("stream.upload"):
                    dev.load(window, c)
                with obs_trace.span("cdc.scan"):
                    scanned = dev.scan()
                with obs_trace.span("cdc.decode"):
                    pos_w, is_s_w = dev.candidates(
                        scanned, window, bytes(carry[1 - GEAR_WINDOW:]))
                    pos_l = np.concatenate([pos_l, pos_w + c])
                    is_s = np.concatenate([is_s, is_s_w])
                with obs_trace.span("stream.select_cuts"):
                    chunks = cuts_to_chunks(select_cuts(
                        pos_l[is_s], pos_l, c + w, self.params))
                # the last chunk's end is the buffer's, not a cut: carry
                # it (all of the buffer, if it is the only one)
                final, last_off = chunks[:-1], chunks[-1][0]
                origin = 0
                keep = pos_l >= last_off
                pos_l, is_s = pos_l[keep] - last_off, is_s[keep]
            with obs_trace.span("stream.slice"):
                pieces = [_host_view(carry, window, off, ln)
                          for off, ln in final]
                if not eof:
                    carry = _host_view(carry, window, last_off,
                                       c + w - last_off)
            digests = []
            if final:
                with obs_trace.span("blake3.stage"):
                    offs, lens = np.array(final, dtype=np.int64).T
                    meta, tiles, row_of = dev.tile_rows(offs + origin, lens)
                with obs_trace.span("blake3.digest"):
                    digests = dev.digest(meta, tiles, row_of)
            with obs_trace.span("stream.emit"):
                for h, (off, ln), data in zip(digests, final, pieces):
                    ref = ChunkRef(offset=base + off, length=ln, hash=h)
                    out.append(ref)
                    if emit is not None:
                        emit(ref, data)
            if eof:
                return out
            base += last_off

    def encode_shards(self, stripes, m):
        from ..erasure import rs_tpu
        return rs_tpu.encode_stripes(stripes, m)

    def decode_shards(self, stripes, k, m, present):
        from ..erasure import rs_tpu
        return rs_tpu.decode_stripes(stripes, k, m, present)

    def encode_stripe(self, data, k, m, missing=(), rand=os.urandom):
        """The base method's contract with the packfile resident on the
        device (:mod:`..erasure.resident`): uploaded once, RS-coded and
        its shard rows digested where they lie, its audit windows
        gathered out of HBM; two waits for the device a packfile.  The
        route's programs are warmed at the first stripe, for every
        length a sealed packfile has (the target size plus the blob
        that crossed it)."""
        from ..erasure import resident as stripe_resident

        bucket = stripe_resident.shard_bucket(
            gf_cpu.shard_len(len(data), k))
        if bucket is None:  # past k x the largest digest class
            return super().encode_stripe(data, k, m, missing, rand)
        stripe_resident.warm(
            k, m, defaults.PACKFILE_TARGET_SIZE + self.params.max_size)
        return stripe_resident.ResidentStripe(data, k, m, bucket,
                                              missing, rand)

    def manifest_many(self, streams):
        results, _flags = self.pipeline.manifest_batch(streams)
        out = []
        for chunks, digests in results:
            out.append([
                ChunkRef(offset=off, length=ln, hash=digests[k].tobytes())
                for k, (off, ln) in enumerate(chunks)])
        return out

    def _rides_mesh_of(self, dedup) -> bool:
        """True where ``dedup`` hands off on the device and on the
        pipeline's mesh (the pipeline takes the index's mesh if it has
        none yet)."""
        pipe = self.pipeline
        if getattr(dedup, "classify_dispatch", None) is None:
            return False
        if pipe.mesh is None:
            pipe.mesh = dedup.mesh
            pipe.mesh_axis = dedup.axis
        return pipe.mesh is dedup.mesh and pipe.mesh_axis == dedup.axis

    def prepare_batches(self, batches, dedup):
        if self._rides_mesh_of(dedup):
            self.pipeline.compile_side_by_side(batches, emit_queries=True)

    def manifest_many_classified(self, streams, dedup):
        """Mesh-sharded manifest with the on-device dedup handoff: the
        digest accumulator feeds ``ShardedDedupIndex.insert_device``
        without a host round trip, and the downloaded found-flags become
        the packer's dup hints via ``resolve_hints``.  Falls back to the
        two-pass base when ``dedup`` has no device handoff or rides a
        different mesh than the pipeline."""
        pipe = self.pipeline
        if not self._rides_mesh_of(dedup):
            return super().manifest_many_classified(streams, dedup)
        results, rowflags = pipe.manifest_batch(streams, dedup)
        out = []
        hashes: List[bytes] = []
        raw: List[Optional[bool]] = []
        for (chunks, digests), fl in zip(results, rowflags):
            refs = [ChunkRef(offset=off, length=ln,
                             hash=digests[k].tobytes())
                    for k, (off, ln) in enumerate(chunks)]
            out.append(refs)
            for k, ref in enumerate(refs):
                hashes.append(ref.hash)
                raw.append(None if fl is None else bool(fl[k]))
        undecided = sum(1 for f in raw if f is None)
        obs_profile.batch_chunks("device_decided", len(raw) - undecided)
        obs_profile.batch_chunks("host_resolved", undecided)
        with obs_trace.span("batch.resolve"):
            return out, dedup.resolve_hints(hashes, raw)


def _host_view(carry: memoryview, window: memoryview, off: int,
               ln: int) -> memoryview:
    """Read-only view of ``ln`` bytes at ``off`` of carry + window: a
    slice of either where the span lies within one, else the two parts
    joined (the one copy a segment costs the host)."""
    c = len(carry)
    if off >= c:
        return window[off - c:off - c + ln]
    if off + ln <= c:
        return carry[off:off + ln]
    with obs_trace.span("stream.boundary_chunk"):
        obs_profile.stream_bytes("host_assembled", ln)
        return memoryview(b"".join((carry[off:], window[:off + ln - c])))


_jax_hooks_installed = False


def _install_jax_hooks() -> None:
    """Once per process, when the first TPU backend is made: spans enter
    ``jax.profiler.TraceAnnotation`` (obs/trace.py; the host half of a
    profiler capture then carries them on the device trace's clock), and
    every backend compile is counted under its function's name
    (``bkw_jit_compile_seconds``, obs/profile.py)."""
    global _jax_hooks_installed
    if _jax_hooks_installed:
        return
    _jax_hooks_installed = True
    import jax
    import jax.monitoring

    def on_duration(event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            fun = str(kw.get("fun_name", "?"))
            if fun.startswith("jit(") and fun.endswith(")"):
                fun = fun[4:-1]
            obs_profile.jit_compiled(fun, secs)

    obs_trace.set_annotator(jax.profiler.TraceAnnotation)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def _accelerator_attached() -> bool:
    """A backend that fails to initialise raises here: on a machine with
    a chip that fault must not turn into a quiet host-only run."""
    import jax
    return jax.default_backend() != "cpu"


def select_backend(prefer: Optional[str] = None,
                   params: Optional[CDCParams] = None) -> ChunkerBackend:
    """``prefer`` in {"cpu", "native", "tpu", None}; None = auto-detect
    (TPU if an accelerator is attached, else the native C pipeline, else
    the numpy oracle)."""
    if prefer == "cpu":
        return CpuBackend(params)
    if prefer == "native":
        return NativeBackend(params)
    if prefer == "tpu":
        return TpuBackend(params)
    if _accelerator_attached():
        return TpuBackend(params)
    from .. import native
    try:
        return NativeBackend(params)
    except native.NativeUnavailable:
        return CpuBackend(params)
