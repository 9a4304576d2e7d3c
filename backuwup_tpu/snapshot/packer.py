"""Directory packer: filesystem tree -> content-addressed snapshot.

Re-designs ``client/src/backup/filesystem/dir_packer.rs``:

* Deepest-first directory walk (``browse_dir_tree``, ``dir_packer.rs:89-132``)
  so every child tree hash exists before its parent is built.
* Files are chunked + fingerprinted through a :class:`ChunkerBackend`
  (CPU oracle or the TPU kernels) — the batched analog of the reference's
  per-file FastCDC/blake3 hot loop (``:246-311``).  All files of one
  directory form one device batch.
* Tree nodes (``Tree`` wire blobs) carry name, metadata, and child hashes;
  nodes with more than TREE_MAX_CHILDREN children split into a
  ``next_sibling`` chain (``dir_packer.rs:35,313-363``), built back-to-front
  so each page embeds the following page's hash.
* The root tree's blob hash is the snapshot id (``dir_packer.rs:47-84``).
* Dedup: every blob (chunk or tree) is checked against the blob index
  before packing (``pack.rs:31-55``); duplicate data costs one hash lookup.
"""

from __future__ import annotations

import logging
import os
import time
from array import array
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Iterator, List, Optional, Tuple

from .. import defaults
from ..obs import metrics as obs_metrics
from ..obs import profile as obs_profile
from ..obs import trace as obs_trace
from ..ops.backend import ChunkerBackend
from ..ops.blake3_cpu import blake3_hash
from ..wire import Blob, BlobKind, Tree, TreeKind, TreeMetadata
from .blob_index import BlobIndex
from .packfile import PackfileWriter

_STAGE_SECONDS = obs_metrics.histogram(
    "bkw_pack_stage_seconds", "", ("stage",))  # declared in packfile.py


@dataclass
class PackStats:
    files: int = 0
    failed_files: int = 0
    dirs: int = 0
    bytes_read: int = 0
    chunks: int = 0
    chunks_deduped: int = 0
    bytes_deduped: int = 0
    dedup_divergences: int = 0
    # wall seconds inside the chunk+hash backend calls — with the
    # pipelined seal (packfile.py seal_workers) this stage overlaps the
    # seal/write/upload stages instead of summing with them.  Busy time
    # only: a streamed file's per-chunk emit (host index, seal queue,
    # writer, the pause behind the send buffer) is left out
    chunk_hash_s: float = 0.0


def _list_dir(path: str) -> Tuple[list, List[str], list, int]:
    """One ``os.scandir`` of ``path`` and one ``lstat`` a regular file,
    the only questions the packer asks the file system about a tree:
    ``(files, subdirs, links, failed)`` in name order (the order
    ``sorted(Path.iterdir())`` gives within one parent).  ``files`` are
    ``(entry, lstat result)`` of the regular files, ``subdirs`` the paths
    of the real directories, ``links`` the symlinks' entries, ``failed``
    the files that were gone or unreadable by their ``lstat``; anything
    else (FIFOs, sockets, devices) is skipped.  An ``os.DirEntry`` knows
    its kind from the directory's own record, so the ``lstat`` is the one
    call a file.  A directory that cannot be listed reads as empty."""
    files: list = []
    subdirs: List[str] = []
    links: list = []
    failed = 0
    try:
        with os.scandir(path) as it:
            entries = sorted(it, key=lambda e: e.name)
    except OSError:
        entries = []
    for entry in entries:
        try:
            if entry.is_file(follow_symlinks=False):
                try:
                    files.append((entry, entry.stat(follow_symlinks=False)))
                except OSError:
                    failed += 1
            elif entry.is_dir(follow_symlinks=False):
                subdirs.append(entry.path)
            elif entry.is_symlink():
                links.append(entry)
        except OSError:
            continue
    obs_profile.tree_scan(scandir_calls=1, lstat_calls=len(files) + failed)
    return files, subdirs, links, failed


@dataclass
class TreeScan:
    """What a backup knows of its tree before it packs it
    (:func:`scan_tree`): integers and directory paths, no ``stat``
    result and no file's path, so O(directories) + 8 bytes a file."""
    #: the directories breadth-first from the root, sorted by name
    #: within a parent; packed in reverse, children before parents
    dirs: List[str]
    #: the size estimate's sum (``Engine.estimate_size``)
    total_bytes: int
    #: a directory of ``dirs`` each: its regular files' lengths in name
    #: order
    file_sizes: List[array]

    def batches(self, batch_bytes: int) -> Iterator[List[int]]:
        """The file lengths of the pack batches to come, a list a batch
        (a directory's files up to ``batch_bytes``, as
        ``DirPacker._pack_files`` cuts them; a larger file is streamed),
        for ``ChunkerBackend.prepare_batches``.  A file that changes
        before it is read costs a compile in its batch, nothing else."""
        for lengths in self.file_sizes:
            sizes: List[int] = []
            pending = 0
            for n in lengths:
                if n > batch_bytes:
                    continue
                sizes.append(n)
                pending += n
                if pending >= batch_bytes:
                    yield sizes
                    sizes, pending = [], 0
            if sizes:
                yield sizes


def scan_tree(root: Path) -> TreeScan:
    """The first of the two looks a backup takes at its tree: a
    breadth-first :func:`_list_dir` walk from ``root``, once a backup
    (``Engine.estimate_size`` makes it in the ``backup.estimate`` phase
    and hands it to :meth:`DirPacker.pack`, which else makes its own).
    The second is :meth:`DirPacker.pack`'s own listing of a directory as
    it packs it, so that a file's times are taken next to its read.

    The estimate counts what ``os.walk`` with a ``stat()`` a file
    counted: every regular file, and for a symlink what it points to
    (one ``stat`` a symlink), unless that is a directory."""
    dirs = [os.fspath(Path(root))]
    file_sizes: List[array] = []
    total = files_seen = 0
    for d in dirs:
        files, subdirs, links, _failed = _list_dir(d)
        sizes = array("q", (st.st_size for _entry, st in files))
        file_sizes.append(sizes)
        dirs.extend(subdirs)
        total += sum(sizes)
        files_seen += len(sizes)
        for link in links:
            try:
                if not link.is_dir():
                    total += link.stat().st_size
            except OSError:
                pass
    obs_profile.tree_scan(dirs=len(dirs), files=files_seen)
    return TreeScan(dirs=dirs, total_bytes=total, file_sizes=file_sizes)


class DirPacker:
    def __init__(self, backend: ChunkerBackend, writer: PackfileWriter,
                 index: BlobIndex,
                 progress: Optional[Callable] = None,
                 batch_bytes: int = 256 * defaults.MiB,
                 should_pause: Optional[Callable] = None,
                 dedup_batch: Optional[Callable] = None,
                 dedup_index=None,
                 on_blob: Optional[Callable] = None):
        self.backend = backend
        self.writer = writer
        self.index = index
        self.progress = progress or (lambda **kw: None)
        self.batch_bytes = batch_bytes
        self.should_pause = should_pause or (lambda: None)
        # manifest hook: called (hash, size) for EVERY blob the snapshot
        # references — duplicates included — so the caller can record the
        # snapshot's full reachable-blob manifest (GC's mark source,
        # docs/lifecycle.md) without a second tree walk
        self.on_blob = on_blob
        # device dedup front.  ``dedup_index`` (a MeshDedupIndex) is the
        # full handle: pack batches then classify through the backend's
        # fused manifest+classify seam (on the TPU backend the digests
        # reach the sharded table without leaving the mesh).
        # ``dedup_batch`` is the narrower legacy hook (batched
        # classify+insert callable); None for both = host-only dedup.
        self.dedup_index = dedup_index
        if dedup_batch is None and dedup_index is not None:
            dedup_batch = dedup_index.classify_insert
        self.dedup_batch = dedup_batch
        self._device_sync: List[bytes] = []
        self.stats = PackStats()
        # lag-bounded incremental emission (docs/dataflow.md): deadline
        # for the next forced partial-packfile emission
        self._emit_deadline = time.monotonic() + defaults.PACK_EMIT_MAX_LAG_S

    # --- blob plumbing -----------------------------------------------------

    def _add_blob(self, blob_hash: bytes, kind: BlobKind, data: bytes,
                  dup_hint: Optional[bool] = None) -> None:
        """Dedup-then-pack one blob (pack.rs:31-55 semantics).

        ``dup_hint`` is the device table's classification when the blob was
        part of a batched classify.  The host index is the authority: on
        disagreement the host verdict wins and the event is logged loudly —
        device=dup/host=new is expected-by-design (astronomically rare
        128-bit truncation collisions in the device table's key prefix,
        see device_dedup.py), and degrading beats failing the whole backup.
        """
        if self.on_blob is not None:
            self.on_blob(bytes(blob_hash), len(data))
        host_dup = self.index.is_duplicate(blob_hash)
        if dup_hint is not None and dup_hint != host_dup:
            self.stats.dedup_divergences += 1
            logging.getLogger(__name__).warning(
                "device/host dedup divergence on %s: device=%s host=%s; "
                "using host verdict", bytes(blob_hash).hex(), dup_hint,
                host_dup)
        if dup_hint is None and self.dedup_batch is not None:
            # blob classified host-side only (tree node or streamed chunk):
            # sync it into the device table at the next batch boundary
            self._device_sync.append(bytes(blob_hash))
        if host_dup:
            if kind == BlobKind.FILE_CHUNK:
                # chunks only, as ``chunks`` counts: a tree node that was
                # there before is no deduplicated chunk
                self.stats.chunks_deduped += 1
                self.stats.bytes_deduped += len(data)
            return
        self.index.mark_queued(blob_hash)
        self.should_pause()
        # a streamed chunk arrives as a view into its segment's buffer:
        # only a new blob's bytes are copied out (no-op for ``bytes``)
        self.writer.add_blob(Blob(hash=blob_hash, kind=kind,
                                  data=bytes(data)))

    def _flush_device_sync(self) -> None:
        if self.dedup_batch is not None and self._device_sync:
            # a step of the pack thread's own (obs/profile.PACK_STEPS):
            # between the route's spans, with ``index.classify`` inside
            with obs_trace.span("pack.device_sync"):
                self.dedup_batch(self._device_sync)
            self._device_sync.clear()

    def _maybe_emit_partial(self) -> None:
        """Incremental emission instead of end-of-tree flush: blobs
        buffered below the packfile target size must not wait for
        ``pack()``'s final flush longer than PACK_EMIT_MAX_LAG_S — on a
        tree of many small directories that flush used to be the ONLY
        emission, so the wire idled for the whole walk.  The deadline
        re-arms whenever the writer is empty, so steady target-size
        emission never pays extra sub-target packfiles."""
        now = time.monotonic()
        if not self.writer.pending_blobs:
            self._emit_deadline = now + defaults.PACK_EMIT_MAX_LAG_S
            return
        if now >= self._emit_deadline:
            self.writer.emit_partial()
            self._emit_deadline = now + defaults.PACK_EMIT_MAX_LAG_S

    def _add_tree(self, tree: Tree) -> bytes:
        encoded = tree.encode_bytes()
        h = blake3_hash(encoded)
        self._add_blob(h, BlobKind.TREE, encoded)
        return h

    def _tree_with_split(self, kind: TreeKind, name: str, meta: TreeMetadata,
                         children: List[bytes]) -> bytes:
        """Build one logical node, splitting into a next_sibling chain at
        TREE_MAX_CHILDREN (dir_packer.rs:313-363); returns the head hash."""
        cap = defaults.TREE_MAX_CHILDREN
        pages = [children[i:i + cap] for i in range(0, len(children), cap)] or [[]]
        next_hash: Optional[bytes] = None
        for page in reversed(pages):
            next_hash = self._add_tree(Tree(
                kind=kind, name=name, metadata=meta, children=list(page),
                next_sibling=next_hash))
        return next_hash

    # --- file chunking (the TPU-batched hot path) --------------------------

    def _pack_files(self, files: List[Tuple[Path, os.stat_result]]
                    ) -> List[Optional[bytes]]:
        """Chunk+hash a directory's files, each with the ``lstat`` its
        listing took; returns each file's tree hash (None for files that
        vanished or failed to read)."""
        hashes: List[Optional[bytes]] = [None] * len(files)
        batch_idx: List[int] = []
        batch_data: List[bytes] = []
        batch_meta: List[TreeMetadata] = []

        to_read: List[tuple] = []

        def flush_batch():
            if to_read:
                with obs_trace.span("batch.read"):
                    for i, path, st in to_read:
                        try:
                            data = path.read_bytes()
                        except OSError:
                            self.stats.failed_files += 1
                            continue
                        self.stats.bytes_read += len(data)
                        batch_idx.append(i)
                        batch_data.append(data)
                        batch_meta.append(TreeMetadata(
                            size=len(data), mtime_ns=st.st_mtime_ns,
                            ctime_ns=st.st_ctime_ns))
                to_read.clear()
            if not batch_idx:
                return
            t0 = time.monotonic()
            hint_list = None
            if self.dedup_index is not None:
                # blobs classified host-side since the last batch (streamed
                # chunks, tree nodes) must reach the device table BEFORE
                # this batch is classified, or a re-occurrence of one of
                # them would read as device-new/host-dup and trip the
                # divergence guard in _add_blob
                self._flush_device_sync()
                # fused manifest+classify: on the TPU backend each digest
                # batch hands its accumulator to the sharded table on
                # device (zero per-batch host round trips); index-stage
                # dispatches are accounted inside the backend/driver
                with obs_trace.span("packer.manifest_many"):
                    manifests, hint_list = \
                        self.backend.manifest_many_classified(
                            batch_data, self.dedup_index)
            else:
                with obs_trace.span("packer.manifest_many"):
                    manifests = self.backend.manifest_many(batch_data)
            dt = time.monotonic() - t0
            self.stats.chunk_hash_s += dt
            _STAGE_SECONDS.observe(dt, stage="chunk_hash")
            total_refs = sum(len(m) for m in manifests)
            if total_refs and hint_list is None:
                # one batched dedup classification per pack batch, whether
                # the device table or the host blob index answers it
                obs_profile.dispatch("index", actual_bytes=32 * total_refs,
                                     padded_bytes=32 * total_refs)
            hints = iter(())
            if hint_list is not None:
                hints = iter(hint_list)
            elif self.dedup_batch is not None:
                # legacy hook path (no full index handle): same sync-then-
                # classify ordering, one device round trip for the batch
                self._flush_device_sync()
                hints = iter(self.dedup_batch(
                    [ref.hash for m in manifests for ref in m]))
            with obs_trace.span("batch.emit"):
                for i, data, meta, manifest in zip(batch_idx, batch_data,
                                                   batch_meta, manifests):
                    for ref in manifest:
                        self.stats.chunks += 1
                        self._add_blob(
                            ref.hash, BlobKind.FILE_CHUNK,
                            data[ref.offset:ref.offset + ref.length],
                            dup_hint=next(hints, None))
                    hashes[i] = self._tree_with_split(
                        TreeKind.FILE, files[i][0].name, meta,
                        [ref.hash for ref in manifest])
                    self.stats.files += 1
                    self.progress(file=str(files[i][0]), bytes=len(data))
            self._flush_device_sync()
            self._maybe_emit_partial()
            batch_idx.clear()
            batch_data.clear()
            batch_meta.clear()

        pending = 0
        for i, (path, st) in enumerate(files):
            if st.st_size > self.batch_bytes:
                # oversized file: stream it so memory stays bounded
                try:
                    hashes[i] = self._pack_file_streaming(path, st)
                except OSError:
                    self.stats.failed_files += 1
                continue
            to_read.append((i, path, st))
            pending += st.st_size
            if pending >= self.batch_bytes:
                flush_batch()
                pending = 0
        flush_batch()
        return hashes

    @obs_trace.traced("stream.file")
    def _pack_file_streaming(self, path: Path, st: os.stat_result) -> bytes:
        """Chunk one huge file through the backend's streaming manifest;
        blobs pack as chunks finalize, so memory stays ~one segment.

        The file is mmapped and fed as memoryview windows
        (dir_packer.rs:252's memmap2 analog), so the packer never holds a
        second buffered copy of the file; the CPU and native backends
        still assemble one per-segment buffer when they splice the carry
        onto each window, ``TpuBackend`` uploads the window as it is.
        The same documented race as the reference applies: a file
        mutating mid-chunk produces a wrong (detectably inconsistent)
        backup of that file, never a crash — mmap failures (e.g. the
        file was truncated to empty after the stat) fall back to plain
        reads.
        """
        import mmap as _mmap

        children: List[bytes] = []
        emit_s = 0.0

        def emit(ref, data):
            # the two clock reads a chunk costs: what the packer does
            # with a final chunk is not the chunk_hash stage's time
            nonlocal emit_s
            t_emit = time.perf_counter()
            self.stats.chunks += 1
            self.stats.bytes_read += ref.length
            children.append(ref.hash)
            self._add_blob(ref.hash, BlobKind.FILE_CHUNK, data)
            emit_s += time.perf_counter() - t_emit

        with open(path, "rb") as f:
            try:
                mm = _mmap.mmap(f.fileno(), 0, access=_mmap.ACCESS_READ)
            except (OSError, ValueError):
                mm = None  # empty/truncated/unmappable: plain reads
            t0 = time.monotonic()
            if mm is None:
                self.backend.manifest_stream(
                    f.read, segment_bytes=self.batch_bytes, emit=emit)
            else:
                view = memoryview(mm)
                pos = 0

                def read(n: int):
                    nonlocal pos
                    out = view[pos:pos + n]
                    pos += len(out)
                    return out

                try:
                    self.backend.manifest_stream(
                        read, segment_bytes=self.batch_bytes, emit=emit)
                finally:
                    view.release()
                    try:
                        mm.close()
                    except BufferError:
                        # an in-flight exception's traceback still holds
                        # window slices; closing would mask the real
                        # error — let GC drop the mapping instead
                        pass
        dt = max(time.monotonic() - t0 - emit_s, 0.0)
        self.stats.chunk_hash_s += dt
        _STAGE_SECONDS.observe(dt, stage="chunk_hash")
        if children:
            # the streamed file's chunks were classified host-side one by
            # one; account them as a single per-file dedup pass
            obs_profile.dispatch("index", actual_bytes=32 * len(children),
                                 padded_bytes=32 * len(children))
        self.stats.files += 1
        self.progress(file=str(path), bytes=st.st_size)
        with obs_trace.span("stream.tree"):
            return self._tree_with_split(
                TreeKind.FILE, path.name,
                TreeMetadata(size=st.st_size, mtime_ns=st.st_mtime_ns,
                             ctime_ns=st.st_ctime_ns),
                children)

    # --- directory walk ----------------------------------------------------

    def pack(self, root: Path, scan: Optional[TreeScan] = None) -> bytes:
        """Pack ``root`` recursively; returns the snapshot id (root hash).
        ``scan``: :func:`scan_tree` of this ``root`` where the caller
        has made it (the engine's estimate)."""
        root = Path(root)
        if not root.is_dir():
            raise NotADirectoryError(str(root))
        if scan is None:
            with obs_trace.span("pack.walk"):
                scan = scan_tree(root)
        elif scan.dirs[0] != os.fspath(root):
            raise ValueError(f"scan of {scan.dirs[0]}, not of {root}")
        if self.dedup_index is not None:
            with obs_trace.span("pack.prepare"):
                self.backend.prepare_batches(scan.batches(self.batch_bytes),
                                             self.dedup_index)
        # the directories were found breadth-first: packed deepest-first,
        # children always hash before parents (dir_packer.rs:89-132)
        dir_hash: dict = {}
        for d in reversed(scan.dirs):
            with obs_trace.span("pack.walk"):
                listed, subdirs, _links, failed = _list_dir(d)
                self.stats.failed_files += failed
                files = [(Path(entry.path), st) for entry, st in listed]
            children = [h for h in self._pack_files(files) if h is not None]
            with obs_trace.span("pack.dir_tree"):
                children.extend(dir_hash[s] for s in subdirs if s in dir_hash)
                try:
                    st = os.stat(d)
                    meta = TreeMetadata(size=0, mtime_ns=st.st_mtime_ns,
                                        ctime_ns=st.st_ctime_ns)
                except OSError:
                    # directory vanished mid-walk: keep its children
                    meta = TreeMetadata()
                name = "" if d == scan.dirs[0] else os.path.basename(d)
                dir_hash[d] = self._tree_with_split(TreeKind.DIR, name, meta,
                                                    children)
                self.stats.dirs += 1
                self._maybe_emit_partial()
        self._flush_device_sync()
        # the wait for every seal and write in flight, and for what the
        # writer thread does after each (``on_packfile``: the index, the
        # seal-time table): none of it is the writer's ``stall``
        with obs_trace.span("pack.flush"):
            self.writer.flush()
        return dir_hash[scan.dirs[0]]
