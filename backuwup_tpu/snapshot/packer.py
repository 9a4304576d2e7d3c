"""Directory packer: filesystem tree -> content-addressed snapshot.

Re-designs ``client/src/backup/filesystem/dir_packer.rs``:

* Deepest-first directory walk (``browse_dir_tree``, ``dir_packer.rs:89-132``)
  so every child tree hash exists before its parent is built.
* Files are chunked + fingerprinted through a :class:`ChunkerBackend`
  (CPU oracle or the TPU kernels) — the batched analog of the reference's
  per-file FastCDC/blake3 hot loop (``:246-311``).  The files of
  consecutive directories form one device batch (:meth:`DirPacker.pack`).
* Tree nodes (``Tree`` wire blobs) carry name, metadata, and child hashes;
  nodes with more than TREE_MAX_CHILDREN children split into a
  ``next_sibling`` chain (``dir_packer.rs:35,313-363``), built back-to-front
  so each page embeds the following page's hash.
* The root tree's blob hash is the snapshot id (``dir_packer.rs:47-84``).
* Dedup: every blob (chunk or tree) is checked against the blob index
  before packing (``pack.rs:31-55``); duplicate data costs one hash lookup.
"""

from __future__ import annotations

import contextlib
import itertools
import logging
import os
import time
from array import array
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Iterator, List, Optional, Tuple

from .. import defaults, native
from ..obs import metrics as obs_metrics
from ..obs import profile as obs_profile
from ..obs import trace as obs_trace
from ..ops.backend import ChunkerBackend
from ..ops.pipeline import _POOL_STREAM_STEP
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

    def batches(self, batch_bytes: int,
                dispatch_bytes: int = _POOL_STREAM_STEP
                ) -> Iterator[List[int]]:
        """The file lengths of the pack batches to come, a list a batch,
        cut as :meth:`DirPacker.pack` cuts them (its docstring has the
        rule; a file over ``batch_bytes`` is streamed and in no batch),
        for ``ChunkerBackend.prepare_batches``.  A file that changes
        before it is read costs a compile in its batch, nothing else."""
        sizes: List[int] = []
        pending = 0
        for lengths in reversed(self.file_sizes):
            if sizes and _closes_batch(pending, lengths, batch_bytes,
                                       dispatch_bytes):
                yield sizes
                sizes, pending = [], 0
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


def _closes_batch(pending: int, lengths, batch_bytes: int,
                  dispatch_bytes: int) -> bool:
    """Whether the open pack batch (``pending`` bytes of files) closes
    before a directory whose files have these ``lengths``: where the
    directory's batched files would take it past a device dispatch's
    worth, and before a directory that streams a file (its blobs are
    packed as they are met, so what was queued before it has to be
    packed first)."""
    batched = sum(n for n in lengths if n <= batch_bytes)
    return pending + batched > dispatch_bytes \
        or any(n > batch_bytes for n in lengths)


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


@dataclass
class _OpenDir:
    """A directory between its listing and its tree node."""
    path: str
    #: its name in its parent's node ("" for the root)
    name: str
    #: its regular files in name order, each with its ``lstat``
    files: List[Tuple[Path, os.stat_result]]
    subdirs: List[str]
    #: a file of ``files`` each: its tree hash (None while it is queued,
    #: and for a file that vanished or failed to read)
    hashes: List[Optional[bytes]]


_oracle_warned = False  # one warning a process


def _tree_node_engine() -> str:
    """What :func:`native.host_digest` hashes a tree node with in this
    process (``obs/profile.TREE_NODE_ENGINES``): the C library where it
    loads, else the scalar-Python oracle, which is said once."""
    global _oracle_warned
    if native.available():
        return "native"
    if not _oracle_warned:
        _oracle_warned = True
        logging.getLogger(__name__).warning(
            "the native library did not load: tree nodes are hashed by "
            "the scalar-Python BLAKE3 oracle (0.14 ms at 100 bytes, "
            "1.3 ms a KiB); a backup of many small files is a fifth "
            "slower for it (bkw_tree_node_digests_total{engine=oracle})")
    return "oracle"


class DirPacker:
    def __init__(self, backend: ChunkerBackend, writer: PackfileWriter,
                 index: BlobIndex,
                 progress: Optional[Callable] = None,
                 batch_bytes: int = 256 * defaults.MiB,
                 dispatch_bytes: int = _POOL_STREAM_STEP,
                 should_pause: Optional[Callable] = None,
                 dedup_batch: Optional[Callable] = None,
                 dedup_index=None,
                 on_blob: Optional[Callable] = None):
        self.backend = backend
        self.writer = writer
        self.index = index
        self.progress = progress or (lambda **kw: None)
        self.batch_bytes = batch_bytes
        self.dispatch_bytes = dispatch_bytes
        # the open pack batch: (directory, index of one of its files, or
        # None for the directory's end) in the order to pack them
        self._queue: List[tuple] = []
        self._queued_files = 0
        self._queued_bytes = 0
        self._dir_hash: dict = {}
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
        # tree nodes are hashed on the host, one as it is built; what
        # hashes them is found here, so that a first build of the C
        # library is set-up and never falls inside a backup
        self._node_engine = _tree_node_engine()
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
        h = native.host_digest(encoded)
        obs_profile.tree_node_digest(self._node_engine)
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

    def _queue_dir(self, odir: _OpenDir) -> None:
        """Queue a listed directory on the open pack batch: its files,
        and behind them its own end.  :meth:`pack` has the rule by which
        a batch closes."""
        if self._queued_files and _closes_batch(
                self._queued_bytes, [st.st_size for _path, st in odir.files],
                self.batch_bytes, self.dispatch_bytes):
            self._flush_batch()
        self._queue_files(odir)
        self._queue.append((odir, None))
        if not self._queued_files:
            # no file is waiting for the device: the node is built now
            self._flush_batch()

    def _queue_files(self, odir: _OpenDir) -> None:
        for i, (path, st) in enumerate(odir.files):
            if st.st_size > self.batch_bytes:
                # oversized file: stream it so memory stays bounded
                try:
                    odir.hashes[i] = self._pack_file_streaming(path, st)
                except OSError:
                    self.stats.failed_files += 1
                continue
            self._queue.append((odir, i))
            self._queued_files += 1
            self._queued_bytes += st.st_size
            if self._queued_bytes >= self.batch_bytes:
                self._flush_batch()

    def _flush_batch(self) -> None:
        """Pack what is queued, in the order it was queued: the files
        read, chunked and hashed as one device batch, then each file's
        blobs and tree node and, behind a directory's last file, the
        directory's own node."""
        queue, self._queue = self._queue, []
        reads = obs_trace.span("batch.read") if self._queued_files \
            else contextlib.nullcontext()
        self._queued_files = self._queued_bytes = 0
        # (directory, index of the file in it or None for its end, the
        # file's bytes, its metadata); a file that cannot be read left out
        events: List[tuple] = []
        batch_data: List[bytes] = []
        with reads:
            for odir, i in queue:
                if i is None:
                    events.append((odir, None, None, None))
                    continue
                path, st = odir.files[i]
                try:
                    data = path.read_bytes()
                except OSError:
                    self.stats.failed_files += 1
                    continue
                self.stats.bytes_read += len(data)
                batch_data.append(data)
                events.append((odir, i, data, TreeMetadata(
                    size=len(data), mtime_ns=st.st_mtime_ns,
                    ctime_ns=st.st_ctime_ns)))
        manifests: list = []
        hints = iter(())
        if batch_data:
            obs_profile.pack_batch(
                dirs=len({id(odir) for odir, i, _d, _m in events
                          if i is not None}),
                files=len(batch_data))
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
            if hint_list is not None:
                hints = iter(hint_list)
            elif self.dedup_batch is not None:
                # legacy hook path (no full index handle): same sync-then-
                # classify ordering, one device round trip for the batch
                self._flush_device_sync()
                hints = iter(self.dedup_batch(
                    [ref.hash for m in manifests for ref in m]))
        self._emit(events, manifests, hints)
        if manifests:
            # the batch's nodes (files' and directories') reach the
            # device table behind it, whatever their count: the index
            # pads a batch of hashes to a bucket (``_pad_queries``)
            self._flush_device_sync()
            self._maybe_emit_partial()

    def _emit(self, events: List[tuple], manifests: list,
              hints: Iterator) -> None:
        """Pack a batch's events in order: a file's new chunks and its
        node (``manifests``: one a file, ``hints``: the device's verdict
        a chunk), a directory's node at its end."""
        of_file = iter(manifests)
        for ends, run in itertools.groupby(events, key=lambda e: e[1] is None):
            if ends:
                for odir, _i, _data, _meta in run:
                    self._close_dir(odir)
                continue
            with obs_trace.span("batch.emit"):
                for odir, i, data, meta in run:
                    path = odir.files[i][0]
                    manifest = next(of_file)
                    for ref in manifest:
                        self.stats.chunks += 1
                        self._add_blob(
                            ref.hash, BlobKind.FILE_CHUNK,
                            data[ref.offset:ref.offset + ref.length],
                            dup_hint=next(hints, None))
                    odir.hashes[i] = self._tree_with_split(
                        TreeKind.FILE, path.name, meta,
                        [ref.hash for ref in manifest])
                    self.stats.files += 1
                    self.progress(file=str(path), bytes=len(data))

    def _close_dir(self, odir: _OpenDir) -> None:
        """A directory's own node, once each of its files and
        subdirectories has its hash."""
        with obs_trace.span("pack.dir_tree"):
            children = [h for h in odir.hashes if h is not None]
            children.extend(self._dir_hash[s] for s in odir.subdirs
                            if s in self._dir_hash)
            try:
                st = os.stat(odir.path)
                meta = TreeMetadata(size=0, mtime_ns=st.st_mtime_ns,
                                    ctime_ns=st.st_ctime_ns)
            except OSError:
                # directory vanished mid-walk: keep its children
                meta = TreeMetadata()
            self._dir_hash[odir.path] = self._tree_with_split(
                TreeKind.DIR, odir.name, meta, children)
            self.stats.dirs += 1
            self._maybe_emit_partial()

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
        has made it (the engine's estimate).

        A pack batch (one ``batch.read``, one ``manifest_many_classified``,
        one emit) spans directories: the files of consecutive directories
        join the open batch, each directory's end queued behind its last
        file, and what is queued is packed in that order, so the blobs
        and the snapshot are what a batch a directory gave.  The batch
        closes at ``batch_bytes`` (inside a directory too: the bound on
        what the packer holds), and at a directory boundary where the
        next directory would take it past ``dispatch_bytes``, a device
        dispatch's worth (``ops/pipeline.py``'s step for a stream's
        padded length): a directory of that size or more is a batch of
        its own and its blobs reach the writer as soon as it is read,
        and a tree of thousands of small directories goes to the device
        in batches of about that size, not a handful of files at a
        time.  :meth:`TreeScan.batches` cuts the same."""
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
                self.backend.prepare_batches(
                    scan.batches(self.batch_bytes, self.dispatch_bytes),
                    self.dedup_index)
        # the directories were found breadth-first: packed deepest-first,
        # children always hash before parents (dir_packer.rs:89-132)
        self._dir_hash = {}
        for d in reversed(scan.dirs):
            with obs_trace.span("pack.walk"):
                listed, subdirs, _links, failed = _list_dir(d)
                self.stats.failed_files += failed
                files = [(Path(entry.path), st) for entry, st in listed]
            name = "" if d == scan.dirs[0] else os.path.basename(d)
            self._queue_dir(_OpenDir(d, name, files, subdirs,
                                     [None] * len(files)))
        self._flush_batch()
        self._flush_device_sync()
        # the wait for every seal and write in flight, and for what the
        # writer thread does after each (``on_packfile``: the index, the
        # seal-time table): none of it is the writer's ``stall``
        with obs_trace.span("pack.flush"):
            self.writer.flush()
        return self._dir_hash[scan.dirs[0]]
