"""Device-pipeline profiler: dispatch accounting + the per-backup report.

The performance half of the obs plane (GWP, Ren et al. — PAPERS.md):
always-on, low-overhead counters wired into the pipeline entry points
in :mod:`backuwup_tpu.ops.pipeline` / :mod:`backuwup_tpu.ops.backend`,
the per-function compile seconds a ``jax.monitoring`` listener feeds
(:func:`jit_compiled`), and the report that folds them and the served
path's span sums into one backup's delta (:func:`report`).

Dispatch accounting semantics (the hand-countable contract the tests
pin; one *dispatch* = one device program launch, or its CPU-fallback
moral equivalent):

=========  =================================================================
stage      what counts as one dispatch
=========  =================================================================
scan       device: one ``scan_select_batch`` launch per batch, alone (the
           host-tiled path) or inside the batch's one manifest program
           (``scan_digest_batch_pool``).  CPU/native fallback: one ``chunk()`` pass
           per stream (native runs the whole pipeline in one C call per
           stream and counts once under every stage).
select     rides the scan program on every path (fused boundary
           selection), so it counts 1:1 with scan.
gather     device: one ``gather_chunks``/``_gather_digest`` tile launch,
           or the leaf pool's gather inside the manifest program.
           CPU fallback: one host piece-slicing pass per stream that
           produced at least one chunk.
digest     device: one batched digest launch (``_gather_digest`` tile,
           the manifest program's leaf pool, a long stream's
           ``pool_digest``, or ``blake3_many_tpu`` tiny-stream batch).  CPU fallback: one batched ``digest_many`` call per
           ``manifest_many``/stream segment with at least one piece.
index      one batched dedup classification per pack batch (device
           ``dedup_batch`` table classify or the host blob-index pass),
           bytes = 32 per ref classified.
=========  =================================================================

Bytes ride each dispatch twice: *actual* payload bytes and *padded*
bytes as dispatched (tile/bucket padding included), so
``bkw_pipeline_pad_efficiency`` exposes how much of every launch was
real work — the number PERF.md round-5 item 1 (merging the per-class
digest dispatches) moves.

Like the rest of ``obs/`` this module is import-light: stdlib +
defaults only, neither jax nor numpy.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterator, Optional, Sequence

from . import journal as _journal
from . import metrics as _metrics
from . import trace as _trace

STAGES = ("scan", "select", "gather", "digest", "index")

_DISPATCH = _metrics.counter(
    "bkw_device_dispatch_total",
    "Pipeline dispatches by logical stage (a fused program counts once "
    "under every stage it implements)", labelnames=("stage",))
_STAGE_BYTES = _metrics.counter(
    "bkw_pipeline_stage_bytes_total",
    "Actual payload bytes processed per pipeline stage",
    labelnames=("stage",))
_STAGE_PADDED = _metrics.counter(
    "bkw_pipeline_stage_padded_bytes_total",
    "Bytes as dispatched per pipeline stage, tile/bucket padding "
    "included", labelnames=("stage",))
_PAD_EFFICIENCY = _metrics.gauge(
    "bkw_pipeline_pad_efficiency",
    "Cumulative actual/padded byte ratio per stage (1.0 = no padding "
    "waste)", labelnames=("stage",))
_JIT_COMPILE = _metrics.histogram(
    "bkw_jit_compile_seconds",
    "Backend-compile seconds per jitted function (a persistent-cache "
    "load reports its retrieval time here too), from the jax.monitoring "
    "listener the TPU backend registers", labelnames=("fun",))

# Per-device twins of the dispatch/bytes/pad families for the mesh
# pipeline (shard_map over the row axis).  Additive alongside the
# unlabeled families above, PR-7 style (bkw_peer_transfer_* next to
# bkw_transfer_*): one shard_map launch still counts ONCE per stage in
# bkw_device_dispatch_total, and additionally once per participating
# device here — so the unlabeled families keep their hand-countable
# "one program launch" meaning while these expose the per-shard split.
_DISPATCH_DEV = _metrics.counter(
    "bkw_mesh_device_dispatch_total",
    "Mesh-pipeline dispatches by stage and participating device shard",
    labelnames=("stage", "device"))
_STAGE_BYTES_DEV = _metrics.counter(
    "bkw_mesh_stage_bytes_total",
    "Actual payload bytes per stage per device shard",
    labelnames=("stage", "device"))
_STAGE_PADDED_DEV = _metrics.counter(
    "bkw_mesh_stage_padded_bytes_total",
    "Bytes as dispatched per stage per device shard, padding included",
    labelnames=("stage", "device"))
_PAD_EFFICIENCY_DEV = _metrics.gauge(
    "bkw_mesh_pad_efficiency",
    "Cumulative actual/padded byte ratio per stage per device shard",
    labelnames=("stage", "device"))
_HBM_HIGH = _metrics.gauge(
    "bkw_mesh_hbm_highwater_bytes",
    "Peak bytes in flight per device across the mesh driver's dispatch "
    "window (buffers + packed cuts + digest accumulator + dedup lanes)",
    labelnames=("device",))

_MESH_HOST_RERUN = _metrics.counter(
    "bkw_mesh_host_rerun_rows_total",
    "Mesh-driver rows the device could not finish and the host re-ran: "
    "kind=shard (the shard's leaf pool or tier cascade overflowed, its "
    "rows re-ran host-tiled) or kind=row (the row's candidate capacity "
    "overflowed, re-chunked on the CPU oracle)", labelnames=("kind",))

# Tiered dedup index families (dedupstore/, docs/dedup_tiering.md): the
# hot/cold/host probe split, the promotion/demotion clock, and the HBM
# footprint of the hot fingerprint table.  Declared here (not in
# dedupstore/) so every family has exactly one construction site and the
# report below can fold the tier split into the per-backup delta.
TIER_PATHS = ("device", "cold", "host")

_TIER_PROBES = _metrics.counter(
    "bkw_tier_probes_total",
    "Tiered dedup probes by answering path (device = hot HBM table, "
    "cold = host LSM fall-through, host = authority fallback)",
    labelnames=("path",))
_TIER_HITS = _metrics.counter(
    "bkw_tier_hits_total",
    "Tiered dedup probe hits (key classified duplicate) by answering "
    "path", labelnames=("path",))
_TIER_PROMOTIONS = _metrics.counter(
    "bkw_tier_promotions_total",
    "Fingerprints promoted cold -> hot by the probe-frequency clock")
_TIER_DEMOTIONS = _metrics.counter(
    "bkw_tier_demotions_total",
    "Fingerprints demoted hot -> cold under the DEDUP_HBM_BUDGET_BYTES "
    "cap")
_TIER_HBM = _metrics.gauge(
    "bkw_tier_hbm_bytes",
    "Current HBM bytes held by the hot fingerprint table (slots x 20 "
    "bytes x mesh devices)")
_TIER_HBM_HIGH = _metrics.gauge(
    "bkw_tier_hbm_highwater_bytes",
    "Peak HBM bytes ever held by the hot fingerprint table")
_TIER_COLD_RUNS = _metrics.gauge(
    "bkw_tier_cold_runs",
    "Sorted immutable runs on disk in the cold fingerprint store")
_TIER_COLD_RECORDS = _metrics.gauge(
    "bkw_tier_cold_records",
    "Records across the cold store's runs + memtable (cross-run "
    "duplicates counted until compaction merges them)")
_TIER_COLD_COMMITS = _metrics.counter(
    "bkw_tier_cold_run_commits_total",
    "Durable cold-tier run commits by kind", labelnames=("kind",))

# What the host is doing inside one streamed file (``stream.file``), by
# span: preparing bytes for the device, waiting for the device (upload,
# program and download), or doing the packer's own work (the per-chunk
# callback, then the file's tree node: what ``chunk_hash`` leaves out).
# :func:`report` sums the spans of a group into its ``stream`` section.
STREAM_GROUPS = {
    "stream.read": "host_prep",
    "cdc.decode": "host_prep",
    "stream.select_cuts": "host_prep",
    "stream.slice": "host_prep",
    "blake3.stage": "host_prep",
    "stream.upload": "device_wait",
    "cdc.scan": "device_wait",
    "blake3.digest": "device_wait",
    "stream.emit": "emit",
    "stream.tree": "emit",
}

# The same for one pack batch of whole files (the batched route:
# ``DirPacker._flush_batch`` -> ``manifest_many_classified`` ->
# ``DevicePipeline.manifest_batch``): reading the files,
# building host batches and decoding what came down, waiting for the
# device (the mesh program's dispatch and collect, the tiny files'
# digest batch, a long file's segmented scan and digest, the index
# answering what the device could not), and the packer's per-chunk loop
# and tree nodes.  :func:`report` sums them into its ``batch`` section.
# Beside those four, ``compile``: the programs a backup's batches need,
# lowered one after the other and compiled side by side before the first
# batch (a process's first backup only; outside ``packer.manifest_many``).
BATCH_GROUPS = {
    "batch.read": "read",
    "batch.stage": "host_stage",
    "batch.decode": "host_stage",
    "batch.compile": "compile",
    "pipeline.mesh_dispatch": "device_wait",
    "pipeline.mesh_collect": "device_wait",
    "batch.tiny_digest": "device_wait",
    "batch.long_stream": "device_wait",
    "batch.resolve": "device_wait",
    "batch.emit": "emit",
}
# Who decided whether a chunk of the batched route is a duplicate: the
# HBM table's found-vector, downloaded with the batch
# (``device_decided``), or ``resolve_hints`` for what the device could
# not classify (tiny and long files, fallback rows, lost lanes:
# ``host_resolved``).  And which way a file went through the prepass.
BATCH_VERDICTS = ("device_decided", "host_resolved")
BATCH_ROUTES = ("tiny", "bucketed", "long")
_BATCH_CHUNKS = _metrics.counter(
    "bkw_batch_chunks_total",
    "Chunks of the batched route by who classified them: the device's "
    "found-vector or the index's resolve_hints", labelnames=("verdict",))
_BATCH_FILES = _metrics.counter(
    "bkw_batch_files_total",
    "Files of the batched route by prepass route: one tiny-file digest "
    "batch, padded buckets, or the long-stream scan",
    labelnames=("route",))

# The pack batches of a backup (``snapshot/packer.py`` ``_flush_batch``:
# one read, one ``manifest_many_classified``, one emit each), and what
# they held: the directories with a file in the batch (one cut by
# ``batch_bytes`` counts in both its batches) and the files read.
PACK_BATCH_ITEMS = {"dirs": "dirs", "files": "batched_files"}  # -> report
_PACK_BATCHES = _metrics.counter(
    "bkw_pack_batches_total",
    "Pack batches the packer handed to the chunker backend")
_PACK_BATCH_ITEMS = _metrics.counter(
    "bkw_pack_batch_items_total",
    "Directories and files the pack batches held", labelnames=("what",))

# The rows of the HBM index's host-fed query batches
# (``ops/dedup_index.py`` ``_pad_queries``, where ``probe``, ``insert``
# and ``_insert_once`` get their shapes): the hashes sent, and the rows
# of the power-of-two bucket they were padded to (at most twice as many
# from 8 up), which is what keeps ``dedup_insert`` / ``dedup_probe``
# from compiling for every new count
# (``bkw_jit_compile_seconds{fun="dedup_insert"}``).
INDEX_QUERY_ROWS = ("actual", "padded")
_INDEX_QUERY_ROWS = _metrics.counter(
    "bkw_index_query_rows_total",
    "Rows of the query batches the host sent the HBM dedup index: the "
    "hashes themselves, and the rows of the bucket they were padded to",
    labelnames=("what",))

# What the packer asked the file system about a backup's tree
# (``snapshot/packer.py`` ``_list_dir``, the only place it asks): the
# ``os.scandir`` calls and the ``lstat`` calls of both passes, and the
# directories and regular files the first pass (``scan_tree``) found.
# Two calls a file and two a directory say no other walk is left.
TREE_SCAN_COUNTS = ("dirs", "files", "scandir_calls", "lstat_calls")
_TREE_SCAN = _metrics.counter(
    "bkw_tree_scan_total",
    "Directories and regular files a backup's tree scan found, and the "
    "scandir and lstat calls its two passes made", labelnames=("what",))

# The tree nodes the packer hashed (``snapshot/packer.py`` ``_add_tree``:
# a file's node, a directory's, every page of a split one), by what
# hashed them: ``native`` is the host's C BLAKE3 (microseconds a node),
# ``oracle`` the scalar-Python reference a process falls back to when
# the library does not build (0.14 ms at 100 bytes, 0.2 s at 147 KB:
# a fifth of a backup of many small files).
TREE_NODE_ENGINES = ("native", "oracle")
_TREE_NODE_DIGESTS = _metrics.counter(
    "bkw_tree_node_digests_total",
    "Tree nodes the packer hashed on the host, by the engine that "
    "hashed them", labelnames=("engine",))

# Bytes the resident streaming route (ops/resident.py) moved for a
# streamed file: ``uploaded`` is everything it put on the device
# (window blocks, chunk rows), about the file's size when every byte
# goes up once; ``carried`` is what stayed there between two windows,
# the open chunk a window ends in, moved to the front of the next
# resident buffer by a device slice (at most ``max_size`` a window);
# ``host_assembled`` is what it had to copy together on the host (the
# chunk that straddles carry and window, a slice rescanned by the
# oracle), at most one chunk a window.  Beside them the windows made
# resident, and what the route's digest tiles were dispatched over by
# leaf class (``leaves``: a tile's row length in 1 KiB leaves): the
# chunks' own bytes, and the tiles' full height and row length.
STREAM_BYTE_KINDS = ("uploaded", "host_assembled", "carried")
_STREAM_BYTES = _metrics.counter(
    "bkw_stream_bytes_total",
    "Bytes the resident streaming route uploaded to the device, moved "
    "to the next resident buffer on the device, and assembled on the "
    "host, per streamed file",
    labelnames=("kind",))
_STREAM_SEGMENTS = _metrics.counter(
    "bkw_stream_segments_total",
    "Windows of streamed files the resident streaming route made "
    "resident on the device")
_STREAM_DIGEST_BYTES = _metrics.counter(
    "bkw_stream_digest_bytes_total",
    "Bytes of the resident streaming route's digest tiles by leaf "
    "class: the chunks' own, and the padded tiles'",
    labelnames=("leaves", "what"))

# What the send stage moves for the packfiles it codes: ``packfile`` is
# the bytes of every packfile whose stripe it coded and audited,
# ``uploaded`` every byte it put on the device for them (the shard
# matrix, digest batches, window rows), whichever route the backend
# takes.  ``bkw_send_dispatches_total`` counts the times a send-stage
# thread waited for the device to hand a result down.  All three count
# only inside :func:`send_stage`, so the same seams' other callers (the
# packer's seal-time table, repair) stay out.
SEND_BYTE_KINDS = ("uploaded", "packfile")
_SEND_BYTES = _metrics.counter(
    "bkw_send_bytes_total",
    "Packfile bytes the send stage coded into stripes, and bytes it "
    "uploaded to the device to do so", labelnames=("kind",))
_SEND_DISPATCHES = _metrics.counter(
    "bkw_send_dispatches_total",
    "Blocking device round trips of the send stage (upload, programs, "
    "download awaited)")
_send_thread = threading.local()
# How each packfile the send stage took up left it: ``striped`` (k+m
# shards acked by k+m holders), ``whole`` (one copy on one holder: the
# swarm cannot carry a stripe), ``deferred`` (k+m holders are there but
# one did not answer its dial in this tick: handed back for the next).
SEND_OUTCOMES = {"striped": "stripes", "whole": "whole",
                 "deferred": "deferred"}  # outcome -> report["send"] key
_SEND_PACKFILES = _metrics.counter(
    "bkw_send_packfiles_total",
    "Packfiles by how the send stage's tick left them",
    labelnames=("outcome",))
# What left through the P2P sockets meanwhile, from the transport's own
# counters (``net/p2p.py`` ``Transport._ship``; read by name, 0 where no
# transport was ever imported): every signed frame byte, and those
# shipped on a socket that negotiated permessage-deflate.
SEND_WIRE_COUNTERS = {"wire_bytes": "bkw_p2p_bytes_sent_total",
                      "deflated_bytes": "bkw_p2p_bytes_deflated_total"}

# One backup's wall, closed (the engine's ``_run_backup_locked``).  Its
# phases follow each other on the backup's coroutine and are told apart
# by clock reads there (a span held across an await cannot nest on the
# loop's line): the size estimate's walk; the pack thread, from its
# start to its pools' shutdown; the blob index's flush; the send loop's
# rest; the snapshot's record, the server's ``backup_done`` and the
# report itself.  :func:`report` makes its ``wall`` section of them.
WALL_PHASES = ("estimate", "pack", "index_flush", "drain", "commit")
# The pack thread's wall (span ``engine.pack``), the same way: its
# top-level spans by step, each opened where the work is and none inside
# another of these, so a step's seconds are exclusive and the steps sum
# to ``engine.pack`` less its self time.  ``walk`` is a directory's
# listing a time (one ``os.scandir``, one ``lstat`` a file), ahead of
# them the tree's scan where the caller handed none (the engine hands
# the estimate's), and the backend told the batches to come from that
# scan's lengths (``batch.compile`` inside it in a process's first
# backup); ``device_sync`` the host-classified
# hashes pushed into the HBM table between batches (``index.classify``
# inside); ``flush`` the wait for every seal and write in flight and
# the pools' shutdown.  :func:`report` makes its ``pack`` section of
# them, with ``stall`` (the packer blocked on the writer's double
# buffer, inside whichever step added the blob) and ``pack.seal_table``
# (the writer thread's work after a packfile's write) beside them.
PACK_STEPS = {
    "pack.walk": "walk",
    "pack.prepare": "walk",
    "batch.read": "read",
    "packer.manifest_many": "manifest",
    "batch.emit": "emit",
    "stream.file": "stream",
    "pack.device_sync": "device_sync",
    "pack.dir_tree": "dir_tree",
    "pack.flush": "flush",
}

# Span names whose bkw_span_seconds sums a pipeline report attributes as
# per-stage wall time: the host-tiled path's dispatch/collect pairs, the
# packer entry point that drives them and the batch's own parts, the
# streamed file and its parts, the index classify, the send stage's
# steps (``send.stripe`` holds ``send.rs_encode``, ``send.challenge_tables``
# and ``send.wire`` of one packfile), and the backup's wall: the pack
# thread and its steps, and what carries the other phases' host work.
REPORT_SPANS = tuple(dict.fromkeys((
    "pipeline.scan_select_dispatch",
    "pipeline.cut_collect",
    "pipeline.digest_dispatch",
    "pipeline.digest_collect",
    "packer.manifest_many",
    "stream.file",
    *STREAM_GROUPS,
    "stream.boundary_chunk",  # inside stream.slice: not a group's term
    *BATCH_GROUPS,  # pipeline.mesh_dispatch / mesh_collect among them
    "index.classify",
    "send.dial",
    "send.rs_encode",
    "send.challenge_tables",
    "send.stripe",
    "send.wire",
    "engine.pack",
    *PACK_STEPS,
    "pack.seal_table",
    "backup.estimate",
    "backup.index_flush",
    "backup.record_snapshot",
)))

# Streaming-dataflow overlap families (the engine's stage graph,
# docs/dataflow.md): per-stage busy seconds attributed to one backup at
# end of run, plus the overlap-efficiency verdict.  Declared here — the
# single construction site for every bkw_* family — and folded by
# :func:`overlap_report`.
_BACKUP_STAGE_BUSY = _metrics.counter(
    "bkw_backup_stage_busy_seconds_total",
    "Busy seconds per backup dataflow stage (chunk_hash / seal / write /"
    " send), attributed per run from the stage-seconds registry deltas",
    labelnames=("stage",))
_BACKUP_OVERLAP = _metrics.gauge(
    "bkw_backup_overlap_efficiency",
    "max(per-stage busy seconds) / end-to-end wall for the most recent"
    " backup; 1.0 means the wall clock converged to the slowest stage")


def dispatch(stage: str, count: int = 1, actual_bytes: int = 0,
             padded_bytes: int = 0) -> None:
    """Record ``count`` dispatches for ``stage`` (see the module table
    for what counts as one).  Cheap enough to be always on."""
    if stage not in STAGES:
        raise ValueError(f"unknown pipeline stage {stage!r}")
    _DISPATCH.inc(count, stage=stage)
    if actual_bytes:
        _STAGE_BYTES.inc(actual_bytes, stage=stage)
    if padded_bytes:
        _STAGE_PADDED.inc(padded_bytes, stage=stage)
        padded = _STAGE_PADDED.value(stage=stage)
        if padded > 0:
            _PAD_EFFICIENCY.set(
                _STAGE_BYTES.value(stage=stage) / padded, stage=stage)


def dispatch_device(stage: str, device: int, count: int = 1,
                    actual_bytes: int = 0, padded_bytes: int = 0) -> None:
    """Record one device shard's share of a mesh launch.

    Touches ONLY the per-device families — the caller records the launch
    itself once via :func:`dispatch`, so ``bkw_device_dispatch_total``
    stays the hand-countable program-launch count and
    ``bkw_mesh_device_dispatch_total`` sums to launches x mesh size."""
    if stage not in STAGES:
        raise ValueError(f"unknown pipeline stage {stage!r}")
    dev = str(device)
    _DISPATCH_DEV.inc(count, stage=stage, device=dev)
    if actual_bytes:
        _STAGE_BYTES_DEV.inc(actual_bytes, stage=stage, device=dev)
    if padded_bytes:
        _STAGE_PADDED_DEV.inc(padded_bytes, stage=stage, device=dev)
        padded = _STAGE_PADDED_DEV.value(stage=stage, device=dev)
        if padded > 0:
            _PAD_EFFICIENCY_DEV.set(
                _STAGE_BYTES_DEV.value(stage=stage, device=dev) / padded,
                stage=stage, device=dev)


def hbm_high_water(device: int, in_flight_bytes: int) -> None:
    """Raise (never lower) the per-device HBM high-water gauge."""
    dev = str(device)
    cur = _HBM_HIGH.value(device=dev)
    if in_flight_bytes > cur:
        _HBM_HIGH.set(in_flight_bytes, device=dev)


def mesh_host_rerun(kind: str, rows: int) -> None:
    """Count ``rows`` mesh-driver rows re-run on the host path."""
    if rows:
        _MESH_HOST_RERUN.inc(rows, kind=kind)


def mesh_host_rerun_rows() -> int:
    """Rows re-run on the host so far, both kinds."""
    return int(sum(_MESH_HOST_RERUN.value(kind=k) for k in ("shard", "row")))


# --- tiered dedup accounting (dedupstore/) -----------------------------------

def tier_probes(path: str, probes: int, hits: int = 0) -> None:
    """Record ``probes`` classify lanes answered on ``path`` (device /
    cold / host), ``hits`` of which classified duplicate."""
    if path not in TIER_PATHS:
        raise ValueError(f"unknown tier path {path!r}")
    if probes:
        _TIER_PROBES.inc(probes, path=path)
    if hits:
        _TIER_HITS.inc(hits, path=path)


def tier_promotions(n: int) -> None:
    if n:
        _TIER_PROMOTIONS.inc(n)


def tier_demotions(n: int) -> None:
    if n:
        _TIER_DEMOTIONS.inc(n)


def tier_hbm_bytes(table_bytes: int) -> None:
    """Set the hot-table HBM gauge; the high-water twin only rises."""
    _TIER_HBM.set(table_bytes)
    if table_bytes > _TIER_HBM_HIGH.value():
        _TIER_HBM_HIGH.set(table_bytes)


def tier_cold_state(runs: int, records: int) -> None:
    _TIER_COLD_RUNS.set(runs)
    _TIER_COLD_RECORDS.set(records)


def tier_cold_commit(kind: str) -> None:
    _TIER_COLD_COMMITS.inc(1, kind=kind)


def stream_bytes(kind: str, n: int) -> None:
    """Count ``n`` bytes of a streamed file uploaded, carried on the
    device or host-assembled."""
    if kind not in STREAM_BYTE_KINDS:
        raise ValueError(f"unknown stream byte kind {kind!r}")
    if n:
        _STREAM_BYTES.inc(n, kind=kind)


def stream_segment() -> None:
    """One window of a streamed file made resident."""
    _STREAM_SEGMENTS.inc()


def stream_digest_tile(leaves: int, actual_bytes: int,
                       padded_bytes: int) -> None:
    """One digest tile of the streaming route's class ``leaves``."""
    _STREAM_DIGEST_BYTES.inc(actual_bytes, leaves=str(leaves), what="actual")
    _STREAM_DIGEST_BYTES.inc(padded_bytes, leaves=str(leaves), what="padded")


def batch_chunks(verdict: str, n: int) -> None:
    """``n`` chunks of one pack batch classified by ``verdict``."""
    if verdict not in BATCH_VERDICTS:
        raise ValueError(f"unknown batch verdict {verdict!r}")
    if n:
        _BATCH_CHUNKS.inc(n, verdict=verdict)


def tree_scan(**counts: int) -> None:
    """Add to the tree scan's counts (``TREE_SCAN_COUNTS``)."""
    for what, n in counts.items():
        if what not in TREE_SCAN_COUNTS:
            raise ValueError(f"unknown tree scan count {what!r}")
        if n:
            _TREE_SCAN.inc(n, what=what)


def tree_node_digest(engine: str) -> None:
    """One tree node hashed by ``engine`` (``TREE_NODE_ENGINES``)."""
    if engine not in TREE_NODE_ENGINES:
        raise ValueError(f"unknown tree node engine {engine!r}")
    _TREE_NODE_DIGESTS.inc(engine=engine)


def pack_batch(dirs: int, files: int) -> None:
    """One pack batch of ``files`` files out of ``dirs`` directories."""
    _PACK_BATCHES.inc()
    _PACK_BATCH_ITEMS.inc(dirs, what="dirs")
    _PACK_BATCH_ITEMS.inc(files, what="files")


def index_query_rows(actual: int, padded: int) -> None:
    """One query batch of ``actual`` hashes in ``padded`` rows."""
    _INDEX_QUERY_ROWS.inc(actual, what="actual")
    _INDEX_QUERY_ROWS.inc(padded, what="padded")


def batch_files(route: str, n: int) -> None:
    """``n`` files of one pack batch sent down ``route``."""
    if route not in BATCH_ROUTES:
        raise ValueError(f"unknown batch route {route!r}")
    if n:
        _BATCH_FILES.inc(n, route=route)


@contextlib.contextmanager
def send_stage(packfile_bytes: int) -> Iterator[None]:
    """The calling thread codes one packfile of ``packfile_bytes`` for
    the send stage: until the block ends, what it stages on the device
    (:func:`device_upload`) and each wait for a result
    (:func:`device_wait`) is the send stage's."""
    _SEND_BYTES.inc(packfile_bytes, kind="packfile")
    _send_thread.depth = getattr(_send_thread, "depth", 0) + 1
    try:
        yield
    finally:
        _send_thread.depth -= 1


def send_packfile(outcome: str) -> None:
    """One packfile left a send tick as ``outcome``."""
    if outcome not in SEND_OUTCOMES:
        raise ValueError(f"unknown send outcome {outcome!r}")
    _SEND_PACKFILES.inc(outcome=outcome)


def device_upload(n: int) -> None:
    """``n`` bytes staged on the device by the digest and erasure seams
    the send stage codes through; counted where :func:`send_stage` is
    open on this thread."""
    if n and getattr(_send_thread, "depth", 0):
        _SEND_BYTES.inc(n, kind="uploaded")


def device_wait() -> None:
    """The calling thread is about to wait for a device result."""
    if getattr(_send_thread, "depth", 0):
        _SEND_DISPATCHES.inc()


# --- which step recompiled ----------------------------------------------------

def jit_compiled(fun: str, seconds: float) -> None:
    """One backend compile of jitted function ``fun``: observed into
    ``bkw_jit_compile_seconds{fun}`` and journaled with the span that
    was open on the compiling thread, so an operator reads which step of
    which backup recompiled."""
    _JIT_COMPILE.observe(seconds, fun=fun)
    ctx = _trace.current()
    _journal.emit(
        "compile", fun=fun, dur_s=round(seconds, 6),
        trace_id=ctx.trace_id if ctx is not None else None,
        span=ctx.name if ctx is not None else None)


def _compile_values() -> Dict[str, float]:
    """{fun: summed seconds} of ``bkw_jit_compile_seconds``."""
    return {s["labels"]["fun"]: s["sum"]
            for s in _JIT_COMPILE._snapshot_series()}


# --- per-backup pipeline report ---------------------------------------------

def _device_values(fam) -> Dict[tuple, float]:
    """{(device, stage): value} for one (stage, device)-labeled family."""
    return {(s["labels"]["device"], s["labels"]["stage"]): s["value"]
            for s in fam._snapshot_series()}


def baseline() -> Dict[str, Dict[str, float]]:
    """Snapshot the profiler families so :func:`report` can attribute a
    delta to one backup (the engine's ``_registry_stage_sums`` idiom)."""
    out = {"dispatch": {}, "bytes": {}, "padded": {}, "span_s": {},
           "compile_s": _compile_values(),
           "dispatch_dev": _device_values(_DISPATCH_DEV),
           "bytes_dev": _device_values(_STAGE_BYTES_DEV),
           "padded_dev": _device_values(_STAGE_PADDED_DEV)}
    for stage in STAGES:
        out["dispatch"][stage] = _DISPATCH.value(stage=stage)
        out["bytes"][stage] = _STAGE_BYTES.value(stage=stage)
        out["padded"][stage] = _STAGE_PADDED.value(stage=stage)
    tier: Dict[str, float] = {"promotions": _TIER_PROMOTIONS.value(),
                              "demotions": _TIER_DEMOTIONS.value()}
    for path in TIER_PATHS:
        tier[f"probes_{path}"] = _TIER_PROBES.value(path=path)
        tier[f"hits_{path}"] = _TIER_HITS.value(path=path)
    out["tier"] = tier
    out["stream_bytes"] = {k: _STREAM_BYTES.value(kind=k)
                           for k in STREAM_BYTE_KINDS}
    out["stream_segments"] = {"segments": _STREAM_SEGMENTS.value()}
    out["stream_digest"] = {
        (s["labels"]["leaves"], s["labels"]["what"]): s["value"]
        for s in _STREAM_DIGEST_BYTES._snapshot_series()}
    out["batch_chunks"] = {v: _BATCH_CHUNKS.value(verdict=v)
                           for v in BATCH_VERDICTS}
    out["batch_files"] = {r: _BATCH_FILES.value(route=r)
                          for r in BATCH_ROUTES}
    out["tree_scan"] = {w: _TREE_SCAN.value(what=w)
                        for w in TREE_SCAN_COUNTS}
    out["tree_nodes"] = {e: _TREE_NODE_DIGESTS.value(engine=e)
                         for e in TREE_NODE_ENGINES}
    out["pack_batches"] = {key: _PACK_BATCH_ITEMS.value(what=w)
                           for w, key in PACK_BATCH_ITEMS.items()}
    out["pack_batches"]["batches"] = _PACK_BATCHES.value()
    out["index_query_rows"] = {w: _INDEX_QUERY_ROWS.value(what=w)
                               for w in INDEX_QUERY_ROWS}
    out["send"] = {f"{k}_bytes": _SEND_BYTES.value(kind=k)
                   for k in SEND_BYTE_KINDS}
    out["send"]["dispatches"] = _SEND_DISPATCHES.value()
    for outcome, key in SEND_OUTCOMES.items():
        out["send"][key] = _SEND_PACKFILES.value(outcome=outcome)
    for key, name in SEND_WIRE_COUNTERS.items():
        fam = _metrics.registry().get(name)
        out["send"][key] = fam.value() if fam is not None else 0.0
    spans = _metrics.registry().get("bkw_span_seconds")
    if spans is not None:
        for name in REPORT_SPANS:
            out["span_s"][name] = spans.sum_value(name=name)
    # declared in snapshot/packfile.py, read by name as the transport's
    pack = _metrics.registry().get("bkw_pack_stage_seconds")
    out["pack_stage_s"] = {
        "stall": pack.sum_value(stage="stall") if pack is not None else 0.0}
    return out


def _by_group(span_s: Dict[str, float],
              groups: Dict[str, str]) -> Dict[str, float]:
    """Span seconds summed by the group ``groups`` puts each name in."""
    out = dict.fromkeys(groups.values(), 0.0)
    for name, group in groups.items():
        out[group] += span_s.get(name, 0.0)
    return {k: round(v, 6) for k, v in out.items()}


def report(base: Optional[dict] = None,
           wall_marks: Optional[Sequence[float]] = None,
           backup_done_s: float = 0.0) -> dict:
    """Dispatch counts, bytes, padding efficiency, and stage seconds
    since ``base`` (or process start when ``base`` is None).

    ``wall_marks``: ``time.monotonic()`` reads on the backup's coroutine,
    its entry and then the end of each of ``WALL_PHASES`` but the last,
    which ends here.  ``backup_done_s``: the part of ``commit`` spent
    awaiting the server (loop time, so a clock read as well)."""
    now = baseline()
    base = base or {}

    def _delta(section: str) -> Dict[str, float]:
        prior = base.get(section, {})
        return {k: v - prior.get(k, 0.0) for k, v in now[section].items()}

    dispatches = {k: int(v) for k, v in _delta("dispatch").items()}
    actual = {k: int(v) for k, v in _delta("bytes").items()}
    padded = {k: int(v) for k, v in _delta("padded").items()}
    efficiency = {
        stage: (round(actual[stage] / padded[stage], 6)
                if padded[stage] > 0 else None)
        for stage in STAGES}
    span_s = _delta("span_s")
    stage_seconds = {name: round(dt, 6)
                     for name, dt in span_s.items() if dt > 0}
    stream: dict = _by_group(span_s, STREAM_GROUPS)
    for kind, n in _delta("stream_bytes").items():
        stream[f"{kind}_bytes"] = int(n)
    stream["segments"] = int(_delta("stream_segments")["segments"])
    # {leaves: {"bytes", "padded_bytes"}} of the classes that ran a tile
    classes: Dict[str, Dict[str, int]] = {}
    for (leaves, what), n in _delta("stream_digest").items():
        if n > 0:
            key = "bytes" if what == "actual" else "padded_bytes"
            classes.setdefault(leaves, {})[key] = int(n)
    stream["digest_classes"] = classes
    batch: dict = _by_group(span_s, BATCH_GROUPS)
    batch["chunks"] = {k: int(v) for k, v in _delta("batch_chunks").items()}
    batch["files"] = {k: int(v) for k, v in _delta("batch_files").items()}
    # ``batches``, ``dirs`` and ``batched_files`` (empty files too, which
    # ``files`` sends down no route)
    batch.update({k: int(v) for k, v in _delta("pack_batches").items()})
    pack = {"total_s": round(span_s.get("engine.pack", 0.0), 6),
            "steps": _by_group(span_s, PACK_STEPS),
            "scan": {k: int(v) for k, v in _delta("tree_scan").items()},
            "tree_nodes": {k: int(v)
                           for k, v in _delta("tree_nodes").items()},
            "stall_s": round(_delta("pack_stage_s")["stall"], 6),
            "seal_table_s": round(span_s.get("pack.seal_table", 0.0), 6)}
    compile_s = {fun: round(dt, 6)
                 for fun, dt in _delta("compile_s").items() if dt > 0}
    # per-device split of the mesh-pipeline launches: {device: {stage: n}}
    # plus per-device pad efficiency, so the report shows whether work
    # divided evenly across the shards (tests/test_mesh_pipeline.py)
    by_device: Dict[str, Dict[str, int]] = {}
    eff_device: Dict[str, Dict[str, Optional[float]]] = {}
    prior_d = base.get("dispatch_dev", {})
    now_d = now["dispatch_dev"]
    for (dev, stage), v in now_d.items():
        n = int(v - prior_d.get((dev, stage), 0.0))
        if n:
            by_device.setdefault(dev, {})[stage] = n
    prior_b, prior_p = base.get("bytes_dev", {}), base.get("padded_dev", {})
    for (dev, stage), v in now["padded_dev"].items():
        dp = v - prior_p.get((dev, stage), 0.0)
        if dp > 0:
            db = now["bytes_dev"].get((dev, stage), 0.0) \
                - prior_b.get((dev, stage), 0.0)
            eff_device.setdefault(dev, {})[stage] = round(db / dp, 6)
    out = {
        "dispatches": dispatches,
        "bytes": actual,
        "padded_bytes": padded,
        "pad_efficiency": efficiency,
        "stage_seconds": stage_seconds,
        "stream": stream,
        "batch": batch,
        "pack": pack,
        "index": {"query_rows": {k: int(v) for k, v
                                 in _delta("index_query_rows").items()}},
        "send": {k: int(v) for k, v in _delta("send").items()},
        "compile_s": compile_s,
        "compile_total_s": round(sum(compile_s.values()), 6),
    }
    # tiered-dedup rows: probe/hit split per answering path plus the
    # promotion/demotion clock movement, only when the tier moved at all
    tier_delta = {k: int(v) for k, v in _delta("tier").items()}
    if any(tier_delta.values()):
        probes = {p: tier_delta[f"probes_{p}"] for p in TIER_PATHS}
        hits = {p: tier_delta[f"hits_{p}"] for p in TIER_PATHS}
        out["tier"] = {
            "probes": probes,
            "hits": hits,
            "promotions": tier_delta["promotions"],
            "demotions": tier_delta["demotions"],
            "device_hit_rate": (round(hits["device"] / probes["device"], 6)
                                if probes["device"] > 0 else None),
            "hbm_highwater_bytes": int(_TIER_HBM_HIGH.value()),
        }
    if by_device:
        out["device_dispatches"] = {
            d: by_device[d] for d in sorted(by_device, key=int)}
        out["device_pad_efficiency"] = {
            d: eff_device.get(d, {}) for d in sorted(by_device, key=int)}
    if wall_marks is not None:
        ends = [*wall_marks[1:], time.monotonic()]
        out["wall"] = {
            "total_s": round(ends[-1] - wall_marks[0], 6),
            "phases": {phase: round(end - start, 6) for phase, start, end
                       in zip(WALL_PHASES, wall_marks, ends)},
            "backup_done_s": round(backup_done_s, 6)}
    return out


def emit_report(rep: dict, **fields) -> None:
    """Journal one ``pipeline_report`` event (no-op without a journal,
    like every obs emission)."""
    _journal.emit("pipeline_report", report=rep, **fields)


def overlap_report(stage_busy: Dict[str, float], wall_s: float,
                   drain_s: float = 0.0) -> dict:
    """Fold one backup's per-stage busy seconds into the overlap
    families and return the summary row the engine stores + journals.

    ``stage_busy`` must hold BUSY stages only — the caller excludes
    idle/wait accumulators (pack stall, transfer admission wait), which
    would otherwise reward a stalled pipeline.  Efficiency is
    max(stage)/wall: 1.0 means the end-to-end wall clock collapsed onto
    the slowest stage (perfect overlap).  Concurrent fan-out can
    legitimately push a stage's summed busy seconds past the wall, so
    values above 1.0 are kept as-is.
    ``drain_s`` is what came after the pack thread was done: the blob
    index's flush, then the send loop's rest up to the last packfile
    acked (the report's ``wall`` section tells the two apart)."""
    busy = {k: max(float(v), 0.0) for k, v in stage_busy.items()}
    for stage, dt in busy.items():
        if dt > 0:
            _BACKUP_STAGE_BUSY.inc(dt, stage=stage)
    max_stage = max(busy.values(), default=0.0)
    eff = (max_stage / wall_s) if wall_s > 0 else 0.0
    _BACKUP_OVERLAP.set(eff)
    rep = {
        "wall_s": round(wall_s, 6),
        "drain_s": round(drain_s, 6),
        "stage_busy_s": {k: round(v, 6) for k, v in busy.items()},
        "max_stage_s": round(max_stage, 6),
        "overlap_efficiency": round(eff, 6),
    }
    _journal.emit("overlap_report", **rep)
    return rep
