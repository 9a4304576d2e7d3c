"""BASELINE.md benchmark configs #2-#6 (config #1 is bench.py's main loop).

Each config times the production device pipeline on a device-synthesized
corpus shaped like the BASELINE workload and gates the numbers on
bit-parity with the CPU oracle over a downloaded subset (speed without
identical dedup output is meaningless):

  #2  many small files     — ~80k kernel-tree-shaped files; files below
      the 256 KiB CDC minimum are single chunks, so the production path
      (engine.manifest_batch's tiny-file branch) is digest-bound: staged
      device tiles + batched Pallas BLAKE3, no scan
  #3  two-snapshot overlap — incremental re-chunk over 2x1 GiB, high dedup
  #4  large stream         — 4 GiB at 64 KiB average chunks (VM-image
      profile), streamed through the zero-round-trip driver
  #5  cross-peer global dedup — sharded HBM index, device-resident
      queries, chained sync-free inserts
  #6  end-to-end backup    — DirPacker over a real on-disk tree on the
      host-side engine (packer/packfile/index overheads made visible)

  #7  erasure coding      — RS shard encode/decode throughput
  #8  transfer plane      — serial-vs-concurrent end-to-end backup over
      loopback p2p with N latency-injected peers (ratio, not sustained)
  #9  chaos scenario      — the composed scorecard gate embedded in the
      bench record (durability regression tripwire)
  #10 wan resume          — resume-enabled vs restart-from-zero
      bytes-on-wire across two injected mid-transfer cuts (ratio)
  #11 crash matrix        — armed commit-seam crashes + recovery sweep
      cost, scorecard embedded
  #12 swarm               — sharded vs single-lock coordination plane:
      direct matchmaking-layer speedup legs plus the HTTP swarm
      scenario's p99/stall/off-loop-commit evidence (gate: ≥ 2x)
  #14 multichip           — matched-work 1-device vs N-device mesh
      manifest (shard_map scan→digest + device-resident dedup handoff);
      parity/even-split/handoff gates always on, wall-clock speedup
      gate armed on hardware only

Environment knobs: BENCH_C2_FILES, BENCH_C3_MIB, BENCH_C4_GIB,
BENCH_C5_HASHES, BENCH_C6_MIB, BENCH_C7_SHARD_KIB, BENCH_C7_STRIPES,
BENCH_C8_MIB, BENCH_C8_PEERS, BENCH_C8_LATENCY_S, BENCH_C10_KIB,
BENCH_C10_CHUNK_KIB, BENCH_C12_CLIENTS, BENCH_C12_S, BENCH_C14_DEVICES,
BENCH_C14_ROWS_PER_DEV, BENCH_C14_ROW_KIB, BENCH_C14_SPEEDUP_GATE,
BENCH_C17_DEVICES, BENCH_C17_POPULATION, BENCH_C17_BATCH,
BENCH_C17_HOT_FRACTION, BENCH_C17_HIT_GATE, BENCH_C17_WALL_GATE.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from backuwup_tpu.ops import cdc_cpu
from backuwup_tpu.ops.blake3_cpu import Blake3Numpy, blake3_hash
from backuwup_tpu.ops.blake3_tpu import digest_padded
from backuwup_tpu.ops.cdc_tpu import _HALO
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.ops.pipeline import DevicePipeline


def segment_mib() -> int:
    """Shared segment-size knob: bench.py's main loop and configs #3/#4
    must agree or the suite silently benchmarks mixed segment sizes."""
    return int(os.environ.get("BENCH_SEGMENT_MIB", "256"))


def min_wall_s() -> float:
    """Minimum sustained wall clock per config (BASELINE discipline:
    sustained minutes-long runs, not seconds-long bursts).  0 disables
    (CPU smoke runs)."""
    return float(os.environ.get("BENCH_MIN_WALL_S", "60"))


class SustainedWindow:
    """One shared implementation of the sustained-window discipline.

    Every timed path cycles its work pool for at least the stated scale
    AND at least :func:`min_wall_s` of wall clock; the window records how
    much work actually ran so throughput = work / wall stays honest.
    """

    def __init__(self, n_min: int = 1):
        self.n_min = n_min
        self.count = 0
        self.t0 = time.time()

    def items(self, pool):
        """Yield pool items cyclically for the window (fine-grained
        paths: one item per segment)."""
        while (self.count < self.n_min
               or time.time() - self.t0 < min_wall_s()):
            yield pool[self.count % len(pool)]
            self.count += 1

    def passes(self):
        """Yield pass indices for the window (coarse paths: one pass =
        the whole stated workload)."""
        while (self.count < max(1, self.n_min)
               or time.time() - self.t0 < min_wall_s()):
            yield self.count
            self.count += 1

    @property
    def wall(self) -> float:
        return time.time() - self.t0


def _oracle(data: bytes, params: CDCParams):
    chunks = cdc_cpu.chunk_stream(data, params)
    digests = Blake3Numpy().digest_batch(
        [data[o:o + l] for o, l in chunks])
    return chunks, digests


def _check(device_result, data: bytes, params: CDCParams, tag: str):
    chunks, digests = device_result
    ref_chunks, ref_digests = _oracle(data, params)
    if chunks != ref_chunks or [bytes(d) for d in digests] != ref_digests:
        raise RuntimeError(f"config {tag}: device/oracle parity FAILED")


@functools.partial(jax.jit, static_argnames=("B", "L", "pallas"))
def _gather_digest_tiles(pool: jnp.ndarray, offs: jnp.ndarray,
                         lens: jnp.ndarray, *, B: int, L: int,
                         pallas: bool) -> jnp.ndarray:
    """Carve (B,) file spans out of a resident pool and digest them in
    ONE program — one dispatch submission per tile instead of two, and
    XLA fuses the zero-mask/word-prep into the gather output."""
    span = L * 1024

    def one(off):
        # no zero-mask here: digest_padded masks past-length bytes itself
        return jax.lax.dynamic_slice(pool, (off,), (span,))

    tiles = jax.vmap(one)(offs.astype(jnp.int32))
    return digest_padded(tiles, lens.astype(jnp.int32), L=L, pallas=pallas)


def config2_small_files(pipeline: DevicePipeline, params: CDCParams,
                        log: Callable) -> Dict:
    """~80k small files, batched digests — BASELINE config #2.

    Kernel-tree shape (BASELINE.md:38): tens of thousands of files, nearly
    all below CDC min chunk size, so each is exactly one chunk and one
    BLAKE3 root.  The production path for these is the tiny-file branch of
    ``DevicePipeline.manifest_batch`` / the engine packer: batched
    digests, no scan.  This config stages the files into (B, L*1024)
    digest tiles on device and times gather+digest+manifest assembly.
    """
    n_files = int(os.environ.get("BENCH_C2_FILES", "80000"))
    rng = np.random.default_rng(21)
    # kernel-tree-ish size mix: mostly 1-32 KiB, tail up to 192 KiB
    sizes = np.minimum(
        (rng.lognormal(mean=9.2, sigma=1.1, size=n_files)).astype(np.int64),
        192 * 1024)
    sizes = np.maximum(sizes, 64)
    total = int(sizes.sum())
    pool_len = 256 << 20
    pool = jax.random.randint(jax.random.PRNGKey(5), (pool_len,), 0, 256,
                              dtype=jnp.uint8)
    offs = rng.integers(0, pool_len - 200 * 1024, size=n_files)
    assert (sizes <= params.min_size).all(), "config2 files must be tiny"

    # bucket by leaf count into a closed tile universe
    leaf_buckets = (4, 8, 16, 32, 64, 128, 192)
    leaves = -(-sizes // 1024)
    bucket_of = np.searchsorted(np.array(leaf_buckets), leaves, side="left")
    B = 512
    plan = []  # (bucket L, file index array padded to B)
    for bi, L in enumerate(leaf_buckets):
        idxs = np.nonzero(bucket_of == bi)[0]
        for s0 in range(0, len(idxs), B):
            plan.append((L, idxs[s0:s0 + B]))

    def run():
        digests = np.zeros((n_files, 32), dtype=np.uint8)
        pend = []
        for L, idxs in plan:
            o = np.zeros(B, dtype=np.int64)
            ln = np.zeros(B, dtype=np.int32)
            o[:len(idxs)] = offs[idxs]
            ln[:len(idxs)] = sizes[idxs]
            cv = _gather_digest_tiles(pool, jnp.asarray(o), jnp.asarray(ln),
                                      B=B, L=L,
                                      pallas=pipeline.pallas_digest)
            try:
                cv.copy_to_host_async()
            except AttributeError:
                pass
            pend.append((idxs, cv))
        for idxs, cv in pend:
            dig = np.ascontiguousarray(
                np.asarray(cv).astype("<u4")).view(np.uint8).reshape(-1, 32)
            digests[idxs] = dig[:len(idxs)]
        return digests

    run()  # warm
    window = SustainedWindow()
    for _ in window.passes():
        digests = run()
    loops = window.count
    dt = window.wall
    mibs = loops * total / (1 << 20) / dt

    # parity: oracle-hash a sample of files (download only their spans,
    # not the whole pool)
    for i in rng.integers(0, n_files, size=8):
        off, ln = int(offs[i]), int(sizes[i])
        data = np.asarray(pool[off:off + ln]).tobytes()
        if blake3_hash(data) != bytes(digests[i]):
            raise RuntimeError("config #2: digest parity FAILED")
        if cdc_cpu.chunk_stream(data, params) != [(0, ln)]:
            raise RuntimeError("config #2: tiny file not single-chunk")
    log(f"config#2 small-files: {loops}x{n_files} files, "
        f"{loops * total / (1 << 20):.0f} MiB in {dt:.2f}s = "
        f"{mibs:.1f} MiB/s")
    return {"files": n_files, "mib_s": round(mibs, 2),
            "wall_s": round(dt, 2)}


def _synth_segments(key, n_seg: int, seg: int):
    row = _HALO + seg

    @jax.jit
    def synth(key):
        s = jax.random.randint(key, (seg,), 0, 256, dtype=jnp.uint8)
        return jnp.concatenate([jnp.zeros(_HALO, dtype=jnp.uint8), s]
                               ).reshape(1, row)

    out = []
    for _ in range(n_seg):
        key, sub = jax.random.split(key)
        out.append(synth(sub))
    jax.block_until_ready(out)
    return out


def config3_incremental(pipeline: DevicePipeline, params: CDCParams,
                        log: Callable) -> Dict:
    """Two consecutive snapshots with small edits — BASELINE config #3."""
    snap_mib = int(os.environ.get("BENCH_C3_MIB", "1024"))
    seg_mib = segment_mib()
    seg = seg_mib << 20
    n_seg = max(1, (snap_mib << 20) // seg)
    key = jax.random.PRNGKey(31)

    @jax.jit
    def edit(buf, key):
        """Overwrite 20 x 4 KiB windows — the incremental delta."""
        flat = buf.reshape(-1)
        ks = jax.random.split(key, 20)
        offs = jax.random.randint(key, (20,), _HALO, buf.shape[1] - 4096)
        for i in range(20):
            patch = jax.random.randint(ks[i], (4096,), 0, 256,
                                       dtype=jnp.uint8)
            flat = jax.lax.dynamic_update_slice(flat, patch, (offs[i],))
        return flat.reshape(1, buf.shape[1])

    snap_a = _synth_segments(key, n_seg, seg)
    key2 = jax.random.PRNGKey(32)
    snap_b = []
    for s in snap_a:
        key2, sub = jax.random.split(key2)
        snap_b.append(edit(s, sub))
    jax.block_until_ready(snap_b)
    nv = np.full(1, seg, dtype=np.int32)
    batches = [(s, nv) for s in snap_a + snap_b]

    list(pipeline.manifest_segments_device(batches[:2],
                                           strict_overflow=True))  # warm
    window = SustainedWindow()
    for n in window.passes():
        out = list(pipeline.manifest_segments_device(
            batches, strict_overflow=True))
        if n == 0:
            results = out
    passes = window.count
    dt = window.wall
    dig_a = set()
    for (chunks, digs), in results[:n_seg]:
        dig_a.update(bytes(d) for d in digs)
    dup = tot = 0
    for (chunks, digs), in results[n_seg:]:
        for d in digs:
            tot += 1
            dup += bytes(d) in dig_a
    ratio = dup / max(tot, 1)
    mibs = passes * 2 * n_seg * seg_mib / dt

    # parity + identical dedup ratio on an 8 MiB sub-pair (clipped to the
    # segment size so tiny smoke runs don't declare bytes past the buffer)
    sub = min(8 << 20, seg)
    a8 = bytes(np.asarray(snap_a[0][0, _HALO:_HALO + sub]))
    b8 = bytes(np.asarray(snap_b[0][0, _HALO:_HALO + sub]))
    ca, da = _oracle(a8, params)
    cb, db = _oracle(b8, params)
    sa = set(da)
    oracle_dup = sum(1 for d in db if d in sa)
    dev_sub = []
    for blob in (a8, b8):
        ext = np.concatenate([np.zeros(_HALO, dtype=np.uint8),
                              np.frombuffer(blob, dtype=np.uint8)])
        (res,), = pipeline.manifest_segments_device(
            [(jnp.asarray(ext.reshape(1, -1)),
              np.full(1, sub, dtype=np.int32))], strict_overflow=True)
        _check(res, blob, params, "#3")
        dev_sub.append(res)
    dev_sa = {bytes(d) for d in dev_sub[0][1]}
    dev_dup = sum(1 for d in dev_sub[1][1] if bytes(d) in dev_sa)
    if dev_dup != oracle_dup:
        raise RuntimeError("config #3: dedup-ratio divergence on sub-pair")
    log(f"config#3 incremental: {passes}x2x{n_seg * seg_mib} MiB in "
        f"{dt:.2f}s = {mibs:.1f} MiB/s, dedup ratio {ratio:.3f} "
        f"(oracle sub-pair dup {oracle_dup}/{len(cb)})")
    return {"mib_s": round(mibs, 2), "dedup_ratio": round(ratio, 4),
            "wall_s": round(dt, 2)}


def config4_large_stream(log: Callable) -> Dict:
    """4 GiB contiguous stream at 64 KiB average chunks — config #4."""
    total_gib = float(os.environ.get("BENCH_C4_GIB", "4"))
    params = CDCParams.from_desired(64 << 10)
    pipeline = DevicePipeline(params, l_bucket=256, b_bucket=512)
    seg_mib = segment_mib()
    seg = seg_mib << 20
    n_seg = max(2, int(total_gib * 1024) // seg_mib)
    pool = _synth_segments(jax.random.PRNGKey(41), min(8, n_seg), seg)
    nv = np.full(1, seg, dtype=np.int32)
    list(pipeline.manifest_segments_device([(pool[0], nv), (pool[1], nv)],
                                           strict_overflow=True))  # warm

    window = SustainedWindow(n_seg)
    n_chunks = 0
    for results in pipeline.manifest_segments_device(
            window.items([(s, nv) for s in pool]), strict_overflow=True):
        for chunks, _d in results:
            n_chunks += len(chunks)
    done = window.count
    dt = window.wall
    mibs = done * seg_mib / dt

    sub = min(8 << 20, seg)
    data = bytes(np.asarray(pool[0][0, _HALO:_HALO + sub]))
    ext = np.concatenate([np.zeros(_HALO, dtype=np.uint8),
                          np.frombuffer(data, dtype=np.uint8)])
    (dev_sub,), = pipeline.manifest_segments_device(
        [(jnp.asarray(ext.reshape(1, -1)), np.full(1, sub, dtype=np.int32))],
        strict_overflow=True)
    _check(dev_sub, data, params, "#4")
    log(f"config#4 large-stream(64KiB): {done * seg_mib / 1024:.1f} GiB in "
        f"{dt:.2f}s = {mibs:.1f} MiB/s ({n_chunks} chunks)")
    return {"mib_s": round(mibs, 2), "chunks": n_chunks,
            "wall_s": round(dt, 2)}


def config5_cross_peer(log: Callable) -> Dict:
    """Cross-peer global dedup on the sharded HBM index — config #5.

    Queries are device-resident (in production the digests land in HBM
    straight from the digest stage) and inserts chain without host syncs;
    a smaller host-checked sub-run gates classification parity first.
    """
    from jax.sharding import Mesh

    from backuwup_tpu.ops.dedup_index import (ShardedDedupIndex,
                                              hashes_to_queries)

    n_hashes = int(os.environ.get("BENCH_C5_HASHES", "4000000"))
    mesh = Mesh(np.array(jax.devices()), ("data",))
    rng = np.random.default_rng(51)

    # --- parity sub-run (200k hashes, host-simulated) ----------------------
    shared = [rng.bytes(32) for _ in range(25000)]
    peers = []
    for p in range(4):
        own = [rng.bytes(32) for _ in range(25000)]
        picks = rng.choice(len(shared), 25000, replace=False)
        peers.append(own + [shared[i] for i in picks])
    cap = 1 << 20
    index = ShardedDedupIndex.create(mesh, capacity=cap)
    host_seen = set()
    dev_flags = []
    host_flags = []
    for corpus in peers:
        q = hashes_to_queries(corpus)
        found = index.insert(q, np.ones(len(corpus), dtype=np.uint32))
        dev_flags.extend(bool(f) for f in found)
        for h in corpus:
            host_flags.append(h in host_seen)
            host_seen.add(h)
    if dev_flags != host_flags:
        raise RuntimeError("config #5: device/host global dedup mismatch")

    # --- timed run: device-resident queries, sync-free inserts -------------
    batch = 500_000
    n_batches = max(1, n_hashes // batch)
    cap = 1 << max(20, (4 * n_hashes).bit_length() - 1)
    index = ShardedDedupIndex.create(mesh, capacity=cap)
    key = jax.random.PRNGKey(55)
    d = mesh.shape["data"]

    @jax.jit
    def synth_q(key, dup_from):
        """Half fresh random keys, half repeats of an earlier batch."""
        fresh = jax.random.bits(key, (batch, 4), dtype=jnp.uint32)
        mix = jnp.where((jnp.arange(batch) % 2 == 0)[:, None],
                        fresh, dup_from)
        return mix.reshape(d, batch // d, 4)

    k0, key = jax.random.split(key)
    first = jax.random.bits(k0, (batch, 4), dtype=jnp.uint32)
    qs = []
    prev = first
    for _ in range(n_batches):
        key, sub = jax.random.split(key)
        q = synth_q(sub, prev)
        prev = q.reshape(batch, 4)
        qs.append(q)
    jax.block_until_ready(qs)
    vals = jnp.ones((d, batch // d), dtype=jnp.uint32)

    # warm insert AND probe programs on a throwaway table (same shapes
    # as the timed table, so both compiles land out of the timed window)
    warm = ShardedDedupIndex.create(mesh, capacity=cap)
    warm.insert_device(qs[0], vals)
    jax.block_until_ready(warm.probe_device(qs[0]))

    t0 = time.time()
    founds = []
    for q in qs:
        found, lost = index.insert_device(q, vals)
        founds.append((found, lost))
    # one sync at the end: download the found/lost flags
    lost_total = 0
    dup_total = 0
    for found, lost in founds:
        lost_total += int(np.asarray(lost).sum())
        dup_total += int((np.asarray(found) != 0).sum())
    insert_dt = time.time() - t0
    if lost_total:
        raise RuntimeError("config #5: unresolved inserts (table too full)")
    total = n_batches * batch
    rate = total / insert_dt

    # sustained window: keep issuing device-resident probe batches (the
    # dominant steady-state operation — inserts are capped by the table's
    # load-factor budget, probes are not)
    # own window, independent of how long the inserts took: the
    # sustained-read metric must exist even when the insert phase alone
    # exceeds the budget
    probes = 0
    probe_chain = []
    t1 = time.time()
    while time.time() - t1 < min_wall_s():
        probe_chain.append(index.probe_device(qs[probes % len(qs)]))
        probes += 1
        if len(probe_chain) >= 8:
            # bound in-flight work with a one-scalar download: device
            # executions run in order, so syncing result i proves all
            # earlier probes completed, without the bulk found-vector
            # transfer (np.asarray of the full vector would time the
            # download, not the probe)
            np.asarray(probe_chain.pop(0).ravel()[0])
    if probe_chain:
        np.asarray(probe_chain[-1].ravel()[0])
    probe_dt = time.time() - t1
    probe_rate = probes * batch / probe_dt if probes else 0.0
    dt = time.time() - t0
    log(f"config#5 cross-peer: {total} hashes over {d} device(s) in "
        f"{insert_dt:.2f}s = {rate:,.0f} inserts/s, dup ratio "
        f"{dup_total/total:.3f}; sustained {probes * batch} probes "
        f"at {probe_rate:,.0f}/s (wall {dt:.1f}s)")
    out = {"hashes_s": round(rate), "dup_ratio": round(dup_total / total, 4),
           "wall_s": round(dt, 2)}
    if probes:
        out["probe_hashes_s"] = round(probe_rate)
    return out


def config6_end_to_end(log: Callable) -> Dict:
    """End-to-end DirPacker over a real on-disk tree — engine overheads.

    Runs the actual backup packer (walk -> chunk -> dedup -> compress ->
    encrypt -> packfile write) on the host CPU backend over a temp corpus,
    so packer/packfile/index costs are visible next to the kernel numbers
    (reference hot path: dir_packer.rs:246-311 + pack.rs:116-204).  The
    same packer on the device backend is what ``chip_smoke.py`` drives.
    """
    import shutil
    import tempfile
    from pathlib import Path

    from backuwup_tpu.crypto import KeyManager
    from backuwup_tpu.ops.backend import CpuBackend, NativeBackend
    from backuwup_tpu.snapshot.blob_index import BlobIndex
    from backuwup_tpu.snapshot.packer import DirPacker
    from backuwup_tpu.snapshot.packfile import PackfileWriter

    total_mib = int(os.environ.get("BENCH_C6_MIB", "256"))
    rng = np.random.default_rng(61)
    tmp = Path(tempfile.mkdtemp(prefix="bkw_bench_"))
    try:
        src = tmp / "src"
        src.mkdir()
        written = 0
        i = 0
        while written < (total_mib << 20):
            sub = src / f"d{i % 16}"
            sub.mkdir(exist_ok=True)
            n = int(rng.integers(16 << 10, 4 << 20))
            (sub / f"f{i}").write_bytes(rng.bytes(n))
            written += n
            i += 1
        keys = KeyManager.generate()
        try:
            backend = NativeBackend()
        except Exception:
            backend = CpuBackend()

        def one_pass(n: int) -> None:
            out = tmp / f"packs{n}"
            out.mkdir()
            packer = DirPacker(backend, PackfileWriter(keys, out),
                               BlobIndex(keys, tmp / f"index{n}"))
            packer.pack(src)
            packer.writer.close()
            shutil.rmtree(out, ignore_errors=True)
            shutil.rmtree(tmp / f"index{n}", ignore_errors=True)

        window = SustainedWindow()
        for n in window.passes():
            one_pass(n)  # fresh index/writer: full work every pass
        passes = window.count
        dt = window.wall
        mibs = passes * written / (1 << 20) / dt
        log(f"config#6 end-to-end: {passes}x{written / (1 << 20):.0f} MiB, "
            f"{i} files packed in {dt:.2f}s = {mibs:.1f} MiB/s "
            f"(host {backend.name} backend)")
        return {"mib_s": round(mibs, 2), "files": i, "wall_s": round(dt, 2)}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def config7_erasure(log: Callable) -> Dict:
    """Reed-Solomon shard encode/decode throughput — BASELINE config #7.

    Times the erasure subsystem's hot path (``backend.encode_shards`` /
    ``decode_shards``: table-lookup GF(2^8) matmul + XOR-reduce under
    jit(vmap) on device, numpy oracle on CPU) over batches of RS_K+RS_M
    stripes.  Decode reconstructs from the WORST-case survivor set (all
    parity shards in play) so the recovery-matrix solve is real work, and
    the gate demands bit-identical output: encode must match the gf_cpu
    oracle, decode must reproduce the original data shards exactly.
    """
    from backuwup_tpu import defaults
    from backuwup_tpu.erasure import gf_cpu
    from backuwup_tpu.ops.backend import select_backend

    k, m = int(defaults.RS_K), int(defaults.RS_M)
    shard_kib = int(os.environ.get("BENCH_C7_SHARD_KIB", "512"))
    batch = int(os.environ.get("BENCH_C7_STRIPES", "64"))
    backend = select_backend()
    ln = shard_kib << 10
    rng = np.random.default_rng(71)
    stripes = rng.integers(0, 256, (batch, k, ln), dtype=np.uint8)

    # parity + round-trip gate on one stripe before anything is timed
    parity = np.asarray(backend.encode_shards(stripes, m), dtype=np.uint8)
    ref = gf_cpu.gf_matmul(gf_cpu.generator_matrix(k, m)[k:], stripes[0])
    if not np.array_equal(parity[0], ref):
        raise RuntimeError("config #7: encode parity FAILED vs gf_cpu")
    present = list(range(m, k + m))  # first m data shards "lost"
    full = np.concatenate([stripes, parity], axis=1)
    surv = full[:, present, :]
    decoded = np.asarray(backend.decode_shards(surv, k, m, present),
                         dtype=np.uint8)
    if not np.array_equal(decoded, stripes):
        raise RuntimeError("config #7: decode round-trip FAILED")

    data_mib = batch * k * ln / (1 << 20)
    window = SustainedWindow()
    for _ in window.passes():
        p = np.asarray(backend.encode_shards(stripes, m))
        np.asarray(backend.decode_shards(surv, k, m, present))
        del p
    passes = window.count
    dt = window.wall
    # each pass encodes AND decodes the full batch of stripes
    enc_dec_mibs = passes * 2 * data_mib / dt
    log(f"config#7 erasure rs({k},{m}): {passes}x{data_mib:.0f} MiB "
        f"enc+dec in {dt:.2f}s = {enc_dec_mibs:.1f} MiB/s "
        f"({backend.name} backend)")
    return {"mib_s": round(enc_dec_mibs, 2), "rs_k": k, "rs_m": m,
            "shard_kib": shard_kib, "backend": backend.name,
            "wall_s": round(dt, 2)}


def config8_transfer(log: Callable) -> Dict:
    """Serial-vs-concurrent transfer plane over loopback p2p — config #8.

    Spins up a CoordinationServer, one source client, and N holder
    clients in-process, then runs the SAME end-to-end backup twice with
    per-send latency injected through the fault plane (a loopback socket
    is too fast for transfer order to matter otherwise):

      serial     — TRANSFER_MAX_INFLIGHT=1, TRANSFER_MAX_PEERS=1,
                   PACK_SEAL_WORKERS=0: one transfer in flight at a
                   time and a synchronous seal, the pre-transfer-plane
                   shape
      concurrent — the shipped defaults: all shards of a stripe in
                   flight to distinct peers, pipelined seal

    Both numbers land in one record so BENCH_r*.json tracks the ratio.
    This is a ratio measurement (one pass each), not a sustained-window
    throughput config.
    """
    import asyncio
    import shutil
    import tempfile
    from pathlib import Path

    from backuwup_tpu import defaults
    from backuwup_tpu.app import ClientApp
    from backuwup_tpu.net.server import CoordinationServer
    from backuwup_tpu.ops.backend import CpuBackend, NativeBackend
    from backuwup_tpu.utils import faults

    total_mib = int(os.environ.get("BENCH_C8_MIB", "4"))
    n_peers = int(os.environ.get("BENCH_C8_PEERS", "6"))
    latency_s = float(os.environ.get("BENCH_C8_LATENCY_S", "0.04"))

    saved = {k: getattr(defaults, k) for k in (
        "PACKFILE_TARGET_SIZE", "TRANSFER_MAX_INFLIGHT",
        "TRANSFER_MAX_PEERS", "PACK_SEAL_WORKERS")}
    tmp = Path(tempfile.mkdtemp(prefix="bkw_bench_c8_"))
    rng = np.random.default_rng(81)
    src = tmp / "src"
    src.mkdir()
    written = 0
    i = 0
    while written < (total_mib << 20):
        sub = src / f"d{i % 8}"
        sub.mkdir(exist_ok=True)
        n = int(rng.integers(64 << 10, 512 << 10))
        (sub / f"f{i}").write_bytes(rng.bytes(n))
        written += n
        i += 1

    async def one_backup(tag: str) -> float:
        server = CoordinationServer(db_path=str(tmp / f"server_{tag}.db"))
        port = await server.start()

        def make_app(name):
            # native chunk+hash where available: the measurement is the
            # transfer plane, not the python oracle chunker
            params = CDCParams.from_desired(16 << 10)
            try:
                backend = NativeBackend(params)
            except Exception:
                backend = CpuBackend(params)
            app = ClientApp(config_dir=tmp / tag / name / "cfg",
                            data_dir=tmp / tag / name / "data",
                            server_addr=f"127.0.0.1:{port}",
                            backend=backend)
            app.store.set_backup_path(str(src))
            return app

        a = make_app("a")
        holders = [make_app(f"p{j}") for j in range(n_peers)]
        apps = [a] + holders
        try:
            for app in apps:
                await app.start()
                app._audit_task.cancel()
            a.engine.auto_repair = False
            amt = 8 * (written + (64 << 20)) // max(1, n_peers)
            for peer in holders:
                a.store.add_peer_negotiated(peer.client_id, amt)
                peer.store.add_peer_negotiated(a.client_id, amt)
                server.db.save_storage_negotiated(
                    bytes(a.client_id), bytes(peer.client_id), amt)
            t0 = time.time()
            snapshot = await asyncio.wait_for(a.backup(), 600)
            if not snapshot:
                raise RuntimeError(f"config #8 {tag}: backup returned none")
            return time.time() - t0
        finally:
            for app in apps:
                try:
                    await app.stop()
                except Exception:
                    pass
            await server.stop()

    async def both() -> Dict:
        # always-fire latency on every FILE send: makes the run
        # transfer-bound so overlap (or its absence) dominates the wall
        faults.install(faults.FaultPlane(seed=8, latency=1.0,
                                         latency_s=latency_s))
        try:
            defaults.PACKFILE_TARGET_SIZE = 128 * 1024
            defaults.TRANSFER_MAX_INFLIGHT = 1
            defaults.TRANSFER_MAX_PEERS = 1
            defaults.PACK_SEAL_WORKERS = 0
            serial_wall = await one_backup("serial")
            defaults.TRANSFER_MAX_INFLIGHT = saved["TRANSFER_MAX_INFLIGHT"]
            defaults.TRANSFER_MAX_PEERS = saved["TRANSFER_MAX_PEERS"]
            defaults.PACK_SEAL_WORKERS = saved["PACK_SEAL_WORKERS"]
            concurrent_wall = await one_backup("concurrent")
            return {"serial": serial_wall, "concurrent": concurrent_wall}
        finally:
            faults.uninstall()

    try:
        walls = asyncio.run(both())
        data_mib = written / (1 << 20)
        serial = data_mib / walls["serial"]
        concurrent = data_mib / walls["concurrent"]
        speedup = walls["serial"] / walls["concurrent"]
        log(f"config#8 transfer: {data_mib:.0f} MiB to {n_peers} peers "
            f"(+{latency_s * 1000:.0f}ms/send): serial {serial:.2f} MiB/s, "
            f"concurrent {concurrent:.2f} MiB/s = {speedup:.2f}x")
        return {"mib_s": round(concurrent, 2),
                "serial_mib_s": round(serial, 2),
                "speedup": round(speedup, 2), "peers": n_peers,
                "latency_ms": round(latency_s * 1000, 1),
                "wall_s": round(walls["serial"] + walls["concurrent"], 2)}
    finally:
        for k, v in saved.items():
            setattr(defaults, k, v)
        shutil.rmtree(tmp, ignore_errors=True)


def config10_wan(log: Callable) -> Dict:
    """Resume-enabled vs restart-from-zero over a cut WAN link — #10.

    One source and one holder over loopback p2p, a 512 KiB payload
    chunked into 16 KiB FILE_PART frames, and the SAME two armed
    exact-offset cuts (at 256 KiB and 384 KiB) severing the connection
    mid-transfer in both legs:

      resume  — TRANSFER_RESUME_ENABLED semantics: each reconnect runs
                the RESUME_QUERY/RESUME_OFFER handshake and continues
                from the receiver's verified partial
      restart — resume negotiation disabled, so every reconnect starts
                the file over from byte zero (the pre-resume shape)

    Both legs report sender-side bytes-on-wire (the
    bkw_p2p_bytes_sent_total delta — every outbound frame crosses the
    one transport chokepoint) and wall clock in one record; the ratio
    is the acceptance number (expected ~0.44, gate <= 0.6).
    """
    import asyncio
    import contextlib
    import shutil
    import tempfile
    from pathlib import Path

    from backuwup_tpu import defaults, wire
    from backuwup_tpu.app import ClientApp
    from backuwup_tpu.net.p2p import P2PError
    from backuwup_tpu.net.server import CoordinationServer
    from backuwup_tpu.obs import metrics as obs_metrics
    from backuwup_tpu.utils import faults

    payload_kib = int(os.environ.get("BENCH_C10_KIB", "512"))
    chunk_kib = int(os.environ.get("BENCH_C10_CHUNK_KIB", "16"))
    cuts = (payload_kib << 10) // 2, 3 * (payload_kib << 10) // 4

    saved = defaults.TRANSFER_CHUNK_BYTES
    tmp = Path(tempfile.mkdtemp(prefix="bkw_bench_c10_"))
    rng = np.random.default_rng(101)
    data = rng.bytes(payload_kib << 10)

    def wire_bytes() -> float:
        fam = obs_metrics.registry().snapshot().get(
            "bkw_p2p_bytes_sent_total") or {}
        return sum(s["value"] for s in fam.get("series", []))

    async def one_leg(a: ClientApp, holder_id: bytes, plane,
                      file_id: bytes, resume: bool) -> Dict:
        plane.arm_cut(holder_id, *cuts)
        before, t0 = wire_bytes(), time.time()
        t = await a.node.connect(holder_id, wire.RequestType.TRANSPORT,
                                 timeout=10.0)
        try:
            for _ in range(len(cuts) + 2):
                try:
                    await t.send_file(data, wire.FileInfoKind.PACKFILE,
                                      file_id, resume=resume)
                    break
                except P2PError:
                    t = await a.node.connect(
                        holder_id, wire.RequestType.TRANSPORT, timeout=10.0)
            else:
                raise RuntimeError("config #10: transfer never completed")
        finally:
            with contextlib.suppress(Exception):
                await t.close()
        return {"bytes_wire": round(wire_bytes() - before),
                "wall_s": round(time.time() - t0, 3)}

    async def both() -> Dict:
        plane = faults.install(faults.FaultPlane(seed=101))
        server = CoordinationServer(db_path=str(tmp / "server.db"))
        port = await server.start()

        def make_app(name):
            app = ClientApp(config_dir=tmp / name / "cfg",
                            data_dir=tmp / name / "data",
                            server_addr=f"127.0.0.1:{port}",
                            tls=False)  # plaintext loopback deployment
            return app

        a, h = make_app("a"), make_app("h")
        try:
            for app in (a, h):
                await app.start()
                app._audit_task.cancel()
            amt = 64 << 20
            a.store.add_peer_negotiated(h.client_id, amt)
            h.store.add_peer_negotiated(a.client_id, amt)
            server.db.save_storage_negotiated(
                bytes(a.client_id), bytes(h.client_id), amt)
            legs = {}
            legs["resume"] = await one_leg(
                a, h.client_id, plane, bytes(range(32)), resume=True)
            legs["restart"] = await one_leg(
                a, h.client_id, plane, bytes(range(32, 64)), resume=False)
            return legs
        finally:
            for app in (a, h):
                with contextlib.suppress(Exception):
                    await app.stop()
            await server.stop()
            faults.uninstall()

    try:
        defaults.TRANSFER_CHUNK_BYTES = chunk_kib << 10
        legs = asyncio.run(both())
        ratio = legs["resume"]["bytes_wire"] / max(
            legs["restart"]["bytes_wire"], 1)
        log(f"config#10 wan resume: {payload_kib} KiB across 2 cuts: "
            f"resume {legs['resume']['bytes_wire']} B on wire in "
            f"{legs['resume']['wall_s']}s, restart "
            f"{legs['restart']['bytes_wire']} B in "
            f"{legs['restart']['wall_s']}s = {ratio:.2f}x")
        return {"payload_kib": payload_kib, "chunk_kib": chunk_kib,
                "cut_offsets": list(cuts), "resume": legs["resume"],
                "restart": legs["restart"], "ratio": round(ratio, 3),
                "wall_s": round(legs["resume"]["wall_s"]
                                + legs["restart"]["wall_s"], 2)}
    finally:
        defaults.TRANSFER_CHUNK_BYTES = saved
        shutil.rmtree(tmp, ignore_errors=True)


def config9_scenario(log: Callable) -> Dict:
    """Composed chaos scenario + scorecard gate — config #9.

    Runs the seeded ``composed`` scenario (scenario/harness.py: backup,
    sustained churn, byzantine corrupt-shard audit demotion, sourceless
    repair, backup + restore + repair racing the exclusivity lock) and
    embeds the full scorecard in the BENCH record, so every bench run
    doubles as a durability regression gate: ``passed`` flips false if
    any hard assertion (zero invariant-violation-seconds, verified
    restore, shards rebuilt, final status ok) regresses.
    """
    import asyncio
    import tempfile
    from pathlib import Path

    from backuwup_tpu.scenario import builtin_scenarios, run_scenario

    spec = builtin_scenarios()["composed"]
    with tempfile.TemporaryDirectory(prefix="bkw_bench_scenario_") as td:
        card = asyncio.run(run_scenario(spec, Path(td)))
    counters = card.counters
    rebuilt = counters.get("bkw_repair_shards_rebuilt_total", 0)
    log(f"config#9 scenario '{card.scenario}' (seed {card.seed}): "
        f"{'PASS' if card.passed else 'FAIL'} in {card.elapsed_s:.1f}s, "
        f"violation_s={card.invariants['violation_seconds']}, "
        f"shards_rebuilt={rebuilt:g}, "
        f"final={card.invariants['final'].get('status', '?')}")
    return {"passed": card.passed,
            "violation_seconds": card.invariants["violation_seconds"],
            "worst_status": card.invariants["worst_status"],
            "shards_rebuilt": int(rebuilt),
            "wall_s": round(card.elapsed_s, 2),
            "scorecard": card.to_dict()}


def config11_crash(log: Callable) -> Dict:
    """Crash matrix + recovery sweep cost — config #11.

    Runs the representative ``crash`` scenario (three armed commit-seam
    crashes mid-backup, each followed by a client restart, the startup
    recovery sweep, a drain re-backup, and an idempotence probe) and
    reports what crash recovery COSTS: sweeps run, items reconciled by
    category, and the sweep wall-time quantiles — with the full
    scorecard embedded so the ``recovery_clean`` hard gate regresses
    loudly in the BENCH record.
    """
    import asyncio
    import tempfile
    from pathlib import Path

    from backuwup_tpu.scenario import builtin_scenarios, run_scenario

    spec = builtin_scenarios()["crash"]
    with tempfile.TemporaryDirectory(prefix="bkw_bench_crash_") as td:
        card = asyncio.run(run_scenario(spec, Path(td)))
    counters = card.counters
    sweeps = sum(v for k, v in counters.items()
                 if k.startswith("bkw_recovery_runs_total"))
    items = {k.split("category=", 1)[1].rstrip("}"): v
             for k, v in counters.items()
             if k.startswith("bkw_recovery_items_total")}
    sweep_q = next((v for k, v in card.quantiles.items()
                    if k.startswith("bkw_recovery_seconds")), {})
    log(f"config#11 crash '{card.scenario}' (seed {card.seed}): "
        f"{'PASS' if card.passed else 'FAIL'} in {card.elapsed_s:.1f}s, "
        f"sweeps={sweeps:g} reconciled={sum(items.values()):g} "
        f"sweep_p99={sweep_q.get('p99')}s")
    return {"passed": card.passed,
            "recovery_sweeps": int(sweeps),
            "items_reconciled": items,
            "sweep_seconds": sweep_q,
            "wall_s": round(card.elapsed_s, 2),
            "scorecard": card.to_dict()}


def config12_swarm(log: Callable) -> Dict:
    """Sharded vs single-lock coordination plane — config #12.

    Two measurements land in ONE record:

    * **speedup legs** — the matchmaker + store pair driven directly by
      time-boxed client coroutines (same file-backed sqlite, same fsync
      discipline, same per-candidate audit-history scan weight in both
      legs): ``baseline`` is the legacy single-lock StorageQueue over
      the direct-commit store, ``sharded`` the pubkey-sharded matchmaker
      over the write-behind store.  The gate is sharded ≥ 2x baseline
      matchmakings/s.  The legs bypass HTTP deliberately: on a
      single-core box the identical per-request HTTP/auth cost dominates
      both tiers and hides the coordination-layer difference.
    * **swarm evidence** — the full HTTP swarm scenario (register, WS
      push, seeded request mix, churn) on the sharded tier, embedding
      the scorecard whose hard gates assert the p99 is measured, the
      event loop never stalled past budget, and no sqlite commit ran on
      the loop thread.
    """
    import asyncio
    import dataclasses
    import tempfile
    from pathlib import Path

    from backuwup_tpu.scenario import (MatchLoadSpec, builtin_swarms,
                                       run_match_load, run_swarm)

    clients = int(os.environ.get("BENCH_C12_CLIENTS", "128"))
    duration_s = float(os.environ.get("BENCH_C12_S", "2.5"))

    spec = MatchLoadSpec(clients=clients, duration_s=duration_s)
    with tempfile.TemporaryDirectory(prefix="bkw_bench_swarm_") as td:
        baseline = run_match_load(
            dataclasses.replace(spec, legacy=True), td)
        sharded = run_match_load(spec, td)
        swarm_spec = builtin_swarms()["swarm"]
        card, swarm = asyncio.run(run_swarm(swarm_spec, Path(td)))
    speedup = (sharded["matchmakings_per_s"]
               / max(baseline["matchmakings_per_s"], 1e-9))
    passed = speedup >= 2.0 and card.passed
    log(f"config#12 swarm: {clients} clients x {duration_s:.1f}s: "
        f"baseline {baseline['matchmakings_per_s']:.0f} mm/s, "
        f"sharded {sharded['matchmakings_per_s']:.0f} mm/s = "
        f"{speedup:.2f}x; http swarm p99={swarm['server_p99_ms']}ms "
        f"stall={swarm['max_stall_ms']}ms "
        f"commits_on_loop={swarm['commits_on_loop']} "
        f"[{'PASS' if passed else 'FAIL'}]")
    return {"passed": passed,
            "matchmakings_per_s": sharded["matchmakings_per_s"],
            "baseline_matchmakings_per_s": baseline["matchmakings_per_s"],
            "speedup": round(speedup, 2),
            "server_p99_ms": swarm["server_p99_ms"],
            "max_stall_ms": swarm["max_stall_ms"],
            "commits_on_loop": swarm["commits_on_loop"],
            "legs": {"baseline": baseline, "sharded": sharded},
            "swarm": swarm,
            "scorecard": card.to_dict()}


def config13_restore(log: Callable) -> Dict:
    """Serial all-holder RESTORE_ALL vs multi-source k-of-n restore — #13.

    One loopback deployment (CoordinationServer, one source, N holders),
    one striped backup, then the SAME restore twice into different
    destinations, both legs in one record:

      serial — the pre-pull-plane shape: the placement map is ignored
               (``_restore_plan`` forced to None) so every holder pushes
               its entire stream and the wall clock waits out the
               slowest; one holder's frames are armed with a per-send
               stall through the fault plane, the WAN shape where one
               seeder crawls
      multi  — the shard-granular pull planner: each stripe from its k
               fastest holders by the peer-stats estimators (the crawler
               is measured-slow, so it is a spare, not a primary), with
               a second holder killed dark between the legs so its
               re-queued pulls must land on healthier peers

    ``speedup`` is serial/multi wall (gate >= 2x), ``bytes_ratio`` is
    multi/serial sender-side bytes-on-wire (the bkw_p2p_bytes_sent_total
    delta; k/n = 4/6 floor ~= 0.67, gate <= 0.8).  Ratio measurement,
    one pass each — not a sustained-window config.
    """
    import asyncio
    import contextlib
    import shutil
    import tempfile
    from pathlib import Path

    from backuwup_tpu import defaults
    from backuwup_tpu.app import ClientApp
    from backuwup_tpu.net.peer_stats import PeerEstimate
    from backuwup_tpu.net.server import CoordinationServer
    from backuwup_tpu.obs import metrics as obs_metrics
    from backuwup_tpu.ops.backend import CpuBackend, NativeBackend
    from backuwup_tpu.utils import faults

    total_mib = int(os.environ.get("BENCH_C13_MIB", "2"))
    n_peers = int(os.environ.get("BENCH_C13_PEERS", "6"))
    latency_s = float(os.environ.get("BENCH_C13_LATENCY_S", "0.4"))

    saved = {k: getattr(defaults, k) for k in (
        "PACKFILE_TARGET_SIZE", "RESTORE_REQUEST_THROTTLE_S")}
    tmp = Path(tempfile.mkdtemp(prefix="bkw_bench_c13_"))
    rng = np.random.default_rng(131)
    src = tmp / "src"
    src.mkdir()
    written = 0
    i = 0
    while written < (total_mib << 20):
        sub = src / f"d{i % 8}"
        sub.mkdir(exist_ok=True)
        n = int(rng.integers(64 << 10, 256 << 10))
        (sub / f"f{i}").write_bytes(rng.bytes(n))
        written += n
        i += 1

    def wire_bytes() -> float:
        fam = obs_metrics.registry().snapshot().get(
            "bkw_p2p_bytes_sent_total") or {}
        return sum(s["value"] for s in fam.get("series", []))

    def tree_bytes(root: Path) -> int:
        return sum(p.stat().st_size for p in root.rglob("*") if p.is_file())

    async def both() -> Dict:
        plane = faults.install(faults.FaultPlane(seed=131))
        server = CoordinationServer(db_path=str(tmp / "server.db"))
        port = await server.start()

        def make_app(name):
            # native chunk+hash where available: the measurement is the
            # restore data plane, not the python oracle chunker
            params = CDCParams.from_desired(16 << 10)
            try:
                backend = NativeBackend(params)
            except Exception:
                backend = CpuBackend(params)
            app = ClientApp(config_dir=tmp / name / "cfg",
                            data_dir=tmp / name / "data",
                            server_addr=f"127.0.0.1:{port}",
                            backend=backend,
                            tls=False)  # plaintext loopback deployment
            return app

        a = make_app("a")
        a.store.set_backup_path(str(src))
        holders = [make_app(f"p{j}") for j in range(n_peers)]
        apps = [a] + holders
        try:
            for app in apps:
                await app.start()
                app._audit_task.cancel()
            a.engine.auto_repair = False
            amt = 8 * (written + (64 << 20)) // max(1, n_peers)
            for peer in holders:
                a.store.add_peer_negotiated(peer.client_id, amt)
                peer.store.add_peer_negotiated(a.client_id, amt)
                server.db.save_storage_negotiated(
                    bytes(a.client_id), bytes(peer.client_id), amt)
            snapshot = await asyncio.wait_for(a.backup(), 600)
            if not snapshot:
                raise RuntimeError("config #13: backup returned none")
            placed = sorted({bytes(peer) for _, peer, _s, idx, _ in
                             a.store.all_placements() if idx >= 0})
            if len(placed) < 3:
                raise RuntimeError(
                    f"config #13: only {len(placed)} striped holders")
            slow, dark = placed[0], placed[1]
            # seed the live estimator bank (ranking reads memory, not the
            # store): the crawling holder is measured-slow so the planner
            # leaves it as a spare; the soon-to-be-dark holder ranks
            # fastest so its failed pulls must re-queue onto the rest
            ps = a.engine.peer_stats
            with ps._lock:
                for j, peer in enumerate(placed):
                    bps = {slow: 1e3, dark: 100e6}.get(peer, (50 + j) * 1e6)
                    ps._est[peer] = PeerEstimate(
                        peer=peer, throughput_bps=bps, latency_s=0.01,
                        success=1.0, samples=10, updated=time.time())
            # slow-seeder injection: pace every file the slow holder
            # serves (both protocols — the holder is slow, period; the
            # multi leg wins by ROUTING around it, not by a kinder fault)
            slow_app = next(h for h in holders
                            if bytes(h.client_id) == slow)

            def paced(serve):
                async def run(peer_id, transport):
                    real = transport.send_file

                    async def crawl(*args, **kw):
                        await asyncio.sleep(latency_s)
                        return await real(*args, **kw)
                    transport.send_file = crawl
                    return await serve(peer_id, transport)
                return run

            slow_app.node.serve_restore = paced(
                slow_app.node.serve_restore)
            slow_app.node.serve_restore_fetch = paced(
                slow_app.node.serve_restore_fetch)

            async def one_restore(tag: str) -> Dict:
                before, t0 = wire_bytes(), time.time()
                out = await asyncio.wait_for(
                    a.restore(dest=tmp / f"out_{tag}"), 600)
                wall = time.time() - t0
                if tree_bytes(Path(out)) != written:
                    raise RuntimeError(
                        f"config #13 {tag}: restored size mismatch")
                return {"bytes_wire": round(wire_bytes() - before),
                        "wall_s": round(wall, 3)}

            legs = {}
            a.engine._restore_plan = lambda: None  # force legacy streams
            try:
                legs["serial"] = await one_restore("serial")
            finally:
                del a.engine._restore_plan
            plane.kill(dark)  # holder goes dark between the legs
            legs["multi"] = await one_restore("multi")
            legs["slow"], legs["dark"] = slow.hex()[:16], dark.hex()[:16]
            return legs
        finally:
            for app in apps:
                with contextlib.suppress(Exception):
                    await app.stop()
            await server.stop()
            faults.uninstall()

    try:
        defaults.PACKFILE_TARGET_SIZE = 128 * 1024
        defaults.RESTORE_REQUEST_THROTTLE_S = 0.0
        legs = asyncio.run(both())
        data_mib = written / (1 << 20)
        speedup = legs["serial"]["wall_s"] / legs["multi"]["wall_s"]
        ratio = legs["multi"]["bytes_wire"] / max(
            legs["serial"]["bytes_wire"], 1)
        passed = speedup >= 2.0 and ratio <= 0.8
        log(f"config#13 restore: {data_mib:.0f} MiB from {n_peers} holders "
            f"(+{latency_s * 1000:.0f}ms/frame to one): serial "
            f"{legs['serial']['wall_s']}s / multi {legs['multi']['wall_s']}s"
            f" = {speedup:.2f}x, bytes {ratio:.2f}x "
            f"[{'PASS' if passed else 'FAIL'}]")
        return {"mib_s": round(data_mib / legs["multi"]["wall_s"], 2),
                "serial_mib_s": round(data_mib / legs["serial"]["wall_s"],
                                      2),
                "speedup": round(speedup, 2),
                "bytes_ratio": round(ratio, 3),
                "passed": passed,
                "serial": legs["serial"], "multi": legs["multi"],
                "slow_holder": legs["slow"], "dark_holder": legs["dark"],
                "peers": n_peers,
                "latency_ms": round(latency_s * 1000, 1),
                "data_mib": round(data_mib, 2),
                "wall_s": round(legs["serial"]["wall_s"]
                                + legs["multi"]["wall_s"], 2)}
    finally:
        for k, v in saved.items():
            setattr(defaults, k, v)
        shutil.rmtree(tmp, ignore_errors=True)


def config15_gc(log: Callable) -> Dict:
    """Snapshot lifecycle plane: retention + GC under a crash — #15.

    Runs a dedicated GC scenario (scenario/harness.py): populate via
    backup, then a ``gc`` phase with ONE armed commit seam
    (``gc.swap.post`` — the make-before-break commit point): retention
    prunes to keep-last:1, the GC run crashes at the seam, the client
    restarts, the startup recovery sweep rolls the interrupted swap
    forward, and a clean re-run finishes reclaiming; a final ``restore``
    phase proves the post-GC world restores byte-identically.

    Hard gates (the scorecard's, restated in the record): bytes actually
    reclaimed on the holders (> 0 at both ends of the RECLAIM protocol),
    zero durability-violation seconds at every sample while packfiles
    were dropped and compacted, and the byte-identical final restore.
    ``gc_reclaim_ratio`` is reclaimed-bytes / bytes-on-wire for the whole
    run — how much of what the run shipped GC later proved dead.
    """
    import asyncio
    import tempfile
    from pathlib import Path

    from backuwup_tpu.scenario import (Phase, ScenarioSpec, builtin_scenarios,
                                       run_scenario)

    site = os.environ.get("BENCH_C15_SITE", "gc.swap.post")
    spec = ScenarioSpec(
        name="gc_bench", seed=151,
        corpus_files=builtin_scenarios()["gc"].corpus_files,
        phases=(Phase("backup"),
                Phase("gc", sites=(site,)),
                Phase("restore")))
    with tempfile.TemporaryDirectory(prefix="bkw_bench_gc_") as td:
        card = asyncio.run(run_scenario(spec, Path(td)))
    counters = card.counters
    reclaimed = sum(v for k, v in counters.items()
                    if k.startswith("bkw_gc_bytes_reclaimed_total"))
    freed = sum(v for k, v in counters.items()
                if k.startswith("bkw_reclaim_bytes_freed_total"))
    dropped = sum(v for k, v in counters.items()
                  if k.startswith("bkw_gc_packfiles_dropped_total"))
    compacted = sum(v for k, v in counters.items()
                    if k.startswith("bkw_gc_packfiles_compacted_total"))
    wire = sum(v for k, v in counters.items()
               if k.startswith("bkw_transfer_bytes_total"))
    ratio = reclaimed / max(wire, 1.0)
    violation_s = card.invariants["violation_seconds"]
    passed = card.passed and reclaimed > 0 and freed > 0 \
        and violation_s == 0
    log(f"config#15 gc '{card.scenario}' (seed {card.seed}, crash {site}):"
        f" {'PASS' if passed else 'FAIL'} in {card.elapsed_s:.1f}s, "
        f"reclaimed={reclaimed / 1024:.0f}KiB freed={freed / 1024:.0f}KiB "
        f"dropped={dropped:g} compacted={compacted:g} "
        f"ratio={ratio:.3f} violation_s={violation_s}")
    return {"passed": passed,
            "gc_reclaim_ratio": round(ratio, 4),
            "bytes_reclaimed": int(reclaimed),
            "holder_bytes_freed": int(freed),
            "packfiles_dropped": int(dropped),
            "packfiles_compacted": int(compacted),
            "violation_seconds": violation_s,
            "crash_site": site,
            "wall_s": round(card.elapsed_s, 2),
            "scorecard": card.to_dict()}


def config14_multichip(log: Callable, n_devices: int = 0) -> Dict:
    """Matched-work single-device vs mesh manifest plane — config #14.

    The SAME staged batch (``BENCH_C14_ROWS_PER_DEV`` rows per device x
    ``BENCH_C14_ROW_KIB`` KiB of random bytes) runs through the
    zero-round-trip single-device driver and through the shard-mapped
    mesh driver (:meth:`DevicePipeline.manifest_segments_mesh`) with the
    manifest->dedup handoff attached (``MeshDedupIndex``), so the record
    captures the whole production multi-chip path: per-shard leaf pools,
    per-device dispatch accounting, and device-resident classify.

    Gates enforced on EVERY platform (forced-8 CPU mesh included):

      * parity — mesh rows bit-identical to the single-device rows, and
        to the CPU oracle on a downloaded row
      * even split — per-device digest dispatch counts within +-1
      * handoff — index-stage dispatches == device batches (classify
        rides ``insert_device``; zero per-batch host round trips), and
        the device found-vector classifies the warmed corpus duplicate

    The wall-clock gate (``speedup >= BENCH_C14_SPEEDUP_GATE``, default
    1.5) arms only on real hardware: a forced-8-device CPU "mesh"
    timeshares one host core pool, so its speedup measures shard_map
    overhead, not scale.
    """
    import pathlib
    import shutil
    import tempfile

    from jax.sharding import Mesh

    from backuwup_tpu.crypto import KeyManager
    from backuwup_tpu.obs import profile as obs_profile
    from backuwup_tpu.snapshot.blob_index import BlobIndex
    from backuwup_tpu.snapshot.device_dedup import MeshDedupIndex

    n_dev = n_devices or int(os.environ.get("BENCH_C14_DEVICES", "8"))
    n_dev = max(1, min(n_dev, jax.device_count()))
    rows_per_dev = int(os.environ.get("BENCH_C14_ROWS_PER_DEV", "2"))
    P = int(os.environ.get("BENCH_C14_ROW_KIB", "1024")) << 10
    B = n_dev * rows_per_dev
    params = CDCParams.from_desired(16 << 10)
    pass_mib = B * P / (1 << 20)

    pipe1 = DevicePipeline(params)
    if not pipe1.pool_digest:
        log("config#14: leaf-pool digest unavailable; mesh plane skipped")
        return {"skipped": "pool_digest unavailable"}

    rng = np.random.default_rng(141)
    buf = np.zeros((B, _HALO + P), dtype=np.uint8)
    buf[:, _HALO:] = rng.integers(0, 256, (B, P), dtype=np.uint8)
    nv = np.full(B, P, dtype=np.int32)
    buf1 = jnp.asarray(buf)

    # --- leg 1: single device, zero-round-trip driver ---------------------
    (single,) = list(pipe1.manifest_segments_device(
        [(buf1, nv)], strict_overflow=True))  # warm + parity reference
    w1 = SustainedWindow(2)
    for _ in w1.passes():
        for _rows in pipe1.manifest_segments_device([(buf1, nv)],
                                                    strict_overflow=True):
            pass
    mibs1 = w1.count * pass_mib / w1.wall

    # --- leg 2: mesh driver + device-resident dedup handoff ---------------
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="bkw_bench_c14_"))
    try:
        dedup = MeshDedupIndex(
            mesh, BlobIndex(KeyManager.from_secret(b"\x0e" * 32),
                            tmp / "index"))
        pipe_n = DevicePipeline(params, mesh=mesh)
        ((mesh_rows, _fl),) = list(pipe_n.manifest_segments_mesh(
            [(buf, nv)], strict_overflow=True, dedup=dedup))  # warm
        for r in range(B):
            if mesh_rows[r][0] != single[r][0] or not np.array_equal(
                    mesh_rows[r][1], single[r][1]):
                raise RuntimeError("config #14: mesh/single parity FAILED")
        _check(mesh_rows[0], bytes(buf[0, _HALO:]), params, "#14")

        base = obs_profile.baseline()
        batches = 0
        dup_flags_ok = True
        w2 = SustainedWindow(2)
        for _ in w2.passes():
            for _rows, flags in pipe_n.manifest_segments_mesh(
                    [(buf, nv)], strict_overflow=True, dedup=dedup):
                batches += 1
                for fl in flags:
                    # the warm pass made every key resident: the device
                    # found-vector must classify all-duplicate
                    if fl is None or not all(bool(x) for x in fl):
                        dup_flags_ok = False
        mibs_n = w2.count * pass_mib / w2.wall
        rep = obs_profile.report(base)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    dev_disp = rep.get("device_dispatches", {})
    digest_counts = [dev_disp.get(str(d), {}).get("digest", 0)
                     for d in range(n_dev)]
    delta = max(digest_counts) - min(digest_counts)
    if delta > 1:
        raise RuntimeError(f"config #14: uneven shard split {digest_counts}")
    if rep["dispatches"]["index"] != batches:
        raise RuntimeError(
            f"config #14: handoff made host round trips "
            f"({rep['dispatches']['index']} index dispatches for "
            f"{batches} batches)")
    for d in range(n_dev):
        if dev_disp.get(str(d), {}).get("index", 0) != batches:
            raise RuntimeError(
                f"config #14: device {d} index dispatches "
                f"{dev_disp.get(str(d), {}).get('index', 0)} != {batches}")
    if not dup_flags_ok:
        raise RuntimeError("config #14: device classify missed residency")

    speedup = mibs_n / mibs1 if mibs1 > 0 else 0.0
    gate = float(os.environ.get("BENCH_C14_SPEEDUP_GATE", "1.5"))
    armed = jax.devices()[0].platform != "cpu"
    if armed and speedup < gate:
        raise RuntimeError(
            f"config #14: multichip speedup {speedup:.2f}x < {gate}x")
    log(f"config#14 multichip: 1dev {mibs1:.1f} MiB/s vs {n_dev}dev "
        f"{mibs_n:.1f} MiB/s = {speedup:.2f}x "
        f"({'gate armed' if armed else 'gate recorded only, CPU mesh'}; "
        f"digest split {digest_counts})")
    return {"n_devices": n_dev, "mib_s_1dev": round(mibs1, 2),
            "mib_s_mesh": round(mibs_n, 2), "speedup": round(speedup, 3),
            "speedup_gate_armed": armed,
            "device_dispatches": dev_disp,
            "device_pad_efficiency": rep.get("device_pad_efficiency", {}),
            "even_split_max_delta": delta,
            "index_dispatches": rep["dispatches"]["index"],
            "batches": batches,
            "hbm_high_water_bytes": max(
                pipe_n.mesh_hbm_high_water.values(), default=0),
            "wall_s": round(w1.wall + w2.wall, 2)}


def config16_federation(log: Callable) -> Dict:
    """Federated coordination plane — config #16.

    Two measurements land in ONE record:

    * **scaling legs** — the SAME seeded client universe driven at
      1, 2, and 4 nodes, each node a real OS process with its own
      ServerStore partition file and real ``/fed/steal`` HTTP between
      processes (scenario/federation.py).  ``federation_speedup_2node``
      / ``_4node`` are always recorded; the throughput gates
      (≥ ``BENCH_C16_SPEEDUP_GATE_2`` = 1.6x at 2 nodes,
      ≥ ``BENCH_C16_SPEEDUP_GATE_4`` = 2.8x at 4 nodes) arm only when
      the host has ≥ 4 CPUs (or ``BENCH_C16_FORCE_GATE=1``): node
      processes timesharing one core measure scheduler overhead, not
      scale — the config-14 precedent.
    * **churn evidence** — the full HTTP federation swarm (3 nodes over
      one partitioned store, client failover, a node kill + same-port
      revive mid-run), embedding the scorecard whose hard gates assert
      zero lost matchmakings (durable negotiation rows ≥ 2x total
      matchmakings across every partition), post-revive matchmaking
      flow, at least one client failover, and bounded per-route p99.
    """
    import asyncio
    import dataclasses
    import tempfile
    from pathlib import Path

    from backuwup_tpu.scenario import builtin_swarms, run_swarm
    from backuwup_tpu.scenario.federation import (FederationLoadSpec,
                                                  run_federation_load)

    clients = int(os.environ.get("BENCH_C16_CLIENTS", "64"))
    duration_s = float(os.environ.get("BENCH_C16_S", "2.0"))
    spec = FederationLoadSpec(nodes=1, clients=clients,
                              duration_s=duration_s)
    legs = {}
    with tempfile.TemporaryDirectory(prefix="bkw_bench_fed_") as td:
        for n in (1, 2, 4):
            legs[n] = run_federation_load(
                dataclasses.replace(spec, nodes=n), Path(td) / f"n{n}")
        card, swarm = asyncio.run(run_swarm(
            builtin_swarms()["federation"], Path(td) / "churn"))
    base = max(legs[1]["matchmakings_per_s"], 1e-9)
    speedup2 = legs[2]["matchmakings_per_s"] / base
    speedup4 = legs[4]["matchmakings_per_s"] / base
    gate2 = float(os.environ.get("BENCH_C16_SPEEDUP_GATE_2", "1.6"))
    gate4 = float(os.environ.get("BENCH_C16_SPEEDUP_GATE_4", "2.8"))
    armed = ((os.cpu_count() or 1) >= 4
             or os.environ.get("BENCH_C16_FORCE_GATE") == "1")
    scaling_ok = (not armed) or (speedup2 >= gate2 and speedup4 >= gate4)
    passed = scaling_ok and card.passed
    mode = "gates armed" if armed else "gates recorded only, few-core host"
    log(f"config#16 federation: {clients} clients x {duration_s:.1f}s: "
        f"1n {legs[1]['matchmakings_per_s']:.0f} mm/s, "
        f"2n {legs[2]['matchmakings_per_s']:.0f} ({speedup2:.2f}x), "
        f"4n {legs[4]['matchmakings_per_s']:.0f} ({speedup4:.2f}x) "
        f"({mode}); churn swarm: "
        f"failovers={swarm['failovers']} rows={swarm['negotiated_rows']} "
        f"mm={swarm['total_matchmakings']} p99={swarm['server_p99_ms']}ms "
        f"[{'PASS' if passed else 'FAIL'}]")
    return {"passed": passed,
            "federation_speedup_2node": round(speedup2, 2),
            "federation_speedup_4node": round(speedup4, 2),
            "speedup_gate_armed": armed,
            "matchmakings_per_s_1node": legs[1]["matchmakings_per_s"],
            "matchmakings_per_s_2node": legs[2]["matchmakings_per_s"],
            "matchmakings_per_s_4node": legs[4]["matchmakings_per_s"],
            "steals_2node": legs[2]["steals"],
            "steals_4node": legs[4]["steals"],
            "churn_failovers": swarm["failovers"],
            "churn_negotiated_rows": swarm["negotiated_rows"],
            "churn_total_matchmakings": swarm["total_matchmakings"],
            "server_p99_ms": swarm["server_p99_ms"],
            "legs": {f"{n}node": legs[n] for n in (1, 2, 4)},
            "swarm": swarm,
            "scorecard": card.to_dict()}


def config17_tiered(log: Callable) -> Dict:
    """Tiered dedup index — config #17 (docs/dedup_tiering.md).

    One ``TieredDedupIndex`` is populated to ~12x its HBM budget (the
    hot table is HARD-capped; the overflow demotes into the cold LSM
    store), then probed through two legs:

    * **skewed** — ``BENCH_C17_HOT_FRACTION`` (default 0.97) of every
      batch drawn from a working set sized to fit the hot table, the
      rest uniform over the whole population (the real-corpus shape:
      incremental backups re-probe recent fingerprints)
    * **uniform** — batches drawn uniformly over the population, the
      adversarial shape that must fall through to the cold tier

    Gates enforced on EVERY platform (CPU mesh included — all three
    are deterministic counting/parity claims, not wall clock):

      * parity — every classification during population bit-identical
        to the BlobIndex oracle, and a post-population sample must
        classify all-duplicate while fresh keys classify all-new
      * budget — ``bkw_tier_hbm_highwater_bytes`` never exceeds the
        budget while the population is >= 10x the hot slot count
      * hit rate — the skewed leg answers > ``BENCH_C17_HIT_GATE``
        (default 0.95) of its device probes on device
        (``bkw_tier_hits/probes_total{path=device}`` deltas — the
        ROADMAP's >95% device-path claim, surfaced as
        ``tiered_hit_rate``)

    The wall gate (skewed leg >= ``BENCH_C17_WALL_GATE`` x the uniform
    leg's probe throughput, default 1.2) arms only on real hardware:
    a forced CPU mesh timeshares the host with the cold tier's numpy
    path, so the ratio measures dispatch overhead, not HBM locality.
    """
    import pathlib
    import shutil
    import tempfile

    from jax.sharding import Mesh

    from backuwup_tpu.crypto import KeyManager
    from backuwup_tpu.dedupstore import TieredDedupIndex
    from backuwup_tpu.obs import metrics as obs_metrics
    from backuwup_tpu.snapshot.blob_index import BlobIndex

    def _tier(name, **labels):
        m = obs_metrics.registry().get(name)
        return 0.0 if m is None else m.value(**labels)

    n_dev = max(1, min(int(os.environ.get("BENCH_C17_DEVICES", "8")),
                       jax.device_count()))
    population = int(os.environ.get("BENCH_C17_POPULATION", "200000"))
    batch = int(os.environ.get("BENCH_C17_BATCH", "4096"))
    hot_frac = float(os.environ.get("BENCH_C17_HOT_FRACTION", "0.97"))
    # budget sized so the population overflows the hot table ~12x
    budget = max(population // 12, n_dev * 64) * 20
    rng = np.random.default_rng(171)
    hashes = [t.tobytes()
              for t in rng.integers(0, 256, (population, 32),
                                    dtype=np.uint8)]
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="bkw_bench_c17_"))
    try:
        host = BlobIndex(KeyManager.from_secret(b"\x11" * 32),
                         tmp / "index")
        ti = TieredDedupIndex(mesh, host, cold_dir=tmp / "cold",
                              hbm_budget_bytes=budget,
                              promote_min_hits=1)
        total_slots = mesh.shape["data"] * ti.capacity
        if population < 10 * total_slots:
            raise RuntimeError(
                f"config #17: population {population} < 10x hot slots "
                f"{total_slots} — overflow claim would not be tested")
        # --- populate to ~12x budget, parity-gated against the oracle
        mismatches = 0
        for s in range(0, population, 8192):
            seg = hashes[s:s + 8192]
            for h, f in zip(seg, ti.classify_insert(seg)):
                if f != host.is_duplicate(h):
                    mismatches += 1
                host.mark_queued(h)
        if mismatches:
            raise RuntimeError(
                f"config #17: {mismatches} oracle parity mismatches")
        if _tier("bkw_tier_hbm_highwater_bytes") > budget:
            raise RuntimeError("config #17: HBM budget exceeded")
        # --- skewed leg: working set sized to the demotion keep-set
        # (a quarter of the table) so churn from the uniform tail
        # cannot push it out of HBM.  The hot lanes scan the set in
        # rotation (an incremental re-backup re-probes every recent
        # fingerprint, not a with-replacement sample — replacement
        # would collapse hot lanes under per-batch dedup and inflate
        # the tail's unique-lane share ~2x past ``1 - hot_frac``).
        hot_n = max(total_slots // 4, batch)
        hot_set = [hashes[i] for i in rng.integers(0, population, hot_n)]
        for s in range(0, hot_n, batch):  # warm: promote the hot set
            ti.classify_insert(hot_set[s:s + batch])
        d0, h0 = (_tier("bkw_tier_probes_total", path="device"),
                  _tier("bkw_tier_hits_total", path="device"))
        w1 = SustainedWindow(4)
        cursor = 0
        for _ in w1.passes():
            n_hot = int(batch * hot_frac)
            leg = [hot_set[(cursor + i) % hot_n] for i in range(n_hot)]
            cursor = (cursor + n_hot) % hot_n
            leg += [hashes[int(i)] for i in
                    rng.integers(0, population, batch - n_hot)]
            if not all(ti.classify_insert(leg)):
                raise RuntimeError("config #17: skewed leg parity FAILED")
        d1, h1 = (_tier("bkw_tier_probes_total", path="device"),
                  _tier("bkw_tier_hits_total", path="device"))
        hit_rate = (h1 - h0) / max(d1 - d0, 1.0)
        skew_pps = w1.count * batch / w1.wall
        # --- uniform leg: the cold tier carries the tail
        w2 = SustainedWindow(4)
        for _ in w2.passes():
            leg = [hashes[int(i)] for i in
                   rng.integers(0, population, batch)]
            if not all(ti.classify_insert(leg)):
                raise RuntimeError("config #17: uniform leg parity FAILED")
        uni_pps = w2.count * batch / w2.wall
        # --- fresh keys still classify new after all the churn
        fresh = [t.tobytes() for t in
                 rng.integers(0, 256, (batch, 32), dtype=np.uint8)]
        if any(ti.classify_insert(fresh)):
            raise RuntimeError("config #17: fresh keys misclassified")
        if _tier("bkw_tier_hbm_highwater_bytes") > budget:
            raise RuntimeError("config #17: HBM budget exceeded post-legs")
        hit_gate = float(os.environ.get("BENCH_C17_HIT_GATE", "0.95"))
        if hit_rate < hit_gate:
            raise RuntimeError(
                f"config #17: device hit rate {hit_rate:.3f} < {hit_gate}")
        speedup = skew_pps / max(uni_pps, 1e-9)
        wall_gate = float(os.environ.get("BENCH_C17_WALL_GATE", "1.2"))
        armed = jax.devices()[0].platform != "cpu"
        if armed and speedup < wall_gate:
            raise RuntimeError(
                f"config #17: skewed/uniform {speedup:.2f}x < {wall_gate}x")
        mode = ("wall gate armed" if armed
                else "wall gate recorded only, CPU mesh")
        log(f"config#17 tiered: {population} keys @ {total_slots} hot "
            f"slots ({population / total_slots:.0f}x): skewed "
            f"{skew_pps / 1e3:.0f}k probes/s hit {hit_rate:.3f}, uniform "
            f"{uni_pps / 1e3:.0f}k probes/s = {speedup:.2f}x ({mode}; "
            f"demotions {int(_tier('bkw_tier_demotions_total'))}, "
            f"promotions {int(_tier('bkw_tier_promotions_total'))}, "
            f"cold runs {int(_tier('bkw_tier_cold_runs'))})")
        return {"population": population,
                "hot_slots": total_slots,
                "overflow_ratio": round(population / total_slots, 1),
                "hbm_budget_bytes": budget,
                "hbm_highwater_bytes":
                    int(_tier("bkw_tier_hbm_highwater_bytes")),
                "tiered_hit_rate": round(hit_rate, 4),
                "hit_gate": hit_gate,
                "parity_mismatches": mismatches,
                "probes_per_s_skewed": round(skew_pps, 1),
                "probes_per_s_uniform": round(uni_pps, 1),
                "skew_speedup": round(speedup, 3),
                "wall_gate_armed": armed,
                "demotions": int(_tier("bkw_tier_demotions_total")),
                "promotions": int(_tier("bkw_tier_promotions_total")),
                "cold_runs": int(_tier("bkw_tier_cold_runs")),
                "cold_records": int(_tier("bkw_tier_cold_records")),
                "wall_s": round(w1.wall + w2.wall, 2)}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def config18_replication(log: Callable) -> Dict:
    """Replicated coordination metadata — config #18 (docs/server.md
    §Replication).

    Two swarm runs land in ONE record:

    * **permakill leg** — the builtin ``replication`` swarm: 3 nodes
      with PER-NODE ``ReplicatedServerStore``s (nothing shared), a
      partition-owning node killed for good mid-run.  Hard gates ride
      the scorecard: a ring successor promoted within the probe
      deadline, matchmaking flowed post-promotion, and zero durable
      negotiation rows lost (``replication_lost_rows`` is recorded
      top-level and must be 0 — the rows' only applier is gone, so
      every surviving row crossed the ship-before-ack barrier).
    * **shared-store baseline** — the SAME spec (clients, think time,
      total load-window duration) with one shared partitioned store
      behind the nodes and no kill: the only differences from the
      permakill leg are the ship barrier and the death, so the rate
      ratio prices the synchronous log ship.  Recorded, not gated —
      one-core hosts measure scheduler noise, the config-16 precedent.
    """
    import asyncio
    import dataclasses
    import tempfile
    from pathlib import Path

    from backuwup_tpu.scenario import Phase, builtin_swarms, run_swarm

    spec = builtin_swarms()["replication"]
    load_s = sum(p.duration_s or 0.0 for p in spec.phases)
    baseline = dataclasses.replace(
        spec, name="replication_shared_baseline", shared_store=True,
        phases=(Phase("register"), Phase("swarm", duration_s=load_s),
                Phase("drain")))
    with tempfile.TemporaryDirectory(prefix="bkw_bench_repl_") as td:
        repl_card, repl = asyncio.run(run_swarm(spec, Path(td) / "repl"))
        base_card, base = asyncio.run(run_swarm(
            baseline, Path(td) / "shared"))
    lost = max(0, 2 * repl["total_matchmakings"]
               - repl["negotiated_rows"])
    repl_rate = repl["total_matchmakings"] / max(repl_card.elapsed_s,
                                                 1e-9)
    base_rate = base["total_matchmakings"] / max(base_card.elapsed_s,
                                                 1e-9)
    passed = (repl_card.passed and base_card.passed and lost == 0
              and repl["promotions"] >= 1)
    log(f"config#18 replication: permakill leg "
        f"mm={repl['total_matchmakings']} rows={repl['negotiated_rows']}"
        f" lost={lost} promote={repl['repl_promote_s']}s"
        f" ({repl_rate:.0f} mm/s); shared baseline "
        f"mm={base['total_matchmakings']} ({base_rate:.0f} mm/s, "
        f"ship cost {repl_rate / max(base_rate, 1e-9):.2f}x) "
        f"[{'PASS' if passed else 'FAIL'}]")
    return {"passed": passed,
            "replication_lost_rows": lost,
            "repl_promote_s": repl["repl_promote_s"],
            "promotions": repl["promotions"],
            "post_promote_matchmakings":
                repl["post_promote_matchmakings"],
            "matchmakings_per_s_replicated": round(repl_rate, 2),
            "matchmakings_per_s_shared": round(base_rate, 2),
            "ship_cost_ratio": round(repl_rate / max(base_rate, 1e-9),
                                     3),
            "server_p99_ms": repl["server_p99_ms"],
            "swarm": repl,
            "baseline_swarm": base,
            "scorecard": repl_card.to_dict()}


def config19_sim(log: Callable) -> Dict:
    """Virtual-clock simulation plane — config #19 (docs/simulation.md).

    Two legs land in one record:

    * **throughput leg** — the tier-1 acceptance builtin
      (``regionfail``: 10⁵ clients, a simulated week, a quarter of the
      regions lost on day 2) at full scale, real matchmaking and
      serverstore on the virtual clock.  Records driver events/s and
      the time-compression ratio (virtual seconds per wall second).
      Hard gates: the scenario's own scorecard all green AND
      compression ≥ ``BENCH_C19_COMPRESSION_GATE`` (default 10⁴× — a
      simulated week inside about a wall minute).
    * **determinism leg** — ``flashcrowd`` at 2 000 clients twice with
      the same seed: the scorecards must be byte-identical
      (``card_json``), the replay contract triage leans on.
    """
    from backuwup_tpu.sim import card_json, run_sim

    clients = int(os.environ.get("BENCH_C19_CLIENTS", "100000"))
    gate = float(os.environ.get("BENCH_C19_COMPRESSION_GATE", "10000"))
    card, stats = run_sim("regionfail", clients=clients)
    d1, _ = run_sim("flashcrowd", clients=2000)
    d2, _ = run_sim("flashcrowd", clients=2000)
    deterministic = card_json(d1) == card_json(d2)
    passed = (card["passed"] and deterministic
              and stats["time_compression"] >= gate)
    log(f"config#19 sim: regionfail@{clients} simulated "
        f"{card['sim_seconds'] / 86400:.0f}d in {stats['wall_s']}s "
        f"({stats['events_per_s']:.0f} ev/s, "
        f"{stats['time_compression']:.0f}x compression vs gate "
        f"{gate:.0f}x) gates={'green' if card['passed'] else 'RED'} "
        f"determinism={'ok' if deterministic else 'BROKEN'} "
        f"[{'PASS' if passed else 'FAIL'}]")
    return {"passed": passed,
            "sim_events_per_s": stats["events_per_s"],
            "sim_time_compression": stats["time_compression"],
            "sim_wall_s": stats["wall_s"],
            "sim_events": card["events"],
            "deterministic": deterministic,
            "match_rate": card["match_rate"],
            "repair_drain_s": card["repair_drain_s"],
            "violation_client_seconds": card["violation_client_seconds"],
            "scorecard": card}


def config20_dataflow(log: Callable) -> Dict:
    """Streaming dataflow vs phased backup — config #20 (docs/dataflow.md).

    The SAME end-to-end backup (one source, N holders over loopback,
    fault-plane latency on every send so the wire leg is comparable to
    the pack leg on a one-core host) runs twice over identical corpora:

      phased — ``BKW_BACKUP_PHASED=1``: the send loop starts only after
               the packer finishes, wall = sum(stage), the pre-dataflow
               shape
      stream — shipped default: sealed packfiles enter transfer
               admission the moment they commit, wall -> max(stage)

    Gates (both hard):
      * stream overlap efficiency ≥ ``BENCH_C20_EFFICIENCY_GATE``
        (default 0.8, i.e. wall ≤ 1.25 x max per-stage busy seconds)
      * phased_wall / stream_wall ≥ ``BENCH_C20_SPEEDUP_GATE`` (1.5)

    Plus a correctness gate: both legs must produce the SAME snapshot
    id — the root hash is content-addressed, so streaming emission
    (lag-bounded partial packfiles, docs/dataflow.md) must be
    byte-invisible in the snapshot.
    """
    import asyncio
    import shutil
    import tempfile
    from pathlib import Path

    from backuwup_tpu import defaults
    from backuwup_tpu.app import ClientApp
    from backuwup_tpu.net.server import CoordinationServer
    from backuwup_tpu.ops.backend import CpuBackend, NativeBackend
    from backuwup_tpu.utils import faults

    # 32 MiB / 8 ms tuned so pack wall and send wall are the same order
    # on a 1-core CPU runner (~6s each): smaller corpora make pack
    # trivially cheap (overlap can't show) and higher latency makes
    # send dominate both legs (speedup ceiling falls toward 1.0)
    total_mib = int(os.environ.get("BENCH_C20_MIB", "32"))
    n_peers = int(os.environ.get("BENCH_C20_PEERS", "6"))
    latency_s = float(os.environ.get("BENCH_C20_LATENCY_S", "0.008"))
    eff_gate = float(os.environ.get("BENCH_C20_EFFICIENCY_GATE", "0.8"))
    speedup_gate = float(os.environ.get("BENCH_C20_SPEEDUP_GATE", "1.5"))

    # ACK_TIMEOUT_S: the injected per-send latency queues behind per-peer
    # ordering, so a late ack is latency backlog, not a dead link — with
    # the 5 s production floor the stall detector aborts ~1% of sends
    # into resume retries and the measured walls pick up seconds of noise
    saved = {k: getattr(defaults, k) for k in ("PACKFILE_TARGET_SIZE",
                                               "ACK_TIMEOUT_S")}
    tmp = Path(tempfile.mkdtemp(prefix="bkw_bench_c20_"))
    rng = np.random.default_rng(20)
    src = tmp / "src"
    src.mkdir()
    written = 0
    i = 0
    # Small-file-heavy corpus with a sprinkle of multi-chunk large files:
    # per-file pack cost (chunk boundaries, manifest rows, dedup probes)
    # is what gives the chunk/seal/write stages real wall time to overlap
    # against the latency-bound send stage — a few big files would make
    # pack trivially cheap and the overlap gate meaningless on CPU.
    while written < (total_mib << 20):
        sub = src / f"d{i % 6}"
        sub.mkdir(exist_ok=True)
        n = int(rng.integers(256 << 10, 768 << 10)) if i % 16 == 0 \
            else int(rng.integers(4 << 10, 32 << 10))
        (sub / f"f{i}").write_bytes(rng.bytes(n))
        written += n
        i += 1

    async def one_backup(tag: str):
        server = CoordinationServer(db_path=str(tmp / f"server_{tag}.db"))
        port = await server.start()

        def make_app(name):
            params = CDCParams.from_desired(16 << 10)
            try:
                backend = NativeBackend(params)
            except Exception:
                backend = CpuBackend(params)
            app = ClientApp(config_dir=tmp / tag / name / "cfg",
                            data_dir=tmp / tag / name / "data",
                            server_addr=f"127.0.0.1:{port}",
                            backend=backend,
                            tls=False)  # plaintext loopback deployment
            app.store.set_backup_path(str(src))
            return app

        a = make_app("a")
        holders = [make_app(f"p{j}") for j in range(n_peers)]
        apps = [a] + holders
        try:
            for app in apps:
                await app.start()
                app._audit_task.cancel()
            a.engine.auto_repair = False
            amt = 8 * (written + (64 << 20)) // max(1, n_peers)
            for peer in holders:
                a.store.add_peer_negotiated(peer.client_id, amt)
                peer.store.add_peer_negotiated(a.client_id, amt)
                server.db.save_storage_negotiated(
                    bytes(a.client_id), bytes(peer.client_id), amt)
            snapshot = await asyncio.wait_for(a.backup(), 600)
            if not snapshot:
                raise RuntimeError(f"config #20 {tag}: backup returned none")
            overlap = dict(a.engine.last_overlap or {})
            return bytes(snapshot), overlap
        finally:
            for app in apps:
                try:
                    await app.stop()
                except Exception:
                    pass
            await server.stop()

    async def both() -> Dict:
        defaults.PACKFILE_TARGET_SIZE = 128 * 1024
        defaults.ACK_TIMEOUT_S = 60.0
        # unmeasured warmup leg: eat the jit-compile walls once so the
        # phased leg (which runs first) is not charged for them
        await one_backup("warm")
        faults.install(faults.FaultPlane(seed=20, latency=1.0,
                                         latency_s=latency_s))
        try:
            # best-of-2 per leg: a 1-core runner's scheduler hiccups land
            # on one leg at a time, so min-wall per mode compares the
            # modes rather than the runner's worst moment.  Snapshot
            # parity must hold across EVERY leg, best or not.
            snaps_p, snaps_s = [], []
            phased = stream = None
            for rep in range(2):
                os.environ["BKW_BACKUP_PHASED"] = "1"
                try:
                    snap_p, leg_p = await one_backup(f"phased{rep}")
                finally:
                    os.environ.pop("BKW_BACKUP_PHASED", None)
                snaps_p.append(snap_p)
                if phased is None or leg_p["wall_s"] < phased["wall_s"]:
                    phased = leg_p
                snap_s, leg_s = await one_backup(f"stream{rep}")
                snaps_s.append(snap_s)
                if stream is None or leg_s["wall_s"] < stream["wall_s"]:
                    stream = leg_s
            return {"snaps_phased": snaps_p, "snaps_stream": snaps_s,
                    "phased": phased, "stream": stream}
        finally:
            faults.uninstall()

    try:
        r = asyncio.run(both())
        data_mib = written / (1 << 20)
        phased, stream = r["phased"], r["stream"]
        speedup = phased["wall_s"] / max(stream["wall_s"], 1e-9)
        efficiency = stream["overlap_efficiency"]
        identical = len(set(r["snaps_phased"] + r["snaps_stream"])) == 1
        passed = (identical and efficiency >= eff_gate
                  and speedup >= speedup_gate)
        log(f"config#20 dataflow: {data_mib:.0f} MiB to {n_peers} peers "
            f"(+{latency_s * 1000:.0f}ms/send): phased "
            f"{phased['wall_s']:.2f}s -> stream {stream['wall_s']:.2f}s "
            f"= {speedup:.2f}x (gate {speedup_gate}x), overlap "
            f"{efficiency:.2f} (gate {eff_gate}), snapshot "
            f"{'identical' if identical else 'DIVERGED'} "
            f"[{'PASS' if passed else 'FAIL'}]")
        return {"passed": passed,
                "mib_s": round(data_mib / stream["wall_s"], 2),
                "dataflow_overlap_efficiency": round(efficiency, 4),
                "dataflow_speedup": round(speedup, 2),
                "snapshot_identical": identical,
                "phased_wall_s": round(phased["wall_s"], 3),
                "stream_wall_s": round(stream["wall_s"], 3),
                "stream_stage_busy_s": stream["stage_busy_s"],
                "phased_stage_busy_s": phased["stage_busy_s"],
                "peers": n_peers,
                "latency_ms": round(latency_s * 1000, 1),
                "wall_s": round(phased["wall_s"] + stream["wall_s"], 2)}
    finally:
        for k, v in saved.items():
            setattr(defaults, k, v)
        shutil.rmtree(tmp, ignore_errors=True)


def config21_slo(log: Callable) -> Dict:
    """Live SLO plane: detection latency + explainer precision — #21.

    Two legs land in one record (docs/observability.md §SLOs):

    * **detection leg** — the ``diagnosis`` scenario (scenario/
      harness.py): a quiet pre-fault baseline, then three of six
      holders permanently dark — below RS k, so durability flips
      violated and the shrunken fast burn windows must fire.  Hard
      gates: the scenario's own scorecard all green, breach detection
      within ``BENCH_C21_DETECTION_GATE`` seconds of the first violated
      sample (default 1.0 — two patched sweep intervals), and explainer
      precision 1.0 (zero pre-fault breaches, the armed fault site in
      the top-3 causes).
    * **determinism leg** — the ``regionfail`` sim at 2 000 clients /
      3 virtual days twice with the same seed: the cards — burn ticks,
      breach times, the ranked diagnosis — must be byte-identical
      (``card_json``), so a paged operator can replay the exact
      incident.
    """
    import asyncio
    import tempfile
    from pathlib import Path

    from backuwup_tpu.scenario import builtin_scenarios
    from backuwup_tpu.scenario.harness import ScenarioHarness
    from backuwup_tpu.sim import card_json, run_sim

    detect_gate = float(os.environ.get("BENCH_C21_DETECTION_GATE", "1.0"))
    spec = builtin_scenarios()["diagnosis"]

    async def one_run(td: str):
        harness = ScenarioHarness(spec, Path(td))
        await harness.setup()
        try:
            card = await harness.run()
        finally:
            await harness.teardown()
        return dict(harness.facts.get("slo") or {}), card

    with tempfile.TemporaryDirectory(prefix="bkw_bench_slo_") as td:
        slo, card = asyncio.run(one_run(td))

    days3 = 3 * 86400.0
    d1, _ = run_sim("regionfail", clients=2000, sim_seconds=days3)
    d2, _ = run_sim("regionfail", clients=2000, sim_seconds=days3)
    deterministic = card_json(d1) == card_json(d2)

    detect_s = slo.get("detection_s")
    precision = slo.get("precision")
    passed = (card.passed and deterministic
              and detect_s is not None and detect_s <= detect_gate
              and precision == 1.0)
    log(f"config#21 slo: diagnosis scenario "
        f"{'green' if card.passed else 'RED'} detection={detect_s}s "
        f"(gate {detect_gate}s) precision={precision} "
        f"breaches={slo.get('breaches')} sim_determinism="
        f"{'ok' if deterministic else 'BROKEN'} "
        f"[{'PASS' if passed else 'FAIL'}]")
    return {"passed": passed,
            "slo_detection_s": detect_s,
            "slo_precision": precision,
            "slo_breaches": slo.get("breaches", 0),
            "top_causes": slo.get("top_causes", []),
            "deterministic": deterministic,
            "sim_slo_status": (d1.get("slo") or {}).get("status"),
            "wall_s": round(card.elapsed_s, 2),
            "scorecard": card.to_dict()}


def run_all(pipeline: DevicePipeline, params: CDCParams, cpu_mibs: float,
            log: Callable) -> Dict:
    out = {}
    for name, fn in (
            ("2_small_files", lambda: config2_small_files(pipeline, params,
                                                          log)),
            ("3_incremental", lambda: config3_incremental(pipeline, params,
                                                          log)),
            ("4_large_stream_64k", lambda: config4_large_stream(log)),
            ("5_cross_peer_dedup", lambda: config5_cross_peer(log)),
            ("6_end_to_end", lambda: config6_end_to_end(log)),
            ("7_erasure", lambda: config7_erasure(log)),
            ("8_transfer", lambda: config8_transfer(log)),
            ("9_scenario", lambda: config9_scenario(log)),
            ("10_wan", lambda: config10_wan(log)),
            ("11_crash", lambda: config11_crash(log)),
            ("12_swarm", lambda: config12_swarm(log)),
            ("13_restore", lambda: config13_restore(log)),
            ("14_multichip", lambda: config14_multichip(log)),
            ("15_gc", lambda: config15_gc(log)),
            ("16_federation", lambda: config16_federation(log)),
            ("17_tiered", lambda: config17_tiered(log)),
            ("18_replication", lambda: config18_replication(log)),
            ("19_sim", lambda: config19_sim(log)),
            ("20_dataflow", lambda: config20_dataflow(log)),
            ("21_slo", lambda: config21_slo(log))):
        # BENCH_ONLY_CONFIG=<substring> re-runs a single config
        only = os.environ.get("BENCH_ONLY_CONFIG", "")
        if only and only not in name:
            continue
        try:
            out[name] = fn()
            if "mib_s" in out[name]:
                out[name]["vs_baseline"] = round(
                    out[name]["mib_s"] / cpu_mibs, 2)
        except Exception as e:  # a config failure must not kill the JSON
            log(f"config {name} FAILED: {e}")
            out[name] = {"error": str(e)[:200]}
    return out
