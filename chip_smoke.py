#!/usr/bin/env python3
"""chip_smoke.py: the served backup path, once, on the chip.

One process.  It starts a coordination server and RS_K + RS_M loopback
holders (native C backend, so only the backing-up client builds a
pipeline and an index on the chip), and one backing-up ``ClientApp`` built
with no ``backend=`` and no ``dedup_mesh=``: ``select_backend()`` and the
engine's default mesh are what runs.  From ``--seed`` it writes a tree of
about 1 GiB (six ~100 MiB files, two of which share all but an inserted
1 MiB; 64 x 900 KiB; 2,000 files below the minimum chunk; one 300 MiB
file for the streaming route), then:

1. backs it up through ``ClientApp.backup()``;
2. holds every file's chunks and digests against ``NativeBackend``;
3. restores into an empty directory and compares every byte;
4. backs the unchanged tree up again: every chunk must classify duplicate.

Before the served path it scans one 128 MiB slice of seeded bytes at each
of the benchmark's two candidate densities (1 MiB and 64 KiB chunks) and
holds ``_scan_segment``'s sparse outputs to the numpy oracle's (untimed).

It asserts that the Pallas kernels were selected, that the HBM tier
answered fingerprints, and that no row was re-run on the host.  Every
phase prints one JSON line; a phase that fails ends the run non-zero.  The
last line of stdout is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--chips 4`` runs only the four-chip path and what it is compared with
(the mesh manifest + on-device classify on a four- and a one-device
mesh).  ``--rehearse`` shrinks the tree and lifts the platform check and
the three kernel assertions, for a run on the CPU; its last line says
``"ok": false`` so it can never be taken for a pass.

Without an accelerator (and without ``--rehearse``) it prints no result
and exits 1.  It never sets ``JAX_PLATFORMS``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

MiB = 1 << 20
KiB = 1 << 10


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class CompileMeter:
    """Counts backend compiles and persistent-cache hits/misses through
    ``jax.monitoring`` (a compile that hits the cache still reports its
    retrieval time under the compile event)."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles,
                "compile_s": round(self.compile_s, 3),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    def since(self, base: dict) -> dict:
        now = self.snapshot()
        return {k: round(now[k] - base[k], 3) for k in now}


def peak_hbm() -> dict:
    import jax
    out = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out[str(d.id)] = stats.get("peak_bytes_in_use")
    return out


# --- the tree ---------------------------------------------------------------

def write_tree(root: Path, seed: int, rehearse: bool) -> dict:
    """Seeded source tree; returns {"files": n, "bytes": n}."""
    import numpy as np
    rng = np.random.default_rng(seed)
    if rehearse:
        big_n, big_sz, ins_sz = 2, 3 * MiB, 64 * KiB
        mid_n, mid_sz = 6, 300 * KiB
        small_dirs, small_per = 2, 20
        stream_sz = 0  # the streaming route starts above 256 MiB
    else:
        big_n, big_sz, ins_sz = 6, 100 * MiB, 1 * MiB
        mid_n, mid_sz = 64, 900 * KiB
        small_dirs, small_per = 20, 100
        stream_sz = 300 * MiB
    files = total = 0

    def put(path: Path, data: bytes) -> None:
        nonlocal files, total
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_bytes(data)
        files += 1
        total += len(data)

    first = rng.bytes(big_sz)
    put(root / "big" / "f0.bin", first)
    # all of f0 but an inserted block: the chunks after the cut re-align
    at = int(big_sz * 0.4)
    put(root / "big" / "f1.bin", first[:at] + rng.bytes(ins_sz) + first[at:])
    del first
    for i in range(2, big_n):
        put(root / "big" / f"f{i}.bin", rng.bytes(big_sz))
    for i in range(mid_n):
        put(root / "mid" / f"m{i:02d}.bin", rng.bytes(mid_sz))
    for d in range(small_dirs):
        for i in range(small_per):
            put(root / "small" / f"d{d:02d}" / f"s{i:03d}.bin",
                rng.bytes(int(rng.integers(1 * KiB, 100 * KiB))))
    if stream_sz:
        put(root / "stream" / "huge.bin", rng.bytes(stream_sz))
    return {"files": files, "bytes": total}


def tree_files(root: Path):
    return sorted(p for p in root.rglob("*") if p.is_file())


# --- one chip: the served path ------------------------------------------------

async def served_path(args, work: Path, meter: CompileMeter) -> None:
    from backuwup_tpu import defaults
    from backuwup_tpu.app import ClientApp
    from backuwup_tpu.net.server import CoordinationServer
    from backuwup_tpu.obs import profile as obs_profile
    from backuwup_tpu.ops.backend import NativeBackend, TpuBackend

    src = work / "src"
    t0 = time.monotonic()
    tree = write_tree(src, args.seed, args.rehearse)
    emit(phase="tree", seconds=round(time.monotonic() - t0, 3), **tree)

    server = CoordinationServer(db_path=str(work / "server.db"))
    port = await server.start()
    addr = f"127.0.0.1:{port}"

    def app(name: str, **kw) -> ClientApp:
        return ClientApp(config_dir=work / name / "cfg",
                         data_dir=work / name / "data",
                         server_addr=addr, tls=False, **kw)

    t0 = time.monotonic()
    base = meter.snapshot()
    # no backend=, no dedup_mesh=: select_backend() and the engine's
    # default mesh.  A rehearsal on the CPU has to name the device backend
    # (select_backend() picks the native one there).
    client = app("client", **({"backend": TpuBackend()}
                              if args.rehearse else {}))
    holders = [app(f"h{i}", backend=NativeBackend())
               for i in range(defaults.RS_K + defaults.RS_M)]
    apps = [client] + holders
    # the client's own log lines (what its dashboard would show) go to
    # stderr, so a phase that fails says why
    client.messenger.subscribe(
        lambda ev: print(f"[client {ev.kind}] {ev.payload.get('text', '')}",
                         file=sys.stderr, flush=True)
        if ev.kind in ("message", "panic", "error") else None)
    try:
        for a in apps:
            await a.start()
        client.store.set_backup_path(str(src))
        # allowances granted directly, as scenario/harness.py does: the
        # matchmaker is host code with tests of its own
        grant = 2 * tree["bytes"] * (defaults.RS_K + defaults.RS_M) \
            // defaults.RS_K
        for h in holders:
            client.store.add_peer_negotiated(h.client_id, grant)
            h.store.add_peer_negotiated(client.client_id, grant)
            server.db.save_storage_negotiated(
                bytes(client.client_id), bytes(h.client_id), grant)

        engine = client.engine
        assert engine.backend.name == "tpu", engine.backend.name
        assert engine.device_dedup is not None, "no device dedup index"
        pipe = engine.backend.pipeline  # runs the kernel probes
        # the platform's one selection: on a TPU the Mosaic scan and leaf
        # kernels (checked against their XLA forms, or the pipeline raised)
        kernels = {"fused": pipe.fused, "pallas_digest": pipe.pallas_digest,
                   "mesh_devices": int(engine.device_dedup.mesh.devices.size)}
        emit(phase="start", seconds=round(time.monotonic() - t0, 3),
             kernels=kernels, **meter.since(base))
        if not args.rehearse:
            assert pipe.fused and pipe.pallas_digest, kernels

        async def backup(label: str) -> dict:
            t0 = time.monotonic()
            base = meter.snapshot()
            snapshot = await client.backup()
            stats = engine.last_pack_stats
            report = engine.last_pipeline_report
            out = {"phase": label,
                   "seconds": round(time.monotonic() - t0, 3),
                   "snapshot": snapshot.hex(), "files": stats.files,
                   "bytes": stats.bytes_read, "chunks": stats.chunks,
                   "chunks_deduped": stats.chunks_deduped,
                   "stage_busy_s": engine.last_overlap["stage_busy_s"],
                   "overlap_efficiency": engine.last_overlap["overlap_efficiency"],
                   "dispatches": report["dispatches"],
                   "tier": report.get("tier"),
                   "host_rerun_rows": obs_profile.mesh_host_rerun_rows(),
                   "peak_bytes_in_use": peak_hbm(), **meter.since(base)}
            emit(**out)
            assert stats.failed_files == 0, stats
            assert stats.files == tree["files"], (stats.files, tree)
            assert stats.bytes_read == tree["bytes"], (stats, tree)
            assert stats.dedup_divergences == 0, stats
            assert out["host_rerun_rows"] == 0, out["host_rerun_rows"]
            assert engine._unsent_packfiles() == [], "packfiles not acked"
            return out

        # 1. first backup: chunk + fingerprint + classify on the chip
        first = await backup("backup")
        assert first["chunks_deduped"] > 0, "f1 shares f0's chunks"
        assert first["tier"] and first["tier"]["probes"]["device"] > 0, \
            f"the HBM tier answered no fingerprint: {first['tier']}"

        # 2. chunk boundaries and digests against the native C pipeline:
        # every native chunk is a blob of the recorded snapshot at that
        # length, and the backup recorded exactly as many chunks
        t0 = time.monotonic()
        native = NativeBackend()
        recorded = client.store.manifest_blobs()
        n_chunks = 0
        for path in tree_files(src):
            for ref in native.manifest(path.read_bytes()):
                n_chunks += 1
                assert recorded.get(ref.hash) == ref.length, \
                    f"{path}: chunk at {ref.offset}+{ref.length} " \
                    f"({ref.hash.hex()[:16]}) not in the snapshot"
        assert n_chunks == first["chunks"], (n_chunks, first["chunks"])
        emit(phase="native_parity", chunks=n_chunks,
             seconds=round(time.monotonic() - t0, 3))

        # 3. restore into an empty directory, every byte compared
        t0 = time.monotonic()
        dest = work / "restored"
        restored = await client.restore(dest)
        n_bytes = 0
        for path in tree_files(src):
            want = path.read_bytes()
            got = (restored / path.relative_to(src)).read_bytes()
            assert got == want, f"{path} restored differently"
            n_bytes += len(got)
        assert len(tree_files(restored)) == tree["files"]
        emit(phase="restore", files=tree["files"], bytes=n_bytes,
             seconds=round(time.monotonic() - t0, 3))
        shutil.rmtree(restored)

        # 4. the unchanged tree again: every chunk a duplicate
        second = await backup("backup_again")
        assert second["chunks"] == first["chunks"], (first, second)
        assert second["chunks_deduped"] >= second["chunks"], second
    finally:
        for a in apps:
            await a.stop()
        await server.stop()


def scan_slices(args) -> None:
    """One ``_scan_segment`` slice at the scanner's segment size and each
    density the benchmark's cells run: indices, loose and strict words and
    the count equal to the numpy oracle's over the same bytes."""
    import jax.numpy as jnp
    import numpy as np

    from backuwup_tpu.ops.cdc_cpu import gear_hashes
    from backuwup_tpu.ops.cdc_tpu import _HALO, TpuCdcScanner, _scan_segment
    from backuwup_tpu.ops.gear import CDCParams

    n = (8 if args.rehearse else 128) * MiB
    ext = np.random.default_rng(args.seed).integers(
        0, 256, _HALO + n, dtype=np.uint8)
    n_valid = n - 77  # ends inside a word
    h = gear_hashes(ext[_HALO:].tobytes(), ext[:_HALO].tobytes())
    h[n_valid:] = 0xFFFFFFFF  # a candidate of neither mask
    bit = np.arange(32, dtype=np.uint32)
    for params in (CDCParams(), CDCParams.from_desired(64 * KiB)):
        k_cap = TpuCdcScanner(params)._k_cap(n)
        widx, wl, ws, count = _scan_segment(
            jnp.asarray(ext), jnp.int32(n_valid), jnp.uint32(params.mask_s),
            jnp.uint32(params.mask_l), k_cap=k_cap)
        cand_l = (h & np.uint32(params.mask_l)) == 0
        cand_s = cand_l & ((h & np.uint32(params.mask_s)) == 0)
        words_l, words_s = (
            (c.reshape(-1, 32).astype(np.uint32) << bit).sum(
                axis=1, dtype=np.uint32) for c in (cand_l, cand_s))
        want = np.flatnonzero(words_l)
        assert 0 < len(want) == int(count) <= k_cap, (len(want), int(count))
        widx = np.asarray(widx)
        assert (widx[:len(want)] == want).all() and (widx[len(want):] == -1).all()
        assert (np.asarray(wl)[:len(want)] == words_l[want]).all()
        assert (np.asarray(ws)[:len(want)] == words_s[want]).all()
        emit(phase="scan_slice", mask_l_bits=params.mask_l_bits, bytes=n,
             k_cap=k_cap, nz_words=len(want), equal_to_oracle=True)


# --- four chips: the mesh manifest and the sharded index ---------------------------

def mesh_path(args, work: Path, meter: CompileMeter) -> None:
    """The same seeded batches through ``manifest_segments_mesh`` +
    ``classify_dispatch`` on a mesh of every device and on a mesh of one;
    chunks, digests and found-flags must be identical."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from backuwup_tpu.crypto import KeyManager
    from backuwup_tpu.obs import profile as obs_profile
    from backuwup_tpu.ops.cdc_tpu import _HALO
    from backuwup_tpu.ops.gear import CDCParams
    from backuwup_tpu.ops.pipeline import DevicePipeline
    from backuwup_tpu.snapshot.blob_index import BlobIndex
    from backuwup_tpu.snapshot.device_dedup import MeshDedupIndex

    devices = jax.devices()
    n_dev = len(devices)
    rng = np.random.default_rng(args.seed)
    # two bucket shapes; the second pass repeats the first, so every
    # chunk of it must come back found
    shapes = [(4, 256 * KiB)] if args.rehearse \
        else [(8, 16 * MiB), (64, 1 * MiB)]
    batches = []
    for rows, width in shapes:
        buf = np.zeros((rows, _HALO + width), dtype=np.uint8)
        nv = np.zeros(rows, dtype=np.int32)
        for r in range(rows):
            n = int(rng.integers(width // 2 + 1, width + 1))
            buf[r, _HALO:_HALO + n] = np.frombuffer(rng.bytes(n), np.uint8)
            nv[r] = n
        batches.append((buf, nv))
    batches = batches + batches
    params = CDCParams.from_desired(8 * KiB) if args.rehearse \
        else CDCParams()

    def run(label: str, devs) -> dict:
        t0 = time.monotonic()
        base = meter.snapshot()
        prof = obs_profile.baseline()
        mesh = Mesh(np.array(devs), ("data",))
        keys = KeyManager.generate()
        host = BlobIndex(keys, work / label / "index")
        dedup = MeshDedupIndex(mesh, host)
        pipe = DevicePipeline(params, l_bucket=max(
            16, -(-params.max_size // 1024)), mesh=mesh)
        if not args.rehearse:
            assert pipe.fused and pipe.pallas_digest
        table_devs = {s.device for s in dedup.sharded.keys.addressable_shards}
        batch_devs = set()
        rows_out, flags_out = [], []
        for rows, flags in pipe.manifest_segments_mesh(
                iter(batches), strict_overflow=True, dedup=dedup):
            # the batches still in flight: where do their rows' packed
            # cuts (the one 2-D int32 array of the mesh program) live?
            for a in jax.live_arrays():
                if a.dtype == np.int32 and a.ndim == 2:
                    batch_devs |= {s.device for s in a.addressable_shards}
            for (chunks, digs), fl in zip(rows, flags):
                assert fl is not None, "device did not classify a row"
                rows_out.append((chunks, digs.tobytes()))
                flags_out.append(np.asarray(fl).tolist())
        rep = obs_profile.report(prof)
        per_dev = rep.get("device_dispatches", {})
        emit(phase=f"mesh_{label}", devices=len(devs),
             seconds=round(time.monotonic() - t0, 3),
             chunks=sum(len(c) for c, _ in rows_out),
             found=sum(sum(f) for f in flags_out),
             table_devices=len(table_devs), batch_devices=len(batch_devs),
             device_dispatches=per_dev, peak_bytes_in_use=peak_hbm(),
             **meter.since(base))
        assert len(table_devs) == len(devs), table_devs
        assert len(batch_devs) == len(devs), batch_devs
        assert len(per_dev) == len(devs), per_dev
        assert len({json.dumps(v, sort_keys=True)
                    for v in per_dev.values()}) == 1, per_dev
        return {"rows": rows_out, "flags": flags_out}

    wide = run("all", devices)
    one = run("one", devices[:1])
    assert wide["rows"] == one["rows"], "chunks or digests differ by mesh"
    assert wide["flags"] == one["flags"], "found-flags differ by mesh"
    half = len(wide["flags"]) // 2
    assert not any(any(f) for f in wide["flags"][:half]), "new data found"
    assert all(all(f) for f in wide["flags"][half:]), "repeat not found"
    assert n_dev == args.chips or args.rehearse, (n_dev, args.chips)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip mesh path and what it "
                         "is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny tree, no platform check, no kernel "
                         "assertions; prints ok=false")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not args.rehearse:
        if dev.platform != "tpu":
            print(f"chip_smoke: no TPU here (jax.devices()[0].platform = "
                  f"{dev.platform!r}); nothing ran", file=sys.stderr)
            return 1
        if device["count"] != args.chips:
            print(f"chip_smoke: --chips {args.chips} but JAX sees "
                  f"{device['count']} device(s); nothing ran",
                  file=sys.stderr)
            return 1

    from backuwup_tpu.utils.jaxcache import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    meter = CompileMeter()
    emit(phase="device", device=device, cache_dir=str(cache_dir),
         seed=args.seed, rehearse=args.rehearse, jax=jax.__version__)

    t0 = time.monotonic()
    work = Path(tempfile.mkdtemp(prefix="bkw_chip_smoke_"))
    try:
        if args.chips == 4:
            mesh_path(args, work, meter)
        else:
            scan_slices(args)
            asyncio.run(served_path(args, work, meter))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    emit(phase="total", seconds=round(time.monotonic() - t0, 3),
         peak_bytes_in_use=peak_hbm(), **meter.snapshot())
    print(json.dumps({"ok": not args.rehearse, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
