"""Streaming dataflow backup engine tests (docs/dataflow.md).

The backup path is one backpressured streaming dataflow: the packer's
chunk stream feeds seal workers through bounded queues and sealed
packfiles enter transfer admission the moment they commit.  These tests
pin the load-bearing properties:

* backpressure — a deliberately slow wire (fault-plane latency) must
  bound the local packfile buffer at its cap and stall the packer
  WITHOUT deadlocking; the run still completes and drains;
* event-driven wakeup — the seal callback's event wakes the send loop;
  the retired ``send_idle`` poll never fires during a streaming backup;
* crash drain — an injected crash mid-pack tears the send loop down
  cleanly, ``recover()`` reconciles the debris, and a re-backup works;
* packfile-boundary parity — the same corpus backed up at different
  ``PACKFILE_TARGET_SIZE`` values yields the SAME snapshot id as a pack
  that never touched the wire: lag-bounded partial emission is
  byte-invisible in the snapshot.
"""

import asyncio
import contextlib
import random
from pathlib import Path

import pytest

from backuwup_tpu import defaults
from backuwup_tpu import engine as engine_mod
from backuwup_tpu.app import ClientApp
from backuwup_tpu.net.server import CoordinationServer
from backuwup_tpu.ops.backend import CpuBackend
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.utils import faults
from backuwup_tpu.utils import retry

pytestmark = pytest.mark.dataflow

SMALL = CDCParams.from_desired(4096)


def _corpus(root: Path, seed: int = 31, files: int = 24,
            lo: int = 8 << 10, hi: int = 32 << 10) -> int:
    rng = random.Random(seed)
    (root / "sub").mkdir(parents=True, exist_ok=True)
    written = 0
    for i in range(files):
        n = rng.randint(lo, hi)
        (root / ("sub" if i % 3 else ".") / f"f{i}").write_bytes(
            rng.randbytes(n))
        written += n
    return written


@contextlib.asynccontextmanager
async def _universe(base: Path, src: Path, tag: str, peers: int = 2):
    """Coordination server + source client ``a`` + ``peers`` holders with
    pre-negotiated storage (no matchmaking dance — these tests exercise
    the dataflow, not the economy)."""
    server = CoordinationServer(db_path=str(base / f"server_{tag}.db"))
    port = await server.start()

    def mk(name):
        app = ClientApp(config_dir=base / tag / name / "cfg",
                        data_dir=base / tag / name / "data",
                        server_addr=f"127.0.0.1:{port}",
                        backend=CpuBackend(SMALL))
        app.store.set_backup_path(str(src))
        return app

    a = mk("a")
    holders = [mk(f"h{i}") for i in range(peers)]
    apps = [a] + holders
    try:
        for app in apps:
            await app.start()
            app._audit_task.cancel()
        a.engine.auto_repair = False
        amt = 64 << 20
        for h in holders:
            a.store.add_peer_negotiated(h.client_id, amt)
            h.store.add_peer_negotiated(a.client_id, amt)
            server.db.save_storage_negotiated(
                bytes(a.client_id), bytes(h.client_id), amt)
        yield a
    finally:
        for app in apps:
            with contextlib.suppress(Exception):
                await app.stop()
        await server.stop()


@pytest.fixture
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


def test_backpressure_bounds_buffer_and_drains(tmp_path, loop, monkeypatch):
    """Slow wire + tiny local buffer cap: the send loop must pause the
    packer when the sealed-but-unsent buffer crosses the cap, the buffer
    must stay bounded (cap + bounded emission slack), and the run must
    complete and drain — stalled upstream, no deadlock."""
    monkeypatch.setattr(defaults, "PACKFILE_TARGET_SIZE", 32 << 10)
    monkeypatch.setattr(defaults, "PACKFILE_LOCAL_BUFFER_LIMIT", 64 << 10)
    monkeypatch.setattr(defaults, "PACKFILE_RESUME_THRESHOLD", 16 << 10)
    src = tmp_path / "src"
    src.mkdir()
    _corpus(src, files=32)
    # the seam itself, not a poll beside it (a sampler on the event loop
    # is starved beside busy cores and misses a short pause): every
    # level the buffer counter takes, and every pause
    levels, pauses = [], []
    adjust = engine_mod.Orchestrator.adjust_buffer
    pause = engine_mod.Orchestrator.pause

    def adjust_seen(self, delta):
        adjust(self, delta)
        levels.append(self.buffer_bytes)

    def pause_seen(self):
        pauses.append(self.buffer_bytes)
        pause(self)
        paused.set()  # on the event loop: the send loop pauses

    paused = asyncio.Event()

    class HeldWire(faults.FaultPlane):
        """No FILE frame leaves before the packer has been paused once:
        beside busy cores the packer (pure Python here) seals a packfile
        slower than the slow wire sends one, the buffer never holds more
        than the cap when the send loop looks, and whether the cap is
        tested at all would be the machine's load to decide."""

        async def on_send(self, peer_id):
            with contextlib.suppress(asyncio.TimeoutError):
                await asyncio.wait_for(paused.wait(), 60)
            return await super().on_send(peer_id)

    monkeypatch.setattr(engine_mod.Orchestrator, "adjust_buffer",
                        adjust_seen)
    monkeypatch.setattr(engine_mod.Orchestrator, "pause", pause_seen)

    async def run():
        # ONE holder and a genuinely slow wire: the single send lane
        # must fall far behind the packer or the cap is never tested
        faults.install(HeldWire(seed=31, latency=1.0, latency_s=0.08))
        try:
            async with _universe(tmp_path, src, "bp", peers=1) as a:
                snap = await asyncio.wait_for(a.backup(), 120)
                assert len(snap) == 32
                # drained: nothing sealed is left local
                assert a.engine._unsent_packfiles() == []
                # the cap held: cap + seal-pipeline slack (the queued
                # seal workers may each commit one more packfile after
                # the pause flag flips — that emission lag is bounded
                # by the seal queue, docs/dataflow.md)
                slack = (defaults.PACK_SEAL_QUEUE_PACKFILES
                         + defaults.PACK_SEAL_WORKERS + 1) \
                    * defaults.PACKFILE_TARGET_SIZE
                assert max(levels) <= \
                    defaults.PACKFILE_LOCAL_BUFFER_LIMIT + slack
                # backpressure actually engaged on this corpus
                assert pauses
        finally:
            faults.uninstall()

    loop.run_until_complete(asyncio.wait_for(run(), 150))


def test_streaming_send_loop_is_event_driven_not_polled(tmp_path, loop):
    """The seal callback's event wakes the send loop; the old
    fixed-interval ``send_idle`` poll must fire zero times during a
    streaming backup."""
    src = tmp_path / "src"
    src.mkdir()
    _corpus(src, files=12)

    async def run():
        async with _universe(tmp_path, src, "ev") as a:
            before = retry._ATTEMPTS.value(policy="send_idle")
            snap = await asyncio.wait_for(a.backup(), 120)
            assert len(snap) == 32
            assert retry._ATTEMPTS.value(policy="send_idle") == before
        return None

    loop.run_until_complete(asyncio.wait_for(run(), 150))


def test_crash_mid_pack_drains_cleanly_then_recovers(tmp_path, loop,
                                                     monkeypatch):
    """An armed ``pack.seal.pre`` crash mid-stream must propagate out of
    ``backup()`` promptly (the send loop is torn down, not left spinning
    against a dead backup); ``recover()`` reconciles the debris and a
    re-backup over the same tree succeeds and drains."""
    # small packfiles so the corpus seals several times — the armed
    # index below must actually be reached mid-stream
    monkeypatch.setattr(defaults, "PACKFILE_TARGET_SIZE", 32 << 10)
    src = tmp_path / "src"
    src.mkdir()
    _corpus(src, files=16)

    async def run():
        plane = faults.install(faults.FaultPlane(seed=31))
        # not the first seal: let the dataflow actually stream a bit so
        # the teardown path runs with transfers in flight
        plane.arm_crash("pack.seal.pre", 2)
        try:
            async with _universe(tmp_path, src, "crash") as a:
                with pytest.raises(faults.CrashInjected):
                    await asyncio.wait_for(a.backup(), 120)
                assert a.engine.orchestrator.failed
                rep = await a.engine.recover()
                assert rep is a.engine.last_recovery
                snap = await asyncio.wait_for(a.backup(), 120)
                assert len(snap) == 32
                assert a.engine._unsent_packfiles() == []
        finally:
            faults.uninstall()

    loop.run_until_complete(asyncio.wait_for(run(), 200))


def _local_snapshot(src: Path, out: Path) -> bytes:
    """The corpus packed with no wire and no send loop, at the shipped
    packfile size: the snapshot id the served backups are held to."""
    from backuwup_tpu.crypto import KeyManager
    from backuwup_tpu.snapshot.blob_index import BlobIndex
    from backuwup_tpu.snapshot.packer import DirPacker
    from backuwup_tpu.snapshot.packfile import PackfileWriter

    keys = KeyManager.from_secret(b"\x2a" * 32)
    index = BlobIndex(keys, out / "index")
    writer = PackfileWriter(
        keys, out / "pack",
        on_packfile=lambda pid, path, hashes, size:
            index.finalize_packfile(pid, hashes))
    snap = DirPacker(CpuBackend(SMALL), writer, index).pack(src)
    writer.close()
    return bytes(snap)


@pytest.mark.parametrize("target", [16 << 10, 32 << 10, 4 << 20])
def test_packfile_boundaries_never_change_the_snapshot(
        tmp_path, loop, monkeypatch, target):
    """The streaming dataflow emits lag-bounded partial packfiles, so
    where a packfile ends on the wire depends on the target size and on
    timing.  The snapshot is content-addressed: whatever the boundaries,
    its id equals that of a pack that never saw the wire."""
    src = tmp_path / "src"
    src.mkdir()
    _corpus(src, files=16)
    want = _local_snapshot(src, tmp_path / "local")
    monkeypatch.setattr(defaults, "PACKFILE_TARGET_SIZE", target)

    async def run():
        async with _universe(tmp_path, src, f"t{target}") as a:
            snap = await asyncio.wait_for(a.backup(), 120)
            sent = {pid for pid, *_ in a.store.all_placements()}
            return bytes(snap), len(sent)

    snap, n_packfiles = loop.run_until_complete(
        asyncio.wait_for(run(), 200))
    assert snap == want
    # the small targets really do cut the corpus (~320 KiB) into several
    # packfiles; how many the large one makes is the lag bound's business
    assert n_packfiles >= (4 if target < (64 << 10) else 1)


def test_overlap_reports_the_drain_inside_the_wall(tmp_path, loop):
    """``drain_s``: from the packer's last blob to the last ack; the
    pack stage ``paused`` rides in the summary's stage sums."""
    src = tmp_path / "src"
    src.mkdir()
    _corpus(src, files=8)

    async def run():
        async with _universe(tmp_path, src, "drain") as a:
            await asyncio.wait_for(a.backup(), 120)
            return a.engine.last_overlap

    overlap = loop.run_until_complete(asyncio.wait_for(run(), 180))
    assert 0 <= overlap["drain_s"] <= overlap["wall_s"]
    assert set(overlap["stage_busy_s"]) == {
        "chunk_hash", "seal", "write", "send"}
