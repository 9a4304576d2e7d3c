"""Concurrent transfer plane (net/transfer.py + engine fan-out).

Deterministic concurrency coverage driven by the PR-2 fault plane's
latency hook: injected per-peer latency makes overlap *measurable*
(a stripe completes in ~max(shard times), not the sum) and
``kill_after`` makes mid-flight peer death exact (only that shard's
transfer fails; the siblings ack to their own peers).  Plus unit
coverage of the scheduler invariants (per-peer ordering, in-flight byte
cap, failure isolation) and the pipelined packfile seal path.
"""

import asyncio
import os
import time

import pytest

from backuwup_tpu import defaults, wire
from backuwup_tpu.crypto import KeyManager
from backuwup_tpu.engine import Engine, Orchestrator
from backuwup_tpu.net.p2p import P2PError
from backuwup_tpu.net.transfer import TransferScheduler
from backuwup_tpu.ops.backend import CpuBackend
from backuwup_tpu.ops.blake3_cpu import blake3_hash
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.snapshot.packfile import (
    DirtyPackfileError,
    PackfileError,
    PackfileReader,
    PackfileWriter,
)
from backuwup_tpu.store import Store
from backuwup_tpu.utils import faults

pytestmark = pytest.mark.concurrency


@pytest.fixture
def plane():
    p = faults.install(faults.FaultPlane(seed=77))
    yield p
    faults.uninstall()


@pytest.fixture
def engine(tmp_path):
    keys = KeyManager.generate()
    store = Store(directory=tmp_path / "cfg", data_base=tmp_path / "data")
    eng = Engine(keys, store, server=None, node=None,
                 backend=CpuBackend(CDCParams.from_desired(4096)))
    yield eng
    store.close()


class FaultedTransport:
    """Fake transport that consults the fault plane exactly where the
    real Transport.send_data does — latency sleeps and peer death flow
    through the identical PR-2 hook."""

    def __init__(self, peer_id: bytes):
        self.peer_id = bytes(peer_id)
        self.sent = []

    async def send_data(self, data, kind, file_id):
        if faults.PLANE is not None:
            action = await faults.PLANE.on_send(self.peer_id)
            if action == faults.ACT_DROP:
                raise P2PError("injected: connection dropped")
        self.sent.append((kind, bytes(file_id), len(data)))

    async def send_file(self, data, kind, file_id, *, resume=True,
                        throughput_bps=0.0, progress=None):
        # sub-chunk payloads ride the legacy frame, like the real
        # Transport.send_file
        await self.send_data(data, kind, file_id)

    async def close(self):
        pass


def _mk_packfile(engine, pid: bytes, payload: bytes):
    d = engine._pack_dir() / pid.hex()[:2]
    d.mkdir(parents=True, exist_ok=True)
    path = d / pid.hex()
    path.write_bytes(payload)
    return path


def _run(coro, timeout=30):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(coro, timeout))
    finally:
        loop.close()


# --- stripe fan-out under injected latency ---------------------------------

def test_stripe_wall_clock_bounded_by_slowest_shard(engine, plane):
    """6 shards to 6 peers, each with 0.5 s injected latency: the serial
    loop would take >= 3.0 s; the concurrent plane is bounded by the
    slowest single shard (one latency window plus bounded overhead)."""
    plane.latency = 1.0  # every send draws the latency sleep
    plane.latency_s = 0.5
    pid = b"\x42" * 12
    path = _mk_packfile(engine, pid, b"x" * 4096)
    peers = [bytes([i + 1]) * 32 for i in range(6)]
    conns = [(FaultedTransport(p), p, 1 << 30) for p in peers]

    async def fake_conns(orch, need, exclude, min_free, unanswered=None):
        return conns[:need]

    engine._get_stripe_connections = fake_conns
    sched = TransferScheduler()

    async def go():
        t0 = time.monotonic()
        leftover, placed = await engine._send_stripes(
            Orchestrator(), sched, [(pid, path, 4096)])
        return time.monotonic() - t0, leftover, placed

    wall, leftover, placed = _run(go())
    assert leftover == [] and placed == 4096
    assert not path.exists()  # deleted only after all k+m acks
    assert [len(t.sent) for t, _, _ in conns] == [1] * 6
    assert len(engine.store.shards_for_packfile(pid)) == 6
    # max-not-sum: one 0.5 s window (+ encode/challenge-table overhead),
    # never the 6 x 0.5 s a serial send would pay
    assert wall < 3 * plane.latency_s, f"shards did not overlap: {wall:.2f}s"


def test_midflight_peer_death_fails_only_that_shard(engine, plane):
    pid = b"\x43" * 12
    payload = b"y" * 4096
    path = _mk_packfile(engine, pid, payload)
    peers = [bytes([i + 0x10]) * 32 for i in range(6)]
    dead = peers[3]
    plane.kill_after(dead, 0)  # the very next send finds the peer dead
    conns = [(FaultedTransport(p), p, 1 << 30) for p in peers]

    async def fake_conns(orch, need, exclude, min_free, unanswered=None):
        # mirror P2PNode.connect: dead peers accept no dial
        return [c for c in conns
                if c[1] not in exclude and not faults.PLANE.is_dead(c[1])
                ][:need]

    engine._get_stripe_connections = fake_conns
    sched = TransferScheduler()

    leftover, placed = _run(engine._send_stripes(
        Orchestrator(), sched, [(pid, path, 4096)]))
    # only the dead peer's shard failed; the stripe is partial and retried
    assert leftover == [(pid, path, 4096)] and placed == 0
    assert path.exists()
    placements = engine.store.shards_for_packfile(pid)
    assert len(placements) == 5
    assert all(bytes(p) != dead for p, _ in placements)
    live = [t for t, p, _ in conns if p != dead]
    assert [len(t.sent) for t in live] == [1] * 5

    # next tick: a replacement peer takes the one missing shard and the
    # stripe completes — the 5 placed shards are not re-sent
    spare = b"\x77" * 32
    conns.append((FaultedTransport(spare), spare, 1 << 30))
    leftover2, placed2 = _run(engine._send_stripes(
        Orchestrator(), sched, leftover))
    assert leftover2 == [] and placed2 == 4096
    assert not path.exists()
    assert len(engine.store.shards_for_packfile(pid)) == 6
    assert [len(t.sent) for t in live] == [1] * 5  # unchanged
    assert len(conns[-1][0].sent) == 1


def test_stripe_read_failure_requeues_for_retry(engine, plane):
    """Satellite regression: a packfile whose file vanished mid-tick must
    land back in leftover (and be logged), not silently skip the run."""
    logged = []

    class Msgr:
        def log(self, msg):
            logged.append(msg)

    engine.messenger = Msgr()
    pid = b"\x44" * 12
    path = engine._pack_dir() / pid.hex()[:2] / pid.hex()  # never created
    peers = [bytes([i + 0x30]) * 32 for i in range(6)]
    conns = [(FaultedTransport(p), p, 1 << 30) for p in peers]

    async def fake_conns(orch, need, exclude, min_free, unanswered=None):
        return conns[:need]

    engine._get_stripe_connections = fake_conns
    leftover, placed = _run(engine._send_stripes(
        Orchestrator(), TransferScheduler(), [(pid, path, 4096)]))
    assert leftover == [(pid, path, 4096)] and placed == 0
    assert any("read failed" in m for m in logged)


# --- whole-file multi-peer fan-out -----------------------------------------

def test_whole_files_fan_out_across_peers(engine, monkeypatch):
    monkeypatch.setattr(defaults, "RS_M", 0)  # striping off: legacy path
    pids = [bytes([0x50 + i]) * 12 for i in range(3)]
    paths = [_mk_packfile(engine, pid, b"z" * 1000) for pid in pids]
    peer_a, peer_b = b"\x05" * 32, b"\x06" * 32
    ta, tb = FaultedTransport(peer_a), FaultedTransport(peer_b)

    async def fake_get_peer(orch, estimate, fulfilled, last_request,
                            min_free=1):
        return ta, peer_a, 10_000

    async def fake_conns(orch, need, exclude, min_free, unanswered=None):
        assert peer_a in exclude  # the first peer is never doubled up
        return [(tb, peer_b, 10_000)]

    engine._get_peer_connection = fake_get_peer
    engine._get_stripe_connections = fake_conns
    orch = Orchestrator()
    orch.packing_completed = True
    orch.buffer_bytes = 3000
    _run(engine._send_loop(orch, 0))
    assert len(ta.sent) + len(tb.sent) == 3
    assert len(ta.sent) >= 1 and len(tb.sent) >= 1  # genuinely fanned out
    assert not any(p.exists() for p in paths)
    assert orch.bytes_sent == 3000
    for pid in pids:
        assert engine.store.shards_for_packfile(pid) != []


# --- scheduler invariants ---------------------------------------------------

def test_scheduler_per_peer_order_cap_and_isolation():
    async def go():
        sched = TransferScheduler(max_inflight_bytes=100, max_transfers=2)
        order = []
        peak = {"count": 0, "bytes": 0}

        def job(name, fail=False):
            async def send():
                peak["count"] = max(peak["count"], sched.inflight_count)
                peak["bytes"] = max(peak["bytes"], sched.inflight_bytes)
                await asyncio.sleep(0)
                order.append(name)
                if fail:
                    raise P2PError("boom")
            return send

        pa, pb = b"a" * 32, b"b" * 32
        tasks = [
            sched.submit(pa, 40, job("a1")),
            sched.submit(pa, 40, job("a2", fail=True)),
            sched.submit(pa, 40, job("a3")),
            sched.submit(pb, 60, job("b1")),
        ]
        results = await sched.gather(tasks)
        return sched, order, results, peak

    sched, order, results, peak = _run(go())
    # per-peer FIFO: a1 < a2 < a3 even though a2 failed mid-flight
    assert [o for o in order if o.startswith("a")] == ["a1", "a2", "a3"]
    assert [r.ok for r in results] == [True, False, True, True]
    assert isinstance(results[1].error, P2PError)  # isolated, not raised
    assert peak["count"] <= 2 and peak["bytes"] <= 100
    assert sched.completed == 3 and sched.failed == 1
    assert sched.inflight_count == 0 and sched.inflight_bytes == 0


def test_scheduler_admits_oversize_transfer_when_empty():
    async def go():
        sched = TransferScheduler(max_inflight_bytes=10, max_transfers=4)
        ran = []

        async def send():
            ran.append(True)

        r = await sched.submit(b"p" * 32, 1000, send)
        return r, ran

    r, ran = _run(go())
    assert r.ok and ran == [True]  # bigger than the cap, still admitted


def test_scheduler_emits_transfer_telemetry():
    events = []

    class Msgr:
        def transfer(self, peer, outcome, **kw):
            events.append((peer, outcome, kw))

    async def go():
        sched = TransferScheduler(messenger=Msgr())

        async def send():
            pass

        await sched.submit(b"\xaa" * 32, 123, send, label="pack:test")
        return sched

    sched = _run(go())
    assert len(events) == 1
    peer, outcome, kw = events[0]
    assert outcome == "sent" and kw["size"] == 123
    assert kw["label"] == "pack:test"
    assert sched.bytes_sent == 123


# --- pipelined packfile seal -------------------------------------------------

def _blob(data: bytes) -> wire.Blob:
    return wire.Blob(hash=blake3_hash(data), kind=wire.BlobKind.FILE_CHUNK,
                     data=data)


def test_pipelined_writer_parity_with_synchronous(tmp_path, monkeypatch):
    """seal_workers>0 must produce readable packfiles holding exactly the
    same blobs, splitting on the target size like the synchronous path."""
    monkeypatch.setattr(defaults, "PACKFILE_TARGET_SIZE", 64 * 1024)
    keys = KeyManager.generate()
    written = []
    writer = PackfileWriter(
        keys, tmp_path / "pack", seal_workers=2,
        on_packfile=lambda pid, path, hashes, size:
            written.append((bytes(pid), list(hashes))))
    blobs = [os.urandom(20_000) for _ in range(20)]
    for data in blobs:
        writer.add_blob(_blob(data))
    writer.flush()
    writer.close()
    assert len(written) >= 2  # target-size splits happened in the pipeline
    reader = PackfileReader(keys, tmp_path / "pack")
    got = {}
    for pid, hashes in written:
        for h in hashes:
            got[bytes(h)] = reader.get_blob(pid, h).data
    assert len(got) == len(blobs)
    for data in blobs:
        assert got[blake3_hash(data)] == data


def test_pipelined_writer_enforces_hard_cap(tmp_path, monkeypatch):
    """The cap check moves to the writer thread (post-seal, actual
    ciphertext sizes) but still fires before anything hits disk."""
    monkeypatch.setattr(defaults, "PACKFILE_MAX_SIZE", 4 * 1024)
    keys = KeyManager.generate()
    writer = PackfileWriter(keys, tmp_path / "pack", seal_workers=1)
    try:
        writer.add_blob(_blob(os.urandom(64 * 1024)))  # incompressible
        with pytest.raises(PackfileError):
            writer.flush()
        assert not list((tmp_path / "pack").rglob("*")) or not [
            p for p in (tmp_path / "pack").rglob("*") if p.is_file()]
    finally:
        writer.shutdown()


def test_pipelined_writer_dirty_close_raises(tmp_path):
    keys = KeyManager.generate()
    writer = PackfileWriter(keys, tmp_path / "pack", seal_workers=1)
    writer.add_blob(_blob(b"q" * 100))
    with pytest.raises(DirtyPackfileError):
        writer.close()
    writer.flush()
    writer.close()  # clean after flush
