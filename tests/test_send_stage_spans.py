"""The send stage's per-packfile spans and its outcome counter
(``send.stripe``, ``send.wire``, ``bkw_send_packfiles_total``), closed
against each other, and the placement guarantee beside a slow holder:

* all-new data to six holders: every packfile ``striped``, none
  ``whole``, none ``deferred``, and a stripe's parts sum to its span;
* one of six holders misses a rendezvous (a live peer, too busy to
  confirm in the dialer's window): the packfile waits for the next tick
  (``deferred``) and is then ``striped`` — never placed whole;
* five holders cannot carry a 4+2 stripe: whole copies, as before.
"""

import asyncio
import contextlib
import random
import time
from pathlib import Path

import pytest

from backuwup_tpu import defaults
from backuwup_tpu.app import ClientApp
from backuwup_tpu.net.server import CoordinationServer
from backuwup_tpu.ops.backend import NativeBackend
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.utils import faults

pytestmark = pytest.mark.dataflow

SMALL = CDCParams.from_desired(4096)
PACKFILE = 256 << 10
FILE_OPS = ("read_bytes", "unlink")  # a stripe's first and last step


def _corpus(root: Path, files: int = 12, size: int = 192 << 10) -> None:
    rng = random.Random(36)
    root.mkdir(parents=True)
    for i in range(files):
        (root / f"f{i}").write_bytes(rng.randbytes(size))


@contextlib.asynccontextmanager
async def _universe(base: Path, src: Path, holders: int):
    """Server, client ``a`` and ``holders`` peers with storage negotiated
    (as tests/test_dataflow.py)."""
    server = CoordinationServer(db_path=str(base / "server.db"))
    port = await server.start()

    def mk(name):
        app = ClientApp(config_dir=base / name / "cfg",
                        data_dir=base / name / "data",
                        server_addr=f"127.0.0.1:{port}",
                        backend=NativeBackend(SMALL))
        app.store.set_backup_path(str(src))
        return app

    a = mk("a")
    peers = [mk(f"h{i}") for i in range(holders)]
    try:
        for app in [a] + peers:
            await app.start()
            app._audit_task.cancel()
        a.engine.auto_repair = False
        for h in peers:
            a.store.add_peer_negotiated(h.client_id, 64 << 20)
            h.store.add_peer_negotiated(a.client_id, 64 << 20)
            server.db.save_storage_negotiated(
                bytes(a.client_id), bytes(h.client_id), 64 << 20)
        yield a, peers
    finally:
        for app in [a] + peers:
            with contextlib.suppress(Exception):
                await app.stop()
        await server.stop()


def _placed(a) -> dict:
    """{packfile: shard indices placed} from the client's store."""
    out: dict = {}
    for pid, _peer, _size, index, _at in a.store.all_placements():
        out.setdefault(bytes(pid), []).append(int(index))
    return out


def _backup(tmp_path, monkeypatch, holders: int, before=None) -> tuple:
    """One backup of all-new data; (send report, stage seconds, placed,
    seconds the packfiles' reads and unlinks took)."""
    monkeypatch.setattr(defaults, "PACKFILE_TARGET_SIZE", PACKFILE)
    src = tmp_path / "src"
    _corpus(src)
    file_ops = []

    async def run():
        async with _universe(tmp_path, src, holders) as (a, peers):
            if before is not None:
                before(peers)
            blocking = a.engine._blocking

            async def timed(fn, *args):
                if getattr(fn, "__name__", "") not in FILE_OPS:
                    return await blocking(fn, *args)
                t0 = time.perf_counter()
                try:
                    return await blocking(fn, *args)
                finally:
                    file_ops.append(time.perf_counter() - t0)

            a.engine._blocking = timed
            await asyncio.wait_for(a.backup(), 120)
            assert a.engine._unsent_packfiles() == []
            rep = a.engine.last_pipeline_report
            return rep["send"], rep["stage_seconds"], _placed(a)

    loop = asyncio.new_event_loop()
    try:
        send, seconds, placed = loop.run_until_complete(
            asyncio.wait_for(run(), 200))
    finally:
        loop.close()
    return send, seconds, placed, sum(file_ops)


def test_new_data_to_six_holders_is_striped_and_the_spans_close(
        tmp_path, monkeypatch):
    send, seconds, placed, file_s = _backup(tmp_path, monkeypatch, 6)
    assert len(placed) >= 6  # ~2.3 MB in 256 KiB packfiles
    assert send["stripes"] == len(placed)
    assert send["whole"] == 0 and send["deferred"] == 0
    assert all(sorted(ix) == list(range(6)) for ix in placed.values())
    # one stripe span a packfile: read, code, tables, wire, unlink
    parts = (file_s + seconds["send.rs_encode"]
             + seconds["send.challenge_tables"] + seconds["send.wire"])
    assert parts == pytest.approx(seconds["send.stripe"], rel=0.10)


def test_a_holder_that_misses_a_rendezvous_defers_the_packfile(
        tmp_path, monkeypatch):
    def slow(peers):
        plane = faults.install(faults.FaultPlane(seed=36))
        plane.arm(f"dial.unanswered:{bytes(peers[0].client_id).hex()}", 0)

    try:
        send, _seconds, placed, _ = _backup(tmp_path, monkeypatch, 6,
                                            before=slow)
    finally:
        faults.uninstall()
    assert send["deferred"] >= 1
    assert send["whole"] == 0 and send["stripes"] == len(placed)
    assert all(sorted(ix) == list(range(6)) for ix in placed.values())


def test_five_holders_cannot_carry_a_stripe_and_go_whole(
        tmp_path, monkeypatch):
    send, _seconds, placed, _ = _backup(tmp_path, monkeypatch, 5)
    assert send["whole"] == len(placed) >= 6
    assert send["stripes"] == 0 and send["deferred"] == 0
    assert all(ix == [-1] for ix in placed.values())
