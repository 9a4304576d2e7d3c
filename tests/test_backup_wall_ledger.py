"""One backup's wall, closed: ``report["wall"]`` (the coroutine's phases,
``obs/profile.WALL_PHASES``) against a clock around ``Engine.run_backup``
and ``report["pack"]`` (the pack thread's steps, ``PACK_STEPS``) against
``engine.pack``, on both routes; a delay put into one phase or step
lands there; and every span this ledger added is opened off the event
loop, through the annotator (so on the profiler's host plane).
"""

import asyncio
import contextlib
import functools
import random
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from backuwup_tpu import defaults
from backuwup_tpu import engine as engine_mod
from backuwup_tpu.app import ClientApp
from backuwup_tpu.net.server import CoordinationServer
from backuwup_tpu.obs import metrics as obs_metrics
from backuwup_tpu.obs import profile as obs_profile
from backuwup_tpu.obs import trace as obs_trace
from backuwup_tpu.ops.backend import NativeBackend
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.snapshot.blob_index import BlobIndex
from backuwup_tpu.snapshot.packer import DirPacker
from backuwup_tpu.snapshot.packfile import PackfileWriter
from benchmark.generators import source_tree

pytestmark = pytest.mark.dataflow

SMALL = CDCParams.from_desired(4096)
PACKFILE = 256 << 10
DELAY = 0.2
NEW_SPANS = ("backup.estimate", "backup.index_flush",
             "backup.record_snapshot", "pack.walk", "pack.prepare",
             "pack.device_sync", "pack.dir_tree", "pack.flush",
             "pack.seal_table")


class HostAnswers:
    """A device index's seam with the host authority behind it: the
    packer then takes the classified route (``pack.prepare``, the sync
    of host-classified hashes between batches) with nothing to compile."""

    def __init__(self, index: BlobIndex):
        self.index = index

    def classify_insert(self, hashes):
        return [self.index.is_duplicate(h) for h in hashes]


# ISSUE 41's tree at a small size: 150 files in 16 directories under
# three top-level ones (the root holds none), pack batches that span them
SOURCE_TREE = {"files": 150, "directories": 16, "top_level": 3,
               "max_depth": 4, "dir_files_median": 7, "dir_files_sigma": 1.2,
               "dir_files_min": 1, "dir_files_max": 600,
               "size_median_bytes": 6144, "size_sigma": 1.45,
               "size_min_bytes": 64, "size_max_bytes": 4194304,
               "size_mean_bytes": 17408}


def _tree(root: Path, route: str) -> None:
    if route == "source_tree":
        source_tree.build(root, SOURCE_TREE, np.random.default_rng([40, 0]))
        return
    rng = random.Random(38)
    (root / "docs").mkdir(parents=True)
    if route == "streaming":  # one file over the packer's batch_bytes
        (root / "image").write_bytes(rng.randbytes(3 << 20))
        return
    for i in range(16):
        (root / "docs" / f"f{i}").write_bytes(rng.randbytes(160 << 10))
    for i in range(4):
        (root / f"top{i}").write_bytes(rng.randbytes(64 << 10))


@contextlib.asynccontextmanager
async def _universe(base: Path, src: Path, holders: int = 6):
    """Server, client ``a`` and ``holders`` peers with storage negotiated
    (as tests/test_send_stage_spans.py)."""
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
        yield a
    finally:
        for app in [a] + peers:
            with contextlib.suppress(Exception):
                await app.stop()
        await server.stop()


def _backup(tmp_path, monkeypatch, route: str = "batched",
            before=None) -> tuple:
    """One backup of all-new data through ``Engine.run_backup``, after
    ``before(client)``; (pipeline report, overlap report, seconds by a
    clock around ``run_backup``)."""
    monkeypatch.setattr(defaults, "PACKFILE_TARGET_SIZE", PACKFILE)
    if route == "streaming":
        monkeypatch.setattr(engine_mod, "DirPacker", functools.partial(
            DirPacker, batch_bytes=256 << 10))
    src = tmp_path / "src"
    _tree(src, route)

    async def run():
        async with _universe(tmp_path, src) as a:
            a.engine.device_dedup = HostAnswers(a.engine.index)
            if before is not None:
                before(a)
            t0 = time.monotonic()
            await asyncio.wait_for(a.engine.run_backup(), 120)
            wall_s = time.monotonic() - t0
            assert a.engine._unsent_packfiles() == []
            return (a.engine.last_pipeline_report, a.engine.last_overlap,
                    wall_s)

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(asyncio.wait_for(run(), 200))
    finally:
        loop.close()


def _delayed_once(monkeypatch, owner, name: str) -> None:
    """``owner.name`` sleeps ``DELAY`` the first time it is called."""
    inner = getattr(owner, name)
    calls = []

    @functools.wraps(inner)
    def slow(*args, **kw):
        if not calls:
            calls.append(1)
            time.sleep(DELAY)
        return inner(*args, **kw)

    monkeypatch.setattr(owner, name, slow)


def _delay_the_device_sync(monkeypatch) -> None:
    """The delay inside ``_flush_device_sync``'s batch, where the sync
    has something to push (the seam its span is opened around)."""
    inner = DirPacker._flush_device_sync
    calls = []

    def slow(self):
        batch = self.dedup_batch

        def delayed(hashes):
            if not calls:
                calls.append(1)
                time.sleep(DELAY)
            return batch(hashes)

        self.dedup_batch = delayed
        try:
            inner(self)
        finally:
            self.dedup_batch = batch

    monkeypatch.setattr(DirPacker, "_flush_device_sync", slow)


@pytest.mark.parametrize("route", ["batched", "streaming", "source_tree"])
def test_the_phases_close_the_wall_and_the_steps_the_pack_thread(
        tmp_path, monkeypatch, route):
    # the two closures are clocks against clocks.  Between the pack
    # thread's steps lies list building only (a directory's files come
    # from ``pack.walk`` with their ``lstat``; the batch's queue of
    # them asks the file system nothing), 0.1-0.3 % of ``engine.pack``
    # on an idle machine, so the steps close at 2 %; on a machine whose
    # cores are all taken a thread that lets go of the interpreter lock
    # between two spans can wait for it longer than that, so a backup
    # that does not close is taken again, twice at most; a span that is
    # missing fails all three
    for attempt in range(3):
        rep, overlap, wall_s = _backup(tmp_path / f"try{attempt}",
                                       monkeypatch, route)
        wall, pack = rep["wall"], rep["pack"]
        closed = (
            wall["total_s"] == pytest.approx(wall_s, rel=0.05)
            and sum(pack["steps"].values()) == pytest.approx(
                pack["total_s"], rel=0.02))
        if closed:
            break
    assert closed, (wall, wall_s, pack)
    assert tuple(wall["phases"]) == obs_profile.WALL_PHASES
    assert sum(wall["phases"].values()) == pytest.approx(
        wall["total_s"], abs=1e-5)
    assert 0 <= wall["backup_done_s"] <= wall["phases"]["commit"]
    assert set(pack["steps"]) == set(obs_profile.PACK_STEPS.values())
    assert pack["total_s"] <= wall["phases"]["pack"]
    # the tree was looked at twice: the estimate's scan, the pack's listing
    scan = pack["scan"]
    assert scan["files"] > 0 and scan["dirs"] == (
        SOURCE_TREE["directories"] + 1 if route == "source_tree" else 2)
    assert scan["lstat_calls"] == 2 * scan["files"]
    assert scan["scandir_calls"] == 2 * scan["dirs"]
    # each route's own steps, and none of the other's
    mine, other = (("stream",), ("read", "manifest", "emit")) \
        if route == "streaming" else (("read", "manifest", "emit"),
                                      ("stream",))
    if route == "source_tree":
        # one batch for the 16 directories, and its counters in the report
        assert rep["batch"]["batches"] == 1
        assert rep["batch"]["dirs"] == SOURCE_TREE["directories"]
        assert rep["batch"]["batched_files"] == SOURCE_TREE["files"]
    assert all(pack["steps"][s] > 0 for s in mine + ("walk", "dir_tree",
                                                     "device_sync", "flush"))
    assert all(pack["steps"][s] == 0 for s in other)
    assert pack["seal_table_s"] > 0 and pack["stall_s"] >= 0
    # the spans that carry the phases' host work lie inside them
    spans = rep["stage_seconds"]
    for phase, name in (("estimate", "backup.estimate"),
                        ("index_flush", "backup.index_flush"),
                        ("commit", "backup.record_snapshot")):
        assert 0 < spans[name] <= wall["phases"][phase]
    # drain_s keeps its meaning: the index's flush, then the send loop's rest
    assert overlap["drain_s"] == pytest.approx(
        wall["phases"]["index_flush"] + wall["phases"]["drain"], abs=1e-5)


# where the delay goes (the owner from the started client), and where
# the ledger has to show it
DELAYS = {
    "estimate_size": (lambda a: a.engine, "estimate_size",
                      "phase", "estimate"),
    "index_flush": (lambda a: a.engine.index, "flush",
                    "phase", "index_flush"),
    "record_snapshot": (lambda a: a.engine.store, "record_snapshot",
                        "phase", "commit"),
    "device_sync": (None, None, "step", "device_sync"),
    "writer_flush": (lambda a: PackfileWriter, "flush", "step", "flush"),
    "challenge_table": (lambda a: engine_mod, "build_challenge_table",
                        "beside", "seal_table_s"),
}


@pytest.mark.parametrize("where", list(DELAYS))
def test_a_delay_lands_in_its_own_phase_or_step(tmp_path, monkeypatch,
                                                where):
    owner, name, level, key = DELAYS[where]

    def delay(a):
        if owner is None:
            _delay_the_device_sync(monkeypatch)
        else:
            _delayed_once(monkeypatch, owner(a), name)

    rep, _overlap, wall_s = _backup(tmp_path, monkeypatch, before=delay)
    wall, pack = rep["wall"], rep["pack"]
    phases, steps = wall["phases"], pack["steps"]
    # no second over the wall and none twice: the phases are the total,
    # the exclusive steps stay under the pack thread's span
    assert sum(phases.values()) == pytest.approx(wall["total_s"], abs=1e-5)
    assert wall["total_s"] <= wall_s
    assert sum(steps.values()) <= pack["total_s"] + 1e-4
    if level == "phase":
        assert phases[key] >= DELAY
        # the pack thread saw none of it
        assert pack["total_s"] <= wall_s - DELAY
    elif level == "step":
        assert steps[key] >= DELAY
        assert phases["pack"] >= pack["total_s"] >= DELAY
        assert sum(v for k, v in steps.items() if k != key) \
            <= pack["total_s"] - DELAY + 1e-4
    else:
        # the writer thread's, beside the partition: the pack thread meets
        # it only where it waits for the writer (stall, flush)
        assert pack[key] >= DELAY
        assert sum(steps.values()) <= pack["total_s"] + 1e-4


def test_every_new_span_is_opened_off_the_loop_through_the_annotator(
        tmp_path, monkeypatch):
    entered = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            loop = asyncio._get_running_loop()
            entered.append((self.name, threading.current_thread().name,
                            loop is not None))

        def __exit__(self, *exc):
            return False

    spans = obs_metrics.registry().get("bkw_span_seconds")
    before = {n: spans.count_value(name=n) for n in NEW_SPANS}
    installed = obs_trace._annotator
    obs_trace.set_annotator(Note)
    try:
        _backup(tmp_path, monkeypatch)
    finally:
        obs_trace.set_annotator(installed)
    opened = {n: int(spans.count_value(name=n) - before[n])
              for n in NEW_SPANS}
    bridged = {n: sum(1 for name, _t, _l in entered if name == n)
               for n in NEW_SPANS}
    assert all(opened[n] >= 1 for n in NEW_SPANS), opened
    # every opening went through the annotator: none was on a loop's thread
    assert bridged == opened
    assert not any(on_loop for name, _t, on_loop in entered
                   if name in NEW_SPANS)
    threads = {name: t for name, t, _l in entered if name in NEW_SPANS}
    assert threads["pack.seal_table"].startswith("pack-write")
    assert threads["pack.walk"] == threads["pack.flush"] \
        == threads["pack.device_sync"]  # the pack thread
