"""A tree is looked at twice (ISSUE 39): ``scan_tree``'s one ``os.scandir``
pass for the estimate and the batch lengths, ``DirPacker.pack``'s one a
directory as it is packed.  Held against a plain copy of the walk the
packer made before (five passes of ``pathlib`` calls, ``ORACLE`` below):
the same snapshot, the same blobs, the same ``PackStats``, the same
estimate and the same lengths handed to ``prepare_batches`` on every tree
of one set; and the calls counted, through ``Engine.run_backup`` and
through ``pack(root)`` alone.
"""

import asyncio
import collections
import contextlib
import dataclasses
import os
import random
from pathlib import Path
from types import SimpleNamespace

import pytest

from backuwup_tpu import defaults
from backuwup_tpu.app import ClientApp
from backuwup_tpu.crypto import KeyManager
from backuwup_tpu.engine import Engine
from backuwup_tpu.net.server import CoordinationServer
from backuwup_tpu.obs import profile as obs_profile
from backuwup_tpu.ops.backend import CpuBackend, NativeBackend
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.snapshot.blob_index import BlobIndex
from backuwup_tpu.snapshot.packer import DirPacker, _OpenDir, scan_tree
from backuwup_tpu.snapshot.packfile import PackfileWriter
from backuwup_tpu.wire import TreeKind, TreeMetadata

KEYS = KeyManager.from_secret(bytes(range(32)))
SMALL = CDCParams.from_desired(4096)
BATCH = 64 << 10


# --- the oracle: the packer's walk as it was before this change ------------

def oracle_estimate(root: Path) -> int:
    """``Engine.estimate_size``'s sum as it was: ``os.walk`` and one
    ``stat()`` a name that is no directory."""
    total = 0
    for dirpath, _dirnames, filenames in os.walk(root):
        for f in filenames:
            try:
                total += (Path(dirpath) / f).stat().st_size
            except OSError:
                pass
    return total


def oracle_dirs(root: Path) -> list:
    """The breadth-first discovery as it was."""
    order = [root]
    for d in order:
        try:
            subdirs = sorted(p for p in d.iterdir()
                             if p.is_dir() and not p.is_symlink())
        except OSError:
            subdirs = []
        order.extend(subdirs)
    return order


def oracle_batch_sizes(dirs: list, batch_bytes: int):
    """``DirPacker._batch_sizes`` as it was: a batch a directory, here
    in the order the directories are packed (since ISSUE 41 a batch
    spans directories up to ``dispatch_bytes``: 0 in ``_packer``, so a
    directory a batch still)."""
    for d in reversed(dirs):
        sizes = []
        pending = 0
        try:
            entries = sorted(d.iterdir())
        except OSError:
            continue
        for p in entries:
            try:
                if p.is_symlink() or not p.is_file():
                    continue
                n = p.lstat().st_size
            except OSError:
                continue
            if n > batch_bytes:
                continue
            sizes.append(n)
            pending += n
            if pending >= batch_bytes:
                yield sizes
                sizes, pending = [], 0
        if sizes:
            yield sizes


def _pack_files(packer: DirPacker, pairs: list) -> list:
    """A directory's files as one batch of their own, as ``_pack_files``
    packed them: their tree hashes."""
    odir = _OpenDir("", "", pairs, [], [None] * len(pairs))
    packer._queue_files(odir)
    packer._flush_batch()
    return odir.hashes


def oracle_pack(packer: DirPacker, root: Path) -> bytes:
    """``DirPacker.pack`` as it was, down to ``_pack_files``' ``lstat``
    loop, which it hands on as the pairs the batch takes now."""
    order = oracle_dirs(root)
    dir_hash = {}
    for d in reversed(order):
        try:
            entries = sorted(d.iterdir())
        except OSError:
            entries = []
        files = [p for p in entries if p.is_file() and not p.is_symlink()]
        subdirs = [p for p in entries if p.is_dir() and not p.is_symlink()]
        pairs = []
        for p in files:
            try:
                pairs.append((p, p.lstat()))
            except OSError:
                packer.stats.failed_files += 1
        children = [h for h in _pack_files(packer, pairs) if h is not None]
        children.extend(dir_hash[s] for s in subdirs if s in dir_hash)
        try:
            st = d.stat()
            meta = TreeMetadata(size=0, mtime_ns=st.st_mtime_ns,
                                ctime_ns=st.st_ctime_ns)
        except OSError:
            meta = TreeMetadata()
        name = "" if d == root else d.name
        dir_hash[d] = packer._tree_with_split(TreeKind.DIR, name, meta,
                                              children)
        packer.stats.dirs += 1
    packer._flush_device_sync()
    packer.writer.flush()
    return dir_hash[root]


# --- the trees -------------------------------------------------------------

def _write(root: Path, files: dict) -> None:
    rng = random.Random(39)
    for rel, size in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(rng.randbytes(size))


def flat(root):
    _write(root, {f"f{i}": 3000 + 700 * i for i in range(6)})


def nested(root):
    _write(root, {"top": 100, "a/one": 5000, "a/b/two": 9000,
                  "a/b/c/three": 20_000, "a/b/c/empty": 0, "z/last": 1})


def empty_dir(root):
    _write(root, {"f": 10})
    (root / "nothing" / "below").mkdir(parents=True)


def symlinks(root):
    _write(root, {"real": 7000, "d/inner": 4000})
    (root / "to_file").symlink_to(root / "real")
    (root / "to_dir").symlink_to(root / "d")
    (root / "d" / "up").symlink_to("..")
    (root / "broken").symlink_to(root / "gone")


def fifo(root):
    _write(root, {"f": 1234, "d/g": 4321})
    os.mkfifo(root / "pipe")
    os.mkfifo(root / "d" / "pipe")


def sort_rules(root):
    # names that sort differently by locale, by case-folding or with
    # the separator counted: the packer's order is the code points'
    names = ["a", "a0", "a-b", "a.b", "A", "ä", "a.d0", "a b", "B", "_"]
    _write(root, {n: 100 + 50 * i for i, n in enumerate(names)})
    _write(root, {"a.d/x": 2000, "a.d/X": 2100, "a-d/y": 10, "Ad/z": 20,
                  "äd/w": 30})


def oversized(root):
    _write(root, {"big": 3 * BATCH + 17, "small": 500, "d/big2": BATCH + 1,
                  "d/fill0": BATCH // 2, "d/fill1": BATCH // 2,
                  "d/fill2": 999})


def unlistable(root):
    _write(root, {"f": 10, "locked/hidden": 5000, "locked/sub/deep": 100,
                  "open/seen": 6000})


TREES = {f.__name__: f for f in (flat, nested, empty_dir, symlinks, fifo,
                                 sort_rules, oversized, unlistable)}


class HostAnswers:
    """A device index's seam answered by the host index: with it the
    packer tells the backend the batches to come (``pack.prepare``)."""

    def __init__(self, index):
        self.index = index

    def classify_insert(self, hashes):
        return [self.index.is_duplicate(h) for h in hashes]


class Recording(CpuBackend):
    def __init__(self):
        super().__init__(SMALL)
        self.prepared = None

    def prepare_batches(self, batches, dedup):
        self.prepared = [list(sizes) for sizes in batches]


def _packer(base: Path):
    index = BlobIndex(KEYS, base / "index")
    writer = PackfileWriter(
        KEYS, base / "pack",
        on_packfile=lambda pid, path, hashes, size:
            index.finalize_packfile(pid, hashes))
    blobs = []
    packer = DirPacker(Recording(), writer, index, batch_bytes=BATCH,
                       dispatch_bytes=0, dedup_index=HostAnswers(index),
                       on_blob=lambda h, n: blobs.append((h, n)))
    return packer, blobs


def _stats(packer) -> dict:
    out = dataclasses.asdict(packer.stats)
    del out["chunk_hash_s"]  # a clock
    return out


@contextlib.contextmanager
def _locked(monkeypatch, path: Path):
    """``path`` cannot be listed (the tests run as root, whom no mode
    stops): ``os.scandir`` and ``os.listdir``, which ``pathlib`` and
    ``os.walk`` go through, raise for it."""
    with monkeypatch.context() as m:
        for name in ("scandir", "listdir"):
            inner = getattr(os, name)

            def refuse(p=".", inner=inner):
                if os.fspath(p) == str(path):
                    raise PermissionError(13, "Permission denied", str(p))
                return inner(p)

            m.setattr(os, name, refuse)
        yield


@pytest.mark.parametrize("tree", list(TREES))
def test_the_snapshot_the_estimate_and_the_lengths_are_the_old_walks(
        tmp_path, monkeypatch, tree):
    src = tmp_path / "src"
    src.mkdir()
    TREES[tree](src)
    lock = _locked(monkeypatch, src / "locked") if tree == "unlistable" \
        else contextlib.nullcontext()
    with lock:
        old, old_blobs = _packer(tmp_path / "old")
        old_root = oracle_pack(old, src)
        new, new_blobs = _packer(tmp_path / "new")
        new_root = new.pack(src)
        engine = SimpleNamespace(
            store=SimpleNamespace(last_backup_size=lambda: None))
        assert Engine.estimate_size(engine, src) == oracle_estimate(src)
        assert new.backend.prepared == list(
            oracle_batch_sizes(oracle_dirs(src), BATCH))
        # the engine's scan, handed on: the same snapshot once more
        handed, handed_blobs = _packer(tmp_path / "handed")
        assert handed.pack(src, scan=engine._tree_scan) == old_root
    assert new_root == old_root
    assert new_blobs == old_blobs == handed_blobs
    assert _stats(new) == _stats(old) == _stats(handed)
    assert old.stats.files > 0 and old.stats.failed_files == 0


def test_names_sort_as_paths_did(tmp_path):
    sort_rules(tmp_path)
    scan = scan_tree(tmp_path)
    assert [Path(d) for d in scan.dirs] == oracle_dirs(tmp_path)
    listed = sorted(p for p in tmp_path.iterdir() if p.is_file())
    assert list(scan.file_sizes[0]) == [p.stat().st_size for p in listed]


def test_a_symlink_adds_what_it_points_to_and_the_incremental_rule_holds(
        tmp_path):
    symlinks(tmp_path)
    total = oracle_estimate(tmp_path)
    assert total == 7000 + 4000 + 7000  # the link to ``real`` once more
    for last in (None, 0, total - 1, 10 * total):
        engine = SimpleNamespace(
            store=SimpleNamespace(last_backup_size=lambda: last))
        want = total if last is None else max(
            total - last, min(total, 50 * 1000 * 1000))
        assert Engine.estimate_size(engine, tmp_path) == want


def test_a_file_removed_between_the_passes_is_not_in_the_snapshot(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    nested(src)
    before = list(oracle_batch_sizes(oracle_dirs(src), BATCH))
    estimate = oracle_estimate(src)
    scan = scan_tree(src)
    (src / "a" / "b" / "two").unlink()
    new, new_blobs = _packer(tmp_path / "new")
    new_root = new.pack(src, scan=scan)
    # the old walk met the same: its discovery and its lengths came
    # before, its listing of ``a/b`` after
    old, old_blobs = _packer(tmp_path / "old")
    assert new_root == oracle_pack(old, src)
    assert new_blobs == old_blobs and _stats(new) == _stats(old)
    assert new.stats.failed_files == 0 and new.stats.files == 5
    assert scan.total_bytes == estimate
    assert new.backend.prepared == before
    assert [9000] in before


@pytest.mark.parametrize("big", [False, True], ids=["batched", "streamed"])
def test_a_file_that_vanishes_after_its_listing_is_counted_failed(
        tmp_path, monkeypatch, big):
    src = tmp_path / "src"
    src.mkdir()
    _write(src, {"keep": 4000, "lost": 3 * BATCH if big else 4000})
    inner = DirPacker._queue_dir

    def vanish_first(self, odir):
        (src / "lost").unlink(missing_ok=True)
        return inner(self, odir)

    monkeypatch.setattr(DirPacker, "_queue_dir", vanish_first)
    packer, blobs = _packer(tmp_path / "new")
    root = packer.pack(src)
    assert packer.stats.failed_files == 1 and packer.stats.files == 1
    monkeypatch.undo()
    again, again_blobs = _packer(tmp_path / "again")
    assert again.pack(src) == root and again_blobs == blobs


def test_a_scan_of_another_tree_is_refused(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    packer, _ = _packer(tmp_path / "p")
    with pytest.raises(ValueError):
        packer.pack(tmp_path / "a", scan=scan_tree(tmp_path / "b"))
    with pytest.raises(NotADirectoryError):
        packer.pack(tmp_path / "none")


def test_the_scan_holds_integers_and_directories_only(tmp_path):
    oversized(tmp_path)
    scan = scan_tree(tmp_path)
    assert scan.dirs == [str(tmp_path), str(tmp_path / "d")]
    assert [a.typecode for a in scan.file_sizes] == ["q", "q"]
    assert scan.total_bytes == sum(map(sum, scan.file_sizes))
    # a larger file is left out of the batches (it is streamed) and the
    # batch open before its directory is closed; a batch is cut where it
    # reaches batch_bytes; ``d`` is packed before the root
    assert list(scan.batches(BATCH)) == [
        [BATCH // 2, BATCH // 2], [999], [500]]
    # nothing streams and both directories are under a dispatch's worth
    assert list(scan.batches(1 << 30)) == [
        [BATCH + 1, BATCH // 2, BATCH // 2, 999, 3 * BATCH + 17, 500]]
    assert list(scan.batches(1 << 30, dispatch_bytes=0)) == [
        [BATCH + 1, BATCH // 2, BATCH // 2, 999], [3 * BATCH + 17, 500]]


# --- the calls, counted ----------------------------------------------------

N_FILES = 200
N_DIRS = 6  # the root, four under it, one under the first


def _counted_tree(root: Path) -> None:
    files = {f"d{i % 4}/f{i:03d}": 800 + 13 * i for i in range(N_FILES - 8)}
    files.update({f"d0/deep/g{i}": 5000 for i in range(4)})
    files.update({f"top{i}": 20_000 for i in range(4)})
    _write(root, files)


class Calls:
    """``os.stat``, ``os.lstat``, ``os.scandir``, ``os.walk`` and
    ``os.listdir`` counted by the path they were asked about, under
    ``root`` (``pathlib`` goes through them on 3.12)."""

    NAMES = ("stat", "lstat", "scandir", "walk", "listdir")

    def __init__(self, monkeypatch, root: Path):
        self.root = str(root)
        self.seen = {name: collections.Counter() for name in self.NAMES}
        for name in self.NAMES:
            monkeypatch.setattr(os, name, self._counting(name))

    def _counting(self, name):
        inner = getattr(os, name)
        seen = self.seen[name]

        def counted(path=".", *args, **kw):
            if not isinstance(path, int):
                p = os.fsdecode(os.fspath(path))
                if p == self.root or p.startswith(self.root + os.sep):
                    seen[p] += 1
            return inner(path, *args, **kw)

        return counted

    def check(self, scan: dict) -> None:
        files = {str(p) for p in Path(self.root).rglob("*") if p.is_file()}
        assert len(files) == N_FILES
        assert not self.seen["walk"] and not self.seen["listdir"]
        assert len(self.seen["scandir"]) == N_DIRS
        assert max(self.seen["scandir"].values()) <= 2
        for name in ("stat", "lstat"):
            assert not files & set(self.seen[name]), name
        assert scan == {"dirs": N_DIRS, "files": N_FILES,
                        "scandir_calls": 2 * N_DIRS,
                        "lstat_calls": 2 * N_FILES}


def test_pack_alone_asks_twice_a_file_and_twice_a_directory(
        tmp_path, monkeypatch):
    src = tmp_path / "src"
    _counted_tree(src)
    packer, _ = _packer(tmp_path / "p")
    base = obs_profile.baseline()
    with monkeypatch.context() as m:
        calls = Calls(m, src)
        packer.pack(src)
    assert packer.stats.files == N_FILES and packer.stats.dirs == N_DIRS
    calls.check(obs_profile.report(base)["pack"]["scan"])


@contextlib.asynccontextmanager
async def _universe(base: Path, src: Path, holders: int = 6):
    """Server, client ``a`` and ``holders`` peers with storage negotiated
    (as tests/test_backup_wall_ledger.py)."""
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


@pytest.mark.dataflow
def test_a_backup_asks_twice_a_file_and_twice_a_directory(
        tmp_path, monkeypatch):
    monkeypatch.setattr(defaults, "PACKFILE_TARGET_SIZE", 256 << 10)
    src = tmp_path / "src"
    _counted_tree(src)

    async def run():
        async with _universe(tmp_path, src) as a:
            a.engine.device_dedup = HostAnswers(a.engine.index)
            with monkeypatch.context() as m:
                calls = Calls(m, src)
                await asyncio.wait_for(a.engine.run_backup(), 120)
            assert a.engine._unsent_packfiles() == []
            assert a.engine._tree_scan is None  # taken by its backup
            return calls, a.engine.last_pipeline_report

    loop = asyncio.new_event_loop()
    try:
        calls, rep = loop.run_until_complete(asyncio.wait_for(run(), 200))
    finally:
        loop.close()
    calls.check(rep["pack"]["scan"])
    # the engine handed its scan on: the pack thread made none
    assert calls.seen["scandir"] == {
        d: 2 for d in calls.seen["scandir"]}
    assert rep["pack"]["steps"]["walk"] > 0
