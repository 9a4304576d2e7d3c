"""Tree nodes are hashed on the host as they are built (ISSUE 45):
``DirPacker._add_tree`` calls ``native.host_digest``, the C library's
BLAKE3 where it loads and the scalar-Python oracle where it does not.
The snapshot does not depend on which; ``bkw_tree_node_digests_total``
says which it was; and no node reaches the backend's ``digest_many`` or
any dispatch (PR 44 sent them there, an upload, a program and a wait a
leaf bucket inside the emit: ``ref-1m.incr`` fell 11 %)."""

import hashlib
import logging
import os

import pytest

from backuwup_tpu import defaults, native
from backuwup_tpu.crypto import KeyManager
from backuwup_tpu.net import p2p
from backuwup_tpu.obs import profile as obs_profile
from backuwup_tpu.ops.backend import CpuBackend
from backuwup_tpu.ops.blake3_cpu import blake3_hash
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.snapshot import packer as packer_mod
from backuwup_tpu.snapshot.blob_index import BlobIndex
from backuwup_tpu.snapshot.packer import DirPacker
from backuwup_tpu.snapshot.packfile import PackfileWriter
from backuwup_tpu.wire import BlobKind, Tree, TreeKind, TreeMetadata

KEYS = KeyManager.from_secret(bytes(range(32)))
SMALL = CDCParams.from_desired(4096)
NODE_LENGTHS = (0, 1, 63, 64, 65, 1023, 1024, 1025, 32_100, 147_000)
STAMP = 1_600_000_000_000_000_000


def _tree(root, rng, files: int = 40, dirs: int = 4) -> int:
    """``files`` small files spread over ``dirs`` directories under
    ``root`` and one of 60 KB beside them; returns the nodes a pack
    builds (a file's each, a directory's each, the root's)."""
    root.mkdir()
    for d in range(dirs):
        (root / f"d{d}").mkdir()
    for i in range(files):
        path = root / f"d{i % dirs}" / f"f{i:03d}.bin"
        path.write_bytes(rng.randbytes(rng.randrange(1, 9000)))
    (root / "big.bin").write_bytes(rng.randbytes(60_000))
    for dirpath, _dirs, names in os.walk(root, topdown=False):
        for name in names:
            os.utime(os.path.join(dirpath, name), ns=(STAMP, STAMP))
        os.utime(dirpath, ns=(STAMP, STAMP))
    return files + 1 + dirs + 1


class Packed:
    """One pack of ``src`` into a state directory of its own: the root
    hash, every blob the writer was given (kind, hash, bytes) in order,
    and the backup's delta of the profile's report."""

    def __init__(self, base, src, backend=None, device: bool = False,
                 **cut):
        index = BlobIndex(KEYS, base / "index")
        writer = PackfileWriter(
            KEYS, base / "pack",
            on_packfile=lambda pid, path, hashes, size:
                index.finalize_packfile(pid, hashes))
        self.blobs = []
        write = writer.add_blob

        def write_seen(blob):
            self.blobs.append((blob.kind, bytes(blob.hash), bytes(blob.data)))
            write(blob)

        writer.add_blob = write_seen
        # the device table's seam answered by the host index: the
        # packer takes the classified route and counts its dispatches
        dedup_batch = ((lambda hashes: [index.is_duplicate(h)
                                        for h in hashes])
                       if device else None)
        rep0 = obs_profile.baseline()
        packer = DirPacker(backend or CpuBackend(SMALL), writer, index,
                           dedup_batch=dedup_batch, **cut)
        self.root_hash = packer.pack(src)
        writer.shutdown()
        self.stats = packer.stats
        self.report = obs_profile.report(rep0)

    def nodes(self) -> list:
        return [(h, data) for kind, h, data in self.blobs
                if kind == BlobKind.TREE]


@pytest.mark.parametrize("n", NODE_LENGTHS)
def test_host_digest_is_the_oracles(n, rng):
    data = rng.randbytes(n)
    assert native.host_digest(data) == blake3_hash(data)


@pytest.mark.parametrize("n", (0, 100, 70_000))
def test_without_the_library_each_caller_gets_its_oracle(n, rng,
                                                         monkeypatch):
    """``host_digest`` where the library does not load: the scalar
    reference for the packer, the numpy batch engine for a transfer's
    whole file (``p2p._file_digest``, as before it was lifted), and
    never the library."""
    data = rng.randbytes(n)
    want = blake3_hash(data)
    assert p2p._file_digest(data) == want
    monkeypatch.setattr(native, "available", lambda: False)
    monkeypatch.setattr(native, "blake3_native", None)  # a call raises
    assert native.host_digest(data) == want
    assert p2p._file_digest(data) == want


def test_the_snapshot_does_not_depend_on_the_engine(tmp_path, rng,
                                                    monkeypatch, caplog):
    n_nodes = _tree(tmp_path / "src", rng)
    found = "native" if native.available() else "oracle"
    other = {"native": "oracle", "oracle": "native"}
    first = Packed(tmp_path / "a", tmp_path / "src")
    assert first.report["pack"]["tree_nodes"] == {found: n_nodes,
                                                  other[found]: 0}

    monkeypatch.setattr(native, "available", lambda: False)
    monkeypatch.setattr(packer_mod, "_oracle_warned", False)
    with caplog.at_level(logging.WARNING, logger=packer_mod.__name__):
        second = Packed(tmp_path / "b", tmp_path / "src")
        third = Packed(tmp_path / "c", tmp_path / "src")
    assert second.report["pack"]["tree_nodes"] == {"native": 0,
                                                   "oracle": n_nodes}
    assert third.report["pack"]["tree_nodes"]["oracle"] == n_nodes
    warned = [r for r in caplog.records if "oracle" in r.getMessage()]
    assert len(warned) == 1  # a process, not a packer

    assert first.root_hash == second.root_hash == third.root_hash
    assert first.blobs == second.blobs  # the same blobs in the same order
    assert len(first.nodes()) == n_nodes
    for h, data in first.nodes():
        assert h == blake3_hash(data)


def test_a_split_nodes_page_chain_is_the_oracles(tmp_path, rng,
                                                 monkeypatch):
    monkeypatch.setattr(defaults, "TREE_MAX_CHILDREN", 10)
    src = tmp_path / "src"
    src.mkdir()
    for i in range(37):
        (src / f"f{i:03d}.txt").write_bytes(f"file {i}".encode())
        os.utime(src / f"f{i:03d}.txt", ns=(STAMP, STAMP))
    os.utime(src, ns=(STAMP, STAMP))
    packed = Packed(tmp_path / "a", src)
    # 37 files' nodes and the root's four pages
    assert sum(packed.report["pack"]["tree_nodes"].values()) == 37 + 4

    by_hash = dict(packed.nodes())
    head = Tree.decode_bytes(by_hash[packed.root_hash])
    children, page = [], head
    while True:
        children.extend(page.children)
        if page.next_sibling is None:
            break
        page = Tree.decode_bytes(by_hash[bytes(page.next_sibling)])
    assert len(children) == 37
    # the chain again, back to front, by the oracle alone
    next_hash = None
    for i in reversed(range(0, 37, 10)):
        next_hash = blake3_hash(Tree(
            kind=TreeKind.DIR, name=head.name, metadata=head.metadata,
            children=children[i:i + 10],
            next_sibling=next_hash).encode_bytes())
    assert next_hash == packed.root_hash


class ChunksOnly(CpuBackend):
    """``digest_many`` answers the backend's own chunk seams
    (``manifest_many``, ``_stream_digest``) and raises for every other
    caller, which is what a tree node sent to it would be."""

    def __init__(self):
        super().__init__(SMALL)
        self._chunks = False
        self.digested = set()

    def _as_chunks(self, seam, arg):
        self._chunks = True
        try:
            return seam(arg)
        finally:
            self._chunks = False

    def manifest_many(self, streams):
        return self._as_chunks(super().manifest_many, streams)

    def _stream_digest(self, pieces):
        return self._as_chunks(super()._stream_digest, pieces)

    def digest_many(self, datas):
        if not self._chunks:
            raise AssertionError("digest_many from outside a chunk seam")
        self.digested.update(bytes(d) for d in datas)
        return super().digest_many(datas)


@pytest.mark.parametrize("route", ("batched", "streaming"))
def test_no_tree_node_reaches_the_backend(tmp_path, rng, monkeypatch,
                                          route):
    """PR 44's lesson: a node through ``digest_many`` is an upload, a
    program and a wait inside the emit.  None goes there, on either
    route, and the dispatch counters do not see the nodes' hashing."""
    n_nodes = _tree(tmp_path / "src", rng)
    # streaming: the 60 KB file is over the batch and streamed
    cut = {"batch_bytes": 20_000} if route == "streaming" else {}
    backend = ChunksOnly()
    packed = Packed(tmp_path / "a", tmp_path / "src", backend=backend,
                    device=True, **cut)
    assert packed.stats.files == 41 and packed.stats.failed_files == 0
    streamed = packed.report["stage_seconds"].get("stream.file", 0) > 0
    assert streamed == (route == "streaming")
    assert len(packed.nodes()) == n_nodes
    assert backend.digested  # the chunks went through it
    assert not backend.digested & {data for _h, data in packed.nodes()}

    # the same pack with nothing hashing the nodes at all
    monkeypatch.setattr(native, "host_digest",
                        lambda data: hashlib.sha256(data).digest())
    unhashed = Packed(tmp_path / "b", tmp_path / "src",
                      backend=ChunksOnly(), device=True, **cut)
    assert unhashed.root_hash != packed.root_hash
    assert sum(packed.report["dispatches"].values()) > 0
    for section in ("dispatches", "bytes", "padded_bytes"):
        assert unhashed.report[section] == packed.report[section]
