"""A pack batch spans directories (ISSUE 41): the files of consecutive
directories join one open batch, which closes at ``batch_bytes`` and, at
a directory boundary, where the next directory would take it past
``dispatch_bytes``.  On a small ``source_tree`` (the benchmark's tree of
many small files in many directories): the snapshot does not depend on
the cut; ``TreeScan.batches`` cuts what ``pack()`` flushes; the counters
count it; and through ``TpuBackend(CDCParams())`` with a tiered device
index the snapshot read back from its root hash is the benchmark's
reference's, after generation 0 and after one ``source_churn`` night.
"""

import dataclasses
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from backuwup_tpu.crypto import KeyManager
from backuwup_tpu.dedupstore import TieredDedupIndex
from backuwup_tpu.obs import metrics as obs_metrics
from backuwup_tpu.obs import profile as obs_profile
from backuwup_tpu.ops.backend import NativeBackend, TpuBackend
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.ops.pipeline import _POOL_STREAM_STEP
from backuwup_tpu.snapshot.blob_index import BlobIndex
from backuwup_tpu.snapshot.packer import DirPacker, scan_tree
from backuwup_tpu.snapshot.packfile import PackfileReader, PackfileWriter
from backuwup_tpu.snapshot.unpacker import fetch_full_tree
from backuwup_tpu.wire import BlobKind, TreeKind
from benchmark import check
from benchmark.generators import source_churn, source_tree
from benchmark.reference import native
from benchmark.reference.gear import CDCParams as RefParams

KEYS = KeyManager.from_secret(bytes(range(32)))
CDC = {"min_size": 262144, "desired_size": 1048576, "max_size": 3145728,
       "mask_s_bits": 22, "mask_l_bits": 18}
REF = RefParams(**CDC)
# 150 files in 16 directories under three top-level ones, 2.5 MiB: the
# three tiny leaf classes and one file over the minimum chunk
TREE = {"files": 150, "directories": 16, "top_level": 3, "max_depth": 4,
        "dir_files_median": 7, "dir_files_sigma": 1.2, "dir_files_min": 1,
        "dir_files_max": 600, "size_median_bytes": 6144, "size_sigma": 1.45,
        "size_min_bytes": 64, "size_max_bytes": 4194304,
        "size_mean_bytes": 17408}
NIGHT = {"directories": 2, "min_dir_files": 12, "rewritten": 8, "added": 2,
         "deleted": 1, "added_step": 7, "max_file_bytes": 262144,
         "new_bytes": 174080, "new_bytes_tolerance": 0.02,
         "tree_params": TREE}
LARGEST = max(source_tree.file_sizes(TREE))


class HostAnswers:
    """A device index's seam answered by the host index: the packer
    takes the classified route with nothing to compile."""

    def __init__(self, index):
        self.index = index
        self.lengths = []  # of every batch of hashes it was asked about

    def classify_insert(self, hashes):
        self.lengths.append(len(hashes))
        return [self.index.is_duplicate(h) for h in hashes]


class Recording(NativeBackend):
    """What ``prepare_batches`` was told, and what was then flushed."""

    def __init__(self):
        super().__init__(CDCParams())
        self.prepared = None
        self.flushed = []

    def prepare_batches(self, batches, dedup):
        self.prepared = [list(sizes) for sizes in batches]

    def manifest_many_classified(self, streams, dedup):
        self.flushed.append([len(s) for s in streams])
        return super().manifest_many_classified(streams, dedup)


@dataclasses.dataclass
class Packed:
    root_hash: bytes
    blobs: list  # (hash, length) of every blob written, in order
    stats: dict
    backend: object
    report: dict


def _pack(base, src, device: bool = True, scan=None, **cut) -> Packed:
    index = BlobIndex(KEYS, base / "index")
    blobs = []
    writer = PackfileWriter(
        KEYS, base / "pack",
        on_packfile=lambda pid, path, hashes, size:
            index.finalize_packfile(pid, hashes))
    write = writer.add_blob

    def write_seen(blob):
        blobs.append((bytes(blob.hash), len(blob.data)))
        write(blob)

    writer.add_blob = write_seen
    backend = Recording()
    backend.answers = HostAnswers(index) if device else None
    packer = DirPacker(backend, writer, index, dedup_index=backend.answers,
                       **cut)
    before = obs_profile.baseline()
    root_hash = packer.pack(src, scan)
    stats = dataclasses.asdict(packer.stats)
    del stats["chunk_hash_s"]  # a clock
    return Packed(root_hash, blobs, stats, backend,
                  obs_profile.report(before))


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    src = tmp_path_factory.mktemp("pack_batches") / "src"
    source_tree.build(src, TREE, np.random.default_rng([40, 0]))
    (src / "t00" / "empty").write_bytes(b"")
    return src


@pytest.fixture(scope="module")
def host_only(tree, tmp_path_factory):
    """``NativeBackend``'s pack of the tree with no device index."""
    return _pack(tmp_path_factory.mktemp("host_only"), tree, device=False)


# the cut: ``batch_bytes`` and ``dispatch_bytes``, and the batches a
# backup of ``TREE`` then makes (None: not counted by hand)
CUTS = {
    "a_few_files_a_batch": ({"batch_bytes": LARGEST, "dispatch_bytes": 0},
                            None),
    "a_directory_a_batch": ({"dispatch_bytes": 0}, 16),
    "the_whole_tree_one_batch": ({"dispatch_bytes": 1 << 40}, 1),
    "the_program_s": ({}, 1),
    "half_a_mebibyte_a_batch": ({"dispatch_bytes": 512 << 10}, None),
    "the_large_files_streamed": ({"batch_bytes": 64 << 10}, None),
}


@pytest.mark.parametrize("cut", list(CUTS))
def test_the_snapshot_does_not_depend_on_the_cut(tree, host_only, tmp_path,
                                                 cut):
    kw, batches = CUTS[cut]
    got = _pack(tmp_path / "cut", tree, **kw)
    assert got.root_hash == host_only.root_hash
    # the same blobs in the same order in the packfiles; a file over
    # batch_bytes is streamed where it is met, ahead of the batch its
    # directory's other files wait in, so there the order is that of a
    # batch a directory with the same batch_bytes
    same_order = host_only
    if cut == "the_large_files_streamed":
        same_order = _pack(tmp_path / "dirs", tree, **{**kw,
                                                       "dispatch_bytes": 0})
        assert sorted(got.blobs) == sorted(host_only.blobs)
    assert got.blobs == same_order.blobs
    assert got.stats == host_only.stats
    assert got.stats["files"] == TREE["files"] + 1
    assert got.stats["dirs"] == TREE["directories"] + 1
    if batches is not None:
        assert len(got.backend.flushed) == batches


@pytest.mark.parametrize("cut", list(CUTS))
def test_the_scan_cuts_the_batches_the_pack_flushes(tree, tmp_path, cut):
    kw, _batches = CUTS[cut]
    got = _pack(tmp_path, tree, **kw)
    # lengths and order: nothing is compiled ahead that the backup does
    # not run, and the backup runs nothing that was not compiled ahead
    assert got.backend.prepared == got.backend.flushed
    scan = scan_tree(tree)
    assert got.backend.flushed == list(scan.batches(
        kw.get("batch_bytes", 256 << 20),
        kw.get("dispatch_bytes", _POOL_STREAM_STEP)))
    streamed = sum(1 for sizes in scan.file_sizes for n in sizes
                   if n > kw.get("batch_bytes", 256 << 20))
    assert sum(map(len, got.backend.flushed)) + streamed \
        == TREE["files"] + 1
    assert (streamed > 0) == (cut == "the_large_files_streamed")


@pytest.mark.parametrize("cut", list(CUTS))
def test_the_counters_count_the_batches_and_what_they_held(tree, tmp_path,
                                                           cut):
    kw, _batches = CUTS[cut]
    got = _pack(tmp_path, tree, **kw)
    batch = got.report["batch"]
    assert batch["batches"] == len(got.backend.flushed)
    assert batch["batched_files"] == sum(map(len, got.backend.flushed))
    # a directory cut by batch_bytes counts in each of its batches
    with_files = sum(1 for sizes in scan_tree(tree).file_sizes
                     if any(n <= kw.get("batch_bytes", 256 << 20)
                            for n in sizes))
    assert batch["dirs"] >= with_files
    if "batch_bytes" not in kw:
        assert batch["dirs"] == with_files == TREE["directories"]
    assert got.report["dispatches"]["index"] >= batch["batches"]


def test_a_directory_of_a_dispatch_s_worth_is_a_batch_of_its_own(tmp_path):
    """Trees of a few large directories batch as they did: what is open
    closes before a directory that would take it past the threshold, so
    a small directory's blobs do not wait for a large one's read."""
    src = tmp_path / "src"
    sizes = {"a": [40_000] * 3, "b": [30_000] * 2, "c": [90_000] * 2,
             "d": [5_000] * 4, "e": [6_000] * 3}
    for name, lengths in sizes.items():
        (src / name).mkdir(parents=True)
        for i, n in enumerate(lengths):
            (src / name / f"f{i}").write_bytes(bytes([i + 1]) * n)
    got = _pack(tmp_path / "p", src, dispatch_bytes=100_000)
    # packed e, d, c, b, a: e + d join (38,000), c would take them past
    # 100,000 and is over it alone, b + a would be 180,000
    assert got.backend.flushed == [
        [6_000] * 3 + [5_000] * 4, [90_000] * 2, [30_000] * 2,
        [40_000] * 3]
    assert got.backend.prepared == got.backend.flushed
    # what the index's device seam is asked, in order: a batch's chunks,
    # then the nodes of the batch (a file each, and each directory that
    # ended in it); the root's, which has no file, with the last push
    assert got.backend.answers.lengths == [
        7, 7 + 2, 2, 2 + 1, 2, 2 + 1, 3, 3 + 1, 1]


# --- what goes wrong between the two looks, inside a spanning batch --------

def _deepest_dir_with_files(src, at_least: int = 3):
    return max((p for p in src.rglob("*") if p.is_dir()
                and sum(c.is_file() for c in p.iterdir()) >= at_least),
               key=lambda p: (len(p.parts), str(p)))


def _a_directory_vanishes(src, monkeypatch):
    import shutil
    shutil.rmtree(_deepest_dir_with_files(src))
    return {"failed_files": 0}


def _a_directory_cannot_be_listed(src, monkeypatch):
    shut = str(_deepest_dir_with_files(src))
    scandir = os.scandir

    def refuse(path):
        if str(path) == shut:
            raise PermissionError(13, "Permission denied", shut)
        return scandir(path)

    monkeypatch.setattr(os, "scandir", refuse)
    return {"failed_files": 0}


def _a_file_vanishes_before_its_listing(src, monkeypatch):
    sorted(p for p in _deepest_dir_with_files(src).iterdir()
           if p.is_file())[1].unlink()
    return {"failed_files": 0}


def _a_file_vanishes_before_its_read(src, monkeypatch):
    lost = sorted(p for p in _deepest_dir_with_files(src).iterdir()
                  if p.is_file())[1]
    read_bytes = type(lost).read_bytes

    def gone(self):
        if self == lost:
            raise FileNotFoundError(2, "No such file or directory", str(self))
        return read_bytes(self)

    monkeypatch.setattr(type(lost), "read_bytes", gone)
    return {"failed_files": 1}


MISHAPS = {f.__name__[1:]: f for f in (
    _a_directory_vanishes, _a_directory_cannot_be_listed,
    _a_file_vanishes_before_its_listing, _a_file_vanishes_before_its_read)}


@pytest.mark.parametrize("mishap", list(MISHAPS))
def test_a_mishap_inside_a_spanning_batch_is_what_it_was(tmp_path,
                                                          monkeypatch,
                                                          mishap):
    """The tree changes between the scan and the pack: the batch that
    spans directories makes of it what a batch a directory made (which
    ``tests/test_tree_scan.py`` holds to the old walk)."""
    src = tmp_path / "src"
    source_tree.build(src, TREE, np.random.default_rng([41, 0]))
    scan = scan_tree(src)
    want = MISHAPS[mishap](src, monkeypatch)
    spanning = _pack(tmp_path / "spanning", src, scan=scan)
    by_dir = _pack(tmp_path / "by_dir", src, scan=scan, dispatch_bytes=0)
    assert len(spanning.backend.flushed) == 1 < len(by_dir.backend.flushed)
    assert spanning.root_hash == by_dir.root_hash
    assert spanning.blobs == by_dir.blobs
    assert spanning.stats == by_dir.stats
    assert spanning.stats["failed_files"] == want["failed_files"]
    # every directory of the scan keeps its node, a vanished one too
    assert spanning.stats["dirs"] == TREE["directories"] + 1
    assert spanning.stats["files"] < TREE["files"]


# --- through the device backend, against the benchmark's reference ---------

def _snapshot_files(resolve, root_hash: bytes) -> dict:
    """{path: [(digest, length) a chunk]} of the snapshot under
    ``root_hash``, read back blob by blob."""
    out = {}
    todo = [(fetch_full_tree(resolve, root_hash), "")]
    while todo:
        node, at = todo.pop()
        for child_hash in node.children:
            child = fetch_full_tree(resolve, child_hash)
            path = f"{at}{child.name}"
            if child.kind == TreeKind.DIR:
                todo.append((child, path + "/"))
            else:
                out[path] = [(bytes(h), len(resolve(h).data))
                             for h in child.children]
    return out


def _index_compiles() -> int:
    """Compiles of the index's probe and insert programs so far
    (``bkw_jit_compile_seconds``, counted by the hook a ``TpuBackend``
    installs)."""
    fam = obs_metrics.registry().get("bkw_jit_compile_seconds")
    return sum(s["count"] for s in fam._snapshot_series()
               if s["labels"]["fun"] in ("dedup_insert", "dedup_probe"))


class DeviceRun:
    """Generation 0 and one night of ``TREE`` through ``TpuBackend`` and
    a tiered device index, one store, on a mesh of ``devices``."""

    def __init__(self, tmp, devices: int):
        mesh = Mesh(np.array(jax.devices()[:devices]), ("data",))
        self.index = BlobIndex(KEYS, tmp / "index")
        self.dedup = TieredDedupIndex(mesh, self.index,
                                      cold_dir=tmp / "cold")
        self.backend = TpuBackend(CDCParams())
        self.backend.attach_mesh(mesh, self.dedup.axis)
        self.pack_dir = tmp / "pack"
        self.src = tmp / "src"
        self.reference = check.Reference(REF)
        source_tree.build(self.src, TREE, np.random.default_rng([40, 0]))
        self.generations = [self.generation()]
        source_churn.step(self.src, NIGHT, np.random.default_rng([40, 1]),
                          {"generation": 1, "work": tmp, "seed": 40})
        self.generations.append(self.generation())

    def generation(self) -> dict:
        seen_before = set(self.reference.seen)
        ref = self.reference.observe(self.src)
        writer = PackfileWriter(
            KEYS, self.pack_dir,
            on_packfile=lambda pid, path, hashes, size:
                self.index.finalize_packfile(pid, hashes))
        packer = DirPacker(self.backend, writer, self.index,
                           dedup_index=self.dedup)
        hints, written = {}, set()
        add_blob, write = packer._add_blob, writer.add_blob

        def add_blob_seen(blob_hash, kind, data, dup_hint=None):
            if kind == BlobKind.FILE_CHUNK:
                hints.setdefault(bytes(blob_hash), dup_hint)
            add_blob(blob_hash, kind, data, dup_hint=dup_hint)

        def write_seen(blob):
            if blob.kind == BlobKind.FILE_CHUNK:
                written.add(bytes(blob.hash))
            write(blob)

        packer._add_blob, writer.add_blob = add_blob_seen, write_seen
        before = obs_profile.baseline()
        compiled = _index_compiles()
        root_hash = packer.pack(self.src)
        writer.shutdown()
        compiled = _index_compiles() - compiled
        reader = PackfileReader(KEYS, self.pack_dir)

        def resolve(h):
            return reader.get_blob(self.index.lookup(h), h)

        want = {}
        for path in check.tree_files(self.src):
            data = np.fromfile(path, dtype=np.uint8)
            want[str(path.relative_to(self.src))] = [
                (digest, length)
                for _off, length, digest in native.manifest(data, REF)]
        return {"ref": ref, "seen_before": seen_before, "hints": hints,
                "written": written, "stats": packer.stats, "want": want,
                "report": obs_profile.report(before),
                "index_compiles": compiled,
                "snapshot": _snapshot_files(resolve, root_hash),
                "root_hash": root_hash}


@pytest.fixture(scope="module", params=[8, 1], ids=["mesh8", "mesh1"])
def device_run(tmp_path_factory, request):
    return DeviceRun(tmp_path_factory.mktemp("pack_batches_device"),
                     request.param)


@pytest.mark.parametrize("generation", [0, 1], ids=["generation0", "night1"])
def test_the_snapshot_read_back_is_the_reference_s(device_run, generation):
    g = device_run.generations[generation]
    # every path of the generator's census, each with the reference's
    # chunks (digest and length)
    assert sorted(g["snapshot"]) == sorted(g["want"])
    assert g["snapshot"] == g["want"]
    assert len(g["want"]) == TREE["files"] + (1 if generation else 0)
    assert g["stats"].chunks == g["ref"]["chunks"]
    assert g["stats"].failed_files == 0 and g["stats"].dedup_divergences == 0
    # what was stored is the reference's unseen set, and no chunk the
    # device (or resolve_hints) called found was unseen before
    assert g["written"] == set(g["ref"]["fresh"])
    assert [d.hex() for d, hint in g["hints"].items()
            if hint and d not in g["seen_before"]] == []
    assert all(hint is not None for hint in g["hints"].values())
    batch = g["report"]["batch"]
    assert batch["batches"] == 1 and batch["dirs"] == TREE["directories"]
    assert batch["batched_files"] == len(g["want"])
    assert sum(batch["files"].values()) == len(g["want"])
    assert batch["files"]["bucketed"] >= 1 and batch["files"]["tiny"] > 100


def test_a_night_compiles_no_index_program(device_run):
    """The second backup hands the index other counts of hashes than the
    first (a night adds and deletes files; the host index leaves other
    chunks undecided), and each falls into a bucket the first compiled:
    on one device a count of its own was a program of its own."""
    first, night = device_run.generations
    assert first["index_compiles"] >= 1
    assert night["index_compiles"] == 0
    rows = night["report"]["index"]["query_rows"]
    assert rows["actual"] >= len(night["want"])
    assert rows["actual"] < rows["padded"] <= 2 * rows["actual"]


def test_the_device_backend_s_root_hash_is_the_host_s(device_run, tmp_path):
    got = _pack(tmp_path, device_run.src)
    assert got.root_hash == device_run.generations[1]["root_hash"]


def test_the_tiny_digest_reports_the_bytes_it_uploads(device_run):
    """``padded_bytes["digest"]`` holds the leaf classes' buffers, rows
    padded to a power of two, not the files' own bytes."""
    rep = device_run.generations[0]["report"]
    sizes = [n for n in source_tree.file_sizes(TREE) if n <= CDC["min_size"]]
    classes = {}
    for n in sizes:
        kib = max(1, -(-n // 1024))
        leaf = next(b for b in (16, 64, 256) if kib <= b)
        classes[leaf] = classes.get(leaf, 0) + 1
    uploaded = 0
    for leaf, count in classes.items():
        rows = 8
        while rows < count:
            rows *= 2
        uploaded += rows * leaf * 1024
    assert len(classes) == 3
    assert rep["padded_bytes"]["digest"] >= uploaded > 2 * sum(sizes)
    assert rep["bytes"]["digest"] >= sum(sizes)
    assert rep["dispatches"]["digest"] >= len(classes)
