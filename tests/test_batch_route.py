"""The batched route at the shipped chunker constants, against the
benchmark's own reference (ISSUE 34): ``DirPacker.pack`` ->
``TpuBackend(CDCParams()).manifest_many_classified`` ->
``DevicePipeline.manifest_batch`` with a tiered device index.

One small ``home_tree`` with every class of file the prepass knows
(empty, at or under the minimum, bucketed by padded length, longer than
the scan segment, which the test reduces), packed once, churned by
``tree_churn`` and packed again.  What the program chunked, stored and
was told by the device is held to ``benchmark/reference/`` (the C
pipeline and the numpy oracles; they import nothing of the program).
"""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from backuwup_tpu.crypto import KeyManager
from backuwup_tpu.dedupstore import TieredDedupIndex
from backuwup_tpu.obs import profile as obs_profile
from backuwup_tpu.ops.backend import TpuBackend
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.snapshot.blob_index import BlobIndex
from backuwup_tpu.snapshot.packer import DirPacker
from backuwup_tpu.snapshot.packfile import PackfileWriter
from backuwup_tpu.wire import BlobKind
from benchmark import check
from benchmark.generators import home_tree, tree_churn
from benchmark.reference import blake3_np, cdc_np
from benchmark.reference.gear import CDCParams as RefParams

MiB = 1 << 20
SEGMENT = 4 * MiB  # the scanner's segment here; 128 MiB in the program
CDC = {"min_size": 262144, "desired_size": 1048576, "max_size": 3145728,
       "mask_s_bits": 22, "mask_l_bits": 18}
REF = RefParams(**CDC)
TREE = {"big_bytes": 3 * MiB + 4096, "big_insert_bytes": 65536,
        "big_insert_at": 0.4, "mid_files": 2, "mid_bytes": 900 * 1024,
        "small_files": 12, "small_min_bytes": 1024,
        "small_max_bytes": 12 * 1024, "long_bytes": 5 * MiB}
CHURN = {"small_rewritten": 2, "small_added": 1, "small_deleted": 1,
         "small_added_step": 5,
         "small_list": {k: TREE[k] for k in (
             "small_files", "small_min_bytes", "small_max_bytes")},
         "f0_overwrites": 1, "f0_overwrite_bytes": 65536,
         "f0_insertions": 0, "f0_insert_bytes": 4096,
         "f0_gap_bytes": 0, "f0_recent_gap_bytes": 0, "f0_recent_nights": 0,
         "f0_target_chunk_bytes": MiB, "f0_pool": 4,
         "f0_new_bytes_tolerance": 1.0, "cdc": CDC}
CLASSES = {"empty": ["small/empty"], "tiny": ["small/s0000", "small/s0011"],
           "bucketed": ["big/f0", "big/f1", "mid/m00", "mid/m01"],
           "long": ["long/l0"]}


class Generation:
    """One ``pack()`` of the tree with everything the tests look at."""

    def __init__(self, root, backend, index, dedup, out):
        self.files = {str(p.relative_to(root)): p.read_bytes()
                      for p in check.tree_files(root)}
        self.manifests = {}  # file bytes -> the refs the backend gave
        self.hints = {}      # chunk digest -> the hint _add_blob was given
        self.written = set()
        inner = backend.manifest_many_classified

        def classified(streams, index_):
            out_, hints = self.tamper(*inner(streams, index_))
            for data, refs in zip(streams, out_):
                self.manifests[bytes(data)] = refs
            return out_, hints

        backend.manifest_many_classified = classified
        writer = PackfileWriter(
            KeyManager.from_secret(b"\x09" * 32), out,
            on_packfile=lambda pid, path, hashes, size:
            index.finalize_packfile(pid, hashes))
        packer = DirPacker(backend, writer, index, dedup_index=dedup)
        add_blob, write = packer._add_blob, writer.add_blob

        def add_blob_seen(blob_hash, kind, data, dup_hint=None):
            if kind == BlobKind.FILE_CHUNK:
                self.hints.setdefault(bytes(blob_hash), dup_hint)
            add_blob(blob_hash, kind, data, dup_hint=dup_hint)

        def write_seen(blob):
            if blob.kind == BlobKind.FILE_CHUNK:
                self.written.add(bytes(blob.hash))
            write(blob)

        packer._add_blob, writer.add_blob = add_blob_seen, write_seen
        base = obs_profile.baseline()
        try:
            packer.pack(root)
        finally:
            del backend.manifest_many_classified
        self.report = obs_profile.report(base)
        self.stats = packer.stats

    tamper = staticmethod(lambda out, hints: (out, hints))


class FlippedHint(Generation):
    """The first chunk the device called new is reported as found."""

    @staticmethod
    def tamper(out, hints):
        hints = list(hints)
        if False in hints:
            hints[hints.index(False)] = True
        return out, hints


class AlteredDigest(Generation):
    """One bit of the first chunk's digest is flipped."""

    @staticmethod
    def tamper(out, hints):
        for refs in out:
            if refs:
                h = bytearray(refs[0].hash)
                h[0] ^= 1
                refs[0] = dataclasses.replace(refs[0], hash=bytes(h))
                break
        return out, hints


def _setup(tmp, kind):
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    index = BlobIndex(KeyManager.from_secret(b"\x07" * 32), tmp / "index")
    dedup = TieredDedupIndex(mesh, index, cold_dir=tmp / "cold")
    backend = TpuBackend(CDCParams())
    backend.attach_mesh(mesh, dedup.axis)
    backend.pipeline.scanner.segment_size = SEGMENT
    root = tmp / "src"
    home_tree.build(root, TREE, np.random.default_rng([34, 0]))
    (root / "small" / "empty").write_bytes(b"")
    reference = check.Reference(REF)
    ref0 = reference.observe(root)
    g0 = Generation(root, backend, index, dedup, tmp / "p0")
    tree_churn.step(root, CHURN, np.random.default_rng([34, 1]),
                    {"generation": 1, "work": tmp, "seed": 34})
    seen_before = set(reference.seen)
    ref1 = reference.observe(root)
    g1 = kind(root, backend, index, dedup, tmp / "p1")
    return {"g0": g0, "g1": g1, "ref0": ref0, "ref1": ref1,
            "seen_before": seen_before}


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _setup(tmp_path_factory.mktemp("batch_route"), Generation)


def _oracle(data: bytes) -> list:
    spans = cdc_np.chunk_stream(data, REF)
    digests = blake3_np.blake3_many([data[o:o + n] for o, n in spans])
    return [(o, n, d) for (o, n), d in zip(spans, digests)]


@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_every_class_of_file_chunks_as_the_numpy_oracles_do(sound, cls):
    g0 = sound["g0"]
    for name in CLASSES[cls]:
        data = g0.files[name]
        got = [(r.offset, r.length, r.hash) for r in g0.manifests[data]]
        assert got == _oracle(data), name
    routes = g0.report["batch"]["files"]
    sizes = [len(d) for d in g0.files.values()]
    want = {"empty": 0,
            "tiny": sum(1 for n in sizes if 0 < n <= CDC["min_size"]),
            "long": sum(1 for n in sizes if n > SEGMENT)}
    want["bucketed"] = len(sizes) - 1 - want["tiny"] - want["long"]
    assert routes.get(cls, 0) == want[cls]


def _second_generation_faults(run: dict) -> list:
    """What the churned generation got wrong against the reference."""
    g1, ref1 = run["g1"], run["ref1"]
    faults = []
    fresh = set(ref1["fresh"])
    if g1.written != fresh:
        faults.append(("stored_is_not_the_unseen_set",
                       len(g1.written - fresh), len(fresh - g1.written)))
    for digest, hint in g1.hints.items():
        if hint and digest not in run["seen_before"]:
            faults.append(("found_but_never_seen", digest.hex()[:12]))
    if g1.stats.dedup_divergences:
        faults.append(("divergences", g1.stats.dedup_divergences))
    if g1.stats.chunks != ref1["chunks"]:
        faults.append(("chunks", g1.stats.chunks, ref1["chunks"]))
    return faults


@pytest.mark.parametrize("kind", [Generation, FlippedHint, AlteredDigest],
                         ids=lambda k: k.__name__)
def test_second_generation_stores_the_reference_s_unseen_chunks(
        sound, tmp_path, kind):
    """Sound, the churned generation stores exactly the chunks the
    reference had not seen, and every chunk the device flagged as found
    is one the reference had seen: a wrong ``dup_hint`` loses data.  With
    one hint flipped or one digest altered the same comparison fails."""
    run = sound if kind is Generation else _setup(tmp_path, kind)
    assert run["g0"].written == set(run["ref0"]["fresh"])
    assert run["ref1"]["new_chunks"] >= 3  # small, mid and f0 all changed
    faults = _second_generation_faults(run)
    if kind is Generation:
        assert faults == []
        # the device's verdict was asked for, and it found what was there
        assert any(run["g1"].hints.values())
    else:
        assert faults


@pytest.mark.parametrize("generation", ["g0", "g1"])
def test_batch_section_covers_the_route_and_counts_every_chunk(
        sound, generation):
    g = sound[generation]
    rep = g.report
    batch = rep["batch"]
    groups = {k: batch[k] for k in ("read", "host_stage", "device_wait",
                                    "emit")}
    assert all(v > 0 for v in groups.values()), groups
    wall = (rep["stage_seconds"]["packer.manifest_many"] + batch["read"]
            + batch["emit"])
    assert abs(sum(groups.values()) - wall) <= 0.05 * wall, (groups, wall)
    assert sum(batch["chunks"].values()) == g.stats.chunks
    assert batch["chunks"]["device_decided"] > 0
    assert batch["chunks"]["host_resolved"] > 0
    assert rep["padded_bytes"]["scan"] >= rep["bytes"]["scan"] > 0


# --- a first batch compiles its programs side by side ----------------------

FIRST_USE = {"tiny": "digest_padded", "scan": "_scan_segment",
             "pool": "pool_digest", "mesh": "shard_fn"}


@pytest.fixture(scope="module")
def first_batch(tmp_path_factory):
    """One batch with every class, most at shapes no other test of this
    file runs (200 KiB tiny files, a 6 MiB scan segment, 2 MiB buckets):
    what ``prepare_batches`` has compiled ahead by then, and what the
    batch itself still had to compile."""
    from backuwup_tpu.ops import pipeline as pl

    tmp = tmp_path_factory.mktemp("first_batch")
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    index = BlobIndex(KeyManager.from_secret(b"\x07" * 32), tmp / "index")
    dedup = TieredDedupIndex(mesh, index, cold_dir=tmp / "cold")
    backend = TpuBackend(CDCParams())
    backend.attach_mesh(mesh, dedup.axis)
    pipe = backend.pipeline
    pipe.scanner.segment_size = 6 * MiB
    rng = np.random.default_rng(340)
    streams = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
               for n in (200 * 1024, 199 * 1024, 198 * 1024, 7 * MiB,
                         MiB + MiB // 2, MiB + MiB // 3, MiB + 5)]
    # as the packer's walk hands them over: a directory a batch
    sizes = [len(s) for s in streams]
    backend.prepare_batches([sizes[:3], sizes[3:4], sizes[4:]], dedup)
    ahead = set(pl._RAN)
    base = obs_profile.baseline()
    out, flags = pipe.manifest_batch(streams, dedup)
    return {"ahead": ahead, "out": out, "streams": streams,
            "compiled": obs_profile.report(base)["compile_s"]}


@pytest.mark.parametrize("kind", sorted(FIRST_USE))
def test_first_batch_compiles_its_programs_side_by_side(first_batch, kind):
    """Every program the batch needs was compiled ahead under the key
    of its shape, and the batch itself compiled none of them again: the
    shapes were lowered through the same jitted callables with the same
    static arguments, dtypes and shardings as the data."""
    want = {"tiny": {("tiny", 8, 256)},
            "scan": {("scan", 8 * MiB), ("scan", MiB)},
            "pool": {("pool", 32 * MiB)},
            "mesh": {("mesh", (8, 31 + 2 * MiB), True)}}[kind]
    assert want <= first_batch["ahead"]
    assert FIRST_USE[kind] not in first_batch["compiled"]
    for data, (chunks, digests) in zip(first_batch["streams"],
                                       first_batch["out"]):
        assert [(o, n, d) for (o, n), d in zip(chunks, map(bytes, digests))] \
            == _oracle(data)
