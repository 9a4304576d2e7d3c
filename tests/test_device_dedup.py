"""MeshDedupIndex: device-batched dedup decisions with BlobIndex parity."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from backuwup_tpu.crypto import KeyManager
from backuwup_tpu.ops.blake3_cpu import blake3_hash
from backuwup_tpu.snapshot.blob_index import BlobIndex
from backuwup_tpu.snapshot.device_dedup import MeshDedupIndex


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


@pytest.fixture
def host_index(tmp_path):
    keys = KeyManager.from_secret(b"\x07" * 32)
    return BlobIndex(keys, tmp_path / "index")


def _hashes(n, seed=0):
    return [blake3_hash(f"{seed}:{i}".encode()) for i in range(n)]


def test_classify_matches_host(mesh, host_index):
    dev = MeshDedupIndex(mesh, host_index, capacity=256)
    hs = _hashes(100)
    flags = dev.classify_insert(hs)
    for h, f in zip(hs, flags):
        assert f == host_index.is_duplicate(h)  # all new
        host_index.mark_queued(h)
    # second round: everything is now a duplicate on both sides
    flags2 = dev.classify_insert(hs)
    assert all(flags2)
    assert all(host_index.is_duplicate(h) for h in hs)


def test_intra_batch_repeats(mesh, host_index):
    dev = MeshDedupIndex(mesh, host_index, capacity=256)
    hs = _hashes(5, seed=1)
    batch = [hs[0], hs[1], hs[0], hs[2], hs[1], hs[0]]
    flags = dev.classify_insert(batch)
    assert flags == [False, False, True, False, True, True]


def test_seeded_from_host(mesh, host_index):
    pre = _hashes(20, seed=2)
    for h in pre[:10]:
        host_index.mark_queued(h)
    host_index.finalize_packfile(b"\x01" * 12, pre[10:15])
    dev = MeshDedupIndex(mesh, host_index, capacity=256)
    flags = dev.classify_insert(pre)
    assert flags == [True] * 15 + [False] * 5


def test_streamed_chunks_synced_before_next_classify(mesh, tmp_path):
    """A chunk first seen via the streaming path (host-classified only)
    must reach the device table before the next batch classify, or its
    re-occurrence reads device-new/host-dup and trips the divergence
    guard."""
    import random

    from backuwup_tpu.ops.backend import CpuBackend
    from backuwup_tpu.ops.gear import CDCParams
    from backuwup_tpu.snapshot.packer import DirPacker
    from backuwup_tpu.snapshot.packfile import PackfileWriter

    keys = KeyManager.from_secret(b"\x08" * 32)
    params = CDCParams.from_desired(4096)
    rng = random.Random(21)
    big = rng.randbytes(200_000)
    src = tmp_path / "src"
    src.mkdir()
    # a_big streams (size > batch_bytes); b_pre shares its leading chunks
    (src / "a_big.bin").write_bytes(big)
    (src / "b_pre.bin").write_bytes(big[:50_000])

    index = BlobIndex(keys, tmp_path / "index")
    dev = MeshDedupIndex(mesh, index, capacity=1024)
    writer = PackfileWriter(keys, tmp_path / "pack",
                            on_packfile=lambda pid, path, hashes, size:
                            index.finalize_packfile(pid, hashes))
    packer = DirPacker(CpuBackend(params), writer, index,
                       batch_bytes=100_000,
                       dedup_batch=dev.classify_insert)
    packer.pack(src)
    # wrong sync order shows up as device/host divergences (host wins,
    # logged + counted)
    assert packer.stats.dedup_divergences == 0
    assert packer.stats.chunks_deduped > 0


def test_grows_under_pressure(mesh, host_index):
    dev = MeshDedupIndex(mesh, host_index, capacity=8)
    hs = _hashes(600, seed=3)
    # host must know the hashes a grow() reseeds from
    flags = []
    for s in range(0, len(hs), 64):
        batch = hs[s:s + 64]
        flags.extend(dev.classify_insert(batch))
        for h in batch:
            host_index.mark_queued(h)
    assert not any(flags)  # all distinct -> all new
    assert dev.capacity > 8  # grew at least once
    assert all(dev.classify_insert(hs))  # now all resident


def test_engine_auto_attaches_mesh_on_accelerator(tmp_path):
    """A plain Engine on the device backend classifies on the HBM index
    over every local device without a caller-supplied mesh (VERDICT r2
    item 5); a caller that passes ``dedup_mesh=`` gets that mesh, on the
    index and on the backend's manifest pipeline alike."""
    from backuwup_tpu.app import ClientApp
    from backuwup_tpu.dedupstore import TieredDedupIndex
    from backuwup_tpu.ops.backend import TpuBackend
    from backuwup_tpu.ops.gear import CDCParams

    app = ClientApp(config_dir=tmp_path / "cfg", data_dir=tmp_path / "data",
                    server_addr="127.0.0.1:1",
                    backend=TpuBackend(CDCParams.from_desired(4096)))
    dedup = app.engine.device_dedup
    assert isinstance(dedup, TieredDedupIndex)
    assert dedup.mesh.devices.size == jax.device_count()

    two = Mesh(np.array(jax.devices()[:2]), ("data",))
    app2 = ClientApp(config_dir=tmp_path / "cfg2",
                     data_dir=tmp_path / "data2",
                     server_addr="127.0.0.1:1",
                     backend=TpuBackend(CDCParams.from_desired(4096)),
                     dedup_mesh=two)
    assert app2.engine.device_dedup.mesh is two
    assert app2.engine.backend.pipeline.mesh is two
