"""Mesh manifest plane: shard-mapped scan->digest must be bit-identical.

Parity posture (ISSUE 12): a mesh that mis-lowers loses
speed, never correctness — so every test here pins bit-exact equality
against BOTH the driver on a mesh of one device and the CPU oracle, across
parameter sets and 1/2/8-device meshes (tests/conftest.py forces
``--xla_force_host_platform_device_count=8``).  The dispatch-contract
tests hand-count launches per the obs/profile.py table: one shard_map
program counts ONCE per stage unlabeled plus once per participating
device in ``bkw_mesh_device_dispatch_total``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from backuwup_tpu.crypto import KeyManager
from backuwup_tpu.obs import profile
from backuwup_tpu.ops import cdc_cpu
from backuwup_tpu.ops.blake3_cpu import Blake3Numpy
from backuwup_tpu.ops.cdc_tpu import _HALO
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.ops.pipeline import DevicePipeline
from backuwup_tpu.snapshot.blob_index import BlobIndex
from backuwup_tpu.snapshot.device_dedup import MeshDedupIndex

SMALL = CDCParams.from_desired(4096)
PARAM_SETS = [CDCParams.from_desired(d) for d in (4096, 8192, 16384)]


def _oracle(data, params):
    chunks = cdc_cpu.chunk_stream(data, params)
    digests = Blake3Numpy().digest_batch([data[o:o + l] for o, l in chunks])
    return chunks, digests


def _stage(rows, P):
    buf = np.zeros((len(rows), _HALO + P), dtype=np.uint8)
    nv = np.zeros(len(rows), dtype=np.int32)
    for r, d in enumerate(rows):
        buf[r, _HALO:_HALO + len(d)] = np.frombuffer(d, dtype=np.uint8)
        nv[r] = len(d)
    return buf, nv


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


# The single-device 8K/16K legs ride the slow tier: tier-1 keeps the
# full multi-device matrix plus the 4096 single-device leg, which
# already pins the mesh-vs-single parity path — the larger desired
# sizes change only the cut mask, covered by the 2/8-device legs.
# (The tier-1 wall budget is a hard 870 s; see ROADMAP.md.)
@pytest.mark.parametrize(
    "params,n_dev",
    [pytest.param(p, n, id=f"{p.desired_size}-{n}",
                  marks=([pytest.mark.slow]
                         if n == 1 and p.desired_size > 4096 else []))
     for p in PARAM_SETS for n in (1, 2, 8)])
def test_mesh_matches_single_device_and_oracle(params, n_dev):
    P = 65536
    rng = np.random.default_rng(13 * n_dev + params.desired_size)
    rows = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            for n in (65536, 30_000, 0, 65536)]
    buf, nv = _stage(rows, P)
    single = list(DevicePipeline(params, mesh=_mesh(1)).manifest_segments_mesh(
        iter([(jnp.asarray(buf), nv)])))[0]
    pipe = DevicePipeline(params, mesh=_mesh(n_dev))
    (mesh_out,) = list(pipe.manifest_segments_mesh(iter([(buf, nv)])))
    assert len(mesh_out) == len(rows)
    for r, data in enumerate(rows):
        s_chunks, s_digs = single[r]
        m_chunks, m_digs = mesh_out[r]
        assert m_chunks == s_chunks
        assert np.array_equal(m_digs, s_digs)
        ref_chunks, ref_digests = _oracle(data, params)
        assert m_chunks == ref_chunks
        assert [bytes(d) for d in m_digs] == ref_digests


def test_mesh_per_shard_overflow_reruns_only_that_shard():
    """All-zero 1 MiB row (chunks entirely at max size) overflows its
    shard's pool; the 7 random shards must NOT re-run.  Hand count:
    unlabeled scan = 1 (the shard_map launch) + 1 (the ONE fallback
    shard's host-tiled re-run); per-device labeled scan = exactly 1
    everywhere (fallback launches are not mesh launches)."""
    P = 1 << 20
    rng = np.random.default_rng(29)
    rows = [b"\0" * P] + [rng.integers(0, 256, P, dtype=np.uint8).tobytes()
                          for _ in range(7)]
    buf, nv = _stage(rows, P)
    pipe = DevicePipeline(SMALL, mesh=_mesh(8))
    base = profile.baseline()
    (out,) = list(pipe.manifest_segments_mesh(iter([(buf, nv)])))
    rep = profile.report(base)
    assert rep["dispatches"]["scan"] == 2, \
        "exactly one shard may re-run on the host-tiled path"
    dev = rep["device_dispatches"]
    assert sorted(dev, key=int) == [str(d) for d in range(8)]
    assert all(dev[d]["scan"] == 1 for d in dev)
    # bytes prove which shard fell back: unlabeled scan actual = the mesh
    # launch (8 MiB) + only shard 0's rows again (1 MiB)
    assert rep["bytes"]["scan"] == 8 * P + P
    for r, data in enumerate(rows):
        chunks, digs = out[r]
        ref_chunks, ref_digests = _oracle(data, SMALL)
        assert chunks == ref_chunks
        assert [bytes(d) for d in digs] == ref_digests


def test_mesh_even_split_across_devices():
    P = 65536
    rng = np.random.default_rng(31)
    rows = [rng.integers(0, 256, P, dtype=np.uint8).tobytes()
            for _ in range(16)]
    buf, nv = _stage(rows, P)
    pipe = DevicePipeline(SMALL, mesh=_mesh(8))
    base = profile.baseline()
    list(pipe.manifest_segments_mesh(iter([(buf, nv)])))
    rep = profile.report(base)
    dev = rep["device_dispatches"]
    counts = [dev[str(d)]["digest"] for d in range(8)]
    assert max(counts) - min(counts) <= 1
    # equal-length rows: byte shares split exactly evenly too
    for d in range(8):
        assert rep["device_pad_efficiency"][str(d)]["scan"] == \
            rep["device_pad_efficiency"]["0"]["scan"]
    assert pipe.mesh_hbm_high_water and \
        len(set(pipe.mesh_hbm_high_water.values())) == 1


def test_mesh_dedup_handoff_zero_host_roundtrips(tmp_path, monkeypatch):
    """The manifest->dedup handoff must classify whole batches without
    any per-batch host round trip of the fingerprints: with the
    host-side query builder booby-trapped, two overlapping passes must
    still produce correct dup hints, and the index-stage dispatch count
    must equal the number of device batches (the insert_device launches
    ride the dispatch contract, not hashes_to_queries)."""
    P = 65536
    rng = np.random.default_rng(37)
    rows_a = [rng.integers(0, 256, P, dtype=np.uint8).tobytes()
              for _ in range(8)]
    rows_b = rows_a[:4] + [rng.integers(0, 256, P, dtype=np.uint8).tobytes()
                           for _ in range(4)]
    keys = KeyManager.from_secret(b"\x07" * 32)
    host = BlobIndex(keys, tmp_path / "index")
    mesh = _mesh(8)
    dev = MeshDedupIndex(mesh, host)
    pipe = DevicePipeline(SMALL, mesh=mesh)

    def _boom(_hashes):
        raise AssertionError("fingerprints crossed the host link")

    monkeypatch.setattr("backuwup_tpu.snapshot.device_dedup."
                        "hashes_to_queries", _boom)

    def classify(rows):
        buf, nv = _stage(rows, P)
        base = profile.baseline()
        ((out, flags),) = list(pipe.manifest_segments_mesh(
            iter([(buf, nv)]), dedup=dev))
        rep = profile.report(base)
        assert rep["dispatches"]["index"] == 1  # one device batch
        assert all(rep["device_dispatches"][str(d)]["index"] == 1
                   for d in range(8))
        hashes, raw = [], []
        for (chunks, digs), fl in zip(out, flags):
            assert fl is not None and len(fl) == len(chunks)
            for k in range(len(chunks)):
                hashes.append(digs[k].tobytes())
                raw.append(bool(fl[k]))
        return hashes, dev.resolve_hints(hashes, raw)

    hashes_a, hints_a = classify(rows_a)
    seen = set()
    for h, hint in zip(hashes_a, hints_a):
        assert hint == (h in seen)
        seen.add(h)
    # pass 2 overlaps pass 1: the repeated rows' chunks are resident in
    # the device table and must classify duplicate; the fresh rows new
    hashes_b, hints_b = classify(rows_b)
    for h, hint in zip(hashes_b, hints_b):
        assert hint == (h in seen)
        seen.add(h)


def test_manifest_many_classified_backend(tmp_path):
    """TpuBackend's fused manifest+classify over mixed stream shapes
    (empty / tiny / batched): hints must match the first-occurrence-new
    rule on an empty index and be all-duplicate on a repeat call.  A
    plain ``manifest_many`` goes first, on a backend no mesh was attached
    to: the default mesh it ran on is not kept, so the index's own mesh
    (four of the eight devices, not the default) is still taken and the
    device decides."""
    from backuwup_tpu.ops.backend import TpuBackend

    rng = np.random.default_rng(41)
    streams = [b"", b"tiny-blob", rng.integers(
        0, 256, 50_000, dtype=np.uint8).tobytes(),
        rng.integers(0, 256, 20_000, dtype=np.uint8).tobytes()]
    keys = KeyManager.from_secret(b"\x07" * 32)
    host = BlobIndex(keys, tmp_path / "index")
    dev = MeshDedupIndex(_mesh(4), host)
    backend = TpuBackend(SMALL)
    plain = backend.manifest_many(streams)
    assert backend.pipeline.mesh is None
    base = profile.baseline()
    manifests, hints = backend.manifest_many_classified(streams, dev)
    assert backend.pipeline.mesh is dev.mesh
    decided = profile.report(base)["batch"]["chunks"]["device_decided"]
    assert decided == sum(len(m) for m in manifests[2:])
    refs = [r for m in manifests for r in m]
    assert len(hints) == len(refs)
    seen = set()
    for ref, hint in zip(refs, hints):
        assert hint == (ref.hash in seen)
        seen.add(ref.hash)
    # parity with the plain manifest path
    assert [[(r.offset, r.length, r.hash) for r in m] for m in manifests] \
        == [[(r.offset, r.length, r.hash) for r in m] for m in plain]
    manifests2, hints2 = backend.manifest_many_classified(streams, dev)
    # device-classified rows are resident from pass 1 -> duplicate; the
    # tiny stream rides the host-authority lane, and the host index has
    # no blobs -> False (hints may only err toward re-storing, never
    # toward skipping a needed store)
    it2 = iter(hints2)
    for m_idx, m in enumerate(manifests2):
        for _ in m:
            assert next(it2) == (m_idx != 1)


def test_pipeline_names_the_benchmark_reads():
    """``benchmark/deployment.py`` reads four names of the program for
    its ``kernels`` line (ROADMAP D16): they stay readable, and on the
    CPU configuration they say the XLA forms and the leaf pool."""
    from backuwup_tpu.ops import scan_fused

    pipe = DevicePipeline(SMALL)
    assert pipe.fused is False
    assert pipe.pallas_digest is False
    assert pipe.pool_digest is True
    assert scan_fused._V2_SELECTED is False  # true once a TPU checked it


def test_nv_cache_is_lru():
    pipe = DevicePipeline(SMALL)
    a = np.arange(4, dtype=np.int32)
    b = np.arange(4, dtype=np.int32) + 1000
    pipe._nv_device(a)
    pipe._nv_device(b)
    pipe._nv_device(a)  # hit: A becomes most-recently-used
    for i in range(62):
        pipe._nv_device(np.full(4, i + 1, dtype=np.int32))
    assert len(pipe._nv_cache) == 64
    pipe._nv_device(np.full(4, 9999, dtype=np.int32))
    assert len(pipe._nv_cache) == 64  # evicts ONE entry, not the world
    assert a.tobytes() in pipe._nv_cache  # hot entry survived
    assert b.tobytes() not in pipe._nv_cache  # coldest entry evicted
