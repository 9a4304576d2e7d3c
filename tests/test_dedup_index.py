"""Sharded HBM dedup index vs the host BlobIndex semantics."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from backuwup_tpu.obs import metrics as obs_metrics
from backuwup_tpu.ops.blake3_cpu import blake3_hash
from backuwup_tpu.ops.dedup_index import (
    KEY_WORDS,
    DedupIndexFull,
    ShardedDedupIndex,
    hashes_to_queries,
    queries_from_cvs,
)


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    return jax.sharding.Mesh(np.array(devs), ("data",))


def _hashes(n, seed=0):
    return [blake3_hash(f"{seed}:{i}".encode()) for i in range(n)]


def test_probe_empty_table(mesh):
    idx = ShardedDedupIndex.create(mesh, capacity=1024)
    found = idx.probe(hashes_to_queries(_hashes(10)))
    assert (found == 0).all()


def test_insert_then_probe(mesh):
    idx = ShardedDedupIndex.create(mesh, capacity=1024)
    hs = _hashes(100)
    q = hashes_to_queries(hs)
    vals = np.arange(100, dtype=np.uint32)
    found = idx.insert(q, vals)
    assert (found == 0).all()  # all new
    got = idx.probe(q)
    assert (got == vals + 1).all()  # value+1 encoding
    # unseen hashes still miss
    assert (idx.probe(hashes_to_queries(_hashes(50, seed=9))) == 0).all()


def test_reinsert_keeps_original_value(mesh):
    idx = ShardedDedupIndex.create(mesh, capacity=1024)
    hs = _hashes(20)
    q = hashes_to_queries(hs)
    idx.insert(q, np.full(20, 5, dtype=np.uint32))
    found = idx.insert(q, np.full(20, 9, dtype=np.uint32))
    assert (found == 6).all()  # found with original value 5 (+1)
    assert (idx.probe(q) == 6).all()


def test_matches_host_index_classification(mesh):
    """Device probe and the host map agree on found/new for a mixed stream."""
    idx = ShardedDedupIndex.create(mesh, capacity=4096)
    host = {}
    rng = np.random.default_rng(3)
    for batch in range(5):
        n = 200
        hs = []
        for i in range(n):
            if host and rng.random() < 0.4:  # resample a known hash
                hs.append(list(host)[int(rng.integers(len(host)))])
            else:
                hs.append(blake3_hash(f"b{batch}i{i}".encode()))
        # host-side de-dup within batch (the packer does this)
        seen_in_batch = set()
        uniq = [h for h in hs if not (h in seen_in_batch or seen_in_batch.add(h))]
        q = hashes_to_queries(uniq)
        vals = np.arange(len(uniq), dtype=np.uint32)
        found = idx.insert(q, vals)
        for h, f in zip(uniq, found):
            assert (f > 0) == (h in host), h.hex()
            if h not in host:
                host[h] = True


def test_probe_exhaustion_raises_not_silently_drops(mesh):
    """Overfilling a shard must raise DedupIndexFull, never silently drop
    keys (which would misclassify later duplicates as new)."""
    idx = ShardedDedupIndex.create(mesh, capacity=8, max_probes=8)
    hs = _hashes(512, seed=11)  # 512 keys into 8*8=64 slots: must overflow
    q = hashes_to_queries(hs)
    with pytest.raises(DedupIndexFull):
        idx.insert(q, np.arange(512, dtype=np.uint32))


def test_capacity_pressure_linear_probing(mesh):
    # capacity 64 per shard * 8 shards = 512 slots; insert 256 keys so some
    # shards see heavy probing but stay under capacity
    idx = ShardedDedupIndex.create(mesh, capacity=64, max_probes=64)
    hs = _hashes(256, seed=4)
    q = hashes_to_queries(hs)
    found = idx.insert(q, np.arange(256, dtype=np.uint32))
    assert (found == 0).all()
    assert (idx.probe(q) > 0).all()


# --- query-construction edge rows ------------------------------------------


def test_hashes_to_queries_edge_rows():
    # empty input: a well-formed (0, 4) slab, not an exception
    empty = hashes_to_queries([])
    assert empty.shape == (0, KEY_WORDS) and empty.dtype == np.uint32
    # exact little-endian word split of the first 16 bytes; bytes 16..31
    # never reach the query (the 128-bit truncation)
    h = bytes(range(32))
    q = hashes_to_queries([h, h[:16] + b"\xff" * 16])
    expect = np.frombuffer(h[:16], dtype="<u4")
    assert np.array_equal(q[0], expect)
    assert np.array_equal(q[0], q[1])
    # memoryview/bytearray inputs coerce like bytes
    q2 = hashes_to_queries([bytearray(h), memoryview(h)])
    assert np.array_equal(q2[0], expect)


def test_zero_query_rows_are_padding_for_probe_and_insert(mesh):
    """All-zero rows are the kernels' padding convention: probe answers
    0 and insert must not burn a slot or report found for them."""
    idx = ShardedDedupIndex.create(mesh, capacity=64)
    hs = _hashes(6, seed=21)
    q = hashes_to_queries(hs)
    padded = np.vstack([q[:3],
                        np.zeros((2, KEY_WORDS), dtype=np.uint32),
                        q[3:]])
    found = idx.insert(padded, np.arange(8, dtype=np.uint32))
    assert (found == 0).all()
    # the real keys landed, the padding rows did not
    assert (idx.probe(q) > 0).all()
    assert (idx.probe(np.zeros((4, KEY_WORDS), dtype=np.uint32)) == 0).all()
    # a second padded probe still reports 0 on the zero rows
    again = idx.probe(padded)
    assert (again[3:5] == 0).all() and (again[:3] > 0).all()


def test_intra_batch_duplicate_fingerprints_single_resident(mesh):
    """Occurrences of one fingerprint inside one insert batch all report
    the pre-batch state ("new"), and exactly one occurrence's value ends
    up resident (which one is a write race — the kernel's contract asks
    for distinct keys per batch, and MeshDedupIndex.classify_insert's
    host-side first-occurrence walk builds on exactly these semantics)."""
    idx = ShardedDedupIndex.create(mesh, capacity=64)
    h = _hashes(1, seed=22)[0]
    q = hashes_to_queries([h, h, h])
    found = idx.insert(q, np.array([4, 9, 13], dtype=np.uint32))
    assert (found == 0).all()  # all report the pre-batch state
    got = idx.probe(hashes_to_queries([h]))
    assert int(got[0]) in (5, 10, 14)  # one occurrence's value (+1)
    # and a later batch sees it as a plain duplicate with that value
    again = idx.insert(q[:1], np.array([77], dtype=np.uint32))
    assert int(again[0]) == int(got[0])


def test_queries_from_cvs_matches_host_path():
    """Slicing the accumulator on device == downloading digests and
    calling hashes_to_queries; all-zero accumulator rows stay padding."""
    rng = np.random.default_rng(23)
    acc = rng.integers(0, 2 ** 32, (16, 8), dtype=np.uint32)
    acc[4] = 0  # unplaced row (digest_pool scatters into zeros)
    acc[11] = 0
    q_dev = np.asarray(queries_from_cvs(jnp.asarray(acc)))
    digests = [np.ascontiguousarray(row.astype("<u4")).tobytes()
               for row in acc]
    q_host = hashes_to_queries(digests)
    assert np.array_equal(q_dev, q_host)
    assert (q_dev[4] == 0).all() and (q_dev[11] == 0).all()


# --- batch lengths come in buckets (ISSUE 41) --------------------------------
# ``_pad_queries`` pads each device's share of a batch to a power of two
# of at least 8 rows.  The answers and the table are those of a plain
# host set of the same keys whatever the length, and a length inside a
# bucket already met compiles nothing.

LENGTHS = [1, 7, 8, 9, 1000, 1025]
MESH_SIZES = [1, 8]


def _mesh_of(size):
    return jax.sharding.Mesh(np.array(jax.devices()[:size]), ("data",))


def _rows(what):
    return obs_metrics.registry().get(
        "bkw_index_query_rows_total").value(what=what)


def _bucket(n, d):
    b = 8
    while b < -(-n // d):
        b *= 2
    return d * b


def _mixed_batch(n, known):
    """``n`` distinct hashes, about a third of them from ``known``."""
    fresh = iter(_hashes(n, seed=f"fresh{n}"))
    old = iter(known)
    return [next(old) if i % 3 == 0 and i // 3 < len(known) else next(fresh)
            for i in range(n)]


def _dump_set(idx):
    keys, values = idx.dump()
    return {k.astype("<u4").tobytes(): int(v) for k, v in zip(keys, values)}


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("size", MESH_SIZES)
def test_a_batch_of_any_length_answers_as_a_host_set(size, n):
    idx = ShardedDedupIndex.create(_mesh_of(size), capacity=4096)
    known = _hashes(300, seed="known")
    idx.insert(hashes_to_queries(known), np.full(300, 7, dtype=np.uint32))
    host = {h[:16]: 7 for h in known}
    batch = _mixed_batch(n, known)
    q = hashes_to_queries(batch)
    want = np.array([host.get(h[:16], -1) + 1 for h in batch],
                    dtype=np.uint32)
    sent, padded = _rows("actual"), _rows("padded")
    assert np.array_equal(idx.probe(q), want)
    # the same found-vector from the insert, and nothing lost; a second
    # attempt of the same rows finds every one and stores nothing
    found, lost = idx._insert_once(q, np.full(n, 9, dtype=np.uint32))
    assert found.shape == lost.shape == (n,)
    assert np.array_equal(found, want) and not lost.any()
    assert np.array_equal(idx.insert(q, np.full(n, 5, dtype=np.uint32)),
                          np.where(want > 0, want, 10))
    for h in batch:
        host.setdefault(h[:16], 9)
    # a padding row never occupies a slot: exactly the live keys
    assert _dump_set(idx) == host
    assert np.array_equal(idx.probe(q),
                          np.array([host[h[:16]] + 1 for h in batch]))
    # counter (b): four batches of n rows, each padded to its bucket
    assert _rows("actual") - sent == 4 * n
    assert _rows("padded") - padded == 4 * _bucket(n, size)


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("size", MESH_SIZES)
def test_classify_insert_of_any_length_answers_as_the_host_index(
        size, n, tmp_path):
    from backuwup_tpu.crypto import KeyManager
    from backuwup_tpu.snapshot.blob_index import BlobIndex
    from backuwup_tpu.snapshot.device_dedup import MeshDedupIndex
    host = BlobIndex(KeyManager.from_secret(b"\x07" * 32), tmp_path / "i")
    known = _hashes(300, seed="known")
    for h in known:
        host.mark_queued(h)
    dev = MeshDedupIndex(_mesh_of(size), host, capacity=4096)
    batch = _mixed_batch(n, known)
    # one repeat inside the batch where it has room: "duplicate"
    asked = batch + batch[:1] if n > 1 else batch
    flags = dev.classify_insert(asked)
    assert flags == [host.is_duplicate(h) for h in batch] \
        + ([True] if n > 1 else [])
    assert all(dev.classify_insert(batch))
    assert set(_dump_set(dev.sharded)) \
        == {h[:16] for h in known} | {h[:16] for h in batch}


def _compiles():
    """Backend compiles of the two programs so far, by the hook a
    ``TpuBackend`` installs (``bkw_jit_compile_seconds``'s count)."""
    fam = obs_metrics.registry().get("bkw_jit_compile_seconds")
    return [sum(s["count"] for s in fam._snapshot_series()
                if s["labels"]["fun"] == fun)
            for fun in ("dedup_insert", "dedup_probe")]


@pytest.mark.parametrize("size", MESH_SIZES)
def test_a_length_inside_a_bucket_already_met_compiles_nothing(size):
    from backuwup_tpu.ops.backend import _install_jax_hooks
    _install_jax_hooks()
    # a capacity no other test uses: programs of this test's own
    idx = ShardedDedupIndex.create(_mesh_of(size),
                                   capacity=65536 + 64 * size)
    start = _compiles()

    def ask(n):
        q = hashes_to_queries(_hashes(n, seed=f"len{n}"))
        idx.insert(q, np.ones(n, dtype=np.uint32))
        assert (idx.probe(q) == 2).all()
        return [now - was for now, was in zip(_compiles(), start)]

    assert ask(1000) == [1, 1]
    assert ask(1025) == [2, 2]
    lo, mid, hi = (_bucket(1000, size) // 2 + 1, _bucket(1000, size),
                   _bucket(1025, size))
    for n in (lo, lo + 199, mid - 1, mid, mid + 2, mid + 475, hi - 1, hi):
        assert ask(n) == [2, 2], n
    assert ask(hi + 1) == [3, 3]
