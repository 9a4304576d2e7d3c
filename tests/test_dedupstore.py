"""Tiered dedup index (dedupstore/): cold LSM units, crash seams,
promotion clock, and the BlobIndex bit-identity parity gates
(docs/dedup_tiering.md)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from backuwup_tpu.crypto import KeyManager
from backuwup_tpu.dedupstore import ColdFingerprintStore, TieredDedupIndex
from backuwup_tpu.dedupstore.cold import pack_keys, unpack_keys
from backuwup_tpu.obs import metrics as obs_metrics
from backuwup_tpu.ops.dedup_index import hashes_to_queries
from backuwup_tpu.snapshot.blob_index import BlobIndex
from backuwup_tpu.utils import faults

pytestmark = pytest.mark.tiered

TIER_SITES = {
    "tier.run.commit.pre", "tier.run.commit.post",
    "tier.compact.commit.pre", "tier.compact.commit.post",
}


@pytest.fixture(autouse=True)
def _isolate():
    obs_metrics.registry().reset()
    yield
    obs_metrics.registry().reset()
    faults.uninstall()


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


@pytest.fixture
def host_index(tmp_path):
    keys = KeyManager.from_secret(b"\x07" * 32)
    return BlobIndex(keys, tmp_path / "index")


def _queries(n, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.integers(1, 2 ** 32, (n, 4), dtype=np.uint32)
    return q


def _hashes(n, seed=0):
    rng = np.random.default_rng(seed)
    return [t.tobytes()
            for t in rng.integers(0, 256, (n, 32), dtype=np.uint8)]


def _metric(name, **labels):
    m = obs_metrics.registry().get(name)
    return 0 if m is None else m.value(**labels)


# --- key packing ------------------------------------------------------------


def test_pack_unpack_roundtrip_and_order():
    q = _queries(4096, seed=3)
    # include words with trailing-zero bytes (numpy S16 strips trailing
    # NULs; packing must stay injective and order-preserving anyway)
    q[:17, 3] = 0
    q[5, :] = [1, 0, 0, 0]
    packed = pack_keys(q)
    assert packed.dtype == np.dtype("S16")
    back = unpack_keys(packed)
    assert np.array_equal(back, q)
    # byte order == numeric (w0, w1, w2, w3) order
    srt = np.sort(packed)
    lex = np.lexsort((q[:, 3], q[:, 2], q[:, 1], q[:, 0]))
    assert np.array_equal(unpack_keys(srt), q[lex])


# --- cold store units -------------------------------------------------------


def test_cold_memtable_classify_and_padding(tmp_path):
    store = ColdFingerprintStore(tmp_path / "cold")
    q = _queries(64, seed=1)
    store.insert(q, np.arange(64, dtype=np.uint32))
    got = store.classify(q)
    assert np.array_equal(got, np.arange(64, dtype=np.uint32) + 1)
    # all-zero padding rows stay 0 through insert AND classify
    padded = np.vstack([np.zeros((2, 4), dtype=np.uint32), q[:3]])
    assert np.array_equal(store.classify(padded)[:2], [0, 0])
    store.insert(np.zeros((5, 4), dtype=np.uint32))
    assert store.classify(np.zeros((1, 4), dtype=np.uint32))[0] == 0
    # absent keys classify 0
    assert (store.classify(_queries(16, seed=2)) == 0).all()


def test_cold_flush_reopen_durable(tmp_path):
    store = ColdFingerprintStore(tmp_path / "cold")
    q = _queries(300, seed=4)
    store.insert(q)
    store.flush()
    assert store.run_count == 1
    again = ColdFingerprintStore(tmp_path / "cold")
    assert (again.classify(q) != 0).all()
    assert len(again) == 300


def test_cold_newest_value_wins(tmp_path):
    store = ColdFingerprintStore(tmp_path / "cold", compact_fanin=64)
    q = _queries(10, seed=5)
    store.insert(q, np.full(10, 7, dtype=np.uint32))
    store.flush()
    store.insert(q[:4], np.full(4, 9, dtype=np.uint32))
    # memtable layer overrides the run
    assert (store.classify(q[:4]) == 10).all()
    store.flush()
    # newer run overrides the older one after flush too
    assert (store.classify(q[:4]) == 10).all()
    assert (store.classify(q[4:]) == 8).all()


def test_cold_compaction_folds_same_size_runs(tmp_path):
    store = ColdFingerprintStore(tmp_path / "cold", compact_fanin=3)
    qs = [_queries(50, seed=10 + i) for i in range(6)]
    for q in qs:
        store.insert(q)
        store.flush()
    # 6 same-tier flushes with fanin 3 fold down (3 -> 1, twice, then
    # the two merged runs sit one tier up)
    assert store.run_count < 6
    for q in qs:
        assert (store.classify(q) != 0).all()
    again = ColdFingerprintStore(tmp_path / "cold")
    for q in qs:
        assert (again.classify(q) != 0).all()


def test_cold_reset_drops_everything(tmp_path):
    store = ColdFingerprintStore(tmp_path / "cold")
    q = _queries(40, seed=6)
    store.insert(q)
    store.flush()
    store.insert(_queries(8, seed=7))
    store.reset()
    assert store.run_count == 0 and len(store) == 0
    assert (store.classify(q) == 0).all()
    assert not list((tmp_path / "cold").glob("r*.run"))


def test_cold_recovery_drops_tmp_leftovers(tmp_path):
    store = ColdFingerprintStore(tmp_path / "cold")
    store.insert(_queries(20, seed=8))
    store.flush()
    junk = tmp_path / "cold" / "r999999999999.tmp"
    junk.write_bytes(b"partial run image")
    again = ColdFingerprintStore(tmp_path / "cold")
    assert not junk.exists()
    assert again.run_count == 1


# --- crash seams ------------------------------------------------------------


def test_tier_crash_sites_registered():
    assert TIER_SITES <= set(faults.crash_sites())


@pytest.mark.crash
@pytest.mark.parametrize("site", ["tier.run.commit.pre",
                                  "tier.run.commit.post"])
def test_crash_around_run_commit_recovers(tmp_path, site):
    store = ColdFingerprintStore(tmp_path / "cold")
    q = _queries(100, seed=20)
    store.insert(q)
    plane = faults.install(faults.FaultPlane(seed=1))
    plane.arm_crash(site)
    with pytest.raises(faults.CrashInjected):
        store.flush()
    faults.uninstall()
    again = ColdFingerprintStore(tmp_path / "cold")
    assert not list((tmp_path / "cold").glob("*.tmp"))
    if site.endswith(".pre"):
        # crash before the rename: the run never became visible; the
        # memtable was volatile by contract (the tiered front only drops
        # hot keys after a successful flush)
        assert again.run_count == 0
        assert (again.classify(q) == 0).all()
    else:
        # crash after the rename: the run is durable and answers
        assert again.run_count == 1
        assert (again.classify(q) != 0).all()


@pytest.mark.crash
@pytest.mark.parametrize("site", ["tier.compact.commit.pre",
                                  "tier.compact.commit.post"])
def test_crash_around_compaction_recovers(tmp_path, site):
    store = ColdFingerprintStore(tmp_path / "cold", compact_fanin=3)
    qs = [_queries(50, seed=30 + i) for i in range(2)]
    for q in qs:
        store.insert(q)
        store.flush()
    assert store.run_count == 2
    plane = faults.install(faults.FaultPlane(seed=1))
    plane.arm_crash(site)
    q3 = _queries(50, seed=32)
    store.insert(q3)
    with pytest.raises(faults.CrashInjected):
        store.flush()  # third same-tier run triggers the merge
    faults.uninstall()
    again = ColdFingerprintStore(tmp_path / "cold")
    assert not list((tmp_path / "cold").glob("*.tmp"))
    if site.endswith(".pre"):
        # merged run never committed: the three inputs survive
        assert again.run_count == 3
    else:
        # merged run committed before the crash, inputs not yet
        # unlinked: recovery rolls the make-before-break forward
        assert again.run_count == 1
    for q in qs + [q3]:
        assert (again.classify(q) != 0).all()


# --- tiered front -----------------------------------------------------------


def test_budget_is_hard_cap_with_demotion(mesh, host_index, tmp_path):
    budget = 8 * 64 * 20  # 512 hot slots across the mesh
    ti = TieredDedupIndex(mesh, host_index, cold_dir=tmp_path / "cold",
                          hbm_budget_bytes=budget, memtable_limit=256)
    hs = _hashes(5000, seed=40)  # ~10x the hot slot count
    for s in range(0, len(hs), 500):
        batch = hs[s:s + 500]
        flags = ti.classify_insert(batch)
        for h, f in zip(batch, flags):
            assert f == host_index.is_duplicate(h)
            host_index.mark_queued(h)
        assert ti.hbm_table_bytes <= budget
    assert _metric("bkw_tier_demotions_total") > 0
    assert _metric("bkw_tier_hbm_highwater_bytes") <= budget
    # every key — hot or demoted — still classifies duplicate
    rng = np.random.default_rng(41)
    sample = [hs[i] for i in rng.integers(0, len(hs), 1000)]
    assert all(ti.classify_insert(sample))
    # fresh keys still classify new (device-miss + cold-miss => new)
    assert not any(ti.classify_insert(_hashes(200, seed=42)))


@pytest.mark.parametrize("n", [1, 7, 8, 9, 1000, 1025])
@pytest.mark.parametrize("size", [1, 8])
def test_a_batch_of_any_length_classifies_as_the_host_index(
        size, n, host_index, tmp_path):
    """The hot table takes a batch in a power-of-two bucket of rows
    (``ops/dedup_index._pad_queries``): the verdicts are the host
    index's and the table holds the batch's keys and no padding row,
    whatever the length."""
    ti = TieredDedupIndex(Mesh(np.array(jax.devices()[:size]), ("data",)),
                          host_index, cold_dir=tmp_path / "cold")
    known = _hashes(300, seed=60)
    assert not any(ti.classify_insert(known))
    for h in known:
        host_index.mark_queued(h)
    fresh = _hashes(n, seed=61 + n)
    batch = [known[i] if i % 3 == 0 and i < len(known) else fresh[i]
             for i in range(n)]
    rows0 = (_metric("bkw_index_query_rows_total", what="actual"),
             _metric("bkw_index_query_rows_total", what="padded"))
    assert ti.classify_insert(batch) \
        == [host_index.is_duplicate(h) for h in batch]
    assert all(ti.classify_insert(batch))
    keys, _values = ti.sharded.dump()
    assert {k.astype("<u4").tobytes() for k in keys} \
        == {h[:16] for h in known + batch}
    bucket = 8
    while bucket < -(-n // size):
        bucket *= 2
    assert _metric("bkw_index_query_rows_total", what="actual") \
        - rows0[0] == 2 * n
    assert _metric("bkw_index_query_rows_total", what="padded") \
        - rows0[1] == 2 * size * bucket


def test_promotion_clock_repins_hot_cold_keys(mesh, host_index, tmp_path):
    budget = 8 * 64 * 20
    ti = TieredDedupIndex(mesh, host_index, cold_dir=tmp_path / "cold",
                          hbm_budget_bytes=budget, memtable_limit=256,
                          clock_windows=1, promote_min_hits=1)
    hs = _hashes(4000, seed=50)
    for s in range(0, len(hs), 500):
        ti.classify_insert(hs[s:s + 500])
        for h in hs[s:s + 500]:
            host_index.mark_queued(h)  # the packer's per-batch queue
    # find keys that were demoted out of HBM (cold answers, hot does not)
    demoted = [h for h in hs
               if ti.sharded.probe(hashes_to_queries([h]))[0] == 0
               and ti.cold.classify(hashes_to_queries([h]))[0] != 0][:32]
    assert demoted, "expected demoted keys at ~8x budget"
    # the dispatch path reports them as device misses (raw False); the
    # cold tier answers, the hits queue promotions, and the one-window
    # clock re-pins them into HBM
    before = _metric("bkw_tier_promotions_total")
    assert all(ti.resolve_hints(demoted, [False] * len(demoted)))
    assert _metric("bkw_tier_promotions_total") > before
    q = hashes_to_queries(demoted)
    assert (ti.sharded.probe(q) != 0).all()  # resident again
    assert ti.hbm_table_bytes <= budget
    # once promoted, the working set answers from the device path: the
    # real flags a dispatch would now produce are all-found
    d0, h0 = (_metric("bkw_tier_probes_total", path="device"),
              _metric("bkw_tier_hits_total", path="device"))
    flags = [bool(f) for f in ti.sharded.probe(q) != 0]
    assert all(ti.resolve_hints(demoted, flags))
    d1, h1 = (_metric("bkw_tier_probes_total", path="device"),
              _metric("bkw_tier_hits_total", path="device"))
    assert d1 - d0 >= len(demoted)
    assert (h1 - h0) / (d1 - d0) > 0.95


def test_skewed_working_set_stays_on_the_device_under_tail_churn(
        mesh, host_index, tmp_path):
    """A population over ten times the hot table, then the shape of an
    incremental backup: 97 % of every batch re-probes a working set
    sized to the demotion keep-set (a quarter of the table, scanned in
    rotation), 3 % is drawn uniformly over the whole population.  The uniform tail's
    churn must not push the working set out of HBM: more than 95 % of
    the device probes are answered on the device, every lane classifies
    duplicate, and the table never exceeds its budget."""
    population, batch, hot_frac = 24_000, 512, 0.97
    budget = (population // 12) * 20
    ti = TieredDedupIndex(mesh, host_index, cold_dir=tmp_path / "cold",
                          hbm_budget_bytes=budget, promote_min_hits=1)
    total_slots = mesh.shape["data"] * ti.capacity
    assert population >= 10 * total_slots
    hs = _hashes(population, seed=170)
    for s in range(0, population, 2048):
        seg = hs[s:s + 2048]
        for h, f in zip(seg, ti.classify_insert(seg)):
            assert f == host_index.is_duplicate(h)
            host_index.mark_queued(h)
    rng = np.random.default_rng(171)
    hot_n = max(total_slots // 4, batch)
    hot = [hs[i] for i in rng.integers(0, population, hot_n)]
    for s in range(0, hot_n, batch):  # warm: promote the working set
        ti.classify_insert(hot[s:s + batch])
    d0, h0 = (_metric("bkw_tier_probes_total", path="device"),
              _metric("bkw_tier_hits_total", path="device"))
    n_hot, cursor = int(batch * hot_frac), 0
    for _ in range(8):
        leg = [hot[(cursor + i) % hot_n] for i in range(n_hot)]
        cursor = (cursor + n_hot) % hot_n
        leg += [hs[int(i)] for i in
                rng.integers(0, population, batch - n_hot)]
        assert all(ti.classify_insert(leg))
    d1, h1 = (_metric("bkw_tier_probes_total", path="device"),
              _metric("bkw_tier_hits_total", path="device"))
    # lanes are deduplicated a batch, and the working set was drawn
    # with replacement: at least half of them are probes
    assert d1 - d0 >= 4 * n_hot
    assert (h1 - h0) / (d1 - d0) > 0.95, (h1 - h0, d1 - d0)
    assert not any(ti.classify_insert(_hashes(batch, seed=172)))
    assert _metric("bkw_tier_hbm_highwater_bytes") <= budget


def test_resolve_hints_cold_fallthrough(mesh, host_index, tmp_path):
    budget = 8 * 64 * 20
    ti = TieredDedupIndex(mesh, host_index, cold_dir=tmp_path / "cold",
                          hbm_budget_bytes=budget, memtable_limit=256)
    hs = _hashes(4000, seed=60)
    for s in range(0, len(hs), 500):
        for h, f in zip(hs[s:s + 500],
                        ti.classify_insert(hs[s:s + 500])):
            host_index.mark_queued(h)
    # raw all-False mimics the pipeline's device-miss flags for keys
    # that were demoted out of HBM: the cold tier must answer True
    demoted = [h for h in hs[:512]
               if ti.cold.classify(hashes_to_queries([h]))[0] != 0][:16]
    assert demoted, "expected some demoted keys at 10x budget"
    flags = ti.resolve_hints(demoted, [False] * len(demoted))
    assert all(flags)
    # a genuinely new hash with a concrete False flag stays new
    fresh = _hashes(4, seed=61)
    assert ti.resolve_hints(fresh, [False] * 4) == [False] * 4
    # None still routes to the host authority
    q = _hashes(2, seed=62)
    host_index.mark_queued(q[0])
    assert ti.resolve_hints(q, [None, None]) == [True, False]


def test_restart_seeds_from_cold_runs(mesh, host_index, tmp_path):
    budget = 8 * 64 * 20
    ti = TieredDedupIndex(mesh, host_index, cold_dir=tmp_path / "cold",
                          hbm_budget_bytes=budget, memtable_limit=256)
    hs = _hashes(3000, seed=70)
    for s in range(0, len(hs), 500):
        ti.classify_insert(hs[s:s + 500])
        for h in hs[s:s + 500]:
            host_index.mark_queued(h)
    ti.cold.flush()
    runs = ti.cold.run_count
    assert runs > 0
    # restart: persisted runs survive the reconcile and keep answering
    ti2 = TieredDedupIndex(mesh, host_index, cold_dir=tmp_path / "cold",
                           hbm_budget_bytes=budget, memtable_limit=256)
    assert ti2.cold.run_count >= runs
    assert all(ti2.classify_insert(hs))
    assert ti2.hbm_table_bytes <= budget


def test_reconcile_wipes_stale_cold_keys(mesh, tmp_path):
    keys = KeyManager.from_secret(b"\x07" * 32)
    host = BlobIndex(keys, tmp_path / "index")
    budget = 8 * 64 * 20
    hs = _hashes(3000, seed=80)
    for h in hs:
        host.mark_queued(h)
    ti = TieredDedupIndex(mesh, host, cold_dir=tmp_path / "cold",
                          hbm_budget_bytes=budget, memtable_limit=256)
    ti.cold.flush()
    assert len(ti.cold) > 0
    # the authority pruned half its blobs (GC / peer-loss repair): a
    # fresh front must not let stale cold runs classify them duplicate
    pruned, kept = hs[:1500], hs[1500:]
    host2 = BlobIndex(keys, tmp_path / "index2")
    for h in kept:
        host2.mark_queued(h)
    ti2 = TieredDedupIndex(mesh, host2, cold_dir=tmp_path / "cold",
                           hbm_budget_bytes=budget, memtable_limit=256)
    flags = ti2.classify_insert(pruned[:300])
    assert not any(flags)
    assert all(ti2.classify_insert(kept[:300]))


@pytest.mark.timeout(600)
def test_parity_oracle_1e6_under_budget(mesh, host_index, tmp_path):
    """The acceptance gate: bit-identical classification against the
    BlobIndex oracle at 1e6 fingerprints while the population is ~15x
    the hot slot budget and HBM bytes never exceed the cap."""
    n = 1_000_000
    budget = 8 * 8192 * 20  # 65536 hot slots: population ~15x
    ti = TieredDedupIndex(mesh, host_index, cold_dir=tmp_path / "cold",
                          hbm_budget_bytes=budget)
    rng = np.random.default_rng(90)
    hashes = [t.tobytes()
              for t in rng.integers(0, 256, (n, 32), dtype=np.uint8)]
    mismatches = 0
    for s in range(0, n, 8192):
        batch = hashes[s:s + 8192]
        flags = ti.classify_insert(batch)
        for h, f in zip(batch, flags):
            if f != host_index.is_duplicate(h):
                mismatches += 1
            host_index.mark_queued(h)
        assert ti.hbm_table_bytes <= budget
    assert mismatches == 0
    assert _metric("bkw_tier_hbm_highwater_bytes") <= budget
    assert _metric("bkw_tier_demotions_total") > 0
    # second pass over a sample: everything is a duplicate on both sides
    sample = [hashes[i] for i in rng.integers(0, n, 20000)]
    assert all(ti.classify_insert(sample))
    # fresh keys stay new
    fresh = [t.tobytes()
             for t in rng.integers(0, 256, (2000, 32), dtype=np.uint8)]
    assert not any(ti.classify_insert(fresh))


@pytest.mark.slow
@pytest.mark.timeout(7200)
def test_soak_1e8_cold_population(mesh, host_index, tmp_path):
    """1e8-fingerprint soak: the cold tier absorbs a population four
    orders past the hot budget; classification stays bit-identical on
    sampled slices and HBM never exceeds the cap."""
    n_cold = 100_000_000
    block = 1_000_000
    budget = 8 * 8192 * 20
    ti = TieredDedupIndex(mesh, host_index, cold_dir=tmp_path / "cold",
                          hbm_budget_bytes=budget,
                          memtable_limit=1 << 20)
    rng = np.random.default_rng(99)
    # bulk population goes straight into the cold store (vectorized
    # blocks; seeds are reproducible so sampling can regenerate them)
    for b in range(n_cold // block):
        q = np.random.default_rng(1000 + b).integers(
            1, 2 ** 32, (block, 4), dtype=np.uint32)
        ti.cold.insert(q)
    ti.cold.flush()
    assert ti.hbm_table_bytes <= budget
    # sampled membership via the tiered front's own cold path
    for b in rng.integers(0, n_cold // block, 5):
        q = np.random.default_rng(1000 + int(b)).integers(
            1, 2 ** 32, (block, 4), dtype=np.uint32)
        sel = rng.integers(0, block, 4096)
        assert (ti.cold.classify(q[sel]) != 0).all()
    # absent keys (word 0 == 0 never appears above)
    probe = rng.integers(1, 2 ** 32, (4096, 4), dtype=np.uint32)
    probe[:, 0] = 0
    probe[0] = 0  # padding row
    assert (ti.cold.classify(probe) == 0).all()
    # the live classify interface on top stays exact
    hs = _hashes(50000, seed=101)
    flags = ti.classify_insert(hs)
    assert not any(flags)
    for h in hs:
        host_index.mark_queued(h)
    assert all(ti.classify_insert(hs))
    assert _metric("bkw_tier_hbm_highwater_bytes") <= budget
