"""Leaf-pool digest must be bit-identical to the BLAKE3 spec oracle.

Covers the batched route's digest stage (`ops/digest_pool.py`): one flat
leaf scan + tiered tree reduction, where a stage of padded tiles a length
class ran ~12 digest pipelines a batch.  The reference hashes chunks
serially on the CPU (`dir_packer.rs:285-311`); bit-exact parity with the
spec implementation is the correctness bar.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from backuwup_tpu.ops import cdc_cpu, digest_pool
from backuwup_tpu.ops.blake3_cpu import Blake3Numpy, blake3_hash
from backuwup_tpu.ops.cdc_tpu import _HALO
from backuwup_tpu.ops.digest_pool import (
    leaf_capacity,
    pool_digest,
    tier_caps,
    tier_spans,
)
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.ops.manifest_device import (
    scan_digest_batch_pool,
    scan_digest_batch_pool_mesh,
    tier_plan,
)
from backuwup_tpu.ops.pipeline import DevicePipeline

SMALL = CDCParams.from_desired(4096)


def _digests_of(acc: np.ndarray):
    return [np.ascontiguousarray(row.astype("<u4")).tobytes() for row in acc]


def _run_pool(flat, offs, lens, C, tiers=None, leaf_cap=None, **kw):
    offs_a = np.zeros(C, np.int32)
    lens_a = np.zeros(C, np.int32)
    offs_a[:len(offs)] = offs
    lens_a[:len(lens)] = lens
    if tiers is None:
        tiers = tuple((s, C) for s in tier_spans(128))
    if leaf_cap is None:
        leaf_cap = leaf_capacity(len(flat), C)
    flat_p = np.concatenate([flat, np.zeros(1024, np.uint8)])
    acc, ovf = pool_digest(jnp.asarray(flat_p), jnp.asarray(offs_a),
                           jnp.asarray(lens_a), leaf_cap=leaf_cap,
                           tiers=tiers, **kw)
    return np.asarray(acc), int(np.asarray(ovf)[0])


# the pool with the XLA leaf scan, and with the Pallas leaf kernel interpreted
FORMS = pytest.mark.parametrize("pallas_kw", [
    {"pallas": False},
    {"pallas": True, "interpret": True},
], ids=["xla", "pallas-interpret"])


@FORMS
def test_pool_digest_matches_oracle(pallas_kw):
    rng = np.random.default_rng(5)
    flat = rng.integers(0, 256, 512 * 1024, dtype=np.uint8)
    # every structural edge: sub-block, block boundary, leaf boundary,
    # multi-leaf, power-of-two and odd leaf counts, unused slots
    lens = [1, 2, 63, 64, 65, 1023, 1024, 1025, 2048, 2049, 5 * 1024,
            17 * 1024 + 7, 64 * 1024, 100_000]
    offs, cur = [], 0
    for l in lens:
        offs.append(cur)
        cur += l
    acc, ovf = _run_pool(flat, offs, lens, C=20, **pallas_kw)
    assert ovf == 0
    got = _digests_of(acc)
    for i, l in enumerate(lens):
        assert got[i] == blake3_hash(flat[offs[i]:offs[i] + l].tobytes()), \
            f"len {l}"


def test_pool_digest_overlapping_and_shuffled_spans():
    """Chunks may share bytes (dedup re-reads) and arrive in any order."""
    rng = np.random.default_rng(6)
    flat = rng.integers(0, 256, 256 * 1024, dtype=np.uint8)
    spans = [(0, 10_000), (5_000, 10_000), (5_000, 3_000), (200_000, 50_000),
             (1, 1), (0, 256 * 1024)]
    rng.shuffle(spans)
    offs = [o for o, _ in spans]
    lens = [l for _, l in spans]
    acc, ovf = _run_pool(flat, offs, lens, C=8,
                         tiers=tuple((s, 8) for s in tier_spans(256)))
    assert ovf == 0
    got = _digests_of(acc)
    for i, (o, l) in enumerate(spans):
        assert got[i] == blake3_hash(flat[o:o + l].tobytes())


# --- the gather (PR 42): rows q and q + 1 of the stream's 1 KiB rows, and
# the shift by s = offset % 1024, through the whole pool -----------------

def _assert_oracle(flat, spans, C, **kw):
    acc, ovf = _run_pool(flat, [o for o, _ in spans], [l for _, l in spans],
                         C=C, **kw)
    assert ovf == 0
    got = _digests_of(acc)
    for i, (o, l) in enumerate(spans):
        if l > 0:
            assert got[i] == blake3_hash(flat[o:o + l].tobytes()), (o, l)
        else:
            assert not acc[i].any()
    return acc


@FORMS
@pytest.mark.parametrize("s", [0, 1, 3, 4, 1021, 1023])
def test_pool_gather_misalignment(s, pallas_kw):
    """Chunks whose first byte lies ``s`` bytes into a 1 KiB row: one
    lane, a lane and a byte, many lanes, and a second chunk that starts
    where the first ends (another misalignment)."""
    rng = np.random.default_rng(100 + s)
    flat = rng.integers(0, 256, 96 * 1024, dtype=np.uint8)
    spans = [(s, 1024), (5 * 1024 + s, 1025), (8 * 1024 + s, 20_000),
             (8 * 1024 + s + 20_000, 4097), (40 * 1024 + s, 1)]
    _assert_oracle(flat, spans, C=8, **pallas_kw)


@FORMS
@pytest.mark.parametrize("n", [1, 1023, 1024, 1025, 3 << 20],
                         ids=["1", "1023", "1024", "1025", "3MiB"])
def test_pool_gather_chunk_lengths(n, pallas_kw):
    """One chunk of ``n`` bytes at a byte offset that is no multiple of
    anything, beside a short neighbour on each side."""
    rng = np.random.default_rng(n % 9973)
    flat = rng.integers(0, 256, n + 4099, dtype=np.uint8)
    spans = [(0, 2051), (2051, n), (2051 + n, 2048)]
    _assert_oracle(flat, spans, C=4,
                   tiers=((8, 4), (3072, 4)), **pallas_kw)


@FORMS
def test_pool_gather_chunk_ends_on_last_byte_before_slack(pallas_kw):
    """The stream's last byte belongs to a chunk, whatever row it falls
    in: behind it lies nothing but the ``CHUNK_LEN`` of slack, and the
    view of whole rows is cut inside that slack."""
    rng = np.random.default_rng(77)
    for n in (16 * 1024, 16 * 1024 + 31, 16 * 1024 + 1023):
        flat = rng.integers(0, 256, n, dtype=np.uint8)
        spans = [(0, 5000), (n - 3001, 3001), (n - 1, 1), (n - 1024, 1024)]
        _assert_oracle(flat, spans, C=4, **pallas_kw)


@FORMS
def test_pool_gather_unused_slot_between_used(pallas_kw):
    rng = np.random.default_rng(78)
    flat = rng.integers(0, 256, 64 * 1024, dtype=np.uint8)
    spans = [(7, 5000), (9999, 0), (5007, 7000), (123, -1), (12_007, 1023)]
    acc = _assert_oracle(flat, spans, C=8, **pallas_kw)
    assert not acc[len(spans):].any()


@pytest.mark.parametrize("short", [1, 2, 7])
def test_pool_gather_leaf_cap_short_still_flags(short):
    """A pool with ``short`` lanes too few says so; the chunks that got
    all their lanes still digest to the oracle's value."""
    rng = np.random.default_rng(79)
    flat = rng.integers(0, 256, 32 * 1024, dtype=np.uint8)
    spans = [(3, 8192), (8195, 8192)]  # 16 lanes
    acc, ovf = _run_pool(flat, [o for o, _ in spans], [l for _, l in spans],
                         C=4, tiers=((8, 4), (16, 4)), leaf_cap=16 - short)
    assert ovf == short
    assert _digests_of(acc)[0] == blake3_hash(flat[3:3 + 8192].tobytes())


def _stage_rows(rows, P):
    """``(B, _HALO + P)`` batch buffer and lengths of byte-string rows."""
    buf = np.zeros((len(rows), _HALO + P), dtype=np.uint8)
    nv = np.zeros(len(rows), dtype=np.int32)
    for r, d in enumerate(rows):
        buf[r, _HALO:_HALO + len(d)] = np.frombuffer(d, dtype=np.uint8)
        nv[r] = len(d)
    return buf, nv


def _assert_rows_match_oracle(rows, packed, acc, ovf, cut_cap):
    """A batch program's cuts and digests against the CPU chunker and
    ``blake3_cpu``, row by row."""
    assert not np.asarray(ovf).any()
    packed = np.asarray(packed)
    dig8 = np.ascontiguousarray(np.asarray(acc).astype("<u4")).view(
        np.uint8).reshape(len(rows), cut_cap, 32)
    for r, data in enumerate(rows):
        ref_chunks = cdc_cpu.chunk_stream(data, SMALL)
        ref_digests = Blake3Numpy().digest_batch(
            [data[o:o + l] for o, l in ref_chunks])
        assert packed[r, 0] == 0
        n_cuts = int(packed[r, 1])
        ends = packed[r, 2:2 + n_cuts].astype(np.int64)
        offs = np.concatenate([[0], ends[:-1] + 1])
        assert list(zip(offs.tolist(),
                        (ends - offs + 1).tolist())) == ref_chunks
        assert [bytes(d) for d in dig8[r, :n_cuts]] == ref_digests


def test_pool_gather_through_the_mesh_program():
    """``scan_digest_batch_pool_mesh`` on the 8-device CPU mesh, a row a
    shard: every row's bytes start 31 bytes into its own ``31 + P``, so
    each chunk of each shard meets the gather at another misalignment."""
    n_dev, P = 8, 32768
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
    rng = np.random.default_rng(80)
    rows = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            for n in (P, P - 1, 20_001, 1, 0, 1025, 9_999, 31_744)]
    buf, nv = _stage_rows(rows, P)
    s_cap, l_cap, cut_cap = DevicePipeline(SMALL)._caps(P)
    sharding = NamedSharding(mesh, PartitionSpec("data"))
    packed, acc, ovf = scan_digest_batch_pool_mesh(
        jax.device_put(buf, sharding), jax.device_put(nv, sharding),
        mesh=mesh, axis="data", min_size=SMALL.min_size,
        desired_size=SMALL.desired_size, max_size=SMALL.max_size,
        mask_s=SMALL.mask_s, mask_l=SMALL.mask_l,
        s_cap=s_cap, l_cap=l_cap, cut_cap=cut_cap, fused=False,
        leaf_cap=leaf_capacity(P, cut_cap), tiers=tier_plan(SMALL, P, 1))
    assert np.asarray(ovf).shape == (n_dev,)  # a flag a shard
    _assert_rows_match_oracle(rows, packed, acc, ovf, cut_cap)


def test_pool_digest_tier_cascade_and_overflow():
    rng = np.random.default_rng(8)
    flat = rng.integers(0, 256, 64 * 1024, dtype=np.uint8)
    lens = [4096] * 8  # 4 leaves each
    offs = [i * 4096 for i in range(8)]
    # tier 0 holds only 4 of the 8; the rest must cascade up and still
    # digest correctly in the wider tier
    tiers = ((4, 4), (8, 8))
    acc, ovf = _run_pool(flat, offs, lens, C=8, tiers=tiers)
    assert ovf == 0
    got = _digests_of(acc)
    for i in range(8):
        assert got[i] == blake3_hash(flat[offs[i]:offs[i] + 4096].tobytes())
    # terminus overflow: capacity 4+2 < 8 chunks -> flagged, not silent
    acc, ovf = _run_pool(flat, offs, lens, C=8, tiers=((4, 4), (8, 2)))
    assert ovf > 0


@pytest.mark.parametrize("spans, lens", [
    # the shipped constants' tiers: the trees join level by level and the
    # levels under 3072 / 64 run in the tail loop
    ((768, 1536, 3072), [3 << 20, (2 << 20) + 7, (1 << 20) + 1025,
                         700 * 1024, 300_001, 1024, 1, 1025]),
    # spans that are no halves of each other: the wider group is padded
    # up to the narrower one's span where they join
    ((56, 100), [100 * 1024, 99 * 1024 + 1, 57 * 1024, 56 * 1024,
                 55 * 1024 + 3, 2049, 64]),
    # one tier, a span that is no power of two, tail loop over 3 columns
    ((200,), [200 * 1024, 199 * 1024 + 5, 3 * 1024 + 1, 2 * 1024, 7]),
], ids=["shipped-768-1536-3072", "uneven-56-100", "single-200"])
def test_pool_digest_tiers_share_levels(spans, lens):
    """``tree_reduce_groups``: every tier's chunks digest to the oracle's
    value whatever the spans, with fewer slots in a tier than chunks of
    its class so that some cascade into the next."""
    from backuwup_tpu.ops.pipeline import _blake3_host
    rng = np.random.default_rng(len(spans))
    offs, cur = [], 0
    for ln in lens:
        offs.append(cur)
        cur += ln
    flat = rng.integers(0, 256, cur, dtype=np.uint8)
    C = 16
    tiers = tuple((s, 4 if i < len(spans) - 1 else C)
                  for i, s in enumerate(spans))
    acc, ovf = _run_pool(flat, offs, lens, C=C, tiers=tiers)
    assert ovf == 0
    got = _digests_of(acc)
    for i, ln in enumerate(lens):
        assert got[i] == _blake3_host(flat[offs[i]:offs[i] + ln].tobytes()), \
            (spans, ln)
    assert not acc[len(lens):].any()  # unused slots stay zero


def test_pool_digest_leaf_cap_shortfall_flagged():
    flat = np.zeros(32 * 1024, np.uint8)
    acc, ovf = _run_pool(flat, [0, 8192], [8192, 8192], C=4,
                         tiers=((8, 4), (16, 4)), leaf_cap=8)
    assert ovf > 0  # 16 leaves needed, 8 lanes available


def test_tier_plan_shapes():
    spans = tier_spans(3072)
    assert spans[-1] == 3072 and len(spans) <= 3
    assert all(a < b for a, b in zip(spans, spans[1:]))
    plan = tier_plan(SMALL, 4 << 20, 4)
    assert plan[-1][0] == SMALL.max_size // 1024
    assert all(c % 4 == 0 for _, c in plan)
    assert plan[-1][1] > 0
    assert leaf_capacity(1 << 20, 64) >= (1 << 20) // 1024 + 64


def test_pool_gate_runs():
    # on the test runtime (CPU mesh) the XLA pool path must pass its
    # check, which returns nothing and raises where it fails
    assert digest_pool._pool_digest_probe(False) is None


def test_pool_probe_that_raises_stops_the_pipeline(monkeypatch):
    """The pool is the batched route's only digest: a probe that fails
    stops ``DevicePipeline(...)`` with the probe's own error, on the CPU
    configuration too, and not behind a green run on another digest."""
    def probe(pallas):
        raise RuntimeError("leaf-pool digest disagrees with the oracle")

    monkeypatch.setattr(digest_pool, "_pool_digest_probe", probe)
    with pytest.raises(RuntimeError, match="disagrees with the oracle"):
        DevicePipeline(SMALL)


@pytest.mark.parametrize("sizes", [
    [65536, 30_000, 0, 1, 5000], [65536], [65536, 30_000, 0, 65536],
    [1, 64, 1024]], ids=["mixed-5", "full-1", "mixed-4", "short-3"])
def test_scan_digest_batch_pool_matches_oracle(sizes):
    P = 65536
    rng = np.random.default_rng(13)
    rows = [rng.integers(0, 256, n, dtype=np.uint8).tobytes() for n in sizes]
    buf, nv = _stage_rows(rows, P)
    s_cap, l_cap, cut_cap = DevicePipeline(SMALL)._caps(P)
    packed, acc, ovf = scan_digest_batch_pool(
        jnp.asarray(buf), jnp.asarray(nv), min_size=SMALL.min_size,
        desired_size=SMALL.desired_size, max_size=SMALL.max_size,
        mask_s=SMALL.mask_s, mask_l=SMALL.mask_l,
        s_cap=s_cap, l_cap=l_cap, cut_cap=cut_cap, fused=False,
        leaf_cap=leaf_capacity(len(rows) * P, len(rows) * cut_cap),
        tiers=tier_plan(SMALL, len(rows) * P, len(rows)))
    _assert_rows_match_oracle(rows, packed, acc, ovf, cut_cap)
