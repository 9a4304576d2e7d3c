"""Erasure-coded shard placement (erasure/ + engine/store/wire/server).

Unit level: the GF(2^8) oracle's field algebra and any-k-of-n guarantee,
shard-container parsing and the per-shard digest that turns corruption
into *detection* (a poisoned shard is dropped, any k clean survivors
still reconstruct), byte-identical shard rebuilds, the batched device
kernel's bit-for-bit parity with the oracle, the placement schema's
shard_index column, the 13-byte shard ids on the wire, and the server's
min_peers spread (capped shares with a deep queue, greedy matching — the
exact pre-erasure behavior — with a shallow one).

System level: the striped chaos acceptance scenario — a client backs up
through the coordination server onto six storage peers as RS(4+2)
stripes; the local source tree is then DELETED; one holder dies and is
audit-demoted, and a single ``repair_round()`` rebuilds its shards from
the survivors (no source, no whole copy anywhere) onto a spare peer;
then a SECOND holder goes permanently dark and the restore still
reproduces the source byte-for-byte from the remaining any-4-of-6.
"""

import asyncio
import hashlib
import itertools
import random
import shutil
import time

import numpy as np
import pytest

from backuwup_tpu import defaults, wire
from backuwup_tpu.erasure import gf_cpu
from backuwup_tpu.erasure import stripe as rs_stripe
from backuwup_tpu.ops.backend import CpuBackend
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.store import Store
from backuwup_tpu.utils import faults
from backuwup_tpu.utils.faults import FaultPlane

BACKEND = CpuBackend(CDCParams.from_desired(4096))


@pytest.fixture
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


@pytest.fixture
def plane():
    installed = faults.install(FaultPlane(seed=1234))
    yield installed
    faults.uninstall()


@pytest.fixture
def store(tmp_path):
    s = Store(tmp_path / "cfg", data_base=tmp_path / "data")
    s.set_obfuscation_key(b"\xaa\x01\x7f\x33")
    yield s
    s.close()


# --------------------------------------------------------------------------
# GF(2^8) oracle: field algebra
# --------------------------------------------------------------------------


def _slow_gf_mul(a: int, b: int) -> int:
    """Russian-peasant multiply mod 0x11d — independent of the tables."""
    out = 0
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & 0x100:
            a ^= 0x11D
        b >>= 1
    return out


def test_mul_table_matches_peasant_multiply(rng):
    for _ in range(500):
        a, b = rng.randrange(256), rng.randrange(256)
        assert gf_cpu.gf_mul(a, b) == _slow_gf_mul(a, b)


def test_gf_inverse_property():
    with pytest.raises(ZeroDivisionError):
        gf_cpu.gf_inv(0)
    for a in range(1, 256):
        assert gf_cpu.gf_mul(a, gf_cpu.gf_inv(a)) == 1


def test_generator_every_k_submatrix_invertible():
    # the any-k-of-n property IS this invertibility; check it exhaustively
    # for the production geometry
    k, m = defaults.RS_K, defaults.RS_M
    gen = gf_cpu.generator_matrix(k, m)
    assert np.array_equal(gen[:k], np.eye(k, dtype=np.uint8))  # systematic
    for rows in itertools.combinations(range(k + m), k):
        inv = gf_cpu.gf_invert_matrix(gen[list(rows)])
        prod = gf_cpu.gf_matmul(inv, gen[list(rows)])
        assert np.array_equal(prod, np.eye(k, dtype=np.uint8))


def test_generator_rejects_bad_geometry():
    with pytest.raises(ValueError):
        gf_cpu.generator_matrix(0, 2)
    with pytest.raises(ValueError):
        gf_cpu.generator_matrix(200, 100)


def test_reconstruct_rebuilds_exact_rows(nprng):
    k, m = 4, 2
    data = nprng.integers(0, 256, (k, 64), dtype=np.uint8)
    gen = gf_cpu.generator_matrix(k, m)
    shards = {i: gf_cpu.gf_matmul(gen[i:i + 1], data)[0]
              for i in range(k + m)}
    survivors = {i: shards[i] for i in (1, 3, 4, 5)}
    rebuilt = gf_cpu.reconstruct(survivors, k, m, missing=[0, 2])
    assert np.array_equal(rebuilt[0], shards[0])
    assert np.array_equal(rebuilt[2], shards[2])


# --------------------------------------------------------------------------
# stripe containers: any-k-of-n round trip + corruption detection
# --------------------------------------------------------------------------


@pytest.mark.parametrize("k,m", [(2, 1), (3, 2), (4, 2), (5, 3)])
def test_any_k_of_n_round_trip_every_subset(k, m, rng):
    data = rng.randbytes(k * 97 + 13)  # deliberately not a multiple of k
    containers = rs_stripe.split_packfile(data, k, m, BACKEND)
    assert len(containers) == k + m
    for subset in itertools.combinations(range(k + m), k):
        got = rs_stripe.assemble_packfile(
            [containers[i] for i in subset], BACKEND)
        assert got == data


@pytest.mark.parametrize("size", [0, 1, 4, 4 * 97])
def test_round_trip_edge_sizes(size, rng):
    data = rng.randbytes(size)
    containers = rs_stripe.split_packfile(data, 4, 2, BACKEND)
    assert rs_stripe.assemble_packfile(containers[2:], BACKEND) == data


def test_split_is_deterministic(rng):
    data = rng.randbytes(1000)
    assert rs_stripe.split_packfile(data, 4, 2, BACKEND) == \
        rs_stripe.split_packfile(data, 4, 2, BACKEND)


def test_corrupted_shard_detected_and_survived(rng):
    data = rng.randbytes(5000)
    containers = rs_stripe.split_packfile(data, 4, 2, BACKEND)
    bad = bytearray(containers[1])
    bad[rs_stripe.HEADER_LEN + 5] ^= 0xFF  # flip one payload byte
    bad = bytes(bad)
    shards, geom, drops = rs_stripe.collect_shards(
        [bad] + [containers[i] for i in (0, 2, 3, 4)], BACKEND)
    assert geom == (4, 2, len(data))
    assert 1 not in shards  # the poisoned shard never reaches the solve
    assert any("digest mismatch" in d for d in drops)
    # 4 clean survivors alongside the corrupt one: still reconstructs
    got = rs_stripe.assemble_packfile(
        [bad, containers[0], containers[2], containers[3], containers[4]],
        BACKEND)
    assert got == data
    # fewer than k clean shards: a hard error, not silent garbage
    with pytest.raises(rs_stripe.StripeError, match="need 4"):
        rs_stripe.assemble_packfile(
            [bad, containers[0], containers[2], containers[3]], BACKEND)


def test_parse_shard_rejects_malformed_containers(rng):
    data = rng.randbytes(256)
    good = rs_stripe.split_packfile(data, 2, 1, BACKEND)[0]
    with pytest.raises(rs_stripe.StripeError, match="not a shard"):
        rs_stripe.parse_shard(b"NOPE" + good[4:])
    with pytest.raises(rs_stripe.StripeError, match="version"):
        rs_stripe.parse_shard(good[:4] + bytes([99]) + good[5:])
    with pytest.raises(rs_stripe.StripeError, match="geometry"):
        rs_stripe.parse_shard(good[:6] + b"\x00" + good[7:])  # k = 0
    with pytest.raises(rs_stripe.StripeError, match="length mismatch"):
        rs_stripe.parse_shard(good + b"extra")


def test_shard_id_round_trip():
    pid = bytes(range(12))
    sid = rs_stripe.shard_id(pid, 5)
    assert len(sid) == wire.SHARD_ID_LEN
    assert rs_stripe.parse_shard_id(sid) == (pid, 5)
    with pytest.raises(rs_stripe.StripeError, match="length"):
        rs_stripe.parse_shard_id(pid)


def test_rebuild_shards_byte_identical(rng):
    # sourceless repair leans on this: a rebuilt container equals the
    # original bit-for-bit, so challenge tables stay valid and re-sends
    # to peers that already hold it are acked as idempotent duplicates
    data = rng.randbytes(3333)
    containers = rs_stripe.split_packfile(data, 4, 2, BACKEND)
    rebuilt = rs_stripe.rebuild_shards(
        [containers[i] for i in (1, 2, 4, 5)], [0, 3], BACKEND)
    assert rebuilt[0] == containers[0]
    assert rebuilt[3] == containers[3]
    with pytest.raises(rs_stripe.StripeError):
        rs_stripe.rebuild_shards(containers[:3], [4], BACKEND)  # < k left


def test_assemble_tree_reconstructs_and_reports(tmp_path, rng):
    from backuwup_tpu.snapshot.packfile import packfile_path

    data = rng.randbytes(4000)
    pid_ok, pid_bad = b"\x01" * 12, b"\x02" * 12
    containers = rs_stripe.split_packfile(data, 4, 2, BACKEND)
    shard_root = tmp_path / "shard"
    ok_dir = shard_root / pid_ok.hex()
    ok_dir.mkdir(parents=True)
    for i in (0, 2, 3, 5):  # any 4 of 6
        (ok_dir / f"{i:03d}").write_bytes(containers[i])
    bad_dir = shard_root / pid_bad.hex()
    bad_dir.mkdir(parents=True)
    for i in (0, 1):  # below k: must be reported, not crash the walk
        (bad_dir / f"{i:03d}").write_bytes(containers[i])
    done, failed = rs_stripe.assemble_tree(shard_root, tmp_path / "pack",
                                           BACKEND)
    assert done == [pid_ok]
    assert [pid for pid, _ in failed] == [pid_bad]
    assert packfile_path(tmp_path / "pack", pid_ok).read_bytes() == data


# --------------------------------------------------------------------------
# backend routing: CPU oracle vs batched kernel, bit for bit
# --------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["cpu", "tpu"])
def test_backend_encode_decode_matches_oracle(name, nprng):
    """What the engine calls (``backend.encode_shards`` /
    ``decode_shards``) at its own 4+2 geometry, on the oracle backend and
    on the one a chip selects: parity equals ``gf_cpu``, and the data
    comes back with any m shards dropped."""
    from backuwup_tpu.ops.backend import TpuBackend

    backend = BACKEND if name == "cpu" else \
        TpuBackend(CDCParams.from_desired(4096))
    k, m = defaults.RS_K, defaults.RS_M
    stripes = nprng.integers(0, 256, (3, k, 128), dtype=np.uint8)
    parity = np.asarray(backend.encode_shards(stripes, m), dtype=np.uint8)
    expect = np.stack([gf_cpu.encode_stripe(s, m) for s in stripes])
    assert np.array_equal(parity, expect)
    full = np.concatenate([stripes, parity], axis=1)
    for present in itertools.combinations(range(k + m), k):
        dec = np.asarray(backend.decode_shards(
            full[:, list(present), :], k, m, list(present)), dtype=np.uint8)
        assert np.array_equal(dec, stripes), f"survivors {present}"


def test_device_kernel_matches_oracle_on_host(nprng):
    # rs_tpu's jitted bit-matrix kernel runs on whatever platform jax
    # is pinned to — under the tier-1 cpu pin this IS the parity check the
    # subsystem's ground truth demands (bit-for-bit vs the numpy oracle)
    from backuwup_tpu.erasure import rs_tpu

    k, m = defaults.RS_K, defaults.RS_M
    stripes = nprng.integers(0, 256, (4, k, 256), dtype=np.uint8)
    parity = np.asarray(rs_tpu.encode_stripes(stripes, m))
    expect = np.stack([gf_cpu.encode_stripe(s, m) for s in stripes])
    assert np.array_equal(parity, expect)
    full = np.concatenate([stripes, parity], axis=1)
    for present in itertools.combinations(range(k + m), k):
        dec = np.asarray(rs_tpu.decode_stripes(
            full[:, list(present), :], k, m, list(present)))
        assert np.array_equal(dec, stripes), f"survivors {present}"


@pytest.mark.accel
def test_device_kernel_matches_oracle_on_accelerator(nprng):
    # the same parity contract on real accelerator silicon, at a batch
    # size worth shipping to the device; auto-skipped under the tier-1
    # JAX_PLATFORMS=cpu pin by the conftest `accel` marker hook
    from backuwup_tpu.erasure import rs_tpu

    k, m = defaults.RS_K, defaults.RS_M
    stripes = nprng.integers(0, 256, (64, k, 4096), dtype=np.uint8)
    parity = np.asarray(rs_tpu.encode_stripes(stripes, m))
    expect = np.stack([gf_cpu.encode_stripe(s, m) for s in stripes])
    assert np.array_equal(parity, expect)
    present = list(range(m, k + m))
    full = np.concatenate([stripes, parity], axis=1)
    dec = np.asarray(rs_tpu.decode_stripes(
        full[:, present, :], k, m, present))
    assert np.array_equal(dec, stripes)


def _all_products():
    """Every coefficient against every byte value: the 65,536 products
    of the field, one by one."""
    every = np.arange(256, dtype=np.uint8)
    return every.reshape(256, 1), every.reshape(1, 1, 256)


def _random_product(r, j, batch, length):
    rng = np.random.default_rng([r, j, batch, length])
    return (rng.integers(0, 256, (r, j), dtype=np.uint8),
            rng.integers(0, 256, (batch, j, length), dtype=np.uint8))


_PRODUCT_CASES = [pytest.param(_all_products, id="all-65536-products")] + [
    pytest.param(lambda r=r, j=j, b=b, n=n: _random_product(r, j, b, n),
                 id=f"{r}x{j}-batch{b}-len{n}")
    for r, j in [(2, 4), (4, 4), (1, 1), (3, 5), (6, 10)]
    for b in (1, 3) for n in (1, 255, 4096, 4097)]


@pytest.mark.parametrize("case", _PRODUCT_CASES)
def test_device_product_matches_oracle(case):
    """The device program multiplies bit matrices, the oracle looks
    its table up: any matrix (a parity block, a recovery matrix, any
    coefficients), any batch, lengths on and off ``gf_matmul_stripes``'s
    power-of-two buckets, the same bytes."""
    from backuwup_tpu.erasure import rs_tpu

    mat, stripes = case()
    out = rs_tpu.gf_matmul_stripes(mat, stripes)
    assert out.dtype == np.uint8
    expect = np.stack([gf_cpu.gf_matmul(mat, s) for s in stripes])
    assert np.array_equal(out, expect)


def test_device_product_at_the_bucket_that_is_no_power_of_two(nprng):
    """``_matmul_batched()`` as ``ResidentStripe`` calls it, at RS 4+2
    and the 3 MiB bucket (``defaults.BLAKE3_LEAF_BUCKETS``' last): the
    one shard length that ``gf_matmul_stripes``'s padding never makes."""
    import jax.numpy as jnp

    from backuwup_tpu.erasure import resident, rs_tpu

    k, m = defaults.RS_K, defaults.RS_M
    bucket = resident.shard_bucket(3 << 20)
    assert bucket == 3 << 20 and bucket & (bucket - 1)
    stripe = nprng.integers(0, 256, (1, k, bucket), dtype=np.uint8)
    parity = rs_tpu._matmul_batched()(
        jnp.asarray(resident._parity_bits(k, m)), jnp.asarray(stripe))
    assert parity.shape == (1, m, bucket) and parity.dtype == jnp.uint8
    assert np.array_equal(np.asarray(parity)[0],
                          gf_cpu.encode_stripe(stripe[0], m))


def _seeded_rand(seed: int):
    """``os.urandom``'s shape from a seed; the first offset drawn is 17,
    so one window of the first shard starts inside its 48-byte header."""
    rng = random.Random(seed)
    forced = [(17).to_bytes(8, "little")]

    def rand(n: int) -> bytes:
        return forced.pop() if forced and n == 8 else rng.randbytes(n)

    return rand


@pytest.mark.parametrize("missing", [[0, 1, 2, 3, 4, 5], [1, 4]],
                         ids=["all-six", "one-and-four"])
@pytest.mark.parametrize(
    "size", [1, 4096 - 1, 300 << 10, 3 << 20, (3 << 20) + 5])
def test_resident_stripe_matches_the_host_composition(size, missing, rng):
    """The device route of ``TpuBackend.encode_stripe`` (erasure/
    resident.py) against the oracle: containers byte for byte those of
    ``split_packfile`` on the numpy backend; every audit entry's digest
    is BLAKE3 of ``nonce || container[offset:offset+length]`` by the CPU
    oracle, its window inside the container, sixteen a shard, and only
    for the shards asked; drawn from the same ``rand`` the tables are
    the host composition's, entry for entry."""
    from backuwup_tpu.ops.backend import TpuBackend
    from backuwup_tpu.ops.blake3_cpu import blake3_many

    k, m = defaults.RS_K, defaults.RS_M
    data = rng.randbytes(size)
    stripe = TpuBackend(CDCParams.from_desired(4096)).encode_stripe(
        data, k, m, missing, rand=_seeded_rand(size))
    assert type(stripe) is not rs_stripe.Stripe
    expect = rs_stripe.split_packfile(data, k, m, BACKEND)
    assert stripe.containers == expect
    tables = stripe.challenge_tables()
    assert sorted(tables) == missing
    count = defaults.AUDIT_CHALLENGES_PER_PACKFILE
    entries = [(i, e) for i in missing for e in tables[i]]
    assert len(entries) == count * len(missing)
    for i, e in entries:
        assert 0 <= e.offset and e.offset + e.length <= len(expect[i])
        assert e.length == min(defaults.AUDIT_WINDOW_BYTES, len(expect[i]))
    assert [e.digest for _i, e in entries] == blake3_many(
        [e.nonce + expect[i][e.offset:e.offset + e.length]
         for i, e in entries])
    in_header = tables[missing[0]][0]
    assert in_header.offset < rs_stripe.HEADER_LEN
    if len(expect[0]) > defaults.AUDIT_WINDOW_BYTES + 17:
        assert in_header.offset == 17
    host = BACKEND.encode_stripe(data, k, m, missing,
                                 rand=_seeded_rand(size))
    assert host.containers == expect
    if size <= 300 << 10:  # the numpy oracle hashes ~1 MiB/s
        assert host.challenge_tables() == tables


# --------------------------------------------------------------------------
# store: shard_index schema + deterministic peer ordering
# --------------------------------------------------------------------------


def test_store_shard_placement_round_trip(store):
    pid, pa, pb = b"\x0e" * 12, b"\x61" * 32, b"\x62" * 32
    store.record_placement(pid, pa, 100, shard_index=0)
    store.record_placement(pid, pb, 100, shard_index=1)
    # one shard per peer per stripe: the (pid, peer) key ignores the dup
    store.record_placement(pid, pa, 100, shard_index=2)
    assert store.shard_placements_for_peer(pa) == [(pid, 100, 0)]
    assert sorted(store.shards_for_packfile(pid)) == \
        sorted([(pa, 0), (pb, 1)])
    assert store.retire_placement(pid, pa) == 1
    assert store.shards_for_packfile(pid) == [(pb, 1)]
    assert store.retire_placement(pid, pa) == 0  # idempotent


def test_store_legacy_placement_reads_as_whole(store):
    pid, peer = b"\x0f" * 12, b"\x63" * 32
    store.record_placement(pid, peer, 500)  # pre-erasure call shape
    assert store.shard_placements_for_peer(peer) == [(pid, 500, -1)]
    assert store.shards_for_packfile(pid) == [(peer, -1)]


def test_find_peers_with_storage_tie_break_is_deterministic(store):
    hi, lo = b"\x02" * 32, b"\x01" * 32
    store.add_peer_negotiated(hi, 1000)
    store.add_peer_negotiated(lo, 1000)  # equal free space
    assert [p.pubkey for p in store.find_peers_with_storage()] == [lo, hi]


# --------------------------------------------------------------------------
# wire: 13-byte shard ids + geometry fields
# --------------------------------------------------------------------------


def test_shard_file_frame_round_trip():
    sid = rs_stripe.shard_id(b"\x07" * 12, 5)
    body = wire.P2PBody(
        kind=wire.P2PBodyKind.FILE,
        header=wire.P2PHeader(sequence_number=3,
                              session_nonce=b"\x01" * wire.TRANSPORT_NONCE_LEN),
        file_info=wire.FileInfoKind.SHARD, file_id=sid, data=b"container")
    out = wire.P2PBody.decode_bytes(body.encode_bytes())
    assert out.file_info == wire.FileInfoKind.SHARD
    assert out.file_id == sid and out.data == b"container"


def test_audit_ids_accept_shards_reject_other_lengths():
    sid = rs_stripe.shard_id(b"\x07" * 12, 0)
    c = wire.StorageChallenge(packfile_id=sid, offset=0, length=16,
                              nonce=b"\x00" * wire.AUDIT_NONCE_LEN)
    assert c.packfile_id == sid
    wire.StorageProof(packfile_id=b"\x07" * 12,
                      status=wire.ProofStatus.OK)  # legacy id still fine
    with pytest.raises(ValueError, match="12 or 13 bytes"):
        wire.StorageChallenge(packfile_id=b"\x07" * 11, offset=0, length=1,
                              nonce=b"\x00" * wire.AUDIT_NONCE_LEN)


def test_backup_request_min_peers_round_trip():
    msg = wire.BackupRequest(session_token=b"\x01" * 16,
                             storage_required=123, min_peers=6)
    out = wire.JsonMessage.from_json(msg.to_json())
    assert out.storage_required == 123 and out.min_peers == 6
    # pre-erasure senders omit the field: the default keeps them greedy
    assert wire.BackupRequest(session_token=b"\x01" * 16,
                              storage_required=1).min_peers == 1


def test_backup_restore_info_advertises_geometry():
    msg = wire.BackupRestoreInfo(snapshot_hash=b"\x0a" * 32,
                                 peers=["ff" * 32], rs_k=4, rs_m=2)
    out = wire.JsonMessage.from_json(msg.to_json())
    assert (out.rs_k, out.rs_m) == (4, 2)
    assert wire.BackupRestoreInfo().rs_k == 0  # pre-sharding servers


def test_engine_stripe_geometry_reads_defaults(monkeypatch):
    from backuwup_tpu.engine import Engine

    assert Engine._stripe_geometry() == (defaults.RS_K, defaults.RS_M)
    monkeypatch.setattr(defaults, "RS_M", 0)
    assert Engine._stripe_geometry() is None  # striping disabled entirely


# --------------------------------------------------------------------------
# server: min_peers spread in matchmaking
# --------------------------------------------------------------------------


class _AlwaysOnline:
    def is_online(self, client_id):
        return True

    async def notify(self, client_id, msg):
        return True


def _queue_with_candidates(candidates, each_bytes):
    from backuwup_tpu.net.server import ServerDB, StorageQueue

    db = ServerDB(":memory:")
    q = StorageQueue(db, _AlwaysOnline())
    expires = time.time() + 600
    for c in candidates:
        q._queue.append((bytes(c), each_bytes, expires))
    return db, q


def test_fulfill_spreads_over_min_peers_when_queue_is_deep(loop):
    requester = b"\xa0" * 32
    candidates = [bytes([0xB0 + i]) * 32 for i in range(6)]
    db, q = _queue_with_candidates(candidates, 10_000)
    loop.run_until_complete(q.fulfill(requester, 600, min_peers=6))
    negotiated = db.get_client_negotiated_peers(requester)
    assert sorted(negotiated) == sorted(candidates)  # all six, 100 each
    for c in candidates:
        assert db.get_clients_storing_on(c) == [requester]


def test_fulfill_stays_greedy_with_a_shallow_queue(loop):
    # 2-3-client deployments must see exactly the pre-erasure behavior:
    # the spread cap only arms when the queue could plausibly reach
    # min_peers distinct candidates
    requester = b"\xa1" * 32
    candidates = [b"\xc1" * 32, b"\xc2" * 32]
    db, q = _queue_with_candidates(candidates, 10_000)
    loop.run_until_complete(q.fulfill(requester, 600, min_peers=6))
    assert db.get_client_negotiated_peers(requester) == [candidates[0]]


# --------------------------------------------------------------------------
# chaos end-to-end: the striped acceptance scenario
# --------------------------------------------------------------------------


def _corpus(root, rng):
    root.mkdir(parents=True, exist_ok=True)
    (root / "docs").mkdir()
    (root / "big.bin").write_bytes(rng.randbytes(300_000))
    (root / "docs" / "notes.txt").write_bytes(rng.randbytes(90_000))
    (root / "small.cfg").write_bytes(b"alpha=1\nbeta=2\n")


def _tree_digest(root):
    out = {}
    for p in sorted(root.rglob("*")):
        if p.is_file():
            out[str(p.relative_to(root))] = hashlib.sha256(
                p.read_bytes()).hexdigest()
    return out


def test_chaos_stripe_sourceless_repair_and_two_dark_restore(
        tmp_path, loop, monkeypatch, plane):
    from backuwup_tpu.app import ClientApp
    from backuwup_tpu.net.server import CoordinationServer

    monkeypatch.setattr(defaults, "PACKFILE_TARGET_SIZE", 64 * 1024)
    monkeypatch.setattr(defaults, "ACK_TIMEOUT_S", 1.5)
    monkeypatch.setattr(defaults, "RESTORE_REQUEST_THROTTLE_S", 0.0)
    monkeypatch.setattr(defaults, "AUDIT_SERVE_MIN_INTERVAL_S", 0.0)
    rng = random.Random(21)
    _corpus(tmp_path / "a_src", rng)
    source_digest = _tree_digest(tmp_path / "a_src")
    k, m = defaults.RS_K, defaults.RS_M
    n = k + m
    assert (k, m) == (4, 2)  # the scenario below is written for 4+2

    async def run():
        server = CoordinationServer(db_path=str(tmp_path / "server.db"))
        port = await server.start()

        def make_app(name):
            app = ClientApp(config_dir=tmp_path / name / "cfg",
                            data_dir=tmp_path / name / "data",
                            server_addr=f"127.0.0.1:{port}",
                            backend=CpuBackend(CDCParams.from_desired(4096)))
            app.store.set_backup_path(str(tmp_path / "a_src"))
            return app

        a = make_app("a")
        holders = [make_app(f"p{i}") for i in range(1, n + 1)]
        spare = make_app("spare")
        apps = [a] + holders + [spare]
        for app in apps:
            await app.start()
            app._audit_task.cancel()  # deterministic: tests drive audits
        a.engine.auto_repair = False

        # manual negotiation (matchmaking has its own tests).  The six
        # holders get the larger allowance so free-space ordering places
        # every stripe on them; the spare sorts last and stays fresh for
        # the sourceless rebuild to re-home onto.
        for peer, amt in [(p, 8 << 20) for p in holders] + \
                         [(spare, 6 << 20)]:
            a.store.add_peer_negotiated(peer.client_id, amt)
            peer.store.add_peer_negotiated(a.client_id, amt)
            server.db.save_storage_negotiated(
                bytes(a.client_id), bytes(peer.client_id), amt)

        # --- backup: every packfile becomes a k+m stripe ------------------
        snapshot = await asyncio.wait_for(a.backup(), 180)
        assert snapshot
        pids = set()
        for p in holders:
            rows = a.store.shard_placements_for_peer(p.client_id)
            assert rows, "every holder must carry part of the backup"
            for pid, _size, idx in rows:
                assert idx >= 0, "nothing may fall back to whole placement"
                pids.add(bytes(pid))
        assert len(pids) >= 2, "corpus must span several packfiles"
        for pid in pids:
            srows = a.store.shards_for_packfile(pid)
            assert sorted(i for _, i in srows) == list(range(n))
            assert len({bytes(peer) for peer, _ in srows}) == n
        assert a.store.shard_placements_for_peer(spare.client_id) == []
        # acked stripes delete the local packfiles (fan-out dirs remain)
        assert not [p for p in a.engine._pack_dir().rglob("*")
                    if p.is_file()]

        # --- the local source tree is GONE: repair must be sourceless ----
        shutil.rmtree(tmp_path / "a_src")

        # --- first holder dies and is audit-demoted ----------------------
        p1 = holders[0]
        lost_rows = a.store.shard_placements_for_peer(p1.client_id)
        assert len(lost_rows) == len(pids)  # one shard of every stripe
        plane.kill(p1.client_id)
        await p1.stop()
        t0 = time.time()
        for i in range(defaults.AUDIT_DEMOTE_MISSES):
            res = await a.engine.audit_peer(p1.client_id, now=t0 + i)
            assert res is not None and not res.passed
        assert a.store.get_audit_state(p1.client_id).demoted

        # --- one repair round rebuilds the lost shards from survivors ----
        report = await asyncio.wait_for(
            a.engine.repair_round(now=t0 + 10), 180)
        assert report["shards_rebuilt"] == len(pids)
        assert report["packfiles"] == 0  # nothing orphaned, no re-pack
        assert report["bytes_replaced"] > 0
        assert bytes(p1.client_id).hex() in report["peers"]
        assert a.store.placements_for_peer(p1.client_id) == []
        spare_rows = a.store.shard_placements_for_peer(spare.client_id)
        assert len(spare_rows) == len(pids)
        for pid in pids:  # full n-coverage again, p1 replaced by spare
            srows = a.store.shards_for_packfile(pid)
            assert sorted(i for _, i in srows) == list(range(n))
            assert bytes(p1.client_id) not in {bytes(p) for p, _ in srows}
        n_reports = server.db._db.execute(
            "SELECT COUNT(*) FROM repair_reports WHERE peer = ?",
            (bytes(p1.client_id),)).fetchone()[0]
        assert n_reports == 1

        # --- a second holder goes dark: restore on any 4 of 6 ------------
        p2 = holders[1]
        plane.kill(p2.client_id)
        await p2.stop()
        dest = tmp_path / "restored"
        await asyncio.wait_for(a.restore(dest), 180)
        assert _tree_digest(dest) == source_digest  # byte-for-byte

        for app in apps:
            if app not in (p1, p2):
                await app.stop()
        await server.stop()

    loop.run_until_complete(asyncio.wait_for(run(), 500))
