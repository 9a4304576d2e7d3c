"""Local state store: KV round-trips, peer ledger accounting, event log."""

import pytest

from backuwup_tpu.store import (
    EVENT_BACKUP,
    EVENT_RESTORE_REQUEST,
    Store,
)


@pytest.fixture
def store(tmp_path):
    s = Store(tmp_path / "cfg")
    yield s
    s.close()


def test_identity_round_trip(store):
    assert store.get_root_secret() is None
    assert not store.is_initialized()
    store.set_root_secret(b"\x07" * 32)
    store.set_auth_token(b"\x01" * 16)
    store.set_obfuscation_key(b"\xaa\xbb\xcc\xdd")
    store.set_initialized()
    assert store.get_root_secret() == b"\x07" * 32
    assert store.get_auth_token() == b"\x01" * 16
    assert store.get_obfuscation_key() == b"\xaa\xbb\xcc\xdd"
    assert store.is_initialized()
    store.set_auth_token(None)
    assert store.get_auth_token() is None


def test_obfuscation_key_length_checked(store):
    with pytest.raises(ValueError):
        store.set_obfuscation_key(b"\x01" * 5)


def test_backup_config(store):
    assert store.get_backup_path() is None
    store.set_backup_path("/data/stuff")
    assert store.get_backup_path() == "/data/stuff"
    assert store.get_highest_sent_index() == -1
    store.set_highest_sent_index(17)
    assert store.get_highest_sent_index() == 17


def test_peer_ledger_accounting(store):
    a, b = b"\x01" * 32, b"\x02" * 32
    store.add_peer_negotiated(a, 1000)
    store.add_peer_negotiated(a, 500)   # upsert-increment
    store.add_peer_negotiated(b, 2000)
    store.add_peer_transmitted(a, 300)
    store.add_peer_received(b, 100)
    pa, pb = store.get_peer(a), store.get_peer(b)
    assert pa.bytes_negotiated == 1500 and pa.bytes_transmitted == 300
    assert pa.free_storage == 1200
    assert pb.bytes_received == 100 and pb.free_storage == 2000
    # ordered by free storage, most first
    assert [p.pubkey for p in store.find_peers_with_storage()] == [b, a]


def test_peer_bump_creates_row(store):
    store.add_peer_transmitted(b"\x09" * 32, 42)
    assert store.get_peer(b"\x09" * 32).bytes_transmitted == 42


def test_event_log(store):
    assert store.last_event_time(EVENT_RESTORE_REQUEST) is None
    store.add_event(EVENT_RESTORE_REQUEST, {}, now=100.0)
    store.add_event(EVENT_RESTORE_REQUEST, {}, now=200.0)
    assert store.last_event_time(EVENT_RESTORE_REQUEST) == 200.0
    assert store.last_backup_size() is None
    store.add_event(EVENT_BACKUP, {"size": 12345}, now=300.0)
    assert store.last_backup_size() == 12345


def test_persistence_across_reopen(tmp_path):
    s = Store(tmp_path / "cfg")
    s.set_root_secret(b"\x03" * 32)
    s.add_peer_negotiated(b"\x04" * 32, 777)
    s.close()
    s2 = Store(tmp_path / "cfg")
    assert s2.get_root_secret() == b"\x03" * 32
    assert s2.get_peer(b"\x04" * 32).bytes_negotiated == 777
    s2.close()


def test_tracing_spans_and_report():
    """Host tracing subsystem (SURVEY §5.1: the build adds what the
    reference lacks): spans land in ``bkw_span_seconds``,
    whose per-name count and sum are the aggregate report."""
    from backuwup_tpu.obs import metrics as obs_metrics
    from backuwup_tpu.obs import trace as tracing

    spans = obs_metrics.registry().get("bkw_span_seconds")
    before = {n: spans.count_value(name=n)
              for n in ("unit.test", "unit.decorated")}
    with tracing.span("unit.test"):
        pass

    @tracing.traced("unit.decorated")
    def f():
        return 41

    assert f() == 41
    assert f() == 41
    assert spans.count_value(name="unit.test") == before["unit.test"] + 1
    assert spans.count_value(name="unit.decorated") == \
        before["unit.decorated"] + 2
    assert spans.sum_value(name="unit.decorated") > 0
    assert not hasattr(tracing, "format_report")  # one aggregate, not two

