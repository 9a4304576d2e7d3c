"""P2P data plane over loopback: rendezvous, signed transfer, restore-back."""

import asyncio
import importlib
import os
import random
import types

import pytest

from backuwup_tpu import defaults, wire
from backuwup_tpu.crypto import KeyManager
from backuwup_tpu.net import p2p
from backuwup_tpu.net.client import ServerClient
from backuwup_tpu.net.p2p import (
    P2PError,
    P2PNode,
    ReceivedFilesWriter,
    RestoreFilesWriter,
    obfuscate,
)
from backuwup_tpu.net.server import CoordinationServer
from backuwup_tpu.store import Store


def test_obfuscation_round_trip(rng):
    data = rng.randbytes(123_123)
    key = b"\xaa\x01\x7f\x33"
    assert obfuscate(obfuscate(data, key), key) == data
    assert obfuscate(data, key) != data


@pytest.fixture
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


async def _make_node(tmp_path, name, port, monkeypatch_data_dir):
    keys = KeyManager.from_secret(bytes([len(name)]) * 31 + name.encode()[:1])
    store = Store(tmp_path / name / "cfg")
    store.set_obfuscation_key(b"\x11\x22\x33\x44")
    client = ServerClient(keys, store, addr=f"127.0.0.1:{port}")
    await client.register()
    await client.login()
    node = P2PNode(keys, store, client)
    client.start_ws()
    await asyncio.wait_for(client.ws_connected.wait(), 5)
    return keys, store, client, node


def test_transfer_and_restore_cycle(tmp_path, loop, monkeypatch):
    """A stores two packfiles + an index on B, then restores them back."""
    monkeypatch.setenv("DATA_DIR", str(tmp_path / "b" / "data"))

    async def run():
        server = CoordinationServer()
        port = await server.start()
        ka, sa, ca, na = await _make_node(tmp_path, "a", port, None)
        kb, sb, cb, nb = await _make_node(tmp_path, "b", port, None)

        # peers know each other via a negotiated match (ledger rows)
        sa.add_peer_negotiated(kb.client_id, 10_000_000)
        sb.add_peer_negotiated(ka.client_id, 10_000_000)

        received_done = asyncio.Event()

        async def on_transport(source, transport):
            from backuwup_tpu.net.p2p import Receiver
            writer = ReceivedFilesWriter(sb, source)
            await Receiver(transport, writer.sink).run()
            received_done.set()

        nb.on_transport_request = on_transport
        nb.on_restore_request = lambda src, t: nb.serve_restore(src, t)

        async def on_restore(source, transport):
            await nb.serve_restore(source, transport)

        nb.on_restore_request = on_restore

        # --- A -> B transfer ------------------------------------------------
        t = await na.connect(kb.client_id, wire.RequestType.TRANSPORT)
        pid1, pid2 = b"\x01" * 12, b"\x02" * 12
        data1, data2 = b"packfile-one" * 1000, b"packfile-two" * 2000
        index0 = b"index-file-zero" * 100
        await t.send_data(data1, wire.FileInfoKind.PACKFILE, pid1)
        await t.send_data(data2, wire.FileInfoKind.PACKFILE, pid2)
        await t.send_data(index0, wire.FileInfoKind.INDEX,
                          (0).to_bytes(8, "little"))
        await t.close()
        await asyncio.wait_for(received_done.wait(), 10)

        # stored obfuscated, accounted, de-obfuscatable
        peer = sb.get_peer(ka.client_id)
        assert peer.bytes_received == len(data1) + len(data2) + len(index0)
        stored = list(ReceivedFilesWriter(sb, ka.client_id).iter_stored())
        assert {s[1]: s[2] for s in stored if s[0] == wire.FileInfoKind.PACKFILE} \
            == {pid1: data1, pid2: data2}
        raw_on_disk = next(
            (sb.received_dir(ka.client_id) / "pack" / pid1.hex()).parent.glob(
                pid1.hex())).read_bytes()
        assert raw_on_disk != data1  # obfuscated at rest

        # --- A <- B restore -------------------------------------------------
        restorer = RestoreFilesWriter(sa)
        tr = await na.connect(kb.client_id, wire.RequestType.RESTORE_ALL)
        from backuwup_tpu.net.p2p import Receiver
        got = await Receiver(tr, restorer.sink).run()
        assert got == 3
        pack_dir = sa.restore_dir() / "pack" / pid1.hex()[:2]
        assert (pack_dir / pid1.hex()).read_bytes() == data1

        # immediate second restore is throttled (60 s rate limit)
        tr2 = await na.connect(kb.client_id, wire.RequestType.RESTORE_ALL)
        got2 = await Receiver(tr2, restorer.sink).run()
        assert got2 == 0  # serve_restore raised before sending anything

        await ca.close()
        await cb.close()
        await server.stop()

    loop.run_until_complete(asyncio.wait_for(run(), 60))


def test_unknown_peer_connection_refused(tmp_path, loop, monkeypatch):
    """B ignores rendezvous from clients not in its peer ledger."""
    monkeypatch.setenv("DATA_DIR", str(tmp_path / "bx" / "data"))

    async def run():
        server = CoordinationServer()
        port = await server.start()
        ka, sa, ca, na = await _make_node(tmp_path, "ax", port, None)
        kb, sb, cb, nb = await _make_node(tmp_path, "bx", port, None)
        # no ledger rows: B refuses to even confirm
        with pytest.raises(P2PError):
            await na.connect(kb.client_id, wire.RequestType.TRANSPORT,
                             timeout=1.5)
        await ca.close()
        await cb.close()
        await server.stop()

    loop.run_until_complete(asyncio.wait_for(run(), 30))


def test_quota_enforced(tmp_path, loop, monkeypatch):
    monkeypatch.setenv("DATA_DIR", str(tmp_path / "bq" / "data"))
    # shrink the overuse grace so a transport-sized file can exceed quota
    monkeypatch.setattr(defaults, "PEER_OVERUSE_GRACE", 1024)

    async def run():
        server = CoordinationServer()
        port = await server.start()
        ka, sa, ca, na = await _make_node(tmp_path, "aq", port, None)
        kb, sb, cb, nb = await _make_node(tmp_path, "bq", port, None)
        sa.add_peer_negotiated(kb.client_id, 100)
        sb.add_peer_negotiated(ka.client_id, 100)  # tiny quota

        failures = []

        async def on_transport(source, transport):
            from backuwup_tpu.net.p2p import Receiver
            writer = ReceivedFilesWriter(sb, source)
            try:
                await Receiver(transport, writer.sink).run()
            except P2PError as e:
                failures.append(e)

        nb.on_transport_request = on_transport
        t = await na.connect(kb.client_id, wire.RequestType.TRANSPORT)
        big = b"\x00" * (defaults.PEER_OVERUSE_GRACE + 1000 + 100)
        with pytest.raises(P2PError):  # no ack comes back
            await t.send_data(big, wire.FileInfoKind.PACKFILE, b"\x03" * 12)
        await t.close()
        await asyncio.sleep(0.2)
        assert failures, "receiver must reject over-quota file"
        await ca.close()
        await cb.close()
        await server.stop()

    loop.run_until_complete(asyncio.wait_for(run(), 30))


# --- websocket extensions: none (docs/transfer.md) ---------------------------

def _transport_module(name, old_roles):
    """``p2p.websockets`` as the named transport, with the roles in
    ``old_roles`` calling it the way the tree before PR 31 did: the
    ``compression`` keyword left out, so the wheel's (and the shim's)
    default offers / accepts permessage-deflate."""
    mod = importlib.import_module(
        {"wheel": "websockets",
         "ws_compat": "backuwup_tpu.utils.ws_compat"}[name])

    def role(fn, old):
        if not old:
            return fn

        def call(*args, compression=None, **kw):
            return fn(*args, **kw)
        return call

    return types.SimpleNamespace(
        connect=role(mod.connect, "dial" in old_roles),
        serve=role(mod.serve, "listen" in old_roles),
        ConnectionClosed=mod.ConnectionClosed)


async def _shard_over_p2p(tmp_path):
    """A dials B as ``P2PNode`` does and sends one signed 800 KiB FILE
    frame; returns both ends' negotiated extensions and the two
    counters' deltas once the ack is in and B has stored the bytes."""
    server = CoordinationServer()
    port = await server.start()
    ka, sa, ca, na = await _make_node(tmp_path, "a", port, None)
    kb, sb, cb, nb = await _make_node(tmp_path, "b", port, None)
    sa.add_peer_negotiated(kb.client_id, 10_000_000)
    sb.add_peer_negotiated(ka.client_id, 10_000_000)
    listened = {}
    done = asyncio.Event()

    async def on_transport(source, transport):
        listened["extensions"] = transport.extensions
        await p2p.Receiver(
            transport, ReceivedFilesWriter(sb, source).sink).run()
        done.set()

    nb.on_transport_request = on_transport
    sent0, deflated0 = p2p._P2P_BYTES.value(), p2p._P2P_DEFLATED.value()
    t = await na.connect(kb.client_id, wire.RequestType.TRANSPORT)
    shard, sid = os.urandom(800 * 1024), b"\x05" * 13
    await t.send_data(shard, wire.FileInfoKind.SHARD, sid)  # acked
    await t.close()
    await asyncio.wait_for(done.wait(), 10)
    stored = {s[1]: s[2]
              for s in ReceivedFilesWriter(sb, ka.client_id).iter_stored()}
    assert stored == {sid: shard}
    await ca.close()
    await cb.close()
    await server.stop()
    return (t.extensions, listened["extensions"],
            p2p._P2P_BYTES.value() - sent0,
            p2p._P2P_DEFLATED.value() - deflated0)


async def _two_holder_backup(tmp_path):
    """Two clients back up to each other; returns A's
    ``last_pipeline_report["send"]``."""
    from backuwup_tpu.app import ClientApp
    from backuwup_tpu.ops.backend import CpuBackend
    from backuwup_tpu.ops.gear import CDCParams

    server = CoordinationServer(db_path=str(tmp_path / "server.db"))
    addr = f"127.0.0.1:{await server.start()}"
    apps = []
    for name in "ab":
        src = tmp_path / f"{name}_src"
        src.mkdir()
        (src / "data.bin").write_bytes(
            random.Random(ord(name)).randbytes(300_000))
        app = ClientApp(config_dir=tmp_path / name / "cfg",
                        data_dir=tmp_path / name / "data", server_addr=addr,
                        backend=CpuBackend(CDCParams.from_desired(4096)))
        await app.start()
        app.store.set_backup_path(str(src))
        apps.append(app)
    await asyncio.wait_for(
        asyncio.gather(*(app.backup() for app in apps)), 120)
    send = apps[0].engine.last_pipeline_report["send"]
    for app in apps:
        await app.stop()
    await server.stop()
    return send


@pytest.mark.parametrize("case", [
    "wheel", "wheel-old_dialler", "wheel-old_listener", "wheel-old_both",
    "ws_compat", "ws_compat-old_dialler", "ws_compat-old_listener",
    "ws_compat-old_both", "two_holder_backup"])
def test_p2p_socket_negotiates_no_extension(case, tmp_path, loop,
                                            monkeypatch):
    """A P2P socket opened the way ``P2PNode`` opens it negotiates no
    websocket extension, on the wheel and on the aiohttp shim; a peer of
    the version before (which offered, or accepted, permessage-deflate)
    still gets none from a peer of this one, and its frame and ack go
    through.  Two old ends are the control: they do negotiate it, and
    ``bkw_p2p_bytes_deflated_total`` counts every byte they ship."""
    monkeypatch.setenv("DATA_DIR", str(tmp_path / "b" / "data"))
    if case == "two_holder_backup":
        sent0 = p2p._P2P_BYTES.value()
        deflated0 = p2p._P2P_DEFLATED.value()
        send = loop.run_until_complete(
            asyncio.wait_for(_two_holder_backup(tmp_path), 180))
        assert p2p._P2P_DEFLATED.value() == deflated0
        assert p2p._P2P_BYTES.value() > sent0
        assert send["deflated_bytes"] == 0
        assert send["wire_bytes"] > 300_000  # A's file, sealed and signed
        return
    name, _, old = case.partition("-")
    old_roles = {"old_dialler": ("dial",), "old_listener": ("listen",),
                 "old_both": ("dial", "listen"), "": ()}[old]
    monkeypatch.setattr(p2p, "websockets", _transport_module(name, old_roles))
    dialled, listened, sent, deflated = loop.run_until_complete(
        asyncio.wait_for(_shard_over_p2p(tmp_path), 60))
    assert sent > 800 * 1024
    if old == "old_both":
        assert dialled == listened == ("permessage-deflate",)
        assert deflated == sent
    else:
        assert dialled == listened == ()
        assert deflated == 0
