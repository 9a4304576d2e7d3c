"""Federated coordination plane (net/ring.py, PartitionedServerStore,
cross-node work stealing, client failover — the PR-15 federation).

Tier 1 covers:

* consistent-hash ring semantics — ownership stability under node
  add (bounded key movement) and remove (only the removed node's keys
  move), steal-order parity with the matchmaker's home-shard-last walk;
* PartitionedServerStore routing — first-pubkey routing, fan-out reads
  merged across partitions, reclaim on both endpoint partitions;
* the matchmaker's remote-steal leg — consulted only after every local
  shard is empty, and ``serve_steal``'s candidate-side invariants
  (record-first, rollback on failed push);
* client failover — a refused dial rotates to the next configured node
  without double-submitting, a received response is always final, and
  a wrong-node 421 redirect is followed exactly once;
* the 3-node kill/revive churn swarm (builtin ``federation`` spec) with
  its zero-lost-matchmakings scorecard gate.

The multi-process scaling legs (scenario/federation.py) and the soak
swarm are slow.
"""

import asyncio
import dataclasses
import socket

import pytest

from backuwup_tpu import defaults
from backuwup_tpu.crypto import KeyManager
from backuwup_tpu.net import client as net_client
from backuwup_tpu.net.matchmaking import ShardedMatchmaker
from backuwup_tpu.net.ring import (HashRing, partition_key, partition_of,
                                   successors)
from backuwup_tpu.net.server import CoordinationServer
from backuwup_tpu.net.serverstore import (PartitionedServerStore,
                                          SqliteServerStore)
from backuwup_tpu.obs import metrics as obs_metrics
from backuwup_tpu.scenario import builtin_swarms, run_swarm
from backuwup_tpu.store import Store

pytestmark = pytest.mark.federation

MIB = 1 << 20


@pytest.fixture(autouse=True)
def _isolate():
    obs_metrics.registry().reset()
    yield
    obs_metrics.registry().reset()


@pytest.fixture
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


def pk(i: int) -> bytes:
    return i.to_bytes(8, "big") + bytes(24)


# --- ring semantics ---------------------------------------------------------


def test_ring_ownership_stable_under_add():
    """Adding a node to an N-node ring moves ~1/(N+1) of the keys and
    ONLY toward the new node — every moved key must land on it."""
    nodes = [f"node{i}" for i in range(4)]
    keys = [pk(i) for i in range(4000)]
    ring = HashRing(nodes)
    before = {k: ring.owner(k) for k in keys}
    ring.add("node4")
    moved = {k for k in keys if ring.owner(k) != before[k]}
    assert all(ring.owner(k) == "node4" for k in moved)
    # expected fraction 1/5; 64 vnodes keeps the variance modest
    assert len(moved) / len(keys) < 0.40


def test_ring_remove_moves_only_its_own_keys():
    nodes = [f"node{i}" for i in range(4)]
    keys = [pk(i) for i in range(4000)]
    ring = HashRing(nodes)
    before = {k: ring.owner(k) for k in keys}
    ring.remove("node2")
    for k in keys:
        if before[k] == "node2":
            assert ring.owner(k) != "node2"
        else:
            # a survivor's keys never move on a remove
            assert ring.owner(k) == before[k]


def test_ring_successors_disjoint_and_stable_at_every_size():
    """Replication-chain property sweep over N = 1..64: for every
    partition the successor chain never contains the owner, has no
    duplicates, and is exactly min(count, N-1) long; and removing a
    node OUTSIDE owner+chain leaves both owner and chain untouched
    (the promote-on-death blast radius is the chain, nothing else)."""
    parts = range(8)
    for n in range(1, 65):
        ring = HashRing([f"node{i}" for i in range(n)])
        for part in parts:
            owner = ring.owner(partition_key(part))
            chain = successors(ring, part, count=3)
            assert owner not in chain
            assert len(chain) == len(set(chain)) == min(3, n - 1)
            involved = {owner, *chain}
            outsider = next((f"node{i}" for i in range(n)
                             if f"node{i}" not in involved), None)
            if outsider is None:
                continue  # every node is on this partition's chain
            ring.remove(outsider)
            assert ring.owner(partition_key(part)) == owner
            assert successors(ring, part, count=3) == chain
            ring.add(outsider)  # hash-positioned: exact inverse


def test_ring_steal_order_home_last_parity():
    """steal_order(n) is the OTHER nodes in ring-successor order —
    the federated continuation of the matchmaker's home-shard-last
    walk: self is excluded (home served locally), and walking from
    each node's order must traverse the same cyclic sequence."""
    ring = HashRing([f"node{i}" for i in range(5)])
    order = ring.nodes()
    assert sorted(order) == sorted(f"node{i}" for i in range(5))
    for nid in order:
        steal = ring.steal_order(nid)
        assert nid not in steal
        assert len(steal) == len(order) - 1
        at = order.index(nid)
        assert steal == order[at + 1:] + order[:at]


def test_ring_empty_and_partition_of():
    assert HashRing([]).owner(pk(1)) is None
    assert HashRing([]).steal_order("nodeX") == []
    parts = defaults.SERVER_STORE_PARTITIONS
    for i in range(100):
        p = partition_of(pk(i), parts)
        assert 0 <= p < parts
        assert p == partition_of(pk(i), parts)  # stable


# --- partitioned store routing ----------------------------------------------


def test_partitioned_store_routes_and_fans_out(tmp_path, loop):
    store = PartitionedServerStore(str(tmp_path / "parts"), partitions=4)
    try:
        # place two sources in different partitions
        a = next(pk(i) for i in range(100)
                 if store.partition_for(pk(i)) is store.parts[0])
        b = next(pk(i) for i in range(100)
                 if store.partition_for(pk(i)) is store.parts[1])
        dest = pk(9999)
        for key in (a, b, dest):
            store.register_client(key)
            assert store.client_exists(key)
        store.save_storage_negotiated(a, dest, MIB)
        store.save_storage_negotiated(dest, a, MIB)
        store.save_storage_negotiated(b, dest, MIB)
        store.save_storage_negotiated(dest, b, MIB)
        # fan-out read sees rows living in different partitions
        storing_on = store.get_clients_storing_on(dest)
        assert set(storing_on) == {a, b}
        # audit fan-out: distinct failing reporters summed across the
        # partitions their reports route to (by-reporter placement)
        store.save_audit_report(a, dest, False, "t")
        store.save_audit_report(b, dest, False, "t")
        assert store.audit_failing_reporters(dest, 3600) == 2
        # reclaim touches both endpoint partitions
        assert store.reclaim_negotiation(a, dest) >= 1
        assert dest not in set(store.get_clients_storing_on(a))
    finally:
        store.close()


def test_partitioned_store_write_behind_durable(tmp_path, loop):
    store = PartitionedServerStore(str(tmp_path / "parts"), partitions=2)
    try:
        async def run():
            await store.aio.register_client(pk(1))
            await store.aio.save_storage_negotiated(pk(1), pk(2), MIB)

        loop.run_until_complete(run())
        store.flush()
        assert store.client_exists(pk(1))
        # the reverse edge: pk(1) is the source storing on pk(2)
        assert store.get_clients_storing_on(pk(2)) == [pk(1)]
    finally:
        store.close()


# --- remote steal -----------------------------------------------------------


class StubConns:
    def __init__(self):
        self.fail_notify = set()
        self.notified = {}

    def is_online(self, client_id) -> bool:
        return True

    async def notify(self, client_id, msg) -> bool:
        await asyncio.sleep(0)
        if bytes(client_id) in self.fail_notify:
            return False
        self.notified.setdefault(bytes(client_id), []).append(msg)
        return True


def test_remote_steal_only_after_local_shards_empty(tmp_path, loop):
    """A local candidate must be matched locally; the remote leg fires
    only when every local shard came up empty."""
    store = SqliteServerStore(str(tmp_path / "s.db"))
    conns = StubConns()
    queue = ShardedMatchmaker(store, conns, expiry_s=30)
    calls = []

    async def remote(requester, want, share_cap):
        calls.append(int(want))
        return None

    queue.remote_steal = remote
    try:
        async def run():
            await queue.fulfill(pk(1), MIB)       # enqueues pk(1)
            assert calls == [MIB]                  # ring was starved
            calls.clear()
            await queue.fulfill(pk(2), MIB)       # matches pk(1) locally
            assert calls == []                     # remote leg not taken
            assert pk(1) in conns.notified and pk(2) in conns.notified

        loop.run_until_complete(run())
    finally:
        store.close()


def test_remote_steal_hit_notifies_requester(tmp_path, loop):
    store = SqliteServerStore(str(tmp_path / "s.db"))
    conns = StubConns()
    queue = ShardedMatchmaker(store, conns, expiry_s=30)

    async def remote(requester, want, share_cap):
        return pk(77), int(want)

    queue.remote_steal = remote
    try:
        async def run():
            await queue.fulfill(pk(1), MIB)
            [msg] = conns.notified[pk(1)]
            assert msg.destination_id == pk(77)
            assert msg.storage_available == MIB

        loop.run_until_complete(run())
    finally:
        store.close()


def test_serve_steal_records_both_edges_and_pushes(tmp_path, loop):
    store = SqliteServerStore(str(tmp_path / "s.db"))
    conns = StubConns()
    queue = ShardedMatchmaker(store, conns, expiry_s=30)
    remote_requester = pk(500)
    try:
        async def run():
            await queue.fulfill(pk(1), MIB)        # queue a local candidate
            served = await queue.serve_steal(remote_requester, MIB)
            assert served == (pk(1), MIB)
            # the local candidate got its push; the requester push is
            # the REQUESTER node's job
            assert pk(1) in conns.notified
            assert remote_requester not in conns.notified

        loop.run_until_complete(run())
        store.flush()
        assert set(store.get_clients_storing_on(remote_requester)) == {pk(1)}
        assert set(store.get_clients_storing_on(pk(1))) == {remote_requester}
    finally:
        store.close()


def test_serve_steal_rolls_back_on_failed_candidate_push(tmp_path, loop):
    store = SqliteServerStore(str(tmp_path / "s.db"))
    conns = StubConns()
    conns.fail_notify.add(pk(1))
    queue = ShardedMatchmaker(store, conns, expiry_s=30)
    try:
        async def run():
            await queue.fulfill(pk(1), MIB)
            assert await queue.serve_steal(pk(500), MIB) is None

        loop.run_until_complete(run())
        store.flush()
        assert store.get_clients_storing_on(pk(500)) == []
    finally:
        store.close()


# --- client failover --------------------------------------------------------


def _keys(tag: int) -> KeyManager:
    return KeyManager.from_secret(tag.to_bytes(4, "big").ljust(32, b"\x55"))


def test_client_failover_on_refused_dial_no_double_submit(tmp_path, loop):
    async def run():
        server = CoordinationServer(db_path=str(tmp_path / "s.db"))
        port = await server.start()
        # a port nothing listens on: the dial is REFUSED, which is the
        # only condition that may rotate (the request never reached any
        # server, so a retry cannot double-submit)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            dead = s.getsockname()[1]
        c = net_client.ServerClient(
            _keys(1), Store(tmp_path / "c1"),
            addr=[f"127.0.0.1:{dead}", f"127.0.0.1:{port}"], tls=False)
        try:
            await c.register()
            assert c.failovers == 1
            assert await server.db.aio.client_exists(
                bytes(_keys(1).client_id))
        finally:
            await c.close()
            await server.stop()

    loop.run_until_complete(run())


def test_client_received_response_is_final(tmp_path, loop):
    """A typed server response must NOT rotate.  The identity is
    registered ONLY on the second configured server; a login dialed at
    the first gets a typed CLIENT_NOT_FOUND — if the client treated
    that as a failover trigger, the retry against the second server
    would wrongly succeed."""
    async def run():
        s1 = CoordinationServer(db_path=str(tmp_path / "s1.db"))
        s2 = CoordinationServer(db_path=str(tmp_path / "s2.db"))
        p1, p2 = await s1.start(), await s2.start()
        seed = net_client.ServerClient(
            _keys(2), Store(tmp_path / "seed"),
            addr=f"127.0.0.1:{p2}", tls=False)
        c = net_client.ServerClient(
            _keys(2), Store(tmp_path / "c2"),
            addr=[f"127.0.0.1:{p1}", f"127.0.0.1:{p2}"], tls=False)
        try:
            await seed.register()
            assert await s2.db.aio.client_exists(bytes(_keys(2).client_id))
            with pytest.raises(net_client.ClientNotFound):
                await c.login()
            assert c.failovers == 0
        finally:
            await seed.close()
            await c.close()
            await s1.stop()
            await s2.stop()

    loop.run_until_complete(run())


def test_wrong_node_redirect_followed_once(tmp_path, loop):
    """A session-less request landing on the wrong federation node gets
    a 421 + NodeRedirect toward the ring owner; the client follows it
    (once, and only to a configured URL) so a stale node list never
    loses the matchmaking."""
    async def run():
        s0 = CoordinationServer(db_path=str(tmp_path / "s0.db"))
        s1 = CoordinationServer(db_path=str(tmp_path / "s1.db"))
        p0, p1 = await s0.start(), await s1.start()
        ring = HashRing(["node0", "node1"])
        peers = {"node0": f"http://127.0.0.1:{p0}",
                 "node1": f"http://127.0.0.1:{p1}"}
        s0.enable_federation("node0", ring, peers)
        s1.enable_federation("node1", ring, peers)
        # a key the ring homes on node1, dialed at node0 first
        tag = next(t for t in range(3, 200)
                   if ring.owner(bytes(_keys(t).client_id)) == "node1")
        c = net_client.ServerClient(
            _keys(tag), Store(tmp_path / "c3"),
            addr=[f"127.0.0.1:{p0}", f"127.0.0.1:{p1}"], tls=False)
        try:
            await c.register()
            # the redirect steered the registration to the owner
            assert await s1.db.aio.client_exists(
                bytes(_keys(tag).client_id))
            assert not await s0.db.aio.client_exists(
                bytes(_keys(tag).client_id))
        finally:
            await c.close()
            await s0.stop()
            await s1.stop()

    loop.run_until_complete(run())


# --- the churn swarm --------------------------------------------------------


@pytest.mark.timeout(240)
def test_federation_swarm_kill_revive(tmp_path, loop):
    """Tier-1 federation acceptance: 3 nodes over one partitioned
    store, a node killed and revived on its port mid-run.  The
    scorecard's hard gates: zero lost matchmakings (durable rows >= 2x
    matchmakings across every partition), at least one client failover,
    matchmaking flow after the revive, bounded p99."""
    spec = builtin_swarms()["federation"]
    card, summary = loop.run_until_complete(run_swarm(spec, tmp_path))
    assert card.passed, card.render()
    gates = {a.name: a.passed for a in card.assertions}
    for gate in ("federation_no_lost_matchmakings",
                 "federation_failover_exercised",
                 "federation_post_revive_flow",
                 "federation_p99_bounded",
                 "commits_off_event_loop"):
        assert gates.get(gate) is True, (gate, card.render())
    assert summary["nodes"] == 3
    assert summary["node_kills"] == 1
    assert summary["negotiated_rows"] >= 2 * summary["total_matchmakings"]


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_federation_swarm_soak(tmp_path, loop):
    spec = builtin_swarms()["federation_soak"]
    card, summary = loop.run_until_complete(run_swarm(spec, tmp_path))
    assert card.passed, card.render()
    assert summary["negotiated_rows"] >= 2 * summary["total_matchmakings"]
    assert summary["failovers"] >= 1


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_federation_multiprocess_legs(tmp_path):
    """The scaling legs end-to-end: real OS processes, real /fed/steal
    HTTP.  No throughput is gated (a CPU ratio is not evidence); every
    node must produce matches and the fleet must complete cleanly."""
    from backuwup_tpu.scenario.federation import (FederationLoadSpec,
                                                  run_federation_load)
    out = run_federation_load(
        FederationLoadSpec(nodes=2, clients=32, duration_s=1.0), tmp_path)
    assert out["matchmakings"] > 0
    assert len(out["per_node"]) == 2
    for node in out["per_node"]:
        assert node["fulfills"] > 0
