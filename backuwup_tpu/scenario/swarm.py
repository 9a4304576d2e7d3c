"""Swarm harness: hundreds of control-plane clients against one server.

The chaos scenarios (``scenario/harness.py``) stress the DATA plane — a
handful of clients moving real bytes.  The coordination plane's scaling
question is the opposite shape: MANY clients, tiny requests, all landing
on one aiohttp process.  This module reuses the scenario machinery (the
:class:`~.harness.Phase` script, the sampler, the
:class:`~.scorecard.Scorecard` gates) but swaps the deployment: no
ClientApps, no packfiles — just N :class:`~..net.client.ServerClient`
identities driving registration, login, matchmaking, snapshot
registration, audit verdicts, and WS churn over loopback.

Phases
======

=============  ==========================================================
``register``   every swarm client registers, logs in, and connects its
               WS push channel; a configured subset is then poisoned
               with failing audit reports from distinct reporters so the
               matchmaker's audit-block path stays exercised under load
``swarm``      the measured window: every client loops over a seeded mix
               of storage requests (the matchmaking economy), snapshot
               registrations, audit verdicts, and — for churners — WS
               drops and reconnects; matchmakings/s is counted over
               exactly this window
``drain``      settle in-flight fulfills, flush the store off-loop, and
               capture the verdict facts: event-loop stall ceiling,
               whether any sqlite commit ran on the loop thread, and the
               p99 of ``bkw_server_request_seconds{route="/backups/request"}``
=============  ==========================================================

An event-loop **stall detector** runs through all phases: an asyncio
task that sleeps a fixed tick and records the overshoot.  A blocking
sqlite commit on the loop shows up as a stall spike (and its thread
ident lands in ``store.commit_threads``); the sharded tier must stay
under ``stall_budget_s`` while the legacy tier is expected to blow
through it — that contrast is ``tests/test_swarm.py``'s legacy leg.

Load generation runs OFF the server's event loop: the swarm clients are
distributed over a small pool of worker threads, each with its own
asyncio loop and HTTP sessions.  Co-locating hundreds of client
coroutines on the server's loop would make the shared loop the
bottleneck and flatten any server-side difference (measured: both tiers
plateau at the same matchmakings/s when co-located); with the drivers
off-loop the main loop carries ONLY the server, so the stall detector
and the tier contrast measure the thing under test.
"""

from __future__ import annotations

import asyncio
import contextlib
import math
import random
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import aiohttp

from .. import defaults
from ..crypto import KeyManager
from ..net import client as net_client
from ..net.matchmaking import _MATCHMAKINGS, ShardedMatchmaker
from ..net.ring import HashRing, partition_key
from ..net.server import _REQUEST_SECONDS, CoordinationServer
from ..net.serverstore import PartitionedServerStore, ReplicatedServerStore
from ..obs import metrics as obs_metrics
from .harness import Phase, ScenarioHarness
from . import scorecard as sc

_LOOP_STALL = obs_metrics.histogram(
    "bkw_loop_stall_seconds",
    "Event-loop scheduling overshoot observed by the swarm stall detector",
    buckets=obs_metrics.log_buckets(0.0005, 2.0, 14))


@dataclass(frozen=True)
class SwarmSpec:
    """One swarm run.  ``legacy=True`` assembles the single-lock
    StorageQueue over the direct-commit store (the baseline shape);
    otherwise the sharded matchmaker over the write-behind store."""

    name: str
    phases: tuple
    seed: int = 4242
    sample_interval_s: float = 0.25
    clients: int = 32
    duration_s: float = 2.5
    legacy: bool = False
    shards: Optional[int] = None
    #: bytes each storage request asks for (small keeps matches plentiful)
    request_bytes: int = 1 << 20
    min_peers: int = 1
    #: queued-request expiry; short enough that the deadline heap reaps
    #: during the run
    expiry_s: float = 20.0
    #: clients poisoned with failing audit reports during register
    audit_failers: int = 2
    #: PASSING audit reports preloaded per client before the run: the
    #: matchmaker's per-candidate ``audit_failing_reporters`` scan then
    #: has realistic weight (a long-lived deployment accretes verdict
    #: history), which the baseline pays inside its global lock on the
    #: event loop and the write-behind tier pays on the writer thread
    audit_history: int = 0
    #: every Nth client drops + reconnects its WS during the swarm (0 = off)
    churn_every: int = 8
    #: max tolerated event-loop stall for the non-legacy tier
    stall_budget_s: float = 0.25
    #: per-client think time ceiling between requests (seconds)
    think_s: float = 0.01
    #: load-generator threads the clients are distributed over (keeps
    #: the drivers off the server's event loop — see module docstring)
    workers: int = 8
    #: coordination nodes; >1 deploys the federation: N servers over a
    #: consistent-hash ring with work stealing + notify relay enabled
    #: (implies the sharded tier — ``legacy`` is ignored).  Each node
    #: gets its OWN :class:`~..net.serverstore.ReplicatedServerStore`
    #: with log shipping to ring successors, so node death is
    #: observable at the storage layer
    nodes: int = 1
    #: store partitions when ``nodes > 1`` (defaults to ``nodes``)
    partitions: Optional[int] = None
    #: opt-in BASELINE leg: front every node with one shared
    #: :class:`~..net.serverstore.PartitionedServerStore` (the pre-PR-17
    #: shortcut — killing a node can never lose rows because the store
    #: is shared, which is exactly what it fails to test)
    shared_store: bool = False
    #: probe cadence override for the replicated deployment (tier-1
    #: permakill must converge in well under a second)
    probe_interval_s: float = 0.25
    #: hard per-route p99 ceiling for the federation gate (only asserted
    #: when ``nodes > 1``; generous — loopback plus failover dial cost)
    p99_budget_s: float = 2.5


class _TokenStore:
    """The minimal Store surface ServerClient touches."""

    def __init__(self):
        self._token: Optional[bytes] = None

    def set_auth_token(self, token: Optional[bytes]) -> None:
        self._token = token

    def get_auth_token(self) -> Optional[bytes]:
        return self._token


class SwarmClient:
    """One simulated identity: deterministic keys, its own HTTP session
    and WS push channel, and a count of matches pushed to it."""

    def __init__(self, index: int, seed: int, addr,
                 ring: Optional[HashRing] = None,
                 node_addrs: Optional[Dict[str, str]] = None):
        self.index = index
        self.worker = None  # set by the harness when homed on a worker
        secret = (seed.to_bytes(8, "big", signed=False)
                  + index.to_bytes(8, "big")).ljust(32, b"\x77")
        self.keys = KeyManager.from_secret(secret)
        if ring is not None:
            # federation: dial the ring owner first, then its steal order
            # — the shape a published node list would hand a real client
            owner = ring.owner(bytes(self.keys.client_id))
            order = [owner] + ring.steal_order(owner)
            addr = [node_addrs[n] for n in order]
        self.client = net_client.ServerClient(
            self.keys, _TokenStore(), addr=addr, tls=False)
        self.matches = 0

        async def on_matched(_msg):
            self.matches += 1

        self.client.on_backup_matched = on_matched

    @property
    def client_id(self) -> bytes:
        return bytes(self.keys.client_id)

    async def connect(self) -> None:
        await self.client.register()
        await self.client.login()
        self.client.start_ws()
        await asyncio.wait_for(self.client.ws_connected.wait(), 15)

    async def rejoin_ws(self) -> None:
        """WS churn: drop the push channel (the server sees the client go
        offline and drops its queued entries at pop) and reconnect."""
        if self.client._ws_task is not None:
            self.client._ws_task.cancel()
            with contextlib.suppress(asyncio.CancelledError, Exception):
                await self.client._ws_task
            self.client._ws_task = None
        self.client.ws_connected.clear()
        self.client.start_ws()
        await asyncio.wait_for(self.client.ws_connected.wait(), 15)

    async def close(self) -> None:
        await self.client.close()


class _Worker:
    """One load-generator thread: its own asyncio loop hosting a slice
    of the swarm's clients.  The harness submits phase coroutines with
    :meth:`submit` and awaits them via ``asyncio.wrap_future``."""

    def __init__(self, index: int):
        self.index = index
        self.clients: List[SwarmClient] = []
        #: per-worker fact counters, aggregated by the harness after each
        #: phase (threads must not race on the shared facts dict)
        self.counts = {"requests": 0, "errors": 0, "churns": 0}
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self._ready = threading.Event()
        self.thread = threading.Thread(
            target=self._main, name=f"swarm-worker-{index}", daemon=True)
        self.thread.start()
        self._ready.wait(timeout=10)

    def _main(self) -> None:
        self.loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self.loop)
        self._ready.set()
        try:
            self.loop.run_forever()
        finally:
            self.loop.close()

    def submit(self, coro) -> "asyncio.Future":
        """Schedule ``coro`` on this worker's loop; returns an awaitable
        for the CALLER's loop."""
        return asyncio.wrap_future(
            asyncio.run_coroutine_threadsafe(coro, self.loop))

    def stop(self) -> None:
        if self.loop is not None and self.loop.is_running():
            self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(timeout=10)


class LoopStallDetector:
    """Measures event-loop scheduling overshoot: sleep a fixed tick,
    record how late the wakeup lands.  Any handler blocking the loop —
    e.g. an inline sqlite commit — shows up as a stall at least as long
    as the block."""

    def __init__(self, tick_s: float = 0.02):
        self.tick_s = tick_s
        self.max_stall_s = 0.0
        self.total_stall_s = 0.0
        self.ticks = 0
        self._task: Optional[asyncio.Task] = None

    async def _loop(self) -> None:
        while True:
            t0 = time.monotonic()
            await asyncio.sleep(self.tick_s)
            stall = max(time.monotonic() - t0 - self.tick_s, 0.0)
            self.ticks += 1
            self.total_stall_s += stall
            self.max_stall_s = max(self.max_stall_s, stall)
            _LOOP_STALL.observe(stall)

    def start(self) -> None:
        if self._task is None:
            self._task = asyncio.create_task(self._loop())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._task
            self._task = None


class SwarmHarness(ScenarioHarness):
    """Scenario harness re-pointed at the coordination plane: same phase
    script/sampler/scorecard flow, a completely different deployment."""

    def __init__(self, spec: SwarmSpec, workdir: Path):
        super().__init__(spec, workdir)  # sets rng/samples/t0/facts
        self.spec: SwarmSpec = spec
        self.clients: List[SwarmClient] = []
        self.workers: List[_Worker] = []
        self.stalls = LoopStallDetector()
        self.facts = {"registered": 0, "requests": 0, "errors": 0,
                      "churns": 0, "swarm_matchmakings": 0,
                      "swarm_elapsed_s": 0.0, "matchmakings_per_s": 0.0,
                      "client_matches": 0, "max_stall_s": None,
                      "commits_on_loop": None, "p99_request_s": None,
                      "node_kills": 0, "failovers": 0,
                      "post_revive_matchmakings": None,
                      "total_matchmakings": 0, "negotiated_rows": None,
                      "permakills": 0, "promotions": 0,
                      "repl_promote_s": None,
                      "post_promote_matchmakings": None}
        self.servers: List[CoordinationServer] = []
        self.ring: Optional[HashRing] = None
        self.node_ids: List[str] = []
        self.peer_urls: Dict[str, str] = {}
        self.store = None
        #: node id -> per-node store (replicated deployment)
        self.stores: Dict[str, ReplicatedServerStore] = {}
        self._permakilled: set = set()

    # --- lifecycle ---------------------------------------------------------

    async def setup(self) -> None:
        spec = self.spec
        self._saved = {"BACKUP_REQUEST_EXPIRY_S":
                       defaults.BACKUP_REQUEST_EXPIRY_S}
        defaults.BACKUP_REQUEST_EXPIRY_S = spec.expiry_s
        if spec.nodes > 1:
            self.node_ids = [f"node{i}" for i in range(spec.nodes)]
            self.ring = HashRing(self.node_ids)
            if spec.shared_store:
                # opt-in BASELINE: every node fronts the SAME partitioned
                # store, so killing a node loses connections and
                # in-flight handlers but by construction never rows
                self.store = await asyncio.to_thread(
                    PartitionedServerStore, str(self.workdir / "store"),
                    spec.partitions or spec.nodes)
                for _nid in self.node_ids:
                    srv = CoordinationServer(store=self.store,
                                             shards=spec.shards)
                    await srv.start()
                    self.servers.append(srv)
            else:
                # the real deployment shape: per-node replicated stores
                # with ring-successor log shipping (docs/server.md
                # §Replication) — node death is observable at the
                # storage layer and survived by promote-on-death
                self._saved["REPL_PROBE_INTERVAL_S"] = \
                    defaults.REPL_PROBE_INTERVAL_S
                defaults.REPL_PROBE_INTERVAL_S = spec.probe_interval_s
                for nid in self.node_ids:
                    store = await asyncio.to_thread(
                        ReplicatedServerStore,
                        str(self.workdir / "store" / nid), nid,
                        spec.partitions or spec.nodes)
                    self.stores[nid] = store
                    srv = CoordinationServer(store=store,
                                             shards=spec.shards)
                    await srv.start()
                    self.servers.append(srv)
                self.store = self.stores[self.node_ids[0]]
            self.peer_urls = {
                nid: f"http://127.0.0.1:{srv.port}"
                for nid, srv in zip(self.node_ids, self.servers)}
            for nid, srv in zip(self.node_ids, self.servers):
                srv.enable_federation(nid, self.ring, self.peer_urls)
            self.server = self.servers[0]
            self.server_port = self.server.port
        else:
            self.server = CoordinationServer(
                db_path=str(self.workdir / "server.db"),
                legacy=spec.legacy, shards=spec.shards)
            self.server_port = await self.server.start()
            self.servers = [self.server]
            self.store = self.server.db
        addr = f"127.0.0.1:{self.server_port}"
        node_addrs = {nid: url.removeprefix("http://")
                      for nid, url in self.peer_urls.items()}
        self.workers = [_Worker(i)
                        for i in range(max(1, min(spec.workers,
                                                  spec.clients)))]

        async def make(worker: _Worker, indices: List[int]) -> None:
            # created ON the worker loop so every asyncio primitive the
            # client owns (events, sessions, ws tasks) binds there
            for i in indices:
                c = SwarmClient(i, spec.seed, addr,
                                ring=self.ring, node_addrs=node_addrs)
                c.worker = worker
                worker.clients.append(c)

        await asyncio.gather(*(
            w.submit(make(w, list(range(wi, spec.clients,
                                        len(self.workers)))))
            for wi, w in enumerate(self.workers)))
        self.clients = sorted(
            (c for w in self.workers for c in w.clients),
            key=lambda c: c.index)
        if spec.audit_history:
            await asyncio.to_thread(self._preload_audit_history)
        self._mm0 = _MATCHMAKINGS.value()
        self.stalls.start()

    def _preload_audit_history(self) -> None:
        """Bulk-insert passing verdicts (setup-time, pre-measurement) so
        every client enters matchmaking with a populated audit window.
        Rows route by REPORTER partition when the store is partitioned —
        the same invariant the write path keeps, so the fan-out read
        sees every reporter's latest verdicts."""
        now = time.time()
        groups: Dict[int, Tuple] = {}
        for c in self.clients:
            reporter = self.clients[(c.index + 1) % len(self.clients)]
            rows_for = [
                (reporter.client_id, c.client_id, 1, "preload",
                 now - i * 1e-3)
                for i in range(self.spec.audit_history)]
            if self.stores:
                # replicated deployment: preload EVERY node's copy of
                # the reporter's partition (preloads bypass the op log,
                # so a later promotion must still see them)
                targets = [s.partition_for(reporter.client_id)
                           for s in self.stores.values()]
            elif isinstance(self.store, PartitionedServerStore):
                targets = [self.store.partition_for(reporter.client_id)]
            else:
                targets = [self.store]
            for store in targets:
                _, rows = groups.setdefault(id(store), (store, []))
                rows.extend(rows_for)
        for store, rows in groups.values():
            with getattr(store, "_direct_lock"):
                store._db.executemany(
                    "INSERT INTO audit_reports (reporter, peer, passed,"
                    " detail, timestamp) VALUES (?, ?, ?, ?, ?)", rows)
                store._db.commit()

    async def teardown(self) -> None:
        await self.stalls.stop()

        async def close_all(worker: _Worker) -> None:
            await asyncio.gather(*(c.close() for c in worker.clients),
                                 return_exceptions=True)

        for w in self.workers:
            with contextlib.suppress(Exception):
                await asyncio.wait_for(w.submit(close_all(w)), 30)
            w.stop()
        for srv in (self.servers or
                    ([self.server] if self.server is not None else [])):
            await srv.stop()
        if self.spec.nodes > 1:
            # injected stores: the servers don't own them, close here
            # (idempotent — a permakilled node's store is already closed)
            for store in (self.stores.values() if self.stores
                          else [self.store]):
                await asyncio.to_thread(store.close)
        for k, v in self._saved.items():
            setattr(defaults, k, v)

    # --- sampling (server-side gauges, not durability invariants) ----------

    def _sample_once(self) -> None:
        if self.server is None:
            return
        self.samples.append({
            "t": round(time.time() - self.t0, 3),
            "queue_depth": self.server.queue.pending(),
            "connected": self.server.connections.count(),
            "matchmakings": _MATCHMAKINGS.value(),
            "max_stall_s": round(self.stalls.max_stall_s, 4),
        })

    # --- phases ------------------------------------------------------------

    async def _phase_register(self, ph: Phase) -> None:
        """Register/login/WS-connect the whole swarm, bounded per-worker
        concurrency (the aiohttp server accepts, but hundreds of
        simultaneous handshakes still deserve a ceiling)."""

        async def register_all(worker: _Worker) -> None:
            gate = asyncio.Semaphore(6)

            async def one(c: SwarmClient) -> None:
                async with gate:
                    await c.connect()
                    worker.counts["registered"] = \
                        worker.counts.get("registered", 0) + 1

            await asyncio.gather(*(one(c) for c in worker.clients))

        try:
            await asyncio.gather(*(w.submit(register_all(w))
                                   for w in self.workers))
        finally:
            self.facts["registered"] = sum(
                w.counts.get("registered", 0) for w in self.workers)
        # poison the tail clients with failing audit verdicts from enough
        # DISTINCT reporters to trip the matchmaker's audit-block gate
        failers = self.clients[-self.spec.audit_failers:] \
            if self.spec.audit_failers else []
        for failer in failers:
            reporters = [c for c in self.clients if c is not failer][
                :defaults.AUDIT_SERVER_BLOCK_FAILURES]
            for rep in reporters:
                await rep.worker.submit(rep.client.audit_report(
                    failer.client_id, passed=False, detail="swarm poison"))

    async def _drive(self, c: SwarmClient, deadline: float,
                     counts: Dict) -> None:
        """One client's request loop (runs on its worker's loop): a
        seeded mix of matchmaking, snapshot registration, audit verdicts,
        and (for churners) WS drops.  Server-side rejections count as
        errors; the gate allows a small budget (a churned peer can race
        a fulfill)."""
        spec = self.spec
        rng = random.Random(spec.seed * 1000003 + c.index)
        churner = spec.churn_every and c.index % spec.churn_every == 3
        while time.monotonic() < deadline:
            roll = rng.random()
            try:
                if roll < 0.72:
                    await c.client.backup_storage_request(
                        spec.request_bytes, min_peers=spec.min_peers)
                    counts["requests"] += 1
                elif roll < 0.82:
                    await c.client.backup_done(rng.randbytes(32))
                elif roll < 0.92:
                    peer = self.clients[rng.randrange(len(self.clients))]
                    if peer is not c:
                        await c.client.audit_report(
                            peer.client_id, passed=True)
                elif churner:
                    await c.rejoin_ws()
                    counts["churns"] += 1
            except (net_client.ServerError, aiohttp.ClientError,
                    asyncio.TimeoutError, OSError):
                # server rejections, plus the connection errors a node
                # kill inflicts on requests already in flight (dial
                # failures against live fallbacks are absorbed by the
                # client's failover and never surface here)
                counts["errors"] += 1
            # always yield: a zero-think no-op roll must not spin the
            # worker loop and starve its sibling clients
            await asyncio.sleep(rng.uniform(0.0, spec.think_s)
                                if spec.think_s > 0 else 0)

    async def _drive_window(self, duration: float) -> None:
        """Run every client's request loop across all workers for
        ``duration`` seconds, folding the per-worker counters into the
        facts afterwards."""
        deadline = time.monotonic() + duration

        async def drive_all(worker: _Worker) -> None:
            await asyncio.gather(*(self._drive(c, deadline, worker.counts)
                                   for c in worker.clients))

        try:
            await asyncio.gather(*(w.submit(drive_all(w))
                                   for w in self.workers))
        finally:
            for key in ("requests", "errors", "churns"):
                self.facts[key] = sum(w.counts[key] for w in self.workers)

    async def _phase_swarm(self, ph: Phase) -> None:
        duration = ph.duration_s or self.spec.duration_s
        t0 = time.monotonic()
        mm0 = _MATCHMAKINGS.value()
        await self._drive_window(duration)
        elapsed = time.monotonic() - t0
        made = _MATCHMAKINGS.value() - mm0
        self.facts["swarm_elapsed_s"] = round(elapsed, 3)
        self.facts["swarm_matchmakings"] = int(made)
        self.facts["matchmakings_per_s"] = round(made / elapsed, 2)

    async def _phase_nodekill(self, ph: Phase) -> None:
        """Federation churn: stop a non-primary node mid-run (its homed
        clients fail over along their ring order), keep driving, revive
        a fresh server over the SAME shared store on the SAME port,
        re-enable federation, and drive again.  The gates downstream
        assert no matchmaking's durable rows were lost across the kill
        and that matches flow again after the revive."""
        spec = self.spec
        if len(self.servers) < 2:
            raise RuntimeError("nodekill phase requires nodes > 1")
        window = (ph.duration_s or 1.6) / 2
        victim_i = 1
        nid = self.node_ids[victim_i]
        port = self.servers[victim_i].port
        await self.servers[victim_i].stop()
        self.facts["node_kills"] += 1
        await self._drive_window(window)
        store = self.stores.get(nid, self.store)
        revived = CoordinationServer(store=store, shards=spec.shards)
        await revived.start(port=port)
        revived.enable_federation(nid, self.ring, self.peer_urls)
        if self.stores:
            # rejoin with the CURRENT topology, not the static ring view
            # — survivors may have promoted past us during the outage
            # (the operator hands a rejoining node the live owner map)
            for i, owner in self.servers[0].db.owners.items():
                revived.db.set_owner(i, owner)
        self.servers[victim_i] = revived
        mm0 = _MATCHMAKINGS.value()
        await self._drive_window(window)
        self.facts["post_revive_matchmakings"] = int(
            _MATCHMAKINGS.value() - mm0)

    async def _phase_permakill(self, ph: Phase) -> None:
        """The replication gate: permanently kill a partition-owning
        node mid-run — server stopped, store closed, never revived —
        then wait for a ring successor to detect the death and promote
        (replaying its shipped log tail), and drive load against the
        survivors.  Downstream gates assert zero durable matchmaking
        rows were lost even though the only server that ever APPLIED
        those partitions' writes is gone."""
        spec = self.spec
        if not self.stores:
            raise RuntimeError(
                "permakill phase requires per-node replicated stores"
                " (nodes > 1, shared_store=False)")
        # victim: a non-entry node that owns at least one partition (so
        # the kill actually strands state a successor must recover)
        n_parts = len(self.store.parts)
        victim_i = next(
            i for i in range(1, len(self.node_ids))
            if any(self.ring.owner(partition_key(p)) == self.node_ids[i]
                   for p in range(n_parts)))
        nid = self.node_ids[victim_i]
        owned = [p for p in range(n_parts)
                 if self.servers[0].db.owners.get(p) == nid]
        # clock starts at the kill, not after: graceful stop() overlaps
        # the survivors' probe detection, so promotion is often already
        # visible by the time stop() returns
        t0 = time.monotonic()
        await self.servers[victim_i].stop()
        await asyncio.to_thread(self.stores[nid].close)
        self._permakilled.add(nid)
        self.facts["permakills"] += 1
        self.facts["node_kills"] += 1
        # wait for promote-on-death: every partition the victim owned
        # must land on a live node (probe deadline + replay, with slack)
        survivors = [s for i, s in enumerate(self.servers)
                     if i != victim_i]
        deadline = time.monotonic() + max(
            10 * spec.probe_interval_s * defaults.REPL_PROBE_FAILURES,
            5.0)
        while time.monotonic() < deadline:
            owners = {p: next(
                (s.db.owners.get(p) for s in survivors
                 if s.db.owners.get(p) != nid), None) for p in owned}
            if all(o is not None for o in owners.values()):
                break
            await asyncio.sleep(spec.probe_interval_s / 4)
        else:
            raise RuntimeError(
                f"no successor promoted {nid}'s partitions {owned}")
        self.facts["repl_promote_s"] = round(time.monotonic() - t0, 3)
        self.facts["promotions"] += len(owned)
        # propagate the new ownership to every survivor's table so no
        # forward chases the corpse (announce is best-effort; the drive
        # below must not burn its error budget on stale maps)
        final = {p: next(s.db.owners[p] for s in survivors
                         if s.db.owners.get(p) != nid) for p in owned}
        for s in survivors:
            for p, owner in final.items():
                s.db.set_owner(p, owner)
        mm0 = _MATCHMAKINGS.value()
        await self._drive_window(ph.duration_s or 1.2)
        self.facts["post_promote_matchmakings"] = int(
            _MATCHMAKINGS.value() - mm0)

    async def _phase_drain(self, ph: Phase) -> None:
        """Let in-flight fulfills settle, force the write-behind queue
        through a commit (off-loop), and capture the verdict facts."""
        await asyncio.sleep(ph.duration_s or 0.2)
        live_stores = ([s for n, s in self.stores.items()
                        if n not in self._permakilled]
                       if self.stores else [self.store])
        for store in live_stores:
            await asyncio.to_thread(store.flush)
        self.facts["client_matches"] = sum(c.matches for c in self.clients)
        self.facts["max_stall_s"] = round(self.stalls.max_stall_s, 4)
        self.facts["commits_on_loop"] = any(
            threading.get_ident() in s.commit_threads
            for s in live_stores)
        p99 = _REQUEST_SECONDS.quantile(0.99, route="/backups/request")
        self.facts["p99_request_s"] = (
            None if math.isnan(p99) else round(p99, 5))
        self.facts["total_matchmakings"] = int(
            _MATCHMAKINGS.value() - self._mm0)
        self.facts["failovers"] = sum(
            c.client.failovers for c in self.clients)
        if self.spec.nodes > 1:
            self.facts["negotiated_rows"] = await asyncio.to_thread(
                self._count_negotiated_rows)

    def _count_negotiated_rows(self) -> int:
        """Durable matchmaking evidence across every partition: each
        completed matchmaking writes one row per negotiation endpoint,
        so ``rows >= 2 * matchmakings`` iff no completed matchmaking
        lost its records (kill-window orphans can only ADD rows).

        Replicated deployment: each partition is counted ONCE, from its
        CURRENT owner's store — after a permakill that is the promoted
        successor, so the count fails exactly when promotion lost rows
        the dead primary had acked."""
        if self.stores:
            ref = next(s for i, s in enumerate(self.servers)
                       if self.node_ids[i] not in self._permakilled)
            total = 0
            for p_idx in range(len(self.store.parts)):
                owner = ref.db.owners.get(p_idx)
                store = self.stores.get(owner)
                if store is None or owner in self._permakilled:
                    continue  # unrecovered partition counts nothing
                part = store.parts[p_idx]
                with part._direct_lock:
                    total += part._db.execute(
                        "SELECT COUNT(*) FROM peer_backups"
                    ).fetchone()[0]
            return total
        total = 0
        parts = getattr(self.store, "parts", [self.store])
        for p in parts:
            with getattr(p, "_direct_lock"):
                total += p._db.execute(
                    "SELECT COUNT(*) FROM peer_backups").fetchone()[0]
        return total

    # --- gates -------------------------------------------------------------

    def _assertions(self, error, counters) -> List[sc.Assertion]:
        spec, facts = self.spec, self.facts
        A = sc.Assertion
        out = [A("phases_completed", error is None,
                 "" if error is None else f"{error[0]}: {error[1]}")]
        out.append(A("swarm_registered",
                     facts["registered"] == spec.clients,
                     f"{facts['registered']}/{spec.clients} clients"))
        made = counters.get("bkw_matchmakings_total", 0)
        out.append(A("matchmaking_flowing",
                     made > 0 and facts["client_matches"] > 0,
                     f"matchmakings={made:g}"
                     f" pushed={facts['client_matches']}"))
        budget = max(0.05 * max(facts["requests"], 1), 3)
        out.append(A("error_budget", facts["errors"] <= budget,
                     f"{facts['errors']} errors /"
                     f" {facts['requests']} requests"))
        out.append(A("request_p99_measured",
                     facts["p99_request_s"] is not None,
                     f"p99={facts['p99_request_s']}"))
        if not spec.legacy:
            # the tentpole's two hard gates: the loop never blocks past
            # budget, and no sqlite commit ever ran on the loop thread
            out.append(A("loop_stall_under_budget",
                         facts["max_stall_s"] is not None
                         and facts["max_stall_s"] <= spec.stall_budget_s,
                         f"max_stall={facts['max_stall_s']}s"
                         f" budget={spec.stall_budget_s}s"))
            out.append(A("commits_off_event_loop",
                         facts["commits_on_loop"] is False,
                         "no commit on the event-loop thread"))
            reaps = self.server.queue.reap_ops()
            out.append(A("deadline_heap_live", reaps >= 0,
                         f"reap_ops={reaps}"))
        if spec.nodes > 1:
            # federation gates: clients actually exercised failover,
            # every completed matchmaking kept both durable rows across
            # the kill/revive, matches flowed again after the revive,
            # and the per-route p99 stayed bounded through the churn
            out.append(A("federation_failover_exercised",
                         facts["node_kills"] == 0
                         or facts["failovers"] >= 1,
                         f"failovers={facts['failovers']}"))
            rows, mm = facts["negotiated_rows"], facts["total_matchmakings"]
            out.append(A("federation_no_lost_matchmakings",
                         rows is not None and rows >= 2 * mm,
                         f"negotiated_rows={rows}"
                         f" matchmakings={mm} (need >= {2 * mm})"))
            out.append(A("federation_post_revive_flow",
                         facts["node_kills"] <= facts["permakills"]
                         or (facts["post_revive_matchmakings"] or 0) > 0,
                         "post_revive_matchmakings="
                         f"{facts['post_revive_matchmakings']}"))
            out.append(A("federation_p99_bounded",
                         facts["p99_request_s"] is not None
                         and facts["p99_request_s"] <= spec.p99_budget_s,
                         f"p99={facts['p99_request_s']}s"
                         f" budget={spec.p99_budget_s}s"))
        if facts["permakills"]:
            # replication gates: a successor actually promoted the dead
            # node's partitions (within the probe deadline — the phase
            # raises on timeout, this records how fast), and matches
            # flowed against the survivors afterwards.  Row durability
            # across the permakill is federation_no_lost_matchmakings
            # above, now counted against per-node stores.
            out.append(A("replication_promoted",
                         facts["promotions"] >= 1
                         and facts["repl_promote_s"] is not None,
                         f"promotions={facts['promotions']}"
                         f" in {facts['repl_promote_s']}s"))
            out.append(A("replication_post_promote_flow",
                         (facts["post_promote_matchmakings"] or 0) > 0,
                         "post_promote_matchmakings="
                         f"{facts['post_promote_matchmakings']}"))
            # the permakill must not register as a durability event on
            # any honest client — the promoted successor's replayed
            # state is indistinguishable from the dead primary's
            violation_s = sum(
                v for k, v in counters.items()
                if k.startswith("bkw_durability_violation_seconds_total"))
            out.append(A("replication_durability_invariant",
                         violation_s == 0,
                         f"violation_seconds={violation_s:g}"))
        return out


async def run_swarm(spec: SwarmSpec, workdir) -> Tuple[sc.Scorecard, Dict]:
    """setup -> run -> teardown, returning the scorecard plus the flat
    summary (matchmakings/s, p99, stall, commit mode counts)."""
    harness = SwarmHarness(spec, Path(workdir))
    await harness.setup()
    try:
        card = await harness.run()
    finally:
        await harness.teardown()
    return card, summarize(spec, card, harness.facts)


def summarize(spec: SwarmSpec, card: sc.Scorecard, facts: Dict) -> Dict:
    commits = {
        mode: card.counters.get(
            f"bkw_server_store_commits_total{{mode={mode}}}", 0)
        for mode in ("group", "direct")}
    p99 = facts.get("p99_request_s")
    fed = {} if spec.nodes <= 1 else {
        "nodes": spec.nodes,
        "shared_store": spec.shared_store,
        "node_kills": facts.get("node_kills"),
        "failovers": facts.get("failovers"),
        "post_revive_matchmakings": facts.get("post_revive_matchmakings"),
        "total_matchmakings": facts.get("total_matchmakings"),
        "negotiated_rows": facts.get("negotiated_rows"),
        "permakills": facts.get("permakills"),
        "promotions": facts.get("promotions"),
        "repl_promote_s": facts.get("repl_promote_s"),
        "post_promote_matchmakings": facts.get("post_promote_matchmakings"),
    }
    return {
        "tier": "legacy" if spec.legacy else "sharded",
        "clients": spec.clients,
        **fed,
        "duration_s": facts.get("swarm_elapsed_s"),
        "matchmakings": facts.get("swarm_matchmakings"),
        "matchmakings_per_s": facts.get("matchmakings_per_s"),
        "server_p99_ms": None if p99 is None else round(p99 * 1e3, 3),
        "max_stall_ms": None if facts.get("max_stall_s") is None
        else round(facts["max_stall_s"] * 1e3, 2),
        "commits_on_loop": facts.get("commits_on_loop"),
        "requests": facts.get("requests"),
        "errors": facts.get("errors"),
        "commits": commits,
        "passed": card.passed,
    }


# --- direct matchmaking-layer load (tests/test_swarm.py's legs) ------------
#
# The HTTP swarm above proves the end-to-end properties (p99, stall
# budget, commits off the loop), but on a single-core box the identical
# per-request HTTP/auth/python cost dominates both tiers and flattens
# the matchmaking-layer difference.  The speedup legs therefore drive
# the matchmaker + store pair DIRECTLY — same real file-backed sqlite,
# same fsync discipline, same audit-history weight per candidate scan —
# with time-boxed client coroutines that yield at each request boundary
# exactly like the aiohttp handlers do.  Time-boxing (not fixed rounds)
# keeps the pairing supply saturated in both legs, so matchmakings/s
# measures matchmaker capacity rather than driver shape.


@dataclass(frozen=True)
class MatchLoadSpec:
    """One time-boxed matchmaking-layer load leg."""

    clients: int = 128
    duration_s: float = 2.5
    legacy: bool = False
    shards: Optional[int] = None
    request_bytes: int = 1 << 20
    #: passing audit reports preloaded (total) so every candidate scan
    #: reads a realistically deep verdict window
    audit_history: int = 800
    expiry_s: float = 60.0


class _AlwaysOnline:
    """Connection-registry stub for the direct legs: every client is
    online and every notify lands after one loop yield (the shape of a
    loopback WS push without the socket)."""

    def is_online(self, client_id) -> bool:
        return True

    async def notify(self, client_id, msg) -> bool:
        await asyncio.sleep(0)
        return True


def _bulk_audit_history(store, pubkeys: List[bytes], rows: int) -> None:
    """Setup-time bulk insert of passing verdicts, ring-wise reporters,
    directly on the store's connection (pre-measurement)."""
    now = time.time()
    payload = []
    for i in range(rows):
        peer = pubkeys[i % len(pubkeys)]
        reporter = pubkeys[(i + 1) % len(pubkeys)]
        payload.append((reporter, peer, 1, "preload", now - i * 1e-3))
    with store._direct_lock:
        store._db.executemany(
            "INSERT INTO audit_reports (reporter, peer, passed, detail,"
            " timestamp) VALUES (?, ?, ?, ?, ?)", payload)
        store._db.commit()


async def _match_load(spec: MatchLoadSpec, db_path: str) -> Dict:
    from ..net.server import StorageQueue
    from ..net.serverstore import ServerDB, SqliteServerStore
    pubkeys = [i.to_bytes(8, "big") + bytes(24)
               for i in range(1, spec.clients + 1)]
    if spec.legacy:
        store = ServerDB(db_path)
        queue = StorageQueue(store, _AlwaysOnline(), expiry_s=spec.expiry_s)
    else:
        store = SqliteServerStore(db_path)
        queue = ShardedMatchmaker(store, _AlwaysOnline(),
                                  expiry_s=spec.expiry_s,
                                  shards=spec.shards)
    try:
        if spec.audit_history:
            _bulk_audit_history(store, pubkeys, spec.audit_history)
        fulfills = [0]

        async def drive(pk: bytes, deadline: float) -> None:
            while time.monotonic() < deadline:
                await queue.fulfill(pk, spec.request_bytes)
                fulfills[0] += 1
                # request boundary: yield exactly once, like a handler
                # returning to the loop between requests
                await asyncio.sleep(0)

        mm0 = _MATCHMAKINGS.value()
        t0 = time.monotonic()
        deadline = t0 + spec.duration_s
        await asyncio.gather(*(drive(pk, deadline) for pk in pubkeys))
        elapsed = time.monotonic() - t0
        made = _MATCHMAKINGS.value() - mm0
    finally:
        store.close()
    return {
        "tier": "legacy" if spec.legacy else "sharded",
        "clients": spec.clients,
        "duration_s": round(elapsed, 3),
        "fulfills": fulfills[0],
        "matchmakings": int(made),
        "matchmakings_per_s": round(made / elapsed, 2),
        "fulfills_per_s": round(fulfills[0] / elapsed, 2),
    }


def run_match_load(spec: MatchLoadSpec, workdir) -> Dict:
    """Run one leg in a fresh event loop against a file-backed store
    under ``workdir``; returns the flat leg record."""
    db_path = str(Path(workdir) / f"match_{spec.legacy and 'legacy' or 'sharded'}.db")
    return asyncio.run(_match_load(spec, db_path))


def builtin_swarms() -> Dict[str, SwarmSpec]:
    """``swarm`` is the tier-1 acceptance run (≈32 clients, a few
    seconds on loopback); ``swarm_full`` is the slow-tier load shape."""
    P = Phase
    return {
        "swarm": SwarmSpec(
            name="swarm", seed=101, clients=32,
            phases=(P("register"), P("swarm", duration_s=2.0),
                    P("drain"))),
        "swarm_full": SwarmSpec(
            name="swarm_full", seed=111, clients=192, think_s=0.02,
            phases=(P("register"), P("swarm", duration_s=6.0),
                    P("drain"))),
        # federation acceptance: 3 nodes over one SHARED partitioned
        # store (the explicit opt-in baseline leg — row survival across
        # a kill is by construction), node kill + same-port revive
        # mid-run; tier-1 sized.  WS churn is off — the nodekill phase
        # IS the churn under test
        "federation": SwarmSpec(
            name="federation", seed=202, clients=12, workers=4, nodes=3,
            churn_every=0, think_s=0.005, shared_store=True,
            phases=(P("register"), P("swarm", duration_s=1.2),
                    P("nodekill", duration_s=1.6), P("drain"))),
        # slow-tier soak: more nodes, more clients, a second full swarm
        # window after the revive so steady-state federation throughput
        # is measured post-churn
        "federation_soak": SwarmSpec(
            name="federation_soak", seed=212, clients=48, nodes=4,
            churn_every=0, think_s=0.02, shared_store=True,
            phases=(P("register"), P("swarm", duration_s=4.0),
                    P("nodekill", duration_s=4.0),
                    P("swarm", duration_s=3.0), P("drain"))),
        # replication acceptance (docs/server.md §Replication): 3 nodes
        # with PER-NODE replicated stores and a mid-run PERMAKILL — one
        # node dies forever, a ring successor must promote within the
        # probe deadline and serve its partitions with zero lost
        # matchmaking rows; tier-1 sized
        # load is deliberately gentler than the federation baseline:
        # every foreign-partition write is a real forward hop and every
        # owned write a real ship hop, all sharing one CPU in CI — the
        # gates probe correctness across the permakill, not throughput
        "replication": SwarmSpec(
            name="replication", seed=303, clients=8, workers=4, nodes=3,
            churn_every=0, think_s=0.05, p99_budget_s=8.0,
            phases=(P("register"), P("swarm", duration_s=1.2),
                    P("permakill", duration_s=1.5), P("drain"))),
        # slow-tier soak: longer chains (4 nodes, REPL_SUCCESSORS=2
        # leaves a spare successor after the kill), heavier load, and a
        # second swarm window in the promoted steady state
        # the soak stresses DURATION (a promoted successor keeps serving
        # through two more load windows), not raw client concurrency —
        # 16 clients over 4 nodes is already past what one core serves
        # without queueing, and queueing is not what this gate measures.
        # The p99 budget is a LIVENESS bound, not a latency SLO: with
        # ~200 requests the 99th percentile lands on the one or two
        # requests whose forwards straddled the permakill and paid
        # REPL_FORWARD_TIMEOUT_S (possibly twice — fulfill issues
        # several store ops) before the promoted owner took over
        "replication_soak": SwarmSpec(
            name="replication_soak", seed=313, clients=12, nodes=4,
            churn_every=0, think_s=0.08, p99_budget_s=45.0,
            phases=(P("register"), P("swarm", duration_s=3.0),
                    P("permakill", duration_s=3.0),
                    P("swarm", duration_s=2.0), P("drain"))),
    }
