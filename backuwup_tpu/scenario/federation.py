"""Federation load plane: N coordination nodes as real OS processes.

The swarm harness (``scenario/swarm.py``) proves the federation's
end-to-end properties — failover, zero lost matchmakings across a node
kill, bounded p99 — but its nodes share one event loop, so it cannot
show THROUGHPUT scaling.  The multiprocess legs of
``tests/test_federation.py`` need nodes that genuinely run in parallel:
this module spawns each node as its own OS process with its own
ServerStore partition file, its own consistent-hash ring copy, and real
``/fed/steal`` HTTP between them.

Deployment per node process
===========================

* a :class:`~..net.serverstore.SqliteServerStore` at
  ``<workdir>/node<i>.db`` — per-node partition files keep sqlite WAL
  writers process-local (cross-process WAL sharing would serialize the
  very commits the scaling legs measure),
* a :class:`~..net.server.CoordinationServer` with its connection
  registry stubbed always-online (the drivers call ``queue.fulfill``
  directly, exactly like ``_match_load`` — the thing under test is the
  matchmaker + federation RPC, not the HTTP/auth envelope),
* ``enable_federation`` over the full ring, so a node whose local
  shards drain steals work from its ring successors over real sockets.

Synchronization is file-based and two-stage: every child polls every
peer's ``/healthz`` (proves all sockets are up), drops a ``ready_<i>``
marker, then waits for the parent's ``go.json`` carrying a shared
``t0``/``deadline`` — all nodes measure the same wall-clock window, so
the parent may sum matchmakings and divide by the longest elapsed.
"""

from __future__ import annotations

import asyncio
import json
import os
import socket
import subprocess
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import aiohttp


@dataclass(frozen=True)
class FederationLoadSpec:
    """One multi-process federation throughput leg."""

    nodes: int = 2
    #: pubkey universe shared by all nodes; each node drives the subset
    #: the ring homes on it
    clients: int = 64
    duration_s: float = 2.0
    request_bytes: int = 1 << 20
    shards: Optional[int] = None
    #: ceiling for child startup (interpreter + imports + bind + the
    #: healthz barrier) — generous because the children import the full
    #: package cold
    startup_timeout_s: float = 90.0


def _free_ports(n: int) -> List[int]:
    """Reserve n distinct loopback ports (bind-then-close; the tiny
    rebind race is acceptable for a load leg on loopback)."""
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


class _FedOnline:
    """Always-online connection registry for the load nodes: every
    notify lands after one loop yield.  ``enable_federation`` installs
    its relay hook here, but local notifies never fail so the relay is
    exercised only via remote-steal pushes."""

    def __init__(self):
        self.relay = None

    def count(self) -> int:
        return 0

    def is_online(self, client_id) -> bool:
        return True

    async def notify_local(self, client_id, msg) -> bool:
        await asyncio.sleep(0)
        return True

    async def notify(self, client_id, msg) -> bool:
        await asyncio.sleep(0)
        return True


def _universe(clients: int) -> List[bytes]:
    return [i.to_bytes(8, "big") + bytes(24)
            for i in range(1, clients + 1)]


async def _node_main(cfg: Dict) -> Dict:
    from ..net.matchmaking import _MATCHMAKINGS
    from ..net.ring import HashRing
    from ..net.server import (_FED_STEAL_SERVED, _FED_STEALS,
                              CoordinationServer)
    from ..net.serverstore import SqliteServerStore

    idx = cfg["node_index"]
    node_ids = [f"node{i}" for i in range(cfg["nodes"])]
    nid = node_ids[idx]
    ring = HashRing(node_ids)
    workdir = Path(cfg["workdir"])
    store = await asyncio.to_thread(
        SqliteServerStore, str(workdir / f"{nid}.db"))
    server = CoordinationServer(store=store, shards=cfg["shards"])
    online = _FedOnline()
    # stub BEFORE enable_federation so the relay hook lands on the stub
    server.connections = online
    server.queue.connections = online
    await server.start(port=cfg["ports"][idx])
    peers = {n: f"http://127.0.0.1:{p}"
             for n, p in zip(node_ids, cfg["ports"])}
    server.enable_federation(nid, ring, peers)

    # barrier 1: every peer's socket answers /healthz
    async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=1)) as sess:
        for url in peers.values():
            while True:
                try:
                    async with sess.get(url + "/healthz") as resp:
                        if resp.status == 200:
                            break
                except (aiohttp.ClientError, asyncio.TimeoutError,
                        OSError):
                    pass
                await asyncio.sleep(0.05)
    await asyncio.to_thread(
        (workdir / f"ready_{idx}").write_text, "ok")

    # barrier 2: the parent's go file carries the shared window
    go_path = workdir / "go.json"
    while not go_path.exists():
        await asyncio.sleep(0.02)
    go = json.loads(await asyncio.to_thread(go_path.read_text))

    mine = [pk for pk in _universe(cfg["clients"])
            if ring.owner(pk) == nid]
    fulfills = [0]
    deadline = go["deadline"]

    async def drive(pk: bytes) -> None:
        while time.time() < deadline:
            await server.queue.fulfill(pk, cfg["request_bytes"])
            fulfills[0] += 1
            await asyncio.sleep(0)

    await asyncio.sleep(max(0.0, go["t0"] - time.time()))
    mm0 = _MATCHMAKINGS.value()
    t0 = time.time()
    await asyncio.gather(*(drive(pk) for pk in mine))
    elapsed = time.time() - t0
    made = _MATCHMAKINGS.value() - mm0
    steals = {o: _FED_STEALS.value(outcome=o)
              for o in ("hit", "miss", "error")}
    served = {o: _FED_STEAL_SERVED.value(outcome=o)
              for o in ("hit", "empty")}
    await server.stop()
    await asyncio.to_thread(store.close)
    return {
        "node": nid,
        "owned_clients": len(mine),
        "elapsed_s": round(elapsed, 3),
        "fulfills": fulfills[0],
        "matchmakings": int(made),
        "steals": steals,
        "steals_served": served,
    }


def _child_main(argv: List[str]) -> int:
    cfg = json.loads(Path(argv[1]).read_text())
    out = asyncio.run(_node_main(cfg))
    (Path(cfg["workdir"]) / f"result_{cfg['node_index']}.json").write_text(
        json.dumps(out))
    return 0


def _tail(path: Path, n: int = 12) -> str:
    try:
        return "\n".join(path.read_text(errors="replace").splitlines()[-n:])
    except OSError:
        return "<no log>"


def run_federation_load(spec: FederationLoadSpec, workdir) -> Dict:
    """Spawn the node processes, coordinate the shared measurement
    window, and aggregate.  Raises if any node dies or misses the
    startup ceiling (with its log tail — a load leg must fail loudly,
    not report a partial fleet as a throughput number)."""
    workdir = Path(workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    ports = _free_ports(spec.nodes)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    procs: List[subprocess.Popen] = []
    logs: List[Path] = []
    try:
        for i in range(spec.nodes):
            cfg_path = workdir / f"node_{i}.json"
            cfg_path.write_text(json.dumps({
                "node_index": i, "nodes": spec.nodes,
                "ports": ports, "workdir": str(workdir),
                "clients": spec.clients,
                "request_bytes": spec.request_bytes,
                "shards": spec.shards,
            }))
            log_path = workdir / f"node_{i}.log"
            logs.append(log_path)
            with log_path.open("wb") as lf:
                procs.append(subprocess.Popen(
                    [sys.executable, "-m",
                     "backuwup_tpu.scenario.federation", str(cfg_path)],
                    stdout=lf, stderr=subprocess.STDOUT, env=env))
        t_stop = time.monotonic() + spec.startup_timeout_s
        while not all((workdir / f"ready_{i}").exists()
                      for i in range(spec.nodes)):
            for i, p in enumerate(procs):
                if p.poll() is not None:
                    raise RuntimeError(
                        f"federation node {i} died during startup "
                        f"(rc={p.returncode}):\n{_tail(logs[i])}")
            if time.monotonic() > t_stop:
                raise RuntimeError(
                    "federation nodes missed the startup ceiling "
                    f"({spec.startup_timeout_s}s):\n{_tail(logs[0])}")
            time.sleep(0.05)
        t0 = time.time() + 0.5
        (workdir / "go.json").write_text(json.dumps(
            {"t0": t0, "deadline": t0 + spec.duration_s}))
        results = []
        for i, p in enumerate(procs):
            rc = p.wait(timeout=spec.duration_s + spec.startup_timeout_s)
            if rc != 0:
                raise RuntimeError(
                    f"federation node {i} failed (rc={rc}):\n"
                    f"{_tail(logs[i])}")
            results.append(json.loads(
                (workdir / f"result_{i}.json").read_text()))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
    made = sum(r["matchmakings"] for r in results)
    elapsed = max(r["elapsed_s"] for r in results)
    steals = {o: sum(r["steals"][o] for r in results)
              for o in ("hit", "miss", "error")}
    return {
        "nodes": spec.nodes,
        "clients": spec.clients,
        "duration_s": round(elapsed, 3),
        "matchmakings": made,
        "matchmakings_per_s": round(made / elapsed, 2) if elapsed else 0.0,
        "fulfills": sum(r["fulfills"] for r in results),
        "steals": steals,
        "per_node": results,
    }


if __name__ == "__main__":
    sys.exit(_child_main(sys.argv))
