"""Composed chaos scenario harness (docs/scenarios.md).

The chaos e2e tests each exercise one subsystem; the ROADMAP
scenario-matrix item asks for their composition.  This harness runs a
full loopback deployment — one CoordinationServer, one source client,
N storage holders, and spare peers, all in-process — and drives it
through a scripted sequence of timed phases:

=============  ============================================================
``backup``     full backup of the (optionally grown) corpus; every
               packfile placed as an RS(k+m) stripe on distinct holders
``steady``     idle wall time: the invariant sampler keeps sweeping and
               steady state must stay clean
``churn``      a backup racing sustained peer churn: holders are killed
               and revived through the fault plane every ``interval_s``
               while the transfer plane retries around them
``byzantine``  holders' stored shard bytes are flipped; one audit round
               catches the bad proofs and demotes them
``kill``       unrepaired peer loss: a holder goes permanently dark and
               is audit-demoted via consecutive misses — durability
               must flip to degraded within one monitor sweep
``repair``     one ``engine.repair_round()``: sourceless shard rebuild
               onto spare peers
``race``       backup + restore + repair all fired concurrently on the
               one client; losers of the exclusivity lock spin on
               EngineError until everything completes
``restore``    restore to a fresh directory and verify byte-for-byte
               against the source tree digest
``restore_hedged``  a restore with one measured-fast holder stalled:
               every frame toward it sleeps past the hedge deadline, so
               the download lanes must race redundant shards from the
               spare holders and win
               (``bkw_restore_hedges_total{outcome=won}``)
``wan``        WAN-grade transfer conditions: chunked sends with armed
               mid-transfer cuts that force byte-range resumes, peer
               stats seeded so capacity-aware placement avoids the
               placement-demoted slow holder, and probation recovery
``crash``      the crash matrix: for each armed commit seam the source
               client's backup dies at that exact instruction
               (:func:`~backuwup_tpu.utils.faults.crashpoint`), the
               client is restarted in-process (every in-memory structure
               discarded, directories re-opened) so the startup recovery
               sweep reconciles, then a re-run backup must complete and
               a second ``recover()`` must reconcile zero items
``gc``         snapshot lifecycle: mutate the corpus so a retention
               prune (keep-last:1) creates dead blobs, back up, then
               collect.  With ``sites``, per armed GC seam the
               ``run_gc`` dies mid-commit, the client restarts, and the
               re-run + recovery must converge (same crash-facts shape
               as ``crash``, so the ``recovery_clean`` gate applies);
               without sites, GC races a concurrent backup + restore on
               the exclusivity lock while still reclaiming bytes
=============  ============================================================

Everything is seeded (fault plane, corpus bytes, victim choice), so a
scenario is deterministic enough for a tier-1 test; a background sampler
sweeps :class:`~backuwup_tpu.obs.invariants.InvariantMonitor`
continuously and the run ends in a :class:`~.scorecard.Scorecard` built
from registry deltas with hard pass/fail assertions.
"""

from __future__ import annotations

import asyncio
import contextlib
import hashlib
import random
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

from .. import defaults
from ..app import ClientApp
from ..engine import EngineError
from ..net.server import CoordinationServer
from ..obs import diagnose as obs_diagnose
from ..obs import invariants as obs_invariants
from ..obs import journal as obs_journal
from ..obs import metrics as obs_metrics
from ..obs import slo as obs_slo
from ..obs.series import SeriesRecorder
from ..ops.backend import ChunkerBackend, CpuBackend
from ..net.peer_stats import PeerEstimate
from ..ops.gear import CDCParams
from ..store import PeerStatsRow
from ..utils import faults
from . import scorecard as sc


class ScenarioError(Exception):
    pass


@dataclass(frozen=True)
class Phase:
    """One scripted step; ``kind`` selects the behavior table above."""

    kind: str
    duration_s: float = 0.0  # steady/churn wall time
    count: int = 1           # victims for byzantine/kill
    interval_s: float = 0.3  # churn kill/revive cadence
    grow: bool = False       # write fresh corpus files first
    sites: tuple = ()        # crash: commit seams (() = _CRASH_MATRIX)
    name: str = ""

    @property
    def label(self) -> str:
        return self.name or self.kind


@dataclass(frozen=True)
class ScenarioSpec:
    name: str
    phases: tuple
    seed: int = 1234
    holders: int = 6
    spares: int = 1
    corpus_files: int = 6
    corpus_file_bytes: int = 24 * 1024
    packfile_target: int = 64 * 1024
    chunk_desired: int = 4096
    #: 0 keeps defaults.TRANSFER_CHUNK_BYTES (1 MiB — every loopback
    #: payload rides the legacy single-frame path); the wan scenario
    #: shrinks it so shards span several FILE_PART frames
    chunk_bytes: int = 0
    sample_interval_s: float = 0.1
    expect_violation: bool = False
    expect_final_status: str = "ok"
    min_shards_rebuilt: int = 0
    #: opt into the live SLO plane: a journal at the workdir, series
    #: sampling + burn-rate evaluation riding the invariant sampler, a
    #: diagnosis report on breach, and the slo_* gates
    slo: bool = False
    #: catalog subset to evaluate — loopback runs keep the objectives
    #: whose healthy baseline is provably quiet (overlap efficiency on a
    #: tiny synthetic corpus is not)
    slo_objectives: tuple = ("durability", "transfer_stalls",
                             "backup_p99", "restore_p99")
    #: multi-window pairs shrunk onto loopback seconds
    slo_windows: tuple = ((1.0, 3.0), (6.0, 18.0))


#: The sender-side commit seams a scenario backup crosses, i.e. the
#: default crash matrix (`docs/crash_consistency.md`).  The receiver-side
#: seam (``partial.sink.*``) and the repair re-home seam
#: (``repair.rehome.*``) fire in code paths a plain backup never enters;
#: tests/test_crash.py covers those with targeted unit recoveries.
_CRASH_MATRIX = (
    "pack.seal.pre", "pack.seal.post",
    "challenge.save.pre", "challenge.save.post",
    "index.save.pre", "index.save.post",
    "placement.insert.pre", "placement.insert.post",
    "stripe.finish.pre", "stripe.finish.post",
)


def _crash_count(ph: Phase) -> int:
    return len(ph.sites or _CRASH_MATRIX)


#: defaults shrunk for loopback scenarios; saved/restored around a run.
_PATCH = {
    "ACK_TIMEOUT_S": 1.5,
    "RESTORE_REQUEST_THROTTLE_S": 0.0,
    "AUDIT_SERVE_MIN_INTERVAL_S": 0.0,
    "PEER_WAIT_BASE_S": 0.05,
    "PEER_WAIT_CAP_S": 0.25,
    "DIAL_RETRY_ATTEMPTS": 1,
    "DIAL_RETRY_BASE_S": 0.05,
    "DIAL_RETRY_CAP_S": 0.2,
    "DURABILITY_SWEEP_INTERVAL_S": 0.5,
    "RECLAIM_MIN_INTERVAL_S": 0.0,
}


def _tree_digest(root: Path) -> Dict[str, str]:
    out = {}
    for p in sorted(root.rglob("*")):
        if p.is_file():
            out[str(p.relative_to(root))] = hashlib.sha256(
                p.read_bytes()).hexdigest()
    return out


class ScenarioHarness:
    """Owns the deployment, the fault plane, and the invariant sampler
    for one scenario run.  Use :func:`run_scenario` unless a test needs
    to poke mid-run state (the healthz-flip test does)."""

    def __init__(self, spec: ScenarioSpec, workdir: Path,
                 backend: Optional[ChunkerBackend] = None):
        self.spec = spec
        self.workdir = Path(workdir)
        self.backend = backend
        self.rng = random.Random(spec.seed)
        self.src = self.workdir / "src"
        self.samples: List[dict] = []
        self.facts: Dict = {"backups": 0, "restores": 0, "repairs": 0,
                            "demoted": [], "restore_verified": None,
                            "source_digest": None}
        self.server: Optional[CoordinationServer] = None
        self.a: Optional[ClientApp] = None
        self.holders: List[ClientApp] = []
        self.spares: List[ClientApp] = []
        self.plane: Optional[faults.FaultPlane] = None
        self.monitor = None
        self.server_port: Optional[int] = None
        self.t0 = 0.0
        self._saved: Dict = {}
        self._grown = 0
        self._restores = 0
        self.series: Optional[SeriesRecorder] = None
        self.slo: Optional[obs_slo.SLOMonitor] = None
        self.diagnoses: List[dict] = []
        self._saved_journal = None

    # --- lifecycle ---------------------------------------------------------

    async def setup(self) -> None:
        spec = self.spec
        self._saved = {k: getattr(defaults, k) for k in _PATCH}
        self._saved["PACKFILE_TARGET_SIZE"] = defaults.PACKFILE_TARGET_SIZE
        self._saved["TRANSFER_CHUNK_BYTES"] = defaults.TRANSFER_CHUNK_BYTES
        for k, v in _PATCH.items():
            setattr(defaults, k, v)
        defaults.PACKFILE_TARGET_SIZE = spec.packfile_target
        if spec.chunk_bytes > 0:
            defaults.TRANSFER_CHUNK_BYTES = spec.chunk_bytes
        self.plane = faults.install(faults.FaultPlane(seed=spec.seed))
        if self.backend is None:
            self.backend = CpuBackend(
                CDCParams.from_desired(spec.chunk_desired))
        self._write_corpus("seed")

        self.server = CoordinationServer(
            db_path=str(self.workdir / "server.db"))
        self.server_port = await self.server.start()

        self.a = self._make_app("a")
        self.holders = [self._make_app(f"h{i}")
                        for i in range(spec.holders)]
        self.spares = [self._make_app(f"s{i}")
                       for i in range(spec.spares)]
        for app in self._apps():
            await app.start()
            # the harness drives audits and sweeps; background schedulers
            # would inject nondeterminism
            app._audit_task.cancel()
            app._monitor_task.cancel()
            app._slo_task.cancel()
        self.a.engine.auto_repair = False
        self.monitor = self.a.monitor
        if spec.slo:
            self._saved_journal = obs_journal.get()
            obs_journal.install(obs_journal.Journal(
                self.workdir / "journal.jsonl"))
            catalog = [o for o in obs_slo.parse_catalog()
                       if o.id in spec.slo_objectives]
            families = sorted({o.family for o in catalog}
                              | {o.total_family for o in catalog
                                 if o.total_family})
            self.series = SeriesRecorder(families)
            self.slo = obs_slo.SLOMonitor(
                self.series, catalog=catalog,
                windows=spec.slo_windows,
                on_breach=self._on_breach,
                client=self.a.client_id.hex()[:8])

        # manual negotiation (matchmaking has its own tests); holders get
        # the larger allowance so free-space ordering stripes onto them
        # and spares stay fresh for sourceless repair to re-home onto
        grants = [(h, 32 << 20) for h in self.holders] + \
                 [(s, 8 << 20) for s in self.spares]
        for peer, amount in grants:
            self.a.store.add_peer_negotiated(peer.client_id, amount)
            peer.store.add_peer_negotiated(self.a.client_id, amount)
            self.server.db.save_storage_negotiated(
                bytes(self.a.client_id), bytes(peer.client_id), amount)

    def _make_app(self, name: str) -> ClientApp:
        app = ClientApp(config_dir=self.workdir / name / "cfg",
                        data_dir=self.workdir / name / "data",
                        server_addr=f"127.0.0.1:{self.server_port}",
                        backend=self.backend,
                        tls=False)  # plaintext loopback deployment
        app.store.set_backup_path(str(self.src))
        return app

    async def teardown(self) -> None:
        for app in self._apps():
            try:
                await app.stop()
            except Exception:
                pass
        if self.server is not None:
            await self.server.stop()
        faults.uninstall()
        if self.spec.slo:
            obs_journal.uninstall()
            if self._saved_journal is not None:
                obs_journal.install(self._saved_journal)
        for k, v in self._saved.items():
            setattr(defaults, k, v)

    def _apps(self) -> List[ClientApp]:
        return [self.a] + self.holders + self.spares if self.a else []

    # --- the run -----------------------------------------------------------

    async def run(self) -> sc.Scorecard:
        before = obs_metrics.registry().snapshot()
        self.t0 = time.time()
        sampler = asyncio.create_task(self._sampler())
        error: Optional[tuple] = None
        executed: List[str] = []
        try:
            for phase in self.spec.phases:
                executed.append(phase.label)
                try:
                    await self._run_phase(phase)
                except Exception as e:
                    error = (phase.label, repr(e)[:300])
                    break
        finally:
            sampler.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await sampler
        self._sample_once()  # authoritative final sweep
        after = obs_metrics.registry().snapshot()
        assertions = self._assertions(
            error, sc.counter_deltas(before, after))
        return sc.build_scorecard(self.spec.name, self.spec.seed,
                                  time.time() - self.t0, executed,
                                  before, after, self.samples, assertions)

    async def _run_phase(self, ph: Phase) -> None:
        fn = getattr(self, f"_phase_{ph.kind}", None)
        if fn is None:
            raise ScenarioError(f"unknown phase kind {ph.kind!r}")
        await fn(ph)

    # --- invariant sampling ------------------------------------------------

    def _on_breach(self, breach) -> None:
        """SLO breach hook: diagnose against the run's journal + series
        history, keep the report for the gates."""
        self.facts.setdefault("slo_breaches", []).append({
            "objective": breach.objective, "status": breach.status,
            "t": round(time.time() - self.t0, 3)})
        report = obs_diagnose.explain(breach, recorder=self.series)
        self.diagnoses.append(report)

    def _sample_once(self) -> None:
        if self.monitor is None:  # crash-phase restart window: no live client
            return
        rep = self.monitor.sweep()
        self.samples.append({
            "t": round(time.time() - self.t0, 3),
            "status": rep.status,
            "status_level": obs_invariants._STATUS_LEVEL[rep.status],
            "stripes_total": rep.stripes_total,
            "stripes_degraded": rep.stripes_degraded,
            "stripes_lost": rep.stripes_lost,
            "unrestorable": rep.packfiles_unrestorable,
            "repair_debt_bytes": rep.repair_debt_bytes,
            "orphaned_placements": rep.orphaned_placements,
        })
        if self.slo is not None:
            # the SLO plane rides the invariant sampler's cadence: every
            # evaluation judges the sweep that just published
            self.series.sample()
            self.slo.evaluate()

    async def _sampler(self) -> None:
        while True:
            self._sample_once()
            await asyncio.sleep(self.spec.sample_interval_s)

    # --- corpus ------------------------------------------------------------

    def _write_corpus(self, tag: str) -> None:
        self.src.mkdir(parents=True, exist_ok=True)
        for i in range(self.spec.corpus_files):
            sub = self.src / f"d{i % 2}"
            sub.mkdir(exist_ok=True)
            size = self.spec.corpus_file_bytes + self.rng.randrange(4096)
            (sub / f"{tag}_{i}.bin").write_bytes(self.rng.randbytes(size))

    def _grow(self) -> None:
        self._grown += 1
        self._write_corpus(f"grow{self._grown}")

    def _mutate_corpus(self) -> None:
        """Rewrite every other corpus file in place.  The old contents
        then live only in pre-mutation snapshots, so a retention prune
        turns them into dead blobs — GC's raw material."""
        files = sorted(p for p in self.src.rglob("*.bin") if p.is_file())
        for p in files[::2]:
            p.write_bytes(self.rng.randbytes(p.stat().st_size))

    async def _retry_busy(self, op, pause: float = 0.05):
        """Spin on the engine exclusivity lock — the race phase's whole
        point is that concurrent ops are rejected, counted
        (bkw_engine_busy_rejections_total), and succeed on retry."""
        while True:
            try:
                return await op()
            except EngineError as e:
                if "already running" not in str(e):
                    raise
                await asyncio.sleep(pause)

    def _alive_holders(self) -> List[ClientApp]:
        return [h for h in self.holders
                if not self.plane.is_dead(h.client_id)
                and not self.a.store.get_audit_state(h.client_id).demoted]

    # --- phases ------------------------------------------------------------

    async def _phase_backup(self, ph: Phase) -> None:
        if ph.grow:
            self._grow()
        snapshot = await asyncio.wait_for(self.a.backup(), 180)
        if not snapshot:
            raise ScenarioError("backup returned no snapshot")
        self.facts["backups"] += 1
        self.facts["source_digest"] = _tree_digest(self.src)

    async def _phase_steady(self, ph: Phase) -> None:
        await asyncio.sleep(ph.duration_s)

    async def _phase_churn(self, ph: Phase) -> None:
        """A backup forced to make progress through sustained peer churn:
        one holder is down at any moment, the victim rotating every
        ``interval_s``; the transfer plane must retry around the hole."""
        if ph.grow:
            self._grow()
        backup = asyncio.create_task(self.a.backup())
        deadline = time.time() + ph.duration_s
        try:
            while time.time() < deadline and not backup.done():
                victim = self.holders[self.rng.randrange(len(self.holders))]
                self.plane.kill(victim.client_id)
                await asyncio.sleep(ph.interval_s)
                self.plane.revive(victim.client_id)
                await asyncio.sleep(ph.interval_s / 3)
        finally:
            for h in self.holders:  # nobody stays dead past the phase
                self.plane.revive(h.client_id)
        snapshot = await asyncio.wait_for(backup, 180)
        if not snapshot:
            raise ScenarioError("churn backup returned no snapshot")
        self.facts["backups"] += 1
        self.facts["source_digest"] = _tree_digest(self.src)

    async def _phase_byzantine(self, ph: Phase) -> None:
        """Byzantine holders: every stored shard byte-flipped, so their
        next audit proof is provably wrong and one failed round demotes
        (AUDIT_DEMOTE_FAILURES)."""
        victims = self._alive_holders()[:ph.count]
        if len(victims) < ph.count:
            raise ScenarioError("not enough alive holders to corrupt")
        for victim in victims:
            stored = victim.store.received_dir(self.a.client_id)
            flipped = 0
            for f in sorted(stored.rglob("*")):
                if f.is_file():
                    blob = bytearray(f.read_bytes())
                    if blob:
                        blob[len(blob) // 2] ^= 0xFF
                        f.write_bytes(bytes(blob))
                        flipped += 1
            if not flipped:
                raise ScenarioError(
                    f"byzantine victim {victim.client_id.hex()[:8]}"
                    " holds nothing to corrupt")
            result = await asyncio.wait_for(
                self._retry_busy(
                    lambda v=victim: self.a.engine.audit_peer(v.client_id)),
                60)
            if result is None or result.passed:
                raise ScenarioError("corrupt shards passed their audit")
            if not self.a.store.get_audit_state(victim.client_id).demoted:
                raise ScenarioError("failed audit did not demote")
            self.facts["demoted"].append(victim.client_id.hex()[:8])

    async def _phase_kill(self, ph: Phase) -> None:
        """Unrepaired peer loss: permanently dark, demoted via
        consecutive audit misses.  No repair here — the point is that
        the monitor flips durability to degraded and holds it there."""
        victims = self._alive_holders()[:ph.count]
        if len(victims) < ph.count:
            raise ScenarioError("not enough alive holders to kill")
        t0 = time.time()
        self.facts.setdefault("fault_t", round(t0 - self.t0, 3))
        for victim in victims:
            self.plane.kill(victim.client_id)
            for i in range(defaults.AUDIT_DEMOTE_MISSES):
                await asyncio.wait_for(
                    self._retry_busy(
                        lambda v=victim, i=i: self.a.engine.audit_peer(
                            v.client_id, now=t0 + i)),
                    60)
            if not self.a.store.get_audit_state(victim.client_id).demoted:
                raise ScenarioError("missed audits did not demote")
            self.facts["demoted"].append(victim.client_id.hex()[:8])

    async def _phase_repair(self, ph: Phase) -> None:
        report = await asyncio.wait_for(
            self._retry_busy(lambda: self.a.engine.repair_round()), 180)
        self.facts["repairs"] += 1
        self.facts.setdefault("repair_reports", []).append(
            {k: report[k] for k in ("packfiles", "bytes_replaced",
                                    "shards_rebuilt")})

    async def _phase_race(self, ph: Phase) -> None:
        """backup + restore + repair all at once on one client.  The
        engine's exclusivity lock serializes them; every loser is
        rejected (counted) and retries until it runs."""
        if ph.grow:
            self._grow()
        self._restores += 1
        dest = self.workdir / f"race_restore_{self._restores}"
        await asyncio.wait_for(asyncio.gather(
            self._retry_busy(lambda: self.a.backup()),
            self._retry_busy(lambda: self.a.engine.run_restore(dest)),
            self._retry_busy(lambda: self.a.engine.repair_round()),
        ), 240)
        self.facts["backups"] += 1
        self.facts["restores"] += 1
        self.facts["repairs"] += 1
        self.facts["source_digest"] = _tree_digest(self.src)

    async def _phase_restore(self, ph: Phase) -> None:
        self._restores += 1
        dest = self.workdir / f"restore_{self._restores}"
        await asyncio.wait_for(
            self._retry_busy(lambda: self.a.restore(dest)), 180)
        self.facts["restores"] += 1
        ok = _tree_digest(dest) == self.facts["source_digest"]
        if self.facts["restore_verified"] is None:
            self.facts["restore_verified"] = ok
        else:
            self.facts["restore_verified"] &= ok

    async def _phase_restore_hedged(self, ph: Phase) -> None:
        """Restore with one holder stalled mid-stripe.  The victim is
        seeded as the fastest measured holder, so the restore planner
        must pick it as a primary source for every stripe it touches;
        an armed fault-plane latency then makes every frame the client
        sends toward it (the FETCH_REQUEST, the acks) sleep past the
        hedge deadline.  The download lanes must notice the stall, race
        a redundant shard from a spare holder, and win — the
        ``bkw_restore_hedges_total{outcome=won}`` gate's evidence —
        while the restore still verifies byte-for-byte."""
        placed = sorted({peer for _, peer, _size, idx, _ in
                         self.a.store.all_placements() if idx >= 0})
        if not placed:
            raise ScenarioError("no striped placements to stall")
        now = time.time()
        victim = placed[0]
        ps = self.a.engine.peer_stats
        for peer in placed:
            bps = 80e6 if peer == victim else 20e6
            # the live estimator bank only reads store rows at startup,
            # so seed both: the row (persistence) and the bank (ranking)
            self.a.store.put_peer_stats(PeerStatsRow(
                bytes(peer), bps, 0.01, 1.0, 10, now))
            with ps._lock:
                ps._est[bytes(peer)] = PeerEstimate(
                    peer=bytes(peer), throughput_bps=bps, latency_s=0.01,
                    success=1.0, samples=10, updated=now)
        site = f"send.latency:{bytes(victim).hex()}"
        saved = (self.plane.latency, self.plane.latency_s)
        # rate epsilon keeps every other latency site quiet while the
        # armed indices fire unconditionally on the victim's stream
        self.plane.latency = 1e-12
        self.plane.latency_s = 2.0
        self.plane.arm(site, *range(4096))
        try:
            await self._phase_restore(ph)
        finally:
            self.plane.latency, self.plane.latency_s = saved
            self.plane._armed.pop(site, None)

    async def _phase_wan(self, ph: Phase) -> None:
        """WAN conditions over the chunked transfer plane.  Peer stats
        are seeded so one holder measures slow/flaky and starts
        placement-demoted: capacity-aware placement must stripe onto the
        fast set only.  Every fast holder gets two armed exact-offset
        cuts, so the backup's shard sends are severed mid-transfer and
        must resume from the receiver's verified partial rather than
        restart — the scorecard gates on bkw_transfer_resumes_total and
        on bkw_transfer_bytes_resent_total staying under budget.
        Afterwards the slow holder's probation is expired to show the
        demotion is recoverable, unlike an audit demotion."""
        if ph.grow:
            self._grow()
        now = time.time()
        fast, slow = self.holders[:-1], self.holders[-1]
        for h in fast:
            self.a.store.put_peer_stats(PeerStatsRow(
                bytes(h.client_id), 50e6, 0.01, 1.0, 10, now))
        self.a.store.put_peer_stats(PeerStatsRow(
            bytes(slow.client_id), 2e3, 0.5, 0.1, 10, now))
        self.a.store.set_placement_demoted(slow.client_id, True, now=now)
        for h in fast:
            # one-shot cuts inside the first and second resume attempt's
            # uncovered ranges (chunk_bytes=4096: parts 2 and 3)
            self.plane.arm_cut(h.client_id, 6000, 10000)
        snapshot = await asyncio.wait_for(self.a.backup(), 180)
        if not snapshot:
            raise ScenarioError("wan backup returned no snapshot")
        self.facts["backups"] += 1
        self.facts["source_digest"] = _tree_digest(self.src)
        placed = {peer for _, peer, _, _, _ in self.a.store.all_placements()}
        demoted = self.a.store.placement_demoted_peers()
        self.facts["wan_placement_ok"] = (
            bytes(slow.client_id) in demoted
            and bytes(slow.client_id) not in placed
            and placed <= {bytes(h.client_id) for h in fast}
            | {bytes(s.client_id) for s in self.spares})
        # recoverability: re-demote with a timestamp past the probation
        # window; the lazy expiry in placement_demoted_peers() must clear
        # it, putting the peer back in the placement pool
        self.a.store.set_placement_demoted(
            slow.client_id, True,
            now=time.time() - defaults.PLACEMENT_PROBATION_S - 1)
        self.facts["wan_placement_recovered"] = (
            bytes(slow.client_id)
            not in self.a.store.placement_demoted_peers())

    async def _restart_client(self) -> dict:
        """Simulate process death + reboot of the source client: throw
        away every in-memory structure (engine, blob index, store
        connection) and re-open the same directories — exactly the state
        a real crash loses — then let ``ClientApp.start``'s recovery
        sweep reconcile.  Returns that sweep's report."""
        # null the monitor before the first await: the sampler task shares
        # this loop and must not sweep the closed store mid-restart
        self.monitor = None
        await self.a.stop()
        app = self._make_app("a")
        # recover() runs inside start(); it must not spawn a background
        # repair task — the harness drives every round deterministically
        app.engine.auto_repair = False
        await app.start()
        app._audit_task.cancel()
        app._monitor_task.cancel()
        app._slo_task.cancel()
        self.a = app
        self.monitor = app.monitor
        return app.engine.last_recovery

    async def _phase_crash(self, ph: Phase) -> None:
        """The crash matrix.  Per seam: grow the corpus, arm the crash
        point, drive a backup into the injected crash, restart the
        client, and prove recovery — the re-run backup completes, a
        second ``recover()`` reconciles zero items (idempotency), and
        the invariant sweep shows zero violations."""
        crashes = self.facts.setdefault("crash_sites", [])
        for site in ph.sites or _CRASH_MATRIX:
            self._grow()
            self.plane.arm_crash(site)
            try:
                await asyncio.wait_for(self.a.backup(), 180)
                raise ScenarioError(f"armed crash at {site} never fired")
            except faults.CrashInjected as e:
                if e.site != site:
                    raise ScenarioError(
                        f"crash fired at {e.site}, armed {site}")
            report = await self._restart_client()
            # the drain: the next backup's send loop picks up every
            # leftover unsent packfile alongside the re-packed blobs
            snapshot = await asyncio.wait_for(
                self._retry_busy(lambda: self.a.backup()), 180)
            if not snapshot:
                raise ScenarioError(
                    f"post-crash backup after {site} returned no snapshot")
            self.facts["backups"] += 1
            again = await self.a.engine.recover()
            sweep = self.monitor.sweep()
            crashes.append({
                "site": site,
                "reconciled": report["reconciled"],
                "backlog": report["packfiles_pending"]
                + report["stripes_underplaced"],
                "idempotent": again["reconciled"] == 0,
                "violations_after": len(sweep.violations),
            })
        self.facts["source_digest"] = _tree_digest(self.src)

    async def _phase_gc(self, ph: Phase) -> None:
        """Snapshot lifecycle under pressure (docs/lifecycle.md).

        Both modes start by mutating the corpus and backing it up, so a
        ``keep-last:1`` prune has a victim snapshot whose exclusive
        blobs are provably dead — the bytes-reclaimed gates cannot pass
        vacuously.  ``sites`` mode then walks the GC crash matrix like
        :meth:`_phase_crash` walks the backup's; plain mode races GC
        against a concurrent backup + restore on the exclusivity lock.
        """
        self.a.store.set_retention_policy("keep-last:1")
        gcs = self.facts.setdefault("gc_reports", [])
        if ph.sites:
            crashes = self.facts.setdefault("crash_sites", [])
            for site in ph.sites:
                self._mutate_corpus()
                snapshot = await asyncio.wait_for(
                    self._retry_busy(lambda: self.a.backup()), 180)
                if not snapshot:
                    raise ScenarioError(
                        f"gc setup backup before {site} returned"
                        " no snapshot")
                self.facts["backups"] += 1
                self.plane.arm_crash(site)
                try:
                    await asyncio.wait_for(self.a.engine.run_gc(), 180)
                    raise ScenarioError(
                        f"armed crash at {site} never fired")
                except faults.CrashInjected as e:
                    if e.site != site:
                        raise ScenarioError(
                            f"crash fired at {e.site}, armed {site}")
                report = await self._restart_client()
                # the re-run must converge from whatever the recovery
                # sweep rolled forward or back
                gcs.append(await asyncio.wait_for(
                    self._retry_busy(lambda: self.a.engine.run_gc()), 180))
                again = await self.a.engine.recover()
                sweep = self.monitor.sweep()
                crashes.append({
                    "site": site,
                    "reconciled": report["reconciled"],
                    "backlog": report["packfiles_pending"]
                    + report["stripes_underplaced"],
                    "idempotent": again["reconciled"] == 0,
                    "violations_after": len(sweep.violations),
                })
        else:
            self._mutate_corpus()
            snapshot = await asyncio.wait_for(
                self._retry_busy(lambda: self.a.backup()), 180)
            if not snapshot:
                raise ScenarioError("gc setup backup returned no snapshot")
            self.facts["backups"] += 1
            self._restores += 1
            dest = self.workdir / f"gc_restore_{self._restores}"
            _, _, gc_report = await asyncio.wait_for(asyncio.gather(
                self._retry_busy(lambda: self.a.backup()),
                self._retry_busy(lambda: self.a.engine.run_restore(dest)),
                self._retry_busy(lambda: self.a.engine.run_gc()),
            ), 240)
            gcs.append(gc_report)
            self.facts["backups"] += 1
            self.facts["restores"] += 1
        self.facts["source_digest"] = _tree_digest(self.src)

    # --- gates -------------------------------------------------------------

    def _assertions(self, error, counters) -> List[sc.Assertion]:
        spec, facts = self.spec, self.facts
        A = sc.Assertion
        out = [A("phases_completed", error is None,
                 "" if error is None else f"{error[0]}: {error[1]}")]
        want_backups = sum(
            _crash_count(p) if p.kind == "crash"
            # gc: one setup backup per armed seam, or setup + racer
            else (len(p.sites) if p.sites else 2) if p.kind == "gc"
            else 1
            for p in spec.phases
            if p.kind in ("backup", "churn", "race", "wan", "crash", "gc"))
        out.append(A("backups_completed",
                     facts["backups"] >= want_backups,
                     f"{facts['backups']}/{want_backups}"))
        restore_kinds = ("restore", "restore_hedged")
        if any(p.kind in restore_kinds for p in spec.phases):
            out.append(A("restore_verified",
                         facts["restore_verified"] is True,
                         "byte-for-byte vs source digest"))
        if any(p.kind in restore_kinds + ("race",) for p in spec.phases):
            # the restore data plane must actually pull: a zero delta
            # means every stripe silently fell back to the legacy
            # RESTORE_ALL stream (PR 11)
            pulled = sum(
                v for k, v in counters.items()
                if k.startswith("bkw_restore_bytes_pulled_total"))
            out.append(A("restore_telemetry_flowing", pulled > 0,
                         f"bytes_pulled={pulled:g}"))
        if any(p.kind == "restore_hedged" for p in spec.phases):
            won = counters.get(
                "bkw_restore_hedges_total{outcome=won}", 0)
            out.append(A("hedge_recovered_stall", won >= 1,
                         f"hedges_won={won:g}"))
        violation_s = sum(
            v for k, v in counters.items()
            if k.startswith("bkw_durability_violation_seconds_total"))
        saw_violation = violation_s > 0 or any(
            s.get("status_level", 0) >= 2 for s in self.samples)
        if spec.expect_violation:
            out.append(A("violation_observed", saw_violation,
                         f"violation_seconds={violation_s:.3f}"))
        else:
            out.append(A("zero_violation_seconds", not saw_violation,
                         f"violation_seconds={violation_s:.3f}"))
        final = self.monitor.last_report
        out.append(A("final_status",
                     final is not None
                     and final.status == spec.expect_final_status,
                     f"want {spec.expect_final_status}, got "
                     f"{final.status if final else 'no sweep'}"))
        if spec.min_shards_rebuilt:
            rebuilt = counters.get("bkw_repair_shards_rebuilt_total", 0)
            out.append(A("shards_rebuilt",
                         rebuilt >= spec.min_shards_rebuilt,
                         f"{rebuilt:g} >= {spec.min_shards_rebuilt}"))
        if want_backups:
            # performance telemetry must keep flowing: every backup's
            # chunk pipeline feeds bkw_device_dispatch_total and every
            # finalized transfer feeds a per-peer estimator sample — a
            # zero delta here means the profiler or PeerStats wiring
            # silently died (PR 7)
            dispatches = sum(
                v for k, v in counters.items()
                if k.startswith("bkw_device_dispatch_total"))
            samples = sum(
                v for k, v in counters.items()
                if k.startswith("bkw_peer_transfer_samples_total"))
            out.append(A("telemetry_flowing",
                         dispatches > 0 and samples > 0,
                         f"dispatches={dispatches:g}"
                         f" peer_samples={samples:g}"))
        if any(p.kind == "wan" for p in spec.phases):
            resumes = sum(
                v for k, v in counters.items()
                if k.startswith("bkw_transfer_resumes_total"))
            out.append(A("resume_exercised", resumes >= 1,
                         f"resumes={resumes:g}"))
            resent = sum(
                v for k, v in counters.items()
                if k.startswith("bkw_transfer_bytes_resent_total"))
            sent = sum(
                v for k, v in counters.items()
                if k.startswith("bkw_transfer_bytes_total"))
            # resume must pay back: re-sent bytes a small fraction of
            # the payload bytes moved, not a restart-from-zero doubling
            out.append(A("resent_under_budget",
                         resent <= 0.25 * max(sent, 1.0),
                         f"resent={resent:g} of {sent:g} sent"))
            out.append(A("placement_capacity_aware",
                         facts.get("wan_placement_ok") is True,
                         "shards landed on measured-fast holders only"))
            out.append(A("placement_demotion_recovered",
                         facts.get("wan_placement_recovered") is True,
                         "probation expiry re-admitted the slow holder"))
        crash_like = [p for p in spec.phases if p.kind == "crash"
                      or (p.kind == "gc" and p.sites)]
        if crash_like:
            want = sum(_crash_count(p) if p.kind == "crash"
                       else len(p.sites) for p in crash_like)
            crashes = facts.get("crash_sites", [])
            injections = sum(
                v for k, v in counters.items()
                if k.startswith("bkw_fault_injections_total")
                and "crash." in k)
            out.append(A("crashes_injected",
                         len(crashes) >= want and injections >= want,
                         f"{len(crashes)}/{want} seams crashed"
                         f" ({injections:g} injections counted)"))
            recoveries = sum(
                v for k, v in counters.items()
                if k.startswith("bkw_recovery_runs_total"))
            out.append(A("recoveries_swept", recoveries >= 2 * len(crashes),
                         f"recovery_runs={recoveries:g} for"
                         f" {len(crashes)} crash(es)"))
            # the PR-9 hard gate: every crashed seam recovered to a
            # violation-free world and a provably idempotent recover()
            bad = [c["site"] for c in crashes
                   if not c["idempotent"] or c["violations_after"]]
            out.append(A("recovery_clean", bool(crashes) and not bad,
                         "all seams idempotent + violation-free"
                         if not bad else "dirty: " + ", ".join(bad)))
        if any(p.kind == "gc" for p in spec.phases):
            ok_runs = counters.get("bkw_gc_runs_total{outcome=ok}", 0)
            out.append(A("gc_completed", ok_runs >= 1,
                         f"ok_runs={ok_runs:g}"))
            reclaimed = sum(
                v for k, v in counters.items()
                if k.startswith("bkw_gc_bytes_reclaimed_total"))
            out.append(A("gc_reclaimed_bytes", reclaimed > 0,
                         f"bytes_reclaimed={reclaimed:g}"))
            # make-before-break's other end: the holders really deleted
            # (every peer is in-process, so their serve-side counter
            # lands in the same registry)
            freed = sum(
                v for k, v in counters.items()
                if k.startswith("bkw_reclaim_bytes_freed_total"))
            out.append(A("gc_holders_freed_bytes", freed > 0,
                         f"reclaim_freed={freed:g}"))
        if spec.slo:
            breaches = facts.get("slo_breaches", [])
            fault_t = facts.get("fault_t")
            # detection: the first breach must land within 2 sweep
            # intervals of the first violated invariant sample
            first_bad = next((s["t"] for s in self.samples
                              if s.get("status_level", 0) >= 2), None)
            first_breach = breaches[0]["t"] if breaches else None
            budget_s = 2 * defaults.DURABILITY_SWEEP_INTERVAL_S
            detect_s = (None if first_breach is None or first_bad is None
                        else round(first_breach - first_bad, 3))
            out.append(A("slo_breach_detected",
                         detect_s is not None and detect_s <= budget_s,
                         f"detection={detect_s}s budget={budget_s}s"))
            # precision: every breach must postdate the armed fault
            false_pos = [b for b in breaches
                         if fault_t is None or b["t"] < fault_t]
            out.append(A("slo_no_false_positives", not false_pos,
                         f"{len(false_pos)} breach(es) before the fault"))
            # attribution: the armed fault site (a killed victim's id in
            # a fault:* cause) must rank in the explainer's top-3
            top3 = [c["id"] for d in self.diagnoses
                    for c in d["causes"][:3]]
            victims = facts.get("demoted", [])
            named = any(c.startswith("fault:")
                        and any(v in c for v in victims)
                        for c in top3)
            out.append(A("diagnosis_names_fault", named,
                         f"top causes: {sorted(set(top3))[:6]}"))
            facts["slo"] = {
                "detection_s": detect_s,
                "precision": (round(1.0 - len(false_pos)
                                    / len(breaches), 4)
                              if breaches else None),
                "breaches": len(breaches),
                "top_causes": top3[:3],
            }
        return out


async def run_scenario(spec: ScenarioSpec, workdir,
                       backend: Optional[ChunkerBackend] = None
                       ) -> sc.Scorecard:
    """setup -> run -> teardown; the one-call entry point used by the
    CLI (scripts/scenario.py) and the tests."""
    harness = ScenarioHarness(spec, Path(workdir), backend=backend)
    await harness.setup()
    try:
        return await harness.run()
    finally:
        await harness.teardown()


def builtin_scenarios() -> Dict[str, ScenarioSpec]:
    """The scenario matrix.  ``composed`` is the tier-1 acceptance run
    (churn + byzantine + race, < 60 s on loopback); ``full`` is the slow
    matrix adding unrepaired loss, a second repair wave, and a bigger
    corpus."""
    P = Phase
    return {
        "steady": ScenarioSpec(
            name="steady", seed=11,
            phases=(P("backup"), P("steady", duration_s=0.6),
                    P("restore"))),
        "churn": ScenarioSpec(
            name="churn", seed=21,
            phases=(P("backup"),
                    P("churn", duration_s=2.0, interval_s=0.3, grow=True),
                    # a churn backup may finish with a stripe short a
                    # shard (kept locally unsent); repair drains the debt
                    P("repair"),
                    P("restore"))),
        "byzantine": ScenarioSpec(
            name="byzantine", seed=31, min_shards_rebuilt=1,
            phases=(P("backup"), P("byzantine"), P("repair"),
                    P("restore"))),
        "loss": ScenarioSpec(
            name="loss", seed=41, expect_final_status="degraded",
            phases=(P("backup"), P("kill"), P("steady", duration_s=0.4))),
        # the live-SLO acceptance run: a quiet pre-fault baseline, then
        # three of six holders permanently dark — below RS k, so
        # durability flips to violated, violation-seconds accrue, the
        # fast burn windows fire, and the explainer must pin the armed
        # kills (docs/observability.md §Diagnosis)
        "diagnosis": ScenarioSpec(
            name="diagnosis", seed=121, slo=True,
            expect_violation=True, expect_final_status="violated",
            phases=(P("backup"),
                    P("steady", duration_s=1.0),
                    P("kill", count=3),
                    P("steady", duration_s=1.5))),
        "composed": ScenarioSpec(
            name="composed", seed=51, spares=2, min_shards_rebuilt=1,
            phases=(P("backup"),
                    P("steady", duration_s=0.4),
                    P("churn", duration_s=1.5, interval_s=0.3, grow=True),
                    P("byzantine"),
                    P("repair"),
                    P("race", grow=True),
                    P("restore_hedged"))),
        "wan": ScenarioSpec(
            name="wan", seed=71, corpus_files=4, chunk_bytes=4096,
            phases=(P("wan"), P("restore"))),
        # crash: a representative seam per commit layer (tier-1);
        # crash_full walks every sender-side seam (slow matrix)
        "crash": ScenarioSpec(
            name="crash", seed=81, corpus_files=4,
            phases=(P("backup"),
                    P("crash", sites=("pack.seal.pre", "index.save.pre",
                                      "placement.insert.post")),
                    P("restore"))),
        "crash_full": ScenarioSpec(
            name="crash_full", seed=91, corpus_files=4,
            phases=(P("backup"), P("crash"), P("restore"))),
        # gc: lifecycle race (tier-1); gc_full arms every GC commit seam
        "gc": ScenarioSpec(
            name="gc", seed=101, corpus_files=4,
            phases=(P("backup"), P("gc"), P("restore"))),
        "gc_full": ScenarioSpec(
            name="gc_full", seed=111, corpus_files=4,
            phases=(P("backup"),
                    P("gc", sites=(
                        "gc.prune.pre", "gc.prune.post",
                        "gc.sweep.pre", "gc.sweep.post",
                        "gc.compact.seal.pre", "gc.compact.seal.post",
                        "gc.swap.pre", "gc.swap.post",
                        "gc.reclaim.pre", "gc.reclaim.post")),
                    P("restore"))),
        "full": ScenarioSpec(
            name="full", seed=61, spares=2, corpus_files=10,
            corpus_file_bytes=48 * 1024, min_shards_rebuilt=1,
            phases=(P("backup"),
                    P("steady", duration_s=1.0),
                    P("churn", duration_s=4.0, interval_s=0.4, grow=True),
                    P("byzantine"),
                    P("repair"),
                    P("race", grow=True),
                    P("kill"),
                    P("steady", duration_s=0.6),
                    P("repair"),
                    P("restore"))),
    }
