"""Scenario builtins + deterministic scorecards for the sim plane.

Five population-scale situations the real-time swarm harness
(scenario/swarm.py) cannot reach at its hundreds-of-clients ceiling:

* ``flashcrowd`` — the population arrives inside one hour and all wants
  storage at once; gates on match-rate and p99 time-to-placement.
* ``regionfail`` — a quarter of the regions die at one instant two days
  in (correlated failure); gates on repair-debt drain time and
  population durability-violation client-seconds.  The 10⁵-client
  simulated week of this is the tier-1 acceptance builtin.
* ``auditstorm`` — a freeloader cohort takes placements and drops the
  bytes; the resulting audit-report storm must block the freeloaders
  from further matches (>= 2 distinct failing reporters, the real
  store-side defense) without ever blocking an honest live client.
* ``drought`` — arrivals too sparse to pair inside the request expiry;
  gates that the deadline-heap expiry fires (no immortal queue entries)
  and that persistent retries still converge on matches.
* ``repaircascade`` — an uncorrelated 10% of clients vanish at once;
  the repair thundering herd must drain without starving the economy.

A scorecard is a plain sorted-JSON-able dict computed purely from
virtual time and the seeded model — never from the wall clock — so the
same seed replays **byte-identically** (the determinism acceptance
gate).  Wall-derived numbers (events/s, sim-seconds per wall-second)
ride in a separate stats dict and in the ``bkw_sim_*`` gauges.
"""

from __future__ import annotations

import asyncio
import gc
import json
import time
from typing import Dict, Optional, Tuple

from .. import defaults
from ..obs import diagnose as obs_diagnose
from ..obs import metrics as obs_metrics
from ..obs import slo as obs_slo
from ..obs.series import SeriesRecorder
from .clock import SimClock
from .driver import SimDriver
from .model_client import SimParams, SimWorld

WEEK_S = 7 * 86_400.0

#: Virtual-time cadence of the SLO sampler — 2016 ticks per simulated
#: week, far below the per-event budget.
SLO_SAMPLE_S = 300.0

_EVENTS = obs_metrics.counter(
    "bkw_sim_events_total", "Virtual-clock events fired per scenario run",
    ("scenario",))
_SIM_SECONDS = obs_metrics.counter(
    "bkw_sim_seconds_total", "Simulated seconds advanced per scenario",
    ("scenario",))
_COMPRESSION = obs_metrics.gauge(
    "bkw_sim_time_compression",
    "Sim-seconds per wall-second of the last run", ("scenario",))
_EVENTS_PER_S = obs_metrics.gauge(
    "bkw_sim_events_per_wall_second",
    "Event throughput of the last run", ("scenario",))
_CLIENTS = obs_metrics.gauge(
    "bkw_sim_clients", "Population by model-client state at run end",
    ("scenario", "state"))
_DEBT = obs_metrics.gauge(
    "bkw_sim_repair_debt_bytes",
    "World-truth bytes with no live copy at run end", ("scenario",))
_VIOL = obs_metrics.counter(
    "bkw_sim_violation_client_seconds_total",
    "Client-seconds spent with any unrestorable byte (world truth)",
    ("scenario",))
_WAITS = obs_metrics.histogram(
    "bkw_sim_match_wait_seconds",
    "Sim seconds from first ask to fully placed", ("scenario",),
    buckets=obs_metrics.log_buckets(1.0, 2.0, 22))


def _wall() -> float:
    # The one wall-clock read in the sim plane: measuring its OWN time
    # compression requires real elapsed seconds (BKW006-baselined).
    return time.monotonic()


#: name -> (description, param overrides on top of SimParams defaults)
BUILTINS: Dict[str, Tuple[str, dict]] = {
    "flashcrowd": (
        "whole population arrives within one hour and requests at once",
        dict(clients=20_000, sim_seconds=WEEK_S, arrival_span_s=3600.0)),
    "regionfail": (
        "25% of regions die at one instant on day 2 (tier-1: 1e5 clients)",
        dict(clients=100_000, sim_seconds=WEEK_S,
             fail_at_s=2 * 86_400.0, fail_fraction=0.25,
             fail_kind="region",
             # thinner per-client cadence than default: 10^5 clients is
             # the tier-1 acceptance run, and the failure/repair
             # dynamics (detect -> repair-report -> re-place) do not
             # need a 3-day backup rhythm to be exercised
             backup_interval_s=6 * 86_400.0,
             audit_interval_s=3 * 86_400.0)),
    "auditstorm": (
        "2% freeloaders drop every byte; audit reports must block them",
        dict(clients=20_000, sim_seconds=WEEK_S, freeloader_rate=0.02,
             pass_report_rate=0.05)),
    "drought": (
        "arrivals sparser than the request expiry; retries must converge",
        dict(clients=500, sim_seconds=WEEK_S,
             arrival_span_s=5 * 86_400.0,
             backup_interval_s=10 * 86_400.0)),
    "repaircascade": (
        "10% of clients vanish uncorrelated at once on day 3",
        dict(clients=50_000, sim_seconds=WEEK_S,
             fail_at_s=3 * 86_400.0, fail_fraction=0.10,
             fail_kind="random")),
}


def builtin_sims() -> Dict[str, str]:
    """name -> one-line description (the scripts/scenario.py catalog)."""
    return {name: desc for name, (desc, _p) in BUILTINS.items()}


def make_scenario(name: str, clients: Optional[int] = None,
                  seed: Optional[int] = None,
                  sim_seconds: Optional[float] = None) -> SimParams:
    if name not in BUILTINS:
        raise KeyError(f"unknown sim scenario {name!r};"
                       f" builtins: {sorted(BUILTINS)}")
    _desc, over = BUILTINS[name]
    params = dict(over)
    if clients is not None:
        params["clients"] = int(clients)
    if sim_seconds is not None:
        params["sim_seconds"] = float(sim_seconds)
    params["seed"] = 0 if seed is None else int(seed)
    return SimParams(**params)


# --- gates -------------------------------------------------------------------


def _gate(gates: list, name: str, passed: bool, detail: str) -> None:
    gates.append({"name": name, "passed": bool(passed), "detail": detail})


def _blocked(world: SimWorld, cid: bytes) -> bool:
    return world.store.audit_failing_reporters(
        cid, defaults.AUDIT_REPORT_WINDOW_S) \
        >= defaults.AUDIT_SERVER_BLOCK_FAILURES


def _evaluate_gates(name: str, world: SimWorld, card: dict) -> list:
    gates: list = []
    rate = card["match_rate"]
    viol = card["violation_client_seconds"]
    if name == "flashcrowd":
        _gate(gates, "match_rate>=0.95", rate >= 0.95,
              f"placed/demand = {rate}")
        p99 = card["match_wait_p99_s"]
        _gate(gates, "p99_match_wait<=24h", p99 <= 86_400.0,
              f"p99 first-ask-to-placed = {p99}s")
        _gate(gates, "no_data_at_risk", viol == 0.0,
              f"violation_client_seconds = {viol}")
    elif name in ("regionfail", "repaircascade"):
        _gate(gates, "match_rate>=0.90", rate >= 0.90,
              f"placed/demand = {rate}")
        drain = card["repair_drain_s"]
        _gate(gates, "repair_debt_drained<=3d",
              drain is not None and drain <= 3 * 86_400.0,
              f"debt peak {card['repair_debt_peak_bytes']}b drained to <=5%"
              f" in {drain}s")
        # every affected owner carries ~detect_span/2 of undetected loss
        # plus one repair round-trip; 2 sim-days per lost-data client is
        # a generous population envelope for both builtins
        budget = 2 * 86_400.0 * max(
            1, int(world.params.clients * world.params.fail_fraction))
        _gate(gates, "violation_seconds_bounded", viol <= budget,
              f"{viol} client-seconds <= budget {budget}")
        # the live SLO plane must notice the injected failure (never
        # before it — pre-fault the world is provably quiet) and the
        # explainer must pin the injection site in its top-3 causes
        slo = card.get("slo") or {}
        fail_at = world.params.fail_at_s or 0.0
        first = slo.get("first_breach_t")
        _gate(gates, "slo_breach_after_fault",
              first is not None and first >= fail_at,
              f"first breach at {first}s (fault at {fail_at:g}s)")
        causes = [c["id"] for c in
                  (slo.get("diagnosis") or {}).get("causes", [])[:3]]
        _gate(gates, "slo_diagnosis_names_fault",
              any(c.startswith("fault:sim.") for c in causes),
              f"top causes: {causes}")
    elif name == "auditstorm":
        _gate(gates, "match_rate>=0.90", rate >= 0.90,
              f"placed/demand = {rate}")
        frees = [c for c in world.clients if c.freeloader]
        reported = [c for c in frees
                    if any(p[2] for p in c_pieces(world, c))]
        blocked = sum(1 for c in frees[:500] if _blocked(world, c.cid))
        checked = len(frees[:500])
        _gate(gates, "freeloaders_blocked>=0.8",
              checked > 0 and blocked >= 0.8 * checked,
              f"{blocked}/{checked} freeloaders match-blocked"
              f" ({len(reported)} held dropped pieces)")
        honest = [c for c in world.clients
                  if not c.freeloader and c.state != "dead"][:200]
        honest_blocked = sum(1 for c in honest if _blocked(world, c.cid))
        _gate(gates, "honest_not_blocked", honest_blocked == 0,
              f"{honest_blocked}/{len(honest)} live honest clients blocked")
    elif name == "drought":
        _gate(gates, "requests_expired", card["expired"] > 0,
              f"{card['expired']} queue entries reaped by the deadline heap")
        _gate(gates, "retries_converge>=0.5", rate >= 0.5,
              f"placed/demand = {rate} despite sparse arrivals")
        _gate(gates, "no_data_at_risk", viol == 0.0,
              f"violation_client_seconds = {viol}")
    return gates


def c_pieces(world: SimWorld, client) -> list:
    """A freeloader's *held* pieces are scattered on its victims; walk
    the reverse index (audit evidence for the auditstorm gate detail)."""
    out = []
    for owner_idx, pid in world.held.get(client.cid, ()):
        piece = world.clients[owner_idx].pieces.get(pid)
        if piece is not None:
            out.append(piece)
    return out


# --- the run -----------------------------------------------------------------


async def run_scenario_async(name: str, spec: SimParams
                             ) -> Tuple[dict, dict]:
    """Run one scenario on a fresh SimClock; returns (scorecard, stats).
    The scorecard is wall-clock-free and byte-stable per seed; stats
    carry the wall-derived compression numbers."""
    reg = obs_metrics.registry()

    def _ctr(metric: str) -> float:
        fam = reg.get(metric)
        return fam.value() if fam is not None else 0.0

    matched0 = _ctr("bkw_matchmakings_total")
    expired0 = _ctr("bkw_matchmaking_expired_total")
    clock = SimClock()
    driver = SimDriver(clock)
    world = SimWorld(clock, spec)
    # A 10^6-event run allocates faster than the cyclic collector's
    # default thresholds assume; with collection on, gen-2 sweeps over
    # the (acyclic) piece/heap population cost ~20% of the wall budget.
    # Batch work, single-threaded, bounded lifetime: collect once at
    # the end instead.
    gc_was_enabled = gc.isenabled()
    gc.disable()

    # --- live SLO plane on virtual time (obs/slo.py) ---------------------
    # World-truth numbers are recorded as synthetic series (the registry
    # is only flushed post-run), the burn-rate monitor runs the REAL
    # multi-window spans against the virtual clock, and the first breach
    # is diagnosed against the injected failure — all virtual-time
    # derived, so card["slo"] replays byte-identically per seed.
    recorder = SeriesRecorder((), clock=clock)
    slo_catalog = [obs_slo.Objective(
        id="sim_durability", kind="counter_rate",
        family="sim:violation_fraction_seconds", budget=1e-4,
        description="population fraction-seconds with unrestorable data")]
    slo_state: dict = {"breaches": [], "diagnosis": None, "ticks": 0}

    def _slo_breach(breach) -> None:
        slo_state["breaches"].append(breach.to_dict())
        if slo_state["diagnosis"] is None:
            events = []
            if spec.fail_at_s is not None and spec.fail_fraction > 0:
                events.append({"ts": spec.fail_at_s, "kind": "fault",
                               "site": f"sim.{spec.fail_kind}_fail"})
            # window wide enough to reach back past the detection lag
            # to the injection instant
            slo_state["diagnosis"] = obs_diagnose.explain(
                breach, recorder=recorder, events=events,
                now=breach.t, window_s=4 * 3600.0)

    slo = obs_slo.SLOMonitor(recorder, catalog=slo_catalog, clock=clock,
                             on_breach=_slo_breach, client="sim")

    def _slo_tick() -> None:
        t = clock.monotonic()
        world._accrue()  # bring the lazy ledger up to the tick instant
        recorder.record("sim:violation_fraction_seconds",
                        world.violation_client_seconds
                        / max(spec.clients, 1), t=t, kind="counter")
        recorder.record("sim:repair_debt_bytes",
                        float(world.repair_debt_bytes), t=t)
        recorder.record("sim:deaths", float(world.deaths), t=t,
                        kind="counter")
        slo_state["ticks"] += 1
        slo.evaluate(now=t)
        clock.call_later(SLO_SAMPLE_S, _slo_tick)

    clock.call_later(SLO_SAMPLE_S, _slo_tick)

    t0 = _wall()
    try:
        world.populate()
        await driver.run(spec.sim_seconds)
        world.finish()
        queue_end = world.matchmaker.pending()
        wall_s = max(_wall() - t0, 1e-9)
        waits = sorted(world.match_waits)
        card = {
            "scenario": name,
            "seed": spec.seed,
            "clients": spec.clients,
            "sim_seconds": spec.sim_seconds,
            "events": driver.events,
            "requests": world.requests,
            "retries": world.retries,
            "matchmakings": int(_ctr("bkw_matchmakings_total") - matched0),
            "expired": int(_ctr("bkw_matchmaking_expired_total") - expired0),
            "queue_depth_end": queue_end,
            "transfers": world.transfers,
            "failed_transfers": world.failed_transfers,
            "demand_bytes": world.demand_bytes,
            "granted_bytes": world.granted_bytes,
            "placed_bytes": world.placed_bytes,
            "match_rate": round(world.match_rate(), 6),
            "match_wait_p50_s": round(world.wait_quantile(0.50), 3),
            "match_wait_p99_s": round(world.wait_quantile(0.99), 3),
            "audit_failures": world.audit_failures,
            "audit_passes": world.audit_passes,
            "repairs_started": world.repairs_started,
            "deaths": world.deaths,
            "repair_debt_peak_bytes": world.debt_peak_bytes,
            "repair_debt_bytes_end": world.repair_debt_bytes,
            "repair_drain_s": (None if world.drain_s is None
                               else round(world.drain_s, 3)),
            "violation_client_seconds":
                round(world.violation_client_seconds, 3),
            "population": world.state_counts(),
        }
        card["slo"] = {
            "ticks": slo_state["ticks"],
            "status": slo.summary()["status"],
            "breaches": slo_state["breaches"],
            "first_breach_t": (slo_state["breaches"][0]["t"]
                               if slo_state["breaches"] else None),
            "diagnosis": slo_state["diagnosis"],
        }
        card["gates"] = _evaluate_gates(name, world, card)
        card["passed"] = all(g["passed"] for g in card["gates"])
        stats = {
            "wall_s": round(wall_s, 3),
            "events_per_s": round(driver.events / wall_s, 1),
            "time_compression": round(spec.sim_seconds / wall_s, 1),
        }
        _flush_metrics(name, world, driver, waits, stats)
        return card, stats
    finally:
        await driver.shutdown()
        world.close()
        if gc_was_enabled:
            gc.enable()
            gc.collect()


def _flush_metrics(name: str, world: SimWorld, driver: SimDriver,
                   waits, stats: dict) -> None:
    """One registry write per family AFTER the run — metric plumbing
    stays out of the per-event budget and out of the scorecard."""
    _EVENTS.inc(driver.events, scenario=name)
    _SIM_SECONDS.inc(world.params.sim_seconds, scenario=name)
    _COMPRESSION.set(stats["time_compression"], scenario=name)
    _EVENTS_PER_S.set(stats["events_per_s"], scenario=name)
    for state, count in world.state_counts().items():
        _CLIENTS.set(count, scenario=name, state=state)
    _DEBT.set(world.repair_debt_bytes, scenario=name)
    _VIOL.inc(world.violation_client_seconds, scenario=name)
    for w in waits:
        _WAITS.observe(w, scenario=name)


def run_sim(name: str, clients: Optional[int] = None,
            seed: Optional[int] = None,
            sim_seconds: Optional[float] = None) -> Tuple[dict, dict]:
    """Sync entry point (scripts, tests outside a loop)."""
    spec = make_scenario(name, clients=clients, seed=seed,
                         sim_seconds=sim_seconds)
    return asyncio.run(run_scenario_async(name, spec))


def card_json(card: dict) -> str:
    """The canonical byte-stable rendering (determinism gate compares
    these strings across runs)."""
    return json.dumps(card, sort_keys=True, separators=(",", ":"))
