"""Virtual-clock simulation plane (backuwup_tpu/sim, docs/simulation.md).

Units first: SimClock event ordering and sleep parking, SimDriver
quiescence (including the failure-propagation and stuck-task contracts
that keep determinism honest).  Then the point of the plane: REAL
production code — RetryTimer, InvariantMonitor, ShardedMatchmaker over
a real SqliteServerStore — running on virtual time with exact-value
assertions no wall clock could support.  Integration: same seed ⇒
byte-identical scorecard, and the tier-1 acceptance run — a simulated
week of 10⁵-client churn through regionfail with its gates.  The 10⁶
soak rides the same path, marked slow.
"""

import asyncio
import json

import pytest

from backuwup_tpu import defaults
from backuwup_tpu.net.matchmaking import ShardedMatchmaker
from backuwup_tpu.net.serverstore import SqliteServerStore
from backuwup_tpu.obs import metrics as obs_metrics
from backuwup_tpu.obs.invariants import InvariantMonitor
from backuwup_tpu.sim import (BUILTINS, SimClock, SimDriver, card_json,
                              run_sim)
from backuwup_tpu.store import Store
from backuwup_tpu.utils import retry

pytestmark = pytest.mark.sim

WEEK_S = 7 * 86_400.0


@pytest.fixture
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


def pk(i: int) -> bytes:
    return i.to_bytes(8, "big") + bytes(24)


def _ctr(name: str, **labels) -> float:
    fam = obs_metrics.registry().get(name)
    return fam.value(**labels) if fam is not None else 0.0


# --- SimClock ---------------------------------------------------------------


def test_clock_fires_in_deadline_order_with_submission_tiebreak(loop):
    clock = SimClock()
    driver = SimDriver(clock)
    fired = []
    clock.call_at(5.0, fired.append, "b")
    clock.call_at(2.0, fired.append, "a")
    clock.call_at(5.0, fired.append, "c")  # same deadline: after "b"
    clock.call_later(1.0, fired.append, "first")
    loop.run_until_complete(driver.run(until=10.0))
    assert fired == ["first", "a", "b", "c"]
    assert clock.now() == clock.monotonic() == 10.0
    assert driver.events == 4


def test_clock_clamps_past_deadlines_to_now(loop):
    clock = SimClock(start=100.0)
    driver = SimDriver(clock)
    fired = []
    clock.call_at(3.0, lambda: fired.append(clock.now()))
    loop.run_until_complete(driver.run(until=100.0))
    assert fired == [100.0]  # the past is not addressable


def test_clock_sleep_parks_until_virtual_deadline(loop):
    clock = SimClock()
    driver = SimDriver(clock)
    woke = []

    async def sleeper():
        await clock.sleep(30.0)
        woke.append(clock.now())
        await clock.sleep(12.5)
        woke.append(clock.now())

    async def scenario():
        task = driver.spawn(sleeper())
        await driver.run(until=100.0)
        assert task.done() and clock.blocked == 0

    loop.run_until_complete(scenario())
    assert woke == [30.0, 42.5]


# --- SimDriver contracts ----------------------------------------------------


def test_driver_awaits_async_handlers_inline(loop):
    clock = SimClock()
    driver = SimDriver(clock)
    order = []

    async def handler(tag):
        order.append(("start", tag, clock.now()))
        order.append(("end", tag))

    clock.call_at(1.0, handler, "x")
    clock.call_at(2.0, handler, "y")
    loop.run_until_complete(driver.run(until=5.0))
    # x ran to completion before y fired — no interleaving
    assert order == [("start", "x", 1.0), ("end", "x"),
                     ("start", "y", 2.0), ("end", "y")]


def test_driver_propagates_spawned_task_failures(loop):
    clock = SimClock()
    driver = SimDriver(clock)

    async def doomed():
        await clock.sleep(5.0)
        raise ValueError("sim model bug")

    async def scenario():
        driver.spawn(doomed())
        await driver.run(until=10.0)

    with pytest.raises(ValueError, match="sim model bug"):
        loop.run_until_complete(scenario())


def test_driver_refuses_tasks_parked_off_the_clock(loop):
    """A spawned task blocked on anything but SimClock.sleep would make
    time advance past work that is still pending: the driver raises
    instead of silently racing."""
    clock = SimClock()
    driver = SimDriver(clock)

    async def stuck():
        await asyncio.get_running_loop().create_future()  # never set

    async def scenario():
        driver.spawn(stuck())
        await driver.run(until=1.0)

    with pytest.raises(RuntimeError, match="did not quiesce"):
        loop.run_until_complete(scenario())
    loop.run_until_complete(driver.shutdown())


# --- real production code on virtual time -----------------------------------


def test_retry_timer_reads_the_injected_clock():
    clock = SimClock(start=1000.0)
    p = retry.RetryPolicy(base_s=10.0, cap_s=40.0, jitter=0.0)
    t = retry.RetryTimer(p, clock=clock)
    assert t.due()  # fresh timer fires immediately
    t.fire()
    clock.advance_to(1005.0)
    assert not t.due()
    clock.advance_to(1010.0)
    assert t.due()


def test_invariant_monitor_cadence_on_virtual_clock(tmp_path, loop):
    """InvariantMonitor.run — the production background task, not a
    copy — sweeps on the virtual cadence: five sweeps across 21 virtual
    seconds at interval 5, zero wall waiting."""
    obs_metrics.registry().reset()
    store = Store(tmp_path / "cfg", data_base=tmp_path / "data")
    clock = SimClock()
    driver = SimDriver(clock)
    mon = InvariantMonitor(store, client="simcadence", clock=clock)

    async def scenario():
        driver.spawn(mon.run(interval_s=5.0))
        await driver.run(until=21.0)
        await driver.shutdown()

    try:
        loop.run_until_complete(scenario())
        # sweeps at t = 0, 5, 10, 15, 20
        assert _ctr("bkw_durability_sweeps_total",
                    client="simcadence") == 5.0
    finally:
        store.close()
        obs_metrics.registry().reset()


def test_matchmaker_expiry_on_virtual_clock(loop):
    """A queued request expires on the deadline heap when VIRTUAL time
    passes expiry_s — the real ShardedMatchmaker + SqliteServerStore,
    no wall clock anywhere."""
    store = SqliteServerStore(":memory:", write_behind=False)
    clock = SimClock()
    expired0 = _ctr("bkw_matchmaking_expired_total")

    class AlwaysOnline:
        def is_online(self, client_id):
            return True

        async def notify(self, client_id, msg):
            return True

    m = ShardedMatchmaker(store, AlwaysOnline(), expiry_s=300.0,
                          shards=2, clock=clock)
    try:
        store.register_client(pk(1))
        store.register_client(pk(2))
        loop.run_until_complete(m.fulfill(pk(1), 4096, min_peers=1))
        assert m.pending() == 1  # queued, waiting for a counterparty
        clock.advance_to(301.0)
        assert m.pending() == 0  # reaped: the deadline passed virtually
        assert _ctr("bkw_matchmaking_expired_total") - expired0 == 1.0
        # a fresh request after the expiry finds no stale candidate
        loop.run_until_complete(m.fulfill(pk(2), 4096, min_peers=1))
        assert m.pending() == 1
    finally:
        store.close()


# --- scenarios: determinism and the scorecard -------------------------------


@pytest.mark.parametrize("name,clients,sim_seconds", [
    ("flashcrowd", 1500, None), ("regionfail", 2000, 3 * 86_400.0)])
def test_same_seed_same_scorecard_byte_identical(name, clients, sim_seconds):
    """The replay contract triage leans on.  ``regionfail`` carries the
    live SLO plane through its fault, so its card also pins the burn
    ticks, the breach time and the ranked diagnosis."""
    c1, _ = run_sim(name, clients=clients, sim_seconds=sim_seconds)
    c2, _ = run_sim(name, clients=clients, sim_seconds=sim_seconds)
    assert card_json(c1) == card_json(c2)
    if name == "flashcrowd":
        assert c1["passed"], json.dumps(c1["gates"], indent=1)
    else:
        slo = c1["slo"]
        assert slo["first_breach_t"] >= 2 * 86_400.0
        assert slo["diagnosis"]["causes"]


def test_scorecard_is_wall_clock_free_and_metrics_flush():
    events0 = _ctr("bkw_sim_events_total", scenario="drought")
    card, stats = run_sim("drought")
    assert card["passed"], json.dumps(card["gates"], indent=1)
    # wall-derived numbers live in stats, never in the (replayable) card
    assert not any("wall" in k for k in card)
    assert set(stats) == {"wall_s", "events_per_s", "time_compression"}
    assert _ctr("bkw_sim_events_total",
                scenario="drought") - events0 == card["events"]


def test_builtin_registry_names_and_specs():
    assert set(BUILTINS) == {"flashcrowd", "regionfail", "auditstorm",
                             "drought", "repaircascade"}
    desc, spec = BUILTINS["regionfail"]
    assert spec["clients"] == 100_000 and spec["sim_seconds"] == WEEK_S


# --- the tier-1 acceptance run ----------------------------------------------


def test_simulated_week_of_1e5_client_churn_in_tier1_minutes():
    """The headline: 10⁵ clients, a simulated week, a quarter of the
    regions lost on day 2 — real matchmaking and serverstore paths on
    the virtual clock, gates on match-rate, repair-debt drain, and
    violation client-seconds, and on the live SLO plane noticing the
    failure (never before it) and naming the injection site.  Runs in
    well under a tier-1 minute's budget."""
    card, stats = run_sim("regionfail")
    assert card["clients"] == 100_000
    assert card["sim_seconds"] == WEEK_S
    assert [g["name"] for g in card["gates"]] == [
        "match_rate>=0.90", "repair_debt_drained<=3d",
        "violation_seconds_bounded", "slo_breach_after_fault",
        "slo_diagnosis_names_fault"]
    red = [g for g in card["gates"] if not g["passed"]]
    assert not red and card["passed"], json.dumps(card["gates"], indent=1)
    # a simulated week must not cost a wall week: 3 orders of magnitude
    # is the floor even on a loaded CI box
    assert stats["time_compression"] > 1_000.0


@pytest.mark.slow
def test_simulated_week_of_1e6_client_soak():
    card, _stats = run_sim("regionfail", clients=1_000_000)
    assert card["passed"], json.dumps(card["gates"], indent=1)
    assert card["deaths"] >= 200_000  # a quarter of the regions died
