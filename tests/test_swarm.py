"""Swarm harness acceptance (scenario/swarm.py — PR 10).

Tier 1 runs the ~32-client ``swarm`` spec end-to-end over loopback HTTP
and requires the scorecard to pass: every client registered, the
matchmaking economy flowed, the request p99 was measured from
``bkw_server_request_seconds``, the event loop never stalled past
budget, and no sqlite commit ran on the loop thread.  A second tier-1
run pins the LEGACY tier's expected contrast: its direct-commit store
commits on the event loop (the baseline shape, ROADMAP D3b).  The
192-client load shape and the measured speedup legs are slow.
"""

import asyncio
import dataclasses

import pytest

from backuwup_tpu.obs import metrics as obs_metrics
from backuwup_tpu.scenario import (MatchLoadSpec, builtin_swarms,
                                   run_match_load, run_swarm)

pytestmark = pytest.mark.swarm


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


@pytest.mark.timeout(240)
def test_swarm_acceptance(tmp_path, loop):
    spec = builtin_swarms()["swarm"]
    card, summary = loop.run_until_complete(run_swarm(spec, tmp_path))
    assert card.passed, card.render()
    gates = {a.name: a.passed for a in card.assertions}
    assert gates.get("loop_stall_under_budget") is True
    assert gates.get("commits_off_event_loop") is True
    assert summary["commits_on_loop"] is False
    assert summary["matchmakings"] > 0
    assert summary["server_p99_ms"] is not None
    # the per-route histogram fed the card's quantile section
    assert any(k.startswith("bkw_server_request_seconds")
               for k in card.quantiles), card.quantiles
    # the write-behind store really group-committed during the run
    assert summary["commits"]["group"] > 0
    assert summary["commits"]["direct"] == 0


@pytest.mark.timeout(240)
def test_swarm_legacy_commits_on_loop(tmp_path, loop):
    """The baseline contrast: the legacy tier's
    direct-commit store fsyncs on the event-loop thread (visible in
    ``commit_threads``), which is exactly what the sharded tier's
    ``commits_off_event_loop`` gate forbids."""
    spec = dataclasses.replace(builtin_swarms()["swarm"], name="swarm_legacy",
                               seed=102, legacy=True)
    card, summary = loop.run_until_complete(run_swarm(spec, tmp_path))
    assert card.passed, card.render()
    assert summary["commits_on_loop"] is True
    assert summary["commits"]["direct"] > 0
    assert summary["matchmakings"] > 0


def test_match_load_smoke(tmp_path):
    """Both legs produce matches on a short window (the slow test
    below compares their rates)."""
    spec = MatchLoadSpec(clients=16, duration_s=0.3, audit_history=64)
    legacy = run_match_load(dataclasses.replace(spec, legacy=True), tmp_path)
    sharded = run_match_load(spec, tmp_path)
    for leg in (legacy, sharded):
        assert leg["matchmakings"] > 0
        assert leg["matchmakings_per_s"] > 0
    assert legacy["tier"] == "legacy" and sharded["tier"] == "sharded"


@pytest.mark.slow
@pytest.mark.timeout(300)
def test_swarm_full_load_shape(tmp_path, loop):
    card, summary = loop.run_until_complete(
        run_swarm(builtin_swarms()["swarm_full"], tmp_path))
    assert card.passed, card.render()
    assert summary["commits_on_loop"] is False


@pytest.mark.slow
@pytest.mark.timeout(300)
def test_match_load_speedup(tmp_path):
    """The load shape at full weight; the test bound is kept
    conservative (>= 1.3x) so scheduler noise cannot flake it while a
    real regression — sharded no faster than the single lock — still
    fails loudly."""
    spec = MatchLoadSpec()
    legacy = run_match_load(dataclasses.replace(spec, legacy=True), tmp_path)
    sharded = run_match_load(spec, tmp_path)
    speedup = sharded["matchmakings_per_s"] / legacy["matchmakings_per_s"]
    assert speedup >= 1.3, (legacy, sharded)
