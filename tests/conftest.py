"""Test harness: force an 8-device virtual CPU mesh before JAX imports.

Multi-chip hardware is not available in CI; sharding correctness is validated
on host-platform virtual devices (SURVEY.md section 7 / the driver's
``dryrun_multichip`` contract).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# plaintext loopback for the suite (the reference's local-testing posture,
# docs/src/client.md:22); tests/test_tls.py opts back in with real certs
os.environ.setdefault("USE_TLS", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent compilation cache: the blake3/CDC programs are large unrolled
# graphs; caching compiled executables across pytest runs keeps the suite
# fast after the first run.
from backuwup_tpu.utils.jaxcache import enable_compilation_cache

enable_compilation_cache()

import random
import signal
import threading

import numpy as np
import pytest

# Per-test watchdog: pytest-timeout is not installed in this container, so
# a SIGALRM-based hookwrapper stands in for it.  The default stays below
# the CI harness's outer `timeout 870` kill so a single wedged test fails
# with a readable traceback instead of taking the whole run down with it.
_WATCHDOG_DEFAULT_S = 780.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from the tier-1 run"
        " (-m 'not slow')")
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test watchdog override for the"
        " conftest SIGALRM watchdog")
    config.addinivalue_line(
        "markers", "accel: needs a real accelerator backend; skipped"
        " cleanly when jax runs on the host platform (tier-1 pins"
        " JAX_PLATFORMS=cpu)")
    config.addinivalue_line(
        "markers", "concurrency: deterministic transfer-plane overlap"
        " tests (fault-plane latency/death injection); tier-1 safe")
    config.addinivalue_line(
        "markers", "scenario: composed chaos scenario runs"
        " (scenario/harness.py); the fast seeded ones are tier-1, the"
        " full matrix is also marked slow")
    config.addinivalue_line(
        "markers", "crash: crash-consistency tests (deterministic crash"
        " injection + startup recovery sweep, docs/crash_consistency.md);"
        " the unit recoveries and the representative scenario subset are"
        " tier-1, the full matrix and the kill-9 e2e are also slow")
    config.addinivalue_line(
        "markers", "swarm: coordination-plane swarm runs (scenario/"
        "swarm.py); the ~32-client acceptance run is tier-1, the full"
        " load shape is also marked slow")
    config.addinivalue_line(
        "markers", "federation: multi-node coordination-plane tests"
        " (net/ring.py, PartitionedServerStore, cross-node work"
        " stealing, client failover); the ring/store units and the"
        " 3-node kill/revive churn swarm are tier-1, the soak is slow")
    config.addinivalue_line(
        "markers", "tiered: tiered dedup index tests (dedupstore/ hot"
        " HBM probe over the LSM cold tier, docs/dedup_tiering.md); the"
        " units and the 1e6-fingerprint parity gate are tier-1, the"
        " 1e8 soak is also marked slow")
    config.addinivalue_line(
        "markers", "replication: replicated coordination-metadata tests"
        " (op-log shipping, epoch fencing, promote-on-death,"
        " docs/server.md §Replication); the protocol units and the"
        " 3-node permakill swarm are tier-1, the soak and the kill-9"
        " promote e2e are also marked slow")
    config.addinivalue_line(
        "markers", "dataflow: streaming backup dataflow tests (bounded"
        " inter-stage queues, backpressure, event-driven seal->send"
        " wakeup, packfile-boundary parity, docs/dataflow.md); all"
        " tier-1")
    config.addinivalue_line(
        "markers", "sim: virtual-clock simulation-plane tests"
        " (backuwup_tpu/sim, docs/simulation.md); the 10^5-client"
        " simulated-week builtin is tier-1, the 10^6 soak is also"
        " marked slow")
    config.addinivalue_line(
        "markers", "slo: live SLO-plane tests (obs/series.py burn-rate"
        " windows, obs/slo.py multi-window gating, obs/diagnose.py"
        " ranked explainer, docs/observability.md §SLOs); all tier-1")


def pytest_collection_modifyitems(config, items):
    """Device-only tests (``@pytest.mark.accel``) skip on the CPU host
    platform instead of failing — mirroring the runtime-probe skip the
    blake3 device tests use, but declaratively."""
    import jax
    if jax.default_backend() != "cpu":
        return
    skip = pytest.mark.skip(reason="accel-marked test: no accelerator"
                            " backend (JAX_PLATFORMS=cpu)")
    for item in items:
        if item.get_closest_marker("accel"):
            item.add_marker(skip)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("timeout")
    limit = float(marker.args[0]) if marker and marker.args \
        else _WATCHDOG_DEFAULT_S
    # SIGALRM only fires in the main thread; under xdist/others, skip.
    use_alarm = (threading.current_thread() is threading.main_thread()
                 and hasattr(signal, "SIGALRM") and limit > 0)
    if use_alarm:
        def on_alarm(signum, frame):
            raise TimeoutError(
                f"test exceeded the {limit:.0f}s conftest watchdog"
                " (mark with @pytest.mark.timeout(N) to override)")

        previous = signal.signal(signal.SIGALRM, on_alarm)
        signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        if use_alarm:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, previous)


def pallas_interpret_works() -> bool:
    """Probe interpret-mode availability with a TRIVIAL kernel so real
    kernel bugs in the interpret test modules fail instead of skipping
    (shared by test_scan_fused_v2 / test_blake3_pallas_interpret)."""
    try:
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
    except Exception:  # pragma: no cover
        return False

    def k(o_ref):
        o_ref[...] = jnp.ones_like(o_ref)

    try:
        out = pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.uint32),
            interpret=True)()
        return bool(np.asarray(out).all())
    except Exception:  # pragma: no cover - interpreter gap on this host
        return False


@pytest.fixture
def rng():
    return random.Random(1234)


@pytest.fixture
def nprng():
    return np.random.default_rng(1234)
