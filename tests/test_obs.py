"""Observability plane: metrics registry, correlated traces, journal,
and the /metrics + /healthz exposition (docs/observability.md)."""

import asyncio
import json
import os
import random
import subprocess
import sys
import threading

import pytest

from backuwup_tpu import wire
from backuwup_tpu.obs import journal as obs_journal
from backuwup_tpu.obs import metrics as obs_metrics
from backuwup_tpu.obs import trace as obs_trace
from backuwup_tpu.obs.journal import Journal
from backuwup_tpu.obs.metrics import MetricError, Registry, log_buckets
from backuwup_tpu.ui.messenger import Messenger


@pytest.fixture(autouse=True)
def _isolate():
    """Zero the process registry and drop any installed journal so tests
    never see each other's series."""
    obs_metrics.registry().reset()
    yield
    obs_metrics.registry().reset()
    obs_journal.uninstall()


@pytest.fixture
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


# --- registry ---------------------------------------------------------------

def test_counter_concurrent_exactness():
    reg = Registry()
    c = reg.counter("t_total", "x", ("worker",))

    def work(w):
        for _ in range(2000):
            c.inc(worker=w)
            c.inc(worker="shared")

    threads = [threading.Thread(target=work, args=(f"w{i}",))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(8):
        assert c.value(worker=f"w{i}") == 2000
    assert c.value(worker="shared") == 16000


def test_histogram_bucket_edges():
    reg = Registry()
    h = reg.histogram("t_seconds", "x", buckets=(0.001, 0.002, 0.004))
    # le semantics: a value exactly on a bound lands IN that bucket
    h.observe(0.001)
    h.observe(0.0015)
    h.observe(0.004)
    h.observe(5.0)  # past the last bound: +Inf only
    b = h.bucket_counts()
    assert b["0.001"] == 1
    assert b["0.002"] == 2
    assert b["0.004"] == 3
    assert b["+Inf"] == 4
    assert h.count_value() == 4
    assert h.sum_value() == pytest.approx(5.0065)


def test_log_buckets_geometry():
    assert log_buckets(0.001, 2.0, 4) == (0.001, 0.002, 0.004, 0.008)
    with pytest.raises(MetricError):
        Registry().histogram("t", buckets=())


def test_prometheus_render_golden():
    reg = Registry()
    reg.counter("app_requests_total", "Requests served",
                ("path",)).inc(3, path="/x")
    reg.gauge("app_depth", "Queue depth").set(2)
    h = reg.histogram("app_lat_seconds", "Latency", buckets=(0.5, 1.0))
    h.observe(0.25)
    h.observe(0.75)
    assert reg.render_prometheus() == (
        "# HELP app_depth Queue depth\n"
        "# TYPE app_depth gauge\n"
        "app_depth 2\n"
        "# HELP app_lat_seconds Latency\n"
        "# TYPE app_lat_seconds histogram\n"
        'app_lat_seconds_bucket{le="0.5"} 1\n'
        'app_lat_seconds_bucket{le="1"} 2\n'
        'app_lat_seconds_bucket{le="+Inf"} 2\n'
        "app_lat_seconds_sum 1\n"
        "app_lat_seconds_count 2\n"
        "# HELP app_requests_total Requests served\n"
        "# TYPE app_requests_total counter\n"
        'app_requests_total{path="/x"} 3\n')


def test_prometheus_render_escaping_golden():
    # exposition-format escaping pin: backslash FIRST, then newline and
    # quote — a value like '\n' must render '\\n', never '\\\\n' or a
    # literal line break that tears the sample line
    reg = Registry()
    reg.counter("app_weird_total", 'help with \\ and\nnewline',
                ("path",)).inc(1, path='a\\b"c\nd')
    assert reg.render_prometheus() == (
        "# HELP app_weird_total help with \\\\ and\\nnewline\n"
        "# TYPE app_weird_total counter\n"
        'app_weird_total{path="a\\\\b\\"c\\nd"} 1\n')
    # the escaped exposition must round-trip through a line-oriented
    # parser: exactly 3 lines, the sample line intact
    lines = reg.render_prometheus().splitlines()
    assert len(lines) == 3 and lines[2].endswith("} 1")


def test_family_conflicts():
    reg = Registry()
    c = reg.counter("t_total", "x", ("a",))
    assert reg.counter("t_total", "different help", ("a",)) is c
    with pytest.raises(MetricError):
        reg.histogram("t_total")  # type mismatch
    with pytest.raises(MetricError):
        reg.counter("t_total", "x", ("b",))  # labelnames mismatch


# --- journal ----------------------------------------------------------------

def test_journal_rotation_and_tail(tmp_path):
    j = Journal(tmp_path / "j.jsonl", max_bytes=600, keep=2)
    for i in range(60):
        j.emit("tick", n=i)
    j.close()
    assert j.rotations > 0
    assert (tmp_path / "j.jsonl.1").exists()
    # no generation beyond keep survives
    assert not (tmp_path / "j.jsonl.3").exists()
    tail = j.tail(20)
    assert len(tail) == 20
    # ordered across the rotation boundary, newest last
    assert [r["n"] for r in tail] == list(range(40, 60))
    assert all(r["kind"] == "tick" for r in tail)


def test_journal_rotation_under_concurrent_writers(tmp_path):
    # two writer threads race emit() across dozens of rotation
    # boundaries: every line must parse (no torn writes) and every
    # event must survive (no line lost to a mid-rotation rename)
    j = Journal(tmp_path / "j.jsonl", max_bytes=2000, keep=20)
    per_writer = 150
    barrier = threading.Barrier(2)

    def writer(tag):
        barrier.wait()
        for i in range(per_writer):
            j.emit("tick", w=tag, n=i)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    j.close()
    assert j.rotations > 2
    seen = {"a": [], "b": []}
    for path in j.files():
        for line in path.read_text(encoding="utf-8").splitlines():
            rec = json.loads(line)  # a torn line would raise here
            seen[rec["w"]].append(rec["n"])
    assert sorted(seen["a"]) == list(range(per_writer))
    assert sorted(seen["b"]) == list(range(per_writer))
    assert j.lines_written == 2 * per_writer


def test_journal_panic_dump(tmp_path):
    obs_journal.install(Journal(tmp_path / "j.jsonl"))
    obs_metrics.counter("t_panic_total", "x").inc(7)
    obs_journal.emit("status", event="before")
    path = obs_journal.panic("it broke")
    doc = json.loads(path.read_text())
    assert doc["message"] == "it broke"
    assert doc["metrics"]["t_panic_total"]["series"][0]["value"] == 7
    kinds = [r["kind"] for r in doc["journal_tail"]]
    assert "status" in kinds and "panic" in kinds


def test_journal_emit_without_install_is_noop():
    obs_journal.uninstall()
    obs_journal.emit("status", event="dropped")  # must not raise
    assert obs_journal.panic("nobody home") is None


# --- traces -----------------------------------------------------------------

def test_span_nesting_journals_one_trace(tmp_path):
    obs_journal.install(Journal(tmp_path / "j.jsonl"))
    with obs_trace.span("outer"):
        tid = obs_trace.current_trace_id()
        outer_sid = obs_trace.current_span_id()
        with obs_trace.span("inner"):
            assert obs_trace.current_trace_id() == tid
    recs = {r["name"]: r for r in obs_journal.get().tail(10)
            if r["kind"] == "span"}
    assert recs["inner"]["trace_id"] == recs["outer"]["trace_id"] == tid
    assert recs["inner"]["parent_id"] == outer_sid
    assert recs["outer"]["parent_id"] is None


def test_span_seconds_histogram_always_observes():
    h = obs_metrics.registry().get("bkw_span_seconds")
    n0, s0 = (h.count_value(name="obs_test.work"),
              h.sum_value(name="obs_test.work"))
    for _ in range(2):
        with obs_trace.span("obs_test.work") as ctx:
            assert obs_trace.current().name == "obs_test.work"
            assert ctx.span_id == obs_trace.current_span_id()
    # no gate and no second table: the histogram's count and sum are
    # the per-name aggregate
    assert h.count_value(name="obs_test.work") == n0 + 2
    assert h.sum_value(name="obs_test.work") > s0


def test_clean_trace_id():
    assert obs_trace.clean_trace_id("deadbeef") == "deadbeef"
    assert obs_trace.clean_trace_id("A" * 8) is None
    assert obs_trace.clean_trace_id("g" * 8) is None
    assert obs_trace.clean_trace_id("0" * 33) is None
    assert obs_trace.clean_trace_id("") is None
    assert obs_trace.clean_trace_id(None) is None


def test_wire_trace_id_roundtrip():
    env = wire.EncapsulatedMsg(body=b"b" * 10, signature=b"s" * 64,
                               trace_id="deadbeefcafe0123")
    out = wire.EncapsulatedMsg.decode_bytes(env.encode_bytes())
    assert out.trace_id == "deadbeefcafe0123"
    # absent field (an old peer's frame) decodes as None
    bare = wire.EncapsulatedMsg(body=b"b" * 10, signature=b"s" * 64)
    assert wire.EncapsulatedMsg.decode_bytes(bare.encode_bytes()).trace_id \
        is None


# --- messenger --------------------------------------------------------------

def test_messenger_flushes_final_progress_on_finish():
    m = Messenger(debounce_s=3600.0)
    events = []
    m.subscribe(events.append)
    m.backup_started()
    m.progress(file="a.txt")  # first one passes the debounce gate
    m.progress(file="b.txt")  # debounced away
    m.backup_finished(b"\x01" * 32)
    kinds = [e.kind for e in events]
    assert kinds == ["backup_started", "progress", "progress",
                     "backup_finished"]
    final = events[-2].payload
    assert final["files_done"] == 2  # the debounced update was not lost
    assert final["running"] is False


def test_messenger_counts_and_logs_subscriber_errors(caplog):
    m = Messenger()
    good = []

    def bad(event):
        raise RuntimeError("boom")

    m.subscribe(bad)
    m.subscribe(good.append)
    with caplog.at_level("ERROR", logger="backuwup_tpu.ui.messenger"):
        for i in range(3):
            m.log(f"msg {i}")
    assert len(good) == 3  # a broken subscriber never starves the rest
    errs = obs_metrics.registry().get(
        "bkw_messenger_subscriber_errors_total")
    label = bad.__qualname__
    assert errs.value(subscriber=label) == 3
    logged = [r for r in caplog.records if label in r.getMessage()]
    assert len(logged) == 1  # first failure only


# --- exposition -------------------------------------------------------------

def test_server_metrics_and_healthz(tmp_path, loop):
    import aiohttp

    from backuwup_tpu.net.server import CoordinationServer

    async def run():
        server = CoordinationServer(db_path=str(tmp_path / "s.db"))
        port = await server.start()
        async with aiohttp.ClientSession() as http:
            async with http.get(
                    f"http://127.0.0.1:{port}/metrics") as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith("text/plain")
                text = await resp.text()
            async with http.get(
                    f"http://127.0.0.1:{port}/healthz") as resp:
                assert resp.status == 200
                health = await resp.json()
        await server.stop()
        # the core catalog is advertised even on a fresh server
        for name in ("bkw_transfer_send_seconds", "bkw_audit_total",
                     "bkw_repair_rounds_total",
                     "bkw_matchmaking_queue_depth",
                     "bkw_server_requests_total"):
            assert f"# TYPE {name}" in text, name
        assert health["status"] == "ok"
        assert health["queue_depth"] == 0
        assert health["uptime_s"] >= 0

    loop.run_until_complete(asyncio.wait_for(run(), 30))


def test_client_server_trace_propagation(tmp_path, loop):
    from backuwup_tpu.crypto import KeyManager
    from backuwup_tpu.net.client import ServerClient
    from backuwup_tpu.net.server import CoordinationServer
    from backuwup_tpu.store import Store

    obs_journal.install(Journal(tmp_path / "j.jsonl"))

    async def run():
        server = CoordinationServer(db_path=str(tmp_path / "s.db"))
        port = await server.start()
        keys = KeyManager.from_secret(b"\x05" * 32)
        store = Store(tmp_path / "c")
        c = ServerClient(keys, store, addr=f"127.0.0.1:{port}")
        await c.register()
        await c.login()
        with obs_trace.span("test.op"):
            tid = obs_trace.current_trace_id()
            await c.backup_done(b"\x01" * 32)
        await c.close()
        store.close()
        await server.stop()
        return tid

    tid = loop.run_until_complete(asyncio.wait_for(run(), 30))
    spans = [r for r in obs_journal.get().tail(200) if r["kind"] == "span"]
    server_side = [r for r in spans
                   if r["name"] == "server/backups/done"
                   and r["trace_id"] == tid]
    assert server_side, "server handler span must join the client's trace"


def test_obs_runs_without_accelerator(tmp_path):
    """Tier-1 guard: the whole plane imports and instruments on a bare
    CPU process with no accelerator runtime."""
    prog = (
        "from backuwup_tpu.obs import journal, metrics, trace\n"
        "from backuwup_tpu.obs.journal import Journal\n"
        "journal.install(Journal(r'%s'))\n"
        "metrics.counter('g_total', 'x').inc()\n"
        "with trace.span('g.span'):\n"
        "    pass\n"
        "assert 'g_total 1' in metrics.registry().render_prometheus()\n"
        "assert journal.get().tail(5)[-1]['kind'] == 'span'\n"
        "print('GUARD_OK')\n" % (tmp_path / "g.jsonl"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "GUARD_OK" in out.stdout


# --- end-to-end trace join ---------------------------------------------------

def test_two_client_backup_trace_joins_peer_store(tmp_path, loop):
    """One backup's trace_id must join the sender's pack span to the
    receiving peer's store span across the p2p wire (the Dapper claim)."""
    import aiohttp

    from backuwup_tpu.app import ClientApp
    from backuwup_tpu.net.server import CoordinationServer
    from backuwup_tpu.ops.backend import CpuBackend
    from backuwup_tpu.ops.gear import CDCParams

    obs_journal.install(Journal(tmp_path / "j.jsonl"))
    rng = random.Random(7)
    for name in ("a_src", "b_src"):
        root = tmp_path / name
        (root / "sub").mkdir(parents=True)
        (root / "f.bin").write_bytes(rng.randbytes(200_000))
        (root / "sub" / "g.bin").write_bytes(rng.randbytes(80_000))

    async def run():
        server = CoordinationServer(db_path=str(tmp_path / "server.db"))
        port = await server.start()
        addr = f"127.0.0.1:{port}"
        backend = CpuBackend(CDCParams.from_desired(4096))

        def make_app(name, **kw):
            return ClientApp(config_dir=tmp_path / name / "cfg",
                             data_dir=tmp_path / name / "data",
                             server_addr=addr, backend=backend, **kw)

        a = make_app("a", status_port=0)
        b = make_app("b")
        await a.start()
        await b.start()
        assert a.status_port  # ephemeral port resolved
        a.store.set_backup_path(str(tmp_path / "a_src"))
        b.store.set_backup_path(str(tmp_path / "b_src"))
        await asyncio.wait_for(asyncio.gather(a.backup(), b.backup()), 120)

        # the opt-in client status listener serves the same registry
        async with aiohttp.ClientSession() as http:
            url = f"http://127.0.0.1:{a.status_port}"
            async with http.get(url + "/metrics") as resp:
                text = await resp.text()
            async with http.get(url + "/healthz") as resp:
                health = await resp.json()
        assert 'bkw_backup_runs_total{outcome="ok"} 2' in text
        assert health["client_id"] == a.client_id.hex()

        await a.stop()
        await b.stop()
        await server.stop()

    loop.run_until_complete(asyncio.wait_for(run(), 180))

    spans = [r for r in obs_journal.get().tail(100_000)
             if r["kind"] == "span"]
    pack_traces = {r["trace_id"] for r in spans
                   if r["name"] == "engine.pack" and r["trace_id"]}
    store_traces = {r["trace_id"] for r in spans
                    if r["name"] == "receiver.store" and r["trace_id"]}
    assert pack_traces, "pack spans must journal"
    assert store_traces, "peer store spans must journal"
    joined = pack_traces & store_traces
    assert joined, (
        "a backup's trace_id must survive the p2p wire: "
        f"pack={pack_traces} store={store_traces}")
