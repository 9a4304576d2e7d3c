"""bkwlint toolkit tests: per-rule fixtures, baseline semantics, CLI
contract, and the repo-wide tier-1 gate.

Each rule gets a positive fixture (a tiny package written into
``tmp_path`` that MUST fire) and a negative twin (the same shape with
the invariant honored, which MUST stay silent) — so the gate cannot rot
into a linter that flags nothing.
"""

import io
import json
from pathlib import Path

import pytest

import backuwup_tpu
from backuwup_tpu.analysis import (BaselineError, LintConfig, RULE_IDS,
                                   apply_baseline, collect_findings,
                                   load_baseline, load_graph,
                                   load_package, run_lint,
                                   static_crash_sites, build_graph)
from backuwup_tpu.analysis.cli import main as cli_main

REPO = Path(backuwup_tpu.__file__).resolve().parent.parent


def _mk_pkg(tmp_path, files):
    """Write ``files`` (rel -> source) as package ``pkg`` under tmp."""
    root = tmp_path / "pkg"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
        init = p.parent / "__init__.py"
        if not init.exists():
            init.write_text("")
    if not (root / "__init__.py").exists():
        (root / "__init__.py").write_text("")
    return root


def _lint(root, rules, doc_path=None, baseline_path=None):
    cfg = LintConfig(package_root=root, doc_path=doc_path,
                     baseline_path=baseline_path, rules=set(rules))
    return run_lint(cfg)


# --- BKW001: blocking I/O reachable from async ------------------------------


def test_bkw001_flags_blocking_reachable_through_sync_helper(tmp_path):
    root = _mk_pkg(tmp_path, {"a.py": (
        "import time\n"
        "def helper():\n"
        "    time.sleep(1)\n"
        "async def serve():\n"
        "    helper()\n")})
    report = _lint(root, {"BKW001"})
    assert len(report.findings) == 1
    f = report.findings[0]
    assert f.rule == "BKW001" and "time.sleep" in f.message
    assert "serve" in f.message and "helper" in f.message


def test_bkw001_executor_seam_and_nested_defs_are_off_the_loop(tmp_path):
    root = _mk_pkg(tmp_path, {"a.py": (
        "import asyncio, time\n"
        "class Engine:\n"
        "    @staticmethod\n"
        "    async def _blocking(fn, *args):\n"
        "        loop = asyncio.get_running_loop()\n"
        "        return await loop.run_in_executor(None, fn, *args)\n"
        "    def commit(self):\n"
        "        time.sleep(1)\n"
        "    async def serve(self):\n"
        "        await self._blocking(self.commit)\n"
        "        def pack_thread():\n"
        "            time.sleep(2)\n"
        "        loop = asyncio.get_running_loop()\n"
        "        await loop.run_in_executor(None, pack_thread)\n")})
    assert _lint(root, {"BKW001"}).findings == []


def test_bkw001_sqlite_and_alias_normalization(tmp_path):
    root = _mk_pkg(tmp_path, {"a.py": (
        "import sqlite3 as sq\n"
        "async def serve():\n"
        "    sq.connect(':memory:')\n")})
    report = _lint(root, {"BKW001"})
    assert len(report.findings) == 1
    assert "sqlite3" in report.findings[0].message


def test_bkw001_loop_scheduled_callback_is_a_root(tmp_path):
    # a sync callable handed to call_soon_threadsafe runs ON the loop
    # thread — blocking work inside it must fire even though no async
    # body ever calls it
    root = _mk_pkg(tmp_path, {"a.py": (
        "import asyncio, time\n"
        "def wake():\n"
        "    time.sleep(1)\n"
        "def writer_thread(loop):\n"
        "    loop.call_soon_threadsafe(wake)\n")})
    report = _lint(root, {"BKW001"})
    assert len(report.findings) == 1
    f = report.findings[0]
    assert "time.sleep" in f.message and "wake" in f.message
    assert "call_soon_threadsafe" in f.message


def test_bkw001_event_setting_callback_and_done_callback(tmp_path):
    # the dataflow wakeup shape: a callback that only sets an event is
    # clean, and add_done_callback targets are scanned the same way
    root = _mk_pkg(tmp_path, {"a.py": (
        "import asyncio, time\n"
        "class Orch:\n"
        "    def __init__(self):\n"
        "        self.ev = asyncio.Event()\n"
        "    def notify(self):\n"
        "        self.ev.set()\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self.orch = Orch()\n"
        "    def writer_thread(self, loop):\n"
        "        loop.call_soon_threadsafe(self.orch.notify)\n"
        "def log_done(fut):\n"
        "    time.sleep(1)\n"
        "async def serve():\n"
        "    fut = asyncio.get_running_loop().create_future()\n"
        "    fut.add_done_callback(log_done)\n")})
    report = _lint(root, {"BKW001"})
    assert len(report.findings) == 1
    f = report.findings[0]
    assert "log_done" in f.message and "add_done_callback" in f.message


# --- BKW002: lock held across await -----------------------------------------


def test_bkw002_flags_await_under_threading_lock(tmp_path):
    root = _mk_pkg(tmp_path, {"a.py": (
        "import threading, asyncio\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    async def go(self):\n"
        "        with self._lock:\n"
        "            await asyncio.sleep(0)\n")})
    report = _lint(root, {"BKW002"})
    assert len(report.findings) == 1
    f = report.findings[0]
    assert f.severity == "error" and "threading.Lock" in f.message


def test_bkw002_silent_without_await_or_with_asyncio_lock(tmp_path):
    root = _mk_pkg(tmp_path, {"a.py": (
        "import threading, asyncio\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._alock = asyncio.Lock()\n"
        "    async def sync_crit(self):\n"
        "        with self._lock:\n"
        "            x = 1\n"
        "        await asyncio.sleep(0)\n"
        "    async def async_crit(self):\n"
        "        async with self._alock:\n"
        "            await asyncio.sleep(0)\n")})
    assert _lint(root, {"BKW002"}).findings == []


def test_bkw002_lock_like_unresolved_name_is_warning(tmp_path):
    root = _mk_pkg(tmp_path, {"a.py": (
        "import asyncio\n"
        "async def go(lock):\n"
        "    with lock:\n"
        "        await asyncio.sleep(0)\n")})
    report = _lint(root, {"BKW002"})
    assert len(report.findings) == 1
    assert report.findings[0].severity == "warning"


# --- BKW003: crash-seam coverage --------------------------------------------

_FAULTS_STUB = (
    "CRASH_SITES = set()\n"
    "def register_crash_site(site):\n"
    "    CRASH_SITES.add(site)\n"
    "    return site\n"
    "def crashpoint(site):\n"
    "    pass\n")


def test_bkw003_uncovered_commit_and_dead_site(tmp_path):
    root = _mk_pkg(tmp_path, {
        "utils/faults.py": _FAULTS_STUB,
        "utils/durable.py": "def commit_replace(p, b):\n    pass\n",
        "a.py": (
            "from .utils import durable, faults\n"
            "_CP = faults.register_crash_site('a.never_called')\n"
            "def commit(p, b):\n"
            "    durable.commit_replace(p, b)\n")})
    report = _lint(root, {"BKW003"})
    anchors = {f.anchor for f in report.findings}
    assert "seam:commit:durable.commit_replace" in anchors
    assert "dead-site:a.never_called" in anchors


def test_bkw003_lexical_callee_and_caller_coverage(tmp_path):
    root = _mk_pkg(tmp_path, {
        "utils/faults.py": _FAULTS_STUB,
        "utils/durable.py": "def commit_replace(p, b):\n    pass\n",
        "a.py": (
            "from .utils import durable, faults\n"
            "_CP = faults.register_crash_site('a.commit')\n"
            "_CP2 = faults.register_crash_site('a.append')\n"
            "class Index:\n"
            "    def save(self):\n"
            "        faults.crashpoint(_CP)\n"
            "        durable.commit_replace('p', b'')\n"
            "    def flush(self):\n"
            "        self.save()\n"
            "class Store:\n"
            "    def append(self, b):\n"
            "        durable.commit_replace('q', b)\n"
            "class App:\n"
            "    def __init__(self):\n"
            "        self.index = Index()\n"
            "        self.partials = Store()\n"
            "    def run(self):\n"
            "        faults.crashpoint(_CP2)\n"
            "        self.partials.append(b'x')\n"
            "        self.index.flush()\n")})
    report = _lint(root, {"BKW003"})
    assert report.findings == [], \
        "\n".join(f.render() for f in report.findings)


_REPL_PKG_BODY = (
    "from .utils import durable, faults\n"
    "{consts}"
    "class OpLog:\n"
    "    def append(self, recs):\n"
    "        durable.fsync_file('p')\n"
    "    def set_epoch(self, e):\n"
    "        pass\n"
    "    def truncate_after(self, lsn):\n"
    "        pass\n"
    "class Part:\n"
    "    def __init__(self):\n"
    "        self.log = OpLog()\n"
    "    def _ship_tail(self, recs):\n"
    "        pass\n"
    "    def batch(self, recs):\n"
    "{batch_cp}"
    "        self.log.append(recs)\n"
    "        self._ship_tail(recs)\n"
    "    def promote(self):\n"
    "{promote_cp}"
    "        self.log.set_epoch(1)\n"
    "    def adopt(self):\n"
    "{adopt_cp}"
    "        self.log.truncate_after(0)\n")


def test_bkw003_replication_seams_require_crashpoints(tmp_path):
    """The op-log commit points (append / set_epoch / truncate_after),
    the ship-ack barrier, and the fsync-append helper are commit seams:
    bare, each one is a finding."""
    root = _mk_pkg(tmp_path, {
        "utils/faults.py": _FAULTS_STUB,
        "utils/durable.py": "def fsync_file(p):\n    pass\n",
        "a.py": _REPL_PKG_BODY.format(
            consts="", batch_cp="", promote_cp="", adopt_cp="")})
    report = _lint(root, {"BKW003"})
    seams = {f.message.split("(")[1].split(")")[0]
             for f in report.findings if "commit seam" in f.message}
    assert seams == {"durable.fsync_file", "oplog.append", "repl.ship",
                     "oplog.set_epoch", "oplog.truncate_after"}


def test_bkw003_replication_seams_covered_by_adjacent_crashpoints(tmp_path):
    """Crashpoints lexically beside each replication commit point clear
    every seam — including durable.fsync_file inside OpLog.append,
    covered through its direct caller (the same rule that clears the
    stage-on-executor idiom)."""
    root = _mk_pkg(tmp_path, {
        "utils/faults.py": _FAULTS_STUB,
        "utils/durable.py": "def fsync_file(p):\n    pass\n",
        "a.py": _REPL_PKG_BODY.format(
            consts=("_CP_A = faults.register_crash_site('r.append')\n"
                    "_CP_P = faults.register_crash_site('r.promote')\n"
                    "_CP_T = faults.register_crash_site('r.adopt')\n"),
            batch_cp="        faults.crashpoint(_CP_A)\n",
            promote_cp="        faults.crashpoint(_CP_P)\n",
            adopt_cp="        faults.crashpoint(_CP_T)\n")})
    report = _lint(root, {"BKW003"})
    assert report.findings == [], \
        "\n".join(f.render() for f in report.findings)


def test_bkw003_unregistered_site_literal(tmp_path):
    root = _mk_pkg(tmp_path, {
        "utils/faults.py": _FAULTS_STUB,
        "a.py": (
            "from .utils import faults\n"
            "def go():\n"
            "    faults.crashpoint('a.rogue')\n")})
    report = _lint(root, {"BKW003"})
    assert {f.anchor for f in report.findings} == {
        "unregistered-site:a.rogue"}


# --- BKW004: metrics-catalog sync -------------------------------------------

_METRICS_STUB = (
    "def counter(name, help, labelnames=()):\n    pass\n"
    "def gauge(name, help, labelnames=()):\n    pass\n"
    "def histogram(name, help, labelnames=(), buckets=None):\n    pass\n")


def _doc(tmp_path, rows):
    doc = tmp_path / "observability.md"
    body = ["| Metric | Type | Labels | Instrumented in |",
            "|---|---|---|---|"] + rows
    doc.write_text("\n".join(body) + "\n")
    return doc


def test_bkw004_undocumented_and_unconstructed(tmp_path):
    root = _mk_pkg(tmp_path, {
        "obs/metrics.py": _METRICS_STUB,
        "a.py": ("from .obs import metrics\n"
                 "C = metrics.counter('bkw_live_total', 'h')\n")})
    doc = _doc(tmp_path, ["| `bkw_ghost_total` | counter | — | x |"])
    report = _lint(root, {"BKW004"}, doc_path=doc)
    anchors = {f.anchor for f in report.findings}
    assert anchors == {"undocumented:bkw_live_total",
                       "unconstructed:bkw_ghost_total"}


def test_bkw004_label_drift_and_constant_resolution(tmp_path):
    root = _mk_pkg(tmp_path, {
        "obs/metrics.py": _METRICS_STUB,
        "a.py": ("from .obs import metrics\n"
                 "_LABELS = ('client',)\n"
                 "G = metrics.gauge('bkw_depth', 'h', _LABELS)\n")})
    good = _doc(tmp_path, ["| `bkw_depth` | gauge | `client` | a.py |"])
    assert _lint(root, {"BKW004"}, doc_path=good).findings == []
    bad = _doc(tmp_path, ["| `bkw_depth` | gauge | `peer` | a.py |"])
    report = _lint(root, {"BKW004"}, doc_path=bad)
    assert {f.anchor for f in report.findings} == {"label-drift:bkw_depth"}


def test_bkw004_conflicting_label_sets_across_sites(tmp_path):
    root = _mk_pkg(tmp_path, {
        "obs/metrics.py": _METRICS_STUB,
        "a.py": ("from .obs import metrics\n"
                 "A = metrics.counter('bkw_x_total', 'h', ('op',))\n"),
        "b.py": ("from .obs import metrics\n"
                 "B = metrics.counter('bkw_x_total', 'h', ('kind',))\n")})
    doc = _doc(tmp_path, ["| `bkw_x_total` | counter | `op` | a.py |"])
    report = _lint(root, {"BKW004"}, doc_path=doc)
    assert "conflict:bkw_x_total" in {f.anchor for f in report.findings}


# --- BKW005: wire-handler exhaustiveness ------------------------------------

_WIRE = ("import enum\n"
         "class RequestType(enum.IntEnum):\n"
         "    TRANSPORT = 0\n"
         "    AUDIT = 1\n")


def test_bkw005_unhandled_member(tmp_path):
    root = _mk_pkg(tmp_path, {
        "wire.py": _WIRE,
        "net/p2p.py": ("from .. import wire\n"
                       "def serve(t):\n"
                       "    if t == wire.RequestType.TRANSPORT:\n"
                       "        pass\n")})
    report = _lint(root, {"BKW005"})
    assert {f.anchor for f in report.findings} == {
        "unhandled:RequestType.AUDIT"}


def test_bkw005_dead_member_reference(tmp_path):
    root = _mk_pkg(tmp_path, {
        "wire.py": _WIRE,
        "net/p2p.py": ("from .. import wire\n"
                       "def serve(t):\n"
                       "    if t == wire.RequestType.TRANSPORT:\n"
                       "        pass\n"
                       "    elif t == wire.RequestType.AUDIT:\n"
                       "        pass\n"
                       "    elif t == wire.RequestType.GONE:\n"
                       "        pass\n")})
    report = _lint(root, {"BKW005"})
    assert {f.anchor for f in report.findings} == {
        "dead-member:RequestType.GONE"}


def test_bkw005_exhaustive_dispatch_is_silent(tmp_path):
    root = _mk_pkg(tmp_path, {
        "wire.py": _WIRE,
        "net/p2p.py": ("from .. import wire\n"
                       "HANDLERS = {wire.RequestType.TRANSPORT: 1,\n"
                       "            wire.RequestType.AUDIT: 2}\n")})
    assert _lint(root, {"BKW005"}).findings == []


# --- BKW006: clock-seam purity in sim-covered modules -----------------------


def test_bkw006_flags_wall_clock_in_covered_module(tmp_path):
    root = _mk_pkg(tmp_path, {
        "utils/retry.py": ("import time, asyncio\n"
                           "def due():\n"
                           "    return time.time()\n"
                           "async def pause():\n"
                           "    await asyncio.sleep(1)\n")})
    report = _lint(root, {"BKW006"})
    assert {f.anchor for f in report.findings} == {
        "due->time.time", "pause->asyncio.sleep"}
    assert all(f.severity == "error" for f in report.findings)
    assert "utils/clock.py seam" in report.findings[0].message


def test_bkw006_sim_tree_is_covered_and_others_are_not(tmp_path):
    root = _mk_pkg(tmp_path, {
        "sim/driver.py": ("import time\n"
                          "def tick():\n"
                          "    return time.monotonic()\n"),
        "engine.py": ("import time\n"
                      "def stamp():\n"
                      "    return time.time()\n")})
    report = _lint(root, {"BKW006"})
    assert {f.path for f in report.findings} == {"sim/driver.py"}


def test_bkw006_seam_calls_are_silent(tmp_path):
    root = _mk_pkg(tmp_path, {
        "net/peer_stats.py": (
            "from ..utils import clock as clockmod\n"
            "class PeerStats:\n"
            "    def __init__(self, clock=None):\n"
            "        self.clock = clockmod.resolve(clock)\n"
            "    def observe(self):\n"
            "        return self.clock.now()\n"),
        "utils/clock.py": ("def resolve(c):\n"
                           "    return c\n")})
    assert _lint(root, {"BKW006"}).findings == []


# --- BKW007: SLO-catalog sync -----------------------------------------------


def _slo_pkg(tmp_path, catalog,
             construct="C = metrics.counter('bkw_v_total', 'h',"
                       " ('client',))\n"):
    return _mk_pkg(tmp_path, {
        "obs/metrics.py": _METRICS_STUB,
        "a.py": "from .obs import metrics\n" + construct,
        "defaults.py": f"SLO_CATALOG = {catalog!r}\n"})


def _slo_doc(tmp_path, rows):
    doc = tmp_path / "observability.md"
    body = ["| Objective | Kind | Signal family | Budget |",
            "|---|---|---|---|"] + rows
    doc.write_text("\n".join(body) + "\n")
    return doc


_GOOD_ENTRY = {"id": "durability", "kind": "counter_rate",
               "family": "bkw_v_total", "budget": 0.001}


def test_bkw007_clean_catalog_and_doc(tmp_path):
    root = _slo_pkg(tmp_path, (_GOOD_ENTRY,))
    doc = _slo_doc(tmp_path, [
        "| `durability` | counter_rate | `bkw_v_total` | 0.001 |"])
    assert _lint(root, {"BKW007"}, doc_path=doc).findings == []


def test_bkw007_unknown_family_and_label_drift(tmp_path):
    ghost = dict(_GOOD_ENTRY, id="ghost", family="bkw_ghost_total")
    drift = dict(_GOOD_ENTRY, id="drift", labels={"peer": "x"})
    root = _slo_pkg(tmp_path, (_GOOD_ENTRY, ghost, drift))
    doc = _slo_doc(tmp_path, [
        "| `durability` | counter_rate | `bkw_v_total` | 0.001 |",
        "| `ghost` | counter_rate | `bkw_ghost_total` | 0.001 |",
        "| `drift` | counter_rate | `bkw_v_total` | 0.001 |"])
    report = _lint(root, {"BKW007"}, doc_path=doc)
    assert {f.anchor for f in report.findings} == {
        "slo-unknown-family:ghost:family", "slo-label-drift:drift"}


def test_bkw007_doc_sync_both_directions(tmp_path):
    root = _slo_pkg(tmp_path, (_GOOD_ENTRY,))
    # missing row -> undocumented; stale row -> uncatalogued; a row
    # naming the wrong family -> doc-family-drift
    doc = _slo_doc(tmp_path, [
        "| `durability` | counter_rate | `bkw_other_total` | 0.001 |",
        "| `retired` | counter_rate | `bkw_v_total` | 0.01 |"])
    report = _lint(root, {"BKW007"}, doc_path=doc)
    assert {f.anchor for f in report.findings} == {
        "slo-doc-family-drift:durability", "slo-uncatalogued:retired"}
    report = _lint(root, {"BKW007"}, doc_path=_slo_doc(tmp_path, []))
    assert {f.anchor for f in report.findings} == {
        "slo-undocumented:durability"}


def test_bkw007_malformed_entries_and_unparsable_catalog(tmp_path):
    bad_kind = dict(_GOOD_ENTRY, id="weird", kind="percentile")
    no_total = {"id": "stalls", "kind": "ratio",
                "family": "bkw_v_total", "budget": 0.02}
    root = _slo_pkg(tmp_path, (bad_kind, no_total))
    doc = _slo_doc(tmp_path, [])
    report = _lint(root, {"BKW007"}, doc_path=doc)
    assert {f.anchor for f in report.findings} == {
        "slo-bad-entry:weird", "slo-bad-entry:stalls"}
    root = _mk_pkg(tmp_path / "dyn", {
        "obs/metrics.py": _METRICS_STUB,
        "defaults.py": "SLO_CATALOG = tuple(build())\n"})
    report = _lint(root, {"BKW007"}, doc_path=doc)
    assert {f.anchor for f in report.findings} == {
        "slo-unparsable-catalog"}


# --- baseline semantics -----------------------------------------------------


def _one_finding_pkg(tmp_path):
    return _mk_pkg(tmp_path, {"a.py": (
        "import time\n"
        "async def serve():\n"
        "    time.sleep(1)\n")})


def test_baseline_suppresses_and_expires(tmp_path):
    root = _one_finding_pkg(tmp_path)
    cfg = LintConfig(package_root=root, rules={"BKW001"})
    key = collect_findings(cfg)[0].key
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"key": key, "justification": "deliberate for the fixture"}]}))
    report = _lint(root, {"BKW001"}, baseline_path=bl)
    assert report.findings == [] and len(report.baselined) == 1
    assert report.clean
    # fix the code: the entry goes stale and the report is NOT clean
    (root / "a.py").write_text("async def serve():\n    pass\n")
    report = _lint(root, {"BKW001"}, baseline_path=bl)
    assert report.findings == [] and not report.clean
    assert [e["key"] for e in report.stale_baseline] == [key]


def test_baseline_requires_justification_and_unique_keys(tmp_path):
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"key": "BKW001:a.py:x", "justification": "  "}]}))
    with pytest.raises(BaselineError):
        load_baseline(bl)
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"key": "k", "justification": "a"},
        {"key": "k", "justification": "b"}]}))
    with pytest.raises(BaselineError):
        load_baseline(bl)
    bl.write_text("{not json")
    with pytest.raises(BaselineError):
        load_baseline(bl)


def test_finding_keys_are_line_independent(tmp_path):
    root = _one_finding_pkg(tmp_path)
    cfg = LintConfig(package_root=root, rules={"BKW001"})
    key = collect_findings(cfg)[0].key
    src = (root / "a.py").read_text()
    (root / "a.py").write_text("# a comment\n\n" + src)
    assert collect_findings(cfg)[0].key == key


# --- CLI contract -----------------------------------------------------------


def test_cli_json_schema_and_exit_codes(tmp_path):
    root = _one_finding_pkg(tmp_path)
    out = io.StringIO()
    rc = cli_main([str(root), "--rule", "BKW001", "--format", "json"],
                  out=out)
    assert rc == 1
    doc = json.loads(out.getvalue())
    assert doc["version"] == 1 and doc["clean"] is False
    (f,) = doc["findings"]
    assert set(f) == {"rule", "severity", "path", "line", "message",
                      "key"}
    assert f["rule"] == "BKW001" and f["path"] == "a.py"
    # unknown rule -> usage error
    assert cli_main([str(root), "--rule", "BKW999"]) == 2
    # missing package root -> usage error
    assert cli_main([str(tmp_path / "nope")]) == 2


def test_cli_stale_baseline_exit_code(tmp_path):
    root = _mk_pkg(tmp_path, {"a.py": "async def ok():\n    pass\n"})
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"key": "BKW001:a.py:gone->time.sleep",
         "justification": "was deliberate once"}]}))
    out = io.StringIO()
    rc = cli_main([str(root), "--rule", "BKW001", "--baseline", str(bl)],
                  out=out)
    assert rc == 3
    assert "stale" in out.getvalue()


def test_cli_write_baseline_round_trips(tmp_path):
    root = _one_finding_pkg(tmp_path)
    bl = tmp_path / "bl.json"
    out = io.StringIO()
    assert cli_main([str(root), "--rule", "BKW001",
                     "--write-baseline", str(bl)], out=out) == 0
    doc = json.loads(bl.read_text())
    assert doc["version"] == 1 and len(doc["entries"]) == 1
    # the placeholder stamp gates: a suppression nobody justified is
    # exit 3 until the entry is edited
    out = io.StringIO()
    rc = cli_main([str(root), "--rule", "BKW001", "--baseline", str(bl)],
                  out=out)
    assert rc == 3
    assert "TODO placeholder" in out.getvalue()
    doc["entries"][0]["justification"] = "deliberate: startup-only path"
    bl.write_text(json.dumps(doc))
    rc = cli_main([str(root), "--rule", "BKW001", "--baseline", str(bl)],
                  out=io.StringIO())
    assert rc == 0


def test_cli_write_baseline_with_justification(tmp_path):
    """``--justification`` stamps every written entry with a real
    reason, so the round trip is immediately clean."""
    root = _one_finding_pkg(tmp_path)
    bl = tmp_path / "bl.json"
    assert cli_main([str(root), "--rule", "BKW001",
                     "--write-baseline", str(bl),
                     "--justification",
                     "batch exception: legacy sync seam"],
                    out=io.StringIO()) == 0
    doc = json.loads(bl.read_text())
    assert all(e["justification"] == "batch exception: legacy sync seam"
               for e in doc["entries"])
    assert cli_main([str(root), "--rule", "BKW001", "--baseline", str(bl)],
                    out=io.StringIO()) == 0


def test_unjustified_baseline_entries_reported(tmp_path):
    """apply_baseline routes TODO-prefixed matched entries into
    ``report.unjustified`` (json view included), and ``clean`` is
    False until they are edited."""
    root = _one_finding_pkg(tmp_path)
    cfg = LintConfig(package_root=root, doc_path=None,
                     baseline_path=None, rules={"BKW001"})
    findings = collect_findings(cfg)
    assert findings
    baseline = {findings[0].key: "TODO: justify this exception"}
    report = apply_baseline(findings, baseline)
    assert not report.findings and not report.stale_baseline
    assert [e["key"] for e in report.unjustified] == [findings[0].key]
    assert not report.clean
    assert report.to_dict()["unjustified"]


# --- the repo-wide tier-1 gate ----------------------------------------------


def test_repo_is_lint_clean():
    """The gate: zero unbaselined findings and zero stale baseline
    entries across all six rules on the real tree."""
    report = run_lint(LintConfig.for_repo(REPO))
    assert report.findings == [], \
        "\n".join(f.render() for f in report.findings)
    assert report.stale_baseline == [], report.stale_baseline
    assert report.clean


#: The whole environment surface of the package: operator and safety
#: settings.  A new name here is a new option: it needs a product caller
#: that passes more than one value.
BKW_ENV_NAMES = {
    "BKW_FAULTS", "BKW_FSYNC", "BKW_JOURNAL", "BKW_STATUS_PORT",
    "BKW_TRACE_DIR",
}


def test_repo_environment_switches_are_the_kept_five():
    """Every ``BKW_*`` name in the package's sources (read from the
    environment, or only mentioned) is one of the kept five, and each of
    the five is still read: a quiet knob, or a dead one, fails here."""
    import re
    pkg = REPO / "backuwup_tpu"
    named, read = set(), set()
    for path in pkg.rglob("*.py"):
        src = path.read_text()
        named.update(re.findall(r"\bBKW_[A-Z0-9_]+", src))
        read.update(re.findall(
            r"""environ(?:\.get\(|\[)\s*["\'](BKW_[A-Z0-9_]+)""", src))
    assert named == BKW_ENV_NAMES, sorted(named ^ BKW_ENV_NAMES)
    assert read == BKW_ENV_NAMES, sorted(read ^ BKW_ENV_NAMES)


def test_repo_baseline_entries_all_match(tmp_path):
    """Every baseline entry matches a real finding (apply_baseline in
    reverse: nothing stale), and carries a real justification."""
    bl = load_baseline(REPO / ".bkwlint-baseline.json")
    cfg = LintConfig.for_repo(REPO)
    findings = collect_findings(cfg)
    keys = {f.key for f in findings}
    for key, why in bl.items():
        assert key in keys, f"stale baseline entry: {key}"
        assert len(why.strip()) > 10


def test_repo_rule_ids_cover_all_emitted_findings():
    cfg = LintConfig.for_repo(REPO)
    for f in collect_findings(cfg):
        assert f.rule in RULE_IDS


def test_static_crash_sites_match_runtime_registry():
    """The registry fills at import time, so import exactly the modules
    the static pass says register sites, then demand equality."""
    import importlib

    from backuwup_tpu.analysis.rules_crash import collect_registry
    from backuwup_tpu.utils import faults
    graph = load_graph(REPO / "backuwup_tpu")
    registered, _ = collect_registry(graph)
    for rel, _line in registered.values():
        mod = "backuwup_tpu." + rel[:-3].replace("/", ".")
        importlib.import_module(mod)
    assert static_crash_sites(graph) == set(faults.crash_sites())


def test_loader_survives_syntax_error(tmp_path):
    root = _mk_pkg(tmp_path, {"a.py": "def broken(:\n"})
    with pytest.raises(SyntaxError) as ei:
        load_package(root)
    assert "a.py" in str(ei.value)


def test_callgraph_resolves_mixin_subclass_attrs(tmp_path):
    """The idiom BKW003's caller-coverage depends on: a mixin method
    calling through an attr only the concrete subclass assigns."""
    root = _mk_pkg(tmp_path, {"a.py": (
        "class Store:\n"
        "    def append(self, b):\n"
        "        pass\n"
        "class Mixin:\n"
        "    def sink(self, b):\n"
        "        self.partials.append(b)\n"
        "class Writer(Mixin):\n"
        "    def __init__(self):\n"
        "        self.partials = Store()\n")})
    graph = build_graph(load_package(root))
    sink = graph.functions["a.py::Mixin.sink"]
    (cs,) = [c for c in sink.calls if c.repr.endswith("append")]
    assert cs.target == "a.py::Store.append"
