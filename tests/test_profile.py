"""Pipeline profiler (obs/profile.py): dispatch accounting + reports.

The dispatch counts must be EXACT on the CPU-fallback path (tier-1 pins
``JAX_PLATFORMS=cpu``): every expected number below is an independent
hand count derived from the stage semantics documented in the
obs/profile.py module table and the file layout alone — one scan+select
per stream, one gather per stream that produced chunks, one batched
digest per ``manifest_many`` call, one index classification per pack
batch.  The e2e test runs a full backup through the scenario harness
and checks the whole acceptance bundle: non-zero per-stage counts
matching the hand count, a ``pipeline_report`` journal event, a
Perfetto-loadable timeline merging sender and receiver spans under one
trace id, and per-peer estimator rows that survive a client restart.
"""

import asyncio
import json
from pathlib import Path

import pytest

from backuwup_tpu.crypto import KeyManager
from backuwup_tpu.obs import journal as obs_journal
from backuwup_tpu.obs import metrics as obs_metrics
from backuwup_tpu.obs import profile
from backuwup_tpu.ops.backend import CpuBackend
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.scenario import Phase, ScenarioSpec, run_scenario
from backuwup_tpu.snapshot.blob_index import BlobIndex
from backuwup_tpu.snapshot.packer import DirPacker
from backuwup_tpu.snapshot.packfile import PackfileWriter
from backuwup_tpu.store import Store

KEYS = KeyManager.from_secret(bytes(range(32)))
SMALL = CDCParams.from_desired(4096)


def test_unknown_stage_rejected():
    with pytest.raises(ValueError):
        profile.dispatch("upload")


def test_dispatch_counts_manifest_many_exact(rng):
    """Hand count for one batched CPU manifest_many call: 3 streams
    (one empty) -> scan=3 select=3 gather=2 digest=1 index=0."""
    base = profile.baseline()
    streams = [rng.randbytes(20_000), rng.randbytes(5_000), b""]
    manifests = CpuBackend(SMALL).manifest_many(streams)
    rep = profile.report(base)
    assert rep["dispatches"] == {
        # one chunk() pass per stream, empty or not
        "scan": 3, "select": 3,
        # the empty stream produced no chunks, so no slicing pass
        "gather": 2,
        # ONE batched digest_many per manifest_many call
        "digest": 1,
        # no pack batch involved
        "index": 0,
    }
    total = sum(len(s) for s in streams)
    assert rep["bytes"]["scan"] == total
    assert rep["bytes"]["select"] == total
    # CDC chunks tile each stream exactly, so gather/digest bytes are
    # the non-empty payload
    assert rep["bytes"]["gather"] == total
    assert rep["bytes"]["digest"] == total
    # the CPU fallback never pads
    assert rep["pad_efficiency"]["scan"] == 1.0
    assert rep["pad_efficiency"]["digest"] == 1.0
    assert rep["pad_efficiency"]["index"] is None
    # sanity: the manifests really cover the streams
    assert [sum(r.length for r in m) for m in manifests] == \
        [len(s) for s in streams]


@pytest.mark.parametrize("dispatch_bytes, batches", [(0, 3), (1 << 20, 1)],
                         ids=["a_directory_a_batch", "one_batch"])
def test_dispatch_counts_packer_hand_count(tmp_path, rng, dispatch_bytes,
                                           batches):
    """Hand count for a DirPacker tree, everything far below
    batch_bytes, with d0=3 files, d1=2 files, root=1 file:
    scan=select=gather=6, and digest and index one per pack batch: a
    batch a directory with files where none may span directories, one
    where the three are under a dispatch's worth together."""
    src = tmp_path / "src"
    (src / "d0").mkdir(parents=True)
    (src / "d1").mkdir()
    (src / "d2").mkdir()  # empty dir: no batch, no dispatches
    layout = {"d0/a.bin": 9_000, "d0/b.bin": 7_000, "d0/c.bin": 5_000,
              "d1/d.bin": 8_000, "d1/e.bin": 6_000, "top.bin": 10_000}
    for rel, size in layout.items():
        (src / rel).write_bytes(rng.randbytes(size))

    index = BlobIndex(KEYS, tmp_path / "index")
    writer = PackfileWriter(
        KEYS, tmp_path / "pack",
        on_packfile=lambda pid, path, hashes, size:
            index.finalize_packfile(pid, hashes))
    packer = DirPacker(CpuBackend(SMALL), writer, index,
                       dispatch_bytes=dispatch_bytes)

    base = profile.baseline()
    snapshot = packer.pack(src)
    rep = profile.report(base)

    assert len(snapshot) == 32
    assert packer.stats.files == 6
    assert rep["dispatches"] == {
        "scan": 6, "select": 6, "gather": 6, "digest": batches,
        "index": batches}
    assert rep["batch"]["batches"] == batches
    assert rep["batch"]["dirs"] == 3 and rep["batch"]["batched_files"] == 6
    total = sum(layout.values())
    assert rep["bytes"]["scan"] == total
    assert rep["bytes"]["digest"] == total
    # index bytes are 32 per classified chunk ref; every chunk the
    # manifests produced was classified exactly once
    assert rep["bytes"]["index"] == 32 * packer.stats.chunks
    assert rep["pad_efficiency"]["index"] == 1.0


def test_report_is_a_delta_and_journals(tmp_path):
    jr = obs_journal.install(obs_journal.Journal(tmp_path / "j.jsonl"))
    try:
        profile.dispatch("digest", actual_bytes=100, padded_bytes=400)
        base = profile.baseline()
        profile.dispatch("digest", count=2, actual_bytes=512,
                         padded_bytes=1024)
        rep = profile.report(base)
        # the pre-baseline dispatch is invisible in the delta
        assert rep["dispatches"]["digest"] == 2
        assert rep["bytes"]["digest"] == 512
        assert rep["padded_bytes"]["digest"] == 1024
        assert rep["pad_efficiency"]["digest"] == 0.5
        assert rep["dispatches"]["scan"] == 0
        profile.emit_report(rep, snapshot="ab" * 32, backend="cpu")
    finally:
        obs_journal.uninstall()
    lines = [json.loads(l) for l in
             (tmp_path / "j.jsonl").read_text().splitlines()]
    events = [l for l in lines if l["kind"] == "pipeline_report"]
    assert len(events) == 1
    assert events[0]["report"]["dispatches"]["digest"] == 2
    assert events[0]["backend"] == "cpu"
    # the cumulative gauge tracks all-time bytes, not the delta
    eff = obs_metrics.registry().get("bkw_pipeline_pad_efficiency")
    reg = obs_metrics.registry()
    all_actual = reg.get("bkw_pipeline_stage_bytes_total")
    all_padded = reg.get("bkw_pipeline_stage_padded_bytes_total")
    assert eff.value(stage="digest") == pytest.approx(
        all_actual.value(stage="digest") / all_padded.value(stage="digest"))


# --- the e2e acceptance bundle ----------------------------------------------

@pytest.fixture
def isolated(tmp_path):
    """The test_scenario _isolate idiom: zero the process registry and
    drop any journal so this run's gauges never leak across tests."""
    obs_metrics.registry().reset()
    yield
    obs_metrics.registry().reset()
    obs_journal.uninstall()


@pytest.mark.scenario
def test_backup_e2e_perf_plane_acceptance(tmp_path, isolated):
    """One CPU-fallback backup through the loopback deployment must
    produce: non-zero per-stage dispatch counts matching an independent
    hand count, a pipeline_report journal event, a Perfetto-loadable
    timeline merging sender and receiver spans under one trace id, and
    persisted per-peer estimator rows that survive a client restart."""
    from backuwup_tpu.obs import timeline as obs_timeline

    spec = ScenarioSpec(name="perf_e2e", seed=7,
                        phases=(Phase("backup"),))
    jpath = tmp_path / "journal.jsonl"
    obs_journal.install(obs_journal.Journal(jpath))
    base = profile.baseline()
    loop = asyncio.new_event_loop()
    try:
        card = loop.run_until_complete(
            run_scenario(spec, tmp_path / "run"))
    finally:
        loop.close()
        obs_journal.uninstall()
    assert card.passed, card.render()
    # the scorecard's own telemetry gate fired on real deltas
    assert any(a.name == "telemetry_flowing" and a.passed
               for a in card.assertions)

    # 1) dispatch counts: the harness corpus is 6 small files split
    # d0/d1, so the packer hand count is scan=select=gather=6,
    # digest=1, index=1 (the two directories are one pack batch)
    rep = profile.report(base)
    assert rep["dispatches"] == {
        "scan": 6, "select": 6, "gather": 6, "digest": 1, "index": 1}
    assert all(rep["bytes"][s] > 0 for s in profile.STAGES)

    # 2) the backup journaled its pipeline report, matching the deltas
    lines = [json.loads(l) for l in jpath.read_text().splitlines()]
    reports = [l for l in lines if l["kind"] == "pipeline_report"]
    assert len(reports) == 1
    assert reports[0]["report"]["dispatches"] == rep["dispatches"]
    assert reports[0]["snapshot"]  # tied to the snapshot it profiled

    # 3) Perfetto timeline: sender transfer spans and receiver store
    # spans merge under the one backup trace id
    doc = obs_timeline.export_timeline(
        [jpath], tmp_path / "timeline.json", labels=["perf_e2e"])
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    spans = [e for e in events if e["ph"] == "X"]
    sends = [e for e in spans if e["name"] == "transfer.send"]
    stores = [e for e in spans if e["name"] == "receiver.store"]
    assert sends and stores
    tids = {e["args"]["trace_id"] for e in sends}
    assert len(tids) == 1  # one backup, one trace
    assert tids == {e["args"]["trace_id"] for e in stores}
    # and the written file is valid JSON with the same events
    loaded = json.loads((tmp_path / "timeline.json").read_text())
    assert len(loaded["traceEvents"]) == len(events)

    # 4) per-peer estimators persisted: reopen the sender's config DB
    # (the "client restart") and the rows are still there
    store = Store(directory=tmp_path / "run" / "a" / "cfg",
                  data_base=tmp_path / "run" / "a" / "data")
    try:
        rows = store.all_peer_stats()
        assert rows, "no persisted peer estimator rows"
        assert all(r.samples > 0 and r.throughput_bps > 0 for r in rows)
    finally:
        store.close()
