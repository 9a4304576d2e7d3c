"""Spans on the served backup's streaming route, and their bridges.

What the route enters (``stream.*`` in ``ChunkerBackend.manifest_stream``;
``stream.upload``, ``cdc.*`` and ``blake3.*`` as well in
``TpuBackend.manifest_stream``, whose segment is resident on the device),
what the per-backup report makes of them and of the route's two byte
counters, what ``chunk_hash`` and ``paused`` count, the annotator that
puts a span on the profiler's host plane, and the compile listener.
Beside the streamed file's counters, the send stage's: what a packfile's
stripe uploads and how often it waits for the device, on the host
composition and on the resident route (``erasure/resident.py``).
Counts are deltas of ``bkw_span_seconds``: the registry is the process's,
and other tests feed it too.
"""

import asyncio
import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from backuwup_tpu import defaults
from backuwup_tpu.crypto import KeyManager
from backuwup_tpu.engine import Engine, Orchestrator
from backuwup_tpu.erasure import stripe as rs_stripe
from backuwup_tpu.obs import journal as obs_journal
from backuwup_tpu.obs import metrics as obs_metrics
from backuwup_tpu.obs import profile as obs_profile
from backuwup_tpu.obs import trace as obs_trace
from backuwup_tpu.ops import backend as ops_backend
from backuwup_tpu.ops.backend import CpuBackend, TpuBackend
from backuwup_tpu.ops.blake3_cpu import blake3_hash
from backuwup_tpu.ops.gear import CDCParams
from backuwup_tpu.snapshot.blob_index import BlobIndex
from backuwup_tpu.snapshot.packer import DirPacker
from backuwup_tpu.snapshot.packfile import PackfileWriter
from backuwup_tpu.store import Store

KEYS = KeyManager.from_secret(bytes(range(32)))
SMALL = CDCParams.from_desired(4096)
SEGMENT = 256 << 10
BIG_SEGMENT = 1 << 20
STREAM_ONLY = ("stream.read", "stream.slice", "stream.emit")
DEVICE_ROUTE = ("stream.upload", "cdc.scan", "cdc.decode",
                "stream.select_cuts")


def _span_counts() -> dict:
    spans = obs_metrics.registry().get("bkw_span_seconds")
    return {n: spans.count_value(name=n) for n in obs_profile.REPORT_SPANS}


def _reader(data: bytes):
    pos = 0

    def read(n: int) -> bytes:
        nonlocal pos
        out = data[pos:pos + n]
        pos += len(out)
        return out

    return read


@pytest.fixture
def annotations():
    """Records what the installed annotator is entered with; puts back
    whatever was installed (a TPU backend made earlier in this process
    installs jax's)."""
    seen = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    prior = obs_trace._annotator
    obs_trace.set_annotator(Note)
    yield seen
    obs_trace.set_annotator(prior)


@pytest.mark.parametrize("make", [CpuBackend, TpuBackend],
                         ids=["cpu", "tpu"])
def test_stream_route_enters_each_span_once_per_segment(make, rng):
    data = rng.randbytes(3 * SEGMENT + 1000)
    rounds = 5  # three full reads, the short one, the empty one at EOF
    before, base = _span_counts(), obs_profile.baseline()
    emitted = []
    refs = make(SMALL).manifest_stream(
        _reader(data), segment_bytes=SEGMENT,
        emit=lambda ref, chunk: emitted.append(ref))
    delta = {n: c - before[n] for n, c in _span_counts().items()}
    assert emitted == refs
    assert sum(r.length for r in refs) == len(data)
    for name in STREAM_ONLY:
        assert delta[name] == rounds, (name, delta)
    rep = obs_profile.report(base)
    assert set(rep["stream"]) == {"host_prep", "device_wait", "emit",
                                  "uploaded_bytes", "host_assembled_bytes",
                                  "carried_bytes", "segments",
                                  "digest_classes"}
    assert rep["stream"]["host_prep"] > 0 and rep["stream"]["emit"] > 0
    if make is TpuBackend:
        # every window is uploaded and scanned once (the empty read at
        # EOF has none: the carry is the last chunk as it stands), and
        # every round with final chunks digests them in one pass (the
        # short read may only lengthen the open chunk)
        for name in DEVICE_ROUTE:
            assert delta[name] == rounds - 1, (name, delta)
        digests = delta["blake3.digest"]
        assert delta["blake3.stage"] == digests and \
            rounds - 1 <= digests <= rounds
        assert rep["stream"]["device_wait"] > 0
        for name in ("stream.upload", "cdc.scan", "blake3.digest",
                     "stream.emit"):
            assert rep["stage_seconds"][name] > 0
        # one span per step per segment, none per chunk; the host's one
        # copy a window (the chunk astride carry and window, or the
        # open chunk carried on) is a span of its own inside the slice
        assert len(refs) > 20 * rounds
        assert 0 < delta["stream.boundary_chunk"] <= 2 * (rounds - 1)
        assert sum(delta.values()) == 3 * rounds + 4 * (rounds - 1) \
            + 2 * digests + delta["stream.boundary_chunk"]
        assert rep["stream"]["segments"] == rounds - 1
        tiles = rep["stream"]["digest_classes"]
        assert sum(c["bytes"] for c in tiles.values()) == len(data)
        assert all(c["padded_bytes"] >= c["bytes"] for c in tiles.values())
    else:
        assert all(delta[n] == 0 for n in DEVICE_ROUTE + ("blake3.stage",
                                                          "blake3.digest"))
        assert rep["stream"]["device_wait"] == 0
        assert rep["stream"]["uploaded_bytes"] == 0
        assert rep["stream"]["segments"] == 0
        assert rep["stream"]["digest_classes"] == {}


@pytest.mark.parametrize("make", [CpuBackend, TpuBackend],
                         ids=["cpu", "tpu"])
def test_stream_route_emits_views_of_the_segment_not_copies(make, rng):
    """Slicing a 256 MiB segment into ~3,600 ``bytes`` cost 0.05 or 0.8 s
    a backup by the allocator's mood (PERF.md, PR 25): ``emit`` gets
    read-only views, equal to the chunk's bytes."""
    data = rng.randbytes(2 * SEGMENT + 500)
    seen = []
    refs = make(SMALL).manifest_stream(
        _reader(data), segment_bytes=SEGMENT,
        emit=lambda ref, chunk: seen.append((ref, chunk)))
    assert [r for r, _ in seen] == refs and len(refs) > 8
    for ref, chunk in seen:
        assert isinstance(chunk, memoryview) and chunk.readonly
        assert chunk == data[ref.offset:ref.offset + ref.length]


def test_packer_copies_a_new_streamed_chunk_out_of_its_segment(tmp_path,
                                                                rng):
    src = tmp_path / "src"
    src.mkdir()
    (src / "image").write_bytes(rng.randbytes(64 << 10))
    packer = _packer(tmp_path, batch_bytes=16 << 10)
    kept = []
    add_blob = packer.writer.add_blob
    packer.writer.add_blob = lambda blob: (kept.append(blob),
                                           add_blob(blob))[1]
    packer.pack(src)
    packer.writer.shutdown()
    assert len(kept) > packer.stats.chunks > 4  # chunks and tree nodes
    assert all(type(b.data) is bytes for b in kept)


def test_digest_many_of_other_callers_enters_no_blake3_span(rng):
    """The send stage's threads digest through the same seam; their
    batches must not land in the stream route's sums."""
    before = _span_counts()
    TpuBackend(SMALL).digest_many([rng.randbytes(3000), rng.randbytes(70)])
    after = _span_counts()
    assert after["blake3.stage"] == before["blake3.stage"]
    assert after["blake3.digest"] == before["blake3.digest"]


def test_resident_route_uploads_each_byte_once(rng):
    """``uploaded_bytes`` is everything the route put on the device for
    the stream (window blocks, chunk rows): the stream's length within
    2 %, where the old route uploaded it ~4.3 times.  What the host had
    to copy together is the chunks that straddle two windows."""
    data = rng.randbytes(4 * BIG_SEGMENT)
    base = obs_profile.baseline()
    refs = TpuBackend(SMALL).manifest_stream(_reader(data),
                                             segment_bytes=BIG_SEGMENT)
    stream = obs_profile.report(base)["stream"]
    assert len(data) <= stream["uploaded_bytes"] <= 1.02 * len(data)
    edges = [k * BIG_SEGMENT for k in range(1, 4)]
    straddling = sum(r.length for r in refs for e in edges
                     if r.offset < e < r.offset + r.length)
    assert stream["host_assembled_bytes"] == straddling > 0


def test_resident_stripe_uploads_a_packfile_once_in_two_dispatches(
        tmp_path, rng, monkeypatch):
    """One 3 MiB packfile through the engine's executor-thread half
    (``_encode_stripe``).  The host composition over the device seams
    stages 36 MiB in eight waits (4 MiB for RS, 8 MiB of shard payloads,
    six tables of 4 MiB); the resident route uploads the padded shard
    matrix and the window rows, under 1.5 bytes a packfile byte, and
    waits twice: once in each span, which still bound the work."""
    store = Store(directory=tmp_path / "cfg", data_base=tmp_path / "data")
    engine = Engine(KEYS, store, server=None, node=None,
                    backend=TpuBackend(SMALL))
    data, k, m = rng.randbytes(3 << 20), 4, 2

    base = obs_profile.baseline()
    with obs_profile.send_stage(len(data)):
        host = rs_stripe.Stripe(data, k, m, engine.backend, range(k + m))
        host.challenge_tables()
    send = obs_profile.report(base)["send"]
    assert send["packfile_bytes"] == len(data)
    assert send["uploaded_bytes"] / len(data) == pytest.approx(12.0,
                                                               rel=1e-3)
    assert send["dispatches"] == 8

    engine.backend.encode_stripe(b"warm", k, m)
    pid = bytes(range(12))
    base, spans = obs_profile.baseline(), _span_counts()
    containers = engine._encode_stripe(None, pid, data, k, m,
                                       list(range(k + m)))
    rep, after = obs_profile.report(base), _span_counts()
    assert containers == host.containers
    assert rep["send"]["packfile_bytes"] == len(data)
    assert 1.0 < rep["send"]["uploaded_bytes"] / len(data) < 1.5
    assert rep["send"]["dispatches"] == 2
    for name in ("send.rs_encode", "send.challenge_tables"):
        assert after[name] == spans[name] + 1
        assert rep["stage_seconds"][name] > 0
    for i in range(k + m):
        table = engine.challenge_tables.load(rs_stripe.shard_id(pid, i))
        assert len(table) == defaults.AUDIT_CHALLENGES_PER_PACKFILE
        e = table[-1]
        assert e.digest == blake3_hash(
            e.nonce + containers[i][e.offset:e.offset + e.length])
    # what other threads stage is not the send stage's
    base = obs_profile.baseline()
    engine.backend.digest_many([data[:70000]])
    assert obs_profile.report(base)["send"] == {
        "uploaded_bytes": 0, "packfile_bytes": 0, "dispatches": 0,
        "wire_bytes": 0, "deflated_bytes": 0,
        "stripes": 0, "whole": 0, "deferred": 0}
    store.close()


def test_resident_stripe_programs_are_a_closed_set(rng):
    """The route's programs are a function of (k, m) and the shard-length
    bucket alone: after its warm (the first stripe), stripes of ten
    lengths over every bucket a sealed packfile falls in, with tables
    for all shards or for some, compile nothing."""
    backend = TpuBackend(SMALL)
    backend.encode_stripe(b"warm", 4, 2)
    base = obs_profile.baseline()
    for n in (1, 5000, 65536, 65537, 300 << 10, 1 << 20, (1 << 20) + 1,
              (2 << 20) + 333, 3 << 20, (3 << 20) + (150 << 10)):
        backend.encode_stripe(rng.randbytes(n), 4, 2,
                              [1, 4] if n % 2 else range(6)
                              ).challenge_tables()
    assert obs_profile.report(base)["compile_s"] == {}


def _tpu_packer(tmp_path, name: str, **kw) -> DirPacker:
    out = tmp_path / name
    out.mkdir()
    return DirPacker(TpuBackend(SMALL), PackfileWriter(KEYS, out),
                     BlobIndex(KEYS, tmp_path / f"{name}.idx"), **kw)


def _pack_one_file(tmp_path, name: str, data: bytes) -> dict:
    """Pack a tree holding one streamed file; the pipeline report."""
    src = tmp_path / f"{name}.src"
    src.mkdir()
    (src / "image").write_bytes(data)
    packer = _tpu_packer(tmp_path, name, batch_bytes=BIG_SEGMENT)
    base = obs_profile.baseline()
    packer.pack(src)
    packer.writer.shutdown()
    assert packer.stats.bytes_read == len(data)
    return obs_profile.report(base)


def test_resident_route_names_its_time_and_compiles_once(tmp_path, rng):
    """The three groups name at least 97 % of ``stream.file``, and the
    route's programs are a function of ``segment_bytes`` alone: the first
    streamed file compiles them all, a second of another length (other
    upload blocks, other chunk counts, other tile heights) none."""
    first = _pack_one_file(tmp_path, "a", rng.randbytes(3 * BIG_SEGMENT))
    second = _pack_one_file(
        tmp_path, "b", rng.randbytes(4 * BIG_SEGMENT + 333_333))
    assert second["compile_s"] == {}, second["compile_s"]
    for rep in (first, second):
        assert rep["stage_seconds"]["stream.upload"] > 0
    named = sum(second["stream"][g]
                for g in ("host_prep", "device_wait", "emit"))
    assert named >= 0.97 * second["stage_seconds"]["stream.file"]


def test_streamed_map_closes_with_no_device_array_over_it(tmp_path, rng,
                                                          monkeypatch):
    """On the CPU backend ``device_put`` of aligned host memory aliases
    it.  When ``_pack_file_streaming`` lets go of the map, closing it
    raises no ``BufferError`` (which the packer would swallow) and no
    live device array points into it."""
    import mmap

    import jax

    closes = []

    class WatchedMap(mmap.mmap):
        def close(self):
            probe = np.frombuffer(self, dtype=np.uint8)
            lo, hi = probe.ctypes.data, probe.ctypes.data + probe.size
            del probe
            over = [a.shape for a in jax.live_arrays()
                    if not a.is_deleted()
                    and lo <= a.unsafe_buffer_pointer() < hi]
            try:
                super().close()
                closes.append(("closed", over))
            except BufferError:
                closes.append(("BufferError", over))
                raise

    monkeypatch.setattr(mmap, "mmap", WatchedMap)
    rep = _pack_one_file(tmp_path, "m", rng.randbytes(2 * BIG_SEGMENT + 5))
    assert rep["stream"]["uploaded_bytes"] >= 2 * BIG_SEGMENT
    assert closes == [("closed", [])]


def _packer(tmp_path, **kw) -> DirPacker:
    out = tmp_path / "pack"
    out.mkdir(exist_ok=True)
    writer = PackfileWriter(KEYS, out)
    index = BlobIndex(KEYS, tmp_path / "idx")
    return DirPacker(CpuBackend(SMALL), writer, index, **kw)


def test_chunk_hash_leaves_out_an_emit_that_sleeps(tmp_path, rng):
    data = rng.randbytes(96 << 10)
    src = tmp_path / "src"
    src.mkdir()
    (src / "image").write_bytes(data)
    chunks = {r.hash for r in CpuBackend(SMALL).manifest(data)}
    slept = 0.0

    def on_blob(blob_hash, size):
        nonlocal slept
        if blob_hash in chunks:  # a tree node is packed outside emit
            time.sleep(0.01)
            slept += 0.01

    stage = obs_metrics.registry().get("bkw_pack_stage_seconds")
    busy0 = stage.sum_value(stage="chunk_hash")
    before = _span_counts()
    packer = _packer(tmp_path, batch_bytes=16 << 10, on_blob=on_blob)
    t0 = time.monotonic()
    packer.pack(src)
    wall = time.monotonic() - t0
    packer.writer.shutdown()
    assert packer.stats.chunks == len(chunks) and slept >= 0.1
    after = _span_counts()
    for name in ("stream.file", "stream.tree"):
        assert after[name] == before[name] + 1, name
    assert 0 < packer.stats.chunk_hash_s <= wall - 0.9 * slept
    assert stage.sum_value(stage="chunk_hash") - busy0 == pytest.approx(
        packer.stats.chunk_hash_s)


def test_chunks_deduped_counts_chunks_not_tree_nodes(tmp_path, rng):
    """A second pack of an unchanged tree finds every chunk and every
    tree node there already: ``chunks_deduped`` is the chunks alone (it
    read 4,964 of 2,868 chunks on the chip, the tree nodes among them)."""
    src = tmp_path / "src"
    (src / "d").mkdir(parents=True)
    (src / "big").write_bytes(rng.randbytes(64 << 10))  # streamed
    for i in range(4):
        (src / "d" / f"f{i}").write_bytes(rng.randbytes(9 << 10))
    first = _packer(tmp_path, batch_bytes=32 << 10)
    first.pack(src)
    first.writer.shutdown()
    assert first.stats.chunks_deduped == 0
    again = DirPacker(CpuBackend(SMALL), PackfileWriter(KEYS, tmp_path / "p2"),
                      first.index, batch_bytes=32 << 10)
    again.pack(src)
    again.writer.shutdown()
    assert again.stats.chunks == first.stats.chunks > 8
    assert again.stats.chunks_deduped == again.stats.chunks
    assert again.stats.bytes_deduped == again.stats.bytes_read


def test_block_if_paused_counts_only_the_seconds_it_waited():
    stage = obs_metrics.registry().get("bkw_pack_stage_seconds")
    orch = Orchestrator()
    n0, s0 = (stage.count_value(stage="paused"),
              stage.sum_value(stage="paused"))
    for _ in range(3):
        orch.block_if_paused()  # not paused: no clock read, no sample
    assert stage.count_value(stage="paused") == n0
    orch.pause()
    threading.Timer(0.05, orch.resume).start()
    orch.block_if_paused()
    assert stage.count_value(stage="paused") == n0 + 1
    assert 0.04 <= stage.sum_value(stage="paused") - s0 < 5.0


def test_annotator_is_entered_with_each_span_nested(annotations):
    with obs_trace.span("outer.work"):
        with obs_trace.span("inner.work"):
            pass
        with obs_trace.span("inner.more"):
            pass
    assert annotations == [
        ("enter", "outer.work"), ("enter", "inner.work"),
        ("exit", "inner.work"), ("enter", "inner.more"),
        ("exit", "inner.more"), ("exit", "outer.work")]


def test_annotator_skips_spans_on_an_event_loops_thread(annotations):
    """A span held across an await interleaves with its siblings on the
    loop's thread; one opened in an executor thread nests and is
    bridged."""
    async def main():
        with obs_trace.span("held.across.await"):
            await asyncio.sleep(0)
            await asyncio.get_running_loop().run_in_executor(
                None, _in_thread)

    def _in_thread():
        with obs_trace.span("executor.work"):
            pass

    asyncio.run(main())
    assert annotations == [("enter", "executor.work"),
                           ("exit", "executor.work")]


def test_no_annotator_no_call(annotations):
    obs_trace.set_annotator(None)
    with obs_trace.span("unbridged.work"):
        pass
    assert annotations == []
    spans = obs_metrics.registry().get("bkw_span_seconds")
    assert spans.count_value(name="unbridged.work") >= 1


def test_fresh_jit_counts_one_compile_under_its_name(tmp_path):
    import jax
    import jax.numpy as jnp

    ops_backend._install_jax_hooks()
    hist = obs_metrics.registry().get("bkw_jit_compile_seconds")

    def bkw_test_fresh_program(x):
        return x * 3 + 1

    n0 = hist.count_value(fun="bkw_test_fresh_program")
    base = obs_profile.baseline()
    obs_journal.install(obs_journal.Journal(tmp_path / "j.jsonl"))
    try:
        with obs_trace.span("step.that.compiles"):
            jax.jit(bkw_test_fresh_program)(jnp.ones(7)).block_until_ready()
    finally:
        obs_journal.uninstall()
    assert hist.count_value(fun="bkw_test_fresh_program") == n0 + 1
    rep = obs_profile.report(base)
    assert rep["compile_s"]["bkw_test_fresh_program"] > 0
    assert rep["compile_total_s"] >= rep["compile_s"][
        "bkw_test_fresh_program"]
    lines = [json.loads(ln) for ln in
             (tmp_path / "j.jsonl").read_text().splitlines()]
    mine = [ln for ln in lines if ln["kind"] == "compile"
            and ln["fun"] == "bkw_test_fresh_program"]
    assert len(mine) == 1 and mine[0]["span"] == "step.that.compiles"


def test_profiler_capture_holds_a_bridged_span(tmp_path):
    """The benchmark's capture options (host tracer level 1, no Python
    tracer): the span's name is an event of a host-plane line."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    ops_backend._install_jax_hooks()
    obs_trace.set_annotator(jax.profiler.TraceAnnotation)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs_trace.span("bridged.on.the.host.plane"):
            np.asarray(jnp.arange(64) * 2)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert found
    data = ProfileData.from_file(found[-1])
    names = {ev.name for plane in data.planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events}
    assert "bridged.on.the.host.plane" in names
