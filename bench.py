"""Benchmark: device-resident chunk+hash throughput vs single-thread CPU.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "MiB/s", "vs_baseline": N}

Method (BASELINE.json north star — chunk + fingerprint MiB/s at identical
dedup output):

* TPU path: corpus segments are synthesized **on device** with the JAX PRNG,
  so this number holds the kernels alone: no byte crosses from the host
  (the served path, which reads a tree on the host, is what
  ``chip_smoke.py`` drives).  The timed loop is the zero-round-trip driver
  (``DevicePipeline.manifest_segments_device``): Mosaic strip scan ->
  on-device parallel cut selection -> class-bucketed gather -> Pallas
  BLAKE3, with only async downloads of cuts+digests.
* CPU baseline: the native C implementation (``native/cdc_blake3.c``) of the
  identical pipeline on ONE host thread — the honest stand-in for the
  reference's fastcdc+blake3 crates; parity vs the spec oracle is asserted
  by tests/test_native.py and re-checked here before timing.  Best of 3
  runs (the shared dev host carries background load).
* Parity gate: an 8 MiB corpus is pushed through BOTH paths bit-for-bit;
  chunk boundaries and digests must match exactly or the benchmark reports
  failure — speed without identical dedup output is meaningless.

Scale: the headline corpus is BENCH_GIB GiB (default 10, BASELINE.md:37)
streamed as 256 MiB segments from a rotating pool of 8 device-resident
random segments; every config then keeps cycling until BENCH_MIN_WALL_S
(default 60 s) of sustained wall clock — sustained windows catch HBM
fragmentation, cache eviction, and pipeline-drain effects that
seconds-long bursts hide.  Environment knobs: BENCH_GIB,
BENCH_SEGMENT_MIB, BENCH_CPU_MIB, BENCH_MIN_WALL_S, BENCH_CONFIGS=0.
"""

from __future__ import annotations

import json
import os
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _metrics_snapshot() -> dict:
    """Registry state at report time, embedded in every BENCH record so a
    run's counters/histograms (pack stages, retries, faults) ride along
    with the headline number."""
    from backuwup_tpu.obs import metrics as obs_metrics
    return obs_metrics.registry().snapshot()


def _pipeline_report() -> dict:
    """Whole-run pipeline report (obs/profile.py): dispatch counts per
    stage, bytes, padding efficiency.  bench runs in a fresh process, so
    process totals ARE this run — the before/after the round-5
    digest-dispatch merge diffs (PERF.md)."""
    from backuwup_tpu.obs import profile as obs_profile
    return obs_profile.report()


def main() -> None:
    from backuwup_tpu.utils.jaxcache import enable_compilation_cache
    enable_compilation_cache()

    import jax

    # a measurement path that finds no chip fails; it never writes a host
    # number under a device metric's name
    if jax.default_backend() == "cpu":
        raise SystemExit(
            "bench.py measures the device pipeline and found no accelerator "
            f"(jax.devices() = {jax.devices()})")
    import jax.numpy as jnp
    import numpy as np

    from backuwup_tpu.ops import cdc_cpu
    from backuwup_tpu.ops.blake3_cpu import Blake3Numpy
    from backuwup_tpu.ops.cdc_tpu import _HALO
    from backuwup_tpu.ops.gear import CDCParams
    from backuwup_tpu.ops.pipeline import DevicePipeline

    import bench_configs

    total_gib = float(os.environ.get("BENCH_GIB", "10"))
    seg_mib = bench_configs.segment_mib()
    cpu_mib = int(os.environ.get("BENCH_CPU_MIB", "64"))
    params = CDCParams()  # production 256KiB/1MiB/3MiB
    pipeline = DevicePipeline(params)
    seg_bytes = seg_mib * (1 << 20)
    segments = max(2, int(total_gib * 1024) // seg_mib)

    log(f"devices: {jax.devices()}  fused={pipeline.fused} "
        f"pallas_digest={pipeline.pallas_digest}")

    # --- parity gate -------------------------------------------------------
    rng = np.random.default_rng(1234)
    parity = rng.integers(0, 256, 8 << 20, dtype=np.uint8)
    # tile a block so dedup has real duplicates to find
    parity[4 << 20:6 << 20] = parity[0:2 << 20]
    parity_bytes = parity.tobytes()
    cpu_chunks = cdc_cpu.chunk_stream(parity_bytes, params)
    cpu_digests = Blake3Numpy().digest_batch(
        [parity_bytes[o:o + l] for o, l in cpu_chunks])
    ext = np.concatenate([np.zeros(_HALO, dtype=np.uint8), parity])
    # strict_overflow: an overflow/unresolved row silently re-chunks on the
    # CPU oracle, which would make this gate compare oracle to oracle and
    # pass vacuously exactly when the device path misbehaves.
    (tpu_chunks, tpu_digests), = next(iter(pipeline.manifest_segments_device(
        [(jnp.asarray(ext.reshape(1, -1)),
          np.full(1, len(parity_bytes), dtype=np.int32))],
        strict_overflow=True)))
    tpu_digest_bytes = [bytes(d) for d in tpu_digests]
    if tpu_chunks != cpu_chunks or tpu_digest_bytes != cpu_digests:
        print(json.dumps({"metric": "chunk+hash parity FAILED", "value": 0.0,
                          "unit": "MiB/s", "vs_baseline": 0.0,
                          "metrics": _metrics_snapshot()}))
        return
    dedup = len(set(cpu_digests)) / len(cpu_digests)
    log(f"parity OK: {len(cpu_chunks)} chunks, unique-ratio {dedup:.3f}")

    # --- TPU timing: sustained streaming over the 10 GiB corpus ------------
    key = jax.random.PRNGKey(0)
    row = _HALO + seg_bytes
    nv = np.full(1, seg_bytes, dtype=np.int32)

    @jax.jit
    def synth(key):
        seg = jax.random.randint(key, (seg_bytes,), 0, 256, dtype=jnp.uint8)
        return jnp.concatenate([jnp.zeros(_HALO, dtype=jnp.uint8), seg]
                               ).reshape(1, row)

    # pool of 8 distinct resident segments cycled through the stream (the
    # whole corpus cannot live in HBM at once; per-segment state is nil)
    pool = []
    for _ in range(min(8, segments)):
        key, sub = jax.random.split(key)
        pool.append((synth(sub), nv))
    jax.block_until_ready([b for b, _ in pool])

    # warm every compiled shape out of the timed loop
    list(pipeline.manifest_segments_device(pool[:2], strict_overflow=True))

    # staged-ahead feeder (PERF.md round-5 item 3): keep two upcoming
    # segments committed to device ahead of the consuming driver so any
    # synth/staging DMA rides under manifest compute instead of
    # serializing with it — the upload-side twin of the window's
    # overlapped downloads, same ring discipline as
    # ops/pipeline.manifest_segments_stream.  Resident pool items make
    # device_put a no-op, so the headline device-resident semantics are
    # unchanged; host-built segments (cpu fallback, future host-streamed
    # corpora) get real overlap.
    def _staged_ahead(items, depth=2):
        from collections import deque
        it = iter(items)
        ring = deque()

        def stage_one():
            for buf, nv in it:
                ring.append((jax.device_put(buf), nv))
                return True
            return False

        while True:
            while len(ring) < depth and stage_one():
                pass
            if not ring:
                return
            yield ring.popleft()

    # sustained window: the stated corpus, then keep cycling until the
    # minimum wall clock elapses (sustained numbers catch HBM
    # fragmentation / cache-eviction / pipeline-drain effects that
    # seconds-long bursts hide)
    window = bench_configs.SustainedWindow(segments)
    total_chunks = 0
    for results in pipeline.manifest_segments_device(
            _staged_ahead(window.items(pool)), strict_overflow=True):
        for chunks, _dig in results:
            total_chunks += len(chunks)
    tpu_s = window.wall
    done_segments = window.count
    tpu_mibs = done_segments * seg_mib / tpu_s
    log(f"tpu: {done_segments}x{seg_mib} MiB "
        f"({done_segments*seg_mib/1024:.1f} GiB) "
        f"in {tpu_s:.2f}s = {tpu_mibs:.1f} MiB/s ({total_chunks} chunks)")

    # --- CPU baseline: native C pipeline, single thread, best of 3 ---------
    from backuwup_tpu import native

    host = rng.integers(0, 256, cpu_mib << 20, dtype=np.uint8).tobytes()
    baseline_kind = "native C fastcdc-class+blake3 pipeline, 1 host thread"
    try:
        nat_chunks, nat_digests = native.manifest_native(parity_bytes, params)
        if nat_chunks != cpu_chunks or nat_digests != cpu_digests:
            print(json.dumps({"metric": "native baseline parity FAILED",
                              "value": 0.0, "unit": "MiB/s",
                              "vs_baseline": 0.0,
                              "metrics": _metrics_snapshot()}))
            return
        cpu_s = min(_timed(native.manifest_native, host, params)
                    for _ in range(3))
        cpu_mibs = cpu_mib / cpu_s
        log(f"cpu-native: {cpu_mib} MiB in {cpu_s:.2f}s = {cpu_mibs:.1f}"
            " MiB/s (single thread, best of 3)")
    except native.NativeUnavailable as e:
        # no C compiler on this host: fall back to the numpy oracle as the
        # (much slower) baseline rather than crashing the JSON contract
        log(f"native baseline unavailable ({e}); using numpy oracle")
        baseline_kind = "numpy oracle pipeline, 1 host thread (no C compiler)"
        t0 = time.time()
        chunks = cdc_cpu.chunk_stream(host, params)
        Blake3Numpy().digest_batch([host[o:o + l] for o, l in chunks])
        cpu_s = time.time() - t0
        cpu_mibs = cpu_mib / cpu_s

    # --- BASELINE configs #2-#6 -------------------------------------------
    configs = {}
    if os.environ.get("BENCH_CONFIGS", "1") != "0":
        configs = bench_configs.run_all(pipeline, params, cpu_mibs, log)

    record = {
        "metric": "dedup pipeline chunk+hash throughput (device-resident)",
        "value": round(tpu_mibs, 2),
        "unit": "MiB/s",
        "vs_baseline": round(tpu_mibs / cpu_mibs, 2),
        "baseline": f"{baseline_kind} ({cpu_mibs:.1f} MiB/s)",
        "corpus_gib": round(done_segments * seg_mib / 1024, 2),
        "wall_s": round(tpu_s, 2),
        "configs": configs,
    }
    # config #8 measures serial-vs-concurrent in one run; surface the
    # ratio at top level so BENCH_r*.json diffs track it directly
    transfer = configs.get("8_transfer", {})
    if "speedup" in transfer:
        record["transfer_speedup"] = transfer["speedup"]
    # config #9 is pass/fail: surface the scorecard verdict at top level
    # so a durability regression is one grep away in BENCH_r*.json
    scenario = configs.get("9_scenario", {})
    if "passed" in scenario:
        record["scenario_passed"] = scenario["passed"]
        record["scenario_violation_seconds"] = \
            scenario.get("violation_seconds", 0)
    # config #12 is the coordination-plane scale-out gate: surface the
    # sharded tier's matchmaking throughput and request p99 at top level
    swarm = configs.get("12_swarm", {})
    if "matchmakings_per_s" in swarm:
        record["matchmakings_per_s"] = swarm["matchmakings_per_s"]
        record["server_p99_ms"] = swarm.get("server_p99_ms")
    # config #13 measures serial-vs-multi-source restore in one run;
    # surface both acceptance numbers (wall speedup, bytes-on-wire
    # ratio) at top level so BENCH_r*.json diffs track them directly
    restore = configs.get("13_restore", {})
    if "speedup" in restore:
        record["restore_speedup"] = restore["speedup"]
        record["restore_bytes_ratio"] = restore.get("bytes_ratio")
    # config #14 is the mesh manifest plane: surface the matched-work
    # multichip speedup at top level (parity/even-split/handoff gates run
    # everywhere; the wall-clock gate arms on hardware only)
    multichip = configs.get("14_multichip", {})
    if "speedup" in multichip:
        record["multichip_speedup"] = multichip["speedup"]
    # config #15 is the snapshot lifecycle plane: surface how much of the
    # shipped data GC reclaimed (and the zero-violation verdict) at top
    # level so BENCH_r*.json diffs track the collector directly
    gc = configs.get("15_gc", {})
    if "gc_reclaim_ratio" in gc:
        record["gc_reclaim_ratio"] = gc["gc_reclaim_ratio"]
        record["gc_passed"] = gc.get("passed")
    # config #16 is the federated coordination plane: surface the
    # multi-node matchmaking speedups at top level (scaling gates arm on
    # >=4-CPU hosts; the churn scorecard's zero-lost gate runs
    # everywhere) so BENCH_r*.json diffs track federation directly
    federation = configs.get("16_federation", {})
    if "federation_speedup_2node" in federation:
        record["federation_speedup_2node"] = \
            federation["federation_speedup_2node"]
        record["federation_speedup_4node"] = \
            federation["federation_speedup_4node"]
    # config #17 is the tiered dedup index: surface the skewed-corpus
    # device-path hit rate at top level (parity/budget/hit-rate gates
    # run everywhere; the wall gate arms on hardware only) so
    # BENCH_r*.json diffs track the tier split directly
    tiered = configs.get("17_tiered", {})
    if "tiered_hit_rate" in tiered:
        record["tiered_hit_rate"] = tiered["tiered_hit_rate"]
        record["tiered_overflow_ratio"] = tiered.get("overflow_ratio")
    # config #18 is replicated coordination metadata: surface the
    # permakill durability count (must stay 0) and the promote-to-
    # serving time at top level so BENCH_r*.json diffs track the
    # replication plane directly
    repl = configs.get("18_replication", {})
    if "replication_lost_rows" in repl:
        record["replication_lost_rows"] = repl["replication_lost_rows"]
        record["repl_promote_s"] = repl.get("repl_promote_s")
    # config #19 is the virtual-clock simulation plane: surface the
    # driver throughput and the time-compression ratio at top level so
    # BENCH_r*.json diffs track whether a simulated week still fits a
    # tier-1 minute
    sim = configs.get("19_sim", {})
    if "sim_time_compression" in sim:
        record["sim_events_per_s"] = sim["sim_events_per_s"]
        record["sim_time_compression"] = sim["sim_time_compression"]
    # config #20 is the streaming dataflow engine: surface the overlap
    # efficiency (max stage busy / wall) and the phased->stream speedup
    # at top level so BENCH_r*.json diffs track whether the backup wall
    # still converges to max(stage) rather than sum(stage)
    dataflow = configs.get("20_dataflow", {})
    if "dataflow_overlap_efficiency" in dataflow:
        record["dataflow_overlap_efficiency"] = \
            dataflow["dataflow_overlap_efficiency"]
        record["dataflow_speedup"] = dataflow["dataflow_speedup"]
    # config #21 is the live SLO plane: surface breach-detection latency
    # and explainer precision at top level so BENCH_r*.json diffs (and
    # scripts/bench_trend.py) track whether a durability incident still
    # pages within the budget and the root-cause ranking stays exact
    slo = configs.get("21_slo", {})
    if "slo_detection_s" in slo:
        record["slo_detection_s"] = slo["slo_detection_s"]
        record["slo_precision"] = slo["slo_precision"]
    print(json.dumps({
        **record,
        "note": "corpus synthesized on-device (kernels alone, no host->"
                "device staging in the window); parity vs CPU oracle gated "
                "per config",
        "pipeline_report": _pipeline_report(),
        "metrics": _metrics_snapshot(),
    }))


def _timed(fn, *args):
    t0 = time.time()
    fn(*args)
    return time.time() - t0


if __name__ == "__main__":
    main()
