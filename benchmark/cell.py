"""One run of one cell: set-up, the window of generations, the check.

A cell's window is a sequence of generations: prepare the tree (untimed),
back it up (timed by the host clock around ``await client.backup(root)``,
which returns only after every packfile is acked by its holders and the
snapshot is recorded), until the timed total reaches ``--seconds``; the
backup in progress finishes and counts.  Generation 0 (the
configuration's tree, where the traffic builds on it) and the warm-up
generations are set-up.  The reference's work is in neither.
"""

from __future__ import annotations

import json
import shutil
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

from benchmark import check, meters, readers, specs, tracered


def emit(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def _stats(ps) -> dict:
    return {k: int(getattr(ps, k)) for k in
            ("files", "failed_files", "bytes_read", "chunks",
             "chunks_deduped", "dedup_divergences")}


class Run:
    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 rehearse: bool, t_start: float, controls: bool = False):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.rehearse, self.t_start = trace, rehearse, t_start
        self.controls = controls
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.params = specs.cdc_params(self.config)
        self.reference = check.Reference(self.params)
        self.meter = meters.CompileMeter()
        self.metrics = {m["name"]: specs.layer_metric(m["name"])
                        for m in cell["per_layer"]} if trace else {}
        self.series = readers.registry_series(list(self.metrics.values()))
        dcfg = self.config["deployment"]
        self.k, self.m = int(dcfg["rs_k"]), int(dcfg["rs_m"])
        self.backups: list = []
        self.placed_before: set = set()  # packfiles of earlier backups
        self.reference_s = 0.0
        self.trace_result = None
        self.traced = None

    def _rng(self, generation: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, generation])

    def _registry(self) -> dict:
        return {key: meters.registry_sum(key[0], dict(key[1]), key[2])
                for key in self.series}

    async def backup(self, dep, generation: int, root: Path,
                     timed: bool, trace_dir: str = "") -> dict:
        """Offer ``root`` to the client once; returns the record the
        check and the readers use."""
        from backuwup_tpu.obs import profile as obs_profile
        engine = dep.client.engine
        t0 = time.monotonic()
        census = check.census(root)
        ref = self.reference.observe(root)
        self.reference_s += time.monotonic() - t0
        stored0 = dep.stored_bytes()
        rerun0, reg0 = obs_profile.mesh_host_rerun_rows(), self._registry()
        n_sum, comp0 = len(dep.summaries), self.meter.snapshot()
        n_names = len(self.meter.names)
        rec = {"generation": generation, "timed": timed, "census": census,
               "user_bytes": census["bytes"], "ref": ref, "error": None}
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t0 = time.monotonic()
        try:
            await dep.client.backup(root)
        except Exception as e:  # the run goes on to report it as failed
            rec["error"] = repr(e)
        rec["wall_s"] = time.monotonic() - t0
        if trace_dir:
            jax.profiler.stop_trace()
        # the packfiles this backup placed, as they stand on its return
        rows = [r for r in dep.client.store.all_placements()
                if bytes(r[0]) not in self.placed_before]
        self.placed_before.update(bytes(r[0]) for r in rows)
        rec["compiles"] = self.meter.since(comp0)
        rec["compile_wall"] = {
            key: rec["compiles"][key] for key in ("compile_s",
                                                  "trace_lower_s")}
        rec["compiled"] = self.meter.names[n_names:]
        stored = [a - b for a, b in zip(dep.stored_bytes(), stored0)]
        rec.update(
            stored=stored, stored_total=sum(stored),
            placed=check.placement_census(rows, self.k, self.m),
            host_rerun_rows=obs_profile.mesh_host_rerun_rows() - rerun0,
            unsent_packfiles=len(engine._unsent_packfiles()),
            registry={k: v - reg0[k] for k, v in self._registry().items()})
        if rec["error"] is None:
            rec["stats"] = _stats(engine.last_pack_stats)
            rec["overlap"] = engine.last_overlap
            rec["pipeline"] = engine.last_pipeline_report
            rec["summary"] = (dep.summaries[-1]
                              if len(dep.summaries) > n_sum else {})
        self.backups.append(rec)
        emit(phase="backup", generation=generation, timed=timed,
             wall_s=rec["wall_s"], user_bytes=rec["user_bytes"],
             files=census["files"], new_bytes=ref["new_bytes"],
             stored=rec["stored_total"], error=rec["error"],
             compiles=rec["compiles"]["compiles"],
             compiled=rec["compiled"] if timed else None,
             trace_lower_s=rec["compiles"]["trace_lower_s"],
             compile_s=rec["compiles"]["compile_s"],
             packed=(rec.get("summary") or {}).get("size"),
             placed=rec["placed"],
             stage_busy_s=(rec.get("overlap") or {}).get("stage_busy_s"))
        return rec

    def _warm_shapes(self, dep) -> None:
        """The configuration's ``warm`` block, through the backend's
        own ``encode_shards`` / ``digest_many``: the send stage's
        programs at every length a packfile can have (RS encode, the
        shard and challenge-table digests) and the digest batch shapes
        a night's chunk counts can reach beside generation 0's."""
        warm = self.config.get("warm", {})
        backend = dep.client.engine.backend
        for nbytes in warm.get("rs_shard_bytes", []):
            backend.encode_shards(
                np.zeros((1, self.k, int(nbytes)), dtype=np.uint8), self.m)
        for count, nbytes in warm.get("digests", []):
            backend.digest_many([bytes(int(nbytes))] * int(count))

    async def run(self, dep_factory) -> dict:
        work = Path(tempfile.mkdtemp(prefix="bkw_bench_"))
        try:
            return await self._run(work, dep_factory)
        finally:
            shutil.rmtree(work, ignore_errors=True)

    async def _run(self, work: Path, dep_factory) -> dict:
        parts = {"imports_s": time.monotonic() - self.t_start}
        traffic_gen = specs.generator(self.traffic["generator"])
        root = work / "src"
        t0 = time.monotonic()
        base = self.traffic["base"] == "config_tree"
        if base:
            tree = self.config["tree"]
            specs.generator(tree["generator"]).build(
                root, tree["params"], self._rng(0))
        parts["tree_s"] = time.monotonic() - t0

        t0 = time.monotonic()
        comp0 = self.meter.snapshot()
        dep = dep_factory(work / "apps", self.config, self.rehearse)
        try:
            kernels = await dep.start()
            parts["start_s"] = time.monotonic() - t0
            emit(phase="start", seconds=parts["start_s"], kernels=kernels,
                 **self.meter.since(comp0))
            generation = 0
            t0 = time.monotonic()
            self._warm_shapes(dep)
            parts["warm_shapes_s"] = time.monotonic() - t0
            t0 = time.monotonic()
            if base:
                await self.backup(dep, 0, root, timed=False)
            parts["generation0_s"] = time.monotonic() - t0

            async def next_generation(timed: bool, trace_dir: str = ""):
                nonlocal generation, root
                generation += 1
                root = traffic_gen.step(
                    root, self.traffic["params"], self._rng(generation),
                    {"generation": generation, "work": work,
                     "seed": self.seed})
                return await self.backup(dep, generation, root, timed,
                                         trace_dir)

            t0 = time.monotonic()
            for _ in range(int(self.traffic.get("warmup_generations", 1))):
                await next_generation(timed=False)
            parts["warmup_s"] = time.monotonic() - t0
            setup_s = (time.monotonic() - self.t_start) - self.reference_s
            parts["reference_s_left_out"] = self.reference_s
            emit(phase="setup", setup_s=setup_s, parts=parts,
                 **self.meter.since(comp0))

            # --- the window ---
            # What compiles inside the window is not written to the
            # persistent cache, so a run never loads what an earlier
            # run of the same seed compiled there: every run pays the
            # same compiles (PERF.md section 2).
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 1e9)
            timed_s, first = 0.0, True
            trace_dir = str(work / "trace") if self.trace else ""
            compiles_in_window = 0
            while timed_s < self.seconds:
                rec = await next_generation(
                    timed=True, trace_dir=trace_dir if first else "")
                compiles_in_window += rec["compiles"]["compiles"]
                if first and self.trace:
                    self.traced = rec
                first = False
                timed_s += rec["wall_s"]
                if rec["error"]:
                    break
            window = [b for b in self.backups if b["timed"]]
            if self.trace:
                self._reduce_trace(trace_dir)

            recorded = dep.client.store.manifest_blobs()
            placements = dep.client.store.all_placements()
            memory_peak = meters.memory_peak_bytes()
        finally:
            await dep.stop()

        return self._report(window, recorded, placements, root, setup_s,
                            compiles_in_window, memory_peak)

    def _report(self, window: list, recorded: dict, placements: list,
                root: Path, setup_s: float, compiles_in_window: int,
                memory_peak: int) -> dict:
        """The check, then what ``run.py`` makes the result line from."""
        t0 = time.monotonic()
        judged = (self.backups, recorded, placements, root, self.params,
                  self.seed, self.k, self.m)
        verdict = check.judge(*judged)
        # every number compared, beside its limit
        emit(phase="check", rows=verdict.rows)
        emit(phase="check_summary", compared=len(verdict.rows),
             failed=[r["check"] for r in verdict.rows if not r["ok"]],
             seconds=time.monotonic() - t0, reference_s=self.reference_s)
        controls = None
        if self.controls:
            controls = check.controls(*judged)
            emit(phase="controls", **controls)

        done = [b for b in window if not b["error"]]
        out = {"verdict": verdict.ok, "controls": controls,
               "attempted": sum(b["census"]["files"] for b in window),
               "failed": sum(
                   b["census"]["files"]
                   if (b["error"] or b["unsent_packfiles"])
                   else b["stats"]["failed_files"] for b in window),
               "memory_peak_bytes": memory_peak,
               "backups_in_window": len(window),
               "compiles_in_window": compiles_in_window,
               "compile_s_in_window": sum(
                   sum(b["compile_wall"].values()) for b in window)}
        if self.trace:
            out["layer_ctx"] = {
                "backups": done, "trace": self.trace_result,
                "traced": self.traced, "device": None,
                "meters": {"compiles_in_window": compiles_in_window,
                           "memory_peak_bytes": memory_peak}}
            return out
        user = sum(b["user_bytes"] for b in done)
        wall = sum(b["wall_s"] for b in done)
        out["end_to_end"] = {
            "backup_mib_s": (user / specs.MiB / wall) if wall else None,
            "setup_s": setup_s}
        return out

    def _reduce_trace(self, trace_dir: str) -> None:
        path = tracered.find_xplane(trace_dir)
        if path is None:
            return
        self.trace_result = tracered.reduce_xplane(
            path, self.traced["wall_s"],
            phase=f"backup:{self.traced['generation']}")
        if self.trace_result is None:
            return
        emit(phase="trace", **{k: v for k, v in self.trace_result.items()
                               if k not in ("device_ops", "idle_gaps")})
