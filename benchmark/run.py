#!/usr/bin/env python3
"""benchmark/run.py: one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, generators and per-layer metric
readers are files found by name (benchmark/specs.py).  The last line of
stdout is the result object; with ``--trace 0`` its metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics (the
first timed backup runs under the profiler).

Where ``jax.devices()[0].platform`` is not ``tpu``, or JAX sees another
number of chips than the cell asks for, nothing runs and the exit code is
1.  ``--rehearse`` lifts that for a run on the CPU at the tiny sizes the
files' ``rehearse`` blocks give; its result says ``"correct": false`` so
that it can never be taken for a measurement.  ``JAX_PLATFORMS`` is never
set here, and ``BENCH_RUN`` is not read.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
# the script's own directory leaves the path (its ``tests`` and
# ``reference`` would shadow top-level names); the checkout's root joins
sys.path[:] = [str(_ROOT)] + [p for p in sys.path
                              if Path(p or ".").resolve() != _ROOT / "benchmark"]


def configure_compile_cache() -> str:
    """The persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    where that is set, else at ``<checkout>/.jax_cache`` (the program's
    own rule, ``utils/jaxcache.py``), with no minimum compile time or
    entry size: the program's 44 sub-second start-up programs are cached
    too, which its own 1.0 s threshold leaves out."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        path = env
    else:
        path = str(_ROOT / ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None, controls: bool = False) -> int:
    """``controls``: after the check, run its controls on this run's
    record (``benchmark/tests/chip_controls.py``; not a flag, the
    benchmark's own runs never run them)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints correct=false")
    args = ap.parse_args(argv)

    from benchmark import specs
    try:
        cell = specs.cell(args.workload, rehearse=args.rehearse)
    except specs.SpecError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    try:
        import backuwup_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout ({e}); "
              f"nothing ran", file=sys.stderr)
        return 1

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not args.rehearse:
        if dev.platform != "tpu":
            print(f"benchmark: no TPU here (jax.devices()[0].platform = "
                  f"{dev.platform!r}); nothing ran", file=sys.stderr)
            return 1
        if device["count"] != cell["chips"]:
            print(f"benchmark: cell {cell['name']} asks for "
                  f"{cell['chips']} chip(s), JAX sees {device['count']}; "
                  f"nothing ran", file=sys.stderr)
            return 1
        specs.peaks(device["kind"])  # an unknown kind is an error

    cache_dir = configure_compile_cache()
    from benchmark.cell import Run, emit
    from benchmark.deployment import Deployment
    emit(phase="device", device=device, cache_dir=cache_dir,
         workload=cell["name"], seed=args.seed, seconds=args.seconds,
         trace=args.trace, rehearse=args.rehearse, jax=jax.__version__)
    run = Run(cell, args.seed, args.seconds, bool(args.trace),
              args.rehearse, _T_START, controls=controls)
    out = asyncio.run(run.run(Deployment))
    print(json.dumps(result_line(cell, out, device, args.rehearse)),
          flush=True)
    return 0


def result_line(cell: dict, out: dict, device: dict, rehearse: bool) -> dict:
    from benchmark import readers
    from benchmark.specs import layer_metric
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    metrics = {}
    if "end_to_end" in out:
        for m in cell["end_to_end"]:
            value = out["end_to_end"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        ctx = out["layer_ctx"]
        ctx["device"] = device
        for m in cell["per_layer"]:
            value = readers.read(m["name"], layer_metric(m["name"]), ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if ctx["trace"]:
            device.update(busy_s=ctx["trace"]["busy_s"],
                          window_s=ctx["trace"]["window_s"])
    line = {"correct": bool(out["verdict"]) and not rehearse,
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device,
            "backups_in_window": out["backups_in_window"],
            "compiles_in_window": out["compiles_in_window"],
            "compile_s_in_window": out["compile_s_in_window"]}
    if rehearse:
        line["rehearsal_verdict"] = bool(out["verdict"])
    if out.get("controls") is not None:
        line["controls"] = out["controls"]
    if "layer_ctx" in out and out["layer_ctx"]["trace"]:
        line["breakdown"] = {
            "device_ops": out["layer_ctx"]["trace"]["device_ops"],
            "idle_gaps": out["layer_ctx"]["trace"]["idle_gaps"]}
    return line


if __name__ == "__main__":
    sys.exit(main())
