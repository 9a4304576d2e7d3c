#!/usr/bin/env python3
"""Per-config trend deltas across the BENCH_*.json capture history.

Every capture (a ``BENCH_*.json`` file holding one ``bench.py`` output
line) carries the same shape: a
top-level headline (``metric``/``value``/``vs_baseline``) plus a
``configs`` map of per-config numeric evidence.  This script lines the
captures up in time order and prints, for every config metric, the
latest value against its previous appearance — then **exits nonzero
when a gated metric regressed** beyond the tolerance, so the watch
loop (and a human about to trust a number) learns about a slide the
moment it is captured, not at the next paper-draft read-through.

Direction is inferred from the metric name (throughput/speedup/
ratio/efficiency-style names must not drop; seconds/latency/debt-style
names must not rise); names that match neither way are printed as
informational but never gate.  Stdlib-only, like every script here.

    python scripts/bench_trend.py                  # repo-root history
    python scripts/bench_trend.py --dir out/ --tolerance 0.05
    BENCH_TREND_TOLERANCE=0.2 python scripts/bench_trend.py file1 file2
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

#: substrings that mark a metric higher-is-better (checked first: a
#: throughput named mib_s must not fall into the seconds bucket below)
_HIGHER = ("mib_s", "speedup", "throughput", "ratio", "efficiency",
           "hit_rate", "events_per", "compression", "precision",
           "vs_baseline", "files")
#: substrings / suffixes that mark a metric lower-is-better
_LOWER_SUB = ("latency", "lag", "debt", "lost", "violation", "stall",
              "detection", "wait")
_LOWER_SUFFIX = ("_s", "_seconds", "_bytes", "_p99", "_p50")


def direction(metric: str) -> int:
    """+1 must-not-drop, -1 must-not-rise, 0 informational only."""
    m = metric.lower()
    if any(s in m for s in _HIGHER):
        return 1
    if any(s in m for s in _LOWER_SUB) or m.endswith(_LOWER_SUFFIX):
        return -1
    return 0


def load_record(path: str):
    """The capture's parsed BENCH record, or None when the file is not
    a usable capture (torn write, device-down run carrying ``error``)."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]  # driver wrapper {cmd, rc, parsed, ...}
    if not isinstance(doc, dict) or doc.get("error"):
        return None
    return doc


def flatten(record: dict) -> "dict[tuple, float]":
    """(config, metric) -> value; the headline rides as config ''."""
    out = {}
    headline = str(record.get("metric", "value"))
    for key in ("value", "vs_baseline"):
        if isinstance(record.get(key), (int, float)):
            tag = headline if key == "value" \
                else f"{headline} vs_baseline"
            out[("", tag)] = float(record[key])
    configs = record.get("configs")
    if isinstance(configs, dict):
        for cfg, metrics in configs.items():
            if not isinstance(metrics, dict):
                continue
            for metric, value in metrics.items():
                if isinstance(value, (int, float)) \
                        and not isinstance(value, bool):
                    out[(str(cfg), str(metric))] = float(value)
    return out


def compare(history, tolerance: float):
    """[(config, metric, prev, last, rel_delta, regressed)] between each
    key's last two appearances across the time-ordered history."""
    series: dict = {}
    for _path, flat in history:
        for key, value in flat.items():
            series.setdefault(key, []).append(value)
    rows = []
    for (cfg, metric), values in sorted(series.items()):
        if len(values) < 2:
            continue
        prev, last = values[-2], values[-1]
        base = max(abs(prev), 1e-12)
        rel = (last - prev) / base
        sense = direction(metric)
        regressed = (sense > 0 and rel < -tolerance) or \
                    (sense < 0 and rel > tolerance)
        rows.append((cfg, metric, prev, last, rel, regressed))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*",
                    help="explicit captures in time order (default:"
                         " BENCH_*.json under --dir, mtime order)")
    ap.add_argument("--dir", default=os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."),
        help="directory to glob BENCH_*.json from (default: repo root)")
    ap.add_argument("--tolerance", type=float, default=float(
        os.environ.get("BENCH_TREND_TOLERANCE", 0.10)),
        help="relative slide a gated metric may take before the exit"
             " code turns nonzero (default 0.10, env"
             " BENCH_TREND_TOLERANCE)")
    args = ap.parse_args(argv)

    paths = args.files or sorted(
        glob.glob(os.path.join(args.dir, "BENCH_*.json")),
        key=lambda p: (os.path.getmtime(p), p))
    history = []
    for path in paths:
        record = load_record(path)
        if record is not None:
            history.append((path, flatten(record)))
    if len(history) < 2:
        print(f"bench-trend: {len(history)} usable capture(s) — need 2"
              f" for a delta; nothing to compare")
        return 0

    rows = compare(history, args.tolerance)
    regressions = 0
    for cfg, metric, prev, last, rel, regressed in rows:
        tag = f"{cfg}/{metric}" if cfg else metric
        flag = ""
        if regressed:
            flag = "  REGRESSION"
            regressions += 1
        elif direction(metric) == 0:
            flag = "  (info)"
        print(f"{tag}: {prev:g} -> {last:g} ({rel:+.1%}){flag}")
    print(f"bench-trend: {len(history)} captures, {len(rows)} tracked"
          f" metrics, {regressions} regression(s)"
          f" (tolerance {args.tolerance:.0%})")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
