"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

A configuration is ``benchmark/configs/<config>.json``, a traffic mix
``benchmark/traffic/<traffic>.json``, a per-layer metric
``benchmark/layer_metrics/<metric>.json`` (and ``<metric>.py`` where its
reader is a module), a generator ``benchmark/generators/<name>.py``.  A
later PR adds files and entries; nothing here names a cell.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

from benchmark.reference.gear import CDCParams

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MiB = 1 << 20
GiB = 1 << 30


class SpecError(Exception):
    pass


def _load(path: Path) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise SpecError(f"{path.relative_to(ROOT)} does not exist") from None


def benchmark() -> dict:
    return _load(ROOT / "BENCHMARK.json")


def rehearsed(spec: dict) -> dict:
    """``spec`` with its ``rehearse`` block laid over it (tiny sizes for
    a run on the CPU)."""
    out = dict(spec)
    out.update(spec.get("rehearse", {}))
    return out


def cell(name: str, rehearse: bool = False) -> dict:
    bench = benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    config = _load(BENCH / "configs" / f"{w['config']}.json")
    traffic = _load(BENCH / "traffic" / f"{w['traffic']}.json")
    if rehearse:
        config, traffic = rehearsed(config), rehearsed(traffic)

    def mine(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "name": name, "chips": int(w["chips"]), "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def layer_metric(name: str) -> dict:
    return _load(BENCH / "layer_metrics" / f"{name}.json")


def generator(name: str):
    return importlib.import_module(f"benchmark.generators.{name}")


def cdc_params(config: dict) -> CDCParams:
    return CDCParams(**{k: int(v) for k, v in config["cdc"].items()})


def peaks(device_kind: str) -> dict:
    table = _load(BENCH / "peaks.json")
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"benchmark/peaks.json; add it with its source")
    return table[device_kind]
