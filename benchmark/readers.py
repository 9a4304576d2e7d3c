"""Per-layer metric readers.  A metric is ``layer_metrics/<name>.json``:
its layer, unit, the end-to-end metric it moves, and a ``reader``:

* ``engine_report``: ``num`` (and optionally ``den``) are terms summed
  over the window's backups.  A term is ``{"report": <which>, "path":
  [...]}`` with ``report`` one of ``overlap`` (``engine.last_overlap``),
  ``pipeline`` (``engine.last_pipeline_report``), ``summary`` (the
  engine's summary event) or ``backup`` (the benchmark's own record of
  that backup); a path that ends at a mapping sums its values.
* ``registry_sum``: ``num`` is ``{"family", "labels", "field"}``, the
  delta of that series of the program's registry over the timed backups.
* ``trace``: ``reduction`` names a value of the trace reduction
  (``busy_s``, ``idle_share``), read in the traced backup.
* ``meter``: one of the benchmark's own meters.
* ``module``: ``layer_metrics/<name>.py`` with one ``read(ctx)``.

``den`` may also be ``"user_gib"`` (user bytes of the backups the
numerator covers, in GiB) or a number; ``scale`` multiplies the result.
A reader that finds nothing to read returns ``None`` and the metric is
left out of the line.
"""

from __future__ import annotations

import importlib
from typing import Optional

from benchmark import specs


def _at(obj, path):
    for key in path:
        if not isinstance(obj, dict) or key not in obj:
            return None
        obj = obj[key]
    if isinstance(obj, dict):
        vals = [v for v in obj.values() if isinstance(v, (int, float))]
        return float(sum(vals)) if vals else None
    return None if obj is None else float(obj)


def _term(term, backups: list) -> Optional[float]:
    vals = []
    for b in backups:
        src = b if term["report"] == "backup" else b.get(term["report"])
        v = _at(src or {}, term["path"])
        if v is not None:
            vals.append(v)
    return sum(vals) if vals else None


def _den(den, backups: list) -> Optional[float]:
    if den is None:
        return 1.0
    if den == "user_gib":
        return sum(b["user_bytes"] for b in backups) / specs.GiB
    if isinstance(den, (int, float)):
        return float(den)
    return _term(den, backups)


def _series(num: dict) -> tuple:
    return (num["family"], tuple(sorted(num.get("labels", {}).items())),
            num.get("field", "sum"))


def registry_series(metrics: list) -> list:
    """The (family, labels, field) series the cell's registry readers
    name, for the harness to snapshot around each timed backup."""
    return [_series(spec["reader"]["num"]) for spec in metrics
            if spec["reader"]["kind"] == "registry_sum"]


def read(name: str, spec: dict, ctx: dict) -> Optional[float]:
    r = spec["reader"]
    kind = r["kind"]
    backups = ctx["backups"]
    if kind == "module":
        mod = importlib.import_module(f"benchmark.layer_metrics.{name}")
        return mod.read(ctx)
    if kind == "meter":
        num = ctx["meters"].get(r["meter"])
    elif kind == "engine_report":
        num = _term(r["num"], backups)
    elif kind == "registry_sum":
        key = _series(r["num"])
        vals = [b["registry"][key] for b in backups
                if key in b.get("registry", {})]
        num = sum(vals) if vals else None
    elif kind == "trace":
        if not ctx.get("trace"):
            return None
        num = ctx["trace"].get(r["reduction"])
        backups = [ctx["traced"]]
    else:
        raise specs.SpecError(f"{name}: unknown reader kind {kind!r}")
    den = _den(r.get("den"), backups)
    if num is None or not den:
        return None
    return float(num) / den * float(r.get("scale", 1.0))
