"""Reduction from the profiler's trace (``.xplane.pb``) to device busy
seconds, idle gaps and the operations that took most time.

Device planes are the planes named ``/device:TPU:<n>`` (the profiler
also writes ``/device:CUSTOM:...`` planes that hold no operation).  On a
TPU plane the line ``XLA Ops`` holds one event per operation the chip
ran, the body of a loop nested inside the loop's own event; ``XLA
Modules`` holds one event per program (``jit_<name>(<hash>)``) and would
hide the gaps between operations.  Busy is the union of the ``XLA Ops``
intervals, averaged over the device planes; the breakdown names the
programs and the operations with the most summed seconds.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAME_CHARS = 120


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _events(plane, line_name: str) -> list:
    """[(start_ns, end_ns, name)] of one line of a device plane."""
    out = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            start = float(ev.start_ns)
            out.append((start, start + float(ev.duration_ns), ev.name))
    return out


def _top(events: list, n: int, prefix: str = "") -> list:
    by_name: dict = {}
    for s, e, name in events:
        if prefix:  # a program: jit_<name>(<hash>) -> jit_<name>
            name = prefix + name.split("(")[0]
        by_name[name] = by_name.get(name, 0.0) + (e - s) / 1e9
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:NAME_CHARS], secs] for name, secs in top]


def _union(events: list) -> tuple:
    """(busy_ns, gaps) of sorted intervals; a gap is (seconds, after)."""
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    last_name = ""
    for s, e, name in sorted(events):
        if cur_e is None:
            cur_s, cur_e, last_name = s, e, name
        elif s <= cur_e:
            if e > cur_e:
                cur_e, last_name = e, name
        else:
            busy += cur_e - cur_s
            gaps.append(((s - cur_e) / 1e9, last_name))
            cur_s, cur_e, last_name = s, e, name
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def reduce_xplane(path: str, window_s: float,
                  phase: str = "") -> Optional[dict]:
    """``busy_s`` (mean over device planes), ``idle_share`` of
    ``window_s``, the four programs and six operations with the most
    summed seconds, and the ten longest gaps between operations (named by
    the benchmark's phase and the operation the gap followed)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = [p for p in data.planes if DEVICE_PLANE.match(p.name)]
    if not planes:
        return None  # no device plane (a rehearsal on the CPU)
    busy, ops, modules, gaps = [], [], [], []
    for plane in planes:
        events = _events(plane, OPS_LINE)
        b, g = _union(events)
        busy.append(b / 1e9)
        gaps.extend(g)
        ops.extend(events)
        modules.extend(_events(plane, MODULES_LINE))
    busy_s = sum(busy) / len(busy)
    longest = sorted(gaps, key=lambda g: -g[0])[:10]
    return {
        "device_planes": [p.name for p in planes],
        "device_events": len(ops),
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": (1.0 - busy_s / window_s) if window_s > 0 else None,
        "device_ops": _top(modules, 4, prefix="program ") + _top(ops, 6),
        "idle_gaps": [[f"{phase} after {name}".strip()[:NAME_CHARS], secs]
                      for secs, name in longest],
    }

