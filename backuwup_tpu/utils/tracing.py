"""Backward-compat facade over :mod:`backuwup_tpu.obs.trace`.

The original host-tracing module (SURVEY §5.1) grew into the unified
observability plane: spans carry trace/span ids that propagate across
threads, tasks, and the wire, feed the ``bkw_span_seconds`` histogram
(its per-name count and sum are the aggregate the old flat table held),
and journal their closes.  Everything here re-exports the obs
implementation so the ``from ..utils import tracing`` call sites (and
external scripts) keep working unchanged; ``jax_profiler`` is the
``BKW_TRACE_DIR`` device-trace hook.

New code should import :mod:`backuwup_tpu.obs.trace` directly.
"""

from __future__ import annotations

from ..obs.trace import (  # noqa: F401  (re-exported API)
    bind,
    current,
    current_span_id,
    current_trace_id,
    jax_profiler,
    new_span_id,
    new_trace_id,
    span,
    traced,
)

__all__ = [
    "bind", "current", "current_span_id", "current_trace_id",
    "jax_profiler", "new_span_id", "new_trace_id", "span", "traced",
]
