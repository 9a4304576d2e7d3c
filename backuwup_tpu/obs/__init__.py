"""Unified observability plane: metrics, correlated traces, event journal.

The single source of truth for runtime signals (SURVEY §5.5; the
reference prints ad-hoc lines and keeps no machine-readable telemetry):

* :mod:`~backuwup_tpu.obs.metrics` — a thread-safe process-wide registry
  of labeled Counters, Gauges, and log-bucketed Histograms with
  Prometheus text exposition and a JSON snapshot API;
* :mod:`~backuwup_tpu.obs.trace` — hierarchical spans with Dapper-style
  trace/span ids, propagated across the wire (p2p ``EncapsulatedMsg``
  and client<->server JSON messages) so one backup's
  pack -> seal -> transfer -> ack -> audit chain is joinable across
  processes;
* :mod:`~backuwup_tpu.obs.journal` — a size-rotated append-only JSONL
  journal of status events, span closes, retry firings, and fault-plane
  injections, with a panic handler that dumps the metrics snapshot plus
  the last N journal lines;
* :mod:`~backuwup_tpu.obs.invariants` — the durability invariant
  monitor: sweeps the verifier-side placement/audit state into live
  ``bkw_durability_*`` facts (clean survivors per stripe, repair debt,
  unrestorable packfiles) that /healthz and the scenario scorecard
  consume;
* :mod:`~backuwup_tpu.obs.expo` — ``GET /metrics`` + ``GET /healthz``
  exposition shared by the coordination server and the opt-in client
  status port;
* :mod:`~backuwup_tpu.obs.profile` — the performance half (GWP,
  PAPERS.md): per-stage device dispatch accounting
  (``bkw_device_dispatch_total``), padded-vs-actual byte efficiency,
  the honest chained-execution stage timer, and the per-backup
  pipeline report;
* :mod:`~backuwup_tpu.obs.timeline` — journals + spans exported as
  Chrome trace-event JSON (Perfetto), merging multiple clients'
  journals into one cross-process timeline keyed by trace id.

Import-light by design: this package depends only on the stdlib and
:mod:`backuwup_tpu.defaults` (``expo`` additionally on aiohttp), never
on jax or any accelerator runtime, so every layer can instrument itself
without import cycles or device initialization.
"""

from . import invariants, journal, metrics, profile, timeline, trace

__all__ = ["invariants", "journal", "metrics", "profile", "timeline",
           "trace"]
