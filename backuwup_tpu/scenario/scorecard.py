"""Scenario scorecard: registry deltas + invariant samples -> pass/fail.

A scenario run (scenario/harness.py) captures a registry snapshot before
the first phase and after the last, and samples the durability invariant
gauges throughout.  This module turns those three inputs into the
scorecard the ISSUE/ROADMAP scenario-matrix item calls for:

* **counters** — per-series deltas of the interesting ``bkw_*_total``
  families (backups by outcome, shards rebuilt, audit verdicts, fault
  injections, engine busy rejections, retry firings, ...), so the card
  states what the run *did*, not what the process has ever done;
* **quantiles** — p50/p99 per labeled series of the latency histograms
  (span times, transfer wait/send, pack stages), estimated from the
  delta of the cumulative bucket counts with
  :func:`backuwup_tpu.obs.metrics.quantile_from_buckets`;
* **invariants** — seconds spent with a durability invariant violated
  (the headline), the worst status seen across samples, and the final
  sweep summary;
* **assertions** — the hard gates the harness derived from the scenario
  spec; ``passed`` is their conjunction.

Rendered as JSON (one machine-readable document), JSONL (the raw
invariant samples, one per line), or a human table (:meth:`render`).
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

from ..obs import metrics as obs_metrics

#: Counter families whose deltas the card surfaces (a family absent from
#: either snapshot simply contributes nothing).
COUNTER_FAMILIES = (
    "bkw_backup_runs_total",
    "bkw_restore_runs_total",
    "bkw_audit_rounds_total",
    "bkw_audit_total",
    "bkw_repair_rounds_total",
    "bkw_repair_shards_rebuilt_total",
    "bkw_engine_busy_rejections_total",
    "bkw_transfers_total",
    "bkw_transfer_bytes_total",
    "bkw_fault_injections_total",
    "bkw_retry_attempts_total",
    "bkw_erasure_events_total",
    "bkw_durability_sweeps_total",
    "bkw_durability_violation_seconds_total",
    # performance plane (PR 7): pipeline dispatch accounting and the
    # per-peer estimator feed — the telemetry_flowing gate reads these
    "bkw_device_dispatch_total",
    "bkw_pipeline_stage_bytes_total",
    "bkw_peer_transfer_samples_total",
    # resumable WAN transfer plane (PR 8): chunked frames, byte-range
    # resume accounting, stall aborts, and capacity-aware placement
    "bkw_p2p_bytes_sent_total",
    "bkw_p2p_sequence_breaks_total",
    "bkw_transfer_parts_total",
    "bkw_transfer_resumes_total",
    "bkw_transfer_stalls_total",
    "bkw_transfer_bytes_resent_total",
    "bkw_placement_demotions_total",
    # crash-consistency plane (PR 9): startup recovery sweeps, what each
    # sweep reconciled, and the receiver-side partial janitor — the
    # recovery_clean gate's evidence trail
    "bkw_recovery_runs_total",
    "bkw_recovery_items_total",
    "bkw_partials_expired_total",
    # scale-out coordination plane (PR 10): the matchmaking economy's
    # throughput, deadline-heap expiry, per-route request counts, and
    # the write-behind store's commit modes (group vs direct is the
    # swarm scenario's off-loop evidence)
    "bkw_matchmakings_total",
    "bkw_matchmaking_expired_total",
    "bkw_server_requests_total",
    "bkw_server_store_commits_total",
    # restore data plane (PR 11): shard-granular pull traffic per source
    # peer and the hedging policy's win/loss record — the restore
    # telemetry gate's evidence
    "bkw_restore_bytes_pulled_total",
    "bkw_restore_hedges_total",
    # snapshot lifecycle plane (PR 13): GC runs, what each swap retired,
    # and both ends of the reclaim protocol — the gc_* gates' evidence
    "bkw_gc_runs_total",
    "bkw_gc_snapshots_pruned_total",
    "bkw_gc_packfiles_dropped_total",
    "bkw_gc_packfiles_compacted_total",
    "bkw_gc_bytes_reclaimed_total",
    "bkw_reclaim_requests_total",
    "bkw_reclaim_bytes_freed_total",
    # live SLO plane (PR 20): recorder sweeps, budget breaches, and the
    # diagnosis reports the slo_* gates read
    "bkw_series_samples_total",
    "bkw_slo_breaches_total",
    "bkw_diagnosis_reports_total",
)

#: Histogram families quantiled in the card.
HISTOGRAM_FAMILIES = (
    "bkw_span_seconds",
    "bkw_transfer_wait_seconds",
    "bkw_transfer_send_seconds",
    "bkw_pack_stage_seconds",
    "bkw_peer_transfer_wait_seconds",
    "bkw_peer_transfer_send_seconds",
    "bkw_recovery_seconds",
    # scale-out coordination plane (PR 10)
    "bkw_server_request_seconds",
    "bkw_loop_stall_seconds",
    "bkw_server_store_batch_ops",
    # restore data plane (PR 11): how many distinct holders each stripe
    # actually drew from
    "bkw_restore_sources_per_stripe",
)


def _series_map(snapshot: dict, family: str) -> Dict[str, dict]:
    """{label-string: series dict} for one family of a snapshot."""
    fam = snapshot.get(family)
    if not fam:
        return {}
    out = {}
    for series in fam.get("series", []):
        labels = series.get("labels", {})
        key = ",".join(f'{k}={labels[k]}' for k in sorted(labels))
        out[key] = series
    return out


def _flat(family: str, key: str) -> str:
    return f"{family}{{{key}}}" if key else family


def counter_deltas(before: dict, after: dict,
                   families=COUNTER_FAMILIES) -> Dict[str, float]:
    """Positive per-series counter deltas, flattened to
    ``name{label=value,...}`` keys."""
    out: Dict[str, float] = {}
    for family in families:
        prior = _series_map(before, family)
        for key, series in _series_map(after, family).items():
            delta = float(series.get("value", 0.0)) - \
                float(prior.get(key, {}).get("value", 0.0))
            if delta > 0:
                out[_flat(family, key)] = round(delta, 6)
    return out


def _bucket_delta(before_b: Dict[str, int],
                  after_b: Dict[str, int]):
    """(bounds, per-bucket counts) from two cumulative exposition views."""
    keys = [k for k in after_b if k != "+Inf"]
    keys.sort(key=float)
    bounds = [float(k) for k in keys]
    cum_prev = 0
    counts: List[int] = []
    for k in keys:
        cum = int(after_b.get(k, 0)) - int(before_b.get(k, 0))
        counts.append(cum - cum_prev)
        cum_prev = cum
    inf = int(after_b.get("+Inf", 0)) - int(before_b.get("+Inf", 0))
    counts.append(inf - cum_prev)
    return bounds, counts


def histogram_quantiles(before: dict, after: dict,
                        families=HISTOGRAM_FAMILIES,
                        qs=(0.5, 0.99)) -> Dict[str, dict]:
    """Per-series p50/p99 (and count/mean) of the run's OWN observations
    — the bucket-count deltas, not the process lifetime."""
    out: Dict[str, dict] = {}
    for family in families:
        prior = _series_map(before, family)
        for key, series in _series_map(after, family).items():
            pb = prior.get(key, {})
            bounds, counts = _bucket_delta(pb.get("buckets", {}),
                                           series.get("buckets", {}))
            total = sum(counts)
            if total <= 0 or not bounds:
                continue
            entry = {"count": total}
            dsum = float(series.get("sum", 0.0)) - float(pb.get("sum", 0.0))
            entry["mean"] = round(dsum / total, 6)
            for q in qs:
                v = obs_metrics.quantile_from_buckets(bounds, counts, q)
                entry[f"p{int(q * 100)}"] = \
                    None if math.isnan(v) else round(v, 6)
            out[_flat(family, key)] = entry
    return out


@dataclass
class Assertion:
    """One hard gate: named, binary, with the evidence inline."""

    name: str
    passed: bool
    detail: str = ""

    def to_dict(self) -> dict:
        return {"name": self.name, "passed": bool(self.passed),
                "detail": self.detail}


@dataclass
class Scorecard:
    scenario: str
    seed: int
    elapsed_s: float
    phases: List[str]
    counters: Dict[str, float]
    quantiles: Dict[str, dict]
    invariants: dict
    assertions: List[Assertion]
    samples: List[dict] = field(default_factory=list, repr=False)

    @property
    def passed(self) -> bool:
        return all(a.passed for a in self.assertions)

    def to_dict(self, with_samples: bool = False) -> dict:
        doc = {
            "scenario": self.scenario,
            "seed": self.seed,
            "elapsed_s": round(self.elapsed_s, 3),
            "passed": self.passed,
            "phases": list(self.phases),
            "counters": dict(self.counters),
            "quantiles": dict(self.quantiles),
            "invariants": dict(self.invariants),
            "assertions": [a.to_dict() for a in self.assertions],
        }
        if with_samples:
            doc["samples"] = list(self.samples)
        return doc

    def write_json(self, path) -> None:
        Path(path).write_text(
            json.dumps(self.to_dict(), indent=2, sort_keys=True) + "\n")

    def write_samples_jsonl(self, path) -> None:
        with open(path, "w", encoding="utf-8") as f:
            for sample in self.samples:
                f.write(json.dumps(sample, sort_keys=True) + "\n")

    def render(self) -> str:
        """Human-readable card for the CLI."""
        lines = [f"scenario {self.scenario} (seed {self.seed}): "
                 f"{'PASS' if self.passed else 'FAIL'} "
                 f"in {self.elapsed_s:.1f}s over "
                 f"{len(self.phases)} phase(s)"]
        inv = self.invariants
        lines.append(
            f"  invariants: violation_seconds="
            f"{inv.get('violation_seconds', 0)} "
            f"worst_status={inv.get('worst_status', '?')} "
            f"final_status={inv.get('final', {}).get('status', '?')} "
            f"samples={inv.get('samples', 0)}")
        for name, value in sorted(self.counters.items()):
            lines.append(f"  {name} {value:g}")
        for name, entry in sorted(self.quantiles.items()):
            lines.append(
                f"  {name} p50={entry.get('p50')} p99={entry.get('p99')}"
                f" n={entry['count']}")
        for a in self.assertions:
            mark = "ok " if a.passed else "FAIL"
            lines.append(f"  [{mark}] {a.name}"
                         + (f" — {a.detail}" if a.detail else ""))
        return "\n".join(lines)


def build_scorecard(scenario: str, seed: int, elapsed_s: float,
                    phases: List[str], before: dict, after: dict,
                    samples: List[dict],
                    assertions: List[Assertion]) -> Scorecard:
    """Assemble the card from the harness's raw captures."""
    counters = counter_deltas(before, after)
    violation_s = sum(
        v for k, v in counters.items()
        if k.startswith("bkw_durability_violation_seconds_total"))
    worst = 0
    for sample in samples:
        worst = max(worst, int(sample.get("status_level", 0)))
    invariants = {
        "violation_seconds": round(violation_s, 3),
        "worst_status": ["ok", "degraded", "violated"][min(worst, 2)],
        "samples": len(samples),
        "final": samples[-1] if samples else {},
    }
    return Scorecard(scenario=scenario, seed=seed, elapsed_s=elapsed_s,
                     phases=phases, counters=counters,
                     quantiles=histogram_quantiles(before, after),
                     invariants=invariants, assertions=assertions,
                     samples=samples)
