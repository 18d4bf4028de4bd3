"""Versioned SLO report assembled from registry snapshot deltas.

The report's claim to honesty: every latency/throughput number is computed
from the *serving stack's own* observability — deltas between the registry
snapshots the runner took before and after the run (each stamped with the
monotonic ``captured_at`` that :meth:`Registry.snapshot` embeds, so the
throughput denominator is the same process's clock that counted the
tokens), with percentiles interpolated from obs ``Histogram`` buckets via
:func:`quantile_from_snapshot`. The client contributes only what no server
registry can know: outcome counts (a 429-rejected request never reaches an
engine histogram) and the schedule digest that pins what was asked.

Schema: ``slo_schema`` versions the report; a consumer seeing a bigger
number than it knows should fail loud, not guess. Field catalog in
docs/benchmarking.md.
"""

from __future__ import annotations

import math
from typing import Any

from prime_tpu.obs.metrics import (
    counter_delta,
    hist_delta,
    hist_series_from_snapshot,
    merge_hists,
    quantile_from_snapshot,
    snapshot_captured_at,
)

SLO_SCHEMA = 1

# the delta/merge arithmetic itself lives in obs/metrics.py (shared with the
# observatory time-series — one implementation, two consumers); this module
# keeps only the report-shaped selection logic on top of it
_captured_at = snapshot_captured_at
_hist_series = hist_series_from_snapshot
_hist_delta = hist_delta
_merge_hists = merge_hists


def _family(snapshot: dict, name: str) -> dict | None:
    family = snapshot.get(name)
    return family if isinstance(family, dict) else None


def _scalar(snapshot: dict, name: str, labels: dict | None = None) -> float:
    """A counter/gauge series value (0.0 when absent)."""
    family = _family(snapshot, name)
    if family is None:
        return 0.0
    want = labels or {}
    for series in family.get("series", []):
        if series.get("labels", {}) == want:
            return float(series.get("value", 0.0))
    return 0.0


def _scalar_sum(snapshot: dict, name: str, **fixed: str) -> float:
    """Sum of every series of a labeled counter matching ``fixed``."""
    family = _family(snapshot, name)
    if family is None:
        return 0.0
    total = 0.0
    for series in family.get("series", []):
        labels = series.get("labels", {})
        if all(labels.get(k) == v for k, v in fixed.items()):
            total += float(series.get("value", 0.0))
    return total


def _labeled_values(snapshot: dict, name: str, label: str) -> dict[str, float]:
    family = _family(snapshot, name)
    out: dict[str, float] = {}
    if family is None:
        return out
    for series in family.get("series", []):
        key = series.get("labels", {}).get(label)
        if key is not None:
            out[key] = out.get(key, 0.0) + float(series.get("value", 0.0))
    return out


def _quantiles(hist: dict | None, qs: tuple[float, ...] = (0.5, 0.95)) -> dict[str, float | None]:
    out: dict[str, float | None] = {}
    for q in qs:
        key = f"p{int(q * 100)}"
        if hist is None or hist["count"] <= 0:
            out[key] = None
        else:
            value = quantile_from_snapshot(hist["buckets"], hist["counts"], q)
            out[key] = None if math.isnan(value) else round(value, 6)
    return out


def snapshot_delta_seconds(before: dict, after: dict) -> float | None:
    """Wall seconds between two snapshots of the SAME registry, from the
    embedded monotonic ``captured_at`` — the report's only throughput
    denominator (never a client stopwatch)."""
    b, a = _captured_at(before), _captured_at(after)
    if b is None or a is None:
        return None
    return max(0.0, a - b)


def _engine_components(snapshots: dict[str, dict]) -> list[str]:
    """Components holding engine registries: the in-process ``engine`` key
    or any HTTP-scraped ``<label>.engine`` section."""
    return [
        name
        for name in snapshots
        if name == "engine" or name.endswith(".engine")
    ]


def _router_components(snapshots: dict[str, dict]) -> list[str]:
    return [
        name
        for name in snapshots
        if name == "router" or name.endswith(".router")
    ]


def scenario_row(result) -> dict[str, Any]:
    """One scenario's SLO row from a :class:`RunResult`'s snapshot pair."""
    before, after = result.before, result.after
    engines = _engine_components(after)
    routers = _router_components(after)
    warnings: list[str] = []
    if not engines:
        # loud, not a silent 0.0: a router-only scrape has no token counters
        # or latency histograms to window — the caller forgot the replica
        # URLs in HTTPTarget(scrape_urls=...), and a zero here would be
        # indistinguishable from the dead-backend trajectory
        warnings.append(
            "no engine registries in the scrape (pass replica URLs via "
            "HTTPTarget scrape_urls) — tok_s/latency fields are undefined"
        )
    if getattr(result, "timed_out", False):
        warnings.append(
            "run hit its deadline and was truncated — numbers cover only "
            "the completed portion of the schedule"
        )

    durations = [
        snapshot_delta_seconds(before.get(name, {}), after[name])
        for name in engines
        if name in before
    ]
    durations = [d for d in durations if d]
    duration_s = max(durations) if durations else None
    if engines and duration_s is None:
        warnings.append(
            "engine snapshots carry no captured_at window (pre-schema "
            "registry?) — tok_s is undefined, not zero"
        )
    if result.outcomes.get("failed", 0):
        warnings.append(
            f"{result.outcomes['failed']} request(s) FAILED client-side — "
            "throughput covers only the survivors"
        )

    def edelta(metric: str, labels: dict | None = None) -> float:
        # reset-aware (obs/metrics.counter_delta): a replica restarting
        # mid-run must clamp to its post-reset count, not subtract negative
        return sum(
            counter_delta(
                _scalar(before.get(name, {}), metric, labels),
                _scalar(after[name], metric, labels),
            )[0]
            for name in engines
        )

    def ehist(metric: str, labels: dict | None = None) -> dict | None:
        return _merge_hists(
            _hist_delta(
                _hist_series(before.get(name, {}), metric, labels),
                _hist_series(after[name], metric, labels),
            )
            for name in engines
        )

    tokens = edelta("serve_tokens_emitted_total")
    admitted = edelta("serve_requests_admitted_total")
    hits = edelta("serve_prefix_hits_total")

    def phases_delta(family: str, **fixed: str) -> float:
        return sum(
            counter_delta(
                _scalar_sum(before.get(name, {}), family, **fixed),
                _scalar_sum(after[name], family, **fixed),
            )[0]
            for name in engines
        )

    # the phase clock's two families: of the loop's seconds with a request
    # queued or live, the percentage in which the chip was seen to have no
    # model program left to run; absent where an engine has no starved clock
    with_work = phases_delta("serve_loop_seconds_total") - phases_delta(
        "serve_loop_seconds_total", phase="no_work"
    )
    starved = phases_delta("serve_loop_starved_seconds_total") - phases_delta(
        "serve_loop_starved_seconds_total", phase="no_work"
    )
    has_starved_clock = any(
        "serve_loop_starved_seconds_total" in after[name] for name in engines
    )
    spec_accepted = (ehist("serve_spec_accepted_tokens") or {"sum": 0.0})["sum"]
    spec_proposed = edelta("serve_spec_draft_tokens_total")

    row: dict[str, Any] = {
        "scenario": result.scenario,
        "seed": result.seed,
        "schedule_digest": result.digest,
        "requests": result.requests,
        "outcomes": dict(result.outcomes),
        "client_tokens": result.client_tokens,
        "duration_s": round(duration_s, 6) if duration_s else None,
        "tokens": int(tokens),
        "tok_s": round(tokens / duration_s, 2) if duration_s else 0.0,
        "admitted": int(admitted),
        "completed": int(edelta("serve_requests_completed_total")),
        "cancelled": int(edelta("serve_requests_cancelled_total")),
        "failed": int(edelta("serve_requests_failed_total")),
        "chip_starved_share": (
            round(100.0 * starved / with_work, 2)
            if has_starved_clock and with_work > 0 else None
        ),
        "prefix_hit_ratio": round(hits / admitted, 4) if admitted else None,
        "prefix_hit_tokens": {
            tier: int(
                sum(
                    (_hist_series(after[name], "serve_prefix_hit_tokens", {"tier": tier}) or {"sum": 0.0})["sum"]
                    - (_hist_series(before.get(name, {}), "serve_prefix_hit_tokens", {"tier": tier}) or {"sum": 0.0})["sum"]
                    for name in engines
                )
            )
            for tier in ("device", "host")
        },
        "prefix_spills": int(edelta("serve_prefix_spills_total")),
        "prefix_reuploads": int(edelta("serve_prefix_reuploads_total")),
        "wasted_decode_tokens": int(edelta("serve_wasted_decode_tokens_total")),
        # speculative decoding (registry-windowed, like everything else):
        # accepted drafts from the histogram's sum delta, the ratio against
        # the proposed-draft counter delta. None when no verify window ran
        # in this scenario's bracket (spec off, or an idle window).
        "spec_accepted_tokens": int(spec_accepted),
        "spec_accept_ratio": (
            round(spec_accepted / spec_proposed, 4) if spec_proposed else None
        ),
        "ttft_s": _quantiles(ehist("serve_ttft_seconds")),
        "tpot_s": _quantiles(ehist("serve_tpot_seconds")),
        "queue_wait_s": _quantiles(ehist("serve_queue_wait_seconds")),
        "rejected_429": int(result.outcomes.get("rejected_429", 0)),
    }
    # multi-LoRA splits (windowed like everything else): per-adapter token
    # and TTFT attribution from the adapter-labeled engine families — the
    # evidence the fairness ratio and the ≥0.8x-of-base acceptance gate are
    # computed from. Absent entirely on bankless engines (no series).
    adapter_tokens: dict[str, float] = {}
    for name in engines:
        prev = _labeled_values(
            before.get(name, {}), "serve_adapter_tokens_total", "adapter"
        )
        for key, value in _labeled_values(
            after[name], "serve_adapter_tokens_total", "adapter"
        ).items():
            adapter_tokens[key] = (
                adapter_tokens.get(key, 0.0) + value - prev.get(key, 0.0)
            )
    adapter_tokens = {k: v for k, v in adapter_tokens.items() if v > 0}
    if adapter_tokens:
        row["adapters"] = {
            key: {
                "tokens": int(value),
                "tok_s": round(value / duration_s, 2) if duration_s else 0.0,
                "ttft_s": _quantiles(
                    ehist("serve_adapter_ttft_seconds", {"adapter": key})
                ),
                "queue_wait_s": _quantiles(
                    ehist("serve_adapter_queue_wait_seconds", {"adapter": key})
                ),
            }
            for key, value in sorted(adapter_tokens.items())
        }
    if warnings:
        row["warning"] = "; ".join(warnings)

    if routers:
        def rdelta(metric: str, **fixed: str) -> float:
            return sum(
                _scalar_sum(after[name], metric, **fixed)
                - _scalar_sum(before.get(name, {}), metric, **fixed)
                for name in routers
            )

        affinity_requests = rdelta("fleet_affinity_requests_total")
        affinity_hits = rdelta("fleet_affinity_hits_total")
        reroutes: dict[str, float] = {}
        for name in routers:
            for reason, value in _labeled_values(
                after[name], "fleet_reroutes_total", "reason"
            ).items():
                prev = _labeled_values(
                    before.get(name, {}), "fleet_reroutes_total", "reason"
                ).get(reason, 0.0)
                reroutes[reason] = reroutes.get(reason, 0.0) + value - prev
        # phase-split migrations (disaggregated serving), windowed like the
        # reroutes — a long-lived router's lifetime migration totals (warmup
        # traffic included) must not be misattributed to this scenario
        migrations: dict[str, float] = {}
        for name in routers:
            prev_m = _labeled_values(
                before.get(name, {}), "fleet_migrations_total", "outcome"
            )
            for outcome, value in _labeled_values(
                after[name], "fleet_migrations_total", "outcome"
            ).items():
                migrations[outcome] = (
                    migrations.get(outcome, 0.0) + value - prev_m.get(outcome, 0.0)
                )
        # per-replica split as a WINDOWED delta, like every other field in
        # the row — a long-lived router's lifetime totals must not be
        # misattributed to this scenario
        by_replica: dict[str, float] = {}
        for name in routers:
            prev = _labeled_values(
                before.get(name, {}), "fleet_requests_total", "replica"
            )
            for replica, value in _labeled_values(
                after[name], "fleet_requests_total", "replica"
            ).items():
                by_replica[replica] = (
                    by_replica.get(replica, 0.0) + value - prev.get(replica, 0.0)
                )
        row["fleet"] = {
            "affinity_ratio": (
                round(affinity_hits / affinity_requests, 4)
                if affinity_requests
                else None
            ),
            "cache_routed": int(rdelta("fleet_cache_routed_total")),
            "migrations": {k: int(v) for k, v in migrations.items() if v},
            "migrate_bytes": int(rdelta("fleet_migrate_bytes_total")),
            "reroutes": {k: int(v) for k, v in reroutes.items() if v},
            "admission_rejected": int(rdelta("fleet_admission_rejected_total")),
            "requests_by_replica": {
                replica: int(value) for replica, value in by_replica.items() if value
            },
        }
    return row


def spec_comparison_record(
    off_row: dict[str, Any], on_row: dict[str, Any], *, digits: int | None = None
) -> dict[str, Any]:
    """The ONE owner of the spec-on/off record keys the loadgen smoke
    publishes (and the committed BENCH_*.json rounds hold): spec-on/off tok/s,
    the speedup, the accept ratio, and the TPOT p50 pair — computed from
    two :func:`scenario_row` results over the same schedule. ``digits``
    rounds the tok/s values (bench's historical 1-decimal style)."""
    def _toks(row):
        value = row["tok_s"]
        return round(value, digits) if digits is not None else value

    record: dict[str, Any] = {
        "serve_spec_off_tok_s": _toks(off_row),
        "serve_spec_tok_s": _toks(on_row),
    }
    if off_row["tok_s"]:
        record["serve_spec_speedup"] = round(on_row["tok_s"] / off_row["tok_s"], 3)
    if on_row.get("spec_accept_ratio") is not None:
        record["serve_spec_accept_ratio"] = on_row["spec_accept_ratio"]
    for key, row in (("serve_spec", on_row), ("serve_spec_off", off_row)):
        p50 = (row.get("tpot_s") or {}).get("p50")
        if isinstance(p50, (int, float)):
            record[f"{key}_tpot_p50_ms"] = round(p50 * 1e3, 3)
    return record


def build_report(
    results, *, meta: dict | None = None, device_profile: dict | None = None
) -> dict[str, Any]:
    """The versioned SLO report: one row per scenario plus the aggregate
    headline. ``meta`` merges into the top level (backend identity, git
    rev, CI round). ``device_profile`` (a DeviceProfiler.summary() dict:
    per-phase step seconds, compile totals, cost-model MFU) rides under its
    own key — perf_delta tolerates rounds without it."""
    if not isinstance(results, (list, tuple)):
        results = [results]
    rows = [scenario_row(r) for r in results]
    total_tokens = sum(r["tokens"] for r in rows)
    total_duration = sum(r["duration_s"] or 0.0 for r in rows)
    report: dict[str, Any] = {
        "slo_schema": SLO_SCHEMA,
        "scenarios": rows,
        "headline": {
            "tok_s": round(total_tokens / total_duration, 2) if total_duration else 0.0,
            "tokens": int(total_tokens),
            "duration_s": round(total_duration, 6),
            "requests": sum(r["requests"] for r in rows),
            "rejected_429": sum(r["rejected_429"] for r in rows),
        },
    }
    if device_profile:
        report["device_profile"] = device_profile
    if meta:
        report.update(meta)
    return report
