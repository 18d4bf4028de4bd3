"""Offline reports over what a serve run leaves behind.

Where a live engine's wall-clock goes is the phase clock's to say
(``serve_loop_seconds_total{phase}``, always on; docs/observability.md
"Engine loop phases"), and which phase each device idle gap fell in is
``scripts/loop_gaps.py``'s; what the benchmark measures is
``python3 benchmark/run.py`` and ``PERF.md``. This script reads files and
scrapes a router:

- ``python scripts/serve_profile.py --trace trace.jsonl`` — read a
  PRIME_TRACE JSONL (from any serve run) and print the per-chunk
  dispatch-vs-sync overlap report: for every decode chunk, how long the
  host spent enqueuing it (``serve.dispatch``), how long it later blocked
  fetching the tokens (``serve.sync``), and the host-stall fraction of the
  dispatch→sync window. A well-overlapped engine shows stall fractions near
  zero; ~1.0 means the loop is effectively synchronous. When the run touched
  the prefix cache's host spill tier, a tier report follows: spill (demotion)
  timing from the store path and host-re-upload vs pure-HBM assemble costs
  (``serve.assemble`` spans carry ``tier=device|host``). ``--trace`` repeats:
  pass each process's JSONL (client, router, replicas) and spans sharing a
  W3C trace id are merged into a per-request cross-process waterfall —
  router queue → replica queue → prefill → decode, with parent→child gaps
  called out (``--trace-id`` narrows to one request).
- ``python scripts/serve_profile.py --fleet http://router:8080`` — scrape a
  running `prime serve fleet` router and print the routing report: request
  distribution and outcomes per replica, affinity hit ratio (the fraction of
  keyed requests the consistent-hash scheduler landed on their prefix-warm
  replica), reroute reasons, breaker states, and admission-gate queue waits.
- ``--slo report.json`` (alone, or with either of the above) — merge a
  loadgen SLO report's per-scenario rows (docs/benchmarking.md) into the
  output: the scenario table prints first (which scenario regressed), the
  waterfalls below it say where inside a request the time went. Repeatable
  to compare two reports side by side.
"""

from __future__ import annotations

import argparse
import json
import os
from collections import defaultdict


def overlap_report(path: str, quiet: bool = False) -> None:
    """Pair serve.dispatch / serve.sync spans by chunk seq and print the
    per-chunk host-stall breakdown plus aggregates. One PRIME_TRACE file can
    hold several engines' spans back-to-back (a process that builds a fresh
    engine per section, each restarting seq at 0): a dispatch whose seq was
    already seen starts a new run, so runs are reported separately instead
    of silently overwriting each other. Concurrent engines interleaving one
    sink are not disambiguated. ``quiet`` suppresses the no-engine-spans
    diagnostic — in multi-file waterfall mode, router/client files can never
    contain dispatch/sync pairs, and the hint would read as a serving
    misconfiguration that does not exist."""
    runs: list[tuple[dict[int, dict], dict[int, dict]]] = [({}, {})]
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            span = json.loads(line)
            seq = span.get("attrs", {}).get("seq")
            if seq is None:
                continue
            dispatch, sync = runs[-1]
            if span["name"] in ("serve.dispatch", "serve.spec_dispatch"):
                # spec chunks pipeline identically (one fused propose+verify
                # dispatch per seq) — same pairing, same stall math
                if seq in dispatch:  # seq restarted: a new engine's spans begin
                    dispatch, sync = {}, {}
                    runs.append((dispatch, sync))
                dispatch[seq] = span
            elif span["name"] == "serve.sync":
                sync[seq] = span
    runs = [(d, s) for d, s in runs if set(d) & set(s)]
    if not runs:
        if not quiet:
            print(f"no paired serve.dispatch/serve.sync spans in {path}")
        return
    tot_stall = tot_window = 0.0
    for i, (dispatch, sync) in enumerate(runs):
        seqs = sorted(set(dispatch) & set(sync))
        label = f" (engine run {i + 1}/{len(runs)})" if len(runs) > 1 else ""
        print(f"--- overlap report: {len(seqs)} chunks from {path}{label}")
        print(
            f"{'chunk':>6} {'dispatch_ms':>12} {'stall_ms':>10} "
            f"{'window_ms':>10} {'stall_frac':>10}"
        )
        for seq in seqs:
            d, s = dispatch[seq], sync[seq]
            # window: dispatch start -> sync end, on the shared monotonic clock
            window = (s["start_s"] + s["duration_s"]) - d["start_s"]
            stall = s["duration_s"]
            tot_stall += stall
            tot_window += window
            print(
                f"{seq:>6} {d['duration_s'] * 1e3:>12.2f} {stall * 1e3:>10.2f} "
                f"{window * 1e3:>10.2f} {stall / window if window > 0 else 0.0:>10.3f}"
            )
    frac = tot_stall / tot_window if tot_window > 0 else 0.0
    print(
        f"--- total: stall {tot_stall:.3f}s of {tot_window:.3f}s window "
        f"({frac:.1%} stalled, {1 - frac:.1%} overlapped)"
    )


def tier_report(paths: list[str]) -> None:
    """Prefix-cache tier timing from the trace JSONL: what demotions to the
    host spill tier cost on the store path (``serve.spill`` synthetic spans —
    each is a forced device sync) and what a host-tier hit's re-upload added
    to its assemble (``serve.assemble`` spans carry ``tier`` + token counts).
    Silent when the run never touched the spill tier — single-tier traces
    should not grow a table of zeros."""
    spans = _load_spans(paths)
    spills = [s for s in spans if s.get("name") == "serve.spill"]
    assembles = [s for s in spans if s.get("name") == "serve.assemble"]
    by_tier: dict[str, list[dict]] = defaultdict(list)
    for span in assembles:
        by_tier[(span.get("attrs") or {}).get("tier", "device")].append(span)
    if not spills and not by_tier.get("host"):
        return
    print("--- prefix-cache tier report")
    print(f"{'path':>22} {'count':>6} {'total_ms':>9} {'mean_ms':>8}  detail")
    if spills:
        total = sum(s.get("duration_s") or 0.0 for s in spills)
        segments = sum((s.get("attrs") or {}).get("segments", 0) for s in spills)
        nbytes = sum((s.get("attrs") or {}).get("bytes", 0) for s in spills)
        print(
            f"{'spill (store path)':>22} {len(spills):>6} {total * 1e3:>9.2f} "
            f"{total / len(spills) * 1e3:>8.2f}  {segments} segments, "
            f"{nbytes / 1e6:.2f} MB demoted"
        )
    for tier in ("host", "device"):
        group = by_tier.get(tier)
        if not group:
            continue
        total = sum(s.get("duration_s") or 0.0 for s in group)
        tokens = sum((s.get("attrs") or {}).get("hit_tokens", 0) for s in group)
        label = "assemble (re-upload)" if tier == "host" else "assemble (HBM hit)"
        detail = f"{tokens} hit tokens"
        if tier == "host":
            host_tokens = sum(
                (s.get("attrs") or {}).get("host_tokens", 0) for s in group
            )
            detail += f", {host_tokens} re-uploaded"
        print(
            f"{label:>22} {len(group):>6} {total * 1e3:>9.2f} "
            f"{total / len(group) * 1e3:>8.2f}  {detail}"
        )


def _load_spans(paths: list[str]) -> list[dict]:
    """Every parseable span from every file, tagged with its source file —
    the waterfall marks parent→child edges that cross files as the
    cross-process hops they are."""
    spans: list[dict] = []
    for path in paths:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    span = json.loads(line)
                except ValueError:
                    continue
                if isinstance(span, dict) and "name" in span:
                    span["_source"] = os.path.basename(path)
                    spans.append(span)
    return spans


def waterfall_report(paths: list[str], trace_id: str | None = None, limit: int = 5) -> None:
    """Merge spans from N processes' JSONL files by trace id and print a
    per-request waterfall: one indented tree per trace, offsets on the
    shared wall clock (start_unix_s — the cross-process axis; the monotonic
    start_s only orders within one process), durations, and the gap between
    each child's start and its parent's, flagged ``[cross-process]`` when
    the edge spans two files. Gaps are where a distributed request's time
    goes missing: router queue → replica queue → prefill → decode should
    tile the root span; a hole is a stall nobody's histogram attributes."""
    spans = _load_spans(paths)
    groups: dict[str, list[dict]] = defaultdict(list)
    for span in spans:
        tid = span.get("trace_id")
        if tid:
            groups[tid].append(span)
    if trace_id is not None:
        if trace_id not in groups:
            print(f"no spans with trace id {trace_id}")
            return
        selected = [(trace_id, groups[trace_id])]
    else:
        multi = {tid: g for tid, g in groups.items() if len(g) >= 2}
        if not multi:
            if trace_id is None and not any(s.get("trace_id") for s in spans):
                return  # single-process legacy file: the overlap report said it all
            print("no multi-span traces to stitch (single-span traces only)")
            return
        # newest requests first, bounded — a long serve run holds thousands
        newest = sorted(
            multi.items(),
            key=lambda item: max(s.get("start_unix_s", 0) for s in item[1]),
            reverse=True,
        )
        selected = newest[:limit]
        if len(newest) > limit:
            print(
                f"--- showing the {limit} newest of {len(newest)} stitched "
                "traces (use --trace-id to pick one)"
            )
    for tid, group in selected:
        sources = sorted({s["_source"] for s in group})
        print(f"--- trace {tid}: {len(group)} spans from {', '.join(sources)}")
        print(f"{'offset_ms':>10} {'dur_ms':>9}  span")
        by_id = {s["span_id"]: s for s in group if s.get("span_id")}
        children: dict[str, list[dict]] = defaultdict(list)
        roots: list[dict] = []
        for span in group:
            parent = by_id.get(span.get("parent_id"))
            if parent is not None and parent is not span:
                children[span["parent_id"]].append(span)
            else:
                roots.append(span)
        t0 = min(s.get("start_unix_s", 0.0) for s in group)

        def emit(span: dict, depth: int, parent: dict | None) -> None:
            start = span.get("start_unix_s", 0.0) - t0
            dur = span.get("duration_s") or 0.0
            notes = []
            if parent is not None:
                gap = span.get("start_unix_s", 0.0) - parent.get("start_unix_s", 0.0)
                hop = span["_source"] != parent["_source"]
                if hop or gap * 1e3 >= 1.0:
                    notes.append(
                        f"+{gap * 1e3:.2f} ms after parent"
                        + (" [cross-process]" if hop else "")
                    )
            attrs = span.get("attrs") or {}
            brief = ", ".join(
                f"{k}={attrs[k]}"
                for k in (
                    "replica", "request", "outcome", "slot", "prompt_len",
                    "tokens", "tier", "hit_tokens", "host_tokens",
                )
                if k in attrs
            )
            line = f"{start * 1e3:>10.2f} {dur * 1e3:>9.2f}  {'  ' * depth}{span['name']}"
            if brief:
                line += f" ({brief})"
            if notes:
                line += "  " + " ".join(notes)
            print(line)
            for child in sorted(
                children.get(span.get("span_id"), []),
                key=lambda s: s.get("start_unix_s", 0.0),
            ):
                emit(child, depth + 1, span)

        for root in sorted(roots, key=lambda s: s.get("start_unix_s", 0.0)):
            emit(root, 0, None)


def slo_report(paths: list[str]) -> None:
    """Per-scenario SLO rows from loadgen report file(s) — printed above
    the waterfalls so one invocation answers both 'which scenario
    regressed' and 'where in the request did the time go'. Multiple
    reports print in argument order (pass previous + current to eyeball
    the delta; `prime bench delta` renders the committed trajectory)."""

    def ms(quantiles: dict | None, key: str) -> str:
        value = (quantiles or {}).get(key)
        return f"{value * 1e3:.1f}" if isinstance(value, (int, float)) else "—"

    for path in paths:
        try:
            with open(path) as f:
                report = json.load(f)
        except (OSError, ValueError) as e:
            print(f"--- SLO report {path}: unreadable ({e})")
            continue
        headline = report.get("headline", {})
        print(
            f"--- SLO report {path} (schema {report.get('slo_schema', '?')}): "
            f"aggregate {headline.get('tok_s', '?')} tok/s, "
            f"{headline.get('requests', '?')} requests, "
            f"{headline.get('rejected_429', 0)} rejected"
        )
        print(
            f"{'scenario':>18} {'tok/s':>8} {'ttft_p50':>9} {'ttft_p95':>9} "
            f"{'tpot_p50':>9} {'starved%':>8} {'hit':>6} {'429s':>5}  outcomes"
        )
        for row in report.get("scenarios", []):
            outcomes = ", ".join(
                f"{k}={v}" for k, v in sorted((row.get("outcomes") or {}).items())
            )
            fleet = row.get("fleet") or {}
            if fleet.get("affinity_ratio") is not None:
                outcomes += f" | affinity {fleet['affinity_ratio']}"
            print(
                f"{row.get('scenario', '?'):>18} {row.get('tok_s', 0):>8} "
                f"{ms(row.get('ttft_s'), 'p50'):>9} {ms(row.get('ttft_s'), 'p95'):>9} "
                f"{ms(row.get('tpot_s'), 'p50'):>9} "
                f"{row.get('chip_starved_share') if row.get('chip_starved_share') is not None else '—':>8} "
                f"{row.get('prefix_hit_ratio') if row.get('prefix_hit_ratio') is not None else '—':>6} "
                f"{row.get('rejected_429', 0):>5}  {outcomes}"
            )


def fleet_report(url: str) -> None:
    """Scrape a FleetRouter's /metrics and /admin/fleet and print where the
    traffic went and why — the first question when fleet throughput
    disappoints is 'did affinity routing actually concentrate the shared
    prefixes, or did saturation/reroutes scatter them?'."""
    import httpx

    base = url.rstrip("/")
    stats = httpx.get(f"{base}/metrics", timeout=10).json()
    print(f"--- fleet routing report: {base}")
    print(
        f"affinity: {stats['affinity_hits']}/{stats['affinity_requests']} keyed "
        f"requests hit their hash target (ratio {stats['affinity_hit_ratio']})"
    )
    rejected = stats.get("admission_rejected", 0)
    if rejected:
        print(f"admission gate rejected {rejected} requests (fleet saturated)")
    if stats.get("reroutes"):
        reasons = ", ".join(f"{k}={v}" for k, v in sorted(stats["reroutes"].items()))
        print(f"reroutes: {reasons}")
    print(f"{'replica':>24} {'state':>9} {'breaker':>9} {'queue':>6} {'slots':>8} requests")
    for rid, replica in sorted(stats.get("replicas", {}).items()):
        outcomes = stats.get("requests_by_replica", {}).get(rid, {})
        shown = ", ".join(f"{k}={v}" for k, v in sorted(outcomes.items())) or "-"
        slots = f"{replica['active_slots']}/{replica['max_slots'] or '?'}"
        print(
            f"{rid:>24} {replica['state']:>9} {replica['breaker']:>9} "
            f"{replica['queue_depth']:>6} {slots:>8} {shown}"
        )
    registry = httpx.get(f"{base}/metrics", params={"format": "registry"}, timeout=10).json()
    wait = next(
        (s for s in registry["router"]["fleet_queue_wait_seconds"]["series"] if s["count"]),
        None,
    )
    if wait:
        print(
            f"admission queue wait: {wait['count']} requests, "
            f"mean {wait['sum'] / wait['count'] * 1e3:.2f} ms"
        )


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--trace", metavar="JSONL", action="append", default=None,
        help="Print the dispatch-vs-sync overlap report from a PRIME_TRACE "
             "JSONL. Repeatable: multiple files (router + replicas) are also "
             "stitched by trace id into per-request cross-process waterfalls.",
    )
    parser.add_argument(
        "--trace-id", metavar="HEX", default=None,
        help="With --trace: stitch only this W3C trace id's waterfall.",
    )
    parser.add_argument(
        "--fleet", metavar="ROUTER_URL", default=None,
        help="Print the routing report scraped from a running "
             "`prime serve fleet` router.",
    )
    parser.add_argument(
        "--slo", metavar="REPORT_JSON", action="append", default=None,
        help="Merge a loadgen SLO report's per-scenario rows into the "
             "output (above the waterfalls). Repeatable.",
    )
    args = parser.parse_args()
    if not (args.slo or args.trace or args.fleet):
        parser.error("one of --trace, --fleet or --slo is required")
    # --slo composes with every mode: scenario rows print first, then
    # whichever detail view (--trace waterfalls / --fleet routing)
    if args.slo:
        slo_report(args.slo)
    if args.trace:
        for path in args.trace:
            overlap_report(path, quiet=len(args.trace) > 1)
        tier_report(args.trace)
        waterfall_report(args.trace, trace_id=args.trace_id)
    elif args.fleet:
        fleet_report(args.fleet)


if __name__ == "__main__":
    main()
