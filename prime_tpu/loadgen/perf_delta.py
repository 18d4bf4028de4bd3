"""Per-PR perf trajectory: diff committed BENCH_*.json rounds into a table.

Every bench round committed one JSON record (last-JSON-line-wins; the
single-chip rounds came from the root-level bench script PR 28 deleted, the
CPU rounds from ``prime bench smoke``).
This module — stdlib-only, importable by both ``scripts/perf_delta.py`` and
``prime bench delta`` — loads every committed round, labels each with its
record schema (schema 1: the pre-loadgen rounds, headline-only fields;
schema 2: adds the loadgen SLO report under ``loadgen``), and renders the
metric-by-round delta table that answers the only question a perf PR has to
answer: which headline moved, by how much, since the previous round.

Zero-valued headlines are real data (five rounds of ``0.0 tok/s — backend
unresponsive`` ARE the trajectory this tooling exists to end) and render as
written; deltas are computed against the latest previous round with a
usable value so one dead round doesn't blind the comparison.
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any

# record keys → table rows, in display order. Ratios render raw; everything
# else is a rate where bigger is better.
HEADLINE_METRICS: tuple[tuple[str, str], ...] = (
    ("headline tok/s", "value"),
    ("decode-only tok/s", "decode_only_tok_s"),
    ("eval samples/s", "eval_samples_per_sec"),
    ("serve tok/s", "serve_tok_s"),
    ("serve int8 tok/s", "serve_int8_tok_s"),
    ("serve spec tok/s", "serve_spec_tok_s"),
    ("serve spec-off tok/s", "serve_spec_off_tok_s"),
    ("serve spec speedup", "serve_spec_speedup"),
    ("serve spec accept ratio", "serve_spec_accept_ratio"),
    ("prefixburst tok/s", "serve_prefixburst_tok_s"),
    ("prefixburst hit ratio", "serve_prefixburst_hit_ratio"),
    # paged-gather hit seeding (own keys: paged and copy numbers come from
    # one dedicated longprefix comparison and only delta against
    # themselves; seed-ms rows are the seeding-path wall time per hit)
    ("longprefix tok/s", "serve_longprefix_tok_s"),
    ("longprefix copy tok/s", "serve_longprefix_copy_tok_s"),
    ("longprefix seed ms", "serve_longprefix_seed_ms"),
    ("longprefix copy seed ms", "serve_longprefix_copy_seed_ms"),
    # kernel autotune round-trip (kernels with a winner + sweep wall time)
    ("autotune kernels", "autotune_kernels"),
    ("autotune sweep s", "autotune_sweep_s"),
    ("fleet tok/s", "serve_fleet_tok_s"),
    ("fleet affinity ratio", "serve_fleet_affinity_ratio"),
    # batched multi-LoRA serving (own keys: mixed-adapter and base-only
    # numbers come from one dedicated comparison and only delta against
    # themselves — the ratio row is the ≥0.8x acceptance gate's evidence)
    ("multilora tok/s", "serve_multilora_tok_s"),
    ("multilora base tok/s", "serve_multilora_base_tok_s"),
    ("multilora ratio", "serve_multilora_ratio"),
    ("multilora fairness", "serve_multilora_fairness"),
    # elastic fleet (own keys: the autoscaler's live 1→N→1 rate_storm leg —
    # peak/final counts are the control-loop evidence, tok/s the final
    # post-scale round's throughput; only ever deltas against itself)
    ("elastic tok/s", "serve_elastic_tok_s"),
    ("elastic peak replicas", "serve_elastic_peak_replicas"),
    ("elastic scale ups", "serve_elastic_scale_ups"),
    ("elastic scale downs", "serve_elastic_scale_downs"),
    # disaggregated prefill/decode (own keys, never folded into the serve/
    # fleet rows above: the phase-split and colocated numbers come from a
    # dedicated scenario and must only ever delta against themselves)
    ("disagg tok/s", "serve_disagg_tok_s"),
    ("disagg colocated tok/s", "serve_disagg_colo_tok_s"),
    ("disagg speedup", "serve_disagg_speedup"),
    ("disagg ttft p50 ms", "serve_disagg_ttft_p50_ms"),
    ("disagg colocated ttft p50 ms", "serve_disagg_colo_ttft_p50_ms"),
    ("disagg ttft p95 ms", "serve_disagg_ttft_p95_ms"),
    ("disagg colocated ttft p95 ms", "serve_disagg_colo_ttft_p95_ms"),
    ("disagg migrate bytes", "serve_disagg_migrate_bytes"),
    ("sharded tok/s", "serve_sharded_tok_s"),
    ("int8 tok/s", "int8_weights_tok_s"),
    ("int4 tok/s", "int4_weights_tok_s"),
    ("longctx pallas speedup", "longctx_pallas_speedup"),
    ("trainstep tok/s", "trainstep_tok_s"),
)

_ROUND_RE = re.compile(r"BENCH_(?:(?P<kind>[a-z_]+)_)?r(?P<num>\d+)\.json$")
_MULTICHIP_RE = re.compile(r"MULTICHIP_(?:(?P<kind>[a-z_]+)_)?r(?P<num>\d+)\.json$")


@dataclass
class Round:
    label: str
    path: str
    order: tuple
    schema: int
    record: dict[str, Any]
    metrics: dict[str, float] = field(default_factory=dict)

    @property
    def error(self) -> str | None:
        return self.record.get("error")


def _slo_metrics(report: dict) -> dict[str, float]:
    """Flatten a loadgen SLO report (schema 2 records carry one under
    ``loadgen``) into table rows: the aggregate headline plus per-scenario
    throughput and TTFT p50/p95."""
    out: dict[str, float] = {}
    headline = report.get("headline") or {}
    if isinstance(headline.get("tok_s"), (int, float)):
        out["loadgen tok/s"] = float(headline["tok_s"])
    for row in report.get("scenarios") or []:
        # "slo:" prefix keeps SLO-row names disjoint from HEADLINE_METRICS
        # labels — a scenario named "serve" must not silently overwrite the
        # record-field "serve tok/s" cell (different rounding, different
        # sourcing era)
        name = f"slo:{row.get('scenario', '?')}"
        if isinstance(row.get("tok_s"), (int, float)):
            out[f"{name} tok/s"] = float(row["tok_s"])
        for family, unit in (("ttft_s", "ttft"), ("tpot_s", "tpot")):
            quantiles = row.get(family) or {}
            for q in ("p50", "p95"):
                value = quantiles.get(q)
                if isinstance(value, (int, float)):
                    out[f"{name} {unit} {q} ms"] = round(value * 1e3, 3)
        ratio = row.get("spec_accept_ratio")
        if isinstance(ratio, (int, float)) and not isinstance(ratio, bool):
            out[f"{name} accept ratio"] = float(ratio)
        # the phase clock's starved seconds over the loop's with work (a round
        # from before the starved clock has no such field, and no such row)
        starved = row.get("chip_starved_share")
        if isinstance(starved, (int, float)) and not isinstance(starved, bool):
            out[f"{name} chip starved %"] = float(starved)
    return out


def _device_profile_metrics(profile: dict) -> dict[str, float]:
    """Flatten a record's ``device_profile`` section (the profiler summary
    a round embeds: per-phase step seconds, cost-model MFU, compile totals)
    into "dp:"-prefixed rows — disjoint from HEADLINE_METRICS labels like
    the "slo:" rows. Rounds without the section (every pre-profiler
    baseline) simply render "—" for these rows, never an error."""
    out: dict[str, float] = {}
    for phase, entry in sorted((profile.get("phases") or {}).items()):
        if not isinstance(entry, dict):
            continue
        mean = entry.get("mean_s")
        if isinstance(mean, (int, float)) and not isinstance(mean, bool):
            out[f"dp:{phase} step ms"] = round(float(mean) * 1e3, 3)
        for key, label in (
            ("mfu", "mfu"),
            ("achieved_tflops", "tflops"),
            ("achieved_gbps", "gb/s"),
        ):
            value = entry.get(key)
            if isinstance(value, (int, float)) and not isinstance(value, bool):
                out[f"dp:{phase} {label}"] = float(value)
    compiles = profile.get("compiles")
    if isinstance(compiles, dict):
        for key, label in (("total", "compiles"), ("seconds", "compile s")):
            value = compiles.get(key)
            if isinstance(value, (int, float)) and not isinstance(value, bool):
                out[f"dp:{label}"] = float(value)
    return out


def _is_sharded_smoke_record(record: dict[str, Any]) -> bool:
    """The dedicated sharded loadgen smoke record (run_smoke --mesh) is
    recognizable by its OWN evidence — top-level ``mesh_devices`` plus the
    ``serve_sharded_tok_s`` metric name — so committing one under a
    BENCH_*.json name still routes it to the mc rows instead of rendering
    its sharded headline in the single-chip 'cpu-smoke tok/s' trajectory.
    Full single-chip bench records are NOT matched (their sharded section fields are
    ``serve_``-prefixed and their metric is the decode headline): those are
    genuinely mixed records whose family the filename decides."""
    inner = (
        record.get("parsed")
        if ("parsed" in record and "rc" in record)
        else record
    )
    if not isinstance(inner, dict):
        return False
    return bool(inner.get("mesh_devices")) and str(
        inner.get("metric", "")
    ).startswith("serve_sharded_tok_s")


def _round_from_record(path: str, record: dict[str, Any]) -> Round:
    # family is inferred from the FILENAME or the record's own sharded
    # stamps, not a caller flag: an explicit --pattern 'MULTICHIP_*.json'
    # must parse multichip rounds identically to the merged default view,
    # and a sharded smoke record committed under a BENCH name must not
    # contaminate the single-chip rows
    if os.path.basename(path).startswith("MULTICHIP_") or _is_sharded_smoke_record(
        record
    ):
        return _multichip_round(path, record)
    m = _ROUND_RE.search(os.path.basename(path))
    kind = (m.group("kind") if m else None) or ""
    # no r<N> in the name: sort AFTER every numbered round (it must never
    # become r01's delta baseline) and label it by its filename stem
    num = int(m.group("num")) if m else None
    # the driver wraps each round's bench record: {"n", "cmd", "rc", "tail",
    # "parsed": <last JSON line or null>}. Unwrap it; a null parse (a run
    # killed before it printed) becomes an explicit error record rather
    # than a skipped round — a dead round is part of the trajectory.
    if "parsed" in record and "rc" in record:
        num, record = _unwrap_driver_record(num, record)
    if num is None:
        label = os.path.basename(path)[: -len(".json")]
        order: tuple = (float("inf"), label)
    else:
        label = f"r{num:02d}" + (f"-{kind}" if kind else "")
        order = (num, kind)
    # schema 1: every round before the loadgen era (no "schema" key). The
    # labeling here is what lets a delta across nine historical rounds parse
    # without guessing which fields can exist.
    schema = int(record.get("schema", 1))
    metrics: dict[str, float] = {}
    for row_label, key in HEADLINE_METRICS:
        value = record.get(key)
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            if key == "value" and not str(
                record.get("metric", "decode_tokens_per_sec")
            ).startswith("decode_tokens_per_sec"):
                # a CPU loadgen smoke's headline is not the TPU decode
                # headline — same row would render a nonsense cross-backend
                # delta; give it its own trajectory row
                row_label = "cpu-smoke tok/s"
            metrics[row_label] = float(value)
    if schema >= 2 and isinstance(record.get("loadgen"), dict):
        metrics.update(_slo_metrics(record["loadgen"]))
    if isinstance(record.get("device_profile"), dict):
        metrics.update(_device_profile_metrics(record["device_profile"]))
    # opportunistic/secondary records sort after the driver record of the
    # same round number
    return Round(
        label=label, path=path, order=order, schema=schema,
        record=record, metrics=metrics,
    )


def _unwrap_driver_record(
    num: int | None, record: dict[str, Any]
) -> tuple[int | None, dict[str, Any]]:
    """Unwrap the driver's ``{"n", "cmd", "rc", "tail", "parsed"}`` wrapper
    (shared by BENCH and MULTICHIP rounds). A null parse (a run killed
    before it printed) becomes an explicit error record rather than a
    skipped round — a dead round is part of the trajectory."""
    num = int(record.get("n") or num or 0)
    parsed = record["parsed"]
    if isinstance(parsed, dict):
        return num, parsed
    return num, {
        "value": 0.0,
        "error": f"record unparseable (driver rc={record.get('rc')})",
    }


def _multichip_round(path: str, record: dict[str, Any]) -> Round:
    """A committed MULTICHIP_*.json round: the multi-chip trajectory rendered
    NEXT TO the BENCH rounds, never against them. Every row name is
    ``mc``-prefixed, so the delta math (which compares a metric against the
    latest previous round carrying the same name) can never compute a
    cross-backend delta between a TPU BENCH headline and a multichip round.

    Two shapes exist: the historical dryrun wrapper (``{"n_devices", "rc",
    "ok", "tail"}`` from the 8-virtual-device compile/execute smoke) renders
    as a pass/fail row; schema-2 records (the sharded-replica loadgen smoke)
    contribute a real throughput row plus their SLO scenario rows."""
    # a sharded smoke record routed here by content may carry a BENCH_rNN
    # name — fall back to the BENCH pattern so it keeps its round number
    # (and its place in the timeline) instead of sorting last unnumbered
    m = _MULTICHIP_RE.search(os.path.basename(path)) or _ROUND_RE.search(
        os.path.basename(path)
    )
    kind = (m.group("kind") if m else None) or ""
    num = int(m.group("num")) if m else None
    if "parsed" in record and "rc" in record:  # driver wrapper, like BENCH
        num, record = _unwrap_driver_record(num, record)
    if num is None:
        label = "mc-" + os.path.basename(path)[: -len(".json")]
        order: tuple = (float("inf"), label)
    else:
        label = f"mc{num:02d}" + (f"-{kind}" if kind else "")
        # "~" sorts after every [a-z_] kind: the multichip column of round N
        # lands right of round N's BENCH columns
        order = (num, "~" + kind)
    metrics: dict[str, float] = {}
    schema = int(record.get("schema", 1))
    if "value" not in record and "n_devices" in record:
        # legacy dryrun wrapper: no throughput was ever measured — the row
        # records that the sharding programs compiled and executed
        metrics["mc dryrun ok"] = 1.0 if record.get("ok") else 0.0
        if not record.get("ok") and not record.get("error"):
            record = {**record, "error": f"dryrun failed (rc={record.get('rc')})"}
    else:
        # the sharded headline: a full single-chip bench record committed as a
        # MULTICHIP round carries it under serve_sharded_tok_s (its "value"
        # is the single-chip decode headline — the wrong trajectory here);
        # the dedicated loadgen --mesh smoke record carries it as "value"
        # and stamps top-level mesh/mesh_devices as the evidence. A bench
        # record whose sharded section failed has neither — no row, never
        # the single-chip headline masquerading as the multichip number.
        value = record.get("serve_sharded_tok_s")
        if (
            value is None
            and (record.get("mesh_devices") or record.get("mesh"))
            and str(record.get("metric", "")).startswith("serve_sharded_tok_s")
        ):
            # only the dedicated sharded smoke's own headline may take this
            # row — a mesh-stamped record measuring something else (e.g. the
            # role-preset disagg round) must not masquerade as the sharded
            # fleet number
            value = record.get("value")
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            metrics["mc sharded tok/s"] = float(value)
        # role-preset disaggregation rounds (run_disagg_mesh_round): their
        # serve_disagg_* keys render as mc-prefixed rows, disjoint from the
        # single-chip disagg rows exactly like every other mc metric
        for row_label, key in (
            ("mc disagg tok/s", "serve_disagg_tok_s"),
            ("mc disagg colo tok/s", "serve_disagg_colo_tok_s"),
            ("mc disagg speedup", "serve_disagg_speedup"),
            ("mc disagg ttft p50 ms", "serve_disagg_ttft_p50_ms"),
            ("mc disagg colo ttft p50 ms", "serve_disagg_colo_ttft_p50_ms"),
            ("mc disagg ttft p95 ms", "serve_disagg_ttft_p95_ms"),
            ("mc disagg colo ttft p95 ms", "serve_disagg_colo_ttft_p95_ms"),
            ("mc disagg migrate bytes", "serve_disagg_migrate_bytes"),
        ):
            mc_value = record.get(key)
            if isinstance(mc_value, (int, float)) and not isinstance(mc_value, bool):
                metrics[row_label] = float(mc_value)
        devices = (
            record.get("serve_mesh_devices")
            or record.get("mesh_devices")
            or record.get("n_devices")
        )
        if isinstance(devices, (int, float)) and not isinstance(devices, bool):
            metrics["mc mesh devices"] = float(devices)
        if schema >= 2 and isinstance(record.get("loadgen"), dict):
            metrics.update(
                {f"mc-{k}": v for k, v in _slo_metrics(record["loadgen"]).items()}
            )
        if isinstance(record.get("device_profile"), dict):
            metrics.update(
                {
                    f"mc-{k}": v
                    for k, v in _device_profile_metrics(
                        record["device_profile"]
                    ).items()
                }
            )
    return Round(
        label=label, path=path, order=order, schema=schema,
        record=record, metrics=metrics,
    )


def round_from_report(report: dict[str, Any], *, label: str = "candidate") -> Round:
    """A synthetic Round from a fresh loadgen SLO report (smoke's
    slo_report.json) — what `prime bench sentinel --report` appends as the
    gate's candidate round before any record is committed. Carries the same
    "loadgen tok/s" + "slo:" rows a committed schema-2 record would, so the
    candidate gates against exactly the history those rows accumulated."""
    metrics = _slo_metrics(report if isinstance(report, dict) else {})
    return Round(
        label=label, path="<report>", order=(float("inf"), label),
        schema=2, record={"loadgen": report}, metrics=metrics,
    )


def load_rounds(root: str = ".", pattern: str = "BENCH_*.json") -> list[Round]:
    """Every parseable committed round under ``root``, oldest first.
    Unparseable files are skipped (a half-written record must not take the
    delta table down); files without a BENCH_r<N> name sort last by name.
    ``MULTICHIP_*``-named files parse as multichip rounds whatever the
    pattern that matched them."""
    rounds: list[Round] = []
    for path in sorted(glob.glob(os.path.join(root, pattern))):
        try:
            with open(path) as f:
                record = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(record, dict):
            rounds.append(_round_from_record(path, record))
    rounds.sort(key=lambda r: (r.order, r.label))
    return rounds


def load_all_rounds(root: str = ".") -> list[Round]:
    """BENCH and MULTICHIP rounds merged into one timeline: multichip rounds
    interleave by round number (sorting after the same-numbered BENCH round)
    but keep disjoint ``mc``-prefixed metric rows — own rows, no
    cross-backend deltas."""
    rounds = load_rounds(root, "BENCH_*.json") + load_rounds(root, "MULTICHIP_*.json")
    rounds.sort(key=lambda r: (r.order, r.label))
    return rounds


def _fmt(value: float) -> str:
    if value == int(value) and abs(value) >= 100:
        return str(int(value))
    return f"{value:.3g}" if abs(value) < 10 else f"{value:.1f}"


def delta_table(rounds: list[Round], *, min_rounds: int = 2) -> str:
    """Render the metric-by-round table with per-round deltas vs the latest
    previous round that measured the same metric (Δ% for rates/ratios)."""
    if len(rounds) < min_rounds:
        return (
            f"need at least {min_rounds} BENCH_*.json rounds for a delta "
            f"table; found {len(rounds)}"
        )
    metric_names: list[str] = []
    for r in rounds:
        for name in r.metrics:
            if name not in metric_names:
                metric_names.append(name)
    if not metric_names:
        return "no numeric metrics found in any round"
    label_w = max(len(n) for n in metric_names + ["sentinel verdict"]) + 2
    headers = [
        r.label + (f" (s{r.schema})" if r.schema == 1 else "") for r in rounds
    ]
    col_w = max(16, max(len(h) for h in headers) + 2)
    lines = ["".join([" " * label_w] + [f"{h:>{col_w}}" for h in headers])]
    for name in metric_names:
        cells = [f"{name:<{label_w}}"]
        prev: float | None = None
        for r in rounds:
            value = r.metrics.get(name)
            if value is None:
                cells.append(f"{'—':>{col_w}}")
                continue
            cell = _fmt(value)
            if prev not in (None, 0.0):
                pct = (value - prev) / prev * 100.0
                cell += f" ({pct:+.0f}%)"
            elif prev == 0.0 and value > 0:
                cell += " (∅→live)"
            cells.append(f"{cell:>{col_w}}")
            prev = value
        lines.append("".join(cells))
    # sentinel verdict row: same implementation as the `prime bench
    # sentinel` CI gate (obs/sentinel.trajectory_verdicts), so the table a
    # human reads and the exit code CI trusts can never disagree
    for verdict in _sentinel_rows(rounds):
        cells = [f"{'sentinel verdict':<{label_w}}"]
        for cell in verdict:
            cells.append(f"{cell:>{col_w}}")
        lines.append("".join(cells))
    notes = [
        f"{r.label}: {r.error}" for r in rounds if r.error
    ]
    if notes:
        lines.append("")
        lines.append("round errors:")
        lines.extend(f"  {n}" for n in notes)
    return "\n".join(lines)


def _sentinel_verdicts(rounds: list[Round]) -> list[dict[str, Any]]:
    """Per-round sentinel verdicts, or [] when the sentinel can't run
    (import trouble must not take the delta table down)."""
    try:
        from prime_tpu.obs.sentinel import trajectory_verdicts
    except Exception:  # noqa: BLE001 — the table renders without the row
        return []
    try:
        return trajectory_verdicts(rounds)
    except Exception:  # noqa: BLE001
        return []


def _sentinel_rows(rounds: list[Round]) -> list[list[str]]:
    """The `sentinel verdict` table row (one cell per round) as a
    single-row list, or [] when verdicts are unavailable."""
    verdicts = _sentinel_verdicts(rounds)
    if not verdicts:
        return []
    cells = []
    for v in verdicts:
        if v["verdict"] == "regressed":
            cells.append(f"REGRESSED({len(v['regressions'])})")
        elif v["verdict"] == "ok":
            cells.append("ok")
        else:
            cells.append("no-history")
    return [cells]


def delta_json(rounds: list[Round]) -> dict[str, Any]:
    """Machine form of the same table (CI step summaries, dashboards)."""
    verdicts = _sentinel_verdicts(rounds)
    by_label: dict[str, dict[str, Any]] = {v["label"]: v for v in verdicts}
    return {
        "rounds": [
            {
                "label": r.label,
                "path": os.path.basename(r.path),
                "schema": r.schema,
                "error": r.error,
                "metrics": r.metrics,
                "sentinel": by_label.get(r.label),
            }
            for r in rounds
        ]
    }
