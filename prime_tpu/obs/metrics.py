"""Thread-safe metrics registry with Prometheus text-format exposition.

The shape follows prometheus_client's data model (families → labeled series)
without the dependency: a ``Registry`` owns metric families; every family
created by one registry shares that registry's single lock, so
``snapshot()`` / ``render_prometheus()`` observe a mutually consistent view
across ALL series — the cross-field inconsistency the bare engine counters
had (ADVICE r5, serve/engine.py) cannot recur through this layer.

Histograms are fixed-bucket (cumulative ``le`` semantics, like Prometheus):
``observe`` is O(#buckets) with no allocation, and quantiles are estimated
host-side by linear interpolation within the bucket that crosses the rank —
good enough for TTFT/TPOT p50/p95 dashboards, exact at bucket boundaries.
"""

from __future__ import annotations

import math
import re
import threading
import time
from typing import Any, Iterable, Mapping

# Reserved snapshot key: Registry.snapshot() embeds the monotonic capture
# instant under this name (family-shaped, so snapshot consumers that iterate
# families keep working). Registering a real metric with this name is
# refused — the two would collide in every snapshot.
SNAPSHOT_CAPTURED_AT = "captured_at"

# Prometheus-style latency buckets, widened past 10s because a first-compile
# TTFT on a cold engine is legitimately minutes, not milliseconds.
DEFAULT_LATENCY_BUCKETS: tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
)
# power-of-two size buckets (admission batch sizes, token counts, ...)
DEFAULT_SIZE_BUCKETS: tuple[float, ...] = (1, 2, 4, 8, 16, 32, 64, 128, 256)
# token-length buckets for prompt/prefix histograms: MIN_BUCKET-aligned
# block counts up to long-context slot capacities (serve_prefix_hit_tokens)
DEFAULT_TOKEN_BUCKETS: tuple[float, ...] = (
    16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192,
)

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


def _escape_help(text: str) -> str:
    return text.replace("\\", r"\\").replace("\n", r"\n")


def _escape_label_value(value: str) -> str:
    return value.replace("\\", r"\\").replace('"', r"\"").replace("\n", r"\n")


def _format_value(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    if isinstance(v, float) and math.isnan(v):
        # text-format spec spelling — repr() would emit "nan", which the
        # reference Prometheus parser rejects
        return "NaN"
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    return repr(v)


def _label_str(names: tuple[str, ...], values: tuple[str, ...], extra: str = "") -> str:
    parts = [f'{n}="{_escape_label_value(v)}"' for n, v in zip(names, values)]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class _Metric:
    """One metric family: a name, help text, label names, and a series per
    distinct label-value tuple. Lock is the owning registry's."""

    kind = "untyped"

    def __init__(self, name: str, help: str, labelnames: tuple[str, ...], lock: threading.RLock) -> None:
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        for label in labelnames:
            if not _LABEL_RE.match(label):
                raise ValueError(f"invalid label name {label!r}")
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._lock = lock
        self._series: dict[tuple[str, ...], Any] = {}

    def _key(self, labels: Mapping[str, Any]) -> tuple[str, ...]:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"{self.name} expects labels {self.labelnames}, got {tuple(labels)}"
            )
        return tuple(str(labels[n]) for n in self.labelnames)

    def _new_series(self) -> Any:
        raise NotImplementedError

    def _get(self, labels: Mapping[str, Any]) -> Any:
        key = self._key(labels)
        series = self._series.get(key)
        if series is None:
            series = self._series[key] = self._new_series()
        return series


class Counter(_Metric):
    """Monotonically increasing counter."""

    kind = "counter"

    def _new_series(self) -> list[float]:
        return [0.0]

    def inc(self, amount: float = 1.0, **labels: Any) -> None:
        if amount < 0:
            raise ValueError("counters only go up")
        with self._lock:
            self._get(labels)[0] += amount

    def value(self, **labels: Any) -> float:
        with self._lock:
            series = self._series.get(self._key(labels))
            return series[0] if series else 0.0

    def total(self) -> float:
        """Sum over every series of the family."""
        with self._lock:
            return sum(series[0] for series in self._series.values())


class Gauge(_Metric):
    """Value that can go up and down."""

    kind = "gauge"

    def _new_series(self) -> list[float]:
        return [0.0]

    def set(self, value: float, **labels: Any) -> None:
        with self._lock:
            self._get(labels)[0] = float(value)

    def inc(self, amount: float = 1.0, **labels: Any) -> None:
        with self._lock:
            self._get(labels)[0] += amount

    def dec(self, amount: float = 1.0, **labels: Any) -> None:
        self.inc(-amount, **labels)

    def value(self, **labels: Any) -> float:
        with self._lock:
            series = self._series.get(self._key(labels))
            return series[0] if series else 0.0


class _HistSeries:
    __slots__ = ("counts", "sum", "count")

    def __init__(self, n_buckets: int) -> None:
        self.counts = [0] * (n_buckets + 1)  # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0


class Histogram(_Metric):
    """Fixed-bucket histogram with Prometheus cumulative ``le`` semantics."""

    kind = "histogram"

    def __init__(
        self,
        name: str,
        help: str,
        labelnames: tuple[str, ...],
        lock: threading.RLock,
        buckets: Iterable[float] = DEFAULT_LATENCY_BUCKETS,
    ) -> None:
        super().__init__(name, help, labelnames, lock)
        self.buckets = tuple(float(b) for b in buckets)
        if not self.buckets or any(
            a >= b for a, b in zip(self.buckets, self.buckets[1:])
        ):
            raise ValueError("buckets must be non-empty and strictly increasing")

    def _new_series(self) -> _HistSeries:
        return _HistSeries(len(self.buckets))

    def observe(self, value: float, **labels: Any) -> None:
        with self._lock:
            series = self._get(labels)
            # first bucket whose upper bound holds the value (le: v <= bound);
            # past the last bound it lands in +Inf only
            for i, bound in enumerate(self.buckets):
                if value <= bound:
                    series.counts[i] += 1
                    break
            else:
                series.counts[-1] += 1
            series.sum += value
            series.count += 1

    def quantile(self, q: float, **labels: Any) -> float:
        """Estimated q-quantile (0 <= q <= 1) by linear interpolation inside
        the bucket that crosses rank q*count. Values beyond the last finite
        bound clamp to it (the +Inf bucket has no width to interpolate)."""
        if not 0.0 <= q <= 1.0:
            raise ValueError("q must be in [0, 1]")
        with self._lock:
            series = self._series.get(self._key(labels))
            if series is None or series.count == 0:
                return float("nan")
            return quantile_from_snapshot(list(self.buckets), series.counts, q)

    def mean(self, default: float = 0.0, **labels: Any) -> float:
        """Mean of every observed value (sum/count), or ``default`` when the
        series is empty/absent — the engine and the fleet router both derive
        Retry-After estimates from queue-wait means, so the arithmetic lives
        here once instead of on both callers."""
        with self._lock:
            series = self._series.get(self._key(labels))
            if series is None or series.count == 0:
                return default
            return series.sum / series.count

    def total(self) -> float:
        """Sum of every observed value, over every series of the family."""
        with self._lock:
            return sum(series.sum for series in self._series.values())

    def series_snapshot(self, **labels: Any) -> dict | None:
        with self._lock:
            series = self._series.get(self._key(labels))
            if series is None:
                return None
            return {
                "buckets": list(self.buckets),
                "counts": list(series.counts),
                "sum": series.sum,
                "count": series.count,
            }


class Registry:
    """A set of metric families sharing ONE lock: any read path
    (``snapshot``, ``render_prometheus``, bulk ``values``) sees every series
    at a single consistent point, and every write is a short critical
    section (CPython-cheap; nothing here runs on a jit hot path — metrics
    record around device dispatches, not inside them)."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._metrics: dict[str, _Metric] = {}

    def _register(self, cls, name: str, help: str, labelnames, **kw) -> Any:
        if name == SNAPSHOT_CAPTURED_AT:
            raise ValueError(
                f"{name!r} is reserved for the snapshot capture timestamp"
            )
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if not isinstance(existing, cls) or existing.labelnames != tuple(labelnames):
                    raise ValueError(
                        f"metric {name!r} already registered as {existing.kind} "
                        f"with labels {existing.labelnames}"
                    )
                return existing
            metric = cls(name, help, tuple(labelnames), self._lock, **kw)
            self._metrics[name] = metric
            return metric

    def counter(self, name: str, help: str = "", labelnames: Iterable[str] = ()) -> Counter:
        return self._register(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str = "", labelnames: Iterable[str] = ()) -> Gauge:
        return self._register(Gauge, name, help, labelnames)

    def histogram(
        self,
        name: str,
        help: str = "",
        labelnames: Iterable[str] = (),
        buckets: Iterable[float] = DEFAULT_LATENCY_BUCKETS,
    ) -> Histogram:
        return self._register(Histogram, name, help, labelnames, buckets=buckets)

    def get(self, name: str) -> _Metric | None:
        with self._lock:
            return self._metrics.get(name)

    def hold(self) -> threading.RLock:
        """The one lock, for ``with registry.hold():`` around several writes
        that every reader must see together or not at all."""
        return self._lock

    def values(self) -> dict[str, float]:
        """Unlabeled counter/gauge values in one consistent read — the
        engine's ``stats()`` composes its legacy JSON from this."""
        with self._lock:
            out = {}
            for name, metric in self._metrics.items():
                if isinstance(metric, (Counter, Gauge)) and not metric.labelnames:
                    series = metric._series.get(())
                    out[name] = series[0] if series else 0.0
            return out

    def snapshot(self) -> dict[str, dict]:
        """JSON-able dump of every family and series, taken under the one
        lock (mutually consistent across metrics). The reserved
        ``captured_at`` entry stamps the capture instant on this process's
        MONOTONIC clock (family-shaped so family-iterating consumers need no
        special case): two snapshots of the same registry subtract to a
        well-defined wall-seconds window, which is what the loadgen SLO
        report divides token deltas by — a throughput whose numerator and
        denominator come from the same process, immune to client clock skew
        (docs/benchmarking.md)."""
        with self._lock:
            out: dict[str, dict] = {
                SNAPSHOT_CAPTURED_AT: {
                    "type": "gauge",
                    "help": "Monotonic capture instant of this snapshot (seconds)",
                    "series": [{"labels": {}, "value": time.monotonic()}],
                }
            }
            for name, metric in self._metrics.items():
                series_list = []
                for key, series in metric._series.items():
                    labels = dict(zip(metric.labelnames, key))
                    if isinstance(metric, Histogram):
                        series_list.append(
                            {
                                "labels": labels,
                                "buckets": list(metric.buckets),
                                "counts": list(series.counts),
                                "sum": series.sum,
                                "count": series.count,
                            }
                        )
                    else:
                        series_list.append({"labels": labels, "value": series[0]})
                out[name] = {
                    "type": metric.kind,
                    "help": metric.help,
                    "series": series_list,
                }
            return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition format 0.0.4 of every family. Always
        well-formed: a label-less family that exists but was never observed
        still emits its (zero) series — a registered histogram must expose
        zero-count buckets, not a bare HELP/TYPE header some scrapers choke
        on — and non-finite values render in spec spelling (+Inf/-Inf/NaN).
        Labeled families with no series have nothing emittable (the label
        values are unknown) and legally render headers only."""
        with self._lock:
            lines: list[str] = []
            for name, metric in self._metrics.items():
                if metric.help:
                    lines.append(f"# HELP {name} {_escape_help(metric.help)}")
                lines.append(f"# TYPE {name} {metric.kind}")
                series_items = list(metric._series.items())
                if not series_items and not metric.labelnames:
                    series_items = [((), metric._new_series())]
                for key, series in series_items:
                    if isinstance(metric, Histogram):
                        cumulative = 0
                        for bound, count in zip(
                            metric.buckets + (math.inf,), series.counts
                        ):
                            cumulative += count
                            le = f'le="{_format_value(float(bound))}"'
                            lines.append(
                                f"{name}_bucket"
                                f"{_label_str(metric.labelnames, key, le)} {cumulative}"
                            )
                        labels = _label_str(metric.labelnames, key)
                        lines.append(f"{name}_sum{labels} {_format_value(series.sum)}")
                        lines.append(f"{name}_count{labels} {series.count}")
                    else:
                        labels = _label_str(metric.labelnames, key)
                        lines.append(f"{name}{labels} {_format_value(series[0])}")
            return "\n".join(lines) + "\n" if lines else ""


def quantile_from_snapshot(buckets: list[float], counts: list[int], q: float) -> float:
    """Histogram quantile estimate from snapshot data (same interpolation as
    :meth:`Histogram.quantile`) — for consumers holding a serialized
    snapshot, e.g. `prime serve metrics` rendering a scraped registry."""
    total = sum(counts)
    if total == 0:
        return float("nan")
    rank = q * total
    cumulative = 0
    lower = 0.0
    for i, bound in enumerate(buckets):
        in_bucket = counts[i]
        if cumulative + in_bucket >= rank and in_bucket > 0:
            frac = (rank - cumulative) / in_bucket
            return lower + (bound - lower) * min(max(frac, 0.0), 1.0)
        cumulative += in_bucket
        lower = bound
    return buckets[-1]


# ---- snapshot arithmetic ----------------------------------------------------
# The one shared implementation of delta/merge math over serialized
# Registry.snapshot() data. The loadgen SLO report (loadgen/report.py) and
# the observatory time-series (obs/timeseries.py) both window counters and
# histograms through these — two diverging copies of the bucket arithmetic
# is exactly the drift the obs contract exists to prevent.


def snapshot_captured_at(snapshot: Mapping[str, Any]) -> float | None:
    """The monotonic capture instant :meth:`Registry.snapshot` embeds under
    the reserved ``captured_at`` family, or None on pre-schema snapshots."""
    family = snapshot.get(SNAPSHOT_CAPTURED_AT)
    if not isinstance(family, Mapping):
        return None
    series = family.get("series") or []
    try:
        return float(series[0]["value"]) if series else None
    except (TypeError, KeyError, ValueError, IndexError):
        return None


def scalar_from_snapshot(
    snapshot: Mapping[str, Any], name: str, labels: Mapping[str, str] | None = None
) -> float:
    """One counter/gauge series value out of a snapshot (0.0 when the family
    or series is absent — the same "never existed = never incremented"
    default the report has always used)."""
    family = snapshot.get(name)
    if not isinstance(family, Mapping):
        return 0.0
    want = dict(labels or {})
    for series in family.get("series", []):
        if series.get("labels", {}) == want:
            try:
                return float(series.get("value", 0.0))
            except (TypeError, ValueError):
                return 0.0
    return 0.0


def hist_series_from_snapshot(
    snapshot: Mapping[str, Any], name: str, labels: Mapping[str, str] | None = None
) -> dict | None:
    """One histogram series (buckets/counts/sum/count) out of a snapshot."""
    family = snapshot.get(name)
    if not isinstance(family, Mapping):
        return None
    want = dict(labels or {})
    for series in family.get("series", []):
        if series.get("labels", {}) == want and "counts" in series:
            return series
    return None


def counter_delta(before: float, after: float) -> tuple[float, bool]:
    """``after − before`` for a monotonic counter, reset-aware: a replica
    restart makes the raw subtraction negative, and a negative "rate" is a
    lie no dashboard should ever render. On a reset the best unbiased
    estimate of the window's traffic is the post-reset value itself (the
    count since the restart — everything before it is unknowable).
    Returns ``(delta, reset_detected)``."""
    if after < before:
        return after, True
    return after - before, False


def hist_delta(before: dict | None, after: dict | None) -> dict | None:
    """``after − before`` for one histogram series (same bucket layout),
    reset-aware like :func:`counter_delta`: a shrunk total count means the
    process restarted, and the post-reset series IS the window's delta. A
    missing ``before`` (new series mid-window) degrades the same way."""
    if after is None:
        return None
    if before is None or after["count"] < before["count"] or any(
        a < b for a, b in zip(after["counts"], before["counts"])
    ):
        return {
            "buckets": list(after["buckets"]),
            "counts": list(after["counts"]),
            "sum": after["sum"],
            "count": after["count"],
        }
    return {
        "buckets": list(after["buckets"]),
        "counts": [a - b for a, b in zip(after["counts"], before["counts"])],
        "sum": after["sum"] - before["sum"],
        "count": after["count"] - before["count"],
    }


def merge_hists(deltas: Iterable[dict | None]) -> dict | None:
    """Pointwise sum of same-layout histogram series across components
    (engines of a fleet, replicas of a ring) — mismatched bucket layouts are
    skipped rather than summed into nonsense."""
    merged: dict | None = None
    for delta in deltas:
        if delta is None:
            continue
        if merged is None:
            merged = {
                "buckets": list(delta["buckets"]),
                "counts": list(delta["counts"]),
                "sum": delta["sum"],
                "count": delta["count"],
            }
        elif merged["buckets"] == delta["buckets"]:
            merged["counts"] = [
                a + b for a, b in zip(merged["counts"], delta["counts"])
            ]
            merged["sum"] += delta["sum"]
            merged["count"] += delta["count"]
    return merged


# quoted label values may legally contain '}' and ','; only '"', '\' and
# newline are escaped — so the labels block and the pair splitter must be
# quote-aware, not delimiter-naive
_QUOTED = r'"(?:[^"\\]|\\.)*"'
_LABEL_PAIR = rf"[a-zA-Z_][a-zA-Z0-9_]*={_QUOTED}"
_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    rf"(?P<labels>\{{(?:[^\"}}]|{_QUOTED})*\}})?"
    r" (?P<value>\S+)(?: (?P<ts>-?[0-9]+))?$"
)
# the exposition format permits a trailing comma before '}'
_LABELS_RE = re.compile(rf"^{_LABEL_PAIR}(?:,{_LABEL_PAIR})*,?$")
_LABEL_FIND_RE = re.compile(rf"([a-zA-Z_][a-zA-Z0-9_]*)=({_QUOTED})")


def lint_prometheus_text(
    text: str, catalog: Mapping[str, str] | None = None
) -> list[str]:
    """Pure-python lint of Prometheus text exposition format 0.0.4. Returns
    a list of problems (empty = well-formed). Checked: sample-line syntax
    and label syntax, values parse (incl. +Inf/-Inf/NaN spellings — 'nan'
    is a violation), no duplicate series, TYPE declared at most once per
    family, and histogram invariants per series (cumulative non-decreasing
    buckets, a +Inf bucket, _count equal to the +Inf bucket). The tests and
    the CI serve-smoke job run every /metrics endpoint through this.

    ``catalog`` (family name -> declared type, e.g. from
    ``prime_tpu.analysis.obs_contract.load_metrics_catalog`` over the
    docs/observability.md tables) additionally pins the exposition to the
    documented contract: a family whose TYPE line disagrees with the catalog,
    a family the catalog has never heard of, or a cataloged family exposed
    without a HELP line are all problems — so the live exposition and the
    operator docs cannot drift independently of each other."""
    problems: list[str] = []
    typed: dict[str, str] = {}
    helped: set[str] = set()
    seen_samples: set[str] = set()
    # histogram accounting: series key -> list of (le, cumulative count)
    buckets: dict[str, list[tuple[float, float]]] = {}
    counts: dict[str, float] = {}
    for lineno, line in enumerate(text.splitlines(), start=1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 2 and parts[1] == "TYPE":
                if len(parts) < 4 or parts[3] not in (
                    "counter", "gauge", "histogram", "summary", "untyped"
                ):
                    problems.append(f"line {lineno}: malformed TYPE comment: {line!r}")
                elif parts[2] in typed:
                    problems.append(f"line {lineno}: duplicate TYPE for {parts[2]}")
                else:
                    typed[parts[2]] = parts[3]
            elif len(parts) >= 2 and parts[1] == "HELP":
                if len(parts) >= 3:
                    helped.add(parts[2])
            elif len(parts) >= 2:
                problems.append(f"line {lineno}: unknown comment keyword: {line!r}")
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            problems.append(f"line {lineno}: unparseable sample: {line!r}")
            continue
        name, labels, value = m.group("name"), m.group("labels"), m.group("value")
        if labels:
            inner = labels[1:-1]
            if inner and not _LABELS_RE.match(inner):
                problems.append(f"line {lineno}: malformed labels {inner!r}")
        sample_key = f"{name}{labels or ''}"
        if sample_key in seen_samples:
            problems.append(f"line {lineno}: duplicate series {sample_key}")
        seen_samples.add(sample_key)
        if value in ("+Inf", "-Inf", "NaN"):
            parsed = {"+Inf": math.inf, "-Inf": -math.inf, "NaN": math.nan}[value]
        else:
            try:
                parsed = float(value)
            except ValueError:
                problems.append(f"line {lineno}: unparseable value {value!r}")
                continue
            if value.lower() in ("nan", "inf", "-inf", "+inf") and value not in (
                "+Inf", "-Inf", "NaN"
            ):
                problems.append(
                    f"line {lineno}: non-finite value {value!r} not in spec "
                    "spelling (+Inf/-Inf/NaN)"
                )
        # histogram bookkeeping: strip the le label to key the series
        for suffix, store in (("_bucket", buckets), ("_count", counts)):
            if not name.endswith(suffix):
                continue
            base = name[: -len(suffix)]
            if typed.get(base) != "histogram":
                continue
            pairs = _LABEL_FIND_RE.findall(labels or "")
            le = next((v[1:-1] for k, v in pairs if k == "le"), None)
            rest = ",".join(sorted(f"{k}={v}" for k, v in pairs if k != "le"))
            series_key = f"{base}{{{rest}}}"
            if store is buckets:
                if le is None:
                    problems.append(f"line {lineno}: histogram bucket without le label")
                else:
                    bound = math.inf if le == "+Inf" else float(le)
                    buckets.setdefault(series_key, []).append((bound, parsed))
            else:
                counts[series_key] = parsed
    for series_key, entries in buckets.items():
        bounds = [b for b, _ in entries]
        if bounds != sorted(bounds):
            problems.append(f"{series_key}: bucket bounds not increasing")
        cumulative = [c for _, c in entries]
        if any(a > b for a, b in zip(cumulative, cumulative[1:])):
            problems.append(f"{series_key}: bucket counts not cumulative")
        if not entries or entries[-1][0] != math.inf:
            problems.append(f"{series_key}: missing +Inf bucket")
        elif series_key in counts and counts[series_key] != entries[-1][1]:
            problems.append(
                f"{series_key}: _count {counts[series_key]} != +Inf bucket "
                f"{entries[-1][1]}"
            )
    if catalog is not None:
        for family, kind in typed.items():
            expected = catalog.get(family)
            if expected is None:
                problems.append(
                    f"{family}: exposed but absent from the metrics catalog "
                    "(docs/observability.md)"
                )
                continue
            if expected in ("counter", "gauge", "histogram") and expected != kind:
                problems.append(
                    f"{family}: TYPE {kind} but the catalog documents {expected}"
                )
            if family not in helped:
                problems.append(
                    f"{family}: cataloged family exposed without a HELP line"
                )
    return problems


# Process-wide default registry: core.client's HTTP metrics and anything else
# without a natural owner records here. Servers and engines own their OWN
# registries (per-instance isolation keeps tests and multi-engine processes
# from cross-contaminating) and expose them through `GET /metrics`.
REGISTRY = Registry()
