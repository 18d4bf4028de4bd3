"""From the client's records to the end-to-end numbers. Pure arithmetic.

A record is one turn as the client saw it (client.py). All latencies are taken
from the due time, over the turns due inside the window; tokens per second are
all ids any stream delivered inside the window, over its length.
"""

from __future__ import annotations

import json
from pathlib import Path


def load(path) -> list[dict]:
    return [json.loads(line) for line in Path(path).read_text().splitlines() if line.strip()]


def percentile(values, q: float) -> float | None:
    """Linear interpolation between order statistics (numpy's default)."""
    values = sorted(values)
    if not values:
        return None
    pos = (len(values) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(values) - 1)
    return values[lo] + (values[hi] - values[lo]) * (pos - lo)


def complete(record: dict) -> bool:
    return bool(record["status"] == 200 and record["done"] and record["ids"]
                and not record.get("garbled") and not record.get("error")
                and len(record["ids"]) <= record["max_tokens"])


def in_window(records: list[dict], w0: float, w1: float) -> list[dict]:
    return [r for r in records if r["phase"] == "run" and w0 <= r["due"] < w1]


def ttft_ms(record: dict) -> float:
    return (record["events"][0][0] - record["due"]) * 1e3


def tpot_ms(record: dict) -> float | None:
    """Mean gap between tokens after the first flush; None where the whole
    answer came in one flush."""
    events = record["events"]
    later = sum(n for _, n in events[1:])
    if later <= 0:
        return None
    return (events[-1][0] - events[0][0]) / later * 1e3


def latency_ms(record: dict) -> float:
    """Due time to the last content event: what a user waits for a whole answer."""
    return (record["events"][-1][0] - record["due"]) * 1e3


def tokens_in_window(records: list[dict], w0: float, w1: float) -> int:
    return sum(n for r in records for t, n in r["events"] if w0 <= t < w1)


def longest_silence(records: list[dict], w0: float, w1: float) -> dict:
    """The longest stretch of the window in which no stream delivered anything:
    a stall of the whole server shows here, whatever its cause."""
    times = sorted(t for r in records for t, _ in r["events"] if w0 <= t < w1)
    edges = [w0] + times + [w1]
    gap, at = max(((b - a, a) for a, b in zip(edges, edges[1:])), default=(0.0, w0))
    return {"seconds": gap, "at_s": at - w0}


def end_to_end(records: list[dict], w0: float, w1: float) -> dict:
    due = in_window(records, w0, w1)
    good = [r for r in due if complete(r)]
    ttfts = [ttft_ms(r) for r in good]
    tpots = [v for v in (tpot_ms(r) for r in good) if v is not None]
    return {
        "attempted": len(due),
        "failed": len(due) - len(good),
        "ttft_p50_ms": percentile(ttfts, 50),
        "ttft_p90_ms": percentile(ttfts, 90),
        "tpot_p50_ms": percentile(tpots, 50),
        "tpot_p90_ms": percentile(tpots, 90),
        "latency_mean_ms": sum(latency_ms(r) for r in good) / len(good) if good else None,
        "output_tok_s": tokens_in_window(records, w0, w1) / (w1 - w0),
    }
