"""Percentile, TTFT, TPOT and tokens-per-second arithmetic on hand-made records."""
import pytest

from benchmark.harness import records as rec


def record(due, first, gaps, ids_per_event=8, phase="run", status=200, done=True, max_tokens=None):
    events, t = [], first
    for i, gap in enumerate([0.0] + gaps):
        t += gap
        events.append([t, ids_per_event])
    n = ids_per_event * len(events)
    return {"phase": phase, "due": due, "sent": due + 0.001, "end": t, "status": status,
            "done": done, "events": events, "ids": list(range(n)),
            "max_tokens": max_tokens or n, "numeric_prompt": [1, 2, 3]}


def test_percentile_interpolates():
    assert rec.percentile([1, 2, 3, 4, 5], 50) == 3
    assert rec.percentile([10, 20], 90) == pytest.approx(19.0)
    assert rec.percentile([], 50) is None


def test_ttft_is_from_the_due_time_and_tpot_skips_the_first_flush():
    r = record(due=10.0, first=10.25, gaps=[0.2, 0.2, 0.2])
    assert rec.ttft_ms(r) == pytest.approx(250.0)
    assert rec.tpot_ms(r) == pytest.approx(0.6 / 24 * 1e3)
    assert rec.tpot_ms(record(10.0, 10.1, [])) is None


def test_end_to_end_counts_the_window_only():
    w0, w1 = 100.0, 110.0
    inside = [record(100.0 + i, 100.1 + i * 1.0 + 0.01 * i, [0.2, 0.2]) for i in range(10)]
    before = record(95.0, 99.9, [0.2, 0.2])          # ramp: its late flushes count as tokens
    after = record(110.5, 110.6, [0.2])              # due after the close
    broken = record(105.0, 105.1, [0.2], status=500, done=False)
    primer = record(101.0, 101.1, [0.2], phase="primer")
    out = rec.end_to_end(inside + [before, after, broken, primer], w0, w1)
    assert out["attempted"] == 11 and out["failed"] == 1
    ttfts = sorted(rec.ttft_ms(r) for r in inside)
    assert out["ttft_p50_ms"] == pytest.approx(rec.percentile(ttfts, 50))
    assert out["ttft_p90_ms"] == pytest.approx(rec.percentile(ttfts, 90))
    assert out["tpot_p50_ms"] == pytest.approx(0.4 / 16 * 1e3)
    assert out["latency_mean_ms"] == pytest.approx(sum(rec.latency_ms(r) for r in inside) / 10)
    assert rec.latency_ms(inside[3]) == pytest.approx((inside[3]["events"][-1][0] - 103.0) * 1e3)
    tokens = sum(n for r in inside + [before, after, broken, primer]
                 for t, n in r["events"] if w0 <= t < w1)
    assert out["output_tok_s"] == pytest.approx(tokens / 10.0)
    assert tokens == 8 * (30 + 2 + 2 + 2)  # 'before' has two flushes inside, 'after' none


def test_a_stream_longer_than_asked_or_garbled_is_not_complete():
    r = record(0.0, 0.1, [0.1], max_tokens=8)
    assert not rec.complete(r)
    ok = record(0.0, 0.1, [0.1])
    assert rec.complete(ok)
    ok["garbled"] = True
    assert not rec.complete(ok)
