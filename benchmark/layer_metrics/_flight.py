"""Shared by the readers that use the program's flight recorder: one request's
timeline on the host's monotonic clock."""

from __future__ import annotations


def times(timeline: dict, clock_offset: float) -> dict | None:
    """submit / admitted / first_token / retire (monotonic seconds), tokens."""
    submit = timeline["start_unix_s"] - clock_offset
    at = {e["event"]: e for e in timeline.get("events", []) if isinstance(e, dict)}
    if "admitted" not in at or timeline.get("duration_s") is None:
        return None
    first = at.get("first_token")
    return {
        "submit": submit,
        "admitted": submit + at["admitted"]["t_s"],
        "first_token": submit + first["t_s"] if first else None,
        "retire": submit + timeline["duration_s"],
        "prompt_tokens": int(timeline.get("prompt_tokens", 0)),
        "tokens": int(at.get("completed", {}).get("tokens", 0)),
    }


def all_times(run: dict) -> list[dict]:
    out = [times(t, run["clock_offset"]) for t in run["flights"].values()]
    return [t for t in out if t is not None]


def window_pairs(run: dict) -> list[tuple[dict, dict]]:
    """(client record, timeline times) of the requests due in the window."""
    w0, w1 = run["window"]
    pairs = []
    for record in run["records"]:
        if record["phase"] != "run" or not w0 <= record["due"] < w1:
            continue
        timeline = run["flights"].get(record.get("trace_id"))
        t = times(timeline, run["clock_offset"]) if timeline else None
        if t is not None:
            pairs.append((record, t))
    return pairs


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def traced_prefill(run: dict):
    """Prompt lengths of the requests whose first token fell in the traced span,
    and the tokens of them that the prefix cache served."""
    t0, t1 = run["trace_span"]
    lengths = [t["prompt_tokens"] for t in all_times(run)
               if t["first_token"] is not None and t0 <= t["first_token"] < t1]
    cached = run["counter_delta"](run["trace_counters"], "serve_prefix_hit_tokens")
    return lengths, int(cached)


def traced_live_tokens(run: dict) -> float:
    """Mean number of cached tokens the decode step had to read, over the traced
    span: each request in a slot counts its prompt plus half of what it emitted."""
    t0, t1 = run["trace_span"]
    total = 0.0
    for t in all_times(run):
        start = t["first_token"] if t["first_token"] is not None else t["admitted"]
        total += overlap(start, t["retire"], t0, t1) * (t["prompt_tokens"] + t["tokens"] / 2.0)
    return total / (t1 - t0)
