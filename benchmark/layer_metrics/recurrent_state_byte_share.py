"""Cache: how hard the cell works the matrix state a slot carries. Bytes a decode
step moves for the state and conv tail of the live rows of the traced span, read
and written (rooflines/<config.roofline> ``mechanism_bytes``), over the step's
least bytes with them in: weights outside the experts + head + the live K/V +
the state. From the program's flight recorder; a configuration whose roofline
module prices no per-row state, a program that holds no state leaves, or a run
without timelines gives nothing."""
import importlib

from benchmark.layer_metrics._flight import all_times, overlap


def live_rows_and_tokens(run):
    """Mean rows in a slot and mean cached tokens over the traced span."""
    t0, t1 = run["trace_span"]
    rows = tokens = 0.0
    for t in all_times(run):
        start = t["first_token"] if t["first_token"] is not None else t["admitted"]
        share = overlap(start, t["retire"], t0, t1) / (t1 - t0)
        rows += share
        tokens += share * (t["prompt_tokens"] + t["tokens"] / 2.0)
    return rows, tokens


def read(run):
    counts = importlib.import_module(f"benchmark.rooflines.{run['config']['roofline']}")
    if not hasattr(counts, "state_bytes_per_row") or not run["flights"] or "trace_span" not in run:
        return None
    held = (run["counters"][1].get("serve_cache_bytes") or {}).get("series", [])
    if not any(s.get("labels", {}).get("kind") == "state" and s.get("value", 0) > 0 for s in held):
        return None  # the program's slots hold no state
    rows, tokens = live_rows_and_tokens(run)
    if rows <= 0:
        return None
    mechanism = counts.mechanism_bytes(run["model"], rows)
    return 100.0 * mechanism / (counts.decode_step_bytes(run["model"], tokens) + mechanism)
