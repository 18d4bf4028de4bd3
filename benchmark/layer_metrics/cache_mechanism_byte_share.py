"""Cache: how hard the cell works what this configuration adds to a slot.
Bytes a decode step moves for the window rings, the recurrent state and the
cross layers' reads of the shared row (rooflines/<config.roofline>
``mechanism_bytes``, from the live rows of the traced span) over the step's
least bytes with them in: weights + the row once + the mechanism. From the
program's flight recorder; a configuration whose roofline module prices no
mechanism, or a run without timelines, gives nothing."""
import importlib

from benchmark.layer_metrics._flight import all_times, overlap


def read(run):
    counts = importlib.import_module(f"benchmark.rooflines.{run['config']['roofline']}")
    if not hasattr(counts, "mechanism_bytes") or not run["flights"] or "trace_span" not in run:
        return None
    if not run["counters"][1].get("serve_cache_bytes"):
        return None  # the program holds no such cache (the parent of the PR that brought it)
    t0, t1 = run["trace_span"]
    window = int(run["model"]["sliding_window"])
    rows = ring_columns = tokens = 0.0
    for t in all_times(run):
        start = t["first_token"] if t["first_token"] is not None else t["admitted"]
        share = overlap(start, t["retire"], t0, t1) / (t1 - t0)
        held = t["prompt_tokens"] + t["tokens"] / 2.0
        rows += share
        ring_columns += share * min(held, window)
        tokens += share * held
    if rows <= 0:
        return None
    mechanism = counts.mechanism_bytes(run["model"], rows, ring_columns, tokens)
    return 100.0 * mechanism / (counts.decode_step_bytes(run["model"], tokens) + mechanism)
