"""Model step: device time of the chunk-prefill program's events in the trace,
per thousand prompt tokens actually prefilled in the traced span (prompt tokens
of the requests whose first token fell in it, less those the cache served)."""
from benchmark.layer_metrics._flight import traced_prefill


def read(run):
    prefill = (run["trace"] or {}).get("programs", {}).get("chunk_prefill")
    if not prefill or not prefill["events"]:
        return None
    lengths, cached = traced_prefill(run)
    tokens = sum(lengths) - cached
    if tokens <= 0:
        return None
    return prefill["seconds"] * 1e3 / (tokens / 1000.0)
