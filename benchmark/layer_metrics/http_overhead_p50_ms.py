"""Server (serve/server.py): what HTTP, rendering, tokenizing and SSE add before
the first token: the client's send -> first content event, less the flight
recorder's submit -> first token, per request; median."""
from benchmark.harness.records import percentile
from benchmark.layer_metrics._flight import window_pairs


def read(run):
    extra = [((r["events"][0][0] - r["sent"]) - (t["first_token"] - t["submit"])) * 1e3
             for r, t in window_pairs(run) if r["events"] and t["first_token"] is not None]
    return percentile(extra, 50)
