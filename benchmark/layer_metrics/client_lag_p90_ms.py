"""Load generator: how late it sent, send time - due time, 90th percentile.
A starved generator must not read as a fast server."""
from benchmark.harness.records import in_window, percentile


def read(run):
    lag = [(r["sent"] - r["due"]) * 1e3 for r in in_window(run["records"], *run["window"])
           if r["sent"] is not None]
    return percentile(lag, 90)
