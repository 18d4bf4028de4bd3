"""Engine (serve/engine.py): flight recorder, submit -> admitted to a slot,
90th percentile over the requests due in the window."""
from benchmark.harness.records import percentile
from benchmark.layer_metrics._flight import window_pairs


def read(run):
    return percentile([(t["admitted"] - t["submit"]) * 1e3 for _, t in window_pairs(run)], 90)
