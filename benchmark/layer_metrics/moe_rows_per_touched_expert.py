"""Expert layer: token-expert pairs a held expert that had any computed, averaged
over the decode steps' expert-layer calls: how full a group of the grouped
product is (a 32-row tile holds it whole until it passes 32). Program counters
over the window."""
from benchmark.layer_metrics._moe import decode_counts


def read(run):
    counts = decode_counts(run)
    if counts is None or counts["touched"] <= 0:
        return None
    return counts["local"] / counts["touched"]
