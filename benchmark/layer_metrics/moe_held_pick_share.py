"""Expert layer: the decode steps' token-expert pairs that fell on an expert held
here, as a share of those that fell on a routed expert anywhere. A chip that
holds half the experts reads 50.0 under even routing; a reading off it says a
mask or the held range is wrong. Program counters over the window."""
from benchmark.layer_metrics._moe import decode_counts


def read(run):
    counts = decode_counts(run)
    if counts is None or counts["local"] + counts["elsewhere"] <= 0:
        return None
    return 100.0 * counts["local"] / (counts["local"] + counts["elsewhere"])
