"""Engine, admission's chunk plan: positions of the window's chunk-prefill programs
that held no prompt token (the fill of a prompt's last chunk, or of a shorter
member of a wave), as a share of all their positions: what a longer chunk cap
costs. Program counters over the window."""
from benchmark.layer_metrics._prefill_chunks import chunk_counts


def read(run):
    counts = chunk_counts(run)
    if counts is None:
        return None
    return 100.0 * counts["pad"] / (counts["prompt"] + counts["pad"])
