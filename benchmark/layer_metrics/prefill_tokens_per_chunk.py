"""Engine, admission's chunk plan: prompt tokens a chunk-prefill program carried,
averaged over the window's chunk programs: what each read of the weights (of every
held expert, on an expert model) served. The plan's cap bounds it
(``serve/engine.py prefill_shapes``). Program counters over the window."""
from benchmark.layer_metrics._prefill_chunks import chunk_counts


def read(run):
    counts = chunk_counts(run)
    if counts is None:
        return None
    return counts["prompt"] / counts["chunks"]
