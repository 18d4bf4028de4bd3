"""Engine: the part of ``chip_starved_share`` charged to the loop's upkeep
(dispatch up to the enqueue's return, a sync_wait entered with the chunk
already there, stats, kv_jobs, other): what every tick costs the chip when it
arrives with the queue empty. Program counters (the phase clock)."""

from benchmark.layer_metrics._starved import UPKEEP, starved_share


def read(run):
    return starved_share(run, *UPKEEP)
