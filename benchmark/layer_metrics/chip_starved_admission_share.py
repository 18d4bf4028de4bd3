"""Engine: the part of ``chip_starved_share`` charged to an admission's phases
(admit, prefix_seed, prefill up to its first enqueue, prefix_store, and what of
prefill_sync follows the chip's finishing): the chip ran out of decode chunks
while the host planned, seeded and enqueued a prompt. Program counters (the
phase clock)."""

from benchmark.layer_metrics._starved import ADMISSION, starved_share


def read(run):
    return starved_share(run, *ADMISSION)
