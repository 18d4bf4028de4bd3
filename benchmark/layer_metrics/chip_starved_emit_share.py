"""Engine: the part of ``chip_starved_share`` charged to the ``emit`` phase:
the loop routed tokens to their streams with the chip's queue empty, so the
emit loop outlasts the chunk in flight (or there was none: the first token of
an admission). Program counters (the phase clock)."""

from benchmark.layer_metrics._starved import EMIT, starved_share


def read(run):
    return starved_share(run, *EMIT)
