"""Engine: the width of ``chip_starved_share``'s bound, on the same
denominator: seconds between a look that found the chip with a model program
to run and the next look, which found none. The chip finished somewhere in
between, so its true idle time with work lies between ``chip_starved_share``
and that plus this. Wide where one phase is long beside a chunk (an emit loop
over many rows). Program counters (the phase clock)."""

from benchmark.layer_metrics._starved import unseen_share


def read(run):
    return unseen_share(run)
