"""Engine: the part of ``chip_starved_share`` charged to program_build (a
first use of a shape on the loop thread: trace, lowering, compile or load) and
to gc (a garbage collection that struck the loop thread), with the chip's
queue empty. Program counters (the phase clock)."""

from benchmark.layer_metrics._starved import BUILD, starved_share


def read(run):
    return starved_share(run, *BUILD)
