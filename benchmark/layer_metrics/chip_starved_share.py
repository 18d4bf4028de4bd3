"""Engine: of the loop thread's time with a request queued or live, over the
window, the share in which the loop had seen that the chip had no model program
left to run: the sum over every phase but no_work of the starved seconds / (all
phases - no_work). The program's own lower bound on the chip's idle time with
work (its width is ``chip_starved_unseen_share``); the four groups
(``chip_starved_emit_share``, ``..._admission_share``, ``..._upkeep_share``,
``..._build_share``) sum to it. Program counters (the phase clock)."""

from benchmark.layer_metrics._starved import starved_share


def read(run):
    return starved_share(run)
