"""Engine: of the loop thread's time with a request queued or live, over the
window, the share in which it ran host code instead of waiting for the chip:
1 - (sync_wait + prefill_sync) / (all phases - no_work). Near 100 the host sets
the pace and the chip waits for it; near 0 the loop does nothing but wait for
the device. Program counters (the phase clock)."""

from benchmark.layer_metrics._loop import BLOCKED_ON_DEVICE, phase_s, with_work_s


def read(run):
    pair = run["counters"]
    times = with_work_s(run, pair)
    if times is None or times[1] <= 0:
        return None
    return 100.0 * (1.0 - phase_s(run, pair, *BLOCKED_ON_DEVICE) / times[1])
