"""Engine: host time of one request's admission, over the window: (admit +
prefix_seed + prefill + prefix_store) per request admitted: planning, seeding
from the cache, enqueueing the prefill, storing the pages. The loop runs host
code in them and dispatches no decode chunk meanwhile, so what of it falls
after the prefill has finished leaves the chip idle. The wait on the device in
between is not in it (``admission_sync_ms``), nor are programs built on first
use inside an admission (``program_build_stall_ms``). Program counters (the
phase clock)."""

from benchmark.layer_metrics._loop import ADMISSION_HOST, phase_s


def read(run):
    pair = run["counters"]
    seconds = phase_s(run, pair, *ADMISSION_HOST)
    admitted = run["counter_delta"](pair, "serve_requests_admitted_total")
    if seconds is None or admitted <= 0:
        return None
    return seconds * 1e3 / admitted
