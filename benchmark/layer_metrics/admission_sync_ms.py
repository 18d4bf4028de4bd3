"""Engine: time the loop is blocked on the device per request admitted, over
the window: ``prefill_sync``, the wait for the first token. The chip is busy
throughout: it finishes the decode chunk that the same tick dispatched, then
runs the prefill. So this is device time that is also in ``decode_step_ms``
and the prefill's, kept apart from ``admission_stall_ms``: it moves with the
chunk's length and the prompt's, not with the host path. Program counters (the
phase clock)."""

from benchmark.layer_metrics._loop import phase_s


def read(run):
    pair = run["counters"]
    seconds = phase_s(run, pair, "prefill_sync")
    admitted = run["counter_delta"](pair, "serve_requests_admitted_total")
    if seconds is None or admitted <= 0:
        return None
    return seconds * 1e3 / admitted
