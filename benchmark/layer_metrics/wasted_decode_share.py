"""Engine: decode tokens computed for nobody (a slot retired mid-chunk), as a
share of all decode tokens computed in the window. Program counters."""


def read(run):
    wasted = run["counter_delta"](run["counters"], "serve_wasted_decode_tokens_total")
    emitted = run["counter_delta"](run["counters"], "serve_tokens_emitted_total")
    if wasted + emitted <= 0:
        return None
    return 100.0 * wasted / (wasted + emitted)
