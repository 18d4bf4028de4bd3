"""Engine: recurrent states started from zero a request admitted, over the
window. 1.0 on a model that carries state: more would be a state reset twice,
less a new tenant given a slot's old state. Program counters
``serve_state_resets_total`` and ``serve_requests_admitted_total``; a program
without the first gives nothing."""


def read(run):
    pair = run["counters"]
    if "serve_state_resets_total" not in pair[1]:
        return None
    admitted = run["counter_delta"](pair, "serve_requests_admitted_total")
    if admitted <= 0:
        return None
    return run["counter_delta"](pair, "serve_state_resets_total") / admitted
