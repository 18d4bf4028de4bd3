"""Engine (serve/engine.py): admissions whose recurrent state started from a
snapshot the prefix cache kept, as a share of the window's admissions into slots
that hold state (``serve_state_starts_total{from="snapshot" | "zero"}``). A
program without the counter, or a window without admissions, gives nothing."""


def read(run):
    pair = run["counters"]
    if "serve_state_starts_total" not in pair[1]:
        return None
    delta = run["counter_delta"]
    restored = delta(pair, "serve_state_starts_total", {"from": "snapshot"})
    zero = delta(pair, "serve_state_starts_total", {"from": "zero"})
    if restored + zero <= 0:
        return None
    return 100.0 * restored / (restored + zero)
