"""Device: share of the traced span in which no operation ran, averaged over
the chips used: 1 - union of device-operation intervals / span."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
