"""Engine: share of slot-seconds in use over the window, from the flight
recorder's admitted -> retired of every request that touched the window."""
from benchmark.layer_metrics._flight import all_times, overlap


def read(run):
    w0, w1 = run["window"]
    times = all_times(run)
    if not times:
        return None
    used = sum(overlap(t["admitted"], t["retire"], w0, w1) for t in times)
    return 100.0 * used / ((w1 - w0) * run["serve"]["max_slots"])
