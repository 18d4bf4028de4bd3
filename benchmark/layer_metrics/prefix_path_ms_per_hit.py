"""Prefix cache (serve/prefix_cache.py, kv_pool.py): loop time of the cache's
own path per hit, over the window: (prefix_seed + prefix_store) / hits: the
radix match, the page gather's dispatch, and slicing and storing the new tail.
What a hit costs on the host, beside the prefill it saves on the device.
Program counters (the phase clock)."""

from benchmark.layer_metrics._loop import phase_s


def read(run):
    pair = run["counters"]
    seconds = phase_s(run, pair, "prefix_seed", "prefix_store")
    hits = run["counter_delta"](pair, "serve_prefix_hits_total")
    if seconds is None or hits <= 0:
        return None
    return seconds * 1e3 / hits
