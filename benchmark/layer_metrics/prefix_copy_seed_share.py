"""Prefix cache (serve/prefix_cache.py, kv_pool.py): hits seeded by the copy path
(one assemble program per tuple of segment shapes) instead of the page gather, as
a share of the window's hits. Above 0 the page pool was full when a segment was
stored, which it is whenever the cache evicts. Program counters."""


def read(run):
    hits = run["counter_delta"](run["counters"], "serve_prefix_hits_total")
    if hits <= 0:
        return None
    return 100.0 * run["counter_delta"](run["counters"], "serve_prefix_assembles_total") / hits
