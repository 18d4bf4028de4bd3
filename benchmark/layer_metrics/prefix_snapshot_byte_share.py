"""Prefix cache (serve/prefix_cache.py, kv_pool.py): bytes of state snapshots
(``serve_prefix_state_bytes``) as a share of all the bytes the cache holds on the
device at the window's end (``serve_prefix_cache_bytes``, which counts them).
The stride rule keeps it near or under half. A program without the gauge, or an
empty cache, gives nothing."""


def _gauge(snapshot, name):
    series = (snapshot.get(name) or {}).get("series", [])
    return sum(s.get("value", 0.0) for s in series) if series else None


def read(run):
    at_end = run["counters"][1]
    state, held = _gauge(at_end, "serve_prefix_state_bytes"), _gauge(at_end, "serve_prefix_cache_bytes")
    if state is None or not held:
        return None
    return 100.0 * state / held
