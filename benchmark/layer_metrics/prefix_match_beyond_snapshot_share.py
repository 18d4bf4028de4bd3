"""Prefix cache (serve/prefix_cache.py, kv_pool.py): the price of the stride at
which it keeps snapshots of state. Tokens of K/V the radix walk matched past the
deepest snapshot, or on a path with none (``serve_prefix_match_tokens_total
{kind="beyond_snapshot"}``: prefilled again), as a share of all it matched at the
window's admissions. A program without the counter, or a window that matched
nothing, gives nothing."""


def read(run):
    pair = run["counters"]
    if "serve_prefix_match_tokens_total" not in pair[1]:
        return None
    delta = run["counter_delta"]
    used = delta(pair, "serve_prefix_match_tokens_total", {"kind": "used"})
    beyond = delta(pair, "serve_prefix_match_tokens_total", {"kind": "beyond_snapshot"})
    if used + beyond <= 0:
        return None
    return 100.0 * beyond / (used + beyond)
