"""Prefix cache (serve/prefix_cache.py, kv_pool.py): prompt tokens served from
cached prefixes, as a share of the prompt tokens the clients sent in the window."""


def read(run):
    w0, w1 = run["window"]
    sent = sum(len(r["prompt_ids"] or []) for r in run["records"]
               if r["sent"] is not None and w0 <= r["sent"] < w1)
    if sent <= 0:
        return None
    return 100.0 * run["counter_delta"](run["counters"], "serve_prefix_hit_tokens") / sent
