"""Prefix cache (serve/prefix_cache.py, kv_pool.py), for a model whose slots hold
recurrent state: prompt tokens not prefilled because a snapshot of the state
covered them (``serve_prefix_match_tokens_total{kind="used"}``), as a share of
the prompt tokens the clients sent in the window. A program without the counter
(the parent of the PR that brought it) gives nothing."""


def read(run):
    pair = run["counters"]
    if "serve_prefix_match_tokens_total" not in pair[1]:
        return None
    w0, w1 = run["window"]
    sent = sum(len(r["prompt_ids"] or []) for r in run["records"]
               if r["sent"] is not None and w0 <= r["sent"] < w1)
    if sent <= 0:
        return None
    return 100.0 * run["counter_delta"](pair, "serve_prefix_match_tokens_total", {"kind": "used"}) / sent
