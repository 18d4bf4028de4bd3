"""Model step, prefill: how much of the cross half (the layers that keep no
state and that only the head reads) the chunk prefills computed, as a share of
what running it at every prompt token would be: the cross half's token-layers
over the self half's, times self layers over cross layers. 100 where every
prompt token runs all the layers; 100 / (mean prompt length) where the cross
half runs at a prompt's last position alone. Program counter
``serve_prefill_layer_tokens_total{half}`` over the window; a program without
it gives nothing."""


def read(run):
    delta, pair = run["counter_delta"], run["counters"]
    own = delta(pair, "serve_prefill_layer_tokens_total", {"half": "self"})
    cross = delta(pair, "serve_prefill_layer_tokens_total", {"half": "cross"})
    layers = int(run["model"]["num_hidden_layers"])
    if own <= 0 or layers < 8:
        return None
    return 100.0 * (cross / own) * (layers // 2 + 2) / (layers // 2 - 2)
