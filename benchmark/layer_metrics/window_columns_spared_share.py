"""Cache: of the positions the sliding layers' decode steps had behind them, the
share beyond the window, which a ring neither holds nor reads
(``serve_decode_attn_columns_total{kind="window_spared"}`` over ``window`` +
``window_spared``): how far past the window the contexts reach. A program
without the counter, or a window in which no decode step ran, gives nothing."""


def read(run):
    pair, delta = run["counters"], run["counter_delta"]
    read_ = delta(pair, "serve_decode_attn_columns_total", {"kind": "window"})
    spared = delta(pair, "serve_decode_attn_columns_total", {"kind": "window_spared"})
    if read_ + spared <= 0:
        return None
    return 100.0 * spared / (read_ + spared)
